"""Flagship model: decoder-only transformer (GPT family), TPU-first.

Design (no reference counterpart — Ray hosts models, it doesn't ship them;
this repo's north star BASELINE.md requires a GPT-2-125M fine-tune and a 7B
config):
  * pure functional: params are a pytree, forward is a jittable function —
    plays directly with pjit/GSPMD and donation;
  * layers are STACKED on a leading dim and applied with `lax.scan` — one
    compiled block regardless of depth (fast compiles, small HLO);
  * every param leaf has a logical sharding spec (parallel.sharding rules
    decide DP/FSDP/TP placement);
  * attention = flash (Pallas) on one chip and per shard (shard_map over
    batch and heads) under a mesh, ring attention when the mesh has a seq
    axis > 1;
  * optional Switch-style MoE MLP for expert parallelism;
  * `jax.checkpoint` (remat) on the block when configured — trades FLOPs for
    HBM, the standard TPU memory lever.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import mesh_flash_attention
from ray_tpu.parallel.sharding import (
    logical_to_spec, named_sharding, tree_shardings, with_logical_constraint)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # GPT-2 vocab padded to a multiple of 128
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 1024
    # Activation dtype.  Params are kept fp32 (training updates them);
    # `serving_params` makes the form the engine's step multiplies.
    dtype: Any = jnp.bfloat16
    n_experts: int = 0               # 0 = dense MLP; >0 = Switch MoE
    capacity_factor: float = 1.25
    remat: bool = False
    tie_embeddings: bool = True
    # lax.scan unroll factor over layers.  Unrolling lets XLA fuse and
    # schedule across layer boundaries (measured +33% on one chip, PERF.md)
    # at the cost of compile time; keep 1 for very deep/remat configs.
    scan_unroll: int = 1

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# Preset configs (BASELINE.md targets).
CONFIGS = {
    "nano": GPTConfig(vocab_size=512, n_layers=2, d_model=64, n_heads=4,
                      d_ff=128, max_seq_len=128, dtype=jnp.float32),
    "nano-moe": GPTConfig(vocab_size=512, n_layers=2, d_model=64, n_heads=4,
                          d_ff=128, max_seq_len=128, n_experts=4,
                          dtype=jnp.float32),
    "gpt2-small": GPTConfig(scan_unroll=12),       # 124M
    "gpt2-medium": GPTConfig(n_layers=24, d_model=1024, n_heads=16,
                             d_ff=4096, scan_unroll=8),
    "gpt2-xl": GPTConfig(n_layers=48, d_model=1600, n_heads=25, d_ff=6400,
                         scan_unroll=4),
    "7b": GPTConfig(vocab_size=32000, n_layers=32, d_model=4096, n_heads=32,
                    d_ff=11008, max_seq_len=4096, remat=True),
}


def param_specs(config: GPTConfig) -> dict:
    """Logical sharding spec tree, congruent with init_params output."""
    blocks = {
        "ln1_scale": ("layers", "embed"),
        "ln1_bias": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "kv"),
        "wk": ("layers", "embed", "heads", "kv"),
        "wv": ("layers", "embed", "heads", "kv"),
        "wo": ("layers", "heads", "kv", "embed"),
        "ln2_scale": ("layers", "embed"),
        "ln2_bias": ("layers", "embed"),
    }
    if config.n_experts:
        blocks.update({
            "router": ("layers", "embed", "experts"),
            "w_up": ("layers", "experts", "embed", "expert_mlp"),
            "w_down": ("layers", "experts", "expert_mlp", "embed"),
        })
    else:
        blocks.update({
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        })
    specs = {
        # Table embed dims stay unsharded (vocab carries tensor+fsdp, see
        # parallel/sharding.py DEFAULT_RULES["vocab"]); pos_embed is tiny
        # and replicated.
        "tok_embed": ("vocab", None),
        "pos_embed": (None, None),
        "blocks": blocks,
        "final_ln_scale": ("embed",),
        "final_ln_bias": ("embed",),
    }
    if not config.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
    return specs


def init_params(config: GPTConfig, key: jax.Array) -> dict:
    c = config
    n, d, h, dh, f = c.n_layers, c.d_model, c.n_heads, c.head_dim, c.d_ff
    keys = iter(jax.random.split(key, 16))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in))

    blocks = {
        "ln1_scale": jnp.ones((n, d)),
        "ln1_bias": jnp.zeros((n, d)),
        "wq": dense(next(keys), (n, d, h, dh), d),
        "wk": dense(next(keys), (n, d, h, dh), d),
        "wv": dense(next(keys), (n, d, h, dh), d),
        # Residual-branch outputs scaled per GPT-2 (1/sqrt(2*n_layers)).
        "wo": dense(next(keys), (n, h, dh, d), h * dh) / np.sqrt(2 * n),
        "ln2_scale": jnp.ones((n, d)),
        "ln2_bias": jnp.zeros((n, d)),
    }
    if c.n_experts:
        e = c.n_experts
        blocks["router"] = dense(next(keys), (n, d, e), d)
        blocks["w_up"] = dense(next(keys), (n, e, d, f), d)
        blocks["w_down"] = dense(next(keys), (n, e, f, d), f) / np.sqrt(2 * n)
    else:
        blocks["w_up"] = dense(next(keys), (n, d, f), d)
        blocks["w_down"] = dense(next(keys), (n, f, d), f) / np.sqrt(2 * n)

    params = {
        "tok_embed": jax.random.normal(next(keys), (c.vocab_size, d)) * 0.02,
        "pos_embed": jax.random.normal(next(keys), (c.max_seq_len, d)) * 0.01,
        "blocks": blocks,
        "final_ln_scale": jnp.ones((d,)),
        "final_ln_bias": jnp.zeros((d,)),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(next(keys), (d, c.vocab_size), d)
    return params


def shard_params(params: dict, mesh, config: GPTConfig, rules=None) -> dict:
    return jax.device_put(params,
                          tree_shardings(mesh, param_specs(config), rules))


def num_params(config: GPTConfig) -> int:
    shapes = jax.eval_shape(partial(init_params, config), jax.random.key(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def _layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _moe_mlp(x, router, w_up, w_down, config: GPTConfig, mesh):
    """Switch-style top-1 MoE with dense dispatch (einsum one-hot masks —
    static shapes, XLA-friendly; no sort/scatter)."""
    b, l, d = x.shape
    e = config.n_experts
    t = b * l
    cap = int(math.ceil(t / e * config.capacity_factor))
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ router.astype(jnp.float32))  # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, -1)                      # [T]
    expert = jnp.argmax(probs, -1)                 # [T]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)       # [T,E]
    # Position of each token within its expert's queue.
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0             # [T,E]
    keep = (pos < cap) & (onehot > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap) * keep[..., None]
    dispatch = pos_oh                                            # [T,E,C]

    ex_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)
    ex_in = with_logical_constraint(ex_in, ("experts", None, "embed"),
                                    mesh=mesh)
    hidden = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", ex_in,
                                    w_up.astype(x.dtype)))
    ex_out = jnp.einsum("ecf,efd->ecd", hidden, w_down.astype(x.dtype))
    combine = dispatch * gate[:, None, None]
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ex_out)

    # Load-balancing aux loss (Switch eq. 4): mean prob * mean assignment.
    density = jnp.mean(onehot, 0)
    density_prob = jnp.mean(probs, 0)
    aux = e * jnp.sum(density * density_prob)
    return out.reshape(b, l, d), aux


def _block(x, p, config: GPTConfig, mesh):
    c = config
    h = _layernorm(x, p["ln1_scale"], p["ln1_bias"])
    q = jnp.einsum("bld,dhk->blhk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bld,dhk->blhk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bld,dhk->blhk", h, p["wv"].astype(h.dtype))
    q = with_logical_constraint(q, ("batch", "length", "heads", "kv"),
                                mesh=mesh)
    attn = mesh_flash_attention(q, k, v, mesh=mesh, causal=True)
    attn_out = jnp.einsum("blhk,hkd->bld", attn, p["wo"].astype(h.dtype))
    x = x + attn_out

    h = _layernorm(x, p["ln2_scale"], p["ln2_bias"])
    if c.n_experts:
        mlp_out, aux = _moe_mlp(h, p["router"], p["w_up"], p["w_down"], c,
                                mesh)
    else:
        hidden = jax.nn.gelu(
            jnp.einsum("bld,df->blf", h, p["w_up"].astype(h.dtype)))
        hidden = with_logical_constraint(hidden, ("batch", "length", "mlp"),
                                         mesh=mesh)
        mlp_out = jnp.einsum("blf,fd->bld", hidden,
                             p["w_down"].astype(h.dtype))
        aux = jnp.zeros((), jnp.float32)
    x = x + mlp_out
    x = with_logical_constraint(x, ("batch", "length", "act_embed"), mesh=mesh)
    return x, aux


def forward(params: dict, tokens: jax.Array, config: GPTConfig,
            mesh=None, position_offset: int = 0) -> tuple[jax.Array,
                                                          jax.Array]:
    """tokens [B, L] int32 -> (logits [B, L, V], moe_aux_loss scalar)."""
    c = config
    x, aux = forward_trunk(params, tokens, c, mesh, position_offset)
    logits = lm_head(params, x, c)
    logits = with_logical_constraint(logits, ("batch", "length", "vocab"),
                                     mesh=mesh)
    return logits, aux


def lm_head(params: dict, x: jax.Array, config: GPTConfig) -> jax.Array:
    """Project hidden states [..., D] to vocab logits [..., V]."""
    head = (params["tok_embed"].T if config.tie_embeddings
            else params["lm_head"]).astype(config.dtype)
    return x @ head


def forward_trunk(params: dict, tokens: jax.Array, config: GPTConfig,
                  mesh=None, position_offset: int = 0) -> tuple[jax.Array,
                                                                jax.Array]:
    """Transformer stack up to (excluding) the lm head.
    tokens [B, L] -> (x [B, L, D], moe_aux_loss).

    position_offset shifts the learned position table: a suffix call at
    absolute position p must read pos_embed[p:p+l], not pos_embed[:l]
    (the cached decode path depends on this)."""
    c = config
    b, l = tokens.shape
    x = params["tok_embed"][tokens].astype(c.dtype)
    pos = jax.lax.dynamic_slice_in_dim(params["pos_embed"],
                                       position_offset, l)
    x = x + pos[None].astype(c.dtype)
    x = with_logical_constraint(x, ("batch", "length", "act_embed"), mesh=mesh)

    block = partial(_block, config=c, mesh=mesh)
    if c.remat:
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.nothing_saveable)

    def body(x, layer_params):
        x, aux = block(x, layer_params)
        return x, aux

    x, auxes = jax.lax.scan(body, x, params["blocks"],
                            unroll=min(c.scan_unroll, c.n_layers))
    x = _layernorm(x, params["final_ln_scale"], params["final_ln_bias"])
    return x, jnp.sum(auxes)


# The leaves `forward_cached` and `lm_head` cast to the activation dtype
# where they use them; the layer-norm leaves are used in float32.
_SERVED_LEAVES = ("tok_embed", "pos_embed", "lm_head",
                  "wq", "wk", "wv", "wo", "w_up", "w_down")


def _w_down_served(w):
    """[layers, d_ff, d_model] -> `w_down_t` [layers, d_model, d_ff] where
    d_model is no multiple of 128: a bf16 [6400, 1600] array lies on a TPU
    with d_ff minor (the layout that pads nothing), and the T=1 step
    copied all 48 layers of it every time to multiply it the other way
    round.  Rows of a multiple of 128 lie as they are written."""
    if w.shape[-1] % 128 == 0:
        return {"w_down": w}
    return {"w_down_t": jnp.swapaxes(w, -1, -2)}


def _rows_served(name, keep):
    """A lookup table [n, d_model] -> `<name>_rows`, its rows padded to a
    multiple of 128 columns: with rows of 1600 a table lies with n minor,
    which the tied head reads as it is (so `keep` the table for it) and
    a lookup cannot, so the step copied the whole table for 16 rows."""
    def served(table):
        pad = -table.shape[1] % 128
        if not pad:
            return {f"{name}_embed": table}
        rows = {f"{name}_rows": jnp.pad(table, ((0, 0), (0, pad)))}
        return {f"{name}_embed": table, **rows} if keep else rows
    return served


_SERVED_FORMS = {"w_down": _w_down_served,
                 "tok_embed": _rows_served("tok", keep=True),
                 "pos_embed": _rows_served("pos", keep=False)}


def serving_params(params: dict, config: GPTConfig) -> dict:
    """`params` as `forward_cached` and `lm_head` multiply them: the
    leaves they cast at their use held in `config.dtype`, the others as
    given (models/_functional.py::serving_params), and of those re-made
    `w_down` and the two tables in the forms their uses read in place.
    The engine makes this once per set of weights and its step takes it;
    the raw tree gives the same tokens, paying casts and copies in every
    call."""
    from ray_tpu.models._functional import serving_params as _shared
    return _shared(params, config.dtype, _SERVED_LEAVES, _SERVED_FORMS)


def _embed(params, name, index, config: GPTConfig):
    """Rows `index` of the `name` table: from the served rows where the
    tree has them (serving_params), else cast as they are gathered."""
    if f"{name}_rows" in params:
        return params[f"{name}_rows"][index][..., :config.d_model]
    return params[f"{name}_embed"][index].astype(config.dtype)


def _block_cached(x, k_pool, v_pool, layer, p, config: GPTConfig,
                  block_tables, positions, valid, ctx_lens):
    """One transformer block over a paged KV cache: new K/V rows are
    written into the whole pools at `layer`, then attention runs over the
    block table in the same buffers (ops/attention.py paged path).
    x [B, T, D]; positions [B, T] absolute; ctx_lens [B] = context length
    including this slice."""
    from ray_tpu.ops.attention import paged_attention, paged_kv_update

    h = _layernorm(x, p["ln1_scale"], p["ln1_bias"])
    q = jnp.einsum("bld,dhk->blhk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bld,dhk->blhk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bld,dhk->blhk", h, p["wv"].astype(h.dtype))
    k_pool, v_pool = paged_kv_update(k_pool, v_pool, k, v, block_tables,
                                     positions, valid, layer)
    attn = paged_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                           positions, layer)
    x = x + jnp.einsum("blhk,hkd->bld", attn, p["wo"].astype(h.dtype))

    h = _layernorm(x, p["ln2_scale"], p["ln2_bias"])
    hidden = jax.nn.gelu(
        jnp.einsum("bld,df->blf", h, p["w_up"].astype(h.dtype)))
    if "w_down_t" in p:         # the served form, see serving_params
        x = x + jnp.einsum("blf,df->bld", hidden, p["w_down_t"])
    else:
        x = x + jnp.einsum("blf,fd->bld", hidden,
                           p["w_down"].astype(h.dtype))
    return x, k_pool, v_pool


def forward_cached(params: dict, tokens: jax.Array, positions: jax.Array,
                   valid: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                   block_tables: jax.Array, ctx_lens: jax.Array,
                   config: GPTConfig):
    """Cached (incremental) trunk for autoregressive decode/prefill.

    tokens [B, T] is a SLICE of each lane's sequence at absolute
    `positions` [B, T] (per-lane offsets — lanes decode at different
    depths); K/V for the slice are written into the paged pools
    [n_layers, NB, BS, W] (inference/kv_cache.py's stored layout)
    and attention covers each lane's whole block table.  The pools ride
    the layer loop as its carry, whole: a layer writes its rows and reads
    its blocks by index, nothing slices a layer out or stacks it back.
    `valid` masks padding lanes/overhang (their cache writes are
    dropped).  Returns (x [B, T, D], k_pool, v_pool) — the lm head is
    applied by the caller on the positions it needs, so a prefill chunk
    never materializes [B, T, V].

    Dense-MLP configs only (n_experts == 0): MoE decode would need
    per-token expert dispatch, which the serving engine doesn't support.
    """
    c = config
    if c.n_experts:
        raise NotImplementedError("cached decode supports dense MLP only")
    pos = jnp.clip(positions, 0, c.max_seq_len - 1)
    x = _embed(params, "tok", tokens, c) + _embed(params, "pos", pos, c)

    def body(carry, layer):
        p, i = layer
        return _block_cached(*carry, i, p, c, block_tables, positions,
                             valid, ctx_lens), None

    (x, k_pool, v_pool), _ = jax.lax.scan(
        body, (x, k_pool, v_pool),
        (params["blocks"], jnp.arange(c.n_layers, dtype=jnp.int32)),
        unroll=min(c.scan_unroll, c.n_layers))
    x = _layernorm(x, params["final_ln_scale"], params["final_ln_bias"])
    return x, k_pool, v_pool


def loss_fn(params: dict, batch: dict, config: GPTConfig, mesh=None):
    """batch = {"tokens": [B, L]} — next-token cross-entropy.

    Runs the model on the FULL length L and shifts targets instead of
    slicing inputs to L-1: the sequence dim must stay divisible by the
    mesh's seq axis for ring attention, and L-1 never is.

    Single chip uses the fused chunked cross-entropy (never materializes
    [B, L, V] — see ops/cross_entropy.py and PERF.md; the naive fp32
    log_softmax was ~75% of the train step).  Under a mesh the shard_map
    variant keeps the same property per-chip with vocab-sharded
    logsumexp; the naive path remains only as the fallback for
    non-divisible shapes.
    """
    from ray_tpu.ops.cross_entropy import (fused_cross_entropy,
                                           fused_cross_entropy_spmd,
                                           spmd_ce_applicable)

    tokens = batch["tokens"]
    c = config
    targets = jnp.roll(tokens, -1, axis=1)
    # Last position predicts the rolled-around token 0 — always masked.
    valid = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    mask = batch.get("loss_mask")
    if mask is not None:
        valid = valid * mask

    multichip = mesh is not None and any(
        s > 1 for s in mesh.shape.values())
    if not multichip:
        x, aux = forward_trunk(params, tokens, c, mesh)
        b, l, d = x.shape
        head = (params["tok_embed"].T if c.tie_embeddings
                else params["lm_head"]).astype(c.dtype)
        loss = fused_cross_entropy(x.reshape(b * l, d), head,
                                   targets.reshape(-1), valid.reshape(-1))
        return loss + 0.01 * aux

    if spmd_ce_applicable(mesh, c.vocab_size, *tokens.shape):
        x, aux = forward_trunk(params, tokens, c, mesh)
        head = (params["tok_embed"].T if c.tie_embeddings
                else params["lm_head"]).astype(c.dtype)
        loss = fused_cross_entropy_spmd(x, head, targets, valid, mesh)
        return loss + 0.01 * aux

    logits, aux = forward(params, tokens, c, mesh)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
    return loss + 0.01 * aux


def make_train_step(config: GPTConfig, optimizer, mesh=None):
    """Returns (init_state, train_step) — the shared functional-LM
    contract (models/_functional.py): jittable train_step; under a mesh,
    params AND optimizer state are sharded (ZeRO-3: Adam moments inherit
    each param's sharding via GSPMD propagation through
    jit(optimizer.init)) and XLA inserts the collectives."""
    from ray_tpu.models._functional import make_train_step as _shared
    return _shared(config, optimizer, mesh, init_params=init_params,
                   loss_fn=loss_fn, param_specs=param_specs)
