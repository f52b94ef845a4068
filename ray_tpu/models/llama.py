"""Llama model family: RMSNorm + RoPE + SwiGLU + grouped-query attention.

Design follows models/gpt.py (no reference counterpart — Ray hosts models
rather than shipping them; BASELINE.md's north star names a Llama-2-7B
fine-tune):
  * pure functional params-pytree + jittable forward (pjit/GSPMD-ready);
  * layers stacked on a leading dim, applied with `lax.scan`;
  * every param leaf carries a logical sharding spec (parallel/sharding.py
    rules place DP/FSDP/TP; "kv_heads" shards GQA kv projections);
  * flash attention (Pallas) on one chip, ring attention over a seq axis;
  * rotary embeddings computed on the fly (no position table);
  * `jax.checkpoint` remat for the big configs;
  * the block's options are the architecture's own: `n_experts` > 0 swaps
    the SwiGLU for a dropless top-k expert layer (ops/moe.py), `qk_norm`
    puts an RMSNorm on the projected queries and keys (OLMoE has both),
    `param_dtype` is the dtype the parameters are held in (bf16 where
    float32 would not fit a chip: weights are multiplied as stored).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import moe
from ray_tpu.ops.attention import mesh_flash_attention
from ray_tpu.parallel.sharding import (
    tree_shardings, with_logical_constraint)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layers: int = 32
    d_model: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 32          # < n_heads = grouped-query attention
    d_ff: int = 11008             # SwiGLU hidden
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    scan_unroll: int = 1
    n_experts: int = 0            # 0 = dense SwiGLU; > 0: d_ff is one expert's
    n_experts_per_tok: int = 0
    norm_topk_prob: bool = False  # renormalise the chosen experts' weights
    qk_norm: bool = False         # RMSNorm on the projected q and k
    param_dtype: Any = jnp.float32   # a dtype or its name ("bfloat16")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


CONFIGS = {
    "llama-tiny": LlamaConfig(vocab_size=512, n_layers=2, d_model=64,
                              n_heads=4, n_kv_heads=2, d_ff=128,
                              max_seq_len=128, dtype=jnp.float32),
    # OLMoE's block at nano size: experts, top-k, q/k norm (tests).
    "olmoe-nano": LlamaConfig(vocab_size=512, n_layers=2, d_model=64,
                              n_heads=4, n_kv_heads=4, d_ff=32,
                              max_seq_len=128, dtype=jnp.float32,
                              n_experts=16, n_experts_per_tok=2,
                              qk_norm=True),
    "llama-1b": LlamaConfig(vocab_size=32000, n_layers=22, d_model=2048,
                            n_heads=32, n_kv_heads=4, d_ff=5632,
                            max_seq_len=2048),
    "llama2-7b": LlamaConfig(remat=True),
    "llama3-8b": LlamaConfig(vocab_size=128256, n_layers=32, d_model=4096,
                             n_heads=32, n_kv_heads=8, d_ff=14336,
                             max_seq_len=8192, rope_theta=500000.0,
                             remat=True),
}


def param_specs(config: LlamaConfig) -> dict:
    blocks = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "kv"),
        "wk": ("layers", "embed", "kv_heads", "kv"),
        "wv": ("layers", "embed", "kv_heads", "kv"),
        "wo": ("layers", "heads", "kv", "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if config.n_experts:
        blocks.update({
            "router": ("layers", "embed", "experts"),
            "w_gate": ("layers", "experts", "embed", "expert_mlp"),
            "w_up": ("layers", "experts", "embed", "expert_mlp"),
            "w_down": ("layers", "experts", "expert_mlp", "embed"),
        })
    if config.qk_norm:
        blocks.update({"q_norm": ("layers", "heads", "kv"),
                       "k_norm": ("layers", "kv_heads", "kv")})
    return {
        "tok_embed": ("vocab", None),
        "blocks": blocks,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(config: LlamaConfig, key: jax.Array) -> dict:
    """Parameters in `param_dtype`.  Every matrix is drawn in float32 one
    slice of its leading dims at a time and stored as it is drawn, so no
    float32 copy of a whole array is ever alive beside the parameters
    (OLMoE's experts are 12.9 GB in bf16 on a 16 GB chip)."""
    c = config
    n, d, h, kh, dh, f, e = (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
                             c.head_dim, c.d_ff, c.n_experts)
    pd = jnp.dtype(c.param_dtype)
    keys = iter(jax.random.split(key, 16))

    def dense(key, shape, fan_in, lead=1):
        """normal / sqrt(fan_in), drawn per index of the `lead` leading
        dims (a loop on the device: its float32 temporary is one slice)."""
        scale = 1.0 / np.sqrt(fan_in)
        rows = int(np.prod(shape[:lead]))
        out = jax.lax.map(
            lambda k: (jax.random.normal(k, shape[lead:], jnp.float32)
                       * scale).astype(pd),
            jax.random.split(key, rows))
        return out.reshape(shape)

    ones = partial(jnp.ones, dtype=pd)
    blocks = {
        "attn_norm": ones((n, d)),
        "wq": dense(next(keys), (n, d, h, dh), d),
        "wk": dense(next(keys), (n, d, kh, dh), d),
        "wv": dense(next(keys), (n, d, kh, dh), d),
        "wo": dense(next(keys), (n, h, dh, d), h * dh * 2 * n),
        "mlp_norm": ones((n, d)),
    }
    ffn = (n, e) if e else (n,)
    blocks["w_gate"] = dense(next(keys), ffn + (d, f), d, len(ffn))
    blocks["w_up"] = dense(next(keys), ffn + (d, f), d, len(ffn))
    blocks["w_down"] = dense(next(keys), ffn + (f, d), f * 2 * n, len(ffn))
    if e:
        blocks["router"] = dense(next(keys), (n, d, e), d)
    if c.qk_norm:
        blocks["q_norm"] = ones((n, h, dh))
        blocks["k_norm"] = ones((n, kh, dh))
    return {
        "tok_embed": dense(next(keys), (c.vocab_size, d), 2500.0, 0),
        "blocks": blocks,
        "final_norm": ones((d,)),
        "lm_head": dense(next(keys), (d, c.vocab_size), d, 0),
    }


def shard_params(params: dict, mesh, config: LlamaConfig, rules=None) -> dict:
    return jax.device_put(params,
                          tree_shardings(mesh, param_specs(config), rules))


def num_params(config: LlamaConfig) -> int:
    shapes = jax.eval_shape(partial(init_params, config), jax.random.key(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _rope(x, theta: float, offset=0):
    """Rotary position embedding over [B, L, H, K] (rotate-half pairing:
    the head dim splits into two halves treated as (real, imag)).

    `offset` is the absolute position of x's first token: a scalar shared
    by the batch, or a per-lane [B] array (cached decode — lanes sit at
    different depths)."""
    b, l, h, k = x.shape
    half = k // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    off = jnp.asarray(offset, jnp.float32)
    pos = off[..., None] + jnp.arange(l, dtype=jnp.float32)  # [L] or [B, L]
    ang = pos[..., None] * freqs                      # [L, half] / [B, L, half]
    if ang.ndim == 2:
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _qkv(h, p, config: LlamaConfig):
    """Projected q, k, v [B, L, heads, head_dim] of normed h; with
    `qk_norm`, q and k RMS-normalised over all their heads together
    (OLMoE: the norm spans the whole projected vector, before RoPE)."""
    q = jnp.einsum("bld,dhk->blhk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bld,dhk->blhk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bld,dhk->blhk", h, p["wv"].astype(h.dtype))
    if config.qk_norm:
        def norm(x, scale):
            flat = _rmsnorm(x.reshape(*x.shape[:2], -1), scale.reshape(-1),
                            config.norm_eps)
            return flat.reshape(x.shape)
        q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
    return q, k, v


def _moe_ffn(h, p, config: LlamaConfig, valid=None):
    """The expert layer on normed h [B, L, D]: softmax router, top-k,
    dropless dispatch (ops/moe.py).  Router product, softmax and top-k run
    in float32 (the eighth expert is often chosen by a fourth decimal);
    the chosen probabilities weight the experts as they are unless
    `norm_topk_prob`.  `p` holds the layer's router [D, E] and the experts
    of ALL layers with the index `layer` (the kernel reads them in place).

    Returns (y [B, L, D], load [E]: the assignments each expert took)."""
    c = config
    b, l, d = h.shape
    x = h.reshape(b * l, d)
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, c.n_experts_per_tok)
    if c.norm_topk_prob:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    y, load = moe.expert_ffn(
        x, experts, weights, p["w_gate"], p["w_up"], p["w_down"],
        p["layer"], None if valid is None else valid.reshape(-1))
    return y.reshape(b, l, d), load


def _ffn(h, p, config: LlamaConfig, mesh=None, valid=None):
    """The block's feed-forward on normed h: (y, expert load or None)."""
    if config.n_experts:
        return _moe_ffn(h, p, config, valid)
    gate = jax.nn.silu(jnp.einsum("bld,df->blf", h,
                                  p["w_gate"].astype(h.dtype)))
    up = jnp.einsum("bld,df->blf", h, p["w_up"].astype(h.dtype))
    hidden = with_logical_constraint(gate * up, ("batch", "length", "mlp"),
                                     mesh=mesh)
    return jnp.einsum("blf,fd->bld", hidden,
                      p["w_down"].astype(h.dtype)), None


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _layer_stack(blocks: dict, config: LlamaConfig):
    """(what the layer loop scans over, what it closes over): the expert
    arrays stay whole outside the scan, each layer takes its index."""
    layers = jnp.arange(config.n_layers, dtype=jnp.int32)
    if not config.n_experts:
        return (blocks, layers), {}
    scanned = {k: v for k, v in blocks.items() if k not in _EXPERT_LEAVES}
    return (scanned, layers), {k: blocks[k] for k in _EXPERT_LEAVES}


def _block(x, p, config: LlamaConfig, mesh, position_offset=0):
    c = config
    h = _rmsnorm(x, p["attn_norm"], c.norm_eps)
    q, k, v = _qkv(h, p, c)
    q = _rope(q, c.rope_theta, position_offset)
    k = _rope(k, c.rope_theta, position_offset)
    if c.q_per_kv > 1:
        # GQA: each kv head serves q_per_kv query heads.  Materializing
        # the repeat keeps the attention kernels head-uniform; XLA fuses
        # the broadcast into the kernel operand load.
        k = jnp.repeat(k, c.q_per_kv, axis=2)
        v = jnp.repeat(v, c.q_per_kv, axis=2)
    q = with_logical_constraint(q, ("batch", "length", "heads", "kv"),
                                mesh=mesh)
    attn = mesh_flash_attention(q, k, v, mesh=mesh, causal=True)
    x = x + jnp.einsum("blhk,hkd->bld", attn, p["wo"].astype(h.dtype))

    h = _rmsnorm(x, p["mlp_norm"], c.norm_eps)
    x = x + _ffn(h, p, c, mesh)[0]
    return with_logical_constraint(x, ("batch", "length", "act_embed"),
                                   mesh=mesh)


def forward_trunk(params: dict, tokens: jax.Array, config: LlamaConfig,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed).

    position_offset rotates RoPE as if tokens started at that absolute
    position (scalar or per-lane [B]) — single-token decode steps depend
    on this; without it every suffix call re-rotates from position 0."""
    c = config
    x = params["tok_embed"][tokens].astype(c.dtype)
    x = with_logical_constraint(x, ("batch", "length", "act_embed"),
                                mesh=mesh)
    block = partial(_block, config=c, mesh=mesh,
                    position_offset=position_offset)
    if c.remat:
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.nothing_saveable)

    scanned, experts = _layer_stack(params["blocks"], c)

    def body(x, layer):
        p, i = layer
        return block(x, {**p, **experts, "layer": i}), None

    x, _ = jax.lax.scan(body, x, scanned,
                        unroll=min(c.scan_unroll, c.n_layers))
    return _rmsnorm(x, params["final_norm"], c.norm_eps)


def forward(params: dict, tokens: jax.Array, config: LlamaConfig,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V]."""
    x = forward_trunk(params, tokens, config, mesh, position_offset)
    logits = jnp.einsum("bld,dv->blv", x,
                        params["lm_head"].astype(config.dtype))
    return with_logical_constraint(logits, ("batch", "length", "vocab"),
                                   mesh=mesh)


def lm_head(params: dict, x: jax.Array, config: LlamaConfig) -> jax.Array:
    """Project hidden states [..., D] to vocab logits [..., V]."""
    return x @ params["lm_head"].astype(config.dtype)


# The leaves `forward_cached` and `lm_head` cast to the activation dtype
# where they use them; norm scales and the router are used in float32.
_SERVED_LEAVES = ("tok_embed", "lm_head",
                  "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def serving_params(params: dict, config: LlamaConfig) -> dict:
    """`params` as `forward_cached` and `lm_head` multiply them: the
    leaves they cast at their use held in `config.dtype`, the others as
    given (models/_functional.py::serving_params).  Parameters already
    stored so (`param_dtype`) come back as the same arrays; experts are
    multiplied as stored (`_moe_ffn` casts nothing), so they stay too."""
    from ray_tpu.models._functional import serving_params as _shared
    return _shared(params, config.dtype, tuple(
        k for k in _SERVED_LEAVES
        if not (config.n_experts and k in _EXPERT_LEAVES)))


def _block_cached(x, k_pool, v_pool, p, config: LlamaConfig,
                  block_tables, positions, valid, ctx_lens):
    """One Llama block over a paged KV cache, written and read in the
    whole pools at `p["layer"]`.  K/V are cached with kv_heads (GQA
    un-repeated — the whole point of the grouped cache); the paged
    attention path expands groups itself.  Returns (x, pools, the expert
    layer's load or None)."""
    from ray_tpu.ops.attention import paged_attention, paged_kv_update

    c = config
    layer = p["layer"]
    h = _rmsnorm(x, p["attn_norm"], c.norm_eps)
    q, k, v = _qkv(h, p, c)
    # Per-token rotation at each token's own absolute position: offset =
    # positions[:, 0] with L-consecutive slices means positions must be
    # contiguous per lane, which prefill/decode slices always are.
    q = _rope(q, c.rope_theta, positions[:, 0])
    k = _rope(k, c.rope_theta, positions[:, 0])
    k_pool, v_pool = paged_kv_update(k_pool, v_pool, k, v, block_tables,
                                     positions, valid, layer)
    attn = paged_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                           positions, layer, kv_heads=c.n_kv_heads)
    x = x + jnp.einsum("blhk,hkd->bld", attn, p["wo"].astype(h.dtype))

    h = _rmsnorm(x, p["mlp_norm"], c.norm_eps)
    y, load = _ffn(h, p, c, valid=valid)
    return x + y, k_pool, v_pool, load


def forward_cached(params: dict, tokens: jax.Array, positions: jax.Array,
                   valid: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                   block_tables: jax.Array, ctx_lens: jax.Array,
                   config: LlamaConfig, moe_load=None):
    """Cached (incremental) trunk — same contract as gpt.forward_cached:
    tokens [B, T] at per-lane absolute `positions`, paged pools
    [n_layers, NB, BS, W] (rows of n_kv_heads x head_dim) carried
    whole through the layer loop, returns (x [B, T, D], k_pool, v_pool).

    With `moe_load` (int32 [n_experts + 2], an expert configuration's
    running counters: assignments per expert, then experts hit summed
    over (layer, step) pairs, then the count of those pairs) it is carried
    through the layer loop too and returned fourth: the load stays on the
    device until somebody asks."""
    c = config
    x = params["tok_embed"][tokens].astype(c.dtype)
    scanned, experts = _layer_stack(params["blocks"], c)
    count = moe_load is not None

    def body(carry, layer):
        x, k_pool, v_pool, seen = carry
        p, i = layer
        x, k_pool, v_pool, load = _block_cached(
            x, k_pool, v_pool, {**p, **experts, "layer": i}, c,
            block_tables, positions, valid, ctx_lens)
        if count:
            seen = seen + jnp.concatenate([
                load, jnp.sum(load > 0, dtype=jnp.int32)[None],
                jnp.ones((1,), jnp.int32)])
        return (x, k_pool, v_pool, seen), None

    (x, k_pool, v_pool, moe_load), _ = jax.lax.scan(
        body, (x, k_pool, v_pool, moe_load if count else 0), scanned,
        unroll=min(c.scan_unroll, c.n_layers))
    x = _rmsnorm(x, params["final_norm"], c.norm_eps)
    return (x, k_pool, v_pool, moe_load) if count else (x, k_pool, v_pool)


def loss_fn(params: dict, batch: dict, config: LlamaConfig, mesh=None):
    """Next-token cross-entropy; same shift/mask scheme as gpt.loss_fn
    (full-length forward, rolled targets, last position masked).  Single
    chip rides the fused chunked cross-entropy; a mesh rides the
    shard_map variant (vocab-sharded logsumexp), with the naive path as
    the non-divisible-shape fallback."""
    from ray_tpu.ops.cross_entropy import (fused_cross_entropy,
                                           fused_cross_entropy_spmd,
                                           spmd_ce_applicable)

    c = config
    if c.n_experts:
        raise NotImplementedError(
            "training an expert configuration is not supported yet: the "
            "grouped matmul (ops/moe.py) has no backward pass and the "
            "router's auxiliary losses are not computed (ROADMAP.md R1)")
    tokens = batch["tokens"]
    targets = jnp.roll(tokens, -1, axis=1)
    valid = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    mask = batch.get("loss_mask")
    if mask is not None:
        valid = valid * mask

    multichip = mesh is not None and any(
        s > 1 for s in mesh.shape.values())
    if not multichip:
        x = forward_trunk(params, tokens, c, mesh)
        b, l, d = x.shape
        return fused_cross_entropy(
            x.reshape(b * l, d), params["lm_head"].astype(c.dtype),
            targets.reshape(-1), valid.reshape(-1))

    if spmd_ce_applicable(mesh, c.vocab_size, *tokens.shape):
        x = forward_trunk(params, tokens, c, mesh)
        return fused_cross_entropy_spmd(
            x, params["lm_head"].astype(c.dtype), targets, valid, mesh)

    logits = forward(params, tokens, c, mesh)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)


def make_train_step(config: LlamaConfig, optimizer, mesh=None):
    """(init_state, train_step) — the shared functional-LM contract
    (models/_functional.py)."""
    from ray_tpu.models._functional import make_train_step as _shared
    return _shared(config, optimizer, mesh, init_params=init_params,
                   loss_fn=loss_fn, param_specs=param_specs)
