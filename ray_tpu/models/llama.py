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
  * `jax.checkpoint` remat for the big configs.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

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
    return {
        "tok_embed": ("vocab", None),
        "blocks": blocks,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(config: LlamaConfig, key: jax.Array) -> dict:
    c = config
    n, d, h, kh, dh, f = (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
                          c.head_dim, c.d_ff)
    keys = iter(jax.random.split(key, 16))

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    blocks = {
        "attn_norm": jnp.ones((n, d)),
        "wq": dense(next(keys), (n, d, h, dh), d),
        "wk": dense(next(keys), (n, d, kh, dh), d),
        "wv": dense(next(keys), (n, d, kh, dh), d),
        "wo": dense(next(keys), (n, h, dh, d), h * dh) / np.sqrt(2 * n),
        "mlp_norm": jnp.ones((n, d)),
        "w_gate": dense(next(keys), (n, d, f), d),
        "w_up": dense(next(keys), (n, d, f), d),
        "w_down": dense(next(keys), (n, f, d), f) / np.sqrt(2 * n),
    }
    return {
        "tok_embed": jax.random.normal(next(keys), (c.vocab_size, d)) * 0.02,
        "blocks": blocks,
        "final_norm": jnp.ones((d,)),
        "lm_head": dense(next(keys), (d, c.vocab_size), d),
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


def _block(x, p, config: LlamaConfig, mesh, position_offset=0):
    c = config
    h = _rmsnorm(x, p["attn_norm"], c.norm_eps)
    q = jnp.einsum("bld,dhk->blhk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bld,dhk->blhk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bld,dhk->blhk", h, p["wv"].astype(h.dtype))
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
    gate = jax.nn.silu(jnp.einsum("bld,df->blf", h,
                                  p["w_gate"].astype(h.dtype)))
    up = jnp.einsum("bld,df->blf", h, p["w_up"].astype(h.dtype))
    hidden = with_logical_constraint(gate * up, ("batch", "length", "mlp"),
                                     mesh=mesh)
    x = x + jnp.einsum("blf,fd->bld", hidden, p["w_down"].astype(h.dtype))
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

    def body(x, layer_params):
        return block(x, layer_params), None

    x, _ = jax.lax.scan(body, x, params["blocks"],
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


def _block_cached(x, k_pool, v_pool, layer, p, config: LlamaConfig,
                  block_tables, positions, valid, ctx_lens):
    """One Llama block over a paged KV cache, written and read in the
    whole pools at `layer`.  K/V are cached with kv_heads (GQA
    un-repeated — the whole point of the grouped cache); the paged
    attention path expands groups itself."""
    from ray_tpu.ops.attention import paged_attention, paged_kv_update

    c = config
    h = _rmsnorm(x, p["attn_norm"], c.norm_eps)
    q = jnp.einsum("bld,dhk->blhk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bld,dhk->blhk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bld,dhk->blhk", h, p["wv"].astype(h.dtype))
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
    gate = jax.nn.silu(jnp.einsum("bld,df->blf", h,
                                  p["w_gate"].astype(h.dtype)))
    up = jnp.einsum("bld,df->blf", h, p["w_up"].astype(h.dtype))
    x = x + jnp.einsum("blf,fd->bld", gate * up,
                       p["w_down"].astype(h.dtype))
    return x, k_pool, v_pool


def forward_cached(params: dict, tokens: jax.Array, positions: jax.Array,
                   valid: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                   block_tables: jax.Array, ctx_lens: jax.Array,
                   config: LlamaConfig):
    """Cached (incremental) trunk — same contract as gpt.forward_cached:
    tokens [B, T] at per-lane absolute `positions`, paged pools
    [n_layers, NB, BS, W] (rows of n_kv_heads x head_dim) carried
    whole through the layer loop, returns (x [B, T, D], k_pool, v_pool)."""
    c = config
    x = params["tok_embed"][tokens].astype(c.dtype)

    def body(carry, layer):
        p, i = layer
        return _block_cached(*carry, i, p, c, block_tables, positions,
                             valid, ctx_lens), None

    (x, k_pool, v_pool), _ = jax.lax.scan(
        body, (x, k_pool, v_pool),
        (params["blocks"], jnp.arange(c.n_layers, dtype=jnp.int32)),
        unroll=min(c.scan_unroll, c.n_layers))
    x = _rmsnorm(x, params["final_norm"], c.norm_eps)
    return x, k_pool, v_pool


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
