"""EvaByte family: a byte-level decoder with EVA attention (an exact window of
`window_size` positions beside one learned summary row for every
`chunk_size` positions behind it; Zheng et al., arXiv:2302.04542), RoPE,
SwiGLU, an RMSNorm whose learned scale is stored less one, a residual
stream added in float32, and an untied head of `num_pred_heads` x
`vocab_size` columns of which decoding samples the first `vocab_size` (the
next byte's; the others predict the bytes after it).

What is the family's own: the config and its presets, the parameter format
(`param_specs`, `init_params`) and `spec`, which names the parts of
models/decoder.py its block is made of (`decoder.EVA`, `decoder.SWIGLU`).
Everything that runs is the decoder's, bound to `spec` under the names
below.  The config's fields carry the published names' meanings.  Served
only: EVA's whole-sequence form is plain XLA and has no train path yet
(ROADMAP.md).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import decoder


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320         # bytes + specials
    n_layers: int = 32
    d_model: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008             # SwiGLU hidden
    max_seq_len: int = 32768
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    window_size: int = 2048       # positions attended exactly
    chunk_size: int = 16          # positions a summary row stands for
    num_pred_heads: int = 8       # the head is num_pred_heads x vocab wide
    norm_unit_offset: bool = True     # norm(x) * (1 + g)
    fp32_residual: bool = True    # the residual stream is added in float32
    dtype: Any = jnp.bfloat16
    remat: bool = False
    scan_unroll: int = 1
    param_dtype: Any = jnp.bfloat16   # a dtype or its name ("bfloat16")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_experts(self) -> int:
        return 0


CONFIGS = {
    # The block at nano size (tests): windows of 32, chunks of 4.
    "evabyte-nano": EvaByteConfig(
        vocab_size=64, n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=256, window_size=32, chunk_size=4,
        num_pred_heads=2, dtype=jnp.float32, param_dtype=jnp.float32),
}


def param_specs(config: EvaByteConfig) -> dict:
    return {
        "tok_embed": ("vocab", None),
        "blocks": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "kv"),
            "wk": ("layers", "embed", "kv_heads", "kv"),
            "wv": ("layers", "embed", "kv_heads", "kv"),
            "wo": ("layers", "heads", "kv", "embed"),
            "eva_mu": ("layers", "kv_heads", "kv"),
            "eva_phi": ("layers", "kv_heads", "kv"),
            "mlp_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(config: EvaByteConfig, key: jax.Array) -> dict:
    """Parameters in `param_dtype`.  Every matrix is drawn in float32 one
    layer at a time and stored as it is drawn, as `llama.init_params` does,
    at llama's scales; `eva_mu` and `eva_phi` are normal(0, 1) clipped to
    [-1, 1] times head_dim ** -0.5; a norm's stored scale is 0 where the
    unit offset adds the one."""
    c = config
    n, d, h, kh, dh, f = (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
                          c.head_dim, c.d_ff)
    pd = jnp.dtype(c.param_dtype)
    keys = iter(jax.random.split(key, 16))

    def dense(shape, fan_in, lead=1, clip=None):
        scale = 1.0 / np.sqrt(fan_in)
        rows = int(np.prod(shape[:lead]))

        def draw(k):
            x = jax.random.normal(k, shape[lead:], jnp.float32)
            if clip is not None:
                x = jnp.clip(x, -clip, clip)
            return (x * scale).astype(pd)

        return jax.lax.map(draw, jax.random.split(next(keys), rows)).reshape(
            shape)

    norm = partial(jnp.zeros if c.norm_unit_offset else jnp.ones, dtype=pd)
    return {
        "tok_embed": dense((c.vocab_size, d), 2500.0, 0),
        "blocks": {
            "attn_norm": norm((n, d)),
            "wq": dense((n, d, h, dh), d),
            "wk": dense((n, d, kh, dh), d),
            "wv": dense((n, d, kh, dh), d),
            "wo": dense((n, h, dh, d), h * dh * 2 * n),
            "eva_mu": dense((n, kh, dh), dh, clip=1.0),
            "eva_phi": dense((n, kh, dh), dh, clip=1.0),
            "mlp_norm": norm((n, d)),
            "w_gate": dense((n, d, f), d),
            "w_up": dense((n, d, f), d),
            "w_down": dense((n, f, d), f * 2 * n),
        },
        "final_norm": norm((d,)),
        "lm_head": dense((d, c.num_pred_heads * c.vocab_size), d, 0),
    }


def spec(config: EvaByteConfig) -> decoder.Spec:
    c = config
    if c.n_kv_heads != c.n_heads:
        raise NotImplementedError(
            "EVA's summaries are per key head and its queries read their "
            "own: n_kv_heads == n_heads (as published)")
    wide = c.fp32_residual and jnp.dtype(c.dtype) != jnp.float32
    return decoder.Spec(
        norm=partial(decoder.rmsnorm, eps=c.norm_eps,
                     unit_offset=c.norm_unit_offset,
                     dtype=c.dtype if wide else None),
        attn_norm=("attn_norm",), mlp_norm=("mlp_norm",),
        final_norm=("final_norm",),
        attn=decoder.EVA, ffn=decoder.SWIGLU,
        rope_theta=c.rope_theta,
        residual_dtype=jnp.float32 if wide else None,
        logits_dtype=jnp.float32,
        init_params=init_params, param_specs=param_specs)


# The decoder bound to `spec` (signatures and docs: models/decoder.py,
# less its first argument).
_bound = decoder.bind(spec)
lm_head = _bound.lm_head
forward_cached = _bound.forward_cached
compact_cached = _bound.compact_cached
loss_fn = _bound.loss_fn
serving_params = _bound.serving_params
shard_params = _bound.shard_params
num_params = _bound.num_params
make_train_step = _bound.make_train_step


def forward_trunk(params: dict, tokens: jax.Array, config: EvaByteConfig,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed): the
    decoder's, less the auxiliary loss no part of this family has.  Windows
    are counted from position 0: a whole sequence, no suffix."""
    return _bound.forward_trunk(params, tokens, config, mesh,
                                position_offset)[0]


def forward(params: dict, tokens: jax.Array, config: EvaByteConfig,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, num_pred_heads * V], the next
    token's head first (the decoder's, as above)."""
    return _bound.forward(params, tokens, config, mesh, position_offset)[0]
