"""Llama family: RMSNorm + RoPE + SwiGLU + grouped-query attention, untied
head.

What is the family's own: the config and its presets, the parameter format
(`param_specs`, `init_params`) and `spec`, which names the parts of
models/decoder.py its block is made of.  Everything that runs is the
decoder's, bound to `spec` under the names below.  The config's options are
the architecture's own: `n_experts` > 0 swaps the SwiGLU for the dropless
top-k expert layer (`decoder.moe_ffn` over ops/moe.py), `qk_norm` puts an
RMSNorm on the projected queries and keys (OLMoE has both), `param_dtype`
is the dtype the parameters are held in (bf16 where float32 would not fit
a chip: weights are multiplied as stored).
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
    scoring_func: str = "softmax"     # the router's scores (decoder.moe_ffn)
    routed_scale: float = 1.0         # on the chosen experts' weights
    experts_offset: int = 0       # a share's first expert (its leaves hold
                                  # fewer than n_experts: decoder.moe_ffn)
    qk_norm: bool = False         # RMSNorm on the projected q and k
    param_dtype: Any = jnp.float32   # a dtype or its name ("bfloat16")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


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


def spec(config: LlamaConfig) -> decoder.Spec:
    return decoder.Spec(
        norm=partial(decoder.rmsnorm, eps=config.norm_eps),
        attn_norm=("attn_norm",), mlp_norm=("mlp_norm",),
        final_norm=("final_norm",),
        ffn=decoder.EXPERTS if config.n_experts else decoder.SWIGLU,
        rope_theta=config.rope_theta,
        qk_norm=config.norm_eps if config.qk_norm else None,
        init_params=init_params, param_specs=param_specs)


# The decoder bound to `spec` (signatures and docs: models/decoder.py,
# less its first argument).
_bound = decoder.bind(spec)
lm_head = _bound.lm_head
forward_cached = _bound.forward_cached
loss_fn = _bound.loss_fn
loss_and_metrics = _bound.loss_and_metrics
serving_params = _bound.serving_params
shard_params = _bound.shard_params
num_params = _bound.num_params
make_train_step = _bound.make_train_step


def forward_trunk(params: dict, tokens: jax.Array, config: LlamaConfig,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed): the
    decoder's, less the auxiliary loss no part of this family has."""
    return _bound.forward_trunk(params, tokens, config, mesh,
                                position_offset)[0]


def forward(params: dict, tokens: jax.Array, config: LlamaConfig,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V] (the decoder's, as above)."""
    return _bound.forward(params, tokens, config, mesh, position_offset)[0]


# The decoder's parts as benchmark/tools/olmoe_precision.py reads them.
_rmsnorm, _rope, _moe_ffn = decoder.rmsnorm, decoder.rope, decoder.moe_ffn


def _qkv(h, p, config: LlamaConfig):
    return decoder._qkv(spec(config), h, p)


def _layer_stack(blocks: dict, config: LlamaConfig):
    return decoder._layer_stack(blocks, config.n_layers,
                                spec(config).ffn.whole)
