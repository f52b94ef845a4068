"""A.X-K1 family (a DeepSeek-V3-shaped block): RMSNorm + multi-head latent
attention (MLA) with YaRN-scaled rotary positions + leading dense SwiGLU
layers, then layers of sigmoid-routed top-k experts beside a shared expert,
untied head.

What is the family's own: the config and its presets, the parameter format
(`param_specs`, `init_params`) and `spec`, which names the parts of
models/decoder.py its block is made of (`decoder.LATENT`,
`decoder.SHARED_EXPERTS`, a leading run of `decoder.SWIGLU` layers).
Everything that runs is the decoder's, bound to `spec` under the names
below.  The config's fields carry the published names' meanings.

A config may describe ONE CHIP'S SHARE of an expert-parallel deployment:
`n_experts_held` < `n_routed_experts` experts from `experts_offset` on live
here (the router keeps all `n_routed_experts` outputs and its top-k; the
assignments that fall elsewhere add nothing here), and `vocab_size` may be
a slice of the published vocabulary (then simply a smaller vocabulary).
Served only: latent attention has no train path (ROADMAP.md); the expert
layer has its backward pass since Mellum 2 was trained.
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
class Axk1Config:
    vocab_size: int = 163840
    n_layers: int = 61
    d_model: int = 7168
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_dense_layers: int = 1   # leading layers with a dense SwiGLU
    d_ff: int = 18432             # their hidden width
    d_expert: int = 2048          # one routed (or shared) expert's width
    n_routed_experts: int = 192   # the router's outputs
    n_experts_held: int = 0       # experts that live here; 0 = all of them
    experts_offset: int = 0       # the first of them
    n_shared_experts: int = 1
    n_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    routed_scale: float = 2.5
    max_seq_len: int = 131072
    rope_theta: float = 10000.0
    # YaRN (decoder.yarn_freqs); rope_factor 1 is plain RoPE.
    rope_factor: float = 32.0
    rope_original_max_seq_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = False
    scan_unroll: int = 1
    param_dtype: Any = jnp.bfloat16   # a dtype or its name ("bfloat16")

    @property
    def n_experts(self) -> int:
        """The router's width, as `decoder.moe_ffn` reads it."""
        return self.n_routed_experts

    @property
    def held(self) -> int:
        return self.n_experts_held or self.n_routed_experts


CONFIGS = {
    # The block at nano size, whole (tests).
    "axk1-nano": Axk1Config(
        vocab_size=512, n_layers=3, d_model=64, n_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, d_ff=128, d_expert=32, n_routed_experts=16,
        n_experts_per_tok=4, max_seq_len=256, rope_factor=4.0,
        rope_original_max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32),
}
# One of four shares of it: experts 4 to 7 of 16.
CONFIGS["axk1-nano-share"] = dataclasses.replace(
    CONFIGS["axk1-nano"], n_experts_held=4, experts_offset=4)


def _attention_specs() -> dict:
    return {
        "attn_norm": ("layers", "embed"),
        "w_qa": ("layers", "embed", None),
        "q_norm": ("layers", None),
        "w_qb": ("layers", None, "heads", "kv"),
        "w_kva": ("layers", "embed", None),
        "kv_norm": ("layers", None),
        "w_kvb": ("layers", None, "heads", "kv"),
        "wo": ("layers", "heads", "kv", "embed"),
        "mlp_norm": ("layers", "embed"),
    }


def param_specs(config: Axk1Config) -> dict:
    out = {
        "tok_embed": ("vocab", None),
        "blocks": {
            **_attention_specs(),
            "router": ("layers", "embed", "experts"),
            "w_gate": ("layers", "experts", "embed", "expert_mlp"),
            "w_up": ("layers", "experts", "embed", "expert_mlp"),
            "w_down": ("layers", "experts", "expert_mlp", "embed"),
            "ws_gate": ("layers", "embed", "mlp"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    if config.first_dense_layers:
        out["lead_blocks"] = {
            **_attention_specs(),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
    return out


def init_params(config: Axk1Config, key: jax.Array) -> dict:
    """Parameters in `param_dtype`.  Every matrix is drawn in float32 one
    slice of its leading dims at a time and stored as it is drawn, as
    `llama.init_params` does (a layer's 12 held experts are 1.06 GB in
    bf16), at llama's scales."""
    c = config
    d, h = c.d_model, c.n_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    pd = jnp.dtype(c.param_dtype)
    keys = iter(jax.random.split(key, 40))

    def dense(shape, fan_in, lead=1):
        """normal / sqrt(fan_in), drawn per index of the `lead` leading
        dims (a loop on the device: its float32 temporary is one slice)."""
        scale = 1.0 / np.sqrt(fan_in)
        rows = int(np.prod(shape[:lead]))
        out = jax.lax.map(
            lambda k: (jax.random.normal(k, shape[lead:], jnp.float32)
                       * scale).astype(pd),
            jax.random.split(next(keys), rows))
        return out.reshape(shape)

    ones = partial(jnp.ones, dtype=pd)
    depth = 2 * c.n_layers

    def attention(n):
        return {
            "attn_norm": ones((n, d)),
            "w_qa": dense((n, d, c.q_lora_rank), d),
            "q_norm": ones((n, c.q_lora_rank)),
            "w_qb": dense((n, c.q_lora_rank, h, qk), c.q_lora_rank),
            "w_kva": dense((n, d, c.kv_lora_rank + c.qk_rope_head_dim), d),
            "kv_norm": ones((n, c.kv_lora_rank)),
            "w_kvb": dense((n, c.kv_lora_rank, h,
                            c.qk_nope_head_dim + c.v_head_dim),
                           c.kv_lora_rank),
            "wo": dense((n, h, c.v_head_dim, d), h * c.v_head_dim * depth),
            "mlp_norm": ones((n, d)),
        }

    n = c.n_layers - c.first_dense_layers
    e, f, fs = c.held, c.d_expert, c.n_shared_experts * c.d_expert
    params = {
        "tok_embed": dense((c.vocab_size, d), 2500.0, 0),
        "blocks": {
            **attention(n),
            "router": dense((n, d, c.n_routed_experts), d),
            "w_gate": dense((n, e, d, f), d, 2),
            "w_up": dense((n, e, d, f), d, 2),
            "w_down": dense((n, e, f, d), f * depth, 2),
            "ws_gate": dense((n, d, fs), d),
            "ws_up": dense((n, d, fs), d),
            "ws_down": dense((n, fs, d), fs * depth),
        },
        "final_norm": ones((d,)),
        "lm_head": dense((d, c.vocab_size), d, 0),
    }
    if c.first_dense_layers:
        n = c.first_dense_layers
        params["lead_blocks"] = {
            **attention(n),
            "w_gate": dense((n, d, c.d_ff), d),
            "w_up": dense((n, d, c.d_ff), d),
            "w_down": dense((n, c.d_ff, d), c.d_ff * depth),
        }
    return params


def spec(config: Axk1Config) -> decoder.Spec:
    c = config
    yarn = c.rope_factor > 1
    if yarn and c.rope_mscale != c.rope_mscale_all_dim:
        # YaRN's factor on cos and sin, mscale / mscale_all_dim, is 1 for
        # the published pair (1, 1) and is applied nowhere.
        raise NotImplementedError("rope_mscale != rope_mscale_all_dim")
    mscale = decoder.yarn_mscale(c.rope_factor, c.rope_mscale_all_dim)
    return decoder.Spec(
        norm=partial(decoder.rmsnorm, eps=c.norm_eps),
        attn_norm=("attn_norm",), mlp_norm=("mlp_norm",),
        final_norm=("final_norm",),
        attn=decoder.LATENT,
        ffn=(decoder.SHARED_EXPERTS if c.n_shared_experts
             else decoder.EXPERTS),
        first_dense_layers=c.first_dense_layers,
        lead_ffn=decoder.SWIGLU if c.first_dense_layers else None,
        rope_theta=c.rope_theta,
        rope_freqs=tuple(decoder.yarn_freqs(
            c.qk_rope_head_dim, c.rope_theta, c.rope_factor,
            c.rope_original_max_seq_len, c.rope_beta_fast,
            c.rope_beta_slow).tolist()) if yarn else None,
        attn_scale=float((c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
                         * mscale * mscale),
        init_params=init_params, param_specs=param_specs)


# The decoder bound to `spec` (signatures and docs: models/decoder.py,
# less its first argument).
_bound = decoder.bind(spec)
lm_head = _bound.lm_head
forward_cached = _bound.forward_cached
loss_fn = _bound.loss_fn
serving_params = _bound.serving_params
shard_params = _bound.shard_params
num_params = _bound.num_params
make_train_step = _bound.make_train_step


def forward_trunk(params: dict, tokens: jax.Array, config: Axk1Config,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed): the
    decoder's, less the auxiliary loss no part of this family has."""
    return _bound.forward_trunk(params, tokens, config, mesh,
                                position_offset)[0]


def forward(params: dict, tokens: jax.Array, config: Axk1Config,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V] (the decoder's, as above)."""
    return _bound.forward(params, tokens, config, mesh, position_offset)[0]
