"""Falcon-H1 family (tiiuae/Falcon-H1-34B-Instruct, `model_type`
falcon_h1): RMSNorm blocks in which a state-space mixer (Mamba-2) and
grouped-query attention heads read the SAME normed input and both results
are added to the residual stream, over a dense SwiGLU; every path has its
stated factor (the model's maximal-update parametrisation); untied head.

  u = norm(h)
  h = h + Attn(u m_attn_in) m_attn_out + SSM(u m_ssm_in) m_ssm_out
  v = norm(h)
  h = h + W_down(silu(W_gate v m_0) * (W_up v)) m_1

Attn: `decoder.HEADS` with RoPE over the whole head and the keys times
`key_multiplier`.  SSM: `decoder.SSM` (`[z | x B C | dt] = W_in u`, each of
the five segments times its `ssm_multipliers` entry; a causal depthwise
convolution of 4 taps and SiLU on [x B C]; the recurrence of ops/ssm.py,
32 heads of 128 with a state of 256, B and C shared by the 16 heads of a
group; `y silu(z)`, an RMSNorm over each group's 2,048 columns, `W_out`).

What is the family's own: the config, the parameter format (`param_specs`,
`init_params`) and `spec`.  Everything that runs is the decoder's.  Over a
paged cache a layer owns K and V rows AND a fixed-size recurrent state
(`inference/kv_cache.py`, kind "state").  Served only.

A config may describe ONE STAGE of a pipeline and a slice of the
vocabulary, as `models/evabyte.py`'s and `models/axk1.py`'s do.
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
class FalconH1Config:
    vocab_size: int = 261120
    n_layers: int = 72
    d_model: int = 5120
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 21504
    rope_theta: float = 1e11
    # the state-space mixer (Mamba-2's names in the published config)
    ssm_heads: int = 32           # mamba_n_heads
    ssm_head_dim: int = 128       # mamba_d_head; heads x head_dim = d_ssm
    ssm_state: int = 256          # mamba_d_state
    ssm_groups: int = 2           # mamba_n_groups
    ssm_conv: int = 4             # mamba_d_conv
    ssm_chunk: int = 128          # mamba_chunk_size
    # the stated factors
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    # on z, x, B, C, dt
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    n_experts: int = 0
    dtype: Any = jnp.bfloat16
    remat: bool = False
    scan_unroll: int = 1
    param_dtype: Any = jnp.bfloat16   # a dtype or its name ("bfloat16")

    def __post_init__(self):
        # (a configuration file gives lists; the config is a jit's static
        # argument)
        for name in ("mlp_multipliers", "ssm_multipliers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state


CONFIGS = {
    # The block at nano size, every factor the published one (tests).
    "falconh1-nano": FalconH1Config(
        vocab_size=512, n_layers=3, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, ssm_heads=4, ssm_head_dim=8, ssm_state=16,
        ssm_groups=2, ssm_chunk=8, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32),
}


def param_specs(config: FalconH1Config) -> dict:
    return {
        "tok_embed": ("vocab", None),
        "blocks": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "kv"),
            "wk": ("layers", "embed", "kv_heads", "kv"),
            "wv": ("layers", "embed", "kv_heads", "kv"),
            "wo": ("layers", "heads", "kv", "embed"),
            "w_in": ("layers", "embed", "mlp"),
            "conv_w": ("layers", None, None),
            "conv_b": ("layers", None),
            "A_log": ("layers", None),
            "dt_bias": ("layers", None),
            "D": ("layers", None),
            "ssm_norm": ("layers", None),
            "w_out": ("layers", "mlp", "embed"),
            "mlp_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(config: FalconH1Config, key: jax.Array) -> dict:
    """Parameters in `param_dtype`, matrices drawn as `axk1.init_params`
    draws them (float32 a layer at a time, normal / sqrt(fan_in), stored as
    drawn) and then DIVIDED by the factor their path states: a trained
    Falcon-H1 carries its factors with weights that grew against them, and a
    random one drawn without would have keys of 1% of a query's size (a
    softmax that never leaves uniform) and a mixer that adds a tenth of what
    the attention adds: a check on such weights would not see half the
    block.  So every path, its factor included, has the scale `llama.py`'s
    draw gives it, and a factor left out or put in the wrong place moves the
    logits by its whole size.

    The recurrence's own parameters as Mamba-2 draws them: A uniform in
    1..16 (`A_log` its logarithm), dt log-uniform in 0.001..0.1 (`dt_bias`
    the inverse softplus of it), D ones; the convolution as a depthwise
    Conv1d's default (uniform in +-K^-0.5, its bias too); the gated norm's
    scale ones."""
    c = config
    d, n = c.d_model, c.n_layers
    pd = jnp.dtype(c.param_dtype)
    keys = iter(jax.random.split(key, 24))
    depth = 2 * n

    def dense(shape, fan_in, lead=1, over=1.0):
        scale = 1.0 / np.sqrt(fan_in) / over
        rows = int(np.prod(shape[:lead]))
        out = jax.lax.map(
            lambda k: (jax.random.normal(k, shape[lead:], jnp.float32)
                       * scale).astype(pd),
            jax.random.split(next(keys), rows))
        return out.reshape(shape)

    ones = partial(jnp.ones, dtype=pd)
    h, kh, hd = c.n_heads, c.n_kv_heads, c.head_dim
    gn = c.ssm_groups * c.ssm_state
    mz, mx, mb, mc, mdt = c.ssm_multipliers
    # W_in's columns [z | x | B | C | dt], each segment against its factor
    w_in = jnp.concatenate([
        dense((n, d, width), d, over=c.ssm_in_multiplier * m)
        for width, m in ((c.d_ssm, mz), (c.d_ssm, mx), (gn, mb), (gn, mc),
                         (c.ssm_heads, mdt))], axis=-1)
    bound = c.ssm_conv ** -0.5
    dt0 = jnp.exp(jax.random.uniform(
        next(keys), (n, c.ssm_heads), jnp.float32, np.log(1e-3),
        np.log(1e-1)))
    blocks = {
        "attn_norm": ones((n, d)),
        "wq": dense((n, d, h, hd), d),
        "wk": dense((n, d, kh, hd), d, over=c.key_multiplier),
        "wv": dense((n, d, kh, hd), d),
        "wo": dense((n, h, hd, d), h * hd * depth,
                    over=c.attention_out_multiplier),
        "w_in": w_in,
        "conv_w": jax.random.uniform(
            next(keys), (n, c.ssm_conv, c.conv_width), jnp.float32,
            -bound, bound).astype(pd),
        "conv_b": jax.random.uniform(
            next(keys), (n, c.conv_width), jnp.float32, -bound,
            bound).astype(pd),
        "A_log": jnp.log(jax.random.uniform(
            next(keys), (n, c.ssm_heads), jnp.float32, 1.0, 16.0)),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "D": jnp.ones((n, c.ssm_heads), jnp.float32),
        "ssm_norm": ones((n, c.d_ssm)),
        "w_out": dense((n, c.d_ssm, d), c.d_ssm * depth,
                       over=c.ssm_out_multiplier),
        "mlp_norm": ones((n, d)),
        "w_gate": dense((n, d, c.d_ff), d, over=c.mlp_multipliers[0]),
        "w_up": dense((n, d, c.d_ff), d),
        "w_down": dense((n, c.d_ff, d), c.d_ff * depth,
                        over=c.mlp_multipliers[1]),
    }
    return {
        "tok_embed": dense((c.vocab_size, d), 2500.0, 0,
                           over=c.embedding_multiplier),
        "blocks": blocks,
        "final_norm": ones((d,)),
        "lm_head": dense((d, c.vocab_size), d, 0,
                         over=c.lm_head_multiplier),
    }


def spec(config: FalconH1Config) -> decoder.Spec:
    c = config
    wide = jnp.dtype(c.dtype) != jnp.float32
    return decoder.Spec(
        norm=partial(decoder.rmsnorm, eps=c.norm_eps,
                     dtype=c.dtype if wide else None),
        attn_norm=("attn_norm",), mlp_norm=("mlp_norm",),
        final_norm=("final_norm",),
        ffn=decoder.SCALED_SWIGLU, rope_theta=c.rope_theta,
        runs=(decoder.Run("blocks", c.n_layers, decoder.SCALED_SWIGLU,
                          decoder.HEADS, mixer=decoder.SSM),),
        # Seventy-two layers of small additions to a stream the embedding's
        # factor made large: added in float32 under bf16 matrices.
        residual_dtype=jnp.float32 if wide else None,
        logits_dtype=jnp.float32,
        mult=decoder.Multipliers(
            embedding=c.embedding_multiplier, lm_head=c.lm_head_multiplier,
            key=c.key_multiplier, attn_in=c.attention_in_multiplier,
            attn_out=c.attention_out_multiplier,
            mixer_in=c.ssm_in_multiplier, mixer_out=c.ssm_out_multiplier),
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


def forward_trunk(params: dict, tokens: jax.Array, config: FalconH1Config,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed): the
    decoder's, less the auxiliary loss no part of this family has."""
    return _bound.forward_trunk(params, tokens, config, mesh,
                                position_offset)[0]


def forward(params: dict, tokens: jax.Array, config: FalconH1Config,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V] (the decoder's, as above)."""
    return _bound.forward(params, tokens, config, mesh, position_offset)[0]
