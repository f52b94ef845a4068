"""Nemotron-H family (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
`model_type` nemotron_h): a stack whose every layer is ONE part behind one
RMSNorm, `h = h + part(norm(h))`, the part named by a letter of the
config's pattern:

  M  a state-space mixer (Mamba-2): `decoder.SSM` alone, no attention
     beside it, no feed-forward behind it (`[z | x B C | dt] = W_in u`, a
     causal depthwise convolution of 4 taps and SiLU on [x B C], the
     recurrence of ops/ssm.py, 64 heads of 64 with a state of 128, B and C
     shared by the 8 heads of a group; `y silu(z)`, an RMSNorm over each
     group's 512 columns, `W_out`; no factor on any segment);
  *  grouped-query attention, 32 query heads over 2 key/value heads of 128,
     with NO positional encoding of any kind (the order is the mixers' to
     carry): `decoder.HEADS` under a spec with no rotation and no table;
  E  128 sigmoid-routed experts, top-6 by score + bias, the chosen scores
     normed to one and scaled by 2.5, each expert `W_down relu(W_up u)^2`
     (two matrices, no gate), beside one shared expert of the same form:
     `decoder.SHARED_RELU2_EXPERTS`.

Untied head, bf16 residual stream.  What is the family's own: the config,
the parameter format (`param_specs`, `init_params`: a stack of leaves a
KIND of layer, `mixers`, `attns`, `experts`) and `spec`, which turns the
pattern into the decoder's runs (`decoder.Run`): a run for every stretch of
one letter, the runs of a letter sharing that letter's stack
(`Run.offset`) and its part of the cache (`Run.first`: K and V pools over
the `*` layers, the state part over the `M` layers; an `E` layer keeps
nothing).  Everything that runs is the decoder's.  Served only.

A config may describe ONE STAGE of a pipeline (the pattern's first letters),
one chip's share of the experts (`n_experts_held` of `n_routed_experts`
from `experts_offset` on: `models/axk1.py`) and a slice of the vocabulary.
"""

from __future__ import annotations

import dataclasses
import itertools
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import decoder

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# a letter's stack of leaves in the parameter tree
STACKS = {"M": "mixers", "*": "attns", "E": "experts"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    n_layers: int = 52
    pattern: str = PATTERN        # hybrid_override_pattern, a letter a layer
    d_model: int = 2688
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # the state-space mixer (named as `FalconH1Config` names them)
    ssm_heads: int = 64           # mamba_num_heads
    ssm_head_dim: int = 64        # mamba_head_dim
    ssm_state: int = 128          # ssm_state_size
    ssm_groups: int = 8           # n_groups
    ssm_conv: int = 4             # conv_kernel
    ssm_chunk: int = 128          # chunk_size
    # on z, x, B, C, dt: the family states none
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    # the experts
    d_expert: int = 1856          # moe_intermediate_size
    d_shared: int = 3712          # moe_shared_expert_intermediate_size
    n_routed_experts: int = 128   # the router's outputs
    n_experts_held: int = 0       # experts that live here; 0 = all of them
    experts_offset: int = 0       # the first of them
    n_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    routed_scale: float = 2.5     # routed_scaling_factor
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    scan_unroll: int = 1
    param_dtype: Any = jnp.bfloat16   # a dtype or its name ("bfloat16")

    def __post_init__(self):
        object.__setattr__(self, "ssm_multipliers",
                           tuple(self.ssm_multipliers))
        if len(self.pattern) != self.n_layers or set(self.pattern) - set(
                STACKS):
            raise ValueError(
                f"pattern {self.pattern!r}: one of M, *, E for each of "
                f"{self.n_layers} layers")

    @property
    def n_experts(self) -> int:
        """The router's width, as `decoder.moe_ffn` reads it."""
        return self.n_routed_experts

    @property
    def held(self) -> int:
        return self.n_experts_held or self.n_routed_experts

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    def layers_of(self, letter: str) -> int:
        return self.pattern.count(letter)


CONFIGS = {
    # The pattern's first seven layers (every kind, and every pair of
    # neighbours the pattern has) at nano size, the mixer's heads half the
    # lane width as published, so that two fold into a lane row (tests).
    "nemotronh-nano": NemotronHConfig(
        vocab_size=512, n_layers=7, pattern=PATTERN[:7], d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=64,
        ssm_state=16, ssm_groups=2, ssm_chunk=8, d_expert=24, d_shared=48,
        n_routed_experts=16, n_experts_per_tok=4, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32),
}
# One of two shares of it: experts 8 to 15 of 16.
CONFIGS["nemotronh-nano-share"] = dataclasses.replace(
    CONFIGS["nemotronh-nano"], n_experts_held=8, experts_offset=8)


def param_specs(config: NemotronHConfig) -> dict:
    c = config
    stacks = {
        "mixers": {
            "norm": ("layers", "embed"),
            "w_in": ("layers", "embed", "mlp"),
            "conv_w": ("layers", None, None),
            "conv_b": ("layers", None),
            "A_log": ("layers", None),
            "dt_bias": ("layers", None),
            "D": ("layers", None),
            "ssm_norm": ("layers", None),
            "w_out": ("layers", "mlp", "embed"),
        },
        "attns": {
            "norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "kv"),
            "wk": ("layers", "embed", "kv_heads", "kv"),
            "wv": ("layers", "embed", "kv_heads", "kv"),
            "wo": ("layers", "heads", "kv", "embed"),
        },
        "experts": {
            "norm": ("layers", "embed"),
            "router": ("layers", "embed", "experts"),
            "router_bias": ("layers", "experts"),
            # [E, F, D], a Linear's [out, in] as published: 1856 columns
            # are no multiple of the lane width (ops/moe.py)
            "w_up_t": ("layers", "experts", "expert_mlp", "embed"),
            "w_down": ("layers", "experts", "expert_mlp", "embed"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed"),
        },
    }
    return {
        "tok_embed": ("vocab", None),
        **{STACKS[k]: stacks[STACKS[k]] for k in STACKS if c.layers_of(k)},
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(config: NemotronHConfig, key: jax.Array) -> dict:
    """Parameters in `param_dtype`, matrices drawn as `axk1.init_params`
    draws them (float32 a slice at a time, normal / sqrt(fan_in), stored as
    drawn), so that each part adds to the residual stream at one order
    (the argument in `falconh1.init_params`): a layer is ONE part, so the
    depth an output projection is drawn against is the number of layers;
    the mixer's `W_out` reads a normed vector, the attention's `W_o` a
    mean of unit values, and an expert's `W_down` reads `relu(u)^2` of a
    unit normal u, whose second moment is 3/2 (mean 1/2): its draw is
    divided by sqrt(3/2) more.  The router's bias is small and seeded
    (0.02 normal: it moves a choice only between scores that close).

    The recurrence's own parameters as Mamba-2 draws them: A uniform in
    1..16, dt log-uniform in 0.001..0.1 (`time_step_min`, `time_step_max`;
    `dt_bias` its inverse softplus), D ones; the convolution as a
    depthwise Conv1d's default (uniform in +-K^-0.5, its bias too); the
    gated norm's scale ones."""
    c = config
    d, depth = c.d_model, c.n_layers
    pd = jnp.dtype(c.param_dtype)
    keys = iter(jax.random.split(key, 24))

    def dense(shape, fan_in, lead=1):
        scale = 1.0 / np.sqrt(fan_in)
        rows = int(np.prod(shape[:lead]))
        out = jax.lax.map(
            lambda k: (jax.random.normal(k, shape[lead:], jnp.float32)
                       * scale).astype(pd),
            jax.random.split(next(keys), rows))
        return out.reshape(shape)

    ones = partial(jnp.ones, dtype=pd)
    params = {"tok_embed": dense((c.vocab_size, d), 2500.0, 0)}
    n = c.layers_of("M")
    if n:
        gn = c.ssm_groups * c.ssm_state
        bound = c.ssm_conv ** -0.5
        dt0 = jnp.exp(jax.random.uniform(
            next(keys), (n, c.ssm_heads), jnp.float32, np.log(1e-3),
            np.log(1e-1)))
        params["mixers"] = {
            "norm": ones((n, d)),
            # columns [z | x | B | C | dt]
            "w_in": dense((n, d, 2 * c.d_ssm + 2 * gn + c.ssm_heads), d),
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
            "w_out": dense((n, c.d_ssm, d), c.d_ssm * depth),
        }
    n = c.layers_of("*")
    if n:
        h, kh, hd = c.n_heads, c.n_kv_heads, c.head_dim
        params["attns"] = {
            "norm": ones((n, d)),
            "wq": dense((n, d, h, hd), d),
            "wk": dense((n, d, kh, hd), d),
            "wv": dense((n, d, kh, hd), d),
            "wo": dense((n, h, hd, d), h * hd * depth),
        }
    n = c.layers_of("E")
    if n:
        e, f, fs = c.held, c.d_expert, c.d_shared
        params["experts"] = {
            "norm": ones((n, d)),
            "router": dense((n, d, c.n_routed_experts), d),
            "router_bias": 0.02 * jax.random.normal(
                next(keys), (n, c.n_routed_experts), jnp.float32),
            "w_up_t": dense((n, e, f, d), d, 2),
            "w_down": dense((n, e, f, d), 1.5 * f * depth, 2),
            "ws_up": dense((n, d, fs), d),
            "ws_down": dense((n, fs, d), 1.5 * fs * depth),
        }
    params["final_norm"] = ones((d,))
    params["lm_head"] = dense((d, c.vocab_size), d, 0)
    return params


def runs_of(config: NemotronHConfig) -> tuple:
    """The pattern as the decoder's runs: one for every stretch of a
    letter; `offset` counts the letter's layers before it in its stack,
    `first` the same in its part of the cache (K and V pools 0 and 1 for
    `*`, the state's two buffers behind them for `M`, none for `E`)."""
    parts = {
        "M": dict(ffn=None, attn=None, mixer=decoder.SSM, pools=(2, 3)),
        "*": dict(ffn=None, attn=decoder.HEADS, pools=(0, 1)),
        "E": dict(ffn=decoder.SHARED_RELU2_EXPERTS, attn=None, pools=()),
    }
    seen = dict.fromkeys(STACKS, 0)
    out = []
    for letter, group in itertools.groupby(config.pattern):
        n = len(list(group))
        out.append(decoder.Run(STACKS[letter], n, first=seen[letter],
                               offset=seen[letter], **parts[letter]))
        seen[letter] += n
    return tuple(out)


def spec(config: NemotronHConfig) -> decoder.Spec:
    c = config
    return decoder.Spec(
        norm=partial(decoder.rmsnorm, eps=c.norm_eps),
        # a layer's one norm, in front of whichever part it is
        attn_norm=("norm",), mlp_norm=("norm",),
        final_norm=("final_norm",),
        ffn=decoder.SHARED_RELU2_EXPERTS, attn=decoder.HEADS,
        # no rotation and no table: causal order alone
        rope_theta=None, pos_table=False,
        runs=runs_of(c), logits_dtype=jnp.float32,
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


def forward_trunk(params: dict, tokens: jax.Array, config: NemotronHConfig,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed): the
    decoder's, less the auxiliary loss no part of this family has."""
    return _bound.forward_trunk(params, tokens, config, mesh,
                                position_offset)[0]


def forward(params: dict, tokens: jax.Array, config: NemotronHConfig,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V] (the decoder's, as above)."""
    return _bound.forward(params, tokens, config, mesh, position_offset)[0]
