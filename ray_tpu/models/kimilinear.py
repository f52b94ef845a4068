"""Kimi Linear family (moonshotai/Kimi-Linear-48B-A3B-Instruct, `model_type`
kimi_linear): pre-norm RMSNorm blocks, `x = x + mix(input_layernorm(x))`,
`x = x + ffn(post_attention_layernorm(x))`, whose `mix` is named a layer by
the config's two lists (1-indexed, as published):

  kda_layers        Kimi Delta Attention (`decoder.KDA`): 32 heads of 128,
                    q, k and v through a causal depthwise convolution of 4
                    taps and SiLU, q and k L2-normed a head, a float32 state
                    [128, 128] a head decayed a KEY CHANNEL
                    (`g = -exp(A_log) softplus((h W_fa) W_fb + dt_bias)`)
                    and corrected by a delta rule (`beta = sigmoid(h W_b)`),
                    an RMSNorm a head and a low-rank sigmoid gate on the
                    output.  What a lane keeps between steps: the state and
                    the convolutions' last three rows;
  full_attn_layers  multi-head latent attention with NO positional encoding
                    (`mla_use_nope`): a direct query (`q_lora_rank` null),
                    one latent row of 512 + 64 numbers a token, the 64
                    "rope" numbers of query and key used as projected:
                    `decoder.LATENT` under a run's `LatentSizes` with no
                    query rank and no rotation.

Three KDA layers to one latent layer; the order of tokens comes from the
KDA layers' recurrence alone (no rotation, no position table).  The
feed-forward of the first `first_dense_layers` layers is a SwiGLU; of the
others 256 sigmoid-routed SwiGLU experts, the 8 of largest `s + bias`
weighted by the unbiased s there over their sum times
`routed_scaling_factor`, beside one shared expert
(`decoder.SHARED_EXPERTS`).  A last RMSNorm, an untied head.

What is the family's own: the config, the parameter format (`param_specs`,
`init_params`: a stack of leaves for each KIND of layer, mixer x
feed-forward: `dense_kdas`, `dense_mlas`, `kdas`, `mlas`) and `spec`, which
turns the two lists into the decoder's runs (`decoder.Run`): a run for every
stretch of one kind, the runs of a kind sharing that kind's stack
(`Run.offset`), every latent run the cache's ONE latent pool and every KDA
run the state part's two buffers behind it (`Run.first`: the latent layers,
or the KDA layers, before it).  Everything that runs is the decoder's.
Served only (the scan has no backward pass, latent attention no train path).

A config may describe ONE STAGE of a pipeline (the lists' entries up to
`n_layers`, the leading dense layers counted once), one chip's share of the
experts (`n_experts_held` of `n_routed_experts` from `experts_offset` on:
`models/axk1.py`) and a slice of the vocabulary.
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

KDA, MLA = "kda", "mla"
# Kimi-Linear-48B-A3B's twenty-seven: K K K M six times, K K M.
FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)
KDA_LAYERS = tuple(i for i in range(1, 28) if i not in FULL_ATTN_LAYERS)
# (mixer, dense feed-forward?) -> its stack of leaves
STACKS = {(KDA, True): "dense_kdas", (MLA, True): "dense_mlas",
          (KDA, False): "kdas", (MLA, False): "mlas"}
# E[silu(g)^2] of a unit normal g, and E[sigmoid(g)^2]: what a SwiGLU's
# product and a sigmoid gate keep of a unit second moment (`init_params`)
_SILU2, _SIGMOID2 = 0.355, 0.293


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    n_layers: int = 27
    kda_layers: tuple = KDA_LAYERS              # 1-indexed, as published
    full_attn_layers: tuple = FULL_ATTN_LAYERS
    d_model: int = 2304
    # the latent attention (named as `Axk1Config` names them)
    n_heads: int = 32
    q_lora_rank: int = 0            # null: the query is projected directly
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64      # the published name; nothing is rotated
    v_head_dim: int = 128
    # Kimi Delta Attention (linear_attn_config)
    kda_heads: int = 32             # num_heads
    kda_head_dim: int = 128         # head_dim: a key's and a value's, and
    #                                 the rank of the two low-rank gates
    kda_conv: int = 4               # short_conv_kernel_size
    kda_chunk: int = 32             # positions a chunk of the scan
    # the feed-forwards
    first_dense_layers: int = 1     # first_k_dense_replace
    d_ff: int = 9216                # intermediate_size (dense layers)
    d_expert: int = 1024            # moe_intermediate_size
    n_routed_experts: int = 256     # the router's outputs
    n_experts_held: int = 0         # experts that live here; 0 = all of them
    experts_offset: int = 0         # the first of them
    n_shared_experts: int = 1
    n_experts_per_tok: int = 8
    norm_topk_prob: bool = True     # moe_renormalize
    scoring_func: str = "sigmoid"   # moe_router_activation_func
    routed_scale: float = 2.446     # routed_scaling_factor
    max_seq_len: int = 1048576
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    scan_unroll: int = 1
    param_dtype: Any = jnp.bfloat16     # a dtype or its name ("bfloat16")

    def __post_init__(self):
        for name in ("kda_layers", "full_attn_layers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        both = sorted(self.kda_layers + self.full_attn_layers)
        if both != list(range(1, self.n_layers + 1)):
            raise ValueError(
                f"kda_layers {self.kda_layers!r} and full_attn_layers "
                f"{self.full_attn_layers!r}: each of layers 1 to "
                f"{self.n_layers} in exactly one")

    @property
    def n_experts(self) -> int:
        """The router's width, as `decoder.moe_ffn` reads it."""
        return self.n_routed_experts

    @property
    def held(self) -> int:
        return self.n_experts_held or self.n_routed_experts

    @property
    def kinds(self) -> list:
        """(mixer, dense feed-forward?) of every layer, in order."""
        return [(KDA if i + 1 in self.kda_layers else MLA,
                 i < self.first_dense_layers) for i in range(self.n_layers)]


CONFIGS = {
    # Every kind of layer the 48B model has and every pair of neighbours (a
    # dense KDA layer, a run of KDA expert layers, a latent layer behind it,
    # a KDA layer behind that, a last latent layer), at nano size with the
    # state's columns the lane width as published (tests, rehearsals).
    "kimilinear-nano": KimiLinearConfig(
        vocab_size=512, n_layers=6, kda_layers=(1, 2, 3, 5),
        full_attn_layers=(4, 6), d_model=64, n_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kda_heads=2,
        kda_head_dim=128, kda_chunk=8, d_ff=96, d_expert=24,
        n_routed_experts=16, n_experts_per_tok=4, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32),
}
# One of four shares of it: experts 4 to 7 of 16.
CONFIGS["kimilinear-nano-share"] = dataclasses.replace(
    CONFIGS["kimilinear-nano"], n_experts_held=4, experts_offset=4)

_MIXER_SPECS = {
    KDA: {"w_qkv": ("layers", "embed", "mlp"),
          "conv_w": ("layers", None, None),
          "w_fa": ("layers", "embed", None),
          "w_fb": ("layers", None, "mlp"),
          "dt_bias": ("layers", None),
          "A_log": ("layers", None),
          "w_beta": ("layers", "embed", None),
          "w_ga": ("layers", "embed", None),
          "w_gb": ("layers", None, "mlp"),
          "o_norm": ("layers", None),
          "w_out": ("layers", "mlp", "embed")},
    MLA: {"wq": ("layers", "embed", "heads", "kv"),
          "w_kva": ("layers", "embed", None),
          "kv_norm": ("layers", None),
          "w_kvb": ("layers", None, "heads", "kv"),
          "wo": ("layers", "heads", "kv", "embed")},
}
_FFN_SPECS = {
    True: {"w_gate": ("layers", "embed", "mlp"),
           "w_up": ("layers", "embed", "mlp"),
           "w_down": ("layers", "mlp", "embed")},
    False: {"router": ("layers", "embed", "experts"),
            "router_bias": ("layers", "experts"),
            "w_gate": ("layers", "experts", "embed", "expert_mlp"),
            "w_up": ("layers", "experts", "embed", "expert_mlp"),
            "w_down": ("layers", "experts", "expert_mlp", "embed"),
            "ws_gate": ("layers", "embed", "mlp"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed")},
}


def _counts(config: KimiLinearConfig) -> dict:
    """The layers of each kind the config holds (kinds it has none of left
    out), in `STACKS`' order."""
    kinds = config.kinds
    return {kind: kinds.count(kind) for kind in STACKS if kind in kinds}


def param_specs(config: KimiLinearConfig) -> dict:
    norms = {"attn_norm": ("layers", "embed"), "mlp_norm": ("layers", "embed")}
    return {
        "tok_embed": ("vocab", None),
        **{STACKS[mix, dense]: {**norms, **_MIXER_SPECS[mix],
                                **_FFN_SPECS[dense]}
           for mix, dense in _counts(config)},
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init_params(config: KimiLinearConfig, key: jax.Array) -> dict:
    """Parameters in `param_dtype`, matrices drawn as `axk1.init_params`
    draws them (float32 a slice at a time, normal / sqrt(fan_in), stored as
    drawn), so that each part adds to the residual stream at one order (the
    argument in `falconh1.init_params`): a layer is two parts, so the depth
    an output projection is drawn against is twice the layers; each is also
    drawn against what its input keeps of a unit second moment: KDA's
    `W_out` reads a head-normed vector times a sigmoid gate (0.293), the
    latent attention's `W_o` a mean of unit values, a SwiGLU's `W_down`
    `silu(g) u` (0.355), and the chosen experts' results are summed under
    weights that add up to `routed_scale`.  The router's selection bias is
    small and seeded (0.02 normal: it moves a choice only between scores
    that close).

    The recurrence's own parameters as Mamba-2's here (`assumed` in the
    benchmark's configuration file): A uniform in 1..16 a head (`A_log` its
    log), the decay's bias the inverse softplus of a log-uniform draw in
    0.001..0.1 a key channel; the convolutions as a depthwise Conv1d's
    default (uniform in +-K^-0.5, no bias); the head norm's scale ones."""
    c = config
    d, depth = c.d_model, 2 * c.n_layers
    pd = jnp.dtype(c.param_dtype)
    keys = iter(jax.random.split(key, 96))

    def dense(shape, fan_in, lead=1):
        scale = 1.0 / np.sqrt(fan_in)
        rows = int(np.prod(shape[:lead]))
        out = jax.lax.map(
            lambda k: (jax.random.normal(k, shape[lead:], jnp.float32)
                       * scale).astype(pd),
            jax.random.split(next(keys), rows))
        return out.reshape(shape)

    ones = partial(jnp.ones, dtype=pd)
    heads, hd = c.kda_heads, c.kda_head_dim
    wide = heads * hd
    bound = c.kda_conv ** -0.5

    def mixer(mix, n):
        if mix == KDA:
            dt0 = jnp.exp(jax.random.uniform(
                next(keys), (n, wide), jnp.float32, np.log(1e-3),
                np.log(1e-1)))
            return {
                # columns [q | k | v]
                "w_qkv": dense((n, d, 3 * wide), d),
                "conv_w": jax.random.uniform(
                    next(keys), (n, c.kda_conv, 3 * wide), jnp.float32,
                    -bound, bound).astype(pd),
                "w_fa": dense((n, d, hd), d),
                "w_fb": dense((n, hd, wide), hd),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (n, heads), jnp.float32, 1.0, 16.0)),
                "w_beta": dense((n, d, heads), d),
                "w_ga": dense((n, d, hd), d),
                "w_gb": dense((n, hd, wide), hd),
                "o_norm": ones((n, hd)),
                "w_out": dense((n, wide, d), wide * depth * _SIGMOID2)}
        h, qk = c.n_heads, c.qk_nope_head_dim + c.qk_rope_head_dim
        return {
            "wq": dense((n, d, h, qk), d),
            "w_kva": dense((n, d, c.kv_lora_rank + c.qk_rope_head_dim), d),
            "kv_norm": ones((n, c.kv_lora_rank)),
            "w_kvb": dense((n, c.kv_lora_rank, h,
                            c.qk_nope_head_dim + c.v_head_dim),
                           c.kv_lora_rank),
            "wo": dense((n, h, c.v_head_dim, d), h * c.v_head_dim * depth)}

    def ffn(is_dense, n):
        if is_dense:
            return {"w_gate": dense((n, d, c.d_ff), d),
                    "w_up": dense((n, d, c.d_ff), d),
                    "w_down": dense((n, c.d_ff, d),
                                    c.d_ff * depth * _SILU2)}
        e, f, fs = c.held, c.d_expert, c.n_shared_experts * c.d_expert
        return {
            "router": dense((n, d, c.n_routed_experts), d),
            "router_bias": 0.02 * jax.random.normal(
                next(keys), (n, c.n_routed_experts), jnp.float32),
            "w_gate": dense((n, e, d, f), d, 2),
            "w_up": dense((n, e, d, f), d, 2),
            "w_down": dense((n, e, f, d), f * depth * _SILU2
                            * c.n_experts_per_tok / c.routed_scale ** 2, 2),
            "ws_gate": dense((n, d, fs), d),
            "ws_up": dense((n, d, fs), d),
            "ws_down": dense((n, fs, d), fs * depth * _SILU2)}

    params = {"tok_embed": dense((c.vocab_size, d), 2500.0, 0)}
    for (mix, is_dense), n in _counts(c).items():
        params[STACKS[mix, is_dense]] = {
            "attn_norm": ones((n, d)), **mixer(mix, n),
            "mlp_norm": ones((n, d)), **ffn(is_dense, n)}
    params["final_norm"] = ones((d,))
    params["lm_head"] = dense((d, c.vocab_size), d, 0)
    return params


def latent_sizes(config: KimiLinearConfig) -> decoder.LatentSizes:
    """What `decoder.LATENT` reads of a latent run: MLA's published sizes,
    no query rank, no rotation, the scores scaled by (128 + 64)^-0.5."""
    c = config
    return decoder.LatentSizes(
        c.n_heads, c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
        c.qk_rope_head_dim, c.v_head_dim, rope_theta=None,
        attn_scale=float((c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5),
        norm_eps=c.norm_eps)


def runs_of(config: KimiLinearConfig) -> tuple:
    """The two lists as the decoder's runs: one for every stretch of a kind
    (mixer x feed-forward); `offset` counts the kind's layers before it in
    its stack, `first` the layers of its MIXER before it in that mixer's
    part of the cache (the one latent pool, 0, for a latent run; the state
    part's two buffers behind it for a KDA run)."""
    sizes = latent_sizes(config)
    stacked = dict.fromkeys(STACKS, 0)
    cached = {KDA: 0, MLA: 0}
    out = []
    for (mix, dense), group in itertools.groupby(config.kinds):
        n = len(list(group))
        part = (dict(attn=None, mixer=decoder.KDA, pools=(1, 2))
                if mix == KDA else
                dict(attn=decoder.LATENT, sizes=sizes, pools=(0,)))
        out.append(decoder.Run(
            STACKS[mix, dense], n,
            decoder.SWIGLU if dense else decoder.SHARED_EXPERTS,
            first=cached[mix], offset=stacked[mix, dense], **part))
        stacked[mix, dense] += n
        cached[mix] += n
    return tuple(out)


def spec(config: KimiLinearConfig) -> decoder.Spec:
    c = config
    return decoder.Spec(
        norm=partial(decoder.rmsnorm, eps=c.norm_eps),
        attn_norm=("attn_norm",), mlp_norm=("mlp_norm",),
        final_norm=("final_norm",),
        ffn=decoder.SHARED_EXPERTS, attn=decoder.LATENT,
        # no rotation and no table: causal order alone (the latent runs'
        # own sizes say the same: `latent_sizes`)
        rope_theta=None, pos_table=False,
        attn_scale=latent_sizes(c).attn_scale,
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


def forward_trunk(params: dict, tokens: jax.Array, config: KimiLinearConfig,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed): the
    decoder's, less the auxiliary loss no part of this family has."""
    return _bound.forward_trunk(params, tokens, config, mesh,
                                position_offset)[0]


def forward(params: dict, tokens: jax.Array, config: KimiLinearConfig,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V] (the decoder's, as above)."""
    return _bound.forward(params, tokens, config, mesh, position_offset)[0]
