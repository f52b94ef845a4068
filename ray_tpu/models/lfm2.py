"""LFM2 family (LiquidAI/LFM2-24B-A2B, `model_type` lfm2_moe): pre-norm
RMSNorm blocks, `x = x + op(operator_norm(x))`, `x = x + ffn(ffn_norm(x))`,
whose operator is named a layer by the config's `layer_types`:

  conv            a gated short convolution (`decoder.CONV`):
                  `[B | C | u] = h W_in`, `v = B * u`, a causal depthwise
                  convolution of `conv_L_cache` (3) taps over v with neither
                  bias nor activation, `out = (C * conv) W_out`.  The gate
                  B * u comes BEFORE the convolution, so what a lane keeps
                  between steps is the last two rows of v a layer and
                  nothing else: no recurrent state;
  full_attention  grouped-query attention, 32 query heads over 8 key/value
                  heads of 64, an RMSNorm over each head's own 64 numbers on
                  q and on k BEFORE the rotation (base 1,000,000, the whole
                  head), no biases: `decoder.HEADS` under a run's
                  `HeadSizes`.

The feed-forward of the first `num_dense_layers` layers is a SwiGLU; of the
others 64 sigmoid-routed SwiGLU experts, no shared one: the 4 of largest
`s + expert_bias`, weighted by the UNBIASED s there divided by (their sum +
1e-6), times `routed_scaling_factor` (`decoder.EXPERTS`;
`config.norm_topk_eps`).  A last RMSNorm, the head tied to the embedding.

What is the family's own: the config, the parameter format (`param_specs`,
`init_params`: a stack of leaves for each KIND of layer, operator x
feed-forward: `dense_convs`, `dense_attns`, `convs`, `attns`) and `spec`,
which turns `layer_types` into the decoder's runs (`decoder.Run`): a run for
every stretch of one kind, the runs of a kind sharing that kind's stack
(`Run.offset`), every attention run the K and V pools and every conv run the
cache's state part, whose ONE buffer is the tails (`Run.first`: the
attention layers, or the conv layers, before it).  Everything that runs is
the decoder's.  Served only (the convolution's whole-sequence form has never
been trained).

A config may describe ONE STAGE of a pipeline: the `layer_types` it holds,
with the leading dense layers counted once.
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

CONV, ATTN = "conv", "full_attention"
# LFM2-24B-A2B's forty: two conv layers, then an attention layer and three
# conv layers nine times over, an attention layer and a conv layer.
LAYER_TYPES = (CONV,) + (CONV, ATTN, CONV, CONV) * 9 + (CONV, ATTN, CONV)
# (operator, dense feed-forward?) -> its stack of leaves
STACKS = {(CONV, True): "dense_convs", (ATTN, True): "dense_attns",
          (CONV, False): "convs", (ATTN, False): "attns"}
# E[silu(g)^2] of a unit normal g: what a SwiGLU's product keeps of its
# up projection's second moment (`init_params`)
_SILU2 = 0.355


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    n_layers: int = 40
    layer_types: tuple = LAYER_TYPES    # "conv" or "full_attention" a layer
    n_dense_layers: int = 2             # num_dense_layers, leading
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64                  # hidden_size / num_attention_heads
    conv_taps: int = 3                  # conv_L_cache
    d_ff: int = 11776                   # intermediate_size (dense layers)
    d_expert: int = 1536                # moe_intermediate_size
    n_experts: int = 64
    n_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    norm_topk_eps: float = 1e-6         # under the chosen scores' sum
    scoring_func: str = "sigmoid"
    routed_scale: float = 1.0           # routed_scaling_factor
    rope_theta: float = 1e6
    max_seq_len: int = 128000
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    scan_unroll: int = 1
    param_dtype: Any = jnp.bfloat16     # a dtype or its name ("bfloat16")

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.n_layers \
                or set(self.layer_types) - {CONV, ATTN}:
            raise ValueError(
                f"layer_types {self.layer_types!r}: one of {CONV!r}, "
                f"{ATTN!r} for each of {self.n_layers} layers")

    @property
    def kinds(self) -> list:
        """(operator, dense feed-forward?) of every layer, in order."""
        return [(op, i < self.n_dense_layers)
                for i, op in enumerate(self.layer_types)]


CONFIGS = {
    # Every kind of layer the 24B model has and every pair of neighbours
    # (a dense conv layer, an attention layer, a run of TWO conv layers, an
    # attention layer behind it), at nano size (tests, rehearsals).
    "lfm2-nano": Lfm2Config(
        vocab_size=512, n_layers=5,
        layer_types=(CONV, ATTN, CONV, CONV, ATTN), n_dense_layers=1,
        d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
        d_expert=24, n_experts=16, n_experts_per_tok=4, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32),
}

_OPERATOR_SPECS = {
    CONV: {"w_in": ("layers", "embed", "mlp"),
           "conv_w": ("layers", None, None),
           "w_out": ("layers", "mlp", "embed")},
    ATTN: {"wq": ("layers", "embed", "heads", "kv"),
           "wk": ("layers", "embed", "kv_heads", "kv"),
           "wv": ("layers", "embed", "kv_heads", "kv"),
           "q_norm": ("layers", None),
           "k_norm": ("layers", None),
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
            "w_down": ("layers", "experts", "expert_mlp", "embed")},
}


def _counts(config: Lfm2Config) -> dict:
    """The layers of each kind the config holds (kinds it has none of left
    out), in `STACKS`' order."""
    kinds = config.kinds
    return {kind: kinds.count(kind) for kind in STACKS if kind in kinds}


def param_specs(config: Lfm2Config) -> dict:
    norms = {"operator_norm": ("layers", "embed"),
             "ffn_norm": ("layers", "embed")}
    return {
        "tok_embed": ("vocab", None),
        **{STACKS[op, dense]: {**norms, **_OPERATOR_SPECS[op],
                               **_FFN_SPECS[dense]}
           for op, dense in _counts(config)},
        "final_norm": ("embed",),
    }


def init_params(config: Lfm2Config, key: jax.Array) -> dict:
    """Parameters in `param_dtype`, matrices drawn as `axk1.init_params`
    draws them (float32 a slice at a time, normal / sqrt(fan_in), stored as
    drawn), so that each part adds to the residual stream at one order
    (the argument in `falconh1.init_params`): a layer is two parts, so the
    depth an output projection is drawn against is twice the layers; each
    is also drawn against what its input keeps of a unit normal's second
    moment: the attention's `W_o` reads a mean of unit values; the conv
    operator's `W_out` reads `C * conv(B * u)`, three taps of second moment
    1/9 each (a depthwise Conv1d's default draw, uniform in +-K^-0.5): 1/3;
    a SwiGLU's `W_down` reads `silu(g) u`: 0.355; and the four chosen
    experts' results are summed under weights that add up to one: a
    quarter of one's.  The embedding is drawn at 1 / sqrt(d_model): it is
    the head too, and the logits of a normed stream then have unit size.
    The router's selection bias is small and seeded (0.02 normal: it moves
    a choice only between scores that close, and a zero one would leave
    the choice and the weights the same thing)."""
    c = config
    d, depth = c.d_model, 2 * c.n_layers
    pd = jnp.dtype(c.param_dtype)
    keys = iter(jax.random.split(key, 64))

    def dense(shape, fan_in, lead=1):
        scale = 1.0 / np.sqrt(fan_in)
        rows = int(np.prod(shape[:lead]))
        out = jax.lax.map(
            lambda k: (jax.random.normal(k, shape[lead:], jnp.float32)
                       * scale).astype(pd),
            jax.random.split(next(keys), rows))
        return out.reshape(shape)

    ones = partial(jnp.ones, dtype=pd)
    h, kh, hd = c.n_heads, c.n_kv_heads, c.head_dim
    bound = c.conv_taps ** -0.5

    def operator(op, n):
        if op == CONV:
            return {
                # columns [B | C | u]
                "w_in": dense((n, d, 3 * d), d),
                "conv_w": jax.random.uniform(
                    next(keys), (n, c.conv_taps, d), jnp.float32, -bound,
                    bound).astype(pd),
                "w_out": dense((n, d, d), d * depth / 3.0)}
        return {
            "wq": dense((n, d, h, hd), d),
            "wk": dense((n, d, kh, hd), d),
            "wv": dense((n, d, kh, hd), d),
            "q_norm": ones((n, hd)),
            "k_norm": ones((n, hd)),
            "wo": dense((n, h, hd, d), h * hd * depth)}

    def ffn(is_dense, n):
        if is_dense:
            return {"w_gate": dense((n, d, c.d_ff), d),
                    "w_up": dense((n, d, c.d_ff), d),
                    "w_down": dense((n, c.d_ff, d),
                                    c.d_ff * depth * _SILU2)}
        e, f = c.n_experts, c.d_expert
        return {
            "router": dense((n, d, e), d),
            "router_bias": 0.02 * jax.random.normal(
                next(keys), (n, e), jnp.float32),
            "w_gate": dense((n, e, d, f), d, 2),
            "w_up": dense((n, e, d, f), d, 2),
            "w_down": dense((n, e, f, d),
                            f * depth * _SILU2 / c.n_experts_per_tok, 2)}

    params = {"tok_embed": dense((c.vocab_size, d), d, 0)}
    for (op, is_dense), n in _counts(c).items():
        params[STACKS[op, is_dense]] = {
            "operator_norm": ones((n, d)), **operator(op, n),
            "ffn_norm": ones((n, d)), **ffn(is_dense, n)}
    params["final_norm"] = ones((d,))
    return params


def head_sizes(config: Lfm2Config) -> decoder.HeadSizes:
    """What `decoder.HEADS` reads of an attention run: the head counts, the
    rotation over the whole head, the RMSNorm over each head's own numbers
    on q and k."""
    c = config
    return decoder.HeadSizes(c.n_heads, c.n_kv_heads, c.head_dim,
                             rope_theta=c.rope_theta, qk_norm=c.norm_eps)


def runs_of(config: Lfm2Config) -> tuple:
    """`layer_types` as the decoder's runs: one for every stretch of a kind
    (operator x feed-forward); `offset` counts the kind's layers before it
    in its stack, `first` the layers of its OPERATOR before it in that
    operator's part of the cache (K and V pools 0 and 1 for an attention,
    the state part's one buffer, the tails, behind them for a conv)."""
    sizes = head_sizes(config)
    stacked = dict.fromkeys(STACKS, 0)
    cached = {CONV: 0, ATTN: 0}
    out = []
    for (op, dense), group in itertools.groupby(config.kinds):
        n = len(list(group))
        part = (dict(attn=None, mixer=decoder.CONV, pools=(2,))
                if op == CONV else
                dict(attn=decoder.HEADS, sizes=sizes, pools=(0, 1)))
        out.append(decoder.Run(
            STACKS[op, dense], n,
            decoder.SWIGLU if dense else decoder.EXPERTS,
            first=cached[op], offset=stacked[op, dense], **part))
        stacked[op, dense] += n
        cached[op] += n
    return tuple(out)


def spec(config: Lfm2Config) -> decoder.Spec:
    c = config
    return decoder.Spec(
        norm=partial(decoder.rmsnorm, eps=c.norm_eps),
        attn_norm=("operator_norm",), mlp_norm=("ffn_norm",),
        final_norm=("final_norm",),
        ffn=decoder.EXPERTS, attn=decoder.HEADS,
        # (the attention runs' own too: `head_sizes`; no position table)
        rope_theta=c.rope_theta, tied_head=True,
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


def forward_trunk(params: dict, tokens: jax.Array, config: Lfm2Config,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed): the
    decoder's, less the auxiliary loss no part of this family has."""
    return _bound.forward_trunk(params, tokens, config, mesh,
                                position_offset)[0]


def forward(params: dict, tokens: jax.Array, config: Lfm2Config,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V] (the decoder's, as above)."""
    return _bound.forward(params, tokens, config, mesh, position_offset)[0]
