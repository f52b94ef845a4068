"""Mellum family (JetBrains/Mellum2-12B-A2.5B: `model_type` mellum): RMSNorm
blocks of a grouped-query attention of two kinds in one stack, each over
softmax-routed top-k experts with no shared expert; untied head.

  * a WINDOW layer (`layer_types[i] == "sliding_attention"`): q and k
    rotated by theta's own frequencies, causal attention over the last
    `sliding_window` positions (the token's own among them);
  * a FULL layer (`"full_attention"`): q and k rotated by YaRN's blended
    frequencies (`decoder.yarn_freqs`: static, the same at every length)
    with the cosines and sines times `attention_factor`, over the whole
    context.

Every layer's feed-forward is the experts: a router over all
`n_routed_experts` (softmax, float32), the top `n_experts_per_tok`, their
probabilities divided by their sum.  A chip may hold a share of a layer's
experts (`n_experts_held` from `experts_offset`: one chip of an
expert-parallel deployment, without its exchange): the router still
chooses among all of them, what falls on experts held elsewhere adds
nothing here, and the router's gradient through the held experts'
weights is the rank's own part of a sum over ranks, kept whole
(`decoder.moe_ffn`).

What is the family's own: the config, the parameter format (`param_specs`,
`init_params`) and `spec`, which names the RUNS of like layers
(`decoder.Run`: each with `decoder.HEADS` at its own `decoder.HeadSizes`)
in the order of `layer_types`.  Everything that runs is the decoder's: it
trains (`make_train_step`: the flash kernels take the window, the grouped
multiply has its backward pass, the router's balancing loss joins the
loss) and it serves over a paged cache, a full layer's K and V rows in
pools 0 and 1 (the growing table), a window layer's in pools 2 and 3 (the
sliding table): `inference/kv_cache.py`.
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

FULL, WINDOW = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    n_layers: int = 28
    d_model: int = 2304
    # One entry a layer; () is the published pattern S S S F repeated.
    layer_types: tuple = ()
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 500000.0      # both kinds of layer
    sliding_window: int = 1024        # positions attended, the token's own too
    # YaRN, the full layers' `rope_parameters`
    rope_factor: float = 16.0
    rope_original: int = 8192         # `original_max_position_embeddings`
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782
    d_expert: int = 896               # `moe_intermediate_size`
    n_routed_experts: int = 64        # the router's width
    n_experts_held: int = 0           # 0: all of them
    experts_offset: int = 0           # the first expert held
    n_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "softmax"
    routed_scale: float = 1.0
    max_seq_len: int = 131072
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = False
    scan_unroll: int = 1
    param_dtype: Any = jnp.float32    # a dtype or its name ("float32")

    def __post_init__(self):
        # (a configuration file gives a list; the config is a jit's static
        # argument)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))

    @property
    def n_experts(self) -> int:
        """The router's width, as `decoder.moe_ffn` reads it."""
        return self.n_routed_experts

    @property
    def held(self) -> int:
        return self.n_experts_held or self.n_routed_experts

    @property
    def kinds(self) -> tuple:
        """A layer's kind, for each of the `n_layers`."""
        kinds = tuple(self.layer_types) or tuple(
            FULL if i % 4 == 3 else WINDOW for i in range(self.n_layers))
        if len(kinds) != self.n_layers or set(kinds) - {FULL, WINDOW}:
            raise ValueError(
                f"layer_types: {self.n_layers} of {FULL!r} / {WINDOW!r}")
        return kinds

    def sizes(self, kind: str) -> decoder.HeadSizes:
        """What `decoder.HEADS` reads of a layer of `kind`: the window is
        the window layers', YaRN's frequencies and factor the full
        layers'."""
        if kind == WINDOW:
            return decoder.HeadSizes(
                self.n_heads, self.n_kv_heads, self.head_dim,
                rope_theta=self.rope_theta, window=self.sliding_window)
        freqs = decoder.yarn_freqs(
            self.head_dim, self.rope_theta, self.rope_factor,
            self.rope_original, self.rope_beta_fast, self.rope_beta_slow)
        return decoder.HeadSizes(
            self.n_heads, self.n_kv_heads, self.head_dim,
            rope_theta=self.rope_theta,
            rope_freqs=tuple(float(f) for f in freqs),
            rope_scale=self.attention_factor)


CONFIGS = {
    # One period at nano size (tests): S S S F over 16 experts, top-4, a
    # window of 9; YaRN's ramp over an original length of 32 at a factor
    # of 4, whose `attention_factor` is 0.1 ln 4 + 1.
    "mellum-nano": MellumConfig(
        vocab_size=512, n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, sliding_window=9, rope_theta=10000.0, rope_factor=4.0,
        rope_original=32, attention_factor=1.1386294361119891, d_expert=32,
        n_routed_experts=16,
        n_experts_per_tok=4, max_seq_len=256, dtype=jnp.float32),
}


def param_specs(config: MellumConfig) -> dict:
    return {
        "tok_embed": ("vocab", None), "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
        "blocks": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "kv"),
            "wk": ("layers", "embed", "kv_heads", "kv"),
            "wv": ("layers", "embed", "kv_heads", "kv"),
            "wo": ("layers", "heads", "kv", "embed"),
            "mlp_norm": ("layers", "embed"),
            "router": ("layers", "embed", "experts"),
            "w_gate": ("layers", "experts", "embed", "expert_mlp"),
            "w_up": ("layers", "experts", "embed", "expert_mlp"),
            "w_down": ("layers", "experts", "expert_mlp", "embed"),
        }}


def init_params(config: MellumConfig, key: jax.Array) -> dict:
    """Parameters in `param_dtype`, drawn float32 one slice of the leading
    dims at a time (`axk1.init_params`'s way: no draw is larger than one
    matrix), normal / sqrt(fan_in); the matrices that write into the
    residual stream (`wo`, `w_down`) further by 1 / sqrt(2 n_layers), as
    `gpt.init_params` has them; the embedding at 0.02; every norm's scale
    1.  A share's experts are drawn from their own index among all
    `n_routed_experts` (the key is split over all of them), so the shares
    of one seed are the slices of the whole layer's."""
    c = config
    d, n = c.d_model, c.n_layers
    pd = jnp.dtype(c.param_dtype)
    keys = iter(jax.random.split(key, 16))

    def draw(ks, shape, std):        # one matrix a key
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape, jnp.float32)
                       * std).astype(pd), ks)

    def dense(shape, fan_in, lead=1, scale=1.0):
        ks = jax.random.split(next(keys), int(np.prod(shape[:lead])))
        return draw(ks, shape[lead:], scale / np.sqrt(fan_in)).reshape(shape)

    def experts(shape, fan_in, scale=1.0):
        """[layers, held, ...] of the draw for [layers, all, ...]."""
        ks = jax.random.split(next(keys), n * c.n_routed_experts).reshape(
            n, -1)[:, c.experts_offset:c.experts_offset + c.held]
        return draw(ks.reshape(-1), shape, scale / np.sqrt(fan_in)).reshape(
            (n, c.held) + shape)

    ones = partial(jnp.ones, dtype=pd)
    h, kh, hd, f = c.n_heads, c.n_kv_heads, c.head_dim, c.d_expert
    out_scale = 1.0 / np.sqrt(2 * n)
    return {
        "tok_embed": dense((c.vocab_size, d), 1.0, 0, 0.02),
        "final_norm": ones((d,)),
        "lm_head": dense((d, c.vocab_size), d, 0),
        "blocks": {
            "attn_norm": ones((n, d)),
            "wq": dense((n, d, h, hd), d),
            "wk": dense((n, d, kh, hd), d),
            "wv": dense((n, d, kh, hd), d),
            "wo": dense((n, h, hd, d), h * hd, scale=out_scale),
            "mlp_norm": ones((n, d)),
            "router": dense((n, d, c.n_routed_experts), d),
            "w_gate": experts((d, f), d),
            "w_up": experts((d, f), d),
            "w_down": experts((f, d), f, out_scale),
        }}


def runs(config: MellumConfig) -> tuple:
    """The stack's runs of like layers in order.  Full layers share pools
    0 and 1 and the first half of the block tables, window layers pools 2
    and 3 and the second half; a model of one kind alone keeps the one
    pair and the whole table."""
    c = config
    both = len(set(c.kinds)) > 1
    stacked, cached, out = 0, {FULL: 0, WINDOW: 0}, []
    for kind, group in itertools.groupby(c.kinds):
        n = len(list(group))
        window = kind == WINDOW
        out.append(decoder.Run(
            "blocks", n, decoder.EXPERTS, decoder.HEADS, first=cached[kind],
            offset=stacked, sizes=c.sizes(kind),
            pools=((2, 3) if window else (0, 1)) if both else None,
            table=((1, 2) if window else (0, 2)) if both else None))
        stacked += n
        cached[kind] += n
    return tuple(out)


def spec(config: MellumConfig) -> decoder.Spec:
    c = config
    return decoder.Spec(
        norm=partial(decoder.rmsnorm, eps=c.norm_eps),
        attn_norm=("attn_norm",), mlp_norm=("mlp_norm",),
        final_norm=("final_norm",), attn=decoder.HEADS, ffn=decoder.EXPERTS,
        rope_theta=c.rope_theta, runs=runs(c),
        init_params=init_params, param_specs=param_specs)


# The decoder bound to `spec` (signatures and docs: models/decoder.py,
# less its first argument).
_bound = decoder.bind(spec)
forward_trunk = _bound.forward_trunk
forward = _bound.forward
lm_head = _bound.lm_head
forward_cached = _bound.forward_cached
loss_fn = _bound.loss_fn
loss_and_metrics = _bound.loss_and_metrics
serving_params = _bound.serving_params
shard_params = _bound.shard_params
num_params = _bound.num_params
make_train_step = _bound.make_train_step
