"""AFMoE family (arcee-ai/Trinity-Mini, Trinity-Nano: `model_type` afmoe):
RMSNorm blocks with a norm on either side of each part (FOUR a layer), a
gated grouped-query attention of two kinds in one stack over sigmoid-routed
top-k experts with a selection bias beside a shared expert, behind leading
dense SwiGLU layers; the embedding times sqrt(d_model); untied head.

  * a WINDOW layer (`layer_types[i] == "sliding_attention"`): q and k each
    RMS-normalised over its own head's numbers, ROTATED, causal attention
    over the last `sliding_window` positions (the token's own among them),
    the result times sigmoid(h W_g) elementwise before the output product;
  * a FULL layer (`"full_attention"`): the same with NO positional encoding
    at all (no rotation: causal order alone) over the whole context.

What is the family's own: the config, the parameter format (`param_specs`,
`init_params`) and `spec`, which names the RUNS of like layers
(`decoder.Run`: each with `decoder.HEADS` at its own `decoder.HeadSizes`,
its feed-forward, its stack and its pools) in the order of `layer_types`.
Everything that runs is the decoder's.  The stacks are `lead_blocks` (the
leading dense layers) and `blocks` (the expert layers, in order: the two
kinds of attention have the same leaves).  Over a paged cache a full layer
leaves a K and a V row a token in pools 0 and 1 (the growing table), a
window layer in pools 2 and 3 (the sliding table): `inference/kv_cache.py`.

It serves and it trains: the flash kernels take the window, the grouped
multiply has a backward pass, the routers' balancing loss joins the loss.
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
class AfmoeConfig:
    vocab_size: int = 200192
    n_layers: int = 32
    d_model: int = 2048
    # One entry a layer; () is the published pattern S S S F repeated.
    layer_types: tuple = ()
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10000.0   # window layers only
    sliding_window: int = 2048    # positions attended, the token's own too
    first_dense_layers: int = 2   # leading layers with a dense SwiGLU
    d_ff: int = 6144              # their hidden width
    d_expert: int = 1024          # one routed (or shared) expert's width
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    n_experts_per_tok: int = 8
    norm_topk_prob: bool = True   # `route_norm`
    scoring_func: str = "sigmoid"
    routed_scale: float = 2.826   # `route_scale`
    embed_scale: bool = True      # `mup_enabled`: embedding x sqrt(d_model)
    max_seq_len: int = 131072
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    scan_unroll: int = 1
    param_dtype: Any = jnp.bfloat16   # a dtype or its name ("bfloat16")

    def __post_init__(self):
        # (a configuration file gives a list; the config is a jit's static
        # argument)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))

    @property
    def n_experts(self) -> int:
        """The router's width, as `decoder.moe_ffn` reads it."""
        return self.n_routed_experts

    experts_offset = 0      # every expert of a layer lives here

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
        """What `decoder.HEADS` reads of a layer of `kind`: the rotation
        and the window are the window layers' alone."""
        window = kind == WINDOW
        return decoder.HeadSizes(
            self.n_heads, self.n_kv_heads, self.head_dim,
            rope_theta=self.rope_theta if window else None,
            window=self.sliding_window if window else 0,
            qk_norm=self.norm_eps, gate=True)


CONFIGS = {
    # The block at nano size, whole (tests): dense S S, then S F S S S F
    # over experts, a window of 9.
    "afmoe-nano": AfmoeConfig(
        vocab_size=512, n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, sliding_window=9, d_ff=128, d_expert=32,
        n_routed_experts=16, n_experts_per_tok=4, max_seq_len=256,
        dtype=jnp.float32, param_dtype=jnp.float32),
}


def _counts(config: AfmoeConfig) -> tuple:
    """(leading dense layers, expert layers)."""
    return (config.first_dense_layers,
            config.n_layers - config.first_dense_layers)


_ATTENTION_SPECS = {
    "attn_norm": ("layers", "embed"),
    "wq": ("layers", "embed", "heads", "kv"),
    "wk": ("layers", "embed", "kv_heads", "kv"),
    "wv": ("layers", "embed", "kv_heads", "kv"),
    "q_norm": ("layers", None),
    "k_norm": ("layers", None),
    "w_attn_gate": ("layers", "embed", "heads", "kv"),
    "wo": ("layers", "heads", "kv", "embed"),
    "attn_post_norm": ("layers", "embed"),
    "mlp_norm": ("layers", "embed"),
    "mlp_post_norm": ("layers", "embed"),
}

_EXPERT_SPECS = {
    "router": ("layers", "embed", "experts"),
    "router_bias": ("layers", "experts"),
    "w_gate": ("layers", "experts", "embed", "expert_mlp"),
    "w_up": ("layers", "experts", "embed", "expert_mlp"),
    "w_down": ("layers", "experts", "expert_mlp", "embed"),
    "ws_gate": ("layers", "embed", "mlp"),
    "ws_up": ("layers", "embed", "mlp"),
    "ws_down": ("layers", "mlp", "embed"),
}


def param_specs(config: AfmoeConfig) -> dict:
    lead, rest = _counts(config)
    out = {"tok_embed": ("vocab", None), "final_norm": ("embed",),
           "lm_head": ("embed", "vocab")}
    if lead:
        out["lead_blocks"] = {**_ATTENTION_SPECS,
                              "w_gate": ("layers", "embed", "mlp"),
                              "w_up": ("layers", "embed", "mlp"),
                              "w_down": ("layers", "mlp", "embed")}
    if rest:
        out["blocks"] = {**_ATTENTION_SPECS, **_EXPERT_SPECS}
    return out


def init_params(config: AfmoeConfig, key: jax.Array) -> dict:
    """Parameters in `param_dtype`, drawn as `axk1.init_params` draws them
    (float32 one slice of the leading dims at a time, normal / sqrt(fan_in),
    stored as drawn).  Every part's result passes a norm of its own before
    it joins the stream, so what a part adds has the size of that norm's
    scale whatever its matrices' (the argument of `falconh1.init_params`,
    the other way round: here no draw can make a part small).  The scales
    are ones and the embedding is drawn at 1 / sqrt(d_model), so that times
    its factor sqrt(d_model) a token's row has the size of one part's
    addition: the token, the attentions and the feed-forwards all stand in
    the stream at one order, and a part left out or a norm misplaced moves
    the logits by its whole size.  The selection bias is drawn too (normal
    x 0.02: a trained one is not zero, and a zero one would leave the
    choice and the weights the same thing)."""
    c = config
    d = c.d_model
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

    def attention(n):
        return {
            "attn_norm": ones((n, d)),
            "wq": dense((n, d, h, hd), d),
            "wk": dense((n, d, kh, hd), d),
            "wv": dense((n, d, kh, hd), d),
            "q_norm": ones((n, hd)),
            "k_norm": ones((n, hd)),
            "w_attn_gate": dense((n, d, h, hd), d),
            "wo": dense((n, h, hd, d), h * hd),
            "attn_post_norm": ones((n, d)),
            "mlp_norm": ones((n, d)),
            "mlp_post_norm": ones((n, d)),
        }

    def experts(n):
        e, f, fs = c.n_routed_experts, c.d_expert, \
            c.n_shared_experts * c.d_expert
        return {
            "router": dense((n, d, e), d),
            "router_bias": 0.02 * jax.random.normal(
                next(keys), (n, e), jnp.float32),
            "w_gate": dense((n, e, d, f), d, 2),
            "w_up": dense((n, e, d, f), d, 2),
            "w_down": dense((n, e, f, d), f, 2),
            "ws_gate": dense((n, d, fs), d),
            "ws_up": dense((n, d, fs), d),
            "ws_down": dense((n, fs, d), fs),
        }

    lead, rest = _counts(c)
    params = {
        "tok_embed": dense((c.vocab_size, d),
                           d if c.embed_scale else 1.0, 0),
        "final_norm": ones((d,)),
        "lm_head": dense((d, c.vocab_size), d, 0),
    }
    if lead:
        params["lead_blocks"] = {
            **attention(lead),
            "w_gate": dense((lead, d, c.d_ff), d),
            "w_up": dense((lead, d, c.d_ff), d),
            "w_down": dense((lead, c.d_ff, d), c.d_ff)}
    if rest:
        params["blocks"] = {**attention(rest), **experts(rest)}
    return params


def _ffn(config: AfmoeConfig) -> decoder.FeedForward:
    return (decoder.SHARED_EXPERTS if config.n_shared_experts
            else decoder.EXPERTS)


def runs(config: AfmoeConfig) -> tuple:
    """The stack's runs of like layers in order.  Full layers share pools
    0 and 1 and the first half of the block tables, window layers pools 2
    and 3 and the second half; a model of one kind alone keeps the one
    pair and the whole table."""
    c = config
    kinds = [("lead_blocks" if i < c.first_dense_layers else "blocks", kind)
             for i, kind in enumerate(c.kinds)]
    both = len(set(c.kinds)) > 1
    stacked = dict.fromkeys(("lead_blocks", "blocks"), 0)
    cached = {FULL: 0, WINDOW: 0}
    out = []
    for (blocks, kind), group in itertools.groupby(kinds):
        n = len(list(group))
        window = kind == WINDOW
        out.append(decoder.Run(
            blocks, n, decoder.SWIGLU if blocks == "lead_blocks" else _ffn(c),
            decoder.HEADS,
            first=cached[kind], offset=stacked[blocks], sizes=c.sizes(kind),
            pools=((2, 3) if window else (0, 1)) if both else None,
            table=((1, 2) if window else (0, 2)) if both else None))
        stacked[blocks] += n
        cached[kind] += n
    return tuple(out)


def spec(config: AfmoeConfig) -> decoder.Spec:
    c = config
    return decoder.Spec(
        norm=partial(decoder.rmsnorm, eps=c.norm_eps),
        attn_norm=("attn_norm",), mlp_norm=("mlp_norm",),
        attn_post_norm=("attn_post_norm",),
        mlp_post_norm=("mlp_post_norm",), final_norm=("final_norm",),
        attn=decoder.HEADS, ffn=_ffn(c),
        first_dense_layers=c.first_dense_layers,
        lead_ffn=decoder.SWIGLU if c.first_dense_layers else None,
        # (the window runs' own; a full run's `HeadSizes` has none)
        rope_theta=c.rope_theta, runs=runs(c),
        mult=(decoder.Multipliers(embedding=float(np.sqrt(c.d_model)))
              if c.embed_scale else None),
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


def forward_trunk(params: dict, tokens: jax.Array, config: AfmoeConfig,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed): the
    decoder's, less the routers' balancing loss."""
    return _bound.forward_trunk(params, tokens, config, mesh,
                                position_offset)[0]


def forward(params: dict, tokens: jax.Array, config: AfmoeConfig,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V] (the decoder's, as above)."""
    return _bound.forward(params, tokens, config, mesh, position_offset)[0]
