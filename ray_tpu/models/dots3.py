"""dots3-note family (dots-studio/dots3-note-prev's language model): RMSNorm
blocks of two kinds of multi-head latent attention (MLA) in one stack, over
sigmoid-routed top-k experts with a selection bias beside a shared expert,
behind a leading dense SwiGLU layer; untied head.

  * a FULL layer (`layer_types[i] == "full_attention"`): MLA whose normed
    latents are rescaled, made sparse by a learned indexer (DeepSeek-V3.2's
    lightning indexer: `index_n_heads` queries of `index_head_dim` against
    one key a token, the `index_topk` positions of largest score attended),
    with a sigmoid gate a head on the output;
  * a WINDOW layer (`"sliding_attention"`): a second, wider MLA (the `swa_*`
    sizes, its own rotation) over the last `sliding_window` positions, the
    token's own among them, with its own gate and no indexer.

What is the family's own: the config, the parameter format (`param_specs`,
`init_params`) and `spec`, which names the RUNS of like layers
(`decoder.Run`: each with `decoder.LATENT` at its own `LatentSizes`, its
feed-forward, its stacks and its pools) in the order of `layer_types`.
Everything that runs is the decoder's.  The stacks are held by kind:
`lead_blocks` (the leading dense layers, full attention), `full_blocks`
and `win_blocks` (the expert layers of either attention), so a kind's runs
index one stack and the expert kernel reads a layer's experts in place.
Over a paged cache a full layer leaves a latent row and an index key a
token (pools 0 and 1, the growing table), a window layer a wider latent row
(pool 2, the sliding table): `inference/kv_cache.py`.

A config may describe ONE CHIP'S SHARE of an expert-parallel deployment, as
`models/axk1.py`'s does.  Served only.  The vision and audio towers and the
MTP module of the published model are no part of this family.
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
class Dots3Config:
    vocab_size: int = 152064
    n_layers: int = 46
    d_model: int = 5120
    # One entry a layer; () is the published pattern F | F S S S | F S S S..
    layer_types: tuple = ()
    # full layers
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # window layers
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window: int = 513     # positions attended, the token's own too
    # both: the normed latents times (d_model / rank) ** 0.5, and a
    # headwise sigmoid gate on the attention's output
    mla_rescale: bool = True
    head_gate: bool = True
    first_dense_layers: int = 1   # leading layers with a dense SwiGLU
    d_ff: int = 13824             # their hidden width
    d_expert: int = 1536          # one routed (or shared) expert's width
    n_routed_experts: int = 256   # the router's outputs
    n_experts_held: int = 0       # experts that live here; 0 = all of them
    experts_offset: int = 0       # the first of them
    n_shared_experts: int = 1
    n_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    routed_scale: float = 1.0
    max_seq_len: int = 524288
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

    @property
    def held(self) -> int:
        return self.n_experts_held or self.n_routed_experts

    @property
    def kinds(self) -> tuple:
        """A layer's kind, for each of the `n_layers`."""
        if self.layer_types:
            kinds = tuple(self.layer_types)
        else:
            kinds = (FULL,) + tuple(
                FULL if i % 4 == 0 else WINDOW
                for i in range(self.n_layers - 1))
        if len(kinds) != self.n_layers or set(kinds) - {FULL, WINDOW} \
                or WINDOW in kinds[:self.first_dense_layers]:
            raise ValueError(
                f"layer_types: {self.n_layers} of {FULL!r} / {WINDOW!r}, "
                f"the leading dense layers full")
        return kinds

    def sizes(self, kind: str) -> decoder.LatentSizes:
        """What `decoder.LATENT` reads of a layer of `kind`."""
        full = kind == FULL

        def own(name):
            return getattr(self, name if full else "swa_" + name)

        def rescale(rank):
            return float((self.d_model / rank) ** 0.5) if self.mla_rescale \
                else 1.0

        return decoder.LatentSizes(
            own("n_heads"), own("q_lora_rank"), own("kv_lora_rank"),
            own("qk_nope_head_dim"), own("qk_rope_head_dim"),
            own("v_head_dim"), own("rope_theta"),
            float((own("qk_nope_head_dim") + own("qk_rope_head_dim"))
                  ** -0.5), self.norm_eps,
            q_rescale=rescale(own("q_lora_rank")),
            kv_rescale=rescale(own("kv_lora_rank")), gate=self.head_gate,
            window=0 if full else self.sliding_window,
            index_topk=self.index_topk if full else 0,
            index_n_heads=self.index_n_heads if full else 0,
            index_head_dim=self.index_head_dim if full else 0)


CONFIGS = {
    # The block at nano size, whole (tests): F | F S S S | F S S S, an
    # indexer that chooses 16 positions and a window of 9.
    "dots3-nano": Dots3Config(
        vocab_size=512, n_layers=9, d_model=64, n_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=16,
        swa_n_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=32,
        swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
        sliding_window=9, d_ff=128, d_expert=32, n_routed_experts=16,
        n_experts_per_tok=4, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32),
}
# One of four shares of it: experts 4 to 7 of 16.
CONFIGS["dots3-nano-share"] = dataclasses.replace(
    CONFIGS["dots3-nano"], n_experts_held=4, experts_offset=4)


def _counts(config: Dots3Config) -> tuple:
    """(leading dense layers, full expert layers, window layers)."""
    kinds = config.kinds[config.first_dense_layers:]
    return (config.first_dense_layers, kinds.count(FULL),
            kinds.count(WINDOW))


def _attention_specs(kind: str) -> dict:
    out = {
        "attn_norm": ("layers", "embed"),
        "w_qa": ("layers", "embed", None),
        "q_norm": ("layers", None),
        "w_qb": ("layers", None, "heads", "kv"),
        "w_kva": ("layers", "embed", None),
        "kv_norm": ("layers", None),
        "w_kvb": ("layers", None, "heads", "kv"),
        "w_head_gate": ("layers", "embed", "heads"),
        "wo": ("layers", "heads", "kv", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if kind == FULL:
        out.update({"w_iq": ("layers", None, None, None),
                    "w_ik": ("layers", "embed", None),
                    "ik_scale": ("layers", None), "ik_bias": ("layers", None),
                    "w_iw": ("layers", "embed", None)})
    return out


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


def param_specs(config: Dots3Config) -> dict:
    lead, full, win = _counts(config)
    out = {"tok_embed": ("vocab", None), "final_norm": ("embed",),
           "lm_head": ("embed", "vocab")}
    if lead:
        out["lead_blocks"] = {**_attention_specs(FULL),
                              "w_gate": ("layers", "embed", "mlp"),
                              "w_up": ("layers", "embed", "mlp"),
                              "w_down": ("layers", "mlp", "embed")}
    if full:
        out["full_blocks"] = {**_attention_specs(FULL), **_EXPERT_SPECS}
    if win:
        out["win_blocks"] = {**_attention_specs(WINDOW), **_EXPERT_SPECS}
    return out


def init_params(config: Dots3Config, key: jax.Array) -> dict:
    """Parameters in `param_dtype`, drawn as `axk1.init_params` draws them
    (float32 one slice of the leading dims at a time, normal / sqrt(fan_in),
    stored as drawn).  A matrix that multiplies a RESCALED latent (`w_qb`,
    `w_iq`, `w_kvb` under `mla_rescale`) is drawn at fan-in d_model: the
    rescale gives the latent the norm of a d_model-wide vector, so that is
    the fan-in that keeps queries, keys and values at unit variance (at the
    rank's own, the scores of a full layer have a deviation of 7 and its
    softmax is one key's: no rounding survives that, PERF.md section 6,
    PR 41).  The selection bias is drawn too (normal x 0.02: a trained one
    is not zero, and a zero one would leave the mechanism untested); the
    indexer's LayerNorm starts at scale 1, bias 0."""
    c = config
    d = c.d_model
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
    depth = 2 * c.n_layers

    def attention(n, kind):
        s = c.sizes(kind)
        h, qk = s.n_heads, s.qk_nope_head_dim + s.qk_rope_head_dim
        q_in = d if c.mla_rescale else s.q_lora_rank
        kv_in = d if c.mla_rescale else s.kv_lora_rank
        out = {
            "attn_norm": ones((n, d)),
            "w_qa": dense((n, d, s.q_lora_rank), d),
            "q_norm": ones((n, s.q_lora_rank)),
            "w_qb": dense((n, s.q_lora_rank, h, qk), q_in),
            "w_kva": dense((n, d, s.kv_lora_rank + s.qk_rope_head_dim), d),
            "kv_norm": ones((n, s.kv_lora_rank)),
            "w_kvb": dense((n, s.kv_lora_rank, h,
                            s.qk_nope_head_dim + s.v_head_dim), kv_in),
            "w_head_gate": dense((n, d, h), d),
            "wo": dense((n, h, s.v_head_dim, d), h * s.v_head_dim * depth),
            "mlp_norm": ones((n, d)),
        }
        if s.index_topk:
            hi, di = s.index_n_heads, s.index_head_dim
            out.update({
                "w_iq": dense((n, s.q_lora_rank, hi, di), q_in),
                "w_ik": dense((n, d, di), d),
                "ik_scale": jnp.ones((n, di), jnp.float32),
                "ik_bias": jnp.zeros((n, di), jnp.float32),
                "w_iw": dense((n, d, hi), d)})
        return out

    def experts(n):
        e, f, fs = c.held, c.d_expert, c.n_shared_experts * c.d_expert
        return {
            "router": dense((n, d, c.n_routed_experts), d),
            "router_bias": 0.02 * jax.random.normal(
                next(keys), (n, c.n_routed_experts), jnp.float32),
            "w_gate": dense((n, e, d, f), d, 2),
            "w_up": dense((n, e, d, f), d, 2),
            "w_down": dense((n, e, f, d), f * depth, 2),
            "ws_gate": dense((n, d, fs), d),
            "ws_up": dense((n, d, fs), d),
            "ws_down": dense((n, fs, d), fs * depth),
        }

    lead, full, win = _counts(c)
    params = {
        "tok_embed": dense((c.vocab_size, d), 2500.0, 0),
        "final_norm": ones((d,)),
        "lm_head": dense((d, c.vocab_size), d, 0),
    }
    if lead:
        params["lead_blocks"] = {
            **attention(lead, FULL),
            "w_gate": dense((lead, d, c.d_ff), d),
            "w_up": dense((lead, d, c.d_ff), d),
            "w_down": dense((lead, c.d_ff, d), c.d_ff * depth)}
    if full:
        params["full_blocks"] = {**attention(full, FULL), **experts(full)}
    if win:
        params["win_blocks"] = {**attention(win, WINDOW), **experts(win)}
    return params


def runs(config: Dots3Config) -> tuple:
    """The stack's runs of like layers in order.  Full layers (dense or
    expert) share pools 0 and 1 and the first half of the block tables,
    window layers pool 2 and the second half."""
    c = config
    ffn = decoder.SHARED_EXPERTS if c.n_shared_experts else decoder.EXPERTS
    kinds = [("lead_blocks" if i < c.first_dense_layers else
              "full_blocks" if kind == FULL else "win_blocks", kind)
             for i, kind in enumerate(c.kinds)]
    stacked = dict.fromkeys(("lead_blocks", "full_blocks", "win_blocks"), 0)
    cached = {FULL: 0, WINDOW: 0}
    out = []
    for (blocks, kind), group in itertools.groupby(kinds):
        n = len(list(group))
        out.append(decoder.Run(
            blocks, n, decoder.SWIGLU if blocks == "lead_blocks" else ffn,
            decoder.LATENT, first=cached[kind], offset=stacked[blocks],
            sizes=c.sizes(kind),
            pools=(0, 1) if kind == FULL else (2,),
            table=(0, 2) if kind == FULL else (1, 2)))
        stacked[blocks] += n
        cached[kind] += n
    return tuple(out)


def spec(config: Dots3Config) -> decoder.Spec:
    c = config
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
        attn_scale=c.sizes(FULL).attn_scale, runs=runs(c),
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


def forward_trunk(params: dict, tokens: jax.Array, config: Dots3Config,
                  mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> hidden states [B, L, D] (pre-head, normed): the
    decoder's, less the auxiliary loss no part of this family has."""
    return _bound.forward_trunk(params, tokens, config, mesh,
                                position_offset)[0]


def forward(params: dict, tokens: jax.Array, config: Dots3Config,
            mesh=None, position_offset=0) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V] (the decoder's, as above)."""
    return _bound.forward(params, tokens, config, mesh, position_offset)[0]
