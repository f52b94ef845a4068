"""The decoder-only transformer, defined once for every LM family.

A family (models/gpt.py, models/llama.py, models/axk1.py,
models/evabyte.py, models/dots3.py, models/falconh1.py,
models/nemotronh.py, models/afmoe.py, models/lfm2.py,
models/kimilinear.py, models/mellum.py) is a config
dataclass, its parameter format (`init_params`, `param_specs`) and
`spec(config)`: a `Spec` naming the parts its block is made of (norms: one
in front of each part, and where the model has them one behind each too,
four a layer; an
`Attention`, a `FeedForward`, a leading run of layers with another
feed-forward; or its `Run`s of like layers, each with its own attention,
sizes, stacks and pools) and the leaves they read.  Everything that runs is here: the
training block and the block over a paged cache, the two layer scans, the
head, the loss, the form the weights are served in and the train step.
The public functions take the family's `spec` function first; a family
module exports them bound to it (`bind`).

Design (no reference counterpart: Ray hosts models, it doesn't ship them):
  * pure functional: params are a pytree, forward is a jittable function
    (plays directly with pjit/GSPMD and donation);
  * layers are STACKED on a leading dim and applied with `lax.scan`: one
    compiled block regardless of depth (fast compiles, small HLO).  The
    train path scans over the stacks; a served layer (the loops over a
    paged cache) takes its matrices out of the stacks by its own index,
    so each is read once, where it is held (`_layer_of`);
  * every param leaf has a logical sharding spec (parallel.sharding rules
    decide DP/FSDP/TP placement; "kv_heads" shards GQA kv projections);
  * attention is a part (`HEADS`: per-head K and V, flash (Pallas) on one
    chip and per shard (shard_map over batch and heads) under a mesh, ring
    attention when the mesh has a seq axis > 1; over a paged KV cache,
    ops/attention.py's paged path; by a run's `HeadSizes` with a rotation
    of the run's own or none (YaRN's frequencies and factor among them),
    a window a run (the flash kernels take it; over a cache the rows
    behind it are neither kept nor read: a sliding table), an RMSNorm over
    each head's q and k and an elementwise gate on the result.  `LATENT`:
    multi-head latent attention,
    expanded for a whole sequence, absorbed over a latent paged cache; by
    a run's `LatentSizes` also over a window, or over the positions a
    learned indexer chooses, with a gate a head.
    `EVA`: an exact window beside one summary row for every chunk behind
    it, `HEADS`'s cached form over a table whose rows are not one a token);
  * a run of layers may have a second mixer BESIDE its attention (`Mixer`;
    `SSM`: Mamba-2's state-space mixer, `KDA`: Kimi Delta Attention, a
    state decayed a key channel and corrected by a delta rule, both
    ops/ssm.py): both read the block's
    one normed input and both results are added to the residual stream,
    each path by its own factor where the model states them
    (`Multipliers`).  Over a cache the mixer's state is not rows: a
    fixed-size slot a lane, overwritten by every step;
  * a run's layers may be ONE part alone: the mixer with no attention, the
    attention with no feed-forward behind it, or the feed-forward with
    nothing in front (`Run.attn`, `Run.ffn`, `Run.mixer` None), each behind
    its one norm; or the mixer in the attention's PLACE, in front of a
    feed-forward (`CONV`: LFM2's gated short convolution, whose lane state
    is its convolution's tail and no recurrence; `KDA` likewise, with its
    recurrent state); a stack is then its runs
    in order, the runs of a kind sharing one stack of leaves and one part
    of the cache;
  * `jax.checkpoint` (remat) on the block when configured: trades FLOPs for
    HBM, the standard TPU memory lever.

A new architecture is a new `Spec` over these parts, or a new part; a
choice is made from the spec, never from a family's name.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import types
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import mesh_flash_attention
from ray_tpu.parallel.sharding import tree_shardings, with_logical_constraint


# --------------------------------------------------------------------------
# Parts: norms and positions
# --------------------------------------------------------------------------

def layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def rmsnorm(x, scale, eps, unit_offset: bool = False, dtype=None):
    """x / rms(x) * scale in float32, back in x's dtype (or `dtype`: a
    float32 residual stream normed into the matrices' dtype); with
    `unit_offset` the learned scale is stored less one."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    if unit_offset:
        scale = 1.0 + scale.astype(jnp.float32)
    return (y * scale).astype(dtype or x.dtype)


def _scaled(x, factor):
    """x times a model's stated factor, in x's dtype (1: x itself)."""
    return x if factor == 1.0 else x * jnp.asarray(factor, x.dtype)


def rope(x, theta: float, offset=0, freqs=None, scale: float = 1.0):
    """Rotary position embedding over [B, L, H, K] (rotate-half pairing:
    the head dim splits into two halves treated as (real, imag)).

    `offset` is the absolute position of x's first token: a scalar shared
    by the batch, or a per-lane [B] array (cached decode: lanes sit at
    different depths).  `freqs` [K / 2] replaces theta's own frequencies
    (`yarn_freqs`); `scale` multiplies the cosines and sines (YaRN's
    `attention_factor`: on q and on k, so on the scores its square)."""
    b, l, h, k = x.shape
    half = k // 2
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(freqs, jnp.float32)
    off = jnp.asarray(offset, jnp.float32)
    pos = off[..., None] + jnp.arange(l, dtype=jnp.float32)  # [L] or [B, L]
    ang = pos[..., None] * freqs                      # [L, half] / [B, L, half]
    if ang.ndim == 2:
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_freqs(dim: int, theta: float, factor: float, original: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies [dim / 2]: theta's own where a dimension
    turns more than `beta_fast` times over the `original` positions, those
    divided by `factor` where it turns less than `beta_slow` times, and a
    linear ramp between the two over the dimensions in between."""
    own = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (own / factor * ramp + own * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


# --------------------------------------------------------------------------
# Parts: feed-forwards.  `apply(h, p, config, mesh=None, valid=None)` on
# normed h [B, L, D] with the layer's leaves `p` returns (y [B, L, D], the
# layer's auxiliary loss or None, its expert load [E] or None).
# --------------------------------------------------------------------------

def _down(hidden, p):
    """The down projection, from `w_down_t` where the tree holds that
    form (serving_params)."""
    if "w_down_t" in p:
        return jnp.einsum("blf,df->bld", hidden, p["w_down_t"])
    return jnp.einsum("blf,fd->bld", hidden, p["w_down"].astype(hidden.dtype))


def gelu_mlp(h, p, config, mesh=None, valid=None):
    hidden = jax.nn.gelu(
        jnp.einsum("bld,df->blf", h, p["w_up"].astype(h.dtype)))
    hidden = with_logical_constraint(hidden, ("batch", "length", "mlp"),
                                     mesh=mesh)
    return _down(hidden, p), None, None


def swiglu_mlp(h, p, config, mesh=None, valid=None):
    gate = jax.nn.silu(jnp.einsum("bld,df->blf", h,
                                  p["w_gate"].astype(h.dtype)))
    up = jnp.einsum("bld,df->blf", h, p["w_up"].astype(h.dtype))
    hidden = with_logical_constraint(gate * up, ("batch", "length", "mlp"),
                                     mesh=mesh)
    return _down(hidden, p), None, None


def scaled_swiglu_mlp(h, p, config, mesh=None, valid=None):
    """`swiglu_mlp` with the two factors a model states on it
    (`config.mlp_multipliers`): one on the gate's projection, inside the
    SiLU, one on the result."""
    m_gate, m_down = config.mlp_multipliers
    gate = jnp.einsum("bld,df->blf", h, p["w_gate"].astype(h.dtype))
    gate = jax.nn.silu(gate * jnp.asarray(m_gate, gate.dtype))
    up = jnp.einsum("bld,df->blf", h, p["w_up"].astype(h.dtype))
    hidden = with_logical_constraint(gate * up, ("batch", "length", "mlp"),
                                     mesh=mesh)
    y = _down(hidden, p)
    return y * jnp.asarray(m_down, y.dtype), None, None


# `moe_ffn`: whole sequences of this many tokens and more take the experts'
# rows back to their tokens through `ops.moe.moe_combine`, this many tokens
# a work item (`scripts/moe_train_time.py combine`: of tiles of 128, 256 and
# 512 tokens, 256 was fastest over the rows the train cell's layers hold at
# 16,384 tokens, and at every count down to 256).
COMBINE_FROM, COMBINE_TILE = 4096, 256


def moe_ffn(h, p, config, mesh=None, valid=None):
    """Dropless top-k experts: a router over all `config.n_experts`, top-k,
    dropless dispatch (ops/moe.py).  Router product, scores and top-k run
    in float32 (the eighth expert is often chosen by a fourth decimal).
    `config.scoring_func` is "softmax" over the experts or "sigmoid" of
    each; the chosen scores weight the experts as they are unless
    `config.norm_topk_prob` (then they sum to one: divided by their sum,
    plus `config.norm_topk_eps` where the family states one), times
    `config.routed_scale`.  `p` holds the layer's router [D, E] and the
    experts of ALL layers with the index `layer` (the kernel reads them in
    place), and where the router has one its `router_bias` [E].

    The experts may be a share of the router's: `p` then holds experts
    `config.experts_offset` to `experts_offset + held` (an expert-parallel
    deployment's chip).  The router still chooses among all E, this layer
    computes the assignments that fall on its own, and the rest add
    nothing here: they are another chip's part of the sum.  The load is
    the assignments each held expert took.

    An expert is a SwiGLU over `w_gate`, `w_up`, `w_down`; where the layer
    has no `w_gate` it is `w_down relu(w_up x)^2` over two
    (`ops.moe.expert_ffn`).  The up matrix may be held as published,
    [E, F, D] under the name `w_up_t`: what an expert width that is no
    multiple of 128 needs (`ops.moe.grouped_matmul`).

    The auxiliary loss is the router's balancing loss, E sum_e f_e P_e
    over all E: f_e the share of the step's T x k assignments that chose
    e, P_e the mean over the tokens of e's score as a share of a token's
    scores (the softmax's probability), float32; 1 where both are even.

    A share's router hears the task through the experts held here alone
    (the rank's own part of a gradient that a deployment sums over its
    ranks): nothing is cut from it, and with no other rank present it
    draws tokens to this chip (PERF.md 6, PR 61).

    Without `valid` the rows are whole sequences' (the train path): a
    share's sorted rows are then held to twice the rows its experts
    expect, T k held / E (`ops.moe.expert_ffn(rows=)`: a bound on buffers
    that routing may pass at the cost of time, never of an assignment),
    and where an expert expects 512 rows or more the grouped products take
    a row tile of 512: a tile of 128 rows reads its expert's [K, N] once
    for 112 flops a byte, under the chip's 240.  From `COMBINE_FROM` tokens
    on, the experts' rows go back to their tokens through
    `ops.moe.moe_combine` (`token_tile`): at 16,384 tokens x 8 the kernel
    takes 1.6-1.9 ms a call where the gather of every assignment's row
    takes 6.3-7.2, at 4,096 and under they are level (PERF.md 6, PR 62),
    and a page behind the first costs its own rows, not a gather over all
    T k assignments.  A served iteration (`valid` given:
    16-128 tokens, a few thousand with admissions) keeps the gather."""
    from ray_tpu.ops import moe

    c = config
    b, l, d = h.shape
    x, scores = _scores(h, p, c)
    experts, weights = _choose(scores, p, c)
    held_t = "w_up_t" in p
    up = p["w_up_t" if held_t else "w_up"]
    held = p.get("w_gate", up).shape[-3]
    share = held < c.n_experts
    t, k = experts.shape
    sized = {}
    if valid is None:
        tile = sized["block_m"] = 512 if t * k // c.n_experts >= 512 else 128
        if share:
            sized["rows"] = -(-2 * t * k * held // c.n_experts // tile) * tile
        if t >= COMBINE_FROM:
            sized["token_tile"] = COMBINE_TILE
    y, load = moe.expert_ffn(
        x, experts, weights, p.get("w_gate"), up, p["w_down"],
        p["layer"], None if valid is None else valid.reshape(-1),
        first_held=c.experts_offset if share else None,
        up_transposed=held_t, **sized)
    return y.reshape(b, l, d), _balance(scores, experts, valid), load


def _balance(scores, experts, valid=None):
    """The router's balancing loss of scores [T, E] and the chosen experts
    [T, k] (`moe_ffn`); tokens that `valid` [..] masks count for
    nothing."""
    e = scores.shape[-1]
    share = scores / jnp.sum(scores, -1, keepdims=True)
    chose = jnp.sum(experts[..., None] == jnp.arange(e), 1,
                    dtype=jnp.float32)                          # [T, E]
    if valid is not None:
        keep = valid.reshape(-1, 1).astype(jnp.float32)
        share, chose = share * keep, chose * keep
        n = jnp.maximum(jnp.sum(keep), 1.0)
    else:
        n = scores.shape[0]
    f = jnp.sum(chose, 0) / (n * experts.shape[1])
    return e * jnp.sum(f * jnp.sum(share, 0) / n)


def _route(h, p, config):
    """`moe_ffn`'s router: (x [T, D], each token's chosen experts [T, k],
    what each counts for [T, k] float32)."""
    x, scores = _scores(h, p, config)
    return (x, *_choose(scores, p, config))


def _scores(h, p, config):
    """(x [T, D], every expert's score [T, E] float32)."""
    x = h.reshape(-1, h.shape[-1])
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return x, (jax.nn.sigmoid(logits) if config.scoring_func == "sigmoid"
               else jax.nn.softmax(logits, axis=-1))


def _choose(scores, p, config):
    c = config
    if "router_bias" in p:
        # A selection bias (DeepSeek-V3's `noaux_tc`): added to the scores
        # for the choice only, the weights are the unbiased scores.
        _, experts = jax.lax.top_k(scores + p["router_bias"],
                                   c.n_experts_per_tok)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        weights, experts = jax.lax.top_k(scores, c.n_experts_per_tok)
    if c.norm_topk_prob:
        total = jnp.sum(weights, -1, keepdims=True)
        # (a family that states an epsilon under the sum: LFM2's 1e-6)
        eps = getattr(c, "norm_topk_eps", 0.0)
        weights = weights / (total + eps if eps else total)
    if c.routed_scale != 1.0:
        weights = weights * c.routed_scale
    return experts, weights


def shared_moe_ffn(h, p, config, mesh=None, valid=None):
    """`moe_ffn` beside a shared expert every token passes through: a dense
    SwiGLU (`ws_gate`, `ws_up`, `ws_down`) whose result is added as it
    is."""
    y, aux, load = moe_ffn(h, p, config, mesh, valid)
    gate = jax.nn.silu(jnp.einsum("bld,df->blf", h,
                                  p["ws_gate"].astype(h.dtype)))
    up = jnp.einsum("bld,df->blf", h, p["ws_up"].astype(h.dtype))
    shared = jnp.einsum("blf,fd->bld", gate * up,
                        p["ws_down"].astype(h.dtype))
    return shared + y, aux, load


def shared_relu2_moe_ffn(h, p, config, mesh=None, valid=None):
    """`moe_ffn` over two-matrix squared-ReLU experts (no `w_gate`; the up
    matrix held [E, F, D], `w_up_t`) beside a shared expert of the same
    form every token passes through (`ws_up`, `ws_down`), added as it
    is."""
    y, aux, load = moe_ffn(h, p, config, mesh, valid)
    hidden = jnp.square(jax.nn.relu(
        jnp.einsum("bld,df->blf", h, p["ws_up"].astype(h.dtype))))
    shared = jnp.einsum("blf,fd->bld", hidden, p["ws_down"].astype(h.dtype))
    return shared + y, aux, load


def switch_moe(h, p, config, mesh=None, valid=None):
    """Switch-style top-1 experts with dense dispatch (einsum one-hot masks:
    static shapes, XLA-friendly; no sort/scatter) and a capacity: the one
    expert layer with a backward pass and an `expert` mesh axis."""
    b, l, d = h.shape
    e = config.n_experts
    t = b * l
    cap = int(math.ceil(t / e * config.capacity_factor))
    xt = h.reshape(t, d)

    logits = (xt.astype(jnp.float32)
              @ p["router"].astype(jnp.float32))                # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, -1)                      # [T]
    expert = jnp.argmax(probs, -1)                 # [T]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)       # [T,E]
    # Position of each token within its expert's queue.
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0             # [T,E]
    keep = (pos < cap) & (onehot > 0)
    dispatch = (jax.nn.one_hot(pos.astype(jnp.int32), cap)
                * keep[..., None])                               # [T,E,C]

    ex_in = jnp.einsum("tec,td->ecd", dispatch.astype(h.dtype), xt)
    ex_in = with_logical_constraint(ex_in, ("experts", None, "embed"),
                                    mesh=mesh)
    hidden = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", ex_in,
                                    p["w_up"].astype(h.dtype)))
    ex_out = jnp.einsum("ecf,efd->ecd", hidden, p["w_down"].astype(h.dtype))
    combine = dispatch * gate[:, None, None]
    out = jnp.einsum("tec,ecd->td", combine.astype(h.dtype), ex_out)

    # Load-balancing aux loss (Switch eq. 4): mean prob * mean assignment.
    density = jnp.mean(onehot, 0)
    density_prob = jnp.mean(probs, 0)
    aux = e * jnp.sum(density * density_prob)
    return out.reshape(b, l, d), aux, None


@dataclasses.dataclass(frozen=True)
class FeedForward:
    apply: Callable
    # Leaves `apply` casts to the activation dtype at their use, which
    # `serving_params` therefore holds in it (the rest are used as stored).
    cast: tuple = ()
    # Leaves that the loops over a paged cache keep whole and read in place
    # at `p["layer"]`: taking a layer's experts out of their stack would
    # copy them in every step.  (The train path scans them with their
    # layer: `forward_trunk`.)
    whole: tuple = ()
    serves: bool = True     # runs over a paged KV cache


GELU = FeedForward(gelu_mlp, cast=("w_up", "w_down"))
SWIGLU = FeedForward(swiglu_mlp, cast=("w_gate", "w_up", "w_down"))
SCALED_SWIGLU = FeedForward(scaled_swiglu_mlp,
                            cast=("w_gate", "w_up", "w_down"))
EXPERTS = FeedForward(moe_ffn, whole=("w_gate", "w_up", "w_down"))
SHARED_EXPERTS = FeedForward(shared_moe_ffn,
                             cast=("ws_gate", "ws_up", "ws_down"),
                             whole=("w_gate", "w_up", "w_down"))
SHARED_RELU2_EXPERTS = FeedForward(shared_relu2_moe_ffn,
                                   cast=("ws_up", "ws_down"),
                                   whole=("w_up_t", "w_down"))
SWITCH = FeedForward(switch_moe, serves=False)


# --------------------------------------------------------------------------
# Parts: attentions.  `apply(h, p, spec, config, mesh, position_offset)` on
# normed h [B, L, D] is the causal attention of a whole sequence, projected
# back to [B, L, D]; `cached(h, pools, p, spec, config, block_tables,
# positions, valid, ctx_lens)` writes what the slice's tokens leave in the
# paged pools at `p["cache_layer"]`, attends over each lane's block table
# there and returns ([B, T, D], pools).
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HeadSizes:
    """What `HEADS` reads of a run of layers where that is not the model's
    config and its spec's one rotation (`Run.sizes`; `head_sizes`): the
    head counts, the run's OWN rotation (`rope_theta` None: the run's
    layers have no positional encoding at all; `rope_freqs`: frequencies
    other than theta's own, `yarn_freqs`; `rope_scale`: a factor on the
    cosines and sines, YaRN's `attention_factor`), a `window` (positions
    attended, the token's own among them; 0: the whole context), the eps of
    an RMSNorm on q and k over each head's own `head_dim` numbers
    (`q_norm`, `k_norm` [head_dim]; `Spec.qk_norm` is the norm over the
    whole projected vector), and whether the attention's result is gated
    elementwise by sigmoid(h W_g) (`w_attn_gate` [D, H, head_dim]) before
    the output projection."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: Optional[float] = None
    window: int = 0
    qk_norm: Optional[float] = None
    gate: bool = False
    rope_freqs: Optional[tuple] = None
    rope_scale: float = 1.0


def _rotated(x, sizes: HeadSizes, offset):
    """x [B, L, heads, K] under the run's rotation, where it has one."""
    if sizes.rope_theta is None:
        return x
    return rope(x, sizes.rope_theta, offset, sizes.rope_freqs,
                sizes.rope_scale)


def head_sizes(spec, config) -> HeadSizes:
    """The sizes `HEADS` goes by: the run's own, or those of a config that
    states its head counts beside its spec's rotation."""
    if isinstance(config, HeadSizes):
        return config
    return HeadSizes(config.n_heads, config.n_kv_heads, config.head_dim,
                     spec.rope_theta)


def _to_heads(h, w):
    """h [B, L, D] through w [D, heads, K] as [B, L, heads, K]."""
    return jnp.einsum("bld,dhk->blhk", h, w.astype(h.dtype))


def _to_heads_side_by_side(h, w):
    """`_to_heads` with the product made [B, L, heads x K] wide and then
    seen as heads: the same numbers, for a forward over whole sequences and
    the train step.  A product that comes out in four dimensions is laid
    out by XLA with the length innermost wherever a head is narrower than
    the 128 lanes of a tile, and the flash kernels, which read the heads
    side by side, would get a transposed copy of it (PERF.md section 6,
    PR 51); the two views of one [B, L, heads x K] array cancel and the
    kernels read what the product wrote.  The engine's programs keep
    `_to_heads`: their weights arrive as prepared [D, heads, K] stacks
    whose layout is fixed, and the wide view of one is a copy of it in
    every step (EvaByte's T=1 step compiled for a v5e copies 537 MB of
    weights with this form and none with `_to_heads`:
    tests/test_tpu_aot.py holds it to none), where the train step's
    float32 weights are converted a step anyway."""
    wide = jnp.einsum("bld,de->ble", h,
                      w.astype(h.dtype).reshape(w.shape[0], -1))
    return wide.reshape(*h.shape[:2], *w.shape[1:])


def _from_heads_side_by_side(attn, w):
    """attn [B, L, heads, K] through w [heads, K, D] as [B, L, D], attn
    read [B, L, heads x K] wide (`_to_heads_side_by_side`'s other end: with
    "blhk,hkd->bld" here the train cell read 155,497 tokens/s and 12.01 GB
    against 159,282 and 11.59, PERF.md section 6, PR 51; the engine's
    programs keep the einsum, whose `wo` they read where it is held)."""
    return jnp.einsum("ble,ed->bld", attn.reshape(*attn.shape[:2], -1),
                      w.astype(attn.dtype).reshape(-1, w.shape[2]))


def _qkv(spec: Spec, h, p, sizes: Optional[HeadSizes] = None,
         to_heads=_to_heads):
    """Projected q, k, v [B, L, heads, head_dim] of normed h; with
    `qk_norm`, q and k RMS-normalised over all their heads together
    (OLMoE: the norm spans the whole projected vector, before RoPE), with
    a run's own (`HeadSizes.qk_norm`) each head over its own numbers."""
    q, k, v = (to_heads(h, p[w]) for w in ("wq", "wk", "wv"))
    if spec.qk_norm is not None:
        def norm(x, scale):
            flat = rmsnorm(x.reshape(*x.shape[:2], -1), scale.reshape(-1),
                           spec.qk_norm)
            return flat.reshape(x.shape)
        q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
    if sizes is not None and sizes.qk_norm is not None:
        q = rmsnorm(q, p["q_norm"], sizes.qk_norm)
        k = rmsnorm(k, p["k_norm"], sizes.qk_norm)
    if spec.mult is not None:
        k = _scaled(k, spec.mult.key)
    return q, k, v


def _attn_gate(attn, h, p, sizes: HeadSizes):
    """attn [B, L, H, K] times the elementwise gate sigmoid(h W_g)
    [B, L, H, K] of the block's normed input h, where the run has one."""
    if not sizes.gate:
        return attn
    g = jax.nn.sigmoid(jnp.einsum(
        "bld,dhk->blhk", h, p["w_attn_gate"].astype(h.dtype)).astype(
            jnp.float32))
    return attn * g.astype(attn.dtype)


def heads_attention(h, p, spec, config, mesh, position_offset=0):
    """Multi-head or grouped-query attention with per-head K and V, by the
    flash kernels, which take a run's `window` and K and V at their own
    `n_kv_heads`; a length that does not tile runs their XLA form, all
    L x L scores under the mask."""
    c = head_sizes(spec, config)
    q, k, v = _qkv(spec, h, p, c, _to_heads_side_by_side)
    q, k = _rotated(q, c, position_offset), _rotated(k, c, position_offset)
    # constrained as the kernels read it, the heads side by side: a
    # [B, L, heads, 64] value that stands on its own is copied on both sides
    q = with_logical_constraint(
        q.reshape(*q.shape[:2], -1), ("batch", "length", "heads"),
        mesh=mesh).reshape(q.shape)
    attn = mesh_flash_attention(q, k, v, mesh=mesh, causal=True,
                                window=c.window)
    attn = _attn_gate(attn, h, p, c)
    return _from_heads_side_by_side(attn, p["wo"])


def _attend_rows(q, k, v, pools, p, config, lanes, rows, n_rows,
                 window: int = 0):
    """The slice's K and V written into the whole pools at the layer, at
    `rows` [B, T] of each lane's table, then attention of the `valid` rows
    over the table's first `n_rows` [B] rows in the same buffers
    (ops/attention.py paged path).  K/V are cached with kv_heads (GQA
    un-repeated: the whole point of the grouped cache); the paged attention
    path groups the query heads itself.  With `window` a row attends its
    last `window` rows alone."""
    from ray_tpu.ops.attention import paged_attention, paged_kv_update

    layer = p["cache_layer"]
    k_pool, v_pool = paged_kv_update(*pools, k, v, lanes.block_tables, rows,
                                     lanes.valid, layer)
    attn = paged_attention(q, k_pool, v_pool, lanes.block_tables, n_rows,
                           rows, layer, valid=lanes.valid,
                           kv_heads=config.n_kv_heads,
                           **({"window": window} if window else {}))
    return attn, (k_pool, v_pool)


# --------------------------------------------------------------------------
# An attention over a paged cache is three stages (`Attention.project`,
# `.attend`, `.finish`; a mixer's likewise).  The first and the last are
# ROW-WISE: products of a row with the layer's weights, whatever lane or
# position the row has beyond its rotation, so they run once over all the
# rows of a step, of one population [B, T] or of two laid end to end
# (`forward_cached`'s `chunk`), and read a leaf of the weights once.  The
# middle one is a LANE's own: the rows' write into the pools and the
# attention over the lane's table, by the kernel its population's T takes.
#   project(h, p, spec, sizes, offset) -> arrays a row each [B, L, ...]
#     (`offset` [B]: the position of each lane's first row)
#   attend(rows, pools, p, spec, sizes, lanes: Lanes) -> (arrays a row
#     each, pools)
#   finish(out, rows, h, p, spec, sizes) -> [B, L, D]
# --------------------------------------------------------------------------

class Lanes(NamedTuple):
    """One population of a step as its lanes' own stage sees it: each
    lane's table [B, MB], its rows' absolute positions [B, T] (consecutive
    a lane) and which of them hold a token, its context length with the
    slice [B], and over a state cache its slot [B] (None: row i's is slot
    i)."""
    block_tables: Any
    positions: Any
    valid: Any
    ctx_lens: Any
    slots: Any = None


def _heads_project(h, p, spec, config, offset):
    """(Per-token rotation at each token's own absolute position: `offset`
    with L-consecutive slices means positions must be contiguous per lane,
    which prefill/decode slices always are.)  A run's own `HeadSizes` may
    take the rotation away."""
    c = head_sizes(spec, config)
    q, k, v = _qkv(spec, h, p, c)
    return _rotated(q, c, offset), _rotated(k, c, offset), v


def _heads_attend(rows, pools, p, spec, config, lanes):
    """A token's K and V are one row of its lane's table, at its position;
    under a run's window the rows behind it are not read (the run's table
    is a sliding one)."""
    c = head_sizes(spec, config)
    return _attend_rows(*rows, pools, p, c, lanes, lanes.positions,
                        lanes.ctx_lens, c.window)


def _heads_finish(attn, rows, h, p, spec, config):
    """The run's gate, where it has one, and the output projection."""
    attn = _attn_gate(attn, h, p, head_sizes(spec, config))
    return jnp.einsum("blhk,hkd->bld", attn, p["wo"].astype(rows[0].dtype))


def eva_attention(h, p, spec, config, mesh, position_offset=0):
    """EVA over a whole sequence that starts at position 0 (windows are
    counted from there): ops/attention.py's `eva_attention`."""
    from ray_tpu.ops import attention as ops

    q, k, v = _qkv(spec, h, p, to_heads=_to_heads_side_by_side)
    q = rope(q, spec.rope_theta, position_offset)
    k = rope(k, spec.rope_theta, position_offset)
    attn = ops.eva_attention(q, k, v, p["eva_mu"], p["eva_phi"],
                             window=config.window_size,
                             chunk=config.chunk_size, mesh=mesh)
    return jnp.einsum("blhk,hkd->bld", attn, p["wo"].astype(h.dtype))


def _eva_attend(rows, pools, p, spec, config, lanes):
    """`_heads_attend` (between `HEADS`' own row-wise stages: the same
    projections, rotation and output) over a table whose rows are not one a
    token: the summary rows of a lane's closed windows, then the exact rows
    of its
    open one (`ops.eva_row`).  Rotation goes by the true position, the write
    and the mask by the row, computed from it here; a slice lies inside one
    window (the engine cuts chunks at window edges), so its rows are
    consecutive like its positions.  Single-query attention over the first
    `eva_row(ctx - 1) + 1` rows IS EVA's one softmax over exact keys and
    summaries."""
    from ray_tpu.ops.attention import eva_row

    c, positions = config, lanes.positions
    at = (eva_row(positions[:, :1], c.window_size, c.chunk_size)
          + jnp.arange(positions.shape[1], dtype=positions.dtype))
    n_rows = eva_row(jnp.maximum(lanes.ctx_lens - 1, 0), c.window_size,
                     c.chunk_size) + 1
    return _attend_rows(*rows, pools, p, c, lanes, at, n_rows)


def eva_compact(pools, p, config, src, dst, live):
    """Close a window at one layer: `ops.eva_summarise` with the layer's
    `eva_mu`, `eva_phi`."""
    from ray_tpu.ops.attention import eva_summarise

    return eva_summarise(*pools, p["eva_mu"], p["eva_phi"], src, dst, live,
                         p["cache_layer"], chunk=config.chunk_size,
                         kv_heads=config.n_kv_heads,
                         head_dim=config.head_dim)


@dataclasses.dataclass(frozen=True)
class LatentSizes:
    """What `LATENT` reads of a run of layers: MLA's published sizes, the
    rotation and the softmax scale, and what a run may add to plain MLA: a
    factor on each normed latent, a sigmoid gate a head on the output
    (`w_head_gate`), a `window` (positions attended, the token's own
    among them; 0: the whole context) and a learned indexer that chooses
    the `index_topk` positions attended (`w_iq`, `w_ik`, `ik_scale`,
    `ik_bias`, `w_iw`; 0: none).  `q_lora_rank` 0: the query has no latent
    of its own, `q = h W_q` (`wq` [D, H, qk_nope + qk_rope]; an indexer
    needs the latent).  `rope_theta` None: nothing is rotated, the last
    qk_rope numbers of a query and the one shared key are used as they are
    projected (the stored row is the same [c_kv | k_pe]).  A model of one
    kind of layer states them
    in its config and spec (`latent_sizes`); one with several gives each
    run its own (`Run.sizes`)."""
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: Optional[float]
    attn_scale: float
    norm_eps: float
    rope_freqs: Optional[tuple] = None
    q_rescale: float = 1.0
    kv_rescale: float = 1.0
    gate: bool = False
    window: int = 0
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0


def latent_sizes(spec, config) -> LatentSizes:
    """The sizes `LATENT` goes by: the run's own, or those of a config
    that states MLA's sizes under their published names beside its spec's
    rotation and scale."""
    c = config
    if isinstance(c, LatentSizes):
        return c
    return LatentSizes(
        c.n_heads, c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
        c.qk_rope_head_dim, c.v_head_dim, spec.rope_theta, spec.attn_scale,
        c.norm_eps, spec.rope_freqs)


def _latent_qkv(h, p, s: LatentSizes, offset):
    """MLA's projections of normed h [B, L, D]: per head q_nope
    [B, L, H, qk_nope] and the rotated q_rope [B, L, H, qk_rope]; per token
    the normed latent c_kv [B, L, kv_lora_rank] and the one rotated key
    k_rope [B, L, qk_rope] all heads share (neither rotated where the
    sizes state no rotation); last the query's own normed latent c_q
    [B, L, q_lora_rank] (an indexer's queries are made of it; None where
    the query has none: `q_lora_rank` 0)."""
    eps = s.norm_eps
    if s.q_lora_rank:
        c_q = rmsnorm(jnp.einsum("bld,dr->blr", h,
                                 p["w_qa"].astype(h.dtype)),
                      p["q_norm"], eps)
        if s.q_rescale != 1.0:
            c_q = c_q * jnp.asarray(s.q_rescale, c_q.dtype)
        q = jnp.einsum("blr,rhk->blhk", c_q, p["w_qb"].astype(h.dtype))
    else:
        c_q, q = None, _to_heads(h, p["wq"])
    kv = jnp.einsum("bld,dr->blr", h, p["w_kva"].astype(h.dtype))
    c_kv = rmsnorm(kv[..., :s.kv_lora_rank], p["kv_norm"], eps)
    if s.kv_rescale != 1.0:
        c_kv = c_kv * jnp.asarray(s.kv_rescale, c_kv.dtype)
    q_rope, k_rope = q[..., s.qk_nope_head_dim:], kv[..., s.kv_lora_rank:]
    if s.rope_theta is not None:
        freqs = s.rope_freqs
        q_rope = rope(q_rope, s.rope_theta, offset, freqs)
        k_rope = rope(k_rope[..., None, :], s.rope_theta, offset,
                      freqs)[:, :, 0]
    return q[..., :s.qk_nope_head_dim], q_rope, c_kv, k_rope, c_q


def _index_qkw(h, c_q, p, s: LatentSizes, offset):
    """The indexer's side of a token (DeepSeek-V3.2's lightning indexer):
    queries q_i [B, L, Hi, Di] from the query latent, ONE key k_i
    [B, L, Di] a token (a LayerNorm of a projection of h), both with their
    first qk_rope dims rotated, and the heads' weights w [B, L, Hi] in
    float32, the two scales (Hi^-0.5, Di^-0.5) folded in.
    I(t, s) = sum_j w[t, j] relu(q_i[t, j] . k_i[s])."""
    r = s.qk_rope_head_dim
    q_i = jnp.einsum("blr,rhk->blhk", c_q, p["w_iq"].astype(h.dtype))
    k_i = layernorm(jnp.einsum("bld,dk->blk", h, p["w_ik"].astype(h.dtype)),
                    p["ik_scale"], p["ik_bias"])
    q_i = jnp.concatenate([
        rope(q_i[..., :r], s.rope_theta, offset, s.rope_freqs),
        q_i[..., r:]], -1)
    k_i = jnp.concatenate([
        rope(k_i[..., None, :r], s.rope_theta, offset, s.rope_freqs)[:, :, 0],
        k_i[..., r:]], -1)
    w = jnp.einsum("bld,dh->blh", h, p["w_iw"].astype(h.dtype)).astype(
        jnp.float32) * (s.index_n_heads ** -0.5 * s.index_head_dim ** -0.5)
    return q_i, k_i, w


def _head_gate(attn, h, p, s: LatentSizes):
    """attn [B, L, H, V] times the headwise gate sigmoid(h W_g) [B, L, H]
    where the run has one."""
    if not s.gate:
        return attn
    g = jax.nn.sigmoid(jnp.einsum(
        "bld,dh->blh", h, p["w_head_gate"].astype(h.dtype)).astype(
            jnp.float32))
    return attn * g[..., None].astype(attn.dtype)


def _latent_up(p, s: LatentSizes):
    """(w_uk [H, qk_nope, C], w_uv [H, C, v]) of one layer: the two halves
    of `w_kvb` [C, H, qk_nope + v], from the tree where it holds them
    (serving_params), else split here."""
    if "w_uk" in p:
        return p["w_uk"], p["w_uv"]
    return _kvb_served(p["w_kvb"], s.qk_nope_head_dim).values()


def latent_attention(h, p, spec, config, mesh, position_offset=0):
    """Multi-head latent attention (MLA), expanded: every token's key and
    value of every head are made from its latent (`w_kvb`), the key gains
    the shared rotated part, and plain causal attention runs over them.
    score = (q_nope . k_nope + q_rope . k_rope) * attn_scale."""
    from ray_tpu.ops.attention import reference_attention

    c = latent_sizes(spec, config)
    q_nope, q_rope, c_kv, k_rope, c_q = _latent_qkv(h, p, c,
                                                    position_offset)
    kv = jnp.einsum("blc,chk->blhk", c_kv, p["w_kvb"].astype(h.dtype))
    k = jnp.concatenate([
        kv[..., :c.qk_nope_head_dim],
        jnp.broadcast_to(k_rope[:, :, None], q_rope.shape)], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    if c.window or c.index_topk:
        attn = _chosen_attention(q, k, kv[..., c.qk_nope_head_dim:],
                                 _latent_chosen(h, c_q, p, c,
                                                position_offset),
                                 c.attn_scale)
    else:
        attn = reference_attention(q, k, kv[..., c.qk_nope_head_dim:],
                                   causal=True, scale=c.attn_scale)
    attn = _head_gate(attn.astype(h.dtype), h, p, c)
    return jnp.einsum("blhk,hkd->bld", attn, p["wo"].astype(h.dtype))


def _latent_chosen(h, c_q, p, s: LatentSizes, offset):
    """[B, L, L] bool: the positions each token of a whole sequence
    attends, under the causal mask: the last `window` of them, or the
    `index_topk` of largest index score (`jax.lax.top_k`: exact, ties to
    the lower position), all of them while there are no more than that."""
    length = h.shape[1]
    pos = jnp.arange(length)
    keep = pos[None, :] <= pos[:, None]
    if s.window:
        keep = keep & (pos[None, :] > pos[:, None] - s.window)
    keep = jnp.broadcast_to(keep[None], (h.shape[0], length, length))
    if s.index_topk and length > s.index_topk:
        q_i, k_i, w = _index_qkw(h, c_q, p, s, offset)
        scores = jnp.einsum(
            "blh,blhs->bls", w, jax.nn.relu(jnp.einsum(
                "blhk,bsk->blhs", q_i, k_i,
                preferred_element_type=jnp.float32)))
        _, chosen = jax.lax.top_k(jnp.where(keep, scores, -jnp.inf),
                                  s.index_topk)
        keep = keep & jnp.any(chosen[..., None] == pos, axis=-2)
    return keep


def _chosen_attention(q, k, v, keep, scale):
    """Plain attention [B, L, H, K] under a mask `keep` [B, L, L]."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    probs = jax.nn.softmax(jnp.where(keep[:, None], logits.astype(
        jnp.float32), -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _latent_project(h, p, spec, config, offset):
    """MLA over a latent paged cache, absorbed: a token leaves one row
    [c_kv | k_rope] in the one pool; the query's no-position part is
    carried into the latent space (q_nope W_uk^T), scores and the weighted
    sum run against the cached rows as they are, and the result comes back
    through W_uv (`_latent_finish`).  The same mathematics as
    `latent_attention`, reassociated.  Returns (the query's packed row, the
    token's stored row) and, of an indexed run, (its index queries, their
    weights, the token's one index key as its second pool stores it)."""
    from ray_tpu.ops import attention as ops

    c = latent_sizes(spec, config)
    q_nope, q_rope, c_kv, k_rope, c_q = _latent_qkv(h, p, c, offset)
    w_uk, _ = _latent_up(p, c)
    q_lat = jnp.einsum("blhk,hkc->blhc", q_nope, w_uk.astype(h.dtype))
    out = (ops.pack_latent_rows(q_lat, q_rope),
           ops.pack_latent_rows(c_kv, k_rope))
    if c.index_topk:
        q_i, k_i, w_i = _index_qkw(h, c_q, p, c, offset)
        out += (q_i, w_i, ops.pack_kv_rows(k_i[..., None, :]))
    return out


def _latent_attend(rows, pools, p, spec, config, lanes):
    from ray_tpu.ops import attention as ops

    c = latent_sizes(spec, config)
    layer = p["cache_layer"]
    q, row, *index = rows
    tables, positions, valid, ctx_lens, _ = lanes
    pool, *index_pool = ops.paged_rows_update(
        pools, (row, *index[2:]), tables, positions, valid, layer)
    if c.index_topk:
        out = ops.sparse_latent_attention(
            q, *index[:2], pool, index_pool[0], tables, ctx_lens, positions,
            valid, layer, v_width=c.kv_lora_rank, scale=c.attn_scale,
            topk=c.index_topk)
    else:
        out = ops.latent_attention(
            q, pool, tables, ctx_lens, positions, valid, layer,
            v_width=c.kv_lora_rank, scale=c.attn_scale,
            **({"window": c.window} if c.window else {}))
    return out, (pool, *index_pool)


def _latent_finish(out, rows, h, p, spec, config):
    c = latent_sizes(spec, config)
    _, w_uv = _latent_up(p, c)
    attn = jnp.einsum("blhc,hcv->blhv", out, w_uv.astype(h.dtype))
    attn = _head_gate(attn, h, p, c)
    return jnp.einsum("blhk,hkd->bld", attn, p["wo"].astype(h.dtype))


def _kvb_served(w, qk_nope: int):
    """`w_kvb` [..., C, H, qk_nope + v] -> its two halves as the absorbed
    form multiplies them: `w_uk` [..., H, qk_nope, C] and `w_uv`
    [..., H, C, v]."""
    return {"w_uk": jnp.moveaxis(w[..., :qk_nope], -3, -1),
            "w_uv": jnp.moveaxis(w[..., qk_nope:], -3, -2)}


@dataclasses.dataclass(frozen=True)
class CacheRows:
    """What an attention leaves in a paged cache, as the cache manager
    needs to know it (`PagedKVCache.for_model`): a stored row's shape, and
    how many rows a lane holds: one a token (`window` 0), or the exact rows
    of the open window of `window` tokens behind one summary row for every
    `chunk` tokens of each closed one; or (`slide`) one a token of which
    only the last `slide` positions are ever read again (a sliding table:
    blocks behind them go back to the allocator).  `extra`: the widths of
    further rows a token leaves in pools of their own, in the same blocks
    under the same table (an indexer's key)."""
    kv_heads: int
    head_dim: int
    window: int = 0
    chunk: int = 0
    slide: int = 0
    extra: tuple = ()


@dataclasses.dataclass(frozen=True)
class Attention:
    apply: Callable
    # Over a paged cache: the row-wise stage, the lanes' own, the row-wise
    # end (above `Lanes`).
    project: Callable
    attend: Callable
    finish: Callable
    rows: Callable          # config -> CacheRows
    # A K and a V pool, or one pool (a latent row: inference/kv_cache.py).
    pools: int = 2
    # Leaves `serving_params` holds in the activation dtype.
    cast: tuple = ()
    # The leaf `serving_params` re-makes into its served halves, once.
    absorbed: Optional[str] = None
    # Where a lane's closed window becomes its summary rows:
    # `compact(pools, p, config, src, dst, live)` at one layer, reading the
    # leaves `compact_leaves` (`compact_cached`).
    compact: Optional[Callable] = None
    compact_leaves: tuple = ()
    trains: bool = True     # `apply` is fit for the train path

    # The older names, as benchmark/tools/aot_axk1_sizes.py reads them.
    def cache_row(self, config) -> tuple:
        rows = self.rows(config)
        return rows.kv_heads, rows.head_dim

    @property
    def latent(self) -> bool:
        return self.pools == 1


HEADS = Attention(heads_attention, _heads_project, _heads_attend,
                  _heads_finish,
                  rows=lambda c: CacheRows(
                      c.n_kv_heads, c.head_dim,
                      slide=c.window if isinstance(c, HeadSizes) else 0),
                  cast=("wq", "wk", "wv", "wo", "w_attn_gate"))
LATENT = Attention(latent_attention, _latent_project, _latent_attend,
                   _latent_finish,
                   rows=lambda c: CacheRows(
                       1, c.kv_lora_rank + c.qk_rope_head_dim,
                       slide=getattr(c, "window", 0),
                       extra=((c.index_head_dim,)
                              if getattr(c, "index_topk", 0) else ())),
                   pools=1, trains=False,
                   cast=("w_qa", "w_qb", "wq", "w_kva", "w_kvb", "wo",
                         "w_head_gate", "w_iq", "w_ik", "w_iw"),
                   absorbed="w_kvb")
EVA = Attention(eva_attention, _heads_project, _eva_attend, _heads_finish,
                rows=lambda c: CacheRows(c.n_kv_heads, c.head_dim,
                                         c.window_size, c.chunk_size),
                cast=("wq", "wk", "wv", "wo"),
                compact=eva_compact, compact_leaves=("eva_mu", "eva_phi"),
                trains=False)


# --------------------------------------------------------------------------
# Parts: a mixer, beside the attention or alone.  `apply(h, p, config)` on
# normed h [B, L, D] is the mixer over a whole sequence from its zero
# state, projected back to [B, L, D]; over a state cache its three stages
# (above `Lanes`) continue each row's state in the mixer's buffers
# (`pools`: what `Mixer.state` describes, a slot a lane) at
# `p["cache_layer"]` and overwrite it with the state behind the slice's last
# valid token.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StateRows:
    """What a mixer keeps of a lane between steps, as the cache manager
    needs to know it (`PagedKVCache.for_model`), a layer each: the last
    `conv - 1` rows of `conv_width` columns that its convolution reads
    again and, where it has a recurrence (`heads` > 0), a float32 state
    [heads, d_state, head_dim] (stored as `ops.ssm.state_shape` folds it:
    heads narrower than the lane width side by side).  A mixer with no
    recurrence (a gated short convolution) states the rows alone, and the
    cache holds that one buffer and no other."""
    conv: int
    conv_width: int
    heads: int = 0
    head_dim: int = 0
    d_state: int = 0
    groups: int = 1     # heads that share B and C fold together, or none


def _ssm_split(h, p, config):
    """The state-space mixer's projection of normed h [B, L, D]: the gate z
    [B, L, d_ssm], the convolution's input [x | B | C] [B, L, d_ssm + 2 G N]
    and dt [B, L, H], each of the five segments times its factor."""
    c = config
    d_ssm, gn = c.ssm_heads * c.ssm_head_dim, c.ssm_groups * c.ssm_state
    mz, mx, mb, mc, mdt = c.ssm_multipliers
    proj = jnp.einsum("bld,de->ble", h, p["w_in"].astype(h.dtype))
    scale = np.concatenate([np.full(d_ssm, mx), np.full(gn, mb),
                            np.full(gn, mc)]).astype(np.float32)
    xbc = proj[..., d_ssm:2 * d_ssm + 2 * gn]
    if (scale != 1.0).any():
        xbc = xbc * jnp.asarray(scale, xbc.dtype)
    return (_scaled(proj[..., :d_ssm], mz), xbc,
            _scaled(proj[..., 2 * d_ssm + 2 * gn:], mdt))


def _ssm_heads(xbc, dt, p, config, valid=None):
    """The convolved [x | B | C] taken apart (x [B, T, H, P], B and C
    [B, T, G, N]), dt = softplus(dt + dt_bias) [B, T, H] in float32 (0 at a
    row that is not `valid`: the recurrence's identity) and A = -exp(A_log)
    [H]."""
    c = config
    b, t = xbc.shape[:2]
    d_ssm, gn = c.ssm_heads * c.ssm_head_dim, c.ssm_groups * c.ssm_state
    x = xbc[..., :d_ssm].reshape(b, t, c.ssm_heads, c.ssm_head_dim)
    bm = xbc[..., d_ssm:d_ssm + gn].reshape(b, t, c.ssm_groups, c.ssm_state)
    cm = xbc[..., d_ssm + gn:].reshape(b, t, c.ssm_groups, c.ssm_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    if valid is not None:
        dt = dt * valid[..., None]
    return x, bm, cm, dt, -jnp.exp(p["A_log"].astype(jnp.float32))


def _ssm_out(y, x, z, p, config):
    """Behind the recurrence: the skip `D x`, the gate (`y silu(z)`, then
    the norm: Mamba-2's `norm_before_gate` false), an RMSNorm over each
    group's columns with one learned scale, and the projection back."""
    c = config
    b, t = z.shape[:2]
    y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(b, t, -1) * jax.nn.silu(z.astype(jnp.float32))
    y = rmsnorm(y.reshape(b, t, c.ssm_groups, -1), 1.0, c.norm_eps)
    y = (y.reshape(b, t, -1) * p["ssm_norm"]).astype(z.dtype)
    return jnp.einsum("ble,ed->bld", y, p["w_out"].astype(z.dtype))


def _conv_act(conv, p, dtype):
    """Mamba-2's end of its convolution: the taps' sum (`ops.ssm.conv_tail`)
    plus the bias, through SiLU, in the activations' dtype."""
    return jax.nn.silu(p["conv_b"].astype(jnp.float32) + conv).astype(dtype)


def ssm_mixer(h, p, config):
    """Mamba-2's mixer over a whole sequence from the zero state
    (ops/ssm.py has the recurrence)."""
    from ray_tpu.ops import ssm

    c = config
    z, xbc, dt = _ssm_split(h, p, c)
    b, t, width = xbc.shape
    conv, _ = ssm.conv_tail(xbc, jnp.zeros((b, c.ssm_conv - 1, width),
                                           xbc.dtype),
                            p["conv_w"], jnp.full((b,), t, jnp.int32))
    xbc = _conv_act(conv, p, xbc.dtype)
    x, bm, cm, dt, a = _ssm_heads(xbc, dt, p, c)
    y, _ = ssm.ssm_sequence(x, dt, a, bm, cm, chunk=c.ssm_chunk)
    return _ssm_out(y, x, z, p, c)


def _slot_rows(buffer, layer, slots, b: int, new=None):
    """Rows `slots` [B] (None: row i's is slot i) of a slotted buffer
    [L, S, W] at `layer`, or with `new` [B, W] the buffer with those rows
    written: as `dynamic_update_slice`s, one for all rows or one a row,
    which leave the loop-carried buffer where it is (a scatter copied all
    of it in every layer; PERF.md section 6, PR 43)."""
    zero = jnp.zeros((), jnp.int32)
    width = buffer.shape[2]
    if slots is None:
        if new is None:
            return jax.lax.dynamic_slice(buffer, (layer, zero, zero),
                                         (1, b, width))[0]
        return jax.lax.dynamic_update_slice(buffer, new[None],
                                            (layer, zero, zero))
    if new is None:
        # (a row at a time too: `buffer[layer, slots]` slices the whole
        # layer out first)
        return jnp.concatenate([jax.lax.dynamic_slice(
            buffer, (layer, slots[i], zero), (1, 1, width))[0]
            for i in range(b)])
    return jax.lax.fori_loop(
        0, b, lambda i, buf: jax.lax.dynamic_update_slice(
            buf, jax.lax.dynamic_index_in_dim(new, i)[None],
            (layer, slots[i], zero)), buffer, unroll=True)


def _conv_one_token(tails, layer, x, conv_w, fresh, valid):
    """One token a row whose slot is its index (the decoding lanes of every
    step) through a causal depthwise convolution behind the slots' tails
    `tails` [L, S, (K - 1) W] at `layer`: `ops.ssm.conv_tail`'s steps on
    the slots' rows AS THEY ARE STORED, [B, (K - 1) W], with no row
    gathered (the form over [B, K - 1, W] gathers the new tail by each
    row's count of valid tokens, and compiled for the chip turned the whole
    buffer's layout around it: tests/test_tpu_aot.py).  x [B, W]; a `fresh`
    row reads zeros, a row that is not `valid` leaves its slot as it was.
    Returns (the taps' sum [B, W] float32, tails)."""
    b, width = x.shape
    taps = conv_w.shape[0]
    old = _slot_rows(tails, layer, None, b)
    start = jnp.where(fresh[:, None], 0, old)
    w = conv_w.astype(jnp.float32)
    behind = [start[:, i * width:(i + 1) * width] for i in range(taps - 1)]
    # (`ops.ssm.conv_tail`'s order of the sum: from the oldest)
    conv = sum(r.astype(jnp.float32) * w[i]
               for i, r in enumerate(behind + [x]))
    new = jnp.where(valid[:, :1], jnp.concatenate(
        [start[:, width:], x.astype(old.dtype)], axis=1), old)
    return conv, _slot_rows(tails, layer, None, b, new)


def _ssm_project(h, p, spec, config, offset):
    return _ssm_split(h, p, config)


def _ssm_attend(rows, pools, p, spec, config, lanes):
    """The mixer over a slice, continued from each row's slot (`lanes.slots`
    [B]; None: row i's is slot i) of the two buffers `pools` = (state
    [L, S, H, N, P] float32 (as `ops.ssm.state_shape` folds a slot), tail
    [L, S, (K - 1) C]: a slot's K - 1 rows one behind the other) at
    `p["cache_layer"]`: a row whose slice starts at
    position 0 starts from nothing, every other from what its slot holds
    (what the step before left there, or a snapshot the engine copied in),
    and the slot is left holding the state and the convolution's tail
    behind the row's last valid token; a row with no valid token leaves its
    slot as it was.  One token a row is the update (`ops.ssm_update`), more
    the chunked scan (`ops.ssm_scan`).  Returns ((y, the convolved x),
    pools)."""
    from ray_tpu.ops import ssm

    c = config
    state, tails = pools
    layer = p["cache_layer"]
    _, xbc, dt = rows
    _, positions, valid, _, slots = lanes
    # (a row nobody has also stands at position 0: it starts nothing)
    fresh = (positions[:, 0] == 0) & valid[:, 0]
    with jax.named_scope("ssm_conv"):
        b = xbc.shape[0]
        tail = jnp.where(fresh[:, None, None], 0, _slot_rows(
            tails, layer, slots, b).reshape(b, c.ssm_conv - 1, -1))
        conv, tail = ssm.conv_tail(xbc, tail, p["conv_w"],
                                   jnp.sum(valid, axis=1, dtype=jnp.int32))
        xbc = _conv_act(conv, p, xbc.dtype)
        tails = _slot_rows(tails, layer, slots, b, tail.reshape(b, -1))
    if slots is None:
        slots = jnp.arange(b, dtype=jnp.int32)
    x, bm, cm, dt, a = _ssm_heads(xbc, dt, p, c, valid)
    if xbc.shape[1] == 1:
        with jax.named_scope("ssm_update"):
            # (a decode token never stands at position 0)
            y, state = ssm.ssm_update(state, x[:, 0], dt[:, 0], a, bm[:, 0],
                                      cm[:, 0], slots, layer)
            y = y[:, None]
    else:
        with jax.named_scope("ssm_scan"):
            y, state = ssm.ssm_scan(state, x, dt, a, bm, cm, slots, fresh,
                                    layer, chunk=c.ssm_chunk)
    return (y, x), (state, tails)


def _ssm_finish(out, rows, h, p, spec, config):
    return _ssm_out(*out, rows[0], p, config)


@dataclasses.dataclass(frozen=True)
class Mixer:
    apply: Callable
    # Over a state cache, the three stages of a part (above `Lanes`).
    project: Callable
    attend: Callable
    finish: Callable
    state: Callable         # config -> StateRows
    # Leaves `serving_params` holds in the activation dtype.
    cast: tuple = ()
    # The named scope around its whole part over a cache (None: none).
    scope: Optional[str] = None


SSM = Mixer(ssm_mixer, _ssm_project, _ssm_attend, _ssm_finish,
            state=lambda c: StateRows(
                c.ssm_conv, c.ssm_heads * c.ssm_head_dim
                + 2 * c.ssm_groups * c.ssm_state, c.ssm_heads,
                c.ssm_head_dim, c.ssm_state, c.ssm_groups),
            cast=("w_in", "w_out"))


# --------------------------------------------------------------------------
# A gated short convolution (LFM2's `conv` operator): [B | C | u] = h W_in,
# v = B * u, a causal depthwise convolution of `conv_L_cache` taps over v
# with neither bias nor activation, out = (C * conv) W_out.  The gate B * u
# comes BEFORE the convolution, so what a lane keeps between steps is the
# last conv - 1 rows of the gated product v and nothing else: no
# recurrence.
# --------------------------------------------------------------------------

def _conv_in(h, p):
    """[B | C | u] [B, L, 3 d_model] of normed h: `w_in` [D, 3 D], its
    thirds in that order."""
    return jnp.einsum("bld,de->ble", h, p["w_in"].astype(h.dtype))


def _gated_conv(bcu, tail, p, n_valid):
    """The part between the two products: v = B * u, the taps over the
    carried `tail` [B, K - 1, D] and v, the gate C.  Returns (C * conv
    [B, T, D], the tail behind the first `n_valid` rows)."""
    from ray_tpu.ops import ssm

    gate_b, gate_c, u = jnp.split(bcu, 3, axis=-1)
    conv, tail = ssm.conv_tail(gate_b * u, tail, p["conv_w"], n_valid)
    return gate_c * conv.astype(u.dtype), tail


def conv_mixer(h, p, config):
    """The gated short convolution over a whole sequence, zeros before
    it."""
    with jax.named_scope("conv_mix"):
        bcu = _conv_in(h, p)
        b, t, width = bcu.shape
        y, _ = _gated_conv(
            bcu, jnp.zeros((b, config.conv_taps - 1, width // 3), bcu.dtype),
            p, jnp.full((b,), t, jnp.int32))
        return jnp.einsum("ble,ed->bld", y, p["w_out"].astype(h.dtype))


def _conv_project(h, p, spec, config, offset):
    return (_conv_in(h, p),)


def _conv_attend(rows, pools, p, spec, config, lanes):
    """The lanes' own part of the gated short convolution over a slice,
    continued from each row's slot (`lanes.slots` [B]; None: row i's is
    slot i) of the ONE buffer `pools` = (tail [L, S, (K - 1) D]: a slot's
    K - 1 rows of the gated product one behind the other) at
    `p["cache_layer"]`: the gate B * u, the taps over the slot's rows and
    the slice's, and the slot overwritten with the last K - 1 rows behind
    the row's last valid token.  A row whose slice starts at position 0
    starts from zeros, every other from what its slot holds (what the step
    before left there, or a snapshot the engine copied in); a row with no
    valid token leaves its slot as it was.  One token a row whose slot is
    its index takes the same steps on the slots' rows as they are stored
    (`_conv_one_token`).  Returns ((C * conv,), pools)."""
    tails, = pools
    layer = p["cache_layer"]
    bcu, = rows
    _, positions, valid, _, slots = lanes
    # (a row nobody has also stands at position 0: it starts nothing)
    fresh = (positions[:, 0] == 0) & valid[:, 0]
    with jax.named_scope("conv_tail"):
        b = bcu.shape[0]
        if bcu.shape[1] == 1 and slots is None:
            gate_b, gate_c, u = jnp.split(bcu[:, 0], 3, axis=-1)
            conv, tails = _conv_one_token(tails, layer, gate_b * u,
                                          p["conv_w"], fresh, valid)
            y = gate_c * conv.astype(u.dtype)
            return (y[:, None],), (tails,)
        tail = jnp.where(fresh[:, None, None], 0, _slot_rows(
            tails, layer, slots, b).reshape(b, config.conv_taps - 1, -1))
        y, tail = _gated_conv(bcu, tail, p,
                              jnp.sum(valid, axis=1, dtype=jnp.int32))
        tails = _slot_rows(tails, layer, slots, b, tail.reshape(b, -1))
    return (y,), (tails,)


def _conv_finish(out, rows, h, p, spec, config):
    return jnp.einsum("ble,ed->bld", out[0], p["w_out"].astype(h.dtype))


CONV = Mixer(conv_mixer, _conv_project, _conv_attend, _conv_finish,
             state=lambda c: StateRows(c.conv_taps, c.d_model),
             cast=("w_in", "w_out"), scope="conv_mix")


# --------------------------------------------------------------------------
# Kimi Delta Attention (Kimi Linear's `kda` layers), H heads of N = P =
# `kda_head_dim`.  With h the normed input:
#   [q | k | v] = silu(conv(h W_qkv))   a causal depthwise convolution of
#       `kda_conv` taps over each of the 3 H N columns, no bias;
#   q <- q / |q|_2 N^-0.5,  k <- k / |k|_2   a head over its own N numbers;
#   g = -exp(A_log[head]) softplus((h W_fa) W_fb + dt_bias)   [H N], the log
#       of the decay, a number a KEY CHANNEL, float32;
#   beta = sigmoid(h W_b)   a number a head;
#   the recurrence of ops/ssm.py (`kda_update`, `kda_scan`), o [H, P];
#   out = (rmsnorm_head(o) o_norm sigmoid((h W_ga) W_gb)) W_o.
# What a lane keeps between steps: the float32 state [H, N, P] and the last
# `kda_conv` - 1 rows of h W_qkv, the two buffers of a state part.
# --------------------------------------------------------------------------

# Under the L2 norms of q and k (the family's l2norm: x rsqrt(sum x^2 + eps)).
_KDA_L2_EPS = 1e-6


def _kda_in(h, p):
    """The row-wise products of normed h [B, L, D]: ([q | k | v] before the
    convolution [B, L, 3 H N], the decay's and the gate's low-rank
    projections [B, L, H N] each, beta's [B, L, H])."""
    def low_rank(a, b):
        return jnp.einsum("blr,re->ble", jnp.einsum(
            "bld,dr->blr", h, p[a].astype(h.dtype)), p[b].astype(h.dtype))

    return (jnp.einsum("bld,de->ble", h, p["w_qkv"].astype(h.dtype)),
            low_rank("w_fa", "w_fb"), low_rank("w_ga", "w_gb"),
            jnp.einsum("bld,dh->blh", h, p["w_beta"].astype(h.dtype)))


def _kda_heads(conv, decay, beta, p, config, valid=None):
    """Behind the convolution's sum `conv` [B, T, 3 H N] float32: SiLU, the
    heads taken apart and the two L2 norms (q, k [B, T, H, N], v
    [B, T, H, P] in float32), the decay's log g [B, T, H, N] and beta
    [B, T, H], both the identity's (0) at a row that is not `valid`."""
    c = config
    b, t = conv.shape[:2]
    heads, n = c.kda_heads, c.kda_head_dim
    q, k, v = (x.reshape(b, t, heads, n)
               for x in jnp.split(jax.nn.silu(conv), 3, axis=-1))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                 + _KDA_L2_EPS)

    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        decay.astype(jnp.float32).reshape(b, t, heads, n)
        + p["dt_bias"].reshape(heads, n))
    beta = jax.nn.sigmoid(beta.astype(jnp.float32))
    if valid is not None:
        g, beta = g * valid[..., None, None], beta * valid[..., None]
    return unit(q) * n ** -0.5, unit(k), v, g, beta


def _kda_out(o, gate, p, config, dtype):
    """Behind the recurrence: an RMSNorm over each head's own numbers with
    one learned scale [P], the sigmoid gate, the projection back."""
    b, t = o.shape[:2]
    y = rmsnorm(o, p["o_norm"].astype(jnp.float32), config.norm_eps)
    y = y * jax.nn.sigmoid(gate.astype(jnp.float32).reshape(o.shape))
    return jnp.einsum("ble,ed->bld", y.reshape(b, t, -1).astype(dtype),
                      p["w_out"].astype(dtype))


def kda_mixer(h, p, config):
    """Kimi Delta Attention over a whole sequence from the zero state
    (ops/ssm.py has the recurrence)."""
    from ray_tpu.ops import ssm

    c = config
    with jax.named_scope("kda_mix"):
        qkv, decay, gate, beta = _kda_in(h, p)
        b, t, width = qkv.shape
        conv, _ = ssm.conv_tail(
            qkv, jnp.zeros((b, c.kda_conv - 1, width), qkv.dtype),
            p["conv_w"], jnp.full((b,), t, jnp.int32))
        o, _ = ssm.kda_sequence(*_kda_heads(conv, decay, beta, p, c),
                                chunk=c.kda_chunk)
        return _kda_out(o, gate, p, c, h.dtype)


def _kda_project(h, p, spec, config, offset):
    return _kda_in(h, p)


def _kda_attend(rows, pools, p, spec, config, lanes):
    """The lanes' own part over a slice, continued from each row's slot
    (`lanes.slots` [B]; None: row i's is slot i) of the two buffers `pools`
    = (state [L, S, H, N, P] float32, tail [L, S, (K - 1) 3 H N]: a slot's
    K - 1 rows of [q | k | v] one behind the other) at `p["cache_layer"]`,
    as `_ssm_attend`: a row whose slice starts at position 0 starts from
    nothing, every other from what its slot holds, and the slot is left
    holding the state and the tail behind the row's last valid token; a row
    with no valid token leaves its slot as it was.  One token a row is
    `ops.ssm.kda_update`, more `kda_scan`.  Returns ((o,), pools)."""
    from ray_tpu.ops import ssm

    c = config
    state, tails = pools
    layer = p["cache_layer"]
    qkv, decay, _, beta = rows
    _, positions, valid, _, slots = lanes
    # (a row nobody has also stands at position 0: it starts nothing)
    fresh = (positions[:, 0] == 0) & valid[:, 0]
    with jax.named_scope("kda_conv"):
        b = qkv.shape[0]
        if qkv.shape[1] == 1 and slots is None:
            conv, tails = _conv_one_token(tails, layer, qkv[:, 0],
                                          p["conv_w"], fresh, valid)
            conv = conv[:, None]
        else:
            tail = jnp.where(fresh[:, None, None], 0, _slot_rows(
                tails, layer, slots, b).reshape(b, c.kda_conv - 1, -1))
            conv, tail = ssm.conv_tail(
                qkv, tail, p["conv_w"],
                jnp.sum(valid, axis=1, dtype=jnp.int32))
            tails = _slot_rows(tails, layer, slots, b, tail.reshape(b, -1))
        q, k, v, g, beta = _kda_heads(conv, decay, beta, p, c, valid)
    if slots is None:
        slots = jnp.arange(b, dtype=jnp.int32)
    if qkv.shape[1] == 1:
        with jax.named_scope("kda_update"):
            # (a row that starts at position 0 decays what its slot held
            # to nothing: exp(-inf) = 0)
            o, state = ssm.kda_update(
                state, q[:, 0], k[:, 0], v[:, 0],
                jnp.where(fresh[:, None, None], -jnp.inf, g[:, 0]),
                beta[:, 0], slots, layer)
            o = o[:, None]
    else:
        with jax.named_scope("kda_scan"):
            o, state = ssm.kda_scan(state, q, k, v, g, beta, slots, fresh,
                                    layer, chunk=c.kda_chunk)
    return (o,), (state, tails)


def _kda_finish(out, rows, h, p, spec, config):
    return _kda_out(out[0], rows[2], p, config, h.dtype)


KDA = Mixer(kda_mixer, _kda_project, _kda_attend, _kda_finish,
            state=lambda c: StateRows(
                c.kda_conv, 3 * c.kda_heads * c.kda_head_dim, c.kda_heads,
                c.kda_head_dim, c.kda_head_dim),
            cast=("w_qkv", "w_fa", "w_fb", "w_ga", "w_gb", "w_beta",
                  "w_out"),
            scope="kda_mix")


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """The factors a model states on its paths (Falcon-H1's, from its
    maximal-update parametrisation): on the embedding, on the logits, on
    the keys, on the input and the output of the attention and of the
    mixer beside it.  (The feed-forward's two are its own:
    `scaled_swiglu_mlp`; the mixer's five segments': `_ssm_split`.)"""
    embedding: float = 1.0
    lm_head: float = 1.0
    key: float = 1.0
    attn_in: float = 1.0
    attn_out: float = 1.0
    mixer_in: float = 1.0
    mixer_out: float = 1.0


@dataclasses.dataclass(frozen=True)
class Run:
    """A run of like layers in a model's stack: its feed-forward, its
    attention and where its layers' leaves and cached rows live.  A model
    whose layers differ in more than a leading run's feed-forward names
    its runs itself (`Spec.runs`), in order.  `attn` None: no attention
    (then `mixer` stands in its place behind the first norm, or there is
    no first norm at all); `ffn` None: no second norm and no
    feed-forward."""
    blocks: str             # the key of its stacks in the parameter tree
    n_layers: int
    ffn: Optional[FeedForward]
    attn: Optional[Attention]
    # Its first layer's index in its pools (a run of one part: in that
    # part's, the attention's K and V or the mixer's buffers; of both: in
    # both alike), and in its stacks (the runs of a kind share one).
    first: int = 0
    offset: int = 0
    # What its attention reads where that is not the model's config
    # (`LatentSizes`).
    sizes: Any = None
    # Which of the cache's pools are its kind's, and which (part, of how
    # many equal parts) of the block tables' columns: `None` is all.
    pools: Optional[tuple] = None
    table: Optional[tuple] = None
    # A mixer beside the attention, on the same normed input, or in its
    # place; over a cache its buffers follow the attention's pools.
    mixer: Optional[Mixer] = None


@dataclasses.dataclass(frozen=True)
class Spec:
    """The parts of one family's block at one config, and their leaves.
    Shapes come from the config every family has: n_layers, d_model,
    n_heads, vocab_size, max_seq_len, dtype, remat, scan_unroll, and what
    its attention reads (`HEADS`: n_kv_heads (< n_heads: grouped-query
    attention) and head_dim)."""
    norm: Callable          # norm(x, *leaves): layernorm, or rmsnorm + eps
    attn_norm: tuple        # the leaves of the block's first norm,
    mlp_norm: tuple         # of its second,
    final_norm: tuple       # and of the trunk's last
    ffn: FeedForward
    init_params: Callable   # (config, key) -> params
    param_specs: Callable   # (config) -> the congruent logical-spec tree
    attn: Attention = HEADS
    # The rotation's base; None: no rotation.  Positions then come from a
    # learned table `pos_embed` added to the token embedding, or
    # (`pos_table` false) from nowhere: causal order alone, as a model has
    # it whose state-space layers carry the order.
    rope_theta: Optional[float] = None
    pos_table: bool = True
    # Rotary frequencies other than theta's own (`yarn_freqs`), and the
    # factor on the scores where it is not head_dim ** -0.5 (`LATENT`).
    rope_freqs: Optional[tuple] = None
    attn_scale: Optional[float] = None
    # eps of an RMSNorm (`q_norm`, `k_norm`) on the projected q and k.
    qk_norm: Optional[float] = None
    tied_head: bool = False     # the head is `tok_embed.T`, not `lm_head`
    # A stack that is not uniform: its first `first_dense_layers` layers
    # (`params["lead_blocks"]`, scanned apart) have `lead_ffn` as their
    # feed-forward; the other n_layers - first_dense_layers have `ffn`.
    first_dense_layers: int = 0
    lead_ffn: Optional[FeedForward] = None
    # Or, for a stack of several kinds of layer, its runs in order (each
    # with its attention, its feed-forward and its pools: `Run`); `attn`
    # and `ffn` above are then those of its most common run.
    runs: tuple = ()
    # The dtype the residual stream is added in where it is not the
    # activations' (float32 under bf16 matrices; `norm` then casts back),
    # and that of the logits where the head's product is kept wider.
    residual_dtype: Optional[Any] = None
    logits_dtype: Optional[Any] = None
    # The factors a model states on its paths; None: none anywhere.
    mult: Optional[Multipliers] = None
    # Sandwich norms: the leaves of a norm BEHIND the attention (and a
    # mixer beside it) and of one behind the feed-forward, each on the
    # part's result before it is added to the stream; (): none.
    attn_post_norm: tuple = ()
    mlp_post_norm: tuple = ()


# --------------------------------------------------------------------------
# The block, for training and over a paged KV cache
# --------------------------------------------------------------------------

def _norm(spec: Spec, x, p, leaves):
    return spec.norm(x, *(p[name] for name in leaves))


def _behind(spec: Spec, leaves: tuple, p, y):
    """A part's result `y` through the norm behind it (`attn_post_norm`,
    `mlp_post_norm`), where the spec names one."""
    return _norm(spec, y, p, leaves) if leaves else y


def _block(x, p, spec: Spec, run: Run, config, mesh, position_offset=0):
    c = config
    m = spec.mult or Multipliers()
    if run.attn is not None or run.mixer is not None:
        h = _norm(spec, x, p, spec.attn_norm)
    if run.attn is not None:
        x = x + _behind(spec, spec.attn_post_norm, p, _scaled(
            run.attn.apply(_scaled(h, m.attn_in), p, spec, run.sizes or c,
                           mesh, position_offset), m.attn_out))
    if run.mixer is not None:
        x = x + _behind(spec, spec.attn_post_norm, p, _scaled(
            run.mixer.apply(_scaled(h, m.mixer_in), p, c), m.mixer_out))

    aux = load = None
    if run.ffn is not None:
        h = _norm(spec, x, p, spec.mlp_norm)
        y, aux, load = run.ffn.apply(h, p, c, mesh)
        x = x + _behind(spec, spec.mlp_post_norm, p, y)
    if aux is None:
        aux = jnp.zeros((), jnp.float32)
    x = with_logical_constraint(x, ("batch", "length", "act_embed"),
                                mesh=mesh)
    return x, (aux, load)


def _part_cached(part, h, pools, p, spec, config, lanes: tuple, offset):
    """A part (`Attention` or `Mixer`) of a block over the step's rows
    `h` [B, T, D] of one population, or [rows, 1, D] of several laid end to
    end (`lanes`: a `Lanes` each, in that order): its row-wise stages once
    over all of them, the lanes' own once a population on the rows that are
    its, one after the other on the same pools.  `offset`: the position of
    the first row of each of h's leading entries.  Returns ([.., D],
    pools)."""
    rows = part.project(h, p, spec, config, offset)
    if len(lanes) == 1:
        out, pools = part.attend(rows, pools, p, spec, config, lanes[0])
        return part.finish(out, rows, h, p, spec, config), pools
    outs, at = [], 0
    for pop in lanes:
        b, t = pop.positions.shape
        mine = jax.tree.map(
            lambda a: a[at:at + b * t].reshape(b, t, *a.shape[2:]), rows)
        out, pools = part.attend(mine, pools, p, spec, config, pop)
        outs.append(jax.tree.map(
            lambda a: a.reshape(b * t, 1, *a.shape[2:]), out))
        at += b * t
    out = jax.tree.map(lambda *a: jnp.concatenate(a), *outs)
    return part.finish(out, rows, h, p, spec, config), pools


def _block_cached(x, pools, p, spec: Spec, run: Run, config, lanes: tuple,
                  offset, valid):
    """One block of a run over a paged cache: what the slice's tokens leave
    there is written into the run's whole pools at `p["cache_layer"]`, then
    attention runs over its kind's block table in the same buffers
    (`Attention.attend`).  x [B, T, D] of one population (`lanes`: its
    `Lanes`, positions [B, T] absolute, ctx_lens [B] the context length
    including this slice) or [rows, 1, D] of several laid end to end;
    `offset` and `valid` as x's rows lie.  A run with a mixer beside its
    attention hands that the pools behind the attention's and each row's
    slot in them (`Lanes.slots`).  A run of one part runs that part alone,
    over the pools that are its (`Run.pools`).  Returns (x, pools, the
    expert layer's load or None)."""
    m = spec.mult or Multipliers()
    if run.attn is not None or run.mixer is not None:
        h = _norm(spec, x, p, spec.attn_norm)
    n = (0 if run.attn is None else
         run.attn.pools if run.mixer is not None else len(pools))
    if run.attn is not None:
        attn, rows = _part_cached(run.attn, _scaled(h, m.attn_in), pools[:n],
                                  p, spec, run.sizes or config, lanes,
                                  offset)
        x = x + _behind(spec, spec.attn_post_norm, p,
                        _scaled(attn, m.attn_out))
        pools = (*rows, *pools[n:])
    if run.mixer is not None:
        with (jax.named_scope(run.mixer.scope) if run.mixer.scope
              else contextlib.nullcontext()):
            y, state = _part_cached(run.mixer, _scaled(h, m.mixer_in),
                                    pools[n:], p, spec, config, lanes,
                                    offset)
        x = x + _behind(spec, spec.attn_post_norm, p,
                        _scaled(y, m.mixer_out))
        pools = (*pools[:n], *state)

    if run.ffn is None:
        return x, pools, None
    h = _norm(spec, x, p, spec.mlp_norm)
    y, _, load = run.ffn.apply(h, p, config, valid=valid)
    return x + _behind(spec, spec.mlp_post_norm, p, y), pools, load


def _layer_stack(blocks: dict, n_layers: int, whole: tuple):
    """(what the layer loop scans over, what it closes over): the `whole`
    leaves stay outside the scan, each layer takes its index."""
    layers = jnp.arange(n_layers, dtype=jnp.int32)
    scanned = {k: v for k, v in blocks.items() if k not in whole}
    return (scanned, layers), {k: blocks[k] for k in whole}


def _layer_of(blocks: dict, i, whole: tuple = ()) -> dict:
    """Layer `i`'s leaves over a paged cache, each read where its stack is
    held: a leaf is the stack indexed at `i` (a one-layer slice, which XLA
    fuses into the product that reads it; a GROUP of layers sliced out, as
    `lax.scan` slices its `xs` when unrolled, is copied through HBM), a
    `whole` leaf the stack itself, for a kernel that takes it with
    `"layer"`."""
    p = {k: v if k in whole
         else jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
         for k, v in blocks.items()}
    return {**p, "layer": i}


def _whole(run: Run) -> tuple:
    """The leaves of a run's layers that stay outside the layer loop."""
    return run.ffn.whole if run.ffn is not None else ()


def _stacks(spec: Spec, config) -> tuple:
    """The runs of like layers, in order: the spec's own, or a leading run
    of `first_dense_layers` layers with another feed-forward and the
    rest."""
    if spec.runs:
        return spec.runs
    lead = spec.first_dense_layers
    main = Run("blocks", config.n_layers - lead, spec.ffn, spec.attn, lead)
    if not lead:
        return (main,)
    return (Run("lead_blocks", lead, spec.lead_ffn, spec.attn), main)


def layer_counts(spec: Spec, config) -> dict:
    """The layers a step runs by what they keep or read: `kv` with an
    attention (cached rows), of which `window` read a window of them
    alone, `state` with a mixer (a recurrent state), `experts` with
    dropless experts.  In a stack of one-part layers these are different
    numbers, none of them `n_layers`."""
    runs = _stacks(spec, config)
    return {
        "kv": sum(r.n_layers for r in runs if r.attn is not None),
        "window": sum(r.n_layers for r in runs if r.attn is not None
                      and getattr(r.sizes, "window", 0)),
        "state": sum(r.n_layers for r in runs if r.mixer is not None),
        "experts": sum(r.n_layers for r in runs if _whole(r))}


def cache_kinds(runs, config) -> tuple:
    """([rows, layers] of the growing kind, the same of the sliding kinds)
    of runs of several kinds (`Run.table`), as `PagedKVCache.for_model`
    turns them into pools and a part: one growing kind and at most one
    sliding kind, both of latent rows (a pool a kind) or both of K and V
    rows (a pair a kind)."""
    kinds: dict = {}
    for run in runs:
        rows = run.attn.rows(run.sizes or config)
        layers = kinds.setdefault(run.table[0], [rows, 0])
        layers[1] = max(layers[1], run.first + run.n_layers)
        if layers[0] != rows or run.attn.pools != runs[0].attn.pools \
                or rows.window:
            raise NotImplementedError(
                "layers of several kinds: one shape of row a kind, and "
                "every kind a latent pool or every kind a K and a V pool")
    grow = [k for k in kinds.values() if not k[0].slide]
    slid = [k for k in kinds.values() if k[0].slide]
    if len(grow) != 1 or len(slid) > 1:
        raise NotImplementedError(
            "layers of several kinds: one growing kind and at most one "
            "sliding kind")
    return grow[0], slid


# --------------------------------------------------------------------------
# Forward, head and loss
# --------------------------------------------------------------------------

def forward_trunk(family, params: dict, tokens: jax.Array, config,
                  mesh=None, position_offset=0):
    """Transformer stack up to (excluding) the lm head.
    tokens [B, L] -> (x [B, L, D] normed, auxiliary loss summed over layers).

    position_offset is the absolute position of the first token: a suffix
    call at position p must read pos_embed[p:p+l], not pos_embed[:l], and
    rotate RoPE from p (there a scalar or a per-lane [B] array)."""
    x, aux, _ = _trunk(family, params, tokens, config, mesh, position_offset)
    return x, aux


# What `remat` keeps of a layer with experts: what only a kernel's forward
# makes and its backward reads again (the flash kernels' result and
# logsumexp, the experts' products over their sorted rows:
# `ops/attention.py`, `ops/moe.py` name them).  Everything else of a layer
# is made again from the layer's input, products with the weights and
# passes over them.  A run without experts keeps nothing, as it always
# has: its flash results alone are 40 MB a layer at gpt2-xl's fsdp4 share,
# 7.15 -> 10.12 GB of temporaries a chip (AOT, PERF.md 6, PR 61), and a
# job that asks for remat asks for memory.
REMAT_KEEPS = ("flash_out", "expert_rows")


def _run_layers(params: dict, runs: tuple) -> list:
    """Each run's layers of its stack.  Where the runs of a stack divide it
    between them in order they are its `lax.split`, whose transpose is one
    concatenation of the runs' gradients; slices of it would each come back
    padded to the stack's size, and the runs' gradients be summed at that
    size (a read and a write of every layer's experts a run)."""
    out = [None] * len(runs)
    stacks: dict = {}
    for i, run in enumerate(runs):
        stacks.setdefault(run.blocks, []).append(i)
    for name, mine in stacks.items():
        blocks = params[name]
        sizes = tuple(runs[i].n_layers for i in mine)
        layers = jax.tree.leaves(blocks)[0].shape[0]
        if len(mine) > 1 and sum(sizes) == layers and all(
                runs[i].offset == sum(sizes[:j]) for j, i in enumerate(mine)):
            parts = {k: jax.lax.split(v, sizes) for k, v in blocks.items()}
            for j, i in enumerate(mine):
                out[i] = {k: v[j] for k, v in parts.items()}
            continue
        for i in mine:
            lo, n = runs[i].offset, runs[i].n_layers
            out[i] = blocks if not lo and n == layers else {
                k: v[lo:lo + n] for k, v in blocks.items()}
    return out


def _trunk(family, params: dict, tokens: jax.Array, config, mesh=None,
           position_offset=0):
    """`forward_trunk` and the expert layers' loads [expert layers, held]
    (None: no layer has experts).

    Every leaf of a layer is scanned with it, a layer's experts too: the
    train path casts a float32 master a layer anyway, and the scan's
    backward then writes a layer's gradient into its place in the stack.
    (A stack kept outside the scan and read at a layer index, as the loops
    over a paged cache keep it, gets a cotangent of the whole stack's size
    a layer, zeros but for that layer, and the sum of them is a read and a
    write of every layer's experts a layer.)"""
    c, spec = config, family(config)
    x = params["tok_embed"][tokens].astype(c.dtype)
    if spec.rope_theta is None and spec.pos_table:
        pos = jax.lax.dynamic_slice_in_dim(params["pos_embed"],
                                           position_offset, tokens.shape[1])
        x = x + pos[None].astype(c.dtype)
    if spec.mult is not None:
        x = _scaled(x, spec.mult.embedding)
    if spec.residual_dtype is not None:
        x = x.astype(spec.residual_dtype)
    x = with_logical_constraint(x, ("batch", "length", "act_embed"), mesh=mesh)

    aux, loads = None, []
    runs = _stacks(spec, c)
    for run, blocks in zip(runs, _run_layers(params, runs)):
        n_layers = run.n_layers
        block = partial(_block, spec=spec, run=run, config=c, mesh=mesh,
                        position_offset=position_offset)
        if c.remat:
            policies = jax.checkpoint_policies
            block = jax.checkpoint(
                block, policy=policies.save_only_these_names(*REMAT_KEEPS)
                if _whole(run) else policies.nothing_saveable)

        def body(x, p, block=block):
            return block(x, {**p, "layer": 0})

        x, (auxes, load) = jax.lax.scan(body, x, blocks,
                                        unroll=min(c.scan_unroll, n_layers))
        aux = jnp.sum(auxes) if aux is None else aux + jnp.sum(auxes)
        if load is not None:
            loads.append(load)
    return (_norm(spec, x, params, spec.final_norm), aux,
            jnp.concatenate(loads) if loads else None)


def _head(spec: Spec, params: dict, config):
    return (params["tok_embed"].T if spec.tied_head
            else params["lm_head"]).astype(config.dtype)


def lm_head(family, params: dict, x: jax.Array, config) -> jax.Array:
    """Project hidden states [..., D] to vocab logits [..., V] (the head's
    columns: `vocab_size` of them, or that many for each of a model's
    prediction heads, the next token's first)."""
    spec = family(config)
    head = _head(spec, params, config)
    if spec.logits_dtype is not None:
        logits = jnp.dot(x, head, preferred_element_type=spec.logits_dtype)
    else:
        logits = x @ head
    return logits if spec.mult is None else _scaled(logits,
                                                    spec.mult.lm_head)


def forward(family, params: dict, tokens: jax.Array, config, mesh=None,
            position_offset=0):
    """tokens [B, L] int32 -> (logits [B, L, V], auxiliary loss scalar)."""
    x, aux = forward_trunk(family, params, tokens, config, mesh,
                           position_offset)
    logits = lm_head(family, params, x, config)
    return with_logical_constraint(logits, ("batch", "length", "vocab"),
                                   mesh=mesh), aux


def loss_fn(family, params: dict, batch: dict, config, mesh=None):
    """batch = {"tokens": [B, L]}: next-token cross-entropy, plus 0.01 of
    the feed-forward's auxiliary loss (`loss_and_metrics`'s first)."""
    return loss_and_metrics(family, params, batch, config, mesh)[0]


def loss_and_metrics(family, params: dict, batch: dict, config, mesh=None):
    """(`loss_fn`'s loss, what the step reports of a model with expert
    layers: `aux_loss`, the routers' balancing losses summed over the
    layers, and `expert_load` [expert layers, held] int32, the assignments
    each held expert took: with a share, the counter that says routing
    stayed whole.  {} for a model without).

    Runs the model on the FULL length L and shifts targets instead of
    slicing inputs to L-1: the sequence dim must stay divisible by the
    mesh's seq axis for ring attention, and L-1 never is.

    Single chip uses the fused chunked cross-entropy (never materializes
    [B, L, V]: see ops/cross_entropy.py and PERF.md; the naive fp32
    log_softmax was ~75% of the train step).  Under a mesh the shard_map
    variant keeps the same property per-chip with vocab-sharded
    logsumexp; the naive path remains only as the fallback for
    non-divisible shapes.
    """
    from ray_tpu.ops.cross_entropy import (fused_cross_entropy,
                                           fused_cross_entropy_spmd,
                                           spmd_ce_applicable)

    c, spec = config, family(config)
    runs = _stacks(spec, c)
    if not all(run.attn is None or run.attn.trains for run in runs):
        raise NotImplementedError(
            "this attention has no train path yet: latent attention is "
            "served absorbed, EVA's whole-sequence form is plain XLA "
            "without a backward pass of its own (ROADMAP.md)")
    if any(run.mixer is not None for run in runs):
        raise NotImplementedError(
            "a mixer has no train path yet: the chunked scans of the "
            "state-space mixer and of Kimi Delta Attention (ops/ssm.py) "
            "have no backward pass of their own, and the short "
            "convolution's whole-sequence form has never been trained "
            "(ROADMAP.md)")
    tokens = batch["tokens"]
    targets = jnp.roll(tokens, -1, axis=1)
    # Last position predicts the rolled-around token 0: always masked.
    valid = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    mask = batch.get("loss_mask")
    if mask is not None:
        valid = valid * mask

    multichip = mesh is not None and any(
        s > 1 for s in mesh.shape.values())
    if not multichip or spmd_ce_applicable(mesh, c.vocab_size,
                                           *tokens.shape):
        x, aux, load = _trunk(family, params, tokens, c, mesh)
        head = _head(spec, params, c)
        if multichip:
            loss = fused_cross_entropy_spmd(x, head, targets, valid, mesh)
        else:
            b, l, d = x.shape
            loss = fused_cross_entropy(x.reshape(b * l, d), head,
                                       targets.reshape(-1),
                                       valid.reshape(-1))
    else:
        x, aux, load = _trunk(family, params, tokens, c, mesh)
        logits = with_logical_constraint(
            lm_head(family, params, x, c), ("batch", "length", "vocab"),
            mesh=mesh)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
    metrics = {} if load is None else {"aux_loss": aux, "expert_load": load}
    return loss + 0.01 * aux, metrics


# --------------------------------------------------------------------------
# Serving: the weights as the cached forward multiplies them, and that
# forward
# --------------------------------------------------------------------------

def _w_down_served(w):
    """[layers, d_ff, d_model] -> `w_down_t` [layers, d_model, d_ff] where
    d_model is no multiple of 128: a bf16 [6400, 1600] array lies on a TPU
    with d_ff minor (the layout that pads nothing), and the T=1 step
    copied all 48 layers of it every time to multiply it the other way
    round.  Rows of a multiple of 128 lie as they are written."""
    if w.shape[-1] % 128 == 0:
        return {"w_down": w}
    return {"w_down_t": jnp.swapaxes(w, -1, -2)}


def _rows_served(name, keep):
    """A lookup table [n, d_model] -> `<name>_rows`, its rows padded to a
    multiple of 128 columns: with rows of 1600 a table lies with n minor,
    which the tied head reads as it is (so `keep` the table for it) and
    a lookup cannot, so the step copied the whole table for 16 rows."""
    def served(table):
        pad = -table.shape[1] % 128
        if not pad:
            return {f"{name}_embed": table}
        rows = {f"{name}_rows": jnp.pad(table, ((0, 0), (0, pad)))}
        return {f"{name}_embed": table, **rows} if keep else rows
    return served


# By whether the head is the token table, and the widths at which the
# attentions' `absorbed` leaves split, a stack each (module level:
# `_remake` is compiled once per set of forms).
@functools.lru_cache(maxsize=None)
def _served_forms(tied: bool, absorbed: tuple):
    forms = (("w_down", _w_down_served),
             ("tok_embed", _rows_served("tok", keep=tied)),
             ("pos_embed", _rows_served("pos", keep=False)))
    return forms + tuple((key, partial(_kvb_served, qk_nope=split))
                         for key, split in absorbed)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _remake(leaves, names, dtype, forms):
    forms = dict(forms)
    return [forms[name](x.astype(dtype)) if name in forms
            else {name: x.astype(dtype)} for x, name in zip(leaves, names)]


def serving_params(family, params: dict, config) -> dict:
    """`params` as `forward_cached` and `lm_head` multiply them.  Every
    table, head, attention matrix and leaf the feed-forward casts
    (`Attention.cast`, `FeedForward.cast`; norm scales and routers are
    used in float32, dropless experts as stored) is held in
    `config.dtype`: the rounding the cached forward applies to that leaf
    at its use (`p["wq"].astype(h.dtype)`), done once for all steps instead
    of once per step.  Of those, `w_down` and the two tables are re-made in
    the forms their uses read in place (`_w_down_served`, `_rows_served`),
    and a latent attention's up-projection is split into the two halves
    its absorbed form multiplies (`_kvb_served`), whatever its dtype.

    It goes by the leaf's own dtype: one that is already in `config.dtype`
    comes back as the same array, as does every leaf not named, so a tree
    held in it (OLMoE's bf16 leaves, a float32 config) is returned as it
    is, with no program run and no copy made.  The rest are made in one
    compiled program.  The engine makes this once per set of weights and
    its step takes it; the raw tree gives the same tokens, paying casts
    and copies in every call."""
    spec = family(config)
    runs = _stacks(spec, config)
    cast = ("tok_embed", "pos_embed", "lm_head") + tuple(
        name for run in runs
        for part in (run.attn, run.ffn, run.mixer) if part is not None
        for name in part.cast)
    # stack -> (its attention's absorbed leaf, the width it splits at)
    absorbed = {run.blocks: (run.attn.absorbed, (
        run.sizes or config).qk_nope_head_dim)
        for run in runs if run.attn is not None and run.attn.absorbed}
    dtype = jnp.dtype(config.dtype)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    names = [path[-1].key for path, _ in flat]

    def form_key(path, name):
        """The name a leaf's form goes by: an absorbed leaf's with its
        stack's split."""
        leaf, split = absorbed.get(path[0].key, (None, 0))
        return f"{name}:{split}" if name == leaf else name

    keys = [form_key(path, name) for name, (path, _) in zip(names, flat)]
    todo = [i for i, (_, x) in enumerate(flat)
            if keys[i] != names[i]
            or (names[i] in cast and x.dtype != dtype)]
    if not todo:
        return params
    made = dict(zip(todo, _remake(
        [flat[i][1] for i in todo], tuple(keys[i] for i in todo), dtype,
        _served_forms(spec.tied_head, tuple(sorted(
            {(f"{name}:{split}", split)
             for name, split in absorbed.values()}))))))
    out = {}
    for i, (path, x) in enumerate(flat):
        node = out
        for k in path[:-1]:
            node = node.setdefault(k.key, {})
        node.update(made.get(i, {names[i]: x}))
    return out


def _embed(params, name, index, config):
    """Rows `index` of the `name` table: from the served rows where the
    tree has them (serving_params), else cast as they are gathered."""
    if f"{name}_rows" in params:
        return params[f"{name}_rows"][index][..., :config.d_model]
    return params[f"{name}_embed"][index].astype(config.dtype)


def forward_cached(family, params: dict, tokens: jax.Array,
                   positions: jax.Array, valid: jax.Array,
                   k_pool: jax.Array, v_pool: Optional[jax.Array],
                   block_tables: jax.Array, ctx_lens: jax.Array, config,
                   moe_load=None, slots=None, chunk=None):
    """Cached (incremental) trunk for autoregressive decode/prefill.

    tokens [B, T] is a SLICE of each lane's sequence at absolute
    `positions` [B, T] (per-lane offsets: lanes decode at different
    depths); what the slice leaves in the cache is written into the paged
    pools [n_layers, NB, BS, W] (inference/kv_cache.py's stored layout:
    rows of n_kv_heads x head_dim in a K and a V pool, or, where the
    spec's attention is latent, one latent row in the one pool `k_pool`,
    `v_pool` None) and attention covers each lane's whole block
    table.  The pools ride the layer loop as its carry, whole: a layer
    writes its rows and reads its blocks by index, nothing slices a layer
    out or stacks it back.  The weights are read the same way: the loop
    scans over the layer indices alone and closes over the stacks, layer i
    indexes its own leaves (`_layer_of`) and the `whole` ones go to their
    kernel with `"layer"`; `scan_unroll` says how many layer bodies a
    trip holds, for the scheduler to overlap, and nothing about what is
    copied.  (The stacks as the scan's `xs` at `unroll=k` are sliced out k
    layers at a time, and that group is materialised: every matrix written
    and read back once a step, 38% of gpt2-xl's decode step until PR 34.)
    `valid` masks padding lanes/overhang (their cache writes are dropped).
    Returns (x [B, T, D], k_pool, v_pool): the lm head is applied by the
    caller on the positions it needs, so a prefill chunk never materializes
    [B, T, V].

    With `moe_load` (int32 [experts held + 2], an expert configuration's
    running counters: assignments per expert, then experts hit summed
    over (layer, step) pairs, then the count of those pairs) it is carried
    through the layer loop too and returned fourth: the load stays on the
    device until somebody asks.

    Where a run has a mixer beside its attention, its state buffers follow
    the K and V pools in the tuple `k_pool` (`PagedKVCache.step_pools`) and
    ride the same carry; `slots` [B] names each row's slot there (None: row
    i's is slot i).

    `chunk`: a SECOND population in the same step, as (tokens [N, C], its
    `Lanes`): the prefilling lanes' rows beside the decoding lanes' [B, 1].
    The rows of both are laid end to end, [B T + N C, 1], and every row-wise
    product of a layer (norms, projections, gates, the feed-forward with
    its router) runs once over all of them, so a leaf of the weights is
    read once a step; rotation goes by each row's own position, and what is a
    lane's own (its rows' write, its attention or scan, by the kernel its
    population's T takes) runs a population at a time on the same pools,
    the first's before the second's (`_part_cached`).  x then comes back
    [B T + N C, 1, D], in that order."""
    c, spec = config, family(config)
    runs = _stacks(spec, c)
    if not all(run.ffn is None or run.ffn.serves for run in runs):
        raise NotImplementedError(
            "this feed-forward has no path over a paged KV cache (the "
            "Switch layer's capacity is a whole batch's)")
    lanes = (Lanes(block_tables, positions, valid, ctx_lens, slots),)
    if chunk is not None:
        more, pop = chunk
        lanes += (pop,)
        tokens, positions, valid = (
            jnp.concatenate([a.reshape(-1, 1), b.reshape(-1, 1)])
            for a, b in ((tokens, more), (positions, pop.positions),
                         (valid, pop.valid)))
    if spec.rope_theta is None and spec.pos_table:
        pos = jnp.clip(positions, 0, c.max_seq_len - 1)
        x = _embed(params, "tok", tokens, c) + _embed(params, "pos", pos, c)
    else:
        x = _embed(params, "tok", tokens, c)
    if spec.mult is not None:
        x = _scaled(x, spec.mult.embedding)
    if spec.residual_dtype is not None:
        x = x.astype(spec.residual_dtype)
    # A cache of several kinds of layer hands its pools over as one tuple
    # (`k_pool`; `PagedKVCache.k`), a run takes its own (`Run.pools`) and
    # its kind's columns of the block tables (`Run.table`).
    several = isinstance(k_pool, (tuple, list))
    pools = (tuple(k_pool) if several
             else (k_pool,) if v_pool is None else (k_pool, v_pool))
    seen = moe_load
    for run in runs:
        blocks, n_layers, first, off = (params[run.blocks], run.n_layers,
                                        run.first, run.offset)
        mine = lanes
        if run.table is not None:
            part, parts = run.table
            mb = block_tables.shape[1] // parts
            mine = tuple(pop._replace(block_tables=jax.lax.slice_in_dim(
                pop.block_tables, part * mb, (part + 1) * mb, axis=1))
                for pop in lanes)

        def body(carry, i, run=run, blocks=blocks, first=first, off=off,
                 mine=mine):
            x, pools, seen = carry
            own = pools if run.pools is None else tuple(
                pools[j] for j in run.pools)
            x, own, load = _block_cached(
                x, own, {**_layer_of(blocks, i + off if off else i,
                                     _whole(run)),
                         "cache_layer": i + first if first else i},
                spec, run, c, mine, positions[:, 0], valid)
            if run.pools is None:
                pools = own
            else:
                pools = tuple(own[run.pools.index(j)] if j in run.pools
                              else pool for j, pool in enumerate(pools))
            if seen is not None and load is not None:
                seen = seen + jnp.concatenate([
                    load, jnp.sum(load > 0, dtype=jnp.int32)[None],
                    jnp.ones((1,), jnp.int32)])
            return (x, pools, seen), None

        (x, pools, seen), _ = jax.lax.scan(
            body, (x, pools, seen), jnp.arange(n_layers, dtype=jnp.int32),
            unroll=min(c.scan_unroll, n_layers))
    x = _norm(spec, x, params, spec.final_norm)
    k_pool, v_pool = ((pools, None) if several else pools
                      if len(pools) == 2 else (pools[0], None))
    return (x, k_pool, v_pool) if seen is None else (x, k_pool, v_pool, seen)


def compact_cached(family, params: dict, k_pool: jax.Array,
                   v_pool: jax.Array, src: jax.Array, dst: jax.Array,
                   live: jax.Array, config):
    """Close a window of some lanes in every layer of the paged pools, in
    place (`Attention.compact`): row i's exact rows in pool blocks `src[i]`
    become the summary rows in blocks `dst[i]`; `live` [N] masks the rows
    nobody has.  The pools ride the layer loop whole, as in
    `forward_cached`; of the weights only the attention's
    `compact_leaves` are read.  Returns (k_pool, v_pool)."""
    c, spec = config, family(config)
    pools = (k_pool, v_pool)
    for run in _stacks(spec, c):
        blocks, first = params[run.blocks], run.first
        read = {k: blocks[k] for k in run.attn.compact_leaves}

        def body(pools, i, run=run, read=read, first=first):
            return run.attn.compact(
                pools, {**_layer_of(read, i), "cache_layer": i + first},
                c, src, dst, live), None

        pools, _ = jax.lax.scan(body, pools,
                                jnp.arange(run.n_layers, dtype=jnp.int32))
    return pools


# --------------------------------------------------------------------------
# Parameters on a mesh, and the train step
# --------------------------------------------------------------------------

def shard_params(family, params: dict, mesh, config, rules=None) -> dict:
    return jax.device_put(params, tree_shardings(
        mesh, family(config).param_specs(config), rules))


def num_params(family, config) -> int:
    shapes = jax.eval_shape(partial(family(config).init_params, config),
                            jax.random.key(0))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def make_train_step(family, config, optimizer, mesh=None):
    """Returns (init_state, train_step): jittable train_step with
    donation; under a mesh, params AND optimizer state are sharded
    (ZeRO-3: Adam moments inherit each param's sharding via GSPMD
    propagation through jit(optimizer.init)) and XLA inserts the
    collectives."""
    import optax

    spec = family(config)

    def init_state(key):
        # One compiled program each, not one dispatch per op (building a
        # gpt2-small engine op by op took 55 s on a v5e chip); under a
        # mesh the params are born sharded, never whole on one device.
        shardings = None
        if mesh is not None:
            from ray_tpu.parallel.sharding import shard_opt_state
            shardings = tree_shardings(mesh, spec.param_specs(config))
        params = jax.jit(spec.init_params, static_argnums=0,
                         out_shardings=shardings)(config, key)
        opt_state = jax.jit(optimizer.init)(params)
        if mesh is not None:
            opt_state = shard_opt_state(opt_state, params, shardings, mesh)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32)}

    def train_step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            partial(loss_and_metrics, family), has_aux=True)(
                state["params"], batch, config, mesh)
        updates, opt_state = optimizer.update(grads, state["opt_state"],
                                              state["params"])
        params = optax.apply_updates(state["params"], updates)
        return ({"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1},
                {"loss": loss, **metrics})

    return init_state, train_step


def bind(family) -> types.SimpleNamespace:
    """The decoder's public functions with `family` (config -> Spec)
    bound: what a family module exports, and what the engine takes as its
    `model` (with the family's own `init_params`, or given `params`)."""
    return types.SimpleNamespace(spec=family, **{
        f.__name__: partial(f, family) for f in (
            forward_trunk, forward, lm_head, forward_cached, compact_cached,
            loss_fn, loss_and_metrics, serving_params, shard_params,
            num_params, make_train_step)})
