"""Plain reference for Trinity-Mini (`model_type` afmoe): the forward pass in
straightforward `jax.numpy`, float32 throughout, matrix multiplications at
`highest` precision, no kernel, no cache, no `top_k` primitive, no dispatch:
attention a masked softmax over every key, every expert on every token
masked by the router's weight for it.

The equations (HF `modeling_afmoe.py`'s; every norm an RMSNorm with eps 1e-5
and a plain learned scale; no bias anywhere):

  x_0 = E[token] * sqrt(D)                       (`mup_enabled`)
  a layer, FOUR norms:
    x = x + N2(attn(N1(x)))       N1 input_layernorm, N2 post_attention_..
    x = x + N4(ffn(N3(x)))        N3 pre_mlp_layernorm, N4 post_mlp_..
  attention on h = N1(x):
    q_i = h W_q[i], g_i = h W_g[i]   (32 heads of 128)
    k_j = h W_k[j], v_j = h W_v[j]   (4 heads of 128; head i reads i // 8)
    q_i, k_j each RMS-normalised over its OWN 128 numbers (q_norm, k_norm)
    a window layer: q, k rotated (theta 10,000, all 128 dims, rotate-half),
                    S_t = {s : t - window < s <= t}
    a full layer:   NO positional encoding, S_t = {s : s <= t}
    o_i = softmax_{s in S_t}(q_i . k_s * 128^-0.5) v
    out = concat_i(o_i * sigmoid(g_i)) W_o
  layers 0, 1:   ffn(h) = (silu(h W_gate) * (h W_up)) W_down   (6144)
  expert layers: s = sigmoid(float32(h) W_r) over all experts
                 chosen = the k experts of largest s + b (b `expert_bias`,
                 for the choice only; ties: the lower index; n_group 1)
                 w_e = s_e / sum_chosen s * route_scale
                 ffn(h) = shared(h) + sum_{e chosen} w_e expert_e(h)
  logits = N(x_L) W_head

It takes the parameters in the program's own layout (`lead_blocks`,
`blocks`, layers stacked on a leading dimension) in whatever dtype they are
served from, and computes attention a group of heads and a block of queries
at a time, so that a 17k-token request runs in the memory a replica has
left beside its weights and cache.  It shares no code with the program
(`ray_tpu/models/decoder.py`, `ray_tpu/ops/`); the float32 upcast, the
feed-forward's slices and the gap between two rows of logits are
`benchmark/reference/axk1.py`'s, the RMSNorm, the rotation, the router's
weights by rank and the head by slices of the vocabulary
`benchmark/reference/dots3.py`'s (the same equations there).

Not in the parameters, so constants here (the published values): the order
of the layers' kinds (S S S F repeated), eps, theta, the window, experts
per token (one in 16 of the router's outputs: 8 of 128), the route scale.
`SIZES` holds them, and for the hidden size of the configuration's
`rehearsal_fields` (64) the nano values the CPU rehearsal and the tests run
at.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.axk1 import HIGHEST, _gaps_jit, f32, swiglu
from benchmark.reference.dots3 import (_blocks, _head_jit, rms_norm, rope,
                                       router_weights)

PUBLISHED = {"eps": 1e-5, "theta": 10000.0, "window": 2048,
             "experts_one_in": 16, "routed_scale": 2.826, "full_every": 4,
             "embed_scale": True}
# by hidden size: the published model's, and the rehearsal's nano one
SIZES = {2048: PUBLISHED, 64: dict(PUBLISHED, window=9, experts_one_in=4)}
HEAD_GROUP = 8          # query heads whose scores are alive at once
QUERY_BLOCK = 512       # query rows whose scores are alive at once


def sizes_of(params, **over) -> tuple:
    """The constants for these parameters as a hashable tuple of pairs."""
    d = params["tok_embed"].shape[1]
    return tuple(sorted(dict(SIZES.get(d, PUBLISHED), **over).items()))


def kinds_of(n_lead: int, n_rest: int, full_every: int = 4) -> list:
    """The published order of the layers as (stack, index in it, window?):
    every `full_every`-th layer of the model is a full one, the others
    window layers; the first `n_lead` are the dense ones."""
    return [("lead" if i < n_lead else "rest",
             i if i < n_lead else i - n_lead, (i + 1) % full_every != 0)
            for i in range(n_lead + n_rest)]


def attention(x, p, eps: float, theta: float, window: int = 0):
    """x [L, D]; p one layer's leaves.  `window` 0: a full layer (no
    rotation); else a window layer.  Returns attn(N1(x)) [L, D]: neither
    the norm behind it nor the residual."""
    length, d = x.shape
    _, heads, dim = p["wq"].shape
    kv_heads = p["wk"].shape[1]
    per = heads // kv_heads
    h = rms_norm(x, p["attn_norm"], eps)
    k = rms_norm(jnp.einsum("ld,dhk->lhk", h, f32(p["wk"])), p["k_norm"], eps)
    v = jnp.einsum("ld,dhk->lhk", h, f32(p["wv"]))
    if window:
        # (rotate-half pairing, dimension i with i + 64: with random
        # weights a permutation of an interleaved pairing's columns)
        k = rope(k, theta)
    kpos = jnp.arange(length)
    group = math.gcd(per, HEAD_GROUP)      # query heads of ONE kv head
    block = _blocks(length)

    def heads_of(w, g, axis=1):
        return f32(jax.lax.dynamic_slice_in_dim(w, g * group, group, axis))

    def one_group(g, out):
        q = rms_norm(jnp.einsum("ld,dhk->lhk", h, heads_of(p["wq"], g)),
                     p["q_norm"], eps)
        if window:
            q = rope(q, theta)
        gate = jax.nn.sigmoid(jnp.einsum("ld,dhk->lhk", h,
                                         heads_of(p["w_attn_gate"], g)))
        kv = g * group // per
        k_g = jax.lax.dynamic_index_in_dim(k, kv, 1, keepdims=False)
        v_g = jax.lax.dynamic_index_in_dim(v, kv, 1, keepdims=False)
        w_o = heads_of(p["wo"], g, 0)

        def one_block(b, out):
            rows = lambda a: jax.lax.dynamic_slice_in_dim(
                a, b * block, block, 0)
            qpos = b * block + jnp.arange(block)
            keep = kpos[None, :] <= qpos[:, None]
            if window:
                # the window counts the token's own position: t attends
                # t - window + 1 .. t
                keep = keep & (kpos[None, :] > qpos[:, None] - window)
            scores = jnp.einsum("qhk,sk->hqs", rows(q), k_g) * dim ** -0.5
            scores = jnp.where(keep[None], scores, -jnp.inf)
            o = jnp.einsum("hqs,sk->qhk", jax.nn.softmax(scores, -1), v_g)
            o = o * rows(gate)
            add = o.reshape(block, group * dim) @ w_o.reshape(group * dim, d)
            return jax.lax.dynamic_update_slice_in_dim(
                out, rows(out) + add, b * block, 0)

        return jax.lax.fori_loop(0, length // block, one_block, out)

    return jax.lax.fori_loop(0, heads // group, one_group, jnp.zeros_like(x))


def experts(h2, p, layer, top_k: int, routed_scale: float):
    """h2 [L, D] = N3(x) through the expert layer `layer` of the stacked
    `p`: the shared expert, and every routed expert on every token, masked
    by the router's weight for it."""
    weights = router_weights(h2, p["router"][layer], p["router_bias"][layer],
                             top_k, routed_scale)
    y = swiglu(h2, p["ws_gate"][layer], p["ws_up"][layer],
               p["ws_down"][layer]) if "ws_gate" in p else jnp.zeros_like(h2)

    def one(e, acc):
        out = swiglu(h2, p["w_gate"][layer, e], p["w_up"][layer, e],
                     p["w_down"][layer, e])
        return acc + jax.lax.dynamic_slice_in_dim(weights, e, 1, 1) * out

    return jax.lax.fori_loop(0, p["w_gate"].shape[1], one, y)


_ATTENTION_LEAVES = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm",
                     "w_attn_gate", "wo")


@functools.partial(jax.jit, static_argnames=("dense", "windowed", "top_k",
                                             "sizes"))
def _layer_jit(x, blocks, layer, dense, windowed, top_k, sizes):
    s = dict(sizes)
    with HIGHEST():
        leaves = {k: blocks[k][layer] for k in _ATTENTION_LEAVES}
        x = x + rms_norm(
            attention(x, leaves, s["eps"], s["theta"],
                      s["window"] if windowed else 0),
            blocks["attn_post_norm"][layer], s["eps"])
        h2 = rms_norm(x, blocks["mlp_norm"][layer], s["eps"])
        if dense:
            y = swiglu(h2, blocks["w_gate"][layer], blocks["w_up"][layer],
                       blocks["w_down"][layer])
        else:
            y = experts(h2, blocks, layer, top_k, s["routed_scale"])
        return x + rms_norm(y, blocks["mlp_post_norm"][layer], s["eps"])


def hidden(params, tokens, top_k=None, **over):
    """tokens [L] -> the last layer's output [L, D], before the final
    norm; one small program dispatched per layer.  `over`: constants other
    than `SIZES`'s (`window`, ...)."""
    sizes = sizes_of(params, **over)
    s = dict(sizes)
    lead, rest = params.get("lead_blocks"), params.get("blocks")
    count = lambda b: b["attn_norm"].shape[0] if b else 0
    top_k = top_k or max(1, rest["router"].shape[-1] // s["experts_one_in"])
    x = f32(params["tok_embed"][jnp.asarray(tokens, jnp.int32)])
    if s["embed_scale"]:
        x = x * math.sqrt(x.shape[-1])
    for which, layer, windowed in kinds_of(count(lead), count(rest),
                                           s["full_every"]):
        x = _layer_jit(x, lead if which == "lead" else rest, layer,
                       which == "lead", windowed, top_k, sizes)
    return x


def row_logits(params, tokens, rows=None, **kw):
    """tokens [L] -> logits [L, V] (over the vocabulary slice the
    parameters hold); with `rows` (start, count), of those rows alone."""
    x = hidden(params, tokens, **kw)
    if rows is not None:
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
    vocab = params["lm_head"].shape[1]
    chunks = 8 if vocab % 8 == 0 and vocab >= 8192 else 1
    return _head_jit(x, params["final_norm"], params["lm_head"], chunks)


def logits(params, tokens, **kw):
    """tokens [B, L] -> logits [B, L, V], a sequence at a time."""
    return jnp.stack([row_logits(params, row, **kw)
                      for row in np.asarray(tokens)])


def served_token_gaps(params, prompt, output, bucket: int = 512, **kw):
    """One full forward over prompt + served output.  Returns, for every
    generated position, (gap, rank): the reference's largest logit minus its
    logit of the served token, and how many tokens the reference ranks above
    the served one (0 = the reference's own greedy choice).  The sequence is
    padded at its end to a multiple of `bucket` so that a few compiled
    programs serve every length; attention is causal, so what follows a
    position cannot change it.  Only the generated positions' rows go
    through the head."""
    seq = list(prompt) + list(output)
    first, n = len(prompt) - 1, len(output)
    tokens = jnp.asarray(seq + [0] * (-len(seq) % bucket), jnp.int32)
    count = -(-n // 256) * 256          # rows through the head, bucketed
    start = max(0, min(first, tokens.shape[0] - count))
    rows = row_logits(params, tokens, rows=(start, min(count,
                                                       tokens.shape[0])),
                      **kw)
    nxt = jnp.asarray((seq + [0] * tokens.shape[0])[
        start + 1:start + 1 + rows.shape[0]], jnp.int32)
    gap, rank = _gaps_jit(rows, nxt)
    lo = first - start
    return (np.asarray(gap)[lo:lo + n].tolist(),
            np.asarray(rank)[lo:lo + n].tolist())
