"""Plain reference for LFM2-24B-A2B (`model_type` lfm2_moe): the forward pass
over a whole sequence in straightforward `jax.numpy`, float32 throughout,
matrix multiplications at `highest` precision, no kernel, no cache, no tail,
no batching of lanes, no `top_k` primitive, no dispatch: the short
convolution is a pad-and-sum over the sequence, attention a masked softmax
over every key, every expert multiplies every token, the result weighted by
the router's weight for it (zero where the token did not choose it).

The equations (the family's published modelling code, `modeling_lfm2_moe.py`;
every norm an RMSNorm with eps 1e-5 and a plain learned scale; no bias
anywhere):

  x_0 = E[token]
  a layer:  x = x + op(N1(x))      N1 operator_norm
            x = x + ffn(N2(x))     N2 ffn_norm
  op, by `layer_types[i]`:
    conv            [B | C | u] = h W_in      (W_in 2048 x 6144, thirds in
                    that order); v = B * u (elementwise);
                    y_t = sum_{j=0..2} w_j * v_{t-2+j}   (depthwise, causal,
                    w [3, 2048], zeros before the sequence, no bias, NO
                    activation);  out = (C * y) W_out
    full_attention  q_i = h W_q[i] (32 heads of 64), k_j = h W_k[j],
                    v_j = h W_v[j] (8 heads of 64; head i reads i // 4);
                    q_i, k_j each RMS-normalised over its OWN 64 numbers
                    (q_layernorm, k_layernorm), THEN rotated (base 1,000,000,
                    all 64 dims, rotate-half pairing);
                    o_i = softmax_{s <= t}(q_i . k_s * 64^-0.5) v;
                    out = concat_i(o_i) W_o
  ffn:  the first `num_dense_layers` layers
                    (silu(h W_gate) * (h W_up)) W_down          (11776)
        the others  s = sigmoid(float32(h) W_r) over the 64 experts;
                    chosen = the 4 of largest s + expert_bias (for the
                    choice only; ties: the lower index);
                    w_e = s_e / (sum_chosen s + 1e-6) * routed_scaling_factor
                    ffn(h) = sum_{e chosen} w_e expert_e(h)   (SwiGLU 1536,
                    no shared expert)
  logits = N(x_L) E^T              (the head tied to the embedding)

Which layers the parameters hold is read from the configuration's file
(`benchmark/configs/lfm2-24b-a2b.json`: `layer_types`, `num_dense_layers`),
so this computes the stage that is served, not a guess at it; the nano model
of the rehearsal and the tests (hidden size 64) goes by the same file's
`rehearsal_fields`.

Departures from the published description and values the catalog's config
lacks (the file's `assumed` has each): head size 64 = hidden_size /
num_attention_heads (the config has no `head_dim`; here it is the
parameters' own shape); the head tied to the embedding (the family's
`tie_embedding`); the 1e-6 under the chosen scores' sum; the order of
W_in's thirds, B then C then u; the rotation pairs dimension i with i + 32
(with random weights a permutation of an interleaved pairing's columns).

It takes the parameters in the program's own layout (a stack of leaves for
each kind of layer, operator x feed-forward: `dense_convs`, `dense_attns`,
`convs`, `attns`) in whatever dtype they are served from and upcasts a slice
at a time, so that a 4.9k-token request runs in the memory a replica has
left beside its weights, pools and tails.  It shares no code with the
program (`ray_tpu/`); the float32 upcast, the SwiGLU's slices and the gap
between two rows of logits are `benchmark/reference/axk1.py`'s, the RMSNorm
and the rotation `benchmark/reference/dots3.py`'s (the same equations
there).
"""

from __future__ import annotations

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.axk1 import HIGHEST, _gaps_jit, f32, swiglu
from benchmark.reference.dots3 import _blocks, rms_norm, rope

CONFIG_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "lfm2-24b-a2b.json")
# (operator, dense feed-forward?) -> the stack of leaves of that kind
STACKS = {("conv", True): "dense_convs", ("full_attention", True):
          "dense_attns", ("conv", False): "convs",
          ("full_attention", False): "attns"}
TOPK_EPS = 1e-6         # under the chosen scores' sum (`assumed`)
HEAD_GROUP = 8          # query heads whose scores are alive at once


@functools.lru_cache(maxsize=None)
def _sizes_by_width() -> dict:
    """Hidden size -> what the parameters do not say, from the
    configuration's file: the served stage's, and the nano model's."""
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    served = {"layer_types": tuple(cfg["layer_types"]),
              "dense": cfg["num_dense_layers"], "eps": cfg["norm_eps"],
              "theta": float(cfg["rope_parameters"]["rope_theta"]),
              "top_k": cfg["num_experts_per_tok"],
              "routed_scale": float(cfg["routed_scaling_factor"]),
              "topk_eps": TOPK_EPS, "conv_tail": True, "conv_c_gate": True}
    nano = cfg["rehearsal_fields"]
    return {cfg["hidden_size"]: served, nano["d_model"]: dict(
        served, layer_types=tuple(nano["layer_types"]),
        dense=nano["n_dense_layers"], top_k=nano["n_experts_per_tok"])}


def sizes_of(params, **over) -> tuple:
    """The constants for these parameters as a hashable tuple of pairs."""
    d = params["tok_embed"].shape[1]
    return tuple(sorted(dict(_sizes_by_width()[d], **over).items()))


def kinds_of(layer_types, dense: int) -> list:
    """(stack, index in it, operator, dense?) of every layer in order."""
    seen: dict = {}
    out = []
    for i, op in enumerate(layer_types):
        stack = STACKS[op, i < dense]
        out.append((stack, seen.get(stack, 0), op, i < dense))
        seen[stack] = seen.get(stack, 0) + 1
    return out


def short_conv(h, p, tail: bool = True, c_gate: bool = True):
    """h [L, D] = N1(x) through the gated short convolution: a pad and a
    sum over the sequence.  (`tail` false: the taps behind a position's own
    left out, as if no lane carried a tail; `c_gate` false: the gate C left
    out.  Two wrong mechanisms, for `tools/lfm2_precision.py` alone.)"""
    length, d = h.shape
    proj = h @ f32(p["w_in"])
    gate_b, gate_c, u = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    v = gate_b * u
    w = f32(p["conv_w"])                                # [K, D]
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), v.dtype), v])
    y = sum(w[j] * padded[j:j + length]
            for j in range(0 if tail else taps - 1, taps))
    return ((gate_c * y) if c_gate else y) @ f32(p["w_out"])


def attention(h, p, eps: float, theta: float):
    """h [L, D] = N1(x) through grouped-query attention: each head's q and
    k normed over their own numbers, then rotated; a masked softmax over
    every key, a group of heads and a block of queries at a time."""
    length, d = h.shape
    _, heads, dim = p["wq"].shape
    kv_heads = p["wk"].shape[1]
    per = heads // kv_heads
    k = rope(rms_norm(jnp.einsum("ld,dhk->lhk", h, f32(p["wk"])),
                      p["k_norm"], eps), theta)
    v = jnp.einsum("ld,dhk->lhk", h, f32(p["wv"]))
    kpos = jnp.arange(length)
    group = math.gcd(per, HEAD_GROUP)       # query heads of ONE kv head
    block = _blocks(length)

    def heads_of(w, g, axis=1):
        return f32(jax.lax.dynamic_slice_in_dim(w, g * group, group, axis))

    def one_group(g, out):
        q = rope(rms_norm(jnp.einsum("ld,dhk->lhk", h, heads_of(p["wq"], g)),
                          p["q_norm"], eps), theta)
        kv = g * group // per
        k_g = jax.lax.dynamic_index_in_dim(k, kv, 1, keepdims=False)
        v_g = jax.lax.dynamic_index_in_dim(v, kv, 1, keepdims=False)
        w_o = heads_of(p["wo"], g, 0)

        def one_block(b, out):
            rows = lambda a: jax.lax.dynamic_slice_in_dim(
                a, b * block, block, 0)
            qpos = b * block + jnp.arange(block)
            keep = kpos[None, :] <= qpos[:, None]
            scores = jnp.einsum("qhk,sk->hqs", rows(q), k_g) * dim ** -0.5
            scores = jnp.where(keep[None], scores, -jnp.inf)
            o = jnp.einsum("hqs,sk->qhk", jax.nn.softmax(scores, -1), v_g)
            add = o.reshape(block, group * dim) @ w_o.reshape(group * dim, d)
            return jax.lax.dynamic_update_slice_in_dim(
                out, rows(out) + add, b * block, 0)

        return jax.lax.fori_loop(0, length // block, one_block, out)

    return jax.lax.fori_loop(0, heads // group, one_group, jnp.zeros_like(h))


def router_weights(h2, router, bias, top_k: int, routed_scale: float,
                   topk_eps: float = TOPK_EPS):
    """[L, E] weights over ALL experts: sigmoid scores s, the top_k of
    largest s + bias (ties: the lower index; by rank, not by a sort or a
    top-k primitive), the chosen UNBIASED s divided by (their sum +
    topk_eps), times the routed scale; 0 for the experts a token did not
    choose."""
    scores = jax.nn.sigmoid(h2 @ f32(router))
    n = scores.shape[-1]
    lower = jnp.arange(n)[None, :] < jnp.arange(n)[:, None]   # j < e

    def rows(s):                         # [R, E] -> weights [R, E]
        biased = s + f32(bias)[None, :]
        a, b = biased[:, :, None], biased[:, None, :]         # e, j
        rank = jnp.sum((b > a) | ((b == a) & lower[None]), -1)
        picked = jnp.where(rank < top_k, s, 0.0)
        return picked / (jnp.sum(picked, -1, keepdims=True) + topk_eps) \
            * routed_scale

    length = scores.shape[0]
    block = _blocks(length)
    return jax.lax.map(rows, scores.reshape(length // block, block, n)
                       ).reshape(length, n)


def experts(h2, p, s: dict):
    """h2 [L, D] = N2(x) through one expert layer's leaves `p`: every
    expert on every token, masked by the router's weight for it."""
    weights = router_weights(h2, p["router"], p["router_bias"], s["top_k"],
                             s["routed_scale"], s["topk_eps"])

    def one(e, acc):
        out = swiglu(h2, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        return acc + jax.lax.dynamic_slice_in_dim(weights, e, 1, 1) * out

    return jax.lax.fori_loop(0, p["w_gate"].shape[0], one,
                             jnp.zeros_like(h2))


@functools.partial(jax.jit, static_argnames=("op", "dense", "sizes"))
def _layer_jit(x, stack, layer, op, dense, sizes):
    s = dict(sizes)
    with HIGHEST():
        p = {k: v[layer] for k, v in stack.items()}
        h = rms_norm(x, p["operator_norm"], s["eps"])
        x = x + (short_conv(h, p, s["conv_tail"], s["conv_c_gate"])
                 if op == "conv"
                 else attention(h, p, s["eps"], s["theta"]))
        h2 = rms_norm(x, p["ffn_norm"], s["eps"])
        return x + (swiglu(h2, p["w_gate"], p["w_up"], p["w_down"])
                    if dense else experts(h2, p, s))


def hidden(params, tokens, **over):
    """tokens [L] -> the last layer's output [L, D], before the final
    norm; one small program dispatched per layer.  `over`: constants other
    than the configuration file's (`top_k`, `topk_eps`, ...)."""
    sizes = sizes_of(params, **over)
    s = dict(sizes)
    kinds = kinds_of(s["layer_types"], s["dense"])
    held = {stack: int(leaves["operator_norm"].shape[0])
            for stack, leaves in params.items() if stack in STACKS.values()}
    want = {stack: sum(k[0] == stack for k in kinds) for stack in held}
    if held != want or len(held) != len({k[0] for k in kinds}):
        raise ValueError(f"the parameters hold {held}, the configuration's "
                         f"layer_types make {want}")
    x = f32(params["tok_embed"][jnp.asarray(tokens, jnp.int32)])
    for stack, layer, op, dense in kinds:
        x = _layer_jit(x, params[stack], layer, op, dense, sizes)
    return x


@functools.partial(jax.jit, static_argnames=("chunks", "eps"))
def _head_jit(x, final_norm, tok_embed, chunks=1, eps=1e-5):
    """[L, D] -> logits [L, V] through the tied head, a slice of the
    vocabulary's rows at a time."""
    with HIGHEST():
        x = rms_norm(x, final_norm, eps)
        rows = tok_embed.shape[0] // chunks
        return jnp.concatenate([
            x @ f32(jax.lax.dynamic_slice_in_dim(tok_embed, i * rows, rows,
                                                 0)).T
            for i in range(chunks)], -1)


def row_logits(params, tokens, rows=None, **over):
    """tokens [L] -> logits [L, V]; with `rows` (start, count), of those
    rows alone."""
    x = hidden(params, tokens, **over)
    if rows is not None:
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
    vocab = params["tok_embed"].shape[0]
    chunks = 8 if vocab % 8 == 0 and vocab >= 8192 else 1
    return _head_jit(x, params["final_norm"], params["tok_embed"], chunks,
                     dict(sizes_of(params))["eps"])


def logits(params, tokens, **over):
    """tokens [B, L] -> logits [B, L, V], a sequence at a time."""
    return jnp.stack([row_logits(params, row, **over)
                      for row in np.asarray(tokens)])


def served_token_gaps(params, prompt, output, bucket: int = 512, **over):
    """One full forward over prompt + served output.  Returns, for every
    generated position, (gap, rank): the reference's largest logit minus its
    logit of the served token, and how many tokens the reference ranks above
    the served one (0 = the reference's own greedy choice).  The sequence is
    padded at its end to a multiple of `bucket` so that a few compiled
    programs serve every length; the convolution and the attention are
    causal, so what follows a position cannot change it.  Only the
    generated positions' rows go through the head."""
    seq = list(prompt) + list(output)
    first, n = len(prompt) - 1, len(output)
    tokens = jnp.asarray(seq + [0] * (-len(seq) % bucket), jnp.int32)
    count = -(-n // 256) * 256          # rows through the head, bucketed
    start = max(0, min(first, tokens.shape[0] - count))
    rows = row_logits(params, tokens, rows=(start, min(count,
                                                       tokens.shape[0])),
                      **over)
    nxt = jnp.asarray((seq + [0] * tokens.shape[0])[
        start + 1:start + 1 + rows.shape[0]], jnp.int32)
    gap, rank = _gaps_jit(rows, nxt)
    lo = first - start
    return (np.asarray(gap)[lo:lo + n].tolist(),
            np.asarray(rank)[lo:lo + n].tolist())
