"""Plain reference for OLMoE (allenai/OLMoE-1B-7B-0125-Instruct): the forward
pass in straightforward `jax.numpy`, float32 throughout, matrix
multiplications at `highest` precision, no kernel, no cache, no sort, no
top-k primitive, no dispatch: every expert is computed for every token, in a
loop over experts, and masked by the router's weights.

It follows the published modelling code, line by line:

  h  = RMSNorm(x; attn_norm)
  q  = RMSNorm(h Wq; q_norm)   k = RMSNorm(h Wk; k_norm)   v = h Wv
       (the norm spans the whole projected vector, all heads together)
  q, k -> heads, rotary embedding (rotate-half, theta 1e4) at each position
  x  = x + softmax(q k^T / sqrt(head_dim), causal) v Wo
  h2 = RMSNorm(x; mlp_norm)
  p  = softmax(float32(h2) Wr) over the experts
  S  = the k experts of largest p (ties: the lower index)
  x  = x + sum_{e in S} p_e * (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
       (p_e as it is: `norm_topk_prob` false; no shared expert, no capacity)
  logits = RMSNorm(x_L; final_norm) W_head        (untied; eps 1e-5)

It takes the parameters in the program's own layout (layers stacked on a
leading dimension, `wq [L, D, H, K]`, experts `[L, E, D, F]`), in whatever
dtype they are served from, and upcasts them a layer's attention, one expert
or one slice of the vocabulary at a time, so that it runs in the memory a
replica has left beside 13.8 GB of weights.  It shares no code with the
program (`ray_tpu/models/llama.py`, `ray_tpu/ops/moe.py`).

Not in the parameters, so constants here: experts per token (one in eight of
the experts, the published 8 of 64), `norm_topk_prob` false, eps, theta.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")
EPS = 1e-5
THETA = 10000.0
EXPERTS_PER_TOKEN_ONE_IN = 8       # 8 of 64


def f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps=EPS):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(scale)


def rope(x, theta=THETA):
    """x [L, H, K] at positions 0..L-1; pairs (i, i + K/2) rotate by
    position * theta^(-2i/K)."""
    length, _, k = x.shape
    inv = theta ** (-jnp.arange(0, k, 2, dtype=jnp.float32) / k)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., k // 2:], x[..., :k // 2]], -1)
    return x * cos + rotated * sin


def attention(x, p):
    """x [L, D]; p one layer's attention weights (float32 here)."""
    length = x.shape[0]
    d, heads, dh = p["wq"].shape
    kv_heads = p["wk"].shape[1]
    h = rms_norm(x, p["attn_norm"])
    q = h @ f32(p["wq"]).reshape(d, heads * dh)
    k = h @ f32(p["wk"]).reshape(d, kv_heads * dh)
    v = h @ f32(p["wv"]).reshape(d, kv_heads * dh)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"].reshape(-1))
        k = rms_norm(k, p["k_norm"].reshape(-1))
    q = rope(q.reshape(length, heads, dh))
    k = rope(k.reshape(length, kv_heads, dh))
    v = v.reshape(length, kv_heads, dh)
    if kv_heads != heads:                 # grouped queries share a kv head
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("qhk,shk->hqs", q, k) / jnp.sqrt(float(dh))
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, -1), v)
    return x + out.reshape(length, heads * dh) @ f32(p["wo"]).reshape(
        heads * dh, d)


def top_k_weights(probs, top_k: int, norm_topk_prob: bool = False):
    """[L, E] weights: a probability where its expert is among the token's
    top_k (ties: the lower index), else 0.  By rank, not by sort: expert
    e's rank is the number of experts that beat it."""
    a, b = probs[:, :, None], probs[:, None, :]          # e, j
    n = probs.shape[-1]
    lower = jnp.arange(n)[None, :] < jnp.arange(n)[:, None]   # j < e
    rank = jnp.sum((b > a) | ((b == a) & lower[None]), -1)
    weights = jnp.where(rank < top_k, probs, 0.0)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights


def router_weights(h2, router, top_k: int, norm_topk_prob: bool = False):
    return top_k_weights(jax.nn.softmax(h2 @ f32(router), -1), top_k,
                         norm_topk_prob)


def expert_sum(h2, p, layer, weights):
    """Every expert of `layer` on every token of h2 [L, D], weighted (0 for
    the experts a token did not choose), one expert's weights upcast at a
    time; p holds the stacked blocks."""
    def one(e, acc):
        gate = h2 @ f32(p["w_gate"][layer, e])
        up = h2 @ f32(p["w_up"][layer, e])
        out = (jax.nn.silu(gate) * up) @ f32(p["w_down"][layer, e])
        return acc + jax.lax.dynamic_slice_in_dim(weights, e, 1, 1) * out

    return jax.lax.fori_loop(0, weights.shape[-1], one, jnp.zeros_like(h2))


def experts(x, p, layer, top_k: int, norm_topk_prob: bool = False):
    """x [L, D] through the expert layer of `layer`, residual added."""
    h2 = rms_norm(x, p["mlp_norm"][layer])
    weights = router_weights(h2, p["router"][layer], top_k, norm_topk_prob)
    return x + expert_sum(h2, p, layer, weights)


_ATTENTION_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk_prob"))
def _layer_jit(x, blocks, layer, top_k, norm_topk_prob):
    with HIGHEST():
        x = attention(x, {k: blocks[k][layer] for k in _ATTENTION_LEAVES
                          if k in blocks})
        return experts(x, blocks, layer, top_k, norm_topk_prob)


def top_k_of(params) -> int:
    return max(1, params["blocks"]["router"].shape[-1]
               // EXPERTS_PER_TOKEN_ONE_IN)


def hidden(params, tokens, top_k=None, norm_topk_prob=False):
    """tokens [L] -> the last layer's output [L, D], before the final
    norm; one small program dispatched per layer."""
    top_k = top_k or top_k_of(params)
    x = f32(params["tok_embed"][jnp.asarray(tokens, jnp.int32)])
    for layer in range(params["blocks"]["router"].shape[0]):
        x = _layer_jit(x, params["blocks"], layer, top_k, norm_topk_prob)
    return x


@functools.partial(jax.jit, static_argnames=("chunks",))
def _head_jit(x, final_norm, lm_head, chunks=1):
    """[L, D] -> logits [L, V], a slice of the vocabulary at a time."""
    with HIGHEST():
        x = rms_norm(x, final_norm)
        width = lm_head.shape[1] // chunks
        return jnp.concatenate([
            x @ f32(jax.lax.dynamic_slice_in_dim(lm_head, i * width, width,
                                                 1))
            for i in range(chunks)], -1)


def row_logits(params, tokens, top_k=None, norm_topk_prob=False):
    """tokens [L] -> logits [L, V]."""
    vocab = params["lm_head"].shape[1]
    chunks = 8 if vocab % 8 == 0 and vocab >= 8192 else 1
    return _head_jit(hidden(params, tokens, top_k, norm_topk_prob),
                     params["final_norm"], params["lm_head"], chunks)


def logits(params, tokens, top_k=None, norm_topk_prob=False):
    """tokens [B, L] -> logits [B, L, V], a sequence at a time."""
    return jnp.stack([row_logits(params, row, top_k, norm_topk_prob)
                      for row in np.asarray(tokens)])


# -- serving ------------------------------------------------------------------

@jax.jit
def _gaps_jit(row_logits, tokens, first):
    """At each position from `first` on, how far the reference logit of the
    token that follows in `tokens` lies under the reference's largest."""
    row = row_logits[:-1]
    nxt = tokens[1:]
    own = jnp.take_along_axis(row, nxt[:, None], -1)
    gap = jnp.max(row, -1) - own[:, 0]
    rank = jnp.sum(row > own, -1)
    keep = jnp.arange(row.shape[0]) >= first
    return jnp.where(keep, gap, 0.0), jnp.where(keep, rank, 0)


def served_token_gaps(params, prompt, output, bucket: int = 256):
    """One full forward over prompt + served output.  Returns, for every
    generated position, (gap, rank): the reference's largest logit minus its
    logit of the served token, and how many tokens the reference ranks above
    the served one (0 = the reference's own greedy choice).  The sequence is
    padded at its end to a multiple of `bucket` so that a few compiled
    programs serve every length; attention is causal, so what follows a
    position cannot change it."""
    seq = list(prompt) + list(output)
    tokens = jnp.asarray(seq + [0] * (-len(seq) % bucket), jnp.int32)
    gap, rank = _gaps_jit(row_logits(params, tokens), tokens,
                          len(prompt) - 1)
    first, last = len(prompt) - 1, len(seq) - 1
    # one transfer each: iterating a device array fetches element by element
    return (np.asarray(gap)[first:last].tolist(),
            np.asarray(rank)[first:last].tolist())
