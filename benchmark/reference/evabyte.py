"""Plain reference for EvaByte (EvaByte/EvaByte): the forward pass in
straightforward `jax.numpy`, float32 throughout, matrix multiplications at
`highest` precision, no kernel, no cache, no batching, and EVA attention
(Zheng et al., arXiv:2302.04542) written as what it is over a whole
sequence: one mask over exact keys, a second over chunk summaries, one
softmax over both.

The equations (W the window, C the chunk, d the head size, s = d^-0.5,
w(i) = floor(i / W); every product in float32):

  norm(x) = x / sqrt(mean(x^2) + 1e-5) * (1 + g)
  h = norm1(x);  q_i, k_i, v_i = h_i W_q, h_i W_k, h_i W_v   (H heads of d)
  q_i, k_i = rope(q_i, i), rope(k_i, i)        theta 100000, pairs (j, j+d/2)
  for every chunk c = positions [Cc, Cc + C), per head:
    kbar_c = sum_j softmax_j(mu . k_j) k_j     vbar_c = sum_j softmax_j(phi . k_j) v_j
  position i attends to E_i = { j : w(j) = w(i), j <= i }   (exact keys) and
                        R_i = { c : C c < W w(i) }     (every chunk of every
                                        EARLIER window, none of its own):
    Z_i = sum_{E_i} exp(s q_i.k_j) + sum_{R_i} exp(s q_i.kbar_c)
    o_i = (sum_{E_i} exp(s q_i.k_j) v_j + sum_{R_i} exp(s q_i.kbar_c) vbar_c) / Z_i
  x = x + concat_heads(o) W_o
  x = x + (silu(h2 W_gate) * (h2 W_up)) W_down,  h2 = norm2(x)
  logits = norm_f(x_L) W_head      [num_pred_heads x vocab] columns a position;
                                   the first `vocab` are the next byte's

It takes the parameters in the program's own layout (layers stacked on a
leading dimension), in whatever dtype they are served from, and upcasts
them a group of heads or a slice of the feed-forward at a time, and computes
attention a group of heads and a block of queries at a time, so that a
15k-position request runs in the memory a replica has left beside 12.9 GB
of weights and cache.  It shares no code with the program
(`ray_tpu/models/decoder.py`, `ray_tpu/ops/`).

Not in the parameters, so constants here (the published values): theta,
eps, and the window and the chunk, which are taken by their published ratio
to the head size (2048 = 16 x 128, 16 = 128 / 8) unless given, so that a
rehearsal at a nano head size closes windows too.  What the published
config does not fix is listed in `benchmark/configs/evabyte.json` under
`assumed`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")
EPS = 1e-5
THETA = 100000.0
WINDOW_PER_HEAD_DIM = 16    # window_size 2048 over head size 128
HEAD_DIM_PER_CHUNK = 8      # head size 128 over chunk_size 16
HEAD_GROUP = 4          # heads whose scores are alive at once
QUERY_BLOCK = 512       # query rows whose scores are alive at once
WIDTH_BLOCK = 1376      # columns of the feed-forward's hidden layer at once


def f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps=EPS):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + f32(scale))


def rope(x, theta=THETA):
    """x [L, H, K] at positions 0..L-1; pairs (i, i + K/2) rotate by
    position * theta^(-2i/K)."""
    length, _, k = x.shape
    inv = jnp.asarray(theta ** (-np.arange(0, k, 2, dtype=np.float64) / k),
                      jnp.float32)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., k // 2:], x[..., :k // 2]], -1)
    return x * cos + rotated * sin


def shape_of(params, window=None, chunk=None) -> tuple:
    """(window, chunk): as given, else by the published ratio to the head
    size."""
    d = params["blocks"]["wq"].shape[-1]
    return (window or WINDOW_PER_HEAD_DIM * d,
            chunk or max(1, d // HEAD_DIM_PER_CHUNK))


def pooled(k, v, mu, phi, chunk: int):
    """Rotated keys and values [L, H, d] (L a multiple of `chunk`) -> the
    chunk summaries (kbar, vbar) [L / chunk, H, d]."""
    length, heads, d = k.shape
    kc, vc = (a.reshape(length // chunk, chunk, heads, d) for a in (k, v))
    wk = jax.nn.softmax(jnp.sum(kc * f32(mu), -1), axis=1)     # [N, C, H]
    wv = jax.nn.softmax(jnp.sum(kc * f32(phi), -1), axis=1)
    return (jnp.sum(wk[..., None] * kc, 1), jnp.sum(wv[..., None] * vc, 1))


def visible(qpos, length: int, window: int, chunk: int):
    """The two masks of query positions `qpos` [Q] over a sequence of
    `length` (a multiple of `chunk`): exact [Q, L] (same window, not after
    the query) and summaries [Q, L / chunk] (chunks of earlier windows)."""
    kpos = jnp.arange(length)
    exact = ((kpos[None, :] // window == qpos[:, None] // window)
             & (kpos[None, :] <= qpos[:, None]))
    first = jnp.arange(length // chunk) * chunk
    return exact, first[None, :] < window * (qpos[:, None] // window)


def attention(x, p, window: int, chunk: int):
    """x [L, D] (L a multiple of `chunk`); p one layer's attention leaves.
    Residual added."""
    length, d_model = x.shape
    _, heads, d = p["wq"].shape
    scale = d ** -0.5
    h = rms_norm(x, p["attn_norm"])
    group = math.gcd(heads, HEAD_GROUP)
    block = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length

    def heads_of(w, g, axis):
        return f32(jax.lax.dynamic_slice_in_dim(w, g * group, group, axis))

    def one_group(g, out):
        q = rope(jnp.einsum("ld,dhk->lhk", h, heads_of(p["wq"], g, 1)))
        k = rope(jnp.einsum("ld,dhk->lhk", h, heads_of(p["wk"], g, 1)))
        v = jnp.einsum("ld,dhk->lhk", h, heads_of(p["wv"], g, 1))
        kbar, vbar = pooled(k, v, heads_of(p["eva_mu"], g, 0),
                            heads_of(p["eva_phi"], g, 0), chunk)
        w_o = heads_of(p["wo"], g, 0)

        def one_block(b, out):
            rows = lambda a: jax.lax.dynamic_slice_in_dim(
                a, b * block, block, 0)
            exact, behind = visible(b * block + jnp.arange(block), length,
                                    window, chunk)
            s_exact = jnp.where(
                exact[None], jnp.einsum("qhk,shk->hqs", rows(q), k) * scale,
                -jnp.inf)
            s_behind = jnp.where(
                behind[None],
                jnp.einsum("qhk,shk->hqs", rows(q), kbar) * scale, -jnp.inf)
            probs = jax.nn.softmax(
                jnp.concatenate([s_exact, s_behind], -1), -1)
            o = (jnp.einsum("hqs,shk->qhk", probs[..., :length], v)
                 + jnp.einsum("hqs,shk->qhk", probs[..., length:], vbar))
            add = o.reshape(block, group * d) @ w_o.reshape(group * d,
                                                            d_model)
            return jax.lax.dynamic_update_slice_in_dim(
                out, rows(out) + add, b * block, 0)

        return jax.lax.fori_loop(0, length // block, one_block, out)

    return x + jax.lax.fori_loop(0, heads // group, one_group,
                                 jnp.zeros_like(x))


def swiglu(h, w_gate, w_up, w_down):
    """(silu(h W_gate) * (h W_up)) W_down, a slice of the hidden width at
    a time; weights [D, F] / [F, D] in any dtype."""
    width = w_gate.shape[1]
    block = WIDTH_BLOCK if width % WIDTH_BLOCK == 0 else width

    def one(i, acc):
        gate = h @ f32(jax.lax.dynamic_slice_in_dim(w_gate, i * block,
                                                    block, 1))
        up = h @ f32(jax.lax.dynamic_slice_in_dim(w_up, i * block, block, 1))
        return acc + (jax.nn.silu(gate) * up) @ f32(
            jax.lax.dynamic_slice_in_dim(w_down, i * block, block, 0))

    return jax.lax.fori_loop(0, width // block, one, jnp.zeros_like(h))


_ATTENTION_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "eva_mu",
                     "eva_phi")


@functools.partial(jax.jit, static_argnames=("window", "chunk"))
def _layer_jit(x, blocks, layer, window, chunk):
    with HIGHEST():
        x = attention(x, {k: blocks[k][layer] for k in _ATTENTION_LEAVES},
                      window, chunk)
        h2 = rms_norm(x, blocks["mlp_norm"][layer])
        return x + swiglu(h2, blocks["w_gate"][layer], blocks["w_up"][layer],
                          blocks["w_down"][layer])


def hidden(params, tokens, window=None, chunk=None):
    """tokens [L] -> the last layer's output [L, D], before the final norm;
    one small program dispatched per layer.  The sequence is padded at its
    end to whole chunks (attention is causal and a chunk is seen only from
    later windows, so what follows a position cannot change it)."""
    window, chunk = shape_of(params, window, chunk)
    tokens = jnp.asarray(tokens, jnp.int32)
    length = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -length % chunk))
    x = f32(params["tok_embed"][tokens])
    for layer in range(params["blocks"]["attn_norm"].shape[0]):
        x = _layer_jit(x, params["blocks"], layer, window, chunk)
    return x[:length]


@jax.jit
def _head_jit(x, final_norm, lm_head):
    with HIGHEST():
        return rms_norm(x, final_norm) @ f32(lm_head)


def row_logits(params, tokens, rows=None, **kw):
    """tokens [L] -> logits [L, num_pred_heads * V], the next token's head
    first; with `rows` (start, count), of those rows alone."""
    x = hidden(params, tokens, **kw)
    if rows is not None:
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
    return _head_jit(x, params["final_norm"], params["lm_head"])


def logits(params, tokens, **kw):
    """tokens [B, L] -> logits [B, L, num_pred_heads * V], a sequence at a
    time."""
    return jnp.stack([row_logits(params, row, **kw)
                      for row in np.asarray(tokens)])


# -- serving ------------------------------------------------------------------

@jax.jit
def _gaps_jit(rows, nxt):
    """How far the reference logit of the token that follows each row lies
    under the reference's largest, and how many tokens rank above it."""
    own = jnp.take_along_axis(rows, nxt[:, None], -1)
    return jnp.max(rows, -1) - own[:, 0], jnp.sum(rows > own, -1)


def served_token_gaps(params, prompt, output, bucket: int = 512, **kw):
    """One full forward over prompt + served output.  Returns, for every
    generated position, (gap, rank): the reference's largest logit of the
    NEXT byte's head (the first `vocab` columns, which decoding samples)
    minus its logit of the served token, and how many tokens the reference
    ranks above the served one (0 = the reference's own greedy choice).
    The sequence is padded at its end to a multiple of `bucket` so that a
    few compiled programs serve every length; what follows a position
    cannot change it.  Only the generated positions' rows go through the
    head."""
    seq = list(prompt) + list(output)
    first, n = len(prompt) - 1, len(output)
    vocab = params["tok_embed"].shape[0]
    tokens = jnp.asarray(seq + [0] * (-len(seq) % bucket), jnp.int32)
    count = -(-n // 256) * 256          # rows through the head, bucketed
    start = max(0, min(first, tokens.shape[0] - count))
    rows = row_logits(params, tokens, rows=(start, min(count,
                                                       tokens.shape[0])),
                      **kw)[:, :vocab]
    nxt = jnp.asarray((seq + [0] * tokens.shape[0])[
        start + 1:start + 1 + rows.shape[0]], jnp.int32)
    gap, rank = _gaps_jit(rows, nxt)
    lo = first - start
    # one transfer each: iterating a device array fetches element by element
    return (np.asarray(gap)[lo:lo + n].tolist(),
            np.asarray(rank)[lo:lo + n].tolist())
