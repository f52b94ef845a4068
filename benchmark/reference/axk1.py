"""Plain reference for A.X-K1 (skt/A.X-K1, a DeepSeek-V3-shaped model): the
forward pass in straightforward `jax.numpy`, float32 throughout, matrix
multiplications at `highest` precision, no kernel, no cache, no sort, no
top-k primitive, no dispatch, and latent attention in its EXPANDED form:
every token's key and value of every head are made from its latent.

The equations (h the normed input; every norm an RMSNorm, eps 1e-6):

  c_q = norm(h W_qa)             q = c_q W_qb -> per head [q_nope | q_rope]
  [c | r] = h W_kva              c_kv = norm(c)    k_rope = rope(r), one for
                                                   all heads
  [k_nope | v]_head = c_kv W_kvb
  score = (q_nope . k_nope + rope(q_rope) . k_rope) * s, causal softmax
  x = x + concat_heads(p v) W_o
  rope: YaRN.  Frequencies f_i = theta^(-2i/d) and f_i / factor, blended by
        a linear ramp over the dimensions between the one that turns
        beta_fast times over the original positions and the one that turns
        beta_slow times;  s = (qk_nope + qk_rope)^-0.5 * m^2,
        m = 0.1 * mscale_all_dim * ln(factor) + 1;  the factor on cos and
        sin is mscale / mscale_all_dim = 1.
  first layer(s): x = x + (silu(h2 W_gate) * (h2 W_up)) W_down
  expert layers:  scores = sigmoid(float32(h2) W_g) over ALL routed experts
                  S = the k experts of largest score (ties: the lower index)
                  w_e = scores_e / sum_S scores * routed_scale
                  x = x + shared(h2) + sum_{e in S, e held here} w_e
                      expert_e(h2)          (both SwiGLUs as above)
  logits = norm(x_L) W_head

`topk_method` is "none" as published: no group limit, no correction bias.

It is given the same share of an expert-parallel deployment as the program:
the parameters hold the experts `first_held` to `first_held + held` of each
layer and a slice of the vocabulary; what the absent experts would add is
left out, here as there.  It takes the parameters in the program's own
layout (layers stacked on a leading dimension, `lead_blocks` for the dense
layers, `blocks` for the expert ones), in whatever dtype they are served
from, and upcasts them a group of heads, a slice of a feed-forward or a slice
of the vocabulary at a time, and computes attention a group of heads and a
block of queries at a time, so that a 16.8k-token request runs in the memory
a replica has left beside 11.4 GB of weights and cache.  It shares no code
with the program (`ray_tpu/models/decoder.py`, `ray_tpu/ops/`).

Not in the parameters, so constants here (the published values): experts per
token (one in 24 of the router's outputs: 8 of 192), the routed scale, eps,
and YaRN's numbers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")
EPS = 1e-6
ROUTED_SCALE = 2.5
EXPERTS_PER_TOKEN_ONE_IN = 24      # 8 of 192
YARN = {"theta": 10000.0, "factor": 32.0, "original": 4096,
        "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
        "mscale_all_dim": 1.0}
HEAD_GROUP = 8          # heads whose keys and values are alive at once
QUERY_BLOCK = 512       # query rows whose scores are alive at once
WIDTH_BLOCK = 2048      # columns of a feed-forward's hidden layer at once


def f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps=EPS):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(scale)


def yarn_inv_freq(dim: int, yarn: dict):
    """[dim / 2] rotary frequencies under YaRN."""
    theta, factor = yarn["theta"], yarn["factor"]
    own = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return own

    def dim_of(turns):      # the dimension that turns `turns` times
        return dim * math.log(yarn["original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim_of(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return own / factor * (1.0 - keep) + own * keep


def softmax_scale(qk_dim: int, yarn: dict) -> float:
    m = 1.0
    if yarn["factor"] > 1:
        m = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0
    return qk_dim ** -0.5 * m * m


def rope(x, yarn: dict):
    """x [L, H, K] at positions 0..L-1; pairs (i, i + K/2) rotate by
    position * frequency_i."""
    length, _, k = x.shape
    inv = jnp.asarray(yarn_inv_freq(k, yarn), jnp.float32)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., k // 2:], x[..., :k // 2]], -1)
    return x * cos + rotated * sin


def attention(x, p, yarn: dict):
    """x [L, D]; p one layer's attention leaves.  Expanded MLA, a group of
    heads and a block of queries at a time."""
    length, d = x.shape
    _, heads, qk = p["w_qb"].shape
    latent = p["kv_norm"].shape[0]
    rope_dim = p["w_kva"].shape[1] - latent
    nope = qk - rope_dim
    v_dim = p["w_kvb"].shape[2] - nope
    scale = softmax_scale(qk, yarn)
    h = rms_norm(x, p["attn_norm"])
    c_q = rms_norm(h @ f32(p["w_qa"]), p["q_norm"])
    kv = h @ f32(p["w_kva"])
    c_kv = rms_norm(kv[:, :latent], p["kv_norm"])
    k_rope = rope(kv[:, None, latent:], yarn)[:, 0]            # [L, R]
    group = math.gcd(heads, HEAD_GROUP)
    block = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length
    kpos = jnp.arange(length)

    def heads_of(w, g):     # [.., H, K] -> the group's [.., group, K]
        return f32(jax.lax.dynamic_slice_in_dim(w, g * group, group, 1))

    def one_group(g, out):
        q = jnp.einsum("lr,rhk->lhk", c_q, heads_of(p["w_qb"], g))
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], yarn)
        kvh = jnp.einsum("lc,chk->lhk", c_kv, heads_of(p["w_kvb"], g))
        k_nope, v = kvh[..., :nope], kvh[..., nope:]
        w_o = f32(jax.lax.dynamic_slice_in_dim(p["wo"], g * group, group, 0))

        def one_block(b, out):
            rows = lambda a: jax.lax.dynamic_slice_in_dim(
                a, b * block, block, 0)
            scores = (jnp.einsum("qhk,shk->hqs", rows(q_nope), k_nope)
                      + jnp.einsum("qhk,sk->hqs", rows(q_rope), k_rope)
                      ) * scale
            qpos = b * block + jnp.arange(block)
            scores = jnp.where(kpos[None, None, :] <= qpos[None, :, None],
                               scores, -jnp.inf)
            o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, -1), v)
            add = o.reshape(block, group * v_dim) @ w_o.reshape(
                group * v_dim, d)
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


def router_weights(h2, router, top_k: int, routed_scale=ROUTED_SCALE):
    """[L, E] weights over ALL routed experts: sigmoid scores, the top_k of
    them (ties: the lower index; by rank, not by sort: expert e's rank is
    the number of experts that beat it), normalised to sum to one, times
    the routed scale; 0 for the experts a token did not choose."""
    scores = jax.nn.sigmoid(h2 @ f32(router))
    n = scores.shape[-1]
    lower = jnp.arange(n)[None, :] < jnp.arange(n)[:, None]   # j < e

    def rows(s):                         # [R, E] -> weights [R, E]
        a, b = s[:, :, None], s[:, None, :]                  # e, j
        rank = jnp.sum((b > a) | ((b == a) & lower[None]), -1)
        chosen = jnp.where(rank < top_k, s, 0.0)
        return chosen / jnp.sum(chosen, -1, keepdims=True) * routed_scale

    length = scores.shape[0]
    block = 512 if length % 512 == 0 else length
    return jax.lax.map(rows, scores.reshape(length // block, block, n)
                       ).reshape(length, n)


def experts(x, p, layer, top_k: int, first_held: int = 0,
            routed_scale=ROUTED_SCALE):
    """x [L, D] through the expert layer `layer` of the stacked `p`,
    residual added: the shared expert, and of the routed experts those
    held here (`first_held` on), each on every token and masked by the
    router's weight for it."""
    h2 = rms_norm(x, p["mlp_norm"][layer])
    weights = router_weights(h2, p["router"][layer], top_k, routed_scale)
    y = swiglu(h2, p["ws_gate"][layer], p["ws_up"][layer],
               p["ws_down"][layer]) if "ws_gate" in p else jnp.zeros_like(x)

    def one(e, acc):
        out = swiglu(h2, p["w_gate"][layer, e], p["w_up"][layer, e],
                     p["w_down"][layer, e])
        return acc + jax.lax.dynamic_slice_in_dim(
            weights, first_held + e, 1, 1) * out

    return x + jax.lax.fori_loop(0, p["w_gate"].shape[1], one, y)


_ATTENTION_LEAVES = ("attn_norm", "w_qa", "q_norm", "w_qb", "w_kva",
                     "kv_norm", "w_kvb", "wo")


def _yarn(yarn):
    return dict(YARN, **dict(yarn or ()))


@functools.partial(jax.jit, static_argnames=("yarn",))
def _dense_layer_jit(x, blocks, layer, yarn):
    with HIGHEST():
        x = attention(x, {k: blocks[k][layer] for k in _ATTENTION_LEAVES},
                      _yarn(yarn))
        h2 = rms_norm(x, blocks["mlp_norm"][layer])
        return x + swiglu(h2, blocks["w_gate"][layer], blocks["w_up"][layer],
                          blocks["w_down"][layer])


@functools.partial(jax.jit, static_argnames=("top_k", "first_held", "yarn",
                                             "routed_scale"))
def _expert_layer_jit(x, blocks, layer, top_k, first_held, yarn,
                      routed_scale):
    with HIGHEST():
        x = attention(x, {k: blocks[k][layer] for k in _ATTENTION_LEAVES},
                      _yarn(yarn))
        return experts(x, blocks, layer, top_k, first_held, routed_scale)


def top_k_of(params) -> int:
    return max(1, params["blocks"]["router"].shape[-1]
               // EXPERTS_PER_TOKEN_ONE_IN)


def hidden(params, tokens, top_k=None, first_held=0, yarn=None,
           routed_scale=ROUTED_SCALE):
    """tokens [L] -> the last layer's output [L, D], before the final
    norm; one small program dispatched per layer.  `yarn`: a tuple of
    (name, value) pairs over `YARN`'s published numbers."""
    top_k = top_k or top_k_of(params)
    x = f32(params["tok_embed"][jnp.asarray(tokens, jnp.int32)])
    lead = params.get("lead_blocks")
    for layer in range(lead["attn_norm"].shape[0] if lead else 0):
        x = _dense_layer_jit(x, lead, layer, yarn)
    for layer in range(params["blocks"]["router"].shape[0]):
        x = _expert_layer_jit(x, params["blocks"], layer, top_k, first_held,
                              yarn, routed_scale)
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


# -- serving ------------------------------------------------------------------

@jax.jit
def _gaps_jit(rows, nxt):
    """How far the reference logit of the token that follows each row lies
    under the reference's largest, and how many tokens rank above it."""
    own = jnp.take_along_axis(rows, nxt[:, None], -1)
    return jnp.max(rows, -1) - own[:, 0], jnp.sum(rows > own, -1)


def served_token_gaps(params, prompt, output, bucket: int = 512, **kw):
    """One full forward over prompt + served output.  Returns, for every
    generated position, (gap, rank): the reference's largest logit minus its
    logit of the served token, and how many tokens the reference ranks above
    the served one (0 = the reference's own greedy choice).  The sequence is
    padded at its end to a multiple of `bucket` so that a few compiled
    programs serve every length (the cell's 16.5k to 16.8k tokens all
    make 16,896); attention is causal, so what follows a position cannot
    change it.  Only the generated positions' rows go through the head."""
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
    # one transfer each: iterating a device array fetches element by element
    return (np.asarray(gap)[lo:lo + n].tolist(),
            np.asarray(rank)[lo:lo + n].tolist())
