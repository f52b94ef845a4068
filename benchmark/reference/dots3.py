"""Plain reference for dots3-note-prev's language model: the forward pass in
straightforward `jax.numpy`, float32 throughout, matrix multiplications at
`highest` precision, no kernel, no cache, no `top_k` primitive, no dispatch,
latent attention in its EXPANDED form (every token's key and value of every
head are made from its latent) and the indexer's choice and the window as
masks over plain causal attention.

The equations (x the block's normed input at position t; every norm an
RMSNorm with eps 1e-5 but the indexer's LayerNorm; pre-norm residual blocks):

  both kinds of layer, each by its own sizes and rotation:
    c_q = r_q norm(x W_qa)      q_h = c_q W_qb[h] = [q_nope | q_rope]
    [c | r] = x W_kva           c_kv = r_kv norm(c)   k_rope = rope(r), one
                                                      for all heads
    [k_nope | v]_h = c_kv W_kvb[h]
    score_h(t, s) = (q_nope . k_nope + rope(q_rope) . k_rope) * (nope +
                    rope)^-0.5 over the positions S_t, softmax, o_h = p v_h
    g = sigmoid(x W_g) (one scalar a head)   out = concat_h(g_h o_h) W_o
    r_q = (D / q_lora_rank)^0.5, r_kv = (D / kv_lora_rank)^0.5
  a full layer:   S_t = the index_topk positions s <= t of largest
                  I(t, s) = sum_j w_j relu(q^I_j . k^I_s)   (all s <= t
                  while t < index_topk; ties: the lower position)
                  q^I_j = c_q W_iq[j], k^I = LayerNorm(x W_ik), both with
                  their first rope dims rotated (the layer's theta);
                  w = (x W_iw) Hi^-0.5 Di^-0.5
  a window layer: S_t = {s : t - window < s <= t}
  layer 0:        x = x + (silu(h2 W_gate) * (h2 W_up)) W_down
  expert layers:  s = sigmoid(float32(h2) W_r) over ALL routed experts
                  chosen = the k experts of largest s + b (b the selection
                  bias; ties: the lower index; no group limit)
                  w_e = s_e / sum_chosen s * routed_scale
                  x = x + shared(h2) + sum_{e chosen, e held here} w_e
                      expert_e(h2)
  logits = norm(x_L) W_head

It is given the same share of an expert-parallel deployment as the program
(`benchmark/reference/axk1.py` says how), takes the parameters in the
program's own layout (`lead_blocks`, `full_blocks`, `win_blocks`, layers
stacked on a leading dimension) in whatever dtype they are served from, and
computes attention a group of heads and a block of queries at a time, so
that a 17k-token request runs in the memory a replica has left beside its
weights and cache.  It shares no code with the program
(`ray_tpu/models/decoder.py`, `ray_tpu/ops/`); the feed-forward's slices,
the float32 upcast and the gap between two rows of logits are
`benchmark/reference/axk1.py`'s.

Not in the parameters, so constants here (the published values): the order
of the layers' kinds, eps, the two thetas, the window, index_topk, experts
per token (one in 32 of the router's outputs: 8 of 256), the routed scale,
the rescale.  `SIZES` holds them, and for the hidden size of the
configuration's `rehearsal_fields` (64) the nano values the CPU rehearsal
and the tests run at.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.axk1 import HIGHEST, _gaps_jit, f32, swiglu

PUBLISHED = {"eps": 1e-5, "theta": 8e7, "swa_theta": 5e4, "window": 513,
             "index_topk": 2048, "experts_one_in": 32, "routed_scale": 1.0,
             "rescale": True}
# by hidden size: the published model's, and the rehearsal's nano one
SIZES = {5120: PUBLISHED, 64: dict(PUBLISHED, window=9, index_topk=16)}
HEAD_GROUP = 8          # heads whose keys and values are alive at once
QUERY_BLOCK = 512       # query rows whose scores are alive at once
INDEX_BLOCK = 64        # and whose index scores, a head each, are


def sizes_of(params, **over) -> tuple:
    """The constants for these parameters as a hashable tuple of pairs."""
    d = params["tok_embed"].shape[1]
    return tuple(sorted(dict(SIZES.get(d, PUBLISHED), **over).items()))


def kinds_of(n_lead: int, n_full: int, n_win: int) -> list:
    """The published order of the layers as (stack, index in it): the
    leading dense layers (full attention), then F S S S repeated, a last F
    where the count asks."""
    out, full, win = [("lead", i) for i in range(n_lead)], 0, 0
    while full < n_full or win < n_win:
        if (full + win) % 4 == 0 and full < n_full or win == n_win:
            out.append(("full", full))
            full += 1
        else:
            out.append(("win", win))
            win += 1
    return out


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(scale)


def layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(scale) + f32(bias)


def rope(x, theta: float):
    """x [L, ..., K] at positions 0..L-1; pairs (i, i + K/2) rotate by
    position * theta^(-2i/K)."""
    length, k = x.shape[0], x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, k, 2, dtype=np.float64) / k),
                      jnp.float32)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (length,) + (1,) * (x.ndim - 2) + (k,)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1).reshape(shape)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1).reshape(shape)
    rotated = jnp.concatenate([-x[..., k // 2:], x[..., :k // 2]], -1)
    return x * cos + rotated * sin


def _blocks(length: int, block: int = QUERY_BLOCK) -> int:
    return block if length % block == 0 else length


def chosen(h, c_q, p, theta: float, rope_dim: int, topk: int):
    """[L, L] bool: for each position t of a full layer the positions it
    attends: the `topk` of largest index score under the causal mask (ties:
    the lower position), all of them while there are no more than that.
    By a sort of each row and a count, not by a top-k primitive."""
    length = h.shape[0]
    hi, di = p["w_iq"].shape[1:]
    q_i = jnp.einsum("lr,rhk->lhk", c_q, f32(p["w_iq"]))
    k_i = layer_norm(h @ f32(p["w_ik"]), p["ik_scale"], p["ik_bias"])
    q_i = jnp.concatenate([rope(q_i[..., :rope_dim], theta),
                           q_i[..., rope_dim:]], -1)
    k_i = jnp.concatenate([rope(k_i[..., :rope_dim], theta),
                           k_i[..., rope_dim:]], -1)
    w = (h @ f32(p["w_iw"])) * (hi ** -0.5 * di ** -0.5)
    block = _blocks(length, INDEX_BLOCK)
    kpos = jnp.arange(length)

    def rows(b):
        at = lambda a: jax.lax.dynamic_slice_in_dim(a, b * block, block, 0)
        scores = jnp.einsum("qh,qhs->qs", at(w), jax.nn.relu(
            jnp.einsum("qhk,sk->qhs", at(q_i), k_i)))
        causal = kpos[None, :] <= (b * block + jnp.arange(block))[:, None]
        if length <= topk:
            return causal
        scores = jnp.where(causal, scores, -jnp.inf)
        kth = -jnp.sort(-scores, axis=-1)[:, topk - 1:topk]
        above = scores > kth
        tied = scores == kth
        room = topk - jnp.sum(above, -1, keepdims=True)
        return causal & (above | (tied & (jnp.cumsum(tied, -1) <= room)))

    return jax.lax.map(rows, jnp.arange(length // block)).reshape(
        length, length)


def attention(x, p, theta: float, eps: float, rescale: bool, window: int = 0,
              topk: int = 0):
    """x [L, D]; p one layer's attention leaves.  Expanded MLA under the
    layer's mask, a group of heads and a block of queries at a time."""
    length, d = x.shape
    _, heads, qk = p["w_qb"].shape
    q_rank, latent = p["q_norm"].shape[0], p["kv_norm"].shape[0]
    rope_dim = p["w_kva"].shape[1] - latent
    nope = qk - rope_dim
    v_dim = p["w_kvb"].shape[2] - nope
    scale = qk ** -0.5
    h = rms_norm(x, p["attn_norm"], eps)
    c_q = rms_norm(h @ f32(p["w_qa"]), p["q_norm"], eps)
    kv = h @ f32(p["w_kva"])
    c_kv = rms_norm(kv[:, :latent], p["kv_norm"], eps)
    if rescale:
        c_q = c_q * (d / q_rank) ** 0.5
        c_kv = c_kv * (d / latent) ** 0.5
    k_rope = rope(kv[:, latent:], theta)                        # [L, R]
    kpos = jnp.arange(length)
    if topk:
        keep = chosen(h, c_q, p, theta, rope_dim, topk)
    else:
        keep = (kpos[None, :] <= kpos[:, None]) \
            & (kpos[None, :] > kpos[:, None] - window)
    gate = jax.nn.sigmoid(h @ f32(p["w_head_gate"]))            # [L, H]
    group = math.gcd(heads, HEAD_GROUP)
    block = _blocks(length)

    def heads_of(w, g, axis=1):
        return f32(jax.lax.dynamic_slice_in_dim(w, g * group, group, axis))

    def one_group(g, out):
        q = jnp.einsum("lr,rhk->lhk", c_q, heads_of(p["w_qb"], g))
        q_nope, q_rope = q[..., :nope], rope(q[..., nope:], theta)
        kvh = jnp.einsum("lc,chk->lhk", c_kv, heads_of(p["w_kvb"], g))
        k_nope, v = kvh[..., :nope], kvh[..., nope:]
        w_o = heads_of(p["wo"], g, 0)
        g_gate = jax.lax.dynamic_slice_in_dim(gate, g * group, group, 1)

        def one_block(b, out):
            rows = lambda a: jax.lax.dynamic_slice_in_dim(
                a, b * block, block, 0)
            scores = (jnp.einsum("qhk,shk->hqs", rows(q_nope), k_nope)
                      + jnp.einsum("qhk,sk->hqs", rows(q_rope), k_rope)
                      ) * scale
            scores = jnp.where(rows(keep)[None], scores, -jnp.inf)
            o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, -1), v)
            o = o * rows(g_gate)[:, :, None]
            add = o.reshape(block, group * v_dim) @ w_o.reshape(
                group * v_dim, d)
            return jax.lax.dynamic_update_slice_in_dim(
                out, rows(out) + add, b * block, 0)

        return jax.lax.fori_loop(0, length // block, one_block, out)

    return x + jax.lax.fori_loop(0, heads // group, one_group,
                                 jnp.zeros_like(x))


def router_weights(h2, router, bias, top_k: int, routed_scale: float):
    """[L, E] weights over ALL routed experts: sigmoid scores s, the top_k
    of largest s + bias (ties: the lower index; by rank, not by sort), the
    chosen s normalised to sum to one, times the routed scale; 0 for the
    experts a token did not choose."""
    scores = jax.nn.sigmoid(h2 @ f32(router))
    n = scores.shape[-1]
    lower = jnp.arange(n)[None, :] < jnp.arange(n)[:, None]   # j < e

    def rows(s):                         # [R, E] -> weights [R, E]
        biased = s + f32(bias)[None, :]
        a, b = biased[:, :, None], biased[:, None, :]         # e, j
        rank = jnp.sum((b > a) | ((b == a) & lower[None]), -1)
        picked = jnp.where(rank < top_k, s, 0.0)
        return picked / jnp.sum(picked, -1, keepdims=True) * routed_scale

    length = scores.shape[0]
    block = _blocks(length)
    return jax.lax.map(rows, scores.reshape(length // block, block, n)
                       ).reshape(length, n)


def experts(x, p, layer, top_k: int, first_held: int, eps: float,
            routed_scale: float):
    """x [L, D] through the expert layer `layer` of the stacked `p`,
    residual added: the shared expert, and of the routed experts those held
    here (`first_held` on), each on every token and masked by the router's
    weight for it."""
    h2 = rms_norm(x, p["mlp_norm"][layer], eps)
    weights = router_weights(h2, p["router"][layer], p["router_bias"][layer],
                             top_k, routed_scale)
    y = swiglu(h2, p["ws_gate"][layer], p["ws_up"][layer],
               p["ws_down"][layer]) if "ws_gate" in p else jnp.zeros_like(x)

    def one(e, acc):
        out = swiglu(h2, p["w_gate"][layer, e], p["w_up"][layer, e],
                     p["w_down"][layer, e])
        return acc + jax.lax.dynamic_slice_in_dim(
            weights, first_held + e, 1, 1) * out

    return x + jax.lax.fori_loop(0, p["w_gate"].shape[1], one, y)


_ATTENTION_LEAVES = ("attn_norm", "w_qa", "q_norm", "w_qb", "w_kva",
                     "kv_norm", "w_kvb", "w_head_gate", "wo", "w_iq",
                     "w_ik", "ik_scale", "ik_bias", "w_iw")


@functools.partial(jax.jit, static_argnames=("kind", "top_k", "first_held",
                                             "sizes"))
def _layer_jit(x, blocks, layer, kind, top_k, first_held, sizes):
    s = dict(sizes)
    with HIGHEST():
        leaves = {k: blocks[k][layer] for k in _ATTENTION_LEAVES
                  if k in blocks}
        if kind == "win":
            x = attention(x, leaves, s["swa_theta"], s["eps"], s["rescale"],
                          window=s["window"])
        else:
            x = attention(x, leaves, s["theta"], s["eps"], s["rescale"],
                          topk=s["index_topk"])
        if kind == "lead":
            h2 = rms_norm(x, blocks["mlp_norm"][layer], s["eps"])
            return x + swiglu(h2, blocks["w_gate"][layer],
                              blocks["w_up"][layer], blocks["w_down"][layer])
        return experts(x, blocks, layer, top_k, first_held, s["eps"],
                       s["routed_scale"])


def hidden(params, tokens, top_k=None, first_held=0, **over):
    """tokens [L] -> the last layer's output [L, D], before the final
    norm; one small program dispatched per layer.  `over`: constants other
    than `SIZES`'s (`window`, `index_topk`, ...)."""
    sizes = sizes_of(params, **over)
    lead, full, win = (params.get(k) for k in ("lead_blocks", "full_blocks",
                                               "win_blocks"))
    count = lambda b: b["attn_norm"].shape[0] if b else 0
    routers = (full or win)["router"].shape[-1]
    top_k = top_k or max(1, routers // dict(sizes)["experts_one_in"])
    x = f32(params["tok_embed"][jnp.asarray(tokens, jnp.int32)])
    stacks = {"lead": lead, "full": full, "win": win}
    for which, layer in kinds_of(count(lead), count(full), count(win)):
        x = _layer_jit(x, stacks[which], layer, which, top_k, first_held,
                       sizes)
    return x


@functools.partial(jax.jit, static_argnames=("chunks", "eps"))
def _head_jit(x, final_norm, lm_head, chunks=1, eps=1e-5):
    """[L, D] -> logits [L, V], a slice of the vocabulary at a time."""
    with HIGHEST():
        x = rms_norm(x, final_norm, eps)
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


def served_token_gaps(params, prompt, output, bucket: int = 512, **kw):
    """One full forward over prompt + served output.  Returns, for every
    generated position, (gap, rank): the reference's largest logit minus its
    logit of the served token, and how many tokens the reference ranks above
    the served one (0 = the reference's own greedy choice).  The sequence is
    padded at its end to a multiple of `bucket` so that a few compiled
    programs serve every length; attention is causal and the choice of a
    position's top-k looks at no later one, so what follows a position
    cannot change it.  Only the generated positions' rows go through the
    head."""
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
