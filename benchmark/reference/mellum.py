"""Plain reference for Mellum2-12B-A2.5B (`model_type` mellum): forward pass,
loss with the routers' balancing term, gradients and AdamW in
straightforward `jax.numpy`, float32 throughout, matrix multiplications at
`highest` precision, no kernel, no scan, no `top_k` primitive, no dispatch:
attention a masked softmax over the keys a block of queries can see, every
held expert on every token weighted by the router's weight for it (zero
where it was not chosen), the held experts eight to a batched product
(written as sixteen products in a Python loop the four layer programs took
326 s to compile for a v5e and 3 s to run).

The equations (the configuration's; every norm an RMSNorm with eps 1e-6 and
a plain learned scale; no bias anywhere):

  x_0 = E[token]
  a layer:  x = x + attn(N1(x));  x = x + ffn(N2(x))
  attention on h = N1(x):
    q_i = h W_q[i]  (32 heads of 128);  k_j = h W_k[j], v_j = h W_v[j]
    (4 heads of 128; head i reads i // 8); q and k rotated (rotate-half)
    a window layer: by theta^(-2i/128), S_t = {s : t - window < s <= t}
    a full layer:   by YaRN's blended frequencies, the cosines and sines
                    times `attention_factor`, S_t = {s : s <= t}
    o_i = softmax_{s in S_t}(q_i . k_s * 128^-0.5) v;  out = concat(o) W_o
  feed-forward on h = N2(x):
    p = softmax(float32(h) W_r) over all E experts
    chosen = the k experts of largest p (ties: the lower index)
    w_e = p_e / sum_chosen p
    ffn(h) = sum over the HELD chosen experts e of
             w_e W_down[e] (silu(h W_gate[e]) * (h W_up[e]))
    (a share holds experts `first` .. `first + held`; what falls on the
    others is another chip's part of the sum and adds nothing here; the
    gradient is this sum's as it stands, so a share's router hears the
    task through the held experts alone)
  logits = N(x_L) W_head
  loss = mean over every position but a sequence's last of the next
         token's cross-entropy  +  0.01 * sum over the layers of
         E sum_e f_e P_e,  f_e the share of the batch's T x k choices that
         chose e, P_e the mean of p_e over the batch's T tokens (all E).

It takes the parameters in the program's own layout (`blocks`, layers
stacked on a leading dimension) and shares no code with the program
(`ray_tpu/models/`, `ray_tpu/ops/`).  A batch of 2 x 8,192 tokens at the
published widths fits a chip beside the float32 parameters, gradients and
AdamW moments (9.5 GB) because nothing here holds more than a layer's
intermediates: `loss_and_grad` runs the layers one program each, forward
keeping each layer's input, then backward through `jax.vjp` of one layer at
a time, attention a block of queries at a time under `jax.checkpoint` (the
32 x 8192 x 8192 scores of one sequence are 8.6 GB; a block is 512 queries
on a full layer, 0.5 GB of scores, and a window's on a window layer, which
see two windows of keys).  The balancing loss
couples the sequences of a batch (f and P are the batch's), so the forward
pass over all micro-batches comes first and gives every layer's f; P is a
sum over tokens, so with f in hand a micro-batch's share of the loss and
its gradient are exact.

Not in the parameters, so constants here (`SIZES`, by hidden size: the
published values at 2,304, the configuration's `rehearsal_fields` at 64):
the order of the layers' kinds (S S S F repeated), eps, theta, the window,
YaRN's numbers, the experts a token takes, the first expert held.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")
AUX_WEIGHT = 0.01

SIZES = {
    2304: dict(eps=1e-6, theta=500000.0, window=1024, top_k=8, first=0,
               yarn=dict(factor=16.0, original=8192, beta_fast=32.0,
                         beta_slow=1.0, attention_factor=1.2772588722239782),
               norm_topk=True, q_block=512),
    64: dict(eps=1e-6, theta=10000.0, window=9, top_k=4, first=0,
             yarn=dict(factor=4.0, original=32, beta_fast=32.0,
                       beta_slow=1.0, attention_factor=1.1386294361119891),
             norm_topk=True, q_block=16),
}


def sizes_of(params) -> dict:
    return SIZES[params["final_norm"].shape[0]]


def is_full(layer: int) -> bool:
    """S S S F repeated: every fourth layer attends the whole context."""
    return layer % 4 == 3


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def frequencies(dim: int, theta: float, yarn=None) -> np.ndarray:
    """The rotation's dim / 2 frequencies: theta^(-2i/dim), or YaRN's blend
    of those and those divided by `factor`: a dimension that turns more
    than `beta_fast` times over the `original` positions keeps its own,
    one that turns less than `beta_slow` times is divided, and between the
    two dimensions where that happens the blend is linear."""
    own = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return own.astype(np.float32)

    def dimension_turning(turns):
        return dim * math.log(yarn["original"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dimension_turning(yarn["beta_fast"])), 0)
    high = min(math.ceil(dimension_turning(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    divided = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (own / yarn["factor"] * divided
            + own * (1 - divided)).astype(np.float32)


def rotate(x, freqs, factor: float = 1.0):
    """x [B, L, H, K] rotated at positions 0 .. L - 1 (rotate-half: the
    head's two halves are the pairs' two parts)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend_block(q, k, v, q0: int, k0: int, window: int):
    """Queries q [B, n, H, K] at positions q0.. over keys k, v [B, m, G, K]
    at positions k0..: head i reads group i // (H / G)."""
    b, n, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(b, n, g, h // g, d)
    scores = jnp.einsum("bqgrd,bsgd->bgrqs", q, k) / math.sqrt(d)
    t = q0 + jnp.arange(n)[:, None]
    s = k0 + jnp.arange(k.shape[1])[None, :]
    seen = s <= t
    if window:
        seen = seen & (t - s < window)
    scores = jnp.where(seen, scores, -jnp.inf)
    out = jnp.einsum("bgrqs,bsgd->bqgrd", jax.nn.softmax(scores, -1), v)
    return out.reshape(b, n, h, d)


def attention(q, k, v, window: int, q_block: int):
    """Causal attention [B, L, H, K], a block of queries at a time over the
    keys it can see, each block's scores made again in the backward pass
    (`jax.checkpoint`), never all L x L of them."""
    length = q.shape[1]
    if window:      # a window of queries sees two windows of keys
        q_block = max(q_block, window)
    out = []
    for q0 in range(0, length, q_block):
        q1 = min(q0 + q_block, length)
        k0 = max(0, q0 - window + 1) if window else 0
        out.append(jax.checkpoint(
            functools.partial(_attend_block, q0=q0, k0=k0, window=window))(
                q[:, q0:q1], k[:, k0:q1], v[:, k0:q1]))
    return jnp.concatenate(out, 1)


def route(h, router, top_k: int, norm_topk: bool):
    """(p [T, E] the router's probabilities, chosen [T, E] bool, w [T, E]
    what each expert counts for: zero where it was not chosen)."""
    p = jax.nn.softmax(h @ router, -1)
    e = p.shape[-1]
    ahead = (p[:, None, :] > p[:, :, None]) | (
        (p[:, None, :] == p[:, :, None])
        & (jnp.arange(e)[None, None, :] < jnp.arange(e)[None, :, None]))
    chosen = jnp.sum(ahead, -1) < top_k
    w = jnp.where(chosen, p, 0.0)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    return p, chosen, w


EXPERTS_AT_ONCE = 8


def experts(h, w, p, first: int):
    """sum over the held experts of w_e expert_e(h), h [T, D], w [T, E]:
    every held expert on every token, the router's weight (zero where the
    expert was not chosen) on its hidden row; `EXPERTS_AT_ONCE` experts a
    batched product (their hidden rows are 235 MB in float32 at 8,192
    tokens, and the backward holds several)."""
    out = jnp.zeros_like(h)
    for lo in range(0, p["w_down"].shape[0], EXPERTS_AT_ONCE):
        part = slice(lo, lo + EXPERTS_AT_ONCE)
        hidden = (jax.nn.silu(jnp.einsum("td,edf->etf", h, p["w_gate"][part]))
                  * jnp.einsum("td,edf->etf", h, p["w_up"][part]))
        weighted = hidden * w[:, first + lo:first + lo + hidden.shape[0]].T[
            :, :, None]
        out = out + jnp.einsum("etf,efd->td", weighted, p["w_down"][part])
    return out


def layer(p, x, full: bool, sizes: dict):
    """One layer on x [B, L, D]; p one layer's weights.  Returns (x, the
    tokens that chose each expert [E], the sum over the tokens of the
    router's probabilities [E])."""
    s = sizes
    h = rms_norm(x, p["attn_norm"], s["eps"])
    q = jnp.einsum("bld,dhk->blhk", h, p["wq"])
    k = jnp.einsum("bld,dhk->blhk", h, p["wk"])
    v = jnp.einsum("bld,dhk->blhk", h, p["wv"])
    freqs = frequencies(q.shape[-1], s["theta"], s["yarn"] if full else None)
    factor = s["yarn"]["attention_factor"] if full else 1.0
    q, k = rotate(q, freqs, factor), rotate(k, freqs, factor)
    attn = attention(q, k, v, 0 if full else s["window"], s["q_block"])
    x = x + jnp.einsum("blhk,hkd->bld", attn, p["wo"])
    h = rms_norm(x, p["mlp_norm"], s["eps"]).reshape(-1, x.shape[-1])
    probs, chosen, w = route(h, p["router"], s["top_k"], s["norm_topk"])
    x = x + experts(h, w, p, s["first"]).reshape(x.shape)
    return x, jnp.sum(chosen, 0).astype(jnp.float32), jnp.sum(probs, 0)


def head(params, x, sizes):
    return rms_norm(x, params["final_norm"], sizes["eps"]) @ params["lm_head"]


def nll(all_logits, tokens):
    """Mean next-token cross-entropy; the last position predicts nothing."""
    logp = jax.nn.log_softmax(all_logits[:, :-1], -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return -jnp.mean(picked)


def balance(chose, prob_sum, tokens: int, top_k: int):
    """E sum_e f_e P_e of a layer's counts [E] and summed probabilities [E]
    over `tokens` tokens."""
    e = chose.shape[0]
    return e * jnp.sum(chose / (tokens * top_k) * prob_sum / tokens)


def _layer_of(blocks, i):
    return jax.tree.map(lambda a: a[i], blocks)


def logits_and_aux(params, tokens, sizes=None):
    """tokens [B, L] -> (logits [B, L, V], the layers' balancing losses
    summed), all layers in one trace (nano sizes; `loss_by_layer` where
    that program would be too large)."""
    with HIGHEST():
        params = f32(params)
        s = sizes or sizes_of(params)
        x = params["tok_embed"][tokens]
        aux = 0.0
        for i in range(params["blocks"]["wq"].shape[0]):
            x, chose, prob_sum = layer(_layer_of(params["blocks"], i), x,
                                       is_full(i), s)
            aux = aux + balance(chose, prob_sum, tokens.size, s["top_k"])
        return head(params, x, s), aux


def logits(params, tokens, sizes=None):
    return logits_and_aux(params, tokens, sizes)[0]


def loss(params, tokens, sizes=None):
    all_logits, aux = logits_and_aux(params, tokens, sizes)
    return nll(all_logits, tokens) + AUX_WEIGHT * aux


# -- the same arithmetic, one program a layer ---------------------------------

def _hashable(sizes: dict):
    return tuple(sorted((k, _hashable(v) if isinstance(v, dict) else v)
                        for k, v in sizes.items()))


def _sizes(frozen) -> dict:
    return {k: _sizes(v) if isinstance(v, tuple) else v for k, v in frozen}


@jax.jit
def _embed_jit(table, tokens):
    return table.astype(jnp.float32)[tokens]


def _layer_program(own, x, full, frozen):
    with HIGHEST():
        return layer(f32(own), x, full, _sizes(frozen))


@functools.partial(jax.jit, static_argnames=("full", "frozen"))
def _layer_jit(blocks, i, x, full, frozen):
    return _layer_program(_layer_of(blocks, i), x, full, frozen)


def _head_loss(final_norm, lm_head, x, tokens, frozen):
    with HIGHEST():
        p = f32({"final_norm": final_norm, "lm_head": lm_head})
        return nll(head(p, x, _sizes(frozen)), tokens)


_head_loss_jit = jax.jit(_head_loss, static_argnames=("frozen",))


def _forward(params, tokens, micro_batch: int, sizes=None):
    """The forward pass over every micro-batch: (each micro-batch's tokens,
    for each the inputs of its layers and of the head, its cross-entropy,
    and for each layer the batch's counts and summed probabilities)."""
    frozen = _hashable(sizes or sizes_of(params))
    n_layers = params["blocks"]["wq"].shape[0]
    parts, inputs, ces = [], [], []
    chose = [0.0] * n_layers
    prob_sum = [0.0] * n_layers
    for at in range(0, tokens.shape[0], micro_batch):
        part = tokens[at:at + micro_batch]
        xs = [_embed_jit(params["tok_embed"], part)]
        for i in range(n_layers):
            x, c, p = _layer_jit(params["blocks"], i, xs[-1], is_full(i),
                                 frozen)
            xs.append(x)
            chose[i], prob_sum[i] = chose[i] + c, prob_sum[i] + p
        ces.append(_head_loss_jit(params["final_norm"], params["lm_head"],
                                  xs[-1], part, frozen))
        parts.append(part)
        inputs.append(xs)
    return parts, inputs, ces, chose, prob_sum


def _total(parts, ces, chose, prob_sum, n_tokens: int, top_k: int) -> float:
    n = sum(p.shape[0] for p in parts)
    ce = sum(float(c) * p.shape[0] / n for c, p in zip(ces, parts))
    aux = sum(float(balance(c, p, n_tokens, top_k))
              for c, p in zip(chose, prob_sum))
    return ce + AUX_WEIGHT * aux


def loss_by_layer(params, tokens, micro_batch: int, sizes=None) -> float:
    """`loss` over tokens [B, L], `micro_batch` sequences at a time and a
    layer a program (every sequence carries the same number of targets, so
    the mean of the micro-batch means is the mean; the balancing loss is
    the whole batch's)."""
    parts, _, ces, chose, prob_sum = _forward(params, tokens, micro_batch,
                                              sizes)
    top_k = (sizes or sizes_of(params))["top_k"]
    return _total(parts, ces, chose, prob_sum, tokens.size, top_k)


# -- gradients and AdamW ------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("frozen",))
def _head_grad_jit(final_norm, lm_head, x, tokens, weight, frozen):
    """d(weight * cross-entropy) in the head's two leaves and its input."""
    fn = functools.partial(_head_loss, tokens=tokens, frozen=frozen)
    return jax.vjp(fn, final_norm, lm_head, x)[1](weight)


@functools.partial(jax.jit, static_argnames=("full", "frozen"),
                   donate_argnums=(2,))
def _layer_grad_jit(blocks, i, grads, x, dx, d_prob_sum, full, frozen):
    """One layer backward: `grads` (the blocks' stacked gradients, given up
    and returned) with the layer's own added at i, and dx of its input.
    The layer's outputs count as dx says and, the summed probabilities, as
    the balancing loss's f says."""
    fn = functools.partial(_layer_program, full=full, frozen=frozen)
    (_, chose, _), back = jax.vjp(fn, _layer_of(blocks, i), x)
    d_own, dx = back((dx, jnp.zeros_like(chose), d_prob_sum))
    return jax.tree.map(lambda g, d: g.at[i].add(d.astype(g.dtype)),
                        grads, d_own), dx


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_grad_jit(grad, tokens, dx):
    return grad.at[tokens].add(dx.astype(grad.dtype))


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_jit(total, part):
    return jax.tree.map(jnp.add, total, part)


def loss_and_grad(params, tokens, micro_batch: int, sizes=None):
    """Loss and gradient over the whole batch, `micro_batch` sequences and
    one layer at a time (the module's docstring says why that is exact)."""
    s = sizes or sizes_of(params)
    frozen = _hashable(s)
    parts, inputs, ces, chose, prob_sum = _forward(params, tokens,
                                                   micro_batch, s)
    n, n_tokens = tokens.shape[0], tokens.size
    n_layers = len(chose)
    e = chose[0].shape[0]
    # d(0.01 * aux_i) / d(prob_sum_i): E f_i / T
    d_prob_sum = [AUX_WEIGHT * e * c / (n_tokens * s["top_k"]) / n_tokens
                  for c in chose]
    grads = jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params)
    for part, xs in zip(parts, inputs):
        weight = jnp.float32(part.shape[0] / n)
        d_norm, d_head, dx = _head_grad_jit(
            params["final_norm"], params["lm_head"], xs[-1], part, weight,
            frozen)
        grads["final_norm"] = grads["final_norm"] + d_norm
        grads["lm_head"] = _add_jit(grads["lm_head"], d_head)
        for i in reversed(range(n_layers)):
            grads["blocks"], dx = _layer_grad_jit(
                params["blocks"], i, grads["blocks"], xs[i], dx,
                d_prob_sum[i], is_full(i), frozen)
        grads["tok_embed"] = _embed_grad_jit(grads["tok_embed"], part, dx)
    return _total(parts, ces, chose, prob_sum, n_tokens, s["top_k"]), grads


def adamw_init(params):
    zeros = lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), params)
    return {"m": zeros(), "v": zeros(), "t": 0}


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"),
                   donate_argnums=(0, 2, 3))
def _adamw_update(params, grads, m, v, t, *, lr, b1, b2, eps, wd):
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def new(p, m_, v_):
        m_hat = m_ / (1 - b1 ** t)
        v_hat = v_ / (1 - b2 ** t)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)

    return jax.tree.map(new, params, m, v), m, v


def adamw_step(params, grads, state, *, learning_rate, b1=0.9, b2=0.999,
               eps=1e-8, weight_decay=1e-4):
    """Decoupled weight decay (Loshchilov and Hutter), optax.adamw's
    keywords and defaults, the decay applied to every parameter as the
    program does.  The parameters and the moments given are given up: the
    new ones take their memory (three trees of 2.4 GB at the published
    widths, beside the gradients)."""
    t = state["t"] + 1
    params, m, v = _adamw_update(params, grads, state["m"], state["v"],
                                 jnp.float32(t), lr=learning_rate, b1=b1,
                                 b2=b2, eps=eps, wd=weight_decay)
    return params, {"m": m, "v": v, "t": t}
