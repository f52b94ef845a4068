"""Plain reference for the GPT-2 configurations: forward pass, loss, gradients
and AdamW in straightforward `jax.numpy`, float32 throughout, matrix
multiplications at `highest` precision (on a TPU a float32 matmul otherwise
runs in bf16 passes), no kernels, no cache, no scan, no batching tricks.

It follows the GPT-2 description (pre-norm blocks, learned positions, tanh
GELU, tied output embedding).  Departures, all of them the program's and
stated in the configuration files: no projection biases, the vocabulary
padded to 50304, the last position of a sequence carries no loss.  It takes
the parameters in the program's own layout (layers stacked on a leading
dimension) so that both sides can be given the very same weights, and it
shares no code with the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = functools.partial(jax.default_matmul_precision, "highest")


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(x, p):
    """One transformer block on x [B, L, D]; p holds one layer's weights."""
    length = x.shape[1]
    dh = p["wq"].shape[-1]
    h = layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    q = jnp.einsum("bld,dhk->bhlk", h, p["wq"])
    k = jnp.einsum("bld,dhk->bhlk", h, p["wk"])
    v = jnp.einsum("bld,dhk->bhlk", h, p["wv"])
    scores = jnp.einsum("bhqk,bhsk->bhqs", q, k) / jnp.sqrt(float(dh))
    causal = jnp.tril(jnp.ones((length, length), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqs,bhsk->bhqk", jax.nn.softmax(scores, -1), v)
    x = x + jnp.einsum("bhlk,hkd->bld", attn, p["wo"])
    h = layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    return x + gelu_new(h @ p["w_up"]) @ p["w_down"]


def embed(params, tokens):
    return params["tok_embed"][tokens] + params["pos_embed"][:tokens.shape[1]]


def head(params, x):
    x = layer_norm(x, params["final_ln_scale"], params["final_ln_bias"])
    return x @ params["tok_embed"].T


def logits(params, tokens):
    """tokens [B, L] -> logits [B, L, V], all layers unrolled in one trace
    (use `logits_by_layer` where that program would be too large)."""
    with HIGHEST():
        params = f32(params)
        x = embed(params, tokens)
        for i in range(params["blocks"]["wq"].shape[0]):
            x = block(x, jax.tree.map(lambda a: a[i], params["blocks"]))
        return head(params, x)


def nll(all_logits, tokens):
    """Mean next-token cross-entropy; the last position predicts nothing."""
    logp = jax.nn.log_softmax(all_logits[:, :-1], -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return -jnp.mean(picked)


def loss(params, tokens):
    return nll(logits(params, tokens), tokens)


# -- the same forward pass, one small program per layer ----------------------

@jax.jit
def _embed_jit(params, tokens):
    return embed(f32(params), tokens)


@jax.jit
def _block_jit(x, blocks, i):
    with HIGHEST():
        return block(x, f32(jax.tree.map(lambda a: a[i], blocks)))


@jax.jit
def _head_jit(params, x):
    with HIGHEST():
        return head(f32(params), x)


def logits_by_layer(params, tokens):
    """`logits`, dispatched layer by layer: the same arithmetic, but the
    compiled programs stay small however deep the model is."""
    x = _embed_jit({k: params[k] for k in ("tok_embed", "pos_embed")}, tokens)
    for i in range(params["blocks"]["wq"].shape[0]):
        x = _block_jit(x, params["blocks"], i)
    return _head_jit({k: params[k] for k in (
        "tok_embed", "final_ln_scale", "final_ln_bias")}, x)


_nll_jit = jax.jit(nll)


def loss_by_layer(params, tokens, micro_batch: int) -> float:
    """Mean loss over tokens [B, L], `micro_batch` sequences at a time (every
    sequence carries the same number of targets, so the mean of the
    micro-batch means is the mean)."""
    total = 0.0
    n = 0
    for i in range(0, tokens.shape[0], micro_batch):
        part = tokens[i:i + micro_batch]
        total += float(_nll_jit(logits_by_layer(params, part), part)) \
            * part.shape[0]
        n += part.shape[0]
    return total / n


# -- gradients and AdamW ------------------------------------------------------

_loss_and_grad = jax.jit(jax.value_and_grad(loss))


def loss_and_grad(params, tokens, micro_batch: int):
    """Loss and gradient over the whole batch, accumulated over micro-batches
    so that the [B, heads, L, L] scores of a plain attention fit."""
    n = tokens.shape[0]
    total, grads = 0.0, None
    for i in range(0, n, micro_batch):
        part = tokens[i:i + micro_batch]
        value, g = _loss_and_grad(params, part)
        w = part.shape[0] / n
        total += float(value) * w
        g = jax.tree.map(lambda a: a * w, g)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total, grads


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": 0}


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"))
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
    program does."""
    t = state["t"] + 1
    params, m, v = _adamw_update(params, grads, state["m"], state["v"],
                                 jnp.float32(t), lr=learning_rate, b1=b1,
                                 b2=b2, eps=eps, wd=weight_decay)
    return params, {"m": m, "v": v, "t": t}


# -- serving ------------------------------------------------------------------

@jax.jit
def _gaps_jit(all_logits, tokens, first):
    """At each position from `first` on, how far the reference logit of the
    token that follows in `tokens` lies under the reference's largest."""
    row = all_logits[0, :-1]
    nxt = tokens[0, 1:]
    gap = jnp.max(row, -1) - jnp.take_along_axis(row, nxt[:, None], -1)[:, 0]
    rank = jnp.sum(row > jnp.take_along_axis(row, nxt[:, None], -1), -1)
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
    room = params["pos_embed"].shape[0]
    padded = seq + [0] * min(-len(seq) % bucket, room - len(seq))
    tokens = jnp.asarray([padded], jnp.int32)
    gap, rank = _gaps_jit(logits_by_layer(params, tokens), tokens,
                          len(prompt) - 1)
    first, last = len(prompt) - 1, len(seq) - 1
    # one transfer each: iterating a device array fetches element by element
    return (np.asarray(gap)[first:last].tolist(),
            np.asarray(rank)[first:last].tolist())
