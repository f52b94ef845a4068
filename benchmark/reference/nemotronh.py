"""Plain reference for Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16):
the forward pass in straightforward `jax.numpy`, float32 throughout, matrix
multiplications at `highest` precision, no kernel, no cache, no chunks, no
batching of lanes, no dispatch of tokens to experts: the state-space
recurrence is a `lax.scan` over POSITIONS, attention is a masked softmax,
and every held expert multiplies every token, the result weighted by the
router's weights (zero for a token that did not choose it).

The equations (h the residual stream; every norm an RMSNorm with eps 1e-5;
a layer is ONE part, named by its letter of the pattern
`MEMEM*EMEMEM*...`):

  h_0 = E[token]
  layer:  h = h + part(norm(h))
  M:      [z | x B C | dt] = u W_in  (4096 | 4096 + 2 x 8 x 128 | 64)
          xBC_t = silu(b + sum_k w[k] xBC_{t - 3 + k})   (depthwise, causal,
          4 taps, zeros before the sequence)
          x_t [64, 64], B_t and C_t [8, 128];  dt_t = softplus(dt_t +
          dt_bias) [64];  A = -exp(A_log) [64]
          head j of group g = j // 8:
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S [64, 128]
            y_t = S_t C_t + D_j x_t
          y = y * silu(z), RMSNorm over each group's 512 columns, times a
          learned scale; W_out
  *:      q_j = u W_q[j], k_i = u W_k[i], v_i = u W_v[i]  (32 query heads
          over 2 key/value heads of 128); causal softmax of q . k 128^-0.5;
          concat_j(o_j) W_o
  E:      s = sigmoid(u W_r) in float32; the 6 experts of largest s + bias;
          weights s of the chosen / their sum x 2.5;
          sum_e weight_e W_down[e] relu(W_up[e] u)^2
          + Ws_down relu(Ws_up u)^2
  logits = norm(h_L) W_head

Departures from the published description, each at its line below: no
rotary embedding in `attention` (the family's published code applies none;
the config's `rope_theta` and `partial_rotary_factor` are carried unused),
and the SHARE in `experts`: the parameters hold experts `experts_offset` to
`experts_offset` + held of the router's 128, the router chooses among all
128, and an assignment to an expert that is not held adds nothing (it is
the other chip's part of the sum; the shared expert is on both and is
counted here).  What the config leaves open is listed under `assumed` in
`benchmark/configs/nemotron-3-nano-30b-a3b.json`.

It takes the parameters in the program's own layout (a stack of leaves a
kind of layer: `mixers`, `attns`, `experts`) in whatever dtype they are
served from and upcasts a slice at a time (an expert, a head's scores, a
block of a projection's columns), so that a 6.7k-token request runs in the
memory a replica has left beside its weights, pools and states.  It shares
no code with the program (`ray_tpu/`); the float32 upcast and the gap
between two rows of logits are `benchmark/reference/axk1.py`'s.

Not in the parameters, so constants here (the published values): the
pattern (the parameters hold its first layers), eps, the mixer's groups,
the experts a token chooses and their scale.  `SIZES` holds them by hidden
size; the nano model of the rehearsal and the tests (hidden size 64) has
its own groups and top-k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.axk1 import HIGHEST, _gaps_jit, f32

PUBLISHED = {
    "pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "eps": 1e-5, "groups": 8, "top_k": 6, "routed_scale": 2.5,
    # the first expert the parameters hold, of the router's
    "experts_offset": 0,
}
SIZES = {2688: PUBLISHED, 64: dict(PUBLISHED, groups=2, top_k=4)}
STACKS = {"M": "mixers", "*": "attns", "E": "experts"}
WIDTH_BLOCK = 2048      # columns of a projection upcast and multiplied at once


def sizes_of(params, **over) -> tuple:
    """The constants for these parameters as a hashable tuple of pairs."""
    d = params["tok_embed"].shape[1]
    return tuple(sorted(dict(SIZES.get(d, PUBLISHED), **over).items()))


def pattern_of(params, sizes) -> str:
    """The letters of the layers the parameters hold: the pattern's first."""
    n = sum(params[k]["norm"].shape[0] for k in STACKS.values()
            if k in params)
    pattern = dict(sizes)["pattern"][:n]
    for letter, stack in STACKS.items():
        held = params[stack]["norm"].shape[0] if stack in params else 0
        if pattern.count(letter) != held:
            raise ValueError(f"{held} layers {letter!r} in the parameters, "
                             f"the pattern's first {n} have "
                             f"{pattern.count(letter)}")
    return pattern


def rms_norm(x, scale, eps):
    x = f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(scale)


def matmul(x, w):
    """x [L, D] @ w [D, F] (any dtype), `WIDTH_BLOCK` columns at a time."""
    width = w.shape[1]
    if width <= WIDTH_BLOCK:
        return x @ f32(w)
    edges = list(range(0, width, WIDTH_BLOCK)) + [width]
    return jnp.concatenate(
        [x @ f32(w[:, a:b]) for a, b in zip(edges, edges[1:])], -1)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def attention(u, p, s: dict):
    """u [L, D] (normed) -> [L, D], a query head's [L, L] scores at a
    time."""
    length = u.shape[0]
    d, h, k = p["wq"].shape
    kh = p["wk"].shape[1]
    # (no rotary embedding on q and key: the departure the docstring names)
    q = matmul(u, p["wq"].reshape(d, h * k)).reshape(length, h, k)
    key = matmul(u, p["wk"].reshape(d, kh * k)).reshape(length, kh, k)
    val = matmul(u, p["wv"].reshape(d, kh * k)).reshape(length, kh, k)
    causal = jnp.tril(jnp.ones((length, length), bool))
    rep = h // kh

    def one(j):                          # query head j over kv head j // rep
        qj = jax.lax.dynamic_index_in_dim(q, j, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(key, j // rep, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(val, j // rep, 1, keepdims=False)
        scores = (qj @ kj.T) * k ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return probs @ vj                                     # [L, K]

    out = jax.lax.map(one, jnp.arange(h))                     # [H, L, K]
    return jnp.moveaxis(out, 0, 1).reshape(length, h * k) \
        @ f32(p["wo"].reshape(h * k, d))


def mixer(u, p, s: dict):
    """u [L, D] (normed) -> [L, D]: the recurrence one position at a
    time."""
    length = u.shape[0]
    heads = p["A_log"].shape[0]
    d_ssm = p["ssm_norm"].shape[0]
    taps, width = p["conv_w"].shape
    groups = s["groups"]
    gn = (width - d_ssm) // 2
    n, hp = gn // groups, d_ssm // heads
    proj = matmul(u, p["w_in"])             # columns [z | x | B | C | dt]
    z = proj[:, :d_ssm]
    xbc = proj[:, d_ssm:d_ssm + width]
    dt = proj[:, d_ssm + width:]

    padded = jnp.concatenate([jnp.zeros((taps - 1, width)), xbc], 0)
    w = f32(p["conv_w"])
    xbc = jax.nn.silu(f32(p["conv_b"]) + sum(
        w[i] * padded[i:i + length] for i in range(taps)))
    x = xbc[:, :d_ssm].reshape(length, heads, hp)
    bm = xbc[:, d_ssm:d_ssm + gn].reshape(length, groups, n)
    cm = xbc[:, d_ssm + gn:].reshape(length, groups, n)
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))              # [L, H]
    a = -jnp.exp(f32(p["A_log"]))
    skip = f32(p["D"])
    per = heads // groups

    def step(state, row):                # state [H, P, N]
        x_t, b_t, c_t, dt_t = row
        b_h, c_h = jnp.repeat(b_t, per, 0), jnp.repeat(c_t, per, 0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", state, c_h) + skip[:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, hp, n)), (x, bm, cm, dt))
    y = y.reshape(length, d_ssm) * jax.nn.silu(z)
    y = y.reshape(length, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + s["eps"])
    return matmul(y.reshape(length, d_ssm) * f32(p["ssm_norm"]), p["w_out"])


def experts(u, p, s: dict):
    """u [L, D] (normed) -> [L, D]: every held expert over every token,
    weighted by what the router gave it (0 where the token chose others)."""
    length = u.shape[0]
    n_routed = p["router"].shape[1]
    score = jax.nn.sigmoid(u @ f32(p["router"]))              # [L, E]
    _, chosen = jax.lax.top_k(score + f32(p["router_bias"]), s["top_k"])
    weight = jnp.take_along_axis(score, chosen, -1)
    weight = weight / jnp.sum(weight, -1, keepdims=True) * s["routed_scale"]
    dense = jnp.sum(jax.nn.one_hot(chosen, n_routed) * weight[..., None], 1)
    # (the share: experts offset .. offset + held of the router's n_routed;
    # what the router gave the others is the other chip's to add)
    held, offset = p["w_down"].shape[0], s["experts_offset"]

    def one(i, acc):
        # (`w_up_t` [E, F, D]: a Linear's [out, in], as published)
        up = f32(jax.lax.dynamic_index_in_dim(p["w_up_t"], i,
                                              keepdims=False))
        down = f32(jax.lax.dynamic_index_in_dim(p["w_down"], i,
                                                keepdims=False))
        mine = jax.lax.dynamic_index_in_dim(dense, offset + i, 1)   # [L, 1]
        return acc + mine * (relu2(u @ up.T) @ down)

    routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))
    return routed + matmul(relu2(matmul(u, p["ws_up"])), p["ws_down"])


PARTS = {"M": mixer, "*": attention, "E": experts}


@functools.partial(jax.jit, static_argnames=("letter", "sizes"))
def _layer_jit(h, stack, layer, letter, sizes):
    s = dict(sizes)
    with HIGHEST():
        p = {k: jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
             for k, v in stack.items()}
        return h + PARTS[letter](rms_norm(h, p["norm"], s["eps"]), p, s)


def hidden(params, tokens, **over):
    """tokens [L] -> the residual stream behind the last layer [L, D]."""
    sizes = sizes_of(params, **over)
    h = f32(params["tok_embed"][jnp.asarray(tokens, jnp.int32)])
    seen = dict.fromkeys(STACKS, 0)
    for letter in pattern_of(params, sizes):
        h = _layer_jit(h, params[STACKS[letter]], seen[letter], letter,
                       sizes)
        seen[letter] += 1
    return h


@functools.partial(jax.jit, static_argnames=("chunks", "eps"))
def _head_jit(x, final_norm, lm_head, chunks, eps):
    """[L, D] -> logits [L, V], a slice of the vocabulary at a time."""
    with HIGHEST():
        x = rms_norm(x, final_norm, eps)
        width = lm_head.shape[1] // chunks
        return jnp.concatenate([
            x @ f32(jax.lax.dynamic_slice_in_dim(lm_head, i * width, width,
                                                 1))
            for i in range(chunks)], -1)


def row_logits(params, tokens, rows=None, **over):
    """tokens [L] -> logits [L, V] (over the vocabulary slice the
    parameters hold); with `rows` (start, count), of those rows alone."""
    x = hidden(params, tokens, **over)
    if rows is not None:
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
    vocab = params["lm_head"].shape[1]
    chunks = 8 if vocab % 8 == 0 and vocab >= 8192 else 1
    return _head_jit(x, params["final_norm"], params["lm_head"], chunks,
                     dict(sizes_of(params, **over))["eps"])


def logits(params, tokens, **over):
    """tokens [B, L] -> logits [B, L, V], a sequence at a time."""
    return jnp.stack([row_logits(params, row, **over)
                      for row in np.asarray(tokens)])


def served_token_gaps(params, prompt, output, bucket: int = 512, **over):
    """One full forward over prompt + served output, from token 0.  Returns,
    for every generated position, (gap, rank): the reference's largest
    logit minus its logit of the served token, and how many tokens the
    reference ranks above the served one (0 = the reference's own greedy
    choice).  The sequence is padded at its end to a multiple of `bucket`
    so that a few compiled programs serve every length; attention, the
    convolution and the recurrence are causal, so what follows a position
    cannot change it.  Only the generated positions' rows go through the
    head."""
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
