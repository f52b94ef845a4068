"""Plain reference for Falcon-H1 (tiiuae/Falcon-H1-34B-Instruct): the forward
pass in straightforward `jax.numpy`, float32 throughout, matrix
multiplications at `highest` precision, no kernel, no cache, no chunks, no
batching of lanes: the state-space recurrence is a `lax.scan` over
POSITIONS, attention is plain causal softmax attention.

The equations (h the residual stream, D = hidden size; every norm an
RMSNorm with eps 1e-5; `m_*` the config's multipliers):

  h_0 = E[token] m_emb
  block:  u = norm(h)
          h = h + Attn(u m_attn_in) m_attn_out + SSM(u m_ssm_in) m_ssm_out
          v = norm(h)
          h = h + W_down(silu((W_gate v) m_0) * (W_up v)) m_1
  Attn:   q_j = u W_q[j], k_i = (u W_k[i]) m_key, v_i = u W_v[i]  (20 query
          heads over 4 key/value heads of 128); RoPE (theta 1e11, the whole
          head, rotate-half pairing: dimension i with i + 64) on q and k;
          causal softmax of q . k 128^-0.5; concat_j(o_j) W_o
  SSM:    [z | x B C | dt] = u W_in  (4096 | 4096 + 2 x 2 x 256 | 32), the
          segments z, x, B, C, dt each times its `ssm_multipliers` entry
          xBC_t = silu(b + sum_k w[k] xBC_{t - 3 + k})   (depthwise, causal,
          4 taps, zeros before the sequence)
          x_t [32, 128], B_t and C_t [2, 256];  dt_t = softplus(dt_t +
          dt_bias) [32];  A = -exp(A_log) [32]
          head j of group g = j // 16:
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S [128, 256]
            y_t = S_t C_t + D_j x_t
          y = y * silu(z), RMSNorm over each group's 2,048 columns, times a
          learned scale; W_out
  logits = (norm(h_L) W_head) m_head

Departures from the published description: none known.  What the config
leaves open is listed under `assumed` in
`benchmark/configs/falcon-h1-34b.json` (the order of W_in's segments, no
clamp on dt, the pairing of the rotation).

It takes the parameters in the program's own layout (`blocks`, layers
stacked on a leading dimension) in whatever dtype they are served from and
upcasts a slice at a time, so that a 2.8k-token request runs in the memory
a replica has left beside its weights, pools and states.  It shares no code
with the program (`ray_tpu/`); the float32 upcast and the gap between two
rows of logits are `benchmark/reference/axk1.py`'s.

Not in the parameters, so constants here (the published values): the
multipliers, eps, theta, the number of groups.  `SIZES` holds them by hidden
size; the nano model of the rehearsal and the tests (hidden size 64) has the
same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.axk1 import HIGHEST, _gaps_jit, f32

PUBLISHED = {
    "eps": 1e-5, "theta": 1e11, "groups": 2,
    "m_emb": 5.656854249492381, "m_head": 0.0078125,
    "m_key": 0.011048543456039804, "m_attn_in": 1.0,
    "m_attn_out": 0.0375, "m_ssm_in": 0.25,
    "m_ssm_out": 0.08838834764831845,
    "m_mlp": (0.1767766952966369, 0.011160714285714284),
    "m_ssm": (0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
              0.3535533905932738),
    # None: the state stays float32; a dtype's name: rounded to it after
    # every step (benchmark/tools/falconh1_precision.py's second reading)
    "state_dtype": None,
}
SIZES = {5120: PUBLISHED, 64: PUBLISHED}
WIDTH_BLOCK = 2048      # columns of a projection upcast and multiplied at once


def sizes_of(params, **over) -> tuple:
    """The constants for these parameters as a hashable tuple of pairs."""
    d = params["tok_embed"].shape[1]
    return tuple(sorted(dict(SIZES.get(d, PUBLISHED), **over).items()))


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


def rope(x, theta: float):
    """x [L, H, K], positions 0..L-1: dimension i turns with i + K / 2."""
    length, _, k = x.shape
    half = k // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, p, s: dict):
    """u [L, D] (the normed input times m_attn_in) -> [L, D]."""
    length = u.shape[0]
    d, h, k = p["wq"].shape
    kh = p["wk"].shape[1]
    q = rope(matmul(u, p["wq"].reshape(d, h * k)).reshape(length, h, k),
             s["theta"])
    key = rope(matmul(u, p["wk"].reshape(d, kh * k)).reshape(length, kh, k)
               * s["m_key"], s["theta"])
    val = matmul(u, p["wv"].reshape(d, kh * k)).reshape(length, kh, k)
    causal = jnp.tril(jnp.ones((length, length), bool))
    rep = h // kh

    def one(i):                          # the `rep` query heads of kv head i
        qi = jax.lax.dynamic_slice_in_dim(q, i * rep, rep, 1)
        ki, vi = key[:, i], val[:, i]
        scores = jnp.einsum("qhk,sk->hqs", qi, ki) * k ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return jnp.einsum("hqs,sk->qhk", probs, vi)

    out = jnp.concatenate([one(i) for i in range(kh)], 1)     # [L, H, K]
    return out.reshape(length, h * k) @ f32(p["wo"].reshape(h * k, d))


def mixer(u, p, s: dict):
    """u [L, D] (the normed input times m_ssm_in) -> [L, D]: the recurrence
    one position at a time."""
    length = u.shape[0]
    heads = p["A_log"].shape[0]
    d_ssm = p["ssm_norm"].shape[0]
    taps, width = p["conv_w"].shape
    groups = s["groups"]
    gn = (width - d_ssm) // 2
    n, hp = gn // groups, d_ssm // heads
    mz, mx, mb, mc, mdt = s["m_ssm"]
    proj = matmul(u, p["w_in"])
    z = proj[:, :d_ssm] * mz
    xbc = proj[:, d_ssm:d_ssm + width] * jnp.concatenate([
        jnp.full((d_ssm,), mx), jnp.full((gn,), mb), jnp.full((gn,), mc)])
    dt = proj[:, d_ssm + width:] * mdt

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
        if s["state_dtype"]:
            # (`reduce_precision`: a cast there and back is one the
            # compiler may drop, and on the chip does)
            kind = jnp.finfo(s["state_dtype"])
            state = jax.lax.reduce_precision(state, kind.nexp, kind.nmant)
        y_t = jnp.einsum("hpn,hn->hp", state, c_h) + skip[:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, hp, n)), (x, bm, cm, dt))
    y = y.reshape(length, d_ssm) * jax.nn.silu(z)
    y = y.reshape(length, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + s["eps"])
    return matmul(y.reshape(length, d_ssm) * f32(p["ssm_norm"]), p["w_out"])


def feed_forward(v, p, s: dict):
    """W_down(silu((W_gate v) m_0) * (W_up v)) m_1, a slice of the hidden
    width at a time."""
    m_gate, m_down = s["m_mlp"]
    width = p["w_gate"].shape[1]
    block = WIDTH_BLOCK if width % WIDTH_BLOCK == 0 else width

    def one(i, acc):
        def cols(w, axis):
            return f32(jax.lax.dynamic_slice_in_dim(w, i * block, block,
                                                    axis))
        gate = jax.nn.silu((v @ cols(p["w_gate"], 1)) * m_gate)
        return acc + (gate * (v @ cols(p["w_up"], 1))) @ cols(p["w_down"], 0)

    return jax.lax.fori_loop(0, width // block, one,
                             jnp.zeros_like(v)) * m_down


@functools.partial(jax.jit, static_argnames=("sizes",))
def _layer_jit(h, blocks, layer, sizes):
    s = dict(sizes)
    with HIGHEST():
        p = {k: jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
             for k, v in blocks.items()}
        u = rms_norm(h, p["attn_norm"], s["eps"])
        h = (h + attention(u * s["m_attn_in"], p, s) * s["m_attn_out"]
             + mixer(u * s["m_ssm_in"], p, s) * s["m_ssm_out"])
        v = rms_norm(h, p["mlp_norm"], s["eps"])
        return h + feed_forward(v, p, s)


def hidden(params, tokens, **over):
    """tokens [L] -> the residual stream behind the last block [L, D]."""
    sizes = sizes_of(params, **over)
    h = f32(params["tok_embed"][jnp.asarray(tokens, jnp.int32)]) \
        * dict(sizes)["m_emb"]
    for layer in range(params["blocks"]["attn_norm"].shape[0]):
        h = _layer_jit(h, params["blocks"], layer, sizes)
    return h


@functools.partial(jax.jit, static_argnames=("chunks", "eps", "m_head"))
def _head_jit(x, final_norm, lm_head, chunks, eps, m_head):
    """[L, D] -> logits [L, V], a slice of the vocabulary at a time."""
    with HIGHEST():
        x = rms_norm(x, final_norm, eps)
        width = lm_head.shape[1] // chunks
        return jnp.concatenate([
            x @ f32(jax.lax.dynamic_slice_in_dim(lm_head, i * width, width,
                                                 1))
            for i in range(chunks)], -1) * m_head


def row_logits(params, tokens, rows=None, **over):
    """tokens [L] -> logits [L, V] (over the vocabulary slice the
    parameters hold); with `rows` (start, count), of those rows alone."""
    x = hidden(params, tokens, **over)
    if rows is not None:
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], 0)
    s = dict(sizes_of(params, **over))
    vocab = params["lm_head"].shape[1]
    chunks = 8 if vocab % 8 == 0 and vocab >= 8192 else 1
    return _head_jit(x, params["final_norm"], params["lm_head"], chunks,
                     s["eps"], s["m_head"])


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
