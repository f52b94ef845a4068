"""State-space ops (Mamba-2's selective state space, SSD form): the causal
depthwise convolution with a carried tail, the one-token update of a
recurrent state and the chunked scan of a slice of tokens.

The recurrence, a head j of group g at position t (x_t [P], B_t and C_t [N]
shared by the group's heads, dt_t and A < 0 scalars a head):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T          S [P, N]
    y_t = S_t C_t

(the skip `D x_t`, the gate and the norm are the caller's).  dt_t = 0 is the
identity: S_t = S_{t-1} exactly, which is how a padded row and a lane that
is not stepped pass through.

The state lives in ONE float32 buffer [layers, slots, H, N, P] for an
engine's lifetime (inference/kv_cache.py: a slot a lane, and one more that
rows nobody has are sent to), stored transposed (N rows of P columns: the
columns of a head lie along the device's lanes, so x_t and y_t are rows,
and the sums over N run down the sublanes).  A head of P = 128 columns is
the lane width itself.  A narrower head (P = 64) would be padded to it, the
buffer twice its bytes in HBM and both kernels moving the padding, so
`state_shape` FOLDS 128 / P neighbouring heads of a group into one row of
lanes: the buffer is [layers, slots, H / f, N, f P], head f j + i's columns
at lanes i P to (i + 1) P of folded head j (x, dt x, the decays and y fold
by a reshape; B and C are the group's and do not change).  Both kernels
read the fold off the buffer's shape; at f = 1 nothing is folded and their
programs are what they were.  Both read and
write the slots of their rows in place (`input_output_aliases`), a block a
grid step, named by the layer and the row's slot through scalar prefetch:

  * `ssm_update` (T = 1): pure bandwidth, every number of the state read
    and written once, a few multiply-adds each on the vector unit;
  * `ssm_scan` (T > 1): chunks of `chunk` positions; inside a chunk the
    recurrence unrolled into products on the matrix unit
    (y = ((C B^T) * L) (dt x) + exp(cum) C S,  L[i, j] = exp(cum_i - cum_j)
    for i >= j, cum the running sum of dt A inside the chunk), between
    chunks the carried state in the kernel's output block.  What a chunk
    needs beside the products (L, the decayed C and B, the chunk's total
    decay) is made by XLA in front of the kernel, in float32.

On the CPU (tests) the same arithmetic runs as plain XLA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _interpret_kernels

_HIGHEST = jax.lax.Precision.HIGHEST
# Heads of one grid step of the update kernel: 8 x [256, 128] float32 is
# 1 MB a block, 4 MB with both directions double-buffered.
_UPDATE_HEADS = 8


def state_shape(heads: int, d_state: int, head_dim: int,
                groups: int = 1) -> tuple:
    """A slot's stored shape [H / f, N, f P] (module docstring): f
    neighbouring heads folded into one lane row where a head is narrower
    than the 128 lanes, else f = 1: [H, N, P].  The heads of a fold share
    B and C, so a GROUP's heads must divide by f (`_folded` refuses a
    buffer folded otherwise): where they do not, nothing is folded."""
    fold = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    if (heads // groups) % fold:
        fold = 1
    return heads // fold, d_state, fold * head_dim


def _folded(state, heads: int, head_dim: int, groups: int) -> int:
    """The fold of a state buffer for heads [H, P] in `groups` groups."""
    fold = state.shape[-1] // head_dim
    if fold > 1 and (heads // groups) % fold:
        raise ValueError(f"{heads // groups} heads a group do not fold by "
                         f"{fold} into rows of {state.shape[-1]} lanes")
    return fold


def _fold(v, fold: int):
    """[..., H, R, P] -> [..., H / f, R, f P]: f neighbouring heads side by
    side along the last axis."""
    if fold == 1:
        return v
    *lead, h, r, p = v.shape
    v = v.reshape(*lead, h // fold, fold, r, p)
    return jnp.moveaxis(v, -3, -2).reshape(*lead, h // fold, r, fold * p)


def _unfold(v, fold: int):
    """`_fold`'s inverse: [..., H / f, R, f P] -> [..., H, R, P]."""
    if fold == 1:
        return v
    *lead, h, r, p = v.shape
    v = v.reshape(*lead, h, r, fold, p // fold)
    return jnp.moveaxis(v, -2, -3).reshape(*lead, h * fold, r, p // fold)


def conv_tail(x, tail, conv_w, n_valid):
    """Causal depthwise convolution of a slice behind its lane's carried
    tail: the sum of the taps alone, in float32 (a bias and an activation
    are the caller's: Mamba-2's mixer has both, a gated short convolution
    neither).  x [B, T, C] (the slice's rows, the first `n_valid` [B] of
    them real); tail [B, K - 1, C] (the lane's last K - 1 rows before the
    slice); conv_w [K, C] (tap K - 1 meets the row's own position).
    Returns (conv [B, T, C] float32, the tail after the slice's real rows
    [B, K - 1, C] in tail's dtype: the old one where none was)."""
    k, t = conv_w.shape[0], x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = conv_w.astype(jnp.float32)
    y = sum(ext[:, i:i + t].astype(jnp.float32) * w[i] for i in range(k))
    rows = n_valid[:, None] + jnp.arange(k - 1, dtype=n_valid.dtype)
    new_tail = jnp.take_along_axis(ext, rows[:, :, None], axis=1)
    return y, new_tail.astype(tail.dtype)


# --------------------------------------------------------------------------
# T = 1: the update
# --------------------------------------------------------------------------

def _update_kernel(layer_ref, slot_ref, s_ref, dec_ref, dtx_ref, b_ref,
                   c_ref, so_ref, y_ref, *, heads: int):
    """One (row, block of `heads` heads of one group) grid step: every head's
    [N, P] state decayed, the outer product of B and dt x added, written
    back, and read against C."""
    del layer_ref, slot_ref             # only the index maps read them
    n, p = s_ref.shape[-2:]
    # B and C as columns, broadcast over the head's P columns.
    b_col = jnp.broadcast_to(b_ref[...], (p, n)).T
    c_col = jnp.broadcast_to(c_ref[...], (p, n)).T
    for h in range(heads):
        s = s_ref[h] * dec_ref[h:h + 1, :] + b_col * dtx_ref[h:h + 1, :]
        so_ref[h] = s
        y_ref[h:h + 1, :] = jnp.sum(s * c_col, axis=0, keepdims=True)


def ssm_update(state, x, dt, a, bm, cm, slots, layer=0, *,
               use_kernel: Optional[bool] = None,
               interpret: Optional[bool] = None):
    """One token a row: the states of rows `slots` at `layer` overwritten
    in place, and each read against its C.

    state [L, S, H, N, P] float32 (or folded: `state_shape`); x [B, H, P];
    dt [B, H] float32 (0: the row is not stepped); a [H] (negative); bm, cm
    [B, G, N]; slots [B] int32.  Returns (y [B, H, P] float32, state)."""
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
    if interpret is None:
        interpret = _interpret_kernels()
    return _ssm_update(state, x, dt, a, bm, cm, slots, layer,
                       use_kernel=use_kernel, interpret=interpret)


# (jitted, path and interpreter chosen outside: a process traces the call
# once a shape and a program lowers it once, not once in each of the layer
# bodies of each program that calls it: thirteen unscanned bodies in
# Nemotron-3's programs; PERF.md section 6, PRs 49 and 53)
@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def _ssm_update(state, x, dt, a, bm, cm, slots, layer, *, use_kernel: bool,
                interpret: bool):
    b, h, p = x.shape
    g, n = bm.shape[1:]
    fold = _folded(state, h, p, g)
    dec = jnp.broadcast_to(jnp.exp(dt * a)[..., None], (b, h, p))
    dtx = dt[..., None] * x.astype(jnp.float32)
    bm, cm = bm.astype(jnp.float32), cm.astype(jnp.float32)
    layer = jnp.asarray(layer, jnp.int32)
    slots = slots.astype(jnp.int32)
    if not use_kernel:
        rep = h // g
        s = _unfold(state[layer, slots], fold)                # [B, H, N, P]
        s = s * dec[:, :, None, :] + (
            jnp.repeat(bm, rep, axis=1)[..., None] * dtx[:, :, None, :])
        y = jnp.einsum("bhnp,bhn->bhp", s, jnp.repeat(cm, rep, axis=1),
                       precision=_HIGHEST)
        return y, state.at[layer, slots].set(_fold(s, fold), mode="drop")
    if fold > 1:
        # f heads a lane row: the kernel's head is the folded one
        h, p = h // fold, p * fold
        dec, dtx = dec.reshape(b, h, p), dtx.reshape(b, h, p)
    hb = min(_UPDATE_HEADS, h // g)
    if (h // g) % hb:
        raise ValueError(f"{h // g} heads a group in blocks of {hb}")
    per_group = h // g // hb

    def state_map(i, j, ly, sl):
        return (ly[0], sl[i], j, 0, 0)

    # A block of fewer heads than a tile's 8 sublanes has to be whole
    # trailing dimensions of its array: [B, H / hb, hb, P].
    split = hb % 8 != 0
    if split:
        dec, dtx = (v.reshape(b, h // hb, hb, p) for v in (dec, dtx))
    heads_block = (None, None, hb, p) if split else (None, hb, p)

    def head_map(i, j, ly, sl):
        return (i, j, 0, 0) if split else (i, j, 0)

    def group_map(i, j, ly, sl):
        return (i, j // per_group, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # layer, the rows' slots
        grid=(b, h // hb),
        in_specs=[pl.BlockSpec((None, None, hb, n, p), state_map),
                  pl.BlockSpec(heads_block, head_map),
                  pl.BlockSpec(heads_block, head_map),
                  pl.BlockSpec((None, None, 1, n), group_map),
                  pl.BlockSpec((None, None, 1, n), group_map)],
        out_specs=[pl.BlockSpec((None, None, hb, n, p), state_map),
                   pl.BlockSpec(heads_block, head_map)],
    )
    state, y = pl.pallas_call(
        functools.partial(_update_kernel, heads=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(dec.shape, jnp.float32)],
        input_output_aliases={2: 0},        # the state, after the scalars
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="ssm_update",
    )(layer.reshape(1), slots, state, dec, dtx, bm[:, :, None, :],
      cm[:, :, None, :])
    return (y if y.shape == x.shape else y.reshape(x.shape)), state


# --------------------------------------------------------------------------
# T > 1: the chunked scan
# --------------------------------------------------------------------------

def _chunk_terms(x, dt, a, bm, cm, chunk: int):
    """What a chunked scan multiplies, head-major and float32, the slice
    padded at its end to whole chunks with dt = 0 (the identity):
    dtx [B, H, T, P], bm and cm [B, G, T, N] as given, lmat
    [B, H, T / chunk, chunk, chunk], cs and bw [B, H, T, N] (C decayed from
    its chunk's start, B to its chunk's end), dec [B, H, T / chunk] (a
    chunk's whole decay)."""
    b, t, h, p = x.shape
    g = bm.shape[2]
    pad = -t % chunk
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
            v.ndim - 2)) for v in (x, dt, bm, cm))
    nc = (t + pad) // chunk
    dt = jnp.moveaxis(dt.astype(jnp.float32), 1, 2)           # [B, H, T]
    dtx = dt[..., None] * jnp.moveaxis(x.astype(jnp.float32), 1, 2)
    bm = jnp.moveaxis(bm.astype(jnp.float32), 1, 2)           # [B, G, T, N]
    cm = jnp.moveaxis(cm.astype(jnp.float32), 1, 2)
    cum = jnp.cumsum((dt * a[None, :, None]).reshape(b, h, nc, chunk), -1)
    total = cum[..., -1:]
    diff = cum[..., :, None] - cum[..., None, :]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    lmat = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    rep = h // g
    into = jnp.exp(cum).reshape(b, h, -1)[..., None]          # [B, H, T, 1]
    out_of = jnp.exp(total - cum).reshape(b, h, -1)[..., None]
    cs = jnp.repeat(cm, rep, axis=1) * into
    bw = jnp.repeat(bm, rep, axis=1) * out_of
    return dtx, bm, cm, lmat, cs, bw, jnp.exp(total[..., 0])


def _scan_kernel(layer_ref, slot_ref, fresh_ref, s_ref, dtx_ref, c_ref,
                 b_ref, l_ref, cs_ref, bw_ref, dec_ref, so_ref, y_ref, *,
                 fold: int = 1):
    """One (row, head, chunk) grid step; the chunks of a (row, head) run in
    order with the state carried in the output block, which is the head's
    [N, P] state in the buffer: written back once, after the last.  With
    `fold` > 1 the block is `fold` heads side by side along the lanes
    ([N, f P]; the decays' refs have a leading axis of `fold`): each head's
    products run over the whole lane row (the matrix unit is 128 columns
    wide whatever the head's are) and keep their own lanes."""
    del layer_ref, slot_ref
    i, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        so_ref[...] = jnp.where(fresh_ref[i] != 0, 0.0, s_ref[...])

    s, dtx = so_ref[...], dtx_ref[...]
    scores = jax.lax.dot_general(
        c_ref[...], b_ref[...], (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)

    def head(lmat, cs, bw, dec):
        y = (jnp.dot(scores * lmat, dtx, precision=_HIGHEST,
                     preferred_element_type=jnp.float32)
             + jnp.dot(cs, s, precision=_HIGHEST,
                       preferred_element_type=jnp.float32))
        return y, dec * s + jax.lax.dot_general(
            bw, dtx, (((0,), (0,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32)

    if fold == 1:
        y_ref[...], so_ref[...] = head(l_ref[...], cs_ref[...], bw_ref[...],
                                       dec_ref[...])
        return
    p = s.shape[-1] // fold
    y, new = head(l_ref[0], cs_ref[0], bw_ref[0], dec_ref[0])
    for f in range(1, fold):
        y_f, new_f = head(l_ref[f], cs_ref[f], bw_ref[f], dec_ref[f])
        own_y = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1) // p == f
        own_s = jax.lax.broadcasted_iota(jnp.int32, new.shape, 1) // p == f
        y, new = jnp.where(own_y, y_f, y), jnp.where(own_s, new_f, new)
    y_ref[...], so_ref[...] = y, new


def _scan_chunks(terms, s0):
    """The chunked scan as plain XLA: s0 [B, H, N, P] -> (y [B, H, T, P],
    the state after the last chunk)."""
    dtx, bm, cm, lmat, cs, bw, dec = terms
    b, h, t, p = dtx.shape
    nc, chunk = lmat.shape[2:4]
    rep = h // bm.shape[1]

    def by_chunk(v):                    # [B, X, T, W] -> [nc, B, X, chunk, W]
        return jnp.moveaxis(v.reshape(*v.shape[:2], nc, chunk, -1), 2, 0)

    scores = jnp.einsum("cbgin,cbgjn->cbgij", by_chunk(cm), by_chunk(bm),
                        precision=_HIGHEST)
    scores = jnp.repeat(scores, rep, axis=2) * jnp.moveaxis(lmat, 2, 0)

    def one(s, xs):
        scores, dtx, cs, bw, dec = xs
        y = (jnp.einsum("bhij,bhjp->bhip", scores, dtx, precision=_HIGHEST)
             + jnp.einsum("bhin,bhnp->bhip", cs, s, precision=_HIGHEST))
        s = dec[..., None, None] * s + jnp.einsum(
            "bhjn,bhjp->bhnp", bw, dtx, precision=_HIGHEST)
        return s, y

    s, y = jax.lax.scan(one, s0, (scores, by_chunk(dtx), by_chunk(cs),
                                  by_chunk(bw), jnp.moveaxis(dec, 2, 0)))
    return jnp.moveaxis(y, 0, 2).reshape(b, h, t, p), s


def ssm_sequence(x, dt, a, bm, cm, *, chunk: int):
    """A whole sequence from the zero state: x [B, T, H, P], dt [B, T, H],
    a [H], bm and cm [B, T, G, N] -> (y [B, T, H, P] float32, the final
    state [B, H, N, P])."""
    b, t, h, p = x.shape
    y, s = _scan_chunks(_chunk_terms(x, dt, a, bm, cm, chunk),
                        jnp.zeros((b, h, bm.shape[-1], p), jnp.float32))
    return jnp.moveaxis(y[:, :, :t], 1, 2), s


def ssm_scan(state, x, dt, a, bm, cm, slots, fresh, layer=0, *, chunk: int,
             use_kernel: Optional[bool] = None,
             interpret: Optional[bool] = None):
    """A slice of T tokens a row, from each row's state at `layer` (zero
    where `fresh`), which is overwritten in place with the state behind the
    slice's last stepped token.

    state [L, S, H, N, P] float32 (or folded: `state_shape`); x
    [B, T, H, P]; dt [B, T, H] float32 (0 at a padded row: the identity); a
    [H]; bm, cm [B, T, G, N]; slots [B] int32; fresh [B] bool.  Returns
    (y [B, T, H, P] float32, state)."""
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
    if interpret is None:
        interpret = _interpret_kernels()
    return _ssm_scan(state, x, dt, a, bm, cm, slots, fresh, layer,
                     chunk=chunk, use_kernel=use_kernel, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel",
                                             "interpret"))
def _ssm_scan(state, x, dt, a, bm, cm, slots, fresh, layer, *, chunk: int,
              use_kernel: bool, interpret: bool):
    b, t, h, p = x.shape
    n = bm.shape[-1]
    fold = _folded(state, h, p, bm.shape[2])
    terms = _chunk_terms(x, dt, a, bm, cm, chunk)
    layer = jnp.asarray(layer, jnp.int32)
    slots = slots.astype(jnp.int32)
    if not use_kernel:
        s0 = jnp.where(fresh[:, None, None, None], 0.0,
                       _unfold(state[layer, slots], fold))
        y, s = _scan_chunks(terms, s0)
        return (jnp.moveaxis(y[:, :, :t], 1, 2),
                state.at[layer, slots].set(_fold(s, fold), mode="drop"))
    dtx, bm, cm, lmat, cs, bw, dec = terms
    nc = lmat.shape[2]
    dec = jnp.broadcast_to(dec[..., None, None], (b, h, nc, 1, p * fold))
    # `held`: a block's extent along the heads of the decays' arrays (None:
    # the one head, squeezed); the kernel's head is the folded one.
    held = None
    if fold > 1:
        h, p, held, dtx = h // fold, p * fold, fold, _fold(dtx, fold)
    per_group = h // bm.shape[1]

    def state_map(i, j, c, ly, sl, fr):
        return (ly[0], sl[i], j, 0, 0)

    def rows_map(i, j, c, ly, sl, fr):
        return (i, j, c, 0)

    def group_map(i, j, c, ly, sl, fr):
        return (i, j // per_group, c, 0)

    def chunk_map(i, j, c, ly, sl, fr):
        return (i, j, c, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # layer, the rows' slots, which are fresh
        grid=(b, h, nc),
        in_specs=[pl.BlockSpec((None, None, None, n, p), state_map),
                  pl.BlockSpec((None, None, chunk, p), rows_map),
                  pl.BlockSpec((None, None, chunk, n), group_map),
                  pl.BlockSpec((None, None, chunk, n), group_map),
                  pl.BlockSpec((None, held, None, chunk, chunk), chunk_map),
                  pl.BlockSpec((None, held, chunk, n), rows_map),
                  pl.BlockSpec((None, held, chunk, n), rows_map),
                  pl.BlockSpec((None, held, None, 1, p), chunk_map)],
        out_specs=[pl.BlockSpec((None, None, None, n, p), state_map),
                   pl.BlockSpec((None, None, chunk, p), rows_map)],
    )
    state, y = pl.pallas_call(
        _scan_kernel if fold == 1 else functools.partial(_scan_kernel,
                                                         fold=fold),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(dtx.shape, jnp.float32)],
        input_output_aliases={3: 0},        # the state, after the scalars
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="ssm_scan",
    )(layer.reshape(1), slots, fresh.astype(jnp.int32), state, dtx, cm, bm,
      lmat, cs, bw, dec)
    return jnp.moveaxis(_unfold(y, fold)[:, :, :t], 1, 2), state


# --------------------------------------------------------------------------
# Kimi Delta Attention: a state decayed a KEY CHANNEL and corrected by a
# delta rule.  A head at position t (q_t, k_t [N] by key channel, v_t [P],
# g_t [N] <= 0 the log of the decay alpha_t = exp(g_t), beta_t a scalar):
#
#     S~  = diag(alpha_t) S_{t-1}                       S [N, P]
#     S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
#     o_t = S_t^T q_t
#
# (the convolution, the L2 norms, the gate and the norm behind it are the
# caller's).  g_t = 0 and beta_t = 0 is the identity: how a padded row and
# a lane that is not stepped pass through.  The state lives in the same
# kind of buffer as Mamba-2's, [layers, slots, H, N, P] float32 with N rows
# (key channels, down the sublanes) of P = 128 columns (the lane width: no
# fold), read and written in place by both kernels, a head's block named
# by the layer and the row's slot through scalar prefetch:
#
#   * `kda_update` (T = 1): ONE read and ONE write of the state.  With
#     S~ = alpha * S in vector memory, p = S~^T k and r = S~^T q are sums
#     down the sublanes of one pass, u = beta (v - p), S_t = S~ + k u^T the
#     pass that writes the block, and o = r + (k . q) u;
#   * `kda_scan` (T > 1): chunks of `chunk` positions; inside a chunk the
#     recurrence unrolled into five products on the matrix unit, between
#     chunks the carried state in the kernel's output block, so the state
#     is read once and written once a (row, head) whatever the slice's
#     length.  The corrections u of a chunk solve a unit-lower-triangular
#     system whose entries are sums over the key channels of
#     exp(G_i - G_j) k_i k_j: the decay is a channel's own, so the system
#     is no product of two matrices as it stands (`_kda_chunk_terms` has
#     how it is made one, sub-chunk by sub-chunk).  What a chunk needs
#     beside the products (the system's inverse, the decayed q and k, the
#     chunk's total decay) is made by XLA in front of the kernel, in
#     float32, as Mamba-2's `_chunk_terms` are.  (A kernel that walks a
#     chunk's positions one at a time with the state in registers, the
#     update's pass a position, took 7.3 ms for `[4, 256]` rows where this
#     takes 2.0: PERF.md section 6, PR 57.)
#
# In the update alpha, k and q meet the state as COLUMNS (a number a
# sublane, the same along the lanes); they arrive as rows, and the kernel
# makes a row's column form by a transpose of the row laid over 128
# sublanes.
# --------------------------------------------------------------------------

# Heads of one grid step of `kda_update`: 16 x [128, 128] float32 is 1 MB a
# block, 4 MB with both directions double-buffered.
_KDA_UPDATE_HEADS = 16


def _column(row, n: int, p: int):
    """row [1, N] as [N, P]: entry i along the whole of sublane i."""
    return jnp.broadcast_to(row, (p, n)).T


def _kda_update_kernel(layer_ref, slot_ref, s_ref, a_ref, k_ref, q_ref,
                       v_ref, beta_ref, kq_ref, so_ref, o_ref, *,
                       heads: int):
    """One (row, block of `heads` heads) grid step: every head's [N, P]
    state decayed a row, read against k and q in one pass, corrected and
    written back; v, beta and kq = k . q are rows [1, P]."""
    del layer_ref, slot_ref             # only the index maps read them
    n, p = s_ref.shape[-2:]
    for h in range(heads):
        at = slice(h, h + 1)
        k_col = _column(k_ref[at, :], n, p)
        s = s_ref[h] * _column(a_ref[at, :], n, p)
        seen = jnp.sum(s * k_col, axis=0, keepdims=True)
        read = jnp.sum(s * _column(q_ref[at, :], n, p), axis=0,
                       keepdims=True)
        u = beta_ref[at, :] * (v_ref[at, :] - seen)
        so_ref[h] = s + k_col * u
        o_ref[at, :] = read + kq_ref[at, :] * u


def kda_update(state, q, k, v, g, beta, slots, layer=0, *,
               use_kernel: Optional[bool] = None,
               interpret: Optional[bool] = None):
    """One token a row: the states of rows `slots` at `layer` overwritten
    in place, and each read against its q.

    state [L, S, H, N, P] float32; q, k [B, H, N]; v [B, H, P]; g [B, H, N]
    float32 (the decay's log, <= 0; 0 with beta 0: the row is not stepped);
    beta [B, H] float32; slots [B] int32.  Returns (o [B, H, P] float32,
    state)."""
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
    if interpret is None:
        interpret = _interpret_kernels()
    return _kda_update(state, q, k, v, g, beta, slots, layer,
                       use_kernel=use_kernel, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def _kda_update(state, q, k, v, g, beta, slots, layer, *, use_kernel: bool,
                interpret: bool):
    b, h, n = k.shape
    p = v.shape[-1]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    alpha = jnp.exp(g.astype(jnp.float32))
    beta = beta.astype(jnp.float32)[..., None]
    layer = jnp.asarray(layer, jnp.int32)
    slots = slots.astype(jnp.int32)
    if not use_kernel:
        s = state[layer, slots] * alpha[..., None]            # [B, H, N, P]
        pred = jnp.einsum("bhnp,bhn->bhp", s, k, precision=_HIGHEST)
        u = beta * (v - pred)
        s = s + k[..., None] * u[:, :, None, :]
        o = jnp.einsum("bhnp,bhn->bhp", s, q, precision=_HIGHEST)
        return o, state.at[layer, slots].set(s, mode="drop")
    hb = min(_KDA_UPDATE_HEADS, h)
    if h % hb or hb % 8:
        raise ValueError(f"{h} heads in blocks of {hb}")
    beta, kq = (jnp.broadcast_to(x, (b, h, p)) for x in (
        beta, jnp.sum(k * q, -1, keepdims=True)))

    def state_map(i, j, ly, sl):
        return (ly[0], sl[i], j, 0, 0)

    def head_map(i, j, ly, sl):
        return (i, j, 0)

    by_key, by_value = (pl.BlockSpec((None, hb, w), head_map)
                        for w in (n, p))
    state, o = pl.pallas_call(
        functools.partial(_kda_update_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,              # layer, the rows' slots
            grid=(b, h // hb),
            in_specs=[pl.BlockSpec((None, None, hb, n, p), state_map),
                      by_key, by_key, by_key, by_value, by_value, by_value],
            out_specs=[pl.BlockSpec((None, None, hb, n, p), state_map),
                       by_value]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, h, p), jnp.float32)],
        input_output_aliases={2: 0},        # the state, after the scalars
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="kda_update",
    )(layer.reshape(1), slots, state, alpha, k, q, v, beta, kq)
    return o, state


# Positions of a sub-chunk of `_kda_chunk_terms`: the pairs inside one are
# taken an exponential a (pair, channel), the pairs across two by a product.
_KDA_SUB = 16


def _kda_chunk_terms(g, k, q, beta, chunk: int):
    """What a chunked scan multiplies, head-major and float32, of g, k, q
    [B, H, T, N] and beta [B, H, T, 1] (T whole chunks).  Inside a chunk
    from S_0, with G_i the running sum of the decays' logs (Gamma_i =
    exp(G_i)):

        u_i = beta_i (v_i - S_0^T (Gamma_i k_i) - sum_{j<i} A_ij u_j)
        o_i = S_0^T (Gamma_i q_i) + sum_{j<=i} Q_ij u_j
        S_C = diag(Gamma_C) S_0 + sum_j diag(Gamma_C / Gamma_j) k_j u_j^T

    A_ij = sum_n k_in k_jn exp(G_in - G_jn) and Q_ij likewise with q_i.  The
    decay is a channel's own, so A is no product of two matrices as it
    stands; the ratios are exponentials of differences, never a division by
    a small Gamma.  A chunk is cut into sub-chunks of `_KDA_SUB`: a pair
    (i, j) of ONE sub-chunk takes its exponential a channel; a pair across
    two goes through the start R of i's sub-chunk, exp(G_i - R) exp(R -
    G_j) with both exponents <= 0, which IS a product of two matrices.
    Returns (kin = Gamma k, qin = Gamma q, kout = (Gamma_C / Gamma) k
    [B, H, T, N]; solve [B, H, T / chunk, chunk, chunk] with u = solve (v -
    kin S_0): (I + diag(beta) A)^-1 diag(beta); qmat, Q's lower triangle,
    the same shape; dec = Gamma_C [B, H, T / chunk, 1, N])."""
    b, h, t, n = k.shape
    nc = t // chunk
    sub = _KDA_SUB if chunk % _KDA_SUB == 0 else chunk
    ns = chunk // sub

    def cut(x):                             # [B, H, T, N] -> [.., ns, sub, N]
        return x.reshape(b, h, nc, ns, sub, n)

    cum = jnp.cumsum(g.reshape(b, h, nc, chunk, n), axis=-2)
    kc, qc, gc = cut(k), cut(q), cut(cum.reshape(b, h, t, n))
    # pairs of one sub-chunk
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    ratio = jnp.exp(jnp.where(
        lower[..., None], gc[..., :, None, :] - gc[..., None, :, :],
        -jnp.inf))                              # [.., ns, sub, sub, N]
    near = [jnp.einsum("...in,...jn,...ijn->...ij", x, kc, ratio,
                       precision=_HIGHEST) for x in (kc, qc)]
    if ns == 1:
        amat, qmat = (x[..., 0, :, :] for x in near)
    else:
        # pairs across sub-chunks, through the start of i's: R = G behind
        # the sub-chunk before it
        start = jnp.concatenate([jnp.zeros_like(gc[..., :1, 0, :]),
                                 gc[..., :-1, -1, :]], axis=-2)
        toward = jnp.exp(gc - start[..., None, :])      # i from its R: <= 1
        # j to each later sub-chunk's R (0 where j is not before it)
        before = (jnp.arange(chunk)[None, :]
                  < sub * jnp.arange(ns)[:, None])[..., None]
        back = jnp.exp(jnp.where(
            before, start[..., :, None, :] - cum[..., None, :, :], -jnp.inf))
        kj = k.reshape(b, h, nc, 1, chunk, n) * back    # [.., ns, chunk, N]
        far = [jnp.einsum("...sin,...sjn->...sij", x * toward, kj,
                          precision=_HIGHEST).reshape(b, h, nc, chunk, chunk)
               for x in (kc, qc)]
        eye = jnp.eye(ns)[:, None, :, None]
        amat, qmat = (f + (x[..., :, :, None, :] * eye).reshape(
            b, h, nc, chunk, chunk) for f, x in zip(far, near))
    betac = beta.reshape(b, h, nc, chunk, 1)
    strict = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)
    solve = jax.scipy.linalg.solve_triangular(
        jnp.eye(chunk) + betac * amat * strict,
        jnp.broadcast_to(jnp.eye(chunk), amat.shape), lower=True,
        unit_diagonal=True) * jnp.swapaxes(betac, -1, -2)
    into = jnp.exp(cum)
    total = cum[..., -1:, :]
    out_of = jnp.exp(total - cum)
    flat = lambda x: x.reshape(b, h, t, n)
    return (flat(into) * k, flat(into) * q, flat(out_of) * k, solve, qmat,
            jnp.exp(total))


def _kda_chunks(terms, v, s0):
    """The chunked scan as plain XLA: s0 [B, H, N, P], v [B, H, T, P] ->
    (o [B, H, T, P], the state behind the last chunk)."""
    kin, qin, kout, solve, qmat, dec = terms
    b, h, t, _ = kin.shape
    nc, chunk = solve.shape[2:4]

    def by_chunk(x):                    # [B, H, T, W] -> [nc, B, H, chunk, W]
        return jnp.moveaxis(x.reshape(b, h, nc, chunk, -1), 2, 0)

    def one(s, xs):
        kin, qin, kout, v, solve, qmat, dec = xs
        u = jnp.einsum("bhij,bhjp->bhip", solve, v - jnp.einsum(
            "bhin,bhnp->bhip", kin, s, precision=_HIGHEST),
            precision=_HIGHEST)
        o = jnp.einsum("bhin,bhnp->bhip", qin, s, precision=_HIGHEST) \
            + jnp.einsum("bhij,bhjp->bhip", qmat, u, precision=_HIGHEST)
        s = jnp.swapaxes(dec, -1, -2) * s + jnp.einsum(
            "bhjn,bhjp->bhnp", kout, u, precision=_HIGHEST)
        return s, o

    s, o = jax.lax.scan(one, s0, (
        *map(by_chunk, (kin, qin, kout, v)),
        *(jnp.moveaxis(x, 2, 0) for x in (solve, qmat, dec))))
    return jnp.moveaxis(o, 0, 2).reshape(b, h, t, -1), s


def _kda_head_major(q, k, v, g, beta, chunk: int):
    """[B, T, H, .] -> float32 [B, H, T', .], the slice padded at its end
    to whole chunks with the identity (g = 0, beta = 0): (g, k, q, v, beta
    [B, H, T', 1])."""
    pad = -q.shape[1] % chunk

    def lay(x):
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x, 1, 2)

    return lay(g), lay(k), lay(q), lay(v), lay(beta[..., None])


def kda_sequence(q, k, v, g, beta, *, chunk: int):
    """A whole sequence from the zero state: q, k, g [B, T, H, N], v
    [B, T, H, P], beta [B, T, H] -> (o [B, T, H, P] float32, the final
    state [B, H, N, P])."""
    b, t, h, n = k.shape
    g, k, q, v, beta = _kda_head_major(q, k, v, g, beta, chunk)
    o, s = _kda_chunks(_kda_chunk_terms(g, k, q, beta, chunk), v,
                       jnp.zeros((b, h, n, v.shape[-1]), jnp.float32))
    return jnp.moveaxis(o[:, :, :t], 1, 2), s


def _kda_scan_kernel(layer_ref, slot_ref, fresh_ref, s_ref, kin_ref,
                     qin_ref, kout_ref, v_ref, solve_ref, qmat_ref, dec_ref,
                     so_ref, o_ref):
    """One (row, head, chunk) grid step; the chunks of a (row, head) run in
    order with the state carried in the output block, which is the head's
    [N, P] state in the buffer: written back once, after the last.  Five
    products on the matrix unit a chunk (`_kda_chunk_terms`)."""
    del layer_ref, slot_ref
    i, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        so_ref[...] = jnp.where(fresh_ref[i] != 0, 0.0, s_ref[...])

    dot = functools.partial(jnp.dot, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    s = so_ref[...]
    u = dot(solve_ref[...], v_ref[...] - dot(kin_ref[...], s))
    o_ref[...] = dot(qin_ref[...], s) + dot(qmat_ref[...], u)
    so_ref[...] = _column(dec_ref[...], *s.shape) * s + jax.lax.dot_general(
        kout_ref[...], u, (((0,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def kda_scan(state, q, k, v, g, beta, slots, fresh, layer=0, *, chunk: int,
             use_kernel: Optional[bool] = None,
             interpret: Optional[bool] = None):
    """A slice of T tokens a row, from each row's state at `layer` (zero
    where `fresh`), which is overwritten in place with the state behind the
    slice's last stepped token.

    state [L, S, H, N, P] float32; q, k [B, T, H, N]; v [B, T, H, P]; g
    [B, T, H, N] float32 (<= 0; 0 with beta 0 at a padded row: the
    identity); beta [B, T, H] float32; slots [B] int32; fresh [B] bool.
    Returns (o [B, T, H, P] float32, state)."""
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
    if interpret is None:
        interpret = _interpret_kernels()
    return _kda_scan(state, q, k, v, g, beta, slots, fresh, layer,
                     chunk=chunk, use_kernel=use_kernel, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel",
                                             "interpret"))
def _kda_scan(state, q, k, v, g, beta, slots, fresh, layer, *, chunk: int,
              use_kernel: bool, interpret: bool):
    b, t, h, n = k.shape
    p = v.shape[-1]
    g, k, q, v, beta = _kda_head_major(q, k, v, g, beta, chunk)
    layer = jnp.asarray(layer, jnp.int32)
    slots = slots.astype(jnp.int32)
    if not use_kernel:
        s0 = jnp.where(fresh[:, None, None, None], 0.0, state[layer, slots])
        o, s = _kda_chunks(_kda_chunk_terms(g, k, q, beta, chunk), v, s0)
        return (jnp.moveaxis(o[:, :, :t], 1, 2),
                state.at[layer, slots].set(s, mode="drop"))
    total = k.shape[2]

    def state_map(i, j, c, ly, sl, fr):
        return (ly[0], sl[i], j, 0, 0)

    def rows_map(i, j, c, ly, sl, fr):
        return (i, j, c, 0)

    def chunk_map(i, j, c, ly, sl, fr):
        return (i, j, c, 0, 0)

    by_key, by_value = (pl.BlockSpec((None, None, chunk, w), rows_map)
                        for w in (n, p))
    kin, qin, kout, solve, qmat, dec = _kda_chunk_terms(g, k, q, beta, chunk)
    square = pl.BlockSpec((None, None, None, chunk, chunk), chunk_map)
    state, o = pl.pallas_call(
        _kda_scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, the rows' slots, which are fresh
            grid=(b, h, total // chunk),
            in_specs=[pl.BlockSpec((None, None, None, n, p), state_map),
                      by_key, by_key, by_key, by_value, square, square,
                      pl.BlockSpec((None, None, None, 1, n), chunk_map)],
            out_specs=[pl.BlockSpec((None, None, None, n, p), state_map),
                       by_value]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, h, total, p), jnp.float32)],
        input_output_aliases={3: 0},        # the state, after the scalars
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="kda_scan",
    )(layer.reshape(1), slots, fresh.astype(jnp.int32), state, kin, qin,
      kout, v, solve, qmat, dec)
    return jnp.moveaxis(o[:, :, :t], 1, 2), state


def copy_slot(dst_buffers, src_buffers, src, dst):
    """One slot of a state cache copied into other buffers, every layer:
    into[:, dst] = frm[:, src] for each pair of the two tuples (a mixer's
    recurrent state and its convolution's tail, or the tail alone: a
    snapshot taken or adopted; `dst` out of range: nothing is written).
    One `dynamic_update_slice` a buffer, so that the donated destination
    stays where it is."""
    with jax.named_scope("ssm_snapshot"):
        out = []
        for into, frm in zip(dst_buffers, src_buffers):
            live = (dst >= 0) & (dst < into.shape[1])
            at = jnp.clip(dst, 0, into.shape[1] - 1)
            new = jax.lax.dynamic_slice_in_dim(frm, src, 1, axis=1)
            old = jax.lax.dynamic_slice_in_dim(into, at, 1, axis=1)
            out.append(jax.lax.dynamic_update_slice_in_dim(
                into, jnp.where(live, new.astype(into.dtype), old), at,
                axis=1))
        return tuple(out)
