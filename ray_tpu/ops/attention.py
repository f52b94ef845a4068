"""Attention ops: blockwise (flash) attention with Pallas TPU kernels.

No reference counterpart — Ray delegates compute to hosted frameworks
(SURVEY.md §5 "Long-context: absent").  Here attention is a core op: the
Pallas kernels keep the softmax accumulation in VMEM (online softmax, never
materialising the [L, L] score matrix in HBM), walk only the tiles of it at
or under the causal diagonal and tile the contractions onto the MXU in the
inputs' dtype; a pure-jnp fallback covers CPU tests and odd shapes.

Layouts: q/k/v are [batch, length, heads, head_dim] (BLHD) throughout; the
flash kernels read them as [batch, length, heads x head_dim], the same
bytes wherever the caller made them that wide.
"""

from __future__ import annotations

import functools
import itertools
import logging
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.parallel.sharding import logical_to_spec

logger = logging.getLogger(__name__)

NEG_INF = -1e30


def _interpret_kernels() -> bool:
    """Pallas mode for the default backend: compiled Mosaic on TPU, the
    interpreter on CPU (tests).  Anything else has no kernel path here,
    and silently interpreting there would hide that."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"attention kernels target TPU (Mosaic) and CPU (interpreter); "
        f"backend is {backend!r}")


@functools.lru_cache(maxsize=None)
def _log_reference_path(op: str, shape: tuple) -> None:
    """Once per (op, shape): a TPU run took the [L, L] XLA path."""
    logger.warning("%s: shape %s does not fit the Pallas kernel; "
                   "running the XLA reference path", op, shape)


def reference_attention(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        segment_ids=None, window: int = 0) -> jax.Array:
    """Plain XLA attention (fallback + ground truth for kernel tests) of
    q [batch, length, heads, d] and k, v [batch, length, kv_heads, d], a
    kv head serving heads // kv_heads query heads that follow one another
    (repeated here: the oracle, not the path); `window`: a position
    attends the last `window` positions alone, its own among them."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if k.shape[2] != q.shape[2]:
        k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)
                for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = _build_mask(q.shape[1], k.shape[1], causal, segment_ids)
    if window:
        near = jnp.triu(jnp.ones((q.shape[1], k.shape[1]), bool),
                        k=k.shape[1] - q.shape[1] - window + 1)[None, None]
        mask = near if mask is None else (mask & near)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _build_mask(q_len, k_len, causal, segment_ids):
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((q_len, k_len), bool),
                        k=k_len - q_len)[None, None]
    if segment_ids is not None:
        seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = seg if mask is None else (mask & seg)
    return mask


# ---------------------------------------------------------------------------
# Pallas flash-attention kernels
# ---------------------------------------------------------------------------
#
# One head's scores are a [q_len, kv_len] square that never exists.  The
# grid walks it in blocks (`block_q` x `block_k`, the whole head at 1,024),
# K and V (backward: Q and dO) arriving a block a grid step with the carry
# in VMEM scratch between them, and a grid step walks its block in tiles.
# Which tiles is decided while tracing, not on the chip: a causal block on
# the diagonal visits the tiles at or under it (3 of 4 at 512 x 512) and
# masks only those the diagonal crosses, a block under the diagonal visits
# all of its tiles and masks none, a block over it is neither copied in nor
# computed.  So a grid step is straight-line code the compiler schedules
# across tiles; the same walk as a `fori_loop` with bounds from the tile's
# position was slower than the kernels it was to replace (PERF.md section
# 6, PR 44: a loop trip waits out every product's latency).  The mask is a
# constant the compiler knows: of a crossed tile it computes the vector
# unit's passes (scale, maximum, exponential, sum) only for the registers
# (8 kv x 128 q positions) that hold a visible pair, whole tile or not, so
# what a crossed tile wastes is its products' time on the matrix units.
# The backward walks a crossed tile as sub-tiles for that (`_tiles`); the
# forward does not: each sub-tile is a step of the online softmax with its
# own rescale, and its products fill the four matrix units worse than the
# whole tile's (PERF.md section 6, PR 56).
#
# A tile is S^T: kv positions along sublanes, q positions along lanes.  The
# softmax's running maximum and sum, the logsumexp and delta = rowsum(dO * O)
# are then [1, q] rows, dense in their vector registers and 4 bytes a q
# position in HBM ([heads, 1, q_len]; a [.., q_len, 1] array pads every
# position to 128 lanes, 151 MB a layer at the train cell's shape), a
# reduction over kv is over sublanes (no lane shuffle), and P^T dO and
# dS^T Q are plain products.  Every product takes its operands in the
# inputs' dtype and accumulates in float32 (P and dS are cast as
# `reference_attention` casts `probs`); the scale multiplies the float32
# scores; statistics and accumulators are float32.  The backward is one
# kernel: S, P, dP and dS of a tile are computed once and feed dv, dk and
# dq (five products a tile beside the forward's two), dq gathering as
# dq^T over the kv blocks in a scratch of the whole length, and delta is
# made in it from dO and O, a q tile at a time.
#
# How a head lies in HBM: where the model leaves it.  q, k, v, dO, O and
# the results are [batch, length, heads x d], which is [batch, length,
# heads, d] seen without its last split: the heads of a position side by
# side in one row, as the projections write and read them
# (`models/decoder.py::heads_attention`).  The grid's first axis goes over
# (batch, column block), a column block being max(d, 128) columns: one head
# of 128 or 256, or TWO heads of 64, and a grid step walks the tiles of each
# of its heads.  Of two heads in a block, one's S^T is the product over all
# 128 lanes with the OTHER head's lanes of Q selected to zero (`where`: a
# lane that is not the head's must not reach a score), likewise dP with dO,
# and the two heads' are ONE product, [kv, 2 x q] of K with the two
# selections of Q one under the other (`_heads_rows`): eight column parts
# for the four matrix units where a head's four (two in a sub-tile) left
# them to wait on each other, and from it the compiler drops the passes
# that only the mask's constant would replace (PR 56: the forward 15% and
# the backward 10% faster for it).  The backward's statistics, P and dS are
# then [kv, 2 x q] too, and P^T dO and dS^T Q of the two heads, dO and Q so
# selected, are each one product that contracts over both heads' q and
# gives the packed dv and dk; V^T P and K^T dS take the head's 64 lanes of V
# and K and give the head's 64 rows of the transposed accumulators O^T and dq^T
# [128, q], which are transposed once into lane-dense [q, 128] stores.
# (V^T P over all 128 lanes with half the rows thrown away was 16% slower
# in the forward: PERF.md section 6, PR 51.)
#
# How a group lies: k and v are [batch, length, kv_heads x d], the model's
# own few heads, and a kv head's `group` query heads follow one another in
# q's columns (query head h reads kv head h // group), so a kv-side block
# of one head of 128 or 256 columns has its group's `group` blocks of q, dO
# and O right beside one another: a grid step takes the kv block and
# `part` of them as ONE q-side block [block, part x lanes] (`_block_heads`).
# The group's heads multiply the SAME K tile and the SAME V tile.  In the
# forward their products are the two heads' one product without its
# selects: the q tiles of `wide` heads one under the other (`_heads_rows`,
# each head's own columns whole) give S^T [kv, wide x q] in one product of
# K, its statistics ONE [1, wide x q] row, and V^T P one product [d, wide x
# q] (`_flash_fwd_tiles`, `_FLASH_FWD_COLUMNS`).  The backward's products
# stay a kv block's own heads' (the matrix units are its already): a
# group's heads follow one another on the K and V tile that was copied in
# once for them all, and each adds its P^T dO and dS^T Q to the same dv and
# dk, which IS the sum over the group: dk and dv gather in float32 over
# all of a group's heads and leave the kernel [batch, length, kv_heads x
# d], and no array of the query heads' count ever holds K, V, dk or dv.
# How many heads a grid step takes is what its straight-line code and VMEM
# hold (`_flash_plan`, `_FLASH_FWD_PAIRS`; the backward keeps dq^T of the
# whole length): where that is a part of the group, the parts follow one
# another on the grid's second axis while dk and dv gather in a scratch of
# the whole length, and the part's K and V blocks are copied in once a
# part, not once a head.  A group of heads narrower than a block's 128
# lanes (two kv heads a block, their groups' lanes not aligned with their
# own) has K and V repeated to the query heads' count in HBM, as every
# group had before PR 63, and runs as a group of one (`_FlashPlan.spread`):
# dk and dv then leave the kernels a query head each and XLA sums them.
# A head count that is odd at
# 64 leaves the last block half empty: what the copy brought past the last
# column is selected to zero in K, V, dO and O as the other head's lanes
# are, the half block's second head computes on zeros, and the partial
# store drops its results.  Until PR 51 the kernels took [batch x heads,
# length, d] and every call transposed q, k, v (and dO) to it and the
# results back, 1.28 ms a layer-step beside kernels of 2.08 at the train
# cell's shape.

# Rows and columns of a tile at most, forward and backward, and the columns
# of the score product (q positions x the heads of a column block) down to
# which the backward cuts a tile the diagonal crosses.  The sweeps of
# PERF.md section 6: PR 44's at a head of 64 over 1,024 positions (whole
# tiles, a product a head: forward 512, backward 256) and PR 56's with the
# heads' scores one product, ms a layer-step at [24,1024,12,64] / at
# [4,2048,16,128], the parent's whole tiles of 256 at 1.335 / 1.180:
# backward 512 cut to 128: 1.162 / 1.216; 512 cut to 256: 1.194 / 1.175;
# 512 whole: 1.399 / 1.284; 256 cut to 128: 1.189 / 1.221; 256 whole:
# 1.210 / 1.180.  So 128 q positions where two heads share a block and 256
# where a head has one to itself, which is 256 columns in both.  The
# forward's crossed tiles stay whole: 0.634 against 0.672 as halves and
# 0.820 as quarters (0.745, 0.975 and 0.994 with a product a head).
_FLASH_FWD_TILE = 512
_FLASH_BWD_TILE = 512
_FLASH_BWD_CROSSED = 256
# A group's heads (PR 63, at [2,8192,32,128] over 4 kv heads, ms a call
# forward | backward, without a window / under one of 1,024; the kernels
# before groups, K and V repeated: 10.79 | 15.98 / 3.26 | 4.82).  Columns at
# most of a score product that a group's heads share in the forward, which
# halves its tile down to 256 until the heads of a grid step fit in them
# (four heads at 256: 7.79 / 2.21; eight at 256, 2,048 columns: 7.56 / 2.17;
# eight at 512: 7.77 / 2.49).  The backward's products stay a kv head's own
# (15.32 / 4.52 where two heads a product read 15.30 / 4.47: what it gains
# of a group is K and V copied in once and dk and dv summed in VMEM; four
# heads a step and a product read 14.77 / 4.02 and sat at 64.3 thousand
# bundles).  And the (q, kv) pairs at most, over its heads, of the
# block that a grid step's straight-line code walks: a kernel past 65,536
# bundles runs at half its speed (the forward of eight heads at 66-68
# thousand 17.8-18.7 where 64 thousand reads 7.6, the backward of four at
# 67-69 thousand 35.3-36.3 where 64.3 reads 14.8; `scripts/flash_bundles.py`
# counts them), so four heads of a block of 1,024 forward (33 thousand) and
# two backward, and compiles in a quarter of the time.  The pairs are a
# head of 128 columns'; one of 256 counts twice (two heads of a group of 4
# at 256 compiled to 65.7 thousand backward, 56.6 four forward).
_FLASH_FWD_COLUMNS = 1024
_FLASH_FWD_PAIRS = 4 * 1024 * 1024
_FLASH_BWD_PAIRS = 2 * 1024 * 1024
# Scoped VMEM a kernel may ask for beyond the compiler's default 16 MiB
# (the backward holds a head's dq: 8 bytes a q position and column).
_FLASH_VMEM_LIMIT = 96 * 1024 * 1024
_FLASH_VMEM_DEFAULT = 16 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _tiles(n_q, bq, n_kv, bk, diagonal, least=0, q_at=0, k_at=0, back=0,
           window=0):
    """(q0, rows, k0, columns, offset): where the tiles to visit in a block
    of n_q x n_kv tiles begin (from `q_at`, `k_at`) and how many q and kv
    positions each holds.  Off the diagonal (or not causal) all of them,
    whole and unmasked (offset None).  On it the tiles wholly under the
    diagonal, whole and unmasked, and the tiles the diagonal crosses with
    the offset of their first kv position past their first q position,
    which is what their mask needs.  With `least`, a crossed tile of twice
    `least` positions (or a multiple) a side is walked as its four quarters
    in the same way: the one over the diagonal is not visited, the one
    under it is unmasked, the two it crosses are cut again.

    With `window` (a q position sees the `window` kv positions up to its
    own) there is a second diagonal, the window's edge, and the kv block
    lies `back` positions behind the q block: a tile wholly behind the
    edge is not visited either, and a tile the edge crosses is cut in the
    same way or listed with a sixth entry, `edge`: what a pair's q
    position past its kv position, counted as `offset` counts it, has to
    stay under (None where only the diagonal crosses the tile)."""
    for q0 in range(q_at, q_at + n_q * bq, bq):
        for k0 in range(k_at, k_at + n_kv * bk, bk):
            # a pair's q position less its kv position is `ahead` for the
            # tile's first of each, and over the tile nearest .. farthest
            ahead = q0 + back - k0
            nearest, farthest = ahead - (bk - 1), ahead + bq - 1
            under = not diagonal or nearest >= 0
            inside = not window or farthest < window
            if (diagonal and farthest < 0) or (window and nearest >= window):
                pass            # wholly over the diagonal, or behind the edge
            elif under and inside:
                yield (q0, bq, k0, bk, None) + ((None,) if window else ())
            elif least and min(bq, bk) % (2 * least) == 0:
                yield from _tiles(2, bq // 2, 2, bk // 2, diagonal, least,
                                  q0, k0, back, window)
            elif window:
                yield (q0, bq, k0, bk, None if under else -ahead,
                       None if inside else window - ahead)
            else:
                yield q0, bq, k0, bk, -ahead


def _scores_t(k, q, scale):
    """S^T = K Q^T * scale of one tile for every head of the column block
    at once, float32 [kv, heads x q positions]: q [heads x q positions,
    lanes] holds the tile's q positions once a head (`_heads_rows`).  Why
    one product and not one a head: the comment above, on how a head lies
    in HBM."""
    return _dot(k, q, _NT) * scale


def _masked(s, offset, n_q, edge=None):
    """s, scores [kv, q] of a tile or [kv, heads x n_q] of its heads side by
    side, with NEG_INF where a pair lies over the diagonal; `offset` is the
    tile's first kv position past its first q position (`_tiles`), None
    for a tile under the diagonal.  `edge`: NEG_INF also where a pair lies
    behind the window's edge (`_tiles`'s sixth entry)."""
    if offset is None and edge is None:
        return s
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if s.shape[1] != n_q:
        col = jax.lax.rem(col, n_q)
    ahead = col - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    if offset is None:
        visible = ahead < edge
    elif edge is None:
        visible = ahead >= offset
    else:
        visible = (ahead >= offset) & (ahead < edge)
    return jnp.where(visible, s, NEG_INF)


def _on_or_under_the_diagonal(walk, q_block, kv_block, causal, n_blocks):
    """Run `walk(diagonal)` as the pair of blocks asks: causal blocks are
    square, so the diagonal crosses the pairs of equal index alone, and a
    head of one block has no other pair to trace, lower and compile."""
    if not causal:
        walk(False)
    elif n_blocks == 1:
        walk(True)
    else:
        pl.when(q_block == kv_block)(functools.partial(walk, True))
        pl.when(q_block > kv_block)(functools.partial(walk, False))


def _window_steps(window: int, block: int, n_blocks: int) -> int:
    """The kv blocks a q block visits under a window: its own and those of
    which some position lies within `window` of its first."""
    return min(-(-(window - 1) // block) + 1, n_blocks)


def _in_the_window(walk, tiles_of, distance, there, steps: int, block: int):
    """Run `walk(tiles)` as a pair of blocks under a window asks: the kv
    block lies `distance` blocks behind the q block (0 .. steps - 1, known
    on the chip), and what the walk visits and masks depends on that alone
    (`tiles_of(back)`, `back` in positions), so each kind of walk is traced
    once: the block the diagonal crosses, the blocks wholly inside the
    window, the block its edge crosses.  `there`: the pair exists."""
    kinds = {}
    for r in range(steps):
        kinds.setdefault(tuple(tiles_of(r * block)), []).append(r)
    for tiles, at in kinds.items():
        if tiles:
            hit = functools.reduce(jnp.logical_or,
                                   [distance == r for r in at])
            pl.when(hit & there)(functools.partial(walk, tiles))


def _only_lanes(x, lo, hi):
    """x [rows, lanes] with zeros outside lanes lo .. hi - 1.  A select,
    not a multiply: whatever a lane that is not chosen holds (the other
    head, or what a copy past the arrays' last column left there) must not
    reach a product."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= lo) & (lane < hi), x, jnp.zeros_like(x))


class _Head(NamedTuple):
    """A query head of a grid step (`_block_heads`)."""
    rows: slice         # its columns of the q-side block, which are its rows
    #                     of a transposed accumulator (O^T, dq^T)
    lanes: slice        # the kv block's width of those columns it lies in
    kv: slice           # its kv head's columns of the kv-side block
    only: object        # x [rows, that width] with every lane not its own 0


def _block_heads(lanes, d, width, block, part=1):
    """How the heads of column block `block` lie in their blocks: a kv-side
    block is `lanes` columns (its kv heads: two of 64, or one) of arrays
    that would be `width` (heads x d) columns wide at a group of one, and
    the q-side block beside it `part` times that, each kv head's `part`
    query heads.  `whole(x)`, x [rows, lanes] with the lanes past the
    arrays' last column zero, and a `_Head` for each of the q-side block's
    heads.  A head that has its width of lanes whole (128 or 256 columns:
    every head of a group) selects nothing."""
    ragged = width % lanes != 0
    end = width - block * lanes     # `lanes` or more in all but a last half

    def select(lo, hi):
        if ragged:
            return lambda x: _only_lanes(x, lo, jnp.minimum(hi, end))
        if (lo, hi) == (0, lanes):
            return lambda x: x
        return lambda x: _only_lanes(x, lo, hi)

    def head(lo):       # from column `lo` of the q-side block
        at, within = lo - lo % lanes, lo % lanes
        return _Head(slice(lo, lo + d), slice(at, at + lanes),
                     slice(within, within + d), select(within, within + d))

    return select(0, lanes), [head(lo) for lo in range(0, part * lanes, d)]


def _heads_rows(x, heads):
    """x [rows, a q-side block's columns] once a head of `heads`
    (`_block_heads`), one under the other, [heads x rows, lanes]: a head's
    rows are its kv block's width of x with zeros in every lane that is
    not its own, so that a product over all the lanes with them is the
    head's."""
    return jnp.concatenate([h.only(x[:, h.lanes]) for h in heads])


def _products(heads, wide):
    """The heads of a grid step in runs of `wide` whose scores are one
    product, each run [(the head's number in the step, head)]."""
    heads = list(enumerate(heads))
    return [heads[n:n + wide] for n in range(0, len(heads), wide)]


def _of_a_kv_head(run):
    """[(heads, first)]: a run's heads that share a kv head (two heads of 64
    in a block: each alone; a group's: all), and where their columns begin
    among the run's, in heads."""
    first, found = 0, []
    for _, heads in itertools.groupby(run, key=lambda h: h[1].kv):
        found.append((list(heads), first))
        first += len(found[-1][0])
    return found


def _beside(xs):
    """[.., n] arrays side by side, [.., sum of n]; one is itself."""
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=1)


def _apart(x, n):
    """x [.., n x columns] as its n equal parts; one is itself."""
    size = x.shape[1] // n
    return [x] if n == 1 else [x[:, g * size:(g + 1) * size]
                               for g in range(n)]


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *, d: int, width: int, bq: int, bk: int,
                  n_blocks: int, causal: bool, scale: float, window: int,
                  parts: int, wide: int):
    """Grid (batch x kv column block, q block, kv block): the online softmax
    of a q block over the kv blocks up to its own, for each query head of
    the kv column block's heads.  Refs: k, v [block_k, lanes]; q, o
    [block_q, part x lanes], a kv head's `part` query heads; lse [heads, 1,
    block_q]; scratch m, l [heads, 1, block_q] and acc [part x lanes,
    block_q] (O^T, unnormalised, a head's d rows under those of the head
    before it), float32, carried between kv blocks.  `wide` heads' scores
    are one product (`_products`), and of them those of one kv head share
    their statistics' rows [1, heads x q] and V^T P.  With a `window` the
    last axis is the kv blocks a q block visits, the farthest first
    (`_window_steps`), not all of them; with `parts` the second is (part of
    the group, q block)."""
    i, j, c = (pl.program_id(a) for a in range(3))
    if parts > 1:
        j = j % n_blocks
    n_q, n_kv = q_ref.shape[0] // bq, k_ref.shape[0] // bk
    lanes = k_ref.shape[1]
    whole, heads = _block_heads(lanes, d, width, i % pl.cdiv(width, lanes),
                                q_ref.shape[1] // lanes)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def walk(tiles):
        for q0, tiles in itertools.groupby(tiles, key=lambda t: t[0]):
            tiles, cols = list(tiles), slice(q0, q0 + bq)
            q_tile = q_ref[cols, :]
            for run in _products(heads, wide):
                q = _heads_rows(q_tile, [h for _, h in run])
                shares = _of_a_kv_head(run)
                carry = [(_beside([m_ref[g, :, cols] for g, _ in of]),
                          _beside([l_ref[g, :, cols] for g, _ in of]),
                          _beside([acc_ref[h.rows, cols] for _, h in of]))
                         for of, _ in shares]
                for _, _, k0, _, *mask in tiles:
                    at = slice(k0, k0 + bk)
                    k, v = whole(k_ref[at, :]), v_ref[at, :]
                    scores = _scores_t(k, q, scale)
                    for n, (of, first) in enumerate(shares):
                        m, l, acc = carry[n]
                        s = _masked(
                            scores[:, first * bq:(first + len(of)) * bq],
                            mask[0], bq, *mask[1:])
                        m_new = jnp.maximum(m, jnp.max(s, axis=0,
                                                       keepdims=True))
                        p = jnp.exp(s - m_new)
                        alpha = jnp.exp(m - m_new)
                        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
                        acc = acc * alpha + _dot(
                            v[:, of[0][1].kv], p.astype(v.dtype),
                            _TN)                            # [d, heads x bq]
                        carry[n] = m_new, l, acc
                for (of, _), kept in zip(shares, carry):
                    for (g, h), m, l, acc in zip(
                            of, *(_apart(x, len(of)) for x in kept)):
                        m_ref[g, :, cols], l_ref[g, :, cols] = m, l
                        acc_ref[h.rows, cols] = acc

    if window:
        steps = pl.num_programs(2)
        _in_the_window(
            walk, lambda back: _tiles(n_q, bq, n_kv, bk, True, back=back,
                                      window=window),
            steps - 1 - c, j >= steps - 1 - c, steps, q_ref.shape[0])
    else:
        _on_or_under_the_diagonal(
            lambda diagonal: walk(_tiles(n_q, bq, n_kv, bk, diagonal)),
            j, c, causal, n_blocks)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        for g, h in enumerate(heads):
            acc_ref[h.rows, :] = acc_ref[h.rows, :] / l_safe[g]
        o_ref[...] = acc_ref[...].T.astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l_safe)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, dqt_ref, dk_acc, dv_acc, *,
                      d: int, width: int, bq: int, bk: int, least: int,
                      causal: bool, scale: float, window: int, parts: int):
    """Grid (batch x kv column block, kv block, q block): dk and dv of a kv
    block over the q blocks from its own on, and every pair's share of dq,
    for each query head of the kv column block's heads.
    dS = P * (dO V^T - delta), delta = rowsum(dO * O); dv = P^T dO;
    dk = dS^T Q * scale; dq = dS K * scale.  Refs: k, v, dk, dv
    [block_k, lanes]; q, dO, O [block_q, part x lanes], a kv head's `part`
    query heads; lse [heads, 1, block_q]; dq [q_len, part x lanes],
    written at the column block's last grid step from the scratch dq^T
    [q blocks, part x lanes, block_q]; scratch dk, dv [block_k, lanes];
    float32.  A kv block's own heads (two of 64, or one) are one product
    each way: with a head's Q and dO zero in the other head's lanes, dS^T Q
    and P^T dO over the heads' q positions side by side are the packed dk
    and dv; a group's heads follow one another on the same K and V tile
    and add to the same dk and dv, which is the sum over the group.  A
    tile the diagonal crosses is walked as sub-tiles down to `least` q and
    kv positions (`_tiles`).  With a `window` the last axis is the q blocks
    that see the kv block, its own first (`_window_steps`), not all of
    them.  With `parts` the second axis is (part of the group, kv block),
    the scratch dk, dv [kv blocks, block_k, lanes] gather over the parts,
    and what a part writes of them the next overwrites."""
    i, j, c = (pl.program_id(a) for a in range(3))
    n_kv_blocks, first = pl.num_programs(1), None
    if parts > 1:
        n_kv_blocks = n_kv_blocks // parts
        first, j = j < n_kv_blocks, j % n_kv_blocks
        dk_acc, dv_acc = dk_acc.at[j], dv_acc.at[j]
    last = ((j == n_kv_blocks - 1) & (c == pl.num_programs(2) - 1))
    n_q, n_kv = q_ref.shape[0] // bq, k_ref.shape[0] // bk
    lanes = k_ref.shape[1]
    whole, heads = _block_heads(lanes, d, width, i % pl.cdiv(width, lanes),
                                q_ref.shape[1] // lanes)
    n_blocks = dqt_ref.shape[0]
    # the q block of this step, and whether the step is the first to add
    # to its dq
    if window:
        steps = pl.num_programs(2)
        there = j + c < n_blocks
        qb = jnp.minimum(j + c, n_blocks - 1)
        opens = there & ((j == 0) | (c == steps - 1))
    else:
        qb, opens = c, j == 0

    @pl.when(c == 0 if first is None else (c == 0) & first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(opens)
    def _init_dq():
        dqt_ref[qb] = jnp.zeros(dqt_ref.shape[1:], jnp.float32)

    def walk(tiles):
        for tile, tiles in itertools.groupby(tiles,
                                             key=lambda t: t[0] // bq):
            cols = slice(tile * bq, (tile + 1) * bq)
            q, do = q_ref[cols, :], do_ref[cols, :]
            qs = [h.only(q[:, h.lanes]) for h in heads]
            dos = [h.only(do[:, h.lanes]) for h in heads]
            # (dO * O)^T: a head's delta is the sum of its rows, a [1, q]
            # row like the logsumexp it stands beside
            do_o = (whole(do).astype(jnp.float32)
                    * whole(o_ref[cols, :]).astype(jnp.float32)).T
            deltas = [jnp.sum(do_o[h.rows], axis=0, keepdims=True)
                      for h in heads]
            for q0, nq, k0, nk, *mask in tiles:
                at, part = slice(k0, k0 + nk), slice(q0, q0 + nq)
                of = slice(q0 - tile * bq, q0 - tile * bq + nq)
                k, v = whole(k_ref[at, :]), whole(v_ref[at, :])
                for run in _products(heads, lanes // d):
                    # the heads one beside the other: [kv, heads x q]
                    q_all = jnp.concatenate([qs[g][of] for g, _ in run])
                    do_all = jnp.concatenate([dos[g][of] for g, _ in run])
                    lse = jnp.concatenate(
                        [lse_ref[g, :, part] for g, _ in run], axis=1)
                    delta = jnp.concatenate(
                        [deltas[g][:, of] for g, _ in run], axis=1)
                    p = jnp.exp(_masked(_scores_t(k, q_all, scale), mask[0],
                                        nq, *mask[1:]) - lse)
                    ds = (p * (_dot(v, do_all, _NT) - delta)).astype(q.dtype)
                    dv_acc[at, :] += _dot(p.astype(do.dtype), do_all)
                    dk_acc[at, :] += _dot(ds, q_all)
                    for n, (_, h) in enumerate(run):
                        dqt_ref[qb, h.rows, part] += _dot(
                            k[:, h.kv], ds[:, n * nq:(n + 1) * nq], _TN)

    if window:
        _in_the_window(
            walk, lambda back: _tiles(n_q, bq, n_kv, bk, True, least,
                                      back=back, window=window),
            c, there, steps, q_ref.shape[0])
    else:
        _on_or_under_the_diagonal(
            lambda diagonal: walk(_tiles(n_q, bq, n_kv, bk, diagonal,
                                         least)),
            c, j, causal, n_blocks)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(last)
    def _finalize_dq():
        block_q = q_ref.shape[0]
        for n in range(dqt_ref.shape[0]):
            dq_ref[n * block_q:(n + 1) * block_q, :] = (
                dqt_ref[n] * scale).T.astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "window"))
def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None, block_q: int = 1024,
                    block_k: int = 1024, interpret: Optional[bool] = None,
                    window: int = 0):
    """Blockwise attention via Pallas of q [batch, length, heads, d] and
    k, v [batch, length, kv_heads, d], kv_heads dividing heads: a kv head
    serves the heads // kv_heads query heads that follow one another, and
    is read, and its gradient written, once for them all.  Falls back to
    XLA attention when the shape does not tile (length % block != 0;
    logged once per shape on TPU).

    `window` (causal only): a position attends the last `window` positions
    alone, its own among them.  The kernels then visit, of a q block's kv
    blocks, its own and those the window reaches (two of a mean 4.5 at a
    window and blocks of 1,024 over 8,192 positions), and mask the tiles
    that the window's edge crosses as they mask those on the diagonal; in
    a trace they are `window_flash_attention`.

    The kernels read q, k and v and write the result as [batch, length,
    heads x d], two heads of 64 a block of 128 columns: no transpose on
    either side.  That view is free where the caller's arrays came out that
    wide (a product "bld,de->ble" seen as heads); an array MADE in four
    dimensions XLA lays out with the length innermost when d < 128, and
    the view then costs the copy it was to save.

    Differentiable end-to-end in Pallas: the forward saves (O, logsumexp)
    and the backward is one flash-style kernel for dq, dk and dv (causal
    tile skipping, f32 VMEM accumulators) — never materializing [L, L]."""
    if window and not causal:
        raise ValueError("a window is a causal attention's")
    if q.shape[2] % k.shape[2] or k.shape != v.shape:
        raise ValueError(f"{q.shape[2]} query heads are no whole groups of "
                         f"k {k.shape} and v {v.shape}")
    if window >= k.shape[1]:
        window = 0      # every position sees all that came before it
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret,
                  window)


def mesh_flash_attention(q, k, v, *, mesh=None, causal: bool = True,
                         window: int = 0):
    """flash_attention for a model block that may run under a mesh.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned"), so on more than one device the kernel
    runs per shard inside shard_map: batch split over the data axes and
    heads over tensor, the two dims attention is independent across: whole
    groups to a shard, k and v split over their own kv heads by the axis
    that splits q's heads.  A mesh with a seq axis rides ring attention
    instead."""
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal=causal, window=window)
    if mesh.shape.get("seq", 1) > 1:
        if window:
            raise NotImplementedError("ring attention has no window")
        from ray_tpu.ops.ring_attention import ring_attention
        # the ring's steps take K and V at the query heads' count
        k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)
                for x in (k, v))
        return ring_attention(q, k, v, mesh=mesh, causal=causal)
    # The shards' edge is crossed [batch, length, heads x d] wide, as the
    # kernels read: a [batch, length, heads, 64] value that stands on its
    # own there is laid out with the length innermost and copied on both
    # sides.  Whole heads to a shard, as the split of `heads` gave them.
    spec = logical_to_spec(("batch", "length", "heads"), mesh=mesh)
    d = q.shape[-1]
    over = spec[2] if len(spec) > 2 and spec[2] else ()
    shards = math.prod(mesh.shape[a] for a in
                       ((over,) if isinstance(over, str) else over))
    if k.shape[2] % shards:
        raise ValueError(f"{shards} shards of the heads would cut the groups "
                         f"of {k.shape[2]} kv heads")

    def of_a_shard(*wide):
        out = flash_attention(*(x.reshape(*x.shape[:2], -1, d) for x in wide),
                              causal=causal, window=window)
        return _heads_side_by_side(out)

    return jax.shard_map(
        of_a_shard, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(*map(_heads_side_by_side, (q, k, v))).reshape(q.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, window):
    out, _ = _flash_forward_impl(q, k, v, causal, scale, block_q, block_k,
                                 interpret, window)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret, window):
    out, lse = _flash_forward_impl(q, k, v, causal, scale, block_q, block_k,
                                   interpret, window)
    # (by name, so that a `jax.checkpoint` around the layer may keep the
    # two that only the forward kernel makes; q, k and v are products)
    out = checkpoint_name(out, "flash_out")
    if lse is not None:
        lse = checkpoint_name(lse, "flash_out")
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse = res
    if lse is None:  # forward took the XLA fallback: recompute via XLA
        _, vjp = jax.vjp(
            lambda q, k, v: reference_attention(
                q, k, v, causal=causal, scale=scale, window=window), q, k, v)
        return vjp(g)
    return _flash_backward_impl(q, k, v, out, lse, g, causal, scale,
                                block_q, block_k, interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _use_pallas(q_len, kv_len, d, block_q, block_k, causal):
    return (q_len % block_q == 0 and kv_len % block_k == 0
            and d in (64, 128, 256) and not (causal and q_len != kv_len))


def _fit_blocks(q_len, kv_len, block_q, block_k):
    """Clamp blocks to the lengths, then halve until they tile — lengths
    like 1536 must ride the Pallas path with 512-blocks rather than fall
    back to the [L,L]-materializing XLA reference."""
    block_q = min(block_q, q_len)
    block_k = min(block_k, kv_len)
    while block_q > 128 and q_len % block_q:
        block_q //= 2
    while block_k > 128 and kv_len % block_k:
        block_k //= 2
    return block_q, block_k


def _flash_tile(block: int, most: int) -> int:
    """Rows (or columns) of a tile inside a block: the largest of `most`,
    its halves down to 128 that divides the block, else the block (a short
    or odd length is one tile)."""
    while most >= 128:
        if block % most == 0:
            return most
        most //= 2
    return block


class _FlashPlan(NamedTuple):
    """What a call's shapes decide: the grid's blocks along the length and
    across the heads' columns, how many of a group's heads a grid step
    takes, and whether to run the kernels under the interpreter."""
    block_q: int
    block_k: int
    causal: bool
    scale: float
    interpret: bool
    d: int              # columns of a head
    width: int          # columns of q: heads x d
    window: int = 0     # positions a q position sees (0: all before it)
    group: int = 1      # query heads a kv head of the kernels' serves
    fwd_part: int = 1   # of them a grid step of the forward takes,
    bwd_part: int = 1   # and one of the backward (which holds their dq)
    spread: int = 1     # times K and V are repeated before the kernels

    @property
    def lanes(self) -> int:
        """Columns of a kv-side block: whole kv heads, two of 64 to fill
        128 lanes; a q-side block is a part of the group times that."""
        return max(self.d, 128)

    @property
    def heads(self) -> int:
        return self.lanes // self.d

    @property
    def column_blocks(self) -> int:
        """Of K and V.  The last is not whole where the heads are odd at
        64: the kernels select what a copy leaves past the last column to
        zero."""
        return pl.cdiv(self.width // self.group, self.lanes)


def _flash_plan(q, k, causal, scale, block_q, block_k, interpret,
                window=0):
    """The plan for q [batch, length, heads, d] and k [batch, length,
    kv_heads, d], or None where the shapes do not tile or not one head's
    dq fits VMEM (the caller takes the XLA reference).  A group too large
    for VMEM is walked in parts, the largest that fit.  A group of heads
    narrower than a block's 128 lanes (two kv heads a block, their groups'
    lanes not aligned with their own) is `spread`: K and V repeated to the
    query heads' count, as every group was before PR 63, and the kernels
    those of a group of one."""
    (_, q_len, h, d), (_, kv_len, kv_heads, _) = q.shape, k.shape
    block_q, block_k = _fit_blocks(q_len, kv_len, block_q, block_k)
    if causal:          # square blocks: the diagonal crosses equal indices
        block_q = block_k = min(block_q, block_k)
    if interpret is None:
        interpret = _interpret_kernels()
    spread = h // kv_heads if d < 128 else 1
    plan = _FlashPlan(block_q, block_k, causal,
                      scale if scale is not None else 1.0 / np.sqrt(d),
                      interpret, d, h * d, window, h // kv_heads // spread,
                      spread=spread)

    def part(vmem, pairs):
        return max((n for n in range(1, plan.group + 1)
                    if plan.group % n == 0
                    and (n == 1 or n * block_q * block_k * plan.lanes
                         <= 128 * pairs)
                    and _flash_scoped(vmem(n)) <= _FLASH_VMEM_LIMIT),
                   default=0)

    plan = plan._replace(
        fwd_part=part(lambda n: _flash_fwd_vmem(plan, n, q.dtype),
                      _FLASH_FWD_PAIRS),
        bwd_part=part(lambda n: _flash_bwd_vmem(plan, n, q_len, kv_len,
                                                q.dtype), _FLASH_BWD_PAIRS))
    if (not _use_pallas(q_len, kv_len, d, block_q, block_k, causal)
            or not plan.fwd_part or not plan.bwd_part):
        if not interpret:
            _log_reference_path("flash_attention", (q.shape, k.shape))
        return None
    return plan


def _flash_fwd_tiles(plan, part):
    """(q positions, kv positions, heads) of a score product of the forward
    at `part` of a group's heads a grid step: the tile halved down to 256
    until the step's heads fit in `_FLASH_FWD_COLUMNS` columns, and as
    many of them a product as do."""
    most = _FLASH_FWD_TILE
    while most > 256 and most * plan.heads * part > _FLASH_FWD_COLUMNS:
        most //= 2
    bq, bk = _flash_tile(plan.block_q, most), _flash_tile(plan.block_k, most)
    return bq, bk, plan.heads * min(part, max(
        1, _FLASH_FWD_COLUMNS // (bq * plan.heads)))


def _flash_scoped(held):
    """Scoped VMEM that a call holding `held` bytes asks for, which is what
    the plan holds against `_FLASH_VMEM_LIMIT`: twice those bytes, and no
    less than the compiler's default beside them for the blocks and a
    tile's values (a group of 4 at 2,048 positions, 6 MiB held, was refused
    on the chip when it asked for 12 where the default gives 16)."""
    return max(2 * held, held + _FLASH_VMEM_DEFAULT)


def _flash_fwd_vmem(plan, part, dtype):
    """Bytes the forward's call holds at `part` heads a grid step
    (`_flash_scoped`): O^T in float32, once more while it is turned round,
    the blocks of q and the result in two buffers each, and the scores and
    probabilities of a tile beyond those of a kv block's own heads.  0: the
    compiler's default holds a kv block's own heads."""
    if part == 1:
        return 0
    bq, bk, wide = _flash_fwd_tiles(plan, part)
    return (plan.block_q * part * plan.lanes
            * (8 + 4 * jnp.dtype(dtype).itemsize)
            + 8 * bk * bq * (wide - plan.heads))


def _flash_bwd_vmem(plan, part, q_len, kv_len, dtype):
    """The same of the backward's call: dq^T of the whole length in float32
    and the output block in two buffers, and dk and dv of the whole length,
    float32, where they gather over the parts of a group."""
    return (q_len * part * plan.lanes * (4 + 2 * jnp.dtype(dtype).itemsize)
            + (8 * kv_len * plan.lanes if part < plan.group else 0))


def _flash_call(plan, kernel, vmem=0, **kwargs):
    """One of the kernels as a `pallas_call`.  Both carry the name the
    trace reader keys on (`benchmark/readers.py::flash_roofline`), the
    calls with a window one of their own."""
    return pl.pallas_call(
        functools.partial(kernel, d=plan.d, width=plan.width // plan.group,
                          causal=plan.causal, scale=plan.scale,
                          window=plan.window),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            # the default 16 MiB holds the blocks and a dq of 4 MiB
            vmem_limit_bytes=(_flash_scoped(vmem) if vmem > 4 * 1024 * 1024
                              else None)),
        interpret=plan.interpret,
        name="window_flash_attention" if plan.window else "flash_attention",
        **kwargs)


def _flash_specs(plan, part, blocks, q_at, kv_at):
    """Block specs of a grid (batch x kv column block, a, b): a q-side
    array [batch, length, heads x d] and a kv-side array [batch, length,
    kv_heads x d], read where the model left them, a q-side row of float32
    a head, whose blocks along the length are `q_at(a, b)` and `kv_at(a,
    b)`, and `column(i, a)`, the q-side column block of a grid step.
    Where a grid step takes a `part` of the group and not all of it, the
    second axis is (part, a), `blocks` of a to a part."""
    n, parts = plan.column_blocks, plan.group // part
    column, rows, block = (lambda i, a: i % n), (lambda i, a: i), (lambda a: a)
    if parts > 1:
        column = lambda i, a: i % n * parts + a // blocks
        rows = lambda i, a: i * parts + a // blocks
        block = lambda a: a % blocks
    return (pl.BlockSpec((None, plan.block_q, part * plan.lanes),
                         lambda i, a, b: (i // n, q_at(block(a), b),
                                          column(i, a))),
            pl.BlockSpec((None, plan.block_k, plan.lanes),
                         lambda i, a, b: (i // n, kv_at(block(a), b), i % n)),
            pl.BlockSpec((part * plan.heads, 1, plan.block_q),
                         lambda i, a, b: (rows(i, a), 0,
                                          q_at(block(a), b))),
            column)


def _flash_rows(plan, batch, q_len):
    """Shape of a float32 row a head and q position (the logsumexp, delta):
    a column block's heads together, a head past the arrays' last among
    them where the last block is not whole."""
    return (batch * plan.column_blocks * plan.group * plan.heads, 1, q_len)


def _flash_fwd_heads(plan, q, k, v):
    """out [batch, q_len, heads x d] and the logsumexp (`_flash_rows`) of
    q [batch, length, heads x d] and k, v [batch, length, kv_heads x d]."""
    (batch, q_len, _), kv_len = q.shape, k.shape[1]
    part, n_blocks = plan.fwd_part, q_len // plan.block_q
    # A kv block past the q block's own is not copied in: the index stays
    # at the last block the pair needs, and an unchanged block is kept.
    # Under a window the last axis is the blocks the window reaches, the
    # farthest first, ending in the q block's own.
    steps = kv_len // plan.block_k
    kv_at = lambda a, b: jnp.minimum(a, b) if plan.causal else b
    if plan.window:
        steps = _window_steps(plan.window, plan.block_k, steps)
        kv_at = lambda a, b: jnp.maximum(a - (steps - 1) + b, 0)
    q_spec, kv_spec, row_spec, _ = _flash_specs(plan, part, n_blocks,
                                                lambda a, b: a, kv_at)
    bq, bk, wide = _flash_fwd_tiles(plan, part)
    return _flash_call(
        plan, functools.partial(
            _flash_kernel, bq=bq, bk=bk, n_blocks=n_blocks,
            parts=plan.group // part, wide=wide),
        vmem=_flash_fwd_vmem(plan, part, q.dtype),
        grid=(batch * plan.column_blocks, plan.group // part * n_blocks,
              steps),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(_flash_rows(plan, batch, q_len),
                                        jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((part * plan.heads, 1, plan.block_q), jnp.float32),
            pltpu.VMEM((part * plan.heads, 1, plan.block_q), jnp.float32),
            pltpu.VMEM((part * plan.lanes, plan.block_q), jnp.float32)],
    )(q, k, v)


def _flash_bwd_heads(plan, q, k, v, do, out, lse):
    """dq [batch, length, heads x d] and dk, dv [batch, length, kv_heads x
    d] of q, k, v, dO and the forward's out and logsumexp (`_flash_rows`)."""
    (batch, q_len, _), kv_len = q.shape, k.shape[1]
    part, parts = plan.bwd_part, plan.group // plan.bwd_part
    # Nor is a q block before the kv block's own; under a window the last
    # axis is the q blocks that see the kv block, its own first.
    steps = n_blocks = q_len // plan.block_q
    kv_blocks = kv_len // plan.block_k
    q_at = lambda a, b: jnp.maximum(a, b) if plan.causal else b
    if plan.window:
        steps = _window_steps(plan.window, plan.block_q, n_blocks)
        q_at = lambda a, b: jnp.minimum(a + b, n_blocks - 1)
    q_spec, kv_spec, row_spec, column = _flash_specs(
        plan, part, kv_blocks, q_at, lambda a, b: a)
    n = plan.column_blocks
    # dk and dv of a kv block, of all of them where they gather over parts
    gathered = ((kv_blocks,) if parts > 1 else ()) + (plan.block_k,
                                                      plan.lanes)
    return _flash_call(
        plan, functools.partial(
            _flash_bwd_kernel, bq=_flash_tile(plan.block_q, _FLASH_BWD_TILE),
            bk=_flash_tile(plan.block_k, _FLASH_BWD_TILE),
            least=max(_FLASH_BWD_CROSSED // plan.heads, 128), parts=parts),
        vmem=_flash_bwd_vmem(plan, part, q_len, kv_len, q.dtype),
        grid=(batch * n, parts * kv_blocks, steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec],
        out_specs=[pl.BlockSpec((None, q_len, part * plan.lanes),
                                lambda i, a, b: (i // n, 0, column(i, a))),
                   kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((n_blocks, part * plan.lanes, plan.block_q),
                       jnp.float32)] + 2 * [
            pltpu.VMEM(gathered, jnp.float32)],
    )(q, k, v, do, out, lse)


def _heads_side_by_side(x):
    """[batch, length, heads, d] as the kernels take it, [batch, length,
    heads x d]: the same bytes, no copy."""
    return x.reshape(*x.shape[:2], -1)


def _spread(x, times):
    """k or v [batch, length, kv_heads, d] with every head `times` times,
    one after the other (query head h reads kv head h // times)."""
    return x if times == 1 else jnp.repeat(x, times, axis=2)


def _flash_forward_impl(q, k, v, causal, scale, block_q, block_k, interpret,
                        window=0):
    plan = _flash_plan(q, k, causal, scale, block_q, block_k, interpret,
                       window)
    if plan is None:
        return reference_attention(q, k, v, causal=causal, scale=scale,
                                   window=window), None
    out, lse = _flash_fwd_heads(
        plan, _heads_side_by_side(q),
        *(_heads_side_by_side(_spread(x, plan.spread)) for x in (k, v)))
    return out.reshape(q.shape), lse


def _flash_backward_impl(q, k, v, out, lse, g, causal, scale, block_q,
                         block_k, interpret, window=0):
    plan = _flash_plan(q, k, causal, scale, block_q, block_k, interpret,
                       window)
    dq, dk, dv = _flash_bwd_heads(
        plan, _heads_side_by_side(q),
        *(_heads_side_by_side(_spread(x, plan.spread)) for x in (k, v)),
        *(_heads_side_by_side(x) for x in (g, out)), lse)
    if plan.spread > 1:     # a kv head's gradient is its copies' sum
        dk, dv = (jnp.sum(dx.reshape(*k.shape[:3], plan.spread, -1),
                          axis=3, dtype=jnp.float32).astype(dx.dtype)
                  for dx in (dk, dv))
    return tuple(dx.reshape(x.shape) for dx, x in zip((dq, dk, dv),
                                                      (q, k, v)))


# ---------------------------------------------------------------------------
# Paged-KV attention (decode path for the inference engine)
# ---------------------------------------------------------------------------
#
# The KV cache is ONE buffer per K and V for the engine's lifetime,
# [n_layers, num_blocks, block_size, W] (inference/kv_cache.py): a token's
# K (or V) of every kv head is one row of W = kv_heads * head_dim columns,
# rounded up to a multiple of 128 so that the array's device layout is the
# row-major one a Mosaic DMA reads (a minor dim of 25 x 64 = 1600 makes the
# runtime pick another, and XLA then converts the whole pool around every
# kernel call).  Pad columns are zero and never reach a result.
#
# Each sequence owns a row of a block table mapping its logical context
# positions onto pool blocks.  A step writes the blocks its new tokens fall
# in at (layer, block) and attends over the lane's blocks of that layer;
# both take the whole pool and a layer index, so the pool is never sliced,
# stacked or copied (`inference/compiled.py`'s `count_pool_copies` checks the
# compiled program).  The Pallas kernel copies the blocks that hold a lane's context
# out of the pool by scalar-prefetched block-table indices, a run of blocks
# at a time, and no others (unused table entries are never read; rows past
# the context length inside the last block are masked); the dense fallback
# gathers the table into a contiguous context and masks (there unused
# entries may point anywhere valid) — it covers the T=1 step on the CPU
# and at odd head dims, and is the tests' oracle.  A T > 1 slice (prefill
# chunk, draft run) attends in tiles of plain XLA over the blocks of the
# lanes that have valid rows (`paged_chunk_attention`), on every backend.

from ray_tpu.ops import paged_write  # noqa: E402  (the write path's kernel)

KV_ROW_ALIGN = 128
# Blocks a trip of the XLA loops that move whole blocks (the write path
# where it has no kernel, `_table_blocks`, `eva_summarise`): a few are
# straight-line code, many a short loop.
_KV_WRITE_UNROLL = 8


def kv_row_width(kv_heads: int, head_dim: int) -> int:
    """Columns of one stored K/V row: kv_heads * head_dim rounded up to
    the lane width."""
    return -(-kv_heads * head_dim // KV_ROW_ALIGN) * KV_ROW_ALIGN


def pack_kv_rows(x):
    """[..., KH, D] -> [..., W] stored rows (heads side by side, zero
    pad columns)."""
    *lead, kh, d = x.shape
    pad = kv_row_width(kh, d) - kh * d
    rows = x.reshape(*lead, kh * d)
    return jnp.pad(rows, [(0, 0)] * len(lead) + [(0, pad)]) if pad else rows


def unpack_kv_rows(rows, kv_heads: int, head_dim: int):
    """[..., W] stored rows -> [..., KH, D]."""
    return rows[..., :kv_heads * head_dim].reshape(
        *rows.shape[:-1], kv_heads, head_dim)


def paged_rows_update(pools, rows, block_tables, positions, valid, layer=0,
                      *, use_kernel: Optional[bool] = None):
    """Write one layer's new stored rows into their pools, in place.

    pools: arrays [L, NB, BS, W_i]; rows: as many [B, T, W_i] (a token's
    row as it is stored); block_tables [B, MB] int32; positions [B, T]
    absolute and consecutive per lane (positions[:, :1] + arange(T), as
    every prefill chunk, decode token and draft run is); valid [B, T] bool
    (an invalid slot (padding lane, prompt overhang) changes nothing).
    `layer` may be traced (the layer loop's index).  Lanes with a valid
    row write blocks of their own; what the table of a lane without one
    names is not touched.

    On TPU ONE kernel call (`paged_write.paged_rows_write`): the aligned
    groups of rows (a tile's: 16 of bfloat16) that get a new row are
    copied out of every pool with all copies in flight, merged and copied
    back, and a group no valid row lands in is skipped.  Until PR 45 it
    was the loop below on every backend, 1.65 us a block whatever a block
    held: 37 us a layer for gpt2-xl's 16 lanes, a fifth of its T=1 step
    (PERF.md section 6).  The loop is the CPU's path (the interpreter is
    too slow for the engine tests), the path of a block that is neither
    whole tiles nor at most 32 rows and of a chunk of which one lane's
    groups pass VMEM (logged once per shape on TPU), and the tests'
    oracle: the pools come out bit for bit the same."""
    rows = tuple(r.astype(p.dtype) for r, p in zip(rows, pools))
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
        if use_kernel and paged_write.group_rows(
                pools, positions.shape[1]) is None:
            _log_reference_path("paged_rows_write",
                                (*(p.shape for p in pools), positions.shape))
            use_kernel = False
    if not use_kernel:
        return _rows_update_loop(pools, rows, block_tables, positions, valid,
                                 layer)
    return paged_write.paged_rows_write(
        pools, rows, block_tables, positions, valid, layer,
        interpret=_interpret_kernels())


def _rows_update_loop(pools, rows, block_tables, positions, valid, layer):
    """`paged_rows_update` as plain XLA.  A lane's run touches at most
    (T + BS - 2) // BS + 1 blocks.  Each is read, merged with the rows
    that fall in it and written back with one `dynamic_update_slice` of a
    whole [BS, W] block, one after the other: XLA updates the loop-carried
    pool in place and keeps its layout (a scatter makes it pick another
    layout for the whole pool), and a tile-aligned block costs no more
    than one row (a row at a time, a 32-token chunk took 46 ms over 48
    layers on the v5e, this 3.5; PERF.md section 6)."""
    bs = pools[0].shape[2]
    b, t = positions.shape
    n_touch = (t + bs - 2) // bs + 1
    first, lead = positions[:, 0] // bs, positions[:, 0] % bs
    j = jnp.arange(n_touch)
    # run[b, j, r]: which of the lane's T rows lands in row r of its j-th
    # touched block.
    run = j[None, :, None] * bs - lead[:, None, None] + jnp.arange(bs)
    inside = (run >= 0) & (run < t)
    run = jnp.clip(run, 0, t - 1).reshape(b, n_touch * bs)
    write_row = (inside.reshape(b, -1)
                 & jnp.take_along_axis(valid, run, axis=1)
                 ).reshape(b * n_touch, bs, 1)
    phys = jnp.take_along_axis(
        block_tables,
        jnp.clip(first[:, None] + j, 0, block_tables.shape[1] - 1),
        axis=1).reshape(-1).astype(jnp.int32)                # [B * n_touch]
    new_blocks = tuple(
        jnp.take_along_axis(new, run[:, :, None], axis=1).reshape(
            b * n_touch, bs, new.shape[2]) for new in rows)
    layer = jnp.asarray(layer, jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def write(i, pools):
        at = (layer, phys[i], zero, zero)

        def merged(pool, blocks):
            old = jax.lax.dynamic_slice(pool, at, (1, 1) + pool.shape[2:])
            block = jnp.where(write_row[i], blocks[i], old[0, 0])
            return jax.lax.dynamic_update_slice(pool, block[None, None], at)

        return tuple(merged(*pb) for pb in zip(pools, new_blocks))

    return jax.lax.fori_loop(0, b * n_touch, write, tuple(pools),
                             unroll=min(b * n_touch, _KV_WRITE_UNROLL))


def paged_kv_update(k_pool, v_pool, k_new, v_new, block_tables, positions,
                    valid, layer=0, *, use_kernel: Optional[bool] = None):
    """`paged_rows_update` for K and V: k_pool/v_pool [L, NB, BS, W];
    k_new/v_new [B, T, KH, D], packed into stored rows here."""
    return paged_rows_update(
        (k_pool, v_pool), (pack_kv_rows(k_new), pack_kv_rows(v_new)),
        block_tables, positions, valid, layer, use_kernel=use_kernel)


# Rows up to this many bytes are gathered by XLA's own gather.
_GATHER_ROW_BYTES = 4096


def _table_blocks(pool, layer, block_tables):
    """pool[layer, block_tables]: the blocks a table (of any rank) names,
    [*block_tables.shape, BS, W].  One gather where a row is at most
    `_GATHER_ROW_BYTES`.  A wider row (EvaByte's 4,096 bf16 columns) XLA
    gathers in halves, each from a slice of the WHOLE pool that it first
    copies out (2.4 GB four times a layer at that cell's sizes, compiled
    for a v5e): there the blocks are sliced out one by one into the result,
    a megabyte each."""
    _, _, bs, w = pool.shape
    if w * pool.dtype.itemsize <= _GATHER_ROW_BYTES:
        return pool[layer, block_tables]
    flat = block_tables.reshape(-1).astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def copy(i, out):
        block = jax.lax.dynamic_slice(pool, (layer, flat[i], zero, zero),
                                      (1, 1, bs, w))
        return jax.lax.dynamic_update_slice(out, block[0], (i, zero, zero))

    out = jax.lax.fori_loop(0, flat.shape[0], copy,
                            jnp.zeros((flat.shape[0], bs, w), pool.dtype),
                            unroll=min(flat.shape[0], _KV_WRITE_UNROLL))
    return out.reshape(*block_tables.shape, bs, w)


def paged_attention_reference(q, k_pool, v_pool, block_tables, ctx_lens,
                              q_positions, layer=0, *,
                              kv_heads: Optional[int] = None, scale=None,
                              window: int = 0):
    """Masked-dense paged attention (the T=1 fallback and the oracle).

    q [B, T, H, D] at absolute q_positions [B, T]; pools
    [L, NB, BS, W], read at `layer`; kv_heads (default H) may divide
    H — GQA; ctx_lens [B] = tokens written per lane.  Each query attends
    to context positions <= its own (the query's K/V must already be in
    the pool), with `window` to the last `window` of them alone, its own
    among them.  All-masked rows (inactive lanes) come out as a uniform
    average, never NaN (finite NEG_INF).
    """
    b, t, h, d = q.shape
    bs = k_pool.shape[2]
    kh = kv_heads or h
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    max_ctx = block_tables.shape[1] * bs
    # Straight out of the engine's buffer: [B, MB, BS, W].
    k_ctx = unpack_kv_rows(_table_blocks(k_pool, layer, block_tables), kh,
                           d).reshape(b, max_ctx, kh, d)
    v_ctx = unpack_kv_rows(_table_blocks(v_pool, layer, block_tables), kh,
                           d).reshape(b, max_ctx, kh, d)
    if h != kh:
        k_ctx = jnp.repeat(k_ctx, h // kh, axis=2)
        v_ctx = jnp.repeat(v_ctx, h // kh, axis=2)
    logits = jnp.einsum("bthd,bkhd->bhtk", q.astype(jnp.float32),
                        k_ctx.astype(jnp.float32)) * scale
    kpos = jnp.arange(max_ctx)
    mask = ((kpos[None, None, None, :] <= q_positions[:, None, :, None])
            & (kpos[None, None, None, :] < ctx_lens[:, None, None, None]))
    if window:
        mask = mask & (kpos[None, None, None, :]
                       > q_positions[:, None, :, None] - window)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhtk,bkhd->bthd", probs, v_ctx.astype(jnp.float32))
    return out.astype(q.dtype)


# VMEM the decode kernel holds in cache rows: a run of blocks of each pool,
# twice (one run being multiplied, the next in flight).
_PAGED_RUN_VMEM = 4 << 20
# A run's blocks are fetched by one DMA each, started and awaited in
# straight-line code: past this many a run is mostly that code.
_PAGED_RUN_BLOCKS = 16


def paged_blocks_per_step(block_size: int, width: int, itemsize: int,
                          max_blocks: int) -> int:
    """Blocks in a run of the decode kernel: the power of two that fits
    `_PAGED_RUN_VMEM` (2 pools x 2 buffers x rows x width), at most
    `_PAGED_RUN_BLOCKS` and the table's length.  256 tokens at the rows
    the serve cells store (bf16, 1664 and 2048 columns, blocks of 16):
    in the sweep of PERF.md section 6, PR 32, runs of 8 and of 16 blocks
    read within 4% of each other and 16 was ahead on long contexts."""
    fit = _PAGED_RUN_VMEM // (4 * block_size * width * itemsize)
    kb = 1
    while kb * 2 <= min(fit, _PAGED_RUN_BLOCKS, max_blocks):
        kb *= 2
    return kb


def _head_columns(h: int, kh: int, d: int, w: int, dtype):
    """Where head h's query sits in a [H, W] block-diagonal query: `own`
    [H, W], the columns of its kv head h // (H / KH), and `spread` [D, W],
    0/1 with column c holding dim c % D.  `where(own, q @ spread, 0)` lays
    q [.., H, D] out there and `where(own, o, 0) @ spread.T` takes an
    output's own columns back, each one exact fusion (a broadcast, a
    reshape and a pad of [B, H, KH, D] are a relayout each for heads
    narrower than the lane width)."""
    col = np.arange(w)
    own = col[None, :] // d == np.arange(h)[:, None] // (h // kh)
    spread = col[None, :] % d == np.arange(d)[:, None]
    return own, spread.astype(dtype)


def _paged_decode_kernel(bt_ref, len_ref, layer_ref, *refs, scale: float,
                         windowed: bool = False):
    """One lane of single-query paged attention: a grid step sweeps the
    lane's context run by run, R cache blocks a run, and only the runs
    that hold context.  With `windowed` the next scalar-prefetched operand
    is the lanes' first attended positions: the sweep begins at the block
    of a lane's own, so the blocks behind it are neither fetched nor scored
    (a sliding table names nothing there), and the positions before it in
    that block are masked.

    The pools stay where they are (HBM); the scalar-prefetched block
    table, context lengths and layer index say which [BS, W] blocks of
    stored rows to copy into k_buf / v_buf [2, R, BS, W], one DMA a live
    block, the next run (or the next lane's first) in flight while this
    one is multiplied.  q [H, W] is block-diagonal (head h's query in the
    columns of its kv head, zeros elsewhere), so a row-by-row dot gives
    per-head scores with no relayout of K.  The accumulator is [H, W]:
    head h's output is its kv head's column slice, taken outside.  The
    scores' product runs in the wider of the query's and the pool's dtype
    (bf16 x bf16 is exact in the float32 it accumulates in); the softmax
    state, the probabilities and their product with the values are
    float32, as in the flash kernel above (the copies bound the kernel:
    probabilities rounded to the pool's dtype read no faster).  The state
    is updated once a run where a block is whole tile rows of its dtype,
    so that a run's blocks are one [R * BS, W] operand without a relayout
    (one update a block of 16 and a grid step a block held the kernel at
    10-17% of its roofline: PERF.md section 6, PR 32)."""
    start_ref, refs = (refs[0], refs[1:]) if windowed else (None, refs)
    (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, state, m_ref, l_ref,
     acc_ref) = refs
    _, kb, bs, _ = k_buf.shape
    lanes, mb = bt_ref.shape
    lane = pl.program_id(0)
    layer = layer_ref[0]
    run_tokens = kb * bs
    nxt = jnp.minimum(lane + 1, lanes - 1)
    n_ctx = len_ref[lane]
    if windowed:
        first = start_ref[lane] // bs
        n_runs = (jnp.maximum((n_ctx + bs - 1) // bs - first, 0)
                  + kb - 1) // kb
    else:
        first = 0
        n_runs = (n_ctx + run_tokens - 1) // run_tokens
    dtype = jnp.promote_types(q_ref.dtype, k_buf.dtype)

    def each_copy(i, run, slot, do):
        """`do` every DMA of lane i's `run` into buffer `slot`: the
        blocks that hold context, no others."""
        n_blocks = (len_ref[i] + bs - 1) // bs
        for r in range(kb):
            blk = run * kb + r
            if windowed:
                blk = blk + start_ref[i] // bs

            @pl.when(blk < n_blocks)
            def _(r=r, blk=blk):
                phys = bt_ref[i, jnp.minimum(blk, mb - 1)]
                for p, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    do(pltpu.make_async_copy(
                        hbm.at[layer, phys], buf.at[slot, r],
                        sems.at[p, slot]))

    def start(i, run, slot):
        each_copy(i, run, slot, lambda dma: dma.start())

    @pl.when(lane == 0)
    def _first():
        state[0] = 0                # the buffer of this lane's first run
        state[1] = 0                # 1: that run is already in flight
        # Rows behind a lane's last block are never fetched: their scores
        # are masked, and their values must be finite.
        v_buf[...] = jnp.zeros_like(v_buf)

    slot0 = state[0]
    next_live = (n_runs > 0) & (lane + 1 < lanes) & (len_ref[nxt] > 0)

    @pl.when((n_runs > 0) & (state[1] == 0))
    def _cold():                    # lane 0, or the lane before was empty
        start(lane, 0, slot0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(k, v, base):
        """One online-softmax update with the rows k, v [N, W] of the
        tokens from `base` on."""
        s = jax.lax.dot_general(
            q_ref[...].astype(dtype), k.astype(dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, N]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = pos < n_ctx
        if windowed:
            keep = keep & (pos >= start_ref[lane])
        s = jnp.where(keep, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        pv = jnp.dot(p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)     # [H, W]
        acc_ref[...] = acc_ref[...] * alpha + pv

    def sweep(run, carry):
        slot = (slot0 + run) & 1

        @pl.when(run + 1 < n_runs)
        def _ahead():
            start(lane, run + 1, 1 - slot)

        @pl.when((run + 1 == n_runs) & next_live)
        def _next_lane():
            start(nxt, 0, 1 - slot)

        each_copy(lane, run, slot, lambda dma: dma.wait())
        base = run * run_tokens
        if windowed:
            base = base + first * bs
        if bs % (32 // k_buf.dtype.itemsize) == 0:
            # Whole tile rows: the run's blocks are one operand, and the
            # softmax state (a max, an exp, a rescale of the accumulator)
            # is updated once for the run.
            update(k_buf[slot].reshape(run_tokens, -1),
                   v_buf[slot].reshape(run_tokens, -1), base)
        else:
            # A block that is part of a tile row does not stack without a
            # relayout: one update a block.
            for r in range(kb):
                @pl.when(base + r * bs < n_ctx)
                def _(r=r):
                    update(k_buf[slot, r], v_buf[slot, r], base + r * bs)
        return carry

    jax.lax.fori_loop(0, n_runs, sweep, 0)

    @pl.when(n_runs > 0)
    def _advance():
        state[0] = (slot0 + n_runs) & 1

    state[1] = next_live.astype(jnp.int32)
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
        o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                           layer=0, *, kv_heads: Optional[int] = None,
                           scale: Optional[float] = None,
                           blocks_per_step: Optional[int] = None,
                           use_kernel: Optional[bool] = None,
                           interpret: Optional[bool] = None):
    """Single-query paged attention: q [B, H, D] (one decode token per
    lane) over each lane's block table, in the pools [L, NB, BS, W]
    at `layer` (may be traced).  By default the Pallas kernel on TPU
    where the head dim allows, the masked-dense path on CPU (the
    interpreter is too slow for the engine tests) and for other head dims
    (logged once per shape on TPU).  ctx_lens counts tokens already
    written to the pool INCLUDING the current one.

    The kernel takes a lane a grid step and a run of `blocks_per_step`
    blocks at a time (by default what `paged_blocks_per_step` reads from
    the pool's block size, row width and dtype; the argument is the
    sweep's and the tests'): a lane costs the runs that hold its context,
    an inactive lane (`ctx_lens` 0) nothing, and comes out zero."""
    b, h, d = q.shape
    kh = kv_heads or h
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
        if use_kernel and d not in (64, 128, 256):
            _log_reference_path("paged_decode_attention",
                                (q.shape, k_pool.shape))
            use_kernel = False
    if not use_kernel:
        out = paged_attention_reference(
            q[:, None], k_pool, v_pool, block_tables, ctx_lens,
            (ctx_lens - 1)[:, None], layer, kv_heads=kh, scale=scale)
        return out[:, 0]
    if interpret is None:
        interpret = _interpret_kernels()
    _, _, bs, w = k_pool.shape
    mb = block_tables.shape[1]
    kb = min(blocks_per_step or paged_blocks_per_step(
        bs, w, k_pool.dtype.itemsize, mb), mb)
    return _paged_walk_call(q, k_pool, v_pool, block_tables, ctx_lens, None,
                            layer, kh=kh, scale=scale, kb=kb,
                            name="paged_decode_attention",
                            interpret=interpret)


# (jitted like `_latent_walk_call`, below: a process traces the kernel once a
# shape and a program lowers it once, whichever layer body or program calls
# it: the T=1 program and the pair's call it alike)
@functools.partial(jax.jit, static_argnames=("kh", "scale", "kb", "name",
                                             "interpret"))
def _paged_walk_call(q, k_pool, v_pool, block_tables, ctx_lens, starts,
                     layer, *, kh: int, scale: Optional[float], kb: int,
                     name: str, interpret: bool):
    """The `pallas_call` of `_paged_decode_kernel`: a lane a grid step over
    the pools handed in whole, runs of `kb` blocks; `starts` [B] (the
    lanes' first attended positions) or None."""
    b, h, d = q.shape
    _, _, bs, w = k_pool.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    own, spread = _head_columns(h, kh, d, w, q.dtype)
    q_rows = jnp.where(own, jnp.einsum(
        "bhd,dw->bhw", q, spread, precision=jax.lax.Precision.HIGHEST),
        jnp.zeros((), q.dtype))                                 # [B, H, W]
    prefetch = [block_tables, ctx_lens, jnp.asarray(layer).reshape(1)] + (
        [] if starts is None else [starts])
    lane_spec = pl.BlockSpec((None, h, w), lambda i, *_: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block tables, context lengths, layer (, first positions)
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=[lane_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=lane_spec,
        scratch_shapes=[
            pltpu.VMEM((2, kb, bs, w), k_pool.dtype),
            pltpu.VMEM((2, kb, bs, w), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # [pool, buffer]
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, w), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          windowed=starts is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, w), q.dtype),
        interpret=interpret,
        # Lane by lane in order: a lane starts the next one's first fetch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # The instruction's name in the HLO and so in a device trace: in
        # the engine's layer scan it would be `closed_call.N` without.
        name=name,
    )(*(x.astype(jnp.int32) for x in prefetch), q_rows, k_pool, v_pool)
    # [B, H, W] -> head h's own D columns, the same way back.
    return jnp.einsum("bhw,dw->bhd",
                      jnp.where(own, out, jnp.zeros((), out.dtype)), spread,
                      precision=jax.lax.Precision.HIGHEST)


def window_blocks_per_step(block_size: int, width: int, itemsize: int,
                           max_blocks: int) -> int:
    """Blocks in a run of the windowed decode kernel: runs of one length
    over the `max_blocks` a span can touch, as the kernels that walk ONE
    pool take them (`latent_blocks_per_step`; a K and a V row side by side
    are a row of twice the width).  A window of 2,048 positions over blocks
    of 128 rows of 512 bf16 columns touches 17 blocks: two runs of 9, where
    `paged_blocks_per_step`'s powers of two would make three of 8."""
    return latent_blocks_per_step(block_size, 2 * width, itemsize, max_blocks)


def window_paged_decode_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                                  starts, layer=0, *, span: int,
                                  kv_heads: Optional[int] = None,
                                  scale: Optional[float] = None,
                                  blocks_per_step: Optional[int] = None,
                                  use_kernel: Optional[bool] = None,
                                  interpret: Optional[bool] = None):
    """`paged_decode_attention` over the positions `starts` [B] to
    `ctx_lens` - 1 of each lane, at most `span` of them (a window layer's
    T=1 step over K and V rows: `starts` = max(ctx_lens - span, 0)): the
    kernel's sweep begins at the block of a lane's start, so what lies
    behind it is neither fetched nor scored (a sliding table names nothing
    there), under a name of its own in the HLO and in a device trace
    (`paged_decode_attention` stays the full layers' alone)."""
    b, h, d = q.shape
    kh = kv_heads or h
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
        if use_kernel and d not in (64, 128, 256):
            _log_reference_path("window_paged_decode_attention",
                                (q.shape, k_pool.shape))
            use_kernel = False
    if not use_kernel:
        out = paged_attention_reference(
            q[:, None], k_pool, v_pool, block_tables, ctx_lens,
            (ctx_lens - 1)[:, None], layer, kv_heads=kh, scale=scale,
            window=span)
        return out[:, 0]
    _, _, bs, w = k_pool.shape
    most = min((span + bs - 2) // bs + 1, block_tables.shape[1])
    kb = min(blocks_per_step or window_blocks_per_step(
        bs, w, k_pool.dtype.itemsize, most), most)
    return _paged_walk_call(
        q, k_pool, v_pool, block_tables, ctx_lens, starts, layer, kh=kh,
        scale=scale, kb=kb, name="window_paged_decode_attention",
        interpret=_interpret_kernels() if interpret is None else interpret)


def _chunk_attention(q, out, block_tables, ctx_lens, q_positions, valid, *,
                     fold, read, score, weigh, unfold, heads: int,
                     block_size: int, v_width: int, scale: float,
                     q_tile: int, ctx_tile: int, skip_idle: bool = False,
                     window: int = 0):
    """The loop of the tiled T > 1 paths (`paged_chunk_attention`,
    `latent_chunk_attention`), plain XLA.  Only the lanes that have valid
    rows do work, each over its OWN blocks: per lane a loop over the tiles
    of `q_tile` query rows of q [B, T, ...] that hold a valid row (`valid`
    [B, T] marks a prefix of each lane's rows, `q_positions` [B, T] are
    consecutive), and under it one over the tiles of `ctx_tile` context
    rows at or before the tile's last row, with the online softmax of the
    kernels above in float32.  A padding lane, the rows of a chunk behind
    the prompt's end and the context behind the causal boundary cost
    nothing; no lane's whole context is ever gathered.  Rows without work
    stay as `out` [B, T, ...] has them (zero).

    What a row is, is the caller's: `fold(tile)` makes a tile's queries
    [QT, ...] the rows [..., QT * heads, K] the products take, `heads` to a
    query, one after the other; `read(table, ids)` reads the context tile
    at blocks `ids` of the lane's `table`; `score(rows, c)` [..., R, CT]
    and `weigh(p, c)` [..., R, v_width] are the two products, in float32;
    `unfold(o)` lays the rows' results out as [1, QT, ...] of `out`.
    With `skip_idle` the lane loop makes a trip for each lane that has a
    valid row and none for the others (a trip that finds no work is 2.5 us
    on a v5e: 1.7 ms of a step of 48 layers in which 14 of 16 lanes ride
    along; PERF.md section 6, PR 38).  With `window` a row attends its last
    `window` positions alone, its own among them, and a query tile's loop
    over the context begins at the tile that holds its first row's first
    attended position: what lies behind is neither read nor scored."""
    t = q.shape[1]
    mb = block_tables.shape[1]
    qt = min(q_tile, t)
    while t % qt:
        qt -= 1
    # blocks a context tile: no more than a table has
    per = max(1, min(ctx_tile // block_size, mb))
    ct = per * block_size
    n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)           # [B]
    kcol = jnp.arange(ct, dtype=jnp.int32)

    def lane_body(lane, out):
        table = block_tables[lane]
        pos0 = q_positions[lane, 0]
        n_ctx = ctx_lens[lane]

        def q_body(qi, out):
            rows = fold(jax.lax.dynamic_slice_in_dim(q[lane], qi * qt, qt, 0))
            qpos = jnp.repeat(pos0 + qi * qt
                              + jnp.arange(qt, dtype=jnp.int32), heads)
            reach = jnp.minimum(pos0 + (qi + 1) * qt, n_ctx)

            def ctx_body(kj, carry):
                m, l, acc = carry
                ids = jnp.clip(kj * per + jnp.arange(per), 0, mb - 1)
                c = read(table, ids)
                s = score(rows, c) * scale
                kpos = kj * ct + kcol
                keep = ((kpos[None, :] <= qpos[:, None])
                        & (kpos[None, :] < n_ctx))
                if window:
                    keep = keep & (kpos[None, :] > qpos[:, None] - window)
                s = jnp.where(keep, s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(p, -1, keepdims=True)
                acc = acc * alpha + weigh(p, c)
                return m_new, l, acc

            lead = rows.shape[:-1]
            init = (jnp.full(lead + (1,), NEG_INF, jnp.float32),
                    jnp.zeros(lead + (1,), jnp.float32),
                    jnp.zeros(lead + (v_width,), jnp.float32))
            lo = jnp.maximum(pos0 + qi * qt - (window - 1), 0) // ct \
                if window else 0
            _, l, acc = jax.lax.fori_loop(lo, -(-reach // ct), ctx_body,
                                          init)
            o = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
            return jax.lax.dynamic_update_slice(
                out, unfold(o), (lane, qi * qt) + (0,) * (out.ndim - 2))

        return jax.lax.fori_loop(0, -(-n_valid[lane] // qt), q_body, out)

    if not skip_idle:
        return jax.lax.fori_loop(0, q.shape[0], lane_body, out)
    busy = n_valid > 0
    order = jnp.argsort(~busy, stable=True)         # the busy lanes first
    return jax.lax.fori_loop(0, jnp.sum(busy, dtype=jnp.int32),
                             lambda i, out: lane_body(order[i], out), out)


def paged_chunk_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                          q_positions, valid, layer=0, *,
                          kv_heads: Optional[int] = None,
                          scale: Optional[float] = None,
                          q_tile: int = 512,
                          ctx_tile: Optional[int] = None,
                          window: int = 0):
    """Paged attention for a [B, T, H, D] slice of T > 1 query rows (a
    prefill chunk, a draft run) over K/V pools [L, NB, BS, W] at `layer`:
    `_chunk_attention`'s tiles, so a step of 16 lanes of which two prefill
    64 tokens reads and scores those two lanes' 64 rows, not 1,024 rows of
    all 16.  A tile's rows are read through the lane's table
    (`_table_blocks`: block by block where a row is too wide for XLA's
    gather) and unpacked to `kv_heads` heads; the query heads of a group
    multiply their one K/V head as they are (GQA without a repeat of the
    context).  Products in the pools' dtype accumulated in float32, as the
    T=1 kernel's.  Each valid query attends to the rows at or before its
    own `q_positions` (with `window`: the last `window` of them, and the
    tiles behind a query tile's window are not read); rows without work
    come out zero.

    Tiles by the shapes (the arguments are the sweep's and the tests'): a
    chunk of up to 512 rows is one query tile, so a context tile is read
    once a chunk (EvaByte's [4, 512] over 2.8k rows: 9.7 ms for eight
    layers at 512, 21.0 at 128, 33.7 dense); a context tile is four times
    the chunk, between 128 and 512 rows: a short chunk's products are
    small and it pays by the rows it reads (gpt2-xl's [16, 32] over 100-350
    tokens: 19.2 ms for 48 layers at 128, 27.6 at 512), a long chunk's by
    the trips it makes (v5e, PERF.md section 6, PR 38)."""
    b, t, h, d = q.shape
    kh = kv_heads or h
    g = h // kh
    bs, w = k_pool.shape[2:]
    layer = jnp.asarray(layer, jnp.int32)
    if ctx_tile is None:
        ctx_tile = min(512, max(128, 4 * t))
    if w * k_pool.dtype.itemsize > _GATHER_ROW_BYTES:
        # Never one block a tile where `_table_blocks` slices blocks out:
        # a lone `dynamic_slice` fuses into the product that reads it, and
        # XLA re-lays the WHOLE pool for that product (both pools copied,
        # 9 GB at EvaByte's sizes: the program does not fit a v5e).
        ctx_tile = max(ctx_tile, 2 * bs)

    def fold(tile):                     # [QT, H, D] -> [KH, QT * G, D]
        return jnp.moveaxis(tile.reshape(-1, kh, g, d), 1, 0).reshape(
            kh, -1, d)

    def read(table, ids):               # K and V, each [CT, KH, D]
        return tuple(unpack_kv_rows(
            _table_blocks(pool, layer, table[ids]), kh, d).reshape(-1, kh, d)
            for pool in (k_pool, v_pool))

    def score(rows, kv):
        return jnp.einsum("nrd,cnd->nrc", rows, kv[0],
                          preferred_element_type=jnp.float32)

    def weigh(p, kv):
        return jnp.einsum("nrc,cnd->nrd", p.astype(kv[1].dtype), kv[1],
                          preferred_element_type=jnp.float32)

    def unfold(o):                      # [KH, QT * G, D] -> [1, QT, H, D]
        return jnp.moveaxis(o.reshape(kh, -1, g, d), 0, 1).reshape(
            1, -1, h, d)

    out = _chunk_attention(
        q.astype(k_pool.dtype), jnp.zeros(q.shape, k_pool.dtype),
        block_tables, ctx_lens, q_positions, valid, fold=fold, read=read,
        score=score, weigh=weigh, unfold=unfold, heads=g, block_size=bs,
        v_width=d, scale=scale if scale is not None else 1.0 / np.sqrt(d),
        q_tile=q_tile, ctx_tile=ctx_tile, skip_idle=True, window=window)
    return out.astype(q.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, ctx_lens, q_positions,
                    layer=0, *, valid=None, kv_heads: Optional[int] = None,
                    scale: Optional[float] = None, window: int = 0):
    """Dispatch paged attention for a [B, T, H, D] query slice: the T=1
    decode step rides the single-query kernel path, longer slices the
    tiled path over the rows `valid` [B, T] marks (default: all).  With
    `window` a row attends its last `window` positions alone
    (`window_paged_decode_attention`, the tiled path's `window`)."""
    if q.shape[1] == 1 and window:
        return window_paged_decode_attention(
            q[:, 0], k_pool, v_pool, block_tables, ctx_lens,
            jnp.maximum(ctx_lens - window, 0), layer, span=window,
            kv_heads=kv_heads, scale=scale)[:, None]
    if q.shape[1] == 1:
        return paged_decode_attention(
            q[:, 0], k_pool, v_pool, block_tables, ctx_lens, layer,
            kv_heads=kv_heads, scale=scale)[:, None]
    if valid is None:
        valid = jnp.ones(q.shape[:2], bool)
    return paged_chunk_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                                 q_positions, valid, layer,
                                 kv_heads=kv_heads, scale=scale,
                                 window=window)


# ---------------------------------------------------------------------------
# EVA: an exact window beside one summary row for every chunk behind it
# ---------------------------------------------------------------------------
#
# Position i attends exactly to the keys of its own window of `window`
# positions and, for every EARLIER window, to one summary row (k-bar, v-bar)
# for each `chunk` positions of it; one softmax over both.  A summary is a
# chunk's keys (values) averaged under softmax(mu . k) (softmax(phi . k)),
# mu and phi learned vectors per head.  In a lane's paged table the summary
# rows of the closed windows come first, `window // chunk` a window, then the
# exact rows of the open one: a row is a row, so the paged kernels above read
# the table as it is.


def eva_row(pos, window: int, chunk: int):
    """The row of token position `pos` in its lane's table (ints or arrays):
    behind the summary rows of the windows before its own."""
    return pos // window * (window // chunk) + pos % window


def eva_summaries(k, v, mu, phi):
    """Chunks of rotated keys and values [..., C, H, D] -> their summary rows
    (k-bar, v-bar) [..., H, D], in float32: weights softmax over the chunk's C
    positions of mu . k for the keys and of phi . k for the values, mu, phi
    [H, D].  Products and sums elementwise, so that float32 stays float32 on
    a TPU (a chunk is 16 rows: nothing here is a matrix worth the MXU)."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    def pooled(w, x):
        logits = jnp.sum(k32 * w.astype(jnp.float32), -1)       # [..., C, H]
        p = jax.nn.softmax(logits, axis=-2)
        return jnp.sum(p[..., None] * x, axis=-3)

    return pooled(mu, k32), pooled(phi, v32)


def eva_summarise(k_pool, v_pool, mu, phi, src, dst, live, layer=0, *,
                  chunk: int, kv_heads: int, head_dim: int):
    """Close a window of `src.shape[0]` lanes at one layer, in place: the
    exact rows in pool blocks `src` [N, window / BS] become summary rows
    written into blocks `dst` [N, window / chunk / BS] of the same pools;
    `live` [N] bool (a row nobody has changes nothing).  `layer` may be
    traced.  The blocks are gathered by index and written back one whole
    [BS, W] block at a time, as `_rows_update_loop` writes: the pools stay
    where they are."""
    bs = k_pool.shape[2]
    n, n_dst = dst.shape
    rows = src.shape[1] * bs
    layer = jnp.asarray(layer, jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def one_lane(blocks):
        # A lane at a time: the float32 chunks of one window are 34 MB a
        # pool at EvaByte's widths (`_table_blocks` says why its blocks are
        # not gathered at those widths).
        k, v = (unpack_kv_rows(_table_blocks(pool, layer, blocks[None]),
                               kv_heads, head_dim).reshape(
            rows // chunk, chunk, kv_heads, head_dim)
            for pool in (k_pool, v_pool))
        return tuple(pack_kv_rows(x.astype(pool.dtype)).reshape(
            n_dst, bs, pool.shape[3])
            for x, pool in zip(eva_summaries(k, v, mu, phi),
                               (k_pool, v_pool)))

    new = tuple(x.reshape(n * n_dst, bs, x.shape[-1])
                for x in jax.lax.map(one_lane, src))
    phys = dst.reshape(-1).astype(jnp.int32)
    keep = jnp.repeat(live, n_dst)

    def write(i, pools):
        at = (layer, phys[i], zero, zero)

        def merged(pool, blocks):
            old = jax.lax.dynamic_slice(pool, at, (1, 1) + pool.shape[2:])
            block = jnp.where(keep[i], blocks[i], old[0, 0])
            return jax.lax.dynamic_update_slice(pool, block[None, None], at)

        return tuple(merged(*pb) for pb in zip(pools, new))

    return jax.lax.fori_loop(0, n * n_dst, write, (k_pool, v_pool),
                             unroll=min(n * n_dst, _KV_WRITE_UNROLL))


def eva_attention(q, k, v, mu, phi, *, window: int, chunk: int, mesh=None):
    """EVA over a whole sequence from position 0: q, k, v [B, L, H, D]
    (rotated), mu, phi [H, D] -> [B, L, H, D].  Up to one window it is
    causal attention as it stands (`mesh_flash_attention`); past it, plain
    XLA over windows: each window's causal scores beside its scores against
    the summaries of every chunk of the windows before it, one softmax in
    float32 (the serve path never runs this: it attends through the paged
    cache, `eva_row`; a kernel and a backward pass are owed, ROADMAP.md)."""
    b, l, h, d = q.shape
    if l <= window:
        return mesh_flash_attention(q, k, v, mesh=mesh, causal=True)
    n_win, per = -(-l // window), window // chunk
    pad = [(0, 0), (0, n_win * window - l), (0, 0), (0, 0)]
    qw, kw, vw = (jnp.pad(x, pad).astype(jnp.float32).reshape(
        b, n_win, window, h, d) for x in (q, k, v))
    kbar, vbar = (x.reshape(b, n_win * per, h, d) for x in eva_summaries(
        kw.reshape(b, n_win, per, chunk, h, d),
        vw.reshape(b, n_win, per, chunk, h, d), mu, phi))
    scale = 1.0 / np.sqrt(d)
    exact = jnp.einsum("bwqhd,bwkhd->bwhqk", qw, kw) * scale
    causal = jnp.tril(jnp.ones((window, window), bool))
    exact = jnp.where(causal, exact, NEG_INF)
    summ = jnp.einsum("bwqhd,bshd->bwhqs", qw, kbar) * scale
    behind = (jnp.arange(n_win * per)[None, :] // per
              < jnp.arange(n_win)[:, None])                    # [n_win, S]
    summ = jnp.where(behind[None, :, None, None, :], summ, NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([summ, exact], -1), axis=-1)
    out = (jnp.einsum("bwhqs,bshd->bwqhd", probs[..., :n_win * per], vbar)
           + jnp.einsum("bwhqk,bwkhd->bwqhd", probs[..., n_win * per:], vw))
    return out.reshape(b, n_win * window, h, d)[:, :l].astype(q.dtype)


# ---------------------------------------------------------------------------
# Latent attention over a latent paged cache (MLA, absorbed form)
# ---------------------------------------------------------------------------
#
# A latent pool [n_layers, num_blocks, block_size, W] holds ONE row a token
# a layer: the normed latent c_kv (`v_width` columns, which are the key's
# no-position part and the value at once) and behind it the one rotated key
# k_rope all heads share, rounded up to the lane width with zero columns.
# A query row is laid out the same way: q_nope carried into the latent
# space (q_nope W_uk^T) and behind it the head's rotated q_rope.  Then
# score = q_row . row, and the output in latent space is p @ row[:v_width];
# the caller takes it through W_uv.  Every head reads the same row, so a
# tile of the cache is read once for all heads, scores and values.

def latent_row_width(v_width: int, rope_dim: int) -> int:
    """Columns of one stored latent row, rounded up to the lane width."""
    return -(-(v_width + rope_dim) // KV_ROW_ALIGN) * KV_ROW_ALIGN


def pack_latent_rows(latent, rope_part):
    """[..., C] and [..., R] -> [..., W] rows (zero pad columns)."""
    pad = latent_row_width(latent.shape[-1], rope_part.shape[-1]) \
        - latent.shape[-1] - rope_part.shape[-1]
    rows = jnp.concatenate([latent, rope_part.astype(latent.dtype)], -1)
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)]) if pad \
        else rows


def latent_attention_reference(q, pool, block_tables, ctx_lens, q_positions,
                               layer=0, *, v_width: int, scale: float,
                               window: int = 0):
    """Masked-dense latent attention (ground truth, and the CPU's T=1
    path): q [B, T, H, W] rows at absolute q_positions [B, T]; pool
    [L, NB, BS, W] read at `layer`.  Gathers every lane's whole table.
    With `window` a row attends its last `window` positions, its own
    among them.  Returns the output in latent space [B, T, H, v_width]."""
    b = q.shape[0]
    bs, w = pool.shape[2:]
    max_ctx = block_tables.shape[1] * bs
    ctx = pool[layer, block_tables].reshape(b, max_ctx, w).astype(
        jnp.float32)
    logits = jnp.einsum("bthw,bkw->bhtk", q.astype(jnp.float32), ctx) * scale
    kpos = jnp.arange(max_ctx)
    mask = ((kpos[None, None, None, :] <= q_positions[:, None, :, None])
            & (kpos[None, None, None, :] < ctx_lens[:, None, None, None]))
    if window:
        mask = mask & (kpos[None, None, None, :]
                       > q_positions[:, None, :, None] - window)
    probs = jax.nn.softmax(jnp.where(mask, logits, NEG_INF), axis=-1)
    out = jnp.einsum("bhtk,bkc->bthc", probs, ctx[..., :v_width])
    return out.astype(q.dtype)


# VMEM the kernels that walk ONE pool of rows hold in them: a run of blocks,
# twice.  16 blocks of 128 rows of 640 bf16 columns are 5.2 MB.
_ROWS_RUN_VMEM = 6 << 20


def latent_blocks_per_step(block_size: int, width: int, itemsize: int,
                           max_blocks: int) -> int:
    """Blocks in a run of the kernels that walk one pool of rows (latent
    rows, window rows, index keys): as many runs as a lane of `max_blocks`
    blocks needs where a run fits `_ROWS_RUN_VMEM` (2 buffers x rows x
    width) and is at most `_PAGED_RUN_BLOCKS`, and those runs of one
    length: a run is multiplied whole, so a last run of a few live blocks
    costs a full one, and these kernels' time goes by the blocks they
    multiply (A.X-K1's 132 blocks a lane on a v5e: 0.925 ms a call in runs
    of 11, 12 or 15, 0.965 in 10 runs of 14, 0.983 in 9 of 16; PERF.md
    section 6, PR 49).  At blocks of 128 bf16 rows: 15 of 640 columns over
    A.X-K1's table of 132, all 16 of dots3's gathered blocks, 6 of 1,152
    (its window rows: all a span of 513 touches), 15 of 128 over its 133
    blocks of index keys."""
    fit = _ROWS_RUN_VMEM // (2 * block_size * width * itemsize)
    n_runs = -(-max_blocks // max(1, min(fit, _PAGED_RUN_BLOCKS)))
    return -(-max_blocks // n_runs)


def _walk_lane_runs(bt_ref, len_ref, layer_ref, start_ref, hbm, buf, sems,
                    state, on_rows):
    """The walk of the single-query kernels over one pool of rows, as
    `_paged_decode_kernel` walks two: a grid step is a lane, and sweeps the
    lane's context run by run, R blocks a run, from the block of its first
    attended position (`start_ref[lane]`, or 0 without one) to its last.

    The pool stays where it is (HBM); the scalar-prefetched block table,
    context lengths and layer index say which [BS, W] blocks to copy into
    buf [2, R, BS, W], one DMA a live block, the next run (or the next
    lane's first) in flight while `on_rows(rows, base)` works on the run
    that has arrived: its rows [N, W], the first of them position `base`.
    That is the whole run, [R * BS, W], where a block is whole tile rows of
    its dtype, so that a run's blocks are one operand without a relayout
    (a kernel with an online softmax then updates its state once a run),
    and otherwise each block that holds context in turn.  A lane without
    context starts no copy and makes no trip.  Rows behind a lane's last
    block are never fetched: the buffer is zeroed once, at lane 0, so that
    they are finite, and `on_rows` masks them by position."""
    _, kb, bs, _ = buf.shape
    lanes, mb = bt_ref.shape
    lane = pl.program_id(0)
    layer = layer_ref[0]
    nxt = jnp.minimum(lane + 1, lanes - 1)

    def blocks(i):
        """Lane i's first attended block, and one past its last."""
        first = 0 if start_ref is None else start_ref[i] // bs
        return first, (len_ref[i] + bs - 1) // bs

    def each_copy(i, run, slot, do):
        """`do` every DMA of lane i's `run` into buffer `slot`: the
        blocks that hold context, no others.  (Straight-line code, a
        branch a block: in a loop over the live blocks a copy of a block of
        index keys, 32 KB, took longer to issue than to make: 0.55 ms a
        call where this form takes 0.45; PERF.md section 6, PR 49.)"""
        first, end = blocks(i)
        for r in range(kb):
            blk = first + run * kb + r

            @pl.when(blk < end)
            def _(r=r, blk=blk):
                do(pltpu.make_async_copy(
                    hbm.at[layer, bt_ref[i, jnp.minimum(blk, mb - 1)]],
                    buf.at[slot, r], sems.at[slot]))

    def start(i, run, slot):
        each_copy(i, run, slot, lambda dma: dma.start())

    @pl.when(lane == 0)
    def _first():
        state[0] = 0                # the buffer of this lane's first run
        state[1] = 0                # 1: that run is already in flight
        buf[...] = jnp.zeros_like(buf)

    first, end = blocks(lane)
    n_runs = (jnp.maximum(end - first, 0) + kb - 1) // kb
    nxt_first, nxt_end = blocks(nxt)
    next_live = (n_runs > 0) & (lane + 1 < lanes) & (nxt_end > nxt_first)
    slot0 = state[0]

    @pl.when((n_runs > 0) & (state[1] == 0))
    def _cold():                    # lane 0, or the lane before was empty
        start(lane, 0, slot0)

    def sweep(run, carry):
        slot = (slot0 + run) & 1

        @pl.when(run + 1 < n_runs)
        def _ahead():
            start(lane, run + 1, 1 - slot)

        @pl.when((run + 1 == n_runs) & next_live)
        def _next_lane():
            start(nxt, 0, 1 - slot)

        each_copy(lane, run, slot, lambda dma: dma.wait())
        base = (first + run * kb) * bs
        if bs % (32 // buf.dtype.itemsize) == 0:
            on_rows(buf[slot].reshape(kb * bs, -1), base)
        else:
            for r in range(kb):
                @pl.when(base + r * bs < len_ref[lane])
                def _(r=r):
                    on_rows(buf[slot, r], base + r * bs)
        return carry

    jax.lax.fori_loop(0, n_runs, sweep, 0)

    @pl.when(n_runs > 0)
    def _advance():
        state[0] = (slot0 + n_runs) & 1

    state[1] = next_live.astype(jnp.int32)


def _latent_decode_kernel(bt_ref, len_ref, layer_ref, *refs, v_width: int,
                          scale: float, windowed: bool = False):
    """One lane of single-query latent attention (`_walk_lane_runs`; with
    `windowed` the next scalar-prefetched operand is the lanes' first
    attended positions, and positions before a lane's are masked).  q
    [H, W]; a run's rows are read once: scores for all H heads against
    them, then their first v_width columns as the values.  Products run on
    the MXU in the pool's dtype with float32 accumulation, the
    probabilities rounded to the pool's dtype; the softmax state is float32
    in scratch across the lane's sweep, as in the kernels above, and is
    updated once for the whole run where a block is whole tile rows (one
    update a block was 31% of the kernel's roofline at blocks of 128 and
    4.5% at blocks of 16, and a grid step a run of 512 rows 63%: PERF.md
    section 6, PRs 31 and 49)."""
    start_ref, refs = (refs[0], refs[1:]) if windowed else (None, refs)
    q_ref, hbm, o_ref, buf, sems, state, m_ref, l_ref, acc_ref = refs
    lane = pl.program_id(0)
    n_ctx = len_ref[lane]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(c, base):
        """One online-softmax update with the rows c [N, W] of the
        positions from `base` on."""
        s = jax.lax.dot_general(
            q_ref[...], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [H, N]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = pos < n_ctx
        if windowed:
            keep = keep & (pos >= start_ref[lane])
        s = jnp.where(keep, s * scale, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(c.dtype), c[:, :v_width],
            preferred_element_type=jnp.float32)                  # [H, C]

    _walk_lane_runs(bt_ref, len_ref, layer_ref, start_ref, hbm, buf, sems,
                    state, update)
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
        o_ref.dtype)


# (jitted, like `sparse_select`, so that a process traces a kernel once and a
# program lowers it once, not once a layer body: a body of 3 x R copy sites
# is a third of a second to trace and lower, and dots3's step programs call
# these kernels from five layer bodies each)
@functools.partial(jax.jit, static_argnames=(
    "v_width", "scale", "max_blocks", "blocks_per_step", "name",
    "interpret"))
def _latent_walk_call(q, pool, block_tables, ctx_lens, starts, layer, *,
                      v_width: int, scale: float, max_blocks: int,
                      blocks_per_step: Optional[int], name: str,
                      interpret: bool):
    """The `pallas_call` of `_latent_decode_kernel`: a lane a grid step over
    the pool handed in whole, `max_blocks` the most a lane's walk can
    touch; `starts` [B] or None."""
    b, h, w = q.shape
    bs = pool.shape[2]
    kb = min(blocks_per_step or latent_blocks_per_step(
        bs, w, pool.dtype.itemsize, max_blocks), max_blocks)
    prefetch = [block_tables, ctx_lens, jnp.asarray(layer).reshape(1)] + (
        [] if starts is None else [starts])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block tables, context lengths, layer (, first positions)
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=[pl.BlockSpec((None, h, w), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, h, v_width), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kb, bs, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),          # a buffer each
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, v_width), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, v_width=v_width,
                          scale=scale, windowed=starts is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, v_width), q.dtype),
        interpret=interpret,
        # Lane by lane in order: a lane starts the next one's first fetch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        # The instruction's name in the HLO and so in a device trace.
        name=name,
    )(*(x.astype(jnp.int32) for x in prefetch), q.astype(pool.dtype), pool)


def window_latent_decode_attention(q, pool, block_tables, ctx_lens, starts,
                                   layer=0, *, v_width: int, scale: float,
                                   span: int,
                                   name="window_latent_decode_attention",
                                   blocks_per_step: Optional[int] = None,
                                   use_kernel: Optional[bool] = None,
                                   interpret: Optional[bool] = None):
    """`latent_decode_attention` over the positions `starts` [B] to
    `ctx_lens` - 1 of each lane, at most `span` of them: the walk begins at
    the block of a lane's start, so the blocks behind it are neither
    fetched nor scored (its table may name anything there), and a run is
    as long as the blocks a span can touch where they fit (a span of 513
    over blocks of 128: one run of its 5 or 6 live blocks)."""
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
    if not use_kernel:
        return latent_attention_reference(
            q[:, None], pool, block_tables, ctx_lens,
            (ctx_lens - 1)[:, None], layer, v_width=v_width, scale=scale,
            window=span)[:, 0]
    bs = pool.shape[2]
    return _latent_walk_call(
        q, pool, block_tables, ctx_lens, starts, layer, v_width=v_width,
        scale=scale, name=name, blocks_per_step=blocks_per_step,
        max_blocks=min((span + bs - 2) // bs + 1, block_tables.shape[1]),
        interpret=_interpret_kernels() if interpret is None else interpret)


def latent_decode_attention(q, pool, block_tables, ctx_lens, layer=0, *,
                            v_width: int, scale: float,
                            blocks_per_step: Optional[int] = None,
                            use_kernel: Optional[bool] = None,
                            interpret: Optional[bool] = None,
                            name="latent_decode_attention"):
    """Single-query latent attention: q [B, H, W] rows (one decode token a
    lane) over each lane's block table in the latent pool [L, NB, BS, W]
    at `layer` (may be traced); ctx_lens counts the tokens written,
    the current one included.  Returns [B, H, v_width], the output in
    latent space.  The Pallas kernel on TPU, the masked-dense path on the
    CPU (the interpreter is too slow for the engine tests).

    The kernel takes a lane a grid step and a run of `blocks_per_step`
    blocks at a time (by default what `latent_blocks_per_step` reads from
    the pool's block size, row width and dtype; the argument is the
    sweep's and the tests'): a lane costs the runs that hold its context,
    an inactive lane (`ctx_lens` 0) nothing, and comes out zero."""
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
    if not use_kernel:
        return latent_attention_reference(
            q[:, None], pool, block_tables, ctx_lens,
            (ctx_lens - 1)[:, None], layer, v_width=v_width,
            scale=scale)[:, 0]
    return _latent_walk_call(
        q, pool, block_tables, ctx_lens, None, layer, v_width=v_width,
        scale=scale, name=name, blocks_per_step=blocks_per_step,
        max_blocks=block_tables.shape[1],
        interpret=_interpret_kernels() if interpret is None else interpret)


def latent_chunk_attention(q, pool, block_tables, ctx_lens, q_positions,
                           valid, layer=0, *, v_width: int, scale: float,
                           q_tile: int = 128, ctx_tile: int = 512):
    """Latent attention for a [B, T, H, W] slice of T > 1 query rows (a
    prefill chunk, a draft run): `_chunk_attention`'s tiles over the latent
    pool.  A tile of the cache is gathered through the lane's block table
    once for all heads: its rows whole for the scores, their first
    `v_width` columns for the values.  Rows without work come out zero.
    Returns [B, T, H, v_width]."""
    b, t, h, w = q.shape
    layer = jnp.asarray(layer, jnp.int32)

    def score(rows, c):
        return jax.lax.dot_general(rows, c, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def weigh(p, c):
        return jnp.dot(p.astype(c.dtype), c[:, :v_width],
                       preferred_element_type=jnp.float32)

    return _chunk_attention(
        q, jnp.zeros((b, t, h, v_width), q.dtype), block_tables, ctx_lens,
        q_positions, valid,
        fold=lambda tile: tile.reshape(-1, w),
        read=lambda table, ids: pool[layer, table[ids]].reshape(-1, w),
        score=score, weigh=weigh,
        unfold=lambda o: o.reshape(1, -1, h, v_width), heads=h,
        block_size=pool.shape[2], v_width=v_width, scale=scale,
        q_tile=q_tile, ctx_tile=ctx_tile)


def latent_attention(q, pool, block_tables, ctx_lens, q_positions, valid,
                     layer=0, *, v_width: int, scale: float,
                     window: int = 0):
    """Dispatch latent attention for a [B, T, H, W] query slice: the T=1
    decode step rides the single-query kernel, longer slices the tiled
    path.  With `window` a row attends its last `window` positions alone
    (`window_latent_decode_attention`; a longer slice's rows each as a
    lane of its own: `_rows_as_lanes`)."""
    if window:
        def attend(name):
            def fn(tables, ctx, q_rows):
                return window_latent_decode_attention(
                    q_rows, pool, tables, ctx, jnp.maximum(ctx - window, 0),
                    layer, v_width=v_width, scale=scale, span=window,
                    name=name)
            return fn
        if q.shape[1] == 1:
            return attend("window_latent_decode_attention")(
                block_tables, ctx_lens, q[:, 0])[:, None]
        return _rows_as_lanes(
            attend("window_latent_chunk_attention"), block_tables,
            q_positions, valid, (q,),
            jnp.zeros(q.shape[:-1] + (v_width,), q.dtype))
    if q.shape[1] == 1:
        return latent_decode_attention(
            q[:, 0], pool, block_tables, ctx_lens, layer, v_width=v_width,
            scale=scale)[:, None]
    return latent_chunk_attention(q, pool, block_tables, ctx_lens,
                                  q_positions, valid, layer,
                                  v_width=v_width, scale=scale)


# Rows of a T > 1 slice a trip of `_rows_as_lanes` takes as its lanes.
_ROW_TILE = 64


def _rows_as_lanes(fn, block_tables, q_positions, valid, parts, out):
    """A T > 1 slice through a single-query form: every valid row is a lane
    of its own whose context ends at its own position (the slice's rows are
    in the pool before anything attends), `_ROW_TILE` rows a trip, a trip
    for the tiles that hold a valid row and none for the others.
    `fn(tables [R, MB], ctx_lens [R], *rows [R, ...])` -> [R, ...];
    `parts` are the [B, T, ...] arrays whose rows it takes; rows without
    work stay as `out` [B, T, ...] has them."""
    b, t = q_positions.shape
    n = b * t
    tile = min(_ROW_TILE, n)
    flat_valid = valid.reshape(n)
    order = jnp.argsort(~flat_valid, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, -n % tile), constant_values=n)
    n_valid = jnp.sum(flat_valid, dtype=jnp.int32)
    flat_pos = q_positions.reshape(n)
    flat_parts = [x.reshape((n,) + x.shape[2:]) for x in parts]

    def body(i, flat_out):
        rows = jax.lax.dynamic_slice_in_dim(order, i * tile, tile)
        live = i * tile + jnp.arange(tile, dtype=jnp.int32) < n_valid
        at = jnp.minimum(rows, n - 1)
        ctx = jnp.where(live, flat_pos[at] + 1, 0)
        res = fn(block_tables[at // t], ctx, *(x[at] for x in flat_parts))
        return flat_out.at[jnp.where(live, rows, n)].set(
            res.astype(flat_out.dtype), mode="drop")

    flat = jax.lax.fori_loop(0, -(-n_valid // tile), body,
                             out.reshape((n,) + out.shape[2:]))
    return flat.reshape(out.shape)


# --------------------------------------------------------------------------
# Indexed (sparse) latent attention: a learned indexer scores every cached
# position of a lane against the query (a paged pool of ONE index key a
# token, in the blocks and under the table of the latent rows), the `topk`
# positions of largest score are chosen, exactly, and attention reads the
# chosen rows of the latent pool and no others.  The choice sorts nothing
# (`sparse_select`, since PR 48): the k-th score by bisection over the
# scores' bits, the chosen set by compares against it, their places by
# counts over chunks of 128 positions and one one-hot product a lane.
# The keys and the chosen rows are read by the kernels that walk a lane's
# rows in runs (`_walk_lane_runs`, since PR 49): `_index_scores_kernel` over
# the pool of index keys through the lane's table, `_latent_decode_kernel`
# over the gathered rows, which XLA's gather has laid side by side, as a
# pool of one layer whose table counts its blocks up.
# --------------------------------------------------------------------------

def index_scores_reference(q_i, w_i, index_pool, block_tables, ctx_lens,
                           layer=0):
    """I[b, s] = sum_j w_i[b, j] relu(q_i[b, j] . k[b, s]) for every
    position s of lane b's table, NEG_INF from its context's end on
    (ground truth and the CPU's path).  q_i [B, Hi, Di], w_i [B, Hi]
    float32, index_pool [L, NB, BS, Di]; float32 [B, MB * BS]."""
    b = q_i.shape[0]
    keys = index_pool[layer, block_tables].reshape(b, -1, q_i.shape[-1])
    s = jnp.einsum("bhd,bsd->bhs", q_i.astype(keys.dtype), keys,
                   preferred_element_type=jnp.float32)
    scores = jnp.sum(jax.nn.relu(s) * w_i[:, :, None], axis=1)
    kpos = jnp.arange(keys.shape[1])
    return jnp.where(kpos[None, :] < ctx_lens[:, None], scores, NEG_INF)


def _index_scores_kernel(bt_ref, len_ref, layer_ref, q_ref, w_ref, hbm,
                         o_ref, buf, sems, state):
    """One lane of the indexer's scores (`_walk_lane_runs` over the pool of
    index keys): a run's keys against all Hi index queries on the MXU,
    ReLU, the heads' weighted sum, and the run's scores written where they
    belong in the lane's row [1, N]; NEG_INF from the context's end on."""
    n_ctx = len_ref[pl.program_id(0)]
    o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    def score(keys, base):
        s = jax.lax.dot_general(
            q_ref[...], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [Hi, N]
        scores = jnp.sum(jnp.maximum(s, 0.0) * w_ref[...], axis=0,
                         keepdims=True)                         # [1, N]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        at = pl.ds(pl.multiple_of(base, keys.shape[0]), keys.shape[0])
        o_ref[:, at] = jnp.where(pos < n_ctx, scores, NEG_INF)

    _walk_lane_runs(bt_ref, len_ref, layer_ref, None, hbm, buf, sems, state,
                    score)


def sparse_index_scores(q_i, w_i, index_pool, block_tables, ctx_lens,
                        layer=0, *, name: str = "sparse_index_scores",
                        blocks_per_step: Optional[int] = None,
                        use_kernel: Optional[bool] = None,
                        interpret: Optional[bool] = None):
    """The indexer's score of every cached position of each lane, one
    decode token a lane (`index_scores_reference`): float32 [B, N] with
    N >= MB * BS.  The Pallas kernel on TPU (a lane a grid step, its keys
    walked in runs of `blocks_per_step` blocks, by default what
    `latent_blocks_per_step` reads from the pool: 15 blocks of 128 keys
    over dots3's table of 133; only the blocks that hold context are
    fetched), the gathered form on the CPU."""
    q_i = pack_kv_rows(q_i[..., None, :])   # as wide as a (padded) key
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
    if not use_kernel:
        return index_scores_reference(q_i, w_i, index_pool, block_tables,
                                      ctx_lens, layer)
    return _index_walk_call(
        q_i, w_i, index_pool, block_tables, ctx_lens, layer, name=name,
        blocks_per_step=blocks_per_step,
        interpret=_interpret_kernels() if interpret is None else interpret)


@functools.partial(jax.jit, static_argnames=("blocks_per_step", "name",
                                             "interpret"))
def _index_walk_call(q_i, w_i, index_pool, block_tables, ctx_lens, layer, *,
                     blocks_per_step: Optional[int], name: str,
                     interpret: bool):
    """The `pallas_call` of `_index_scores_kernel` (jitted for what
    `_latent_walk_call` is); q_i [B, Hi, Di] as wide as a key."""
    b, hi, di = q_i.shape
    bs = index_pool.shape[2]
    mb = block_tables.shape[1]
    kb = min(blocks_per_step or latent_blocks_per_step(
        bs, di, index_pool.dtype.itemsize, mb), mb)
    # A run's scores are stored at a dynamic column of the lane's row:
    # whole lane widths of them (blocks past the table are never fetched).
    per = math.lcm(bs, 128) // bs
    kb = -(-kb // per) * per
    n = -(-mb // kb) * kb * bs          # whole runs

    def lane_spec(*shape):
        return pl.BlockSpec((None,) + shape, lambda i, *_: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # block tables, context lengths, layer
        grid=(b,),
        in_specs=[lane_spec(hi, di), lane_spec(hi, 1),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=lane_spec(1, n),
        scratch_shapes=[
            pltpu.VMEM((2, kb, bs, di), index_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),          # a buffer each
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        _index_scores_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
        interpret=interpret,
        # Lane by lane in order: a lane starts the next one's first fetch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name,
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q_i.astype(index_pool.dtype), w_i.astype(jnp.float32)[:, :, None],
      index_pool)
    return out[:, 0]


# Columns of a chunk the choice counts over.  A chunk's running counts ride
# the matrix unit in bfloat16, which holds every integer up to 256.
_SELECT_CHUNK = 128
_KEY_MIN = np.int32(-2 ** 31)


def _order_keys(scores):
    """int32 keys that order as the float32 scores do (-inf < NEG_INF <
    0.0): the bits are a sign and a magnitude, the key the same number in
    two's complement.  -0.0 and +0.0 get one key: a comparison of floats
    calls them equal, as the sort that chose until PR 48 did and the
    benchmark's reference does (an index score is exactly zero, of either
    sign, wherever every head's ReLU is).  No score's key is the least
    int32, which is what a position that is none gets."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, -(bits & np.int32(2 ** 31 - 1)), bits)


def _kth_key(keys, k: int):
    """The k-th largest key of each lane [B, ...] -> [B], exactly: the
    threshold's bits from the top, the sign first (the least key plus
    2**31 wraps round to 0); a bit stays where at least `k` keys are at or
    over the candidate.  32 passes of compare and count, no sort."""
    axes = tuple(range(1, keys.ndim))
    at = (slice(None),) + (None,) * len(axes)

    def bit(i, kth):
        trial = kth + jnp.left_shift(jnp.int32(1), 31 - i)
        enough = jnp.sum(keys >= trial[at], axes, dtype=jnp.int32) >= k
        return jnp.where(enough, trial, kth)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.full(keys.shape[:1], _KEY_MIN, jnp.int32))


def sparse_select_reference(scores, block_tables, *, block_size: int,
                            k: int, base=0, n: Optional[int] = None,
                            chunk: int = _SELECT_CHUNK):
    """The places in the pool's table of rows of the `k` positions of
    largest score of each lane, ascending by position (ground truth and
    the CPU's path; `sparse_select`).  Plain `jax.numpy`: compares, counts
    and two one-hot products, no sort, gather or scatter."""
    b, width = scores.shape
    n = width if n is None else n
    c = -(-width // chunk)
    # (positions from `n` on get the least key there is, under a true -inf's)
    keys = jnp.pad(jnp.where(jnp.arange(width) < n,
                             _order_keys(scores.astype(jnp.float32)),
                             _KEY_MIN), ((0, 0), (0, c * chunk - width)),
                   constant_values=_KEY_MIN).reshape(b, c, chunk)
    kth = _kth_key(keys, k)[:, None, None]
    above, tied = keys > kth, keys == kth
    upper = jnp.triu(jnp.ones((chunk, chunk), jnp.bfloat16))

    def running(mask):                  # inclusive, along a chunk
        return jnp.einsum("bcx,xy->bcy", mask.astype(jnp.bfloat16), upper,
                          preferred_element_type=jnp.float32)

    def before(count):                  # exclusive, over the chunks
        return jnp.cumsum(count, axis=1) - count

    # Of a chunk's ties, the first `take`: what the chunks before left of
    # the room the scores over the k-th leave.
    tied_run = running(tied)
    room = k - jnp.sum(above, (1, 2), dtype=jnp.float32)
    take = jnp.clip(room[:, None] - before(tied_run[..., -1]), 0, chunk)
    chosen_run = running(above) + jnp.minimum(tied_run, take[..., None])
    count = chosen_run[..., -1]
    start = before(count)[:, None, :]                       # [B, 1, C]
    slot = jnp.arange(k, dtype=jnp.float32)[None, :, None]  # [1, k, 1]
    # Slot j's chunk is the one whose chosen hold the j-th: one-hot
    # [B, k, C]; its row of running counts by a product (integers up to
    # `chunk`, exact in bfloat16); its column is how many of them are no
    # more than j's rank inside the chunk.
    hot = (start <= slot) & (slot < start + count[:, None, :])
    run_of = jnp.einsum("bjc,bcx->bjx", hot.astype(jnp.bfloat16),
                        chosen_run.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    rank = slot - jnp.sum(jnp.where(hot, start, 0), -1, keepdims=True)
    col = jnp.sum(run_of <= rank, -1, dtype=jnp.int32)
    pos = jnp.sum(jnp.where(hot, jnp.arange(c, dtype=jnp.int32) * chunk, 0),
                  -1) + col
    # The block's number in the lane's table: a masked sum over a one-hot
    # of blocks, in int32 (block numbers pass bfloat16's integers).
    mb = block_tables.shape[1]
    block = jnp.sum(jnp.where(
        (pos // block_size)[..., None] == jnp.arange(mb, dtype=jnp.int32),
        block_tables[:, None, :], 0), -1)
    return block * block_size + pos % block_size + base


# Lanes a grid step of the choice's kernel takes: their thresholds are found
# together, a sublane each (at 8 the 32 passes wait for each other: 0.170 ms
# for 64 lanes of 17,024 on a v5e where 16 take 0.158; PERF.md section 6,
# PR 48).
_SELECT_LANES = 16


def _select_kernel(s_ref, t_ref, o_ref, keys_ref, kth_ref, *, k: int,
                   n: int):
    """One grid step: `_SELECT_LANES` lanes' scores [G, C, 128] (a chunk
    of 128 positions a row) and their tables [G, C] -> the places of each
    lane's `k` best, ascending by position, [G, kp].  Every vector over
    chunks is a column, every vector over slots a row, so slots lie along
    the 128 lanes of a register and what comes out is stored dense."""
    g_lanes, c, ch = s_ref.shape
    kp = o_ref.shape[1]
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32

    def ones_where(mask, dtype=bf16):
        return jnp.where(mask, 1.0, 0.0).astype(dtype)

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(i32, shape, axis)

    pos = iota((c, ch), 0) * ch + iota((c, ch), 1)
    for g in range(g_lanes):
        keys_ref[g] = jnp.where(pos < n, _order_keys(s_ref[g]), _KEY_MIN)

    # The k-th key of every lane of the step at once: lane g's candidate
    # along sublane g, one count across the 128 columns a pass.
    sub = iota((g_lanes, ch), 0)

    def bit(i, kth):
        trial = kth + jnp.left_shift(jnp.int32(1), 31 - i)
        part = jnp.zeros((g_lanes, ch), i32)
        for g in range(g_lanes):
            hit = keys_ref[g] >= trial[g:g + 1, :]
            part = jnp.where(sub == g, jnp.sum(
                jnp.where(hit, 1, 0), axis=0, keepdims=True), part)
        enough = jnp.sum(part, axis=1, keepdims=True) >= k
        return jnp.where(enough, trial, kth)

    kth_ref[...] = jax.lax.fori_loop(
        0, 32, bit, jnp.full((g_lanes, ch), _KEY_MIN, i32))

    upper = ones_where(iota((ch, ch), 0) <= iota((ch, ch), 1))  # [x', x]
    lower = ones_where(iota((ch, ch), 1) <= iota((ch, ch), 0))  # [x, x']
    earlier = ones_where(iota((c, c), 1) < iota((c, c), 0))     # [c, c']
    ones = jnp.ones((8, ch), bf16)
    row = iota((8, c), 0)
    slot = iota((1, kp), 1).astype(f32)
    nt = (((1,), (1,)), ((), ()))

    def dot(a, b, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(a, b, dims, preferred_element_type=f32)

    def before(mask16):     # a chunk's count in the chunks before it [C, ch]
        return dot(earlier, mask16)

    def lane(g, carry):
        keys = keys_ref[g]
        kth = kth_ref[pl.ds(g, 1), :]
        above, tied = keys > kth, keys == kth
        tied16 = ones_where(tied)
        # Ties go to the lower position: a tie is chosen while the ties up
        # to it are no more than the room the scores over the k-th leave.
        room = k - jnp.sum(jnp.sum(ones_where(above, f32), axis=0,
                                   keepdims=True), axis=1, keepdims=True)
        tie_rank = dot(tied16, upper) + jnp.sum(
            before(tied16), axis=1, keepdims=True)
        chosen16 = ones_where(above | (tied & (tie_rank <= room)))
        prior = before(chosen16)
        start = jnp.sum(prior, axis=1, keepdims=True)               # [C, 1]
        end = start + jnp.sum(chosen16.astype(f32), axis=1, keepdims=True)
        # The chosen's running count along each chunk, a chunk a column,
        # and under it rows of what a slot needs of its chunk besides: the
        # table's block number and the chunk's start, each in digits of
        # base 256, which bfloat16 holds exactly.
        start_row = dot(ones, prior.astype(bf16), nt)               # [8, C]
        table = t_ref[pl.ds(g, 1), :]
        start_hi = jnp.floor(start_row * (1.0 / 256))
        extra = functools.reduce(
            lambda rest, at: jnp.where(row == at[0], at[1], rest),
            enumerate([table >> 16, (table >> 8) & 255, table & 255,
                       start_hi, start_row - start_hi * 256]),
            jnp.zeros((8, c), f32))
        of_chunk = jnp.concatenate(
            [dot(lower, chosen16, nt).astype(bf16), extra.astype(bf16)],
            axis=0)                                             # [ch + 8, C]
        of_slot = dot(of_chunk, ones_where((start <= slot) & (slot < end)))
        block, start_of = (
            functools.reduce(lambda hi, lo: hi * 256 + lo,
                             [of_slot[ch + i:ch + i + 1] for i in rows])
            for rows in ((0, 1, 2), (3, 4)))
        col = jnp.sum(jnp.where(of_slot[:ch] <= slot - start_of, 1, 0),
                      axis=0, keepdims=True)
        o_ref[pl.ds(g, 1), :] = block.astype(i32) * ch + col
        return carry

    jax.lax.fori_loop(0, g_lanes, lane, 0)


# (jitted so that a process traces the kernel once, not once a layer body of
# every step program: each trace and lowering is a quarter of a second of
# `setup_s`, PERF.md section 6, PR 48)
@functools.partial(jax.jit, static_argnames=(
    "block_size", "k", "n", "name", "use_kernel", "interpret"))
def sparse_select(scores, block_tables, *, block_size: int, k: int, base=0,
                  n: Optional[int] = None, name: str = "sparse_select",
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None):
    """The choice of indexed attention: for each lane the places, in the
    pool's table of rows [L * NB * BS, W], of the `k` positions of largest
    score among the first `n` of `scores` [B, >= n] float32 (by default
    all of them), ties to the lower position, as `jax.lax.top_k` chooses
    (but -0.0 a tie of +0.0, as a stable sort has it); int32 [B, k],
    ASCENDING BY POSITION, `base` (a layer's first row) added.  Nothing
    is sorted, gathered or scattered:

    1. the k-th largest score, exactly: the scores' bits as integers of
       the same order (`_order_keys`), the threshold built bit by bit from
       the top, a bit kept where at least `k` keys are at or over the
       candidate (`_kth_key`: 32 passes of compare and count);
    2. the chosen set: the keys over the k-th, and of those equal to it
       the first as many as there is room left;
    3. their places: with a lane's positions laid out [chunks, 128], slot
       j's chunk is the one whose share of the chosen holds the j-th (a
       count over the chunks' running totals: a one-hot), the chunk's row
       of running counts comes from a one-hot product on the matrix unit
       (integers up to 128, exact in bfloat16), the column is how many of
       that row are no more than j's rank inside the chunk, and the
       block's number in the lane's table rides the same product.

    On TPU the Pallas kernel `sparse_select` where a block is a chunk of
    128 (`_SELECT_LANES` lanes a grid step); anywhere else the same steps
    in `jax.numpy` (`sparse_select_reference`)."""
    b, width = scores.shape
    n = width if n is None else n
    ch = _SELECT_CHUNK
    c = -(-(-(-width // ch)) // 16) * 16        # whole bfloat16 tiles
    if use_kernel is None:
        use_kernel = not _interpret_kernels()
    # (the kernel's counts over chunks ride the matrix unit too: 256 chunks)
    if not use_kernel or block_size != ch or c > 256:
        return sparse_select_reference(scores, block_tables, k=k, n=n,
                                       block_size=block_size, base=base)
    if interpret is None:
        interpret = _interpret_kernels()
    g = min(_SELECT_LANES, -(-b // 8) * 8)
    bp, kp = -(-b // g) * g, -(-k // 128) * 128
    scores = jnp.pad(scores.astype(jnp.float32),
                     ((0, bp - b), (0, c * ch - width)))
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, bp - b), (0, c - block_tables.shape[1])))
    place = pl.pallas_call(
        functools.partial(_select_kernel, k=k, n=n),
        grid=(bp // g,),
        in_specs=[pl.BlockSpec((g, c, ch), lambda i: (i, 0, 0)),
                  pl.BlockSpec((g, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((g, kp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, kp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((g, c, ch), jnp.int32),
                        pltpu.VMEM((g, ch), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name=name,
    )(scores.reshape(bp, c, ch), tables)
    return place[:b, :k] + base


def sparse_latent_decode_attention(q, q_i, w_i, pool, index_pool,
                                   block_tables, ctx_lens, layer=0, *,
                                   v_width: int, scale: float, topk: int,
                                   names=("sparse_index_scores",
                                          "sparse_select",
                                          "sparse_latent_decode_attention")):
    """Single-query latent attention over the `topk` positions the indexer
    scores highest (all of a context no longer than that): scores over the
    index keys (`sparse_index_scores`), the exact choice of the best `topk`
    (`sparse_select`: a threshold and counts, no sort; ties go to the
    lower position, as `jax.lax.top_k`'s do), ONE gather of the chosen rows
    of the latent pool, and `latent_decode_attention` over those rows laid
    side by side: a pool of one layer, a lane's `topk` rows its blocks of
    128 one after the other, which the kernel walks in runs as it walks a
    context through a table (at 2,048 rows one run a lane).  The context's
    latent rows are never read.  q [B, H, W], q_i [B, Hi, Di], w_i
    [B, Hi]; returns [B, H, v_width].

    The choice hands over each row's place in the pool, not its position
    (a position looked up in the table afterwards is a gather of single
    numbers: 1.4 ms for 64 x 2,048 on a v5e), ascending by position: the
    softmax that follows is over a SET of rows, and where a context is
    shorter than `topk` its own positions come first, the filled ones
    behind them, which is where `latent_decode_attention` stops reading.
    The rows are taken from the pool as a table of rows [L * NB * BS, W]
    (2.1 ms, where the same rows by (layer, block, row) take 2.4:
    PERF.md section 6, PR 41)."""
    b = q.shape[0]
    n_layers, nb, bs, w = pool.shape
    n = block_tables.shape[1] * bs
    k = min(topk, n)
    scores = sparse_index_scores(q_i, w_i, index_pool, block_tables,
                                 ctx_lens, layer, name=names[0])
    place = sparse_select(
        scores, block_tables, block_size=bs, k=k, n=n, name=names[1],
        base=jnp.asarray(layer, jnp.int32) * (nb * bs))
    with jax.named_scope("sparse_gather"):
        rows = jnp.take(pool.reshape(n_layers * nb * bs, w), place, axis=0,
                        mode="clip")
    per = max(d for d in range(1, min(k, 128) + 1) if k % d == 0)
    return latent_decode_attention(
        q, rows.reshape(1, b * k // per, per, w),
        jnp.arange(b * k // per, dtype=jnp.int32).reshape(b, k // per),
        jnp.minimum(ctx_lens, k), 0, v_width=v_width, scale=scale,
        name=names[2])


def sparse_latent_attention(q, q_i, w_i, pool, index_pool, block_tables,
                            ctx_lens, q_positions, valid, layer=0, *,
                            v_width: int, scale: float, topk: int):
    """Dispatch indexed latent attention for a [B, T, ...] slice: the T=1
    step as it is, a longer slice's valid rows each as a lane of its own
    (`_rows_as_lanes`; kernels `sparse_index_chunk_scores`,
    `sparse_select_chunk`, `sparse_latent_chunk_attention`)."""
    def attend(**names):
        def fn(tables, ctx, q_rows, qi_rows, wi_rows):
            return sparse_latent_decode_attention(
                q_rows, qi_rows, wi_rows, pool, index_pool, tables, ctx,
                layer, v_width=v_width, scale=scale, topk=topk, **names)
        return fn
    if q.shape[1] == 1:
        return attend()(block_tables, ctx_lens, q[:, 0], q_i[:, 0],
                        w_i[:, 0])[:, None]
    return _rows_as_lanes(
        attend(names=("sparse_index_chunk_scores", "sparse_select_chunk",
                      "sparse_latent_chunk_attention")),
        block_tables, q_positions, valid, (q, q_i, w_i),
        jnp.zeros(q.shape[:-1] + (v_width,), q.dtype))
