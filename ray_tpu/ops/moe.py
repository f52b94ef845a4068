"""Sparse-expert feed-forward: dropless dispatch and a grouped matmul.

A mixture-of-experts layer sends every token to its k experts.  Dropless
means no capacity: the [T * k] (token, expert) assignments are ordered by
expert, each expert multiplies exactly the rows that chose it, and the
results go back to their tokens.  The multiply is one Pallas kernel over
rows grouped by expert (`grouped_matmul`, in a device trace
`moe_grouped_matmul`): it walks the (row tile, expert) pairs that hold rows,
so an expert nobody chose is never read, and it reads an expert's weights
from the stacked `[layers, experts, K, N]` array where they are (the layer
is a scalar the kernel's index maps take: a per-layer slice handed to a
custom call would be a copy of the layer's experts every step).

No token is dropped whatever the routing; padding tokens (`valid` false) go
behind the last expert's rows and cost nothing, and so do assignments to
experts that live elsewhere, where the experts held are a share of those the
router chooses among (`expert_ffn(first_held=...)`: one chip of an
expert-parallel deployment, without its exchange).

Both have a backward pass.  Of `grouped_matmul`, dx is the same kernel in
its other form over dy (it contracts the stored matrix's other dimension:
no transposed copy of a layer's experts), and dw is a kernel of its own
(`moe_grouped_matmul_dw` in a trace): over the same (row tile, expert)
pairs, the rows of a tile that are an expert's contracted into its
[K, N], in float32.  Of `expert_ffn`, whose dispatch is a sort, the
backward is the same sort read the other way: gathers and sums and no
scatter.  Where the experts held are a share, the sorted rows of the
experts held come first and the buffers behind the sort (rows in, hidden,
rows out) may be held to a bound (`expert_ffn(rows=)`): what routing sends
elsewhere then costs neither multiply time nor activations.

The way back, the experts' rows to their tokens, has two forms of one sum
(`expert_ffn(token_tile=)`).  For few tokens a gather of every assignment's
row, `[T x k, D]`, masked and summed over k (`_gathered`).  For whole
sequences a kernel over the sort's runs (`moe_combine` in a trace): the
sort is stable, so the rows of one expert that belong to a tile of
consecutive tokens are one run of the sorted order, and a 0/1 matrix takes
them to their tokens through the matrix units, exactly; it reads the held
experts' rows alone, so a page of sorted rows behind the first costs its
own rows and no longer a gather over all T x k assignments.  The backward's
dx is the same kernel with weights of one.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _interpret_kernels


def _grouped_matmul_kernel(item_group, item_tile, starts, ends, n_items,
                           layer, x_ref, w_ref, o_ref, *, block_m: int,
                           transposed: bool = False):
    """Grid (N tiles, work items).  Item i is (expert g, row tile t): the
    rows of tile t that belong to g are x[t] @ w[g] (`transposed`: w[g] is
    held [N, K] and both contract their last dimension); the other rows of
    the tile keep what earlier items wrote (zero on the tile's first
    visit)."""
    del layer                        # the index maps' operand
    i = pl.program_id(1)

    @pl.when(i < n_items[0])
    def _item():
        g, t = item_group[i], item_tile[i]
        if transposed:
            acc = jax.lax.dot_general(
                x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            acc = jnp.dot(x_ref[...], w_ref[...],
                          preferred_element_type=jnp.float32)
        rows = t * block_m + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (rows >= starts[g]) & (rows < ends[g])
        acc = acc.astype(o_ref.dtype)
        first = (i == 0) | (item_tile[jnp.maximum(i - 1, 0)] != t)

        @pl.when(first)
        def _():
            o_ref[...] = jnp.where(mine, acc, jnp.zeros_like(acc))

        @pl.when(jnp.logical_not(first))
        def _():
            o_ref[...] = jnp.where(mine, acc, o_ref[...])


def _work_items(group_sizes, block_m: int, n_tiles: int,
                every_group: bool = False):
    """The (expert, row tile) pairs that hold rows, in row order, padded to
    the static bound n_tiles + experts - 1 by repeating the last pair (the
    same blocks again: nothing is fetched for a padding item).  With
    `every_group` an expert with no rows has one pair too, with the last
    tile that holds rows (none of them its own): the kernel that writes a
    result an expert (`_grouped_dw_kernel`) then writes its zeros."""
    n_groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first_tile = starts // block_m
    tiles = jnp.where(group_sizes > 0,
                      (ends - 1) // block_m - first_tile + 1, 0)
    if every_group:
        tiles = jnp.maximum(tiles, 1)
        first_tile = jnp.minimum(
            first_tile, jnp.maximum(ends[-1] - 1, 0) // block_m)
    item_end = jnp.cumsum(tiles)
    n_items = item_end[-1]
    i = jnp.minimum(jnp.arange(n_tiles + n_groups - 1),
                    jnp.maximum(n_items - 1, 0))
    group = jnp.minimum(jnp.searchsorted(item_end, i, side="right"),
                        n_groups - 1).astype(jnp.int32)
    tile = first_tile[group] + i - (item_end - tiles)[group]
    tile = jnp.clip(tile, 0, n_tiles - 1).astype(jnp.int32)
    return group, tile, starts, ends, n_items.reshape(1)


def _grouped_dw_kernel(item_group, item_tile, starts, ends, n_items,
                       a_ref, b_ref, o_ref, acc_ref, *, block_m: int):
    """Grid (P tiles, Q tiles, work items).  Item i is (expert g, row tile
    t): the rows of tile t that are g's add a[t]^T b[t] to g's [P, Q]
    (float32 in `acc_ref`), which is written with g's last item.  The rows
    of the tile that are not g's are selected to zero on both sides, not
    multiplied: whatever they hold must not reach a sum."""
    i = pl.program_id(2)

    @pl.when(i < n_items[0])
    def _item():
        g, t = item_group[i], item_tile[i]

        def mine(ref):
            rows = t * block_m + jax.lax.broadcasted_iota(
                jnp.int32, ref.shape, 0)
            return jnp.where((rows >= starts[g]) & (rows < ends[g]),
                             ref[...], jnp.zeros(ref.shape, ref.dtype))

        part = jax.lax.dot_general(mine(a_ref), mine(b_ref),
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        first = (i == 0) | (item_group[jnp.maximum(i - 1, 0)] != g)
        last = (i == n_items[0] - 1) | (item_group[i + 1] != g)

        @pl.when(first)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += part

        @pl.when(last)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tile_of(n: int, most: int) -> int:
    """The widest tile of at most `most` columns that divides n in
    multiples of the lane width; an n that is no such multiple is one
    tile."""
    if n % 128:
        return n
    tile = min(most, n) // 128 * 128
    while n % tile:
        tile -= 128
    return tile


def _rows_padded(x, block_m: int):
    pad = -x.shape[0] % block_m
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _grouped_dw(a, b, group_sizes, dtype, block_m: int, interpret: bool):
    """a [M, P], b [M, Q], rows ordered by group -> [G, P, Q] in `dtype`:
    for each group the sum over its rows r of a[r]^T b[r], accumulated in
    float32; zeros for a group without rows; rows behind the last group are
    not read."""
    a, b = _rows_padded(a, block_m), _rows_padded(b, block_m)
    (m, p), q = a.shape, b.shape[1]
    g = group_sizes.shape[0]
    n_tiles = m // block_m
    # [1152, 896] of float32 is 4 MB in the scratch and twice in the
    # result's two buffers; a tile of 512 rows against it is 800 flops a
    # byte read, over the chip's 240.
    block_p, block_q = _tile_of(p, 1152), _tile_of(q, 1024)
    items = _work_items(group_sizes.astype(jnp.int32), block_m, n_tiles,
                        every_group=True)
    # (one pair more than the bound: the kernel looks one item ahead)
    items = (jnp.pad(items[0], (0, 1), constant_values=-1),) + items[1:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,       # item group/tile, starts, ends, count
        grid=(p // block_p, q // block_q, n_tiles + g - 1),
        in_specs=[
            pl.BlockSpec((block_m, block_p),
                         lambda jp, jq, i, ig, it, s, e, c: (it[i], jp)),
            pl.BlockSpec((block_m, block_q),
                         lambda jp, jq, i, ig, it, s, e, c: (it[i], jq)),
        ],
        out_specs=pl.BlockSpec(
            (None, block_p, block_q),
            lambda jp, jq, i, ig, it, s, e, c: (ig[i], jp, jq)),
        scratch_shapes=[pltpu.VMEM((block_p, block_q), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_grouped_dw_kernel, block_m=block_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, p, q), dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="moe_grouped_matmul_dw",
    )(*items, a, b)


def _grouped_product(x, w, group_sizes, layer, block_m: int, block_n: int,
                     interpret: bool, transposed: bool):
    """`grouped_matmul` of w [L, G, K, N] in x's dtype."""
    m, k = x.shape
    g, n = w.shape[1], w.shape[2 if transposed else 3]
    # A decode step is bound by reading each hit expert's [K, N] once, so
    # the tile is the whole matrix where it fits (one 4 MB DMA for OLMoE's
    # experts) and the row tile is tall: on a v5e 128 x 2048 read 86% of
    # the chip's bandwidth and 16 x 512 80% (PERF.md 6, PR 27).
    # Wider experts (K 7168) take the widest tile under 8 MB that divides
    # N, so that two buffers of it fit the kernel's fast memory.
    # An N that is no multiple of the lane width (1856 = 14.5 x 128) is
    # one whole-N tile: the result's block has a last dimension that is a
    # multiple of 128 or the array's own (10 MB a buffer at K 2688: two
    # fit).
    if n % 128:
        block_n = n
    else:
        block_n = min(block_n, n, max(128, (4 * 2 ** 20 // k) // 128 * 128))
        while block_n > 128 and n % block_n:
            block_n -= 128
    x = _rows_padded(x, block_m)
    n_tiles = x.shape[0] // block_m
    items = _work_items(group_sizes, block_m, n_tiles)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,   # item group/tile, starts, ends, count, layer
        grid=(n // block_n, n_tiles + g - 1),
        in_specs=[
            pl.BlockSpec((block_m, k),
                         lambda j, i, ig, it, s, e, c, ly: (it[i], 0)),
            pl.BlockSpec((None, None, block_n, k),
                         lambda j, i, ig, it, s, e, c, ly:
                         (ly[0], ig[i], j, 0)) if transposed else
            pl.BlockSpec((None, None, k, block_n),
                         lambda j, i, ig, it, s, e, c, ly:
                         (ly[0], ig[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, i, ig, it, s, e, c, ly: (it[i], j)),
    )
    out = pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, block_m=block_m,
                          transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], n), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        # The instruction's name in the HLO and so in a device trace.
        name="moe_grouped_matmul",
    )(*items, layer, x, w)
    return out[:m] if x.shape[0] != m else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _grouped(x, w, group_sizes, layer, block_m, block_n, interpret,
             transposed):
    return _grouped_product(x, w.astype(x.dtype), group_sizes, layer,
                            block_m, block_n, interpret, transposed)


def _grouped_fwd(x, w, group_sizes, layer, block_m, block_n, interpret,
                 transposed):
    return (_grouped(x, w, group_sizes, layer, block_m, block_n, interpret,
                     transposed), (x, w, group_sizes, layer))


def _grouped_bwd(block_m, block_n, interpret, transposed, res, dy):
    """dx is the product's other form over dy, reading w where it is; dw
    the layer's [G, K, N] (`transposed`: [G, N, K]), in w's own dtype from
    float32 sums: a float32 master that the forward read in bf16 gets its
    gradient unrounded.  A stacked w's is zeros but for the layer."""
    x, w, group_sizes, layer = res
    dx = _grouped_product(dy, w.astype(dy.dtype), group_sizes, layer,
                          block_m, block_n, interpret, not transposed)
    a, b = (dy, x) if transposed else (x, dy)
    dw = _grouped_dw(a, b, group_sizes, w.dtype, block_m, interpret)
    if w.shape[0] > 1:
        dw = jnp.zeros_like(w).at[layer[0]].set(dw)
    else:
        dw = dw[None]
    return dx, dw, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret", "transposed"))
def grouped_matmul(x, w, group_sizes, layer=0, *, block_m: int = 128,
                   block_n: int = 2048, interpret: Optional[bool] = None,
                   transposed: bool = False):
    """x [M, K], its rows ordered by group; w [L, G, K, N] (or [G, K, N]);
    group_sizes [G], summing to M or less.  Row r of the result is
    x[r] @ w[layer, g] for the group g that holds r.  Rows behind the last
    group are unspecified (the caller masks them), and so are theirs of
    the gradient in x; a w of another dtype is read in x's (a float32
    master under bf16 rows: its gradient comes back in float32).

    `transposed`: w is [L, G, N, K], a Linear's [out, in] as published.
    That is how a matrix whose N is no multiple of the lane width has to be
    held: as [K, N] the device lays it out with K minor (the layout that
    pads nothing) and a kernel that wants it row-major is handed a copy of
    every layer's experts every step."""
    if interpret is None:
        interpret = _interpret_kernels()
    return _grouped(x, w if w.ndim == 4 else w[None],
                    group_sizes.astype(jnp.int32),
                    jnp.asarray(layer, jnp.int32).reshape(1), block_m,
                    block_n, interpret, transposed)


# `moe_combine`'s block of sorted rows: the matrix units' depth (of 64, 128
# and 256, 128 was fastest at every token tile: PERF.md 6, PR 62).
COMBINE_ROWS = 128


def _combine_kernel(item_tile, item_block, item_group, item_lo, item_hi,
                    n_items, page_block, *refs, tile: int, block_rows: int):
    """Grid (work items,).  Item n is (token tile i, block j of the page's
    sorted rows, the rows lo to hi of held expert g's run of the tile's
    tokens): a 0/1 matrix [tile, block rows] with a one where a row is a
    token's takes each token's row out of the block through the matrix
    units (one row a token at most, since an expert holds a token once:
    the float32 product is that row, exactly), the token's float32 weight
    for g scales it, and the tile's float32 sum, kept in `acc_ref` over the
    tile's items, is written with the tile's last.  The block's other rows
    meet zeros of the matrix, so they must be finite (`_zeros_behind` sees
    to those behind the held experts' last)."""
    del page_block                   # the index maps' operand
    if len(refs) == 6:
        tok_ref, ids_ref, w_ref, rows_ref, o_ref, acc_ref = refs
    else:
        (tok_ref, rows_ref, o_ref, acc_ref), ids_ref = refs, None
    n = pl.program_id(0)

    @pl.when(n < n_items[0])
    def _item():
        i, j = item_tile[n], item_block[n]

        @pl.when((n == 0) | (item_tile[jnp.maximum(n - 1, 0)] != i))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

        shape = (tile, block_rows)
        col = j * block_rows + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        tok = i * tile + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        hit = ((tok_ref[...] == tok) & (col >= item_lo[n])
               & (col < item_hi[n]))
        part = jnp.dot(hit.astype(rows_ref.dtype), rows_ref[...],
                       preferred_element_type=jnp.float32,
                       precision=None if rows_ref.dtype == jnp.bfloat16
                       else jax.lax.Precision.HIGHEST)
        if ids_ref is not None:
            part = part * jnp.sum(
                jnp.where(ids_ref[...] == item_group[n], w_ref[...], 0.0),
                axis=1, keepdims=True)
        acc_ref[...] += part

        @pl.when((n == n_items[0] - 1) | (item_tile[n + 1] != i))
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tile_runs(sort: "_Dispatch", k: int, tile: int):
    """(first, rows) [token tiles, E]: the sorted row at which the run of
    each (tile of `tile` consecutive tokens, held expert) starts and how
    many rows it has.  The sort is stable, so inside an expert's rows the
    tokens ascend and a tile's are one run."""
    e = sort.load.shape[0]
    flat = jnp.pad(sort.flat, (0, -(sort.flat.shape[0] // k) % tile * k),
                   constant_values=e)
    rows = jnp.sum(flat.reshape(-1, tile * k, 1) == jnp.arange(e), axis=1,
                   dtype=jnp.int32)
    before = jnp.cumsum(rows, axis=0) - rows
    return (jnp.cumsum(sort.load) - sort.load)[None, :] + before, rows


def _combine_items(first, rows, lo, r: int, block_rows: int):
    """The (token tile, block of sorted rows) pairs that a run touches in
    the page of `r` sorted rows from `lo`, tile by tile and inside a tile
    in row order, with each one's expert and run (first row, behind its
    last: in the page's own count); padded to the static bound
    r / block_rows + tiles x experts by repeating the last (the same
    blocks again: nothing is fetched for a padding item).  A tile's first
    expert has an item whatever it holds, so that a tile no run touches is
    written too (its zeros)."""
    n_tiles, e = rows.shape
    n_blocks = r // block_rows
    a = jnp.clip(first - lo, 0, r).reshape(-1)
    b = jnp.clip(first + rows - lo, 0, r).reshape(-1)
    first_block = jnp.minimum(a // block_rows, n_blocks - 1)
    blocks = jnp.where(b > a, (b - 1) // block_rows - first_block + 1, 0)
    blocks = jnp.maximum(blocks, jnp.arange(n_tiles * e) % e == 0)
    item_end = jnp.cumsum(blocks)
    n_items = item_end[-1]
    n = jnp.minimum(jnp.arange(n_blocks + n_tiles * e), n_items - 1)
    pair = jnp.minimum(jnp.searchsorted(item_end, n, side="right"),
                       n_tiles * e - 1)
    block = first_block[pair] + n - (item_end - blocks)[pair]
    # (one tile more than the bound: the kernel looks one item ahead)
    return tuple(v.astype(jnp.int32) for v in (
        jnp.pad(pair // e, (0, 1), constant_values=-1), block, pair % e,
        a[pair], b[pair], n_items.reshape(1)))


def _zeros_behind(rows, sort: "_Dispatch", p=0,
                  block_rows: int = COMBINE_ROWS):
    """Page p's sorted rows [r, D] with zeros from the held experts' last
    row to the end of its block of `block_rows`: what `moe_combine` may be
    given (the block's rows meet zeros of its 0/1 matrix, and 0 x nan is
    nan; blocks behind that one are in no work item).  One block is
    rewritten where it lies."""
    r = rows.shape[0]
    end = jnp.clip(jnp.sum(sort.load) - p * r, 0, r)
    at = jnp.clip(end // block_rows * block_rows, 0,
                  max(r - block_rows, 0))
    last = jax.lax.dynamic_slice_in_dim(rows, at, min(block_rows, r))
    last = jnp.where(at + jnp.arange(last.shape[0])[:, None] < end, last,
                     jnp.zeros_like(last))
    return jax.lax.dynamic_update_slice_in_dim(rows, last, at, 0)


@functools.partial(jax.jit, static_argnames=("k", "tile", "block_rows",
                                             "interpret"))
def moe_combine(rows, sort: "_Dispatch", k: int, weights=None, *, tile: int,
                p=0, block_rows: int = COMBINE_ROWS,
                interpret: Optional[bool] = None):
    """The experts' sorted rows back at their tokens.  rows [r, D] are
    sorted rows p r to (p + 1) r of `sort` (what the held experts made of
    them; behind the held experts' rows finite to their block's end,
    `_zeros_behind`, then anything; a page that is not the whole sort is
    whole blocks, r a multiple of `block_rows`); weights
    [T, k] float32 or None for ones.  out[token] = the sum over the token's
    assignments with a held expert and a row in the page of weight x row,
    float32 products summed in float32 in the experts' order, [T, D] in
    rows' dtype: `_gathered` without its [T x k, D] gather (`moe_combine`
    in a device trace).  A token's k experts are distinct, as a top-k's
    are.  `tile`: the tokens a work item takes."""
    if interpret is None:
        interpret = _interpret_kernels()
    r, d = rows.shape
    t = sort.flat.shape[0] // k
    assert r == t * k or r % block_rows == 0, (r, block_rows)
    rows = _rows_padded(rows, block_rows)
    tok = _rows_padded((sort.order // k).astype(jnp.int32)[:, None],
                       block_rows).reshape(-1, 1, block_rows)
    last_block = tok.shape[0] - 1
    items = _combine_items(*_tile_runs(sort, k, tile), p * r, rows.shape[0],
                           block_rows)
    page_block = jnp.asarray(p * (r // block_rows), jnp.int32).reshape(1)
    at_tile = pl.BlockSpec((tile, k), lambda n, it, *_: (it[n], 0))
    operands = [] if weights is None else [
        _rows_padded(sort.flat.reshape(t, k), tile),
        _rows_padded(weights.astype(jnp.float32), tile)]
    n_tiles = -(-t // tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # item tile/block/expert, its run's ends, the count, the page
        num_scalar_prefetch=7,
        grid=(items[1].shape[0],),
        in_specs=[
            pl.BlockSpec((None, 1, block_rows),
                         lambda n, it, ib, ig, lo, hi, c, pb:
                         (jnp.minimum(pb[0] + ib[n], last_block), 0, 0)),
            *[at_tile] * len(operands),
            pl.BlockSpec((block_rows, d), lambda n, it, ib, *_: (ib[n], 0))],
        out_specs=pl.BlockSpec((tile, d), lambda n, it, *_: (it[n], 0)),
        scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_combine_kernel, tile=tile, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, d), rows.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="moe_combine",
    )(*items, page_block, tok, *operands, rows)
    return out[:t] if out.shape[0] != t else out


class _Dispatch(NamedTuple):
    """The sort behind `expert_ffn`: `flat` [T * k] each assignment's
    expert among those held (`experts`: none of them), `order` the
    assignment of each sorted row, `rank` the sorted row of each
    assignment, `load` [E] the assignments each held expert took."""
    flat: jax.Array
    order: jax.Array
    rank: jax.Array
    load: jax.Array


def _dispatch(expert_ids, e: int, valid, first_held) -> _Dispatch:
    k = expert_ids.shape[1]
    flat = expert_ids.reshape(-1).astype(jnp.int32)            # [T * k]
    if first_held is not None:
        flat = flat - first_held
        flat = jnp.where((flat >= 0) & (flat < e), flat, e)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, e)   # behind every group
    order = jnp.argsort(flat, stable=True)     # sorted row -> assignment
    load = jnp.sum(flat[:, None] == jnp.arange(e)[None, :], axis=0,
                   dtype=jnp.int32)
    rank = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    return _Dispatch(flat, order, rank, load)


def _act(g, u):
    """The experts' hidden rows of their products' float32 values: SwiGLU,
    or with no gate a squared ReLU."""
    if g is None:
        return jnp.square(jax.nn.relu(u))
    return jax.nn.silu(g) * u


def _page(p, r: int, sort: _Dispatch, masks):
    """Sorted rows p r to (p + 1) r as `_experts_at` reads them: (the
    assignment of each of the page's rows, each held expert's rows among
    them, for each assignment its row in the page, and whether it has one:
    None where the page is the whole sort and nothing is masked)."""
    n = sort.order.shape[0]
    if r == n:
        return sort.order, sort.load, sort.rank, masks
    lo = p * r
    order = jnp.take(sort.order, lo + jnp.arange(r), mode="clip")
    ends = jnp.cumsum(sort.load)
    load = jnp.clip(ends, lo, lo + r) - jnp.clip(ends - sort.load, lo, lo + r)
    here = (sort.rank >= lo) & (sort.rank < lo + r)
    if masks is not None:
        here = here & masks
    return order, load, jnp.clip(sort.rank - lo, 0, r - 1), here


def _gathered(rows, rank, here, k: int, weights=None):
    """`moe_combine` by a gather of EVERY assignment's row, [T x k, D]: its
    form where the tokens fill no tile."""
    t = rank.shape[0] // k
    y = rows[rank].reshape(t, k, -1).astype(jnp.float32)
    if here is not None:
        y = jnp.where(here.reshape(t, k, 1), y, 0.0)   # rows no expert wrote
    if weights is None:
        return jnp.sum(y, 1).astype(rows.dtype)
    out = jnp.einsum("tk,tkd->td", weights.astype(jnp.float32), y)
    return out.astype(rows.dtype)


def _experts_at(p, r: int, x, weights, w_gate, w_up, w_down, layer, sort,
                masks, static):
    """`expert_ffn` over page `p` of `r` sorted rows.  Returns (y [T, D],
    what the backward reads again: the products g and u [r, F] (g None
    without a gate) and the experts' results ys [r, D])."""
    k, up_transposed, block_m, tile = static
    t, d = x.shape
    order, load, rank, here = _page(p, r, sort, masks)
    mm = functools.partial(grouped_matmul, group_sizes=load, layer=layer,
                           block_m=block_m)
    xs = x[order // k]                                             # [r, D]
    g = None if w_gate is None else mm(xs, w_gate)
    u = mm(xs, w_up, transposed=up_transposed)
    hidden = _act(g, u)
    ys = mm(hidden, w_down)                                        # [r, D]
    if tile:
        ys = _zeros_behind(ys, sort, p)
        return moe_combine(ys, sort, k, weights, tile=tile,
                           p=jnp.int32(p)), (g, u, ys)
    return _gathered(ys, rank, here, k, weights), (g, u, ys)


def _experts_bwd_at(p, r: int, x, weights, w_gate, w_up, w_down, layer, sort,
                    masks, static, saved, dout):
    """The cotangents of x, weights and the three matrices from page `p` of
    `r` sorted rows.  The sort is a permutation, so what the forward
    gathered by `order` comes back gathered by `rank` and the other way
    round: a token's dx is the sum of its k sorted rows' (those of experts
    held elsewhere masked: no kernel wrote them), a sorted row's dy is its
    token's times its weight.  No scatter."""
    k, up_transposed, block_m, tile = static
    t, d = x.shape
    interpret = _interpret_kernels()
    g, u, ys = saved
    order, load, rank, here = _page(p, r, sort, masks)
    tok = order // k
    xs = x[tok]

    def back(rows_in, w, dy, transposed=False):
        """(the product's dx, its dw in w's own shape): `_grouped_bwd`."""
        dx, dw, _, _ = _grouped_bwd(
            block_m, 2048, interpret, transposed,
            (rows_in, w if w.ndim == 4 else w[None], load, layer), dy)
        return dx, dw if w.ndim == 4 else dw[0]

    dtok = dout[tok].astype(jnp.float32)     # [r, D]: each row's token's
    # a sorted row's share of the router's weight: <dout of its token, ys>
    dweights = jnp.sum(dtok * ys.astype(jnp.float32), -1)[rank]
    dys = (weights.astype(jnp.float32).reshape(-1)[order][:, None]
           * dtok).astype(x.dtype)                                 # [r, D]
    dhidden, dw_down = back(_act(g, u), w_down, dys)
    dhidden, u32 = dhidden.astype(jnp.float32), u.astype(jnp.float32)
    if g is None:
        du = (dhidden * 2.0 * jax.nn.relu(u32)).astype(x.dtype)
        dw_gate = None
    else:
        g32 = g.astype(jnp.float32)
        sig = jax.nn.sigmoid(g32)
        du = (dhidden * g32 * sig).astype(x.dtype)
        dg = (dhidden * u32 * sig * (1.0 + g32 * (1.0 - sig))).astype(x.dtype)
    dxs, dw_up = back(xs, w_up, du, up_transposed)
    if g is not None:
        more, dw_gate = back(xs, w_gate, dg)
        dxs = dxs + more
    if here is not None:
        dweights = jnp.where(here, dweights, 0.0)
    if tile:
        dx = moe_combine(_zeros_behind(dxs, sort, p), sort, k, tile=tile,
                         p=jnp.int32(p))
    else:
        dx = _gathered(dxs, rank, here, k)
    return (dx, dweights.reshape(t, k).astype(weights.dtype), dw_gate,
            dw_up, dw_down)


def _pages(sort: _Dispatch, rows: int):
    """How many pages of `rows` sorted rows the held experts' rows fill."""
    return (jnp.sum(sort.load) + rows - 1) // rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _experts(x, weights, w_gate, w_up, w_down, layer, sort, masks, rows,
             static):
    return _experts_fwd(x, weights, w_gate, w_up, w_down, layer, sort,
                        masks, rows, static)[0]


def _experts_fwd(x, weights, w_gate, w_up, w_down, layer, sort, masks, rows,
                 static):
    """The first page, whose products are kept for the backward, and behind
    it as many more as the held experts' rows fill: none where routing
    stays inside the bound."""
    args = (x, weights, w_gate, w_up, w_down, layer, sort, masks, static)
    y, saved = _experts_at(0, rows, *args)
    # (by name, so that a `jax.checkpoint` around the layer may keep them:
    # made again they are three grouped products and two gathers)
    saved = jax.tree.map(lambda a: checkpoint_name(a, "expert_rows"), saved)
    if rows < sort.order.shape[0]:
        # (the later pages' sum starts from zeros, not from y: where nothing
        # reads y, as in remat's second forward, the first page's gather
        # goes with it, and a loop that y ran through would keep it)
        y = y + jax.lax.fori_loop(
            1, _pages(sort, rows),
            lambda p, more: more + _experts_at(p, rows, *args)[0],
            jnp.zeros_like(y))
    return y, (*args[:-1], saved)


def _experts_bwd(rows, static, res, dout):
    *args, saved = res
    grads = _experts_bwd_at(0, rows, *args, static, saved, dout)
    if rows < args[6].order.shape[0]:
        def more(p, grads):     # what did not fit was not kept: once more
            saved = _experts_at(p, rows, *args, static)[1]
            return jax.tree.map(jnp.add, grads, _experts_bwd_at(
                p, rows, *args, static, saved, dout))
        grads = jax.lax.fori_loop(1, _pages(args[6], rows), more, grads)
    return (*grads, None, None, None)


_experts.defvjp(_experts_fwd, _experts_bwd)


def expert_ffn(x, expert_ids, expert_weights, w_gate, w_up, w_down,
               layer=0, valid=None, first_held=None, up_transposed=False,
               rows=None, block_m: int = 128,
               token_tile: Optional[int] = None):
    """Dropless experts: SwiGLU over three matrices
    (`w_down (silu(w_gate x) * (w_up x))`), or with `w_gate` None a
    squared ReLU over two (`w_down relu(w_up x)^2`).  x [T, D]; expert_ids
    / expert_weights [T, k] (each token's chosen experts and what each
    counts for); weights [L, E, D, F] / [L, E, F, D] (or without L),
    multiplied as stored, or in x's dtype where theirs is another
    (`up_transposed`: `w_up` is held [L, E, F, D],
    `grouped_matmul(transposed=True)`); `valid` [T] masks padding tokens,
    which reach no expert.

    With `first_held` the E experts held here are a share of those the
    ids run over: first_held to first_held + E.  An assignment to any
    other goes behind every group, as a padding token's do, so the grouped
    multiply sees only held experts and that assignment adds nothing.

    `rows`: the sorted rows that are worked on at a time (None: all
    T x k).  The rows of the experts held are the first of the sort, so
    where they are a share, buffers of the share's expected rows and some
    room hold every one of them, and the rows in, the hidden rows and the
    rows out are that long, forward and backward.  It bounds memory, never
    routing: the sort is walked a page of `rows` at a time for as many
    pages as the held experts' rows fill (a loop whose count is read on
    the chip; its backward makes a later page's products again, since only
    the first page's were kept), so however many assignments the held
    experts take, each is multiplied: none can be dropped, whatever the
    router does.  `block_m`: the row tile of the grouped products.

    `token_tile`: the experts' rows go back to their tokens through
    `moe_combine`, this many tokens a work item (None: through the gather
    of every assignment's row, `_gathered`; the same float32 sum either
    way, a token's k terms in the experts' order or the slots').  A page is
    then whole blocks of `COMBINE_ROWS` sorted rows (`rows` rounded up).

    Differentiable in x, expert_weights and the three matrices
    (`_experts_bwd_at`).  Returns (y [T, D], load [E] int32: the
    assignments each expert took)."""
    t, k = expert_ids.shape
    if token_tile and rows is not None:
        rows = -(-rows // COMBINE_ROWS) * COMBINE_ROWS
    sort = _dispatch(expert_ids, w_down.shape[-3], valid, first_held)
    if first_held is not None:
        masks = sort.flat < w_down.shape[-3]
    elif valid is not None:
        masks = jnp.repeat(valid, k)
    else:
        masks = None
    y = _experts(x, expert_weights, w_gate, w_up, w_down,
                 jnp.asarray(layer, jnp.int32).reshape(1), sort, masks,
                 t * k if rows is None else min(rows, t * k),
                 (k, up_transposed, block_m, token_tile))
    return y, sort.load
