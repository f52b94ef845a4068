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
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
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


def _work_items(group_sizes, block_m: int, n_tiles: int):
    """The (expert, row tile) pairs that hold rows, in row order, padded to
    the static bound n_tiles + experts - 1 by repeating the last pair (the
    same blocks again: nothing is fetched for a padding item)."""
    n_groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first_tile = starts // block_m
    tiles = jnp.where(group_sizes > 0,
                      (ends - 1) // block_m - first_tile + 1, 0)
    item_end = jnp.cumsum(tiles)
    n_items = item_end[-1]
    i = jnp.minimum(jnp.arange(n_tiles + n_groups - 1),
                    jnp.maximum(n_items - 1, 0))
    group = jnp.minimum(jnp.searchsorted(item_end, i, side="right"),
                        n_groups - 1).astype(jnp.int32)
    tile = first_tile[group] + i - (item_end - tiles)[group]
    tile = jnp.clip(tile, 0, n_tiles - 1).astype(jnp.int32)
    return group, tile, starts, ends, n_items.reshape(1)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret", "transposed"))
def grouped_matmul(x, w, group_sizes, layer=0, *, block_m: int = 128,
                   block_n: int = 2048, interpret: Optional[bool] = None,
                   transposed: bool = False):
    """x [M, K], its rows ordered by group; w [L, G, K, N] (or [G, K, N]);
    group_sizes [G], summing to M or less.  Row r of the result is
    x[r] @ w[layer, g] for the group g that holds r.  Rows behind the last
    group are unspecified (the caller masks them).

    `transposed`: w is [L, G, N, K], a Linear's [out, in] as published.
    That is how a matrix whose N is no multiple of the lane width has to be
    held: as [K, N] the device lays it out with K minor (the layout that
    pads nothing) and a kernel that wants it row-major is handed a copy of
    every layer's experts every step."""
    if w.ndim == 3:
        w = w[None]
    m, k = x.shape
    g, n = w.shape[1], w.shape[2 if transposed else 3]
    if interpret is None:
        interpret = _interpret_kernels()
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
    pad = -m % block_m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n_tiles = (m + pad) // block_m
    group_sizes = group_sizes.astype(jnp.int32)
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
        out_shape=jax.ShapeDtypeStruct((m + pad, n), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        # The instruction's name in the HLO and so in a device trace.
        name="moe_grouped_matmul",
    )(*items, jnp.asarray(layer, jnp.int32).reshape(1), x, w)
    return out[:m] if pad else out


def expert_ffn(x, expert_ids, expert_weights, w_gate, w_up, w_down,
               layer=0, valid=None, first_held=None, up_transposed=False):
    """Dropless experts: SwiGLU over three matrices
    (`w_down (silu(w_gate x) * (w_up x))`), or with `w_gate` None a
    squared ReLU over two (`w_down relu(w_up x)^2`).  x [T, D]; expert_ids
    / expert_weights [T, k] (each token's chosen experts and what each
    counts for); weights [L, E, D, F] / [L, E, F, D] (or without L),
    multiplied as stored (`up_transposed`: `w_up` is held [L, E, F, D],
    `grouped_matmul(transposed=True)`); `valid` [T] masks padding tokens,
    which reach no expert.

    With `first_held` the E experts held here are a share of those the
    ids run over: first_held to first_held + E.  An assignment to any
    other goes behind every group, as a padding token's do, so the grouped
    multiply sees only held experts and that assignment adds nothing.

    Returns (y [T, D], load [E] int32: the assignments each expert took)."""
    t, d = x.shape
    k = expert_ids.shape[1]
    e = w_down.shape[-3]
    flat = expert_ids.reshape(-1).astype(jnp.int32)            # [T * k]
    if first_held is not None:
        flat = flat - first_held
        flat = jnp.where((flat >= 0) & (flat < e), flat, e)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, e)   # behind every group
    order = jnp.argsort(flat, stable=True)     # sorted row -> assignment
    load = jnp.sum(flat[:, None] == jnp.arange(e)[None, :], axis=0,
                   dtype=jnp.int32)
    xs = x[order // k]                                         # [T * k, D]
    if w_gate is None:
        hidden = jnp.square(jax.nn.relu(grouped_matmul(
            xs, w_up, load, layer, transposed=up_transposed)))
    else:
        hidden = (jax.nn.silu(grouped_matmul(xs, w_gate, load, layer))
                  * grouped_matmul(xs, w_up, load, layer))
    ys = grouped_matmul(hidden, w_down, load, layer)           # [T * k, D]
    rank = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))  # assignment -> sorted row
    y = ys[rank].reshape(t, k, d).astype(jnp.float32)
    if first_held is not None:
        y = jnp.where((flat < e).reshape(t, k, 1), y, 0.0)
    elif valid is not None:
        y = jnp.where(valid[:, None, None], y, 0.0)   # rows no expert wrote
    out = jnp.einsum("tk,tkd->td", expert_weights.astype(jnp.float32), y)
    return out.astype(x.dtype), load
