"""The paged cache's write path as one kernel call a layer.

A step leaves a few new rows a lane in every layer's pools
([L, NB, BS, W], ops/attention.py).  A row is not something a DMA can
address there: the pool's tile is 8 sublanes of 32 bits, so 8 float32 or
16 bfloat16 rows share a tile (two bfloat16 rows share every word of it),
and Mosaic refuses a slice of fewer rows than a tile holds (PERF.md
section 6, PR 45).  The unit of the write is therefore the aligned GROUP
of rows that one tile holds: `paged_rows_write` copies every group that
gets a new row out of the pools, all of them in flight at once, merges the
new rows in by a row mask, and copies the groups back, all in flight
again.  Two DMA latencies a layer, where the XLA loop that is the CPU's
path and the tests' oracle (`attention._rows_update_loop`) makes a chain of
`2 * B * n_touch` dependent whole-block updates.

What lands where comes through scalar prefetch, computed by XLA from the
block table, the positions and `valid`: a group's physical block, its
first row inside that block, and a word of bits, one a row of the group,
set where a valid new row lands.  A group with no bit set is skipped, not
read and written back as it was: with every copy in flight, the stale
table entry of a padding lane may name the very block a live lane writes.
PRECONDITION: the lanes of a call that have a valid row write blocks of
their own.  With every copy in flight, two of them landing in one group
would be a lost write (the XLA loop is serial there: the last lane wins).
The cache manager gives it (inference/kv_cache.py, "private tail": a block
that is being filled is held by one lane; shared blocks are sealed and
never written), and `tests/test_paged_write.py` holds every program of the
engine to it on the CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes of groups in flight in one grid step (every pool's): past that the
# kernel walks the lanes a few at a time.
_GROUPS_VMEM = 8 << 20
_VMEM_DEFAULT = 16 << 20            # Mosaic's own scoped limit on a v5e
_VMEM_MOST = 100 << 20


def group_rows(pools, t: int = 1) -> Optional[int]:
    """Rows of the aligned group the kernel moves: what one tile of the
    narrowest dtype among `pools` holds (8 sublanes of 32 bits), or the
    whole block where a block is not whole tiles.  None where the kernel
    does not apply: the group's rows are a bit each of one int32, a stored
    row is whole lanes of 128 columns, and ONE lane's groups and rows of a
    call of `t` rows a lane fit VMEM (the kernel walks lanes, not a
    lane's groups)."""
    bs = pools[0].shape[2]
    tile = 32 // min(p.dtype.itemsize for p in pools)
    g = tile if bs % tile == 0 else bs
    if g > 32 or any(p.shape[3] % 128 or p.shape[2] != bs for p in pools):
        return None
    if _vmem_limit(pools, g, t, 1) > _VMEM_MOST:
        return None
    return g


def _groups_of(t: int, g: int) -> int:
    """Groups of g rows that t consecutive rows touch at most."""
    return (t + g - 2) // g + 1


def _vmem_limit(pools, g: int, t: int, lanes: int) -> int:
    """What a grid step of `lanes` lanes asks of VMEM: their groups, and
    their rows' blocks twice (the next step's in flight), half again."""
    row_bytes = sum(p.shape[3] * p.dtype.itemsize for p in pools)
    rows = t if t == 1 else t + -t % g          # whole chunks of g rows
    return lanes * (_groups_of(t, g) * g + 2 * rows) * row_bytes * 3 // 2


def _rows_write_kernel(layer_ref, phys_ref, goff_ref, bits_ref, shift_ref,
                       *refs, n_pools: int):
    """The groups of `lanes` lanes (a grid step's): every live group read
    out of the pools, merged with the rows that land in it, written back."""
    rows = refs[:n_pools]                         # [lanes, T, W] VMEM blocks
    pools = refs[2 * n_pools:3 * n_pools]         # the outputs: the pools
    bufs = refs[3 * n_pools:4 * n_pools]          # [lanes, NG, g, W]
    sems = refs[4 * n_pools]
    lanes, n_groups, g, _ = bufs[0].shape
    t = rows[0].shape[1]
    lane0 = pl.program_id(0) * lanes
    layer = layer_ref[0]

    def each_live_group(do):
        # A loop, not straight-line code: a step program is traced and
        # lowered in every process before its compiled form is looked up,
        # and 16 lanes of straight-line copies in each of four layer
        # bodies cost the T=32 program 7 s there for 0.24 ms of its 14.
        def unit(u, carry):
            i, j = u // n_groups, u % n_groups
            n = (lane0 + i) * n_groups + j

            @pl.when(bits_ref[n] != 0)
            def _():
                do(i, j, n)
            return carry
        jax.lax.fori_loop(0, lanes * n_groups, unit, 0)

    def each_copy(back: bool, do):
        def group(i, j, n):
            at = pl.ds(pl.multiple_of(goff_ref[n], g), g)
            for p in range(n_pools):
                hbm = pools[p].at[layer, phys_ref[n], at]
                ends = (bufs[p].at[i, j], hbm) if back else (
                    hbm, bufs[p].at[i, j])
                do(pltpu.make_async_copy(*ends, sems.at[p]))
        each_live_group(group)

    def landing(p, i, j):
        """The new rows of lane i as group j's rows hold them, [g, W]: row
        r is the lane's row j * g + r - shift (anything where that is
        none of its T rows: the bits mask it)."""
        if t == 1:
            return jnp.broadcast_to(rows[p][i], bufs[p].shape[2:])
        # Two aligned chunks of g rows hold them; rotated down by the
        # lane's shift they line up with the group.  (In float32: a
        # rotation by sublanes moves 32-bit words.)
        last = t // g - 1
        lo, hi = (rows[p][i, pl.ds(pl.multiple_of(
            jnp.clip(c, 0, last) * g, g), g), :] for c in (j - 1, j))
        both = jnp.concatenate([lo, hi]).astype(jnp.float32)
        return pltpu.roll(both, shift_ref[lane0 + i], 0)[g:].astype(
            rows[p].dtype)

    def merge(i, j, n):
        for p in range(n_pools):
            row = jax.lax.broadcasted_iota(jnp.int32, bufs[p].shape[2:], 0)
            lands = (jax.lax.shift_right_logical(bits_ref[n], row) & 1) == 1
            bufs[p][i, j] = jnp.where(lands, landing(p, i, j), bufs[p][i, j])

    each_copy(False, lambda dma: dma.start())
    each_copy(False, lambda dma: dma.wait())
    each_live_group(merge)
    each_copy(True, lambda dma: dma.start())
    each_copy(True, lambda dma: dma.wait())


def write_plan(pools, positions, block_tables, valid):
    """Where a call's rows land, group by group: (g, n_groups, phys, goff,
    bits, shift) with phys, goff and bits int32 [B * n_groups] (the
    group's pool block, its first row in that block, bit r set where a
    valid row lands in the group's row r) and shift [B] (how far down its
    first group a lane's first row sits)."""
    b, t = positions.shape
    g = group_rows(pools, t)
    bs = pools[0].shape[2]
    mb = block_tables.shape[1]
    n_groups = _groups_of(t, g)
    first = positions[:, 0].astype(jnp.int32)
    shift = first % g
    group = first[:, None] // g + jnp.arange(n_groups, dtype=jnp.int32)
    block = group * g // bs
    phys = jnp.take_along_axis(block_tables.astype(jnp.int32),
                               jnp.clip(block, 0, mb - 1), axis=1)
    # run[b, k]: which of the lane's T rows lands in row k of its groups.
    run = jnp.arange(n_groups * g, dtype=jnp.int32)[None] - shift[:, None]
    lands = ((run >= 0) & (run < t)
             & jnp.take_along_axis(valid, jnp.clip(run, 0, t - 1), axis=1))
    bits = jnp.sum(lands.reshape(b, n_groups, g).astype(jnp.uint32)
                   << jnp.arange(g, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)
    # nothing lands off the table
    bits = jnp.where((block >= 0) & (block < mb), bits, jnp.uint32(0))
    return (g, n_groups, phys.reshape(-1), (group * g % bs).reshape(-1),
            jax.lax.bitcast_convert_type(bits, jnp.int32).reshape(-1), shift)


# (jitted: a process traces the call once a shape and a program lowers it
# once, not once a layer body and a program: PERF.md section 6, PRs 49, 53)
@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_rows_write(pools, rows, block_tables, positions, valid, layer=0,
                     *, interpret: bool = False):
    """`attention.paged_rows_update` as one Pallas call: pools [L, NB, BS,
    W_i] (aliased in place), rows [B, T, W_i] already in their pool's
    dtype; `group_rows(pools, T)` must not be None."""
    g, n_groups, phys, goff, bits, shift = write_plan(
        pools, positions, block_tables, valid)
    b, t = positions.shape
    # (at most 36 MB where lanes > 1; one lane's fit, or `group_rows` is None)
    vmem = _vmem_limit(pools, g, t, 1)
    if t > 1 and t % g:
        # whole chunks of g rows (a draft run of 5: rows nobody masks in)
        rows = tuple(jnp.pad(r, ((0, 0), (0, -t % g), (0, 0))) for r in rows)
        t += -t % g
    row_bytes = sum(p.shape[3] * p.dtype.itemsize for p in pools)
    fit = max(1, _GROUPS_VMEM // (n_groups * g * row_bytes))
    lanes = max(n for n in range(1, b + 1) if b % n == 0 and n <= fit)
    n = len(pools)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # layer, the groups' block, row, bits; shifts
        grid=(b // lanes,),
        in_specs=[pl.BlockSpec((lanes, t, p.shape[3]),
                               lambda i, *_: (i, 0, 0)) for p in pools]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
        scratch_shapes=[pltpu.VMEM((lanes, n_groups, g, p.shape[3]), p.dtype)
                        for p in pools] + [pltpu.SemaphoreType.DMA((n,))],
    )
    return tuple(pl.pallas_call(
        functools.partial(_rows_write_kernel, n_pools=n),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # the pools, behind the scalars and the rows
        input_output_aliases={5 + n + i: i for i in range(n)},
        interpret=interpret,
        # In order: a step's copies are back before the next one's start.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(lanes * vmem, _VMEM_DEFAULT)),
        name="paged_rows_write",
    )(jnp.asarray(layer, jnp.int32).reshape(1), phys, goff, bits, shift,
      *rows, *pools))
