"""The paged cache's write path as a kernel (`ops/paged_write.py`, the TPU's
path, here in the Pallas interpreter) against the XLA loop that is the CPU's
path and its oracle (`ops/attention.py::_rows_update_loop`).  What the loop
itself leaves is `tests/test_inference.py`'s
`test_paged_kv_update_masks_invalid_lanes`, which runs both paths against
numpy; that the kernel compiles for a v5e at the serve cells' shapes and
keeps the pools in place is `tests/test_tpu_aot.py`'s.  Last, the kernel's
precondition held against the engine: no block written by two lanes of a
step."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import manifest
from scripts.engine_step_time import cell_writes


def _cell_writes():
    """{name: (lanes, the prefill program's (rows, T), pools)}: every call
    of the write path a layer body of a serve cell's step makes, at the
    shapes the benchmark's own files give (`cell_writes`): the one table of
    this file and of `tests/test_tpu_aot.py`."""
    out = {}
    for cell in manifest.load().cells:
        if cell.startswith("serve_"):
            lanes, chunk, calls = cell_writes(cell)
            for i, pools in enumerate(calls):
                bs, widths = _bs_widths(pools)
                name = "%s%s_%dx%s" % (cell.split("_")[1], "_call2" * i, bs,
                                       "_".join(str(w) for w in widths))
                out[name] = (lanes, chunk, pools)
    return out


def _bs_widths(pools):
    return pools[0].shape[2], tuple(p.shape[3] for p in pools)


CELL_WRITES = _cell_writes()
# Block size and the row widths of the pools one call writes: the cells',
# and three pools of three widths.
_WRITE_POOLS = {
    **{name: _bs_widths(pools) for name, (_, _, pools) in CELL_WRITES.items()},
    "three_pools_16x256_128_384": (16, (256, 128, 384)),
}


def _write_case(id, pools="three_pools_16x256_128_384", dtype=jnp.bfloat16,
                t=1, lanes=3, first=None, valid=None, scan=False):
    return pytest.param(*_WRITE_POOLS[pools], dtype, t, lanes, first, valid,
                        scan, id=id)


@pytest.mark.parametrize("bs,widths,dtype,t,lanes,first,valid,scan", [
    *(_write_case(f"{pools}_{jnp.dtype(dtype).name}_t{t}", pools, dtype, t)
      for pools in _WRITE_POOLS for dtype in (jnp.bfloat16, jnp.float32)
      for t in (1, 5, 32)),
    # A chunk that starts mid-block and crosses into the lane's next block
    # with dead positions inside it and at its end (a prompt's overhang),
    # beside a lane that ends on a block's last row.
    _write_case("t6_crosses_a_block_dead_inside_and_at_its_end", t=6,
                lanes=2, first=[13, 10],
                valid=[[True, True, False, True, True, False],
                       [True, False, False, True, True, True]]),
    _write_case("t40_three_blocks_of_16_from_its_last_row", t=40, lanes=2,
                first=[15, 31]),
    # Lanes 1 and 2 have no valid row, and (as every case here) the table
    # of such a lane names the blocks the live lane before it writes: its
    # groups are skipped, not read and written back in a race with lane 0.
    _write_case("invalid_lanes_name_the_blocks_a_live_lane_writes",
                dtype=jnp.float32, t=5, first=[14, 14, 14],
                valid=[[True] * 5, [False] * 5, [False] * 5]),
    _write_case("all_lanes_invalid_t1", valid=[[False]] * 3),
    _write_case("all_lanes_invalid_t32", t=32, valid=[[False] * 32] * 3),
    _write_case("layer_traced_in_a_scan_t1", scan=True),
    _write_case("layer_traced_in_a_scan_t5_float32", dtype=jnp.float32, t=5,
                scan=True),
    _write_case("t1_70_lanes", lanes=70),
])
def test_paged_rows_write_kernel_leaves_what_the_loop_leaves(
        bs, widths, dtype, t, lanes, first, valid, scan):
    """`paged_rows_write` (the TPU's path, here in the interpreter) against
    the XLA loop that is the CPU's path and its oracle: the pools of one
    call, bit for bit, over random pools, rows and tables, lanes that
    start anywhere in their first three blocks, three rows in ten dead,
    and one lane with no valid row whose table names a live lane's
    blocks.  `scan`: a layer a trip of a `lax.scan` over the layers."""
    from ray_tpu.ops.attention import paged_rows_update
    from ray_tpu.ops import paged_write
    rng = np.random.default_rng(7)
    layers = 3 if scan else 2
    mb = (t + 3 * bs - 2) // bs + 1
    nb = lanes * mb + 1
    pools = tuple(jnp.asarray(rng.standard_normal((layers, nb, bs, w)), dtype)
                  for w in widths)
    rows = tuple(jnp.asarray(rng.standard_normal((lanes, t, w)), dtype)
                 for w in widths)
    tables = rng.permutation(nb)[:lanes * mb].reshape(lanes, mb).astype(
        np.int32)
    if first is None:
        first = rng.integers(0, 3 * bs - 1, lanes)
    positions = (np.asarray(first)[:, None] + np.arange(t)).astype(np.int32)
    if valid is None:
        valid = rng.random((lanes, t)) < 0.7
        valid[0, 0] = True
        valid[lanes - 1] = False
    valid = np.asarray(valid)
    for lane in range(1, lanes):
        if not valid[lane].any():
            tables[lane] = tables[lane - 1]
    assert paged_write.group_rows(pools) == 32 // jnp.dtype(dtype).itemsize

    def write(use_kernel):
        def one(pools, layer):
            return paged_rows_update(pools, rows, tables, positions, valid,
                                     layer, use_kernel=use_kernel)
        if scan:
            return jax.jit(lambda pools: jax.lax.scan(
                lambda pools, i: (one(pools, i), None), pools,
                jnp.arange(1, layers))[0])(pools)
        return jax.jit(functools.partial(one, layer=1))(pools)

    want, got = write(False), write(True)
    for before, loop, kernel in zip(pools, want, got):
        np.testing.assert_array_equal(
            np.asarray(kernel.astype(jnp.float32)),
            np.asarray(loop.astype(jnp.float32)))
        changed = np.asarray((kernel != before).any(axis=(1, 2, 3)))
        assert list(changed) == [False] + [bool(valid.any())] * (layers - 1)


def test_a_block_the_write_kernel_does_not_take_goes_through_the_loop():
    """A block of 40 rows is neither whole tiles of bfloat16 nor at most
    the 32 rows a group's word of bits holds: no group, and on TPU the XLA
    loop writes it (said once per shape in the log)."""
    from ray_tpu.ops import paged_write
    pool = jax.ShapeDtypeStruct((2, 8, 40, 128), jnp.bfloat16)
    assert paged_write.group_rows((pool,)) is None
    assert paged_write.group_rows(
        (jax.ShapeDtypeStruct((2, 8, 40, 128), jnp.float32),)) == 8
    # a block of 4 float32 rows is part of a tile: the whole block a group
    assert paged_write.group_rows(
        (jax.ShapeDtypeStruct((2, 8, 4, 128), jnp.float32),)) == 4
    assert paged_write.group_rows(
        (jax.ShapeDtypeStruct((2, 8, 16, 100), jnp.float32),)) is None
    # The kernel walks lanes, not a lane's groups: a chunk of which ONE
    # lane's groups and rows pass VMEM (2,048 rows of EvaByte's two pools:
    # 151 MB) is the loop's, the cell's own chunk of 512 (38 MB) is not.
    eva = CELL_WRITES["evabyte_128x4096_4096"][2]
    assert paged_write.group_rows(eva, 512) == 16
    assert paged_write.group_rows(eva, 2048) is None


def _watch_the_write_path(monkeypatch):
    """Every call of `paged_rows_update` that a program makes from here on
    reports, when it RUNS, the blocks each of its lanes with a valid row
    writes: `calls` gathers a list of sets a call, a set a live lane."""
    from ray_tpu.ops import attention as ops
    calls, real = [], ops.paged_rows_update

    def blocks_written(bs, tables, positions, valid):
        calls.append([{int(tables[lane, p // bs]) for p in at[ok]}
                      for lane, (at, ok) in enumerate(zip(positions, valid))
                      if ok.any()])

    def watched(pools, rows, block_tables, positions, valid, layer=0, **kw):
        jax.debug.callback(
            functools.partial(blocks_written, pools[0].shape[2]),
            block_tables, positions, valid)
        return real(pools, rows, block_tables, positions, valid, layer, **kw)

    monkeypatch.setattr(ops, "paged_rows_update", watched)
    return calls


def _engine_case(id, family, config, **kw):
    return pytest.param(family, config, kw, id=id)


@pytest.mark.parametrize("family,config,kw", [
    _engine_case("gpt_chunks_behind_decoding_lanes", "gpt", "nano",
                 max_lanes=3, block_size=4, prefill_chunk=8),
    _engine_case("gpt_verify_steps_of_4_rows", "gpt", "nano", max_lanes=3,
                 block_size=4, prefill_chunk=8, spec_k=3),
    _engine_case("gpt_compact_prefill_of_2_lanes_of_4", "gpt", "nano",
                 max_lanes=4, block_size=4, prefill_chunk=8, prefill_lanes=2),
    _engine_case("llama_gqa_step_ahead", "llama", "llama-tiny", max_lanes=3,
                 block_size=4, prefill_chunk=8),
    _engine_case("evabyte_windows_and_summaries", "evabyte", "evabyte-nano",
                 max_lanes=3, block_size=8, prefill_chunk=16, prefill_lanes=2,
                 num_blocks=64),
    _engine_case("dots3_latent_index_and_sliding_pools", "dots3",
                 "dots3-nano-share", max_lanes=4, block_size=4,
                 prefill_chunk=8, prefill_lanes=2, num_blocks=(96, 48),
                 max_seq_len=96),
    _engine_case("falconh1_heads_beside_a_state", "falconh1", "falconh1-nano",
                 max_lanes=4, block_size=4, prefill_chunk=8, prefill_lanes=2,
                 num_blocks=(96, 4), max_seq_len=96),
])
def test_the_live_lanes_of_a_step_write_blocks_of_their_own(
        monkeypatch, family, config, kw):
    """The write kernel's precondition (`ops/paged_write.py`), held against
    the engine and its cache manager on the CPU: more requests than lanes,
    prompts that share a prefix of whole blocks and part ways inside one
    (so lanes hold the same sealed blocks while they fill their own), lanes
    that end and whose blocks the next request takes.  In every call of
    the write path that every program makes (T=1, prefill chunks behind
    decoding lanes, compact prefill programs, verify steps), no block is
    written by two lanes.  With every copy in flight on the TPU that would
    be a lost write."""
    from ray_tpu.inference.engine import InferenceEngine
    calls = _watch_the_write_path(monkeypatch)
    eng = InferenceEngine(family, config, auto_start=False, seed=0, **kw)
    vocab = eng.config.vocab_size
    rng = np.random.default_rng(5)
    shared = rng.integers(1, vocab, 19).tolist()    # whole blocks and a part
    prompts = [shared + rng.integers(1, vocab, n).tolist()
               for n in (3, 9, 1, 14, 6)] + [rng.integers(1, vocab, 7).tolist()]
    handles = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, (9, 5, 12, 7, 10, 8))]
    while eng.step():
        pass
    jax.effects_barrier()
    assert [len(h.tokens()) for h in handles] == [9, 5, 12, 7, 10, 8]
    assert eng.stats()["prefix_hits"] > 0
    # several lanes wrote in one call, rows of a chunk among them
    assert max(len(lanes) for lanes in calls) >= 2
    assert max(len(blocks) for lanes in calls for blocks in lanes) >= 2
    for lanes in calls:
        assert sum(len(blocks) for blocks in lanes) == len(
            set().union(*lanes)), lanes
