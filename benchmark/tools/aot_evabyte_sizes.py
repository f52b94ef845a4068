#!/usr/bin/env python3
"""`aot_serve_sizes.py` for a cell whose engine has a windowed cache
(`serve_evabyte_sessions_decode`): compile the engine's three kinds of
program at their real size for a described v5e with no chip: the T=1 step
over all lanes, the T=chunk and T=chunk/4 steps over `prefill_lanes` rows and
over one, and the compaction over `prefill_lanes` lanes (a window's exact blocks ->
its summary rows, all layers), with the block table as wide as the cache
manager makes it (the peak of the sawtooth, not max_seq_len / block_size);
and print what each needs of a chip's memory, its kernels, `pool_copies`
and `weight_bytes_copied`.  Nothing runs, so nothing here is a time.
(`aot_serve_sizes.py` and `aot_axk1_sizes.py` size the table by the longest
request and know no compaction; they are left as they are for the cells
they size.)

Usage (in the sandbox, JAX_PLATFORMS=cpu):
  python3 benchmark/tools/aot_evabyte_sizes.py [cell] [n_layers] [num_blocks]
"""

from __future__ import annotations

import importlib
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from benchmark import manifest


def programs(device, cell_name="serve_evabyte_sessions_decode",
             n_layers=None, num_blocks=None):
    """Yields (name, compiled program, pool shape, parameter shapes) for
    each program of the cell's engine, lowered for `device` (one of a
    described topology's)."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.inference.kv_cache import PagedKVCache
    from ray_tpu.ops.attention import kv_row_width
    m = manifest.load()
    cell = m.cells[cell_name]
    config = m.load_config(cell["config"])
    engine = dict(m.load_traffic(cell["traffic"])["engine"])
    if num_blocks:
        engine["num_blocks"] = int(num_blocks)
    cfg = manifest.model_config(
        config, {"n_layers": int(n_layers)} if n_layers else None)
    model = importlib.import_module(config["module"])
    dev = SingleDeviceSharding(device)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    eng = object.__new__(InferenceEngine)
    eng.model, eng.config, eng._capture_logp = model, cfg, False
    eng.backend, eng._step_impls = "tpu", {}
    shapes = jax.eval_shape(
        lambda k: model.serving_params(model.init_params(cfg, k), cfg),
        jax.random.key(0))
    params = jax.tree.map(lambda x: arg(x.shape, x.dtype), shapes)
    rows_of = model.spec(cfg).attn.rows(cfg)
    lanes, bs = engine["max_lanes"], engine["block_size"]
    pool = arg((cfg.n_layers, engine["num_blocks"], bs,
                kv_row_width(rows_of.kv_heads, rows_of.head_dim)), cfg.dtype)
    # The table's width and a window's blocks, as the manager counts them
    # (a one-block cache of the same geometry: no pool of the cell's size).
    book = PagedKVCache(1, rows_of.kv_heads, rows_of.head_dim, num_blocks=1,
                        block_size=bs, max_lanes=1,
                        max_seq_len=engine["max_seq_len"],
                        window=rows_of.window, chunk=rows_of.chunk)
    mb = book.max_blocks_per_seq
    prefill_rows = min(engine.get("prefill_lanes") or lanes, lanes)
    chunk = engine["prefill_chunk"]
    steps = [(1, lanes), (chunk, prefill_rows)]
    if prefill_rows < lanes and chunk // 4:
        steps.append((chunk // 4, prefill_rows))        # engine._prefill_len
    if 1 < prefill_rows < lanes:        # and each at one row: one lane alone
        steps += [(t, 1) for t, _ in steps[1:]]
    for t, rows in steps:
        compact = rows < lanes
        compiled = eng._make_step_fn(False, False, compact).lower(
            params, pool, pool, arg((rows, t), jnp.int32),
            arg((rows, t), jnp.int32), arg((rows, t), jnp.bool_),
            arg((lanes, mb), jnp.int32), arg((rows,), jnp.int32),
            arg((rows,), jnp.int32), arg((rows,), jnp.float32),
            arg((rows,), jnp.uint32), arg((rows,), jnp.int32),
            *((arg((rows,), jnp.int32),) if compact else ()),
            arg((lanes,), jnp.int32)).compile()
        yield (f"engine step T={t} rows={rows} of {lanes} lanes table={mb} "
               f"blocks={engine['num_blocks']}x{bs} layers={cfg.n_layers}",
               compiled, pool.shape, params)
    if book.window:
        compiled = eng._make_compact_fn().lower(
            params, pool, pool, arg((prefill_rows, book._win_blocks),
                                    jnp.int32),
            arg((prefill_rows, book._sum_blocks), jnp.int32),
            arg((prefill_rows,), jnp.bool_)).compile()
        yield (f"compaction rows={prefill_rows} {book._win_blocks} -> "
               f"{book._sum_blocks} blocks layers={cfg.n_layers}",
               compiled, pool.shape, params)


def main(*args):
    from jax.experimental import topologies

    from benchmark.tools.aot_serve_sizes import report
    from ray_tpu.inference.kv_cache import (count_pool_copies,
                                            count_weight_bytes_copied)
    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    t0 = time.perf_counter()
    for name, compiled, pool_shape, params in programs(device, *args):
        text = report(name, compiled, t0)
        print("  pool_copies", count_pool_copies(text, pool_shape),
              "weight_bytes_copied",
              dict(count_weight_bytes_copied(text, params)), flush=True)
        t0 = time.perf_counter()


if __name__ == "__main__":
    jax.default_backend = lambda: "tpu"     # kernel paths as on the chip
    main(*sys.argv[1:])
