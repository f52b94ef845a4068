#!/usr/bin/env python3
"""`aot_falconh1_sizes.py` for a cell whose state part and K/V pools have
layer counts of their own and whose step carries an expert layer's load
counters (`serve_nemotron3_agents_decode`): compile the program that makes
the weights, the engine's T=1 step over all lanes and its T=chunk and
T=chunk/4 steps over `prefill_lanes` and over one row, at their real size
for a described v5e with no chip, over the buffers `PagedKVCache.for_model`
makes for the cell (K and V pools over the attention layers, the recurrent
states' slots and the convolutions' tails over the mixer layers), and print
what each needs of a chip's memory, its kernels, `pool_copies` of every
buffer and `weight_bytes_copied`.  Nothing runs, so nothing here is a time.

Usage (in the sandbox, JAX_PLATFORMS=cpu):
  python3 benchmark/tools/aot_nemotronh_sizes.py [cell] [t1|short|all]
"""

from __future__ import annotations

import importlib
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmark import manifest
from benchmark.tools.aot_serve_sizes import report


def main(cell_name="serve_nemotron3_agents_decode", which="all"):
    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.inference.kv_cache import (PagedKVCache, count_pool_copies,
                                            count_weight_bytes_copied)
    m = manifest.load()
    cell = m.cells[cell_name]
    config = m.load_config(cell["config"])
    engine = dict(m.load_traffic(cell["traffic"])["engine"])
    cfg = manifest.model_config(config)
    model = importlib.import_module(config["module"])
    try:
        dev = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    except Exception as e:      # no v5e topology can be described here
        raise RuntimeError(f"no v5e:2x2 topology: {e}") from e

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    def nbytes(tree):
        return sum(math.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    if which == "all":
        t0 = time.perf_counter()
        init = jax.jit(model.init_params, static_argnums=0,
                       out_shardings=dev).lower(
            cfg, jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                      sharding=dev)).compile()
        report(f"init_params {cell['config']} layers={cfg.n_layers}", init,
               t0)

    eng = object.__new__(InferenceEngine)
    eng.model, eng.config, eng._capture_logp = model, cfg, False
    eng.backend, eng._step_impls = "tpu", {}
    shapes = jax.eval_shape(
        lambda k: model.serving_params(model.init_params(cfg, k), cfg),
        jax.random.key(0))
    params = jax.tree.map(lambda x: arg(x.shape, x.dtype), shapes)
    lanes, chunk = engine["max_lanes"], engine["prefill_chunk"]
    made = {}

    def pools_of():
        cache = PagedKVCache.for_model(
            model, cfg, num_blocks=engine["num_blocks"],
            block_size=engine["block_size"], max_lanes=lanes,
            max_seq_len=engine["max_seq_len"])
        made["tables"] = cache.block_tables.shape
        made["snapshots"] = nbytes((cache.snaps, cache.snap_tails))
        return cache.step_pools[0]

    pools = jax.tree.map(lambda x: arg(x.shape, x.dtype),
                         jax.eval_shape(pools_of))
    print(f"weights {nbytes(params) / 1e9:.3f} GB, buffers "
          f"{[tuple(p.shape) for p in pools]} = {nbytes(pools) / 1e9:.3f} GB, "
          f"snapshots {made['snapshots'] / 1e9:.3f} GB, block tables "
          f"{made['tables']}", flush=True)
    moe = (arg((cfg.held + 2,), jnp.int32),)
    prefill_rows = min(engine.get("prefill_lanes") or lanes, lanes)
    programs = {"t1": [(1, lanes)], "short": [(chunk // 4, 1)]}.get(
        which, [(1, lanes), (chunk, prefill_rows),
                (chunk // 4, prefill_rows), (chunk, 1), (chunk // 4, 1)])
    texts = {}
    for t, rows in programs:
        compact = rows < lanes
        t0 = time.perf_counter()
        compiled = eng._make_step_fn(False, False, compact).lower(
            params, pools, None, arg((rows, t), jnp.int32),
            arg((rows, t), jnp.int32), arg((rows, t), jnp.bool_),
            arg(made["tables"], jnp.int32), arg((rows,), jnp.int32),
            arg((rows,), jnp.int32), arg((rows,), jnp.float32),
            arg((rows,), jnp.uint32), arg((rows,), jnp.int32),
            *((arg((rows,), jnp.int32),) if compact else ()),
            arg((lanes,), jnp.int32), *moe).compile()
        text = report(
            f"engine step T={t} rows={rows} of {lanes} lanes "
            f"layers={cfg.n_layers}", compiled, t0)
        print("  pool_copies",
              [count_pool_copies(text, p.shape) for p in pools],
              "weight_bytes_copied",
              dict(count_weight_bytes_copied(text, params)), flush=True)
        texts[(t, rows)] = (text, compiled.memory_analysis(), pools)
    return texts


if __name__ == "__main__":
    jax.default_backend = lambda: "tpu"     # kernel paths as on the chip
    main(*sys.argv[1:])
