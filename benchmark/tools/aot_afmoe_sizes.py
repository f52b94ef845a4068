#!/usr/bin/env python3
"""`aot_dots3_sizes.py` for a cell whose cache holds K and V rows of two
kinds of layer (`serve_trinity_docs_decode`): compile the program that makes
the weights, the engine's T=1 step over all lanes and its T=chunk and
T=chunk/4 steps over `prefill_lanes` and over one row, at their real size
for a described v5e with no chip, over the pools `PagedKVCache.for_model`
makes for the cell (the full layers' K and V rows, the window layers' K and
V rows) and a block table of both halves, and print what each needs of a
chip's memory, its kernels, `pool_copies` and `weight_bytes_copied`.
Nothing runs, so nothing here is a time.

Usage (in the sandbox, JAX_PLATFORMS=cpu):
  python3 benchmark/tools/aot_afmoe_sizes.py [cell] [t1|short|all]
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


def main(cell_name="serve_trinity_docs_decode", which="all"):
    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.inference.kv_cache import (PagedKVCache, count_pool_copies,
                                            count_weight_bytes_copied)
    m = manifest.load()
    cell = m.cells[cell_name]
    config = m.load_config(cell["config"])
    engine = dict(m.load_traffic(cell["traffic"])["engine"])
    cfg = manifest.model_config(config)
    model = importlib.import_module(config["module"])
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

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
            max_seq_len=engine["max_seq_len"], ahead=2 * chunk)
        made["tables"] = cache.block_tables.shape
        return cache.k

    pools = jax.tree.map(lambda x: arg(x.shape, x.dtype),
                         jax.eval_shape(pools_of))
    print(f"weights {nbytes(params) / 1e9:.3f} GB, pools "
          f"{[tuple(p.shape) for p in pools]} = {nbytes(pools) / 1e9:.3f} GB, "
          f"block tables {made['tables']}", flush=True)
    moe = (arg((cfg.n_routed_experts + 2,), jnp.int32),)
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
