#!/usr/bin/env python3
"""`aot_serve_sizes.py` for a cell whose engine has a latent cache and a
prefill program over fewer lanes than the decode one
(`serve_axk1_docs_decode`): compile the engine's T=1 step over all lanes and
its T=chunk and T=chunk/4 steps over `prefill_lanes`, and the program that
makes the
weights, at their real size for a described v5e with no chip, and print what
each needs of a chip's memory, its kernels, `pool_copies` and
`weight_bytes_copied`.  Nothing runs, so nothing here is a time.
(`aot_serve_sizes.py` hands the step a K and a V pool and `max_lanes` rows
for both programs; it is left as it is for the cells it sizes.)

Usage (in the sandbox, JAX_PLATFORMS=cpu):
  python3 benchmark/tools/aot_axk1_sizes.py [cell] [n_layers] [num_blocks]
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
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmark import manifest
from benchmark.tools.aot_serve_sizes import report


def main(cell_name="serve_axk1_docs_decode", n_layers=None, num_blocks=None):
    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.inference.kv_cache import (count_pool_copies,
                                            count_weight_bytes_copied)
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
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    t0 = time.perf_counter()
    init = jax.jit(model.init_params, static_argnums=0,
                   out_shardings=dev).lower(
        cfg, jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                  sharding=dev)).compile()
    report(f"init_params {cell['config']} layers={cfg.n_layers}", init, t0)

    eng = object.__new__(InferenceEngine)
    eng.model, eng.config, eng._capture_logp = model, cfg, False
    eng.backend, eng._step_impls = "tpu", {}
    shapes = jax.eval_shape(
        lambda k: model.serving_params(model.init_params(cfg, k), cfg),
        jax.random.key(0))
    params = jax.tree.map(lambda x: arg(x.shape, x.dtype), shapes)
    attn = model.spec(cfg).attn
    lanes, bs = engine["max_lanes"], engine["block_size"]
    pool = arg((cfg.n_layers, engine["num_blocks"], bs,
                kv_row_width(*attn.cache_row(cfg))), cfg.dtype)
    pools = (pool, None if attn.latent else pool)
    mb = -(-min(engine.get("max_seq_len", cfg.max_seq_len),
                cfg.max_seq_len) // bs)
    held = getattr(cfg, "n_experts_held", 0) or getattr(cfg, "n_experts", 0)
    moe = (arg((held + 2,), jnp.int32),) if held else ()
    texts = {}
    prefill_rows = min(engine.get("prefill_lanes") or lanes, lanes)
    chunk = engine["prefill_chunk"]
    programs = [(1, lanes), (chunk, prefill_rows)]
    if prefill_rows < lanes and chunk // 4:
        programs.append((chunk // 4, prefill_rows))     # engine._prefill_len
    for t, rows in programs:
        compact = rows < lanes
        t0 = time.perf_counter()
        compiled = eng._make_step_fn(False, False, compact).lower(
            params, *pools, arg((rows, t), jnp.int32),
            arg((rows, t), jnp.int32), arg((rows, t), jnp.bool_),
            arg((lanes, mb), jnp.int32), arg((rows,), jnp.int32),
            arg((rows,), jnp.int32), arg((rows,), jnp.float32),
            arg((rows,), jnp.uint32), arg((rows,), jnp.int32),
            *((arg((rows,), jnp.int32),) if compact else ()),
            arg((lanes,), jnp.int32), *moe).compile()
        text = texts[t] = report(
            f"engine step T={t} rows={rows} of {lanes} lanes "
            f"blocks={engine['num_blocks']}x{bs} layers={cfg.n_layers}",
            compiled, t0)
        print("  pool_copies", count_pool_copies(text, pool.shape),
              "weight_bytes_copied",
              dict(count_weight_bytes_copied(text, params)), flush=True)
    return texts


if __name__ == "__main__":
    jax.default_backend = lambda: "tpu"     # kernel paths as on the chip
    main(*sys.argv[1:])
