#!/usr/bin/env python3
"""`aot_sizes.py serve` for a configuration of any model module: compile the
engine's T=1 and T=chunk steps, and the program that makes the weights, at
their real size for a described v5e with no chip, and print what each needs
of a chip's memory.  The model is built from the configuration file's own
`module` and the engine from the cell's traffic file.  Nothing runs, so
nothing here is a time.

Usage (in the sandbox, JAX_PLATFORMS=cpu):
  python3 benchmark/tools/aot_serve_sizes.py <cell> [n_layers] [num_blocks]
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


def report(name, compiled, t0):
    """`aot_sizes.report`'s line, then the compiled program's kernels by
    name; returns the program's text."""
    from benchmark.tools import aot_sizes
    aot_sizes.report(name, compiled, t0)
    text = compiled.as_text()
    print("  kernels:", sorted({
        line.split(" = ")[0].strip().lstrip("%").split(".")[0]
        for line in text.splitlines() if "tpu_custom_call" in line
        and " = " in line}), flush=True)
    return text


def main(cell_name, n_layers=None, num_blocks=None):
    from ray_tpu.inference.engine import InferenceEngine
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
    step = eng._make_step_fn(False)
    params = jax.tree.map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: model.init_params(cfg, k),
                       jax.random.key(0)))
    lanes, bs = engine["max_lanes"], engine["block_size"]
    pool = arg((cfg.n_layers, engine["num_blocks"], bs,
                kv_row_width(getattr(cfg, "n_kv_heads", cfg.n_heads),
                             cfg.head_dim)), cfg.dtype)
    mb = -(-min(engine.get("max_seq_len", cfg.max_seq_len),
                cfg.max_seq_len) // bs)
    moe = ((arg((cfg.n_experts + 2,), jnp.int32),)
           if getattr(cfg, "n_experts", 0) else ())
    texts = {}
    for t in (1, engine["prefill_chunk"]):
        t0 = time.perf_counter()
        compiled = step.lower(
            params, pool, pool, arg((lanes, t), jnp.int32),
            arg((lanes, t), jnp.int32), arg((lanes, t), jnp.bool_),
            arg((lanes, mb), jnp.int32), arg((lanes,), jnp.int32),
            arg((lanes,), jnp.int32), arg((lanes,), jnp.float32),
            arg((lanes,), jnp.uint32), arg((lanes,), jnp.int32),
            *moe).compile()
        texts[t] = report(
            f"engine step T={t} lanes={lanes} blocks={engine['num_blocks']} "
            f"layers={cfg.n_layers}", compiled, t0)
    return texts


if __name__ == "__main__":
    jax.default_backend = lambda: "tpu"     # kernel paths as on the chip
    out = main(*sys.argv[1:])
    dump = os.environ.get("AOT_DUMP_DIR")
    if dump:
        for t, text in out.items():
            with open(os.path.join(dump, f"step_t{t}.hlo.txt"), "w") as f:
                f.write(text)
