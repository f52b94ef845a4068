#!/usr/bin/env python3
"""`aot_sizes.py train` for a configuration of any family: compile a train
cell's step at its real size for one described v5e chip, with no chip, and
print what it needs of the chip's memory and the kernels it calls.  Nothing
runs, so nothing here is a time.

Usage (in the sandbox, JAX_PLATFORMS=cpu):
  python3 benchmark/tools/aot_train_sizes.py <config> <traffic> [remat 0|1]
"""

from __future__ import annotations

import collections
import importlib
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmark import manifest


def compile_step(config_name: str, traffic_name: str, overrides=None):
    """The compiled step of `config_name` under `traffic_name`'s batch and
    optimizer on one described v5e chip."""
    import optax
    m = manifest.load()
    config, traffic = m.load_config(config_name), m.load_traffic(traffic_name)
    module = importlib.import_module(config["module"])
    cfg = manifest.model_config(
        config, {**traffic.get("config_overrides", {}), **(overrides or {})})
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    opt = getattr(optax, traffic["optimizer"]["name"])(
        **traffic["optimizer"]["args"])
    _, train_step = module.make_train_step(cfg, opt)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=dev), tree)

    params = on_chip(jax.eval_shape(
        lambda k: module.init_params(cfg, k), jax.random.key(0)))
    state = {"params": params,
             "opt_state": on_chip(jax.eval_shape(opt.init, params)),
             "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)}
    tokens = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]),
                                  jnp.int32, sharding=dev)
    return cfg, jax.jit(train_step, donate_argnums=0).lower(
        state, {"tokens": tokens}).compile()


def kernel_counts(text: str) -> dict:
    """Pallas calls of a compiled program's text by the name each carries
    (a call inside a loop's body is counted once)."""
    return dict(collections.Counter(
        line.split(" = ")[0].strip().lstrip("%").split(".")[0]
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line))


if __name__ == "__main__":
    jax.default_backend = lambda: "tpu"     # kernel paths as on the chip
    name, traffic, *rest = sys.argv[1:]
    t0 = time.perf_counter()
    cfg, compiled = compile_step(
        name, traffic, {"remat": bool(int(rest[0]))} if rest else None)
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"train step {name} / {traffic} remat={cfg.remat}: compiled in "
          f"{time.perf_counter() - t0:.1f} s; arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB; kernels "
          f"{kernel_counts(text)}", flush=True)
    out = os.path.join(ROOT, "benchmark", "out", f"aot_{name}.txt")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(text)
    print(f"compiled text in {out}")
