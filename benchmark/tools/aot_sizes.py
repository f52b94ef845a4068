#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile a cell's programs at
their real size for a described v5e, with no chip, and print what each needs
of a chip's memory.  Nothing runs, so nothing here is a time.

Usage (in the sandbox, JAX_PLATFORMS=cpu):
  python3 benchmark/tools/aot_sizes.py serve gpt2-xl <lanes> <num_blocks>
  python3 benchmark/tools/aot_sizes.py train gpt2-xl <global_batch> <remat 0|1> <fsdp>
"""

from __future__ import annotations

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
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; "
          f"arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
          f"outputs {m.output_size_in_bytes / 1e9:.2f} GB, "
          f"aliased {m.alias_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB; "
          f"{text.count('tpu_custom_call')} kernel calls, "
          f"all-gather {text.count(' all-gather(') + text.count(' all-gather-start(')}, "
          f"all-reduce {text.count(' all-reduce(') + text.count(' all-reduce-start(')}, "
          f"reduce-scatter {text.count(' reduce-scatter(')}", flush=True)


def serve(config_name, lanes, num_blocks, block_size=16, chunk=32):
    from ray_tpu.inference.engine import InferenceEngine
    from ray_tpu.models import gpt
    from ray_tpu.ops.attention import kv_row_width
    cfg = manifest.model_config(manifest.load().load_config(config_name))
    dev = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    eng = object.__new__(InferenceEngine)
    eng.model, eng.config, eng._capture_logp = gpt, cfg, False
    eng.backend, eng._step_impls = "tpu", {}
    step = eng._make_step_fn(False)
    params = jax.tree.map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: gpt.init_params(cfg, k), jax.random.key(0)))
    # as `PagedKVCache` stores it: rows of W columns, one layout for the
    # write and the kernel's read
    pool = arg((cfg.n_layers, num_blocks, block_size,
                kv_row_width(cfg.n_heads, cfg.head_dim)), cfg.dtype)
    mb = cfg.max_seq_len // block_size
    for t in (1, chunk):
        t0 = time.perf_counter()
        compiled = step.lower(
            params, pool, pool, arg((lanes, t), jnp.int32),
            arg((lanes, t), jnp.int32), arg((lanes, t), jnp.bool_),
            arg((lanes, mb), jnp.int32), arg((lanes,), jnp.int32),
            arg((lanes,), jnp.int32), arg((lanes,), jnp.float32),
            arg((lanes,), jnp.uint32), arg((lanes,), jnp.int32)).compile()
        report(f"engine step T={t} lanes={lanes} blocks={num_blocks}",
               compiled, t0)


def train(config_name, batch, remat, fsdp, seq=1024):
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import named_sharding, tree_shardings
    cfg = manifest.model_config(manifest.load().load_config(config_name),
                                {"remat": bool(remat)})
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[:fsdp]
    mesh = create_mesh(MeshConfig(data=1, fsdp=fsdp), devices=devices)
    opt = optax.adamw(1e-4)
    _, train_step = gpt.make_train_step(cfg, opt, mesh if fsdp > 1 else None)
    shardings = tree_shardings(mesh, gpt.param_specs(cfg))
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        jax.eval_shape(lambda k: gpt.init_params(cfg, k), jax.random.key(0)),
        shardings)
    rep = NamedSharding(mesh, P())

    # Adam's moments take their parameter's sharding (found by shape: the
    # parameter shapes of one model are distinct enough); counters replicate.
    opt_shapes = jax.eval_shape(opt.init, params)
    flat_p = {x.shape: x.sharding for x in jax.tree.leaves(params)}
    opt_state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=flat_p.get(x.shape, rep)), opt_shapes)
    state = {"params": params, "opt_state": opt_state,
             "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32,
        sharding=named_sharding(mesh, ("batch", "length")))
    t0 = time.perf_counter()
    compiled = jax.jit(train_step, donate_argnums=0).lower(
        state, {"tokens": tokens}).compile()
    report(f"train step {config_name} batch={batch} remat={remat} "
           f"fsdp={fsdp}", compiled, t0)


if __name__ == "__main__":
    jax.default_backend = lambda: "tpu"     # kernel paths as on the chip
    kind, name, *rest = sys.argv[1:]
    if kind == "serve":
        serve(name, *(int(x) for x in rest))
    else:
        train(name, *(int(x) for x in rest))
