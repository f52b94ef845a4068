#!/usr/bin/env python3
"""Time the engine's compiled step alone at `serve_gpt2xl_decode`'s sizes
(gpt2-xl whole on the tree `serving_params` prepares, 16 lanes of 100-350
tokens, 512 blocks of 16): the T=1 program; the T=32 program with two
lanes prefilling a chunk, once with a valid row in each of the other 14
lanes (`t32`, the figure PERF.md has carried since PR 34) and once with
them masked and at context 1 as `_build_batch` hands a prefill step over
(`t32_masked`); and the speculative verify program (`verify_t5`: `spec_k`
4, every lane's five rows valid over its whole context).  Each is called
back to back with the pools fed round, so the figure is the device
program's length and holds no host work.  Since PR 34 the host is the longer side of that cell's traced
slice, and `decode_step_ms_p50` there reads the host (PERF.md sections 5
and 7); this is where the program's own length comes from.  Not a tool the
benchmark runs.  On the chip, from the root of a checkout (the parent's,
to compare):

  python3 scripts/engine_step_time.py [scan_unroll ...]

With no argument the configuration's own `scan_unroll`; several give the
layer loop at each (PERF.md section 6, PR 34: 9.45 ms at 4, 9.53 at 1,
14.00 before the loop indexed its stacks).  The last line is one JSON
object, ms a call over three sets of calls.

Since PR 42 the engine runs each step behind the unpacking of the lanes' one
buffer (`_make_entry`): every program is timed that way too (`..._entry_ms`,
beside the nine-array step's `..._ms`: the difference is what the unpacking
costs the device), and `host_upload_ms` is what handing a T=1 population to
the device costs the host with nothing else running (`_upload`, mean of 200
calls, three sets), beside the eight `jnp.asarray` of the same arrays that
the engine made before (`host_upload_eight_ms`).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import types

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import manifest
from ray_tpu.inference.engine import InferenceEngine, _lane_views
from ray_tpu.models import gpt
from ray_tpu.ops.attention import kv_row_width


def main(unrolls):
    m = manifest.load()
    cell = m.cells["serve_gpt2xl_decode"]
    base = manifest.model_config(m.load_config(cell["config"]), None)
    engine = m.load_traffic(cell["traffic"])["engine"]
    lanes, bs, nb = (engine[k] for k in ("max_lanes", "block_size",
                                         "num_blocks"))
    mb = base.max_seq_len // bs
    params = jax.block_until_ready(jax.jit(
        lambda k: gpt.serving_params(gpt.init_params(base, k), base))(
            jax.random.key(0)))
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    out = {"device": [dev.platform, dev.device_kind], "tree": os.getcwd()}
    for unroll in unrolls or [base.scan_unroll]:
        cfg = dataclasses.replace(base, scan_unroll=unroll)
        eng = object.__new__(InferenceEngine)     # the step, no thread
        eng.model, eng.config, eng._capture_logp = gpt, cfg, False
        eng.backend, eng._step_impls = jax.default_backend(), {}
        steps = {spec: eng._make_step_fn(False, spec)
                 for spec in (False, True)}
        chunk = engine["prefill_chunk"]
        for name, t in (("t1", 1), (f"t{chunk}", chunk),
                        (f"t{chunk}_masked", chunk), ("verify_t5", 5)):
            step = steps[name.startswith("verify")]
            shape = (cfg.n_layers, nb, bs,
                     kv_row_width(cfg.n_heads, cfg.head_dim))
            k, v = jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
            ctx = rng.integers(100, 350, lanes)
            tables = (np.arange(lanes)[:, None] * (nb // lanes)
                      + np.arange(mb)[None]) % nb
            valid = np.zeros((lanes, t), bool)
            valid[:, 0] = True
            positions = np.repeat((ctx - 1)[:, None], t, 1)
            gather = np.zeros(lanes, np.int32)
            if name.startswith("verify"):           # every lane, its draft
                valid[:], gather[:] = True, t - 1
                positions = ctx[:, None] - t + np.arange(t)[None]
            elif name.endswith("masked"):
                valid[:], ctx[:], positions[:] = False, 1, 0
            for lane in (0, 1) if t == chunk else ():
                # two lanes prefill, 14 decode
                valid[lane], ctx[lane], gather[lane] = True, t, t - 1
                positions[lane] = np.arange(t)
            host = (
                rng.integers(0, cfg.vocab_size, (lanes, t)).astype(np.int32),
                positions.astype(np.int32), valid, ctx.astype(np.int32),
                gather, np.zeros(lanes, np.float32),
                np.zeros(lanes, np.uint32), np.zeros(lanes, np.int32))
            args = [jnp.asarray(a) for a in host]
            args.insert(3, jnp.asarray(tables.astype(np.int32)))
            # the same population as the engine hands it over: one buffer
            buffer, views, _ = _lane_views(lanes, t, False, lanes)
            for view, a in zip(views, host):
                view[...] = a
            entry = eng._make_entry(t, False, name.startswith("verify"), 0)
            packed = (jnp.asarray(buffer), args[3])

            def run(fn, args, n, k, v):
                t0 = time.perf_counter()
                for _ in range(n):
                    tok, k, v = fn(params, k, v, *args)
                jax.block_until_ready((tok, k, v))
                return 1000 * (time.perf_counter() - t0) / n, k, v

            for fn, given, key in ((step, args, "ms"),
                                   (entry, packed, "entry_ms")):
                _, k, v = run(fn, given, 3, k, v)         # compile and warm
                ms = []
                for _ in range(3):
                    each, k, v = run(fn, given, 200 if t == 1 else 40, k, v)
                    ms.append(round(each, 4))
                out[f"unroll{unroll}_{name}_{key}"] = ms
            del k, v
            if t == 1 and "host_upload_ms" not in out:
                eng._uploads = dict.fromkeys(
                    ("populations", "transfers", "bytes"), 0)
                eng.cache = types.SimpleNamespace(    # tables unchanged
                    tables_on_device=True, block_tables=tables,
                    device_tables=lambda: args[3])
                population = (1, False, buffer, views, None)
                for key, upload in (
                        ("host_upload_ms", lambda: eng._upload(population)),
                        ("host_upload_eight_ms",
                         lambda: [jnp.asarray(a) for a in views])):
                    upload()
                    ms = []
                    for _ in range(3):
                        t0 = time.perf_counter()
                        for _ in range(200):
                            kept = upload()
                        ms.append(round(
                            (time.perf_counter() - t0) * 1000 / 200, 4))
                        jax.block_until_ready(kept)
                    out[key] = ms
    print(json.dumps(out))


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:]])
