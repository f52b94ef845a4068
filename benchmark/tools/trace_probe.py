#!/usr/bin/env python3
"""Look at one device trace by hand (on-chip-measurement guide, section 6).

Traces two gpt2-small train steps at a batch of 2 and a dozen engine steps,
in this process, with no cluster around them, prints which planes are
devices, which lines they carry and how the operations are named, and leaves
the `.xplane.pb` files under `chiprun_out/probe/`.  The recorded trace under
`benchmark/testdata/` was made by this script.  It is a tool for whoever
changes `benchmark/trace_reduce.py`; no cell runs it.

Usage: python3 benchmark/tools/trace_probe.py     (needs a TPU)
"""

from __future__ import annotations

import collections
import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def describe(path: str, top: int = 45) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    print(f"== {path} ({os.path.getsize(path)} bytes)")
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"-- plane {plane.name!r}: {len(lines)} line(s); stats "
              f"{[(k, str(v)[:40]) for k, v in list(plane.stats)[:12]]}")
        for line in lines:
            events = list(line.events)
            print(f"   line {line.name!r}: {len(events)} event(s)")
            if not events or not plane.name.startswith("/device:"):
                continue
            total = collections.Counter()
            count = collections.Counter()
            for ev in events:
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
            for name, ns in total.most_common(top):
                print(f"      {ns / 1e6:10.3f} ms  x{count[name]:<5d} {name[:110]}")
            ev = events[len(events) // 2]
            print(f"      sample event: start_ns={ev.start_ns} "
                  f"dur_ns={ev.duration_ns} stats="
                  f"{[(k, str(v)[:60]) for k, v in list(ev.stats)[:16]]}")


def main() -> None:
    import jax
    import optax

    from ray_tpu.models import gpt

    dev = jax.devices()
    print("devices", [(d.platform, d.device_kind, d.id) for d in dev])
    if dev[0].platform != "tpu":
        raise SystemExit("trace_probe needs a TPU")
    print("memory_stats", dev[0].memory_stats())
    out = os.path.join(ROOT, "chiprun_out", "probe")
    os.makedirs(out, exist_ok=True)

    cfg = gpt.CONFIGS["gpt2-small"]
    init_state, train_step = gpt.make_train_step(cfg, optax.adamw(1e-4), None)
    state = init_state(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 1024), 0,
                                          cfg.vocab_size)}
    step = jax.jit(train_step, donate_argnums=0)
    state, m = step(state, batch)
    jax.block_until_ready(m)
    tdir = os.path.join(out, "train")
    jax.profiler.start_trace(tdir)
    for i in range(2):
        with jax.profiler.StepTraceAnnotation("train_step", step_num=i):
            state, m = step(state, batch)
    jax.block_until_ready(m)
    jax.profiler.stop_trace()
    del state

    from ray_tpu.inference import InferenceEngine
    import numpy as np
    eng = InferenceEngine("gpt", cfg, max_lanes=8, auto_start=False)
    rng = np.random.default_rng(0)
    for n in (40, 70, 100, 130):
        eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(), 64)
    for _ in range(12):
        eng.step()
    eng.submit(rng.integers(0, cfg.vocab_size, 90).tolist(), 64)
    edir = os.path.join(out, "engine")
    jax.profiler.start_trace(edir)
    for _ in range(12):
        with jax.profiler.TraceAnnotation("engine_step"):
            eng.step()
    jax.profiler.stop_trace()
    eng.shutdown()

    for name, d in (("train", tdir), ("engine", edir)):
        for path in glob.glob(os.path.join(d, "plugins", "profile", "*",
                                           "*.xplane.pb")):
            keep = os.path.join(out, f"{name}.xplane.pb")
            shutil.copy(path, keep)
            describe(keep)
        shutil.rmtree(d)


if __name__ == "__main__":
    main()
