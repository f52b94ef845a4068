#!/usr/bin/env python3
"""Host time the engine's step accounting adds to one scheduler step, with
the profiler stopped (ISSUE 23's budget: 50 us).

Runs the bookkeeping of one single-population `InferenceEngine.step` with
empty bodies, as the step did it before (two `with lock:`, one
`engine/step` record of three fields) and as it does now (five
`spans.phase`, two `ExitStack`s around the lock, the counters, one record of
nine fields), and prints the median difference per step.  No device is
touched; jax is imported because `spans.phase` only annotates where it is.

Usage: python3 scripts/step_phase_overhead.py [steps]
"""

from __future__ import annotations

import contextlib
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402,F401  (spans.phase annotates only where jax is loaded)

from ray_tpu.util import events, spans  # noqa: E402

PHASES = ("admit", "build_batch", "dispatch", "fetch", "commit")


def before(lock, work):
    with lock:
        events.record("engine", "step", decode=8, prefill=0, waiting=3)
    with work:
        work.notify()


def after(lock, work, totals):
    took = dict.fromkeys(PHASES, 0.0)
    with contextlib.ExitStack() as locked:
        with spans.phase("engine", "admit") as ph:
            locked.enter_context(lock)
        t_start = ph.t0
        took["admit"] = ph.seconds
        with spans.phase("engine", "build_batch") as ph:
            pass
        took["build_batch"] = ph.seconds
    with spans.phase("engine", "dispatch") as ph:
        pass
    took["dispatch"] += ph.seconds
    with spans.phase("engine", "fetch") as ph:
        pass
    took["fetch"] += ph.seconds
    with contextlib.ExitStack() as locked:
        with spans.phase("engine", "commit") as ph:
            locked.enter_context(work)
            work.notify()
        took["commit"] = ph.seconds
        wall = ph.t0 + ph.seconds - t_start
        totals["steps"] += 1
        totals["wall"] += wall
        for name in PHASES:
            totals[name] += took[name]
        events.record(
            "engine", "step", decode=8, prefill=0, waiting=3,
            wall_ms=wall * 1e3, admit_ms=took["admit"] * 1e3,
            build_ms=took["build_batch"] * 1e3,
            dispatch_ms=took["dispatch"] * 1e3, fetch_ms=took["fetch"] * 1e3,
            commit_ms=took["commit"] * 1e3)


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 20_000
    lock = threading.Lock()
    work = threading.Condition(lock)
    totals = dict.fromkeys(PHASES + ("steps", "wall"), 0.0)
    rounds = {"before": [], "after": []}
    for _ in range(7):
        for name, fn, args in (("before", before, (lock, work)),
                               ("after", after, (lock, work, totals))):
            t0 = time.perf_counter()
            for _ in range(n):
                fn(*args)
            rounds[name].append((time.perf_counter() - t0) / n * 1e6)
    b, a = (statistics.median(rounds[k]) for k in ("before", "after"))
    print(json.dumps({"steps_per_round": n, "rounds": 7,
                      "before_us_per_step": b, "after_us_per_step": a,
                      "added_us_per_step": a - b,
                      "after_us_min_max": [min(rounds["after"]),
                                           max(rounds["after"])]}))


if __name__ == "__main__":
    main()
