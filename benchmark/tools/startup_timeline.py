#!/usr/bin/env python3
"""A run's set-up phase by phase and process by process, from the start-up
records the program keeps (`ray_tpu.state.startup_timeline()`, PERF.md
section 3): runs the cell as `benchmark/run.py` does, then prints every row
that began before the window opened, seconds from the benchmark process's
start, with the benchmark's own marks between them.

  python3 benchmark/tools/startup_timeline.py --run serve_gpt2xl_decode \
      --seed 7 [--trace 1] [--rehearse] [--seconds 10]

The rows also go to `benchmark/out/<cell>/startup.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest as manifest_mod  # noqa: E402
from benchmark import startup  # noqa: E402


def show(run: dict, out=sys.stdout) -> list:
    rows = startup.timeline(run) or []
    t0, t_open = startup.bounds(run)
    marks = [(a, b, "(benchmark's clock)") for a, b in
             startup.named_by_the_benchmark(run, rows)]
    lines = [(r["start"], r["dur"], f'{r["plane"]}/{r["kind"]}',
              f'{r["role"]} {r["pid"]}', r["payload"]) for r in rows
             if r["start"] < t_open]
    lines += [(a, b - a, what, "benchmark", None) for a, b, what in marks]
    print(f"set-up {t_open - t0:.2f} s; seconds from the benchmark "
          f"process's start", file=out)
    for start, dur, name, who, payload in sorted(lines, key=lambda x: x[0]):
        note = ""
        if payload:
            note = " ".join(
                f"{k}={round(v, 3) if isinstance(v, float) else v}"
                for k, v in payload.items())
        print(f"{start - t0:8.2f} {dur:8.2f}  {who:<16} {name:<32} "
              f"{note[:110]}", file=out)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run", required=True, help="the cell to run")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import run as run_mod
    manifest = manifest_mod.load(ROOT)
    traffic = manifest.load_traffic(manifest.cells[args.run]["traffic"])
    driver = manifest_mod.module("drivers", traffic["driver"])
    inner, kept = driver.run, {}

    def keeping(ctx, say):
        kept["run"] = inner(ctx, say)
        return kept["run"]

    driver.run = keeping
    argv = ["--workload", args.run, "--seed", str(args.seed),
            "--trace", str(args.trace)]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    if args.rehearse:
        argv.append("--rehearse")
    try:
        code = run_mod.main(argv)
    finally:
        driver.run = inner
    rows = show(kept["run"])
    with open(os.path.join(ROOT, "benchmark", "out", args.run,
                           "startup.json"), "w") as f:
        json.dump(rows, f, indent=1, default=repr)
    return code


if __name__ == "__main__":
    sys.exit(main())
