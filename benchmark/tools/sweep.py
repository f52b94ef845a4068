#!/usr/bin/env python3
"""Sizing sweeps: one cell at several settings of its traffic file, to find a
knee or a size once, on the chip.  Never the measured command: `run.py` has
no option for this.  Each point runs `run.py` in a process of its own with
the cell's traffic file as it is on disk plus that point's overrides (one
level deep: `{"clients": 24, "requests": {"fill_requests": 24}}`), and its
`[bench]` lines are printed under the point.

  python3 benchmark/tools/sweep.py --workload serve_gpt2xl_decode --seed 3 \
      --points '[{"clients": 16}, {"clients": 32}]' [--seconds 51]

Arguments it does not know (`--seconds`, `--trace`, `--rehearse`) go on to
`run.py`.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def one(point: dict, argv: list) -> int:
    """This process: the cell with `point` laid over its traffic file."""
    from benchmark import manifest, run
    load = manifest.Manifest.load_traffic

    def load_with_point(self, name):
        traffic = load(self, name)
        for key, value in point.items():
            if isinstance(value, dict) and isinstance(traffic.get(key), dict):
                traffic[key] = dict(traffic[key], **value)
            else:
                traffic[key] = value
        return traffic

    manifest.Manifest.load_traffic = load_with_point
    return run.main(argv)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--points", help="JSON list of override objects")
    ap.add_argument("--one", help="(internal) one point, as JSON")
    args, rest = ap.parse_known_args()
    argv = ["--workload", args.workload, "--seed", str(args.seed)] + rest
    if args.one:
        return one(json.loads(args.one), argv)
    worst = 0
    for point in json.loads(args.points):
        print(f"== sweep point {json.dumps(point)}", flush=True)
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one",
             json.dumps(point)] + argv, capture_output=True, text=True)
        for line in child.stdout.splitlines():
            if line.startswith(("[bench]", "{")):
                print(line, flush=True)
        if child.returncode:
            print(f"exit code {child.returncode}\n{child.stderr[-2000:]}",
                  flush=True)
            worst = child.returncode
    return worst


if __name__ == "__main__":
    sys.exit(main())
