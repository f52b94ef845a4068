#!/usr/bin/env python3
"""Run one cell of the benchmark once.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cell's entry in BENCHMARK.json names a configuration and a traffic mix;
the traffic file names the driver (`benchmark/drivers/<driver>.py`) that
takes the system through the entry point a user calls.  This process never
starts a jax backend: the chip belongs to the worker or replica that the
program's own runtime leases it to.  No chip, or fewer than the cell asks
for, is a failure, never a fall-back to the CPU.

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics with --trace 0, its
per-layer metrics with --trace 1), `device` and, traced, `breakdown`.
Everything else a run is worth goes on earlier lines (`[bench] ...`) and
into `benchmark/out/<cell>/`.

`--rehearse` is the harness's rehearsal flag (PERF.md section 7): the same
path at nano size on the CPU with faked chips.  Its last line says
`"rehearsal": true` and names the platform it ran on, so it can never be
taken for a measurement.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse
import ctypes
import json
import os
import shutil
import signal
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest as manifest_mod  # noqa: E402


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def adopt_orphans() -> None:
    """Make this process the one that orphans of its process tree fall to
    (Linux `PR_SET_CHILD_SUBREAPER`), not init: the runtime's daemons start
    workers of their own, and a worker that outlives its daemon would
    otherwise be nobody's to wait for."""
    ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)


def end_children(grace_s: float = 10.0) -> int:
    """Wait until every child of this process has ended, killing what is
    still alive after `grace_s`; returns how many it had to kill."""
    me, killed, t0 = str(os.getpid()), 0, time.time()
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return killed                     # no child is left
        if pid:
            continue
        if time.time() - t0 > grace_s:
            for entry in os.listdir("/proc"):
                try:
                    with open(f"/proc/{entry}/stat") as f:
                        parent = f.read().rsplit(")", 1)[1].split()[1]
                    if entry.isdigit() and parent == me:
                        os.kill(int(entry), signal.SIGKILL)
                        killed += 1
                except (OSError, IndexError):
                    pass                      # not a process, or gone
            t0 = time.time()
        time.sleep(0.05)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    manifest = manifest_mod.load(ROOT)
    if args.workload not in manifest.cells:
        raise SystemExit(f"unknown workload {args.workload!r}; the manifest "
                         f"has {sorted(manifest.cells)}")
    cell = manifest.cells[args.workload]
    config = manifest.load_config(cell["config"])
    traffic = manifest.load_traffic(cell["traffic"])
    if args.rehearse:
        traffic.update(traffic.get("rehearsal", {}))
    seconds = (args.seconds if args.seconds is not None
               else float(manifest.data["run_seconds"]))

    # Fail before anything starts where the machine lacks the chips.  The
    # count reads device nodes and opens none (the worker must find them
    # free); the worker checks again what jax itself reports.
    from ray_tpu._private import accelerators, compile_cache
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    elif accelerators.count_local_chips() < cell["chips"]:
        raise SystemExit(
            f"{args.workload} needs {cell['chips']} TPU chip(s); this "
            f"machine has {accelerators.count_local_chips()}")
    cache_dir = compile_cache.place()
    # Keep every program in the persistent cache, not only those that took
    # a second to compile: each run is a new process tree and pays set-up.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

    out_dir = os.path.join(HERE, "out", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    ctx = {
        "cell": cell, "config": config, "traffic": traffic,
        "fields": manifest_mod.fields(config, args.rehearse),
        "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
        "rehearse": args.rehearse, "out_dir": out_dir,
        "t_process_start": T_PROCESS_START,
    }
    say(f"cell {args.workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, {cell['chips']} chip(s), seed {args.seed}, "
        f"{seconds:g} s, trace {args.trace}, compile cache {cache_dir} "
        f"({compile_cache.entry_count(cache_dir)} entries)")
    driver = manifest_mod.module("drivers", traffic["driver"])
    adopt_orphans()
    try:
        run = driver.run(ctx, say)
    finally:
        killed = end_children()
        if killed:
            say(f"{killed} process(es) outlived the runtime's shutdown and "
                f"were killed")

    device = run["device"]
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] != cell["chips"]):
        raise SystemExit(f"the cell ran on {device}, not on "
                         f"{cell['chips']} TPU chip(s)")
    if args.trace:
        names = manifest.metrics_of(args.workload, "per_layer")
        values = {}
        for name in names:
            reader = manifest_mod.module("layer_metrics", name)
            run.pop("not_measured", None)
            try:
                value = reader.read(run)
            except KeyError as e:
                if not args.rehearse:
                    raise
                say(f"rehearsal: {name}: {e}")  # the CPU has no peaks row
                value = None
            if value is None:
                why = run.get("not_measured")   # `readers.not_measured`
                say(f"per-layer metric {name}: not measured"
                    + (f" ({why})" if why else ""))
            else:
                values[name] = value
        units = manifest.per_layer
    else:
        names = manifest.metrics_of(args.workload, "end_to_end")
        values = {n: run["end_to_end"][n] for n in names}
        units = manifest.end_to_end
    line = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": {n: {"value": float(v), "unit": units[n]["unit"]}
                    for n, v in values.items()},
        "device": device,
    }
    if args.trace and run.get("breakdown"):
        line["breakdown"] = run["breakdown"]
    if args.rehearse:
        line["rehearsal"] = True
    # What `correct` compared, each number beside its limit: the line's last
    # key and standard error's last lines, which is all the driver's record
    # keeps of a run that was not correct.
    line["compared"] = run["compared"]
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump({"line": line, "notes": run.get("notes", {})}, f, indent=1)
    say(f"entries in the compile cache now: "
        f"{compile_cache.entry_count(cache_dir)}; wall "
        f"{time.time() - T_PROCESS_START:.1f} s")
    for name, c in line["compared"].items():
        print(f"[bench] compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
