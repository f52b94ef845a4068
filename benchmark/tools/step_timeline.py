#!/usr/bin/env python3
"""A run's window second by second, from the timeline the engine keeps
(`stats()["timeline"]`, PERF.md section 3) as `benchmark/run.py` left it in
`benchmark/out/<cell>/result.json` (`notes.stats0`, `notes.stats1`), traced
or not.

  python3 benchmark/tools/step_timeline.py --cell serve_gpt2xl_decode
  python3 benchmark/tools/step_timeline.py --result path/to/result.json \
      --cell serve_gpt2xl_decode
  python3 benchmark/tools/step_timeline.py --run serve_gpt2xl_decode \
      --seed 7 --trace 1        # runs the cell first, keeping its records

`result.json` keeps no marks, so the window opens with the second after
`stats0`'s newest row (`stats0` is fetched at the window's first instant)
and the profiler's session is where the cell's traffic file plans it
(`trace.at_s`, `trace.slice_s`), widened by a second either way; what its
stop took beyond that shows in the rows themselves.  `--run` runs the cell
as `benchmark/run.py` does and keeps, beside `result.json`, what that file
leaves out (`steps.json`: the marks and the `engine/step` records the p50
readers saw), which gives the session's true seconds, the share of the
window's iterations those records cover, how much of `build_ms` and
`commit_ms` their parts name, and the window's longest iterations one by one
(phase, part, `gc_ms`, and `cpu_ms` of `cpu_wall_ms` where the iteration read
the thread's CPU clock).
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
from benchmark import metrics, step_parts  # noqa: E402


LONGEST = 8             # iterations listed one by one from the records
PHASE_FIELDS = ("admit_ms", "build_ms", "dispatch_ms", "fetch_ms",
                "commit_ms")
PART_FIELDS = ("windows_ms", "assemble_ms", "upload_ms", "release_ms",
               "lock_ms", "deliver_ms")


def run_and_keep(cell: str, seed: int, seconds, trace: int) -> None:
    """`benchmark/run.py` on `cell`, with the driver's result looked at on
    its way through: marks and step records go to `steps.json`."""
    from benchmark import run as run_mod
    manifest = manifest_mod.load(ROOT)
    traffic = manifest.load_traffic(manifest.cells[cell]["traffic"])
    driver = manifest_mod.module("drivers", traffic["driver"])
    inner = driver.run

    def keeping(ctx, say):
        run = inner(ctx, say)
        lo, hi = run["base"], run["base"] + run["seconds"]
        records = [dict(e["payload"], ts=e.get("ts_adj", e["ts"]))
                   for e in run.get("engine_events", [])
                   if e["kind"] == "step"]
        with open(os.path.join(ctx["out_dir"], "steps.json"), "w") as f:
            json.dump({"base": run["base"], "seconds": run["seconds"],
                       "marks": {k: v for k, v in run["marks"].items()
                                 if isinstance(v, float)},
                       "records": [r for r in records if lo <= r["ts"] < hi],
                       "records_fetched": len(records)}, f)
        return run

    driver.run = keeping
    argv = ["--workload", cell, "--seed", str(seed), "--trace", str(trace)]
    if seconds is not None:
        argv += ["--seconds", str(seconds)]
    run_mod.main(argv)


def describe(rows: list) -> dict:
    """One line's worth of a set of rows: iterations, their period, the
    host's work, its parts, the off-CPU share, collector time, the longest
    iteration."""
    s = step_parts.sums(rows)
    if not s["steps"]:
        return {"seconds": s["seconds"], "steps": 0}
    per = 1e3 / s["steps"]
    out = {
        "seconds": s["seconds"], "steps": s["steps"],
        "prefill_steps": s["prefill_steps"],
        "period_ms": s["wall_s"] * per,
        "host_ms": (s["wall_s"] - s["fetch_s"]) * per,
        "fetch_ms": s["fetch_s"] * per,
        "fetch_wait_pct": 100.0 * s["fetch_s"] / s["wall_s"],
        "offcpu_pct": step_parts.offcpu_pct(s, least=1),
        "clocked": s["cpu_steps"],
        "gc_ms_per_s": 1e3 * s["gc_s"] / s["seconds"],
        "longest_ms": s["longest_ms"],
        "phase_ms": {p: v * per for p, v in s["phase_s"].items()},
        "part_ms": {p: v * per for p, v in s["part_s"].items()},
    }
    out["largest_part"] = max(out["part_ms"], key=out["part_ms"].get)
    return out


def line(label: str, d: dict, longest_phase: str = "") -> str:
    if not d["steps"]:
        return f"{label:>12}  no iteration"
    return (f"{label:>12} {d['steps']:6d} {d['prefill_steps']:4d} "
            f"{d['period_ms']:8.3f} {d['host_ms']:8.3f} {d['fetch_ms']:8.3f} "
            f"{d['largest_part']:>9} {d['part_ms'][d['largest_part']]:7.3f} "
            f"{-1 if d['offcpu_pct'] is None else d['offcpu_pct']:7.2f} "
            f"{d['clocked']:5d} {d['gc_ms_per_s']:8.3f} "
            f"{d['longest_ms']:9.2f} {longest_phase}")


HEADER = (f"{'second':>12} {'steps':>6} {'pf':>4} {'period':>8} {'host':>8} "
          f"{'fetch':>8} {'largest':>9} {'part ms':>7} {'offcpu%':>7} "
          f"{'of':>5} {'gc ms/s':>8} {'longest':>9} its phase   (ms an "
          f"iteration; offcpu% of so many iterations that read the CPU "
          f"clock, -1 of none)")


def report(result: dict, cell: str, seconds: float, steps_file, say) -> dict:
    notes = result.get("notes", {})
    rows0 = step_parts.rows(notes.get("stats0"))
    rows1 = step_parts.rows(notes.get("stats1"))
    if not rows0 or not rows1:
        say("no timeline in notes.stats0 / notes.stats1: the program that "
            "made this result keeps none")
        return {}
    manifest = manifest_mod.load(ROOT)
    trace = manifest.load_traffic(manifest.cells[cell]["traffic"])["trace"]
    traced = "breakdown" in result.get("line", {})
    kept = None
    if steps_file and os.path.exists(steps_file):
        with open(steps_file) as f:
            kept = json.load(f)
    if kept:
        base, seconds, marks = kept["base"], kept["seconds"], kept["marks"]
    else:
        base = rows0[-1]["t"] + 1.0
        marks = ({"trace_on": base + trace["at_s"],
                  "trace_off": base + trace["at_s"] + trace["slice_s"]}
                 if traced else {})
    touched = step_parts.session(base, marks, trace)
    parts = step_parts.split_rows(rows1, base, seconds, touched)
    say(f"cell {cell}: window opens at {base:.2f}, {seconds:g} s, "
        + (f"profiler's session (widened) {touched[0] - base:.2f} .. "
           f"{touched[1] - base:.2f} s into it"
           + ("" if kept else " as the traffic file plans it")
           if touched else "no profiler session"))
    say(HEADER)
    shown = sorted((r["t"], r) for seg in parts.values() for r in seg)
    for t, r in shown:
        mark = "*" if r in parts["inside"] else " "
        say(line(f"{t - base:+.1f}{mark}", describe([r]), r["longest_phase"]))
    out = {}
    say("segments (`*` rows are inside):")
    say(HEADER)
    for name in ("before", "inside", "after"):
        out[name] = describe(parts[name])
        say(line(name, out[name]))
    out["untraced"] = describe(parts["before"] + parts["after"])
    say(line("untraced", out["untraced"]))
    out["window"] = describe([r for _, r in shown])
    say(line("window", out["window"]))
    for name in ("before", "inside", "after", "untraced"):
        d = out[name]
        if d["steps"]:
            say(f"{name}: phases ms an iteration "
                + ", ".join(f"{p} {v:.3f}" for p, v in d["phase_ms"].items())
                + "; parts " + ", ".join(
                    f"{p} {v:.3f}" for p, v in d["part_ms"].items()))
    s0, s1 = notes["stats0"], notes["stats1"]
    ran = s1["steps"] - s0["steps"]
    say(f"stats1 - stats0: {ran} iterations, step_wall_s "
        f"{s1['step_wall_s'] - s0['step_wall_s']:.3f}, cpu_s "
        f"{s1['cpu_s'] - s0['cpu_s']:.3f} of cpu_wall_s "
        f"{s1['cpu_wall_s'] - s0['cpu_wall_s']:.3f} in "
        f"{s1['cpu_steps'] - s0['cpu_steps']} clocked iterations, gc "
        f"{[b - a for a, b in zip(s0['gc']['collections'], s1['gc']['collections'])]}"
        f" collections in {s1['gc']['seconds'] - s0['gc']['seconds']:.3f} s "
        f"(full {s1['gc']['full_seconds'] - s0['gc']['full_seconds']:.3f})")
    if kept:
        records = kept["records"]
        out["records"] = len(records)
        out["coverage_pct"] = 100.0 * len(records) / ran if ran else 0.0
        say(f"engine/step records the p50 readers saw: {len(records)} in "
            f"the window of {kept['records_fetched']} fetched = "
            f"{out['coverage_pct']:.1f}% of the window's {ran} iterations")
        if records:
            for seg, (lo, hi) in {
                    "before": (base, touched[0] if touched else base + seconds),
                    "inside": touched or (0, 0),
                    "after": (touched[1] if touched else 0, base + seconds),
            }.items():
                mine = [r for r in records if lo <= r["ts"] < hi]
                if mine:
                    say(f"records {seg}: {len(mine)}, wall_ms p50 "
                        f"{metrics.percentile([r['wall_ms'] for r in mine], 50):.3f}"
                        f", host (wall - fetch) p50 "
                        f"{metrics.percentile([r['wall_ms'] - r['fetch_ms'] for r in mine], 50):.3f}")
            for field in ("build_ms", "commit_ms"):
                names = (("windows", "assemble", "upload")
                         if field == "build_ms"
                         else ("release", "lock", "deliver"))
                share = [sum(r[n + "_ms"] for n in names) / r[field]
                         for r in records if r.get(field) and "upload_ms" in r]
                if share:
                    say(f"parts of {field}: their sum over it, p50 "
                        f"{100 * metrics.percentile(share, 50):.2f}%, p5 "
                        f"{100 * metrics.percentile(share, 5):.2f}%")
            say("the longest iterations the records hold:")
            for r in sorted(records, key=lambda r: -r["wall_ms"])[:LONGEST]:
                phase = max(PHASE_FIELDS, key=lambda f: r.get(f, 0.0))
                part = max(PART_FIELDS, key=lambda f: r.get(f, 0.0))
                say(f"  {r['ts'] - base:+8.2f} s  wall {r['wall_ms']:9.2f} ms"
                    f"  {phase} {r.get(phase, 0.0):.2f}"
                    f"  {part} {r.get(part, 0.0):.2f}"
                    f"  gc_ms {r.get('gc_ms', 0.0):.2f}"
                    + (f"  cpu_ms {r['cpu_ms']:.2f} of cpu_wall_ms "
                       f"{r['cpu_wall_ms']:.2f}" if "cpu_ms" in r else ""))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell")
    ap.add_argument("--result")
    ap.add_argument("--run", help="run this cell first, keeping its records")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--json", help="write the segments' numbers here")
    args = ap.parse_args(argv)
    cell = args.run or args.cell
    if not cell:
        ap.error("one of --cell and --run")
    if args.run:
        run_and_keep(cell, args.seed, args.seconds, args.trace)
    out_dir = os.path.join(ROOT, "benchmark", "out", cell)
    path = args.result or os.path.join(out_dir, "result.json")
    with open(path) as f:
        result = json.load(f)
    seconds = args.seconds if args.seconds is not None else float(
        manifest_mod.load(ROOT).data["run_seconds"])
    out = report(result, cell, seconds,
                 os.path.join(os.path.dirname(path), "steps.json"),
                 lambda m: print(f"[timeline] {m}", flush=True))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
