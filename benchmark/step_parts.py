"""The host's side of an engine iteration, from the two records the program
keeps of it (PERF.md section 3, PR 35): the parts of `build_batch` and
`commit` in every `engine/step` ring record, and `stats()["timeline"]`, the
loop's sums by the wall-clock second, which `stats1` holds for the whole
window in traced and untraced runs alike.

A traced run's window has three segments: before the profiler's session,
inside it (its start and its stop included) and after.  The records' medians
are taken over all of the window that the ring still held, which in a traced
run is mostly the stretch in which the profiler's stop slows every call into
the runtime (PERF.md section 5, PR 35); the timeline's "untraced rows" are
the seconds of the window that the session did not touch, which is how the
scored run (`--trace 0`) spends all of its seconds: the parts' means over
those rows (`part_untraced_ms`) are what a lever is judged by.
Every function returns None, and never raises, on a program that keeps no
such record (the parent of PR 35) or a run without the marks it needs.
"""

from __future__ import annotations

import os

from benchmark import idle_phases, metrics, trace_reduce
from benchmark.layer_metrics.decode_step_ms_p50 import steps

UPLOAD = "engine.build_batch/upload"
PREFIX = "engine."              # the nested parts' annotations
MIN_CLOCKED = 32                # iterations with a CPU reading, to say a share
_cache: dict = {}


def part_ms_p50(run: dict, part: str):
    """Median `<part>_ms` of the window's `engine/step` records."""
    values = [p[part + "_ms"] for p in steps(run) if part + "_ms" in p]
    return metrics.percentile(values, 50) if values else None


def rows(stats: dict) -> list:
    """The timeline of one `stats()` as a list of dicts, a row a second,
    `phase_s` and `part_s` by name; [] where the program keeps none."""
    timeline = (stats or {}).get("timeline")
    if not timeline:
        return []
    out = []
    for raw in timeline["rows"]:
        row = dict(zip(timeline["columns"], raw))
        row["phase_s"] = dict(zip(timeline["phases"], row["phase_s"]))
        row["part_s"] = dict(zip(timeline["parts"], row["part_s"]))
        out.append(row)
    return out


def session(base: float, marks: dict, trace: dict | None):
    """(start, end) of the seconds the profiler's session touched, widened
    by one second either way, or None where the run has no such marks.  The
    `trace_on` mark is set when the profiler's start has returned, so the
    start itself began at the instant the traffic file plans (`at_s`) where
    that is earlier."""
    if "trace_on" not in marks or "trace_off" not in marks:
        return None
    on = marks["trace_on"]
    if trace and "at_s" in trace:
        on = min(on, base + trace["at_s"])
    return on - 1.0, marks["trace_off"] + 1.0


def split_rows(timeline: list, base: float, seconds: float, touched) -> dict:
    """The rows that lie inside the window [base, base + seconds) (a row
    `t` holds the iterations that ended in the second [t, t + 1)), as
    {"before", "inside", "after"} of the seconds `touched` (`session`); all
    "before" where that is None.  A row's iterations reach back before `t`
    by as much as its longest one lasted, so a row is "after" only if that
    one began after the session too (an iteration of 1.7 s that the
    profiler's stop held ends a second or two behind it)."""
    out = {"before": [], "inside": [], "after": []}
    for row in timeline:
        t = row["t"]
        if t < base or t + 1 > base + seconds:
            continue
        if touched is None or t + 1 <= touched[0]:
            out["before"].append(row)
        elif t - row["longest_ms"] / 1e3 >= touched[1]:
            out["after"].append(row)
        else:
            out["inside"].append(row)
    return out


def untraced_rows(run: dict):
    """The window's rows of `stats1` that do not touch the profiler's
    session; every row of the window in a run without marks; None where the
    program keeps no timeline."""
    timeline = rows(run.get("stats1"))
    if not timeline:
        return None
    parts = split_rows(
        timeline, run["base"], run["seconds"],
        session(run["base"], run.get("marks") or {},
                (run.get("traffic") or {}).get("trace")))
    return parts["before"] + parts["after"]


def sums(timeline: list) -> dict:
    """What the per-layer metrics need of a set of rows, summed."""
    return {
        "seconds": len(timeline),
        "steps": sum(r["steps"] for r in timeline),
        "prefill_steps": sum(r["prefill_steps"] for r in timeline),
        "wall_s": sum(r["wall_s"] for r in timeline),
        "fetch_s": sum(r["phase_s"]["fetch"] for r in timeline),
        "cpu_s": sum(r["cpu_s"] for r in timeline),
        "cpu_wall_s": sum(r["cpu_wall_s"] for r in timeline),
        "cpu_steps": sum(r["cpu_steps"] for r in timeline),
        "gc_s": sum(r["gc_s"] for r in timeline),
        "phase_s": {p: sum(r["phase_s"][p] for r in timeline)
                    for p in (timeline[0]["phase_s"] if timeline else ())},
        "part_s": {p: sum(r["part_s"][p] for r in timeline)
                   for p in (timeline[0]["part_s"] if timeline else ())},
        "longest_ms": max((r["longest_ms"] for r in timeline), default=0.0),
    }


def _untraced(run: dict):
    timeline = untraced_rows(run)
    if not timeline:
        return None
    s = sums(timeline)
    return s if s["steps"] and s["wall_s"] > 0 else None


def offcpu_pct(s: dict, least: int = MIN_CLOCKED):
    """100 x (wall - CPU) / wall over the `cpu_steps` iterations of `sums`
    that read the thread's CPU clock (one in four, drawn: `cpu_s` beside
    `cpu_wall_s`, both over admit, build_batch, dispatch and commit); None
    where fewer than `least` did: an iteration waits for the interpreter
    lock for 5 ms or not at all, so a few of them are no share.  As read: a
    value outside 0-100 is a fault of the clocks and shows as one."""
    if s is None or s["cpu_steps"] < max(least, 1) or not s["cpu_wall_s"] > 0:
        return None
    return 100.0 * (s["cpu_wall_s"] - s["cpu_s"]) / s["cpu_wall_s"]


def host_offcpu_pct(run: dict):
    """Share of the four host phases' wall time in which the engine thread
    held no core (waiting for the interpreter lock or a mutex, or asleep in
    a runtime call), over the untraced rows."""
    return offcpu_pct(_untraced(run))


def host_untraced_ms(run: dict):
    """The host's work an iteration as the scored run has it: mean of
    (`wall_s` - fetch) / `steps` over the untraced rows."""
    s = _untraced(run)
    return None if s is None else 1e3 * (s["wall_s"] - s["fetch_s"]) \
        / s["steps"]


def part_untraced_ms(run: dict, part: str):
    """Mean `<part>` seconds an iteration over the untraced rows, in ms:
    the part as the scored run has it, where the records' median in a
    traced run is taken inside the profiler's stop."""
    s = _untraced(run)
    return None if s is None else 1e3 * s["part_s"][part] / s["steps"]


def fetch_wait_pct(run: dict):
    """Share of the loop's time in which the host waits for the device."""
    s = _untraced(run)
    return None if s is None else 100.0 * s["fetch_s"] / s["wall_s"]


def longest_step_ms(run: dict):
    s = _untraced(run)
    return None if s is None else s["longest_ms"]


def gc_ms_per_s(run: dict):
    """Collector pauses inside iterations, a second of the untraced rows."""
    s = _untraced(run)
    return None if s is None else 1e3 * s["gc_s"] / s["seconds"]


def idle_by_part(trace: dict):
    """`idle_phases.split` over the nested parts' annotations
    (`engine.<phase>/<part>`) in place of the flat phases': the same gaps of
    device 0, laid over names that no accepted reader sees.  None where the
    trace has no device operation or no such annotation."""
    host = [(s, e, "engine/" + name, thread)
            for s, e, name, thread in trace.get("host", [])
            if name.startswith(PREFIX)]
    parts = idle_phases.split({"devices": trace.get("devices"),
                               "host": host})
    if parts is not None:
        parts["by_phase"] = {name[len("engine/"):]: ns
                             for name, ns in parts["by_phase"].items()}
    return parts


def idle_upload_pct(run: dict):
    """Percent of the traced slice in which device 0 idled while the engine
    thread handed a population's arrays to the device."""
    try:
        trace_dir = os.path.join(idle_phases.HERE, "out",
                                 run["cell"]["name"], "trace")
        if trace_dir not in _cache:
            _cache[trace_dir] = idle_by_part(
                trace_reduce.load(trace_reduce.find(trace_dir)))
        parts = _cache[trace_dir]
    except (KeyError, OSError):      # no cell name, no trace directory
        return None
    if parts is None:
        return None
    return 100.0 * parts["by_phase"].get(UPLOAD, 0) / parts["window_ns"]
