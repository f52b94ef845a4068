"""Device idle time of a serve trace, split by what the engine's thread was
doing: the gaps of device 0 laid over the program's own `engine/<phase>`
annotations (`ray_tpu.util.spans.phase`), which the jax profiler writes into
the `/host:CPU` plane on the clock of the device's operations.

The gaps are taken exactly as `trace_reduce.reduce` takes them (the window
from the first to the last device operation, the first device's `XLA Ops`
line, gaps of 2 us and more), so the shares here and the unattributed rest
add up to the cell's device idle share.  The phases are flat siblings in the
program; should two ever overlap, the shorter one takes the time.  A trace
with no device plane (a CPU rehearsal), no trace at all, or a program that
writes no `engine/` annotation gives None, never an exception.

What it cannot do (PERF.md sections 5 and 7, PR 23): on the v5e the device
plane of a profiler session leads its host plane by 0.3 to 1.6 ms, another
offset every session, so a device program appears to start before the
`engine/dispatch` that launches it.  The sum of the shares is exact; their
split is shifted by that lead, from dispatch and build_batch to fetch.
"""

from __future__ import annotations

import os

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
PREFIX = "engine/"
MIN_GAP_NS = 2_000          # as trace_reduce.reduce: shorter is between two ops
HOST_PHASES = ("engine/commit", "engine/admit", "engine/build_batch")
_cache: dict = {}


def attribute(gaps, phases) -> dict:
    """Nanoseconds of the (start, end) `gaps` that each phase name covers.
    `phases` are (start, end, name); where several cover an instant, the
    shortest one has it."""
    out: dict = {}
    phases = sorted(phases)
    for g0, g1 in gaps:
        over = [p for p in phases if p[0] < g1 and p[1] > g0]
        cuts = sorted({g0, g1, *(min(max(t, g0), g1)
                                 for p in over for t in p[:2])})
        for a, b in zip(cuts, cuts[1:]):
            cover = [p for p in over if p[0] <= a and p[1] >= b]
            if cover:
                name = min(cover, key=lambda p: p[1] - p[0])[2]
                out[name] = out.get(name, 0) + (b - a)
    return out


def split(trace: dict):
    """{window_ns, idle_ns, by_phase} of a loaded trace (`trace_reduce.load`),
    or None where it has no device operation or no `engine/` annotation."""
    devices = trace.get("devices") or {}
    ops = {plane: lines.get(trace_reduce.OPS_LINE)
           or lines.get(trace_reduce.MODULES_LINE, [])
           for plane, lines in devices.items()}
    spans = [(s, e) for line in ops.values() for s, e, _ in line]
    phases = [(s, e, name) for s, e, name, _ in trace.get("host", [])
              if name.startswith(PREFIX)]
    if not spans or not phases:
        return None
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    first = [(s, e) for s, e, _ in ops[sorted(ops)[0]]]
    gaps = [(s, e) for s, e in trace_reduce.gaps(first, lo, hi)
            if e - s >= MIN_GAP_NS]
    return {"window_ns": hi - lo, "idle_ns": sum(e - s for s, e in gaps),
            "by_phase": attribute(gaps, phases)}


def of_run(run: dict):
    """`split` of the trace this run recorded (read once a process)."""
    try:
        trace_dir = os.path.join(HERE, "out", run["cell"]["name"], "trace")
        if trace_dir not in _cache:
            _cache[trace_dir] = split(
                trace_reduce.load(trace_reduce.find(trace_dir)))
        return _cache[trace_dir]
    except (KeyError, OSError):      # no cell name, no trace directory
        return None


def share_pct(run: dict, names):
    """Percent of the traced slice in which device 0 was idle under one of
    the phases `names`."""
    s = of_run(run)
    if s is None:
        return None
    return 100.0 * sum(s["by_phase"].get(n, 0) for n in names) / s["window_ns"]


def attributed_pct(run: dict):
    """Percent of device 0's idle time (gaps of 2 us and more) that some
    `engine/` annotation covers."""
    s = of_run(run)
    if s is None or not s["idle_ns"]:
        return None
    return 100.0 * sum(s["by_phase"].values()) / s["idle_ns"]
