"""From a profiler trace (`.xplane.pb`) to device numbers.

What a v5e trace looks like (seen with `tools/trace_probe.py`, PERF.md
section 3): one plane `/device:TPU:<n>` per chip; on it the line `XLA Ops`
holds every operation the core ran, one after another (their durations add
up to those of the line `XLA Modules`), named by its HLO text
(`%fusion.12 = bf16[...] fusion(...)`), and the line `Async XLA Ops` holds
copies and collectives in flight beside them.  A Mosaic kernel is an
operation whose text has `custom_call_target="tpu_custom_call"`; its HLO
name is the kernel's (`%flash_attention.7`) or, where the program gave it
none, `%closed_call.3`.  Host threads are lines of the plane `/host:CPU`, on
the same clock.

Busy time is the union of the `XLA Ops` intervals of a device; the window is
from the first to the last device operation of the trace, over all devices.
`tests/test_trace_reduce.py` checks this file on the recorded trace in
`testdata/`.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_HEAD = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:[.\d]*)? = \(?(\w+\[[\d,]*\])?")


def describe(name: str) -> tuple:
    """(base, label, is_kernel, is_collective) of one operation's HLO text.
    `base` is the instruction's name without its number (`fusion`,
    `flash_attention`, `all-gather-start`); `label` adds the first output
    shape, which tells the many `fusion`s apart."""
    m = _HEAD.match(name)
    if m is None:
        base, shape = name.split(" ")[0].lstrip("%"), None
    else:
        base, shape = m.group(1), m.group(2)
    kernel = KERNEL_MARK in name
    label = base + (" " + shape if shape else "") + (" (kernel)" if kernel
                                                     else "")
    return base, label, kernel, bool(COLLECTIVE.match(base))


def union(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def gaps(intervals, lo, hi) -> list:
    """The uncovered stretches of [lo, hi], as (start, end)."""
    out, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            out.append((reach, min(start, hi)))
        reach = max(reach, end)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return out


def self_times(ops) -> list:
    """(name, self_ns) of every operation: its duration less that of the
    operations nested in it.  A layer scan is one `while` operation whose
    interval holds the operations of its body; without this the loop would
    be counted once as itself and once more as its contents."""
    out, stack = [], []        # stack of [end, index into out]
    for start, end, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(end, stack[-1][0]) - start
        out.append([name, end - start])
        stack.append([end, len(out) - 1])
    return [(name, max(0, ns)) for name, ns in out]


def load(path: str) -> dict:
    """The trace as plain lists: per device plane its operations
    (start_ns, end_ns, name) by line, and the host's events
    (start_ns, end_ns, name, thread)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                             line.name) for e in line.events)
    return {"devices": devices, "host": host}


def _label_gap(host, start, end, window_ns) -> str:
    """The host event that covers most of an idle gap, among those that are
    not a thread's whole life (longer than half the window)."""
    best, best_cover = "no host span", 0.0
    for h0, h1, name, thread in host:
        if h1 - h0 > window_ns / 2:
            continue
        cover = min(h1, end) - max(h0, start)
        if cover > best_cover:
            best, best_cover = f"{name} [{thread.split('/')[0]}]", cover
    return best[:120]


def reduce(trace: dict, top: int = 10) -> dict:
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace has no /device:TPU plane")
    spans = [(s, e) for lines in devices.values()
             for s, e, _ in lines.get(OPS_LINE) or lines.get(MODULES_LINE, [])]
    if not spans:
        raise ValueError("no operation ran on the device in this trace")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    window_ns = hi - lo
    cache: dict = {}
    per_device, labels, kernels = {}, {}, {}
    coll_exposed = coll_total = 0.0
    for plane, lines in sorted(devices.items()):
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE, [])
        busy = union((s, e) for s, e, _ in ops)
        per_device[plane] = {"busy_s": busy / 1e9, "ops": len(ops)}
        def described(name):
            d = cache.get(name)
            if d is None:
                d = cache[name] = describe(name)
            return d

        coll = [(s, e) for line in (OPS_LINE, ASYNC_LINE)
                for s, e, name in lines.get(line, []) if described(name)[3]]
        coll_total += union(coll)
        for name, ns in self_times(lines.get(OPS_LINE, [])):
            base, label, kernel, collective = described(name)
            labels[label] = labels.get(label, 0.0) + ns
            if collective:
                coll_exposed += ns
            if kernel:
                k = kernels.setdefault(base, {"calls": 0, "seconds": 0.0})
                k["calls"] += 1
                k["seconds"] += ns / 1e9
    n = len(devices)
    busy_s = sum(d["busy_s"] for d in per_device.values()) / n
    first = sorted(devices)[0]
    first_ops = devices[first].get(OPS_LINE) or devices[first].get(
        MODULES_LINE, [])
    by_label: dict = {}
    for s, e in gaps([(s, e) for s, e, _ in first_ops], lo, hi):
        if e - s < 2_000:          # under 2 us: between two operations
            label = "between operations (< 2 us each)"
        else:
            label = _label_gap(trace["host"], s, e, window_ns)
        by_label[label] = by_label.get(label, 0.0) + (e - s)
    ops_table = [[label, ns / 1e9 / n] for label, ns in sorted(
        labels.items(), key=lambda kv: -kv[1])[:60]]
    return {
        "window_s": window_ns / 1e9, "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / (window_ns / 1e9)),
        "devices": n, "per_device": per_device,
        # seconds per device (kernels and collectives summed over devices,
        # then divided by their number)
        "kernels": {b: {"calls": k["calls"] / n, "seconds": k["seconds"] / n}
                    for b, k in kernels.items()},
        "kernel_s": sum(k["seconds"] for k in kernels.values()) / n,
        "collective_s": coll_total / 1e9 / n,
        "collective_exposed_s": coll_exposed / 1e9 / n,
        "breakdown": {
            "device_ops": ops_table[:top],
            "idle_gaps": [[label, ns / 1e9] for label, ns in sorted(
                by_label.items(), key=lambda kv: -kv[1])[:top]],
        },
        "ops_table": ops_table,
    }


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str) -> dict:
    return reduce(load(find(trace_dir)))


def attach(run: dict, trace_dir: str, ctx: dict, say) -> None:
    """Reduce the trace under `trace_dir` into the run's record: the table
    the per-layer readers use, `busy_s` and `window_s` for the last line's
    `device`, and the `breakdown`."""
    try:
        reduced = reduce_dir(trace_dir)
    except ValueError as e:
        if not ctx["rehearse"]:
            raise
        say(f"rehearsal: {e} (a CPU trace has no device plane)")
        return
    run["trace"] = reduced
    run["device"]["busy_s"] = reduced["busy_s"]
    run["device"]["window_s"] = reduced["window_s"]
    run["breakdown"] = reduced["breakdown"]
    write_tables(reduced, ctx["out_dir"], say)


def write_tables(reduced: dict, out_dir: str, say) -> None:
    """The whole reduction into `out_dir/trace.json`, its head on earlier
    lines of the run's output."""
    with open(os.path.join(out_dir, "trace.json"), "w") as f:
        json.dump(reduced, f, indent=1)
    kernels = reduced["kernels"]
    say(f"trace: window {reduced['window_s']:.4f} s, busy "
        f"{reduced['busy_s']:.4f} s per device over {reduced['devices']} "
        f"device(s), idle {reduced['idle_pct']:.2f}%; kernels "
        f"{reduced['kernel_s']:.4f} s "
        f"{ {b: round(k['seconds'], 4) for b, k in kernels.items()} }"
        f"; collectives {reduced['collective_s']:.4f} s of which on the "
        f"core's own line {reduced['collective_exposed_s']:.4f} s")
    for label, s in reduced["ops_table"][:12]:
        say(f"  op {s:9.5f} s  {label}")
    for label, s in reduced["breakdown"]["idle_gaps"][:6]:
        say(f"  idle {s:9.5f} s  {label}")
