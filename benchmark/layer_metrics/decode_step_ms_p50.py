"""Median wall time of one engine step inside the window, from the
`engine/step` records the program writes at the end of each step (`wall_ms`:
first phase's start to last phase's end, on the engine's `perf_counter`)."""

from __future__ import annotations


def steps(run: dict) -> list:
    """Payloads of the window's `engine/step` records that carry durations
    (a program from before them writes the record without)."""
    lo, hi = run["base"], run["base"] + run["seconds"]
    return [e["payload"] for e in run.get("engine_events", [])
            if e["kind"] == "step" and lo <= e.get("ts_adj", e["ts"]) < hi
            and "wall_ms" in (e.get("payload") or {})]


def read(run: dict):
    from benchmark import metrics
    walls = [p["wall_ms"] for p in steps(run)]
    return metrics.percentile(walls, 50) if walls else None
