"""Mean share of the engine's lanes that decode, over the `engine/step` events
the program records inside the window (lanes that are empty, or still
prefilling, produce no token in that step)."""

from __future__ import annotations


def read(run: dict):
    lanes = run["traffic"]["engine"]["max_lanes"]
    lo, hi = run["base"], run["base"] + run["seconds"]
    steps = [e["payload"]["decode"] for e in run.get("engine_events", [])
             if e["kind"] == "step" and lo <= e.get("ts_adj", e["ts"]) < hi]
    return 100.0 * sum(steps) / (len(steps) * lanes) if steps else None
