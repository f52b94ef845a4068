"""How uneven the routing was over the window: the busiest expert's
assignments over the mean expert's (1.0 = perfectly even), from the engine's
device-side counters summed over layers and steps."""

from __future__ import annotations

from benchmark import moe_flops


def read(run: dict):
    load = moe_flops.window_load(run)
    if load is None or not load[0]:
        return None
    assignments, per_expert, _, _ = load
    return max(per_expert) * len(per_expert) / assignments
