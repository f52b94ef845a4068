"""Share of a layer's experts that a step reads, inside the window: the
engine's device-side counters (`stats()["moe"]`, read at the window's two
ends), experts that took at least one assignment summed over (layer, step)
pairs, over those pairs times the experts a layer has."""

from __future__ import annotations

from benchmark import moe_flops


def read(run: dict):
    load = moe_flops.window_load(run)
    if load is None:
        return None
    _, _, hit, layer_steps = load
    return 100.0 * hit / (layer_steps * run["fields"]["n_experts"])
