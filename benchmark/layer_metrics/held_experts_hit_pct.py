"""Share of the experts held here that a step reads, inside the window:
the engine's device-side counters (`stats()["moe"]` of a share, read at the
window's two ends), held experts that took at least one assignment summed
over (layer, step) pairs, over those pairs times the experts held."""

from __future__ import annotations

from benchmark import latent_flops


def read(run: dict):
    load = latent_flops.held_load(run)
    if load is None:
        return None
    _, hit, pairs = load
    return 100.0 * hit / (pairs * run["fields"]["n_experts_held"])
