"""How uneven the routing was over the experts held here, inside the
window: the busiest held expert's assignments over the mean held expert's
(1.0 = perfectly even), from the engine's device-side counters
(`stats()["moe"]["expert_load"]`, summed over layers and steps, read at
the window's two ends)."""

from __future__ import annotations

from benchmark import latent_flops


def read(run: dict):
    w = latent_flops.window(run, "moe")
    if w is None:
        return None
    load = [b - a for a, b in zip(w[1]["expert_load"], w[0]["expert_load"])]
    return max(load) * len(load) / sum(load) if sum(load) > 0 else None
