"""Share of the experts held here that a step reads, inside the window:
`held_experts_hit_pct`'s reading (the engine's device-side counters,
`stats()["moe"]` of a share, at the window's two ends: held experts that took
at least one assignment summed over (layer, step) pairs, over those pairs
times the experts held), under this cell's name."""

from benchmark.layer_metrics.held_experts_hit_pct import read  # noqa: F401
