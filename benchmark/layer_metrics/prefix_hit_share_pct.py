"""Share of admitted prompt tokens served from the prefix cache inside the
window: the engine's own `stats()` counters, read at the window's two
ends."""

from __future__ import annotations


def read(run: dict):
    s0, s1 = run.get("stats0") or {}, run.get("stats1") or {}
    if "prefix_hit_tokens" not in s0 or "prefix_hit_tokens" not in s1:
        return None
    hit = s1["prefix_hit_tokens"] - s0["prefix_hit_tokens"]
    miss = s1["prefix_miss_tokens"] - s0["prefix_miss_tokens"]
    return 100.0 * hit / (hit + miss) if hit + miss else None
