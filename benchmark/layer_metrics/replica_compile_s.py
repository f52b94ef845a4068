"""Seconds the replica's process spent compiling programs and loading them
from the persistent cache, from its start to the window's open: `compile_s +
cache_load_s` of the engine's `stats()["compile"]` at the window's first
instant (`ray_tpu._private.compile_cache.counters`, fed by jax's own
monitoring events)."""

from __future__ import annotations


def read(run: dict):
    compiled = run.get("stats0", {}).get("compile")
    if not compiled:
        return None
    return compiled["compile_s"] + compiled["cache_load_s"]
