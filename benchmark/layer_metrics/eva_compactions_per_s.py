"""Windows closed a second inside the window: `stats()["eva"]`
(`compactions`), read at the window's two ends, over its seconds."""

from __future__ import annotations

from benchmark import eva_flops


def read(run: dict):
    c = eva_flops.counters(run)
    if c is None:
        return None
    return c["compactions"] / run["seconds"]
