"""Blocks of the window layers' pool given back in mid-sequence a second,
inside the window: `stats()["windows"]` (`blocks_freed`), read at the
window's two ends, over its seconds."""

from __future__ import annotations

from benchmark import sparse_flops


def read(run: dict):
    w = sparse_flops.window(run, "windows")
    if w is None:
        return None
    return (w[0]["blocks_freed"] - w[1]["blocks_freed"]) / run["seconds"]
