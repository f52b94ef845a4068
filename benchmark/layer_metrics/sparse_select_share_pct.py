"""The choice's share of device busy time in the traced slice: the exact
top-k of the index scores (`sparse_flops.select_seconds`: on a TPU one
stable sort of every row's scores with their positions), of the T=1 steps
and of the chunks' rows alike."""

from __future__ import annotations

from benchmark import sparse_flops


def read(run: dict):
    t = run.get("trace") or {}
    seconds = sparse_flops.select_seconds(run)
    if not seconds or not t.get("busy_s"):
        return None
    return 100.0 * seconds / t["busy_s"]
