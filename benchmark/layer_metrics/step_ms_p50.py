"""Median of the worker's host clock around single steps that each end in
`block_until_ready` (traced run only: the measured window never waits per
step)."""

from __future__ import annotations


def read(run: dict):
    from benchmark import metrics
    return metrics.percentile(run["step_ms"], 50) if run.get("step_ms") \
        else None
