"""Median per engine step of `wall_ms - fetch_ms`: the serial host work one
token costs (admit, build the batch, launch, commit) when the device is
free; `engine/fetch` is where the host waits for the device."""

from __future__ import annotations


def read(run: dict):
    from benchmark import metrics
    from benchmark.layer_metrics.decode_step_ms_p50 import steps
    host = [p["wall_ms"] - p["fetch_ms"] for p in steps(run)]
    return metrics.percentile(host, 50) if host else None
