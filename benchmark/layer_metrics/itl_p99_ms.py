"""99th percentile of every gap between two consecutive output tokens of one
request at the client: the stall one long prompt imposes on the rest of the
batch."""

from __future__ import annotations


def read(run: dict):
    from benchmark import metrics
    gaps = metrics.token_gaps_ms(run.get("counted", []))
    return metrics.percentile(gaps, 99) if gaps else None
