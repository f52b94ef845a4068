"""Device trace, four-chip cell: time the core's own operation line spends in
all-gather, all-reduce, reduce-scatter (and their -start/-done halves),
during which it computes nothing, over the traced slice.  Collectives in
flight on the asynchronous line beside compute are hidden and not counted."""

from __future__ import annotations


def read(run: dict):
    t = run.get("trace")
    return 100.0 * t["collective_exposed_s"] / t["window_s"] if t else None
