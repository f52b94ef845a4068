"""Rows a full layer's T=1 step chose over the context tokens it scored,
inside the window: `stats()["sparse"]` (`rows_chosen` over `ctx_tokens`),
read at the window's two ends.  100 while contexts are no longer than
index_topk."""

from __future__ import annotations

from benchmark import sparse_flops


def read(run: dict):
    per = sparse_flops.per_step(run)
    return 100.0 * per[1] / per[0] if per and per[0] else None
