"""Rows attended over context tokens held, in the T=1 steps inside the
window: `stats()["eva"]` (`rows_attended` over `ctx_tokens`).  100 where no
window was ever closed; an eighth and less behind many."""

from __future__ import annotations

from benchmark import eva_flops


def read(run: dict):
    c = eva_flops.counters(run)
    if c is None or c["ctx_tokens"] <= 0:
        return None
    return 100.0 * c["rows_attended"] / c["ctx_tokens"]
