"""Rows a window layer's T=1 step read over what a full read of the same
contexts would, inside the window: `stats()["paged"]` (`rows_window` a
window layer over `ctx_tokens`), read at the window's two ends.  100 while
contexts are no longer than sliding_window; 100 x 2,048 / context past
it."""

from __future__ import annotations

from benchmark import window_flops


def read(run: dict):
    per = window_flops.rows_per_step(run)
    return 100.0 * per[2] / per[0] if per and per[0] else None
