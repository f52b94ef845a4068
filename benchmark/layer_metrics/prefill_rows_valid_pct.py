"""Share of the rows the T=prefill_chunk programs computed inside the window
that held a prompt token: `stats()["prefill"]` (`rows_valid` over `rows`),
read at the window's two ends."""

from __future__ import annotations

from benchmark import latent_flops


def read(run: dict):
    w = latent_flops.window(run, "prefill")
    if w is None:
        return None
    rows = w[0]["rows"] - w[1]["rows"]
    return 100.0 * (w[0]["rows_valid"] - w[1]["rows_valid"]) / rows \
        if rows > 0 else None
