"""Step programs the engine dispatched for each iteration of its loop that
dispatched any, inside the window: `stats()["programs"]` (`programs` over
`iterations`; PR 53), read at the window's two ends.  1 where every
admission rides the decoding lanes' step; a program from before the counter
has none and reads nothing."""

from __future__ import annotations

from benchmark import latent_flops


def read(run: dict):
    w = latent_flops.window(run, "programs")
    if w is None or "iterations" not in w[0] or "iterations" not in w[1]:
        return None
    iterations = w[0]["iterations"] - w[1]["iterations"]
    return (w[0]["programs"] - w[1]["programs"]) / iterations \
        if iterations > 0 else None
