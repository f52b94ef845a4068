"""Seconds jax spent tracing and lowering programs in the process that holds
the chip, up to the window's open: `trace_s + lower_s` of
`compile_cache.counters()` (a serve cell's `stats0["compile"]`; the train
worker's `proc/compile` rows that end before the window opens).  Paid
whether or not the persistent cache then answers."""

from benchmark import startup


def read(run: dict):
    return startup.trace_lower_s(run)
