"""Seconds the benchmark's clock saw around the calls that compile the cell's
programs (the train step's lower-and-compile; the serve warm-up request that
first runs the T=chunk and T=1 steps).  With a warm persistent cache this is
the time to load them."""

from __future__ import annotations


def read(run: dict):
    return run.get("compile_s")
