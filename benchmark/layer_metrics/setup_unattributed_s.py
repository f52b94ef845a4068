"""Seconds of `setup_s` that nothing names: `setup_s` less the union, on the
wall clock, of every row of every process's start-up record and of what the
benchmark's own clock names (a serve cell's warm-up requests and lead-in;
the train worker's loop from its `ready` report to the window's open)."""

from benchmark import startup


def read(run: dict):
    return startup.unattributed_s(run)
