"""Seconds the step programs' first calls took beyond making them: the
`engine.dispatch/make_program` spans less their trace, lower, load and
compile seconds (the first execution, and the heap's tidying behind it)."""

from benchmark import startup


def read(run: dict):
    return startup.first_runs_s(run)
