"""Median `deliver_ms` of the window's `engine/step` records: `_commit` for
every retired population (tokens to their streams, blocks sealed, lanes
freed) and the `notify`."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.part_ms_p50(run, "deliver")
