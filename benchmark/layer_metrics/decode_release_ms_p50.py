"""Median `release_ms` of the window's `engine/step` records: letting go of
the retired steps' device arrays at the head of `engine/commit`."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.part_ms_p50(run, "release")
