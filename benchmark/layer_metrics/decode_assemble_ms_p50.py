"""Median `assemble_ms` of the window's `engine/step` records: the host
arrays of a population, the per-lane loop, `ensure_capacity` and the host-side
counters, summed over an iteration's populations."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.part_ms_p50(run, "assemble")
