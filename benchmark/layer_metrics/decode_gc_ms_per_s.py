"""Collector pauses that fell inside iterations of the engine's loop,
milliseconds a second, over the timeline's untraced rows (`gc_s`, from the
engine's `gc.callbacks` hook)."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.gc_ms_per_s(run)
