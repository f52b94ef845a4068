"""The longest iteration of the window's untraced seconds (`longest_ms` of
the timeline's rows): what a stall, a collection or an admission cost at
worst."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.longest_step_ms(run)
