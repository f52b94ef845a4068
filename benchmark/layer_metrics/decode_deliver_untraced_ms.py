"""Mean `deliver` milliseconds an iteration (`_commit` for every retired
population and the `notify`) over the seconds of the window's timeline that
the profiler's session did not touch: the part
as the scored run has it.  `decode_deliver_ms_p50` is the median of the ring's
records, most of which a traced run writes inside the profiler's stop."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.part_untraced_ms(run, "deliver")
