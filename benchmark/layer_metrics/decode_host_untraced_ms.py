"""The host's work an iteration as the scored run has it: (`wall_s` - fetch)
over `steps`, from the timeline's rows of `stats1` that the profiler's session
did not touch (`decode_host_ms_p50` is the median over a traced window)."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.host_untraced_ms(run)
