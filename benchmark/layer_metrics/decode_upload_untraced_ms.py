"""Mean `upload` milliseconds an iteration (the copy of the block tables,
`cache.device_tables()`, and eight `jnp.asarray` uploads a population) over
the seconds of the window's timeline that the profiler's session did not
touch: the part as the scored run has it.  `decode_upload_ms_p50` is the
median of the ring's records, most of which a traced run writes inside the
profiler's stop."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.part_untraced_ms(run, "upload")
