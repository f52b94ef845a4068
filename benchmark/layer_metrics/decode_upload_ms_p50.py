"""Median `upload_ms` of the window's `engine/step` records: the copy of the
block tables (`cache.device_tables()`) and the eight `jnp.asarray` uploads a
population (a compact program's `rows` stay under `dispatch`)."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.part_ms_p50(run, "upload")
