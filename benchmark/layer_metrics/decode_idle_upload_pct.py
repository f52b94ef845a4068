"""Share of the traced slice in which device 0 idled under the engine's
`engine.build_batch/upload` annotation: the gaps of `idle_phases.split`, laid
over the nested parts' names (0.0 where the uploads ran under a device
program; None for a program that writes no such annotation)."""

from benchmark import step_parts


def read(run: dict):
    return step_parts.idle_upload_pct(run)
