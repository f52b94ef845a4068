"""Snapshots of a recurrent state adopted by admissions a second, inside the
window: `stats()["ssm"]` (`snapshots_adopted`), read at the window's two
ends, over its seconds."""

from __future__ import annotations

from benchmark import ssm_flops


def read(run: dict):
    adopted = ssm_flops.delta(run, "ssm", "snapshots_adopted")
    return None if adopted is None else adopted / run["seconds"]
