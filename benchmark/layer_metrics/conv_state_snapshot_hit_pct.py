"""Share of the window's admissions that adopted a snapshot of the conv
layers' tails behind their prompt's sealed head (and so convolved only their
own question): `stats()["state"]["snapshots_adopted"]` over `admitted`, both
read at the window's two ends.  Nothing where the program's state part
counts under another key."""

from __future__ import annotations

from benchmark import ssm_flops


def read(run: dict):
    adopted = ssm_flops.delta(run, "state", "snapshots_adopted")
    s0, s1 = run.get("stats0") or {}, run.get("stats1") or {}
    if adopted is None or "admitted" not in s0 or "admitted" not in s1:
        return None
    admitted = s1["admitted"] - s0["admitted"]
    return 100.0 * adopted / admitted if admitted > 0 else None
