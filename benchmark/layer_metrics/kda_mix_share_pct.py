"""Share of device busy time in the traced slice that the KDA layers'
recurrence takes: the kernels `kda_update` and `kda_scan` together (the
convolutions, norms and gates around them are XLA's own fusions, which the
trace's reader cannot tell from the other layers': a lower reading than the
scope `kda_mix`'s whole)."""

from __future__ import annotations

from benchmark import ssm_flops


def read(run: dict):
    t = run.get("trace") or {}
    if not t.get("busy_s") or "kda_heads" not in run["fields"]:
        return None
    seconds = sum(k["seconds"] for k in (
        ssm_flops.kernel(run, name) for name in ("kda_update", "kda_scan"))
        if k)
    return 100.0 * seconds / t["busy_s"] if seconds else None
