"""Share of device busy time in the traced slice that the one-token update
of the recurrent states takes: the kernel `ssm_update`."""

from __future__ import annotations

from benchmark import ssm_flops


def read(run: dict):
    t = run.get("trace") or {}
    kernel = ssm_flops.kernel(run, "ssm_update")
    if not kernel or not t.get("busy_s"):
        return None
    return 100.0 * kernel["seconds"] / t["busy_s"]
