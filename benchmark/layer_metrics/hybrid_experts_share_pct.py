"""Share of device busy time in the traced slice that the experts' grouped
multiplies take: the kernel `moe_grouped_matmul`."""

from __future__ import annotations

from benchmark import ssm_flops


def read(run: dict):
    t = run.get("trace") or {}
    kernel = ssm_flops.kernel(run, "moe_grouped_matmul")
    if not kernel or not t.get("busy_s"):
        return None
    return 100.0 * kernel["seconds"] / t["busy_s"]
