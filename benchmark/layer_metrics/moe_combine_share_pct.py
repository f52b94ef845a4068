"""The kernels named `moe_combine` (the experts' sorted rows taken back to
their tokens over the sort's runs, forward and dx: `ray_tpu/ops/moe.py`)
over device busy time in the traced train steps: what the dispatch's way
back costs of a step.  A program without the kernel (a parent of the PR
that brought it, whose rows went back by a gather XLA made) reads
nothing."""

from __future__ import annotations


def read(run: dict):
    t = run.get("trace")
    kernel = (t or {}).get("kernels", {}).get("moe_combine")
    if not kernel or not kernel["seconds"] or not t["busy_s"]:
        return None
    return 100.0 * kernel["seconds"] / t["busy_s"]
