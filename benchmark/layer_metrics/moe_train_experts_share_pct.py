"""The grouped products of the experts (`moe_grouped_matmul`, forward and
dx, and `moe_grouped_matmul_dw`) over device busy time in the traced train
steps: what the experts' multiplies are of a step, their dispatch (sort and
gathers, plain XLA) left out."""

from __future__ import annotations


def read(run: dict):
    from benchmark.layer_metrics.moe_train_grouped_matmul_roofline import (
        seconds)
    t = run.get("trace")
    if not t or not t["busy_s"] or not seconds(run):
        return None
    return 100.0 * seconds(run) / t["busy_s"]
