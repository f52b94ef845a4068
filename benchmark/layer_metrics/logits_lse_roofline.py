"""Device trace time of the kernels named `logits_lse` (the loss head's logits
product, which carries its own logsumexp: `ray_tpu/ops/cross_entropy.py`)
against the least time the chip could take for ONE product of a step's rows
with the head, `[T, D] x [D, V]` at `T = batch x seq` a chip, bf16 operands
and a float32 result: the larger of `2 T D V` FLOPs over peak and
`4 T V + 2 T D + 2 D V` bytes over bandwidth, times the traced steps.  One
product a step whatever implements it and however it is chunked, so a
recomputed product and every re-read of the head count as time, not as
work.  A program with no such kernel (a parent of the PR that brought it)
reads nothing."""

from __future__ import annotations


def read(run: dict):
    from benchmark import flops, manifest
    t = run.get("trace")
    kernel = (t or {}).get("kernels", {}).get("logits_lse")
    if not kernel or not kernel["seconds"]:
        return None
    f, traffic = run["fields"], run["traffic"]
    rows = traffic["batch"] // run["device"]["count"] * traffic["seq"]
    d, v = f["d_model"], f["vocab_size"]
    least = flops.roofline_s(
        2.0 * rows * d * v, 4.0 * rows * v + 2.0 * rows * d + 2.0 * d * v,
        manifest.peaks(run["device"]["kind"]))[0]
    return 100.0 * least * traffic["trace_steps"] / kernel["seconds"]
