"""Device trace time of the kernels named `loss_head_grads` (the loss head's
two gradient products, dx and dhead, from one read of a chunk's float32
logits: `ray_tpu/ops/cross_entropy.py`) against the least time the chip
could take for TWO products of a step's rows with the head, `[T, V] x [V, D]`
and `[V, T] x [T, D]` at `T = batch x seq` a chip, bf16 operands: the larger
of `4 T D V` FLOPs over peak and `4 T V + 2 T D + 2 D V + 4 D V` bytes over
bandwidth (the float32 logits read once, x, the head, the float32 dhead
written once), times the traced steps.  Two products a step however they
are chunked, so every re-read and re-written dhead tile counts as time, not
as work.  A program with no such kernel (a parent of the PR that brought
it) reads nothing."""

from __future__ import annotations


def read(run: dict):
    from benchmark import flops, manifest
    t = run.get("trace")
    kernel = (t or {}).get("kernels", {}).get("loss_head_grads")
    if not kernel or not kernel["seconds"]:
        return None
    f, traffic = run["fields"], run["traffic"]
    rows = traffic["batch"] // run["device"]["count"] * traffic["seq"]
    d, v = f["d_model"], f["vocab_size"]
    least = flops.roofline_s(
        4.0 * rows * d * v,
        4.0 * rows * v + 2.0 * rows * d + 2.0 * d * v + 4.0 * d * v,
        manifest.peaks(run["device"]["kind"]))[0]
    return 100.0 * least * traffic["trace_steps"] / kernel["seconds"]
