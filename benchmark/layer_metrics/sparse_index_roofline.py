"""Device trace time of the `sparse_index_scores` kernel (the T=1 steps'
index scoring) in the traced slice against the least the chip could take for
its calls: each call is one full layer's scores over the context the T=1
steps scored (`stats()["sparse"]`, the window's average per step), the
larger of its FLOPs over peak and its bytes over bandwidth
(`sparse_flops.index_scores`)."""

from __future__ import annotations

from benchmark import flops, manifest, sparse_flops


def read(run: dict):
    kernel = sparse_flops.kernel(run, "sparse_index_scores")
    per = sparse_flops.per_step(run)
    if not kernel or per is None:
        return None
    least, _ = flops.roofline_s(*sparse_flops.index_scores(
        per[0], run["traffic"]["engine"]["max_lanes"], run["fields"]),
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
