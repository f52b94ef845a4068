"""Device trace time of the `paged_decode_attention` kernel in the traced
slice against the least the chip could take for its calls: each call is one
layer's single-query attention over the ROWS the live lanes held during the
slice (the client's own records through `eva_flops.rows`, averaged over
the slice: the summaries of a lane's closed windows and the exact rows of
its open one),
the larger of its FLOPs over peak and its bytes over bandwidth (at one FLOP
a byte it is bound by bytes)."""

from __future__ import annotations

from benchmark import eva_flops, flops, manifest


def read(run: dict):
    t = run.get("trace") or {}
    kernel = (t.get("kernels") or {}).get("paged_decode_attention")
    if not kernel or not kernel["seconds"] \
            or "window_size" not in run["fields"]:
        return None
    total = eva_flops.slice_rows(run)
    if not total:
        return None
    least, _ = flops.roofline_s(*eva_flops.decode_attention(
        total, run["traffic"]["engine"]["max_lanes"], run["fields"]),
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
