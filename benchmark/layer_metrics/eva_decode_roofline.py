"""Device trace time of the `paged_decode_attention` kernel in the traced
slice against the least the chip could take for its calls: each call is one
layer's single-query attention over the ROWS the live lanes held during the
slice (the client's own records through `eva_flops.rows`, averaged over
the slice: the summaries of a lane's closed windows and the exact rows of
its open one),
the larger of its FLOPs over peak and its bytes over bandwidth (at one FLOP
a byte it is bound by bytes)."""

from __future__ import annotations

from benchmark import eva_flops, flops, manifest, readers


def read(run: dict):
    t = run.get("trace") or {}
    kernel = (t.get("kernels") or {}).get("paged_decode_attention")
    if "window_size" not in run["fields"]:
        return None
    if not kernel or not kernel["seconds"]:
        return readers.not_measured(run, "no trace, or no kernel time: no "
                                         "paged_decode_attention call in it")
    total = eva_flops.slice_rows(run)
    if total is None:
        return readers.not_measured(run, readers.NO_SESSION)
    if not total:
        return readers.not_measured(run, "no rows in the slice: no request "
                                         "held a token during it")
    least, _ = flops.roofline_s(*eva_flops.decode_attention(
        total, run["traffic"]["engine"]["max_lanes"], run["fields"]),
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
