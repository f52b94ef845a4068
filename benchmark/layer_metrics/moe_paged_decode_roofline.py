"""`paged_decode_roofline` for a model with other kernels beside it: device
trace time of the kernel named `paged_decode_attention` alone against the
least time for single-query attention over the context the live requests
held during the traced slice (`flops.paged_decode`; the context comes from
the client's own records)."""

from __future__ import annotations

from benchmark import flops, manifest, moe_flops


def read(run: dict):
    kernel = ((run.get("trace") or {}).get("kernels") or {}).get(
        "paged_decode_attention")
    context = moe_flops.slice_context(run)
    if not kernel or not kernel["seconds"] or not context:
        return None
    f = run["fields"]
    least, _ = flops.roofline_s(*flops.paged_decode(
        context, run["traffic"]["engine"]["max_lanes"],
        f.get("n_kv_heads", f["n_heads"]), f["d_model"] // f["n_heads"]),
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
