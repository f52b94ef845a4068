"""Device trace time of the kernels named `window_flash_attention` (the flash
kernels called with a window: forward, and dq with dk/dv) against the least
the chip could take for the window layers' attention in the traced steps:
`swa_moe_train_flops.window_flash` a window layer and step at the per-chip
batch, the larger of FLOPs over peak and bytes over bandwidth, the window's
pairs counted and no others.  A forward recomputed under remat and the
masked part of a tile the window's edge crosses count as time, not as work.
Counted from `fields` and the traffic file.  A program with no such kernel
reads nothing."""

from __future__ import annotations


def read(run: dict):
    from benchmark import flops, manifest, swa_moe_train_flops as counts
    t = run.get("trace")
    kernel = (t or {}).get("kernels", {}).get("window_flash_attention")
    if not kernel or not kernel["seconds"]:
        return None
    f, traffic = run["fields"], run["traffic"]
    least = flops.roofline_s(*counts.window_flash(
        traffic["batch"] // run["device"]["count"], f["n_heads"],
        traffic["seq"], f["head_dim"], f["sliding_window"]),
        manifest.peaks(run["device"]["kind"]))[0]
    return (100.0 * least * counts.layer_kinds(f)[0] * traffic["trace_steps"]
            / kernel["seconds"])
