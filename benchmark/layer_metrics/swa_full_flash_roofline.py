"""Device trace time of the kernels named `flash_attention` (the full layers'
forward, dq and dk/dv; the window layers' calls carry another name) against
the least the chip could take for the full layers' attention in the traced
steps: `flops.flash_fwd` + `flops.flash_bwd` a full layer and step at the
per-chip batch over `n_heads` heads of the configuration's own `head_dim`
(which here is not d_model / n_heads).  A forward recomputed under remat
counts as time, not as work.  Counted from `fields` and the traffic file.
A program with no such kernel reads nothing."""

from __future__ import annotations


def read(run: dict):
    from benchmark import flops, manifest, swa_moe_train_flops as counts
    t = run.get("trace")
    kernel = (t or {}).get("kernels", {}).get("flash_attention")
    if not kernel or not kernel["seconds"]:
        return None
    f, traffic = run["fields"], run["traffic"]
    least = flops.roofline_s(*counts.full_flash(
        traffic["batch"] // run["device"]["count"], f["n_heads"],
        traffic["seq"], f["head_dim"]),
        manifest.peaks(run["device"]["kind"]))[0]
    return (100.0 * least * counts.layer_kinds(f)[1] * traffic["trace_steps"]
            / kernel["seconds"])
