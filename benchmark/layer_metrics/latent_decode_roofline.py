"""Device trace time of the `latent_decode_attention` kernel in the traced
slice against the least the chip could take for its calls: each call is one
layer's single-query latent attention over the context the T=1 steps
attended over (`stats()["latent"]`, the window's average per step), the
larger of its FLOPs over peak and its bytes over bandwidth
(`latent_flops.latent_decode`; at 121 FLOPs a byte it is bound by bytes on a
v5e, whose ridge is 240)."""

from __future__ import annotations

from benchmark import flops, latent_flops, manifest


def read(run: dict):
    t = run.get("trace") or {}
    kernel = (t.get("kernels") or {}).get("latent_decode_attention")
    ctx = latent_flops.ctx_tokens_per_step(run)
    if not kernel or not kernel["seconds"] or ctx is None:
        return None
    least, _ = flops.roofline_s(*latent_flops.latent_decode(
        ctx, run["traffic"]["engine"]["max_lanes"], run["fields"]),
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
