"""Device trace time of the `window_latent_decode_attention` kernel in the
traced slice against the least the chip could take for its calls: each call
is one window layer's single-query latent attention over the rows of the
lanes' windows (`stats()["sparse"]`: `window_rows`, the window's average per
step; at most sliding_window a lane), the larger of its FLOPs over peak and
its bytes over bandwidth (`sparse_flops.latent_rows` at the window layers'
sizes)."""

from __future__ import annotations

from benchmark import flops, manifest, sparse_flops


def read(run: dict):
    kernel = sparse_flops.kernel(run, "window_latent_decode_attention")
    per = sparse_flops.per_step(run)
    if not kernel or per is None:
        return None
    f = run["fields"]
    least, _ = flops.roofline_s(*sparse_flops.latent_rows(
        per[2], run["traffic"]["engine"]["max_lanes"],
        sparse_flops.sizes(f, sparse_flops.WINDOW)),
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
