"""Device trace time of the `sparse_latent_decode_attention` kernel in the
traced slice against the least the chip could take for its calls: each call
is one full layer's single-query latent attention over the rows the T=1
steps CHOSE (`stats()["sparse"]`: `rows_chosen`, the window's average per
step; at most index_topk a lane, never the context), the larger of its FLOPs
over peak and its bytes over bandwidth (`sparse_flops.latent_rows`).  A
kernel that read the whole context under a mask would read some 13% here."""

from __future__ import annotations

from benchmark import flops, manifest, sparse_flops


def read(run: dict):
    kernel = sparse_flops.kernel(run, "sparse_latent_decode_attention")
    per = sparse_flops.per_step(run)
    if not kernel or per is None:
        return None
    f = run["fields"]
    least, _ = flops.roofline_s(*sparse_flops.latent_rows(
        per[1], run["traffic"]["engine"]["max_lanes"],
        sparse_flops.sizes(f, sparse_flops.FULL)),
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
