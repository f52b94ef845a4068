"""Share of device busy time in the traced slice that scoring, choosing and
the two attentions take together: the kernels `sparse_index_scores`,
`sparse_latent_decode_attention`, `window_latent_decode_attention` and their
chunk forms, and the choice (`sparse_flops.select_seconds`).  The gather of
the chosen rows is XLA's and not among them."""

from __future__ import annotations

from benchmark import sparse_flops

KERNELS = ("sparse_index_scores", "sparse_latent_decode_attention",
           "window_latent_decode_attention", "sparse_index_chunk_scores",
           "sparse_latent_chunk_attention", "window_latent_chunk_attention")


def read(run: dict):
    t = run.get("trace") or {}
    kernels = t.get("kernels") or {}
    if not t.get("busy_s") or "sparse_index_scores" not in kernels:
        return None
    seconds = sum(kernels[k]["seconds"] for k in KERNELS if k in kernels)
    return 100.0 * (seconds + (sparse_flops.select_seconds(run) or 0.0)) \
        / t["busy_s"]
