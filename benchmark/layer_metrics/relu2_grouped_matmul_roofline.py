"""Device trace time of the `moe_grouped_matmul` kernel in the traced slice,
where an expert is two matrices and a squared ReLU between them, against the
least the chip could take: per expert layer and step an up and a down
multiply of the PUBLISHED matrices (d_model x d_expert and back, whatever
padding or tiling implements them) over the assignments that fell on held
experts and the held experts hit (`stats()["moe"]`, the window's average
per (layer, step) pair), each the larger of FLOPs over peak and bytes over
bandwidth (`hybrid_moe_flops.relu2_layer_s`).  There are half as many
pairs as calls of the kernel; a prefill chunk's pairs are among them on
both sides (the least of the average pair is at most the average of the
pairs' leasts, so the share reads low, never high)."""

from __future__ import annotations

from benchmark import hybrid_moe_flops, latent_flops, manifest, ssm_flops


def read(run: dict):
    kernel = ssm_flops.kernel(run, "moe_grouped_matmul")
    load = latent_flops.held_load(run)
    if not kernel or load is None or "d_shared" not in run["fields"]:
        return None
    held, hit, pairs = load
    least = hybrid_moe_flops.relu2_layer_s(
        held / pairs, hit / pairs, run["fields"],
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * (kernel["calls"] / 2) / kernel["seconds"]
