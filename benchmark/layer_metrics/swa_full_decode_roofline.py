"""Device trace time of the `paged_decode_attention` kernel in the traced
slice (the FULL layers' calls alone: the window layers' kernel has a name
of its own) against the least the chip could take for them: each call is
one full layer's single-query attention of the query heads over the
key/value heads' cached rows of the context the slice's own T=1 steps
attended over (`ssm_flops.slice_context`, the mean of the program's
`engine/step` records of the slice; the client's records,
`metrics.slice_mean`, where no record carries the count)."""

from __future__ import annotations

from benchmark import manifest, ssm_flops, window_flops


def read(run: dict):
    kernel = ssm_flops.kernel(run, window_flops.FULL_KERNEL)
    context = ssm_flops.slice_context(run)
    if not kernel or context is None \
            or not ssm_flops.kernel(run, window_flops.WINDOW_KERNEL):
        return None
    least = window_flops.attention_s(
        context, run["traffic"]["engine"]["max_lanes"], run["fields"],
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
