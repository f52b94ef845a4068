"""`paged_decode_roofline` for grouped-query attention beside a state-space
mixer: device trace time of the `paged_decode_attention` kernel against the
least time for single-query attention of 20 query heads over the 4
key/value heads' cached rows of the context the slice's own T=1 steps
attended over (`ssm_flops.paged_decode`; the context is the mean of the
program's `engine/step` records of the slice, `ssm_flops.slice_context`,
the head's size the configuration's own `head_dim`)."""

from __future__ import annotations

from benchmark import flops, manifest, ssm_flops


def read(run: dict):
    kernel = ssm_flops.kernel(run, "paged_decode_attention")
    context = ssm_flops.slice_context(run)
    if not kernel or context is None:
        return None
    least, _ = flops.roofline_s(*ssm_flops.paged_decode(
        context, run["traffic"]["engine"]["max_lanes"], run["fields"]),
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
