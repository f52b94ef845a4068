"""Device trace time of the `ssm_update` kernel in the traced slice against
the least the chip could take for its calls: each call is one layer's
one-token update of the lanes a T=1 step stepped (`stats()["ssm"]`:
`tokens_updated` over the window's T=1 steps), the larger of its FLOPs over
peak and its bytes over bandwidth (`ssm_flops.update`: the float32 state
read and written once).  A kernel that rewrites the lanes nobody decodes in
reads lower by their share."""

from __future__ import annotations

from benchmark import flops, manifest, ssm_flops


def read(run: dict):
    kernel = ssm_flops.kernel(run, "ssm_update")
    lanes = ssm_flops.lanes_per_update(run)
    if not kernel or lanes is None:
        return None
    least, _ = flops.roofline_s(*ssm_flops.update(lanes, run["fields"]),
                                manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
