"""Device trace time of the `kda_update` kernel in the traced slice against
the least the chip could take for its calls: each call is one KDA layer's
one-token update of the lanes a T=1 population stepped (`stats()["ssm"]`
`tokens_updated` over the window's T=1 steps, `stats()["latent"]`), the
larger of its FLOPs over peak and its bytes over bandwidth
(`kda_flops.update`: the float32 state read once and written once).  A
kernel that rewrites the lanes nobody decodes in reads lower by their
share."""

from __future__ import annotations

from benchmark import flops, kda_flops, manifest, ssm_flops


def read(run: dict):
    if "kda_heads" not in run["fields"]:
        return None
    kernel = ssm_flops.kernel(run, "kda_update")
    lanes = kda_flops.lanes_per_update(run)
    if not kernel or lanes is None:
        return None
    least, _ = flops.roofline_s(*kda_flops.update(lanes, run["fields"]),
                                manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
