"""Device trace time of the `kda_scan` kernel in the traced slice against
the least the chip could take for its calls: each call is one KDA layer's
scan of the tokens a chunk fed its prefilling lanes (the window's averages
from `stats()["ssm"]` `tokens_scanned` and `stats()["prefill"]`), the larger
of its FLOPs over the bf16 peak and its bytes over bandwidth
(`kda_flops.scan`: the published recurrence's operations a token, a row's
state read and written once).  The recurrence runs on the vector unit in
float32, a position at a time, and padded rows are walked too: it reads far
under the matrix unit's peak."""

from __future__ import annotations

from benchmark import flops, kda_flops, manifest, ssm_flops


def read(run: dict):
    if "kda_heads" not in run["fields"]:
        return None
    kernel = ssm_flops.kernel(run, "kda_scan")
    per = ssm_flops.per_prefill_step(run)
    if not kernel or per is None:
        return None
    least, _ = flops.roofline_s(*kda_flops.scan(*per, run["fields"]),
                                manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
