"""Device trace time of the `ssm_scan` kernel in the traced slice against
the least the chip could take for its calls: each call is one layer's
chunked scan of the tokens a T>1 step fed its prefilling lanes (the
window's averages from `stats()["ssm"]` `tokens_scanned` and
`stats()["prefill"]`), the larger of its FLOPs over the bf16 peak and its
bytes over bandwidth (`ssm_flops.scan`: a row's state read and written
once).  Padded rows and float32 products at several passes read lower."""

from __future__ import annotations

from benchmark import flops, manifest, ssm_flops


def read(run: dict):
    kernel = ssm_flops.kernel(run, "ssm_scan")
    per = ssm_flops.per_prefill_step(run)
    if not kernel or per is None:
        return None
    least, _ = flops.roofline_s(*ssm_flops.scan(*per, run["fields"]),
                                manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
