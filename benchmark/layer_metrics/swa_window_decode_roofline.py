"""Device trace time of the `window_paged_decode_attention` kernel in the
traced slice against the least the chip could take for its calls: each call
is one window layer's single-query attention over the K and V rows of the
lanes' windows (`stats()["paged"]`: `rows_window`, the window's average per
step and layer; at most sliding_window a lane, never the context), the
larger of its FLOPs over peak and its bytes over bandwidth
(`window_flops.attention_s`), whatever runs or tiles implement it.  A
kernel that read the whole context under a mask would read some 12% at
16.7k tokens."""

from __future__ import annotations

from benchmark import manifest, ssm_flops, window_flops


def read(run: dict):
    kernel = ssm_flops.kernel(run, window_flops.WINDOW_KERNEL)
    per = window_flops.rows_per_step(run)
    if not kernel or per is None or not per[2]:
        return None
    least = window_flops.attention_s(
        per[2], run["traffic"]["engine"]["max_lanes"], run["fields"],
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
