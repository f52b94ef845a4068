"""Share of device busy time in the traced slice that the two single-query
attention kernels take together: `window_paged_decode_attention` (the
window layers') and `paged_decode_attention` (the full layers')."""

from __future__ import annotations

from benchmark import window_flops


def read(run: dict):
    t = run.get("trace") or {}
    kernels = t.get("kernels") or {}
    if not t.get("busy_s") or window_flops.WINDOW_KERNEL not in kernels:
        return None
    seconds = sum(kernels[k]["seconds"] for k in (
        window_flops.WINDOW_KERNEL, window_flops.FULL_KERNEL) if k in kernels)
    return 100.0 * seconds / t["busy_s"]
