"""The `paged_decode_attention` kernel's share of device busy time in the
traced slice, in a cell whose lanes attend EVA's rows."""

from __future__ import annotations


def read(run: dict):
    t = run.get("trace") or {}
    kernel = (t.get("kernels") or {}).get("paged_decode_attention")
    if not kernel or not t.get("busy_s") \
            or "window_size" not in run["fields"]:
        return None
    return 100.0 * kernel["seconds"] / t["busy_s"]
