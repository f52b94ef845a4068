"""The `latent_decode_attention` kernel's share of device busy time in the
traced slice."""

from __future__ import annotations


def read(run: dict):
    t = run.get("trace") or {}
    kernel = (t.get("kernels") or {}).get("latent_decode_attention")
    if not kernel or not t.get("busy_s"):
        return None
    return 100.0 * kernel["seconds"] / t["busy_s"]
