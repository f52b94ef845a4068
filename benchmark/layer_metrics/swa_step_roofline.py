"""The traced slice's device busy time against the bytes its steps must
move, over the chip's bandwidth (`window_flops.step_bytes`): per step the
three published matrices of every expert hit (the window's average from
`stats()["moe"]`), the shared experts and routers, every layer's attention
with its gate, the dense layers' feed-forward and the head; per T=1 step the
K and V rows of the slice's own context in every full layer and of the
lanes' windows (`stats()["paged"]`: `rows_window`) in every window layer.
The layers of each kind are the program's own count (`stats()["layers"]`).
The share of the whole step: a decode step is bound by these bytes; the
prefill chunks in the slice are not, and read lower."""

from __future__ import annotations

from benchmark import manifest, window_flops


def read(run: dict):
    t = run.get("trace") or {}
    if not t.get("busy_s"):
        return None
    nbytes = window_flops.step_bytes(run)
    if nbytes is None:
        return None
    bandwidth = manifest.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / t["busy_s"]
