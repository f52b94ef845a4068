"""The traced slice's device busy time against the bytes its T=1 steps must
read, over the chip's bandwidth: per step every layer's matrices, the head
and the rows the lanes attend over in every layer (`eva_flops.step_bytes`,
the rows from `stats()["eva"]`, the window's average a step).  T=1 steps
are counted from the trace: `paged_decode_attention` calls over the layers.
A decode step is bound by these bytes; the prefill chunks and compactions in
the slice are not counted and make it read lower."""

from __future__ import annotations

from benchmark import eva_flops, manifest


def read(run: dict):
    t = run.get("trace") or {}
    kernel = (t.get("kernels") or {}).get("paged_decode_attention")
    per_step = eva_flops.rows_per_step(run)
    if not kernel or not t.get("busy_s") or per_step is None:
        return None
    f = run["fields"]
    steps = kernel["calls"] / f["n_layers"]
    bandwidth = manifest.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * steps * eva_flops.step_bytes(f, per_step) / bandwidth \
        / t["busy_s"]
