"""The traced slice's device busy time against the bytes its steps must
read, over the chip's bandwidth: per (layer, step) pair the attention
projections, the router and three matrices of every expert hit (the
window's average from `stats()["moe"]`), per step the output head, per T=1
step the cached keys and values of the context the lanes held during the
slice (`metrics.slice_context_tokens`).  Pairs are counted
from the trace (`moe_grouped_matmul` calls / 3), T=1 steps from the paged
kernel's calls; a decode step is bound by these bytes, not by FLOPs."""

from __future__ import annotations

from benchmark import manifest, metrics, moe_flops, readers


def read(run: dict):
    t = run.get("trace") or {}
    kernels = t.get("kernels") or {}
    grouped = kernels.get("moe_grouped_matmul")
    load = moe_flops.window_load(run)
    context = metrics.slice_context_tokens(run)
    if not grouped or not t.get("busy_s"):
        return readers.not_measured(run, "no trace, or no moe_grouped_matmul "
                                         "call in it")
    if load is None:
        return readers.not_measured(run, "no expert counters in the "
                                         "window's stats")
    if context is None:
        return readers.not_measured(run, readers.NO_SESSION)
    f = run["fields"]
    _, _, hit, layer_steps = load
    pairs = grouped["calls"] / 3
    steps = pairs / f["n_layers"]
    decode_steps = kernels.get("paged_decode_attention", {}).get(
        "calls", 0) / f["n_layers"]
    nbytes = (pairs * moe_flops.layer_weight_bytes(f, hit / layer_steps)
              + steps * moe_flops.head_bytes(f)
              + decode_steps * moe_flops.kv_bytes(f, context))
    bandwidth = manifest.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / t["busy_s"]
