"""The traced slice's device busy time against the bytes its steps must
read, over the chip's bandwidth: per step every layer's attention weights,
the dense feed-forward, per expert layer router, shared expert and three
matrices of every held expert hit (the window's average from
`stats()["moe"]`), and the head (`sparse_flops.step_weight_bytes`); per T=1
step the index keys of the context, the chosen rows and the windows' rows
(`stats()["sparse"]`, `sparse_flops.step_cache_bytes`).  Steps are counted
from the trace (`moe_grouped_matmul` calls / 3 / expert layers), T=1 steps
from the index kernel's calls.  A decode step is bound by these bytes; the
prefill chunks in the slice are not, and read lower."""

from __future__ import annotations

from benchmark import manifest, sparse_flops


def read(run: dict):
    t = run.get("trace") or {}
    grouped = sparse_flops.kernel(run, "moe_grouped_matmul")
    index = sparse_flops.kernel(run, "sparse_index_scores")
    load = sparse_flops.held_load(run)
    per = sparse_flops.per_step(run)
    if not grouped or not index or not t.get("busy_s") or load is None \
            or per is None:
        return None
    f = run["fields"]
    _, hit, pairs_w = load
    lead, full, win = sparse_flops.layers(f)
    steps = grouped["calls"] / 3 / (full + win)
    decode_steps = index["calls"] / (lead + full)
    nbytes = (steps * sparse_flops.step_weight_bytes(f, hit / pairs_w)
              + decode_steps * sparse_flops.step_cache_bytes(f, *per))
    bandwidth = manifest.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / t["busy_s"]
