"""`held_grouped_matmul_roofline` for this cell: device trace time of the
`moe_grouped_matmul` kernel in the traced slice's T=1 steps against the
least the chip could take for them: per expert layer and step a gate, an up
and a down multiply over the assignments that fell on held experts and the
held experts hit (`stats()["moe"]`, the window's average per (layer, step)
pair), each the larger of FLOPs over peak and bytes over bandwidth
(`moe_flops`).  T=1 calls are told from a chunk's by their row count in the
operation's shape (lanes x top-k); there are as many (layer, step) pairs of
them as calls of the index kernel times expert layers over full layers."""

from __future__ import annotations

import re

from benchmark import manifest, moe_flops, sparse_flops


def read(run: dict):
    t = run.get("trace") or {}
    index = sparse_flops.kernel(run, "sparse_index_scores")
    load = sparse_flops.held_load(run)
    if not index or load is None:
        return None
    f = run["fields"]
    rows = run["traffic"]["engine"]["max_lanes"] * f["n_experts_per_tok"]
    shape = re.compile(r"^moe_grouped_matmul \w+\[%d,\d+\] \(kernel\)$" % rows)
    seconds = sum(s for label, s in t.get("ops_table", [])
                  if shape.match(label))
    if not seconds:
        return None
    held, hit, pairs = load
    least = moe_flops.expert_layer_s(
        held / pairs, hit / pairs,
        {"d_model": f["d_model"], "d_ff": f["d_expert"]},
        manifest.peaks(run["device"]["kind"]))
    lead, full, win = sparse_flops.layers(f)
    calls = index["calls"] / (lead + full) * (full + win)
    return 100.0 * least * calls / seconds
