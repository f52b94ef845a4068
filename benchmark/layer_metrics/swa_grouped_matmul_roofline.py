"""Device trace time of the `moe_grouped_matmul` kernel in the traced
slice's T=1 steps against the least the chip could take for them: per
expert layer and step a gate, an up and a down multiply over the step's
assignments and the experts hit (`stats()["moe"]`, the window's average per
(layer, step) pair) at the PUBLISHED matrices (d_model x d_expert), each
the larger of FLOPs over peak and bytes over bandwidth (`moe_flops`).  T=1
calls are told from a chunk's by their row count in the operation's shape
(lanes x top-k); there are as many (layer, step) pairs of them as T=1 steps
(`window_flops.decode_steps`) times expert layers."""

from __future__ import annotations

import re

from benchmark import manifest, moe_flops, window_flops


def read(run: dict):
    t = run.get("trace") or {}
    n = window_flops.layers(run)
    steps = window_flops.decode_steps(run)
    load = moe_flops.window_load(run)
    if not n or steps is None or load is None:
        return None
    f = run["fields"]
    rows = run["traffic"]["engine"]["max_lanes"] * f["n_experts_per_tok"]
    shape = re.compile(r"^moe_grouped_matmul \w+\[%d,\d+\] \(kernel\)$" % rows)
    seconds = sum(s for label, s in t.get("ops_table", [])
                  if shape.match(label))
    if not seconds:
        return None
    assignments, _, hit, pairs = load
    least = moe_flops.expert_layer_s(
        assignments / pairs, hit / pairs,
        {"d_model": f["d_model"], "d_ff": f["d_expert"]},
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * steps * n["experts"] / seconds
