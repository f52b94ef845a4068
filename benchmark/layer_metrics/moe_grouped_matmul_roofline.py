"""Device trace time of the `moe_grouped_matmul` kernel in the traced slice's
T=1 steps against the least the chip could take for them: per layer and step
a gate, an up and a down multiply over lanes x top-k rows and the experts hit
(`stats()["moe"]`, the window's average per (layer, step) pair: an expert
nobody chose is not counted as read), each the larger of FLOPs over peak and
bytes over bandwidth (`moe_flops`).  T=1 calls are told from a prefill
chunk's by their row count in the operation's shape; there are as many
(layer, step) pairs of them as calls of the paged decode kernel.  A chunk's
calls are left out: a prompt's tokens hit far fewer experts than the window's
average, and a slice holds more or fewer chunks than the window's share."""

from __future__ import annotations

import re

from benchmark import manifest, moe_flops


def read(run: dict):
    t = run.get("trace") or {}
    paged = (t.get("kernels") or {}).get("paged_decode_attention")
    load = moe_flops.window_load(run)
    if not paged or load is None:
        return None
    f = run["fields"]
    rows = run["traffic"]["engine"]["max_lanes"] * f["n_experts_per_tok"]
    shape = re.compile(r"^moe_grouped_matmul \w+\[%d,\d+\] \(kernel\)$" % rows)
    seconds = sum(s for label, s in t.get("ops_table", [])
                  if shape.match(label))
    if not seconds:
        return None
    _, _, hit, layer_steps = load
    least = moe_flops.expert_layer_s(rows, hit / layer_steps, f,
                                     manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * paged["calls"] / seconds
