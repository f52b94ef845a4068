"""Share of device busy time in the traced slice that the conv mixers'
own operations take, as far as the trace tells them apart: the kernel
`conv_tail` (the per-lane part: gate, taps, the tail's overwrite) and every
operation whose result is the projection [.., 3 d_model] wide (the product
with W_in, of the T=1 rows, of a pair's rows and of a chunk's).  A named
scope reaches nothing the trace's reader sees (PERF.md section 3), and the
product with W_out has the shape of the attention's output product, so it is
not told apart and not counted: a lower reading than the scope's whole.
Nothing where the program has no `conv_tail` kernel."""

from __future__ import annotations

import re

from benchmark import ssm_flops


def read(run: dict):
    t = run.get("trace") or {}
    kernel = ssm_flops.kernel(run, "conv_tail")
    if not kernel or not t.get("busy_s") or "conv_taps" not in run["fields"]:
        return None
    wide = re.compile(r"\[(\d+,)*%d\]" % (3 * run["fields"]["d_model"]))
    seconds = kernel["seconds"] + sum(
        s for label, s in t.get("ops_table", [])
        if wide.search(label) and not label.startswith("conv_tail"))
    return 100.0 * seconds / t["busy_s"]
