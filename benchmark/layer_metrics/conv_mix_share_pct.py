"""Share of device busy time in the traced slice that the conv mixers'
own operations take, as far as the trace tells them apart, which is by the
shape of their result (a named scope reaches nothing the trace's reader
sees: PERF.md section 3): every operation whose result is the projection
[.., 3 d_model] wide (the product with W_in, of the T=1 rows, of a pair's
rows and of a chunk's) and every operation whose result is a slot's tail
[.., (taps - 1) d_model] wide (the per-lane part's reads of the lanes'
tails and their overwrite in the tails' buffer).  The per-lane part's other
fusions and the product with W_out have the shapes of the attention's and
the feed-forward's own, so they are not told apart and not counted, and an
operation under the first sixty of the slice's table is not seen: a lower
reading than the scope `conv_mix`'s whole.  Nothing where the configuration
has no conv mixer."""

from __future__ import annotations

import re


def read(run: dict):
    t, f = run.get("trace") or {}, run["fields"]
    if not t.get("busy_s") or "conv_taps" not in f:
        return None
    own = re.compile(r"\[(\d+,)*(%d|%d)\]" % (
        3 * f["d_model"], (f["conv_taps"] - 1) * f["d_model"]))
    seconds = sum(s for label, s in t.get("ops_table", [])
                  if own.search(label))
    return 100.0 * seconds / t["busy_s"] if seconds else None
