"""The traced slice's device busy time against the bytes its steps must
move, over the chip's bandwidth, for a stack of one-part layers
(`hybrid_moe_flops.step_bytes`): per step the two published matrices of
every held expert hit (the window's average from `stats()["moe"]`), the
shared experts and routers of the expert layers, the mixer layers' and the
attention layers' weights and the head; per T=1 step the states of the
lanes it stepped, read and written, in every mixer layer, and the K and V
rows of the context the slice's steps attended over in every attention
layer; per T>1 step the states of its prefilling lanes.  The layers of each
kind are the program's own count (`stats()["layers"]`); steps are counted
from the trace (`ssm_update` and `ssm_scan` calls over the mixer layers).
The share of the whole step: a decode step is bound by these bytes."""

from __future__ import annotations

from benchmark import hybrid_moe_flops, manifest


def read(run: dict):
    t = run.get("trace") or {}
    if not t.get("busy_s") or "d_shared" not in run["fields"]:
        return None
    nbytes = hybrid_moe_flops.step_bytes(run)
    if nbytes is None:
        return None
    bandwidth = manifest.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / t["busy_s"]
