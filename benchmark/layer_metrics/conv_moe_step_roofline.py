"""The traced slice's device busy time against the bytes its steps must
move, over the chip's bandwidth, for a stack of conv and attention operators
over experts (`conv_flops.step_bytes`): per step the three matrices of every
expert hit (the window's average from `stats()["moe"]`), the routers, the
conv and attention operators' weights, the dense layer and the tied head;
the K and V rows of the context the slice's own steps attended over in
every attention layer; the tails of the lanes stepped, read and written, in
every conv layer.  Steps are counted from the trace (`paged_decode_attention`
calls over the attention layers, the program's own count).  The share of the whole step: a
decode step is bound by these bytes."""

from __future__ import annotations

from benchmark import conv_flops, manifest


def read(run: dict):
    t = run.get("trace") or {}
    if not t.get("busy_s") or "conv_taps" not in run["fields"]:
        return None
    nbytes = conv_flops.step_bytes(run)
    if nbytes is None:
        return None
    bandwidth = manifest.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / t["busy_s"]
