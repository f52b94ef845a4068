"""The traced slice's device busy time against the bytes its step programs
must move, over the chip's bandwidth, for a stack of KDA and latent layers
over a share of experts (`kda_flops.step_bytes`): per program the three
matrices of every held expert hit (the window's average from
`stats()["moe"]`), the shared experts and routers, the KDA and latent
mixers' weights, the dense layer and the head; the latent rows of the
context the slice's own steps attended over in every latent layer; the
float32 states and the tails of the lanes stepped, read and written, in
every KDA layer.  The layers of each kind are the program's own count
(`stats()["layers"]`); programs are counted from the trace (`kda_update`
calls over the KDA layers: a pair's program is one, and reads its weights
once).  The share of the whole step: a decode step is bound by these
bytes."""

from __future__ import annotations

from benchmark import kda_flops, manifest


def read(run: dict):
    t = run.get("trace") or {}
    if not t.get("busy_s") or "kda_heads" not in run["fields"]:
        return None
    nbytes = kda_flops.step_bytes(run)
    if nbytes is None:
        return None
    bandwidth = manifest.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / t["busy_s"]
