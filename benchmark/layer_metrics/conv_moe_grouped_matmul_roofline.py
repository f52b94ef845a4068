"""Device trace time of EVERY `moe_grouped_matmul` call of the traced slice
against the least the chip could take for them, seconds, calls and load over
one population: three calls a (layer, step) pair, T=1 steps' and mixed
iterations' alike, each pair a gate, an up and a down multiply over the
window's average assignments and experts hit a (layer, step) pair
(`stats()["moe"]`, which counts the same pairs), at the published matrices
(d_model x d_expert), each multiply the larger of FLOPs over peak and bytes
over bandwidth (`conv_flops.grouped_matmul_least_s`).  (The accepted
`*_grouped_matmul_roofline` readers divide the seconds of T=1-SHAPED calls
into a least built from steps and load that count mixed iterations too, and
read over 100% since PR 53: PERF.md section 7.)"""

from __future__ import annotations

from benchmark import conv_flops, manifest


def read(run: dict):
    if "conv_taps" not in run["fields"]:
        return None
    both = conv_flops.grouped_matmul_least_s(
        run, manifest.peaks(run["device"]["kind"]))
    if both is None:
        return None
    least, seconds = both
    return 100.0 * least / seconds
