"""Device trace time of EVERY `moe_grouped_matmul` call of the traced slice
against the least the chip could take for them, seconds, calls and load over
one population (as `conv_moe_grouped_matmul_roofline`, here over a SHARE of
the experts): three calls a (layer, step) pair, T=1 steps' and pairs' alike,
each pair a gate, an up and a down multiply over the window's average
assignments that fell on held experts and held experts hit a (layer, step)
pair (`stats()["moe"]`, which counts the same pairs), at the published
matrices (d_model x d_expert), each multiply the larger of FLOPs over peak
and bytes over bandwidth (`kda_flops.grouped_matmul_least_s`).  The least of
the average pair is at most the average of the pairs' leasts: it reads low,
never high."""

from __future__ import annotations

from benchmark import kda_flops, manifest


def read(run: dict):
    if "kda_heads" not in run["fields"]:
        return None
    both = kda_flops.grouped_matmul_least_s(
        run, manifest.peaks(run["device"]["kind"]))
    if both is None:
        return None
    least, seconds = both
    return 100.0 * least / seconds
