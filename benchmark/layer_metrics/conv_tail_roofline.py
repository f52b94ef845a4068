"""Device trace time of the kernel `conv_tail` (a conv mixer's per-lane part
of a T=1 population: the gate B * u, the taps behind the slot's tail, the
tail's overwrite in place, the gate C) in the traced slice against the least
the chip could take for its calls: a call is one conv layer over the lanes
that held a token (the window's average, `stats()["conv"]`), the larger of
operations over peak and bytes over bandwidth (`conv_flops.conv_tail`: the
tails read and written and the taps; the projection and the result are its
neighbours' operands and stay on the chip).
Nothing where the program has no such kernel."""

from __future__ import annotations

from benchmark import conv_flops, manifest, ssm_flops


def read(run: dict):
    kernel = ssm_flops.kernel(run, "conv_tail")
    lanes = conv_flops.lanes_per_step(run)
    if not kernel or lanes is None:
        return None
    least = conv_flops.roofline_s(
        conv_flops.conv_tail(lanes, run["fields"]),
        manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]
