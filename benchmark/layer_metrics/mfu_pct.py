"""Model FLOP/s utilisation: `flops.flops_per_token` (forward and backward,
recomputation not counted, causal attention counted once) x the window's
tokens per second, over chips x the device_kind's bf16 peak."""

from __future__ import annotations


def read(run: dict):
    from benchmark import flops, manifest
    peak = manifest.peaks(run["device"]["kind"])["bf16_flops"]
    return 100.0 * flops.mfu(
        run["fields"], run["traffic"]["seq"],
        run["end_to_end"]["train_tokens_per_s"], run["device"]["count"], peak)
