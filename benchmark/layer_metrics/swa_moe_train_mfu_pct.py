"""Model FLOP/s utilisation of a train step whose layers mix window and full
attention over a share of routed experts: `swa_moe_train_flops.flops_per_token`
(forward and backward; a window counted as a window, the held experts'
rows only, recomputation not counted) x the window's tokens per second,
over chips x the device_kind's bf16 peak.  The experts' rows are the mean
the window's steps sent them (`expert_load`, where the driver carries it:
`drivers/train_state.py`), else an even router's EXPECTED rows; the rest
is counted from `fields` and the traffic file."""

from __future__ import annotations


def read(run: dict):
    from benchmark import manifest, swa_moe_train_flops
    peak = manifest.peaks(run["device"]["kind"])["bf16_flops"]
    traffic = run["traffic"]
    rows = swa_moe_train_flops.rows_sent(run, "window")
    if rows is not None:
        rows /= traffic["batch"] // run["device"]["count"] * traffic["seq"]
    return 100.0 * swa_moe_train_flops.flops_per_token(
        run["fields"], traffic["seq"], rows) \
        * run["end_to_end"]["train_tokens_per_s"] \
        / (run["device"]["count"] * peak)
