"""Device trace time of the kernels named `moe_grouped_matmul` (forward and
dx) and `moe_grouped_matmul_dw` in the traced train steps against the least
the chip could take for an expert layer's nine products (gate, up, down;
each forward, dx, dw) over the rows the traced steps' routers sent to the
held experts (`swa_moe_train_flops.grouped_products` over the mean of the
steps' `expert_load`, which `drivers/train_state.py` carries; under a
driver that carries none, the EXPECTED rows of an even router), each the
larger of FLOPs over peak and bytes over bandwidth, times the expert
layers and the traced steps.  Products made again (remat's
forward) count as time, not as work.  A program with no such kernels reads
nothing."""

from __future__ import annotations

KERNELS = ("moe_grouped_matmul", "moe_grouped_matmul_dw")


def seconds(run: dict):
    kernels = (run.get("trace") or {}).get("kernels", {})
    return sum(kernels[k]["seconds"] for k in KERNELS if k in kernels)


def read(run: dict):
    from benchmark import flops, manifest, swa_moe_train_flops as counts
    spent = seconds(run)
    if not spent:
        return None
    f, traffic = run["fields"], run["traffic"]
    peaks = manifest.peaks(run["device"]["kind"])
    tokens = traffic["batch"] // run["device"]["count"] * traffic["seq"]
    least = sum(flops.roofline_s(work, nbytes, peaks)[0]
                for work, nbytes in counts.grouped_products(
                    f, tokens, rows=counts.rows_sent(run, "traced")))
    return (100.0 * least * sum(counts.layer_kinds(f))
            * traffic["trace_steps"] / spent)
