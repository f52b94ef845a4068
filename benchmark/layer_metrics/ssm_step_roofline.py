"""The traced slice's device busy time against the bytes its steps must
move, over the chip's bandwidth: per step every layer's weights and the
head (`ssm_flops.step_weight_bytes`); per T=1 step the states of the lanes
it stepped, read and written (`ssm_flops.update`), and the K and V rows of
the context the slice's steps attended over (`ssm_flops.kv_bytes` of
`ssm_flops.slice_context`);
per T>1 step the states of its prefilling lanes.  Steps are counted from
the trace (`ssm_update` and `ssm_scan` calls over the layers).  A decode
step is bound by these bytes; the prefill chunks in the slice are bound by
their products, and read lower."""

from __future__ import annotations

from benchmark import manifest, ssm_flops


def read(run: dict):
    t = run.get("trace") or {}
    update = ssm_flops.kernel(run, "ssm_update")
    lanes = ssm_flops.lanes_per_update(run)
    context = ssm_flops.slice_context(run)
    if not update or not t.get("busy_s") or lanes is None or context is None:
        return None
    f = run["fields"]
    decode_steps = update["calls"] / f["n_layers"]
    scan = ssm_flops.kernel(run, "ssm_scan")
    per = ssm_flops.per_prefill_step(run)
    prefill_steps = scan["calls"] / f["n_layers"] if scan and per else 0.0
    nbytes = ((decode_steps + prefill_steps) * ssm_flops.step_weight_bytes(f)
              + decode_steps * (f["n_layers"] * ssm_flops.update(lanes, f)[1]
                                + ssm_flops.kv_bytes(f, context)))
    if prefill_steps:
        nbytes += prefill_steps * f["n_layers"] * ssm_flops.scan(
            0.0, per[1], f)[1]
    bandwidth = manifest.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / t["busy_s"]
