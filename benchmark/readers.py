"""Arithmetic the per-layer metric files share: from the reduced trace, the
program's events and the client's records to a number."""

from __future__ import annotations

from benchmark import flops, manifest, metrics


def device_idle_pct(run: dict):
    """1 - the union of the intervals in which an operation ran on the device
    over the traced slice, averaged over the chips.  Never from host time."""
    return run["trace"]["idle_pct"] if run.get("trace") else None


def hbm_peak_gb(run: dict):
    """The last line's `memory_peak_bytes`: bytes in use at the window's end
    plus the largest scratch reservation of a program
    (`manifest.memory_report`)."""
    return run["device"]["memory_peak_bytes"] / 1e9


def hbm_live_gb(run: dict):
    """Bytes in live buffers at the window's end (weights, pool, state,
    batches), a program's scratch left out."""
    memory = run.get("memory")
    return memory["bytes_in_use"] / 1e9 if memory else None


def kernel_share(run: dict):
    """Time in Mosaic kernels (`tpu_custom_call`) over device busy time."""
    t = run.get("trace")
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]


def flash_roofline(run: dict):
    t = run.get("trace")
    kernel = (t or {}).get("kernels", {}).get("flash_attention")
    if not kernel or not kernel["seconds"]:
        return None
    f, traffic = run["fields"], run["traffic"]
    peaks = manifest.peaks(run["device"]["kind"])
    per_chip = traffic["batch"] // run["device"]["count"]
    dh = f["d_model"] // f["n_heads"]
    least = sum(flops.roofline_s(*fn(per_chip, f["n_heads"], traffic["seq"],
                                     dh), peaks)[0]
                for fn in (flops.flash_fwd, flops.flash_bwd))
    needed = least * f["n_layers"] * traffic["trace_steps"]
    return 100.0 * needed / kernel["seconds"]


NO_SESSION = ("no closed profiler session: the trace_on or trace_off mark "
              "is missing")


def not_measured(run: dict, why: str) -> None:
    """A reader's `None`, with the input it lacked kept for `run.py`'s "not
    measured" line: a metric left out of a traced run's last line refuses
    the run, and the refusal does not say which input was missing."""
    run["not_measured"] = why
    return None


def paged_roofline(run: dict, kv_heads: int):
    """Device time of the kernel named `paged_decode_attention` in the traced
    slice against the least the chip could take for its calls: each call is
    one layer's single-query attention, `kv_heads` cached heads wide, over
    the context the lanes held DURING the slice
    (`metrics.slice_context_tokens`, from the client's own records)."""
    t = run.get("trace")
    if not t:
        return not_measured(run, "no trace")
    kernel = t["kernels"].get("paged_decode_attention")
    if not kernel or not kernel["seconds"]:
        return not_measured(run, "no kernel time: no paged_decode_attention "
                                 "call in the trace")
    context = metrics.slice_context_tokens(run)
    if context is None:
        return not_measured(run, NO_SESSION)
    if not context:
        return not_measured(run, "no context in the slice: no request held "
                                 "a token during it")
    f = run["fields"]
    least, _ = flops.roofline_s(*flops.paged_decode(
        context, run["traffic"]["engine"]["max_lanes"], kv_heads,
        f["d_model"] // f["n_heads"]), manifest.peaks(run["device"]["kind"]))
    return 100.0 * least * kernel["calls"] / kernel["seconds"]


def span_seconds(events: list, kind: str) -> dict:
    """trace id -> duration of that trace's first `kind` span, from the
    flight recorder's begin and end events (the end carries `dur`)."""
    out = {}
    for e in events:
        payload = e.get("payload") or {}
        if e["kind"] == kind and payload.get("ph") == "E" \
                and e.get("trace_id") and e["trace_id"] not in out:
            out[e["trace_id"]] = payload["dur"]
    return out
