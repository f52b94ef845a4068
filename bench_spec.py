"""Speculative decoding benchmark: accepted-tokens/step + tokens/s.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Runs the continuous-batching engine twice over the same repetitive-text
workload — once plain (one token per jitted step) and once with
self-speculative n-gram drafting (`spec_k` drafts verified per step) —
at 1/8/32 concurrent lanes, and reports per-lane-count tokens/s, the
speedup ratio, accepted-tokens-per-verify-step, and decode TBT p50/p99
from the engine's SLO histograms (bucket-count deltas around each run,
so the two configurations don't pollute each other).

Repetitive text is speculation's home turf: code, templated prose and
multi-turn transcripts make the n-gram proposer's lookups land, so
acceptance approaches spec_k and per-step overhead (dispatch, host
scheduling, sampling commit) amortizes over several tokens.  The
headline row (value / vs_baseline / accepted_per_step) is the
single-lane latency regime — the regime speculative decoding targets,
where each decode step is overhead-bound and a T=k+1 verify costs
barely more than a T=1 step; the bar there is accepted-tokens/step
> 1.5 and a tokens/s speedup >= 1.3x.  Higher lane counts are reported
alongside (and their TBT p50 still drops) but on a compute-saturated
device the verify step's extra B*T positions cost real FLOPs, so the
aggregate-throughput win shrinks as batch grows — the classic reason
serving stacks gate speculation on batch occupancy.
"""

from __future__ import annotations

import argparse
import gc
import json
import time


def _prompts(n_seqs, prompt_len, period, vocab):
    """Cyclic token streams (distinct phase/alphabet per sequence), the
    stand-in for repetitive text."""
    return [[(i * 17 + (j % period)) % vocab for j in range(prompt_len)]
            for i in range(n_seqs)]


def _tbt_snapshot():
    from ray_tpu.util import metrics
    snap = metrics.collect().get("inference_tbt_s")
    if not snap or not snap["series"]:
        return None, []
    return snap, list(snap["series"][0]["value"]["buckets"])


def _tbt_quantiles(before):
    """p50/p99 of the TBT observations made since `before` (bucket-count
    delta against the current snapshot)."""
    from ray_tpu.util import metrics
    snap, counts = _tbt_snapshot()
    if snap is None:
        return float("nan"), float("nan")
    delta = [c - b for c, b in zip(counts, before + [0] * len(counts))]
    q = metrics.quantiles_from_buckets(snap["buckets"], delta,
                                       qs=(0.5, 0.99))
    return q[0.5], q[0.99]


def _run(engine, prompts, new_tokens):
    """Aggregate generated-tokens/s plus the TBT p50/p99 of this run.

    Cycle-collector pauses are excluded (collect, then disable for the
    timed region — the same hygiene ``timeit`` applies): a single gen-2
    sweep is tens of ms, an order of magnitude over the per-step cost
    being measured, and it lands on whichever run crosses the
    allocation threshold rather than on the slower engine."""
    _, before = _tbt_snapshot()
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        handles = [engine.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        while engine.step():
            pass
        for h in handles:
            assert len(h.tokens()) == new_tokens
        dt = time.perf_counter() - t0
    finally:
        gc.enable()
    p50, p99 = _tbt_quantiles(before)
    return len(prompts) * new_tokens / dt, p50, p99


def main():
    from ray_tpu._private import compile_cache
    compile_cache.place()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="nano",
                    help="model config (nano keeps the number tracking "
                    "per-step overhead, the thing speculation amortizes)")
    ap.add_argument("--lanes", default="1,8,32")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--period", type=int, default=4,
                    help="token period of the repetitive workload")
    ap.add_argument("--new-tokens", type=int, default=96)
    ap.add_argument("--spec-k", type=int, default=4)
    args = ap.parse_args()

    from ray_tpu.inference import InferenceEngine

    lane_counts = [int(x) for x in args.lanes.split(",")]
    max_seq_len = args.prompt_len + args.new_tokens + args.spec_k + 16
    rows = []
    params = None
    for lanes in lane_counts:
        plain = InferenceEngine(
            "gpt", args.config, params, max_lanes=lanes, block_size=16,
            max_seq_len=max_seq_len, prefill_chunk=args.prompt_len,
            auto_start=False, seed=0)
        params = plain.params
        spec = InferenceEngine(
            "gpt", args.config, params, max_lanes=lanes, block_size=16,
            max_seq_len=max_seq_len, prefill_chunk=args.prompt_len,
            auto_start=False, seed=0, spec_k=args.spec_k)
        prompts = _prompts(lanes, args.prompt_len, args.period,
                           plain.config.vocab_size)
        # Warmup: compile every step shape — prefill + T=1 via a short
        # generate, then the T=1 fallback and each verify width the
        # engine may dispatch (T=2..spec_k+1: the step is sized to the
        # widest draft actually proposed) via empty fully-masked
        # batches.  A short warmup generate is not guaranteed to draft,
        # and a mid-run compile would land a ~0.5s stall inside the
        # timed region.
        plain.generate(prompts[0], max_new_tokens=4)
        spec.generate(prompts[0], max_new_tokens=4)
        spec._run_step(spec._build_batch([], 1)[0])
        for t in range(2, args.spec_k + 2):
            spec._run_step(spec._build_batch([], t)[0], True)

        plain_tps, pp50, pp99 = _run(plain, prompts, args.new_tokens)
        spec_tps, sp50, sp99 = _run(spec, prompts, args.new_tokens)
        st = spec.stats()
        sample = spec.generate(prompts[0], args.new_tokens)
        assert sample == plain.generate(prompts[0], args.new_tokens), \
            "speculative output diverged from the plain engine"
        rows.append({
            "lanes": lanes,
            "plain_tokens_per_sec": round(plain_tps, 1),
            "spec_tokens_per_sec": round(spec_tps, 1),
            "speedup": round(spec_tps / plain_tps, 3),
            "accepted_per_step": round(st["spec_accepted_per_step"], 3),
            "plain_tbt_p50_ms": round(pp50 * 1e3, 3),
            "plain_tbt_p99_ms": round(pp99 * 1e3, 3),
            "spec_tbt_p50_ms": round(sp50 * 1e3, 3),
            "spec_tbt_p99_ms": round(sp99 * 1e3, 3),
        })
        plain.shutdown()
        spec.shutdown()

    # Headline = the lowest lane count (the latency regime speculation
    # targets); the full by_lanes table keeps the saturation curve
    # honest.
    top = min(rows, key=lambda r: r["lanes"])
    print(json.dumps({
        "metric": "spec_decode_tokens_per_sec",
        "value": top["spec_tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": top["speedup"],
        "accepted_per_step": top["accepted_per_step"],
        "spec_k": args.spec_k,
        "config": args.config,
        "new_tokens": args.new_tokens,
        "by_lanes": rows,
    }))


if __name__ == "__main__":
    main()
