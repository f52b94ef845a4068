"""Decode benchmark: GPT-2-small continuous-batching throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Measures aggregate steady-state decode tokens/s with the paged-KV
continuous-batching engine at 32 concurrent sequences, and the same
engine serving one sequence at a time.  `vs_baseline` is the ratio —
the speedup continuous batching buys over sequential decoding.  Decode
is weight-streaming-bound, so one 32-lane step costs roughly one
1-lane step and the ratio should approach the lane count (the
acceptance bar is >= 5x).
"""

from __future__ import annotations

import argparse
import json
import time


def _decode_tps(engine, n_seqs, prompt_len, new_tokens, *, sequential):
    """Aggregate generated-tokens/s over n_seqs requests."""
    prompts = [[(7 * i + j) % engine.config.vocab_size
                for j in range(prompt_len)] for i in range(n_seqs)]
    t0 = time.perf_counter()
    if sequential:
        for p in prompts:
            engine.generate(p, max_new_tokens=new_tokens)
    else:
        handles = [engine.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        while engine.step():
            pass
        for h in handles:
            h.tokens()
    dt = time.perf_counter() - t0
    return n_seqs * new_tokens / dt


def main():
    from ray_tpu._private import compile_cache
    compile_cache.place()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="gpt2-small")
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=48)
    ap.add_argument("--seq-probe", type=int, default=2,
                    help="sequences timed for the sequential baseline")
    args = ap.parse_args()

    from ray_tpu.inference import InferenceEngine

    max_seq_len = args.prompt_len + args.new_tokens + 16
    engine = InferenceEngine(
        "gpt", args.config, max_lanes=args.lanes, block_size=16,
        max_seq_len=max_seq_len, prefill_chunk=args.prompt_len,
        auto_start=False)

    # Warmup: compile both step shapes (prefill chunk + pure decode).
    engine.generate([1] * args.prompt_len, max_new_tokens=4)

    batched_tps = _decode_tps(engine, args.lanes, args.prompt_len,
                              args.new_tokens, sequential=False)
    seq_tps = _decode_tps(engine, args.seq_probe, args.prompt_len,
                          args.new_tokens, sequential=True)

    print(json.dumps({
        "metric": "gpt2_decode_tokens_per_sec",
        "value": round(batched_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(batched_tps / seq_tps, 3),
        "lanes": args.lanes,
        "sequential_tokens_per_sec": round(seq_tps, 1),
    }))


if __name__ == "__main__":
    main()
