"""Prefix-cache benchmark: shared-system-prompt TTFT, cold vs warm.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Real serving traffic is dominated by shared prefixes (system prompts,
few-shot templates, multi-turn history).  This measures time-to-first-
token for a prompt of `--prefix-len` shared tokens plus a `--suffix-len`
unique tail, two ways on the SAME engine: warm (the shared prefix is
sealed in the content-addressed block index, admission adopts it by
reference and prefills only the tail) and cold (a never-seen prefix —
every token prefills from scratch).  `vs_baseline` is cold_ttft /
warm_ttft — the speedup prefix caching buys; with the default shapes
the cached prefix covers ~94% of the prompt's blocks and the acceptance
bar is >= 5x.  Decode tokens/s is reported for both phases to show the
steady-state path is untouched.
"""

from __future__ import annotations

import argparse
import itertools
import json
import statistics
import time

_uid = itertools.count(1)


def _measure(engine, prompt, new_tokens):
    """(ttft_seconds, decode_tokens_per_sec) for one request, driving
    the scheduler manually so TTFT is not hostage to thread wakeups."""
    h = engine.submit(prompt, max_new_tokens=new_tokens)
    t0 = time.perf_counter()
    while h._req.out.qsize() == 0:
        engine.step()
    ttft = time.perf_counter() - t0
    t1 = time.perf_counter()
    while engine.step():
        pass
    toks = h.tokens(timeout=60)
    decode_dt = time.perf_counter() - t1
    tps = (len(toks) - 1) / decode_dt if decode_dt > 0 else float("inf")
    return ttft, tps


def main():
    from ray_tpu._private import compile_cache
    compile_cache.place()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="gpt2-small")
    ap.add_argument("--prefix-len", type=int, default=512,
                    help="shared system-prompt length (tokens)")
    ap.add_argument("--suffix-len", type=int, default=32,
                    help="unique per-request tail length (tokens)")
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    args = ap.parse_args()

    from ray_tpu.inference import InferenceEngine

    total = args.prefix_len + args.suffix_len
    engine = InferenceEngine(
        "gpt", args.config, max_lanes=4, block_size=args.block_size,
        max_seq_len=total + args.new_tokens + args.block_size,
        prefill_chunk=args.prefill_chunk, auto_start=False)
    vocab = engine.config.vocab_size

    def tail(n):
        return [(13 * next(_uid) + j) % vocab for j in range(n)]

    system_prompt = [(3 * j + 1) % vocab for j in range(args.prefix_len)]

    # Warmup compiles both step shapes AND seals the shared prefix.
    engine.generate(system_prompt + tail(args.suffix_len), max_new_tokens=2)

    # Warm first: cold runs below seal their own (unique) prefixes and
    # under pool pressure would LRU-evict the shared one.
    warm = [_measure(engine, system_prompt + tail(args.suffix_len),
                     args.new_tokens) for _ in range(args.repeats)]
    cold = [_measure(engine, tail(args.prefix_len) + tail(args.suffix_len),
                     args.new_tokens) for _ in range(args.repeats)]

    warm_ttft = statistics.median(t for t, _ in warm)
    cold_ttft = statistics.median(t for t, _ in cold)
    stats = engine.stats()
    hit_blocks = args.prefix_len // args.block_size
    total_blocks = -(-total // args.block_size)

    print(json.dumps({
        "metric": "gpt2_prefix_warm_ttft_ms",
        "value": round(warm_ttft * 1e3, 2),
        "unit": "ms",
        "vs_baseline": round(cold_ttft / warm_ttft, 2),
        "cold_ttft_ms": round(cold_ttft * 1e3, 2),
        "warm_decode_tokens_per_sec":
            round(statistics.median(r for _, r in warm), 1),
        "cold_decode_tokens_per_sec":
            round(statistics.median(r for _, r in cold), 1),
        "prefix_len": args.prefix_len,
        "suffix_len": args.suffix_len,
        "hit_block_fraction": round(hit_blocks / total_blocks, 3),
        "prefix_hit_tokens": stats["prefix_hit_tokens"],
        "blocks_evicted": stats["blocks_evicted"],
    }))


if __name__ == "__main__":
    main()
