"""Disaggregated-serving benchmark: prefill/decode split vs monolithic.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}
and writes the full document to DISAGG_BENCH.json.

Three measurements, one claim each:

1. **TTFT/TBT/goodput, equal hardware.**  The same multi-client
   shared-prefix streaming workload runs against 2 monolithic
   LLMDeployment replicas and against 1 prefill + 1 decode replica
   (serve/kv_tier).  Monolithic p2c routing splits each group's prefix
   across both replica caches — a request landing on the "wrong"
   replica re-prefills the whole shared prefix, and that prefill
   interleaves into the same engine loop its neighbours are decoding
   through.  Disaggregation concentrates ALL prefill (and the prefix
   cache) on the prefill replica and ships sealed blocks to the decode
   replica, so `vs_baseline` for TTFT p99 is monolithic/disagg (>1
   means the split wins).

2. **Prefix hit-rate with/without the spill tier.**  One engine with a
   device pool too small for the working set replays a prompt cycle;
   with a KVTierCache attached, evicted chains restore from host/store
   instead of re-prefilling.  The claim is strictly-higher hit rate.

3. **Token-exactness through the handoff.**  Greedy AND seeded-sampled
   output through export -> codec -> import equals a monolithic
   engine's, asserted (not just reported).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _percentile(xs, q):
    xs = sorted(xs)
    if not xs:
        return 0.0
    i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[i]


def _prompts(args):
    """`requests` prompts in `groups` shared-prefix groups: a long
    shared head (the disaggregation target) + a short unique tail."""
    out = []
    for i in range(args.requests):
        g = i % args.groups
        head = [1 + ((g * 13 + t) % 96) for t in range(args.prefix_len)]
        out.append(head + [100 + i % 150, 101 + i % 150, 1 + i % 96])
    return out


def _drive(stream_fn, prompts, budget, concurrency):
    """Fire the workload; returns (ttfts, tbts, wall_s, tokens_out)."""
    ttfts, tbts = [], []
    tokens_out = [0]
    lock = threading.Lock()
    it = iter(list(enumerate(prompts)))

    def worker():
        while True:
            with lock:
                nxt = next(it, None)
            if nxt is None:
                return
            _i, prompt = nxt
            t0 = time.perf_counter()
            last = None
            got = 0
            for _tok in stream_fn(prompt, budget):
                now = time.perf_counter()
                if last is None:
                    with lock:
                        ttfts.append(now - t0)
                else:
                    with lock:
                        tbts.append(now - last)
                last = now
                got += 1
            with lock:
                tokens_out[0] += got

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return ttfts, tbts, time.perf_counter() - t0, tokens_out[0]


def _teardown():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.serve import _private as sp
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()
    with sp._router_states_lock:
        sp._router_states.clear()
    GLOBAL_CONFIG.invalidate_cache()


def run_monolithic(args):
    """Equal hardware baseline: 2 monolithic replicas behind p2c."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_stream_resume

    ray_tpu.init(num_cpus=4, object_store_memory=128 << 20)
    serve.start()
    try:
        handle = serve.run(serve.LLMDeployment.options(
            name="llm_mono_bench", num_replicas=2).bind(
                model="gpt", config="nano", max_lanes=args.concurrency,
                seed=0)).options("generate", failover=llm_stream_resume)
        list(handle.stream([1, 2, 3], 2))            # compile both shapes
        return _drive(lambda p, b: handle.stream(p, b),
                      _prompts(args), args.budget, args.concurrency)
    finally:
        _teardown()


def run_disagg(args):
    """1 prefill + 1 decode replica — same chip count as the baseline."""
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4, object_store_memory=128 << 20)
    serve.start()
    try:
        handle = serve.run_disaggregated(
            model="gpt", config="nano", max_lanes=args.concurrency,
            seed=0, name="llm_disagg_bench")
        list(handle.stream([1, 2, 3], 2))            # compile both engines
        return _drive(handle.stream,
                      _prompts(args), args.budget, args.concurrency)
    finally:
        _teardown()


def run_hit_rate(with_tier: bool):
    """Prefix hit rate over a working set larger than the device pool;
    the spill tier turns second-pass evictions back into hits."""
    from ray_tpu.inference import InferenceEngine
    from ray_tpu.serve.kv_tier import KVTierCache

    eng = InferenceEngine("gpt", "nano", seed=0, auto_start=False,
                          num_blocks=8, block_size=16)
    if with_tier:
        eng.cache.attach_tier(KVTierCache(host_blocks=16,
                                          store_blocks=32))
    prompts = [list(range(s, s + 48)) for s in
               (1, 60, 120, 180, 240, 300)]
    for _cycle in range(2):
        for p in prompts:
            eng.generate(p, 4)
    st = eng.stats()
    hit, miss = st["prefix_hit_tokens"], st["prefix_miss_tokens"]
    return hit / max(1, hit + miss), st


def check_token_exact():
    """Greedy + seeded equality through export -> codec -> import."""
    from ray_tpu.inference import InferenceEngine
    from ray_tpu.serve.kv_tier import KVBlockCodec

    prompt = list(range(1, 49))
    prefill = InferenceEngine("gpt", "nano", seed=0, auto_start=False)
    prefill.prefill(prompt).tokens()
    blob = KVBlockCodec.encode(prefill.export_prefix(prompt))
    results = {}
    for name, temp, seed in (("greedy", 0.0, None), ("seeded", 0.8, 7)):
        decode = InferenceEngine("gpt", "nano", seed=0, auto_start=False)
        mono = InferenceEngine("gpt", "nano", seed=0, auto_start=False)
        decode.import_prefix(KVBlockCodec.decode(blob))
        got = decode.generate(prompt, 12, temperature=temp, seed=seed)
        ref = mono.generate(prompt, 12, temperature=temp, seed=seed)
        assert got == ref, f"{name} handoff output diverged: {got} != {ref}"
        results[name] = True
    return results


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=36)
    ap.add_argument("--groups", type=int, default=6)
    ap.add_argument("--prefix-len", type=int, default=96)
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--concurrency", type=int, default=6)
    ap.add_argument("--phase", choices=["engines"], default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.phase == "engines":
        rate_cold, _ = run_hit_rate(with_tier=False)
        rate_tier, st_tier = run_hit_rate(with_tier=True)
        print(json.dumps({"exact": check_token_exact(),
                          "rate_cold": rate_cold, "rate_tier": rate_tier,
                          "st_tier": st_tier}))
        return

    # The in-process engine phases run in a fresh interpreter: this
    # process goes on to start replica processes that need the device, so
    # it must never touch jax itself.
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--phase", "engines"], capture_output=True,
                       text=True, timeout=600)
    if p.returncode != 0:
        raise SystemExit(f"engine phases failed:\n{p.stderr[-2000:]}")
    eng = json.loads(p.stdout.strip().splitlines()[-1])
    exact, st_tier = eng["exact"], eng["st_tier"]
    rate_cold, rate_tier = eng["rate_cold"], eng["rate_tier"]
    assert rate_tier > rate_cold, (
        f"spill tier did not raise hit rate: {rate_tier} <= {rate_cold}")

    mono_ttft, mono_tbt, mono_wall, mono_toks = run_monolithic(args)
    dis_ttft, dis_tbt, dis_wall, dis_toks = run_disagg(args)

    mono_p99 = _percentile(mono_ttft, 0.99)
    dis_p99 = _percentile(dis_ttft, 0.99)
    doc = {
        "metric": "disagg_ttft_p99_ms",
        "value": round(dis_p99 * 1000, 1),
        "unit": "ms",
        "vs_baseline": round(mono_p99 / max(dis_p99, 1e-9), 3),
        "monolithic_ttft_p99_ms": round(mono_p99 * 1000, 1),
        "ttft_p50_ms": {
            "monolithic": round(_percentile(mono_ttft, 0.5) * 1000, 1),
            "disagg": round(_percentile(dis_ttft, 0.5) * 1000, 1)},
        "tbt_p99_ms": {
            "monolithic": round(_percentile(mono_tbt, 0.99) * 1000, 1),
            "disagg": round(_percentile(dis_tbt, 0.99) * 1000, 1)},
        "goodput_tok_s": {
            "monolithic": round(mono_toks / mono_wall, 1),
            "disagg": round(dis_toks / dis_wall, 1)},
        "prefix_hit_rate": {
            "no_tier": round(rate_cold, 4),
            "spill_tier": round(rate_tier, 4),
            "tier_restored_blocks": st_tier.get(
                "kv_tier_restored_blocks", 0)},
        "token_exact": exact,
        "requests": args.requests,
        "groups": args.groups,
        "prefix_len": args.prefix_len,
        "budget": args.budget,
        "concurrency": args.concurrency,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "DISAGG_BENCH.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
