"""Podracer RL substrate benchmark: engine-backed rollout throughput,
publish wall, learner steps/s vs staleness bound.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}
and writes the full document to RL_BENCH.json.

Three measurements, one async-RL story:

1. Rollout tokens/s, speculative decoding ON vs OFF at fixed hardware
   (same nano model, same repetitive-prompt workload, greedy).  Spec
   decoding is token-exact, so on the rollout path it is a pure
   throughput multiplier over an UNCHANGED behavior policy — the bar is
   >= 1.2x at 1 lane (the overhead-bound regime), with the multi-lane
   row alongside.  Exactness is asserted, not assumed: the spec
   rollout's action tokens must equal the plain rollout's.

2. Publish wall as a fraction of rollout wall at the bench shape: a
   2-actor remote gang generates through real engines while the driver
   publishes a fresh weight version (one put + gang-wide adopt, wait
   for adoption) every round.  The bar is publish < 10% of rollout —
   in-place adoption by reference must be noise next to generation.

3. Learner steps/s vs staleness bound k on the CartPole loop: k=0
   forces on-policy (fragments racing a publish are dropped), larger k
   lets the learner train whatever the gang delivers.  The curve is the
   price of freshness — updates/s should rise from k=0 to k>=1.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import subprocess
import sys
import time


def _prompts(n, prompt_len, period, vocab):
    return [[(i * 17 + (j % period)) % vocab for j in range(prompt_len)]
            for i in range(n)]


def _make_actor(spec_k, lanes, args, params):
    from ray_tpu.rl import EngineRolloutActor
    return EngineRolloutActor(
        "gpt", args.config, params=params, max_lanes=lanes,
        spec_k=spec_k, temperature=0.0, seed=0, block_size=16,
        max_seq_len=args.prompt_len + args.new_tokens + args.spec_k + 16,
        prefill_chunk=args.prompt_len)


def _warm(actor, prompts, spec_k):
    """Compile outside the timed region: prefill + T=1 via a short
    rollout, then every verify width spec may dispatch."""
    actor.rollout(prompts[:1], max_new_tokens=4)
    eng = actor.engine
    if spec_k:
        eng._run_step(eng._upload(eng._build_batch([], 1)[0]))
        for t in range(2, spec_k + 2):
            eng._run_step(eng._upload(eng._build_batch([], t)[0]), True)


def _timed_rollout(actor, prompts, new_tokens):
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        batch, _version, metrics = actor.rollout(prompts, new_tokens)
        dt = time.perf_counter() - t0
    finally:
        gc.enable()
    return metrics["tokens"] / dt, batch


def bench_rollout_spec(args):
    rows = []
    params = None
    for lanes in (1, 4):
        plain = _make_actor(0, lanes, args, params)
        params = plain.engine.params
        spec = _make_actor(args.spec_k, lanes, args, params)
        prompts = _prompts(lanes, args.prompt_len, args.period,
                           plain.engine.config.vocab_size)
        _warm(plain, prompts, 0)
        _warm(spec, prompts, args.spec_k)
        plain_tps, pb = _timed_rollout(plain, prompts, args.new_tokens)
        spec_tps, sb = _timed_rollout(spec, prompts, args.new_tokens)
        assert (sb["actions"] == pb["actions"]).all(), \
            "speculative rollout diverged from the plain behavior policy"
        st = spec.engine.stats()
        rows.append({
            "lanes": lanes,
            "plain_tokens_per_sec": round(plain_tps, 1),
            "spec_tokens_per_sec": round(spec_tps, 1),
            "speedup": round(spec_tps / plain_tps, 3),
            "accepted_per_step": round(st["spec_accepted_per_step"], 3),
        })
        plain.engine.shutdown()
        spec.engine.shutdown()
    return rows


def bench_publish_vs_rollout(args):
    import ray_tpu
    from ray_tpu.rl import EngineRolloutActor, WeightPublisher

    remote_cls = ray_tpu.remote(num_cpus=1)(EngineRolloutActor)
    actors = [remote_cls.remote(
        "gpt", args.config, max_lanes=args.gang_lanes, spec_k=args.spec_k,
        temperature=0.0, seed=i, block_size=16,
        max_seq_len=args.prompt_len + args.new_tokens + args.spec_k + 16,
        prefill_chunk=args.prompt_len) for i in range(args.gang_size)]
    prompts = _prompts(args.gang_lanes, args.prompt_len, args.period, 256)
    # Warmup round compiles each remote engine (and its spec widths via
    # the first drafted steps) outside the timed loop.
    ray_tpu.get([a.rollout.remote(prompts, args.new_tokens)
                 for a in actors])
    # Publish real params.  A worker builds the payload tree: this
    # driver's children hold the devices, so it must not touch jax.
    @ray_tpu.remote(num_cpus=1)
    def payload(config):
        import jax
        import numpy as np

        from ray_tpu.models import gpt
        return jax.tree.map(np.asarray, gpt.init_params(
            gpt.CONFIGS[config], jax.random.key(0)))

    weights = ray_tpu.get(payload.remote(args.config))
    publisher = WeightPublisher()
    rollout_wall = publish_wall = 0.0
    tokens = 0
    for round_i in range(args.rounds):
        t0 = time.perf_counter()
        publisher.publish(weights, actors, version=round_i + 1, wait=True)
        publish_wall += time.perf_counter() - t0
        t0 = time.perf_counter()
        out = ray_tpu.get([a.rollout.remote(prompts, args.new_tokens)
                           for a in actors])
        rollout_wall += time.perf_counter() - t0
        tokens += sum(m["tokens"] for _b, _v, m in out)
        for _b, v, _m in out:
            assert v == round_i + 1, "gang missed a version boundary"
    for a in actors:
        ray_tpu.kill(a)
    return {
        "gang_size": args.gang_size,
        "rounds": args.rounds,
        "rollout_tokens_per_sec": round(tokens / rollout_wall, 1),
        "rollout_wall_s": round(rollout_wall, 3),
        "publish_wall_s": round(publish_wall, 3),
        "publish_frac_of_rollout": round(publish_wall / rollout_wall, 4),
    }


def bench_learner_vs_staleness(args):
    from ray_tpu.rl import PodracerConfig
    rows = []
    for k in (0, 1, 2):
        cfg = (PodracerConfig().environment("CartPole-v1")
               .rollouts(num_rollout_workers=2, num_envs_per_worker=8,
                         rollout_fragment_length=32)
               .training(staleness_bound=k, publish_interval=1,
                         min_updates_per_step=2)
               .debugging(seed=0))
        algo = cfg.build()
        try:
            for _ in range(2):   # spawn + compile outside the window
                algo.train()
            u0 = algo.learner.num_updates
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < args.learner_window_s:
                r = algo.train()
            dt = time.perf_counter() - t0
            st = r["queue"]
            rows.append({
                "staleness_bound": k,
                "updates_per_sec": round(
                    (algo.learner.num_updates - u0) / dt, 2),
                "stale_dropped": st["stale_dropped"],
                "accepted": st["accepted"],
            })
        finally:
            algo.stop()
    return rows


def main():
    from ray_tpu._private import compile_cache
    compile_cache.place()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="nano")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--period", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=96)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--gang-size", type=int, default=2)
    ap.add_argument("--gang-lanes", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--learner-window-s", type=float, default=6.0)
    ap.add_argument("--phase", choices=["spec", "publish", "learner"],
                    default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.phase == "spec":
        print(json.dumps(bench_rollout_spec(args)))
        return
    if args.phase is not None:
        import ray_tpu
        ray_tpu.init(num_cpus=max(4, args.gang_size + 2),
                     object_store_memory=128 << 20)
        try:
            fn = (bench_publish_vs_rollout if args.phase == "publish"
                  else bench_learner_vs_staleness)
            print(json.dumps(fn(args)))
        finally:
            ray_tpu.shutdown()
        return

    def run(phase):
        """Each phase in a fresh interpreter: the in-process engines, the
        engine gang and the driver-side learner each need the device, and
        a process that has touched jax keeps it from the next."""
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--phase", phase]
            + sys.argv[1:], capture_output=True, text=True, timeout=1800)
        if p.returncode != 0:
            raise SystemExit(f"{phase} phase failed:\n{p.stderr[-2000:]}")
        return json.loads(p.stdout.strip().splitlines()[-1])

    spec_rows = run("spec")
    pub = run("publish")
    learner_rows = run("learner")

    top = next(r for r in spec_rows if r["lanes"] == 1)
    doc = {
        "metric": "rl_rollout_spec_tokens_per_sec",
        "value": top["spec_tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": top["speedup"],
        "accepted_per_step": top["accepted_per_step"],
        "spec_k": args.spec_k,
        "config": args.config,
        "new_tokens": args.new_tokens,
        "rollout_by_lanes": spec_rows,
        "publish": pub,
        "learner_by_staleness_bound": learner_rows,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "RL_BENCH.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
