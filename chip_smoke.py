#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of gpt2-small with seeded random weights:

  kernels  own subprocess: the Pallas kernels compiled by Mosaic
           (interpret=False) against their XLA references;
  train    ray_tpu.init() -> JaxTrainer -> gpt.make_train_step, b24 x 1024,
           2 warm-up + 8 steps; on a multi-chip host also under dp x tp2 and
           fsdp meshes over every chip, against the one-chip first loss;
  serve    serve.run(LLMDeployment x <chips>) -> 8 concurrent streaming
           requests, then sequential repeats of one greedy prompt.

This process never imports jax: a parent that has touched jax holds the
chip, and the workers that need it would fail.  Any failed check raises;
the exit code is then non-zero and no result is printed.  The last line
of stdout is `{"ok": true, "device": {"platform", "kind", "count"}}` as jax
reports the device; the line before it, `[chip_smoke] summary {...}`, holds
the per-leg facts.  Nothing there is a speed: wall and compile seconds are
set-up facts of one run.

Usage: python3 chip_smoke.py          (needs a TPU; about 3 minutes cold)
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import signal
import subprocess
import sys
import threading
import time

from ray_tpu._private import compile_cache

TIME_LIMIT_S = 1150          # the driver allows 1200 s, compilation included
LOSS_TOL = 0.05              # |mesh first loss - one-chip first loss|


@dataclasses.dataclass(frozen=True)
class Sizes:
    platform: str = "tpu"
    model: str = "gpt2-small"
    batch: int = 24           # the only train shape with history (PERF.md)
    seq: int = 1024
    warmup: int = 2
    steps: int = 8
    lanes: int = 32
    prompt_lens: tuple = (128, 192, 256, 320, 384, 448, 512, 300)
    shared_head: int = 256    # prompts 6 and 7 share their first tokens
    new_tokens: int = 64


FULL = Sizes()


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(ok, why) -> None:
    """A check that survives `python -O`."""
    if not ok:
        raise AssertionError(why)


# ---------------------------------------------------------------------------
# Leg 1: kernels (runs in its own interpreter and exits, freeing the chip)
# ---------------------------------------------------------------------------

def kernels_leg() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import attention as A

    t_leg = time.perf_counter()
    dev = jax.devices()
    require(dev[0].platform == "tpu", (
        f"jax found platform {dev[0].platform!r}, not a TPU"))
    compile_s = 0.0

    def compiled(fn, *args):
        nonlocal compile_s
        t0 = time.perf_counter()
        c = jax.jit(fn).lower(*args).compile()
        compile_s += time.perf_counter() - t0
        require("tpu_custom_call" in c.as_text(), "no Mosaic call compiled")
        return c

    def check(name, got, ref):
        got, ref = (np.asarray(x, np.float32) for x in (got, ref))
        # two bf16 ulps at the reference's largest magnitude
        tol = 2.0 ** -6 * max(1.0, float(np.abs(ref).max()))
        err = float(np.abs(got - ref).max())
        require(np.isfinite(got).all() and err <= tol, (
            f"{name}: max |kernel - reference| = {err} > {tol}"))
        return err

    errs = {}
    key = jax.random.key(0)

    def flash(q, k, v):
        return A.flash_attention(q, k, v, causal=True, interpret=False)

    def ref(q, k, v):
        return A.reference_attention(q, k, v, causal=True)

    # gpt2-small's heads, and a kv head's group of 4 query heads of 128 over
    # 2,048 positions (a grid step of the backward takes two of them and
    # holds bytes of the whole length beside its blocks)
    for name, (batch, length, heads, kv_heads, d) in (
            ("flash", (4, 1024, 12, 12, 64)),
            ("flash_group", (1, 2048, 8, 2, 128))):
        q, k, v, g = (jax.random.normal(
            jax.random.fold_in(key, i),
            (batch, length, kv_heads if i in (1, 2) else heads, d),
            jnp.bfloat16) for i in range(4))

        def grads(fn):
            return jax.grad(lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32)),
                argnums=(0, 1, 2))

        errs[f"{name}_fwd"] = check(
            f"{name} fwd", compiled(flash, q, k, v)(q, k, v), ref(q, k, v))
        for grad, got, want in zip(("dq", "dk", "dv"),
                                   compiled(grads(flash), q, k, v)(q, k, v),
                                   jax.jit(grads(ref))(q, k, v)):
            errs[f"{name}_{grad}"] = check(f"{name} {grad}", got, want)

    # the loss head's kernel at gpt2-small's head, a row tile of a chunk
    from ray_tpu.ops import cross_entropy as C
    x = jax.random.normal(jax.random.fold_in(key, 4), (2048, 768),
                          jnp.bfloat16)
    w = (0.02 * jax.random.normal(jax.random.fold_in(key, 5), (50304, 768))
         ).astype(jnp.bfloat16)
    logits, lse = compiled(C.logits_lse, x, w)(x, w)
    want = jax.lax.dot(x, w.T, preferred_element_type=jnp.float32)
    errs["logits_lse_logits"] = check("logits_lse logits", logits, want)
    errs["logits_lse_lse"] = check(
        "logits_lse lse", lse, jax.scipy.special.logsumexp(want, axis=-1))
    # and the kernel that makes dx and dhead of those logits, against the
    # two XLA products it replaces; the running dhead is not zero
    targets = jax.random.randint(jax.random.fold_in(key, 6), (2048,), 0,
                                 50304)
    scale = jnp.full((2048,), 1.0 / 2048, jnp.float32).at[:9].set(0.0)
    dhead = 1e-3 * jax.random.normal(jax.random.fold_in(key, 7),
                                     (50304, 768), jnp.float32)

    def products(logits, lse, targets, scale, x, w, dhead):
        p = ((jnp.exp(logits - lse[:, None]) - jax.nn.one_hot(
            targets, w.shape[0])) * scale[:, None]).astype(x.dtype)
        return jax.lax.dot(p, w), dhead + jax.lax.dot(
            p.T, x, preferred_element_type=jnp.float32)

    args = (logits, lse, targets, scale, x, w, dhead)
    for name, got, want in zip(
            ("dx", "dhead"), compiled(C.loss_head_grads, *args)(*args),
            jax.jit(products)(*args)):
        # gradients of a mean over 2,048 rows: held to a relative error
        top = float(jnp.abs(want).max())
        errs[f"loss_head_grads_{name}"] = check(
            f"loss_head_grads {name}", got / top, want / top) * top

    lanes, bs, nb, mb = 32, 16, 2048, 64
    rng = np.random.default_rng(0)
    tables = jnp.asarray(rng.permutation(nb)[:lanes * mb]
                         .reshape(lanes, mb).astype(np.int32))
    ctx_lens = jnp.asarray(rng.integers(1, mb * bs + 1, lanes)
                           .astype(np.int32))          # ragged
    # Pools as the engine stores them, [L, NB, BS, W], read at layer 1;
    # gpt2-xl's 25 x 64 row is the one that is padded (1600 -> 1664).
    layer = jnp.asarray(1, jnp.int32)
    for name, h, kh, d in (("gpt2-small", 12, 12, 64),
                           ("gpt2-xl", 25, 25, 64),
                           ("llama-1b", 32, 4, 64)):
        kk = jax.random.fold_in(key, h)
        pq = jax.random.normal(kk, (lanes, h, d), jnp.bfloat16)
        kp, vp = (A.pack_kv_rows(jax.random.normal(
            jax.random.fold_in(kk, i), (2, nb, bs, kh, d), jnp.bfloat16))
            for i in (1, 2))

        def paged(*a, kh=kh):
            return A.paged_decode_attention(*a, kv_heads=kh, use_kernel=True,
                                            interpret=False)

        got = compiled(paged, pq, kp, vp, tables, ctx_lens, layer)(
            pq, kp, vp, tables, ctx_lens, layer)
        want = A.paged_attention_reference(
            pq[:, None], kp, vp, tables, ctx_lens,
            (ctx_lens - 1)[:, None], layer, kv_heads=kh)[:, 0]
        errs[f"paged_{name}"] = check(f"paged decode {name}", got, want)
        # The T = 32 step's tiles (plain XLA, no Mosaic call) on the same
        # pools: every lane's last rows as one chunk, short where the
        # context is, one lane in four with no valid row.
        t = 32
        cq = jax.random.normal(jax.random.fold_in(kk, 3), (lanes, t, h, d),
                               jnp.bfloat16)
        start = jnp.maximum(ctx_lens - t, 0)
        pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        valid = (pos < ctx_lens[:, None]) & (jnp.arange(lanes) % 4 < 3)[:, None]
        got = jax.jit(functools.partial(A.paged_chunk_attention, kv_heads=kh))(
            cq, kp, vp, tables, ctx_lens, pos, valid, layer)
        want = A.paged_attention_reference(cq, kp, vp, tables, ctx_lens, pos,
                                           layer, kv_heads=kh)
        keep = valid[..., None, None]
        errs[f"paged_t32_{name}"] = check(
            f"paged chunk T=32 {name}", jnp.where(keep, got, 0),
            jnp.where(keep, want, 0))
        require(not np.asarray(got[3::4], np.float32).any(),
                f"paged chunk T=32 {name}: a lane without work is not zero")

    return {"platform": dev[0].platform, "device_kind": dev[0].device_kind,
            "n_devices": len(dev), "max_abs_err": errs,
            "compile_s": round(compile_s, 1),
            "wall_s": round(time.perf_counter() - t_leg, 1)}


def run_kernels_leg() -> dict:
    """The kernels leg in a fresh interpreter; its last stdout line is its
    JSON result.  A non-zero exit raises."""
    proc = subprocess.run([sys.executable, os.path.abspath(__file__),
                           "kernels"], stdout=subprocess.PIPE, text=True,
                          check=True, timeout=600)
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# Leg 2: train (the loop runs in the JaxTrainer worker that leases the chips)
# ---------------------------------------------------------------------------

def train_loop(config: dict) -> None:
    import jax
    import numpy as np
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshConfig, create_mesh, shard_batch
    from ray_tpu.train import session

    sz = Sizes(**config["sizes"])
    devices = jax.devices()
    n = len(devices)
    require(devices[0].platform == sz.platform, (
        f"train worker is on {devices[0].platform!r}, not {sz.platform!r}"))
    require(n == config["n_devices"], (
        f"worker sees {n} devices, the host has {config['n_devices']}"))
    cfg = gpt.CONFIGS[sz.model]
    tokens = jax.random.randint(jax.random.key(1), (sz.batch, sz.seq), 0,
                                cfg.vocab_size)
    total = sz.warmup + sz.steps

    def run_layout(name, mesh_cfg, steps):
        mesh = create_mesh(mesh_cfg, devices=devices) if mesh_cfg else None
        init_state, train_step = gpt.make_train_step(
            cfg, optax.adamw(1e-4), mesh)
        t0 = time.perf_counter()
        state = init_state(jax.random.key(0))
        batch = {"tokens": tokens}
        if mesh is not None:
            batch = shard_batch(mesh, batch)
        jax.block_until_ready(state)
        init_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        step = jax.jit(train_step, donate_argnums=0).lower(
            state, batch).compile()
        compile_s = time.perf_counter() - t0
        custom_calls = step.as_text().count("tpu_custom_call")
        for i in range(steps):
            state, metrics = step(state, batch)
            session.report({"layout": name, "step": i,
                            "warmup": i < sz.warmup,
                            "loss": float(metrics["loss"])})
        facts = {"layout": name, "init_s": round(init_s, 1),
                 "compile_s": round(compile_s, 1),
                 "custom_calls": custom_calls}
        if mesh is not None:
            # Every device must hold a shard of the state, and the big
            # weights must actually be split, not replicated n times.
            w = state["params"]["blocks"]["w_up"]
            holders = {s.device for s in w.addressable_shards}
            require(holders == set(devices), (
                f"{name}: w_up lives on {len(holders)} of {n} devices"))
            shard = w.addressable_shards[0].data.shape
            require(np.prod(shard) < np.prod(w.shape), (
                f"{name}: w_up is replicated, shard {shard} of {w.shape}"))
            facts["w_up_shard"] = [list(shard), list(w.shape)]
        return facts

    layouts = []
    if n == 1:
        layouts.append(run_layout("one-chip", None, total))
    else:
        layouts.append(run_layout("one-chip", None, 1))
        layouts.append(run_layout(f"dp{n // 2}xtp2",
                                  MeshConfig(data=n // 2, tensor=2), total))
        layouts.append(run_layout(f"fsdp{n}",
                                  MeshConfig(data=1, fsdp=n), total))
    mem = devices[0].memory_stats() or {}
    session.report({"final": True, "backend": jax.default_backend(),
                    "device_kind": devices[0].device_kind,
                    "n_devices": n, "layouts": layouts,
                    "chips": os.environ.get("RAY_TPU_CHIPS", ""),
                    "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
                    "peak_bytes_reserved": mem.get("peak_bytes_reserved"),
                    "bytes_limit": mem.get("bytes_limit")})


def train_leg(sz: Sizes, n: int) -> dict:
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train import JaxTrainer

    t_leg = time.perf_counter()
    result = JaxTrainer(
        train_loop,
        train_loop_config={"sizes": dataclasses.asdict(sz), "n_devices": n},
        scaling_config=ScalingConfig(num_workers=1)).fit()
    if result.error is not None:
        raise result.error
    final = result.metrics
    require(final.get("final") and final["backend"] == sz.platform, final)
    by_layout: dict = {}
    for m in result.metrics_history[:-1]:
        by_layout.setdefault(m["layout"], []).append(m["loss"])
    expect_kernels = sz.platform == "tpu"
    first_one_chip = by_layout["one-chip"][0]
    out = {}
    for facts in final["layouts"]:
        name = facts["layout"]
        losses = by_layout[name]
        require(all(x == x and abs(x) < 1e4 for x in losses), (name, losses))
        if expect_kernels:
            require(facts["custom_calls"] > 0, (
                f"{name}: compiled train step has no tpu_custom_call"))
        if len(losses) > 1:
            require(losses[-1] < losses[0], (
                f"{name}: loss did not fall: {losses}"))
        require(abs(losses[0] - first_one_chip) <= LOSS_TOL, (
            f"{name}: first loss {losses[0]} vs one-chip {first_one_chip}"))
        out[name] = dict(facts, first_loss=round(losses[0], 4),
                         last_loss=round(losses[-1], 4), steps=len(losses))
        say(f"train {name}: loss {losses[0]:.4f} -> {losses[-1]:.4f} over "
            f"{len(losses)} step(s), {facts['custom_calls']} kernel calls")
    return {"wall_s": round(time.perf_counter() - t_leg, 1),
            "compile_s": round(sum(f["compile_s"]
                                   for f in final["layouts"]), 1),
            "chips": final["chips"], "layouts": out,
            "peak_bytes_in_use": final["peak_bytes_in_use"],
            "peak_bytes_reserved": final["peak_bytes_reserved"],
            "bytes_limit": final["bytes_limit"]}


# ---------------------------------------------------------------------------
# Leg 3: serve
# ---------------------------------------------------------------------------

def _wait_chips_free(n: int, timeout: float = 120.0) -> None:
    """The trainer's worker must be gone before replicas can lease."""
    import ray_tpu
    deadline = time.monotonic() + timeout
    while ray_tpu.available_resources().get("TPU", 0) < n:
        if time.monotonic() > deadline:
            raise TimeoutError("the trainer never returned its chips")
        time.sleep(0.5)


def serve_leg(sz: Sizes, n: int) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import gpt

    t_leg = time.perf_counter()
    if sz.platform == "tpu":
        _wait_chips_free(n)
    handle = serve.run(serve.LLMDeployment.options(num_replicas=n).bind(
        model="gpt", config=sz.model, max_lanes=sz.lanes))
    ready_s = time.perf_counter() - t_leg

    vocab = gpt.CONFIGS[sz.model].vocab_size
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, size=m).tolist()
               for m in sz.prompt_lens]
    prompts[7][:sz.shared_head] = prompts[6][:sz.shared_head]
    # Greedy, plus one seeded sampled request.
    sampling = [{}] * 7 + [{"temperature": 0.8, "seed": 1234}]

    def generate(prompt, **kw):
        return list(handle.options("generate").stream(
            prompt, max_new_tokens=sz.new_tokens, **kw))

    outputs: list = [None] * len(prompts)

    def worker(i):
        outputs[i] = generate(prompts[i], **sampling[i])

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    for i, toks in enumerate(outputs):
        require(toks is not None and len(toks) == sz.new_tokens, (
            f"request {i}: {None if toks is None else len(toks)} tokens"))
        require(all(0 <= t < vocab for t in toks), f"request {i}: bad token")
    # Sequential requests on an idle deployment are routed round-robin, so
    # n + 1 repeats of one greedy prompt reach every replica, and one of
    # them twice: a prefix hit, and the same tokens from every chip.
    for r in range(n + 1):
        again = generate(prompts[0])
        require(again == outputs[0], (
            f"greedy repeat {r} differs: {again[:8]} vs {outputs[0][:8]}"))

    controller = ray_tpu.get_actor("SERVE_CONTROLLER", "serve")
    replicas = ray_tpu.get(controller.get_routing.remote("llm"),
                           timeout=60)["replicas"]
    require(len(replicas) == n, f"{len(replicas)} replicas for {n} chips")
    stats = ray_tpu.get([r.handle_request.remote("stats", (), {})
                         for r in replicas], timeout=120)
    steps = ray_tpu.get([r.handle_request.remote("compiled_steps", (), {})
                         for r in replicas], timeout=600)
    # K and V pools, which the decode step must write and read where they
    # are: donated, and no instruction that copies, slices out or stacks
    # back the pool or a layer of it.
    c = gpt.CONFIGS[sz.model]
    pool_bytes = 2 * c.n_layers * (sz.lanes * c.max_seq_len) * c.d_model * 2
    for st, cs in zip(stats, steps):
        require(st["backend"] == sz.platform, st)
        require(st["prefix_hits"] + st["prefix_misses"] > 0, (
            f"replica on chips {st['chips']} answered no request"))
        if sz.platform == "tpu":
            require(len(st["chips"]) == 1, st["chips"])
            decode = cs["t1"]
            require(decode["custom_calls"] > 0, (
                f"T=1 decode step has no tpu_custom_call: {cs}"))
            require(decode["donated_bytes"] >= pool_bytes, (
                f"KV pools not donated: {decode} < {pool_bytes}"))
            require(decode["pool_copies"] == 0, (
                f"T=1 decode step moves the KV pool: {decode}"))
            # ... and multiply the weights the engine prepared as they
            # are held: no cast, turn-round or re-made copy of a matrix.
            copied = decode["weight_bytes_copied"]
            require(not set(copied) & {"convert", "copy", "transpose",
                                       "remat"}, (
                f"T=1 decode step re-makes its weights: {copied}"))
            say(f"serve: T=1 step on chips {st['chips']}: temp_bytes "
                f"{decode['temp_bytes']}, donated_bytes "
                f"{decode['donated_bytes']}, pool_copies 0, "
                f"weight_bytes_copied {copied}")
    chips = [tuple(st["chips"]) for st in stats]
    if sz.platform == "tpu":
        require(len(set(chips)) == n, f"replicas share chips: {chips}")
    hit_tokens = sum(st["prefix_hit_tokens"] for st in stats)
    require(hit_tokens > 0, "no prompt token was served from the prefix cache")
    say(f"serve: {n} replica(s) on chips {chips}, "
        f"{len(prompts) + n + 1} requests, {hit_tokens} prefix-hit tokens")
    return {"wall_s": round(time.perf_counter() - t_leg, 1),
            "ready_s": round(ready_s, 1),
            "compile_s": round(max(sum(s["compile_s"] for s in cs.values())
                                   for cs in steps), 1),
            "replicas": [{"chips": st["chips"], "backend": st["backend"],
                          "device_kind": st["device_kind"],
                          "requests": st["prefix_hits"] + st["prefix_misses"],
                          "steps": cs}
                         for st, cs in zip(stats, steps)],
            "prefix_hit_tokens": hit_tokens,
            "kv_pool_bytes": pool_bytes}


# ---------------------------------------------------------------------------

def main() -> None:
    def on_alarm(signum, frame):
        raise TimeoutError(f"chip_smoke exceeded {TIME_LIMIT_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(TIME_LIMIT_S)
    t_start = time.perf_counter()
    cache_dir = compile_cache.place()
    entries_before = compile_cache.entry_count(cache_dir)

    say("kernels leg")
    kernels = run_kernels_leg()
    n = kernels["n_devices"]
    say(f"kernels ok on {n} x {kernels['device_kind']}: max abs errors "
        f"{kernels['max_abs_err']}")

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init()
    try:
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        require(advertised == n, (
            f"ray_tpu.init() advertises TPU: {advertised}, jax sees {n}"))
        say("train leg")
        train = train_leg(FULL, n)
        say("serve leg")
        served = serve_leg(FULL, n)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    device = {"platform": kernels["platform"],
              "kind": kernels["device_kind"], "count": n}
    say("summary " + json.dumps({
        "legs": {"kernels": kernels, "train": train, "serve": served},
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": compile_cache.entry_count(cache_dir)},
        "wall_s": round(time.perf_counter() - t_start, 1)}))
    # The driver's contract: the last line is this object and nothing more.
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    if sys.argv[1:] == ["kernels"]:
        print(json.dumps(kernels_leg()))
    else:
        main()
