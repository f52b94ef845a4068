"""Train cells: `JaxTrainer(train_loop).fit()` on one leased worker.

The parent calls `ray_tpu.init()` and the trainer, as a user would; the loop
below runs in the worker that the runtime leases the chips to.  It builds
the model from the configuration file, makes the train state on the device
from the seed, warms the one step program up and steps for `--seconds`.
After the window, in no metric, the configuration's plain reference
(`reference/<reference_module>.py`) takes the same first steps from the
same seed, and the first losses must agree.

Traffic file keys: `batch`, `seq`, `n_batches` (generator), `mesh` (null or
MeshConfig fields), `config_overrides` (job-level fields of the model config,
such as `remat`), `optimizer` (`name`: an optax constructor that the
reference has as `<name>_init` and `<name>_step`; `args`: its keywords),
`warmup_steps`, `report_every`, `check`, `trace_steps`, `sync_steps`,
`rehearsal` (overrides for a CPU rehearsal).
"""

from __future__ import annotations

import collections
import importlib
import os
import time

from benchmark import manifest, trace_reduce


# ---------------------------------------------------------------------------
# In the worker
# ---------------------------------------------------------------------------

def train_loop(spec: dict) -> None:
    import jax
    import optax

    from ray_tpu.parallel import MeshConfig, create_mesh, shard_batch
    from ray_tpu.train import session

    session.report({"event": "ready", "t": time.time()})
    traffic, cell = spec["traffic"], spec["cell"]
    devices = jax.devices()
    platform = devices[0].platform
    if not spec["rehearse"] and platform != "tpu":
        raise RuntimeError(f"the train worker is on {platform!r}, not a TPU")
    if len(devices) != cell["chips"]:
        raise RuntimeError(f"the worker sees {len(devices)} device(s); the "
                           f"cell asks for {cell['chips']}")
    module = importlib.import_module(spec["config"]["module"])
    cfg = manifest.model_config(spec["config"],
                                traffic.get("config_overrides"),
                                spec["rehearse"])
    mesh = (create_mesh(MeshConfig(**traffic["mesh"]), devices=devices)
            if traffic.get("mesh") else None)
    opt = traffic["optimizer"]
    init_state, train_step = module.make_train_step(
        cfg, getattr(optax, opt["name"])(**opt["args"]), mesh)

    t0 = time.perf_counter()
    key = jax.random.key(spec["seed"])
    state = init_state(key)
    batches = manifest.module("generators", traffic["generator"]).make(
        traffic, spec["seed"], cfg.vocab_size)
    batches = [{"tokens": b} for b in batches]
    if mesh is not None:
        batches = [shard_batch(mesh, b) for b in batches]
    jax.block_until_ready((state, batches))
    init_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    step = jax.jit(train_step, donate_argnums=0).lower(
        state, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    kernel_calls = step.as_text().count("tpu_custom_call")

    check = traffic["check"]
    losses = []
    n_warm = max(traffic["warmup_steps"], check["reference_steps"] + 1)
    for i in range(n_warm):
        state, metrics = step(state, batches[i % len(batches)])
        losses.append(float(metrics["loss"]))
    session.report({"event": "setup", "init_s": init_s,
                    "compile_s": compile_s, "kernel_calls": kernel_calls,
                    "warmup_losses": losses})

    # -- the measured window: nothing below compiles -------------------------
    every = traffic["report_every"]
    pending = collections.deque()
    steps = n_warm
    done = 0
    shown = None
    t_start_wall = time.time()
    t_start = time.perf_counter()
    while True:
        state, metrics = step(state, batches[steps % len(batches)])
        pending.append(metrics["loss"])
        steps += 1
        done += 1
        if len(pending) > 2:
            # Two steps stay queued so the device never waits for the host;
            # the clock below is at most two steps ahead of the device.
            shown = pending.popleft()
            shown.block_until_ready()
        if done % every == 0 and shown is not None:
            session.report({"event": "progress", "step": done,
                            "loss": float(shown)})
        if time.perf_counter() - t_start >= spec["seconds"]:
            break
    final_loss = float(pending[-1])       # host fetch: the window's end
    window_s = time.perf_counter() - t_start
    window = {"steps": done, "seconds": window_s,
              "tokens": done * traffic["batch"] * traffic["seq"],
              "t_start": t_start_wall, "final_loss": final_loss}
    memory = manifest.memory_report([d.memory_stats() or {} for d in devices])

    traced = {}
    if spec["trace"]:
        step_ms = []
        for i in range(traffic["sync_steps"]):
            t0 = time.perf_counter()
            state, metrics = step(state, batches[(steps + i) % len(batches)])
            metrics["loss"].block_until_ready()
            step_ms.append((time.perf_counter() - t0) * 1e3)
        trace_dir = os.path.join(spec["out_dir"], "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        pending.clear()
        for i in range(traffic["trace_steps"]):
            with jax.profiler.StepTraceAnnotation("bench/train_step",
                                                  step_num=i):
                state, metrics = step(state, batches[i % len(batches)])
            pending.append(metrics["loss"])
            if len(pending) > 2:
                with jax.profiler.TraceAnnotation("bench/wait_loss"):
                    pending.popleft().block_until_ready()
        with jax.profiler.TraceAnnotation("bench/wait_last_loss"):
            jax.block_until_ready(metrics)
        jax.profiler.stop_trace()
        traced = {"step_ms": step_ms, "trace_dir": trace_dir}

    # -- correctness, outside every metric: the plain reference takes the
    # same first steps from the same seed (the step donated the state, so
    # the initial parameters are made again) ---------------------------------
    del state, metrics
    pending.clear()
    reference = manifest.module("reference",
                                spec["config"]["reference_module"])
    t0 = time.perf_counter()
    params = init_state(key)["params"]
    ref_losses = {}
    if check["reference_steps"]:
        opt_state = getattr(reference, opt["name"] + "_init")(params)
        for i in range(check["reference_steps"]):
            ref_losses[i], grads = reference.loss_and_grad(
                params, batches[i % len(batches)]["tokens"],
                check["micro_batch"])
            params, opt_state = getattr(reference, opt["name"] + "_step")(
                params, grads, opt_state, **opt["args"])
        del opt_state, grads
    last = check["reference_steps"]
    ref_losses[last] = reference.loss_by_layer(
        params, batches[last % len(batches)]["tokens"], check["micro_batch"])
    checks = [{"step": i, "system": losses[i], "reference": ref,
               "tolerance": check["tolerance"][str(i)],
               "ok": abs(losses[i] - ref) <= check["tolerance"][str(i)]}
              for i, ref in sorted(ref_losses.items())]
    reference_s = time.perf_counter() - t0

    session.report({
        "event": "final", "window": window, "traced": traced,
        "checks": checks, "reference_s": reference_s,
        "device": {"platform": platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": memory["memory_peak_bytes"]},
        "memory": memory,
        "chips": os.environ.get("RAY_TPU_CHIPS", "")})


# ---------------------------------------------------------------------------
# In the parent
# ---------------------------------------------------------------------------

def run(ctx: dict, say) -> dict:
    import ray_tpu
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train import JaxTrainer

    cell, traffic = ctx["cell"], ctx["traffic"]
    ray_tpu.init(**({"num_tpus": cell["chips"]} if ctx["rehearse"] else {}))
    try:
        t_fit = time.time()
        result = JaxTrainer(
            train_loop, train_loop_config=ctx,
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                tpus_per_worker=cell["chips"])).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    events = {}
    for m in result.metrics_history:
        events.setdefault(m["event"], []).append(m)
    setup, final = events["setup"][0], events["final"][0]
    window = final["window"]
    for c in final["checks"]:
        say(f"reference check, loss at step {c['step']}: system "
            f"{c['system']:.5f}, reference {c['reference']:.5f}, |diff| "
            f"{abs(c['system'] - c['reference']):.5f} (tolerance "
            f"{c['tolerance']}) {'ok' if c['ok'] else 'FAILED'}")
    for p in events.get("progress", []):
        say(f"step {p['step']}: loss {p['loss']:.4f}")
    tokens_per_s = window["tokens"] / window["seconds"]
    setup_s = window["t_start"] - ctx["t_process_start"]
    worker_ready_s = events["ready"][0]["t"] - t_fit
    say(f"window: {window['steps']} steps, {window['tokens']} tokens in "
        f"{window['seconds']:.3f} s = {tokens_per_s:.1f} tokens/s; final "
        f"loss {window['final_loss']:.4f}; setup {setup_s:.1f} s (worker "
        f"ready {worker_ready_s:.1f}, state {setup['init_s']:.1f}, step "
        f"compile {setup['compile_s']:.1f}); reference check after the "
        f"window {final['reference_s']:.1f} s; {setup['kernel_calls']} kernel calls "
        f"in the step; HBM {manifest.memory_line(final['memory'])}; chips "
        f"{final['chips'] or '-'}")
    finite = all(x == x and abs(x) < 1e4 for x in
                 setup["warmup_losses"] + [window["final_loss"]])
    run = {
        "correct": finite and all(c["ok"] for c in final["checks"]),
        "compared": {
            f"loss_diff_step{c['step']}": {
                "value": abs(c["system"] - c["reference"]),
                "limit": c["tolerance"]} for c in final["checks"]},
        "attempted": window["steps"], "failed": 0 if finite else 1,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "device": dict(final["device"]),
        "fields": ctx["fields"], "traffic": traffic, "cell": cell,
        "compile_s": setup["compile_s"], "worker_ready_s": worker_ready_s,
        "memory": final["memory"],
        "notes": {"setup": setup, "window": window,
                  "checks": final["checks"]},
    }
    if ctx["trace"]:
        traced = final["traced"]
        run["step_ms"] = traced["step_ms"]
        trace_reduce.attach(run, traced["trace_dir"], ctx, say)
    return run
