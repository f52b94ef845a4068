"""Train cells held to their STATE: `drivers/train.py`'s run, and a check
of the parameters' change beside its check of the losses.

`train` compares the losses of the first steps with the plain reference's.
On fresh random batches that says the forward pass is right and nothing of
the update: step i's batch was never seen, so what the optimizer changed
shows in no loss, and parameters kept in a lower precision than the
configuration states, a moment left unchanged or a wrong update pass any
limit that bf16 activations pass (PERF.md section 6, PR 61).  This driver
is the same path (one leased worker, `make_train_step`, the same warm-up,
window, traced slice and result keys, so every reader reads it as it reads
`train`'s), and after `check.reference_steps` steps it also compares the
parameters themselves:

    params_change_diff       |p_system - p_reference| / |p_reference - p_0|
                             over the whole tree (Euclidean norms)
    params_change_diff_leaf  the largest such quotient of a single leaf

p_0 the seed's parameters, both sides `reference_steps` optimizer steps
later.  A state left unchanged reads 1; equal updates read 0; what sound
runs read is the optimizer's answer to rounding noise (AdamW divides an
element's gradient by its own size, so an element whose gradient is bf16
noise moves by the learning rate either way).  The system's parameters
are copied to the host during warm-up (set-up, not the window) because the
step donates its state.

Where the step's metrics carry `expert_load` ([expert layers, held]: the
assignments each held expert took), the run carries `expert_rows`: the rows
a layer's held experts multiplied, by layer, the mean over the window's
steps and over the traced steps (device arrays kept by reference and
fetched behind the window and the trace: no transfer inside either).  The
readers of the experts' roofline and of the step's model FLOPs count those
rows and not an even router's.

Traffic file keys: `train`'s, and `check.params_change`: {"whole": limit,
"leaf": limit}.

`drivers/train.py` is not this PR's to edit (an accepted benchmark file);
a `benchmark` PR folds this check into it (PERF.md section 7).
"""

from __future__ import annotations

import collections
import importlib
import os
import time

from benchmark import manifest, trace_reduce


def _rows_by_layer(loads) -> list:
    """The mean over steps of each expert layer's summed `expert_load`;
    [] where the step reports none."""
    import numpy as np
    if not loads or loads[0] is None:
        return []
    return np.mean([np.asarray(load).sum(-1) for load in loads], 0).tolist()


def params_change(got, want, first) -> dict:
    """How far the parameters `got` are from `want` as a share of how far
    `want` moved from `first` (three trees of one structure, on the host or
    the device): {"whole", "leaf", "worst": the leaf that read `leaf`}.
    A leaf that did not move on the reference's side is left out of `leaf`
    and counts in `whole`."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def squares(a, b, c):
        a, b, c = (x.astype(jnp.float32) for x in (a, b, c))
        return jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b - c))

    off = moved = 0.0
    leaf, worst = 0.0, None
    for (path, a), b, c in zip(jax.tree_util.tree_leaves_with_path(got),
                               jax.tree.leaves(want), jax.tree.leaves(first)):
        num, den = (float(x) for x in squares(a, b, c))
        off, moved = off + num, moved + den
        if den > 0 and (num / den) ** 0.5 > leaf:
            leaf, worst = (num / den) ** 0.5, jax.tree_util.keystr(path)
    return {"whole": (off / moved) ** 0.5 if moved > 0 else float("inf"),
            "leaf": leaf, "worst": worst}


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
        if i == check["reference_steps"]:
            # what the optimizer made of the parameters so far, to the host:
            # the next step gives this state up
            held = jax.device_get(state["params"])
        state, metrics = step(state, batches[i % len(batches)])
        losses.append(float(metrics["loss"]))
    session.report({"event": "setup", "init_s": init_s,
                    "compile_s": compile_s, "kernel_calls": kernel_calls,
                    "warmup_losses": losses})

    # -- the measured window: nothing below compiles -------------------------
    every = traffic["report_every"]
    pending = collections.deque()
    loads = []
    steps = n_warm
    done = 0
    shown = None
    t_start_wall = time.time()
    t_start = time.perf_counter()
    while True:
        state, metrics = step(state, batches[steps % len(batches)])
        pending.append(metrics["loss"])
        loads.append(metrics.get("expert_load"))
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
    expert_rows = {"window": _rows_by_layer(loads)}

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
        loads = []
        for i in range(traffic["trace_steps"]):
            with jax.profiler.StepTraceAnnotation("bench/train_step",
                                                  step_num=i):
                state, metrics = step(state, batches[i % len(batches)])
            pending.append(metrics["loss"])
            loads.append(metrics.get("expert_load"))
            if len(pending) > 2:
                with jax.profiler.TraceAnnotation("bench/wait_loss"):
                    pending.popleft().block_until_ready()
        with jax.profiler.TraceAnnotation("bench/wait_last_loss"):
            jax.block_until_ready(metrics)
        jax.profiler.stop_trace()
        traced = {"step_ms": step_ms, "trace_dir": trace_dir}
        expert_rows["traced"] = _rows_by_layer(loads)

    # -- correctness, outside every metric: the plain reference takes the
    # same first steps from the same seed (the step donated the state, so
    # the initial parameters are made again) ---------------------------------
    del state, metrics, loads
    pending.clear()
    reference = manifest.module("reference",
                                spec["config"]["reference_module"])
    t0 = time.perf_counter()
    params = init_state(key)["params"]
    ref_losses = {}
    opt_state = getattr(reference, opt["name"] + "_init")(params)
    for i in range(check["reference_steps"]):
        ref_losses[i], grads = reference.loss_and_grad(
            params, batches[i % len(batches)]["tokens"],
            check["micro_batch"])
        params, opt_state = getattr(reference, opt["name"] + "_step")(
            params, grads, opt_state, **opt["args"])
        del grads
    del opt_state
    last = check["reference_steps"]
    ref_losses[last] = reference.loss_by_layer(
        params, batches[last % len(batches)]["tokens"], check["micro_batch"])
    checks = [{"step": i, "system": losses[i], "reference": ref,
               "tolerance": check["tolerance"][str(i)],
               "ok": abs(losses[i] - ref) <= check["tolerance"][str(i)]}
              for i, ref in sorted(ref_losses.items())]
    change = params_change(held, params, init_state(key)["params"])
    reference_s = time.perf_counter() - t0

    session.report({
        "event": "final", "window": window, "traced": traced,
        "checks": checks, "params_change": change,
        "expert_rows": expert_rows,
        "reference_s": reference_s,
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
    compared = {
        f"loss_diff_step{c['step']}": {
            "value": abs(c["system"] - c["reference"]),
            "limit": c["tolerance"]} for c in final["checks"]}
    change, limits = final["params_change"], traffic["check"]["params_change"]
    compared["params_change_diff"] = {"value": change["whole"],
                                      "limit": limits["whole"]}
    compared["params_change_diff_leaf"] = {"value": change["leaf"],
                                           "limit": limits["leaf"]}
    for c in final["checks"]:
        say(f"reference check, loss at step {c['step']}: system "
            f"{c['system']:.5f}, reference {c['reference']:.5f}")
    for name, c in compared.items():
        say(f"reference check, {name}: {c['value']:.5f} (limit {c['limit']}) "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    say(f"reference check, the leaf that moved farthest from the "
        f"reference's: {change['worst']}")
    for p in events.get("progress", []):
        say(f"step {p['step']}: loss {p['loss']:.4f}")
    for part, rows in final["expert_rows"].items():
        say(f"rows a layer's held experts took a step, {part}: "
            f"{[round(r) for r in rows]}")
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
        # (a value that is not a number is not under its limit)
        "correct": finite and all(c["value"] <= c["limit"]
                                  for c in compared.values()),
        "compared": compared,
        "attempted": window["steps"], "failed": 0 if finite else 1,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "device": dict(final["device"]),
        "fields": ctx["fields"], "traffic": traffic, "cell": cell,
        "compile_s": setup["compile_s"], "worker_ready_s": worker_ready_s,
        "memory": final["memory"],
        "expert_rows": final["expert_rows"],
        "notes": {"setup": setup, "window": window,
                  "checks": final["checks"], "params_change": change,
                  "expert_rows": final["expert_rows"]},
    }
    if ctx["trace"]:
        traced = final["traced"]
        run["step_ms"] = traced["step_ms"]
        trace_reduce.attach(run, traced["trace_dir"], ctx, say)
    return run
