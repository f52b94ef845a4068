#!/usr/bin/env python3
"""What decides `correct` in `train_mellum2_8k_ep4share`, read both ways on
the chip at the cell's own sizes: the program's first losses against the
plain reference's (`benchmark/reference/mellum.py`), and the reference's
own losses when one thing is wrong that the cell's limits have to catch:

  bf16_params    the parameters rounded to bf16 at the start and after every
                 AdamW step (the nearest precision below the float32 the
                 configuration states)
  window_2048    the window layers attending 2,048 positions
  raw_topk       the chosen experts' probabilities not divided by their sum

Each row is the loss at steps 0, 1 and 2 (two AdamW steps between them, the
traffic file's optimizer) and its distance from the reference's, and the
parameters after those two steps against the reference's as
`drivers/train_state.py` compares them (`params_change`: the whole tree's
and the worst leaf's |p - p_reference| / |p_reference - p_0|).  A limit
belongs between the program's distance and the least of the wrong rows'
largest.  Run on the chip, from the root of a checkout:

  python3 benchmark/tools/mellum_precision.py [--seed N] [--cell NAME]
      [--rehearse] [--only program,reference,...]

The last line is one JSON object.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from benchmark import manifest
from benchmark.drivers.train_state import params_change
from benchmark.reference import mellum as reference

STEPS = 3
WRONG = {
    "bf16_params": {"round": True},
    "window_2048": {"sizes": {"window": 2048}},
    "raw_topk": {"sizes": {"norm_topk": False}},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--cell", default="train_mellum2_8k_ep4share")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    import optax
    m = manifest.load()
    cell = m.cells[args.cell]
    config, traffic = m.load_config(cell["config"]), m.load_traffic(
        cell["traffic"])
    if args.rehearse:
        traffic.update(traffic["rehearsal"])
    module = importlib.import_module(config["module"])
    cfg = manifest.model_config(config, traffic.get("config_overrides"),
                                args.rehearse)
    opt = traffic["optimizer"]
    init_state, train_step = module.make_train_step(
        cfg, getattr(optax, opt["name"])(**opt["args"]))
    key = jax.random.key(args.seed)
    batches = manifest.module("generators", traffic["generator"]).make(
        traffic, args.seed, cfg.vocab_size)
    micro = traffic["check"]["micro_batch"]
    only = set(filter(None, args.only.split(",")))
    rows, after = {}, {}     # (a row's parameters after two steps: host)

    if not only or "program" in only:
        state = init_state(key)
        step = jax.jit(train_step, donate_argnums=0)
        losses, loads = [], []
        for i in range(STEPS):
            if i == STEPS - 1:
                after["program"] = jax.device_get(state["params"])
            state, metrics = step(state, {"tokens": batches[i % len(batches)]})
            losses.append(float(metrics["loss"]))
            loads.append(metrics["expert_load"])
        rows["program"] = losses
        print("program", losses, "assignments the held experts took a step",
              [int(load.sum()) for load in loads], flush=True)
        del state, metrics, step
        # nothing dropped: at step 0 (the seed's own parameters on both
        # sides) each held expert's load is the reference's count of the
        # tokens that chose it, but for the choices bf16 rows flip
        first = reference.sizes_of(
            {"final_norm": jnp.zeros(cfg.d_model)})["first"]
        chose = reference._forward(init_state(key)["params"], batches[0],
                                   micro)[3]
        want = jnp.stack(chose)[:, first:first + loads[0].shape[1]]
        print("expert_load at step 0: the program's", int(loads[0].sum()),
              "the reference's", int(want.sum()), "of",
              batches[0].size * cfg.n_experts_per_tok * len(chose),
              "choices; largest difference an expert",
              int(jnp.abs(loads[0] - want).max()), flush=True)

    def reference_losses(round=False, sizes=None):
        s = {**reference.sizes_of({"final_norm": jnp.zeros(cfg.d_model)}),
             **(sizes or {})}
        as_held = (lambda t: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), t)
            ) if round else (lambda t: t)
        params = as_held(init_state(key)["params"])
        state = reference.adamw_init(params)
        losses = []
        for i in range(STEPS - 1):
            loss, grads = reference.loss_and_grad(
                params, batches[i % len(batches)], micro, s)
            losses.append(loss)
            params, state = reference.adamw_step(params, grads, state,
                                                 **opt["args"])
            params = as_held(params)
            del grads
        losses.append(reference.loss_by_layer(
            params, batches[(STEPS - 1) % len(batches)], micro, s))
        return losses, jax.device_get(params)

    for name, wrong in {"reference": {}, **WRONG}.items():
        if only and name not in only:
            continue
        rows[name], after[name] = reference_losses(**wrong)
        memory = jax.devices()[0].memory_stats() or {}
        print(name, rows[name], "peak in use",
              memory.get("peak_bytes_in_use"), "largest reservation",
              memory.get("peak_bytes_reserved"), flush=True)
    base = rows.get("reference")
    out = {"seed": args.seed, "cell": args.cell, "losses": rows}
    if base:
        out["distance"] = {
            name: [abs(a - b) for a, b in zip(row, base)]
            for name, row in rows.items() if name != "reference"}
        first = jax.device_get(init_state(key)["params"])
        out["params_change"] = {
            name: params_change(p, after["reference"], first)
            for name, p in after.items() if name != "reference"}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
