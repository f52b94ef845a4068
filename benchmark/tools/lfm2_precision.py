#!/usr/bin/env python3
"""How far the served LFM2 stage is from its float32 reference, and what a
lower precision or a wrong mechanism would read: the readings behind
`check.max_gap` / `mean_gap` of `traffic/decode_conv_moe_rag.json` (PERF.md
section 2).  On the chip, one process, no cluster:

  python3 benchmark/tools/lfm2_precision.py [--seed N] [--requests 192]
                                            [--fixed-choice]

It serves the cell's own load through the engine built from the cell's own
files (bf16 weights, K/V pools over the attention layers and the tails'
buffer over the conv layers, the grouped multiply over the 64 experts, the
paged kernels): `--requests` greedy requests handed in AT
ONCE over the cell's `max_lanes` (its 16 shared 4,096-token heads, a
question of the cell's lengths behind each, answers of the cell's lengths),
so that every lane decodes beside 127 others, heads are prefilled in chunks
and snapshotted and later admissions adopt blocks and snapshot, as in the
window.  It lets the engine go and judges `check.samples` finished
requests, drawn as the driver draws them, with `reference/lfm2.py` BY THE
CELL'S OWN COMPARISON (`drivers/serve.py`: `served_token_gaps` as the
replica's `reference_check` calls it; correct where the largest gap is
within `check.max_gap` and the mean within `check.mean_gap`): as the
reference is (float32 arithmetic on the served weights), which must read
correct; with the convolution's tail dropped (the two taps behind a
position's own left out: what a lane would compute that carried no tail),
with the gate C left out, and with every matrix rounded to an 8-bit float's
mantissa (e4m3's three bits under an ideal per-tensor scale: the nearest
precision under the configuration's bf16; rounded in place, the chip holds
one copy), each of which must NOT.  Exit code 1 where a verdict is the
other way.

`--fixed-choice` is the reading that says what the distance above is made
of.  A top-4 of 64 sigmoid scores that lie close together is not stable
under bf16 rounding: where the served path and the float32 reference choose
other experts for a token in one layer, everything behind it differs, and
the limits above have to leave room for that.  With the router's selection
bias raised by 10 on every expert layer's first four experts (`s` lies in
(0, 1): both sides then choose experts 0-3 for every token, weighted by
their own unbiased `s` as before) the same engine, lanes, chunks, snapshots
and decode rows are compared with the same reference, and what is left is
the arithmetic's own distance.  The mode runs that one reading, prints it
beside the limits, and writes `chiprun_out/lfm2_precision_fixed.json`; it
must read correct, and by how much less than the drawn router's reading is
the finding (PERF.md section 2).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import numpy as np

from benchmark import manifest
from benchmark.reference import lfm2 as ref
from benchmark.tools.dots3_precision import round_mantissa

CELL = "serve_lfm2_rag_decode"


def verdict(gaps, check) -> bool:
    """`drivers/serve.py`'s `ok_tokens`, on the same numbers."""
    return bool(gaps) and max(gaps) <= check["max_gap"] \
        and float(np.mean(gaps)) <= check["mean_gap"]


def fixed_choice(params, k: int, by: float = 10.0):
    """`params` with every `router_bias` raised by `by` on its first `k`
    experts: the other leaves are the same arrays (the chip holds one
    copy)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x.at[..., :k].add(by)
        if getattr(path[-1], "key", None) == "router_bias" else x, params)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=192)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--fixed-choice", action="store_true")
    args = ap.parse_args(argv)
    from ray_tpu._private import compile_cache
    from ray_tpu.inference.engine import InferenceEngine
    compile_cache.place()
    m = manifest.load()
    cell = m.cells[CELL]
    config = m.load_config(cell["config"])
    traffic = m.load_traffic(cell["traffic"])
    if args.rehearse:
        traffic.update(traffic["rehearsal"])
    cfg = manifest.model_config(config, None, args.rehearse)
    check, req = traffic["check"], traffic["requests"]
    eng = InferenceEngine(model=config["module"].rsplit(".", 1)[-1],
                          config=cfg, seed=args.seed, auto_start=False,
                          **traffic["engine"])
    if args.fixed_choice:
        eng.update_params(fixed_choice(eng.params, cfg.n_experts_per_tok))
    rng = np.random.default_rng([args.seed, 5])
    heads = [rng.integers(0, cfg.vocab_size,
                          req["sessions"]["head_len"]).tolist()
             for _ in range(req["sessions"]["groups"])]

    def draw(span):
        return int(rng.integers(span["lo"], span["hi"] + 1))

    prompts = [heads[i % len(heads)] + rng.integers(
        0, cfg.vocab_size, draw(req["prompt_len"])).tolist()
        for i in range(args.requests)]
    handles = [eng.submit(p, draw(req["output_len"])) for p in prompts]
    lanes = steps = 0
    while eng.step():
        steps += 1
        if steps % 32 == 0:     # (`stats()` fetches counters: not a step)
            lanes = max(lanes, eng.stats()["active"])
    st = eng.stats()
    pick = np.random.default_rng([args.seed, 7]).permutation(
        len(prompts))[:check["samples"]]
    served = [(prompts[i], handles[i].tokens()) for i in pick]
    print("[precision]", len(prompts), "requests, at most", lanes, "of",
          traffic["engine"]["max_lanes"], "lanes live; judged",
          [len(o) for _, o in served], "tokens; prefix hits",
          st["prefix_hit_tokens"], "snapshots adopted",
          st["ssm"]["snapshots_adopted"], "misses",
          st["ssm"]["snapshot_misses"], "conv", st["conv"], flush=True)
    params = eng.params
    eng.shutdown()
    del eng, handles
    gc.collect()
    over = {"bucket": 16} if args.rehearse else {}

    def judge(params, what, want, **wrong):
        gaps, ranks = [], []
        for prompt, out in served:
            g, r = ref.served_token_gaps(params, prompt, out, **over,
                                         **wrong)
            gaps += g
            ranks += r
        line = {"reading": what, "tokens": len(gaps),
                "max_gap": float(max(gaps)), "mean_gap": float(np.mean(gaps)),
                "limits": {k: check[k] for k in ("max_gap", "mean_gap")},
                "argmax_pct": 100.0 * float(np.mean([k == 0 for k in ranks])),
                "lanes_live": lanes, "correct": verdict(gaps, check),
                "must_be_correct": want}
        print("[precision]", json.dumps(line), flush=True)
        return line

    if args.fixed_choice:
        out = [judge(params, "float32 reference on the served bf16 weights, "
                     "both sides' choice fixed to experts 0-3", True)]
    else:
        out = [judge(params, "float32 reference on the served bf16 weights",
                     True),
               judge(params, "the convolution's tail dropped (its own tap "
                     "alone)", False, conv_tail=False),
               judge(params, "the gate C left out", False,
                     conv_c_gate=False)]
        rounder = jax.jit(round_mantissa, donate_argnums=0)
        params = jax.tree.map(lambda x: rounder(x) if x.ndim >= 2 else x,
                              params)
        out.append(judge(params, "every matrix rounded to a 3-bit mantissa",
                         False))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = "lfm2_precision_fixed" if args.fixed_choice else "lfm2_precision"
    with open(os.path.join(ROOT, "chiprun_out", name + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    wrong = [r["reading"] for r in out if r["correct"] != r["must_be_correct"]]
    for reading in wrong:
        print("[precision] WRONG VERDICT under the cell's limits:", reading,
              flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
