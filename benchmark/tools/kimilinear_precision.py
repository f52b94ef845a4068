#!/usr/bin/env python3
"""How far the served Kimi Linear stage is from its float32 reference, and
what a lower precision or a wrong mechanism would read: the readings behind
`check.max_gap` / `mean_gap` of `traffic/decode_kda_latent_reasoning.json`
(PERF.md section 2).  On the chip, one process, no cluster:

  python3 benchmark/tools/kimilinear_precision.py [--seed N] [--requests 192]

It serves the cell's own load through the engine built from the cell's own
files (bf16 weights, ONE latent pool over the latent layers and float32
state slots over the KDA layers, the chunked scan, the grouped multiply
over the held experts and the T=1 kernels): `--requests` greedy requests
handed in AT ONCE over the cell's `max_lanes` (its 16 shared 4,096-token
heads, a problem of the cell's lengths behind each, answers of the cell's
lengths), so that every lane decodes beside 127 others, heads are prefilled
in chunks and snapshotted and later admissions adopt blocks and snapshot,
as in the window.  It lets the engine go and judges `check.samples`
finished requests, drawn as the driver draws them, with
`reference/kimilinear.py` BY THE CELL'S OWN COMPARISON (`drivers/serve.py`:
`served_token_gaps` as the replica's `reference_check` calls it; correct
where the largest gap is within `check.max_gap` and the mean within
`check.mean_gap`): as the reference is (float32 arithmetic on the served
weights: what separates the two is the activations' rounding and the expert
it flips), which must read correct; with a wrong MECHANISM in the reference
(the delta correction left out; the decay taken a head, its channels' mean),
each of which must NOT; with the state rounded to bf16 behind every step,
which is reported whichever way it falls; and last with every matrix rounded
to an 8-bit float's mantissa (e4m3's three bits under an ideal per-tensor
scale: the nearest precision under the configuration's bf16; rounded in
place, the chip holds one copy), which must NOT.  Exit code 1 where a
verdict is the other way.
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
from benchmark.reference import kimilinear as ref
from benchmark.tools.dots3_precision import round_mantissa

CELL = "serve_kimilinear_reasoning_decode"


def verdict(gaps, check) -> bool:
    """`drivers/serve.py`'s `ok_tokens`, on the same numbers."""
    return bool(gaps) and max(gaps) <= check["max_gap"] \
        and float(np.mean(gaps)) <= check["mean_gap"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=192)
    ap.add_argument("--rehearse", action="store_true")
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
          st["ssm"]["snapshot_misses"], flush=True)
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
                "must_be_correct": want}        # (None: reported alone)
        print("[precision]", json.dumps(line), flush=True)
        return line

    out = [judge(params, "float32 reference on the served bf16 weights",
                 True)]
    # Wrong MECHANISMS, in the reference: the served tokens are judged by
    # a model that lacks what the family states.
    out.append(judge(params, "the delta correction left out", False,
                     delta=False))
    out.append(judge(params, "the decay a head's mean, not a channel's",
                     False, decay="head"))
    out.append(judge(params, "the state rounded to bf16 behind every step",
                     None, state_dtype="bfloat16"))
    rounder = jax.jit(round_mantissa, donate_argnums=0)
    params = jax.tree.map(lambda x: rounder(x) if x.ndim >= 2 else x, params)
    out.append(judge(params, "every matrix rounded to a 3-bit mantissa",
                     False))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kimilinear_precision.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    wrong = [r["reading"] for r in out if r["must_be_correct"] is not None
             and r["correct"] != r["must_be_correct"]]
    for reading in wrong:
        print("[precision] WRONG VERDICT under the cell's limits:", reading,
              flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
