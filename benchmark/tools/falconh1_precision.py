#!/usr/bin/env python3
"""How far the served Falcon-H1 stage is from its float32 reference, and what
lower precisions would read: the readings behind `check.max_gap` /
`mean_gap` of `traffic/decode_hybrid_chat.json` (PERF.md section 2).  On the
chip, one process, no cluster:

  python3 benchmark/tools/falconh1_precision.py [--seed N] [--requests 3] \
      [--new-tokens 320]

It serves greedy requests of the cell's shape (one 2,048-token shared
prompt, a turn behind it: the first prefills it in chunks and leaves a
snapshot, the others adopt the blocks and the snapshot) through the engine
built from the cell's own files (bf16 weights, K/V pools and float32 state
slots, the chunked scan and the T=1 kernels), lets the engine go, and judges
the served tokens three times with `reference/falconh1.py`: as the
reference is (float32 arithmetic on the served weights: what separates the
two is the activations' rounding); (a) with every matrix rounded to an 8-bit
float's mantissa (e4m3's three bits under an ideal per-tensor scale: the
nearest precision under the configuration's bf16; rounded in place, the
chip holds one copy), which must come out as not correct under the limits;
(b) with the recurrent state rounded to bf16 after every step (the served
weights again), reported beside it.
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
from benchmark.reference import falconh1 as ref
from benchmark.tools.dots3_precision import round_mantissa

CELL = "serve_falconh1_chat_decode"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--new-tokens", type=int, default=320)
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
    head = traffic["requests"]["sessions"]["head_len"]
    new = min(args.new_tokens, traffic["requests"]["output_len"]["hi"])
    eng = InferenceEngine(model=config["module"].rsplit(".", 1)[-1],
                          config=cfg, seed=args.seed, auto_start=False,
                          **traffic["engine"])
    rng = np.random.default_rng([args.seed, 5])
    doc = rng.integers(0, cfg.vocab_size, head).tolist()
    served = []
    for _ in range(args.requests):
        q = rng.integers(0, cfg.vocab_size, int(rng.integers(
            traffic["requests"]["prompt_len"]["lo"],
            traffic["requests"]["prompt_len"]["hi"] + 1))).tolist()
        served.append((doc + q, eng.generate(doc + q, new)))
    st = eng.stats()
    print("[precision] served", [len(o) for _, o in served],
          "tokens; prefix hits", st["prefix_hit_tokens"], "snapshots adopted",
          st["ssm"]["snapshots_adopted"], flush=True)
    params = eng.params
    eng.shutdown()
    del eng
    gc.collect()
    bucket = 512 if not args.rehearse else 16

    def judge(params, what, **over):
        gaps, ranks = [], []
        for prompt, out in served:
            g, r = ref.served_token_gaps(params, prompt, out, bucket=bucket,
                                         **over)
            gaps += g
            ranks += r
        line = {"reading": what, "tokens": len(gaps),
                "max_gap": float(max(gaps)), "mean_gap": float(np.mean(gaps)),
                "argmax_pct": 100.0 * float(np.mean([k == 0 for k in ranks]))}
        print("[precision]", json.dumps(line), flush=True)
        return line

    out = [judge(params, "float32 reference on the served bf16 weights"),
           judge(params, "(b) the state rounded to bf16 after every step",
                 state_dtype="bfloat16")]
    rounder = jax.jit(round_mantissa, donate_argnums=0)
    params = jax.tree.map(lambda x: rounder(x) if x.ndim >= 2 else x, params)
    out.append(judge(params, "(a) every matrix rounded to a 3-bit mantissa"))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "falconh1_precision.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
