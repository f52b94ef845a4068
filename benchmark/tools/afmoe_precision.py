#!/usr/bin/env python3
"""How far the served Trinity-Mini stage is from its float32 reference, and
what a lower precision would read: the two readings behind `check.max_gap` /
`mean_gap` of `traffic/decode_window_docs.json` (PERF.md section 2).  On the
chip, one process, no cluster:

  python3 benchmark/tools/afmoe_precision.py [--seed N] [--requests 2] \
      [--new-tokens 320]

It serves greedy requests of the cell's shape (one 16,384-token document, a
question behind it) through the engine built from the cell's own files (bf16
weights, a paged cache of K and V rows of both kinds of layer, the [4, 512]
prefill program and the T=1 kernels), lets the engine go, and judges the
served tokens twice with `reference/afmoe.py`: as the reference is (float32
arithmetic on the served weights: what separates the two is the
activations' rounding and an expert of a token's 8 that it flips), and with
every matrix rounded to an 8-bit float's mantissa (e4m3's three bits under
an ideal per-tensor scale: the nearest precision under the configuration's
bf16; rounded in place, the chip holds one copy).  The last must come out
as not correct under the limits.
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
import jax.numpy as jnp
import numpy as np

from benchmark import manifest
from benchmark.reference import afmoe as ref

CELL = "serve_trinity_docs_decode"


def round_mantissa(x, bits: int = 3):
    """x rounded to `bits` bits of mantissa behind the leading one."""
    x32 = x.astype(jnp.float32)
    m, e = jnp.frexp(x32)                       # x = m * 2**e, |m| in [.5, 1)
    step = 2.0 ** (bits + 1)
    return jnp.ldexp(jnp.round(m * step) / step, e).astype(x.dtype)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=2)
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
        served.append((doc + q, eng.generate(doc + q, args.new_tokens)))
    print("[precision] served", [len(o) for _, o in served],
          "tokens; prefix hits", eng.stats()["prefix_hit_tokens"], flush=True)
    params = eng.params
    eng.shutdown()
    del eng
    gc.collect()

    def judge(params, what):
        gaps, ranks = [], []
        for prompt, out in served:
            g, r = ref.served_token_gaps(params, prompt, out)
            gaps += g
            ranks += r
        line = {"reading": what, "tokens": len(gaps),
                "max_gap": float(max(gaps)), "mean_gap": float(np.mean(gaps)),
                "argmax_pct": 100.0 * float(np.mean([k == 0 for k in ranks]))}
        print("[precision]", json.dumps(line), flush=True)
        return line

    out = [judge(params, "float32 reference on the served bf16 weights")]
    rounder = jax.jit(round_mantissa, donate_argnums=0)
    params = jax.tree.map(lambda x: rounder(x) if x.ndim >= 2 else x, params)
    out.append(judge(params, "every matrix rounded to a 3-bit mantissa"))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "afmoe_precision.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
