#!/usr/bin/env python3
"""How far the served EvaByte stage is from its float32 reference, and what
a lower precision or a mechanism left out would read: the readings behind
`check.max_gap` / `mean_gap` of `traffic/decode_eva_sessions.json` (PERF.md
section 2).  On the chip, one process, no cluster:

  python3 benchmark/tools/evabyte_precision.py [--seed N]

It serves two greedy requests of the cell's shape (one 10,240-byte file, a
history and a turn behind it) through the engine built from the cell's own
files (bf16 weights, the windowed paged cache, the [4, 512] prefill program,
the compaction and the T=1 kernel): one whose answer crosses a window's edge
while it decodes, one whose prompt and answer end inside one window.  Then
it lets the engine go and judges the served tokens with
`reference/evabyte.py`: as the reference is (float32 arithmetic on the
served weights); with every matrix rounded to an 8-bit float's mantissa
(e4m3's three bits: the nearest precision under the configuration's bf16);
with the window one chunk short (2,032: the last chunk of every window seen
as a summary a window early); and with the summaries left out (the second
request's last window alone: rotary attention inside a window does not
change when the window is moved to position 0).  The last three must come
out as not correct under the limits.
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
from benchmark.reference import evabyte as ref
from benchmark.tools.axk1_precision import round_mantissa

CELL = "serve_evabyte_sessions_decode"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
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
    window, chunk = cfg.window_size, cfg.chunk_size
    eng = InferenceEngine(model=config["module"].rsplit(".", 1)[-1],
                          config=cfg, seed=args.seed, auto_start=False,
                          **traffic["engine"])
    rng = np.random.default_rng([args.seed, 5])
    doc = rng.integers(0, cfg.vocab_size, head).tolist()
    # behind the file: a prompt that ends 3/4 into the next window and an
    # answer that crosses its edge; a prompt that ends a quarter into that
    # window and an answer that stays inside it
    shapes = [(3 * window // 4, window // 2), (window // 4, window // 4)]
    served = []
    for extra, new in shapes:
        prompt = doc + rng.integers(0, cfg.vocab_size, extra).tolist()
        served.append((prompt, eng.generate(prompt, new)))
    stats = eng.stats()
    print("[precision] served", [(len(p), len(o)) for p, o in served],
          "prefix hits", stats["prefix_hit_tokens"], "eva", stats["eva"],
          flush=True)
    params = eng.params
    eng.shutdown()
    del eng
    gc.collect()

    def judge(params, what, samples, **kw):
        gaps, ranks = [], []
        for prompt, out in samples:
            g, r = ref.served_token_gaps(params, prompt, out, **kw)
            gaps += g
            ranks += r
        line = {"reading": what, "tokens": len(gaps),
                "max_gap": float(max(gaps)), "mean_gap": float(np.mean(gaps)),
                "argmax_pct": 100.0 * float(np.mean([k == 0 for k in ranks]))}
        print("[precision]", json.dumps(line), flush=True)
        return line

    out = [judge(params, "float32 reference on the served bf16 weights",
                 served)]
    out.append(judge(params, "the window one chunk short", served,
                     window=window - chunk, chunk=chunk))
    prompt, answer = served[1]
    cut = (len(prompt) - 1) // window * window
    assert (len(prompt) + len(answer) - 1) // window == cut // window
    out.append(judge(params, "the summaries left out",
                     [(prompt[cut:], answer)], window=window, chunk=chunk))
    rounder = jax.jit(round_mantissa, donate_argnums=0)
    params = jax.tree.map(lambda x: rounder(x) if x.ndim >= 2 else x, params)
    out.append(judge(params, "every matrix rounded to a 3-bit mantissa",
                     served))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "evabyte_precision.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
