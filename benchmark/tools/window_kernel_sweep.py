#!/usr/bin/env python3
"""Time the two single-query K/V attention kernels of
`serve_trinity_docs_decode` alone at the cell's shape (64 lanes, 32 query
heads over 4 key/value heads of 128, blocks of 128 rows of 512 bf16
columns, ragged contexts of 16.5-16.9k tokens, one layer of a pool the
cell's size): `paged_decode_attention` over the whole context (the full
layers') and `window_paged_decode_attention` over the last 2,048 positions
(the window layers'), the latter over the blocks a run takes.  Each figure
is ms a call (20 calls back to back, best of three sets) and the share of
the least the chip could take for it (`window_flops.attention_s`).  On the
chip:

  python3 benchmark/tools/window_kernel_sweep.py
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import manifest, window_flops
from ray_tpu.ops.attention import (paged_decode_attention,
                                   window_paged_decode_attention)

F = {"n_heads": 32, "n_kv_heads": 4, "head_dim": 128}
LANES, BS, WIDTH, WINDOW, MB = 64, 128, 512, 2048, 133


def timed(fn, *args) -> float:
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(*args)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / 20)
    return best


def main() -> int:
    peaks = manifest.peaks(jax.devices()[0].device_kind)
    rng = np.random.default_rng(0)
    lens_np = rng.integers(16500, 16900, LANES)
    lens = jnp.asarray(lens_np, jnp.int32)
    starts = jnp.maximum(lens - WINDOW, 0)
    key = jax.random.key(0)
    q = jax.random.normal(key, (LANES, 32, 128), jnp.bfloat16)
    rows = []

    def note(kernel, kb, s, least):
        rows.append({"kernel": kernel, "blocks_per_step": kb, "ms": s * 1e3,
                     "least_ms": least * 1e3,
                     "roofline_pct": 100 * least / s})
        print("[sweep]", json.dumps(rows[-1]), flush=True)

    # the full layers' pool: 1,536 blocks, every lane's table its own blocks
    nb = 1536
    k = jax.random.normal(key, (1, nb, BS, WIDTH), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(1), (1, nb, BS, WIDTH), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, nb, (LANES, MB)), jnp.int32)
    least = window_flops.attention_s(float(lens_np.sum()), LANES, F, peaks)
    for kb in (None, 4, 8, 12, 16):
        fn = jax.jit(lambda q, k, v, t, n, kb=kb: paged_decode_attention(
            q, k, v, t, n, 0, kv_heads=4, blocks_per_step=kb,
            use_kernel=True))
        note("paged_decode_attention", kb, timed(fn, q, k, v, tables, lens),
             least)
    # the window layers' pool: 768 blocks
    nb = 768
    k, v = k[:, :nb], v[:, :nb]
    tables = jnp.asarray(rng.integers(0, nb, (LANES, MB)), jnp.int32)
    least = window_flops.attention_s(float(LANES * WINDOW), LANES, F, peaks)
    for kb in (None, 3, 5, 6, 8, 9, 12, 16, 17):
        fn = jax.jit(lambda q, k, v, t, n, s, kb=kb:
                     window_paged_decode_attention(
                         q, k, v, t, n, s, 0, span=WINDOW, kv_heads=4,
                         blocks_per_step=kb, use_kernel=True))
        try:
            note("window_paged_decode_attention", kb,
                 timed(fn, q, k, v, tables, lens, starts), least)
        except Exception as e:      # a run that does not fit VMEM
            print("[sweep] window kb", kb, "refused:", repr(e)[:300],
                  flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "window_sweep.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
