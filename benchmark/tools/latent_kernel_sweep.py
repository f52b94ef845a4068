#!/usr/bin/env python3
"""Time `latent_decode_attention` alone over the latent pool's block sizes
and the blocks a grid step takes, at the cell's shape (32 lanes, 64 heads,
16.6k-token contexts, a pool of 196,608 tokens, one layer): the sweep behind
`block_size` in `traffic/decode_latent_docs.json`.  On the chip:

  python3 benchmark/tools/latent_kernel_sweep.py
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

from benchmark import flops, latent_flops, manifest
from ray_tpu.ops.attention import latent_decode_attention

F = {"kv_lora_rank": 512, "qk_rope_head_dim": 64, "n_heads": 64}
LANES, CTX, POOL, WIDTH = 32, 16600, 196608, 640


def main() -> int:
    peaks = manifest.peaks(jax.devices()[0].device_kind)
    least, bound = flops.roofline_s(
        *latent_flops.latent_decode(LANES * CTX, LANES, F), peaks)
    rows = []
    for bs in (16, 32, 64, 128):
        nb, mb = POOL // bs, -(-16896 // bs)
        key = jax.random.key(bs)
        pool = jax.random.normal(key, (1, nb, bs, WIDTH), jnp.bfloat16)
        q = jax.random.normal(key, (LANES, 64, WIDTH), jnp.bfloat16)
        tables = jnp.asarray(np.random.default_rng(bs).integers(
            0, nb, (LANES, mb)), jnp.int32)
        lens = jnp.full((LANES,), CTX, jnp.int32)
        for tokens_per_step in (256, 512, 1024):
            kb = tokens_per_step // bs
            if kb > 32:
                continue            # one DMA stream per block: too many
            fn = jax.jit(lambda q, pool, t, n, kb=kb: latent_decode_attention(
                q, pool, t, n, 0, v_width=512, scale=0.13,
                blocks_per_step=kb, use_kernel=True))
            fn(q, pool, tables, lens).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(20):
                out = fn(q, pool, tables, lens)
            out.block_until_ready()
            s = (time.perf_counter() - t0) / 20
            rows.append({"block_size": bs, "blocks_per_step": kb,
                         "ms": s * 1e3, "roofline_pct": 100 * least / s})
            print("[sweep]", json.dumps(rows[-1]), flush=True)
    print(f"[sweep] least {least * 1e3:.3f} ms a call ({bound})")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "latent_sweep.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
