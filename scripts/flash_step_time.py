#!/usr/bin/env python3
"""Time the flash-attention kernels alone at the shapes the training
programs call them with: `train_gpt2s_1chip`'s `[24,1024,12,64]`, gpt2-xl's
per-chip fsdp4 share `[6,1024,25,64]` and its whole `[4,1024,25,64]` (25
heads of 64: the thirteenth block of 128 columns is half a block), and one
head-128 shape `[4,2048,16,128]`, bf16, causal.  Each shape runs
`flash_attention` and its gradients as the models call it: q, k and v
arrive `[b, l, h*d]`, as `models/decoder.py::heads_attention`'s products
write them, are seen as `[b, l, h, d]` for the call, and the gradients
leave `[b, l, h*d]` again.  A few calls under the profiler; the figures are
the device's own durations of the Mosaic calls (the forward and the
backward, told apart by their operands since they carry one name; a tree
with a dq and a dk/dv kernel has the two summed), in ms a call and as a
share of `benchmark/flops.py`'s roofline (`flash_fwd`, `flash_bwd`), beside
what else the program ran (`other_ms`: since PR 51 the cotangent's product
with `g` and what XLA still moves between the `[b, l, h*d]` arrays and the
kernels, which is nothing where `h*d` is whole blocks of 128; on a tree
before PR 51 also the head transposes to `[b*h, l, d]` and back and the
`delta` pass; `others` names its longest rows) and the tiles the causal
walk visits of a head's score square.  The train cell's twin of
`scripts/engine_step_time.py`: not a tool the benchmark runs.  On the chip,
from the root of a checkout (a parent's, to compare, with this file's path:
it needs nothing of the program but `flash_attention`):

  python3 scripts/flash_step_time.py [tile ...]

With no argument the program's own tiles (`ops.attention._FLASH_FWD_TILE`,
`_FLASH_BWD_TILE`); `fwd,bwd` pairs give the walk at each (1024 is one tile
a head: nothing skipped, every score masked).  `--f32` times float32
inputs, whose products stay float32.  The last line is one JSON object.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp

from benchmark import flops, manifest, trace_reduce
from ray_tpu.ops import attention as A

SHAPES = {"gpt2s_b24": (24, 1024, 12, 64), "gpt2xl_fsdp4": (6, 1024, 25, 64),
          "gpt2xl_b4": (4, 1024, 25, 64), "head128": (4, 2048, 16, 128)}
CALLS = 10


def tiles_visited(tile, block):
    """(visited, masked, all) tiles of a diagonal block's walk, as the
    program lists them; a tree before PR 44 has one masked tile a head."""
    if not hasattr(A, "_tiles"):
        return 1, 1, 1
    n = block // tile
    seen = list(A._tiles(n, tile, n, tile, True))
    return len(seen), sum(t[2] is not None for t in seen), n * n


def is_forward(text: str) -> bool:
    """Whether a Mosaic call's HLO text is the forward's: it reads three
    arrays (the backward kernels read dO and the saved statistics too)."""
    operands = text.split(" custom-call(", 1)[1].split(
        "custom_call_target", 1)[0]
    # layouts, dimensions and comments hold commas and brackets of their own
    operands = re.sub(r"\{[^}]*\}|\[[^\]]*\]|/\*.*?\*/", "", operands)
    return operands.split(")", 1)[0].count(",") == 2


def device_ms(fn, args):
    """ms a call on the device: the forward kernel, the backward kernel(s),
    and everything else the program ran (`others`: its longest rows)."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find(trace_dir))
    (lines,) = trace["devices"].values()
    took = {"fwd": 0.0, "bwd": 0.0, "other": 0.0}
    others = collections.Counter()
    for text, ns in trace_reduce.self_times(lines[trace_reduce.OPS_LINE]):
        part = "other"
        if trace_reduce.KERNEL_MARK in text:
            part = "fwd" if is_forward(text) else "bwd"
        else:
            others[trace_reduce.describe(text)[1]] += ns / 1e6 / CALLS
        took[part] += ns / 1e6 / CALLS
    return took, [[label, round(ms, 4)] for label, ms in others.most_common(4)]


def main(tiles, dtype):
    dev = jax.devices()[0]
    peaks = manifest.peaks(dev.device_kind)
    result = {"device": [dev.platform, dev.device_kind], "tree": os.getcwd(),
              "dtype": jnp.dtype(dtype).name, "rows": []}
    own = (getattr(A, "_FLASH_FWD_TILE", 1024),
           getattr(A, "_FLASH_BWD_TILE", 1024))   # a tree before PR 44: 1024
    for fwd_tile, bwd_tile in tiles or [own]:
        if tiles:
            A._FLASH_FWD_TILE, A._FLASH_BWD_TILE = fwd_tile, bwd_tile
            A.flash_attention.clear_cache()
        for name, (b, s, h, d) in SHAPES.items():
            q, k, v, g = (jax.random.normal(jax.random.key(i), (b, s, h * d),
                                            dtype) for i in range(4))

            def step(q, k, v, shape=(b, s, h, d)):
                def weighed(*wide):
                    out = A.flash_attention(
                        *(x.reshape(shape) for x in wide), causal=True)
                    return jnp.sum(out.reshape(g.shape).astype(jnp.float32)
                                   * g.astype(jnp.float32))
                return jax.value_and_grad(weighed, argnums=(0, 1, 2))(q, k, v)

            took, others = device_ms(step, (q, k, v))
            block = min(s, 1024)
            row = {"shape": name, "tile": [fwd_tile, bwd_tile],
                   "tiles": [tiles_visited(t, block)
                             for t in (fwd_tile, bwd_tile)]}
            row.update({f"{key}_ms": ms for key, ms in took.items()})
            size = jnp.dtype(dtype).itemsize
            least = {"fwd": flops.roofline_s(
                         *flops.flash_fwd(b, h, s, d, size), peaks)[0],
                     "bwd": flops.roofline_s(
                         *flops.flash_bwd(b, h, s, d, size), peaks)[0]}
            for part in ("fwd", "bwd"):
                row[f"{part}_roofline_pct"] = 1e5 * least[part] / took[part]
            row["all_ms"] = took["fwd"] + took["bwd"]
            row["all_roofline_pct"] = 1e5 * sum(least.values()) / row["all_ms"]
            row["others"] = others
            result["rows"].append(row)
            print("  ".join(f"{key}={val:.3f}" if isinstance(val, float)
                            else f"{key}={val}" for key, val in row.items()),
                  flush=True)
    print(json.dumps(result))


if __name__ == "__main__":
    args = sys.argv[1:]
    main([tuple(int(t) for t in a.split(",")) for a in args if a != "--f32"],
         jnp.float32 if "--f32" in args else jnp.bfloat16)
