#!/usr/bin/env python3
"""Time the flash-attention kernels alone at the shapes the training
programs call them with: `train_gpt2s_1chip`'s `[24,1024,12,64]`, gpt2-xl's
per-chip fsdp4 share `[6,1024,25,64]` and its whole `[4,1024,25,64]` (25
heads of 64: the thirteenth block of 128 columns is half a block), one
head-128 shape `[4,2048,16,128]`, and `train_mellum2_8k_ep4share`'s
`[2,8192,32,128]` over 4 kv heads, without a window (`mellum2_b2`, the full
layer's calls) and under one of 1,024 (`mellum2_b2_w1024`), and three
groups no cell runs: llama3-8b's 32 heads over 8 (`llama3_8b_b2`), Gemma 2
9B's 16 heads of 256 over 8 (`gemma2_9b_b2`) and llama-1b's 32 heads of 64
over 4 (`llama1b_b8`: narrower than a block's lanes, so K and V are
repeated before the kernels of a group of one), bf16, causal.
A shape is `(batch, length, heads, d[, kv heads[, window]])`, the kv heads
the heads' unless given; a tree whose kernels take K and V at the heads'
count alone (before PR 63) is handed them repeated, as its
`heads_attention` did, and the repeat and the sum over each group then
stand under `other_ms`.  Each shape runs
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
walk visits of a head's score square, by count and by area.  The train
cell's twin of `scripts/engine_step_time.py`: not a tool the benchmark
runs.  On the chip, from the root of a checkout (a parent's, to compare,
with this file's path: it needs nothing of the program but
`flash_attention`):

  python3 scripts/flash_step_time.py [shape ...] [tile ...]

With no shape all of them; with no tile the program's own tiles (`ops.attention._FLASH_FWD_TILE`,
`_FLASH_BWD_TILE`), sub-tiles (`_FLASH_BWD_CROSSED`), widest score
product of a group's heads (`_FLASH_FWD_COLUMNS`) and pairs a grid step
walks (`_FLASH_FWD_PAIRS`, `_FLASH_BWD_PAIRS`); `fwd,bwd` pairs,
`fwd,bwd,crossed` triples or `fwd,bwd,crossed,fwd columns,fwd pairs,bwd
pairs` give the walk at each (1024 is one tile a head:
nothing skipped, every score masked; `crossed` 0: no sub-tiles).  `tiles`
holds, forward then backward, the tiles the causal walk visits of a
block's score square, those of them it masks and all there are, then the
visited and the masked AREA as shares of the square, which a parent's and
the change's checkout print alike whatever their tiles are.  `--f32` times
float32 inputs, whose products stay float32.  The last line is one JSON
object.
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
          "gpt2xl_b4": (4, 1024, 25, 64), "head128": (4, 2048, 16, 128),
          "mellum2_b2": (2, 8192, 32, 128, 4),
          "mellum2_b2_w1024": (2, 8192, 32, 128, 4, 1024),
          "llama3_8b_b2": (2, 8192, 32, 128, 8),
          "gemma2_9b_b2": (2, 8192, 16, 256, 8),
          "llama1b_b8": (8, 2048, 32, 64, 4)}
CALLS = 10


def shape_of(name):
    """(batch, length, heads, d, kv heads, window) of a `SHAPES` entry."""
    b, s, h, d, *rest = SHAPES[name]
    kv_heads, window = (rest + [h, 0][len(rest):])
    return b, s, h, d, kv_heads, window


def least_s(b, h, s, d, window, size, peaks):
    """{"fwd", "bwd"}: the least seconds of a call, `benchmark/flops.py`'s
    counts; under a window the products over the window's pairs, not the
    triangle's."""
    counts = {"fwd": flops.flash_fwd(b, h, s, d, size),
              "bwd": flops.flash_bwd(b, h, s, d, size)}
    if window:
        share = (window * s - window * (window - 1) / 2) / (s * (s + 1) / 2)
        counts = {part: (work * share, nbytes)
                  for part, (work, nbytes) in counts.items()}
    return {part: flops.roofline_s(*count, peaks)[0]
            for part, count in counts.items()}


def tiles_visited(tile, block, least):
    """(visited, masked, all) tiles of a diagonal block's walk as the
    program lists them, then the visited and the masked AREA as shares of
    the block's score square.  A tree since PR 56 lists each tile with its
    extent and, with `least`, a crossed tile as sub-tiles down to that many
    positions; a tree before it lists whole tiles (`least` None).  Where
    two trees' counts differ in kind their areas are the figures to
    compare.  A tree before PR 44 has one masked tile a head."""
    if not hasattr(A, "_tiles"):
        return 1, 1, 1, 1.0, 1.0
    tile = A._flash_tile(block, tile)
    n = block // tile
    if least is None:
        seen = [(tile * tile, t[2]) for t in A._tiles(n, tile, n, tile, True)]
    else:
        seen = [(t[1] * t[3], t[4])
                for t in A._tiles(n, tile, n, tile, True, least)]
    masked = [area for area, offset in seen if offset is not None]
    return (len(seen), len(masked), n * n,
            round(sum(area for area, _ in seen) / block ** 2, 4),
            round(sum(masked) / block ** 2, 4))


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


def main(shapes, tiles, dtype):
    dev = jax.devices()[0]
    peaks = manifest.peaks(dev.device_kind)
    result = {"device": [dev.platform, dev.device_kind], "tree": os.getcwd(),
              "dtype": jnp.dtype(dtype).name, "rows": []}
    names = ("_FLASH_FWD_TILE", "_FLASH_BWD_TILE", "_FLASH_BWD_CROSSED",
             "_FLASH_FWD_COLUMNS",
             "_FLASH_FWD_PAIRS", "_FLASH_BWD_PAIRS")
    # a tree before PR 44: one tile of 1,024; before PR 56: no sub-tiles
    own = tuple(getattr(A, name, 1024 if "TILE" in name else None)
                for name in names)
    for walk in tiles or [own]:
        walk = walk + own[len(walk):]
        fwd_tile, bwd_tile, crossed, *columns = walk
        if tiles:
            for name, size in zip(names, walk):
                if size is not None:
                    setattr(A, name, size)
            A.flash_attention.clear_cache()
        for name in shapes or SHAPES:
            b, s, h, d, kv_heads, window = shape_of(name)
            q, k, v, g = (jax.random.normal(
                jax.random.key(i), (b, s, (kv_heads if i in (1, 2) else h) * d),
                dtype) for i in range(4))
            # a tree before PR 63: K and V at the heads' count, repeated
            repeat = 1 if "group" in A._FlashPlan._fields else h // kv_heads

            def step(q, k, v, d=d, repeat=repeat, window=window):
                def weighed(*wide):
                    q, k, v = (x.reshape(*x.shape[:2], -1, d) for x in wide)
                    if repeat > 1:
                        k, v = (jnp.repeat(x, repeat, axis=2) for x in (k, v))
                    out = A.flash_attention(
                        q, k, v, causal=True,
                        **({"window": window} if window else {}))
                    return jnp.sum(out.reshape(g.shape).astype(jnp.float32)
                                   * g.astype(jnp.float32))
                return jax.value_and_grad(weighed, argnums=(0, 1, 2))(q, k, v)

            took, others = device_ms(step, (q, k, v))
            block = min(s, 1024)
            row = {"shape": name, "tile": [fwd_tile, bwd_tile],
                   "crossed": crossed, "columns": columns,
                   # the forward's crossed tiles stay whole; the backward's
                   # are cut until the heads of a block of max(d, 128)
                   # columns make a score product `crossed` columns wide
                   "tiles": [tiles_visited(fwd_tile, block,
                                           None if crossed is None else 0),
                             tiles_visited(bwd_tile, block, crossed
                                           and crossed // (max(d, 128) // d))]}
            row.update({f"{key}_ms": ms for key, ms in took.items()})
            least = least_s(b, h, s, d, window, jnp.dtype(dtype).itemsize,
                            peaks)
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
    main([a for a in args if a in SHAPES],
         [tuple(int(t) for t in a.split(","))
          for a in args if a != "--f32" and a not in SHAPES],
         jnp.float32 if "--f32" in args else jnp.bfloat16)
