#!/usr/bin/env python3
"""Time the kernels that `train_mellum2_8k_ep4share` brought, each alone at
the shape the cell calls it with, against the least time the chip could
take (`benchmark/swa_moe_train_flops.py`'s counts, the larger of FLOPs over
peak and bytes over bandwidth):

  grouped  the grouped multiply over 32,768 held rows of a buffer of 65,536
           (the sorted rows of 16 held experts of 64 at 16,384 tokens x 8),
           `[rows, 2304] x 16 x [2304, 896]`, row tile 512: forward, dx
           (the same kernel's other form over dy) and dw
           (`moe_grouped_matmul_dw`), ms a product, for the up and the
           down shapes
  combine  the experts' sorted rows back at their tokens, alone: a page of
           65,536 sorted rows `[rows, 2304]` of which an even router's
           32,768 or twice that are the 16 held experts', into 16,384
           tokens x 8: the kernel over the sort's runs (`moe_combine`, and
           with it the XLA that builds its work items) beside the gather
           of all 131,072 assignments' rows it replaces (`moe._gathered`),
           weighted (the forward's) and with ones (dx's); and the same at
           fewer tokens, where `decoder.moe_ffn`'s edge between the two
           was set
  window   the flash kernels with a window of 1,024 at `[2, 8192, 32 x 128]`:
           forward, and the backward (dq with dk/dv, one kernel)
  full     the same without a window (the full layer's calls)

A few calls under the profiler; the figures are the device's own durations.
The train cell's twin of `scripts/loss_head_time.py`: not a tool the
benchmark runs.  On the chip, from the root of a checkout:

  python3 scripts/moe_train_time.py [grouped] [combine] [window] [full]
      [block_m=N] [tile=N] [block_rows=N] [tokens=N]

With no argument all four (`tile=`, `block_rows=` and `tokens=`, each as
often as wanted, sweep the combine's token tile, its block of sorted rows
and the tokens it is timed at).  The last line is one JSON object.
"""

from __future__ import annotations

import collections
import functools
import json
import os
import sys
import tempfile

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp

from benchmark import flops, manifest, swa_moe_train_flops as counts
from benchmark import trace_reduce
from ray_tpu.models import decoder
from ray_tpu.ops import attention, moe

TOKENS, TOP_K, HELD, ROUTED, D, F = 16384, 8, 16, 64, 2304, 896
ROWS = TOKENS * TOP_K * HELD // ROUTED            # 32,768 held rows
BUFFER = 2 * ROWS                                 # `decoder.moe_ffn`'s bound
BATCH, SEQ, HEADS, HEAD_DIM, WINDOW = 2, 8192, 32, 128, 1024
CALLS = 5


def kernel_ms(fn, args) -> dict:
    """{kernel name: ms a call of `fn`} from the device's own trace, and
    under "all" everything the program ran."""
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
    took = collections.Counter()
    for text, ns in trace_reduce.self_times(lines[trace_reduce.OPS_LINE]):
        base, _, kernel, _ = trace_reduce.describe(text)
        took["all"] += ns / 1e6 / CALLS
        if kernel:
            took[base] += ns / 1e6 / CALLS
    return dict(took)


def grouped(peaks, block_m: int) -> list:
    keys = jax.random.split(jax.random.key(0), 4)
    # an even router's sizes, a little uneven as a drawn one's are
    sizes = ROWS // HELD + jnp.arange(HELD, dtype=jnp.int32) % 5 * 16 - 32
    rows = []
    for name, (k, n) in (("up", (D, F)), ("down", (F, D))):
        x = jax.random.normal(keys[0], (BUFFER, k), jnp.bfloat16)
        dy = jax.random.normal(keys[1], (BUFFER, n), jnp.bfloat16)
        w = jax.random.normal(keys[2], (HELD, k, n), jnp.float32) / k ** 0.5
        least = [1e3 * flops.roofline_s(work, nbytes, peaks)[0]
                 for work, nbytes in counts.grouped_products(
                     {"d_model": D, "d_expert": F, "n_routed_experts": ROUTED,
                      "n_experts_held": HELD, "n_experts_per_tok": TOP_K},
                     TOKENS)]
        forward = kernel_ms(
            lambda x, w: moe.grouped_matmul(x, w.astype(x.dtype), sizes,
                                            block_m=block_m), (x, w))
        both = kernel_ms(
            lambda x, w, dy: jax.vjp(
                lambda x, w: moe.grouped_matmul(x, w, sizes, block_m=block_m),
                x, w)[1](dy), (x, w, dy))
        row = {"product": name, "block_m": block_m,
               "forward_ms": forward["moe_grouped_matmul"],
               # (nothing reads the vjp's primal result, so its program
               # holds no forward product: the one call is dx)
               "dx_ms": both["moe_grouped_matmul"],
               "dw_ms": both["moe_grouped_matmul_dw"],
               "least_forward_ms": least[0], "least_dw_ms": least[-1]}
        rows.append(row)
    return rows


_KEPT = {}


def _once(key, make):
    if key not in _KEPT:
        _KEPT[key] = make()
    return _KEPT[key]


def combine(tokens: int, routed: int, tile: int, block_rows: int) -> dict:
    """ms a call at `tokens` tokens x 8 whose router is even over `routed`
    experts, the first 16 held: `routed` 64 the cell's share, 32 twice its
    rows."""
    keys = jax.random.split(jax.random.key(2), 3)
    ids = jax.lax.top_k(jax.random.uniform(keys[0], (tokens, routed)),
                        TOP_K)[1]
    weights = jax.random.uniform(keys[1], (tokens, TOP_K), jnp.float32)
    sort = jax.jit(lambda ids: moe._dispatch(ids, HELD, None, 0))(ids)
    r = min(BUFFER * tokens // TOKENS, tokens * TOP_K)
    # (in the step one block of the rows is rewritten where it lies; an
    # argument of a timed program would be copied whole for it)
    rows = jax.jit(functools.partial(moe._zeros_behind,
                                     block_rows=block_rows))(
        jax.random.normal(keys[2], (r, D), jnp.bfloat16), sort)

    def gathered(rows, sort, weights=None):
        _, _, rank, here = moe._page(0, r, sort, sort.flat < HELD)
        return moe._gathered(rows, rank, here, TOP_K, weights)

    def kernel(rows, sort, weights=None):
        return moe.moe_combine(rows, sort, TOP_K, weights, tile=tile,
                               block_rows=block_rows)

    row = {"tokens": tokens, "held_rows": int(jnp.sum(sort.load)),
           "page": r, "tile": tile, "block_rows": block_rows}
    # the forward's call (the router's weights) and dx's (ones), a program
    # each: in one, XLA would make their common part once
    for form, args in (("weighted", (rows, sort, weights)),
                       ("ones", (rows, sort))):
        a, b = jax.jit(gathered)(*args), jax.jit(kernel)(*args)
        took = kernel_ms(kernel, args)
        row[form] = {
            "kernel_ms": took["moe_combine"],
            "with_its_items_ms": took["all"],
            "gathered_ms": _once((tokens, routed, form), lambda: kernel_ms(
                gathered, args)["all"]),
            "largest_difference": float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32))))}
    return row


def flash(peaks, window: int) -> dict:
    keys = jax.random.split(jax.random.key(1), 4)
    q, k, v, do = (jax.random.normal(key, (BATCH, SEQ, HEADS, HEAD_DIM),
                                     jnp.bfloat16) for key in keys)
    name = "window_flash_attention" if window else "flash_attention"
    forward = kernel_ms(lambda q, k, v: attention.flash_attention(
        q, k, v, window=window), (q, k, v))
    both = kernel_ms(lambda q, k, v, do: jax.vjp(
        lambda q, k, v: attention.flash_attention(q, k, v, window=window),
        q, k, v)[1](do), (q, k, v, do))
    if window:
        work, nbytes = counts.window_flash(BATCH, HEADS, SEQ, HEAD_DIM,
                                           window)
    else:
        work, nbytes = counts.full_flash(BATCH, HEADS, SEQ, HEAD_DIM)
    return {"kernel": name, "forward_ms": forward[name],
            "backward_ms": both[name] - forward[name],
            "least_forward_and_backward_ms":
            1e3 * flops.roofline_s(work, nbytes, peaks)[0]}


def main(argv):
    dev = jax.devices()[0]
    peaks = manifest.peaks(dev.device_kind)
    given = collections.defaultdict(list)
    for a in argv:
        if "=" in a:
            given[a.split("=")[0]].append(int(a.split("=")[1]))
    block_m = given["block_m"]
    parts = [a for a in argv if "=" not in a] or [
        "grouped", "combine", "window", "full"]
    result = {"device": [dev.platform, dev.device_kind], "tree": os.getcwd()}
    for part in parts:
        if part == "grouped":
            result[part] = [row for m in block_m or [512]
                            for row in grouped(peaks, m)]
        elif part == "combine":
            result[part] = [
                combine(tokens, routed, tile, rows)
                for tokens in given["tokens"] or [TOKENS, 4096, 2048, 1024]
                for routed in ([64, 32] if tokens == TOKENS else [64])
                for tile in given["tile"] or [decoder.COMBINE_TILE]
                for rows in given["block_rows"] or [moe.COMBINE_ROWS]]
        else:
            result[part] = flash(peaks, WINDOW if part == "window" else 0)
        print(part, result[part], flush=True)
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1:])
