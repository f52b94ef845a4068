#!/usr/bin/env python3
"""Time the loss head alone at the shape `train_gpt2s_1chip` calls it with:
`fused_cross_entropy` over `[24576, 768]` rows (bf16) and the tied
embedding `[50304, 768]` (float32, turned round and cast as
`models/decoder.py::_head` does), four chunks of 6,144 rows, its value and
both gradients, as a train step runs it.  Three forms from the one tree:
the program's own (`kernel`: a `logits_lse` and a `loss_head_grads` call a
chunk, `ops/cross_entropy.py`'s plans take the shape), `products` (the
`logits_lse` kernel and XLA's two gradient products: what every shape the
gradients' plan refuses runs, forced here), and `xla` (no kernel: XLA's
product with a pass of its own over the float32 logits for the logsumexp).
A few calls under the profiler; the figures are the device's own durations,
in ms a CHUNK, of

  logits   the chunk's `f32[6144,50304]` product (the kernel, or XLA's fusion
           with the row maximum)
  pass     what else reads `[6144,50304]` for the logsumexp and the target's
           logit (XLA form only: the kernel form has no such row)
  dx       XLA's product `[6144,50304] x [50304,768]`
  dhead    XLA's products `[768,6144] x [6144,50304]`, summed over the chunks
  grads    the `loss_head_grads` kernel, which makes both (then `dx` and
           `dhead` read 0)
  other    the rest of the program (the target's gather and row dot, the
           head's cast, the sums), whose longest rows `others` names

and `logits_roofline_pct`, the least time for one `[24576,768] x
[768,50304]` product (`benchmark/layer_metrics/logits_lse_roofline.py`'s
count) over the time of the `logits` row, and `grads_roofline_pct`, the
least time for the two gradient products
(`benchmark/layer_metrics/loss_head_grads_roofline.py`'s count) over the
`dx`, `dhead` and `grads` rows.  The train cell's twin of
`scripts/engine_step_time.py`, as `scripts/flash_step_time.py` is for the
flash kernels: not a tool the benchmark runs.  On the chip, from the root of
a checkout:

  python3 scripts/loss_head_time.py [xla] [products] [kernel]
      [tm,tn[,sub] ...] [grads=tm,tn[,sub] ...]

With no argument: `xla`, `products`, then `kernel`.  `tm,tn,sub` gives
`logits_lse` a row tile, a vocabulary tile and a sub-tile of rows
(`_LSE_ROW_TILES`, `_LSE_COL_TILE`, `_LSE_SUB_ROWS`); `grads=tm,tn,sub`
gives `loss_head_grads` its own (`_GRAD_ROW_TILES`, `_GRAD_COL_TILE`,
`_GRAD_SUB_ROWS`), and says so where the plan refuses them.  The last line
is one JSON object.
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
from ray_tpu.ops import cross_entropy as ce

T, D, V, CHUNKS = 24576, 768, 50304, 4
CALLS = 5
WIDE = re.compile(rf"\[{T // CHUNKS},{V}\]")


def part_of(text: str) -> str:
    """Which row of the table an operation of the program belongs to, by
    its HLO text: its name, its result and whether it reads or writes a
    chunk's `[6144,50304]`."""
    base, label, kernel, _ = trace_reduce.describe(text)
    if kernel:
        return {"logits_lse": "logits", "loss_head_grads": "grads"}.get(
            base, "other")
    made = text.split(" = ", 1)[-1]       # `(a, b) fusion(...` or `a fusion(`
    result = made[:made.index(") ") + 1] if made.startswith("(") \
        else made.split(" ", 1)[0]
    product = "convolution" in base or "convolution" in text.split("(")[0]
    if f"[{D},{V}]" in result or f"[{V},{D}]" in result:
        return "dhead" if WIDE.search(text) or product else "other"
    if re.search(rf"\[(1,)?{T // CHUNKS},{D}\]", result) and (
            WIDE.search(text) or product):
        return "dx"
    if WIDE.search(text):
        # XLA's logits product is a fusion that also gives the row maximum
        # and is named after it; the pass reads the logits and gives sums
        return "logits" if f"f32[{T // CHUNKS},{V}]" in result else "pass"
    return "other"


def device_ms(fn, args):
    """{part: ms a call} and the longest `other` rows of `fn`."""
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
    rows = collections.Counter()
    for text, ns in trace_reduce.self_times(lines[trace_reduce.OPS_LINE]):
        part = part_of(text)
        took[part] += ns / 1e6 / CALLS
        rows[f"{part}: {trace_reduce.describe(text)[1]}"] += ns / 1e6 / CALLS
    return took, [[label, round(ms, 4)] for label, ms in rows.most_common(14)]


def main(forms):
    dev = jax.devices()[0]
    peaks = manifest.peaks(dev.device_kind)
    least_ms = 1e3 * flops.roofline_s(
        2.0 * T * D * V, 4.0 * T * V + 2.0 * T * D + 2.0 * D * V, peaks)[0]
    least_grads_ms = 1e3 * flops.roofline_s(
        4.0 * T * D * V, 4.0 * T * V + 2.0 * T * D + 6.0 * D * V, peaks)[0]
    keys = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(keys[0], (T, D), jnp.bfloat16)
    embed = 0.02 * jax.random.normal(keys[1], (V, D), jnp.float32)
    targets = jax.random.randint(keys[2], (T,), 0, V)
    valid = (jax.random.uniform(keys[3], (T,)) > 0.01).astype(jnp.float32)

    def step(x, embed):
        return jax.value_and_grad(
            lambda x, embed: ce.fused_cross_entropy(
                x, embed.T.astype(x.dtype), targets, valid, CHUNKS),
            argnums=(0, 1))(x, embed)

    own = (ce._LSE_ROW_TILES, ce._LSE_COL_TILE, ce._LSE_SUB_ROWS)
    own_grads = (ce._GRAD_ROW_TILES, ce._GRAD_COL_TILE, ce._GRAD_SUB_ROWS)
    plan, grads_plan = ce._lse_plan, ce._grads_plan
    result = {"device": [dev.platform, dev.device_kind], "tree": os.getcwd(),
              "shape": [T, D, V, CHUNKS], "least_logits_ms_a_chunk":
              least_ms / CHUNKS, "rows": []}
    for form in forms or ["xla", "products", "kernel"]:
        ce._lse_plan, ce._grads_plan = plan, grads_plan
        ce._LSE_ROW_TILES, ce._LSE_COL_TILE, ce._LSE_SUB_ROWS = own
        ce._GRAD_ROW_TILES, ce._GRAD_COL_TILE, ce._GRAD_SUB_ROWS = own_grads
        if form in ("xla", "products"):
            ce._grads_plan = lambda *shape: None
            if form == "xla":
                ce._lse_plan = lambda *shape: None
        elif form.startswith("grads="):
            tiles = form[len("grads="):].split(",")
            ce._GRAD_ROW_TILES, ce._GRAD_COL_TILE = (int(tiles[0]),), int(
                tiles[1])
            if len(tiles) > 2:
                ce._GRAD_SUB_ROWS = int(tiles[2])
            if ce._grads_plan(T // CHUNKS, D, V) is None:
                print(f"form={form}: the plan refuses it", flush=True)
                continue
        elif form != "kernel":
            tiles = [int(t) for t in form.split(",")]
            ce._LSE_ROW_TILES, ce._LSE_COL_TILE = (tiles[0],), tiles[1]
            if len(tiles) > 2:
                ce._LSE_SUB_ROWS = tiles[2]
        jax.clear_caches()
        try:
            took, rows = device_ms(step, (x, embed))
        except Exception as e:     # a tile the compiler refuses: say, go on
            print(f"form={form} failed: {str(e)[:300]}", flush=True)
            continue
        row = {"form": form}
        row.update({f"{part}_ms": took[part] / CHUNKS for part in
                    ("logits", "pass", "dx", "dhead", "grads", "other")})
        row["all_ms_a_call"] = sum(took.values())
        row["logits_roofline_pct"] = 100.0 * least_ms / max(took["logits"],
                                                             1e-9)
        row["grads_roofline_pct"] = 100.0 * least_grads_ms / max(
            took["grads"] + took["dx"] + took["dhead"], 1e-9)
        row["rows"] = rows
        result["rows"].append(row)
        print("  ".join(f"{key}={val:.3f}" if isinstance(val, float)
                        else f"{key}={val}" for key, val in row.items()),
              flush=True)
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1:])
