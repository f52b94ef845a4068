#!/usr/bin/env python3
"""Time `paged_decode_attention` alone at the two K/V serve cells' shapes
(16 lanes, 64 blocks of 16 a lane, bf16; gpt2-xl's 25 heads of 64 in rows
of 1664, OLMoE's 16 heads of 128 in rows of 2048) over the blocks a grid
step takes and both ways of fetching them: the evidence behind
`ops/attention.py::paged_blocks_per_step` and the form that ships
(PERF.md section 6, PR 32).  Not a tool the benchmark runs.  On the chip:

  python3 scripts/paged_kernel_sweep.py [--baseline-root <a checkout>]

Lanes' contexts are drawn as the cells' are (uniform over 16-576 tokens).
A call's time is the wall of one compiled program of `CALLS` calls chained
as the engine's layer scan chains them, over the calls; the bytes a call
needs are the live context's K and V rows, once.

`dma` is the shipped kernel: the pools handed in whole (`pl.ANY`), a lane a
grid step, its live blocks copied by `make_async_copy` into a
double-buffered [R, BS, W] scratch, the trip count read from `ctx_lens`, the
next lane's first run started under the lane's last.  `specs` is the form
kept here for the comparison, the latent kernel's until PR 49 (the record
of the form that is gone from `ops/attention.py`): a grid step a run, every
block of the run by a `BlockSpec` of its own whose index map names a block
already in VMEM for what is past the context, the pipeline fetching what
changed.
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as A

# name: heads, kv heads, head dim, layers of the cell's pool
SHAPES = {"gpt2-xl": (25, 25, 64, 48), "olmoe": (16, 16, 128, 16)}
LANES, MB, BS, NB = 16, 64, 16, 512
POOL_LAYERS = 4                 # the calls go round these
CTX = (16, 576)
HBM_BYTES_PER_S = 819e9         # v5e, Google Cloud documentation


def _specs_kernel(bt_ref, len_ref, layer_ref, q_ref, *refs, block_size,
                  blocks_per_step, n_steps, scale):
    """One (lane, run of `blocks_per_step` blocks) grid step: each block
    arrives by a `BlockSpec` of its own; one softmax update a run."""
    del bt_ref, layer_ref               # only the index maps read them
    kb = blocks_per_step
    k_blocks, v_blocks = refs[:kb], refs[kb:2 * kb]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * kb:]
    lane = pl.program_id(0)
    step = pl.program_id(1)
    n_ctx = len_ref[lane]

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, A.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = step * kb * block_size

    @pl.when(run < n_ctx)
    def _compute():
        k = jnp.concatenate([k[...] for k in k_blocks], axis=0)
        v = jnp.concatenate([v[...] for v in v_blocks], axis=0)
        s = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, R * BS]
        pos = run + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < n_ctx, s, A.NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(step == n_steps - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def specs_paged_decode_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                                 layer=0, *, kv_heads=None, scale=None,
                                 blocks_per_step=8, interpret=False):
    """`ops.attention.paged_decode_attention`'s arguments and result, the
    blocks fetched by the pipeline (the latent kernel's form until PR 49)."""
    b, h, d = q.shape
    kh = kv_heads or h
    _, _, bs, w = k_pool.shape
    mb = block_tables.shape[1]
    kb = min(blocks_per_step, mb)
    n_steps = -(-mb // kb)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    own, spread = A._head_columns(h, kh, d, w, q.dtype)
    q_rows = jnp.where(own, jnp.einsum(
        "bhd,dw->bhw", q, spread, precision=jax.lax.Precision.HIGHEST),
        jnp.zeros((), q.dtype))

    def block_map(r):
        def index(i, j, bt, ln, ly):
            # Steps past the lane's last run name that run again, and a
            # slot past its last block the block the slot held a run
            # before (the last block, in a first run): the pipeline finds
            # each already in VMEM and fetches nothing.
            last = jnp.minimum(jnp.maximum(ln[i] - 1, 0) // bs, mb - 1)
            blk = jnp.minimum(j, last // kb) * kb + r
            blk = jnp.where(blk <= last, blk,
                            jnp.where(blk >= kb, blk - kb, last))
            return (ly[0], bt[i, blk], 0, 0)
        return index

    lane_spec = pl.BlockSpec((None, h, w),
                             lambda i, j, bt, ln, ly: (i, 0, 0))
    pool_specs = [pl.BlockSpec((None, None, bs, w), block_map(r))
                  for r in range(kb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_steps),
        in_specs=[lane_spec] + pool_specs + pool_specs,
        out_specs=lane_spec,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, w), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_specs_kernel, block_size=bs, blocks_per_step=kb,
                          n_steps=n_steps, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, w), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_rows,
      *([k_pool] * kb), *([v_pool] * kb))
    return jnp.einsum("bhw,dw->bhd",
                      jnp.where(own, out, jnp.zeros((), out.dtype)), spread,
                      precision=jax.lax.Precision.HIGHEST)


def _inputs(name, seed, lanes=LANES, mb=MB, bs=BS, nb=NB,
            layers=POOL_LAYERS, dtype=jnp.bfloat16, ctx=CTX):
    h, kh, d, _ = SHAPES[name]
    rng = np.random.default_rng(seed)
    w = A.kv_row_width(kh, d)
    lens = rng.integers(ctx[0], min(ctx[1], mb * bs) + 1, lanes)
    nb = max(nb, 1 + int(sum(-(-int(n) // bs) for n in lens)))
    kk, kv, kq = jax.random.split(jax.random.key(seed), 3)
    pools = [A.pack_kv_rows(jax.random.normal(
        k, (layers, nb, bs, kh, d), jnp.float32)).astype(dtype)
        for k in (kk, kv)]
    assert pools[0].shape[-1] == w
    q = jax.random.normal(kq, (lanes, h, d), jnp.float32).astype(dtype)
    tables = np.zeros((lanes, mb), np.int32)
    free = rng.permutation(np.arange(1, nb))
    at = 0
    for lane, n in enumerate(lens):         # unused entries stay 0
        n_blk = -(-int(n) // bs)
        tables[lane, :n_blk] = free[at:at + n_blk]
        at += n_blk
    return q, pools[0], pools[1], jnp.asarray(tables), jnp.asarray(
        lens, jnp.int32)


def _chain(fn, calls, layers):
    """`calls` calls of the kernel, each call's query the sum of the one
    before and its output, the layer going round the pool's."""
    def run(q, k_pool, v_pool, tables, lens):
        def layer(x, i):
            return (x + fn(x, k_pool, v_pool, tables, lens, i % layers)
                    ).astype(x.dtype), None
        return jax.lax.scan(layer, q, jnp.arange(calls))[0]
    return jax.jit(run)


def _time(fn, args, calls, reps=10):
    prog = _chain(fn, calls, args[1].shape[0])
    prog(*args).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        prog(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / calls


def _load_baseline(root):
    spec = importlib.util.spec_from_file_location(
        "baseline_attention", os.path.join(root, "ray_tpu", "ops",
                                           "attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.paged_decode_attention


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline-root", default=None,
                    help="a checkout whose kernel is timed beside these")
    ap.add_argument("--runs", default="1,2,4,8,16,32")
    ap.add_argument("--seeds", default="1,2")
    ap.add_argument("--ctx", default="%d,%d" % CTX,
                    help="a lane's context is uniform over lo,hi tokens")
    ap.add_argument("--forms", default="specs,dma")
    ap.add_argument("--check", action="store_true",
                    help="a tiny size in the interpreter: both forms "
                         "against the masked-dense reference, no timing")
    a = ap.parse_args()
    if a.check:
        return _check()
    runs = [int(r) for r in a.runs.split(",")]
    rows = []
    for name, (h, kh, d, calls) in SHAPES.items():
        forms = []
        if a.baseline_root:
            base = _load_baseline(a.baseline_root)
            forms.append(("parent", None, functools.partial(
                base, kv_heads=kh, use_kernel=True)))
        for form, fn in (("specs", specs_paged_decode_attention),
                         ("dma", functools.partial(
                             A.paged_decode_attention, use_kernel=True))):
            forms += [(form, kb, functools.partial(
                fn, kv_heads=kh, blocks_per_step=kb))
                for kb in runs if form in a.forms]
        for seed in (int(s) for s in a.seeds.split(",")):
            args = _inputs(name, seed,
                           ctx=tuple(int(c) for c in a.ctx.split(",")))
            lens = np.asarray(args[4])
            need = int(lens.sum()) * args[1].shape[-1] * 2 * 2
            want = np.asarray(A.paged_attention_reference(
                args[0][:, None], *args[1:], (args[4] - 1)[:, None], 1,
                kv_heads=kh)[:, 0], np.float32)
            for form, kb, fn in forms:
                row = {"shape": name, "seed": seed, "form": form,
                       "blocks_per_step": kb,
                       "ctx_tokens": int(lens.sum()), "need_bytes": need}
                try:
                    got = np.asarray(jax.jit(fn)(*args, 1), np.float32)
                    row["max_err"] = float(np.abs(got - want).max())
                    s = _time(fn, args, calls)
                    row["us_a_call"] = s * 1e6
                    row["need_over_time_pct"] = (
                        100 * need / HBM_BYTES_PER_S / s)
                except Exception as e:      # a form the compiler refuses
                    row["error"] = str(e).splitlines()[0][:300]
                rows.append(row)
                print("[sweep]", json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "paged_sweep.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    return 0


def _check() -> int:
    for name in SHAPES:
        h, kh, d, _ = SHAPES[name]
        for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)):
            args = _inputs(name, 3, lanes=4, mb=8, bs=16, nb=40, layers=2,
                           dtype=dtype, ctx=(0, 128))
            args = args[:4] + (args[4].at[1].set(0).at[2].set(128),)
            want = np.asarray(A.paged_attention_reference(
                args[0][:, None], *args[1:], (args[4] - 1)[:, None], 1,
                kv_heads=kh)[:, 0], np.float32)
            live = np.asarray(args[4]) > 0
            for kb in (1, 2, 8):
                for form, fn in (
                        ("specs", specs_paged_decode_attention),
                        ("dma", functools.partial(
                            A.paged_decode_attention, use_kernel=True))):
                    got = np.asarray(fn(
                        *args, 1, kv_heads=kh, blocks_per_step=kb,
                        interpret=True), np.float32)
                    assert np.isfinite(got).all(), (name, form, kb)
                    err = np.abs(got - want)[live].max()
                    print(name, jnp.dtype(dtype).name, form, kb, err)
                    assert err < tol, (name, form, kb, err)
    print("ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
