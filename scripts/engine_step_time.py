#!/usr/bin/env python3
"""Time the engine's compiled step alone at `serve_gpt2xl_decode`'s sizes
(gpt2-xl whole on the tree `serving_params` prepares, 16 lanes of 100-350
tokens, 512 blocks of 16): the T=1 program; the T=32 program with two
lanes prefilling a chunk, once with a valid row in each of the other 14
lanes (`t32`, the figure PERF.md has carried since PR 34) and once with
them masked and at context 1 as `_build_batch` hands a prefill step over
(`t32_masked`); and the speculative verify program (`verify_t5`: `spec_k`
4, every lane's five rows valid over its whole context).  Each is called
back to back with the pools fed round, so the figure is the device
program's length and holds no host work.  Since PR 34 the host is the longer side of that cell's traced
slice, and `decode_step_ms_p50` there reads the host (PERF.md sections 5
and 7); this is where the program's own length comes from.  Not a tool the
benchmark runs.  On the chip, from the root of a checkout (the parent's,
to compare):

  python3 scripts/engine_step_time.py [scan_unroll ...]

With no argument the configuration's own `scan_unroll`; several give the
layer loop at each (PERF.md section 6, PR 34: 9.45 ms at 4, 9.53 at 1,
14.00 before the loop indexed its stacks).  The last line is one JSON
object, ms a call over three sets of calls.

Since PR 42 the engine runs each step behind the unpacking of the lanes' one
buffer (`_make_entry`): every program is timed that way too (`..._entry_ms`,
beside the nine-array step's `..._ms`: the difference is what the unpacking
costs the device), and `host_upload_ms` is what handing a T=1 population to
the device costs the host with nothing else running (`_upload`, mean of 200
calls, three sets), beside the eight `jnp.asarray` of the same arrays that
the engine made before (`host_upload_eight_ms`).

Since PR 45 also the write path alone (`ops/attention.py::paged_rows_update`):
a layer's call in a loop of 48 trips over donated pools, the trip's layer its
index, nothing else in the loop, as the XLA loop that wrote the rows until
then (`loop`) and as the kernel (`kernel`), ms a 48-trip call, so 1000 / 48
of it is microseconds a layer; both forms must leave the same pools (a
digest of each that weighs a row by where it lies), or the script fails.
`rows_write_t1_ms` is the T=1 step's call (every lane a row),
`rows_write_t32_ms` a prefill step's as the engine builds it (two lanes a
chunk, the others masked), at gpt2-xl's shapes; a cell's name as an
argument (`write=evabyte`, `write=falconh1`, `write=olmoe`, `write=axk1`,
`write=dots3`) gives `rows_write_<name>_t1_ms` and `..._t<chunk>_ms` at that
cell's lanes, pools and prefill program, read from the files the benchmark
runs the cell from (`cell_writes`; a second call a layer body makes, dots3's
window layers', is `rows_write_<name>_call2_...`).  With `write=` arguments
alone the step programs are not timed:

  python3 scripts/engine_step_time.py write=gpt2xl write=evabyte

Since PR 48 also an indexed layer's choice of rows alone
(`ops/attention.py::sparse_select`), `select=dots3`: every lane's `topk` of
the scores of a full table at the cell's lanes, blocks and context, in a loop
of 20 trips over four sets of scores (one of them rounded to quarters, ties by
the hundred), ms a trip (`select_<name>_t1_ms`): as the stable two-operand
`jax.lax.sort` that made the choice until then, re-created here (`sort`), as
the kernel (`kernel`, with the relayout XLA makes in front of it) and as the
same steps in `jax.numpy` (`jnp`, the CPU's path, compiled for the chip).  All
three must choose the same set, or the script fails.

Since PR 49 also the single-query kernels over one pool of rows alone
(`ops/attention.py`: `latent_decode_attention`, and dots3's
`sparse_index_scores`, `window_latent_decode_attention` and the indexed
attention over the gathered rows), `attend=axk1` / `attend=dots3`: each of
the cell's kernels at its lanes, pools, table and a context a block short of
full, 20 calls chained in one program as the layer scan chains them, ms a
call (`attend_<name>_<kernel>_ms`) and the share of the least the chip could
take by the benchmark's own arithmetic (`..._least_pct`:
`latent_flops.latent_decode`, `sparse_flops.index_scores` / `.latent_rows`).
`--baseline-root <a checkout>` times that tree's `ops/attention.py` beside
this one's (`parent`), and `runs=4,8,16` this tree's at those
`blocks_per_step`:

  python3 scripts/engine_step_time.py attend=axk1 attend=dots3 \
      runs=4,8,16 --baseline-root .scratch/parent

Since PR 53 also an ADMITTING iteration's programs, `mixed=<cell>`
(`mixed=gpt2xl`, `mixed=olmoe`, ...): the cell's own engine (its weights from
a seed, its lanes, pools and chunk), every lane but one decoding at a
context of a quarter to a half of its share of the pool (130-320 tokens in
gpt2-xl's cell) and one prefilling a chunk of its prompt, and
the programs the engine dispatches for that iteration, called back to back
on the batches the engine itself builds: the T=1 program alone
(`mixed_<cell>_t1_ms`) and the whole iteration (`mixed_<cell>_admit_ms`: in a
tree from before PR 53 the T=1 program and the prefill program behind it,
since then the pair's one program; `..._programs` says how many it was).
It goes by what the engine in the working directory's tree does, so the
same script times a parent checkout when run from its root
(`mixed=gpt2xl:8`: the chunk at 8 lanes, whatever the traffic or the rows
rule says):

  python3 scripts/engine_step_time.py mixed=gpt2xl mixed=olmoe
  (cd .scratch/parent && python3 ../../scripts/engine_step_time.py \
      mixed=gpt2xl mixed=olmoe)

Since PR 54 `mixed=lfm2:4` is `serve_lfm2_rag_decode`'s shapes: the T=1
program at 128 lanes (`mixed_lfm2_lanes4_t1_ms`: 127 lanes decoding at
1.2-2.4k tokens, the experts of 8 layers, the conv mixers' per-lane part
over 128 tails in 7) and the pair's at 128 + 4 x 256 rows
(`mixed_lfm2_lanes4_admit_ms`); `mixed=lfm2` the one-row chunk an admission
behind a cached head rides ([1, 256] beside the 128):

  python3 scripts/engine_step_time.py mixed=lfm2:4

Since PR 57 also Kimi Delta Attention's two kernels alone
(`ops/ssm.py`), `update=kimilinear` / `scan=kimilinear`, at
`serve_kimilinear_reasoning_decode`'s state buffer ([6, 129, 32, 128, 128]
float32) from the files the benchmark runs the cell from: `kda_update` over
the cell's 128 lanes, 24 calls chained in one program with the layer going
round as the layer loop does, ms a call and the share of the least the chip
could take by the benchmark's own arithmetic (`kda_flops.update`), beside
`ssm_update` (Mamba-2's recurrence as `nemotron-3-nano-30b-a3b` runs it: 64
heads of 64 folded by two, 8 groups, a state of 128) on a buffer of the same
shape and the same lanes; `kda_scan` over
`[4, 256]` and `[1, 256]` rows (an admission's chunk), as the kernel and as
the same chunked form in plain XLA (`ops.ssm._kda_chunks`), at the
configuration's `kda_chunk` and at chunks of 16 and 64; every form must
leave the same state, or the script fails:

  python3 scripts/engine_step_time.py update=kimilinear scan=kimilinear
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import importlib.util
import json
import os
import sys
import time
import types

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, latent_flops, manifest, sparse_flops
from ray_tpu.inference.engine import InferenceEngine, _lane_views
from ray_tpu.inference.kv_cache import PagedKVCache
from ray_tpu.models import decoder, gpt
from ray_tpu.ops import attention
from ray_tpu.ops.attention import (NEG_INF, kv_row_width, paged_rows_update,
                                   sparse_select, sparse_select_reference)

WRITE_TRIPS = 48
# The prefill program timed where it is not the cell's widest ([prefill_lanes,
# prefill_chunk]): the one row of a turn behind an adopted prompt, which is
# what these cells' windows hold (PERF.md section 5).
ONE_ROW_CHUNKS = {"serve_falconh1_chat_decode": (1, 64),
                  "serve_dots3_docs_decode": (1, 128)}


def cell_writes(cell):
    """What serve cell `cell` asks of the write path, from the files the
    benchmark runs it from (`BENCHMARK.json`, `benchmark/configs`,
    `benchmark/traffic`): (lanes, the prefill program's (rows, T), the
    calls a step makes), a call the pools ([L, NB, BS, W] shapes) that one
    `paged_rows_update` of a layer writes.  The pools are those of the
    cache the engine would make (nothing is allocated); the pools of one
    kind of layer, under one table, go through one call."""
    m = manifest.load()
    file = m.load_config(m.cells[cell]["config"])
    config = manifest.model_config(file, None)
    eng = m.load_traffic(m.cells[cell]["traffic"])["engine"]
    pools = jax.eval_shape(lambda: PagedKVCache.for_model(
        importlib.import_module(file["module"]), config,
        num_blocks=eng["num_blocks"], block_size=eng["block_size"],
        max_lanes=eng["max_lanes"],
        max_seq_len=eng.get("max_seq_len", config.max_seq_len),
        ahead=2 * eng["prefill_chunk"]).step_pools)
    calls = {}
    for pool in jax.tree.leaves(pools):
        if pool.ndim == 4:                  # rows in blocks, not a state
            calls.setdefault(pool.shape[:3], []).append(pool)
    chunk = ONE_ROW_CHUNKS.get(cell, (
        eng.get("prefill_lanes", eng["max_lanes"]), eng["prefill_chunk"]))
    return eng["max_lanes"], chunk, [tuple(c) for c in calls.values()]


def time_rows_write(name, out):
    """The write path alone at the shapes of the serve cell of configuration
    `name`, into `out`."""
    (cell,) = [c for c in manifest.load().cells
               if c.startswith(f"serve_{name}_")]
    lanes, chunk, calls = cell_writes(cell)
    for i, shapes in enumerate(calls):
        key = "rows_write_" + ("" if name == "gpt2xl" else name + "_") + (
            f"call{i + 1}_" if i else "")
        time_one_call(key, lanes, chunk, shapes, out)


def time_one_call(key, lanes, chunk, shapes, out):
    """One call of the write path, `shapes` its pools, into `out[key...]`."""
    layers, nb, bs = shapes[0].shape[:3]
    chunk_rows, chunk = chunk
    rng = np.random.default_rng(0)
    mb = nb // lanes
    tables = jnp.asarray((np.arange(lanes)[:, None] * mb
                          + np.arange(mb)[None]).astype(np.int32))
    for rows, t in ((lanes, 1), (chunk_rows, chunk)):
        ctx = rng.integers(100, min(350, mb * bs - t), rows)
        positions = (ctx[:, None] + np.arange(t)[None]).astype(np.int32)
        valid = np.ones((rows, t), bool)
        if t > 1 and rows == lanes:     # two lanes prefill, the others wait
            valid[2:], positions[2:] = False, 0
        new = tuple(jnp.asarray(rng.standard_normal((rows, t, p.shape[3])),
                                p.dtype) for p in shapes)
        args = (new, tables[:rows], jnp.asarray(positions),
                jnp.asarray(valid))
        left = {}

        @jax.jit
        def digest(pool):               # every row weighed by where it lies
            at = (1 + jnp.arange(nb * bs) % 1021).reshape(nb, bs, 1)
            return jnp.sum(pool.astype(jnp.float32) * at, axis=(1, 2))

        for form, use_kernel in (("loop", False), ("kernel", True)):
            def layers_of_writes(pools, new, tables, positions, valid):
                return jax.lax.fori_loop(
                    0, WRITE_TRIPS, lambda i, pools: paged_rows_update(
                        pools, new, tables, positions, valid, i % layers,
                        use_kernel=use_kernel), pools)

            fn = jax.jit(layers_of_writes, donate_argnums=0)
            pools = tuple(jnp.zeros(p.shape, p.dtype) for p in shapes)
            pools = jax.block_until_ready(fn(pools, *args))
            # what one call leaves: a digest of each pool, on the device
            left[form] = [np.asarray(digest(p)) for p in pools]
            ms = []
            for _ in range(3):
                n = 50 if t == 1 else 20
                t0 = time.perf_counter()
                for _ in range(n):
                    pools = fn(pools, *args)
                jax.block_until_ready(pools)
                ms.append(round(1000 * (time.perf_counter() - t0) / n, 4))
            out.setdefault(f"{key}t{t}_ms", {})[form] = ms
            del pools
        for a, b in zip(left["loop"], left["kernel"]):
            np.testing.assert_array_equal(a, b)
            assert a.any()


SELECT_TRIPS = 20


def time_select(name, out):
    """The choice alone at the shapes of the serve cell of configuration
    `name`, into `out`."""
    m = manifest.load()
    (cell,) = [c for c in m.cells if c.startswith(f"serve_{name}_")]
    file = m.load_config(m.cells[cell]["config"])
    config = manifest.model_config(file, None)
    eng = m.load_traffic(m.cells[cell]["traffic"])["engine"]
    spec = importlib.import_module(file["module"]).spec(config)
    topk = max(getattr(run.sizes, "index_topk", 0) for run in spec.runs)
    lanes, bs = eng["max_lanes"], eng["block_size"]
    nb = np.ravel(eng["num_blocks"])[0]     # the full layers' pool first
    mb = -(-eng["max_seq_len"] // bs)
    n, k = mb * bs, min(topk, mb * bs)
    rng = np.random.default_rng(0)
    scores = rng.standard_normal((4, lanes, n)).astype(np.float32)
    scores[1] = np.round(scores[1] * 4) / 4
    ctx = rng.integers(n - 5 * bs, n, (4, lanes, 1))
    scores = jnp.asarray(np.where(np.arange(n) < ctx, scores, NEG_INF))
    tables = jnp.asarray(np.stack([rng.permutation(nb)[:mb]
                                   for _ in range(lanes)]).astype(np.int32))

    def sort(scores, tables):       # the choice until PR 48
        place = (jnp.repeat(tables, bs, axis=1) * bs
                 + jnp.arange(n, dtype=jnp.int32) % bs)
        return jax.lax.sort((-scores, place), dimension=1, num_keys=1,
                            is_stable=True)[1][:, :k]

    forms = {"sort": sort,
             "kernel": functools.partial(sparse_select, block_size=bs, k=k),
             "jnp": functools.partial(sparse_select_reference,
                                      block_size=bs, k=k)}
    chosen = {}
    for form, fn in forms.items():
        def trips(stack, tables):
            return jax.lax.fori_loop(
                0, SELECT_TRIPS,
                lambda i, acc: acc + fn(stack[i % 4], tables),
                jnp.zeros((lanes, k), jnp.int32))

        chosen[form] = np.sort(np.asarray(jax.jit(fn)(scores[1], tables)))
        many = jax.jit(trips)
        jax.block_until_ready(many(scores, tables))
        ms = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(5):
                left = many(scores, tables)
            jax.block_until_ready(left)
            ms.append(round(1000 * (time.perf_counter() - t0)
                            / (5 * SELECT_TRIPS), 4))
        out.setdefault(f"select_{name}_t1_ms", {})[form] = ms
    for form in ("kernel", "jnp"):
        np.testing.assert_array_equal(chosen[form], chosen["sort"])


ATTEND_CALLS = 20


def time_attend(name, out, runs=(), baseline_root=None):
    """The single-query kernels over one pool of rows alone at the shapes
    of the serve cell of configuration `name`, into `out`."""
    m = manifest.load()
    (cell,) = [c for c in m.cells if c.startswith(f"serve_{name}_")]
    file = m.load_config(m.cells[cell]["config"])
    config = manifest.model_config(file, None)
    eng = m.load_traffic(m.cells[cell]["traffic"])["engine"]
    spec = importlib.import_module(file["module"]).spec(config)
    lanes, _, calls = cell_writes(cell)
    pools = {p.shape[3]: p for call in calls for p in call}
    bs = eng["block_size"]
    mb = -(-eng["max_seq_len"] // bs)
    peaks = manifest.peaks(jax.devices()[0].device_kind)
    rng = np.random.default_rng(0)
    ctx = jnp.asarray(rng.integers((mb - 2) * bs, (mb - 1) * bs, lanes),
                      jnp.int32)
    n_ctx = float(ctx.sum())
    trees = {"kernel": attention}
    if baseline_root:
        at = importlib.util.spec_from_file_location(
            "baseline_attention",
            os.path.join(baseline_root, "ray_tpu", "ops", "attention.py"))
        trees["parent"] = importlib.util.module_from_spec(at)
        at.loader.exec_module(trees["parent"])

    def timed(kernel, least, pool, make, swept=True):
        """`make(ops, **kw)(pool, layer)` -> a call's result, for each
        tree's `ops` (and this tree's at each of `runs`)."""
        forms = [(form, ops, {}) for form, ops in trees.items()]
        forms += [(f"run{kb}", attention, {"blocks_per_step": kb})
                  for kb in runs if swept]
        data = jax.random.normal(jax.random.key(1), pool.shape, pool.dtype)
        want = None
        for form, ops, kw in forms:
            call = make(ops, **kw)

            def chain(data):
                first = call(data, 0)
                return jax.lax.fori_loop(
                    1, ATTEND_CALLS, lambda i, acc: acc + call(
                        data, i % pool.shape[0]).astype(acc.dtype),
                    first.astype(jnp.float32)), first

            many = jax.jit(chain)
            _, first = jax.block_until_ready(many(data))
            first = np.asarray(first, np.float32)
            first = np.where(first > NEG_INF / 2, first, 0)
            if want is None:
                want = first
            # (bf16 products summed in another order; a row of scores is
            # as long as a form's whole runs)
            width = min(first.shape[-1], want.shape[-1])
            np.testing.assert_allclose(first[..., :width], want[..., :width],
                                       atol=0.05, rtol=0.05)
            ms = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(5):
                    left = many(data)
                jax.block_until_ready(left)
                ms.append(round(1000 * (time.perf_counter() - t0)
                                / (5 * ATTEND_CALLS), 4))
            key = f"attend_{name}_{kernel}"
            out.setdefault(key + "_ms", {})[form] = ms
            out.setdefault(key + "_least_pct", {})[form] = round(
                100 * least * 1000 / min(ms), 1)
            print("[attend]", key, form, ms, flush=True)

    def tables_of(nb):
        return jnp.asarray(np.stack([rng.permutation(nb)[:mb]
                                     for _ in range(lanes)]).astype(np.int32))

    def rows(shape, dtype):
        return jax.random.normal(jax.random.key(2), shape, dtype)

    seen = set()
    for run in decoder._stacks(spec, config):
        s = decoder.latent_sizes(spec, run.sizes or config)
        if s in seen:
            continue
        seen.add(s)
        f = dataclasses.asdict(s)
        pool = pools[attention.latent_row_width(s.kv_lora_rank,
                                                s.qk_rope_head_dim)]
        tables = tables_of(pool.shape[1])
        q = rows((lanes, s.n_heads, pool.shape[3]), pool.dtype)
        kw = dict(v_width=s.kv_lora_rank, scale=s.attn_scale)
        if s.window:
            least, _ = flops.roofline_s(*sparse_flops.latent_rows(
                lanes * s.window, lanes, f), peaks)
            timed("window_latent_decode_attention", least, pool,
                  lambda ops, **x: lambda data, layer:
                  ops.window_latent_decode_attention(
                      q, data, tables, ctx, ctx - s.window, layer,
                      span=s.window, use_kernel=True, **kw, **x))
        elif s.index_topk:
            keys = pools[s.index_head_dim]
            q_i = rows((lanes, s.index_n_heads, s.index_head_dim),
                       keys.dtype)
            w_i = rows((lanes, s.index_n_heads), jnp.float32)
            least, _ = flops.roofline_s(*sparse_flops.index_scores(
                n_ctx, lanes, f), peaks)
            timed("sparse_index_scores", least, keys,
                  lambda ops, **x: lambda data, layer:
                  ops.sparse_index_scores(q_i, w_i, data, tables, ctx,
                                          layer, use_kernel=True, **x))
            # the indexed attention's own call: the gathered rows as a
            # pool of one layer under an `arange` table
            k = min(s.index_topk, mb * bs)
            least, _ = flops.roofline_s(*sparse_flops.latent_rows(
                lanes * k, lanes, f), peaks)
            gathered = jax.ShapeDtypeStruct(
                (1, lanes * k // bs, bs, pool.shape[3]), pool.dtype)
            own = jnp.arange(lanes * k // bs, dtype=jnp.int32).reshape(
                lanes, k // bs)
            timed("sparse_latent_decode_attention", least, gathered,
                  lambda ops, **x: lambda data, layer:
                  ops.latent_decode_attention(
                      q, data, own, jnp.minimum(ctx, k), layer,
                      use_kernel=True, **kw, **x))
        else:
            least, _ = flops.roofline_s(*latent_flops.latent_decode(
                n_ctx, lanes, f), peaks)
            timed("latent_decode_attention", least, pool,
                  lambda ops, **x: lambda data, layer:
                  ops.latent_decode_attention(
                      q, data, tables, ctx, layer, use_kernel=True, **kw,
                      **x))


def time_steps(unrolls, out):
    """The engine's step programs at `serve_gpt2xl_decode`'s sizes, into
    `out`."""
    m = manifest.load()
    cell = m.cells["serve_gpt2xl_decode"]
    base = manifest.model_config(m.load_config(cell["config"]), None)
    engine = m.load_traffic(cell["traffic"])["engine"]
    lanes, bs, nb = (engine[k] for k in ("max_lanes", "block_size",
                                         "num_blocks"))
    mb = base.max_seq_len // bs
    params = jax.block_until_ready(jax.jit(
        lambda k: gpt.serving_params(gpt.init_params(base, k), base))(
            jax.random.key(0)))
    rng = np.random.default_rng(0)
    for unroll in unrolls or [base.scan_unroll]:
        cfg = dataclasses.replace(base, scan_unroll=unroll)
        eng = object.__new__(InferenceEngine)     # the step, no thread
        eng.model, eng.config, eng._capture_logp = gpt, cfg, False
        eng.backend, eng._step_impls = jax.default_backend(), {}
        steps = {spec: eng._make_step_fn(False, spec)
                 for spec in (False, True)}
        chunk = engine["prefill_chunk"]
        for name, t in (("t1", 1), (f"t{chunk}", chunk),
                        (f"t{chunk}_masked", chunk), ("verify_t5", 5)):
            step = steps[name.startswith("verify")]
            shape = (cfg.n_layers, nb, bs,
                     kv_row_width(cfg.n_heads, cfg.head_dim))
            k, v = jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
            ctx = rng.integers(100, 350, lanes)
            tables = (np.arange(lanes)[:, None] * (nb // lanes)
                      + np.arange(mb)[None]) % nb
            valid = np.zeros((lanes, t), bool)
            valid[:, 0] = True
            positions = np.repeat((ctx - 1)[:, None], t, 1)
            gather = np.zeros(lanes, np.int32)
            if name.startswith("verify"):           # every lane, its draft
                valid[:], gather[:] = True, t - 1
                positions = ctx[:, None] - t + np.arange(t)[None]
            elif name.endswith("masked"):
                valid[:], ctx[:], positions[:] = False, 1, 0
            for lane in (0, 1) if t == chunk else ():
                # two lanes prefill, 14 decode
                valid[lane], ctx[lane], gather[lane] = True, t, t - 1
                positions[lane] = np.arange(t)
            host = (
                rng.integers(0, cfg.vocab_size, (lanes, t)).astype(np.int32),
                positions.astype(np.int32), valid, ctx.astype(np.int32),
                gather, np.zeros(lanes, np.float32),
                np.zeros(lanes, np.uint32), np.zeros(lanes, np.int32))
            args = [jnp.asarray(a) for a in host]
            args.insert(3, jnp.asarray(tables.astype(np.int32)))
            # the same population as the engine hands it over: one buffer
            buffer, views, _ = _lane_views(lanes, t, False, lanes)
            for view, a in zip(views, host):
                view[...] = a
            entry = eng._make_entry(t, False, name.startswith("verify"), 0)
            packed = (jnp.asarray(buffer), args[3])

            def run(fn, args, n, k, v):
                t0 = time.perf_counter()
                for _ in range(n):
                    tok, k, v = fn(params, k, v, *args)
                jax.block_until_ready((tok, k, v))
                return 1000 * (time.perf_counter() - t0) / n, k, v

            for fn, given, key in ((step, args, "ms"),
                                   (entry, packed, "entry_ms")):
                _, k, v = run(fn, given, 3, k, v)         # compile and warm
                ms = []
                for _ in range(3):
                    each, k, v = run(fn, given, 200 if t == 1 else 40, k, v)
                    ms.append(round(each, 4))
                out[f"unroll{unroll}_{name}_{key}"] = ms
            del k, v
            if t == 1 and "host_upload_ms" not in out:
                eng._uploads = dict.fromkeys(
                    ("populations", "transfers", "bytes"), 0)
                eng.cache = types.SimpleNamespace(    # tables unchanged
                    tables_on_device=True, block_tables=tables,
                    device_tables=lambda: args[3])
                population = (1, False, buffer, views, None)
                for key, upload in (
                        ("host_upload_ms", lambda: eng._upload(population)),
                        ("host_upload_eight_ms",
                         lambda: [jnp.asarray(a) for a in views])):
                    upload()
                    ms = []
                    for _ in range(3):
                        t0 = time.perf_counter()
                        for _ in range(200):
                            kept = upload()
                        ms.append(round(
                            (time.perf_counter() - t0) * 1000 / 200, 4))
                        jax.block_until_ready(kept)
                    out[key] = ms


def time_mixed(name, out):
    """An admitting iteration's programs at the serve cell of configuration
    `name`, from the engine of the tree in the working directory, into
    `out`."""
    m = manifest.load()
    name, _, rows = name.partition(":")     # `gpt2xl:8`: a chunk of 8 lanes
    (cell,) = [c for c in m.cells if c.startswith(f"serve_{name}_")]
    file = m.load_config(m.cells[cell]["config"])
    traffic = m.load_traffic(m.cells[cell]["traffic"])["engine"]
    eng = InferenceEngine(
        file["module"].rsplit(".", 1)[-1], manifest.model_config(file, None),
        seed=0, auto_start=False, prefix_cache=False,
        **{**traffic, **({"prefill_lanes": int(rows)} if rows else {})})
    if rows:
        eng._widths, name = False, f"{name}_lanes{rows}"
    lanes, chunk = eng.max_lanes, eng.prefill_chunk
    rng = np.random.default_rng(0)
    vocab = eng.config.vocab_size
    # (admission reserves a lane's blocks to its worst-case final length)
    blocks = traffic["num_blocks"]
    room = min(eng.cache.max_seq_len, eng.cache.block_size * (
        blocks if isinstance(blocks, int) else blocks[0]) // lanes)
    for n in rng.integers(room // 4, room // 2, lanes - 1):
        eng.submit(rng.integers(0, vocab, int(n)).tolist(), room - int(n))
    while eng._waiting or any(r is not None and r.next_fed < len(r.prompt)
                              for r in eng._lanes):
        eng.step()
    eng.step(), eng.step()              # the T=1 program is made
    eng.submit(rng.integers(0, vocab, 2 * chunk + 3).tolist(), 8)
    with eng._lock:
        eng._admit()
    live = [(i, r) for i, r in enumerate(eng._lanes) if r is not None]
    decode = [(i, r) for i, r in live if r.next_fed == len(r.prompt)]
    prefill = [(i, r) for i, r in live if r.next_fed < len(r.prompt)]
    assert (len(decode), len(prefill)) == (lanes - 1, 1)
    parts = dict.fromkeys(("windows", "assemble", "upload"), 0.0)
    if hasattr(eng, "_pairs"):
        alone = [eng._plan(parts, False, decode, [], 1)[4]]
        admit = [eng._plan(parts, False, decode, prefill, chunk)[4]]
    else:
        alone = [eng._plan(parts, False, decode, 1)[4]]
        admit = [alone[0], eng._plan(parts, False, prefill, chunk, True)[4]]

    def run(batches, n):
        for batch in batches:           # made and warm
            eng._run_step(batch)
        jax.block_until_ready(eng.cache.step_pools)
        ms = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                for batch in batches:
                    eng._run_step(batch)
            jax.block_until_ready(eng.cache.step_pools)
            ms.append(round(1000 * (time.perf_counter() - t0) / n, 4))
        return ms

    out[f"mixed_{name}_t1_ms"] = run(alone, 100)
    out[f"mixed_{name}_admit_ms"] = run(admit, 40)
    out[f"mixed_{name}_programs"] = len(admit)
    rows = admit[-1][3]             # the chunk's rows, where it is compact
    out[f"mixed_{name}_chunk"] = [lanes if rows is None else len(rows), chunk]
    eng.shutdown()      # (one engine's weights a process: a second `mixed=`
    #                     of a cell this size needs a process of its own)


KDA_CALLS = 24


def _kda_cell():
    """(the config's fields, the engine's, the state buffer's shape) of
    `serve_kimilinear_reasoning_decode`."""
    m = manifest.load()
    cell = m.cells["serve_kimilinear_reasoning_decode"]
    f = manifest.fields(m.load_config(cell["config"]))
    eng = m.load_traffic(cell["traffic"])["engine"]
    layers = len(f["kda_layers"])
    return f, eng, (layers, eng["max_lanes"] + 1, f["kda_heads"],
                    f["kda_head_dim"], f["kda_head_dim"])


def _chained(call, state, calls=KDA_CALLS):
    """ms a call of `call(state, layer) -> (y, state)`, `calls` of them
    chained in one program over the donated state, the layer going round;
    best of three sets."""
    layers = state.shape[0]

    @functools.partial(jax.jit, donate_argnums=0)
    def run(state):
        def body(i, carry):
            state, acc = carry
            y, state = call(state, i % layers)
            return state, acc + jnp.sum(y[..., :1])
        return jax.lax.fori_loop(0, calls, body, (state, jnp.float32(0)))

    state, _ = jax.block_until_ready(run(state))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        state, _ = jax.block_until_ready(run(state))
        best = min(best, (time.perf_counter() - t0) / calls * 1e3)
    return best, state


def _kda_rows(rng, shape, n, p):
    """q, k (unit a head), v, the decays' logs and beta of rows `shape` +
    [H], as the mixer hands them over."""
    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    keys = jax.random.split(rng, 5)
    q = unit(jax.random.normal(keys[0], shape + (n,))) * n ** -0.5
    k = unit(jax.random.normal(keys[1], shape + (n,)))
    v = jax.random.normal(keys[2], shape + (p,))
    g = -0.05 * jnp.exp(jax.random.normal(keys[3], shape + (n,)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape))
    return q, k, v, g, beta


def time_kda_update(name, out):
    """`kda_update` at the cell's lanes beside `ssm_update` on a buffer of
    the same shape, into `out`."""
    from benchmark import kda_flops, ssm_flops
    from ray_tpu.ops import ssm
    f, eng, shape = _kda_cell()
    lanes, (_, _, h, n, p) = eng["max_lanes"], shape
    peaks = manifest.peaks(jax.devices()[0].device_kind)
    slots = jnp.arange(lanes, dtype=jnp.int32)
    q, k, v, g, beta = _kda_rows(jax.random.key(0), (lanes, h), n, p)
    ms, _ = _chained(lambda s, ly: ssm.kda_update(s, q, k, v, g, beta,
                                                  slots, ly),
                     jnp.zeros(shape, jnp.float32))
    key = f"update_{name}_"
    least = flops.roofline_s(*kda_flops.update(lanes, f), peaks)[0]
    out[key + "kda_update_ms"] = ms
    out[key + "kda_update_least_pct"] = 100.0 * least * 1e3 / ms
    # Mamba-2's update as nemotron-3-nano-30b-a3b runs it, the same buffer
    mamba = {"ssm_heads": 64, "ssm_head_dim": 64, "ssm_state": 128,
             "ssm_groups": 8}
    assert ssm.state_shape(64, 128, 64, 8) == shape[2:]
    x = jax.random.normal(jax.random.key(1), (lanes, 64, 64))
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(2), (lanes, 64)))
    a = -jnp.exp(jax.random.normal(jax.random.key(3), (64,)))
    bm, cm = jax.random.normal(jax.random.key(4), (2, lanes, 8, 128))
    ms, _ = _chained(lambda s, ly: ssm.ssm_update(s, x, dt, a, bm, cm,
                                                  slots, ly),
                     jnp.zeros(shape, jnp.float32))
    least = flops.roofline_s(*ssm_flops.update(lanes, mamba), peaks)[0]
    out[f"update_{name}_ssm_update_same_buffer_ms"] = ms
    out[f"update_{name}_ssm_update_same_buffer_least_pct"] = \
        100.0 * least * 1e3 / ms


def time_kda_scan(name, out):
    """`kda_scan` over an admission's chunks, the kernel and the chunked
    form in plain XLA, into `out`."""
    from benchmark import kda_flops
    from ray_tpu.ops import ssm
    f, eng, shape = _kda_cell()
    _, _, h, n, p = shape
    peaks = manifest.peaks(jax.devices()[0].device_kind)
    t = eng["prefill_chunk"]
    CHUNKS = (f["kda_chunk"], 16, 64)
    for rows in (eng["prefill_lanes"], 1):
        q, k, v, g, beta = _kda_rows(jax.random.key(rows), (rows, t, h), n, p)
        slots = jnp.arange(rows, dtype=jnp.int32)
        fresh = jnp.zeros((rows,), bool)
        left = {}
        for form, kw in (("kda_scan", dict(use_kernel=True)),
                         ("chunked_xla", dict(use_kernel=False))):
            for chunk in CHUNKS if form != "chunked_xla" else CHUNKS[:1]:
                ms, state = _chained(
                    lambda s, ly, kw=kw, chunk=chunk: ssm.kda_scan(
                        s, q, k, v, g, beta, slots, fresh, ly, chunk=chunk,
                        **kw),
                    jax.random.normal(jax.random.key(9), shape), calls=6)
                left[form, chunk] = np.asarray(state[:, :rows])
                out[f"scan_{name}_rows{rows}x{t}_{form}_chunk{chunk}_ms"] = ms
        for got in left.values():
            np.testing.assert_allclose(got, left["chunked_xla", CHUNKS[0]],
                                       atol=2e-4)
        least = flops.roofline_s(*kda_flops.scan(rows * t, rows, f),
                                 peaks)[0]
        out[f"scan_{name}_rows{rows}x{t}_kda_scan_least_pct"] = (
            100.0 * least * 1e3
            / out[f"scan_{name}_rows{rows}x{t}_kda_scan_chunk{CHUNKS[0]}_ms"])


def main(argv):
    baseline_root = None
    if "--baseline-root" in argv:
        at = argv.index("--baseline-root")
        baseline_root = argv[at + 1]
        argv = argv[:at] + argv[at + 2:]
    named = {kind: [a.split("=", 1)[1] for a in argv
                    if a.startswith(kind + "=")]
             for kind in ("write", "select", "attend", "runs", "mixed",
                          "update", "scan")}
    runs = [int(kb) for r in named.pop("runs") for kb in r.split(",")]
    unrolls = [int(a) for a in argv if "=" not in a]
    dev = jax.devices()[0]
    out = {"device": [dev.platform, dev.device_kind], "tree": os.getcwd()}
    if unrolls or not any(named.values()):
        time_steps(unrolls, out)
    for name in named["write"] or ["gpt2xl"] * (
            not named["select"] and not named["attend"]
            and not named["mixed"] and not named["update"]
            and not named["scan"]):
        time_rows_write(name, out)
    for name in named["select"]:
        time_select(name, out)
    for name in named["attend"]:
        time_attend(name, out, runs, baseline_root)
    for name in named["mixed"]:
        time_mixed(name, out)
    for name in named["update"]:
        time_kda_update(name, out)
    for name in named["scan"]:
        time_kda_scan(name, out)
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
