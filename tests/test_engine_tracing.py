"""What the engine says about its own step: one `engine/step` ring record
with the five flat phases' durations and the `stats()` counters that sum
them, the same phases as annotations in a jax profiler trace (flat: no two
overlap, which is what lets an idle gap of the device be named after one of
them), compiles counted where jax reports them, and the spans of one traced
request."""

import glob
import os
import time

import pytest

import jax

from ray_tpu._private import compile_cache
from ray_tpu.inference import InferenceEngine
from ray_tpu.inference.speculative import DraftProposer
from ray_tpu.util import events, tracing

PHASE_FIELDS = ("admit_ms", "build_ms", "dispatch_ms", "fetch_ms",
                "commit_ms")
PHASES = ("admit", "build_batch", "dispatch", "fetch", "commit")
# what `build_batch` and `commit` are made of, and the two clocks beside them
BUILD_PARTS = ("windows", "assemble", "upload")
COMMIT_PARTS = ("release", "lock", "deliver")
PARTS = BUILD_PARTS + COMMIT_PARTS
PART_FIELDS = tuple(f"{p}_ms" for p in PARTS)
STEP_FIELDS = {"decode", "prefill", "decode_ctx", "waiting", "wall_ms",
               *PHASE_FIELDS, "ahead", *PART_FIELDS, "gc_ms"}
# one iteration in `engine._CPU_EVERY`, drawn, reads the thread's CPU clock too
CLOCK_FIELDS = {"cpu_ms", "cpu_wall_ms"}


def _engine(**kw):
    kw.setdefault("max_lanes", 2)
    kw.setdefault("prefill_chunk", 8)
    return InferenceEngine("gpt", "nano", auto_start=False, **kw)


def _drain(engine):
    steps = 0
    while engine.step():
        steps += 1
    return steps


def _steps_since(seq):
    return [e for e in events.snapshot(plane="engine", kind="step")
            if e["seq"] > seq]


def _last_seq():
    tail = events.tail(1)
    return tail[-1]["seq"] if tail else -1


def test_step_records_hold_the_phases_and_stats_sum_them():
    engine = _engine()
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    s0, seq = engine.stats(), _last_seq()
    handles = [engine.submit(list(range(1, n)), 6) for n in (4, 12, 20)]
    ran = _drain(engine)
    assert all(len(h.tokens()) == 6 for h in handles)
    s1 = engine.stats()
    records = [e["payload"] for e in _steps_since(seq)]
    assert len(records) == ran == s1["steps"] - s0["steps"]
    # three requests on two lanes: some step ran both populations, the
    # third request waited for a lane
    assert any(r["decode"] and r["prefill"] for r in records)
    assert any(r["waiting"] for r in records)
    for r in records:
        assert all(r[f] >= 0.0 for f in PHASE_FIELDS)
        assert 0.0 < sum(r[f] for f in PHASE_FIELDS) <= r["wall_ms"]
    assert s1["step_wall_s"] - s0["step_wall_s"] == pytest.approx(
        sum(r["wall_ms"] for r in records) / 1e3)
    for phase, field in zip(PHASES, PHASE_FIELDS):
        assert s1["phase_s"][phase] - s0["phase_s"][phase] == pytest.approx(
            sum(r[field] for r in records) / 1e3)
    assert s1["admitted"] - s0["admitted"] == 3
    assert s1["queue_wait_s"] > s0["queue_wait_s"]
    # an idle step is no step: no record, no count
    assert engine.step() is False
    assert engine.stats()["steps"] == s1["steps"] and not _steps_since(
        _last_seq())


def test_an_expert_engine_adds_counters_to_stats_and_nothing_to_a_step():
    """OLMoE's block at nano size: the same one `engine/step` record a
    step with the same fields, no other ring event per step or token, and
    `stats()["moe"]` growing by tokens x top-k x layers."""
    from ray_tpu.models import llama
    cfg = llama.CONFIGS["olmoe-nano"]
    engine = InferenceEngine("llama", cfg, auto_start=False, max_lanes=2,
                             prefill_chunk=8)
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    s0, seq = engine.stats(), _last_seq()
    out = engine.generate(list(range(1, 20)), 6)
    s1 = engine.stats()
    since = [e for e in events.snapshot(plane="engine") if e["seq"] > seq]
    steps = [e for e in since if e["kind"] == "step"]
    assert len(steps) == s1["steps"] - s0["steps"] > 0
    assert {e["kind"] for e in since} <= {"step", "submit", "admit",
                                          "finish", "prefix_miss"}
    for e in steps:
        assert set(e["payload"]) - CLOCK_FIELDS == STEP_FIELDS
    moe0, moe1 = s0["moe"], s1["moe"]
    assert moe1["assignments"] - moe0["assignments"] == (
        (19 + len(out) - 1) * cfg.n_experts_per_tok * cfg.n_layers)
    assert len(moe1["expert_load"]) == cfg.n_experts
    # (the last iteration dispatches nothing: it fetches the last step)
    dispatched = [e for e in steps
                  if e["payload"]["decode"] or e["payload"]["prefill"]]
    assert len(dispatched) == len(steps) - 1
    assert moe1["layer_steps"] - moe0["layer_steps"] >= len(dispatched) * \
        cfg.n_layers
    assert 0 < moe1["experts_hit"] - moe0["experts_hit"] <= (
        moe1["layer_steps"] - moe0["layer_steps"]) * cfg.n_experts


def _phases_in_trace(trace_dir):
    """(start, end, name) of the `engine/` annotations in a profiler
    trace, in time order, from the one thread that has any."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    host, = [p for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU"]
    lines = {}
    for line in host.lines:
        mine = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in line.events if e.name.startswith("engine/")]
        if mine:
            lines[line.name] = sorted(mine)
    assert len(lines) == 1, list(lines)        # the engine thread's line
    spans, = lines.values()
    return spans


def test_phases_are_flat_siblings_in_the_profilers_trace(tmp_path):
    engine = InferenceEngine("gpt", "nano", max_lanes=2, prefill_chunk=8)
    try:
        engine.generate(list(range(1, 6)), 2)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            handles = [engine.submit(list(range(1, n)), 5) for n in (4, 14)]
            assert all(len(h.tokens()) == 5 for h in handles)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.shutdown()
    spans = _phases_in_trace(tmp_path)
    assert {name for _, _, name in spans} == {f"engine/{p}" for p in PHASES}
    # flat: each phase ends before the next begins, so none encloses another
    for (_, end, name), (start, _, after) in zip(spans, spans[1:]):
        assert end <= start, (name, after)
    # and in the order of a step
    assert [n for _, _, n in spans[:5]] == [f"engine/{p}" for p in PHASES]


class _NeverDrafts(DraftProposer):
    def propose(self, context, k):
        return []


@pytest.mark.parametrize("proposer", [False, True],
                         ids=["a_step_ahead", "a_proposer_fetches_first"])
def test_the_next_step_is_dispatched_before_the_last_one_is_fetched(
        tmp_path, proposer):
    """N steps of one request, by the phases' own clocks and the tokens
    committed after each iteration: without a proposer the `dispatch` of
    step i+1 has begun before the `fetch` that returns step i's tokens
    ends, `stats()["ahead"]` counts those iterations, and every
    `engine/step` record says which kind it was.  A proposer needs the
    token on the host: each iteration fetches what it dispatched.  Either
    way five flat phases and one ring record an iteration, none a token."""
    n = 8
    engine = _engine(**(dict(spec_k=1, draft_proposer=_NeverDrafts())
                        if proposer else {}))
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    s0, seq = engine.stats(), _last_seq()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        # a prompt of one chunk: step 1 prefills, steps 2..n decode
        handle = engine.submit(list(range(1, 6)), n)
        committed = []
        while engine.step():
            committed.append(len(handle._req.emitted))
    finally:
        jax.profiler.stop_trace()
    assert len(handle.tokens()) == n
    s1 = engine.stats()
    since = [e for e in events.snapshot(plane="engine") if e["seq"] > seq]
    assert {e["kind"] for e in since} - {"spec_draft"} <= {
        "step", "submit", "finish", "prefix_miss", "prefix_hit"}
    records = [e["payload"] for e in since if e["kind"] == "step"]
    assert len(records) == len(committed) == s1["steps"] - s0["steps"]
    assert s1["step_wall_s"] - s0["step_wall_s"] == pytest.approx(
        sum(r["wall_ms"] for r in records) / 1e3)
    ahead = {k: s1["ahead"][k] - s0["ahead"][k] for k in s1["ahead"]}

    spans = _phases_in_trace(tmp_path)
    # (the iteration that only fetches the last step dispatches nothing;
    # the idle step() that ends the loop looks for admissions and returns)
    assert [name for _, _, name in spans] == [
        f"engine/{p}" for p in PHASES] * n + [
        f"engine/{p}" for p in PHASES if p != "dispatch"] * (not proposer) + [
        "engine/admit"]
    for (_, end, name), (start, _, after) in zip(spans, spans[1:]):
        assert end <= start, (name, after)
    dispatches = [s for s in spans if s[2] == "engine/dispatch"]
    fetches = [s for s in spans if s[2] == "engine/fetch"]
    if proposer:
        # iteration i dispatches step i and commits its token
        assert committed == list(range(1, n + 1))
        assert ahead == {"steps": 0, "sync_steps": n, "overrun_tokens": 0}
        assert [r["ahead"] for r in records] == [0] * n
    else:
        # iteration i dispatches step i, then commits step i-1's token;
        # one more iteration fetches step n's
        assert committed == list(range(0, n + 1))
        for i in range(1, n):           # step i's tokens come back in the
            fetch_i = fetches[i]        # fetch of iteration i+1, after the
            assert dispatches[i][0] < fetch_i[1]    # dispatch of step i+1
            assert dispatches[i][1] <= fetch_i[0]
        assert ahead == {"steps": n - 1, "sync_steps": 2,
                         "overrun_tokens": 0}
        assert [r["ahead"] for r in records] == [0] + [1] * (n - 1) + [0]
        assert [r["decode"] + r["prefill"] for r in records] == [1] * n + [0]


def test_compiles_are_counted_where_jax_reports_them():
    engine = _engine()
    engine.generate(list(range(1, 6)), 3)          # greedy pair and T=1
    assert len(engine._step_fns) == 2

    def built(c):       # a program is new whether XLA built or the cache had it
        return c["compiles"] + c["cache_hits"]

    c0, seq = compile_cache.counters(), _last_seq()
    engine.generate(list(range(2, 9)), 4)          # the same two shapes
    assert compile_cache.counters() == c0 == engine.stats()["compile"]
    assert not [e for e in events.snapshot(plane="proc", kind="compile")
                if e["seq"] > seq]
    # a sampled request is a new (t, sample, spec) shape at T=8 and at T=1
    engine.generate(list(range(1, 6)), 3, temperature=0.8)
    c1 = compile_cache.counters()
    assert len(engine._step_fns) == 4 and built(c1) >= built(c0) + 2
    assert c1["compile_s"] + c1["cache_load_s"] > \
        c0["compile_s"] + c0["cache_load_s"]
    new = [e["payload"] for e in events.snapshot(plane="proc", kind="compile")
           if e["seq"] > seq]
    assert len(new) == built(c1) - built(c0)
    assert all(p["seconds"] > 0 for p in new)
    assert any("step" in p["fun"] for p in new)


def test_a_traced_request_leaves_one_queue_prefill_and_decode_span():
    engine = _engine()
    with tracing.trace("one request") as trace_id:
        handle = engine.submit(list(range(1, 12)), 5)
    _drain(engine)
    assert len(handle.tokens()) == 5
    mine = [e for e in events.snapshot(plane="engine")
            if e["trace_id"] == trace_id]
    edges = {}
    for e in mine:
        ph = (e["payload"] or {}).get("ph")
        if ph:
            edges.setdefault(e["kind"], []).append((ph, e))
    assert {k: [ph for ph, _ in v] for k, v in edges.items()} == {
        "queue": ["B", "E"], "prefill": ["B", "E"], "decode": ["B", "E"]}
    prefill_b, queue_b = edges["prefill"][0][1], edges["queue"][0][1]
    assert queue_b["payload"]["parent"] == prefill_b["span_id"]
    durs = {k: v[1][1]["payload"]["dur"] for k, v in edges.items()}
    assert 0.0 <= durs["queue"] <= durs["prefill"]
    assert edges["decode"][1][1]["payload"]["tokens"] == 5
    admitted, = [e for e in mine if e["kind"] in ("prefix_hit",
                                                  "prefix_miss")]
    assert admitted["payload"]["wait_ms"] == pytest.approx(
        durs["queue"] * 1e3, abs=5.0)


def test_a_latent_share_engine_counts_on_the_host_and_adds_nothing_to_a_step():
    """A.X-K1's block at nano size, one share of four, with a compact
    prefill program: the same one `engine/step` record a step, no other
    ring event per step or token, and `stats()` growing `latent` (the T=1
    steps and the context they attended over), `prefill` (programs, lanes,
    rows, rows that held a prompt token) and a share's `moe` (the router's
    assignments in all, counted on the host, beside those that fell on
    held experts, counted on the device)."""
    from ray_tpu.models import axk1
    cfg = axk1.CONFIGS["axk1-nano-share"]
    engine = InferenceEngine("axk1", cfg, auto_start=False, max_lanes=2,
                             prefill_chunk=8, prefill_lanes=1, block_size=8)
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    s0, seq = engine.stats(), _last_seq()
    prompt = list(range(1, 20))
    out = engine.generate(prompt, 6)
    s1 = engine.stats()
    since = [e for e in events.snapshot(plane="engine") if e["seq"] > seq]
    steps = [e for e in since if e["kind"] == "step"]
    assert len(steps) == s1["steps"] - s0["steps"] > 0
    assert {e["kind"] for e in since} <= {"step", "submit", "admit",
                                          "finish", "prefix_miss"}
    for e in steps:
        assert set(e["payload"]) - CLOCK_FIELDS == STEP_FIELDS

    def grew(key):
        return {k: s1[key][k] - s0[key][k] for k in s1[key]
                if not isinstance(s1[key][k], list)}

    # 19 prompt tokens in three chunks of a [1, 8] program, whose last
    # rows attend over 8, 16 and 19 rows of the lane's table
    assert grew("prefill") == {"steps": 3, "lanes": 3, "rows": 24,
                               "rows_valid": 19, "ctx_rows": 8 + 16 + 19}
    # five T=1 steps, over contexts of 20, 21, ... tokens (the first
    # output token comes from the prefill)
    assert grew("latent") == {"decode_steps": len(out) - 1,
                              "ctx_tokens": sum(range(20, 19 + len(out)))}
    # and each iteration's record says what its own T=1 step attended over
    assert [e["payload"]["decode_ctx"] for e in steps
            if e["payload"]["decode"]] == list(range(20, 19 + len(out)))
    moe = grew("moe")
    expert_layers = cfg.n_layers - cfg.first_dense_layers
    tokens = 19 + len(out) - 1
    assert moe["assignments"] == tokens * cfg.n_experts_per_tok * expert_layers
    assert 0 < moe["assignments_held"] < moe["assignments"]
    assert len(s1["moe"]["expert_load"]) == cfg.n_experts_held
    assert sum(s1["moe"]["expert_load"]) == s1["moe"]["assignments_held"]
    assert moe["layer_steps"] >= (3 + len(out) - 1) * expert_layers
    assert 0 < moe["experts_hit"] <= moe["layer_steps"] * cfg.n_experts_held


def test_engines_of_k_and_v_rows_have_no_latent_counters():
    engine = _engine()
    engine.generate(list(range(1, 12)), 3)
    stats = engine.stats()
    assert "latent" not in stats and "moe" not in stats
    assert stats["prefill"] == {"steps": 2, "lanes": 2, "rows": 32,
                                "rows_valid": 11, "ctx_rows": 8 + 11}


def test_an_engine_of_k_and_v_rows_counts_the_decode_kernels_live_runs():
    """`stats()["paged"]` (PR 32): the T=1 steps, the context tokens their
    lanes attended over and the runs of the decode kernel that held
    context, of `decode_steps` x lanes x runs a lane: host integers summed
    while a batch is built, in a step's one `engine/step` record's shadow
    (no ring event of their own)."""
    engine = _engine()
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    s0, seq = engine.stats(), _last_seq()
    out = engine.generate(list(range(1, 12)), 8)
    s1 = engine.stats()
    grew = {k: s1["paged"][k] - s0["paged"][k] for k in s1["paged"]}
    assert all(type(v) is int for v in s1["paged"].values())
    # seven T=1 steps, over contexts of 12, 13, ... tokens (the first
    # output token comes from the prefill)
    run = engine._paged_run
    assert run % engine.cache.block_size == 0 and run > 0
    ctx = range(12, 11 + len(out))
    assert grew == {"decode_steps": len(out) - 1, "ctx_tokens": sum(ctx),
                    "runs_live": sum(-(-c // run) for c in ctx)}
    since = [e for e in events.snapshot(plane="engine") if e["seq"] > seq]
    assert {e["kind"] for e in since} <= {"step", "submit", "admit",
                                          "finish", "prefix_miss",
                                          "prefix_hit"}


def test_a_programs_first_call_freezes_what_it_left_on_the_heap():
    """The engine collects and freezes the heap behind the first call of
    each step program (PR 32): what tracing and compiling leave there is
    out of the collector's way before the engine serves, and a later full
    collection walks the young objects alone.  A shape that has run does
    not freeze again: nothing a request allocates is frozen with it."""
    import gc

    gc.unfreeze()
    engine = _engine()
    assert gc.get_freeze_count() == 0
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    frozen = gc.get_freeze_count()
    assert frozen > 1000
    engine.generate(list(range(1, 12)), 8)         # both shapes have run
    assert gc.get_freeze_count() <= frozen         # some have died since
    gc.unfreeze()


def test_a_windowed_engine_counts_rows_and_compactions_and_adds_a_span():
    """EvaByte's block at nano size (windows of 32, chunks of 4): `stats()`
    grows `eva` (the T=1 steps, the context tokens of their lanes and the
    rows they attended over instead, host sums; the windows closed; the
    pool's blocks by kind), `paged` counts the rows the kernel reads, a
    step still leaves its one `engine/step` record, and a compaction is one
    span of its own name, `eva_compact`, on the ring (spans are recorded
    there) and nothing else."""
    from ray_tpu.models import evabyte
    cfg = evabyte.CONFIGS["evabyte-nano"]
    engine = InferenceEngine("evabyte", cfg, auto_start=False, max_lanes=2,
                             prefill_chunk=16, prefill_lanes=1, block_size=8,
                             num_blocks=32)
    engine.generate(list(range(1, 40)), 2)         # compile all four shapes
    s0, seq = engine.stats(), _last_seq()
    assert s0["eva"]["compactions"] == 1
    out = engine.generate(list(range(2, 30)), 40)  # 28 + 40: edges 32, 64
    s1 = engine.stats()
    since = [e for e in events.snapshot(plane="engine") if e["seq"] > seq]
    steps = [e for e in since if e["kind"] == "step"]
    assert len(steps) == s1["steps"] - s0["steps"] > 0
    for e in steps:
        assert set(e["payload"]) - CLOCK_FIELDS == STEP_FIELDS
    assert {e["kind"] for e in since} <= {"step", "submit", "admit",
                                          "finish", "prefix_miss",
                                          "eva_compact"}
    compacts = [e for e in since if e["kind"] == "eva_compact"
                and e["payload"].get("ph") == "E"]
    assert len(compacts) == 2 and all(
        e["payload"]["lanes"] == 1 for e in compacts)
    grew = {k: s1["eva"][k] - s0["eva"][k] for k in s1["eva"]}
    ctx = list(range(29, 28 + len(out)))
    rows = [engine.cache.rows_held(c) for c in ctx]
    assert rows[3:5] == [32, 8 + 1] and rows[-1] == 16 + 3
    assert {k: grew[k] for k in ("decode_steps", "ctx_tokens",
                                 "rows_attended", "compactions")} == {
        "decode_steps": len(out) - 1, "ctx_tokens": sum(ctx),
        "rows_attended": sum(rows), "compactions": 2}
    assert all(type(v) is int for v in s1["eva"].values())
    # the request ended: what the pool still holds is what the index keeps
    assert s1["eva"]["summary_blocks"] + s1["eva"]["window_blocks"] \
        == s1["cached_blocks"]
    assert s1["paged"]["ctx_tokens"] - s0["paged"]["ctx_tokens"] == sum(rows)
    assert "eva" not in _engine().stats()
    programs = engine.compiled_steps()
    assert {"t1", "t16_pair1", "compact_lanes1"} <= set(programs)
    # (its `pool_copies` are 0 compiled for the chip: tests/test_tpu_aot.py;
    # the CPU backend donates nothing)
    assert programs["compact_lanes1"]["custom_calls"] == 0


def _median(values):
    values = sorted(values)
    return values[len(values) // 2]


def test_step_records_name_the_parts_of_build_batch_and_commit():
    """The six parts and the two clocks in the step's one record: the
    parts of a phase add up to it (what is left over is loop glue, a few
    microseconds); one iteration in `_CPU_EVERY` on average, drawn and not
    counted off, reads the thread's CPU clock around the four host phases
    (`cpu_ms`, no more than their wall, `cpu_wall_ms`); and `stats()` sums
    all of it and counts those iterations (`cpu_steps`)."""
    engine = _engine()
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    s0, seq = engine.stats(), _last_seq()
    handles = [engine.submit(list(range(1, n)), 12) for n in (4, 12, 20)]
    ran = _drain(engine)
    assert all(len(h.tokens()) == 12 for h in handles)
    s1 = engine.stats()
    since = [e for e in events.snapshot(plane="engine") if e["seq"] > seq]
    records = [e["payload"] for e in since if e["kind"] == "step"]
    # one ring event an iteration, none a token (36 were delivered)
    assert len(records) == ran == s1["steps"] - s0["steps"]
    assert {e["kind"] for e in since} <= {"step", "submit", "admit", "finish",
                                          "prefix_miss", "prefix_hit"}
    clocked = [r for r in records if "cpu_ms" in r]
    assert 0 < len(clocked) < ran
    assert len(clocked) == s1["cpu_steps"] - s0["cpu_steps"]
    for r in records:
        assert set(r) == STEP_FIELDS | (CLOCK_FIELDS if r in clocked
                                        else set())
        assert all(r[f] >= 0.0 for f in PART_FIELDS + ("gc_ms",))
        assert r["windows_ms"] == 0.0              # no windowed cache here
        assert sum(r[f"{p}_ms"] for p in BUILD_PARTS) <= r["build_ms"]
        assert sum(r[f"{p}_ms"] for p in COMMIT_PARTS) <= r["commit_ms"]
    for r in clocked:
        # from admit's start to dispatch's end and over commit: the four
        # host phases and the glue between them, fetch left out
        host = r["wall_ms"] - r["fetch_ms"]
        assert host * 0.9 - 0.05 <= r["cpu_wall_ms"] <= host + 0.1
        assert 0.0 <= r["cpu_ms"] <= r["cpu_wall_ms"] * 1.05 + 0.05
    # an iteration that built something: its parts are nearly all of it
    # (the median: one preempted iteration of a loaded machine is allowed)
    built = [r for r in records if r["decode"] or r["prefill"]]
    assert _median([sum(r[f"{p}_ms"] for p in BUILD_PARTS) / r["build_ms"]
                    for r in built]) > 0.9
    # (`commit` is tens of microseconds here, of which the glue around its
    # three parts is a few: judged with an absolute slack of 50 us, as the
    # `cpu_wall_ms` lines above are, not by a bare ratio, which read 0.8992
    # against 0.9 on a loaded machine)
    assert _median([sum(r[f"{p}_ms"] for p in COMMIT_PARTS)
                    - (r["commit_ms"] * 0.9 - 0.05) for r in records]) >= 0.0
    assert all(r["assemble_ms"] > 0.0 and r["upload_ms"] > 0.0 for r in built)
    assert set(s1["part_s"]) == set(PARTS)
    for part in PARTS:
        assert s1["part_s"][part] - s0["part_s"][part] == pytest.approx(
            sum(r[f"{part}_ms"] for r in records) / 1e3)
    for key in ("cpu", "cpu_wall"):
        assert s1[f"{key}_s"] - s0[f"{key}_s"] == pytest.approx(
            sum(r[f"{key}_ms"] for r in clocked) / 1e3)
    assert 0.0 < s1["cpu_s"] - s0["cpu_s"] <= (
        s1["cpu_wall_s"] - s0["cpu_wall_s"]) * 1.05
    assert set(s1["phase_s"]) == set(PHASES)       # five keys, as before


def test_upload_sums_grow_by_population():
    """`stats()["upload"]` (PR 42): host sums `_upload` makes, a program
    at a time: the populations handed to the device (a decode and a prefill
    population of one iteration are two, in the pair's ONE buffer since
    PR 53), the transfers that took (a program's one buffer, and the block
    tables' copy where a table changed) and their bytes.  No ring event and
    no transfer of its own."""
    engine = _engine()
    assert engine.stats()["upload"] == {"populations": 0, "transfers": 0,
                                        "bytes": 0}
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    s0, seq = engine.stats(), _last_seq()
    handles = [engine.submit(list(range(1, n)), 6) for n in (4, 12, 20)]
    _drain(engine)
    assert all(len(h.tokens()) == 6 for h in handles)
    up0, up1 = s0["upload"], engine.stats()["upload"]
    records = [e["payload"] for e in _steps_since(seq)]
    pops = sum(bool(r["decode"]) + bool(r["prefill"]) for r in records)
    assert up1["populations"] - up0["populations"] == pops > 0
    assert any(r["decode"] and r["prefill"] for r in records)
    # an iteration's populations go in one transfer
    programs = sum(bool(r["decode"] or r["prefill"]) for r in records)
    extra = up1["transfers"] - up0["transfers"] - programs
    assert 0 < extra <= programs < pops            # tables changed, not always
    # two lanes: a T=1 buffer is [2, 8] int32, a pair's that and the chunk's
    # compact [2, 30] behind it; a table copy the cache's whole table
    t8 = sum(bool(r["prefill"]) for r in records)
    assert up1["bytes"] - up0["bytes"] == (
        programs * 2 * 8 * 4 + t8 * 2 * 30 * 4
        + extra * engine.cache.block_tables.nbytes)
    ran0, ran1 = s0["programs"], engine.stats()["programs"]
    assert ran1["programs"] - ran0["programs"] == programs \
        == ran1["iterations"] - ran0["iterations"]
    assert ran1["mixed"] - ran0["mixed"] == t8
    assert all(type(v) is int for v in up1.values())
    assert {e["kind"] for e in events.snapshot(plane="engine")
            if e["seq"] > seq} <= {"step", "submit", "admit", "finish",
                                   "prefix_miss", "prefix_hit"}


def test_bench_rl_warms_every_verify_width_through_build_upload_run():
    """`bench_rl.py` compiles its engine's programs outside the timed
    rollout by the chain the loop itself runs, `_build_batch` -> `_upload`
    -> `_run_step`, over no lane at all: the script's own `_warm` on a
    nano actor leaves a program for T=1 and every verify width, and a
    rollout behind it compiles nothing."""
    import argparse
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        import bench_rl
    finally:
        sys.path.remove(root)
    args = argparse.Namespace(config="nano", prompt_len=8, new_tokens=6,
                              spec_k=2)
    actor = bench_rl._make_actor(args.spec_k, 2, args, None)
    prompts = bench_rl._prompts(2, args.prompt_len, 4, 64)
    bench_rl._warm(actor, prompts, args.spec_k)
    engine = actor.engine
    made = set(engine._step_fns)
    assert {(1, False, False, 0), (2, False, True, 0), (3, False, True, 0)} \
        <= made
    batch, _, metrics = actor.rollout(prompts, args.new_tokens)
    assert metrics["tokens"] == 2 * args.new_tokens
    assert set(engine._step_fns) == made


def test_collector_pauses_are_counted_where_they_happen():
    """One `gc.callbacks` hook a process, whatever the number of engines:
    `stats()["gc"]` counts collections by generation with their seconds,
    and a collection that falls inside an iteration is in that iteration's
    `gc_ms`."""
    import gc

    from ray_tpu.inference import engine as engine_mod

    engine, other = _engine(), _engine()
    assert gc.callbacks.count(engine_mod._gc_hook) == 1
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    g0 = engine.stats()["gc"]
    gc.collect()
    gc.collect(0)
    g1 = engine.stats()["gc"]
    assert g1 == other.stats()["gc"]               # the process's, not an engine's
    assert g1["collections"][2] == g0["collections"][2] + 1
    assert g1["collections"][0] == g0["collections"][0] + 1
    assert g1["seconds"] > g0["seconds"]
    assert g0["full_seconds"] < g1["full_seconds"] <= g1["seconds"]
    # a full collection inside every iteration's `admit`
    admit = engine._admit
    engine._admit = lambda: (gc.collect(), admit())[1]
    seq = _last_seq()
    engine.submit(list(range(1, 6)), 4)
    ran = _drain(engine)
    records = [e["payload"] for e in _steps_since(seq)]
    g2 = engine.stats()["gc"]
    assert len(records) == ran and all(r["gc_ms"] > 0.0 for r in records)
    assert g2["collections"][2] >= g1["collections"][2] + ran
    assert sum(r["gc_ms"] for r in records) / 1e3 <= (
        g2["seconds"] - g1["seconds"]) * (1 + 1e-9)
    assert all(r["gc_ms"] <= r["admit_ms"] for r in records)


def test_the_timeline_keeps_the_loops_sums_by_the_second(monkeypatch):
    """`stats()["timeline"]`: rows of the wall-clock seconds in which the
    loop ran, which add up to `steps`, `step_wall_s`, `phase_s`, `part_s`
    and `cpu_s` over the same span; plain lists of host numbers under one
    header (JSON as it stands); never more than 128 rows."""
    import json
    import time

    engine = _engine()
    engine.generate(list(range(1, 6)), 2)          # compile both shapes
    first = engine.stats()
    assert first["timeline"]["columns"] == [
        "t", "steps", "prefill_steps", "wall_s", "phase_s", "part_s",
        "cpu_s", "cpu_wall_s", "cpu_steps", "gc_s", "longest_ms",
        "longest_phase"]
    assert first["timeline"]["phases"] == list(PHASES)
    assert first["timeline"]["parts"] == list(PARTS)

    def totals(stats):
        rows = stats["timeline"]["rows"]
        return {"steps": sum(r[1] for r in rows),
                "prefill_steps": sum(r[2] for r in rows),
                "wall_s": sum(r[3] for r in rows),
                "phase_s": [sum(r[4][i] for r in rows) for i in range(5)],
                "part_s": [sum(r[5][i] for r in rows) for i in range(6)],
                "cpu_s": sum(r[6] for r in rows),
                "cpu_wall_s": sum(r[7] for r in rows),
                "cpu_steps": sum(r[8] for r in rows)}

    # everything this engine ever ran is still in the timeline
    t = totals(first)
    assert t["steps"] == first["steps"]
    assert t["wall_s"] == pytest.approx(first["step_wall_s"])
    assert t["phase_s"] == pytest.approx(
        [first["phase_s"][p] for p in PHASES])
    assert t["part_s"] == pytest.approx([first["part_s"][p] for p in PARTS])
    assert t["cpu_s"] == pytest.approx(first["cpu_s"])
    assert t["cpu_wall_s"] == pytest.approx(first["cpu_wall_s"]) and \
        t["cpu_wall_s"] > 0.0
    assert t["cpu_steps"] == first["cpu_steps"] > 0
    assert t["prefill_steps"] == first["prefill"]["steps"] == 1
    handles = [engine.submit(list(range(1, n)), 6) for n in (4, 20)]
    _drain(engine)
    assert all(len(h.tokens()) == 6 for h in handles)
    later = engine.stats()
    t = totals(later)
    assert t["steps"] == later["steps"] > first["steps"]
    assert t["wall_s"] == pytest.approx(later["step_wall_s"])
    assert t["phase_s"] == pytest.approx(
        [later["phase_s"][p] for p in PHASES])
    rows = later["timeline"]["rows"]
    now = int(time.time())
    assert [r[0] for r in rows] == sorted({r[0] for r in rows})
    assert all(now - 120 < r[0] <= now for r in rows)
    for r in rows:
        assert r[1] >= 1 and r[11] in PHASES       # ran, so something was longest
        assert r[10] / 1e3 <= r[3] * (1 + 1e-9)    # the longest is one of them
    # what a copy of stats() holds is the caller's: host numbers, JSON
    text = json.dumps(later["timeline"])
    assert json.loads(text) == later["timeline"]
    rows[0][4][0] += 1.0
    assert engine.stats()["timeline"]["rows"][0][4][0] != rows[0][4][0]

    # 200 seconds of iterations leave the newest 128
    clock = iter(range(10_000, 10_200))
    monkeypatch.setattr(time, "time", lambda: float(next(clock)))
    for _ in range(200):
        engine._close_second(int(time.time()))
        engine._steps += 1
        engine._step_wall_s += 0.005
        engine._second[2:] = 5.0, "fetch"
    monkeypatch.undo()
    rows = engine.stats()["timeline"]["rows"]
    # (the open second is given too: 127 closed rows behind it)
    assert len(rows) == 128 and [r[0] for r in rows] == list(
        range(10_072, 10_200))
    assert rows[-1][1:3] == [1, 0] and rows[-1][3] == pytest.approx(0.005)
    assert rows[-1][10:] == [5.0, "fetch"]
    assert len(engine._timeline) == 128
    assert len(json.dumps(engine.stats()["timeline"])) < 64 * 1024


def _annotations(trace_dir, prefix):
    """(start, end, name) of the annotations whose name starts with
    `prefix`, in time order, over every line of the host plane."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    host, = [p for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU"]
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for line in host.lines for e in line.events
                  if e.name.startswith(prefix))


def test_every_part_lies_inside_its_phase_in_the_profilers_trace(tmp_path):
    """`engine.build_batch/<part>` and `engine.commit/<part>` are nested in
    `engine/build_batch` and `engine/commit`; the five `engine/` phases
    stay flat siblings, and no part's name starts with the `engine/` that
    the idle split and the flatness test read."""
    from ray_tpu.models import evabyte
    engine = InferenceEngine(
        "evabyte", evabyte.CONFIGS["evabyte-nano"], auto_start=False,
        max_lanes=2, prefill_chunk=16, prefill_lanes=1, block_size=8,
        num_blocks=32)
    engine.generate(list(range(1, 40)), 2)         # compile all four shapes
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        seq = _last_seq()
        assert len(engine.generate(list(range(2, 30)), 10)) == 10
    finally:
        jax.profiler.stop_trace()
    records = [e["payload"] for e in _steps_since(seq)]
    assert any(r["windows_ms"] > 0.0 for r in records)      # the edge at 32
    phases = _phases_in_trace(tmp_path)
    for (_, end, name), (start, _, after) in zip(phases, phases[1:]):
        assert end <= start, (name, after)
    parts = _annotations(tmp_path, "engine.")
    assert {name for _, _, name in parts} == {
        *(f"engine.build_batch/{p}" for p in BUILD_PARTS),
        *(f"engine.commit/{p}" for p in COMMIT_PARTS)}
    for start, end, name in parts:
        phase = name.split("/")[0].replace(".", "/")
        assert any(p0 <= start and end <= p1 and pname == phase
                   for p0, p1, pname in phases), name
    # and flat among themselves, in the order of an iteration
    for (_, end, name), (start, _, after) in zip(parts, parts[1:]):
        assert end <= start, (name, after)
    closes = [p for p in parts if p[2] == "engine.build_batch/windows"]
    assert len(closes) == sum(r["decode"] + r["prefill"] > 0 for r in records)


def test_a_replica_traces_itself_and_says_when(tmp_path):
    """`serve.LLMReplica` (the class `serve.LLMDeployment` deploys) opens
    and closes a profiler session on its own chip; a session leaves one
    `engine/profile` span with the seconds its start and stop took, and a
    trace that holds the engine's phases."""
    from ray_tpu import serve
    assert serve.LLMDeployment._cls_or_fn is serve.LLMReplica
    replica = serve.LLMReplica(model="gpt", config="nano", max_lanes=2,
                               prefill_chunk=8)
    try:
        list(replica.generate(list(range(1, 6)), 2))
        seq = _last_seq()
        assert replica.start_trace(str(tmp_path)) is True
        assert len(list(replica.generate(list(range(1, 9)), 4))) == 4
        assert replica.stop_trace() is True
    finally:
        replica._engine.shutdown()
    edges = [e for e in events.snapshot(plane="engine", kind="profile")
             if e["seq"] > seq]
    assert [e["payload"]["ph"] for e in edges] == ["B", "E"]
    assert edges[0]["payload"]["trace_dir"] == str(tmp_path)
    end = edges[1]["payload"]
    assert end["start_s"] > 0.0 and end["stop_s"] > 0.0
    assert end["dur"] >= end["start_s"] + end["stop_s"]
    assert {n for _, _, n in _phases_in_trace(tmp_path)} >= {
        "engine/build_batch", "engine/fetch", "engine/commit"}


def test_stats_hold_what_the_engine_did_once():
    """`stats()["setup"]`: the process's start-up record folded (seconds by
    kind, the process's start), with one `make_program` a step key whose
    parts (jax's own trace, lower, load and compile seconds) sum to no more
    than the span: the rest is the first run."""
    events.reset()                  # a record of this engine alone
    t0 = time.time()
    engine = _engine()
    engine.generate(list(range(1, 6)), 3)          # greedy pair and T=1
    setup = engine.stats()["setup"]
    assert setup["start"] <= t0
    assert {"backend_init", "init_params", "prepare", "pools",
            "make_program"} <= set(setup["seconds"])
    assert all(s >= 0.0 for s in setup["seconds"].values())
    assert setup["seconds"]["init_params"] > 0.0
    programs = setup["programs"]
    assert sorted(tuple(p["key"]) for p in programs) == sorted(
        engine._step_fns) == [(1, False, False, 0), (8, False, False, 2)]
    for p in programs:
        parts = sum(p[k] for k in ("trace_s", "lower_s", "cache_load_s",
                                   "compile_s"))
        assert 0.0 < parts <= p["dur"] and p["start"] >= t0
        assert p["trace_s"] > 0.0 and p["lower_s"] > 0.0
        assert p["wall_s"] == pytest.approx(p["dur"], abs=0.05)
    assert setup["seconds"]["make_program"] == pytest.approx(
        sum(p["dur"] for p in programs))
    # the same split, by the step's name, where a check asks for it
    made = {name: s["made"] for name, s in engine.compiled_steps().items()}
    assert set(made) == {"t1", "t8_pair2"}
    assert all(m["wall_s"] >= m["trace_s"] + m["lower_s"] > 0
               for m in made.values())
    # a step whose program is there adds no row
    engine.generate(list(range(2, 9)), 4)
    assert engine.stats()["setup"]["programs"] == programs


def test_a_program_made_in_a_traced_slice_lies_in_the_trace(tmp_path):
    """`engine.dispatch/make_program` is an annotation too, inside the
    `engine/dispatch` that found the program missing."""
    engine = _engine()
    engine.generate(list(range(1, 6)), 2)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        engine.generate(list(range(1, 6)), 2, temperature=0.7)  # two shapes
    finally:
        jax.profiler.stop_trace()
    made = [a for a in _annotations(tmp_path, "engine.")
            if a[2] == "engine.dispatch/make_program"]
    assert len(made) == 2
    dispatches = [p for p in _phases_in_trace(tmp_path)
                  if p[2] == "engine/dispatch"]
    for start, end, _ in made:
        assert any(d0 <= start and end <= d1 for d0, d1, _ in dispatches)


def test_an_iteration_held_half_a_second_leaves_one_stall(monkeypatch):
    engine = _engine()
    engine.generate(list(range(1, 6)), 2)
    seq, commit = _last_seq(), engine._commit

    def held(*args, **kwargs):
        monkeypatch.setattr(engine, "_commit", commit)      # this once
        time.sleep(0.55)
        return commit(*args, **kwargs)

    monkeypatch.setattr(engine, "_commit", held)
    engine.generate(list(range(2, 9)), 4)
    stalls = [e["payload"] for e in events.snapshot(plane="engine",
                                                    kind="stall")
              if e["seq"] > seq]
    assert len(stalls) == 1
    stall, = stalls
    assert stall["phase"] == "commit" and stall["wall_ms"] > 550.0
    assert stall["gc_ms"] >= 0.0
    # nothing was made meanwhile: the counters' difference says so
    assert stall["compiles"] == stall["cache_hits"] == stall["programs"] == 0
    assert stall["trace_s"] == stall["lower_s"] == 0.0
    steps = [e["payload"] for e in _steps_since(seq)]
    assert sum(s["wall_ms"] > 500.0 for s in steps) == 1
