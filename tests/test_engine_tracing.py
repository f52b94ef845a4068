"""What the engine says about its own step: one `engine/step` ring record
with the five flat phases' durations and the `stats()` counters that sum
them, the same phases as annotations in a jax profiler trace (flat: no two
overlap, which is what lets an idle gap of the device be named after one of
them), compiles counted where jax reports them, and the spans of one traced
request."""

import glob
import os

import pytest

import jax

from ray_tpu._private import compile_cache
from ray_tpu.inference import InferenceEngine
from ray_tpu.util import events, tracing

PHASE_FIELDS = ("admit_ms", "build_ms", "dispatch_ms", "fetch_ms",
                "commit_ms")
PHASES = ("admit", "build_batch", "dispatch", "fetch", "commit")


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
        assert set(e["payload"]) == {"decode", "prefill", "waiting",
                                     "wall_ms", *PHASE_FIELDS}
    moe0, moe1 = s0["moe"], s1["moe"]
    assert moe1["assignments"] - moe0["assignments"] == (
        (19 + len(out) - 1) * cfg.n_experts_per_tok * cfg.n_layers)
    assert len(moe1["expert_load"]) == cfg.n_experts
    assert moe1["layer_steps"] - moe0["layer_steps"] >= len(steps) * \
        cfg.n_layers
    assert 0 < moe1["experts_hit"] - moe0["experts_hit"] <= (
        moe1["layer_steps"] - moe0["layer_steps"]) * cfg.n_experts


def test_phases_are_flat_siblings_in_the_profilers_trace(tmp_path):
    from jax.profiler import ProfileData
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
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
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
    assert {name for _, _, name in spans} == {f"engine/{p}" for p in PHASES}
    # flat: each phase ends before the next begins, so none encloses another
    for (_, end, name), (start, _, after) in zip(spans, spans[1:]):
        assert end <= start, (name, after)
    # and in the order of a step
    assert [n for _, _, n in spans[:5]] == [f"engine/{p}" for p in PHASES]


def test_compiles_are_counted_where_jax_reports_them():
    engine = _engine()
    engine.generate(list(range(1, 6)), 3)          # greedy T=8 and T=1
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
