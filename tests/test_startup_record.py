"""The start-up record: what a process does once, kept beside the ring.

jax's own trace / lower / load / compile seconds by program
(`compile_cache`), spans closed with `pin=True` in a list that nothing
overwrites (`events.pinned`), their way through a dump, and the merged
timeline of a local cluster after `ray_tpu.shutdown()`."""

import os
import time

import pytest

import ray_tpu
from ray_tpu import state
from ray_tpu._private import compile_cache
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu.util import events, spans


@pytest.fixture(autouse=True)
def _fresh_recorder():
    events.reset()
    yield
    events.reset()
    GLOBAL_CONFIG.invalidate_cache()


def _sums():
    c = compile_cache.counters()
    by_program = c.pop("by_program")
    return c, by_program


# ---------------------------------------------------------------------------
# jax's own seconds, by program
# ---------------------------------------------------------------------------


def test_a_first_call_moves_the_sums_and_its_row_and_a_second_nothing(
        monkeypatch):
    import jax
    import jax.numpy as jnp
    compile_cache.watch()
    # (a process that has made 64 programs would put this one with the rest)
    monkeypatch.setattr(compile_cache, "_by_program", {})

    def _startup_probe(x):
        return jnp.tanh(x) @ x

    f = jax.jit(_startup_probe)
    x = jnp.ones((8, 8))                 # (its own small programs first)
    c0, rows0 = _sums()
    assert "_startup_probe" not in rows0
    f(x).block_until_ready()
    c1, rows1 = _sums()
    assert c1["trace_s"] > c0["trace_s"]
    assert c1["lower_s"] > c0["lower_s"]
    assert c1["programs"] == c0["programs"] + 1
    assert c1["compiles"] + c1["cache_hits"] \
        == c0["compiles"] + c0["cache_hits"] + 1
    row = rows1["_startup_probe"]       # traced as that, compiled as jit(..)
    assert row["n"] == 1 and row["trace_s"] > 0 and row["lower_s"] > 0
    assert row["compile_s"] + row["cache_load_s"] > 0
    f(x).block_until_ready()
    assert _sums() == (c1, rows1)


class _Clock:
    """`time` for `compile_cache`, moved by hand."""

    def __init__(self):
        self.now = 1_000.0

    def perf_counter(self):
        return self.now

    def time(self):
        return self.now


def _report(clock, event, seconds, fun):
    clock.now += seconds
    compile_cache._on_duration(event, seconds, fun_name=fun)
    clock.now += 1e-3


@pytest.fixture
def listener(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(compile_cache, "time", clock)
    monkeypatch.setattr(compile_cache, "_by_program", {})
    monkeypatch.setattr(compile_cache, "_counts",
                        dict.fromkeys(compile_cache._counts, 0))
    compile_cache._pending.__dict__.clear()     # this thread's, on the
    yield clock                                 # real clock so far
    compile_cache._pending.__dict__.clear()


def test_the_table_stops_at_64_names_and_sums_the_rest(listener):
    for i in range(70):
        _report(listener, compile_cache._LOWER, 0.01, f"jit(f{i})")
    _, rows = _sums()
    assert len(rows) == compile_cache.BY_PROGRAM + 1 == 65
    assert rows["(other)"]["lower_s"] == pytest.approx(0.06)
    assert rows["f63"]["lower_s"] == pytest.approx(0.01)
    assert compile_cache.counters()["programs"] == 70


@pytest.mark.parametrize("scale,kept", [(1.0, True), (0.1, False)])
def test_a_program_that_cost_a_tenth_of_a_second_keeps_its_row(
        listener, scale, kept):
    _report(listener, compile_cache._TRACE, 0.06 * scale, "step")
    _report(listener, compile_cache._LOWER, 0.03 * scale, "jit(step)")
    _report(listener, compile_cache._CACHE_LOAD, 0.01 * scale, None)
    _report(listener, compile_cache._COMPILE, 0.02 * scale, "jit(step)")
    ring = events.snapshot(plane="proc", kind="compile")
    assert len(ring) == 1 and ring[0]["payload"]["cached"] is True
    assert ring[0]["payload"]["trace_s"] == pytest.approx(0.06 * scale)
    assert ring[0]["payload"]["lower_s"] == pytest.approx(0.03 * scale)
    rows = events.pinned()["rows"]
    assert len(rows) == (1 if kept else 0)
    if kept:
        assert (rows[0]["plane"], rows[0]["kind"]) == ("proc", "compile")
        assert rows[0]["dur"] == pytest.approx(0.11)
        assert rows[0]["payload"]["fun"] == "jit(step)"
    c, by_program = _sums()
    assert c["cache_hits"] == 1 and c["compiles"] == 0
    assert by_program["step"]["n"] == 1


def test_what_a_trace_holds_is_not_counted_twice(listener):
    # tracing `outer` (0.5 s) traced `inner` (0.2 s) on the way
    listener.now += 0.25
    _report(listener, compile_cache._TRACE, 0.2, "inner")
    listener.now += 0.049
    compile_cache._on_duration(compile_cache._TRACE, 0.5, fun_name="outer")
    c, by_program = _sums()
    assert c["trace_s"] == pytest.approx(0.5)
    assert by_program["outer"]["trace_s"] == pytest.approx(0.3)


# ---------------------------------------------------------------------------
# Pinned spans: the list beside the ring
# ---------------------------------------------------------------------------


def test_a_pinned_span_survives_the_rings_turning_over():
    with spans.span("proc", "init", pin=True, who="test") as outer:
        tok = spans.begin("proc", "boot", pin=True)
        spans.end(tok, ok=True)
        with spans.span("proc", "imports"):     # not pinned
            pass
    for i in range(5_000):
        events.record("engine", "step", i=i)
    assert not [e for e in events.snapshot() if e["kind"] == "init"]
    record = events.pinned()
    assert record["pid"] == os.getpid() and record["role"] == "driver"
    assert record["start"] <= time.time()
    kinds = [(r["plane"], r["kind"]) for r in record["rows"]]
    assert kinds == [("proc", "boot"), ("proc", "init")]    # as they closed
    boot, init = record["rows"]
    assert boot["parent"] == init["sid"] == outer.sid
    assert boot["trace_id"] == init["trace_id"] is not None
    assert boot["payload"] == {"ok": True}
    assert init["payload"] == {"who": "test"}
    assert init["start"] <= boot["start"]
    assert boot["start"] + boot["dur"] <= init["start"] + init["dur"] + 1e-3


def test_the_record_stops_at_its_limit():
    for i in range(events.PIN_ROWS + 40):
        spans.end(spans.begin("proc", "boot", pin=True, i=i))
    rows = events.pinned()["rows"]
    assert len(rows) == events.PIN_ROWS == 256
    assert [r["payload"]["i"] for r in rows] == list(range(256))


def test_a_dump_gives_the_rows_back_with_their_parents(tmp_path):
    with spans.span("proc", "init", pin=True) as outer:
        spans.end(spans.begin("proc", "boot", pin=True, n=1))
    events.record("proc", "ok")
    path = events.dump(str(tmp_path / "flightrec-1-0.jsonl"), "t")
    back = events.read_dumps(str(tmp_path))
    pinned = [e for e in back if e.get("pinned")]
    assert [e["kind"] for e in pinned] == ["boot", "init"]
    assert [e["kind"] for e in back][:2] == ["boot", "init"]  # ahead of it
    assert pinned[0]["pinned"]["parent"] == pinned[1]["span_id"] == outer.sid
    assert pinned[0]["payload"] == {"n": 1}
    assert pinned[0]["pinned"]["dur"] >= 0.0
    assert all(e["role"] == "driver" and e["proc_start"] for e in pinned)
    assert any(e["kind"] == "ok" and not e.get("pinned") for e in back)
    assert path and all(e["pid"] == os.getpid() for e in back)


def test_with_the_recorder_off_nothing_is_kept(monkeypatch):
    monkeypatch.setenv("RAY_TPU_EVENTS", "0")
    GLOBAL_CONFIG.invalidate_cache()
    events.reset()
    with spans.span("proc", "init", pin=True) as tok:
        assert tok is None
    events.pin("proc", "compile", time.time(), 1.0)
    assert events.pinned()["rows"] == []
    assert events.snapshot() == []


# ---------------------------------------------------------------------------
# The merged timeline of a cluster, after its shutdown
# ---------------------------------------------------------------------------


def test_the_timeline_outlives_the_shutdown_and_the_workers():
    @ray_tpu.remote
    class Probe:
        def pid(self):
            return os.getpid()

    ray_tpu.init(num_cpus=2, object_store_memory=64 << 20)
    try:
        stays, goes = Probe.remote(), Probe.remote()
        pids = ray_tpu.get([stays.pid.remote(), goes.pid.remote()],
                           timeout=60)
        ray_tpu.kill(goes)
        deadline = time.time() + 20
        while time.time() < deadline:           # gone before the shutdown
            try:
                os.kill(pids[1], 0)
            except OSError:
                break
            time.sleep(0.05)
        live = state.startup_timeline()
        assert any(r["kind"] == "init" for r in live)
    finally:
        ray_tpu.shutdown()
        GLOBAL_CONFIG.invalidate_cache()
    rows = state.startup_timeline()
    assert rows == sorted(rows, key=lambda r: r["start"])
    assert {"pid", "role", "plane", "kind", "start", "dur", "sid", "parent",
            "payload"} <= set(rows[0])
    init = [r for r in rows if (r["plane"], r["kind"]) == ("proc", "init")]
    assert len(init) == 1 and init[0]["pid"] == os.getpid()
    assert init[0]["role"] == "driver"
    children = {r["kind"] for r in rows if r["parent"] == init[0]["sid"]}
    assert children == {"gcs_start", "hostd_start", "driver_connect"}
    made = {r["sid"]: r for r in rows
            if (r["plane"], r["kind"]) == ("sched", "worker_boot")}
    assert all(r["role"] == "hostd" for r in made.values())
    boots = {r["pid"]: r for r in rows
             if (r["plane"], r["kind"]) == ("proc", "boot")}
    assert set(pids) <= set(boots)      # the one that had exited too
    for pid, boot in boots.items():
        assert boot["role"] == "worker"
        assert made[boot["parent"]]["payload"]["pid"] == pid
    for pid in pids:
        mine = {r["kind"] for r in rows if r["pid"] == pid}
        assert {"boot", "imports", "core_worker", "ready_rpc",
                "actor_init"} <= mine
    # and it stays what it was once the session is gone
    assert state.startup_timeline() == rows


def test_whoever_the_shutdown_did_not_hear_from_left_a_dump(monkeypatch):
    """A node that does not answer costs nothing where the session's logs
    can be read: hostd and its workers dump on their way out, and the
    shutdown of a cluster it owned reads the dumps' heads."""
    @ray_tpu.remote
    class Probe:
        def pid(self):
            return os.getpid()

    monkeypatch.setattr(
        state, "_collect_startup_records",
        lambda address, timeout=4.0: [("driver", 0.0, events.pinned())])
    ray_tpu.init(num_cpus=2, object_store_memory=64 << 20)
    try:
        probe = Probe.remote()
        pid = ray_tpu.get(probe.pid.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()
        GLOBAL_CONFIG.invalidate_cache()
    rows = state.startup_timeline()
    assert {r["role"] for r in rows} >= {"driver", "hostd", "worker"}
    boot, = [r for r in rows if r["pid"] == pid and r["kind"] == "boot"]
    made, = [r for r in rows if r["sid"] == boot["parent"]]
    assert (made["plane"], made["kind"], made["role"]) == (
        "sched", "worker_boot", "hostd")
    assert len([r for r in rows if r["kind"] == "init"]) == 1


def test_a_report_holds_however_many_ended_inside_it(listener):
    # a step of many layer bodies: 600 jitted kernels traced on the way
    for i in range(600):
        _report(listener, compile_cache._TRACE, 0.002, f"kernel{i % 7}")
    listener.now += 0.1
    compile_cache._on_duration(compile_cache._TRACE, 600 * 0.003 + 0.1,
                               fun_name="step")
    c, by_program = _sums()
    assert c["trace_s"] == pytest.approx(1.9)       # the wall, not 3.1
    assert by_program["step"]["trace_s"] == pytest.approx(0.7)
