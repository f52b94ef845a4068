"""The readers of the step record's parts and of the engine's timeline
(`benchmark/step_parts.py`): on a hand-made run, on a parent's records that
lack the fields, with and without the profiler's marks; and the idle split
with and without the nested `engine.` annotations."""

import json

import pytest

from benchmark import idle_phases, manifest, step_parts, trace_reduce
from benchmark.tools import step_timeline

NEW = ("decode_assemble_ms_p50", "decode_upload_ms_p50",
       "decode_release_ms_p50", "decode_deliver_ms_p50",
       "decode_host_offcpu_pct", "decode_host_untraced_ms",
       "decode_fetch_wait_pct", "decode_longest_step_ms",
       "decode_gc_ms_per_s", "decode_idle_upload_pct",
       "decode_assemble_untraced_ms", "decode_upload_untraced_ms",
       "decode_release_untraced_ms", "decode_deliver_untraced_ms")
SERVE_CELLS = ["serve_gpt2xl_decode", "serve_olmoe_decode",
               "serve_axk1_docs_decode", "serve_evabyte_sessions_decode"]
PHASES = ["admit", "build_batch", "dispatch", "fetch", "commit"]
PARTS = ["windows", "assemble", "upload", "release", "lock", "deliver"]
COLUMNS = ["t", "steps", "prefill_steps", "wall_s", "phase_s", "part_s",
           "cpu_s", "cpu_wall_s", "cpu_steps", "gc_s", "longest_ms",
           "longest_phase"]


def read(name, run):
    return manifest.module("layer_metrics", name).read(run)


def row(t, steps=100, fetch=0.1, host=0.9, cpu=0.72, gc_s=0.0, longest=12.0,
        phase="build_batch"):
    """One second of the loop: `host` seconds over the four host phases
    (admit 10%, build_batch 60%, dispatch 10%, commit 20%), `fetch` blocked;
    one iteration in four read the CPU clock (`cpu` of `host` scaled)."""
    return [t, steps, 5, fetch + host,
            [0.1 * host, 0.6 * host, 0.1 * host, fetch, 0.2 * host],
            [0.0, 0.2 * host, 0.4 * host, 0.05 * host, 0.0, 0.15 * host],
            cpu / 4, host / 4, steps // 4, gc_s, longest, phase]


def stats(rows):
    return {"timeline": {"columns": COLUMNS, "phases": PHASES,
                         "parts": PARTS, "rows": rows}}


def step(ts, **parts):
    payload = {"decode": 8, "wall_ms": 10.0, "fetch_ms": 1.0}
    payload.update({k + "_ms": v for k, v in parts.items()})
    return {"kind": "step", "ts": ts, "payload": payload}


def run_of(rows, marks=None, events=()):
    return {"base": 1000.0, "seconds": 10.0, "stats1": stats(rows),
            "marks": marks or {}, "engine_events": list(events),
            "traffic": {"trace": {"at_s": 4.0, "slice_s": 1.0}},
            "cell": {"name": "no_such_cell"}}


def test_the_manifest_declares_the_new_readers_in_the_four_serve_cells():
    m = manifest.load()
    for name in NEW:
        entry = m.per_layer[name]
        assert entry["workloads"] == SERVE_CELLS, name
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["layer"] == (
            "device" if name == "decode_idle_upload_pct" else "engine")
    assert [p["name"] for p in m.data["per_layer"]][-len(NEW):] == list(NEW)
    untraced = [n for n in NEW if n.endswith("_untraced_ms")]
    assert len(untraced) == 5 and all(
        m.per_layer[n]["source"] == "program_counter" for n in untraced)
    assert m.per_layer["decode_gc_ms_per_s"]["unit"] == "ms/s"
    assert m.per_layer["decode_fetch_wait_pct"]["better"] == "higher"


def test_the_parts_medians_come_from_the_windows_step_records():
    events = [step(999.0, assemble=99.0, upload=99.0, release=9.0, deliver=9.0),
              step(1001.0, assemble=2.0, upload=4.0, release=0.5, deliver=1.0),
              step(1002.0, assemble=2.2, upload=4.4, release=0.7, deliver=1.2),
              step(1003.0, assemble=2.4, upload=4.8, release=0.9, deliver=1.4),
              step(1011.0, assemble=99.0, upload=99.0, release=9.0,
                   deliver=9.0)]
    run = run_of([], events=events)
    assert read("decode_assemble_ms_p50", run) == 2.2
    assert read("decode_upload_ms_p50", run) == 4.4
    assert read("decode_release_ms_p50", run) == 0.7
    assert read("decode_deliver_ms_p50", run) == 1.2


@pytest.mark.parametrize("name", NEW)
def test_a_parents_record_without_the_fields_reads_none(name):
    """The parent of PR 35: `engine/step` records with the five phases
    alone, `stats()` without a timeline, a trace without `engine.` names;
    and a run with nothing at all."""
    parent = {"base": 1000.0, "seconds": 10.0,
              "stats0": {"steps": 5}, "stats1": {"steps": 900},
              "marks": {"trace_on": 1004.5, "trace_off": 1006.0},
              "engine_events": [step(1001.0), step(1002.0)],
              "traffic": {"trace": {"at_s": 4.0, "slice_s": 1.0}},
              "cell": {"name": "no_such_cell"}}
    assert read(name, parent) is None
    assert read(name, {"base": 0.0, "seconds": 1.0}) is None
    assert read(name, dict(parent, stats1=stats([]))) is None


def test_untraced_rows_leave_out_what_the_session_touched():
    rows = [row(t) for t in range(998, 1012)]
    # no marks (an untraced run): every second that lies inside the window
    kept = step_parts.untraced_rows(run_of(rows))
    assert [r["t"] for r in kept] == list(range(1000, 1010))
    # the start returned at 1004.6 but began when the file plans it, 1004.0;
    # the stop returned at 1006.3: 1003.0 .. 1007.3 is touched
    marks = {"trace_on": 1004.6, "trace_off": 1006.3}
    kept = step_parts.untraced_rows(run_of(rows, marks))
    assert [r["t"] for r in kept] == [1000, 1001, 1002, 1008, 1009]
    # a start that came early counts from its own mark
    early = {"trace_on": 1003.2, "trace_off": 1006.3}
    kept = step_parts.untraced_rows(run_of(rows, early))
    assert [r["t"] for r in kept] == [1000, 1001, 1008, 1009]
    # a second whose longest iteration began inside the session is touched
    held = [row(t) for t in range(998, 1008)] + [
        row(1008, longest=1700.0), row(1009)] + [row(1010), row(1011)]
    kept = step_parts.untraced_rows(run_of(held, marks))
    assert [r["t"] for r in kept] == [1000, 1001, 1002, 1009]
    # one mark alone is no session
    kept = step_parts.untraced_rows(run_of(rows, {"trace_on": 1004.6}))
    assert len(kept) == 10
    parts = step_parts.split_rows(
        step_parts.rows(stats(rows)), 1000.0, 10.0, (1003.0, 1007.3))
    assert [[r["t"] for r in parts[k]] for k in ("before", "inside",
                                                   "after")] == [
        [1000, 1001, 1002], [1003, 1004, 1005, 1006, 1007], [1008, 1009]]


def test_the_timelines_readers_on_a_hand_made_window():
    slow = row(1005, steps=60, fetch=0.02, host=0.97, cpu=0.5, gc_s=0.09,
               longest=130.0, phase="admit")
    rows = [row(t) for t in range(1000, 1005)] + [slow] + [
        row(t, gc_s=0.004, longest=14.0) for t in range(1006, 1010)]
    marks = {"trace_on": 1004.6, "trace_off": 1006.3}
    run = run_of(rows, marks)
    # untraced: 1000-1002 and 1008-1009, five seconds of 100 iterations
    assert read("decode_host_untraced_ms", run) == pytest.approx(9.0)
    assert read("decode_fetch_wait_pct", run) == pytest.approx(10.0)
    assert read("decode_host_offcpu_pct", run) == pytest.approx(20.0)
    assert read("decode_longest_step_ms", run) == 14.0
    assert read("decode_gc_ms_per_s", run) == pytest.approx(1e3 * 0.008 / 5)
    # without marks the slow second is in: the scored run has no session
    run = run_of(rows)
    assert read("decode_longest_step_ms", run) == 130.0
    assert read("decode_gc_ms_per_s", run) == pytest.approx(
        1e3 * (0.09 + 4 * 0.004) / 10)
    assert read("decode_host_untraced_ms", run) == pytest.approx(
        1e3 * (9 * 0.9 + 0.97) / 960)
    # a CPU reading over the wall is a fault of the clocks and shows as one
    assert read("decode_host_offcpu_pct", run_of(
        [row(1001, cpu=0.95), row(1002, cpu=0.95)])) == pytest.approx(
            -100 * 0.05 / 0.9)
    # too few iterations read the CPU clock to say a share (25 < 32), or none
    assert step_parts.MIN_CLOCKED == 32
    assert read("decode_host_offcpu_pct", run_of([row(1001)])) is None
    assert read("decode_host_offcpu_pct", run_of(
        [row(1001), row(1002)])) == pytest.approx(20.0)
    blind = row(1001)
    blind[6:9] = 0.0, 0.0, 0
    assert read("decode_host_offcpu_pct", run_of([blind, blind])) is None
    assert read("decode_host_untraced_ms", run_of([blind])) == pytest.approx(
        9.0)


def test_the_parts_means_come_from_the_untraced_rows():
    """The parts as the scored run has them: seconds over iterations in the
    rows that the session did not touch, whatever the ring's records say."""
    slow = row(1005, steps=60, host=1.5)
    rows = [row(t) for t in range(1000, 1005)] + [slow] + [
        row(t, host=0.8) for t in range(1006, 1010)]
    marks = {"trace_on": 1004.6, "trace_off": 1006.3}
    events = [step(1005.5, assemble=99.0, upload=99.0, release=9.0,
                   deliver=9.0)]
    run = run_of(rows, marks, events)
    # untraced: 1000-1002 at 0.9 s of host work, 1008-1009 at 0.8
    host = (3 * 0.9 + 2 * 0.8) / 500
    assert read("decode_assemble_untraced_ms", run) == pytest.approx(
        1e3 * 0.2 * host)
    assert read("decode_upload_untraced_ms", run) == pytest.approx(
        1e3 * 0.4 * host)
    assert read("decode_release_untraced_ms", run) == pytest.approx(
        1e3 * 0.05 * host)
    assert read("decode_deliver_untraced_ms", run) == pytest.approx(
        1e3 * 0.15 * host)
    assert read("decode_upload_ms_p50", run) == 99.0
    # without marks every second of the window counts, the slow one too
    assert read("decode_upload_untraced_ms", run_of(rows)) == pytest.approx(
        1e3 * 0.4 * (5 * 0.9 + 1.5 + 4 * 0.8) / 960)


def _trace(host):
    ops = [(0, 1_000, "%a = f32[1] add()"), (1_500, 2_000, "%b = f32[1] add()"),
           (10_000, 11_000, "%c = f32[1] add()"),
           (20_000, 21_000, "%d = f32[1] add()")]
    return {"devices": {"/device:TPU:0": {trace_reduce.OPS_LINE: ops}},
            "host": host}


FLAT = [(1_900, 9_000, "engine/fetch", "python3/7"),
        (9_000, 9_500, "engine/commit", "python3/7"),
        (9_500, 9_800, "engine/admit", "python3/7"),
        (9_800, 10_300, "engine/build_batch", "python3/7"),
        (10_300, 10_400, "engine/dispatch", "python3/7"),
        (11_000, 19_000, "engine/build_batch", "python3/7")]
NESTED = [(9_010, 9_100, "engine.commit/release", "python3/7"),
          (9_100, 9_150, "engine.commit/lock", "python3/7"),
          (9_150, 9_490, "engine.commit/deliver", "python3/7"),
          (9_810, 9_900, "engine.build_batch/assemble", "python3/7"),
          (9_900, 10_290, "engine.build_batch/upload", "python3/7"),
          (11_010, 12_000, "engine.build_batch/assemble", "python3/7"),
          (12_000, 18_990, "engine.build_batch/upload", "python3/7")]


def test_the_idle_split_does_not_see_the_nested_annotations():
    """`idle_phases.split` gives the same `by_phase` with and without the
    `engine.` annotations (they would take their phases' idle time if they
    were named `engine/...`: the shorter covering span has an instant), and
    the parts' own split puts the same gaps under the parts."""
    without = idle_phases.split(_trace(FLAT))
    with_parts = idle_phases.split(_trace(sorted(FLAT + NESTED)))
    assert with_parts == without
    assert without["by_phase"] == {
        "engine/fetch": 7_000, "engine/commit": 500, "engine/admit": 300,
        "engine/build_batch": 200 + 8_000}
    parts = step_parts.idle_by_part(_trace(sorted(FLAT + NESTED)))
    assert parts["window_ns"] == without["window_ns"] == 21_000
    assert parts["idle_ns"] == without["idle_ns"]
    assert parts["by_phase"] == {
        "engine.commit/release": 90, "engine.commit/lock": 50,
        "engine.commit/deliver": 340, "engine.build_batch/assemble": 90 + 990,
        "engine.build_batch/upload": 100 + 6_990}
    # a parent's trace, and a CPU's
    assert step_parts.idle_by_part(_trace(FLAT)) is None
    assert step_parts.idle_by_part({"devices": {}, "host": NESTED}) is None
    # the label of a gap may become the part's; no metric reads it
    reduced = trace_reduce.reduce(_trace(sorted(FLAT + NESTED)))
    assert reduced["idle_pct"] == trace_reduce.reduce(
        _trace(FLAT))["idle_pct"]


def test_idle_upload_pct_reads_the_runs_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(idle_phases, "HERE", str(tmp_path))
    monkeypatch.setattr(trace_reduce, "find", lambda d: d)
    monkeypatch.setattr(step_parts, "_cache", {})
    run = {"cell": {"name": "a_cell"}}
    monkeypatch.setattr(trace_reduce, "load",
                        lambda p: _trace(sorted(FLAT + NESTED)))
    assert read("decode_idle_upload_pct", run) == pytest.approx(
        100.0 * 7_090 / 21_000)
    # annotations there, none over a gap: 0.0, a number
    monkeypatch.setattr(step_parts, "_cache", {})
    monkeypatch.setattr(trace_reduce, "load", lambda p: _trace(
        FLAT + [(100, 900, "engine.build_batch/upload", "python3/7")]))
    assert read("decode_idle_upload_pct", run) == 0.0
    monkeypatch.setattr(step_parts, "_cache", {})
    monkeypatch.setattr(trace_reduce, "load", lambda p: _trace(FLAT))
    assert read("decode_idle_upload_pct", run) is None


def test_the_tool_prints_the_window_by_the_second_and_its_segments(tmp_path):
    rows = [row(t) for t in range(990, 1052)]
    rows[14] = row(1004, steps=70, fetch=0.02, host=0.97, longest=40.0)
    s0 = dict(stats(rows[:10]), steps=900, step_wall_s=9.0, cpu_s=0.4,
              cpu_wall_s=0.5, cpu_steps=225, gc={"collections": [5, 1, 0], "seconds": 0.01,
                  "full_seconds": 0.0})
    s1 = dict(stats(rows), steps=6070, step_wall_s=61.0, cpu_s=2.8,
              cpu_wall_s=3.5, cpu_steps=1517, gc={"collections": [50, 4, 1], "seconds": 0.05,
                  "full_seconds": 0.012})
    result = {"line": {"breakdown": {}}, "notes": {"stats0": s0, "stats1": s1}}
    said = []
    out = step_timeline.report(result, "serve_gpt2xl_decode", 51.0, None,
                               said.append)
    # the window opens behind stats0's newest row (999): 1000 .. 1051; the
    # cell's file plans the session at 10 s for 2 s: 1009 .. 1013 touched,
    # and 1014, whose longest iteration (12 ms) began before 1014.0
    assert out["before"]["seconds"] == 9 and out["inside"]["seconds"] == 5
    assert out["after"]["seconds"] == 37
    assert out["untraced"]["steps"] == 46 * 100 - 30
    assert out["window"]["steps"] == 51 * 100 - 30
    assert out["before"]["host_ms"] == pytest.approx(
        1e3 * (8 * 0.9 + 0.97) / 870)
    assert out["after"]["largest_part"] == "upload"
    assert out["after"]["part_ms"]["upload"] == pytest.approx(3.6)
    assert out["after"]["offcpu_pct"] == pytest.approx(20.0)
    assert out["after"]["clocked"] == 37 * 25
    assert sum(line.lstrip().startswith("+") for line in said) == 51
    assert sum(line.split()[0].endswith("*") for line in said) == 5
    assert any("5170 iterations" in line and "1292 clocked" in line
               for line in said)
    # with the records and marks `--run` keeps: the session's true seconds
    # and the records' coverage
    kept = {"base": 1000.25, "seconds": 51.0,
            "marks": {"trace_on": 1011.0, "trace_off": 1016.5},
            "records_fetched": 3000,
            "records": [dict(step(1000.5 + 0.02 * i, windows=0.0, assemble=2.0,
                                  upload=3.9, release=0.4, lock=0.0,
                                  deliver=1.5)["payload"],
                             ts=1000.5 + 0.02 * i, build_ms=6.0, commit_ms=2.0)
                        for i in range(2500)]}
    kept["records"][7].update(wall_ms=180.0, commit_ms=170.0, release_ms=168.0,
                              gc_ms=90.0, cpu_ms=3.0, cpu_wall_ms=175.0)
    path = tmp_path / "steps.json"
    path.write_text(json.dumps(kept))
    said.clear()
    out = step_timeline.report(result, "serve_gpt2xl_decode", 51.0, str(path),
                               said.append)
    assert out["inside"]["seconds"] == 9       # 1009 .. 1017 touched (17.5)
    assert out["records"] == 2500
    assert out["coverage_pct"] == pytest.approx(100.0 * 2500 / 5170)
    assert any("parts of build_ms" in line and "98.33%" in line
               for line in said)
    # the window's longest iterations one by one, the longest first: phase,
    # part, collector time, and the CPU clock where the iteration read it
    at = said.index("the longest iterations the records hold:")
    assert len(said) == at + 1 + step_timeline.LONGEST
    assert ("wall    180.00 ms  commit_ms 170.00  release_ms 168.00  gc_ms "
            "90.00  cpu_ms 3.00 of cpu_wall_ms 175.00") in said[at + 1]
    assert "cpu_ms" not in said[at + 2] and "upload_ms 3.90" in said[at + 2]
    # a parent's result has no timeline: said, not raised
    said.clear()
    assert step_timeline.report({"notes": {"stats0": {}, "stats1": {}}},
                                "serve_gpt2xl_decode", 51.0, None,
                                said.append) == {}
    assert "keeps none" in said[0]
