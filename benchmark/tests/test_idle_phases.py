import json
import os
import subprocess
import sys

import pytest

from benchmark import idle_phases, manifest, trace_reduce

NEW = ("decode_step_ms_p50", "decode_host_ms_p50", "decode_idle_fetch_pct",
       "decode_idle_host_pct", "decode_idle_dispatch_pct",
       "decode_idle_attributed_pct", "replica_compile_s")
ROOT = manifest.ROOT


def test_a_gap_is_split_over_the_phases_that_cover_it():
    # one gap, 100..200: fetch to 130, commit to 150, nothing to 170,
    # dispatch from 170 on and past the gap's end
    phases = [(40, 130, "engine/fetch"), (130, 150, "engine/commit"),
              (170, 260, "engine/dispatch"), (300, 400, "engine/admit")]
    assert idle_phases.attribute([(100, 200)], phases) == {
        "engine/fetch": 30, "engine/commit": 20, "engine/dispatch": 30}
    # two gaps under one phase add up; a phase that touches no gap is absent
    assert idle_phases.attribute([(0, 10), (50, 60)], phases) == {
        "engine/fetch": 10}


def test_where_phases_overlap_the_shorter_one_takes_the_time():
    phases = [(0, 100, "engine/outer"), (20, 40, "engine/inner"),
              (30, 35, "engine/innermost")]
    assert idle_phases.attribute([(10, 50)], phases) == {
        "engine/outer": 20, "engine/inner": 15, "engine/innermost": 5}


def _trace(host):
    ops = [(0, 1_000, "%a = f32[1] add()"), (1_500, 2_000, "%b = f32[1] add()"),
           (10_000, 11_000, "%c = f32[1] add()"),
           (20_000, 21_000, "%d = f32[1] add()")]
    return {"devices": {"/device:TPU:0": {trace_reduce.OPS_LINE: ops}},
            "host": host}


def test_split_takes_the_gaps_as_the_reduction_does():
    host = [(1_900, 9_000, "engine/fetch", "python3/7"),
            (9_000, 9_500, "engine/commit", "python3/7"),
            (9_500, 9_800, "engine/admit", "python3/7"),
            (9_800, 9_900, "engine/build_batch", "python3/7"),
            (9_900, 10_400, "engine/dispatch", "python3/7"),
            (11_000, 19_000, "np.asarray(jax.Array)", "python3/7")]
    s = idle_phases.split(_trace(host))
    # the 500 ns gap is between two operations and in no share
    assert s["window_ns"] == 21_000 and s["idle_ns"] == 8_000 + 9_000
    assert s["by_phase"] == {
        "engine/fetch": 7_000, "engine/commit": 500, "engine/admit": 300,
        "engine/build_batch": 100, "engine/dispatch": 100}
    reduced = trace_reduce.reduce(_trace(host))
    assert reduced["idle_pct"] == pytest.approx(
        100.0 * (s["idle_ns"] + 500) / s["window_ns"])


@pytest.mark.parametrize("trace", [
    {"devices": {}, "host": [(0, 5, "engine/fetch", "t")]},   # the CPU
    _trace([(0, 5, "np.asarray(jax.Array)", "t")]),           # the parent
])
def test_no_device_plane_or_no_annotation_is_none(trace, tmp_path):
    assert idle_phases.split(trace) is None
    run = {"cell": {"name": "no_such_cell"}, "base": 0.0, "seconds": 1.0,
           "engine_events": [{"kind": "step", "ts": 0.5,
                              "payload": {"decode": 8}}], "stats0": {}}
    for name in NEW:
        assert manifest.module("layer_metrics", name).read(run) is None, name


def test_step_readers_take_the_medians_of_the_windows_records():
    def step(ts, wall, fetch):
        return {"kind": "step", "ts": ts,
                "payload": {"decode": 8, "wall_ms": wall, "fetch_ms": fetch}}
    run = {"base": 10.0, "seconds": 5.0, "engine_events": [
        step(9.0, 500.0, 1.0), step(11.0, 80.0, 70.0), step(12.0, 78.0, 71.0),
        step(13.0, 79.0, 71.5), step(16.0, 900.0, 1.0)],
        "stats0": {"compile": {"compiles": 2, "compile_s": 1.5,
                               "cache_hits": 40, "cache_load_s": 6.25}}}
    def read(name):
        return manifest.module("layer_metrics", name).read(run)

    assert read("decode_step_ms_p50") == 79.0
    assert read("decode_host_ms_p50") == 7.5
    assert read("replica_compile_s") == 7.75


def test_a_traced_rehearsal_prints_every_new_reader_and_ends_in_its_line():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearse", "--trace", "1", "--workload", "serve_gpt2xl_decode",
         "--seconds", "5"], capture_output=True, text=True, timeout=600,
        cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "Traceback" not in out.stdout + out.stderr
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    for name in NEW:
        assert name in line["metrics"] or \
            f"per-layer metric {name}: not measured" in out.stdout, name
    # the program's own records reach the readers on any backend
    for name in ("decode_step_ms_p50", "decode_host_ms_p50",
                 "replica_compile_s"):
        assert line["metrics"][name]["value"] > 0.0
