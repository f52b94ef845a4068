"""The shared reader of the `setup_*` metrics on a canned timeline of three
processes: rows that overlap, a row that crosses the window's open, a
process with no rows; every one of the eight metrics reads a number, and a
program without a start-up record reads none."""

import importlib

import pytest

from benchmark import manifest, startup

NAMES = ("setup_cluster_boot_s", "setup_worker_boot_s",
         "setup_backend_init_s", "setup_weights_s", "setup_trace_lower_s",
         "setup_programs_compiled", "setup_first_runs_s",
         "setup_unattributed_s")
T0 = 1_000.0            # the benchmark process's start
DRIVER, HOSTD, CONTROLLER, REPLICA = 10, 11, 12, 13


def _row(of, role, name, start, dur, **payload):
    plane, kind = name.split("/")
    return {"pid": of, "role": role, "plane": plane, "kind": kind,
            "start": T0 + start, "dur": dur, "sid": None, "parent": None,
            "payload": payload or None}


def _serve_rows():
    made = dict(trace_s=1.0, lower_s=0.5, cache_load_s=0.25, compile_s=0.0,
                cached=1)
    return [
        _row(DRIVER, "driver", "proc/init", 2, 3),
        _row(DRIVER, "driver", "serve/run", 5, 15),
        _row(HOSTD, "hostd", "sched/worker_boot", 5.5, 1.5, pid=CONTROLLER),
        _row(CONTROLLER, "worker", "proc/boot", 6, 1),     # inside the last
        _row(CONTROLLER, "worker", "serve/replica_start", 7.5, 12.5),
        _row(HOSTD, "hostd", "sched/worker_boot", 8, 2, pid=REPLICA),
        _row(HOSTD, "hostd", "sched/worker_boot", 8, 2, pid=99),  # another's
        _row(REPLICA, "worker", "proc/boot", 9, 1.5),      # overlaps it
        _row(REPLICA, "worker", "proc/jax_import", 11, 3),
        _row(REPLICA, "worker", "proc/backend_init", 14, 1),
        _row(REPLICA, "worker", "engine/init_params", 15, 2),
        _row(REPLICA, "worker", "engine/prepare", 17, 0.5),
        _row(REPLICA, "worker", "engine/pools", 17.5, 1.5),
        _row(REPLICA, "worker", "engine.dispatch/make_program", 21, 3,
             key=[8, False, False, 0], **made),
        _row(REPLICA, "worker", "proc/compile", 21, 1.75, seconds=0.25,
             cached=True, fun="jit(step)", trace_s=1.0, lower_s=0.5),
        # made in the window: no part of set-up
        _row(REPLICA, "worker", "engine.dispatch/make_program", 50, 2,
             key=[1, True, False, 0], **made),
        # opened in set-up, closed in the window: cut at its open
        _row(DRIVER, "driver", "sched/lease_wait", 39, 5),
    ]


def _serve_run(rows):
    # set-up is 40 s: warm-up 5 s up to the lead-in's start at 29.8
    return {"base": T0 + 40.0, "end_to_end": {"setup_s": 40.0},
            "compile_s": 5.0, "worker_ready_s": 15.0,
            "traffic": {"requests": {"lead_in_s": 10.0}},
            "stats0": {"compile": {
                "compiles": 0, "compile_s": 0.0, "cache_hits": 3,
                "cache_load_s": 0.5, "trace_s": 2.0, "lower_s": 1.25,
                "programs": 3}},
            "startup_timeline": rows}


def _read(name, run):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(run)


def test_every_metric_reads_a_number_from_a_serve_cells_timeline():
    run = _serve_run(_serve_rows())
    got = {name: _read(name, run) for name in NAMES}
    assert all(isinstance(v, (int, float)) for v in got.values()), got
    assert got["setup_cluster_boot_s"] == pytest.approx(3.0)
    # 5.5-7 (the controller's), 8-10.5 (the replica's two, overlapping),
    # 39-40 (the lease wait, cut at the window's open); not pid 99's
    assert got["setup_worker_boot_s"] == pytest.approx(1.5 + 2.5 + 1.0)
    assert got["setup_backend_init_s"] == pytest.approx(4.0)
    assert got["setup_weights_s"] == pytest.approx(4.0)
    assert got["setup_trace_lower_s"] == pytest.approx(3.25)
    assert got["setup_programs_compiled"] == 0
    assert got["setup_first_runs_s"] == pytest.approx(3 - 1.75)
    # named: 2-20 (init, run), 21-24, 24.8-40 (warm-up, lead-in, the wait)
    assert got["setup_unattributed_s"] == pytest.approx(2 + 1 + 0.8)


def test_without_stats0_the_counters_come_from_the_rows():
    run = _serve_run(_serve_rows())
    run["stats0"] = {}
    assert _read("setup_trace_lower_s", run) == pytest.approx(1.5)
    assert _read("setup_programs_compiled", run) == 0


def test_the_train_cell_reads_its_six_and_names_the_workers_loop():
    worker = 20
    rows = [
        _row(DRIVER, "driver", "proc/init", 1, 2),
        _row(DRIVER, "driver", "train/fit_start", 3, 9),
        _row(HOSTD, "hostd", "sched/worker_boot", 3.5, 2, pid=worker),
        _row(worker, "worker", "proc/boot", 4, 1.5),
        _row(worker, "worker", "proc/jax_import", 6, 3),
        _row(worker, "worker", "proc/backend_init", 9, 1),
        _row(worker, "worker", "proc/compile", 14, 4, seconds=3.0,
             cached=False, fun="jit(train_step)", trace_s=0.75,
             lower_s=0.25),
        # the reference check's, behind the window
        _row(worker, "worker", "proc/compile", 80, 2, seconds=1.0,
             cached=False, fun="jit(loss)", trace_s=0.5, lower_s=0.5),
    ]
    run = {"end_to_end": {"setup_s": 25.0}, "compile_s": 4.0,
           "worker_ready_s": 9.0, "traffic": {},
           "notes": {"window": {"t_start": T0 + 25.0}},
           "startup_timeline": rows}
    cells = manifest.load(manifest.ROOT)
    names = [n for n in cells.metrics_of("train_gpt2s_1chip", "per_layer")
             if n.startswith("setup_")]
    assert len(names) == 6
    got = {name: _read(name, run) for name in names}
    assert got["setup_cluster_boot_s"] == pytest.approx(2.0)
    assert got["setup_worker_boot_s"] == pytest.approx(2.0)
    assert got["setup_backend_init_s"] == pytest.approx(4.0)
    assert got["setup_trace_lower_s"] == pytest.approx(1.0)
    assert got["setup_programs_compiled"] == 1
    # named: 1-12 (init, fit_start), 12-25 (ready at 3 + 9 to the open)
    assert got["setup_unattributed_s"] == pytest.approx(1.0)


def test_a_process_with_no_rows_and_an_empty_timeline_read_zeros():
    run = _serve_run([_row(DRIVER, "driver", "proc/init", 2, 3)])
    got = {name: _read(name, run) for name in NAMES}
    assert got["setup_weights_s"] == 0.0
    assert got["setup_backend_init_s"] == 0.0
    assert got["setup_worker_boot_s"] == 0.0
    run = _serve_run([])
    assert all(_read(name, run) is not None for name in NAMES)


def test_a_program_without_the_record_reads_nothing(monkeypatch):
    from ray_tpu import state
    monkeypatch.delattr(state, "startup_timeline")
    run = _serve_run([])
    del run["startup_timeline"]
    for name in NAMES:
        assert _read(name, run) is None
        assert run["not_measured"] == startup.NO_TIMELINE


def test_the_manifest_lists_each_metric_in_the_cells_it_reads():
    cells = manifest.load(manifest.ROOT)
    for cell in cells.cells:
        listed = [n for n in cells.metrics_of(cell, "per_layer")
                  if n.startswith("setup_")]
        assert len(listed) == (6 if cell.startswith("train_") else 8)
