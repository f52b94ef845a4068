"""What `setup_s` is made of, read from inside the program: the shared
reader of the eight `setup_*` per-layer metrics.

The program keeps, in every process, a start-up record: one row for each
span it closed with `pin=True` and for each program that cost 0.1 s to make
(`ray_tpu.util.events.pinned`).  `ray_tpu.shutdown()` collects every
process's, those that had already ended included, and
`ray_tpu.state.startup_timeline()` gives the merged rows afterwards, in the
benchmark's own process: `pid, role, plane, kind, start, dur, sid, parent,
payload`, times on this process's `time.time()`.

Every reader here cuts the rows at the window's open (`run["base"]` in a
serve cell, the worker's `t_start` in the train cell) and at the benchmark
process's start (`setup_s` before that), and returns seconds.  On a program
that keeps no such record (`startup_timeline` is not there) every reader
returns None and the metric is left out of the line.
"""

from __future__ import annotations

from benchmark import readers

NO_TIMELINE = ("the program keeps no start-up record: "
               "ray_tpu.state.startup_timeline is not there")
_WEIGHTS = ("init_params", "prepare", "pools")
_SPLIT = ("trace_s", "lower_s", "cache_load_s", "compile_s")


def timeline(run: dict):
    """The session's rows (None on a program without them), fetched once a
    run and kept in `run["startup_timeline"]`, where a test puts its own."""
    if "startup_timeline" not in run:
        try:
            from ray_tpu import state
            fetch = state.startup_timeline
        except (ImportError, AttributeError):
            return readers.not_measured(run, NO_TIMELINE)
        run["startup_timeline"] = fetch()
    return run["startup_timeline"]


def bounds(run: dict) -> tuple:
    """(the benchmark process's start, the window's open)."""
    t_open = (run["base"] if "base" in run
              else run["notes"]["window"]["t_start"])
    return t_open - run["end_to_end"]["setup_s"], t_open


def _cut(rows, run: dict) -> list:
    """(start, end) of each row, cut to set-up; rows outside it dropped."""
    t0, t_open = bounds(run)
    spans = [(max(r["start"], t0), min(r["start"] + r["dur"], t_open))
             for r in rows]
    return [(a, b) for a, b in spans if b > a]


def _union(spans) -> float:
    """Seconds covered by at least one of the intervals."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(spans):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def _of(rows, *names, pid=None) -> list:
    """The rows named `plane/kind` (of one process, where `pid` is given)."""
    return [r for r in rows
            if f'{r["plane"]}/{r["kind"]}' in names
            and (pid is None or r["pid"] == pid)]


def chip_pid(rows):
    """The process that holds the chip: the one that started a jax backend
    under the program's span (a replica, or the train worker)."""
    found = _of(rows, "proc/backend_init", "engine/init_params")
    return found[0]["pid"] if found else None


def _chip_rows(rows, *names) -> list:
    pid = chip_pid(rows)
    return [] if pid is None else _of(rows, *names, pid=pid)


def _path_pids(rows) -> set:
    """The chip's process and, in a serve cell, the controller's: the
    workers booted on the way to the first dispatch."""
    pids = {r["pid"] for r in _of(rows, "serve/replica_start")}
    return pids | ({chip_pid(rows)} - {None})


def cluster_boot_s(run: dict):
    rows = timeline(run)
    if rows is None:
        return None
    return _union(_cut(_of(rows, "proc/init"), run))


def worker_boot_s(run: dict):
    """Union of the lease waits, of hostd's `zygote_fork` and `worker_boot`
    for the processes on the path, and of those processes' own boots."""
    rows = timeline(run)
    if rows is None:
        return None
    path = _path_pids(rows)
    spawned = [r for r in _of(rows, "sched/zygote_fork", "sched/worker_boot")
               if (r["payload"] or {}).get("pid") in path]
    booted = [r for r in _of(rows, "proc/boot") if r["pid"] in path]
    return _union(_cut(_of(rows, "sched/lease_wait") + spawned + booted,
                       run))


def backend_init_s(run: dict):
    """`proc/jax_import` and `proc/backend_init` of the chip's process, and
    its `sched/arg_fetch` rows: where the model's configuration is an
    argument of the replica's constructor, unpickling it is what first
    imports jax, ahead of the span around the program's own import."""
    rows = timeline(run)
    if rows is None:
        return None
    return sum(b - a for a, b in _cut(_chip_rows(
        rows, "sched/arg_fetch", "proc/jax_import", "proc/backend_init"),
        run))


def weights_s(run: dict):
    rows = timeline(run)
    if rows is None:
        return None
    return sum(b - a for a, b in _cut(_chip_rows(
        rows, *(f"engine/{k}" for k in _WEIGHTS)), run))


def _programs(run: dict, rows) -> list:
    """The chip process's `proc/compile` rows that ended before the window
    opened (programs that cost 0.1 s and more: the others have no row)."""
    _, t_open = bounds(run)
    return [r for r in _chip_rows(rows, "proc/compile")
            if r["start"] + r["dur"] <= t_open]


def trace_lower_s(run: dict):
    """A serve cell: the replica's counters at the window's open.  The
    train cell (and a serve run whose `stats0` call failed): the worker's
    rows, since its counters go on through the reference check."""
    rows = timeline(run)
    if rows is None:
        return None
    counted = run.get("stats0", {}).get("compile", {})
    if "trace_s" in counted:
        return counted["trace_s"] + counted["lower_s"]
    return sum(r["payload"]["trace_s"] + r["payload"]["lower_s"]
               for r in _programs(run, rows))


def programs_compiled(run: dict):
    """XLA compiles that the persistent cache did not answer, before the
    window's open: 0 in a warm run."""
    rows = timeline(run)
    if rows is None:
        return None
    counted = run.get("stats0", {}).get("compile", {})
    if "trace_s" in counted:
        return counted["compiles"]
    return sum(not r["payload"]["cached"] for r in _programs(run, rows))


def first_runs_s(run: dict):
    """The `engine.dispatch/make_program` spans that began before the
    window opened, less what jax says making the program took."""
    rows = timeline(run)
    if rows is None:
        return None
    _, t_open = bounds(run)
    return sum(max(0.0, r["dur"] - sum(r["payload"][k] for k in _SPLIT))
               for r in _chip_rows(rows, "engine.dispatch/make_program")
               if r["start"] < t_open)


def named_by_the_benchmark(run: dict, rows) -> list:
    """The intervals of set-up that the benchmark's own clock names and the
    program cannot know.  A serve cell: the lead-in (`lead_in_s` + 0.2
    before the window's open) and, ending where it starts, the warm-up
    requests (`compile_s` long).  The train cell: the worker's loop from
    its `ready` report (`worker_ready_s` after `train/fit_start` opens) to
    the window's open: the benchmark's own code, which `init_s`,
    `compile_s`, `setup_backend_init_s` and `setup_trace_lower_s` break
    down."""
    _, t_open = bounds(run)
    if "base" in run:
        lead = t_open - (float(run["traffic"]["requests"]["lead_in_s"])
                         + 0.2)
        return [(lead - run["compile_s"], lead), (lead, t_open)]
    fit = _of(rows, "train/fit_start")
    if not fit:
        return []
    return [(fit[0]["start"] + run["worker_ready_s"], t_open)]


def unattributed_s(run: dict):
    """`setup_s` less the union, on the wall clock, of every row of every
    process and of what the benchmark's own clock names."""
    rows = timeline(run)
    if rows is None:
        return None
    t0, t_open = bounds(run)
    named = [(max(a, t0), min(b, t_open))
             for a, b in named_by_the_benchmark(run, rows)]
    return (t_open - t0) - _union(
        _cut(rows, run) + [(a, b) for a, b in named if b > a])
