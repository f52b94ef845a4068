"""Where JAX's persistent compilation cache lives, and what this process
compiled or loaded from it.

The directory is part of the cache key's lookup, so it must not move
between runs: it is wherever `JAX_COMPILATION_CACHE_DIR` points (JAX reads
that variable itself; nothing else is set in code), else `.jax_cache` in the
checkout.  Processes started afterwards inherit the choice through the
environment; hostd hands the same variable to TPU-leased workers.

`watch()` counts where jax itself reports.  A program is traced
(`/jax/core/compile/jaxpr_trace_duration`), lowered
(`.../jaxpr_to_mlir_module_duration`) and then compiled
(`.../backend_compile_duration`); one that the persistent cache answered
reports `/jax/compilation_cache/cache_retrieval_time_sec` just before the
last, all four on one thread and under one `fun_name`.  `counters()` gives
the sums and the same by program; each compile or load is also one
`proc/compile` event in the flight recorder with the trace and lower
seconds that led to it, so a compile in the middle of a serving window has
a timestamp, and one that cost `PIN_S` in all is a row of the start-up
record (`events.pin`) that the ring cannot lose.
"""

from __future__ import annotations

import collections
import os
import sys
import threading
import time

ENV = "JAX_COMPILATION_CACHE_DIR"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
PIN_S = 0.1             # a program this dear keeps its row
BY_PROGRAM = 64         # names in `by_program`; the rest under `_OTHER`
_OTHER = "(other)"

_counts = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
           "cache_load_s": 0.0, "trace_s": 0.0, "lower_s": 0.0,
           "programs": 0}
_by_program: dict = {}
_watching = False
_lock = threading.Lock()
# Per thread, between a program's first report and its compile event: the
# trace, lower and load seconds so far (a traced function that calls
# jitted ones reports theirs first: each compile event takes what is there).
_pending = threading.local()


def default_dir() -> str:
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def place() -> str:
    """Fix the cache directory for this process and its children; call
    before the first compile.  Returns the directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.environ[ENV] = default_dir()
    if "jax" in sys.modules:
        # jax read the variable when it was imported; tell the live config.
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


def entry_count(path: str) -> int:
    """Cached executables in `path` (0 if it does not exist yet)."""
    try:
        return sum(name.endswith("-cache") for name in os.listdir(path))
    except FileNotFoundError:
        return 0


def _row(fun: str) -> dict:
    """`by_program`'s row for `fun` (under `_lock`): jax names a program
    `step` where it traces it and `jit(step)` where it lowers and compiles
    it; the row is `step`'s."""
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]
    if fun not in _by_program and len(_by_program) >= BY_PROGRAM:
        fun = _OTHER
    return _by_program.setdefault(fun, {
        "n": 0, "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
        "cache_load_s": 0.0})


def _mine() -> dict:
    """This thread's pending parts (`_pending`), as a dict."""
    return _pending.__dict__


def _own_seconds(seconds: float) -> float:
    """`seconds` of a report that ends now, less the reports of this thread
    that ended inside it: tracing a function traces the jitted functions it
    calls and compiles what it runs eagerly, and each of those reports for
    itself first (a step of thirteen unscanned layer bodies holds hundreds).
    So the sums add up to wall seconds, not to more.  A report takes the
    place of those it held; what is left are the reports no other has held
    yet, kept for `_HELD_S`."""
    now = time.perf_counter()
    ended = _mine().setdefault("ended", collections.deque())
    inside = 0.0
    while ended and ended[-1][0] > now - seconds:
        inside += ended.pop()[1]
    while ended and ended[0][0] < now - _HELD_S:
        ended.popleft()
    ended.append((now, seconds))
    return max(0.0, seconds - inside)


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    part = _PARTS.get(event)
    if part is None:
        return
    fun = str(kwargs.get("fun_name", ""))
    mine = _mine()
    if part == "cache_load_s":
        # inside the compile-or-load that jax reports next, as one
        mine["cache_load_s"] = seconds
        with _lock:
            _counts["cache_hits"] += 1
            _counts["cache_load_s"] += seconds
        return
    if part != "compile_s":
        seconds = _own_seconds(seconds)
        mine[part] = mine.get(part, 0.0) + seconds
        with _lock:
            _counts[part] += seconds
            _counts["programs"] += part == "lower_s"
            _row(fun)[part] += seconds
        return
    _own_seconds(seconds)
    cached = "cache_load_s" in mine
    led = {p: mine.pop(p, 0.0)
           for p in ("trace_s", "lower_s", "cache_load_s")}
    with _lock:
        row = _row(fun)
        row["n"] += 1
        if cached:
            row["cache_load_s"] += led["cache_load_s"]
        else:
            _counts["compiles"] += 1
            _counts["compile_s"] += seconds
            row["compile_s"] += seconds
    from ray_tpu.util import events
    payload = dict(seconds=seconds, cached=cached, fun=fun,
                   trace_s=led["trace_s"], lower_s=led["lower_s"])
    events.record("proc", "compile", **payload)
    took = led["trace_s"] + led["lower_s"] + seconds
    if took >= PIN_S:
        events.pin("proc", "compile", time.time() - took, took,
                   payload=payload)


_PARTS = {_TRACE: "trace_s", _LOWER: "lower_s", _CACHE_LOAD: "cache_load_s",
          _COMPILE: "compile_s"}
_HELD_S = 3600.0     # no report is longer: older ones are let go


def watch() -> None:
    """Count this process's compiles and cache loads from now on (once per
    process; later calls do nothing).  Imports jax."""
    global _watching
    with _lock:
        if _watching:
            return
        _watching = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)


def sums() -> dict:
    """`counters()` less `by_program`: seven numbers, for a difference."""
    with _lock:
        return dict(_counts)


def counters() -> dict:
    """Since `watch()`: {compiles, compile_s} programs XLA built here,
    {cache_hits, cache_load_s} programs the persistent cache supplied,
    {programs, trace_s, lower_s} programs jax traced and lowered to ask
    for either, and `by_program`: {fun_name: {n, trace_s, lower_s,
    compile_s, cache_load_s}} for the first `BY_PROGRAM` names, the rest
    summed under "(other)"."""
    with _lock:
        return dict(_counts, by_program={
            fun: dict(row) for fun, row in _by_program.items()})
