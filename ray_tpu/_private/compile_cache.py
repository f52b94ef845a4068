"""Where JAX's persistent compilation cache lives, and what this process
compiled or loaded from it.

The directory is part of the cache key's lookup, so it must not move
between runs: it is wherever `JAX_COMPILATION_CACHE_DIR` points (JAX reads
that variable itself; nothing else is set in code), else `.jax_cache` in the
checkout.  Processes started afterwards inherit the choice through the
environment; hostd hands the same variable to TPU-leased workers.

`watch()` counts where jax itself reports: every program the process
compiles goes through `/jax/core/compile/backend_compile_duration`, and one
that the persistent cache answered reports
`/jax/compilation_cache/cache_retrieval_time_sec` just before, on the same
thread.  `counters()` gives the sums; each compile or load is also one
`proc/compile` event in the flight recorder, so a compile in the middle of
a serving window has a timestamp.
"""

from __future__ import annotations

import os
import sys
import threading

ENV = "JAX_COMPILATION_CACHE_DIR"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

_counts = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
           "cache_load_s": 0.0}
_watching = False
_lock = threading.Lock()
_loaded = threading.local()    # set between a cache load and its compile event


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


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    if event == _CACHE_LOAD:
        _loaded.hit = True
        with _lock:
            _counts["cache_hits"] += 1
            _counts["cache_load_s"] += seconds
        return
    if event != _COMPILE:
        return
    # jax times compile-or-load as one: a load reported itself just before.
    cached = getattr(_loaded, "hit", False)
    _loaded.hit = False
    if not cached:
        with _lock:
            _counts["compiles"] += 1
            _counts["compile_s"] += seconds
    from ray_tpu.util import events
    events.record("proc", "compile", seconds=seconds, cached=cached,
                  fun=str(kwargs.get("fun_name", "")))


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


def counters() -> dict:
    """{compiles, compile_s, cache_hits, cache_load_s} since `watch()`:
    programs XLA built here, and programs the persistent cache supplied."""
    with _lock:
        return dict(_counts)
