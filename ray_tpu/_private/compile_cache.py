"""Where JAX's persistent compilation cache lives.

The directory is part of the cache key's lookup, so it must not move
between runs: it is wherever `JAX_COMPILATION_CACHE_DIR` points (JAX reads
that variable itself; nothing else is set in code), else `.jax_cache` in the
checkout.  Processes started afterwards inherit the choice through the
environment; hostd hands the same variable to TPU-leased workers.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"


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
