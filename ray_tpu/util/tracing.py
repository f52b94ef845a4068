"""Distributed trace-context propagation across task/actor boundaries.

Reference parity: python/ray/util/tracing/tracing_helper.py:87 — the
reference injects the OpenTelemetry context into a reserved field of every
task/actor call at SUBMIT time and extracts it at EXECUTE time, so spans
from a driver and all its transitive tasks share one trace.  This build
carries the same (trace_id, span_id) pair in `TaskSpec.trace_ctx`, keeps
it in a contextvar inside executing tasks (nested submits propagate
automatically), and stamps every task timeline event with
trace_id/span_id/parent_id — the timeline IS the span store, so
`state.timeline()` / the Chrome trace groups a whole trace without an
external collector.

The switch is the `trace()` scope itself: outside any active trace the
context is None, submission attaches nothing, and execution skips span
bookkeeping — a contextvar read per submit is the entire idle cost.
A worker that receives a carried context always forwards it (its own
processes never need configuring).

Usage:
    from ray_tpu.util import tracing
    with tracing.trace("my-request"):
        ray_tpu.get(f.remote())   # f's span joins "my-request"'s trace
"""

from __future__ import annotations

import contextlib
import contextvars
import secrets
from typing import Optional, Tuple

# (trace_id_hex, span_id_hex) of the CURRENT span in this context.
_ctx: contextvars.ContextVar[Optional[Tuple[str, str]]] = \
    contextvars.ContextVar("ray_tpu_trace_ctx", default=None)


def current_context() -> Optional[Tuple[str, str]]:
    """(trace_id, span_id) to inject into an outgoing task spec, or None
    when no trace is active in this context."""
    return _ctx.get()


@contextlib.contextmanager
def trace(name: str = "trace"):
    """Open (or continue) a trace in this context; tasks submitted inside
    join it as child spans.  The scope itself is recorded as the trace's
    root span (a B/E pair on the flight recorder), so `state.spans()`
    reconstructs a rooted tree with the user's name on top."""
    parent = _ctx.get()
    if parent is None:
        trace_id = secrets.token_hex(8)
    else:
        trace_id = parent[0]
    sid = secrets.token_hex(4)
    token = _ctx.set((trace_id, sid))
    from ray_tpu.util import spans  # late: spans imports this module
    tok = spans.begin("proc", "trace",
                      ctx=(trace_id, parent[1] if parent else None),
                      sid=sid, name=name)
    try:
        yield trace_id
    finally:
        _ctx.reset(token)
        spans.end(tok)


@contextlib.contextmanager
def untraced():
    """Leave the active trace for a scope: what is submitted inside joins
    none.  For a fire-and-forget task that outlives the caller's trace (a
    control loop started from inside a traced call would otherwise trace
    every task it ever submits)."""
    token = _ctx.set(None)
    try:
        yield
    finally:
        _ctx.reset(token)


def enter_task(spec) -> Optional[Tuple[str, str, str]]:
    """Called by the worker when a task starts executing.  Installs the
    propagated context (so the task's own submissions become children) and
    returns (trace_id, span_id, parent_span_id) for the timeline event —
    or None when the spec carries no context."""
    carried = getattr(spec, "trace_ctx", None)
    if carried is None:
        return None
    trace_id, parent_span = carried
    span_id = secrets.token_hex(4)
    _ctx.set((trace_id, span_id))
    return trace_id, span_id, parent_span


def exit_task() -> None:
    _ctx.set(None)
