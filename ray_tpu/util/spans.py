"""Durational spans layered on the flight recorder.

PR 10 gave every plane an *instant* event ring (`events.record`); this
module turns pairs of those events into **spans** — named intervals with
parent links — without changing the ring's cost model: a span is exactly
two ring slots (a ``ph="B"`` begin and a ``ph="E"`` end carrying the
duration), appended through the same signal-safe fast path.  With
``RAY_TPU_EVENTS=0`` the whole module collapses to one global read per
``begin()`` and ``end()`` returns immediately on the ``None`` token.

Wire format (what ``state.spans()`` reconstructs from):

  B:  (ts, plane, kind, (trace_id, sid), {"ph": "B", "parent": psid, ...})
  E:  (ts, plane, kind, (trace_id, sid), {"ph": "E", "dur": seconds, ...})

``sid`` is cluster-unique (a per-process random prefix plus a local
counter), so begin/end pair by span id alone even after crash dumps from
several processes are merged into one stream.  ``trace_id`` may be None:
such spans never join a trace tree but still feed
``state.latency_breakdown()`` aggregates.

Pairing is structural, not by name: ``end()`` takes the token ``begin()``
returned, so a begin can never be closed with a mismatched kind, and a
token can cross threads or asyncio callbacks (scheduler-queue and
dispatch spans ride on the pending-task object between the submitting
thread and the io loop).

Usage:
    tok = spans.begin("sched", "lease_wait", key=key)   # may return None
    ...
    spans.end(tok, granted=True)

    with spans.span("ingest", "h2d"):        # context form; nested spans
        device_put(batch)                    # become children via tracing

``pin=True`` on either form keeps the closed span in the process's
start-up record too (`events.pin`: one row with its start, duration, ids
and both payloads, which the ring's turning over cannot lose).  It is for
what happens once between a process's start and its first dispatch.  A
pinned span always belongs to a trace (it opens one where none is
active), so that what it causes in other processes hangs off it.

A **phase** is the third form, for intervals too frequent for two ring
slots (the engine's step has five, a dozen times a second):

    with spans.phase("engine", "fetch") as ph:
        toks = np.asarray(next_tok)
    fetch_s += ph.seconds

It writes nothing to the ring.  The caller gets the elapsed seconds and
folds them into one record of its own; the interval itself goes, as
``engine/fetch``, into the jax profiler's trace when a session is open
(`jax.profiler.TraceAnnotation`: a flag test in C++ when none is), where
it lies on the device's clock beside the device's own operations.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import secrets
import sys
import time
from typing import Any, Optional, Tuple

from ray_tpu.util import events, tracing

# Cluster-unique span ids: 3 random bytes of per-process prefix + local
# counter.  Distinct from tracing's token_hex(4) task span ids on
# purpose — a prefix collision between two processes would need ~2^12
# concurrent processes (birthday bound on 2^24).
_PREFIX = secrets.token_hex(3)
_SEQ = itertools.count()


def _new_prefix() -> None:
    global _PREFIX
    _PREFIX = secrets.token_hex(3)


# A worker forked from the zygote must not mint its template's ids.
os.register_at_fork(after_in_child=_new_prefix)


class Span:
    """Token returned by :func:`begin`; pass it to :func:`end`."""

    __slots__ = ("plane", "kind", "trace_id", "sid", "t0", "pin")

    def __init__(self, plane: str, kind: str, trace_id: Optional[str],
                 sid: str, t0: float, pin: Optional[tuple] = None):
        self.plane = plane
        self.kind = kind
        self.trace_id = trace_id
        self.sid = sid
        self.t0 = t0
        self.pin = pin      # (start on time.time(), parent, begin payload)

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"Span({self.plane}:{self.kind} sid={self.sid})"


def new_sid() -> str:
    """A span id ahead of its span (`begin(sid=...)`), for a parent that
    must name itself to a child before it can open: hostd hands a worker
    its `sched/worker_boot` id through the child's environment."""
    return f"{_PREFIX}{next(_SEQ):x}"


def begin(plane: str, kind: str,
          ctx: Optional[Tuple[Optional[str], Optional[str]]] = None,
          sid: Optional[str] = None, parent: Optional[str] = None,
          pin: bool = False, **payload: Any) -> Optional[Span]:
    """Open a span.  Returns None when the recorder is off (the disabled
    fast path is one global read, same as ``events.record``).

    ``ctx`` is an explicit (trace_id, parent_span_id) — e.g. a task
    spec's carried ``trace_ctx`` — and defaults to the calling context's
    active trace.  ``sid`` pins the span id (used when another layer,
    like ``tracing.enter_task``, already minted the id that children
    will reference as their parent)."""
    r = events._recorder
    if r is None:
        if events._initialized:
            return None
        r = events._init()
        if r is None:
            return None
    if ctx is None:
        ctx = tracing.current_context()
    trace_id = ctx[0] if ctx else None
    if parent is None and ctx is not None:
        parent = ctx[1]
    if pin and trace_id is None:
        trace_id = secrets.token_hex(8)
    s = sid or new_sid()
    p: dict = {"ph": "B"}
    if parent is not None:
        p["parent"] = parent
    if payload:
        p.update(payload)
    r.append(plane, kind, p, (trace_id, s))
    return Span(plane, kind, trace_id, s, time.perf_counter(),
                (time.time(), parent, payload) if pin else None)


def end(tok: Optional[Span], **payload: Any) -> None:
    """Close a span.  No-op on a None token (recorder was off at begin)
    or when the recorder has been reset since."""
    if tok is None:
        return
    r = events._recorder
    if r is None:
        return
    dur = time.perf_counter() - tok.t0
    p: dict = {"ph": "E", "dur": dur}
    if payload:
        p.update(payload)
    r.append(tok.plane, tok.kind, p, (tok.trace_id, tok.sid))
    if tok.pin is not None:
        start, parent, began = tok.pin
        events.pin(tok.plane, tok.kind, start, dur, tok.sid, parent,
                   tok.trace_id, {**began, **payload} or None)


@contextlib.contextmanager
def under(tok: Optional[Span]):
    """Make an open span the active trace context (when it belongs to a
    trace): spans begun inside, and tasks submitted inside, attach to it
    as children.  The scope of `span`, for a span held as a token."""
    cv = None
    if tok is not None and tok.trace_id is not None:
        cv = tracing._ctx.set((tok.trace_id, tok.sid))
    try:
        yield tok
    finally:
        if cv is not None:
            tracing._ctx.reset(cv)


@contextlib.contextmanager
def span(plane: str, kind: str,
         ctx: Optional[Tuple[Optional[str], Optional[str]]] = None,
         pin: bool = False, **payload: Any):
    """Context-manager form.  While open, the span becomes the active
    trace context (when it belongs to a trace), so nested spans and any
    tasks submitted inside attach to it as children."""
    tok = begin(plane, kind, ctx=ctx, pin=pin, **payload)
    try:
        with under(tok):
            yield tok
    finally:
        end(tok)


class phase:
    """Context manager around one phase of a hot loop: ``seconds`` (and
    ``t0``, both on ``time.perf_counter``) for the caller, a
    ``<plane>/<kind>`` annotation for the jax profiler, nothing for the
    ring.  Processes that never imported jax (the control-plane daemons)
    get the clock alone and do not import it here."""

    __slots__ = ("_ann", "t0", "seconds")

    def __init__(self, plane: str, kind: str):
        jax = sys.modules.get("jax")
        self._ann = (jax.profiler.TraceAnnotation(f"{plane}/{kind}")
                     if jax is not None else None)

    def __enter__(self) -> "phase":
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
