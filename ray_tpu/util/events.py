"""Flight recorder: always-on per-process ring buffer of runtime events.

Reference parity: Ray's EventManager / export-event path
(src/ray/util/event.h, python/ray/_private/event/event_logger.py) records
structured per-component events to files; the debugging story here follows
an aircraft flight recorder instead — every plane (scheduler, object
store, engine, serve, checkpoint, ingest, train) appends decision events
to a fixed-size in-memory ring, and the ring is

  * dumped atomically to ``<logs>/flightrec-<pid>-<incarnation>.jsonl``
    on crash, SIGTERM, chaos kill, and fatal error (the black box),
  * scrapeable live over the hostd/CoreWorker ``CollectEvents`` RPC
    (``state.events()`` aggregates cluster-wide, normalizes clock skew,
    and joins by trace id),
  * mergeable into the Chrome task timeline (``cli timeline --events``).

The append fast path is lock-free-ish: slot allocation is one
``next(itertools.count())`` (a single C call, atomic under the GIL and
safe from signal handlers — no bytecode boundary splits it) plus one
list-item store.  Overflow overwrites the oldest slot; ``snapshot()``
reorders by the monotonic sequence number each event carries.  With
``RAY_TPU_EVENTS=0`` the whole module collapses to one global read per
``record()`` call.

Beside the ring lies the **start-up record**: at most ``PIN_ROWS`` rows
that nothing overwrites, one per span closed with ``pin=True``
(`spans.begin`) and one per program that cost 0.1 s to make
(`compile_cache`).  A process's set-up happens once and the ring turns
over within seconds of serving, so what set-up was made of is kept here:
``pinned()`` gives the rows with the process's pid, role and start time,
``dump()`` writes them ahead of the ring, and
``state.startup_timeline()`` merges every process's.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.util import tracing

# Planes (the `plane` field of every event).  Free-form strings are
# accepted; these constants document the instrumented set.
PLANES = ("sched", "object", "engine", "serve", "ckpt", "ingest", "train",
          "proc", "gcs", "pp", "link", "kv", "rl")


class FlightRecorder:
    """Fixed-capacity ring of ``(ts, plane, kind, trace, payload, seq)``
    tuples.  ``append`` is re-entrant (signal handlers included): the
    sequence counter is a C-level ``itertools.count`` and the slot store
    is a single list assignment, so interleaved appenders race only for
    distinct slots."""

    def __init__(self, capacity: int = 4096):
        self.capacity = max(16, int(capacity))
        self._buf: List[Optional[tuple]] = [None] * self.capacity
        self._seq = itertools.count()

    def append(self, plane: str, kind: str,
               payload: Optional[Dict[str, Any]] = None,
               trace: Optional[Tuple[str, str]] = None) -> None:
        if trace is None:
            trace = tracing.current_context()
        i = next(self._seq)
        self._buf[i % self.capacity] = (
            time.time(), plane, kind, trace, payload, i)

    # -- read side (slow path: snapshots copy the ring) -------------------

    def snapshot(self, since: float = 0.0, plane: Optional[str] = None,
                 kind: Optional[str] = None) -> List[Dict[str, Any]]:
        """Events currently in the ring, oldest first, as dicts."""
        raw = [e for e in list(self._buf) if e is not None]
        raw.sort(key=lambda e: e[5])
        out = []
        for ts, pl, kd, trace, payload, seq in raw:
            if ts < since:
                continue
            if plane is not None and pl != plane:
                continue
            if kind is not None and kd != kind:
                continue
            out.append({
                "ts": ts, "plane": pl, "kind": kd,
                "trace_id": trace[0] if trace else None,
                "span_id": trace[1] if trace else None,
                "payload": payload, "seq": seq,
            })
        return out

    def tail(self, n: int = 50) -> List[Dict[str, Any]]:
        return self.snapshot()[-n:]

    def __len__(self) -> int:
        return sum(1 for e in self._buf if e is not None)


# ---------------------------------------------------------------------------
# Process-global recorder
# ---------------------------------------------------------------------------

PIN_ROWS = 256
# What this process is, for the readers of its start-up record: "driver"
# unless its entry point says otherwise ("worker", "hostd", "gcs").
role = "driver"
_pinned: List[Dict[str, Any]] = []
_T_IMPORT = time.time()

_recorder: Optional[FlightRecorder] = None
_initialized = False
_init_lock = threading.Lock()


def _init() -> Optional[FlightRecorder]:
    global _recorder, _initialized
    with _init_lock:
        if _initialized:
            return _recorder
        from ray_tpu._private.config import GLOBAL_CONFIG
        if GLOBAL_CONFIG.events:
            _recorder = FlightRecorder(GLOBAL_CONFIG.events_ring_size)
        else:
            _recorder = None
        _initialized = True
        return _recorder


def record(plane: str, kind: str,
           trace: Optional[Tuple[str, str]] = None, **payload) -> None:
    """Append one event.  The disabled fast path is a global read; the
    enabled fast path is a dict build + ring append (< 5 µs, see
    `events_append` of scripts/microbench.py)."""
    r = _recorder
    if r is None:
        if _initialized:
            return
        r = _init()
        if r is None:
            return
    r.append(plane, kind, payload or None, trace)


def pin(plane: str, kind: str, start: float, dur: float,
        sid: Optional[str] = None, parent: Optional[str] = None,
        trace_id: Optional[str] = None,
        payload: Optional[Dict[str, Any]] = None) -> None:
    """Keep one closed interval in the start-up record: `start` on
    ``time.time()``, `dur` in seconds.  The first ``PIN_ROWS`` rows stay
    and later ones are dropped (set-up comes first; nothing is ever
    overwritten).  Nothing is kept while the recorder is off."""
    if len(_pinned) < PIN_ROWS and enabled():
        _pinned.append({"plane": plane, "kind": kind, "start": start,
                        "dur": dur, "sid": sid, "parent": parent,
                        "trace_id": trace_id, "payload": payload})


def _process_start() -> float:
    """When this process began, on ``time.time()``: the kernel's start
    time of the process (field 22 of ``/proc/self/stat``, in clock ticks
    since boot, against ``CLOCK_BOOTTIME``), which for a worker forked
    from the zygote is the instant of its fork and for any other the
    instant of its exec: one file read, no argument handed down.  Where
    /proc cannot be read, the instant this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
        if age >= 0.0:
            return time.time() - age
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return _T_IMPORT


def pinned() -> Dict[str, Any]:
    """This process's start-up record: ``{"pid", "role", "start",
    "rows"}``, each row ``{plane, kind, start, dur, sid, parent, trace_id,
    payload}`` in the order the intervals closed."""
    return {"pid": os.getpid(), "role": role, "start": _process_start(),
            "rows": [dict(row) for row in list(_pinned)]}


def enabled() -> bool:
    if not _initialized:
        _init()
    return _recorder is not None


def get_recorder() -> Optional[FlightRecorder]:
    if not _initialized:
        _init()
    return _recorder


def snapshot(since: float = 0.0, plane: Optional[str] = None,
             kind: Optional[str] = None) -> List[Dict[str, Any]]:
    r = get_recorder()
    return r.snapshot(since, plane, kind) if r is not None else []


def tail(n: int = 50) -> List[Dict[str, Any]]:
    r = get_recorder()
    return r.tail(n) if r is not None else []


def reset() -> None:
    """Drop the process recorder (tests flip config flags between
    scenarios; the next record()/get_recorder() re-reads config)."""
    global _recorder, _initialized
    with _init_lock:
        _recorder = None
        _initialized = False
        del _pinned[:]


# ---------------------------------------------------------------------------
# Crash dumps (the black box)
# ---------------------------------------------------------------------------


def _dump_dir() -> str:
    # The env var wins over the (cached) config flag: hostd points itself
    # and every child at <session>/logs after the config may already have
    # been read in this process.
    d = os.environ.get("RAY_TPU_FLIGHTREC_DIR", "")
    if not d:
        try:
            from ray_tpu._private.config import GLOBAL_CONFIG
            d = GLOBAL_CONFIG.flightrec_dir
        except Exception:
            d = ""
    return d or os.path.join("/tmp", "ray_tpu", "flightrec")


def _incarnation() -> str:
    return os.environ.get("RAY_TPU_CHAOS_PROC_SALT") or "0"


def dump(path: str, reason: str = "") -> Optional[str]:
    """Write the start-up record and then the ring to `path` as jsonl,
    atomically (tmp + fsync + rename): a reader either sees the whole
    dump or no file.  Returns the path, or None when the recorder is
    off/empty."""
    events = snapshot()
    record = pinned()
    if not events and not record["rows"]:
        return None
    header = {"_flightrec": 1, "pid": os.getpid(),
              "incarnation": _incarnation(), "reason": reason,
              "wall_time": time.time(), "role": role,
              "start": record["start"]}
    # (two threads of a dying process may both dump: a file each)
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            f.write(json.dumps(header) + "\n")
            for row in record["rows"]:
                f.write(json.dumps(dict(row, pinned=True), default=repr)
                        + "\n")
            for e in events:
                f.write(json.dumps(e, default=repr) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def dump_crash(reason: str) -> Optional[str]:
    """The black-box write: called from kill paths (chaos kills, SIGTERM,
    fatal errors, daemon teardown) right before the process dies.  Never
    raises — a failed forensics write must not mask the real exit."""
    try:
        record("proc", "crash_dump", reason=reason)
        path = os.path.join(
            _dump_dir(), f"flightrec-{os.getpid()}-{_incarnation()}.jsonl")
        return dump(path, reason)
    except Exception:
        return None


def dumped_records(dumped: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The start-up records (as `pinned()` gives them) of the processes
    whose dumps `read_dumps` read into `dumped`."""
    records: Dict[Any, Dict[str, Any]] = {}
    for e in dumped:
        if e.get("pinned"):
            records.setdefault(e["pid"], {
                "pid": e["pid"], "role": e["role"],
                "start": e["proc_start"], "rows": []},
            )["rows"].append(e["pinned"])
    return list(records.values())


def read_dumps(directory: str,
               pinned_only: bool = False) -> List[Dict[str, Any]]:
    """Parse every flightrec-*.jsonl in `directory`; each event gains
    ``pid``, ``source="crash"``, and the dump's ``reason``.  A row of
    the start-up record comes back under an event's keys (``ts`` its
    start, ``span_id`` its sid, no ``seq``) with the row itself as
    ``pinned`` and the process's ``role`` and ``proc_start`` beside it;
    with `pinned_only` a file is read no further than those rows, which
    stand at its head.  Corrupt or half-written files are skipped (dumps
    are atomic, but the directory may hold unrelated debris)."""
    out: List[Dict[str, Any]] = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return out
    for name in names:
        if not (name.startswith("flightrec-") and name.endswith(".jsonl")):
            continue
        try:
            with open(os.path.join(directory, name)) as f:
                header = json.loads(next(f, "") or "{}")
                if header.get("_flightrec") != 1:
                    continue
                for line in f:
                    e = json.loads(line)
                    if e.pop("pinned", False):
                        e = {"ts": e["start"], "plane": e["plane"],
                             "kind": e["kind"], "trace_id": e["trace_id"],
                             "span_id": e["sid"], "payload": e["payload"],
                             "seq": None, "pinned": e,
                             "role": header.get("role"),
                             "proc_start": header.get("start")}
                    elif pinned_only:
                        break
                    e["pid"] = header.get("pid")
                    e["source"] = "crash"
                    e["reason"] = header.get("reason")
                    out.append(e)
        except Exception:
            continue
    return out
