"""State observability API: list live cluster entities.

Reference parity: python/ray/experimental/state/api.py (list_actors,
list_nodes, list_placement_groups, list_workers, list_objects,
summarize_*) backed by dashboard/state_aggregator.py over GCS tables.
Here the GCS tables and per-node daemons are queried directly; works both
inside a connected driver (address=None) and standalone against a GCS
address (the CLI's mode).
"""

from __future__ import annotations

import asyncio
import logging
from typing import Any, Dict, List, Optional

_logger = logging.getLogger("ray_tpu.state")


def _run(coro):
    from ray_tpu import api
    if api._worker is not None:
        return api._worker.io.run(coro)
    return asyncio.run(coro)


def _gcs_address(address: Optional[str]) -> str:
    if address:
        return address
    from ray_tpu import api
    if api._worker is not None:
        return api._worker.gcs_address
    raise RuntimeError(
        "not connected: pass address= or call ray_tpu.init() first")


async def _gcs_call(address: str, method: str, req: dict | None = None):
    from ray_tpu._private.rpc import RpcClient
    from ray_tpu import api
    if api._worker is not None and address == api._worker.gcs_address:
        return await api._worker.gcs.call("Gcs", method, req or {})
    client = RpcClient(address)
    try:
        return await client.call("Gcs", method, req or {}, timeout=30)
    finally:
        await client.close()


def list_nodes(address: Optional[str] = None) -> List[Dict[str, Any]]:
    addr = _gcs_address(address)
    reply = _run(_gcs_call(addr, "get_nodes"))
    return [{
        "node_id": n.node_id.hex(),
        "address": n.address,
        "alive": n.alive,
        "is_head": n.is_head,
        "resources_total": dict(n.resources_total),
        "resources_available": dict(n.resources_available),
    } for n in reply["nodes"]]


def list_actors(address: Optional[str] = None) -> List[Dict[str, Any]]:
    addr = _gcs_address(address)
    reply = _run(_gcs_call(addr, "list_actors"))
    out = []
    for a in reply["actors"]:
        out.append({
            "actor_id": a.actor_id.hex(),
            "class_name": a.class_name,
            "state": a.state,
            "name": a.name or None,
            "namespace": a.namespace or None,
            "node_id": a.node_id.hex() if a.node_id else None,
            "num_restarts": a.num_restarts,
            "death_cause": a.death_cause or None,
        })
    return out


def list_placement_groups(address: Optional[str] = None
                          ) -> List[Dict[str, Any]]:
    addr = _gcs_address(address)
    reply = _run(_gcs_call(addr, "list_placement_groups"))
    return [{
        "placement_group_id": p.pg_id.hex(),
        "state": p.state,
        "strategy": p.strategy,
        "bundles": list(p.bundles),
        "bundle_nodes": [n.hex() if n else None for n in p.bundle_nodes],
    } for p in reply["placement_groups"]]


async def _each_node(address: str, service: str, method: str,
                     req: dict | None = None) -> Dict[str, Any]:
    from ray_tpu._private.rpc import RpcClient
    nodes = (await _gcs_call(address, "get_nodes"))["nodes"]
    out = {}
    for n in nodes:
        if not n.alive:
            continue
        client = RpcClient(n.address)
        try:
            out[n.node_id.hex()] = await client.call(
                service, method, req or {}, timeout=10)
        except Exception as e:
            out[n.node_id.hex()] = {"error": repr(e)}
        finally:
            await client.close()
    return out


def list_workers(address: Optional[str] = None) -> List[Dict[str, Any]]:
    """Worker processes across every alive node."""
    addr = _gcs_address(address)
    per_node = _run(_each_node(addr, "NodeManager", "ListWorkers"))
    out = []
    for node_id, reply in per_node.items():
        for w in reply.get("workers", []):
            out.append({"node_id": node_id, **w})
    return out


def list_objects(address: Optional[str] = None) -> List[Dict[str, Any]]:
    """Object-store summary per node (per-object enumeration requires the
    owner's table; the connected driver's own objects are included)."""
    addr = _gcs_address(address)
    per_node = _run(_each_node(addr, "NodeManager", "StoreStats"))
    out = [{"node_id": nid, **stats} for nid, stats in per_node.items()]
    from ray_tpu import api
    if api._worker is not None:
        w = api._worker
        for oid, st in list(w.objects.items()):
            out.append({
                "object_id": oid.hex(), "owner": "self",
                "pending": st.pending, "pins": st.pins,
                "local_refs": st.local_refs,
                "locations": [l.hex() if hasattr(l, "hex") else str(l)
                              for l in st.locations],
            })
    return out


def list_tasks(address: Optional[str] = None,
               limit: int = 10000) -> List[Dict[str, Any]]:
    """Recently executed tasks from the GCS task-event sink (reference:
    experimental/state/api.py list_tasks over task events)."""
    addr = _gcs_address(address)
    reply = _run(_gcs_call(addr, "get_task_events", {"limit": limit}))
    return list(reply.get("events", []))


# A node's clock may be ahead of the caller's by up to this much without
# its fresh events being pre-filtered away at the remote ring.  The raw
# `since` forwarded to each node is widened by this slack — it is only a
# bandwidth optimization; the authoritative cutoff is applied locally on
# the skew-adjusted ts_adj.
_SKEW_SLACK_S = 300.0


def _clock_offset(reply: Dict[str, Any], t0: float, t1: float) -> float:
    """What to add to a remote timestamp to put it on the caller's clock,
    from one call sent at `t0` and answered at `t1`: the remote stamped
    `recv` when the call arrived and `now` when it answered (NTP's four
    instants; a reply with `now` alone is taken to have been handled in
    no time, at the call's midpoint)."""
    mid = (t0 + t1) / 2.0
    now = reply.get("now", mid)
    return mid - (reply.get("recv", now) + now) / 2.0


def _normalize_events_reply(reply: Dict[str, Any], node_id: str,
                            t0: float, t1: float) -> List[Dict[str, Any]]:
    """Put one node's CollectEvents reply on the caller's clock.

    ``ts_adj = ts + offset`` with `_clock_offset`'s estimate (NTP-grade,
    good enough to order cross-node decision sequences)."""
    offset = _clock_offset(reply, t0, t1)
    out = []
    for e in reply.get("events", []):
        e = dict(e)
        e["node_id"] = node_id
        e["ts_adj"] = e["ts"] + offset
        out.append(e)
    return out


def _merge_event_streams(streams: List[List[Dict[str, Any]]], *,
                         plane: Optional[str] = None,
                         kind: Optional[str] = None,
                         trace_id: Optional[str] = None,
                         since: float = 0.0) -> List[Dict[str, Any]]:
    """Pure merge of already-normalized per-process event streams:
    dedup by (pid, seq) preferring live copies over crash-dump copies
    of the same event, apply every filter AFTER normalization (`since`
    compares ts_adj, never the raw per-process ts), order by ts_adj."""
    best: Dict[tuple, Dict[str, Any]] = {}
    extra: List[Dict[str, Any]] = []
    for stream in streams:
        for e in stream:
            key = (e.get("pid"), e.get("seq"))
            if key[0] is None or key[1] is None:
                extra.append(e)
                continue
            cur = best.get(key)
            if cur is None or (cur.get("source") == "crash"
                               and e.get("source") != "crash"):
                best[key] = e
    evs = list(best.values()) + extra
    evs = [e for e in evs
           if e.get("ts_adj", e["ts"]) >= since
           and (plane is None or e.get("plane") == plane)
           and (kind is None or e.get("kind") == kind)
           and (trace_id is None or e.get("trace_id") == trace_id)]
    evs.sort(key=lambda e: (e.get("ts_adj", e["ts"]),
                            str(e.get("pid")), e.get("seq") or 0))
    return evs


def events(address: Optional[str] = None, *, plane: Optional[str] = None,
           kind: Optional[str] = None, trace_id: Optional[str] = None,
           since: float = 0.0) -> List[Dict[str, Any]]:
    """Cluster-wide flight-recorder aggregation: every node's
    CollectEvents scrape (the hostd ring + live worker rings + crash
    dumps from dead processes) plus the connected driver's own ring,
    time-skew normalized and merged into one ordered stream.

    Filter semantics: `since` (like the ordering) applies to the
    skew-adjusted ``ts_adj`` after the merge — a node whose clock runs
    behind the caller's cannot leak stale events past the cutoff, and
    one running ahead cannot hide fresh ones.  The remote rings are
    pre-filtered with a widened window (`_SKEW_SLACK_S`) purely to
    bound reply size."""
    import os
    import time as _time

    addr = _gcs_address(address)
    pre_since = max(0.0, since - _SKEW_SLACK_S)

    async def _collect():
        from ray_tpu._private.rpc import RpcClient
        nodes = (await _gcs_call(addr, "get_nodes"))["nodes"]
        streams: List[List[Dict[str, Any]]] = []
        for n in nodes:
            if not n.alive:
                continue
            client = RpcClient(n.address)
            try:
                t0 = _time.time()
                reply = await client.call(
                    "NodeManager", "CollectEvents", {"since": pre_since},
                    timeout=10)
                t1 = _time.time()
            except Exception:
                continue
            finally:
                await client.close()
            streams.append(_normalize_events_reply(
                reply, n.node_id.hex(), t0, t1))
        # The GCS runs in its own process with its own ring (gcs/flush
        # spans, actor-manager events) that no hostd scrapes.
        client = RpcClient(addr)
        try:
            t0 = _time.time()
            reply = await client.call("Gcs", "collect_events",
                                      {"since": pre_since}, timeout=10)
            t1 = _time.time()
            streams.append(_normalize_events_reply(reply, "gcs", t0, t1))
        except Exception:
            pass
        finally:
            await client.close()
        return streams

    streams = _run(_collect())
    # The caller's own ring: serve routers and train drivers record from
    # the driver process, which no hostd scrapes.  The driver's clock IS
    # the reference clock, so ts_adj == ts.
    from ray_tpu import api
    from ray_tpu.util import events as ev
    # Included whenever this process is connected — even with an explicit
    # address (the in-process CLI path): the driver ring holds the
    # submit-side spans no hostd can see.
    if api._worker is not None:
        driver_pid = os.getpid()
        streams.append([
            dict(e, pid=driver_pid, source="live", node_id="driver",
                 ts_adj=e["ts"])
            for e in ev.snapshot(since=pre_since)])
    return _merge_event_streams(streams, plane=plane, kind=kind,
                                trace_id=trace_id, since=since)


# ---------------------------------------------------------------------------
# The start-up timeline: every process's start-up record, merged
# ---------------------------------------------------------------------------

_last_startup_records: list = []      # of the last session (`shutdown`)


def _timeline_rows(records) -> List[Dict[str, Any]]:
    """(node_id, clock offset, `events.pinned()` record) triples -> rows,
    a process once (its first record), sorted by start."""
    rows, seen = [], set()
    for node_id, offset, record in records:
        if record["pid"] in seen:
            continue
        seen.add(record["pid"])
        rows += [dict(row, pid=record["pid"], role=record["role"],
                      node_id=node_id, proc_start=record["start"] + offset,
                      start=row["start"] + offset)
                 for row in record["rows"]]
    rows.sort(key=lambda r: (r["start"], -r["dur"]))
    return rows


def _collect_startup_records(address: str, timeout: float = 4.0) -> list:
    """The caller's own record, then one CollectEvents call a node (hostd
    answers for its live workers and for the dumps of those that ended)
    and one to the GCS, each under `timeout`.  A node that does not answer
    costs its rows and nothing else."""
    import time as _time

    async def _collect():
        from ray_tpu._private.rpc import RpcClient
        nodes = (await _gcs_call(address, "get_nodes"))["nodes"]
        calls = [(n.node_id.hex(), n.address, "NodeManager", "CollectEvents")
                 for n in nodes if n.alive]
        calls.append(("gcs", address, "Gcs", "collect_events"))
        records = []
        for node_id, addr, service, method in calls:
            client = RpcClient(addr)
            try:
                t0 = _time.time()
                reply = await client.call(
                    service, method, {"since": 1e18, "timeout": timeout / 2},
                    timeout=timeout)
                offset = _clock_offset(reply, t0, _time.time())
                records += [(node_id, offset, r)
                            for r in reply.get("pinned") or []]
            except Exception as e:
                _logger.warning("no start-up records from %s: %r",
                                node_id, e)
            finally:
                await client.close()
        return records

    from ray_tpu.util import events as ev
    records = [("driver", 0.0, ev.pinned())]
    try:
        records += _run(asyncio.wait_for(_collect(), 3 * timeout))
    except Exception as e:
        _logger.warning("start-up records not collected: %r", e)
    return records


def _keep_startup_timeline(logs_dir: Optional[str] = None) -> None:
    """`ray_tpu.shutdown()` calls this twice: before it tears anything
    down (every node asked once), and, of a cluster it owned, after, with
    the session's log directory: whoever was not heard from the first time
    has left its dump there on its way out."""
    global _last_startup_records
    from ray_tpu import api
    from ray_tpu.util import events as ev
    if logs_dir is None:
        if not getattr(api._worker, "gcs_address", None):
            return
        _last_startup_records = _collect_startup_records(
            api._worker.gcs_address)
    else:
        _last_startup_records += [
            ("dump", 0.0, r)
            for r in ev.dumped_records(
                ev.read_dumps(logs_dir, pinned_only=True))]


def startup_timeline(address: Optional[str] = None) -> List[Dict[str, Any]]:
    """What every process of the session did once, between its start and
    its first dispatch: the rows of the start-up records (`events.pinned`:
    spans closed with ``pin=True``, and programs that cost 0.1 s to make)
    of the driver, the GCS, every hostd and every worker, those that have
    ended included (their exit dumps), sorted by start.  Each row:
    ``pid, role, proc_start, node_id, plane, kind, start, dur, sid, parent,
    trace_id, payload``, times on the caller's ``time.time()``.

    Connected (or with ``address=``): collected now.  After
    ``ray_tpu.shutdown()``: the last session's, collected by the shutdown
    before it tore anything down."""
    from ray_tpu import api
    if address is None and api._worker is None:
        return _timeline_rows(_last_startup_records)
    return _timeline_rows(_collect_startup_records(_gcs_address(address)))


# ---------------------------------------------------------------------------
# Spans: durational reconstruction over the merged event stream
# ---------------------------------------------------------------------------


def build_spans(evs: List[Dict[str, Any]],
                trace_id: Optional[str] = None
                ) -> tuple[Dict[str, Dict[str, Any]],
                           List[Dict[str, Any]]]:
    """Pair ``ph="B"``/``ph="E"`` events from a merged, ts_adj-ordered
    stream into span records and link them into trees.

    Tolerant by construction: events may arrive out of order (fields
    just fill in), a missing begin (ring overflow dropped it) marks the
    span ``truncated`` and back-dates its start from the end event's
    ``dur``, and a missing end marks it ``torn`` and terminates it at
    its process's crash-dump time (the black box pins when the process
    died) or, failing that, at the observation horizon.  Returns
    ``(spans_by_sid, roots)`` — roots are spans whose parent is absent
    from the stream (including spans orphaned by overflow)."""
    crash_time: Dict[Any, float] = {}
    horizon = 0.0
    for e in evs:
        t = e.get("ts_adj", e["ts"])
        if t > horizon:
            horizon = t
        if e.get("source") == "crash":
            p = e.get("pid")
            if t > crash_time.get(p, 0.0):
                crash_time[p] = t
    table: Dict[str, Dict[str, Any]] = {}
    for e in evs:
        pl = e.get("payload") or {}
        ph = pl.get("ph")
        if ph not in ("B", "E"):
            continue
        if trace_id is not None and e.get("trace_id") != trace_id:
            continue
        sid = e.get("span_id")
        if sid is None:
            continue
        rec = table.get(sid)
        if rec is None:
            rec = table[sid] = {
                "sid": sid, "trace_id": e.get("trace_id"),
                "plane": e.get("plane"), "kind": e.get("kind"),
                "parent": None, "start": None, "end": None, "dur": None,
                "pid": e.get("pid"), "node_id": e.get("node_id"),
                "torn": False, "truncated": False, "payload": {},
                "children": [],
            }
        if ph == "B":
            rec["start"] = e.get("ts_adj", e["ts"])
            rec["parent"] = pl.get("parent")
            rec["pid"] = e.get("pid")
            rec["node_id"] = e.get("node_id")
        else:
            rec["end"] = e.get("ts_adj", e["ts"])
            rec["dur"] = pl.get("dur")
        for k, v in pl.items():
            if k not in ("ph", "parent", "dur"):
                rec["payload"][k] = v
    for rec in table.values():
        if rec["start"] is None:
            rec["truncated"] = True
            if rec["end"] is not None and rec["dur"] is not None:
                rec["start"] = rec["end"] - rec["dur"]
            else:
                rec["start"] = rec["end"]
        if rec["end"] is None:
            rec["torn"] = True
            t = crash_time.get(rec["pid"])
            if t is not None and rec["start"] is not None \
                    and t >= rec["start"]:
                rec["end"] = t
            else:
                rec["end"] = max(horizon, rec["start"] or 0.0)
        if rec["dur"] is None and rec["start"] is not None \
                and rec["end"] is not None:
            rec["dur"] = rec["end"] - rec["start"]
    roots: List[Dict[str, Any]] = []
    ordered = sorted(table.values(),
                     key=lambda r: (r["start"] is None, r["start"] or 0.0))
    for rec in ordered:
        p = rec["parent"]
        if p is not None and p != rec["sid"] and p in table:
            table[p]["children"].append(rec)
        else:
            roots.append(rec)
    return table, roots


def spans(trace_id: str, address: Optional[str] = None, *,
          since: float = 0.0) -> Dict[str, Any]:
    """Cluster-wide span tree for one trace: scrape every ring + crash
    dump, normalize clocks, pair begins/ends, link parents.  The result
    is rooted (a synthetic root is added when the trace's own root span
    was lost) and annotated with torn/truncated markers."""
    evs = events(address, since=since)
    table, roots = build_spans(evs, trace_id)
    flat = sorted(table.values(), key=lambda r: r["start"] or 0.0)
    torn = sum(1 for r in flat if r["torn"])
    if not flat:
        return {"trace_id": trace_id, "root": None, "spans": [],
                "torn": 0}
    if len(roots) == 1:
        root = roots[0]
    else:
        root = {
            "sid": "(root)", "trace_id": trace_id, "plane": "proc",
            "kind": "trace", "parent": None,
            "start": min(r["start"] for r in flat),
            "end": max(r["end"] for r in flat),
            "pid": None, "node_id": None, "torn": False,
            "truncated": True, "payload": {}, "children": roots,
        }
        root["dur"] = root["end"] - root["start"]
    return {"trace_id": trace_id, "root": root, "spans": flat,
            "torn": torn}


def _critical_segments(node: Dict[str, Any], lo: float, hi: float,
                       segs: List[Dict[str, Any]], depth: int = 0) -> None:
    """Append segments attributing (lo, hi] along the critical path, in
    reverse time order: walk backward from `hi`, descend into the child
    that ends latest before the cursor, and charge gaps between
    children to the node itself."""
    if depth > 64 or hi - lo <= 0:
        return
    cursor = hi
    kids = [c for c in node.get("children", [])
            if c.get("start") is not None and c.get("end") is not None
            and c["end"] > lo and c["start"] < hi]
    while cursor - lo > 1e-9:
        best = None
        for c in kids:
            if c["start"] >= cursor:
                continue
            if best is None or min(c["end"], cursor) > \
                    min(best["end"], cursor):
                best = c
        if best is None:
            segs.append({"sid": node["sid"], "plane": node.get("plane"),
                         "kind": node["kind"], "start": lo, "end": cursor,
                         "torn": bool(node.get("torn"))})
            return
        ce = min(best["end"], cursor)
        if cursor - ce > 1e-9:
            segs.append({"sid": node["sid"], "plane": node.get("plane"),
                         "kind": node["kind"], "start": ce, "end": cursor,
                         "torn": bool(node.get("torn"))})
        cs = max(best["start"], lo)
        _critical_segments(best, cs, ce, segs, depth + 1)
        cursor = cs
        kids = [c for c in kids if c is not best and c["start"] < cursor]


def critical_path(trace_id: str, address: Optional[str] = None, *,
                  since: float = 0.0) -> Dict[str, Any]:
    """The sequence of spans that bound this trace's wall clock: at any
    instant, the deepest span covering it on the latest-ending-child
    walk.  Shrinking any segment on the path shrinks the trace."""
    tree = spans(trace_id, address, since=since)
    root = tree["root"]
    if root is None:
        return {"trace_id": trace_id, "wall": 0.0, "segments": [],
                "by_kind": {}, "torn": 0}
    segs: List[Dict[str, Any]] = []
    _critical_segments(root, root["start"], root["end"], segs)
    segs.reverse()
    by_kind: Dict[str, float] = {}
    for s in segs:
        k = f'{s["plane"]}:{s["kind"]}'
        by_kind[k] = by_kind.get(k, 0.0) + (s["end"] - s["start"])
    by_kind = dict(sorted(by_kind.items(), key=lambda kv: -kv[1]))
    return {"trace_id": trace_id, "wall": root["end"] - root["start"],
            "segments": segs, "by_kind": by_kind, "torn": tree["torn"]}


def _pctile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(q * len(sorted_vals) + 0.5) - 1))
    return sorted_vals[idx]


def build_breakdown(evs: List[Dict[str, Any]], *,
                    plane: Optional[str] = None,
                    trace_id: Optional[str] = None) -> Dict[str, Any]:
    """Aggregate per-(plane, kind) span durations from a merged stream:
    count / p50 / p95 / p99 / total seconds and fraction of the
    observed wall clock.  Root `trace` scopes are excluded (they span
    the whole window and would attribute everything twice)."""
    table, _ = build_spans(evs, trace_id)
    lo = hi = None
    groups: Dict[tuple, List[float]] = {}
    for rec in table.values():
        if rec["start"] is None or rec["end"] is None:
            continue
        if lo is None or rec["start"] < lo:
            lo = rec["start"]
        if hi is None or rec["end"] > hi:
            hi = rec["end"]
        if rec["kind"] == "trace":
            continue
        if plane is not None and rec["plane"] != plane:
            continue
        groups.setdefault((rec["plane"], rec["kind"]), []).append(
            rec["dur"] if rec["dur"] is not None
            else rec["end"] - rec["start"])
    wall = (hi - lo) if lo is not None else 0.0
    phases = []
    for (pl, kd), durs in groups.items():
        durs.sort()
        total = sum(durs)
        phases.append({
            "plane": pl, "kind": kd, "count": len(durs),
            "p50": _pctile(durs, 0.5), "p95": _pctile(durs, 0.95),
            "p99": _pctile(durs, 0.99), "max": durs[-1],
            "total": total,
            "fraction": (total / wall) if wall > 0 else 0.0,
        })
    phases.sort(key=lambda r: -r["total"])
    return {"wall": wall, "window": (lo, hi), "phases": phases}


def latency_breakdown(address: Optional[str] = None, *,
                      plane: Optional[str] = None,
                      trace_id: Optional[str] = None,
                      since: float = 0.0) -> Dict[str, Any]:
    """Cluster-wide per-phase latency attribution: every span kind's
    p50/p95/p99/total and fraction of wall clock, ranked.  `plane`
    narrows to one plane; `trace_id` narrows to one trace."""
    evs = events(address, since=since)
    return build_breakdown(evs, plane=plane, trace_id=trace_id)


def timeline(address: Optional[str] = None,
             include_events: bool = False) -> List[Dict[str, Any]]:
    """Chrome trace events (chrome://tracing / perfetto 'X' phases) —
    reference: `ray timeline` scripts.py:1840.  With `include_events`
    the flight-recorder stream is merged in as instant events, so one
    trace shows tasks AND the runtime decisions around them, and the
    start-up records' rows (`startup_timeline`) as intervals beside them:
    what each process did once, however long ago the ring lost it."""
    task_events = list_tasks(address)
    out = []
    for e in task_events:
        out.append({
            "name": e["name"],
            "cat": "actor_task" if e.get("actor_id") else "task",
            "ph": "X",
            "ts": e["start"] * 1e6,
            "dur": max(e["end"] - e["start"], 1e-6) * 1e6,
            "pid": f'{e.get("node_id", "")}:{e.get("pid", 0)}',
            "tid": e.get("worker_id", ""),
            "args": {"task_id": e.get("task_id"),
                     "actor_id": e.get("actor_id")},
        })
    if include_events:
        for r in startup_timeline(address):
            out.append({
                "name": f'{r["plane"]}:{r["kind"]}',
                "cat": f'startup:{r["plane"]}',
                "ph": "X",
                "ts": r["start"] * 1e6,
                "dur": max(r["dur"], 1e-6) * 1e6,
                "pid": f'{r.get("node_id", "")}:{r["pid"]}',
                "tid": "startup",
                "args": {"payload": r["payload"], "span_id": r["sid"],
                         "parent": r["parent"], "role": r["role"]},
            })
        for e in events(address):
            if e.get("pinned"):
                continue        # (a dead process's rows: drawn above)
            out.append({
                "name": f'{e["plane"]}:{e["kind"]}',
                "cat": f'event:{e["plane"]}',
                "ph": "i",
                "s": "p",
                "ts": e.get("ts_adj", e["ts"]) * 1e6,
                "pid": f'{e.get("node_id", "")}:{e.get("pid", 0)}',
                "tid": e.get("source", "live"),
                "args": {"payload": e.get("payload"),
                         "trace_id": e.get("trace_id"),
                         "span_id": e.get("span_id")},
            })
    return out


def cluster_metrics(address: Optional[str] = None) -> Dict[str, Any]:
    """Per-process metric snapshots: GCS + every alive node daemon
    (reference: state aggregation over per-node metrics agents)."""
    addr = _gcs_address(address)
    gcs = _run(_gcs_call(addr, "get_metrics"))
    per_node = _run(_each_node(addr, "NodeManager", "Metrics"))
    return {"gcs": gcs.get("metrics", {}),
            "nodes": {nid: r.get("metrics", {})
                      for nid, r in per_node.items()}}


def prometheus_metrics(address: Optional[str] = None) -> str:
    """Cluster-wide Prometheus exposition text."""
    from ray_tpu.util import metrics as mt
    snap = cluster_metrics(address)
    out = [mt.prometheus_text(snap["gcs"], {"component": "gcs"})]
    for nid, m in snap["nodes"].items():
        out.append(mt.prometheus_text(
            m, {"component": "hostd", "node_id": nid[:12]}))
    return "".join(out)


def summarize_cluster(address: Optional[str] = None) -> Dict[str, Any]:
    addr = _gcs_address(address)
    nodes = list_nodes(addr)
    actors = list_actors(addr)
    pgs = list_placement_groups(addr)
    total: Dict[str, float] = {}
    avail: Dict[str, float] = {}
    for n in nodes:
        if not n["alive"]:
            continue
        for k, v in n["resources_total"].items():
            total[k] = total.get(k, 0.0) + v
        for k, v in n["resources_available"].items():
            avail[k] = avail.get(k, 0.0) + v
    by_state: Dict[str, int] = {}
    for a in actors:
        by_state[a["state"]] = by_state.get(a["state"], 0) + 1
    return {
        "nodes_alive": sum(n["alive"] for n in nodes),
        "nodes_dead": sum(not n["alive"] for n in nodes),
        "resources_total": total,
        "resources_available": avail,
        "actors": by_state,
        "placement_groups": len(pgs),
    }


def stack_traces(address: Optional[str] = None) -> Dict[str, Any]:
    """Live per-thread Python stacks for every daemon/worker process
    (reference: `ray stack`, scripts.py:1798)."""
    addr = _gcs_address(address)
    return _run(_each_node(addr, "NodeManager", "StackTraces"))
