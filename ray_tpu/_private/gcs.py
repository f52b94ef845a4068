"""GCS — the cluster control plane.

Reference parity: src/ray/gcs/gcs_server/ — GcsKvManager, GcsNodeManager,
GcsHealthCheckManager, GcsActorManager (+GcsActorScheduler two-phase
register/create, gcs_actor_manager.h:249), GcsResourceManager, GcsJobManager.
One asyncio process; state is in-memory (the reference's default
gcs_storage="memory", ray_config_def.h:382) with a pluggable table layer so a
persistent backend can slot in later.

Scheduling policy: the cluster-wide resource view lives here (fed by hostd
heartbeats, the reference's RaySyncer gossip), and `pick_node` implements the
hybrid/spread/affinity policies of src/ray/raylet/scheduling/policy/.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import time

from ray_tpu._private.ids import ActorID, NodeID, PlacementGroupID
from ray_tpu._private.protocol import ActorInfo, NodeInfo, PlacementGroupInfo
from ray_tpu._private.rpc import ClientPool, RpcServer
from ray_tpu._private import scheduler as sched

logger = logging.getLogger("ray_tpu.gcs")

def _cfg():
    from ray_tpu._private.config import GLOBAL_CONFIG
    return GLOBAL_CONFIG


_M = None


def _metrics():
    global _M
    if _M is None:
        from ray_tpu.util import metrics as mt
        _M = {
            "actors_created": mt.Counter(
                "actors_created", "actors scheduled successfully"),
            "actor_restarts": mt.Counter(
                "actor_restarts", "actor failover restarts"),
            "placement_groups_created": mt.Counter(
                "placement_groups_created", "placement groups scheduled"),
            "nodes_alive": mt.Gauge("nodes_alive", "alive nodes"),
            "gcs_flush_rows": mt.Counter(
                "gcs_flush_rows", "rows written by GCS persistence flushes"),
            "gcs_flush_seconds": mt.Counter(
                "gcs_flush_seconds", "seconds spent in GCS flush commits"),
        }
    return _M


HEARTBEAT_INTERVAL_S = _cfg().heartbeat_interval_s
NODE_DEATH_TIMEOUT_S = _cfg().node_death_timeout_s


class KvManager:
    def __init__(self):
        self._data: dict[str, dict[str, bytes]] = {}
        self.on_change = None  # set by GcsServer for persistence

    async def kv_put(self, req):
        """Typed (pb.KvPutRequest) or legacy dict (reference: the KV rows
        of gcs_service.proto InternalKVPut)."""
        from ray_tpu import protocol
        typed = protocol.is_message(req)
        if typed:
            req = {"ns": req.ns, "key": req.key, "value": req.value,
                   "overwrite": req.overwrite}
        ns = self._data.setdefault(req.get("ns", ""), {})
        existed = req["key"] in ns
        if req.get("overwrite", True) or not existed:
            ns[req["key"]] = req["value"]
            if self.on_change is not None:
                self.on_change(req.get("ns", ""), req["key"])
        if typed:
            return protocol.pb.KvPutReply(existed=existed)
        return {"existed": existed}

    async def kv_get(self, req):
        from ray_tpu import protocol
        if protocol.is_message(req):
            v = self._data.get(req.ns, {}).get(req.key)
            return protocol.pb.KvGetReply(found=v is not None,
                                          value=v or b"")
        return {"value": self._data.get(req.get("ns", ""), {}).get(req["key"])}

    async def kv_del(self, req):
        from ray_tpu import protocol
        typed = protocol.is_message(req)
        if typed:
            req = {"ns": req.ns, "key": req.key}
        ns = self._data.get(req.get("ns", ""), {})
        deleted = ns.pop(req["key"], None) is not None
        if deleted and self.on_change is not None:
            # Without this, a deleted key would resurrect on restore.
            self.on_change(req.get("ns", ""), req["key"])
        if typed:
            return protocol.pb.KvDelReply(deleted=deleted)
        return {"deleted": deleted}

    async def kv_exists(self, req):
        return {"exists": req["key"] in self._data.get(req.get("ns", ""), {})}

    async def kv_keys(self, req):
        ns = self._data.get(req.get("ns", ""), {})
        prefix = req.get("prefix", "")
        return {"keys": [k for k in ns if k.startswith(prefix)]}


class GcsTableStorage:
    """Pluggable control-plane persistence (reference:
    gcs/store_client/ — in_memory_store_client.h vs redis_store_client.h,
    selected by gcs_storage, ray_config_def.h:382).  The sqlite backend
    stores one row per record, so a mutation costs O(changed records) —
    the redis store client's role — not a whole-state snapshot; node
    membership IS persisted (the reference keeps the node table in the
    GCS store and reconciles against re-registration after restart)."""

    def __init__(self, path: str | None):
        self.path = path  # None = memory-only
        self._db = None
        self.write_ops = 0  # rows written, for O(delta) assertions

    def _conn(self):
        if self._db is None and self.path:
            import sqlite3
            db = sqlite3.connect(self.path, check_same_thread=False)
            try:
                db.execute("PRAGMA journal_mode=WAL")
                db.execute("PRAGMA synchronous=NORMAL")
                db.execute(
                    "CREATE TABLE IF NOT EXISTS t "
                    "(tab TEXT, k BLOB, v BLOB, PRIMARY KEY (tab, k))")
                db.commit()
            except sqlite3.DatabaseError:
                # Unreadable / pre-sqlite persist file: rotate it away and
                # start fresh rather than wedging the control plane.
                db.close()
                try:
                    os.replace(self.path, self.path + ".corrupt")
                except OSError:
                    pass
                db = sqlite3.connect(self.path, check_same_thread=False)
                db.execute(
                    "CREATE TABLE IF NOT EXISTS t "
                    "(tab TEXT, k BLOB, v BLOB, PRIMARY KEY (tab, k))")
                db.commit()
            self._db = db
        return self._db

    def write_rows(self, puts: list, dels: list) -> None:
        """One transaction: upsert `puts` [(tab, key, value)] and remove
        `dels` [(tab, key)]."""
        db = self._conn()
        if db is None:
            return
        with db:
            if puts:
                db.executemany(
                    "INSERT INTO t (tab, k, v) VALUES (?, ?, ?) "
                    "ON CONFLICT(tab, k) DO UPDATE SET v=excluded.v", puts)
            if dels:
                db.executemany("DELETE FROM t WHERE tab=? AND k=?", dels)
            # Scripted mid-flush kill: every row of this flush is staged
            # on the connection but the transaction has NOT committed.
            # Dying here must roll the whole flush back on restore —
            # the crash-atomicity proof for the coalesced-write path.
            from ray_tpu._private.fault_injection import get_chaos
            chaos = get_chaos()
            if chaos is not None and chaos.kill_gcs_flush():
                from ray_tpu.util import events
                events.record("gcs", "chaos_kill_flush",
                              rows=len(puts) + len(dels))
                events.dump_crash("chaos_kill_gcs_flush")
                os._exit(1)
        self.write_ops += len(puts) + len(dels)

    def load_all(self) -> dict | None:
        """{tab: {key_bytes: value_bytes}} or None when empty/memory-only."""
        import sqlite3
        if not self.path or not os.path.exists(self.path):
            return None
        db = self._conn()
        if db is None:
            return None
        try:
            rows = db.execute("SELECT tab, k, v FROM t").fetchall()
        except sqlite3.DatabaseError:
            return None
        out: dict = {}
        for tab, k, v in rows:
            out.setdefault(tab, {})[bytes(k)] = bytes(v)
        return out or None

    def close(self):
        if self._db is not None:
            self._db.close()
            self._db = None


class GcsServer:
    def __init__(self, host: str = "127.0.0.1",
                 storage: GcsTableStorage | None = None):
        self.host = host
        self.storage = storage or GcsTableStorage(
            os.environ.get("RAY_TPU_GCS_PERSIST") or None)
        self._persist_pending = False
        self._dirty: set = set()   # (tab, key) records awaiting a flush
        # Serializes flushes: two concurrent write_rows on the shared
        # sqlite connection could interleave and commit a STALE value of
        # a key dirtied in both windows over the fresh one.
        self._persist_lock = asyncio.Lock()
        self.kv = KvManager()
        self.kv.on_change = lambda ns, key: self._mark_dirty("kv", (ns, key))
        self._task_events: list = []  # ring buffer for the timeline
        self._log_lines: list = []    # (seq, record) worker-log ring
        self._log_seq = 0
        # Generic pub/sub channels (reference: src/ray/pubsub/ long-poll
        # publisher/subscriber): channel -> ring of (seq, message).
        self._channels: dict[str, list] = {}
        self._channel_seq: dict[str, int] = {}
        self._channel_events: dict[str, asyncio.Event] = {}
        self.nodes: dict[NodeID, NodeInfo] = {}
        self.node_heartbeat: dict[NodeID, float] = {}
        self.actors: dict[ActorID, ActorInfo] = {}
        self.named_actors: dict[tuple[str, str], ActorID] = {}
        self.placement_groups: dict[PlacementGroupID, PlacementGroupInfo] = {}
        self.pool = ClientPool()
        self._native_sub = None   # lazy framed-TCP pusher (taskrpc.cc)
        self.server = RpcServer(host)
        self.next_job = 0
        self._job_lock = asyncio.Lock()
        self._shutdown = asyncio.Event()
        self._cluster_version = 0  # bumped on node/actor table changes
        # Event-driven waiters: every state change swaps + fires this event
        # so long-polls and scheduler retries wake immediately instead of
        # sleep-polling (reference: pubsub/publisher.h long-poll channels).
        self._change_event = asyncio.Event()
        self._actor_events: dict = {}   # ActorID -> Event (targeted polls)
        self._wake_scheduled = False    # coalesces broadcast wakes per tick
        # Per-boot nonce, carried on every get_nodes reply: a supervised
        # respawn binds the same address, so a changed boot_id is how
        # clients detect "the GCS restarted underneath me" and push their
        # anti-entropy re-register even though the restored node table
        # still lists them alive (no reregister nudge from heartbeats).
        self.boot_id = os.urandom(8).hex()
        # Restored-alive nodes that still owe that re-register; their
        # heartbeats answer reregister=True until the snapshot arrives.
        self._resync_pending: set = set()

    def _bump(self, tab: str | None = None, key=None):
        """Record a state change and wake every waiter.  With (tab, key)
        the changed record is marked dirty for the incremental persist
        flush; without them the change is volatile (resource heartbeats)
        and only wakes waiters.

        The broadcast wake is coalesced to once per loop tick: a batched
        mutation (N actors registered in one RPC burst) fires the parked
        long-polls a single time instead of N times, while targeted
        per-actor wakes stay immediate."""
        self._cluster_version += 1
        if not self._wake_scheduled:
            self._wake_scheduled = True
            try:
                asyncio.get_running_loop().call_soon(self._fire_change)
            except RuntimeError:   # no loop (teardown/test) — fire inline
                self._fire_change()
        if tab == "actors" and key is not None:
            # Targeted wake for per-actor long-polls: during an actor
            # storm, hundreds of get_actor_info polls are parked, and
            # waking ALL of them on EVERY cluster change is an O(n^2)
            # coroutine stampede.
            aev = self._actor_events.pop(key, None)
            if aev is not None:
                aev.set()
        if tab is not None:
            self._dirty.add((tab, key))
            self._schedule_persist()

    def _fire_change(self):
        self._wake_scheduled = False
        ev = self._change_event
        self._change_event = asyncio.Event()
        ev.set()

    def _mark_dirty(self, tab: str, key) -> None:
        self._dirty.add((tab, key))
        self._schedule_persist()

    def _schedule_persist(self):
        if self.storage.path and not self._persist_pending:
            self._persist_pending = True
            asyncio.ensure_future(self._persist_soon())

    # Durable tables: dirty-set tab name -> live dict (record pickled per
    # row; a flush touches only rows dirtied since the last one).
    def _tables(self) -> dict:
        return {
            "actors": self.actors,
            "nodes": self.nodes,
            "named_actors": self.named_actors,
            "placement_groups": self.placement_groups,
            "kv": None,  # nested ns dict, resolved in _persist_soon
        }

    async def _persist_soon(self):
        """Debounced incremental flush: a burst of changes becomes ONE
        transaction writing only the dirtied rows (O(delta), reference
        redis_store_client role) plus a constant meta row."""
        await asyncio.sleep(max(0.0, _cfg().gcs_flush_interval_ms) / 1000.0)
        self._persist_pending = False
        import pickle
        async with self._persist_lock:
            await self._flush_dirty(pickle)

    async def _flush_dirty(self, pickle):
        dirty, self._dirty = self._dirty, set()
        if not dirty:
            return
        tables = self._tables()
        puts, dels = [], []
        # Serialize ON the loop thread (no mutation can interleave — a
        # torn row would mix pre/post-transition state), then hand only
        # opaque rows to the executor for disk IO.
        for tab, key in dirty:
            kb = pickle.dumps(key, protocol=5)
            if tab == "kv":
                ns, k = key
                table = self.kv._data.get(ns, {})
                obj, present = table.get(k), k in table
            else:
                d = tables.get(tab)
                if d is None:
                    continue
                obj, present = d.get(key), key in d
            if present:
                puts.append((tab, kb, pickle.dumps(obj, protocol=5)))
            else:
                dels.append((tab, kb))
        puts.append(("meta", b"next_job",
                     pickle.dumps(self.next_job, protocol=5)))
        puts.append(("meta", b"cluster_version",
                     pickle.dumps(self._cluster_version, protocol=5)))
        from ray_tpu.util import spans
        tok = spans.begin("gcs", "flush",
                          rows=len(puts) + len(dels), dirty=len(dirty))
        t0 = time.monotonic()
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, self.storage.write_rows, puts, dels)
            spans.end(tok)
            m = _metrics()
            m["gcs_flush_rows"].inc(len(puts) + len(dels))
            m["gcs_flush_seconds"].inc(time.monotonic() - t0)
        except Exception:
            spans.end(tok, error=True)
            logger.exception("GCS persistence write failed")
            # Re-mark AND reschedule: without the reschedule a transient
            # write failure during a quiescent period would leave durable
            # state unwritten until some unrelated future mutation.
            self._dirty |= dirty
            self._schedule_persist()

    def _restore(self) -> None:
        import pickle
        state = self.storage.load_all()
        if not state:
            return
        unp = pickle.loads
        for kb, vb in state.get("actors", {}).items():
            self.actors[unp(kb)] = unp(vb)
        for kb, vb in state.get("named_actors", {}).items():
            self.named_actors[unp(kb)] = unp(vb)
        for kb, vb in state.get("placement_groups", {}).items():
            self.placement_groups[unp(kb)] = unp(vb)
        now = time.monotonic()
        for kb, vb in state.get("nodes", {}).items():
            info = unp(vb)
            self.nodes[unp(kb)] = info
            if info.alive:
                # Grace stamp: a surviving hostd keeps heartbeating and
                # stays; a gone one times out through the normal sweep.
                self.node_heartbeat[unp(kb)] = now
        for kb, vb in state.get("kv", {}).items():
            ns, k = unp(kb)
            self.kv._data.setdefault(ns, {})[k] = unp(vb)
        meta = state.get("meta", {})
        if b"next_job" in meta:
            self.next_job = max(self.next_job, unp(meta[b"next_job"]))
        if b"cluster_version" in meta:
            self._cluster_version = unp(meta[b"cluster_version"])
        # Restored tables are a *hypothesis* about the cluster, not ground
        # truth: every restored-alive node owes an anti-entropy snapshot
        # before its heartbeats read as healthy again.
        self._resync_pending = {nid for nid, info in self.nodes.items()
                                if info.alive}
        logger.info("restored GCS state: %d actors, %d PGs, %d nodes, "
                    "job=%d", len(self.actors), len(self.placement_groups),
                    len(self.nodes), self.next_job)
        from ray_tpu.util import events
        events.record("gcs", "restored", boot=self.boot_id,
                      actors=len(self.actors),
                      pgs=len(self.placement_groups),
                      nodes=len(self.nodes))
        asyncio.ensure_future(self._reconcile_restored())

    async def _reconcile_restored(self):
        """Post-restart reconciliation (reference: RayletNotifyGCSRestart,
        core_worker.proto:403): ping restored ALIVE actors; unreachable
        ones go through the normal interruption/restart path.  PGs lose
        their bundle placements (nodes re-register fresh) and reschedule."""
        for info in list(self.placement_groups.values()):
            if info.state in ("CREATED", "PENDING", "RESCHEDULING"):
                info.state = "PENDING"
                info.bundle_nodes = [None] * len(info.bundles)
                info.bundle_addresses = [""] * len(info.bundles)
                asyncio.ensure_future(self._schedule_pg(info))
        for actor in list(self.actors.values()):
            if actor.state in ("PENDING", "RESTARTING"):
                # Never failed — resume scheduling without burning a
                # restart from the budget.
                asyncio.ensure_future(self._schedule_actor(actor))
                continue
            if actor.state != "ALIVE":
                continue
            reachable = False
            if actor.address:
                try:
                    await self.pool.get(actor.address).call(
                        "CoreWorker", "Ping", {}, timeout=5)
                    reachable = True
                except Exception:
                    reachable = False
            if not reachable:
                await self._on_actor_interrupted(actor, "GCS restarted")

    async def _wait_change(self, timeout: float) -> bool:
        """Wait until the next state change (or timeout); returns whether a
        change fired.  Callers re-check their condition in a loop."""
        if timeout <= 0:
            return False
        ev = self._change_event
        try:
            await asyncio.wait_for(ev.wait(), timeout)
            return True
        except asyncio.TimeoutError:
            return False

    # ---------------- node manager ----------------

    async def register_node(self, req):
        info: NodeInfo = req["info"]
        nid = info.node_id
        inc = int(getattr(info, "incarnation", 0) or 0)
        prev = self.nodes.get(nid)
        if prev is not None and not prev.alive:
            prev_inc = int(getattr(prev, "incarnation", 0) or 0)
            if inc <= prev_inc:
                # Split-brain fence: this node healed after we declared
                # it dead and failed its actors over.  Its gang is stale
                # — letting it back in as-is could double-apply updates
                # against the replacements.  Refuse, grant the next node
                # incarnation, and let the hostd fence itself (kill its
                # workers) before re-registering as the fresh incarnation.
                from ray_tpu.util import events
                events.record("gcs", "node_fenced", node=nid.hex()[:8],
                              stale_incarnation=inc,
                              granted_incarnation=prev_inc + 1)
                logger.warning(
                    "node %s re-registered after being declared dead; "
                    "fencing (stale incarnation %d, granting %d)",
                    nid.hex()[:8], inc, prev_inc + 1)
                return {"ok": False, "fenced": True,
                        "incarnation": prev_inc + 1}
        info.alive = True
        self.nodes[nid] = info
        self.node_heartbeat[nid] = time.monotonic()
        self._resync_pending.discard(nid)
        self._bump("nodes", nid)
        stale = await self._reconcile_node_snapshot(info,
                                                    req.get("snapshot"))
        logger.info("node %s registered at %s (%s, incarnation %d)",
                    nid.hex()[:8], info.address, info.resources_total, inc)
        return {"ok": True, "incarnation": inc, "stale_actors": stale}

    async def _reconcile_node_snapshot(self, info: NodeInfo, snapshot):
        """Anti-entropy against a re-registering node's ground truth.

        The snapshot lists what the hostd actually runs (live actor
        workers and their addresses, lease/worker counts).  Two ways the
        restored/stale tables can disagree, both fixed here: an actor we
        think is ALIVE on this node but the node no longer runs →
        interrupt it through the normal restart path; an actor the node
        still runs but we have failed over, killed, or never heard of →
        return it as stale so the hostd reaps that worker (the
        incarnation living at `address` lost ownership).
        """
        if not isinstance(snapshot, dict):
            return []
        reported: dict = {}
        for entry in snapshot.get("actors", ()):
            try:
                reported[entry["actor_id"]] = entry.get("address", "")
            except (TypeError, KeyError):
                continue
        stale = []
        for aid, addr in reported.items():
            a = self.actors.get(aid)
            if (a is None or a.state != "ALIVE" or a.node_id != info.node_id
                    or (addr and a.address != addr)):
                stale.append(aid)
        lost = 0
        for a in list(self.actors.values()):
            if a.state == "ALIVE" and a.node_id == info.node_id \
                    and a.actor_id not in reported:
                lost += 1
                await self._on_actor_interrupted(
                    a, "anti-entropy: node re-registered without the actor")
        if reported or stale or lost:
            from ray_tpu.util import events
            events.record("gcs", "node_resync",
                          node=info.node_id.hex()[:8],
                          reported=len(reported), stale=len(stale),
                          lost=lost)
        return stale

    async def heartbeat(self, req):
        """Typed (protocol.pb.HeartbeatRequest) or legacy dict."""
        from ray_tpu import protocol
        typed = protocol.is_message(req)
        if typed:
            nid = NodeID(req.node_id)
            available = dict(req.available.amounts)
        else:
            nid = req["node_id"]
            available = req["available"]

        def reply(*, reregister=False, shutdown=False):
            if typed:
                return protocol.pb.HeartbeatReply(
                    shutdown=shutdown, reregister=reregister)
            return {"ok": not reregister, "reregister": reregister,
                    "shutdown": shutdown}

        info = self.nodes.get(nid)
        if info is None or not info.alive or nid in self._resync_pending:
            return reply(reregister=True)
        self.node_heartbeat[nid] = time.monotonic()
        if info.resources_available != available:
            info.resources_available = available
            self._bump()
        return reply(shutdown=self._shutdown.is_set())

    async def get_nodes(self, req):
        return {"nodes": list(self.nodes.values()),
                "version": self._cluster_version,
                "boot_id": self.boot_id}

    async def add_task_events(self, req):
        """Sink for worker task-event buffers (reference: TaskEventBuffer
        task_event_buffer.h:188 streaming to GCS for observability)."""
        self._task_events.extend(req.get("events", []))
        overflow = len(self._task_events) - 20000
        if overflow > 0:
            del self._task_events[:overflow]
        return {"ok": True}

    async def pub_publish(self, req):
        """Publish messages to a channel (reference: publisher.h:302)."""
        channel = req["channel"]
        ring = self._channels.setdefault(channel, [])
        seq = self._channel_seq.get(channel, 0)
        for msg in req.get("messages", []):
            seq += 1
            ring.append((seq, msg))
        self._channel_seq[channel] = seq
        overflow = len(ring) - 10000
        if overflow > 0:
            del ring[:overflow]
        ev = self._channel_events.pop(channel, None)
        if ev is not None:
            ev.set()
        return {"seq": seq}

    async def pub_poll(self, req):
        """Long-poll a channel past after_seq (reference: long-poll
        subscriber channels, subscriber.h:70): holds the request until a
        publish or timeout."""
        channel = req["channel"]
        after = req.get("after_seq", 0)
        deadline = time.monotonic() + req.get("timeout_s", 10.0)
        import bisect
        while True:
            ring = self._channels.get(channel, [])
            start = bisect.bisect_right(ring, after, key=lambda e: e[0])
            if start < len(ring):
                return {"messages": ring[start:],
                        "seq": self._channel_seq.get(channel, 0)}
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return {"messages": [],
                        "seq": self._channel_seq.get(channel, 0)}
            ev = self._channel_events.get(channel)
            if ev is None:
                ev = self._channel_events[channel] = asyncio.Event()
            try:
                await asyncio.wait_for(ev.wait(), min(remaining, 1.0))
            except asyncio.TimeoutError:
                pass

    async def add_log_lines(self, req):
        """Worker-log sink (reference: log lines flow to the driver over
        GCS pubsub, _private/gcs_pubsub.py)."""
        for rec in req.get("lines", []):
            self._log_seq += 1
            self._log_lines.append((self._log_seq, rec))
        overflow = len(self._log_lines) - 10000
        if overflow > 0:
            del self._log_lines[:overflow]
        return {"ok": True, "seq": self._log_seq}

    async def get_log_lines(self, req):
        after = req.get("after_seq", 0)
        job = req.get("job_id")
        # Ring is seq-ordered: bisect to the first unseen entry instead of
        # scanning 10k records per poll per driver.
        import bisect
        start = bisect.bisect_right(
            self._log_lines, after, key=lambda e: e[0])
        out = [(seq, rec) for seq, rec in self._log_lines[start:]
               if job is None or rec.get("job_id") == job]
        return {"lines": out, "seq": self._log_seq}

    async def get_task_events(self, req):
        limit = req.get("limit", 10000)
        if limit <= 0:
            return {"events": []}
        return {"events": self._task_events[-limit:]}

    async def get_metrics(self, req):
        from ray_tpu.util import metrics as mt
        _metrics()["nodes_alive"].set(
            sum(1 for n in self.nodes.values() if n.alive))
        return {"metrics": mt.collect()}

    async def drain_node(self, req):
        await self._mark_node_dead(req["node_id"], "drained")
        return {"ok": True}

    async def _mark_node_dead(self, nid: NodeID, reason: str):
        info = self.nodes.get(nid)
        if info is None or not info.alive:
            return
        info.alive = False
        self._bump("nodes", nid)
        logger.warning("node %s dead: %s", nid.hex()[:8], reason)
        # Fail over actors that lived there.
        for actor in list(self.actors.values()):
            if actor.node_id == nid and actor.state in ("ALIVE", "PENDING"):
                await self._on_actor_interrupted(actor, f"node died: {reason}")
        # Re-place bundles that lived there.
        self._reschedule_pgs_for_dead_node(nid)

    async def _health_loop(self):
        while not self._shutdown.is_set():
            now = time.monotonic()
            for nid, last in list(self.node_heartbeat.items()):
                info = self.nodes.get(nid)
                if info is not None and info.alive and not info.is_head \
                        and now - last > NODE_DEATH_TIMEOUT_S:
                    await self._mark_node_dead(nid, "heartbeat timeout")
            await asyncio.sleep(HEARTBEAT_INTERVAL_S)

    # ---------------- job manager ----------------

    async def next_job_id(self, req):
        async with self._job_lock:
            self.next_job += 1
            # ("meta", None) survives to the flush (which always writes
            # the meta rows) but matches no live table row.
            self._mark_dirty("meta", None)
            return {"job_id": self.next_job}

    # ---------------- actor manager ----------------
    # Two-phase as in the reference (gcs_actor_manager.h:249): RegisterActor
    # persists the record, CreateActor drives scheduling.  We fuse the
    # scheduling trigger into register for simplicity but keep the externally
    # visible states PENDING -> ALIVE (-> RESTARTING) -> DEAD.

    async def register_actor(self, req):
        info: ActorInfo = req["info"]
        if info.name:
            key = (info.namespace, info.name)
            existing_id = self.named_actors.get(key)
            if existing_id is not None:
                existing = self.actors.get(existing_id)
                if existing is not None and existing.state != "DEAD":
                    if req.get("get_if_exists"):
                        return {"existing": existing}
                    raise ValueError(
                        f"actor name {info.name!r} already taken in "
                        f"namespace {info.namespace!r}")
            self.named_actors[key] = info.actor_id
            self._mark_dirty("named_actors", key)
        self.actors[info.actor_id] = info
        self._mark_dirty("actors", info.actor_id)
        asyncio.ensure_future(self._schedule_actor(info))
        return {"existing": None}

    async def _schedule_actor(self, info: ActorInfo):
        """Lease a dedicated worker on some node and run the creation task."""
        demand = info.resources.to_dict()
        # Pick with >=1 CPU so default actors land on nodes with headroom,
        # but reserve only the declared demand (1-for-scheduling /
        # 0-for-running, as in the reference).
        pick_demand = demand or {"CPU": 1.0}
        spec = info.creation_spec
        pg_id = spec.placement_group if spec is not None else None
        tried: set[NodeID] = set()
        refused = None    # reason of a node's permanent lease refusal
        refusers: set[NodeID] = set()
        attempt = 0
        # PG actors pend until the PG is removed (reference: PG-scheduled
        # work queues on the bundle indefinitely); non-PG actors give up
        # after 100 placement attempts.
        while pg_id is not None or attempt < 100:
            attempt += 1
            if info.state == "DEAD":
                return
            bundle = None
            if pg_id is not None:
                pg = self.placement_groups.get(pg_id)
                if pg is None or pg.state == "REMOVED":
                    info.state = "DEAD"
                    info.death_cause = "placement group unavailable"
                    info.version += 1
                    return
                if pg.state != "CREATED":
                    await self._wait_change(0.1)
                    continue
                idx = spec.bundle_index
                if idx >= len(pg.bundles):
                    info.state = "DEAD"
                    info.death_cause = (f"bundle index {idx} out of range "
                                        f"({len(pg.bundles)} bundles)")
                    info.version += 1
                    return

                def fits(b: dict) -> bool:
                    return all(b.get(k, 0.0) + 1e-9 >= v
                               for k, v in demand.items() if v > 0)

                candidates = [idx] if idx >= 0 else \
                    [i for i in range(len(pg.bundles)) if fits(pg.bundles[i])]
                if (idx >= 0 and not fits(pg.bundles[idx])) or not candidates:
                    info.state = "DEAD"
                    info.death_cause = (
                        f"actor demands {demand}, which exceeds "
                        f"{'bundle %d' % idx if idx >= 0 else 'every bundle'}"
                        f" of its placement group")
                    info.version += 1
                    return
                if idx < 0:
                    # Rotate across feasible bundles so concurrent actors
                    # spread out and a full bundle doesn't starve the rest.
                    idx = candidates[(attempt - 1 + info.num_restarts)
                                     % len(candidates)]
                node = self.nodes.get(pg.bundle_nodes[idx])
                if node is None or not node.alive:
                    await self._wait_change(0.2)
                    continue
                bundle = (pg_id.hex(), idx)
            else:
                node = sched.pick_node(self._alive_nodes(), pick_demand,
                                       strategy="DEFAULT", exclude=tried)
            if node is None:
                if refusers and tried <= refusers:
                    break       # every feasible node refused for good
                await self._wait_change(0.2)  # wait for capacity/new nodes
                tried.clear()
                tried.update(refusers)
                continue
            job_int = int.from_bytes(
                info.creation_spec.job_id.binary(), "little") \
                if info.creation_spec is not None else 0
            try:
                lease = await self.pool.get(node.address).call(
                    "NodeManager", "LeaseWorkerForActor",
                    {"actor_id": info.actor_id, "resources": demand,
                     "job_id": job_int, "bundle": bundle,
                     "runtime_env": getattr(info.creation_spec,
                                            "runtime_env", None)
                     if info.creation_spec is not None else None},
                    timeout=45)  # > the hostd's 30s lease queue window
            except Exception as e:
                logger.info("lease on %s failed: %s", node.address, e)
                tried.add(node.node_id)
                # Back off on transport errors too: a spin here burns the
                # attempt budget in seconds when the sole node's daemon
                # is briefly unreachable (storm overload, restart).
                await self._wait_change(0.2)
                continue
            if not lease.get("granted"):
                if lease.get("permanent"):
                    # This node can never serve the demand (e.g. a chip
                    # count it cannot bind).  Try the others (a bundle has
                    # no others); when none is left, fail with the reason
                    # instead of burning the attempt budget.
                    refused = lease["reason"]
                    refusers.add(node.node_id)
                    tried.add(node.node_id)
                    if pg_id is not None:
                        break
                    continue
                if lease.get("reason") in ("busy", "resources"):
                    # Saturation is not a placement failure: the node
                    # queued us for its whole lease window and is still
                    # full.  Actors PEND until capacity exists
                    # (reference: GCS actor scheduler retries leases
                    # indefinitely while the raylet queues) — don't burn
                    # the attempt budget, don't spin.
                    attempt -= 1
                    await self._wait_change(0.2)
                else:
                    tried.add(node.node_id)
                    if pg_id is not None:
                        await self._wait_change(0.2)
                continue
            worker_addr = lease["worker_address"]
            try:
                reply = await self._push_create(
                    worker_addr, lease.get("native_port", 0),
                    info.creation_spec)
            except Exception as e:
                logger.warning("actor %s creation push failed: %s",
                               info.actor_id.hex()[:8], e)
                tried.add(node.node_id)
                continue
            if info.state == "DEAD":
                # Killed while we were scheduling it: don't resurrect; tear
                # down the worker we just created it on.
                try:
                    await self.pool.get(worker_addr).call(
                        "CoreWorker", "KillActor",
                        {"actor_id": info.actor_id, "no_restart": True},
                        timeout=5)
                except Exception:
                    pass
                return
            if reply.get("error") is not None:
                info.state = "DEAD"
                info.death_cause = f"creation failed: {reply['error']}"
                info.version += 1
                self._bump("actors", info.actor_id)
                return
            info.state = "ALIVE"
            info.death_cause = ""
            info.address = worker_addr
            info.native_port = lease.get("native_port", 0)
            info.node_id = node.node_id
            info.version += 1
            _metrics()["actors_created"].inc()
            self._bump("actors", info.actor_id)
            logger.info("actor %s alive at %s", info.actor_id.hex()[:8],
                        worker_addr)
            return
        else:
            refused = None      # the attempts ran out; nothing broke out
        info.state = "DEAD"
        if refused is not None:
            info.death_cause = f"lease refused: {refused}"
        else:
            info.death_cause = "scheduling failed after 100 attempts" + (
                f"; last worker failure: {info.death_cause}"
                if info.death_cause else "")
        info.version += 1
        self._bump("actors", info.actor_id)

    async def _push_create(self, worker_addr: str, native_port: int,
                           spec):
        """Push the creation task to the freshly leased worker over the
        native plane when it advertises one (a PushTaskRequest proto,
        spec_codec — the same typed wire contract task submission
        speaks; no per-worker gRPC channel in the GCS), falling back to
        the CreateActor RPC."""
        if native_port:
            from ray_tpu._private import spec_codec
            from ray_tpu._private.task_transport import (
                ConnClosedError,
                NativeSubmitter,
            )
            try:
                if self._native_sub is None:
                    self._native_sub = NativeSubmitter(
                        asyncio.get_running_loop())
                    self._native_sub.set_caller(b"gcs")
                naddr = (f"{worker_addr.rsplit(':', 1)[0]}:{native_port}")
                payload = spec_codec.push_request_to_wire(spec, b"gcs", 0)
                data = await asyncio.wait_for(
                    self._native_sub.call(naddr, payload), 120)
                return spec_codec.reply_from_wire(data)
            except (ConnClosedError, ConnectionError):
                # The worker never (completely) received the push: safe
                # to fall back to the RPC path on the same worker.
                logger.info("native creation push connection failed; "
                            "falling back to RPC")
            # Any other failure (timeout included) may have DELIVERED the
            # creation — a same-worker fallback would run __init__ twice
            # in one process.  Surface it; the scheduler retries on a
            # different worker like a failed RPC.
        return await self.pool.get(worker_addr).call(
            "CoreWorker", "CreateActor",
            {"spec": spec, "actor_id": spec.actor_id}, timeout=120)

    async def _on_actor_interrupted(self, actor: ActorInfo, reason: str):
        if actor.num_restarts < actor.max_restarts or actor.max_restarts == -1:
            actor.num_restarts += 1
            _metrics()["actor_restarts"].inc()
            actor.state = "RESTARTING"
            actor.address = ""
            actor.version += 1
            self._bump("actors", actor.actor_id)
            logger.info("restarting actor %s (%d/%s): %s",
                        actor.actor_id.hex()[:8], actor.num_restarts,
                        actor.max_restarts, reason)
            asyncio.ensure_future(self._schedule_actor(actor))
        else:
            actor.state = "DEAD"
            actor.death_cause = reason
            actor.address = ""
            actor.version += 1
            self._bump("actors", actor.actor_id)

    async def report_actor_death(self, req):
        actor = self.actors.get(req["actor_id"])
        # Incarnation guard: a corpse report names the worker address it
        # died at.  If the actor has already been restarted elsewhere
        # (fast restarts outrun the ~0.2s corpse sweep), the stale report
        # must not consume another restart — or kill the live actor.
        dead_addr = req.get("address")
        if (actor is not None and dead_addr and actor.address
                and dead_addr != actor.address):
            return {"ok": True, "stale": True}
        if actor is not None and actor.state == "RESTARTING":
            # A worker died while (re)constructing the actor.  Scheduling
            # is already retrying; keep what the worker said for the
            # cause, should the retries run out.
            actor.death_cause = req.get("reason", "")
        if actor is not None and actor.state in ("ALIVE", "PENDING"):
            if req.get("intentional"):
                actor.state = "DEAD"
                actor.death_cause = req.get("reason", "killed")
                actor.address = ""
                actor.version += 1
                self._bump("actors", actor.actor_id)
            else:
                await self._on_actor_interrupted(actor, req.get("reason", "?"))
        return {"ok": True}

    async def get_actor_info(self, req):
        aid = req["actor_id"]
        actor = self.actors.get(aid)
        # Long-poll: while the actor is pending/restarting — or not yet
        # registered at all (registration is async; a handle can be
        # resolved by a borrower before the owner's register lands) —
        # hold the request briefly so callers don't spin (reference:
        # pubsub long-poll).  Parked on a PER-ACTOR event: unrelated
        # cluster changes must not wake every parked poll.
        deadline = time.monotonic() + req.get("wait_s", 0)
        try:
            while (actor is None
                   or actor.state in ("PENDING", "RESTARTING")) \
                    and time.monotonic() < deadline:
                ev = self._actor_events.get(aid)
                if ev is None:
                    ev = self._actor_events[aid] = asyncio.Event()
                try:
                    await asyncio.wait_for(
                        ev.wait(), min(0.5, deadline - time.monotonic()))
                except asyncio.TimeoutError:
                    pass
                actor = self.actors.get(aid)
        finally:
            if self.actors.get(aid) is None:
                # Never-registered id: no _bump will ever pop the entry;
                # drop it so stale/garbage ids can't grow the dict.
                # Concurrent pollers of the same id just re-create it on
                # their next loop iteration.
                self._actor_events.pop(aid, None)
        return {"info": actor}

    async def get_named_actor(self, req):
        aid = self.named_actors.get((req.get("namespace", "default"), req["name"]))
        return {"info": self.actors.get(aid) if aid else None}

    async def list_actors(self, req):
        return {"actors": list(self.actors.values())}

    async def kill_actor(self, req):
        actor = self.actors.get(req["actor_id"])
        if actor is None:
            return {"ok": False}
        no_restart = req.get("no_restart", True)
        address = actor.address
        if no_restart:
            actor.state = "DEAD"
            actor.death_cause = "ray_tpu.kill"
            actor.address = ""
            actor.version += 1
            self._bump("actors", actor.actor_id)
        else:
            # Kill the process but honor max_restarts (reference:
            # ray.kill(no_restart=False) semantics).
            await self._on_actor_interrupted(actor, "ray_tpu.kill(no_restart=False)")
        if address:
            try:
                await self.pool.get(address).call(
                    "CoreWorker", "KillActor",
                    {"actor_id": req["actor_id"], "no_restart": no_restart},
                    timeout=5)
            except Exception:
                pass
        return {"ok": True}

    # ---------------- placement-group manager ----------------
    # Reference: gcs_placement_group_manager.h (lifecycle) +
    # gcs_placement_group_scheduler.h (bundle placement + 2PC against the
    # per-node daemons).  Strategies: placement_group.h PACK/SPREAD/
    # STRICT_PACK/STRICT_SPREAD.

    async def create_placement_group(self, req):
        info: PlacementGroupInfo = req["info"]
        if not info.bundle_nodes:
            info.bundle_nodes = [None] * len(info.bundles)
            info.bundle_addresses = [""] * len(info.bundles)
        self.placement_groups[info.pg_id] = info
        self._mark_dirty("placement_groups", info.pg_id)
        asyncio.ensure_future(self._schedule_pg(info))
        return {"ok": True}

    def _plan_bundles(self, info: PlacementGroupInfo):
        """Choose a node for every unplaced bundle against a scratch copy of
        the cluster's available resources.  Returns {index: NodeInfo} or
        None when currently infeasible."""
        nodes = self._alive_nodes()
        scratch = {n.node_id: dict(n.resources_available) for n in nodes}
        by_id = {n.node_id: n for n in nodes}
        used_nodes = {nid for nid in info.bundle_nodes if nid is not None}
        pending = [i for i, nid in enumerate(info.bundle_nodes) if nid is None]

        def fits(nid, demand):
            avail = scratch[nid]
            return all(avail.get(k, 0.0) + 1e-9 >= v
                       for k, v in demand.items() if v > 0)

        def take(nid, demand):
            for k, v in demand.items():
                if v > 0:
                    scratch[nid][k] = scratch[nid].get(k, 0.0) - v

        plan = {}
        if info.strategy == "STRICT_PACK":
            # All bundles on ONE node (for TPU: one bundle group = one host;
            # a slice-atomic unit).
            anchor = next(iter(used_nodes), None)
            candidates = ([by_id[anchor]] if anchor in by_id else nodes)
            for node in candidates:
                trial = dict(scratch[node.node_id])
                ok = True
                for i in pending:
                    d = info.bundles[i]
                    if all(trial.get(k, 0.0) + 1e-9 >= v
                           for k, v in d.items() if v > 0):
                        for k, v in d.items():
                            if v > 0:
                                trial[k] = trial.get(k, 0.0) - v
                    else:
                        ok = False
                        break
                if ok:
                    for i in pending:
                        plan[i] = node
                    return plan
            return None

        prefer_spread = info.strategy in ("SPREAD", "STRICT_SPREAD")
        for i in pending:
            demand = info.bundles[i]
            cands = [n for n in nodes if fits(n.node_id, demand)]
            if prefer_spread:
                fresh = [n for n in cands
                         if n.node_id not in used_nodes
                         and n.node_id not in {p.node_id for p in plan.values()}]
                if fresh:
                    cands = fresh
                elif info.strategy == "STRICT_SPREAD":
                    return None
                # Spread: least-utilized first.
                cands.sort(key=lambda n: -sum(scratch[n.node_id].values()))
            else:
                # PACK: prefer nodes already carrying bundles of this PG.
                cands.sort(key=lambda n: (
                    n.node_id not in used_nodes
                    and n.node_id not in {p.node_id for p in plan.values()},
                    sum(scratch[n.node_id].values())))
            if not cands:
                return None
            node = cands[0]
            take(node.node_id, demand)
            plan[i] = node
        return plan

    async def _schedule_pg(self, info: PlacementGroupInfo):
        # Pends until satisfiable or removed (reference: PGs wait for
        # capacity indefinitely — e.g. created ahead of autoscaling).
        while info.state != "REMOVED":
            plan = self._plan_bundles(info)
            if not plan:
                await self._wait_change(0.2)
                continue
            # Phase 1: prepare every bundle; roll back all on any failure.
            prepared = []
            ok = True
            for i, node in plan.items():
                try:
                    r = await self.pool.get(node.address).call(
                        "NodeManager", "PrepareBundle",
                        {"pg_id": info.pg_id.hex(), "index": i,
                         "resources": info.bundles[i]}, timeout=10)
                except Exception:
                    ok = False
                    break
                if not r.get("ok"):
                    ok = False
                    break
                prepared.append((i, node))
            if not ok:
                # Roll back on EVERY planned node, not just confirmed
                # prepares: a Prepare whose reply was lost still reserved
                # server-side (CancelBundle on an unprepared key is a no-op).
                await self._cancel_bundles_on(plan.items(), info)
                await self._wait_change(0.2)
                continue
            # Phase 2: commit.  A failed commit on a live node leaves the
            # bundle unusable (leases check committed=True) — cancel it and
            # re-place rather than shipping a wedged CREATED group.
            failed = []
            for i, node in plan.items():
                try:
                    await self.pool.get(node.address).call(
                        "NodeManager", "CommitBundle",
                        {"pg_id": info.pg_id.hex(), "index": i}, timeout=10)
                except Exception:
                    failed.append((i, node))
                    continue
                info.bundle_nodes[i] = node.node_id
                info.bundle_addresses[i] = node.address
            if info.state == "REMOVED":
                # Removed while we were preparing/committing: the removal
                # saw empty bundle_nodes and had nothing to cancel — undo
                # everything we just reserved.
                await self._cancel_bundles_on(plan.items(), info)
                return
            if failed:
                await self._cancel_bundles_on(failed, info)
                await self._wait_change(0.2)
                continue
            # A planned node may have died while prepare/commit RPCs were in
            # flight — its death event fired before bundle_nodes was written,
            # so _reschedule_pgs_for_dead_node saw nothing.  Re-check here.
            lost = [i for i, nid in enumerate(info.bundle_nodes)
                    if nid is not None and (
                        self.nodes.get(nid) is None
                        or not self.nodes[nid].alive)]
            if lost:
                for i in lost:
                    info.bundle_nodes[i] = None
                    info.bundle_addresses[i] = ""
                await self._wait_change(0.2)
                continue
            info.state = "CREATED"
            info.version += 1
            _metrics()["placement_groups_created"].inc()
            self._bump("placement_groups", info.pg_id)
            logger.info("placement group %s created (%d bundles)",
                        info.pg_id.hex()[:8], len(info.bundles))
            return

    async def _cancel_bundles_on(self, pairs, info: PlacementGroupInfo):
        for i, node in pairs:
            try:
                await self.pool.get(node.address).call(
                    "NodeManager", "CancelBundle",
                    {"pg_id": info.pg_id.hex(), "index": i}, timeout=10)
            except Exception:
                pass
            info.bundle_nodes[i] = None
            info.bundle_addresses[i] = ""

    async def remove_placement_group(self, req):
        info = self.placement_groups.get(req["pg_id"])
        if info is None:
            return {"ok": False}
        info.state = "REMOVED"
        info.version += 1
        self._bump("placement_groups", info.pg_id)
        nodes = {nid for nid in info.bundle_nodes if nid is not None}
        for nid in nodes:
            node = self.nodes.get(nid)
            if node is None or not node.alive:
                continue
            try:
                await self.pool.get(node.address).call(
                    "NodeManager", "CancelBundle",
                    {"pg_id": info.pg_id.hex()}, timeout=10)
            except Exception:
                pass
        # Actors created inside the PG die with it (reference semantics).
        for actor in list(self.actors.values()):
            spec = actor.creation_spec
            if spec is not None and spec.placement_group == info.pg_id \
                    and actor.state != "DEAD":
                await self.kill_actor({"actor_id": actor.actor_id,
                                       "no_restart": True})
        return {"ok": True}

    async def cleanup_job(self, req):
        """Driver exit: tear down the job's non-detached placement groups
        (reference: GcsPlacementGroupManager::CleanPlacementGroupIfNeeded-
        WhenJobDead) and its non-detached actors."""
        job = req["job_id"]
        removed = 0
        for info in list(self.placement_groups.values()):
            if info.creator_job == job and not info.lifetime_detached \
                    and info.state != "REMOVED":
                await self.remove_placement_group({"pg_id": info.pg_id})
                removed += 1
        for actor in list(self.actors.values()):
            spec = actor.creation_spec
            if spec is not None and int.from_bytes(
                    spec.job_id.binary(), "little") == job \
                    and not actor.lifetime_detached \
                    and actor.state not in ("DEAD",):
                await self.kill_actor({"actor_id": actor.actor_id,
                                       "no_restart": True})
        return {"ok": True, "removed_pgs": removed}

    async def get_placement_group(self, req):
        info = self.placement_groups.get(req["pg_id"])
        deadline = time.monotonic() + req.get("wait_s", 0)
        while info is not None and info.state in ("PENDING", "RESCHEDULING") \
                and time.monotonic() < deadline:
            await self._wait_change(min(0.5, deadline - time.monotonic()))
        return {"info": info}

    async def list_placement_groups(self, req):
        return {"placement_groups": list(self.placement_groups.values())}

    def _reschedule_pgs_for_dead_node(self, nid: NodeID):
        for info in self.placement_groups.values():
            if info.state not in ("CREATED", "RESCHEDULING", "PENDING"):
                continue
            lost = [i for i, b in enumerate(info.bundle_nodes) if b == nid]
            if not lost:
                continue
            for i in lost:
                info.bundle_nodes[i] = None
                info.bundle_addresses[i] = ""
            if info.state == "CREATED":
                info.state = "RESCHEDULING"
                info.version += 1
                asyncio.ensure_future(self._schedule_pg(info))

    # ---------------- scheduling service ----------------

    async def pick_node(self, req):
        node = sched.pick_node(
            self._alive_nodes(), req["resources"],
            strategy=req.get("strategy", "DEFAULT"),
            exclude=set(req.get("exclude") or ()),
            affinity=req.get("node_affinity"),
            affinity_soft=req.get("node_affinity_soft", True),
            locality=req.get("locality"),
        )
        return {"node": node}

    def _alive_nodes(self) -> list[NodeInfo]:
        return [n for n in self.nodes.values() if n.alive]

    # ---------------- cluster lifecycle ----------------

    async def cluster_resources(self, req):
        total: dict[str, float] = {}
        avail: dict[str, float] = {}
        for n in self._alive_nodes():
            for k, v in n.resources_total.items():
                total[k] = total.get(k, 0) + v
            for k, v in n.resources_available.items():
                avail[k] = avail.get(k, 0) + v
        return {"total": total, "available": avail}

    async def shutdown_cluster(self, req):
        self._shutdown.set()
        return {"ok": True}

    async def ping(self, req):
        return {"ok": True, "version": self._cluster_version}

    async def collect_events(self, req):
        """Own flight-recorder ring.  The GCS is its own process — no
        hostd scrapes it — so without this the `gcs/flush` spans and
        actor-manager events would be invisible to state.events()."""
        from ray_tpu.util import events as ev
        return {"events": ev.snapshot(since=req.get("since", 0.0)),
                "now": time.time(), "pinned": [ev.pinned()]}

    # ---------------- lifecycle ----------------

    def _arm_chaos_kill(self):
        """Scripted head kill: wrap every registered control-plane handler
        so this GCS incarnation can os._exit(1) right before serving its
        `chaos_kill_gcs_at`-th request.  Which operation lands on that
        ordinal is scenario-determined — a heartbeat, a PG schedule, a KV
        put — which is the point: the supervised restart must absorb a
        death at ANY request boundary.  The flight ring is dumped first so
        `cli analyze` can reconstruct what the head was doing when it
        died."""
        from ray_tpu._private.fault_injection import get_chaos
        if get_chaos() is None:
            return
        from ray_tpu.util import events

        def wrap(path, fn):
            async def wrapped(request):
                chaos = get_chaos()
                if chaos is not None and chaos.kill_gcs():
                    events.record("gcs", "chaos_kill", method=path)
                    events.dump_crash("chaos_kill_gcs")
                    os._exit(1)
                return await fn(request)
            return wrapped

        for path, fn in list(self.server._methods.items()):
            self.server._methods[path] = wrap(path, fn)

    async def start(self, port: int = 0) -> int:
        self.server.register_service("Kv", self.kv)
        self.server.register_service("Gcs", self)
        self._arm_chaos_kill()
        self._restore()
        port = await self.server.start(port)
        self._health_task = asyncio.ensure_future(self._health_loop())
        return port

    async def run_until_shutdown(self):
        await self._shutdown.wait()
        await asyncio.sleep(2 * HEARTBEAT_INTERVAL_S)  # let hostds see it
        await self.server.stop()
        await self.pool.close_all()
        if self._native_sub is not None:
            try:
                self._native_sub.close()
            except Exception:
                pass


def main():
    from ray_tpu.util import events as ev
    ev.role = "gcs"
    parser = argparse.ArgumentParser()
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--ready-file", default="")
    parser.add_argument("--watch-pid", type=int, default=0,
                        help="exit when this process disappears "
                             "(driver-embedded clusters)")
    args = parser.parse_args()
    logging.basicConfig(level=os.environ.get("RAY_TPU_LOGLEVEL", "INFO"))

    if args.watch_pid:
        import threading
        import time as _time

        def _watch():
            while True:
                try:
                    os.kill(args.watch_pid, 0)
                except ProcessLookupError:
                    logger.warning("driver %d gone; GCS exiting",
                                   args.watch_pid)
                    os._exit(0)
                except PermissionError:
                    pass
                _time.sleep(1.0)

        threading.Thread(target=_watch, daemon=True,
                         name="driver-watch").start()

    from ray_tpu._private.profiling import start_periodic_profile
    start_periodic_profile("RAY_TPU_PROFILE_GCS", "gcs")

    async def run():
        gcs = GcsServer(args.host)
        port = await gcs.start(args.port)
        if args.ready_file:
            tmp = args.ready_file + ".tmp"
            with open(tmp, "w") as f:
                f.write(str(port))
            os.replace(tmp, args.ready_file)
        logger.info("GCS listening on %s:%d", args.host, port)
        await gcs.run_until_shutdown()

    asyncio.run(run())


if __name__ == "__main__":
    main()
