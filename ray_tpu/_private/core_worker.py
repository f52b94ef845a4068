"""CoreWorker — the runtime library linked into every driver and worker.

Reference parity: src/ray/core_worker/core_worker.h:284 —
Put/Get/Wait/SubmitTask/CreateActor/SubmitActorTask, plus the subsystems it
owns: in-process memory store for small objects (memory_store.h:43),
ownership-based reference counting (reference_count.h:61), the pending-task
table with retries + lineage reconstruction (task_manager.h:90), the direct
task submitter with worker leasing (transport/direct_task_transport.h:75),
and the per-actor ordered submitter (direct_actor_task_submitter.h:67).

Threading: the public API is synchronous; all networking runs on a dedicated
asyncio thread (rpc.EventLoopThread) — the same split as the reference's
Python-on-C++-asio design.  Worker-side task execution runs on the process
main thread, fed by a queue from the RPC handlers.
"""

from __future__ import annotations

import asyncio
import contextvars
import itertools
import logging
import os
import pickle as _pickle
import queue
import threading
import time
import traceback
from collections import deque
from dataclasses import dataclass, field

from ray_tpu import object_ref as object_ref_mod
from ray_tpu.exceptions import (
    ActorDiedError,
    ObjectLostError,
    ObjectStoreFullError,
    RayTpuTimeoutError,
    TaskError,
    WorkerCrashedError,
)
from ray_tpu.object_ref import ObjectRef
from ray_tpu._private import serialization as ser
from ray_tpu._private import spec_codec
from ray_tpu._private.function_manager import FunctionManager
from ray_tpu._private.ids import ActorID, JobID, NodeID, ObjectID, TaskID, WorkerID
from ray_tpu._private.object_store import ObjectStore
from ray_tpu.util import events, spans, tracing
from ray_tpu._private.protocol import (
    INLINE_LIMIT,
    RefArg,
    Resources,
    TaskSpec,
    ValueArg,
)
from ray_tpu._private.rpc import (ClientPool, EventLoopThread, GcsClient,
                                  RpcClient, RpcServer)


def _pg_id_of(pg):
    """Accept a PlacementGroup handle, a PlacementGroupID, or None."""
    if pg is None:
        return None
    return getattr(pg, "id", pg)


@dataclass
class _BundleNode:
    """Lease target resolved from a placement-group bundle record."""
    address: str
    node_id: object

logger = logging.getLogger("ray_tpu.worker")

# A task whose arguments took this long to fetch and unpickle keeps a
# `sched/arg_fetch` row in the process's start-up record.
_ARGS_PIN_S = 0.1


@dataclass
class _ObjectState:
    """Owner-side record for one owned object (directory + refcount)."""

    inline: tuple | None = None          # (data, metadata)
    locations: set = field(default_factory=set)  # node_id hex strings
    error: BaseException | None = None
    pending: bool = True
    local_refs: int = 0
    borrows: int = 0
    pins: int = 0                        # in-flight task args etc.
    event: asyncio.Event | None = None   # set when no longer pending
    waiters: list | None = None          # _BatchWaiters (bulk get)
    producing_task: TaskID | None = None


class _BatchWaiter:
    """One shared completion waiter for a bulk get(): counts outstanding
    objects and wakes the BLOCKED CALLER THREAD directly — no coroutine,
    no timer, no loop wake to start or finish a wait.  An errored object
    wakes the waiter early.  `done` may fire from the event loop (task
    completions) or a user thread (put publications); threading.Event is
    safe from both."""

    __slots__ = ("remaining", "error", "event", "lock")

    def __init__(self):
        self.remaining = 0
        self.error: BaseException | None = None
        self.event = threading.Event()
        self.lock = threading.Lock()

    def done(self, st: "_ObjectState"):
        with self.lock:
            self.remaining -= 1
            if st.error is not None and self.error is None:
                self.error = st.error
            fire = self.remaining <= 0 or st.error is not None
        if fire:
            self.event.set()


@dataclass
class _PendingTask:
    spec: TaskSpec
    retries_left: int
    future: object                       # concurrent.futures.Future | None
    lineage: bool = False                # keep spec for reconstruction
    cancelled: bool = False              # ray.cancel requested
    worker_address: str | None = None    # where the task was pushed
    payload: bytes | None = None         # packed native task descriptor
    template: tuple | None = None        # (tpl_id, TaskSpecP prefix bytes)
    sched_key: tuple | None = None       # cached _sched_key(spec, ())
    payload_epoch_base: int = 0          # sub.epoch_base baked into payload
    q_span: object = None                # open sched_queue span (traced only)


class _ActorSubmitter:
    """Client-side per-actor ordered pipeline
    (reference: direct_actor_task_submitter.h:67).

    seq is assigned in program order at submit time.  On actor restart the
    fresh worker expects wire sequence numbers from 0, so sends are rebased:
    wire_seq = seq - epoch_base, where epoch_base is the count of completed
    calls when the restart was detected (execution is in-order per actor, so
    completed calls form a prefix)."""

    def __init__(self, actor_id: ActorID):
        self.actor_id = actor_id
        self.seq = 0
        self.epoch_base = 0
        self.completed = 0
        self.address: str | None = None
        self.version = -1
        self.dead: str | None = None
        # (method, num_returns, max_retries) -> (tpl_id, TaskSpecP prefix)
        self.tpl_cache: dict = {}
        # threading.Lock: sequence numbers are assigned in the SUBMITTING
        # thread (program order), while failure rebasing happens on the
        # event loop.
        self.lock = threading.Lock()


class CoreWorker:
    def __init__(self, *, mode: str, gcs_address: str, store_path: str | None,
                 node_id: NodeID | None, hostd_address: str | None,
                 job_id: JobID | None = None, host: str = "127.0.0.1"):
        self.mode = mode                      # "driver" | "worker"
        self.worker_id = WorkerID.from_random()
        self.gcs_address = gcs_address
        self.node_id = node_id
        self.hostd_address = hostd_address
        self.host = host
        self.io = EventLoopThread()
        # GcsClient, not a bare RpcClient: control-plane calls ride
        # through supervised-GCS restarts (buffer-and-retry up to
        # gcs_outage_deadline_s) instead of failing the driver on a
        # head blip.  The data plane (tasks/objects) is peer-to-peer
        # and never routes through this channel.
        self.gcs = GcsClient(gcs_address)
        self.pool = ClientPool()
        self.store = ObjectStore.attach(store_path) if store_path else None
        self.store_path = store_path
        self.fn_manager = FunctionManager(self._kv_call)
        self.job_id = job_id
        self.objects: dict[ObjectID, _ObjectState] = {}
        self.tasks: dict[TaskID, _PendingTask] = {}
        self._pg_rr: dict = {}  # placement group -> round-robin counter
        # Lineage reconstructions in flight, by producing task: concurrent
        # getters of a lost object piggyback on one resubmission instead
        # of burning one retry each (reference:
        # object_recovery_manager.h objects_pending_recovery_).
        self._reconstructing: dict = {}   # TaskID -> asyncio.Event
        # Lease pipelining (reference: direct_task_transport.h:53-55,151 —
        # queued tasks with the same SchedulingKey reuse a held worker
        # lease instead of paying pick_node+lease+return per task).
        self._lease_cache: dict = {}      # sched_key -> _KeyScheduler
        self._free_buffer: dict = {}      # node_id -> [oid binary]
        self._free_flusher = None
        # Execution-side cancellation state (reference: CancelTask:433).
        self._cancelled_exec: set = set()
        self._running_tasks: dict = {}    # TaskID -> executing thread id
        self._cancel_lock = threading.Lock()
        self._renv_cache: dict = {}       # user runtime_env json -> descriptor
        self._opts_cache: dict = {}       # id(opts) -> (opts, invariants)
        self._tpl_ids = itertools.count(1)  # native spec-template ids
        self._tpl_content: dict = {}      # template bytes -> (id, bytes)
        self._pending_actor_reg: set = set()  # async registrations in flight
        # Loop-tick dispatch coalescing: pumps triggered by a completion
        # batch share one native flush per worker per tick.
        self._tick_batches: dict = {}
        self._tick_flush_scheduled = False
        # Task timeline events, flushed to the GCS in batches (reference:
        # core_worker/task_event_buffer.h:188).
        self._task_events: list = []
        self._task_event_flusher = None
        self.actor_submitters: dict[ActorID, _ActorSubmitter] = {}
        self.borrowed: dict[ObjectID, str] = {}  # borrowed ref -> owner addr
        self._put_index = 0
        self._obj_lock = threading.RLock()
        # Per-task execution context.  ContextVars isolate it both across
        # pool threads AND across interleaved coroutines on an async actor's
        # event loop (each asyncio.Task runs in its own context copy) —
        # thread-locals would be clobbered by concurrent async tasks.
        self._ctx_task_id: contextvars.ContextVar = \
            contextvars.ContextVar("raytpu_task_id", default=None)
        self._ctx_task_spec: contextvars.ContextVar = \
            contextvars.ContextVar("raytpu_task_spec", default=None)
        self._default_task_id = TaskID.of()   # driver context task
        self.current_actor_pg = None          # PG the actor was created in
        # Actor execution concurrency (set up at actor creation).
        self._exec_pool = None                # ThreadPoolExecutor | None
        self._async_loop = None               # asyncio loop thread | None
        self._async_sem: asyncio.Semaphore | None = None
        self.address = ""
        self._shutdown = False
        # Execution side (worker mode)
        self.exec_queue: queue.Queue = queue.Queue()
        self.actor_instance = None
        self.actor_id: ActorID | None = None
        self._actor_seq_state: dict[bytes, dict] = {}  # caller -> ordering
        self.server = RpcServer(host)
        self._register_services()
        port = self.io.run(self.server.start(0))
        self.address = f"{host}:{port}"
        # Native task transport (reference: the C++ direct task transports,
        # direct_task_transport.h:75 / direct_actor_transport.h:50).  The
        # receiver serves PushTask over the framed-TCP plane; the submitter
        # is created lazily on first use.  Target native addresses are
        # discovered once per peer via the NativePort RPC.
        self._native_sub = None
        self._native_rx = None
        self._native_addrs: dict[str, str | None] = {}
        self._native_seq_lock = threading.Lock()
        # Submit-side wakeup coalescing: one loop self-pipe write per
        # burst of submissions, not one per task.
        self._fast_q: deque = deque()
        self._fast_scheduled = False
        from ray_tpu._private.config import GLOBAL_CONFIG as _gc
        self._native_on = _gc.native_task_transport
        # Optional dispatch-coalescing window (sched_batch_wait_ms): a
        # burst's per-worker batches park in _tick_batches for up to this
        # long so trailing submissions ride the same library call.
        self._batch_wait_s = max(0.0, _gc.sched_batch_wait_ms) / 1000.0
        if mode == "worker" and _gc.native_task_transport:
            try:
                from ray_tpu._private.task_transport import NativeReceiver
                self._native_rx = NativeReceiver(
                    self._native_push_handler, host=host)
            except Exception:
                logger.exception("native task receiver unavailable; "
                                 "falling back to RPC transport")
        object_ref_mod._install_hooks(_RefHooks(self))

    # ---- per-task execution context ----------------------------------

    @property
    def current_task_id(self) -> TaskID:
        tid = self._ctx_task_id.get()
        return self._default_task_id if tid is None else tid

    @current_task_id.setter
    def current_task_id(self, value):
        self._ctx_task_id.set(value)

    @property
    def current_task_spec(self):
        return self._ctx_task_spec.get()

    @current_task_spec.setter
    def current_task_spec(self, value):
        self._ctx_task_spec.set(value)

    def _next_put_index(self) -> int:
        with self._obj_lock:
            self._put_index += 1
            return self._put_index

    # ------------------------------------------------------------------
    # RPC services (owner + execution)
    # ------------------------------------------------------------------

    def _register_services(self):
        s = self.server
        s.register("CoreWorker", "PushTask", self._rpc_push_task)
        s.register("CoreWorker", "CancelTask", self._rpc_cancel_task)
        s.register("CoreWorker", "CreateActor", self._rpc_create_actor)
        s.register("CoreWorker", "KillActor", self._rpc_kill_actor)
        s.register("CoreWorker", "GetObjectStatus", self._rpc_get_object_status)
        s.register("CoreWorker", "AddBorrow", self._rpc_add_borrow)
        s.register("CoreWorker", "RemoveBorrow", self._rpc_remove_borrow)
        s.register("CoreWorker", "AddLocation", self._rpc_add_location)
        s.register("CoreWorker", "StackTrace", self._rpc_stack_trace)
        s.register("CoreWorker", "Metrics", self._rpc_metrics)
        s.register("CoreWorker", "CollectEvents", self._rpc_collect_events)
        s.register("CoreWorker", "Ping", self._rpc_ping)
        s.register("CoreWorker", "NativePort", self._rpc_native_port)
        s.register("CoreWorker", "NodeDead", self._rpc_node_dead)
        s.register("CoreWorker", "PreemptionNotice",
                   self._rpc_preemption_notice)

    async def _rpc_preemption_notice(self, req):
        """Hostd fans its preemption notice down to each worker: this
        host dies in `grace_s` seconds.  If a train session lives here,
        arm it — its next report() races a proactive checkpoint save
        against the window, then aborts at the step boundary with
        TrainPreemptedError.  The train module is looked up, never
        imported: non-train workers must not pay the import."""
        import sys
        grace = float(req.get("grace_s", 0.0))
        from ray_tpu.util import metrics as mt
        mt.Counter("train_preemption_notices",
                   "preemption notices delivered to this worker").inc()
        sess_mod = sys.modules.get("ray_tpu.train.session")
        sess = getattr(sess_mod, "_session", None) if sess_mod else None
        if sess is not None:
            sess.notify_preemption(grace)
            return {"ok": True, "armed": True}
        return {"ok": True, "armed": False}

    async def _rpc_native_port(self, req):
        """Native-transport discovery: callers connect to this port for the
        framed-TCP PushTask plane (0 = native transport disabled here)."""
        return {"port": self._native_rx.port if self._native_rx else 0}

    async def _rpc_ping(self, req):
        return {"ok": True, "worker_id": self.worker_id}

    async def _rpc_node_dead(self, req):
        """Hostd pushes GCS-detected node death down to its workers
        (reference: raylet NodeRemoved pub/sub -> core-worker object
        directory invalidation).  Drop the dead node from every owned
        object's location set (gets fail over to live copies or lineage),
        forget its pooled channel and native route, and purge its leases
        from every key scheduler so queued work re-leases elsewhere."""
        dead_hex = req["node_id"]
        dead_addr = req.get("address") or ""
        with self._obj_lock:
            for st in self.objects.values():
                st.locations.discard(dead_hex)
        self._node_cache = None   # next _node_table() refetches live view
        if dead_addr:
            self.pool.invalidate(dead_addr)
        purged = 0
        for ks in list(self._lease_cache.values()):
            purged += ks.purge_node(dead_hex)
        if purged:
            logger.info("node %s dead: purged %d lease(s)",
                        dead_hex[:8], purged)
        return {"ok": True, "purged": purged}

    async def _kv_call(self, method: str, request):
        return await self.gcs.call("Kv", method, request)

    # ---- owner services ----

    async def _rpc_get_object_status(self, req):
        """Resolve an object for a borrower: inline value, locations, or
        error.  Long-polls while the producing task is still running
        (reference: core_worker.proto GetObjectStatus:411)."""
        oid = ObjectID(req["id"])
        wait_s = req.get("wait_s", 30.0)
        st = self.objects.get(oid)
        if st is None:
            return {"status": "unknown"}
        if st.pending:
            if st.event is None:
                st.event = asyncio.Event()
            try:
                await asyncio.wait_for(st.event.wait(), wait_s)
            except asyncio.TimeoutError:
                return {"status": "pending"}
            st = self.objects.get(oid)
            if st is None:
                return {"status": "unknown"}
        if st.error is not None:
            return {"status": "error", "error": st.error}
        if st.inline is not None:
            return {"status": "inline", "data": st.inline[0],
                    "metadata": st.inline[1]}
        return {"status": "locations", "locations": sorted(st.locations)}

    async def _rpc_add_borrow(self, req):
        st = self.objects.get(ObjectID(req["id"]))
        if st is not None:
            st.borrows += 1
        return {"ok": True}

    async def _rpc_remove_borrow(self, req):
        oid = ObjectID(req["id"])
        st = self.objects.get(oid)
        if st is not None:
            st.borrows = max(0, st.borrows - 1)
            self._maybe_free(oid)
        return {"ok": True}

    async def _rpc_add_location(self, req):
        st = self.objects.get(ObjectID(req["id"]))
        if st is not None:
            st.locations.add(req["node"])
        return {"ok": True}

    async def _rpc_stack_trace(self, req):
        """Live per-thread Python stacks + the flight-recorder tail
        (reference: `ray stack` scripts.py:1798)."""
        from ray_tpu._private.stack_dump import dump_state
        return {"pid": os.getpid(), **dump_state()}

    async def _rpc_metrics(self, req):
        """This worker's util.metrics registry, pulled by hostd into the
        node-level scrape — application metrics (serve replica engines,
        user Counters/Gauges) live here, not in the daemon."""
        from ray_tpu.util import metrics as mt
        return {"pid": os.getpid(), "metrics": mt.collect()}

    async def _rpc_collect_events(self, req):
        """This worker's flight-recorder ring (live scrape side of the
        black box).  `now` rides along so the aggregator can normalize
        clock skew across nodes."""
        return {"pid": os.getpid(), "now": time.time(),
                "events": events.snapshot(since=req.get("since", 0.0)),
                "pinned": events.pinned()}

    # ---- execution services ----

    async def _rpc_cancel_task(self, req):
        """Cancel a queued or running task on this worker (reference:
        core_worker.proto CancelTask:433).  Queued -> dropped; running with
        force -> process exit; running without force -> async exception
        injected into the executing thread.  The injection happens under
        _cancel_lock, which _execute_task also holds while registering/
        deregistering, so the exception cannot target a thread that has
        already moved on to a different task."""
        from ray_tpu.exceptions import TaskCancelledError
        task_id = TaskID(req["task_id"])
        self._cancelled_exec.add(task_id)
        with self._cancel_lock:
            tid = self._running_tasks.get(task_id)
            if tid is not None:
                if req.get("force"):
                    logger.info("force-cancel: exiting worker (task %s)",
                                task_id)
                    os._exit(1)
                import ctypes
                ctypes.pythonapi.PyThreadState_SetAsyncExc(
                    ctypes.c_ulong(tid), ctypes.py_object(TaskCancelledError))
        return {"ok": True, "running": tid is not None}

    # ---- native-transport execution side ----

    def _native_push_handler(self, payload: bytes, reply):
        """Entry point for tasks arriving over the native plane (runs on
        the tpt-exec thread, in per-connection FIFO order).  The wire
        format is PushTaskRequest proto (raytpu.proto) — parsed by upb,
        no pickle on the control path.  Normal tasks execute inline — no
        event-loop hop; actor tasks route through the per-caller sequence
        window and the actor's concurrency mode."""
        spec = None
        try:
            spec, caller, wire_seq = spec_codec.push_request_from_wire(
                payload)
            if spec.actor_creation:
                # Creation runs on the MAIN exec thread like the RPC path
                # (actor __init__ and methods must share a thread —
                # user code may keep thread-local state).
                self.actor_id = spec.actor_id
                self.exec_queue.put(
                    (spec, self._native_done_sink(reply), None))
            elif spec.actor_id is not None:
                self._enqueue_actor_native(spec, caller, wire_seq, reply)
            else:
                self._run_one_native(spec, reply)
        except BaseException as e:  # noqa: BLE001
            try:
                reply(spec_codec.reply_to_wire(
                    self._error_reply(spec, e) if spec is not None
                    else {"returns": [], "error": TaskError(
                        "native-push", traceback.format_exc(), None)}))
            except Exception:
                logger.exception("native reply failed")

    def _run_one_native(self, spec: TaskSpec, reply):
        try:
            r = self._execute_task(spec)
        except BaseException as e:  # noqa: BLE001
            r = self._error_reply(spec, e)
        try:
            data = spec_codec.reply_to_wire(r)
        except Exception as e:
            data = spec_codec.reply_to_wire(self._error_reply(spec, e))
        reply(data)

    def _enqueue_actor_native(self, spec, caller, wire_seq, reply):
        """Per-caller in-order release, same window logic as the RPC path
        (_enqueue_actor_task) but completing via the native reply stream.
        The lock makes the window safe from the tpt-exec thread.

        Tasks are released onto the SAME exec_queue as the RPC path (with
        a callable done-sink in place of an asyncio future, loop=None):
        a sync actor with mixed-transport callers must still run its
        methods strictly serialized on the one exec thread, and the held
        window must hold one entry shape."""
        entry = (spec, self._native_done_sink(reply), None)
        with self._native_seq_lock:
            state = self._actor_seq_state.setdefault(
                caller, {"next": 0, "held": {}})
            if wire_seq < state["next"]:
                self.exec_queue.put(entry)
                return
            state["held"][wire_seq] = entry
            while state["next"] in state["held"]:
                self.exec_queue.put(state["held"].pop(state["next"]))
                state["next"] += 1

    @staticmethod
    def _native_done_sink(reply):
        def sink(r):
            try:
                reply(spec_codec.reply_to_wire(r))
            except Exception:
                logger.exception("native reply failed")
        return sink

    # ---- native-transport submission side ----

    def _ensure_native_sub(self):
        if not self._native_on:
            return None
        if self._native_sub is None:
            try:
                from ray_tpu._private.task_transport import NativeSubmitter
                self._native_sub = NativeSubmitter(self.io.loop)
                self._native_sub.set_caller(self.worker_id.binary())
            except Exception:
                logger.exception("native submitter unavailable")
                self._native_sub = False
        return self._native_sub or None

    async def _native_call_worker(self, addr: str, spec,
                                  wire_seq: int = 0) -> dict | None:
        """Push a task to `addr` (a worker's RPC address) over the native
        plane as a full PushTaskRequest proto (cold path: retries, exotic
        scheduling — the hot path uses the template codec).  Returns None
        when either side has no native transport — the caller then falls
        back to the RPC path.  Transport failures raise, like an RPC
        failure would."""
        sub = self._ensure_native_sub()
        if sub is None:
            return None
        naddr = self._native_addrs.get(addr, "?")
        if naddr == "?":
            try:
                r = await self.pool.get(addr).call(
                    "CoreWorker", "NativePort", {}, timeout=10)
                port = r.get("port") or 0
            except Exception:
                port = 0
            naddr = (f"{addr.rsplit(':', 1)[0]}:{port}" if port else None)
            self._native_addrs[addr] = naddr
        if naddr is None:
            return None
        payload = spec_codec.push_request_to_wire(
            spec, self.worker_id.binary(), wire_seq)
        try:
            data = await sub.call(naddr, payload)
        except ConnectionError:
            # Dead conn: drop the mapping so a replacement worker at the
            # same RPC address re-discovers, then surface as a failure.
            self._native_addrs.pop(addr, None)
            sub.invalidate(naddr)
            raise
        return spec_codec.reply_from_wire(data)

    async def _rpc_push_task(self, req):
        """Queue a task for the execution thread and await its result
        (reference: core_worker.proto PushTask:406)."""
        spec: TaskSpec = req["spec"]
        loop = asyncio.get_running_loop()
        done = loop.create_future()
        if spec.actor_id is not None and not spec.actor_creation:
            self._enqueue_actor_task(req, done, loop)
        else:
            self.exec_queue.put((spec, done, loop))
        return await done

    def _enqueue_actor_task(self, req, done, loop):
        """Order actor tasks per caller by sequence number
        (reference: transport/actor_scheduling_queue.h:40).

        A restarted actor starts with no ordering state while callers keep
        counting, so the first seq seen from an unknown caller initializes
        the expectation; anything below `next` is a stale retry and runs
        immediately rather than being held forever."""
        spec: TaskSpec = req["spec"]
        caller = req.get("caller", b"")
        wire_seq = req.get("seq", spec.seq_no)
        with self._native_seq_lock:  # shared with the native receiver path
            state = self._actor_seq_state.setdefault(
                caller, {"next": 0, "held": {}})
            if wire_seq < state["next"]:
                # Stale retry rebased below the horizon: run immediately.
                self.exec_queue.put((spec, done, loop))
                return
            state["held"][wire_seq] = (spec, done, loop)
            while state["next"] in state["held"]:
                item = state["held"].pop(state["next"])
                state["next"] += 1
                self.exec_queue.put(item)

    async def _rpc_create_actor(self, req):
        spec: TaskSpec = req["spec"]
        loop = asyncio.get_running_loop()
        done = loop.create_future()
        self.actor_id = req["actor_id"]
        self.exec_queue.put((spec, done, loop))
        return await done

    async def _rpc_kill_actor(self, req):
        # What this process recorded outlives it: hostd reads the dump for
        # `CollectEvents`, a killed replica's start-up record among it.
        events.dump_crash("actor_killed")
        self.exec_queue.put(None)  # sentinel: exit main loop
        asyncio.get_running_loop().call_later(0.5, os._exit, 0)
        return {"ok": True}

    # ------------------------------------------------------------------
    # Public API: put / get / wait
    # ------------------------------------------------------------------

    def put(self, value) -> ObjectRef:
        oid = ObjectID.for_put(self.current_task_id, self._next_put_index())
        sv = ser.serialize(value, ref_sink=self._pin_serialized_ref)
        try:
            self._store_owned_value(oid, sv)
        except ObjectStoreFullError:
            # Ask the node daemon to spill to disk, then retry (reference:
            # raylet SpillObjects on OOM, local_object_manager.h:41).
            for attempt in range(3):
                freed = self.io.run(self._request_spill(sv.total_size))
                try:
                    self._store_owned_value(oid, sv)
                    break
                except ObjectStoreFullError:
                    if not freed:
                        time.sleep(0.2)
            else:
                self._store_owned_value(oid, sv)
        return ObjectRef(oid, self.address)

    async def _request_spill(self, nbytes: int) -> int:
        if not self.hostd_address:
            return 0
        try:
            reply = await self.pool.get(self.hostd_address).call(
                "NodeManager", "SpillObjects",
                {"bytes_needed": int(nbytes * 1.5)}, timeout=30)
            return reply.get("freed", 0)
        except Exception:
            return 0

    def _store_owned_value(self, oid: ObjectID, sv: ser.SerializedValue):
        with self._obj_lock:
            st = self.objects.setdefault(oid, _ObjectState())
        if sv.total_size < INLINE_LIMIT or self.store is None:
            st.inline = (sv.to_bytes(), sv.metadata)
        else:
            view = self.store.create_object(oid, sv.total_size, sv.metadata)
            sv.write_into(view)
            self.store.seal(oid)
            st.locations.add(self.node_id.hex())
        # Publication order: value/locations first, THEN pending=False —
        # the caller-thread get() fast path reads states without the
        # loop, so `pending` is the publish flag.  The flip is under
        # _obj_lock: _wait_owned registration checks pending under the
        # same lock (see _signal_ready).
        with self._obj_lock:
            st.pending = False
        self._signal_ready(oid, st)

    def _signal_ready(self, oid: ObjectID, st: _ObjectState):
        if st.event is not None:
            if threading.get_ident() == self.io.ident:
                # Already on the loop: set directly — the threadsafe
                # variant writes the loop's self-pipe (~30us) per call.
                st.event.set()
            else:
                self.io.loop.call_soon_threadsafe(st.event.set)
        ws = None
        if st.waiters:
            # Pop under the same lock that guards registration: a get()
            # on another thread is either already in the list (we
            # deliver) or will see pending=False under the lock and
            # self-deliver — exactly once either way.
            with self._obj_lock:
                ws = st.waiters
                st.waiters = None
        if ws:
            for w in ws:
                w.done(st)

    def get(self, refs, timeout: float | None = None):
        single = isinstance(refs, ObjectRef)
        if single:
            refs = [refs]
        # About to block: if THIS thread holds batched native replies
        # (worker exec threads inside a burst), ship them first — a
        # caller elsewhere may be waiting on one of those replies to
        # produce the very object this get polls for (batching must
        # never introduce a cross-worker dependency deadlock).
        rx = getattr(self, "_native_rx", None)
        if rx is not None:
            rx.flush_thread_batch()
        # Caller-thread bulk path for OWNED refs: wait with ONE loop-side
        # waiter per batch (not a coroutine + timer per ref — measured
        # ~15us/ref of loop machinery), then resolve inline values right
        # here, off the event loop.  Anything non-trivial (borrowed refs,
        # store/remote copies, lost objects) falls back to the general
        # coroutine path below.  One deadline covers both phases.
        deadline = None if timeout is None else time.monotonic() + timeout
        objects = self.objects
        my_addr = self.address
        pending_refs = []
        for r in refs:
            if r.owner_address in ("", my_addr):
                st = objects.get(r.id)
                if st is not None and st.pending:
                    pending_refs.append(r)
        if pending_refs:
            self._wait_owned(pending_refs, deadline)
        values = []
        slow: list = []          # (index, ref) pairs for the general path
        for r in refs:
            st = objects.get(r.id) \
                if r.owner_address in ("", my_addr) else None
            if st is not None and not st.pending and st.error is None \
                    and st.inline is not None:
                values.append(ser.deserialize(*st.inline))
            else:
                values.append(None)
                slow.append((len(values) - 1, r))
        if slow:
            left = None if deadline is None else \
                max(0.0, deadline - time.monotonic())
            resolved = self.io.run(self._get_async(
                [r for _i, r in slow], left))
            for (i, _r), v in zip(slow, resolved):
                values[i] = v
        return values[0] if single else values

    def _wait_owned(self, refs, deadline):
        """Block the CALLING thread until every owned ref in `refs` has
        completed (value, location, or error — resolution happens back
        in get()).  One shared waiter serves the whole batch;
        registration races with completions (loop thread, put threads)
        are settled by the remove-to-deliver dance below.  An errored
        object wakes the waiter early so a failed task surfaces before
        stragglers finish."""
        waiter = _BatchWaiter()
        registered = []
        for r in refs:
            st = self.objects.get(r.id)
            if st is None or not st.pending:
                continue
            with waiter.lock:
                waiter.remaining += 1
            # Registration is atomic with the pending check under
            # _obj_lock: publication flips `pending` and pops the list
            # under the same lock, so the waiter is either delivered by
            # the publisher or self-delivered here — never both, never
            # neither.
            with self._obj_lock:
                if st.pending:
                    if st.waiters is None:
                        st.waiters = []
                    st.waiters.append(waiter)
                    registered.append(st)
                    continue
            waiter.done(st)   # completed before we got in
        try:
            while waiter.remaining > 0 and waiter.error is None:
                waiter.event.clear()
                if waiter.remaining <= 0 or waiter.error is not None:
                    break        # fired between the checks and the clear
                left = None if deadline is None else \
                    deadline - time.monotonic()
                if left is not None and left <= 0:
                    raise RayTpuTimeoutError("get() timed out")
                if not waiter.event.wait(left):
                    raise RayTpuTimeoutError("get() timed out")
        finally:
            if waiter.remaining > 0:
                # Timed out (or errored early) with objects still
                # pending: unregister so a polling caller doesn't leak a
                # waiter per attempt into long-lived object states.
                with self._obj_lock:
                    for st in registered:
                        if st.waiters:
                            try:
                                st.waiters.remove(waiter)
                            except ValueError:
                                pass
        # An early error stops the wait; the caller-thread resolution
        # (or the per-ref fallback path) raises it in ref order.

    async def _get_async(self, refs, timeout):
        return await asyncio.gather(*[self._get_one(r, timeout) for r in refs])

    async def _get_one(self, ref: ObjectRef, timeout: float | None):
        deadline = None if timeout is None else \
            asyncio.get_running_loop().time() + timeout
        for attempt in range(5):
            data, metadata = await self._resolve_bytes(ref, deadline)
            if data is not None:
                return ser.deserialize(data, metadata)
            # Object lost: try lineage reconstruction then loop.
            if not await self._try_reconstruct(ref):
                raise ObjectLostError(ref.id, "no live copy and no lineage")
        raise ObjectLostError(ref.id, "reconstruction did not converge")

    async def _resolve_bytes(self, ref: ObjectRef, deadline):
        """Return (data, metadata) or (None, None) if the object was lost."""
        oid = ref.id
        owned = ref.owner_address in ("", self.address)
        while True:
            st = self.objects.get(oid) if owned else None
            if owned and st is None:
                raise ObjectLostError(oid, "owner has no record of object")
            if owned and not st.pending:
                if st.error is not None:
                    raise st.error
                if st.inline is not None:
                    return st.inline
                got = await self._fetch_from_locations(oid, sorted(st.locations))
                if got is not None:
                    return got
                st.locations.clear()
                return None, None
            if not owned:
                # Local store fast path before asking the owner.
                if self.store is not None:
                    buf = self.store.get(oid)
                    if buf is not None:
                        try:
                            return bytes(buf.data), buf.metadata
                        finally:
                            buf.release()
                reply = await self._call_owner(
                    ref, "GetObjectStatus",
                    {"id": oid.binary(), "wait_s": 5.0})
                status = reply["status"]
                if status == "inline":
                    return reply["data"], reply["metadata"]
                if status == "error":
                    raise reply["error"]
                if status == "locations":
                    got = await self._fetch_from_locations(
                        oid, reply["locations"], owner=ref.owner_address)
                    if got is not None:
                        return got
                    return None, None
                if status == "unknown":
                    raise ObjectLostError(oid, "owner does not know object")
            # pending → check deadline and loop (owner long-polls internally)
            if owned and st.pending:
                if st.event is None:
                    st.event = asyncio.Event()
                try:
                    wait = None if deadline is None else \
                        deadline - asyncio.get_running_loop().time()
                    if wait is not None and wait <= 0:
                        raise RayTpuTimeoutError(f"get({oid}) timed out")
                    await asyncio.wait_for(st.event.wait(),
                                           None if wait is None else wait)
                except asyncio.TimeoutError:
                    raise RayTpuTimeoutError(f"get({oid}) timed out") from None
            elif deadline is not None and \
                    asyncio.get_running_loop().time() > deadline:
                raise RayTpuTimeoutError(f"get({oid}) timed out")

    async def _call_owner(self, ref: ObjectRef, method: str, req):
        try:
            return await self.pool.get(ref.owner_address).call(
                "CoreWorker", method, req)
        except Exception as e:
            raise ObjectLostError(
                ref.id, f"owner {ref.owner_address} unreachable: {e}") from e

    async def _fetch_from_locations(self, oid: ObjectID, locations,
                                    owner: str | None = None):
        """Pull the object into the local store from any live location
        (reference: object_manager PullManager, locations from the owner —
        OwnershipBasedObjectDirectory)."""
        my_node = self.node_id.hex() if self.node_id else None
        # Local copy?
        if self.store is not None and (my_node in locations):
            buf = self.store.get(oid)
            if buf is not None:
                try:
                    return bytes(buf.data), buf.metadata
                finally:
                    buf.release()
        nodes = await self._node_table()
        # Own node stays in the candidate list: a local store miss with a
        # local location means the object was SPILLED — the hostd restores
        # it from disk through the same pull path.
        for loc in locations:
            addr = nodes.get(loc)
            if addr is None:
                continue
            try:
                fetched = await self._pull_from_node(addr, oid)
            except Exception:
                continue
            if fetched is None:
                continue
            data, metadata = fetched
            if self.store is not None:
                try:
                    if not self.store.contains(oid):
                        self.store.put_bytes(oid, data, metadata)
                    if owner:
                        asyncio.ensure_future(self.pool.get(owner).call(
                            "CoreWorker", "AddLocation",
                            {"id": oid.binary(), "node": my_node}))
                    elif oid in self.objects:
                        self.objects[oid].locations.add(my_node)
                except Exception:
                    pass
            return data, metadata
        return None

    # Chunked node-to-node transfer (reference: object_manager/ chunked
    # push/pull, push_manager.h in-flight chunk throttling).
    PULL_CHUNK_BYTES = 8 << 20
    PULL_MAX_INFLIGHT = 4

    async def _pull_from_node(self, addr: str, oid: ObjectID):
        """Fetch (data, metadata) from one node.  Small objects (the
        common case) cost ONE RPC; past max_inline the daemon answers
        too_large and the payload streams as bounded-concurrency chunks.
        The whole pull is one `object`/`transfer` span (begin -> end with
        mode/bytes), so cross-node data waits show up in critical paths."""
        client = self.pool.get(addr)
        tok = spans.begin("object", "transfer",
                          oid=oid.binary().hex()[:16], src=addr)
        try:
            reply = await client.call(
                "NodeManager", "PullObject",
                {"id": oid.binary(), "max_inline": self.PULL_CHUNK_BYTES})
        except BaseException:
            spans.end(tok, ok=False)
            raise
        if not reply.get("found"):
            spans.end(tok, ok=False)
            return None
        if not reply.get("too_large"):
            spans.end(tok, bytes=len(reply["data"]), mode="inline")
            return reply["data"], reply["metadata"]
        size = reply["data_size"]
        metadata = reply["metadata"]
        # Large payloads ride the native data plane when the remote store
        # serves one (objtransfer.cc): bytes land shm-to-shm with no
        # Python copies.  Any failure falls back to the chunk RPCs below
        # (which also cover spilled objects).
        port = reply.get("transfer_port")
        if port and self.store is not None and self.store_path:
            import socket as _socket

            from ray_tpu._private import object_transfer
            host = addr.rsplit(":", 1)[0]

            def resolve_and_fetch():
                # DNS may block — keep it off the event loop too.
                ip = _socket.gethostbyname(host)
                return object_transfer.fetch(self.store_path, ip, port, oid)

            try:
                ok = await asyncio.get_running_loop().run_in_executor(
                    None, resolve_and_fetch)
            except Exception as e:
                logger.debug("native pull of %s from %s failed: %s",
                             oid, addr, e)
                ok = False
            if ok:
                buf = self.store.get(oid)
                if buf is not None:
                    try:
                        spans.end(tok, bytes=size, mode="native")
                        return bytes(buf.data), buf.metadata
                    finally:
                        buf.release()
        out = bytearray(size)
        sem = asyncio.Semaphore(self.PULL_MAX_INFLIGHT)
        failed = []

        from ray_tpu import protocol

        async def fetch(offset: int):
            length = min(self.PULL_CHUNK_BYTES, size - offset)
            async with sem:
                chunk = await client.call(
                    "NodeManager", "PullObjectChunk",
                    protocol.pb.PullObjectChunkRequest(
                        id=oid.binary(), offset=offset, length=length))
            if not chunk.found:
                failed.append(offset)
                return
            out[offset:offset + length] = chunk.data

        results = await asyncio.gather(
            *[fetch(off) for off in range(0, size, self.PULL_CHUNK_BYTES)],
            return_exceptions=True)
        if failed or any(isinstance(r, BaseException) for r in results):
            spans.end(tok, ok=False)
            return None
        spans.end(tok, bytes=size, mode="chunked")
        return bytes(out), metadata

    _node_cache: tuple | None = None

    async def _node_table(self) -> dict:
        """node_id hex -> hostd address, cached briefly."""
        now = asyncio.get_running_loop().time()
        if self._node_cache is not None and now - self._node_cache[0] < 1.0:
            return self._node_cache[1]
        reply = await self.gcs.call("Gcs", "get_nodes", {})
        table = {n.node_id.hex(): n.address for n in reply["nodes"] if n.alive}
        self._node_cache = (now, table)
        return table

    def wait(self, refs, num_returns=1, timeout=None, fetch_local=True):
        rx = getattr(self, "_native_rx", None)
        if rx is not None:   # see get(): never block on held replies
            rx.flush_thread_batch()
        return self.io.run(self._wait_async(refs, num_returns, timeout))

    async def _ready_probe(self, ref: ObjectRef):
        """Block until the object is ready WITHOUT pulling its payload
        (errored objects count as ready, as in the reference)."""
        oid = ref.id
        owned = ref.owner_address in ("", self.address)
        while True:
            if owned:
                st = self.objects.get(oid)
                if st is None:
                    return  # freed/unknown: surfaces as error on get()
                if not st.pending:
                    return
                if st.event is None:
                    st.event = asyncio.Event()
                await st.event.wait()
            else:
                if self.store is not None and self.store.contains(oid):
                    return
                try:
                    reply = await self._call_owner(
                        ref, "GetObjectStatus",
                        {"id": oid.binary(), "wait_s": 5.0})
                except ObjectLostError:
                    return
                if reply["status"] != "pending":
                    return

    async def _wait_async(self, refs, num_returns, timeout):
        pending = {asyncio.ensure_future(self._ready_probe(r)): r
                   for r in refs}
        ready = []
        try:
            deadline = None if timeout is None else \
                asyncio.get_running_loop().time() + timeout
            while pending and len(ready) < num_returns:
                wait_t = None if deadline is None else max(
                    0, deadline - asyncio.get_running_loop().time())
                done, _ = await asyncio.wait(
                    pending.keys(), timeout=wait_t,
                    return_when=asyncio.FIRST_COMPLETED)
                if not done:
                    break
                for f in done:
                    f.exception()  # consume; errored objects count as ready
                    # Cap at num_returns ("at most num_returns" contract):
                    # several probes can complete in one event-loop tick, and
                    # extras must stay in pending, not be silently dropped.
                    if len(ready) < num_returns:
                        ready.append(pending.pop(f))
        finally:
            for f in pending:
                f.cancel()
        return ready, [r for r in refs if r not in ready]

    # ------------------------------------------------------------------
    # Task submission
    # ------------------------------------------------------------------

    def submit_task(self, fn, args, kwargs, opts) -> list[ObjectRef]:
        task_id = TaskID.of()
        num_returns = opts.get("num_returns", 1)
        refs = [ObjectRef(ObjectID.for_return(task_id, i), self.address)
                for i in range(num_returns)]
        for ref in refs:
            st = self.objects.setdefault(ref.id, _ObjectState())
            st.producing_task = task_id
        # Fast path: build the spec in the calling thread and hand it to the
        # event loop fire-and-forget.  The blocking io.run round trip (two
        # thread handoffs per submit, ~2.5ms measured) is only needed when
        # something requires the loop: first-time fn export, an uncached
        # runtime_env descriptor, or args big enough to go through the store.
        if not self._launch_sync(fn, args, kwargs, opts, task_id):
            self.io.run(
                self._prepare_and_launch(fn, args, kwargs, opts, task_id))
        return refs

    def _launch_sync(self, fn, args, kwargs, opts, task_id) -> bool:
        fn_key = self.fn_manager.export_cached(fn)
        if fn_key is None:
            return False
        user_env = opts.get("runtime_env")
        renv_desc = {}
        if user_env:
            import json as _json
            renv_desc = self._renv_cache.get(
                _json.dumps(user_env, sort_keys=True, default=str))
            if renv_desc is None:
                return False
        pins: list = []          # applied only if the fast path commits
        packed: list = []

        def pack(value):
            if isinstance(value, ObjectRef):
                pins.append(value)
                return RefArg(value.id.binary(),
                              value.owner_address or self.address)
            sv = ser.serialize(value, ref_sink=pins.append)
            if sv.total_size >= INLINE_LIMIT:
                return None      # store promotion may spill -> loop path
            return ValueArg(sv.to_bytes(), sv.metadata)

        pargs = []
        for a in args:
            p = pack(a)
            if p is None:
                return False
            pargs.append(p)
        pkwargs = {}
        for k, v in kwargs.items():
            p = pack(v)
            if p is None:
                return False
            pkwargs[k] = p
        # Per-options invariants (resources parse, name, retry fields)
        # compute once per RemoteFunction: the opts dict is immutable
        # after validation and identity-stable, and the cache pins it so
        # an id() can never be recycled by a different dict.
        cached = self._opts_cache.get(id(opts))
        if cached is None or cached[0] is not opts:
            cached = (opts, {
                "num_returns": opts.get("num_returns", 1),
                "resources": Resources.from_options(opts),
                "max_retries": opts.get("max_retries", 3),
                "retry_exceptions": bool(opts.get("retry_exceptions",
                                                  False)),
                "scheduling_strategy": (opts.get("scheduling_strategy")
                                        or "DEFAULT"),
                "node_affinity": opts.get("_node_id"),
                "placement_group": _pg_id_of(opts.get("placement_group")),
                "bundle_index": opts.get("placement_group_bundle_index",
                                         -1),
            })
            if len(self._opts_cache) > 4096:
                self._opts_cache.clear()
            self._opts_cache[id(opts)] = cached
        c = cached[1]
        spec = TaskSpec(
            task_id=task_id,
            job_id=self.job_id or JobID.nil(),
            name=getattr(fn, "__qualname__", str(fn)),
            fn_key=fn_key,
            args=pargs,
            kwargs=pkwargs,
            num_returns=c["num_returns"],
            resources=c["resources"],
            max_retries=c["max_retries"],
            retry_exceptions=c["retry_exceptions"],
            owner_address=self.address,
            scheduling_strategy=c["scheduling_strategy"],
            node_affinity=c["node_affinity"],
            placement_group=c["placement_group"],
            bundle_index=c["bundle_index"],
            runtime_env=renv_desc,
        )
        spec.trace_ctx = tracing.current_context()
        # Task-lifecycle spans only exist under an explicit trace: the
        # untraced hot path pays a single None check per site.
        tok_submit = (spans.begin("sched", "submit", ctx=spec.trace_ctx,
                                  name=spec.name)
                      if spec.trace_ctx is not None else None)
        for r in pins:
            self._pin_serialized_ref(r)
        pending = _PendingTask(
            spec=spec, retries_left=spec.max_retries, future=None,
            lineage=True)
        renv_key = id(renv_desc) if user_env else 0
        sk = c.get("_sk")
        if sk is None or sk[0] != renv_key:
            sk = (renv_key, self._sched_key(spec, ()))
            c["_sk"] = sk
        pending.sched_key = sk[1]
        if self._native_on:
            # Pack the native task descriptor off the event loop: dispatch
            # hands it to the C codec (taskrpc.cc tpt_send_specs), which
            # splices it with the per-(fn, opts) template into TaskSpecP
            # wire bytes — no Python serialization of the spec at all.
            tpl = c.get("_tpl_key")
            if tpl is None or tpl[0] != (fn_key, renv_key):
                tpl_bytes = spec_codec.build_template(
                    job_id=spec.job_id.binary(), name=spec.name,
                    fn_key=fn_key, num_returns=c["num_returns"],
                    resources=c["resources"],
                    max_retries=c["max_retries"],
                    retry_exceptions=c["retry_exceptions"],
                    owner_address=self.address,
                    scheduling_strategy=c["scheduling_strategy"],
                    runtime_env=renv_desc)
                # Dedupe by CONTENT: per-call .options() mints a fresh
                # opts dict every submit, and identity-keyed ids would
                # leak a new template into the C registry each time.
                # Distinct contents ~ distinct (fn, options) pairs —
                # bounded in any sane program, like exported fns.
                ent = self._tpl_content.get(tpl_bytes)
                if ent is None:
                    ent = (next(self._tpl_ids), tpl_bytes)
                    self._tpl_content[tpl_bytes] = ent
                tpl = ((fn_key, renv_key), ent)
                c["_tpl_key"] = tpl
            pending.template = tpl[1]
            trace_blob = (_pickle.dumps(spec.trace_ctx, 5)
                          if spec.trace_ctx is not None else None)
            pending.payload = spec_codec.pack_desc(
                tpl[1][0], 0, 0, task_id.binary(), trace_blob,
                pargs, pkwargs)
        self.tasks[task_id] = pending
        # Zero-hop dispatch: a dependency-free task whose scheduling key
        # already holds a lease with a free slot goes to the wire from
        # THIS thread — no event-loop wake on submit (the dominant cost
        # of a sync round trip on a one-core host).
        if pending.payload is not None and not pins and self._native_sub:
            sched = self._lease_cache.get(pending.sched_key)
            if sched is not None and sched.try_direct(pending, spec):
                spans.end(tok_submit, zero_hop=True)
                return True
        if tok_submit is not None:
            # Queue time = enqueue here until a scheduler claims a lease
            # slot in _dispatch; the token rides on the pending task.
            pending.q_span = spans.begin("sched", "sched_queue",
                                         ctx=spec.trace_ctx,
                                         name=spec.name)
        self._enqueue_fast(("task", task_id))
        spans.end(tok_submit)
        return True

    def _enqueue_fast(self, item):
        """Queue a loop-side dispatch, waking the loop once per burst (the
        GIL makes the flag check/append atomic enough: the drain clears
        the flag BEFORE popping, so late appends re-schedule)."""
        self._fast_q.append(item)
        if not self._fast_scheduled:
            self._fast_scheduled = True
            self.io.loop.call_soon_threadsafe(self._drain_fast)

    def _drain_fast(self):
        self._fast_scheduled = False
        q = self._fast_q
        # ONE shared per-worker batch for the whole burst — actor pushes
        # AND normal-task dispatches coalesce into one library call per
        # worker (a per-_pump dict would flush single-payload batches).
        batches: dict = {}   # native addr -> [(payload, cb)]
        while q:
            kind, *rest = q.popleft()
            if kind == "task":
                self._fast_submit(rest[0], batches=batches)
            else:
                self._fast_submit_actor(*rest, batches=batches)
        if not batches:
            return
        if self._batch_wait_s > 0:
            # Park this burst's batches in the tick dict: more
            # submissions arriving within the window append to the same
            # per-worker vectors and ship in ONE call_spec_batch.
            tb = self._tick_batches
            for naddr, items in batches.items():
                tb.setdefault(naddr, []).extend(items)
            if not self._tick_flush_scheduled:
                self._tick_flush_scheduled = True
                self.io.loop.call_later(self._batch_wait_s,
                                        self._flush_tick_batches)
            return
        for naddr, items in batches.items():
            self._ship_batch(naddr, items)

    def _ship_batch(self, naddr, items):
        """Flush one per-worker dispatch batch.  Items carry an optional
        `sched/dispatch` span token in slot 3: the span covers dispatch
        DECISION through this ship (the actual scheduler work); the
        residency tail — shipped until the push completes — is a
        separate `sched/inflight` span closed by each completion
        callback, so pipelined waiting is never booked as dispatch."""
        self._native_sub.call_spec_batch(
            naddr, [(p, t, cb) for p, t, cb, _tok in items])
        for _p, _t, _cb, tok in items:
            if tok is not None:
                spans.end(tok)

    def _shared_batches(self) -> dict:
        """Per-loop-tick native dispatch batch: every _pump triggered
        inside one completion batch appends here, and ONE call_soon'd
        flush ships a single call_spec_batch per worker.  Without this,
        each completion's pump dispatched 1-3 tasks in its own library
        call (measured: 1,373 batches for 4,000 tasks)."""
        if not self._tick_flush_scheduled:
            self._tick_flush_scheduled = True
            self.io.loop.call_soon(self._flush_tick_batches)
        return self._tick_batches

    def _flush_tick_batches(self):
        self._tick_flush_scheduled = False
        b = self._tick_batches
        if not b:
            return
        self._tick_batches = {}
        if not self._native_sub:
            return
        for naddr, items in b.items():
            self._ship_batch(naddr, items)

    def _pending_dep_events(self, spec: TaskSpec) -> list:
        """asyncio.Events for this task's UNRESOLVED owned dependencies.

        Dependency gating (reference: raylet dependency manager,
        task_dependency_manager.h — a task is not dispatched until its
        args are available): normal tasks execute INLINE in per-worker
        FIFO order, so a task pushed ahead of its not-yet-finished
        producer would block the worker its producer needs — a
        head-of-line deadlock when both land on one worker.  Holding
        dispatch until owned deps complete makes the order safe by
        construction.  Borrowed refs (owner elsewhere) stay eager: their
        producers were submitted by another owner, so no local FIFO
        ordering exists to violate, and the worker-side poll makes
        progress independently."""
        evs = []
        for arg in list(spec.args) + list(spec.kwargs.values()):
            if not isinstance(arg, RefArg):
                continue
            st = self.objects.get(ObjectID(arg.id_binary))
            if st is not None and st.pending:
                if st.event is None:
                    st.event = asyncio.Event()
                evs.append((ObjectID(arg.id_binary), st))
        return evs

    async def _submit_after_deps(self, task_id, deps):
        await self._await_deps(deps)
        self._fast_submit(task_id)

    async def _await_deps(self, deps) -> None:
        for _oid, st in deps:
            while st.pending:
                ev = st.event
                if ev is None:
                    ev = st.event = asyncio.Event()
                try:
                    # Bounded wait: lineage reconstruction replaces the
                    # event object, so re-read it instead of blocking on
                    # a stale one forever.
                    await asyncio.wait_for(ev.wait(), 1.0)
                except asyncio.TimeoutError:
                    pass

    def _fast_submit(self, task_id, batches=None):
        """Loop-side entry for fast-path tasks: enqueue on the scheduling-
        key scheduler with a direct-completion sink (no coroutine, no
        future).  Placement/affinity strategies take the coroutine path.
        With `batches`, dispatches accumulate for the caller's one-call-
        per-worker flush (_drain_fast)."""
        pending = self.tasks.get(task_id)
        if pending is None:
            return
        spec = pending.spec
        deps = self._pending_dep_events(spec)
        if deps:
            asyncio.ensure_future(self._submit_after_deps(task_id, deps))
            return
        if (spec.placement_group is not None
                or spec.scheduling_strategy not in (None, "DEFAULT")
                or spec.node_affinity):
            asyncio.ensure_future(self._run_task_to_completion(task_id))
            return
        key = pending.sched_key
        if key is None:
            key = self._sched_key(spec, ())
        sched = self._lease_cache.get(key)
        if sched is None:
            sched = self._lease_cache[key] = _KeyScheduler(
                self, key, spec, [])
        sched.submit_nowait(spec, batches=batches)

    async def _resume_task_fast(self, task_id: TaskID, exc):
        """Apply one failure outcome to a fast-path task, then continue in
        the standard retry loop (mirrors _run_task_to_completion's except
        arms; exc None = app error under retry_exceptions)."""
        from ray_tpu.exceptions import TaskCancelledError
        pending = self.tasks.get(task_id)
        if pending is None:
            return
        spec = pending.spec
        if pending.cancelled:
            self._complete_task_error(
                spec, TaskCancelledError(f"task {spec.name} cancelled"))
            return
        if exc is None:
            pending.retries_left -= 1
            await self._run_task_to_completion(task_id, exclusive=True)
        elif isinstance(exc, _RetryableSubmitError):
            if exc.busy:
                await asyncio.sleep(0.1)
                await self._run_task_to_completion(task_id)
            elif pending.retries_left > 0:
                pending.retries_left -= 1
                await self._run_task_to_completion(task_id, exclusive=True)
            else:
                self._complete_task_error(
                    spec, WorkerCrashedError(f"task {spec.name}: {exc}"))
        else:
            self._complete_task_error(spec, exc)

    async def _build_runtime_env(self, user_env) -> dict:
        """Package a user runtime_env once per unique value (content-
        addressed uploads make repeats cheap anyway)."""
        if not user_env:
            return {}
        import json as _json

        from ray_tpu._private import runtime_env as renv
        cache_key = _json.dumps(user_env, sort_keys=True, default=str)
        cached = self._renv_cache.get(cache_key)
        if cached is None:
            cached = await renv.build_descriptor(user_env, self._kv_call)
            self._renv_cache[cache_key] = cached
        return cached

    async def _prepare_and_launch(self, fn, args, kwargs, opts, task_id):
        fn_key = await self.fn_manager.export(self._job_int(), fn)
        spec = TaskSpec(
            task_id=task_id,
            job_id=self.job_id or JobID.nil(),
            name=getattr(fn, "__qualname__", str(fn)),
            fn_key=fn_key,
            args=[await self._pack_arg(a) for a in args],
            kwargs={k: await self._pack_arg(v) for k, v in kwargs.items()},
            num_returns=opts.get("num_returns", 1),
            resources=Resources.from_options(opts),
            max_retries=opts.get("max_retries", 3),
            retry_exceptions=bool(opts.get("retry_exceptions", False)),
            owner_address=self.address,
            scheduling_strategy=opts.get("scheduling_strategy") or "DEFAULT",
            node_affinity=opts.get("_node_id"),
            placement_group=_pg_id_of(opts.get("placement_group")),
            bundle_index=opts.get("placement_group_bundle_index", -1),
            runtime_env=await self._build_runtime_env(
                opts.get("runtime_env")),
        )
        spec.trace_ctx = tracing.current_context()
        self.tasks[task_id] = _PendingTask(
            spec=spec, retries_left=spec.max_retries, future=None, lineage=True)
        asyncio.ensure_future(self._run_task_to_completion(task_id))

    def _job_int(self) -> int:
        return int.from_bytes((self.job_id or JobID.nil()).binary(), "little")

    async def _pack_arg(self, value):
        if isinstance(value, ObjectRef):
            self._pin_serialized_ref(value)
            return RefArg(value.id.binary(), value.owner_address or self.address)
        sv = ser.serialize(value, ref_sink=self._pin_serialized_ref)
        if sv.total_size >= INLINE_LIMIT:
            # Promote big args to the object store (reference: args >100KB go
            # through plasma, _raylet.pyx submit_task).
            oid = ObjectID.for_put(self.current_task_id, self._next_put_index())
            try:
                self._store_owned_value(oid, sv)
            except ObjectStoreFullError:
                for attempt in range(3):
                    freed = await self._request_spill(sv.total_size)
                    try:
                        self._store_owned_value(oid, sv)
                        break
                    except ObjectStoreFullError:
                        if not freed:
                            await asyncio.sleep(0.2)
                else:
                    self._store_owned_value(oid, sv)
            st = self.objects[oid]
            st.pins += 1
            return RefArg(oid.binary(), self.address)
        return ValueArg(sv.to_bytes(), sv.metadata)

    def cancel_task(self, ref: ObjectRef, force: bool = False,
                    recursive: bool = True):
        """Cancel the task producing `ref` (reference: worker.py
        ray.cancel:2793 + core_worker.proto CancelTask:433)."""
        st = self.objects.get(ref.id)
        if st is None or st.producing_task is None:
            raise ValueError(
                "ray_tpu.cancel() only supports task returns; use "
                "ray_tpu.kill() for actors")
        pending = self.tasks.get(st.producing_task)
        if pending is None or not st.pending:
            return  # already finished
        pending.cancelled = True
        self.io.run(self._cancel_pending(pending, force), timeout=15)

    async def _cancel_pending(self, pending: _PendingTask, force: bool):
        from ray_tpu.exceptions import TaskCancelledError
        task_id = pending.spec.task_id
        # Still queued client-side: drop it from its key scheduler.
        for sched in list(self._lease_cache.values()):
            for item in list(sched.queue):
                spec, fut, _excl = item
                if spec.task_id == task_id:
                    try:
                        sched.queue.remove(item)
                    except ValueError:
                        continue
                    exc = TaskCancelledError(f"task {spec.name} cancelled")
                    if fut is None:
                        self._complete_task_error(spec, exc)
                    elif not fut.done():
                        fut.set_exception(exc)
                    sched._maybe_gc()
                    return
        # Already pushed: cancel at the executing worker.
        if pending.worker_address:
            try:
                await self.pool.get(pending.worker_address).call(
                    "CoreWorker", "CancelTask",
                    {"task_id": task_id.binary(), "force": force},
                    timeout=10)
            except Exception:
                pass

    async def _run_task_to_completion(self, task_id: TaskID,
                                      exclusive: bool = False):
        from ray_tpu.exceptions import TaskCancelledError
        pending = self.tasks.get(task_id)
        spec = pending.spec
        # Dependency gate (see _pending_dep_events): never push a task
        # ahead of its unfinished producer.
        await self._await_deps(self._pending_dep_events(spec))
        exclude: list = []
        # Resubmissions dispatch exclusively (see _KeyScheduler._pump's
        # dependency-safety sketch).
        while True:
            if pending.cancelled:
                self._complete_task_error(
                    spec, TaskCancelledError(f"task {spec.name} cancelled"))
                return
            try:
                reply = await self._submit_once(spec, exclude,
                                                exclusive=exclusive)
            except TaskCancelledError as e:
                self._complete_task_error(spec, e)
                return
            except _RetryableSubmitError as e:
                if pending.cancelled:
                    self._complete_task_error(
                        spec,
                        TaskCancelledError(f"task {spec.name} cancelled"))
                    return
                if e.busy:
                    # Saturated cluster: keep queueing, don't burn retries
                    # (the reference queues tasks in the raylet indefinitely).
                    exclude.clear()
                    await asyncio.sleep(0.1)
                    continue
                if pending.retries_left > 0:
                    pending.retries_left -= 1
                    exclusive = True
                    if e.node_id is not None:
                        exclude.append(e.node_id)
                    logger.info("retrying task %s (%s left): %s", spec.name,
                                pending.retries_left, e)
                    continue
                self._complete_task_error(
                    spec, WorkerCrashedError(f"task {spec.name}: {e}"))
                return
            except Exception as e:  # scheduling errors etc.
                self._complete_task_error(spec, e)
                return
            err = reply.get("error")
            if err is not None and spec.retry_exceptions \
                    and pending.retries_left > 0 \
                    and not pending.cancelled \
                    and not isinstance(err, TaskCancelledError):
                pending.retries_left -= 1
                continue
            self._complete_task_reply(spec, reply)
            return

    def _sched_key(self, spec: TaskSpec, exclude) -> tuple:
        """Reference SchedulingKey (direct_task_transport.h:53-55):
        tasks with identical scheduling requirements share leases."""
        from ray_tpu._private import runtime_env as renv
        return (tuple(sorted(spec.resources.to_dict().items())),
                spec.scheduling_strategy,
                spec.placement_group.hex() if spec.placement_group else None,
                spec.bundle_index, spec.node_affinity, tuple(exclude),
                renv.env_hash(spec.runtime_env))

    async def _push_on_lease(self, spec: TaskSpec, lease: dict):
        addr = lease["worker_address"]
        reply = await self._native_call_worker(addr, spec)
        if reply is None:  # peer (or self) has no native plane
            req = {"spec": spec, "caller": self.worker_id.binary()}
            reply = await self.pool.get(addr).call(
                "CoreWorker", "PushTask", req, timeout=None)
        return reply

    async def _return_lease(self, lease: dict, kill: bool = False):
        try:
            await self.pool.get(lease["node_address"]).call(
                "NodeManager", "ReturnWorker",
                {"lease_id": lease["lease_id"], "kill": kill}, timeout=5)
        except Exception:
            pass

    async def _drain_leases(self):
        scheds = list(self._lease_cache.values())
        self._lease_cache.clear()
        for sched in scheds:
            await sched.drain()

    async def _submit_once(self, spec: TaskSpec, exclude,
                           exclusive: bool = False):
        """Queue the task under its scheduling key; the per-key scheduler
        pipelines queued tasks onto held worker leases (reference:
        direct_task_transport.h OnWorkerIdle:151, lease request rate
        limiting :59)."""
        key = self._sched_key(spec, exclude)
        sched = self._lease_cache.get(key)
        if sched is None:
            sched = self._lease_cache[key] = _KeyScheduler(
                self, key, spec, list(exclude))
        return await sched.submit(spec, exclusive=exclusive)

    async def _resolve_bundle(self, spec: TaskSpec):
        """Map (placement_group, bundle_index) to the bundle's node + lease
        bundle key, waiting for the PG to finish scheduling."""
        reply = await self.gcs.call(
            "Gcs", "get_placement_group",
            {"pg_id": spec.placement_group, "wait_s": 30})
        info = reply.get("info")
        if info is None or info.state == "REMOVED":
            raise ValueError(
                f"placement group {spec.placement_group.hex()[:8]} is "
                f"{'missing' if info is None else 'removed'}")
        if info.state != "CREATED":
            raise _RetryableSubmitError("placement group not ready",
                                        None, busy=True)
        demand = spec.resources.to_dict()

        def bundle_fits(b: dict) -> bool:
            return all(b.get(k, 0.0) + 1e-9 >= v
                       for k, v in demand.items() if v > 0)

        idx = spec.bundle_index
        if idx < 0:
            # Any bundle whose RESERVATION can fit the demand; rotate for
            # balance.  No bundle large enough = permanent infeasibility.
            feasible = [i for i, b in enumerate(info.bundles)
                        if bundle_fits(b)]
            if not feasible:
                raise ValueError(
                    f"task {spec.name} demands {demand}, which exceeds "
                    f"every bundle of placement group "
                    f"{spec.placement_group.hex()[:8]}")
            rr = self._pg_rr.get(spec.placement_group, 0)
            idx = feasible[rr % len(feasible)]
            self._pg_rr[spec.placement_group] = rr + 1
        elif idx >= len(info.bundles):
            raise ValueError(f"bundle index {idx} out of range "
                             f"({len(info.bundles)} bundles)")
        elif not bundle_fits(info.bundles[idx]):
            raise ValueError(
                f"task {spec.name} demands {demand}, which exceeds bundle "
                f"{idx} ({info.bundles[idx]}) of placement group "
                f"{spec.placement_group.hex()[:8]}")
        # The PG record already carries the bundle's node and address — no
        # extra get_nodes round-trip; a dead node surfaces as a failed
        # lease RPC, which is retryable anyway.
        node_id, address = info.bundle_nodes[idx], info.bundle_addresses[idx]
        if node_id is None or not address:
            raise _RetryableSubmitError("bundle unplaced", None, busy=True)
        node = _BundleNode(address=address, node_id=node_id)
        return node, (spec.placement_group.hex(), idx)

    def _complete_task_reply(self, spec: TaskSpec, reply):
        returns = reply.get("returns", [])
        err = reply.get("error")
        for i in range(spec.num_returns):
            oid = ObjectID.for_return(spec.task_id, i)
            st = self.objects.setdefault(oid, _ObjectState())
            if err is not None:
                st.error = err
            else:
                kind, payload, meta = returns[i]
                if kind == "inline":
                    st.inline = (payload, meta)
                else:  # "location"
                    st.locations.add(payload)
            with self._obj_lock:
                st.pending = False   # publish flag: set last (see get())
            self._signal_ready(oid, st)
        self._release_arg_pins(spec)

    def _complete_task_error(self, spec: TaskSpec, exc: BaseException):
        for i in range(spec.num_returns):
            oid = ObjectID.for_return(spec.task_id, i)
            st = self.objects.setdefault(oid, _ObjectState())
            st.error = exc
            with self._obj_lock:
                st.pending = False   # publish flag: set last (see get())
            self._signal_ready(oid, st)
        self._release_arg_pins(spec)

    def _release_arg_pins(self, spec: TaskSpec):
        if not spec.args and not spec.kwargs:
            return
        for arg in list(spec.args) + list(spec.kwargs.values()):
            if isinstance(arg, RefArg):
                oid = ObjectID(arg.id_binary)
                with self._obj_lock:
                    st = self.objects.get(oid)
                    if st is not None:
                        st.pins = max(0, st.pins - 1)
                if st is not None:
                    self._maybe_free(oid)
                elif arg.owner_address not in ("", self.address):
                    asyncio.ensure_future(
                        self.pool.get(arg.owner_address).call(
                            "CoreWorker", "RemoveBorrow",
                            {"id": arg.id_binary}))

    async def _try_reconstruct(self, ref: ObjectRef) -> bool:
        """Lineage reconstruction: resubmit the producing task
        (reference: object_recovery_manager.h:41).

        Retry accounting: exactly ONE retry is burned per lost-output
        event regardless of how many getters notice — concurrent getters
        (and getters of sibling returns of the same task) piggyback on
        the in-flight resubmission via `_reconstructing` instead of each
        decrementing `retries_left` and racing duplicate resubmits."""
        st = self.objects.get(ref.id)
        if st is None or st.producing_task is None:
            return False
        tid = st.producing_task
        inflight = self._reconstructing.get(tid)
        if inflight is not None:
            await inflight.wait()
            return True
        pending = self.tasks.get(tid)
        if pending is None or pending.retries_left <= 0:
            return False
        pending.retries_left -= 1
        done = asyncio.Event()
        self._reconstructing[tid] = done
        try:
            for i in range(pending.spec.num_returns):
                oid = ObjectID.for_return(pending.spec.task_id, i)
                rst = self.objects.setdefault(oid, _ObjectState())
                rst.pending = True
                rst.inline = None
                rst.error = None
                rst.locations.clear()
                rst.event = asyncio.Event()
            logger.info("reconstructing %s via task %s", ref.id,
                        pending.spec.name)
            await self._run_task_to_completion(tid)
        finally:
            self._reconstructing.pop(tid, None)
            done.set()
        return True

    # ------------------------------------------------------------------
    # Actors
    # ------------------------------------------------------------------

    def create_actor(self, cls, args, kwargs, opts) -> ActorID:
        actor_id = ActorID.of(self.job_id or JobID.nil())
        if opts.get("name") or opts.get("get_if_exists"):
            # Named actors need the registration reply (it may resolve to
            # an existing actor's id).
            return self.io.run(
                self._create_actor_async(actor_id, cls, args, kwargs, opts))
        # Anonymous actors register ASYNCHRONOUSLY (reference:
        # core_worker actor creation is non-blocking; an actor storm must
        # pipeline registrations, not serialize on one GCS round trip per
        # handle).  The handle is immediately usable: method submission
        # waits in _resolve_actor while the id is in _pending_actor_reg.
        self._pending_actor_reg.add(actor_id)
        asyncio.run_coroutine_threadsafe(
            self._register_actor_bg(actor_id, cls, args, kwargs, opts),
            self.io.loop)
        return actor_id

    async def _register_actor_bg(self, actor_id, cls, args, kwargs, opts):
        try:
            await self._create_actor_async(actor_id, cls, args, kwargs,
                                           opts)
        except Exception:
            # Surfaces as ActorDiedError("unknown actor") at first use.
            logger.exception("background actor registration failed")
        finally:
            self._pending_actor_reg.discard(actor_id)

    async def _create_actor_async(self, actor_id, cls, args, kwargs, opts):
        from ray_tpu._private.protocol import ActorInfo
        fn_key = await self.fn_manager.export(self._job_int(), cls)
        spec = TaskSpec(
            task_id=TaskID.of(actor_id),
            job_id=self.job_id or JobID.nil(),
            name=f"{cls.__name__}.__init__",
            fn_key=fn_key,
            args=[await self._pack_arg(a) for a in args],
            kwargs={k: await self._pack_arg(v) for k, v in kwargs.items()},
            # Reference semantics: a default actor takes 1 CPU for scheduling
            # but 0 while running, so resident actors don't starve tasks.
            resources=Resources.from_options(opts, default_cpu=0.0),
            owner_address=self.address,
            actor_id=actor_id,
            actor_creation=True,
            max_concurrency=opts.get("max_concurrency") or 0,
            placement_group=_pg_id_of(opts.get("placement_group")),
            bundle_index=opts.get("placement_group_bundle_index", -1),
            runtime_env=await self._build_runtime_env(
                opts.get("runtime_env")),
        )
        spec.trace_ctx = tracing.current_context()
        info = ActorInfo(
            actor_id=actor_id,
            name=opts.get("name") or "",
            namespace=opts.get("namespace") or "default",
            class_name=cls.__name__,
            owner_address=self.address,
            max_restarts=opts.get("max_restarts", 0) or 0,
            lifetime_detached=(opts.get("lifetime") == "detached"),
            creation_spec=spec,
            resources=Resources.from_options(opts, default_cpu=0.0),
        )
        reply = await self.gcs.call(
            "Gcs", "register_actor",
            {"info": info, "get_if_exists": opts.get("get_if_exists", False)})
        if reply.get("existing") is not None:
            return reply["existing"].actor_id
        return actor_id

    # ------------------------------------------------------------------
    # Placement groups (client side)
    # ------------------------------------------------------------------

    def create_placement_group(self, bundles, strategy="PACK", name="",
                               lifetime=None):
        from ray_tpu._private.ids import PlacementGroupID
        from ray_tpu._private.protocol import PlacementGroupInfo
        pg_id = PlacementGroupID.from_random()
        info = PlacementGroupInfo(
            pg_id=pg_id, bundles=list(bundles), strategy=strategy, name=name,
            creator_job=self._job_int(),
            lifetime_detached=(lifetime == "detached"))
        self.io.run(self.gcs.call("Gcs", "create_placement_group",
                                  {"info": info}))
        return pg_id

    def wait_placement_group_ready(self, pg_id, timeout: float | None):
        deadline = None if timeout is None else timeout
        reply = self.io.run(self.gcs.call(
            "Gcs", "get_placement_group",
            {"pg_id": pg_id, "wait_s": 3600 if deadline is None else deadline}))
        info = reply.get("info")
        return info is not None and info.state == "CREATED"

    def get_placement_group_info(self, pg_id):
        return self.io.run(self.gcs.call(
            "Gcs", "get_placement_group", {"pg_id": pg_id}))["info"]

    def remove_placement_group(self, pg_id):
        self.io.run(self.gcs.call("Gcs", "remove_placement_group",
                                  {"pg_id": pg_id}))

    def list_placement_groups(self):
        return self.io.run(self.gcs.call(
            "Gcs", "list_placement_groups", {}))["placement_groups"]

    def _get_submitter(self, actor_id: ActorID) -> "_ActorSubmitter":
        sub = self.actor_submitters.get(actor_id)
        if sub is None:
            with self._obj_lock:
                sub = self.actor_submitters.setdefault(
                    actor_id, _ActorSubmitter(actor_id))
        return sub

    def submit_actor_task(self, actor_id: ActorID, method_name: str, args,
                          kwargs, opts) -> list[ObjectRef]:
        task_id = TaskID.of(actor_id)
        num_returns = opts.get("num_returns", 1)
        refs = [ObjectRef(ObjectID.for_return(task_id, i), self.address)
                for i in range(num_returns)]
        # Sequence numbers are claimed HERE, in the submitting thread, so
        # program order == seq order regardless of which path (sync fast /
        # loop slow) finishes building the spec first.
        sub = self._get_submitter(actor_id)
        with sub.lock:
            seq_no = sub.seq
            sub.seq += 1
        if not self._launch_actor_sync(sub, method_name, args, kwargs, opts,
                                       task_id, seq_no):
            self.io.run(self._prep_actor_task(sub, method_name, args, kwargs,
                                              opts, task_id, seq_no))
        return refs

    def _actor_spec(self, sub, method_name, packed_args, packed_kwargs,
                    opts, task_id, seq_no) -> TaskSpec:
        spec = TaskSpec(
            task_id=task_id,
            job_id=self.job_id or JobID.nil(),
            name=method_name,
            fn_key="",
            args=packed_args,
            kwargs=packed_kwargs,
            num_returns=opts.get("num_returns", 1),
            owner_address=self.address,
            actor_id=sub.actor_id,
            method_name=method_name,
            max_retries=opts.get("max_task_retries", 0),
        )
        spec.seq_no = seq_no
        spec.trace_ctx = tracing.current_context()
        return spec

    def _launch_actor_sync(self, sub, method_name, args, kwargs, opts,
                           task_id, seq_no) -> bool:
        """Caller-thread actor submission fast path (mirrors
        _launch_sync)."""
        pins: list = []

        def pack(value):
            if isinstance(value, ObjectRef):
                pins.append(value)
                return RefArg(value.id.binary(),
                              value.owner_address or self.address)
            sv = ser.serialize(value, ref_sink=pins.append)
            if sv.total_size >= INLINE_LIMIT:
                return None
            return ValueArg(sv.to_bytes(), sv.metadata)

        pargs = []
        for a in args:
            p = pack(a)
            if p is None:
                return False
            pargs.append(p)
        pkwargs = {}
        for k, v in kwargs.items():
            p = pack(v)
            if p is None:
                return False
            pkwargs[k] = p
        spec = self._actor_spec(sub, method_name, pargs, pkwargs, opts,
                                task_id, seq_no)
        for r in pins:
            self._pin_serialized_ref(r)
        pending = _PendingTask(
            spec=spec, retries_left=spec.max_retries, future=None)
        if self._native_on:
            with sub.lock:
                epoch_base = sub.epoch_base
            nret = spec.num_returns
            mret = spec.max_retries
            tpl = sub.tpl_cache.get((method_name, nret, mret))
            if tpl is None:
                tpl_bytes = spec_codec.build_template(
                    job_id=spec.job_id.binary(), name=method_name,
                    fn_key="", num_returns=nret,
                    resources=spec.resources, max_retries=mret,
                    retry_exceptions=False, owner_address=self.address,
                    actor_id=sub.actor_id.binary(),
                    method_name=method_name)
                tpl = (next(self._tpl_ids), tpl_bytes)
                sub.tpl_cache[(method_name, nret, mret)] = tpl
            pending.template = tpl
            trace_blob = (_pickle.dumps(spec.trace_ctx, 5)
                          if spec.trace_ctx is not None else None)
            pending.payload = spec_codec.pack_desc(
                tpl[0], seq_no, seq_no - epoch_base, task_id.binary(),
                trace_blob, pargs, pkwargs)
            pending.payload_epoch_base = epoch_base
        self.tasks[task_id] = pending
        self._enqueue_fast(("actor", sub, task_id))
        return True

    def _fast_submit_actor(self, sub, task_id, batches):
        """Loop-side actor dispatch: straight onto the native plane when
        the actor's address and native route are already known.  With
        `batches`, the push is accumulated for a one-call-per-worker
        flush by the caller (_drain_fast)."""
        pending = self.tasks.get(task_id)
        if pending is None:
            return
        addr = sub.address
        if (addr and pending.payload is not None and self._native_sub
                and pending.payload_epoch_base == sub.epoch_base):
            # The epoch check guards a submit-time-baked wire seq: a
            # restart detected between payload build and this dispatch
            # rebases epoch_base, and a stale (too-large) wire seq could
            # collide in the receiver's held window.  Rebased tasks take
            # the slow path, which computes the seq fresh per attempt.
            naddr = self._native_addrs.get(addr)
            if naddr:
                # Always batched: the only caller is _drain_fast, which
                # owns the burst's per-worker batch dict and flushes it.
                # Capture the incarnation now: by the time a failure
                # callback fires the submitter may point at a restart.
                ver = sub.version
                cb = (lambda status, data: self._on_actor_push_done(
                    sub, task_id, addr, status, data, ver))
                batches.setdefault(naddr, []).append(
                    (pending.payload, pending.template, cb, None))
                return
        asyncio.ensure_future(self._run_actor_task(sub, task_id))

    def _on_actor_push_done(self, sub, task_id, addr, status, data,
                            version: int = -1):
        pending = self.tasks.get(task_id)
        if pending is None:
            return
        spec = pending.spec
        if status == 0:
            try:
                reply = spec_codec.reply_from_wire(data)
            except BaseException as e:  # noqa: BLE001
                self._complete_task_error(spec, e)
                return
            sub.completed += 1
            self._complete_task_reply(spec, reply)
            return
        from ray_tpu._private.task_transport import ConnClosedError
        asyncio.ensure_future(
            self._actor_push_failed_cont(
                sub, task_id, addr,
                ConnClosedError("native connection closed"), version))

    async def _actor_push_failed_cont(self, sub, task_id, addr, exc,
                                      version: int = -1):
        pending = self.tasks.get(task_id)
        if pending is None:
            return
        if await self._actor_failure_step(sub, pending, pending.spec, addr,
                                          exc, version):
            return
        await self._run_actor_task(sub, task_id)

    async def _prep_actor_task(self, sub, method_name, args, kwargs,
                               opts, task_id, seq_no):
        spec = self._actor_spec(
            sub, method_name,
            [await self._pack_arg(a) for a in args],
            {k: await self._pack_arg(v) for k, v in kwargs.items()},
            opts, task_id, seq_no)
        self.tasks[task_id] = _PendingTask(
            spec=spec, retries_left=spec.max_retries, future=None)
        asyncio.ensure_future(self._run_actor_task(sub, task_id))

    async def _run_actor_task(self, sub: _ActorSubmitter, task_id: TaskID):
        pending = self.tasks[task_id]
        spec = pending.spec
        while True:
            try:
                addr = await self._resolve_actor(sub)
            except ActorDiedError as e:
                self._complete_task_error(spec, e)
                return
            ver = sub.version   # incarnation this dispatch targets
            try:
                reply = await self._native_call_worker(
                    addr, spec, wire_seq=spec.seq_no - sub.epoch_base)
                if reply is None:
                    req = {"spec": spec, "caller": self.worker_id.binary(),
                           "seq": spec.seq_no - sub.epoch_base}
                    reply = await self.pool.get(addr).call(
                        "CoreWorker", "PushTask", req, timeout=None)
                sub.completed += 1
                self._complete_task_reply(spec, reply)
                return
            except Exception as e:
                if await self._actor_failure_step(sub, pending, spec,
                                                  addr, e, ver):
                    return

    async def _actor_failure_step(self, sub, pending, spec, addr,
                                  e, version: int = -1) -> bool:
        """One transport-failure outcome for an actor call; True = the task
        completed terminally (with an error).

        `version` is the actor incarnation the caller OBSERVED when it
        dispatched (captured at resolve time).  The rebase below must run
        once per incarnation death: without the version guard, a stale
        failure callback arriving after the actor restarted on a reused
        address would rebase a LIVE incarnation's window and desequence
        every in-flight call."""
        self.pool.invalidate(addr)
        with sub.lock:
            if sub.address == addr and (version < 0
                                        or sub.version == version):
                # First detector of this incarnation's death: rebase
                # the wire sequence for the next incarnation.
                sub.address = None
                sub.epoch_base = sub.completed
        if pending.retries_left != 0:
            if pending.retries_left > 0:
                pending.retries_left -= 1
            await asyncio.sleep(0.1)
            return False
        # Terminal failure of an undelivered call: its wire slot on
        # the new incarnation will never be filled, so shift the
        # window or every later call would be held forever.
        with sub.lock:
            sub.completed += 1
            sub.epoch_base += 1
        self._complete_task_error(
            spec, ActorDiedError(sub.actor_id, f"call failed: {e}"))
        return True

    async def _resolve_actor(self, sub: _ActorSubmitter) -> str:
        if sub.address:
            return sub.address
        # Reference semantics: calls on a PENDING actor wait for it (a
        # storm's last actors can legitimately take minutes to schedule
        # on a saturated cluster); the cap only guards true losses.
        deadline = asyncio.get_running_loop().time() + 600
        while asyncio.get_running_loop().time() < deadline:
            reply = await self.gcs.call(
                "Gcs", "get_actor_info",
                {"actor_id": sub.actor_id, "wait_s": 5.0})
            info = reply["info"]
            if info is None:
                if sub.actor_id in self._pending_actor_reg:
                    # Our own registration is still in flight.
                    await asyncio.sleep(0.02)
                    continue
                raise ActorDiedError(sub.actor_id, "unknown actor")
            if info.state == "ALIVE":
                sub.address = info.address
                sub.version = info.version
                port = getattr(info, "native_port", 0)
                if port and info.address not in self._native_addrs:
                    # The actor record carries the native route: skip the
                    # per-worker NativePort discovery RPC.
                    self._native_addrs[info.address] = (
                        f"{info.address.rsplit(':', 1)[0]}:{port}")
                return info.address
            if info.state == "DEAD":
                raise ActorDiedError(sub.actor_id, info.death_cause)
            await asyncio.sleep(0.1)
        raise ActorDiedError(sub.actor_id, "timed out waiting for actor")

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        self.io.run(self.gcs.call("Gcs", "kill_actor",
                                  {"actor_id": actor_id,
                                   "no_restart": no_restart}))

    def get_named_actor(self, name: str, namespace: str = "default"):
        reply = self.io.run(self.gcs.call(
            "Gcs", "get_named_actor", {"name": name, "namespace": namespace}))
        return reply["info"]

    # ------------------------------------------------------------------
    # Reference counting (owner side)
    # ------------------------------------------------------------------

    def _pin_serialized_ref(self, ref: ObjectRef):
        if ref.owner_address in ("", self.address):
            with self._obj_lock:
                st = self.objects.get(ref.id)
                if st is not None:
                    st.pins += 1
        else:
            self.io.spawn(self.pool.get(ref.owner_address).call(
                "CoreWorker", "AddBorrow", {"id": ref.id.binary()}))

    def on_ref_created(self, ref: ObjectRef):
        if ref.owner_address in ("", self.address):
            with self._obj_lock:
                st = self.objects.setdefault(ref.id, _ObjectState())
                st.local_refs += 1

    def on_ref_deleted(self, ref: ObjectRef):
        if self._shutdown:
            return
        if ref.owner_address in ("", self.address):
            with self._obj_lock:
                st = self.objects.get(ref.id)
                if st is not None:
                    st.local_refs = max(0, st.local_refs - 1)
            self._maybe_free(ref.id)
        else:
            owner = self.borrowed.pop(ref.id, None)
            if owner:
                try:
                    self.io.spawn(self.pool.get(owner).call(
                        "CoreWorker", "RemoveBorrow", {"id": ref.id.binary()}))
                except Exception:
                    pass

    def on_ref_deserialized(self, ref: ObjectRef):
        if ref.owner_address not in ("", self.address):
            self.borrowed[ref.id] = ref.owner_address
            try:
                self.io.spawn(self.pool.get(ref.owner_address).call(
                    "CoreWorker", "AddBorrow", {"id": ref.id.binary()}))
            except Exception:
                pass

    def _maybe_free(self, oid: ObjectID):
        with self._obj_lock:
            st = self.objects.get(oid)
            if st is None or st.pending:
                return
            if st.local_refs > 0 or st.borrows > 0 or st.pins > 0:
                return
            self.objects.pop(oid, None)
        if st.locations:
            self.io.spawn(self._free_locations(oid, set(st.locations)))
        self.tasks.pop(st.producing_task, None)

    async def _free_locations(self, oid: ObjectID, locations):
        """Buffer frees and flush batched (one FreeObjects RPC per node per
        flush window) — per-object RPCs would clog the daemon under churn."""
        for loc in locations:
            self._free_buffer.setdefault(loc, []).append(oid.binary())
        if self._free_flusher is None or self._free_flusher.done():
            self._free_flusher = asyncio.ensure_future(self._flush_frees())

    async def _flush_frees(self):
        # Loop until the buffer is empty at a non-awaiting point: frees
        # that arrive DURING the RPC awaits below must not strand until
        # some later free reschedules the flusher.
        while True:
            await asyncio.sleep(0.05)
            buffered, self._free_buffer = self._free_buffer, {}
            if not buffered:
                return
            nodes = await self._node_table()
            for loc, ids in buffered.items():
                addr = nodes.get(loc)
                if addr:
                    try:
                        await self.pool.get(addr).call(
                            "NodeManager", "FreeObjects", {"ids": ids})
                    except Exception:
                        pass
            if not self._free_buffer:
                return

    # ------------------------------------------------------------------
    # Execution loop (worker mode)
    # ------------------------------------------------------------------

    def run_task_loop(self):
        """Blocks executing tasks until KillActor/shutdown
        (reference: CoreWorker::RunTaskExecutionLoop via default_worker.py).

        Actor tasks are dispatched by the actor's concurrency mode
        (reference: transport/concurrency_group_manager.h):
        - default: run inline on this thread, strictly serialized;
        - max_concurrency>1: run on a thread pool of that size;
        - async actor (any coroutine method): scheduled on a dedicated
          asyncio loop, bounded by a semaphore.
        """
        import contextlib
        stop = False
        while not stop:
            burst = [self.exec_queue.get()]
            while True:
                try:
                    burst.append(self.exec_queue.get_nowait())
                except queue.Empty:
                    break
            # Replies of a burst coalesce into one native flush per conn
            # (a per-reply enqueue costs an io wakeup; see NativeReceiver).
            rx = getattr(self, "_native_rx", None)
            scope = rx.batch_scope() if rx is not None \
                else contextlib.nullcontext()
            with scope:
                for item in burst:
                    if item is None:
                        stop = True
                        break
                    t0 = time.monotonic()
                    self._exec_one_item(item)
                    if rx is not None and time.monotonic() - t0 > 0.002:
                        # Don't hold fast tasks' replies behind a slow
                        # burst neighbour (head-of-line).
                        rx.flush_thread_batch()
        if self._exec_pool is not None:
            self._exec_pool.shutdown(wait=False)
        if self._async_loop is not None:
            self._async_loop.call_soon_threadsafe(self._async_loop.stop)

    def _exec_one_item(self, item):
        spec, done, loop = item
        is_actor_call = spec.actor_id is not None and not spec.actor_creation
        if is_actor_call and self._async_loop is not None:
            def _complete(r, d=done, lp=loop):
                if lp is None:
                    d(r)  # native done-sink: pickles + streams reply
                else:
                    lp.call_soon_threadsafe(
                        lambda: d.done() or d.set_result(r))
            asyncio.run_coroutine_threadsafe(
                self._execute_actor_async(spec, _complete),
                self._async_loop)
        elif is_actor_call and self._exec_pool is not None:
            self._exec_pool.submit(self._run_one, spec, done, loop)
        else:
            self._run_one(spec, done, loop)

    def _run_one(self, spec: TaskSpec, done, loop):
        try:
            reply = self._execute_task(spec)
        except BaseException as e:  # noqa: BLE001 - e.g. a cancel async-exc
            # landing in the sliver between the task body returning and the
            # running-task deregistration; don't kill the exec thread.
            reply = self._error_reply(spec, e)
        if loop is None:
            done(reply)  # native done-sink
        else:
            loop.call_soon_threadsafe(
                lambda d=done, r=reply: d.done() or d.set_result(r))

    def _setup_actor_execution(self, cls, spec: TaskSpec):
        """Choose the actor's execution mode after __init__ succeeds.
        spec.max_concurrency: 0 = unset; async actors then default to the
        reference's 1000, sync actors to 1 (an EXPLICIT 1 on an async actor
        serializes its tasks, as in the reference)."""
        import inspect as _inspect
        is_async = any(
            _inspect.iscoroutinefunction(getattr(cls, name, None))
            for name in dir(cls)
            if not name.startswith("__") or name == "__call__")
        mc = spec.max_concurrency
        if is_async:
            limit = mc if mc > 0 else 1000
            loop = asyncio.new_event_loop()
            self._async_loop = loop
            rx = getattr(self, "_native_rx", None)
            if rx is not None:
                rx.enable_tick_batching(loop)
            self._async_sem = asyncio.Semaphore(limit)
            threading.Thread(target=loop.run_forever, daemon=True,
                             name="actor-async-exec").start()
        elif mc > 1:
            from concurrent.futures import ThreadPoolExecutor
            self._exec_pool = ThreadPoolExecutor(
                max_workers=mc, thread_name_prefix="actor-exec")

    def _record_task_event(self, spec: TaskSpec, started: float,
                           span=None):
        """Buffer one execution event; a loop-side flusher ships batches.
        With tracing on, the event doubles as the task's SPAN: trace_id/
        span_id/parent_id group a driver's whole call tree in the
        timeline (reference: tracing_helper.py spans per task).  The hot
        path appends a tuple; dict shaping happens in the 1 Hz flusher."""
        self._task_events.append(
            (spec.task_id, spec.name, spec.actor_id, started, time.time(),
             span))
        if self._task_event_flusher is None:
            def _start_flusher():
                if self._task_event_flusher is None:
                    self._task_event_flusher = asyncio.ensure_future(
                        self._flush_task_events())
            self.io.loop.call_soon_threadsafe(_start_flusher)

    async def _flush_task_events(self):
        static = {
            "worker_id": self.worker_id.hex()[:12],
            "pid": os.getpid(),
            "node_id": self.node_id.hex()[:12] if self.node_id else "",
        }
        while not self._shutdown:
            await asyncio.sleep(1.0)
            if not self._task_events:
                continue
            batch, self._task_events = self._task_events, []
            events = []
            for task_id, name, actor_id, started, end, span in batch:
                ev = {
                    "task_id": task_id.hex(),
                    "name": name,
                    "actor_id": actor_id.hex() if actor_id else None,
                    "start": started,
                    "end": end,
                    **static,
                }
                if span is not None:
                    ev["trace_id"], ev["span_id"], ev["parent_id"] = span
                events.append(ev)
            try:
                await self.gcs.call("Gcs", "add_task_events",
                                    {"events": events})
            except Exception:
                pass

    def _pack_reply(self, spec: TaskSpec, result) -> dict:
        return {"returns": self._pack_returns(spec, result), "error": None}

    def _error_reply(self, spec: TaskSpec, e: BaseException) -> dict:
        from ray_tpu.exceptions import TaskCancelledError, TrainPreemptedError
        tb = traceback.format_exc()
        logger.info("task %s failed:\n%s", spec.name, tb)
        # TrainPreemptedError stays typed across the wire: the driver
        # routes it to the preemption recovery path (resume from the
        # grace-window save), not the crash path.
        err = e if isinstance(e, (TaskError, ActorDiedError,
                                  TaskCancelledError, TrainPreemptedError)) \
            else TaskError(spec.name, tb, None)
        return {"returns": [], "error": err}

    async def _execute_actor_async(self, spec: TaskSpec, complete):
        """Async-actor execution path: every method runs on the actor's
        event loop (reference semantics — a blocking sync method blocks the
        loop; use a threaded actor for blocking work).  Arg resolution may
        touch the network, so it runs in an executor, concurrently.
        `complete(reply_dict)` delivers the result (transport-agnostic)."""
        import inspect as _inspect
        async with self._async_sem:
            try:
                loop = asyncio.get_running_loop()

                async def resolve(a):
                    # Inline ValueArgs deserialize in-memory — no executor
                    # hop; only ObjectRef args (which may hit the network)
                    # go to the thread pool.
                    if isinstance(a, ValueArg):
                        return self._resolve_arg(a)
                    return await loop.run_in_executor(
                        None, self._resolve_arg, a)

                arg_vals, kw_vals = await asyncio.gather(
                    asyncio.gather(*[resolve(a) for a in spec.args]),
                    asyncio.gather(*[resolve(v)
                                     for v in spec.kwargs.values()]))
                kwargs = dict(zip(spec.kwargs.keys(), kw_vals))
                if self.actor_instance is None:
                    raise ActorDiedError(spec.actor_id, "no instance")
                self.current_task_id = spec.task_id
                self.current_task_spec = spec
                # Install the carried trace context: this coroutine runs
                # as its own asyncio task (own contextvar copy), so the
                # set is isolated per concurrent method call.
                span = tracing.enter_task(spec)
                tok_task = (spans.begin("sched", "task",
                                        ctx=(span[0], span[2]),
                                        sid=span[1], name=spec.name)
                            if span is not None else None)
                try:
                    method = getattr(self.actor_instance, spec.method_name)
                    result = method(*arg_vals, **kwargs)
                    if _inspect.iscoroutine(result):
                        result = await result
                finally:
                    spans.end(tok_task)
                    if span is not None:
                        tracing.exit_task()
                reply = self._pack_reply(spec, result)
            except BaseException as e:  # noqa: BLE001
                reply = self._error_reply(spec, e)
            finally:
                self.current_task_spec = None
            complete(reply)

    def _execute_task(self, spec: TaskSpec) -> dict:
        from ray_tpu.exceptions import TaskCancelledError
        from ray_tpu._private.fault_injection import get_chaos
        chaos = get_chaos()
        if chaos is not None and self.mode == "worker" \
                and chaos.kill_worker():
            # Injected preemption: die BEFORE touching the task, exactly
            # like a SIGKILL'd/preempted worker — the owner sees the
            # connection drop and must retry/reconstruct.
            logger.warning("chaos: killing worker before task %s", spec.name)
            events.record("proc", "chaos_kill", task=spec.name,
                          trace=getattr(spec, "trace_ctx", None))
            events.dump_crash("chaos_kill_worker")
            os._exit(1)
        _t0 = time.time()
        if spec.task_id in self._cancelled_exec:
            self._cancelled_exec.discard(spec.task_id)
            return {"returns": [],
                    "error": TaskCancelledError(f"task {spec.name} cancelled")}
        with self._cancel_lock:
            self._running_tasks[spec.task_id] = threading.get_ident()
        span = tracing.enter_task(spec)  # nested submits join the trace
        # The task's own span reuses enter_task's span id, so the phase
        # spans below (and any nested submits) hang off it as children.
        tok_task = (spans.begin("sched", "task", ctx=(span[0], span[2]),
                                sid=span[1], name=spec.name)
                    if span is not None else None)
        # An actor's construction (its arguments and class fetched, its
        # __init__ run) happens once a process: kept in the start-up
        # record, a child of the creating task where that was traced.
        tok_actor = (spans.begin("proc", "actor_init", pin=True,
                                 name=spec.name)
                     if spec.actor_creation else None)
        try:
            tok = spans.begin("sched", "arg_fetch",
                              n=len(spec.args) + len(spec.kwargs)) \
                if tok_task is not None else None
            t_args = time.perf_counter()
            args = [self._resolve_arg(a) for a in spec.args]
            kwargs = {k: self._resolve_arg(v) for k, v in spec.kwargs.items()}
            spans.end(tok)
            if spec.actor_creation:
                cls = self.fn_manager.fetch_cached(spec.fn_key) or \
                    self.io.run(self.fn_manager.fetch(spec.fn_key))
            took = time.perf_counter() - t_args
            if took >= _ARGS_PIN_S:
                # Unpickling what a task was given can import half the
                # program (a model's config brings jax): where it cost
                # this much it has a row the ring cannot lose.
                events.pin("sched", "arg_fetch", time.time() - took, took,
                           payload={"name": spec.name})
            self.current_task_id = spec.task_id
            self.current_task_spec = spec
            if spec.actor_creation:
                with spans.under(tok_actor):
                    self.current_actor_pg = spec.placement_group
                    self.actor_instance = cls(*args, **kwargs)
                self._setup_actor_execution(cls, spec)
                return {"returns": [], "error": None}
            tok = spans.begin("sched", "exec", name=spec.name) \
                if tok_task is not None else None
            if spec.actor_id is not None:
                if self.actor_instance is None:
                    raise ActorDiedError(spec.actor_id, "no instance")
                method = getattr(self.actor_instance, spec.method_name)
                result = method(*args, **kwargs)
                if asyncio.iscoroutine(result):
                    # Sync-mode actor with an occasional async method.
                    result = asyncio.run(result)
            else:
                fn = self.fn_manager.fetch_cached(spec.fn_key) or \
                    self.io.run(self.fn_manager.fetch(spec.fn_key))
                result = fn(*args, **kwargs)
            spans.end(tok)
            tok = spans.begin("sched", "result_seal") \
                if tok_task is not None else None
            reply = self._pack_reply(spec, result)
            spans.end(tok)
            return reply
        except BaseException as e:  # noqa: BLE001
            return self._error_reply(spec, e)
        finally:
            spans.end(tok_actor)
            spans.end(tok_task)
            if span is not None:
                tracing.exit_task()
            with self._cancel_lock:
                self._running_tasks.pop(spec.task_id, None)
            self._cancelled_exec.discard(spec.task_id)
            self._record_task_event(spec, _t0, span)
            # Don't leak this task's context (e.g. its placement group) to
            # whatever runs on this reused worker next.
            self.current_task_spec = None

    def _resolve_arg(self, arg):
        if isinstance(arg, ValueArg):
            return ser.deserialize(arg.data, arg.metadata)
        ref = ObjectRef(ObjectID(arg.id_binary), arg.owner_address,
                        _register=False)
        return self.get(ref)

    def _pack_returns(self, spec: TaskSpec, result) -> list:
        if spec.num_returns == 1:
            results = [result]
        else:
            results = list(result)
            if len(results) != spec.num_returns:
                raise ValueError(
                    f"task {spec.name} declared num_returns="
                    f"{spec.num_returns} but returned {len(results)} values")
        packed = []
        for i, value in enumerate(results):
            oid = ObjectID.for_return(spec.task_id, i)
            sv = ser.serialize(value, ref_sink=self._pin_serialized_ref)
            if sv.total_size < INLINE_LIMIT or self.store is None:
                packed.append(("inline", sv.to_bytes(), sv.metadata))
            else:
                if not self.store.contains(oid):
                    try:
                        view = self.store.create_object(
                            oid, sv.total_size, sv.metadata)
                        sv.write_into(view)
                        self.store.seal(oid)
                    except Exception:
                        packed.append(("inline", sv.to_bytes(), sv.metadata))
                        continue
                packed.append(("location", self.node_id.hex(), sv.metadata))
        return packed

    # ------------------------------------------------------------------

    def shutdown(self):
        self._shutdown = True
        object_ref_mod._install_hooks(None)
        try:
            self.io.run(self._drain_leases(), timeout=5)
        except Exception:
            pass
        if self.mode == "driver":
            # Job-scoped cleanup: non-detached placement groups (and their
            # reserved bundles) die with the driver (reference: GCS job
            # manager cleanup on driver exit).
            try:
                self.io.run(self.gcs.call(
                    "Gcs", "cleanup_job", {"job_id": self._job_int()},
                    timeout=10))
            except Exception:
                pass
        for native in (self._native_sub, self._native_rx):
            if native:
                try:
                    native.close()
                except Exception:
                    pass
        try:
            self.io.run(self.server.stop())
            self.io.run(self.pool.close_all())
            self.io.run(self.gcs.close())
        except Exception:
            pass
        self.io.stop()
        if self.store is not None:
            self.store.close()

    # hooks used by ObjectRef.future()/await
    def as_future(self, ref: ObjectRef):
        import concurrent.futures
        fut = concurrent.futures.Future()

        async def run():
            try:
                fut.set_result(await self._get_one(ref, None))
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)
        self.io.spawn(run())
        return fut

    async def await_ref(self, ref: ObjectRef):
        return await self._get_one(ref, None)


class _KeyScheduler:
    """Per-SchedulingKey task queue + lease pool.

    Reference: CoreWorkerDirectTaskSubmitter (direct_task_transport.h:75) —
    tasks queue client-side by key; worker leases are requested at a capped
    rate while the queue is non-empty; each granted lease executes queued
    tasks back-to-back (OnWorkerIdle) with ONE PushTask RPC per task; idle
    leases are returned after a TTL.
    """

    def __init__(self, worker: "CoreWorker", key: tuple, proto_spec,
                 exclude: list):
        # Flags snapshot (reference: max_pending_lease_requests / lease TTL
        # — RAY_TPU_* flags in _private/config.py).  Read once: these sit
        # in the per-task dispatch loop.
        from ray_tpu._private.config import GLOBAL_CONFIG
        self.MAX_PENDING_LEASES = GLOBAL_CONFIG.max_pending_lease_requests
        self.IDLE_TTL = GLOBAL_CONFIG.lease_idle_ttl_s
        self.DEPTH = GLOBAL_CONFIG.lease_pipeline_depth
        self.BATCH_MAX = max(1, GLOBAL_CONFIG.sched_batch_max)
        self.worker = worker
        self.key = key
        self.proto_spec = proto_spec     # any spec with this key (for pick)
        self.exclude = exclude
        self.queue: deque = deque()      # (spec, fut, exclusive)
        self.leases: list = []           # granted leases (dicts)
        self.pending_leases = 0          # requested-but-ungranted workers
        self._reaper = None
        # Guards lease membership + inflight counts: the submitting
        # thread may claim a slot directly (try_direct) while the loop
        # dispatches/reaps.  Loop-side sections are short and
        # uncontended in the common case.
        self.tlock = threading.Lock()

    @property
    def held(self):
        return len(self.leases)

    # -- public -----------------------------------------------------------
    async def submit(self, spec, exclusive: bool = False) -> dict:
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self.queue.append((spec, fut, exclusive))
        self._pump()
        return await fut

    def submit_nowait(self, spec, batches=None):
        """Fast-path enqueue: completion flows straight into the owner's
        object table (sink None) — no future, no coroutine.  An external
        `batches` dict lets a burst of submissions share one native
        flush per worker (_drain_fast owns the flush then)."""
        self.queue.append((spec, None, False))
        self._pump(batches)

    def try_direct(self, pending, spec) -> bool:
        """Caller-thread dispatch for a dependency-free native task:
        claim a free lease slot under tlock and write the frame from
        THIS thread (the C layer writevs inline on an idle connection)
        — the submit never touches the event loop.  Safe because a task
        with no ref args can never wait on anything, so putting it
        ahead of still-queued submissions cannot create a waits-on
        cycle (see _pump's dependency-safety sketch)."""
        worker = self.worker
        sub = worker._native_sub
        if not sub:
            return False
        with self.tlock:
            if self.queue:
                return False     # loop-side work queued: keep FIFO
            best = None
            for lease in self.leases:
                if lease["inflight"] < self.DEPTH and (
                        best is None
                        or lease["inflight"] < best["inflight"]):
                    best = lease
            if best is None:
                return False
            naddr = worker._native_addrs.get(best["worker_address"])
            if not naddr:
                return False
            best["inflight"] += 1
        pending.worker_address = best["worker_address"]
        tok = (spans.begin("sched", "dispatch", ctx=spec.trace_ctx,
                           name=spec.name, zero_hop=True)
               if getattr(spec, "trace_ctx", None) is not None else None)
        if tok is None:
            cb = (lambda status, data: self._on_push_done(
                spec, None, best, status, data))
        else:
            itok = spans.begin("sched", "inflight", ctx=spec.trace_ctx,
                               name=spec.name)

            def cb(status, data, _itok=itok):
                spans.end(_itok, status=status)
                self._on_push_done(spec, None, best, status, data)
        sub.call_spec_batch(naddr, [(pending.payload, pending.template,
                                     cb)])
        spans.end(tok)
        return True

    async def drain(self):
        if self._reaper is not None:
            self._reaper.cancel()
            await asyncio.gather(self._reaper, return_exceptions=True)
            self._reaper = None
        with self.tlock:
            leases, self.leases = self.leases, []
        for lease in leases:
            await self.worker._return_lease(lease)

    def purge_node(self, node_hex: str) -> int:
        """Forget every lease on a dead node WITHOUT a return RPC (the
        daemon is gone) and re-pump so queued work leases elsewhere.
        In-flight pushes on the purged leases fail through their own
        transport callbacks, which find the lease already removed and
        route each task into the normal retry machinery."""
        def _hex(nid):
            h = getattr(nid, "hex", None)
            return h() if callable(h) else nid
        with self.tlock:
            dead = [l for l in self.leases
                    if _hex(l.get("node_id")) == node_hex]
            for lease in dead:
                self.leases.remove(lease)
        for lease in dead:
            self.worker.pool.invalidate(lease["worker_address"])
            self.worker._native_addrs.pop(lease["worker_address"], None)
        if dead:
            self._pump()
        return len(dead)

    # -- internals ---------------------------------------------------------
    def _pump(self, batches=None):
        """Dispatch queued tasks onto held leases, several in flight per
        lease (reference OnWorkerIdle:151 pushes every queued task onto a
        granted lease; the receiver queues them).  Retried tasks dispatch
        exclusively (sole occupant of a lease): normal submissions enter
        worker FIFOs in program order, so a task can only ever wait behind
        strictly-earlier tasks — a retry would break that invariant and
        could park a dependency behind its dependent.

        Dependency-safety sketch: waits-on edges (arg refs) always point to
        earlier-submitted tasks; per-worker FIFOs are subsequences of
        submission order (exclusive retries exempt but never queued behind
        anything); hence the waits-on relation is acyclic and the earliest
        blocked task's dependency is always running or done."""
        flush_here = batches is None
        if batches is None:
            batches = {}   # native addr -> list[(payload, cb)]
        while self.queue:
            spec, sink, exclusive = self.queue[0]
            cap = 1 if exclusive else self.DEPTH
            with self.tlock:
                best = None
                for lease in self.leases:
                    if lease["inflight"] < cap and (
                            best is None
                            or lease["inflight"] < best["inflight"]):
                        best = lease
                if best is None or (exclusive and best["inflight"] > 0):
                    break
                best["inflight"] += 1
            self.queue.popleft()
            self._dispatch(spec, sink, best, batches)
        if flush_here and batches:
            for naddr, items in batches.items():
                self.worker._ship_batch(naddr, items)
        # Lease demand scales by pipeline depth (a lease carries DEPTH
        # tasks).  Anything still queued found every held lease full, so
        # the remaining queue needs NEW leases; only the number of
        # in-flight lease GRANTS is capped (reference
        # lease_policy/max_pending_lease_requests_per_scheduling_category)
        # — total held leases are bounded by cluster resources at the
        # hostd, not by the client.  Demand is amortized into batched
        # requests: ONE LeaseWorker RPC carries up to BATCH_MAX grants,
        # so a deep queue costs ceil(want / BATCH_MAX) round trips
        # instead of `want`.
        want = min((len(self.queue) + self.DEPTH - 1) // self.DEPTH
                   - self.pending_leases,
                   self.MAX_PENDING_LEASES - self.pending_leases)
        while want > 0:
            n = min(want, self.BATCH_MAX)
            want -= n
            self.pending_leases += n
            asyncio.ensure_future(self._acquire_lease(n))

    def _dispatch(self, spec, sink, lease, batches):
        """Native-route dispatches accumulate into `batches` (flushed by
        the _pump that owns the dict — one library call per worker);
        unknown routes (fresh worker, native off) take the coroutine
        path, which performs discovery."""
        worker = self.worker
        pending = worker.tasks.get(spec.task_id)
        if pending is not None:
            pending.worker_address = lease["worker_address"]
            if pending.q_span is not None:
                spans.end(pending.q_span)
                pending.q_span = None
        tok = (spans.begin("sched", "dispatch", ctx=spec.trace_ctx,
                           name=spec.name)
               if getattr(spec, "trace_ctx", None) is not None else None)
        if (pending is not None and pending.payload is not None
                and worker._native_sub):
            naddr = worker._native_addrs.get(lease["worker_address"])
            if naddr:
                if tok is None:
                    cb = (lambda status, data: self._on_push_done(
                        spec, sink, lease, status, data))
                else:
                    # Residency on the worker's pipeline (shipped ->
                    # push completion) is its own span; the dispatch
                    # token is closed by _ship_batch once the frame is
                    # handed to the transport.
                    itok = spans.begin("sched", "inflight",
                                       ctx=spec.trace_ctx, name=spec.name)

                    def cb(status, data, _itok=itok):
                        spans.end(_itok, status=status)
                        self._on_push_done(spec, sink, lease, status, data)
                batches.setdefault(naddr, []).append(
                    (pending.payload, pending.template, cb, tok))
                return
        asyncio.ensure_future(self._run_on_lease(spec, sink, lease, tok))

    def _on_push_done(self, spec, sink, lease, status, data):
        """Completion callback for zero-coroutine native pushes (runs
        inline on the io loop, one batch of these per loop wakeup)."""
        worker = self.worker
        if status != 0:
            worker.pool.invalidate(lease["worker_address"])
            with self.tlock:
                dead = lease in self.leases
                if dead:
                    self.leases.remove(lease)
            if dead:
                asyncio.ensure_future(
                    worker._return_lease(lease, kill=True))
            self._deliver(spec, sink, None, _RetryableSubmitError(
                "worker died: native connection closed",
                lease.get("node_id")))
            self._pump()
            return
        with self.tlock:
            lease["inflight"] -= 1
            if lease["inflight"] == 0:
                lease["idle_since"] = time.monotonic()
        try:
            reply = spec_codec.reply_from_wire(data)
        except BaseException as e:  # noqa: BLE001
            self._deliver(spec, sink, None, e)
            self._pump()
            return
        self._deliver(spec, sink, reply, None)
        if self._reaper is None:
            self._reaper = asyncio.ensure_future(self._reap_idle())
        # Completion batches deliver many of these callbacks per loop
        # tick; their re-dispatches coalesce into one flush per worker.
        self._pump(self.worker._shared_batches())

    def _deliver(self, spec, sink, reply, exc):
        """Resolve one dispatched task: slow path -> its future; fast path
        (sink None) -> finalize the owner's object table directly, with
        failures handed to the coroutine retry machinery."""
        worker = self.worker
        if sink is not None:
            if sink.done():
                return
            if exc is not None:
                sink.set_exception(exc)
            else:
                sink.set_result(reply)
            return
        if exc is not None:
            asyncio.ensure_future(
                worker._resume_task_fast(spec.task_id, exc))
            return
        err = reply.get("error")
        pending = worker.tasks.get(spec.task_id)
        if err is not None and spec.retry_exceptions \
                and pending is not None and pending.retries_left > 0 \
                and not pending.cancelled:
            from ray_tpu.exceptions import TaskCancelledError
            if not isinstance(err, TaskCancelledError):
                asyncio.ensure_future(
                    worker._resume_task_fast(spec.task_id, None))
                return
        worker._complete_task_reply(spec, reply)

    def _fail_one(self, exc: BaseException):
        """Deliver a lease failure to one queued task (its retry loop in
        _run_task_to_completion decides what happens next)."""
        while self.queue:
            spec, sink, _excl = self.queue.popleft()
            if sink is None or not sink.done():
                self._deliver(spec, sink, None, exc)
                return

    def _maybe_gc(self):
        """Drop this scheduler from the cache when fully idle — otherwise
        keys that never got a lease (failed/excluded nodes) accumulate."""
        if not self.queue and not self.leases \
                and not self.pending_leases:
            if self._reaper is not None:
                self._reaper.cancel()
                self._reaper = None
            self.worker._lease_cache.pop(self.key, None)

    async def _run_on_lease(self, spec, sink, lease, tok=None):
        pending = self.worker.tasks.get(spec.task_id)
        if pending is not None:
            pending.worker_address = lease["worker_address"]
        try:
            reply = await self.worker._push_on_lease(spec, lease)
            spans.end(tok, status=0)
        except Exception as e:
            spans.end(tok, status=1)
            self.worker.pool.invalidate(lease["worker_address"])
            with self.tlock:
                dead = lease in self.leases
                if dead:
                    self.leases.remove(lease)
            if dead:
                await self.worker._return_lease(lease, kill=True)
            self._deliver(spec, sink, None, _RetryableSubmitError(
                f"worker died: {e}", lease.get("node_id")))
            self._pump()
            return
        with self.tlock:
            lease["inflight"] -= 1
            if lease["inflight"] == 0:
                lease["idle_since"] = time.monotonic()
        self._deliver(spec, sink, reply, None)
        if self._reaper is None:
            self._reaper = asyncio.ensure_future(self._reap_idle())
        # Completion batches deliver many of these callbacks per loop
        # tick; their re-dispatches coalesce into one flush per worker.
        self._pump(self.worker._shared_batches())

    async def _acquire_lease(self, count: int = 1):
        """Request up to `count` worker grants in ONE LeaseWorker RPC.
        The hostd grants what it can immediately (parking only when it
        can grant zero); a partial fill resolves here and the follow-up
        _pump re-requests the remainder."""
        worker = self.worker
        spec = self.proto_spec
        # Lease demand is driven by the queue head: attribute the wait to
        # the trace actually blocked on it (specs sharing a key share the
        # lease, so this is the lease's best single owner).
        head = self.queue[0][0] if self.queue else spec
        tok = (spans.begin("sched", "lease_wait", pin=True,
                           ctx=getattr(head, "trace_ctx", None),
                           key=str(self.key)[:64], count=count)
               if getattr(head, "trace_ctx", None) is not None else None)
        try:
            bundle = None
            if spec.placement_group is not None:
                node, bundle = await worker._resolve_bundle(spec)
            else:
                # Locality hint: count owned object args per holding node
                # (reference: lease_policy.h LocalityAwareLeasePolicy asks
                # the locality-data provider for object-bytes-per-node).
                # Read args off the task actually WAITING, not proto_spec —
                # tasks sharing a scheduling key differ in their args, and
                # the first-ever spec's locations must not steer every
                # later lease (reference keys include depended_object_ids).
                loc_spec = self.queue[0][0] if self.queue else spec
                locality: dict[str, int] = {}
                if spec.scheduling_strategy in (None, "DEFAULT"):
                    from ray_tpu._private.protocol import RefArg
                    from ray_tpu._private.ids import ObjectID
                    ref_args = [a for a in list(loc_spec.args)
                                + list(loc_spec.kwargs.values())
                                if isinstance(a, RefArg)]
                    for a in ref_args:
                        st = worker.objects.get(ObjectID(a.id_binary))
                        if st is not None:
                            for loc in st.locations:
                                locality[loc] = locality.get(loc, 0) + 1
                pick = await worker.gcs.call("Gcs", "pick_node", {
                    "resources": spec.resources.to_dict(),
                    "strategy": spec.scheduling_strategy,
                    "exclude": self.exclude,
                    "node_affinity": spec.node_affinity,
                    "locality": locality or None,
                })
                node = pick["node"]
            if node is None:
                if self.exclude:
                    raise _RetryableSubmitError(
                        "all feasible nodes excluded", None, busy=True)
                raise ValueError(
                    f"no node can satisfy resources "
                    f"{spec.resources.to_dict()} for task {spec.name}")
            try:
                lease = await worker.pool.get(node.address).call(
                    "NodeManager", "LeaseWorker",
                    {"resources": spec.resources.to_dict(),
                     "job_id": worker._job_int(), "bundle": bundle,
                     "runtime_env": spec.runtime_env,
                     "count": count},
                    timeout=60)
            except Exception as e:
                raise _RetryableSubmitError(f"lease rpc failed: {e}",
                                            node.node_id)
            if lease.get("permanent"):
                raise ValueError(f"lease refused: {lease['reason']}")
            if not lease.get("granted"):
                raise _RetryableSubmitError(
                    f"lease rejected: {lease.get('reason')}", node.node_id,
                    busy=lease.get("reason") in ("busy", "resources"))
        except BaseException as e:  # noqa: BLE001 - routed to a queued task
            spans.end(tok, granted=False)
            self.pending_leases -= count
            # A busy rejection while we HOLD leases is not a task failure:
            # queued tasks are draining through the held workers; failing
            # one would send it to the back of the queue after a pointless
            # 0.1s sleep.  Only surface busy when no progress is possible.
            busy = isinstance(e, _RetryableSubmitError) and e.busy
            if busy and (self.held > 0 or self.pending_leases > 0):
                return
            if not isinstance(e, _RetryableSubmitError):
                # Permanent infeasibility applies to EVERY queued task with
                # this key — failing just one would strand the rest.
                while self.queue:
                    self._fail_one(e)
                self._maybe_gc()
                return
            self._fail_one(e)
            # Re-pump: remaining queued tasks still need leases, and the
            # task we just failed may never resubmit (cancelled, retries
            # exhausted) — without this they'd strand with no lease
            # requests in flight.
            self._pump()
            self._maybe_gc()
            return
        # A batched reply carries one grant dict per worker; a legacy
        # single-grant reply IS the grant.  Partial fills are normal —
        # the hostd returns what it could satisfy without parking.
        grants = lease.get("grants") or [lease]
        spans.end(tok, granted=True, grants=len(grants))
        self.pending_leases -= count
        fresh = []
        for g in grants:
            g = dict(g)
            g["node_address"] = node.address
            g["node_id"] = node.node_id
            g["idle_since"] = time.monotonic()
            g["inflight"] = 0
            port = g.get("native_port", 0)
            waddr = g.get("worker_address", "")
            if port and waddr and waddr not in worker._native_addrs:
                # The grant carries the worker's native route: the FIRST
                # push to a fresh worker already goes over the native
                # plane (no NativePort discovery RPC, no coroutine
                # detour).
                worker._native_addrs[waddr] = (
                    f"{waddr.rsplit(':', 1)[0]}:{port}")
            fresh.append(g)
        with self.tlock:
            self.leases.extend(fresh)
        if self._reaper is None:
            self._reaper = asyncio.ensure_future(self._reap_idle())
        self._pump()

    async def _reap_idle(self):
        try:
            while True:
                await asyncio.sleep(self.IDLE_TTL / 2)
                now = time.monotonic()
                with self.tlock:
                    # Remove under the lock BEFORE returning: a direct
                    # dispatcher must never claim a lease being reaped.
                    expire = [l for l in self.leases
                              if l["inflight"] == 0
                              and now - l["idle_since"] > self.IDLE_TTL]
                    for lease in expire:
                        self.leases.remove(lease)
                for lease in expire:
                    await self.worker._return_lease(lease)
                if not self.leases and not self.queue \
                        and not self.pending_leases:
                    self.worker._lease_cache.pop(self.key, None)
                    self._reaper = None
                    return
        except asyncio.CancelledError:
            pass


class _RefHooks:
    """Bridges ObjectRef lifecycle events to the core worker."""

    def __init__(self, cw: CoreWorker):
        self.cw = cw

    def on_ref_created(self, ref):
        self.cw.on_ref_created(ref)

    def on_ref_deleted(self, ref):
        self.cw.on_ref_deleted(ref)

    def on_ref_serialized(self, ref):
        pass  # pinning handled via serializer ref_sink

    def on_ref_deserialized(self, ref):
        self.cw.on_ref_deserialized(ref)

    def as_future(self, ref):
        return self.cw.as_future(ref)

    def await_ref(self, ref):
        return self.cw.await_ref(ref)


class _RetryableSubmitError(Exception):
    def __init__(self, msg: str, node_id, busy: bool = False):
        super().__init__(msg)
        self.node_id = node_id
        self.busy = busy  # transient saturation: requeue without burning retries
