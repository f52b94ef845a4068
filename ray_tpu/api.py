"""Public API surface.

Reference parity: python/ray/_private/worker.py (ray.init:1108, get:2411,
put:2544, wait:2606, remote:3034, kill:2763, get_actor:2728, shutdown),
python/ray/remote_function.py and python/ray/actor.py (@remote wrapping,
.options(), ActorHandle/ActorMethod).
"""

from __future__ import annotations

import atexit
import functools
import inspect
import threading

from ray_tpu.exceptions import ActorDiedError
from ray_tpu.object_ref import ObjectRef
from ray_tpu._private.accelerators import default_num_tpus
from ray_tpu._private.ids import ActorID, JobID
from ray_tpu._private.protocol import validate_options

_global_lock = threading.Lock()
_worker = None          # CoreWorker of this process (driver or task worker)
_cluster = None         # dict describing processes we spawned (head only)


def is_initialized() -> bool:
    return _worker is not None


def _get_worker():
    global _worker
    if _worker is None:
        # Inside a task-executing worker process the core worker already
        # exists; find it via the worker_main-installed global.
        raise RuntimeError("ray_tpu.init() has not been called")
    return _worker


def init(address: str | None = None, *, num_cpus=None, num_tpus=None,
         resources=None, namespace: str = "default",
         object_store_memory: int = 256 << 20, ignore_reinit_error=False,
         log_to_driver: bool = True, _system_config=None):
    """Connect to (or bootstrap) a cluster.  Reference: worker.py ray.init:1108.

    One `proc/init` span in the start-up record, with the daemons' starts
    and the driver's own connection as children."""
    from ray_tpu.util import spans
    with spans.span("proc", "init", pin=True):
        return _init(address, num_cpus, num_tpus, resources, namespace,
                     object_store_memory, ignore_reinit_error, log_to_driver,
                     _system_config)


def _init(address, num_cpus, num_tpus, resources, namespace,
          object_store_memory, ignore_reinit_error, log_to_driver,
          _system_config):
    from ray_tpu.util import spans
    global _worker, _cluster
    if address is None:
        # Reference parity: RAY_ADDRESS lets submitted job drivers join the
        # cluster that launched them (job_manager.py sets it on entrypoints).
        import os as _os0
        address = _os0.environ.get("RAY_TPU_ADDRESS") or None
    with _global_lock:
        if _worker is not None:
            if ignore_reinit_error:
                return _connection_info()
            raise RuntimeError("ray_tpu.init() called twice")
        from ray_tpu._private.config import GLOBAL_CONFIG
        GLOBAL_CONFIG.apply_system_config(_system_config)
        # Spawned daemons inherit overrides through the env (reference:
        # _system_config forwarded to gcs/raylet at bootstrap); shutdown()
        # undoes both so config can't leak into a later init().
        import os as _os
        global _applied_system_config
        _applied_system_config = list(_system_config or {})
        for k, v in (_system_config or {}).items():
            _os.environ[f"RAY_TPU_{k.upper()}"] = str(v)
        if address and address.startswith("ray_tpu://"):
            # Thin-client mode (reference: Ray Client, ray://): no local
            # store/daemons — every call proxies to the client server.
            from ray_tpu.util.client import ClientWorker
            _worker = ClientWorker(address[len("ray_tpu://"):])
            _cluster = {"group": None, "gcs": address, "owned": False}
            if log_to_driver:
                _start_log_echo(_worker)
            atexit.register(shutdown)
            return _connection_info()

        from ray_tpu._private import node as node_mod
        from ray_tpu._private.core_worker import CoreWorker
        from ray_tpu._private.rpc import RpcClient

        group = None
        if address is None:
            session_dir = node_mod.new_session_dir()
            group = node_mod.ProcessGroup()
            try:
                with spans.span("proc", "gcs_start", pin=True):
                    gcs_address = node_mod.start_gcs(
                        session_dir, group, watch_parent=True)
                with spans.span("proc", "hostd_start", pin=True):
                    head = node_mod.start_hostd(
                        gcs_address, session_dir, group, num_cpus=num_cpus,
                        num_tpus=num_tpus, resources=resources,
                        store_capacity=object_store_memory, head=True)
            except Exception:
                group.reap()
                raise
            _cluster = {"group": group, "gcs": gcs_address,
                        "session_dir": session_dir, "owned": True}
            from ray_tpu._private import usage as _usage
            _usage.record_usage(session_dir)
        else:
            gcs_address = address
            # Find a hostd on this machine to use as our home node.
            import asyncio

            async def find_home():
                gcs = RpcClient(gcs_address)
                try:
                    reply = await gcs.call("Gcs", "get_nodes", {}, timeout=10)
                finally:
                    await gcs.close()
                import socket
                hostname = socket.gethostname()
                alive = [n for n in reply["nodes"] if n.alive]
                for n in alive:
                    if n.hostname == hostname:
                        return n
                raise RuntimeError(
                    "no alive node on this host; start one with "
                    "`ray_tpu start --address=...`")
            head_info = asyncio.run(find_home())
            head = {"address": head_info.address,
                    "node_id": head_info.node_id.hex(),
                    "store_path": head_info.store_path}
            _cluster = {"group": None, "gcs": gcs_address, "owned": False}

        from ray_tpu._private.ids import NodeID
        from ray_tpu._private.rpc import RpcClient as _Rpc
        import asyncio as _aio

        async def next_job():
            gcs = _Rpc(gcs_address)
            try:
                reply = await gcs.call("Gcs", "next_job_id", {}, timeout=10)
                return reply["job_id"]
            finally:
                await gcs.close()

        try:
            with spans.span("proc", "driver_connect", pin=True):
                job_int = _aio.run(next_job())
                _worker = CoreWorker(
                    mode="driver",
                    gcs_address=gcs_address,
                    store_path=head["store_path"],
                    node_id=NodeID.from_hex(head["node_id"]),
                    hostd_address=head["address"],
                    job_id=JobID(job_int.to_bytes(4, "little")),
                )
        except Exception:
            _cluster = None
            if group is not None:
                group.reap()
            raise
        if log_to_driver:
            _start_log_echo(_worker)
        _start_driver_telemetry()
        atexit.register(shutdown)
        return _connection_info()


_log_echo_stop = None
_telemetry = None


def _start_driver_telemetry():
    """Driver-process pull endpoints (/metrics /events /healthz): serve
    routers, train drivers, and user Counters record in THIS process,
    which no hostd scrapes — the driver exports its own."""
    global _telemetry
    import time as _time

    from ray_tpu.util import metrics as mt
    from ray_tpu.util import telemetry

    # A lean driver may never touch a library Counter, and an empty
    # /metrics body reads as a broken scrape — always export uptime.
    up = mt.Gauge("driver_uptime_seconds", "seconds since ray_tpu.init")
    t0 = _time.time()

    def metrics_fn():
        up.set(_time.time() - t0)
        return mt.prometheus_text(mt.collect(), {"component": "driver"})

    def events_fn(plane, kind, trace_id, since):
        from ray_tpu.util import events as ev
        return [e for e in ev.snapshot(since=since, plane=plane, kind=kind)
                if trace_id is None or e.get("trace_id") == trace_id]

    _telemetry = telemetry.start_server(
        metrics_fn=metrics_fn, events_fn=events_fn, component="driver")


def _start_log_echo(worker):
    """Echo worker stdout/stderr to the driver terminal (reference:
    worker.py log streaming via GCS pubsub; prefix = (pid, stream))."""
    global _log_echo_stop
    import sys
    import threading as _th
    import time as _time

    stop = _th.Event()
    _log_echo_stop = stop
    job = worker._job_int()

    def loop():
        after = 0
        while not stop.is_set():
            _time.sleep(0.5)
            coro = worker.gcs.call(
                "Gcs", "get_log_lines",
                {"after_seq": after, "job_id": job}, timeout=10)
            try:
                reply = worker.io.run(coro, timeout=15)
            except RuntimeError:
                # Loop gone before scheduling: the coroutine never ran —
                # closing is safe and silences the never-awaited warning.
                coro.close()
                continue
            except Exception:
                # Scheduled but failed/timed out: the loop owns the
                # coroutine — closing from this thread would race it.
                continue
            # Advance past EVERYTHING the GCS scanned (global seq), not
            # just this job's lines, or quiet jobs rescan the whole ring.
            after = max(after, reply.get("seq", after))
            try:
                for seq, rec in reply.get("lines", []):
                    out = (sys.stderr if rec["stream"] == "stderr"
                           else sys.stdout)
                    print(f"(pid={rec['pid']}) {rec['line']}", file=out)
            except (BrokenPipeError, OSError):
                return  # stdout gone (piped driver exited) — stop echoing
            except Exception:
                pass

    _th.Thread(target=loop, daemon=True, name="raytpu-log-echo").start()


def _connection_info():
    return {"gcs_address": _cluster["gcs"] if _cluster else None,
            "session_dir": (_cluster or {}).get("session_dir")}


_applied_system_config: list = []


def shutdown():
    """Disconnect; if we bootstrapped the cluster, tear it down."""
    global _worker, _cluster, _applied_system_config, _log_echo_stop, \
        _telemetry
    if _log_echo_stop is not None:
        _log_echo_stop.set()
        _log_echo_stop = None
    if _telemetry is not None:
        _telemetry.stop()
        _telemetry = None
    with _global_lock:
        if _worker is None:
            return
        # Before anything is torn down: every process's start-up record,
        # kept for `state.startup_timeline()` to give after the session.
        from ray_tpu import state as _state
        try:
            _state._keep_startup_timeline()
        except Exception:
            pass
        cluster, worker = _cluster, _worker
        _worker = None
        _cluster = None
        import os as _os

        from ray_tpu._private.config import GLOBAL_CONFIG
        for k in _applied_system_config:
            GLOBAL_CONFIG._overrides.pop(k, None)
            _os.environ.pop(f"RAY_TPU_{k.upper()}", None)
        if _applied_system_config:
            # Resolved values are cached on read; dropping the overrides
            # without this would leak them into a later init().
            GLOBAL_CONFIG.invalidate_cache()
            from ray_tpu._private import fault_injection
            fault_injection.reset()
        _applied_system_config = []
    try:
        if cluster and cluster.get("owned"):
            try:
                worker.io.run(worker.gcs.call("Gcs", "shutdown_cluster", {}),
                              timeout=5)
            except Exception:
                pass
    finally:
        worker.shutdown()
        if cluster and cluster.get("owned") and cluster.get("group"):
            cluster["group"].reap()
            try:        # (whoever the first call did not hear from)
                _state._keep_startup_timeline(
                    _os.path.join(cluster["session_dir"], "logs"))
            except Exception:
                pass


def put(value) -> ObjectRef:
    return _get_worker().put(value)


def get(refs, *, timeout: float | None = None):
    return _get_worker().get(refs, timeout)


def wait(refs, *, num_returns: int = 1, timeout: float | None = None,
         fetch_local: bool = True):
    if not isinstance(refs, list):
        raise TypeError("wait() expects a list of ObjectRefs")
    return _get_worker().wait(refs, num_returns, timeout, fetch_local)


def kill(actor, *, no_restart: bool = True):
    if not isinstance(actor, ActorHandle):
        raise TypeError("kill() expects an ActorHandle")
    _get_worker().kill_actor(actor._actor_id, no_restart)


def cancel(ref, *, force: bool = False, recursive: bool = True):
    """Cancel a pending or running task (reference: worker.py
    ray.cancel:2793).  force=False interrupts the running task with
    TaskCancelledError; force=True kills the executing worker process.

    recursive=True is accepted for reference compatibility, but
    cancellation is NOT yet propagated to child tasks spawned by the
    cancelled task — a warning is logged when this could matter.
    """
    if not isinstance(ref, ObjectRef):
        raise TypeError("cancel() expects an ObjectRef")
    if recursive:
        global _warned_recursive_cancel
        if not _warned_recursive_cancel:
            _warned_recursive_cancel = True
            import logging
            logging.getLogger("ray_tpu").warning(
                "cancel(recursive=True): child-task cancellation is not "
                "yet propagated; only the target task is cancelled")
    _get_worker().cancel_task(ref, force, recursive)


_warned_recursive_cancel = False


def get_actor(name: str, namespace: str = "default") -> "ActorHandle":
    info = _get_worker().get_named_actor(name, namespace)
    if info is None or info.state == "DEAD":
        raise ValueError(f"actor {name!r} not found in namespace {namespace!r}")
    return ActorHandle(info.actor_id, info.class_name, None)


def cluster_resources() -> dict:
    w = _get_worker()
    return w.io.run(w.gcs.call("Gcs", "cluster_resources", {}))["total"]


def available_resources() -> dict:
    w = _get_worker()
    return w.io.run(w.gcs.call("Gcs", "cluster_resources", {}))["available"]


def nodes() -> list:
    w = _get_worker()
    reply = w.io.run(w.gcs.call("Gcs", "get_nodes", {}))
    return [
        {"NodeID": n.node_id.hex(), "Alive": n.alive, "Address": n.address,
         "Resources": n.resources_total, "IsHead": n.is_head}
        for n in reply["nodes"]
    ]


# ---------------------------------------------------------------------------
# @remote
# ---------------------------------------------------------------------------


class RemoteFunction:
    def __init__(self, fn, options: dict):
        self._fn = fn
        self._options = validate_options(options, for_actor=False)
        functools.update_wrapper(self, fn)

    def remote(self, *args, **kwargs):
        refs = _get_worker().submit_task(self._fn, args, kwargs, self._options)
        return refs[0] if self._options.get("num_returns", 1) == 1 else refs

    def options(self, **opts) -> "RemoteFunction":
        merged = dict(self._options)
        merged.update(opts)  # constructor re-validates the merged set
        return RemoteFunction(self._fn, merged)

    def bind(self, *args, **kwargs):
        """Lazy DAG authoring (reference: dag/function_node.py)."""
        from ray_tpu.dag import FunctionNode
        return FunctionNode(self, args, kwargs)

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"remote function {self._fn.__name__} cannot be called directly; "
            f"use .remote()")


class ActorMethod:
    def __init__(self, handle: "ActorHandle", name: str, num_returns: int = 1):
        self._handle = handle
        self._name = name
        self._num_returns = num_returns

    def remote(self, *args, **kwargs):
        refs = _get_worker().submit_actor_task(
            self._handle._actor_id, self._name, args, kwargs,
            {"num_returns": self._num_returns,
             "max_task_retries": self._handle._max_task_retries})
        return refs[0] if self._num_returns == 1 else refs

    def options(self, num_returns: int = 1, **_):
        return ActorMethod(self._handle, self._name, num_returns)


class ActorHandle:
    def __init__(self, actor_id: ActorID, class_name: str,
                 method_meta: dict | None, max_task_retries: int = 0):
        self._actor_id = actor_id
        self._class_name = class_name
        self._method_meta = method_meta or {}
        self._max_task_retries = max_task_retries

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return ActorMethod(self, name,
                           self._method_meta.get(name, {}).get("num_returns", 1))

    def __repr__(self):
        return f"ActorHandle({self._class_name}, {self._actor_id.hex()[:12]})"

    def __reduce__(self):
        return (ActorHandle, (self._actor_id, self._class_name,
                              self._method_meta, self._max_task_retries))


class ActorClass:
    def __init__(self, cls, options: dict):
        self._cls = cls
        self._options = validate_options(options, for_actor=True)

    def remote(self, *args, **kwargs) -> ActorHandle:
        worker = _get_worker()
        opts = dict(self._options, num_tpus=default_num_tpus(
            self._cls, self._options.get("num_tpus")))
        actor_id = worker.create_actor(self._cls, args, kwargs, opts)
        meta = {}
        for name, fn in inspect.getmembers(self._cls, inspect.isfunction):
            meta[name] = {"num_returns": 1}
        return ActorHandle(actor_id, self._cls.__name__, meta,
                           self._options.get("max_task_retries", 0))

    def options(self, **opts) -> "ActorClass":
        merged = dict(self._options)
        merged.update(opts)
        return ActorClass(self._cls, merged)

    def bind(self, *args, **kwargs):
        """Lazy DAG authoring (reference: dag/class_node.py)."""
        from ray_tpu.dag import ClassNode
        return ClassNode(self, args, kwargs)

    def __call__(self, *a, **k):
        raise TypeError(f"actor class {self._cls.__name__} cannot be "
                        f"instantiated directly; use .remote()")


def remote(*args, **kwargs):
    """@remote decorator for tasks and actors (reference: worker.py:3034)."""
    if len(args) == 1 and not kwargs and (inspect.isfunction(args[0])
                                          or inspect.isclass(args[0])):
        return _make_remote(args[0], {})
    if args:
        raise TypeError("@remote options must be keyword arguments")

    def wrap(obj):
        return _make_remote(obj, kwargs)
    return wrap


def _make_remote(obj, options: dict):
    if inspect.isclass(obj):
        return ActorClass(obj, options)
    if inspect.isfunction(obj) or callable(obj):
        return RemoteFunction(obj, options)
    raise TypeError(f"@remote cannot wrap {obj!r}")


def method(num_returns: int = 1):
    """@method decorator inside actor classes (num_returns for methods)."""
    def wrap(fn):
        fn._num_returns = num_returns
        return fn
    return wrap
