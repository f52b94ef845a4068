"""hostd — the per-node daemon (reference: src/ray/raylet/).

Owns the node's shared-memory object store, the worker pool
(raylet/worker_pool.h: spawn/pop/cache idle workers), local resource
accounting (LocalResourceManager), worker leasing for tasks and actors
(NodeManager::HandleRequestWorkerLease, node_manager.cc:1817), node-to-node
object transfer (object_manager/: pull semantics), and the GCS heartbeat.

Scheduling split, as in the reference: the GCS resource view proposes a node;
this daemon is the admission controller — a lease can be rejected and the
submitter reschedules (spillback).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import subprocess
import sys
import time
from collections import deque

from ray_tpu._private import compile_cache
from ray_tpu._private import gcs as gcs_mod
from ray_tpu._private import worker_main
from ray_tpu._private.accelerators import (
    LEASED_CHIPS_ENV, NODE_CHIPS_ENV, ChipAllocator, chip_env,
    count_local_chips, leasable)
from ray_tpu._private.ids import NodeID, WorkerID
from ray_tpu._private.object_store import ObjectStore
from ray_tpu._private.protocol import NodeInfo
from ray_tpu._private.rpc import ClientPool, GcsClient, RpcClient, RpcServer
from ray_tpu.util import events
from ray_tpu.util import spans

logger = logging.getLogger("ray_tpu.hostd")

def _cfg():
    from ray_tpu._private.config import GLOBAL_CONFIG
    return GLOBAL_CONFIG


def _metrics():
    """Daemon metric definitions (reference: stats/metric_defs.h:46-110)."""
    global _M
    if _M is None:
        from ray_tpu.util import metrics as mt
        _M = {
            "leases_granted": mt.Counter(
                "leases_granted", "worker leases granted"),
            "workers_spawned": mt.Counter(
                "workers_spawned", "worker processes spawned"),
            "objects_spilled": mt.Counter(
                "objects_spilled", "objects written to spill storage"),
            "bytes_spilled": mt.Counter(
                "bytes_spilled", "bytes written to spill storage"),
            "objects_restored": mt.Counter(
                "objects_restored", "spilled objects read back"),
            "store_used_bytes": mt.Gauge(
                "store_used_bytes", "shm object store bytes in use"),
            "oom_workers_killed": mt.Counter(
                "oom_workers_killed",
                "workers killed by the memory monitor"),
            "preemption_notices": mt.Counter(
                "preemption_notices",
                "preemption notices received by this hostd"),
            "preemption_grace_s": mt.Gauge(
                "preemption_grace_s",
                "grace window of the most recent preemption notice"),
        }
    return _M


_M = None


def detect_resources() -> dict:
    res = {"CPU": float(os.cpu_count() or 1)}
    # TPU: an explicit count (set by the pod provisioner) wins; otherwise
    # count the chips' device nodes.  hostd never opens the TPU runtime —
    # it would hold the chips its workers need.
    if "RAY_TPU_NUM_TPUS" in os.environ:
        n = float(os.environ["RAY_TPU_NUM_TPUS"])
    else:
        n = float(count_local_chips())
    if n > 0:
        res["TPU"] = n
    # Schedulable memory: 70% of system RAM (reference: resource_spec.py
    # caps the memory resource below total so daemons/OS keep headroom).
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    res["memory"] = float(int(line.split()[1]) * 1024 * 0.7)
                    break
    except OSError:
        pass
    # Accelerator type advertisement (reference: accelerator_type:<T>
    # node resource; util/accelerators knows NVIDIA only — TPU gens here).
    acc = os.environ.get("RAY_TPU_ACCELERATOR_TYPE")
    if acc:
        res[f"accelerator_type:{acc}"] = 1.0
    return res


class _ForkedProc:
    """Popen-compatible view of a worker forked by the zygote.

    The child belongs to the zygote's process tree, so exit detection is
    authoritative only through the zygote's reap reports (`exits` — a
    shared {pid: code} map the hostd refreshes each reaper sweep).  The
    kill(pid, 0) probe alone would misreport after pid reuse and always
    lose the exit code; here it only accelerates detection between
    sweeps, and the real code replaces the placeholder when the report
    lands."""

    def __init__(self, pid: int, exits: dict):
        self.pid = pid
        self.returncode: int | None = None
        self._exits = exits

    def poll(self):
        if self.returncode is not None:
            return self.returncode
        code = self._exits.pop(self.pid, None)
        if code is not None:
            self.returncode = code
            return code
        try:
            os.kill(self.pid, 0)
            return None
        except ProcessLookupError:
            # Gone but the reap report hasn't arrived yet; report dead
            # with an unknown-exit placeholder (refined above if the
            # report lands before anyone reads it).
            self.returncode = self._exits.pop(self.pid, 255)
            return self.returncode
        except PermissionError:
            return None

    def terminate(self):
        try:
            os.kill(self.pid, 15)
        except ProcessLookupError:
            pass

    def kill(self):
        try:
            os.kill(self.pid, 9)
        except ProcessLookupError:
            pass

    def wait(self, timeout: float | None = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and time.monotonic() > deadline:
                raise subprocess.TimeoutExpired(f"pid {self.pid}", timeout)
            time.sleep(0.02)
        return self.returncode


class _Zygote:
    """Manages the fork-server process (see worker_zygote.py).

    Spawn requests COALESCE: concurrent callers (the spawn thread pool
    during a storm or a batched lease) enqueue their request and one of
    them — whoever wins the pipe lock — ships every pending request as a
    single batched {"spawn": [...]} line, so the zygote forks K children
    per select wakeup instead of one pipe round trip per worker.  A lone
    caller degenerates to the old one-request/one-reply exchange cost."""

    def __init__(self, env: dict, batch_max: int = 8):
        import threading
        self._lock = threading.Lock()        # pipe ownership
        self._qlock = threading.Lock()       # pending-request queue
        self._pending: list = []             # [req, Event, pid, exc]
        self.batch_max = max(1, batch_max)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.worker_zygote"],
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL)
        import json as _json
        line = self.proc.stdout.readline()  # waits for {"ready": true}
        if not line or not _json.loads(line).get("ready"):
            raise RuntimeError("zygote failed to start")

    def spawn(self, argv: list, env: dict, stdout: str, stderr: str) -> int:
        import threading
        item = [{"argv": argv, "env": env, "stdout": stdout,
                 "stderr": stderr}, threading.Event(), None, None]
        with self._qlock:
            self._pending.append(item)
        while not item[1].is_set():
            # Whoever holds the pipe flushes EVERYONE's pending requests;
            # the rest block here until their reply (or help flush the
            # next wave once the pipe frees up).
            if not self._lock.acquire(timeout=0.05):
                continue
            try:
                if item[1].is_set():
                    break
                with self._qlock:
                    batch = self._pending[:self.batch_max]
                    del self._pending[:len(batch)]
                if batch:
                    self._spawn_batch(batch)
            finally:
                self._lock.release()
        if item[3] is not None:
            raise item[3]
        return item[2]

    def _spawn_batch(self, batch: list) -> None:
        """Ship one batched fork request; runs under self._lock."""
        import json as _json
        line = None
        exc = None
        try:
            self.proc.stdin.write((_json.dumps(
                {"spawn": [it[0] for it in batch]}) + "\n").encode())
            self.proc.stdin.flush()
            line = self.proc.stdout.readline()
        except Exception as e:  # noqa: BLE001 - fanned to every waiter
            exc = e
        if exc is None and not line:
            exc = RuntimeError("zygote died")
        if exc is None:
            pids = _json.loads(line).get("pids", [])
            if len(pids) != len(batch):
                exc = RuntimeError("zygote spawn reply shape mismatch")
        for i, it in enumerate(batch):
            if exc is not None:
                it[3] = exc
            else:
                it[2] = int(pids[i])
            it[1].set()

    def poll_exits(self, into: dict) -> None:
        """Drain the zygote's reap reports into `into` ({pid: code})."""
        import json as _json
        with self._lock:
            self.proc.stdin.write(b'{"reap": true}\n')
            self.proc.stdin.flush()
            line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError("zygote died")
        for pid, code in _json.loads(line).get("exited", []):
            into[int(pid)] = int(code)

    def close(self):
        try:
            self.proc.stdin.close()
            self.proc.terminate()
        except Exception:
            pass


class WorkerHandle:
    def __init__(self, proc: subprocess.Popen, job_id: int,
                 env_hash: str = "", chips: tuple = ()):
        self.proc = proc
        self.job_id = job_id
        self.env_hash = env_hash  # runtime-env cache key (worker_pool.h:156)
        self.chips = chips       # TPU chips bound to the process for life
        self.worker_id: WorkerID | None = None
        self.address: str = ""
        self.native_port: int = 0  # worker's framed-TCP plane (taskrpc.cc)
        self.state = "starting"  # starting/idle/claimed/leased/actor
        self.reserved = False    # pinned for the lease that spawned it
        self.lease_id: str | None = None
        self.lease_resources: dict = {}
        self.lease_bundle: tuple | None = None  # (pg_hex, index) if in a PG
        self.actor_id = None
        self.idle_since = time.monotonic()
        self.leased_at = 0.0
        # Set via the WorkerExiting RPC when the worker announces a
        # deliberate exit (SIGTERM drain, preemption abort) so the reaper
        # reports intent instead of "crash" (reference: raylet
        # DisconnectClient carries a WorkerExitType).
        self.exit_reason: str | None = None
        self.log_paths: dict = {}
        self.log_offsets: dict = {}
        self.boot_span = None    # sched/worker_boot, closed by WorkerReady
        self.ready = asyncio.Event()


class NodeDaemon:
    def __init__(self, gcs_address: str, resources: dict | None = None,
                 store_capacity: int = 256 << 20, is_head: bool = False,
                 host: str = "127.0.0.1", session_dir: str = "/tmp/ray_tpu"):
        self.node_id = NodeID.from_random()
        self.gcs_address = gcs_address
        self.gcs = GcsClient(gcs_address)
        self.pool = ClientPool()
        # Node incarnation (split-brain fencing): starts at 0, adopted
        # from the GCS's fencing verdict when this node re-registers
        # after having been declared dead — see _register_with_gcs.
        self.incarnation = 0
        # Last GCS boot id seen in get_nodes replies; a change means the
        # head restarted underneath us and owes an anti-entropy resync.
        self._gcs_boot_id: str | None = None
        self.host = host
        self.is_head = is_head
        self.session_dir = session_dir
        os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
        # This daemon's own black box lands with the worker dumps so
        # collect_events finds every dead process's ring in one place.
        os.environ.setdefault("RAY_TPU_FLIGHTREC_DIR",
                              os.path.join(session_dir, "logs"))
        self.store_path = os.path.join(
            "/dev/shm", f"ray_tpu_{self.node_id.hex()[:12]}")
        self.store = ObjectStore.create(self.store_path, store_capacity)
        self.resources_total = dict(resources or detect_resources())
        self.resources_available = dict(self.resources_total)
        # Placement-group bundles reserved on this node:
        # (pg_hex, index) -> {"reserved": demand, "available": remaining,
        #                     "committed": bool}
        # (reference: raylet PlacementGroupResourceManager 2PC,
        #  placement_group_resource_manager.h:46)
        self.bundles: dict[tuple, dict] = {}
        self.workers: dict[int, WorkerHandle] = {}  # pid -> handle
        # Chip bindings outlive the handle: _kill_worker forgets a worker
        # at once, but libtpu holds its chips until the process is gone.
        self.chips = ChipAllocator(int(self.resources_total.get("TPU", 0)))
        self._chip_procs: list = []     # (proc, chips) until proc exits
        # Preemption notice state (simulated TPU maintenance event):
        # while `preempting`, every new lease / bundle prepare is rejected
        # with reason "preempting" so the scheduler spills to healthy
        # nodes, and `_preempt_victims` pins the pids alive at notice time
        # so the deadline kill can never hit a later-formed gang.
        self.preempting = False
        self._preempt_victims: set[int] = set()
        self._lease_seq = 0
        self.server = RpcServer(host)
        self._shutdown = asyncio.Event()
        self.max_workers = _cfg().max_workers_per_node or max(
            8, int(self.resources_total.get("CPU", 1)) * 4)
        # Startup throttling (reference: worker_pool.h:245 startup tokens /
        # maximum_startup_concurrency scales with host cores): concurrent
        # python spawns contend for cores — past this many in-flight
        # spawns, lease requests wait for an existing worker instead of
        # forking another interpreter.
        # Floor of 4: spawning is import-I/O heavy, and on small/cgroup-
        # restricted hosts (cpu_count()==1) a throttle of 1 serializes the
        # whole pool ramp-up behind one ~0.3s boot at a time.
        self.max_startup_concurrency = (
            _cfg().max_startup_concurrency or max(4, os.cpu_count() or 1))
        # Fork-server (worker_zygote.py): prestarted off-loop at daemon
        # start so its cold-import time never blocks a lease; until it's
        # ready, spawns fall back to the classic Popen path.
        self._zygote: _Zygote | None = None
        self._zygote_exits: dict = {}   # pid -> exit code (reap reports)
        # Process creation runs off-loop (see _spawn_worker); _spawning
        # counts in-executor spawns for the startup throttle.
        from concurrent.futures import ThreadPoolExecutor
        self._spawn_exec = ThreadPoolExecutor(
            max_workers=max(4, _cfg().zygote_spawn_parallelism),
            thread_name_prefix="spawn")
        self._spawning = 0
        self._spawn_seq = 0
        # Recent lease demand, (t, (job_id, env_hash, tpu)) — drives the
        # pre-warm pool (see _prewarm_tick): a storm's lease rate sizes
        # how many idle workers to keep forked ahead of the next wave.
        self._lease_demand: deque = deque(maxlen=512)
        self._capacity_freed: asyncio.Event | None = None  # made on start()
        # Parked lease waiters, FIFO: capacity events hand off to ONE
        # waiter (see _notify_capacity).
        self._worker_waiters: deque = deque()
        # Object spilling (reference: raylet LocalObjectManager
        # local_object_manager.h:41 + _private/external_storage.py:246
        # FileSystemStorage).  With spilling on, LRU eviction is disabled:
        # primary copies are written to disk under memory pressure and
        # restored on demand instead of destroyed.
        self.spill_enabled = _cfg().spill_enabled
        self.spill_dir = os.environ.get("RAY_TPU_SPILL_DIR") or os.path.join(
            session_dir, "spill", self.node_id.hex()[:12])
        self.spill_high = _cfg().spill_high_watermark
        self.spill_low = _cfg().spill_low_watermark
        self.spilled: dict[bytes, tuple[str, int]] = {}  # id -> (path, size)
        self.spilled_bytes = 0

    # ---------------- worker pool ----------------

    async def _spawn_worker(self, job_id: int,
                            runtime_env: dict | None = None,
                            chips: tuple = ()) -> WorkerHandle:
        """Spawn a worker WITHOUT blocking the event loop: the zygote
        pipe round trip (or cold Popen) costs ~10ms of wall — measured
        at 12ms/spawn of loop stall during an actor storm — so the
        process-creation step runs in a small thread pool while the
        loop keeps serving leases, heartbeats and WorkerReady RPCs."""
        from ray_tpu._private import runtime_env as renv
        log_base = os.path.join(self.session_dir, "logs",
                                f"worker-{self._spawn_seq}-{os.getpid()}")
        self._spawn_seq += 1
        env = dict(os.environ)
        env["RAY_TPU_NODE_ID"] = self.node_id.hex()
        # Chaos identity: the spawn ordinal salts the worker's fault
        # schedule so a killed worker's replacement doesn't replay the
        # draw that killed it (fault_injection.ChaosController).
        env["RAY_TPU_CHAOS_PROC_SALT"] = str(self._spawn_seq)
        # Flight-recorder black box: crash dumps land next to the worker
        # logs so CollectEvents / state.events() can stitch a dead
        # worker's ring with live peers.
        env["RAY_TPU_FLIGHTREC_DIR"] = os.path.join(
            self.session_dir, "logs")
        if self.chips.host_chips:
            env[NODE_CHIPS_ENV] = str(self.chips.host_chips)
        if chips:
            # Confine libtpu to the chips bound to this worker (nothing to
            # confine when it holds them all), and keep its compiles in
            # the cache the driver placed.
            env[LEASED_CHIPS_ENV] = ",".join(map(str, chips))
            env.update(chip_env(chips, self.chips.host_chips))
            env.setdefault(compile_cache.ENV, compile_cache.default_dir())
        else:
            # Leases without a TPU demand get a worker pinned to the host
            # CPU: an unset or "tpu" JAX_PLATFORMS would open the TPU
            # runtime on first jax use and seize chips away from
            # TPU-leased workers.  A runtime_env env_vars override below
            # still wins (applied after this).
            env.pop(LEASED_CHIPS_ENV, None)
            env["JAX_PLATFORMS"] = "cpu"
        if runtime_env:
            import json as _json
            env.update(runtime_env.get("env_vars", {}))
            env["RAY_TPU_RUNTIME_ENV"] = _json.dumps(runtime_env)
            env["RAY_TPU_RUNTIME_ENV_CACHE"] = os.path.join(
                self.session_dir, "runtime_env")
        # The worker's `proc/boot` names the `sched/worker_boot` span
        # below as its parent: the id goes ahead of the span.
        boot_sid = env[worker_main.BOOT_SPAN_ENV] = spans.new_sid()
        argv = ["--gcs", self.gcs_address,
                "--hostd", f"{self.host}:{self.server.port}",
                "--store", self.store_path,
                "--node-id", self.node_id.hex(),
                "--job-id", str(job_id)]
        self._spawning += 1
        # Spawn-path attribution (actor_storm mode in scale_attrib.py):
        # zygote_fork covers process creation (fork round trip or cold
        # Popen), worker_boot the child's interpreter/runtime ramp until
        # its WorkerReady lands.
        # (Both are kept in the start-up record, like the worker's own.)
        ftok = spans.begin("sched", "zygote_fork", pin=True,
                           cold=self._zygote is None or bool(chips))
        proc = None
        try:
            proc = await asyncio.get_running_loop().run_in_executor(
                self._spawn_exec, self._make_proc, argv, env, log_base,
                bool(chips))
        finally:
            self._spawning -= 1
            spans.end(ftok, pid=proc.pid if proc is not None else None)
        handle = WorkerHandle(proc, job_id, renv.env_hash(runtime_env),
                              chips)
        if chips:
            self._chip_procs.append((proc, chips))
        handle.boot_span = spans.begin("sched", "worker_boot", pin=True,
                                       sid=boot_sid, pid=proc.pid)
        handle.log_paths = {"stdout": log_base + ".out",
                            "stderr": log_base + ".err"}
        handle.log_offsets = {"stdout": 0, "stderr": 0}
        _metrics()["workers_spawned"].inc()
        self.workers[proc.pid] = handle
        logger.info("spawned worker pid=%d job=%d env=%s", proc.pid, job_id,
                    handle.env_hash or "-")
        return handle

    def _make_proc(self, argv, env, log_base, tpu):
        """Blocking process creation — runs on the spawn thread pool."""
        proc = None
        if not tpu and _cfg().worker_zygote:
            # Fast path: fork the pre-imported template (~1-2ms vs ~300ms
            # cold spawn).  TPU workers never fork — PJRT state must not
            # cross a fork.
            try:
                pid = self._zygote_spawn(
                    argv, env, log_base + ".out", log_base + ".err")
                if pid is not None:
                    proc = _ForkedProc(pid, self._zygote_exits)
            except Exception:
                logger.exception("zygote spawn failed; cold-spawning")
                # Same rule as the reap poll: never kill a live zygote —
                # its death would cascade to every forked worker.
                if (self._zygote is not None
                        and self._zygote.proc.poll() is not None):
                    self._zygote_close()
        if proc is None:
            cmd = [sys.executable, "-m", "ray_tpu._private.worker_main",
                   *argv]
            out = open(log_base + ".out", "ab")
            err = open(log_base + ".err", "ab")
            proc = subprocess.Popen(cmd, env=env, stdout=out, stderr=err)
        return proc

    def _zygote_spawn(self, argv, env, out_path, err_path) -> int | None:
        """Fork via the prestarted zygote; None while it's still warming
        (caller cold-spawns instead of waiting)."""
        if self._zygote is None:
            self._prestart_zygote()
            return None
        return self._zygote.spawn(argv, env, out_path, err_path)

    def _prestart_zygote(self):
        if getattr(self, "_zygote_starting", False):
            return
        self._zygote_starting = True

        def _boot():
            try:
                zenv = dict(os.environ)
                zenv["JAX_PLATFORMS"] = "cpu"
                self._zygote = _Zygote(
                    zenv, batch_max=_cfg().zygote_spawn_parallelism)
            except Exception:
                logger.exception("zygote failed to start; cold spawns only")
            finally:
                self._zygote_starting = False

        import threading
        threading.Thread(target=_boot, daemon=True,
                         name="zygote-boot").start()

    def _zygote_close(self):
        if self._zygote is not None:
            self._zygote.close()
            self._zygote = None

    async def worker_ready(self, req):
        """Called by a freshly started worker process."""
        handle = self.workers.get(req["pid"])
        if handle is None:
            return {"ok": False}
        handle.worker_id = req["worker_id"]
        handle.address = req["address"]
        handle.native_port = req.get("native_port", 0)
        handle.state = "idle"
        handle.idle_since = time.monotonic()
        if handle.boot_span is not None:
            spans.end(handle.boot_span)
            handle.boot_span = None
        handle.ready.set()
        # Wake lease requests parked behind the startup throttle.
        self._notify_capacity()
        return {"ok": True, "node_id": self.node_id}

    async def _get_worker(self, job_id: int, timeout: float = 60.0,
                          runtime_env: dict | None = None,
                          n_chips: int = 0):
        """Pop an idle worker for (job, runtime-env hash, chips bound),
        spawning if necessary.  The returned handle is already claimed
        (state="claimed") so concurrent leases can never share a worker."""
        from ray_tpu._private import runtime_env as renv
        want_hash = renv.env_hash(runtime_env)
        deadline = asyncio.get_event_loop().time() + timeout
        while True:
            for handle in self.workers.values():
                if handle.state == "idle" and not handle.reserved \
                        and handle.job_id == job_id \
                        and handle.env_hash == want_hash \
                        and len(handle.chips) == n_chips:
                    handle.state = "claimed"
                    return handle
            # No liveness syscalls here: this scan runs hundreds of times
            # per storm, and a kill(pid, 0) per handle per pass measured
            # ~4ms/actor.  `returncode` is refreshed by the reaper sweep
            # (and by anyone who polls); a just-died worker counts live
            # for <1 sweep, which only makes the throttle conservative.
            live = [w for w in self.workers.values()
                    if w.proc.returncode is None]
            starting = sum(1 for w in live if w.state == "starting") \
                + self._spawning
            # Forked (zygote) spawns skip the interpreter+import cost, so
            # the anti-thundering-herd throttle — which exists because
            # cold spawns contend for cores — opens up for them.  Only
            # when the zygote is actually SERVING: while it's still
            # warming (or failed), spawns are cold Popens and must keep
            # the cold throttle.
            throttle = self.max_startup_concurrency
            if not n_chips and self._zygote is not None:
                throttle = max(throttle, 32)
            if starting >= throttle:
                # Throttle check comes BEFORE eviction: only kill an idle
                # worker when a replacement spawn will actually follow.
                remaining = deadline - asyncio.get_event_loop().time()
                if remaining <= 0:
                    return None
                await self._wait_worker_slot(remaining)
                continue
            if len(live) >= self.max_workers:
                # Evict an idle worker that can't serve this lease — other
                # job OR same job with a different runtime-env hash.
                for handle in live:
                    if handle.state == "idle" and not handle.reserved \
                            and (handle.job_id != job_id
                                 or handle.env_hash != want_hash
                                 or len(handle.chips) != n_chips):
                        self._kill_worker(handle)
                        break
                else:
                    return None
            # Spawn a worker pinned to this lease (reserved=True) so another
            # lease cannot steal it the moment it boots — stealing cascades
            # into one extra spawn per steal.
            chips = ()
            if n_chips:
                self._release_exited_chips()
                chips = self.chips.acquire(n_chips)
                if chips is None:
                    # The TPU resource is free but the chips are not:
                    # idle pooled workers keep theirs until they exit.
                    # Retire them and wait for the processes to go.
                    for handle in list(self.workers.values()):
                        if handle.chips and handle.state == "idle" \
                                and not handle.reserved:
                            self._kill_worker(handle)
                    remaining = deadline - asyncio.get_event_loop().time()
                    if remaining <= 0:
                        return None
                    await self._wait_worker_slot(min(remaining, 0.2))
                    continue
            try:
                handle = await self._spawn_worker(job_id, runtime_env,
                                                  chips)
            except BaseException:
                self.chips.release(chips)
                raise
            handle.reserved = True
            try:
                await asyncio.wait_for(
                    handle.ready.wait(),
                    max(0.1, deadline - asyncio.get_event_loop().time()))
            except asyncio.TimeoutError:
                self._kill_worker(handle)
                return None
            handle.reserved = False
            handle.state = "claimed"
            return handle

    async def _escalate_kill(self, proc, grace: float | None = None):
        """Bounded SIGTERM -> wait -> SIGKILL escalation.

        SIGTERM can be ignored or deferred by native code (TPU runtime,
        compiled extensions) and by the worker's own graceful-exit drain;
        polling every 50ms keeps detection prompt while the grace window
        (worker_sigterm_grace_s) bounds how long a stuck child can wedge
        teardown before SIGKILL ends it unconditionally."""
        if grace is None:
            grace = _cfg().worker_sigterm_grace_s
        deadline = time.monotonic() + max(0.0, grace)
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                return
            await asyncio.sleep(0.05)
        if proc.poll() is None:
            try:
                proc.kill()
            except Exception:
                pass

    def _release_exited_chips(self):
        """Free the chips of worker processes that have exited."""
        live = []
        for proc, chips in self._chip_procs:
            if proc.poll() is None:
                live.append((proc, chips))
            else:
                self.chips.release(chips)
        if len(live) != len(self._chip_procs):
            self._chip_procs = live
            self._notify_capacity()

    def _kill_worker(self, handle: WorkerHandle):
        self.workers.pop(handle.proc.pid, None)
        if handle.proc.poll() is None:
            handle.proc.terminate()
            try:
                asyncio.ensure_future(self._escalate_kill(handle.proc))
            except RuntimeError:
                pass  # no running loop (teardown path escalates itself)

    # ---------------- leasing ----------------

    def _reserve(self, demand: dict) -> bool:
        for k, v in demand.items():
            if v > 0 and self.resources_available.get(k, 0.0) + 1e-9 < v:
                return False
        for k, v in demand.items():
            if v > 0:
                self.resources_available[k] = self.resources_available.get(k, 0.0) - v
        return True

    def _unreserve(self, demand: dict):
        for k, v in demand.items():
            if v > 0:
                self.resources_available[k] = min(
                    self.resources_available.get(k, 0.0) + v,
                    self.resources_total.get(k, float("inf")))
        self._notify_capacity()

    def _notify_capacity(self, n: int | None = None):
        if self._capacity_freed is not None:
            self._capacity_freed.set()
            self._capacity_freed = asyncio.Event()
        # Hand freed workers/slots to as many parked leases as current
        # capacity can plausibly satisfy in ONE pass — under batched
        # grants a single release can unblock several small leases, and
        # a one-baton handoff serialized them a release event apart.
        # Still bounded: broadcasting to EVERY parked waiter is
        # O(waiters x workers) per event — the measured collapse mode of
        # a 1,000-actor storm (each ready wakes 1,000 leases, each
        # rescanning 1,000 handles) — so the wake count is capped by
        # idle workers plus startup-throttle headroom (a woken waiter
        # that can't use the slot re-parks, which self-limits).
        if not self._worker_waiters:
            return
        if n is None:
            idle = sum(1 for w in self.workers.values()
                       if w.state == "idle" and not w.reserved)
            starting = sum(1 for w in self.workers.values()
                           if w.state == "starting") + self._spawning
            headroom = self.max_startup_concurrency - starting
            n = max(1, idle + max(0, headroom))
        while self._worker_waiters and n > 0:
            fut = self._worker_waiters.popleft()
            if not fut.done():
                fut.set_result(None)
                n -= 1

    async def _wait_capacity(self, timeout: float):
        if self._capacity_freed is None:
            self._capacity_freed = asyncio.Event()
        ev = self._capacity_freed
        try:
            await asyncio.wait_for(ev.wait(), timeout)
        except asyncio.TimeoutError:
            pass

    async def _wait_worker_slot(self, timeout: float):
        """Park until ONE capacity event is handed to us (FIFO), with a
        bounded nap as a backstop — both for lost wakeups and for the
        baton landing on a waiter that can't use the freed slot (a
        tpu/runtime-env mismatch re-parks without passing it on; the
        1s cap bounds that added latency).  Callers re-check their
        condition in a loop either way."""
        fut = asyncio.get_running_loop().create_future()
        self._worker_waiters.append(fut)
        try:
            await asyncio.wait_for(fut, min(timeout, 1.0))
        except asyncio.TimeoutError:
            pass
        finally:
            if not fut.done():
                fut.cancel()

    def _bundle_reserve(self, bundle_key: tuple, demand: dict) -> bool:
        """Charge a lease against a committed bundle's remaining capacity."""
        b = self.bundles.get(bundle_key)
        if b is None or not b["committed"]:
            return False
        avail = b["available"]
        for k, v in demand.items():
            if v > 0 and avail.get(k, 0.0) + 1e-9 < v:
                return False
        for k, v in demand.items():
            if v > 0:
                avail[k] = avail.get(k, 0.0) - v
        return True

    def _bundle_unreserve(self, bundle_key: tuple, demand: dict):
        b = self.bundles.get(bundle_key)
        if b is None:  # PG removed while the lease was out; nothing to refund
            return
        for k, v in demand.items():
            if v > 0:
                b["available"][k] = min(
                    b["available"].get(k, 0.0) + v, b["reserved"].get(k, v))
        self._notify_capacity()

    def _release_lease(self, handle: "WorkerHandle"):
        if handle.lease_bundle is not None:
            self._bundle_unreserve(handle.lease_bundle,
                                   handle.lease_resources)
        else:
            self._unreserve(handle.lease_resources)
        handle.lease_resources = {}
        handle.lease_bundle = None

    def _chips_for_lease(self, demand: dict):
        """(chips to bind, None) for a lease — its ``TPU`` demand
        (``num_tpus``) — or (0, rejection): a demand this host can never
        bind is refused for good, not queued."""
        n = demand.get("TPU", 0)
        why = None
        if n != int(n):
            why = (f"TPU demand {n} is not a whole number of chips; a chip "
                   f"belongs to one process at a time")
        elif n and not leasable(n, self.chips.host_chips):
            why = (f"cannot lease {int(n)} of this host's "
                   f"{self.chips.host_chips} chips to one worker: a worker "
                   f"is confined to one chip or given them all")
        if why:
            events.record("sched", "lease_reject", reason="chips",
                          demand=demand)
            return 0, {"granted": False, "reason": why, "permanent": True}
        return int(n), None

    async def lease_worker(self, req):
        """Lease a worker for normal task execution; queues while the node is
        saturated (reference: RequestWorkerLease node_manager.proto:363 +
        LocalTaskManager dispatch queue).  With req["bundle"]=(pg_hex, idx)
        the demand is charged against that placement-group bundle."""
        if self.preempting:
            events.record("sched", "lease_reject", reason="preempting")
            return {"granted": False, "reason": "preempting"}
        demand = req.get("resources", {})
        bundle = tuple(req["bundle"]) if req.get("bundle") else None
        job_id = req.get("job_id", 0)
        # Batched grants: the request carries how many same-key leases the
        # driver's queue wants; grant as many as this node can satisfy
        # RIGHT NOW in one reply (parking only while it can grant zero).
        # Worker acquisition for a multi-grant runs concurrently, so N
        # cold spawns coalesce into one batched zygote fork.
        count = max(1, int(req.get("count", 1)))
        n_chips, reject = self._chips_for_lease(demand)
        if reject:
            return reject
        self._note_lease_demand(job_id, req.get("runtime_env"),
                                bool(n_chips), count)
        loop = asyncio.get_running_loop()
        deadline = loop.time() + req.get("queue_timeout", 10.0)
        grants: list[WorkerHandle] = []
        while True:
            k = 0
            while len(grants) + k < count:
                reserved = (self._bundle_reserve(bundle, demand) if bundle
                            else self._reserve(demand))
                if not reserved:
                    break
                k += 1
            if k:
                handles = await asyncio.gather(*[
                    self._get_worker(job_id,
                                     runtime_env=req.get("runtime_env"),
                                     n_chips=n_chips)
                    for _ in range(k)])
                for handle in handles:
                    if handle is not None:
                        grants.append(handle)
                        continue
                    if bundle:
                        self._bundle_unreserve(bundle, demand)
                    else:
                        self._unreserve(demand)
                if not grants and not any(
                        w.state == "idle" or w.proc.poll() is None
                        for w in self.workers.values()):
                    events.record("sched", "lease_reject",
                                  reason="no_worker")
                    return {"granted": False, "reason": "no_worker"}
            elif not grants and bundle and bundle not in self.bundles:
                events.record("sched", "lease_reject", reason="no_bundle")
                return {"granted": False, "reason": "no_bundle"}
            if grants:
                # Partial fills return immediately: the driver re-pumps
                # for the remainder; holding granted workers hostage to
                # the stragglers would idle them for the parking window.
                break
            remaining = deadline - loop.time()
            if remaining <= 0:
                events.record("sched", "lease_reject", reason="busy",
                              demand=demand)
                return {"granted": False, "reason": "busy"}
            await self._wait_worker_slot(remaining)
        # Chain wake: capacity may remain (fractional demand) — pass the
        # baton to the next parked leases instead of broadcasting.
        self._notify_capacity()
        out = []
        for handle in grants:
            self._lease_seq += 1
            _metrics()["leases_granted"].inc()
            lease_id = f"{self.node_id.hex()[:8]}-{self._lease_seq}"
            handle.leased_at = time.monotonic()
            handle.state = "leased"
            handle.lease_id = lease_id
            handle.lease_resources = dict(demand)
            handle.lease_bundle = bundle
            out.append({"worker_address": handle.address,
                        "native_port": handle.native_port,
                        "lease_id": lease_id, "node_id": self.node_id})
        logger.info("lease %s -> %d worker(s), head pid=%d", out[0]["lease_id"],
                    len(out), grants[0].proc.pid)
        events.record("sched", "lease_grant", lease_id=out[0]["lease_id"],
                      pid=grants[0].proc.pid, granted=len(out),
                      requested=count)
        reply = dict(out[0])
        reply["granted"] = True
        reply["grants"] = out
        return reply

    async def return_worker(self, req):
        for handle in self.workers.values():
            if handle.lease_id == req["lease_id"]:
                self._release_lease(handle)
                logger.info("return lease %s pid=%d", req["lease_id"], handle.proc.pid)
                handle.lease_id = None
                if req.get("kill") or handle.proc.poll() is not None:
                    self._kill_worker(handle)
                else:
                    handle.state = "idle"
                    handle.idle_since = time.monotonic()
                return {"ok": True}
        return {"ok": False}

    async def lease_worker_for_actor(self, req):
        """Dedicated worker for an actor (reference: GcsActorScheduler
        leases via the same raylet path, gcs_actor_scheduler.h:111).

        QUEUES while the node is saturated, like lease_worker: an actor
        storm must drain at worker-spawn speed, not convert transient
        saturation into rejections the GCS spins its placement-attempt
        budget against (reference: leases wait in the raylet's dispatch
        queue until resources and a worker exist)."""
        if self.preempting:
            aid = req.get("actor_id")
            events.record("sched", "lease_reject", reason="preempting",
                          actor=getattr(aid, "hex", lambda: aid)())
            return {"granted": False, "reason": "preempting"}
        demand = req.get("resources", {})
        bundle = tuple(req["bundle"]) if req.get("bundle") else None
        n_chips, reject = self._chips_for_lease(demand)
        if reject:
            return reject
        self._note_lease_demand(req.get("job_id", 0),
                                req.get("runtime_env"), bool(n_chips))
        loop = asyncio.get_running_loop()
        deadline = loop.time() + req.get("queue_timeout", 30.0)
        while True:
            if bundle:
                reserved = self._bundle_reserve(bundle, demand)
                if not reserved and bundle not in self.bundles:
                    return {"granted": False, "reason": "no_bundle"}
            else:
                reserved = self._reserve(demand)
            if reserved:
                handle = await self._get_worker(
                    req.get("job_id", 0),
                    runtime_env=req.get("runtime_env"),
                    n_chips=n_chips)
                if handle is not None:
                    break
                if bundle:
                    self._bundle_unreserve(bundle, demand)
                else:
                    self._unreserve(demand)
            remaining = deadline - loop.time()
            if remaining <= 0:
                return {"granted": False, "reason": "busy"}
            await self._wait_worker_slot(remaining)
        self._notify_capacity()   # chain wake: see lease_worker
        actor_id = req["actor_id"]
        events.record("sched", "lease_grant",
                      actor=getattr(actor_id, "hex", lambda: actor_id)(),
                      pid=handle.proc.pid)
        handle.state = "actor"
        handle.actor_id = req["actor_id"]
        handle.lease_resources = demand
        handle.lease_bundle = bundle
        return {"granted": True, "worker_address": handle.address,
                "native_port": handle.native_port,
                "node_id": self.node_id}

    # ---------------- placement-group bundles (2PC) ----------------
    # Reference: node_manager.proto:378 PrepareBundleResources /
    # :382 CommitBundleResources / CancelResourceReserve + raylet
    # placement_group_resource_manager.h:46.

    async def prepare_bundle(self, req):
        if self.preempting:
            # A doomed node must not accept new gang reservations during
            # its grace window: the PG would commit and immediately die.
            return {"ok": False, "reason": "preempting"}
        key = (req["pg_id"], req["index"])
        demand = req["resources"]
        if key in self.bundles:
            return {"ok": True}  # idempotent re-prepare
        if not self._reserve(demand):
            return {"ok": False, "reason": "resources"}
        self.bundles[key] = {"reserved": dict(demand),
                             "available": dict(demand), "committed": False}
        return {"ok": True}

    async def commit_bundle(self, req):
        b = self.bundles.get((req["pg_id"], req["index"]))
        if b is None:
            return {"ok": False}
        b["committed"] = True
        return {"ok": True}

    async def cancel_bundle(self, req):
        """Release one bundle (or all bundles of a PG when index is None).
        Workers leased against the bundle are killed — their resources were
        the bundle's (reference: raylet kills PG workers on removal)."""
        pg_id = req["pg_id"]
        index = req.get("index")
        keys = [k for k in self.bundles
                if k[0] == pg_id and (index is None or k[1] == index)]
        for key in keys:
            for handle in list(self.workers.values()):
                if handle.lease_bundle == key:
                    handle.lease_resources = {}
                    handle.lease_bundle = None
                    self._kill_worker(handle)
            b = self.bundles.pop(key)
            self._unreserve(b["reserved"])
        return {"ok": True, "released": len(keys)}

    # ---------------- object transfer ----------------

    async def pull_object(self, req):
        """Read an object out of the local store for a remote node.  With
        req["max_inline"], larger objects reply {"too_large", data_size,
        metadata} and the caller switches to the chunk protocol — small
        objects (the common case) stay one round trip."""
        from ray_tpu._private.ids import ObjectID
        max_inline = req.get("max_inline")
        buf = self.store.get(ObjectID(req["id"]), timeout_ms=int(
            req.get("timeout_ms", 0)))
        if buf is None:
            spilled = self._spilled_meta(req["id"])
            if spilled is None:
                return {"found": False}
            data_size, metadata = spilled
            if max_inline is not None and data_size > max_inline:
                return {"found": True, "too_large": True,
                        "data_size": data_size, "metadata": metadata}
            restored = self._read_spilled(req["id"])
            if restored is None:
                return {"found": False}
            _metrics()["objects_restored"].inc()
            data, metadata = restored
            return {"found": True, "data": data, "metadata": metadata,
                    "spilled": True}
        try:
            if max_inline is not None and len(buf.data) > max_inline:
                xfer = getattr(self, "transfer_server", None)
                return {"found": True, "too_large": True,
                        "data_size": len(buf.data),
                        "metadata": buf.metadata,
                        "transfer_port":
                            xfer.port if xfer is not None else None}
            return {"found": True, "data": bytes(buf.data),
                    "metadata": buf.metadata}
        finally:
            buf.release()

    async def pull_object_meta(self, req):
        """Size/metadata probe for the chunked pull path (reference:
        object_manager chunked transfer: ObjectBufferPool chunk layout).
        Accepts the typed contract (protocol.pb.PullObjectMetaRequest) or
        the legacy dict, replying in kind."""
        from ray_tpu import protocol
        from ray_tpu._private.ids import ObjectID
        typed = protocol.is_message(req)
        id_binary = req.id if typed else req["id"]
        oid = ObjectID(id_binary)
        xfer = getattr(self, "transfer_server", None)
        xfer_port = xfer.port if xfer is not None else None

        def reply(found, data_size=0, metadata=b"", spilled=False,
                  port=None):
            if typed:
                return protocol.pb.PullObjectMetaReply(
                    found=found, data_size=data_size, metadata=metadata,
                    spilled=spilled, transfer_port=port or 0)
            return {"found": found, "data_size": data_size,
                    "metadata": metadata, "spilled": spilled,
                    "transfer_port": port}

        buf = self.store.get(oid, timeout_ms=0)
        if buf is not None:
            try:
                return reply(True, len(buf.data), buf.metadata, False,
                             xfer_port)
            finally:
                buf.release()
        spilled = self._spilled_meta(id_binary)
        if spilled is None:
            return reply(False)
        data_size, meta = spilled
        # Spilled payloads live on disk, not in the shm segment — the
        # native plane can't serve them; the puller stays on chunk RPCs.
        return reply(True, data_size, meta, True)

    async def pull_object_chunk(self, req):
        """One chunk of an object's payload (reference: push_manager.h
        chunked pushes with in-flight throttling — here the PULLER
        throttles).  Typed (PullObjectChunkRequest) or legacy dict."""
        from ray_tpu import protocol
        from ray_tpu._private.ids import ObjectID
        typed = protocol.is_message(req)
        if typed:
            id_binary, offset, length = req.id, req.offset, req.length
        else:
            id_binary, offset, length = req["id"], req["offset"], \
                req["length"]

        def reply(found, data=b""):
            if typed:
                return protocol.pb.PullObjectChunkReply(found=found,
                                                        data=data)
            return {"found": found, "data": data}

        buf = self.store.get(ObjectID(id_binary), timeout_ms=0)
        if buf is not None:
            try:
                return reply(True, bytes(buf.data[offset:offset + length]))
            finally:
                buf.release()
        chunk = self._read_spilled_range(id_binary, offset, length)
        if chunk is None:
            return reply(False)
        return reply(True, chunk)

    async def push_object(self, req):
        from ray_tpu import protocol
        from ray_tpu._private.ids import ObjectID
        typed = protocol.is_message(req)
        if typed:
            id_binary, data, metadata = req.id, req.data, req.metadata
        else:
            id_binary, data, metadata = req["id"], req["data"], \
                req.get("metadata", b"")
        oid = ObjectID(id_binary)
        if not self.store.contains(oid):
            try:
                self.store.put_bytes(oid, data, metadata)
            except Exception as e:  # duplicate create race is fine
                logger.debug("push_object: %s", e)
        return protocol.pb.PushObjectReply(ok=True) if typed \
            else {"ok": True}

    async def free_object(self, req):
        from ray_tpu._private.ids import ObjectID
        self.store.delete(ObjectID(req["id"]))
        self._drop_spilled(req["id"])
        return {"ok": True}

    async def free_objects(self, req):
        """Batched form: owners buffer freed ids and flush one RPC
        (reference: raylet FreeObjects batches plasma deletions)."""
        from ray_tpu._private.ids import ObjectID
        for id_binary in req["ids"]:
            self.store.delete(ObjectID(id_binary))
            self._drop_spilled(id_binary)
        return {"ok": True}

    async def store_stats(self, req):
        stats = self.store.stats()
        stats["spilled_objects"] = len(self.spilled)
        stats["spilled_bytes"] = self.spilled_bytes
        return stats

    # ---------------- worker log streaming ----------------

    def _collect_worker_log_lines(self, handle, final: bool = False):
        """New COMPLETE lines from a worker's log files.  Only consumes up
        to the last newline so a line straddling the read boundary (or a
        mid-write flush) is never split — unless `final` (worker dead:
        loop to EOF and flush everything, including a trailing partial
        line).  Returns (lines, undo) where undo restores the offsets if
        the publish fails (lines must not be lost to a GCS blip)."""
        lines = []
        undo = []
        for stream, path in handle.log_paths.items():
            prev = handle.log_offsets[stream]
            consumed = 0
            try:
                with open(path, "rb") as f:
                    f.seek(prev)
                    while True:
                        chunk = f.read(256 * 1024)
                        if not chunk:
                            break
                        if not final:
                            cut = chunk.rfind(b"\n")
                            if cut < 0:
                                break  # no complete line yet
                            chunk = chunk[:cut + 1]
                        consumed += len(chunk)
                        for raw in chunk.decode(
                                "utf-8", "replace").splitlines():
                            lines.append({"pid": handle.proc.pid,
                                          "job_id": handle.job_id,
                                          "stream": stream, "line": raw})
                        if not final:
                            break  # one bounded read per tick
            except OSError:
                continue
            if consumed:
                handle.log_offsets[stream] = prev + consumed
                undo.append((handle, stream, prev))
        return lines, undo

    async def _publish_log_lines(self, lines: list, undo: list) -> None:
        if not lines:
            return
        try:
            await self.gcs.call("Gcs", "add_log_lines", {"lines": lines})
        except Exception:
            # Rewind so the next tick re-reads — a GCS blip must not
            # create silent gaps in the stream.
            for handle, stream, prev in undo:
                handle.log_offsets[stream] = prev

    async def _log_tail_loop(self):
        """Tail worker stdout/stderr into the GCS log channel (reference:
        _private/log_monitor.py -> GCS pubsub -> driver echo)."""
        while True:
            await asyncio.sleep(0.5)
            lines = []
            undo = []
            for handle in list(self.workers.values()):
                ls, ud = self._collect_worker_log_lines(handle)
                lines.extend(ls)
                undo.extend(ud)
            await self._publish_log_lines(lines, undo)

    # ---------------- memory monitor ----------------

    @staticmethod
    def _read_memory_fraction() -> float:
        """Node memory usage fraction from /proc/meminfo (reference:
        common/memory_monitor.cc cgroup/system probing)."""
        try:
            info = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    parts = line.split()
                    if len(parts) >= 2:
                        info[parts[0].rstrip(":")] = int(parts[1])
            total = info.get("MemTotal", 0)
            avail = info.get("MemAvailable", 0)
            if total <= 0:
                return 0.0
            return 1.0 - avail / total
        except OSError:
            return 0.0

    def _pick_oom_victim(self):
        """Newest leased task worker first (its task is retriable), then
        newest actor worker (restartable per policy) — reference:
        raylet/worker_killing_policy.cc retriable-LIFO."""
        leased = [w for w in self.workers.values()
                  if w.state == "leased" and w.proc.poll() is None]
        if leased:
            return max(leased, key=lambda w: w.leased_at)
        actors = [w for w in self.workers.values()
                  if w.state == "actor" and w.proc.poll() is None]
        if actors:
            return max(actors, key=lambda w: w.leased_at)
        return None

    async def _memory_monitor_loop(self):
        while True:
            interval = _cfg().memory_monitor_interval_s
            await asyncio.sleep(interval)
            try:
                threshold = _cfg().memory_usage_threshold
                frac = self._read_memory_fraction()
                if frac < threshold:
                    continue
                victim = self._pick_oom_victim()
                if victim is None:
                    continue
                logger.error(
                    "node memory at %.0f%% (threshold %.0f%%): killing "
                    "worker pid=%d to relieve pressure", frac * 100,
                    threshold * 100, victim.proc.pid)
                _metrics()["oom_workers_killed"].inc()
                self._release_lease(victim)
                # _kill_worker already schedules the bounded
                # SIGTERM -> wait -> SIGKILL escalation (_escalate_kill);
                # the old one-shot 2s poll here could miss a worker whose
                # native code ignored SIGTERM and raced the poll.
                self._kill_worker(victim)
                # Cooldown: give the kernel time to reclaim before judging
                # again — otherwise one spike serially destroys the node.
                await asyncio.sleep(max(3 * interval, 2.0))
            except Exception:
                logger.exception("memory monitor pass failed")

    # ---------------- spilling ----------------

    def _spill_some(self, bytes_needed: int = 0) -> int:
        """Spill sealed, unreferenced objects (oldest LRU first) until
        usage is under the low watermark (plus any immediate need)."""
        stats = self.store.stats()
        used, cap = stats["used"], stats["capacity"]
        goal = self.spill_low * cap
        if bytes_needed:
            goal = min(goal, cap - min(bytes_needed, cap))
        if used <= (self.spill_high * cap if not bytes_needed else goal):
            return 0
        os.makedirs(self.spill_dir, exist_ok=True)
        from ray_tpu.util import spans
        tok = spans.begin("object", "spill", store_used=used)
        freed = 0
        count = 0
        for oid, size, refcount, sealed, _tick in self.store.list_objects():
            if used - freed <= goal:
                break
            if not sealed or refcount != 0:
                continue
            if oid.binary() in self.spilled:
                continue
            buf = self.store.get(oid, timeout_ms=0)
            if buf is None:
                continue
            path = os.path.join(self.spill_dir, oid.hex())
            try:
                meta = bytes(buf.metadata) if buf.metadata else b""
                data = bytes(buf.data)
            finally:
                buf.release()
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(len(meta).to_bytes(8, "little"))
                f.write(meta)
                f.write(data)
            os.replace(tmp, path)
            self.spilled[oid.binary()] = (path, size)
            self.spilled_bytes += size
            _metrics()["objects_spilled"].inc()
            _metrics()["bytes_spilled"].inc(size)
            self.store.delete(oid)
            freed += size
            count += 1
        spans.end(tok, freed=freed, objects=count)
        if freed:
            logger.info("spilled %d bytes (%d objects on disk)", freed,
                        len(self.spilled))
        return freed

    def _read_spilled(self, id_binary: bytes):
        ent = self.spilled.get(id_binary)
        if ent is None:
            return None
        path, _size = ent
        try:
            with open(path, "rb") as f:
                meta_len = int.from_bytes(f.read(8), "little")
                meta = f.read(meta_len)
                data = f.read()
            return data, meta
        except FileNotFoundError:
            return None

    def _spilled_meta(self, id_binary: bytes):
        """(data_size, metadata) without reading the payload."""
        ent = self.spilled.get(id_binary)
        if ent is None:
            return None
        path, _size = ent
        try:
            total = os.path.getsize(path)
            with open(path, "rb") as f:
                meta_len = int.from_bytes(f.read(8), "little")
                meta = f.read(meta_len)
            return total - 8 - meta_len, meta
        except OSError:
            return None

    def _read_spilled_range(self, id_binary: bytes, offset: int,
                            length: int):
        """Seek+read one payload range — chunked pulls of spilled objects
        must not re-read the whole file per chunk."""
        ent = self.spilled.get(id_binary)
        if ent is None:
            return None
        path, _size = ent
        try:
            with open(path, "rb") as f:
                meta_len = int.from_bytes(f.read(8), "little")
                f.seek(8 + meta_len + offset)
                return f.read(length)
        except OSError:
            return None

    def _drop_spilled(self, id_binary: bytes):
        ent = self.spilled.pop(id_binary, None)
        if ent is not None:
            self.spilled_bytes -= ent[1]
            try:
                os.unlink(ent[0])
            except OSError:
                pass

    async def spill_objects(self, req):
        """Spill request from a worker whose put hit OOM (reference:
        raylet SpillObjects RPC, core_worker.proto:443).  Disk writes run
        in an executor thread — blocking the daemon loop would starve
        heartbeats and lease RPCs exactly when the node is under memory
        pressure."""
        if not self.spill_enabled:
            return {"freed": 0}
        loop = asyncio.get_running_loop()
        freed = await loop.run_in_executor(
            None, self._spill_some, req.get("bytes_needed", 0))
        return {"freed": freed}

    async def _spill_loop(self):
        loop = asyncio.get_running_loop()
        while True:
            await asyncio.sleep(0.5)
            try:
                if self.spill_enabled:
                    await loop.run_in_executor(None, self._spill_some, 0)
            except Exception:
                logger.exception("spill sweep failed")

    async def get_metrics(self, req):
        """Node-level metric snapshot (reference: per-node agent scrape
        path, _private/metrics_agent.py): the daemon's own registry plus
        every live worker's, merged.  Application metrics live in worker
        processes (e.g. serve replica inference engines export prefix
        cache hit rates), so a hostd-only scrape would miss them.
        Worker probes run concurrently and failures are skipped — a
        wedged worker must not take down the node scrape."""
        from ray_tpu.util import metrics as mt
        _metrics()["store_used_bytes"].set(self.store.stats()["used"])
        merged = mt.collect()
        handles = [h for h in self.workers.values() if h.address]

        async def probe(handle):
            try:
                reply = await self.pool.get(handle.address).call(
                    "CoreWorker", "Metrics", {}, timeout=2)
                return reply.get("metrics") or {}
            except Exception:
                return {}

        for snap in await asyncio.gather(*[probe(h) for h in handles]):
            mt.merge_snapshot(merged, snap)
        return {"metrics": merged, "node_id": self.node_id.hex()}

    async def stack_traces(self, req):
        """Aggregate live thread stacks from this node's workers plus the
        daemon itself (reference: `ray stack` scripts.py:1798).  Worker
        probes run CONCURRENTLY: a node full of wedged workers — the very
        thing this exists to debug — must dump in ~one timeout, not N."""
        from ray_tpu._private.stack_dump import dump_state
        out = [{"pid": os.getpid(), "kind": "hostd", **dump_state()}]
        handles = [h for h in self.workers.values() if h.address]

        async def probe(handle):
            try:
                reply = await self.pool.get(handle.address).call(
                    "CoreWorker", "StackTrace", {}, timeout=5)
                return {"pid": reply["pid"], "kind": "worker",
                        "state": handle.state, "threads": reply["threads"],
                        "recent_events": reply.get("recent_events") or []}
            except Exception as e:
                return {"pid": handle.proc.pid, "kind": "worker",
                        "state": handle.state, "error": repr(e),
                        "threads": []}

        out.extend(await asyncio.gather(*[probe(h) for h in handles]))
        return {"processes": out}

    async def collect_stacks(self, req):
        """Live thread dumps from a SPECIFIC set of this node's workers
        (by pid) — the train hang watchdog's diagnosis RPC.  Unlike
        stack_traces this skips the daemon self-dump and probes only the
        gang's workers, concurrently: a wedged gang must dump in ~one
        probe timeout, not N."""
        pids = set(req.get("pids") or [])
        handles = [h for h in self.workers.values()
                   if h.address and (not pids or h.proc.pid in pids)]

        async def probe(handle):
            try:
                reply = await self.pool.get(handle.address).call(
                    "CoreWorker", "StackTrace", {}, timeout=5)
                return {"pid": reply["pid"], "state": handle.state,
                        "threads": reply["threads"],
                        "recent_events": reply.get("recent_events") or []}
            except Exception as e:
                return {"pid": handle.proc.pid, "state": handle.state,
                        "error": repr(e), "threads": []}

        return {"processes":
                await asyncio.gather(*[probe(h) for h in handles]),
                "node_id": self.node_id.hex()}

    async def collect_events(self, req):
        """Node-level flight-recorder scrape: the daemon's own ring, every
        live worker's ring (concurrent CollectEvents probes), and any
        crash dumps in the session log dir — the black boxes of processes
        that already died.  Each event gains pid/source; `now` rides
        along for cluster-wide clock-skew normalization.  `pinned` holds
        the start-up records (`events.pinned()`) of the same processes,
        the dead ones' rebuilt from their dumps."""
        recv = time.time()
        since = float(req.get("since", 0.0))
        out = [dict(e, pid=os.getpid(), source="live")
               for e in events.snapshot(since=since)]
        records = {os.getpid(): events.pinned()}     # by pid
        handles = [h for h in self.workers.values() if h.address]

        async def probe(handle):
            try:
                reply = await self.pool.get(handle.address).call(
                    "CoreWorker", "CollectEvents", {"since": since},
                    timeout=float(req.get("timeout", 5)))
                return (reply.get("pinned"),
                        [dict(e, pid=reply["pid"], source="live")
                         for e in reply.get("events") or []])
            except Exception:
                return None, []

        for record, chunk in await asyncio.gather(
                *[probe(h) for h in handles]):
            out.extend(chunk)
            if record:
                records[record["pid"]] = record
        # (asked for nothing but the records: the dumps' heads will do)
        dumped = events.read_dumps(os.path.join(self.session_dir, "logs"),
                                   pinned_only=since > recv)
        out.extend(e for e in dumped if e["ts"] >= since)
        for record in events.dumped_records(dumped):
            # (a live process's own answer outranks a dump of it)
            records.setdefault(record["pid"], record)
        # (`recv` beside `now`: probing the workers and reading the dumps
        # can take a second, and the caller sets its clock against both)
        return {"events": out, "node_id": self.node_id.hex(),
                "recv": recv, "now": time.time(),
                "pinned": list(records.values())}

    # ---------------- preemption (maintenance events) ----------------

    async def notify_preemption(self, req):
        """Advance notice that this host will be reclaimed in `grace_s`
        seconds (TPU maintenance event / spot preemption; in production
        wired to the metadata-server preemption signal, here driven by
        the chaos plane).  The daemon immediately stops granting leases
        and bundle reservations, fans the notice out to every live
        worker — train sessions there race a proactive checkpoint save
        against the window — and schedules the kill at the deadline."""
        grace = float(req.get("grace_s", _cfg().chaos_preempt_grace_s))
        if self.preempting:
            return {"ok": True, "already": True}
        self.preempting = True
        self._preempt_victims = {
            h.proc.pid for h in self.workers.values()
            if h.proc.poll() is None}
        _metrics()["preemption_notices"].inc()
        _metrics()["preemption_grace_s"].set(grace)
        logger.warning(
            "preemption notice: node %s reclaimed in %.1fs (%d workers "
            "notified)", self.node_id.hex()[:8], grace,
            len(self._preempt_victims))

        async def _notify(handle):
            try:
                await self.pool.get(handle.address).call(
                    "CoreWorker", "PreemptionNotice",
                    {"grace_s": grace}, timeout=2)
            except Exception:
                pass  # worker mid-exit; the deadline kill covers it

        targets = [h for h in list(self.workers.values())
                   if h.address and h.proc.poll() is None]
        if targets:
            await asyncio.gather(*[_notify(h) for h in targets])
        asyncio.ensure_future(self._preempt_kill(grace))
        return {"ok": True, "grace_s": grace}

    async def _preempt_kill(self, grace: float):
        """The reclaim at the end of the grace window.  A non-head node
        dies whole (os._exit, like a real preemption — the GCS health
        loop declares it dead and peers learn via node-watch).  A head
        node degrades to killing only the workers alive at notice time:
        the colocated GCS must survive so the cluster can re-form, which
        also keeps single-node chaos scenarios runnable."""
        await asyncio.sleep(max(0.0, grace))
        if not self.is_head:
            logger.warning("preemption: node %s reclaimed",
                           self.node_id.hex()[:8])
            os._exit(1)
        for pid in list(self._preempt_victims):
            handle = self.workers.get(pid)
            if handle is not None and handle.proc.poll() is None:
                self._kill_worker(handle)
        self._preempt_victims = set()
        self.preempting = False
        logger.warning("preemption: head %s lost its workers; leasing "
                       "re-enabled", self.node_id.hex()[:8])

    async def worker_exiting(self, req):
        """A worker announcing a deliberate exit (SIGTERM drain,
        preemption abort) before it dies, so the reaper reports intent
        instead of a crash and the owner's retry logic can tell a
        drained worker from a wedged one."""
        handle = self.workers.get(int(req.get("pid", 0)))
        if handle is None:
            return {"ok": False}
        handle.exit_reason = str(req.get("reason", "deliberate"))
        return {"ok": True}

    async def list_workers(self, req):
        """Per-node worker table for the state API (reference:
        experimental/state/api.py list_workers via raylet)."""
        out = []
        for handle in self.workers.values():
            out.append({
                "pid": handle.proc.pid,
                "worker_id": (handle.worker_id.hex()
                              if handle.worker_id else None),
                "state": handle.state,
                "job_id": handle.job_id,
                "address": handle.address,
                "lease_id": handle.lease_id,
                "lease_resources": dict(handle.lease_resources),
                "actor_id": (handle.actor_id.hex()
                             if handle.actor_id else None),
                "idle_s": round(time.monotonic() - handle.idle_since, 1)
                          if handle.state == "idle" else None,
                "alive": handle.proc.poll() is None,
            })
        return {"workers": out, "node_id": self.node_id.hex(),
                "store": self.store.stats(),
                "resources_total": dict(self.resources_total),
                "resources_available": dict(self.resources_available)}

    # ---------------- lifecycle ----------------

    async def shutdown_node(self, req):
        self._shutdown.set()
        return {"ok": True}

    def node_info(self) -> NodeInfo:
        import socket
        return NodeInfo(
            node_id=self.node_id,
            address=f"{self.host}:{self.server.port}",
            store_path=self.store_path,
            hostname=socket.gethostname(),
            resources_total=dict(self.resources_total),
            resources_available=dict(self.resources_available),
            is_head=self.is_head,
            incarnation=self.incarnation,
        )

    def _state_snapshot(self) -> dict:
        """Ground truth shipped with every (re-)register: what this node
        actually runs right now.  After a GCS restart the restored tables
        are a hypothesis; the anti-entropy reconcile trusts this instead
        (reference: raylet's RegisterNode piggybacks its live worker set
        on GCS restart via RayletNotifyGCSRestart)."""
        actors = []
        leased = 0
        for h in self.workers.values():
            if h.proc.poll() is not None:
                continue
            if h.state == "actor" and h.actor_id is not None:
                actors.append({"actor_id": h.actor_id,
                               "address": h.address})
            elif h.state == "leased":
                leased += 1
        return {"actors": actors, "leases": leased,
                "workers": len(self.workers),
                "incarnation": self.incarnation}

    def _fence_self(self, granted_incarnation: int, reason: str):
        """The GCS declared this node dead and failed its actors over;
        everything running here is a stale gang.  Kill ALL workers (an
        op from a fenced incarnation must never double-apply against the
        failed-over replacements), drop bundle reservations, and adopt
        the granted incarnation so the follow-up register is accepted."""
        victims = [h for h in list(self.workers.values())
                   if h.proc.poll() is None]
        logger.warning(
            "fencing node %s: %s (incarnation %d -> %d, killing %d "
            "stale workers)", self.node_id.hex()[:8], reason,
            self.incarnation, granted_incarnation, len(victims))
        events.record("proc", "node_fenced", node=self.node_id.hex()[:8],
                      incarnation=granted_incarnation,
                      stale_workers=len(victims), reason=reason)
        for h in victims:
            self._kill_worker(h)
        self.workers.clear()
        self.bundles.clear()
        self.resources_available = dict(self.resources_total)
        self.incarnation = int(granted_incarnation)

    async def _register_with_gcs(self, timeout: float = 10):
        """Register (or re-register) with the anti-entropy snapshot,
        honoring a fencing verdict: on "fenced" the node kills its stale
        gang, adopts the granted incarnation, and registers again as the
        fresh incarnation.  Stale actors the GCS reports back (workers
        whose incarnation lost ownership while we were partitioned) are
        reaped here."""
        req = {"info": self.node_info(), "snapshot": self._state_snapshot()}
        reply = await self.gcs.call("Gcs", "register_node", req,
                                    timeout=timeout)
        if isinstance(reply, dict) and reply.get("fenced"):
            self._fence_self(
                int(reply.get("incarnation", self.incarnation + 1)),
                "GCS refused registration: node was declared dead")
            reply = await self.gcs.call(
                "Gcs", "register_node",
                {"info": self.node_info(),
                 "snapshot": self._state_snapshot()},
                timeout=timeout)
        stale = (reply.get("stale_actors") or []) \
            if isinstance(reply, dict) else []
        if stale:
            stale_set = set(stale)
            for h in list(self.workers.values()):
                if h.actor_id is not None and h.actor_id in stale_set:
                    logger.warning(
                        "reaping stale actor worker pid %d: its actor "
                        "was failed over while this node was away",
                        h.proc.pid)
                    events.record("proc", "stale_actor_reaped",
                                  node=self.node_id.hex()[:8],
                                  pid=h.proc.pid)
                    self._kill_worker(h)
        return reply

    async def _heartbeat_loop(self):
        from ray_tpu import protocol
        from ray_tpu._private.fault_injection import get_chaos
        last_ok = time.monotonic()
        while not self._shutdown.is_set():
            chaos = get_chaos()
            if chaos is not None and chaos.kill_hostd(self.is_head):
                # Injected node failure: die like a preempted host — no
                # cleanup, no dereg.  The GCS health loop declares the
                # node dead after node_death_timeout_s and fails over its
                # actors; peers learn through their node-watch loops.
                logger.warning("chaos: killing hostd %s",
                               self.node_id.hex()[:8])
                events.record("proc", "chaos_kill",
                              node=self.node_id.hex()[:8])
                events.dump_crash("chaos_kill_hostd")
                os._exit(1)
            if (chaos is not None and not self.preempting
                    and chaos.preempt_hostd(self.is_head)):
                # Injected maintenance event: a preemption NOTICE with a
                # grace window, not an instant kill.  Unlike kill_hostd
                # this may target the head — it degrades to losing only
                # its workers so the colocated GCS survives.
                logger.warning("chaos: preemption notice on hostd %s",
                               self.node_id.hex()[:8])
                asyncio.ensure_future(self.notify_preemption(
                    {"grace_s": _cfg().chaos_preempt_grace_s}))
            try:
                hb = protocol.pb.HeartbeatRequest(
                    node_id=self.node_id.binary())
                for k, v in self.resources_available.items():
                    hb.available.amounts[k] = v
                # outage_retry=False: the heartbeat MEASURES GCS liveness
                # (the silence window below keys on it), so it must fail
                # fast per tick instead of riding the outage out inside
                # the client.
                reply = await self.gcs.call("Gcs", "heartbeat", hb,
                                            timeout=5, outage_retry=False)
                last_ok = time.monotonic()
                if reply.shutdown:
                    self._shutdown.set()
                if reply.reregister:
                    await self._register_with_gcs()
            except Exception:
                # Slow is not dead: a saturated single-core GCS (actor
                # storm, bulk submissions) can stall past any single RPC
                # timeout; a hostd suicide then cascades into hundreds of
                # "connection refused" failures.  Exit only after a
                # sustained silent window — real GCS death also trips the
                # driver/launcher watchdogs.  With a supervised GCS the
                # window never expires the node: the head is coming back
                # at the same address, and a suicide here would turn a
                # restartable head outage into whole-cluster loss.
                silent = time.monotonic() - last_ok
                if silent > float(_cfg().gcs_silent_window_s):
                    if _cfg().gcs_supervise:
                        logger.warning(
                            "GCS unreachable for %.0fs; supervised head — "
                            "riding the outage out", silent)
                        last_ok = time.monotonic()  # re-arm the window
                    else:
                        logger.error(
                            "GCS unreachable for %.0fs; hostd exiting",
                            silent)
                        self._shutdown.set()
            await asyncio.sleep(gcs_mod.HEARTBEAT_INTERVAL_S)

    async def _node_watch_loop(self):
        """Propagate GCS-detected node death to this node's workers
        (reference: raylet subscribes to GCS NodeRemoved and notifies its
        core workers).

        The heartbeat reply is a compiled proto with no room for
        membership deltas, so the daemon polls the GCS node table — the
        cluster version makes the no-change iteration one cheap RPC —
        and, when a peer transitions alive->dead, invalidates the peer's
        pooled channel and pushes a NodeDead notification to every live
        local worker.  Owners there drop the dead node from object
        location sets and purge its worker leases
        (core_worker._rpc_node_dead), reconnecting lease demand to the
        surviving nodes."""
        known_alive: set | None = None
        version = None
        while not self._shutdown.is_set():
            try:
                reply = await self.gcs.call("Gcs", "get_nodes", {},
                                            timeout=5)
            except Exception as e:
                # Not silent: the outage is already metered by the
                # GcsClient (gcs/unreachable + gcs_unreachable_seconds);
                # this marks the watch loop itself as degraded so `cli
                # events` shows WHICH consumer was blind, then keeps
                # polling — membership deltas resume on reconnect.
                events.record("gcs", "unreachable", loop="node_watch",
                              error=str(e)[:120])
                await asyncio.sleep(gcs_mod.HEARTBEAT_INTERVAL_S)
                continue
            boot = reply.get("boot_id")
            if boot is not None and boot != self._gcs_boot_id:
                if self._gcs_boot_id is not None:
                    # The head restarted underneath us.  Its restored
                    # tables list this node alive, so no heartbeat will
                    # nudge a reregister — push the anti-entropy snapshot
                    # proactively so GCS state converges to ground truth.
                    logger.warning("GCS restarted (boot %s); "
                                   "re-registering with snapshot", boot)
                    try:
                        await self._register_with_gcs()
                    except Exception:
                        pass  # the resync-pending heartbeat nudge remains
                self._gcs_boot_id = boot
            if reply.get("version") != version:
                version = reply.get("version")
                nodes = reply["nodes"]
                alive = {n.node_id.hex() for n in nodes if n.alive}
                if known_alive is not None:
                    addr_of = {n.node_id.hex(): n.address for n in nodes}
                    for nid in known_alive - alive:
                        if nid == self.node_id.hex():
                            continue
                        addr = addr_of.get(nid, "")
                        logger.warning("peer node %s (%s) declared dead",
                                       nid[:8], addr)
                        if addr:
                            self.pool.invalidate(addr)
                        await self._broadcast_node_dead(nid, addr)
                known_alive = alive
            await asyncio.sleep(gcs_mod.HEARTBEAT_INTERVAL_S)

    async def _broadcast_node_dead(self, nid_hex: str, addr: str):
        async def _notify(handle):
            try:
                await self.pool.get(handle.address).call(
                    "CoreWorker", "NodeDead",
                    {"node_id": nid_hex, "address": addr}, timeout=2)
            except Exception:
                pass  # worker may be mid-exit; its own RPCs will fail over

        targets = [h for h in list(self.workers.values())
                   if h.address and h.proc.poll() is None]
        if targets:
            await asyncio.gather(*[_notify(h) for h in targets])

    async def _reaper_loop(self):
        """Detect dead/idle-expired workers; report dead actor workers."""
        while not self._shutdown.is_set():
            now = time.monotonic()
            z = self._zygote   # snapshot: _zygote_close can race the await
            if z is not None:
                # Drain reap reports (authoritative exit codes for forked
                # workers) off-loop; the pipe round trip is ~1ms but must
                # not stall RPC serving under load.
                try:
                    await asyncio.get_running_loop().run_in_executor(
                        None, z.poll_exits, self._zygote_exits)
                except Exception:
                    # Close ONLY if the zygote process is actually dead:
                    # terminating it reparents every forked worker, whose
                    # ppid watch then kills them — one transient pipe
                    # error must never take down the node's workers.
                    if z.proc.poll() is not None:
                        logger.warning("zygote died; cold spawns only")
                        if self._zygote is z:
                            self._zygote_close()
                    else:
                        logger.warning("zygote reap poll failed (kept)")
            for handle in list(self.workers.values()):
                if handle.proc.poll() is not None:
                    # Final log read FIRST: a crashing worker's traceback
                    # is exactly what must reach the driver.
                    ls, ud = self._collect_worker_log_lines(handle,
                                                            final=True)
                    await self._publish_log_lines(ls, ud)
                    self.workers.pop(handle.proc.pid, None)
                    self._release_lease(handle)
                    if handle.state == "actor" and handle.actor_id is not None:
                        # A crash's last words (a libtpu abort, say) are
                        # the cause the actor's callers need to see.
                        tail = " | ".join(
                            ln["line"] for ln in ls
                            if ln["stream"] == "stderr")[-400:]
                        reason = (f"worker exited deliberately "
                                  f"({handle.exit_reason})"
                                  if handle.exit_reason else
                                  f"worker exited "
                                  f"({handle.proc.returncode})"
                                  + (f": {tail}" if tail else ""))
                        try:
                            await self.gcs.call(
                                "Gcs", "report_actor_death",
                                {"actor_id": handle.actor_id,
                                 "address": handle.address,
                                 "reason": reason},
                                timeout=2)
                        except Exception:
                            pass
                elif (handle.state == "idle"
                      and now - handle.idle_since > _cfg().worker_idle_ttl_s):
                    self._kill_worker(handle)
            self._release_exited_chips()
            self._prewarm_tick()
            await asyncio.sleep(0.2)

    def _note_lease_demand(self, job_id: int, runtime_env, tpu: bool,
                           count: int = 1) -> None:
        from ray_tpu._private import runtime_env as renv
        key = (job_id, renv.env_hash(runtime_env), tpu)
        t = time.monotonic()
        for _ in range(min(count, 64)):
            self._lease_demand.append((t, key, runtime_env))

    def _prewarm_tick(self, window_s: float = 5.0):
        """Keep idle workers forked ahead of demand: recent lease traffic
        for a (job, env, non-TPU) pool seeds up to zygote_spawn_parallelism
        spare workers per tick, so the next storm wave claims an idle fork
        instead of paying a cold spawn inside its lease RPC.  Only while
        the zygote is serving (forks are ~1-2ms; pre-warming cold Popens
        would fight the startup throttle it exists to protect)."""
        if (self._zygote is None or self.preempting
                or not _cfg().worker_prewarm or not self._lease_demand):
            return
        # Pre-warm only fills SPARE startup capacity.  During a storm the
        # lease path keeps the throttle saturated on its own; unthrottled
        # extra forks would steal CPU from boots already in flight (which
        # is strictly worse than doing nothing — measured 23/s -> 8/s on
        # a 1-core actor storm before this guard existed).
        starting = sum(1 for w in self.workers.values()
                       if w.state == "starting") + self._spawning
        headroom = self.max_startup_concurrency - starting
        if headroom <= 0:
            return
        horizon = time.monotonic() - window_s
        while self._lease_demand and self._lease_demand[0][0] < horizon:
            self._lease_demand.popleft()
        if not self._lease_demand:
            return
        demand: dict = {}
        envs: dict = {}
        for _, key, runtime_env in self._lease_demand:
            demand[key] = demand.get(key, 0) + 1
            envs[key] = runtime_env
        live = sum(1 for w in self.workers.values()
                   if w.proc.returncode is None)
        budget = min(_cfg().zygote_spawn_parallelism, headroom,
                     self.max_workers - live)
        for (job_id, env_hash, tpu), seen in sorted(
                demand.items(), key=lambda kv: -kv[1]):
            if budget <= 0:
                break
            if tpu:
                continue   # TPU workers never fork; no cheap pre-warm
            # Supply = every live matching worker, whatever its state:
            # leases counted in `seen` were served by workers that are
            # now leased/actor — counting only idle+starting here would
            # re-buy satisfied demand every tick.
            have = sum(1 for w in self.workers.values()
                       if w.job_id == job_id and w.env_hash == env_hash
                       and not w.chips and w.proc.returncode is None)
            want = min(budget, seen - have)
            for _ in range(max(0, want)):
                budget -= 1
                asyncio.ensure_future(
                    self._spawn_worker(job_id, envs[(job_id, env_hash,
                                                     tpu)]))

    async def start(self, port: int = 0) -> int:
        self.server.register("NodeManager", "WorkerReady", self.worker_ready)
        self.server.register("NodeManager", "LeaseWorker", self.lease_worker)
        self.server.register("NodeManager", "ReturnWorker", self.return_worker)
        self.server.register("NodeManager", "LeaseWorkerForActor",
                             self.lease_worker_for_actor)
        self.server.register("NodeManager", "PrepareBundle",
                             self.prepare_bundle)
        self.server.register("NodeManager", "CommitBundle",
                             self.commit_bundle)
        self.server.register("NodeManager", "CancelBundle",
                             self.cancel_bundle)
        self.server.register("NodeManager", "PullObject", self.pull_object)
        self.server.register("NodeManager", "PullObjectMeta",
                             self.pull_object_meta)
        self.server.register("NodeManager", "PullObjectChunk",
                             self.pull_object_chunk)
        self.server.register("NodeManager", "PushObject", self.push_object)
        self.server.register("NodeManager", "FreeObject", self.free_object)
        self.server.register("NodeManager", "FreeObjects", self.free_objects)
        self.server.register("NodeManager", "StoreStats", self.store_stats)
        self.server.register("NodeManager", "SpillObjects",
                             self.spill_objects)
        self.server.register("NodeManager", "ListWorkers", self.list_workers)
        self.server.register("NodeManager", "StackTraces", self.stack_traces)
        self.server.register("NodeManager", "CollectStacks",
                             self.collect_stacks)
        self.server.register("NodeManager", "NotifyPreemption",
                             self.notify_preemption)
        self.server.register("NodeManager", "WorkerExiting",
                             self.worker_exiting)
        self.server.register("NodeManager", "Metrics", self.get_metrics)
        self.server.register("NodeManager", "CollectEvents",
                             self.collect_events)
        self.server.register("NodeManager", "ShutdownNode", self.shutdown_node)
        port = await self.server.start(port)
        # Native bulk-data plane: serves this store's sealed objects over
        # raw TCP (objtransfer.cc); pullers learn the port from the
        # PullObjectMeta probe.
        try:
            from ray_tpu._private.object_transfer import TransferServer
            self.transfer_server = TransferServer(self.store_path)
        except Exception as e:
            logger.warning("native transfer plane unavailable: %s", e)
            self.transfer_server = None
        await self._register_with_gcs(timeout=10)
        if _cfg().worker_zygote:
            self._prestart_zygote()  # off-loop; cold imports never block
        self._tasks = [asyncio.ensure_future(self._heartbeat_loop()),
                       asyncio.ensure_future(self._reaper_loop()),
                       asyncio.ensure_future(self._node_watch_loop())]
        if self.spill_enabled:
            self.store.set_eviction(False)
            self._tasks.append(asyncio.ensure_future(self._spill_loop()))
        if _cfg().memory_monitor_enabled:
            self._tasks.append(
                asyncio.ensure_future(self._memory_monitor_loop()))
        self._tasks.append(asyncio.ensure_future(self._log_tail_loop()))
        self._start_telemetry()
        return port

    def _start_telemetry(self):
        """Pull endpoints (/metrics /events /healthz) for external
        scrapers.  Handlers run on the HTTP thread pool and hop onto the
        daemon loop for the node-level merges; rides the flight-recorder
        switch (RAY_TPU_EVENTS=0 -> no server)."""
        from ray_tpu.util import telemetry
        loop = asyncio.get_running_loop()

        def metrics_fn():
            from ray_tpu.util import metrics as mt
            reply = asyncio.run_coroutine_threadsafe(
                self.get_metrics({}), loop).result(timeout=10)
            return mt.prometheus_text(
                reply.get("metrics", {}),
                {"component": "hostd", "node_id": self.node_id.hex()[:12]})

        def events_fn(plane, kind, trace_id, since):
            reply = asyncio.run_coroutine_threadsafe(
                self.collect_events({"since": since}), loop).result(
                    timeout=10)
            return [e for e in reply.get("events", [])
                    if (plane is None or e.get("plane") == plane)
                    and (kind is None or e.get("kind") == kind)
                    and (trace_id is None or e.get("trace_id") == trace_id)]

        def healthz_fn():
            return {"node_id": self.node_id.hex(),
                    "workers": len(self.workers)}

        self.telemetry = telemetry.start_server(
            metrics_fn=metrics_fn, events_fn=events_fn,
            component="hostd", healthz_fn=healthz_fn)

    def install_signal_handlers(self):
        import signal
        loop = asyncio.get_event_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, self._shutdown.set)
            except (NotImplementedError, RuntimeError):
                pass

    async def run_until_shutdown(self):
        await self._shutdown.wait()
        # Black box + profile flush before the teardown starts killing
        # things: this daemon's ring records the node's last decisions.
        events.record("proc", "hostd_shutdown",
                      node=self.node_id.hex()[:8])
        events.dump_crash("hostd_shutdown")
        from ray_tpu._private.profiling import stop_periodic_profiles
        stop_periodic_profiles()
        if getattr(self, "telemetry", None) is not None:
            self.telemetry.stop()
            self.telemetry = None
        for t in self._tasks:
            t.cancel()
        # Teardown escalation: SIGTERM everyone, give the pool one shared
        # grace window to drain (workers' own SIGTERM handlers finish the
        # in-flight task), then SIGKILL any survivor — shutdown can never
        # wedge on a worker whose native code ignores SIGTERM.
        victims = list(self.workers.values())
        for handle in victims:
            self._kill_worker(handle)
        self._zygote_close()
        deadline = time.monotonic() + max(3.0, _cfg().worker_sigterm_grace_s)
        for handle in victims:
            try:
                handle.proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except Exception:
                handle.proc.kill()
        await self.server.stop()
        await self.pool.close_all()
        await self.gcs.close()
        if getattr(self, "transfer_server", None) is not None:
            # close() blocks in native code (join + drain, up to ~5s) —
            # keep it off the event loop.
            await asyncio.get_running_loop().run_in_executor(
                None, self.transfer_server.close)
        self.store.close()


def main():
    events.role = "hostd"
    parser = argparse.ArgumentParser()
    parser.add_argument("--gcs", required=True)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--ready-file", default="")
    parser.add_argument("--num-cpus", type=float, default=None)
    parser.add_argument("--num-tpus", type=float, default=None)
    parser.add_argument("--resources", default="")  # "k=v,k=v"
    parser.add_argument("--store-capacity", type=int, default=256 << 20)
    parser.add_argument("--head", action="store_true")
    parser.add_argument("--session-dir", default="/tmp/ray_tpu")
    args = parser.parse_args()
    logging.basicConfig(level=os.environ.get("RAY_TPU_LOGLEVEL", "INFO"), format="%(asctime)s.%(msecs)03d %(message)s", datefmt="%H:%M:%S")

    resources = detect_resources()
    if args.num_cpus is not None:
        resources["CPU"] = args.num_cpus
    if args.num_tpus is not None:
        if args.num_tpus > 0:
            resources["TPU"] = args.num_tpus
        else:
            resources.pop("TPU", None)
    for kv in filter(None, args.resources.split(",")):
        k, v = kv.split("=")
        resources[k] = float(v)

    from ray_tpu._private.profiling import start_periodic_profile
    start_periodic_profile("RAY_TPU_PROFILE_HOSTD", "hostd")

    async def run():
        daemon = NodeDaemon(args.gcs, resources, args.store_capacity,
                            is_head=args.head, host=args.host,
                            session_dir=args.session_dir)
        port = await daemon.start(args.port)
        daemon.install_signal_handlers()
        if args.ready_file:
            tmp = args.ready_file + ".tmp"
            with open(tmp, "w") as f:
                f.write(f"{port}\n{daemon.node_id.hex()}\n{daemon.store_path}")
            os.replace(tmp, args.ready_file)
        logger.info("hostd %s on port %d resources=%s",
                    daemon.node_id.hex()[:8], port, resources)
        await daemon.run_until_shutdown()

    asyncio.run(run())


if __name__ == "__main__":
    main()
