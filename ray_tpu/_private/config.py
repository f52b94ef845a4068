"""Central config registry: typed flags, env-overridable.

Reference parity: src/ray/common/ray_config_def.h (~700 RAY_CONFIG(type,
name, default) entries overridable via RAY_<name> env vars or the
_system_config dict at init, mirrored through includes/ray_config.pxi).
Here every knob is declared once, reads `RAY_TPU_<NAME>` from the
environment, and can be overridden per-process via
`ray_tpu.init(_system_config={...})`.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict


class _Flag:
    __slots__ = ("name", "default", "cast", "doc")

    def __init__(self, name: str, default, cast: Callable, doc: str):
        self.name = name
        self.default = default
        self.cast = cast
        self.doc = doc


def _bool(v) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).lower() not in ("0", "false", "no", "off", "")


class RayTpuConfig:
    """Singleton registry; access flags as attributes."""

    _FLAGS: Dict[str, _Flag] = {}

    @classmethod
    def _define(cls, name: str, default, cast, doc: str):
        cls._FLAGS[name] = _Flag(name, default, cast, doc)

    def __init__(self):
        self._overrides: Dict[str, Any] = {}
        self._cache: Dict[str, Any] = {}

    def apply_system_config(self, overrides: Dict[str, Any] | None) -> None:
        """ray_tpu.init(_system_config={...}) hook."""
        for k, v in (overrides or {}).items():
            if k not in self._FLAGS:
                raise ValueError(f"unknown config flag {k!r}; known: "
                                 f"{sorted(self._FLAGS)}")
            self._overrides[k] = self._FLAGS[k].cast(v)
        self._cache.clear()

    def invalidate_cache(self) -> None:
        """Call after mutating RAY_TPU_* env vars in-process (tests do)."""
        self._cache.clear()

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        # Resolved values are cached: flag reads sit on per-task hot paths
        # (lease pump, submit), and an os.environ hit per read is ~7us.
        cached = self._cache.get(name, self)
        if cached is not self:
            return cached
        flag = self._FLAGS.get(name)
        if flag is None:
            raise AttributeError(name)
        if name in self._overrides:
            value = self._overrides[name]
        else:
            env = os.environ.get(f"RAY_TPU_{name.upper()}")
            value = flag.cast(env) if env is not None else flag.default
        self._cache[name] = value
        return value

    def dump(self) -> Dict[str, Any]:
        return {name: getattr(self, name) for name in sorted(self._FLAGS)}


_D = RayTpuConfig._define
# -- core runtime ----------------------------------------------------------
_D("object_store_memory", 256 << 20, int,
   "default per-node shared-memory store capacity (bytes)")
_D("inline_object_limit", 100 * 1024, int,
   "returns/args below this size travel inline instead of via the store")
_D("lease_idle_ttl_s", 1.0, float,
   "held worker leases idle past this return to the daemon")
_D("max_pending_lease_requests", 16, int,
   "in-flight LeaseWorker RPCs per scheduling key")
_D("lease_pipeline_depth", 8, int,
   "tasks in flight per held worker lease (receiver queues them; "
   "reference: OnWorkerIdle pushes all queued tasks onto a lease)")
_D("task_max_retries", 3, int, "default task retry budget")
_D("worker_idle_ttl_s", 60.0, float,
   "idle pooled workers are reaped after this")
_D("max_workers_per_node", 0, int,
   "worker-pool cap per node; 0 = max(8, 4x CPUs)")
_D("max_startup_concurrency", 0, int,
   "concurrent worker spawns per node; 0 = max(4, host core count)")
_D("worker_zygote", True, _bool,
   "fork non-TPU workers from a pre-imported template process "
   "(worker_zygote.py) instead of cold-spawning an interpreter")
_D("native_task_transport", True, _bool,
   "push tasks over the native framed-TCP plane (taskrpc.cc) instead of "
   "the Python RPC layer")
_D("heartbeat_interval_s", 0.5, float, "hostd -> GCS heartbeat period")
_D("gcs_flush_interval_ms", 200.0, float,
   "GCS persistence debounce: a burst of table mutations becomes one "
   "sqlite executemany transaction at most this often")
_D("node_death_timeout_s", 5.0, float,
   "missed-heartbeat window before a node is declared dead")
# -- spilling --------------------------------------------------------------
_D("spill_enabled", True, _bool, "spill to disk instead of LRU eviction")
_D("spill_high_watermark", 0.8, float, "store fraction that starts a sweep")
_D("spill_low_watermark", 0.5, float, "sweep target store fraction")
# -- memory monitor --------------------------------------------------------
_D("memory_monitor_enabled", True, _bool,
   "kill workers when node memory nears exhaustion")
_D("memory_usage_threshold", 0.95, float,
   "node memory fraction that triggers OOM worker killing")
_D("memory_monitor_interval_s", 1.0, float, "memory check period")
# -- serve -----------------------------------------------------------------
_D("serve_controller_threads", 64, int,
   "controller thread pool (long-polls + control loop)")
_D("serve_backpressure_timeout_s", 60.0, float,
   "how long a handle waits for a replica under its "
   "max_concurrent_queries cap before raising TimeoutError")
_D("serve_drain_deadline_s", 30.0, float,
   "how long a DRAINING replica may finish its in-flight requests "
   "before the controller force-kills it")
_D("serve_queue_length", 128, int,
   "default per-deployment admission queue bound: callers waiting for a "
   "replica slot beyond this fast-fail with ServeOverloadedError "
   "(0 = unbounded, legacy backpressure-wait behavior)")
_D("serve_retry_after_hint_s", 1.0, float,
   "retry-after hint carried by ServeOverloadedError when a request "
   "is shed at the admission queue")
_D("serve_request_deadline_s", 0.0, float,
   "default end-to-end deadline for every serve request (admission + "
   "execution + retries); 0 = none.  Per-call override: "
   "handle.options(timeout_s=...)")
_D("serve_failover_attempts", 2, int,
   "max mid-stream failover resubmissions per streaming request")
_D("spec_k", 4, int,
   "default speculative draft length when an engine/deployment enables "
   "speculative decoding: up to this many draft tokens ride each verify "
   "step (the verify dispatch shape is spec_k+1)")
_D("spec_adaptive", True, _bool,
   "adapt each lane's draft length to its measured acceptance: grow on "
   "full acceptance, back off on rejection, so incompressible streams "
   "stop paying rejected verify FLOPs")
# -- disaggregated serving / KV tier ---------------------------------------
_D("serve_prefix_routing", False, _bool,
   "prefix-cache-aware replica routing: the handle scrapes a compact "
   "prefix-index summary from each LLM replica and routes a request to "
   "the replica holding its longest cached prefix chain, falling back "
   "to power-of-two-choices on ties or stale summaries.  Off by "
   "default: non-LLM deployments have no summary to scrape")
_D("serve_prefix_scrape_s", 1.0, float,
   "period of the router's prefix-summary scrape thread")
_D("serve_prefix_staleness_s", 5.0, float,
   "summaries older than this never attract traffic (dead or "
   "redeployed replicas age out of prefix scoring within one bound)")
_D("serve_prefix_summary_size", 256, int,
   "max chain hashes a replica exports per prefix summary (newest "
   "sealed blocks win — bounds scrape payload size)")
_D("kv_tier", False, _bool,
   "tiered KV cache: refcount-0 sealed blocks spill to host memory "
   "and then the object store / disk instead of being destroyed; the "
   "prefix index keeps a SPILLED state and match/adopt restores "
   "spilled chains on hit")
_D("kv_tier_host_blocks", 256, int,
   "host-memory tier capacity in KV blocks (LRU beyond this "
   "overflows to the store tier)")
_D("kv_tier_store_blocks", 1024, int,
   "object-store/disk tier capacity in KV blocks (LRU beyond this "
   "is dropped for real); 0 disables the second tier")
# -- train fault tolerance -------------------------------------------------
_D("train_hang_timeout_s", 600.0, float,
   "gang declared hung when NO worker makes observable progress (a "
   "consumed report or an advanced step beacon) for this long; the "
   "watchdog then collects per-rank stacks and fails the gang instead "
   "of waiting in a collective forever.  Must exceed the slowest "
   "legitimate train step, and the first step includes its compile: "
   "gpt2-small's takes 45 s cold on a v5e chip after ~40 s of start-up, "
   "which the earlier 60 s default would have called a hang.")
_D("train_beacon_poll_s", 5.0, float,
   "how often the driver-side watchdog polls worker step beacons while "
   "blocked waiting on gang reports")
_D("train_elastic_timeout_s", 120.0, float,
   "overall deadline for an elastic restart to form SOME gang between "
   "min_workers and num_workers before the restart fails")
_D("train_pg_timeout_s", 15.0, float,
   "placement-group reservation wait per elastic gang-size attempt "
   "(the non-elastic path keeps its legacy 120s wait)")
_D("train_resize_check_interval_s", 5.0, float,
   "how often a resized-down gang probes the cluster for returned "
   "capacity (resize-up happens at the next step boundary after a "
   "successful probe)")
_D("worker_sigterm_grace_s", 3.0, float,
   "bounded SIGTERM -> wait -> SIGKILL escalation window: how long a "
   "terminated worker may finish its in-flight task before the kill "
   "(hostd child teardown and the worker's own SIGTERM handler)")
# -- ingest / device feed --------------------------------------------------
_D("ingest_queue_depth", 2, int,
   "bounded handoff queue between the background batch producer and the "
   "training thread (batches buffered ahead of the consumer)")
_D("ingest_prefetch_blocks", 4, int,
   "block refs the ingest path touches ahead of the blocking fetch")
_D("ingest_device_buffers", 2, int,
   "device batches kept in flight by iter_device_batches: while the "
   "jitted step consumes batch k, batch k+1 is already being device_put")
_D("ingest_work_stealing", False, _bool,
   "trainer dataset shards lease blocks from a SplitCoordinator instead "
   "of static per-worker lists — a straggler no longer strands its "
   "shard.  Off by default: the static split is deterministic "
   "(token-exact elastic restores)")
_D("ingest_lease_timeout_s", 30.0, float,
   "a work-stealing split re-queues a worker's outstanding block leases "
   "once the worker has been silent this long AND the fresh pool is "
   "exhausted (crash recovery; mark_dead re-queues immediately)")
# -- observability / flight recorder ---------------------------------------
_D("events", True, _bool,
   "flight recorder master switch: every plane appends structured "
   "decision events to a per-process ring buffer (util/events.py), "
   "dumped on crash and scrapeable via CollectEvents.  RAY_TPU_EVENTS=0 "
   "reduces record() to a single global read")
_D("events_ring_size", 4096, int,
   "flight-recorder ring capacity (events per process); overflow "
   "overwrites oldest")
_D("flightrec_dir", "", str,
   "directory for crash dumps (flightrec-<pid>-<incarnation>.jsonl); "
   "hostd points workers at <session>/logs via RAY_TPU_FLIGHTREC_DIR, "
   "empty = /tmp/ray_tpu/flightrec")
_D("telemetry_port", 0, int,
   "base port for the pull telemetry HTTP endpoints (/metrics /events "
   "/healthz) served by hostd and the driver; 0 = ephemeral (the bound "
   "port is announced as a proc/telemetry_listen event).  The server "
   "only starts when the flight recorder is enabled; -1 disables it "
   "outright")
_D("telemetry_host", "127.0.0.1", str,
   "bind address for the telemetry HTTP endpoints; set 0.0.0.0 to "
   "expose scrapes off-host")
# -- scheduling ------------------------------------------------------------
_D("scheduler_spread_threshold", 0.5, float,
   "hybrid policy: pack until this utilization, then best-node")
_D("sched_batch_max", 8, int,
   "worker grants requested per LeaseWorker RPC: a deep same-key queue "
   "asks the hostd for up to this many workers in ONE round trip "
   "instead of one RPC per lease (the hostd grants what it can and the "
   "driver re-pumps for the rest); 1 = legacy single-grant leasing")
_D("sched_batch_wait_ms", 0.0, float,
   "optional submit-side coalescing window: the fast-path drain waits "
   "up to this long for more same-burst submissions before flushing "
   "its per-worker dispatch batches (0 = flush at the end of the "
   "current loop tick, the latency-neutral default)")
_D("zygote_spawn_parallelism", 8, int,
   "forks per zygote wakeup: concurrent spawn requests coalesce into "
   "one batched fork request of up to this many children (and the "
   "hostd pre-warm pool seeds at most this many workers per tick)")
_D("worker_prewarm", True, _bool,
   "hostd pre-warms idle workers sized by recent lease demand while "
   "the zygote is serving, so storms stop paying cold-spawn per lease")
# -- rpc retry -------------------------------------------------------------
_D("rpc_max_retries", 4, int,
   "transient-failure (UNAVAILABLE/disconnect) retries per RpcClient.call; "
   "0 disables retrying")
_D("rpc_retry_base_ms", 50.0, float,
   "first retry backoff; doubles per attempt with +/-50% jitter")
_D("rpc_retry_max_ms", 2000.0, float, "backoff ceiling per retry sleep")
# -- GCS fault tolerance ---------------------------------------------------
_D("gcs_supervise", False, _bool,
   "the launcher supervises the GCS child: on an unexpected death it "
   "respawns `python -m ray_tpu._private.gcs` at the SAME address from "
   "the same sqlite persistence path, so clients reconnect without "
   "re-resolving anything.  Implies persistence (a gcs.sqlite under the "
   "session dir) when RAY_TPU_GCS_PERSIST is unset")
_D("gcs_supervisor_restarts", 10, int,
   "supervised-GCS respawn budget per cluster lifetime; past it the "
   "supervisor gives up and the cluster degrades to today's "
   "head-is-gone behavior")
_D("gcs_outage_deadline_s", 30.0, float,
   "GcsClient ride-through window: control-plane calls buffer-and-retry "
   "transport failures against the (restarting) GCS for up to this long "
   "before surfacing the error.  The data plane is peer-to-peer and "
   "never waits on this")
_D("gcs_silent_window_s", 90.0, float,
   "hostd suicide window: heartbeat loop exits the daemon after the GCS "
   "has been unreachable this long — UNLESS gcs_supervise is on, in "
   "which case the hostd rides the outage out and re-registers on "
   "reconnect instead of orphaning its workers")
# -- fault injection (chaos) ----------------------------------------------
# Deterministic seeded chaos: see _private/fault_injection.py.  All
# probabilities are per-event in [0,1]; flags propagate to daemons and
# workers through the RAY_TPU_* env export in api.init.
_D("chaos_enabled", False, _bool,
   "master switch for the fault-injection layer")
_D("chaos_seed", 0, int,
   "seed for the deterministic fault schedule (same seed => same faults)")
_D("chaos_max_faults", 0, int,
   "total faults to inject before going quiet; 0 = unlimited")
_D("chaos_rpc_drop", 0.0, float,
   "probability an outbound RPC attempt fails with ChaosInjectedError")
_D("chaos_rpc_delay_p", 0.0, float,
   "probability an outbound RPC attempt is delayed")
_D("chaos_rpc_delay_ms", 100.0, float, "injected RPC delay duration")
_D("chaos_rpc_disconnect", 0.0, float,
   "probability an outbound RPC attempt tears down its channel first")
_D("chaos_native_drop", 0.0, float,
   "probability a native-transport task push is dropped")
_D("chaos_object_fetch_drop", 0.0, float,
   "probability an object-transfer fetch reports the copy missing")
_D("chaos_kill_worker", 0.0, float,
   "probability a worker kills itself before executing a task")
_D("chaos_kill_worker_salts", "", str,
   "scripted kills: csv of worker spawn ordinals that self-kill (see "
   "fault_injection.ChaosController.kill_worker)")
_D("chaos_kill_worker_at", 0, int,
   "task-execution index at which a scripted worker kill fires")
_D("chaos_kill_hostd", 0.0, float,
   "probability hostd kills itself at a heartbeat tick")
_D("chaos_kill_hostd_salts", "", str,
   "scripted hostd kills: csv of hostd spawn ordinals ('h1', 'h2', ... "
   "as stamped by node.start_hostd; or '*' for any non-head hostd) that "
   "die at their chaos_kill_hostd_at-th heartbeat tick (see "
   "fault_injection.ChaosController.kill_hostd)")
_D("chaos_kill_hostd_at", 0, int,
   "heartbeat tick ordinal at which the scripted hostd kill fires")
_D("chaos_ckpt_kill", 0.0, float,
   "probability the checkpoint writer kills its process right before the "
   "COMMIT rename (data fully written, directory left torn)")
_D("chaos_ckpt_kill_salts", "", str,
   "scripted mid-save kills: csv of worker spawn ordinals whose "
   "checkpoint writer dies (see fault_injection.kill_ckpt_commit)")
_D("chaos_ckpt_kill_at", 0, int,
   "save ordinal at which the scripted mid-save kill fires")
_D("chaos_kill_replica", 0.0, float,
   "probability a serve replica kills its process at a serve-plane "
   "event (request dispatch or stream-chunk pull)")
_D("chaos_kill_replica_salts", "", str,
   "scripted replica kills: csv of worker spawn ordinals (or '*' for "
   "any serve replica process) that die at their chaos_kill_replica_at-"
   "th serve-plane event (see fault_injection.kill_replica)")
_D("chaos_kill_replica_at", 0, int,
   "serve-plane event index at which the scripted replica kill fires")
_D("chaos_preempt", 0.0, float,
   "probability a hostd receives a preemption notice at a heartbeat "
   "tick (simulated TPU maintenance event: SIGTERM after a grace "
   "window)")
_D("chaos_preempt_at", -1, int,
   "scripted preemption: heartbeat tick ordinal at which the notice "
   "fires on every hostd matching chaos_preempt_target (-1 = disabled)")
_D("chaos_preempt_target", "any", str,
   "which hostds a scripted preemption hits: 'any', 'head', or "
   "'nonhead'.  A preempted head degrades to killing only its workers "
   "(slice loss) instead of exiting, so a colocated GCS survives.")
_D("chaos_preempt_grace_s", 5.0, float,
   "grace window between the injected preemption notice and the kill")
_D("chaos_stall_worker", 0.0, float,
   "probability a train worker stalls at a step boundary (hang chaos "
   "for the train watchdog)")
_D("chaos_stall_worker_salts", "", str,
   "scripted stalls: csv of worker spawn ordinals that stall at their "
   "chaos_stall_at-th session.report (see "
   "fault_injection.stall_train_step)")
_D("chaos_stall_at", 0, int,
   "report ordinal at which the scripted train stall fires")
_D("chaos_stall_s", 3600.0, float,
   "how long an injected train stall sleeps (interruptible; default "
   "is effectively forever relative to train_hang_timeout_s)")
_D("chaos_kill_gcs_at", -1, int,
   "scripted GCS kill: the GCS process os._exit(1)s right before "
   "serving its N-th control-plane request (-1 = disabled).  Which "
   "request lands on ordinal N is scenario-determined: a heartbeat, a "
   "PG schedule, a KV put — the supervised restart must absorb any of "
   "them (see fault_injection.ChaosController.kill_gcs)")
_D("chaos_kill_gcs_salts", "gcs0", str,
   "which GCS incarnations a scripted kill arms on: csv of process "
   "salts ('gcs0' is the first boot, 'gcs1' the first supervised "
   "respawn, ...; '*' = every incarnation).  The default arms only the "
   "first boot so a supervised respawn converges instead of dying at "
   "the same ordinal forever")
_D("chaos_kill_gcs_flush_at", -1, int,
   "scripted mid-flush GCS kill: os._exit(1) INSIDE the sqlite "
   "write_rows transaction of the N-th persistence flush, after the "
   "executemany but before commit (-1 = disabled).  Proves the "
   "coalesced-write path is crash-atomic: the torn flush must roll "
   "back wholesale on restore")
_D("chaos_partition_links", "", str,
   "scripted sustained network partitions: ';'-separated rules "
   "'src>dst@start+duration', e.g. 'h2>gcs@40+6.0;driver>gcs@0+2'. "
   "src names a process salt ('h2', 'gcs0', 'driver' for the saltless "
   "driver, '*' for any); dst is 'gcs', a literal host:port, or '*'. "
   "The rule blackholes every matching outbound rpc/native send "
   "starting at the src process's start-th call on that link, for "
   "duration seconds, then heals.  Directional — partition asymmetry "
   "is expressed by listing one direction only (see "
   "fault_injection.ChaosController.link_fault)")


GLOBAL_CONFIG = RayTpuConfig()


def get_config() -> RayTpuConfig:
    return GLOBAL_CONFIG
