"""BackendExecutor: owns the worker gang and drives the training lifecycle.

Reference parity: python/ray/train/_internal/backend_executor.py —
BackendExecutor:43 (start:94 creates PG + WorkerGroup, start_training:325,
get_with_failure_handling:522, _restart:583 elastic restart).

On top of the reference lifecycle this executor carries the train-plane
fault-tolerance layer:

- **Hang watchdog** — while blocked waiting for gang reports it polls
  per-worker progress beacons (served on a concurrent actor thread); no
  observable progress for ``train_hang_timeout_s`` converts the infinite
  collective wait into `TrainHungError` carrying the laggard ranks, their
  beacon ages, and live per-rank thread stacks collected through the
  hostd CollectStacks RPC.
- **Elastic gang formation** — with ``ScalingConfig.min_workers`` set,
  `restart()` re-forms on the surviving hosts (fewer workers, data
  re-sharded by the new world size) instead of waiting for a lost host's
  replacement, and `resize_up()` re-admits returned capacity at a step
  boundary.  Each (re)formation bumps a generation counter that feeds
  `TrainContext.restart_count`, so checkpoint save_ids never alias
  across gang incarnations.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import ScalingConfig
from ray_tpu.exceptions import TrainHungError
from ray_tpu.train.backend import Backend, BackendConfig
from ray_tpu.train.session import TrainContext
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util import spans

logger = logging.getLogger("ray_tpu.train")


def _cfg():
    from ray_tpu._private.config import GLOBAL_CONFIG
    return GLOBAL_CONFIG


_M = None


def _metrics():
    """Train-plane recovery metrics (exported via util.metrics like every
    other plane; `cli metrics` scrapes them from the driver)."""
    global _M
    if _M is None:
        from ray_tpu.util import metrics as mt
        _M = {
            "train_recoveries": mt.Counter(
                "train_recoveries",
                "gang restarts/resizes, tagged by reason"),
            "train_recovery_seconds": mt.Histogram(
                "train_recovery_seconds",
                "wall seconds from failure to a re-formed gang"),
            "train_hangs": mt.Counter(
                "train_hangs", "gangs declared hung by the watchdog"),
        }
    return _M


class TrainingFailedError(RuntimeError):
    pass


class BackendExecutor:
    def __init__(self, backend_config: BackendConfig,
                 scaling_config: ScalingConfig,
                 max_failures: int = 0):
        self._backend_config = backend_config
        self._backend: Backend = backend_config.backend_cls()()
        self._scaling = scaling_config
        self._max_failures = max_failures
        self._num_failures = 0
        # Gang incarnation: bumped on EVERY re-formation (failure restart
        # or resize-up) — feeds TrainContext.restart_count so a new
        # gang's checkpoint save_id never aliases a dead gang's torn
        # markers.  _num_failures stays the can_restart budget only.
        self._generation = 0
        self._last_resize_check = 0.0
        # First monotonic time the resize-up capacity probe saw room to
        # grow (None = not seen).  A single sighting is not trusted: the
        # GCS resource view lags hostd state by a heartbeat, so right
        # after a gang forms its own freshly-reserved bundles can still
        # read as free capacity — acting on that tears the gang down in
        # a resize loop.  Growth requires the surplus to persist across
        # two probes spaced at least two heartbeats apart.
        self._resize_ready_since: Optional[float] = None
        self.worker_group: Optional[WorkerGroup] = None
        # Optional ray_tpu.checkpoint.CheckpointManager over the run's
        # storage root: workers learn its root through TrainContext, and
        # elastic restart resumes from its latest COMMITTED step.
        self.checkpoint_manager = None

    def set_checkpoint_manager(self, manager) -> None:
        self.checkpoint_manager = manager

    # ---------------- gang formation ----------------

    def start(self):
        # (Both once a gang: in the start-up record, under `train/fit_start`
        # where `fit()` has it open.)
        with spans.span("train", "form_gang", pin=True):
            self.worker_group = self._form_gang()
        with spans.span("train", "backend_start", pin=True):
            self._backend.on_start(self.worker_group, self._backend_config)
        # Fresh gang: restart the capacity-probe debounce so a stale
        # pre-formation resource view can't immediately trigger a resize.
        self._last_resize_check = time.monotonic()
        self._resize_ready_since = None

    def _form_gang(self) -> WorkerGroup:
        """Reserve and boot a gang.  Without min_workers this is the
        legacy exact-size path.  With it, try the full size first, then
        walk down to min_workers (resize-down onto survivors), retrying
        under train_elastic_timeout_s — a lost host shrinks the gang
        instead of stalling the restart until a replacement appears."""
        s = self._scaling
        min_w = getattr(s, "min_workers", None)
        if min_w is None:
            return WorkerGroup(s.num_workers, s.worker_resources(),
                               s.placement_strategy)
        min_w = max(1, min(int(min_w), s.num_workers))
        deadline = time.monotonic() + _cfg().train_elastic_timeout_s
        attempt_s = _cfg().train_pg_timeout_s
        last_err: Optional[BaseException] = None
        while True:
            for n in range(s.num_workers, min_w - 1, -1):
                budget = deadline - time.monotonic()
                if budget <= 0:
                    break
                try:
                    wg = WorkerGroup(
                        n, s.worker_resources(), s.placement_strategy,
                        pg_timeout_s=min(attempt_s, max(1.0, budget)))
                    if n < s.num_workers:
                        logger.warning(
                            "elastic start: formed %d/%d workers "
                            "(resize-down onto survivors)",
                            n, s.num_workers)
                    return wg
                except Exception as e:  # noqa: BLE001
                    last_err = e
            if time.monotonic() >= deadline:
                raise TrainingFailedError(
                    f"could not form an elastic gang of "
                    f"{min_w}..{s.num_workers} workers within "
                    f"{_cfg().train_elastic_timeout_s:g}s"
                ) from last_err

    def start_training(self, train_fn: Callable[[], None],
                       checkpoint: Optional[Checkpoint] = None,
                       datasets: Optional[dict] = None):
        """Launch the user loop on every worker of the CURRENT gang.
        Datasets are split here, by the actual gang size — an elastic
        restart that re-formed smaller re-shards by the new world size
        instead of leaving shards orphaned on dead ranks."""
        wg = self.worker_group
        self._backend.on_training_start(wg, self._backend_config)
        # ingest_work_stealing=True swaps the static per-worker lists for
        # SplitCoordinator leases (straggler-proof; re-split per (re)start
        # so gang resizes recreate the coordinator).  The static split
        # stays the default: it is deterministic, which token-exact
        # elastic restores rely on.
        steal = _cfg().ingest_work_stealing
        dataset_shards = {
            name: ds.streaming_split(len(wg), equal=True, steal=steal)
            for name, ds in (datasets or {}).items()}
        local = wg.local_ranks()
        node_ranks = wg.node_ranks()
        refs = []
        ckpt_root = (self.checkpoint_manager.root
                     if self.checkpoint_manager is not None else "")
        for rank, worker in enumerate(wg.workers):
            ctx = TrainContext(
                world_rank=rank,
                world_size=len(wg),
                local_rank=local[rank][0],
                local_world_size=local[rank][1],
                node_rank=node_ranks[rank],
                checkpoint_root=ckpt_root,
                restart_count=self._generation)
            per_worker = {name: shards[rank] for name, shards
                          in dataset_shards.items()}
            refs.append(worker.actor.init_session.remote(
                train_fn, ctx, checkpoint, per_worker))
        ray_tpu.get(refs, timeout=120)

    # How long some workers may keep reporting after others finished before
    # the SPMD-mismatch diagnostic fires (a finished worker never reports
    # again, so this only delays an error, never a success).
    MISMATCH_GRACE_S = 60.0

    # ---------------- report pump + hang watchdog ----------------

    def get_next_results(self) -> Optional[List]:
        """One report from EVERY worker, or None when all finished.
        A dead worker surfaces as an RPC error (the caller decides on
        restart); a worker that FINISHES while peers still report trips the
        SPMD-mismatch diagnostic instead of hanging forever in a collective.

        While blocked, the hang watchdog polls per-worker step beacons:
        progress is a ready report OR any beacon-step advance; a stall
        past train_hang_timeout_s raises TrainHungError naming the
        laggard ranks with their live thread stacks."""
        wg = self.worker_group
        refs = [w.actor.get_next.remote(None) for w in wg.workers]
        results: List = [None] * len(refs)
        pending = {ref: i for i, ref in enumerate(refs)}
        got: set = set()
        first_done_at = None
        hang_timeout = _cfg().train_hang_timeout_s
        poll_s = max(0.1, _cfg().train_beacon_poll_s)
        last_progress = time.monotonic()
        last_beacons: Dict[int, dict] = {}
        last_poll = 0.0
        while pending:
            ready, _ = ray_tpu.wait(list(pending), num_returns=1,
                                    timeout=min(5.0, poll_s))
            now = time.monotonic()
            if ready:
                last_progress = now
            for r in ready:
                i = pending.pop(r)
                results[i] = ray_tpu.get(r)
                got.add(i)
            if pending and not ready and now - last_poll >= poll_s:
                last_poll = now
                beacons = self._poll_beacons(sorted(pending.values()))
                for rank, b in beacons.items():
                    prev = last_beacons.get(rank)
                    if prev is not None and b["step"] > prev["step"]:
                        last_progress = now  # a rank moved: not hung
                    last_beacons[rank] = b
            if pending and now - last_progress > hang_timeout:
                self._raise_hung(sorted(pending.values()), last_beacons,
                                 hang_timeout)
            finished = [i for i in got if results[i] is None]
            if finished and first_done_at is None:
                first_done_at = time.monotonic()
            if finished and pending and first_done_at is not None \
                    and time.monotonic() - first_done_at \
                    > self.MISMATCH_GRACE_S:
                raise TrainingFailedError(
                    self._mismatch_message(sorted(pending.values()),
                                           last_beacons))
        if all(r is None for r in results):
            return None
        if any(r is None for r in results):
            laggards = [i for i, r in enumerate(results) if r is not None]
            raise TrainingFailedError(
                self._mismatch_message(laggards, last_beacons))
        return results

    def _poll_beacons(self, ranks: List[int]) -> Dict[int, dict]:
        """Best-effort beacon snapshot from the given ranks (concurrent
        actor method: answers even while get_next blocks)."""
        wg = self.worker_group
        refs = {wg.workers[r].actor.beacon.remote(): r for r in ranks}
        out: Dict[int, dict] = {}
        ready, _ = ray_tpu.wait(list(refs), num_returns=len(refs),
                                timeout=2.0)
        for ref in ready:
            try:
                b = ray_tpu.get(ref)
            except Exception:
                continue  # dead worker: its get_next ref carries the error
            if b is not None:
                out[refs[ref]] = b
        return out

    def _mismatch_message(self, laggard_ranks: List[int],
                          beacons: Dict[int, dict]) -> str:
        ages = ", ".join(
            f"rank {r}: "
            + (f"{beacons[r]['age_s']:.1f}s ago (step "
               f"{beacons[r]['step']})" if r in beacons else "unknown")
            for r in laggard_ranks)
        return (
            "some workers finished while others are still reporting — the "
            "train loop must be SPMD (same number of report() calls on "
            f"every worker); laggard rank(s) {laggard_ranks} "
            f"(last beacon: {ages})")

    def _raise_hung(self, pending_ranks: List[int],
                    beacons: Dict[int, dict], timeout_s: float):
        """Diagnose and raise: laggards are the pending ranks at the
        LOWEST beacon step (healthy ranks also look stale while blocked
        on the driver, but they sit at the gang's furthest step)."""
        fresh = self._poll_beacons(pending_ranks)
        beacons = dict(beacons)
        beacons.update(fresh)
        steps = {r: beacons[r]["step"] for r in pending_ranks
                 if r in beacons}
        if steps:
            lowest = min(steps.values())
            laggards = sorted(r for r, s in steps.items() if s == lowest)
        else:
            laggards = list(pending_ranks)  # no beacons at all
        ages = {r: beacons[r]["age_s"] for r in laggards if r in beacons}
        stacks = self._collect_stacks(laggards)
        _metrics()["train_hangs"].inc()
        from ray_tpu.util import events
        events.record("train", "hang", laggards=laggards,
                      timeout_s=timeout_s)
        raise TrainHungError(timeout_s, laggards, ages, stacks)

    def _collect_stacks(self, ranks: List[int]) -> str:
        """Live thread dumps for the given ranks via each node's hostd
        CollectStacks RPC (per-node fan-out; inside each node the hostd
        probes its workers concurrently)."""
        from ray_tpu import api
        cw = api._worker
        wg = self.worker_group
        if cw is None or wg is None:
            return ""
        by_node: Dict[str, List[int]] = {}
        pid_rank: Dict[int, int] = {}
        for r in ranks:
            w = wg.workers[r]
            if w.node_id and w.pid:
                by_node.setdefault(w.node_id, []).append(w.pid)
                pid_rank[w.pid] = r
        lines: List[str] = []
        try:
            table = cw.io.run(cw._node_table(), timeout=10)
        except Exception:
            return ""
        for nid, pids in by_node.items():
            addr = table.get(nid)
            if not addr:
                continue
            try:
                reply = cw.io.run(cw.pool.get(addr).call(
                    "NodeManager", "CollectStacks", {"pids": pids},
                    timeout=10), timeout=15)
            except Exception as e:
                lines.append(f"[node {nid[:8]}] stack collection failed: "
                             f"{e!r}")
                continue
            for proc in reply.get("processes", []):
                rank = pid_rank.get(proc.get("pid"), "?")
                lines.append(f"[rank {rank} pid {proc.get('pid')} "
                             f"node {nid[:8]}]")
                if proc.get("error"):
                    lines.append(f"  probe error: {proc['error']}")
                for t in proc.get("threads", []):
                    lines.append(f"  thread {t.get('name')}:")
                    for sl in str(t.get("stack", "")).splitlines():
                        lines.append(f"    {sl}")
        return "\n".join(lines)

    # ---------------- lifecycle ----------------

    def finish_training(self):
        wg = self.worker_group
        ray_tpu.get([w.actor.finish_session.remote() for w in wg.workers],
                    timeout=120)

    def can_restart(self) -> bool:
        return (self._max_failures == -1
                or self._num_failures < self._max_failures)

    def latest_committed_checkpoint(self) -> Optional[Checkpoint]:
        """The newest COMMITTED step under the checkpoint manager, as a
        Checkpoint — what an elastic restart resumes from.  An async
        save the dead gang never committed is invisible here by
        construction (no COMMIT marker), so a restart can never resume
        from a torn checkpoint."""
        mgr = self.checkpoint_manager
        if mgr is None:
            return None
        try:
            mgr.wait_until_finished()   # drain any driver-side writer
        except Exception as e:
            logger.warning("async checkpoint write failed: %s", e)
        step = mgr.latest_step()
        if step is None:
            return None
        return Checkpoint.from_sharded_dir(mgr.step_dir(step))

    def restart(self, reason: str = "failure"):
        """Elastic restart: tear the gang down, rebuild, re-rendezvous
        (reference: backend_executor.py:583).  On TPU a lost host means
        the slice re-forms as a whole — per-worker restart is not a
        thing.  With min_workers set the rebuild may come back SMALLER
        (resize-down onto survivors) instead of waiting for the lost
        host's replacement."""
        t0 = time.monotonic()
        self._num_failures += 1
        self._generation += 1
        logger.warning("restarting worker group (failure %d/%s, "
                       "reason=%s)", self._num_failures,
                       self._max_failures, reason)
        self.shutdown()
        self.start()
        dt = time.monotonic() - t0
        _metrics()["train_recoveries"].inc(tags={"reason": reason})
        _metrics()["train_recovery_seconds"].observe(
            dt, tags={"reason": reason})
        from ray_tpu.util import events
        events.record("train", "recovery", reason=reason,
                      workers=len(self.worker_group),
                      seconds=round(dt, 3))
        logger.warning("gang re-formed with %d worker(s) in %.2fs",
                       len(self.worker_group), dt)

    def should_resize_up(self) -> bool:
        """True when a resized-down gang can grow back: capacity for the
        missing workers is available again (a preempted host returned).
        Rate-limited by train_resize_check_interval_s so the probe never
        taxes the step loop."""
        s = self._scaling
        if getattr(s, "min_workers", None) is None \
                or self.worker_group is None:
            return False
        cur = len(self.worker_group)
        if cur >= s.num_workers:
            return False
        now = time.monotonic()
        if now - self._last_resize_check \
                < _cfg().train_resize_check_interval_s:
            return False
        self._last_resize_check = now
        need = s.num_workers - cur
        demand = s.worker_resources()
        try:
            avail = ray_tpu.available_resources()
        except Exception:
            return False
        if not all(avail.get(k, 0.0) + 1e-9 >= v * need
                   for k, v in demand.items() if v > 0):
            self._resize_ready_since = None
            return False
        # Debounce: trust the surplus only once it has outlived the GCS
        # heartbeat lag (two ticks), so our own just-placed bundles —
        # still reading as free in a stale view — never trigger growth.
        if self._resize_ready_since is None:
            self._resize_ready_since = now
            return False
        settle = max(1.0, 2 * _cfg().heartbeat_interval_s)
        return now - self._resize_ready_since >= settle

    def resize_up(self, reason: str = "resize_up"):
        """Re-admit returned capacity at a step boundary: cooperatively
        stop the running sessions, tear down, and re-form at (up to)
        full size.  The caller resumes from the latest COMMITTED
        checkpoint, exactly like a failure restart — but this path is
        voluntary, so nothing counts against the failure budget."""
        t0 = time.monotonic()
        self._generation += 1
        wg = self.worker_group
        if wg is not None:
            try:
                ray_tpu.get([w.actor.stop_session.remote()
                             for w in wg.workers], timeout=10)
            except Exception:
                pass  # dead/stuck workers die with the gang teardown
        logger.warning("resize-up: re-forming gang at full size (%d)",
                       self._scaling.num_workers)
        self.shutdown()
        self.start()
        dt = time.monotonic() - t0
        _metrics()["train_recoveries"].inc(tags={"reason": reason})
        _metrics()["train_recovery_seconds"].observe(
            dt, tags={"reason": reason})
        from ray_tpu.util import events
        events.record("train", "recovery", reason=reason,
                      workers=len(self.worker_group),
                      seconds=round(dt, 3))
        logger.warning("gang re-formed with %d worker(s) in %.2fs",
                       len(self.worker_group), dt)

    def shutdown(self):
        if self.worker_group is not None:
            try:
                self._backend.on_shutdown(self.worker_group,
                                          self._backend_config)
            except Exception:
                pass
            self.worker_group.shutdown()
            self.worker_group = None
