"""Worker-side training session.

Reference parity: python/ray/train/_internal/session.py — _TrainSession:63
(user fn in a thread, result_queue(1)/error_queue :119-125, report:322,
checkpoint:284) and python/ray/air/session.py (the public accessors).

The user's train loop runs in a thread on the worker actor; `report()`
blocks the loop on a depth-1 queue until the driver consumes the result —
natural backpressure, exactly the reference's design.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.exceptions import TrainPreemptedError
from ray_tpu.util import spans

_session: Optional["_TrainSession"] = None

_STEP_MET = None


def _step_metrics():
    global _STEP_MET
    if _STEP_MET is None:
        from ray_tpu.util import metrics as mt
        _STEP_MET = {
            "step_time": mt.Histogram(
                "train_step_time_s",
                "wall seconds between report() step boundaries",
                tag_keys=("rank",),
                buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                         0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                         120.0, 300.0)),
        }
    return _STEP_MET


@dataclass
class TrainContext:
    world_rank: int
    world_size: int
    local_rank: int
    local_world_size: int
    node_rank: int
    experiment_name: str = ""
    trial_name: str = ""
    # Sharded-checkpoint plumbing: where this run's CheckpointManager
    # lives (empty = no persistent storage configured) and which elastic
    # incarnation this worker belongs to (bumped per restart; save_id
    # fodder so a new gang never aliases a dead gang's torn save).
    checkpoint_root: str = ""
    restart_count: int = 0


class _TrainSession:
    def __init__(self, train_fn: Callable[[], Any], context: TrainContext,
                 checkpoint: Optional[Checkpoint] = None,
                 dataset_shards: Optional[dict] = None):
        self.context = context
        self.loaded_checkpoint = checkpoint
        self.dataset_shards = dataset_shards or {}
        self.result_queue: queue.Queue = queue.Queue(maxsize=1)
        self.continue_event = threading.Event()
        self.error: Optional[BaseException] = None
        self.finished = False
        self._stop = False
        # Progress beacon: step counter + wall time of the last completed
        # step boundary, polled by the driver watchdog through the actor's
        # concurrent beacon() method while get_next blocks.
        self._beacon_step = 0
        self._beacon_t = time.monotonic()
        # Preemption notice state: armed by the hostd fan-out (via the
        # CoreWorker PreemptionNotice RPC); consumed at the next report()
        # step boundary — run the grace-window save hook, then abort with
        # TrainPreemptedError so at most the in-flight step is lost.
        self._preempt_pending = False
        self._preempt_deadline: Optional[float] = None
        self._preempt_grace = 0.0
        self._preempt_hook: Optional[Callable[[float], Any]] = None
        # Interruptible chaos stall (hang injection for the watchdog).
        self._stall_abort = threading.Event()
        # Open train/step span between report() boundaries (always on:
        # step cadence is orders of magnitude below the ring's budget).
        self._step_span = None
        # From the session's making to the user's loop, once a session: in
        # the start-up record.
        tok_start = spans.begin("train", "session_start", pin=True,
                                rank=context.world_rank)

        def run():
            global _session
            _session = self
            spans.end(tok_start)
            try:
                train_fn()
            except StopIteration:
                pass
            except BaseException as e:  # noqa: BLE001
                self.error = e
            finally:
                spans.end(self._step_span, final=True)
                self._step_span = None
                # Sentinel BEFORE the finished flag: a concurrent get_next
                # must never see finished+empty while an error is pending.
                try:
                    self.result_queue.put(("__done__", None), timeout=0)
                except queue.Full:
                    pass
                self.finished = True

        self.thread = threading.Thread(target=run, daemon=True)

    def start(self):
        self.thread.start()

    def report(self, metrics: dict, checkpoint: Optional[Checkpoint] = None):
        if self._stop:
            raise StopIteration  # unblocks and ends the user loop
        # Chaos stall BEFORE the beacon update: the stalled rank's beacon
        # stays at the previous step, so the driver watchdog classifies
        # it as the laggard.  Interruptible via stop().
        from ray_tpu._private.fault_injection import get_chaos
        chaos = get_chaos()
        if chaos is not None:
            stall = chaos.stall_train_step()
            if stall:
                from ray_tpu.util import events
                events.record("train", "chaos_stall", stall_s=stall,
                              rank=self.context.world_rank)
                self._stall_abort.wait(stall)
                if self._stop:
                    raise StopIteration
        prev_t = self._beacon_t
        self._beacon_step += 1
        self._beacon_t = time.monotonic()
        from ray_tpu.util import events
        events.record("train", "beacon", step=self._beacon_step,
                      rank=self.context.world_rank)
        # Durational step span: one per inter-report gap (the span for
        # step N opens at report N-1 and closes here).
        spans.end(self._step_span)
        self._step_span = spans.begin(
            "train", "step", step=self._beacon_step + 1,
            rank=self.context.world_rank)
        if self._beacon_step > 1:
            # Wall time between step boundaries — the worker-side
            # train_step_time_s SLO histogram (first report excluded: it
            # measures setup, not a step).
            _step_metrics()["step_time"].observe(
                self._beacon_t - prev_t,
                tags={"rank": str(self.context.world_rank)})
        if self._preempt_pending:
            # Step boundary after a preemption notice: run the proactive
            # save hook with whatever is left of the grace window, then
            # abort — resuming from this save loses at most the step that
            # was in flight when the notice landed.
            self._preempt_pending = False
            remaining = self._preempt_grace
            if self._preempt_deadline is not None:
                remaining = max(0.0,
                                self._preempt_deadline - time.monotonic())
            if self._preempt_hook is not None:
                try:
                    self._preempt_hook(remaining)
                except Exception:
                    pass  # a failed rescue save must not mask the abort
            events.record("train", "preempt_abort",
                          rank=self.context.world_rank,
                          step=self._beacon_step,
                          grace_remaining_s=round(remaining, 3))
            raise TrainPreemptedError(self._preempt_grace,
                                      self.context.world_rank)
        self.result_queue.put((metrics, checkpoint))  # blocks when full
        self.continue_event.wait()
        self.continue_event.clear()
        if self._stop:
            raise StopIteration

    def notify_preemption(self, grace_s: float) -> None:
        """Arm the step-boundary abort (called from the CoreWorker
        PreemptionNotice RPC thread)."""
        from ray_tpu.util import events
        events.record("train", "preempt_notice", grace_s=float(grace_s),
                      rank=self.context.world_rank)
        self._preempt_grace = float(grace_s)
        self._preempt_deadline = time.monotonic() + float(grace_s)
        self._preempt_pending = True

    def beacon(self) -> dict:
        """Progress snapshot for the driver watchdog (served through a
        concurrent actor method while get_next blocks)."""
        return {"step": self._beacon_step,
                "age_s": time.monotonic() - self._beacon_t,
                "finished": self.finished}

    def get_next(self, timeout: float | None = None):
        """Driver side (via actor RPC): next report, or None when done.
        Blocks indefinitely by default — worker DEATH surfaces as an RPC
        failure to the caller, not as a queue timeout, so a long-running
        train step must not be mistaken for a failure."""
        if self.finished and self.result_queue.empty():
            if self.error is not None:
                raise self.error
            return None
        item = self.result_queue.get(timeout=timeout)
        if item == ("__done__", None):
            if self.error is not None:
                raise self.error
            return None
        self.continue_event.set()
        return item

    def finish(self, timeout: float = 60.0):
        self.thread.join(timeout)
        # Drain this worker's async checkpoint writer: training is not
        # "finished" while its last save could still be torn.
        mgr = getattr(self, "_ckpt_manager", None)
        if mgr is not None:
            mgr.wait_until_finished()
        if self.error is not None:
            raise self.error

    def stop(self):
        self._stop = True
        self.continue_event.set()
        self._stall_abort.set()  # wake an injected stall so teardown works


def get_session() -> "_TrainSession":
    if _session is None:
        raise RuntimeError(
            "No training session active — this API must be called inside a "
            "train_loop_per_worker launched by a Trainer")
    return _session


# ---------------------------------------------------------------------------
# Public session API (reference: ray.air.session / ray.train.*)
# ---------------------------------------------------------------------------


def report(metrics: dict, checkpoint=None) -> None:
    """Stream one step's metrics (and optionally a checkpoint) to the
    driver.  `checkpoint` may be an air.Checkpoint OR an async
    ray_tpu.checkpoint.SaveHandle — a handle crosses to the driver as a
    lightweight (directory, step) ticket, so reporting never blocks on
    checkpoint serialization or I/O."""
    get_session().report(dict(metrics), checkpoint)


def get_checkpoint_manager():
    """This worker's CheckpointManager over the run's storage root
    (requires RunConfig.storage_path on the trainer).  Its save_id is
    derived from the elastic restart count, so saves from a restarted
    gang never alias a dead gang's torn directories."""
    sess = get_session()
    mgr = getattr(sess, "_ckpt_manager", None)
    if mgr is None:
        root = sess.context.checkpoint_root
        if not root:
            raise RuntimeError(
                "no checkpoint storage configured — pass "
                "RunConfig(storage_path=...) to the trainer to use "
                "sharded checkpointing")
        from ray_tpu.checkpoint import CheckpointManager
        mgr = CheckpointManager(
            root, save_id=f"i{sess.context.restart_count}")
        sess._ckpt_manager = mgr
    return mgr


def get_dataset_shard(name: str = "train"):
    """This worker's streaming shard of a trainer dataset (reference:
    air/session.py get_dataset_shard backed by streaming_split)."""
    shard = get_session().dataset_shards.get(name)
    if shard is None:
        raise KeyError(
            f"no dataset shard {name!r}; pass datasets={{{name!r}: ds}} to "
            f"the trainer")
    return shard


def iter_device_batches(name: str = "train", *, sharding=None, **kwargs):
    """Overlapped device feed over this worker's dataset shard —
    shorthand for ``get_dataset_shard(name).iter_device_batches(...)``.
    Yields batches already on the accelerator (double-buffered H2D: batch
    k+1 transfers while the step consumes batch k); pass ``sharding=``
    a NamedSharding, a Mesh, or a dict column -> Sharding to land each
    batch pre-sharded for the jitted step."""
    return get_dataset_shard(name).iter_device_batches(
        sharding=sharding, **kwargs)


def set_preemption_hook(fn: Callable[[float], Any]) -> None:
    """Register the grace-window rescue: on a preemption notice, `fn`
    runs at the next step boundary with the REMAINING grace seconds and
    should save a checkpoint (typically
    ``get_checkpoint_manager().save(state, step).wait()``).  report()
    then aborts the loop with TrainPreemptedError, so an elastic restart
    resumes from this save having lost at most the in-flight step."""
    get_session()._preempt_hook = fn


def preemption_deadline() -> Optional[float]:
    """Seconds until this host is reclaimed, or None if no preemption
    notice is pending — lets a train loop skip non-essential work (eval,
    logging) when the clock is running."""
    sess = get_session()
    if sess._preempt_deadline is None:
        return None
    return max(0.0, sess._preempt_deadline - time.monotonic())


def get_checkpoint() -> Optional[Checkpoint]:
    return get_session().loaded_checkpoint


def get_context() -> TrainContext:
    return get_session().context


def get_world_rank() -> int:
    return get_session().context.world_rank


def get_world_size() -> int:
    return get_session().context.world_size


def get_local_rank() -> int:
    return get_session().context.local_rank


def get_local_world_size() -> int:
    return get_session().context.local_world_size


def get_node_rank() -> int:
    return get_session().context.node_rank
