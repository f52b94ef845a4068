"""Trainers: BaseTrainer.fit() and the data-parallel (SPMD) trainer.

Reference parity: python/ray/train/base_trainer.py (BaseTrainer.fit:557,
Result) + data_parallel_trainer.py:56 (DataParallelTrainer,
training_loop:385).  The reference wraps fit() in a single-trial Tune run;
here fit() drives the BackendExecutor directly and the Tune integration
layers on top (tune.Tuner can wrap any Trainer via .as_trainable()).

`JaxTrainer` is the flagship entrypoint: DataParallelTrainer with the
TpuBackend — N workers, one per TPU host, fused into one jax.distributed
fabric; the user loop sees the global mesh.
"""

from __future__ import annotations

import dataclasses
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ray_tpu._private.accelerators import chips_per_host
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import RunConfig, ScalingConfig
from ray_tpu.train.backend import BackendConfig, TpuConfig
from ray_tpu.util import spans
from ray_tpu.train.backend_executor import (
    BackendExecutor, TrainingFailedError)


@dataclass
class Result:
    """Reference: air/result.py."""

    metrics: Optional[dict] = None
    checkpoint: Optional[Checkpoint] = None
    error: Optional[BaseException] = None
    metrics_dataframe: Optional[Any] = None
    metrics_history: List[dict] = field(default_factory=list)


class BaseTrainer:
    """Reference: train/base_trainer.py:557."""

    def __init__(self, *, scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None):
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()

    def training_loop(self) -> None:
        raise NotImplementedError

    def fit(self) -> Result:
        raise NotImplementedError

    def as_trainable(self):
        """Adapter so tune.Tuner can run this trainer as a trial."""
        trainer = self

        def trainable(config: dict):
            import copy
            t = copy.copy(trainer)
            if config:
                t = t.with_config_overrides(config)
            result = t.fit()
            if result.error is not None:
                raise result.error
            # Surface the run's final metrics (+ checkpoint) as this
            # trial's report, as the reference's trainable wrapper does.
            from ray_tpu.train import session
            session.report(result.metrics or {}, result.checkpoint)

        trainable.__name__ = type(self).__name__
        return trainable

    def with_config_overrides(self, config: dict):
        return self


class DataParallelTrainer(BaseTrainer):
    """Run `train_loop_per_worker` on every worker of the gang (SPMD).

    Reference: train/data_parallel_trainer.py:56.  Every worker must make
    the same number of session.report() calls (the same invariant the
    reference enforces; on TPU it is also the SPMD compile invariant).
    """

    _backend_config_cls = BackendConfig

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[dict] = None,
                 backend_config: Optional[BackendConfig] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None,
                 datasets: Optional[dict] = None):
        super().__init__(scaling_config=scaling_config,
                         run_config=run_config)
        self._train_loop = train_loop_per_worker
        self._train_loop_config = dict(train_loop_config or {})
        self._backend_config = backend_config or self._backend_config_cls()
        self._resume_from = resume_from_checkpoint
        self._datasets = dict(datasets or {})

    def with_config_overrides(self, config: dict):
        import copy
        t = copy.copy(self)
        merged = dict(self._train_loop_config)
        merged.update(config)
        t._train_loop_config = merged
        return t

    def fit(self) -> Result:
        executor = BackendExecutor(
            self._backend_config, self.scaling_config,
            max_failures=self.run_config.failure_config.max_failures)
        manager = self._manager = self._make_checkpoint_manager()
        executor.set_checkpoint_manager(manager)
        train_fn = self._bind_train_fn()
        history: List[dict] = []
        last_checkpoint = self._resolve_resume(manager)
        error: Optional[BaseException] = None

        # From here to the first result: one `train/fit_start` span in the
        # start-up record; the gang's formation, the backend's start and
        # what the workers do meanwhile hang off it.
        fit_tok = spans.begin("train", "fit_start", pin=True)
        with spans.under(fit_tok):
            executor.start()
        try:
            while True:
                # Datasets travel raw: the executor splits by the ACTUAL
                # gang size each (re)start, so an elastic resize
                # re-shards by the new world size (reference:
                # DataParallelTrainer datasets= + streaming_split).
                with spans.under(fit_tok):
                    executor.start_training(train_fn, last_checkpoint,
                                            self._datasets)
                resized = False
                try:
                    while True:
                        # Step-boundary resize-up: returned capacity is
                        # re-admitted between reports, resuming from the
                        # latest committed step — voluntary, so it never
                        # burns the failure budget.
                        if executor.should_resize_up():
                            executor.resize_up()
                            committed = \
                                executor.latest_committed_checkpoint()
                            if committed is not None:
                                last_checkpoint = committed
                            resized = True
                            break
                        results = executor.get_next_results()
                        fit_tok = spans.end(fit_tok)    # (once: None now)
                        if results is None:
                            break
                        metrics = results[0][0]  # rank-0 metrics canonical
                        ckpts = [c for _, c in results if c is not None]
                        if ckpts:
                            last_checkpoint = ckpts[0]
                            self._persist_checkpoint(last_checkpoint,
                                                     len(history), metrics)
                        history.append(metrics)
                    if resized:
                        continue
                    executor.finish_training()
                    break
                except Exception as e:  # worker failure path
                    if isinstance(e, KeyboardInterrupt):
                        raise
                    if executor.can_restart():
                        from ray_tpu.exceptions import (
                            TrainHungError, TrainPreemptedError)

                        def _reason(err):
                            seen = set()
                            while err is not None and id(err) not in seen:
                                seen.add(id(err))
                                if isinstance(err, TrainPreemptedError):
                                    return "preempted"
                                if isinstance(err, TrainHungError):
                                    return "hang"
                                err = getattr(err, "cause", None) \
                                    or err.__cause__
                            return "failure"
                        executor.restart(reason=_reason(e))
                        # Elastic resume point: the latest COMMITTED step
                        # — an async save the dead gang never finished has
                        # no COMMIT marker and is skipped by construction.
                        committed = executor.latest_committed_checkpoint()
                        if committed is not None:
                            last_checkpoint = committed
                        continue
                    # Surface the real worker exception, not the gang
                    # wrapper around it.
                    error = e.__cause__ \
                        if (isinstance(e, TrainingFailedError)
                            and e.__cause__ is not None) else e
                    break
        finally:
            spans.end(fit_tok, failed=True)     # (no result ever came)
            executor.shutdown()
            if manager is not None:
                try:
                    manager.wait_until_finished()
                except Exception as ckpt_err:
                    if error is None:
                        error = ckpt_err

        return Result(
            metrics=history[-1] if history else None,
            checkpoint=self._finalize_checkpoint(last_checkpoint, manager),
            error=error,
            metrics_history=history)

    def _bind_train_fn(self) -> Callable[[], None]:
        fn = self._train_loop
        cfg = dict(self._train_loop_config)
        import inspect
        takes_config = len(inspect.signature(fn).parameters) >= 1

        def bound():
            if takes_config:
                fn(cfg)
            else:
                fn()

        return bound

    def _make_checkpoint_manager(self):
        """CheckpointManager over storage_path/name (None when the run
        has no persistent storage).  CheckpointConfig maps to retention:
        num_to_keep bounds keep-best when a score attribute is set
        (reference semantics), keep-last otherwise."""
        root = self.run_config.storage_path
        if not root:
            return None
        from ray_tpu.checkpoint import CheckpointManager
        cc = self.run_config.checkpoint_config
        name = self.run_config.name or "train_run"
        if cc.checkpoint_score_attribute is not None:
            keep_last, keep_best = None, cc.num_to_keep
        else:
            keep_last, keep_best = cc.num_to_keep, None
        return CheckpointManager(
            os.path.join(root, name),
            keep_last_k=keep_last, keep_best_k=keep_best,
            best_metric=cc.checkpoint_score_attribute,
            best_mode=cc.checkpoint_score_order)

    def _resolve_resume(self, manager):
        """resume_from_checkpoint routed through the manager: "latest"
        (or "auto") resumes from the newest committed step in storage; a
        SaveHandle resolves to its directory once committed."""
        resume = self._resume_from
        from ray_tpu.checkpoint import SaveHandle
        if isinstance(resume, str):
            if resume not in ("latest", "auto"):
                raise ValueError(
                    f"resume_from_checkpoint string form must be "
                    f"'latest'/'auto', got {resume!r}")
            if manager is None:
                raise ValueError(
                    "resume_from_checkpoint='latest' requires "
                    "RunConfig(storage_path=...)")
            return manager.latest_checkpoint()
        if isinstance(resume, SaveHandle):
            return self._finalize_checkpoint(resume, manager)
        return resume

    def _persist_checkpoint(self, checkpoint, step: int,
                            metrics: Optional[dict] = None):
        """Route a reported checkpoint through the manager.  A
        SaveHandle means a worker already wrote sharded data under the
        manager root (its commit marker lands asynchronously) — only
        retention bookkeeping remains.  A dict-form Checkpoint is saved
        by the driver, asynchronously: the report loop never blocks on
        serialization or I/O."""
        manager = self._manager
        if manager is None:
            return
        from ray_tpu.checkpoint import SaveHandle
        if isinstance(checkpoint, SaveHandle):
            manager.track(checkpoint.step if checkpoint.step is not None
                          else step, metrics)
        elif isinstance(checkpoint, Checkpoint) and checkpoint.is_sharded:
            manager.track(step, metrics)
        else:
            manager.save(step, checkpoint.to_dict(), metrics=metrics)

    def _finalize_checkpoint(self, checkpoint, manager):
        """Result.checkpoint must be restorable by the caller: resolve a
        SaveHandle to its committed directory (worker-side handles are
        polled through the COMMIT marker on the shared filesystem)."""
        from ray_tpu.checkpoint import SaveHandle
        if not isinstance(checkpoint, SaveHandle):
            return checkpoint
        deadline = time.monotonic() + 60.0
        while not checkpoint.committed() and time.monotonic() < deadline:
            time.sleep(0.05)
        if checkpoint.committed():
            return Checkpoint.from_sharded_dir(checkpoint.directory)
        # Never committed (writer died): fall back to the newest step
        # that did.
        return manager.latest_checkpoint() if manager is not None else None


class TorchTrainer(DataParallelTrainer):
    """Data-parallel torch training over a real torch.distributed process
    group (reference: train/torch/torch_trainer.py:15 — workers are
    actors; the gradient allreduce is torch's own gloo/nccl collective,
    the framework stays out of the data path)."""

    def __init__(self, train_loop_per_worker, *, torch_config=None,
                 **kwargs):
        from ray_tpu.train.backend import TorchConfig
        self._backend_config_cls = TorchConfig
        super().__init__(train_loop_per_worker,
                         backend_config=torch_config or TorchConfig(),
                         **kwargs)


class JaxTrainer(DataParallelTrainer):
    """DataParallelTrainer wired to the jax.distributed TPU backend
    (the TorchTrainer/NCCL analogue — reference train/torch/torch_trainer.py
    :15 — with the fabric swapped for ICI + XLA collectives)."""

    _backend_config_cls = TpuConfig

    def __init__(self, train_loop_per_worker: Callable,
                 *, jax_config: Optional[TpuConfig] = None, **kwargs):
        super().__init__(train_loop_per_worker,
                         backend_config=jax_config or TpuConfig(), **kwargs)

    def fit(self) -> Result:
        # One worker is one jax host: on a cluster that advertises TPU a
        # worker whose scaling config names no TPU leases a whole host's
        # chips, rather than being pinned to the CPU beside idle chips.
        if "TPU" not in self.scaling_config.worker_resources():
            chips = chips_per_host()
            if chips:
                self.scaling_config = dataclasses.replace(
                    self.scaling_config, use_tpu=True,
                    tpus_per_worker=chips)
        return super().fit()
