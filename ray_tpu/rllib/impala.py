"""IMPALA: async actor-learner RL.

Reference parity: rllib/algorithms/impala/impala.py:509 (training_step:659
— async sampling queues feeding a learner thread, periodic weight
broadcast) + rllib/execution/learner_thread.py:17 (LearnerThread).
TPU-first differences: the V-trace correction + SGD step is one jitted XLA
program over time-major fragments, and the learner thread is the host-side
pipeline that keeps the chip fed while rollout actors run ahead
asynchronously.
"""

from __future__ import annotations

import queue
import threading
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

import ray_tpu
from ray_tpu.rllib.algorithm import Algorithm, AlgorithmConfig
from ray_tpu.rllib.models import make_model
from ray_tpu.rllib.sample_batch import SampleBatch
from ray_tpu.rllib.vtrace import vtrace
from ray_tpu.rllib.worker_set import WorkerSet


class IMPALAConfig(AlgorithmConfig):
    def __init__(self):
        super().__init__(algo_class=IMPALA)
        self.lr = 6e-4
        self.grad_clip = 40.0
        self.vf_loss_coeff = 0.5
        self.entropy_coeff = 0.01
        self.clip_rho_threshold = 1.0
        self.clip_c_threshold = 1.0
        self.broadcast_interval = 1       # updates between weight broadcasts
        self.learner_queue_size = 16
        self.min_updates_per_step = 1


class _VTraceLearner:
    """Single-fragment jitted V-trace SGD step over time-major batches."""

    def __init__(self, obs_dim: int, num_actions: int, cfg: IMPALAConfig,
                 hidden, seed: int, mesh=None):
        use_lstm = getattr(cfg, "use_lstm", False)
        apply_seq = apply_step = None
        if use_lstm:
            from ray_tpu.rllib.models import make_recurrent_model
            init_params, apply_step, apply_seq, _init_state = \
                make_recurrent_model(obs_dim, num_actions, hidden,
                                     getattr(cfg, "lstm_size", 64))
            self.apply = apply_seq
        else:
            init_params, self.apply = make_model(obs_dim, num_actions,
                                                 hidden)
        self.params = init_params(jax.random.key(seed))
        self.tx = optax.chain(
            optax.clip_by_global_norm(cfg.grad_clip),
            optax.adam(cfg.lr, eps=1e-5))
        self.opt_state = self.tx.init(self.params)
        self.num_updates = 0
        self.mesh = (mesh if mesh is not None
                     and any(s > 1 for s in mesh.shape.values()) else None)

        gamma = cfg.gamma
        vf_coeff = cfg.vf_loss_coeff
        ent_coeff = cfg.entropy_coeff
        rho_bar, c_bar = cfg.clip_rho_threshold, cfg.clip_c_threshold
        # APPO (reference: rllib/algorithms/appo/appo.py — IMPALA's
        # architecture with PPO's clipped surrogate on V-trace
        # advantages): when clip_param is set, the policy loss becomes
        # the clipped importance-ratio surrogate vs the BEHAVIOR policy.
        clip_param = getattr(cfg, "clip_param", None)
        apply = self.apply

        def loss(params, batch):
            obs = batch[SampleBatch.OBS]      # [T, B, D] or [T, B, H, W, C]
            T, B = obs.shape[:2]
            if use_lstm:
                # Time-major V-trace fragments are the LSTM's native
                # layout: one masked-reset scan over the chunk
                # (reference: rnn_sequencing in the IMPALA learner).
                logits, values = apply_seq(
                    params, obs, batch["state_in"], batch["resets"])
                _, bootstrap_value, _ = apply_step(
                    params, batch["bootstrap_obs"],
                    batch["bootstrap_state"])
            else:
                logits, values = apply(
                    params, obs.reshape((T * B,) + obs.shape[2:]))
                logits = logits.reshape(T, B, -1)
                values = values.reshape(T, B)
                _, bootstrap_value = apply(params, batch["bootstrap_obs"])

            logp_all = jax.nn.log_softmax(logits)
            actions = batch[SampleBatch.ACTIONS].astype(jnp.int32)
            target_logp = jnp.take_along_axis(
                logp_all, actions[..., None], axis=-1)[..., 0]

            done = (batch[SampleBatch.TERMINATEDS]
                    | batch[SampleBatch.TRUNCATEDS]).astype(jnp.float32)
            discounts = gamma * (1.0 - done)
            vt = vtrace(batch[SampleBatch.ACTION_LOGP], target_logp,
                        batch[SampleBatch.REWARDS], discounts, values,
                        bootstrap_value, rho_bar, c_bar)

            if clip_param is not None:
                ratio = jnp.exp(target_logp
                                - batch[SampleBatch.ACTION_LOGP])
                adv = vt.pg_advantages
                surr = jnp.minimum(
                    ratio * adv,
                    jnp.clip(ratio, 1 - clip_param, 1 + clip_param) * adv)
                pg_loss = -surr.mean()
            else:
                pg_loss = -(vt.pg_advantages * target_logp).mean()
            vf_loss = 0.5 * ((vt.vs - values) ** 2).mean()
            entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
            total = pg_loss + vf_coeff * vf_loss - ent_coeff * entropy
            return total, {"total_loss": total, "policy_loss": pg_loss,
                           "vf_loss": vf_loss, "entropy": entropy}

        def step(params, opt_state, batch):
            (_, metrics), grads = jax.value_and_grad(
                loss, has_aux=True)(params, batch)
            if self.mesh is not None:
                grads = jax.lax.pmean(grads, "data")
                metrics = jax.lax.pmean(metrics, "data")
            updates, opt_state = self.tx.update(updates=grads,
                                                state=opt_state,
                                                params=params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, metrics

        if self.mesh is not None:
            # Data-parallel learner: fragments (the batch dim of the
            # time-major [T, B] batch) are sliced across the data axis;
            # V-trace is per-sequence so slicing columns is exact, and
            # the gradient pmean reconstructs the global batch gradient
            # (reference: LearnerGroup's DDP fleet, learner_group.py:51).
            from jax.sharding import PartitionSpec as P

            k = self.mesh.shape["data"]

            def shard_step(params, opt_state, batch):
                idx = jax.lax.axis_index("data")

                def slice_cols(key, x):
                    axis = 0 if key == "bootstrap_obs" else 1
                    rows = x.shape[axis] // k
                    return jax.lax.dynamic_slice_in_dim(
                        x, idx * rows, rows, axis=axis)

                local = {key: slice_cols(key, v)
                         for key, v in batch.items()}
                return step(params, opt_state, local)

            step_fn = jax.shard_map(
                shard_step, mesh=self.mesh, in_specs=(P(), P(), P()),
                out_specs=(P(), P(), P()), check_vma=False)
        else:
            step_fn = step
        # No donation: the learner thread updates params while the driver
        # thread concurrently reads them for weight broadcast — donating
        # would delete buffers out from under the reader.
        self._step = jax.jit(step_fn)

    def update(self, batch: SampleBatch) -> Dict[str, float]:
        jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
        self.params, self.opt_state, metrics = self._step(
            self.params, self.opt_state, jbatch)
        self.num_updates += 1
        return {k: float(v) for k, v in metrics.items()}

    def get_weights(self):
        return jax.device_get(self.params)

    def get_state(self):
        return {"params": jax.device_get(self.params),
                "opt_state": jax.device_get(self.opt_state)}

    def set_state(self, state):
        self.params = jax.device_put(state["params"])
        self.opt_state = jax.device_put(state["opt_state"])


class LearnerThread(threading.Thread):
    """Consumes fragments from a queue, runs SGD continuously.

    Reference: rllib/execution/learner_thread.py:17.
    """

    def __init__(self, learner: _VTraceLearner, queue_size: int):
        super().__init__(daemon=True, name="impala-learner")
        self.learner = learner
        self.inqueue: queue.Queue = queue.Queue(maxsize=queue_size)
        self.last_metrics: Dict[str, float] = {}
        self.stopped = False
        self._error = None

    def run(self) -> None:
        while not self.stopped:
            batch = self.inqueue.get()
            if batch is None:
                return
            try:
                self.last_metrics = self.learner.update(batch)
            except Exception as e:  # surface in training_step
                self._error = e
                return

    def stop(self) -> None:
        self.stopped = True
        try:
            self.inqueue.put_nowait(None)
        except queue.Full:
            pass

    def check_error(self) -> None:
        if self._error is not None:
            raise self._error


class IMPALA(Algorithm):
    def setup(self) -> None:
        cfg = self.config
        self.workers = WorkerSet(
            num_workers=max(cfg.num_rollout_workers, 1),
            num_cpus_per_worker=cfg.num_cpus_per_worker,
            worker_kwargs=dict(
                env=cfg.env, num_envs=cfg.num_envs_per_worker,
                rollout_fragment_length=cfg.rollout_fragment_length,
                gamma=cfg.gamma, lam=cfg.lambda_,
                hidden=cfg.model_hidden, seed=cfg.seed,
                postprocess=False,
                **({"policy_kind": "recurrent",
                    "lstm_size": cfg.lstm_size}
                   if getattr(cfg, "use_lstm", False) else {})))
        self.learner = _VTraceLearner(
            self.obs_dim, self.num_actions, cfg, cfg.model_hidden, cfg.seed,
            mesh=cfg.learner_mesh)
        self.workers.sync_weights(self.learner.get_weights())
        self.learner_thread = LearnerThread(
            self.learner, cfg.learner_queue_size)
        self.learner_thread.start()
        self._inflight: Dict[Any, Any] = {}   # ref -> worker
        self._updates_at_broadcast = 0

    def _launch(self, worker) -> None:
        self._inflight[worker.sample.remote()] = worker

    def training_step(self) -> Dict[str, Any]:
        """Reference: impala.py:659 — async sample -> learner queue ->
        periodic broadcast."""
        cfg = self.config
        self.learner_thread.check_error()
        for w in self.workers.remote_workers:
            if w not in self._inflight.values():
                self._launch(w)

        updates_before = self.learner.num_updates
        fragments = 0
        episodes = 0
        # Drain until the learner has made progress this step.
        while (self.learner.num_updates - updates_before
               < cfg.min_updates_per_step):
            self.learner_thread.check_error()
            ready, _ = ray_tpu.wait(list(self._inflight), num_returns=1,
                                    timeout=10.0)
            if not ready:
                continue
            for ref in ready:
                worker = self._inflight.pop(ref)
                try:
                    batch, metrics = ray_tpu.get(ref)
                except Exception:
                    worker = self.workers.replace_worker(worker)
                    self._launch(worker)
                    continue
                episodes += self._record_metrics([metrics])
                fragments += 1
                # Bounded put with error polling: if the learner thread died
                # with the queue full, a bare put() would deadlock the
                # driver instead of surfacing the learner exception.
                while True:
                    self.learner_thread.check_error()
                    if self.learner_thread.stopped:
                        return {"fragments_this_iter": fragments,
                                "episodes_this_iter": episodes,
                                "learner_updates_total":
                                    self.learner.num_updates}
                    try:
                        self.learner_thread.inqueue.put(batch, timeout=1.0)
                        break
                    except queue.Full:
                        continue
                # Broadcast newest weights to the worker that just
                # delivered, then relaunch it (reference: per-worker
                # broadcast on result, impala.py broadcast_interval).
                if (self.learner.num_updates - self._updates_at_broadcast
                        >= cfg.broadcast_interval):
                    ref_w = ray_tpu.put(self.learner.get_weights())
                    worker.set_weights.remote(ref_w)
                    self._updates_at_broadcast = self.learner.num_updates
                self._launch(worker)

        self.workers.local_worker.set_weights(self.learner.get_weights())
        return {"fragments_this_iter": fragments,
                "episodes_this_iter": episodes,
                "learner_updates_total": self.learner.num_updates,
                **{f"learner/{k}": v
                   for k, v in self.learner_thread.last_metrics.items()}}

    def stop(self) -> None:
        self.learner_thread.stop()
        super().stop()

    def save_to_dict(self) -> Dict[str, Any]:
        return {"learner_state": self.learner.get_state(),
                "config": self.config.to_dict()}

    def restore_from_dict(self, state: Dict[str, Any]) -> None:
        self.learner.set_state(state["learner_state"])
        self.workers.sync_weights(self.learner.get_weights())
