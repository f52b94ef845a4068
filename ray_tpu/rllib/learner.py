"""JaxLearner: jitted SGD on sample batches.

Reference parity: rllib/core/learner/learner.py:94 (compute_gradients:280,
apply_gradients:291, update:674) and torch_learner.py:45.  The TPU-first
difference: the ENTIRE update — epoch loop, minibatch permutation, grad,
optimizer step — is one jitted function (lax.scan over minibatches inside
lax.scan over epochs), so a training_step launches exactly one XLA program
instead of num_epochs*num_minibatches eager steps.  For multi-chip
learners the same function runs under shard_map with a psum on gradients
(data-parallel learner group, reference learner_group.py:51).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.rllib.models import (
    gaussian_logp,
    make_continuous_model,
    make_model,
)
from ray_tpu.rllib.sample_batch import SampleBatch


class JaxLearner:
    """Minibatch-SGD learner over an ActorCritic model.

    loss_fn(apply, params, minibatch, cfg) -> (loss, metrics) is supplied
    by the algorithm (PPO/IMPALA define theirs below/in impala.py).
    """

    def __init__(self, obs_dim: int, num_actions: int, *,
                 loss_fn: Callable, config: Dict[str, Any],
                 hidden=(64, 64), seed: int = 0,
                 mesh: Optional[Any] = None, action_dim: int = 0,
                 model: str = "fc", lstm_size: int = 64):
        self.config = config
        if model == "lstm":
            from ray_tpu.rllib.models import make_recurrent_model
            init_params, _step, self.apply, self.initial_state = \
                make_recurrent_model(obs_dim, num_actions, hidden,
                                     lstm_size)
        elif num_actions == 0 and action_dim > 0:
            init_params, self.apply = make_continuous_model(
                obs_dim, action_dim, hidden)
        else:
            init_params, self.apply = make_model(obs_dim, num_actions,
                                                 hidden)
        self.params = init_params(jax.random.key(seed))
        lr = config.get("lr", 3e-4)
        sched = lr
        if config.get("lr_schedule") == "linear":
            sched = optax.linear_schedule(
                lr, 0.0, config.get("lr_decay_steps", 1000))
        self.tx = optax.chain(
            optax.clip_by_global_norm(config.get("grad_clip", 0.5)),
            optax.adam(sched, eps=1e-5),
        )
        self.opt_state = self.tx.init(self.params)
        self._loss_fn = loss_fn
        self._rng = jax.random.key(seed + 17)
        # Data-parallel learner group over the mesh's data axis
        # (reference: learner_group.py:51 — a fleet of DDP-wrapped
        # learners; here one SPMD program with a pmean on gradients).
        self.mesh = None
        if mesh is not None and any(s > 1 for s in mesh.shape.values()):
            bad = [a for a, s in mesh.shape.items()
                   if s > 1 and a != "data"]
            if bad:
                raise ValueError(
                    f"JaxLearner is data-parallel only; mesh axes {bad} "
                    f"have size > 1 (shard the model with models/, not "
                    f"the RL learner)")
            self.mesh = mesh
        make = self._make_update_dp if self.mesh else self._make_update
        self._update = jax.jit(make(), donate_argnums=(0, 1))

    def _make_update(self):
        num_epochs = self.config.get("num_sgd_iter", 1)
        mb_size = self.config.get("sgd_minibatch_size", 128)
        loss_fn, apply, tx, cfg = self._loss_fn, self.apply, self.tx, self.config

        def minibatch_step(carry, mb):
            params, opt_state = carry
            (_, metrics), grads = jax.value_and_grad(
                partial(loss_fn, apply), has_aux=True)(params, mb, cfg)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), metrics

        def update(params, opt_state, batch, rng):
            n = jax.tree_util.tree_leaves(batch)[0].shape[0]
            num_mb = max(n // mb_size, 1)
            take = num_mb * min(mb_size, n)

            def epoch_step(carry, rng_e):
                params, opt_state = carry
                perm = jax.random.permutation(rng_e, n)
                mbs = jax.tree_util.tree_map(
                    lambda x: x[perm][:take].reshape(
                        (num_mb, take // num_mb) + x.shape[1:]), batch)
                (params, opt_state), metrics = jax.lax.scan(
                    minibatch_step, (params, opt_state), mbs)
                return (params, opt_state), metrics

            rngs = jax.random.split(rng, num_epochs)
            (params, opt_state), metrics = jax.lax.scan(
                epoch_step, (params, opt_state), rngs)
            mean_metrics = jax.tree_util.tree_map(
                lambda m: jnp.mean(m), metrics)
            return params, opt_state, mean_metrics

        return update

    def _make_update_dp(self):
        """SPMD data-parallel update: every shard holds the full batch
        (replicated in_specs), computes identical global permutations and
        per-minibatch advantage normalization, then takes ITS slice of
        each minibatch; gradients pmean over the data axis reconstruct
        the exact global-minibatch gradient, so a dp-k learner walks the
        same parameter trajectory as a single chip (up to fp summation
        order — regression-gated in tests/test_rllib_dp.py)."""
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        k = mesh.shape["data"]
        num_epochs = self.config.get("num_sgd_iter", 1)
        mb_size = self.config.get("sgd_minibatch_size", 128)
        loss_fn, apply, tx = self._loss_fn, self.apply, self.tx
        # Normalization already applied globally per minibatch below.
        cfg = dict(self.config)
        cfg["advantages_prenormalized"] = True

        def minibatch_step(carry, mb):
            params, opt_state = carry
            (_, metrics), grads = jax.value_and_grad(
                partial(loss_fn, apply), has_aux=True)(params, mb, cfg)
            grads = jax.lax.pmean(grads, "data")
            metrics = jax.lax.pmean(metrics, "data")
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), metrics

        def shard_update(params, opt_state, batch, rng):
            idx = jax.lax.axis_index("data")
            n = jax.tree_util.tree_leaves(batch)[0].shape[0]
            num_mb = max(n // mb_size, 1)
            mb_rows = (min(mb_size, n) // k) * k   # divisible by k
            take = num_mb * mb_rows
            local_rows = mb_rows // k

            def epoch_step(carry, rng_e):
                params, opt_state = carry
                perm = jax.random.permutation(rng_e, n)  # same every shard
                mbs = jax.tree_util.tree_map(
                    lambda x: x[perm][:take].reshape(
                        (num_mb, mb_rows) + x.shape[1:]), batch)
                if SampleBatch.ADVANTAGES in mbs:
                    adv = mbs[SampleBatch.ADVANTAGES]
                    # Normalize over every non-minibatch axis (recurrent
                    # batches carry a time axis after the row axis).
                    ax = tuple(range(1, adv.ndim))
                    mbs[SampleBatch.ADVANTAGES] = (
                        (adv - adv.mean(ax, keepdims=True))
                        / (adv.std(ax, keepdims=True) + 1e-8))
                local = jax.tree_util.tree_map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, idx * local_rows, local_rows, axis=1), mbs)
                (params, opt_state), metrics = jax.lax.scan(
                    minibatch_step, (params, opt_state), local)
                return (params, opt_state), metrics

            rngs = jax.random.split(rng, num_epochs)
            (params, opt_state), metrics = jax.lax.scan(
                epoch_step, (params, opt_state), rngs)
            mean_metrics = jax.tree_util.tree_map(
                lambda m: jnp.mean(m), metrics)
            return params, opt_state, mean_metrics

        return jax.shard_map(
            shard_update, mesh=mesh, in_specs=(P(), P(), P(), P()),
            out_specs=(P(), P(), P()), check_vma=False)

    def update(self, batch: SampleBatch) -> Dict[str, float]:
        jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
        self._rng, sub = jax.random.split(self._rng)
        self.params, self.opt_state, metrics = self._update(
            self.params, self.opt_state, jbatch, sub)
        return {k: float(v) for k, v in metrics.items()}

    def get_weights(self):
        return jax.device_get(self.params)

    def set_weights(self, weights) -> None:
        self.params = jax.device_put(weights)

    def get_state(self) -> Dict[str, Any]:
        return {"params": jax.device_get(self.params),
                "opt_state": jax.device_get(self.opt_state)}

    def set_state(self, state: Dict[str, Any]) -> None:
        self.params = jax.device_put(state["params"])
        self.opt_state = jax.device_put(state["opt_state"])


def policy_terms(apply, params, mb, cfg=None):
    """Shared per-minibatch terms: (values, taken-action logp, normalized
    advantages, entropy) — used by the PPO and A2C losses."""
    logits, values = apply(params, mb[SampleBatch.OBS])
    logp_all = jax.nn.log_softmax(logits)
    actions = mb[SampleBatch.ACTIONS].astype(jnp.int32)
    logp = jnp.take_along_axis(logp_all, actions[:, None], axis=1)[:, 0]
    adv = mb[SampleBatch.ADVANTAGES]
    if not (cfg or {}).get("advantages_prenormalized"):
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
    return values, logp, adv, entropy


def _ppo_surrogate(mb, cfg, values, logp, entropy):
    """Shared clipped-surrogate + clamped-vf assembly used by the discrete
    and Gaussian PPO losses (reference semantics: ppo_torch_policy.py —
    SQUARED vf error clamped at vf_clip_param, zero-gradding outliers)."""
    clip = cfg.get("clip_param", 0.2)
    vf_clip = cfg.get("vf_clip_param", 100.0)
    vf_coeff = cfg.get("vf_loss_coeff", 0.5)
    ent_coeff = cfg.get("entropy_coeff", 0.0)

    adv = mb[SampleBatch.ADVANTAGES]
    if not cfg.get("advantages_prenormalized"):
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    ratio = jnp.exp(logp - mb[SampleBatch.ACTION_LOGP])
    surr = jnp.minimum(ratio * adv,
                       jnp.clip(ratio, 1 - clip, 1 + clip) * adv)
    policy_loss = -surr.mean()
    vf_loss = jnp.minimum(
        (values - mb[SampleBatch.VALUE_TARGETS]) ** 2, vf_clip).mean()
    total = policy_loss + vf_coeff * vf_loss - ent_coeff * entropy
    return total, {"total_loss": total, "policy_loss": policy_loss,
                   "vf_loss": vf_loss, "entropy": entropy,
                   "kl": (mb[SampleBatch.ACTION_LOGP] - logp).mean()}


def ppo_loss(apply, params, mb, cfg) -> Tuple[jnp.ndarray, Dict]:
    """Clipped-surrogate PPO loss (categorical actions)."""
    values, logp, _adv, entropy = policy_terms(apply, params, mb)
    return _ppo_surrogate(mb, cfg, values, logp, entropy)


def ppo_loss_recurrent(apply_seq, params, mb, cfg) -> Tuple[jnp.ndarray,
                                                            Dict]:
    """Clipped-surrogate PPO over LSTM sequence chunks.  Minibatch rows
    are SEQUENCES: OBS [b, T, D], actions/logp/advantages/targets
    [b, T], resets [b, T], state_in [b, 2, H] (reference:
    rnn_sequencing.py chunked training — here a masked-reset lax.scan
    replay instead of padded variable-length sequences)."""
    obs = jnp.moveaxis(mb[SampleBatch.OBS], 0, 1)        # [T, b, D]
    resets = mb["resets"].T                              # [T, b]
    state0 = jnp.moveaxis(mb["state_in"], 0, 1)          # [2, b, H]
    logits, values = apply_seq(params, obs, state0, resets)
    logits = jnp.moveaxis(logits, 0, 1)                  # [b, T, A]
    values = values.T                                    # [b, T]
    logp_all = jax.nn.log_softmax(logits)
    actions = mb[SampleBatch.ACTIONS].astype(jnp.int32)
    logp = jnp.take_along_axis(logp_all, actions[..., None],
                               axis=-1)[..., 0]
    entropy = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
    return _ppo_surrogate(mb, cfg, values, logp, entropy)


def ppo_loss_continuous(apply, params, mb, cfg) -> Tuple[jnp.ndarray, Dict]:
    """Clipped-surrogate PPO for diagonal-Gaussian policies (reference:
    ppo loss over DiagGaussian action dists)."""
    mean, log_std, values = apply(params, mb[SampleBatch.OBS])
    logp = gaussian_logp(mean, log_std, mb[SampleBatch.ACTIONS])
    # Diagonal-Gaussian entropy: 0.5*log(2*pi*e) + log_std per dim.
    entropy = jnp.sum(log_std + 0.5 * jnp.log(2 * jnp.pi * jnp.e))
    return _ppo_surrogate(mb, cfg, values, logp, entropy)
