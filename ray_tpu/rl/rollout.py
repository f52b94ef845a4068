"""Rollout actors: versioned trajectory generation for the RL substrate.

Two gang members, one contract — `adopt(version, weights)` swaps the
policy in place and `rollout()`/`sample_versioned()` emits trajectories
TAGGED with the policy version that produced them:

- `EngineRolloutActor` generates through the serving `InferenceEngine`:
  continuous batching across concurrent episodes, prefix-cache reuse of
  the shared prompt template, and speculative decoding as a pure
  rollout-throughput multiplier (token-exact, so the behavior policy is
  unchanged).  The engine runs with `capture_logp=True`, so every
  emitted token carries the behavior log-prob V-trace needs.
- `EnvRolloutActor` is the classic vectorized-env `RolloutWorker` in
  time-major V-trace layout (`postprocess=False`), version-tagged the
  same way — the CartPole parity path.

Weight adoption on the engine path is BETWEEN scheduler steps: in-flight
lanes keep their paged-KV state and continue under the new weights, so
a publish never drops rollout work.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.rllib.rollout_worker import RolloutWorker
from ray_tpu.rllib.sample_batch import SampleBatch
from ray_tpu.util import spans


class EngineRolloutActor:
    """Trajectory generation through the serving engine.

    Usable in-process or as a `ray_tpu` actor.  On a cluster that
    advertises TPU each actor leases one chip (``leases_chip``) unless
    its options say otherwise; elsewhere it runs on the worker's CPU.
    """

    leases_chip = True

    def __init__(self, model="gpt", config="nano", *, params=None,
                 max_lanes: int = 4, spec_k: int = 0,
                 temperature: float = 1.0, seed: int = 0,
                 prefix_cache: bool = True,
                 reward_fn: Optional[Callable[[List[int], List[int]],
                                              float]] = None,
                 **engine_kwargs):
        from ray_tpu.inference.engine import InferenceEngine
        self.engine = InferenceEngine(
            model, config, params, max_lanes=max_lanes, spec_k=spec_k,
            seed=seed, prefix_cache=prefix_cache, auto_start=False,
            capture_logp=True, **engine_kwargs)
        self.temperature = float(temperature)
        self.version = 0
        self._reward_fn = reward_fn
        self._total_tokens = 0

    # -- weights -----------------------------------------------------------
    def adopt(self, version: int, weights: Any) -> int:
        """In-place weight swap: live lanes keep generating."""
        with spans.span("rl", "adopt", version=int(version),
                        live_lanes=self.engine.num_active):
            self.engine.update_params(weights, int(version))
        self.version = int(version)
        return self.version

    def get_version(self) -> int:
        return self.version

    # -- sampling ----------------------------------------------------------
    def rollout(self, prompts: Sequence[Sequence[int]],
                max_new_tokens: int = 32,
                seed: Optional[int] = None
                ) -> Tuple[SampleBatch, int, Dict]:
        """Generate one trajectory per prompt; all prompts ride the
        lane scheduler concurrently (continuous batching — finished
        lanes are refilled from the queue mid-flight).

        Returns (batch, version, metrics): `batch` is a time-major
        [T, B] SampleBatch of token trajectories (right-padded to the
        longest episode, `valid` masks the padding) and `version` is the
        policy version EVERY token in it was sampled under — rollout()
        drains the gang between adoptions, so a batch is never
        version-mixed."""
        import time
        t0 = time.monotonic()
        version = self.version
        with spans.span("rl", "rollout", version=version,
                        prompts=len(prompts)):
            handles = [
                self.engine.submit(
                    list(p), max_new_tokens, temperature=self.temperature,
                    seed=None if seed is None else seed + i)
                for i, p in enumerate(prompts)]
            while self.engine.step():
                pass
            episodes = [(h.tokens(), h.logps) for h in handles]
        B = len(episodes)
        T = max(1, max(len(toks) for toks, _ in episodes))
        actions = np.zeros((T, B), np.int32)
        logp = np.zeros((T, B), np.float32)
        rewards = np.zeros((T, B), np.float32)
        terminateds = np.zeros((T, B), np.bool_)
        valid = np.zeros((T, B), np.bool_)
        tokens_out = 0
        for b, ((toks, lps), prompt) in enumerate(zip(episodes, prompts)):
            n = len(toks)
            tokens_out += n
            actions[:n, b] = toks
            logp[:n, b] = lps
            valid[:n, b] = True
            if n:
                terminateds[n - 1, b] = True
                if self._reward_fn is not None:
                    rewards[n - 1, b] = float(
                        self._reward_fn(list(prompt), toks))
        self._total_tokens += tokens_out
        batch = SampleBatch({
            SampleBatch.ACTIONS: actions,
            SampleBatch.ACTION_LOGP: logp,
            SampleBatch.REWARDS: rewards,
            SampleBatch.TERMINATEDS: terminateds,
            SampleBatch.TRUNCATEDS: np.zeros((T, B), np.bool_),
            "valid": valid,
            "policy_version": np.full((T, B), version, np.int32),
        })
        wall = time.monotonic() - t0
        st = self.engine.stats()
        metrics = {"tokens": tokens_out, "wall_s": wall,
                   "tokens_per_s": tokens_out / wall if wall > 0 else 0.0,
                   "total_tokens": self._total_tokens,
                   "prefix_hit_tokens": st["prefix_hit_tokens"],
                   "spec_accepted_per_step": st["spec_accepted_per_step"]}
        return batch, version, metrics

    def stats(self) -> dict:
        return self.engine.stats()

    def ping(self) -> bool:
        return True


class EnvRolloutActor(RolloutWorker):
    """Vectorized-env rollout worker with version tagging.

    Always collects in the time-major V-trace layout (postprocess is
    forced off); `sample_versioned()` is `sample()` plus the policy
    version the fragment was collected under.
    """

    def __init__(self, *args, **kwargs):
        kwargs["postprocess"] = False
        super().__init__(*args, **kwargs)
        self.version = 0

    def adopt(self, version: int, weights: Any) -> int:
        with spans.span("rl", "adopt", version=int(version)):
            self.set_weights(weights)
        self.version = int(version)
        return self.version

    def get_version(self) -> int:
        return self.version

    def sample_versioned(self) -> Tuple[SampleBatch, int, Dict]:
        version = self.version
        with spans.span("rl", "rollout", version=version):
            batch, metrics = self.sample()
        T, B = batch[SampleBatch.ACTIONS].shape[:2]
        batch["policy_version"] = np.full((T, B), version, np.int32)
        return batch, version, metrics
