"""Podracer RL substrate tests (PR 20): trajectory queue semantics,
in-place engine weight publication, versioned rollouts, the
stale-tolerant V-trace learner, and the two chaos gates (rollout-worker
kill -> re-form + re-adopt; learner kill -> resume from COMMITTED).

Learning-curve gates (parity vs sync PPO at k=0; still-learns at k=2)
are @slow — they run real CartPole training loops.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rl import (
    EngineRolloutActor,
    Podracer,
    PodracerConfig,
    StaleTolerantLearner,
    TrajectoryQueue,
    WeightPublisher,
)
from ray_tpu.rllib.sample_batch import SampleBatch


@pytest.fixture(scope="module")
def cluster():
    info = ray_tpu.init(num_cpus=8, object_store_memory=128 << 20)
    yield info
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Trajectory queue: staleness bound + backpressure
# ---------------------------------------------------------------------------


def test_trajectory_queue_staleness_and_backpressure():
    q = TrajectoryQueue(capacity=2, staleness_bound=1)
    assert q.put("a", version=5, learner_version=5)
    assert q.put("b", version=4, learner_version=5)      # staleness 1: ok
    assert not q.put("c", version=3, learner_version=5)  # staleness 2: drop
    assert not q.put("d", version=5, learner_version=5)  # full: backpressure
    assert q.full and len(q) == 2
    st = q.stats()
    assert st["accepted"] == 2
    assert st["stale_dropped"] == 1
    assert st["backpressured"] == 1

    batch, version = q.get(learner_version=5)
    assert (batch, version) == ("a", 5)
    # "b" (version 4) went stale while queued once the learner hits 6:
    # get() must evict it in passing, not hand it over.
    assert q.get(learner_version=6) is None
    assert q.stats()["stale_dropped"] == 2
    assert len(q) == 0


def test_trajectory_queue_get_timeout_and_evict_stale():
    q = TrajectoryQueue(capacity=4, staleness_bound=0)
    t0 = time.monotonic()
    assert q.get(learner_version=1, timeout=0.05) is None
    assert time.monotonic() - t0 >= 0.04
    for v in (1, 2, 3):
        assert q.put(f"b{v}", version=v, learner_version=3 if v == 3 else v)
    # Learner resumed at version 3: only the version-3 entry survives.
    assert q.evict_stale(learner_version=3) == 2
    assert q.get(learner_version=3) == ("b3", 3)
    with pytest.raises(ValueError):
        TrajectoryQueue(capacity=0)
    with pytest.raises(ValueError):
        TrajectoryQueue(staleness_bound=-1)


# ---------------------------------------------------------------------------
# Engine path: in-place weight swap + versioned logp-carrying rollouts
# ---------------------------------------------------------------------------


def test_engine_weight_swap_mid_flight_keeps_lanes():
    """update_params between scheduler steps must not drop the in-flight
    lane: the request finishes its full budget, the engine reports the
    new policy version, and every emitted token carries a log-prob."""
    actor = EngineRolloutActor("gpt", "nano", max_lanes=2,
                               temperature=1.0, seed=0)
    eng = actor.engine
    h = eng.submit(list(range(1, 9)), max_new_tokens=8, temperature=1.0,
                   seed=7)
    for _ in range(3):
        assert eng.step()
    new_version = actor.adopt(7, eng.params)   # swap mid-request
    assert new_version == 7
    while eng.step():
        pass
    assert len(h.tokens()) == 8
    assert len(h.logps) == 8
    assert all(np.isfinite(lp) and lp <= 0.0 for lp in h.logps)
    assert eng.policy_version == 7
    assert eng.stats()["policy_version"] == 7
    # float32 learner weights under float32 activations: the swap prepared
    # them (counted) and what the step takes is the tree it was handed.
    assert eng.stats()["weights"]["prepared"] == 2
    assert eng._served is eng.params


def test_engine_rollout_actor_versioned_batch():
    """rollout() emits a time-major V-trace-shaped SampleBatch tagged
    with the producing policy version; adoption re-tags the next batch."""
    rewards_seen = []

    def reward_fn(prompt, completion):
        rewards_seen.append((tuple(prompt), tuple(completion)))
        return float(len(completion))

    actor = EngineRolloutActor("gpt", "nano", max_lanes=4, temperature=1.0,
                               seed=0, reward_fn=reward_fn)
    prompts = [[1, 2, 3], [1, 2, 4], [1, 2, 5]]
    batch, version, metrics = actor.rollout(prompts, max_new_tokens=6,
                                            seed=11)
    assert version == 0
    T, B = batch[SampleBatch.ACTIONS].shape
    assert B == 3 and 1 <= T <= 6
    for key in (SampleBatch.ACTION_LOGP, SampleBatch.REWARDS,
                SampleBatch.TERMINATEDS, "valid", "policy_version"):
        assert batch[key].shape == (T, B)
    assert (batch["policy_version"] == 0).all()
    # Each lane terminates exactly once, where its terminal reward sits.
    assert batch[SampleBatch.TERMINATEDS].sum(axis=0).tolist() == [1, 1, 1]
    n_valid = batch["valid"].sum(axis=0)
    for b in range(B):
        t_last = int(n_valid[b]) - 1
        assert batch[SampleBatch.TERMINATEDS][t_last, b]
        assert batch[SampleBatch.REWARDS][t_last, b] == float(n_valid[b])
    assert len(rewards_seen) == 3
    assert metrics["tokens"] == int(batch["valid"].sum())
    assert metrics["tokens_per_s"] > 0

    actor.adopt(4, actor.engine.params)
    batch2, version2, _ = actor.rollout(prompts, max_new_tokens=4, seed=12)
    assert version2 == 4 and (batch2["policy_version"] == 4).all()


# ---------------------------------------------------------------------------
# Stale-tolerant learner: staleness accounting + COMMITTED durability
# ---------------------------------------------------------------------------


def _fake_fragment(rng, T=8, B=4, obs_dim=4, num_actions=2):
    return SampleBatch({
        SampleBatch.OBS: rng.normal(size=(T, B, obs_dim)).astype(np.float32),
        SampleBatch.ACTIONS: rng.integers(0, num_actions,
                                          size=(T, B)).astype(np.int32),
        SampleBatch.ACTION_LOGP: np.full((T, B), -0.7, np.float32),
        SampleBatch.REWARDS: rng.normal(size=(T, B)).astype(np.float32),
        SampleBatch.TERMINATEDS: np.zeros((T, B), np.bool_),
        SampleBatch.TRUNCATEDS: np.zeros((T, B), np.bool_),
        "bootstrap_obs": rng.normal(size=(B, obs_dim)).astype(np.float32),
        "policy_version": np.ones((T, B), np.int32),
        "valid": np.ones((T, B), np.bool_),
    })


def test_learner_staleness_versioning_and_checkpoint_resume(tmp_path):
    rng = np.random.default_rng(0)
    ln = StaleTolerantLearner(4, 2, hidden=(8,), seed=0,
                              ckpt_dir=str(tmp_path), ckpt_interval=2)
    assert ln.version == 1
    m1 = ln.update(_fake_fragment(rng), behavior_version=1)
    assert m1["staleness"] == 0.0 and np.isfinite(m1["total_loss"])
    version, weights = ln.publish_boundary()
    assert version == 2 and weights is not None
    m2 = ln.update(_fake_fragment(rng), behavior_version=1)
    assert m2["staleness"] == 1.0
    # ckpt_interval=2 -> a COMMITTED checkpoint exists at update 2.
    ln2 = StaleTolerantLearner(4, 2, hidden=(8,), seed=123,
                               ckpt_dir=str(tmp_path))
    restored = ln2.restore_latest()
    assert restored == 2
    assert ln2.version == 2 and ln2.num_updates == 2
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(ln.get_weights()),
                    jax.tree_util.tree_leaves(ln2.get_weights())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Fresh dir: nothing to restore.
    ln3 = StaleTolerantLearner(4, 2, hidden=(8,), seed=0,
                               ckpt_dir=str(tmp_path / "empty"))
    assert ln3.restore_latest() is None


# ---------------------------------------------------------------------------
# Chaos gates: rollout-worker kill + learner kill, one live cluster
# ---------------------------------------------------------------------------


def test_podracer_chaos_worker_kill_and_learner_resume(cluster, tmp_path):
    cfg = (PodracerConfig()
           .environment("CartPole-v1")
           .rollouts(num_rollout_workers=2, num_envs_per_worker=2,
                     rollout_fragment_length=8)
           .training(min_updates_per_step=2, staleness_bound=2,
                     queue_capacity=4, ckpt_dir=str(tmp_path),
                     ckpt_interval=1)
           .debugging(seed=0))
    algo = cfg.build()
    try:
        r = algo.train()
        assert r["learner_updates_total"] >= 2
        assert r["policy_version"] >= 2

        # Gate 1: kill a rollout worker mid-gang.  The loop must detect
        # the death at delivery, re-form the gang, and the replacement
        # must re-adopt the CURRENT published weights (no new put).
        ray_tpu.kill(algo.workers.remote_workers[0])
        for _ in range(3):
            r = algo.train()
        assert algo.workers.num_remote_workers == 2
        versions = ray_tpu.get(
            [w.get_version.remote() for w in algo.workers.remote_workers])
        assert all(v >= 1 for v in versions)
        # The gang converges onto the newest published version.
        r = algo.train()
        versions = ray_tpu.get(
            [w.get_version.remote() for w in algo.workers.remote_workers])
        assert max(versions) == algo.publisher.version

        # Gate 2: kill the learner.  Resume must come from the newest
        # COMMITTED checkpoint and must not poison the queue — entries
        # beyond the restored staleness horizon are evicted, training
        # continues.
        updates_before = algo.learner.num_updates
        committed = algo.learner._ckpt.latest_step()
        assert committed is not None and committed <= updates_before
        algo.learner = None   # the "kill": in-memory state is gone
        restored = algo.recover_learner()
        assert restored == committed
        assert algo.learner.num_updates == committed
        for _, v in list(algo.queue._dq):
            assert algo.learner.version - v <= algo.queue.staleness_bound
        r = algo.train()
        assert algo.learner.num_updates > committed
        assert np.isfinite(r["learner/total_loss"])
    finally:
        algo.stop()


# ---------------------------------------------------------------------------
# Learning gates (slow): parity vs sync PPO at k=0; still learns at k=2
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_podracer_k0_parity_with_sync_ppo(cluster):
    """At staleness_bound=0 every trained batch is exactly on-policy, so
    the async loop is a sync actor-learner with extra plumbing — it must
    reach the same CartPole milestone as rllib's synchronous PPO within
    a bounded sample-budget factor.  The 6x tolerance is measured
    headroom, not hand-waving: PPO does 6 SGD epochs per batch where
    V-trace trains each fragment once, and at k=0 roughly half the
    produced fragments are dropped at publish boundaries (the async
    loop's on-policy tax) — observed ratio ~4.3x."""
    from ray_tpu.rllib import PPOConfig

    TARGET = 100.0

    def steps_to_target_ppo(budget_steps):
        cfg = (PPOConfig().environment("CartPole-v1")
               .rollouts(num_rollout_workers=0, num_envs_per_worker=16,
                         rollout_fragment_length=32)
               .training(train_batch_size=512, sgd_minibatch_size=128,
                         num_sgd_iter=6, lr=5e-4, entropy_coeff=0.005)
               .debugging(seed=1))
        algo = cfg.build()
        try:
            while algo.total_env_steps < budget_steps:
                r = algo.train()
                if r["episode_reward_mean"] >= TARGET:
                    return algo.total_env_steps
            return None
        finally:
            algo.stop()

    def steps_to_target_podracer(budget_steps):
        cfg = (PodracerConfig().environment("CartPole-v1")
               .rollouts(num_rollout_workers=1, num_envs_per_worker=16,
                         rollout_fragment_length=32)
               .training(staleness_bound=0, publish_interval=1,
                         min_updates_per_step=2, lr=1e-3,
                         entropy_coeff=0.005)
               .debugging(seed=1))
        algo = cfg.build()
        steps = 0
        try:
            while steps < budget_steps:
                r = algo.train()
                steps += r["fragments_this_iter"] * 16 * 32
                assert r.get("learner/staleness", 0.0) == 0.0
                if r["episode_reward_mean"] >= TARGET:
                    return steps
            return None
        finally:
            algo.stop()

    ppo_steps = steps_to_target_ppo(120_000)
    assert ppo_steps is not None, "sync PPO baseline failed its own gate"
    pod_steps = steps_to_target_podracer(6 * ppo_steps)
    assert pod_steps is not None, \
        f"podracer@k=0 did not reach {TARGET} within 6x PPO's " \
        f"{ppo_steps} env steps"


@pytest.mark.slow
def test_podracer_still_learns_at_k2(cluster):
    """With staleness_bound=2 and a publish per update, most batches are
    trained off-policy — V-trace must still move reward well off the
    random floor, and the loop must actually have trained stale data."""
    cfg = (PodracerConfig().environment("CartPole-v1")
           .rollouts(num_rollout_workers=2, num_envs_per_worker=8,
                     rollout_fragment_length=32)
           .training(staleness_bound=2, publish_interval=1,
                     min_updates_per_step=2, lr=5e-4, entropy_coeff=0.01)
           .debugging(seed=0))
    algo = cfg.build()
    try:
        best, max_staleness = 0.0, 0.0
        for _ in range(60):
            r = algo.train()
            best = max(best, r["episode_reward_mean"])
            max_staleness = max(max_staleness,
                                r.get("learner/staleness", 0.0))
            if best > 60 and max_staleness > 0:
                break
        assert best > 60, f"podracer@k=2 made no progress: best={best}"
        assert max_staleness > 0, "async loop never trained a stale batch"
        assert max_staleness <= 2, \
            f"staleness bound violated: {max_staleness}"
    finally:
        algo.stop()
