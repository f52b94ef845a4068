"""Train library tests: worker gangs, jax.distributed rendezvous across
actor processes (2 workers x 2 virtual CPU devices = 4-device fabric),
session streaming, checkpoints, elastic restart.

Reference coverage model: python/ray/train/tests/test_backend.py +
test_data_parallel_trainer.py, with the torch/NCCL fabric replaced by
multi-controller JAX on CPU.
"""

import os

import pytest

import ray_tpu
from ray_tpu.air import Checkpoint, FailureConfig, RunConfig, ScalingConfig
from ray_tpu.train import (
    DataParallelTrainer, JaxTrainer, TpuConfig)

WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
}


@pytest.fixture(scope="module")
def cluster():
    info = ray_tpu.init(num_cpus=8, object_store_memory=64 << 20)
    yield info
    ray_tpu.shutdown()


def test_data_parallel_trainer_basic(cluster):
    def loop(config):
        from ray_tpu.train import session
        for step in range(config["steps"]):
            session.report({"step": step,
                            "rank": session.get_world_rank(),
                            "world": session.get_world_size()})

    trainer = DataParallelTrainer(
        loop, train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(num_workers=2))
    result = trainer.fit()
    assert result.error is None
    assert len(result.metrics_history) == 3
    assert result.metrics["step"] == 2
    assert result.metrics["world"] == 2


def test_jax_trainer_distributed_fabric(cluster):
    """2 worker processes x 2 CPU devices -> one 4-device jax fabric with a
    cross-process psum (the ICI-collective path, simulated on CPU)."""

    def loop():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from ray_tpu.train import session

        assert jax.process_count() == 2
        assert len(jax.devices()) == 4
        mesh = Mesh(np.array(jax.devices()), ("data",))
        sharding = NamedSharding(mesh, P("data"))
        # Each process contributes its local shard of the global array.
        local = np.full((2, 4), 1.0 + jax.process_index(), np.float32)
        arr = jax.make_array_from_process_local_data(sharding, local, (4, 4))
        total = jax.jit(lambda x: jnp.sum(x))(arr)   # cross-process reduce
        session.report({"total": float(total),
                        "devices": len(jax.devices())})

    trainer = JaxTrainer(
        loop,
        jax_config=TpuConfig(env_per_worker=WORKER_ENV),
        scaling_config=ScalingConfig(num_workers=2))
    result = trainer.fit()
    assert result.error is None
    # 8 elements of 1.0 (process 0) + 8 of 2.0 (process 1) = 24.
    assert result.metrics["total"] == 24.0
    assert result.metrics["devices"] == 4


def test_trainer_checkpointing(cluster, tmp_path):
    def loop(config):
        from ray_tpu.train import session
        start = 0
        ckpt = session.get_checkpoint()
        if ckpt is not None:
            start = ckpt.to_dict()["step"] + 1
        for step in range(start, 4):
            session.report({"step": step},
                           checkpoint=Checkpoint.from_dict({"step": step}))

    trainer = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="ckpt_run", storage_path=str(tmp_path)))
    result = trainer.fit()
    assert result.error is None
    assert result.checkpoint.to_dict() == {"step": 3}
    saved = sorted(os.listdir(tmp_path / "ckpt_run"))
    assert len(saved) == 4

    # Resume from the checkpoint: only remaining steps run.
    trainer2 = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        resume_from_checkpoint=result.checkpoint)
    result2 = trainer2.fit()
    assert result2.error is None
    assert result2.metrics_history == []  # start=4: nothing left to do


def test_trainer_error_propagates(cluster):
    def loop():
        from ray_tpu.train import session
        session.report({"step": 0})
        raise RuntimeError("boom in train loop")

    trainer = DataParallelTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2))
    result = trainer.fit()
    assert result.error is not None
    assert "boom" in str(result.error)
    assert len(result.metrics_history) == 1


def test_trainer_elastic_restart(cluster, tmp_path):
    marker = tmp_path / "crashed_once"

    def loop(config):
        import os as _os
        from ray_tpu.train import session
        start = 0
        ckpt = session.get_checkpoint()
        if ckpt is not None:
            start = ckpt.to_dict()["step"] + 1
        for step in range(start, 5):
            if step == 2 and session.get_world_rank() == 0 \
                    and not _os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                _os._exit(1)  # hard-kill this worker mid-training
            session.report({"step": step},
                           checkpoint=Checkpoint.from_dict({"step": step}))

    trainer = DataParallelTrainer(
        loop, train_loop_config={"marker": str(marker)},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(failure_config=FailureConfig(max_failures=2)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 4
    assert marker.exists()


def test_jax_trainer_gpt_finetune_e2e(cluster):
    """BASELINE.md target: GPT LM fine-tune, DataParallelTrainer-equivalent,
    across a multi-worker jax fabric (nano config on the CPU mesh)."""

    def loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import optax
        from ray_tpu.models import gpt
        from ray_tpu.parallel import MeshConfig, create_mesh, global_batch
        from ray_tpu.train import session

        cfg = gpt.CONFIGS["nano"]
        mesh = create_mesh(MeshConfig(data=-1))  # all 4 global devices
        init_state, train_step = gpt.make_train_step(
            cfg, optax.adam(1e-2), mesh)
        state = init_state(jax.random.key(0))
        step = jax.jit(train_step, donate_argnums=0)

        rng = np.random.default_rng(session.get_world_rank())
        local = rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int32)
        batch = global_batch(mesh, {"tokens": local})
        for i in range(config["steps"]):
            state, metrics = step(state, batch)
            session.report({"loss": float(metrics["loss"]), "step": i})

    trainer = JaxTrainer(
        loop, train_loop_config={"steps": 4},
        jax_config=TpuConfig(env_per_worker=WORKER_ENV),
        scaling_config=ScalingConfig(num_workers=2))
    result = trainer.fit()
    assert result.error is None
    losses = [m["loss"] for m in result.metrics_history]
    assert losses[-1] < losses[0]


def test_data_to_train_streaming_ingest(cluster):
    """Data -> Train: each worker iterates ITS OWN shard stream via
    session.get_dataset_shard (reference: DataParallelTrainer datasets= +
    streaming_split ingest)."""
    from ray_tpu import data as rdata
    from ray_tpu import train
    from ray_tpu.train import session

    ds = rdata.range(512).map(lambda r: {"id": r["id"], "x": float(r["id"])})

    def loop():
        shard = session.get_dataset_shard("train")
        ctx = session.get_context()
        rows = 0
        total = 0.0
        for batch in shard.iter_batches(batch_size=64):
            rows += len(batch["x"])
            total += float(batch["x"].sum())
        session.report({"rows": rows, "total": total,
                        "rank": ctx.world_rank})

    from ray_tpu.air import ScalingConfig
    trainer = train.JaxTrainer(
        loop,
        scaling_config=ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 1}),
        datasets={"train": ds})
    result = trainer.fit()
    assert result.error is None
    # Rank-0 metrics: each worker saw exactly half the rows.
    assert result.metrics["rows"] == 256


def test_torch_trainer_ddp_gloo(cluster):
    """TorchTrainer: 2 workers form a real torch.distributed gloo group
    and allreduce gradients (reference: train/torch/config.py:155 +
    torch_trainer.py — the collective is torch's own, not ours)."""
    from ray_tpu import train
    from ray_tpu.train import session

    def loop():
        import torch
        import torch.distributed as dist

        rank = dist.get_rank()
        world = dist.get_world_size()
        model = torch.nn.Linear(4, 1, bias=False)
        with torch.no_grad():
            model.weight.fill_(1.0)
        # Rank-dependent data -> rank-dependent grads; allreduce averages.
        x = torch.full((8, 4), float(rank + 1))
        loss = model(x).sum()
        loss.backward()
        dist.all_reduce(model.weight.grad, op=dist.ReduceOp.SUM)
        model.weight.grad /= world
        session.report({
            "rank": rank, "world": world,
            "grad0": float(model.weight.grad[0, 0]),
        })

    trainer = train.TorchTrainer(
        loop,
        scaling_config=ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 1}))
    result = trainer.fit()
    assert result.error is None
    # grads: rank0 data=1 -> grad 8; rank1 data=2 -> grad 16; mean = 12.
    assert result.metrics["grad0"] == pytest.approx(12.0)
    assert result.metrics["world"] == 2
