"""Training backends: how a worker gang becomes one SPMD compute fabric.

Reference parity: python/ray/train/backend.py (Backend/BackendConfig) +
torch/config.py:155 _TorchBackend (rank-0 TCP rendezvous ->
dist.init_process_group(nccl), :69-:113).

TPU-native design: the collective fabric is jax.distributed — worker 0
publishes a coordinator address, every worker calls
`jax.distributed.initialize(coordinator, num_processes, process_id)`, and
from then on `jax.devices()` spans the whole gang and XLA compiles
collectives onto ICI/DCN.  No NCCL, no process groups: the mesh IS the
communicator.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional

from ray_tpu.train.worker_group import WorkerGroup


@dataclass
class BackendConfig:
    def backend_cls(self):
        return Backend


class Backend:
    """Hooks around the training lifecycle (reference: train/backend.py)."""

    def on_start(self, worker_group: WorkerGroup, config: BackendConfig):
        pass

    def on_shutdown(self, worker_group: WorkerGroup, config: BackendConfig):
        pass

    def on_training_start(self, worker_group: WorkerGroup,
                          config: BackendConfig):
        pass


# ------------------------- TPU / JAX backend -------------------------------


@dataclass
class TpuConfig(BackendConfig):
    """Configuration for the jax.distributed fabric.

    env_per_worker: extra env vars set on every worker BEFORE jax imports
    (e.g. {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_
    device_count=2"} to simulate a 2-chip host per worker in tests).
    """

    env_per_worker: dict = field(default_factory=dict)
    coordinator_port: Optional[int] = None
    init_timeout_s: float = 120.0

    def backend_cls(self):
        return TpuBackend


def _find_free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _coordinator_host() -> str:
    import socket
    return socket.gethostbyname(socket.gethostname())


def _init_jax_distributed(coordinator: Optional[str], num_processes: int,
                          process_id: int, env: dict):
    os.environ.update({k: str(v) for k, v in env.items()})
    from ray_tpu._private import compile_cache
    from ray_tpu.util import spans

    # Where a train worker first reaches jax, once a process: in the
    # start-up record, as a replica's are.
    with spans.span("proc", "jax_import", pin=True):
        import jax
    compile_cache.watch()       # before the worker's first program

    if "JAX_PLATFORMS" in env:
        # jax read the variable at import; a process that imported jax
        # before this call needs the live config told too.
        jax.config.update("jax_platforms", env["JAX_PLATFORMS"])
    if num_processes > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id)
    with spans.span("proc", "backend_init", pin=True):
        local = len(jax.local_devices())        # the client starts here
    return {"process_id": process_id, "local_devices": local,
            "global_devices": len(jax.devices())}


def _shutdown_jax_distributed():
    import jax
    try:
        jax.distributed.shutdown()
    except Exception:
        pass
    return True


class TpuBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, config: TpuConfig):
        n = len(worker_group)
        coordinator = None    # one worker is its own fabric: no rendezvous
        if n > 1:
            port = config.coordinator_port or worker_group.execute_single(
                0, _find_free_port)
            host = worker_group.execute_single(0, _coordinator_host)
            coordinator = f"{host}:{port}"
        refs = []
        for rank, worker in enumerate(worker_group.workers):
            refs.append(worker.actor.run.remote(
                _init_jax_distributed, coordinator, n, rank,
                dict(config.env_per_worker)))
        import ray_tpu
        infos = ray_tpu.get(refs, timeout=config.init_timeout_s)
        devices = {i["global_devices"] for i in infos}
        if len(devices) != 1:
            raise RuntimeError(
                f"inconsistent global device view across workers: {infos}")

    def on_shutdown(self, worker_group: WorkerGroup, config: TpuConfig):
        try:
            worker_group.execute(_shutdown_jax_distributed)
        except Exception:
            pass


# ------------------------- Torch backend -----------------------------------


@dataclass
class TorchConfig(BackendConfig):
    """torch.distributed process-group fabric (reference:
    train/torch/config.py:155 _TorchBackend; :69 _setup_torch_process_group
    -> dist.init_process_group:113).  Backend "gloo" (CPU; this image ships
    CPU torch — on CUDA hosts "nccl" slots in unchanged)."""

    backend: str = "gloo"
    init_timeout_s: float = 120.0

    def backend_cls(self):
        return TorchBackend


def _init_torch_process_group(master_addr: str, master_port: int,
                              backend: str, rank: int, world_size: int,
                              timeout_s: float):
    import datetime

    import torch.distributed as dist
    if dist.is_initialized():
        dist.destroy_process_group()
    dist.init_process_group(
        backend=backend,
        init_method=f"tcp://{master_addr}:{master_port}",
        rank=rank, world_size=world_size,
        timeout=datetime.timedelta(seconds=timeout_s))
    return {"rank": dist.get_rank(), "world_size": dist.get_world_size()}


def _shutdown_torch_process_group():
    import torch.distributed as dist
    if dist.is_initialized():
        dist.destroy_process_group()
    return True


class TorchBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, config: TorchConfig):
        port = worker_group.execute_single(0, _find_free_port)
        host = worker_group.execute_single(0, _coordinator_host)
        n = len(worker_group)
        import ray_tpu
        refs = [worker.actor.run.remote(
                    _init_torch_process_group, host, port, config.backend,
                    rank, n, config.init_timeout_s)
                for rank, worker in enumerate(worker_group.workers)]
        infos = ray_tpu.get(refs, timeout=config.init_timeout_s)
        if any(i["world_size"] != n for i in infos):
            raise RuntimeError(f"torch process group mismatch: {infos}")

    def on_shutdown(self, worker_group: WorkerGroup, config: TorchConfig):
        try:
            worker_group.execute(_shutdown_torch_process_group)
        except Exception:
            pass
