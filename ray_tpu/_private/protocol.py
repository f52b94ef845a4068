"""Wire-level task/actor specs and options.

Reference parity: src/ray/common/task/task_spec.h + python/ray/_private/
ray_option_utils.py (option surface) — trimmed to the fields the runtime
uses today; every field name matches the reference concept it mirrors.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

from ray_tpu._private.ids import ActorID, JobID, NodeID, PlacementGroupID, TaskID

# Results smaller than this return inline in the PushTask reply and live in
# the owner's memory store (reference: task returns "in plasma" vs "direct").
INLINE_LIMIT = 100 * 1024


@dataclass
class Resources:
    """Logical resource demand. TPU is first-class (the reference only knows
    GPU; accelerators live in python/ray/util/accelerators/accelerators.py)."""

    cpu: float = 1.0
    tpu: float = 0.0
    memory: float = 0.0
    custom: dict = field(default_factory=dict)

    def __reduce__(self):
        return (Resources, (self.cpu, self.tpu, self.memory, self.custom))

    def to_dict(self) -> dict:
        d = dict(self.custom)
        if self.cpu:
            d["CPU"] = self.cpu
        if self.tpu:
            d["TPU"] = self.tpu
        if self.memory:
            d["memory"] = self.memory
        return d

    @classmethod
    def from_options(cls, opts: dict, default_cpu: float = 1.0) -> "Resources":
        # NB: options default to None (unset), which must mean "default", not
        # zero — otherwise every task demands nothing and admission control
        # stops gating concurrency.
        cpu = opts.get("num_cpus")
        tpu = opts.get("num_tpus")
        mem = opts.get("memory")
        custom = dict(opts.get("resources") or {})
        # accelerator_type targets nodes advertising that hardware
        # (reference: ray_option_utils.py accelerator_type:74 — adds a
        # fractional accelerator_type:<T> resource demand).
        acc = opts.get("accelerator_type")
        if acc:
            custom.setdefault(f"accelerator_type:{acc}", 0.001)
        return cls(
            cpu=default_cpu if cpu is None else float(cpu),
            tpu=0.0 if tpu is None else float(tpu),
            memory=0.0 if mem is None else float(mem),
            custom=custom,
        )


# An argument is either an inline serialized value or an object reference.
@dataclass
class ValueArg:
    data: bytes
    metadata: bytes

    def __reduce__(self):  # tuple-based: ~2x faster than dataclass default
        return (ValueArg, (self.data, self.metadata))


@dataclass
class RefArg:
    id_binary: bytes
    owner_address: str

    def __reduce__(self):
        return (RefArg, (self.id_binary, self.owner_address))


def _mk_taskspec(*fields) -> "TaskSpec":
    """Positional reconstructor for TaskSpec.__reduce__ (pickling a spec
    sits on the per-task hot path on both sides of the wire; a tuple
    avoids the dataclass default's per-field name dict)."""
    s = TaskSpec.__new__(TaskSpec)
    (s.task_id, s.job_id, s.name, s.fn_key, s.args, s.kwargs,
     s.num_returns, s.resources, s.max_retries, s.retry_exceptions,
     s.owner_address, s.actor_id, s.actor_creation, s.method_name,
     s.seq_no, s.max_concurrency, s.placement_group, s.bundle_index,
     s.node_affinity, s.node_affinity_soft, s.scheduling_strategy,
     s.runtime_env, s.trace_ctx) = fields
    return s


@dataclass
class TaskSpec:
    task_id: TaskID
    job_id: JobID
    name: str                     # human-readable function/method name
    fn_key: str                   # GCS KV key of the pickled function/class
    args: list                    # list[ValueArg | RefArg]
    kwargs: dict                  # name -> ValueArg | RefArg
    num_returns: int = 1
    resources: Resources = field(default_factory=Resources)
    max_retries: int = 3
    retry_exceptions: bool = False
    owner_address: str = ""       # RPC address of the submitting worker
    # Actor fields
    actor_id: Optional[ActorID] = None       # set for actor method calls
    actor_creation: bool = False             # this task constructs an actor
    method_name: str = ""
    seq_no: int = 0               # per-handle ordering for actor tasks
    # Execution concurrency for the created actor; 0 = unset, so the worker
    # can apply per-mode defaults (async actors: 1000, sync: 1).  Reference:
    # core_worker/transport/concurrency_group_manager.h + thread_pool.h.
    max_concurrency: int = 0
    # Scheduling hints
    placement_group: Optional[PlacementGroupID] = None
    bundle_index: int = -1
    node_affinity: Optional[NodeID] = None
    node_affinity_soft: bool = True
    scheduling_strategy: str = "DEFAULT"     # DEFAULT | SPREAD
    runtime_env: dict = field(default_factory=dict)
    # Propagated trace context (trace_id, span_id) — injected at submit,
    # extracted at execute (reference: tracing_helper.py:87).
    trace_ctx: Optional[tuple] = None

    def __reduce__(self):
        return (_mk_taskspec, (
            self.task_id, self.job_id, self.name, self.fn_key, self.args,
            self.kwargs, self.num_returns, self.resources,
            self.max_retries, self.retry_exceptions, self.owner_address,
            self.actor_id, self.actor_creation, self.method_name,
            self.seq_no, self.max_concurrency, self.placement_group,
            self.bundle_index, self.node_affinity,
            self.node_affinity_soft, self.scheduling_strategy,
            self.runtime_env, self.trace_ctx))


@dataclass
class ActorInfo:
    """GCS actor-table record (reference: gcs_actor_manager.h state machine)."""

    actor_id: ActorID
    name: str = ""
    namespace: str = "default"
    class_name: str = ""
    state: str = "PENDING"  # PENDING/ALIVE/RESTARTING/DEAD
    address: str = ""       # worker RPC address when ALIVE
    native_port: int = 0    # worker's framed-TCP task plane, 0 = none
    node_id: Optional[NodeID] = None
    owner_address: str = ""
    max_restarts: int = 0
    num_restarts: int = 0
    death_cause: str = ""
    lifetime_detached: bool = False
    creation_spec: Optional[TaskSpec] = None
    resources: Resources = field(default_factory=Resources)
    version: int = 0        # bumped on every state change (client cache inval)


@dataclass
class PlacementGroupInfo:
    """GCS placement-group table record.

    Reference parity: src/ray/gcs/gcs_server/gcs_placement_group_manager.h
    (lifecycle) + gcs_placement_group_scheduler.h (bundle 2PC against
    raylets, node_manager.proto:378 Prepare/CommitBundleResources).
    """

    pg_id: PlacementGroupID
    bundles: list                 # list[dict] resource demand per bundle
    strategy: str = "PACK"        # PACK/SPREAD/STRICT_PACK/STRICT_SPREAD
    name: str = ""
    state: str = "PENDING"        # PENDING/CREATED/RESCHEDULING/REMOVED
    # Per-bundle placement, filled when scheduled (None = unplaced).
    bundle_nodes: list = field(default_factory=list)      # list[NodeID|None]
    bundle_addresses: list = field(default_factory=list)  # list[str]
    creator_job: int = 0
    lifetime_detached: bool = False
    version: int = 0


@dataclass
class NodeInfo:
    node_id: NodeID
    address: str            # hostd RPC address
    store_path: str         # shm segment path (same-host attach)
    hostname: str = ""
    resources_total: dict = field(default_factory=dict)
    resources_available: dict = field(default_factory=dict)
    alive: bool = True
    is_head: bool = False
    # Node incarnation: bumped by the GCS when it fences a node that
    # re-registers after being declared dead (its actors already failed
    # over).  The actor-path incarnation guards key on addresses; this is
    # the node-level analogue, so a healed-but-stale gang can never
    # double-apply an update.  getattr-defensive readers tolerate 0 on
    # records restored from pre-incarnation sqlite tables.
    incarnation: int = 0


def option_defaults(for_actor: bool = False) -> dict:
    """The @remote option surface (reference: _private/ray_option_utils.py)."""
    common = {
        "num_cpus": None, "num_tpus": None, "memory": None, "resources": None,
        "accelerator_type": None,
        "runtime_env": None, "scheduling_strategy": None, "name": None,
        "placement_group": None, "placement_group_bundle_index": -1,
        "_node_id": None,
    }
    if for_actor:
        common.update({
            "max_restarts": 0, "max_task_retries": 0, "lifetime": None,
            "namespace": None, "max_concurrency": None, "get_if_exists": False,
        })
    else:
        common.update({
            "num_returns": 1, "max_retries": 3, "retry_exceptions": False,
        })
    return common


def validate_options(opts: dict, for_actor: bool) -> dict:
    allowed = option_defaults(for_actor)
    merged = dict(allowed)
    for k, v in opts.items():
        if k not in allowed:
            kind = "actor" if for_actor else "task"
            raise ValueError(f"invalid {kind} option {k!r}; allowed: {sorted(allowed)}")
        merged[k] = v
    tpus = merged.get("num_tpus")
    if tpus is not None and tpus != int(tpus):
        raise ValueError(
            f"num_tpus={tpus}: chips are leased whole — a chip belongs to "
            f"one process at a time, so a fraction of one cannot be shared")
    return merged


Any  # keep typing import alive for doc tooling
