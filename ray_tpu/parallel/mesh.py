"""Device-mesh construction for multi-dimensional parallelism.

The reference (Ray) has no first-class mesh concept — DP/TP/PP live in the
hosted frameworks (SURVEY.md §2.5, reference release/alpa_tests/).  Here the
mesh IS the first-class object: every parallelism strategy is an axis of one
`jax.sharding.Mesh` and XLA/GSPMD compiles the collectives onto ICI.

Axis vocabulary (MaxText-style, one mesh for the whole program):
  data    — pure data parallelism (batch split, gradients psum over ICI/DCN)
  fsdp    — data parallelism with sharded params/optimizer (ZeRO-3 style;
            params all-gathered per layer, grads reduce-scattered)
  expert  — expert parallelism for MoE layers (experts split across devices,
            tokens routed via all-to-all)
  seq     — sequence/context parallelism (ring attention over this axis)
  tensor  — tensor (megatron) parallelism within attention/mlp blocks
  stage   — pipeline stage axis (used by parallel.pipeline, not by GSPMD)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("data", "fsdp", "expert", "seq", "tensor", "stage")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each parallelism axis; -1 means "absorb remaining devices".

    At most one axis may be -1.  The product of resolved sizes must equal the
    device count.
    """

    data: int = -1
    fsdp: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1
    stage: int = 1

    def resolve(self, n_devices: int) -> dict:
        sizes = {a: getattr(self, a) for a in AXES}
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes ({fixed})")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return sizes


def create_mesh(config: Optional[MeshConfig] = None,
                devices: Optional[Sequence[jax.Device]] = None,
                axis_names: Sequence[str] = AXES) -> Mesh:
    """Build a Mesh over `devices` (default: all) per `config`.

    Device order follows jax.devices(), which JAX arranges so that adjacent
    devices are ICI neighbours on TPU; trailing (fastest-varying) mesh axes
    therefore get the best ICI locality — put `tensor` and `seq` last, which
    the default axis order already does.
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in axis_names)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def create_two_level_mesh(
        ici: Optional[MeshConfig] = None,
        dcn: Optional[MeshConfig] = None,
        n_slices: int = 1,
        devices: Optional[Sequence[jax.Device]] = None,
        axis_names: Sequence[str] = AXES) -> Mesh:
    """Multi-slice (pod-to-pod) mesh: every logical axis is the product
    of a DCN part (across slices) and an ICI part (within a slice), with
    the DCN part slowest-varying — so walking any axis stays inside one
    slice until its ICI block is exhausted (SURVEY §2.5 "DCN collectives
    between slices", §7 P7).

    Lay DP (and optionally FSDP) on the DCN axes and keep TP/SP/EP
    strictly ICI: per-step DCN traffic is then one gradient
    reduce-scatter/all-gather, while the bandwidth-hungry activation
    collectives ride ICI.  XLA lowers a collective over a combined axis
    hierarchically when the device assignment is slice-contiguous (the
    megascale path on real multi-slice jobs; on the CPU simulator the
    topology is emulated but the assignment invariants are identical and
    are what the tests check).

    `devices` are grouped into `n_slices` equal contiguous blocks in
    order — matching jax.devices(), which sorts by (slice_index,
    on-slice coordinates) on real multi-slice TPU.
    """
    devices = list(devices if devices is not None else jax.devices())
    if n_slices <= 0 or len(devices) % n_slices:
        raise ValueError(
            f"{len(devices)} devices not divisible into {n_slices} slices")
    per_slice = len(devices) // n_slices
    ici_sizes = (ici or MeshConfig()).resolve(per_slice)
    dcn_sizes = (dcn or MeshConfig(data=n_slices)).resolve(n_slices)
    for a in axis_names:
        if a in ("tensor", "seq", "expert") and dcn_sizes[a] > 1:
            raise ValueError(
                f"axis {a!r} must stay inside a slice (ICI): per-step "
                f"activation collectives over DCN would dominate the "
                f"step; shard it with the ici config instead")
    n_ax = len(axis_names)
    dev = np.asarray(devices).reshape(
        [dcn_sizes[a] for a in axis_names]
        + [ici_sizes[a] for a in axis_names])
    # Interleave (dcn_a, ici_a) per axis and merge: combined axis a has
    # the DCN part as the high-order digits.
    order = [i for pair in zip(range(n_ax), range(n_ax, 2 * n_ax))
             for i in pair]
    dev = dev.transpose(order).reshape(
        [dcn_sizes[a] * ici_sizes[a] for a in axis_names])
    return Mesh(dev, axis_names)


def slice_index_of(mesh: Mesh, n_slices: int) -> np.ndarray:
    """Map each mesh position to its slice id — the topology oracle the
    tests assert against: moving along an ICI-only axis must never
    change slice.  Real multi-slice TPUs expose device.slice_index; the
    simulator falls back to contiguous id blocks (the grouping
    create_two_level_mesh used)."""
    devs = np.asarray(mesh.devices)
    first = devs.reshape(-1)[0]
    if getattr(first, "slice_index", None) is not None:
        return np.vectorize(lambda d: d.slice_index)(devs)
    per_slice = devs.size // n_slices
    return np.vectorize(lambda d: d.id // per_slice)(devs)


def stage_slice_plan(n_gangs: int, n_slices: int) -> list:
    """Gang -> slice assignment for topology-aware pipeline placement.

    Gangs (pipeline stage-actor groups, `train.pipeline_trainer`) are
    packed into contiguous blocks per slice, so chunk hand-offs between
    gangs inside one block ride ICI and only block boundaries cross DCN
    — the multislice discipline `create_two_level_mesh` encodes for
    GSPMD programs, applied to the MPMD actor pipeline.  With the
    interleaved schedule (gang g owns chunks ``g, g+n_gangs, ...``)
    adjacent chunks are owned by adjacent gangs (mod n_gangs), so a
    contiguous gang block keeps adjacent chunks ICI-near by
    construction.

    Returns a list of length `n_gangs`: plan[g] = slice id.
    """
    if n_slices <= 0:
        raise ValueError(f"n_slices must be positive, got {n_slices}")
    if n_gangs % n_slices:
        raise ValueError(
            f"{n_gangs} gangs not divisible into {n_slices} slices — "
            f"unequal blocks would leave one slice's ICI underused")
    per = n_gangs // n_slices
    return [g // per for g in range(n_gangs)]


def dcn_cut_edges(plan: Sequence[int], n_chunks: int) -> list:
    """Chunk boundaries (c, c+1) whose hand-off crosses a DCN (slice)
    boundary under a gang->slice `plan` with round-robin chunk
    ownership (chunk c is owned by gang ``c % len(plan)``).

    This is the placement quality oracle: the pipeline should be cut at
    as few DCN edges as the slice count forces — ``len(plan)`` gangs in
    ``s`` slices force at least ``s - 1`` cuts per forward pass (plus
    interleave wraparounds), and a contiguous-block plan achieves that
    minimum for v=1."""
    n_gangs = len(plan)
    cuts = []
    for c in range(n_chunks - 1):
        if plan[c % n_gangs] != plan[(c + 1) % n_gangs]:
            cuts.append((c, c + 1))
    return cuts


def pipeline_placement_resources(plan: Sequence[int],
                                 prefix: str = "pp_slice_") -> list:
    """Per-gang custom-resource dicts realizing a `stage_slice_plan`:
    gang g's placement-group bundles demand ``{prefix}{plan[g]}: 1`` so
    its actors can only land on nodes advertising that slice resource
    (nodes declare e.g. ``resources={"pp_slice_0": 4}`` at start).
    Feed the result to ``PipelineTrainer(placement_plan=...)``."""
    return [{f"{prefix}{s}": 1} for s in plan]


def single_device_mesh() -> Mesh:
    """A 1-chip mesh with all axes size 1 — lets one jitted program serve
    both single-chip and pod runs without branching."""
    return create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)
