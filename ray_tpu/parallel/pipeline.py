"""Pipeline parallelism: GPipe-style microbatch rotation over a mesh axis.

No reference counterpart — Ray hosts frameworks that do PP externally
(SURVEY.md §2.5 lists PP as "NO first-class").  TPU-native design: the
`stage` mesh axis holds one pipeline stage per device group; microbatches
circulate stage-to-stage with `jax.lax.ppermute` (a single-hop ICI transfer),
and the whole schedule is one `lax.scan` inside `shard_map`, so XLA overlaps
the permute with each stage's compute.

Layout convention: stage-local layer parameters are stacked on a leading
"stage" dim of every param leaf; inputs arrive with microbatches on a leading
dim of size `n_micro` and are fed one per scan step.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   mesh: Mesh,
                   stage_params: Any,
                   microbatches: jax.Array,
                   axis: str = "stage") -> jax.Array:
    """Run `stage_fn(params_for_stage, x) -> y` as a pipeline over mesh
    `axis`.

    Args:
      stage_fn: computes one stage on one microbatch (same shape in/out).
      stage_params: pytree whose leaves have leading dim = n_stages (sharded
        over `axis`).
      microbatches: [n_micro, micro_batch, ...] input, replicated over
        `axis` (only stage 0 consumes it; replication keeps the shard_map
        specs simple and the input small relative to activations).

    Returns [n_micro, micro_batch, ...] output from the final stage,
    replicated over `axis`.
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    total_steps = n_micro + n_stages - 1

    param_spec = P(axis)
    # Microbatch stream: replicated over the stage axis, but the per-
    # microbatch batch dim stays sharded over the data axes (each data
    # slice pipelines its own batch shard; P() here would make every
    # slice redundantly compute the global batch).
    from ray_tpu.parallel.mesh import mesh_axis_size
    batch_axes = tuple(a for a in ("data", "fsdp")
                       if mesh_axis_size(mesh, a) > 1)
    io_spec = P(None, batch_axes if batch_axes else None)

    def per_stage(params, mb):
        # Inside shard_map: params leaves have leading dim 1 (this stage's
        # slice); mb is the full [n_micro, ...] stream.
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)

        state = jnp.zeros_like(mb[0])          # activation held by this stage
        outputs = jnp.zeros_like(mb)

        def step(carry, t):
            state, outputs = carry
            # Stage 0 ingests microbatch t (when still available).
            feed = mb[jnp.minimum(t, n_micro - 1)]
            x = jnp.where(stage == 0, feed, state)
            y = stage_fn(params, x)
            # Rotate: stage i -> i+1 (last stage's output is collected).
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # Last stage finishes microbatch (t - (n_stages-1)) at step t.
            out_idx = t - (n_stages - 1)
            valid = (stage == n_stages - 1) & (out_idx >= 0)
            outputs = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.maximum(out_idx, 0)].set(y),
                lambda o: o,
                outputs)
            return (nxt, outputs), None

        (state, outputs), _ = jax.lax.scan(
            step, (state, outputs), jnp.arange(total_steps))
        # Replicate the final outputs (held only by the last stage) to all
        # stages: zero elsewhere, then psum — callers can apply loss anywhere.
        outputs = jnp.where(stage == n_stages - 1, outputs,
                            jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: param_spec, stage_params,
                               is_leaf=lambda x: x is None),
                  io_spec),
        out_specs=io_spec, check_vma=False)
    return fn(stage_params, microbatches)


def chunk_assignment(n_chunks: int, n_gangs: int) -> list:
    """Round-robin chunk ownership for the interleaved (looping) MPMD
    schedule: gang g owns chunks ``g, g+n_gangs, ...`` — non-adjacent by
    construction, so every gang has work during warmup/drain and the
    pipeline bubble shrinks ~1/v for ``v = n_chunks // n_gangs`` chunks
    per gang.  Shared between the MPMD trainer and tests so the dryrun
    parity checks assert against the exact ownership the trainer uses.

    Returns a list of length `n_gangs`: assignment[g] = sorted chunk ids.
    """
    if n_gangs <= 0 or n_chunks % n_gangs:
        raise ValueError(
            f"{n_chunks} chunks not divisible across {n_gangs} gangs")
    return [list(range(g, n_chunks, n_gangs)) for g in range(n_gangs)]


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack a list of per-stage param pytrees along a new leading dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def pipeline_loss_dryrun(stage_fn: Callable, loss_fn: Callable,
                         mesh: Mesh, stage_params: Any,
                         microbatches: jax.Array, targets: jax.Array,
                         axis: str = "stage") -> jax.Array:
    """Mean microbatch loss of the single-program GPipe dryrun — the
    reference value the MPMD trainer (train/pipeline_trainer.py) must
    match to fp tolerance on the same schedule (the standing parity
    gate, tests/test_pipeline_mpmd.py).

    `loss_fn(y, target) -> scalar` is applied per microbatch to the
    final stage's outputs; `targets` has the same [n_micro, ...] leading
    layout as `microbatches`."""
    outputs = pipeline_apply(stage_fn, mesh, stage_params, microbatches,
                             axis=axis)
    losses = jax.vmap(loss_fn)(outputs, targets)
    return jnp.mean(losses)
