"""Ring attention: exact attention over sequences sharded on a mesh axis.

No reference counterpart (SURVEY.md §2.5: sequence parallelism ABSENT in
Ray).  TPU-native design: each device holds a contiguous sequence shard of
q/k/v; K/V blocks rotate around the ring with `jax.lax.ppermute` (single-hop
ICI) while each device accumulates its shard's online-softmax state — compute
on block i overlaps the transfer of block i+1, so ICI time hides behind MXU
time for large enough shards.  Wraps to plain flash attention on a 1-device
axis.

Causal masking with sequence shards: device r holds positions
[r*S, (r+1)*S); a KV block that originated at ring slot s is entirely in the
past iff s < r, entirely in the future iff s > r, and diagonal iff s == r.
Past blocks need no mask, future blocks are skipped (their contribution is
fully masked), the diagonal block uses the local causal mask.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import NEG_INF


def _block_attend(q, k, v, scale, mask):
    """One q-shard x kv-block contribution: returns (m, l, acc) partials.
    q [B,Lq,H,D], k/v [B,Lk,H,D]; mask [Lq,Lk] bool or None.  acc stays
    float32 across merges (matches the Pallas kernel's f32 accumulator)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,H,Lq]
    # Guard fully-masked rows (m == NEG_INF) against exp overflow/NaN.
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe[..., None])
    l = jnp.sum(p, axis=-1)                                   # [B,H,Lq]
    acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)      # [B,Lq,H,D]
    return m, l, acc


def _merge(m1, l1, a1, m2, l2, a2):
    """Combine two online-softmax partial states (all f32)."""
    m = jnp.maximum(m1, m2)
    e1 = jnp.exp(m1 - m)
    e2 = jnp.exp(m2 - m)
    l = l1 * e1 + l2 * e2
    # e* are [B,H,Lq]; acc is [B,Lq,H,D] — transpose scale factors.
    s1 = e1.transpose(0, 2, 1)[..., None]
    s2 = e2.transpose(0, 2, 1)[..., None]
    a = a1 * s1 + a2 * s2
    return m, l, a


def ring_attention(q, k, v, *, mesh: Mesh, axis: str = "seq",
                   causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Exact (flash-equivalent) attention with q/k/v sequence-sharded over
    mesh `axis`.  Inputs/outputs are global arrays [B, L, H, D]; sharding of
    the length dim over `axis` is applied via shard_map.
    """
    from ray_tpu.parallel.mesh import mesh_axis_size
    from ray_tpu.parallel.sharding import DEFAULT_RULES

    n_ring = mesh_axis_size(mesh, axis)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if n_ring == 1:
        from ray_tpu.ops.attention import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale)

    # Batch stays sharded over the data axes and heads over tensor — only
    # the length dim participates in the ring (otherwise every DP replica
    # would recompute the full global batch).
    def _mapped(name):
        ax = DEFAULT_RULES.get(name)
        axes = (ax,) if isinstance(ax, str) else tuple(ax or ())
        axes = tuple(a for a in axes if mesh_axis_size(mesh, a) > 1)
        return None if not axes else (axes[0] if len(axes) == 1 else axes)

    spec = P(_mapped("batch"), axis, _mapped("heads"), None)

    def local(qs, ks, vs):
        r = jax.lax.axis_index(axis)
        lq = qs.shape[1]
        causal_mask = jnp.tril(jnp.ones((lq, lq), bool)) if causal else None

        B, _, H, D = qs.shape
        perm = [(i, (i + 1) % n_ring) for i in range(n_ring)]

        # Block 0: the local (diagonal) KV shard — no transfer needed.
        m, l, acc = _block_attend(qs, ks, vs, scale,
                                  causal_mask if causal else None)

        def step(carry, i):
            m, l, acc, kb, vb = carry
            # Rotate first: after i rotations we hold the KV shard that
            # originated at ring slot (r - i) mod n.  Exactly n_ring - 1
            # rotations happen in total (no wasted final hop).
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            src = (r - i) % n_ring
            if causal:
                def past(_):
                    return _block_attend(qs, kb, vb, scale, None)

                def future(_):
                    return (jnp.full_like(m, NEG_INF), jnp.zeros_like(l),
                            jnp.zeros_like(acc))

                bm, bl, ba = jax.lax.cond(src < r, past, future, None)
            else:
                bm, bl, ba = _block_attend(qs, kb, vb, scale, None)
            m, l, acc = _merge(m, l, acc, bm, bl, ba)
            return (m, l, acc, kb, vb), None

        (m, l, acc, _, _), _ = jax.lax.scan(
            step, (m, l, acc, ks, vs), jnp.arange(1, n_ring))
        denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return (acc.astype(jnp.float32) / denom).astype(qs.dtype)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
