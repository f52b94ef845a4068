"""Fused chunked softmax cross-entropy over a large vocabulary.

No reference counterpart (Ray hosts frameworks; the loss lives here).
Motivation, measured on one v5e chip (PERF.md): computing GPT-2 logits
[B,L,V] then fp32 log_softmax materializes ~2.4GB of HBM traffic per
direction and ran the lm-head at ~10% MFU — ~100ms of a 130ms train step.

This op never materializes the full [T, V] logits: it scans over row
chunks, computing chunk logits -> logsumexp -> target gather on the fly,
and the custom VJP recomputes chunk logits in the backward (flash-attention
-style recompute, here for the classifier head).  Peak extra memory is one
[chunk, V] block instead of [T, V].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_cross_entropy(x, head, targets, valid, n_chunks: int = 4):
    """Mean masked NLL of `targets` under softmax(x @ head).

    x: [T, D] activations (bf16 ok); head: [D, V]; targets: [T] int;
    valid: [T] float mask.  Returns scalar fp32:
        sum(valid * nll) / max(sum(valid), 1).
    """
    loss, _ = _ce_fwd_impl(x, head, targets, valid, n_chunks)
    return loss


def _chunk(arr, n_chunks):
    t = arr.shape[0]
    c = t // n_chunks
    return arr[: c * n_chunks].reshape((n_chunks, c) + arr.shape[1:])


def _ce_fwd_impl(x, head, targets, valid, n_chunks):
    t = x.shape[0]
    if t % n_chunks:
        n_chunks = 1
    xs = _chunk(x, n_chunks)
    ts = _chunk(targets, n_chunks)
    vs = _chunk(valid, n_chunks)

    def body(acc, inp):
        x_c, t_c, v_c = inp
        # bf16 MXU matmul with fp32 accumulation — never an fp32 matmul
        # (8x slower on the MXU) and no separate [C, V] cast buffer.
        logits = jax.lax.dot(x_c, head,
                             preferred_element_type=jnp.float32)  # [C, V]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        # Row-gather of the target logit as a masked reduction — gathers/
        # scatters on [C, V] do not vectorize on TPU, iota compares do.
        iota_v = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        tgt = jnp.sum(jnp.where(iota_v == t_c[:, None].astype(jnp.int32),
                                logits, 0.0), axis=1)
        return acc + jnp.sum((lse - tgt) * v_c), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts, vs),
                            unroll=True)
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    return total / denom, denom


def _ce_fwd(x, head, targets, valid, n_chunks):
    loss, denom = _ce_fwd_impl(x, head, targets, valid, n_chunks)
    return loss, (x, head, targets, valid, denom)


def _ce_bwd(n_chunks, res, g):
    x, head, targets, valid, denom = res
    t, d = x.shape
    v = head.shape[1]
    nc = n_chunks if t % n_chunks == 0 else 1
    xs = _chunk(x, nc)
    ts = _chunk(targets, nc)
    vs = _chunk(valid, nc)
    scale = (g / denom).astype(jnp.float32)

    c = xs.shape[1]

    def body(dhead_acc, inp):
        x_c, t_c, v_c = inp
        logits = jax.lax.dot(x_c, head,
                             preferred_element_type=jnp.float32)  # [C, V]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        sv = v_c * scale                                  # [C]
        # dlogits = (softmax - onehot(t)) * sv as ONE fused elementwise
        # chain: exp, scale, and an iota-mask subtraction (a scatter here
        # would serialize on TPU).
        iota_v = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        is_tgt = iota_v == t_c[:, None].astype(jnp.int32)
        dlogits = ((jnp.exp(logits - lse[:, None])
                    - jnp.where(is_tgt, 1.0, 0.0))
                   * sv[:, None]).astype(x.dtype)         # [C, V] bf16
        dx_c = jax.lax.dot(dlogits, head.T.astype(x.dtype))   # [C, D]
        # bf16 x bf16 -> fp32 accumulate on the MXU for the head grad.
        dhead_acc = dhead_acc + jax.lax.dot(
            x_c.T, dlogits, preferred_element_type=jnp.float32)
        return dhead_acc, dx_c

    dhead, dxs = jax.lax.scan(
        body, jnp.zeros((d, v), jnp.float32), (xs, ts, vs), unroll=True)
    dx = dxs.reshape(t, d)
    return dx, dhead.astype(head.dtype), None, None


fused_cross_entropy.defvjp(_ce_fwd, _ce_bwd)


# ---------------------------------------------------------------------------
# SPMD variant: shard_map over the mesh, vocab-sharded logsumexp
# ---------------------------------------------------------------------------

ROW_AXES = ("data", "fsdp", "seq")   # mesh axes that shard rows (tokens)
VOCAB_AXIS = "tensor"                # mesh axis that shards the vocab dim


def spmd_ce_applicable(mesh, vocab: int, batch: int, length: int) -> bool:
    """The shard_map CE path needs the sharded dims to divide evenly."""
    if mesh is None:
        return False
    t = mesh.shape.get(VOCAB_AXIS, 1)
    rows = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
    seq = mesh.shape.get("seq", 1)
    return vocab % t == 0 and batch % rows == 0 and length % seq == 0


def _spmd_rows(x_l, t_l, v_l, n_chunks):
    d = x_l.shape[-1]
    x2 = x_l.reshape(-1, d)
    t2 = t_l.reshape(-1).astype(jnp.int32)
    v2 = v_l.reshape(-1)
    nc = n_chunks if x2.shape[0] % n_chunks == 0 else 1
    return x2, t2, v2, nc


def _spmd_lse_tgt(logits, t_c, offset):
    """Vocab-sharded logsumexp + target-logit via psum over the tensor
    axis (max-shifted for stability)."""
    m = jax.lax.pmax(jnp.max(logits, axis=-1), VOCAB_AXIS)
    s = jax.lax.psum(
        jnp.sum(jnp.exp(logits - m[:, None]), axis=-1), VOCAB_AXIS)
    lse = m + jnp.log(s)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + offset
    tgt = jax.lax.psum(
        jnp.sum(jnp.where(iota == t_c[:, None], logits, 0.0), axis=1),
        VOCAB_AXIS)
    return lse, tgt, iota


def _vshard(mesh, head):
    return head.shape[1] // max(mesh.shape.get(VOCAB_AXIS, 1), 1)


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_cross_entropy_spmd(x, head, targets, valid, mesh,
                             n_chunks: int = 4):
    """Mesh-parallel fused CE: never materializes [T, V] logits on ANY
    chip.  Rows (batch x length) shard over (data, fsdp, seq); the vocab
    dim of `head` shards over the tensor axis, with the logsumexp, target
    gather, and dx reduced across vocab shards by explicit psum/pmax —
    the distributed form of the chunked custom-VJP above.

    The custom VJP wraps AROUND the shard_map calls (fwd and bwd are each
    a forward-only shard_map), so shard_map's transpose semantics never
    enter the picture — every cross-shard reduction is an explicit
    collective in this file.

    x: [B, L, D]; head: [D, V]; targets/valid: [B, L].  Returns a
    replicated fp32 scalar.  Gradients flow to x and head only.
    """
    loss, _ = _spmd_fwd_call(x, head, targets, valid, mesh, n_chunks)
    return loss


def _spmd_fwd_call(x, head, targets, valid, mesh, n_chunks):
    from jax.sharding import PartitionSpec as P

    vshard = _vshard(mesh, head)

    def fwd_impl(x_l, head_l, t_l, v_l):
        x2, t2, v2, nc = _spmd_rows(x_l, t_l, v_l, n_chunks)
        offset = jax.lax.axis_index(VOCAB_AXIS) * vshard

        def body(acc, inp):
            x_c, t_c, v_c = inp
            logits = jax.lax.dot(x_c, head_l,
                                 preferred_element_type=jnp.float32)
            lse, tgt, _ = _spmd_lse_tgt(logits, t_c, offset)
            return acc + jnp.sum((lse - tgt) * v_c), None

        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32),
            (_chunk(x2, nc), _chunk(t2, nc), _chunk(v2, nc)), unroll=True)
        total = jax.lax.psum(total, ROW_AXES + (VOCAB_AXIS,)) \
            / mesh.shape.get(VOCAB_AXIS, 1)
        denom = jnp.maximum(
            jax.lax.psum(jnp.sum(v2), ROW_AXES), 1.0)
        return total / denom, denom

    return jax.shard_map(
        fwd_impl, mesh=mesh,
        in_specs=(P(("data", "fsdp"), "seq", None), P(None, VOCAB_AXIS),
                  P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq")),
        out_specs=(P(), P()), check_vma=False,
    )(x, head, targets, valid)


def _ce_spmd_fwd(x, head, targets, valid, mesh, n_chunks):
    loss, denom = _spmd_fwd_call(x, head, targets, valid, mesh, n_chunks)
    return loss, (x, head, targets, valid, denom)


def _ce_spmd_bwd(mesh, n_chunks, res, g):
    from jax.sharding import PartitionSpec as P

    x, head, targets, valid, denom = res
    vshard = _vshard(mesh, head)
    scale_g = (g / denom).astype(jnp.float32)

    def bwd_impl(x_l, head_l, t_l, v_l, scale):
        x2, t2, v2, nc = _spmd_rows(x_l, t_l, v_l, n_chunks)
        d = x2.shape[1]
        offset = jax.lax.axis_index(VOCAB_AXIS) * vshard

        def body(dhead_acc, inp):
            x_c, t_c, v_c = inp
            logits = jax.lax.dot(x_c, head_l,
                                 preferred_element_type=jnp.float32)
            lse, _, iota = _spmd_lse_tgt(logits, t_c, offset)
            sv = v_c * scale
            dlogits = ((jnp.exp(logits - lse[:, None])
                        - jnp.where(iota == t_c[:, None], 1.0, 0.0))
                       * sv[:, None]).astype(x_l.dtype)
            # Partial over this vocab shard's columns; the tensor-axis
            # psum (once, after the scan) completes dx.
            dx_c = jax.lax.dot(dlogits, head_l.T.astype(x_l.dtype))
            dhead_acc = dhead_acc + jax.lax.dot(
                x_c.T, dlogits, preferred_element_type=jnp.float32)
            return dhead_acc, dx_c

        dhead_l, dxs = jax.lax.scan(
            body, jnp.zeros((d, head_l.shape[1]), jnp.float32),
            (_chunk(x2, nc), _chunk(t2, nc), _chunk(v2, nc)), unroll=True)
        dx_l = jax.lax.psum(dxs.reshape(x_l.shape), VOCAB_AXIS)
        # Rows are disjoint across (data, fsdp, seq): psum completes the
        # row-sum, leaving dhead replicated there and vocab-sharded.
        dhead_l = jax.lax.psum(dhead_l, ROW_AXES).astype(head_l.dtype)
        return dx_l, dhead_l

    dx, dhead = jax.shard_map(
        bwd_impl, mesh=mesh,
        in_specs=(P(("data", "fsdp"), "seq", None), P(None, VOCAB_AXIS),
                  P(("data", "fsdp"), "seq"), P(("data", "fsdp"), "seq"),
                  P()),
        out_specs=(P(("data", "fsdp"), "seq", None), P(None, VOCAB_AXIS)),
        check_vma=False,
    )(x, head, targets, valid, scale_g)
    return dx, dhead, None, None


fused_cross_entropy_spmd.defvjp(_ce_spmd_fwd, _ce_spmd_bwd)
