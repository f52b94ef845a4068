"""Fused chunked softmax cross-entropy over a large vocabulary.

No reference counterpart (Ray hosts frameworks; the loss lives here).
Computing GPT-2's logits [B, L, V] whole and then a float32 log_softmax
moved some 2.4 GB a direction and was most of a train step on one v5e chip
(PERF.md).  This op holds one chunk of rows' float32 logits [chunk, V] at a
time instead of [T, V]:

- a chunk's logits and their logsumexp come from ONE Mosaic kernel,
  `logits_lse`: the product walks the vocabulary tile by tile and keeps a
  running row maximum and sum of exponentials beside it, so no pass reads
  the logits back to make the logsumexp (PERF.md section 6, PR 58; until
  then XLA's product gave the row maximum and a pass of its own over the
  chunk's 1.236 GB summed the exponentials);
- the target's logit is a row dot with the head's gathered columns, not a
  pass over [chunk, V];
- the custom VJP computes each chunk's logits once: its forward rule makes
  dx and dhead while the chunk's logits are there and keeps them, and the
  backward rule scales them by the cotangent.  A loss without a gradient
  runs the `logits_lse` kernel and the row dot alone;
- dx and dhead of a chunk come from ONE Mosaic kernel, `loss_head_grads`:
  it reads a tile of the float32 logits once, forms `(softmax - onehot) *
  valid / denom` in float32 and casts it once, and feeds both `dx += p @ W`
  and `dhead += p^T @ x`; dhead is summed over the chunks where it lies and
  comes out `[V, D]`, the tied embedding's own order (PERF.md section 6,
  PR 60; until then two XLA products each read the chunk's 1.236 GB of
  logits and each formed `softmax - onehot` in its own prologue, and the
  `[D, V]` sum was turned round for the lookup's scatter-add).

Each kernel takes the call by the operands' shapes (`_lse_plan`,
`_grads_plan`); any other shape takes XLA's product and `logsumexp`, and
XLA's two gradient products: the forms this file had before the kernels
and the tests' references for them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF, _TN, _dot, _interpret_kernels

_LANES = 128
# `logits_lse`'s tiles.  A row tile's `x` stays in VMEM while the head
# streams under it, so the head is read rows / row tile times a chunk (at
# 1,024 rows the kernel waits for it); a vocabulary tile of 384 divides
# GPT-2's padded 50,304 = 128 x 3 x 131 (any other width has a ragged last
# tile, which the kernel masks, and a tile of 1,024 was slower besides).
# `_LSE_SUB_ROWS` rows at a time go through the matrix units and the online
# update.  PERF.md section 6, PR 58 has the sweep.
_LSE_ROW_TILES = (3072, 2048, 1024, 512, 256, 128)
_LSE_COL_TILE = 384
_LSE_SUB_ROWS = 256
_LSE_VMEM_MOST = 64 * 1024 * 1024     # of a v5e core's 128 MiB


def _lse_vmem(tm: int, tn: int, d: int, size: int) -> int:
    """VMEM bytes of a grid step: the pipeline's two buffers of each block
    (x, the head's tile, the logits' tile, the logsumexp's column), the
    statistics, and room for a sub-tile's values."""
    blocks = (tm + tn) * d * size + tm * tn * 4 + tm * _LANES * 4
    return (2 * blocks + 2 * tm * _LANES * 4
            + 8 * min(_LSE_SUB_ROWS, tm) * tn * 4)


def _lse_plan(rows: int, d: int, v: int, size: int = 2):
    """(row tile, vocabulary tile) of `logits_lse` for a chunk of `rows`
    rows of width `d` (`size` bytes a number), or None where the shapes do
    not fit the kernel: the vocabulary a multiple of 128 lanes, the rows a
    multiple of a row tile whose blocks fit `_LSE_VMEM_MOST`."""
    if v % _LANES:
        return None
    tn = min(_LSE_COL_TILE, v)
    return next(((tm, tn) for tm in _LSE_ROW_TILES if rows % tm == 0
                 and _lse_vmem(tm, tn, d, size) <= _LSE_VMEM_MOST), None)


def _logits_lse_kernel(x_ref, w_ref, z_ref, lse_ref, m_ref, l_ref, *,
                       v: int, sub: int):
    """One (row tile, vocabulary tile) grid step.  `m_ref` and `l_ref` hold
    a running maximum and a running sum of exponentials for each row AND
    lane, [tm, 128]: a lane keeps the statistics of the columns that fall
    on it, so a tile's update is elementwise, with no reduction across
    lanes (which would go through the cross-lane unit once a tile), and the
    lanes are combined once, with the last tile."""
    j, last = pl.program_id(1), pl.num_programs(1) - 1
    tm, tn = z_ref.shape

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    def walk(ragged: bool):
        for r in range(tm // sub):
            rows = pl.ds(r * sub, sub)
            z = jax.lax.dot_general(
                x_ref[rows, :], w_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [sub, tn]
            z_ref[rows, :] = z
            if ragged:      # columns past V: out of the maximum and the sum
                col = j * tn + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
                z = jnp.where(col < v, z, NEG_INF)
            parts = [z[:, k:k + _LANES] for k in range(0, tn, _LANES)]
            m_old = m_ref[rows, :]
            m_new = functools.reduce(jnp.maximum, parts, m_old)
            l_ref[rows, :] = (l_ref[rows, :] * jnp.exp(m_old - m_new)
                              + sum(jnp.exp(part - m_new) for part in parts))
            m_ref[rows, :] = m_new

    if v % tn:
        pl.when(j == last)(lambda: walk(True))
        pl.when(j != last)(lambda: walk(False))
    else:
        walk(False)

    @pl.when(j == last)
    def _():
        for r in range(tm // sub):
            rows = pl.ds(r * sub, sub)
            m = m_ref[rows, :]
            top = jnp.max(m, axis=1, keepdims=True)
            total = jnp.sum(l_ref[rows, :] * jnp.exp(m - top), axis=1,
                            keepdims=True)
            lse_ref[rows, :] = top + jnp.log(total)


def logits_lse(x, w):
    """(x @ w.T as float32 [C, V], its row logsumexp [C]) from one Mosaic
    kernel.  `w` is the head as the tied embedding lies, [V, D], contracted
    over D: the compiled step then feeds the kernel the embedding's bf16
    cast as it is, where a `[D, V]` operand cost a transposed copy of it
    (PERF.md section 6, PR 58).  The operands as they are (bf16 in a train
    step), float32 accumulation, float32 statistics over the float32
    logits.  The shapes are ones `_lse_plan` takes."""
    (c, d), v = x.shape, w.shape[0]
    size = jnp.dtype(x.dtype).itemsize
    tm, tn = _lse_plan(c, d, v, size)
    logits, lse = pl.pallas_call(
        functools.partial(_logits_lse_kernel, v=v,
                          sub=min(_LSE_SUB_ROWS, tm)),
        name="logits_lse",
        grid=(c // tm, pl.cdiv(v, tn)),
        in_specs=[pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((tn, d), lambda i, j: (j, 0))],
        out_specs=[pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
                   pl.BlockSpec((tm, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((c, v), jnp.float32),
                   jax.ShapeDtypeStruct((c, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tm, _LANES), jnp.float32),
                        pltpu.VMEM((tm, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(_lse_vmem(tm, tn, d, size),
                                 16 * 1024 * 1024)),
        cost_estimate=pl.CostEstimate(
            flops=2 * c * d * v, transcendentals=c * v,
            bytes_accessed=4 * c * v + size * (c * d + (c // tm) * d * v)),
        interpret=_interpret_kernels(),
    )(x, w)
    return logits, lse[:, 0]


# `loss_head_grads`' tiles.  The vocabulary is the grid's outer axis and the
# rows its inner one: a vocabulary tile's dhead stays in VMEM while the
# chunk's rows pass under it, and the whole chunk's x and float32 dx stay
# there over the call.  Every grid step costs its branches and the rows'
# three columns again, so the row tile is the whole chunk where that fits
# (a tile of 1,024 rows of GPT-2's 6,144 was 3.5% slower); `_GRAD_SUB_ROWS`
# rows at a time are formed and go through the matrix units.  PERF.md
# section 6, PR 60 has the sweep.
_GRAD_ROW_TILES = (6144, 3072, 2048, 1024, 512, 256, 128)
_GRAD_COL_TILE = 384
_GRAD_SUB_ROWS = 512
_GRAD_VMEM_MOST = 96 * 1024 * 1024    # of a v5e core's 128 MiB


def _grads_vmem(c: int, tm: int, tn: int, d: int, size: int) -> int:
    """VMEM bytes of `loss_head_grads`: the chunk's x and dx (one buffer
    each) and dx's float32 sum; the pipeline's two buffers of a logits
    tile, of the head's tile and of dhead's tile in and out; the rows'
    three columns (a lane tile wide each, one buffer where the row tile is
    the chunk); and room for a sub-tile's values."""
    d = -(-d // _LANES) * _LANES          # as VMEM holds a row of it
    whole = c * d * (2 * size + 4)
    columns = 3 * tm * _LANES * 4 * (1 if tm == c else 2)
    blocks = tm * tn * 4 + tn * d * (size + 8)
    return (whole + columns + 2 * blocks
            + 4 * min(_GRAD_SUB_ROWS, tm) * max(tn, d) * 4)


def _grads_plan(rows: int, d: int, v: int, size: int = 2):
    """(row tile, vocabulary tile) of `loss_head_grads` for a chunk of
    `rows` rows of width `d` (`size` bytes a number), or None where the
    shapes do not fit the kernel: the vocabulary a multiple of 128 lanes,
    the rows a multiple of a row tile, and the chunk's x and dx and the
    tiles within `_GRAD_VMEM_MOST`."""
    if v % _LANES:
        return None
    tn = min(_GRAD_COL_TILE, v)
    return next(((tm, tn) for tm in _GRAD_ROW_TILES if rows % tm == 0
                 and _grads_vmem(rows, tm, tn, d, size) <= _GRAD_VMEM_MOST),
                None)


def _loss_head_grads_kernel(z_ref, lse_ref, tgt_ref, scale_ref, x_ref, w_ref,
                            sum_ref, dx_ref, dw_ref, dx_acc, *, v: int,
                            sub: int):
    """One (vocabulary tile, row tile) grid step: `softmax - onehot` of the
    tile's logits, formed once in float32 and cast once, goes into both
    products.  `dx_acc` [C, D] gathers dx over the vocabulary's tiles and
    is cast into `dx_ref` with the last; `dw_ref` starts as the running
    dhead's tile (`sum_ref`, the same HBM) and gathers the row tiles'."""
    j, i = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(0) - 1
    tm, tn = z_ref.shape
    tile = pl.ds(pl.multiple_of(i * tm, tm), tm)

    @pl.when(j == 0)
    def _():
        dx_acc[tile, :] = jnp.zeros((tm, dx_acc.shape[1]), jnp.float32)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = sum_ref[...]

    def walk(ragged: bool):
        w = w_ref[...]
        if ragged:      # the head's rows past V: out of dx
            row = j * tn + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
            w = jnp.where(row < v, w, jnp.zeros_like(w))
        for r in range(tm // sub):
            rows = pl.ds(r * sub, sub)
            of_chunk = pl.ds(pl.multiple_of(i * tm + r * sub, sub), sub)
            z = z_ref[rows, :]
            col = j * tn + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
            # the same arithmetic in the same order as XLA's form below
            p = ((jnp.exp(z - lse_ref[rows, :])
                  - jnp.where(col == tgt_ref[rows, :], 1.0, 0.0))
                 * scale_ref[rows, :])
            if ragged:  # columns past V: out of both products
                p = jnp.where(col < v, p, 0.0)
            p = p.astype(x_ref.dtype)                          # [sub, tn]
            dx_acc[of_chunk, :] += _dot(p, w)                  # [sub, D]
            dw_ref[...] += _dot(p, x_ref[of_chunk, :], _TN)    # [tn, D]

    if v % tn:
        pl.when(j == last)(lambda: walk(True))
        pl.when(j != last)(lambda: walk(False))
    else:
        walk(False)

    @pl.when(j == last)
    def _():
        dx_ref[tile, :] = dx_acc[tile, :].astype(dx_ref.dtype)


def loss_head_grads(logits, lse, targets, scale, x, w, dhead):
    """(dx [C, D] in x's dtype, `dhead` + the chunk's part of it, [V, D]
    float32) from one Mosaic kernel: a tile of the chunk's float32 `logits`
    [C, V] is read once, `(exp(logits - lse) - onehot(targets)) * scale` is
    formed in float32 and cast to x's dtype once, and feeds both
    `dx += p @ w` and `dhead += p.T @ x`, each summed in float32.  `w` is
    the head as the tied embedding lies, [V, D], and so is `dhead`, which
    the call updates where it is.  The shapes are ones `_grads_plan`
    takes."""
    (c, d), v = x.shape, w.shape[0]
    size = jnp.dtype(x.dtype).itemsize
    tm, tn = _grads_plan(c, d, v, size)
    once = dict(pipeline_mode=pl.Buffered(1))   # fetched once: one buffer
    whole = pl.BlockSpec((c, d), lambda j, i: (0, 0), **once)
    column = pl.BlockSpec((tm, 1), lambda j, i: (i, 0),
                          **(once if tm == c else {}))
    of_head = pl.BlockSpec((tn, d), lambda j, i: (j, 0))
    return pl.pallas_call(
        functools.partial(_loss_head_grads_kernel, v=v,
                          sub=min(_GRAD_SUB_ROWS, tm)),
        name="loss_head_grads",
        grid=(pl.cdiv(v, tn), c // tm),
        in_specs=[pl.BlockSpec((tm, tn), lambda j, i: (i, j)),
                  column, column, column, whole, of_head, of_head],
        out_specs=[whole, of_head],
        out_shape=[jax.ShapeDtypeStruct((c, d), x.dtype),
                   jax.ShapeDtypeStruct((v, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((c, d), jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(_grads_vmem(c, tm, tn, d, size),
                                 16 * 1024 * 1024)),
        cost_estimate=pl.CostEstimate(
            flops=4 * c * d * v, transcendentals=c * v,
            bytes_accessed=(4 * c * v + 8 * d * v
                            + size * (2 * c * d + d * v))),
        interpret=_interpret_kernels(),
    )(logits, lse.reshape(c, 1), targets.reshape(c, 1), scale.reshape(c, 1),
      x, w, dhead)


def _chunk_head(x_c, head, t_c):
    """A chunk's float32 logits [C, V], their logsumexp [C] and the
    targets' logits [C]."""
    if _lse_plan(x_c.shape[0], *head.shape, x_c.dtype.itemsize) is not None:
        w = head.T      # a tied head is the embedding turned round: undone
        logits, lse = logits_lse(x_c, w)
        # the targets' rows of it, gathered, and a row dot: the same bf16
        # products in float32 as the kernel's, a [C, D] affair
        tgt = jnp.sum(x_c.astype(jnp.float32)
                      * jnp.take(w, t_c, axis=0, mode="clip"), axis=1)
        return logits, lse, tgt
    # bf16 MXU matmul with fp32 accumulation, never an fp32 matmul (8x
    # slower on the MXU) and no separate [C, V] cast buffer.
    logits = jax.lax.dot(x_c, head, preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    # Row-gather of the target logit as a masked reduction: gathers and
    # scatters on [C, V] do not vectorize on TPU, iota compares do.
    tgt = jnp.sum(jnp.where(_is_target(logits, t_c), logits, 0.0), axis=1)
    return logits, lse, tgt


def _is_target(logits, t_c):
    return jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) == t_c[:, None]


def _chunk(arr, n_chunks):
    t = arr.shape[0]
    c = t // n_chunks
    return arr[: c * n_chunks].reshape((n_chunks, c) + arr.shape[1:])


def _ce_chunks(x, head, targets, valid, n_chunks, with_grads: bool):
    """(loss, dx, dhead): the chunks' walk.  With `with_grads` each chunk's
    logits, while they are there, also give their part of the gradients of
    the LOSS (cotangent 1): dx [T, D] in x's dtype, dhead [D, V] float32,
    from `loss_head_grads` where it takes the chunk and else from two XLA
    products."""
    (t, d), v = x.shape, head.shape[1]
    if t % n_chunks:
        n_chunks = 1
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    targets = targets.astype(jnp.int32)
    kernel = with_grads and _grads_plan(
        t // n_chunks, d, v, x.dtype.itemsize) is not None

    def body(carry, inp):
        total, dhead = carry
        x_c, t_c, v_c = inp
        logits, lse, tgt = _chunk_head(x_c, head, t_c)
        total = total + jnp.sum((lse - tgt) * v_c)
        if not with_grads:
            return (total, dhead), None
        if kernel:      # dhead as the embedding lies, [V, D]
            dx_c, dhead = loss_head_grads(
                logits, lse, t_c, v_c / denom, x_c, head.T.astype(x.dtype),
                dhead)
            return (total, dhead), dx_c
        # dlogits = (softmax - onehot(t)) * valid / denom as ONE fused
        # elementwise chain in each product's prologue: exp, scale, and an
        # iota-mask subtraction (a scatter here would serialize on TPU).
        dlogits = ((jnp.exp(logits - lse[:, None])
                    - jnp.where(_is_target(logits, t_c), 1.0, 0.0))
                   * (v_c / denom)[:, None]).astype(x.dtype)  # [C, V] bf16
        dx_c = jax.lax.dot(dlogits, head.T.astype(x.dtype))   # [C, D]
        # bf16 x bf16 -> fp32 accumulate on the MXU for the head grad.
        dhead = dhead + jax.lax.dot(x_c.T, dlogits,
                                    preferred_element_type=jnp.float32)
        return (total, dhead), dx_c

    dhead0 = jnp.zeros(((v, d) if kernel else (d, v)) if with_grads else (),
                       jnp.float32)
    # With the kernels the chunks are a loop in the compiled step too: laid
    # out in a row, XLA held the chunks' logits side by side (3.6 GB more at
    # GPT-2 small's shapes) and, with no product of its own left in the
    # head, gave VMEM to other values of the whole step (PERF.md section 6,
    # PR 60).  XLA's own products stay in a row, which it fuses across.
    (total, dhead), dxs = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), dhead0),
        (_chunk(x, n_chunks), _chunk(targets, n_chunks),
         _chunk(valid, n_chunks)), unroll=not kernel)
    return (total / denom, dxs.reshape(t, d) if with_grads else None,
            dhead.T if kernel else dhead)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_cross_entropy(x, head, targets, valid, n_chunks: int = 4):
    """Mean masked NLL of `targets` under softmax(x @ head).

    x: [T, D] activations (bf16 ok); head: [D, V]; targets: [T] int;
    valid: [T] float mask.  Returns scalar fp32:
        sum(valid * nll) / max(sum(valid), 1).
    """
    return _ce_chunks(x, head, targets, valid, n_chunks, False)[0]


def _ce_fwd(x, head, targets, valid, n_chunks):
    loss, dx, dhead = _ce_chunks(x, head, targets, valid, n_chunks, True)
    return loss, (dx, dhead.astype(head.dtype))


def _ce_bwd(n_chunks, res, g):
    dx, dhead = (
        (grad.astype(jnp.float32) * g).astype(grad.dtype) for grad in res)
    return dx, dhead, None, None


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
