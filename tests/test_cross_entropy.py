"""The one-chip loss head (`ray_tpu/ops/cross_entropy.py`): the `logits_lse`
kernel under the Pallas interpreter against XLA's product and
`jax.scipy.special.logsumexp`, the `loss_head_grads` kernel against the two
XLA products it replaces, then `fused_cross_entropy`'s value and both
gradients against a float32 `log_softmax` on the kernels' path and on the
fallback's.  What the chip's compiler makes of the kernel is
`tests/test_tpu_aot.py`'s; its speed is `scripts/loss_head_time.py`'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import cross_entropy as ce


def _operands(rows, d, v, dtype, seed=0):
    kx, kw = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (rows, d), jnp.float32).astype(dtype)
    w = (0.3 * jax.random.normal(kw, (v, d), jnp.float32)).astype(dtype)
    return x, w


# rows, width, vocabulary: the tiles `_lse_plan` gives them
LSE_SHAPES = {
    "one_tile_each_way": (128, 128, 384, (128, 384)),
    "an_even_vocabulary_of_three_tiles": (128, 64, 1152, (128, 384)),
    "a_ragged_last_tile": (128, 128, 512, (128, 384)),
    "a_vocabulary_under_one_tile": (256, 128, 256, (256, 256)),
    "several_row_tiles": (384, 128, 640, (128, 384)),
    "two_sub_tiles_a_row_tile": (512, 128, 896, (512, 384)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", list(LSE_SHAPES))
def test_logits_lse_gives_the_product_and_its_logsumexp(shape, dtype):
    rows, d, v, plan = LSE_SHAPES[shape]
    assert ce._lse_plan(rows, d, v) == plan
    x, w = _operands(rows, d, v, dtype)
    logits, lse = jax.jit(ce.logits_lse)(x, w)
    want = jax.lax.dot(x, w.T, preferred_element_type=jnp.float32)
    assert logits.dtype == lse.dtype == jnp.float32
    assert logits.shape == (rows, v) and lse.shape == (rows,)
    # the same products, float32 sums in another order
    np.testing.assert_allclose(logits, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        lse, jax.scipy.special.logsumexp(logits, axis=-1), rtol=2e-6,
        atol=2e-6)


@pytest.mark.parametrize("step", [80.0, 120.0, -80.0, -120.0],
                         ids=lambda s: f"a_tile_{s:+.0f}")
def test_a_maximum_that_moves_tile_after_tile_neither_overflows_nor_forgets(
        step):
    """Row 0's logits rise (or fall) by `step` from one vocabulary tile to
    the next over four tiles, the last ragged: the rescale `exp(m - m')`
    of a running sum underflows to 0 without a NaN when they rise, and the
    first tile's sum is still the answer when they fall.  The other rows
    are small and random."""
    rows, d, v = 128, 128, 1280
    x, w = _operands(rows, d, v, jnp.float32)
    tile = jnp.arange(v) // ce._LSE_COL_TILE
    x = x.at[0].set(0.0).at[0, 0].set(1.0)
    w = w.at[:, 0].set(step * tile + 0.01 * jnp.cos(jnp.arange(v)))
    logits, lse = jax.jit(ce.logits_lse)(x, w)
    assert abs(float(logits[0, -1] - logits[0, 0])) > 3 * abs(step) - 1
    assert np.isfinite(np.asarray(lse)).all()
    np.testing.assert_allclose(
        lse, jax.scipy.special.logsumexp(logits, axis=-1), rtol=1e-6,
        atol=1e-5)
    # the tile that holds the maximum decides: its own logsumexp, to e-80
    top = 3 if step > 0 else 0
    np.testing.assert_allclose(
        lse[0], jax.scipy.special.logsumexp(logits[0][tile == top]),
        rtol=1e-6)


def _reference_loss(x, head, targets, valid):
    logp = jax.nn.log_softmax(
        x.astype(jnp.float32) @ head.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)


# rows, width, vocabulary, dtype of x and the head, chunks, share of rows
# that count, whether the kernel takes the chunks
LOSS_CASES = {
    "kernel": (512, 128, 512, jnp.float32, 4, 1.0, True),
    "kernel_masked_rows": (512, 128, 640, jnp.float32, 4, 0.6, True),
    "kernel_all_rows_masked": (512, 128, 512, jnp.float32, 4, 0.0, True),
    "kernel_bf16": (1024, 128, 768, jnp.bfloat16, 4, 0.8, True),
    "kernel_chunks_that_do_not_divide_the_rows": (
        384, 128, 512, jnp.float32, 5, 0.7, True),
    "kernel_one_chunk": (256, 64, 384, jnp.float32, 1, 1.0, True),
    "fallback_vocabulary_of_100": (512, 128, 100, jnp.float32, 4, 1.0,
                                   False),
    "fallback_masked_rows": (512, 128, 100, jnp.float32, 4, 0.6, False),
    "fallback_all_rows_masked": (512, 128, 100, jnp.float32, 4, 0.0, False),
    "fallback_bf16": (512, 128, 100, jnp.bfloat16, 4, 0.8, False),
    "fallback_rows_no_tile_divides": (200, 128, 512, jnp.float32, 4, 0.7,
                                      False),
    "fallback_chunks_that_do_not_divide_the_rows": (
        250, 64, 100, jnp.float32, 4, 0.7, False),
}


@pytest.mark.parametrize("case", list(LOSS_CASES))
def test_fused_cross_entropy_against_a_float32_log_softmax(case):
    rows, d, v, dtype, n_chunks, kept, kernel = LOSS_CASES[case]
    x, w = _operands(rows, d, v, dtype, seed=1)
    head = w.T
    kt, kv = jax.random.split(jax.random.key(2))
    targets = jax.random.randint(kt, (rows,), 0, v)
    valid = (jax.random.uniform(kv, (rows,)) < kept).astype(jnp.float32)
    chunk = rows // n_chunks if rows % n_chunks == 0 else rows
    assert (ce._lse_plan(chunk, d, v) is not None) == kernel

    def loss(x, head):
        return ce.fused_cross_entropy(x, head, targets, valid, n_chunks)

    calls = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        x, head)).count("logits_lse")
    # one call in the body of the chunks' one loop: a chunk's logits are
    # made once, for the value and the gradients
    assert calls == (1 if kernel else 0)

    got, (dx, dhead) = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
        x, head)
    want, (rx, rhead) = jax.value_and_grad(_reference_loss, argnums=(0, 1))(
        x, head, targets, valid)
    assert dx.dtype == x.dtype and dhead.dtype == head.dtype
    np.testing.assert_allclose(got, want, rtol=2e-6 if dtype == jnp.float32
                               else 1e-5)
    # bf16: `softmax - onehot` is rounded to bf16 before its two products
    rel = 1e-5 if dtype == jnp.float32 else 2e-2
    for grad, ref in ((dx, rx), (dhead, rhead)):
        ref = np.asarray(ref, np.float32)
        np.testing.assert_allclose(np.asarray(grad, np.float32), ref,
                                   atol=rel * max(np.abs(ref).max(), 1e-6))
    # the value alone: the same number from the kernel and nothing else
    alone = jax.jit(loss)(x, head)
    assert float(alone) == float(got)
    if kept == 0.0:
        assert float(got) == 0.0 and not np.asarray(dx, np.float32).any()


@pytest.mark.parametrize("path", ["kernel", "fallback"])
def test_the_cotangent_scales_both_gradients(path):
    rows, d, v = 256, 64, 384 if path == "kernel" else 100
    x, w = _operands(rows, d, v, jnp.float32, seed=3)
    targets = jax.random.randint(jax.random.key(4), (rows,), 0, v)
    valid = jnp.ones((rows,), jnp.float32)

    def loss(x, head, by):
        return by * ce.fused_cross_entropy(x, head, targets, valid, 2)

    one = jax.grad(loss, argnums=(0, 1))(x, w.T, 1.0)
    three = jax.grad(loss, argnums=(0, 1))(x, w.T, 3.0)
    for a, b in zip(one, three):
        np.testing.assert_allclose(3.0 * a, b, rtol=1e-6, atol=1e-9)


def test_a_target_outside_the_vocabulary_on_a_masked_row_is_no_nan():
    """An ignore index (-100) on a row the mask drops: the gathered row is
    clipped, not filled with NaN, and the row adds nothing."""
    rows, d, v = 128, 64, 384
    x, w = _operands(rows, d, v, jnp.float32, seed=5)
    targets = jax.random.randint(jax.random.key(6), (rows,), 0, v)
    valid = jnp.ones((rows,), jnp.float32).at[:7].set(0.0)
    marked = targets.at[:7].set(-100)
    a = ce.fused_cross_entropy(x, w.T, targets, valid, 1)
    b, grads = jax.value_and_grad(ce.fused_cross_entropy, argnums=(0, 1))(
        x, w.T, marked, valid, 1)
    assert float(a) == float(b)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


# ---------------------------------------------------------------------------
# `loss_head_grads`: dx and dhead of a chunk from one read of its logits
# ---------------------------------------------------------------------------

def _xla_grads(logits, lse, targets, scale, x, w, dhead):
    """The two products `_ce_chunks` makes where the kernel does not take
    the chunk: `softmax - onehot` formed in float32, cast to x's dtype, and
    multiplied with the head and with x; dhead here as the kernel gives it,
    [V, D]."""
    p = ((jnp.exp(logits - lse[:, None])
          - jnp.where(ce._is_target(logits, targets), 1.0, 0.0))
         * scale[:, None]).astype(x.dtype)
    dx = jax.lax.dot(p, w)
    return dx, dhead + jax.lax.dot(p.T, x, preferred_element_type=jnp.float32)


# rows, width, vocabulary: the tiles `_grads_plan` gives them
GRADS_SHAPES = {
    "one_tile_each_way": (128, 128, 384, (128, 384)),
    "an_even_vocabulary_of_three_tiles": (128, 64, 1152, (128, 384)),
    "a_ragged_last_tile": (128, 128, 512, (128, 384)),
    "a_vocabulary_under_one_tile": (256, 128, 256, (256, 256)),
    "several_row_tiles": (384, 128, 640, (128, 384)),
    "several_row_tiles_of_two_sub_tiles": (2560, 64, 512, (512, 384)),
    "two_sub_tiles_a_row_tile": (1024, 128, 896, (1024, 384)),
    "a_width_of_one_block_and_a_half": (256, 192, 384, (256, 384)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", list(GRADS_SHAPES))
def test_loss_head_grads_gives_the_two_products_it_replaces(
        shape, dtype, monkeypatch):
    """Rows 0-6 are masked (scale 0) and carry an ignore index, the running
    dhead is not zero, and the scale is no power of two."""
    rows, d, v, plan = GRADS_SHAPES[shape]
    if rows == 2560:        # sub-tiles of 256: two a row tile of 512
        monkeypatch.setattr(ce, "_GRAD_SUB_ROWS", 256)
    assert ce._grads_plan(rows, d, v, jnp.dtype(dtype).itemsize) == plan
    x, w = _operands(rows, d, v, dtype, seed=7)
    kt, kd = jax.random.split(jax.random.key(8))
    targets = jax.random.randint(kt, (rows,), 0, v).at[:7].set(-100)
    scale = jnp.full((rows,), 1.0 / 3.0, jnp.float32).at[:7].set(0.0)
    dhead = jax.random.normal(kd, (v, d), jnp.float32)
    logits = jax.lax.dot(x, w.T, preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    args = (logits, lse, targets, scale, x, w, dhead)
    dx, total = jax.jit(ce.loss_head_grads)(*args)
    want_dx, want_total = jax.jit(_xla_grads)(*args)
    assert dx.dtype == x.dtype and dx.shape == x.shape
    assert total.dtype == jnp.float32 and total.shape == (v, d)
    assert not np.asarray(dx[:7], np.float32).any()
    # the same `softmax - onehot`, number for number: float32 sums of the
    # same products in another order, and dx rounded once, to x's dtype
    want_dx = np.asarray(want_dx, np.float32)
    np.testing.assert_allclose(
        np.asarray(dx, np.float32), want_dx,
        atol=(1e-5 if dtype == jnp.float32 else 2e-2) * np.abs(want_dx).max())
    np.testing.assert_allclose(total, want_total, rtol=1e-5, atol=1e-5)
    part = want_total - dhead
    np.testing.assert_allclose(total - dhead, part,
                               atol=1e-5 * np.abs(part).max())


def test_the_gradients_plan_by_shape():
    # `train_gpt2s_1chip`'s chunk: the whole chunk a row tile
    assert ce._grads_plan(6144, 768, 50304) == (6144, 384)
    # gpt2-xl's width, twelve and a half blocks of 128
    assert ce._grads_plan(1024, 1600, 50304) == (1024, 384)
    # a chunk whose x, dx and float32 dx do not fit beside the tiles
    assert ce._grads_plan(24576, 768, 50304) is None
    assert ce._grads_plan(12288, 768, 50304) == (2048, 384)
    assert ce._grads_plan(12288, 768, 50304, 4) is None
    # a vocabulary that is no multiple of 128, rows no tile divides
    assert ce._grads_plan(512, 128, 100) is None
    assert ce._grads_plan(200, 128, 512) is None
    assert all(ce._grads_vmem(6144, tm, 384, 768, 2) <= ce._GRAD_VMEM_MOST
               for tm in (6144, 3072, 1024))


# rows, width, vocabulary, dtype, chunks, share of rows that count, the
# cotangent
GRADS_CASES = {
    "all_rows": (512, 128, 512, jnp.float32, 4, 1.0, 1.0),
    "masked_rows_and_a_ragged_tile": (512, 128, 640, jnp.float32, 4, 0.6,
                                      1.0),
    "all_rows_masked": (512, 128, 512, jnp.float32, 4, 0.0, 1.0),
    "bf16": (1024, 128, 768, jnp.bfloat16, 4, 0.8, 1.0),
    "bf16_a_cotangent_of_three": (512, 128, 768, jnp.bfloat16, 2, 0.8, 3.0),
    "a_cotangent_of_three": (256, 64, 384, jnp.float32, 2, 1.0, 3.0),
    "one_chunk": (256, 64, 384, jnp.float32, 1, 0.9, 1.0),
    "chunks_that_do_not_divide_the_rows": (384, 128, 512, jnp.float32, 5,
                                           0.7, -0.5),
}


def _loss_and_grads(x, head, targets, valid, n_chunks, by):
    def loss(x, head):
        return by * ce.fused_cross_entropy(x, head, targets, valid, n_chunks)
    fn = jax.value_and_grad(loss, argnums=(0, 1))
    return str(jax.make_jaxpr(fn)(x, head)), jax.jit(fn)(x, head)


@pytest.mark.parametrize("case", list(GRADS_CASES))
def test_the_kernels_gradients_are_the_two_xla_products(case, monkeypatch):
    """`fused_cross_entropy` with `loss_head_grads` a chunk against the
    same call with the plan refusing every shape (the two XLA products, as
    before the kernel): the same value, dx and dhead; the masked rows carry
    an ignore index."""
    rows, d, v, dtype, n_chunks, kept, by = GRADS_CASES[case]
    x, w = _operands(rows, d, v, dtype, seed=9)
    kt, kv = jax.random.split(jax.random.key(10))
    valid = (jax.random.uniform(kv, (rows,)) < kept).astype(jnp.float32)
    targets = jnp.where(valid > 0, jax.random.randint(kt, (rows,), 0, v),
                        -100)
    text, (got, (dx, dhead)) = _loss_and_grads(x, w.T, targets, valid,
                                               n_chunks, by)
    # one call of each kernel in the body of the chunks' one loop
    assert text.count("loss_head_grads") == text.count("logits_lse") == 1
    monkeypatch.setattr(ce, "_grads_plan", lambda *shape: None)
    text, (want, (rx, rhead)) = _loss_and_grads(x, w.T, targets, valid,
                                                n_chunks, by)
    assert "loss_head_grads" not in text and text.count("logits_lse") == 1
    assert float(got) == float(want)
    assert dx.dtype == rx.dtype == x.dtype
    assert dhead.dtype == rhead.dtype == x.dtype and dhead.shape == (d, v)
    rel = 1e-5 if dtype == jnp.float32 else 1e-2
    for grad, ref in ((dx, rx), (dhead, rhead)):
        grad, ref = np.asarray(grad, np.float32), np.asarray(ref, np.float32)
        assert np.isfinite(grad).all()
        np.testing.assert_allclose(grad, ref,
                                   atol=rel * max(np.abs(ref).max(), 1e-6))
    if kept == 0.0:
        assert not np.asarray(dx, np.float32).any()
        assert not np.asarray(dhead, np.float32).any()


def test_a_chunk_the_gradients_plan_refuses_takes_the_xla_products(
        monkeypatch):
    """A chunk `logits_lse` takes and `loss_head_grads` does not (here: no
    room in VMEM for the chunk's x and dx): the two XLA products, and the
    numbers of the float32 reference."""
    rows, d, v = 512, 128, 640
    monkeypatch.setattr(ce, "_GRAD_VMEM_MOST", 256 * 1024)
    assert ce._lse_plan(rows // 2, d, v, 4) is not None
    assert ce._grads_plan(rows // 2, d, v, 4) is None
    x, w = _operands(rows, d, v, jnp.float32, seed=11)
    targets = jax.random.randint(jax.random.key(12), (rows,), 0, v)
    valid = jnp.ones((rows,), jnp.float32).at[::5].set(0.0)
    text, (got, grads) = _loss_and_grads(x, w.T, targets, valid, 2, 1.0)
    assert "loss_head_grads" not in text and text.count("logits_lse") == 1
    want, refs = jax.value_and_grad(_reference_loss, argnums=(0, 1))(
        x, w.T, targets, valid)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for grad, ref in zip(grads, refs):
        np.testing.assert_allclose(grad, ref,
                                   atol=1e-5 * np.abs(ref).max())
