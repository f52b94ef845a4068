"""The one-chip loss head (`ray_tpu/ops/cross_entropy.py`): the `logits_lse`
kernel under the Pallas interpreter against XLA's product and
`jax.scipy.special.logsumexp`, then `fused_cross_entropy`'s value and both
gradients against a float32 `log_softmax` on the kernel's path and on the
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
