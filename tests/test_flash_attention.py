"""The flash kernels' forward and gradients against `jax.grad` of the plain
reference, on the CPU interpreter: tiles under, on and (non-causal) over the
diagonal, a head of one block and of many, tiles smaller than a block, and
how the heads lie in a block of 128 columns: two of 64 side by side, one
alone in the last block of an odd count, one of 128 or 256 a block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as A
from ray_tpu.ops import flash_attention, reference_attention

# (q_len, kv_len, blocks or None for the defaults)
SHAPES = [
    (256, 256, (128, 128)),
    (512, 512, (128, 64)),
    (384, 384, None),
    (1536, 1536, None),
    (1024, 1024, None),
]


def _grads(fn, g):
    return jax.grad(lambda q, k, v: jnp.sum(
        fn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32)),
        argnums=(0, 1, 2))


def _check(q_len, kv_len, blocks, d, dtype, causal, scale=None, heads=None,
           apart=False):
    """out, dq, dk and dv of `heads` heads, each head against the
    reference's: float32 to 2e-5, bf16 to two ulps at the head's largest
    value.  With `apart`, v and the cotangent of a head are a hundred times
    its neighbour's (1, 100, 0.01, 1, ...): a head that reached its
    neighbour's lanes would drown it, and there alone the float32 limit
    too goes by the head's largest value (values of 100 do not round to
    2e-5)."""
    if heads is None:
        heads = 2 if q_len <= 512 else 1
    q, k, v, g = (jax.random.normal(
        jax.random.fold_in(jax.random.key(q_len + d), i),
        (1, kv_len if i in (1, 2) else q_len, heads, d), jnp.float32)
        for i in range(4))
    if apart:
        size = (100.0 ** ((jnp.arange(heads) + 1) % 3 - 1))[:, None]
        v, g = v * size, g / size
    q, k, v, g = (x.astype(dtype) for x in (q, k, v, g))
    blocks = dict(zip(("block_q", "block_k"), blocks)) if blocks else {}

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale, **blocks)

    def ref(q, k, v):
        return reference_attention(q, k, v, causal=causal, scale=scale)

    got = (flash(q, k, v),) + _grads(flash, g)(q, k, v)
    want = (ref(q, k, v),) + _grads(ref, g)(q, k, v)
    for name, xs, ys in zip(("out", "dq", "dk", "dv"), got, want):
        xs, ys = (np.asarray(a, np.float32) for a in (xs, ys))
        assert xs.shape == ys.shape and np.isfinite(xs).all(), name
        for head in range(heads):
            x, y = xs[:, :, head], ys[:, :, head]
            if dtype == jnp.float32:
                atol = 2e-5 * (max(1.0, float(np.abs(y).max())) if apart
                               else 1.0)
                np.testing.assert_allclose(x, y, atol=atol, rtol=2e-5,
                                           err_msg=f"{name} of head {head}")
            else:   # chip_smoke.py's rule: two bf16 ulps at the largest value
                tol = 2.0 ** -6 * max(1.0, float(np.abs(y).max()))
                assert float(np.abs(x - y).max()) <= tol, (name, head)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("q_len,kv_len,blocks", SHAPES,
                         ids=[f"{s[0]}-{s[2]}" for s in SHAPES])
def test_flash_forward_and_gradients_match_reference(q_len, kv_len, blocks,
                                                     causal, dtype):
    _check(q_len, kv_len, blocks, 64, dtype, causal)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_gradients_at_a_head_of_128(causal, dtype):
    _check(256, 256, (128, 128), 128, dtype, causal)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_gradients_of_a_ring_step(dtype):
    """Non-causal, an explicit scale, more keys than queries."""
    _check(256, 512, (128, 128), 64, dtype, False, scale=0.1)


# (heads, d, whether the heads' sizes lie apart): how heads fill a block of
# max(d, 128) columns of the [b, l, heads x d] arrays the kernels read.
HEADS = [
    (2, 64, True),      # two a block: a head's lanes must not reach the other
    (3, 64, True),      # the second block holds one head and half a block
    (25, 64, False),    # gpt2-xl: twelve whole blocks and a thirteenth half
    (1, 128, False),    # a head a block
    (2, 128, True),
    (1, 256, False),    # a head a block of 256 columns
]


def test_the_interpreter_poisons_what_a_block_reads_past_the_array():
    """The last of an odd count's blocks is half a head's lanes and half
    whatever the copy left: under the interpreter NaN, so a kernel that
    let those lanes reach a result would fail the odd cases below."""
    from jax.experimental import pallas as pl

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    got = pl.pallas_call(
        copy, grid=(2,), interpret=True,
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, i))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, 256), jnp.float32),
    )(jnp.ones((8, 192), jnp.float32))
    assert np.isnan(np.asarray(got[:, 192:])).all()
    assert (np.asarray(got[:, :192]) == 1).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("heads,d,apart", HEADS,
                         ids=[f"{h[0]}x{h[1]}" for h in HEADS])
def test_flash_heads_side_by_side_in_a_block(heads, d, apart, causal, dtype):
    _check(256, 256, (128, 128), d, dtype, causal, heads=heads, apart=apart)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [2, 3])
def test_flash_gradients_of_a_ring_step_at_heads_apart(heads, dtype):
    _check(256, 512, (128, 128), 64, dtype, False, scale=0.1, heads=heads,
           apart=True)


def test_flash_plan_puts_whole_heads_in_blocks_of_128_columns():
    def like(h, d):
        return jax.ShapeDtypeStruct((2, 256, h, d), jnp.bfloat16)

    for h, d, lanes, heads, blocks in [(12, 64, 128, 2, 6), (25, 64, 128, 2, 13),
                                       (16, 128, 128, 1, 16),
                                       (4, 256, 256, 1, 4)]:
        plan = A._flash_plan(like(h, d), like(h, d), True, None, 1024, 1024,
                             True)
        assert (plan.lanes, plan.heads, plan.column_blocks) == (
            lanes, heads, blocks)
        # a float32 row a head of every block, the odd count's phantom too
        assert A._flash_rows(plan, 2, 256) == (2 * blocks * heads, 1, 256)


def test_flash_tiles_follow_the_blocks():
    assert A._flash_tile(1024, 512) == 512 and A._flash_tile(1024, 256) == 256
    assert A._flash_tile(384, 512) == 128 and A._flash_tile(96, 256) == 96
    # the diagonal block of 1,024 in tiles of 256: 10 of 16, 4 of them masked
    tiles = list(A._tiles(4, 256, 4, 256, True))
    assert len(tiles) == 10 and sum(t[2] is not None for t in tiles) == 4
    assert len(list(A._tiles(4, 256, 4, 256, False))) == 16
    # 128 x 64: a q tile sees the kv tiles up to its last row
    assert [t[1:] for t in A._tiles(1, 128, 4, 64, True)] == [
        (0, 0), (64, 64)]


def test_a_head_whose_dq_does_not_fit_vmem_takes_the_reference(monkeypatch):
    """The backward holds a head's dq in VMEM; past the limit the plan is
    None and the call runs (and differentiates) through the XLA reference."""
    like = jax.ShapeDtypeStruct((1, 1024, 1, 64), jnp.float32)
    assert A._flash_plan(like, like, True, None, 1024, 1024, True) is not None
    monkeypatch.setattr(A, "_FLASH_VMEM_LIMIT", 1024)
    assert A._flash_plan(like, like, True, None, 1024, 1024, True) is None
    A.flash_attention.clear_cache()
    try:
        _check(256, 256, (128, 128), 64, jnp.float32, True)
    finally:
        A.flash_attention.clear_cache()
