"""The flash kernels' forward and gradients against `jax.grad` of the plain
reference, on the CPU interpreter: tiles under, on and (non-causal) over the
diagonal, a head of one block and of many, tiles smaller than a block."""

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


def _check(q_len, kv_len, blocks, d, dtype, causal, scale=None):
    heads = 2 if q_len <= 512 else 1
    q, k, v, g = (jax.random.normal(
        jax.random.fold_in(jax.random.key(q_len + d), i),
        (1, kv_len if i in (1, 2) else q_len, heads, d), dtype)
        for i in range(4))
    blocks = dict(zip(("block_q", "block_k"), blocks)) if blocks else {}

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=scale, **blocks)

    def ref(q, k, v):
        return reference_attention(q, k, v, causal=causal, scale=scale)

    got = (flash(q, k, v),) + _grads(flash, g)(q, k, v)
    want = (ref(q, k, v),) + _grads(ref, g)(q, k, v)
    for name, x, y in zip(("out", "dq", "dk", "dv"), got, want):
        x, y = (np.asarray(a, np.float32) for a in (x, y))
        assert np.isfinite(x).all(), name
        if dtype == jnp.float32:
            np.testing.assert_allclose(x, y, atol=2e-5, rtol=2e-5,
                                       err_msg=name)
        else:   # chip_smoke.py's rule: two bf16 ulps at the largest value
            tol = 2.0 ** -6 * max(1.0, float(np.abs(y).max()))
            assert float(np.abs(x - y).max()) <= tol, name


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
