"""The T > 1 step of a K/V cache: `ops.attention.paged_chunk_attention`
(tiles over the prefilling lanes' own blocks) against the masked-dense
`paged_attention_reference`, and the engine's tokens over it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import InferenceEngine
from ray_tpu.ops import attention as ops


def _case(seed, *, lanes, h, kh, d, t, bs, mb, dtype):
    """A pool of `lanes` * mb + 1 blocks whose LAST block is poison (NaN),
    every lane's table a run of its own blocks in a shuffled order."""
    rng = np.random.default_rng(seed)
    nb = lanes * mb + 1
    w = ops.kv_row_width(kh, d)
    k_pool, v_pool = (rng.standard_normal((2, nb, bs, w)).astype(np.float32)
                      for _ in range(2))
    k_pool[:, -1] = v_pool[:, -1] = np.nan
    tables = rng.permutation(nb - 1).reshape(lanes, mb).astype(np.int32)
    q = rng.standard_normal((lanes, t, h, d)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(k_pool, dtype),
            jnp.asarray(v_pool, dtype), tables)


# start and valid rows of every lane, by what T is: a chunk that starts
# mid-block and is whole, one from near the table's start that is shorter
# than T, a lane nobody has, a chunk that ends on the table's last row.
def _lanes(t, bs, mb):
    return np.asarray([bs + 4, 3, 0, mb * bs - t]), np.asarray(
        [t, max(t - 2, 1), 0, t])


@pytest.mark.parametrize(
    "h,kh,d,t,bs,mb,q_tile,ctx_tile,dtype,atol", [
        pytest.param(25, 25, 64, 32, 16, 8, 128, 512, jnp.float32, 2e-5,
                     id="mha_25x64_rows_of_1664_t32"),
        pytest.param(25, 25, 64, 32, 16, 8, 128, 512, jnp.bfloat16, 3e-2,
                     id="mha_25x64_bf16_t32"),
        pytest.param(8, 2, 64, 5, 8, 6, 128, 512, jnp.float32, 2e-5,
                     id="gqa_8_over_2_t5"),
        pytest.param(8, 1, 32, 32, 8, 8, 16, 24, jnp.float32, 2e-5,
                     id="mqa_8_over_1_two_q_tiles"),
        pytest.param(32, 32, 128, 32, 16, 6, 128, 64, jnp.float32, 2e-5,
                     id="wide_rows_4096_sliced_block_by_block"),
        pytest.param(32, 32, 128, 5, 16, 4, 128, 32, jnp.bfloat16, 3e-2,
                     id="wide_rows_4096_bf16_8kb_t5"),
        pytest.param(4, 4, 64, 128, 16, 16, 32, 48, jnp.float32, 2e-5,
                     id="t128_four_q_tiles_context_across_tile_edges"),
        pytest.param(4, 2, 64, 128, 8, 32, 128, 8, jnp.float32, 2e-5,
                     id="t128_a_block_a_context_tile"),
        pytest.param(6, 3, 64, 5, 8, 4, 2, 16, jnp.float32, 2e-5,
                     id="t5_q_tile_of_one_row"),
        pytest.param(12, 12, 80, 32, 16, 8, 128, 512, jnp.float32, 2e-5,
                     id="head_dim_80_no_kernel_has"),
    ])
@pytest.mark.parametrize("layer", [0, 1])
def test_paged_chunk_attention_reads_its_own_blocks_in_tiles(
        h, kh, d, t, bs, mb, q_tile, ctx_tile, dtype, atol, layer):
    """Every valid row equals the masked-dense reference's.  The lane
    without a valid row comes out zero and its table is never read: it
    names blocks past the pool's end, where XLA's clamp lands on the
    poisoned last block (the reference's rows of that lane are NaN)."""
    q, k_pool, v_pool, tables = _case(11, lanes=4, h=h, kh=kh, d=d, t=t,
                                      bs=bs, mb=mb, dtype=dtype)
    start, chunk = _lanes(t, bs, mb)
    tables[2] = k_pool.shape[1] + 5
    tables = jnp.asarray(tables)
    pos = jnp.asarray(start[:, None] + np.arange(t)[None, :], jnp.int32)
    valid = jnp.asarray(np.arange(t)[None, :] < chunk[:, None])
    ctx = jnp.asarray(np.maximum(start + chunk, 1), jnp.int32)
    want = ops.paged_attention_reference(q, k_pool, v_pool, tables, ctx, pos,
                                         layer, kv_heads=kh, scale=0.2)
    got = jax.jit(lambda *a: ops.paged_chunk_attention(
        *a, kv_heads=kh, scale=0.2, q_tile=q_tile, ctx_tile=ctx_tile))(
            q, k_pool, v_pool, tables, ctx, pos, valid, layer)
    assert got.shape == q.shape and got.dtype == q.dtype
    assert np.isnan(np.asarray(want[2], np.float32)).all()
    keep = np.asarray(valid)[..., None, None]
    np.testing.assert_allclose(
        np.where(keep, np.asarray(got, np.float32), 0),
        np.where(keep, np.asarray(want, np.float32), 0), atol=atol, rtol=0)
    assert not np.asarray(got[2], np.float32).any()


def test_paged_attention_sends_a_t_gt_1_slice_to_the_tiles(monkeypatch):
    """The dispatch: T > 1 never reaches the masked-dense path, with or
    without `valid` (default: every row); T = 1 goes where it went."""
    q, k_pool, v_pool, tables = _case(5, lanes=2, h=4, kh=2, d=64, t=8, bs=8,
                                      mb=4, dtype=jnp.float32)
    tables = jnp.asarray(tables)
    pos = jnp.asarray(np.asarray([[9], [0]]) + np.arange(8)[None], jnp.int32)
    ctx = jnp.asarray([17, 8], jnp.int32)
    want = ops.paged_attention_reference(q, k_pool, v_pool, tables, ctx, pos,
                                         kv_heads=2)
    one = ops.paged_attention(q[:, :1], k_pool, v_pool, tables, ctx, None,
                              kv_heads=2)

    def refuse(*a, **kw):
        raise AssertionError("the masked-dense path, from a T > 1 slice")
    monkeypatch.setattr(ops, "paged_attention_reference", refuse)
    got = ops.paged_attention(q, k_pool, v_pool, tables, ctx, pos,
                              kv_heads=2)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    half = jnp.asarray(np.arange(8)[None, :] < np.asarray([[8], [3]]))
    got = ops.paged_attention(q, k_pool, v_pool, tables, ctx, pos,
                              valid=half, kv_heads=2)
    np.testing.assert_allclose(np.where(half[..., None, None], got, 0),
                               np.where(half[..., None, None], want, 0),
                               atol=2e-5, rtol=0)
    assert one.shape == (2, 1, 4, 64)


def _dense_chunks(q, k_pool, v_pool, block_tables, ctx_lens, q_positions,
                  valid, layer=0, *, kv_heads=None, scale=None, **tiles):
    """What a T > 1 step ran until PR 38."""
    return ops.paged_attention_reference(
        q, k_pool, v_pool, block_tables, ctx_lens, q_positions, layer,
        kv_heads=kv_heads, scale=scale)


@pytest.mark.parametrize("family,config,spec_k", [
    pytest.param("gpt", "nano", 0, id="gpt_prefill_behind_decoding_lanes"),
    pytest.param("llama", "llama-tiny", 0,
                 id="llama_gqa_prefill_behind_decoding_lanes"),
    pytest.param("gpt", "nano", 3, id="gpt_speculative_verify_steps"),
])
def test_the_engine_serves_the_tokens_it_served_over_the_dense_path(
        monkeypatch, family, config, spec_k):
    """Three lanes, five requests of unlike lengths: prompts of several
    chunks are prefilled while other lanes decode, and with `spec_k` every
    decode lane is verified T = spec_k + 1 rows at a time over its whole
    context.  Greedy tokens equal those of the same engine with the
    masked-dense path in the tiles' place."""
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], [2, 7],
               list(range(1, 24)), [8, 8, 8, 8, 8, 8, 8, 8, 8], [6]]
    lengths = [9, 14, 5, 12, 7]
    kw = dict(max_lanes=3, block_size=8, prefill_chunk=8, auto_start=False,
              seed=0, spec_k=spec_k)

    def serve(params=None):
        eng = InferenceEngine(family, config, params, **kw)
        handles = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, lengths)]
        while eng.step():
            pass
        return eng, [h.tokens() for h in handles]

    eng, got = serve()
    stats = eng.stats()
    assert stats["prefill"]["steps"] >= 5
    assert stats["prefill"]["ctx_rows"] >= stats["prefill"]["rows_valid"]
    if spec_k:
        assert stats["spec_drafted_tokens"] > 0
    monkeypatch.setattr(ops, "paged_chunk_attention", _dense_chunks)
    _, want = serve(eng.params)
    assert got == want and [len(x) for x in got] == lengths
