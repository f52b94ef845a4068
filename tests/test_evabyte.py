"""EvaByte (models/evabyte.py: EVA attention over a windowed paged cache)
against its plain reference (benchmark/reference/evabyte.py), on seeded
random weights at nano size on the CPU: windows of 32 positions, chunks of
4, blocks of 8, so a window is 4 exact blocks and its summaries fill one.
Logits are compared, never tokens; every tolerance is for float32 sums in
another order (the config is float32: the reference and the program then
differ by rounding alone)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import evabyte as reference
from ray_tpu.inference.engine import InferenceEngine
from ray_tpu.inference.kv_cache import PagedKVCache
from ray_tpu.models import decoder, evabyte
from ray_tpu.ops import attention as ops
from tests import serving_script

CFG = evabyte.CONFIGS["evabyte-nano"]
W, C, BS = CFG.window_size, CFG.chunk_size, 8
SHAPE = dict(window=W, chunk=C)
# float32 against float32 in another order of summation, logits of size ~1
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def params():
    p = serving_script.init_params(evabyte, CFG, 3)
    # Norm scales off their initial zero, so that the unit offset shows.
    for name in ("attn_norm", "mlp_norm"):
        p["blocks"][name] = 0.1 * jax.random.normal(
            jax.random.key(4), p["blocks"][name].shape)
    p["final_norm"] = 0.1 * jax.random.normal(jax.random.key(5),
                                              p["final_norm"].shape)
    return p


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


def _engine(params, **kw):
    kw = {"max_lanes": 2, "prefill_chunk": 16, "prefill_lanes": 1,
          "block_size": BS, "num_blocks": 64, "auto_start": False, **kw}
    return InferenceEngine("evabyte", CFG, params, **kw)


@pytest.mark.parametrize("length", [7, W - 1, W, W + 1, 2 * W, 3 * W + 5],
                         ids=["short", "under_a_window", "a_window",
                              "over_the_edge", "two_windows",
                              "over_three_windows"])
def test_whole_sequence_logits_of_all_heads_match_the_reference(params,
                                                                length):
    tokens = _tokens(length, seed=length)
    got = serving_script.forward(evabyte, params,
                                 jnp.asarray(tokens)[None], CFG)[0]
    want = reference.row_logits(params, tokens, **SHAPE)
    assert got.shape == (length, CFG.num_pred_heads * CFG.vocab_size)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_the_reference_takes_window_and_chunk_by_the_published_ratio():
    shapes = {"blocks": {"wq": np.zeros((1, 8, 2, 128))}}
    assert reference.shape_of(shapes) == (2048, 16)
    assert reference.shape_of(shapes, 32, 4) == (32, 4)


def test_summaries_left_out_or_a_window_one_chunk_short_change_the_logits(
        params):
    """What the cell's check must catch: the gap such faults open at nano
    size is orders over the tolerance above."""
    tokens = _tokens(3 * W, seed=11)
    want = np.asarray(reference.row_logits(params, tokens, **SHAPE))
    # no summaries: every window alone
    alone = np.concatenate([np.asarray(reference.row_logits(
        params, tokens[i:i + W], **SHAPE)) for i in range(0, 3 * W, W)])
    assert np.abs(alone[:W] - want[:W]).max() < 1e-4     # the first window
    assert np.abs(alone[W:] - want[W:]).max() > 1e-2
    short = np.asarray(reference.row_logits(params, tokens, window=W - C,
                                            chunk=C))
    assert np.abs(short - want).max() > 1e-2


@pytest.mark.parametrize("chunk", [16, 5], ids=["chunk16", "chunk5"])
def test_prefill_in_chunks_then_decode_across_two_window_edges(params, chunk):
    """The engine's own path: a prompt of 50 fed in chunks that are cut at
    position 32, then 60 T=1 steps that cross positions 64 and 96, each
    through a compaction; the sampled tokens are the greedy choice of the
    program's own whole-sequence form, and the reference's logit of each
    lies within rounding of its largest."""
    engine = _engine(params, prefill_chunk=chunk)
    prompt = _tokens(50, seed=1).tolist()
    out = engine.generate(prompt, 60)
    stats = engine.stats()
    assert stats["eva"]["compactions"] == 3
    seq = np.asarray(prompt + out)
    logits = serving_script.forward(evabyte, params,
                                    jnp.asarray(seq)[None], CFG)[0]
    own = np.asarray(jnp.argmax(logits[:, :CFG.vocab_size], -1))
    np.testing.assert_array_equal(np.asarray(out),
                                  own[len(prompt) - 1:len(seq) - 1])
    gaps, ranks = reference.served_token_gaps(params, prompt, out, bucket=4,
                                              **SHAPE)
    assert len(gaps) == 60 and max(gaps) < 1e-4, max(gaps)


@pytest.mark.parametrize("scan_unroll", [1, 2],
                         ids=["a_layer_a_trip", "both_layers_a_trip"])
def test_cached_logits_equal_the_reference_at_every_position(params,
                                                             scan_unroll):
    """Logits, not tokens: the cached forward a position at a time (the T=1
    program's mathematics) against the reference's full forward, over two
    window edges, with the windows closed by `compact_cached`; both loops
    read a layer's leaves out of their stacks by the layer's index."""
    cfg = dataclasses.replace(CFG, scan_unroll=scan_unroll)
    cache = PagedKVCache.for_model(evabyte, cfg, num_blocks=32,
                                   block_size=BS, max_lanes=1,
                                   max_seq_len=128)
    tokens = _tokens(2 * W + 9, seed=2)
    cache.alloc_lane(0, 1)
    # (a window closed between two slices is not a step of the one serving
    # script: its slices here, as one program each, and the compaction as
    # another)
    compact = jax.jit(evabyte.compact_cached, static_argnums=6)
    rows = []
    for pos, tok in enumerate(tokens):
        if cache.window_due(0, pos):
            src, dst = cache.close_window(0, tokens[:pos].tolist())
            cache.update_pools(*compact(
                params, cache.k, cache.v, jnp.asarray([src]),
                jnp.asarray([dst]), jnp.asarray([True]), cfg))
        cache.ensure_capacity(0, pos + 1)
        logits, k, v, _ = serving_script.step(
            evabyte, cfg, params, jnp.asarray([[tok]]), jnp.asarray([[pos]]),
            jnp.asarray([[True]]), cache.k, cache.v, cache.device_tables(),
            jnp.asarray([pos + 1]))
        cache.update_pools(k, v)
        cache.seq_lens[0] = pos + 1
        rows.append(logits[0, 0])
    want = reference.row_logits(params, tokens, **SHAPE)
    np.testing.assert_allclose(np.asarray(jnp.stack(rows)),
                               np.asarray(want), **TOL)
    # the lane holds two summary blocks and the open window's two
    assert len(cache.lane_blocks(0)) == 2 + 2
    assert cache.rows_held(len(tokens)) == 2 * (W // C) + 9


def test_eva_summarise_on_pool_blocks_matches_the_references_pooling():
    h, d, n_layers = 4, 16, 2
    rng = np.random.default_rng(5)
    k, v = (jnp.asarray(rng.normal(size=(2, W, h, d)), jnp.float32)
            for _ in range(2))
    mu, phi = (jnp.asarray(rng.normal(size=(h, d)) * 0.3, jnp.float32)
               for _ in range(2))
    shape = (n_layers, 16, BS, ops.kv_row_width(h, d))
    k_pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
    src = np.asarray([[3, 9, 1, 12], [5, 2, 14, 7]], np.int32)
    dst = np.asarray([[4], [10]], np.int32)
    for lane in range(2):
        for j, block in enumerate(src[lane]):
            rows = slice(j * BS, (j + 1) * BS)
            k_pool = k_pool.at[1, block].set(ops.pack_kv_rows(k[lane, rows]))
            v_pool = v_pool.at[1, block].set(ops.pack_kv_rows(v[lane, rows]))
    k_new, v_new = ops.eva_summarise(
        k_pool, v_pool, mu, phi, jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray([True, False]), 1, chunk=C, kv_heads=h, head_dim=d)
    kbar, vbar = reference.pooled(k[0], v[0], mu, phi, C)
    np.testing.assert_allclose(
        np.asarray(ops.unpack_kv_rows(k_new[1, 4], h, d)), np.asarray(kbar),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ops.unpack_kv_rows(v_new[1, 4], h, d)), np.asarray(vbar),
        rtol=1e-5, atol=1e-5)
    # a row nobody has changes nothing, nor does any other block or layer
    untouched = np.ones(16, bool)
    untouched[4] = False
    for new, old in ((k_new, k_pool), (v_new, v_pool)):
        np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(old[0]))
        np.testing.assert_array_equal(np.asarray(new[1, untouched]),
                                      np.asarray(old[1, untouched]))


def test_eva_row_maps_positions_behind_the_summaries():
    pos = np.arange(3 * W)
    rows = ops.eva_row(pos, W, C)
    per = W // C
    np.testing.assert_array_equal(rows[:W], pos[:W])
    np.testing.assert_array_equal(rows[W:2 * W], per + np.arange(W))
    np.testing.assert_array_equal(rows[2 * W:], 2 * per + np.arange(W))


def test_served_tokens_equal_with_the_prefix_cold_and_hit(params):
    """A second request over the same 70-token head takes window 0's
    summary block and window 1's exact blocks from the prefix index and
    serves what a cold engine serves."""
    head = _tokens(70, seed=6).tolist()
    a, b = head + [1, 2, 3], head + [4, 5]
    warm = _engine(params)
    warm.generate(a, 40)
    s0 = warm.stats()
    hit = warm.generate(b, 40)
    s1 = warm.stats()
    # the match: 32 (a summary block) + 4 exact blocks of 8
    assert s1["prefix_hit_tokens"] - s0["prefix_hit_tokens"] == 64
    cold = _engine(params, prefix_cache=False).generate(b, 40)
    assert hit == cold
    gaps, _ = reference.served_token_gaps(params, b, hit, bucket=4, **SHAPE)
    assert max(gaps) < 1e-4


def test_served_tokens_equal_with_dispatch_ahead_on_and_off(params):
    """The scheduler thread runs a step ahead of its results (a window is
    closed while the step that fills it is in flight); `step()` driven to
    idle by `generate` on a manual engine commits before it builds."""
    prompt = _tokens(30, seed=8).tolist()
    manual = _engine(params).generate(prompt, 70)
    ahead = _engine(params, auto_start=True)
    try:
        got = ahead.generate(prompt, 70)
        assert ahead.stats()["ahead"]["steps"] > 0
    finally:
        ahead.shutdown()
    assert got == manual


def test_two_lanes_close_their_windows_in_one_compaction_program(params):
    engine = _engine(params, prefill_lanes=2)
    prompts = [_tokens(20, seed=s).tolist() for s in (20, 21)]
    handles = [engine.submit(p, 50) for p in prompts]
    while engine.step():
        pass
    outs = [h.tokens() for h in handles]
    assert engine.stats()["eva"]["compactions"] == 4
    for p, out in zip(prompts, outs):
        assert out == _engine(params).generate(p, 50)


def test_a_speculative_engine_over_windows_is_refused(params):
    with pytest.raises(NotImplementedError):
        _engine(params, spec_k=2)


def test_the_family_is_served_only():
    with pytest.raises(NotImplementedError):
        evabyte.loss_fn({}, {"tokens": jnp.zeros((1, 4), jnp.int32)}, CFG)


def test_bf16_matrices_keep_a_float32_residual_and_float32_logits():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    spec = evabyte.spec(cfg)
    assert spec.residual_dtype == jnp.float32
    p = serving_script.init_params(evabyte, cfg)
    assert {x.dtype for x in jax.tree.leaves(p)} == {jnp.dtype(jnp.bfloat16)}
    tokens = jnp.asarray(_tokens(W + 3))[None]
    x, _ = decoder.forward_trunk(evabyte.spec, p, tokens, cfg)
    assert x.dtype == jnp.bfloat16          # normed for the head's product
    logits = serving_script.forward(evabyte, p, tokens, cfg)
    assert logits.dtype == jnp.float32
    # the bf16 program against the float32 reference on the same weights:
    # bf16's rounding, not a fault (8 mantissa bits on logits of size ~1)
    want = reference.row_logits(p, np.asarray(tokens[0]), **SHAPE)
    assert np.abs(np.asarray(logits[0]) - np.asarray(want)).max() < 0.1
    # a served tree is the given tree: nothing to convert
    assert evabyte.serving_params(p, cfg) is p
