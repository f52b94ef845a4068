"""Inference engine tests: paged KV cache invariants, content-addressed
prefix caching (seal/match/adopt/evict + token-exactness vs a cold
engine), cached-decode vs full-forward logits equivalence (GPT +
Llama/GQA), the paged attention kernel against its dense reference,
continuous-batching lane admission and pool-exhaustion FIFO, in-step
sampling determinism, and end-to-end streaming generation through
serve."""

import dataclasses
import functools
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.inference import BlockAllocator, InferenceEngine, PagedKVCache
from ray_tpu.inference.speculative import DraftProposer
from ray_tpu.models import decoder, gpt, llama
from ray_tpu.ops import paged_attention_reference, paged_decode_attention, \
    paged_kv_update
from ray_tpu.ops.attention import pack_kv_rows, unpack_kv_rows
from tests import serving_script


# ---------------------------------------------------------------------------
# Block allocator / cache invariants
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_reuse():
    a = BlockAllocator(4)
    b1 = a.alloc(3)
    assert a.num_free == 1
    assert len(set(b1)) == 3
    a.free(b1[:2])
    assert a.num_free == 3
    # LIFO: the most recently freed block comes back first.
    b2 = a.alloc(1)
    assert b2[0] == b1[1]
    assert a.can_alloc(2) and not a.can_alloc(3)


def test_allocator_exhaustion_and_double_free():
    a = BlockAllocator(2)
    blocks = a.alloc(2)
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc(1)
    a.free(blocks)
    with pytest.raises(ValueError, match="double free"):
        a.free(blocks)


def test_cache_lane_lifecycle():
    cache = PagedKVCache(n_layers=1, kv_heads=2, head_dim=4, num_blocks=6,
                         block_size=4, max_lanes=2, max_seq_len=24)
    cache.alloc_lane(0, prompt_len=9)          # 3 blocks
    assert len(cache.lane_blocks(0)) == 3
    assert cache.allocator.num_free == 3
    with pytest.raises(ValueError, match="already allocated"):
        cache.alloc_lane(0, prompt_len=1)
    # Growth across a block boundary claims exactly one more block.
    cache.ensure_capacity(0, 12)
    assert len(cache.lane_blocks(0)) == 3
    cache.ensure_capacity(0, 13)
    assert len(cache.lane_blocks(0)) == 4
    # Freeing returns every block; the table is reusable by a new lane.
    freed = cache.lane_blocks(0)
    cache.free_lane(0)
    assert cache.allocator.num_free == 6
    cache.alloc_lane(1, prompt_len=16)
    assert set(cache.lane_blocks(1)) & set(freed)  # blocks are recycled
    with pytest.raises(RuntimeError, match="max_seq_len"):
        cache.ensure_capacity(1, 25)


def test_cache_admission_control():
    cache = PagedKVCache(n_layers=1, kv_heads=1, head_dim=4, num_blocks=4,
                         block_size=4, max_lanes=4, max_seq_len=16)
    assert cache.can_admit(16)
    cache.alloc_lane(0, prompt_len=12)         # 3 of 4 blocks
    assert cache.can_admit(4) and not cache.can_admit(5)


def test_allocator_refcount_and_lru_eviction():
    evicted = []
    a = BlockAllocator(3, on_evict=evicted.append)
    b = a.alloc(2)
    a.mark_cached(b[0])
    a.mark_cached(b[1])
    a.free(b)                       # cached blocks park evictable, not free
    assert a.num_free == 3          # evictable still counts as capacity
    assert a.is_evictable(b[0]) and a.is_evictable(b[1])
    a.incref(b[1])                  # prefix reuse revives an evictable block
    assert not a.is_evictable(b[1]) and a.refcount(b[1]) == 1
    # Allocating past the plain-free supply evicts LRU-first (b[0]) and
    # fires the index-drop hook; the live share of b[1] is untouchable.
    got = a.alloc(2)
    assert evicted == [b[0]]
    assert a.evictions == 1
    assert b[1] not in got
    a.free([b[1]] + got)
    assert a.num_free == 3


# ---------------------------------------------------------------------------
# Prefix cache: seal / match / adopt / evict
# ---------------------------------------------------------------------------

def test_prefix_cache_seal_match_adopt():
    cache = PagedKVCache(n_layers=1, kv_heads=1, head_dim=4, num_blocks=8,
                         block_size=4, max_lanes=2, max_seq_len=32)
    toks = list(range(1, 13))                    # 12 tokens = 3 full blocks
    cache.alloc_lane(0, 12)
    cache.seq_lens[0] = 12
    cache.seal_full_blocks(0, toks)
    assert cache.num_indexed_blocks == 3
    # The match is capped so at least one prompt token always prefills
    # (its logits seed the first sampled token).
    assert len(cache.match_prefix(toks)) == 2
    assert cache.match_prefix(toks + [99]) == cache.lane_blocks(0)[:3]
    # A diverging block breaks the chain at the divergence point.
    assert len(cache.match_prefix(toks[:4] + [77] + toks[5:] + [99])) == 1
    # Adoption takes refcounted shares of blocks a LIVE lane still owns —
    # mid-flight sharing, no copy.
    reused = cache.adopt_prefix(1, toks + [99, 98])
    assert reused == 12
    shared = cache.lane_blocks(0)[:3]
    assert cache.lane_blocks(1)[:3] == shared
    assert all(cache.allocator.refcount(b) == 2 for b in shared)
    cache.free_lane(0)
    assert all(cache.allocator.refcount(b) == 1 for b in shared)
    cache.free_lane(1)
    # Finished sequences leave sealed blocks indexed at refcount 0: still
    # counted free, still matchable.
    assert cache.allocator.num_free == 8
    assert cache.num_indexed_blocks == 3
    assert len(cache.match_prefix(toks + [99])) == 3


@pytest.mark.parametrize("prompt", [
    list(range(1, 13)) + [99, 98],               # three cached blocks, a tail
    list(range(1, 13)),                          # ends on a block's edge
    list(range(1, 5)) + [77] + list(range(6, 14)),   # parts after a block
    [50, 51, 52],                                # shorter than a block
])
def test_prefix_lookups_by_chain_keys_match_the_walk(prompt):
    """A caller that has a prompt's `chain_keys` (the engine: `submit`
    makes them) gets from `match_prefix`, `can_admit_prefix` and
    `adopt_prefix` what the walk over the tokens gives, and the adopted
    lane's chain cursor is where the walk left it: blocks sealed behind
    it extend the same chain."""
    from ray_tpu.inference.kv_cache import chain_hashes, chain_keys

    def cache_with_three_sealed():
        cache = PagedKVCache(n_layers=1, kv_heads=1, head_dim=4,
                             num_blocks=12, block_size=4, max_lanes=2,
                             max_seq_len=32)
        cache.alloc_lane(0, 12)
        cache.seq_lens[0] = 12
        cache.seal_full_blocks(0, list(range(1, 13)))
        return cache

    keys = chain_keys(prompt, 4)
    assert [hash(k) for k in keys] == chain_hashes(prompt, 4)
    assert len(keys) == (len(prompt) - 1) // 4
    walked, keyed = cache_with_three_sealed(), cache_with_three_sealed()
    assert keyed.match_prefix(prompt, keys) == walked.match_prefix(prompt)
    for headroom in (0, 6, 9):
        assert keyed.can_admit_prefix(prompt, headroom, keys=keys) == \
            walked.can_admit_prefix(prompt, headroom)
    assert keyed.adopt_prefix(1, prompt, keys) == walked.adopt_prefix(
        1, prompt)
    assert keyed.lane_blocks(1) == walked.lane_blocks(1)
    assert keyed._lane_parent[1] == walked._lane_parent[1] == (
        hash(keys[len(walked.match_prefix(prompt)) - 1])
        if walked.match_prefix(prompt) else 0)
    # What the lane seals next hangs on the same chain in both.
    grown = prompt + list(range(200, 200 + 8))
    for cache in (walked, keyed):
        cache.ensure_capacity(1, len(grown))
        cache.seq_lens[1] = len(grown)
        cache.seal_full_blocks(1, grown)
    assert keyed.match_prefix(grown + [1]) == walked.match_prefix(grown + [1])
    assert len(keyed.match_prefix(grown + [1])) == len(grown) // 4


def test_prefix_cache_lru_eviction_under_pressure():
    cache = PagedKVCache(n_layers=1, kv_heads=1, head_dim=4, num_blocks=4,
                         block_size=4, max_lanes=2, max_seq_len=16)
    toks = list(range(1, 9))                     # 8 tokens = 2 blocks
    cache.alloc_lane(0, 8)
    cache.seq_lens[0] = 8
    cache.seal_full_blocks(0, toks)
    cache.free_lane(0)
    assert cache.num_indexed_blocks == 2
    assert cache.allocator.num_free == 4
    # A 16-token request wants the whole pool: plain-free blocks first,
    # then the cached pair is reclaimed LRU and drops out of the index.
    cache.alloc_lane(1, 16)
    assert cache.allocator.evictions == 2
    assert cache.num_indexed_blocks == 0
    assert cache.match_prefix(toks + [9]) == []


# ---------------------------------------------------------------------------
# Paged attention: kernel (interpret) vs dense reference
# ---------------------------------------------------------------------------

def _stored_pool(blocks):
    """Wire-format blocks [L, NB, BS, KH, D] -> the stored pool
    [L, NB, BS, W] (zero pad columns)."""
    return pack_kv_rows(jnp.asarray(blocks))


def _dense_paged_attention(q, k_blocks, v_blocks, tables, ctx_lens,
                           q_positions):
    """Plain numpy attention over ONE layer's wire-format blocks
    [NB, BS, KH, D]: the ground truth both paged paths answer to."""
    q, k_blocks, v_blocks = (np.asarray(a, np.float64)
                             for a in (q, k_blocks, v_blocks))
    b, t, h, d = q.shape
    kh = k_blocks.shape[2]
    out = np.zeros_like(q)
    for lane in range(b):
        k_ctx = k_blocks[np.asarray(tables[lane])].reshape(-1, kh, d)
        v_ctx = v_blocks[np.asarray(tables[lane])].reshape(-1, kh, d)
        for i in range(t):
            n = min(int(ctx_lens[lane]), int(q_positions[lane, i]) + 1)
            for head in range(h):
                g = head // (h // kh)
                s = k_ctx[:n, g] @ q[lane, i, head] / np.sqrt(d)
                p = np.exp(s - s.max())
                out[lane, i, head] = (p / p.sum()) @ v_ctx[:n, g]
    return out


@pytest.mark.parametrize("case", [
    # layers, layer, (kh, d), positions [B, T], valid [B, T]
    pytest.param((1, 0, (2, 8), [[0], [5]], [[True], [False]]),
                 id="t1_one_layer"),
    pytest.param((3, 2, (2, 8), [[0], [5]], [[True], [False]]),
                 id="t1_layer_2_of_3"),
    pytest.param((2, 1, (25, 64), [[3], [6]], [[True], [True]]),
                 id="t1_padded_row_25x64"),
    # A chunk that starts mid-block, crosses into the lane's next block
    # and has dead positions inside it and at its end (prompt overhang).
    pytest.param((2, 1, (3, 8),
                  [[2, 3, 4, 5, 6, 7], [0, 1, 2, 3, 4, 5]],
                  [[True, True, False, True, True, False],
                   [False, False, False, False, False, False]]),
                 id="t6_crosses_block_boundary_with_invalid"),
])
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla_loop", "kernel_interpreted"])
def test_paged_kv_update_masks_invalid_lanes(case, use_kernel):
    layers, layer, (kh, d), positions, valid = case
    nb, bs = 4, 4
    rng = np.random.default_rng(3)
    positions, valid = np.asarray(positions, np.int32), np.asarray(valid)
    b, t = positions.shape
    before = rng.standard_normal((2, layers, nb, bs, kh, d)).astype(
        np.float32)
    k_new = rng.standard_normal((b, t, kh, d)).astype(np.float32)
    v_new = rng.standard_normal((b, t, kh, d)).astype(np.float32)
    tables = np.array([[1, 2], [3, 0]], np.int32)

    k2, v2 = jax.jit(functools.partial(paged_kv_update,
                                       use_kernel=use_kernel))(
        _stored_pool(before[0]), _stored_pool(before[1]), k_new, v_new,
        tables, positions, valid, layer)

    want = before.copy()
    for lane, i in zip(*np.nonzero(valid)):
        pos = positions[lane, i]
        want[0, layer, tables[lane, pos // bs], pos % bs] = k_new[lane, i]
        want[1, layer, tables[lane, pos // bs], pos % bs] = v_new[lane, i]
    for got, expect in ((k2, want[0]), (v2, want[1])):
        # Valid rows written, at the given layer only, and an invalid row
        # changed nothing.
        np.testing.assert_array_equal(
            np.asarray(unpack_kv_rows(got, kh, d)), expect)
        assert not np.asarray(got[..., kh * d:]).any()         # pad columns


def _kernel_case(id, kh=2, q_per_kv=1, layers=1, layer=0, d=64, bs=8, mb=4,
                 dtype=jnp.float32, ctx=(5, 17, 32), kb=(None,)):
    """A case of the decode kernel's test: `ctx` tokens a lane (0: an
    inactive lane, its table all zero), `kb` the `blocks_per_step` values
    that must all give the reference's output (None: the kernel's own)."""
    return pytest.param(kh, q_per_kv, layers, layer, d, bs, mb, dtype, ctx,
                        kb, id=id)


# Contexts over runs of 2 blocks of 8 (16 tokens): one token, a run's edge,
# the middle of the second run, an inactive lane, every block of the table.
_RUN_EDGES = (1, 16, 21, 0, 64)


@pytest.mark.parametrize("kh,q_per_kv,layers,layer,d,bs,mb,dtype,ctx,kb", [
    _kernel_case("mha"),                    # partial / several / all blocks
    _kernel_case("gqa4", q_per_kv=4),
    _kernel_case("mha_layer_1_of_3", layers=3, layer=1),
    _kernel_case("gqa2_padded_row_layer_2_of_3", kh=3, q_per_kv=2, layers=3,
                 layer=2),
    _kernel_case("runs_of_2_blocks_edges_and_an_inactive_lane", mb=8,
                 ctx=_RUN_EDGES, kb=(2,)),
    _kernel_case("blocks_per_step_1_2_and_default_agree", mb=8,
                 ctx=_RUN_EDGES, kb=(1, 2, None)),
    _kernel_case("gqa4_layer_1_of_2_runs_of_4_blocks", q_per_kv=4, layers=2,
                 layer=1, mb=8, ctx=(33, 1, 64, 32), kb=(4,)),
    _kernel_case("gpt2xl_row_25x64_f32", kh=25, mb=8, ctx=(40, 0, 64),
                 kb=(2, None)),
    _kernel_case("bf16_blocks_of_16_stacked", bs=16, mb=8, dtype=jnp.bfloat16,
                 ctx=(1, 32, 45, 0, 128), kb=(1, 2, None)),
    _kernel_case("bf16_blocks_of_8_one_update_a_block", mb=8,
                 dtype=jnp.bfloat16, ctx=_RUN_EDGES, kb=(1, 2, None)),
    _kernel_case("bf16_gqa4_blocks_of_16", q_per_kv=4, bs=16,
                 dtype=jnp.bfloat16, ctx=(17, 64, 3), kb=(2,)),
    _kernel_case("gpt2xl_row_25x64_bf16_blocks_of_16", kh=25, bs=16,
                 dtype=jnp.bfloat16, ctx=(50, 16, 64), kb=(2, None)),
    _kernel_case("heads_of_128_bf16_blocks_of_16", kh=4, d=128, bs=16,
                 dtype=jnp.bfloat16, ctx=(64, 0, 31), kb=(None,)),
])
def test_paged_decode_kernel_matches_reference(kh, q_per_kv, layers, layer,
                                               d, bs, mb, dtype, ctx, kb):
    rng = np.random.default_rng(0)
    b = len(ctx)
    h = kh * q_per_kv
    nb = b * mb + 1
    # Inputs as the dtype holds them, so that the float64 ground truth
    # differs from the kernel by arithmetic alone.
    as_stored = lambda a: np.asarray(jnp.asarray(a, dtype), np.float32)
    q = as_stored(rng.standard_normal((b, h, d)))
    k_blocks = as_stored(rng.standard_normal((layers, nb, bs, kh, d)))
    v_blocks = as_stored(rng.standard_normal((layers, nb, bs, kh, d)))
    k_pool = _stored_pool(k_blocks).astype(dtype)
    v_pool = _stored_pool(v_blocks).astype(dtype)
    tables = 1 + rng.permutation(nb - 1)[:b * mb].reshape(b, mb)
    ctx_lens = np.asarray(ctx, np.int32)
    live = ctx_lens > 0
    tables[~live] = 0                               # as the cache leaves it
    tables = jnp.asarray(tables, jnp.int32)
    want = _dense_paged_attention(
        q[live, None], k_blocks[layer], v_blocks[layer],
        np.asarray(tables)[live], ctx_lens[live],
        (ctx_lens[live] - 1)[:, None])[:, 0]
    # float32 as before this kernel took runs; bf16: the output's rounding.
    tol = 2e-5 if dtype == jnp.float32 else 1.6e-2
    for blocks_per_step in kb:
        out_k = np.asarray(paged_decode_attention(
            jnp.asarray(q, dtype), k_pool, v_pool, tables,
            jnp.asarray(ctx_lens), layer, kv_heads=kh,
            blocks_per_step=blocks_per_step, use_kernel=True,
            interpret=True), np.float32)
        assert np.isfinite(out_k).all()             # the inactive lane too
        np.testing.assert_allclose(out_k[live], want, atol=tol, rtol=tol)
    out_ref = paged_attention_reference(
        jnp.asarray(q, dtype)[:, None], k_pool, v_pool, tables,
        jnp.asarray(ctx_lens), jnp.asarray(ctx_lens - 1)[:, None], layer,
        kv_heads=kh)[:, 0]
    np.testing.assert_allclose(np.asarray(out_ref, np.float32)[live], want,
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# Cached decode == full forward (the correctness core of the engine)
# ---------------------------------------------------------------------------

# 25 heads of 64 at nano depth: gpt2-xl's K/V row, 1600 columns stored in
# rows of 1664.
_GPT_25X64 = gpt.GPTConfig(vocab_size=256, n_layers=2, d_model=1600,
                           n_heads=25, d_ff=128, max_seq_len=64,
                           dtype=jnp.float32)


# gpt's cases: the cached layer loop at depths its unroll fills (4 of 4)
# and leaves a remainder of (6 layers: trips of 4 and 2): every layer
# reads its own matrices out of the stacks by its index (decoder._layer_of).
_nano = functools.partial(dataclasses.replace, gpt.CONFIGS["nano"])
_CACHED = {"gpt": gpt.CONFIGS["nano"], "llama": llama.CONFIGS["llama-tiny"],
           "gpt_25x64": _GPT_25X64,
           "gpt_4_layers_unroll_4": _nano(n_layers=4, scan_unroll=4),
           "gpt_6_layers_unroll_4": _nano(n_layers=6, scan_unroll=4),
           "gpt_6_layers_unroll_1": _nano(n_layers=6)}


@pytest.mark.parametrize("family", list(_CACHED))
def test_cached_logits_match_full_forward(family):
    model = llama if family == "llama" else gpt
    config = _CACHED[family]
    params = serving_script.init_params(model, config, 1)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, config.vocab_size, size=21).tolist()
    prefill = 6

    full = serving_script.forward(
        model, params, jnp.asarray([tokens], jnp.int32), config)
    if isinstance(full, tuple):                 # gpt returns (logits, aux)
        full = full[0]
    full = np.asarray(full[0], np.float32)      # [n, vocab]

    n = len(tokens)
    block_size = 8
    cache = PagedKVCache.for_model(
        model, config, num_blocks=-(-n // block_size) + 1,
        block_size=block_size, max_lanes=1, max_seq_len=config.max_seq_len)
    # chunked prefill, then position > 0 decode; a chunk's last position
    (logits,), _, _ = serving_script.serve(
        model, config, params, cache, [tokens], prefill, [0],
        prefill=[prefill])
    got = {pos: logits[pos].astype(np.float32)
           for pos in range(prefill - 1, n)}

    for pos, logits in got.items():
        np.testing.assert_allclose(logits, full[pos], atol=2e-4, rtol=2e-4,
                                   err_msg=f"{family} position {pos}")
    # and the greedy token of every position is the full forward's
    assert [int(np.argmax(got[pos])) for pos in sorted(got)] == [
        int(np.argmax(full[pos])) for pos in sorted(got)]


# ---------------------------------------------------------------------------
# A family is a spec of the one decoder (models/decoder.py)
# ---------------------------------------------------------------------------

def _layernorm_rope_gelu():
    """GPT's block (LayerNorm with bias, GELU, tied head) under RoPE: its
    parameter format less the position table."""
    def init_params(config, key):
        params = gpt.init_params(config, key)
        del params["pos_embed"]
        return params

    def param_specs(config):
        specs = gpt.param_specs(config)
        del specs["pos_embed"]
        return specs

    def spec(config):
        return decoder.Spec(
            norm=decoder.layernorm, attn_norm=("ln1_scale", "ln1_bias"),
            mlp_norm=("ln2_scale", "ln2_bias"),
            final_norm=("final_ln_scale", "final_ln_bias"),
            ffn=decoder.GELU, rope_theta=10000.0, tied_head=True,
            init_params=init_params, param_specs=param_specs)

    return decoder.bind(spec), gpt.CONFIGS["nano"], init_params


def _rmsnorm_table_swiglu_gqa():
    """Llama's block (RMSNorm, SwiGLU, 4 heads over 2 kv heads, untied
    head) under a learned position table: its parameter format plus one."""
    def init_params(config, key):
        key, table = jax.random.split(key)
        return {**llama.init_params(config, key),
                "pos_embed": jax.random.normal(
                    table, (config.max_seq_len, config.d_model)) * 0.01}

    def param_specs(config):
        return {**llama.param_specs(config), "pos_embed": (None, None)}

    def spec(config):
        return decoder.Spec(
            norm=functools.partial(decoder.rmsnorm, eps=config.norm_eps),
            attn_norm=("attn_norm",), mlp_norm=("mlp_norm",),
            final_norm=("final_norm",), ffn=decoder.SWIGLU,
            init_params=init_params, param_specs=param_specs)

    return decoder.bind(spec), llama.CONFIGS["llama-tiny"], init_params


_FAMILIES = {
    "layernorm_rope_gelu": _layernorm_rope_gelu,
    "rmsnorm_table_swiglu_gqa": _rmsnorm_table_swiglu_gqa,
    "gpt": lambda: (gpt, gpt.CONFIGS["nano"], gpt.init_params),
    "llama": lambda: (llama, llama.CONFIGS["llama-tiny"], llama.init_params),
}


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_a_spec_of_the_decoder_is_a_family_the_engine_serves_and_a_step_trains(
        family):
    """The seam is one: a spec put together here from the decoder's parts,
    in a combination no shipped family has, is served by the engine
    (chunked prefill, then decode over the paged cache) token for token as
    its own full forward continues the prompt, and one train step lowers
    its loss.  The shipped families are two more such specs."""
    import optax
    model, config, init_params = _FAMILIES[family]()
    params = init_params(config, jax.random.key(2))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, config.vocab_size, size=n).tolist()
               for n in (21, 5)]
    new = 10

    engine = InferenceEngine(model, config, params, auto_start=False,
                             max_lanes=2, block_size=8, prefill_chunk=8,
                             max_seq_len=64)
    handles = [engine.submit(p, new) for p in prompts]
    while engine.step():
        pass

    for prompt, handle in zip(prompts, handles):
        # Causal, so one forward over prompt + continuation says what a
        # greedy loop of full forwards would have emitted at each position.
        emitted = handle.tokens()
        logits, _ = decoder.forward(
            model.spec, params, jnp.asarray([prompt + emitted], jnp.int32),
            config)
        want = jnp.argmax(logits[0, len(prompt) - 1:-1], axis=-1)
        assert len(emitted) == new and emitted == want.tolist(), family

    init_state, train_step = model.make_train_step(config, optax.adam(1e-2))
    step = jax.jit(train_step)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, config.vocab_size, size=(4, 32)), jnp.int32)}
    state, first = step(init_state(jax.random.key(3)), batch)
    _, second = step(state, batch)
    assert float(second["loss"]) < float(first["loss"])


def test_export_import_round_trip_keeps_the_wire_format():
    """Outside the engine a block is [L, n, BS, KH, D] whatever the pool's
    stored layout: what one engine exports, a second imports and exports
    again byte for byte, and the stored rows' pad columns stay zero."""
    from ray_tpu.serve.kv_tier.codec import KVBlockCodec
    prompt = list(range(1, 42))                 # (41-1)//8 = 5 sealed blocks
    engines = [InferenceEngine("gpt", "nano", seed=0, max_lanes=2,
                               block_size=8, max_seq_len=64, prefill_chunk=8,
                               auto_start=False) for _ in range(2)]
    first, second = engines
    c = first.config
    assert c.n_heads * c.head_dim % 128       # nano's row IS padded
    first.prefill(prompt)
    sent = first.export_prefix(prompt)
    assert sent["k"].shape == sent["v_pool"].shape == (
        c.n_layers, 5, 8, c.n_heads, c.head_dim)
    assert sent["k"].dtype == first.cache.k.dtype and np.abs(sent["k"]).sum()

    assert second.import_prefix(
        KVBlockCodec.decode(KVBlockCodec.encode(sent))) == 5
    back = second.export_prefix(prompt)
    assert back["chain"] == sent["chain"]
    for name in ("k", "v_pool"):
        assert back[name].shape == sent[name].shape
        assert back[name].tobytes() == sent[name].tobytes()
    for eng in engines:
        for pool in (eng.cache.k, eng.cache.v):
            assert pool.shape == (c.n_layers, eng.cache.allocator.num_blocks,
                                  8, 128)
            assert not np.asarray(pool[..., c.n_heads * c.head_dim:]).any()


# ---------------------------------------------------------------------------
# Continuous batching: lane admission mid-flight
# ---------------------------------------------------------------------------

def test_engine_admits_waiting_request_mid_flight():
    eng = InferenceEngine("gpt", "nano", max_lanes=2, block_size=8,
                          prefill_chunk=4, auto_start=False, seed=0)
    h1 = eng.submit([3, 1, 4], max_new_tokens=3)
    h2 = eng.submit([2, 7, 1], max_new_tokens=12)
    h3 = eng.submit([5, 9, 2], max_new_tokens=3)
    assert eng.num_waiting == 3

    saw_mid_flight_admission = False
    while eng.step():
        # The third request must enter lane 0/1 while the long request
        # is still mid-generation — no batch barrier.
        if eng.num_waiting == 0 and eng.num_active == 2 and \
                h1.finish_reason == "length" and \
                h2.finish_reason is None:
            saw_mid_flight_admission = True
    assert saw_mid_flight_admission
    assert len(h1.tokens()) == 3
    assert len(h2.tokens()) == 12
    assert len(h3.tokens()) == 3
    # Everything was freed on finish.
    assert eng.num_active == 0
    assert eng.cache.allocator.num_free == eng.cache.allocator.num_blocks

    # Batched-greedy output equals one-at-a-time generation.
    solo = InferenceEngine("gpt", "nano", params=eng.params, max_lanes=1,
                           block_size=8, prefill_chunk=4, auto_start=False)
    eng2 = InferenceEngine("gpt", "nano", params=eng.params, max_lanes=2,
                           block_size=8, prefill_chunk=4, auto_start=False)
    hs = [eng2.submit(p, max_new_tokens=5)
          for p in ([3, 1, 4], [2, 7, 1], [5, 9, 2])]
    while eng2.step():
        pass
    batched = [h.tokens() for h in hs]
    for prompt, got in zip(([3, 1, 4], [2, 7, 1], [5, 9, 2]), batched):
        assert got == solo.generate(prompt, max_new_tokens=5)


def test_engine_temperature_sampling_and_eos():
    eng = InferenceEngine("gpt", "nano", max_lanes=1, block_size=8,
                          prefill_chunk=4, auto_start=False, seed=7)
    toks = eng.generate([1, 2, 3], max_new_tokens=50, temperature=1.0)
    assert 0 < len(toks) <= 50
    assert all(0 <= t < eng.config.vocab_size for t in toks)
    # eos_id cuts generation short the moment it is sampled.
    greedy = eng.generate([1, 2, 3], max_new_tokens=8)
    if len(greedy) > 1:
        h = eng.submit([1, 2, 3], max_new_tokens=8, eos_id=greedy[0])
        while eng.step():
            pass
        assert h.tokens() == greedy[:1]
        assert h.finish_reason == "eos"


# ---------------------------------------------------------------------------
# One step ahead: the same tokens as the engine that fetches before it builds
# ---------------------------------------------------------------------------

class _NeverDrafts(DraftProposer):
    """An engine with a proposer fetches a step before it builds the next
    (the loop's depth 0).  This one proposes nothing, so every step is the
    plain one: the order of old, through the public surface."""

    def propose(self, context, k):
        return []


_AHEAD_MODELS = {"gpt": ("gpt", "nano"), "llama": ("llama", "llama-tiny"),
                 "olmoe": ("llama", "olmoe-nano")}
# name -> (prompt, max_new_tokens); six requests over two lanes
_AHEAD_REQUESTS = {
    "short": (list(range(3, 8)), 9),
    "chunks": (list(range(40, 51)), 6),          # three prefill chunks
    "eos": ([7, 1, 9], 12),
    "max_seq_len": (list(range(100, 118)), 40),  # 18 + 40 > 24
    "cancel": ([5, 9, 2, 6], 12),
    "deadline": ([8, 8, 3], 12),
}


def _serve_ahead_requests(eng, temperature, eos_id):
    handles = {
        name: eng.submit(prompt, max_new_tokens=new, temperature=temperature,
                         seed=11 + i, deadline_s=3600.0,
                         eos_id=eos_id if name == "eos" else None)
        for i, (name, (prompt, new)) in enumerate(_AHEAD_REQUESTS.items())}
    cancel, deadline = handles["cancel"], handles["deadline"]
    while eng.step():
        # Both by what has been streamed, not by the clock or the step
        # count: at depth 1 the next step is then already in flight.
        if len(cancel._req.emitted) == 3 and cancel.finish_reason is None:
            assert cancel.cancel()
        if len(deadline._req.emitted) == 2:
            deadline._req.deadline = time.monotonic() - 1.0
    assert eng.num_active == 0 and eng.num_waiting == 0
    assert eng.cache.allocator.num_free == eng.cache.allocator.num_blocks
    return {name: (h.tokens(), h.logps, h.finish_reason)
            for name, h in handles.items()}


@pytest.mark.parametrize("family,mode", [
    ("gpt", "greedy"), ("gpt", "sampled"), ("gpt", "capture_logp"),
    ("llama", "greedy"), ("llama", "sampled"), ("llama", "capture_logp"),
    ("olmoe", "greedy")])
def test_a_step_ahead_serves_the_tokens_of_the_step_by_step_order(family,
                                                                  mode):
    """Requests of staggered prompt and output lengths on fewer lanes than
    requests, one ending by `eos` mid-stream, one by `max_seq_len`, one
    cancelled and one past its deadline while a step is in flight: tokens,
    log-probs and finish reasons are those of an engine that fetches each
    step before it builds the next.  The token computed past an end is
    counted and goes nowhere, and every block comes back."""
    model, config = _AHEAD_MODELS[family]
    temperature = 0.0 if mode == "greedy" else 0.9
    kw = dict(max_lanes=2, block_size=4, max_seq_len=24, prefill_chunk=4,
              auto_start=False, seed=0, capture_logp=mode == "capture_logp")
    ahead = InferenceEngine(model, config, **kw)
    by_step = InferenceEngine(model, config, ahead.params, spec_k=1,
                              draft_proposer=_NeverDrafts(), **kw)
    # The eos id from a first run's own output: a token its stream had not
    # shown before, so that it ends there and not earlier.
    stream = _serve_ahead_requests(by_step, temperature, None)["eos"][0]
    assert len(stream) == 12
    cut = next(j for j in (*range(4, 11), 3, 2, 1, 0)
               if stream[j] not in stream[:j])

    want = _serve_ahead_requests(by_step, temperature, stream[cut])
    got = _serve_ahead_requests(ahead, temperature, stream[cut])
    for name in _AHEAD_REQUESTS:
        assert got[name][0] == want[name][0], name
        assert got[name][1] == pytest.approx(want[name][1], abs=1e-5), name
        assert got[name][2] == want[name][2], name
        assert len(got[name][1]) == (
            len(got[name][0]) if mode == "capture_logp" else 0)
    assert {name: reason for name, (_, _, reason) in got.items()} == {
        "short": "length", "chunks": "length", "eos": "eos",
        "max_seq_len": "max_seq_len", "cancel": "cancelled",
        "deadline": "deadline"}
    assert got["eos"][0] == stream[:cut + 1]          # eos streamed, last
    assert len(got["max_seq_len"][0]) == 24 - 18 + 1
    assert len(got["cancel"][0]) == 3 and len(got["deadline"][0]) == 2
    # One token each was in flight past the eos, the cancel and the
    # deadline; a finish by count was foreseen, and nothing ran past it.
    s1, s0 = ahead.stats(), by_step.stats()
    assert s1["ahead"]["overrun_tokens"] == 3
    assert s1["ahead"]["steps"] > 0
    assert s1["ahead"]["steps"] + s1["ahead"]["sync_steps"] == s1["steps"]
    assert s0["ahead"] == {"steps": 0, "sync_steps": s0["steps"],
                           "overrun_tokens": 0}


# ---------------------------------------------------------------------------
# Prefix reuse: token-exactness vs a cold engine
# ---------------------------------------------------------------------------

def test_prefix_reuse_token_exact_vs_cold():
    warm = InferenceEngine("gpt", "nano", max_lanes=2, block_size=8,
                           prefill_chunk=8, auto_start=False, seed=0)
    cold = InferenceEngine("gpt", "nano", params=warm.params, max_lanes=2,
                           block_size=8, prefill_chunk=8, auto_start=False,
                           seed=0, prefix_cache=False)
    prefix = list(range(1, 25))                  # 24 shared tokens
    p1, p2 = prefix + [30, 31], prefix + [40, 41, 42]

    a1 = warm.generate(p1, max_new_tokens=6)     # seals the prefix
    assert warm.stats()["prefix_hits"] == 0
    a2 = warm.generate(p2, max_new_tokens=6)     # admits via the cache
    assert warm.stats()["prefix_hits"] == 1
    assert warm.stats()["prefix_hit_tokens"] == 24
    # Greedy output with prefix reuse is identical to full prefill.
    assert cold.generate(p1, max_new_tokens=6) == a1
    assert cold.generate(p2, max_new_tokens=6) == a2
    # Seeded sampling too: the PRNG key depends only on (seed, produced).
    s_warm = warm.generate(p2, max_new_tokens=6, temperature=0.9, seed=123)
    s_cold = cold.generate(p2, max_new_tokens=6, temperature=0.9, seed=123)
    assert warm.stats()["prefix_hits"] == 2
    assert s_warm == s_cold


def test_sampled_output_independent_of_batch_composition():
    eng = InferenceEngine("gpt", "nano", max_lanes=4, block_size=8,
                          prefill_chunk=8, auto_start=False, seed=0)
    prompt = [2, 3, 4, 5, 6]
    solo = eng.generate(prompt, max_new_tokens=6, temperature=0.8, seed=99)
    # Same request inside a full, heterogeneous batch (different prompts,
    # temperatures, greedy neighbours) must sample the same tokens.
    h = eng.submit(prompt, max_new_tokens=6, temperature=0.8, seed=99)
    eng.submit([9, 8, 7], max_new_tokens=6, temperature=1.3, seed=5)
    eng.submit([1, 1, 2, 3], max_new_tokens=4)
    eng.submit([4, 4], max_new_tokens=8, temperature=0.4, seed=99)
    while eng.step():
        pass
    assert h.tokens() == solo


# ---------------------------------------------------------------------------
# Admission under pool exhaustion
# ---------------------------------------------------------------------------

def test_admission_fifo_head_not_starved_by_smaller_requests():
    # Pool of 6 blocks x 4 tokens.  r1 fits; r2 (20 tokens = 5 blocks + 1
    # headroom) cannot fit while r1 is live; r3 (1 block + headroom)
    # COULD fit but must wait behind r2 — FIFO admission never starves
    # the head.
    eng = InferenceEngine("gpt", "nano", max_lanes=3, block_size=4,
                          num_blocks=6, max_seq_len=24, prefill_chunk=4,
                          auto_start=False, seed=0)
    h1 = eng.submit(list(range(1, 9)), max_new_tokens=8)
    h2 = eng.submit(list(range(1, 21)), max_new_tokens=2)
    h3 = eng.submit([7, 7, 7, 7], max_new_tokens=2)
    eng.step()
    assert eng.num_active == 1 and eng.num_waiting == 2
    order = []
    while eng.step():
        for h, name in ((h2, "r2"), (h3, "r3")):
            if h.finish_reason and name not in order:
                order.append(name)
    # r2 entered (a lane freed mid-flight was reused) and finished before
    # r3 was admitted.
    assert order == ["r2", "r3"]
    assert len(h1.tokens()) == 8
    assert len(h2.tokens()) == 2
    assert len(h3.tokens()) == 2
    assert eng.cache.allocator.num_free == eng.cache.allocator.num_blocks


# ---------------------------------------------------------------------------
# Satellites: submit validation, tokens() deadline, no [B, V] transfer
# ---------------------------------------------------------------------------

def test_submit_validates_inputs():
    eng = InferenceEngine("gpt", "nano", max_lanes=1, auto_start=False)
    vocab = eng.config.vocab_size
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([])
    with pytest.raises(ValueError, match="out of range"):
        eng.submit([1, vocab])
    with pytest.raises(ValueError, match="out of range"):
        eng.submit([-1])
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1], max_new_tokens=0)


def test_tokens_timeout_is_overall_deadline():
    from ray_tpu.inference.engine import GenerationHandle, _Request
    req = _Request(rid=1, prompt=[1], max_new_tokens=100)
    h = GenerationHandle(req)

    def feeder():   # a token every 50ms — each gap alone beats 0.4s
        for i in range(100):
            time.sleep(0.05)
            req.out.put(i)

    threading.Thread(target=feeder, daemon=True).start()
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):   # and never queue.Empty
        h.tokens(timeout=0.4)
    # Per-token semantics would stream all 100 tokens (~5s) without
    # raising; the overall deadline fires at ~0.4s.
    assert time.monotonic() - t0 < 2.0


def test_cancel_evicts_lane_and_engine_stays_usable():
    eng = InferenceEngine("gpt", "nano", max_lanes=2, block_size=8,
                          prefill_chunk=4, auto_start=False, seed=0)
    h = eng.submit([1, 2, 3], max_new_tokens=1000)
    eng.step()
    assert eng.num_active == 1
    assert h.cancel() is True
    assert h.finish_reason == "cancelled"
    assert h.cancel() is False          # idempotent
    assert eng.num_active == 0
    assert eng.cache.allocator.num_free == eng.cache.allocator.num_blocks
    # The lane is genuinely reusable afterwards.
    assert len(eng.generate([4, 5, 6], max_new_tokens=3)) == 3


def test_tokens_timeout_cancels_upstream():
    """Satellite fix: a client-side tokens() deadline must CANCEL the
    request (dequeue / evict the lane), not leave the engine generating
    for a consumer that already gave up."""
    eng = InferenceEngine("gpt", "nano", max_lanes=1, block_size=8,
                          prefill_chunk=4, auto_start=False, seed=0)
    h = eng.submit([1, 2, 3], max_new_tokens=1000)
    assert eng.num_waiting == 1
    with pytest.raises(TimeoutError):
        h.tokens(timeout=0.1)           # never stepped: still queued
    assert h.finish_reason == "cancelled"
    assert eng.num_waiting == 0 and eng.num_active == 0


def test_request_deadline_evicts_lane():
    eng = InferenceEngine("gpt", "nano", max_lanes=1, block_size=8,
                          prefill_chunk=4, auto_start=False, seed=0)
    h = eng.submit([1, 2, 3], max_new_tokens=100000, deadline_s=0.15)
    while eng.step():
        pass
    assert h.finish_reason == "deadline"
    assert len(h.tokens()) < 100000
    assert eng.num_active == 0
    assert eng.cache.allocator.num_free == eng.cache.allocator.num_blocks


def test_sample_offset_resume_is_seed_consistent():
    """Failover building block: resubmitting with the produced tokens
    appended to the prompt and sample_offset=len(produced) draws the
    SAME per-step sampling keys the original request would have drawn,
    so a resumed sampled stream is token-exact."""
    eng = InferenceEngine("gpt", "nano", max_lanes=2, block_size=8,
                          prefill_chunk=8, auto_start=False, seed=0)
    prompt = [2, 3, 4, 5]
    full = eng.generate(prompt, max_new_tokens=8, temperature=0.9, seed=42)
    if len(full) < 4:
        pytest.skip("sampled run hit max_seq_len too early")
    part = eng.generate(prompt, max_new_tokens=3, temperature=0.9, seed=42)
    assert part == full[:3]
    h = eng.submit(prompt + part, max_new_tokens=len(full) - 3,
                   temperature=0.9, seed=42, sample_offset=3)
    while eng.step():
        pass
    assert h.tokens() == full[3:]


# ---------------------------------------------------------------------------
# The served weights: prepared once, multiplied as prepared
# ---------------------------------------------------------------------------

NANO_BF16 = gpt.GPTConfig(vocab_size=512, n_layers=2, d_model=64, n_heads=4,
                          d_ff=128, max_seq_len=128, dtype=jnp.bfloat16)


def test_serving_params_rounds_each_matrix_once_and_keeps_the_rest():
    """float32 parameters under bf16 activations (gpt2-xl's case): every
    leaf `forward_cached` casts at its use is held as that cast, `w_down`
    the way round its matmul reads it and each table also as padded rows;
    the layer norms, used in float32, are the very arrays given."""
    params = serving_script.init_params(gpt, NANO_BF16)
    served = gpt.serving_params(params, NANO_BF16)
    blocks, given = served["blocks"], params["blocks"]
    for name in ("wq", "wk", "wv", "wo", "w_up"):
        assert blocks[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(blocks[name],
                                      given[name].astype(jnp.bfloat16))
    assert "w_down" not in blocks
    np.testing.assert_array_equal(
        blocks["w_down_t"],
        jnp.swapaxes(given["w_down"], 1, 2).astype(jnp.bfloat16))
    np.testing.assert_array_equal(served["tok_embed"],
                                  params["tok_embed"].astype(jnp.bfloat16))
    for rows, table in (("tok_rows", "tok_embed"), ("pos_rows", "pos_embed")):
        assert served[rows].shape == (params[table].shape[0], 128)
        np.testing.assert_array_equal(
            served[rows][:, :64], params[table].astype(jnp.bfloat16))
        assert not np.asarray(served[rows][:, 64:], np.float32).any()
    assert "pos_embed" not in served       # only ever looked up
    for name in ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias"):
        assert blocks[name] is given[name]
    assert served["final_ln_scale"] is params["final_ln_scale"]
    assert served["final_ln_bias"] is params["final_ln_bias"]
    # the leaves already rounded are not made again
    again = gpt.serving_params(
        {**params, "blocks": {**given, "wq": blocks["wq"]}}, NANO_BF16)
    assert again["blocks"]["wq"] is blocks["wq"]


@pytest.mark.parametrize("family", ["gpt", "llama", "olmoe_bf16"])
def test_serving_params_of_a_tree_held_in_the_activation_dtype_is_the_tree(
        family):
    """Nothing to round: the float32 CPU presets, and OLMoE's block with
    `param_dtype="bfloat16"` (13.84 GB on the chip: no room for a second
    copy).  The tree comes back leaf for leaf, and no program runs."""
    import dataclasses
    if family == "gpt":
        mod, cfg = gpt, gpt.CONFIGS["nano"]
    elif family == "llama":
        mod, cfg = llama, llama.CONFIGS["llama-tiny"]
    else:
        mod, cfg = llama, dataclasses.replace(
            llama.CONFIGS["olmoe-nano"], dtype=jnp.bfloat16,
            param_dtype="bfloat16")
    params = serving_script.init_params(mod, cfg)
    served = mod.serving_params(params, cfg)
    given, kept = jax.tree.leaves(params), jax.tree.leaves(served)
    assert len(given) == len(kept)
    assert all(a is b for a, b in zip(given, kept))
    engine = InferenceEngine(mod, cfg, params, auto_start=False, max_lanes=2)
    w = engine.stats()["weights"]
    assert w["prepared"] == 1 and w["served_bytes"] == w["given_bytes"]
    assert all(a is b for a, b in zip(given,
                                      jax.tree.leaves(engine._served)))


def test_a_float32_llama_under_bf16_activations_is_served_like_gpt2xl():
    """The dense llama presets hold float32 parameters: the same treatment
    (one `serving_params`, models/decoder.py), by the leaf's dtype and shape
    alone; norms stay float32 and the same arrays.  Rows of 64 get the
    stored forms gpt2-xl's rows of 1600 get; the untied head has no use for
    the token table beside its rows."""
    import dataclasses
    cfg = dataclasses.replace(llama.CONFIGS["llama-tiny"],
                              dtype=jnp.bfloat16)
    params = serving_script.init_params(llama, cfg)
    served = llama.serving_params(params, cfg)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up"):
        np.testing.assert_array_equal(
            served["blocks"][name],
            params["blocks"][name].astype(jnp.bfloat16))
    assert "w_down" not in served["blocks"] and "tok_embed" not in served
    np.testing.assert_array_equal(
        served["blocks"]["w_down_t"],
        jnp.swapaxes(params["blocks"]["w_down"], 1, 2).astype(jnp.bfloat16))
    np.testing.assert_array_equal(
        served["tok_rows"][:, :64], params["tok_embed"].astype(jnp.bfloat16))
    np.testing.assert_array_equal(served["lm_head"],
                                  params["lm_head"].astype(jnp.bfloat16))
    for name in ("attn_norm", "mlp_norm"):
        assert served["blocks"][name] is params["blocks"][name]
    assert served["final_norm"] is params["final_norm"]


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_engine_on_prepared_weights_emits_forward_cached_on_the_raw_tree(
        family):
    """The step takes the prepared tree; `forward_cached` on the raw
    float32 parameters (an engine made to serve them as given, the way
    every tree before PR 28 did) gives the same greedy tokens: the same
    operand values enter the same matmuls."""
    import dataclasses
    if family == "gpt":
        mod, cfg = gpt, NANO_BF16
    else:
        mod, cfg = llama, dataclasses.replace(llama.CONFIGS["llama-tiny"],
                                              dtype=jnp.bfloat16)
    params = serving_script.init_params(mod, cfg, 3)
    prompts = [list(range(1, 40)), [7, 9, 11], list(range(100, 150))]

    def run(raw):
        engine = InferenceEngine(family, cfg, params, auto_start=False,
                                 max_lanes=2, prefill_chunk=16)
        if raw:
            engine._served = engine.params
        else:
            assert engine.params is params
            assert engine._served is not params
        handles = [engine.submit(p, 12) for p in prompts]
        while engine.step():
            pass
        return [h.tokens() for h in handles]

    assert run(raw=False) == run(raw=True)


def test_update_params_prepares_once_and_the_next_step_serves_the_new_tree():
    """One preparation at load and one per swap, each one
    `engine/weights_prepare` ring event and none per step; after the swap
    the lanes go on under the new weights: the tokens a fresh engine on
    those weights emits from the same state."""
    from ray_tpu.util import events
    old = serving_script.init_params(gpt, NANO_BF16)
    new = serving_script.init_params(gpt, NANO_BF16, 1)
    prompt = list(range(1, 30))

    def prepares():
        return [e for e in events.snapshot(plane="engine")
                if e["kind"] == "weights_prepare"]

    n0 = len(prepares())
    engine = InferenceEngine("gpt", NANO_BF16, old, auto_start=False,
                             max_lanes=2, prefill_chunk=32,
                             prefix_cache=False)
    assert engine.stats()["weights"]["prepared"] == 1
    assert len(prepares()) == n0 + 1
    head = engine.generate(prompt, 4)
    assert engine.update_params(new) == 1
    w = engine.stats()["weights"]
    assert w["prepared"] == 2 and len(prepares()) == n0 + 2
    assert w["given_bytes"] == sum(x.nbytes for x in jax.tree.leaves(new))
    assert w["served_bytes"] < w["given_bytes"]        # bf16 for float32
    assert engine.params is new
    tail = engine.generate(prompt, 6)
    assert len(prepares()) == n0 + 2                   # none per step
    fresh = InferenceEngine("gpt", NANO_BF16, new, auto_start=False,
                            max_lanes=2, prefill_chunk=32,
                            prefix_cache=False)
    assert tail == fresh.generate(prompt, 6)
    assert head != tail


def test_sampled_step_keeps_logits_on_device():
    eng = InferenceEngine("gpt", "nano", max_lanes=2, block_size=8,
                          max_seq_len=32, prefill_chunk=8,
                          auto_start=False, seed=0)
    h = eng.submit([1, 2, 3, 4], max_new_tokens=3, temperature=0.7, seed=1)
    while eng.step():
        pass
    assert len(h.tokens()) == 3
    assert True in eng._step_impls      # the sampling step really ran
    vocab = eng.config.vocab_size
    b = eng.max_lanes
    for t in (1, eng.prefill_chunk):
        for impl in eng._step_impls.values():
            out = jax.eval_shape(
                impl, eng.params, eng.cache.k, eng.cache.v,
                jnp.zeros((b, t), jnp.int32), jnp.zeros((b, t), jnp.int32),
                jnp.zeros((b, t), bool), eng.cache.device_tables(),
                jnp.ones((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.uint32),
                jnp.zeros((b,), jnp.int32))
            next_tok = jax.tree_util.tree_leaves(out)[0]
            assert next_tok.shape == (b,)   # one int per lane comes home
            # No step output carries a vocab-sized dim: sampling happened
            # in-graph and the [B, V] logits never left the device.
            for leaf in jax.tree_util.tree_leaves(out):
                assert vocab not in leaf.shape


# ---------------------------------------------------------------------------
# Serve integration: streaming generation end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster():
    import ray_tpu
    from ray_tpu import serve
    info = ray_tpu.init(num_cpus=8, object_store_memory=64 << 20)
    serve.start()
    yield info
    serve.shutdown()
    ray_tpu.shutdown()


def test_llm_deployment_streams_tokens(cluster):
    from ray_tpu import serve
    handle = serve.run(serve.LLMDeployment.bind(
        model="gpt", config="nano", max_lanes=4, block_size=8,
        prefill_chunk=4))
    prompt = [3, 14, 15, 9]
    streamed = list(handle.options("generate").stream(
        prompt, max_new_tokens=6))
    assert len(streamed) == 6
    assert all(isinstance(t, int) for t in streamed)
    # Non-streaming call agrees with the streamed tokens (greedy).
    assert handle.remote(prompt, 6).result(timeout=60) == streamed
    stats = handle.stats.remote().result(timeout=60)
    assert stats["active"] == 0 and stats["max_lanes"] == 4
    serve.delete("llm")


def test_a_replicas_stream_says_what_has_arrived_and_closes_its_lane():
    """`LLMReplica.generate` hands back a `TokenStream`: `ready()` is the
    next token, or the end, being here (so serve's `next_chunk` sends
    what has arrived in one reply); it iterates like the generator it
    was; a consumer that leaves (`close()`) evicts the lane, and one
    that ran to the end leaves nothing to cancel."""
    import time

    from ray_tpu import serve
    replica = serve.LLMReplica(model="gpt", config="nano", max_lanes=2,
                               block_size=8, prefill_chunk=4)
    try:
        engine = replica._engine
        whole = list(replica.generate([3, 14, 15, 9], 6))
        assert len(whole) == 6
        stream = replica.generate([3, 14, 15, 9], 6)
        assert isinstance(stream, serve.llm.TokenStream)
        assert next(stream) == whole[0]
        deadline = time.time() + 60
        while engine.stats()["active"]:             # every token is out
            assert time.time() < deadline
            time.sleep(0.01)
        got = []
        while stream.ready():                       # five, then the end
            try:
                got.append(next(stream))
            except StopIteration:
                break
        assert got == whole[1:] and not stream.ready()
        stream.close()                              # nothing to cancel
        left = replica.generate([3, 14, 15, 9], 4000)
        assert next(left) == whole[0]
        left.close()
        assert engine.stats()["active"] == 0
        assert len(list(left)) < 3999       # ends behind what was out
    finally:
        replica._engine.shutdown()


def test_llm_replica_metrics_scraped_through_cli_path(cluster):
    from ray_tpu import serve, state
    handle = serve.run(serve.LLMDeployment.bind(
        model="gpt", config="nano", max_lanes=2, block_size=8,
        prefill_chunk=4))
    prompt = list(range(1, 18))
    first = handle.remote(prompt, 4).result(timeout=120)
    second = handle.remote(prompt, 4).result(timeout=120)
    assert first == second
    # The engine lives in a serve replica (a worker process); its
    # counters must reach the node-level scrape `cli metrics` renders —
    # hostd pulls worker registries over the CoreWorker Metrics RPC and
    # merges them into its own snapshot.
    text = state.prometheus_metrics()
    assert "inference_prefix_hit_tokens" in text
    assert "inference_prefix_miss_tokens" in text
    assert "inference_waiting_requests" in text
    stats = handle.stats.remote().result(timeout=60)
    assert stats["prefix_hits"] >= 1        # second request reused blocks
    assert stats["prefix_hit_tokens"] >= 16
    serve.delete("llm")
