"""dots3-note (models/dots3.py): runs of two kinds of latent attention in one
stack over a paged cache of several kinds of layer, against the plain
reference (benchmark/reference/dots3.py) on seeded random weights at nano
size on the CPU: an indexer that chooses 16 positions, a window of 9,
contexts to 80, float32 throughout.

Tolerances: float32 sums in another order (the program absorbs the
up-projection and gathers chosen rows, the reference expands every key and
masks); logits are of order 4, so 2e-4 is five digits."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dots3 as ref
from ray_tpu.inference import PagedKVCache
from ray_tpu.models import decoder, dots3
from ray_tpu.ops import attention as ops
from tests import serving_script

NANO = dots3.CONFIGS["dots3-nano"]
SHARE = dots3.CONFIGS["dots3-nano-share"]
LOGIT_TOL = 2e-4


def _ref_kw(cfg):
    return dict(top_k=cfg.n_experts_per_tok, first_held=cfg.experts_offset,
                index_topk=cfg.index_topk, window=cfg.sliding_window)


def _init(cfg, seed=0):
    return serving_script.init_params(dots3, cfg, seed)


def _params(cfg, seed=0):
    """Seeded weights with norm scales off one, so that a norm left out or
    applied twice shows."""
    params = dict(_init(cfg, seed))
    for stack in ("lead_blocks", "full_blocks", "win_blocks"):
        params[stack] = {
            k: v * (1.0 + 0.1 * jax.random.normal(jax.random.key(9), v.shape))
            if k.endswith("_norm") or k == "ik_scale" else v
            for k, v in params[stack].items()}
    return params


def _tokens(cfg, shape, seed=1):
    return jax.random.randint(jax.random.key(seed), shape, 0, cfg.vocab_size)


def test_the_spec_names_the_runs_of_like_layers_in_order():
    runs = dots3.spec(NANO).runs
    assert [(r.blocks, r.n_layers, r.first, r.offset) for r in runs] == [
        ("lead_blocks", 1, 0, 0), ("full_blocks", 1, 1, 0),
        ("win_blocks", 3, 0, 0), ("full_blocks", 1, 2, 1),
        ("win_blocks", 3, 3, 3)]
    assert [r.pools for r in runs] == [(0, 1), (0, 1), (2,), (0, 1), (2,)]
    full, win = runs[0].sizes, runs[2].sizes
    assert (full.index_topk, full.window, win.index_topk, win.window) == (
        16, 0, 0, 9)
    assert full.q_rescale == pytest.approx((64 / 32) ** 0.5)
    assert full.kv_rescale == pytest.approx((64 / 16) ** 0.5)
    assert win.kv_rescale == pytest.approx((64 / 32) ** 0.5)
    # the published pattern, where the config gives none
    kinds = dots3.Dots3Config().kinds
    assert kinds.count(dots3.FULL) == 13 and kinds.count(dots3.WINDOW) == 33
    assert kinds[:6] == (dots3.FULL, dots3.FULL) + (dots3.WINDOW,) * 3 + (
        dots3.FULL,)
    # a model of one kind of layer has the two runs it always had
    from ray_tpu.models import axk1
    cfg = axk1.CONFIGS["axk1-nano"]
    assert [(r.blocks, r.n_layers, r.first, r.sizes, r.pools) for r in
            decoder._stacks(axk1.spec(cfg), cfg)] == [
        ("lead_blocks", 1, 0, None, None), ("blocks", 2, 1, None, None)]


@pytest.mark.parametrize("cfg", [NANO, SHARE], ids=["whole", "share"])
def test_uncached_forward_matches_the_reference_on_logits(cfg):
    params = _params(cfg)
    tokens = _tokens(cfg, (2, 80))
    with jax.default_matmul_precision("highest"):
        got = serving_script.forward(dots3, params, tokens, cfg)
    want = ref.logits(params, tokens, **_ref_kw(cfg))
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    assert float(jnp.abs(want).max()) > 100 * LOGIT_TOL


def _cached_logits(cfg, params, tokens, chunk, block_size=4, served=False):
    """Prefill `tokens` [L] in chunks of `chunk`, the last 30 one token at
    a time (the T=1 path), through a cache of both kinds whose blocks are
    dealt out of order, giving back the sliding kind's blocks as the window
    moves on; logits of every position, and the cache."""
    cache = PagedKVCache.for_model(dots3, cfg, num_blocks=(40, 12),
                                   block_size=block_size, max_lanes=2,
                                   max_seq_len=96, ahead=chunk)
    assert cache.kind == "layered" and cache.v is None
    assert [p.shape for p in cache.k] == [
        (3, 40, block_size, 128), (3, 40, block_size, 128),
        (6, 12, block_size, 128)]
    cache.allocator.alloc(3)              # lane 1 does not start at block 0
    cache.parts[0].index.allocator.alloc(2)
    tree = dots3.serving_params(params, cfg) if served else params
    (_, got), (pools, none), _ = serving_script.serve(
        dots3, cfg, tree, cache, [None, tokens], chunk, [0, 1],
        prefill=[0, (len(tokens) - 30) // chunk * chunk], precision="highest")
    assert none is None and len(pools) == 3
    return got, cache


@pytest.mark.parametrize("cfg,served", [(NANO, False), (NANO, True),
                                        (SHARE, True)],
                         ids=["whole_raw_tree", "whole_served_tree",
                              "share_served_tree"])
def test_prefill_in_chunks_then_decode_matches_the_reference(cfg, served):
    """Through the cache (absorbed, indexed, chosen rows gathered; the
    window's blocks given back behind it) = the reference's expanded, masked
    full forward: across the window's slide (9 of 80 positions) and past
    index_topk (16), in chunks whose rows each choose for themselves and at
    T=1."""
    params = _params(cfg)
    tokens = np.asarray(_tokens(cfg, (80,)))
    got, cache = _cached_logits(cfg, params, tokens, chunk=8, served=served)
    want = ref.row_logits(params, tokens, **_ref_kw(cfg))
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # 80 tokens in blocks of 4: the window's 8 positions behind position 80
    # lie in slots 18 and 19 (72..79); every slot behind went back.
    assert sorted(cache.parts[0].held(1)) == [18, 19]
    assert cache.stats["slide_blocks_freed"] == 18
    assert len(cache.lane_blocks(1)) == 20            # the growing kind


def test_a_chunk_of_more_rows_than_a_tile_takes_its_rows_in_tiles(monkeypatch):
    """`ops._rows_as_lanes` with 16 rows a chunk in tiles of 3: a trip for
    each tile that holds a valid row, the last one part empty."""
    monkeypatch.setattr(ops, "_ROW_TILE", 3)
    # (a trace reads the tile's height, and jit keeps its traces by the
    # function: a function of this case's own, so that neither the module's
    # trace of this config answers here nor this one a later case)
    step = serving_script.step.__wrapped__
    monkeypatch.setattr(serving_script, "step", jax.jit(
        lambda *args: step(*args), static_argnums=(0, 1)))
    cfg = NANO
    params = _params(cfg)
    tokens = np.asarray(_tokens(cfg, (80,)))
    got, _ = _cached_logits(cfg, params, tokens, chunk=8)
    want = ref.row_logits(params, tokens, **_ref_kw(cfg))
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_serving_params_split_each_kinds_up_projection_at_its_own_width():
    cfg = NANO
    params = _params(cfg)
    tree = dots3.serving_params(params, cfg)
    for stack, (heads, nope, c, v) in {"lead_blocks": (4, 16, 16, 16),
                                       "full_blocks": (4, 16, 16, 16),
                                       "win_blocks": (2, 24, 32, 16)}.items():
        n = params[stack]["w_kvb"].shape[0]
        assert "w_kvb" not in tree[stack]
        assert tree[stack]["w_uk"].shape == (n, heads, nope, c)
        assert tree[stack]["w_uv"].shape == (n, heads, c, v)
        np.testing.assert_array_equal(
            tree[stack]["w_uk"][0, 1],
            params[stack]["w_kvb"][0, :, 1, :nope].T)
    assert tree["full_blocks"]["router_bias"].dtype == jnp.float32


def _layer(cfg, params, stack, kind, x, **over):
    """One layer's whole-sequence attention (`decoder.LATENT.apply`) at the
    sizes of `kind`, with some replaced."""
    sizes = dataclasses.replace(cfg.sizes(kind), **over)
    p = {k: v[0] for k, v in params[stack].items()}
    with jax.default_matmul_precision("highest"):
        return decoder.LATENT.apply(x, p, dots3.spec(cfg), sizes, None)


def test_the_indexed_form_is_the_dense_one_while_the_context_fits_topk():
    """A full layer over 16 positions with index_topk 16 chooses them all:
    its output is plain causal latent attention's (no indexer); over 40
    positions it is not."""
    cfg, params = NANO, _params(NANO)
    x = jax.random.normal(jax.random.key(3), (2, 40, cfg.d_model))
    short = x[:, :16]
    np.testing.assert_allclose(
        _layer(cfg, params, "full_blocks", dots3.FULL, short),
        _layer(cfg, params, "full_blocks", dots3.FULL, short, index_topk=0),
        atol=1e-5, rtol=0)
    sparse = _layer(cfg, params, "full_blocks", dots3.FULL, x)
    dense = _layer(cfg, params, "full_blocks", dots3.FULL, x, index_topk=0)
    np.testing.assert_allclose(sparse[:, :16], dense[:, :16], atol=1e-5,
                               rtol=0)
    assert float(jnp.abs(sparse[:, 16:] - dense[:, 16:]).max()) > 1e-3


def test_a_window_layer_is_a_full_latent_layer_under_a_band_mask():
    """The window layer's whole-sequence form = the reference's expanded
    attention of the same leaves under the band t - 9 < s <= t; without the
    window it is another function."""
    cfg, params = NANO, _params(NANO)
    x = jax.random.normal(jax.random.key(3), (1, 40, cfg.d_model))
    p = {k: v[0] for k, v in params["win_blocks"].items()}
    h = decoder.rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    got = _layer(cfg, params, "win_blocks", dots3.WINDOW, h)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x[0], p, cfg.swa_rope_theta, cfg.norm_eps, True,
                             window=cfg.sliding_window) - x[0]
        wide = ref.attention(x[0], p, cfg.swa_rope_theta, cfg.norm_eps, True,
                             window=41) - x[0]
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=0)
    assert float(jnp.abs(want - wide).max()) > 1e-3


def test_the_router_chooses_by_the_biased_scores_and_weighs_by_the_unbiased():
    cfg = NANO
    params = _params(cfg)
    p = {k: v[0] for k, v in params["full_blocks"].items()
         if k not in ("w_gate", "w_up", "w_down")}
    # a bias that decides: expert 3 always in, expert 5 never
    bias = jnp.zeros((cfg.n_routed_experts,)).at[3].set(10.).at[5].set(-10.)
    x = jax.random.normal(jax.random.key(4), (1, 24, cfg.d_model))
    want = np.asarray(ref.router_weights(x[0], p["router"], bias,
                                         cfg.n_experts_per_tok, 1.0))
    assert (want[:, 3] > 0).all() and (want[:, 5] == 0).all()
    np.testing.assert_allclose(want.sum(-1), 1.0, atol=1e-6)
    # the weight of expert 3 is its unbiased score's share, not the biased
    scores = np.asarray(jax.nn.sigmoid(x[0] @ p["router"]))
    chosen = want > 0
    np.testing.assert_allclose(
        want[:, 3], scores[:, 3] / (scores * chosen).sum(-1), atol=1e-6)
    held = {k: params["full_blocks"][k] for k in ("w_gate", "w_up", "w_down")}
    y, _, load = decoder.moe_ffn(
        x, {**p, **held, "router_bias": bias, "layer": 0}, cfg)
    assert int(load[3]) == 24 and int(load[5]) == 0


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares (`experts_offset` 0, 4, 8, 12): the routed
    parts of all four shares, plus what every chip computes alike (the
    shared expert) counted once, equal the uncut reference layer."""
    cfg = NANO
    params = _params(cfg)
    blocks = params["win_blocks"]
    layer = 1
    p = {k: v[layer] for k, v in blocks.items()
         if k not in ("w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.key(4), (2, 12, cfg.d_model))
    h2 = decoder.rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    flat = x.reshape(-1, cfg.d_model)
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(flat, blocks, layer, cfg.n_experts_per_tok, 0,
                            cfg.norm_eps, cfg.routed_scale) - flat
        shared = ref.swiglu(h2.reshape(-1, cfg.d_model), p["ws_gate"],
                            p["ws_up"], p["ws_down"])
        total, loads = jnp.zeros_like(flat), []
        for s in range(4):
            share = dataclasses.replace(cfg, n_experts_held=4,
                                        experts_offset=4 * s)
            held = {k: blocks[k][:, 4 * s:4 * s + 4]
                    for k in ("w_gate", "w_up", "w_down")}
            y, _, load = decoder.moe_ffn(h2, {**p, **held, "layer": layer},
                                         share)
            total = total + y.reshape(-1, cfg.d_model)
            loads.append(np.asarray(load))
    np.testing.assert_allclose(total + shared, whole, atol=5e-5, rtol=0)
    assert sum(int(load.sum()) for load in loads) == 24 * cfg.n_experts_per_tok
    assert float(jnp.abs(whole).max()) > 10 * 5e-5


# -- the kernels, interpreted, against the gathered forms ---------------------

def _pool_case(seed, lanes=3, bs=16, nb=24, mb=6, layers=2, width=128):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((layers, nb, bs, width)),
                       jnp.float32)
    tables = jnp.asarray(rng.permutation(nb)[:lanes * mb].reshape(lanes, mb),
                         jnp.int32)
    return rng, pool, tables


@pytest.mark.parametrize("window", [21, 33, 64])
def test_window_decode_kernel_reads_from_the_lanes_start_on(window):
    """The kernel over positions ctx - window .. ctx - 1 = the masked dense
    path; table entries behind a lane's start may name anything (they are
    never fetched)."""
    rng, pool, tables = _pool_case(0)
    ctx = jnp.asarray([90, 5, 37], jnp.int32)
    q = jnp.asarray(rng.standard_normal((3, 4, 128)), jnp.float32)
    starts = jnp.maximum(ctx - window, 0)
    kw = dict(v_width=96, scale=0.1, span=window)
    for layer in (0, 1):
        want = ops.window_latent_decode_attention(
            q, pool, tables, ctx, starts, layer, use_kernel=False, **kw)
        freed = tables.at[0, :int(starts[0]) // 16].set(0)
        got = ops.window_latent_decode_attention(
            q, pool, freed, ctx, starts, layer, use_kernel=True,
            interpret=True, **kw)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


def test_index_scores_kernel_matches_the_gathered_form():
    rng, pool, tables = _pool_case(1)
    ctx = jnp.asarray([90, 5, 37], jnp.int32)
    q_i = jnp.asarray(rng.standard_normal((3, 4, 128)), jnp.float32)
    w_i = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
    want = ops.sparse_index_scores(q_i, w_i, pool, tables, ctx, 1,
                                   use_kernel=False)
    got = ops.sparse_index_scores(q_i, w_i, pool, tables, ctx, 1,
                                  use_kernel=True, interpret=True)
    np.testing.assert_allclose(got[:, :96], want, atol=1e-4, rtol=0)
    assert (np.asarray(want)[1, 5:] == ops.NEG_INF).all()
    # a score is the heads' weighted sum of ReLUs
    keys = np.asarray(pool[1, tables[0, 0]])
    s = np.maximum(np.asarray(q_i[0]) @ keys.T, 0)
    np.testing.assert_allclose(want[0, :16], np.asarray(w_i[0]) @ s,
                               atol=1e-4, rtol=0)


def _poisoned(pool, tables, ctx, bs):
    """The pool with block 0 NaN, the tables naming it wherever a lane
    holds no context (behind its last block; all of an empty lane's), and
    the pool with block 0 zero for the gathered forms: a kernel that
    fetched a block it has no use for would hand NaN on."""
    dead = np.arange(tables.shape[1])[None] >= -(-np.asarray(ctx)[:, None]
                                                 // bs)
    tables = jnp.where(dead, 0, jnp.where(tables == 0, tables.max() + 1,
                                          tables))
    clean = pool.at[:, 0].set(0)
    return clean.at[:, 0].set(jnp.nan), clean, tables


# (window, blocks a run, or None for the default: all a span can touch)
@pytest.mark.parametrize("window,blocks_per_step", [
    (21, None), (26, None), (26, 1), (40, 2), (64, 2), (70, None)],
    ids=["start_inside_a_block", "start_at_a_blocks_edge",
         "a_block_a_run", "span_crosses_a_runs_edge", "three_runs",
         "window_longer_than_a_context"])
def test_window_walk_begins_at_the_block_of_each_lanes_start(
        window, blocks_per_step):
    """The window kernel's walk from the block of `starts[lane]`: lane 0
    (90 tokens) starts inside a block (69), at a block's edge (64) or runs
    over several runs; a lane shorter than the window starts at 0; an
    empty lane between live ones comes out zero and fetches nothing; the
    blocks behind a start and past an end are never fetched."""
    rng, pool, tables = _pool_case(4, lanes=4, nb=25)
    ctx = jnp.asarray([90, 0, 37, 5], jnp.int32)
    pool, clean, tables = _poisoned(pool, tables, ctx, 16)
    starts = jnp.maximum(ctx - window, 0)
    tables = jnp.where(jnp.arange(6)[None] < starts[:, None] // 16, 0,
                       tables)              # freed behind the start
    q = jnp.asarray(rng.standard_normal((4, 4, 128)), jnp.float32)
    kw = dict(v_width=96, scale=0.1, span=window)
    want = ops.window_latent_decode_attention(
        q, clean, tables, ctx, starts, 1, use_kernel=False, **kw)
    got = ops.window_latent_decode_attention(
        q, pool, tables, ctx, starts, 1, use_kernel=True, interpret=True,
        blocks_per_step=blocks_per_step, **kw)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-6, rtol=0)
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("blocks_per_step", [1, 2, 4, None])
@pytest.mark.parametrize("bs", [16, 128])
def test_index_walk_scores_the_context_and_nothing_behind_it(
        bs, blocks_per_step):
    """The index kernel's walk: a run's scores land where they belong in
    the lane's row, NEG_INF from the context's end on (inside a run, at a
    run's edge, behind the last run) and all along an empty lane's row;
    the keys behind a context's end are never fetched."""
    rng, pool, tables = _pool_case(5, lanes=4, bs=bs, nb=25)
    ctx = jnp.asarray([5 * bs + 10, 0, 2 * bs, 5], jnp.int32)
    pool, clean, tables = _poisoned(pool, tables, ctx, bs)
    q_i = jnp.asarray(rng.standard_normal((4, 4, 128)), jnp.float32)
    w_i = jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)
    want = np.asarray(ops.index_scores_reference(
        q_i, w_i, clean, tables, ctx, 1))
    got = np.asarray(ops.sparse_index_scores(
        q_i, w_i, pool, tables, ctx, 1, use_kernel=True, interpret=True,
        blocks_per_step=blocks_per_step))
    assert got.shape[1] >= 6 * bs and got.shape[1] % bs == 0
    np.testing.assert_allclose(got[:, :6 * bs], want, atol=1e-4, rtol=0)
    for lane, n in enumerate(np.asarray(ctx)):
        assert (got[lane, n:] == ops.NEG_INF).all()
        assert (got[lane, :n] > ops.NEG_INF).all()


@pytest.mark.parametrize("heads", [4, 128])
def test_indexed_attention_walks_the_gathered_rows_of_a_short_context(heads):
    """The indexed attention's own call, as `sparse_latent_decode_attention`
    makes it: the chosen rows side by side as a pool of one layer under an
    `arange` table, read by the kernel as far as min(context, topk).  A
    context shorter than `topk` has its own positions first and fill behind
    them, which the walk never multiplies in; the reference path of the
    whole function is the oracle."""
    rng, pool, tables = _pool_case(6, bs=16)
    _, index_pool, _ = _pool_case(7, bs=16)
    ctx = jnp.asarray([90, 12, 32], jnp.int32)      # 12 < topk = 32 = a lane
    q = jnp.asarray(rng.standard_normal((3, heads, 128)), jnp.float32)
    q_i = jnp.asarray(rng.standard_normal((3, 4, 128)), jnp.float32)
    w_i = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
    kw = dict(v_width=96, scale=0.1)
    want = ops.sparse_latent_decode_attention(
        q, q_i, w_i, pool, index_pool, tables, ctx, 1, topk=32, **kw)
    scores = ops.sparse_index_scores(q_i, w_i, index_pool, tables, ctx, 1,
                                     use_kernel=True, interpret=True)
    place = ops.sparse_select(scores, tables, block_size=16, k=32, n=96,
                              base=24 * 16)
    rows = pool.reshape(-1, 128)[place]                         # [3, 32, W]
    for per, kb in ((16, None), (32, None), (8, 3)):
        got = ops.latent_decode_attention(
            q, rows.reshape(1, 3 * 32 // per, per, 128),
            jnp.arange(3 * 32 // per, dtype=jnp.int32).reshape(3, -1),
            jnp.minimum(ctx, 32), 0, use_kernel=True, interpret=True,
            blocks_per_step=kb, name="sparse_latent_decode_attention", **kw)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_sparse_decode_attends_the_chosen_rows_and_no_others():
    """Indexed single-query attention = masked dense attention over the
    top-k positions by index score; a lane whose context fits topk attends
    all of it."""
    rng, pool, tables = _pool_case(2)
    _, index_pool, _ = _pool_case(3)
    ctx = jnp.asarray([90, 12, 37], jnp.int32)
    q = jnp.asarray(rng.standard_normal((3, 4, 128)), jnp.float32)
    q_i = jnp.asarray(rng.standard_normal((3, 4, 128)), jnp.float32)
    w_i = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
    got = ops.sparse_latent_decode_attention(
        q, q_i, w_i, pool, index_pool, tables, ctx, 1, v_width=96,
        scale=0.1, topk=16)
    scores = np.asarray(ops.index_scores_reference(q_i, w_i, index_pool,
                                                   tables, ctx, 1))
    rows = np.asarray(pool[1, tables].reshape(3, -1, 128))
    for lane in range(3):
        keep = np.argsort(-scores[lane], kind="stable")[:16]
        keep = keep[keep < int(ctx[lane])]
        logits = np.asarray(q[lane]) @ rows[lane, keep].T * 0.1
        p = np.exp(logits - logits.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[lane, keep, :96]
        np.testing.assert_allclose(got[lane], want, atol=2e-5, rtol=0)
    dense = ops.latent_decode_attention(q, pool, tables, ctx, 1, v_width=96,
                                        scale=0.1)
    np.testing.assert_allclose(got[1], dense[1], atol=2e-5, rtol=0)
    assert float(jnp.abs(got[0] - dense[0]).max()) > 1e-3


# -- the choice alone, against jax.lax.top_k as a set -------------------------

# (block size, table blocks, k, chunk the counts run over or None for the
# Pallas kernel, interpreted): small blocks through the jax.numpy form, which
# is the CPU's path, at chunks that cut a lane into several and do not match
# its blocks; the cell's own sizes through that form and through the kernel.
_SELECT_SIZES = {"bs4": (4, 9, 12, 8), "bs16": (16, 6, 32, 32),
                 "cell": (128, 133, 2048, 128),
                 "cell_kernel": (128, 133, 2048, None)}


def _select_scores(case, rng, lanes, n, k):
    """Scores [lanes, n] and each lane's context for one case of the
    choice."""
    scores = rng.standard_normal((lanes, n)).astype(np.float32)
    ctx = np.full(lanes, n)
    if case == "ties_by_the_hundred":       # a few values, the k-th among them
        scores = np.round(scores * (2 if n > 1000 else 1)) / 2
    elif case == "all_equal":
        scores[:] = 0.25
    elif case == "signed_zeros":            # -0.0 beside +0.0 at the k-th
        scores = np.round(scores * 0.75)
        zero = scores == 0
        scores[zero] = np.where(rng.random(zero.sum()) < 0.5, 0.0, -0.0)
    elif case == "negatives":
        scores = -np.abs(scores) - 1
    elif case == "true_inf":                # under the NEG_INF of a tail
        scores[:, ::3] = -np.inf
        ctx = np.full(lanes, n - n // 8)
    else:
        ctx = np.resize({"ctx_under_k": [5, k - 1], "ctx_is_k": [k],
                         "ctx_is_k_plus_1": [k + 1, k + 2],
                         "ctx_is_n": [n, n - 1]}[case], lanes)
    return np.where(np.arange(n) < ctx[:, None], scores, ops.NEG_INF), ctx


@pytest.mark.parametrize("case", [
    "ties_by_the_hundred", "all_equal", "signed_zeros", "negatives",
    "true_inf", "ctx_under_k", "ctx_is_k", "ctx_is_k_plus_1", "ctx_is_n"])
@pytest.mark.parametrize("size", list(_SELECT_SIZES))
def test_the_choice_is_top_k_as_a_set_ascending_by_position(size, case):
    """`sparse_select` chooses what `jax.lax.top_k` chooses (the k largest,
    ties to the lower position, -inf under NEG_INF; -0.0 a tie of +0.0, as
    the stable sort before it and the benchmark's reference have it, which
    `top_k` is asked by adding 0.0), exactly k places a lane, ascending by
    position so that a short context's own positions come first, each the
    row's place in the pool's table of rows under the lane's table and the
    layer."""
    bs, mb, k, chunk = _SELECT_SIZES[size]
    lanes, layer, n = 3, 2, bs * mb
    nb = lanes * mb + 5
    rng = np.random.default_rng(len(case) + bs)
    tables = rng.permutation(nb)[:lanes * mb].reshape(lanes, mb).astype(
        np.int32)
    scores, ctx = _select_scores(case, rng, lanes, n, k)
    # the kernel takes the index kernel's row as it comes: longer than the
    # table, NEG_INF behind it, which must never be chosen before a -inf
    given = np.pad(scores, ((0, 0), (0, 0 if chunk else 128)),
                   constant_values=ops.NEG_INF)
    kw = dict(block_size=bs, k=k, n=n, base=layer * nb * bs)
    place = np.asarray(
        ops.sparse_select(jnp.asarray(given), jnp.asarray(tables),
                          use_kernel=True, interpret=True, **kw) if not chunk
        else ops.sparse_select_reference(
            jnp.asarray(given), jnp.asarray(tables), chunk=chunk, **kw))
    assert place.shape == (lanes, k) and place.dtype == np.int32
    block, row = np.divmod(place - layer * nb * bs, bs)
    for lane in range(lanes):
        # every place is a row of a block of the lane's table: its position
        at = {int(b): i for i, b in enumerate(tables[lane])}
        pos = np.asarray([at[int(b)] for b in block[lane]]) * bs + row[lane]
        assert (np.diff(pos) > 0).all()     # ascending: k of them, distinct
        assert (pos[:min(ctx[lane], k)] < ctx[lane]).all()
        want = np.sort(np.asarray(jax.lax.top_k(
            jnp.asarray(scores[lane] + np.float32(0)), k)[1]))
        np.testing.assert_array_equal(pos, want)
        np.testing.assert_array_equal(
            place[lane], tables[lane, pos // bs] * bs + pos % bs
            + layer * nb * bs)
