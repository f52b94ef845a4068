"""The A.X-K1 family (models/axk1.py) against its plain reference
(benchmark/reference/axk1.py) on seeded random weights at nano size: latent
attention expanded and absorbed, the latent paged cache and its kernel, the
sigmoid router, an expert layer that holds a share of the router's experts,
a stack that leads with a dense layer, a sliced vocabulary, and the
engine's [prefill_lanes, T] prefill program for every family.

Logits are compared, not tokens.  Everything here is float32 on the CPU, so
the tolerances are those of float32 sums in another order: 2e-4 on logits
of magnitude 1 to 5 (the expanded and the absorbed form associate the same
products differently; measured 6e-6 to 3e-5)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import axk1 as ref
from ray_tpu.inference import InferenceEngine, PagedKVCache
from ray_tpu.models import axk1, decoder, gpt, llama
from ray_tpu.ops import attention as ops
from ray_tpu.ops import moe
from tests import serving_script

NANO = axk1.CONFIGS["axk1-nano"]
SHARE = axk1.CONFIGS["axk1-nano-share"]
LOGIT_TOL = 2e-4


def _ref_kw(cfg):
    """The reference's constants are the published ones; a nano config
    states its own."""
    return dict(top_k=cfg.n_experts_per_tok, first_held=cfg.experts_offset,
                routed_scale=cfg.routed_scale,
                yarn=(("factor", cfg.rope_factor),
                      ("original", cfg.rope_original_max_seq_len),
                      ("beta_fast", cfg.rope_beta_fast),
                      ("beta_slow", cfg.rope_beta_slow),
                      ("theta", cfg.rope_theta)))


def _params(cfg, seed=0):
    """Seeded weights with norm scales off one, so that a norm applied to
    the wrong thing shows."""
    params = serving_script.init_params(axk1, cfg, seed)

    def jitter(tree):
        return {k: v * (1.0 + 0.1 * jax.random.normal(jax.random.key(9),
                                                      v.shape))
                if k.endswith("_norm") else v for k, v in tree.items()}

    for name in ("blocks", "lead_blocks"):
        if name in params:
            params[name] = jitter(params[name])
    return params


def _tokens(cfg, shape, seed=1):
    return jax.random.randint(jax.random.key(seed), shape, 0, cfg.vocab_size)


@pytest.mark.parametrize("cfg", [NANO, SHARE], ids=["whole", "share"])
def test_uncached_forward_matches_the_reference_on_logits(cfg):
    params, tokens = _params(cfg), _tokens(cfg, (2, 48))
    with jax.default_matmul_precision("highest"):
        got = serving_script.forward(axk1, params, tokens, cfg)
    want = ref.logits(params, tokens, **_ref_kw(cfg))
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("lead", [0, 1, 2], ids=["no_dense", "one_dense",
                                                 "two_dense"])
def test_a_stack_may_lead_with_dense_layers(lead):
    """`first_dense_layers` layers of the dense SwiGLU, scanned apart,
    before the expert layers: 0 (a uniform stack), the published 1, and 2."""
    cfg = dataclasses.replace(NANO, first_dense_layers=lead, n_layers=4)
    params, tokens = _params(cfg), _tokens(cfg, (1, 24))
    assert ("lead_blocks" in params) == bool(lead)
    if lead:
        assert params["lead_blocks"]["w_gate"].shape == (lead, 64, cfg.d_ff)
    assert params["blocks"]["router"].shape[0] == 4 - lead
    with jax.default_matmul_precision("highest"):
        got = serving_script.forward(axk1, params, tokens, cfg)
    np.testing.assert_allclose(got, ref.logits(params, tokens, **_ref_kw(cfg)),
                               atol=LOGIT_TOL, rtol=0)


def _cached_logits(cfg, params, tokens, chunk, block_size=8, served=False):
    """Prefill `tokens` [L] in chunks of `chunk`, the last 6 one token at a
    time (the T=1 path), through a latent paged cache whose blocks are
    dealt out of order; logits of every position."""
    cache = PagedKVCache.for_model(axk1, cfg, num_blocks=40,
                                   block_size=block_size, max_lanes=2,
                                   max_seq_len=128)
    assert cache.kind == "latent" and cache.v is None
    assert cache.k.shape == (cfg.n_layers, 40, block_size, 128)
    cache.allocator.alloc(3)              # lane 1 does not start at block 0
    tree = axk1.serving_params(params, cfg) if served else params
    (_, got), (_, none), _ = serving_script.serve(
        axk1, cfg, tree, cache, [None, tokens], chunk, [0, 1],
        prefill=[0, (len(tokens) - 6) // chunk * chunk], precision="highest")
    assert none is None
    return got


# Two dense layers lead three expert layers, two layer bodies a trip: the
# lead run is one trip, the main run a trip of two and a remainder of one,
# and a layer of the main run writes the cache at its index + 2.
LEAD_2_OF_5 = dataclasses.replace(NANO, first_dense_layers=2, n_layers=5,
                                  scan_unroll=2)


@pytest.mark.parametrize("cfg,served", [(NANO, False), (NANO, True),
                                        (SHARE, True), (LEAD_2_OF_5, True),
                                        (LEAD_2_OF_5, False)],
                         ids=["whole_raw_tree", "whole_served_tree",
                              "share_served_tree",
                              "lead_2_of_5_unroll_2_served_tree",
                              "lead_2_of_5_unroll_2_raw_tree"])
def test_prefill_in_chunks_then_decode_matches_the_reference(cfg, served):
    """Absorbed over the latent paged cache = the reference's expanded full
    forward, from the raw tree (w_kvb split in every call) and from the
    tree `serving_params` prepares (w_uk, w_uv made once)."""
    params = _params(cfg)
    tokens = np.asarray(_tokens(cfg, (45,)))
    got = _cached_logits(cfg, params, tokens, chunk=8, served=served)
    want = ref.row_logits(params, tokens, **_ref_kw(cfg))
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_serving_params_split_the_up_projection_once():
    params = _params(NANO)
    served = axk1.serving_params(params, NANO)
    for stack in ("blocks", "lead_blocks"):
        assert "w_kvb" not in served[stack]
        w = params[stack]["w_kvb"]                        # [n, C, H, K + V]
        np.testing.assert_array_equal(
            served[stack]["w_uk"], jnp.moveaxis(w[..., :16], -3, -1))
        np.testing.assert_array_equal(
            served[stack]["w_uv"], jnp.moveaxis(w[..., 16:], -3, -2))
        assert served[stack]["w_uk"].shape[1:] == (4, 16, 16)   # [H, K, C]
        # every other leaf is the array it was
        assert served[stack]["w_qb"] is params[stack]["w_qb"]
    nbytes = lambda t: sum(x.nbytes for x in jax.tree.leaves(t))
    assert nbytes(served) == nbytes(params)


def _latent_case(seed, lanes=3, heads=4, bs=8, nb=24, mb=6, layers=2):
    """A latent pool with blocks SHARED between lanes (a common prefix) and
    ragged context lengths, among them one that ends on a block boundary
    and one of a single token."""
    rng = np.random.default_rng(seed)
    c, r = 16, 8
    w = ops.latent_row_width(c, r)
    pool = jnp.zeros((layers, nb, bs, w), jnp.float32).at[..., :c + r].set(
        jnp.asarray(rng.standard_normal((layers, nb, bs, c + r)),
                    jnp.float32))
    tables = rng.permutation(nb)[:lanes * mb].reshape(lanes, mb)
    tables[1, :2] = tables[0, :2]                  # lanes 0 and 1 share 16
    ctx = np.asarray([bs * mb - 3, bs * 2, 1][:lanes], np.int32)
    q = jnp.asarray(rng.standard_normal((lanes, heads, c + r)), jnp.float32)
    q = ops.pack_latent_rows(q[..., :c], q[..., c:])
    return q, pool, jnp.asarray(tables, jnp.int32), jnp.asarray(ctx), c


@pytest.mark.parametrize("blocks_per_step", [1, 2, 4, 6])
def test_latent_decode_kernel_matches_the_masked_dense_path(blocks_per_step):
    """`latent_decode_attention` in interpret mode, several blocks a grid
    step (also more than the table holds a multiple of), in the second
    layer of the pool."""
    q, pool, tables, ctx, c = _latent_case(blocks_per_step)
    want = ops.latent_attention_reference(
        q[:, None], pool, tables, ctx, (ctx - 1)[:, None], 1, v_width=c,
        scale=0.3)[:, 0]
    got = ops.latent_decode_attention(
        q, pool, tables, ctx, jnp.asarray(1), v_width=c, scale=0.3,
        blocks_per_step=blocks_per_step, use_kernel=True, interpret=True)
    assert got.shape == (3, 4, c)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_latent_decode_kernel_updates_once_a_run_at_lane_wide_blocks():
    """Blocks of 128 tokens sit side by side: one update of the softmax
    state for the run, not one a block; the same result."""
    q, pool, tables, ctx, c = _latent_case(11, bs=128, nb=12, mb=3)
    want = ops.latent_attention_reference(
        q[:, None], pool, tables, ctx, (ctx - 1)[:, None], 0, v_width=c,
        scale=0.2)[:, 0]
    for blocks_per_step in (1, 2, 3):
        got = ops.latent_decode_attention(
            q, pool, tables, ctx, 0, v_width=c, scale=0.2,
            blocks_per_step=blocks_per_step, use_kernel=True, interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def _walk_case(seed, ctx, *, bs, mb=5, heads=4, dtype=jnp.float32,
               layers=2):
    """Lanes of the contexts `ctx` over a pool whose block 0 is NaN: every
    table entry behind a lane's last block names it, and so does all of an
    empty lane's table, so a kernel that fetched a block it has no use for
    would hand NaN on (a probability of 0 times NaN).  Returns the
    kernel's arguments and the same pool with block 0 zeroed, for the
    masked-dense reference."""
    rng = np.random.default_rng(seed)
    c, r, lanes = 96, 32, len(ctx)
    nb = lanes * mb + 1
    rows = rng.standard_normal((layers, nb, bs, c + r)).astype(np.float32)
    clean = jnp.asarray(rows, dtype).at[:, 0].set(0)
    tables = 1 + rng.permutation(nb - 1).reshape(lanes, mb)
    ctx = np.asarray(ctx, np.int32)
    tables[np.arange(mb)[None] >= -(-ctx[:, None] // bs)] = 0
    q = jnp.asarray(rng.standard_normal((lanes, heads, c + r)), dtype)
    return (q, clean.at[:, 0].set(jnp.nan), clean,
            jnp.asarray(tables, jnp.int32), jnp.asarray(ctx), c)


def _walk_matches_the_masked_dense_path(case, atol, **kw):
    q, pool, clean, tables, ctx, c = case
    want = ops.latent_attention_reference(
        q[:, None], clean, tables, ctx, (ctx - 1)[:, None], 1, v_width=c,
        scale=0.2)[:, 0]
    got = ops.latent_decode_attention(
        q, pool, tables, ctx, jnp.asarray(1), v_width=c, scale=0.2,
        use_kernel=True, interpret=True, **kw)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=atol, rtol=0)
    # a lane without context comes out zero (the reference's uniform
    # average over masked rows is its own business)
    assert not np.asarray(got, np.float32)[~live].any()


@pytest.mark.parametrize("blocks_per_step", [1, 2, None])
@pytest.mark.parametrize("bs", [16, 32, 64, 128])
def test_latent_walk_ends_where_each_lanes_context_does(bs, blocks_per_step):
    """The run-walking kernel at every block size the cells' sweeps tried
    and at runs of one block, of two and of the default (the whole table
    here): contexts that end inside a run, at a run's edge (of two blocks
    and of the table), of one token, and an EMPTY lane between live ones,
    whose table names nothing good.  Only the blocks that hold context are
    fetched."""
    ctx = [3 * bs - 5, 2 * bs, 0, 1, 5 * bs]
    _walk_matches_the_masked_dense_path(
        _walk_case(bs, ctx, bs=bs), 2e-5, blocks_per_step=blocks_per_step)


@pytest.mark.parametrize("heads", [64, 128])
def test_latent_walk_takes_the_cells_heads(heads):
    """64 query rows a tile (A.X-K1, dots3's window layers) and 128 (dots3's
    indexed attention), at blocks of 128 in runs of two."""
    _walk_matches_the_masked_dense_path(
        _walk_case(heads, [300, 0, 256, 130], bs=128, mb=3, heads=heads),
        2e-5, blocks_per_step=2)


@pytest.mark.parametrize("ctx", [[1, 0, 0], [0, 0, 23], [0, 40, 0]])
def test_latent_walk_starts_cold_behind_an_empty_lane(ctx):
    """A lane starts the next one's first fetch only where that lane has
    context; behind an empty lane (or as lane 0) a lane starts its own."""
    _walk_matches_the_masked_dense_path(
        _walk_case(sum(ctx), ctx, bs=8), 2e-5, blocks_per_step=2)


def test_latent_walk_updates_once_a_block_where_blocks_do_not_stack():
    """bfloat16 blocks of 8 rows are half a tile row: they do not sit one
    under the other as one operand, so the softmax state is updated once a
    block, as the paged kernel's."""
    _walk_matches_the_masked_dense_path(
        _walk_case(5, [29, 0, 16, 1], bs=8, dtype=jnp.bfloat16), 3e-2,
        blocks_per_step=3)


def test_latent_blocks_per_step_reads_the_run_from_the_rows():
    """As many runs as the table needs at runs that fit the buffers' VMEM
    and hold at most 16 blocks, and those of one length."""
    assert ops.latent_blocks_per_step(128, 640, 2, 132) == 15   # 9 runs
    assert ops.latent_blocks_per_step(128, 640, 2, 16) == 16
    assert ops.latent_blocks_per_step(128, 1152, 2, 6) == 6
    assert ops.latent_blocks_per_step(128, 1152, 2, 133) == 10  # 14 runs
    assert ops.latent_blocks_per_step(128, 128, 2, 133) == 15
    assert ops.latent_blocks_per_step(16, 640, 2, 1056) == 16
    assert ops.latent_blocks_per_step(128, 640, 2, 3) == 3
    assert ops.latent_blocks_per_step(2048, 4096, 4, 8) == 1


@pytest.mark.parametrize("t,q_tile,ctx_tile", [(8, 4, 16), (8, 8, 8),
                                               (6, 128, 512)])
def test_latent_chunk_attention_reads_its_own_blocks_in_tiles(t, q_tile,
                                                              ctx_tile):
    """The tiled T > 1 path against the masked-dense one: lanes at
    different depths, one with fewer valid rows than T, one with none (its
    rows come out zero), causal inside the chunk."""
    _, pool, tables, _, c = _latent_case(7)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((3, t, 4, 24)), jnp.float32)
    q = ops.pack_latent_rows(q[..., :c], q[..., c:])
    start = np.asarray([20, 3, 0])
    chunk = np.asarray([t, t - 2, 0])
    pos = jnp.asarray(start[:, None] + np.arange(t)[None, :], jnp.int32)
    valid = jnp.asarray(np.arange(t)[None, :] < chunk[:, None])
    ctx = jnp.asarray(np.maximum(start + chunk, 1), jnp.int32)
    want = ops.latent_attention_reference(q, pool, tables, ctx, pos, 0,
                                          v_width=c, scale=0.3)
    got = ops.latent_chunk_attention(q, pool, tables, ctx, pos, valid, 0,
                                     v_width=c, scale=0.3, q_tile=q_tile,
                                     ctx_tile=ctx_tile)
    np.testing.assert_allclose(np.where(valid[..., None, None], got, 0),
                               np.where(valid[..., None, None], want, 0),
                               atol=2e-5, rtol=0)
    assert not np.asarray(got[2]).any()


def test_yarn_frequencies_and_scale_are_the_references():
    spec = axk1.spec(axk1.Axk1Config())
    want = ref.yarn_inv_freq(64, ref.YARN)
    np.testing.assert_allclose(spec.rope_freqs, want, rtol=1e-6)
    # the fast dimensions keep theta's own frequency, the slow ones 1/32
    own = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert spec.rope_freqs[0] == pytest.approx(own[0])
    assert spec.rope_freqs[-1] == pytest.approx(own[-1] / 32)
    m = 0.1 * np.log(32.0) + 1.0
    assert m == pytest.approx(1.3466, abs=1e-4)
    assert spec.attn_scale == pytest.approx(192 ** -0.5 * m * m)
    assert spec.attn_scale == pytest.approx(ref.softmax_scale(192, ref.YARN))


def test_router_is_sigmoid_top_k_normalised_scaled_in_float32():
    """What `decoder.moe_ffn` hands the expert layer, read back through
    experts that return their input times a constant of their own."""
    cfg = dataclasses.replace(NANO, n_shared_experts=0)
    d, e, k = cfg.d_model, cfg.n_routed_experts, cfg.n_experts_per_tok
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((2, 5, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(h, np.float64).reshape(-1, d)
                                   @ np.asarray(router, np.float64))))
    chosen = np.argsort(-scores, -1, kind="stable")[:, :k]
    picked = np.take_along_axis(scores, chosen, -1)
    picked = picked / picked.sum(-1, keepdims=True) * cfg.routed_scale
    assert np.allclose(picked.sum(-1), 2.5)
    want = np.zeros((10, e))
    np.put_along_axis(want, chosen, picked, -1)

    seen = {}

    def fake_expert_ffn(x, ids, weights, *a, **kw):
        seen["ids"], seen["weights"] = ids, weights
        assert weights.dtype == jnp.float32
        return x, jnp.zeros((e,), jnp.int32)

    orig, moe.expert_ffn = moe.expert_ffn, fake_expert_ffn
    try:
        decoder.moe_ffn(h, {"router": router, "layer": 0,
                            "w_gate": jnp.zeros((1, e, d, 4)),
                            "w_up": None, "w_down": None}, cfg)
    finally:
        moe.expert_ffn = orig
    got = np.zeros((10, e))
    np.put_along_axis(got, np.asarray(seen["ids"]),
                      np.asarray(seen["weights"]), -1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(
        ref.router_weights(h.reshape(-1, d), router, k, cfg.routed_scale),
        want, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares: the routed parts of all four shares, plus
    what every chip computes alike (the shared expert) counted once, equal
    the uncut reference layer; and each share's routed part is what the
    reference gives for that share."""
    cfg = NANO
    params = _params(cfg)
    blocks = params["blocks"]
    layer = 1
    p = {k: v[layer] for k, v in blocks.items()
         if k not in ("w_gate", "w_up", "w_down")}
    x = jax.random.normal(jax.random.key(4), (2, 12, cfg.d_model))
    h2 = decoder.rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    flat = x.reshape(-1, cfg.d_model)
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(flat, blocks, layer, cfg.n_experts_per_tok,
                            0, cfg.routed_scale) - flat
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
            routed_only = {k: v for k, v in {**blocks, **held}.items()
                           if not k.startswith("ws_")}
            want = ref.experts(flat, routed_only, layer,
                               cfg.n_experts_per_tok, 4 * s,
                               cfg.routed_scale) - flat
            np.testing.assert_allclose(y.reshape(-1, cfg.d_model), want,
                                       atol=2e-5, rtol=0)
            total = total + y.reshape(-1, cfg.d_model)
            loads.append(np.asarray(load))
    np.testing.assert_allclose(total + shared, whole, atol=5e-5, rtol=0)
    # every (token, choice) assignment fell on exactly one share
    assert sum(int(load.sum()) for load in loads) == 24 * cfg.n_experts_per_tok
    assert float(jnp.abs(whole).max()) > 10 * 5e-5


def test_a_sliced_vocabulary_is_a_smaller_vocabulary():
    """An eighth of the vocabulary: ids are drawn from the slice, the
    logits are the whole model's over the slice's columns, the argmax is
    over the slice."""
    cfg = NANO
    params = _params(cfg)
    sliced_cfg = dataclasses.replace(cfg, vocab_size=cfg.vocab_size // 8)
    sliced = {**params, "tok_embed": params["tok_embed"][:64],
              "lm_head": params["lm_head"][:, :64]}
    shapes = jax.eval_shape(lambda k: axk1.init_params(sliced_cfg, k),
                            jax.random.key(0))
    assert jax.tree.map(lambda x: x.shape, shapes) == jax.tree.map(
        lambda x: x.shape, sliced)
    tokens = _tokens(sliced_cfg, (1, 20))
    assert int(tokens.max()) < 64
    whole = serving_script.forward(axk1, params, tokens, cfg)
    got = serving_script.forward(axk1, sliced, tokens, sliced_cfg)
    assert got.shape == (1, 20, 64)
    np.testing.assert_allclose(got, whole[..., :64], atol=1e-5)
    eng = InferenceEngine("axk1", sliced_cfg, sliced, auto_start=False,
                          max_lanes=2, block_size=8, prefill_chunk=8)
    out = eng.generate(np.asarray(tokens[0]).tolist(), 5)
    assert all(0 <= t < 64 for t in out)
    assert out[0] == int(jnp.argmax(got[0, -1]))
    with pytest.raises(ValueError, match="out of range"):
        eng.submit([64], 1)


FAMILIES = {
    "gpt": (gpt, gpt.CONFIGS["nano"]),
    "llama": (llama, llama.CONFIGS["olmoe-nano"]),
    "axk1": (axk1, SHARE),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_prefill_lanes_serve_the_same_tokens_from_fewer_rows(family):
    """With `prefill_lanes` the T=prefill_chunk program computes
    [prefill_lanes, T] rows, not [max_lanes, T]; further prefilling lanes
    wait a step; the served tokens are those of the default."""
    model, cfg = FAMILIES[family]
    params = serving_script.init_params(model, cfg, 2)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (21, 9, 30, 14, 5)]

    def serve(**kw):
        eng = InferenceEngine(model, cfg, params, auto_start=False,
                              max_lanes=4, block_size=8, prefill_chunk=8,
                              max_seq_len=64, prefix_cache=False, **kw)
        handles = [eng.submit(p, 6) for p in prompts]
        while eng.step():
            pass
        return [h.tokens() for h in handles], eng

    want, base = serve()
    got, eng = serve(prefill_lanes=2)
    assert got == want
    assert base.prefill_lanes == 4 and eng.prefill_lanes == 2
    pf0, pf = base.stats()["prefill"], eng.stats()["prefill"]
    assert pf0["rows"] == pf0["steps"] * 4 * 8
    # the pair's programs with a chunk of [2, 8], and of [2, 2] for steps
    # whose lanes all had two tokens or fewer left to feed; each also at one
    # row, for steps in which one lane prefilled (made together:
    # `_warm_widths`)
    # (a pair's lane arrays arrive as one flat buffer, `_pair_views`: the
    # decoding lanes' [4, 8], then the chunk's, a row a lane)
    shapes = {(k[3], k[0]) for k in eng._step_avals if k[3]}
    assert all(v[3].shape == (4 * 8 + k[3] * (3 * k[0] + 6),)
               for k, v in eng._step_avals.items() if k[3])
    assert {(2, 8), (1, 8)} <= shapes <= {(2, 8), (1, 8), (2, 2), (1, 2)}
    assert ((2, 2) in shapes) == ((1, 2) in shapes)
    assert pf["rows"] <= pf["steps"] * 2 * 8 and pf["rows"] % 2 == 0
    assert pf["rows_valid"] == pf0["rows_valid"] == sum(map(len, prompts))
    assert pf["lanes"] <= 2 * pf["steps"] and pf["steps"] > pf0["steps"]
    # lanes not named: the rows rule's (every lane here), ONE chunk program
    assert {k[3] for k in base._step_avals if k[3]} == {4}
    for e in (base, eng):       # an iteration, a program
        ran = e.stats()["programs"]
        assert ran["programs"] == ran["iterations"]
        assert ran["mixed"] == e.stats()["prefill"]["steps"]


def test_a_question_behind_a_cached_document_runs_the_short_program():
    """Under `prefill_lanes` a step whose prefilling lanes have a quarter
    of a chunk or less left to feed runs the [prefill_lanes, T / 4]
    program: the document came from the prefix cache, the question is
    short."""
    cfg = SHARE
    eng = InferenceEngine("axk1", cfg, auto_start=False, max_lanes=4,
                          block_size=8, prefill_chunk=16, prefill_lanes=2,
                          max_seq_len=128)
    doc = list(range(1, 65))
    eng.generate(doc + [70, 71, 72], 2)           # seals the document
    before = eng.stats()["prefill"]
    assert before == {"steps": 5, "lanes": 5, "rows": 4 * 16 + 4,
                      "rows_valid": 67,           # 4 x [1, 16], then [1, 4]
                      "ctx_rows": 16 + 32 + 48 + 64 + 67}
    out = eng.generate(doc + [80, 81, 82, 83], 2)
    after = eng.stats()["prefill"]
    assert {k: after[k] - before[k] for k in after} == {
        "steps": 1, "lanes": 1, "rows": 4, "rows_valid": 4, "ctx_rows": 68}
    assert eng.stats()["prefix_hit_tokens"] == 64 and len(out) == 2
    # the same tokens as an engine with one program
    plain = InferenceEngine("axk1", cfg, eng.params, auto_start=False,
                            max_lanes=4, block_size=8, prefill_chunk=16,
                            max_seq_len=128)
    assert plain.generate(doc + [80, 81, 82, 83], 2) == out


def test_latent_cache_wire_format_says_its_kind():
    from ray_tpu.serve.kv_tier.codec import KVBlockCodec
    cfg = SHARE
    params = serving_script.init_params(axk1, cfg)
    kw = dict(auto_start=False, max_lanes=2, block_size=8, prefill_chunk=8,
              max_seq_len=64, num_blocks=16)
    a = InferenceEngine("axk1", cfg, params, **kw)
    prompt = list(range(1, 30))
    want = a.generate(prompt, 4)
    payload = a.export_prefix(prompt)
    assert payload["kind"] == "latent" and payload["v_pool"] is None
    assert payload["k"].shape == (cfg.n_layers, 3, 8, 1, 24)
    wire = KVBlockCodec.decode(KVBlockCodec.encode(payload))
    assert wire["kind"] == "latent"
    b = InferenceEngine("axk1", cfg, params, **kw)
    assert b.import_prefix(wire) == 3
    assert b.generate(prompt, 4) == want
    assert b.stats()["prefix_hit_tokens"] == 24
    # a cache of K and V rows installs no latent block, and the other way
    other = PagedKVCache(cfg.n_layers, 1, 24, num_blocks=8, block_size=8,
                         max_lanes=1, max_seq_len=64)
    assert other.kind == "kv" and other.install_prefix(wire) == 0
    kv = dict(wire, kind="kv", v_pool=wire["k"])
    assert b.cache.install_prefix(kv) == 0


def test_a_request_waits_for_a_head_that_another_lane_is_sealing():
    """Two requests with one long head arrive together: the second is
    admitted once the first has sealed the head, and takes it from the
    prefix cache instead of prefilling its own copy."""
    cfg = SHARE
    eng = InferenceEngine("axk1", cfg, auto_start=False, max_lanes=2,
                          block_size=8, prefill_chunk=8, max_seq_len=128)
    head = list(range(1, 41))
    first = eng.submit(head + [50, 51, 52], 3)
    second = eng.submit(head + [60, 61], 3)
    eng.step()
    assert eng.num_active == 1 and eng.num_waiting == 1
    while eng.step():
        pass
    assert len(first.tokens()) == len(second.tokens()) == 3
    s = eng.stats()
    assert s["prefix_hit_tokens"] == 40 and s["prefix_hits"] == 1
    # unshared prompts never wait
    eng.submit(list(range(100, 130)), 2)
    eng.submit(list(range(200, 230)), 2)
    eng.step()
    assert eng.num_active == 2
    while eng.step():
        pass
