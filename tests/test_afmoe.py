"""AFMoE (models/afmoe.py; Trinity-Mini's family): window layers beside
full layers over K and V heads in one paged cache, a gated attention with
rotation on the window layers only, a norm on either side of each part,
sigmoid-routed experts beside a shared one, against the plain reference
(benchmark/reference/afmoe.py) and numpy float64 loops on seeded random
weights at nano size on the CPU: a window of 9, contexts to 80, float32
throughout.

Tolerances: float32 sums in another order; logits are of order 4, so 2e-4
is five digits."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe as ref
from ray_tpu.inference import InferenceEngine, PagedKVCache
from ray_tpu.models import afmoe, decoder
from ray_tpu.ops import attention as ops
from tests import serving_script

NANO = afmoe.CONFIGS["afmoe-nano"]
LOGIT_TOL = 2e-4


def _init(cfg, seed=0):
    return serving_script.init_params(afmoe, cfg, seed)


def _params(cfg, seed=0):
    """Seeded weights with norm scales off one, so that a norm left out,
    misplaced or applied twice shows."""
    params = dict(_init(cfg, seed))
    for stack in ("lead_blocks", "blocks"):
        params[stack] = {
            k: v * (1.0 + 0.1 * jax.random.normal(jax.random.key(9), v.shape))
            if k.endswith("_norm") else v
            for k, v in params[stack].items()}
    return params


def _tokens(cfg, shape, seed=1):
    return jax.random.randint(jax.random.key(seed), shape, 0, cfg.vocab_size)


def test_the_spec_names_the_runs_of_like_layers_in_order():
    """Dense S S, then S F S S S F over experts: a full run has no rotation
    and no window, a window run has both; the two kinds' rows in pools of
    their own under tables of their own."""
    spec = afmoe.spec(NANO)
    runs = spec.runs
    assert [(r.blocks, r.n_layers, r.first, r.offset) for r in runs] == [
        ("lead_blocks", 2, 0, 0), ("blocks", 1, 2, 0), ("blocks", 1, 0, 1),
        ("blocks", 3, 3, 2), ("blocks", 1, 1, 5)]
    assert [r.pools for r in runs] == [(2, 3), (2, 3), (0, 1), (2, 3), (0, 1)]
    assert [r.table for r in runs] == [(1, 2), (1, 2), (0, 2), (1, 2), (0, 2)]
    win, full = runs[0].sizes, runs[2].sizes
    assert (win.rope_theta, win.window) == (10000.0, 9)
    assert (full.rope_theta, full.window) == (None, 0)
    assert win.gate and full.gate and win.qk_norm == full.qk_norm == 1e-5
    assert runs[0].attn is decoder.HEADS and runs[0].attn.trains
    assert runs[2].attn is decoder.HEADS
    assert runs[0].ffn is decoder.SWIGLU
    assert runs[1].ffn is decoder.SHARED_EXPERTS
    assert spec.attn_post_norm == ("attn_post_norm",)
    assert spec.mlp_post_norm == ("mlp_post_norm",)
    assert spec.mult.embedding == pytest.approx(8.0)
    assert decoder.layer_counts(spec, NANO) == {
        "kv": 8, "window": 6, "state": 0, "experts": 6}
    # the published pattern, where the config gives none: S S S F x 8
    kinds = afmoe.AfmoeConfig().kinds
    assert kinds.count(afmoe.FULL) == 8 and kinds.count(afmoe.WINDOW) == 24
    assert kinds[:4] == (afmoe.WINDOW,) * 3 + (afmoe.FULL,)
    # what the cache manager is told of each kind
    rows = [r.attn.rows(r.sizes) for r in runs]
    assert rows[0] == decoder.CacheRows(2, 16, slide=9)
    assert rows[2] == decoder.CacheRows(2, 16)
    # a model of one kind of layer still has no `slide`
    from ray_tpu.models import llama
    cfg = llama.CONFIGS["llama-nano"] if "llama-nano" in llama.CONFIGS \
        else next(iter(llama.CONFIGS.values()))
    assert decoder.HEADS.rows(cfg).slide == 0


def test_uncached_forward_matches_the_reference_on_logits():
    params = _params(NANO)
    tokens = _tokens(NANO, (2, 80))
    with jax.default_matmul_precision("highest"):
        got = serving_script.forward(afmoe, params, tokens, NANO)
    want = ref.logits(params, tokens)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    assert float(jnp.abs(want).max()) > 100 * LOGIT_TOL


def test_it_trains_at_nano_size_against_the_references_loss():
    """What `loss_fn` refused until the flash kernels took a window and
    the grouped multiply had a backward pass: the loss is the reference's
    cross-entropy plus 0.01 of the sigmoid routers' balancing losses (a
    token's scores as shares of their sum; 1 a layer where the router is
    even), every leaf but the selection bias (which only chooses) has a
    gradient, and steps down the gradient lower the loss."""
    import optax
    params = _params(NANO)
    tokens = _tokens(NANO, (2, 40))
    batch = {"tokens": tokens}
    loss, metrics = afmoe.loss_and_metrics(params, batch, NANO)
    logp = jax.nn.log_softmax(ref.logits(params, tokens)[:, :-1], -1)
    want = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
    assert float(loss - 0.01 * metrics["aux_loss"]) == pytest.approx(
        float(want), abs=LOGIT_TOL)
    assert 6 <= float(metrics["aux_loss"]) < 12          # 6 expert layers
    assert metrics["expert_load"].shape == (6, NANO.n_routed_experts)
    assert np.asarray(metrics["expert_load"]).sum(1).tolist() == [
        tokens.size * NANO.n_experts_per_tok] * 6
    grads = jax.grad(afmoe.loss_fn)(params, batch, NANO)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.isfinite(g).all(), path
        if "router_bias" not in str(path):
            assert float(jnp.abs(g).max()) > 0, path
    init_state, train_step = afmoe.make_train_step(NANO, optax.sgd(0.3))
    state = {**init_state(jax.random.key(0)), "params": params}
    step = jax.jit(train_step)
    losses = []
    for _ in range(4):
        state, out = step(state, batch)
        losses.append(float(out["loss"]))
    assert losses[0] == pytest.approx(float(loss), abs=1e-5)
    assert losses[-1] < losses[0] - 0.05


def _cached_logits(cfg, params, tokens, chunk, block_size=4, served=False):
    """Prefill `tokens` [L] in chunks of `chunk`, the last 30 one token at
    a time (the T=1 path), through a cache of both kinds whose blocks are
    dealt out of order, giving back the sliding kind's blocks as the window
    moves on; logits of every position, and the cache."""
    cache = PagedKVCache.for_model(afmoe, cfg, num_blocks=(40, 12),
                                   block_size=block_size, max_lanes=2,
                                   max_seq_len=96, ahead=chunk)
    assert cache.kind == "layered" and cache.v is None and not cache.latent
    assert [p.shape for p in cache.k] == [
        (2, 40, block_size, 128), (2, 40, block_size, 128),
        (6, 12, block_size, 128), (6, 12, block_size, 128)]
    cache.allocator.alloc(3)              # lane 1 does not start at block 0
    cache.parts[0].index.allocator.alloc(2)
    tree = afmoe.serving_params(params, cfg) if served else params
    (_, got), (pools, none), _ = serving_script.serve(
        afmoe, cfg, tree, cache, [None, tokens], chunk, [0, 1],
        prefill=[0, (len(tokens) - 30) // chunk * chunk], precision="highest")
    assert none is None and len(pools) == 4
    return got, cache


@pytest.mark.parametrize("served", [False, True],
                         ids=["raw_tree", "served_tree"])
def test_prefill_in_chunks_then_decode_matches_the_reference(served):
    """Through the cache (K and V rows of both kinds, the window's blocks
    given back behind it) = the reference's masked full forward: across the
    window's slide (9 of 80 positions), in chunks and at T=1."""
    params = _params(NANO)
    tokens = np.asarray(_tokens(NANO, (80,)))
    got, cache = _cached_logits(NANO, params, tokens, chunk=8, served=served)
    want = ref.row_logits(params, tokens)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # 80 tokens in blocks of 4: the window's 8 positions behind position 80
    # lie in slots 18 and 19 (72..79); every slot behind went back.
    assert sorted(cache.parts[0].held(1)) == [18, 19]
    assert cache.stats["slide_blocks_freed"] == 18
    assert len(cache.lane_blocks(1)) == 20            # the growing kind


# ---- one attention layer against a numpy float64 loop --------------------

def _np_attention(x, p, cfg, window, theta, per_head_norm=True, gate=True):
    """One layer's gated attention of normed x [L, D] in float64, a position
    and a head at a time.  `theta` None: no rotation."""
    x = np.asarray(x, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    length = x.shape[0]
    h, kh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    half = d // 2

    def norm(v, scale):
        return v / np.sqrt(np.mean(v * v) + cfg.norm_eps) * scale

    def rot(v, pos):
        if theta is None:
            return v
        ang = pos * theta ** (-np.arange(half) / half)
        a, b = v[:half], v[half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)])

    out = np.zeros((length, x.shape[1]))
    keys = np.zeros((length, kh, d))
    vals = np.zeros((length, kh, d))
    for t in range(length):
        for j in range(kh):
            k = x[t] @ p["wk"][:, j]
            keys[t, j] = rot(norm(k, p["k_norm"]) if per_head_norm else k, t)
            vals[t, j] = x[t] @ p["wv"][:, j]
    for t in range(length):
        lo = max(0, t - window + 1) if window else 0
        for i in range(h):
            q = x[t] @ p["wq"][:, i]
            q = rot(norm(q, p["q_norm"]) if per_head_norm else q, t)
            j = i // (h // kh)
            s = keys[lo:t + 1, j] @ q * d ** -0.5
            w = np.exp(s - s.max())
            o = (w / w.sum()) @ vals[lo:t + 1, j]
            if gate:
                o = o / (1.0 + np.exp(-(x[t] @ p["w_attn_gate"][:, i])))
            out[t] += o @ p["wo"][i]
    return out


def _layer(cfg, p, kind, x, **over):
    """One layer's whole-sequence attention (`decoder.HEADS.apply`) at the
    sizes of `kind`, with some replaced."""
    sizes = dataclasses.replace(cfg.sizes(kind), **over)
    with jax.default_matmul_precision("highest"):
        return decoder.HEADS.apply(x, p, afmoe.spec(cfg), sizes, None)


@pytest.mark.parametrize("kind", [afmoe.WINDOW, afmoe.FULL])
def test_a_layers_attention_is_the_float64_loops(kind):
    """Per-head q/k norm, the elementwise gate, the window that counts the
    token's own position, and rotation on WINDOW layers only: the loop with
    the other kind's rotation (a full layer rotated, a window layer not) is
    another function, as is the loop without the gate or with no norm."""
    cfg, params = NANO, _params(NANO)
    p = {k: v[3] for k, v in params["blocks"].items()
         if k in ("wq", "wk", "wv", "q_norm", "k_norm", "w_attn_gate", "wo")}
    x = jax.random.normal(jax.random.key(3), (1, 24, cfg.d_model))
    got = np.asarray(_layer(cfg, p, kind, x)[0])
    window = cfg.sliding_window if kind == afmoe.WINDOW else 0
    theta = cfg.rope_theta if kind == afmoe.WINDOW else None
    want = _np_attention(x[0], p, cfg, window, theta)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    swapped = _np_attention(x[0], p, cfg, window,
                            None if theta else cfg.rope_theta)
    assert np.abs(got - swapped).max() > 1e-2
    assert np.abs(got - _np_attention(x[0], p, cfg, window, theta,
                                      gate=False)).max() > 1e-2
    assert np.abs(got - _np_attention(x[0], p, cfg, window, theta,
                                      per_head_norm=False)).max() > 1e-3
    if window:
        assert np.abs(got - _np_attention(x[0], p, cfg, window + 1,
                                          theta)).max() > 1e-4


def test_the_block_has_a_norm_on_either_side_of_each_part():
    """x + N2(attn(N1 x)), then x + N4(ffn(N3 x)): against numpy on one
    dense layer, and another function with a post-norm left out."""
    cfg, params = NANO, _params(NANO)
    spec = afmoe.spec(cfg)
    run = spec.runs[0]
    p = {k: v[1] for k, v in params["lead_blocks"].items()}
    x = jax.random.normal(jax.random.key(5), (1, 20, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decoder._block(x, p, spec, run, cfg, None)[0][0])
        bare = np.asarray(decoder._block(
            x, p, dataclasses.replace(spec, attn_post_norm=()), run, cfg,
            None)[0][0])
    pn = {k: np.asarray(v, np.float64) for k, v in p.items()}

    def norm(v, scale):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True)
                           + cfg.norm_eps) * scale

    x64 = np.asarray(x[0], np.float64)
    a = _np_attention(norm(x64, pn["attn_norm"]), p, cfg, cfg.sliding_window,
                      cfg.rope_theta)
    x64 = x64 + norm(a, pn["attn_post_norm"])
    h = norm(x64, pn["mlp_norm"])
    gate = h @ pn["w_gate"]
    y = (gate / (1 + np.exp(-gate)) * (h @ pn["w_up"])) @ pn["w_down"]
    want = x64 + norm(y, pn["mlp_post_norm"])
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)
    assert np.abs(got - bare).max() > 1e-2


def test_the_router_chooses_by_the_biased_scores_and_weighs_by_the_unbiased():
    cfg, params = NANO, _params(NANO)
    p = {k: v[0] for k, v in params["blocks"].items()
         if k not in ("w_gate", "w_up", "w_down")}
    # a bias that decides: expert 3 always in, expert 5 never
    bias = jnp.zeros((cfg.n_routed_experts,)).at[3].set(10.).at[5].set(-10.)
    x = jax.random.normal(jax.random.key(4), (1, 24, cfg.d_model))
    want = np.asarray(ref.router_weights(x[0], p["router"], bias,
                                         cfg.n_experts_per_tok,
                                         cfg.routed_scale))
    assert (want[:, 3] > 0).all() and (want[:, 5] == 0).all()
    np.testing.assert_allclose(want.sum(-1), cfg.routed_scale, atol=1e-5)
    _, experts, weights = decoder._route(x, {**p, "router_bias": bias}, cfg)
    dense = np.zeros_like(want)
    np.put_along_axis(dense, np.asarray(experts), np.asarray(weights), -1)
    np.testing.assert_allclose(dense, want, atol=1e-5)


# ---- the windowed K/V attention of ops/attention.py ----------------------

def _ragged_pools(seed, lens, kh=2, d=128, bs=8, layers=2):
    """K and V pools [L, NB, BS, W] with each lane's blocks dealt out of
    order, only the blocks a window of 16 behind `lens` can reach given to
    the table (a sliding table names nothing behind them)."""
    rng = np.random.default_rng(seed)
    mb = -(-max(lens) // bs)
    nb = len(lens) * mb + 3
    k = jnp.asarray(rng.normal(size=(layers, nb, bs, kh * d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(layers, nb, bs, kh * d)), jnp.float32)
    perm = rng.permutation(nb - 1) + 1
    tables = perm[:len(lens) * mb].reshape(len(lens), mb).astype(np.int32)
    return k, v, tables, mb


@pytest.mark.parametrize("window", [16, 64])
def test_the_windowed_decode_kernel_reads_the_window_and_nothing_behind(
        window):
    """`window_paged_decode_attention` (interpreted) at ragged contexts
    shorter than, equal to and longer than the window, and an idle lane,
    against `paged_attention_reference` with the window this PR gives it;
    the table's slots behind a lane's window name block 0, whose rows are
    poison: a kernel that fetched and scored them would show it."""
    lens = [5, window, window + 1, 0, 3 * window + 5, 2 * window, 1]
    kh, h, d, bs = 2, 4, 128, 8
    k, v, tables, mb = _ragged_pools(0, lens, kh, d, bs)
    k = k.at[:, 0].set(1e4)
    v = v.at[:, 0].set(1e4)
    ctx = jnp.asarray(lens, jnp.int32)
    starts = jnp.maximum(ctx - window, 0)
    behind = np.arange(mb)[None, :] < np.asarray(starts)[:, None] // bs
    sliding = jnp.asarray(np.where(behind, 0, tables))
    q = jax.random.normal(jax.random.key(1), (len(lens), h, d))
    want = ops.paged_attention_reference(
        q[:, None], k, v, jnp.asarray(tables), ctx, (ctx - 1)[:, None], 1,
        kv_heads=kh, window=window)[:, 0]
    for per_step in (None, 2, 3):
        got = ops.window_paged_decode_attention(
            q, k, v, sliding, ctx, starts, 1, span=window, kv_heads=kh,
            blocks_per_step=per_step, use_kernel=True, interpret=True)
        live = np.asarray(lens) > 0
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live], atol=2e-5, rtol=0)
        assert not np.asarray(got)[~live].any()
    # the full kernel over the same pools is another function past a window
    full = ops.paged_decode_attention(q, k, v, jnp.asarray(tables), ctx, 1,
                                      kv_heads=kh, use_kernel=True,
                                      interpret=True)
    assert np.abs(np.asarray(full - want)[4]).max() > 1e-3
    np.testing.assert_allclose(np.asarray(full)[0], np.asarray(want)[0],
                               atol=2e-5, rtol=0)
    import inspect
    import re
    names = re.findall(r'name="(\w+)"', inspect.getsource(ops))
    assert names.count("window_paged_decode_attention") == 1
    assert names.count("paged_decode_attention") == 1


@pytest.mark.parametrize("window,ctx_tile", [(16, 16), (16, 32), (40, 16)])
def test_the_windowed_chunk_attention_is_the_reference_under_the_band(
        window, ctx_tile):
    """`paged_chunk_attention(window=)`: chunks of 12 rows that end shorter
    than, at and past the window, ragged valid prefixes, an idle lane;
    what lies behind a tile's window is poison and is not read."""
    kh, h, d, bs, t = 2, 4, 128, 8, 12
    ends = [7, window, 0, window + 9, 3 * window + 2]       # ctx after chunk
    n_valid = [7, 12, 0, 5, 12]
    k, v, tables, mb = _ragged_pools(2, [e + t for e in ends], kh, d, bs)
    k = k.at[:, 0].set(1e4)
    v = v.at[:, 0].set(1e4)
    ctx = jnp.asarray(ends, jnp.int32)
    first = np.asarray([e - n for e, n in zip(ends, n_valid)])
    pos = jnp.asarray(first[:, None] + np.arange(t)[None, :], jnp.int32)
    valid = jnp.asarray(np.arange(t)[None, :] < np.asarray(n_valid)[:, None])
    behind = np.arange(mb)[None, :] < np.maximum(
        first - window + 1, 0)[:, None] // bs
    sliding = jnp.asarray(np.where(behind, 0, tables))
    q = jax.random.normal(jax.random.key(3), (len(ends), t, h, d))
    want = ops.paged_attention_reference(
        q, k, v, jnp.asarray(tables), ctx, pos, 0, kv_heads=kh,
        window=window)
    got = ops.paged_chunk_attention(
        q, k, v, sliding, ctx, pos, valid, 0, kv_heads=kh, q_tile=4,
        ctx_tile=ctx_tile, window=window)
    mask = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(want)[mask],
                               atol=2e-5, rtol=0)
    assert not np.asarray(got)[2].any()         # the idle lane: no trip
    plain = ops.paged_chunk_attention(
        q, k, v, jnp.asarray(tables), ctx, pos, valid, 0, kv_heads=kh,
        q_tile=4, ctx_tile=ctx_tile)
    assert np.abs(np.asarray(plain - want)[4][mask[4]]).max() > 1e-3


# ---- through the engine ---------------------------------------------------

ENGINE = dict(auto_start=False, max_lanes=4, block_size=4,
              num_blocks=(96, 48), max_seq_len=96, prefill_chunk=8)


def _run(eng, *handles):
    while eng.step():
        pass
    return [h.tokens() for h in handles]


def test_the_engine_serves_the_references_greedy_tokens():
    cfg = NANO
    params = _init(cfg)
    eng = InferenceEngine("afmoe", cfg, params, **ENGINE, prefill_lanes=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (37, 21)]
    outs = _run(eng, *(eng.submit(p, 40) for p in prompts))
    for prompt, out in zip(prompts, outs):
        seq = prompt + out
        want = np.asarray(jnp.argmax(ref.row_logits(
            params, np.asarray(seq)), -1))
        assert out == want[len(prompt) - 1:len(seq) - 1].tolist()
    st = eng.stats()
    assert "sparse" not in st and "latent" not in st
    paged = st["paged"]
    assert paged["decode_steps"] > 0
    # 2 full layers read the context, 6 window layers at most 9 rows a lane
    assert paged["rows_full"] == 2 * paged["ctx_tokens"]
    assert 0 < paged["rows_window"] < 6 * paged["ctx_tokens"]
    assert paged["rows_window"] <= 6 * 9 * 2 * paged["decode_steps"]
    assert st["layers"] == {"kv": 8, "window": 6, "state": 0, "experts": 6}
    win = st["windows"]
    assert win["blocks_freed"] > 0
    assert win["sliding_bytes"] == 2 * 6 * 48 * 4 * 128 * 4
    assert win["growing_bytes"] == 2 * 2 * 96 * 4 * 128 * 4
    assert st["moe"]["layer_steps"] % 6 == 0          # 6 expert layers
    assert st["moe"]["assignments"] % cfg.n_experts_per_tok == 0
    # everything went back: no lane holds a block of either kind
    assert eng.cache.parts[0].index.allocator.num_free == 48
    assert eng.cache.allocator.num_free == 96
    steps = eng.compiled_steps()
    assert "t1" in steps and any(name.startswith("t8") for name in steps)
    assert all({"pool_copies", "weight_bytes_copied", "temp_bytes"} <= set(s)
               and "select_sorts" not in s for s in steps.values())


def test_an_adopted_shared_prefix_serves_the_same_tokens():
    """A 40-token document + question: the second request takes the
    document's 10 blocks from the growing kind and its last 2 from the
    sliding kind (positions 32..39 cover the window's 8 behind position
    40), decodes past the window so that adopted blocks are released in
    mid-decode, and the reference agrees with every token."""
    cfg = NANO
    params = _init(cfg)
    doc = np.random.default_rng(5).integers(0, 512, 40).tolist()
    eng = InferenceEngine("afmoe", cfg, params, **ENGINE)
    first = eng.generate(doc + [7, 8, 9], 12)
    hit0 = eng.stats()["prefix_hit_tokens"]
    freed0 = eng.stats()["windows"]["blocks_freed"]
    again = eng.generate(doc + [7, 8, 9], 12)
    assert again == first
    assert eng.stats()["prefix_hit_tokens"] - hit0 == 40
    assert eng.stats()["windows"]["blocks_freed"] - freed0 >= 2
    prompt = doc + [1, 2, 3]
    other = eng.generate(prompt, 20)
    want = np.asarray(jnp.argmax(ref.row_logits(
        params, np.asarray(prompt + other)), -1))
    assert other == want[len(prompt) - 1:len(prompt) + 19].tolist()
    # with the sliding tail evicted nothing of the document can be served,
    # and the answer is the same
    slide = eng.cache.parts[0].index
    for block, _key in list(slide.items()):
        slide.allocator.uncache(block)
        slide.evicted(block)
    assert eng.cache.match_len(prompt) == 0
    hit1 = eng.stats()["prefix_hit_tokens"]
    assert eng.generate(prompt, 20) == other
    assert eng.stats()["prefix_hit_tokens"] == hit1


def test_sliding_blocks_go_back_as_the_window_moves_and_never_one_attended():
    cfg = NANO
    eng = InferenceEngine("afmoe", cfg, _init(cfg), **ENGINE)
    cache, bs = eng.cache, 4
    h = eng.submit(list(range(1, 30)), 50)
    seen_peak = 0
    while eng.step():
        for lane, req in enumerate(eng._lanes):
            if req is None:
                continue
            held = cache.parts[0].held(lane)
            length = int(cache.seq_lens[lane])
            first = max(length - (cfg.sliding_window - 1), 0) // bs
            assert min(held, default=first) >= first
            assert all(slot in held
                       for slot in range(first, -(-length // bs)))
            seen_peak = max(seen_peak, len(held))
    assert len(h.tokens()) == 50
    assert seen_peak <= cache.parts[0].peak(True)
    assert cache.stats["slide_blocks_freed"] >= (29 + 50 - 8) // bs - 1


def test_the_wire_format_carries_both_pools_of_each_kind():
    from ray_tpu.serve.kv_tier.codec import KVBlockCodec
    cfg = NANO
    params = _init(cfg)
    a = InferenceEngine("afmoe", cfg, params, **ENGINE)
    prompt = list(range(1, 42))
    want = a.generate(prompt, 6)
    payload = a.export_prefix(prompt)
    assert payload["kind"] == "layered"
    assert payload["k"].shape == payload["v_pool"].shape == (2, 10, 4, 2, 16)
    assert np.abs(payload["v_pool"]).sum() > 0
    more = payload["more"]
    assert set(more) == {"slide_from", "slide"}
    # a match of 10 blocks reads the sliding kind's last two, K and V
    assert more["slide_from"] == 8
    assert [x.shape for x in more["slide"]] == [(6, 2, 4, 2, 16)] * 2
    assert not np.array_equal(*more["slide"])
    wire = KVBlockCodec.decode(KVBlockCodec.encode(payload))
    b = InferenceEngine("afmoe", cfg, params, **ENGINE)
    assert b.import_prefix(wire) == 12
    assert b.import_prefix(wire) == 0                  # idempotent
    assert b.generate(prompt, 6) == want
    assert b.stats()["prefix_hit_tokens"] == 40
    # a cache of latent rows of several kinds installs none of it
    from ray_tpu.models import dots3
    other = PagedKVCache.for_model(dots3, dots3.CONFIGS["dots3-nano"],
                                   num_blocks=(16, 8), block_size=4,
                                   max_lanes=1, max_seq_len=64)
    assert other.kind == "layered" and other.install_prefix(wire) == 0


def test_the_cell_rehearses_on_the_cpu():
    """`benchmark/run.py --rehearse`: the cell's whole path (the replica,
    the generator's shared documents, the prefix cache of both kinds, the
    reference check) at nano size.  (A window of 10 s: under the suite's
    six workers a request of 8-16 tokens takes seconds, and a window in
    which none runs to its end has nothing to compare and reads `correct`
    false, as dots3's rehearsal of 4 s does one run in five: PERF.md
    section 7.)"""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "serve_trinity_docs_decode", "--seed", "2147483659", "--seconds",
         "10", "--trace", "0", "--rehearse"], cwd=root, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and not line["failed"], (
        line, out.stderr[-600:])
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
