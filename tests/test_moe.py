"""The sparse-expert path (OLMoE's block in `models/llama.py`, `ops/moe.py`,
the engine's load counters) against the plain float32 reference
(`benchmark/reference/olmoe.py`), at nano size on the CPU with seeded
weights.

Tolerance: both sides compute in float32 from the same float32 weights, so
they differ only by the order of additions (the reference sums every expert
for every token, the program only the chosen ones; the kernel accumulates a
tile at a time): logits of magnitude 5 agree to 1e-4 with a margin of thirty
or more (2e-6 to 3e-6 observed).  Computing the router, an expert product or
the weights in bf16 moves logits by 1e-2 and more and fails every case here.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmoe as ref
from ray_tpu.inference import InferenceEngine
from ray_tpu.inference.kv_cache import PagedKVCache
from ray_tpu.models import decoder, llama
from ray_tpu.ops import moe
from tests import serving_script

CFG = llama.CONFIGS["olmoe-nano"]     # 2 layers, 64 wide, 16 experts, top-2
TOL = 1e-4
_LOADS = {}                           # n_layers -> the cached run's counters


def _tokens(shape, seed=1):
    return jax.random.randint(jax.random.key(seed), shape, 0, CFG.vocab_size)


@pytest.mark.parametrize("qk_norm", [True, False],
                         ids=["qk_norm", "no_qk_norm"])
@pytest.mark.parametrize("norm_topk_prob", [False, True],
                         ids=["probs_as_they_are", "norm_topk_prob"])
def test_forward_matches_the_reference(qk_norm, norm_topk_prob):
    cfg = dataclasses.replace(CFG, qk_norm=qk_norm,
                              norm_topk_prob=norm_topk_prob)
    params = serving_script.init_params(llama, cfg)
    assert ("q_norm" in params["blocks"]) == qk_norm
    # the norms' scales are ones at init: make them count
    params["blocks"] = {
        k: v * (1.0 + 0.1 * jax.random.normal(jax.random.key(7), v.shape))
        if k.endswith("_norm") else v for k, v in params["blocks"].items()}
    tokens = _tokens((3, 40))
    got = serving_script.forward(llama, params, tokens, cfg)
    want = ref.logits(params, tokens, top_k=cfg.n_experts_per_tok,
                      norm_topk_prob=norm_topk_prob)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(got, want, atol=TOL)
    if norm_topk_prob:      # and the option is not a no-op
        other = ref.logits(params, tokens, top_k=cfg.n_experts_per_tok)
        assert float(jnp.max(jnp.abs(other - want))) > 100 * TOL


@pytest.mark.parametrize("n_layers,scan_unroll", [(2, 1), (2, 2), (3, 2)],
                         ids=["2_layers", "2_layers_unroll_2",
                              "3_layers_unroll_2"])
def test_chunked_prefill_then_decode_through_the_paged_cache_matches(
        n_layers, scan_unroll):
    """Three lanes at different depths in one batch: a prefill chunk per
    lane, then decode steps, against the reference's full forward; the
    expert arrays go to their kernel whole, every other leaf is its layer's
    slice of its stack, and the load counters ride the loop whatever a
    trip holds (one layer, all, two and a remainder of one)."""
    cfg = dataclasses.replace(CFG, n_layers=n_layers,
                              scan_unroll=scan_unroll)
    params = serving_script.init_params(llama, cfg, 1)
    rows = np.asarray(_tokens((3, 30), seed=2))
    prefill = [5, 8, 3]                   # each lane's first chunk
    want = np.asarray(ref.logits(params, rows))
    bs, lanes = 8, 3
    cache = PagedKVCache.for_model(llama, cfg, num_blocks=lanes * 4 + 1,
                                   block_size=bs, max_lanes=lanes,
                                   max_seq_len=32)
    # one slice of 8 rows, then 6 of T=1, every lane at its own depth
    seqs = [rows[lane, :n + 6] for lane, n in enumerate(prefill)]
    got, _, load = serving_script.serve(
        llama, cfg, params, cache, seqs, max(prefill), [0, 1, 2],
        prefill=prefill, load=jnp.zeros((cfg.n_experts + 2,), jnp.int32))
    for lane, logits in enumerate(got):
        np.testing.assert_allclose(logits, want[lane, :len(logits)],
                                   atol=TOL, err_msg=f"lane {lane}")
    tokens_run = sum(len(seq) for seq in seqs)
    load = np.asarray(load)
    assert load[:-2].sum() == tokens_run * cfg.n_experts_per_tok \
        * cfg.n_layers                    # padding positions reach no expert
    assert load[-1] == 7 * cfg.n_layers   # (layer, step) pairs
    # the same layers count the same experts however many a trip holds
    np.testing.assert_array_equal(_LOADS.setdefault(n_layers, load), load)


@pytest.mark.parametrize("t, k, e, d, f, block_m", [
    (16, 2, 8, 64, 128, 8), (37, 2, 8, 64, 128, 8), (5, 3, 8, 64, 128, 16),
    (64, 8, 16, 128, 256, 16)])
@pytest.mark.parametrize("masked", [False, True], ids=["all_valid", "masked"])
def test_grouped_path_matches_the_dense_per_expert_loop(t, k, e, d, f,
                                                        block_m, masked):
    rng = np.random.default_rng(t)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(2, e, d, f)), jnp.float32)
                    / 8 for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(2, e, f, d)), jnp.float32) / 8
    ids = np.stack([rng.permutation(e)[:k] for _ in range(t)])
    ids[:, 0] = 3                         # a skewed router: 3 takes everyone
    ids[:, 1:][ids[:, 1:] == 3] = 4
    ids = jnp.asarray(ids, jnp.int32)
    weights = jnp.asarray(rng.random(size=(t, k)), jnp.float32)
    valid = jnp.asarray(rng.random(t) < 0.7) if masked else None
    got, load = moe.expert_ffn(x, ids, weights, w_gate, w_up, w_down, 1,
                               valid)
    want = jnp.zeros((t, d))
    for ex in range(e):
        hidden = jax.nn.silu(x @ w_gate[1, ex]) * (x @ w_up[1, ex])
        share = jnp.sum(jnp.where(ids == ex, weights, 0.0), 1)
        want += share[:, None] * (hidden @ w_down[1, ex])
    keep = np.ones(t, bool) if valid is None else np.asarray(valid)
    np.testing.assert_allclose(got, jnp.where(keep[:, None], want, 0.0),
                               atol=1e-4)
    assert np.asarray(load).tolist() == [
        int(((np.asarray(ids) == ex) & keep[:, None]).sum())
        for ex in range(e)]
    if block_m:                # the same rows through one explicit tiling
        order = jnp.argsort(ids.reshape(-1), stable=True)
        rows = x[order // k]
        counts = jnp.bincount(ids.reshape(-1), length=e)
        tiled = moe.grouped_matmul(rows, w_gate, counts, 1, block_m=block_m,
                                   block_n=f // 2)
        np.testing.assert_allclose(
            tiled, moe.grouped_matmul(rows, w_gate, counts, 1), atol=1e-5)


# (rows, group sizes, K, N, row tile, transposed, the layer of a stack or
# None for one layer's [G, K, N])
GROUPED_GRADS = {
    "plain": (40, [5, 0, 20, 7], 32, 24, 16, False, None),
    "transposed": (40, [5, 0, 20, 7], 32, 24, 16, True, None),
    "stacked_with_a_layer": (40, [5, 0, 20, 7], 32, 24, 16, False, 1),
    "stacked_and_transposed": (40, [9, 9, 0, 9], 24, 40, 8, True, 2),
    "rows_no_multiple_of_the_tile": (45, [11, 3, 0, 30], 32, 24, 16, False,
                                     None),
    "a_tile_of_several_groups": (32, [3, 2, 4, 1, 0, 9], 16, 128, 32, False,
                                 None),
}


@pytest.mark.parametrize("case", list(GROUPED_GRADS))
def test_grouped_matmul_gradients_match_a_loop_over_the_groups(case):
    """dx (the product's other form over dy) and dw (`_grouped_dw_kernel`)
    against `jax.grad` of a loop of plain products a group: a group
    nobody chose gets zeros, the rows behind the last group count for
    nothing, and of a stack only the layer read has a gradient."""
    m, sizes, k, n, block_m, transposed, layer = GROUPED_GRADS[case]
    rng = np.random.default_rng(m + k)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    shape = (len(sizes),) + ((n, k) if transposed else (k, n))
    w = jnp.asarray(rng.normal(size=((3,) if layer is not None else ())
                               + shape), jnp.float32) / 4
    held = sum(sizes)
    cot = jnp.asarray(rng.normal(size=(held, n)), jnp.float32)

    def kernel(x, w):
        y = moe.grouped_matmul(x, w, jnp.asarray(sizes), layer or 0,
                               block_m=block_m, transposed=transposed)
        return jnp.sum(y[:held] * cot)

    def loop(x, w):
        own = w if layer is None else w[layer]
        out, at = [], 0
        for g, size in enumerate(sizes):
            out.append(x[at:at + size] @ (own[g].T if transposed else own[g]))
            at += size
        return jnp.sum(jnp.concatenate(out) * cot)

    (dx, dw), (want_dx, want_dw) = (jax.grad(f, (0, 1))(x, w)
                                    for f in (kernel, loop))
    np.testing.assert_allclose(dx[:held], want_dx[:held], atol=1e-5)
    np.testing.assert_allclose(dw, want_dw, atol=1e-5)
    assert float(jnp.abs(want_dw).max()) > 0.1
    empty = sizes.index(0)
    assert not np.asarray(dw if layer is None else dw[layer])[empty].any()


def _dense_experts(x, ids, weights, w_gate, w_up, w_down, first=0,
                   valid=None, up_transposed=False):
    """Every held expert on every token, weighted by what the router gave
    it (zero where it was not chosen): the plain form of `expert_ffn`."""
    out = jnp.zeros_like(x)
    for ex in range(w_down.shape[0]):
        up = x @ (w_up[ex].T if up_transposed else w_up[ex])
        hidden = (jnp.square(jax.nn.relu(up)) if w_gate is None
                  else jax.nn.silu(x @ w_gate[ex]) * up)
        share = jnp.sum(jnp.where(ids == ex + first, weights, 0.0), 1)
        if valid is not None:
            share = share * valid
        out = out + share[:, None] * (hidden @ w_down[ex])
    return out


# (tokens, k, routed experts, held, the first held or None for all, width,
# expert width, `rows` bound, row tile, padding rows, relu2 experts)
EXPERT_GRADS = {
    "all_experts_held": (24, 2, 8, 8, None, 32, 24, None, 16, False, False),
    "padding_rows": (37, 2, 8, 8, None, 32, 24, None, 16, True, False),
    "a_share_with_first_held": (50, 4, 16, 4, 4, 32, 24, None, 16, False,
                                False),
    "a_share_inside_its_bound": (50, 4, 16, 4, 4, 32, 24, 96, 16, False,
                                 False),
    "a_share_past_its_bound_two_pages": (50, 4, 16, 4, 4, 32, 24, 32, 16,
                                         False, False),
    "a_share_past_its_bound_four_pages": (50, 4, 16, 4, 8, 32, 24, 16, 8,
                                          False, False),
    "a_bound_and_padding_rows": (41, 4, 16, 4, 0, 32, 24, 48, 16, True,
                                 False),
    "tokens_no_multiple_of_the_tile": (13, 3, 8, 8, None, 16, 40, None, 32,
                                       False, False),
    "relu2_experts_held_transposed": (30, 2, 8, 4, 2, 32, 24, 32, 16, False,
                                      True),
}


@pytest.mark.parametrize("case", list(EXPERT_GRADS))
def test_expert_ffn_gradients_match_the_dense_loop(case):
    """x, the router's weights and the three matrices against `jax.grad`
    of every held expert on every token; expert 1 of those held is
    nobody's choice (its gradient is zeros, not what memory held); with a
    `rows` bound that routing passes the pages behind the first are
    walked, and nothing is dropped: the load says so."""
    (t, k, routed, held, first, d, f, rows, block_m, padded,
     relu2) = EXPERT_GRADS[case]
    rng = np.random.default_rng(t * k)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    nobody = (first or 0) + 1
    ids = np.stack([rng.permutation(
        [e for e in range(routed) if e != nobody])[:k] for _ in range(t)])
    ids = jnp.asarray(ids, jnp.int32)
    weights = jnp.asarray(rng.random(size=(t, k)), jnp.float32)
    valid = jnp.asarray(rng.random(t) < 0.7) if padded else None
    w_gate = None if relu2 else jnp.asarray(
        rng.normal(size=(held, d, f)), jnp.float32) / 4
    w_up = jnp.asarray(rng.normal(size=(held, f, d) if relu2
                                  else (held, d, f)), jnp.float32) / 4
    w_down = jnp.asarray(rng.normal(size=(held, f, d)), jnp.float32) / 4
    cot = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    share = first if held < routed else None

    def kernel(x, weights, w_gate, w_up, w_down):
        y, load = moe.expert_ffn(x, ids, weights, w_gate, w_up, w_down,
                                 valid=valid, first_held=share,
                                 up_transposed=relu2, rows=rows,
                                 block_m=block_m)
        return jnp.sum(y * cot), load

    def dense(x, weights, w_gate, w_up, w_down):
        return jnp.sum(_dense_experts(
            x, ids, weights, w_gate, w_up, w_down, first or 0, valid,
            relu2) * cot)

    args = (x, weights, w_gate, w_up, w_down)
    wrt = tuple(i for i, a in enumerate(args) if a is not None)
    (got, load), grads = jax.value_and_grad(kernel, wrt, has_aux=True)(*args)
    want, want_grads = jax.value_and_grad(dense, wrt)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=2e-5)
        assert float(jnp.abs(w).max()) > 1e-3
    # dropless: every assignment of a valid token to a held expert counted
    keep = np.ones(t, bool) if valid is None else np.asarray(valid)
    lo = first or 0
    taken = ((np.asarray(ids) >= lo) & (np.asarray(ids) < lo + held)
             & keep[:, None])
    assert int(load.sum()) == int(taken.sum())
    if rows is not None and "past" in case:
        assert int(load.sum()) > rows
    assert int(load[1]) == 0 and not np.asarray(grads[-1])[1].any()


def _routing(rng, t, k, routed, kind, first, held):
    """[t, k] distinct experts a token, drawn as `kind` says."""
    ids = np.stack([rng.permutation(routed)[:k] for _ in range(t)])
    if kind == "one_expert_takes_everyone":
        taker = (first or 0) + 2
        for row in ids:                 # `taker` first, the others distinct
            rest = [e for e in row if e != taker][:k - 1]
            row[:] = [taker] + rest
    elif kind == "tokens_without_a_held_expert":
        elsewhere = [e for e in range(routed)
                     if not first <= e < first + held]
        for i in list(range(16, 32)) + list(range(40, t, 3)):
            ids[i] = rng.permutation(elsewhere)[:k]     # a whole tile too
    return jnp.asarray(ids, jnp.int32)


# (tokens, k, routed experts, held, the first held or None for all, the
# page's sorted rows or None for all, token tile, block of sorted rows, the
# router, padding tokens, the forward's weights or dx's ones, dtype)
COMBINE = {
    "an_even_router": (64, 2, 8, 8, None, None, 16, 8, "even", False, True,
                       jnp.float32),
    "one_expert_takes_every_token": (64, 2, 8, 8, None, None, 16, 8,
                                     "one_expert_takes_everyone", False,
                                     True, jnp.float32),
    "tokens_with_no_held_expert": (64, 4, 16, 4, 4, None, 16, 8,
                                   "tokens_without_a_held_expert", False,
                                   True, jnp.float32),
    "runs_across_blocks_and_blocks_of_two_experts": (
        96, 4, 8, 8, None, None, 32, 16, "even", False, True, jnp.float32),
    "tokens_no_multiple_of_the_tile": (37, 3, 8, 8, None, None, 16, 8,
                                       "even", False, True, jnp.float32),
    "a_share_in_pages": (50, 4, 16, 8, 4, 32, 16, 8, "even", False, True,
                         jnp.float32),
    "a_share_in_pages_one_expert_takes_everyone": (
        50, 4, 16, 4, 8, 16, 8, 8, "one_expert_takes_everyone", False, True,
        jnp.float32),
    "padding_tokens": (41, 4, 16, 4, 0, 48, 16, 8, "even", True, True,
                       jnp.float32),
    "weights_of_one_the_dx_form": (50, 4, 16, 8, 4, 32, 16, 8, "even",
                                   False, False, jnp.float32),
    "the_dx_form_over_all_rows": (64, 2, 8, 8, None, None, 16, 8, "even",
                                  False, False, jnp.float32),
    "bf16_rows_and_float32_weights": (64, 4, 16, 8, 4, 128, 32, 16, "even",
                                      False, True, jnp.bfloat16),
    "a_tile_of_all_the_tokens": (24, 2, 8, 8, None, None, 32, 8, "even",
                                 False, True, jnp.float32),
}


@pytest.mark.parametrize("case", list(COMBINE))
def test_moe_combine_matches_the_gather_of_every_assignments_row(case):
    """`moe_combine` over the sort's runs against `_gathered` (`ys[rank]`,
    a `where` and the weighted sum over k), page by page: what the held
    experts did not write holds NaN, which must reach no sum (behind the
    held experts' last block no work item reads, and `_zeros_behind` clears
    that block's end); a token with no held expert, and a whole tile
    of them, comes out zeros; the pages' results add up to the unpaged
    one's.  Float32 rows differ by the order of a token's k additions
    alone; bf16 rows by one rounding of that sum."""
    (t, k, routed, held, first, rows, tile, block_rows, kind, padded,
     weighted, dtype) = COMBINE[case]
    rng = np.random.default_rng(t + k)
    ids = _routing(rng, t, k, routed, kind, first, held)
    valid = jnp.asarray(rng.random(t) < 0.7) if padded else None
    sort = moe._dispatch(ids, held, valid, first)
    masks = (sort.flat < held if first is not None
             else None if valid is None else jnp.repeat(valid, k))
    n = t * k
    taken = int(sort.load.sum())
    made = jnp.asarray(rng.normal(size=(n, 24)), dtype)
    made = jnp.where(jnp.arange(n)[:, None] < taken, made, jnp.nan)
    weights = (jnp.asarray(rng.random(size=(t, k)), jnp.float32)
               if weighted else None)
    r = rows or n
    pages = -(-taken // r)
    if kind == "one_expert_takes_everyone":
        assert int(sort.load.max()) == t
    if "pages" in case:
        assert pages > 1
    total = want_total = 0.0
    for p in range(max(pages, 1)):
        page = jnp.take(made, p * r + jnp.arange(r), axis=0, mode="clip")
        _, _, rank, here = moe._page(p, r, sort, masks)
        want = moe._gathered(page, rank, here, k, weights)
        got = moe.moe_combine(
            moe._zeros_behind(page, sort, p, block_rows), sort, k, weights,
            tile=tile, p=p, block_rows=block_rows)
        assert got.shape == (t, 24) and got.dtype == dtype
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32),
            atol=1e-6 if dtype == jnp.float32 else 0,
            rtol=0 if dtype == jnp.float32 else 2 ** -7)
        total = total + got.astype(jnp.float32)
        want_total = want_total + want.astype(jnp.float32)
    assert np.isfinite(np.asarray(total)).all()
    assert float(jnp.abs(want_total).max()) > 1.0
    none_held = np.asarray((sort.flat.reshape(t, k) == held).all(1))
    if kind == "tokens_without_a_held_expert":
        assert none_held[16:32].all()
    assert not np.asarray(total)[none_held].any()
    if rows is not None and dtype == jnp.float32:     # and against no pages
        whole = moe.moe_combine(
            moe._zeros_behind(made, sort, block_rows=block_rows), sort, k,
            weights, tile=tile, block_rows=block_rows)
        np.testing.assert_allclose(total, whole, atol=1e-5)


# (tokens, k, routed, held, the first held or None, `rows`, padding rows,
# relu2 experts held transposed)
COMBINE_GRADS = {
    "all_experts_held": (70, 2, 8, 8, None, None, False, False),
    "a_share_in_two_pages": (100, 4, 16, 8, 4, 128, False, False),
    "a_bound_and_padding_rows": (90, 4, 16, 8, 0, 128, True, False),
    "relu2_experts_held_transposed": (70, 2, 8, 4, 2, None, False, True),
}


@pytest.mark.parametrize("case", list(COMBINE_GRADS))
def test_expert_ffn_gradients_with_the_combine_kernel_and_without(case):
    """`expert_ffn(token_tile=)` (the rows back through `moe_combine`,
    forward and dx) against the same call without it and against the dense
    loop: y and the gradients in x, the weights and the matrices agree to
    float32 reassociation, the load is the same, nothing is dropped."""
    t, k, routed, held, first, rows, padded, relu2 = COMBINE_GRADS[case]
    d, f = 32, 24
    rng = np.random.default_rng(t + k)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    ids = _routing(rng, t, k, routed, "even", first, held)
    weights = jnp.asarray(rng.random(size=(t, k)), jnp.float32)
    valid = jnp.asarray(rng.random(t) < 0.7) if padded else None
    w_gate = None if relu2 else jnp.asarray(
        rng.normal(size=(held, d, f)), jnp.float32) / 4
    w_up = jnp.asarray(rng.normal(size=(held, f, d) if relu2
                                  else (held, d, f)), jnp.float32) / 4
    w_down = jnp.asarray(rng.normal(size=(held, f, d)), jnp.float32) / 4
    cot = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    share = first if held < routed else None

    def through(token_tile):
        def loss(x, weights, w_gate, w_up, w_down):
            y, load = moe.expert_ffn(
                x, ids, weights, w_gate, w_up, w_down, valid=valid,
                first_held=share, up_transposed=relu2, rows=rows,
                block_m=16, token_tile=token_tile)
            return jnp.sum(y * cot), (y, load)
        return loss

    def dense(x, weights, w_gate, w_up, w_down):
        return jnp.sum(_dense_experts(
            x, ids, weights, w_gate, w_up, w_down, first or 0, valid,
            relu2) * cot)

    args = (x, weights, w_gate, w_up, w_down)
    wrt = tuple(i for i, a in enumerate(args) if a is not None)
    (_, (y, load)), grads = jax.value_and_grad(
        through(32), wrt, has_aux=True)(*args)
    (_, (y_plain, load_plain)), plain = jax.value_and_grad(
        through(None), wrt, has_aux=True)(*args)
    want = jax.grad(dense, wrt)(*args)
    np.testing.assert_allclose(y, y_plain, atol=1e-5)
    np.testing.assert_array_equal(load, load_plain)
    for g, same, w in zip(grads, plain, want):
        np.testing.assert_allclose(g, same, atol=1e-5)
        np.testing.assert_allclose(g, w, atol=2e-5)
        assert float(jnp.abs(w).max()) > 1e-3
    if rows is not None and not padded:
        assert int(load.sum()) > rows        # a page behind the first ran


@pytest.mark.parametrize("trace, want", [
    ({"busy_s": 2.0, "kernels": {"moe_combine": {"calls": 50,
                                                 "seconds": 0.08}}}, 4.0),
    ({"busy_s": 2.0, "kernels": {}}, None),      # a parent of the kernel's PR
    ({"busy_s": 0.0, "kernels": {"moe_combine": {"calls": 0,
                                                 "seconds": 0.0}}}, None),
    (None, None)], ids=["traced", "no_such_kernel", "nothing_ran",
                        "untraced"])
def test_the_combines_share_reads_its_kernel_or_nothing(trace, want):
    from benchmark import manifest
    read = manifest.module("layer_metrics", "moe_combine_share_pct").read
    got = read({"trace": trace})
    assert got is None if want is None else got == pytest.approx(want)
    entry = manifest.load().per_layer["moe_combine_share_pct"]
    assert entry["workloads"] == ["train_mellum2_8k_ep4share"]
    assert (entry["better"], entry["moves"]) == ("lower",
                                                 "train_tokens_per_s")


def test_no_token_is_dropped_when_one_expert_takes_every_token():
    """A router biased so that expert 3 is every token's first choice and
    expert 5 nobody's (a feature every token's embedding shares, and two
    router columns that read it): a capacity-bound dispatch would drop most
    of 3's tokens; the dropless one agrees with the reference on all."""
    params = serving_script.init_params(llama, CFG, 3)
    params["tok_embed"] = params["tok_embed"].at[:, 0].set(4.0)
    params["blocks"]["router"] = params["blocks"]["router"].at[
        :, 0, 3].set(8.0).at[:, 0, 5].set(-8.0)
    tokens = _tokens((2, 24), seed=4)
    np.testing.assert_allclose(
        serving_script.forward(llama, params, tokens, CFG),
        ref.logits(params, tokens, top_k=CFG.n_experts_per_tok), atol=TOL)
    eng = InferenceEngine("llama", CFG, params=params, max_lanes=2,
                          block_size=8, prefill_chunk=8, auto_start=False)
    prompt = np.asarray(tokens[0]).tolist()
    out = eng.generate(prompt, 8)
    load = eng.stats()["moe"]["expert_load"]
    routed = (len(prompt) + len(out) - 1) * CFG.n_layers
    assert load[3] == routed and load[5] == 0
    assert sum(load) == routed * CFG.n_experts_per_tok
    gaps, _ = ref.served_token_gaps(params, prompt, out)
    assert max(gaps) < TOL
    eng.shutdown()


def test_engine_serves_the_configuration_and_counts_its_expert_load():
    eng = InferenceEngine("llama", CFG, max_lanes=4, block_size=8,
                          prefill_chunk=8, auto_start=False, seed=3)
    assert eng.stats()["moe"] == {
        "assignments": 0, "expert_load": [0] * CFG.n_experts,
        "experts_hit": 0, "layer_steps": 0}
    prompts = [list(range(5, 25)), list(range(40, 47)), list(range(90, 123))]
    handles = [eng.submit(p, n) for p, n in zip(prompts, (12, 5, 9))]
    while eng.step():
        pass
    outs = [h.tokens() for h in handles]
    for prompt, out in zip(prompts, outs):
        gaps, ranks = ref.served_token_gaps(eng.params, prompt, out)
        assert len(gaps) == len(out) and max(gaps) < TOL and set(ranks) == {0}
    stats = eng.stats()
    # every prompt token and every generated token but a request's last
    # went through every layer's router once, and chose top-k experts
    tokens = sum(len(p) + len(o) - 1 for p, o in zip(prompts, outs))
    moe_stats = stats["moe"]
    assert moe_stats["assignments"] == sum(moe_stats["expert_load"]) \
        == tokens * CFG.n_experts_per_tok * CFG.n_layers
    # (an iteration that dispatched ran ONE program: a layer, a step)
    assert moe_stats["layer_steps"] == (
        stats["programs"]["programs"] * CFG.n_layers)
    assert stats["programs"]["programs"] == stats["programs"]["iterations"]
    assert 0 < moe_stats["experts_hit"] \
        <= moe_stats["layer_steps"] * CFG.n_experts
    # a dense configuration's stats carry no such key
    dense = InferenceEngine("llama", "llama-tiny", max_lanes=2,
                            auto_start=False)
    assert "moe" not in dense.stats()
    eng.shutdown(), dense.shutdown()


def test_a_bf16_param_dtype_engine_holds_no_float32_leaf():
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16,
                              param_dtype="bfloat16")
    eng = InferenceEngine("llama", cfg, max_lanes=2, block_size=8,
                          prefill_chunk=8, auto_start=False, seed=0)
    dtypes = {x.dtype for x in jax.tree.leaves(eng.params)}
    assert dtypes == {jnp.dtype(jnp.bfloat16)}
    assert eng.cache.k.dtype == jnp.bfloat16
    out = eng.generate(list(range(3, 20)), 6)
    assert len(out) == 6
    # bf16 weights against their own float32 upcast: activations round,
    # and a near-tie of the router may flip; the served token stays within
    # rounding of the reference's best
    gaps, _ = ref.served_token_gaps(eng.params, list(range(3, 20)), out)
    assert max(gaps) < 0.25
    eng.shutdown()
    # the presets and every configuration without the field stay float32
    assert {x.dtype for x in jax.tree.leaves(jax.eval_shape(
        lambda k: llama.init_params(llama.CONFIGS["llama-tiny"], k),
        jax.random.key(0)))} == {jnp.dtype(jnp.float32)}


def test_olmoe_sizes_specs_and_the_train_path():
    cfg = llama.LlamaConfig(
        vocab_size=50304, n_layers=16, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=1024, max_seq_len=4096, n_experts=64,
        n_experts_per_tok=8, qk_norm=True, param_dtype="bfloat16")
    shapes = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                            jax.random.key(0))
    assert shapes["blocks"]["w_gate"].shape == (16, 64, 2048, 1024)
    assert shapes["blocks"]["w_down"].shape == (16, 64, 1024, 2048)
    assert shapes["blocks"]["router"].shape == (16, 2048, 64)
    assert llama.num_params(cfg) == 6_919_161_856       # 13.84 GB in bf16
    specs = llama.param_specs(cfg)
    assert specs["blocks"]["w_up"] == ("layers", "experts", "embed",
                                       "expert_mlp")
    assert jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, tuple)) == jax.tree.structure(
        shapes)
    assert decoder.EXPERTS.whole == ("w_gate", "w_up", "w_down")


def _next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                         -1)[..., 0])


def test_olmoe_trains_at_nano_size_against_the_references_loss():
    """What `loss_fn` refused until the grouped multiply had a backward
    pass: the loss is the reference's cross-entropy plus 0.01 of the
    routers' balancing losses (1 a layer where the router is even, more
    where it is not), every leaf has a gradient, the load counts every
    assignment, and steps down the gradient lower the loss."""
    params = serving_script.init_params(llama, CFG, 5)
    tokens = _tokens((2, 24), seed=6)
    batch = {"tokens": tokens}
    loss, metrics = llama.loss_and_metrics(params, batch, CFG)
    want = _next_token_loss(
        ref.logits(params, tokens, top_k=CFG.n_experts_per_tok), tokens)
    assert float(loss - 0.01 * metrics["aux_loss"]) == pytest.approx(
        float(want), abs=TOL)
    assert CFG.n_layers <= float(metrics["aux_loss"]) < 2 * CFG.n_layers
    assert metrics["expert_load"].shape == (CFG.n_layers, CFG.n_experts)
    assert np.asarray(metrics["expert_load"]).sum(1).tolist() == [
        tokens.size * CFG.n_experts_per_tok] * CFG.n_layers
    import optax
    init_state, train_step = llama.make_train_step(CFG, optax.sgd(0.5))
    state = {**init_state(jax.random.key(0)), "params": params}
    grads = jax.grad(llama.loss_fn)(params, batch, CFG)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.isfinite(g).all() and float(jnp.abs(g).max()) > 0, path
    losses = []
    step = jax.jit(train_step)
    for _ in range(4):
        state, out = step(state, batch)
        losses.append(float(out["loss"]))
    assert losses[0] == pytest.approx(float(loss), abs=1e-5)
    assert losses[-1] < losses[0] - 0.1
