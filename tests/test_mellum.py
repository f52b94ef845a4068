"""Mellum (models/mellum.py; Mellum2-12B-A2.5B's family): window layers
beside YaRN-rotated full layers over K and V heads, each over softmax-routed
top-k experts, TRAINED: against the plain reference
(benchmark/reference/mellum.py) on seeded random weights at nano size on the
CPU, float32 throughout: logits, the loss with its balancing term, the
gradient of every leaf, two AdamW steps, the cached forward, and the shares
of an expert-parallel layer against the layer whole.

Tolerances: float32 sums in another order; logits are of order 4 and losses
of order 7, so 1e-4 is five digits."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import mellum as ref
from ray_tpu.inference import PagedKVCache
from ray_tpu.models import decoder, mellum
from tests import serving_script

NANO = mellum.CONFIGS["mellum-nano"]
TOL = 1e-4


def _params(cfg=NANO, seed=0):
    """Seeded weights with norm scales off one, so that a norm left out or
    misplaced shows."""
    params = dict(serving_script.init_params(mellum, cfg, seed))
    params["blocks"] = {
        k: v * (1.0 + 0.1 * jax.random.normal(jax.random.key(9), v.shape))
        if k.endswith("_norm") else v for k, v in params["blocks"].items()}
    return params


def _tokens(shape, seed=1):
    return jax.random.randint(jax.random.key(seed), shape, 0,
                              NANO.vocab_size)


def test_the_spec_names_the_runs_and_each_runs_rotation():
    """S S S F: a window run (theta's own frequencies, the window) and a
    full run (YaRN's frequencies and its factor on cos and sin, no window),
    both `HEADS`, which trains, over `EXPERTS`; the two kinds' rows in
    pools and tables of their own."""
    runs = mellum.spec(NANO).runs
    assert [(r.blocks, r.n_layers, r.first, r.offset) for r in runs] == [
        ("blocks", 3, 0, 0), ("blocks", 1, 0, 3)]
    assert [r.pools for r in runs] == [(2, 3), (0, 1)]
    assert [r.table for r in runs] == [(1, 2), (0, 2)]
    win, full = (r.sizes for r in runs)
    assert (win.window, win.rope_freqs, win.rope_scale) == (9, None, 1.0)
    assert full.window == 0 and full.rope_scale == pytest.approx(
        0.1 * np.log(4.0) + 1.0)
    np.testing.assert_allclose(
        full.rope_freqs, ref.frequencies(16, 1e4, ref.SIZES[64]["yarn"]),
        rtol=1e-6)
    assert full.rope_freqs[0] == 1.0 and full.rope_freqs[-1] == \
        pytest.approx(1e4 ** (-14 / 16) / 4.0)
    assert all(r.attn is decoder.HEADS and r.attn.trains
               and r.ffn is decoder.EXPERTS for r in runs)
    assert not hasattr(decoder, "WINDOW_HEADS")
    published = mellum.MellumConfig()
    assert len(published.kinds) == 28 and published.kinds[:4] == (
        mellum.WINDOW,) * 3 + (mellum.FULL,)
    assert mellum.num_params(dataclasses.replace(
        published, n_layers=4, n_experts_held=16,
        vocab_size=24576)) == 595_153_152         # x 16 bytes = 9.52 GB


def test_forward_matches_the_reference_on_logits():
    params = _params()
    tokens = _tokens((3, 40))
    got = serving_script.forward(mellum, params, tokens, NANO)[0]
    want = ref.logits(params, tokens)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("wrong", ["window", "norm_topk", "yarn",
                                   "attention_factor"])
def test_each_part_of_the_layer_moves_the_logits(wrong):
    """The agreement above is no accident of small numbers: the reference
    with another window, unnormalised top-k weights, no YaRN blend or no
    factor on cos and sin parts from the program."""
    s = dict(ref.SIZES[64])
    s.update({"window": {"window": 18}, "norm_topk": {"norm_topk": False},
              "yarn": {"yarn": {**s["yarn"], "factor": 1.0}},
              "attention_factor": {"yarn": {**s["yarn"],
                                            "attention_factor": 1.0}}}[wrong])
    params = _params()
    tokens = _tokens((2, 40))
    got = serving_script.forward(mellum, params, tokens, NANO)[0]
    assert float(jnp.abs(got - ref.logits(params, tokens, s)).max()) > 50 * TOL


@pytest.mark.parametrize("held", [0, 4], ids=["every_expert", "a_share"])
def test_loss_gradients_and_two_adamw_steps_match_the_reference(held):
    """`loss_fn` (cross-entropy + 0.01 of the routers' balancing losses),
    its gradient in every leaf (the grouped multiply's dx and dw, the
    dispatch's gathers, the windowed attention's and the router's own
    through the balancing loss), and the train step's AdamW against
    `reference.loss_and_grad` / `adamw_step`, as `drivers/train.py` calls
    them.  On a share (experts 0-3 of 16 held) the weights of the chosen
    experts teach the router nothing, on both sides: its gradient is the
    balancing loss's alone."""
    NANO = dataclasses.replace(globals()["NANO"], n_experts_held=held)
    params = _params(NANO)
    batches = [_tokens((2, 40), seed) for seed in (2, 3)]
    opt = {"learning_rate": 1e-3, "weight_decay": 1e-4}
    loss, metrics = mellum.loss_and_metrics(params, {"tokens": batches[0]},
                                            NANO)
    want, want_grads = ref.loss_and_grad(params, batches[0], 1)
    assert float(loss) == pytest.approx(want, abs=1e-5)
    assert want == pytest.approx(float(ref.loss(params, batches[0])),
                                 abs=1e-5)
    aux = float(metrics["aux_loss"])
    assert NANO.n_layers < aux < 2 * NANO.n_layers
    grads = jax.grad(mellum.loss_fn)(params, {"tokens": batches[0]}, NANO)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, atol=2e-6, err_msg=str(path))
        assert float(jnp.abs(w).max()) > 1e-4, path
    init_state, train_step = mellum.make_train_step(NANO, optax.adamw(**opt))
    state = {**init_state(jax.random.key(0)), "params": params}
    step = jax.jit(train_step)
    # (the reference's step gives up the tree it is handed: its own copy)
    ref_params = jax.tree.map(jnp.copy, params)
    ref_state = ref.adamw_init(params)
    for tokens in batches:
        state, out = step(state, {"tokens": tokens})
        want, g = ref.loss_and_grad(ref_params, tokens, 1)
        ref_params, ref_state = ref.adamw_step(ref_params, g, ref_state,
                                               **opt)
        assert float(out["loss"]) == pytest.approx(want, abs=2e-5)
        # every one of the T x k assignments is some expert's: none dropped
        if not held:
            assert np.asarray(out["expert_load"]).sum(1).tolist() == [
                tokens.size * NANO.n_experts_per_tok] * NANO.n_layers
    # (AdamW divides a gradient by its own size: an element whose gradient
    # is rounding noise moves by up to the learning rate either way; two
    # steps move an element by 2e-3, and 2e-4 of that may be such noise)
    for (path, got), want, was in zip(
            jax.tree_util.tree_leaves_with_path(state["params"]),
            jax.tree.leaves(ref_params), jax.tree.leaves(params)):
        np.testing.assert_allclose(got, want, atol=2e-4, err_msg=str(path))
        assert float(jnp.abs(want - was).max()) > 1e-3, path


def test_prefill_in_chunks_then_decode_matches_the_reference():
    """Through the cache (a full layer's K and V rows in the growing
    table, the window layers' in the sliding one, the full layer's rotated
    by YaRN's frequencies at each row's own position) = the reference's
    whole forward."""
    params = _params()
    tokens = np.asarray(_tokens((72,)))
    cache = PagedKVCache.for_model(mellum, NANO, num_blocks=(40, 12),
                                   block_size=4, max_lanes=2, max_seq_len=96,
                                   ahead=8)
    assert [p.shape[0] for p in cache.k] == [1, 1, 3, 3]
    (_, got), _, _ = serving_script.serve(
        mellum, NANO, mellum.serving_params(params, NANO), cache,
        [None, tokens], 8, [0, 1], prefill=[0, 40], precision="highest")
    want = ref.logits(params, jnp.asarray(tokens)[None])[0]
    np.testing.assert_allclose(got, want, atol=2 * TOL, rtol=0)
    assert cache.stats["slide_blocks_freed"] > 0


def test_four_shares_add_up_to_the_whole_layer_and_their_grads_are_slices():
    """One chip of four holds 4 of the 16 experts (`n_experts_held`,
    `experts_offset`): the same seed draws a share's experts as the whole
    layer's slice, the four shares' expert layers add up to the uncut
    layer's result, their loads are its load's slices, the gradient of a
    share's experts is the slice of the whole layer's (the rows that fall
    on experts held elsewhere add nothing and cost no gradient), and the
    four shares' gradients of the ROUTER and of the layer's input add up to
    the uncut layer's: a share's is its rank's part of a sum, with nothing
    cut from it.  A share's sorted rows are held to a bound
    (`expert_ffn(rows=)`) that the first share below passes: its router
    sends it every token twice."""
    whole = dataclasses.replace(NANO, n_layers=1,
                                layer_types=(mellum.WINDOW,))
    params = _params(whole)["blocks"]
    p = {k: v[0] for k, v in params.items()}
    # every token's first two choices are experts 1 and 2: share 0 gets
    # 128 + its part of the other 128 assignments, past its bound of 128
    # (the other two fall on every share)
    u = jnp.sign(jax.random.normal(jax.random.key(7), (whole.d_model,)))
    p["router"] = p["router"].at[:, 1:3].add(
        6.0 / whole.d_model * u[:, None])
    h = jax.random.normal(jax.random.key(5), (2, 32, whole.d_model)) + u
    cot = jax.random.normal(jax.random.key(6), h.shape)

    def layer(leaves, cfg):
        y, aux, load = decoder.moe_ffn(
            leaves["h"], {**p, **leaves, "layer": 0}, cfg)
        return jnp.sum(y * cot), (y, aux, load)

    names = ("w_gate", "w_up", "w_down")
    shared = {"router": p["router"], "h": h}
    (_, (y, aux, load)), grads = jax.value_and_grad(layer, has_aux=True)(
        {**{k: p[k] for k in names}, **shared}, whole)
    total = 0.0
    summed = {k: 0.0 for k in shared}
    for rank in range(4):
        cfg = dataclasses.replace(whole, n_experts_held=4,
                                  experts_offset=4 * rank)
        own = serving_script.init_params(mellum, cfg, 0)["blocks"]
        for k in names:
            np.testing.assert_array_equal(
                own[k][0],
                _params(whole)["blocks"][k][0, 4 * rank:4 * rank + 4])
        held = {k: p[k][4 * rank:4 * rank + 4] for k in names}
        (_, (y_own, aux_own, load_own)), g_own = jax.value_and_grad(
            layer, has_aux=True)({**held, **shared}, cfg)
        total = total + y_own
        assert np.asarray(load_own).tolist() == np.asarray(
            load)[4 * rank:4 * rank + 4].tolist()
        assert float(aux_own) == pytest.approx(float(aux), abs=1e-6)
        for k in names:
            np.testing.assert_allclose(
                g_own[k], grads[k][4 * rank:4 * rank + 4], atol=1e-5)
        for k in shared:
            summed[k] = summed[k] + g_own[k]
    np.testing.assert_allclose(total, y, atol=1e-5)
    for k in shared:
        np.testing.assert_allclose(summed[k], grads[k], atol=2e-5,
                                   err_msg=k)
        assert float(jnp.abs(grads[k]).max()) > 1e-3, k
    assert all(np.asarray(load).reshape(4, 4).sum(1) > 0)
    assert int(load.sum()) == 64 * whole.n_experts_per_tok
    assert int(load[:4].sum()) > 2 * 64 * 4 * 4 // 16          # the bound


def test_the_cell_rehearses_on_the_cpu_and_its_state_is_compared():
    """`benchmark/run.py --rehearse` of `train_mellum2_8k_ep4share`: the
    cell's whole path at nano size (a leased worker, `make_train_step`, the
    window, the reference's two AdamW steps) under `drivers/train_state.py`,
    which compares the PARAMETERS after those steps beside the losses: in
    float32 on the CPU the program's change of them is the reference's to
    four digits, where a state left unchanged would read 1."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "train_mellum2_8k_ep4share", "--seed", "2147483659", "--seconds",
         "2", "--trace", "0", "--rehearse"], cwd=root, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and not line["failed"], (
        line, out.stderr[-600:])
    compared = line["compared"]
    assert set(compared) == {
        "loss_diff_step0", "loss_diff_step1", "loss_diff_step2",
        "params_change_diff", "params_change_diff_leaf"}
    assert 0 < compared["params_change_diff"]["value"] < 1e-3
    assert compared["params_change_diff_leaf"]["value"] < 1e-3
    assert "rows a layer's held experts took a step, window" in out.stdout
