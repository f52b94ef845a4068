"""`benchmark/reference/mellum.py` against a numpy float64 loop that shares
nothing with it (both kinds of attention, YaRN's frequencies, the router and
the balancing loss); its layer-by-layer gradient against `jax.grad` of its
own one-trace loss; the configuration file against the catalog row;
`benchmark/swa_moe_train_flops.py` and the new readers on hand-computed
numbers.  CPU, tiny size."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, swa_moe_train_flops as counts
from benchmark.reference import mellum as ref

CELL = "train_mellum2_8k_ep4share"
NAMES = ("swa_moe_train_mfu_pct", "window_flash_roofline",
         "swa_full_flash_roofline", "moe_train_grouped_matmul_roofline",
         "moe_train_experts_share_pct")

D, H, KH, HD, FE, V = 32, 4, 2, 8, 24, 64
E, HELD, FIRST, K, WINDOW = 8, 4, 2, 3, 5
YARN = dict(factor=4.0, original=16, beta_fast=4.0, beta_slow=1.0,
            attention_factor=1.2)
SIZES = dict(eps=1e-6, theta=100.0, window=WINDOW, top_k=K, first=FIRST,
             yarn=YARN, norm_topk=True, q_block=4)


def _weights(seed=0, layers=4):
    """One period S S S F over a share (experts 2-5 of 8), float32, norm
    scales off one."""
    rng = np.random.default_rng(seed)

    def w(*shape, fan):
        return (rng.normal(size=shape) / np.sqrt(fan)).astype(np.float32)

    n = layers
    return {
        "tok_embed": w(V, D, fan=1), "final_norm": 1 + 0.1 * w(D, fan=1),
        "lm_head": w(D, V, fan=D),
        "blocks": {"attn_norm": 1 + 0.1 * w(n, D, fan=1),
                   "wq": w(n, D, H, HD, fan=D), "wk": w(n, D, KH, HD, fan=D),
                   "wv": w(n, D, KH, HD, fan=D),
                   "wo": w(n, H, HD, D, fan=H * HD),
                   "mlp_norm": 1 + 0.1 * w(n, D, fan=1),
                   "router": 2 * w(n, D, E, fan=D),
                   "w_gate": w(n, HELD, D, FE, fan=D),
                   "w_up": w(n, HELD, D, FE, fan=D),
                   "w_down": w(n, HELD, FE, D, fan=FE)}}


def _np_yarn(dim, theta, yarn):
    """YaRN's frequencies a dimension at a time, as the paper words them:
    the wavelengths that fit the original context more than beta_fast times
    are kept, those that fit less than beta_slow times are stretched by the
    factor, a linear ramp over the dimensions in between."""
    def dim_of(turns):
        return dim * math.log(yarn["original"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim_of(yarn["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        own = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(own / yarn["factor"] * ramp + own * (1 - ramp))
    return np.asarray(out)


def _np_loss(params, tokens, window=WINDOW, yarn=True, factor=True,
             norm_topk=True, first=FIRST):
    """(logits [B, L, V], loss) a sequence, a position, a head and an
    expert at a time in float64."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    tokens = np.asarray(tokens)
    n_tokens = tokens.size

    def norm(v, s):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + 1e-6) * s

    def silu(v):
        return v / (1 + np.exp(-v))

    def rot(v, pos, freqs, scale):
        half = HD // 2
        ang = pos * freqs
        a, b = v[:half], v[half:]
        return scale * np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                                       b * np.cos(ang) + a * np.sin(ang)])

    n_layers = p["blocks"]["wq"].shape[0]
    xs = [p["tok_embed"][row] for row in tokens]
    aux = 0.0
    for i in range(n_layers):
        b = {k: v[i] for k, v in p["blocks"].items()}
        full = i % 4 == 3
        own = 100.0 ** (-np.arange(HD // 2) / (HD // 2))
        freqs = _np_yarn(HD, 100.0, YARN) if full and yarn else own
        scale = YARN["attention_factor"] if full and factor else 1.0
        chose, prob_sum = np.zeros(E), np.zeros(E)
        for s, x in enumerate(xs):
            length = len(x)
            h = norm(x, b["attn_norm"])
            keys = np.zeros((length, KH, HD))
            vals = np.zeros((length, KH, HD))
            for t in range(length):
                for j in range(KH):
                    keys[t, j] = rot(h[t] @ b["wk"][:, j], t, freqs, scale)
                    vals[t, j] = h[t] @ b["wv"][:, j]
            attn = np.zeros_like(x)
            for t in range(length):
                lo = 0 if full else max(0, t - window + 1)
                for n in range(H):
                    q = rot(h[t] @ b["wq"][:, n], t, freqs, scale)
                    j = n // (H // KH)
                    sc = keys[lo:t + 1, j] @ q * HD ** -0.5
                    w = np.exp(sc - sc.max())
                    attn[t] += (w / w.sum()) @ vals[lo:t + 1, j] @ b["wo"][n]
            x = x + attn
            h2 = norm(x, b["mlp_norm"])
            y = np.zeros_like(x)
            for t in range(length):
                logit = h2[t] @ b["router"]
                prob = np.exp(logit - logit.max())
                prob = prob / prob.sum()
                pick = np.argsort(-prob, kind="stable")[:K]
                chose[pick] += 1
                prob_sum += prob
                for e in pick:
                    if first <= e < first + HELD:
                        weight = prob[e] / (prob[pick].sum() if norm_topk
                                            else 1.0)
                        y[t] += weight * (
                            silu(h2[t] @ b["w_gate"][e - first])
                            * (h2[t] @ b["w_up"][e - first])
                        ) @ b["w_down"][e - first]
            xs[s] = x + y
        aux += E * np.sum(chose / (n_tokens * K) * prob_sum / n_tokens)
    logits = np.stack([norm(x, p["final_norm"]) @ p["lm_head"] for x in xs])
    z = logits[:, :-1]
    logp = z - z.max(-1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(-1, keepdims=True))
    nll = -np.mean(np.take_along_axis(logp, tokens[:, 1:, None], -1))
    return logits, nll + 0.01 * aux


def test_yarns_frequencies_are_the_papers():
    np.testing.assert_allclose(ref.frequencies(HD, 100.0, YARN),
                               _np_yarn(HD, 100.0, YARN), rtol=1e-6)
    np.testing.assert_allclose(ref.frequencies(HD, 100.0),
                               100.0 ** (-np.arange(0, HD, 2) / HD),
                               rtol=1e-6)
    # the published numbers: dimension 0 keeps its frequency, the last is
    # divided by 16, and some lie between
    pub = ref.frequencies(128, 500000.0, ref.SIZES[2304]["yarn"])
    own = ref.frequencies(128, 500000.0)
    assert pub[0] == own[0] and pub[-1] == pytest.approx(own[-1] / 16)
    assert ((pub < own * 0.999) & (pub > own / 16 * 1.001)).any()
    assert ref.SIZES[2304]["yarn"]["attention_factor"] == pytest.approx(
        0.1 * math.log(16.0) + 1.0)


def test_the_reference_is_the_float64_loop():
    """Both kinds of attention, the router's top-k with its normalisation,
    the share's experts and the balancing loss over the whole batch, over
    14 positions (nearly three windows) of two sequences: the loop with a
    mechanism changed is another function by far more than the reference
    is off."""
    params = _weights()
    tokens = np.random.default_rng(1).integers(0, V, (2, 14))
    got = np.asarray(ref.logits(params, jnp.asarray(tokens), SIZES))
    got_loss = float(ref.loss(params, jnp.asarray(tokens), SIZES))
    want, want_loss = _np_loss(params, tokens)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    assert got_loss == pytest.approx(want_loss, abs=1e-5)
    assert ref.loss_by_layer(params, jnp.asarray(tokens), 1, SIZES) \
        == pytest.approx(want_loss, abs=1e-5)
    for change in (dict(window=WINDOW + 1), dict(yarn=False),
                   dict(factor=False), dict(norm_topk=False),
                   dict(first=FIRST + 1)):
        assert np.abs(got - _np_loss(params, tokens, **change)[0]).max() \
            > 1e-2, change


def test_the_balancing_loss_is_one_where_the_router_is_even():
    assert float(ref.balance(jnp.full((8,), 30.0), jnp.full((8,), 10.0),
                             80, 3)) == pytest.approx(1.0)
    # all on one expert of eight: f = P = 1 there, so E
    chose = jnp.zeros(8).at[2].set(80.0)
    assert float(ref.balance(chose, chose, 80, 1)) == pytest.approx(8.0)


def test_the_gradient_a_layer_at_a_time_is_the_whole_losss():
    """`loss_and_grad` (layer by layer, a micro-batch at a time, the
    balancing loss coupled over the batch through f) against `jax.grad` of
    the one-trace `loss`, and two AdamW steps against optax's."""
    import optax
    params = jax.tree.map(jnp.asarray, _weights(3))
    tokens = jnp.asarray(np.random.default_rng(4).integers(0, V, (3, 12)))
    want, want_grads = jax.value_and_grad(ref.loss)(params, tokens, SIZES)
    for micro in (1, 2, 3):
        got, grads = ref.loss_and_grad(params, tokens, micro, SIZES)
        assert got == pytest.approx(float(want), abs=1e-5)
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads)):
            np.testing.assert_allclose(g, w, atol=1e-6, err_msg=str(path))
            assert float(jnp.abs(w).max()) > 1e-5, path
    opt = optax.adamw(1e-3, weight_decay=1e-4)
    state = opt.init(params)
    theirs = jax.tree.map(jnp.copy, params)
    ours, ours_state = jax.tree.map(jnp.copy, params), ref.adamw_init(params)
    for _ in range(2):
        updates, state = opt.update(want_grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        ours, ours_state = ref.adamw_step(
            ours, want_grads, ours_state, learning_rate=1e-3,
            weight_decay=1e-4)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_allclose(a, b, atol=1e-6)


# -- the configuration file ---------------------------------------------------

@pytest.fixture(scope="module")
def config():
    return manifest.load().load_config("mellum2-12b-a2.5b")


def test_the_configuration_file_is_the_catalog_row_but_for_its_cuts(config):
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "mlp_layer_types", "num_experts",
                                 "vocab_size"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 24576)
    assert config["layer_types"] == published["layer_types"][:4]
    assert config["mlp_layer_types"] == ["sparse"] * 4
    assert published["vocab_size"] == 4 * 24576
    # the published widths, untouched
    assert [published[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "sliding_window", "num_experts", "moe_intermediate_size",
        "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")] == [
            2304, 32, 4, 128, 1024, 64, 896, 8, True, 1e-6]
    yarn = published["rope_parameters"]["full_attention"]
    assert [yarn[k] for k in ("rope_theta", "factor",
                              "original_max_position_embeddings",
                              "beta_fast", "beta_slow")] == [
        500000, 16, 8192, 32, 1]
    cfg = manifest.model_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.sliding_window, cfg.n_routed_experts, cfg.n_experts_held,
            cfg.d_expert, cfg.n_experts_per_tok, cfg.norm_eps) == (
        2304, 32, 4, 128, 1024, 64, 16, 896, 8, 1e-6)
    assert (cfg.rope_theta, cfg.rope_factor, cfg.rope_original,
            cfg.rope_beta_fast, cfg.rope_beta_slow, cfg.attention_factor) \
        == (500000.0, 16.0, 8192, 32.0, 1.0, yarn["attention_factor"])
    assert cfg.scoring_func == "softmax" and cfg.norm_topk_prob
    assert len(config["assumed"]) >= 10 and "four chips" in config[
        "deployment"]
    # the constants the reference keeps are the file's too
    s = ref.SIZES[2304]
    assert (s["eps"], s["theta"], s["window"], s["top_k"]) == (
        published["rms_norm_eps"], 500000.0, published["sliding_window"],
        published["num_experts_per_tok"])
    assert s["yarn"] == {"factor": 16.0, "original": 8192, "beta_fast": 32.0,
                         "beta_slow": 1.0,
                         "attention_factor": yarn["attention_factor"]}
    nano = ref.SIZES[config["rehearsal_fields"]["d_model"]]
    r = config["rehearsal_fields"]
    assert (nano["window"], nano["top_k"], nano["theta"]) == (
        r["sliding_window"], r["n_experts_per_tok"], r["rope_theta"])
    assert nano["yarn"]["attention_factor"] == r["attention_factor"]
    # the cell's traffic is the issue's, number for number
    m = manifest.load()
    assert m.cells[CELL] == {**m.cells[CELL], "config": "mellum2-12b-a2.5b",
                             "traffic": "train_b2x8192_moe", "chips": 1}
    traffic = m.load_traffic("train_b2x8192_moe")
    assert (traffic["driver"], traffic["generator"], traffic["batch"],
            traffic["seq"], traffic["n_batches"], traffic["mesh"]) == (
        "train_state", "token_batches", 2, 8192, 4, None)
    assert traffic["optimizer"] == {"name": "adamw", "args": {
        "learning_rate": 1e-4, "weight_decay": 1e-4}}
    assert (traffic["warmup_steps"], traffic["sync_steps"],
            traffic["trace_steps"]) == (4, 10, 5)
    assert (traffic["check"]["reference_steps"],
            traffic["check"]["micro_batch"]) == (2, 1)
    assert set(m.metrics_of(CELL, "per_layer")) >= set(NAMES)


# -- the yardstick's arithmetic ----------------------------------------------

def test_the_counts_are_the_hand_counts(config):
    f = config["fields"]
    assert counts.layer_kinds(f) == (3, 1)
    # a window's pairs: W L - W (W - 1) / 2, all of the triangle where the
    # window is the sequence or longer
    assert counts.window_pairs(8192, 1024) == 1024 * 8192 - 1024 * 1023 / 2
    assert counts.window_pairs(10, 3) == 1 + 2 + 3 * 8 == sum(
        min(t + 1, 3) for t in range(10))
    assert counts.window_pairs(8, 8) == counts.window_pairs(8, 99) == 36
    assert counts.expert_rows(f, 16384) == 32768
    parts = counts.forward_flops_per_token(f, 8192)
    # ISSUE 61's count a token: projections 170 M, head 113 M, the full
    # layer's scores 67 M, three window layers' 50 M, held experts 99 M
    assert parts["projections"] == 4 * 2 * (2 * 2304 * 4096 + 2 * 2304 * 512
                                            + 2304 * 64)
    assert parts["head"] == 2 * 2304 * 24576
    assert parts["full_scores"] == 4 * 4096 * 8193 / 2
    assert parts["window_scores"] == pytest.approx(
        3 * 4 * 4096 * (1024 * 8192 - 1024 * 1023 / 2) / 8192)
    assert parts["experts"] == 4 * 2 * 6 * 2304 * 896
    assert [round(parts[k] / 1e6) for k in (
        "projections", "head", "full_scores", "window_scores", "experts")] \
        == [171, 113, 67, 47, 99]
    assert counts.flops_per_token(f, 8192) * 16384 == pytest.approx(
        24.46e12, rel=1e-3)
    # unwindowed, the three layers' scores would be 201 M
    assert 3 * 4 * 4096 * 8193 / 2 == pytest.approx(201e6, rel=2e-3)
    work, nbytes = counts.window_flash(2, 32, 8192, 128, 1024)
    assert work == 6 * 2 * 128 * 2 * 32 * counts.window_pairs(8192, 1024)
    assert nbytes == 12 * 2 * 8192 * 4096 * 2 + 2 * 2 * 32 * 8192 * 4
    products = counts.grouped_products(f, 16384)
    assert len(products) == 9 and {w for w, _ in products} == {
        2 * 32768 * 2304 * 896}
    assert products[0][1] == 32768 * (2304 + 896) * 2 + 16 * 2304 * 896 * 2
    assert products[-1][1] == 32768 * (2304 + 896) * 2 + 16 * 2304 * 896 * 4


def _run(fields, traffic):
    kernels = {"window_flash_attention": {"calls": 45, "seconds": 0.30},
               "flash_attention": {"calls": 15, "seconds": 0.25},
               "moe_grouped_matmul": {"calls": 180, "seconds": 0.40},
               "moe_grouped_matmul_dw": {"calls": 60, "seconds": 0.20}}
    return {"fields": fields, "traffic": traffic,
            "device": {"kind": "TPU v5 lite", "count": 1},
            "end_to_end": {"train_tokens_per_s": 40000.0},
            "trace": {"busy_s": 2.0, "kernels": kernels}}


def test_every_new_reader_reads_a_number_and_nothing_without_kernels(config):
    m = manifest.load()
    traffic = m.load_traffic("train_b2x8192_moe")
    run = _run(config["fields"], traffic)
    values = {n: manifest.module("layer_metrics", n).read(run)
              for n in NAMES}
    assert all(v is not None and 0 < v <= 100 for v in values.values()), \
        values
    peak, steps = 197e12, traffic["trace_steps"]
    assert values["swa_moe_train_mfu_pct"] == pytest.approx(
        100 * counts.flops_per_token(config["fields"], 8192) * 40000 / peak)
    assert values["window_flash_roofline"] == pytest.approx(
        100 * 3 * steps * counts.window_flash(2, 32, 8192, 128, 1024)[0]
        / peak / 0.30)
    assert values["swa_full_flash_roofline"] == pytest.approx(
        100 * steps * 6 * 2 * 2 * 32 * 128 * 8192 * 8193 / 2 / peak / 0.25)
    assert values["moe_train_grouped_matmul_roofline"] == pytest.approx(
        100 * 4 * steps * 9 * 2 * 32768 * 2304 * 896 / peak / 0.60)
    assert values["moe_train_experts_share_pct"] == pytest.approx(30.0)
    # a program without the kernels (the parent): nothing, and no raise
    bare = {**run, "trace": {"busy_s": 2.0, "kernels": {}}}
    assert [manifest.module("layer_metrics", n).read(bare)
            for n in NAMES[1:]] == [None] * 4
    assert [manifest.module("layer_metrics", n).read({**run, "trace": None})
            for n in NAMES[1:]] == [None] * 4


@pytest.mark.parametrize("rows", [49152.0, 32768.0, 111000.0])
def test_the_readers_count_the_rows_the_run_carries(config, rows):
    """Where the driver carries the step's `expert_load`
    (`drivers/train_state.py`), the experts' roofline counts the traced
    steps' rows and the step's model FLOPs the window's: a share's router
    does not stay even."""
    m = manifest.load()
    traffic = m.load_traffic("train_b2x8192_moe")
    f = config["fields"]
    even = _run(f, traffic)
    run = {**even, "expert_rows": {"window": [rows] * 4,
                                   "traced": [rows / 2, rows * 3 / 2,
                                              rows, rows]}}
    read = {n: manifest.module("layer_metrics", n).read for n in NAMES}
    assert counts.rows_sent(run, "traced") == rows
    assert counts.rows_sent(even, "traced") is None
    assert counts.rows_sent({**even, "expert_rows": {"window": []}},
                            "window") is None          # no expert layer
    name = "moe_train_grouped_matmul_roofline"
    assert read[name](run) == pytest.approx(
        read[name](even) * rows / 32768, rel=0.02)   # (the matrices' bytes)
    products = counts.grouped_products(f, 16384, rows=rows)
    assert {w for w, _ in products} == {2 * rows * 2304 * 896}
    per_token = counts.forward_flops_per_token(f, 8192, rows / 16384)
    assert per_token["experts"] == pytest.approx(
        4 * rows / 16384 * 6 * 2304 * 896)
    assert read["swa_moe_train_mfu_pct"](run) == pytest.approx(
        100 * 3 * sum(per_token.values()) * 40000 / 197e12)


def test_params_change_reads_one_for_a_state_left_unchanged():
    """`drivers/train_state.py`'s comparison: 0 where the system's
    parameters are the reference's, 1 where they are still the seed's, and
    the worst leaf's quotient beside the whole tree's."""
    from benchmark.drivers.train_state import params_change
    first = {"a": np.zeros((4, 8), np.float32),
             "b": {"c": np.ones(16, np.float32)}}
    want = {"a": first["a"] + 0.25, "b": {"c": first["b"]["c"] - 0.5}}
    assert params_change(want, want, first) == {
        "whole": 0.0, "leaf": 0.0, "worst": None}
    same = params_change(first, want, first)
    assert same["whole"] == pytest.approx(1.0)
    assert same["leaf"] == pytest.approx(1.0)
    # one leaf of two left as it was: the whole tree hides it, the leaf not
    got = {"a": want["a"], "b": {"c": first["b"]["c"]}}
    one = params_change(got, want, first)
    assert one["leaf"] == pytest.approx(1.0) and one["worst"] == "['b']['c']"
    assert one["whole"] == pytest.approx(
        (16 * 0.25 / (32 * 0.0625 + 16 * 0.25)) ** 0.5)
    # a leaf the reference did not move is in no quotient of its own
    still = params_change({**got, "a": first["a"] + 1e-3},
                          {**want, "a": first["a"]}, first)
    assert still["worst"] == "['b']['c']" and still["whole"] > 1.0
