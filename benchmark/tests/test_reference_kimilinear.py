"""The Kimi Linear reference against a second, independent formulation (a KDA
layer and the router written once more as the equations read: numpy float64,
a loop over positions, the convolutions by indexing, the recurrence a head
at a time with its matrices written out, each token's chosen experts one by
one, nothing in blocks), on which layers the served stage holds, on what the
check must catch, and on the arithmetic of `kda_flops.py` and its readers
against hand counts."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import flops, kda_flops, manifest
from benchmark.layer_metrics import (kda_grouped_matmul_roofline,
                                     kda_latent_step_roofline,
                                     kda_mix_share_pct, kda_scan_roofline,
                                     kda_update_roofline)
from benchmark.reference import kimilinear as ref
from ray_tpu.models import kimilinear

CFG = kimilinear.CONFIGS["kimilinear-nano"]          # float32
CELL = "serve_kimilinear_reasoning_decode"


@pytest.fixture(scope="module")
def params():
    p = kimilinear.init_params(CFG, jax.random.key(0))
    for i, stack in enumerate(("dense_kdas", "kdas", "mlas")):
        noisy = {k: 1.0 + 0.1 * jax.random.normal(jax.random.key(9 + i),
                                                  v.shape)
                 for k, v in p[stack].items() if k.endswith("norm")}
        p[stack] = {**p[stack], **noisy}
    return p


def _f64(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


def kda_second_formulation(u, w):
    """u [L, D] (normed) -> [L, D]: Kimi Delta Attention a position and a
    head at a time, the state's update written as the one matrix
    (I - beta k k^T) diag(alpha) S + beta k v^T."""
    n = u.shape[0]
    heads = w["A_log"].shape[0]
    wide = w["w_fb"].shape[1]
    d = wide // heads
    taps = w["conv_w"].shape[0]

    def silu(x):
        return x / (1.0 + np.exp(-x))

    proj = u @ w["w_qkv"]
    conv = np.zeros_like(proj)
    for t in range(n):
        for tap in range(taps):
            src = t - (taps - 1) + tap
            if src >= 0:
                conv[t] += w["conv_w"][tap] * proj[src]
    conv = silu(conv)
    decay = np.log1p(np.exp((u @ w["w_fa"]) @ w["w_fb"] + w["dt_bias"]))
    beta = 1.0 / (1.0 + np.exp(-(u @ w["w_beta"])))
    gate = 1.0 / (1.0 + np.exp(-((u @ w["w_ga"]) @ w["w_gb"])))
    out = np.zeros((n, wide))
    for j in range(heads):
        cols = slice(j * d, (j + 1) * d)
        q, k, v = (conv[:, i * wide:(i + 1) * wide][:, cols]
                   for i in range(3))
        state = np.zeros((d, d))
        for t in range(n):
            q_t = q[t] / np.sqrt(q[t] @ q[t] + 1e-6) * d ** -0.5
            k_t = k[t] / np.sqrt(k[t] @ k[t] + 1e-6)
            alpha = np.exp(-np.exp(w["A_log"][j]) * decay[t, cols])
            state = (np.eye(d) - beta[t, j] * np.outer(k_t, k_t)) @ (
                alpha[:, None] * state) + beta[t, j] * np.outer(k_t, v[t])
            o = state.T @ q_t
            o = o / np.sqrt((o * o).mean() + 1e-5) * w["o_norm"]
            out[t, cols] = o * gate[t, cols]
    return out @ w["w_out"]


def router_second_formulation(u, w, top_k, scale):
    """[L, E] weights: each token's chosen experts one by one."""
    s = 1.0 / (1.0 + np.exp(-(u @ w["router"])))
    out = np.zeros_like(s)
    for t in range(u.shape[0]):
        order = sorted(range(s.shape[1]),
                       key=lambda e: (-(s[t, e] + w["router_bias"][e]), e))
        chosen = order[:top_k]
        total = sum(s[t, e] for e in chosen)
        for e in chosen:
            out[t, e] = s[t, e] / total * scale
    return out


def test_the_kda_layer_is_the_recurrence_a_position_and_a_head_at_a_time(
        params):
    w = {k: v[1] for k, v in params["kdas"].items()}
    u = np.asarray(jax.random.normal(jax.random.key(3), (19, 64)))
    s = dict(ref.sizes_of(params))
    with ref.HIGHEST():
        got = np.asarray(ref.kda(jax.numpy.asarray(u), w, s))
    want = kda_second_formulation(u.astype(np.float64), _f64(w))
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())
    # and each wrong mechanism is another layer
    for over in (dict(delta=False), dict(decay="head"), dict(beta_one=True),
                 dict(l2=False), dict(state_dtype="bfloat16")):
        with ref.HIGHEST():
            moved = np.asarray(ref.kda(jax.numpy.asarray(u), w,
                                       dict(s, **over)))
        assert np.abs(moved - want).max() > 1e-3 * np.abs(want).max(), over


def test_the_router_chooses_by_score_plus_bias_and_scales_by_2_446(params):
    w = {k: v[0] for k, v in params["mlas"].items()}
    w["router_bias"] = w["router_bias"] + 0.3 * np.sin(np.arange(16))
    u = np.asarray(jax.random.normal(jax.random.key(4), (23, 64)))
    with ref.HIGHEST():
        got = np.asarray(ref.router_weights(
            jax.numpy.asarray(u), w["router"], w["router_bias"], 4, 2.446,
            0.0))
    want = router_second_formulation(u.astype(np.float64), _f64(w), 4, 2.446)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got.sum(-1), 2.446, rtol=1e-5)
    # the bias moved some token's choice, and is not in the weights
    plain = router_second_formulation(
        u.astype(np.float64), dict(_f64(w), router_bias=np.zeros(16)), 4,
        2.446)
    assert ((plain > 0) != (want > 0)).any()


def test_the_held_layers_are_the_published_layers_1_to_8():
    """The configuration's file cuts the published lists to their entries
    up to 8, K(dense) K K M K K K M, and the reference goes by the file."""
    m = manifest.load()
    file = m.load_config("kimi-linear-48b-a3b")
    pub, cut = (c["linear_attn_config"] for c in (file["published"], file))
    assert file["num_hidden_layers"] == 8
    assert pub["kda_layers"][:6] == cut["kda_layers"] == [1, 2, 3, 5, 6, 7]
    assert pub["full_attn_layers"][:2] == cut["full_attn_layers"] == [4, 8]
    assert sorted(pub["kda_layers"] + pub["full_attn_layers"]) == list(
        range(1, 28))
    served = ref._sizes_by_width()[2304]
    kinds = ref.kinds_of(served["kda_layers"], served["full_attn_layers"],
                         served["dense"])
    assert [(k[2], k[3]) for k in kinds] == [
        ("kda", True), ("kda", False), ("kda", False), ("mla", False),
        ("kda", False), ("kda", False), ("kda", False), ("mla", False)]
    assert [k[:2] for k in kinds] == [
        ("dense_kdas", 0), ("kdas", 0), ("kdas", 1), ("mlas", 0),
        ("kdas", 2), ("kdas", 3), ("kdas", 4), ("mlas", 1)]
    # every width as published; only depth, the lists, the experts held
    # and the vocabulary differ
    differ = {k for k in file["published"]
              if file["published"][k] != file.get(k)}
    assert differ == set(file["reduced"]) == {
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"}
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert pub[key] == cut[key]
    fields = file["fields"]
    assert (fields["n_routed_experts"], fields["n_experts_held"]) == (256, 64)
    cfg = manifest.model_config(file, None)
    assert tuple(fields["kda_layers"]) == cfg.kda_layers
    with pytest.raises(ValueError, match="both lists or in neither"):
        ref.kinds_of((1, 2), (2, 4), 1)


F = {"d_model": 2304, "kda_heads": 32, "kda_head_dim": 128, "kda_conv": 4,
     "n_heads": 32, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
     "qk_rope_head_dim": 64, "v_head_dim": 128, "d_ff": 9216,
     "d_expert": 1024, "n_routed_experts": 256, "n_experts_held": 64,
     "n_shared_experts": 1, "n_experts_per_tok": 8, "vocab_size": 40960}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_kda_flops_against_hand_counts():
    state = 32 * 128 * 128                      # numbers a lane a layer
    assert kda_flops.state_numbers(F) == state == 524288
    rows = 32 * (5 * 128 + 1)                   # q, k, g, v, o and beta
    assert kda_flops.update(128, F) == (
        8.0 * 128 * state, 4.0 * 128 * (2 * state + rows))
    # the update is bound by its bytes: 0.54 GB a layer at 128 lanes
    least, bound = flops.roofline_s(*kda_flops.update(128, F), PEAKS)
    assert bound == "memory" and 0.65e-3 < least < 0.67e-3
    # a scan of 1,024 tokens over 4 rows: the recurrence's operations a
    # token, four states read and written once
    assert kda_flops.scan(1024, 4, F) == (
        8.0 * 1024 * state, 4.0 * (4 * 2 * state + 1024 * rows))
    # a KDA mixer's weights: 39.5M parameters (ISSUE 57's count)
    assert abs(kda_flops.kda_weight_bytes(F) / 2 - 39.5e6) < 0.1e6
    assert abs(kda_flops.latent_weight_bytes(F) / 2 - 29.1e6) < 0.1e6
    n = {"state": 6, "kv": 2, "experts": 7}
    whole = kda_flops.step_weight_bytes(F, n, 64.0)
    # every held expert hit: the 7.545 GB the tree holds, less the
    # embedding's table (a step looks 128 rows of it up)
    assert abs(whole - (7.545e9 - 2 * 40960 * 2304)) < 0.01e9
    assert whole - kda_flops.step_weight_bytes(F, n, 63.0) \
        == 7 * 3 * 2304 * 1024 * 2
    assert kda_flops.latent_bytes(F, n, 1000.0) == 2 * 2 * 1000.0 * 576
    assert kda_flops.tail_bytes(F, n, 128) == 2 * 6 * 128 * 2 * 3 * 12288


def _run(**over):
    stats = lambda steps, updated, scanned, hit, held: {
        "layers": {"kv": 2, "window": 0, "state": 6, "experts": 7},
        "ssm": {"tokens_updated": updated, "tokens_scanned": scanned},
        "latent": {"decode_steps": steps, "ctx_tokens": 0},
        "prefill": {"steps": steps // 10, "lanes": steps // 10},
        "moe": {"assignments": 0, "assignments_held": held,
                "expert_load": [0] * 64, "experts_hit": hit,
                "layer_steps": 7 * steps}}
    run = {
        "fields": F, "device": {"kind": "TPU v5 lite"},
        "traffic": {"engine": {"max_lanes": 128},
                    "trace": {"at_s": 10.0, "slice_s": 2.0}},
        "stats0": stats(0, 0, 0, 0, 0),
        "stats1": stats(1000, 128000, 25600, 7 * 1000 * 63,
                        7 * 1000 * 256),
        "base": 100.0, "marks": {"trace_on": 110.0, "trace_off": 112.0},
        "engine_events": [{"kind": "step", "ts": 111.0,
                           "payload": {"decode_ctx": 640000}}],
        "trace": {"busy_s": 1.8, "kernels": {
            "kda_update": {"calls": 600, "seconds": 0.5},
            "kda_scan": {"calls": 60, "seconds": 0.1},
            "moe_grouped_matmul": {"calls": 2100, "seconds": 0.9}}}}
    run.update(over)
    return run


def test_the_readers_against_hand_counts():
    run = _run()
    least = flops.roofline_s(*kda_flops.update(128, F), PEAKS)[0]
    assert kda_update_roofline.read(run) == pytest.approx(
        100 * least * 600 / 0.5)
    assert kda_mix_share_pct.read(run) == pytest.approx(100 * 0.6 / 1.8)
    # 256 tokens a chunk over one row, by the window's averages
    least = flops.roofline_s(*kda_flops.scan(256.0, 1.0, F), PEAKS)[0]
    assert kda_scan_roofline.read(run) == pytest.approx(
        100 * least * 60 / 0.1)
    # 100 programs (600 update calls over 6 KDA layers), each the weights
    # at 63 experts hit, 640,000 latent rows in 2 layers, 128 lanes' states
    # and tails in 6
    n = run["stats1"]["layers"]
    nbytes = 100 * (kda_flops.step_weight_bytes(F, n, 63.0)
                    + 2 * 2 * 640000 * 576
                    + 6 * kda_flops.update(128, F)[1]
                    + kda_flops.tail_bytes(F, n, 128))
    assert kda_latent_step_roofline.read(run) == pytest.approx(
        100 * nbytes / 819e9 / 1.8)
    # 700 (layer, step) pairs of 256 held assignments on 63 experts
    got = kda_grouped_matmul_roofline.read(run)
    assert 0 < got < 100
    assert got == pytest.approx(100 * kda_flops.grouped_matmul_least_s(
        run, PEAKS)[0] / 0.9)
    # nothing to read: nothing returned, nothing raised
    bare = _run(trace={"busy_s": 1.8, "kernels": {}})
    other = _run(fields={k: v for k, v in F.items()
                         if not k.startswith("kda_")})
    for reader in (kda_update_roofline, kda_scan_roofline, kda_mix_share_pct,
                   kda_latent_step_roofline, kda_grouped_matmul_roofline):
        assert reader.read(bare) is None
        assert reader.read(other) is None
        assert reader.read(_run(trace=None)) is None


def test_the_cell_and_its_files_say_what_issue_57_fixed():
    m = manifest.load()
    cell = m.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b", "decode_kda_latent_reasoning", 1)
    t = m.load_traffic(cell["traffic"])
    assert t["clients"] == 192 and t["driver"] == "serve"
    r, s = t["requests"], t["requests"]["sessions"]
    assert (s["count"], s["groups"], s["head_len"], s["grouped_share"],
            s["restart_prob"], s["preroll_turns"]) == (192, 16, 4096, 1.0,
                                                       1.0, 0)
    assert (r["prompt_len"]["lo"], r["prompt_len"]["hi"]) == (128, 512)
    assert (r["output_len"]["lo"], r["output_len"]["hi"]) == (1024, 2048)
    assert r["fill_requests"] == 128 and r["lead_in_s"] == 30.0
    assert t["engine"] == {
        "max_lanes": 128, "block_size": 128, "num_blocks": [3840, 32],
        "prefill_chunk": 256, "prefill_lanes": 4, "max_seq_len": 6784}
    names = set(m.metrics_of(CELL, "per_layer"))
    assert {"kda_update_roofline", "kda_scan_roofline", "kda_mix_share_pct",
            "kda_latent_step_roofline", "kda_grouped_matmul_roofline",
            "latent_decode_roofline", "ssm_state_gb",
            "decode_programs_per_step", "held_experts_hit_pct"} <= names
    path = os.path.join(manifest.ROOT, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    with open(path) as f:
        assert json.load(f)["check"]["samples"] == 3
