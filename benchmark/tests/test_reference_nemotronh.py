"""The Nemotron-H reference against a second, independent formulation (the
model written once more as the equations read: numpy float64, a loop over
positions, the convolution by indexing, the recurrence a head at a time,
each token's chosen experts one by one, nothing in blocks), on what the
check must catch, on the arithmetic of `hybrid_moe_flops.py` and its
readers on hand-computed numbers, and through the cell's rehearsal."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import flops, hybrid_moe_flops, manifest, ssm_flops
from benchmark.layer_metrics import (held_experts_hit_pct,
                                     hybrid_experts_share_pct,
                                     hybrid_load_max_over_mean,
                                     hybrid_step_roofline,
                                     relu2_grouped_matmul_roofline,
                                     ssm_paged_decode_roofline,
                                     ssm_update_share_pct)
from benchmark.reference import nemotronh as ref
from ray_tpu.models import nemotronh

CFG = nemotronh.CONFIGS["nemotronh-nano"]          # float32
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve_nemotron3_agents_decode"


@pytest.fixture(scope="module")
def params():
    p = nemotronh.init_params(CFG, jax.random.key(0))
    for i, stack in enumerate(("mixers", "attns", "experts")):
        noisy = {k: 1.0 + 0.1 * jax.random.normal(jax.random.key(9 + i),
                                                  v.shape)
                 for k, v in p[stack].items()
                 if k.endswith("norm") or k == "D"}
        p[stack] = {**p[stack], **noisy}
    p["final_norm"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.key(8), p["final_norm"].shape)
    return p


def second_formulation(params, tokens, offset=0):
    """[L] tokens -> [L, V] logits, numpy float64, a position at a time."""
    c = ref.SIZES[64]
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    n = len(tokens)
    h = p["tok_embed"][np.asarray(tokens)]

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g

    def silu(x):
        return x / (1.0 + np.exp(-x))

    def softmax(z):
        e = np.exp(z - z.max())
        return e / e.sum()

    def mixer(u, w):
        n_heads = w["A_log"].shape[0]
        d_ssm = w["ssm_norm"].shape[0]
        taps, width = w["conv_w"].shape
        gn = (width - d_ssm) // 2
        groups = c["groups"]
        d_state, hp = gn // groups, d_ssm // n_heads
        proj = u @ w["w_in"]
        z, xbc = proj[:, :d_ssm], proj[:, d_ssm:d_ssm + width]
        dt = proj[:, d_ssm + width:]
        conv = np.zeros_like(xbc)
        for t in range(n):
            acc = w["conv_b"].copy()
            for tap in range(taps):
                src = t - (taps - 1) + tap
                if src >= 0:
                    acc += w["conv_w"][tap] * xbc[src]
            conv[t] = silu(acc)
        dt = np.log1p(np.exp(dt + w["dt_bias"]))
        a = -np.exp(w["A_log"])
        y = np.zeros((n, n_heads, hp))
        for j in range(n_heads):
            g = j // (n_heads // groups)
            state = np.zeros((hp, d_state))
            for t in range(n):
                x_t = conv[t, j * hp:(j + 1) * hp]
                b_t = conv[t, d_ssm + g * d_state:d_ssm + (g + 1) * d_state]
                c_t = conv[t, d_ssm + gn + g * d_state:
                           d_ssm + gn + (g + 1) * d_state]
                state = np.exp(dt[t, j] * a[j]) * state \
                    + dt[t, j] * np.outer(x_t, b_t)
                y[t, j] = state @ c_t + w["D"][j] * x_t
        y = y.reshape(n, d_ssm) * silu(z)
        y = y.reshape(n, groups, -1)
        y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5)
        return (y.reshape(n, d_ssm) * w["ssm_norm"]) @ w["w_out"]

    def attention(u, w):                 # no positions of any kind
        q = np.einsum("ld,dhk->lhk", u, w["wq"])
        k = np.einsum("ld,dhk->lhk", u, w["wk"])
        v = np.einsum("ld,dhk->lhk", u, w["wv"])
        heads, hd = q.shape[1:]
        rep = heads // k.shape[1]
        attn = np.zeros((n, heads, hd))
        for t in range(n):
            for j in range(heads):
                probs = softmax(k[:t + 1, j // rep] @ q[t, j] * hd ** -0.5)
                attn[t, j] = probs @ v[:t + 1, j // rep]
        return np.einsum("lhk,hkd->ld", attn, w["wo"])

    def experts(u, w):
        out = np.zeros_like(u)
        held = w["w_down"].shape[0]
        for t in range(n):
            s = 1.0 / (1.0 + np.exp(-(u[t] @ w["router"])))
            chosen = np.argsort(-(s + w["router_bias"]), kind="stable")[
                :c["top_k"]]
            total = s[chosen].sum()
            for e in chosen:
                if offset <= e < offset + held:
                    up = np.maximum(w["w_up_t"][e - offset] @ u[t], 0.0) ** 2
                    out[t] += (s[e] / total * c["routed_scale"]) \
                        * (up @ w["w_down"][e - offset])
            out[t] += (np.maximum(u[t] @ w["ws_up"], 0.0) ** 2) @ w["ws_down"]
        return out

    parts = {"M": ("mixers", mixer), "*": ("attns", attention),
             "E": ("experts", experts)}
    seen = dict.fromkeys(parts, 0)
    for letter in c["pattern"][:CFG.n_layers]:
        stack, part = parts[letter]
        w = {k: v[seen[letter]] for k, v in p[stack].items()}
        seen[letter] += 1
        h = h + part(norm(h, w["norm"]), w)
    return norm(h, p["final_norm"]) @ p["lm_head"]


def test_the_reference_is_the_equations_a_position_at_a_time(params):
    tokens = np.random.default_rng(0).integers(0, 512, 29)
    got = np.asarray(ref.row_logits(params, tokens))
    want = second_formulation(params, tokens)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_references_share_is_the_held_experts_alone(params):
    cut = {**params, "experts": {
        **params["experts"],
        "w_up_t": params["experts"]["w_up_t"][:, 8:],
        "w_down": params["experts"]["w_down"][:, 8:]}}
    tokens = np.random.default_rng(3).integers(0, 512, 17)
    got = np.asarray(ref.row_logits(cut, tokens, experts_offset=8))
    want = second_formulation(cut, tokens, offset=8)
    np.testing.assert_allclose(got, want, atol=2e-4)
    whole = np.asarray(ref.row_logits(params, tokens))
    assert np.abs(got - whole).max() > 0.01


def test_the_reference_is_causal_where_the_check_pads(params):
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 512, 21)
    short = np.asarray(ref.row_logits(params, tokens))
    padded = np.asarray(ref.row_logits(
        params, np.concatenate([tokens, rng.integers(0, 512, 11)])))
    np.testing.assert_allclose(padded[:21], short, atol=1e-5)


def test_the_check_catches_a_lost_state_a_rotation_and_a_gated_expert(
        params):
    """Served tokens are the reference's own greedy ones (gap 0); judged by
    a reference whose recurrence forgets (every A times 8), whose experts
    are scaled otherwise or whose router's bias is another, they are not."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 512, 24).tolist()
    seq = list(prompt)
    for _ in range(12):
        seq.append(int(np.argmax(np.asarray(
            ref.row_logits(params, np.asarray(seq)))[-1])))
    out = seq[len(prompt):]
    gaps, ranks = ref.served_token_gaps(params, prompt, out, bucket=16)
    assert max(gaps) == 0.0 and set(ranks) == {0}
    wrong = {**params, "mixers": {
        **params["mixers"],
        "A_log": params["mixers"]["A_log"] + np.log(8.0)}}
    gaps, _ = ref.served_token_gaps(wrong, prompt, out, bucket=16)
    assert max(gaps) > 0.01
    gaps, _ = ref.served_token_gaps(params, prompt, out, bucket=16,
                                    routed_scale=1.0)
    assert max(gaps) > 0.01
    gaps, _ = ref.served_token_gaps(params, prompt, out, bucket=16,
                                    top_k=2)
    assert max(gaps) > 0.01


def test_the_configuration_file_is_the_catalog_row_but_for_its_cuts():
    config = manifest.load().load_config("nemotron-3-nano-30b-a3b")
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers",
                                 "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (13, 64, 16384)
    assert config["hybrid_override_pattern"] == \
        published["hybrid_override_pattern"][:13] == "MEMEM*EMEMEM*"
    assert published["num_hidden_layers"] == 52 == 4 * 13
    assert published["n_routed_experts"] == 128 == 2 * 64
    assert published["vocab_size"] == 131072 == 8 * 16384
    # the widths, untouched
    widths = {"hidden_size": 2688, "num_attention_heads": 32,
              "num_key_value_heads": 2, "head_dim": 128,
              "mamba_num_heads": 64, "mamba_head_dim": 64,
              "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
              "moe_intermediate_size": 1856, "num_experts_per_tok": 6,
              "moe_shared_expert_intermediate_size": 3712,
              "routed_scaling_factor": 2.5}
    for key, value in widths.items():
        assert config[key] == published[key] == value, key
    cfg = manifest.model_config(config)
    for key, field in {**config["field_of"],
                       **config["reduced_field_of"]}.items():
        assert getattr(cfg, field) == config[key], key
    assert cfg.n_routed_experts == 128 and cfg.experts_offset == 0
    # the constants the reference keeps are the file's too
    for ours, theirs in {"pattern": "hybrid_override_pattern",
                         "groups": "n_groups", "top_k": "num_experts_per_tok",
                         "routed_scale": "routed_scaling_factor",
                         "eps": "norm_eps"}.items():
        assert ref.PUBLISHED[ours] == published[theirs], ours
    assert nemotronh.PATTERN == published["hybrid_override_pattern"]
    # the traffic is the issue's, number for number
    t = manifest.load().load_traffic("decode_hybrid_moe_agents")
    r, e = t["requests"], t["engine"]
    assert (t["clients"], e["max_lanes"], e["block_size"], e["num_blocks"],
            e["prefill_chunk"], e["prefill_lanes"]) == (
                96, 64, 128, [2048, 16], 256, 4)
    assert e["max_seq_len"] == 6784 == 53 * 128 >= 4096 + 512 + 2048 + 2
    assert (r["sessions"]["count"], r["sessions"]["groups"],
            r["sessions"]["head_len"], r["lead_in_s"]) == (96, 8, 4096, 20.0)
    assert (r["prompt_len"]["lo"], r["prompt_len"]["hi"],
            r["output_len"]["lo"], r["output_len"]["hi"]) == (
                128, 512, 1024, 2048)
    assert t["trace"]["at_s"] == 10.0 and t["trace"]["slice_s"] == 2.0
    assert t["check"]["samples"] == 3


def test_the_arithmetic_of_a_step_is_the_issues():
    """ISSUE 47's reckoning from the configuration's own fields: an expert
    layer's 61 hit experts 1.26 GB, the states of 64 lanes read and
    written in 6 layers 1.61 GB, 2,048 B of K/V a token over both attention
    layers, 9.3 GB a step in all."""
    f = manifest.fields(manifest.load().load_config(
        "nemotron-3-nano-30b-a3b"))
    n = {"state": 6, "kv": 2, "experts": 5}     # `stats()["layers"]`
    assert [f["pattern"].count(k) for k in "M*E"] == list(n.values())
    assert hybrid_moe_flops.layers({"stats1": {"layers": n}}) == n
    assert hybrid_moe_flops.layers({"stats1": {}}) is None
    assert ssm_flops.state_numbers(f) == 64 * 64 * 128
    one = hybrid_moe_flops.expert_layer_weight_bytes(f, 61.0)
    assert one == 2 * 2688 * (2 * 61 * 1856 + 2 * 3712 + 128)
    assert abs(5 * one / 1e9 - 6.29) < 0.01
    assert abs(6 * hybrid_moe_flops.mixer_weight_bytes(f) / 1e9
               - 0.465) < 0.001
    assert abs(2 * hybrid_moe_flops.attention_weight_bytes(f) / 1e9
               - 0.0936) < 0.0001
    _, state = ssm_flops.update(64, f)
    assert abs(6 * state / 1e9 - 1.62) < 0.01
    assert hybrid_moe_flops.kv_bytes(f, n, 1.0) == 2 * 2 * 2 * 128 * 2 == 2048
    step = (hybrid_moe_flops.step_weight_bytes(f, n, 61.0) + 6 * state
            + hybrid_moe_flops.kv_bytes(f, n, 64 * 5500.0))
    assert abs(step / 1e9 - 9.27) < 0.05
    # an expert's two published matrices, whatever tiles move them
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = hybrid_moe_flops.relu2_layer_s(192.0, 61.0, f, peaks)
    nbytes = 2 * (61 * 2688 * 1856 * 2 + 192 * (2688 + 1856) * 2)
    assert abs(least - nbytes / 819e9) < 1e-9


def _run(busy_s=2.0):
    """A traced run's reduced inputs for the readers, by hand: 100 T=1
    steps and 10 prefill steps in the slice, a window in which every
    (layer, step) pair saw 200 held assignments on 60 experts."""
    f = manifest.fields(manifest.load().load_config(
        "nemotron-3-nano-30b-a3b"))

    def stats(k):
        return {"moe": {"assignments": 400 * 5 * k, "layer_steps": 5 * k,
                        "assignments_held": 200 * 5 * k,
                        "experts_hit": 60 * 5 * k,
                        "expert_load": [3 * k] * 63 + [11 * k]},
                "layers": {"kv": 2, "state": 6, "experts": 5},
                "ssm": {"tokens_updated": 64 * k, "tokens_scanned": 30 * k},
                "paged": {"decode_steps": k},
                "prefill": {"steps": k // 10, "lanes": k // 5}}

    def step(ts, ctx):
        return {"kind": "step", "ts": ts, "payload": {"decode_ctx": ctx}}

    return {
        "fields": f, "device": {"kind": "TPU v5 lite"}, "seconds": 51.0,
        "base": 100.0, "marks": {"trace_on": 110.0, "trace_off": 113.0},
        "traffic": {"trace": {"at_s": 10.0, "slice_s": 2.0},
                    "engine": {"max_lanes": 64}},
        "stats0": stats(100), "stats1": stats(1100),
        "engine_events": [step(110.5, 300000), step(111.5, 340000)],
        "trace": {"busy_s": busy_s, "kernels": {
            "ssm_update": {"calls": 600, "seconds": 0.3},
            "ssm_scan": {"calls": 60, "seconds": 0.02},
            "moe_grouped_matmul": {"calls": 1100, "seconds": 1.2}}}}


def test_the_readers_on_hand_computed_numbers():
    run = _run()
    f = run["fields"]
    assert hybrid_moe_flops.steps(run) == (100.0, 10.0)
    # per step: weights with 60 experts hit; per T=1 step 6 layers' states
    # of 64 lanes and the K/V rows of 320,000 context tokens; per prefill
    # step the states of 2 lanes
    want = (110 * hybrid_moe_flops.step_weight_bytes(
                f, run["stats1"]["layers"], 60.0)
            + 100 * (6 * ssm_flops.update(64.0, f)[1] + 2048 * 320000)
            + 10 * 6 * 2 * 2 * 4 * 64 * 64 * 128)
    assert hybrid_moe_flops.step_bytes(run) == pytest.approx(want)
    assert hybrid_step_roofline.read(run) == pytest.approx(
        100.0 * want / 819e9 / 2.0)
    peaks = manifest.peaks("TPU v5 lite")
    assert relu2_grouped_matmul_roofline.read(run) == pytest.approx(
        100.0 * hybrid_moe_flops.relu2_layer_s(200.0, 60.0, f, peaks)
        * 550 / 1.2)
    assert hybrid_experts_share_pct.read(run) == pytest.approx(60.0)
    assert hybrid_load_max_over_mean.read(run) == pytest.approx(
        11 * 64 / (3 * 63 + 11))
    # no roofline share over 100 at a busy time the bytes could not fit in
    assert hybrid_step_roofline.read(_run(busy_s=0.5)) > 100   # (a fault)
    # a program without the counters or the kernels: nothing, not an error
    bare = dict(run, stats0={}, stats1={}, trace={"busy_s": 2.0,
                                                  "kernels": {}})
    for reader in (hybrid_step_roofline, relu2_grouped_matmul_roofline,
                   hybrid_experts_share_pct, hybrid_load_max_over_mean):
        assert reader.read(bare) is None
    other = dict(run, fields={"n_layers": 9})       # another family's cell
    assert hybrid_step_roofline.read(other) is None
    # the accepted readers this cell is listed under read it unedited
    assert ssm_update_share_pct.read(run) == pytest.approx(15.0)
    assert held_experts_hit_pct.read(run) == pytest.approx(100 * 60 / 64)
    run["trace"]["kernels"]["paged_decode_attention"] = {
        "calls": 200, "seconds": 0.1}
    least, _ = flops.roofline_s(*ssm_flops.paged_decode(
        320000.0, 64, f), peaks)
    assert ssm_paged_decode_roofline.read(run) == pytest.approx(
        100.0 * least * 200 / 0.1)


def test_the_cell_rehearses_on_the_cpu():
    """`--rehearse`: the same path at nano size with faked chips: shared
    prompts prefilled in chunks and snapshotted, admissions that adopt
    blocks and a snapshot, a traced window, the reference check behind it
    and a last line the driver can read."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "4100000077",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["attempted"] > 50
    assert line["metrics"]["ssm_snapshots_adopted_per_s"]["value"] > 1
    assert line["metrics"]["prefix_hit_share_pct"]["value"] > 50
    assert line["metrics"]["ssm_state_gb"]["value"] > 0
    assert 0 < line["metrics"]["held_experts_hit_pct"]["value"] <= 100
