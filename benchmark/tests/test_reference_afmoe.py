"""`benchmark/reference/afmoe.py` against a numpy float64 loop that shares
nothing with it; the configuration file against the catalog row; the `swa_*`
readers and `benchmark/window_flops.py` on hand-computed numbers; the cell's
rehearsal.  CPU, nano size."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, window_flops
from benchmark.reference import afmoe as ref

ROOT = manifest.ROOT
CELL = "serve_trinity_docs_decode"
NAMES = ("swa_step_roofline", "swa_window_decode_roofline",
         "swa_full_decode_roofline", "swa_grouped_matmul_roofline",
         "swa_attn_share_pct", "swa_window_rows_pct")


# -- the reference held against a float64 loop ------------------------------

D, H, KH, HD, FF, FE, E, K, V, WINDOW = 64, 4, 2, 16, 48, 24, 8, 2, 96, 9


def _weights(seed=0):
    """Two dense layers and four expert layers (S S | S F S S in the
    published order), float32, norm scales off one."""
    rng = np.random.default_rng(seed)

    def w(*shape, fan):
        return (rng.normal(size=shape) / np.sqrt(fan)).astype(np.float32)

    def attention(n):
        return {"attn_norm": 1 + 0.1 * w(n, D, fan=1),
                "wq": w(n, D, H, HD, fan=D), "wk": w(n, D, KH, HD, fan=D),
                "wv": w(n, D, KH, HD, fan=D),
                "q_norm": 1 + 0.1 * w(n, HD, fan=1),
                "k_norm": 1 + 0.1 * w(n, HD, fan=1),
                "w_attn_gate": w(n, D, H, HD, fan=D),
                "wo": w(n, H, HD, D, fan=H * HD),
                "attn_post_norm": 1 + 0.1 * w(n, D, fan=1),
                "mlp_norm": 1 + 0.1 * w(n, D, fan=1),
                "mlp_post_norm": 1 + 0.1 * w(n, D, fan=1)}

    return {
        "tok_embed": w(V, D, fan=D), "final_norm": 1 + 0.1 * w(D, fan=1),
        "lm_head": w(D, V, fan=D),
        "lead_blocks": {**attention(2), "w_gate": w(2, D, FF, fan=D),
                        "w_up": w(2, D, FF, fan=D),
                        "w_down": w(2, FF, D, fan=FF)},
        "blocks": {**attention(4), "router": w(4, D, E, fan=D),
                   "router_bias": 0.3 * w(4, E, fan=1),
                   "w_gate": w(4, E, D, FE, fan=D),
                   "w_up": w(4, E, D, FE, fan=D),
                   "w_down": w(4, E, FE, D, fan=FE),
                   "ws_gate": w(4, D, FE, fan=D), "ws_up": w(4, D, FE, fan=D),
                   "ws_down": w(4, FE, D, fan=FE)}}


def _np_logits(params, tokens, window=WINDOW, theta=10000.0, scale=2.826,
               rotate_full=False, gate=True, bias=True):
    """The forward pass a position, a head and an expert at a time in
    float64."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    eps = 1e-5

    def norm(v, s):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + eps) * s

    def silu(v):
        return v / (1 + np.exp(-v))

    def rot(v, pos):
        half = HD // 2
        ang = pos * theta ** (-np.arange(half) / half)
        a, b = v[:half], v[half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)])

    length = len(tokens)
    x = p["tok_embed"][np.asarray(tokens)] * np.sqrt(D)
    order = [("lead_blocks", 0, True), ("lead_blocks", 1, True),
             ("blocks", 0, True), ("blocks", 1, False),
             ("blocks", 2, True), ("blocks", 3, True)]
    for stack, i, windowed in order:
        b = {k: v[i] for k, v in p[stack].items()}
        h = norm(x, b["attn_norm"])
        turn = windowed or rotate_full
        keys = np.zeros((length, KH, HD))
        vals = np.zeros((length, KH, HD))
        for t in range(length):
            for j in range(KH):
                k = norm(h[t] @ b["wk"][:, j], b["k_norm"])
                keys[t, j] = rot(k, t) if turn else k
                vals[t, j] = h[t] @ b["wv"][:, j]
        attn = np.zeros_like(x)
        for t in range(length):
            lo = max(0, t - window + 1) if windowed else 0
            for n in range(H):
                q = norm(h[t] @ b["wq"][:, n], b["q_norm"])
                q = rot(q, t) if turn else q
                j = n // (H // KH)
                s = keys[lo:t + 1, j] @ q * HD ** -0.5
                w = np.exp(s - s.max())
                o = (w / w.sum()) @ vals[lo:t + 1, j]
                if gate:
                    o = o / (1 + np.exp(-(h[t] @ b["w_attn_gate"][:, n])))
                attn[t] += o @ b["wo"][n]
        x = x + norm(attn, b["attn_post_norm"])
        h2 = norm(x, b["mlp_norm"])
        if stack == "lead_blocks":
            y = (silu(h2 @ b["w_gate"]) * (h2 @ b["w_up"])) @ b["w_down"]
        else:
            y = (silu(h2 @ b["ws_gate"]) * (h2 @ b["ws_up"])) @ b["ws_down"]
            for t in range(length):
                s = 1 / (1 + np.exp(-(h2[t] @ b["router"])))
                pick = np.argsort(-(s + (b["router_bias"] if bias else 0)),
                                  kind="stable")[:K]
                for e in pick:
                    out = (silu(h2[t] @ b["w_gate"][e])
                           * (h2[t] @ b["w_up"][e])) @ b["w_down"][e]
                    y[t] += s[e] / s[pick].sum() * scale * out
        x = x + norm(y, b["mlp_post_norm"])
    return norm(x, p["final_norm"]) @ p["lm_head"]


KW = dict(top_k=K, window=WINDOW)


def test_the_reference_is_the_float64_loop():
    """Every mechanism of the stack at once, over 30 positions (three
    windows): the loop with a mechanism changed is another function by far
    more than the reference is off."""
    params = _weights()
    tokens = np.random.default_rng(1).integers(0, V, 30)
    got = np.asarray(ref.row_logits(params, tokens, **KW))
    want = _np_logits(params, tokens)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    for change in (dict(rotate_full=True), dict(gate=False),
                   dict(bias=False), dict(window=WINDOW + 1),
                   dict(scale=1.0)):
        assert np.abs(got - _np_logits(params, tokens, **change)).max() \
            > 2e-2, change


def test_served_token_gaps_are_the_references_own_rows():
    params = _weights(2)
    seq = np.random.default_rng(3).integers(0, V, 26).tolist()
    prompt, out = seq[:14], seq[14:]
    gaps, ranks = ref.served_token_gaps(params, prompt, out, bucket=8, **KW)
    rows = np.asarray(ref.row_logits(params, np.asarray(seq), **KW))[13:25]
    own = rows[np.arange(12), out]
    np.testing.assert_allclose(gaps, rows.max(-1) - own, atol=2e-4, rtol=0)
    assert ranks == (rows > own[:, None]).sum(-1).tolist()


def test_the_published_order_of_layers():
    kinds = ref.kinds_of(2, 30)
    assert [w for _, _, w in kinds] == [True, True, True, False] * 8
    assert [(s, i) for s, i, _ in kinds[:4]] == [
        ("lead", 0), ("lead", 1), ("rest", 0), ("rest", 1)]
    assert dict(ref.sizes_of({"tok_embed": np.zeros((8, 2048))}))[
        "window"] == 2048
    assert dict(ref.sizes_of({"tok_embed": np.zeros((8, 64))}))[
        "window"] == 9


# -- the configuration file ---------------------------------------------------

@pytest.fixture(scope="module")
def config():
    return manifest.load().load_config("trinity-mini")


def test_the_configuration_file_is_the_catalog_row_but_for_its_cuts(config):
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"]) == (8, 50048)
    assert config["layer_types"] == published["layer_types"][:8]
    assert published["vocab_size"] == 4 * 50048
    # the published widths, untouched
    assert [published[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "sliding_window", "intermediate_size", "num_experts",
        "moe_intermediate_size", "num_experts_per_tok",
        "num_shared_experts", "route_scale", "rope_theta",
        "rms_norm_eps")] == [2048, 32, 4, 128, 2048, 6144, 128, 1024, 8, 1,
                             2.826, 10000, 1e-5]
    cfg = manifest.model_config(config)
    for key, field in {**config["field_of"],
                       **config["reduced_field_of"]}.items():
        got = getattr(cfg, field)
        assert (list(got) if isinstance(got, tuple) else got) \
            == config[key], key
    assert len(config["assumed"]) >= 6 and "deployment" in config
    # the constants the reference keeps are the file's too
    for ours, theirs in {"theta": "rope_theta", "window": "sliding_window",
                         "routed_scale": "route_scale",
                         "eps": "rms_norm_eps",
                         "full_every": "global_attn_every_n_layers",
                         "embed_scale": "mup_enabled"}.items():
        assert ref.PUBLISHED[ours] == published[theirs], ours
    assert published["num_experts"] // ref.PUBLISHED["experts_one_in"] \
        == published["num_experts_per_tok"]
    # the cell's traffic is the issue's, number for number
    traffic = manifest.load().load_traffic("decode_window_docs")
    assert traffic["clients"] == 96
    assert traffic["engine"] == {
        "max_lanes": 64, "block_size": 128, "num_blocks": [1536, 768],
        "prefill_chunk": 512, "prefill_lanes": 4, "max_seq_len": 17024}
    r = traffic["requests"]
    assert (r["prompt_len"], r["output_len"]) == (
        {"dist": "uniform", "lo": 32, "hi": 128},
        {"dist": "uniform", "lo": 256, "hi": 512})
    s = r["sessions"]
    assert (s["count"], s["groups"], s["head_len"], s["restart_prob"],
            s["preroll_turns"]) == (96, 8, 16384, 1.0, 0)
    assert traffic["trace"]["at_s"] == 10.0
    assert traffic["trace"]["slice_s"] == 2.0
    assert traffic["check"]["samples"] == 2


# -- the yardstick's arithmetic ----------------------------------------------

N = {"full": 2, "window": 6, "experts": 6, "dense": 2}


def test_the_cut_by_the_configurations_own_numbers(config):
    """ISSUE 50's arithmetic, re-reckoned from `fields` (bf16)."""
    f = config["fields"]
    assert window_flops.attention_weight_bytes(f) / 2 == pytest.approx(
        27.26e6, rel=1e-3)
    expert = 3 * f["d_model"] * f["d_expert"]
    assert expert == pytest.approx(6.29e6, rel=1e-3)
    assert (window_flops.expert_layer_weight_bytes(f, 128)
            + window_flops.attention_weight_bytes(f)) / 2 == pytest.approx(
        839.1e6, rel=1e-3)
    # all of it, the embedding too: 5,370 M parameters
    whole = window_flops.step_weight_bytes(f, N, 128) \
        + 2 * f["d_model"] * f["vocab_size"]
    assert whole / 2 == pytest.approx(5370e6, rel=1e-3)
    # a step at 64 lanes: 126 experts hit; 16.4 GB with the rows
    assert window_flops.step_weight_bytes(f, N, 126) == pytest.approx(
        9.92e9 + 0.26e9 + 0.21e9, rel=5e-3)
    assert window_flops.row_bytes(f) == 2048
    rows = window_flops.row_bytes(f) * (2 * 64 * 16750 + 6 * 64 * 2048)
    assert rows == pytest.approx(4.39e9 + 1.61e9, rel=2e-3)
    assert window_flops.step_weight_bytes(f, N, 126) + rows \
        == pytest.approx(16.4e9, rel=5e-3)


def _run(fields, **over):
    """A traced run as the readers see it: 80 T=1 steps and 4 chunks in a
    slice of 2.2 busy seconds, 64 lanes at 16.7k of context."""
    def paged(steps):
        return {"decode_steps": steps, "ctx_tokens": steps * 64 * 16700,
                "runs_live": steps * 64 * 17,
                "rows_full": 2 * steps * 64 * 16700,
                "rows_window": 6 * steps * 64 * 2048}

    def moe(pairs):
        return {"assignments": pairs * 512, "expert_load": [pairs * 4] * 128,
                "experts_hit": pairs * 126, "layer_steps": pairs}

    layers = {"kv": 8, "window": 6, "state": 0, "experts": 6}
    s0 = {"paged": paged(10), "moe": moe(60), "layers": layers,
          "windows": {"blocks_freed": 100}}
    s1 = {"paged": paged(2010), "moe": moe(60 + 2100 * 6), "layers": layers,
          "windows": {"blocks_freed": 1120}}
    kernels = {
        "window_paged_decode_attention": {"calls": 480, "seconds": 0.24},
        "paged_decode_attention": {"calls": 160, "seconds": 0.6},
        "moe_grouped_matmul": {"calls": 84 * 6 * 3, "seconds": 1.2}}
    run = {"stats0": s0, "stats1": s1, "fields": fields, "seconds": 51.0,
           "base": 100.0, "records": [],
           "marks": {"trace_on": 110.0, "trace_off": 130.0},
           "engine_events": [
               {"kind": "step", "ts": 110.0 + 0.02 * i,
                "payload": {"decode": 64, "decode_ctx": 64 * 16700}}
               for i in range(80)],
           "traffic": {"engine": {"max_lanes": 64},
                       "trace": {"at_s": 10.0, "slice_s": 2.0}},
           "device": {"kind": "TPU v5 lite"},
           "trace": {"busy_s": 2.2, "kernels": kernels, "ops_table": [
               ["moe_grouped_matmul bf16[512,1024] (kernel)", 0.75],
               ["moe_grouped_matmul bf16[512,2048] (kernel)", 0.37],
               ["moe_grouped_matmul bf16[16384,1024] (kernel)", 0.08]]}}
    run.update(over)
    return run


def test_every_new_reader_reads_a_number_and_nothing_without_counters(config):
    f = config["fields"]
    run = _run(f)
    values = {n: manifest.module("layer_metrics", n).read(run)
              for n in NAMES}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["swa_window_rows_pct"] == pytest.approx(100 * 2048 / 16700)
    assert values["swa_attn_share_pct"] == pytest.approx(100 * 0.84 / 2.2)
    # a window layer's call: 64 x 2,048 rows of 2,048 bytes + q and o of 32
    # heads of 128 a lane, at 819 GB/s = 0.3297 ms; 480 calls took 0.24 s
    nbytes = 2048 * 64 * 2048 + 2 * 2 * 64 * 32 * 128
    assert values["swa_window_decode_roofline"] == pytest.approx(
        100 * 480 * nbytes / 819e9 / 0.24, rel=1e-6)
    nbytes = 2048 * 64 * 16700 + 2 * 2 * 64 * 32 * 128
    assert values["swa_full_decode_roofline"] == pytest.approx(
        100 * 160 * nbytes / 819e9 / 0.6, rel=1e-6)
    # 80 T=1 steps x 6 expert layers of 512 rows on 126 experts: a gate and
    # an up multiply of 2048 x 1024 and a down of 1024 x 2048, by their bytes
    one = (126 * 2048 * 1024 + 512 * (2048 + 1024)) * 2 / 819e9
    assert values["swa_grouped_matmul_roofline"] == pytest.approx(
        100 * 80 * 6 * 3 * one / 1.12, rel=1e-6)
    # 84 steps' weights and 80 steps' rows over 2.2 busy seconds
    nbytes = 84 * window_flops.step_weight_bytes(f, N, 126) \
        + 80 * 2048 * (2 * 64 * 16700 + 6 * 64 * 2048)
    assert values["swa_step_roofline"] == pytest.approx(
        100 * nbytes / 819e9 / 2.2, rel=1e-6)
    assert all(values[n] <= 100 for n in NAMES)
    # the accepted readers the cell lists read it right, unedited
    assert manifest.module("layer_metrics", "window_blocks_freed_per_s").read(
        run) == pytest.approx(20.0)
    assert manifest.module("layer_metrics", "moe_load_max_over_mean").read(
        run) == pytest.approx(1.0)
    # a program without the counters or the kernels (the parent's): nothing
    bare = _run(f, stats0={}, stats1={}, trace={
        "busy_s": 2.2, "kernels": {}, "ops_table": []})
    assert all(manifest.module("layer_metrics", n).read(bare) is None
               for n in NAMES)
    untraced = _run(f, trace=None)
    assert all(manifest.module("layer_metrics", n).read(untraced) is None
               for n in NAMES if n != "swa_window_rows_pct")
    # the manifest lists the cell for each, and for no family reader that
    # would read it wrong
    m = manifest.load()
    listed = m.metrics_of(CELL, "per_layer")
    assert set(NAMES) <= set(listed)
    assert not {"moe_paged_decode_roofline", "ssm_paged_decode_roofline",
                "moe_experts_hit_pct"} & set(listed)
    assert {"serve_tokens_per_s", "setup_s"} == set(
        m.metrics_of(CELL, "end_to_end"))


def test_the_cell_rehearses_on_the_cpu():
    """`--rehearse`: the same path at nano size with faked chips: shared
    documents prefilled in chunks through both kinds, admissions that adopt
    blocks of both, a traced window, the reference check behind it and a
    last line the driver can read."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "4100000077", "--seconds", "6",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["attempted"] > 10
    assert line["metrics"]["window_blocks_freed_per_s"]["value"] > 1
    assert line["metrics"]["prefix_hit_share_pct"]["value"] > 50
    assert 0 < line["metrics"]["swa_window_rows_pct"]["value"] < 40
