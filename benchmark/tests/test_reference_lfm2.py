"""`benchmark/reference/lfm2.py` against a numpy float64 loop over POSITIONS
that shares nothing with it (the convolution tap by tap behind each position,
the router's choice by a sort, attention a query at a time); the held layers
against the catalog row; `benchmark/conv_flops.py` and the `conv_*` readers
on hand-computed numbers.  CPU, nano size."""

import numpy as np
import pytest

from benchmark import conv_flops, manifest
from benchmark.reference import lfm2 as ref

CELL = "serve_lfm2_rag_decode"
D, H, KH, HD, FF, FE, E, K, V, TAPS = 64, 4, 2, 16, 96, 24, 16, 4, 96, 3
# the nano stage's kinds (`rehearsal_fields`): conv+dense, attn, conv x2, attn
NANO = ("conv", "full_attention", "conv", "conv", "full_attention")


def _weights(seed=0):
    rng = np.random.default_rng(seed)

    def w(*shape, fan):
        return (rng.normal(size=shape) / np.sqrt(fan)).astype(np.float32)

    def norms(n):
        return {"operator_norm": 1 + 0.1 * w(n, D, fan=1),
                "ffn_norm": 1 + 0.1 * w(n, D, fan=1)}

    def conv(n):
        return {"w_in": w(n, D, 3 * D, fan=D),
                "conv_w": rng.uniform(-0.6, 0.6, (n, TAPS, D)).astype(
                    np.float32), "w_out": w(n, D, D, fan=D)}

    def attn(n):
        return {"wq": w(n, D, H, HD, fan=D), "wk": w(n, D, KH, HD, fan=D),
                "wv": w(n, D, KH, HD, fan=D),
                "q_norm": 1 + 0.1 * w(n, HD, fan=1),
                "k_norm": 1 + 0.1 * w(n, HD, fan=1),
                "wo": w(n, H, HD, D, fan=H * HD)}

    def experts(n):
        return {"router": w(n, D, E, fan=D),
                "router_bias": 0.3 * w(n, E, fan=1),
                "w_gate": w(n, E, D, FE, fan=D), "w_up": w(n, E, D, FE, fan=D),
                "w_down": w(n, E, FE, D, fan=FE)}

    return {
        "tok_embed": w(V, D, fan=D), "final_norm": 1 + 0.1 * w(D, fan=1),
        "dense_convs": {**norms(1), **conv(1), "w_gate": w(1, D, FF, fan=D),
                        "w_up": w(1, D, FF, fan=D),
                        "w_down": w(1, FF, D, fan=FF)},
        "convs": {**norms(2), **conv(2), **experts(2)},
        "attns": {**norms(2), **attn(2), **experts(2)}}


def _rms(x, scale, eps=1e-5):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1 + np.exp(-x))


def _rope(x, pos, theta=1e6):
    half = x.shape[-1] // 2
    ang = pos * theta ** (-np.arange(half) / half)
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


def _loop_conv(h, p):
    """Position by position: the gated product's last TAPS - 1 rows are all
    a position needs of what came before."""
    tail = [np.zeros(D)] * (TAPS - 1)
    out = []
    for t in range(len(h)):
        proj = h[t] @ p["w_in"]
        b, c, u = proj[:D], proj[D:2 * D], proj[2 * D:]
        v = b * u
        rows = tail + [v]
        y = sum(p["conv_w"][j] * rows[j] for j in range(TAPS))
        tail = rows[1:]
        out.append((c * y) @ p["w_out"])
    return np.stack(out)


def _loop_attention(h, p):
    per, out = H // KH, []
    ks, vs = [], []
    for t in range(len(h)):
        ks.append(_rope(_rms(np.einsum("d,dhk->hk", h[t], p["wk"]),
                             p["k_norm"]), t))
        vs.append(np.einsum("d,dhk->hk", h[t], p["wv"]))
        q = _rope(_rms(np.einsum("d,dhk->hk", h[t], p["wq"]), p["q_norm"]), t)
        heads = []
        for i in range(H):
            s = np.array([q[i] @ k[i // per] for k in ks]) * HD ** -0.5
            w = np.exp(s - s.max())
            w /= w.sum()
            heads.append(sum(wi * v[i // per] for wi, v in zip(w, vs)))
        out.append(np.einsum("hk,hkd->d", np.stack(heads), p["wo"]))
    return np.stack(out)


def _loop_router(h, p):
    """[L, E] weights: by a stable sort of s + bias, the unbiased s over
    (their sum + 1e-6)."""
    out = np.zeros((len(h), E))
    for t in range(len(h)):
        s = 1 / (1 + np.exp(-(h[t] @ p["router"])))
        chosen = np.argsort(-(s + p["router_bias"]), kind="stable")[:K]
        out[t, chosen] = s[chosen] / (s[chosen].sum() + 1e-6)
    return out


def _loop_logits(params, tokens):
    f64 = lambda tree: {k: (f64(v) if isinstance(v, dict)
                            else np.asarray(v, np.float64))
                        for k, v in tree.items()}
    params = f64(params)
    x = params["tok_embed"][tokens]
    for stack, i, op, dense in ref.kinds_of(NANO, 1):
        p = {k: v[i] for k, v in params[stack].items()}
        h = _rms(x, p["operator_norm"])
        x = x + (_loop_conv(h, p) if op == "conv" else _loop_attention(h, p))
        h = _rms(x, p["ffn_norm"])
        if dense:
            y = (_silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
        else:
            w = _loop_router(h, p)
            y = sum(w[:, e:e + 1] * ((_silu(h @ p["w_gate"][e])
                                      * (h @ p["w_up"][e])) @ p["w_down"][e])
                    for e in range(E))
        x = x + y
    return _rms(x, params["final_norm"]) @ params["tok_embed"].T


def test_the_reference_is_the_loop_over_positions():
    params = _weights()
    tokens = np.random.default_rng(1).integers(0, V, 23)
    want = _loop_logits(params, tokens)
    got = np.asarray(ref.row_logits(params, tokens))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


def test_the_convolution_and_the_router_alone_are_the_loops():
    params = _weights(2)
    rng = np.random.default_rng(3)
    h = rng.normal(size=(17, D)).astype(np.float32)
    conv = {k: v[1] for k, v in params["convs"].items()}
    np.testing.assert_allclose(
        ref.short_conv(h, conv),
        _loop_conv(h.astype(np.float64),
                   {k: v.astype(np.float64) for k, v in conv.items()}),
        atol=1e-5)
    np.testing.assert_allclose(
        ref.router_weights(h, conv["router"], conv["router_bias"], K, 1.0),
        _loop_router(h.astype(np.float64), conv), atol=1e-6)
    # the two wrong mechanisms the precision tool reads are wrong here too
    for wrong in (dict(tail=False), dict(c_gate=False)):
        assert np.abs(np.asarray(ref.short_conv(h, conv, **wrong))
                      - np.asarray(ref.short_conv(h, conv))).max() > 0.05
    # a bias large enough moves a choice and never a weight's size
    plain = np.asarray(ref.router_weights(h, conv["router"],
                                          np.zeros(E, np.float32), K, 1.0))
    assert ((plain > 0) != (_loop_router(h, conv) > 0)).any()


# -- the configuration file ---------------------------------------------------

@pytest.fixture(scope="module")
def config():
    return manifest.load().load_config("lfm2-24b-a2b")


def test_the_held_layers_are_the_published_layer_types_1_to_9(config):
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_dense_layers"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["layer_types"] == published["layer_types"][1:10]
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (9, 1)
    assert config["layer_types"].count("conv") == 7
    assert [i for i, k in enumerate(published["layer_types"])
            if k == "full_attention"] == list(range(2, 40, 4))
    # the published widths, untouched
    assert [published[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "conv_L_cache", "intermediate_size", "num_experts",
        "moe_intermediate_size", "num_experts_per_tok", "vocab_size",
        "norm_eps")] == [2048, 32, 8, 3, 11776, 64, 1536, 4, 65536, 1e-5]
    cfg = manifest.model_config(config)
    for key, field in {**config["field_of"],
                       **config["reduced_field_of"]}.items():
        got = getattr(cfg, field)
        assert (list(got) if isinstance(got, tuple) else got) \
            == config[key], key
    assert cfg.head_dim * cfg.n_heads == cfg.d_model
    assert {"head_dim", "tie_embedding", "norm_topk_eps",
            "in_proj_order"} <= set(config["assumed"])
    assert "4 pipeline stages" in config["deployment"]
    # the reference goes by the file: the stage, and the nano model
    served = dict(ref.sizes_of({"tok_embed": np.zeros((8, 2048))}))
    assert served["layer_types"] == tuple(config["layer_types"])
    assert (served["dense"], served["top_k"], served["theta"]) == (1, 4, 1e6)
    nano = dict(ref.sizes_of({"tok_embed": np.zeros((8, 64))}))
    assert nano["layer_types"] == NANO and nano["top_k"] == K
    kinds = ref.kinds_of(served["layer_types"], 1)
    assert [k[0] for k in kinds] == ["dense_convs", "attns"] + ["convs"] * 3 \
        + ["attns"] + ["convs"] * 3
    assert [k[1] for k in kinds] == [0, 0, 0, 1, 2, 1, 3, 4, 5]
    # the cell's traffic is the issue's, number for number
    traffic = manifest.load().load_traffic("decode_conv_moe_rag")
    assert traffic["clients"] == 192
    assert traffic["engine"] == {
        "max_lanes": 128, "block_size": 128, "num_blocks": [5376, 32],
        "prefill_chunk": 256, "prefill_lanes": 4, "max_seq_len": 4864}
    r = traffic["requests"]
    assert (r["prompt_len"], r["output_len"], r["lead_in_s"],
            r["fill_requests"]) == (
        {"dist": "uniform", "lo": 64, "hi": 256},
        {"dist": "uniform", "lo": 256, "hi": 512}, 20.0, 128)
    s = r["sessions"]
    assert (s["count"], s["groups"], s["head_len"], s["grouped_share"],
            s["restart_prob"], s["preroll_turns"]) == (
        192, 16, 4096, 1.0, 1.0, 0)
    assert traffic["check"]["samples"] == 3


# -- conv_flops and the readers, by hand ---------------------------------------

def _fields():
    return manifest.fields(manifest.load().load_config("lfm2-24b-a2b"))


def test_conv_flops_against_hand_counts():
    f = _fields()
    n = {"state": 7, "kv": 2, "experts": 8, "window": 0}
    # a conv mixer's weights: 2048 x 6144 + 2048 x 2048 + 3 x 2048, bf16
    assert conv_flops.conv_weight_bytes(f) == 2 * (12582912 + 4194304 + 6144)
    assert round(conv_flops.conv_weight_bytes(f) / 1e6, 1) == 33.6
    # the whole call of 128 lanes: two products, 8 operations a lane and
    # column between them; 2 rows of tail in and out, the normed input,
    # the projection written and read, the result
    flops, nbytes = conv_flops.conv_mix(128, f)
    assert flops == 2 * 128 * 4 * 2048 * 2048 + 128 * 8 * 2048
    assert nbytes == conv_flops.conv_weight_bytes(f) + 2 * 128 * 2048 * (
        4 + 1 + 3 + 1 + 1)
    # a step's weights with all 64 experts hit: ISSUE 54's 9.66 + 0.69 GB
    weights = conv_flops.step_weight_bytes(f, n, 64.0)
    experts = 2 * 8 * 64 * 3 * 2048 * 1536
    assert round(experts / 1e9, 2) == 9.66
    rest = weights - experts
    assert rest == 2 * (7 * (4 * 2048 * 2048 + 3 * 2048)
                        + 2 * 2048 * 64 * (2 * 32 + 2 * 8)
                        + 8 * 2048 * 64 + 3 * 2048 * 11776 + 2048 * 65536)
    assert round(rest / 1e9, 2) == 0.69
    # 4 KB a token over the 2 attention layers; 128 lanes' tails in 7
    assert conv_flops.kv_bytes(f, n, 1.0) == 4096
    assert conv_flops.tail_bytes(f, n, 128) == 2 * 7 * 128 * 2 * 2 * 2048


def _run(**over):
    f = _fields()
    run = {
        "fields": f, "device": {"kind": "TPU v5 lite"},
        "traffic": {"engine": {"max_lanes": 128}, "trace": {}},
        "stats0": {"layers": {"state": 7, "kv": 2, "experts": 8, "window": 0},
                   "prefix_misses": 10,
                   "conv": {"steps_t1": 0, "rows_t1": 0},
                   "ssm": {"snapshots_adopted": 90},
                   "moe": {"assignments": 0, "expert_load": [0] * 64,
                           "experts_hit": 0, "layer_steps": 0}},
        "stats1": {"layers": {"state": 7, "kv": 2, "experts": 8, "window": 0},
                   "prefix_misses": 12,
                   "conv": {"steps_t1": 1000, "rows_t1": 126000},
                   "ssm": {"snapshots_adopted": 288},
                   "moe": {"assignments": 8000 * 600, "expert_load": [1] * 64,
                           "experts_hit": 8000 * 64, "layer_steps": 8000}},
        "trace": {"busy_s": 2.0, "kernels": {
            "paged_decode_attention": {"calls": 200.0, "seconds": 0.3},
            "moe_grouped_matmul": {"calls": 2400.0, "seconds": 1.4}},
            "ops_table": [["fusion bf16[128,6144]", 0.02],
                          ["fusion bf16[1152,1,6144]", 0.01],
                          ["bitcast_select_fusion bf16[128,4096]", 0.002],
                          ["fusion bf16[7,129,4096]", 0.0015],
                          ["fusion bf16[128,11776]", 0.3],
                          ["fusion bf16[128,2048]", 0.5]]},
    }
    run.update(over)
    return run


def test_the_conv_readers_on_hand_computed_numbers(monkeypatch):
    from benchmark import ssm_flops
    read = lambda name, run: manifest.module("layer_metrics", name).read(run)
    run = _run()
    monkeypatch.setattr(ssm_flops, "slice_context", lambda run: 128 * 4500.0)
    # 198 admissions adopted a snapshot, 2 found no prefix
    assert read("conv_state_snapshot_hit_pct", run) == 100.0 * 198 / 200
    # the W_in products and the operations on the lanes' tails, by shape
    assert read("conv_mix_share_pct", run) == pytest.approx(
        100.0 * (0.02 + 0.01 + 0.002 + 0.0015) / 2.0)
    f = run["fields"]
    # 100 step programs in the slice (200 calls over 2 attention layers)
    assert conv_flops.steps(run) == 100.0
    nbytes = 100 * (conv_flops.step_weight_bytes(f, run["stats1"]["layers"],
                                                 64.0)
                    + 4096 * 128 * 4500.0
                    + conv_flops.tail_bytes(f, run["stats1"]["layers"], 126))
    assert read("conv_moe_step_roofline", run) == pytest.approx(
        100.0 * nbytes / 819e9 / 2.0)
    # ONE population: 2,400 calls are 800 (layer, step) pairs at the
    # window's own 600 assignments and 64 experts a pair
    from benchmark import moe_flops
    pair = moe_flops.expert_layer_s(600.0, 64.0, {"d_model": 2048,
                                                 "d_ff": 1536},
                                    manifest.peaks("TPU v5 lite"))
    got = read("conv_moe_grouped_matmul_roofline", run)
    assert got == pytest.approx(100.0 * pair * 800 / 1.4)
    assert got <= 100.0
    # a program without the kernel, the counters or the family: nothing,
    # and no raise (the parent's side of a traced run)
    bare = _run(stats0={}, stats1={}, trace={"busy_s": 2.0, "kernels": {},
                                            "ops_table": []})
    for name in ("conv_mix_share_pct", "conv_moe_step_roofline",
                 "conv_moe_grouped_matmul_roofline",
                 "conv_state_snapshot_hit_pct"):
        assert read(name, bare) is None, name
        other = dict(bare, fields={"d_model": 2048})
        assert read(name, other) is None, name
