"""The dots3 reference against a second, independent formulation (numpy
float64, everything at once: rotary embedding by complex multiplication,
the indexer's choice by an argsort, the window by a loop over positions,
the router by a sorted top-k), against the system at nano size on the CPU,
and the arithmetic of `sparse_flops.py` and its readers on a made-up run."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import manifest, sparse_flops
from benchmark.reference import dots3 as ref
from ray_tpu.models import dots3

CFG = dots3.CONFIGS["dots3-nano"]        # float32 throughout
KW = dict(top_k=CFG.n_experts_per_tok, index_topk=CFG.index_topk,
          window=CFG.sliding_window)


@pytest.fixture(scope="module")
def setup():
    params = jax.jit(dots3.init_params, static_argnums=0)(
        CFG, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (60,), 0, CFG.vocab_size)
    return params, np.asarray(tokens)


def second_formulation(params, tokens):
    """[L] tokens -> [L, V] logits, numpy float64 arithmetic."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    n, d = len(tokens), CFG.d_model

    def norm(x, w):
        return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w

    def swiglu(h, g, u, dn):
        a = h @ g
        return (a / (1.0 + np.exp(-a)) * (h @ u)) @ dn

    def rotate(x, theta):                       # [L, ..., K], by complex
        k = x.shape[-1]
        z = x[..., :k // 2] + 1j * x[..., k // 2:]
        ang = np.arange(n)[:, None] * theta ** (-np.arange(k // 2) * 2.0 / k)
        z = z * np.exp(1j * ang).reshape((n,) + (1,) * (x.ndim - 2) + (-1,))
        return np.concatenate([z.real, z.imag], -1)

    def attention(x, b, i, full):
        theta = CFG.rope_theta if full else CFG.swa_rope_theta
        h = norm(x, b["attn_norm"][i])
        qr, c = b["q_norm"].shape[1], b["kv_norm"].shape[1]
        c_q = norm(h @ b["w_qa"][i], b["q_norm"][i]) * np.sqrt(d / qr)
        kv = h @ b["w_kva"][i]
        c_kv = norm(kv[:, :c], b["kv_norm"][i]) * np.sqrt(d / c)
        k_rope = rotate(kv[:, c:], theta)
        r = k_rope.shape[1]
        q = np.einsum("lr,rhk->lhk", c_q, b["w_qb"][i])
        nope = q.shape[-1] - r
        q_rope = rotate(q[..., nope:], theta)
        kvh = np.einsum("lc,chk->lhk", c_kv, b["w_kvb"][i])
        allowed = [list(range(max(0, t - CFG.sliding_window + 1), t + 1))
                   for t in range(n)]
        if full:
            q_i = np.einsum("lr,rhk->lhk", c_q, b["w_iq"][i])
            k_i = h @ b["w_ik"][i]
            mu = k_i.mean(-1, keepdims=True)
            k_i = (k_i - mu) / np.sqrt(((k_i - mu) ** 2).mean(
                -1, keepdims=True) + 1e-5) * b["ik_scale"][i] + b["ik_bias"][i]
            q_i = np.concatenate([rotate(q_i[..., :r], theta),
                                  q_i[..., r:]], -1)
            k_i = np.concatenate([rotate(k_i[:, :r], theta), k_i[:, r:]], -1)
            hi, di = q_i.shape[1:]
            w = h @ b["w_iw"][i] / np.sqrt(hi * di)
            allowed = []
            for t in range(n):
                score = (w[t][:, None] * np.maximum(
                    q_i[t] @ k_i[:t + 1].T, 0)).sum(0)
                allowed.append(sorted(np.argsort(-score, kind="stable")[
                    :CFG.index_topk].tolist()))
        gate = 1.0 / (1.0 + np.exp(-(h @ b["w_head_gate"][i])))
        out = np.zeros((n, q.shape[1], kvh.shape[-1] - nope))
        for t in range(n):
            s = allowed[t]
            logits = (np.einsum("hk,shk->hs", q[t, :, :nope], kvh[s, :, :nope])
                      + q_rope[t] @ k_rope[s].T) / np.sqrt(nope + r)
            pr = np.exp(logits - logits.max(-1, keepdims=True))
            pr /= pr.sum(-1, keepdims=True)
            out[t] = np.einsum("hs,shk->hk", pr, kvh[s, :, nope:])
        out = out * gate[:, :, None]
        return x + np.einsum("lhk,hkd->ld", out, b["wo"][i])

    def experts(x, b, i):
        h2 = norm(x, b["mlp_norm"][i])
        s = 1.0 / (1.0 + np.exp(-(h2 @ b["router"][i])))
        y = swiglu(h2, b["ws_gate"][i], b["ws_up"][i], b["ws_down"][i])
        for t in range(n):
            top = np.argsort(-(s[t] + b["router_bias"][i]), kind="stable")[
                :CFG.n_experts_per_tok]
            for e in top:
                y[t] += s[t, e] / s[t, top].sum() * swiglu(
                    h2[t], b["w_gate"][i, e], b["w_up"][i, e],
                    b["w_down"][i, e])
        return x + y

    x = p["tok_embed"][tokens]
    full = win = 0
    for kind in CFG.kinds:
        if full == 0:                           # layer 0: dense, full
            b = p["lead_blocks"]
            x = attention(x, b, 0, True)
            x = x + swiglu(norm(x, b["mlp_norm"][0]), b["w_gate"][0],
                           b["w_up"][0], b["w_down"][0])
            full = 1
            continue
        if kind == dots3.FULL:
            x = experts(attention(x, p["full_blocks"], full - 1, True),
                        p["full_blocks"], full - 1)
            full += 1
        else:
            x = experts(attention(x, p["win_blocks"], win, False),
                        p["win_blocks"], win)
            win += 1
    return norm(x, p["final_norm"]) @ p["lm_head"]


def test_the_reference_agrees_with_a_second_formulation(setup):
    """Across the window's slide (9 of 60) and past index_topk (16); the
    float32 reference against float64: 1e-4 of logits of order 4."""
    params, tokens = setup
    want = second_formulation(params, tokens)
    got = np.asarray(ref.row_logits(params, tokens, **KW))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    assert np.abs(want).max() > 1.0


def test_the_reference_runs_in_blocks_and_by_rows(setup):
    """A padded sequence's first rows are the unpadded one's (what follows
    a position changes neither its choice nor its attention), and `rows`
    gives the rows asked for."""
    params, tokens = setup
    whole = np.asarray(ref.row_logits(params, tokens, **KW))
    padded = np.concatenate([tokens, np.zeros(68, tokens.dtype)])   # 128
    part = np.asarray(ref.row_logits(params, padded, rows=(20, 40), **KW))
    np.testing.assert_allclose(part, whole[20:60], atol=1e-4, rtol=0)
    gaps, ranks = ref.served_token_gaps(
        params, tokens[:40].tolist(), tokens[40:].tolist(), bucket=64, **KW)
    rows, nxt = whole[39:59], tokens[40:]
    own = rows[np.arange(20), nxt]
    np.testing.assert_allclose(gaps, rows.max(-1) - own, atol=2e-4, rtol=0)
    assert ranks == (rows > own[:, None]).sum(-1).tolist()


def test_the_published_order_of_layers():
    kinds = ref.kinds_of(1, 12, 33)
    assert [k for k, _ in kinds] == ["lead"] + (
        ["full"] + ["win"] * 3) * 11 + ["full"]
    assert ref.kinds_of(1, 2, 6) == [
        ("lead", 0), ("full", 0), ("win", 0), ("win", 1), ("win", 2),
        ("full", 1), ("win", 3), ("win", 4), ("win", 5)]
    assert dict(ref.sizes_of({"tok_embed": np.zeros((8, 5120))}))[
        "index_topk"] == 2048
    assert dict(ref.sizes_of({"tok_embed": np.zeros((8, 64))}))[
        "window"] == 9


# -- the yardstick's arithmetic -------------------------------------------------

@pytest.fixture(scope="module")
def fields():
    with open(os.path.join(manifest.ROOT, "benchmark", "configs",
                           "dots3-note-prev.json")) as f:
        return json.load(f)["fields"]


def test_the_cut_by_the_configurations_own_numbers(fields):
    """The issue's arithmetic, re-reckoned from `fields` (bf16)."""
    f = fields
    assert sparse_flops.layers(f) == (1, 2, 6)
    full = sparse_flops.attention_weight_bytes(f, sparse_flops.FULL) / 2
    win = sparse_flops.attention_weight_bytes(f, sparse_flops.WINDOW) / 2
    assert full == pytest.approx(144.05e6, rel=2e-3)
    assert win == pytest.approx(90.83e6, rel=2e-3)
    expert = 3 * f["d_model"] * f["d_expert"]
    assert expert == pytest.approx(23.59e6, rel=1e-3)
    layer = sparse_flops.expert_bytes(f, f["n_experts_held"]) / 2
    assert full + layer == pytest.approx(546.4e6, rel=2e-3)
    assert win + layer == pytest.approx(493.2e6, rel=2e-3)
    whole = sparse_flops.step_weight_bytes(f, f["n_experts_held"]) \
        + 2 * f["d_model"] * f["vocab_size"]          # + the embedding
    assert whole == pytest.approx(9.20e9, rel=3e-3)
    # a lane's T=1 step over 16.7k of context, one layer of each kind
    assert sparse_flops.index_scores(16700, 1, f)[1] == pytest.approx(
        4.3e6, rel=0.03)
    assert sparse_flops.latent_rows(2048, 1, sparse_flops.sizes(
        f, sparse_flops.FULL))[1] == pytest.approx(2.6e6, rel=0.05)
    assert sparse_flops.latent_rows(513, 1, sparse_flops.sizes(
        f, sparse_flops.WINDOW))[1] == pytest.approx(1.2e6, rel=0.2)


def _run(fields, **over):
    """A traced run as the readers see it: 100 T=1 steps and 4 chunks in a
    slice of 1.5 busy seconds, 64 lanes at 16.7k of context."""
    s0 = {"sparse": {"decode_steps": 10, "ctx_tokens": 10 * 64 * 16700,
                     "rows_chosen": 10 * 64 * 2048,
                     "window_rows": 10 * 64 * 513},
          "windows": {"blocks_freed": 100},
          "moe": {"assignments_held": 1000, "experts_hit": 900,
                  "layer_steps": 80, "assignments": 0, "expert_load": []}}
    s1 = {"sparse": {"decode_steps": 1010, "ctx_tokens": 1010 * 64 * 16700,
                     "rows_chosen": 1010 * 64 * 2048,
                     "window_rows": 1010 * 64 * 513},
          "windows": {"blocks_freed": 2140},
          "moe": {"assignments_held": 1000 + 8000 * 32,
                  "experts_hit": 900 + 8000 * 14, "layer_steps": 8080,
                  "assignments": 0, "expert_load": []}}
    kernels = {"sparse_index_scores": {"calls": 300, "seconds": 0.15},
               "sparse_latent_decode_attention": {"calls": 300,
                                                  "seconds": 0.1},
               "window_latent_decode_attention": {"calls": 600,
                                                  "seconds": 0.12},
               "sparse_index_chunk_scores": {"calls": 24, "seconds": 0.02},
               "moe_grouped_matmul": {"calls": 104 * 8 * 3, "seconds": 0.4}}
    run = {"stats0": s0, "stats1": s1, "fields": fields, "seconds": 51.0,
           "traffic": {"engine": {"max_lanes": 64}},
           "device": {"kind": "TPU v5 lite"},
           "trace": {"busy_s": 1.5, "kernels": kernels, "ops_table": [
               ["sort f32[64,17024]", 0.3], ["sort s32[512]", 0.01],
               ["moe_grouped_matmul bf16[512,1536] (kernel)", 0.55],
               ["moe_grouped_matmul bf16[512,5120] (kernel)", 0.3],
               ["moe_grouped_matmul bf16[4096,1536] (kernel)", 0.1]]}}
    run.update(over)
    return run


NAMES = ("sparse_index_roofline", "sparse_decode_roofline",
         "window_decode_roofline", "sparse_select_share_pct",
         "sparse_attn_share_pct", "sparse_step_roofline",
         "sparse_grouped_matmul_roofline", "sparse_experts_hit_pct",
         "sparse_selected_pct", "sparse_ctx_tokens_per_step",
         "window_blocks_freed_per_s")


def test_every_new_reader_reads_a_number_and_nothing_without_counters(fields):
    run = _run(fields)
    values = {n: manifest.module("layer_metrics", n).read(run)
              for n in NAMES}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["sparse_ctx_tokens_per_step"] == 64 * 16700
    assert values["sparse_selected_pct"] == pytest.approx(
        100 * 2048 / 16700)
    assert values["window_blocks_freed_per_s"] == pytest.approx(40.0)
    assert values["sparse_experts_hit_pct"] == pytest.approx(
        100 * 14 / 16)
    assert values["sparse_select_share_pct"] == pytest.approx(20.0)
    # scoring, choice and the attentions: 0.15 + 0.1 + 0.12 + 0.02 + 0.3
    assert values["sparse_attn_share_pct"] == pytest.approx(
        100 * 0.69 / 1.5)
    # an index call is 64 x 16.7k keys of 256 bytes + a float32 score each
    # at 819 GB/s = 0.339 ms; 300 calls took 0.15 s
    assert values["sparse_index_roofline"] == pytest.approx(
        100 * 300 * 64 * 16700 * 260 / 819e9 / 0.15, rel=0.01)
    assert all(values[n] <= 105 for n in NAMES if n.endswith("roofline"))
    # a program without the counters or the kernels (the parent's): nothing
    bare = _run(fields, stats0={}, stats1={}, trace={
        "busy_s": 1.5, "kernels": {}, "ops_table": []})
    assert all(manifest.module("layer_metrics", n).read(bare) is None
               for n in NAMES)
    untraced = _run(fields, trace=None)
    assert manifest.module("layer_metrics", "sparse_index_roofline").read(
        untraced) is None
