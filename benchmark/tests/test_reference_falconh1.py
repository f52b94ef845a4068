"""The Falcon-H1 reference against a second, independent formulation (the
model written once more as the equations read: numpy float64, a loop over
positions, the convolution by indexing, the recurrence a head at a time,
rotary embedding by complex multiplication, nothing in blocks), on what the
check must catch, on the arithmetic of `ssm_flops.py`, and through the
cell's rehearsal."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import manifest, ssm_flops
from benchmark.reference import falconh1 as ref
from ray_tpu.models import falconh1

CFG = falconh1.CONFIGS["falconh1-nano"]          # float32
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def params():
    p = falconh1.init_params(CFG, jax.random.key(0))
    noisy = {k: 1.0 + 0.1 * jax.random.normal(jax.random.key(9), v.shape)
             for k, v in p["blocks"].items()
             if k.endswith("_norm") or k == "D"}
    p["blocks"] = {**p["blocks"], **noisy}
    p["final_norm"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.key(8), p["final_norm"].shape)
    return p


def second_formulation(params, tokens):
    """[L] tokens -> [L, V] logits, numpy float64, a position at a time."""
    c = ref.PUBLISHED
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    n = len(tokens)
    b = p["blocks"]
    h = p["tok_embed"][np.asarray(tokens)] * c["m_emb"]

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g

    def silu(x):
        return x / (1.0 + np.exp(-x))

    def rotate(v):                      # [L, H, d] by complex multiplication
        d = v.shape[-1]
        freq = 1e11 ** (-np.arange(0, d, 2) / d)
        turn = np.exp(1j * np.arange(n)[:, None] * freq[None, :])
        z = (v[..., :d // 2] + 1j * v[..., d // 2:]) * turn[:, None, :]
        return np.concatenate([z.real, z.imag], -1)

    def softmax(z):
        e = np.exp(z - z.max())
        return e / e.sum()

    for layer in range(b["wq"].shape[0]):
        w = {k: v[layer] for k, v in b.items()}
        u = norm(h, w["attn_norm"])
        # -- attention: 4 query heads over 2 key/value heads
        ua = u * c["m_attn_in"]
        q = rotate(np.einsum("ld,dhk->lhk", ua, w["wq"]))
        k = rotate(np.einsum("ld,dhk->lhk", ua, w["wk"]) * c["m_key"])
        v = np.einsum("ld,dhk->lhk", ua, w["wv"])
        heads, hd = q.shape[1:]
        rep = heads // k.shape[1]
        attn = np.zeros((n, heads, hd))
        for t in range(n):
            for j in range(heads):
                probs = softmax(k[:t + 1, j // rep] @ q[t, j] * hd ** -0.5)
                attn[t, j] = probs @ v[:t + 1, j // rep]
        attn = np.einsum("lhk,hkd->ld", attn, w["wo"])
        # -- the state-space mixer
        us = u * c["m_ssm_in"]
        n_heads = w["A_log"].shape[0]
        d_ssm = w["ssm_norm"].shape[0]
        taps, width = w["conv_w"].shape
        gn = (width - d_ssm) // 2
        groups = c["groups"]
        d_state, hp = gn // groups, d_ssm // n_heads
        mz, mx, mb, mc, mdt = c["m_ssm"]
        proj = us @ w["w_in"]
        z = proj[:, :d_ssm] * mz
        xbc = proj[:, d_ssm:d_ssm + width].copy()
        xbc[:, :d_ssm] *= mx
        xbc[:, d_ssm:d_ssm + gn] *= mb
        xbc[:, d_ssm + gn:] *= mc
        dt = proj[:, d_ssm + width:] * mdt
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
        mixed = (y.reshape(n, d_ssm) * w["ssm_norm"]) @ w["w_out"]
        h = h + attn * c["m_attn_out"] + mixed * c["m_ssm_out"]
        # -- the feed-forward
        v2 = norm(h, w["mlp_norm"])
        m_gate, m_down = c["m_mlp"]
        h = h + ((silu((v2 @ w["w_gate"]) * m_gate) * (v2 @ w["w_up"]))
                 @ w["w_down"]) * m_down
    return (norm(h, p["final_norm"]) @ p["lm_head"]) * c["m_head"]


def test_the_reference_is_the_equations_a_position_at_a_time(params):
    tokens = np.random.default_rng(0).integers(0, 512, 29)
    got = np.asarray(ref.row_logits(params, tokens))
    want = second_formulation(params, tokens)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_reference_is_causal_where_the_check_pads(params):
    """`served_token_gaps` pads a sequence to a bucket: what follows a
    position changes none of its logits, through attention, convolution
    and recurrence alike."""
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 512, 21)
    short = np.asarray(ref.row_logits(params, tokens))
    padded = np.asarray(ref.row_logits(
        params, np.concatenate([tokens, rng.integers(0, 512, 11)])))
    np.testing.assert_allclose(padded[:21], short, atol=1e-5)


def test_the_check_catches_a_state_that_is_lost_or_a_scan_from_nothing(
        params):
    """Served tokens are the reference's own greedy ones (gap 0); judged by
    a reference whose recurrence forgets (every A times 8) or whose mixer
    is left out, they are not."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 512, 24).tolist()
    seq = list(prompt)
    for _ in range(12):
        seq.append(int(np.argmax(np.asarray(
            ref.row_logits(params, np.asarray(seq)))[-1])))
    out = seq[len(prompt):]
    gaps, ranks = ref.served_token_gaps(params, prompt, out, bucket=16)
    assert max(gaps) == 0.0 and set(ranks) == {0}
    wrong = {**params, "blocks": {
        **params["blocks"],
        "A_log": params["blocks"]["A_log"] + np.log(8.0)}}
    gaps, _ = ref.served_token_gaps(wrong, prompt, out, bucket=16)
    assert max(gaps) > 0.01
    gaps, _ = ref.served_token_gaps(params, prompt, out, bucket=16,
                                    m_ssm_out=0.0)
    assert max(gaps) > 0.01


def test_the_configuration_file_is_the_catalog_row_but_for_its_cuts():
    config = manifest.load().load_config("falcon-h1-34b")
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"]) == (9, 32640)
    assert published["num_hidden_layers"] == 72 == 8 * 9
    assert published["vocab_size"] == 261120 == 8 * 32640
    cfg = manifest.model_config(config)
    for key, field in {**config["field_of"],
                       **config["reduced_field_of"]}.items():
        value = config[key]
        got = getattr(cfg, field)
        assert (list(got) if isinstance(got, tuple) else got) == value, key
    # the constants the reference keeps are the file's too
    assert ref.PUBLISHED["m_ssm"] == tuple(published["ssm_multipliers"])
    assert ref.PUBLISHED["m_mlp"] == tuple(published["mlp_multipliers"])
    for ours, theirs in {"m_emb": "embedding_multiplier",
                         "m_head": "lm_head_multiplier",
                         "m_key": "key_multiplier",
                         "m_attn_out": "attention_out_multiplier",
                         "m_ssm_in": "ssm_in_multiplier",
                         "m_ssm_out": "ssm_out_multiplier",
                         "theta": "rope_theta",
                         "groups": "mamba_n_groups"}.items():
        assert ref.PUBLISHED[ours] == published[theirs], ours


def test_the_arithmetic_of_a_step_is_the_issues():
    """ISSUE 43's reckoning from the configuration's own fields: 8.08 GB of
    weights a step, 4.83 GB of state read and written at 64 lanes, 18.4 KB
    of K/V a token over the nine layers."""
    f = manifest.fields(manifest.load().load_config("falcon-h1-34b"))
    assert ssm_flops.state_numbers(f) == 32 * 128 * 256
    assert abs(ssm_flops.step_weight_bytes(f) / 1e9 - 8.08) < 0.01
    flops, nbytes = ssm_flops.update(64, f)
    assert abs(9 * nbytes / 1e9 - 4.84) < 0.01
    assert flops == 5.0 * 64 * 32 * 128 * 256
    assert ssm_flops.kv_bytes(f, 1.0) == 9 * 2 * 4 * 128 * 2 == 18432
    # single-query attention reads a cached token's 4 heads once for the
    # 20 query heads
    flops, nbytes = ssm_flops.paged_decode(1000.0, 0, f)
    assert (flops, nbytes) == (4.0 * 1000 * 20 * 128, 2 * 128 * 2000 * 4)
    # a chunk of the scan: C B^T a group, three products a head
    flops, nbytes = ssm_flops.scan(128.0, 1.0, f)
    assert flops == 128 * 2.0 * (2 * 128 * 256 + 32 * (128 * 128
                                                       + 2 * 256 * 128))
    assert nbytes > 2 * 4 * 32 * 128 * 256


def test_the_slices_context_is_the_programs_own_steps():
    """`ssm_flops.slice_context`: the mean of `decode_ctx` over the
    `engine/step` records between the profiler's start and stop, prefill-
    only iterations and records outside the slice left out; the client's
    records only where no record carries the count (the parent's)."""
    def step(ts, **payload):
        return {"kind": "step", "ts": ts, "payload": payload}
    run = {"base": 100.0, "marks": {"trace_on": 110.3, "trace_off": 140.0},
           "traffic": {"trace": {"at_s": 10.0, "slice_s": 2.0}},
           "records": [{"prompt_len": 7, "token_times": [109.0, 113.0]}],
           "engine_events": [
               step(109.9, decode=64, decode_ctx=9000),
               step(110.1, decode=64, decode_ctx=1000),
               step(110.5, decode=0, prefill=4, decode_ctx=0),
               {"kind": "finish", "ts": 111.0, "payload": {}},
               step(111.9, decode=60, decode_ctx=2000),
               step(112.1, decode=64, decode_ctx=9000)]}
    assert ssm_flops.slice_context(run) == 1500.0
    run["engine_events"] = [step(110.1, decode=64), step(111.0, decode=64)]
    assert ssm_flops.slice_context(run) == 8.0      # 7 + the first token
    del run["marks"]["trace_off"]
    assert ssm_flops.slice_context(run) is None


def test_the_cell_rehearses_on_the_cpu():
    """`--rehearse`: the same path at nano size with faked chips: shared
    prompts prefilled in chunks and snapshotted, admissions that adopt
    blocks and a snapshot, a traced window, the reference check behind it
    and a last line the driver can read."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "serve_falconh1_chat_decode", "--seed", "4100000077",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["attempted"] > 50
    assert line["metrics"]["ssm_snapshots_adopted_per_s"]["value"] > 1
    assert line["metrics"]["prefix_hit_share_pct"]["value"] > 50
    assert line["metrics"]["ssm_state_gb"]["value"] > 0
