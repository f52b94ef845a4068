"""The EvaByte reference against a second, independent formulation (the
model written once more as the equations read: numpy float64, a loop over
positions that builds each one's set of exact keys and of chunk summaries
by hand, rotary embedding by complex multiplication, nothing in blocks),
against the system at nano size on the CPU, on what the check must catch,
and through the cell's rehearsal."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import eva_flops, manifest
from benchmark.reference import evabyte as ref
from ray_tpu.models import evabyte

CFG = evabyte.CONFIGS["evabyte-nano"]       # float32; window 32, chunk 4
KW = dict(window=CFG.window_size, chunk=CFG.chunk_size)


@pytest.fixture(scope="module")
def params():
    p = evabyte.init_params(CFG, jax.random.key(0))
    p["blocks"] = {
        k: 0.1 * jax.random.normal(jax.random.key(9), v.shape)
        if k.endswith("_norm") else v for k, v in p["blocks"].items()}
    p["final_norm"] = 0.1 * jax.random.normal(jax.random.key(8),
                                              p["final_norm"].shape)
    return p


def second_formulation(params, tokens, window, chunk):
    """[L] tokens -> [L, heads x V] logits, numpy float64, a position at a
    time."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    n = len(tokens)
    x = p["tok_embed"][np.asarray(tokens)]
    b = p["blocks"]

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * (1 + g)

    def rotate(v):                      # [L, H, d] by complex multiplication
        d = v.shape[-1]
        freq = 100000.0 ** (-np.arange(0, d, 2) / d)
        turn = np.exp(1j * np.arange(n)[:, None] * freq[None, :])
        z = (v[..., :d // 2] + 1j * v[..., d // 2:]) * turn[:, None, :]
        return np.concatenate([z.real, z.imag], -1)

    def softmax(z):
        e = np.exp(z - z.max())
        return e / e.sum()

    for layer in range(b["wq"].shape[0]):
        h = norm(x, b["attn_norm"][layer])
        q = rotate(np.einsum("ld,dhk->lhk", h, b["wq"][layer]))
        k = rotate(np.einsum("ld,dhk->lhk", h, b["wk"][layer]))
        v = np.einsum("ld,dhk->lhk", h, b["wv"][layer])
        heads, d = q.shape[1:]
        out = np.zeros_like(q)
        for head in range(heads):
            mu, phi = b["eva_mu"][layer, head], b["eva_phi"][layer, head]
            for i in range(n):
                w = i // window
                keys = [k[j, head] for j in range(w * window, i + 1)]
                vals = [v[j, head] for j in range(w * window, i + 1)]
                for c in range(0, w * window, chunk):   # earlier windows'
                    ks, vs = k[c:c + chunk, head], v[c:c + chunk, head]
                    keys.append(softmax(ks @ mu) @ ks)
                    vals.append(softmax(ks @ phi) @ vs)
                weights = softmax(np.asarray(keys) @ q[i, head]
                                  / math.sqrt(d))
                out[i, head] = weights @ np.asarray(vals)
        x = x + np.einsum("lhk,hkd->ld", out, b["wo"][layer])
        h = norm(x, b["mlp_norm"][layer])
        gate = h @ b["w_gate"][layer]
        x = x + (gate / (1 + np.exp(-gate)) * (h @ b["w_up"][layer])) \
            @ b["w_down"][layer]
    return norm(x, p["final_norm"]) @ p["lm_head"]


@pytest.mark.parametrize("length", [9, 32, 33, 75], ids=[
    "inside_a_window", "a_window", "over_the_edge", "into_a_third_window"])
def test_reference_matches_a_loop_over_positions(params, length):
    """Its two masks, its pooling and its one softmax against sets built by
    hand; float32 at highest precision against float64."""
    tokens = np.random.default_rng(length).integers(0, CFG.vocab_size, length)
    got = np.asarray(ref.row_logits(params, tokens, **KW))
    want = second_formulation(params, tokens, **KW)
    assert got.shape == (length, CFG.num_pred_heads * CFG.vocab_size)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_the_masks_are_the_sets_of_the_equations():
    exact, behind = ref.visible(jnp.arange(12), 12, 8, 2)
    exact, behind = np.asarray(exact), np.asarray(behind)
    for i in range(12):
        assert [j for j in range(12) if exact[i, j]] == list(
            range(i // 8 * 8, i + 1))
        assert [c for c in range(6) if behind[i, c]] == (
            [] if i < 8 else [0, 1, 2, 3])


def test_program_and_reference_agree_and_a_gap_reads_zero(params):
    tokens = np.random.default_rng(3).integers(0, CFG.vocab_size, 70)
    want = np.asarray(ref.row_logits(params, tokens, **KW))
    got = np.asarray(evabyte.forward(params, jnp.asarray(tokens)[None],
                                     CFG)[0])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    served = np.argmax(want[39:69, :CFG.vocab_size], -1).tolist()
    seq = tokens[:40].tolist()
    gaps, ranks = ref.served_token_gaps(params, seq, served[:1], bucket=4,
                                        **KW)
    assert gaps == [0.0] and ranks == [0]
    # a token the reference ranks second reads its distance to the first
    row = want[39, :CFG.vocab_size]
    second = int(np.argsort(row)[-2])
    gaps, ranks = ref.served_token_gaps(params, seq, [second], bucket=4,
                                        **KW)
    assert ranks == [1] and gaps[0] == pytest.approx(
        float(row.max() - row[second]), abs=1e-5)


def test_rows_and_bytes_of_the_yardstick():
    f = {"window_size": 2048, "chunk_size": 16, "n_heads": 32,
         "d_model": 4096, "d_ff": 11008, "n_layers": 8, "num_pred_heads": 8,
         "vocab_size": 320}
    assert [eva_flops.rows(n, f) for n in (1, 2048, 2049, 14000, 15104)] \
        == [1, 2048, 129, 6 * 128 + 1712, 7 * 128 + 768]
    flops, nbytes = eva_flops.decode_attention(1000, 24, f)
    assert flops == 1000 * 2 * 32 * 128 * 2          # 16,384 a row
    assert nbytes == (2 * 1000 + 2 * 24) * 4096 * 2  # 16,384 bytes a row
    assert eva_flops.layer_weight_bytes(f) == 2 * (4 * 4096 ** 2
                                                   + 3 * 4096 * 11008)
    assert eva_flops.step_bytes(f, 0) == pytest.approx(3.26e9, rel=0.01)
    records = [{"prompt_len": 2040, "token_times": [1.0, 2.0, 3.0]},
               {"prompt_len": 100, "token_times": [5.0], "error": "x"},
               {"prompt_len": 4096, "token_times": [0.5, 9.0]}]
    assert eva_flops.live_rows(records, 2.5, f) == (
        eva_flops.rows(2042, f) + eva_flops.rows(4097, f))
    run = {"records": records, "fields": f, "traffic": {"trace": {
        "slice_s": 2.0}}, "marks": {"trace_on": 1.0, "trace_off": 9.0}}
    # over [1, 3]: the first request all along, the second from 0.5 on
    assert eva_flops.slice_rows(run, instants=2) == (
        eva_flops.live_rows(records, 1.5, f)
        + eva_flops.live_rows(records, 2.5, f)) / 2
    assert eva_flops.slice_rows({"marks": {}}) is None
    # a program without the counters: the readers find nothing, and say so
    assert eva_flops.counters({"stats0": {}, "stats1": {}}) is None
    assert eva_flops.rows_per_step({}) is None


def test_the_configuration_file_keeps_every_published_width():
    m = manifest.load()
    config = m.load_config("evabyte")
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers"}
    cfg = manifest.model_config(config)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
            cfg.window_size, cfg.chunk_size, cfg.num_pred_heads,
            cfg.vocab_size) == (8, 4096, 32, 128, 11008, 2048, 16, 8, 320)
    # the rehearsal's window and chunk keep the published ratio to the head
    # size, which is how the reference finds them
    nano = manifest.model_config(config, None, True)
    assert (nano.window_size, nano.chunk_size) == ref.shape_of(
        {"blocks": {"wq": np.zeros((1, 1, 1, nano.head_dim))}})
    traffic = m.load_traffic("decode_eva_sessions")
    assert traffic["engine"] == {
        "max_lanes": 24, "block_size": 128, "num_blocks": 576,
        "prefill_chunk": 512, "prefill_lanes": 4, "max_seq_len": 15104}
    s = traffic["requests"]["sessions"]
    assert (s["head_len"], s["max_prompt"], s["count"], s["groups"]) == (
        10240, 14336, 32, 8)


def test_the_cell_rehearses_on_the_cpu():
    """`--rehearse`: the same path at nano size with faked chips, windows
    closing under the closed loop; `correct` by the reference."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "serve_evabyte_sessions_decode", "--seed", "3000000019",
         "--seconds", "6", "--rehearse"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["failed"] == 0
