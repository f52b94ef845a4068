"""The A.X-K1 reference against a second, independent formulation (the model
written once more the way the published code lays it out: numpy float64,
sorted top-k with an index mask per expert, rotary embedding by complex
multiplication, absorbed nowhere, everything at once), against the system at
nano size on the CPU, and on what a share leaves out."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import axk1 as ref
from ray_tpu.models import axk1

CFG = axk1.CONFIGS["axk1-nano"]        # float32 throughout
YARN = (("factor", CFG.rope_factor),
        ("original", CFG.rope_original_max_seq_len))
KW = dict(top_k=CFG.n_experts_per_tok, yarn=YARN)


@pytest.fixture(scope="module")
def setup():
    params = axk1.init_params(CFG, jax.random.key(0))
    for stack in ("blocks", "lead_blocks"):
        params[stack] = {
            k: v * (1.0 + 0.1 * jax.random.normal(jax.random.key(9), v.shape))
            if k.endswith("_norm") else v for k, v in params[stack].items()}
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0,
                                CFG.vocab_size)
    return params, tokens


def second_formulation(params, tokens, top_k, first_held=0):
    """[L] tokens -> [L, V] logits, numpy float64 arithmetic."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    n = len(tokens)

    def norm(x, w):
        return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * w

    def swiglu(h, g, u, d):
        a = h @ g
        return (a / (1.0 + np.exp(-a)) * (h @ u)) @ d

    # YaRN, written from the paper's description
    dim, theta = CFG.qk_rope_head_dim, CFG.rope_theta
    factor, orig = CFG.rope_factor, CFG.rope_original_max_seq_len
    i = np.arange(dim // 2)
    own = theta ** (-2.0 * i / dim)
    turns = orig * own / (2 * np.pi)    # turns over the original positions
    lo = max(math.floor(dim * math.log(orig / (32 * 2 * math.pi))
                        / (2 * math.log(theta))), 0)
    hi = min(math.ceil(dim * math.log(orig / (1 * 2 * math.pi))
                       / (2 * math.log(theta))), dim - 1)
    assert turns[lo] >= 32 or lo == 0
    gamma = np.clip((i - lo) / (hi - lo), 0, 1)    # 0: keep, 1: interpolate
    freq = own * (1 - gamma) + own / factor * gamma
    turn = np.exp(1j * np.arange(n)[:, None] * freq[None, :])   # [n, dim/2]
    m = 0.1 * math.log(factor) + 1.0
    nope, rdim, vdim = CFG.qk_nope_head_dim, dim, CFG.v_head_dim
    scale = (nope + rdim) ** -0.5 * m * m

    def rot(t):                      # [n, heads, dim], rotate-half pairing
        z = (t[..., :dim // 2] + 1j * t[..., dim // 2:]) * turn[:, None, :]
        return np.concatenate([z.real, z.imag], -1)

    def attention(x, b, i):
        h = norm(x, b["attn_norm"][i])
        q = np.einsum("lr,rhk->lhk", norm(h @ b["w_qa"][i], b["q_norm"][i]),
                      b["w_qb"][i])
        kv = h @ b["w_kva"][i]
        c_kv = norm(kv[:, :CFG.kv_lora_rank], b["kv_norm"][i])
        k_rope = rot(kv[:, None, CFG.kv_lora_rank:])[:, 0]
        up = np.einsum("lc,chk->lhk", c_kv, b["w_kvb"][i])
        out = np.zeros((n, CFG.n_heads, vdim))
        for head in range(CFG.n_heads):
            qh = np.concatenate([q[:, head, :nope],
                                 rot(q[:, head:head + 1, nope:])[:, 0]], -1)
            kh = np.concatenate([up[:, head, :nope], k_rope], -1)
            s = qh @ kh.T * scale
            s[np.triu_indices(n, 1)] = -np.inf
            w = np.exp(s - s.max(-1, keepdims=True))
            out[:, head] = (w / w.sum(-1, keepdims=True)) @ up[:, head, nope:]
        return x + out.reshape(n, -1) @ b["wo"][i].reshape(-1, CFG.d_model)

    x = p["tok_embed"][np.asarray(tokens)]
    lead = p["lead_blocks"]
    for i in range(lead["attn_norm"].shape[0]):
        x = attention(x, lead, i)
        x = x + swiglu(norm(x, lead["mlp_norm"][i]), lead["w_gate"][i],
                       lead["w_up"][i], lead["w_down"][i])
    b = p["blocks"]
    for i in range(b["router"].shape[0]):
        x = attention(x, b, i)
        h2 = norm(x, b["mlp_norm"][i])
        scores = 1.0 / (1.0 + np.exp(-(h2 @ b["router"][i])))
        chosen = np.argsort(-scores, -1, kind="stable")[:, :top_k]
        picked = np.take_along_axis(scores, chosen, -1)
        picked = picked / picked.sum(-1, keepdims=True) * 2.5
        y = swiglu(h2, b["ws_gate"][i], b["ws_up"][i], b["ws_down"][i])
        for e in range(b["w_gate"].shape[1]):
            rows, slot = np.nonzero(chosen == first_held + e)
            if len(rows):
                y[rows] += picked[rows, slot][:, None] * swiglu(
                    h2[rows], b["w_gate"][i, e], b["w_up"][i, e],
                    b["w_down"][i, e])
        x = x + y
    return norm(x, p["final_norm"]) @ p["lm_head"]


def test_reference_matches_the_second_formulation(setup):
    params, tokens = setup
    got = ref.logits(params, tokens, **KW)
    for row in range(2):
        want = second_formulation(params, np.asarray(tokens[row]),
                                  CFG.n_experts_per_tok)
        np.testing.assert_allclose(got[row], want, atol=2e-5, rtol=0)


def test_reference_is_given_the_same_share(setup):
    """Experts 8 to 11 of 16: what the other twelve would add is left out,
    and the result differs from the whole model's."""
    params, tokens = setup
    share = dict(params, blocks={
        k: v[:, 8:12] if k in ("w_gate", "w_up", "w_down") else v
        for k, v in params["blocks"].items()})
    row = np.asarray(tokens[0])
    got = ref.row_logits(share, row, first_held=8, **KW)
    want = second_formulation(share, row, CFG.n_experts_per_tok, first_held=8)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    whole = ref.row_logits(params, row, **KW)
    assert float(jnp.abs(got - whole).max()) > 0.05


def test_reference_computes_in_blocks_what_it_computes_whole(setup,
                                                             monkeypatch):
    """Head groups, query blocks and feed-forward slices change the order
    of the sums and nothing else."""
    params, tokens = setup
    row = np.asarray(tokens[0])[:32]
    whole = ref.row_logits(params, row, **KW)
    monkeypatch.setattr(ref, "HEAD_GROUP", 2)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "WIDTH_BLOCK", 16)
    jax.clear_caches()
    blocked = ref.row_logits(params, row, **KW)
    jax.clear_caches()
    np.testing.assert_allclose(blocked, whole, atol=2e-5, rtol=0)


def test_system_matches_the_reference_at_nano_size(setup):
    params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got = axk1.forward(params, tokens, CFG)
    np.testing.assert_allclose(got, ref.logits(params, tokens, **KW),
                               atol=2e-4, rtol=0)


def test_served_token_gaps_judge_greedy_tokens(setup):
    """The reference's own greedy continuation has gap 0 and rank 0 at every
    generated position; a wrong token has a positive gap."""
    params, tokens = setup
    prompt = np.asarray(tokens[0])[:20].tolist()
    seq = list(prompt)
    for _ in range(5):
        seq.append(int(jnp.argmax(ref.row_logits(
            params, np.asarray(seq), **KW)[-1])))
    gaps, ranks = ref.served_token_gaps(params, prompt, seq[20:], bucket=16,
                                        **KW)
    assert len(gaps) == 5 and max(gaps) == 0.0 and set(ranks) == {0}
    wrong = [(t + 1) % CFG.vocab_size for t in seq[20:]]
    gaps, ranks = ref.served_token_gaps(params, prompt, wrong[:1], bucket=16,
                                        **KW)
    assert gaps[0] > 0 and ranks[0] > 0


def test_published_constants():
    assert ref.top_k_of({"blocks": {"router": np.zeros((6, 7168, 192))}}) == 8
    assert ref.softmax_scale(192, ref.YARN) == pytest.approx(
        192 ** -0.5 * 1.3466 ** 2, rel=1e-4)
    cfg = dataclasses.asdict(axk1.Axk1Config())
    assert (cfg["routed_scale"], cfg["norm_eps"]) == (ref.ROUTED_SCALE,
                                                      ref.EPS)
