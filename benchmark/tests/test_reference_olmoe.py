"""The OLMoE reference against a second, independent dense formulation (the
model written once more the way the published code lays it out: sorted
top-k, an index mask per expert, rotary embedding by complex multiplication,
everything batched), and against the system at nano size on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmoe as ref
from ray_tpu.models import llama

CFG = llama.CONFIGS["olmoe-nano"]        # float32 throughout
TOP_K = CFG.n_experts_per_tok


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(0))
    params["blocks"] = {
        k: v * (1.0 + 0.1 * jax.random.normal(jax.random.key(9), v.shape))
        if k.endswith("_norm") else v for k, v in params["blocks"].items()}
    tokens = jax.random.randint(jax.random.key(1), (2, 48), 0,
                                CFG.vocab_size)
    return params, tokens


def second_formulation(params, tokens, top_k, norm_topk_prob=False):
    """[L] tokens -> [L, V] logits, numpy float64 arithmetic."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    b = p["blocks"]

    def norm(x, w):
        return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w

    x = p["tok_embed"][np.asarray(tokens)]
    n = len(tokens)
    layers, d, heads, dh = b["wq"].shape
    for i in range(layers):
        h = norm(x, b["attn_norm"][i])
        q = norm(h @ b["wq"][i].reshape(d, -1), b["q_norm"][i].reshape(-1))
        k = norm(h @ b["wk"][i].reshape(d, -1), b["k_norm"][i].reshape(-1))
        v = (h @ b["wv"][i].reshape(d, -1)).reshape(n, heads, dh)
        # rotate-half rope as a complex multiplication of (x_j, x_{j+dh/2})
        freq = 10000.0 ** (-np.arange(dh // 2) / (dh // 2))
        turn = np.exp(1j * np.arange(n)[:, None] * freq[None, :])[:, None, :]

        def rot(t):
            t = t.reshape(n, heads, dh)
            z = (t[..., :dh // 2] + 1j * t[..., dh // 2:]) * turn
            return np.concatenate([z.real, z.imag], -1)

        q, k = rot(q), rot(k)
        out = np.zeros((n, heads, dh))
        for head in range(heads):
            s = q[:, head] @ k[:, head].T / np.sqrt(dh)
            s[np.triu_indices(n, 1)] = -np.inf
            w = np.exp(s - s.max(-1, keepdims=True))
            out[:, head] = (w / w.sum(-1, keepdims=True)) @ v[:, head]
        x = x + out.reshape(n, -1) @ b["wo"][i].reshape(-1, d)

        h2 = norm(x, b["mlp_norm"][i])
        logits = h2 @ b["router"][i]
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        chosen = np.argsort(-probs, -1, kind="stable")[:, :top_k]
        picked = np.take_along_axis(probs, chosen, -1)
        if norm_topk_prob:
            picked = picked / picked.sum(-1, keepdims=True)
        y = np.zeros_like(x)
        for e in range(probs.shape[-1]):
            rows, slot = np.nonzero(chosen == e)
            if not len(rows):
                continue
            g = h2[rows] @ b["w_gate"][i, e]
            act = g / (1.0 + np.exp(-g)) * (h2[rows] @ b["w_up"][i, e])
            y[rows] += picked[rows, slot][:, None] * (act @ b["w_down"][i, e])
        x = x + y
    return norm(x, p["final_norm"]) @ p["lm_head"]


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_reference_agrees_with_a_second_dense_formulation(setup,
                                                          norm_topk_prob):
    params, tokens = setup
    got = ref.logits(params, tokens, TOP_K, norm_topk_prob)
    for row in range(tokens.shape[0]):
        want = second_formulation(params, tokens[row], TOP_K, norm_topk_prob)
        # float32 against float64: the reference's own rounding
        np.testing.assert_allclose(got[row], want, atol=2e-5)


def test_router_weights_keep_the_lower_index_on_a_tie():
    h2 = jnp.ones((1, 4))
    router = jnp.zeros((4, 6)).at[:, 4].set(1.0)       # 4 wins, the rest tie
    w = np.asarray(ref.router_weights(h2, router, 3))
    assert (w[0] > 0).tolist() == [True, True, False, False, True, False]
    # as they are: the three chosen probabilities do not sum to one
    assert 0.5 < w.sum() < 1.0
    assert ref.router_weights(h2, router, 3, True).sum() == pytest.approx(1.0)


def test_system_agrees_and_served_tokens_are_the_references_choice(setup):
    from ray_tpu.inference import InferenceEngine
    params, tokens = setup
    np.testing.assert_allclose(llama.forward(params, tokens, CFG),
                               ref.logits(params, tokens), atol=1e-4)
    assert ref.top_k_of(params) == TOP_K       # one expert in eight
    eng = InferenceEngine("llama", CFG, max_lanes=2, auto_start=False, seed=3)
    prompt = list(range(5, 25))
    out = eng.generate(prompt, 12)
    gaps, ranks = ref.served_token_gaps(eng.params, prompt, out)
    assert len(gaps) == 12 and max(gaps) < 1e-4 and set(ranks) == {0}
    bad = list(out)
    bad[4] = (bad[4] + 1) % CFG.vocab_size      # a wrong token is seen
    gaps, ranks = ref.served_token_gaps(eng.params, prompt, bad)
    assert gaps[4] > 1e-3 and ranks[4] > 0
    eng.shutdown()


def test_reference_shares_no_code_with_the_program():
    import inspect
    source = inspect.getsource(ref)
    for line in source.splitlines():
        if line.startswith(("import ", "from ")):
            assert "ray_tpu" not in line, line
