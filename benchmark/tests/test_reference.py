"""The plain reference against the system at nano size, on the CPU: logits,
loss, gradients, AdamW, and the serving engine's tokens."""

import jax
import numpy as np
import optax
import pytest

from benchmark.reference import gpt2 as ref
from ray_tpu.models import gpt

CFG = gpt.CONFIGS["nano"]        # float32 throughout


@pytest.fixture(scope="module")
def setup():
    params = gpt.init_params(CFG, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 64), 0, CFG.vocab_size)
    return params, tokens


def test_logits_loss_and_gradients_agree(setup):
    params, tokens = setup
    system, _ = gpt.forward(params, tokens, CFG)
    plain = ref.logits(params, tokens)
    # float32 against float32: rounding of a different operation order only
    np.testing.assert_allclose(system, plain, atol=5e-6)
    np.testing.assert_allclose(ref.logits_by_layer(params, tokens), plain,
                               atol=5e-6)
    batch = {"tokens": tokens}
    assert float(gpt.loss_fn(params, batch, CFG)) == pytest.approx(
        ref.loss_by_layer(params, tokens, 2), abs=1e-5)
    value, grads = ref.loss_and_grad(params, tokens, 2)
    want = jax.grad(gpt.loss_fn)(params, batch, CFG)
    assert value == pytest.approx(float(ref.loss(params, tokens)), abs=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_adamw_steps_follow_the_programs_train_step(setup):
    params, tokens = setup
    opt = optax.adamw(1e-3, weight_decay=1e-4)
    init_state, train_step = gpt.make_train_step(CFG, opt, None)
    state = init_state(jax.random.key(0))
    plain, adam = state["params"], ref.adamw_init(state["params"])
    step = jax.jit(train_step)
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens})
        value, grads = ref.loss_and_grad(plain, tokens, 4)
        assert float(metrics["loss"]) == pytest.approx(value, abs=2e-5)
        plain, adam = ref.adamw_step(plain, grads, adam, learning_rate=1e-3,
                                     weight_decay=1e-4)
    state, metrics = step(state, {"tokens": tokens})
    assert float(metrics["loss"]) == pytest.approx(
        ref.loss_by_layer(plain, tokens, 4), abs=1e-4)


def test_served_tokens_are_the_references_choice():
    from ray_tpu.inference import InferenceEngine
    eng = InferenceEngine("gpt", CFG, max_lanes=2, auto_start=False, seed=3)
    prompt = list(range(5, 25))
    out = eng.generate(prompt, 12)
    gaps, ranks = ref.served_token_gaps(eng.params, prompt, out)
    assert len(gaps) == 12 and max(gaps) < 1e-4 and set(ranks) == {0}
    # a wrong token is seen
    bad = list(out)
    bad[4] = (bad[4] + 1) % CFG.vocab_size
    gaps, ranks = ref.served_token_gaps(eng.params, prompt, bad)
    assert gaps[4] > 1e-3 and ranks[4] > 0
    eng.shutdown()
