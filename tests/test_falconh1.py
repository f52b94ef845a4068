"""Falcon-H1 (models/falconh1.py): a state-space mixer beside attention
heads in every block, against the plain reference
(benchmark/reference/falconh1.py) and, for the recurrence itself, against
the loop over positions it is defined by (ops/ssm.py).  Nano size on the
CPU, float32; the engine and its cache are tests/test_state_cache.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import falconh1 as ref
from ray_tpu.inference import PagedKVCache
from ray_tpu.models import decoder, falconh1
from ray_tpu.ops import ssm
from tests import serving_script

NANO = falconh1.CONFIGS["falconh1-nano"]
# float32 on both sides; what differs is the order of the sums (chunks on
# the matrix unit's einsums against a loop over positions, attention tiled
# against whole): 1e-4 of logits of size 5 is some 100 float32 roundings.
TOL = 1e-4


def _init(cfg=NANO, seed=0):
    return serving_script.init_params(falconh1, cfg, seed)


def _loop(x, dt, a, bm, cm, s0):
    """The recurrence a position at a time in float64: x [B, T, H, P], dt
    [B, T, H], a [H], bm and cm [B, T, G, N], s0 [B, H, N, P] ->
    (y [B, T, H, P], the last state)."""
    x, dt, a, bm, cm = (np.asarray(v, np.float64) for v in (x, dt, a, bm, cm))
    rep = x.shape[2] // bm.shape[2]
    bm, cm = np.repeat(bm, rep, 2), np.repeat(cm, rep, 2)
    s, ys = np.asarray(s0, np.float64), []
    for t in range(x.shape[1]):
        s = s * np.exp(dt[:, t] * a)[:, :, None, None] \
            + bm[:, t][..., None] * (dt[:, t][..., None] * x[:, t])[:, :, None]
        ys.append(np.einsum("bhnp,bhn->bhp", s, cm[:, t]))
    return np.stack(ys, 1), s


def _draw(seed, b, t, h=4, p=8, g=2, n=16):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((b, t, h, p)).astype(np.float32),
            (0.3 * np.log1p(np.exp(rng.standard_normal((b, t, h))))
             ).astype(np.float32),
            -rng.uniform(1, 4, h).astype(np.float32),
            rng.standard_normal((b, t, g, n)).astype(np.float32),
            rng.standard_normal((b, t, g, n)).astype(np.float32))


def test_the_forward_pass_gives_the_references_logits():
    params = _init()
    tokens = jax.random.randint(jax.random.key(1), (2, 45), 0, 512)
    got = serving_script.forward(falconh1, params, tokens, NANO)
    want = ref.logits(params, tokens)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("factor", [
    "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
    "attention_out_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
    "mlp_multipliers", "ssm_multipliers"])
def test_every_stated_factor_moves_the_logits(factor):
    """Each factor is applied where the reference applies it: with the
    weights drawn against the factors, one left at 1 (or halved, for the
    pairs) moves the logits by far more than rounding."""
    params = _init()
    tokens = jax.random.randint(jax.random.key(2), (1, 24), 0, 512)
    was = getattr(NANO, factor)
    other = tuple(0.5 * v for v in was) if isinstance(was, tuple) else 1.0
    cfg = dataclasses.replace(NANO, **{factor: other})
    want = ref.logits(params, tokens)
    moved = serving_script.forward(falconh1, params, tokens, cfg)
    assert float(jnp.abs(moved - want).max()) > 100 * TOL


@pytest.mark.parametrize("t,chunk", [(8, 8), (9, 8), (37, 8), (16, 4)],
                         ids=["one_chunk", "one_over", "ragged", "four"])
def test_the_chunked_scan_is_the_recurrence_at_chunk_edges(t, chunk):
    """From a state that is not zero, with padded rows (dt = 0) behind some
    rows' valid tokens and one row not stepped at all: y and the state
    left behind equal the loop's, the untouched slots are untouched."""
    x, dt, a, bm, cm = _draw(t, 3, t)
    valid = np.ones((3, t), np.float32)
    valid[1, t // 2:] = 0
    valid[2] = 0
    dt = dt * valid[..., None]
    rng = np.random.default_rng(7)
    state = rng.standard_normal((2, 5, 4, 16, 8)).astype(np.float32)
    slots = np.array([3, 0, 4], np.int32)
    fresh = np.array([False, True, False])
    s0 = np.where(fresh[:, None, None, None], 0, state[1, slots])
    want_y, want_s = _loop(x, dt, a, bm, cm, s0)
    for kernel in (False, True):        # plain XLA; the kernel, interpreted
        y, new = ssm.ssm_scan(jnp.asarray(state), x, dt, a, bm, cm, slots,
                              fresh, 1, chunk=chunk, use_kernel=kernel,
                              interpret=True)
        np.testing.assert_allclose(y, want_y, atol=2e-5)
        np.testing.assert_allclose(new[1, slots[:2]], want_s[:2], atol=2e-5)
        assert np.array_equal(new[1, 4], state[1, 4])      # not stepped
        assert np.array_equal(new[0], state[0])
        assert np.array_equal(new[1, [1, 2]], state[1, [1, 2]])


def test_the_update_is_one_step_of_the_recurrence():
    x, dt, a, bm, cm = _draw(3, 3, 1)
    dt[2] = 0                            # a lane that is not stepped
    rng = np.random.default_rng(8)
    state = rng.standard_normal((2, 5, 4, 16, 8)).astype(np.float32)
    slots = np.array([3, 0, 4], np.int32)
    want_y, want_s = _loop(x, dt, a, bm, cm, state[1, slots])
    for kernel in (False, True):
        y, new = ssm.ssm_update(jnp.asarray(state), x[:, 0], dt[:, 0], a,
                                bm[:, 0], cm[:, 0], slots, 1,
                                use_kernel=kernel, interpret=True)
        np.testing.assert_allclose(y, want_y[:, 0], atol=1e-5)
        np.testing.assert_allclose(new[1, slots], want_s, atol=1e-5)
        assert np.array_equal(new[1, 4], state[1, 4])
        assert np.array_equal(new[0], state[0])


def test_the_convolution_carries_its_tail_over_padded_rows():
    """A sequence convolved in two slices, the first padded behind its 5
    valid rows, is the sequence convolved whole (the taps' sum alone: bias
    and activation are the caller's)."""
    rng = np.random.default_rng(9)
    seq = rng.standard_normal((1, 11, 6)).astype(np.float32)
    w = rng.standard_normal((4, 6)).astype(np.float32)
    zero = jnp.zeros((1, 3, 6))
    whole, _ = ssm.conv_tail(seq, zero, w, jnp.array([11]))
    padded = np.concatenate([np.zeros((1, 3, 6), np.float32), seq], 1)
    np.testing.assert_allclose(
        whole, sum(padded[:, i:i + 11] * w[i] for i in range(4)), atol=1e-6)
    first = np.concatenate([seq[:, :5], np.full((1, 3, 6), 9.0, np.float32)],
                           1)
    y1, tail = ssm.conv_tail(first, zero, w, jnp.array([5]))
    y2, tail = ssm.conv_tail(seq[:, 5:], tail, w, jnp.array([6]))
    np.testing.assert_allclose(np.concatenate([y1[:, :5], y2], 1), whole,
                               atol=1e-6)
    np.testing.assert_array_equal(tail, seq[:, -3:])
    _, kept = ssm.conv_tail(first, tail, w, jnp.array([0]))
    np.testing.assert_array_equal(kept, tail)       # no valid row: as it was


def test_prefill_in_chunks_then_decode_gives_the_references_logits():
    """The cached forward by hand: three chunks (the last one padded), then
    tokens one at a time, two lanes at different depths in slots that are
    not their rows; every position's logits against one forward pass of
    the reference."""
    cfg, params = NANO, _init()
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 512, n) for n in (29, 22)]
    cache = PagedKVCache.for_model(falconh1, cfg, num_blocks=(32, 2),
                                   block_size=4, max_lanes=4, max_seq_len=64)
    # row i is lane (2, 0)[i]; chunks of 8, 8 and 8 beside 8, 8 and 3 (lane
    # 0's last chunk is padded)
    got, _, _ = serving_script.serve(
        falconh1, cfg, falconh1.serving_params(params, cfg), cache, seqs, 8,
        [2, 0], prefill=[24, 19], name_slots=True)
    for logits, seq in zip(got, seqs):
        np.testing.assert_allclose(logits, ref.row_logits(params, seq),
                                   atol=TOL)


def test_a_training_step_is_refused():
    with pytest.raises(NotImplementedError, match="state-space"):
        falconh1.loss_fn(_init(), {"tokens": jnp.zeros((1, 8), jnp.int32)},
                         NANO)


def test_the_spec_names_the_mixer_and_what_it_keeps():
    run, = falconh1.spec(NANO).runs
    assert run.mixer is decoder.SSM and run.attn is decoder.HEADS
    assert run.mixer.state(NANO) == decoder.StateRows(4, 96, 4, 8, 16, 2)
