"""LFM2 (models/lfm2.py): layers whose operator is a gated short convolution
(a lane's only state its convolution's two-row tail) or grouped-query
attention with a norm a head, over a dense SwiGLU or sigmoid-routed experts,
against the plain reference (benchmark/reference/lfm2.py).  Nano size on the
CPU, float32; the engine and its cache of one state buffer are
tests/test_state_cache.py, the pair's program tests/test_pair_step.py, the
cached forward by hand tests/serving_script.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2 as ref
from ray_tpu.inference import InferenceEngine, PagedKVCache
from ray_tpu.models import decoder, lfm2
from tests import serving_script

NANO = lfm2.CONFIGS["lfm2-nano"]
# float32 on both sides, sums in another order: 2e-5 of the largest logit,
# as the other families' (the logits here have unit size, the largest 4-5)
REL = 2e-5


def _init(seed=0):
    return serving_script.init_params(lfm2, NANO, seed)


def _close(got, want):
    want = np.asarray(want)
    assert float(np.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=REL * np.abs(want).max())


def test_the_forward_pass_gives_the_references_logits():
    params = _init()
    tokens = jax.random.randint(jax.random.key(1), (2, 45), 0, 512)
    _close(serving_script.forward(lfm2, params, tokens, NANO),
           ref.logits(params, tokens))


@pytest.mark.parametrize("chunk", [1, 2, 5])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(chunk):
    """The cached forward by hand: chunks that split inside the three-tap
    window (1 and 2 rows: a chunk shorter than the tail it leaves; 5: the
    last one padded), then tokens one at a time, two lanes at different
    depths in slots that are not their rows; every position's logits
    against one forward pass of the reference.  The K/V pools have the TWO
    attention layers, the state part ONE buffer, the tails of the three
    conv layers; a slot nobody writes stays as it was, whatever it held,
    and a lane that starts at position 0 starts from zeros whatever its
    slot held."""
    cfg, params = NANO, _init()
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 512, n) for n in (14, 9)]
    cache = PagedKVCache.for_model(lfm2, cfg, num_blocks=(32, 2),
                                   block_size=4, max_lanes=4, max_seq_len=64)
    k, v, tails = cache.step_pools[0]
    assert (k.shape[0], v.shape[0], tails.shape) == (2, 2, (3, 5, 2 * 64))
    assert cache.parts[-1].wire == ("tail",)
    # every slot starts as garbage: the lanes' must not read it, the others
    # must keep it
    dirty = jnp.asarray(rng.standard_normal(tails.shape), tails.dtype)
    # row i is lane (2, 0)[i]; lane 0 prefills 10 of its 14 in chunks,
    # lane 1 joins a chunk later and prefills 6 of its 9 (so its last chunk
    # of 5 is padded)
    got, (pools, _), load = serving_script.serve(
        lfm2, cfg, lfm2.serving_params(params, cfg), cache, seqs, chunk,
        [2, 0], prefill=[10, 6], late=[0, 1], name_slots=True,
        pools=((k, v, dirty), None), load=jnp.zeros((16 + 2,), jnp.int32))
    for logits, seq in zip(got, seqs):
        _close(logits, ref.row_logits(params, seq))
    left = np.asarray(pools[2])
    for slot in (1, 3, 4):               # nobody's: as they were
        np.testing.assert_array_equal(left[:, slot], np.asarray(dirty)[:, slot])
    assert not np.array_equal(left[:, 2], np.asarray(dirty)[:, 2])
    # every valid token's 4 assignments in each of the 4 expert layers
    assert int(load[:16].sum()) == 4 * 4 * (14 + 9)


@pytest.mark.parametrize("dtype,rows,width", [
    (jnp.float32, 16, 128), (jnp.bfloat16, 48, 256)],
    ids=["f32_16_rows", "bf16_48_rows"])
def test_one_token_a_row_is_the_gates_the_taps_and_the_overwrite(
        dtype, rows, width):
    """`decoder._conv_attend` over one token a row whose slot is its index
    (the decoding lanes' part of every step) against the same steps written
    out a row at a time in numpy: y = C * (w0 t0 + w1 t1 + w2 B u), the
    taps summed in float32 from the oldest, and the slot left holding
    (t1, B u); a row that holds no token leaves its slot, a fresh row reads
    zeros, the slots behind the rows and the other layers stay.  Rows that
    NAME their slots (a chunk's: the form over [B, K - 1, D] with its
    gather) leave the same tails bit for bit."""
    rng = np.random.default_rng(0)
    layers, slots, taps = 3, rows + 1, 3
    bcu = jnp.asarray(rng.standard_normal((rows, 1, 3 * width)), dtype)
    tails = jnp.asarray(
        rng.standard_normal((layers, slots, (taps - 1) * width)), dtype)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (taps, width)), dtype)
    live = rng.random(rows) < 0.8
    fresh = (rng.random(rows) < 0.3) & live
    assert (~live).any() and fresh.any()
    positions = jnp.asarray(np.where(fresh, 0, 7)[:, None], jnp.int32)
    attend = lambda slots: decoder._conv_attend(
        (bcu,), (tails,), {"conv_w": w, "cache_layer": jnp.int32(1)}, None,
        dataclasses.replace(NANO, conv_taps=taps),
        decoder.Lanes(None, positions, jnp.asarray(live[:, None]), None,
                      slots))
    (y,), (out,) = attend(None)
    (named,), (named_out,) = attend(jnp.arange(rows, dtype=jnp.int32))
    np.testing.assert_array_equal(out, named_out)
    f32 = lambda x: np.asarray(x, np.float32)
    gate_b, gate_c, u = np.split(f32(bcu[:, 0]), 3, axis=-1)
    v = f32(jnp.asarray(gate_b * u, dtype))         # as the tail stores it
    old = f32(tails[1, :rows]).reshape(rows, taps - 1, width)
    start = np.where(fresh[:, None, None], 0, old)
    conv = start[:, 0] * f32(w[0]) + start[:, 1] * f32(w[1]) + v * f32(w[2])
    want = gate_c * f32(jnp.asarray(conv, dtype))
    kept = np.where(live[:, None, None],
                    np.stack([start[:, 1], v], axis=1), old)
    np.testing.assert_array_equal(
        f32(out), f32(tails.at[1, :rows].set(
            jnp.asarray(kept.reshape(rows, -1), dtype))))
    close = (dict(rtol=2 ** -22, atol=1e-6) if dtype == jnp.float32
             else dict(rtol=2 ** -6, atol=0.02))
    np.testing.assert_allclose(f32(y[:, 0]), want, **close)
    np.testing.assert_allclose(f32(named), f32(y), **close)


ENGINE = dict(auto_start=False, max_lanes=4, block_size=4, num_blocks=(96, 4),
              max_seq_len=96, prefill_chunk=8, prefill_lanes=2,
              capture_logp=True)


def _run(eng, *handles):
    while eng.step():
        pass
    return [h.tokens() for h in handles]


def test_a_lane_that_adopts_a_snapshot_decodes_what_one_that_prefilled_does():
    """The second request of a head adopts the attention layers' blocks and
    the conv layers' tails behind them (3 layers x 2 rows x 64 numbers) and
    convolves only its own turn: the tokens and their log-probs are those
    of an engine without a prefix cache, which prefilled from token 0, and
    the tokens the reference's greedy ones."""
    rng = np.random.default_rng(1)
    head = rng.integers(0, 512, 32).tolist()
    first, second = (head + rng.integers(0, 512, n).tolist() for n in (5, 7))
    eng = InferenceEngine("lfm2", NANO, _init(), **ENGINE)
    _run(eng, eng.submit(first, 4))
    st = eng.stats()
    assert st["ssm"]["snapshots_taken"] == 1 and st["prefix_hit_tokens"] == 0
    handle = eng.submit(second, 16)
    out, = _run(eng, handle)
    st = eng.stats()
    assert st["prefix_hit_tokens"] == 32
    assert st["ssm"]["snapshots_adopted"] == 1
    # the part's counters where every state cache has them; no recurrence
    assert st["ssm"]["state_buffers"] == 1
    assert "tokens_updated" not in st["ssm"]
    # 3 conv layers; rows: both prompts' own tokens less the adopted head
    assert st["conv"]["layers"] == 3
    assert st["conv"]["rows_chunk"] == len(first) + 7
    assert st["conv"]["rows_t1"] == 4 + 16 - 2
    assert st["conv"]["steps_chunk"] == st["prefill"]["steps"]
    plain = InferenceEngine("lfm2", NANO, _init(), prefix_cache=False,
                            **ENGINE)
    cold = plain.submit(second, 16)
    assert _run(plain, cold) == [out]
    np.testing.assert_allclose(handle.logps, cold.logps, atol=REL * 5)
    want = np.asarray(jnp.argmax(ref.row_logits(
        _init(), np.asarray(second + out)), -1))
    assert out == want[len(second) - 1:len(second) + 15].tolist()


def test_the_router_chooses_by_score_plus_bias_and_weighs_by_the_score():
    """s = sigmoid(x W_r); the 4 of largest s + bias; their UNBIASED s over
    (their sum + 1e-6), times the scale: by hand in float64, with a bias
    large enough to move the choice and an epsilon large enough to see."""
    rng = np.random.default_rng(2)
    x = rng.standard_normal((11, 64)).astype(np.float32)
    router = (rng.standard_normal((64, 16)) / 8).astype(np.float32)
    bias = rng.standard_normal(16).astype(np.float32) * 0.3
    for eps in (1e-6, 0.5):
        cfg = dataclasses.replace(NANO, norm_topk_eps=eps, routed_scale=1.5)
        _, experts, weights = decoder._route(
            jnp.asarray(x)[None], {"router": jnp.asarray(router),
                                   "router_bias": jnp.asarray(bias)}, cfg)
        s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router)))
        want_e = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :4]
        assert np.array_equal(np.sort(experts, -1), np.sort(want_e, -1))
        # the bias moved some token's choice, and is not in the weights
        assert not np.array_equal(
            np.sort(want_e, -1),
            np.sort(np.argsort(-s, axis=-1, kind="stable")[:, :4], -1))
        chosen = np.take_along_axis(s, np.asarray(experts), -1)
        np.testing.assert_allclose(
            weights, chosen / (chosen.sum(-1, keepdims=True) + eps) * 1.5,
            rtol=1e-5)
    # and the reference's weights are the same numbers, laid over all 16
    dense = np.zeros((11, 16))
    np.put_along_axis(dense, np.asarray(experts), np.asarray(weights), -1)
    np.testing.assert_allclose(
        ref.router_weights(jnp.asarray(x), router, bias, 4, 1.5, 0.5),
        dense, atol=1e-6)


@pytest.mark.parametrize("what", ["bias", "norm_topk_prob", "rope_theta",
                                  "qk_norm", "gate_order"])
def test_what_the_family_states_moves_the_logits(what):
    """The router's bias in the choice, the norm of the chosen scores, the
    rotation's base, the norm a head and the order of W_in's thirds (B and
    C swapped with u: the gate would come after the convolution's input)
    are where the reference has them."""
    params = _init()
    tokens = jax.random.randint(jax.random.key(2), (1, 24), 0, 512)
    want = ref.logits(params, tokens)
    cfg = NANO
    if what == "bias":
        params = {**params, "convs": {
            **params["convs"],
            "router_bias": params["convs"]["router_bias"].at[:, :8].add(1.0)}}
    elif what == "qk_norm":
        params = {**params, "attns": {
            **params["attns"], "q_norm": params["attns"]["q_norm"] * 2.0}}
    elif what == "gate_order":
        w_in = params["convs"]["w_in"]
        params = {**params, "convs": {**params["convs"], "w_in": jnp.roll(
            w_in, w_in.shape[-1] // 3, axis=-1)}}
    else:
        cfg = dataclasses.replace(NANO, **{
            what: 1e4 if what == "rope_theta" else False})
    moved = serving_script.forward(lfm2, params, tokens, cfg)
    assert float(jnp.abs(moved - want).max()) > 100 * REL


def test_a_training_step_is_refused_for_what_is_still_true():
    """Refused because its gated short convolution is a mixer, and no mixer has a
    train path: not for its experts, which train since the grouped multiply has
    its backward."""
    with pytest.raises(NotImplementedError, match="mixer") as refusal:
        lfm2.loss_fn(_init(), {"tokens": jnp.zeros((1, 8), jnp.int32)}, NANO)
    assert "expert" not in str(refusal.value)


def test_the_spec_names_an_operator_and_a_feed_forward_a_run():
    spec = lfm2.spec(NANO)
    assert spec.tied_head and spec.rope_theta == 1e6
    kinds = [(r.blocks, r.n_layers, r.first, r.offset, r.pools,
              r.attn is not None, r.mixer is not None, r.ffn)
             for r in spec.runs]
    assert kinds == [
        ("dense_convs", 1, 0, 0, (2,), False, True, decoder.SWIGLU),
        ("attns", 1, 0, 0, (0, 1), True, False, decoder.EXPERTS),
        ("convs", 2, 1, 0, (2,), False, True, decoder.EXPERTS),
        ("attns", 1, 1, 1, (0, 1), True, False, decoder.EXPERTS)]
    assert decoder.layer_counts(spec, NANO) == {
        "kv": 2, "window": 0, "state": 3, "experts": 4}
    # what the conv mixer states: two rows of d_model, no recurrence
    assert decoder.CONV.state(NANO) == decoder.StateRows(3, 64)
    assert decoder.CONV.state(NANO).heads == 0
    published = lfm2.Lfm2Config()
    assert published.layer_types.count("full_attention") == 10
    assert [i for i, k in enumerate(published.layer_types)
            if k == "full_attention"] == list(range(2, 40, 4))
    assert decoder.layer_counts(lfm2.spec(published), published) == {
        "kv": 10, "window": 0, "state": 30, "experts": 38}
    runs = lfm2.spec(published).runs
    assert (runs[0].blocks, runs[0].n_layers) == ("dense_convs", 2)
    assert lfm2.num_params(published) == 23_843_661_440
