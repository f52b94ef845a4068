"""Nemotron-H (models/nemotronh.py): layers that are ONE part alone (a
state-space mixer, or attention without positions, or squared-ReLU experts
beside a shared one), against the plain reference
(benchmark/reference/nemotronh.py); the recurrence at a head of half the
lane width against the loop over positions it is defined by (ops/ssm.py,
two heads folded into a lane row); the grouped multiply at a width that is
no multiple of 128 (ops/moe.py).  Nano size on the CPU, float32; the engine
and its cache are tests/test_state_cache.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotronh as ref
from ray_tpu.inference import PagedKVCache
from ray_tpu.models import decoder, nemotronh
from ray_tpu.ops import moe, ssm
from tests import serving_script
from tests.test_falconh1 import _draw, _loop

NANO = nemotronh.CONFIGS["nemotronh-nano"]
SHARE = nemotronh.CONFIGS["nemotronh-nano-share"]
TOL = 1e-4      # float32 on both sides: the order of the sums (test_falconh1)


def _init(cfg=NANO, seed=0):
    return serving_script.init_params(nemotronh, cfg, seed)


def test_the_forward_pass_gives_the_references_logits():
    params = _init()
    tokens = jax.random.randint(jax.random.key(1), (2, 45), 0, 512)
    got = serving_script.forward(nemotronh, params, tokens, NANO)
    want = ref.logits(params, tokens)
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_two_expert_shares_add_up_to_the_uncut_layer():
    """Experts 0-7 on one chip and 8-15 on the other, the router whole on
    both: the two shares' expert layers, the shared expert counted once,
    add up to the uncut reference layer, in the program and in the
    reference alike."""
    params = _init()
    e = params["experts"]
    p = {k: v[1] for k, v in e.items()}
    u = jax.random.normal(jax.random.key(3), (37, 64))
    s = dict(ref.sizes_of(params))
    whole = ref.experts(u, p, s)
    shared = ref.matmul(ref.relu2(ref.matmul(u, p["ws_up"])), p["ws_down"])

    def half(lo, reference):
        cut = {**p, "w_up_t": p["w_up_t"][lo:lo + 8],
               "w_down": p["w_down"][lo:lo + 8]}
        if reference:
            return ref.experts(u, cut, dict(s, experts_offset=lo))
        cfg = dataclasses.replace(NANO, n_experts_held=8, experts_offset=lo)
        y, _, load = decoder.shared_relu2_moe_ffn(
            u[None], {**jax.tree.map(lambda a: a[None], cut),
                      "router": p["router"],
                      "router_bias": p["router_bias"], "ws_up": p["ws_up"],
                      "ws_down": p["ws_down"], "layer": 0}, cfg)
        assert load.shape == (8,)
        return y[0]

    for reference in (True, False):
        both = half(0, reference) + half(8, reference) - shared
        np.testing.assert_allclose(both, whole, atol=TOL)
    assert float(jnp.abs(half(0, True) - half(8, True)).max()) > 100 * TOL


def test_the_share_of_the_nano_model_is_the_references_share():
    params = _init(SHARE)
    assert params["experts"]["w_up_t"].shape == (3, 8, 24, 64)
    tokens = jax.random.randint(jax.random.key(4), (1, 30), 0, 512)
    np.testing.assert_allclose(
        serving_script.forward(nemotronh, params, tokens, SHARE),
        ref.logits(params, tokens, experts_offset=8), atol=TOL)


@pytest.mark.parametrize("what", ["positions", "routed_scale", "bias",
                                  "norm_topk_prob"])
def test_what_the_family_states_moves_the_logits(what):
    """No positional encoding (a rotation would move every logit), the
    routed experts' scale, the router's bias in the choice and the norm of
    the chosen scores are where the reference has them."""
    params = _init()
    tokens = jax.random.randint(jax.random.key(2), (1, 24), 0, 512)
    want = ref.logits(params, tokens)
    cfg, fam = NANO, nemotronh
    if what == "positions":
        fam = decoder.bind(lambda c: dataclasses.replace(
            nemotronh.spec(c), rope_theta=10000.0))
        moved = fam.forward(params, tokens, cfg)[0]      # (a family of its own)
    elif what == "bias":
        bumped = {**params, "experts": {
            **params["experts"],
            "router_bias": params["experts"]["router_bias"].at[:, :8].add(
                1.0)}}
        moved = serving_script.forward(nemotronh, bumped, tokens, cfg)
    else:
        cfg = dataclasses.replace(NANO, **{
            what: 1.0 if what == "routed_scale" else False})
        moved = serving_script.forward(nemotronh, params, tokens, cfg)
    assert float(jnp.abs(moved - want).max()) > 100 * TOL


# -- ops/ssm.py at a head of half the lane width -----------------------------

def _folded_state(rng, layers=2, slots=5, h=4, n=16, p=64):
    """A state buffer as the cache stores heads of 64: [L, S, H/2, N, 128],
    and the same as the recurrence sees it [L, S, H, N, P]."""
    plain = rng.standard_normal((layers, slots, h, n, p)).astype(np.float32)
    assert ssm.state_shape(h, n, p) == (h // 2, n, 2 * p)
    return np.asarray(ssm._fold(jnp.asarray(plain), 2)), plain


def test_a_head_of_128_is_stored_as_it_was_and_a_head_of_64_folded():
    assert ssm.state_shape(32, 256, 128) == (32, 256, 128)
    assert ssm.state_shape(64, 128, 64) == (32, 128, 128)
    assert ssm.state_shape(3, 16, 64) == (3, 16, 64)     # odd: not folded
    # a group of 3 heads does not fold by 2, though the 6 heads would: the
    # shape the cache builds is one `_folded` takes
    assert ssm.state_shape(6, 16, 64, groups=2) == (6, 16, 64)
    assert ssm.state_shape(8, 16, 64, groups=2) == (4, 16, 128)
    for shape, groups in (((6, 16, 64), 2), ((4, 16, 128), 2)):
        heads = shape[0] * shape[2] // 64
        assert ssm._folded(jnp.zeros((1, 1) + shape), heads, 64,
                           groups) == shape[2] // 64
    v = jnp.arange(2 * 6 * 3 * 4, dtype=jnp.float32).reshape(2, 6, 3, 4)
    np.testing.assert_array_equal(ssm._unfold(ssm._fold(v, 2), 2), v)
    # head 2j + i's columns are lanes i P to (i + 1) P of folded head j
    np.testing.assert_array_equal(ssm._fold(v, 2)[:, 1, :, 4:], v[:, 3])
    with pytest.raises(ValueError, match="do not fold"):
        ssm.ssm_update(jnp.zeros((1, 2, 2, 16, 128)),
                       jnp.zeros((1, 4, 64)), jnp.zeros((1, 4)),
                       -jnp.ones(4), jnp.zeros((1, 4, 16)),
                       jnp.zeros((1, 4, 16)), jnp.zeros(1, jnp.int32))


@pytest.mark.parametrize("t,chunk", [(8, 8), (9, 8), (37, 8), (16, 4)],
                         ids=["one_chunk", "one_over", "ragged", "four"])
def test_the_chunked_scan_of_folded_heads_is_the_recurrence(t, chunk):
    """`test_falconh1`'s script of the scan (a state that is not zero,
    padded rows, a row not stepped) at heads of 64 in a folded buffer, by
    plain XLA and by the kernel interpreted."""
    x, dt, a, bm, cm = _draw(t, 3, t, p=64)
    valid = np.ones((3, t), np.float32)
    valid[1, t // 2:] = 0
    valid[2] = 0
    dt = dt * valid[..., None]
    folded, plain = _folded_state(np.random.default_rng(7))
    slots = np.array([3, 0, 4], np.int32)
    fresh = np.array([False, True, False])
    s0 = np.where(fresh[:, None, None, None], 0, plain[1, slots])
    want_y, want_s = _loop(x, dt, a, bm, cm, s0)
    for kernel in (False, True):
        y, new = ssm.ssm_scan(jnp.asarray(folded), x, dt, a, bm, cm, slots,
                              fresh, 1, chunk=chunk, use_kernel=kernel,
                              interpret=True)
        assert new.shape == folded.shape
        np.testing.assert_allclose(y, want_y, atol=5e-5)
        new = np.asarray(ssm._unfold(new, 2))
        np.testing.assert_allclose(new[1, slots[:2]], want_s[:2], atol=5e-5)
        assert np.array_equal(new[1, 4], plain[1, 4])      # not stepped
        assert np.array_equal(new[0], plain[0])
        assert np.array_equal(new[1, [1, 2]], plain[1, [1, 2]])


def test_the_update_of_folded_heads_is_one_step_of_the_recurrence():
    x, dt, a, bm, cm = _draw(3, 3, 1, p=64)
    dt[2] = 0                            # a lane that is not stepped
    folded, plain = _folded_state(np.random.default_rng(8))
    slots = np.array([3, 0, 4], np.int32)
    want_y, want_s = _loop(x, dt, a, bm, cm, plain[1, slots])
    for kernel in (False, True):
        y, new = ssm.ssm_update(jnp.asarray(folded), x[:, 0], dt[:, 0], a,
                                bm[:, 0], cm[:, 0], slots, 1,
                                use_kernel=kernel, interpret=True)
        assert y.shape == (3, 4, 64) and new.shape == folded.shape
        np.testing.assert_allclose(y, want_y[:, 0], atol=1e-5)
        new = np.asarray(ssm._unfold(new, 2))
        np.testing.assert_allclose(new[1, slots], want_s, atol=1e-5)
        assert np.array_equal(new[1, 4], plain[1, 4])
        assert np.array_equal(new[0], plain[0])


# -- ops/moe.py at a width that is no multiple of 128 ------------------------

@pytest.mark.parametrize("transposed", [False, True], ids=["kn", "nk"])
def test_grouped_matmul_takes_a_width_that_is_no_multiple_of_128(transposed):
    """N = 232 (1856 / 8: 1.8 lane rows) as one whole-N tile, the matrices
    held [K, N] or as published [N, K]."""
    rng = np.random.default_rng(0)
    sizes = np.array([5, 0, 130, 1, 0, 20], np.int32)
    x = rng.standard_normal((160, 96)).astype(np.float32)
    w = rng.standard_normal((2, 6, 96, 232)).astype(np.float32)
    got = moe.grouped_matmul(
        x, np.swapaxes(w, -1, -2) if transposed else w, sizes, 1,
        interpret=True, transposed=transposed)
    group = np.repeat(np.arange(6), sizes)
    want = np.einsum("mk,mkn->mn", x[:156], w[1, group])
    assert got.shape == (160, 232)
    np.testing.assert_allclose(got[:156], want, atol=1e-4)


@pytest.mark.parametrize("first_held", [None, 4], ids=["whole", "share"])
def test_the_two_matrix_expert_ffn_is_the_einsum(first_held):
    """`w_down relu(w_up x)^2` of each token's chosen experts, weighted:
    against every expert over every token by einsum."""
    rng = np.random.default_rng(1)
    t, d, f, e, k = 50, 32, 40, 8, 3
    held = e if first_held is None else 4
    x = rng.standard_normal((t, d)).astype(np.float32)
    ids = np.stack([rng.permutation(e)[:k] for _ in range(t)])
    wts = rng.uniform(0.1, 1.0, (t, k)).astype(np.float32)
    w_up_t = rng.standard_normal((2, held, f, d)).astype(np.float32) * 0.2
    w_down = rng.standard_normal((2, held, f, d)).astype(np.float32) * 0.2
    valid = np.ones(t, bool)
    valid[-7:] = False
    y, load = moe.expert_ffn(x, ids, wts, None, w_up_t, w_down, 1, valid,
                             first_held=first_held, up_transposed=True)
    lo = first_held or 0
    dense = np.zeros((t, e), np.float32)
    np.put_along_axis(dense, ids, wts, 1)
    dense = dense[:, lo:lo + held] * valid[:, None]
    hidden = np.maximum(np.einsum("td,efd->tef", x, w_up_t[1]), 0) ** 2
    want = np.einsum("te,tef,efd->td", dense, hidden, w_down[1])
    np.testing.assert_allclose(y, want, atol=1e-4)
    np.testing.assert_array_equal(load, (dense > 0).sum(0))


# -- the cached forward -------------------------------------------------------

def test_prefill_in_chunks_then_decode_gives_the_references_logits():
    """The cached forward by hand: three chunks (the last one padded), then
    tokens one at a time, two lanes at different depths in slots that are
    not their rows; every position's logits against one forward pass of
    the reference.  The K/V pools have the ONE attention layer, the state
    buffers the THREE mixer layers."""
    cfg, params = NANO, _init()
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 512, n) for n in (29, 22)]
    cache = PagedKVCache.for_model(nemotronh, cfg, num_blocks=(32, 2),
                                   block_size=4, max_lanes=4, max_seq_len=64)
    assert [p.shape[0] for p in cache.step_pools[0]] == [1, 1, 3, 3]
    assert cache.step_pools[0][2].shape == (3, 5, 2, 16, 128)
    # row i is lane (2, 0)[i]; chunks of 8, 8 and 8 beside 8, 8 and 3 (lane
    # 0's last chunk is padded)
    got, _, load = serving_script.serve(
        nemotronh, cfg, nemotronh.serving_params(params, cfg), cache, seqs,
        8, [2, 0], prefill=[24, 19], name_slots=True,
        load=jnp.zeros((16 + 2,), jnp.int32))
    for logits, seq in zip(got, seqs):
        np.testing.assert_allclose(logits, ref.row_logits(params, seq),
                                   atol=TOL)
    # every valid token's 4 assignments in each of the 3 expert layers
    assert int(load[:16].sum()) == 4 * 3 * (29 + 22)


def test_a_training_step_is_refused_for_what_is_still_true():
    """Refused because its Mamba-2 layers are a mixer whose chunked scan has no
    backward pass: not for its experts, which train since the grouped multiply
    has its backward."""
    with pytest.raises(NotImplementedError, match="mixer") as refusal:
        nemotronh.loss_fn(_init(), {"tokens": jnp.zeros((1, 8), jnp.int32)},
                          NANO)
    assert "expert" not in str(refusal.value)


def test_the_spec_names_one_part_a_run_and_what_each_keeps():
    spec = nemotronh.spec(NANO)
    assert spec.rope_theta is None and not spec.pos_table
    kinds = [(r.blocks, r.n_layers, r.first, r.offset, r.pools)
             for r in spec.runs]
    assert kinds == [
        ("mixers", 1, 0, 0, (2, 3)), ("experts", 1, 0, 0, ()),
        ("mixers", 1, 1, 1, (2, 3)), ("experts", 1, 1, 1, ()),
        ("mixers", 1, 2, 2, (2, 3)), ("attns", 1, 0, 0, (0, 1)),
        ("experts", 1, 2, 2, ())]
    for run in spec.runs:
        parts = [run.attn, run.mixer, run.ffn]
        assert sum(p is not None for p in parts) == 1
    assert decoder.layer_counts(spec, NANO) == {
        "kv": 1, "window": 0, "state": 3, "experts": 3}
    assert decoder.SSM.state(NANO) == decoder.StateRows(4, 320, 4, 64, 16, 2)
    published = nemotronh.NemotronHConfig()
    assert decoder.layer_counts(nemotronh.spec(published), published) == {
        "kv": 6, "window": 0, "state": 23, "experts": 23}
