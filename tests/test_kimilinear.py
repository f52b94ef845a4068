"""Kimi Linear (models/kimilinear.py): layers whose mixer is Kimi Delta
Attention (a float32 state decayed a key channel and corrected by a delta
rule, behind short convolutions) or latent attention without positions, over
a dense SwiGLU or a share of sigmoid-routed experts beside a shared one,
against the plain reference (benchmark/reference/kimilinear.py); the two
kernels of ops/ssm.py against the loop over positions the recurrence is
defined by.  Nano size on the CPU, float32; the cache of one latent pool
beside lane state is tests/test_state_cache.py and tests/test_cache_parts.py,
the pair's program tests/test_pair_step.py, the cached forward by hand
tests/serving_script.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimilinear as ref
from ray_tpu.inference import InferenceEngine, PagedKVCache
from ray_tpu.models import decoder, kimilinear
from ray_tpu.ops import ssm
from tests import serving_script

NANO = kimilinear.CONFIGS["kimilinear-nano"]
SHARE = kimilinear.CONFIGS["kimilinear-nano-share"]
# float32 on both sides, sums in another order: 2e-5 of the largest logit,
# as the other families' (the logits here have unit size, the largest 4-5)
REL = 2e-5


def _init(cfg=NANO, seed=0):
    return serving_script.init_params(kimilinear, cfg, seed)


def _close(got, want, rel=REL):
    want = np.asarray(want)
    assert float(np.abs(want).max()) > 1.0
    np.testing.assert_allclose(got, want, atol=rel * np.abs(want).max())


def test_the_forward_pass_gives_the_references_logits():
    params = _init()
    tokens = jax.random.randint(jax.random.key(1), (2, 45), 0, 512)
    _close(serving_script.forward(kimilinear, params, tokens, NANO),
           ref.logits(params, tokens))


# -- ops/ssm.py: the two kernels against the recurrence ----------------------

def _draw(rng, b, t, h=4, n=16, p=128):
    """q, k unit vectors a head (q scaled), v, the decays' logs and beta of
    a slice [B, T, H, .], as the mixer hands them to the kernels."""
    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.standard_normal((b, t, h, n))) * n ** -0.5
    k = unit(rng.standard_normal((b, t, h, n)))
    v = rng.standard_normal((b, t, h, p))
    g = -0.3 * np.exp(rng.standard_normal((b, t, h, n)))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((b, t, h))))
    return tuple(x.astype(np.float32) for x in (q, k, v, g, beta))


def _loop(s, q, k, v, g, beta):
    """The recurrence as written, a position at a time in float64: s
    [B, H, N, P] -> (o [B, T, H, P], the state behind the last position)."""
    s = s.astype(np.float64).copy()
    o = np.zeros(v.shape)
    for t in range(q.shape[1]):
        s = s * np.exp(g[:, t].astype(np.float64))[..., None]
        seen = np.einsum("bhnp,bhn->bhp", s, k[:, t])
        u = beta[:, t][..., None] * (v[:, t] - seen)
        s = s + k[:, t][..., None] * u[:, :, None, :]
        o[:, t] = np.einsum("bhnp,bhn->bhp", s, q[:, t])
    return o, s


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["chunked_xla", "kernel_interpreted"])
def test_kda_update_is_one_step_of_the_recurrence(use_kernel):
    """Rows that name slots out of order at layer 1 of 2: the slots are
    overwritten with the state behind the token, the other layer and the
    other slots stay; a row with g = 0 and beta = 0 leaves its slot as it
    was, bit for bit."""
    rng = np.random.default_rng(0)
    state = rng.standard_normal((2, 5, 16, 16, 128)).astype(np.float32)
    q, k, v, g, beta = (x[:, 0] for x in _draw(rng, 3, 1, h=16))
    g[2], beta[2] = 0.0, 0.0
    slots = np.asarray([3, 0, 1], np.int32)
    o, out = ssm.kda_update(jnp.asarray(state), q, k, v, g, beta, slots, 1,
                            use_kernel=use_kernel, interpret=True)
    want_o, want_s = _loop(state[1, slots], *(x[:, None] for x in (
        q, k, v, g, beta)))
    np.testing.assert_allclose(o, want_o[:, 0], atol=2e-6)
    np.testing.assert_allclose(np.asarray(out)[1, slots], want_s, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(out)[0], state[0])
    np.testing.assert_array_equal(np.asarray(out)[1, [1, 2, 4]],
                                  state[1, [1, 2, 4]])


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["chunked_xla", "kernel_interpreted"])
@pytest.mark.parametrize("chunk,t", [(1, 11), (3, 11), (16, 11), (32, 45),
                                     (64, 75)])
def test_kda_scan_is_the_recurrence(chunk, t, use_kernel):
    """A length that is no multiple of the chunk (the last chunk is padded
    with the identity; chunks of 32 and 64 are cut into sub-chunks of 16,
    whose pairs across two go through a product) over four rows: one
    continued from its slot, one `fresh` (from zeros, whatever its slot
    held), one whose valid tokens end at 4, and a fourth with no valid
    token at all, whose slot stays as it was."""
    rng = np.random.default_rng(chunk)
    state = rng.standard_normal((2, 6, 4, 16, 128)).astype(np.float32)
    q, k, v, g, beta = _draw(rng, 4, t)
    g[2, 4:], beta[2, 4:] = 0.0, 0.0        # as the mixer masks padding
    g[3], beta[3] = 0.0, 0.0
    slots = np.asarray([3, 0, 1, 5], np.int32)
    fresh = np.asarray([False, True, False, False])
    o, out = ssm.kda_scan(jnp.asarray(state), q, k, v, g, beta, slots,
                          fresh, 1, chunk=chunk, use_kernel=use_kernel,
                          interpret=True)
    start = np.where(fresh[:, None, None, None], 0.0, state[1, slots])
    want_o, want_s = _loop(start, q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, atol=5e-6)
    np.testing.assert_allclose(np.asarray(out)[1, slots], want_s, atol=5e-6)
    np.testing.assert_allclose(np.asarray(out)[1, 5], state[1, 5], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out)[0], state[0])
    # the state behind row 2's four tokens, not behind its padding
    _, at_4 = _loop(start[2:3], *(x[2:3, :4] for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(out)[1, 1], at_4[0], atol=5e-6)


def test_a_chunks_terms_never_divide_by_a_small_gamma():
    """Decays of e^-30 a step: Gamma falls under float32's least number
    inside a chunk, and the terms stay finite and right (the ratios are
    exponentials of differences that are <= 0)."""
    rng = np.random.default_rng(7)
    q, k, v, g, beta = _draw(rng, 1, 64)
    g = (g * 100.0).astype(np.float32)
    assert float(g.sum(1).min()) < -1500.0
    o, s = ssm.kda_sequence(q, k, v, g, beta, chunk=64)
    want_o, want_s = _loop(np.zeros((1, 4, 16, 128)), q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all()
    # (float32 sums of terms this far apart: a few more bits of noise)
    np.testing.assert_allclose(o, want_o, atol=1e-4)
    np.testing.assert_allclose(s, want_s, atol=1e-4)


# -- the cached forward -------------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 2, 5])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(chunk):
    """The cached forward by hand: chunks that split inside the four-tap
    window (1 and 2 rows: a chunk shorter than the tail it leaves; 5: the
    last one padded), then tokens one at a time, two lanes at different
    depths in slots that are not their rows; every position's logits
    against one forward pass of the reference.  The ONE latent pool has the
    two latent layers, the state part its two buffers over the four KDA
    layers; a slot nobody writes stays as it was, and a lane that starts at
    position 0 starts from zeros whatever its slot held."""
    cfg, params = NANO, _init()
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 512, n) for n in (14, 9)]
    cache = PagedKVCache.for_model(kimilinear, cfg, num_blocks=(32, 2),
                                   block_size=4, max_lanes=4, max_seq_len=64)
    pool, state, tails = cache.step_pools[0]
    assert cache.step_pools[1] is None and cache.latent
    assert (pool.shape[0], state.shape, tails.shape) == (
        2, (4, 5, 2, 128, 128), (4, 5, 3 * 768))
    assert cache.parts[-1].wire == ("state", "tail")
    dirty = [jnp.asarray(rng.standard_normal(x.shape), x.dtype)
             for x in (state, tails)]
    # row i is lane (2, 0)[i]; lane 0 prefills 10 of its 14 in chunks,
    # lane 1 joins a chunk later and prefills 6 of its 9 (so its last chunk
    # of 5 is padded)
    got, (pools, _), load = serving_script.serve(
        kimilinear, cfg, kimilinear.serving_params(params, cfg), cache, seqs,
        chunk, [2, 0], prefill=[10, 6], late=[0, 1], name_slots=True,
        pools=((pool, *dirty), None), load=jnp.zeros((16 + 2,), jnp.int32))
    for logits, seq in zip(got, seqs):
        _close(logits, ref.row_logits(params, seq))
    for left, was in zip(pools[1:], dirty):
        for slot in (1, 3, 4):           # nobody's: as they were
            np.testing.assert_array_equal(np.asarray(left)[:, slot],
                                          np.asarray(was)[:, slot])
        assert not np.array_equal(np.asarray(left)[:, 2],
                                  np.asarray(was)[:, 2])
    # every valid token's 4 assignments in each of the 5 expert layers
    assert int(load[:16].sum()) == 4 * 5 * (14 + 9)


ENGINE = dict(auto_start=False, max_lanes=4, block_size=4, num_blocks=(96, 4),
              max_seq_len=96, prefill_chunk=8, prefill_lanes=2,
              capture_logp=True)


def _run(eng, *handles):
    while eng.step():
        pass
    return [h.tokens() for h in handles]


def test_a_lane_that_adopts_a_snapshot_decodes_what_one_that_prefilled_does():
    """The second request of a head adopts the latent layers' blocks and
    the KDA layers' states and tails behind them and scans only its own
    problem: the tokens and their log-probs are those of an engine without
    a prefix cache, which prefilled from token 0, and the tokens the
    reference's greedy ones.  The counters are every state cache's and a
    latent cache's."""
    rng = np.random.default_rng(1)
    head = rng.integers(0, 512, 32).tolist()
    first, second = (head + rng.integers(0, 512, n).tolist() for n in (5, 7))
    eng = InferenceEngine("kimilinear", NANO, _init(), **ENGINE)
    _run(eng, eng.submit(first, 4))
    st = eng.stats()
    assert st["ssm"]["snapshots_taken"] == 1 and st["prefix_hit_tokens"] == 0
    handle = eng.submit(second, 16)
    out, = _run(eng, handle)
    st = eng.stats()
    assert st["prefix_hit_tokens"] == 32
    assert st["ssm"]["snapshots_adopted"] == 1
    assert st["ssm"]["state_buffers"] == 2 and st["ssm"]["state_layers"] == 4
    assert st["ssm"]["state_bytes"] == 4 * 5 * (
        4 * 2 * 128 * 128 + 4 * 3 * 768)
    # the tokens the scans and the updates stepped over: both prompts' own
    # less the adopted head, and every decoded token but each request's
    # first (a chunk's last row samples it)
    assert st["ssm"]["tokens_scanned"] == len(first) + 7
    assert st["ssm"]["tokens_updated"] == 4 + 16 - 2
    assert "conv" not in st and "paged" not in st
    assert st["latent"]["decode_steps"] >= 15
    assert st["layers"] == {"kv": 2, "window": 0, "state": 4, "experts": 5}
    plain = InferenceEngine("kimilinear", NANO, _init(), prefix_cache=False,
                            **ENGINE)
    cold = plain.submit(second, 16)
    assert _run(plain, cold) == [out]
    np.testing.assert_allclose(handle.logps, cold.logps, atol=REL * 5)
    want = np.asarray(jnp.argmax(ref.row_logits(
        _init(), np.asarray(second + out)), -1))
    assert out == want[len(second) - 1:len(second) + 15].tolist()


# -- the share, and what the family states ------------------------------------

def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """Experts 0-3, 4-7, 8-11 and 12-15 on four chips, the router whole on
    each: the four shares' expert layers, the shared expert counted once,
    add up to the uncut reference layer, in the program and in the
    reference alike."""
    params = _init()
    p = {k: v[1] for k, v in params["kdas"].items()}
    u = jax.random.normal(jax.random.key(3), (37, 64))
    s = dict(ref.sizes_of(params))
    whole = ref.experts(u, p, s)
    shared = ref.swiglu(u, p["ws_gate"], p["ws_up"], p["ws_down"])

    def share(lo, reference):
        cut = {**p, **{k: p[k][lo:lo + 4]
                       for k in ("w_gate", "w_up", "w_down")}}
        if reference:
            return ref.experts(u, cut, dict(s, experts_offset=lo))
        cfg = dataclasses.replace(NANO, n_experts_held=4, experts_offset=lo)
        y, _, load = decoder.shared_moe_ffn(
            u[None], {**{k: v[None] if k in decoder.SHARED_EXPERTS.whole
                         else v for k, v in cut.items()}, "layer": 0}, cfg)
        assert load.shape == (4,)
        return y[0]

    for reference in (True, False):
        parts = [share(lo, reference) for lo in (0, 4, 8, 12)]
        np.testing.assert_allclose(sum(parts) - 3 * shared, whole,
                                   atol=REL * float(jnp.abs(whole).max()))
        assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-2


def test_the_share_of_the_nano_model_is_the_references_share():
    params = _init(SHARE)
    assert params["kdas"]["w_up"].shape == (3, 4, 64, 24)
    tokens = jax.random.randint(jax.random.key(4), (1, 30), 0, 512)
    _close(serving_script.forward(kimilinear, params, tokens, SHARE)[0],
           ref.row_logits(params, tokens[0], experts_offset=4))


@pytest.mark.parametrize("what,over", [
    ("delta", dict(delta=False)), ("decay_a_head", dict(decay="head")),
    ("beta_one", dict(beta_one=True)), ("l2_norms", dict(l2=False)),
    ("k_pe_rotated", dict(rotate=True))])
def test_what_the_family_states_moves_the_logits(what, over):
    """The delta correction, a decay a key CHANNEL (not a head's mean),
    beta, the two L2 norms and an unrotated k_pe are where the reference
    has them: the program agrees with the reference as published (the
    first test) and each left out of the reference parts the two."""
    params = _init()
    tokens = jax.random.randint(jax.random.key(2), (1, 24), 0, 512)
    got = serving_script.forward(kimilinear, params, tokens, NANO)[0]
    moved = ref.row_logits(params, tokens[0], **over)
    assert float(jnp.abs(moved - got).max()) > 100 * REL * float(
        jnp.abs(got).max())


def test_a_training_step_is_refused_for_what_is_still_true():
    """Refused because its Kimi Delta Attention layers are a mixer whose chunked
    scan has no backward pass, and its latent attention has no train path: not
    for its experts, which train since the grouped multiply has its backward."""
    with pytest.raises(NotImplementedError, match="no train path") as refusal:
        kimilinear.loss_fn(_init(), {"tokens": jnp.zeros((1, 8), jnp.int32)},
                           NANO)
    assert "expert" not in str(refusal.value)


def test_the_spec_names_a_mixer_and_a_feed_forward_a_run():
    spec = kimilinear.spec(NANO)
    assert spec.rope_theta is None and not spec.pos_table
    assert not spec.tied_head
    kinds = [(r.blocks, r.n_layers, r.first, r.offset, r.pools,
              r.attn is not None, r.mixer is not None, r.ffn)
             for r in spec.runs]
    assert kinds == [
        ("dense_kdas", 1, 0, 0, (1, 2), False, True, decoder.SWIGLU),
        ("kdas", 2, 1, 0, (1, 2), False, True, decoder.SHARED_EXPERTS),
        ("mlas", 1, 0, 0, (0,), True, False, decoder.SHARED_EXPERTS),
        ("kdas", 1, 3, 2, (1, 2), False, True, decoder.SHARED_EXPERTS),
        ("mlas", 1, 1, 1, (0,), True, False, decoder.SHARED_EXPERTS)]
    sizes = spec.runs[2].sizes
    assert sizes.q_lora_rank == 0 and sizes.rope_theta is None
    assert sizes.attn_scale == 24 ** -0.5
    assert decoder.layer_counts(spec, NANO) == {
        "kv": 2, "window": 0, "state": 4, "experts": 5}
    assert decoder.KDA.state(NANO) == decoder.StateRows(4, 768, 2, 128, 128)
    published = kimilinear.KimiLinearConfig()
    assert published.kda_layers == tuple(
        i for i in range(1, 28) if i % 4 and i != 27)
    assert decoder.layer_counts(kimilinear.spec(published), published) == {
        "kv": 7, "window": 0, "state": 20, "experts": 26}
    assert decoder.KDA.state(published) == decoder.StateRows(
        4, 12288, 32, 128, 128)
    assert 47.9e9 < kimilinear.num_params(published) < 49.5e9
    with pytest.raises(ValueError, match="exactly one"):
        dataclasses.replace(NANO, kda_layers=(1, 2, 3))


def test_the_cut_is_served_from_one_latent_pool_and_a_state_part():
    """`for_model` at the benchmark's configuration and engine (shapes
    alone: nothing of the 3.2 GB is made): ONE pool of 576-wide rows over
    the 2 latent layers, the float32 states and the bf16 tails over the 6
    KDA layers, 32 snapshot slots of 13 MB."""
    from benchmark import manifest
    m = manifest.load()
    file = m.load_config("kimi-linear-48b-a3b")
    cfg = manifest.model_config(file, None)
    eng = m.load_traffic("decode_kda_latent_reasoning")["engine"]
    seen = {}

    def make():
        cache = PagedKVCache.for_model(
            kimilinear, cfg, num_blocks=eng["num_blocks"],
            block_size=eng["block_size"], max_lanes=eng["max_lanes"],
            max_seq_len=eng["max_seq_len"], ahead=2 * eng["prefill_chunk"])
        seen.update(kind=cache.kind, latent=cache.latent,
                    wire=cache.parts[-1].wire,
                    snaps=[(s.shape, s.dtype) for s in
                           cache.parts[-1].snap_buffers])
        return cache.step_pools

    pools, none = jax.eval_shape(make)
    assert none is None and seen["kind"] == "state" and seen["latent"]
    assert [(p.shape, p.dtype) for p in pools] == [
        ((2, 3840, 128, 640), jnp.bfloat16),
        ((6, 129, 32, 128, 128), jnp.float32),
        ((6, 129, 3 * 12288), jnp.bfloat16)]
    assert seen["wire"] == ("state", "tail")
    assert seen["snaps"] == [((6, 32, 32, 128, 128), jnp.float32),
                             ((6, 32, 3 * 12288), jnp.bfloat16)]
    spec = kimilinear.spec(cfg)
    assert decoder.layer_counts(spec, cfg) == {
        "kv": 2, "window": 0, "state": 6, "experts": 7}
    assert [(r.blocks, r.n_layers) for r in spec.runs] == [
        ("dense_kdas", 1), ("kdas", 2), ("mlas", 1), ("kdas", 3),
        ("mlas", 1)]
