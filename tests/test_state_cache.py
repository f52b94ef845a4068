"""Falcon-H1 through the engine and its cache of state that is not rows
(inference/kv_cache.py "state"): a slot a lane overwritten by every step,
snapshots under the prefix index, a match served only where K/V blocks AND
a snapshot stand, lanes that are not stepped, the wire format, and what is
refused.  The state part holds the buffers its mixer states: two under
Falcon-H1's and Nemotron-H's Mamba-2 mixers (a recurrent state and the
convolution's tail), ONE under LFM2's gated short convolution (the tail
alone), out of one code path: the cases `two_buffers` and `one_buffer`.
Nano size on the CPU; the models' own tests are tests/test_falconh1.py and
tests/test_lfm2.py, the cell's rehearsal
benchmark/tests/test_rehearse_falconh1.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import falconh1 as ref
from benchmark.reference import kimilinear as kimi_ref
from benchmark.reference import lfm2 as lfm2_ref
from ray_tpu.inference import InferenceEngine, PagedKVCache
from ray_tpu.models import falconh1, kimilinear, lfm2
from ray_tpu.serve.kv_tier.codec import KVBlockCodec

NANO = falconh1.CONFIGS["falconh1-nano"]
ENGINE = dict(auto_start=False, max_lanes=4, block_size=4, num_blocks=(96, 4),
              max_seq_len=96, prefill_chunk=8)


@functools.lru_cache(maxsize=None)
def _init(seed=0):
    return jax.jit(falconh1.init_params, static_argnums=0)(
        NANO, jax.random.key(seed))


@functools.lru_cache(maxsize=None)
def _lfm2_init():
    return jax.jit(lfm2.init_params, static_argnums=0)(
        lfm2.CONFIGS["lfm2-nano"], jax.random.key(0))


@functools.lru_cache(maxsize=None)
def _kimi_init():
    return jax.jit(kimilinear.init_params, static_argnums=0)(
        kimilinear.CONFIGS["kimilinear-nano"], jax.random.key(0))


# buffers of the state part -> family, config, parameters, reference
FAMILIES = {
    "two_buffers": ("falconh1", NANO, _init, ref),
    "one_buffer": ("lfm2", lfm2.CONFIGS["lfm2-nano"], _lfm2_init, lfm2_ref),
    # two buffers (Kimi Delta Attention's state and its tail) behind ONE
    # latent pool: the chain snapshots are keyed by is of latent rows
    "latent_pool": ("kimilinear", kimilinear.CONFIGS["kimilinear-nano"],
                    _kimi_init, kimi_ref),
}
BUFFERS = list(FAMILIES)
N_BUFFERS = {"two_buffers": 2, "one_buffer": 1, "latent_pool": 2}


def _engine(buffers="two_buffers", **kw):
    family, cfg, init, _ = FAMILIES[buffers]
    return InferenceEngine(family, cfg, init(), **{**ENGINE, **kw})


def _run(eng, *handles):
    while eng.step():
        pass
    return [h.tokens() for h in handles]


def _greedy(prompt, n):
    """The reference's own greedy continuation of `prompt`."""
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(ref.row_logits(_init(),
                                                 np.asarray(seq))[-1])))
    return seq[len(prompt):]


def _served_is_the_references(prompt, out, buffers="two_buffers"):
    _, _, init, reference = FAMILIES[buffers]
    want = np.asarray(jnp.argmax(reference.row_logits(
        init(), np.asarray(prompt + out)), -1))
    return out == want[len(prompt) - 1:len(prompt) + len(out) - 1].tolist()


def _prompts(seed, head_len, tails):
    rng = np.random.default_rng(seed)
    head = rng.integers(0, 512, head_len).tolist()
    return [head + rng.integers(0, 512, n).tolist() for n in tails]


@pytest.mark.parametrize("prefill_lanes", [None, 2, 1],
                         ids=["all_lanes", "two_rows", "one_row"])
def test_the_engine_serves_the_references_greedy_tokens(prefill_lanes):
    """Five requests of unlike lengths over four lanes: lanes prefill in
    chunks while others decode (a lane that is not stepped keeps its state:
    a decode step passes over the prefilling lanes, a prefill program over
    the decoding ones, and with fewer rows than prefilling lanes some wait
    whole steps), the fifth takes a lane another has left."""
    eng = _engine(prefill_lanes=prefill_lanes, prefix_cache=False)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (37, 9, 21, 30, 13)]
    outs = _run(eng, *(eng.submit(p, n) for p, n in zip(
        prompts, (12, 30, 18, 9, 14))))
    for prompt, out in zip(prompts, outs):
        assert _served_is_the_references(prompt, out)
    ssm = eng.stats()["ssm"]
    assert ssm["tokens_scanned"] == sum(map(len, prompts))
    assert ssm["tokens_updated"] == 12 + 30 + 18 + 9 + 14 - 5
    assert ssm["state_slots_live"] == 0 and ssm["state_slots"] == 4
    assert ssm["snapshots_taken"] == ssm["snapshot_slots"] == 0


@pytest.mark.parametrize("buffers", BUFFERS)
def test_a_lane_adopted_from_a_snapshot_equals_one_prefilled_from_token_0(
        buffers):
    """The second request of a head adopts its blocks and the snapshot
    behind them (state and tail, or the tail alone) and scans only its own
    turn; what it serves is what an engine without a prefix cache serves
    for the same prompt, token for token, and the reference's."""
    first, second = _prompts(1, 32, (5, 7))
    eng = _engine(buffers, prefill_lanes=2)
    _run(eng, eng.submit(first, 4))
    st = eng.stats()
    assert st["ssm"]["snapshots_taken"] == 1 and st["prefix_hit_tokens"] == 0
    out, = _run(eng, eng.submit(second, 16))
    st = eng.stats()
    assert st["prefix_hit_tokens"] == 32 and st["ssm"]["snapshots_adopted"] == 1
    part = eng.cache.parts[0]
    assert st["ssm"]["state_buffers"] == len(part.wire) == len(
        eng.cache.buffers) == N_BUFFERS[buffers]
    assert eng.cache.latent == (buffers == "latent_pool")
    assert st["ssm"]["state_bytes"] == sum(b.nbytes for b in part.buffers)
    assert st["ssm"]["snapshot_bytes"] == sum(
        b.nbytes for b in part.snap_buffers)
    if buffers != "one_buffer":
        assert st["ssm"]["tokens_scanned"] == len(first) + 7
    else:
        assert st["conv"]["rows_chunk"] == len(first) + 7
    plain = _engine(buffers, prefix_cache=False)
    cold, = _run(plain, plain.submit(second, 16))
    assert out == cold
    assert _served_is_the_references(second, out, buffers)


def test_a_snapshot_stands_where_a_cold_prompts_last_whole_chunk_ends():
    """A lane that matched nothing leaves one snapshot, behind the last
    chunk that leaves a chunk to prefill: 40 + 5 tokens in chunks of 8 from
    0 -> at 40.  A lane that adopts those 40 and adds a turn of 19 (two
    chunks and more) takes NONE: what it prefills is its own turn, and a
    snapshot at 56 would serve nobody and cost a slot."""
    first, second = _prompts(2, 40, (5, 19))
    eng = _engine(prefill_lanes=2)
    _run(eng, eng.submit(first, 2))
    cache = eng.cache
    assert cache.match_len(first) == 40 and cache.match_len(second) == 40
    out, = _run(eng, eng.submit(second, 6))
    assert cache.match_len(second) == 40
    assert cache.match_len(second[:50] + [0] * 9) == 40
    st = eng.stats()["ssm"]
    assert st["snapshots_taken"] == 1 and st["snapshots_adopted"] == 1
    assert st["snapshots_evicted"] == 0
    assert _served_is_the_references(second, out)


def test_a_snapshot_is_taken_where_matched_blocks_had_none():
    """A head of 36 tokens (off the chunks' grid of 8) under turns longer
    than a chunk.  The first request matched nothing and leaves its one
    snapshot at 48, behind its own turn.  The second matches the head's 9
    blocks and no snapshot: it is counted, prefills from token 0, CUTS a
    chunk at 36 (where the index saw the shared head end) and leaves a
    snapshot there.  The third adopts blocks and snapshot and takes none."""
    first, second, third = _prompts(6, 36, (13, 10, 11))
    eng = _engine(prefill_lanes=2)
    _run(eng, eng.submit(first, 2))
    cache = eng.cache
    assert cache.match_len(first) == 48 and cache.match_len(second) == 0
    steps = eng.stats()["prefill"]["steps"]
    out, = _run(eng, eng.submit(second, 5))
    st = eng.stats()
    assert st["ssm"]["snapshot_misses"] == 1
    assert st["ssm"]["snapshots_taken"] == 2
    # 8, 16, 24, 32, |36, 44, 46: one program more than six chunks of 8
    assert st["prefill"]["steps"] - steps == 7
    assert _served_is_the_references(second, out)
    assert cache.match_len(second) == cache.match_len(third) == 36
    out, = _run(eng, eng.submit(third, 5))
    st = eng.stats()
    assert st["ssm"]["snapshot_misses"] == 1
    assert st["ssm"]["snapshots_adopted"] == 1
    assert st["ssm"]["snapshots_taken"] == 2
    assert st["prefix_hit_tokens"] == 36
    assert _served_is_the_references(third, out)


@pytest.mark.parametrize("buffers", BUFFERS)
def test_a_match_is_refused_where_blocks_exist_and_the_snapshot_was_evicted(
        buffers):
    """Two snapshot slots, three heads: the first head's snapshot is the
    least recently used and goes; its K/V blocks are still indexed, and the
    index serves nothing of them: the request prefills from token 0 and is
    counted."""
    eng = _engine(buffers, num_blocks=(96, 2), prefill_lanes=2)
    heads = [_prompts(10 + i, 32, (5,))[0] for i in range(3)]
    for prompt in heads:
        _run(eng, eng.submit(prompt, 2))
    cache = eng.cache
    assert [cache.match_len(p) for p in heads] == [0, 32, 32]
    assert len(cache.match_prefix(heads[0])) == 0 and cache.parts[0].beyond == 9
    assert len(cache.index) and eng.stats()["ssm"]["snapshots_evicted"] == 1
    again = heads[0][:32] + [1, 2, 3]
    out, = _run(eng, eng.submit(again, 8))
    st = eng.stats()["ssm"]
    assert st["snapshot_misses"] == 1 and st["snapshots_adopted"] == 0
    assert _served_is_the_references(again, out, buffers)
    assert cache.match_len(again) == 32         # taken anew behind its head


def test_slots_and_snapshot_slots_are_all_free_after_the_lanes_go():
    eng = _engine(prefill_lanes=2)
    prompts = _prompts(3, 24, (5, 9, 3, 7, 11))
    _run(eng, *(eng.submit(p, 6) for p in prompts))
    cache, st = eng.cache, eng.stats()
    assert st["active"] == 0 and st["ssm"]["state_slots_live"] == 0
    assert cache.allocator.num_free == cache.allocator.num_blocks
    snaps = cache.parts[0].index
    assert snaps.allocator.num_free == snaps.allocator.num_blocks
    assert all(snaps.allocator.refcount(s) == 0 for s, _key in snaps.items())
    # a snapshot goes with the block it stands behind
    for block, _key in list(cache.index.items()):
        cache.allocator.uncache(block)
        cache.index.evicted(block)
    assert not len(snaps) and not list(snaps.items())
    assert snaps.allocator.num_unused == snaps.allocator.num_blocks


def test_what_a_state_cannot_do_is_refused():
    with pytest.raises(NotImplementedError, match="rolled back"):
        _engine(spec_k=2)
    cache = _engine().cache
    cache.alloc_lane(0, 9)
    with pytest.raises(NotImplementedError, match="rolled back"):
        cache.truncate_lane(0, 4)
    with pytest.raises(NotImplementedError, match="snapshots"):
        cache.attach_tier(object())


@pytest.mark.parametrize("buffers", BUFFERS)
def test_the_wire_format_carries_the_snapshot_and_a_cache_installs_its_own(
        buffers):
    """The payload's `more` holds exactly the buffers the part states,
    under their names; a cache that states others installs none of it."""
    first, second = _prompts(4, 32, (5, 6))
    eng = _engine(buffers, prefill_lanes=2)
    _run(eng, eng.submit(first, 2))
    payload = KVBlockCodec.decode(KVBlockCodec.encode(
        eng.export_prefix(second)))
    assert payload["kind"] == "state" and len(payload["chain"]) == 8
    # a latent frame (one pool's rows, no V) or a K and a V frame, and the
    # state frame of one lane beside it
    assert (payload["v_pool"] is None) == (buffers == "latent_pool")
    assert {k: v.shape for k, v in payload["more"].items()} == {
        "two_buffers": {"state": (3, 4, 16, 8), "tail": (3, 3 * 96)},
        "one_buffer": {"tail": (3, 2 * 64)},
        "latent_pool": {"state": (4, 2, 128, 128), "tail": (4, 3 * 768)},
    }[buffers]
    other = _engine(buffers, prefill_lanes=2)
    assert other.import_prefix(payload) == 8
    assert other.import_prefix(payload) == 0            # idempotent
    out, = _run(other, other.submit(second, 12))
    assert other.stats()["prefix_hit_tokens"] == 32
    assert _served_is_the_references(second, out, buffers)
    # nothing of it without its snapshot, nothing into a cache of K/V alone
    bare = dict(payload, more={})
    assert _engine(buffers).import_prefix(bare) == 0
    assert _engine(buffers, num_blocks=(96, 0)).import_prefix(payload) == 0
    # nor into a cache whose part states other buffers: one more, one fewer
    tail = payload["more"]["tail"]
    for more in ({"tail": tail, "state": np.zeros((3, 4, 16, 8), np.float32),
                  "extra_state": tail}, {"state": tail},
                 {"tail": tail[:, :-1]}):
        assert set(more) != set(payload["more"]) or more["tail"].shape \
            != tail.shape
        assert _engine(buffers).import_prefix(dict(payload, more=more)) == 0
    from ray_tpu.models import llama
    plain = PagedKVCache.for_model(
        llama, llama.CONFIGS["llama-tiny"], num_blocks=16, block_size=4,
        max_lanes=2)
    assert plain.install_prefix(payload) == 0
    # nor into a state cache whose rows are of the other kind (latent rows
    # into K and V pools, K and V rows into a latent pool)
    foreign = "two_buffers" if buffers == "latent_pool" else "latent_pool"
    assert _engine(foreign, prefill_lanes=2).import_prefix(payload) == 0


def test_compiled_steps_count_the_state_buffers_copies():
    """What `compiled_steps()` reports of a state cache's programs.  (On
    the CPU the update is XLA's scatter into a buffer it cannot donate;
    that it is 0 compiled for the chip is tests/test_tpu_aot.py's.)"""
    eng = _engine(prefill_lanes=2)
    _run(eng, eng.submit(_prompts(5, 16, (3,))[0], 3))
    steps = eng.compiled_steps()
    assert set(steps) == {"t8_pair1", "t8_pair2", "t1"}
    assert all(isinstance(s["state_copies"], int) for s in steps.values())


# -- a state part with a layer count of its own -------------------------------
# Nemotron-H's nano model: three mixer layers, one attention layer and three
# expert layers, a layer ONE part (tests/test_nemotronh.py has the model).

from benchmark.reference import nemotronh as nemo_ref  # noqa: E402
from ray_tpu.models import nemotronh  # noqa: E402

NEMO = nemotronh.CONFIGS["nemotronh-nano"]


@functools.lru_cache(maxsize=None)
def _nemo_init():
    return jax.jit(nemotronh.init_params, static_argnums=0)(
        NEMO, jax.random.key(0))


def _nemo_engine(**kw):
    return InferenceEngine("nemotronh", NEMO, _nemo_init(),
                           **{**ENGINE, **kw})


def _nemo_served_is_the_references(prompt, out):
    want = np.asarray(jnp.argmax(nemo_ref.row_logits(
        _nemo_init(), np.asarray(prompt + out)), -1))
    return out == want[len(prompt) - 1:len(prompt) + len(out) - 1].tolist()


@pytest.mark.parametrize("prefill_lanes", [None, 2],
                         ids=["all_lanes", "two_rows"])
def test_one_part_layers_serve_the_references_greedy_tokens(prefill_lanes):
    """Five requests over four lanes through layers that are a mixer, or
    an attention, or experts: the state buffers have the three mixer
    layers, the K and V pools the one attention layer, and what the step
    counts goes by each kind's own layers."""
    eng = _nemo_engine(prefill_lanes=prefill_lanes, prefix_cache=False)
    state, tail = eng.cache.buffers
    assert state.shape == (3, 5, 2, 16, 128) and tail.shape[0] == 3
    assert eng.cache.pool_shape[0] == 1
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (37, 9, 21, 30, 13)]
    news = (12, 30, 18, 9, 14)
    outs = _run(eng, *(eng.submit(p, n) for p, n in zip(prompts, news)))
    for prompt, out in zip(prompts, outs):
        assert _nemo_served_is_the_references(prompt, out)
    st = eng.stats()
    assert st["layers"] == {"kv": 1, "window": 0, "state": 3, "experts": 3}
    fed = sum(map(len, prompts)) + sum(news) - 5
    assert st["ssm"]["tokens_scanned"] + st["ssm"]["tokens_updated"] == fed
    assert st["ssm"]["state_layers"] == 3
    assert st["ssm"]["state_bytes"] == state.nbytes + tail.nbytes
    # 4 choices a token in each of the 3 expert layers, all 16 held
    assert st["moe"]["assignments"] == 4 * 3 * fed
    assert sum(st["moe"]["expert_load"]) == 4 * 3 * fed
    assert st["moe"]["experts_hit"] <= 16 * st["moe"]["layer_steps"]


def test_a_share_of_the_experts_counts_what_it_holds():
    share = nemotronh.CONFIGS["nemotronh-nano-share"]
    params = jax.jit(nemotronh.init_params, static_argnums=0)(
        share, jax.random.key(0))
    eng = InferenceEngine("nemotronh", share, params, **ENGINE)
    prompt = np.random.default_rng(1).integers(0, 512, 19).tolist()
    out, = _run(eng, eng.submit(prompt, 6))
    want = np.asarray(jnp.argmax(nemo_ref.row_logits(
        params, np.asarray(prompt + out), experts_offset=8), -1))
    assert out == want[18:24].tolist()
    moe = eng.stats()["moe"]
    assert len(moe["expert_load"]) == 8
    assert moe["assignments"] == 4 * 3 * (19 + 5)
    assert 0 < moe["assignments_held"] < moe["assignments"]


def test_one_part_layers_adopt_a_snapshot_behind_shared_blocks():
    """The second request of a head adopts the attention layer's blocks and
    the three mixer layers' snapshot and scans only its own turn: what an
    engine without a prefix cache serves, and the reference's."""
    first, second = _prompts(1, 32, (5, 7))
    eng = _nemo_engine(prefill_lanes=2)
    _run(eng, eng.submit(first, 4))
    assert eng.stats()["ssm"]["snapshots_taken"] == 1
    out, = _run(eng, eng.submit(second, 16))
    st = eng.stats()
    assert st["prefix_hit_tokens"] == 32 and st["ssm"]["snapshots_adopted"] == 1
    assert st["ssm"]["tokens_scanned"] == len(first) + 7
    plain = _nemo_engine(prefix_cache=False)
    cold, = _run(plain, plain.submit(second, 16))
    assert out == cold and _nemo_served_is_the_references(second, out)
    payload = KVBlockCodec.decode(KVBlockCodec.encode(
        eng.export_prefix(second)))
    assert payload["kind"] == "state"
    assert payload["more"]["state"].shape == (3, 2, 16, 128)
    # a cache whose state part has other layers installs none of it
    assert _engine(prefill_lanes=2).import_prefix(payload) == 0
    steps = eng.compiled_steps()
    assert set(steps) == {"t8_pair1", "t8_pair2", "t1"}
    assert all(isinstance(s["state_copies"], int) and "temp_bytes" in s
               and "pool_copies" in s and "weight_bytes_copied" in s
               for s in steps.values())


def test_a_stack_with_no_attention_at_all_is_still_refused():
    """A lane's snapshots are keyed by its chain of K/V blocks: a pure
    state-space stack has none to hang its state behind."""
    import dataclasses
    pure = dataclasses.replace(NEMO, n_layers=2, pattern="ME")
    with pytest.raises(NotImplementedError, match="at least one layer"):
        PagedKVCache.for_model(nemotronh, pure, num_blocks=(8, 2),
                               block_size=4, max_lanes=2)
