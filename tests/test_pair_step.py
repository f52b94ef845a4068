"""An iteration that admits dispatches ONE program (PR 53): the prefilling
lanes' compact [prefill_lanes, T] rows ride the decoding lanes' [max_lanes,
1] step, every row-wise product of a layer once over the rows of both
(`decoder.forward_cached`'s `chunk`), what is a lane's own a population at a
time on the same pools.  For every kind of cache the pair's step must leave
what the two programs it replaces leave, run one after the other (the
decoding lanes' first: a drafting engine's order, which an engine told not
to pair keeps): the pools, the state, every lane's last token on the
device, the served tokens and their log-probs."""

import importlib

import jax
import numpy as np
import pytest

from ray_tpu.inference import InferenceEngine
from ray_tpu.inference import engine as engine_mod

LAYERED = dict(max_lanes=4, block_size=4, num_blocks=(96, 48),
               max_seq_len=96, prefill_chunk=8)
STATE = dict(max_lanes=4, block_size=4, num_blocks=(96, 4), max_seq_len=96,
             prefill_chunk=8)
# family, config, the engine's shape, (first prompt, its tokens), the
# prompts admitted beside it while it decodes.  What each is there for:
CASES = {
    # K and V rows, `prefill_lanes` from the rows rule (every lane here)
    "paged": ("gpt", "nano", dict(
        max_lanes=4, block_size=8, prefill_chunk=8), 5, (13, 20, 30)),
    # the same under dropless experts: an expert's rows are another set
    "paged_experts": ("llama", "olmoe-nano", dict(
        max_lanes=4, block_size=8, prefill_chunk=8), 5, (13, 20, 30)),
    # latent rows, a share of the experts held
    "latent": ("axk1", "axk1-nano-share", dict(
        max_lanes=4, block_size=8, prefill_chunk=8), 5, (13, 20, 30)),
    # EVA (window 32, chunk 4): windows close inside the prompts' chunks
    # and under the decoding lane, the compaction program in between;
    # named lanes: the quarter-T form and the one-row width
    "windowed": ("evabyte", "evabyte-nano", dict(
        max_lanes=3, block_size=8, prefill_chunk=16, prefill_lanes=2,
        num_blocks=64), 27, (70, 41, 33)),
    # indexed and window latent layers over two tables
    "layered": ("dots3", "dots3-nano", LAYERED, 5, (13, 37, 30)),
    # a state beside K/V rows: a snapshot is due behind the chunk that
    # ends at 16 of 20, and the second prompt adopts it
    "state": ("falconh1", "falconh1-nano", STATE, 5, (20, 13, 30)),
    # one-part layers: state and K/V rows in different layers
    "state_one_part": ("nemotronh", "nemotronh-nano", STATE, 5,
                       (20, 13, 30)),
    # a conv mixer in the attention's place over experts: the state part
    # is the tails alone, one buffer
    "state_tail_only": ("lfm2", "lfm2-nano", STATE, 5, (20, 13, 30)),
    # a recurrent state and its tail behind ONE latent pool, a share of the
    # experts held: the update and the scan in one program
    "state_latent_pool": ("kimilinear", "kimilinear-nano-share", STATE, 5,
                          (20, 13, 30)),
    # window and full layers over K and V heads, two pairs of pools
    "layered_kv": ("afmoe", "afmoe-nano", LAYERED, 5, (13, 37, 30)),
}
# The pair multiplies a row beside other rows than the two programs did.  On
# the CPU that is the same products in the same order a row in most kinds,
# whose float32 pools, tokens and log-probs come out bit for bit; where a
# product's blocking goes by its row count (the indexed layers' and the
# mixer's wide projections) the sums differ in their last bits.
TOL = {"layered": dict(rtol=1e-3, atol=1e-5),
       "state": dict(rtol=1e-3, atol=1e-5)}
SAMPLED = ("paged", "state")


def _engine(case, pairs):
    family, cfg, kw, *_ = CASES[case]
    cfg = importlib.import_module(f"ray_tpu.models.{family}").CONFIGS[cfg]
    eng = InferenceEngine(family, cfg, auto_start=False, seed=0,
                          capture_logp=True, **kw)
    eng._pairs = pairs
    return eng


def _make_programs(eng, sampled):
    """Every program the engine can need, made by steps nobody is in (as
    `_warm_widths` makes a sibling width): a program still to be made waits
    for the step in flight to land, and the two engines, whose programs are
    not the same set, would fall an iteration apart where one of them has
    one to make."""
    lanes, chunk = eng.max_lanes, eng.prefill_chunk
    widths = {1, eng.prefill_lanes} if eng._widths else {eng.prefill_lanes}
    for sample in (False, True) if sampled else (False,):
        buffer, _, _ = engine_mod._lane_views(lanes, 1, False, lanes)
        eng._run_step(eng._upload((1, sample, buffer, None, None)))
        for t in {chunk, chunk // 4} if eng._widths else {chunk}:
            for n in widths:
                if eng._pairs:
                    buffer, _, (_, _, rows) = engine_mod._pair_views(
                        lanes, n, t)
                else:
                    buffer, _, rows = engine_mod._lane_views(
                        n, t, True, lanes)
                eng._run_step(eng._upload((t, sample, buffer, None, rows)))
    del eng._to_warm[:]


def _left(eng):
    """What a step leaves on the device."""
    return [np.asarray(x) for x in jax.tree.leaves(
        (eng.cache.step_pools, eng._last_tok))]


@pytest.mark.parametrize("case", list(CASES))
def test_the_pair_leaves_what_the_two_programs_leave(case):
    *_, first, beside = CASES[case]
    one, two = _engine(case, True), _engine(case, False)
    # (a sampled request beside greedy ones in two kinds)
    sampled = case in SAMPLED
    _make_programs(one, sampled), _make_programs(two, sampled)
    vocab = one.config.vocab_size
    rng = np.random.default_rng(5)
    head = rng.integers(0, vocab, 16).tolist()
    prompts = [rng.integers(0, vocab, first).tolist()] + [
        head[:min(16, n - 1)] + rng.integers(0, vocab, n - min(16, n - 1))
        .tolist() for n in beside]
    handles = []

    def both(fn):
        handles.append([fn(eng) for eng in (one, two)])

    def step():
        more = [eng.step() for eng in (one, two)]
        assert more[0] == more[1]
        for a, b in zip(_left(one), _left(two)):
            if case in TOL:
                np.testing.assert_allclose(a, b, **TOL[case])
            else:
                np.testing.assert_array_equal(a, b)
        return more[0]

    both(lambda eng: eng.submit(prompts[0], 16))
    for _ in range(4):
        step()
    both(lambda eng: eng.submit(prompts[1], 5))
    both(lambda eng: eng.submit(prompts[2], 4, seed=3,
                                temperature=0.7 if sampled else 0.0))
    for _ in range(3):
        step()
    for p in prompts[3:]:
        both(lambda eng: eng.submit(p, 3))
    while step():
        pass
    for a, b in handles:
        assert a.tokens() == b.tokens()
        np.testing.assert_allclose(a.logps, b.logps, **TOL.get(
            case, dict(rtol=0, atol=0)))
    # one program an iteration against two where both populations stood
    ran, apart = one.stats()["programs"], two.stats()["programs"]
    assert ran["programs"] == ran["iterations"] == apart["iterations"]
    assert ran["mixed"] == one.stats()["prefill"]["steps"] > 0
    assert apart["programs"] > apart["iterations"] and not apart["mixed"]
    assert ran["decode_rows"] == ran["mixed"] * one.max_lanes
    assert ran["chunk_rows"] == one.stats()["prefill"]["rows"]


def test_an_iteration_with_both_populations_dispatches_one_program():
    eng = _engine("paged", True)
    calls = []
    run = eng._run_step
    eng._run_step = lambda batch, spec=False: (
        calls.append((batch[0], batch[3] is not None)), run(batch, spec))[1]
    a = eng.submit(list(range(1, 6)), 12)
    for _ in range(4):
        eng.step()
    # alone it prefills in the pair's program (its decode rows masked); a
    # program still to be made waits for the step in flight to land
    assert calls == [(8, True), (1, False), (1, False)]
    del calls[:]
    b = eng.submit(list(range(7, 27)), 4)
    eng.step()
    # one lane decodes, one prefills: ONE program, the pair's
    assert calls == [(8, True)]
    rec = eng.stats()
    assert rec["programs"] == {
        "iterations": 4, "programs": 4, "mixed": 2,
        "decode_rows": 2 * eng.max_lanes,
        "chunk_rows": 2 * eng.prefill_lanes * 8}
    assert rec["prefill"]["steps"] == 2
    assert rec["prefill"]["rows"] == 2 * eng.prefill_lanes * 8
    assert rec["paged"]["decode_steps"] == 3    # the lone admission's: none
    while eng.step():
        pass
    assert len(a.tokens()) == 12 and len(b.tokens()) == 4
    # the T=1 program and the pair's, no [max_lanes, T] program
    assert sorted(eng._step_fns) == [(1, False, False, 0),
                                     (8, False, False, eng.prefill_lanes)]
    assert sorted(eng.compiled_steps()) == [
        "t1", f"t8_pair{eng.prefill_lanes}"]
    rec = eng.stats()["programs"]
    assert rec["programs"] == rec["iterations"]


@pytest.mark.parametrize("lanes,chunk,want", [
    (16, 32, 4),        # gpt2-xl's and OLMoE's cells: 16 + 4 x 32 = 144 rows
    (4, 8, 4), (2, 8, 2), (8, 8, 8),        # the tests' engines: every lane
    (64, 512, 1), (16, 128, 1), (32, 16, 8)])
def test_prefill_lanes_not_named_come_from_the_rows_rule(lanes, chunk, want):
    assert engine_mod._chunk_lanes(lanes, chunk) == want
    assert lanes + want * chunk <= engine_mod._PAIR_ROWS or want == 1


def test_a_drafting_engine_keeps_its_two_programs():
    eng = InferenceEngine("gpt", "nano", auto_start=False, max_lanes=2,
                          block_size=8, prefill_chunk=8, spec_k=2,
                          draft_proposer="ngram")
    a = eng.submit([5, 9, 2, 7] * 4, 8)
    for _ in range(4):
        eng.step()
    b = eng.submit([5, 9, 2, 7] * 3, 4)
    while eng.step():
        pass
    assert len(a.tokens()) == 8 and len(b.tokens()) == 4
    rec = eng.stats()["programs"]
    assert rec["mixed"] == 0 and rec["programs"] > rec["iterations"]
    assert all(name.endswith("_lanes2") or "_spec" in name or name == "t1"
               for name in eng.compiled_steps())

