"""A population's lane arrays reach the step through ONE buffer (PR 42:
`engine._lane_views` on the host, `_upload`'s one transfer,
`engine._unpack_lanes` in front of the step body): what the body receives
is what `_build_batch` wrote, bit for bit, for every kind of cache and every
kind of program, and the served tokens are the ones the nine-array engine
served (pinned from the parent's tree, commit d8dd0a8: `python
tests/test_upload.py` prints them)."""

import importlib

import jax
import numpy as np
import pytest

from ray_tpu.inference import InferenceEngine

BIG_SEED = 3_000_000_019            # above 2^31: negative as an int32's bits

CASES = {
    # a K/V cache, a chunk of every lane (the rows rule)
    "kv": ("gpt", "nano", dict(max_lanes=2, block_size=8, prefill_chunk=8)),
    # the same behind a proposer: verify steps of T = 2 and 3, the chunk
    # in a program of its own
    "kv_spec": ("gpt", "nano", dict(max_lanes=2, block_size=8,
                                    prefill_chunk=8, spec_k=2,
                                    draft_proposer="ngram")),
    # a latent cache, the pair's programs at 1 and `prefill_lanes` rows
    "latent_prefill_lanes": ("axk1", "axk1-nano-share", dict(
        max_lanes=3, block_size=8, prefill_chunk=8, prefill_lanes=2)),
    # EVA's windows: the compaction program beside the steps
    "eva": ("evabyte", "evabyte-nano", dict(
        max_lanes=2, block_size=8, prefill_chunk=16, prefill_lanes=1,
        num_blocks=32)),
    # layers of several kinds over two tables
    "layered": ("dots3", "dots3-nano", dict(
        max_lanes=4, block_size=4, num_blocks=(96, 48), max_seq_len=96,
        prefill_chunk=8, prefill_lanes=2)),
}

# Served by the parent's engine (nine `jnp.asarray` a population) from the
# same seeds: [greedy alone, greedy beside sampled, sampled, greedy third].
_KV = [[50, 437, 437, 437, 437, 437, 437, 437, 437, 223],
       [437, 437, 437, 437, 437, 437, 437, 437, 437, 223, 437, 437],
       [253, 75, 485, 256, 50, 47, 442, 386, 461, 61, 316, 394, 117, 82],
       [204, 204, 50, 284, 18, 210]]
PINNED = {
    "kv": _KV,
    "kv_spec": _KV,             # a verify step is token-exact with T=1
    "latent_prefill_lanes": [
        [178, 7, 7, 78, 347, 203, 51, 255, 280, 5],
        [7, 7, 223, 507, 77, 77, 77, 479, 508, 71, 196, 357],
        [329, 75, 329, 256, 4, 47, 442, 433, 77, 62, 316, 107, 117, 105],
        [169, 400, 454, 149, 65, 400]],
    "eva": [
        [16, 20, 11, 4, 35, 20, 12, 35, 35, 35],
        [11, 4, 35, 12, 12, 35, 12, 35, 12, 35, 12, 35],
        [9, 9, 40, 32, 4, 47, 46, 45, 23, 61, 63, 10, 23, 47],
        [27, 17, 4, 27, 33, 33]],
    "layered": [
        [111, 131, 436, 83, 56, 357, 201, 261, 126, 106],
        [131, 328, 41, 376, 400, 97, 40, 220, 97, 40, 298, 210],
        [7, 75, 78, 97, 50, 47, 314, 125, 226, 61, 391, 394, 117, 82],
        [21, 438, 298, 444, 291, 94]],
}

NAMES = ("tokens", "positions", "valid", "ctx_lens", "gather", "temps",
         "seeds", "counters", "rows")


def _engine(case):
    family, cfg, kw = CASES[case]
    cfg = importlib.import_module(f"ray_tpu.models.{family}").CONFIGS[cfg]
    return InferenceEngine(family, cfg, auto_start=False, seed=0, **kw)


def _serve(eng):
    """A greedy request alone, then three at once (greedy, sampled with a
    seed above 2^31, greedy: more than some engines have lanes): T=1, whole
    and partial prefill chunks, verify steps where a proposer drafts."""
    vocab = eng.config.vocab_size
    cycle = [t % vocab for t in (5, 9, 2, 7)] * 6       # an n-gram's food
    rng = np.random.default_rng(11)
    other = rng.integers(0, vocab, 37).tolist()
    third = rng.integers(0, vocab, 9).tolist()
    out = [eng.generate(cycle[:11], 10)]
    handles = [eng.submit(cycle, 12),
               eng.submit(other, 14, temperature=0.8, seed=BIG_SEED),
               eng.submit(third, 6)]
    while eng.step():
        pass
    return out + [h.tokens() for h in handles]


def _spy(eng, built, received):
    """Record (copies of) what `_build_batch` hands over and, by a callback
    from inside the compiled program, what the step body is called with: a
    program's populations in order (a pair's two: the decoding lanes',
    then the chunk's)."""
    build, make = eng._build_batch, eng._make_step_fn

    def build_batch(live, t, *more):
        arrays, chunks = build(live, t, *more)
        _, _, _, host, rows = arrays
        built.append([np.array(a) for a in host]
                     + ([] if rows is None else [np.array(rows)]))
        return arrays, chunks

    def make_step_fn(sample, spec=False, compact=False, pair=False):
        step = make(sample, spec, compact, pair)

        def seen(*populations):
            flat = [x for p in populations for x in p]
            cut = len(populations[0])
            jax.debug.callback(
                lambda *a: received.append([
                    [np.array(x) for x in p] for p in (a[:cut], a[cut:])
                    if p]),
                *flat, ordered=True)

        def body(params, k, v, tokens, positions, valid, tables, *rest):
            seen((tokens, positions, valid, *rest[:6 if compact else 5]))
            return step(params, k, v, tokens, positions, valid, tables,
                        *rest)

        def pair_body(params, k, v, decode, chunk, *rest):
            seen(decode, chunk)
            return step(params, k, v, decode, chunk, *rest)

        body.__name__ = pair_body.__name__ = step.__name__
        return pair_body if pair else body

    eng._build_batch, eng._make_step_fn = build_batch, make_step_fn


@pytest.mark.parametrize("case", list(CASES))
def test_the_step_body_receives_build_batchs_arrays_bit_for_bit(case):
    eng = _engine(case)
    built, received = [], []
    _spy(eng, built, received)
    tokens = _serve(eng)
    jax.effects_barrier()
    # (`_warm_widths`' programs run a population nobody is in, which no
    # `_build_batch` made: every row masked and no lane's)
    received = [got for program in received
                if len(program[-1]) == 8
                or (program[-1][8] < eng.max_lanes).any()
                for got in program]
    assert len(received) == len(built) > 10
    for host, got in zip(built, received):
        assert len(host) == len(got)
        for name, a, b in zip(NAMES, host, got):
            if name == "valid":         # 0 / 1 on the host, bool in the step
                assert b.dtype == bool and set(np.unique(a)) <= {0, 1}
                a = a != 0
            assert a.dtype == b.dtype and a.shape == b.shape, name
            # (by the bits: a float's -0.0 or NaN would compare otherwise)
            assert a.tobytes() == b.tobytes(), name
    widths = {h[0].shape for h in built}
    lanes, chunk = eng.max_lanes, eng.prefill_chunk
    assert (lanes, 1) in widths
    # a chunk is compact; an engine that names its lanes served both widths
    assert {len(h) for h in built} == {8, 9}
    assert (eng.prefill_lanes, chunk) in widths
    if eng._widths:
        assert (1, chunk) in widths
    if eng.spec_k:
        assert widths & {(lanes, 2), (lanes, 3)}
    # the sampled lane's seed went over by its bits, its temperature too
    seeds = np.concatenate([h[6] for h in built])
    temps = np.concatenate([h[5] for h in built])
    assert seeds.dtype == np.uint32 and BIG_SEED in seeds
    assert temps.dtype == np.float32 and np.float32(0.8) in temps
    assert tokens == PINNED[case]


if __name__ == "__main__":              # on the parent's tree: the pins
    for name in CASES:
        print(f'    "{name}": {_serve(_engine(name))},')
