"""dots3-note through the engine and its cache of several kinds of layer
(inference/kv_cache.py "layered"): the growing kind's latent rows and index
keys, the sliding kind's rows under a second table whose blocks go back as
the window moves, admission by each kind's peak, a prefix served only where
both kinds hold it, the wire format by kind, and the cell's rehearsal.  Nano
size on the CPU; the model's own tests are tests/test_dots3.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dots3 as ref
from ray_tpu.inference import InferenceEngine, PagedKVCache
from ray_tpu.models import dots3

NANO = dots3.CONFIGS["dots3-nano"]
SHARE = dots3.CONFIGS["dots3-nano-share"]


def _ref_kw(cfg):
    return dict(top_k=cfg.n_experts_per_tok, first_held=cfg.experts_offset,
                index_topk=cfg.index_topk, window=cfg.sliding_window)


@functools.lru_cache(maxsize=None)
def _init(cfg, seed=0):
    """(one compiled program a config, not one dispatch an op)"""
    return jax.jit(dots3.init_params, static_argnums=0)(
        cfg, jax.random.key(seed))


ENGINE = dict(auto_start=False, max_lanes=4, block_size=4,
              num_blocks=(96, 48), max_seq_len=96, prefill_chunk=8)


def _run(eng, *handles):
    while eng.step():
        pass
    return [h.tokens() for h in handles]


def test_the_engine_serves_the_references_greedy_tokens():
    cfg = NANO
    params = _init(cfg)
    eng = InferenceEngine("dots3", cfg, params, **ENGINE, prefill_lanes=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).tolist() for n in (37, 21)]
    outs = _run(eng, *(eng.submit(p, 40) for p in prompts))
    for prompt, out in zip(prompts, outs):
        seq = prompt + out
        want = np.asarray(jnp.argmax(ref.row_logits(
            params, np.asarray(seq), **_ref_kw(cfg)), -1))
        assert out == want[len(prompt) - 1:len(seq) - 1].tolist()
    st = eng.stats()
    sp = st["sparse"]
    assert sp["decode_steps"] == st["latent"]["decode_steps"] > 0
    assert sp["ctx_tokens"] == st["latent"]["ctx_tokens"]
    # contexts pass index_topk (16) and the window (9) from the start
    assert sp["rows_chosen"] < sp["ctx_tokens"]
    assert sp["window_rows"] < sp["rows_chosen"]
    assert st["windows"]["blocks_freed"] > 0
    assert st["moe"]["layer_steps"] % 8 == 0          # 8 expert layers
    # everything went back: no lane holds a block of either kind
    assert eng.cache.parts[0].index.allocator.num_free == 48
    assert eng.cache.allocator.num_free == 96


def test_compiled_steps_say_that_no_program_sorts_a_lanes_scores():
    """`compiled_steps()` over layers of several kinds: `select_sorts`, the
    sorts of a [rows, context] array in each program, 0 in the T=1 step
    and in the chunk's (compiled for the chip: tests/test_tpu_aot.py)."""
    cfg = NANO
    eng = InferenceEngine("dots3", cfg, _init(cfg), **ENGINE)
    _run(eng, eng.submit(list(range(30)), 3))
    steps = eng.compiled_steps()
    assert {"t1", "t8_pair4"} <= set(steps)
    assert all(s["select_sorts"] == 0 for s in steps.values())


def test_sliding_blocks_go_back_as_the_window_moves_and_never_one_attended():
    """After every commit a lane holds exactly the sliding blocks its window
    still reaches (and those its next positions were given), each behind
    them is back with the allocator, and a refcount never runs negative."""
    cfg = NANO
    eng = InferenceEngine("dots3", cfg, _init(cfg), **ENGINE)
    cache, bs = eng.cache, 4
    h = eng.submit(list(range(1, 30)), 50)
    seen_peak = 0
    while eng.step():
        for lane, req in enumerate(eng._lanes):
            if req is None:
                continue
            held = cache.parts[0].held(lane)
            length = int(cache.seq_lens[lane])
            first = max(length - (cfg.sliding_window - 1), 0) // bs
            assert min(held, default=first) >= first
            # every position the next token attends lies in a held block
            need = range(first, -(-length // bs))
            assert all(slot in held for slot in need)
            assert all(cache.parts[0].index.allocator.refcount(b) >= 1
                       for b in held.values())
            seen_peak = max(seen_peak, len(held))
    assert len(h.tokens()) == 50
    assert seen_peak <= cache.parts[0].peak(True)
    assert cache.stats["slide_blocks_freed"] >= (29 + 50 - 8) // bs - 1


def test_admission_reserves_each_kind_at_its_own_peak():
    """A request is admitted only where BOTH allocators have its peak
    beside every live lane's: with sliding blocks for one lane and a half,
    the second request waits for the first to end, though the growing kind
    has room for ten."""
    cfg = NANO
    params = _init(cfg)
    probe = PagedKVCache.for_model(dots3, cfg, num_blocks=(96, 48),
                                   block_size=4, max_lanes=4, max_seq_len=96,
                                   ahead=16)
    peak = probe.parts[0].peak(True)
    assert peak == (9 + 16 - 2) // 4 + 2 and probe.parts[0].peak(False) == 4
    eng = InferenceEngine("dots3", cfg, params, **dict(
        ENGINE, num_blocks=(96, peak + peak // 2)))
    a = eng.submit(list(range(1, 20)), 6)
    b = eng.submit(list(range(101, 120)), 6)
    eng.step()
    assert eng.num_active == 1 and eng.num_waiting == 1
    outs = _run(eng, a, b)
    assert [len(o) for o in outs] == [6, 6]
    assert eng.cache.parts[0].index.allocator.num_free == peak + peak // 2


def test_a_prefix_is_served_only_where_both_kinds_hold_it():
    """A 40-token document + question: the second request takes the
    document's 10 blocks from the growing kind and its last 2 from the
    sliding kind (positions 32..39 cover the window's 8 behind position
    40).  With the sliding tail evicted the match is shorter or none, and
    the answer is the same."""
    cfg = NANO
    params = _init(cfg)
    doc = np.random.default_rng(5).integers(0, 512, 40).tolist()
    eng = InferenceEngine("dots3", cfg, params, **ENGINE)
    first = eng.generate(doc + [7, 8, 9], 12)
    hit0 = eng.stats()["prefix_hit_tokens"]
    cache = eng.cache
    keys = [k for _b, k in cache.index.items()
            if not isinstance(k[1][0], str)]
    slide = cache.parts[0].index
    assert len(slide) >= 2
    again = eng.generate(doc + [7, 8, 9], 12)
    assert again == first
    assert eng.stats()["prefix_hit_tokens"] - hit0 == 40
    # only the tail of the sliding kind was shared: slots 8 and 9
    other = eng.generate(doc + [1, 2, 3], 12)
    # evict every sliding block: nothing of the document can be served
    for block, _key in list(slide.items()):
        slide.allocator.uncache(block)
        slide.evicted(block)
    assert cache.match_len(doc + [1, 2, 3]) == 0
    assert len(cache.match_prefix(doc + [1, 2, 3])) == 0
    hit1 = eng.stats()["prefix_hit_tokens"]
    assert eng.generate(doc + [1, 2, 3], 12) == other
    assert eng.stats()["prefix_hit_tokens"] == hit1
    assert keys


def test_a_match_ends_where_the_sliding_kind_still_holds_its_tail():
    """The growing kind holds a chain of 10 blocks; the sliding kind only
    blocks 4..6: the longest head both can serve is 7 blocks (its window,
    positions 20..27, lies in blocks 5 and 6)."""
    cfg = NANO
    cache = PagedKVCache.for_model(dots3, cfg, num_blocks=(40, 24),
                                   block_size=4, max_lanes=2, max_seq_len=96)
    tokens = list(range(1, 45))
    cache.alloc_lane(0, len(tokens))
    cache.ensure_capacity(0, 40)
    cache.seq_lens[0] = 40
    cache.seal_full_blocks(0, tokens)
    assert cache.match_len(tokens) == 40
    from ray_tpu.inference.kv_cache import chain_keys
    keys = chain_keys(tokens, 4)
    for i in (7, 8, 9):
        cache.parts[0].index.discard(keys[i])
    assert cache.match_len(tokens) == 28
    cache.parts[0].index.discard(keys[5])
    # 7 needs blocks 5, 6; 6 needs 4, 5; 5 needs 3 (held), 4: five blocks
    assert cache.match_len(tokens) == 20
    cache.free_lane(0)
    assert cache.adopt_prefix(1, tokens) == 20
    assert sorted(cache.parts[0].held(1)) == [3, 4]


def test_the_wire_format_says_which_kind_a_block_carries():
    from ray_tpu.serve.kv_tier.codec import KVBlockCodec
    cfg = SHARE
    params = _init(cfg)
    a = InferenceEngine("dots3", cfg, params, **ENGINE)
    prompt = list(range(1, 42))
    want = a.generate(prompt, 6)
    payload = a.export_prefix(prompt)
    assert payload["kind"] == "layered" and payload["v_pool"] is None
    assert payload["k"].shape == (3, 10, 4, 1, 24)
    more = payload["more"]
    assert [x.shape for x in more["extra"]] == [(3, 10, 4, 1, 16)]
    # a match of 10 blocks reads the sliding kind's last two
    assert more["slide_from"] == 8 and more["slide"].shape == (6, 2, 4, 1, 40)
    wire = KVBlockCodec.decode(KVBlockCodec.encode(payload))
    assert wire["kind"] == "layered" and wire["more"]["slide_from"] == 8
    b = InferenceEngine("dots3", cfg, params, **ENGINE)
    assert b.import_prefix(wire) == 12
    assert b.import_prefix(wire) == 0                  # idempotent
    assert b.generate(prompt, 6) == want
    assert b.stats()["prefix_hit_tokens"] == 40
    # a latent cache of one kind installs none of it, and the other way
    from ray_tpu.models import axk1
    other = PagedKVCache.for_model(axk1, axk1.CONFIGS["axk1-nano"],
                                   num_blocks=16, block_size=4, max_lanes=1,
                                   max_seq_len=64)
    assert other.kind == "latent" and other.install_prefix(wire) == 0
    assert b.cache.install_prefix(dict(wire, kind="latent")) == 0


def test_the_cell_rehearses_on_the_cpu():
    """`benchmark/run.py --rehearse`: the cell's whole path (the replica,
    the generator's shared documents, the prefix cache of both kinds, the
    reference check) at nano size.  (A window of 10 s, as Trinity-Mini's
    rehearsal in tests/test_afmoe.py: at 4 s, under the suite's six workers,
    one run in five ended with no request run to its end, nothing to
    compare and `correct` false.)"""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "serve_dots3_docs_decode", "--seed", "2147483659", "--seconds", "10",
         "--trace", "0", "--rehearse"], cwd=root, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and not line["failed"]
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
