"""The paged cache's book-keeping where a lane does not hold one row a token
(inference/kv_cache.py, `window` and `chunk`): windows of 32 tokens, chunks
of 4, blocks of 8, so a window is 4 exact blocks and its 8 summary rows fill
one.  The manager alone: no model, no device program (a closed window's
summary block is whatever the pool holds there)."""

import numpy as np
import pytest

from ray_tpu.inference.kv_cache import PagedKVCache, chain_keys
from ray_tpu.serve.kv_tier.codec import KVBlockCodec

W, C, BS = 32, 4, 8


def _cache(num_blocks=32, max_seq_len=160, **kw):
    return PagedKVCache(2, 2, 4, num_blocks=num_blocks, block_size=BS,
                        max_lanes=3, max_seq_len=max_seq_len, window=W,
                        chunk=C, **kw)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 50, n).tolist()


def _feed(cache, lane, tokens, upto=None, start=None):
    """What the engine does for a lane, a position at a time: close the
    window the position opens, claim its block, commit, seal."""
    closed = []
    for pos in range(int(cache.seq_lens[lane]) if start is None else start,
                     len(tokens) if upto is None else upto):
        if cache.window_due(lane, pos):
            closed.append(cache.close_window(lane, tokens))
        cache.ensure_capacity(lane, pos + 1)
        cache.seq_lens[lane] = pos + 1
        cache.seal_full_blocks(lane, tokens)
    return closed


@pytest.mark.parametrize("n,blocks,rows", [
    (1, 1, 1), (8, 1, 8), (9, 2, 9), (32, 4, 32), (33, 2, 9), (40, 2, 16),
    (41, 3, 17), (64, 5, 40), (65, 3, 17), (100, 4, 28)])
def test_blocks_needed_is_a_sawtooth(n, blocks, rows):
    cache = _cache()
    assert cache.layout.blocks_needed(n) == blocks
    assert cache.rows_held(n) == rows


def test_a_cache_of_a_row_a_token_is_the_degenerate_case():
    plain = PagedKVCache(2, 2, 4, num_blocks=8, block_size=BS, max_lanes=1,
                         max_seq_len=64)
    assert plain.kind == "kv" and not plain.window
    assert [plain.layout.blocks_needed(n) for n in (1, 8, 9, 64)] == [1, 1, 2, 8]
    assert plain.layout.peak_blocks(40) == plain.layout.blocks_needed(40) == 5
    assert plain.rows_held(40) == 40 and plain.window_room(3) == 64
    assert not plain.window_due(0, 32) and plain.max_blocks_per_seq == 8


def test_the_tables_width_is_the_peak_not_the_end():
    assert _cache(max_seq_len=160).max_blocks_per_seq == 4 + 4
    assert _cache(max_seq_len=161).max_blocks_per_seq == 4 + 4
    assert _cache(max_seq_len=129).max_blocks_per_seq == 3 + 4   # not 4 + 1
    assert _cache(max_seq_len=20).max_blocks_per_seq == 3
    with pytest.raises(ValueError):
        PagedKVCache(2, 2, 4, num_blocks=8, block_size=16, max_lanes=1,
                     max_seq_len=64, window=W, chunk=C)   # 8 rows, blocks of 16
    with pytest.raises(ValueError):
        PagedKVCache(2, 2, 4, num_blocks=8, block_size=BS, max_lanes=1,
                     max_seq_len=64, window=W, chunk=C, latent=True)


def test_the_table_before_and_after_a_window_closes():
    cache = _cache()
    tokens = _tokens(70)
    cache.alloc_lane(0, len(tokens))
    assert len(cache.lane_blocks(0)) == 4           # the first window's
    _feed(cache, 0, tokens, upto=32)
    before = cache.lane_blocks(0)
    free = cache.allocator.num_free
    assert len(before) == 4 and not cache.window_due(0, 31)
    assert cache.window_due(0, 32)
    with pytest.raises(RuntimeError):
        cache.ensure_capacity(0, 33)                # the window is not closed
    src, dst = cache.close_window(0, tokens)
    assert src == before and len(dst) == 1 and dst[0] not in before
    assert cache.lane_blocks(0) == dst
    assert list(cache.block_tables[0, :2]) == [dst[0], 0]
    # sealed exact blocks stay cached (evictable); all four came back
    assert cache.allocator.num_free == free + 4 - 1
    assert all(cache.allocator.is_evictable(b) for b in src)
    assert not cache.window_due(0, 32)
    _feed(cache, 0, tokens, start=32)
    blocks = cache.lane_blocks(0)                   # [s0, s1 | one open]
    assert len(blocks) == 3 and blocks[0] == dst[0]
    assert cache.stats["windows_closed"] == 2
    assert cache.blocks_by_kind() == (2, 4 + 4 + 1)
    cache.free_lane(0)
    assert cache.layout.closed[0] == 0
    assert cache.allocator.num_free == 32


def test_unsealed_window_blocks_go_straight_back_to_the_free_list():
    cache = _cache(prefix_cache=False)
    tokens = _tokens(40)
    cache.alloc_lane(0, len(tokens))
    _feed(cache, 0, tokens, upto=32)
    src, dst = cache.close_window(0, tokens)
    assert cache.allocator.num_unused == 32 - 1
    assert cache.num_indexed_blocks == 0


@pytest.mark.parametrize("final,closed,peak", [
    (20, 0, 3), (32, 0, 4), (33, 0, 5), (64, 0, 5), (65, 0, 6), (100, 0, 7),
    (100, 2, 7), (100, 3, 4), (65, 2, 3)])
def test_the_peak_admission_reserves(final, closed, peak):
    """The most a lane owns on its way: the close of the last window it
    completes (that window's four blocks, the summaries before it and the
    fresh summary block), unless that is behind it."""
    assert _cache().layout.peak_blocks(final, closed) == peak


def test_admission_counts_the_peak_and_a_lane_never_outgrows_it():
    cache = _cache(num_blocks=7)
    tokens = _tokens(100)
    assert cache.can_admit_prefix(tokens[:10], final_len=100)
    assert not cache.can_admit_prefix(tokens[:10], headroom_blocks=1,
                                      final_len=100)
    assert cache.can_admit_prefix(tokens[:10], headroom_blocks=4)
    cache.adopt_prefix(0, tokens[:10])
    most = 0
    for pos in range(100):
        if cache.window_due(0, pos):
            cache.close_window(0, tokens)
            most = max(most, cache.allocator.num_blocks
                       - cache.allocator.num_free + 4)   # held a moment ago
        cache.ensure_capacity(0, pos + 1)
        cache.seq_lens[0] = pos + 1
        most = max(most, len(cache.lane_blocks(0)))
        assert cache.lane_peak(0, 100) >= len(cache.lane_blocks(0))
    assert most == cache.layout.peak_blocks(100) == 7


def _sealed(tokens, n=None):
    cache = _cache()
    cache.adopt_prefix(0, tokens)
    _feed(cache, 0, tokens, upto=n)
    return cache


@pytest.mark.parametrize("n_prompt,covered,blocks", [
    (20, 16, 2),        # inside window 0: its exact blocks
    (32, 24, 3),        # a prompt that ends at the edge keeps a token back
    (33, 32, 1),        # at a window's edge: the summary block alone
    (50, 48, 3),        # in the open window: a summary, two exact blocks
    (70, 64, 2),        # two summaries
    (90, 88, 5)])       # two summaries and the open window's three blocks
def test_a_prefix_match_ends_where_the_prompt_does(n_prompt, covered, blocks):
    tokens = _tokens(100)
    cache = _sealed(tokens, 90)
    prompt = tokens[:n_prompt]
    assert cache.match_len(prompt) == covered
    assert cache.match_len(prompt, chain_keys(prompt, BS)) == covered
    assert len(cache.match_prefix(prompt)) == blocks
    got = cache.adopt_prefix(1, prompt)
    assert got == covered and int(cache.seq_lens[1]) == covered
    assert cache.layout.closed[1] == covered // W
    assert len(cache.lane_blocks(1)) == cache.layout.to_start(
        n_prompt, covered // W) >= blocks
    # ... and the lane goes on from there like the one that sealed them:
    # the summaries it adopted are lane 0's, those it makes are its own
    _feed(cache, 1, tokens, upto=95)
    assert cache.layout.closed[1] == 2
    shared = covered // W
    assert cache.lane_blocks(1)[:shared] == cache.lane_blocks(0)[:shared]
    assert not set(cache.lane_blocks(1)[shared:2]) & set(
        cache.lane_blocks(0))
    assert cache.match_len(tokens[:95]) == 88


def test_a_match_that_ends_in_a_closed_window_takes_its_exact_blocks():
    tokens = _tokens(100)
    cache = _sealed(tokens, 90)
    other = tokens[:44] + [49] * 30     # parts ways inside window 1
    assert cache.match_len(other) == 40
    # a summary block, then window 1's first exact block, still cached
    match = cache.match_prefix(other)
    assert len(match) == 2 and match[0] == cache.lane_blocks(0)[0]
    assert cache.allocator.is_evictable(match[1])
    cache.adopt_prefix(1, other)
    closed = _feed(cache, 1, other)
    assert len(closed) == 1             # it closed window 1 for itself
    assert cache.lane_blocks(1)[0] == cache.lane_blocks(0)[0]
    assert cache.lane_blocks(1)[1] != cache.lane_blocks(0)[1]


def test_a_full_window_without_its_summary_is_adopted_open():
    """All four exact blocks of window 0 are sealed but nobody closed it:
    the match ends at the edge and the adopting lane closes it first."""
    tokens = _tokens(60)
    cache = _sealed(tokens, 32)
    assert cache.match_len(tokens) == 32 and cache.layout.closed[0] == 0
    cache.adopt_prefix(1, tokens)
    assert cache.layout.closed[1] == 0 and len(cache.lane_blocks(1)) == 4
    assert cache.window_due(1, 32)
    assert len(_feed(cache, 1, tokens)) == 1


def test_evicting_a_closed_windows_exact_blocks_leaves_longer_matches_whole():
    tokens = _tokens(100)
    cache = _sealed(tokens, 90)
    cache.free_lane(0)
    summaries = cache.match_prefix(tokens[:70])
    # the LRU end of the evictable list: the exact blocks of window 0
    taken = cache.allocator.alloc(cache.allocator.num_unused + 4)
    assert cache.allocator.evictions == 4
    assert cache.match_prefix(tokens[:70]) == summaries
    assert cache.match_len(tokens[:90]) == 88
    assert cache.match_len(tokens[:20]) == 0        # they served only this
    assert cache.match_len(tokens[:50]) == 48       # window 1's are there
    cache.allocator.free(taken)


@pytest.mark.parametrize("n_prompt", [50, 70, 90])
def test_export_install_round_trip_carries_both_kinds(n_prompt):
    tokens = _tokens(100)
    src = _sealed(tokens, 90)
    # recognisable contents: block b holds b + 1 everywhere
    for b in range(src.allocator.num_blocks):
        shape = (2, 1, BS, 2, 4)
        src.write_blocks(np.asarray([b]), np.full(shape, b + 1, np.float32),
                         np.full(shape, -(b + 1), np.float32))
    prompt = tokens[:n_prompt]
    payload = KVBlockCodec.decode(KVBlockCodec.encode(
        src.export_prefix(prompt)))
    assert payload["kind"] == "windowed"
    closed = (n_prompt - 1) // W
    assert [len(blk) for blk in payload["chain"]] == (
        [W] * closed + [BS] * ((n_prompt - 1) % W // BS))
    dst = _cache()
    assert dst.install_prefix(payload) == len(payload["chain"])
    assert dst.install_prefix(payload) == 0         # idempotent
    assert dst.match_len(prompt) == src.match_len(prompt)
    want, got = src.match_prefix(prompt), dst.match_prefix(prompt)
    for a, b in zip(want, got):
        ka, va = src.read_blocks(np.asarray([a]))
        kb, vb = dst.read_blocks(np.asarray([b]))
        np.testing.assert_array_equal(ka, kb)
        np.testing.assert_array_equal(va, vb)
    # a cache of a row a token refuses the windowed payload, and back
    plain = PagedKVCache(2, 2, 4, num_blocks=8, block_size=BS, max_lanes=1,
                         max_seq_len=64)
    assert plain.install_prefix(payload) == 0
    # the lane that adopts the installed chain seals on as the source did
    dst.adopt_prefix(0, tokens[:95])
    _feed(dst, 0, tokens, upto=95)
    assert dst.layout.closed[0] == 2


def test_a_dispatched_steps_table_is_not_the_hosts_live_one():
    """A step dispatched ahead reads the table it was given after the host
    has closed a window and rewritten the lane's row."""
    cache = _cache()
    tokens = _tokens(40)
    cache.alloc_lane(0, len(tokens))
    _feed(cache, 0, tokens, upto=32)
    given = cache.device_tables()
    before = np.asarray(given).copy()
    cache.close_window(0, tokens)
    np.testing.assert_array_equal(np.asarray(given), before)
    assert (np.asarray(cache.device_tables()) != before).any()
