"""The cache manager's contract over its kinds (inference/kv_cache.py): one
script of a lane's life through the cache `for_model` makes for each of the
five families, host book-keeping and block copies only (no model forward),
with every index's allocator conserving its blocks after every step; and
the source held to what the module's docstring says: the shared methods
name no kind, one sealed index and one install loop serve growing blocks,
sliding blocks and snapshot slots, and one builder makes every cache."""

import ast
import collections
import importlib
import inspect
import re
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import kv_cache
from ray_tpu.inference.kv_cache import PagedKVCache, SealedIndex, chain_keys
from ray_tpu.serve.kv_tier.codec import KVBlockCodec

BS, N, MARK = 8, 77, 64       # block size, prompt length, checkpoint's edge
# family, its smallest config, num_blocks (a tuple: the part's own second)
KINDS = {"kv": ("gpt", "nano", 40),
         "latent": ("axk1", "axk1-nano", 40),
         "windowed": ("evabyte", "evabyte-nano", 40),
         "layered": ("dots3", "dots3-nano", (40, 24)),
         # the sliding part over a K and a V pool beside the growing pair
         "layered:pairs": ("afmoe", "afmoe-nano", (40, 24)),
         "state": ("falconh1", "falconh1-nano", (40, 3)),
         # the state part over three mixer layers, K and V pools over one
         # attention layer: a part with a layer count of its own
         "state:own_layers": ("nemotronh", "nemotronh-nano", (40, 3)),
         # the state part of ONE buffer: a gated short convolution keeps its
         # tail and no recurrent state
         "state:tail_only": ("lfm2", "lfm2-nano", (40, 3)),
         # the state part (a recurrent state and a tail over four KDA
         # layers) behind ONE latent pool over two latent layers
         "state:latent_pool": ("kimilinear", "kimilinear-nano", (40, 3))}


def _make(kind) -> PagedKVCache:
    family, name, num_blocks = KINDS[kind]
    model = importlib.import_module(f"ray_tpu.models.{family}")
    cache = PagedKVCache.for_model(
        model, model.CONFIGS[name], num_blocks=num_blocks, block_size=BS,
        max_lanes=3, max_seq_len=96)
    assert cache.kind == kind.split(":")[0]
    # every pool and buffer distinct everywhere: a copy that lands is seen
    cache.update_pools(*jax.tree.map(
        lambda x: (jnp.arange(x.size) % 251).reshape(x.shape).astype(x.dtype),
        cache.step_pools))
    return cache


def _conserved(cache) -> None:
    """Every index's allocator: free + live + evictable = total, only
    sealed content is kept at refcount 0, a free block is indexed nowhere;
    and the growing blocks' refcounts are the lanes' shares."""
    for index in cache.indexes:
        a = index.allocator
        live = sum(r > 0 for r in a._ref)
        assert a.num_unused + len(a._evictable) + live == a.num_blocks
        assert len(set(a._free)) == len(a._free)
        sealed = dict(index.items())
        assert len(sealed) == len(index)
        assert set(a._evictable) <= set(sealed)
        assert not set(a._free) & set(sealed)
        assert all(index.get(key) == b and key in index
                   for b, key in sealed.items())
    held = collections.Counter(
        b for blocks in cache._lane_blocks for b in blocks)
    a = cache.allocator
    assert all(a.refcount(b) == n for b, n in held.items())
    assert sum(a._ref) == sum(held.values())


def _all_free(cache) -> bool:
    return all(i.allocator.num_free == i.allocator.num_blocks
               for i in cache.indexes)


def _drive(cache, lane, tokens, mark=None) -> None:
    """What the engine does for a lane, a position at a time: close the
    window the position opens, grow, commit, seal, let the parts release,
    and keep a checkpoint behind the block that ends at `mark`."""
    keys = chain_keys(tokens, BS)
    for pos in range(int(cache.seq_lens[lane]), len(tokens)):
        assert cache.closes or not cache.window_due(lane, pos)
        if cache.window_due(lane, pos):
            cache.close_window(lane, tokens)
        cache.ensure_capacity(lane, pos + 1)
        cache.seq_lens[lane] = pos + 1
        cache.seal_full_blocks(lane, tokens)
        cache.after_commit([lane])              # (nothing without `releases`)
        if pos + 1 == mark:
            assert cache.checkpoint(lane, keys[mark // BS - 1]) \
                == cache.checkpoints
        _conserved(cache)


def _tokens(seed):
    return np.random.default_rng(seed).integers(0, 64, N).tolist()


def _same(a, b) -> bool:
    return jax.tree.structure(a) == jax.tree.structure(b) and all(
        np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a),
                                             jax.tree.leaves(b)))


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_lanes_life_is_the_same_script_over_every_kind(kind):
    cache, tokens = _make(kind), _tokens(0)
    # What a warm adopt covers: every whole block but the last token's, or
    # where the kind keeps checkpoints the blocks one stands behind.
    promise = MARK if cache.checkpoints else (N - 1) // BS * BS
    _conserved(cache)
    # admit and adopt cold, grow across block (and window) edges, seal
    assert cache.can_admit(N) and cache.can_admit_prefix(tokens, final_len=N)
    assert cache.adopt_prefix(0, tokens) == 0
    _conserved(cache)
    _drive(cache, 0, tokens, mark=MARK)
    assert cache.rows_held(N) <= N and len(cache.lane_blocks(0)) \
        == cache.layout.blocks_needed(N)
    cache.free_lane(0)
    _conserved(cache)
    assert _all_free(cache) and cache.num_indexed_blocks > 0
    # the same prompt warm
    assert cache.match_len(tokens) == promise
    assert cache.can_admit_prefix(tokens, keys=chain_keys(tokens, BS))
    assert cache.adopt_prefix(1, tokens) == promise
    assert int(cache.seq_lens[1]) == promise
    _conserved(cache)
    _drive(cache, 1, tokens)
    cache.free_lane(1)
    assert _all_free(cache) and cache.stats["hit_tokens"] == promise
    # export, and install into a second cache: the same content there
    payload = KVBlockCodec.decode(KVBlockCodec.encode(
        cache.export_prefix(tokens)))
    assert payload["kind"] == kind.split(":")[0]
    assert set(payload.get("more", ())) == set(cache._wire_more)
    other = _make(kind)
    assert other.install_prefix(payload) > 0
    _conserved(other)
    assert other.install_prefix(payload) == 0           # idempotent
    assert _same(other.export_prefix(tokens), cache.export_prefix(tokens))
    assert other.adopt_prefix(0, tokens) == promise
    _conserved(other)
    other.free_lane(0)
    assert _all_free(other)
    # a cache of another kind installs none of it
    foreign = _make("kv" if kind != "kv" else "latent")
    assert foreign.install_prefix(payload) == 0 and _all_free(foreign)
    # evict under pressure: new prompts until the oldest content goes
    for seed in range(1, 12):
        if cache.allocator.evictions:
            break
        fresh = _tokens(seed)
        assert cache.can_admit_prefix(fresh, final_len=N)
        assert cache.adopt_prefix(2, fresh) == 0
        _drive(cache, 2, fresh, mark=MARK)
        cache.free_lane(2)
        _conserved(cache)
    assert cache.allocator.evictions > 0
    assert cache.match_len(tokens) <= promise
    got = cache.adopt_prefix(0, tokens)
    assert got <= promise and got % BS == 0
    _drive(cache, 0, tokens)
    cache.free_lane(0)
    _conserved(cache)
    assert _all_free(cache)


# The shared life of a lane: written once, for every kind.
SHARED = ("can_admit", "alloc_lane", "match_prefix", "_match_dev",
          "_match_chain", "match_len", "can_admit_prefix", "adopt_prefix",
          "ensure_capacity", "has_blocks_to_seal", "seal_full_blocks", "_seal",
          "_dropped", "export_prefix", "install_prefix", "after_commit",
          "checkpoint", "truncate_lane", "free_lane", "step_pools",
          "update_pools", "attach_tier", "prefix_summary")
KIND_WORDS = ("kind", "window", "slid", "state", "snap", "latent",
              "sawtooth")


def _names(fn) -> set:
    """The identifiers and attribute names of a function's code (its
    docstring and comments say what they like)."""
    if isinstance(fn, property):
        fn = fn.fget
    tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
    return {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} | {
        n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}


@pytest.mark.parametrize("method", SHARED)
def test_the_shared_methods_name_no_kind(method):
    """No `self.kind`, `self.window`, `self.slide_window`, `self.state`, no
    class of a layout or a part: what differs is asked of `self.layout` and
    looped over `self.parts`."""
    names = _names(inspect.getattr_static(PagedKVCache, method))
    named = {n for n in names for w in KIND_WORDS if w in n.lower()}
    assert not named, named


def test_one_sealed_index_and_one_install_loop_serve_every_kind():
    src = inspect.getsource(kv_cache)
    # one place makes an allocator, one marks a block as sealed content,
    # one allocates for foreign content
    assert src.count("BlockAllocator(") == 1
    assert src.count(".mark_cached(") == 1
    # (a block at a time: `install`, `ensure_capacity`, a part's `grow`)
    assert src.count(".alloc(1)") == 3
    assert "alloc(1)" in inspect.getsource(SealedIndex.install)
    for kind, n in (("kv", 1), ("layered", 2), ("state", 2)):
        indexes = _make(kind).indexes
        assert len(indexes) == n
        assert all(type(i) is SealedIndex for i in indexes)
    # no part: no per-lane work beside the chain
    assert all(_make(kind).parts == [] for kind in ("kv", "latent",
                                                    "windowed"))


def test_a_sliding_part_takes_its_pools_in_pairs_where_rows_are_k_and_v():
    """`SlidingRows` over as many pools as the model's attention leaves rows
    in (a latent row: one; K and V rows: two), on the same blocks under the
    same table; `k` is then every pool as the model's runs index them, the
    growing pair first, and the wire format carries a list of the sliding
    pools' blocks where there is more than one."""
    latent, pairs = _make("layered"), _make("layered:pairs")
    assert len(latent.parts[0].pools) == 1 and latent.latent
    assert latent.parts[0].pools == (2,) and len(latent.k) == 3
    assert pairs.parts[0].pools == (2, 3) and not pairs.latent
    assert pairs.v is None and len(pairs.k) == 4
    assert [p.shape[0] for p in pairs.k] == [2, 2, 6, 6]
    assert pairs.k[2].shape == pairs.k[3].shape == (6, 24, BS, 128)
    assert pairs.block_tables.shape == (3, 2 * pairs.max_blocks_per_seq)
    assert pairs.step_pools == (pairs.k, None)
    k_np, v_np = pairs.read_blocks(jnp.asarray([1, 2]))
    assert k_np.shape == v_np.shape == (2, 2, BS, 2, 16)
    pairs.write_blocks(jnp.asarray([5]), k_np[:, :1], v_np[:, 1:] + 1)
    k5, v5 = pairs.read_blocks(jnp.asarray([5]))
    assert np.array_equal(k5, k_np[:, :1])
    assert np.array_equal(v5, v_np[:, 1:] + 1)
    # no family's name in the manager, the decoder or the engine
    from ray_tpu.inference import engine
    from ray_tpu.models import decoder
    for module in (kv_cache, decoder, engine):
        src = inspect.getsource(module)
        code = "\n".join(line.split("#")[0] for line in src.splitlines())
        code = re.sub(r'"""[\s\S]*?"""', "", code)
        assert not re.search(r"afmoe|trinity", code, re.I), module.__name__


def test_one_builder_makes_every_cache():
    """`for_model` makes every cache through the keyword constructor, and
    the keywords only `for_model` passed are no constructor options: a
    tuple `num_blocks` is taken apart there, for the part that owns it."""
    params = set(inspect.signature(PagedKVCache.__init__).parameters)
    assert params == {"self", "n_layers", "kv_heads", "head_dim",
                      "num_blocks", "block_size", "max_lanes", "max_seq_len",
                      "dtype", "prefix_cache", "latent", "window", "chunk",
                      "_extra", "_parts"}
    src = inspect.getsource(PagedKVCache.for_model.__func__)
    assert src.count("cls(") == 1 and "__new__" not in src
    by_hand = PagedKVCache(2, 4, 16, num_blocks=8, block_size=BS,
                           max_lanes=1, max_seq_len=64)
    model = importlib.import_module("ray_tpu.models.gpt")
    built = PagedKVCache.for_model(model, model.CONFIGS["nano"], num_blocks=8,
                                   block_size=BS, max_lanes=1, max_seq_len=64)
    assert vars(by_hand).keys() == vars(built).keys()
    assert by_hand.kind == built.kind == "kv"
    assert by_hand.pool_shape == built.pool_shape
    assert _make("layered").parts[0].index.allocator.num_blocks == 24
    assert _make("state").parts[0].slots == 3


@pytest.mark.parametrize("kind,names", [
    ("state", ("state", "tail")), ("state:own_layers", ("state", "tail")),
    ("state:tail_only", ("tail",)), ("state:latent_pool", ("state", "tail"))])
def test_a_state_part_holds_the_buffers_its_mixer_states(kind, names):
    """One buffer or two out of one code path: what the part allocates,
    hands to a step, takes back, snapshots and ships is exactly what the
    mixer's `StateRows` states; a checkpoint copies a lane's slot of every
    buffer into a snapshot slot and an adoption copies it back, other slots
    as they were; a payload of other buffers is refused."""
    cache, tokens = _make(kind), _tokens(0)
    part = cache.parts[-1]
    assert part.wire == names == cache._wire_more
    assert len(part.buffers) == len(part.snap_buffers) == len(names)
    pools, none = cache.step_pools
    rows = 1 if cache.latent else 2     # one latent pool, or a K and a V
    assert none is None and len(pools) == rows + len(names)
    assert all(a is b for a, b in zip(pools[rows:], part.buffers))
    assert all(b.shape[:2] == (part.buffers[0].shape[0], 3 + 1)
               and s.shape == b.shape[:1] + (3,) + b.shape[2:]
               for b, s in zip(part.buffers, part.snap_buffers))
    if "state" not in names:
        with pytest.raises(AttributeError, match="no 'state' buffer"):
            part.snaps
        assert part.tail is part.buffers[0]
    else:
        assert part.state.dtype == jnp.float32 and part.snaps.ndim == 5
    # a step's return is rebound buffer for buffer
    cache.update_pools(tuple(x + 1 for x in pools), None)
    assert all(np.array_equal(a, b + 1) for a, b in zip(
        cache.parts[-1].buffers, pools[rows:]))
    assert (cache.v is None) == cache.latent
    assert np.array_equal(cache.k, pools[0] + 1)
    # checkpoint lane 0, adopt into lane 1: lane 1's slot is lane 0's
    assert cache.adopt_prefix(0, tokens) == 0
    _drive(cache, 0, tokens, mark=MARK)
    before = [np.asarray(b) for b in part.buffers]
    assert cache.adopt_prefix(1, tokens) == MARK
    for old, new in zip(before, part.buffers):
        new = np.asarray(new)
        np.testing.assert_array_equal(new[:, 1], old[:, 0])
        np.testing.assert_array_equal(np.delete(new, 1, 1),
                                      np.delete(old, 1, 1))
    payload = cache.export_prefix(tokens[:MARK] + [1])
    assert set(payload["more"]) == set(names)
    fewer = {k: v for k, v in payload["more"].items() if k != names[0]}
    more = {**payload["more"], "other": payload["more"]["tail"]}
    for wrong in (fewer, more):
        assert _make(kind).install_prefix(dict(payload, more=wrong)) == 0
    assert _make(kind).install_prefix(payload) == MARK // BS
    # a frame of latent rows and a state goes through the codec, and into
    # no cache whose rows are a K and a V pool (nor the other way round)
    assert (payload["v_pool"] is None) == cache.latent
    decoded = KVBlockCodec.decode(KVBlockCodec.encode(payload))
    assert decoded["kind"] == "state" and set(decoded["more"]) == set(names)
    other = "state" if cache.latent else "state:latent_pool"
    assert _make(other).install_prefix(decoded) == 0


def test_what_the_cache_cannot_do_is_asked_of_it_once():
    """One question before speculative decoding, one before a spill tier:
    the layout and each part answer with their reason."""
    from ray_tpu.inference import InferenceEngine
    src = inspect.getsource(InferenceEngine)
    assert src.count("speculative decoding over") == 1
    assert src.count("self.cache.no_rollback") == 2
    assert not re.findall(r"self\.cache\.(window|slide_window|state)\b", src)
    for kind in ("kv", "latent"):
        assert _make(kind).no_rollback is None is _make(kind).no_tier
    for kind, word in (("windowed", "window"), ("layered", "sliding"),
                       ("state", "rolled back")):
        cache = _make(kind)
        assert word in cache.no_rollback
        cache.alloc_lane(0, 9)
        with pytest.raises(NotImplementedError, match=word):
            cache.truncate_lane(0, 4)
    for kind in ("layered", "state"):
        with pytest.raises(NotImplementedError, match="spill"):
            _make(kind).attach_tier(object())


def test_the_names_benchmark_tools_hold_the_program_to():
    """What benchmark/tools/aot_*_sizes.py reach inside functions (so that
    importing the tools proves nothing), kept working until the `benchmark`
    issue of ROADMAP.md D18 has freed the names: this test goes then."""
    from ray_tpu.inference.kv_cache import (  # noqa: F401
        PagedKVCache, count_pool_copies, count_weight_bytes_copied)
    from ray_tpu.inference import InferenceEngine, compiled
    assert count_pool_copies is compiled.count_pool_copies
    assert count_weight_bytes_copied is compiled.count_weight_bytes_copied
    # aot_evabyte_sizes.py:73: a one-block cache of the cell's geometry
    book = PagedKVCache(1, 4, 16, num_blocks=1, block_size=16, max_lanes=1,
                        max_seq_len=512, window=256, chunk=16)
    assert book.window == 256 and book.max_blocks_per_seq == 16 + 1
    assert (book._win_blocks, book._sum_blocks) == (16, 1)
    for name in ("_make_step_fn", "_make_compact_fn"):
        assert hasattr(InferenceEngine, name), name
    for name in ("self._step_impls", "self._capture_logp"):
        assert name in inspect.getsource(InferenceEngine.__init__), name
    # aot_falconh1_sizes.py:85, aot_dots3_sizes.py:81
    state = _make("state")
    assert state.snaps.shape[1] == 3 == state.snap_tails.shape[1]
    assert len(state.step_pools[0]) == 4 and state.step_pools[1] is None
    layered = _make("layered")
    assert len(layered.k) == 3
    assert layered.block_tables.shape == (3, 2 * layered.max_blocks_per_seq)
