"""Paged KV cache: fixed-size blocks in a preallocated device pool.

The pool is one buffer per K and V for the engine's lifetime (no per-request
HBM churn), stored as [n_layers, num_blocks, block_size, W]: a token's K (or
V) of every kv head is one row of W = kv_heads * head_dim columns rounded up
to a multiple of 128, the shape whose device layout is the row-major one the
paged kernel's DMA reads (pad columns are zero and never read into a result;
ops/attention.py).  Each live sequence owns an ordered list of block ids;
the per-lane block tables map logical context positions onto pool blocks, so
sequences of wildly different lengths pack the same pool with at most
block_size - 1 wasted slots each (the vLLM memory model).  Allocation and
free are host-side refcount operations; the device arrays are functional:
the jitted step returns updated pools and the cache rebinds them.  On TPU
they are donated, and the step writes only the blocks its new tokens fall in
and reads only the blocks it attends over, in that one buffer
(`compiled.count_pool_copies` over the compiled step must be 0).  Outside
the engine a block keeps the wire format [n_layers, n, block_size, kv_heads,
head_dim] (export/install, the tier spill, serve/kv_tier/codec.py):
`read_blocks` / `write_blocks` convert at the boundary.

ONE MANAGER, WHOSE KINDS ARE PARTS.  `PagedKVCache` writes a lane's life
once (admit, match, adopt, grow, seal, evict, export, install, truncate,
free) and asks three helpers what differs between the caches `for_model`
makes; none of its shared methods tests which kind it serves
(tests/test_cache_parts.py holds the source to that).

* `SealedIndex`: a refcounted `BlockAllocator` and the content-keyed index
  over it.  The growing blocks have one (`PagedKVCache.index`), and so has
  each part that keeps sealed content of its own.
* The LAYOUT of a lane's table (`PagedKVCache.layout`): `RowATokenLayout`,
  or `SawtoothLayout` (`kind` "windowed", EVA: the open window's exact rows
  behind the closed windows' summaries).  It owns how many blocks a lane of
  n tokens needs and holds at its peak, the windows each lane has closed,
  the summaries-first walk of a match and the summary entries of the wire
  format.
* PARTS (`PagedKVCache.parts`, a `CachePart` each; none for "kv", "latent"
  and "windowed"): what a lane keeps BESIDE its chain of growing blocks.
  `SlidingRows` (`kind` "layered", dots3's window layers beside its full
  ones) and `LaneState` (`kind` "state", a state-space mixer's state:
  beside the attention heads in every layer, or in layers of its own with
  their own count).  The manager loops over them at each point
  of a lane's life: which head of a matched chain the part can serve too,
  whether it admits, adopt, grow, seal, release after a commit, free, its
  share of the wire format's `more`, its buffers beside the pools, and why
  a lane cannot be rolled back or spilled.

What a ROW holds is the pools' business: K and V rows in a pool each (`kind`
"kv"), or ONE pool whose row is a token's latent and the rotated key its
heads share (`kind` "latent": kv_heads 1, `v` None; 576 numbers a token a
layer where 64 heads of K and V would be 16,384), with further pools on the
same blocks and table for the other rows a layer leaves a token (`extra`: an
indexer's key; `k` is then the tuple of all pools as the model's runs index
them, a sliding part's last).  `kind` is a read-only word for the wire
format (a cache installs only its own), `KVBlockCodec` and `stats()`.

Prefix caching (content-addressed block sharing): a block that has been
completely written ("sealed") is indexed by a hash chain over (parent_hash,
block_tokens).  The chain hash of a block is a function of every token up to
and including its own, and K/V at a position depend on exactly that prefix,
so sequences whose prefixes agree block-for-block may share the physical
blocks.  Sealed blocks are immutable (writes land past the sealed boundary,
in each lane's private tail), so copy-on-write comes for free.  The write
path relies on that tail being private: the blocks the valid rows of one
step land in (`ensure_capacity`'s, a sliding part's slots) are held by one
lane each, so the TPU's write kernel (ops/paged_write.py) may have every
lane's copy in flight at once (`tests/test_paged_write.py` holds the
engine's programs to it).  When a sequence finishes, its sealed blocks stay
in the index at refcount 0 on an LRU list and are evicted only when the
allocator needs the space; a new request reuses the longest block-aligned
cached prefix instead of re-prefilling it.
"""

from __future__ import annotations

import collections
import itertools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The compiled-program checks live in compiled.py; benchmark/tools/aot_*.py
# import these two names from here (ROADMAP.md D18).
from ray_tpu.inference.compiled import (  # noqa: F401
    count_pool_copies, count_weight_bytes_copied)
from ray_tpu.ops.attention import (kv_row_width, pack_kv_rows,
                                   unpack_kv_rows)
from ray_tpu.ops.ssm import copy_slot, state_shape

# Root of every hash chain (a block with no parent).
_ROOT_HASH = 0


def _iter_chain_keys(tokens: Sequence[int], block_size: int):
    parent = _ROOT_HASH
    for i in range((len(tokens) - 1) // block_size):
        key = (parent, tuple(map(int, tokens[i * block_size:
                                             (i + 1) * block_size])))
        yield key
        parent = hash(key)


def chain_keys(tokens: Sequence[int], block_size: int) -> List[Tuple]:
    """The prefix index's key of every block-aligned prefix of `tokens`:
    `(parent, block_tokens)` with `parent` the hash of the key before
    (root 0), and the same one-token-left cap as match_prefix.  Whoever has
    a prompt's keys (the engine makes them in `submit`, on the caller's
    thread) hands them to `can_admit_prefix`, `adopt_prefix` and
    `match_prefix`, which then do not walk the prompt again (three walks of
    a 16k-token document were 7.7 ms of an admitting iteration: PR 32)."""
    return list(_iter_chain_keys(tokens, block_size))


def _summary_key(last_key: Tuple, part: int) -> Tuple:
    """The index key of block `part` of a closed window's summary rows:
    under the chain hash of the window's last token block, so a function of
    every token up to the window's end, like the rows."""
    return (hash(last_key), ("summary", part))


def _is_summary(key: Tuple) -> bool:
    return key[1][:1] == ("summary",)


def _keys(entries: Sequence[Tuple]) -> List[Tuple]:
    """The chain keys of a matched chain's (where, key, block) entries."""
    return [key for _at, key, _block in entries]


def _chain_cursor(key: Tuple) -> int:
    """The parent hash of whatever follows the block indexed under `key`."""
    return key[0] if _is_summary(key) else hash(key)


def chain_hashes(tokens: Sequence[int], block_size: int) -> List[int]:
    """Cumulative chain hash of every block-aligned prefix of `tokens`, in
    the prefix index's own convention (`hash((parent, block_tokens))`, root
    0; match_prefix's one-token-left cap).  Tuple-of-int hashing is
    deterministic across processes (PYTHONHASHSEED randomizes str/bytes
    only), so a router scores replica summaries without shipping tokens."""
    return [hash(key) for key in chain_keys(tokens, block_size)]



class BlockAllocator:
    """Refcounted free-list over pool block ids.

    Three states per block: free (no content), live (refcount >= 1) and
    evictable (refcount 0 but still holding indexed cached content:
    reusable without recompute, reclaimable under pressure).  `num_free`
    counts free + evictable, both available capacity: admission control is
    built on can_alloc.  No implicit growth: exhaustion raises."""

    def __init__(self, num_blocks: int,
                 on_evict: Optional[Callable[[int], None]] = None):
        if num_blocks < 1:
            raise ValueError("need at least one block")
        self.num_blocks = num_blocks
        # LIFO: recently-freed blocks are re-used first (their pool slots
        # are warm in HBM caches on real hardware).
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref = [0] * num_blocks
        self._cached = [False] * num_blocks   # block holds indexed content
        # refcount-0 cached blocks, insertion order = LRU eviction order.
        self._evictable: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self.on_evict = on_evict
        self.evictions = 0

    @property
    def num_free(self) -> int:
        return len(self._free) + len(self._evictable)

    @property
    def num_unused(self) -> int:
        """Blocks that hold nothing (free, not merely evictable)."""
        return len(self._free)

    def can_alloc(self, n: int) -> bool:
        return n <= self.num_free

    def alloc(self, n: int = 1) -> List[int]:
        if n > self.num_free:
            raise RuntimeError(
                f"KV pool exhausted: want {n} blocks, {self.num_free} free")
        out = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                # Reclaim the least-recently-used cached block; the index
                # owner drops its entry via the eviction hook.
                b, _ = self._evictable.popitem(last=False)
                self._cached[b] = False
                self.evictions += 1
                if self.on_evict is not None:
                    self.on_evict(b)
            self._ref[b] = 1
            out.append(b)
        return out

    def incref(self, block: int) -> None:
        """Take a share of a cached block (prefix reuse)."""
        if self._ref[block] == 0:
            if block not in self._evictable:
                raise ValueError(f"incref of free block {block}")
            del self._evictable[block]
        self._ref[block] += 1

    def decref(self, block: int) -> None:
        """Drop one share.  At refcount 0 an indexed block parks on the
        LRU evictable list (content stays reusable); anything else goes
        straight back to the free list."""
        if self._ref[block] <= 0:
            raise ValueError(f"double free of block {block}")
        self._ref[block] -= 1
        if self._ref[block] == 0:
            if self._cached[block]:
                self._evictable[block] = None    # most-recently-used end
            else:
                self._free.append(block)

    def free(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            self.decref(b)

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def is_evictable(self, block: int) -> bool:
        return block in self._evictable

    def mark_cached(self, block: int) -> None:
        """The prefix index now references this block's content."""
        self._cached[block] = True

    def uncache(self, block: int) -> None:
        """The prefix index dropped this block; if it was parked
        evictable it becomes plain free."""
        self._cached[block] = False
        if block in self._evictable:
            del self._evictable[block]
            self._free.append(block)


class SealedIndex:
    """A refcounted allocator and the content-keyed index over it: sealed
    content is looked up by key, stays at refcount 0 as evictable, and
    leaves the index when the allocator takes its block (`on_drop(key,
    block)` tells the owner, the content still in place)."""

    def __init__(self, num_blocks: int,
                 on_drop: Optional[Callable[[Tuple, int], None]] = None):
        self.allocator = BlockAllocator(num_blocks, on_evict=self.evicted)
        # key -> block.  Keys compare by equality, so within one chain
        # level collisions are impossible; the int parent hash aliasing two
        # distinct prefixes is the usual 64-bit-hash-chain gamble (vLLM
        # makes the same one).
        self._block: Dict[Tuple, int] = {}
        self._key: Dict[int, Tuple] = {}
        self.on_drop = on_drop

    def __len__(self) -> int:
        return len(self._block)

    def __contains__(self, key: Tuple) -> bool:
        return key in self._block

    def get(self, key: Tuple) -> Optional[int]:
        return self._block.get(key)

    def items(self):
        """(block, key) of everything sealed, oldest seal first."""
        return self._key.items()

    def seal(self, key: Tuple, block: int) -> bool:
        """Index `block`'s content under `key`.  First writer wins: where
        an identical block is indexed this one stays un-indexed freight
        (freed normally later); a shared block re-seals as itself (False)."""
        if key in self._block or block in self._key:
            return False
        self._block[key] = block
        self._key[block] = key
        self.allocator.mark_cached(block)
        return True

    def evicted(self, block: int) -> None:
        """The allocator reclaimed `block`: drop its entry.  Children of an
        evicted chain node stay indexed, unreachable until an identical
        parent is sealed again, and then valid by construction."""
        key = self._key.pop(block, None)
        if key is not None and self._block.get(key) == block:
            del self._block[key]
            if self.on_drop is not None:
                self.on_drop(key, block)

    def discard(self, key: Tuple) -> None:
        """Forget `key`'s content; its block, if parked evictable, is free."""
        block = self._block.pop(key, None)
        if block is not None:
            del self._key[block]
            self.allocator.uncache(block)

    def install(self, wanted: Sequence[Tuple[int, Tuple]], write) -> int:
        """Foreign content into fresh blocks, indexed at refcount 0: a block
        for each (pos, key) of `wanted` not held; `write(idx, pos)` stores
        the payload's entries `pos` at blocks `idx` (numpy), once.  May
        evict least recently used content (new beats old) but never steals
        live capacity: it stops at the first block that cannot be had.
        Blocks stay at refcount 1 until all are allocated, so a later one
        never reclaims an earlier one of the same import."""
        new = []
        for pos, key in wanted:
            if key in self._block:
                continue
            try:
                (block,) = self.allocator.alloc(1)
            except RuntimeError:
                break
            new.append((pos, key, block))
        if new:
            write(np.asarray([b for *_, b in new], np.int32),
                  np.asarray([pos for pos, *_ in new]))
        for _pos, key, block in new:
            self.seal(key, block)
            self.allocator.decref(block)
        return len(new)


class RowATokenLayout:
    """A lane's table where it holds one row a token: entry i is token
    block i.  These are the questions `PagedKVCache` asks a layout."""

    kind = "kv"
    closes = False              # a lane's table is re-laid as it grows (`due`)
    window = chunk = win_blocks = sum_blocks = shrink = 0
    no_rollback: Optional[str] = None

    def __init__(self, block_size: int, max_seq_len: int, max_lanes: int):
        self.block_size, self.max_seq_len = block_size, max_seq_len
        # Windows closed per lane: the first `sum_blocks` x closed entries
        # of a lane's blocks are summaries, and token block i sits at entry
        # i - closed x `shrink` (all 0 here).
        self.closed = [0] * max_lanes

    def blocks_needed(self, seq_len: int) -> int:
        """Table slots of a lane that holds `seq_len` tokens."""
        return math.ceil(max(seq_len, 1) / self.block_size)

    def rows_held(self, seq_len: int) -> int:
        """Rows a lane of `seq_len` tokens attends over."""
        return seq_len

    def peak_blocks(self, final_len: int, closed: int = 0) -> int:
        """The most blocks a lane owns on its way to `final_len` tokens
        with `closed` windows already closed: what admission reserves."""
        return self.blocks_needed(final_len)

    @property
    def max_blocks(self) -> int:
        """The most table slots a lane fills."""
        return self.blocks_needed(self.max_seq_len)

    def room(self, start: int) -> int:
        """Positions from `start` that one slice may write."""
        return self.max_seq_len

    def to_start(self, prompt_len: int, closed: int = 0) -> int:
        """Blocks a lane starts with, `closed` windows adopted."""
        return self.blocks_needed(prompt_len)

    def due(self, lane: int, start: int) -> bool:
        """Whether the table is re-laid before `start` is written."""
        return False

    def check_open(self, lane: int, new_len: int) -> None:
        """Raise where the table cannot grow to `new_len` as it is."""

    def heads(self, tokens, keys, held) -> Tuple[List[Tuple], Sequence]:
        """(entries that stand for whole closed windows, the chain keys of
        the token blocks to look up behind them) of a prompt's walk through
        the index; `held(key)` is an entry or None."""
        return [], (_iter_chain_keys(tokens, self.block_size)
                    if keys is None else keys)

    def matched(self, entries: List[Tuple]) -> Tuple[int, int]:
        """(windows closed, tokens covered) of a matched chain."""
        return 0, len(entries) * self.block_size

    def whole(self, entries: List[Tuple], usable: int) -> int:
        """`usable` entries of a chain, cut to what a lane can start from."""
        return usable

    def wire_chain(self, keys: List[Tuple], tokens) -> List[List[int]]:
        """The wire format's chain entry of every matched block."""
        return [list(key[1]) for key in keys]

    def stats(self, cache) -> dict:
        return {}

    def wire_keys(self, chain) -> List[Tuple]:
        """The index keys of a wire format's chain entries, from the root."""
        keys, parent = [], _ROOT_HASH
        for blk_tokens in chain:
            keys.append((parent, tuple(int(t) for t in blk_tokens)))
            parent = hash(keys[-1])
        return keys


class SawtoothLayout(RowATokenLayout):
    """EVA (`window`, `chunk`): a lane holds the exact rows of its open
    window of `window` tokens behind one summary row for every `chunk`
    tokens of each closed one.  Both kinds of row have the same shape and
    live in the same pools under the same allocator.  A lane's table is
    laid out [summary blocks of windows 0..w-1 | the open window's blocks]:
    `win_blocks` exact and `sum_blocks` summary blocks a window, so a lane
    of n tokens needs a sawtooth of blocks and a closed window shortens its
    table by `shrink`.  A full window is closed by
    `PagedKVCache.close_window`.  The prefix index then holds a token
    block's exact rows under its chain key, as ever, and a closed window's
    summary blocks under the chain hash of the window's last token block: a
    prefix of m tokens is the summary blocks of the windows it completes
    plus the exact blocks of the window it ends in, and a closed window's
    exact blocks serve only a match that ends inside it."""

    kind = "windowed"
    closes = True
    no_rollback = "a verify chunk may cross a window's edge (ROADMAP.md)"

    def __init__(self, block_size, max_seq_len, max_lanes, window, chunk):
        super().__init__(block_size, max_seq_len, max_lanes)
        rows = window // max(chunk, 1)
        if chunk < 1 or window % chunk or rows % block_size:
            raise ValueError(
                f"a windowed cache needs a window ({window}) whose summary "
                f"rows (one per {chunk}) fill whole blocks of {block_size}")
        self.window, self.chunk = window, chunk
        self.win_blocks = window // block_size
        self.sum_blocks = rows // block_size
        self.shrink = self.win_blocks - self.sum_blocks

    def blocks_needed(self, seq_len):
        """The summary blocks of the windows before the last token's, and
        the blocks its own window has filled so far."""
        closed, last = divmod(max(seq_len, 1) - 1, self.window)
        return closed * self.sum_blocks + last // self.block_size + 1

    def rows_held(self, seq_len):
        if seq_len < 1:
            return seq_len
        closed, last = divmod(seq_len - 1, self.window)
        return closed * (self.window // self.chunk) + last + 1

    def peak_blocks(self, final_len, closed=0):
        """The close of the last window the lane completes, where the
        window's blocks and the fresh summary blocks are held together for
        a moment, if that is still to come and more than the end needs."""
        at_end = self.blocks_needed(final_len)
        last = (max(final_len, 1) - 1) // self.window
        if last <= closed:
            return at_end
        return max(at_end, (last - 1) * self.sum_blocks + self.win_blocks
                   + self.sum_blocks)

    @property
    def max_blocks(self):
        """At the lane's end, or at the close of its last whole window."""
        return max(self.blocks_needed(self.max_seq_len), self.blocks_needed(
            (self.max_seq_len - 1) // self.window * self.window))

    def room(self, start):
        """To the end of `start`'s window: a slice never crosses one."""
        return self.window - start % self.window

    def to_start(self, prompt_len, closed=0):
        """The adopted windows' summaries and the prompt's part of the next
        window (the rest come as windows close)."""
        return closed * self.sum_blocks + math.ceil(
            min(prompt_len - closed * self.window, self.window)
            / self.block_size)

    def due(self, lane, start):
        """`start` opens a window and the one before is still open."""
        return start > 0 and start % self.window == 0 \
            and self.closed[lane] < start // self.window

    def check_open(self, lane, new_len):
        if (new_len - 1) // self.window > self.closed[lane]:
            raise RuntimeError(f"lane {lane}: window not closed")

    def heads(self, tokens, keys, held):
        if keys is None:
            keys = chain_keys(tokens, self.block_size)
        out, per, done = [], self.win_blocks, 0
        while (done + 1) * per <= len(keys):
            parts = [held(_summary_key(keys[(done + 1) * per - 1], s))
                     for s in range(self.sum_blocks)]
            if None in parts:
                break
            out += parts
            done += 1
        return out, keys[done * per:(done + 1) * per]

    def matched(self, entries):
        summaries = sum(_is_summary(key) for _at, key, _b in entries)
        closed = summaries // self.sum_blocks
        return closed, (closed * self.window
                        + (len(entries) - summaries) * self.block_size)

    def whole(self, entries, usable):
        """A summary block missing takes its window's others with it."""
        if usable < len(entries) and _is_summary(entries[usable][1]):
            usable -= usable % self.sum_blocks
        return usable

    def wire_chain(self, keys, tokens):
        """The kind of each block by the length of its chain entry: a
        summary block carries its whole window's tokens (every block of one
        window the same), an exact block its own."""
        chain = super().wire_chain(keys, tokens)
        per = self.sum_blocks
        for i in range(0, sum(map(_is_summary, keys)), per):
            at = i // per * self.window
            chain[i:i + per] = [list(map(int, tokens[
                at:at + self.window]))] * per
        return chain

    def stats(self, cache):
        """Windows closed so far and the pool's blocks by kind now (engine
        `stats()["eva"]`)."""
        summary, exact = cache.blocks_by_kind()
        return {"compactions": cache.stats["windows_closed"],
                "summary_blocks": summary, "window_blocks": exact}

    def wire_keys(self, chain):
        keys, parent, part = [], _ROOT_HASH, 0
        bs = self.block_size
        for blk_tokens in chain:
            if len(blk_tokens) == self.window:
                # A summary block: the chain runs through its window's
                # token blocks once, at the window's first part.
                if part == 0:
                    for j in range(0, self.window, bs):
                        parent = hash((parent, tuple(
                            int(t) for t in blk_tokens[j:j + bs])))
                keys.append((parent, ("summary", part)))
                part = (part + 1) % self.sum_blocks
            else:
                keys.append((parent, tuple(int(t) for t in blk_tokens)))
            parent = _chain_cursor(keys[-1])
        return keys


class CachePart:
    """What a lane keeps beside its chain of growing blocks, as
    `PagedKVCache` calls it at each point of a lane's life (`keys`: chain
    keys of token blocks, the first block's first).  This one keeps nothing.
    `adopt(lane, n_tokens, keys=None)`: a lane starts with a prompt of
    `n_tokens`, its first blocks those of `keys` (None: no match was looked
    for); `grow(lane, new_len)`: it is about to be written up to `new_len`
    tokens; `seal(lane, i, key)`: its token block `i` is full; `release(
    lane)`: a step's tokens were committed to it; `free(lane)`: it ends;
    `dropped(key)`: the growing block sealed under `key` was evicted;
    `rebind(buffers)`: the `buffers` a step returned."""

    kind = ""                   # the wire format's word for a cache with it
    wire: Tuple[str, ...] = ()  # its entries of the wire format's `more`
    # Why a lane cannot be truncated past what a rejected draft wrote, and
    # why no spill tier is attached (None: it can).
    no_rollback: Optional[str] = None
    no_tier: Optional[str] = None
    # What the engine's loop need not call it for: `release`, `checkpoint`.
    releases = checkpoints = False
    index: Optional[SealedIndex] = None
    buffers: tuple = ()         # what a step takes and returns beside pools

    def _nothing(self, *args) -> None:
        return None

    adopt = grow = seal = release = free = dropped = rebind = _nothing

    def serves(self, keys: List[Tuple]) -> int:
        """The blocks of a matched chain, from its first, it can serve."""
        return len(keys)

    def admits(self, keys: Sequence[Tuple]) -> bool:
        """Room for one more lane that starts from the matched `keys`."""
        return True

    def checkpoint(self, lane: int, key: Tuple) -> bool:
        """Keep what the lane holds now (once the programs dispatched so
        far have run) as standing behind the block of `key`."""
        return False

    def wanted(self, lane: int) -> int:
        """The blocks of the lane's prompt behind which a `checkpoint` is
        wanted (0: nowhere that the part knows of)."""
        return 0

    def export(self, keys: List[Tuple]) -> dict:
        """Its share of the wire format's `more` for a chain of `keys`."""
        return {}

    def install(self, more: dict, keys: List[Tuple]) -> Optional[int]:
        """Index its share of a payload: the blocks that installed, or None
        where the payload's blocks would serve nobody without it."""
        return 0

    def stats(self, cache) -> dict:
        return {}


class SlidingRows(CachePart):
    """A row a token of which only a lane's last `window` positions are
    ever read again: its pools (`pools` of them on the same blocks: one of
    latent rows, or a K and a V pool), a `SealedIndex` and a second table
    (the second half of `block_tables`' columns, a slot a token block) of
    its own.  A
    block wholly behind a lane's window goes back in mid-sequence
    (`release`, after every commit), staying indexed as evictable if it was
    sealed; a token block is sealed here and in the growing kind under the
    same chain key; a match of m blocks is served only where the blocks that
    cover its last `window` - 1 positions are held here too (`serves`), and
    admission counts the part at its own peak (`admits`)."""

    kind = "layered"
    wire = ("slide_from", "slide")
    releases = True
    no_rollback = "a rejected draft's sliding blocks are not rolled back yet"
    no_tier = ("a spilled block would have to carry every kind's rows "
               "(ROADMAP.md)")

    def __init__(self, cache: "PagedKVCache", n_layers: int, kv_heads: int,
                 head_dim: int, window: int, ahead: int,
                 num_blocks: Optional[int] = None, pools: int = 1):
        self.cache, self.window = cache, window
        # Positions a lane may be written past its committed length: the
        # engine's longest slice, twice (one step runs ahead of the last
        # commit); a decoding lane's are 2.
        self._ahead = max(int(ahead), 2)
        if num_blocks is None:
            num_blocks = cache.max_lanes * self.peak(True)
        self.index = SealedIndex(num_blocks)
        self.pools = tuple(cache._claim_pool(n_layers, num_blocks, kv_heads,
                                             head_dim)
                           for _ in range(pools))
        self.column = cache._claim_table()
        # lane -> {slot: block}, and its prompt's length (0: no lane)
        self._lane: List[Dict[int, int]] = [
            {} for _ in range(cache.max_lanes)]
        self._prompt = [0] * cache.max_lanes
        cache.stats["slide_blocks_freed"] = 0

    def _from(self, length: int) -> int:
        """The first table slot a lane of `length` tokens still reads:
        position `length`, the next written, attends from
        `length - (window - 1)` on."""
        return max(length - (self.window - 1), 0) // self.cache.block_size

    def peak(self, prefilling: bool) -> int:
        """The most blocks a lane holds at once: its window and the
        positions written past its committed length, wherever the two fall
        in their blocks."""
        ahead = self._ahead if prefilling else 2
        return (self.window + ahead - 2) // self.cache.block_size + 2

    def held(self, lane: int) -> Dict[int, int]:
        """{table slot: block} of the blocks the lane holds."""
        return dict(self._lane[lane])

    def _tail(self, n: int) -> range:
        """The slots of a matched chain of `n` blocks a lane reads here:
        those that cover the last `window` - 1 positions before its end,
        and nothing of what lies behind them."""
        return range(self._from(n * self.cache.block_size), n)

    def _set(self, lane: int, slot: int, block: int) -> None:
        self.cache.block_tables[lane, self.column + slot] = block
        self.cache._dev_tables = None

    def serves(self, keys):
        for m in range(len(keys), 0, -1):
            if all(keys[i] in self.index for i in self._tail(m)):
                return m
        return 0

    def admits(self, keys):
        """Its own peak less the matched tail it shares, beside what every
        live lane may still claim."""
        tail = [self.index.get(keys[i]) for i in self._tail(len(keys))]
        reserve = sum(
            max(0, self.peak(int(self.cache.seq_lens[lane]) < prompt)
                - len(self._lane[lane]))
            for lane, prompt in enumerate(self._prompt) if prompt)
        alloc = self.index.allocator
        return (self.peak(True) - len(tail) + reserve
                <= alloc.num_free - sum(alloc.is_evictable(b) for b in tail))

    def adopt(self, lane, n_tokens, keys=None):
        """The matched tail, shared like the rest; what the prompt adds is
        claimed as it is written (`grow`)."""
        self._prompt[lane] = n_tokens
        for slot in self._tail(len(keys or ())):
            block = self._lane[lane][slot] = self.index.get(keys[slot])
            self.index.allocator.incref(block)
            self._set(lane, slot, block)

    def grow(self, lane, new_len):
        held = self._lane[lane]
        for slot in range(self._from(int(self.cache.seq_lens[lane])),
                          -(-new_len // self.cache.block_size)):
            if slot not in held:
                (held[slot],) = self.index.allocator.alloc(1)
                self._set(lane, slot, held[slot])

    def seal(self, lane, i, key):
        """The same token block here, if the lane still holds it."""
        block = self._lane[lane].get(i)
        if block is not None:
            self.index.seal(key, block)

    def release(self, lane):
        """Give back the blocks that lie wholly behind the lane's window at
        its committed length; a sealed one stays indexed as evictable.
        What a step in flight reads lies at or past the committed length's
        window, and whoever is given a block next writes it by a program
        dispatched later."""
        held = self._lane[lane]
        keep = self._from(int(self.cache.seq_lens[lane]))
        gone = [slot for slot in held if slot < keep]
        for slot in gone:
            self.index.allocator.decref(held.pop(slot))
            self._set(lane, slot, 0)
        self.cache.stats["slide_blocks_freed"] += len(gone)

    def free(self, lane):
        self.index.allocator.free(self._lane[lane].values())
        self._lane[lane] = {}
        self._prompt[lane] = 0

    def stats(self, cache):
        """Blocks given back in mid-sequence so far, and the blocks each
        kind holds now, live or cached, with their bytes over all layers
        and pools (engine `stats()["windows"]`)."""
        own, grown = self.index.allocator, cache.allocator
        return {"blocks_freed": cache.stats["slide_blocks_freed"],
                "sliding_blocks": own.num_blocks - own.num_unused,
                "growing_blocks": grown.num_blocks - grown.num_unused,
                "sliding_bytes": sum(cache.pool_bytes(i)
                                     for i in self.pools),
                "growing_bytes": sum(cache.pool_bytes(i)
                                     for i in range(self.pools[0]))}

    def export(self, keys):
        """The blocks from chain position `slide_from` on, which is all a
        match of this length reads of them: one pool's rows, or a list of
        every pool's."""
        tail = self._tail(len(keys))
        idx = jnp.asarray(np.asarray([self.index.get(keys[i]) for i in tail],
                                     np.int32))
        rows = [self.cache.read_blocks(idx, pool) for pool in self.pools]
        return {"slide_from": tail.start,
                "slide": rows[0] if len(rows) == 1 else rows}

    def install(self, more, keys):
        first = int(more["slide_from"])
        rows = more["slide"] if len(self.pools) > 1 else [more["slide"]]

        def write(idx, pos):
            for pool, blocks in zip(self.pools, rows):
                self.cache.write_blocks(jnp.asarray(idx), blocks[:, pos],
                                        None, pool)

        return self.index.install(list(enumerate(keys[first:])), write)


class LaneState(CachePart):
    """State that is not rows: what a layer's mixer keeps of a lane
    (`rows`: `decoder.StateRows`), of a fixed size whatever the lane's
    length.  One SLOT a lane a layer in THE BUFFERS THE MIXER STATES
    (`slot_buffers`), beside the K and V pools, over its OWN `n_layers`
    (the model's layers that have the mixer, which need not be those that
    have K and V rows): where the mixer has a recurrence, `state`
    [n_layers, max_lanes + 1, heads, d_state, head_dim] float32 (narrow
    heads folded: `ops.ssm.state_shape`); always `tail` [n_layers,
    max_lanes + 1, rows x width], its convolution's last rows (the pools
    may be ONE latent pool as well: the chain of blocks that snapshots are
    keyed by is then a chain of latent rows).  A gated
    short convolution keeps the tail alone: one buffer, no other.  A
    lane's slot is its index, the last slot is where a program sends the
    rows nobody has; every step overwrites them: `step_pools` hands the
    step the pools and these as one tuple (ops/ssm.py;
    `count_pool_copies` of the largest's shape must be 0 too).  A prefix
    of sealed K/V blocks is worthless without the state at its end, so
    the prefix index holds SNAPSHOTS beside its blocks: a `SealedIndex`
    of snapshot slots (`snap_buffers`, a buffer for each of the lanes')
    under the chain key of the block a snapshot stands behind, evicted
    least recently used like blocks and dropped with the block of their
    key.  The engine says when one is taken (`checkpoint`, behind the
    prefill step that left the state there); a match is served only up to
    a block a snapshot stands behind (`serves`), and adopting it copies
    the snapshot into the lane's slot ahead of the lane's first step
    (`adopt`).  Blocks that matched PAST the last snapshot are what the
    index has seen shared and cannot serve: the lane that prefills them
    again is asked for a snapshot behind the last of them (`wanted`), so a
    shared head gets its snapshot where it ends, wherever the requests'
    own ends lie.  The wire format carries a snapshot under the buffers'
    names (`wire`), and a cache installs only one of its own names and
    shapes."""

    kind = "state"
    no_rollback = ("the lanes' state has been overwritten past the new "
                   "length and cannot be rolled back")
    no_tier = ("a spilled chain would have to carry its snapshots "
               "(ROADMAP.md)")
    checkpoints = True

    def __init__(self, cache: "PagedKVCache", n_layers: int, rows, dtype,
                 snapshots: Optional[int] = None):
        self.cache = cache
        if snapshots is None:
            snapshots = max(2, cache.max_lanes // 4)
        self.slots = int(snapshots) if cache.prefix_cache_enabled else 0
        # (name on the wire, a slot's shape, dtype) of each buffer the
        # mixer states.  (A tail's K - 1 rows one behind the other in ONE
        # row of its slot: as [slots, 3, width] the compiler lays the
        # three rows out one way for a program of all lanes and another
        # for a program of one, and re-lays the buffer at a program's two
        # ends: 5% of a decode step, PERF.md section 6, PR 43.)
        stated = [("tail", ((rows.conv - 1) * rows.conv_width,), dtype)]
        if rows.heads:
            stated.insert(0, ("state", state_shape(
                rows.heads, rows.d_state, rows.head_dim, rows.groups),
                jnp.float32))
        self.wire = tuple(name for name, _, _ in stated)
        lanes, snaps = cache.max_lanes + 1, max(self.slots, 1)
        self.slot_buffers = tuple(
            jnp.zeros((n_layers, lanes) + one, dt) for _, one, dt in stated)
        self.snap_buffers = tuple(
            jnp.zeros((n_layers, snaps) + one, dt) for _, one, dt in stated)
        self.index = SealedIndex(snaps)
        # blocks the last match found past the last snapshot it could serve
        self.beyond = 0
        # lane -> the blocks of its prompt a snapshot is wanted behind
        self._wanted = [0] * cache.max_lanes
        cache.stats.update(snapshots_taken=0, snapshots_adopted=0,
                           snapshot_misses=0)
        donate = () if jax.default_backend() == "cpu" else (0,)
        self._copy_slot = jax.jit(copy_slot, donate_argnums=donate)
        # Both directions made now, by a copy to nowhere: neither is
        # made under a request that waits.
        self._move(-1, 0, take=True)
        self._move(0, -1, take=False)

    @property
    def buffers(self) -> tuple:
        return self.slot_buffers

    def rebind(self, buffers):
        self.slot_buffers = tuple(buffers)

    def _named(self, buffers: tuple, name: str):
        if name not in self.wire:
            raise AttributeError(f"this mixer states no {name!r} buffer")
        return buffers[self.wire.index(name)]

    # The buffers by their names, where the mixer states them.
    state = property(lambda self: self._named(self.slot_buffers, "state"))
    tail = property(lambda self: self._named(self.slot_buffers, "tail"))
    snaps = property(lambda self: self._named(self.snap_buffers, "state"))
    snap_tails = property(
        lambda self: self._named(self.snap_buffers, "tail"))

    def _move(self, slot: int, lane: int, take: bool) -> None:
        """Dispatch the copy of `lane`'s slot into snapshot slot `slot`
        (`take`) or the other way round, every layer of every buffer.  The
        device runs programs in dispatch order: a snapshot is taken behind
        the step that left the state in the lane's slot, and adopted ahead
        of the lane's first step."""
        src, dst = jnp.int32(lane if take else slot), jnp.int32(
            slot if take else lane)
        if take:
            self.snap_buffers = self._copy_slot(
                self.snap_buffers, self.slot_buffers, src, dst)
        else:
            self.slot_buffers = self._copy_slot(
                self.slot_buffers, self.snap_buffers, src, dst)

    def serves(self, keys):
        """Up to the last block a snapshot stands behind: blocks past it
        are worth nothing to a lane that cannot start its mixers
        there."""
        held = next((m for m in range(len(keys), 0, -1)
                     if keys[m - 1] in self.index), 0)
        self.beyond = len(keys) - held
        return held

    def adopt(self, lane, n_tokens, keys=None):
        """The snapshot behind the last adopted block into the lane's slot,
        ahead of the lane's first step (and most recently used)."""
        self._wanted[lane] = 0
        if keys is not None and self.beyond:
            self._wanted[lane] = len(keys) + self.beyond
            # blocks, no snapshot
            self.cache.stats["snapshot_misses"] += not keys
        if keys:
            slot = self.index.get(keys[-1])
            self.index.allocator.incref(slot)
            self._move(slot, lane, take=False)
            self.index.allocator.decref(slot)
            self.cache.stats["snapshots_adopted"] += 1

    def wanted(self, lane):
        return self._wanted[lane]

    def dropped(self, key):
        """A snapshot goes with the block it stands behind."""
        self.index.discard(key)

    def _take(self, key: Tuple, write) -> bool:
        """A snapshot slot for `key`, the least recently used unreferenced
        one if none is free, filled by `write(slot)` and indexed at once
        (whoever adopts it reads it by a program dispatched later)."""
        return bool(self.slots) and self.index.install(
            [(0, key)], lambda idx, _pos: write(int(idx[0]))) == 1

    def checkpoint(self, lane, key):
        """False where the index has a snapshot under `key` already, or no
        slot can be had."""
        taken = self._take(key, lambda slot: self._move(slot, lane, True))
        self.cache.stats["snapshots_taken"] += taken
        return taken

    def export(self, keys):
        """The chain ends where a snapshot stands (`serves`): it goes with
        the blocks, a buffer under each of the part's names."""
        slot = self.index.get(keys[-1])
        return {name: np.asarray(snap[:, slot])
                for name, snap in zip(self.wire, self.snap_buffers)}

    def install(self, more, keys):
        """The snapshot a payload carries, behind the last block of its
        chain; None where it does not carry exactly this part's buffers in
        their shapes, or no snapshot slot can be had: blocks behind no
        snapshot serve nobody."""
        if not keys or any(
                name not in more or tuple(more[name].shape)
                != snap.shape[:1] + snap.shape[2:]
                for name, snap in zip(self.wire, self.snap_buffers)):
            return None

        def write(slot):
            self.snap_buffers = tuple(
                snap.at[:, slot].set(jnp.asarray(more[name], snap.dtype))
                for name, snap in zip(self.wire, self.snap_buffers))

        return 0 if keys[-1] in self.index or self._take(keys[-1], write) \
            else None

    def stats(self, cache):
        """Slots of state and of snapshots, and what the index did with the
        latter (engine `stats()["ssm"]`)."""
        cs = cache.stats
        return {
            "state_layers": int(self.slot_buffers[0].shape[0]),
            "state_buffers": len(self.slot_buffers),
            "state_slots": self.cache.max_lanes,
            "state_slots_live": sum(map(bool, self.cache._lane_blocks)),
            "state_bytes": sum(int(b.nbytes) for b in self.slot_buffers),
            "snapshot_slots": self.slots,
            "snapshot_slots_live": len(self.index),
            "snapshot_bytes": sum(int(b.nbytes) for b in self.snap_buffers),
            "snapshots_evicted": self.index.allocator.evictions,
            "snapshots_taken": cs["snapshots_taken"],
            "snapshots_adopted": cs["snapshots_adopted"],
            "snapshot_misses": cs["snapshot_misses"],
        }


class PagedKVCache:
    """Device pools + per-lane block tables for a fixed lane capacity.

    Host state (numpy block tables, sequence lengths, the allocator, the
    prefix index) is mirrored to device lazily: `device_tables()`
    re-uploads only after a host-side mutation, so steady-state decode
    ships two tiny arrays per step at most.
    """

    def __init__(self, n_layers: int, kv_heads: int, head_dim: int, *,
                 num_blocks: int, block_size: int, max_lanes: int,
                 max_seq_len: int, dtype=jnp.float32,
                 prefix_cache: bool = True, latent: bool = False,
                 window: int = 0, chunk: int = 0, _extra=(), _parts=()):
        """By hand, a cache of one kind of row and no part; `for_model`
        makes every cache a model is served from, through here: `_extra`
        the widths of further rows on the growing blocks, `_parts` a maker
        each, called (cache, the next number of a tuple `num_blocks` or
        None).  A tuple `num_blocks` is (growing blocks, a part's own:
        sliding blocks, snapshot slots)."""
        num_blocks, *own = (num_blocks if isinstance(
            num_blocks, (tuple, list)) else (num_blocks,))
        self.block_size, self.max_lanes = block_size, max_lanes
        self.max_seq_len, self.prefix_cache_enabled = max_seq_len, prefix_cache
        self.kv_heads, self.head_dim = kv_heads, head_dim
        if window and (latent or _extra or _parts):
            raise ValueError("a windowed cache: K and V pools, no part")
        self.layout = (SawtoothLayout(block_size, max_seq_len, max_lanes,
                                      window, chunk) if window else
                       RowATokenLayout(block_size, max_seq_len, max_lanes))
        self.max_blocks_per_seq = self.layout.max_blocks
        # The growing blocks' allocator and prefix index; evicted content
        # goes to the parts and the spill tier (`_dropped`).
        self.index = SealedIndex(num_blocks, on_drop=self._dropped)
        self.allocator = self.index.allocator
        self.stats = {"hit_tokens": 0, "miss_tokens": 0, "hits": 0,
                      "misses": 0, "sealed_blocks": 0, "imported_blocks": 0,
                      "restored_blocks": 0, "windows_closed": 0}
        # What a row holds (module docstring): K and V rows in a pool each,
        # or (`latent`) one pool, `v` None.  With `extra` or a part's pool,
        # `k` is the tuple of all pools as the model's runs index them, and
        # `_pool_rows` each pool's (kv_heads, head_dim).
        self.extra = tuple(_extra)
        self._dtype = dtype
        # Every pool, in the order the model's runs index them: the growing
        # kind's rows (`_paired`: a K and a V pool; else one), its further
        # rows (`extra`), then what the parts claim.
        self._pools: list = []
        self._pool_rows: List[Tuple[int, int]] = []
        self._tables = 1
        self._paired = not latent
        for _ in range(1 + self._paired):
            self._claim_pool(n_layers, num_blocks, kv_heads, head_dim)
        for width in self.extra:
            self._claim_pool(n_layers, num_blocks, 1, width)
        own = iter(own)
        self.parts: List[CachePart] = [
            make(self, next(own, None)) for make in _parts]
        self.kind = (["layered"] * bool(self.extra)
                     + [part.kind for part in self.parts]
                     + ["latent"] * latent + [self.layout.kind])[0]
        # Why a lane cannot be rolled back and why no spill tier is attached
        # (None: it can); the wire format's `more` as this cache writes it.
        self.no_rollback = next(filter(None, [self.layout.no_rollback] + [
            part.no_rollback for part in self.parts]), None)
        self.no_tier = next(filter(None, [
            part.no_tier for part in self.parts] + [
                "a spilled block would have to carry its further rows "
                "(ROADMAP.md)"] * bool(self.extra)), None)
        self._wire_more = ("extra",) * bool(self.extra) + tuple(
            name for part in self.parts for name in part.wire)
        # Unused table entries stay 0 — always a valid pool index; the
        # attention mask (positions >= ctx_len) hides whatever lives there.
        # (A part's table is a further `max_blocks_per_seq` columns.)
        self.block_tables = np.zeros(
            (max_lanes, self._tables * self.max_blocks_per_seq), np.int32)
        self.seq_lens = np.zeros((max_lanes,), np.int32)
        self._lane_blocks: List[List[int]] = [[] for _ in range(max_lanes)]
        self._dev_tables: Optional[jax.Array] = None
        # Token blocks sealed (hashed into the chain) per lane, and the
        # chain hash cursor at that boundary.
        self._lane_sealed = [0] * max_lanes
        self._lane_parent = [_ROOT_HASH] * max_lanes
        # Optional spill tier (serve/kv_tier): evicted sealed blocks move
        # there instead of being destroyed, and match / adopt restore them.
        self.tier = None

    # The pools as a step takes them: `k` the first and `v` its V pool or
    # None, or, where the cache has more than that, `k` the tuple of all
    # (`v` None).
    @property
    def k(self):
        return self._pools[0] if len(self._pools) == 1 + self._paired \
            else tuple(self._pools)

    @property
    def v(self):
        return self._pools[1] if len(self._pools) == 2 and self._paired \
            else None

    def pool_bytes(self, pool: int) -> int:
        return int(self._pools[pool].nbytes)

    def _claim_pool(self, n_layers, num_blocks, kv_heads, head_dim) -> int:
        """A further pool in the stored layout (module docstring: rows of W
        columns), while the cache is being made; its number in `k`."""
        self._pools.append(jnp.zeros(
            (n_layers, num_blocks, self.block_size,
             kv_row_width(kv_heads, head_dim)), self._dtype))
        self._pool_rows.append((kv_heads, head_dim))
        return len(self._pools) - 1

    def _claim_table(self) -> int:
        """A further table for a part; its first column."""
        self._tables += 1
        return (self._tables - 1) * self.max_blocks_per_seq

    def attach_tier(self, tier) -> None:
        """Attach a spill tier (duck-typed: contains/put/pop/discard/
        summary_hashes/__len__).  Evictions start spilling immediately;
        match/adopt start seeing spilled chains."""
        if self.no_tier:
            raise NotImplementedError(
                f"no spill tier under this cache: {self.no_tier}")
        self.tier = tier

    @classmethod
    def for_model(cls, model, config, *, ahead: int = 2,
                  **kw) -> "PagedKVCache":
        """Build a cache shaped for an LM family's config (models/): the
        layout and the pools from the rows its spec's attention leaves
        there (`decoder.CacheRows`), a `SlidingRows` part where some of its
        runs keep a sliding window's rows in pools of their own, a
        `LaneState` part where some of its layers have a mixer
        (`decoder.StateRows`), beside the attention or in its place,
        behind a K and a V pool or behind ONE latent pool alike.  The
        pools have as many layers as the runs with an attention count
        (`Run.first`), the state part as many as those with the mixer.
        `ahead`: the positions a lane may be written past its committed
        length."""
        from ray_tpu.models.decoder import cache_kinds
        kw.setdefault("max_seq_len", config.max_seq_len)
        kw.setdefault("dtype", config.dtype)
        spec = model.spec(config)
        n_layers, rows, latent = (config.n_layers, spec.attn.rows(config),
                                  spec.attn.pools == 1)

        def layers_of(runs):
            return max((run.first + run.n_layers for run in runs), default=0)

        parts = []
        if any(run.table for run in spec.runs):
            (rows, n_layers), slid = cache_kinds(spec.runs, config)
            if slid:
                (s, layers), = slid
                parts.append(lambda cache, n: SlidingRows(
                    cache, layers, s.kv_heads, s.head_dim, s.slide, ahead, n,
                    pools=spec.attn.pools))
        elif spec.runs:
            n_layers = layers_of(
                run for run in spec.runs if run.attn is not None)
        mixed = [run for run in spec.runs if run.mixer is not None]
        if mixed:
            if not n_layers or any(run.table for run in mixed):
                raise NotImplementedError(
                    "a state cache: rows of at least one layer with an "
                    "attention (a K and a V pool, or one latent pool) "
                    "beside the mixers' state (a lane's chain of blocks "
                    "is what its snapshots are keyed by)")
            state_layers = layers_of(mixed)
            parts.append(lambda cache, n: LaneState(
                cache, state_layers, mixed[0].mixer.state(config),
                kw["dtype"], n))
        return cls(n_layers, rows.kv_heads, rows.head_dim, latent=latent,
                   window=rows.window, chunk=rows.chunk, _extra=rows.extra,
                   _parts=parts, **kw)

    # ---------------- what the layout answers ----------------

    def rows_held(self, seq_len: int) -> int:
        return self.layout.rows_held(seq_len)

    def lane_peak(self, lane: int, final_len: int) -> int:
        return self.layout.peak_blocks(final_len, self.layout.closed[lane])

    def window_room(self, start: int) -> int:
        return self.layout.room(start)

    def window_due(self, lane: int, start: int) -> bool:
        """Whether the lane must `close_window` before `start` is written."""
        return self.layout.due(lane, start)

    # (`window`; `_win_blocks`, `_sum_blocks` as
    # benchmark/tools/aot_evabyte_sizes.py reads them, `snaps` and
    # `snap_tails` as aot_falconh1_sizes.py does: ROADMAP.md D18)
    window = property(lambda self: self.layout.window)
    _win_blocks = property(lambda self: self.layout.win_blocks)
    _sum_blocks = property(lambda self: self.layout.sum_blocks)
    snaps = property(lambda self: self.parts[-1].snaps)
    snap_tails = property(lambda self: self.parts[-1].snap_tails)
    # One pool of latent rows a kind, no V pool.
    latent = property(lambda self: not self._paired)
    # The growing blocks' index and every part's.
    indexes = property(lambda self: [self.index] + [
        part.index for part in self.parts if part.index is not None])

    # ---------------- host-side lane lifecycle ----------------

    def can_admit(self, prompt_len: int) -> bool:
        return self.allocator.can_alloc(self.layout.peak_blocks(prompt_len)) \
            and all(part.admits(()) for part in self.parts)

    def alloc_lane(self, lane: int, prompt_len: int) -> None:
        """Sequence start without prefix reuse: claim fresh blocks
        covering the prompt."""
        self._check_lane(lane, prompt_len)
        blocks = self.allocator.alloc(self.layout.to_start(prompt_len))
        self._install_lane(lane, blocks, cached_len=0)
        for part in self.parts:
            part.adopt(lane, prompt_len)

    def _check_lane(self, lane: int, prompt_len: int) -> None:
        if self._lane_blocks[lane]:
            raise ValueError(f"lane {lane} already allocated")
        if prompt_len > self.max_seq_len:
            raise ValueError(f"prompt of {prompt_len} exceeds max_seq_len "
                             f"{self.max_seq_len}")

    def _install_lane(self, lane: int, blocks: List[int],
                      cached_len: int, closed: int = 0) -> None:
        self._lane_blocks[lane] = blocks
        self.block_tables[lane, :len(blocks)] = blocks
        self.seq_lens[lane] = cached_len
        self._lane_sealed[lane] = cached_len // self.block_size
        self.layout.closed[lane] = closed
        self._lane_parent[lane] = _ROOT_HASH
        self._dev_tables = None

    # ---------------- prefix cache ----------------

    def match_prefix(self, tokens: Sequence[int],
                     keys: Optional[List[Tuple]] = None) -> List[int]:
        """Longest chain of cached sealed blocks covering a block-aligned
        prefix of `tokens`, capped so at least one prompt token is always
        left to prefill (its logits seed the first sampled token).  Pure
        lookup — takes no references.  Device blocks only; spilled chain
        nodes (see `_match_chain`) do not appear here."""
        return [block for _at, _key, block in self._match_dev(tokens, keys)]

    def _match_dev(self, tokens, keys=None) -> List[Tuple]:
        """The device-resident head of `_match_chain`."""
        return list(itertools.takewhile(
            lambda e: e[0] == "dev", self._match_chain(tokens, keys)))

    def _match_chain(self, tokens: Sequence[int],
                     keys: Optional[List[Tuple]] = None) -> List[Tuple]:
        """Longest cached chain covering a block-aligned prefix of
        `tokens` (`keys`: their `chain_keys`, where the caller has them),
        walking THROUGH the spill tier: ("dev", key, block) for a
        device-resident sealed block, ("tier", key, None) for a spilled one
        (restorable on adopt; a device child behind it is reachable again,
        the chain being content-addressed).  What the layout stands for
        whole windows comes first; every part then cuts the chain to the
        head it can serve a lane with too."""
        if not self.prefix_cache_enabled:
            return []

        def held(key):
            block = self.index.get(key)
            if block is not None:
                return ("dev", key, block)
            if self.tier is not None and self.tier.contains(key):
                return ("tier", key, None)
            return None

        out, keys = self.layout.heads(tokens, keys, held)
        for key in keys:
            entry = held(key)
            if entry is None:
                break
            out.append(entry)
        for part in self.parts:
            out = out[:part.serves(_keys(out))]
        return out

    def match_len(self, tokens: Sequence[int],
                  keys: Optional[List[Tuple]] = None) -> int:
        """Tokens of `tokens` that `match_prefix`'s blocks cover."""
        return self.layout.matched(self._match_dev(tokens, keys))[1]

    def can_admit_prefix(self, tokens: Sequence[int],
                         headroom_blocks: int = 0,
                         keys: Optional[List[Tuple]] = None,
                         final_len: Optional[int] = None) -> bool:
        """Admission check that accounts for reuse: device-matched blocks
        are referenced (not allocated), but those parked evictable stop
        counting as free capacity once taken; spilled matches restore into
        fresh blocks, so they stay inside `need`.  With `final_len` the
        request is counted at the most blocks it owns on its way there
        (`peak_blocks`), not at its prompt; every part at its own peak."""
        entries = self._match_chain(tokens, keys)
        dev = [b for at, _k, b in entries if at == "dev"]
        need = (self.layout.peak_blocks(max(final_len or 0, len(tokens)),
                                        self.layout.matched(entries)[0])
                - len(dev) + headroom_blocks)
        free_after = (self.allocator.num_free
                      - sum(self.allocator.is_evictable(b) for b in dev))
        return need <= free_after and all(
            part.admits(_keys(entries)) for part in self.parts)

    def adopt_prefix(self, lane: int, tokens: Sequence[int],
                     keys: Optional[List[Tuple]] = None) -> int:
        """Sequence start with prefix reuse: take shares of the longest
        cached prefix chain (restoring any spilled links from the tier),
        allocate fresh blocks for the rest of the prompt, and report how
        many context tokens came from the cache (the engine skips
        prefilling them).  `keys`: the prompt's `chain_keys`, where the
        caller has them."""
        self._check_lane(lane, len(tokens))
        entries = self._match_chain(tokens, keys)
        # Pop spilled payloads out of the tier FIRST: the allocations below
        # may spill other blocks there, and must not push out the chain
        # being restored.  A pop that misses (aged out since the match)
        # cuts the usable chain at the hole.
        restores: List[Tuple] = []      # (chain_pos, key, (k_np, v_np))
        usable = len(entries)
        for pos, (at, key, _b) in enumerate(entries):
            if at != "tier":
                continue
            payload = self.tier.pop(key)
            if payload is None:
                usable = pos
                break
            restores.append((pos, key, payload))
        usable = self.layout.whole(entries, usable)
        entries = entries[:usable]
        restores = [r for r in restores if r[0] < usable]
        dev_blocks = [b for at, _k, b in entries if at == "dev"]
        # Take the device shares FIRST so the fresh allocation below can
        # never evict a block this very request is about to reuse.
        for b in dev_blocks:
            self.allocator.incref(b)
        closed, cached_len = self.layout.matched(entries)
        try:
            fresh = self.allocator.alloc(
                self.layout.to_start(len(tokens), closed) - len(dev_blocks))
        except RuntimeError:
            for b in dev_blocks:
                self.allocator.decref(b)
            for _pos, key, (k_np, v_np) in restores:
                self.tier.put(key, k_np, v_np)   # undo the pops
            raise
        # The lane's blocks in chain order: device hits keep theirs, spilled
        # hits take fresh ones (filled below), the prompt's tail the rest.
        fresh_iter = iter(fresh)
        chain_blocks = [b if at == "dev" else next(fresh_iter)
                        for at, _key, b in entries]
        if restores:
            self.write_blocks(jnp.asarray(np.asarray(
                [chain_blocks[pos] for pos, *_ in restores], np.int32)), *(
                None if restores[0][2][i] is None else
                np.stack([payload[i] for _p, _k, payload in restores],
                         axis=1) for i in (0, 1)))
            for pos, key, _payload in restores:
                # Restored blocks re-enter the device index (live now,
                # evictable again once the lane lets go).
                self.index.seal(key, chain_blocks[pos])
                self.stats["restored_blocks"] += 1
        self._install_lane(lane, chain_blocks + list(fresh_iter), cached_len,
                           closed)
        for part in self.parts:
            part.adopt(lane, len(tokens), _keys(entries))
        if chain_blocks:
            # The chain cursor at the sealed boundary, so blocks sealed
            # later extend the same chain: the hash of the last key.
            self._lane_parent[lane] = _chain_cursor(entries[-1][1])
            self.stats["hits"] += 1
            self.stats["hit_tokens"] += cached_len
        else:
            self.stats["misses"] += 1
        self.stats["miss_tokens"] += len(tokens) - cached_len
        return cached_len

    def has_blocks_to_seal(self, lane: int) -> bool:
        """Whether `seal_full_blocks` has anything to do for `lane`: asked
        before `tokens` is built (a decoding lane fills a block once in
        block_size steps, and its list may be a 16k-token document long)."""
        return (self.prefix_cache_enabled and self._lane_sealed[lane]
                < int(self.seq_lens[lane]) // self.block_size)

    def seal_full_blocks(self, lane: int, tokens: Sequence[int],
                         upto: Optional[int] = None) -> None:
        """Index every newly-full block of this lane.  `tokens` is the
        lane's full token sequence (prompt + generated); only the first
        seq_lens[lane] of them have K/V in the pool, and a block seals the
        moment the write cursor crosses its end — mid-prefill too, so a
        concurrent identical prompt can reuse the prefix at once.  `upto`:
        the tokens to take as written where that is more than the committed
        `seq_lens` (`close_window`)."""
        if not self.prefix_cache_enabled:
            return
        bs = self.block_size
        full = (int(self.seq_lens[lane]) if upto is None else upto) // bs
        blocks = self._lane_blocks[lane]
        behind = self.layout.closed[lane] * self.layout.shrink
        while self._lane_sealed[lane] < full:
            i = self._lane_sealed[lane]
            key = (self._lane_parent[lane],
                   tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))
            self._seal(key, blocks[i - behind])
            for part in self.parts:
                part.seal(lane, i, key)
            self._lane_parent[lane] = hash(key)
            self._lane_sealed[lane] += 1

    def _seal(self, key: Tuple, block: int) -> None:
        """Index `block`'s content under `key` (first writer wins)."""
        if self.index.seal(key, block):
            self.stats["sealed_blocks"] += 1
            if self.tier is not None:
                # Re-sealed on device: the spilled copy is stale freight
                # now (content-addressed, so identical).
                self.tier.discard(key)

    def _dropped(self, key: Tuple, block: int) -> None:
        """The allocator reclaimed a sealed block: the parts hear of it, and
        the content is spilled into the attached tier, so that the chain
        link survives eviction (its children stay reachable THROUGH it)."""
        for part in self.parts:
            part.dropped(key)
        if self.tier is not None:
            k_np, v_np = self.read_blocks(jnp.asarray([block], jnp.int32))
            self.tier.put(key, k_np[:, 0],
                          None if v_np is None else v_np[:, 0])

    @property
    def num_indexed_blocks(self) -> int:
        return len(self.index)

    # ---------------- disaggregated handoff / summaries ----------------

    def export_prefix(self, tokens: Sequence[int]) -> Optional[dict]:
        """The longest DEVICE-cached chain covering a block-aligned prefix
        of `tokens` as a codec payload: chain token-blocks plus gathered
        contents, enough for a foreign cache to rebuild the same links
        (spilled links don't ship: restore is local); under `more` the
        further rows of every block (`extra`) and every part's share.  None
        when nothing is cached."""
        entries = self._match_dev(tokens)
        if not entries:
            return None
        keys = _keys(entries)
        idx = jnp.asarray(np.asarray([b for *_, b in entries], np.int32))
        k_np, v_np = self.read_blocks(idx)
        more = {}
        if self.extra:
            more["extra"] = [self.read_blocks(idx, self._first_extra + i)
                             for i in range(len(self.extra))]
        for part in self.parts:
            more.update(part.export(keys))
        return self._wire(self.layout.wire_chain(keys, tokens), k_np, v_np,
                          more)

    def _wire(self, chain, k_np, v_np, more: dict) -> dict:
        """A payload of the wire format, of this cache's kind."""
        return {"v": 1, "kind": self.kind, "block_size": self.block_size,
                "chain": chain, "k": k_np, "v_pool": v_np,
                **({"more": more} if more else {})}

    def _wire_fits(self, payload: dict) -> bool:
        """Whether a payload is of this cache's kind and shapes: a foreign
        one is refused quietly."""
        k_arr, more = payload["k"], payload.get("more") or {}
        return (payload.get("v") == 1
                and payload.get("block_size") == self.block_size
                and payload.get("kind", "kv") == self.kind
                and tuple(k_arr.shape[2:]) == (
                    self.block_size, self.kv_heads, self.head_dim)
                and k_arr.shape[0] == self.pool_shape[0]
                and set(more) == set(self._wire_more)
                and len(more.get("extra", ())) == len(self.extra))

    def install_prefix(self, payload: dict) -> int:
        """Adopt foreign sealed blocks (the prefill→decode handoff): each
        shipped chain node not present locally into a fresh block, indexed
        at refcount 0 (`SealedIndex.install`), so that an adopt_prefix of
        the same prompt takes shares as if the blocks had been sealed here;
        every part installs its share likewise, first, and may refuse the
        lot.  Idempotent: a repeated import is a no-op for links already
        present.  Returns how many blocks were installed."""
        if not self.prefix_cache_enabled or not payload \
                or not self._wire_fits(payload):
            return 0
        more = payload.get("more") or {}
        keys = self.layout.wire_keys(payload["chain"])
        n = 0
        for part in self.parts:
            got = part.install(more, keys)
            if got is None:
                return 0
            n += got

        def write(idx, pos):
            idx, v_arr = jnp.asarray(idx), payload["v_pool"]
            self.write_blocks(idx, payload["k"][:, pos],
                              None if v_arr is None else v_arr[:, pos])
            for i, rows in enumerate(more.get("extra", ())):
                self.write_blocks(idx, rows[:, pos], None,
                                  self._first_extra + i)

        n += self.index.install(
            [(i, key) for i, key in enumerate(keys)
             if self.tier is None or not self.tier.contains(key)], write)
        self.stats["imported_blocks"] += n
        return n

    def prefix_summary(self, limit: int = 256) -> dict:
        """Compact routing summary: the chain hashes of every sealed block
        this cache can serve (device index + spill tier), newest last,
        capped at `limit`; a router scores a replica by deepest overlap
        with a request's own hashes, without shipping tokens."""
        hashes = [hash(key) for _block, key in self.index.items()]
        if self.tier is not None:
            hashes.extend(self.tier.summary_hashes())
        # Order-preserving dedup; newest sealed blocks win the cap.
        hashes = list(dict.fromkeys(hashes))[-max(int(limit), 1):]
        return {
            "v": 1,
            "block_size": self.block_size,
            "hashes": hashes,
            "indexed_blocks": len(self.index),
            "tier_blocks": 0 if self.tier is None else len(self.tier),
        }

    # ---------------- windows ----------------

    def close_window(self, lane: int, tokens: Sequence[int]) -> Tuple[
            List[int], List[int]]:
        """The lane's open window is full (`tokens`: the lane's sequence up
        to the window's end at least): its exact blocks `src` leave the
        table and go back to the allocator, fresh blocks `dst` for its
        summary rows take their place, and (src, dst) are returned for the
        device program that makes the one from the other.  The caller
        dispatches that program before any that reads the new table or
        writes a block handed out after this call (the device runs programs
        in dispatch order).  The window's still unsealed exact blocks are
        sealed first (a match that ends inside this window may use them,
        and the chain cursor must stand at the window's end), then the
        summary blocks under that cursor: first writer wins."""
        lay = self.layout
        done = lay.closed[lane]
        self.seal_full_blocks(lane, tokens, upto=(done + 1) * lay.window)
        blocks = self._lane_blocks[lane]
        at = done * lay.sum_blocks
        src = blocks[at:]
        if not lay.window or len(src) != lay.win_blocks:
            raise RuntimeError(f"lane {lane}: window {done} is not full")
        dst = self.allocator.alloc(lay.sum_blocks)
        if self.prefix_cache_enabled:
            for part, block in enumerate(dst):
                self._seal((self._lane_parent[lane], ("summary", part)),
                           block)
        blocks[at:] = dst
        self.block_tables[lane, at:] = 0
        self.block_tables[lane, at:at + len(dst)] = dst
        self.allocator.free(src)
        lay.closed[lane] = done + 1
        self.stats["windows_closed"] += 1
        self._dev_tables = None
        return src, dst

    def blocks_by_kind(self) -> Tuple[int, int]:
        """(summary blocks, exact blocks) the pool holds now, live or
        cached: the lanes' own and what the prefix index keeps."""
        summary = {b for b, key in self.index.items() if _is_summary(key)}
        for blocks, closed in zip(self._lane_blocks, self.layout.closed):
            summary.update(blocks[:closed * self.layout.sum_blocks])
        held = self.allocator.num_blocks - self.allocator.num_unused
        return len(summary), held - len(summary)

    # ---------------- lane growth / teardown ----------------

    def ensure_capacity(self, lane: int, new_len: int) -> None:
        """Grow the lane's table as decode crosses block boundaries (with
        windows, inside the open one: `close_window` comes first), and
        every part's beside it."""
        if new_len > self.max_seq_len:
            raise RuntimeError(f"lane {lane} exceeded max_seq_len")
        self.layout.check_open(lane, new_len)
        need = self.layout.blocks_needed(new_len)
        blocks = self._lane_blocks[lane]
        while len(blocks) < need:
            (b,) = self.allocator.alloc(1)
            self.block_tables[lane, len(blocks)] = b
            blocks.append(b)
            self._dev_tables = None
        for part in self.parts:
            part.grow(lane, new_len)

    def after_commit(self, lanes) -> None:
        """The lanes `lanes` had a step's tokens committed: every part lets
        go of what they no longer read."""
        lanes = list(lanes)
        for part in self.parts:
            for lane in lanes:
                part.release(lane)

    # What the engine's loop is asked for beside its steps: `close_window`
    # where `window_due`, `after_commit`, `checkpoint`.
    closes = property(lambda self: self.layout.closes)
    releases = property(lambda self: any(p.releases for p in self.parts))
    checkpoints = property(
        lambda self: any(p.checkpoints for p in self.parts))

    def checkpoint(self, lane: int, key: Tuple) -> bool:
        """Behind the step that has just been dispatched: what the parts
        keep of the lane to stand behind the block of chain key `key`."""
        return any([part.checkpoint(lane, key) for part in self.parts])

    def checkpoint_wanted(self, lane: int) -> int:
        """The length of the lane's prompt behind which some part wants a
        `checkpoint` (0: none does): where the blocks its admission matched
        ended, if the part could not serve them all."""
        return self.block_size * max(
            (part.wanted(lane) for part in self.parts), default=0)

    def kind_stats(self) -> dict:
        """The layout's and the parts' own counters, as one dict."""
        return {k: v for own in [self.layout, *self.parts]
                for k, v in own.stats(self).items()}

    def truncate_lane(self, lane: int, new_len: int) -> None:
        """Speculative rollback: release the table-tail blocks past what
        ``new_len`` committed tokens need.  Rejected draft tokens were
        written at positions >= the committed length; their K/V is garbage
        the attention mask already hides, and real tokens overwrite those
        slots before the context grows across them, so rollback is pure
        block accounting.  Only wholly-uncommitted tail blocks are released:
        always fresh, exclusively-owned allocations (shared prefix blocks
        live at the front of the table, and the sealed boundary never passes
        the committed length).  Refused where the layout or a part keeps
        what cannot be rolled back (`no_rollback`).

        The one position a step dispatched ahead writes for a request that
        its predecessor's result then ended lies likewise at or past the
        committed length in a block the lane owns alone, and `free_lane`
        returns that block with the others: the device runs programs in
        dispatch order, so whoever gets it next writes before it reads."""
        if self.no_rollback:
            raise NotImplementedError(
                f"lane {lane} is not truncated: {self.no_rollback}")
        blocks = self._lane_blocks[lane]
        keep = max(self.layout.blocks_needed(new_len),
                   self._lane_sealed[lane])
        while len(blocks) > keep:
            b = blocks.pop()
            self.allocator.decref(b)
            self.block_tables[lane, len(blocks)] = 0
            self._dev_tables = None

    def free_lane(self, lane: int) -> None:
        """Sequence finish: drop this lane's share of every block.
        Sealed+indexed blocks whose refcount hits 0 park on the LRU
        evictable list (warm for the next matching prefix); everything
        else returns to the free list."""
        self.allocator.free(self._lane_blocks[lane])
        for part in self.parts:
            part.free(lane)
        self._lane_blocks[lane] = []
        self.block_tables[lane, :] = 0
        self.seq_lens[lane] = 0
        self._lane_sealed[lane] = 0
        self.layout.closed[lane] = 0
        self._lane_parent[lane] = _ROOT_HASH
        self._dev_tables = None

    def lane_blocks(self, lane: int) -> List[int]:
        return list(self._lane_blocks[lane])

    # ---------------- device mirrors ----------------

    @property
    def tables_on_device(self) -> bool:
        """Whether `device_tables()` has its copy (no table changed since
        it was made) and will transfer nothing."""
        return self._dev_tables is not None

    def device_tables(self) -> jax.Array:
        """The tables as a step takes them: a copy made now, on the host
        (`jnp.asarray` of a numpy array is the same memory on the CPU
        backend, `jnp.array`'s copy is the device's in its turn, and a step
        dispatched ahead runs after the host has rewritten a lane's row)."""
        if self._dev_tables is None:
            self._dev_tables = jnp.asarray(self.block_tables.copy())
        return self._dev_tables

    @property
    def buffers(self) -> tuple:
        """What the parts keep on the device beside the pools."""
        return tuple(b for part in self.parts for b in part.buffers)

    @property
    def step_pools(self) -> tuple:
        """(k, v) as a step takes and returns them: the pools, or where a
        part has buffers ((*pools, *buffers), None): K and V, or the one
        latent pool, the model's mixer taking the buffers behind its
        attention's."""
        more = self.buffers
        return (tuple(self._pools) + more, None) if more \
            else (self.k, self.v)

    def update_pools(self, k: jax.Array, v: Optional[jax.Array]) -> None:
        """Rebind the functional pools returned by a jitted step (in the
        form of `step_pools`)."""
        if self.buffers:
            more = list(k[len(self._pools):])
            k = tuple(k[:len(self._pools)])
            for part in self.parts:
                n = len(part.buffers)
                part.rebind(tuple(more[:n]))
                del more[:n]
        self._pools = list(k) if isinstance(k, (tuple, list)) \
            else [k] if v is None else [k, v]

    # ---------------- the wire format's boundary ----------------

    @property
    def pool_shape(self) -> tuple:
        """The stored shape of the (first) pool."""
        return self._pools[0].shape

    # The first of the pools of further rows (`extra`).
    _first_extra = property(lambda self: 1 + self._paired)

    def read_blocks(self, idx: jax.Array, pool: Optional[int] = None):
        """Blocks `idx` in the wire format, on the host: (first pool's rows,
        V rows or None), or the rows of pool `pool` of several, an array."""
        if pool is not None:
            return np.asarray(unpack_kv_rows(self._pools[pool][:, idx],
                                             *self._pool_rows[pool]))
        return tuple(self.read_blocks(idx, i) if i <= self._paired else None
                     for i in (0, 1))

    def write_blocks(self, idx: jax.Array, k_blocks, v_blocks,
                     pool: Optional[int] = None) -> None:
        """Store wire-format blocks at `idx` (pad columns stay zero): K and
        V rows, or `k_blocks` into pool `pool` of several."""
        new = [(pool, k_blocks)] if pool is not None else \
            [(0, k_blocks)] + [(1, v_blocks)] * self._paired
        for i, blocks in new:
            self._pools[i] = self._pools[i].at[:, idx].set(pack_kv_rows(
                jnp.asarray(blocks, self._pools[i].dtype)))
