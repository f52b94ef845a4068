"""Paged KV cache: fixed-size blocks in a preallocated device pool.

The pool is one buffer per K and V for the engine's lifetime (no
per-request HBM churn), stored as [n_layers, num_blocks, block_size, W]:
a token's K (or V) of every kv head is one row of
W = kv_heads * head_dim columns rounded up to a multiple of 128, which
is the shape whose device layout is the row-major one the paged kernel's
DMA reads (pad columns are zero and never read into a result;
ops/attention.py).
Each live sequence owns an ordered list of block ids; the per-lane block
tables map logical context positions onto pool blocks so sequences of
wildly different lengths pack the same pool with at most block_size - 1
wasted slots each (the vLLM memory model).  Allocation and free are
host-side refcount operations; the device arrays are functional — the
jitted step returns updated pools and the cache rebinds them.  On TPU
they are donated, and the step writes only the blocks its new tokens
fall in and reads only the blocks it attends over, in that one buffer:
`count_pool_copies` over the compiled step is the check
(`InferenceEngine.compiled_steps()["pool_copies"]` must be 0).

Outside the engine a block keeps the wire format
[n_layers, n, block_size, kv_heads, head_dim] (export/install, the tier
spill, serve/kv_tier/codec.py): `read_blocks` / `write_blocks` convert at
the boundary.

What a row holds comes from the model's attention (`for_model`): per-head K
and V rows in a pool each (`kind` "kv"), or, for latent attention, ONE pool
whose row is a token's latent and the one rotated key its heads share
(`kind` "latent": kv_heads 1, `v` None; 576 numbers a token a layer where
64 heads of K and V would be 16,384).  Allocator, block tables, prefix
index, sealing and eviction never look inside a row; the wire format says
which kind it carries and a cache installs only its own.

How MANY rows a lane holds also comes from the attention.  One a token, or
(`window`, `chunk`: EVA) the exact rows of the lane's open window of
`window` tokens behind one summary row for every `chunk` tokens of each
closed one (`kind` "windowed").  Both kinds of row have the same shape and
live in the same pools under the same allocator; what differs is the
book-keeping.  A lane's table is laid out [summary blocks of windows
0..w-1 | the open window's blocks], so a lane of n tokens needs a sawtooth
of blocks (`blocks_needed`), not n / block_size.  When the open window is
full its lane `close_window`s: fresh blocks for the summaries take the
window's place in the table (the device program that fills them is the
engine's to dispatch) and the window's exact blocks go back to the
allocator in mid-sequence, staying in the prefix index as evictable if they
were sealed, like any sealed block.  The prefix index then holds two kinds
of entry: a token block's exact rows under its chain key, as ever, and a
closed window's summary blocks under the chain hash of the window's last
token block.  A prefix of m tokens is the summary blocks of the windows it
completes plus the exact blocks of the window it ends in; a closed
window's exact blocks serve only a match that ends inside it, and may be
evicted without breaking a longer one.

Layers of several kinds in one cache (`kind` "layered": dots3's full and
window layers).  A model's runs of layers may leave rows of different
shapes, kept for different spans; each kind has pools of its own, all under
the one cache, its lanes and its chain of sealed blocks.  The GROWING kind
is the cache as above (one latent row a token, kept for the whole
sequence), with further pools on the same blocks and table for the other
rows its layers leave a token (`extra`: an indexer's key).  The SLIDING
kind (`slide`) keeps a row a token too, but only a lane's last
`slide_window` positions are ever read again: its blocks come from a pool
and an allocator of their own, under a second table with a slot a token
block (the second half of `block_tables`' columns), and a block wholly
behind a lane's window goes back in mid-sequence (`slide_release`, after
every commit), staying in the index as evictable if it was sealed.  A token
block is sealed in both kinds under the same chain key.  The prefix index
serves a match of m blocks only where BOTH kinds hold it: every growing
block of the prefix, and the sliding blocks that cover its last
`slide_window` - 1 positions (`_held_by_both`); admission counts each kind
at its own peak (`can_admit_prefix`).

State that is not rows (`kind` "state": Falcon-H1's state-space mixer beside
its attention heads).  A layer's mixer keeps of a lane a float32 state
[heads, d_state, head_dim] and the last rows its convolution reads again,
of a fixed size whatever the lane's length: one SLOT a lane a layer in two
buffers of their own beside the K and V pools (`state`
[n_layers, max_lanes + 1, heads, d_state, head_dim] and `tail`
[n_layers, max_lanes + 1, rows x width]; a lane's slot is its index, the last slot is
where a program sends the rows nobody has), allocated with the lane,
overwritten by every step, never grown, given back with the lane.  The
step takes all four buffers as one tuple (`step_pools`) and updates a slot
in place (ops/ssm.py; `count_pool_copies` of the state's shape over the
compiled step must be 0 too).  A prefix of sealed K/V blocks is worthless
without the state at its end, so the prefix index holds SNAPSHOTS beside
its blocks: a pool of snapshot slots (`snaps`, `snap_tails`) under the chain
key of the block a snapshot stands behind, refcounted and evicted least
recently used like blocks (`snap_allocator`), and dropped with the block of
their key.  The engine says when one is taken (`snapshot`: a copy of the
lane's slot, dispatched behind the prefill step that left the state there);
the index serves a match of m blocks only where the K/V blocks AND a
snapshot at m exist (`_held_with_state`), and adopting it copies the
snapshot into the lane's slot ahead of the lane's first step
(`adopt_prefix`).  The wire format carries the snapshot beside the blocks
(`more`), a cache installs only its own kind, no spill tier is attached, and
a lane is never truncated: a state cannot be rolled back.

Prefix caching (content-addressed block sharing): a block that has been
completely written ("sealed") is indexed by a hash chain over
(parent_hash, block_tokens) — the chain hash of a block is a function of
every token up to and including its own, and K/V at a position depend on
exactly that token prefix, so two sequences whose prefixes agree
block-for-block may share the physical blocks.  Sealed blocks are
immutable (decode writes always land at positions past the sealed
boundary, i.e. in each lane's private tail), so copy-on-write semantics
come for free.  The write path relies on that tail being private: the
blocks that the valid rows of one step land in (`ensure_capacity`'s, a
sliding kind's slots) are held by one lane each, so the TPU's write kernel
(ops/paged_write.py) may have every lane's copy in flight at once; two live
lanes of a step naming one block there would be a lost write, where the XLA
loop let the last lane win.  Whoever shares a block that is still being
filled breaks it: `tests/test_paged_write.py` holds the engine's programs
to it on the CPU.  When a sequence finishes, its sealed blocks stay in the
index at refcount 0 on an LRU list and are evicted only when the
allocator needs the space; a new request reuses the longest
block-aligned cached prefix instead of re-prefilling it.
"""

from __future__ import annotations

import collections
import itertools
import math
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import (kv_row_width, pack_kv_rows,
                                   unpack_kv_rows)

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
    (root 0), and the same one-token-left cap as match_prefix.  Whoever
    has a prompt's keys (the engine makes them in `submit`, on the
    caller's thread) hands them to `can_admit_prefix`, `adopt_prefix` and
    `match_prefix`, which then look blocks up without walking the prompt
    again: three walks of a 16k-token document were 7.7 ms of an admitting
    iteration on the engine's thread (PERF.md section 6, PR 32)."""
    return list(_iter_chain_keys(tokens, block_size))


def _summary_key(last_key: Tuple, part: int) -> Tuple:
    """The index key of block `part` of a closed window's summary rows:
    under the chain hash of the window's last token block, so a function of
    every token up to the window's end, like the rows."""
    return (hash(last_key), ("summary", part))


def _is_summary(key: Tuple) -> bool:
    return key[1][:1] == ("summary",)


def _chain_cursor(key: Tuple) -> int:
    """The parent hash of whatever follows the block indexed under `key`."""
    return key[0] if _is_summary(key) else hash(key)


def chain_hashes(tokens: Sequence[int], block_size: int) -> List[int]:
    """Cumulative chain hash of every block-aligned prefix of `tokens`,
    in the exact convention the prefix index uses (`hash((parent,
    block_tokens))`, root 0) and with the same one-token-left cap as
    match_prefix.  Tuple-of-int hashing is deterministic across
    processes (PYTHONHASHSEED randomizes str/bytes only), so a router
    can score replica summaries against a request without shipping
    tokens."""
    return [hash(key) for key in chain_keys(tokens, block_size)]


# `  ROOT %name = bf16[48,256,16,1664]{3,2,1,0:T(8,128)(2,1)} opcode(%a, %b),
#    attributes`; the type is a tuple of such for a multi-output fusion.
_HLO_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_HLO_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_HLO_LOOP = re.compile(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)")
# Opcodes that make a copy of their operand in another dtype, place or
# extent (`copy-start` is counted at its `copy-done`); `remat` is ours.
_MOVES = ("convert", "copy", "copy-done", "transpose", "slice",
          "dynamic-slice", "remat")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4}


def _parse_hlo(hlo_text: str):
    """(computations, roots, fused, trips) of a compiled module's text:
    computation -> {instruction: (result arrays [(dtype, [dims])], opcode,
    operand names, attributes)}; computation -> its root instruction; the
    computations `fusion`s call; loop body -> its trip count, where the
    loop's condition is `counter < constant` (a scan's)."""
    comps: Dict[str, Dict[str, tuple]] = {}
    roots: Dict[str, str] = {}
    trips: Dict[str, int] = {}
    bounds: Dict[str, int] = {}     # computation -> its last int constant
    current = None
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            current = head.group(1)
            comps[current] = {}
            continue
        m = _HLO_INSTR.match(line)
        if m and current:
            root, name, result, opcode, rest = m.groups()
            operands, _, attrs = rest.partition(")")
            arrays = [(dtype, [int(d) for d in dims.split(",") if d])
                      for dtype, dims in _HLO_ARRAY.findall(result)]
            comps[current][name] = (
                arrays, opcode, re.findall(r"%([\w.\-]+)", operands), attrs)
            if root:
                roots[current] = name
            if opcode == "constant" and operands.isdigit():
                bounds[current] = int(operands)
            loop = opcode == "while" and _HLO_LOOP.search(attrs)
            if loop and "direction=LT" in comps[loop.group(1)][
                    roots[loop.group(1)]][3]:
                trips[loop.group(2)] = bounds.get(loop.group(1), 1)
    fused = {_called(instr[3]) for body in comps.values()
             for instr in body.values() if instr[1] == "fusion"}
    return comps, roots, fused, trips


def _called(attrs: str) -> str:
    return re.search(r"calls=%?([\w.\-]+)", attrs).group(1)


def _made_by(comps, roots, comp: str, name: str) -> str:
    """The opcode that makes an instruction's result: its own, or for a
    `fusion` (and through `bitcast`s) that of the called root."""
    _, opcode, operands, attrs = comps[comp].get(name, ([], "", [], ""))
    if opcode == "fusion":
        return _made_by(comps, roots, _called(attrs), roots[_called(attrs)])
    if opcode == "bitcast" and operands:
        return _made_by(comps, roots, comp, operands[0]) or opcode
    if opcode == "tuple":               # a multi-output fusion's root
        made = [_made_by(comps, roots, comp, o) for o in operands]
        return next((m for m in made if m in _MOVES), opcode)
    return opcode


def count_pool_copies(hlo_text: str, pool_shape: Sequence[int]) -> int:
    """Instructions of a compiled step that move the KV pool instead of
    touching rows and blocks of it: the result is the whole stored pool
    or whole layers of it (as a layer scan slices them out and stacks
    them back), and the instruction, or the root of the fusion it calls,
    is a `copy`, a `scatter`, a `dynamic-slice`, or a
    `dynamic-update-slice` whose update is itself whole layers.  A row or
    a block written into the pool is in place and not counted: where XLA
    cannot update in place it inserts a `copy`, which is.  Zero means the
    pool stays where it is."""
    n_layers, *block = (int(d) for d in pool_shape)
    comps, roots, fused, _ = _parse_hlo(hlo_text)

    def whole_layers(arrays) -> bool:
        return any(shape[-len(block):] == block
                   and n_layers % math.prod(shape[:-len(block)]) == 0
                   for _, shape in arrays)

    def moves(comp: str, name: str) -> bool:
        arrays, opcode, operands, attrs = comps[comp].get(
            name, ([], "", [], ""))
        if not whole_layers(arrays):
            return False
        if opcode == "fusion":
            return moves(_called(attrs), roots[_called(attrs)])
        if opcode == "tuple":           # a multi-output fusion's root
            return any(moves(comp, o) for o in operands)
        if opcode == "bitcast":         # a fusion's root behind a bitcast
            return moves(comp, operands[0])
        if opcode == "dynamic-update-slice":
            return whole_layers(comps[comp].get(operands[1], ([],))[0])
        return opcode in ("copy", "scatter", "dynamic-slice")

    return sum(moves(comp, name) for comp, body in comps.items()
               if comp not in fused for name in body)


def count_weight_bytes_copied(hlo_text: str, weights) -> Dict[str, int]:
    """Bytes of weight-shaped results a compiled step makes in one run,
    by the opcode that makes them ({} when the weights are read where they
    are).  Weight-shaped: the shape of a matrix leaf of `weights` (the
    tree the step takes, arrays or their shapes), of a group of its
    leading dim (as a layer scan slices its groups out: leading dims that
    divide it, the rest equal) or of either with the last two dims
    swapped; counted where the instruction, or the root of the fusion it
    calls, copies its operand (`_MOVES`), times the trip counts of the
    scans around it.  A per-step `convert` is a leaf held in the wrong
    dtype, a `copy` or `transpose` one held the wrong way round, `remat` (an
    instruction XLA named `.remat`) a temporary made again; what a layer
    scan slices out of its stacked arguments reads `dynamic-slice` (and
    `copy-done` where XLA prefetches it)."""
    comps, roots, fused, trips = _parse_hlo(hlo_text)
    # (what the leading dims must divide, the dims that must follow them)
    forms = set()
    for x in jax.tree.leaves(weights):
        shape = tuple(x.shape)
        if len(shape) < 2:
            continue
        for s in (shape, (*shape[:-2], shape[-1], shape[-2])):
            forms.add((1, s))                       # the leaf
            if len(s) >= 3:
                forms.add((s[0], s[1:]))            # a group of its layers

    def weight_shaped(shape) -> bool:
        def fits(lead, rest):
            k = len(shape) - len(rest)
            return (k >= 0 and tuple(shape[k:]) == rest
                    and lead % math.prod(shape[:k]) == 0)
        return any(fits(lead, rest) for lead, rest in forms)

    # Runs of each computation per step: a loop body's trip count times
    # its caller's (computations reached by `call`s and fusions inherit).
    runs: Dict[str, int] = {}

    def visit(comp: str, n: int) -> None:
        runs[comp] = n
        for _, opcode, _, attrs in comps.get(comp, {}).values():
            for callee in re.findall(
                    r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)", attrs):
                if callee not in runs and callee not in fused:
                    visit(callee, n * trips.get(callee, 1))

    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo_text, re.M)
    if entry:
        visit(entry.group(1), 1)
    out: Dict[str, int] = collections.Counter()
    for comp, body in comps.items():
        if comp in fused:
            continue
        for name, (arrays, opcode, _, _) in body.items():
            made = ("remat" if ".remat" in name
                    else _made_by(comps, roots, comp, name)
                    if opcode == "fusion" else opcode)
            if made not in _MOVES:
                continue
            size = sum(_DTYPE_BYTES.get(dtype, 4) * math.prod(shape)
                       for dtype, shape in arrays if weight_shaped(shape))
            if size:
                out[made] += size * runs.get(comp, 1)
    return dict(out)


def _copy_slot(dst_state, dst_tail, src_state, src_tail, src, dst):
    """One slot of a state cache copied into another buffer, every layer:
    dst[:, dst] = src[:, src] for the state and the tail (a snapshot taken
    or adopted; `dst` out of range: nothing is written).  One
    `dynamic_update_slice` a buffer, so that the donated destination stays
    where it is."""
    with jax.named_scope("ssm_snapshot"):
        live = (dst >= 0) & (dst < dst_state.shape[1])
        at = jnp.clip(dst, 0, dst_state.shape[1] - 1)
        out = []
        for into, frm in ((dst_state, src_state), (dst_tail, src_tail)):
            new = jax.lax.dynamic_slice_in_dim(frm, src, 1, axis=1)
            old = jax.lax.dynamic_slice_in_dim(into, at, 1, axis=1)
            out.append(jax.lax.dynamic_update_slice_in_dim(
                into, jnp.where(live, new.astype(into.dtype), old), at,
                axis=1))
        return tuple(out)


class BlockAllocator:
    """Refcounted free-list over pool block ids.

    Three states per block: free (no content), live (refcount >= 1) and
    evictable (refcount 0 but still holding indexed cached content —
    reusable without recompute, reclaimable under pressure).  `num_free`
    counts free + evictable: both are available capacity, and the
    scheduler's admission control is built on can_alloc — a sequence is
    only admitted when its prompt fits.  No implicit growth: exhaustion
    raises.
    """

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
                 window: int = 0, chunk: int = 0, extra: tuple = (),
                 slide: Optional[Tuple[int, int, int, int]] = None,
                 slide_blocks: Optional[int] = None, ahead: int = 2,
                 state=None, snapshots: Optional[int] = None):
        self.block_size = block_size
        self.max_lanes = max_lanes
        self.max_seq_len = max_seq_len
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        # Rows a lane holds (module docstring): one a token (`window` 0),
        # or a window's exact rows behind the closed windows' summaries.
        # `_win_blocks` exact and `_sum_blocks` summary blocks a window;
        # a closed window shortens its lane's table by their difference.
        self.window, self.chunk = window, chunk
        self._win_blocks = self._sum_blocks = self._shrink = 0
        if window:
            rows = window // max(chunk, 1)
            if latent or chunk < 1 or window % chunk or rows % block_size:
                raise ValueError(
                    f"a windowed cache needs K and V pools and a window "
                    f"({window}) whose summary rows (one per {chunk}) fill "
                    f"whole blocks of {block_size}")
            self._win_blocks = window // block_size
            self._sum_blocks = rows // block_size
            self._shrink = self._win_blocks - self._sum_blocks
        # The most table slots a lane fills: at its end or, with windows,
        # at the close of its last whole one.
        self.max_blocks_per_seq = max(
            self.blocks_needed(max_seq_len),
            self.blocks_needed((max_seq_len - 1) // window * window)
            if window else 0)
        # What a row holds: K and V rows in a pool each, or (`latent`:
        # kv_heads 1, head_dim the latent and its rotated key together) one
        # latent row in the one pool.  `k` is that pool, `v` None.
        self.kind = ("layered" if extra or slide else "latent" if latent
                     else "windowed" if window else "state" if state
                     else "kv")
        # The stored layout (module docstring): rows of W columns.
        shape = (n_layers, num_blocks, block_size,
                 kv_row_width(kv_heads, head_dim))
        self.k = jnp.zeros(shape, dtype)
        self.v = None if latent else jnp.zeros(shape, dtype)
        self.allocator = BlockAllocator(num_blocks, on_evict=self._on_evict)
        # Layers of several kinds (module docstring).  `k` is then the
        # tuple of all pools as the model's runs index them: the growing
        # kind's rows, its `extra` rows, the sliding kind's rows.
        self.extra = tuple(extra)
        self.slide_window = 0
        tables = 1
        if self.kind == "layered":
            if not latent or window:
                raise ValueError("layers of several kinds: latent rows only")
            pools = [self.k] + [jnp.zeros(shape[:3] + (kv_row_width(1, w),),
                                          dtype) for w in self.extra]
            if slide:
                s_layers, s_heads, s_dim, self.slide_window = slide
                self._slide_row = (s_heads, s_dim)
                # Positions a lane may be written past its committed
                # length: the engine's longest slice, twice (one step runs
                # ahead of the last commit); a decoding lane's are 2.
                self._ahead = max(int(ahead), 2)
                if slide_blocks is None:
                    slide_blocks = max_lanes * self._slide_peak(True)
                pools.append(jnp.zeros(
                    (s_layers, slide_blocks, block_size,
                     kv_row_width(s_heads, s_dim)), dtype))
                self.slide_allocator = BlockAllocator(
                    slide_blocks, on_evict=self._on_slide_evict)
                # lane -> {slot: block} and the sliding kind's own index
                self._slide_lane: List[Dict[int, int]] = [
                    {} for _ in range(max_lanes)]
                self._slide_index: Dict[Tuple, int] = {}
                self._slide_key: Dict[int, Tuple] = {}
                self._lane_prompt = [0] * max_lanes
                tables = 2
            self.k = tuple(pools)
        # Unused table entries stay 0 — always a valid pool index; the
        # attention mask (positions >= ctx_len) hides whatever lives there.
        # (A sliding kind's table is the second half of the columns.)
        self.block_tables = np.zeros(
            (max_lanes, tables * self.max_blocks_per_seq), np.int32)
        self.seq_lens = np.zeros((max_lanes,), np.int32)
        self._lane_blocks: List[List[int]] = [[] for _ in range(max_lanes)]
        self._dev_tables: Optional[jax.Array] = None
        # ---- prefix index (content-addressed sealed blocks) ----
        self.prefix_cache_enabled = prefix_cache
        # (parent_chain_hash, block_tokens) -> block id.  Keys compare by
        # equality, so within one chain level collisions are impossible;
        # the int parent hash aliasing two distinct prefixes is the usual
        # 64-bit-hash-chain gamble (vLLM makes the same one).
        self._index: Dict[Tuple[int, Tuple[int, ...]], int] = {}
        self._block_key: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        # Token blocks sealed (hashed into the chain) per lane, and windows
        # closed: the first `_sum_blocks` x closed entries of a lane's
        # blocks are summaries, and token block i sits at entry
        # i - closed x `_shrink`.
        self._lane_sealed = [0] * max_lanes
        self._lane_closed = [0] * max_lanes
        self._lane_parent = [_ROOT_HASH] * max_lanes   # chain hash cursor
        self.stats = {"hit_tokens": 0, "miss_tokens": 0, "hits": 0,
                      "misses": 0, "sealed_blocks": 0, "imported_blocks": 0,
                      "restored_blocks": 0, "windows_closed": 0,
                      "slide_blocks_freed": 0}
        # Optional tiered spill cache (serve/kv_tier): evicted sealed
        # blocks move here instead of being destroyed, and the match /
        # adopt path restores them on hit (the SPILLED index state).
        self.tier = None
        # State that is not rows (module docstring): `state` is what the
        # model's mixer keeps of a lane (`decoder.StateRows`).
        self.state = self.tail = self.snaps = self.snap_tails = None
        if state is not None:
            if latent or window:
                raise ValueError("a state cache: beside K and V pools only")
            if snapshots is None:
                snapshots = max(2, max_lanes // 4)
            snapshots = int(snapshots) if prefix_cache else 0
            one = (state.heads, state.d_state, state.head_dim)
            # (a tail's K - 1 rows one behind the other in ONE row of its
            # slot: as [slots, 3, width] the compiler lays the three rows
            # out one way for a program of all lanes and another for a
            # program of one, and re-lays the buffer at a program's two
            # ends: 5% of a decode step, PERF.md section 6, PR 43)
            row = ((state.conv - 1) * state.conv_width,)
            self.state = jnp.zeros((n_layers, max_lanes + 1) + one,
                                   jnp.float32)
            self.tail = jnp.zeros((n_layers, max_lanes + 1) + row, dtype)
            self.snaps = jnp.zeros((n_layers, max(snapshots, 1)) + one,
                                   jnp.float32)
            self.snap_tails = jnp.zeros((n_layers, max(snapshots, 1)) + row,
                                        dtype)
            self.snapshot_slots = snapshots
            self.snap_allocator = BlockAllocator(
                max(snapshots, 1), on_evict=self._on_snap_evict)
            self._snap_index: Dict[Tuple, int] = {}
            self._snap_key: Dict[int, Tuple] = {}
            # blocks the last match found and could serve none of
            self._unserved = 0
            self.stats.update(snapshots_taken=0, snapshots_adopted=0,
                              snapshot_misses=0)
            donate = () if jax.default_backend() == "cpu" else (0, 1)
            self._copy_slot = jax.jit(_copy_slot, donate_argnums=donate)
            # Both directions made now, by a copy to nowhere: neither is
            # made under a request that waits.
            self._move(-1, 0, take=True)
            self._move(0, -1, take=False)

    def attach_tier(self, tier) -> None:
        """Attach a spill tier (duck-typed: contains/put/pop/discard/
        summary_hashes/__len__).  Evictions start spilling immediately;
        match/adopt start seeing spilled chains."""
        if self.kind == "layered":
            raise NotImplementedError(
                "a spill tier under layers of several kinds: a spilled "
                "block would have to carry every kind's rows (ROADMAP.md)")
        if self.kind == "state":
            raise NotImplementedError(
                "a spill tier under a state cache: a spilled chain would "
                "have to carry its snapshots (ROADMAP.md)")
        self.tier = tier

    @classmethod
    def for_model(cls, model, config, **kw) -> "PagedKVCache":
        """Build a cache shaped for an LM family's config (models/): the
        row its spec's attention leaves there (`decoder.Attention`)."""
        kw.setdefault("max_seq_len", config.max_seq_len)
        kw.setdefault("dtype", config.dtype)
        spec = model.spec(config)
        mixers = [run.mixer for run in spec.runs if run.mixer is not None]
        if mixers:
            if len(spec.runs) != 1:
                raise NotImplementedError(
                    "a state cache: one run of layers, each with the mixer")
            kw["state"] = mixers[0].state(config)
            if isinstance(kw.get("num_blocks"), (tuple, list)):
                # (the K/V blocks, the snapshot slots)
                kw["num_blocks"], kw["snapshots"] = kw["num_blocks"]
        if isinstance(kw.get("num_blocks"), (tuple, list)):
            # (the growing kind's blocks, the sliding kind's)
            kw["num_blocks"], kw["slide_blocks"] = kw["num_blocks"]
        if any(run.pools for run in spec.runs):
            return cls._layered(spec.runs, config, **kw)
        kw.pop("ahead", None)
        attn = spec.attn
        rows = attn.rows(config)
        return cls(config.n_layers, rows.kv_heads, rows.head_dim,
                   latent=attn.pools == 1, window=rows.window,
                   chunk=rows.chunk, **kw)

    @classmethod
    def _layered(cls, runs, config, **kw) -> "PagedKVCache":
        """A cache for runs of several kinds (`decoder.Run.table`): one
        growing latent kind, and at most one sliding kind."""
        kinds: Dict[int, list] = {}
        for run in runs:
            rows = run.attn.rows(run.sizes or config)
            layers = kinds.setdefault(run.table[0], [rows, 0])
            layers[1] = max(layers[1], run.first + run.n_layers)
            if layers[0] != rows or run.attn.pools != 1 or rows.window:
                raise NotImplementedError(
                    "layers of several kinds: latent rows, one shape a kind")
        grow = [k for k in kinds.values() if not k[0].slide]
        slid = [k for k in kinds.values() if k[0].slide]
        if len(grow) != 1 or len(slid) > 1:
            raise NotImplementedError(
                "layers of several kinds: one growing kind and at most one "
                "sliding kind")
        (rows, n_layers), = grow
        slide = ((slid[0][1], slid[0][0].kv_heads, slid[0][0].head_dim,
                  slid[0][0].slide) if slid else None)
        return cls(n_layers, rows.kv_heads, rows.head_dim, latent=True,
                   extra=rows.extra, slide=slide, **kw)

    # ---------------- host-side lane lifecycle ----------------

    def blocks_needed(self, seq_len: int) -> int:
        """Table slots of a lane that holds `seq_len` tokens.  With windows
        a sawtooth: the summary blocks of the windows before the last
        token's, and the blocks its own window has filled so far."""
        n = max(seq_len, 1)
        if not self.window:
            return math.ceil(n / self.block_size)
        closed, last = divmod(n - 1, self.window)
        return closed * self._sum_blocks + last // self.block_size + 1

    def rows_held(self, seq_len: int) -> int:
        """Rows a lane of `seq_len` tokens attends over: `seq_len`, or with
        windows the summaries of the closed ones and the open one's rows."""
        if not self.window or seq_len < 1:
            return seq_len
        closed, last = divmod(seq_len - 1, self.window)
        return closed * (self.window // self.chunk) + last + 1

    def peak_blocks(self, final_len: int, closed: int = 0) -> int:
        """The most blocks a lane owns on its way to `final_len` tokens
        with `closed` windows already closed: what admission reserves.
        Without windows that is its end.  With them it is the close of the
        last window it completes, where the window's blocks and the fresh
        summary blocks are held together for a moment, if that is still to
        come and is more than the end needs."""
        at_end = self.blocks_needed(final_len)
        last = (max(final_len, 1) - 1) // self.window if self.window else 0
        if last <= closed:
            return at_end
        return max(at_end, (last - 1) * self._sum_blocks + self._win_blocks
                   + self._sum_blocks)

    def lane_peak(self, lane: int, final_len: int) -> int:
        return self.peak_blocks(final_len, self._lane_closed[lane])

    def window_room(self, start: int) -> int:
        """Positions from `start` to the end of its window: a slice never
        crosses one (no limit without windows)."""
        return (self.window - start % self.window if self.window
                else self.max_seq_len)

    def _blocks_to_start(self, prompt_len: int, closed: int = 0) -> int:
        """Blocks a lane starts with: what holds its prompt, or with
        windows the summaries of the `closed` windows it adopts and the
        prompt's part of the next one (the rest come as windows close)."""
        if not self.window:
            return self.blocks_needed(prompt_len)
        return closed * self._sum_blocks + math.ceil(
            min(prompt_len - closed * self.window, self.window)
            / self.block_size)

    def can_admit(self, prompt_len: int) -> bool:
        return self.allocator.can_alloc(self.peak_blocks(prompt_len)) \
            and self._slide_admits(())

    def alloc_lane(self, lane: int, prompt_len: int) -> None:
        """Sequence start without prefix reuse: claim fresh blocks
        covering the prompt."""
        if self._lane_blocks[lane]:
            raise ValueError(f"lane {lane} already allocated")
        if prompt_len > self.max_seq_len:
            raise ValueError(f"prompt of {prompt_len} exceeds max_seq_len "
                             f"{self.max_seq_len}")
        blocks = self.allocator.alloc(self._blocks_to_start(prompt_len))
        self._install_lane(lane, blocks, cached_len=0)
        if self.slide_window:
            self._lane_prompt[lane] = prompt_len

    def _install_lane(self, lane: int, blocks: List[int],
                      cached_len: int, closed: int = 0) -> None:
        self._lane_blocks[lane] = blocks
        self.block_tables[lane, :len(blocks)] = blocks
        self.seq_lens[lane] = cached_len
        self._lane_sealed[lane] = cached_len // self.block_size
        self._lane_closed[lane] = closed
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
        if not self.prefix_cache_enabled:
            return []
        out: List[int] = []
        for kind, _key, block in self._match_chain(tokens, keys):
            if kind != "dev":
                break
            out.append(block)
        return out

    def _match_chain(self, tokens: Sequence[int],
                     keys: Optional[List[Tuple]] = None) -> List[Tuple]:
        """Longest cached chain covering a block-aligned prefix of
        `tokens` (`keys`: their `chain_keys`, where the caller has them),
        walking THROUGH the spill tier: each entry is
        ("dev", key, block) for a device-resident sealed block or
        ("tier", key, None) for a spilled one (restorable on adopt).  A
        device child behind a spilled parent is reachable again — the
        chain is content-addressed, so the restored parent revalidates
        it by construction."""
        if not self.prefix_cache_enabled:
            return []

        def held(key):
            block = self._index.get(key)
            if block is not None:
                return ("dev", key, block)
            if self.tier is not None and self.tier.contains(key):
                return ("tier", key, None)
            return None

        out: List[Tuple] = []
        if self.window:
            # Whole windows by their summary blocks, then the exact blocks
            # of the window the match ends in.
            if keys is None:
                keys = chain_keys(tokens, self.block_size)
            per, done = self._win_blocks, 0
            while (done + 1) * per <= len(keys):
                parts = [held(_summary_key(keys[(done + 1) * per - 1], s))
                         for s in range(self._sum_blocks)]
                if None in parts:
                    break
                out += parts
                done += 1
            keys = keys[done * per:(done + 1) * per]
        for key in (_iter_chain_keys(tokens, self.block_size)
                    if keys is None else keys):
            entry = held(key)
            if entry is None:
                break
            out.append(entry)
        if self.state is not None:
            held = self._held_with_state(out)
            self._unserved = 0 if held else len(out)
            return held
        return self._held_by_both(out) if self.slide_window else out

    def _held_with_state(self, entries: List[Tuple]) -> List[Tuple]:
        """The longest head of a matched chain of K/V blocks behind which a
        snapshot of the state stands: blocks past it are worth nothing to a
        lane that cannot start its recurrence there."""
        for m in range(len(entries), 0, -1):
            if entries[m - 1][1] in self._snap_index:
                return entries[:m]
        return []

    def _held_by_both(self, entries: List[Tuple]) -> List[Tuple]:
        """The longest head of a matched chain of the growing kind that the
        sliding kind can serve too: of m blocks it needs those that cover
        the last `slide_window` - 1 positions before position m x
        block_size, and nothing of what lies behind them."""
        for m in range(len(entries), 0, -1):
            if all(entries[i][1] in self._slide_index for i in range(
                    self._slide_from(m * self.block_size), m)):
                return entries[:m]
        return []

    def _matched(self, entries: List[Tuple]) -> Tuple[int, int]:
        """(windows closed, tokens covered) of a matched chain."""
        summaries = sum(_is_summary(key) for _kind, key, _b in entries)
        closed = summaries // self._sum_blocks if summaries else 0
        return closed, (closed * self.window
                        + (len(entries) - summaries) * self.block_size)

    def match_len(self, tokens: Sequence[int],
                  keys: Optional[List[Tuple]] = None) -> int:
        """Tokens of `tokens` that `match_prefix`'s blocks cover."""
        entries = list(itertools.takewhile(
            lambda e: e[0] == "dev", self._match_chain(tokens, keys)))
        return self._matched(entries)[1]

    def can_admit_prefix(self, tokens: Sequence[int],
                         headroom_blocks: int = 0,
                         keys: Optional[List[Tuple]] = None,
                         final_len: Optional[int] = None) -> bool:
        """Admission check that accounts for reuse: device-matched blocks
        are referenced (not allocated), but matched blocks currently
        parked evictable stop counting as free capacity once taken.
        Spilled matches still cost an allocation (they restore into
        fresh blocks), so they stay inside `need`.  With `final_len` the
        request is counted at the most blocks it owns on its way there
        (`peak_blocks`), not at its prompt."""
        entries = self._match_chain(tokens, keys)
        dev = [b for kind, _k, b in entries if kind == "dev"]
        need = (self.peak_blocks(max(final_len or 0, len(tokens)),
                                 self._matched(entries)[0])
                - len(dev) + headroom_blocks)
        free_after = (self.allocator.num_free
                      - sum(self.allocator.is_evictable(b) for b in dev))
        return need <= free_after and self._slide_admits(entries)

    def adopt_prefix(self, lane: int, tokens: Sequence[int],
                     keys: Optional[List[Tuple]] = None) -> int:
        """Sequence start with prefix reuse: take shares of the longest
        cached prefix chain (restoring any spilled links from the tier),
        allocate fresh blocks for the rest of the prompt, and report how
        many context tokens came from the cache (the engine skips
        prefilling them).  `keys`: the prompt's `chain_keys`, where the
        caller has them."""
        if self._lane_blocks[lane]:
            raise ValueError(f"lane {lane} already allocated")
        if len(tokens) > self.max_seq_len:
            raise ValueError(f"prompt of {len(tokens)} exceeds max_seq_len "
                             f"{self.max_seq_len}")
        entries = self._match_chain(tokens, keys)
        if self.state is not None and self._unserved:
            self.stats["snapshot_misses"] += 1    # blocks, and no snapshot
        # Pop spilled payloads out of the tier FIRST: once held here,
        # the allocations below can spill other blocks into the tier
        # without LRU pressure dropping the very chain being restored.
        # A pop that misses (aged out since the match) truncates the
        # usable chain at the hole — later links have no K/V under them.
        restores: List[Tuple] = []      # (chain_pos, key, (k_np, v_np))
        usable = len(entries)
        for pos, (kind, key, _b) in enumerate(entries):
            if kind != "tier":
                continue
            payload = self.tier.pop(key)
            if payload is None:
                usable = pos
                break
            restores.append((pos, key, payload))
        if self._sum_blocks > 1 and usable < len(entries) \
                and _is_summary(entries[usable][1]):
            usable -= usable % self._sum_blocks   # whole windows only
        entries = entries[:usable]
        restores = [r for r in restores if r[0] < usable]
        dev_blocks = [b for kind, _k, b in entries if kind == "dev"]
        # Take the device shares FIRST so the fresh allocation below can
        # never evict a block this very request is about to reuse.
        for b in dev_blocks:
            self.allocator.incref(b)
        closed, cached_len = self._matched(entries)
        try:
            fresh = self.allocator.alloc(
                self._blocks_to_start(len(tokens), closed)
                - len(dev_blocks))
        except RuntimeError:
            for b in dev_blocks:
                self.allocator.decref(b)
            for _pos, key, (k_np, v_np) in restores:
                self.tier.put(key, k_np, v_np)   # undo the pops
            raise
        # Assemble the lane's block list in chain order: device hits
        # keep their blocks, spilled hits consume fresh blocks (their
        # contents scatter in below), the prompt tail takes the rest.
        fresh_iter = iter(fresh)
        chain_blocks: List[int] = []
        restored: List[Tuple] = []      # (block, chain_pos, key)
        for pos, (kind, key, b) in enumerate(entries):
            if kind == "dev":
                chain_blocks.append(b)
            else:
                nb = next(fresh_iter)
                chain_blocks.append(nb)
                restored.append((nb, pos, key))
        tail = list(fresh_iter)
        if restored:
            idx = jnp.asarray(np.asarray([b for b, _p, _k in restored],
                                         np.int32))
            self.write_blocks(idx, *(
                None if restores[0][2][i] is None else
                np.stack([payload[i] for _p, _k, payload in restores],
                         axis=1) for i in (0, 1)))
            for nb, _pos, key in restored:
                # Restored blocks re-enter the device index (live now,
                # evictable again once the lane lets go).
                self._index[key] = nb
                self._block_key[nb] = key
                self.allocator.mark_cached(nb)
                self.stats["restored_blocks"] += 1
        cached = chain_blocks
        self._install_lane(lane, cached + tail, cached_len, closed)
        if self.slide_window:
            # The matched tail of the sliding kind, shared like the rest;
            # what the prompt adds there is claimed as it is written
            # (`ensure_capacity`).
            self._lane_prompt[lane] = len(tokens)
            held = self._slide_lane[lane]
            for i in range(self._slide_from(cached_len), len(entries)):
                block = self._slide_index[entries[i][1]]
                self.slide_allocator.incref(block)
                held[i] = block
                self.block_tables[lane, self.max_blocks_per_seq + i] = block
        if cached and self.state is not None:
            # The snapshot behind the last adopted block into the lane's
            # slot, ahead of the lane's first step (and most recently used).
            slot = self._snap_index[entries[-1][1]]
            self.snap_allocator.incref(slot)
            self._move(slot, lane, take=False)
            self.snap_allocator.decref(slot)
            self.stats["snapshots_adopted"] += 1
        if cached:
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
        """Whether `seal_full_blocks` has anything to do for `lane`: a
        caller that must build `tokens` first asks before it does (a
        decoding lane fills a block once in block_size steps, and its
        token list may be a 16k-token document long)."""
        return (self.prefix_cache_enabled and self._lane_sealed[lane]
                < int(self.seq_lens[lane]) // self.block_size)

    def seal_full_blocks(self, lane: int, tokens: Sequence[int],
                         upto: Optional[int] = None) -> None:
        """Index every newly-full block of this lane.  `tokens` is the
        lane's full token sequence (prompt + generated); only the first
        seq_lens[lane] of them have K/V in the pool, and a block seals
        the moment the write cursor crosses its end — mid-prefill too,
        so a concurrent identical prompt can start reusing the prefix
        before the first request even finishes.  `upto`: the tokens to take
        as written where that is more than the committed `seq_lens`
        (`close_window`)."""
        if not self.prefix_cache_enabled:
            return
        bs = self.block_size
        full = (int(self.seq_lens[lane]) if upto is None else upto) // bs
        blocks = self._lane_blocks[lane]
        behind = self._lane_closed[lane] * self._shrink
        while self._lane_sealed[lane] < full:
            i = self._lane_sealed[lane]
            key = (self._lane_parent[lane],
                   tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))
            block = blocks[i - behind]
            # First writer wins: if an identical block is already indexed
            # this one stays un-indexed freight (freed normally later);
            # an adopted shared block re-seals as itself (no-op).
            if key not in self._index and block not in self._block_key:
                self._seal(key, block)
            if self.slide_window:
                # The same token block in the sliding kind, if the lane
                # still holds it.
                block = self._slide_lane[lane].get(i)
                if block is not None and key not in self._slide_index \
                        and block not in self._slide_key:
                    self._slide_index[key] = block
                    self._slide_key[block] = key
                    self.slide_allocator.mark_cached(block)
            self._lane_parent[lane] = hash(key)
            self._lane_sealed[lane] += 1

    def _seal(self, key: Tuple, block: int) -> None:
        """Index `block`'s content under `key`."""
        self._index[key] = block
        self._block_key[block] = key
        self.allocator.mark_cached(block)
        self.stats["sealed_blocks"] += 1
        if self.tier is not None:
            # Re-sealed on device: the spilled copy is stale freight now
            # (content-addressed, so identical).
            self.tier.discard(key)

    def _on_evict(self, block: int) -> None:
        """Allocator reclaimed a cached block: drop its index entry —
        spilling the content into the attached tier first, so the chain
        link survives eviction in SPILLED state.  Children of the
        evicted chain node stay indexed; with a tier they remain
        reachable THROUGH the spilled parent, without one they are
        unreachable until an identical parent is re-sealed — at which
        point they are valid again by construction (content-addressed,
        not block-addressed)."""
        key = self._block_key.pop(block, None)
        if key is not None and self._index.get(key) == block:
            del self._index[key]
            if self.state is not None and key in self._snap_index:
                # A snapshot goes with the block it stands behind.
                slot = self._snap_index.pop(key)
                del self._snap_key[slot]
                self.snap_allocator.uncache(slot)
            if self.tier is not None:
                k_np, v_np = self.read_blocks(
                    jnp.asarray([block], jnp.int32))
                self.tier.put(key, k_np[:, 0],
                              None if v_np is None else v_np[:, 0])

    @property
    def num_indexed_blocks(self) -> int:
        return len(self._index)

    # ---------------- disaggregated handoff / summaries ----------------

    def export_prefix(self, tokens: Sequence[int]) -> Optional[dict]:
        """Snapshot the longest DEVICE-cached chain covering a
        block-aligned prefix of `tokens` as a codec payload: chain
        token-blocks plus gathered K/V contents, enough for a foreign
        cache to rebuild the same content-addressed links.  None when
        nothing is cached."""
        entries = []
        for kind, key, block in self._match_chain(tokens):
            if kind != "dev":
                break           # spilled links don't ship (restore is local)
            entries.append((key, block))
        if not entries:
            return None
        idx = jnp.asarray(np.asarray([b for _k, b in entries], np.int32))
        chain = [list(key[1]) for key, _b in entries]
        if self.kind == "layered":
            # Every kind's blocks, each said to be whose: the growing
            # kind's rows (`k`) and further rows (`extra`) of every block
            # of the chain, the sliding kind's (`slide`) of the blocks from
            # chain position `slide_from` on, which is all a match of this
            # length reads of them.
            first = self._slide_from(len(entries) * self.block_size)
            more = {"extra": [self.read_blocks(idx, 1 + i)
                              for i in range(len(self.extra))]}
            if self.slide_window:
                more.update(slide_from=first, slide=self.read_blocks(
                    jnp.asarray(np.asarray(
                        [self._slide_index[key] for key, _b in
                         entries[first:]], np.int32)), len(self.k) - 1))
            return {"v": 1, "kind": self.kind,
                    "block_size": self.block_size, "chain": chain,
                    "k": self.read_blocks(idx, 0), "v_pool": None,
                    "more": more}
        k_np, v_np = self.read_blocks(idx)
        if self.state is not None:
            # The chain ends where a snapshot stands (`_match_chain`): it
            # goes with the blocks.
            slot = self._snap_index[entries[-1][0]]
            return {"v": 1, "kind": self.kind,
                    "block_size": self.block_size, "chain": chain,
                    "k": k_np, "v_pool": v_np,
                    "more": {"state": np.asarray(self.snaps[:, slot]),
                             "tail": np.asarray(self.snap_tails[:, slot])}}
        if self.window:
            # The kind of each block by the length of its chain entry: a
            # summary block carries its whole window's tokens (every block
            # of one window the same), an exact block its own.
            per = self._sum_blocks
            for i in range(0, sum(_is_summary(k) for k, _b in entries), per):
                at = i // per * self.window
                chain[i:i + per] = [list(map(int, tokens[
                    at:at + self.window]))] * per
        return {
            "v": 1,
            "kind": self.kind,
            "block_size": self.block_size,
            "chain": chain,
            "k": k_np,
            "v_pool": v_np,
        }

    def install_prefix(self, payload: dict) -> int:
        """Adopt foreign sealed blocks (the prefill→decode handoff): for
        each shipped chain node not already present locally, allocate a
        block, scatter the shipped K/V in, and index it at refcount 0
        (evictable) — a subsequent adopt_prefix on the same prompt then
        takes shares exactly as if the blocks had been sealed here.
        Content-addressed and idempotent: repeating the import after a
        failover is a no-op for links already present.  Returns how many
        blocks were installed."""
        if not self.prefix_cache_enabled or not payload:
            return 0
        if payload.get("v") != 1 or payload.get("block_size") != \
                self.block_size or payload.get("kind", "kv") != self.kind:
            return 0
        k_arr, v_arr = payload["k"], payload["v_pool"]
        if tuple(k_arr.shape[2:]) != (self.block_size, self.kv_heads,
                                      self.head_dim) or \
                k_arr.shape[0] != self.pool_shape[0]:
            return 0            # foreign model shape: refuse quietly
        if self.kind == "layered":
            return self._install_layered(payload)
        if self.state is not None and not self._install_snapshot(payload):
            return 0            # blocks behind no snapshot serve nobody
        parent = _ROOT_HASH
        new = []                # (chain_pos, key, block)
        bs, part = self.block_size, 0
        for i, blk_tokens in enumerate(payload["chain"]):
            if self.window and len(blk_tokens) == self.window:
                # A summary block: the chain runs through its window's
                # token blocks once, at the window's first part.
                if part == 0:
                    for j in range(0, self.window, bs):
                        parent = hash((parent, tuple(
                            int(t) for t in blk_tokens[j:j + bs])))
                key = (parent, ("summary", part))
                part = (part + 1) % self._sum_blocks
            else:
                key = (parent, tuple(int(t) for t in blk_tokens))
            present = (key in self._index
                       or (self.tier is not None
                           and self.tier.contains(key)))
            if not present:
                try:
                    # May evict LRU cached blocks (new prefix beats old)
                    # but never steals live capacity: alloc raises only
                    # when everything is referenced, and we stop there.
                    (b,) = self.allocator.alloc(1)
                except RuntimeError:
                    break
                new.append((i, key, b))
            parent = _chain_cursor(key)
        if not new:
            return 0
        idx = jnp.asarray(np.asarray([b for _i, _k, b in new], np.int32))
        pos = np.asarray([i for i, _k, _b in new])
        self.write_blocks(idx, k_arr[:, pos],
                          None if v_arr is None else v_arr[:, pos])
        # Index + park evictable only AFTER every alloc: the blocks stay
        # at refcount 1 through the loop above so a later alloc in the
        # same import can never reclaim an earlier install.
        for _i, key, b in new:
            self._index[key] = b
            self._block_key[b] = key
            self.allocator.mark_cached(b)
            self.allocator.decref(b)
            self.stats["imported_blocks"] += 1
        return len(new)

    def _install_snapshot(self, payload: dict) -> bool:
        """The snapshot a state cache's payload carries, indexed behind the
        last block of its chain; False where it carries none of this
        cache's shape or no snapshot slot can be had."""
        more = payload.get("more") or {}
        state, tail = more.get("state"), more.get("tail")
        if state is None or tail is None or not self.snapshot_slots \
                or tuple(state.shape) != self.snaps.shape[:1] \
                + self.snaps.shape[2:] \
                or tuple(tail.shape) != self.snap_tails.shape[:1] \
                + self.snap_tails.shape[2:]:
            return False
        key, parent = None, _ROOT_HASH
        for blk_tokens in payload["chain"]:
            key = (parent, tuple(int(t) for t in blk_tokens))
            parent = hash(key)
        if key is None:
            return False
        if key in self._snap_index:
            return True
        try:
            (slot,) = self.snap_allocator.alloc(1)
        except RuntimeError:
            return False
        self.snaps = self.snaps.at[:, slot].set(
            jnp.asarray(state, self.snaps.dtype))
        self.snap_tails = self.snap_tails.at[:, slot].set(
            jnp.asarray(tail, self.snap_tails.dtype))
        self._index_snapshot(key, slot)
        return True

    def _install_layered(self, payload: dict) -> int:
        """`install_prefix` for layers of several kinds: the growing kind's
        blocks with their further rows as one, then the sliding kind's
        tail, each indexed under its chain key at refcount 0."""
        more = payload.get("more") or {}
        extra = more.get("extra", [])
        if len(extra) != len(self.extra) or (
                bool(self.slide_window) != ("slide" in more)):
            return 0
        keys, parent = [], _ROOT_HASH
        for blk_tokens in payload["chain"]:
            keys.append((parent, tuple(int(t) for t in blk_tokens)))
            parent = hash(keys[-1])

        def install(alloc, index, block_key, wanted, write):
            new = []
            for pos, key in wanted:
                if key in index:
                    continue
                try:
                    (b,) = alloc.alloc(1)
                except RuntimeError:
                    break
                new.append((pos, key, b))
            if new:
                write(jnp.asarray(np.asarray([b for *_, b in new],
                                             np.int32)),
                      np.asarray([pos for pos, *_ in new]))
            for _pos, key, b in new:
                index[key] = b
                block_key[b] = key
                alloc.mark_cached(b)
                alloc.decref(b)
            return len(new)

        def write_grow(idx, pos):
            self.write_blocks(idx, payload["k"][:, pos], None, 0)
            for i, rows in enumerate(extra):
                self.write_blocks(idx, rows[:, pos], None, 1 + i)

        n = install(self.allocator, self._index, self._block_key,
                    list(enumerate(keys)), write_grow)
        if self.slide_window:
            first = int(more["slide_from"])
            n += install(
                self.slide_allocator, self._slide_index, self._slide_key,
                list(enumerate(keys[first:])),
                lambda idx, pos: self.write_blocks(
                    idx, more["slide"][:, pos], None, len(self.k) - 1))
        self.stats["imported_blocks"] += n
        return n

    def prefix_summary(self, limit: int = 256) -> dict:
        """Compact routing summary: the cumulative chain hashes of every
        sealed block this cache can serve (device index + spill tier),
        newest last, capped at `limit`.  A router holding the request's
        own chain hashes scores this replica by deepest overlap without
        ever shipping tokens."""
        hashes = [hash(k) for k in self._block_key.values()]
        if self.tier is not None:
            hashes.extend(self.tier.summary_hashes())
        # Order-preserving dedup; newest sealed blocks win the cap.
        hashes = list(dict.fromkeys(hashes))[-max(int(limit), 1):]
        return {
            "v": 1,
            "block_size": self.block_size,
            "hashes": hashes,
            "indexed_blocks": len(self._index),
            "tier_blocks": 0 if self.tier is None else len(self.tier),
        }

    # ---------------- windows ----------------

    def window_due(self, lane: int, start: int) -> bool:
        """Whether the lane must `close_window` before position `start` is
        written: `start` opens a window and the one before is still open."""
        return bool(self.window) and start > 0 \
            and start % self.window == 0 \
            and self._lane_closed[lane] < start // self.window

    def close_window(self, lane: int, tokens: Sequence[int]) -> Tuple[
            List[int], List[int]]:
        """The lane's open window is full (`tokens`: the lane's sequence up
        to the window's end at least): its exact blocks `src` leave the
        table and go back to the allocator, fresh blocks `dst` for its
        summary rows take their place, and (src, dst) are returned for the
        device program that makes the one from the other.  The caller
        dispatches that program before any that reads the new table or
        writes a block handed out after this call; the device runs
        programs in dispatch order, so whoever is given a `src` block next
        writes it after the program has read it, and whoever adopts a `dst`
        block from the index reads it after the program has written it.

        The window's still unsealed exact blocks are sealed first (a match
        that ends inside this window may use them, and the chain cursor
        must stand at the window's end), then the summary blocks are
        indexed under that cursor: first writer wins, as for any block."""
        done = self._lane_closed[lane]
        end = (done + 1) * self.window
        self.seal_full_blocks(lane, tokens, upto=end)
        blocks = self._lane_blocks[lane]
        at = done * self._sum_blocks
        src = blocks[at:]
        if len(src) != self._win_blocks:
            raise RuntimeError(f"lane {lane}: window {done} is not full")
        dst = self.allocator.alloc(self._sum_blocks)
        if self.prefix_cache_enabled:
            for part, block in enumerate(dst):
                key = (self._lane_parent[lane], ("summary", part))
                if key not in self._index:
                    self._seal(key, block)
        blocks[at:] = dst
        self.block_tables[lane, at:] = 0
        self.block_tables[lane, at:at + len(dst)] = dst
        self.allocator.free(src)
        self._lane_closed[lane] = done + 1
        self.stats["windows_closed"] += 1
        self._dev_tables = None
        return src, dst

    # ---------------- the sliding kind ----------------

    def _slide_from(self, length: int) -> int:
        """The first table slot a lane of `length` tokens still reads in
        the sliding kind: position `length`, the next written, attends from
        `length - (slide_window - 1)` on."""
        return max(length - (self.slide_window - 1), 0) // self.block_size

    def _slide_peak(self, prefilling: bool) -> int:
        """The most sliding blocks a lane holds at once: its window and the
        positions written past its committed length, wherever the two fall
        in their blocks."""
        ahead = self._ahead if prefilling else 2
        return (self.slide_window + ahead - 2) // self.block_size + 2

    def _slide_admits(self, entries: List[Tuple]) -> bool:
        """Whether the sliding kind has room for one more lane that starts
        from the matched chain `entries`: its own peak less the matched
        tail it shares, beside what every live lane may still claim."""
        if not self.slide_window:
            return True
        tail = [self._slide_index[entries[i][1]] for i in range(
            self._slide_from(len(entries) * self.block_size), len(entries))]
        reserve = sum(
            max(0, self._slide_peak(int(self.seq_lens[lane])
                                    < self._lane_prompt[lane])
                - len(self._slide_lane[lane]))
            for lane, blocks in enumerate(self._lane_blocks) if blocks)
        alloc = self.slide_allocator
        return (self._slide_peak(True) - len(tail) + reserve
                <= alloc.num_free - sum(alloc.is_evictable(b) for b in tail))

    def slide_release(self, lane: int) -> int:
        """Give back the sliding blocks that lie wholly behind the lane's
        window at its committed length (called after every commit); a
        sealed one stays in the index as evictable.  What a step in flight
        reads lies at or past the committed length's window, and whoever is
        given a block next writes it by a program dispatched later.
        Returns how many went back."""
        if not self.slide_window:
            return 0
        held = self._slide_lane[lane]
        keep = self._slide_from(int(self.seq_lens[lane]))
        gone = [slot for slot in held if slot < keep]
        for slot in gone:
            self.slide_allocator.decref(held.pop(slot))
            self.block_tables[lane, self.max_blocks_per_seq + slot] = 0
        if gone:
            self.stats["slide_blocks_freed"] += len(gone)
            self._dev_tables = None
        return len(gone)

    def slide_blocks(self, lane: int) -> Dict[int, int]:
        """{table slot: block} of the sliding blocks the lane holds."""
        return dict(self._slide_lane[lane]) if self.slide_window else {}

    def _on_slide_evict(self, block: int) -> None:
        key = self._slide_key.pop(block, None)
        if key is not None and self._slide_index.get(key) == block:
            del self._slide_index[key]

    def blocks_by_kind(self) -> Tuple[int, int]:
        """(summary blocks, exact blocks) the pool holds now, live or
        cached: the lanes' own and what the prefix index keeps."""
        summary = {b for b, key in self._block_key.items()
                   if _is_summary(key)}
        for blocks, closed in zip(self._lane_blocks, self._lane_closed):
            summary.update(blocks[:closed * self._sum_blocks])
        held = self.allocator.num_blocks - self.allocator.num_unused
        return len(summary), held - len(summary)

    # ---------------- state that is not rows ----------------

    def _move(self, slot: int, lane: int, take: bool) -> None:
        """Dispatch the copy of `lane`'s slot into snapshot slot `slot`
        (`take`) or the other way round, every layer, state and tail.  The
        device runs programs in dispatch order: a snapshot is taken behind
        the step that left the state in the lane's slot, and adopted ahead
        of the lane's first step."""
        src, dst = jnp.int32(lane if take else slot), jnp.int32(
            slot if take else lane)
        if take:
            self.snaps, self.snap_tails = self._copy_slot(
                self.snaps, self.snap_tails, self.state, self.tail, src, dst)
        else:
            self.state, self.tail = self._copy_slot(
                self.state, self.tail, self.snaps, self.snap_tails, src, dst)

    def snapshot(self, lane: int, key: Tuple) -> bool:
        """Keep the state `lane`'s slot holds (once the programs dispatched
        so far have run) as the snapshot behind the block of chain key
        `key`: a slot from the snapshot pool, the least recently used
        unreferenced one if none is free, indexed at once (whoever adopts
        it reads it by a program dispatched later).  False where the index
        has one under `key` already, or no slot can be had."""
        if self.state is None or not self.snapshot_slots \
                or key in self._snap_index:
            return False
        try:
            (slot,) = self.snap_allocator.alloc(1)
        except RuntimeError:
            return False
        self._move(slot, lane, take=True)
        self._index_snapshot(key, slot)
        self.stats["snapshots_taken"] += 1
        return True

    def _index_snapshot(self, key: Tuple, slot: int) -> None:
        """`slot` (held at refcount 1) into the index under `key`, parked
        evictable."""
        self._snap_index[key] = slot
        self._snap_key[slot] = key
        self.snap_allocator.mark_cached(slot)
        self.snap_allocator.decref(slot)

    def _on_snap_evict(self, slot: int) -> None:
        key = self._snap_key.pop(slot, None)
        if key is not None and self._snap_index.get(key) == slot:
            del self._snap_index[key]

    def state_stats(self) -> dict:
        """Slots of state and of snapshots, and what the index did with the
        latter (engine `stats()["ssm"]`)."""
        alloc = self.snap_allocator
        return {
            "state_slots": self.max_lanes,
            "state_slots_live": sum(bool(b) for b in self._lane_blocks),
            "state_bytes": int(self.state.nbytes + self.tail.nbytes),
            "snapshot_slots": self.snapshot_slots,
            "snapshot_slots_live": len(self._snap_index),
            "snapshot_bytes": int(self.snaps.nbytes + self.snap_tails.nbytes),
            "snapshots_evicted": alloc.evictions,
            "snapshots_taken": self.stats["snapshots_taken"],
            "snapshots_adopted": self.stats["snapshots_adopted"],
            "snapshot_misses": self.stats["snapshot_misses"],
        }

    # ---------------- lane growth / teardown ----------------

    def ensure_capacity(self, lane: int, new_len: int) -> None:
        """Grow the lane's table as decode crosses block boundaries (with
        windows, inside the open one: `close_window` comes first)."""
        if new_len > self.max_seq_len:
            raise RuntimeError(f"lane {lane} exceeded max_seq_len")
        if self.window and (new_len - 1) // self.window \
                > self._lane_closed[lane]:
            raise RuntimeError(f"lane {lane}: window not closed")
        need = self.blocks_needed(new_len)
        blocks = self._lane_blocks[lane]
        while len(blocks) < need:
            (b,) = self.allocator.alloc(1)
            self.block_tables[lane, len(blocks)] = b
            blocks.append(b)
            self._dev_tables = None
        if self.slide_window:
            held = self._slide_lane[lane]
            for slot in range(self._slide_from(int(self.seq_lens[lane])),
                              need):
                if slot not in held:
                    (held[slot],) = self.slide_allocator.alloc(1)
                    self.block_tables[
                        lane, self.max_blocks_per_seq + slot] = held[slot]
                    self._dev_tables = None

    def truncate_lane(self, lane: int, new_len: int) -> None:
        """Speculative rollback: release the table-tail blocks past what
        ``new_len`` committed tokens need.  Rejected draft tokens were
        written at positions >= the committed length; their K/V is
        garbage the attention mask already hides (positions >= ctx_len
        never get attended, and real tokens overwrite those slots before
        the context grows across them), so rollback is pure block
        accounting.  Only wholly-uncommitted tail blocks are released —
        they are always fresh, exclusively-owned allocations (shared
        prefix blocks live at the front of the table, and the sealed
        boundary never passes the committed length), so decref returns
        them straight to the free list.

        The same holds for the one position a step dispatched ahead of its
        predecessor's result writes for a request that result then ended
        (an `eos`, a cancel, a deadline): it lies at or past the committed
        length in a block the lane owns alone, claimed by
        `ensure_capacity` or already there, and `free_lane` returns that
        block with the others.  The device runs programs in dispatch
        order, so whoever gets the block next writes a position before it
        reads it."""
        if self.state is not None:
            raise NotImplementedError(
                "a state cache does not truncate a lane: the recurrent state "
                "has been overwritten past the new length and cannot be "
                "rolled back")
        blocks = self._lane_blocks[lane]
        keep = max(self.blocks_needed(new_len), self._lane_sealed[lane]
                   - self._lane_closed[lane] * self._shrink)
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
        blocks = self._lane_blocks[lane]
        for b in blocks:
            self.allocator.decref(b)
        if self.slide_window:
            self.slide_allocator.free(self._slide_lane[lane].values())
            self._slide_lane[lane] = {}
            self._lane_prompt[lane] = 0
        self._lane_blocks[lane] = []
        self.block_tables[lane, :] = 0
        self.seq_lens[lane] = 0
        self._lane_sealed[lane] = 0
        self._lane_closed[lane] = 0
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
        """The tables as a step takes them: a copy made now, on the host.
        (`jnp.asarray` of a numpy array is the same memory on the CPU
        backend, `jnp.array`'s copy is made by the device in its turn, and
        a step dispatched ahead runs after the host has gone on: a lane's
        row is rewritten when its window closes or slides and zeroed when
        it ends.)"""
        if self._dev_tables is None:
            self._dev_tables = jnp.asarray(self.block_tables.copy())
        return self._dev_tables

    @property
    def step_pools(self) -> tuple:
        """(k, v) as a step takes and returns them: the pools, or over a
        state cache ((K, V, state, tail), None), the model's mixer taking
        the buffers behind its attention's."""
        if self.state is not None:
            return (self.k, self.v, self.state, self.tail), None
        return self.k, self.v

    def update_pools(self, k: jax.Array, v: Optional[jax.Array]) -> None:
        """Rebind the functional pools returned by a jitted step (`v` None
        where the cache is latent, and over a state cache: `step_pools`)."""
        if self.state is not None:
            k, v, self.state, self.tail = k
        self.k = k
        self.v = v

    # ---------------- the wire format's boundary ----------------

    @property
    def pool_shape(self) -> tuple:
        """The stored shape of the (first) pool."""
        return (self.k[0] if self.kind == "layered" else self.k).shape

    def _pool_rows(self) -> List[Tuple[int, int]]:
        """(kv_heads, head_dim) of a row of each pool of a layered cache."""
        rows = [(self.kv_heads, self.head_dim)] + [(1, w) for w in self.extra]
        return rows + ([self._slide_row] if self.slide_window else [])

    def read_blocks(self, idx: jax.Array, pool: Optional[int] = None
                    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Blocks `idx` of the pools in the wire format
        [n_layers, n, block_size, kv_heads, head_dim], on the host: (K, V),
        or (latent rows, None) with `kind` "latent"; of a layered cache
        the blocks of its pool number `pool`, an array."""
        if pool is not None:
            return np.asarray(unpack_kv_rows(self.k[pool][:, idx],
                                             *self._pool_rows()[pool]))
        return tuple(None if pool is None else np.asarray(unpack_kv_rows(
            pool[:, idx], self.kv_heads, self.head_dim))
            for pool in (self.k, self.v))

    def write_blocks(self, idx: jax.Array, k_blocks, v_blocks,
                     pool: Optional[int] = None) -> None:
        """Store wire-format blocks at `idx` (pad columns stay zero); of a
        layered cache `k_blocks` into its pool number `pool`."""
        if pool is not None:
            pools = list(self.k)
            pools[pool] = pools[pool].at[:, idx].set(
                pack_kv_rows(jnp.asarray(k_blocks, pools[pool].dtype)))
            self.k = tuple(pools)
            return
        self.k = self.k.at[:, idx].set(
            pack_kv_rows(jnp.asarray(k_blocks, self.k.dtype)))
        if self.v is not None:
            self.v = self.v.at[:, idx].set(
                pack_kv_rows(jnp.asarray(v_blocks, self.v.dtype)))
