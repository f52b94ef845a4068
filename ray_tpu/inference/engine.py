"""Continuous-batching generation engine over the paged KV cache.

One jitted step advances a fixed-capacity LANE array: every live
sequence owns a lane, new requests are admitted into lanes the moment
their previous occupant finishes (mid-flight — no batch barrier), and
padding lanes ride along masked.  Two compiled step shapes total — the
pure decode step (T=1, single-query paged attention — the Pallas kernel
path) and the prefill step (T=prefill_chunk) — and when both
populations are live they dispatch SEPARATELY each scheduler
iteration: decode lanes advance at T=1 cost instead of being charged a
whole prefill chunk of FLOPs just because some other lane is still
prefilling.

Admission rides the prefix cache (kv_cache.py): the longest
block-aligned cached prefix of a prompt is adopted by reference instead
of re-prefilled, so shared system prompts / few-shot templates /
multi-turn history cost their FLOPs once.  Newly-full blocks are sealed
into the content-addressed index as the write cursor crosses them —
mid-prefill included.

Sampling is part of the jitted step: greedy is argmax, temperature
sampling draws from a per-lane PRNG key folded from (request seed,
tokens produced), so sampled output is reproducible per request seed
regardless of batch composition, and the per-step device->host transfer
is one int32 per lane — never the [B, V] logits.

Speculative decoding (``spec_k > 0``, speculative.py) lifts the
one-token-per-step ceiling: a host-side draft proposer suggests up to k
continuation tokens per decode lane from the request's own history, the
step verifies all k+1 positions at once (the chunked-prefill dispatch
shape, per-position in-graph sampling with the SAME fold_in(seed,
produced+j) keys the plain step would use), the longest draft prefix
matching the model's own sampled output commits as one atomic burst,
and the rejected tail rolls back through paged-KV block truncation —
token-exact vs the non-speculative engine by construction, for greedy
and seeded sampling alike.

The engine is host-driven: block allocation, admission and stream
fan-out are Python; the model math (sampling included) is one jax.jit'ed
call per dispatched population.  The scheduler runs one step AHEAD of its
own results: an iteration admits, builds and dispatches step t+1 while
the device still runs step t, and only then fetches and commits step t,
so the host's work happens under a device program and not between two.
Step t+1 is built from what step t will have left, which the host knows
by counting (lengths, positions, sampling counters, a finish by length);
the one thing it does not know, each lane's sampled token, stays on the
device in an int32 array every step takes and hands back.  What only the
fetch can tell (an `eos`) finds one token computed too many: its row is
discarded at commit.  A draft proposer needs the token on the host, so a
speculative engine runs the same loop at depth 0: it fetches each step
in the iteration that dispatched it (`InferenceEngine.step`).

What the host does compute for a step (tokens, positions, masks, context
lengths, sampling temperatures, seeds and counters, a compact program's
rows) it writes into ONE int32 buffer a population (`_lane_views`: a lane a
row, floats and unsigned seeds by their bits) and hands to the device in
one transfer (`_upload`); the compiled program takes the buffer apart in
front of the step (`_make_entry`, `_unpack_lanes`).  A transfer costs the
host the same call whatever its bytes, and nine of them were two thirds of
an iteration's host work (PERF.md section 6, PR 42).  `stats()["upload"]`
counts populations, transfers (the block tables' copy goes only when a
table changed) and bytes.

The KV pools are donated on TPU and
ride the step's layer loop whole: a step writes the blocks its new
tokens fall in and reads the blocks it attends over in the engine's one
buffer
(`compiled_steps()` reports `pool_copies`, which must be 0).

What the cache asks of the loop beside its steps it says once, at
construction (kv_cache.py: `closes`, `releases`, `checkpoints`, and
`no_rollback`, which refuses speculative decoding); the device programs and
their order are the engine's.  Lanes that hold windows (EVA) add a third
program, the compaction: when a lane's next position opens a window, the
host closes the one before (`PagedKVCache.close_window`) and dispatches
`model.compact_cached` for it, a [prefill_lanes] batch by block index,
between the step in flight, which wrote the window's last row, and the step
being built, which reads the summaries.  Positions are the host's by
counting, so the loop stays a step ahead; a prefill chunk is cut at a
window's edge (`window_room`).  A cache with further pools and a second
table (dots3) hands the step all pools as one tuple and ONE table array, so
a population's uploads stay what they were; what a commit leaves behind a
lane's window goes back as the last part of `commit`
(`PagedKVCache.after_commit`, the record's `windows_ms`).  A cache with
state that is not rows (Falcon-H1) hands the step its pools and state
buffers as one tuple (`step_pools`); a lane's slot there is its index, and
a compact program's rows name theirs (`forward_cached`'s `slots`).  A
prefilling lane starts its scan from zero at position 0 and otherwise from
what its slot holds (at admission the snapshot `adopt_prefix` copied in);
padded rows and lanes that are not stepped pass through as the recurrence's
identity.  A checkpoint is taken where the prefix index has SEEN a shared
head end (blocks its match found and no snapshot could serve: the lane
that prefills them again cuts a chunk there), and by a lane that matched
nothing behind the last whole chunk of its prompt that leaves a final
chunk to prefill (`_snapshot_due`); never by a lane that adopted all its
match, whose further chunks are its own turn.  The copy program is
dispatched behind that chunk's step (`PagedKVCache.checkpoint`).
`stats()` adds `eva`, `sparse` and `windows`, or `ssm` by the cache's
`kind`.

The weights the step multiplies are prepared once, not in every step:
`model.serving_params` (one compiled program at construction and at
every `update_params`) holds each leaf the cached forward would cast at
its use in the activation dtype, in the form its matmul reads in place;
`engine.params` stays what the caller gave.  A tree already held so is
served as it is (`compiled_steps()` reports `weight_bytes_copied`: the
bytes of weight-shaped converts, copies and slices a step still makes).
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import logging
import queue
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu import models
from ray_tpu._private import compile_cache
from ray_tpu._private.accelerators import leased_chips, require_chip_lease
from ray_tpu.inference.compiled import (count_pool_copies,
                                        count_select_sorts,
                                        count_weight_bytes_copied)
from ray_tpu.inference.kv_cache import PagedKVCache, chain_keys
from ray_tpu.models.decoder import Lanes, layer_counts
from ray_tpu.ops.attention import paged_blocks_per_step
from ray_tpu.util import events, spans
from ray_tpu.util.metrics import Counter, Gauge, Histogram

logger = logging.getLogger(__name__)

_DONE = object()


def _setup() -> dict:
    """The process's start-up record (`events.pinned`) folded: `start`
    (the process's, on `time.time()`), `seconds` by kind over every row,
    and `programs`: each `engine.dispatch/make_program` row's payload (its
    key and split) with its start and duration."""
    record = events.pinned()
    seconds: dict = {}
    for row in record["rows"]:
        seconds[row["kind"]] = seconds.get(row["kind"], 0.0) + row["dur"]
    return {"start": record["start"], "seconds": seconds,
            "programs": [dict(row["payload"], start=row["start"],
                              dur=row["dur"])
                         for row in record["rows"]
                         if row["kind"] == "make_program"]}


# A program's first call: nested in the `dispatch` phase (or, a compaction
# program's, in `build_batch`) under a name of the parts' kind.
_MAKE_PROGRAM = ("engine.dispatch", "make_program")


@contextlib.contextmanager
def _making(key):
    """Around a program's first call (it is made once a process): one
    `engine.dispatch/make_program` span, kept in the start-up record, with
    the program's key and, at its end, what jax reported meanwhile
    (`compile_cache.counters()`'s difference: seconds tracing, lowering,
    loading from the persistent cache and compiling; the rest of the span is
    the first run and the heap's tidying behind it).  Under the same name an
    annotation in the jax profiler's trace, so that a program made inside a
    traced slice lies over the gap it causes (the `engine.` prefix keeps it
    out of the readers of the flat phases, like the parts of a phase).
    Yields the dict that will hold the split, `wall_s` included."""
    made: dict = {}
    c0 = compile_cache.sums()
    tok = spans.begin(*_MAKE_PROGRAM, pin=True, key=list(key))
    try:
        with spans.phase(*_MAKE_PROGRAM) as ph:
            yield made
    finally:
        c1 = compile_cache.sums()
        made.update({k: c1[k] - c0[k] for k in (
            "trace_s", "lower_s", "cache_load_s", "compile_s")},
            cached=c1["cache_hits"] - c0["cache_hits"], wall_s=ph.seconds)
        spans.end(tok, **made)

# The flat phases of one scheduler step (spans.phase: profiler annotations
# `engine/<phase>`, seconds into the step's one ring record and stats()).
_PHASES = ("admit", "build_batch", "dispatch", "fetch", "commit")
# What `build_batch` and `commit` are made of: each also a spans.phase, nested
# in its phase as `engine.build_batch/<part>` or `engine.commit/<part>` (the
# `engine.` prefix keeps them out of every reader of the flat `engine/`
# names), milliseconds into the step's record, seconds into stats()["part_s"].
_PARTS = ("windows", "assemble", "upload", "release", "lock", "deliver")
# One row of stats()["timeline"]: the loop's sums over one wall-clock second
# (`phase_s` in the order of _PHASES, `part_s` of _PARTS).
_TIMELINE = ("t", "steps", "prefill_steps", "wall_s", "phase_s", "part_s",
             "cpu_s", "cpu_wall_s", "cpu_steps", "gc_s", "longest_ms",
             "longest_phase")
_TIMELINE_ROWS = 128
# An iteration longer than this leaves one `engine/stall` event.
_STALL_S = 0.5
# The rows a step's products may have and still be bound by the weights
# they read: a v5e multiplies some 240 rows of bf16 in the time it reads
# the matrix (197 TFLOP/s over 819 GB/s), so under that many a masked row of
# a pair's chunk costs its lane's share of the chunk's attention and write
# and little else.  An engine that is not told its `prefill_lanes` takes as
# many as stay under it (`_chunk_lanes`).  gpt2-xl's pair at 16 lanes and a
# chunk of 32, one lane prefilling: 8.9 ms at 2 lanes of chunk, 9.6 at 4
# (the rule's), 11.7 at 8, where the two programs before it took 20.4
# (PERF.md section 6, PR 53).
_PAIR_ROWS = 256


def _chunk_lanes(max_lanes: int, chunk: int) -> int:
    """The chunk's lanes of an engine that names none: the largest power of
    two that keeps max_lanes + lanes x chunk rows within `_PAIR_ROWS`, at
    least one, at most every lane."""
    n = 1
    while 2 * n <= max_lanes and max_lanes + 2 * n * chunk <= _PAIR_ROWS:
        n *= 2
    return n


# The thread's CPU clock is read in one iteration of this many on average,
# drawn and not counted off, so that no rhythm of the traffic falls in step
# with it: the clock is a system call (5.5 us alone and 16 us in a serving
# replica on a v5e's host, four reads an iteration; PERF.md section 6,
# PR 35), `perf_counter` is not.
_CPU_EVERY = 4

# The collector's pauses, counted where they happen (`gc.callbacks`; hooked
# by the first engine of a process): collections by generation, their
# seconds, and those of the full ones.  Whichever thread allocates runs a
# collection and every other stands still meanwhile, so the seconds that
# fall inside an iteration of the engine's loop are that iteration's.
_GC = {"collections": [0, 0, 0], "seconds": 0.0, "full_seconds": 0.0,
       "t0": 0.0}


def _gc_hook(phase: str, info: dict) -> None:
    if phase == "start":
        _GC["t0"] = time.perf_counter()
        return
    took = time.perf_counter() - _GC["t0"]
    gen = info["generation"]
    _GC["collections"][gen] += 1
    _GC["seconds"] += took
    if gen == 2:
        _GC["full_seconds"] += took

_MET = None

# SLO latency buckets: generation latencies live in the 1ms–60s range;
# sub-ms resolution at the low end keeps TBT percentiles meaningful.
_LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def _metrics() -> dict:
    global _MET
    if _MET is None:
        _MET = {
            "hit_tokens": Counter(
                "inference_prefix_hit_tokens",
                "Prompt tokens served from the KV prefix cache"),
            "miss_tokens": Counter(
                "inference_prefix_miss_tokens",
                "Prompt tokens prefilled from scratch"),
            "hits": Counter(
                "inference_prefix_hits",
                "Admissions that reused at least one cached block"),
            "misses": Counter(
                "inference_prefix_misses",
                "Admissions with no cached prefix"),
            "evicted": Counter(
                "inference_kv_blocks_evicted",
                "Cached KV blocks reclaimed under pool pressure"),
            "queue_depth": Gauge(
                "inference_waiting_requests",
                "Requests queued behind lane admission"),
            "ttft": Histogram(
                "inference_ttft_s",
                "Time to first token (submit -> first emit)",
                buckets=_LATENCY_BUCKETS),
            "tbt": Histogram(
                "inference_tbt_s",
                "Time between tokens (per-decode emit gap)",
                buckets=_LATENCY_BUCKETS),
            "spec_drafted": Counter(
                "inference_spec_drafted_tokens",
                "Draft tokens proposed for speculative verification"),
            "spec_accepted": Counter(
                "inference_spec_accepted_tokens",
                "Draft tokens accepted by the verify step"),
            "spec_steps": Counter(
                "inference_spec_steps",
                "Speculative verify dispatches"),
            "spec_per_step": Histogram(
                "inference_spec_tokens_per_step",
                "Tokens emitted per lane per speculative verify step "
                "(plain decode would be exactly 1)",
                buckets=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0)),
        }
    return _MET


@dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    eos_id: Optional[int] = None
    seed: int = 0
    out: "queue.Queue" = field(default_factory=queue.Queue)
    # Sampling-counter base: a request resumed after a mid-stream
    # failover re-prefills prompt+produced but must keep drawing from
    # fold_in(seed, OVERALL position) to stay seed-consistent with the
    # unfaulted run.
    sample_offset: int = 0
    deadline: Optional[float] = None   # monotonic; lane evicted past it
    # Flight-recorder / SLO bookkeeping: the trace context is captured at
    # submit() time because every later hop (scheduler thread, _commit)
    # runs outside the submitter's contextvars.
    trace: Optional[tuple] = None
    submitted: float = 0.0             # wall time of submit()
    submitted_pc: float = 0.0          # perf_counter of submit(): queue wait
    last_emit: float = 0.0             # wall time of the previous token
    fed: int = 0            # prompt tokens in the cache (prefilled OR reused)
    produced: int = 0
    # Dispatched and not yet committed (the scheduler runs a step ahead of
    # its results): positions the steps in flight write for this request,
    # and tokens they sample.  `fed`, `produced`, `emitted`, `last_token`
    # and the cache's `seq_lens` are the committed side.
    ahead_len: int = 0
    ahead_new: int = 0
    # Open engine spans for TRACED requests only: the prefill span
    # (submit -> first token) until produced==1, then ONE decode span
    # (first token -> finish); under the prefill span its child, the
    # queue span (submit -> lane).  Untraced requests never pay for these.
    span_tok: object = None
    queue_tok: object = None
    last_token: int = 0
    emitted: List[int] = field(default_factory=list)
    finish_reason: Optional[str] = None
    # Speculative state: the lane's current adaptive draft ceiling and
    # the draft tokens riding the in-flight verify dispatch.
    spec_k: int = 0
    draft: tuple = ()
    # Per-token behavior log-probs (capture_logp engines only), parallel
    # to `emitted` — the RL rollout path needs the sampling
    # distribution's log-prob of every committed token for V-trace.
    logps: List[float] = field(default_factory=list)
    # Disaggregated prefill: run chunked prefill + seal the prompt's
    # blocks, then finish WITHOUT sampling — the sealed chain is the
    # product (export_prefix ships it to a decode engine).
    prefill_only: bool = False
    # The prompt's block chain (`kv_cache.chain_keys`), made by `submit`
    # on the caller's thread where the prefix cache is on: admission looks
    # the prompt's blocks up by it and walks no prompt (`_admit`,
    # `_head_is_being_sealed`).
    chain: Optional[List[tuple]] = None
    # Over a cache that checkpoints: the prompt length behind which the
    # cache wants one of this lane (`PagedKVCache.checkpoint_wanted`; 0:
    # nowhere), and whether its admission matched no block at all.
    snap_at: int = 0
    cold: bool = False

    @property
    def prefilling(self) -> bool:
        return self.fed < len(self.prompt)

    @property
    def next_fed(self) -> int:
        """`fed` as the step in flight will leave it."""
        return self.fed + self.ahead_len if self.prefilling else self.fed

    def samples(self, fed: int, chunk: int) -> bool:
        """Whether the step that takes this request from `fed` prompt
        tokens over `chunk` more positions samples a token for it: every
        decode step does, and the prefill chunk that ends the prompt."""
        return fed + chunk >= len(self.prompt) and not self.prefill_only


class GenerationHandle:
    """Streaming view of one request: iterate to receive token ids as
    the engine emits them (the serve stream-ticket path pulls these)."""

    def __init__(self, req: _Request, engine: "InferenceEngine" = None):
        self._req = req
        self._engine = engine
        # A speculative burst arrives as ONE queue item (a list): the
        # commit is atomic — a consumer never observes a partially
        # delivered draft burst — and iteration unwraps it here.
        self._buf: collections.deque = collections.deque()

    def cancel(self) -> bool:
        """Abort the request: evict its engine lane (or dequeue it) and
        unblock any consumer with end-of-stream.  Idempotent; False if
        the request had already finished."""
        if self._engine is None:
            return False
        return self._engine.cancel(self._req)

    def __iter__(self):
        return self

    def __next__(self) -> int:
        if self._buf:
            return self._buf.popleft()
        item = self._req.out.get()
        if item is _DONE:
            raise StopIteration
        if isinstance(item, list):
            self._buf.extend(item)
            return self._buf.popleft()
        return item

    def ready(self) -> bool:
        """Whether the next token, or the stream's end, is here: `next()`
        would not wait.  For the one consumer that iterates."""
        return bool(self._buf) or not self._req.out.empty()

    def tokens(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the request finishes; returns all generated ids.

        `timeout` is an OVERALL deadline for the whole generation, not a
        per-token gap: if the request has not finished `timeout` seconds
        from this call, the request is CANCELLED (its lane evicted — a
        vanished consumer must not leave the engine generating for
        nobody) and TimeoutError is raised (never queue.Empty)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        out: List[int] = list(self._buf)
        self._buf.clear()
        while True:
            if deadline is None:
                item = self._req.out.get()
            else:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self.cancel()
                    raise TimeoutError(
                        f"generation did not finish within {timeout}s "
                        f"({len(out)} token(s) received)")
                try:
                    item = self._req.out.get(timeout=remaining)
                except queue.Empty:
                    self.cancel()
                    raise TimeoutError(
                        f"generation did not finish within {timeout}s "
                        f"({len(out)} token(s) received)") from None
            if item is _DONE:
                return out
            if isinstance(item, list):
                out.extend(item)
            else:
                out.append(item)

    @property
    def finish_reason(self) -> Optional[str]:
        return self._req.finish_reason

    @property
    def logps(self) -> List[float]:
        """Behavior log-probs of the committed tokens (parallel to the
        emitted stream).  Empty unless the engine was built with
        ``capture_logp=True``."""
        return list(self._req.logps)


def _lane_views(n: int, t: int, compact: bool, max_lanes: int, out=None):
    """A population's lane arrays as views of ONE int32 buffer [n, 3 t + 5]
    (a lane a row: `t` columns each of `tokens`, `positions` and `valid`,
    then `ctx_lens`, `gather`, `temps`, `seeds`, `counters`, and a compact
    program's `rows` last), so that they reach the device in one transfer.
    `temps` (float32) and `seeds` (uint32) lie there by their bits, `valid`
    as 0 / 1.  Nobody's lanes: masked, context 1, sampling nothing, no
    row's lane.  `out`: the zeroed buffer to lay them in (a pair's part of
    the pair's).  Returns (buffer, the eight views, `rows` or None)."""
    lanes = np.zeros((n, 3 * t + 5 + compact), np.int32) if out is None \
        else out
    tokens, positions, valid = (lanes[:, i * t:(i + 1) * t] for i in range(3))
    ctx_lens, gather, temps, seeds, counters = (
        lanes[:, 3 * t + i] for i in range(5))
    ctx_lens[:] = 1
    counters[:] = -1
    rows = None
    if compact:
        rows = lanes[:, -1]
        rows[:] = max_lanes
    return lanes, (tokens, positions, valid, ctx_lens, gather,
                   temps.view(np.float32), seeds.view(np.uint32),
                   counters), rows


# The columns of a T=1 population's buffer (`_lane_views`: 3 x 1 + 5).
_T1_COLUMNS = 8


def _pair_views(max_lanes: int, n: int, t: int):
    """The buffer of a pair's program, flat: the decoding lanes' [max_lanes,
    8] (`_lane_views` at one position) and behind it the chunk's compact
    [n, 3 t + 6], both populations in ONE transfer.  Returns (buffer, the
    decode part's `_lane_views`, the chunk's)."""
    cut = max_lanes * _T1_COLUMNS
    flat = np.zeros((cut + n * (3 * t + 6),), np.int32)
    return (flat,
            _lane_views(max_lanes, 1, False, max_lanes,
                        flat[:cut].reshape(max_lanes, _T1_COLUMNS)),
            _lane_views(n, t, True, max_lanes, flat[cut:].reshape(n, -1)))


def _unpack_lanes(lanes, t: int) -> tuple:
    """`_lane_views`' arrays out of the buffer on the device, as a step
    takes them: static slices, `valid` by `!= 0`, `temps` and `seeds` by
    their bits (a compact program's `rows` ninth)."""
    tokens, positions, valid = (lanes[:, i * t:(i + 1) * t] for i in range(3))
    ctx_lens, gather, temps, seeds, counters, *rows = (
        lanes[:, i] for i in range(3 * t, lanes.shape[1]))
    return (tokens, positions, valid != 0, ctx_lens, gather,
            jax.lax.bitcast_convert_type(temps, jnp.float32),
            jax.lax.bitcast_convert_type(seeds, jnp.uint32), counters, *rows)


def _by_lane(per_row, rows, max_lanes: int):
    """A compact program's per-row results [rows, T] as [max_lanes, T]; a
    pair's [max_lanes + rows, T] likewise: the decoding lanes' own rows
    first, then the chunk's, each given to its lane (a lane is in one of
    the two)."""
    out = np.zeros((max_lanes,) + per_row.shape[1:], per_row.dtype)
    own = len(per_row) - len(rows)
    out[:own] = per_row[:own]
    held = rows < max_lanes
    out[rows[held]] = per_row[own:][held]
    return out


def _end_spans(req: _Request, **payload) -> None:
    """Close whatever spans a traced request still has open: the queue
    span inside its prefill span, or its decode span."""
    spans.end(req.queue_tok, **payload)
    spans.end(req.span_tok, **payload)
    req.queue_tok = req.span_tok = None


class InferenceEngine:
    """max_lanes concurrent sequences over one shared paged KV pool.

    `auto_start=True` (default) runs the scheduler on a daemon thread —
    submit() returns a streaming GenerationHandle immediately.  With
    auto_start=False the caller drives `step()` (deterministic tests,
    microbenchmarks).  `prefix_cache=False` disables content-addressed
    block reuse (every prompt prefills from token zero — the cold
    baseline).

    `spec_k > 0` enables speculative decoding: `draft_proposer`
    (``"ngram"`` or a speculative.DraftProposer) suggests up to spec_k
    continuation tokens per decode lane and one verify dispatch commits
    the accepted prefix as a burst.  `spec_adaptive` backs each lane's
    draft length off when its acceptance is low (and grows it back on
    full acceptance) so incompressible streams stop paying rejected
    verify FLOPs.

    The lanes that prefill are one compact [prefill_lanes, T] batch (the
    oldest requests first; further prefilling lanes wait a step), gathered
    from and scattered to their lanes by index, and they RIDE the decoding
    lanes' step: an iteration that admits dispatches ONE program (the
    pair's: `_make_step_fn`), in which every row-wise product of a layer
    runs once over the [max_lanes, 1] rows and the [prefill_lanes, T] rows
    laid end to end, so that it reads the weights once where a T=1 program
    and a prefill program behind it read them twice (PERF.md section 6,
    PR 53).  An iteration in which nobody prefills runs the T=1 program
    alone; one in which nobody decodes the pair's with its decode rows
    masked.  An engine with a draft proposer keeps the two populations in
    two programs, the decoding lanes' first.  `prefill_lanes` not named is
    as many lanes as keep the pair's rows (max_lanes + prefill_lanes x T)
    under `_PAIR_ROWS`, where a product is still bound by the weights it
    reads and a masked row costs little, a power of two.  An
    engine whose `prefill_lanes` is NAMED, under max_lanes, says that its
    chunks are long (rows of them are compute): it also has the program at
    a quarter of T, for steps in which no lane has more than that left to
    feed (`_prefill_len`), and each of the two at ONE row, for steps in
    which one lane prefills (an admission behind a cached head is alone in
    its program more often than not, and three rows of four were padding):
    four programs, a T's second made behind its first (`_warm_widths`)
    once nobody waits on it.

    Whoever waits for a token waits for at most ONE program to be made (a
    program of a cache of several kinds of layer takes 27 s on a v5e's
    host, a stream's step gives up at 60, and three ahead of a first token
    were 57-64 s: PERF.md section 6, PR 41): a population whose program is
    still to be made is not dispatched ahead of a step in flight, which is
    landed first (`_made`), and a sibling width is made when the loop goes
    idle, or behind a commit with no program made since the commit before
    (`_cold`).
    """

    def __init__(self, model="gpt", config="nano", params=None, *,
                 max_lanes: int = 8, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 prefill_chunk: int = 32,
                 prefill_lanes: Optional[int] = None, seed: int = 0,
                 prefix_cache: bool = True, auto_start: bool = True,
                 spec_k: int = 0, draft_proposer="ngram",
                 spec_adaptive: bool = True,
                 kv_tier: Optional[bool] = None,
                 capture_logp: bool = False):
        require_chip_lease("InferenceEngine")
        compile_cache.watch()      # before this engine's first program
        self.model = models.family(model)
        self.config = (self.model.CONFIGS[config] if isinstance(config, str)
                       else config)
        # What an engine does once goes into the process's start-up record
        # (`pin=True`): the client's start here, then the weights, their
        # preparation and the pools, each waited for inside its span.
        with spans.span("proc", "backend_init", pin=True):
            device = jax.devices()[0]
        self.backend = device.platform
        self.device_kind = device.device_kind
        logger.info("InferenceEngine on backend=%s device_kind=%s chips=%s",
                    self.backend, self.device_kind, leased_chips() or "-")
        if params is None:
            # One compiled program, not one dispatch per op: building a
            # gpt2-small engine op by op took 55 s on a v5e chip.
            with spans.span("engine", "init_params", pin=True):
                params = jax.block_until_ready(
                    jax.jit(self.model.init_params, static_argnums=0)(
                        self.config, jax.random.key(seed)))
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        # What the caller gave, and what the step takes (_prepare).
        self.params = params
        self._weights = {"prepared": 0, "prepare_s": 0.0,
                         "served_bytes": 0, "given_bytes": 0}
        with spans.span("engine", "prepare", pin=True):
            self._served = self._prepare(params)
        # An expert configuration's load counters live on the device and
        # ride the step (forward_cached's `moe_load`); stats() fetches.
        n_experts = self.config.n_experts
        held = getattr(self.config, "n_experts_held", 0) or n_experts
        self._moe_load = (jnp.zeros((held + 2,), jnp.int32)
                          if n_experts else None)
        self.max_lanes = max_lanes
        self.prefill_chunk = prefill_chunk
        # (named under max_lanes: the quarter-T form and the one-row width)
        self._widths = bool(prefill_lanes) and prefill_lanes < max_lanes
        self.prefill_lanes = min(
            prefill_lanes or _chunk_lanes(max_lanes, prefill_chunk),
            max_lanes)
        self.seed = seed
        max_seq_len = min(max_seq_len or self.config.max_seq_len,
                          self.config.max_seq_len)
        if num_blocks is None:
            num_blocks = max_lanes * -(-max_seq_len // block_size)
        with spans.span("engine", "pools", pin=True):
            self.cache = PagedKVCache.for_model(
                self.model, self.config, num_blocks=num_blocks,
                block_size=block_size, max_lanes=max_lanes,
                max_seq_len=max_seq_len, prefix_cache=prefix_cache,
                # (a sliding kind reserves by how far a lane is written
                # past its committed length: the longest slice, one step
                # ahead)
                ahead=2 * max(prefill_chunk, 1 + int(spec_k)))
            jax.block_until_ready(self.cache.step_pools)
        if kv_tier is None:
            from ray_tpu._private.config import GLOBAL_CONFIG
            kv_tier = bool(GLOBAL_CONFIG.kv_tier)
        if kv_tier and prefix_cache:
            # Runtime import: the tier lives with the serving subsystem
            # but depends only on util/, so the cycle never closes.
            from ray_tpu.serve.kv_tier.tier import KVTierCache
            self.cache.attach_tier(KVTierCache.from_config())
        self.spec_k = int(spec_k)
        self._spec_adaptive = bool(spec_adaptive)
        if self.spec_k > 0:
            from ray_tpu.inference.speculative import resolve_draft_proposer
            self._proposer = resolve_draft_proposer(draft_proposer)
        else:
            self._proposer = None
        # Whether the prefilling lanes ride the decoding lanes' step.
        self._pairs = self._proposer is None
        self._spec_stats = {"drafted": 0, "accepted": 0, "emitted": 0,
                            "steps": 0, "bursts": 0}
        # RL rollout support: per-token behavior log-prob capture (the
        # step fns grow one [B(,T)] float32 output) and a policy version
        # stamp advanced by update_params().
        self._capture_logp = bool(capture_logp)
        self.policy_version = 0
        self._lanes: List[Optional[_Request]] = [None] * max_lanes
        self._waiting: "collections.deque[_Request]" = collections.deque()
        # The last sampled token of every lane, on the device: every step
        # takes it and hands it back as the tokens it sampled, so the step
        # after reads its decode lanes' input where the device left it.
        self._last_tok = jnp.zeros((max_lanes,), jnp.int32)
        # The step dispatched by the last iteration and not yet fetched
        # (one entry per population), and how often the loop ran ahead.
        self._flight: list = []
        self._ahead = {"steps": 0, "sync_steps": 0, "overrun_tokens": 0}
        self._rid = itertools.count(1)
        self._step_fns: Dict = {}
        # Compact programs made at one width whose other width is still to
        # make (`_warm_widths`), as the batches that made them; and whether
        # a program was made since the last commit that delivered.
        self._to_warm: list = []
        self._cold = False
        self._step_impls: Dict = {}   # un-jitted twins (shape introspection)
        self._step_avals: Dict = {}   # argument shapes of each step's compile
        self._step_made: Dict = {}    # each step's first call (`_making`)
        self._evictions_reported = 0
        # Cumulative step accounting (stats()): what the engine thread did
        # with its time, by phase, and how long admitted requests queued.
        self._steps = 0
        self._step_wall_s = 0.0
        self._phase_s = dict.fromkeys(_PHASES, 0.0)
        # The same by part of `build_batch` and `commit` (`_PARTS`); the
        # engine thread's CPU seconds over the four host phases beside
        # their wall seconds, in the `_cpu_steps` iterations that read the
        # clock (one in `_CPU_EVERY`, by `_dice`: a linear congruential
        # draw; wall less CPU is the time the thread held no core);
        # collector seconds inside iterations; and all of the loop's sums
        # by the wall-clock second for the last `_TIMELINE_ROWS` seconds in
        # which it ran (rows as `_TIMELINE` names them): differences of the
        # sums between the seconds' first iterations, so that an iteration
        # pays one clock read and one comparison for it.  `_second` is the
        # open row: its second, the sums at its start, and the longest
        # iteration in it with the phase that took most of that.
        self._part_s = dict.fromkeys(_PARTS, 0.0)
        self._prefill_steps = 0
        self._cpu_s = 0.0
        self._cpu_wall_s = 0.0
        self._cpu_steps = 0
        self._dice = 0
        self._gc_s = 0.0
        self._timeline: "collections.deque[list]" = collections.deque(
            maxlen=_TIMELINE_ROWS)
        self._second = [0, self._sums(), 0.0, ""]
        self._compile_base = compile_cache.sums()     # for `engine/stall`
        if _gc_hook not in gc.callbacks:
            gc.callbacks.append(_gc_hook)
        self._admitted = 0
        self._queue_wait_s = 0.0
        # Counted on the host as batches are built (`_build_batch`): the
        # prefill programs and their rows, the valid tokens of all
        # programs, and the T=1 steps with the context tokens they
        # attended over: `_latent` over a latent cache, `_paged` over a
        # K/V one, which also counts the runs of `_paged_run` tokens the
        # decode kernel found context in (it visits no others).
        self._prefill = {"steps": 0, "lanes": 0, "rows": 0, "rows_valid": 0,
                         "ctx_rows": 0}
        # Iterations that dispatched, the step programs they dispatched,
        # and those of them that ran the pair's program (`mixed`) with the
        # rows of its two parts.
        self._programs = {"iterations": 0, "programs": 0, "mixed": 0,
                          "decode_rows": 0, "chunk_rows": 0}
        self._tokens_run = 0
        self._decode_ctx = 0        # of the iteration being built
        # What `_upload` handed to the device: populations, transfers (a
        # changed block table's copy among them) and their bytes.
        self._uploads = {"populations": 0, "transfers": 0, "bytes": 0}
        # (Which of these `stats()` carries goes by the cache's `kind`: they
        # are the parts' own to count once the keys may move, ROADMAP.md.)
        latent, kind = self.cache.latent, self.cache.kind
        self._latent = {"decode_steps": 0, "ctx_tokens": 0} if latent else None
        self._paged = None if latent else {
            "decode_steps": 0, "ctx_tokens": 0, "runs_live": 0}
        self._paged_run = block_size * paged_blocks_per_step(
            block_size, self.cache.pool_shape[3],
            jnp.dtype(self.config.dtype).itemsize,
            self.cache.max_blocks_per_seq)
        # Over layers of several kinds (kv_cache.py), by T=1 step and for
        # ONE layer of each kind: the context tokens an indexer scored, the
        # rows it chose (at most `_index_topk` a lane) and the rows a
        # window layer attended (at most `_slide_rows`, the window).
        self._index_topk, self._slide_rows = (
            max((getattr(run.sizes, name, 0) for run
                 in self.model.spec(self.config).runs), default=0)
            for name in ("index_topk", "window"))
        self._sparse = ({"decode_steps": 0, "ctx_tokens": 0,
                         "rows_chosen": 0, "window_rows": 0}
                        if kind == "layered" and latent else None)
        # Over a windowed cache `_paged` counts the rows attended (what the
        # kernel reads), `_eva` the same T=1 steps with their true context
        # beside those rows.  The compaction program is made at its first
        # use (`_compact`: fn, argument shapes, seconds of the first call).
        self._eva = ({"decode_steps": 0, "ctx_tokens": 0, "rows_attended": 0}
                     if kind == "windowed" else None)
        self._compact: dict = {}
        # Over a state cache, under "ssm" beside the state part's own
        # counters: the tokens its mixers' scans (T > 1) and updates
        # (T = 1) stepped over.  Mixers that keep no recurrence (a gated
        # short convolution: the state part is its tails alone) count
        # under "conv" instead: the populations (`steps`) and the valid
        # rows their layers convolved, T=1 and chunk apart.
        self._stateful = kind == "state"
        self._recurrent = self._stateful and any(
            run.mixer is not None and run.mixer.state(self.config).heads
            for run in self.model.spec(self.config).runs)
        self._conv = {"steps_t1": 0, "rows_t1": 0, "steps_chunk": 0,
                      "rows_chunk": 0}
        # The layers a step runs by kind (K/V rows, state, experts): in a
        # stack of one-part layers no reader can take them from `n_layers`.
        self._layers = layer_counts(self.model.spec(self.config),
                                    self.config)
        if self._paged is not None and self._layers["window"]:
            # K and V rows of which some layers read a window alone: the
            # rows a T=1 step's full layers and its window layers read,
            # summed over the layers of each kind.
            self._paged.update(rows_full=0, rows_window=0)
        self._ssm = {"tokens_scanned": 0, "tokens_updated": 0}
        # A verify step writes past what it may commit: the cache says
        # whether a lane's layout and parts can be rolled back from there.
        if self.spec_k > 0 and self.cache.no_rollback:
            raise NotImplementedError(
                f"speculative decoding over a {kind} cache: "
                f"{self.cache.no_rollback}")
        # What the cache asks of the loop beside its steps, said once: windows
        # to close before a batch, blocks to let go of after a commit,
        # checkpoints behind a prefill step.
        self._closes, self._releases, self._checkpoints = (
            self.cache.closes, self.cache.releases, self.cache.checkpoints)
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        self._auto = auto_start

    # ---------------- public API ----------------

    def submit(self, prompt, max_new_tokens: int = 16, *,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               seed: Optional[int] = None, sample_offset: int = 0,
               deadline_s: Optional[float] = None,
               prefill_only: bool = False) -> GenerationHandle:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        vocab = self.config.vocab_size
        for t in prompt:
            if not 0 <= t < vocab:
                raise ValueError(
                    f"prompt token id {t} out of range for vocab_size "
                    f"{vocab}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) > self.cache.max_seq_len:
            raise ValueError("prompt longer than max_seq_len")
        rid = next(self._rid)
        from ray_tpu.util import tracing
        req = _Request(rid=rid, prompt=prompt,
                       max_new_tokens=max_new_tokens,
                       temperature=temperature, eos_id=eos_id,
                       seed=seed if seed is not None else self.seed + rid,
                       sample_offset=int(sample_offset),
                       deadline=(None if deadline_s is None
                                 else time.monotonic() + deadline_s),
                       trace=tracing.current_context(),
                       submitted=time.time(),
                       submitted_pc=time.perf_counter(),
                       spec_k=self.spec_k,
                       prefill_only=prefill_only,
                       chain=(chain_keys(prompt, self.cache.block_size)
                              if self.cache.prefix_cache_enabled else None))
        events.record("engine", "submit", trace=req.trace, rid=rid,
                      prompt_len=len(prompt), max_new=max_new_tokens)
        if req.trace is not None:
            # Prefill span: submit -> first emitted token (TTFT, queue
            # wait included; its child `queue` ends at admission).
            # _commit swaps it for the request's one decode span.
            req.span_tok = spans.begin("engine", "prefill", ctx=req.trace,
                                       rid=rid, prompt_len=len(prompt))
            if req.span_tok is not None:
                req.queue_tok = spans.begin(
                    "engine", "queue", ctx=(req.trace[0], req.span_tok.sid),
                    rid=rid)
        with self._work:
            if self._stopped:
                raise RuntimeError("engine is shut down")
            self._waiting.append(req)
            self._work.notify()
        if self._auto:
            self._ensure_thread()
        return GenerationHandle(req, self)

    def generate(self, prompt, max_new_tokens: int = 16, *,
                 temperature: float = 0.0, eos_id: Optional[int] = None,
                 seed: Optional[int] = None) -> List[int]:
        """Blocking convenience wrapper: submit + drain."""
        h = self.submit(prompt, max_new_tokens, temperature=temperature,
                        eos_id=eos_id, seed=seed)
        if not self._auto:
            while self.step():
                pass
        return h.tokens()

    def _prepare(self, params):
        """The tree the step takes for `params`: `model.serving_params`,
        one compiled program over the leaves that need re-making and none
        where the caller already holds them so (then the tree itself).
        Counted in `stats()["weights"]` and one ring event."""
        t0 = time.perf_counter()
        served = self.model.serving_params(params, self.config)
        if served is not params:        # a program ran: its seconds
            jax.block_until_ready(served)
        took = time.perf_counter() - t0
        given_bytes, served_bytes = (
            sum(x.nbytes for x in jax.tree.leaves(tree))
            for tree in (params, served))
        with self._lock:
            w = self._weights
            w["prepared"] += 1
            w["prepare_s"] += took
            w["served_bytes"], w["given_bytes"] = served_bytes, given_bytes
        events.record("engine", "weights_prepare", prepare_ms=took * 1e3,
                      served_bytes=served_bytes, given_bytes=given_bytes)
        return served

    def update_params(self, params, version: Optional[int] = None) -> int:
        """Swap the model weights IN PLACE between scheduler steps.

        The jitted step reads the served tree afresh at every dispatch,
        so the swap is a boundary between dispatches: the step in flight
        finishes on the weights it was launched with (its tokens are
        committed after the swap, and carry the log-probs of the weights
        that sampled them); in-flight lanes keep their KV state and
        continue generating under the NEW weights at the next dispatch
        — no lane is dropped, no request restarted.
        The new weights are prepared (`_prepare`) before the lock is
        taken, while the scheduler keeps stepping on the old ones.
        (The actor/learner RL path publishes learner weights through
        here at version boundaries.)  Returns the new policy version
        (``version`` when given, else the previous version + 1)."""
        served = self._prepare(params)
        with self._work:
            self.params, self._served = params, served
            self.policy_version = (int(version) if version is not None
                                   else self.policy_version + 1)
            events.record("engine", "weights_swap",
                          version=self.policy_version,
                          live_lanes=self.num_active)
            self._work.notify()
            return self.policy_version

    # -------- disaggregated prefill/decode (serve/kv_tier) --------

    def prefill(self, prompt, *, seed: Optional[int] = None,
                deadline_s: Optional[float] = None) -> GenerationHandle:
        """Run chunked prefill for `prompt` and seal its KV blocks into
        the prefix index WITHOUT sampling a token (finish_reason
        "prefill").  The handle drains empty; the product is the sealed
        chain, which `export_prefix` snapshots for a decode engine."""
        h = self.submit(prompt, 1, seed=seed, deadline_s=deadline_s,
                        prefill_only=True)
        if not self._auto:
            while self.step():
                pass
        return h

    def export_prefix(self, tokens) -> Optional[dict]:
        """Snapshot the longest device-cached chain covering `tokens`
        (see PagedKVCache.export_prefix) under the engine lock, so the
        scheduler can't reshuffle blocks mid-gather."""
        tokens = [int(t) for t in tokens]
        with self._lock:
            with spans.span("kv", "export", tokens=len(tokens)):
                return self.cache.export_prefix(tokens)

    def import_prefix(self, payload: dict) -> int:
        """Adopt a foreign sealed chain (the prefill→decode handoff)
        under the engine lock; returns blocks installed.  Idempotent —
        see PagedKVCache.install_prefix."""
        with self._lock:
            with spans.span("kv", "import"):
                return self.cache.install_prefix(payload)

    def prefix_summary(self, limit: Optional[int] = None) -> dict:
        """Routing summary of this engine's cached chains (device index
        + spill tier), bounded by `limit` (config
        serve_prefix_summary_size when None)."""
        if limit is None:
            from ray_tpu._private.config import GLOBAL_CONFIG
            limit = GLOBAL_CONFIG.serve_prefix_summary_size
        with self._lock:
            return self.cache.prefix_summary(limit)

    def cancel(self, req: "_Request") -> bool:
        """Abort one request: dequeue it if still waiting, or evict its
        lane (freeing the KV blocks) if live.  The consumer is unblocked
        with end-of-stream; finish_reason becomes "cancelled".  False if
        the request had already finished (idempotent)."""
        with self._work:
            try:
                self._waiting.remove(req)
            except ValueError:
                pass
            else:
                req.finish_reason = "cancelled"
                req.out.put(_DONE)
                _end_spans(req, ok=False)
                return True
            for lane, r in enumerate(self._lanes):
                if r is req:
                    req.finish_reason = "cancelled"
                    req.out.put(_DONE)
                    _end_spans(req, ok=False)
                    self.cache.free_lane(lane)
                    self._lanes[lane] = None
                    events.record("engine", "lane_evict", trace=req.trace,
                                  rid=req.rid, lane=lane,
                                  reason="cancelled")
                    return True
        return False

    def _expire_deadlines(self) -> None:
        """Evict every lane (and drop every queued request) whose
        deadline lapsed — the consumer is gone or has given up, so
        spending decode steps on it only steals FLOPs from live lanes.
        Caller holds the lock."""
        now = time.monotonic()
        for lane, req in enumerate(self._lanes):
            if req is not None and req.deadline is not None \
                    and now > req.deadline:
                req.finish_reason = "deadline"
                req.out.put(_DONE)
                self.cache.free_lane(lane)
                self._lanes[lane] = None
                _end_spans(req, ok=False)
                events.record("engine", "deadline_kill", trace=req.trace,
                              rid=req.rid, lane=lane,
                              produced=req.produced)
        expired = [r for r in self._waiting
                   if r.deadline is not None and now > r.deadline]
        for req in expired:
            self._waiting.remove(req)
            req.finish_reason = "deadline"
            req.out.put(_DONE)
            _end_spans(req, ok=False)
            events.record("engine", "deadline_kill", trace=req.trace,
                          rid=req.rid, lane=None, produced=0)

    def shutdown(self) -> None:
        with self._work:
            self._stopped = True
            for req in list(self._waiting):
                req.out.put(_DONE)
            self._waiting.clear()
            for lane, req in enumerate(self._lanes):
                if req is not None:
                    req.out.put(_DONE)
                    self.cache.free_lane(lane)
                    self._lanes[lane] = None
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._lanes)

    @property
    def num_waiting(self) -> int:
        return len(self._waiting)

    def stats(self) -> dict:
        """Engine occupancy + prefix-cache effectiveness counters +
        speculative acceptance counters."""
        cs = self.cache.stats
        st = self._spec_stats
        return {
            "backend": self.backend,
            "device_kind": self.device_kind,
            "chips": list(leased_chips()),
            "active": self.num_active,
            "waiting": self.num_waiting,
            "max_lanes": self.max_lanes,
            "free_blocks": self.cache.allocator.num_free,
            "cached_blocks": self.cache.num_indexed_blocks,
            "prefix_hits": cs["hits"],
            "prefix_misses": cs["misses"],
            "prefix_hit_tokens": cs["hit_tokens"],
            "prefix_miss_tokens": cs["miss_tokens"],
            "blocks_evicted": self.cache.allocator.evictions,
            "imported_blocks": cs["imported_blocks"],
            "restored_blocks": cs["restored_blocks"],
            **(self.cache.tier.counters if self.cache.tier is not None
               else {}),
            "policy_version": self.policy_version,
            "spec_k": self.spec_k,
            "spec_drafted_tokens": st["drafted"],
            "spec_accepted_tokens": st["accepted"],
            "spec_emitted_tokens": st["emitted"],
            "spec_steps": st["steps"],
            # Tokens per lane per verify step — plain decode is 1.0, so
            # anything above 1 is the speculative multiplier.
            "spec_accepted_per_step": (st["emitted"] / st["bursts"]
                                       if st["bursts"] else 0.0),
            # Where the engine thread's time went, cumulatively: steps
            # that advanced a lane, their wall, the same by phase
            # (`_PHASES`), and submit -> lane summed over admissions.
            "steps": self._steps,
            "step_wall_s": self._step_wall_s,
            "phase_s": dict(self._phase_s),
            # `build_batch` and `commit` by part, the thread's CPU seconds
            # over admit, build_batch, dispatch and commit, the process's
            # collector pauses, and the sums above by the second.
            "part_s": dict(self._part_s),
            "cpu_s": self._cpu_s,
            "cpu_wall_s": self._cpu_wall_s,
            "cpu_steps": self._cpu_steps,
            "gc": {"collections": list(_GC["collections"]),
                   "seconds": _GC["seconds"],
                   "full_seconds": _GC["full_seconds"]},
            "timeline": {
                "columns": list(_TIMELINE), "phases": list(_PHASES),
                "parts": list(_PARTS), "rows": self._timeline_rows()},
            "admitted": self._admitted,
            "queue_wait_s": self._queue_wait_s,
            # The layers a step runs that keep K/V rows, that keep a
            # recurrent state, and that have dropless experts.
            "layers": dict(self._layers),
            # Of those steps: `steps` dispatched while the step before was
            # still unfetched, `sync_steps` could not (a proposer drafts
            # from the fetched token; nothing was in flight; nothing was
            # left to dispatch).  `overrun_tokens`: sampled for a request
            # that had ended by the time they were fetched, and discarded.
            "ahead": dict(self._ahead),
            # This process's XLA compiles and persistent-cache loads, and
            # the seconds jax traced and lowered to ask for them.
            "compile": compile_cache.counters(),
            # What the process did once, from its start-up record.
            "setup": _setup(),
            # Preparations of the served weights (one at load, one per
            # update_params), their seconds, and the bytes of the tree
            # the step takes beside those of the tree given.
            "weights": dict(self._weights),
            # The T=prefill_chunk programs dispatched, the lanes that
            # prefilled in them, the rows they computed and how many of
            # those held a prompt token.
            "prefill": dict(self._prefill),
            # Iterations that dispatched and the step programs they
            # dispatched (1 an iteration where every admission rides the
            # decoding lanes' step); `mixed`: the pair's programs, with the
            # rows of their decode parts and of their chunks.
            "programs": dict(self._programs),
            # Populations handed to the device, the transfers that took (a
            # pair's two go in one) and their bytes (`_upload`).
            "upload": dict(self._uploads),
            # T=1 steps and the context tokens their lanes attended over;
            # over a K/V cache also the runs of the decode kernel that
            # held context, of `decode_steps` x lanes x runs a lane.
            **({"paged": dict(self._paged)} if self._latent is None else
               {"latent": dict(self._latent)}),
            # Over a windowed cache: those T=1 steps with their true context
            # beside the rows attended, the windows closed so far and the
            # pool's blocks by kind now.
            **({} if self._eva is None else
               {"eva": {**self._eva, **self.cache.kind_stats()}}),
            **self._moe_stats(),
            # Layers of several kinds: the T=1 steps' sums for one layer of
            # each kind (`_sparse`), and the sliding kind's blocks given
            # back in mid-sequence so far.
            # A state cache: slots of state and of snapshots, what the
            # index did with the snapshots, and the tokens stepped over.
            **({} if not self._stateful else
               {"ssm": {**self.cache.kind_stats(),
                        **(self._ssm if self._recurrent else {})},
                **({} if self._recurrent else {"conv": {
                    **self._conv, "layers": self._layers["state"]}})}),
            **({} if self._sparse is None else {
                "sparse": dict(self._sparse)}),
            # A cache with a sliding part: its blocks given back in
            # mid-sequence so far, and the blocks and bytes of each kind.
            **({"windows": self.cache.kind_stats()} if self._releases
               else {}),
        }

    def _moe_stats(self) -> dict:
        """An expert configuration's cumulative load, fetched from the
        device here and nowhere else: assignments (token, expert) in all
        and per expert, summed over layers; `experts_hit` summed over the
        `layer_steps` (layer, step) pairs run so far.  Where the experts
        held are a share of the router's, those are the share's
        (`assignments_held`), and `assignments` is what the router made in
        all: top-k for every valid token and expert layer, counted on the
        host."""
        if self._moe_load is None:
            return {}
        c = self.config
        load = np.asarray(self._moe_load).tolist()
        out = {"assignments": sum(load[:-2]), "expert_load": load[:-2],
               "experts_hit": load[-2], "layer_steps": load[-1]}
        if len(load) - 2 < c.n_experts:
            out["assignments_held"] = out["assignments"]
            out["assignments"] = (self._tokens_run * c.n_experts_per_tok
                                  * self._layers["experts"])
        return {"moe": out}

    def compiled_steps(self) -> dict:
        """What XLA built for each step shape dispatched so far: seconds
        its first call took (`compile_s`) and what jax says they were made
        of (`made`: the `engine.dispatch/make_program` span's payload:
        `trace_s`, `lower_s`, `cache_load_s`, `compile_s`, `cached`, the
        rest of `wall_s` being the first run), the number of Mosaic kernel calls
        in the compiled program, the bytes of arguments updated in place
        (`donated_bytes`: the KV pools), the program's scratch
        (`temp_bytes`), the instructions that copy, slice out or stack
        back the pool or whole layers of it (`pool_copies`, see
        `compiled.count_pool_copies`: 0 when the pool stays where it is)
        and the bytes of weight-shaped results the step makes by opcode
        (`weight_bytes_copied`, see `compiled.count_weight_bytes_copied`:
        no `convert`, `copy` or `transpose` when the weights are read
        where they are; a layer scan's slices of its groups are listed).
        Over layers of several kinds also the sorts of [rows, context]
        arrays (`select_sorts`, see `compiled.count_select_sorts`: 0 while
        an indexed layer chooses its rows without sorting a lane's scores).
        Recompiles each shape ahead of time (a persistent-cache hit where
        the cache is on), so call it for a check, not per request."""
        def report(made, fn, avals):
            compiled = fn.lower(*avals).compile()
            text, memory = compiled.as_text(), compiled.memory_analysis()
            out = {
                "compile_s": round(made["wall_s"], 2),
                "made": {k: round(v, 3) for k, v in made.items()},
                "custom_calls": text.count("tpu_custom_call"),
                "donated_bytes": memory.alias_size_in_bytes,
                "temp_bytes": memory.temp_size_in_bytes,
                "pool_copies": count_pool_copies(text,
                                                 self.cache.pool_shape),
                "weight_bytes_copied": count_weight_bytes_copied(
                    text, avals[0])}
            if self._sparse is not None:
                out["select_sorts"] = count_select_sorts(
                    text, (self.cache.max_blocks_per_seq
                           * self.cache.block_size))
            return text, out

        out = {}
        # _step_made is filled last, so its keys are complete steps
        # even while the scheduler thread is adding a new shape.
        for key, made in list(self._step_made.items()):
            t, sample, spec, compact = key
            name = f"t{t}" + ("_sample" if sample else "") \
                + ("_spec" if spec else "") \
                + (f"_{'pair' if self._pairs else 'lanes'}{compact}"
                   if compact else "")
            text, out[name] = report(made, self._step_fns[key],
                                     self._step_avals[key])
            if self._stateful:
                # The state part's (largest) buffer too is updated where
                # it is.
                out[name]["state_copies"] = count_pool_copies(
                    text, max(self.cache.buffers,
                              key=lambda b: b.nbytes).shape)
        if "made" in self._compact:
            _, out[f"compact_lanes{self.prefill_lanes}"] = report(
                self._compact["made"], self._compact["fn"],
                self._compact["avals"])
        return out

    # ---------------- scheduler ----------------

    def _ensure_thread(self):
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="inference-engine")
            self._thread.start()

    def _loop(self):
        while True:
            with self._work:
                while (not self._stopped and not self._waiting
                       and not self._flight
                       and all(r is None for r in self._lanes)):
                    self._work.wait()
                if self._stopped:
                    return
            self.step()

    def _final_len(self, req) -> int:
        return min(len(req.prompt) + req.max_new_tokens,
                   self.cache.max_seq_len)

    def _growth_reserve(self) -> int:
        """Blocks every LIVE lane may still claim before finishing (the most
        it owns on the way to its worst-case final length, which over a
        windowed cache is the close of its last window and not its end,
        minus what it already owns).  Admission
        must leave this much unclaimed or a decode step's block-boundary
        growth can exhaust the pool mid-flight — with no preemption, the
        only safe policy is never to admit past the worst case."""
        reserve = 0
        for lane, req in enumerate(self._lanes):
            if req is None:
                continue
            reserve += max(0, self.cache.lane_peak(lane, self._final_len(req))
                           - len(self.cache.lane_blocks(lane)))
        return reserve

    def _admit(self):
        """Fill free lanes from the FIFO queue — admission control is
        block-level: a request enters only when its worst-case final
        length fits alongside every live lane's worst case, counting
        cached prefix blocks as references, not allocations."""
        met = _metrics()
        for lane in range(self.max_lanes):
            if self._lanes[lane] is not None or not self._waiting:
                continue
            req = self._waiting[0]
            if self._head_is_being_sealed(req):
                break  # its shared head comes from the cache in a moment
            if not self.cache.can_admit_prefix(
                    req.prompt, keys=req.chain,
                    headroom_blocks=self._growth_reserve(),
                    final_len=self._final_len(req)):
                break  # FIFO: don't starve the head with later requests
            reused = self.cache.adopt_prefix(lane, req.prompt, req.chain)
            self._waiting.popleft()
            req.fed = reused
            if self._checkpoints:
                req.snap_at = self.cache.checkpoint_wanted(lane)
                req.cold = not (reused or req.snap_at)
            self._lanes[lane] = req
            met["hit_tokens"].inc(reused)
            met["miss_tokens"].inc(len(req.prompt) - reused)
            met["hits" if reused else "misses"].inc()
            wait_s = time.perf_counter() - req.submitted_pc
            self._admitted += 1
            self._queue_wait_s += wait_s
            spans.end(req.queue_tok, lane=lane)
            req.queue_tok = None
            events.record("engine",
                          "prefix_hit" if reused else "prefix_miss",
                          trace=req.trace, rid=req.rid, lane=lane,
                          reused_tokens=reused,
                          prompt_len=len(req.prompt),
                          wait_ms=wait_s * 1e3)
        met["queue_depth"].set(len(self._waiting))
        evictions = self.cache.allocator.evictions
        if evictions > self._evictions_reported:
            met["evicted"].inc(evictions - self._evictions_reported)
            events.record("engine", "blocks_evicted",
                          n=evictions - self._evictions_reported)
            self._evictions_reported = evictions

    def _head_is_being_sealed(self, req: _Request) -> bool:
        """Whether a lane that still prefills is writing blocks of `req`'s
        own prompt head that the prefix index does not hold yet: `req`
        then waits for them instead of prefilling a copy of its own beside
        that lane (of N requests that arrive together with one 16k
        document in front, one prefills it and N - 1 take it from the
        cache).  The lane it waits for always advances, and once that has
        sealed the shared blocks, or has gone, `req` is admitted.  Heads
        are compared by the block chains `submit` made; the index is asked
        only where some prefilling lane shares one."""
        if req.chain is None:               # no prefix cache
            return False
        shared = max((sum(1 for _ in itertools.takewhile(
            lambda ab: ab[0] == ab[1], zip(req.chain, other.chain)))
            for other in self._lanes
            if other is not None and other.prefilling), default=0)
        return shared > 0 and shared * self.cache.block_size \
            > self.cache.match_len(req.prompt, req.chain)

    def _propose(self, lane: int, req: _Request) -> tuple:
        """Draft for one decode lane: ask the proposer for up to the
        lane's adaptive draft length, clamped so the verify chunk can
        never write past max_seq_len and never drafts beyond the token
        budget (the burst from k drafts is at most k+1 tokens)."""
        limit = min(req.spec_k,
                    req.max_new_tokens - req.produced - 1,
                    self.cache.max_seq_len - 1
                    - int(self.cache.seq_lens[lane]))
        if limit <= 0:
            return ()
        draft = self._proposer.propose(req.prompt + req.emitted, limit)
        vocab = self.config.vocab_size
        out = []
        for t in draft[:limit]:
            t = int(t)
            if not 0 <= t < vocab:
                break       # garbage proposal: verify nothing past it
            out.append(t)
        return tuple(out)

    def _stalled(self, wall: float, took: dict, paused: float) -> None:
        """An iteration held the loop over `_STALL_S`: one `engine/stall`
        event with the phase that took most of it, the collector's pauses
        inside it and what jax compiled, loaded, traced or lowered since
        the open second began (`_close_second` keeps the base: at most a
        second before the iteration did)."""
        now = compile_cache.sums()
        events.record(
            "engine", "stall", phase=max(took, key=took.get),
            wall_ms=wall * 1e3, gc_ms=paused * 1e3,
            **{k: now[k] - v for k, v in self._compile_base.items()})
        self._compile_base = now

    def step(self) -> bool:
        """One scheduler iteration, one step ahead of its own results:
        admit, build and dispatch the next step while the device still runs
        the step the iteration before dispatched, and only then fetch and
        commit that older step.  The device so has the next program queued
        behind the one it runs, and the host's work happens under a device
        program instead of between two.  What the next step needs of the one
        in flight the host knows by counting (positions, context lengths,
        sampling counters, a block boundary, the end of a prompt, a finish by
        length: `_Request.ahead_len`, `ahead_new`), all but the sampled
        token, and that stays on the device (`_last_tok`).  A draft proposer
        does need the sampled token on the host, so an engine that has one
        fetches a step in the iteration that dispatched it: the same loop
        at depth 0, nothing in flight between iterations.

        An iteration dispatches ONE program: the T=1 step where nobody
        prefills, else the pair's, the prefilling lanes' [prefill_lanes, T]
        rows beside the decoding lanes' [max_lanes, 1] under one read of
        the weights.  An engine with a proposer dispatches the two
        populations as SEPARATE jitted steps, the decoding lanes' first.
        When speculation is on and any decode lane
        drafted, the decode population dispatches as ONE verify step
        sized to the WIDEST draft actually proposed this step
        (T = 1+max drafts, never more than spec_k+1) — draftless lanes
        ride along at chunk=1, so mixed speculative/plain lanes share
        the step, and adaptive-k backoff shrinks the verify FLOPs it
        pays for instead of padding to the configured maximum.

        Returns False when fully idle: no lane live and nothing in flight,
        so a caller that steps until then has seen every result.  What only
        the fetch can tell (an `eos`; and a cancel, a deadline or a shutdown
        may come at any time) finds the next step already dispatched with
        the ended request in it: `_commit` discards that row.

        An iteration is five flat phases (`_PHASES`), none inside another,
        so that an idle gap of the device in a profiler trace carries the
        name of what the host was doing; `dispatch` runs once per
        program, so a drafting engine's mixed step has it twice.  One
        `engine/step` ring
        record at the end holds the iteration's durations: `wall_ms` its
        period, `fetch_ms` the time blocked on the older step, `ahead`
        whether it dispatched with that step still unfetched; the parts of
        `build_batch` and `commit` (`_PARTS`: nested annotations under
        `engine.` names, which no reader of the flat ones sees), `gc_ms`
        (collector pauses that fell inside it) and, in one iteration of
        `_CPU_EVERY`, `cpu_ms` beside `cpu_wall_ms` (the thread's CPU time
        and the wall time from `admit`'s start to `dispatch`'s end and
        over `commit`: the blocked `fetch` left out), and `decode_ctx`,
        the tokens of context its decoding lanes held (what the T=1
        attention read: a trace's slice is divided by the sum over its
        own steps, not by a client's later view).  The same sums go
        into `stats()` and, by the wall-clock second, into its
        `timeline`."""
        took = dict.fromkeys(_PHASES, 0.0)         # seconds
        parts = dict.fromkeys(_PARTS, 0.0)
        gc0 = _GC["seconds"]
        self._decode_ctx = 0
        self._dice = (self._dice * 1103515245 + 12345) & 0x7FFFFFFF
        clocked = self._dice < 0x80000000 // _CPU_EVERY
        if clocked:
            cpu0, on0 = time.thread_time(), time.perf_counter()
        older = self._flight
        with contextlib.ExitStack() as locked:
            with spans.phase("engine", "admit") as ph:
                locked.enter_context(self._lock)   # the wait is admission's
                self._expire_deadlines()
                self._admit()
                # A lane whose request the step in flight ends sits out.
                live = [(i, r) for i, r in enumerate(self._lanes)
                        if r is not None and not self._ends_in_flight(i, r)]
                if not live and not older:
                    if not self._waiting:       # idle: nobody waits
                        while self._to_warm:
                            self._warm_widths(self._to_warm.pop())
                    return False
                decode = [(i, r) for i, r in live
                          if r.next_fed == len(r.prompt)]
                prefill = [(i, r) for i, r in live
                           if r.next_fed < len(r.prompt)]
                if len(prefill) > self.prefill_lanes:
                    # The oldest requests first; the others wait a step.
                    prefill = sorted(prefill, key=lambda ir: ir[1].rid)[
                        :self.prefill_lanes]
                spec = False
                if decode and self._proposer is not None:
                    dtok = spans.begin("engine", "spec_draft")
                    drafted = 0
                    for lane, req in decode:
                        req.draft = self._propose(lane, req)
                        drafted += len(req.draft)
                    spec = drafted > 0
                    spans.end(dtok, lanes=len(decode), drafted=drafted)
                waiting = len(self._waiting)
            t_start = ph.t0
            took["admit"] = ph.seconds
            plans = []
            with spans.phase("engine", "build_batch") as ph:
                pops = []
                if prefill and self._pairs:
                    pops.append((False, decode, prefill,
                                 self._prefill_len(prefill)))
                else:
                    if decode:
                        t = (1 + max(len(r.draft) for _, r in decode)
                             if spec else 1)
                        pops.append((spec, decode, [], t))
                    if prefill:
                        pops.append((False, [], prefill,
                                     self._prefill_len(prefill)))
                if older and not all(self._made(*pop) for pop in pops):
                    pops = []       # land the step in flight first
                for pop in pops:
                    plans.append(self._plan(parts, *pop))
            took["build_batch"] = ph.seconds
        newer = []
        self._programs["iterations"] += bool(plans)
        self._programs["programs"] += len(plans)
        for spec, lanes, chunks, news, batch, due in plans:
            vtok = spans.begin("engine", "spec_verify") if spec else None
            with spans.phase("engine", "dispatch") as ph:
                next_tok, lps = self._run_step(batch, spec)
                for lane, key in due:
                    # Behind the step that leaves the state in the slot.
                    self.cache.checkpoint(lane, key)
            took["dispatch"] += ph.seconds
            newer.append((spec, vtok, lanes, chunks, news, next_tok, lps,
                          batch[3]))
        # The loop's depth.  A proposer drafts from the token this step
        # samples: its engine fetches what it has just dispatched.  Any
        # other leaves that in flight and fetches the step before it.
        ahead = int(bool(newer and older))
        keep = bool(newer) and self._proposer is None
        retire = older if keep else older + newer
        self._flight = newer if keep else []
        done = []
        if clocked:
            cpu = time.thread_time() - cpu0
            on = time.perf_counter() - on0
        with spans.phase("engine", "fetch") as ph:
            # The host blocks here until the device has finished the older
            # step; the copy back of one int32 per lane was started when
            # that step was dispatched.
            for spec, vtok, lanes, chunks, news, next_tok, lps, rows \
                    in retire:
                toks = np.asarray(next_tok)
                if lps is not None:
                    lps = np.asarray(lps)
                if toks.ndim == 1:  # plain/prefill: one token per lane
                    toks = toks[:, None]
                if lps is not None and lps.ndim == 1:
                    lps = lps[:, None]
                if rows is not None:
                    # A [prefill_lanes, T] program's row -> its lane.
                    toks, lps = (None if a is None else _by_lane(
                        a, rows, self.max_lanes) for a in (toks, lps))
                spans.end(vtok, lanes=len(lanes))
                if spec:
                    self._spec_stats["steps"] += 1
                    _metrics()["spec_steps"].inc()
                done.append((lanes, chunks, news, toks, lps))
        took["fetch"] = ph.seconds
        if clocked:
            cpu0, on0 = time.thread_time(), time.perf_counter()
        with contextlib.ExitStack() as locked:
            with spans.phase("engine", "commit") as ph:
                with spans.phase("engine.commit", "release") as part:
                    # Let go of the steps' device arrays (the lanes' buffer
                    # and the sampled tokens per population) here, inside a
                    # phase: left to the return, their release and what the
                    # runtime then does took 1.4 ms a step on a v5e, between
                    # two steps, under no phase's name (PERF.md 6, PR 23).
                    plans = batch = older = newer = retire = None
                    next_tok = lps = None
                parts["release"] = part.seconds
                with spans.phase("engine.commit", "lock") as part:
                    locked.enter_context(self._work)
                parts["lock"] = part.seconds
                with spans.phase("engine.commit", "deliver") as part:
                    for lanes, chunks, news, toks, lps in done:
                        self._commit(lanes, chunks, news, toks, lps)
                    self._work.notify()
                parts["deliver"] = part.seconds
                if self._releases:
                    # What a part of the cache keeps of a lane and a commit
                    # leaves behind it (a sliding window's blocks) goes back.
                    with spans.phase("engine.commit", "windows") as part:
                        self.cache.after_commit(
                            lane for lanes, *_ in done for lane, req in lanes
                            if self._lanes[lane] is req)
                    parts["windows"] += part.seconds
            took["commit"] = ph.seconds
            wall = ph.t0 + ph.seconds - t_start
            paused = _GC["seconds"] - gc0
            now = int(time.time())
            if now != self._second[0]:
                self._close_second(now)
            if wall * 1e3 > self._second[2]:
                self._second[2:] = wall * 1e3, max(took, key=took.get)
            if wall > _STALL_S:
                self._stalled(wall, took, paused)
            self._steps += 1
            self._prefill_steps += bool(prefill)
            self._step_wall_s += wall
            self._gc_s += paused
            self._ahead["steps" if ahead else "sync_steps"] += 1
            for name in _PHASES:
                self._phase_s[name] += took[name]
            for name in _PARTS:
                self._part_s[name] += parts[name]
            clock = {}
            if clocked:
                cpu += time.thread_time() - cpu0
                on += time.perf_counter() - on0
                self._cpu_s += cpu
                self._cpu_wall_s += on
                self._cpu_steps += 1
                clock = {"cpu_ms": cpu * 1e3, "cpu_wall_ms": on * 1e3}
            events.record(
                "engine", "step", decode=len(decode), prefill=len(prefill),
                decode_ctx=self._decode_ctx,
                waiting=waiting, wall_ms=wall * 1e3,
                admit_ms=took["admit"] * 1e3,
                build_ms=took["build_batch"] * 1e3,
                dispatch_ms=took["dispatch"] * 1e3,
                fetch_ms=took["fetch"] * 1e3,
                commit_ms=took["commit"] * 1e3, ahead=ahead,
                windows_ms=parts["windows"] * 1e3,
                assemble_ms=parts["assemble"] * 1e3,
                upload_ms=parts["upload"] * 1e3,
                release_ms=parts["release"] * 1e3,
                lock_ms=parts["lock"] * 1e3,
                deliver_ms=parts["deliver"] * 1e3,
                gc_ms=paused * 1e3, **clock)
        if done:
            # Behind a commit that no fresh program held up, a sibling
            # width may hold up the next.
            if self._to_warm and not self._cold:
                self._warm_widths(self._to_warm.pop())
            self._cold = False
        return True

    def _sums(self) -> list:
        """The loop's cumulative sums, in the order of a timeline's row
        from its second column to `gc_s`."""
        return [self._steps, self._prefill_steps, self._step_wall_s,
                [self._phase_s[p] for p in _PHASES],
                [self._part_s[p] for p in _PARTS],
                self._cpu_s, self._cpu_wall_s, self._cpu_steps, self._gc_s]

    def _row_since(self, second: list, sums: list) -> list:
        """The timeline's row of the open second `second`, as the
        difference of `sums` and the sums at its start."""
        t, base, longest_ms, longest_phase = second
        return [t, *([x - y for x, y in zip(a, b)] if isinstance(a, list)
                     else a - b for a, b in zip(sums, base)),
                longest_ms, longest_phase]

    def _close_second(self, now: int) -> None:
        """The first iteration to end in another wall-clock second
        (`time.time()`, the clock a caller's own marks are on) closes the
        row of the second before: host numbers alone, nothing on the ring,
        nothing per token."""
        sums = self._sums()
        if sums[0] > self._second[1][0]:
            self._timeline.append(self._row_since(self._second, sums))
        self._second = [now, sums, 0.0, ""]
        self._compile_base = compile_cache.sums()

    def _timeline_rows(self) -> list:
        """The closed rows and the open one (for `stats()`, from any
        thread: the loop may close a second meanwhile, so the open row is
        given only if it is a later second than the last closed one)."""
        rows = [[list(x) if isinstance(x, list) else x for x in row]
                for row in list(self._timeline)]
        second, sums = self._second, self._sums()
        if sums[0] > second[1][0] and (not rows or second[0] > rows[-1][0]):
            rows.append(self._row_since(second, sums))
        return rows[-_TIMELINE_ROWS:]

    def _prefill_len(self, lanes) -> int:
        """T of this step's prefill program: `prefill_chunk`; under
        `prefill_lanes` < max_lanes a quarter of it where no prefilling
        lane has more than that left to feed (a question behind a document
        that came from the prefix cache: the long program would compute
        four times the rows for it).  Two programs, both warmed by whoever
        warms the engine's shapes; an engine that names no `prefill_lanes`
        has the one."""
        short = self.prefill_chunk // 4
        if short and self._widths and all(
                len(r.prompt) - r.next_fed <= short for _, r in lanes):
            return short
        return self.prefill_chunk

    def _plan(self, parts: dict, spec: bool, decode, prefill, t: int) -> tuple:
        """One program's step, built from what the step in flight will have
        left, and from here on in flight itself: the decoding lanes at `t`
        positions (1, or a verify's), the prefilling lanes at `t`, or both
        (the pair: `decode` at one position beside `prefill` at `t`, each
        built as it would be alone, the decoding lanes first, in its part
        of the pair's one buffer; with nobody decoding that part rides
        along masked and counts as no T=1 step).  Per lane the positions it
        writes (`chunks`) and whether it samples a token (`news`), which
        `_commit` takes off again; over a state cache also the snapshots
        due behind it (`due`).  Lanes whose next position opens a window
        have the one before closed first.  The seconds of its three parts
        are added to `parts`: `windows` (the compaction's dispatch
        included), `assemble` (host arrays, tables' entries, counters),
        `upload` (the host arrays and the block tables handed to the
        device)."""
        pair = bool(prefill) and self._pairs
        flat = ahead = chunk = None
        if pair:
            flat, ahead, chunk = _pair_views(
                self.max_lanes, self._chunk_rows(prefill), t)
        # (whose, at how many positions, as a chunk?, in which part of a
        # pair's buffer)
        populations = [(decode, 1 if pair else t, False, ahead)] * (
            pair or bool(decode))
        if prefill:
            populations.append((prefill, t, True, chunk))
        built, chunks, news, due = [], {}, {}, []
        for live, at, chunked, out in populations:
            if self._closes and live:
                with spans.phase("engine.build_batch", "windows") as ph:
                    self._close_windows(live)
                parts["windows"] += ph.seconds
            with spans.phase("engine.build_batch", "assemble") as ph:
                arrays, fed = self._build_batch(live, at, chunked, out)
                built.append(arrays)
                chunks.update(fed)
                for lane, req in live:
                    news[lane] = int(req.samples(req.next_fed, fed[lane]))
                    req.ahead_len += fed[lane]
                    req.ahead_new += news[lane]
                    if self._checkpoints and chunked:
                        key = self._snapshot_due(lane, req)
                        if key is not None:
                            due.append((lane, key))
            parts["assemble"] += ph.seconds
        arrays = built[0]
        if pair:
            (_, drawn, _, host, _), (_, draws, _, more, rows) = built
            arrays = (t, drawn or draws, flat, (host, more), rows)
            mixed = self._programs
            mixed["mixed"] += 1
            mixed["decode_rows"] += self.max_lanes
            mixed["chunk_rows"] += len(rows) * t
        with spans.phase("engine.build_batch", "upload") as ph:
            batch = self._upload(arrays, bool(decode) + bool(prefill))
        parts["upload"] += ph.seconds
        return spec, decode + prefill, chunks, news, batch, due

    def _snapshot_due(self, lane: int, req: _Request):
        """The chain key to snapshot `lane`'s state under behind the chunk
        just planned, or None.  Where the index has seen a head shared
        (`snap_at`: blocks this lane's admission matched and no snapshot
        could serve), there: `_build_batch` cut the chunk to end on it.
        A lane that matched nothing has seen nothing: behind the chunk that
        ends on a block's edge and leaves one chunk of the prompt to
        prefill (the last such edge: where the longest head it can share
        with another request ends, if that is a multiple of the chunk).  A
        lane that adopted all it matched takes none: what it prefills is
        its own turn, and a snapshot behind it would push a shared head's
        out of the few slots.  One snapshot a prompt, not one a chunk."""
        end = int(self.cache.seq_lens[lane]) + req.ahead_len
        left = len(req.prompt) - end
        if req.chain is None or end % self.cache.block_size or left <= 0 \
                or not (end == req.snap_at
                        or req.cold and left <= self.prefill_chunk):
            return None
        return req.chain[end // self.cache.block_size - 1]

    def _upload(self, arrays, populations: int = 1) -> tuple:
        """A program's lane arrays (`_build_batch`; a pair's: both
        populations') as `_run_step` takes them: their one buffer as ONE
        device array, and the block tables' copy (a transfer only where a
        table has changed since the last).  `rows` stay on the host too
        (`step` gives each fetched row back to its lane by them)."""
        t, sample, lanes, _, rows = arrays
        changed = not self.cache.tables_on_device
        up = self._uploads
        up["populations"] += populations
        up["transfers"] += 1 + changed
        up["bytes"] += lanes.nbytes + changed * self.cache.block_tables.nbytes
        return (t, sample, (jnp.asarray(lanes), self.cache.device_tables()),
                rows)

    def _ends_in_flight(self, lane: int, req: _Request) -> bool:
        """Whether the step in flight ends `req` by a count the host has
        without its result: a token budget, the cache's longest sequence, a
        prefill-only prompt fed whole.  Its lane then sits the next step
        out, and is free for another request when that result is
        committed."""
        if req.prefill_only:
            return req.next_fed == len(req.prompt)
        return req.ahead_new > 0 and (
            req.produced + req.ahead_new >= req.max_new_tokens
            or int(self.cache.seq_lens[lane]) + req.ahead_len
            >= self.cache.max_seq_len)

    def _chunk_rows(self, prefill) -> int:
        """The rows of the compact program that takes the lanes `prefill`:
        `prefill_lanes`, or (`_widths`) one where one lane prefills."""
        return (1 if self._widths and len(prefill) == 1
                else self.prefill_lanes)

    def _build_batch(self, live, t, prefill=False, out=None):
        """Host-side assembly of the fixed-shape lane arrays for one
        population, as views of the one buffer that goes to the device
        (`_lane_views`, or its part `out` of a pair's buffer: that function's
        result; lanes not in `live` ride along fully masked), from the
        lengths and counts the step in flight will have left: committed
        plus `ahead_len` / `ahead_new`.  A decode lane whose last token
        that step is still sampling is told to read it on the device
        (`tokens` -1: the step takes it from `_last_tok`); `counters` is -1
        where the lane samples nothing in this step (masked, or a prefill
        chunk short of its prompt's end), and such a lane's entry of
        `_last_tok` stays what it was.

        A `prefill` population is built compact: row i of its arrays is the
        i-th lane of `live`, `rows` ([prefill_lanes], or (`_widths`) [1]
        where one lane prefills) names each row's lane (max_lanes for a row
        nobody has: it reads lane max_lanes - 1's table fully masked and
        writes nowhere), and the step gathers and scatters by it."""
        compact = prefill
        n = self._chunk_rows(live) if compact else self.max_lanes
        lanes, host, rows = out or _lane_views(n, t, compact, self.max_lanes)
        (tokens, positions, valid, ctx_lens, gather, temps, seeds,
         counters) = host
        chunks = {}
        sample = False
        for i, (lane, req) in enumerate(live):
            row = i if compact else lane
            start = int(self.cache.seq_lens[lane]) + req.ahead_len
            fed = req.next_fed
            if fed < len(req.prompt):
                # (cut at a window's edge: a slice lies inside one window)
                chunk = min(t, len(req.prompt) - fed,
                            self.cache.window_room(start))
                if start < req.snap_at:     # end where a snapshot is due
                    chunk = min(chunk, req.snap_at - start)
                tokens[row, :chunk] = req.prompt[fed:fed + chunk]
            else:
                # Speculative lanes feed [last_token, d_1 .. d_k]; the
                # verify step samples every position.  Draftless lanes
                # are the plain chunk=1 decode, masked alongside.
                chunk = 1 + len(req.draft)
                tokens[row, :chunk] = (
                    -1 if req.ahead_new else req.last_token,) + tuple(
                        req.draft)
            positions[row] = start + np.arange(t)
            valid[row, :chunk] = 1
            ctx_lens[row] = start + chunk
            gather[row] = chunk - 1
            temps[row] = req.temperature
            seeds[row] = req.seed & 0xFFFFFFFF
            if req.samples(fed, chunk):
                counters[row] = (req.produced + req.ahead_new
                                 + req.sample_offset)
            sample = sample or req.temperature > 0
            chunks[lane] = chunk
            if compact:
                rows[row] = lane
            # Table entries must exist before the step writes K/V, and
            # be the lane's alone: the step's write has every lane's copy
            # in flight at once (kv_cache.py, "private tail").
            self.cache.ensure_capacity(lane, start + chunk)
        fed_now = sum(chunks.values())
        self._tokens_run += fed_now
        if self._recurrent:
            self._ssm["tokens_scanned" if t > 1 else "tokens_updated"] \
                += fed_now
        elif self._stateful:
            which = "chunk" if t > 1 else "t1"
            self._conv[f"steps_{which}"] += 1
            self._conv[f"rows_{which}"] += fed_now
        if prefill:
            pf = self._prefill
            pf["steps"] += 1
            pf["lanes"] += len(live)
            pf["rows"] += n * t
            pf["rows_valid"] += fed_now
            # The table rows each prefilling lane's last valid row attends
            # over: what the tiled T > 1 attention reads, where the dense
            # path read every lane's whole table.
            pf["ctx_rows"] += sum(self.cache.rows_held(int(c))
                                  for c in ctx_lens[valid[:, 0] != 0])
        elif t == 1 and live:
            ctx = [int(ctx_lens[lane]) for lane, _ in live]
            self._decode_ctx = sum(ctx)
            if self._eva is not None:
                self._eva["decode_steps"] += 1
                self._eva["ctx_tokens"] += sum(ctx)
                ctx = [self.cache.rows_held(c) for c in ctx]
                self._eva["rows_attended"] += sum(ctx)
            seen = self._paged if self._latent is None else self._latent
            seen["decode_steps"] += 1
            seen["ctx_tokens"] += sum(ctx)
            if self._sparse is not None:
                sp, k, w = (self._sparse, self._index_topk,
                            self._slide_rows)
                sp["decode_steps"] += 1
                sp["ctx_tokens"] += sum(ctx)
                sp["rows_chosen"] += sum(min(c, k) for c in ctx)
                sp["window_rows"] += sum(min(c, w) for c in ctx)
            if self._latent is None:
                self._paged["runs_live"] += sum(
                    -(-c // self._paged_run) for c in ctx)
                if self._layers["window"]:
                    w, n = self._slide_rows, self._layers["window"]
                    self._paged["rows_full"] += (
                        self._layers["kv"] - n) * sum(ctx)
                    self._paged["rows_window"] += n * sum(
                        min(c, w) for c in ctx)
        return (t, sample, lanes, host, rows), chunks

    def _run_step(self, batch, spec: bool = False):
        t, sample, args, rows = batch
        compact = 0 if rows is None else len(rows)      # the program's rows
        key = (t, sample, spec, compact)
        fn = self._step_fns.get(key)
        first = fn is None
        # After the lanes' buffer and the block tables a step takes the
        # arrays that ride from step to step on the device: the lanes'
        # last sampled tokens, and last an expert configuration's load
        # counters (handed back last; not donated: stats() may be reading
        # them).
        moe = () if self._moe_load is None else (self._moe_load,)
        carried = (self._last_tok, *moe)
        if first:
            making = contextlib.ExitStack()
            made = making.enter_context(_making(key))
            fn = self._step_fns[key] = self._make_entry(*key)
            self._step_avals[key] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                (self._served, *self.cache.step_pools, *args, *carried))
        out = list(fn(self._served, *self.cache.step_pools, *args,
                      *carried))
        if compact:
            # Every lane's last token, with those this program sampled
            # scattered to their lanes.
            self._last_tok = out.pop()
        if moe:
            self._moe_load = out.pop()
        next_tok, *logp, k, v = out
        logp = logp[0] if logp else None
        if not spec and not compact:
            # What the step sampled, over what it was given for the lanes
            # that sampled nothing: the next step's `_last_tok`, and what
            # the host fetches (the copy back starts now, ahead of the
            # programs dispatched after this one).
            self._last_tok = next_tok
        next_tok.copy_to_host_async()
        if logp is not None:
            logp.copy_to_host_async()
        if first:
            # The first call of a shape returns once it has been traced,
            # lowered and compiled or loaded (the dispatch itself is
            # asynchronous): `_making`'s span has how long each took.
            # What tracing and compiling (or loading) a program leaves on
            # the heap lives as long as the process: out of the collector's
            # way with it, and with everything else this old.  A full
            # collection walks every tracked object with the interpreter
            # stopped: 90-125 ms every 3 s in a replica that has served a
            # while (215k objects, nearly all jax's), more in one that
            # compiled its programs than in one that loaded them, and the
            # device, one step ahead, idles through most of each; 12-14 ms
            # over what is left (PERF.md section 6, PR 32).
            gc.collect()
            gc.freeze()
            making.close()
            self._step_made[key] = made
        self.cache.update_pools(k, v)
        self._cold = self._cold or first
        if first and compact and self._widths:
            self._to_warm.append(batch)
        return next_tok, logp

    def _made(self, spec: bool, decode, prefill, t: int) -> bool:
        """Whether the program a plan's step will run has been made
        (`_run_step`'s key, from what `_plan` will build)."""
        n = self._chunk_rows(prefill) if prefill else 0
        sample = any(req.temperature > 0 for _, req in decode + prefill)
        return (t, sample, spec, n) in self._step_fns

    def _warm_widths(self, batch) -> None:
        """A compact program has been made at one of its two widths (one
        row, `prefill_lanes` rows): make the other now, by a step nobody is
        in (every row masked and nobody's: it writes nowhere and samples
        nothing), so that whichever warms one shape of an engine has warmed
        both and neither compiles when a second lane first prefills beside
        another, minutes into serving."""
        t, sample, _, rows = batch
        for n in {1, self.prefill_lanes} - {len(rows)}:
            if (t, sample, False, n) not in self._step_fns:
                if self._pairs:
                    lanes, _, (_, _, rows) = _pair_views(self.max_lanes, n, t)
                else:
                    lanes, _, rows = _lane_views(n, t, True, self.max_lanes)
                self._run_step(self._upload((t, sample, lanes, None, rows)))

    def _make_entry(self, t: int, sample: bool, spec: bool, compact: int):
        """The program the engine runs for one `_run_step` key: the step
        (`_make_step_fn`) behind the unpacking of the lanes' one buffer (a
        pair's: the decoding lanes' part, then the chunk's)."""
        pair = bool(compact) and self._pairs
        step = self._make_step_fn(sample, spec, bool(compact), pair)

        def entry(params, k, v, lanes, tables, *carried):
            if pair:
                cut = self.max_lanes * _T1_COLUMNS
                return step(
                    params, k, v,
                    _unpack_lanes(lanes[:cut].reshape(-1, _T1_COLUMNS), 1),
                    _unpack_lanes(lanes[cut:].reshape(compact, -1), t),
                    tables, *carried)
            tokens, positions, valid, *rest = _unpack_lanes(lanes, t)
            return step(params, k, v, tokens, positions, valid, tables,
                        *rest, *carried)

        entry.__name__ = step.__name__      # the program keeps its name
        donate = () if self.backend == "cpu" else (1, 2)
        return jax.jit(entry, donate_argnums=donate)

    def _make_step_fn(self, sample: bool, spec: bool = False,
                      compact: bool = False, pair: bool = False):
        model, config = self.model, self.config
        capture = self._capture_logp

        def _logp_at(logits, out, temps_b):
            # Behavior log-prob of the chosen token under the ACTUAL
            # sampling distribution — softmax(logits/temp) when temp > 0,
            # plain softmax for greedy lanes (argmax is deterministic;
            # its soft log-prob is still the importance-weighting anchor
            # the V-trace learner corrects against).
            z = logits.astype(jnp.float32)
            lp = jax.nn.log_softmax(
                jnp.where(temps_b > 0, z / jnp.maximum(temps_b, 1e-6), z))
            return jnp.take_along_axis(lp, out[..., None], axis=-1)[..., 0]

        n_moe = 1 if config.n_experts else 0
        stateful = any(run.mixer is not None
                       for run in model.spec(config).runs)

        def step(params, k, v, tokens, positions, valid, tables, ctx_lens,
                 gather, temps, seeds, counters, *carried, slots=None):
            # `carried`: the lanes' last sampled tokens, then (an expert
            # configuration's step takes them last and returns them last,
            # summed up on the device) the load counters.  A caller that
            # lowers the step for its shapes alone may leave the tokens
            # out: the same program less two selects.  (`v` is None over a
            # latent cache.)
            moe_load = carried[len(carried) - n_moe:]
            last_tok = carried[0] if len(carried) > n_moe else None
            if last_tok is not None:
                # A decode lane whose token the step before sampled reads
                # it where that step left it (`_build_batch`'s -1).
                tokens = jnp.where(tokens < 0, last_tok[:, None], tokens)
            # (over a state cache a compact program's rows name their
            # slots; row i is lane i otherwise)
            x, k, v, *moe_load = model.forward_cached(
                params, tokens, positions, valid, k, v, tables, ctx_lens,
                config, *moe_load,
                **({"slots": slots} if slots is not None else {}))
            next_tok, *logp = sample_tokens(params, x, gather, temps, seeds,
                                            counters)
            if last_tok is not None and not spec:
                next_tok = jnp.where(counters >= 0, next_tok, last_tok)
            return (next_tok, *logp, k, v, *moe_load)

        def compact_step(params, k, v, tokens, positions, valid, tables,
                         ctx_lens, gather, temps, seeds, counters, rows,
                         last_tok, *moe_load):
            # The same step over [prefill_lanes, T]: row i is lane rows[i]
            # (max_lanes: nobody's).  Its table and its last token are
            # gathered by that index, what it samples is scattered back to
            # it, and every lane's last token is returned last.
            mine = jnp.take(last_tok, rows, mode="clip")
            next_tok, *rest = step(
                params, k, v, tokens, positions, valid,
                jnp.take(tables, rows, axis=0, mode="clip"), ctx_lens,
                gather, temps, seeds, counters, mine, *moe_load,
                slots=rows if stateful else None)
            return (next_tok, *rest,
                    last_tok.at[rows].set(next_tok, mode="drop"))

        def pair_step(params, k, v, decode, chunk, tables, last_tok,
                      *moe_load):
            # The decoding lanes' step and the compact step in ONE: `decode`
            # the T=1 population's arrays (tokens ... counters) over
            # [max_lanes, 1], `chunk` the compact one's (... rows) over
            # [prefill_lanes, T].  The model lays the rows of both end to
            # end under one read of each weight; the head multiplies the
            # one sampled row of every lane of either.  Returns what either
            # sampled, the decoding lanes' first, and last every lane's
            # last token as both leave it.
            tokens, positions, valid, ctx_lens, _, *draws = decode
            (more, positions_c, valid_c, ctx_c, gather, *draws_c,
             rows) = chunk
            tokens = jnp.where(tokens < 0, last_tok[:, None], tokens)
            mine = jnp.take(last_tok, rows, mode="clip")
            x, k, v, *moe_load = model.forward_cached(
                params, tokens, positions, valid, k, v, tables, ctx_lens,
                config, *moe_load, chunk=(more, Lanes(
                    jnp.take(tables, rows, axis=0, mode="clip"),
                    positions_c, valid_c, ctx_c,
                    rows if stateful else None)))
            b, (n, t) = tokens.shape[0], more.shape
            last = jnp.take_along_axis(
                x[b:].reshape(n, t, -1),
                gather[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            temps, seeds, counters = (
                jnp.concatenate(both) for both in zip(draws, draws_c))
            next_tok, *logp = sample_rows(
                params, jnp.concatenate([x[:b, 0], last]), temps, seeds,
                counters)
            next_tok = jnp.where(counters >= 0, next_tok,
                                 jnp.concatenate([last_tok, mine]))
            return (next_tok, *logp, k, v, *moe_load,
                    next_tok[:b].at[rows].set(next_tok[b:], mode="drop"))

        def next_logits(params, x):
            # The next token's logits: the head's first `vocab_size`
            # columns (all of them, but for a model whose head also
            # predicts the tokens after the next).
            return model.lm_head(params, x, config)[..., :config.vocab_size]

        def sample_tokens(params, x, gather, temps, seeds, counters):
            """(next tokens,) or, capturing, (next tokens, their logps)."""
            if spec:
                # Verify shape: EVERY position's next token is sampled
                # in-graph — position j draws with the key the plain
                # step would use after j more commits, fold_in(seed,
                # counter + j), so the accepted prefix is token-exact
                # with non-speculative decode.  T = spec_k+1 is small;
                # the [B, T, V] logits stay on device and the step's
                # only non-pool output is [B, T] int32.
                logits = next_logits(params, x)              # [B, T, V]
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if not sample:
                    out = greedy
                else:
                    offs = jnp.arange(logits.shape[1], dtype=jnp.int32)

                    def draw_lane(rows, temp, seed, counter):
                        def draw_pos(row, off):
                            key = jax.random.fold_in(jax.random.key(seed),
                                                     counter + off)
                            z = row.astype(jnp.float32) / jnp.maximum(temp,
                                                                      1e-6)
                            return jax.random.categorical(key, z).astype(
                                jnp.int32)

                        return jax.vmap(draw_pos)(rows, offs)

                    sampled = jax.vmap(draw_lane)(logits, temps, seeds,
                                                  counters)
                    out = jnp.where(temps[:, None] > 0, sampled, greedy)
                if capture:
                    return out, _logp_at(logits, out, temps[:, None, None])
                return (out,)
            # Only each lane's last valid position reaches the lm head —
            # a prefill chunk never materializes [B, T, V], and the
            # logits never leave the device: sampling happens HERE and
            # the step's only non-pool output is one token id per lane.
            return sample_rows(params, jnp.take_along_axis(
                x, gather[:, None, None].astype(jnp.int32), axis=1)[:, 0],
                temps, seeds, counters)

        def sample_rows(params, xg, temps, seeds, counters):
            """`sample_tokens` of the rows that sample, xg [B, D]."""
            logits = next_logits(params, xg)                 # [B, V]
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if not sample:
                if capture:
                    return greedy, _logp_at(logits, greedy, temps[:, None])
                return (greedy,)

            def draw(row, temp, seed, counter):
                # Key = f(request seed, tokens produced): reproducible
                # per request regardless of lane index or who else is
                # in the batch.
                key = jax.random.fold_in(jax.random.key(seed), counter)
                z = row.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
                return jax.random.categorical(key, z).astype(jnp.int32)

            sampled = jax.vmap(draw)(logits, temps, seeds, counters)
            next_tok = jnp.where(temps > 0, sampled, greedy)
            if capture:
                return next_tok, _logp_at(logits, next_tok, temps[:, None])
            return (next_tok,)

        impl = pair_step if pair else compact_step if compact else step
        self._step_impls[(sample, "spec") if spec else sample] = impl
        # Donated, the pools come back as the buffers they went in as
        # (forward_cached writes and reads blocks of them in place);
        # the CPU backend ignores donation with a warning, so don't ask.
        donate = () if self.backend == "cpu" else (1, 2)
        return jax.jit(impl, donate_argnums=donate)

    def _close_windows(self, lanes) -> None:
        """Close the full window of every lane of `lanes` whose next
        position opens one (`PagedKVCache.close_window`: the table is
        rewritten now, on the host, which knows positions by counting and
        every token of the window: its last was sampled by a step already
        committed) and dispatch the compaction program for them,
        `prefill_lanes` rows a call: behind the step in flight, which wrote
        the window's last row, ahead of the step being built."""
        due = []
        for lane, req in lanes:
            start = int(self.cache.seq_lens[lane]) + req.ahead_len
            if self.cache.window_due(lane, start):
                due.append(self.cache.close_window(
                    lane, req.prompt + req.emitted))
        n = self.prefill_lanes
        for i in range(0, len(due), n):
            group = due[i:i + n]
            tok = spans.begin("engine", "eva_compact")
            src, dst = (np.zeros((n, len(group[0][j])), np.int32)
                        for j in (0, 1))
            for row, (s, d) in enumerate(group):
                src[row], dst[row] = s, d
            args = (self._served, self.cache.k, self.cache.v,
                    jnp.asarray(src), jnp.asarray(dst),
                    jnp.asarray(np.arange(n) < len(group)))
            if not self._compact:
                with _making(("compact", self.prefill_lanes)) as made:
                    self._compact = {
                        "fn": self._make_compact_fn(),
                        "avals": jax.tree.map(
                            lambda x: jax.ShapeDtypeStruct(
                                x.shape, x.dtype), args)}
                    self.cache.update_pools(*self._compact["fn"](*args))
                self._compact["made"] = made
            else:
                self.cache.update_pools(*self._compact["fn"](*args))
            spans.end(tok, lanes=len(group))

    def _make_compact_fn(self):
        """The compaction program: `model.compact_cached` over (served
        weights, K pool, V pool, src, dst, live), the pools donated like a
        step's."""
        donate = () if self.backend == "cpu" else (1, 2)
        return jax.jit(partial(self.model.compact_cached,
                               config=self.config), donate_argnums=donate)

    def _commit(self, live, chunks, news, toks, lps=None):
        """Apply one dispatch's results: advance prefill cursors, seal
        newly-full blocks into the prefix index, stream sampled tokens
        (a multi-token speculative burst commits ATOMICALLY — one queue
        item), roll back rejected draft blocks, finish + free lanes.

        `toks` is [max_lanes, T]: T=1 rows for prefill/plain decode, the
        per-position verify samples for a speculative dispatch.  `chunks`
        and `news` are what `_plan` put in flight for each lane.

        The row of a request that ended while the step was in flight (an
        `eos` in the step before it, cancel(), a deadline, shutdown()) is
        dropped here: never streamed, never sealed, not counted in
        `produced`.  Its blocks, one claimed for the overrun position
        included, went back to the allocator with the lane, and what the
        step wrote there is harmless as a rejected draft's is
        (`PagedKVCache.truncate_lane`)."""
        met = _metrics()
        for lane, req in live:
            req.ahead_len -= chunks[lane]
            req.ahead_new -= news[lane]
            if self._lanes[lane] is not req:
                self._ahead["overrun_tokens"] += news[lane]
                continue
            row = toks[lane]
            draft = req.draft
            req.draft = ()
            was_prefill = req.prefilling
            if was_prefill:
                req.fed += chunks[lane]
                self.cache.seq_lens[lane] += chunks[lane]
                self.cache.seal_full_blocks(lane, req.prompt)
                if req.prefilling:
                    continue  # more prompt to go; nothing sampled yet
                if req.prefill_only:
                    # Disaggregated prefill: the prompt's K/V is sealed
                    # in the prefix index (it survives the lane free as
                    # evictable blocks); no token is sampled or
                    # streamed.  The sampled row is discarded — the
                    # decode replica draws it with the same fold_in
                    # keys, so output stays token-exact.
                    req.finish_reason = "prefill"
                    req.out.put(_DONE)
                    self.cache.free_lane(lane)
                    self._lanes[lane] = None
                    _end_spans(req, tokens=0)
                    events.record("engine", "finish", trace=req.trace,
                                  rid=req.rid, reason="prefill",
                                  produced=0)
                    continue
                burst = [int(row[0])]
                accepted = 0
            else:
                # Exact-match verification: position j's K/V and sample
                # are only valid if every earlier fed draft matched the
                # model's own output, so the burst is the accepted draft
                # prefix plus the first divergent (or bonus) sample.
                accepted = 0
                while (accepted < len(draft)
                       and int(row[accepted]) == draft[accepted]):
                    accepted += 1
                burst = [int(row[j]) for j in range(accepted + 1)]
            # Clamp the burst when a stop condition lands mid-burst:
            # tokens past eos / the max_new_tokens budget were never
            # "generated" — they are discarded, not streamed.
            emit: List[int] = []
            for tok in burst:
                emit.append(tok)
                if req.eos_id is not None and tok == req.eos_id:
                    req.finish_reason = "eos"
                    break
                if req.produced + len(emit) >= req.max_new_tokens:
                    req.finish_reason = "length"
                    break
            m = len(emit)
            if not was_prefill:
                # Commit K/V for the m verified positions, release the
                # blocks the rejected tail claimed, and seal only what
                # is now committed history (drafted blocks never enter
                # the prefix index early: sealing is bounded by
                # seq_lens, which counts accepted tokens only).
                self.cache.seq_lens[lane] += m
                if chunks[lane] > m:
                    self.cache.truncate_lane(
                        lane, int(self.cache.seq_lens[lane]))
                if self.cache.has_blocks_to_seal(lane):
                    self.cache.seal_full_blocks(
                        lane, req.prompt + req.emitted + emit)
            # SLO latency accounting: first emit is TTFT (queue wait +
            # prefill included); a later burst of m tokens closes m TBT
            # gaps of the mean inter-token latency this step achieved.
            now = time.time()
            first = req.produced == 0
            if first:
                if req.submitted:
                    met["ttft"].observe(now - req.submitted)
            elif req.last_emit:
                gap = (now - req.last_emit) / m
                for _ in range(m):
                    met["tbt"].observe(gap)
            req.last_emit = now
            req.last_token = emit[-1]
            req.emitted.extend(emit)
            if lps is not None:
                # lps rows are position-parallel with toks rows, so the
                # clamped emit prefix maps 1:1 onto the first m entries.
                req.logps.extend(float(lps[lane, j]) for j in range(m))
            req.produced += m
            if self._proposer is not None and not was_prefill:
                self._spec_stats["emitted"] += m
                self._spec_stats["bursts"] += 1
                met["spec_per_step"].observe(m)
            if draft:
                self._spec_stats["drafted"] += len(draft)
                self._spec_stats["accepted"] += accepted
                met["spec_drafted"].inc(len(draft))
                met["spec_accepted"].inc(accepted)
                if self._spec_adaptive:
                    # Per-lane draft length: grow on full acceptance,
                    # halve on total rejection, otherwise track what
                    # the stream actually sustains.
                    if accepted == len(draft):
                        req.spec_k = min(self.spec_k, req.spec_k + 1)
                    elif accepted == 0:
                        req.spec_k = max(1, req.spec_k // 2)
                    else:
                        req.spec_k = max(1, min(req.spec_k, accepted + 1))
                self._proposer.observe(len(draft), accepted)
            # The consumer sees a burst as ONE item: no partial-draft
            # exposure, and failover snapshots never split a burst.
            req.out.put(emit[0] if m == 1 else list(emit))
            if req.finish_reason is None \
                    and int(self.cache.seq_lens[lane]) >= self.cache.max_seq_len:
                req.finish_reason = "max_seq_len"
            if req.trace is not None and (first
                                          or req.finish_reason is not None):
                # The first emit closes the prefill span and opens the
                # request's one decode span; the finish closes whichever
                # is open.  Nothing per token in between.
                spans.end(req.span_tok, tokens=req.produced)
                req.span_tok = (
                    None if req.finish_reason is not None else
                    spans.begin("engine", "decode", ctx=req.trace,
                                rid=req.rid))
            if req.finish_reason is not None:
                req.out.put(_DONE)
                self.cache.free_lane(lane)
                self._lanes[lane] = None
                events.record("engine", "finish", trace=req.trace,
                              rid=req.rid, reason=req.finish_reason,
                              produced=req.produced)
