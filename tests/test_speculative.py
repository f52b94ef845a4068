"""Speculative decoding tests: token-exactness vs the non-speculative
engine (greedy and seeded sampling), paged-KV rollback invariants under
rejection storms, adaptive draft-length backoff/recovery, mixed
speculative/plain lanes in one verify step, burst atomicity and
mid-burst stop clamping, prefix-cache interaction (drafted blocks never
sealed until accepted), failover resume, and the chaos gate (replica
kill mid-burst resumes token-exact)."""

import queue

import pytest

import ray_tpu
from ray_tpu._private import fault_injection as fi
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu.inference import InferenceEngine, NgramProposer
from ray_tpu.inference.speculative import (DraftProposer,
                                           ModelDraftProposer,
                                           resolve_draft_proposer)


def _engine(spec_k=0, proposer="ngram", params=None, **kw):
    kw.setdefault("max_lanes", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return InferenceEngine("gpt", "nano", params=params, auto_start=False,
                           seed=0, spec_k=spec_k, draft_proposer=proposer,
                           **kw)


class OracleProposer(DraftProposer):
    """Drafts the exact continuation a reference run produced — 100%
    acceptance by construction (single-request engines only)."""

    def __init__(self, prompt, continuation):
        self.prompt = list(prompt)
        self.cont = [int(t) for t in continuation]
        self.calls = []

    def propose(self, context, k):
        self.calls.append(k)
        pos = len(context) - len(self.prompt)
        return self.cont[pos:pos + k]


class AntiOracleProposer(OracleProposer):
    """Drafts a token guaranteed to DIFFER from the reference
    continuation at every position — 0% acceptance by construction."""

    def __init__(self, prompt, continuation, vocab):
        super().__init__(prompt, continuation)
        self.vocab = vocab

    def propose(self, context, k):
        return [(t + 1) % self.vocab
                for t in super().propose(context, k)]


# ---------------------------------------------------------------------------
# Proposer units
# ---------------------------------------------------------------------------

def test_ngram_proposer_prompt_lookup():
    p = NgramProposer(max_ngram=3)
    # Suffix [7, 8] occurred earlier; the most recent occurrence is
    # followed by [9, 1] — proposed verbatim, capped at k.
    ctx = [7, 8, 9, 1, 7, 8, 9, 1, 7, 8]
    assert p.propose(ctx, 4) == [9, 1, 7, 8]
    assert p.propose(ctx, 2) == [9, 1]
    assert p.propose([1, 2, 3, 4, 5], 4) == []      # nothing repeats
    assert p.propose([5], 4) == []                  # no suffix to match
    # min_ngram=1 catches a constant stream.
    assert p.propose([3, 3, 3], 2) == [3, 3]
    with pytest.raises(ValueError, match="min_ngram"):
        NgramProposer(max_ngram=0)


def test_resolve_draft_proposer():
    assert isinstance(resolve_draft_proposer("ngram"), NgramProposer)
    p = NgramProposer()
    assert resolve_draft_proposer(p) is p
    with pytest.raises(ValueError, match="unknown draft proposer"):
        resolve_draft_proposer("nope")


# ---------------------------------------------------------------------------
# Token-exactness vs the non-speculative engine
# ---------------------------------------------------------------------------

def test_spec_token_exact_greedy_and_sampled():
    plain = _engine()
    spec = _engine(spec_k=4, params=plain.params)
    # Repetitive prompt: n-gram drafting fires and bursts really commit.
    prompt = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    greedy = plain.generate(prompt, 24)
    assert spec.generate(prompt, 24) == greedy
    st = spec.stats()
    assert st["spec_drafted_tokens"] > 0
    assert st["spec_steps"] > 0
    # Seeded sampling: per-position keys are fold_in(seed, produced+j),
    # identical to the keys the plain engine folds step by step.
    sampled = plain.generate(prompt, 24, temperature=0.8, seed=123)
    assert spec.generate(prompt, 24, temperature=0.8, seed=123) == sampled


def test_spec_emits_multi_token_bursts():
    plain = _engine(max_lanes=1)
    full = plain.generate([5, 6, 7], 16)
    spec = _engine(spec_k=4, params=plain.params, max_lanes=1,
                   proposer=OracleProposer([5, 6, 7], full))
    assert spec.generate([5, 6, 7], 16) == full
    st = spec.stats()
    # Perfect drafts: strictly more than one token per verify step.
    assert st["spec_accepted_per_step"] > 1.5
    assert st["spec_steps"] < len(full)


# ---------------------------------------------------------------------------
# Paged-KV rollback under rejection storms
# ---------------------------------------------------------------------------

def test_rejection_storm_rolls_back_blocks():
    plain = _engine(max_lanes=1, prefix_cache=False)
    prompt = [2, 3, 4]
    full = plain.generate(prompt, 20)
    vocab = plain.config.vocab_size
    spec = _engine(spec_k=4, params=plain.params, max_lanes=1,
                   prefix_cache=False,
                   proposer=AntiOracleProposer(prompt, full, vocab),
                   spec_adaptive=False)     # keep drafting k=4 junk
    h = spec.submit(prompt, 20)
    while spec.step():
        # Rollback invariant after EVERY commit: a live lane owns
        # exactly the blocks its committed length needs — rejected
        # draft tokens never leave stray tail blocks behind.
        for lane, req in enumerate(spec._lanes):
            if req is None:
                continue
            assert len(spec.cache.lane_blocks(lane)) == \
                spec.cache.layout.blocks_needed(int(spec.cache.seq_lens[lane]))
    assert h.tokens() == full               # still token-exact
    st = spec.stats()
    assert st["spec_drafted_tokens"] > 0
    assert st["spec_accepted_tokens"] == 0  # every draft rejected
    # Full conservation: everything returned to the free list.
    assert spec.cache.allocator.num_free == spec.cache.allocator.num_blocks


def test_adaptive_k_backs_off_and_recovers():
    plain = _engine(max_lanes=1)
    prompt = [9, 8, 7]
    full = plain.generate(prompt, 40)
    vocab = plain.config.vocab_size
    # Phase 1: guaranteed rejection — the per-lane draft length halves
    # from 8 down to the floor of 1.
    anti = AntiOracleProposer(prompt, full, vocab)
    spec = _engine(spec_k=8, params=plain.params, max_lanes=1,
                   proposer=anti)
    assert spec.generate(prompt, 16) == full[:16]
    assert anti.calls[0] == 8
    assert 1 in anti.calls                  # reached the floor
    assert all(b <= a for a, b in zip(anti.calls, anti.calls[1:]))
    # Phase 2: guaranteed acceptance — the draft length grows back by
    # one per fully-accepted burst (the tail call may shrink again as
    # the remaining token budget clamps the draft).
    oracle = OracleProposer(prompt, full)
    spec = _engine(spec_k=8, params=plain.params, max_lanes=1,
                   proposer=oracle)
    h = spec.submit(prompt, 40)
    h._req.spec_k = 1                       # start the lane at the floor
    while spec.step():
        pass
    assert h.tokens() == full
    assert oracle.calls[0] == 1
    peak = max(oracle.calls)
    assert peak >= 6                        # climbed well off the floor
    climb = oracle.calls[:oracle.calls.index(peak) + 1]
    assert climb == sorted(climb)           # monotone recovery


# ---------------------------------------------------------------------------
# Mixed speculative / plain lanes in one step
# ---------------------------------------------------------------------------

def test_mixed_spec_and_plain_lanes_share_a_step():
    class Selective(DraftProposer):
        """Drafts only for contexts starting with the marker token, so
        one lane speculates while its neighbour decodes plainly in the
        SAME verify dispatch."""

        def __init__(self, marker, inner):
            self.marker = marker
            self.inner = inner

        def propose(self, context, k):
            if context[0] != self.marker:
                return []
            return self.inner.propose(context, k)

    plain = _engine()
    p_spec = [4, 5, 4, 5, 4, 5, 4]
    p_plain = [9, 2, 6]
    a = plain.generate(p_spec, 12)
    b = plain.generate(p_plain, 12)
    spec = _engine(spec_k=3, params=plain.params,
                   proposer=Selective(4, NgramProposer()))
    dispatches = []
    orig = spec._build_batch

    def snoop(live, t, *prefill):
        batch, chunks = orig(live, t, *prefill)
        dispatches.append((t, dict(chunks)))
        return batch, chunks

    spec._build_batch = snoop
    h1 = spec.submit(p_spec, 12)
    h2 = spec.submit(p_plain, 12)
    while spec.step():
        pass
    assert h1.tokens() == a
    assert h2.tokens() == b
    assert spec.stats()["spec_drafted_tokens"] > 0
    # At least one verify dispatch (t > 1) carried BOTH a drafting lane
    # (chunk > 1) and a draftless lane riding at chunk=1.
    assert any(t > 1 and len(ch) == 2
               and min(ch.values()) == 1 and max(ch.values()) > 1
               for t, ch in dispatches)


# ---------------------------------------------------------------------------
# Burst atomicity + mid-burst stop conditions
# ---------------------------------------------------------------------------

def test_burst_commits_atomically():
    plain = _engine(max_lanes=1)
    prompt = [3, 1, 4]
    full = plain.generate(prompt, 12)
    spec = _engine(spec_k=4, params=plain.params, max_lanes=1,
                   proposer=OracleProposer(prompt, full))
    h = spec.submit(prompt, 12)
    items = []
    while spec.step():
        # Drain the stream queue between steps: each element is what one
        # commit made visible — a burst arrives as ONE list item, never
        # as a partially delivered draft.
        while True:
            try:
                items.append(h._req.out.get_nowait())
            except queue.Empty:
                break
    flat = []
    for it in items:
        if isinstance(it, list):
            flat.extend(it)
        elif isinstance(it, int):
            flat.append(it)               # (skips the _DONE sentinel)
    assert flat == full
    assert any(isinstance(it, list) and len(it) > 1 for it in items)


def test_eos_mid_burst_clamps_over_generated_drafts():
    plain = _engine(max_lanes=1)
    prompt = [6, 2, 8]
    full = plain.generate(prompt, 16)
    eos = full[4]                           # lands mid-burst under k=4
    expect = plain.generate(prompt, 16, eos_id=eos)
    spec = _engine(spec_k=4, params=plain.params, max_lanes=1,
                   proposer=OracleProposer(prompt, full))
    h = spec.submit(prompt, 16, eos_id=eos)
    while spec.step():
        pass
    got = h.tokens()
    assert got == expect
    assert got[-1] == eos
    assert h.finish_reason == "eos"
    # Tokens drafted past the stop were discarded, not streamed.
    assert len(got) == full.index(eos) + 1


def test_max_new_tokens_mid_burst_is_exact():
    plain = _engine(max_lanes=1)
    prompt = [1, 7, 3]
    full = plain.generate(prompt, 16)
    spec = _engine(spec_k=4, params=plain.params, max_lanes=1,
                   proposer=OracleProposer(prompt, full))
    h = spec.submit(prompt, 6)              # budget lands mid-burst
    while spec.step():
        pass
    assert h.tokens() == full[:6]
    assert h.finish_reason == "length"


# ---------------------------------------------------------------------------
# Prefix-cache interaction
# ---------------------------------------------------------------------------

def test_drafted_blocks_never_sealed_until_accepted():
    plain = _engine(max_lanes=1)
    prompt = [2, 2, 3] * 6          # 2 full blocks + 2 tokens to prefill
    full = plain.generate(prompt, 16)
    spec = _engine(spec_k=4, params=plain.params, max_lanes=1,
                   proposer=OracleProposer(prompt, full))
    h = spec.submit(prompt, 16)
    while spec.step():
        # Sealing is bounded by the COMMITTED length: a block that
        # still holds unverified draft K/V can never enter the
        # content-addressed index.
        for lane, req in enumerate(spec._lanes):
            if req is not None:
                assert spec.cache._lane_sealed[lane] * \
                    spec.cache.block_size <= int(spec.cache.seq_lens[lane])
    assert h.tokens() == full
    # The sealed chain is the same one the plain engine would build, so
    # a second identical prompt admits through the prefix cache and
    # still decodes token-exact.
    plain.generate(prompt, 16)
    assert spec.cache.num_indexed_blocks == plain.cache.num_indexed_blocks
    spec2 = _engine(spec_k=4, params=plain.params, max_lanes=1,
                    proposer=OracleProposer(prompt, full))
    spec2_full = spec2.generate(prompt, 16)
    hits0 = spec2.stats()["prefix_hits"]
    assert spec2.generate(prompt, 16) == spec2_full == full
    assert spec2.stats()["prefix_hits"] == hits0 + 1


# ---------------------------------------------------------------------------
# Failover building blocks
# ---------------------------------------------------------------------------

def test_sample_offset_resume_is_seed_consistent_with_spec():
    plain = _engine()
    prompt = [1, 2, 1, 2, 1, 2]
    full = plain.generate(prompt, 10, temperature=0.9, seed=42)
    spec = _engine(spec_k=4, params=plain.params)
    part = spec.generate(prompt, 3, temperature=0.9, seed=42)
    assert part == full[:3]
    # Resume mid-stream: produced tokens re-enter as prompt and
    # sample_offset keeps the key counter at the ORIGINAL position even
    # though verify steps now sample several positions at once.
    h = spec.submit(prompt + part, max_new_tokens=len(full) - 3,
                    temperature=0.9, seed=42, sample_offset=3)
    while spec.step():
        pass
    assert h.tokens() == full[3:]


def test_model_draft_proposer_self_draft_accepts():
    plain = _engine(max_lanes=1)
    # The draft model IS the target model (same params): greedy drafts
    # equal greedy verification, so every draft is accepted and the
    # output stays token-exact.
    spec = _engine(spec_k=3, params=plain.params, max_lanes=1,
                   proposer=ModelDraftProposer(
                       "gpt", "nano", params=plain.params, window=32))
    prompt = [4, 9, 1]
    assert spec.generate(prompt, 10) == plain.generate(prompt, 10)
    st = spec.stats()
    assert st["spec_accepted_tokens"] == st["spec_drafted_tokens"] > 0
    assert st["spec_accepted_per_step"] > 1.5


# ---------------------------------------------------------------------------
# Chaos gate: replica kill mid-burst resumes token-exact
# ---------------------------------------------------------------------------

@pytest.fixture
def serve_chaos_cluster(request):
    cfg = dict(getattr(request, "param", {}))
    info = ray_tpu.init(num_cpus=4, object_store_memory=64 << 20,
                        _system_config=cfg)
    from ray_tpu import serve
    serve.start()
    try:
        yield info
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
        from ray_tpu.serve import _private as sp
        with sp._router_states_lock:
            sp._router_states.clear()
        GLOBAL_CONFIG.invalidate_cache()
        fi.reset()


def _metric(name):
    from ray_tpu.util import metrics
    return metrics.read(name) or 0.0


@pytest.mark.chaos
@pytest.mark.parametrize(
    "serve_chaos_cluster",
    [{"chaos_enabled": True, "chaos_seed": 31,
      # Scripted: every replica incarnation dies at its 4th serve event
      # — mid-generation, and with spec_k=4 bursts mid-BURST: the lane
      # is killed between a burst's commit and the stream draining it.
      "chaos_kill_replica_salts": "*",
      "chaos_kill_replica_at": 4,
      "chaos_max_faults": 1}],
    indirect=True)
def test_replica_kill_mid_burst_resumes_token_exact(serve_chaos_cluster):
    from ray_tpu import serve
    prompt, budget = [1, 2, 3, 1, 2, 3, 1, 2], 8
    expected = InferenceEngine("gpt", "nano", seed=0).generate(
        prompt, budget)
    handle = serve.run(serve.LLMDeployment.options(
        name="llm_spec_chaos").bind(model="gpt", config="nano",
                                    max_lanes=4, seed=0,
                                    speculative=True, spec_k=4))
    before = _metric("serve_stream_failovers")
    got = list(handle.options("generate",
                              failover=serve.llm_stream_resume)
               .stream(prompt, budget))
    assert got == expected
    assert _metric("serve_stream_failovers") - before >= 1
