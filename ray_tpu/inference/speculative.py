"""Self-speculative decoding: draft proposers for the batching engine.

The continuous-batching engine emits ONE token per jitted step, so every
per-step cost — dispatch, host scheduling, the sampling commit — is paid
per token.  Speculative decoding breaks that coupling: a cheap DRAFT of
up to k candidate tokens is verified by the real model in a single step.
The verify dispatch feeds ``[last_token, d_1 .. d_k]`` at positions
``[n .. n+k]`` (the chunked-prefill shape, so causal masking inside the
chunk already holds) and samples ALL k+1 next-token positions in-graph;
the longest prefix of drafts that matches the model's own sampled output
commits as one burst, and the blocks claimed for the rejected tail roll
back through ``PagedKVCache.truncate_lane``.

Output is token-exact vs the non-speculative engine by construction:
every emitted token IS the model's sampled token for its position (same
``fold_in(seed, produced)`` key the plain step would use) — drafts only
decide how many of those positions one step may confirm.

The core proposer is **n-gram / prompt-lookup** drafting (no second
model, so it runs on CPU CI): the request's own prompt + produced
history is scanned for the most recent earlier occurrence of the current
suffix n-gram, and the tokens that followed it are proposed verbatim.
On repetitive text (code, templated prose, multi-turn transcripts)
acceptance is high and decode collapses toward (k+1) tokens per step; on
incompressible text the per-request adaptive draft length backs off so
rejected verify FLOPs stay bounded.

``ModelDraftProposer`` is the optional small-draft-model path: a second
(cheaper) model greedily drafts from the tail of the context.  Anything
implementing :class:`DraftProposer` plugs into
``InferenceEngine(draft_proposer=...)``.
"""

from __future__ import annotations

from typing import List, Sequence


class DraftProposer:
    """Pluggable draft source for speculative decoding.

    ``propose`` receives the request's full known token context
    (prompt + everything emitted so far; the last element is the token
    the next step feeds) and may return up to ``k`` candidate
    continuation tokens — fewer (or none) when it has no confident
    guess, which degrades that lane to a plain one-token decode step.
    ``observe`` is acceptance feedback after each verify, for proposers
    that tune themselves.
    """

    def propose(self, context: Sequence[int], k: int) -> List[int]:
        raise NotImplementedError

    def observe(self, drafted: int, accepted: int) -> None:
        """Called after each verify with how many tokens this proposer
        drafted for the lane and how many the model accepted."""


class NgramProposer(DraftProposer):
    """Prompt-lookup drafting: match the longest suffix n-gram of the
    context against its own earlier occurrences (most recent match wins)
    and propose the tokens that followed that occurrence.

    ``max_ngram`` trades precision for match rate: longer suffixes
    produce fewer, better-targeted matches.  The scan falls through to
    shorter n-grams (down to ``min_ngram``) when a longer one has no
    earlier occurrence, and prefers the most RECENT match that still
    has k following tokens — on a cyclic stream the nearest occurrence
    sits only one period back with few followers, so older occurrences
    are what let the draft span several periods.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError("need 1 <= min_ngram <= max_ngram")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def propose(self, context: Sequence[int], k: int) -> List[int]:
        n_ctx = len(context)
        best: List[int] = []
        # The suffix itself (ending at n_ctx) must not count as a match,
        # hence the scan stops one short of the trailing occurrence — so
        # every hit has at least one following token to propose.
        for n in range(min(self.max_ngram, n_ctx - 1),
                       self.min_ngram - 1, -1):
            pattern = tuple(context[n_ctx - n:])
            for i in range(n_ctx - n - 1, -1, -1):
                if tuple(context[i:i + n]) != pattern:
                    continue
                cont = [int(t) for t in context[i + n:i + n + k]]
                if len(cont) >= k:
                    return cont
                if len(cont) > len(best):
                    best = cont
        return best


class ModelDraftProposer(DraftProposer):
    """Small-draft-model drafting: a second (cheaper) model greedily
    continues the tail of the context for up to k tokens.

    The draft model only needs to agree with the target model often
    enough to pay for its own forward passes — classic two-model
    speculative decoding.  ``window`` bounds the context the draft
    forward sees (full forward, no KV cache: the draft model is assumed
    small enough that re-running its prefix is cheaper than managing a
    second paged pool).
    """

    def __init__(self, model="gpt", config="nano", params=None, *,
                 window: int = 64, seed: int = 0):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import decoder, family

        self.model = model = family(model)
        self.config = (model.CONFIGS[config] if isinstance(config, str)
                       else config)
        if params is None:
            params = model.init_params(self.config, jax.random.key(seed))
        self.params = params
        self.window = int(window)

        def _next(params, toks):
            logits, _ = decoder.forward(model.spec, params, toks, self.config)
            return jnp.argmax(logits[0, -1]).astype(jnp.int32)

        self._next = jax.jit(_next)
        self._jnp = jnp

    def propose(self, context: Sequence[int], k: int) -> List[int]:
        toks = [int(t) for t in context[-self.window:]]
        out: List[int] = []
        for _ in range(k):
            nxt = int(self._next(
                self.params,
                self._jnp.asarray([toks[-self.window:]], self._jnp.int32)))
            out.append(nxt)
            toks.append(nxt)
        return out


def resolve_draft_proposer(spec) -> DraftProposer:
    """Engine-side resolution of the ``draft_proposer=`` argument:
    ``"ngram"`` (the CPU-cheap default), or any DraftProposer
    instance."""
    if isinstance(spec, DraftProposer):
        return spec
    if spec == "ngram":
        return NgramProposer()
    raise ValueError(
        f"unknown draft proposer {spec!r}: pass 'ngram' or a "
        f"DraftProposer instance")
