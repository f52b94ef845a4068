"""LLM serving: serve-plane front end for the continuous-batching engine.

One InferenceEngine per replica.  Every serve request — streaming or
not — submits into the replica's shared lane array, so concurrent
requests batch onto the same jitted decode step instead of running the
model once per request; tokens flow back through the existing serve
stream-ticket path (`handle.options("generate").stream(...)` pulls them
incrementally, replica-pinned).

Mid-stream failover: pair the handle with the `llm_stream_resume`
policy (``handle.options("generate", failover=llm_stream_resume)``) and
a replica death mid-generation is absorbed by resubmitting with the
already-produced tokens appended to the prompt.  The prefix cache makes
the re-prefill cheap, and the resumed stream is token-exact for greedy
decoding; sampled decoding is seed-consistent too when the request
carries an explicit ``seed`` (the engine folds the per-step sampling key
from (seed, produced+sample_offset), so the resumed request draws the
same keys the dead replica would have drawn).
"""

import time
from typing import List, Optional

from ray_tpu.serve.api import deployment
from ray_tpu.util import spans


def llm_stream_resume(args, kwargs, received):
    """Failover policy for LLMDeployment.generate streams: resume the
    generation where the dead replica stopped instead of replaying it.

    Rewrites (args, kwargs) so the resubmitted request carries
    ``prompt + received`` as its prompt, a decremented token budget, and
    ``_produced_offset=len(received)`` to keep the in-jit sampling keys
    aligned with the original request.  Returns None when the stream was
    already complete (budget exhausted or EOS emitted), which ends the
    stream cleanly instead of resubmitting a no-op request."""
    args = list(args)
    kwargs = dict(kwargs)
    if args:
        prompt = args.pop(0)
    else:
        prompt = kwargs.pop("prompt")
    if args:
        budget = args.pop(0)
    else:
        budget = kwargs.pop("max_new_tokens", 16)
    # Anything left positionally maps onto generate()'s signature order.
    for name, val in zip(("temperature", "eos_id", "seed"), args):
        kwargs.setdefault(name, val)
    received = [int(t) for t in received]
    remaining = int(budget) - len(received)
    if remaining <= 0:
        return None
    eos_id = kwargs.get("eos_id")
    if eos_id is not None and received and received[-1] == int(eos_id):
        return None
    new_prompt = [int(t) for t in prompt] + received
    kwargs["max_new_tokens"] = remaining
    kwargs["_produced_offset"] = len(received)
    return (new_prompt,), kwargs


class TokenStream:
    """What `LLMReplica.generate` returns: the token ids of one request
    as the engine emits them, for a `for`, a `next()` or a `list()`.
    `ready()` says that the next token, or the stream's end, is here, so
    the replica's `next_chunk` sends a caller what has arrived in one
    reply.  `close()` is the consumer leaving mid-stream (cancel,
    deadline, disconnect): the lane is evicted, so the engine stops
    decoding for nobody; a stream dropped unclosed does the same when it
    is collected."""

    def __init__(self, handle):
        self._handle = handle
        self._open = True           # neither run to its end nor closed

    def __iter__(self):
        return self

    def __next__(self) -> int:
        try:
            return int(next(self._handle))
        except StopIteration:
            self._open = False
            raise

    def ready(self) -> bool:
        return self._handle.ready()

    def close(self) -> None:
        if self._open:
            self._open = False
            self._handle.cancel()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class LLMReplica:
    """Replica callable wrapping an InferenceEngine: the class that
    `serve.LLMDeployment` deploys, under a name of its own for whoever
    subclasses it or calls it without a cluster.

    Usage::

        app = serve.LLMDeployment.bind(model="gpt", config="nano",
                                       max_lanes=8)
        handle = serve.run(app)
        for tok in handle.options("generate").stream([1, 2, 3],
                                                     max_new_tokens=16):
            ...                      # token ids, streamed as generated
        handle.remote([1, 2, 3]).result()   # non-streaming: full list

    On a cluster that advertises TPU every replica leases one chip
    (``leases_chip``); ``ray_actor_options={"num_tpus": n}`` overrides it.
    """

    leases_chip = True

    def __init__(self, model="gpt", config="nano", params=None, *,
                 max_lanes: int = 8, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 prefill_chunk: int = 32,
                 prefill_lanes: Optional[int] = None, seed: int = 0,
                 prefix_cache: bool = True, speculative: bool = False,
                 spec_k: Optional[int] = None, draft_proposer="ngram",
                 kv_tier: Optional[bool] = None):
        from ray_tpu._private.config import GLOBAL_CONFIG
        # jax, and what the engine imports with it: replica-only, once a
        # process, so in the start-up record.
        with spans.span("proc", "jax_import", pin=True):
            from ray_tpu.inference import InferenceEngine
        # `speculative=True` opts the replica into speculative decoding;
        # the draft length defaults to the cluster-wide `spec_k` config
        # knob unless pinned per deployment.
        if spec_k is None:
            spec_k = GLOBAL_CONFIG.spec_k if speculative else 0
        self._engine = InferenceEngine(
            model, config, params, max_lanes=max_lanes,
            block_size=block_size, num_blocks=num_blocks,
            max_seq_len=max_seq_len, prefill_chunk=prefill_chunk,
            prefill_lanes=prefill_lanes, seed=seed,
            prefix_cache=prefix_cache,
            spec_k=int(spec_k), draft_proposer=draft_proposer,
            spec_adaptive=GLOBAL_CONFIG.spec_adaptive,
            kv_tier=kv_tier)
        self._profile = None        # the open profiler session's span

    def generate(self, prompt, max_new_tokens: int = 16,
                 temperature: float = 0.0, eos_id: Optional[int] = None,
                 seed: Optional[int] = None, _produced_offset: int = 0,
                 _deadline_s: Optional[float] = None):
        """Streaming entry point: a `TokenStream`, so serve hands the
        caller a stream ticket and tokens are pulled as the engine emits
        them, those that have arrived since the last pull in one reply.

        `_produced_offset` / `_deadline_s` are serve-plane plumbing:
        the failover policy sets the offset so a resumed request samples
        with the original request's key sequence, and the replica
        injects the remaining deadline budget so the engine evicts the
        lane (instead of decoding for nobody) once it lapses."""
        return TokenStream(self._engine.submit(
            prompt, max_new_tokens, temperature=temperature,
            eos_id=eos_id, seed=seed, sample_offset=_produced_offset,
            deadline_s=_deadline_s))

    def __call__(self, prompt, max_new_tokens: int = 16,
                 temperature: float = 0.0,
                 eos_id: Optional[int] = None,
                 seed: Optional[int] = None,
                 _deadline_s: Optional[float] = None) -> List[int]:
        """Non-streaming: block until the sequence finishes (or the
        propagated request deadline cancels it)."""
        handle = self._engine.submit(prompt, max_new_tokens,
                                     temperature=temperature,
                                     eos_id=eos_id, seed=seed)
        return handle.tokens(timeout=_deadline_s)

    def prefix_summary(self) -> dict:
        """Compact prefix-index summary for prefix-cache-aware routing:
        the router scrapes this periodically and scores this replica by
        the deepest prompt hash-chain prefix it already holds.  Bounded
        by ``serve_prefix_summary_size`` — never the full index."""
        return self._engine.prefix_summary()

    def stats(self) -> dict:
        """The engine's backend and device, occupancy, prefix-cache and
        speculative-acceptance counters (the same numbers the engine
        exports through util.metrics, so `cli metrics` scrapes them from
        the replica process)."""
        return self._engine.stats()

    def compiled_steps(self) -> dict:
        """Kernel calls and in-place bytes of each compiled step shape
        (see InferenceEngine.compiled_steps)."""
        return self._engine.compiled_steps()

    def start_trace(self, trace_dir: str) -> bool:
        """Open a jax profiler session in this replica, the only process
        that can trace its chip (`handle.options("start_trace")`): device
        operations and the engine's `engine/<phase>` annotations on one
        clock, the Python tracer off (it would slow the loop it measures).
        One `engine/profile` span a session says when the profiler was
        open, and how long its start and stop held the caller."""
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        t0 = time.perf_counter()
        self._profile = spans.begin("engine", "profile", trace_dir=trace_dir)
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        self._profile_start_s = time.perf_counter() - t0
        return True

    def stop_trace(self) -> bool:
        """Close the session `start_trace` opened and write its file."""
        import jax
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        spans.end(self._profile, start_s=self._profile_start_s,
                  stop_s=time.perf_counter() - t0)
        self._profile = None
        return True


LLMDeployment = deployment(name="llm", max_concurrent_queries=64)(LLMReplica)
