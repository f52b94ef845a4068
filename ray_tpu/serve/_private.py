"""Serve internals: controller, replica, router/handle, HTTP proxy.

Reference parity: python/ray/serve/_private/ — ServeController
(controller.py:71) reconciles DeploymentState (deployment_state.py:1006);
replicas host user code (replica.py:268); Router picks replicas with
max_concurrent_queries backpressure (router.py:224); HTTPProxy is the
ASGI ingress (http_proxy.py:434).  Config propagation here is pull-based
with revalidation on failure (the reference uses long-poll; same
eventual-consistency contract, no blocked actor threads).

Graceful degradation (reference: serve's replica graceful_shutdown_* +
DeploymentResponseGenerator retry semantics):

- Replica lifecycle STARTING -> RUNNING -> DRAINING -> DEAD.  Downscale,
  redeploy, delete and shutdown move victims to DRAINING: out of the
  routing table immediately, killed only once ``ongoing_requests()``
  quiesces or ``serve_drain_deadline_s`` lapses.
- Mid-stream failover: ``DeploymentHandle.stream``/``stream_async``
  record delivered chunks; on replica loss they heal the replica set and
  resubmit under the handle's failover policy ("replay" skips already-
  delivered chunks; a callable policy rewrites the request — the LLM
  path appends produced tokens to the prompt so the prefix cache makes
  re-prefill cheap and the resumed stream is token-exact).
- Deadline propagation: a per-request deadline bounds admission waits,
  travels to the replica (which aborts not-yet-started work and evicts
  expired streams), and stops retries/failovers.
- Load shedding: a bounded per-deployment admission queue fast-fails
  with ServeOverloadedError (+ retry-after hint) instead of stacking
  unbounded waiters, and ``_pick_replica`` is power-of-two-choices on
  in-flight counts.
"""

from __future__ import annotations

import itertools
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import ray_tpu
from ray_tpu._private.accelerators import default_num_tpus
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu.exceptions import (
    ActorDiedError, ActorUnavailableError, GetTimeoutError,
    ReplicaStreamLostError, ServeOverloadedError, TaskError)
from ray_tpu.util import events, spans, tracing

CONTROLLER_NAME = "SERVE_CONTROLLER"
SERVE_NAMESPACE = "serve"

# Replica lifecycle states (reference: serve ReplicaState).
REPLICA_STARTING = "STARTING"
REPLICA_RUNNING = "RUNNING"
REPLICA_DRAINING = "DRAINING"
REPLICA_DEAD = "DEAD"


def _kill_quietly(actors) -> None:
    """Best-effort kill of replicas that never entered the routing table."""
    for actor in actors:
        try:
            ray_tpu.kill(actor)
        except Exception:
            pass


_SERVE_MET = None


# SLO latency buckets for the serve plane (queue wait is often sub-ms;
# end-to-end can run to minutes under backpressure).
_SLO_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)


def _serve_metrics() -> dict:
    global _SERVE_MET
    if _SERVE_MET is None:
        from ray_tpu.util.metrics import Counter, Gauge, Histogram
        _SERVE_MET = {
            "drained": Counter(
                "serve_replicas_drained",
                "Replicas retired after graceful draining"),
            "drain_deadline_kills": Counter(
                "serve_drain_deadline_kills",
                "Draining replicas force-killed at the drain deadline"),
            "draining": Gauge(
                "serve_draining_replicas",
                "Replicas currently in the DRAINING state"),
            "shed": Counter(
                "serve_requests_shed",
                "Requests fast-failed with ServeOverloadedError at the "
                "admission queue"),
            "failovers": Counter(
                "serve_stream_failovers",
                "Streaming requests resubmitted after replica loss"),
            "retries": Counter(
                "serve_request_retries",
                "Unary requests retried through a healed replica set"),
            "queue_wait": Histogram(
                "serve_queue_wait_s",
                "Admission wait (request arrival -> replica acquired)",
                buckets=_SLO_BUCKETS),
            "e2e": Histogram(
                "serve_e2e_s",
                "Unary request end-to-end latency (call -> result)",
                buckets=_SLO_BUCKETS),
        }
    return _SERVE_MET


def _is_replica_loss(e: BaseException) -> bool:
    """True for errors that mean "the replica (or its stream state) is
    gone" — the triggers for heal + resubmit.  A ReplicaStreamLostError
    raised replica-side crosses the wire wrapped in TaskError, so the
    traceback string is checked too."""
    if isinstance(e, (ActorDiedError, ActorUnavailableError,
                      ReplicaStreamLostError)):
        return True
    if isinstance(e, TaskError):
        return "ReplicaStreamLostError" in (e.traceback_str or "")
    return False


def _chaos_kill_point() -> bool:
    """Serve-plane chaos interposition: a replica process draws one
    deterministic kill verdict per serve event (request dispatch or
    stream-chunk pull) — see fault_injection.kill_replica.  Whether chaos
    is on at all."""
    from ray_tpu._private.fault_injection import get_chaos
    chaos = get_chaos()
    if chaos is None:
        return False
    if chaos.kill_replica():
        import logging
        import os
        logging.getLogger("ray_tpu").warning(
            "chaos: killing serve replica process")
        events.record("serve", "chaos_kill", pid=os.getpid())
        events.dump_crash("chaos_kill_replica")
        os._exit(1)
    return True


@dataclass
class AutoscalingConfig:
    """Queue-depth replica autoscaling (reference:
    serve/_private/autoscaling_policy.py + serve/config.py
    AutoscalingConfig): desired = ceil(total_ongoing_requests /
    target_ongoing_requests), clamped to [min, max], applied after the
    respective delay has elapsed continuously."""

    min_replicas: int = 1
    max_replicas: int = 4
    target_ongoing_requests: float = 2.0
    upscale_delay_s: float = 0.2
    downscale_delay_s: float = 2.0


@dataclass
class DeploymentConfig:
    name: str
    num_replicas: int = 1
    max_concurrent_queries: int = 100
    ray_actor_options: dict = field(default_factory=dict)
    user_config: Any = None
    autoscaling_config: Optional[AutoscalingConfig] = None
    version: int = 0
    # Bound on requests WAITING for a replica slot (per deployment, per
    # client process) before ServeOverloadedError sheds the excess.
    # None = the serve_queue_length config default; 0 = unbounded.
    queue_limit: Optional[int] = None


# Most chunks one next_chunk reply carries behind its first: what a
# consumer a few seconds behind has waiting, and a bound on the pulls a
# reply makes on the replica's loop.
_STREAM_BURST = 256


def _says_what_arrived(result) -> bool:
    """Whether a method's result is a stream with `ready()`: an iterator
    that can say its next chunk is here and has a `close()` for the
    consumer that leaves."""
    return all(callable(getattr(result, name, None))
               for name in ("__next__", "ready", "close"))


def _reply_chunks(out: dict):
    """The chunks of one next_chunk reply, in order."""
    if "chunk" in out:
        yield out["chunk"]
        yield from out.get("more", ())


@ray_tpu.remote
class ReplicaActor:
    """Hosts one copy of the user's callable (reference: replica.py:268).

    An ASYNC actor: the actor's persistent event loop hosts every
    in-flight request, exactly as the reference replica runs a user event
    loop — so an async deployment overlaps its awaits WITHIN one replica
    (10 concurrent requests that each await 100ms take ~100ms, not ~1s).
    Sync callables run on a thread pool so they can never stall the loop
    (and so blocking helpers like @serve.batch keep working)."""

    def __init__(self, cls_or_fn, init_args, init_kwargs, user_config=None,
                 max_concurrent_queries: int = 100):
        import inspect
        from concurrent.futures import ThreadPoolExecutor
        if inspect.isclass(cls_or_fn):
            self._callable = cls_or_fn(*init_args, **(init_kwargs or {}))
        else:
            self._callable = cls_or_fn
        if user_config is not None and hasattr(self._callable,
                                               "reconfigure"):
            self._callable.reconfigure(user_config)
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, min(max_concurrent_queries, 64)),
            thread_name_prefix="replica-sync")
        self._ongoing = 0
        # In-progress streaming responses: stream id -> async generator
        # (reference: replica-side generator streaming, replica.py's
        # handle_request_streaming).  Chunks are PULLED by the caller
        # (proxy or handle) one next_chunk() at a time — incremental by
        # construction, replica-pinned by the router.
        self._streams: dict = {}
        self._stream_ids = itertools.count(1)
        # sid -> absolute monotonic deadline (or None) for deadline
        # enforcement between chunk pulls.
        self._stream_deadlines: dict = {}
        # Streams cancelled while their sync generator was mid-pull on
        # the thread pool (generators cannot be closed while running);
        # the in-flight next_chunk closes them once the pull returns.
        self._cancelled: set = set()
        # method name -> whether its signature accepts `_deadline_s`
        # (deadline-aware deployments get the remaining budget passed in).
        self._deadline_aware: dict = {}

    async def handle_request(self, method_name, args, kwargs,
                             stream: bool = False,
                             deadline_s: Optional[float] = None):
        import asyncio
        import inspect
        _chaos_kill_point()
        # Traced requests get a serve/replica span around the user-code
        # invocation (child of the task exec span via the contextvar set
        # by the worker); `ongoing` captures concurrent load at entry.
        tok = (spans.begin("serve", "replica",
                           method=method_name or "__call__",
                           ongoing=self._ongoing)
               if tracing.current_context() is not None else None)
        cv = (tracing._ctx.set((tok.trace_id, tok.sid))
              if tok is not None and tok.trace_id else None)
        self._ongoing += 1  # loop-thread only: no lock needed
        try:
            target = self._callable
            if method_name and method_name != "__call__":
                target = getattr(self._callable, method_name)
            elif not callable(target):
                raise TypeError("deployment object is not callable")
            kwargs = kwargs or {}
            deadline = None
            if deadline_s is not None:
                if deadline_s <= 0:
                    # Already past the request deadline before any work
                    # started: abort pre-dispatch instead of burning a
                    # replica slot on a result nobody will wait for.
                    raise TimeoutError(
                        f"request deadline exceeded before "
                        f"{method_name or '__call__'!r} started")
                deadline = time.monotonic() + deadline_s
                mname = method_name or "__call__"
                aware = self._deadline_aware.get(mname)
                if aware is None:
                    try:
                        aware = ("_deadline_s"
                                 in inspect.signature(target).parameters)
                    except (TypeError, ValueError):
                        aware = False
                    self._deadline_aware[mname] = aware
                if aware:
                    kwargs["_deadline_s"] = deadline_s
            if inspect.isasyncgenfunction(target) or inspect.isgeneratorfunction(target):
                if not stream:
                    # Non-streaming caller (handle.remote(), plain HTTP
                    # dispatch): a stream ticket would leak its slot
                    # (no one would pull chunks), and materializing an
                    # unbounded generator would wedge the replica —
                    # reference behavior: require the streaming API.
                    raise TypeError(
                        f"method {method_name or '__call__'!r} is a "
                        f"generator; call it via handle.stream() / "
                        f"stream_async() (or the ASGI route), not "
                        f".remote()")
                # Streaming method: stash the generator and hand back a
                # stream ticket; the in-flight slot stays charged until
                # the consumer drains or cancels (next_chunk below).
                ticket = self._open_stream(target(*args, **kwargs), deadline)
                spans.end(tok, stream=True)
                tok = None
                return ticket
            if inspect.iscoroutinefunction(target) or (
                    not inspect.isfunction(target)
                    and not inspect.ismethod(target)
                    and inspect.iscoroutinefunction(
                        getattr(target, "__call__", None))):
                return await target(*args, **kwargs)
            loop = asyncio.get_running_loop()
            # run_in_executor does not propagate contextvars: carry the
            # request's trace context onto the pool thread so engine
            # events recorded inside sync deployments join the trace.
            import contextvars
            ctx = contextvars.copy_context()
            result = await loop.run_in_executor(
                self._pool, lambda: ctx.run(target, *args, **kwargs))
            if inspect.iscoroutine(result):
                # Sync wrapper handing back a coroutine: finish it here.
                return await result
            if _says_what_arrived(result):
                # A stream handed back by a plain method (the LLM
                # replica's `generate`): a generator method's ticket and
                # slot, and `ready()` for next_chunk to send what has
                # arrived in one reply.
                if not stream:
                    result.close()
                    raise TypeError(
                        f"method {method_name or '__call__'!r} returns a "
                        f"stream; call it via handle.stream() / "
                        f"stream_async() (or the ASGI route), not "
                        f".remote()")
                ticket = self._open_stream(result, deadline)
                spans.end(tok, stream=True)
                tok = None
                return ticket
            return result
        finally:
            self._ongoing -= 1
            spans.end(tok)
            if cv is not None:
                tracing._ctx.reset(cv)

    def _open_stream(self, gen, deadline: Optional[float]) -> dict:
        """Stash a streaming response and hand back its ticket; the
        in-flight slot stays charged until the stream ends."""
        sid = next(self._stream_ids)
        self._streams[sid] = gen
        self._stream_deadlines[sid] = deadline
        self._ongoing += 1   # held until stream end
        return {"__serve_stream__": sid}

    async def next_chunk(self, sid: int):
        """Pull the next chunk of stream `sid`: {"chunk": value} or
        {"done": True}.  Sync generators advance on the thread pool so
        they cannot stall the replica loop.  A stream that says what has
        arrived (`ready()`: its next chunk, or its end, is here and a
        pull would not wait) is pulled on the loop while it says so, and
        the reply carries those chunks too, in order, under "more", and
        "done" beside them if the end was among them: a consumer whose
        round trip is longer than the producer's step gets two steps'
        chunks a call, where one a call left it further behind with
        every chunk and seconds late at the stream's end (PERF.md
        section 6, PR 43).  An UNKNOWN sid means this
        replica restarted and lost its in-memory streams — raise
        ReplicaStreamLostError so the handle fails over instead of
        silently truncating the stream with a fake "done"."""
        import asyncio
        import inspect
        # Under chaos a reply carries one chunk: a scripted kill names a
        # replica's N-th serve event, which a stream must reach however
        # the load batches its chunks (test_spans' torn span, ROADMAP D5).
        burst = 0 if _chaos_kill_point() else _STREAM_BURST
        gen = self._streams.get(sid)
        if gen is None:
            raise ReplicaStreamLostError(sid)
        deadline = self._stream_deadlines.get(sid)
        if deadline is not None and time.monotonic() > deadline:
            # Past the request deadline: abort replica-side — closing
            # the generator runs its cleanup (the LLM path cancels its
            # GenerationHandle on GeneratorExit, evicting the engine
            # lane) even if the consumer has already given up.
            await self.cancel_stream(sid)
            raise TimeoutError(
                f"stream {sid}: request deadline exceeded")
        try:
            if inspect.isasyncgen(gen):
                chunk = await gen.__anext__()
            else:
                # StopIteration cannot cross a Future: pull behind a
                # sentinel on the thread pool.
                def _pull():
                    try:
                        return True, gen.__next__()
                    except StopIteration:
                        return False, None
                ready = getattr(gen, "ready", None)
                if ready is not None and ready():
                    alive, chunk = _pull()      # here already: no wait
                else:
                    import contextvars
                    loop = asyncio.get_running_loop()
                    ctx = contextvars.copy_context()
                    alive, chunk = await loop.run_in_executor(
                        self._pool, lambda: ctx.run(_pull))
                    if sid in self._cancelled:
                        # cancel_stream caught this generator mid-pull
                        # and could not close it; it is suspended now.
                        self._cancelled.discard(sid)
                        try:
                            gen.close()
                        except Exception:
                            pass
                        return {"done": True}
                if not alive:
                    self._finish_stream(sid)
                    return {"done": True}
                out = {"chunk": chunk}
                more = []
                while ready is not None and len(more) < burst and ready():
                    alive, chunk = _pull()
                    if not alive:
                        self._finish_stream(sid)
                        out["done"] = True
                        break
                    more.append(chunk)
                if more:
                    out["more"] = more
                return out
            return {"chunk": chunk}
        except StopAsyncIteration:
            self._finish_stream(sid)
            return {"done": True}
        except Exception:
            self._finish_stream(sid)
            raise

    async def cancel_stream(self, sid: int):
        gen = self._streams.get(sid)
        if gen is not None:
            try:
                if hasattr(gen, "aclose"):
                    await gen.aclose()
                else:
                    gen.close()
            except ValueError:
                # Sync generator currently executing on the thread pool:
                # close() is illegal mid-frame.  Tombstone the sid; the
                # in-flight next_chunk closes it when the pull returns.
                self._cancelled.add(sid)
            except Exception:
                pass
            self._finish_stream(sid)
        return True

    def _finish_stream(self, sid: int) -> None:
        self._stream_deadlines.pop(sid, None)
        if self._streams.pop(sid, None) is not None:
            self._ongoing -= 1

    async def ongoing_requests(self) -> int:
        """Autoscaling load signal (reference: replicas report queue
        metrics to the controller)."""
        return self._ongoing

    def reconfigure(self, user_config):
        if hasattr(self._callable, "reconfigure"):
            self._callable.reconfigure(user_config)
        return True

    def ping(self):
        return "pong"


@ray_tpu.remote(max_concurrency=64)
class ServeController:
    """Deployment table + reconciliation (reference: controller.py:71,
    DeploymentStateManager deployment_state.py:1864).  Threaded actor:
    the control loop (autoscaling) and long-poll waiters run alongside
    deploy/routing calls; the deployment table is lock-protected."""

    def __init__(self):
        # name -> {"config": DeploymentConfig, "replicas": [handles],
        #          "deployed_def": (cls, args, kwargs)}
        self._deployments: Dict[str, dict] = {}
        self._lock = threading.RLock()
        self._version = 0
        self._version_cv = threading.Condition(self._lock)
        self._loop_started = False
        self._stopped = False
        # name -> (desired_replicas, since_monotonic) scale intent
        self._scale_intent: Dict[str, tuple] = {}
        # Graceful-drain records, appended whenever a replica leaves the
        # routing table with work possibly in flight:
        # {"name", "replica", "since", "deadline", "zero_streak"}
        self._draining: List[dict] = []
        self._drained_total = 0
        self._drain_deadline_kills = 0

    def _bump_version(self):
        with self._version_cv:
            self._version += 1
            self._version_cv.notify_all()

    # ---------------- long-poll config plane ----------------

    def poll_routing(self, name: str, known_version: int,
                     timeout_s: float = 10.0):
        """Block until the config version moves past known_version (or
        timeout), then return the routing table (reference:
        _private/long_poll.py:68 LongPollHost)."""
        deadline = time.monotonic() + timeout_s
        with self._version_cv:
            while self._version == known_version and not self._stopped:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._version_cv.wait(remaining)
        return self.get_routing(name)

    # ---------------- autoscaling control loop ----------------

    def run_control_loop(self, interval_s: float = 0.2):
        """Reference: the controller's run loop (controller.py) driving
        autoscaling_policy decisions.  Runs on one of this threaded
        actor's pool threads forever."""
        with self._lock:
            if self._loop_started:
                return False
            self._loop_started = True
        while not self._stopped:
            try:
                self._autoscale_pass()
            except Exception:
                pass
            try:
                self._drain_pass()
            except Exception:
                pass
            time.sleep(interval_s)
        return True

    # ---------------- graceful draining ----------------

    def _drain_replica(self, name: str, replica) -> None:
        """Move one replica to DRAINING: the caller has already removed
        it from the routing table; it keeps serving its in-flight
        requests and streams, and _drain_pass kills it only once
        ongoing_requests() quiesces (or the drain deadline lapses)."""
        key = replica._actor_id.binary()
        rec = {"name": name, "replica": replica,
               "since": time.monotonic(),
               "deadline": (time.monotonic()
                            + GLOBAL_CONFIG.serve_drain_deadline_s),
               "zero_streak": 0}
        with self._lock:
            if any(r["replica"]._actor_id.binary() == key
                   for r in self._draining):
                return  # already draining (reconcile/delete race)
            self._draining.append(rec)
            n = len(self._draining)
            # Defense-in-depth: if any path leaves the victim visible in
            # a routing snapshot, its state says DRAINING and the router
            # filters it before scoring candidates.
            entry = self._deployments.get(name)
            if entry is not None and key in entry.get("states", {}):
                entry["states"][key] = REPLICA_DRAINING
        _serve_metrics()["draining"].set(n)
        events.record("serve", "drain_start", deployment=name)

    def _drain_pass(self, immediate: bool = False) -> int:
        """One sweep over DRAINING replicas: fan out ongoing_requests()
        probes, kill every replica that has quiesced or whose drain
        deadline lapsed, and return how many are still draining.

        Quiescence needs TWO consecutive zero observations — a single
        zero can race a request dispatched by a router that has not yet
        seen the post-drain routing table.  `immediate` (the shutdown
        path) kills on the first zero."""
        with self._lock:
            records = list(self._draining)
        if not records:
            return 0
        refs = [r["replica"].ongoing_requests.remote() for r in records]
        try:
            ready, _ = ray_tpu.wait(refs, num_returns=len(refs), timeout=5)
        except Exception:
            ready = []
        ready_ids = {ref.id for ref in ready}
        now = time.monotonic()
        met = _serve_metrics()
        for rec, ref in zip(records, refs):
            kill = dead = False
            if ref.id in ready_ids:
                try:
                    ongoing = ray_tpu.get(ref, timeout=5)
                except Exception:
                    dead = True  # died on its own: nothing left to drain
                else:
                    if ongoing <= 0:
                        rec["zero_streak"] += 1
                        if immediate or rec["zero_streak"] >= 2:
                            kill = True
                    else:
                        rec["zero_streak"] = 0
            if not dead and not kill and now >= rec["deadline"]:
                kill = True
                met["drain_deadline_kills"].inc()
                self._drain_deadline_kills += 1
                events.record("serve", "drain_deadline_kill",
                              deployment=rec.get("name"))
            if not (kill or dead):
                continue
            if kill:
                try:
                    ray_tpu.kill(rec["replica"])
                except Exception:
                    pass
            with self._lock:
                if rec in self._draining:
                    self._draining.remove(rec)
                    self._drained_total += 1
            met["drained"].inc()
            events.record("serve", "drained", deployment=rec.get("name"))
        with self._lock:
            remaining = len(self._draining)
        met["draining"].set(remaining)
        return remaining

    def drain_stats(self):
        with self._lock:
            return {"draining": len(self._draining),
                    "drained_total": self._drained_total,
                    "deadline_kills": self._drain_deadline_kills}

    def _autoscale_pass(self):
        with self._lock:
            entries = {n: e for n, e in self._deployments.items()
                       if e["config"].autoscaling_config is not None}
        for name, entry in entries.items():
            cfg: DeploymentConfig = entry["config"]
            auto: AutoscalingConfig = cfg.autoscaling_config
            replicas = list(entry["replicas"])
            if not replicas:
                continue
            total = 0
            for r in replicas:
                try:
                    total += ray_tpu.get(r.ongoing_requests.remote(),
                                         timeout=5)
                except Exception:
                    pass
            import math
            desired = max(auto.min_replicas,
                          min(auto.max_replicas,
                              math.ceil(total /
                                        max(auto.target_ongoing_requests,
                                            1e-9))))
            now = time.monotonic()
            current = len(replicas)
            if desired == current:
                self._scale_intent.pop(name, None)
                continue
            intent = self._scale_intent.get(name)
            if intent is None or intent[0] != desired:
                self._scale_intent[name] = (desired, now)
                continue
            delay = (auto.upscale_delay_s if desired > current
                     else auto.downscale_delay_s)
            if now - intent[1] < delay:
                continue
            with self._lock:
                entry = self._deployments.get(name)
                if entry is None:
                    continue
                entry["config"].num_replicas = desired
            self._reconcile(name)
            self._scale_intent.pop(name, None)
            self._bump_version()

    def deploy(self, config: DeploymentConfig, cls_or_fn, init_args,
               init_kwargs):
        with self._lock:
            entry = self._deployments.get(config.name)
            if entry is None:
                entry = {"config": config, "replicas": [],
                         "deployed_def": (cls_or_fn, init_args, init_kwargs)}
                self._deployments[config.name] = entry
            else:
                entry["config"] = config
                entry["deployed_def"] = (cls_or_fn, init_args, init_kwargs)
                # New code/config version: existing replicas are stale and
                # get replaced below (reference: deployment_state.py rolling
                # version replacement).
                entry["def_version"] = entry.get("def_version", 0) + 1
            if config.autoscaling_config is not None:
                config.num_replicas = max(
                    config.autoscaling_config.min_replicas,
                    min(config.num_replicas,
                        config.autoscaling_config.max_replicas))
        self._reconcile(config.name)
        self._bump_version()
        return {"name": config.name, "replicas": len(entry["replicas"])}

    def _reconcile(self, name: str):
        """Converge the replica set.  Blocking actor RPCs (pings, replica
        construction) run WITHOUT the table lock — holding it would stall
        every get_routing/poll_routing for the duration of a replica cold
        start.  A per-deployment lock serializes concurrent reconciles."""
        with self._lock:
            entry = self._deployments.get(name)
            if entry is None:
                return
            rlock = entry.setdefault("_rlock", threading.Lock())
        with rlock:
            with self._lock:
                entry = self._deployments.get(name)
                if entry is None:
                    return
                config: DeploymentConfig = entry["config"]
                cls_or_fn, args, kwargs = entry["deployed_def"]
                replicas = list(entry["replicas"])
                def_version = entry.setdefault("def_version", 0)
                vers = dict(entry.setdefault("replica_vers", {}))
            # ---- unlocked: health checks / drains / constructions ----
            to_drain = []  # leave routing now, die only after quiescing
            candidates = []
            for r in replicas:
                key = r._actor_id.binary()
                if vers.get(key, def_version) != def_version:
                    # Stale code/config version: DRAIN, don't hard-kill —
                    # requests in flight on the old version finish
                    # (reference: rolling version replacement +
                    # graceful_shutdown_wait_loop_s).
                    vers.pop(key, None)
                    to_drain.append(r)
                    continue
                candidates.append(r)
            # Health sweep: fan the pings out and collect them with one
            # bounded wait() instead of serial 10s-timeout gets (N dead
            # replicas used to cost N*10s of controller stall).
            ping_refs = [r.ping.remote() for r in candidates]
            ready_ids = set()
            if ping_refs:
                try:
                    ready, _ = ray_tpu.wait(
                        ping_refs, num_returns=len(ping_refs), timeout=10)
                    ready_ids = {ref.id for ref in ready}
                except Exception:
                    pass
            replicas = []
            for r, ref in zip(candidates, ping_refs):
                ok = False
                if ref.id in ready_ids:
                    try:
                        ray_tpu.get(ref, timeout=10)
                        ok = True
                    except Exception:
                        ok = False
                if ok:
                    replicas.append(r)
                else:
                    vers.pop(r._actor_id.binary(), None)
            opts = dict(config.ray_actor_options)
            started = []
            # From the decision to start replicas to their first healthy
            # answers (or the failure of one): in the start-up record.
            tok_start = (spans.begin("serve", "replica_start", pin=True,
                                     name=name, n=config.num_replicas
                                     - len(replicas))
                         if len(replicas) < config.num_replicas else None)
            while len(replicas) + len(started) < config.num_replicas:
                with spans.under(tok_start):
                    actor = ReplicaActor.options(
                        num_cpus=opts.get("num_cpus", 0.1),
                        num_tpus=default_num_tpus(cls_or_fn,
                                                  opts.get("num_tpus")),
                        resources=opts.get("resources"),
                        max_restarts=2,
                        # Replicas must execute up to
                        # max_concurrent_queries requests at once, or
                        # @serve.batch could never accumulate a batch.
                        max_concurrency=config.max_concurrent_queries,
                    ).remote(cls_or_fn, args, kwargs, config.user_config,
                             config.max_concurrent_queries)
                started.append(actor)
                vers[actor._actor_id.binary()] = def_version
            while len(replicas) > config.num_replicas:
                # Downscale: victims drain instead of dropping their
                # in-flight requests on the floor.
                victim = replicas.pop()
                vers.pop(victim._actor_id.binary(), None)
                to_drain.append(victim)
            # Verify new replicas constructed (surface user __init__
            # errors) before committing them to the routing table; fan
            # out first so N cold starts overlap.
            verify = [r.ping.remote() for r in started]
            if verify:
                try:
                    ray_tpu.wait(verify, num_returns=len(verify),
                                 timeout=120)
                except Exception:
                    pass
            try:
                for ref in verify:
                    ray_tpu.get(ref, timeout=120)
            except Exception as e:
                # A replica that cannot start — its __init__ raised (no
                # chip could be opened, say), its worker died, or it is
                # still constructing — must not linger holding a chip
                # lease, and serve.run's caller gets the reason.
                _kill_quietly(started)
                spans.end(tok_start, failed=True)
                why = (f"no answer within 120 s of construction "
                       f"({type(e).__name__}); its worker's log has the rest"
                       if isinstance(e, GetTimeoutError) else str(e))
                raise RuntimeError(
                    f"deployment {name!r}: replica failed to start: "
                    f"{why}") from e
            spans.end(tok_start)
            replicas.extend(started)
            with self._lock:
                entry = self._deployments.get(name)
                if entry is None:
                    # Deployment deleted concurrently: its old replicas
                    # are already draining via delete_deployment; the
                    # freshly-started ones never served and die now.
                    _kill_quietly(started)
                    return
                entry["replicas"][:] = replicas
                entry["replica_vers"] = vers
                entry["states"] = {r._actor_id.binary(): REPLICA_RUNNING
                                   for r in replicas}
            for victim in to_drain:
                self._drain_replica(name, victim)

    def get_routing(self, name: str):
        with self._lock:
            entry = self._deployments.get(name)
            if entry is None:
                return None
            return {"replicas": list(entry["replicas"]),
                    # Per-replica lifecycle states ride the routing table
                    # so the client-side router can filter non-RUNNING
                    # replicas out of its candidate sample (a DRAINING
                    # victim must never attract new traffic — prefix
                    # affinity included).
                    "states": {k: v
                               for k, v in entry.get("states", {}).items()},
                    "max_concurrent_queries":
                        entry["config"].max_concurrent_queries,
                    "queue_limit": entry["config"].queue_limit,
                    "version": self._version}

    def list_deployments(self):
        with self._lock:
            draining: Dict[str, int] = {}
            for rec in self._draining:
                draining[rec["name"]] = draining.get(rec["name"], 0) + 1
            out = {}
            for name, e in self._deployments.items():
                states: Dict[str, int] = {}
                for s in e.get("states", {}).values():
                    states[s] = states.get(s, 0) + 1
                states[REPLICA_DRAINING] = draining.get(name, 0)
                out[name] = {"num_replicas": len(e["replicas"]),
                             "target": e["config"].num_replicas,
                             "states": states}
            return out

    def delete_deployment(self, name: str):
        with self._lock:
            entry = self._deployments.pop(name, None)
        if entry is None:
            return False
        # Out of the routing table NOW; replicas finish their in-flight
        # work and are reaped by the drain pump (or the drain deadline).
        self._bump_version()
        for r in entry["replicas"]:
            self._drain_replica(name, r)
        return True

    def heal(self, name: str):
        """Router-reported replica failure: reconcile this deployment."""
        self._reconcile(name)
        self._bump_version()
        return True

    def shutdown(self):
        self._stopped = True
        with self._version_cv:
            self._version_cv.notify_all()
        for name in list(self._deployments):
            self.delete_deployment(name)
        # Synchronous graceful drain: in-flight requests get until the
        # drain deadline; whatever remains is force-killed so shutdown
        # always terminates.
        deadline = time.monotonic() + GLOBAL_CONFIG.serve_drain_deadline_s
        while (self._drain_pass(immediate=True)
               and time.monotonic() < deadline):
            time.sleep(0.05)
        with self._lock:
            leftovers = list(self._draining)
            self._draining.clear()
        for rec in leftovers:
            try:
                ray_tpu.kill(rec["replica"])
            except Exception:
                pass
        return True


class _RouterState:
    """Per-deployment routing state SHARED by every handle in the process:
    one replica table, one in-flight map, one long-poll thread — however
    many DeploymentHandle facades exist (reference: handles share the
    Router; r2 review: per-handle pollers leaked a thread per
    handle.options() call)."""

    def __init__(self, name: str):
        self.name = name
        self.lock = threading.Lock()
        self.replicas: List = []
        self.max_q = 100
        self.rr = 0
        # In-flight counts keyed by stable replica identity (actor id).
        self.in_flight: Dict[bytes, int] = {}
        # Requests waiting for a replica slot (the bounded admission
        # queue load shedding is measured against).
        self.pending = 0
        self.queue_limit: Optional[int] = None
        self.fetched_at = 0.0
        self.known_version = -1
        self.poller: Optional[threading.Thread] = None
        # Replica lifecycle states from the routing table (actor id ->
        # "RUNNING"/"DRAINING"); non-RUNNING replicas are filtered out
        # of the candidate sample.
        self.states: Dict[bytes, str] = {}
        # Prefix-cache-aware routing (serve_prefix_routing): the scrape
        # thread fills actor id -> {"hashes": set, "block_size", "ts"};
        # summaries older than serve_prefix_staleness_s never score.
        self.prefix: Dict[bytes, dict] = {}
        self.prefix_thread: Optional[threading.Thread] = None
        self.prefix_disabled = False


_router_states: Dict[str, _RouterState] = {}
_router_states_lock = threading.Lock()


# One small shared executor for orphan-stream reaps: each reap can block
# up to 60s on the abandoned call, and a thread PER abandoned request is
# an unbounded leak under a disconnect storm.  A bounded queue-backed
# pool serializes the excess instead; reaps are cleanup, not latency-
# sensitive.
_reaper_pool = None
_reaper_pool_lock = threading.Lock()


def _get_reaper_pool():
    global _reaper_pool
    if _reaper_pool is None:
        with _reaper_pool_lock:
            if _reaper_pool is None:
                from concurrent.futures import ThreadPoolExecutor
                _reaper_pool = ThreadPoolExecutor(
                    max_workers=4, thread_name_prefix="serve-stream-reaper")
    return _reaper_pool


def _reap_orphan_stream(replica, req_ref) -> None:
    """The caller abandoned a handle_request whose ticket it never saw.
    If that call registered a stream replica-side, its generator and
    in-flight slot would be held forever (no one knows the sid) — wait
    out the call on the shared reaper pool and cancel any stream it
    opened."""
    def _reap():
        try:
            ticket = ray_tpu.get(req_ref, timeout=60)
            if isinstance(ticket, dict) and "__serve_stream__" in ticket:
                ray_tpu.get(replica.cancel_stream.remote(
                    ticket["__serve_stream__"]), timeout=10)
        except Exception:
            pass  # replica died or call failed: nothing leaked
    _get_reaper_pool().submit(_reap)


def _get_router_state(name: str) -> _RouterState:
    with _router_states_lock:
        st = _router_states.get(name)
        if st is None:
            st = _router_states[name] = _RouterState(name)
        return st


_UNSET = object()


def _chain_hashes(tokens, block_size: int):
    """Cumulative prefix-chain hash per block of `tokens` — MUST stay
    identical to inference.kv_cache.chain_hashes (pinned by a test);
    duplicated here so the routing path never imports jax."""
    out = []
    parent = 0
    for i in range((len(tokens) - 1) // block_size):
        parent = hash((parent, tuple(int(t) for t in
                                     tokens[i * block_size:
                                            (i + 1) * block_size])))
        out.append(parent)
    return out


class DeploymentHandle:
    """Client-side handle with power-of-two-choices routing + in-flight
    cap (reference: handle.py over router.py:224-263).  Picklable:
    travels to replicas so deployments can compose.  Routing state is
    shared per deployment.

    Per-handle request options (set via .options()):

    - ``timeout_s``: request deadline.  Bounds admission waits, travels
      to the replica (which aborts not-yet-started work and evicts
      expired streams), and stops retries/failovers.  Defaults to the
      ``serve_request_deadline_s`` config (0 = none).
    - ``failover``: mid-stream failover policy for stream()/
      stream_async().  None (default) surfaces replica loss to the
      caller; ``"replay"`` resubmits the original request and skips
      already-delivered chunks (requires a deterministic stream); a
      callable ``policy(args, kwargs, received) -> (args, kwargs) |
      None`` rewrites the request to resume where the dead replica
      stopped (None = the stream was already complete)."""

    def __init__(self, deployment_name: str, method_name: str = "__call__",
                 timeout_s: Optional[float] = None, failover=None):
        self._name = deployment_name
        self._method = method_name
        self._timeout_s = timeout_s
        self._failover = failover
        self._state = _get_router_state(deployment_name)

    def options(self, method_name: Optional[str] = None, *,
                timeout_s=_UNSET, failover=_UNSET) -> "DeploymentHandle":
        return DeploymentHandle(
            self._name,
            method_name if method_name is not None else self._method,
            self._timeout_s if timeout_s is _UNSET else timeout_s,
            self._failover if failover is _UNSET else failover)

    def __getattr__(self, item):
        if item.startswith("_"):
            raise AttributeError(item)
        return _MethodCaller(self, item)

    def _apply_routing(self, routing) -> None:
        st = self._state
        with st.lock:
            st.replicas = routing["replicas"]
            st.max_q = routing["max_concurrent_queries"]
            st.queue_limit = routing.get("queue_limit")
            st.known_version = routing.get("version", -1)
            st.states = dict(routing.get("states") or {})
            st.fetched_at = time.monotonic()
            alive = {r._actor_id.binary() for r in st.replicas}
            for key in list(st.in_flight):
                if key not in alive:
                    del st.in_flight[key]
            # A dead/redeployed replica's prefix summary must never
            # attract traffic: drop it with the replica, not at the
            # staleness horizon.
            for key in list(st.prefix):
                if key not in alive:
                    del st.prefix[key]

    def _refresh(self, force=False):
        st = self._state
        with st.lock:
            fresh = (not force and st.replicas
                     and time.monotonic() - st.fetched_at < 2.0)
        if fresh:
            self._ensure_poller()
            return
        try:
            controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)
            routing = ray_tpu.get(
                controller.get_routing.remote(self._name), timeout=30)
        except Exception:
            # Control-plane outage (GCS restarting, controller lookup
            # timed out).  The replicas themselves are peer-to-peer and
            # very likely still serving — keep routing on the stale
            # table instead of failing the request; the long-poller
            # refreshes the moment the control plane is back.  Only an
            # empty cache (cold start) still surfaces the error.
            with st.lock:
                stale_ok = bool(st.replicas)
                if stale_ok:
                    # Re-arm the freshness window so the next 2s of
                    # requests route on the stale table immediately
                    # instead of each re-paying the failed lookup.
                    st.fetched_at = time.monotonic()
            if not stale_ok:
                raise
            from ray_tpu.util import events
            events.record("serve", "stale_routing", deployment=self._name,
                          replicas=len(st.replicas))
            self._ensure_poller()
            return
        if routing is None:
            raise ValueError(f"deployment {self._name!r} not found")
        self._apply_routing(routing)
        self._ensure_poller()

    # ---------------- prefix-cache-aware routing ----------------

    def _ensure_prefix_scraper(self):
        """One summary-scrape thread per deployment router state (the
        poller pattern), alive only while serve_prefix_routing is on and
        the deployment actually exports summaries."""
        st = self._state
        with st.lock:
            if st.prefix_disabled or (st.prefix_thread is not None
                                      and st.prefix_thread.is_alive()):
                return
            st.prefix_thread = threading.Thread(
                target=self._prefix_scrape_loop, daemon=True,
                name=f"serve-prefix-scrape-{self._name}")
            st.prefix_thread.start()

    def _prefix_scrape_loop(self):
        import ray_tpu.api as _api
        st = self._state
        while (_api._worker is not None and not st.prefix_disabled
               and GLOBAL_CONFIG.serve_prefix_routing):
            with st.lock:
                replicas = list(st.replicas)
            for r in replicas:
                try:
                    summ = ray_tpu.get(
                        r.handle_request.remote("prefix_summary", (), {},
                                                False, 5.0),
                        timeout=5.0)
                    if not isinstance(summ, dict):
                        raise TypeError("not a summary")
                    with st.lock:
                        st.prefix[r._actor_id.binary()] = {
                            "hashes": set(summ.get("hashes") or ()),
                            "block_size": int(summ.get("block_size") or 0),
                            "ts": time.monotonic()}
                except Exception as e:
                    # Deployments without prefix_summary (non-LLM) turn
                    # scraping OFF for this router instead of hammering
                    # every replica forever; dead replicas just age out
                    # (the staleness bound stops their summaries from
                    # scoring long before the table refresh prunes them).
                    msg = f"{type(e).__name__}: {e}"
                    if ("AttributeError" in msg
                            and "prefix_summary" in msg):
                        st.prefix_disabled = True
                        return
            time.sleep(max(GLOBAL_CONFIG.serve_prefix_scrape_s, 0.05))

    def _prefix_order(self, args, kwargs) -> Optional[Dict[bytes, int]]:
        """Score replicas for this request by deepest cached prefix:
        actor id -> matched chain depth, or None when prefix routing is
        off / the request has no token prompt / no fresh summary scores
        (the caller then falls back to pure power-of-two-choices)."""
        if not GLOBAL_CONFIG.serve_prefix_routing:
            return None
        st = self._state
        if st.prefix_disabled:
            return None
        self._ensure_prefix_scraper()
        prompt = args[0] if args else (kwargs or {}).get("prompt")
        if isinstance(prompt, (list, tuple)) and prompt:
            try:
                tokens = [int(t) for t in prompt]
            except (TypeError, ValueError):
                return None
        else:
            return None
        now = time.monotonic()
        stale = GLOBAL_CONFIG.serve_prefix_staleness_s
        with st.lock:
            fresh = [(rid, info) for rid, info in st.prefix.items()
                     if now - info["ts"] <= stale]
        if not fresh:
            return None
        scores: Dict[bytes, int] = {}
        hs_by_bs: Dict[int, list] = {}
        for rid, info in fresh:
            bs = info["block_size"]
            if bs <= 0:
                continue
            hs = hs_by_bs.get(bs)
            if hs is None:
                hs = hs_by_bs[bs] = _chain_hashes(tokens, bs)
            depth = 0
            for h in hs:
                if h not in info["hashes"]:
                    break
                depth += 1
            if depth:
                scores[rid] = depth
        return scores or None

    def _ensure_poller(self):
        """Config changes PUSH to the shared router state via ONE
        controller long-poll thread per deployment (reference:
        _private/long_poll.py:185 config propagation)."""
        st = self._state
        with st.lock:
            if st.poller is not None and st.poller.is_alive():
                return
            st.poller = threading.Thread(
                target=self._poll_loop, daemon=True,
                name=f"serve-longpoll-{self._name}")
            st.poller.start()

    def _poll_loop(self):
        import ray_tpu.api as _api
        st = self._state
        while _api._worker is not None:
            try:
                controller = ray_tpu.get_actor(CONTROLLER_NAME,
                                               SERVE_NAMESPACE)
                routing = ray_tpu.get(
                    controller.poll_routing.remote(
                        self._name, st.known_version, 10.0),
                    timeout=30)
                if routing is None:
                    return  # deployment deleted
                if routing.get("version", -1) != st.known_version:
                    self._apply_routing(routing)
            except Exception:
                time.sleep(1.0)

    def remote(self, *args, **kwargs):
        return self._call(self._method, args, kwargs)

    def _pick_replica(self, prefer: Optional[Dict[bytes, int]] = None):
        """One routing decision under the in-flight cap: power-of-two-
        choices on in-flight counts (reference: router.py's least-loaded
        two-candidate sampling), ties rotated round-robin so idle
        replicas still share traffic.  If both sampled replicas are
        saturated, scan the rest — admission must succeed whenever ANY
        replica is under its cap.

        Replicas whose routing-table state is not RUNNING are filtered
        OUT of the candidate sample up front: a DRAINING victim finishes
        its in-flight work but never attracts new traffic — prefix
        affinity included (this is the draining-victim fix: the old
        sampler only noticed drained replicas at the in-flight probe).

        `prefer` (actor id -> cached-prefix depth, from _prefix_order)
        stable-sorts the candidate order deepest-prefix-first, so the
        p2c/round-robin order is exactly the fallback on ties, unknown
        replicas and stale summaries.  Returns (replica, key) or None
        when every replica is saturated."""
        st = self._state
        with st.lock:
            n = len(st.replicas)
            if n == 0:
                return None
            st.rr += 1
            if st.states:
                elig = [k for k in range(n)
                        if st.states.get(
                            st.replicas[k]._actor_id.binary(),
                            REPLICA_RUNNING) == REPLICA_RUNNING]
                if not elig:
                    # Stale/partial states must not brick routing — the
                    # in-flight probe still backstops a bad pick.
                    elig = list(range(n))
            else:
                elig = list(range(n))
            m = len(elig)
            if m == 1:
                order = list(elig)
            else:
                i = random.randrange(m)
                j = random.randrange(m - 1)
                if j >= i:
                    j += 1
                fi = st.in_flight.get(
                    st.replicas[elig[i]]._actor_id.binary(), 0)
                fj = st.in_flight.get(
                    st.replicas[elig[j]]._actor_id.binary(), 0)
                if fi == fj:
                    # Tie (the common idle case): deterministic round-
                    # robin, so even a short sequential burst provably
                    # spreads across replicas.
                    start = st.rr % m
                    order = [elig[(start + k) % m] for k in range(m)]
                else:
                    if fj < fi:
                        i, j = j, i
                    order = ([elig[i], elig[j]]
                             + [elig[k] for k in range(m)
                                if k not in (i, j)])
            if prefer:
                order.sort(key=lambda idx: -prefer.get(
                    st.replicas[idx]._actor_id.binary(), 0))
            for idx in order:
                key = st.replicas[idx]._actor_id.binary()
                if st.in_flight.get(key, 0) < st.max_q:
                    st.in_flight[key] = st.in_flight.get(key, 0) + 1
                    depth = prefer.get(key, 0) if prefer else 0
                    if depth > 0:
                        events.record("serve", "prefix_route",
                                      deployment=self._name, depth=depth)
                    return st.replicas[idx], key
        return None

    # ---------------- admission: bounded queue + shedding ----------------

    def _request_deadline(self) -> Optional[float]:
        t = self._timeout_s
        if t is None:
            cfg = GLOBAL_CONFIG.serve_request_deadline_s
            t = cfg if cfg and cfg > 0 else None
        return None if t is None else time.monotonic() + t

    def _admission_enter(self) -> None:
        """Count this request as queued; shed it with
        ServeOverloadedError if the bounded per-deployment queue is
        already full (graceful overload degradation: a fast, actionable
        failure instead of an unbounded pile-up of waiters)."""
        st = self._state
        with st.lock:
            limit = st.queue_limit
            if limit is None:
                limit = GLOBAL_CONFIG.serve_queue_length
            if limit and st.pending >= limit:
                _serve_metrics()["shed"].inc()
                events.record("serve", "shed", deployment=self._name,
                              pending=st.pending, limit=limit)
                raise ServeOverloadedError(
                    self._name, GLOBAL_CONFIG.serve_retry_after_hint_s,
                    st.pending, limit)
            st.pending += 1

    def _admission_exit(self) -> None:
        st = self._state
        with st.lock:
            st.pending = max(0, st.pending - 1)

    def _wait_deadline(self, deadline: Optional[float]) -> float:
        limit = time.monotonic() + GLOBAL_CONFIG.serve_backpressure_timeout_s
        return limit if deadline is None else min(limit, deadline)

    def _acquire_replica(self, deadline: Optional[float], prefer=None):
        """Admit one request: pick a replica under its cap (preferring
        `prefer`'s deepest-cached-prefix order when set), else wait in
        the bounded queue until one frees up, the backpressure window
        closes, or the request deadline passes."""
        t0 = time.perf_counter()
        # Traced requests get an explicit admit span (queue wait is the
        # classic serve bottleneck); untraced ones keep the instant event.
        tok = (spans.begin("serve", "admit", deployment=self._name)
               if tracing.current_context() is not None else None)
        pick = self._pick_replica(prefer)
        if pick is not None:
            self._observe_admit(t0)
            spans.end(tok, queued=False)
            return pick
        self._admission_enter()
        try:
            limit = self._wait_deadline(deadline)
            while True:
                pick = self._pick_replica(prefer)
                if pick is not None:
                    self._observe_admit(t0)
                    spans.end(tok, queued=True)
                    return pick
                if time.monotonic() > limit:
                    spans.end(tok, granted=False)
                    raise TimeoutError(
                        f"no replica of {self._name!r} under its "
                        f"max_concurrent_queries cap before the deadline")
                time.sleep(0.01)  # every replica saturated: backpressure
        finally:
            self._admission_exit()

    def _observe_admit(self, t0: float) -> None:
        wait = time.perf_counter() - t0
        _serve_metrics()["queue_wait"].observe(wait)
        events.record("serve", "admit", deployment=self._name,
                      wait_s=round(wait, 6))

    async def _acquire_replica_async(self, deadline: Optional[float],
                                     prefer=None):
        import asyncio
        t0 = time.perf_counter()
        tok = (spans.begin("serve", "admit", deployment=self._name)
               if tracing.current_context() is not None else None)
        pick = self._pick_replica(prefer)
        if pick is not None:
            self._observe_admit(t0)
            spans.end(tok, queued=False)
            return pick
        self._admission_enter()
        try:
            limit = self._wait_deadline(deadline)
            while True:
                pick = self._pick_replica(prefer)
                if pick is not None:
                    self._observe_admit(t0)
                    spans.end(tok, queued=True)
                    return pick
                if time.monotonic() > limit:
                    spans.end(tok, granted=False)
                    raise TimeoutError(
                        f"no replica of {self._name!r} under its "
                        f"max_concurrent_queries cap before the deadline")
                await asyncio.sleep(0.005)
        finally:
            self._admission_exit()

    @staticmethod
    def _remaining(deadline: Optional[float]) -> Optional[float]:
        """Deadline budget left, as handle_request's deadline_s arg."""
        return None if deadline is None else deadline - time.monotonic()

    @staticmethod
    def _step_timeout(deadline: Optional[float]) -> float:
        """Per-RPC timeout for one stream step, clipped to the request
        deadline so an expired request stops waiting promptly."""
        if deadline is None:
            return 60.0
        return max(0.1, min(60.0, deadline - time.monotonic()))

    def _call(self, method, args, kwargs):
        t0 = time.time()
        # Traced requests open a serve/request span covering submit ->
        # result(); routing, admission and the task-lifecycle subtree all
        # parent under it (the contextvar is scoped to this call so the
        # span closes from _TrackedRef on whatever thread collects it).
        tok = (spans.begin("serve", "request", deployment=self._name,
                           method=method or "__call__")
               if tracing.current_context() is not None else None)
        cv = (tracing._ctx.set((tok.trace_id, tok.sid))
              if tok is not None and tok.trace_id else None)
        try:
            self._refresh()
            deadline = self._request_deadline()
            replica, key = self._acquire_replica(
                deadline, self._prefix_order(args, kwargs))
            ref = replica.handle_request.remote(
                method, args, kwargs, False, self._remaining(deadline))
        except BaseException:
            spans.end(tok, ok=False)
            raise
        finally:
            if cv is not None:
                tracing._ctx.reset(cv)
        return _TrackedRef(ref, self, key, method, args, kwargs,
                           deadline=deadline, t0=t0, tok=tok)

    def stream(self, *args, **kwargs):
        """Synchronous streaming call: yields the chunks of a generator
        (or async-generator) deployment method INCREMENTALLY — each
        chunk is pulled from the replica on demand (reference: streaming
        DeploymentResponseGenerator over handle_request_streaming).
        Each attempt is replica-pinned; if the replica dies mid-stream
        and this handle has a failover policy, the replica set is healed
        and the request resubmitted (see the class docstring)."""
        policy = self._failover
        deadline = self._request_deadline()
        received: List[Any] = []
        cur_args, cur_kwargs = args, dict(kwargs)
        skip = 0
        attempts = 0
        while True:
            try:
                for chunk in self._stream_once(cur_args, cur_kwargs,
                                               skip, deadline):
                    received.append(chunk)
                    yield chunk
                return
            except BaseException as e:
                if policy is None or not _is_replica_loss(e):
                    raise
                attempts += 1
                if attempts > GLOBAL_CONFIG.serve_failover_attempts:
                    raise
                if deadline is not None and time.monotonic() > deadline:
                    raise
                _serve_metrics()["failovers"].inc()
                events.record("serve", "failover", deployment=self._name,
                              attempt=attempts, received=len(received))
                self._on_replica_error()
                if callable(policy):
                    resumed = policy(args, dict(kwargs), list(received))
                    if resumed is None:
                        return  # policy says the stream was complete
                    cur_args, cur_kwargs = resumed
                    skip = 0
                else:  # "replay": rerun, swallow already-seen chunks
                    cur_args, cur_kwargs = args, dict(kwargs)
                    skip = len(received)

    def _stream_once(self, args, kwargs, skip: int,
                     deadline: Optional[float]):
        """One replica-pinned streaming attempt; the first `skip` chunks
        are swallowed (already delivered by a previous attempt)."""
        self._refresh()
        replica, key = self._acquire_replica(
            deadline, self._prefix_order(args, kwargs))
        try:
            req_ref = replica.handle_request.remote(
                self._method, args, kwargs, True, self._remaining(deadline))
            try:
                ticket = ray_tpu.get(req_ref,
                                     timeout=self._step_timeout(deadline))
            except BaseException:
                # The replica may still complete the call and register a
                # stream whose sid we never learned — reap it so the
                # in-flight slot isn't held forever.
                _reap_orphan_stream(replica, req_ref)
                raise
            if not (isinstance(ticket, dict)
                    and "__serve_stream__" in ticket):
                # Non-generator method: degrade to a one-item stream.
                if skip <= 0:
                    yield ticket
                return
            sid = ticket["__serve_stream__"]
            try:
                while True:
                    out = ray_tpu.get(replica.next_chunk.remote(sid),
                                      timeout=self._step_timeout(deadline))
                    for chunk in _reply_chunks(out):
                        if skip > 0:
                            skip -= 1
                            continue
                        yield chunk
                    if out.get("done"):
                        return
            except BaseException:
                # Any abandonment (consumer close, get timeout, worker
                # error) must release the replica's stream slot.
                try:
                    ray_tpu.get(replica.cancel_stream.remote(sid),
                                timeout=10)
                except Exception:
                    pass
                raise
        finally:
            self._done(key)

    async def stream_async(self, method, args, kwargs, *,
                           timeout: float = 60.0):
        """Async streaming variant (the proxy's path): an async
        generator over the method's chunks, with the same failover
        semantics as stream()."""
        policy = self._failover
        deadline = self._request_deadline()
        received: List[Any] = []
        cur_args, cur_kwargs = args, dict(kwargs or {})
        skip = 0
        attempts = 0
        while True:
            try:
                agen = self._stream_once_async(
                    method, cur_args, cur_kwargs, skip, deadline, timeout)
                async for chunk in agen:
                    received.append(chunk)
                    yield chunk
                return
            except BaseException as e:
                if policy is None or not _is_replica_loss(e):
                    raise
                attempts += 1
                if attempts > GLOBAL_CONFIG.serve_failover_attempts:
                    raise
                if deadline is not None and time.monotonic() > deadline:
                    raise
                _serve_metrics()["failovers"].inc()
                events.record("serve", "failover", deployment=self._name,
                              attempt=attempts, received=len(received))
                self._on_replica_error()
                if callable(policy):
                    resumed = policy(args, dict(kwargs or {}),
                                     list(received))
                    if resumed is None:
                        return
                    cur_args, cur_kwargs = resumed
                    skip = 0
                else:
                    cur_args, cur_kwargs = args, dict(kwargs or {})
                    skip = len(received)

    async def _stream_once_async(self, method, args, kwargs, skip: int,
                                 deadline: Optional[float],
                                 timeout: float):
        import asyncio

        def _step(base):
            return (base if deadline is None
                    else max(0.1, min(base, deadline - time.monotonic())))

        self._refresh()
        replica, key = await self._acquire_replica_async(
            deadline, self._prefix_order(args, kwargs))
        try:
            # Per-step timeout: a wedged generator must not hold this
            # coroutine (and the in-flight slot) forever — mirror the
            # sync stream()'s bounded gets.
            req_ref = replica.handle_request.remote(
                method, args, kwargs, True, self._remaining(deadline))
            try:
                ticket = await asyncio.wait_for(
                    asyncio.wrap_future(req_ref.future()), _step(timeout))
            except BaseException:
                # Unknown-sid orphan (see stream()): reap off-loop.
                _reap_orphan_stream(replica, req_ref)
                raise
            if not (isinstance(ticket, dict)
                    and "__serve_stream__" in ticket):
                if skip <= 0:
                    yield ticket
                return
            sid = ticket["__serve_stream__"]
            try:
                while True:
                    out = await asyncio.wait_for(asyncio.wrap_future(
                        replica.next_chunk.remote(sid).future()),
                        _step(timeout))
                    for chunk in _reply_chunks(out):
                        if skip > 0:
                            skip -= 1
                            continue
                        yield chunk
                    if out.get("done"):
                        return
            except BaseException:
                # Same slot-release contract as the sync stream().
                try:
                    await asyncio.wait_for(asyncio.wrap_future(
                        replica.cancel_stream.remote(sid).future()), 10)
                except Exception:
                    pass
                raise
        finally:
            self._done(key)

    async def call_async(self, method, args, kwargs, *,
                         timeout: float = 60.0, _retried=False):
        """Async-native request path (reference: the ASGI proxy awaits the
        router/replica without burning a thread per request)."""
        import asyncio

        req_deadline = self._request_deadline()
        deadline = time.monotonic() + timeout
        if req_deadline is not None:
            deadline = min(deadline, req_deadline)
        self._refresh()
        replica, key = await self._acquire_replica_async(
            deadline, self._prefix_order(args, kwargs))
        ref = replica.handle_request.remote(
            method, args, kwargs, False, deadline - time.monotonic())
        released = False

        def release(_=None):
            nonlocal released
            if not released:
                released = True
                self._done(key)

        try:
            fut = asyncio.wrap_future(ref.future())
            try:
                result = await asyncio.wait_for(
                    fut, max(0.1, deadline - time.monotonic()))
            except asyncio.TimeoutError:
                # The request is STILL running on the replica — keep its
                # in-flight slot charged until the underlying call
                # completes, or the admission cap would over-admit.
                fut.add_done_callback(release)
                raise TimeoutError(
                    f"request to {self._name!r} timed out")
            release()
            return result
        except ActorDiedError:
            release()
            if _retried or (req_deadline is not None
                            and time.monotonic() > req_deadline):
                raise
            _serve_metrics()["retries"].inc()
            self._on_replica_error()
            return await self.call_async(
                method, args, kwargs,
                timeout=max(0.1, deadline - time.monotonic()),
                _retried=True)
        except TimeoutError:
            raise
        except BaseException:
            release()
            raise

    def _done(self, key: bytes):
        st = self._state
        with st.lock:
            if key in st.in_flight:
                st.in_flight[key] = max(0, st.in_flight[key] - 1)

    def _on_replica_error(self):
        try:
            controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)
            ray_tpu.get(controller.heal.remote(self._name), timeout=60)
        except Exception:
            pass
        self._refresh(force=True)

    def __reduce__(self):
        # failover callables must be module-level (picklable) to travel.
        return (DeploymentHandle, (self._name, self._method,
                                   self._timeout_s, self._failover))


class _MethodCaller:
    def __init__(self, handle: DeploymentHandle, method: str):
        self._handle = handle
        self._method = method

    def remote(self, *args, **kwargs):
        return self._handle._call(self._method, args, kwargs)


class _TrackedRef:
    """Wraps the reply ref to release the in-flight slot on result() and
    retry once through a healed replica set on replica death (never past
    the request deadline)."""

    def __init__(self, ref, handle: DeploymentHandle, key: bytes,
                 method: str, args, kwargs, retried: bool = False,
                 deadline: Optional[float] = None,
                 t0: Optional[float] = None, tok=None):
        self._ref = ref
        self._handle = handle
        self._idx = key
        self._request = (method, args, kwargs)
        self._retried = retried
        self._deadline = deadline
        self._t0 = t0 if t0 is not None else time.time()
        self._tok = tok          # open serve/request span (traced only)

    def result(self, timeout: Optional[float] = None):
        from ray_tpu.exceptions import ActorDiedError, RayTpuTimeoutError
        try:
            value = ray_tpu.get(self._ref, timeout=timeout)
        except ActorDiedError:
            self._handle._done(self._idx)
            if self._retried or (self._deadline is not None
                                 and time.monotonic() > self._deadline):
                spans.end(self._tok, ok=False)
                self._tok = None
                raise
            _serve_metrics()["retries"].inc()
            events.record("serve", "retry",
                          deployment=self._handle._name,
                          method=self._request[0])
            spans.end(self._tok, retried=True)
            self._tok = None
            self._handle._on_replica_error()
            method, args, kwargs = self._request
            retry = self._handle._call(method, args, kwargs)
            retry._retried = True
            retry._t0 = self._t0
            return retry.result(timeout)
        except RayTpuTimeoutError:
            # Still executing on the replica: keep the slot charged until
            # it actually finishes (admission-cap correctness).  The span
            # stays open; a later result() (or the crash horizon) ends it.
            handle, key = self._handle, self._idx
            self._ref.future().add_done_callback(
                lambda _: handle._done(key))
            raise
        except BaseException:
            self._handle._done(self._idx)
            spans.end(self._tok, ok=False)
            self._tok = None
            raise
        self._handle._done(self._idx)
        _serve_metrics()["e2e"].observe(time.time() - self._t0)
        spans.end(self._tok)
        self._tok = None
        return value

    @property
    def ref(self):
        return self._ref
