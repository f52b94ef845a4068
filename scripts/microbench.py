"""Core runtime microbenchmarks.

Reference parity: python/ray/_private/ray_perf.py:93-305 (`ray
microbenchmark`) — put/get ops/s, task submit+get sync and pipelined,
1:1 actor calls sync and pipelined, async-actor calls.

Writes MICROBENCH.json at the repo root:
    {"<bench>": {"ops_s": N, "n": N}, ...}
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import ray_tpu  # noqa: E402

ROUNDS = 5


def timeit(name, fn, n, results, settle: float = 0.0):
    # Warmup round, then let background churn (frees, spills, worker
    # spawns) drain so sections don't pollute each other.  The committed
    # number is the MEDIAN of five timed rounds with the observed range
    # alongside — this host's run-to-run variance is ±25%, and a best-of
    # methodology on a bimodal distribution reports the lucky phase.
    fn(max(1, n // 10))
    if settle:
        time.sleep(settle)
    rates = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        fn(n)
        dt = time.perf_counter() - t0
        rates.append(n / dt)
    med = statistics.median(rates)
    results[name] = {"ops_s": round(med, 1), "n": n, "rounds": ROUNDS,
                     "min_ops_s": round(min(rates), 1),
                     "max_ops_s": round(max(rates), 1)}
    print(f"{name:32s} {med:10,.1f} ops/s   (median of {ROUNDS}x{n}, "
          f"range {min(rates):,.0f}-{max(rates):,.0f})")


def bench_checkpoint(results: dict):
    """Sharded-checkpoint microbenches: full sync save, the stage
    (device-to-host) half that is all an ASYNC save blocks the step loop
    for, and committed-directory restore.  16 MiB payload so the numbers
    track the checkpoint machinery, not disk bandwidth alone."""
    import shutil
    import tempfile

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.checkpoint import restore_sharded, save_sharded, sharded

    mesh = Mesh(np.array(jax.devices()), ("data",))
    rows = (16 << 20) // (256 * 4)
    rows -= rows % len(jax.devices())
    state = {"w": jax.device_put(np.zeros((rows, 256), np.float32),
                                 NamedSharding(mesh, P("data")))}
    root = tempfile.mkdtemp(prefix="microbench_ckpt_")
    try:
        path = os.path.join(root, "ck")

        def ckpt_save_sync(n):
            for _ in range(n):
                save_sharded(path, state)

        timeit("ckpt_save_sync_16MiB", ckpt_save_sync, 5, results)

        def ckpt_stage(n):
            for _ in range(n):
                sharded.stage(state)

        timeit("ckpt_stage_16MiB", ckpt_stage, 20, results)

        def ckpt_restore(n):
            for _ in range(n):
                jax.block_until_ready(
                    restore_sharded(path, mesh=mesh)["w"])

        timeit("ckpt_restore_16MiB", ckpt_restore, 10, results)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_serve(results: dict):
    """Serve fault-tolerance microbenches: a full mid-stream replica
    kill + failover-resume cycle, and a graceful drain-on-downscale
    cycle.  Both are wall-clock-per-recovery numbers (ops/s of whole
    heal cycles), so regressions in reconcile latency, drain polling,
    or the failover resubmit path all move them."""
    from ray_tpu import serve
    from ray_tpu.serve._private import CONTROLLER_NAME, SERVE_NAMESPACE

    serve.start()
    try:
        @serve.deployment(name="mb_failover", num_replicas=1)
        def chunks(n):
            for i in range(n):
                yield i

        handle = serve.run(chunks.bind()).options(failover="replay")
        assert list(handle.stream(4)) == list(range(4))  # warm replica
        controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)

        def failover_resume(n):
            # One op = stream 8 chunks, kill the replica after 2, let
            # the handle heal (controller respawns) + resume via replay.
            for _ in range(n):
                got = []
                for c in handle.stream(8):
                    got.append(c)
                    if len(got) == 2:
                        routing = ray_tpu.get(
                            controller.get_routing.remote("mb_failover"),
                            timeout=30)
                        ray_tpu.kill(routing["replicas"][0])
                assert got == list(range(8))

        timeit("serve_failover_resume", failover_resume, 3, results)
        serve.delete("mb_failover")

        @serve.deployment(name="mb_drain", num_replicas=1)
        def nopd():
            return 0

        def _wait(pred, timeout=30.0):
            deadline = time.perf_counter() + timeout
            while time.perf_counter() < deadline:
                if pred():
                    return
                time.sleep(0.05)
            raise TimeoutError("serve_drain wait timed out")

        serve.run(nopd.bind())

        def drain_cycle(n):
            # One op = scale 1->2 (wait both RUNNING), downscale 2->1,
            # wait until the victim fully drains out of the table.
            for _ in range(n):
                serve.run(nopd.options(num_replicas=2).bind())
                _wait(lambda: serve.status()["mb_drain"]["states"]
                      .get("RUNNING", 0) == 2)
                before = ray_tpu.get(
                    controller.drain_stats.remote(), timeout=30)
                serve.run(nopd.options(num_replicas=1).bind())
                _wait(lambda: ray_tpu.get(
                    controller.drain_stats.remote(), timeout=30)
                    ["drained_total"] > before["drained_total"])

        timeit("serve_drain", drain_cycle, 3, results)
        serve.delete("mb_drain")
    finally:
        serve.shutdown()


def bench_ingest(results: dict):
    """Input-pipeline microbenches: incremental batch assembly over
    misaligned Arrow blocks (the row-cursor path — batches/s), the
    overlapped device feed end to end (producer thread + double-buffered
    H2D — device batches/s), and the work-stealing coordinator's lease
    round-trip (leases/s: the per-block scheduling overhead a stealing
    split adds over a static split)."""
    import numpy as np

    from ray_tpu import data as rd
    from ray_tpu.data import block as blk
    from ray_tpu.data import ingest

    # Assembly: 64 blocks x 100 rows of a 256-wide float column, batch
    # size 96 deliberately misaligned so every batch crosses a boundary.
    blocks = [blk.batch_to_block(
        {"id": np.arange(i * 100, (i + 1) * 100),
         "x": np.ones((100, 256), np.float32)})
        for i in range(64)]

    def assemble(n):
        done = 0
        while done < n:
            for b in ingest.batches_from_block_iter(iter(blocks), 96):
                done += 1
                if done >= n:
                    break

    timeit("ingest_assemble", assemble, 400, results)

    # Device feed: partial drain (break at n) of the overlapped iterator
    # over a materialized dataset — covers block fetch, producer-thread
    # assembly, handoff queue, and the double-buffered device_put.
    ds = rd.range(4096, parallelism=8).materialize()
    it = ds.streaming_split(1)[0]

    def device_feed(n):
        done = 0
        while done < n:
            feed = it.iter_device_batches(batch_size=64)
            for _ in feed:
                done += 1
                if done >= n:
                    feed.close()
                    break

    timeit("ingest_device_feed", device_feed, 128, results)

    # Lease round-trip: one op = next() ack'ing the previous lease —
    # the steady-state coordinator hop per block.
    coord = ingest.SplitCoordinator.remote([list(range(100_000))])
    ray_tpu.get(coord.register.remote(0, []))

    def steal_lease(n):
        lease = None
        for _ in range(n):
            lease, _ = ray_tpu.get(coord.next.remote(0, lease))

    timeit("split_steal", steal_lease, 500, results)


def bench_train_ft(results: dict):
    """Train fault-tolerance microbenches: the preemption-notice step
    boundary (rescue save + commit + abort — the latency that must fit
    inside the grace window), and a gang down-shift cycle (full-size
    group torn down, smaller group re-formed: PG release, re-placement,
    actor spawn, worker boot) — the elastic resize-down path minus
    checkpoint replay."""
    import shutil
    import tempfile

    import numpy as np

    from ray_tpu.checkpoint import CheckpointManager
    from ray_tpu.exceptions import TrainPreemptedError
    from ray_tpu.train.session import TrainContext, _TrainSession
    from ray_tpu.train.worker_group import WorkerGroup

    root = tempfile.mkdtemp(prefix="microbench_train_ft_")
    state = {"w": np.zeros((256, 256), np.float32), "step": 0}
    ctx = TrainContext(world_rank=0, world_size=1, local_rank=0,
                       local_world_size=1, node_rank=0)
    ops = iter(range(10_000))
    try:
        def preempt_save(n):
            # One op = a notice-to-abort boundary on a live session: the
            # notice arms mid-step, the next report() runs the rescue
            # hook (durable 256 KiB save, wait for COMMIT) and aborts
            # with TrainPreemptedError.
            for _ in range(n):
                i = next(ops)
                mgr = CheckpointManager(root, save_id=f"mb{i}")
                box = {}

                def fn():
                    while True:
                        box["s"].report({"ok": 1})

                def rescue(remaining_s, mgr=mgr, i=i):
                    h = mgr.save(i, state)
                    if not h._event.wait(30):
                        raise TimeoutError("rescue save did not commit")

                sess = _TrainSession(fn, ctx)
                box["s"] = sess
                sess._preempt_hook = rescue
                sess.start()
                sess.get_next(timeout=10)          # first step delivered
                sess.notify_preemption(grace_s=5.0)
                try:
                    while sess.get_next(timeout=10) is not None:
                        pass
                    raise AssertionError("session ended without abort")
                except TrainPreemptedError:
                    pass
                mgr.wait_until_finished()

        timeit("train_preempt_save", preempt_save, 10, results)
    finally:
        shutil.rmtree(root, ignore_errors=True)

    def resize_down(n):
        # One op = a down-shift cycle: form the full-size gang, tear it
        # down (lost node), re-form one worker smaller.
        for _ in range(n):
            wg2 = WorkerGroup(2, {"CPU": 1}, "PACK", pg_timeout_s=30.0)
            wg2.shutdown()
            wg1 = WorkerGroup(1, {"CPU": 1}, "PACK", pg_timeout_s=30.0)
            wg1.shutdown()

    timeit("train_resize_down", resize_down, 2, results, settle=1.0)


def bench_control_plane(results: dict):
    """Batched control-plane microbenches (PR 14).

    `batched_dispatch_burst`: drain rate of a one-shot 8k-task burst
    over held leases — the driver coalesces same-key specs into
    per-worker dispatch vectors, so this number moves with
    `sched_batch_max` and the vectorized result_seal path.

    `zygote_spawn_batch`: actors/s for an 8-actor storm where every
    actor needs a dedicated worker — each op pays lease, batched zygote
    fork (`zygote_spawn_parallelism` children per wakeup), boot, and
    first ping, then kills the actors so the next round forks fresh."""

    @ray_tpu.remote
    def nopc():
        return None

    def batched_dispatch(n):
        ray_tpu.get([nopc.remote() for _ in range(n)])

    batched_dispatch(2000)   # warm the lease pool past ramp-up
    timeit("batched_dispatch_burst", batched_dispatch, 8000, results,
           settle=1.0)

    @ray_tpu.remote
    class Spawn:
        def ping(self):
            return None

    def zygote_spawn(n):
        actors = [Spawn.remote() for _ in range(n)]
        ray_tpu.get([a.ping.remote() for a in actors], timeout=120)
        for a in actors:
            ray_tpu.kill(a)

    timeit("zygote_spawn_batch", zygote_spawn, 8, results, settle=2.0)


def bench_observability(results: dict):
    """Observability hot-path costs: `events_append` is the per-record()
    overhead every instrumented plane pays (budget: < 5 µs/event, i.e.
    > 200k ops/s — the flight recorder must be cheap enough to leave on),
    `metrics_observe` is one bucketed-histogram observation (the SLO
    latency path: TTFT/TBT, queue wait, step time)."""
    from ray_tpu.util import events
    from ray_tpu.util import metrics as mt
    events.reset()

    def events_append(n):
        record = events.record
        for i in range(n):
            record("engine", "bench", i=i)

    timeit("events_append", events_append, 200_000, results)
    events.reset()

    h = mt.Histogram("microbench_observe_s", "observe() hot-path bench")

    def metrics_observe(n):
        obs = h.observe
        for i in range(n):
            obs(0.001 * (i & 1023))

    timeit("metrics_observe", metrics_observe, 200_000, results)

    # One durational span = one begin + one end = two ring slots.  The
    # budget is the same as two record() calls — a span edge must not
    # cost more than the instant events it replaces.
    from ray_tpu.util import spans

    def span_begin_end(n):
        begin, end = spans.begin, spans.end
        for i in range(n):
            end(begin("engine", "bench_span", i=i))

    timeit("span_begin_end", span_begin_end, 100_000, results)
    events.reset()

    # Reconstruction throughput: each op pairs/links a 1k-span chain
    # through the same build_spans path state.spans() uses, so the
    # reported rate is trees/s over a ring-sized stream.
    from ray_tpu import state as _state
    _evs = []
    for i in range(1000):
        sid, parent = f"{i:06x}", (f"{i - 1:06x}" if i else None)
        _evs.append({"ts": float(i), "ts_adj": float(i),
                     "plane": "engine", "kind": "bench_span",
                     "trace_id": "t1", "span_id": sid, "pid": 1,
                     "seq": 2 * i, "node_id": "n1", "source": "live",
                     "payload": {"ph": "B", "parent": parent}})
        _evs.append({"ts": i + 0.5, "ts_adj": i + 0.5, "plane": "engine",
                     "kind": "bench_span", "trace_id": "t1",
                     "span_id": sid, "pid": 1, "seq": 2 * i + 1,
                     "node_id": "n1", "source": "live",
                     "payload": {"ph": "E", "dur": 0.5}})

    def span_reconstruct(n):
        for _ in range(n):
            _state.build_spans(_evs, "t1")

    timeit("span_reconstruct_1k", span_reconstruct, 30, results)


def main():
    ray_tpu.init(num_cpus=8, object_store_memory=256 << 20)
    results: dict = {}

    # --- observability: flight recorder + histogram hot paths --------------
    bench_observability(results)

    # --- object store ------------------------------------------------------
    payload = b"x" * 100

    def put_small(n):
        for _ in range(n):
            ray_tpu.put(payload)

    timeit("put_small_100B", put_small, 2000, results)

    ref = ray_tpu.put(payload)

    def get_small(n):
        for _ in range(n):
            ray_tpu.get(ref)

    timeit("get_small_100B", get_small, 2000, results)

    import numpy as np
    big = np.zeros(1 << 20, np.uint8)  # 1 MiB

    def put_1mb(n):
        for _ in range(n):
            ray_tpu.put(big)

    timeit("put_1MiB", put_1mb, 500, results)
    time.sleep(3.0)  # drain the dropped-ref free/spill storm

    # --- tasks -------------------------------------------------------------
    @ray_tpu.remote
    def nop():
        return None

    def task_sync(n):
        for _ in range(n):
            ray_tpu.get(nop.remote())

    timeit("task_sync_roundtrip", task_sync, 300, results, settle=1.0)

    def task_pipelined(n):
        ray_tpu.get([nop.remote() for _ in range(n)])

    # Extra warmup: the first rounds also pay worker-pool ramp-up.
    task_pipelined(2000)
    timeit("task_pipelined", task_pipelined, 4000, results, settle=1.0)

    # --- actors ------------------------------------------------------------
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.x = 0

        def inc(self):
            self.x += 1
            return self.x

    actor = Counter.remote()
    ray_tpu.get(actor.inc.remote())

    def actor_sync(n):
        for _ in range(n):
            ray_tpu.get(actor.inc.remote())

    timeit("actor_sync_roundtrip", actor_sync, 500, results)

    def actor_pipelined(n):
        ray_tpu.get([actor.inc.remote() for _ in range(n)])

    actor_pipelined(2000)
    timeit("actor_pipelined", actor_pipelined, 6000, results)

    @ray_tpu.remote
    class AsyncActor:
        async def ping(self):
            return 1

    aactor = AsyncActor.remote()
    ray_tpu.get(aactor.ping.remote())

    def async_actor_pipelined(n):
        ray_tpu.get([aactor.ping.remote() for _ in range(n)])

    timeit("async_actor_pipelined", async_actor_pipelined, 2000, results)

    # --- scaling: many concurrent tasks -----------------------------------
    # Fractional-CPU sleepers (reference ray_perf runs trivial tasks far
    # beyond core count): 0.25 CPU x 8-CPU node = 32 concurrent workers,
    # so 10ms tasks can overlap well past the core count and the measured
    # rate proves real overlap (serial would be 100/s).
    @ray_tpu.remote(num_cpus=0.25)
    def sleep10ms():
        time.sleep(0.01)
        return None

    def many_sleepers(n):
        ray_tpu.get([sleep10ms.remote() for _ in range(n)])

    # Steady-state measurement: the 32-worker pool ramps over a few
    # rounds (fork-server spawns + lease grants); a FIXED warmup keeps
    # ramp-up out of the number (reference ray_perf also measures the
    # warmed pool).  Median of five timed rounds with the range — rounds
    # on a 1-core host are bimodal, and a best-of methodology would
    # report the lucky phase (judged r4).  No settle sleep: the 1s lease
    # idle TTL would hand the warmed leases back mid-gap.
    for _ in range(3):
        many_sleepers(500)
    rates = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        many_sleepers(500)
        rates.append(500 / (time.perf_counter() - t0))
    med = statistics.median(rates)
    results["tasks_10ms_x500_concurrent"] = {
        "ops_s": round(med, 1), "n": 500, "rounds": ROUNDS,
        "min_ops_s": round(min(rates), 1),
        "max_ops_s": round(max(rates), 1)}
    print(f"{'tasks_10ms_x500_concurrent':32s} {med:10,.1f} ops/s   "
          f"(median of {ROUNDS}x500, range "
          f"{min(rates):,.0f}-{max(rates):,.0f})")

    # --- control plane: batched dispatch + zygote spawn --------------------
    bench_control_plane(results)

    # --- inference: continuous-batching decode step ------------------------
    # Steady-state decode-step rate of the paged-KV engine (nano model so
    # the number tracks scheduler + cache-update overhead, not matmul
    # time).  One step advances EVERY live lane, so aggregate tokens/s =
    # ops_s * lanes — the lane sweep shows how close a batched step stays
    # to a single-lane step (the continuous-batching win).
    from ray_tpu.inference import InferenceEngine

    for lanes in (1, 8, 32):
        eng = InferenceEngine("gpt", "nano", max_lanes=lanes, block_size=16,
                              prefill_chunk=8, auto_start=False)

        def decode_steps(n, eng=eng, lanes=lanes):
            # n+1 tokens = prefill-step sample + exactly n decode steps,
            # so every lane finishes inside the timed region (no drain
            # tail polluting the rate).
            for _ in range(lanes):
                eng.submit(list(range(8)), max_new_tokens=n + 1)
            eng.step()                    # prefill + first sampled token
            for _ in range(n):
                eng.step()

        timeit(f"decode_step_lanes{lanes}", decode_steps, 64, results)
        eng.shutdown()

    # --- inference: prefix-cache admission (prefill hit vs miss) -----------
    # Full request latency for a 112-token prompt whose first 96 tokens
    # are sealed in the content-addressed block index (admission adopts
    # them by reference; one chunk prefills) vs a never-seen prompt
    # (every chunk prefills).  The hit/miss ratio is the FLOP savings
    # prefix sharing buys on shared-system-prompt traffic — see
    # the serve cells of BENCHMARK.json for the TTFT view at serving scale.
    import itertools
    uid = itertools.count(1)

    def _prefix_engine():
        return InferenceEngine("gpt", "nano", max_lanes=2, block_size=16,
                               num_blocks=64, prefill_chunk=32,
                               auto_start=False)

    eng = _prefix_engine()
    vocab = eng.config.vocab_size
    shared = [(3 * j + 1) % vocab for j in range(96)]
    eng.generate(shared + [5] * 16, max_new_tokens=1)  # seal the prefix

    def prefill_hit(n, eng=eng):
        for _ in range(n):
            tail = [(13 * next(uid) + j) % vocab for j in range(16)]
            eng.generate(shared + tail, max_new_tokens=1)

    timeit("prefill_hit", prefill_hit, 32, results)
    eng.shutdown()

    eng = _prefix_engine()

    def prefill_miss(n, eng=eng):
        for _ in range(n):
            p = [(13 * next(uid) + j) % vocab for j in range(112)]
            eng.generate(p, max_new_tokens=1)

    timeit("prefill_miss", prefill_miss, 32, results)
    eng.shutdown()

    # --- inference: speculative drafting + verify step ---------------------
    # spec_draft: host-side n-gram prompt-lookup over a 256-token
    # repetitive context — this runs per decode lane per step, so it
    # must stay orders of magnitude cheaper than a jitted step.
    # spec_verify: steady-state verify-dispatch rate (T=spec_k+1) of an
    # 8-lane speculative engine on cyclic text; aggregate tokens/s =
    # ops_s * lanes * accepted-per-step, so the number to compare with
    # decode_step_lanes8 is ops_s scaled by the acceptance multiplier.
    from ray_tpu.inference import NgramProposer

    proposer = NgramProposer()
    spec_ctx = [(j % 8) + 1 for j in range(256)]

    def spec_draft(n):
        for _ in range(n):
            proposer.propose(spec_ctx, 4)

    timeit("spec_draft", spec_draft, 20_000, results)

    eng = InferenceEngine("gpt", "nano", max_lanes=8, block_size=16,
                          prefill_chunk=8, auto_start=False, spec_k=4)

    def spec_verify(n, eng=eng):
        hs = [eng.submit([(j % 4) + 1 for j in range(8)],
                         max_new_tokens=5 * n + 8) for _ in range(8)]
        eng.step()                    # prefill + first sampled token
        for _ in range(n):
            eng.step()                # one verify dispatch per call
        for h in hs:
            h.cancel()

    timeit("spec_verify", spec_verify, 64, results)
    eng.shutdown()

    # --- data: ingest assembly / device feed / steal leases ----------------
    bench_ingest(results)

    # --- checkpoint: sharded save / stage / restore ------------------------
    bench_checkpoint(results)

    # --- serve: failover-resume + drain cycles -----------------------------
    bench_serve(results)

    # --- train: preempt-boundary rescue save + gang down-shift -------------
    bench_train_ft(results)

    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "MICROBENCH.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out}")
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
