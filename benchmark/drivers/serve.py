"""Serve cells: `serve.run(<LLM deployment>)`, then a client on
`handle.options("generate").stream(...)`, open or closed loop.

The parent deploys one replica, which leases the chip; the router, the
replica actor, the stream tickets and the engine are all between the client
below and the device.  Each request runs on a thread of its own (a thread
waits on its stream and does nothing else) and is timed from the instant it
was due.  Open loop (no `clients` in the traffic file): a request is sent
when the schedule says, whether or not earlier ones have finished.  Closed
loop (`clients`): that many are in flight, the list is taken in order, and
a request is due the instant a client's last one ended.

Traffic file keys: `generator`, `rate_rps` or `clients`, `requests` (the
generator's parameters), `engine` (lanes, pool, chunk),
`max_concurrent_queries`, `warmup` (the request that compiles the cell's
step shapes), `check`, `max_lateness_p99_ms`, `trace` (`at_s`, `slice_s`,
`trace_every`), `rehearsal` (overrides for a CPU rehearsal).
"""

from __future__ import annotations

import contextlib
import json
import os
import queue
import threading
import time

import numpy as np

from benchmark import manifest, metrics, trace_reduce


def _one(handle, req, rec, stop, traced, slots):
    """One request on its own thread: pull the stream, stamp each token;
    at its end, however it ends, its client is free from that instant."""
    from ray_tpu.util import tracing
    rec["sent"] = time.time()
    gen = None
    try:
        scope = (tracing.trace("bench/request") if traced
                 else contextlib.nullcontext())
        with scope as trace_id:
            rec["trace_id"] = trace_id
            gen = handle.stream(req["prompt"],
                                max_new_tokens=req["max_new_tokens"])
            for tok in gen:
                if stop.is_set():
                    # The window has closed.  A token that arrives now is
                    # not recorded: lanes freed by the cut let queued
                    # requests in one after the other, and their first
                    # tokens belong to no window.
                    rec["cut"] = True
                    break
                rec["token_times"].append(time.time())
                rec["tokens"].append(int(tok))
    except Exception as e:     # boundary: the request failed, the run goes on
        if stop.is_set():
            rec["cut"] = True
        else:
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        if gen is not None:
            try:
                gen.close()      # releases the replica's stream slot
            except Exception as e:
                rec.setdefault("close_error", repr(e)[:200])
        rec["done"] = time.time()
        slots.put(rec["done"])


def _drive(handle, schedule, base, stop, end_at, trace_every, clients=0):
    """Send every request of the schedule, in its order, when it is due;
    returns the records and the threads.  Stops once `stop` is set or
    `end_at` has come.  A request is due at base + its `due`; with `clients`
    (a closed loop) no earlier than the instant one of that many clients
    came free: `slots` is their counting semaphore, and what it counts are
    the instants at which each ended its last request."""
    records, threads, slots = [], [], queue.Queue()
    for _ in range(clients):
        slots.put(float("-inf"))       # free since before the schedule began
    for req in schedule:
        due = base + req["due"]
        while clients and not stop.is_set() and time.time() < end_at:
            try:
                due = max(due, slots.get(timeout=0.05))
                break
            except queue.Empty:
                pass              # every client is waiting for its reply
        while True:
            wait = due - time.time()
            if wait <= 0 or stop.is_set():
                break
            time.sleep(min(wait, 0.05))
        if stop.is_set() or time.time() >= end_at:
            break
        rec = {"id": req["id"], "due": due, "prompt_len": len(req["prompt"]),
               "max_new_tokens": req["max_new_tokens"], "token_times": [],
               "tokens": [], "session": req["session"], "group": req["group"],
               "prompt": req["prompt"]}
        traced = bool(trace_every) and due >= base \
            and req["id"] % trace_every == 0
        th = threading.Thread(target=_one, daemon=True,
                              args=(handle, req, rec, stop, traced, slots))
        th.start()
        records.append(rec)
        threads.append(th)
    return records, threads


def run(ctx: dict, say) -> dict:
    import ray_tpu
    from ray_tpu import serve, state

    from benchmark import replica

    cell, traffic, seconds = ctx["cell"], ctx["traffic"], ctx["seconds"]
    engine = traffic["engine"]
    cfg = manifest.model_config(ctx["config"], None, ctx["rehearse"])
    schedule = manifest.module("generators", traffic["generator"]).make(
        traffic, ctx["seed"], seconds, cfg.vocab_size)
    lead_in = float(traffic["requests"]["lead_in_s"])
    tail = float(traffic["requests"]["tail_s"])

    ray_tpu.init(**({"num_tpus": cell["chips"]} if ctx["rehearse"] else {}))
    stop = threading.Event()
    try:
        t_run = time.time()
        handle = serve.run(replica.deployment(
            traffic["max_concurrent_queries"]).bind(
                model=ctx["config"]["module"].rsplit(".", 1)[-1], config=cfg,
                seed=ctx["seed"], **engine))
        worker_ready_s = time.time() - t_run
        device = handle.options("device_report").remote().result(timeout=120)
        if not ctx["rehearse"] and device["platform"] != "tpu":
            raise RuntimeError(f"the replica is on {device['platform']!r}")
        gen_handle = handle.options("generate")

        # Warm up the cell's own step shapes (greedy T=chunk and T=1), on
        # tokens no request of the schedule shares a block with.
        t0 = time.time()
        warm_rng = np.random.default_rng([ctx["seed"], 99])
        for n_prompt, n_new in traffic["warmup"]:
            list(gen_handle.stream(
                warm_rng.integers(0, cfg.vocab_size, n_prompt).tolist(),
                max_new_tokens=n_new))
        compile_s = time.time() - t0

        stats_fn = handle.options("stats")
        base = time.time() + lead_in + 0.2
        end_at = base + seconds + tail
        marks = {}

        def watcher():
            """Counters at the window's two ends; the profiler's slice."""
            tr = traffic["trace"]
            plan = [(base, "stats0"), (base + seconds, "stats1")]
            if ctx["trace"]:
                # the recorder's ring holds 4096 events: read it at half
                # time too, before the window's first half is overwritten
                plan += [(base + tr["at_s"], "trace_on"),
                         (base + tr["at_s"] + tr["slice_s"], "trace_off"),
                         (base + seconds / 2, "events")]
            for at, what in sorted(plan):
                while time.time() < at and not stop.is_set():
                    time.sleep(min(0.02, max(0.0, at - time.time())))
                if time.time() < at and what != "trace_off":
                    continue          # the run ended before this mark
                if what.startswith("stats"):
                    try:
                        marks[what] = stats_fn.remote().result(timeout=20)
                    except Exception as e:     # a full replica answers late
                        marks[what + "_error"] = repr(e)[:200]
                elif what == "events":
                    marks["events"] = state.events(plane="engine",
                                                   since=base - 1.0)
                elif what == "trace_on":
                    handle.options("start_trace").remote(os.path.join(
                        ctx["out_dir"], "trace")).result(timeout=60)
                    marks["trace_on"] = time.time()
                elif "trace_on" in marks:
                    handle.options("stop_trace").remote().result(timeout=300)
                    marks["trace_off"] = time.time()

        watch = threading.Thread(target=watcher, daemon=True)
        watch.start()
        records, threads = _drive(gen_handle, schedule, base, stop, end_at,
                                  traffic["trace"]["trace_every"]
                                  if ctx["trace"] else 0,
                                  int(traffic.get("clients", 0)))
        if "clients" in traffic and len(records) == len(schedule):
            raise SystemExit(
                f"the closed loop's list of {len(schedule)} requests ran out "
                f"before the window closed: the lanes were not kept full")
        # Requests due inside the window run to their end (tail_s allows
        # for it); whatever is still open at end_at is cut.
        counted = metrics.in_window(records, base, seconds)
        while time.time() < end_at and any("done" not in r for r in counted):
            time.sleep(0.05)
        if time.time() < base + seconds:      # never close the window early
            time.sleep(base + seconds - time.time())
        stop.set()
        for th in threads:
            th.join(timeout=60)
        watch.join(timeout=330)
        hung = sum(th.is_alive() for th in threads)

        engine_events = []
        if ctx["trace"]:
            early = marks.pop("events", [])
            late = state.events(plane="engine", since=base - 1.0)
            seen = {(e["ts"], e["kind"], e.get("span_id")) for e in late}
            engine_events = [e for e in early if (
                e["ts"], e["kind"], e.get("span_id")) not in seen] + late
        after = handle.options("device_report").remote().result(timeout=120)

        # -- correctness: the plain reference on a seeded sample -------------
        check = traffic["check"]
        whole = [r for r in records
                 if not r.get("error") and not r.get("cut")
                 and len(r["tokens"]) == r["max_new_tokens"]]
        pick = np.random.default_rng([ctx["seed"], 7]).permutation(
            len(whole))[:check["samples"]]
        samples = [(whole[i]["prompt"], whole[i]["tokens"]) for i in pick]
        t0 = time.time()
        verdicts = handle.options("reference_check").remote(
            ctx["config"]["reference_module"], samples).result(timeout=300)
        check_s = time.time() - t0
    finally:
        stop.set()
        serve.shutdown()
        ray_tpu.shutdown()

    # -- reduce ---------------------------------------------------------------
    lateness = metrics.lateness_ms(records)
    late_p99 = metrics.percentile(lateness, 99) if lateness else 0.0
    # Judged: every request sent, lead-in included, that the system took up
    # (a first token reached the client) or turned away.  One still queued
    # when the window closes has no outcome yet; over the knee that is most
    # of those due inside the window.
    judged = [r for r in records if r.get("error") or r["token_times"]]
    failed = [r for r in judged if metrics.request_failed(r)]
    cut = sum(bool(r.get("cut")) for r in judged)
    tokens = metrics.tokens_in_window(records, base, seconds)
    ttft = [metrics.ttft_ms(r) for r in counted]
    tpot = [x for x in map(metrics.tpot_ms, counted) if x is not None]
    gaps = [g for v in verdicts for g in v[0]]
    ranks = [k for v in verdicts for k in v[1]]
    argmax_pct = 100.0 * float(np.mean([k == 0 for k in ranks])) \
        if ranks else 0.0
    seen = {"mean_gap": float(np.mean(gaps)) if gaps else None,
            "max_gap": max(gaps) if gaps else None, "argmax_pct": argmax_pct}
    ok_tokens = bool(gaps) and seen["max_gap"] <= check["max_gap"] \
        and seen["mean_gap"] <= check["mean_gap"]
    say(f"reference check on {len(samples)} request(s), {len(gaps)} served "
        f"tokens, {check_s:.1f} s: reference logit of the served token under "
        f"the reference maximum by mean {np.mean(gaps) if gaps else -1:.4f} "
        f"(limit {check['mean_gap']}), max {max(gaps) if gaps else -1:.4f} "
        f"(limit {check['max_gap']}); served token is the reference's "
        f"argmax at {argmax_pct:.1f}% of positions: "
        f"{'ok' if ok_tokens else 'FAILED'}")
    s0, s1 = marks.get("stats0", {}), marks.get("stats1", {})
    late_counted = metrics.lateness_ms(counted)
    say(f"generator lateness p50 "
        f"{metrics.percentile(lateness, 50) if lateness else 0:.2f} ms, p99 "
        f"{late_p99:.2f} ms (limit {traffic['max_lateness_p99_ms']}), of "
        f"those due in the window p99 "
        f"{metrics.percentile(late_counted, 99) if late_counted else 0:.2f} "
        f"ms; "
        f"{len(records)} sent, {len(counted)} due in the window, "
        f"{len(judged)} taken up or turned away, {len(failed)} of them "
        f"failed, {cut} cut in mid-stream when the window closed, {hung} "
        f"thread(s) hung")
    delta = {k: s1[k] - s0[k] for k in ("prefix_hit_tokens",
                                        "prefix_miss_tokens",
                                        "blocks_evicted", "admitted", "steps")
             if k in s0 and k in s1}
    if "compile" in s0 and "compile" in s1:     # nothing compiles in a window
        delta["programs_compiled_or_loaded"] = sum(
            s1["compile"][k] - s0["compile"][k]
            for k in ("compiles", "cache_hits"))
    say(f"window: {tokens} output tokens in {seconds:g} s = "
        f"{tokens / seconds:.1f} tokens/s; lanes at open/close "
        f"{s0.get('active')}/{s1.get('active')} of {engine['max_lanes']}, "
        f"waiting {s0.get('waiting')}/{s1.get('waiting')}; inside the window "
        f"{delta or 'no counters: ' + str(marks.get('stats1_error'))}")
    if ttft:
        say(f"TTFT ms p50 {metrics.percentile(ttft, 50):.1f} p90 "
            f"{metrics.percentile(ttft, 90):.1f} p99 "
            f"{metrics.percentile(ttft, 99):.1f} over {len(ttft)}; TPOT ms "
            f"p50 {metrics.percentile(tpot, 50) if tpot else -1:.2f} p90 "
            f"{metrics.percentile(tpot, 90) if tpot else -1:.2f} over "
            f"{len(tpot)} (end-to-end metrics only in a cell under its knee)")
    for r in failed[:5]:
        say(f"failed request {r['id']}: {r.get('error')} "
            f"({len(r['tokens'])}/{r['max_new_tokens']} tokens)")
    if late_p99 > traffic["max_lateness_p99_ms"]:
        raise SystemExit(f"the generator ran late: p99 {late_p99:.1f} ms; "
                         f"a starved generator is not a fast server")
    if hung:
        raise SystemExit(f"{hung} client thread(s) never returned")
    with open(os.path.join(ctx["out_dir"], "requests.jsonl"), "w") as f:
        for r in records:
            f.write(json.dumps({k: v for k, v in r.items()
                                if k not in ("prompt", "tokens")}) + "\n")

    setup_s = base - ctx["t_process_start"]
    end_to_end = {"setup_s": setup_s, "serve_tokens_per_s": tokens / seconds}
    if ttft:
        end_to_end["ttft_p90_ms"] = metrics.percentile(ttft, 90)
    if tpot:
        end_to_end["tpot_p90_ms"] = metrics.percentile(tpot, 90)
    device = {k: after[k] for k in ("platform", "kind", "count",
                                    "memory_peak_bytes")}
    say(f"setup {setup_s:.1f} s (replica ready {worker_ready_s:.1f}, warm-up "
        f"{compile_s:.1f}, lead-in {lead_in:g}); HBM "
        f"{manifest.memory_line(after)}")
    run = {
        "correct": ok_tokens and not hung,
        "compared": {k: {"value": seen[k], "limit": check[k]}
                     for k in ("mean_gap", "max_gap")},
        "attempted": len(judged), "failed": len(failed),
        "end_to_end": end_to_end, "device": device, "memory": after,
        "fields": ctx["fields"], "traffic": traffic, "cell": cell,
        "records": records, "counted": counted, "base": base, "seconds": seconds,
        "stats0": s0, "stats1": s1, "compile_s": compile_s,
        "worker_ready_s": worker_ready_s,
        "engine_events": engine_events, "marks": marks,
        "notes": {"lateness_p99_ms": late_p99, "cut": cut,
                  "tokens": tokens, "stats0": s0, "stats1": s1,
                  "check": seen, "base": base,
                  "marks": {k: marks[k] for k in ("trace_on", "trace_off")
                            if k in marks}},
    }
    if ctx["trace"] and "trace_off" in marks:
        trace_reduce.attach(run, os.path.join(ctx["out_dir"], "trace"), ctx,
                            say)
    return run
