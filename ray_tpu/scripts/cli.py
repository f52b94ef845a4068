"""`ray_tpu` command-line interface.

Reference parity: python/ray/scripts/scripts.py (start:529, stop:1013,
status:1955, memory:1905) and the state CLI (`ray list`, `ray summary`,
experimental/state/state_cli.py).

Usage:
    python -m ray_tpu.scripts.cli start --head [--num-cpus N]
    python -m ray_tpu.scripts.cli start --address GCS_ADDR
    python -m ray_tpu.scripts.cli status  --address GCS_ADDR
    python -m ray_tpu.scripts.cli list {nodes,actors,workers,placement-groups,objects} --address GCS_ADDR
    python -m ray_tpu.scripts.cli memory --address GCS_ADDR
    python -m ray_tpu.scripts.cli stop   --address GCS_ADDR
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys


def _fmt_table(rows, columns) -> str:
    if not rows:
        return "(none)"
    widths = [max(len(str(c)), max(len(str(r.get(c, ""))) for r in rows))
              for c in columns]
    lines = ["  ".join(str(c).ljust(w) for c, w in zip(columns, widths))]
    lines.append("  ".join("-" * w for w in widths))
    for r in rows:
        lines.append("  ".join(
            str(r.get(c, "")).ljust(w) for c, w in zip(columns, widths)))
    return "\n".join(lines)


def cmd_start(args) -> int:
    from ray_tpu._private import node as node_mod
    if args.head:
        session_dir = node_mod.new_session_dir()
        group = node_mod.ProcessGroup()
        gcs_address = node_mod.start_gcs(session_dir, group,
                                         port=args.gcs_port)
        node_mod.start_hostd(
            gcs_address, session_dir, group, num_cpus=args.num_cpus,
            num_tpus=args.num_tpus, head=True,
            store_capacity=args.object_store_memory)
        print(f"GCS address: {gcs_address}")
        print(f"Session dir: {session_dir}")
        print(f"Connect with ray_tpu.init(address={gcs_address!r}) or "
              f"join nodes with: python -m ray_tpu.scripts.cli start "
              f"--address {gcs_address}")
        if args.block:
            try:
                group.wait()
            except KeyboardInterrupt:
                group.reap()
        return 0
    if not args.address:
        print("either --head or --address is required", file=sys.stderr)
        return 2
    session_dir = node_mod.new_session_dir()
    group = node_mod.ProcessGroup()
    info = node_mod.start_hostd(
        args.address, session_dir, group, num_cpus=args.num_cpus,
        num_tpus=args.num_tpus, head=False,
        store_capacity=args.object_store_memory)
    print(f"Node started, daemon at {info['address']} "
          f"(node {info['node_id'][:12]})")
    if args.block:
        try:
            group.wait()
        except KeyboardInterrupt:
            group.reap()
    return 0


def cmd_stop(args) -> int:
    from ray_tpu._private.rpc import RpcClient

    async def stop():
        client = RpcClient(args.address)
        try:
            await client.call("Gcs", "shutdown_cluster", {}, timeout=10)
        finally:
            await client.close()

    asyncio.run(stop())
    print("cluster shutdown requested")
    return 0


def cmd_status(args) -> int:
    from ray_tpu import state
    s = state.summarize_cluster(args.address)
    if args.json:
        print(json.dumps(s, indent=2))
        return 0
    print(f"Nodes: {s['nodes_alive']} alive, {s['nodes_dead']} dead")
    print("Resources:")
    for k, total in sorted(s["resources_total"].items()):
        avail = s["resources_available"].get(k, 0.0)
        print(f"  {k}: {total - avail:g}/{total:g} used")
    print(f"Actors: " + (", ".join(
        f"{n} {st}" for st, n in sorted(s["actors"].items())) or "none"))
    print(f"Placement groups: {s['placement_groups']}")
    return 0


def cmd_list(args) -> int:
    from ray_tpu import state
    kind = args.kind.replace("-", "_")
    fn = {
        "nodes": (state.list_nodes,
                  ["node_id", "address", "alive", "is_head",
                   "resources_total"]),
        "actors": (state.list_actors,
                   ["actor_id", "class_name", "state", "name", "node_id",
                    "num_restarts"]),
        "workers": (state.list_workers,
                    ["node_id", "pid", "state", "job_id", "actor_id",
                     "idle_s"]),
        "placement_groups": (state.list_placement_groups,
                             ["placement_group_id", "state", "strategy",
                              "bundles"]),
        "objects": (state.list_objects, None),
        "tasks": (state.list_tasks,
                  ["name", "node_id", "pid", "start", "end"]),
    }.get(kind)
    if fn is None:
        print(f"unknown kind {args.kind!r}", file=sys.stderr)
        return 2
    rows = fn[0](args.address)
    if args.json or fn[1] is None:
        print(json.dumps(rows, indent=2, default=str))
        return 0
    for r in rows:  # truncate ids for table form
        for key in ("node_id", "actor_id", "placement_group_id"):
            if isinstance(r.get(key), str) and len(r[key]) > 12:
                r[key] = r[key][:12]
    print(_fmt_table(rows, fn[1]))
    return 0


def cmd_client_server(args) -> int:
    """Run a thin-client server attached to the cluster (reference:
    `ray start --ray-client-server-port`)."""
    from ray_tpu.util.client.server import serve_forever
    serve_forever(args.address, args.host, args.port)
    return 0


def cmd_dashboard(args) -> int:
    """Run the dashboard head (REST + web UI).  Reference: dashboard.py."""
    from ray_tpu.dashboard.head import main as dash_main
    return dash_main(["--address", args.address, "--host", args.host,
                      "--port", str(args.port)])


def cmd_job(args) -> int:
    """Job submission CLI over the dashboard REST API (reference:
    dashboard/modules/job/cli.py — `ray job submit/list/status/logs/stop`)."""
    from ray_tpu.dashboard.sdk import JobSubmissionClient
    client = JobSubmissionClient(args.dashboard_address)
    if args.job_cmd == "submit":
        runtime_env = {}
        if args.working_dir:
            runtime_env["working_dir"] = args.working_dir
        import shlex
        sub_id = client.submit_job(
            entrypoint=shlex.join(args.entrypoint),
            runtime_env=runtime_env or None,
            submission_id=args.submission_id)
        print(f"submitted: {sub_id}")
        if not args.no_wait:
            rec = client.wait_until_finished(sub_id, timeout=args.timeout)
            print(f"status: {rec['status']}"
                  + (f" ({rec['message']})" if rec.get("message") else ""))
            print(client.get_job_logs(sub_id), end="")
            return 0 if rec["status"] == "SUCCEEDED" else 1
        return 0
    if args.job_cmd == "list":
        rows = [{"submission_id": r["submission_id"], "status": r["status"],
                 "entrypoint": r["entrypoint"][:60]}
                for r in client.list_jobs()]
        print(_fmt_table(rows, ["submission_id", "status", "entrypoint"]))
        return 0
    if args.job_cmd == "status":
        print(json.dumps(client.get_job_status(args.submission_id),
                         indent=2, default=str))
        return 0
    if args.job_cmd == "logs":
        print(client.get_job_logs(args.submission_id), end="")
        return 0
    if args.job_cmd == "stop":
        print("stopped" if client.stop_job(args.submission_id)
              else "not running")
        return 0
    return 2


def cmd_serve(args) -> int:
    """Serve control subcommands (reference: serve CLI scripts.py —
    deploy from a config file, status, shutdown)."""
    import json as jsonlib

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(address=args.address)
    try:
        if args.serve_cmd == "status":
            try:
                print(jsonlib.dumps(serve.status(), indent=2))
            except ValueError:
                print("serve is not running on this cluster")
            return 0
        if args.serve_cmd == "shutdown":
            serve.shutdown()
            print("serve shutdown complete")
            return 0
        if args.serve_cmd == "deploy":
            if not args.config:
                print("serve deploy requires a config file", file=sys.stderr)
                return 2
            # Config schema (reference: serve/schema.py, JSON or YAML):
            # {"applications": [{"import_path": "module:app",
            #                    "deployments": [{"name": ...,
            #                                     "num_replicas": ...}]}]}
            import importlib
            import os
            import sys as _sys
            _sys.path.insert(0, os.getcwd())
            with open(args.config) as f:
                text = f.read()
            try:
                cfg = jsonlib.loads(text)
            except jsonlib.JSONDecodeError:
                import yaml
                cfg = yaml.safe_load(text)
            if not isinstance(cfg, dict):
                print(f"invalid serve config {args.config!r}",
                      file=sys.stderr)
                return 2
            serve.start()
            for app_cfg in cfg.get("applications", []):
                mod_name, _, attr = app_cfg["import_path"].partition(":")
                app = getattr(importlib.import_module(mod_name), attr)
                overrides = {d["name"]: d
                             for d in app_cfg.get("deployments", [])}

                def apply(a):
                    for sub in list(a.args) + list(a.kwargs.values()):
                        if type(sub).__name__ == "Application":
                            apply(sub)
                    o = overrides.get(a.deployment.name)
                    if o:
                        for k in ("num_replicas", "max_concurrent_queries",
                                  "user_config"):
                            if k in o:
                                setattr(a.deployment._config, k, o[k])
                apply(app)
                serve.run(app)
                print(f"deployed application from "
                      f"{app_cfg['import_path']}")
            print(jsonlib.dumps(serve.status(), indent=2))
            return 0
        return 2
    finally:
        ray_tpu.shutdown()


def cmd_metrics(args) -> int:
    from ray_tpu import state
    if getattr(args, "json", False):
        # Structured snapshot (per-node registries, un-merged) for
        # scripting; the default stays Prometheus exposition text.
        print(json.dumps(state.cluster_metrics(args.address), indent=2,
                         default=str))
        return 0
    print(state.prometheus_metrics(args.address), end="")
    return 0


def cmd_trace(args) -> int:
    """ASCII span tree of one trace: every process's begin/end pairs,
    clock-normalized and parent-linked, torn spans flagged (a crash dump
    terminates its open spans at dump time)."""
    from ray_tpu import state
    tree = state.spans(args.trace_id, args.address, since=args.since)
    if args.json:
        print(json.dumps(tree, indent=2, default=str))
        return 0
    root = tree["root"]
    if root is None:
        print(f"no spans for trace {args.trace_id}")
        return 1

    def fmt(n) -> str:
        dur = (f"{n['dur'] * 1e3:9.2f}ms" if n.get("dur") is not None
               else "        ?ms")
        flags = "".join([" TORN" if n.get("torn") else "",
                         " ~trunc" if n.get("truncated") else ""])
        where = (f" [{str(n.get('node_id') or '')[:8]}:{n.get('pid', '?')}]"
                 if n.get("pid") else "")
        payload = n.get("payload") or {}
        extras = " ".join(f"{k}={v}" for k, v in payload.items()
                          if k not in ("ph", "parent", "dur"))
        return (f"{n['plane']}/{n['kind']:<12s} {dur}{flags}{where}"
                + (f" {extras}" if extras else ""))

    def walk(n, prefix: str, is_last: bool, is_root: bool):
        if is_root:
            print(fmt(n))
            child_prefix = ""
        else:
            print(f"{prefix}{'└─ ' if is_last else '├─ '}{fmt(n)}")
            child_prefix = prefix + ("   " if is_last else "│  ")
        kids = sorted(n.get("children", []),
                      key=lambda c: c.get("start") or 0.0)
        for i, c in enumerate(kids):
            walk(c, child_prefix, i == len(kids) - 1, False)

    wall = ((root["end"] - root["start"]) * 1e3
            if root.get("end") is not None and root.get("start") is not None
            else 0.0)
    print(f"trace {args.trace_id[:16]}  wall={wall:.2f}ms  "
          f"{len(tree['spans'])} spans  {tree['torn']} torn")
    walk(root, "", True, True)
    cp = state.critical_path(args.trace_id, args.address, since=args.since)
    if cp["by_kind"]:
        print("critical path:")
        for k, v in cp["by_kind"].items():
            if v * 1e3 < 0.005:
                continue  # zero-length bookkeeping segments
            frac = v / cp["wall"] if cp["wall"] else 0.0
            print(f"  {k:<22s} {v * 1e3:9.2f}ms  {frac:6.1%}")
    return 0


def cmd_analyze(args) -> int:
    """Ranked per-phase latency table: where cluster wall clock goes,
    per span kind (p50/p95/p99, total, fraction of the observed
    window)."""
    from ray_tpu import state
    bd = state.latency_breakdown(args.address, plane=args.plane,
                                 trace_id=args.trace, since=args.since)
    if args.json:
        print(json.dumps(bd, indent=2, default=str))
        return 0
    if not bd["phases"]:
        print("no span data (is RAY_TPU_EVENTS on? did anything run "
              "under a trace?)")
        return 1
    print(f"-- latency breakdown (window {bd['wall']:.3f}s) --")
    print(f"{'phase':<24s} {'count':>7s} {'p50(ms)':>9s} {'p95(ms)':>9s} "
          f"{'p99(ms)':>9s} {'total(s)':>9s} {'%wall':>7s}")
    for ph in bd["phases"]:
        print(f"{ph['plane'] + '/' + ph['kind']:<24s} {ph['count']:>7d} "
              f"{ph['p50'] * 1e3:>9.2f} {ph['p95'] * 1e3:>9.2f} "
              f"{ph['p99'] * 1e3:>9.2f} {ph['total']:>9.3f} "
              f"{ph['fraction']:>7.1%}")
    return 0


def cmd_timeline(args) -> int:
    from ray_tpu import state
    evs = state.timeline(args.address,
                         include_events=getattr(args, "events", False))
    out = getattr(args, "out", None) or "ray_tpu_timeline.json"
    with open(out, "w") as f:
        # Event payloads are free-form; stringify anything exotic rather
        # than losing the whole trace to one unserializable field.
        json.dump(evs, f, default=str)
    print(f"wrote {len(evs)} events to {out} "
          f"(open in chrome://tracing or perfetto)")
    return 0


def cmd_events(args) -> int:
    """Cluster-wide flight-recorder stream: live rings + crash dumps,
    skew-normalized and merged (reference: `ray list cluster-events` /
    experimental/state — here backed by util/events.py)."""
    from ray_tpu import state
    evs = state.events(args.address, plane=args.plane, kind=args.kind,
                       trace_id=args.trace, since=args.since)
    if args.limit:
        evs = evs[-args.limit:]
    if args.json:
        print(json.dumps(evs, indent=2, default=str))
        return 0
    for e in evs:
        ts = e.get("ts_adj", e["ts"])
        trace = e.get("trace_id") or ""
        payload = e.get("payload") or {}
        crash = (f" !{e.get('reason', 'crash')}"
                 if e.get("source") == "crash" else "")
        where = f"{str(e.get('node_id', ''))[:8]}:{e.get('pid', '?')}"
        print(f"{ts:.6f} [{where}{crash}] "
              f"{e.get('plane', ''):<6s} {e.get('kind', ''):<20s}"
              + (f" trace={trace[:8]}" if trace else "")
              + ("".join(f" {k}={v}" for k, v in payload.items())
                 if isinstance(payload, dict) else f" {payload}"))
    print(f"({len(evs)} events)")
    return 0


def cmd_top(args) -> int:
    """Live view: per-plane flight-recorder event rates plus latency
    percentiles from every histogram in the cluster scrape (reference:
    `ray status -v` refresh loop; percentile math in util/metrics.py)."""
    import time as _time

    from ray_tpu import state
    from ray_tpu.util import metrics as mt

    def render() -> str:
        now = _time.time()
        evs = state.events(args.address, since=now - args.window)
        rates = {}
        for e in evs:
            rates[e.get("plane", "?")] = rates.get(e.get("plane", "?"), 0) + 1
        snap = state.cluster_metrics(args.address)
        merged = {}
        mt.merge_snapshot(merged, snap["gcs"])
        for m in snap["nodes"].values():
            mt.merge_snapshot(merged, m)
        lines = [f"-- ray_tpu top (window {args.window:g}s, "
                 f"{len(evs)} events) --",
                 "events/s by plane:"]
        for pl in sorted(rates):
            lines.append(f"  {pl:<8s} {rates[pl] / args.window:10.1f}/s")
        if not rates:
            lines.append("  (none)")
        lines.append("latency percentiles:")
        shown = 0
        for name, entry in sorted(merged.items()):
            if entry.get("type") != "histogram":
                continue
            for series in entry.get("series", []):
                q = mt.series_quantiles(entry, series)
                if q is None:
                    continue
                tags = ",".join(f"{k}={v}" for k, v
                                in sorted(series["tags"].items()))
                label = name + ("{" + tags + "}" if tags else "")
                n = series["value"].get("count", 0)
                lines.append(f"  {label} n={n}"
                             f" p50={q[0.5]:.4g} p95={q[0.95]:.4g}"
                             f" p99={q[0.99]:.4g}")
                shown += 1
        if not shown:
            lines.append("  (no histogram data yet)")
        return "\n".join(lines)

    watch = getattr(args, "watch", None)
    interval = watch if watch else args.interval
    i = 0
    try:
        while True:
            if i:
                _time.sleep(interval)
            if watch:
                # Clear + home, full-screen redraw (watch(1)-style).
                print("\x1b[2J\x1b[H", end="")
            print(render(), flush=True)
            i += 1
            if args.count and i >= args.count:
                break
    except KeyboardInterrupt:
        print()  # leave the shell prompt on its own line
    return 0


def cmd_stack(args) -> int:
    """Dump live thread stacks cluster-wide (reference: `ray stack`)."""
    from ray_tpu import state
    per_node = state.stack_traces(args.address)
    if args.json:
        print(json.dumps(per_node, indent=2, default=str))
        return 0
    for node_id, reply in per_node.items():
        print(f"=== node {node_id[:12]} ===")
        if "error" in reply:
            print(f"  unreachable: {reply['error']}")
            continue
        for proc in reply["processes"]:
            state_txt = proc.get("state", "")
            print(f"-- pid {proc['pid']} ({proc['kind']}"
                  f"{' ' + state_txt if state_txt else ''}) --")
            if proc.get("error"):
                print(f"   <no dump: {proc['error']}>")
            for th in proc["threads"]:
                print(f"  thread {th['name']} ({th['thread_id']}):")
                for line in th["stack"].rstrip().splitlines():
                    print(f"    {line}")
    return 0


def cmd_memory(args) -> int:
    from ray_tpu import state
    rows = [r for r in state.list_objects(args.address) if "capacity" in r]
    for r in rows:
        r["node_id"] = r["node_id"][:12]
        r["used_mb"] = round(r.pop("used", 0) / 1e6, 1)
        r["capacity_mb"] = round(r.pop("capacity", 0) / 1e6, 1)
    print(_fmt_table(rows, ["node_id", "used_mb", "capacity_mb",
                            "num_objects", "num_evictions"]))
    return 0


def cmd_local_dump(args) -> int:
    """Collect this host's session logs + cluster state into a tarball
    (reference: scripts.py local_dump — the ops artifact attached to bug
    reports)."""
    import glob
    import json as _json
    import os
    import tarfile
    import tempfile
    import time as _time

    out = args.out or f"ray_tpu_dump_{int(_time.time())}.tar.gz"
    if args.session_dir:
        sessions = [args.session_dir]
    else:
        if args.sessions <= 0:
            print("--sessions must be >= 1", file=sys.stderr)
            return 2

        def _mtime(p):  # a session dir can vanish between glob and sort
            try:
                return os.path.getmtime(p)
            except OSError:
                return 0.0

        sessions = sorted(glob.glob(os.path.join(
            tempfile.gettempdir(), "ray_tpu", "session_*")), key=_mtime)
        sessions = sessions[-args.sessions:]
    with tarfile.open(out, "w:gz") as tar:
        for sess in sessions:
            logs = os.path.join(sess, "logs")
            if os.path.isdir(logs):
                tar.add(logs, arcname=os.path.join(
                    os.path.basename(sess), "logs"))
        if args.address:
            try:
                from ray_tpu import state
                snap = {
                    "nodes": state.list_nodes(args.address),
                    "actors": state.list_actors(args.address),
                    "workers": state.list_workers(args.address),
                    "summary": state.summarize_cluster(args.address),
                }
                blob = _json.dumps(snap, indent=2, default=str).encode()
                import io as _io
                info = tarfile.TarInfo("cluster_state.json")
                info.size = len(blob)
                tar.addfile(info, _io.BytesIO(blob))
            except Exception as e:  # noqa: BLE001
                print(f"warning: no cluster state captured: {e}",
                      file=sys.stderr)
    print(f"wrote {out} ({len(sessions)} session(s))")
    return 0


def cmd_global_gc(args) -> int:
    """Trigger gc.collect() in every worker in the cluster (reference:
    scripts.py global_gc / ray._private.internal_api.global_gc): frees
    cyclic garbage holding ObjectRefs so their objects can release."""
    import ray_tpu
    ray_tpu.init(address=args.address)

    @ray_tpu.remote(num_cpus=0)
    def _gc():
        import gc
        import os
        return os.getpid(), gc.collect()

    try:
        from ray_tpu import state
        workers = [w for w in state.list_workers(args.address)
                   if w.get("alive")]
        # Best effort: tasks land wherever the scheduler places them, so
        # over-subscribe and report the DISTINCT workers actually hit
        # (the reference broadcasts a core-worker RPC instead).
        n = max(4, 2 * len(workers))
        outs = ray_tpu.get([_gc.remote() for _ in range(n)], timeout=120)
        pids = {pid for pid, _ in outs}
        print(f"gc.collect() ran in {len(pids)} worker(s) "
              f"({n} tasks; cycles collected: "
              f"{sum(c for _, c in outs)})")
    finally:
        ray_tpu.shutdown()
    return 0


def cmd_microbenchmark(args) -> int:
    """Core-runtime microbenchmarks (reference: `ray microbenchmark`)."""
    import importlib.util
    import os
    repo_script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "scripts", "microbench.py")
    if not os.path.exists(repo_script):
        print("scripts/microbench.py not found", file=sys.stderr)
        return 2
    spec = importlib.util.spec_from_file_location("microbench", repo_script)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.main()
    return 0


_RLLIB_ALGOS = {
    "PPO": ("ray_tpu.rllib.ppo", "PPOConfig"),
    "APPO": ("ray_tpu.rllib.appo", "APPOConfig"),
    "IMPALA": ("ray_tpu.rllib.impala", "IMPALAConfig"),
    "A2C": ("ray_tpu.rllib.a2c", "A2CConfig"),
    "DQN": ("ray_tpu.rllib.dqn", "DQNConfig"),
    "SAC": ("ray_tpu.rllib.sac", "SACConfig"),
    "TD3": ("ray_tpu.rllib.td3", "TD3Config"),
    "ES": ("ray_tpu.rllib.es", "ESConfig"),
    "ARS": ("ray_tpu.rllib.ars", "ARSConfig"),
    "LinUCB": ("ray_tpu.rllib.bandit", "LinUCBConfig"),
    "LinTS": ("ray_tpu.rllib.bandit", "LinTSConfig"),
}


def cmd_rllib_train(args) -> int:
    """Train an algorithm from the command line (reference:
    rllib/train.py — `rllib train --algo PPO --env CartPole-v1`)."""
    import importlib
    import json as _json

    import ray_tpu
    mod_name, cfg_name = _RLLIB_ALGOS[args.algo]
    cfg_cls = getattr(importlib.import_module(mod_name), cfg_name)
    ray_tpu.init()
    cfg = (cfg_cls().environment(args.env)
           .rollouts(num_rollout_workers=args.num_workers)
           .debugging(seed=args.seed))
    if args.config:
        cfg.training(**_json.loads(args.config))
    algo = cfg.build()
    try:
        for i in range(args.stop_iters):
            r = algo.train()
            mean = r.get("episode_reward_mean")
            print(f"iter {r['training_iteration']}: "
                  f"reward_mean={mean:.1f} steps={r['timesteps_total']}")
            if args.stop_reward is not None and mean == mean \
                    and mean >= args.stop_reward:
                print(f"stop-reward {args.stop_reward} reached")
                break
        if args.out:
            ckpt = algo.save()
            ckpt.to_directory(args.out)
            print(f"checkpoint written to {args.out}")
    finally:
        algo.stop()
        ray_tpu.shutdown()
    return 0


def cmd_rllib_evaluate(args) -> int:
    """Greedy-policy evaluation of a saved checkpoint (reference:
    rllib/evaluate.py)."""
    import importlib

    import ray_tpu
    from ray_tpu.air.checkpoint import Checkpoint
    mod_name, cfg_name = _RLLIB_ALGOS[args.algo]
    cfg_cls = getattr(importlib.import_module(mod_name), cfg_name)
    ray_tpu.init()
    cfg = (cfg_cls().environment(args.env)
           .rollouts(num_rollout_workers=0)
           .debugging(seed=args.seed))
    algo = cfg.build()
    try:
        algo.restore(Checkpoint.from_directory(args.checkpoint))
        # Scale the step budget to the request: the default 1000-step
        # cap would silently truncate long-episode envs.
        stats = algo.workers.local_worker.evaluate(
            num_episodes=args.episodes, max_steps=args.episodes * 1000)
        rets = stats["episode_returns"]
        if rets:
            import statistics
            print(f"{len(rets)} episodes: mean={statistics.fmean(rets):.1f} "
                  f"min={min(rets):.1f} max={max(rets):.1f}")
        else:
            print("no episodes completed")
    finally:
        algo.stop()
        ray_tpu.shutdown()
    return 0


def cmd_rllib_evaluate_offline(args) -> int:
    """Off-policy evaluation of a checkpointed policy against logged
    experiences (reference: rllib/offline/estimators — `rllib train
    --evaluate-offline` workflow)."""
    import importlib

    import numpy as np

    import ray_tpu
    from ray_tpu.air.checkpoint import Checkpoint
    from ray_tpu.rllib.estimators import ESTIMATORS, fit_fqe
    from ray_tpu.rllib.offline import JsonReader
    mod_name, cfg_name = _RLLIB_ALGOS[args.algo]
    cfg_cls = getattr(importlib.import_module(mod_name), cfg_name)
    ray_tpu.init()
    cfg = (cfg_cls().environment(args.env)
           .rollouts(num_rollout_workers=0)
           .debugging(seed=args.seed))
    algo = cfg.build()
    try:
        algo.restore(Checkpoint.from_directory(args.checkpoint))
        policy = algo.workers.local_worker.policy
        if getattr(policy, "num_actions", 0) == 0:
            print("evaluate-offline requires a discrete-action policy "
                  "(the IS/WIS/DM/DR estimators are categorical)")
            return 2

        def target_probs(obs):
            _a, _z, _v, logits = policy.compute_actions(
                np.asarray(obs), explore=False)
            z = logits - logits.max(-1, keepdims=True)
            e = np.exp(z)
            return e / e.sum(-1, keepdims=True)

        batch = JsonReader(args.data).read_all()
        names = [n.strip() for n in args.estimators.split(",") if n.strip()]
        q_fn = None
        if any(n in ("dm", "dr") for n in names):
            q_fn = fit_fqe(batch, target_probs,
                           num_actions=policy.num_actions,
                           gamma=args.gamma, seed=args.seed)
        for name in names:
            cls = ESTIMATORS[name]
            out = cls(target_probs, gamma=args.gamma,
                      q_fn=q_fn).estimate(batch)
            print(f"{name:4s} v_target={out['v_target']:.3f} "
                  f"v_behavior={out['v_behavior']:.3f} "
                  f"v_gain={out['v_gain']:+.3f} "
                  f"({out['episodes']} episodes)")
    finally:
        algo.stop()
        ray_tpu.shutdown()
    return 0


def cmd_up(args) -> int:
    from ray_tpu.autoscaler import launcher
    state = launcher.create_or_update_cluster(
        args.config, no_restart=args.no_restart)
    print(f"cluster up; connect with "
          f"ray_tpu.init(address={state['gcs_address']!r})")
    return 0


def cmd_down(args) -> int:
    from ray_tpu.autoscaler import launcher
    launcher.teardown_cluster(args.config)
    return 0


def cmd_exec(args) -> int:
    from ray_tpu.autoscaler import launcher
    return launcher.exec_cluster(args.config, args.command)


def cmd_submit(args) -> int:
    from ray_tpu.autoscaler import launcher
    return launcher.submit(args.config, args.script, args.script_args)


def cmd_attach(args) -> int:
    import os as _os
    from ray_tpu.autoscaler import launcher
    argv = launcher.attach_command(args.config)
    _os.execvp(argv[0], argv)  # replaces this process


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ray_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start a head node or join a cluster")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address")
    sp.add_argument("--num-cpus", type=float, default=None)
    sp.add_argument("--num-tpus", type=float, default=None)
    sp.add_argument("--object-store-memory", type=int, default=256 << 20)
    sp.add_argument("--gcs-port", type=int, default=0,
                    help="fixed GCS port for --head (0 = ephemeral)")
    sp.add_argument("--block", action="store_true",
                    help="stay attached; ctrl-c tears the node down")
    sp.set_defaults(fn=cmd_start)

    for name, fn in (("stop", cmd_stop), ("status", cmd_status),
                     ("memory", cmd_memory), ("metrics", cmd_metrics),
                     ("timeline", cmd_timeline), ("stack", cmd_stack)):
        q = sub.add_parser(name)
        q.add_argument("--address", required=True)
        q.add_argument("--json", action="store_true")
        if name == "timeline":
            q.add_argument("--out", default="ray_tpu_timeline.json")
            q.add_argument("--events", action="store_true",
                           help="merge flight-recorder events as "
                                "instant events, and the start-up "
                                "records' rows as intervals")
        q.set_defaults(fn=fn)

    q = sub.add_parser("events",
                       help="cluster-wide flight-recorder event stream")
    q.add_argument("--address", required=True)
    q.add_argument("--plane", default=None,
                   help="filter: sched/object/engine/serve/ckpt/"
                        "ingest/train/proc")
    q.add_argument("--kind", default=None)
    q.add_argument("--trace", default=None,
                   help="join: only events carrying this trace id")
    q.add_argument("--since", type=float, default=0.0,
                   help="unix timestamp lower bound")
    q.add_argument("--limit", type=int, default=0,
                   help="keep only the newest N after filtering")
    q.add_argument("--json", action="store_true")
    q.set_defaults(fn=cmd_events)

    q = sub.add_parser("top", help="live per-plane event rates and "
                                   "latency percentiles")
    q.add_argument("--address", required=True)
    q.add_argument("--window", type=float, default=10.0,
                   help="rate window in seconds")
    q.add_argument("--interval", type=float, default=2.0,
                   help="refresh period")
    q.add_argument("--count", type=int, default=0,
                   help="stop after N refreshes (0 = until ctrl-c)")
    q.add_argument("--watch", type=float, nargs="?", const=2.0,
                   default=None, metavar="SECONDS",
                   help="full-screen refresh every N seconds (clear + "
                        "redraw; ctrl-c exits)")
    q.set_defaults(fn=cmd_top)

    q = sub.add_parser("trace",
                       help="ASCII span tree + critical path of one trace")
    q.add_argument("trace_id")
    q.add_argument("--address", required=True)
    q.add_argument("--since", type=float, default=0.0,
                   help="unix timestamp lower bound for the scrape")
    q.add_argument("--json", action="store_true")
    q.set_defaults(fn=cmd_trace)

    q = sub.add_parser("analyze",
                       help="ranked per-phase latency breakdown from "
                            "span durations")
    q.add_argument("--address", required=True)
    q.add_argument("--plane", default=None,
                   help="narrow to one plane (sched/object/engine/serve/"
                        "ckpt/ingest/train/proc)")
    q.add_argument("--trace", default=None,
                   help="narrow to one trace id")
    q.add_argument("--since", type=float, default=0.0,
                   help="unix timestamp lower bound for the scrape")
    q.add_argument("--json", action="store_true")
    q.set_defaults(fn=cmd_analyze)

    q = sub.add_parser("serve", help="serve control (deploy/status/shutdown)")
    q.add_argument("serve_cmd", choices=["deploy", "status", "shutdown"])
    q.add_argument("config", nargs="?", help="config file for deploy")
    q.add_argument("--address", required=True)
    q.set_defaults(fn=cmd_serve)

    q = sub.add_parser("client-server",
                       help="serve thin clients (ray_tpu:// mode)")
    q.add_argument("--address", required=True)
    q.add_argument("--port", type=int, default=10001)
    q.add_argument("--host", default="0.0.0.0")
    q.set_defaults(fn=cmd_client_server)

    q = sub.add_parser("dashboard", help="run the dashboard head "
                                         "(REST API + web UI)")
    q.add_argument("--address", required=True)
    q.add_argument("--host", default="127.0.0.1")
    q.add_argument("--port", type=int, default=8265)
    q.set_defaults(fn=cmd_dashboard)

    q = sub.add_parser("job", help="submit and manage jobs")
    jsub = q.add_subparsers(dest="job_cmd", required=True)
    js = jsub.add_parser("submit")
    js.add_argument("--dashboard-address", required=True)
    js.add_argument("--working-dir")
    js.add_argument("--submission-id")
    js.add_argument("--no-wait", action="store_true")
    js.add_argument("--timeout", type=float, default=600.0)
    js.add_argument("entrypoint", nargs="+")
    js.set_defaults(fn=cmd_job)
    for jname in ("list", "status", "logs", "stop"):
        js = jsub.add_parser(jname)
        js.add_argument("--dashboard-address", required=True)
        if jname != "list":
            js.add_argument("submission_id")
        js.set_defaults(fn=cmd_job)

    q = sub.add_parser("list", help="list live cluster entities")
    q.add_argument("kind", choices=["nodes", "actors", "workers",
                                    "placement-groups", "objects",
                                    "tasks"])
    q.add_argument("--address", required=True)
    q.add_argument("--json", action="store_true")
    q.set_defaults(fn=cmd_list)

    # Cluster launcher (reference: ray up/down/exec/submit/attach,
    # scripts.py:1247) over the CommandRunner plane.
    q = sub.add_parser("up", help="start a cluster from a config file")
    q.add_argument("config")
    q.add_argument("--no-restart", action="store_true")
    q.set_defaults(fn=cmd_up)
    q = sub.add_parser("down", help="tear a launched cluster down")
    q.add_argument("config")
    q.set_defaults(fn=cmd_down)
    q = sub.add_parser("exec", help="run a command on the cluster head")
    q.add_argument("config")
    q.add_argument("command")
    q.set_defaults(fn=cmd_exec)
    q = sub.add_parser("submit", help="ship a script to the head and run it")
    q.add_argument("config")
    q.add_argument("script")
    q.add_argument("script_args", nargs="*")
    q.set_defaults(fn=cmd_submit)
    q = sub.add_parser("attach", help="interactive shell on the head")
    q.add_argument("config")
    q.set_defaults(fn=cmd_attach)

    q = sub.add_parser("local-dump",
                       help="tar up session logs + cluster state")
    q.add_argument("--address", default=None)
    q.add_argument("--out", default=None)
    q.add_argument("--sessions", type=int, default=1,
                   help="how many recent sessions to include")
    q.add_argument("--session-dir", default=None,
                   help="dump exactly this session directory")
    q.set_defaults(fn=cmd_local_dump)
    q = sub.add_parser("global-gc",
                       help="run gc.collect() across the cluster")
    q.add_argument("--address", required=True)
    q.set_defaults(fn=cmd_global_gc)
    q = sub.add_parser("microbenchmark",
                       help="core-runtime microbenchmarks")
    q.set_defaults(fn=cmd_microbenchmark)

    q = sub.add_parser("rllib", help="train/evaluate RL algorithms")
    rsub = q.add_subparsers(dest="rllib_cmd", required=True)
    rt = rsub.add_parser("train")
    rt.add_argument("--algo", choices=sorted(_RLLIB_ALGOS), default="PPO")
    rt.add_argument("--env", default="CartPole-v1")
    rt.add_argument("--num-workers", type=int, default=1)
    rt.add_argument("--stop-iters", type=int, default=50)
    rt.add_argument("--stop-reward", type=float, default=None)
    rt.add_argument("--seed", type=int, default=0)
    rt.add_argument("--config", default=None,
                    help="JSON of extra .training(...) overrides")
    rt.add_argument("--out", default=None,
                    help="write a checkpoint directory on finish")
    rt.set_defaults(fn=cmd_rllib_train)
    re_ = rsub.add_parser("evaluate")
    re_.add_argument("checkpoint")
    re_.add_argument("--algo", choices=sorted(_RLLIB_ALGOS), default="PPO")
    re_.add_argument("--env", default="CartPole-v1")
    re_.add_argument("--episodes", type=int, default=10)
    re_.add_argument("--seed", type=int, default=0)
    re_.set_defaults(fn=cmd_rllib_evaluate)
    ro = rsub.add_parser(
        "evaluate-offline",
        help="off-policy estimates of a checkpointed policy on logged "
             "data (reference: rllib/offline/estimators)")
    ro.add_argument("checkpoint")
    ro.add_argument("--data", required=True,
                    help="JSON experience directory (JsonWriter output)")
    ro.add_argument("--algo", choices=sorted(_RLLIB_ALGOS), default="PPO")
    ro.add_argument("--env", default="CartPole-v1")
    ro.add_argument("--estimators", default="is,wis,dm,dr")
    ro.add_argument("--gamma", type=float, default=0.99)
    ro.add_argument("--seed", type=int, default=0)
    ro.set_defaults(fn=cmd_rllib_evaluate_offline)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
