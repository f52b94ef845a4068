"""Serve tests (reference coverage model: python/ray/serve/tests/) against
a real cluster: deployments, scaling, composition, HTTP ingress, batching,
replica failure healing."""

import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def cluster():
    info = ray_tpu.init(num_cpus=8, object_store_memory=64 << 20)
    serve.start()
    yield info
    serve.shutdown()
    ray_tpu.shutdown()


def test_function_deployment(cluster):
    @serve.deployment
    def echo(x):
        return {"echo": x}

    handle = serve.run(echo.bind())
    assert handle.remote("hi").result(timeout=60) == {"echo": "hi"}


def test_class_deployment_with_state(cluster):
    @serve.deployment(name="counter")
    class Counter:
        def __init__(self, start):
            self.n = start

        def __call__(self, inc):
            self.n += inc
            return self.n

        def peek(self):
            return self.n

    handle = serve.run(Counter.bind(100))
    assert handle.remote(5).result(timeout=60) == 105
    assert handle.peek.remote().result(timeout=60) == 105
    serve.delete("counter")


def test_multiple_replicas_round_robin(cluster):
    @serve.deployment(name="pidsvc", num_replicas=2)
    class PidSvc:
        def __call__(self, _):
            import os
            return os.getpid()

    handle = serve.run(PidSvc.bind())
    pids = {handle.remote(None).result(timeout=60) for _ in range(8)}
    assert len(pids) == 2
    serve.delete("pidsvc")


def test_deployment_graph_composition(cluster):
    @serve.deployment(name="preprocess")
    def preprocess(x):
        return x * 2

    @serve.deployment(name="model")
    class Model:
        def __init__(self, downstream):
            self.downstream = downstream

        def __call__(self, x):
            doubled = self.downstream.remote(x).result(timeout=30)
            return doubled + 1

    handle = serve.run(Model.bind(preprocess.bind()))
    assert handle.remote(10).result(timeout=60) == 21
    serve.delete("model")
    serve.delete("preprocess")


def test_http_ingress(cluster):
    import json
    import urllib.request

    @serve.deployment(name="httpsvc")
    def svc(payload):
        return {"doubled": payload["x"] * 2}

    serve.run(svc.bind())
    port = serve.start(with_proxy=True)
    assert port

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/httpsvc",
        data=json.dumps({"x": 21}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        body = json.loads(resp.read())
    assert body == {"result": {"doubled": 42}}

    # Unknown deployment -> 404.
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/nosuch",
        data=json.dumps({}).encode())
    try:
        urllib.request.urlopen(req, timeout=30)
        raised = False
    except urllib.error.HTTPError as e:
        raised = e.code == 404
    assert raised
    serve.delete("httpsvc")


def test_batching(cluster):
    @serve.deployment(name="batcher")
    class Batcher:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        def handle(self, items):
            self.batch_sizes.append(len(items))
            return [i * 10 for i in items]

        def __call__(self, x):
            return self.handle(x)

        def sizes(self):
            return self.batch_sizes

    handle = serve.run(
        Batcher.options(max_concurrent_queries=16).bind())
    refs = [handle.remote(i) for i in range(8)]
    results = sorted(r.result(timeout=60) for r in refs)
    assert results == [0, 10, 20, 30, 40, 50, 60, 70]
    sizes = handle.sizes.remote().result(timeout=60)
    assert max(sizes) > 1  # batching actually combined requests
    serve.delete("batcher")


def test_replica_failure_heals(cluster):
    @serve.deployment(name="fragile", num_replicas=1)
    class Fragile:
        def __call__(self, cmd):
            if cmd == "die":
                import os
                os._exit(1)
            return "alive"

    handle = serve.run(Fragile.bind())
    assert handle.remote("ping").result(timeout=60) == "alive"
    try:
        handle.remote("die").result(timeout=60)
    except Exception:
        pass
    # Controller heals the replica set; next call must succeed.
    deadline = time.monotonic() + 60
    ok = False
    while time.monotonic() < deadline:
        try:
            if handle.remote("ping").result(timeout=30) == "alive":
                ok = True
                break
        except Exception:
            time.sleep(0.5)
    assert ok
    serve.delete("fragile")


def test_status_and_scaling(cluster):
    @serve.deployment(name="scaleme", num_replicas=1)
    def f(x):
        return x

    serve.run(f.bind())
    assert serve.status()["scaleme"]["num_replicas"] == 1
    serve.run(f.options(num_replicas=3).bind())
    assert serve.status()["scaleme"]["num_replicas"] == 3
    serve.delete("scaleme")


def test_autoscaling_grows_and_shrinks(cluster):
    """Queue-depth autoscaling: replicas grow under sustained load and
    shrink back when idle (reference: _private/autoscaling_policy.py)."""
    import threading

    @serve.deployment(name="auto", max_concurrent_queries=4,
                      autoscaling_config={"min_replicas": 1,
                                          "max_replicas": 3,
                                          "target_ongoing_requests": 1.0,
                                          "upscale_delay_s": 0.1,
                                          "downscale_delay_s": 0.5})
    def slow(x):
        time.sleep(0.4)
        return x

    handle = serve.run(slow.bind())
    assert handle.remote(0).result(timeout=60) == 0
    assert serve.status()["auto"]["num_replicas"] == 1

    # Sustained load: concurrent callers long enough for the control
    # loop to react even on a loaded 1-core CI host.
    stop = time.monotonic() + 15
    errors = []

    def worker():
        while time.monotonic() < stop:
            try:
                handle.remote(1).result(timeout=60)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=worker) for _ in range(12)]
    for t in threads:
        t.start()
    grew = False
    while time.monotonic() < stop:
        if serve.status()["auto"]["num_replicas"] > 1:
            grew = True
            break
        time.sleep(0.2)
    stop = time.monotonic()  # release workers once growth is observed
    for t in threads:
        t.join()
    assert not errors, errors[:1]
    assert grew, "autoscaler never scaled up under load"

    # Idle: must shrink back to min_replicas.
    deadline = time.monotonic() + 40
    while time.monotonic() < deadline:
        if serve.status()["auto"]["num_replicas"] == 1:
            break
        time.sleep(0.3)
    assert serve.status()["auto"]["num_replicas"] == 1
    serve.delete("auto")


def test_long_poll_config_propagation(cluster):
    """A live handle learns about re-deployments via the controller
    long-poll, without forced refreshes (reference: long_poll.py:68)."""
    @serve.deployment(name="lp")
    def v1(x):
        return "v1"

    handle = serve.run(v1.bind())
    assert handle.remote(0).result(timeout=60) == "v1"

    @serve.deployment(name="lp")
    def v2(x):
        return "v2"

    serve.run(v2.bind())
    deadline = time.monotonic() + 15
    seen = None
    while time.monotonic() < deadline:
        seen = handle.remote(0).result(timeout=60)
        if seen == "v2":
            break
        time.sleep(0.2)
    assert seen == "v2", "handle never picked up the new version"
    serve.delete("lp")


def test_http_proxy_concurrency(cluster):
    """30 parallel slow HTTP requests overlap on the async proxy instead
    of serializing through a thread pool."""
    import concurrent.futures
    import json as jsonlib
    import urllib.request

    @serve.deployment(name="slowhttp", num_replicas=2,
                      max_concurrent_queries=32)
    def slowhttp(x):
        time.sleep(0.3)
        return x

    serve.run(slowhttp.bind())
    port = serve.start(with_proxy=True)

    def one(i):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/slowhttp",
            data=jsonlib.dumps(i).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            return jsonlib.loads(resp.read())["result"]

    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(max_workers=30) as pool:
        results = list(pool.map(one, range(30)))
    elapsed = time.monotonic() - t0
    assert sorted(results) == list(range(30))
    # Serial execution would be >= 30 * 0.3 = 9s; two replicas x overlap
    # must land far below that.
    assert elapsed < 6.0, f"requests serialized: {elapsed:.1f}s"
    serve.delete("slowhttp")


def test_serve_cli_deploy_from_config(tmp_path, monkeypatch):
    """`serve deploy <config>` imports an application, applies per-
    deployment overrides, and reports status (reference: serve CLI +
    schema.py config deploy)."""
    import io
    import json
    import subprocess
    import sys
    from contextlib import redirect_stdout

    from ray_tpu.cluster_utils import Cluster

    app_mod = tmp_path / "my_serve_app.py"
    app_mod.write_text(
        "import ray_tpu\n"
        "from ray_tpu import serve\n\n"
        "@serve.deployment(name='hello')\n"
        "def hello(x):\n"
        "    return {'hi': x}\n\n"
        "app = hello.bind()\n")
    config = tmp_path / "serve_config.json"
    config.write_text(json.dumps({
        "applications": [{
            "import_path": "my_serve_app:app",
            "deployments": [{"name": "hello", "num_replicas": 2}],
        }]}))

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 4})
    try:
        import os
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts.cli", "serve",
             "deploy", str(config), "--address", cluster.address],
            capture_output=True, text=True, timeout=180,
            cwd=str(tmp_path), env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert '"hello"' in proc.stdout
        assert '"num_replicas": 2' in proc.stdout
    finally:
        cluster.shutdown()


def test_usage_stats_written(tmp_path):
    from ray_tpu._private import usage

    stats = usage.collect_usage({"probe": 1})
    assert stats["probe"] == 1 and "ray_tpu_version" in stats
    path = usage.record_usage(str(tmp_path))
    assert path and tmp_path.joinpath("usage_stats.json").exists()


def test_async_deployment_intra_replica_concurrency(cluster):
    """A single replica hosting an async handler must overlap awaits on
    its persistent event loop (reference: replica.py:268 runs a user
    event loop): 10 concurrent 150ms-await requests complete together in
    ~1 await's time, not ~10x serially (VERDICT r2 item 10)."""

    @serve.deployment(name="aio", num_replicas=1)
    class Slow:
        async def __call__(self, _):
            import asyncio
            await asyncio.sleep(0.15)
            import os
            return os.getpid()

    handle = serve.run(Slow.bind())
    handle.remote(None).result(timeout=60)  # warm the path
    t0 = time.monotonic()
    futs = [handle.remote(None) for _ in range(10)]
    pids = {f.result(timeout=60) for f in futs}
    dt = time.monotonic() - t0
    assert len(pids) == 1, "expected exactly one replica"
    # Serial execution would take >= 1.5s; overlapped ~0.15s. The bound
    # leaves slack for a loaded single-core CI host.
    assert dt < 0.9, f"async requests did not overlap: {dt:.2f}s"
    serve.delete("aio")


# ---------------------------------------------------------------------------
# ASGI ingress + streaming (reference: serve/api.py @serve.ingress +
# http_proxy.py's ASGI host; streaming DeploymentResponseGenerator).
# ---------------------------------------------------------------------------


def _tiny_asgi_app():
    """Dependency-free ASGI app with two routes, path/query passthrough
    and a chunked streaming route."""

    async def app(scope, receive, send):
        assert scope["type"] == "http"
        path = scope["path"]
        if path == "/hello":
            body = b"hi " + scope["query_string"]
            await send({"type": "http.response.start", "status": 200,
                        "headers": [(b"content-type", b"text/plain"),
                                    (b"x-route", b"hello")]})
            await send({"type": "http.response.body", "body": body})
        elif path.startswith("/echo/"):
            msg = await receive()
            body = path.split("/echo/", 1)[1].encode() + b":" + \
                msg.get("body", b"")
            await send({"type": "http.response.start", "status": 201,
                        "headers": [(b"content-type", b"text/plain")]})
            await send({"type": "http.response.body", "body": body})
        elif path == "/stream":
            await send({"type": "http.response.start", "status": 200,
                        "headers": [(b"content-type", b"text/plain")]})
            for i in range(5):
                await send({"type": "http.response.body",
                            "body": f"c{i};".encode(), "more_body": True})
            await send({"type": "http.response.body", "body": b"end"})
        else:
            await send({"type": "http.response.start", "status": 404,
                        "headers": []})
            await send({"type": "http.response.body", "body": b"nope"})

    return app


def test_asgi_ingress_routes_and_streaming(cluster):
    """An ASGI app mounted on ONE deployment serves multiple routes with
    path/query/body passthrough through the HTTP proxy, and a chunked
    response streams through end to end."""
    import urllib.request

    @serve.deployment(name="asgiapp")
    @serve.ingress(_tiny_asgi_app())
    class Api:
        pass

    serve.run(Api.bind())
    port = serve.start(with_proxy=True)
    base = f"http://127.0.0.1:{port}/asgiapp"

    with urllib.request.urlopen(base + "/hello?who=tpu", timeout=30) as r:
        assert r.status == 200
        assert r.headers["x-route"] == "hello"
        assert r.read() == b"hi who=tpu"

    req = urllib.request.Request(base + "/echo/abc", data=b"payload",
                                 method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 201
        assert r.read() == b"abc:payload"

    with urllib.request.urlopen(base + "/stream", timeout=30) as r:
        assert r.read() == b"c0;c1;c2;c3;c4;end"

    import urllib.error
    try:
        urllib.request.urlopen(base + "/missing", timeout=30)
        raise AssertionError("expected 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404
    serve.delete("asgiapp")


def test_handle_streaming_is_incremental(cluster):
    """handle.stream() pulls generator chunks one at a time from the
    replica: the consumer sees chunk k BEFORE the producer has emitted
    chunk k+1 (pull-based, not collect-then-return)."""

    @serve.deployment(name="streamer")
    class Streamer:
        async def tokens(self, n):
            for i in range(n):
                await __import__("asyncio").sleep(0.15)
                yield {"token": i, "emitted_at": time.monotonic()}

    serve.run(Streamer.bind())
    h = serve.get_deployment_handle("streamer").options("tokens")
    arrivals = []
    chunks = []
    for chunk in h.stream(4):
        arrivals.append(time.monotonic())
        chunks.append(chunk["token"])
    assert chunks == [0, 1, 2, 3]
    # Incremental: successive arrivals are separated by the producer's
    # sleep — a collect-then-return stream would arrive all at once.
    gaps = [b - a for a, b in zip(arrivals, arrivals[1:])]
    assert all(g > 0.05 for g in gaps), gaps
    serve.delete("streamer")


def test_generator_method_non_stream_call_raises_cleanly(cluster):
    """A generator method called through the NON-streaming path
    (handle.remote(), plain HTTP dispatch) raises a clear TypeError
    directing the caller to the streaming API — and must not leak the
    replica's in-flight stream slot (reference: streaming methods
    require the streaming handle API)."""

    @serve.deployment(name="genmat", max_concurrent_queries=2)
    class GenMat:
        def chunks(self, n):
            for i in range(n):
                yield i

        def plain(self):
            return "ok"

    serve.run(GenMat.bind())
    h = serve.get_deployment_handle("genmat")
    # Repeat PAST max_concurrent_queries: a leaked slot per call would
    # saturate the replica and time out the later calls.
    for _ in range(5):
        with pytest.raises(Exception, match="stream"):
            h.options("chunks").remote(3).result(timeout=30)
    # The replica still serves normal calls (no slots were leaked) and
    # the streaming API still works.
    assert h.options("plain").remote().result(timeout=30) == "ok"
    assert list(h.options("chunks").stream(3)) == [0, 1, 2]
    serve.delete("genmat")


def test_asgi_receive_does_not_fabricate_disconnect(cluster):
    """Frameworks (Starlette listen_for_disconnect) await receive()
    concurrently while streaming; a fabricated http.disconnect would
    cancel the stream immediately.  The shim must block instead."""
    import asyncio
    import urllib.request

    async def app(scope, receive, send):
        await receive()  # request body
        cancelled = asyncio.Event()

        async def watch_disconnect():
            msg = await receive()   # must BLOCK, not return immediately
            if msg["type"] == "http.disconnect":
                cancelled.set()

        watcher = asyncio.ensure_future(watch_disconnect())
        await send({"type": "http.response.start", "status": 200,
                    "headers": [(b"content-type", b"text/plain")]})
        for i in range(3):
            await asyncio.sleep(0.05)
            if cancelled.is_set():   # the bug: fires on fabricated msg
                break
            await send({"type": "http.response.body",
                        "body": f"c{i};".encode(), "more_body": True})
        await send({"type": "http.response.body", "body": b"",
                    "more_body": False})
        watcher.cancel()

    @serve.deployment(name="sseapp")
    @serve.ingress(app)
    class SSE:
        pass

    serve.run(SSE.bind())
    port = serve.start(with_proxy=True)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/sseapp/x", timeout=30).read()
    assert body == b"c0;c1;c2;", body
    serve.delete("sseapp")


# ---------------------------------------------------------------------------
# Graceful degradation: deadlines, draining, failover, shedding plumbing
# ---------------------------------------------------------------------------

def test_request_deadline_bounds_admission_wait(cluster):
    """A handle timeout_s caps how long a request may wait for a replica
    slot: with the only slot busy, the second request times out at its
    deadline instead of sitting in the admission queue for the full
    backpressure window."""
    import threading

    @serve.deployment(name="deadliner", num_replicas=1,
                      max_concurrent_queries=1)
    def slow(x):
        time.sleep(1.5)
        return x

    handle = serve.run(slow.bind())
    handle.remote("warm").result(timeout=60)  # routing table populated

    t = threading.Thread(
        target=lambda: handle.remote("hog").result(timeout=60))
    t.start()
    time.sleep(0.2)  # the hog owns the only slot
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        handle.options(timeout_s=0.3).remote("late").result(timeout=60)
    assert time.monotonic() - t0 < 1.2
    t.join(60)
    serve.delete("deadliner")


def test_deadline_propagates_to_replica(cluster):
    """A deadline-aware deployment (signature takes `_deadline_s`)
    receives the remaining budget server-side."""
    @serve.deployment(name="dlaware")
    def report(x, _deadline_s=None):
        return _deadline_s

    handle = serve.run(report.bind())
    # No deadline configured: nothing injected.
    assert handle.remote(0).result(timeout=60) is None
    got = handle.options(timeout_s=7.5).remote(0).result(timeout=60)
    assert got is not None and 0 < got <= 7.5
    serve.delete("dlaware")


def test_stream_deadline_aborts_mid_stream(cluster):
    """A stream that outlives its request deadline is aborted — client
    raises, and the replica-side generator is closed (its finally runs)
    instead of producing for nobody."""
    from ray_tpu.exceptions import TaskError

    @serve.deployment(name="slowstream", num_replicas=1)
    def ticks(n):
        for i in range(n):
            time.sleep(0.25)
            yield i

    handle = serve.run(ticks.bind())
    got = []
    with pytest.raises((TimeoutError, TaskError)):
        for c in handle.options(timeout_s=0.6).stream(100):
            got.append(c)
    assert len(got) < 100
    serve.delete("slowstream")


def test_stream_failover_replay_skips_delivered_chunks(cluster):
    """Generic mid-stream failover: kill the replica mid-stream; with
    failover="replay" the handle heals, resubmits, skips the chunks the
    consumer already saw, and the stream completes without duplicates."""
    from ray_tpu.serve._private import (
        CONTROLLER_NAME, SERVE_NAMESPACE)

    @serve.deployment(name="replaysrc", num_replicas=1)
    def count(n):
        for i in range(n):
            time.sleep(0.05)
            yield i

    handle = serve.run(count.bind())
    controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)
    got = []
    for c in handle.options(failover="replay").stream(8):
        got.append(c)
        if len(got) == 3:
            routing = ray_tpu.get(
                controller.get_routing.remote("replaysrc"), timeout=30)
            ray_tpu.kill(routing["replicas"][0])
    assert got == list(range(8))
    serve.delete("replaysrc")


def test_restarted_replica_raises_stream_lost(cluster):
    """next_chunk for a stream id the replica does not know must raise
    ReplicaStreamLostError (the failover trigger), never fake a clean
    end-of-stream."""
    from ray_tpu.serve._private import (
        CONTROLLER_NAME, SERVE_NAMESPACE, _is_replica_loss)

    @serve.deployment(name="loststream")
    def gen():
        yield 1

    serve.run(gen.bind())
    controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)
    routing = ray_tpu.get(
        controller.get_routing.remote("loststream"), timeout=30)
    replica = routing["replicas"][0]
    with pytest.raises(Exception) as ei:
        ray_tpu.get(replica.next_chunk.remote(424242), timeout=30)
    assert _is_replica_loss(ei.value)
    serve.delete("loststream")


class _Arrived:
    """A stream that says what has arrived: chunks put on a queue, None
    for the end; `closed` counts the consumers that left."""
    closed = 0

    def __init__(self, chunks):
        import queue
        self.q = queue.Queue()
        for c in chunks:
            self.q.put(c)

    def __iter__(self):
        return self

    def __next__(self):
        c = self.q.get()
        if c is None:
            raise StopIteration
        return c

    def ready(self):
        return not self.q.empty()

    def close(self):
        type(self).closed += 1


def _replica_of(name):
    from ray_tpu.serve._private import CONTROLLER_NAME, SERVE_NAMESPACE
    controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)
    routing = ray_tpu.get(controller.get_routing.remote(name), timeout=30)
    return routing["replicas"][0]


@pytest.mark.parametrize("ended", [True, False])
def test_a_reply_carries_every_chunk_that_has_arrived(cluster, ended):
    """A method may hand back a stream with `ready()`: one next_chunk
    reply then carries every chunk that is here, in order, and the
    stream's end beside them if it is here too; what is not here yet is
    the next call's, which waits for it like a generator's pull."""
    from ray_tpu.serve import _private

    @serve.deployment(name="arrived")
    class Arrived:
        def chunks(self, n, ended):
            self.s = _Arrived(list(range(n)) + ([None] if ended else []))
            return self.s

        def put(self, c):
            self.s.q.put(c)

    serve.run(Arrived.bind())
    replica = _replica_of("arrived")
    ticket = ray_tpu.get(replica.handle_request.remote(
        "chunks", (5, ended), {}, True, None), timeout=30)
    sid = ticket["__serve_stream__"]
    out = ray_tpu.get(replica.next_chunk.remote(sid), timeout=30)
    assert list(_private._reply_chunks(out)) == [0, 1, 2, 3, 4]
    assert bool(out.get("done")) is ended
    if not ended:
        ref = replica.next_chunk.remote(sid)        # waits on the pool
        time.sleep(0.2)
        ray_tpu.get(replica.handle_request.remote(
            "put", (7,), {}, False, None), timeout=30)
        assert ray_tpu.get(ref, timeout=30) == {"chunk": 7}
        ray_tpu.get(replica.handle_request.remote(
            "put", (None,), {}, False, None), timeout=30)
        assert ray_tpu.get(replica.next_chunk.remote(sid),
                           timeout=30) == {"done": True}
    with pytest.raises(Exception):                  # the slot was given back
        ray_tpu.get(replica.next_chunk.remote(sid), timeout=30)
    assert ray_tpu.get(replica.ongoing_requests.remote(), timeout=30) == 0
    serve.delete("arrived")


def test_a_slow_consumer_of_such_a_stream_catches_up(cluster):
    """handle.stream() over a stream with `ready()`: a consumer slower
    than the producer still sees every chunk once and in order, hears of
    the end with the last chunks, and one that leaves closes the stream;
    `.remote()` on the method is refused as on a generator's and closes
    it too."""

    @serve.deployment(name="bursty", max_concurrent_queries=2)
    class Bursty:
        def chunks(self, n):
            import threading
            s = _Arrived([])

            def feed():
                for i in range(n):
                    time.sleep(0.01)
                    s.q.put(i)
                s.q.put(None)
            threading.Thread(target=feed, daemon=True).start()
            return s

        def closed(self):
            return _Arrived.closed

    serve.run(Bursty.bind())
    h = serve.get_deployment_handle("bursty")
    got = []
    for c in h.options("chunks").stream(40):
        got.append(c)
        if len(got) == 1:
            time.sleep(0.3)             # 30 chunks' time
    assert got == list(range(40))
    for c in h.options("chunks").stream(1000):
        if c == 3:
            break                       # the consumer leaves
    for _ in range(3):                  # past max_concurrent_queries
        with pytest.raises(Exception, match="stream"):
            h.options("chunks").remote(3).result(timeout=30)
    deadline = time.time() + 30
    while h.options("closed").remote().result(timeout=30) < 4:
        assert time.time() < deadline
        time.sleep(0.05)
    assert list(h.options("chunks").stream(3)) == [0, 1, 2]
    serve.delete("bursty")


def test_status_reports_replica_states(cluster):
    @serve.deployment(name="stately", num_replicas=2)
    def f(x):
        return x

    serve.run(f.bind())
    st = serve.status()["stately"]
    assert st["states"]["RUNNING"] == 2
    assert st["states"]["DRAINING"] == 0
    serve.delete("stately")


def test_llm_stream_resume_policy_rewrites_request():
    """Unit: the LLM failover policy appends produced tokens to the
    prompt, decrements the budget, aligns the sampling offset, and
    signals completion (None) on exhausted budget or EOS."""
    from ray_tpu.serve import llm_stream_resume

    args, kwargs = llm_stream_resume(([1, 2], 8), {}, [5, 6, 7])
    assert args == ([1, 2, 5, 6, 7],)
    assert kwargs["max_new_tokens"] == 5
    assert kwargs["_produced_offset"] == 3
    # Positional temperature/eos_id/seed survive as kwargs.
    args, kwargs = llm_stream_resume(([1], 4, 0.9, 99, 7), {}, [3])
    assert args == ([1, 3],)
    assert kwargs["temperature"] == 0.9 and kwargs["eos_id"] == 99 \
        and kwargs["seed"] == 7
    # Budget exhausted -> the stream was already complete.
    assert llm_stream_resume(([1], 3), {}, [4, 5, 6]) is None
    # EOS emitted -> complete, even with budget left.
    assert llm_stream_resume(([1], 9), {"eos_id": 6}, [4, 6]) is None
