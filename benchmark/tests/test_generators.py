import threading
import time

import numpy as np
import pytest

from benchmark import manifest as M
from benchmark.drivers import serve as serve_driver


@pytest.mark.parametrize("mix", ["decode_saturated", "chat_sessions_p80"])
def test_schedule_is_fixed_by_the_seed(mix):
    traffic = M.load().load_traffic(mix)
    make = M.module("generators", traffic["generator"]).make
    a, b = make(traffic, 5, 30.0, 50304), make(traffic, 5, 30.0, 50304)
    c = make(traffic, 6, 30.0, 50304)
    assert a == b
    assert [r["prompt"] for r in a[:20]] != [r["prompt"] for r in c[:20]]
    # every draw comes from --seed: lengths too, and an open loop's arrival
    # times (a closed loop has none: the driver sends when a client is free)
    if "clients" in traffic:
        assert {r["due"] for r in a} == {-traffic["requests"]["lead_in_s"]}
    else:
        assert [r["due"] for r in a] != [r["due"] for r in c]
    assert [r["max_new_tokens"] for r in a] != [r["max_new_tokens"]
                                                for r in c]
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)
    p = traffic["requests"]
    assert a[0]["due"] >= -p["lead_in_s"]
    assert a[-1]["due"] < 30.0 + p["tail_s"]
    assert all(0 <= t < 50304 for r in a[:10] for t in r["prompt"])


def test_a_longer_window_only_appends_arrivals():
    """Open loop: a longer window appends arrivals.  Closed loop: the list
    is the same for any window."""
    chat = M.load().load_traffic("chat_sessions_p80")
    make = M.module("generators", chat["generator"]).make
    short, long = make(chat, 1, 10.0, 50304), make(chat, 1, 20.0, 50304)
    assert len(long) > len(short)
    assert [r["due"] for r in long[:len(short)]] == [r["due"] for r in short]
    closed = M.load().load_traffic("decode_saturated")
    assert make(closed, 1, 10.0, 50304) == make(closed, 1, 51.0, 50304)


def test_decode_mix_shapes():
    traffic = M.load().load_traffic("decode_saturated")
    reqs = M.module("generators", traffic["generator"]).make(
        traffic, 0, 40.0, 50304)
    p, clients = traffic["requests"], traffic["clients"]
    assert "rate_rps" not in traffic and len(reqs) == p["count"] == 1024
    assert [r["id"] for r in reqs] == list(range(1024))
    # more clients than lanes, fewer than the router would hold back
    assert traffic["engine"]["max_lanes"] < clients \
        <= traffic["max_concurrent_queries"]
    assert all(16 <= len(r["prompt"]) <= 64 for r in reqs)
    assert all(r["session"] == r["group"] == -1 for r in reqs)
    # one request per client is cut short by a uniform draw, so that the
    # lanes hold requests at every stage; the rest run their whole length
    assert p["fill_requests"] == clients
    first, rest = reqs[:clients], reqs[clients:]
    assert all(256 <= r["max_new_tokens"] <= 512 for r in rest)
    assert all(2 <= r["max_new_tokens"] <= 512 for r in first)
    assert sum(r["max_new_tokens"] < 256 for r in first) > clients // 4
    assert abs(np.mean([r["max_new_tokens"] for r in rest]) - 384) < 10
    # a lane's worst case (64 + 512 tokens) fits the pool many times over
    e = traffic["engine"]
    assert e["num_blocks"] * e["block_size"] >= e["max_lanes"] * 448


class _StubHandle:
    """Stands where `handle.options("generate")` does: a stream of
    `max_new_tokens` tokens, slowly; prompts of `fail` raise at the send."""

    def __init__(self, fail=()):
        self.lock, self.fail = threading.Lock(), set(fail)
        self.now = self.most = 0
        self.order = []

    def stream(self, prompt, max_new_tokens):
        with self.lock:
            self.order.append(prompt[0])
            if prompt[0] in self.fail:
                raise RuntimeError("refused")
            self.now += 1
            self.most = max(self.most, self.now)
        return self._tokens(max_new_tokens)

    def _tokens(self, n):
        try:
            for i in range(n):
                time.sleep(0.004)
                yield i
        finally:                     # exhausted, or closed by the client
            with self.lock:
                self.now -= 1


def _stub_schedule(n, new_tokens):
    return [{"id": i, "due": -0.05, "prompt": [i], "max_new_tokens":
             new_tokens, "session": -1, "group": -1} for i in range(n)]


@pytest.mark.parametrize("clients", [1, 3])
def test_closed_loop_keeps_clients_in_flight_in_list_order(clients):
    handle, stop = _StubHandle(fail={2, 5}), threading.Event()
    base = time.time() + 0.05
    records, threads = serve_driver._drive(
        handle, _stub_schedule(12, 5), base, stop, base + 30.0, 0, clients)
    for th in threads:
        th.join(timeout=10)
    assert handle.most == clients and handle.now == 0
    # the list in its order, every request of it: an error freed its slot
    assert handle.order == [r["id"] for r in records] == list(range(12))
    assert [bool(r.get("error")) for r in records] \
        == [i in (2, 5) for i in range(12)]
    assert all(len(r["tokens"]) == 5 for r in records if not r.get("error"))
    # due when a client came free: the first at the schedule's start, each
    # later one at the end of an earlier request, and sent without delay
    assert all(r["due"] == base - 0.05 for r in records[:clients])
    ends = {r["done"] for r in records}
    assert all(r["due"] in ends for r in records[clients:])
    assert all(0 <= r["sent"] - r["due"] < 0.5 for r in records)


def test_closed_loop_ends_with_the_window_and_a_cut_frees_its_slot():
    handle, stop = _StubHandle(), threading.Event()
    base = time.time() + 0.05
    out = {}
    th = threading.Thread(target=lambda: out.update(zip(
        ("records", "threads"), serve_driver._drive(
            handle, _stub_schedule(1024, 10_000), base, stop, base + 30.0,
            0, 4))))
    th.start()
    time.sleep(0.4)
    assert th.is_alive() and handle.most == handle.now == 4
    stop.set()                       # the window closes: every stream is cut
    th.join(timeout=10)
    assert not th.is_alive()
    for t in out["threads"]:
        t.join(timeout=10)
    assert [r["id"] for r in out["records"]] == [0, 1, 2, 3]
    assert all(r["cut"] and "done" in r for r in out["records"])
    assert handle.now == 0
    # and without `stop`, `end_at` ends the loop
    base, stop = time.time(), threading.Event()
    records, threads = serve_driver._drive(
        _StubHandle(), _stub_schedule(64, 10_000), base + 0.05, stop,
        base + 0.3, 0, 2)
    assert time.time() - base < 2.0 and len(records) == 2
    stop.set()
    for t in threads:
        t.join(timeout=10)


def test_open_loop_sends_on_schedule_whatever_is_in_flight():
    handle, stop = _StubHandle(), threading.Event()
    base = time.time() + 0.05
    schedule = [dict(r, due=0.01 * r["id"])
                for r in _stub_schedule(8, 50)]
    records, threads = serve_driver._drive(
        handle, schedule, base, stop, base + 30.0, 0)
    for th in threads:
        th.join(timeout=10)
    assert handle.most > 4 and len(records) == 8
    assert [r["due"] for r in records] == [base + 0.01 * i for i in range(8)]


def test_chat_mix_shares_heads_and_grows_history():
    traffic = M.load().load_traffic("chat_sessions_p80")
    reqs = M.module("generators", traffic["generator"]).make(
        traffic, 0, 60.0, 50304)
    spec = traffic["requests"]["sessions"]
    lens = [len(r["prompt"]) for r in reqs]
    assert 32 <= min(lens) and max(lens) <= spec["max_prompt"]
    assert np.median(lens) < np.mean(lens) + 100     # a tail to the right
    grouped = [r for r in reqs if r["group"] >= 0]
    assert 0.6 < len(grouped) / len(reqs) < 0.9
    heads = {}
    for r in grouped:
        head = tuple(r["prompt"][:spec["head_len"]])
        assert heads.setdefault(r["group"], head) == head
    assert len(set(heads.values())) == spec["groups"]
    # within a session a prompt extends the previous one until it restarts
    by_session = {}
    extends = restarts = 0
    for r in reqs:
        prev = by_session.get(r["session"])
        if prev is not None:
            if r["prompt"][:len(prev)] == prev:
                extends += 1
            else:
                restarts += 1
        by_session[r["session"]] = r["prompt"]
    assert extends > restarts > 0


def test_token_batches_are_fixed_by_the_seed():
    traffic = {"batch": 2, "seq": 16, "n_batches": 3}
    make = M.module("generators", "token_batches").make
    a, b, c = make(traffic, 1, 512), make(traffic, 1, 512), \
        make(traffic, 2, 512)
    assert len(a) == 3 and a[0].shape == (2, 16)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not all((x == y).all() for x, y in zip(a, c))
    assert not (a[0] == a[1]).all()
