import numpy as np
import pytest

from benchmark import manifest as M


@pytest.mark.parametrize("mix", ["decode_saturated", "chat_sessions_p80"])
def test_schedule_is_fixed_by_the_seed(mix):
    traffic = M.load().load_traffic(mix)
    make = M.module("generators", traffic["generator"]).make
    a, b = make(traffic, 5, 30.0, 50304), make(traffic, 5, 30.0, 50304)
    c = make(traffic, 6, 30.0, 50304)
    assert a == b
    assert [r["prompt"] for r in a[:20]] != [r["prompt"] for r in c[:20]]
    # every draw comes from --seed: arrival times and lengths too
    assert [r["due"] for r in a] != [r["due"] for r in c]
    assert [r["max_new_tokens"] for r in a] != [r["max_new_tokens"]
                                                for r in c]
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)
    p = traffic["requests"]
    assert a[0]["due"] >= -p["lead_in_s"]
    assert a[-1]["due"] < 30.0 + p["tail_s"]
    assert all(0 <= t < 50304 for r in a[:10] for t in r["prompt"])


def test_a_longer_window_only_appends_arrivals():
    traffic = M.load().load_traffic("decode_saturated")
    make = M.module("generators", traffic["generator"]).make
    short, long = make(traffic, 1, 10.0, 50304), make(traffic, 1, 20.0, 50304)
    assert [r["due"] for r in long[:len(short)]] == [r["due"] for r in short]


def test_decode_mix_shapes():
    traffic = M.load().load_traffic("decode_saturated")
    reqs = M.module("generators", traffic["generator"]).make(
        traffic, 0, 40.0, 50304)
    fill = traffic["requests"]["fill_requests"]
    assert all(r["due"] == -traffic["requests"]["lead_in_s"]
               for r in reqs[:fill])
    rest = reqs[fill:]
    assert all(16 <= len(r["prompt"]) <= 64 for r in rest)
    assert all(256 <= r["max_new_tokens"] <= 512 for r in rest)
    assert all(r["session"] == -1 for r in rest)
    rate = len(rest) / (40.0 + traffic["requests"]["lead_in_s"])
    assert abs(rate - traffic["rate_rps"]) < 0.5 * traffic["rate_rps"]


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
