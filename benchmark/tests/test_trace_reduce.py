import gzip
import os

import pytest

from benchmark import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(os.path.dirname(HERE), "testdata",
                        "train_b2_two_steps.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    return T.reduce(T.load(str(path)))


def test_busy_and_idle_from_the_recorded_trace(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.045871, abs=1e-5)
    assert reduced["busy_s"] == pytest.approx(0.045848, abs=1e-5)
    assert 0.0 < reduced["idle_pct"] < 0.1
    assert reduced["collective_s"] == 0.0


def test_kernels_are_found_by_their_custom_call_target(reduced):
    # 2 steps x 12 layers x (forward, dq, dk/dv), all named flash_attention
    assert set(reduced["kernels"]) == {"flash_attention"}
    assert reduced["kernels"]["flash_attention"]["calls"] == 72
    assert reduced["kernel_s"] == pytest.approx(0.008536, abs=1e-5)
    top = reduced["breakdown"]["device_ops"][0]
    assert top[0] == "flash_attention bf16[24,1024,64] (kernel)"
    assert len(reduced["breakdown"]["device_ops"]) <= 10
    assert len(reduced["breakdown"]["idle_gaps"]) <= 10


def test_describe_reads_hlo_text():
    assert T.describe(
        '%all-gather-start.3 = (f32[4,8]{1,0}, f32[16,8]{1,0}) '
        'all-gather-start(f32[4,8] %p), dimensions={0}') == (
        "all-gather-start", "all-gather-start f32[4,8]", False, True)
    base, label, kernel, coll = T.describe(
        '%closed_call.7 = bf16[8,25,64]{2,1,0} custom-call(s32[8] %x), '
        'custom_call_target="tpu_custom_call"')
    assert (base, kernel, coll) == ("closed_call", True, False)
    assert T.describe("%fusion.12 = bf16[2,3]{1,0} fusion(...)")[0] == "fusion"


def test_union_and_gaps():
    assert T.union([(0, 4), (2, 6), (10, 11)]) == 7
    assert T.gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [(0, 2), (6, 8),
                                                       (9, 10)]


def test_nested_operations_are_counted_once():
    # a `while` of 100 ns holding two body operations and a gap of 10
    ops = [(0, 100, "while"), (10, 40, "a"), (40, 90, "b"), (100, 120, "c")]
    assert T.self_times(ops) == [("while", 20), ("a", 30), ("b", 50),
                                 ("c", 20)]
    assert sum(ns for _, ns in T.self_times(ops)) == T.union(
        (s, e) for s, e, _ in ops)
