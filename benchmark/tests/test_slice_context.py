"""The paged kernels' roofline shares take their context from the traced
slice itself: `metrics.slice_mean` and the readers over it
(`paged_decode_roofline`, `moe_paged_decode_roofline`, `moe_step_roofline`,
`eva_decode_roofline`), on hand-made runs."""

import pytest

from benchmark import eva_flops, flops, manifest, metrics, moe_flops, readers

ON, SLICE_S, LANES = 100.0, 2.0, 4
PEAKS = manifest.peaks("TPU v5 lite")
FIELDS = {
    "paged_decode_roofline": {"n_heads": 25, "d_model": 1600},
    "moe_paged_decode_roofline": {"n_heads": 16, "n_kv_heads": 8,
                                  "d_model": 2048},
    "moe_step_roofline": {"n_heads": 16, "n_kv_heads": 8, "d_model": 2048,
                          "n_layers": 2, "n_experts": 8, "d_ff": 1024,
                          "vocab_size": 512},
    "eva_decode_roofline": {"n_heads": 32, "d_model": 4096,
                            "window_size": 2048, "chunk_size": 16},
}
READERS = sorted(FIELDS)


def lane(prompt_len, first, last, every=0.05):
    """A request whose tokens reach the client every `every` seconds from
    `first` to `last`."""
    n = int(round((last - first) / every)) + 1
    return {"prompt_len": prompt_len,
            "token_times": [first + i * every for i in range(n)]}


def make_run(name, records, off=2.3, marks=True):
    """What a traced serve run hands a reader: the client's records, the
    watcher's marks (`trace_off` set `off` seconds behind `trace_on`), and a
    reduced trace of 96 paged-kernel calls and 12 grouped multiplies."""
    return {
        "records": records, "fields": FIELDS[name],
        "marks": {"trace_on": ON, "trace_off": ON + off} if marks else {},
        "traffic": {"trace": {"slice_s": SLICE_S},
                    "engine": {"max_lanes": LANES}},
        "device": {"kind": "TPU v5 lite"},
        "stats0": {"moe": {"layer_steps": 0, "assignments": 0,
                           "expert_load": [0] * 8, "experts_hit": 0}},
        "stats1": {"moe": {"layer_steps": 100, "assignments": 3200,
                           "expert_load": [400] * 8, "experts_hit": 700}},
        "trace": {"busy_s": 1.9, "kernel_s": 0.31, "kernels": {
            "paged_decode_attention": {"calls": 96.0, "seconds": 0.01},
            "moe_grouped_matmul": {"calls": 12.0, "seconds": 0.3}}},
    }


def read(name, run):
    run.pop("not_measured", None)
    return manifest.module("layer_metrics", name).read(run)


def steady_lanes():
    """Four lanes that decode from before the slice to 50 s behind its
    start, the window's close: no record has a token after ON + 50."""
    return [lane(40 + 8 * i, ON - 5.0 - i, ON + 50.0) for i in range(LANES)]


def test_slice_mean_is_the_mean_over_the_slice_and_not_one_instant():
    """Half the lanes end at the slice's middle: the mean lies between the
    two ends, and is what sixteen evenly spaced instants give."""
    records = [lane(100, ON - 10.0, ON + 50.0, every=0.5),
               lane(300, ON - 10.0, ON + SLICE_S / 2, every=0.5)]
    run = make_run("paged_decode_roofline", records)
    start = metrics.live_context_tokens(records, ON + 0.01)
    end = metrics.live_context_tokens(records, ON + SLICE_S - 0.01)
    mean = metrics.slice_context_tokens(run)
    assert start == (100 + 21) + (300 + 21) and end == 100 + 24
    assert end < mean < start
    assert mean == sum(metrics.live_context_tokens(
        records, ON + SLICE_S * (i + 0.5) / 16) for i in range(16)) / 16
    # the second lane is live in the first eight instants and gone after
    assert mean == pytest.approx(((100 + 22.5) * 16 + (300 + 21.5) * 8) / 16,
                                 abs=1.0)
    assert metrics.slice_context_tokens(run, instants=2) == (
        metrics.live_context_tokens(records, ON + 0.5)
        + metrics.live_context_tokens(records, ON + 1.5)) / 2
    # a slice cut short (the stop came before slice_s had passed) is capped
    short = make_run("paged_decode_roofline", records, off=0.5)
    assert metrics.slice_context_tokens(short, instants=1) \
        == metrics.live_context_tokens(records, ON + 0.25)
    assert metrics.slice_mean({"marks": {"trace_on": ON}}, None) is None


@pytest.mark.parametrize("name", READERS)
def test_a_late_stop_reads_what_a_prompt_one_reads(name):
    """PR 36's case: `stop_trace()` returns 200 s behind `trace_on`, past the
    last token of every record.  The midpoint of the session then holds no
    context (the reader this replaces said `None`, and the run was refused);
    the slice holds what it held."""
    prompt = read(name, make_run(name, steady_lanes(), off=2.0))
    late = make_run(name, steady_lanes(), off=200.0)
    assert metrics.live_context_tokens(late["records"], ON + 100.0) == 0
    assert prompt is not None and prompt > 0
    assert read(name, late) == prompt
    assert "not_measured" not in late


@pytest.mark.parametrize("name", READERS)
def test_half_the_lanes_ending_mid_slice_reads_the_mean(name):
    whole = steady_lanes()
    half = whole[:2] + [dict(r, token_times=[
        t for t in r["token_times"] if t <= ON + SLICE_S / 2])
        for r in whole[2:]]
    none_end = read(name, make_run(name, whole))
    all_gone = read(name, make_run(name, whole[:2]))
    value = read(name, make_run(name, half))
    assert all_gone < value < none_end
    assert value == pytest.approx((all_gone + none_end) / 2, rel=0.02)


def test_the_share_is_the_least_time_of_the_slices_context_over_the_kernels():
    """The arithmetic around the context is what it was: `flops.paged_decode`
    over the K/V heads of the configuration, the named kernel's calls and
    seconds, the chip's peaks."""
    records = steady_lanes()
    for name, heads in (("paged_decode_roofline", 25),
                        ("moe_paged_decode_roofline", 8)):
        run = make_run(name, records)
        # another kernel in a serve trace is not the paged kernel's time
        run["trace"]["kernels"]["some_other_kernel"] = {"calls": 7.0,
                                                        "seconds": 5.0}
        least, bound = flops.roofline_s(*flops.paged_decode(
            metrics.slice_context_tokens(run), LANES, heads,
            FIELDS[name]["d_model"] // FIELDS[name]["n_heads"]), PEAKS)
        assert bound == "memory"
        assert read(name, run) == pytest.approx(100.0 * least * 96 / 0.01)
    run = make_run("eva_decode_roofline", records)
    least, _ = flops.roofline_s(*eva_flops.decode_attention(
        eva_flops.slice_rows(run), LANES, run["fields"]), PEAKS)
    assert read("eva_decode_roofline", run) == pytest.approx(
        100.0 * least * 96 / 0.01)
    run = make_run("moe_step_roofline", records)
    f = run["fields"]
    nbytes = (4 * moe_flops.layer_weight_bytes(f, 7.0)
              + 2 * moe_flops.head_bytes(f)
              + 48 * moe_flops.kv_bytes(f, metrics.slice_context_tokens(run)))
    assert read("moe_step_roofline", run) == pytest.approx(
        100.0 * nbytes / PEAKS["hbm_bytes_per_s"] / 1.9)


@pytest.mark.parametrize("name", READERS)
def test_none_only_for_a_missing_input_and_the_reader_says_which(name):
    records = steady_lanes()
    run = make_run(name, records)
    del run["trace"]
    assert read(name, run) is None and "no trace" in run["not_measured"]
    run = make_run(name, records)
    kernel = ("moe_grouped_matmul" if name == "moe_step_roofline"
              else "paged_decode_attention")
    del run["trace"]["kernels"][kernel]
    assert read(name, run) is None and kernel in run["not_measured"]
    run = make_run(name, records, marks=False)
    assert read(name, run) is None
    assert "no closed profiler session" in run["not_measured"]
    run["marks"] = {"trace_on": ON}             # opened and never closed
    assert read(name, run) is None
    assert "no closed profiler session" in run["not_measured"]
    if name != "moe_step_roofline":     # its K/V bytes are then none
        run = make_run(name, [lane(40, ON + 10.0, ON + 20.0)])
        assert read(name, run) is None
        assert "in the slice" in run["not_measured"]
    assert readers.not_measured({}, "x") is None
