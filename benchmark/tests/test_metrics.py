import math

import pytest

from benchmark import flops, metrics
from benchmark import manifest as M


def rec(due, sent, times, n=None, **kw):
    return dict({"due": due, "sent": sent, "token_times": times,
                 "prompt_len": 10,
                 "max_new_tokens": len(times) if n is None else n}, **kw)


def test_percentile_interpolates_and_keeps_infinity():
    assert metrics.percentile([1, 2, 3, 4, 5], 50) == 3
    assert metrics.percentile([1, 2, 3, 4], 50) == 2.5
    assert metrics.percentile(range(1, 101), 90) == pytest.approx(90.1)
    assert metrics.percentile([5], 99) == 5
    assert metrics.percentile([1, 2, math.inf], 90) == math.inf
    assert metrics.percentile([1, 2, 3, math.inf], 50) == 2.5


def test_open_loop_timings_count_from_the_due_time():
    r = rec(due=10.0, sent=10.3, times=[11.0, 11.1, 11.3])
    assert metrics.ttft_ms(r) == pytest.approx(1000.0)   # not 700
    assert metrics.tpot_ms(r) == pytest.approx(150.0)
    assert metrics.lateness_ms([r]) == [pytest.approx(300.0)]
    assert metrics.token_gaps_ms([r]) == [pytest.approx(100.0),
                                          pytest.approx(200.0)]
    assert metrics.tpot_ms(rec(0, 0, [1.0])) is None


def test_failed_and_refused_requests_are_infinite():
    refused = rec(0.0, 0.0, [], n=8, error="ServeOverloadedError")
    assert metrics.ttft_ms(refused) == math.inf
    assert metrics.tpot_ms(refused) == math.inf
    assert metrics.request_failed(refused)
    short = rec(0.0, 0.0, [1.0, 2.0], n=8)
    assert metrics.request_failed(short)
    assert not metrics.request_failed(dict(short, cut=True))
    assert not metrics.request_failed(rec(0.0, 0.0, [1.0, 2.0]))


def test_tokens_and_requests_in_the_window():
    records = [rec(-1.0, -1.0, [0.5, 1.5, 9.9, 10.0]),
               rec(5.0, 5.0, [5.5, 12.0]), rec(10.0, 10.0, [10.5])]
    assert metrics.tokens_in_window(records, 0.0, 10.0) == 4
    assert [r["due"] for r in metrics.in_window(records, 0.0, 10.0)] == [5.0]
    assert metrics.live_context_tokens(records, 6.0) == (10 + 2) + (10 + 1)


def test_spread_is_the_quartile_distance_over_the_median():
    assert metrics.spread([98, 99, 100, 101, 102]) == pytest.approx(0.02)


GPT2S = {"vocab_size": 50304, "n_layers": 12, "d_model": 768, "n_heads": 12,
         "d_ff": 3072, "max_seq_len": 1024}


def test_flops_per_token_against_a_hand_count_for_gpt2_small():
    # per layer: q, k, v, o 4 x 768^2 = 2,359,296; MLP 2 x 768 x 3072 =
    # 4,718,592; head 768 x 50304 = 38,633,472
    assert flops.matmul_params(GPT2S) == 12 * 7_077_888 + 38_633_472
    # all parameters: + 4 x 768 of layer norms a layer, embeddings,
    # positions, final norm: 84,971,520 + 38,633,472 + 786,432 + 1,536 (the
    # "124M" of the model card, with the padded vocabulary)
    assert flops.num_params(GPT2S) == 124_392_960
    fwd_matmul = 2 * 123_568_128
    attn = 12 * 4 * 768 * 1025 / 2
    assert flops.forward_flops_per_token(GPT2S, 1024) == fwd_matmul + attn
    assert flops.flops_per_token(GPT2S, 1024) == 3 * (fwd_matmul + attn)
    # 121,164 tokens/s (the last on-chip figure, 2026-08-02) is 49.1% of 197e12
    assert flops.mfu(GPT2S, 1024, 121_164, 1, 197e12) == pytest.approx(
        0.4909, abs=1e-4)


def test_num_params_matches_the_program():
    from ray_tpu.models import gpt
    for name in ("gpt2-small", "gpt2-xl"):
        fields = M.load().load_config(name)["fields"]
        assert flops.num_params(fields) == gpt.num_params(gpt.CONFIGS[name])


def test_kernel_operation_and_byte_counts():
    # flash forward, b=24 h=12 s=1024 dh=64: 2 matmuls x 2 x 24 x 12 x 64 x
    # (1024 x 1025 / 2) = 38,692,454,400 FLOPs; q, k, v, o in bf16 and the
    # float32 logsumexp = 4 x 24 x 1024 x 768 x 2 + 24 x 12 x 1024 x 4
    fl, by = flops.flash_fwd(24, 12, 1024, 64)
    assert fl == 38_692_454_400 and by == 150_994_944 + 1_179_648
    fl2, by2 = flops.flash_bwd(24, 12, 1024, 64)
    assert fl2 == 2 * fl and by2 == 301_989_888 + 1_179_648
    # paged decode over 10,000 cached tokens, 8 lanes, 25 heads of 64
    fl3, by3 = flops.paged_decode(10_000, 8, 25, 64)
    assert fl3 == 4 * 10_000 * 1600 and by3 == (20_000 + 16) * 1600 * 2
    peaks = M.peaks("TPU v5 lite")
    assert flops.roofline_s(fl, by, peaks)[1] == "compute"
    least, bound = flops.roofline_s(fl3, by3, peaks)
    assert bound == "memory" and least == pytest.approx(by3 / 819e9)
