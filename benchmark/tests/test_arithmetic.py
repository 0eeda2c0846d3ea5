import math

import pytest

from benchmark import peaks, readers, record, roofline


def test_peaks_table_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.bf16_flops_per_s, p.hbm_bytes_per_s, p.hbm_bytes) == (197e12, 819e9, 16 * 2**30)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


def test_param_counts_are_the_published_ones():
    small = roofline.gpt2_param_count(n_layer=12, n_embd=768, vocab_size=50257, n_positions=1024)
    xl = roofline.gpt2_param_count(n_layer=48, n_embd=1600, vocab_size=50257, n_positions=1024)
    assert small == 124_439_808
    assert xl == 1_557_611_200


def test_mfu_and_roofline_arithmetic():
    flops = peaks.train_step_flops(124_439_808, 16 * 1024)
    assert flops == 6 * 124_439_808 * 16384
    assert peaks.mfu(flops, 4.5, 197e12) == pytest.approx(0.2794, abs=1e-3)
    assert roofline.kv_bytes_per_token(n_layer=48, n_embd=1600, cache_itemsize=4) == 614_400
    b = roofline.decode_step_bytes(n_params=1_557_611_200, param_itemsize=4,
                                   live_kv_tokens=2000, kv_bytes_token=614_400)
    assert b == 1_557_611_200 * 4 + 2000 * 614_400
    f, by = roofline.flash_fwd_cost(batch=16, heads=12, seq=1024, head_dim=64, itemsize=2)
    assert f == 4 * 16 * 12 * 1024 * 1024 * 64 * 0.5
    assert by == 4 * 16 * 12 * 1024 * 64 * 2
    t, bound = roofline.least_seconds(f, by, peaks.peaks_for("TPU v5e"))
    assert bound == "compute" and t == pytest.approx(f / 197e12)


def test_percentile_and_failed_requests_miss_every_latency():
    assert record.percentile([3, 1, 2, 4], 50) == 2
    assert record.percentile(list(range(1, 101)), 95) == 95
    assert record.percentile([], 95) is None
    ok = {"ok": True, "late_s": 0.01, "ttft_s": 0.09, "client_s": 1.1, "n_tokens": 11}
    bad = dict(ok, ok=False)
    assert readers.ttft_ms(ok) == pytest.approx(100.0)
    assert readers.tpot_ms(ok) == pytest.approx(100.0)
    run = record.Run(cell=None, requests=[ok] * 30 + [bad])   # 3% failed: the tail holds
    assert readers.latency_percentile(run, readers.ttft_ms, 95) == pytest.approx(100.0)
    run = record.Run(cell=None, requests=[ok] * 9 + [bad])    # 10% failed: no p95 to report
    assert readers.latency_percentile(run, readers.ttft_ms, 95) is None
    assert math.isinf(max(readers._latencies(run, readers.ttft_ms)))
