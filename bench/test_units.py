"""The benchmark's arithmetic, checked without a chip: percentiles and
the window rate, the least bytes of the codec's device work, and the
table of peaks."""
import json
import os

import pytest

from bench import stats, work

HERE = os.path.dirname(os.path.abspath(__file__))


def test_percentile_interpolates_between_closest_ranks():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 100) == 5.0
    assert stats.percentile(xs, 95) == pytest.approx(4.8)
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)


def test_percentile_of_one_value_and_of_none():
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_window_rate_counts_all_work_issued_in_the_window():
    work_ = [(0.0, 1.0, 10.0), (1.0, 2.0, 10.0), (1.5, 4.0, 10.0)]
    # 30 units; the last issued request completes at 4.0
    assert stats.window_rate(work_, 0.0, 3.0) == pytest.approx(30.0 / 4.0)


def test_window_rate_a_request_straddling_the_close_stretches_the_time():
    inside = [(0.0, 1.0, 10.0), (1.0, 2.0, 10.0)]
    straddling = inside + [(2.5, 5.0, 10.0)]
    assert stats.window_rate(inside, 0.0, 3.0) == pytest.approx(20.0 / 2.0)
    assert stats.window_rate(straddling, 0.0, 3.0) == pytest.approx(30.0 / 5.0)


def test_window_rate_ignores_work_issued_outside_the_window():
    work_ = [(-1.0, 0.5, 99.0), (0.0, 1.0, 10.0), (3.0, 3.5, 99.0)]
    assert stats.window_rate(work_, 0.0, 3.0) == pytest.approx(10.0)
    with pytest.raises(ValueError):
        stats.window_rate([(5.0, 6.0, 1.0)], 0.0, 3.0)


def test_decode_least_bytes():
    t, h, w = 30, 1080, 1920
    plane = h * w * 3
    want = plane + (t - 1) * plane + t * plane  # I-frame, int8 residuals, u8 out
    assert work.decode_bytes(t, h, w) == want
    assert work.decode_bytes(t, h, w, resid_bytes=2) == want + (t - 1) * plane


def test_least_seconds_uses_the_devices_bandwidth():
    peaks = {"devices": {"chip": {"hbm_bytes_per_s": 1e9}}}
    assert work.least_seconds(2e9, "chip", peaks) == pytest.approx(2.0)


def test_unknown_device_is_an_error():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    assert work.least_seconds(819e9, "TPU v5 lite", peaks) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.least_seconds(1.0, "TPU v9 imaginary", peaks)


def test_peaks_table_names_its_source():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes"] == 16e9
