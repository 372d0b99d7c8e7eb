"""The benchmark's arithmetic, checked without a chip: percentiles (of
completed requests, and of attempts where failures count as slowest) and
the window rate, the least bytes of the codec's device work and the
roofline reader that counts them, and the table of peaks."""
import json
import math
import os
from types import SimpleNamespace

import pytest

from bench import run, stats, work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


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


def test_percentile_of_attempts_counts_failures_as_slowest():
    done = [1.0, 2.0, 3.0, 4.0]
    assert stats.percentile_of_attempts(done, 0, 50) == stats.percentile(
        done, 50)
    # five attempts, one failed: the median is the third fastest
    assert stats.percentile_of_attempts(done, 1, 50) == 3.0
    assert stats.percentile_of_attempts(done, 1, 75) == 4.0
    assert stats.percentile_of_attempts(done, 1, 74) == pytest.approx(3.96)
    assert math.isinf(stats.percentile_of_attempts(done, 1, 90))
    assert math.isinf(stats.percentile_of_attempts([], 2, 10))
    with pytest.raises(ValueError):
        stats.percentile_of_attempts([], 0, 50)


def test_encode_and_transcode_least_bytes():
    t, h, w = 30, 540, 960
    plane = h * w * 3
    # u8 frames in; the u8 I-frame and int8 (or int16) residuals out
    assert work.encode_bytes(t, h, w) == t * plane + plane + (t - 1) * plane
    assert work.encode_bytes(t, h, w, resid_bytes=2) == \
        t * plane + plane + 2 * (t - 1) * plane
    assert work.encode_bytes(t, h, w) == work.decode_bytes(t, h, w)
    # one tier's planes in, the pooled tier's planes out, no frames
    out = plane // 4
    assert work.transcode_bytes(t, h, w, factor=2) == \
        plane + (t - 1) * plane + out + (t - 1) * out
    assert work.transcode_bytes(t, h, w, resid_in=2) == \
        2 * (plane + (t - 1) * plane) + (t - 1) * plane


def codec_roofline():
    return run.load_module(ROOT, "layers", "codec_roofline")


# kernel ops as a v5e trace names them (the decode and encode as recorded
# on the chip, at 960x540 padded to 1024x544)
T8 = "{3,2,1,0:T(8,128)}"
HLO = {
    "decode": "%delta_decode_pallas.1 = f32[30,3,544,1024]" + T8 +
              " custom-call(f32[3,544,1024]{2,1,0:T(8,128)} %iframe.1, "
              "s32[29,3,544,1024]" + T8 + " %residuals.1), custom_call_"
              "target=\"tpu_custom_call\", operand_layout_constraints={f32"
              "[3,544,1024]{2,1,0}, s32[29,3,544,1024]{3,2,1,0}}",
    "encode": "%delta_encode_pallas.1 = (f32[3,544,1024]{2,1,0:T(8,128)}, "
              "s32[4,3,544,1024]" + T8 + ") custom-call(f32[5,3,544,1024]" +
              T8 + " %frames.1), custom_call_target=\"tpu_custom_call\"",
    # a decode that takes its 16-bit residuals as stored, at 480x270
    "decode16": "%delta_decode_pallas.7 = f32[5,3,272,512]" + T8 +
                " custom-call(f32[3,272,512]{2,1,0} %iframe.1, "
                "s16[4,3,272,512]" + T8 + " %residuals.1)",
    "transcode": "%transcode_pallas.2 = (f32[3,272,512]{2,1,0}, "
                 "s32[29,3,272,512]" + T8 + ") custom-call(f32[3,544,1024]"
                 "{2,1,0} %iframe.1, s32[29,3,544,1024]" + T8 +
                 " %residuals.1), custom_call_target=\"tpu_custom_call\"",
    "other": "%histogram_pallas.1 = s32[1,3,256]{2,1,0} custom-call("
             "f32[3,544,1024]{2,1,0} %x.1)",
}
SIZES = [(135, 240), (270, 480), (540, 960)]


def test_codec_roofline_reads_each_kernel_op_from_its_own_hlo():
    m = codec_roofline()
    assert m.gop_bytes(HLO["decode"], SIZES) == work.decode_bytes(30, 540,
                                                                  960)
    assert m.gop_bytes(HLO["encode"], SIZES) == work.encode_bytes(5, 540,
                                                                  960)
    assert m.gop_bytes(HLO["decode16"], SIZES) == work.decode_bytes(
        5, 270, 480, resid_bytes=2)
    assert m.gop_bytes(HLO["transcode"], SIZES) == work.transcode_bytes(
        30, 540, 960, factor=2)
    assert m.gop_bytes(HLO["other"], SIZES) is None
    assert m.gop_bytes(HLO["decode"], [(600, 1100)]) is None  # fits none
    assert m.gop_bytes("%fusion.3 = f32[8,128]{1,0} fusion(f32[8,128] %x)",
                       SIZES) is None


def test_codec_roofline_share_of_the_busy_time():
    m = codec_roofline()
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    trace = {"busy_s": 0.01, "kernels": [HLO["decode"], HLO["other"]]}
    ctx = SimpleNamespace(trace=trace, device_kind="TPU v5 lite",
                          peaks=peaks, frame_sizes=SIZES)
    least = work.decode_bytes(30, 540, 960) / 819e9
    assert m.read(ctx) == pytest.approx(100 * least / 0.01)
    ctx.trace = {"busy_s": 0.01, "kernels": [HLO["other"]]}
    assert m.read(ctx) is None
    ctx.trace = None
    assert m.read(ctx) is None
