"""The reduction from a profiler trace to busy time, idle share and the
breakdown, on small traces: a hand-made one whose answers can be worked
out by hand, and one recorded on a TPU v5e (a cut of a traced scan)."""
import gzip
import json
import os

import pytest

from bench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


def hand_made():
    return {
        "host": [
            [trace_reduce.WINDOW, 0, 100 * MS],
            ["bench.read_batch", 0, 50 * MS],
            ["bench.append", 50 * MS, 50 * MS],
        ],
        "device": [
            # overlapping ops on two lines count once
            ["fusion.1", 10 * MS, 10 * MS],
            ["delta_decode", 15 * MS, 10 * MS],
            # straddles the window's end: clipped, not counted by name
            ["fusion.2", 95 * MS, 10 * MS],
            # before the window: ignored
            ["fusion.3", -20 * MS, 5 * MS],
        ],
        "modules": [
            ["jit_delta_decode_pallas(123)", 15 * MS, 10 * MS],
            ["jit_delta_decode_pallas(123)", 96 * MS, 10 * MS],
        ],
    }


def test_the_clock_marks_ops_are_not_the_programs_work():
    tr = hand_made()
    tr["device"].append(["broadcast_add_fusion", 60 * MS, 1000])
    tr["modules"].append(["jit_bench_clock_mark(7)", 60 * MS - 10, 1020])
    red = trace_reduce.reduce(tr)
    assert red["busy_s"] == pytest.approx(0.020)
    assert "broadcast_add_fusion" not in dict(red["device_ops"])


def test_union_merges_overlaps_and_touching_intervals():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [
        (0, 4), (5, 7)]
    assert trace_reduce.union([]) == []


def test_busy_time_and_idle_share():
    red = trace_reduce.reduce(hand_made())
    assert red["window_s"] == pytest.approx(0.100)
    # [10, 25) and [95, 100) ms
    assert red["busy_s"] == pytest.approx(0.020)
    assert trace_reduce.idle_pct(red) == pytest.approx(80.0)


def test_breakdown_ops_and_gaps():
    red = trace_reduce.reduce(hand_made())
    assert red["device_ops"] == [["fusion.1", pytest.approx(0.010)],
                                 ["delta_decode", pytest.approx(0.010)]]
    # [25, 95) ms lies mostly in bench.append, [0, 10) in read_batch
    assert red["idle_gaps"] == [["bench.append", pytest.approx(0.070)],
                                ["bench.read_batch", pytest.approx(0.010)]]


def test_module_runs_wholly_inside_the_window():
    red = trace_reduce.reduce(hand_made())
    assert trace_reduce.count_modules(red, "delta_decode_pallas") == 1
    assert trace_reduce.count_modules(red, "delta_encode_pallas") == 0


def test_without_a_window_mark_the_device_span_is_the_window():
    tr = hand_made()
    tr["host"] = []
    red = trace_reduce.reduce(tr)
    assert red["window_s"] == pytest.approx(0.125)
    with pytest.raises(ValueError):
        trace_reduce.reduce({"host": [], "device": [], "modules": []})


def test_clock_marks_put_device_events_on_the_host_clock():
    off = 3_000 * MS
    host = [[trace_reduce.MARK, 10 * MS, 1 * MS],
            [trace_reduce.MARK, 90 * MS, 1 * MS]]
    modules = [["jit_bench_clock_mark(7)", off + 10 * MS + 200_000, 1000],
               ["jit_bench_clock_mark(7)", off + 90 * MS + 300_000, 1000],
               ["jit_delta_decode_pallas(1)", off + 50 * MS, 5 * MS]]
    got = trace_reduce.clock_offset(host, modules)
    assert off + 200_000 <= got <= off + 300_000
    assert trace_reduce.clock_offset([], modules) == 0
    assert trace_reduce.clock_offset(host, modules[:1]) == 0


def sweep_busy_ns(device, w0, w1) -> int:
    """Busy time by a sweep over start and end events, an independent
    way to the same union."""
    edges = []
    for _, s, d in device:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            edges += [(a, 1), (b, -1)]
    busy, depth, last = 0, 0, None
    for t, step in sorted(edges, key=lambda e: (e[0], -e[1])):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_tpu_trace():
    with gzip.open(os.path.join(HERE, "fixtures", "scan_trace.json.gz"),
                   "rt") as f:
        trace = json.load(f)
    red = trace_reduce.reduce(trace)
    (w0, dur), = [(s, d) for n, s, d in trace["host"]
                  if n == trace_reduce.WINDOW]
    assert red["window_s"] == pytest.approx(dur / 1e9)
    busy = sweep_busy_ns(trace["device"], w0, w0 + dur)
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    times = [t for _, t in red["device_ops"]]
    assert times == sorted(times, reverse=True)
    gaps = [t for _, t in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= red["window_s"] - red["busy_s"] + 1e-9
    assert trace_reduce.count_modules(red, "delta_decode_pallas") > 0


def test_kernel_ops_wholly_inside_the_window_are_kept():
    tr = hand_made()
    hlo = ("%delta_decode_pallas.1 = f32[30,3,8,128]{} custom-call("
           "f32[3,8,128]{} %i, s32[29,3,8,128]{} %r)")
    tr["kernels"] = [[hlo, 30 * MS, MS],
                     [hlo, 99 * MS, 5 * MS]]  # runs past the window's end
    red = trace_reduce.reduce(tr)
    assert red["kernels"] == [hlo]
    assert trace_reduce.reduce(hand_made())["kernels"] == []
