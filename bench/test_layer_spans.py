"""The per-layer readers of the program's read spans, on span trees whose
answers can be worked out by hand, and on the ``ctx.spans`` of one CPU
rehearsal of ``vroad2k.scan`` (``bench/fixtures/scan_spans.json.gz``:
``python3 bench/run.py --workload vroad2k.scan --cpu-rehearsal --trace 1
--seconds 1 --seed 3313000017``, its spans kept as the readers received
them)."""
import gzip
import json
import math
import os
from types import SimpleNamespace

import pytest

from bench import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MS = 1e-3
READERS = ("fetch_ms_per_gop", "inflate_ms_per_gop", "device_io_ms_per_gop",
           "assemble_ms_per_read", "read_self_ms", "read_offcpu_pct")


def node(name, t_ms, dur_ms, *children, cpu_ms=None, **attrs):
    d = {"name": name, "t_wall": 1000.0 + t_ms * MS, "dur_s": dur_ms * MS}
    if cpu_ms is not None:
        d["cpu_s"] = cpu_ms * MS
    if attrs:
        d["attrs"] = attrs
    if children:
        d["children"] = list(children)
    return d


def read(name, spans):
    return run.read_layer(ROOT, name, SimpleNamespace(spans=spans))


def gop(t, inflate, upload, download):
    return [node("inflate", t, inflate), node("upload", t + inflate, upload),
            node("download", t + inflate + upload, download)]


def batch_of_two():
    """Two reads of one batch: a shared group plan and fetch (one
    interval, recorded on both), then each read's decode, the second
    with an inline fetch of an object the group did not fetch."""
    plan = dict(name="plan", t_ms=1, dur_ms=1)
    fetch = dict(t_ms=2, dur_ms=4)
    a = node("read", 0, 30,
             node(**plan), node("fetch", **fetch, objects=2),
             node("decode", 6, 10, *gop(6, 2, 1, 5),
                  node("assemble", 14, 1), node("touch", 15, 1)),
             cpu_ms=6)
    b = node("read", 0, 30,
             node(**plan), node("fetch", **fetch, objects=1),
             node("decode", 16, 12,
                  node("fetch", 16, 2, inline=True, objects=1),
                  *gop(18, 3, 1, 3),
                  node("assemble", 25, 2), node("assemble", 27, 1)),
             cpu_ms=18)
    return [a, b]


def test_fetch_counts_a_shared_interval_once():
    # 4 ms of group fetch for 3 objects, 2 ms inline for 1
    assert read("fetch_ms_per_gop", batch_of_two()) == pytest.approx(1.5)


def test_inflate_is_the_mean_per_gop():
    assert read("inflate_ms_per_gop", batch_of_two()) == pytest.approx(2.5)


def test_device_io_is_upload_and_download_per_gop():
    # (1 + 5) + (1 + 3) ms over two GOPs
    assert read("device_io_ms_per_gop", batch_of_two()) == pytest.approx(5.0)


def test_assemble_is_per_read():
    assert read("assemble_ms_per_read", batch_of_two()) == pytest.approx(2.0)


def test_read_self_time_is_what_no_child_covers():
    overlapping = node("read", 0, 10,
                       node("plan", 1, 3), node("decode", 3, 3),
                       node("touch", 4, 1),  # inside another child
                       node("admit", 8, 4))  # ends after the root: clipped
    # covered: [1, 6) and [8, 10) -> 7 of 10 ms; a read with no children
    # is all self time
    bare = node("read", 0, 5)
    assert read("read_self_ms", [overlapping, bare]) == pytest.approx(4.0)
    # the batch: 30 ms each; [1, 16) and [1, 2) + [2, 6) + [16, 28)
    assert read("read_self_ms", batch_of_two()) == pytest.approx(
        ((30 - 15) + (30 - 17)) / 2)


def test_offcpu_share_weights_reads_by_their_wall_time():
    assert read("read_offcpu_pct", batch_of_two()) == pytest.approx(
        100 * (1 - 24 / 60))


def test_offcpu_needs_the_cpu_time():
    # a tree without ``cpu_s`` gives no number, and no error
    assert read("read_offcpu_pct", [node("read", 0, 10)]) is None


@pytest.mark.parametrize("name", READERS)
def test_no_spans_give_no_number(name):
    assert read(name, []) is None


@pytest.mark.parametrize("name", READERS[:4])
def test_reads_without_the_stage_give_no_number(name):
    # the tree a store without the stage's span leaves
    assert read(name, [node("read", 0, 10, node("plan", 1, 1),
                            cpu_ms=2)]) is None


@pytest.fixture(scope="module")
def scan_spans():
    with gzip.open(os.path.join(HERE, "fixtures", "scan_spans.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_scan_holds_the_read_tree(scan_spans):
    assert len(scan_spans) == 182
    for root in scan_spans:
        assert root["name"] == "read"
        names = [c["name"] for c in root["children"]]
        assert names == ["plan", "decode"]
        decode = root["children"][1]
        stages = [c["name"] for c in decode["children"]]
        gops = decode["attrs"]["gops"]
        assert stages == (["fetch"] + ["inflate", "upload", "download"] * gops
                          + ["assemble", "touch", "assemble"])


# each reader on the recorded scan, as the reader computed it when the
# fixture was recorded
RECORDED = {
    "fetch_ms_per_gop": 0.3114079423076923,
    "inflate_ms_per_gop": 1.1992227307692307,
    "device_io_ms_per_gop": 3.8678590054945055,
    "assemble_ms_per_read": 0.5209294615384615,
    "read_self_ms": 2.0776975619573905,
    "read_offcpu_pct": 74.44889373554689,
}


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_recorded_scan(name, scan_spans):
    got = read(name, scan_spans)
    assert math.isfinite(got) and got > 0
    assert got == pytest.approx(RECORDED[name], rel=1e-12)


def test_recorded_scan_self_time_is_a_small_part_of_a_read(scan_spans):
    mean_read_ms = 1e3 * sum(r["dur_s"] for r in scan_spans) / len(scan_spans)
    assert read("read_self_ms", scan_spans) < 0.2 * mean_read_ms
    assert 0 < read("read_offcpu_pct", scan_spans) < 100


# -- the served cell's readers ------------------------------------------------

SERVED = ("serve_wait_ms", "plan_ms_per_read", "execute_ms_per_read",
          "coalesce_width")


def served(spans, client_ms=None, coalesce=(0.0, 0)):
    return SimpleNamespace(spans=spans, client_ms=client_ms or [],
                           coalesce=coalesce)


def read_served(name, ctx):
    return run.read_layer(ROOT, name, ctx)


def test_serve_wait_is_client_time_beyond_the_read():
    # two requests of one batch (30 ms roots) took 50 and 70 ms at the
    # client: 30 ms each outside the read
    ctx = served(batch_of_two(), client_ms=[50.0, 70.0])
    assert read_served("serve_wait_ms", ctx) == pytest.approx(30.0)


def test_plan_and_execute_are_per_read():
    ctx = served(batch_of_two())
    # the group's plan (1 ms) on both reads; decodes of 10 and 12 ms
    assert read_served("plan_ms_per_read", ctx) == pytest.approx(1.0)
    assert read_served("execute_ms_per_read", ctx) == pytest.approx(11.0)


def test_coalesce_width_is_requests_per_batch():
    ctx = served([], coalesce=(10.0, 4))
    assert read_served("coalesce_width", ctx) == pytest.approx(2.5)


@pytest.mark.parametrize("name", SERVED)
def test_served_readers_without_their_input_give_no_number(name):
    assert read_served(name, served([])) is None
    bare = [node("read", 0, 10)]
    if name != "coalesce_width":
        assert read_served(name, served(bare)) is None


@pytest.fixture(scope="module")
def query_spans():
    with gzip.open(os.path.join(HERE, "fixtures", "query_spans.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_query_holds_one_read_tree_per_request(query_spans):
    spans = query_spans["spans"]
    assert len(spans) == len(query_spans["client_ms"]) == 40
    for root in spans:
        names = [c["name"] for c in root["children"]]
        assert names in (["plan", "decode"], ["plan", "fetch", "decode"])
    assert query_spans["coalesce"] == [40.0, 28]


# each reader on the recorded query (``python3 bench/run.py --workload
# vroad1k.query --cpu-rehearsal --trace 1 --seconds 1 --seed 3315000017``,
# the spans, client times and coalescer counts kept as the readers
# received them), as the reader computed it when the fixture was recorded
RECORDED_QUERY = {
    "serve_wait_ms": 96.49997993095398,
    "plan_ms_per_read": 0.4210076,
    "execute_ms_per_read": 13.8386544,
    "coalesce_width": 1.4285714285714286,
}


@pytest.mark.parametrize("name", SERVED)
def test_served_readers_on_a_recorded_query(name, query_spans):
    ctx = served(query_spans["spans"], query_spans["client_ms"],
                 tuple(query_spans["coalesce"]))
    got = read_served(name, ctx)
    assert math.isfinite(got) and got > 0
    assert got == pytest.approx(RECORDED_QUERY[name], rel=1e-12)
