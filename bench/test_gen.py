"""The traffic generator without a chip: the scan's requests pinned from a
fixed seed, the open loop's blocks (shares, gaps, seeds), the requests
of each kind, and the GOP shapes a mix's warm-up enumerates."""
import collections
import itertools
import json
import math
import os

import numpy as np
import pytest

from bench import gen

HERE = os.path.dirname(os.path.abspath(__file__))


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def query():
    mix = load("traffic", "query.json")
    kinds = {e["kind"]: load("kinds", e["kind"] + ".json")
             for e in mix["requests"]}
    return mix, kinds, gen.Layout(load("configs", "vroad-1k.json"))


# the first four requests of each consumer of vroad2k.scan on seed
# 3314000101, as the generator drew them before open-loop traffic was added
SCAN_3314000101 = [
    [("cam3", [2.0, 4.0]), ("cam2", [2.0, 4.0]), ("cam3", [1.0, 3.0]),
     ("cam1", [1.0, 3.0])],
    [("cam0", [5.0, 7.0]), ("cam0", [6.0, 8.0]), ("cam3", [2.0, 4.0]),
     ("cam3", [0.0, 2.0])],
    [("cam2", [0.0, 2.0]), ("cam1", [3.0, 5.0]), ("cam0", [2.0, 4.0]),
     ("cam3", [1.0, 3.0])],
    [("cam1", [3.0, 5.0]), ("cam3", [0.0, 2.0]), ("cam1", [4.0, 6.0]),
     ("cam1", [5.0, 7.0])],
]


def test_the_scans_requests_do_not_move():
    mix = load("traffic", "scan.json")
    lay = gen.Layout(load("configs", "vroad-2k.json"))
    for c, want in enumerate(SCAN_3314000101):
        got = list(itertools.islice(
            gen.closed_requests(mix, lay, 3314000101, c), len(want)))
        assert got == [{"name": n, "t": t, "codec": "rgb", "cache": False}
                       for n, t in want]
    assert gen.warmup_request(mix, lay) == {
        "name": "cam0", "t": [1.0, 3.0], "codec": "rgb", "cache": False}
    assert gen.warmup_gops(mix) == 3


def test_zipf_rank_favours_low_ranks():
    rng = gen.rng_for(1, 0)
    n, s = 8, 1.1
    draws = collections.Counter(gen.zipf_rank(rng, n, s)
                                for _ in range(20000))
    assert set(draws) <= set(range(n))
    w = np.arange(1, n + 1) ** -s
    for k in (0, 1, 7):
        assert draws[k] / 20000 == pytest.approx(w[k] / w.sum(), abs=0.02)
    assert gen.zipf_rank(rng, 1, s) == 0


def schedule(reqs, lay):
    """What sets each request's work and arrival: when it is due, its
    kind, its length and the frame of its GOP at which it starts."""
    out = []
    for due, kind, r in reqs:
        f0, f1 = (round(x * lay.fps) for x in r["t"])
        out.append((due, kind, f1 - f0, f0 % lay.gop))
    return out


def test_every_block_holds_the_same_work_at_the_mixes_rate():
    mix, kinds, lay = query()
    block = mix["block"]
    schedules = []
    for seed in (1, 2 ** 40 + 7):
        reqs = list(itertools.islice(gen.open_requests(mix, kinds, lay,
                                                       seed), 3 * block))
        dues = [d for d, _, _ in reqs]
        assert dues == sorted(dues)
        gaps = np.diff([0.0] + dues)
        for b in range(3):
            part = reqs[b * block:(b + 1) * block]
            count = collections.Counter(k for _, k, _ in part)
            assert count == {e["kind"]: round(e["share"] * block)
                             for e in mix["requests"]}
            trims = collections.Counter(
                round((r["t"][1] - r["t"][0]) * lay.fps)
                for _, k, r in part if k == "trim")
            assert trims == {1: 4, 5: 4, 15: 4, 30: 4}
            assert sorted(gaps[b * block:(b + 1) * block]) == pytest.approx(
                sorted(-math.log(1 - (j + 0.5) / block) / mix["rate_per_s"]
                       for j in range(block)))
        assert dues[block - 1] == pytest.approx(
            sum(gaps[:block]))
        schedules.append(schedule(reqs, lay))
    assert schedules[0] == schedules[1]  # every seed, the same schedule
    first = [r for _, _, r in itertools.islice(
        gen.open_requests(mix, kinds, lay, 5), block)]
    again = [r for _, _, r in itertools.islice(
        gen.open_requests(mix, kinds, lay, 5), block)]
    other = [r for _, _, r in itertools.islice(
        gen.open_requests(mix, kinds, lay, 6), block)]
    assert first == again and first != other


def test_each_kind_asks_what_its_file_says():
    mix, kinds, lay = query()
    seen = collections.defaultdict(list)
    for _, kind, req in itertools.islice(
            gen.open_requests(mix, kinds, lay, 3315000001), 400):
        seen[kind].append(req)
        f0, f1 = (round(x * lay.fps) for x in req["t"])
        assert 0 <= f0 < f1 <= lay.frames
        assert 0 <= int(req["name"][3:]) < lay.cameras
        assert req["cache"] is mix["cache"]
    assert {r["codec"] for r in seen["trim"]} == {"h264"}
    assert all(set(r) == {"name", "t", "codec", "cache"}
               for r in seen["trim"])
    assert {r["codec"] for r in seen["convert"]} == {"hevc"}
    for r in seen["pool"]:
        assert r["codec"] == "lossless" and r["resolution"] == [480, 270]
    boxes = collections.Counter()
    for r in seen["roi"]:
        x0, y0, x1, y1 = r["roi"]
        assert (x1 - x0, y1 - y0) in ((240, 135), (480, 270))
        assert 0 <= x0 and x1 <= lay.w and 0 <= y0 and y1 <= lay.h
        boxes[(x0, y0)] += 1
    assert boxes[(0, 0)] == max(boxes.values())  # the hottest cell
    for reqs in seen.values():
        if reqs[0]["codec"] != "h264" or "roi" in reqs[0]:
            assert {round((r["t"][1] - r["t"][0]) * lay.fps)
                    for r in reqs} == {30}
    starts = [round(r["t"][0] * lay.fps) for rs in seen.values() for r in rs]
    assert len({f % lay.gop for f in starts}) > lay.gop // 2  # any frame
    newest = sum(f >= lay.frames - 2 * lay.gop - 30 for f in starts)
    assert newest > len(starts) / 3  # recent footage is favoured


def test_warm_up_shapes_with_views_off_and_on():
    mix, kinds, lay = query()
    enc, dec = gen.warmup_codec_shapes(mix, kinds, lay)
    assert dec == [("h264", t, 540, 960) for t in range(1, 31)]
    assert set(enc) == {("h264", t, 540, 960) for t in range(1, 31)} | {
        ("h264", 30, 135, 240), ("h264", 30, 270, 480),
        ("lossless", 30, 270, 480), ("hevc", 30, 540, 960)}
    enc, dec = gen.warmup_codec_shapes(dict(mix, cache=True), kinds, lay)
    assert set(dec) >= {("hevc", t, 540, 960) for t in range(1, 31)}
    assert set(dec) >= {("lossless", t, 270, 480) for t in range(1, 31)}
    assert ("h264", 7, 135, 240) in enc and ("h264", 7, 135, 240) in dec
