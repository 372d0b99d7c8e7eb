"""The tiled ROI cell, ``vroad4k.tiled_roi``, without a chip: the program
against the plain reference on a 2x2-tiled store at rehearsal size, for
``rgb`` boxes inside one tile, across a seam and across the corner; a
tiled store read as an untiled one; the cell rehearsed with a tile
stitched into the wrong place; the loop's kept answers; and the cell's
three per-layer readers on hand-made spans."""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench import gen, reference, run, video
from bench.run import build_store, load_module, open_store, read_spec
from bench.test_faults import fails, rehearse
from bench.test_harness import ROOT

CELL = "vroad4k.tiled_roi"
CFG = {"width": 128, "height": 72, "fps": 6, "gop_frames": 6,
       "codec": "h264", "cameras": 1, "stored_seconds": 3,
       "backend": "local"}
SEED = 3317000023
LOOP = load_module(ROOT, "loops", "closed_kinds")


def reference_store(lay, tiled):
    cam = video.Camera(lay.w, lay.h, video.camera_seed(SEED, 0))
    return reference.Store(lambda c, g: cam.frames(g * lay.gop,
                                                   (g + 1) * lay.gop),
                           fps=lay.fps, gop=lay.gop, tier=lay.codec,
                           tiled=tiled)


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    out = {}
    for name, extra in (("tiled", {"tiles": [2, 2]}), ("plain", {})):
        cfg = dict(CFG, **extra)
        lay = gen.Layout(cfg)
        vss = open_store(str(tmp_path_factory.mktemp(name) / "store"), cfg)
        build_store(vss, lay, SEED)
        out[name] = (vss, lay)
    yield out
    for vss, _ in out.values():
        vss.close()


def clip(lay, f0, n, roi=None):
    req = {"name": "cam0", "t": [f0 / lay.fps, (f0 + n) / lay.fps],
           "codec": "rgb", "cache": False}
    if roi is not None:
        req["roi"] = list(roi)
    return req


# boxes of the kind's two sides (1/4 and 1/2 of 128x72) at grid corners;
# the tiles are 64x36
BOXES = [((32, 18, 64, 36), 1),   # a 1/4 box inside tile (0, 0)
         ((96, 54, 128, 72), 1),  # a 1/4 box inside tile (1, 1)
         ((32, 0, 96, 36), 2),    # a 1/2 box across the vertical seam
         ((0, 18, 64, 54), 2),    # a 1/2 box across the horizontal seam
         ((32, 18, 96, 54), 4)]   # a 1/2 box across the corner


@pytest.mark.parametrize("box,covered", BOXES,
                         ids=[f"{b[0]}-{b[1]}-{n}" for b, n in BOXES])
@pytest.mark.parametrize("f0", [3, 5], ids=["mid", "last"])
def test_rgb_boxes_read_as_the_reference(stores, box, covered, f0):
    vss, lay = stores["tiled"]
    ref = reference_store(lay, tiled=True)
    # a 1-s clip from frame f0 of the first GOP: across its boundary
    req = clip(lay, f0, lay.gop, box)
    assert LOOP.tile_class(lay, req) == covered
    got = vss.read_batch([read_spec(req)])[0].frames
    assert got.shape == (lay.gop, box[3] - box[1], box[2] - box[0], 3)
    assert reference.frames_differing(got, ref.expect(req)) == 0


def test_a_tiled_store_reads_as_an_untiled_one(stores):
    (tiled, lay), (plain, _) = stores["tiled"], stores["plain"]
    ref = reference_store(lay, tiled=False)
    for f0, n in ((0, lay.frames), (4, 7), (7, 1)):
        req = clip(lay, f0, n)
        a = tiled.read_batch([read_spec(req)])[0].frames
        b = plain.read_batch([read_spec(req)])[0].frames
        assert np.array_equal(a, b)
        assert reference.frames_differing(a, ref.expect(req)) == 0


def _misplaced_tile(monkeypatch):
    """In each GOP of a read that covers more than one tile, the first
    two tiles trade places."""
    from repro.core.store import VSS

    real = VSS._stitch

    def stitch(tiles, gops, rows, cols):
        places = [(r, c) for r in rows for c in cols]
        if len(places) > 1:
            for g in gops:
                a, b = ((g.gop_id,) + p for p in places[:2])
                tiles[a], tiles[b] = tiles[b], tiles[a]
        return real(tiles, gops, rows, cols)

    monkeypatch.setattr(VSS, "_stitch", staticmethod(stitch))


def test_a_misplaced_tile_is_not_correct_and_the_control_differs(
        monkeypatch):
    _misplaced_tile(monkeypatch)
    result = rehearse(CELL, ROOT, control=True)
    assert not result["correct"]
    assert fails(result["checks"])
    assert fails(result["control"])


def test_the_kept_answers_cover_every_class_received(monkeypatch):
    outs = []
    real = run.run_loop

    def run_loop(root, r):
        outs.append(real(root, r))
        return outs[-1]

    monkeypatch.setattr(run, "run_loop", run_loop)
    result = rehearse(CELL, ROOT, seed=2 ** 40 + 17)
    assert result["correct"]
    (out,) = outs
    lay = gen.Layout(run.rehearsal_cfg(*_cell_cfg_mix()))
    received = set()
    kept = []
    for seen, classes in out["classes"].values():
        assert classes == sorted(seen)  # one answer of each class
        received |= set(seen)
        kept += classes
    assert len(received) >= 2  # the window held a seam or a corner
    assert sorted(LOOP.tile_class(lay, req) for req, _ in out["answers"]) \
        == sorted(kept)


def _cell_cfg_mix():
    _, _, cfg, mix = run.load_cell(ROOT, CELL)
    return cfg, mix, run.load_kinds(ROOT, mix)


def test_the_loop_gives_the_decode_shape_of_one_tile():
    cfg, _, _ = _cell_cfg_mix()
    assert LOOP.gop_shape(gen.Layout(cfg)) == (30, 1080, 1920, 3)
    assert LOOP.gop_shape(gen.Layout(dict(cfg, tiles=[1, 1]))) == (
        30, 2160, 3840, 3)


def _span(name, t, dur, children=(), **attrs):
    return {"name": name, "t_wall": t, "dur_s": dur, "cpu_s": dur,
            "attrs": attrs, "children": list(children)}


def _read(t, stitch=None):
    kids = [] if stitch is None else [_span("stitch", t + 0.5, stitch[1],
                                            tiles=stitch[0], decoded=0,
                                            bytes=1)]
    return _span("read", t, 1.0, [_span("decode", t + 0.1, 0.8, kids)])


def test_the_tile_readers_on_hand_made_spans():
    tiles = load_module(ROOT, "layers", "tiles_per_read")
    stitch = load_module(ROOT, "layers", "stitch_ms_per_read")
    roofline = load_module(ROOT, "layers", "tile_decode_roofline")
    reads = [_read(0.0, (2, 0.1)), _read(1.0, (4, 0.3)), _read(2.0, (2, 0.2))]
    ctx = SimpleNamespace(spans=reads, trace=None)
    assert tiles.read(ctx) == pytest.approx(8 / 3)
    assert stitch.read(ctx) == pytest.approx(200.0)
    assert roofline.read(ctx) is None  # no device trace
    # a read without a stitch (a program without the stage) counts in the
    # mean; with no stitch at all the readers find nothing
    ctx.spans = reads + [_read(3.0)]
    assert tiles.read(ctx) == pytest.approx(2.0)
    ctx.spans = [_read(0.0), _read(1.0)]
    assert tiles.read(ctx) is None and stitch.read(ctx) is None
    # the roofline: 10 tile decodes in 1.5 s of device time
    peaks = run.load_json(ROOT, "bench", "peaks.json")
    kind = next(iter(peaks["devices"]))
    ctx = SimpleNamespace(
        spans=[], gop_shape=(30, 1080, 1920, 3), device_kind=kind,
        peaks=peaks, trace={"busy_s": 1.5, "window_s": 3.0,
                            "by_module": {"jit_delta_decode_pallas": 10,
                                          "jit_other": 4}})
    plane = 1080 * 1920 * 3
    least = 10 * (plane + 29 * plane + 30 * plane) / peaks["devices"][
        kind]["hbm_bytes_per_s"]
    assert roofline.read(ctx) == pytest.approx(100 * least / 1.5)
    ctx.trace["by_module"] = {"jit_other": 4}
    assert roofline.read(ctx) is None


def test_the_parent_harness_without_the_cell_exits_at_once(tmp_path):
    """The harness of a tree whose ``BENCHMARK.json`` lacks the cell
    refuses it before it touches a store."""
    import json
    import subprocess
    import sys

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
    root = tmp_path / "parent"
    root.mkdir()
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(ROOT, "bench"), root / "bench")
    p = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload", CELL,
         "--seed", "3", "--seconds", "51", "--trace", "0"],
        cwd=str(root), capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and p.stdout == ""
    assert f"no workload {CELL!r}" in p.stderr
