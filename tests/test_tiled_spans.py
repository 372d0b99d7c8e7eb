"""The tiled read path's ``stitch`` span: a read of a tiled layout
decodes the tile GOPs that cover its box, then joins them in one
``stitch`` stage of its ``decode`` span, whose ``tiles``, ``decoded``
and ``bytes`` count what it placed. Untiled reads have no such stage."""

import numpy as np
import pytest

from repro.core.spec import ReadSpec, WriteSpec
from repro.core.store import VSS

W, H, GOP, FPS = 64, 48, 4, 4.0  # 2x2 tiles of 32x24, one-second GOPs


def spans(node, name):
    if node["name"] == name:
        yield node
    for child in node.get("children", ()):
        yield from spans(child, name)


@pytest.fixture()
def frames():
    rng = np.random.default_rng(17)
    return rng.integers(0, 256, (3 * GOP, H, W, 3), dtype=np.uint8)


def store(tmp_path, frames, tiles):
    vss = VSS(str(tmp_path / "vss"))
    w = vss.writer_spec(WriteSpec(name="v", fps=FPS, codec="h264",
                                  gop_frames=GOP, tiles=tiles))
    w.append(frames)
    w.close()
    return vss


@pytest.mark.parametrize("roi,covered", [
    ((4, 4, 20, 16), 1),    # inside tile (0, 0)
    ((24, 4, 40, 16), 2),   # across the vertical seam
    ((4, 16, 20, 32), 2),   # across the horizontal seam
    ((24, 16, 40, 32), 4),  # across the corner
])
def test_a_tiled_roi_read_leaves_one_stitch_span(tmp_path, frames, roi,
                                                 covered):
    vss = store(tmp_path, frames, (2, 2))
    try:
        # frames 2..5: mid-GOP, across the first GOP boundary
        (res,) = vss.read_batch([ReadSpec(name="v", t=(0.5, 1.5), roi=roi,
                                          codec="rgb", cache=False)])
        (root,) = vss.recent_traces()
    finally:
        vss.close()
    (decode,) = [c for c in root["children"] if c["name"] == "decode"]
    (st,) = list(spans(decode, "stitch"))
    rows = 1 + (roi[1] < H // 2 < roi[3])
    cols = 1 + (roi[0] < W // 2 < roi[2])
    assert rows * cols == covered
    assert st["attrs"]["tiles"] == 2 * covered  # two GOPs
    assert st["attrs"]["decoded"] == 2 * covered
    assert st["attrs"]["bytes"] == 2 * GOP * (rows * H // 2) * (
        cols * W // 2) * 3
    assert decode["attrs"]["gops"] == 2 * covered
    # the stage lies inside its parent, after the decodes it joins
    downloads = list(spans(decode, "download"))
    assert len(downloads) == 2 * covered
    assert all(d["t_wall"] + d["dur_s"] <= st["t_wall"] for d in downloads)
    assert decode["t_wall"] <= st["t_wall"]
    assert st["t_wall"] + st["dur_s"] <= decode["t_wall"] + decode["dur_s"]
    x0, y0, x1, y1 = roi
    assert res.frames.shape == (4, y1 - y0, x1 - x0, 3)


def test_tiles_taken_from_the_batch_are_placed_but_not_decoded(
        tmp_path, frames):
    vss = store(tmp_path, frames, (2, 2))
    try:
        vss.read_batch([
            ReadSpec(name="v", t=(0.0, 1.0), roi=(24, 4, 40, 16),
                     codec="rgb", cache=False),
            ReadSpec(name="v", t=(0.0, 1.0), roi=(36, 4, 52, 16),
                     codec="rgb", cache=False),
        ])
        # roots are recorded as they close, the last spec's first
        first, second = sorted(vss.recent_traces(),
                               key=lambda r: r["read_id"])
    finally:
        vss.close()
    (a,) = list(spans(first, "stitch"))
    (b,) = list(spans(second, "stitch"))
    assert (a["attrs"]["tiles"], a["attrs"]["decoded"]) == (2, 2)
    # the second box lies in tile (0, 1), which the first read decoded
    assert (b["attrs"]["tiles"], b["attrs"]["decoded"]) == (1, 0)
    assert b["attrs"]["bytes"] == GOP * (H // 2) * (W // 2) * 3


@pytest.mark.parametrize("codec", ["rgb", "h264"])
def test_untiled_reads_leave_no_stitch_span(tmp_path, frames, codec):
    vss = store(tmp_path, frames, None)
    try:
        vss.read_batch([
            ReadSpec(name="v", t=(0.5, 1.5), codec=codec, cache=False),
            ReadSpec(name="v", t=(0.0, 2.0), roi=(24, 16, 40, 32),
                     codec=codec, cache=False),
        ])
        roots = vss.recent_traces()
    finally:
        vss.close()
    assert len(roots) == 2
    assert all("decode" in [c["name"] for c in r["children"]] for r in roots)
    assert not [s for r in roots for s in spans(r, "stitch")]


def test_a_tiled_read_returns_the_untiled_frames(tmp_path, frames):
    tiled = store(tmp_path / "tiled", frames, (2, 2))
    plain = store(tmp_path / "plain", frames, None)
    try:
        for roi in (None, (24, 16, 40, 32)):
            spec = ReadSpec(name="v", t=(0.5, 2.5), roi=roi, codec="rgb",
                            cache=False)
            (a,), (b,) = tiled.read_batch([spec]), plain.read_batch([spec])
            assert np.array_equal(a.frames, b.frames)
    finally:
        tiled.close()
        plain.close()
