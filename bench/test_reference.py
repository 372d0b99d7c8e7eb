"""The plain reference against the program, at CPU rehearsal size: for
every request kind of the served mix, and for stride, crops in ``rgb``
and other tiers, each answer serialized as the service serves it and read
back by the reference's own parser. Also the parser and the box pool on
inputs whose answers can be worked out by hand."""
import itertools
import json
import os
import zlib

import numpy as np
import pytest

from bench import gen, reference, video
from bench.run import build_store, open_store, read_spec

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = {"width": 128, "height": 72, "fps": 6, "gop_frames": 6,
       "codec": "h264", "cameras": 1, "stored_seconds": 6,
       "backend": "local"}
SEED = 3315000023


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    lay = gen.Layout(CFG)
    vss = open_store(str(tmp_path_factory.mktemp("ref") / "store"), CFG)
    build_store(vss, lay, SEED)
    cam = video.Camera(lay.w, lay.h, video.camera_seed(SEED, 0))
    ref = reference.Store(lambda c, g: cam.frames(g * lay.gop,
                                                  (g + 1) * lay.gop),
                          fps=lay.fps, gop=lay.gop, tier=lay.codec)
    yield vss, ref, lay
    vss.close()


def served(result):
    """The answer as the service serves it: serialized GOPs."""
    from repro import codec

    encoded = result.encoded if result.encoded is not None else [
        codec.encode_gop(chunk, result.codec)
        for _, chunk in codec.split_into_gops(result.frames, result.codec)]
    return [codec.serialize_gop(e) for e in encoded]


def kinds_of_the_mix():
    with open(os.path.join(HERE, "traffic", "query.json")) as f:
        mix = json.load(f)
    out = []
    for e in mix["requests"]:
        with open(os.path.join(HERE, "kinds", e["kind"] + ".json")) as f:
            kind = json.load(f)
        out += [(e["kind"], kind, v) for v in gen.variants(kind)]
    return mix, out


MIX, KINDS = kinds_of_the_mix()


@pytest.mark.parametrize("name,kind,var", KINDS, ids=[
    f"{n}-{v['frames'] or ''}{v['side'] or ''}" for n, _, v in KINDS])
def test_the_reference_reads_as_the_program_serves(store, name, kind, var):
    vss, ref, lay = store
    rng = gen.rng_for(SEED, 3, len(name))
    reqs = [gen.kind_request(kind, var, lay, rng, dict(MIX, cache=False))
            for _ in range(4)]
    # every start in a GOP, and the last frames of the store
    n = gen.kind_frames(kind, var, lay)
    reqs += [dict(reqs[0], t=[f0 / lay.fps, (f0 + n) / lay.fps])
             for f0 in (0, lay.gop - 1, lay.frames - n)]
    for req in reqs:
        got = reference.decode_segments(served(
            vss.read_batch([read_spec(req)])[0]))
        assert reference.frames_differing(got, ref.expect(req)) == 0, req


def test_a_coalesced_batch_reads_as_single_reads(store):
    vss, ref, lay = store
    rng = gen.rng_for(SEED, 4)
    reqs = [gen.kind_request(k, v, lay, rng, dict(MIX, cache=False))
            for _, k, v in KINDS]
    for req, res in zip(reqs, vss.read_batch([read_spec(r) for r in reqs])):
        got = reference.decode_segments(served(res))
        assert reference.frames_differing(got, ref.expect(req)) == 0, req


@pytest.mark.parametrize("req", [
    {"fps": 3, "codec": "rgb"},
    {"fps": 2, "codec": "h264", "roi": [16, 8, 80, 44]},
    {"codec": "rgb", "roi": [5, 7, 70, 40]},
    {"codec": "tvc-lo", "resolution": [32, 18]},
    {"codec": "rgb", "resolution": [64, 36], "roi": [0, 0, 128, 72]},
    {"codec": "lossless"},
], ids=["stride", "stride-crop", "odd-crop", "pool4-lo", "pool-rgb",
        "lossless"])
def test_stride_crops_and_tiers_read_as_the_program_serves(store, req):
    vss, ref, lay = store
    for f0, n in ((0, 12), (5, 13), (lay.frames - 12, 12)):
        r = dict(req, name="cam0", t=[f0 / lay.fps, (f0 + n) / lay.fps],
                 cache=False)
        got = reference.decode_segments(served(
            vss.read_batch([read_spec(r)])[0]))
        assert reference.frames_differing(got, ref.expect(r)) == 0, r


def test_a_tiled_store_reads_as_the_program_serves(tmp_path):
    # tile objects are never served as they are: a trim in the stored
    # tier is encoded anew from its first frame, not edge GOP by edge GOP
    cfg = dict(CFG, tiles=[2, 2])
    lay = gen.Layout(cfg)
    vss = open_store(str(tmp_path / "store"), cfg)
    try:
        build_store(vss, lay, SEED)
        cam = video.Camera(lay.w, lay.h, video.camera_seed(SEED, 0))
        ref = reference.Store(lambda c, g: cam.frames(g * lay.gop,
                                                      (g + 1) * lay.gop),
                              fps=lay.fps, gop=lay.gop, tier=lay.codec,
                              tiled=True)
        for f0, n, extra in ((5, 5, {}), (2, 12, {}), (0, 6, {}),
                             (3, 7, {"roi": [64, 0, 128, 36]})):
            r = dict(extra, name="cam0", codec="h264", cache=False,
                     t=[f0 / lay.fps, (f0 + n) / lay.fps])
            got = reference.decode_segments(served(
                vss.read_batch([read_spec(r)])[0]))
            assert reference.frames_differing(got, ref.expect(r)) == 0, r
    finally:
        vss.close()


def test_the_control_differs_from_the_program(store):
    vss, ref, lay = store
    ctrl = reference.Store(ref.source, fps=lay.fps, gop=lay.gop,
                           tier=lay.codec, shift=1)
    rng = gen.rng_for(SEED, 9)
    for _, kind, var in KINDS:
        reqs = [gen.kind_request(kind, var, lay, rng, MIX) for _ in range(4)]
        # a one-frame answer at a GOP's start is its I-frame, which every
        # tier stores alike; any longer one differs
        assert sum(reference.frames_differing(ctrl.expect(r), ref.expect(r))
                   for r in reqs) > 0, reqs


def test_segment_parser_on_hand_made_segments():
    frames = np.arange(2 * 2 * 4 * 3, dtype=np.uint8).reshape(2, 2, 4, 3)
    header = json.dumps({"codec": "rgb", "shape": [2, 2, 4, 3]}).encode()
    seg = b"TVC1" + len(header).to_bytes(4, "little") + header + \
        frames.tobytes()
    codec, got = reference.decode_segment(seg)
    assert codec == "rgb" and np.array_equal(got, frames)
    # a tier: the I-frame, then residual planes that step by q = 8
    iframe = np.full((3, 2, 4), 100, np.uint8)
    resid = np.array([[[[1] * 4] * 2] * 3, [[[-2] * 4] * 2] * 3], np.int8)
    chunks = [zlib.compress(iframe.tobytes())] + [
        zlib.compress(r.tobytes()) for r in resid]
    offsets = list(itertools.accumulate([0] + [len(c) for c in chunks]))
    header = json.dumps({"codec": "tvc-med", "shape": [3, 2, 4, 3],
                         "offsets": offsets}).encode()
    seg = b"TVC2" + len(header).to_bytes(4, "little") + header + \
        b"".join(chunks)
    codec, got = reference.decode_segment(seg)
    assert codec == "tvc-med" and got.shape == (3, 2, 4, 3)
    assert [int(got[t, 0, 0, 0]) for t in range(3)] == [100, 108, 92]
    with pytest.raises(ValueError):
        reference.decode_segment(b"TVC9" + seg[4:])
    with pytest.raises(ValueError):  # one chunk short
        reference.decode_segment(seg[:-len(chunks[-1])])


def test_box_pool_rounds_half_to_even():
    frames = np.array([1, 2, 2, 2, 3, 3, 3, 3, 0, 0, 1, 1, 5, 6, 6, 6],
                      np.uint8).reshape(1, 2, 8, 1)
    got = reference.box_pool(frames, 4, 1)
    # 2x2 boxes: means 0.75 -> 1, 1.5 -> 2, 4.25 -> 4, 4.5 -> 4
    assert got.ravel().tolist() == [1, 2, 4, 4]
    with pytest.raises(ValueError):
        reference.box_pool(frames, 3, 1)
