"""The host inflate of a TVC GOP (`tvc.gop_planes`): each per-frame chunk
inflates straight into its slot of one planes buffer, bit for bit what a
plain inflate-join-frombuffer gives, on every tier, GOP length, entropy
codec and payload format; a chunk of the wrong size is refused; and
``vss_codec_inflate_total`` counts each GOP on the path it took."""

import zlib

import numpy as np
import pytest

from repro.codec import tvc
from repro.obs import default_registry

H, W, C = 5, 7, 3  # an odd plane size: int16 residuals start unaligned
ISZ = H * W * C

needs_zstd = pytest.mark.skipif(tvc.zstandard is None,
                                reason="zstandard wheel not installed")
ENTROPY = [pytest.param("zstd", marks=needs_zstd), "zlib"]


def _frames(t, seed=0, h=H, w=W):
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 256, (1, h, w, C))
    drift = rng.integers(-6, 7, (t, h, w, C)).cumsum(axis=0)
    return np.clip(base + drift, 0, 255).astype(np.uint8)


def _encode(frames, codec, entropy, monkeypatch):
    """Encode with zstd, or with zlib as a host without the wheel does."""
    if entropy == "zlib":
        with monkeypatch.context() as m:
            m.setattr(tvc, "zstandard", None)
            return tvc.encode_gop(frames, codec)
    return tvc.encode_gop(frames, codec)


def _plain_inflate(chunk):
    if chunk[:4] == b"\x28\xb5\x2f\xfd":
        return tvc.zstandard.ZstdDecompressor().decompress(chunk)
    return zlib.decompress(chunk)


def _plain_planes(enc):
    """Inflate each chunk, join, and ``np.frombuffer`` the result."""
    t, h, w, c = enc.shape
    off = enc.offsets
    if off is None:
        raw = _plain_inflate(enc.payload)
    else:
        raw = b"".join(_plain_inflate(enc.payload[off[i]:off[i + 1]])
                       for i in range(t))
    isz = h * w * c
    dtype = tvc.TIERS[enc.codec].resid_dtype
    return (np.frombuffer(raw[:isz], np.uint8).reshape(c, h, w),
            np.frombuffer(raw[isz:], dtype).reshape(t - 1, c, h, w))


def _assert_same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _legacy(enc):
    """The same GOP as one single-stream (TVC1) payload."""
    raw = b"".join(_plain_planes(enc)[k].tobytes() for k in (0, 1))
    payload = tvc._zstd(raw, tvc.TIERS[enc.codec].zstd_level)
    return tvc.EncodedGOP(enc.codec, enc.shape, payload, None)


def _join(enc, chunks):
    offsets = [0]
    for ch in chunks:
        offsets.append(offsets[-1] + len(ch))
    return tvc.EncodedGOP(enc.codec, enc.shape, b"".join(chunks),
                          tuple(offsets))


@pytest.mark.parametrize("entropy", ENTROPY)
@pytest.mark.parametrize("t", [1, 2, 30])
@pytest.mark.parametrize("codec", ["tvc-ll", "tvc-med", "tvc-lo"])
def test_planes_equal_a_plain_inflate(codec, t, entropy, monkeypatch):
    enc = _encode(_frames(t), codec, entropy, monkeypatch)
    _assert_same(tvc.gop_planes(enc), _plain_planes(enc))


@pytest.mark.parametrize("entropy", ENTROPY)
@pytest.mark.parametrize("hi", [1, 2, 17])
@pytest.mark.parametrize("codec", ["tvc-ll", "tvc-med"])
def test_prefix_planes_equal_a_plain_inflate(codec, hi, entropy,
                                             monkeypatch):
    enc = tvc.prefix_gop(_encode(_frames(30), codec, entropy, monkeypatch),
                         hi)
    _assert_same(tvc.gop_planes(enc), _plain_planes(enc))


@pytest.mark.parametrize("t", [1, 30])
@pytest.mark.parametrize("codec", ["tvc-ll", "tvc-med", "tvc-lo"])
def test_legacy_stream_planes_equal_a_plain_inflate(codec, t):
    enc = _legacy(tvc.encode_gop(_frames(t), codec))
    _assert_same(tvc.gop_planes(enc), _plain_planes(enc))


@pytest.mark.parametrize("entropy", ENTROPY)
@pytest.mark.parametrize("codec,dtype", [("tvc-ll", np.int16),
                                         ("tvc-med", np.int8)])
def test_iframe_and_residuals_are_views_of_one_buffer(codec, dtype, entropy,
                                                      monkeypatch):
    t = 4
    iframe, resid = tvc.gop_planes(
        _encode(_frames(t), codec, entropy, monkeypatch))
    assert iframe.dtype == np.uint8 and iframe.shape == (C, H, W)
    assert resid.dtype == dtype and resid.shape == (t - 1, C, H, W)
    buf = iframe.base
    assert resid.base is buf and buf.nbytes == iframe.nbytes + resid.nbytes
    assert np.shares_memory(buf, iframe) and np.shares_memory(buf, resid)
    # the residuals' slots follow the I-frame's
    addr = resid.__array_interface__["data"][0]
    assert addr == iframe.__array_interface__["data"][0] + ISZ


@pytest.mark.parametrize("entropy", ENTROPY)
def test_lossless_decode_is_exact_from_unaligned_residuals(entropy,
                                                           monkeypatch):
    frames = _frames(6, seed=3)
    enc = _encode(frames, "tvc-ll", entropy, monkeypatch)
    np.testing.assert_array_equal(tvc.decode_gop(enc), frames)


@pytest.mark.parametrize("entropy", ENTROPY)
@pytest.mark.parametrize("fault", ["smaller_frame", "larger_frame",
                                   "truncated", "extra_chunk",
                                   "missing_chunk"])
def test_a_chunk_of_the_wrong_size_is_refused(fault, entropy, monkeypatch):
    enc = _encode(_frames(3), "tvc-med", entropy, monkeypatch)
    off = enc.offsets
    if fault in ("extra_chunk", "missing_chunk"):
        # the offsets table must bracket exactly one chunk per frame
        chunks = [enc.payload[off[i]:off[i + 1]] for i in range(3)]
        chunks = (chunks + chunks[-1:] if fault == "extra_chunk"
                  else chunks[:-1])
        with pytest.raises(ValueError):
            tvc.gop_planes(_join(enc, chunks))
        return
    if fault == "truncated":
        chunk = enc.payload[off[1]:off[2] - 3]
    else:
        h, w = (H - 1, W) if fault == "smaller_frame" else (H + 1, W)
        other = _encode(_frames(3, seed=1, h=h, w=w), "tvc-med", entropy,
                        monkeypatch)
        chunk = other.payload[other.offsets[1]:other.offsets[2]]
    bad = _join(enc, [enc.payload[off[0]:off[1]], chunk,
                      enc.payload[off[2]:off[3]]])
    if fault == "truncated" and entropy == "zlib":
        with pytest.raises(zlib.error):  # zlib refuses a cut stream itself
            tvc.gop_planes(bad)
        return
    with pytest.raises(ValueError):
        tvc.gop_planes(bad)


def _inflates():
    reg = default_registry()
    return {p: reg.value(tvc.INFLATE_METRIC, {"path": p})
            for p in ("into", "copy", "stream")}


@pytest.mark.parametrize("path", [pytest.param("into", marks=needs_zstd),
                                  "copy", "stream"])
def test_inflate_counter_counts_each_gop_on_its_path(path, monkeypatch):
    enc = _encode(_frames(3), "tvc-med",
                  "zstd" if path == "into" else "zlib", monkeypatch)
    if path == "stream":
        enc = _legacy(enc)
    before = _inflates()
    for _ in range(2):
        tvc.gop_planes(enc)
    after = _inflates()
    assert {p: after[p] - before[p] for p in after} == {
        p: (2.0 if p == path else 0.0) for p in after}


@pytest.mark.parametrize("entropy", ENTROPY)
def test_validation_refuses_what_the_read_path_refuses(entropy, monkeypatch):
    """`validate_gop_bytes` inflates through the read path's own checks:
    a lossless GOP whose I-frame and residual chunks are swapped has the
    right total size but frames of the wrong size, and is refused."""
    from repro import codec
    from repro.storage import validate_gop_bytes

    enc = _encode(_frames(2), "tvc-ll", entropy, monkeypatch)
    off = enc.offsets
    swapped = _join(enc, [enc.payload[off[1]:off[2]],
                          enc.payload[off[0]:off[1]]])
    assert validate_gop_bytes(codec.serialize_gop(enc))
    with pytest.raises(ValueError):
        tvc.gop_planes(swapped)
    assert not validate_gop_bytes(codec.serialize_gop(swapped))
