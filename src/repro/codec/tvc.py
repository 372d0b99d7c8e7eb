"""TVC — the TPU-native tensor video codec.

H264/HEVC/NVENC have no TPU analogue, so VSS-on-TPU ships its own codec
that preserves every structural property the paper's storage manager
exploits:

  * GOPs are independently decodable (no cross-GOP references),
  * within a GOP, frame 0 is an I-frame (independent frame, set A) and
    frames 1.. are closed-loop-quantized temporal residuals (dependent
    frames Δ−A) — decoding frame t requires the look-back chain, which
    is what the paper's look-back cost c_l models,
  * quality tiers trade bitrate for PSNR (like codec CRF levels),
  * transform+quantize runs on-device (Pallas kernels); the entropy
    stage (zstd over the quantized residual planes) runs host-side,
    exactly where NVENC's CABAC would sit.

Tiers (residual quantization step q; PSNR is re-encode quality for
uint8 payloads, MSE ≈ q²/12):

  tvc-ll  q=1,  int16 residuals  → lossless               (alias: "lossless")
  tvc-hi  q=2,  int8             → ≈53 dB                 (alias: "hevc")
  tvc-med q=8,  int8             → ≈41 dB (τ boundary)    (alias: "h264")
  tvc-lo  q=24, int8             → ≈31 dB (near-lossless)

The aliases let the paper's experiments ("read H264 as HEVC") be written
verbatim against this store.
"""
from __future__ import annotations

import dataclasses
import json
import zlib
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

try:  # optional: the entropy stage prefers zstd, falls back to zlib
    import zstandard
except ImportError:  # pragma: no cover - environment-dependent
    zstandard = None

from repro.kernels import ops
from repro.obs import LabelledCounter, Tracer

RGB = "rgb"  # raw uncompressed uint8 frames


@dataclasses.dataclass(frozen=True)
class Tier:
    name: str
    q: float
    resid_bits: int  # 8 or 16
    zstd_level: int

    @property
    def lo(self) -> int:
        return -(2 ** (self.resid_bits - 1))

    @property
    def hi(self) -> int:
        return 2 ** (self.resid_bits - 1) - 1

    @property
    def resid_dtype(self):
        return np.int16 if self.resid_bits == 16 else np.int8


TIERS = {
    "tvc-ll": Tier("tvc-ll", q=1.0, resid_bits=16, zstd_level=3),
    "tvc-hi": Tier("tvc-hi", q=2.0, resid_bits=8, zstd_level=3),
    "tvc-med": Tier("tvc-med", q=8.0, resid_bits=8, zstd_level=3),
    "tvc-lo": Tier("tvc-lo", q=24.0, resid_bits=8, zstd_level=3),
}

CODEC_ALIASES = {
    "lossless": "tvc-ll",
    "hevc": "tvc-hi",
    "h264": "tvc-med",
    "raw": RGB,
}

VMIN, VMAX = 0.0, 255.0  # uint8 payload dynamic range


def canonical_codec(name: str) -> str:
    name = name.lower()
    name = CODEC_ALIASES.get(name, name)
    if name != RGB and name not in TIERS:
        raise ValueError(f"unknown codec {name!r}")
    return name


def is_compressed_codec(name: str) -> bool:
    return canonical_codec(name) != RGB


@dataclasses.dataclass
class EncodedGOP:
    """One independently-decodable unit, ready for (de)serialization."""

    codec: str  # canonical codec name
    shape: Tuple[int, int, int, int]  # (T, H, W, C)
    payload: bytes  # per-frame zstd chunks (TVC, see offsets) / raw (RGB)
    # cumulative payload byte offsets of the T per-frame chunks (unit 0 =
    # the compressed I-frame, unit i = frame i's compressed residual), so
    # offsets[i] .. offsets[i+1] brackets frame i and a payload *prefix*
    # [0, offsets[hi]) decodes frames [0, hi).  None for RGB (offsets are
    # analytic: i*H*W*C) and for legacy single-stream TVC1 payloads.
    offsets: Optional[Tuple[int, ...]] = None

    @property
    def num_frames(self) -> int:
        return self.shape[0]

    @property
    def nbytes(self) -> int:
        return len(self.payload)

    @property
    def pixels(self) -> int:
        t, h, w, c = self.shape
        return t * h * w * c

    @property
    def mbpp(self) -> float:
        """Mean bits per pixel — the paper's compression-error predictor."""
        return 8.0 * self.nbytes / max(self.pixels, 1)


# zstd frames open with a fixed magic; a zlib stream's 2-byte header
# (CMF/FLG) can never alias it because 0x28,0xB5 fails zlib's FCHECK —
# so the payload itself flags which entropy codec produced it and mixed
# environments (written with the wheel, read without, or vice versa)
# round-trip.
_ZSTD_FRAME_MAGIC = b"\x28\xb5\x2f\xfd"


def _zstd(data: bytes, level: int) -> bytes:
    if zstandard is not None:
        return zstandard.ZstdCompressor(level=level).compress(data)
    return zlib.compress(data, min(max(level, 1), 9))


def _zstd_decompressor():
    if zstandard is None:
        raise RuntimeError(
            "GOP payload is zstd-compressed but the zstandard wheel"
            " is not installed"
        )
    return zstandard.ZstdDecompressor()


def _unzstd(data: bytes) -> bytes:
    if data[:4] == _ZSTD_FRAME_MAGIC:
        return _zstd_decompressor().decompress(data)
    return zlib.decompress(data)


def encode_gop(
    frames: np.ndarray,  # (T, H, W, C) uint8
    codec: str,
    *,
    use_pallas: Optional[bool] = None,
) -> EncodedGOP:
    codec = canonical_codec(codec)
    frames = np.asarray(frames, dtype=np.uint8)
    t, h, w, c = frames.shape
    if codec == RGB:
        return EncodedGOP(RGB, (t, h, w, c), frames.tobytes())
    tier = TIERS[codec]
    planar = ops.to_planar(jnp.asarray(frames))  # (T, C, H, W) f32
    if t == 1:
        iframe = np.asarray(planar[0], dtype=np.float32)
        resid = np.zeros((0, c, h, w), tier.resid_dtype)
    else:
        ifr, res = ops.delta_encode(
            planar, q=tier.q, lo=tier.lo, hi=tier.hi, vmin=VMIN, vmax=VMAX,
            use_pallas=use_pallas,
        )
        # narrow on the device: the residuals are clipped to the tier's
        # range, so only a quarter of the int32 bytes cross to the host
        iframe = np.asarray(ifr, dtype=np.float32)
        resid = np.asarray(res.astype(tier.resid_dtype))
    # one independently-compressed chunk per frame (I-frame, then each
    # residual): a payload prefix [0, offsets[hi]) decodes frames
    # [0, hi) without touching — or even fetching — the rest
    return _chunked_gop(codec, (t, h, w, c),
                        iframe.astype(np.uint8), resid, tier)


def _chunked_gop(
    codec: str,
    shape: Tuple[int, int, int, int],
    iframe_u8: np.ndarray,
    resid: np.ndarray,
    tier: Tier,
) -> EncodedGOP:
    chunks = [_zstd(iframe_u8.tobytes(), tier.zstd_level)]
    chunks.extend(
        _zstd(resid[i].tobytes(), tier.zstd_level)
        for i in range(resid.shape[0])
    )
    offsets = [0]
    for ch in chunks:
        offsets.append(offsets[-1] + len(ch))
    return EncodedGOP(codec, shape, b"".join(chunks), tuple(offsets))


def prefix_gop(enc: EncodedGOP, hi: int) -> EncodedGOP:
    """The sub-GOP holding frames [0, hi) of ``enc``, sliced without any
    decode work.  Requires a random-access payload (RGB, or a chunked
    TVC payload with offsets); raises ValueError otherwise."""
    t, h, w, c = enc.shape
    if not 0 < hi <= t:
        raise ValueError(f"prefix [0,{hi}) outside GOP of {t} frames")
    if hi == t:
        return enc
    if enc.codec == RGB:
        return EncodedGOP(RGB, (hi, h, w, c), enc.payload[: hi * h * w * c])
    if enc.offsets is None:
        raise ValueError("legacy single-stream payload has no offsets")
    return EncodedGOP(enc.codec, (hi, h, w, c),
                      enc.payload[: enc.offsets[hi]], enc.offsets[: hi + 1])


INFLATE_METRIC = "vss_codec_inflate_total"

_inflates = LabelledCounter(
    INFLATE_METRIC,
    "TVC GOPs inflated on the host, by path: into (zstd into the planes"
    " buffer), copy (zlib, one copy), stream (legacy single stream)",
    ("path",),
)


def _read_into(reader, slot: memoryview) -> None:
    """Fill ``slot`` from a zstd stream reader that must end there."""
    n, got = len(slot), 0
    while got < n:
        k = reader.readinto(slot[got:])
        if not k:
            raise ValueError(
                f"chunk inflates to {got} bytes, its frame has {n}")
        got += k
    if reader.read(1):
        raise ValueError(f"chunk inflates past its frame's {n} bytes")


def inflate_planes(enc: EncodedGOP) -> Tuple[np.ndarray, str]:
    """The decompressed ``iframe_u8 ++ residuals`` of a TVC GOP as one
    uint8 buffer, and the path that made it: ``into`` (zstd chunks
    inflated straight into their slots), ``copy`` (zlib chunks, one copy
    each) or ``stream`` (a legacy single-stream payload).

    Raises ValueError unless the payload inflates to exactly the planes
    its shape gives, chunk by chunk; a corrupt stream raises its codec's
    own error."""
    t, h, w, c = enc.shape
    isz = h * w * c
    rsz = isz * np.dtype(TIERS[enc.codec].resid_dtype).itemsize
    size = isz + (t - 1) * rsz
    if enc.offsets is None:
        buf = np.frombuffer(_unzstd(enc.payload), np.uint8)
        if buf.size != size:
            raise ValueError(
                f"payload inflates to {buf.size} bytes, its shape has {size}")
        return buf, "stream"
    off = enc.offsets
    if len(off) != t + 1:
        raise ValueError(f"{len(off) - 1} chunks for a GOP of {t} frames")
    buf = np.empty(size, np.uint8)
    out, src = memoryview(buf), memoryview(enc.payload)
    dctx = None  # one per call: reads decode GOPs on many threads
    lo = 0
    for i in range(t):
        hi = lo + (rsz if i else isz)
        chunk, slot = src[off[i]:off[i + 1]], out[lo:hi]
        if chunk[:4] == _ZSTD_FRAME_MAGIC:
            if dctx is None:
                dctx = _zstd_decompressor()
            _read_into(dctx.stream_reader(chunk), slot)
            path = "into"
        else:
            raw = zlib.decompress(chunk, bufsize=len(slot))
            if len(raw) != len(slot):
                raise ValueError(f"chunk inflates to {len(raw)} bytes, "
                                 f"its frame has {len(slot)}")
            slot[:] = raw
            path = "copy"
        lo = hi
    return buf, path


def gop_planes(enc: EncodedGOP) -> Tuple[np.ndarray, np.ndarray]:
    """The stored planes of a TVC GOP: the I-frame ``(C, H, W)`` uint8
    and the quantized residuals ``(T-1, C, H, W)`` in the tier's dtype,
    both views of one buffer (`inflate_planes`).

    The payload is channel-planar, exactly as encoded: the I-frame
    followed by the residuals.  Each per-frame chunk inflates straight
    into its own slot of the buffer, so no payload-sized temporary is
    made.  Inside a traced read this is the ``inflate`` stage."""
    t, h, w, c = enc.shape
    isz = h * w * c
    with Tracer.stage("inflate", bytes_in=len(enc.payload)) as sp:
        buf, path = inflate_planes(enc)
        iframe = buf[:isz].reshape(c, h, w)
        resid = buf[isz:].view(TIERS[enc.codec].resid_dtype).reshape(
            t - 1, c, h, w)
        if sp is not None:
            sp.attrs["bytes_out"] = buf.nbytes
            sp.attrs["path"] = path
    _inflates.inc(path)
    return iframe, resid


def decode_gop(
    enc: EncodedGOP,
    *,
    use_pallas: Optional[bool] = None,
) -> np.ndarray:
    """Returns (T, H, W, C) uint8 frames.

    Inside a traced read a TVC GOP's host time falls in three stages:
    ``inflate`` (`gop_planes`), ``upload`` (the planes handed to JAX)
    and ``download`` (from the first device op's dispatch to the uint8
    frames on the host); an RGB GOP's copy is its ``inflate``."""
    t, h, w, c = enc.shape
    if enc.codec == RGB:
        n = len(enc.payload)
        with Tracer.stage("inflate", bytes_in=n, bytes_out=n):
            return np.frombuffer(enc.payload, np.uint8).reshape(
                t, h, w, c).copy()
    tier = TIERS[enc.codec]
    iframe, resid = gop_planes(enc)
    # planes cross to the device in their stored dtypes and frames come
    # back as uint8; every widening and narrowing happens on the device
    with Tracer.stage("upload", bytes=iframe.nbytes + resid.nbytes):
        iframe = jnp.asarray(iframe)
        if t > 1:
            resid = jnp.asarray(resid)
    with Tracer.stage("download", bytes=t * h * w * c):
        iframe = iframe.astype(jnp.float32)
        if t == 1:
            planar = iframe[None]
        else:
            planar = ops.delta_decode(
                iframe, resid.astype(jnp.int32),
                q=tier.q, vmin=VMIN, vmax=VMAX, use_pallas=use_pallas,
            )
        return _to_u8(ops.from_planar(planar))


def _to_u8(frames: jnp.ndarray) -> np.ndarray:
    """Round and clip float frames on the device; download uint8."""
    return np.asarray(jnp.clip(jnp.round(frames), 0, 255).astype(jnp.uint8))


def transcode_gop(
    enc: EncodedGOP,
    codec: str,
    *,
    scale_factor: int = 1,
    use_pallas: Optional[bool] = None,
) -> EncodedGOP:
    """Transcode a GOP to another codec, optionally box-downsampling by
    ``scale_factor``. TVC→TVC with T>1 uses the fused Pallas transcode
    kernel (decode→pool→re-encode without materializing frames in HBM);
    every other combination goes decode → (pool) → encode.
    """
    codec = canonical_codec(codec)
    t, h, w, c = enc.shape
    f = scale_factor
    if f > 1 and (h % f or w % f):
        raise ValueError(f"scale factor {f} must divide ({h},{w})")
    fused = (
        enc.codec != RGB
        and codec != RGB
        and t > 1
        and h % f == 0
        and w % f == 0
    )
    if fused:
        tin = TIERS[enc.codec]
        tout = TIERS[codec]
        iframe, resid = gop_planes(enc)
        io, ro = ops.transcode(
            jnp.asarray(iframe).astype(jnp.float32),
            jnp.asarray(resid).astype(jnp.int32),
            q_in=tin.q, q_out=tout.q, factor=f,
            lo=tout.lo, hi=tout.hi, vmin=VMIN, vmax=VMAX,
            use_pallas=use_pallas,
        )
        oh, ow = h // f, w // f
        iframe_out = np.asarray(io, np.float32)
        resid_out = np.asarray(ro.astype(tout.resid_dtype))
        return _chunked_gop(codec, (t, oh, ow, c),
                            iframe_out.astype(np.uint8), resid_out, tout)
    frames = decode_gop(enc, use_pallas=use_pallas)
    if f > 1:
        planar = ops.to_planar(jnp.asarray(frames))
        small = planar.reshape(t, c, h // f, f, w // f, f).mean(axis=(3, 5))
        frames = _to_u8(ops.from_planar(small))
    return encode_gop(frames, codec, use_pallas=use_pallas)


# --------------------------------------------------------------------------
# byte-level (de)serialization — one GOP per storage object, as in §2
# --------------------------------------------------------------------------
#
# Blob formats (both readable forever):
#   TVC1: magic ++ hlen(u32le) ++ json{"codec","shape"} ++ payload —
#         payload is raw RGB bytes or ONE compressed stream (legacy).
#   TVC2: same framing, header additionally carries "offsets" (the
#         cumulative per-frame chunk offsets, length T+1) and the
#         payload is the concatenation of T independently-compressed
#         chunks — the byte index that makes ranged sub-GOP reads pay
#         only for the frames they decode.
# RGB GOPs keep writing TVC1: their frame offsets are analytic (i*H*W*C
# from the shape), so the header needs no table for random access.

_MAGIC = b"TVC1"
_MAGIC_V2 = b"TVC2"
_MAGICS = (_MAGIC, _MAGIC_V2)

# one storage read of this size always covers magic + header for any
# plausible GOP (a T=600 offset table is < 5 KiB of JSON)
HEADER_PROBE_BYTES = 8192


def serialize_gop(enc: EncodedGOP) -> bytes:
    meta = {"codec": enc.codec, "shape": enc.shape}
    if enc.codec != RGB and enc.offsets is not None:
        meta["offsets"] = list(enc.offsets)
        magic = _MAGIC_V2
    else:
        magic = _MAGIC
    header = json.dumps(meta).encode()
    return magic + len(header).to_bytes(4, "little") + header + enc.payload


def parse_gop_header(data: bytes):
    """Parse the blob header from a *prefix* of a serialized GOP.

    Returns ``(codec, shape, offsets, payload_start)`` — ``offsets`` is
    None for legacy/RGB blobs.  Raises ValueError when ``data`` is not a
    TVC blob or is too short to hold the whole header."""
    if data[:4] not in _MAGICS:
        raise ValueError("not a TVC GOP object")
    if len(data) < 8:
        raise ValueError("truncated TVC header")
    hlen = int.from_bytes(data[4:8], "little")
    if len(data) < 8 + hlen:
        raise ValueError("truncated TVC header")
    header = json.loads(data[8 : 8 + hlen].decode())
    offsets = header.get("offsets")
    return (
        header["codec"],
        tuple(header["shape"]),
        tuple(offsets) if offsets is not None else None,
        8 + hlen,
    )


def deserialize_gop(data: bytes) -> EncodedGOP:
    codec, shape, offsets, start = parse_gop_header(data)
    return EncodedGOP(codec, shape, data[start:], offsets)
