"""Plain reference of what a VSS read or write must produce.

Independent of the program: it imports nothing of it and takes nothing
it made except the answers under test. It encodes the seeded source
frames with the closed-loop DPCM codec in numpy, in the stored tier, and
cuts each read's frames from the reconstruction.

The codec, as the VSS paper's TVC stand-in defines it:

    iframe  = frames[0]
    r_t     = frames[t] - recon_{t-1}
    rq_t    = clip(round(r_t / q), lo, hi)
    recon_t = clip(recon_{t-1} + rq_t * q, 0, 255)

so a decode returns ``recon``, which the encoder already holds.

A read's answer, as the reference has it: the stored tier's
reconstruction of the read's source frames, cropped to its ``roi``
(half-open), strided to its ``fps``, box-pooled to its ``resolution``
(mean, then round and clip; integer factors only), then, for a
compressed codec, encoded in that tier. A read in the stored codec at
the stored view keeps each whole GOP as stored and re-encodes each edge
GOP from its first kept frame; any other compressed answer is encoded in
GOPs of ``ANSWER_GOP`` frames from its first frame. A served segment is a
serialized GOP, which `decode_segment` reads from its format:

    magic b"TVC1" | b"TVC2", header length (u32 LE), JSON header
    {"codec", "shape": [T, H, W, C], "offsets" (TVC2 only)}, payload

An ``rgb`` payload is the raw frames. A TVC2 payload is T chunks, each
zstd (or zlib) compressed: the I-frame as uint8 planes (C, H, W), then
each frame's quantized residual planes in the tier's width; ``offsets``
are the chunks' cumulative byte offsets. A TVC1 payload of a tier is the
same planes as one compressed stream.
"""
from __future__ import annotations

import json
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

try:  # a segment's chunks are zstd where the writer had the wheel
    import zstandard
except ImportError:  # pragma: no cover - environment-dependent
    zstandard = None

# tier -> (q, residual bits); aliases as the paper's experiments name them
TIERS = {"tvc-ll": (1.0, 16), "tvc-hi": (2.0, 8), "tvc-med": (8.0, 8),
         "tvc-lo": (24.0, 8)}
ALIASES = {"lossless": "tvc-ll", "hevc": "tvc-hi", "h264": "tvc-med",
           "raw": "rgb"}
# the next tier below each one: the control's lower precision
LOWER = {"tvc-ll": "tvc-hi", "tvc-hi": "tvc-med", "tvc-med": "tvc-lo",
         "tvc-lo": "tvc-lo"}
ANSWER_GOP = 30  # frames per GOP of a compressed answer that is not stored
SEGMENT_MAGICS = (b"TVC1", b"TVC2")
ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"


def canonical(codec: str) -> str:
    codec = codec.lower()
    return ALIASES.get(codec, codec)


def roundtrip(frames: np.ndarray, codec: str) -> np.ndarray:
    """Decoded frames of one GOP encoded in ``codec``: (T, H, W, C) u8."""
    codec = canonical(codec)
    if codec == "rgb":
        return frames
    q, bits = TIERS[codec]
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    out = np.empty(frames.shape, np.uint8)
    recon = frames[0].astype(np.float32)
    out[0] = frames[0]
    for t in range(1, len(frames)):
        rq = np.clip(np.round((frames[t] - recon) / np.float32(q)), lo, hi)
        recon = np.clip(recon + rq * np.float32(q), 0.0, 255.0)
        out[t] = recon  # integral: the I-frame plus multiples of q
    return out


def box_pool(frames: np.ndarray, width: int, height: int) -> np.ndarray:
    """(T, H, W, C) uint8 box-downsampled by an integer factor to
    ``width`` x ``height``: each output pixel the mean of its box, then
    rounded half to even and clipped."""
    t, h, w, c = frames.shape
    f = h // height
    if h != f * height or w != f * width:
        raise ValueError(f"no integer box from {w}x{h} to {width}x{height}")
    if f == 1:
        return frames
    s = frames.astype(np.int64).reshape(t, height, f, width, f, c).sum((2, 4))
    return np.clip(np.round(s / float(f * f)), 0, 255).astype(np.uint8)


def _inflate(chunk: bytes) -> bytes:
    if chunk[:4] == ZSTD_MAGIC:
        if zstandard is None:
            raise RuntimeError("a zstd chunk, and no zstandard module")
        return zstandard.ZstdDecompressor().decompress(chunk)
    return zlib.decompress(chunk)


def decode_segment(data: bytes) -> Tuple[str, np.ndarray]:
    """``(codec, frames)`` of one serialized GOP, frames (T, H, W, C)
    uint8; raises ValueError on bytes that do not follow the format."""
    if data[:4] not in SEGMENT_MAGICS or len(data) < 8:
        raise ValueError("not a serialized GOP")
    hlen = int.from_bytes(data[4:8], "little")
    header = json.loads(data[8:8 + hlen].decode())
    payload = data[8 + hlen:]
    codec = canonical(header["codec"])
    t, h, w, c = (int(x) for x in header["shape"])
    if codec == "rgb":
        if len(payload) != t * h * w * c:
            raise ValueError("rgb payload of the wrong size")
        return codec, np.frombuffer(payload, np.uint8).reshape(t, h, w, c)
    q, bits = TIERS[codec]
    rdt = np.dtype(np.int16 if bits == 16 else np.int8).newbyteorder("<")
    plane = c * h * w
    offsets = header.get("offsets")
    if offsets is not None:
        if (data[:4] != b"TVC2" or len(offsets) != t + 1
                or offsets[-1] != len(payload)):
            raise ValueError("offsets that do not fit the shape")
        raw = b"".join(_inflate(payload[a:b])
                       for a, b in zip(offsets[:-1], offsets[1:]))
    else:
        raw = _inflate(payload)
    if len(raw) != plane + (t - 1) * plane * rdt.itemsize:
        raise ValueError("planes of the wrong size")
    recon = np.frombuffer(raw[:plane], np.uint8).reshape(c, h, w).astype(
        np.float32)
    resid = np.frombuffer(raw[plane:], rdt).reshape(t - 1, c, h, w)
    out = np.empty((t, c, h, w), np.float32)
    out[0] = recon
    for i in range(1, t):
        recon = np.clip(recon + resid[i - 1].astype(np.float32)
                        * np.float32(q), 0.0, 255.0)
        out[i] = recon
    frames = np.clip(np.round(out), 0, 255).astype(np.uint8)
    return codec, np.ascontiguousarray(frames.transpose(0, 2, 3, 1))


def decode_segments(segments: Sequence[bytes]) -> np.ndarray:
    """The frames of an answer served as ``segments``, in order."""
    return np.concatenate([decode_segment(s)[1] for s in segments])


class Store:
    """What a read of the benchmark's store must return: the stored
    tier's reconstruction of each camera's seeded frames, GOP by GOP,
    viewed as the read asks.

    ``source(cam, gop)`` gives the source frames of one GOP; ``tier`` is
    the stored codec; ``shift`` lowers it and every answer's tier by that
    many steps (1 for the control). A ``tiled`` store keeps each GOP as
    tile objects, which are never served as they are: every compressed
    answer is encoded anew."""

    def __init__(self, source: Callable[[int, int], np.ndarray], *,
                 fps: float, gop: int, tier: str, shift: int = 0,
                 tiled: bool = False):
        self.source = source
        self.fps, self.gop, self.tiled = fps, gop, tiled
        self.shift = shift  # tiers below the stated ones, 1 for the control
        self.stored = canonical(tier)
        self.tier = self._lower(self.stored)
        self._recon: Dict[Tuple[int, int], np.ndarray] = {}

    def _lower(self, codec: str) -> str:
        for _ in range(self.shift):
            codec = LOWER.get(codec, codec)
        return codec

    def recon(self, cam: int, g: int) -> np.ndarray:
        key = (cam, g)
        if key not in self._recon:
            self._recon[key] = roundtrip(self.source(cam, g), self.tier)
        return self._recon[key]

    def fill(self, reqs: Sequence[dict], workers: int) -> None:
        """Reconstructs every GOP that ``reqs`` read, on ``workers``
        threads (numpy leaves the interpreter's lock in its arithmetic)."""
        todo = sorted({(cam, g) for r in reqs
                       for cam, g in self.gops_read(r)} - set(self._recon))
        with ThreadPoolExecutor(workers) as pool:
            done = pool.map(lambda k: roundtrip(self.source(*k), self.tier),
                            todo)
            self._recon.update(zip(todo, done))

    def _span(self, req: dict) -> Tuple[int, int, int]:
        cam = int(req["name"].rsplit("cam", 1)[1])
        s, e = req["t"]
        return cam, int(round(s * self.fps)), int(round(e * self.fps))

    def gops_read(self, req: dict) -> List[Tuple[int, int]]:
        cam, f0, f1 = self._span(req)
        return [(cam, g) for g in range(f0 // self.gop,
                                        (f1 - 1) // self.gop + 1)]

    def _pieces(self, req: dict) -> List[Tuple[np.ndarray, bool]]:
        """The read's source frames, GOP by GOP: ``(frames, whole)``."""
        cam, f0, f1 = self._span(req)
        parts = []
        for g in range(f0 // self.gop, (f1 - 1) // self.gop + 1):
            gs = g * self.gop
            a, b = max(f0, gs) - gs, min(f1, gs + self.gop) - gs
            parts.append((self.recon(cam, g)[a:b], b - a == self.gop))
        return parts

    def expect(self, req: dict) -> np.ndarray:
        """The frames (T, H, W, C) uint8 that the read ``req`` must
        return, decoded."""
        codec = canonical(req.get("codec", "rgb"))
        parts = self._pieces(req)
        stored_view = not any(req.get(k) for k in ("roi", "resolution",
                                                   "fps"))
        if codec == self.stored and stored_view and not self.tiled:
            return np.concatenate([p if whole else roundtrip(p, self.tier)
                                   for p, whole in parts])
        frames = np.concatenate([p for p, _ in parts])
        if req.get("fps"):
            step = int(round(self.fps / float(req["fps"])))
            if abs(self.fps / float(req["fps"]) - step) > 1e-9:
                raise ValueError(f"fps {req['fps']} does not divide "
                                 f"{self.fps}")
            frames = frames[::step]
        if req.get("roi"):
            x0, y0, x1, y1 = (int(v) for v in req["roi"])
            frames = frames[:, y0:y1, x0:x1]
        if req.get("resolution"):
            frames = box_pool(frames, *(int(v) for v in req["resolution"]))
        if codec == "rgb":
            return frames
        out = self._lower(codec)
        return np.concatenate([roundtrip(frames[i:i + ANSWER_GOP], out)
                               for i in range(0, len(frames), ANSWER_GOP)])


def frames_differing(got: Optional[np.ndarray], want: np.ndarray) -> int:
    """Frames of ``want`` that ``got`` does not reproduce exactly (all of
    them when the shapes differ)."""
    if got is None or got.shape != want.shape:
        return max(len(want), 0 if got is None else len(got))
    return int(np.any((got != want).reshape(len(want), -1), axis=1).sum())
