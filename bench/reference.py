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
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# tier -> (q, residual bits); aliases as the paper's experiments name them
TIERS = {"tvc-ll": (1.0, 16), "tvc-hi": (2.0, 8), "tvc-med": (8.0, 8),
         "tvc-lo": (24.0, 8)}
ALIASES = {"lossless": "tvc-ll", "hevc": "tvc-hi", "h264": "tvc-med",
           "raw": "rgb"}
# the next tier below each one: the control's lower precision
LOWER = {"tvc-ll": "tvc-hi", "tvc-hi": "tvc-med", "tvc-med": "tvc-lo",
         "tvc-lo": "tvc-lo"}


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


class Store:
    """What a read of the benchmark's store must return: the stored
    tier's reconstruction of each camera's seeded frames, GOP by GOP.

    ``source(cam, gop)`` gives the source frames of one GOP; ``tier`` is
    the stored codec (a lower one for the control)."""

    def __init__(self, source: Callable[[int, int], np.ndarray], *,
                 fps: float, gop: int, tier: str, shift: int = 0):
        self.source = source
        self.fps, self.gop = fps, gop
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

    def expect(self, req: dict) -> np.ndarray:
        """The frames (T, H, W, C) uint8 that an ``rgb`` read ``req`` of
        whole frames must return."""
        if canonical(req.get("codec", "rgb")) != "rgb" or any(
                req.get(k) for k in ("roi", "resolution", "fps")):
            raise ValueError(f"no reference for the read {req}")
        cam, f0, f1 = self._span(req)
        parts = []
        for g in range(f0 // self.gop, (f1 - 1) // self.gop + 1):
            gs = g * self.gop
            parts.append(self.recon(cam, g)[max(f0, gs) - gs:
                                            min(f1, gs + self.gop) - gs])
        return np.concatenate(parts)


def frames_differing(got: Optional[np.ndarray], want: np.ndarray) -> int:
    """Frames of ``want`` that ``got`` does not reproduce exactly (all of
    them when the shapes differ)."""
    if got is None or got.shape != want.shape:
        return max(len(want), 0 if got is None else len(got))
    return int(np.any((got != want).reshape(len(want), -1), axis=1).sum())
