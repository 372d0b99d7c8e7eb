"""The one traffic generator: it turns a mix's data file
(``bench/traffic/<mix>.json``) and a seed into requests.

A mix names its ``loop`` and the parameters of its requests:

* ``closed_read_batch``: ``consumers`` in-process callers of
  ``VSS.read_batch``, each sending its next request when the last one
  returned; ``request`` gives ``gops`` (GOP-aligned length), ``codec``
  and ``cache``. Cameras and starts are uniform.
"""
from __future__ import annotations

from typing import List

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (2 ** 63), *stream])


def _t(f0: int, f1: int, fps: float) -> List[float]:
    return [f0 / fps, f1 / fps]


class Layout:
    """The store a cell reads: ``cameras`` cameras of ``gops`` GOPs."""

    def __init__(self, cfg: dict, cameras: int = None, gops: int = None):
        self.w, self.h = cfg["width"], cfg["height"]
        self.fps, self.gop = float(cfg["fps"]), cfg["gop_frames"]
        self.codec = cfg["codec"]
        self.cameras = cameras or cfg["cameras"]
        self.gops = gops or int(cfg["stored_seconds"] * self.fps) // self.gop


def _read(mix: dict, lay: Layout, cam: int, g: int) -> dict:
    req = mix["request"]
    f0 = g * lay.gop
    return {"name": f"cam{cam}",
            "t": _t(f0, f0 + req["gops"] * lay.gop, lay.fps),
            "codec": req["codec"], "cache": req["cache"]}


def closed_requests(mix: dict, lay: Layout, seed: int, consumer: int):
    """Endless requests of one closed-loop consumer."""
    rng = rng_for(seed, 2, consumer)
    span = mix["request"]["gops"]
    while True:
        cam = int(rng.integers(0, lay.cameras))
        g = int(rng.integers(0, lay.gops - span + 1))
        yield _read(mix, lay, cam, g)


def warmup_request(mix: dict, lay: Layout) -> dict:
    """The mix's request on the warm-up store (``warmup_gops`` GOPs of
    one camera), off its first GOP as a scan's may be."""
    return _read(mix, lay, 0, 1)


def warmup_gops(mix: dict) -> int:
    """GOPs of the warm-up store: room for one request off GOP 1."""
    return mix["request"]["gops"] + 1
