"""The one traffic generator: it turns a mix's data file
(``bench/traffic/<mix>.json``) and a seed into requests.

A mix names its ``loop`` and the parameters of its requests:

* ``closed_read_batch``: ``consumers`` in-process callers of
  ``VSS.read_batch``, each sending its next request when the last one
  returned; ``request`` gives ``gops`` (GOP-aligned length), ``codec``
  and ``cache``. Cameras and starts are uniform.
* ``open_http``: requests sent on a schedule at ``rate_per_s``, whatever
  the answers do; ``requests`` lists request kinds by name with their
  ``share``, each kind a data file ``bench/kinds/<kind>.json`` (see
  `kind_request`); ``cache``, ``tenants``, and the Zipf exponents of the
  access pattern: ``camera_zipf_s`` over cameras, ``start_zipf_s`` over
  GOPs counted back from the newest.

Open-loop traffic comes in blocks of ``block`` requests. Every block
holds each kind in exact proportion to its share, each kind's variants
in turn, and the same inter-arrival gaps (the exponential distribution's
``block`` quantiles at the mix's rate). The schedule (which slot of a
block takes which gap, kind and variant, and the frame within its GOP
at which each request starts) is drawn from `SCHEDULE_SEED`, the same
for every seed: it sets the work of each request and when it arrives,
so the queue it builds. The seed draws where each request falls: its
camera, how many GOPs back it starts, and its box.
"""
from __future__ import annotations

import itertools
import math
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (2 ** 63), *stream])


def _t(f0: int, f1: int, fps: float) -> List[float]:
    return [f0 / fps, f1 / fps]


# ``WriteSpec`` fields a configuration may set beyond its sizes and codec
WRITE_FIELDS = ("tiles", "budget_bytes")


class Layout:
    """The store a cell reads: ``cameras`` cameras of ``gops`` GOPs,
    written with the configuration's `WRITE_FIELDS` (``write``)."""

    def __init__(self, cfg: dict, cameras: int = None, gops: int = None):
        self.w, self.h = cfg["width"], cfg["height"]
        self.fps, self.gop = float(cfg["fps"]), cfg["gop_frames"]
        self.codec = cfg["codec"]
        self.write = {k: tuple(cfg[k]) if isinstance(cfg[k], list)
                      else cfg[k] for k in WRITE_FIELDS if k in cfg}
        self.cameras = cameras or cfg["cameras"]
        self.gops = gops or int(cfg["stored_seconds"] * self.fps) // self.gop

    @property
    def frames(self) -> int:
        return self.gops * self.gop


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


def longest_frames(mix: dict, kinds: Dict[str, dict], lay: Layout) -> int:
    """Source frames of the mix's longest request."""
    if "request" in mix:
        return mix["request"]["gops"] * lay.gop
    return max(kind_frames(k, v, lay) for k in kinds.values()
               for v in variants(k))


def warmup_gops(mix: dict) -> int:
    """GOPs of the warm-up store: room for one request off GOP 1."""
    return mix["request"]["gops"] + 1


# -- open-loop traffic ---------------------------------------------------------

def zipf_rank(rng: np.random.Generator, n: int, s: float) -> int:
    """A rank in ``[0, n)`` drawn with probability proportional to
    ``(rank + 1) ** -s``."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
    return int(np.searchsorted(np.cumsum(w), rng.random() * w.sum(),
                                side="right").clip(0, n - 1))


def variants(kind: dict) -> List[dict]:
    """A kind's variants: one per choice of its list-valued parameters
    (``frames`` and ``roi.sides``), in a fixed order."""
    frames = kind.get("frames", [None])
    sides = kind["roi"]["sides"] if "roi" in kind else [None]
    return [{"frames": f, "side": s} for f, s in itertools.product(frames,
                                                                   sides)]


def kind_frames(kind: dict, var: dict, lay: Layout) -> int:
    """Source frames one request of this kind and variant spans."""
    if var["frames"] is not None:
        return int(var["frames"])
    return int(round(kind["seconds"] * lay.fps))


def out_shape(kind: dict, var: dict, lay: Layout) -> Tuple[int, int]:
    """``(width, height)`` of the answer's frames."""
    w, h = lay.w, lay.h
    if var["side"] is not None:
        w, h = int(lay.w * var["side"]), int(lay.h * var["side"])
    d = int(kind.get("downscale", 1))
    if w % d or h % d:
        raise ValueError(f"downscale {d} does not divide {w}x{h}")
    return w // d, h // d


def kind_request(kind: dict, var: dict, lay: Layout,
                 rng: np.random.Generator, mix: dict,
                 offset: Optional[int] = None) -> dict:
    """One request of a kind, as a ``ReadSpec``'s fields.

    A kind is a data file of the request's parameters: its length as
    ``frames`` (a list to choose from) or ``seconds``; ``codec``;
    ``fps_div`` (an integer stride over the stored frames); ``downscale``
    (an integer box-pool factor of the whole frame); and
    ``roi`` (``sides``, box sides as shares of the frame's, placed at
    the corner of a cell of a ``grid`` x ``grid`` grid drawn Zipf with
    ``zipf_s``, shifted inside the frame where it would overhang). The
    camera and the start come from the mix's access pattern: the start
    is ``offset`` frames (drawn from ``rng`` where it is None) before a
    frame a whole number of GOPs back from the latest at which the
    request fits."""
    n = kind_frames(kind, var, lay)
    cam = zipf_rank(rng, lay.cameras, mix["camera_zipf_s"])
    if offset is None:
        offset = int(rng.integers(0, lay.gop))
    latest = lay.frames - n
    if latest < offset:
        raise ValueError(f"{lay.frames} stored frames hold no {n}-frame "
                         f"request {offset} frames off a GOP's start")
    back = zipf_rank(rng, (latest - offset) // lay.gop + 1,
                     mix["start_zipf_s"])
    f0 = latest - offset - back * lay.gop
    req = {"name": f"cam{cam}", "t": _t(f0, f0 + n, lay.fps),
           "codec": kind.get("codec", "rgb"), "cache": mix["cache"]}
    if var["side"] is not None:
        grid = kind["roi"]["grid"]
        cell = zipf_rank(rng, grid * grid, kind["roi"]["zipf_s"])
        bw, bh = int(lay.w * var["side"]), int(lay.h * var["side"])
        x0 = min((cell % grid) * lay.w // grid, lay.w - bw)
        y0 = min((cell // grid) * lay.h // grid, lay.h - bh)
        req["roi"] = [x0, y0, x0 + bw, y0 + bh]
    if int(kind.get("downscale", 1)) > 1:
        req["resolution"] = list(out_shape(kind, var, lay))
    if int(kind.get("fps_div", 1)) > 1:
        req["fps"] = lay.fps / int(kind["fps_div"])
    return req


def block_kinds(mix: dict, kinds: Dict[str, dict]) -> List[Tuple[str, dict]]:
    """The ``(kind, variant)`` of each request of one block, unordered:
    every kind in exact proportion to its share, its variants in turn."""
    block = mix["block"]
    out: List[Tuple[str, dict]] = []
    for entry in mix["requests"]:
        count = entry["share"] * block
        if abs(count - round(count)) > 1e-9:
            raise ValueError(f"share {entry['share']} of {entry['kind']!r} "
                             f"is not a whole part of a {block}-block")
        vs = variants(kinds[entry["kind"]])
        out += [(entry["kind"], vs[i % len(vs)])
                for i in range(int(round(count)))]
    if len(out) != block:
        raise ValueError(f"shares fill {len(out)} of a {block}-block")
    return out


SCHEDULE_SEED = 0  # the open loop's schedule, the same for every seed


def open_requests(mix: dict, kinds: Dict[str, dict], lay: Layout,
                  seed: int) -> Iterator[Tuple[float, str, dict]]:
    """Endless ``(due, kind, request)`` of an open loop at the mix's
    ``rate_per_s``, ``due`` in seconds from the window's start."""
    rate = float(mix["rate_per_s"])
    block = mix["block"]
    slots = block_kinds(mix, kinds)
    gaps = [-math.log(1.0 - (j + 0.5) / block) / rate for j in range(block)]
    due = 0.0
    for b in itertools.count():
        plan = rng_for(SCHEDULE_SEED, 7, b)
        order = plan.permutation(block)
        gap = plan.permutation(gaps)
        offsets = plan.integers(0, lay.gop, block)
        rng = rng_for(seed, 7, b)
        for j in range(block):
            name, var = slots[order[j]]
            due += gap[j]
            yield due, name, kind_request(kinds[name], var, lay, rng, mix,
                                          int(offsets[j]))


ANSWER_GOP = 30  # frames per GOP of a compressed answer the program makes


def warmup_codec_shapes(mix: dict, kinds: Dict[str, dict], lay: Layout
                        ) -> Tuple[List[tuple], List[tuple]]:
    """Every ``(codec, frames, height, width)`` GOP that the mix's reads
    can encode, and every one they can decode.

    A read decodes stored GOPs whole or by any prefix. A read in the
    stored codec at the stored view passes whole GOPs through and
    re-encodes its edge GOPs, of any length; any other compressed
    answer is encoded in GOPs of `ANSWER_GOP` frames from its first
    frame. Where the mix admits views (``cache``), a later read may take
    any part of one, so every length of every answer is both encoded
    and decoded."""
    stored = (lay.codec, lay.h, lay.w)
    lengths = range(1, lay.gop + 1)
    decodes = {stored + (t,) for t in lengths}
    encodes = set()
    for entry in mix["requests"]:
        kind = kinds[entry["kind"]]
        codec = kind.get("codec", "rgb")
        if codec in ("rgb", "raw"):
            continue
        step = int(kind.get("fps_div", 1))
        for var in variants(kind):
            w, h = out_shape(kind, var, lay)
            n = -(-kind_frames(kind, var, lay) // step)  # answer frames
            passthrough = var["side"] is None and step == 1
            if (codec, h, w) == stored and passthrough:
                ts = set(lengths)
            else:
                ts = {min(n, ANSWER_GOP)} | ({n % ANSWER_GOP} - {0})
            if mix.get("cache"):
                ts |= set(range(1, min(n, ANSWER_GOP) + 1))
                decodes |= {(codec, h, w, t) for t in ts}
            encodes |= {(codec, h, w, t) for t in ts}
    return ([(c, t, h, w) for c, h, w, t in sorted(encodes)],
            [(c, t, h, w) for c, h, w, t in sorted(decodes)])
