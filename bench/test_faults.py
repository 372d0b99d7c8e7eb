"""The comparison that decides ``correct``, shown to fail: the control
(the plain reference one codec tier lower, in the program's place), and
the timed path broken underneath a CPU rehearsal of each cell. The
harness's look for a chip is the only part a rehearsal skips. The served
cell's faults break what its answers pass through: the crop, the edge
GOPs of a trim, the box pool, and the service's parked answers."""
import argparse
import threading

import numpy as np
import pytest

from bench import run
from bench.test_harness import ROOT, cells

CELLS = cells()


def rehearse(cell: str, root: str, control: bool = False,
             seed: int = 11) -> dict:
    args = argparse.Namespace(workload=cell, seed=seed, seconds=1.0, trace=0,
                              cpu_rehearsal=True, control=control,
                              trace_dump=None)
    return run.run_cell(args, root=root)


def fails(numbers: dict) -> bool:
    return any(v["value"] > v["limit"] for v in numbers.values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_the_program_passes(cell):
    result = rehearse(cell, ROOT, control=True)
    assert result["correct"] and not fails(result["checks"])
    assert fails(result["control"])


def _altered_decode(monkeypatch):
    """Every decoded GOP comes back one level brighter."""
    from repro.codec import tvc

    real = tvc._to_u8

    def to_u8(frames):
        return np.minimum(real(frames), 254) + np.uint8(1)

    monkeypatch.setattr(tvc, "_to_u8", to_u8)


class _Half:
    """A read result with the second half of its answer left out."""

    def __init__(self, res):
        self._res = res

    @property
    def frames(self):
        f = self._res.frames
        return f[: len(f) // 2]


def _half_batch(monkeypatch):
    from repro.core.store import VSS

    real = VSS.read_batch

    def read_batch(self, specs):
        return [_Half(r) for r in real(self, specs)]

    monkeypatch.setattr(VSS, "read_batch", read_batch)


def _stale_reads(monkeypatch):
    """Each read returns the answer of the first read: state that never
    moves on."""
    from repro.core.store import VSS

    real = VSS.read_batch
    first = {}
    lock = threading.Lock()

    def read_batch(self, specs):
        out = real(self, specs)
        with lock:
            first.setdefault("res", out[0])
            return [first["res"]] * len(out)

    monkeypatch.setattr(VSS, "read_batch", read_batch)


def _crop_off_by_a_row(monkeypatch):
    """Every crop box taken one row lower."""
    from repro.core.store import VSS

    real = VSS._local_box

    def local_box(p, roi):
        x0, y0, x1, y1 = real(p, roi)
        return x0, y0 + 1, x1, y1 + 1

    monkeypatch.setattr(VSS, "_local_box", staticmethod(local_box))


def _edge_gop_not_reencoded(monkeypatch):
    """A trim's edge GOPs served as stored, whole, with no re-encode."""
    from repro import codec
    from repro.core.store import VSS

    real = VSS._extract_encoded

    def extract_encoded(self, run_, a, b, out_codec, io=None):
        encs, ids = real(self, run_, a, b, out_codec, io)
        gops = {g.gop_id: g for g in run_.gops}
        return [codec.deserialize_gop(self.backend.get(gops[i].path))
                for i in ids], ids

    monkeypatch.setattr(VSS, "_extract_encoded", extract_encoded)


def _pool_rounded_down(monkeypatch):
    """The box pool's means truncated instead of rounded."""
    from repro.core import store

    def resample(frames, resolution):
        w, h = resolution
        t, ih, iw, c = frames.shape
        if (iw, ih) == (w, h):
            return frames
        f = ih // h
        x = frames.astype(np.float32).reshape(t, h, f, w, f, c).mean((2, 4))
        return np.floor(x).astype(np.uint8)

    monkeypatch.setattr(store, "resample", resample)


def _first_answer_for_all(monkeypatch):
    """The service parks the first request's answer for every request."""
    from repro.serving.service import VSSService

    real = VSSService.park
    first = {}
    lock = threading.Lock()

    def park(self, result):
        with lock:
            first.setdefault("res", result)
            return real(self, first["res"])

    monkeypatch.setattr(VSSService, "park", park)


FAULTS = [
    ("vroad2k.scan", _altered_decode),
    ("vroad2k.scan", _half_batch),
    ("vroad2k.scan", _stale_reads),
    ("vroad1k.query", _crop_off_by_a_row),
    ("vroad1k.query", _edge_gop_not_reencoded),
    ("vroad1k.query", _pool_rounded_down),
    ("vroad1k.query", _first_answer_for_all),
]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}"
                              for c, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result = rehearse(cell, ROOT)
    assert not result["correct"]
    assert fails(result["checks"])


def test_altered_frames_differ_by_the_reference():
    from bench import reference

    want = np.zeros((4, 2, 2, 3), np.uint8)
    got = want.copy()
    assert reference.frames_differing(got, want) == 0
    got[2, 1, 1, 0] = 1
    assert reference.frames_differing(got, want) == 1
    assert reference.frames_differing(got[:2], want) == 4
    assert reference.frames_differing(None, want) == 4
