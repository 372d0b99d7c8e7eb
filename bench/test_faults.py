"""The comparison that decides ``correct``, shown to fail: the control
(the plain reference one codec tier lower, in the program's place), and
the timed path broken underneath a CPU rehearsal of each cell. The
harness's look for a chip is the only part a rehearsal skips."""
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


FAULTS = [
    ("vroad2k.scan", _altered_decode),
    ("vroad2k.scan", _half_batch),
    ("vroad2k.scan", _stale_reads),
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
