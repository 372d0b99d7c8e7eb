"""From a profiler trace to busy time, idle share and a breakdown.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain dict of ``[name, start_ns, dur_ns]`` lists: ``device``, the
operations of the device planes (their ``XLA Ops`` line where a plane
has one); ``modules``, the compiled programs they ran in (``XLA
Modules``, named after the jitted function); and ``host``, the host
spans the benchmark annotated (``bench.*``); ``kernels``, the device's
custom calls (the Pallas kernels) with the HLO text that names their
shapes; and
``planes``, the names of the planes and their lines, for a reader of a
dump.
``reduce`` works on that dict alone, so it can be checked on a small
recorded trace without a chip.
"""
from __future__ import annotations

import glob
import os
import sys
from typing import Dict, List, Sequence, Tuple

WINDOW = "bench.window"  # the annotation that spans the traced window
MARK = "bench.clock_mark"  # host annotation around one tiny device op
MARK_PROGRAM = "bench_clock_mark"  # the jitted function of that op
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str) -> Dict[str, list]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    print(f"trace file: {os.path.getsize(paths[-1])} bytes", file=sys.stderr,
          flush=True)
    data = ProfileData.from_file(paths[-1])
    device, modules, host, planes, kernels = [], [], [], [], []
    for plane in data.planes:
        planes.append([plane.name, [ln.name for ln in plane.lines]])
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            for ln in ops:
                for e in ln.events:
                    device.append([op_name(e.name), e.start_ns, e.duration_ns])
                    if " custom-call(" in e.name:
                        kernels.append([e.name, e.start_ns, e.duration_ns])
            for ln in lines:
                if ln.name == MODULES_LINE:
                    modules.extend([e.name, e.start_ns, e.duration_ns]
                                   for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in ln.events if e.name.startswith("bench."))
    # the planes of a TPU trace need not share a clock: the benchmark's
    # clock marks put the device's events on the host's
    offset = clock_offset(host, modules)
    for ev in device + modules + kernels:
        ev[1] -= offset
    return {"device": device, "modules": modules, "host": host,
            "kernels": kernels, "planes": planes,
            "clock_offset_ns": offset}


def op_name(event: str) -> str:
    """An op's name without the HLO text a TPU trace appends to it:
    ``%fusion.3 = f32[...] fusion(...)`` gives ``fusion.3``."""
    return event.split(" = ", 1)[0].lstrip("%")


def clock_offset(host: list, modules: list) -> float:
    """Nanoseconds by which the device's events run ahead of the host's:
    the median, over the clock marks, of the start of the mark's program
    on the device less the start of its host annotation (which holds the
    dispatch, so the error is under one annotation's length); 0 where the
    trace holds no pair of marks."""
    hs = sorted(s for n, s, _ in host if n == MARK)
    ds = sorted(s for n, s, _ in modules if MARK_PROGRAM in n)
    if not hs or len(hs) != len(ds):
        return 0
    diffs = sorted(d - h for h, d in zip(hs, ds))
    return diffs[len(diffs) // 2]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def reduce(trace: Dict[str, list], top: int = 10) -> Dict:
    """Busy seconds, window seconds, per-op totals, the ``top`` device
    ops by time and the ``top`` longest idle gaps, each named by the
    benchmark span that the host was in for most of it."""
    host = trace["host"]
    marks = [(s, s + d) for n, s, d in host if n == WINDOW]
    if marks:
        w0, w1 = min(a for a, _ in marks), max(b for _, b in marks)
    elif trace["device"]:
        w0 = min(s for _, s, _ in trace["device"])
        w1 = max(s + d for _, s, d in trace["device"])
    else:
        raise ValueError("trace holds neither a window mark nor device ops")
    # the benchmark's own clock marks are not the program's work
    marks = [(s, s + d) for n, s, d in trace.get("modules", [])
             if MARK_PROGRAM in n]
    clipped = []
    by_name: Dict[str, List[float]] = {}
    for name, s, d in trace["device"]:
        a, b = max(s, w0), min(s + d, w1)
        if b <= a or any(m0 <= s and s + d <= m1 for m0, m1 in marks):
            continue
        clipped.append((a, b))
        if s >= w0 and s + d <= w1:  # whole ops only: counts stay exact
            tot = by_name.setdefault(name, [0, 0.0])
            tot[0] += 1
            tot[1] += d / 1e9
    by_module: Dict[str, int] = {}
    for name, s, d in trace.get("modules", []):
        if s >= w0 and s + d <= w1:
            by_module[name] = by_module.get(name, 0) + 1
    kernels = [name for name, s, d in trace.get("kernels", [])
               if s >= w0 and s + d <= w1]
    busy = union(clipped)
    busy_s = sum(b - a for a, b in busy) / 1e9
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    spans = [(n, s, s + d) for n, s, d in host if n != WINDOW]

    def doing(a, b) -> str:
        best, name = 0.0, "unannotated"
        for n, s, e in spans:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, name = ov, n
        return name

    gaps.sort(key=lambda g: g[0] - g[1])
    ops = sorted(by_name.items(), key=lambda kv: -kv[1][1])
    return {
        "busy_s": busy_s,
        "window_s": (w1 - w0) / 1e9,
        "by_name": by_name,
        "by_module": by_module,
        "kernels": kernels,
        "device_ops": [[n, v[1]] for n, v in ops[:top]],
        "idle_gaps": [[doing(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
    }


def idle_pct(reduced: Dict) -> float:
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def count_modules(reduced: Dict, pattern: str) -> int:
    """Runs of compiled programs wholly inside the window whose name
    holds ``pattern`` (a jitted function's name)."""
    return sum(n for name, n in reduced["by_module"].items()
               if pattern in name)
