"""Closed loop of in-process ``VSS.read_batch`` callers whose requests
come from the mix's request kinds (``bench/kinds/<kind>.json``).

The mix gives ``consumers``, ``requests`` (kinds with their ``share``),
``cache`` and the access pattern's Zipf exponents (``camera_zipf_s``,
``start_zipf_s``), as an open loop's does. Each consumer sends its next
request when the last one returned. It draws each from its own seeded
stream with `gen.kind_request`: the kind by share, that kind's variants
in turn, a start at any frame of a GOP.

On a tiled store a read's class is the number of tiles its box covers
in one GOP (1, 2 across a seam, 4 across a corner of a 2x2 grid). Each
consumer keeps one answer of each class it received, drawn from the
seed with each as likely, so the check covers every class the window
held.
"""
from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, Tuple

from bench import gen, stats
from bench.run import annotate, build_store, open_store, phase, read_spec


def tile_grid(lay: gen.Layout) -> Tuple[int, int]:
    return tuple(lay.write.get("tiles", (1, 1)))


def tile_class(lay: gen.Layout, req: dict) -> int:
    """Tiles of the store's grid that the request's box covers in one
    GOP (1 on an untiled store)."""
    from repro.core.types import tiles_covering

    box = req.get("roi") or (0, 0, lay.w, lay.h)
    rows, cols = tiles_covering(tile_grid(lay), lay.w, lay.h, box)
    return len(rows) * len(cols)


def gop_shape(lay: gen.Layout) -> Tuple[int, int, int, int]:
    """``(frames, height, width, channels)`` of the GOP that one decode
    runs on: a tile's, whose bands come from the program's tile
    geometry."""
    from repro.core.types import tile_bounds

    rows, cols = tile_grid(lay)
    (y0, y1), (x0, x1) = tile_bounds(lay.h, rows)[0], tile_bounds(lay.w,
                                                                   cols)[0]
    return lay.gop, y1 - y0, x1 - x0, 3


def requests(run, lay: gen.Layout, seed: int, consumer: int):
    """Endless requests of one consumer."""
    rng = gen.rng_for(seed, 11, consumer)
    entries = run.mix["requests"]
    shares = [e["share"] for e in entries]
    turn = {e["kind"]: 0 for e in entries}
    while True:
        name = entries[int(rng.choice(len(entries), p=shares))]["kind"]
        kind = run.kinds[name]
        vs = gen.variants(kind)
        var = vs[turn[name] % len(vs)]
        turn[name] += 1
        yield gen.kind_request(kind, var, lay, rng, run.mix)


def warm_up(run) -> None:
    """One request of each variant of each kind, on a one-camera store
    of its own written with the cell's layout, holding one request off
    any frame of a GOP: every shape the window runs, and no other."""
    kinds = [run.kinds[e["kind"]] for e in run.mix["requests"]]
    longest = max(gen.kind_frames(k, v, run.layout) for k in kinds
                  for v in gen.variants(k))
    gops = -(-(longest + run.layout.gop - 1) // run.layout.gop)
    wlay = gen.Layout(run.cfg, cameras=1, gops=gops)
    vss = open_store(os.path.join(run.tmp, "warmup"), run.cfg)
    rng = gen.rng_for(run.seed, 13)
    try:
        build_store(vss, wlay, run.seed)
        for kind in kinds:
            for var in gen.variants(kind):
                req = gen.kind_request(kind, var, wlay, rng, run.mix)
                vss.read_batch([read_spec(req)])
    finally:
        vss.close()


def closed_kinds(run) -> Dict:
    vss = open_store(os.path.join(run.tmp, "store"), run.cfg)
    run.closers.append(vss.close)
    with phase("store"):
        build_store(vss, run.layout, run.seed)
    with phase("warm-up"):
        warm_up(run)
    return measure(run, vss, run.mix["consumers"])


def measure(run, vss, consumers: int) -> Dict:
    """One window of ``consumers`` callers on the built store."""
    lay = run.layout
    work, kept, errors = [], [], []
    # consumer -> (reads received by class, classes of the kept answers)
    tally: Dict[int, tuple] = {}
    lock = threading.Lock()

    def consume(c: int) -> None:
        reqs = requests(run, lay, run.seed, c)
        pick = gen.rng_for(run.seed, 12, c)
        answers: Dict[int, tuple] = {}  # class -> (request, frames)
        seen: Dict[int, int] = {}
        while time.time() < run.t_end:
            req = next(reqs)
            issued = time.time()
            try:
                with annotate(run, "bench.read_batch"):
                    frames = vss.read_batch([read_spec(req)])[0].frames
            except Exception as e:  # noqa: BLE001 - counted as failed
                with lock:
                    errors.append(repr(e))
                continue
            done = time.time()
            k = tile_class(lay, req)
            seen[k] = seen.get(k, 0) + 1
            if pick.random() * seen[k] < 1.0:  # a reservoir of one a class
                answers[k] = (req, frames)
            with lock:
                work.append((issued, done, frames.shape[0] * frames.shape[1]
                             * frames.shape[2] / 1e6))
        with lock:
            kept.extend(answers[k] for k in sorted(answers))
            tally[c] = (seen, sorted(answers))

    threads = [threading.Thread(target=consume, args=(c,))
               for c in range(consumers)]
    run.open_window()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    run.close_window()
    received: Dict[int, int] = {}
    for seen, _ in tally.values():
        for k, n in seen.items():
            received[k] = received.get(k, 0) + n
    print(f"reads by tiles covered: {dict(sorted(received.items()))}; "
          f"answers checked: {len(kept)}", file=sys.stderr, flush=True)
    return {
        "attempted": len(work) + len(errors), "failed": len(errors),
        "errors": errors[:3],
        "metrics": {"read_Mpx_per_s": stats.window_rate(work, run.t0,
                                                        run.t_end)},
        "ctx": {"spans": run.spans(vss), "gop_shape": gop_shape(lay)},
        "answers": kept, "classes": tally,
    }
