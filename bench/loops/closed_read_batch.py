"""Closed loop of in-process ``VSS.read_batch`` callers (see
``bench/gen.py``)."""
from __future__ import annotations

import os
import threading
import time
from typing import Dict

from bench import gen, stats
from bench.run import annotate, build_store, open_store, phase, read_spec, \
    warm_up


def closed_read_batch(run) -> Dict:
    lay, mix = run.layout, run.mix
    vss = open_store(os.path.join(run.tmp, "store"), run.cfg)
    run.closers.append(vss.close)
    with phase("store"):
        build_store(vss, lay, run.seed)
    with phase("warm-up"):
        warm_up(run)
    work, kept, errors = [], [], []
    lock = threading.Lock()

    def consume(c: int) -> None:
        # the consumer's answer that is checked: one drawn from the seed,
        # each of those it received in the window as likely (a reservoir
        # of one)
        reqs = gen.closed_requests(mix, lay, run.seed, c)
        pick = gen.rng_for(run.seed, 5, c)
        answer = None
        n = 0
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
            n += 1
            if pick.random() * n < 1.0:
                answer = (req, frames)
            with lock:
                work.append((issued, done, frames.shape[0] * frames.shape[1]
                             * frames.shape[2] / 1e6))
        if answer is not None:
            with lock:
                kept.append(answer)

    threads = [threading.Thread(target=consume, args=(c,))
               for c in range(mix["consumers"])]
    run.open_window()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    run.close_window()
    spans = run.spans(vss)
    return {
        "attempted": len(work) + len(errors), "failed": len(errors),
        "errors": errors[:3],
        "metrics": {"read_Mpx_per_s": stats.window_rate(work, run.t0,
                                                        run.t_end)},
        "ctx": {"spans": spans, "gop_shape": (lay.gop, lay.h, lay.w, 3)},
        "answers": kept,
    }
