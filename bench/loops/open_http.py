"""Open loop over HTTP: requests sent on the mix's schedule to a
``VSSService`` in this process, by a load generator in a child process.

The child is this file run as a script. It imports nothing but the
standard library (never JAX, so it holds no chip), reads the schedule,
sends each request at its due time on a thread of its own (``POST
/v1/read``, then ``GET`` of every segment of the manifest, one
connection), and writes, for each request, how late it was sent and when
the last byte of its last segment arrived. A request is timed from when
it was due, so a stall of the server or of the generator shows in every
request behind it; one that fails or is shed counts as slower than any
that completed.
"""
from __future__ import annotations

import http.client
import json
import math
import os
import sys
import threading
import time
import urllib.parse

DRAIN_S = 60.0  # how long after the window's close answers are awaited
START_S = 1.0  # from the schedule's hand-over to the window's start
PER_KIND = 3  # answers of each kind that are checked
REHEARSAL_SPEEDUP = 20.0  # a CPU rehearsal sends its schedule this fast
HTTP_TIMEOUT_S = 120.0


# -- the load generator (child process) -------------------------------------

def _fetch(base: str, body: bytes, tenant: str, keep: bool):
    """One request: its manifest, then every segment. Returns
    ``(status, nbytes, segments)``; ``segments`` only when ``keep``."""
    u = urllib.parse.urlsplit(base)
    conn = http.client.HTTPConnection(u.hostname, u.port,
                                      timeout=HTTP_TIMEOUT_S)
    try:
        conn.request("POST", "/v1/read", body=body, headers={
            "Content-Type": "application/json", "X-VSS-Tenant": tenant})
        resp = conn.getresponse()
        data = resp.read()
        if resp.status != 200:
            return resp.status, 0, None
        manifest = json.loads(data)
        segments, n = [], 0
        for seg in manifest["segments"]:
            conn.request("GET", seg["url"])
            resp = conn.getresponse()
            data = resp.read()
            if resp.status != 200:
                return resp.status, n, None
            n += len(data)
            if keep:
                segments.append(data)
        return 200, n, segments
    finally:
        conn.close()


def client(schedule_path: str, out_dir: str) -> None:
    with open(schedule_path) as f:
        plan = json.load(f)
    t0, reqs = plan["t0"], plan["requests"]
    results = [None] * len(reqs)

    def send(i: int, due: float, tenant: str, body: str, keep: bool):
        sent = time.time()
        try:
            status, n, segs = _fetch(plan["url"], body.encode(), tenant, keep)
        except (OSError, http.client.HTTPException, ValueError) as e:
            status, n, segs = repr(e), 0, None
        done = time.time()
        if segs is not None:
            for j, seg in enumerate(segs):
                with open(os.path.join(out_dir, f"{i}.{j}.seg"), "wb") as f:
                    f.write(seg)
        results[i] = {"late_s": sent - due, "latency_s": done - due,
                      "sent": sent, "done": done,
                      "status": status, "nbytes": n,
                      "segments": None if segs is None else len(segs)}

    threads = []
    for i, (offset, tenant, body, keep) in enumerate(reqs):
        due = t0 + offset
        time.sleep(max(0.0, due - time.time()))
        th = threading.Thread(target=send, args=(i, due, tenant, body, keep),
                              daemon=True)
        th.start()
        threads.append(th)
    deadline = plan["t_end"] + DRAIN_S
    for th in threads:
        th.join(max(0.0, deadline - time.time()))
    with open(os.path.join(out_dir, "client.json"), "w") as f:
        json.dump(results, f)


# -- the loop (the process that holds the chip) -----------------------------

def open_http(run) -> dict:
    from bench import gen, stats
    from bench.run import BenchError, build_store, open_store, phase
    from repro.serving.service import VSSService

    lay, mix, kinds = run.layout, run.mix, run.kinds
    vss = open_store(os.path.join(run.tmp, "store"), run.cfg)
    run.closers.append(vss.close)
    with phase("store"):
        build_store(vss, lay, run.seed)
    with phase("warm-up"):
        warm_up(run, vss)
    speed = REHEARSAL_SPEEDUP if run.rehearsal else 1.0
    due = []
    for at, kind, req in gen.open_requests(mix, kinds, lay, run.seed):
        if at / speed >= run.seconds:
            break
        due.append((at / speed, kind, req))
    if not due:
        raise BenchError("no request is due in the window")
    keep = sample(due, run.seed)
    svc = VSSService(vss)
    run.closers.insert(0, svc.close)
    reg = svc.registry
    width0 = reg.histogram_values("vss_serve_coalesce_width")
    answers = os.path.join(run.tmp, "answers")
    os.makedirs(answers)
    t0 = time.time() + START_S
    schedule = os.path.join(run.tmp, "schedule.json")
    with open(schedule, "w") as f:
        json.dump({"url": svc.url, "t0": t0, "t_end": t0 + run.seconds,
                   "requests": [[at, f"tenant{i % mix['tenants']}",
                                 json.dumps(req), i in keep]
                                for i, (at, _, req) in enumerate(due)]}, f)
    child = None
    try:
        child = _spawn_client(schedule, answers)
        run.open_window(t0)
        try:
            child.wait(timeout=run.seconds + START_S + DRAIN_S + 30.0)
        except BaseException:
            child.kill()
            child.wait()
            raise
        run.close_window()
        if child.returncode != 0:
            raise BenchError(f"the load generator exited {child.returncode}")
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
    with open(os.path.join(answers, "client.json")) as f:
        results = json.load(f)
    width1 = reg.histogram_values("vss_serve_coalesce_width")
    done_ms, errors, kept = [], [], []
    for i, ((_, kind, req), res) in enumerate(zip(due, results)):
        ok = res is not None and res["status"] == 200
        if ok:
            done_ms.append(1e3 * res["latency_s"])
        else:
            errors.append(f"{kind}: "
                          f"{'no answer' if res is None else res['status']}")
        if i in keep:
            kept.append((req, _segments(answers, i, res["segments"])
                         if ok else None))
    # each request's HTTP exchange names the idle gaps of a trace
    run.profiler.calls += [["bench.http_request", int(r["sent"] * 1e9),
                            int(r["done"] * 1e9)]
                           for r in results if r is not None]
    late = sorted(1e3 * r["late_s"] for r in results if r is not None)
    print(f"generator: {len(late)} of {len(due)} requests sent; late ms "
          f"p50 {stats.percentile(late, 50):.3f} p99 "
          f"{stats.percentile(late, 99):.3f} max {late[-1]:.3f}",
          file=sys.stderr, flush=True)
    spans = [s for s in vss.recent_traces()
             if s["name"] == "read" and s["t_wall"] >= run.t0]
    print(_load_line(run, due, results, spans), file=sys.stderr, flush=True)
    metrics = {}
    for name, p in (("query_p50_ms", 50), ("query_p75_ms", 75)):
        v = stats.percentile_of_attempts(done_ms, len(errors), p)
        if not math.isfinite(v):
            raise BenchError(f"{name} falls on failed requests: "
                             f"{len(errors)} of {len(due)}, e.g. {errors[:3]}")
        metrics[name] = v
    return {
        "attempted": len(due), "failed": len(errors), "errors": errors[:3],
        "metrics": metrics,
        "ctx": {"spans": spans, "client_ms": done_ms,
                "coalesce": (width1[1] - width0[1], width1[2] - width0[2]),
                "generator_late_ms": late,
                # every (h, w) of the frames the cell's kernels work on
                "frame_sizes": sorted(
                    {(lay.h, lay.w)} | {gen.out_shape(k, v, lay)[::-1]
                                        for k in kinds.values()
                                        for v in gen.variants(k)})},
        "answers": kept,
    }


def _load_line(run, due, results, spans) -> str:
    """How the load went, for a rate sweep: percentiles above those
    reported, the median of the requests due in each third of the window
    (a backlog that grows shows as thirds that rise), and the seconds in
    which a ``read`` was running (the dispatcher's busy time)."""
    from bench import stats

    lat = [1e3 * r["latency_s"] if r is not None and r["status"] == 200
           else math.inf for r in results]
    thirds = [[x for (at, _, _), x in zip(due, lat)
               if k * run.seconds / 3 <= at < (k + 1) * run.seconds / 3]
              for k in range(3)]
    busy, end = 0.0, run.t0
    for a, b in sorted((s["t_wall"], s["t_wall"] + s["dur_s"])
                       for s in spans):
        a = max(a, end)
        if b > a:
            busy, end = busy + b - a, b
    return ("load: latency ms p80 {:.1f} p90 {:.1f}; p50 by third of the "
            "window {}; reads running {:.3f} s ({} requests)".format(
                stats.percentile(lat, 80), stats.percentile(lat, 90),
                " ".join(f"{stats.percentile(t, 50):.1f}" if t else "-"
                         for t in thirds), busy, len(due)))


def _segments(folder: str, i: int, n: int) -> list:
    out = []
    for j in range(n):
        with open(os.path.join(folder, f"{i}.{j}.seg"), "rb") as f:
            out.append(f.read())
    return out


def sample(due, seed: int) -> set:
    """Indices of the answers that are checked: ``PER_KIND`` of each kind
    drawn from the seed among the requests due in the window (all of a
    kind that has fewer), and the request of the most frames."""
    from bench import gen

    rng = gen.rng_for(seed, 11)
    by_kind = {}
    for i, (_, kind, _) in enumerate(due):
        by_kind.setdefault(kind, []).append(i)
    keep = set()
    for kind in sorted(by_kind):
        idx = by_kind[kind]
        keep |= {int(i) for i in rng.choice(idx, min(PER_KIND, len(idx)),
                                            replace=False)}

    def frames(i):
        s, e = due[i][2]["t"]
        return e - s

    return keep | {max(range(len(due)), key=frames)}


def warm_up(run, vss) -> None:
    """Compiles every GOP shape the mix can encode or decode (enumerated
    from its kinds, `gen.warmup_codec_shapes`), then reads one request
    of each kind and variant on a one-camera store of its own."""
    import numpy as np

    from bench import gen
    from bench.run import build_store, open_store, read_spec
    from repro import codec

    lay, mix, kinds = run.layout, run.mix, run.kinds
    encodes, decodes = gen.warmup_codec_shapes(mix, kinds, lay)
    for shape in sorted(set(encodes) | set(decodes)):
        name, t, h, w = shape
        enc = codec.encode_gop(np.zeros((t, h, w, 3), np.uint8), name,
                               use_pallas=vss.use_pallas)
        if shape in decodes:
            codec.decode_gop(enc, use_pallas=vss.use_pallas)
    longest = gen.longest_frames(mix, kinds, lay)
    wlay = gen.Layout(run.cfg, cameras=1, gops=-(-longest // lay.gop) + 1)
    wvss = open_store(os.path.join(run.tmp, "warmup"), run.cfg)
    try:
        build_store(wvss, wlay, run.seed)
        rng = gen.rng_for(run.seed, 13)
        wmix = dict(mix, cache=False)
        wvss.read_batch([read_spec(gen.kind_request(k, v, wlay, rng, wmix))
                         for k in kinds.values() for v in gen.variants(k)])
    finally:
        wvss.close()


def _spawn_client(schedule: str, out_dir: str):
    import subprocess

    return subprocess.Popen([sys.executable, os.path.abspath(__file__),
                             schedule, out_dir])


if __name__ == "__main__":
    client(sys.argv[1], sys.argv[2])
