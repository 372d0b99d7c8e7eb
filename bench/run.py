"""Run one benchmark cell of VSS once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <cell> --cpu-rehearsal   # tiny, CPU, no result

The cell, its configuration (``bench/configs/<config>.json``) and its
traffic mix (``bench/traffic/<mix>.json``) are found by the names in
``BENCHMARK.json``; the mix names its loop (``bench/loops/<loop>.py``)
and its request kinds (``bench/kinds/<kind>.json``); per-layer metrics
are read by ``bench/layers/<metric>.py``. One run builds the cell's store from the
seed, warms up every shape its traffic asks for, measures for
``--seconds`` seconds, reads the device's peak memory, closes the
program, and compares a seeded sample of the answers with the plain
reference (``bench/reference.py``). The last line of stdout is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, then ``checks``); the numbers
compared are also the last lines of stderr. Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.

``--control`` serves the benchmark's own proofs, not its measurements:
it also compares the control (the reference one codec tier lower, in
the program's place) and reports its numbers.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
if __name__ == "__main__":  # the loops import this harness as bench.run
    sys.modules.setdefault("bench.run", sys.modules[__name__])

from bench import gen, reference, trace_reduce, video  # noqa: E402

OVERRIDES = ("VSS_USE_PALLAS", "REPRO_FORCE_PALLAS")
OFF_KERNEL = ("oracle", "interpret")  # codec paths a chip run may not take
TRACE_S = 3.0  # profiler span in the middle of a --trace 1 window
REFERENCE_THREADS = 4  # GOPs the reference reconstructs at once
# CPU rehearsal: the same phases at a size the CPU gets through quickly
REHEARSAL = {"width": 128, "height": 72, "fps": 6, "gop_frames": 6,
             "cameras": 2, "stored_seconds": 4}


class BenchError(RuntimeError):
    """A run that cannot give a result; it exits non-zero."""


@contextlib.contextmanager
def phase(name: str):
    """Prints how long the block took, to stderr."""
    t = time.time()
    yield
    print(f"{name}: {time.time() - t:.3f} s", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(root: str, name: str):
    bench = load_json(root, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(root, entry["file"])
    mix = load_json(root, "bench", "traffic", cell["traffic"] + ".json")
    return bench, cell, cfg, mix


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


def load_module(root: str, folder: str, name: str):
    """``bench/<folder>/<name>.py`` as a module of its own."""
    path = os.path.join(root, "bench", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_layer(root: str, name: str, ctx) -> Optional[float]:
    return load_module(root, "layers", name).read(ctx)


def run_loop(root: str, run) -> Dict:
    """Runs the mix's loop, ``bench/loops/<loop>.py``."""
    name = run.mix["loop"]
    return getattr(load_module(root, "loops", name), name)(run)


def load_kinds(root: str, mix: dict) -> Dict[str, dict]:
    """The request kinds a mix names, ``bench/kinds/<kind>.json``."""
    return {e["kind"]: load_json(root, "bench", "kinds", e["kind"] + ".json")
            for e in mix.get("requests", [])}


class CompileCounter:
    """Counts JAX compile events, and those inside the measured window."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.window = None
        self.total = {e: 0 for e in self.EVENTS}
        self.inside = {e: 0 for e in self.EVENTS}
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event in self.total:
            with self._lock:
                self.total[event] += 1
                if self.window is not None and self.window[0] <= time.time() \
                        <= self.window[1]:
                    self.inside[event] += 1


# -- the store ---------------------------------------------------------------

def open_store(root: str, cfg: dict):
    """A store on the configuration's backend, whatever the environment
    says; every request of a window keeps its span tree (the ring's
    default capacity would drop the start of a long window)."""
    from repro.core.config import VSSConfig
    from repro.core.store import VSS

    return VSS(root, config=VSSConfig(backend=cfg["backend"],
                                      trace_capacity=1 << 16))


def build_store(vss, lay: gen.Layout, seed: int) -> None:
    """``lay.cameras`` cameras of ``lay.gops`` GOPs, written GOP by GOP
    through pipelined writers, one thread per camera, with the
    configuration's writer fields (``lay.write``)."""
    from repro.core.spec import WriteSpec

    errors: List[BaseException] = []

    def write(cam: int) -> None:
        try:
            src = video.Camera(lay.w, lay.h, video.camera_seed(seed, cam))
            w = vss.writer_spec(WriteSpec(name=f"cam{cam}", fps=lay.fps,
                                          codec=lay.codec,
                                          gop_frames=lay.gop, **lay.write))
            for g in range(lay.gops):
                w.append(src.frames(g * lay.gop, (g + 1) * lay.gop))
            w.close()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=write, args=(c,))
               for c in range(lay.cameras)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def read_spec(req: dict):
    from repro.core.spec import ReadSpec

    return ReadSpec(**{k: tuple(v) if isinstance(v, list) else v
                       for k, v in req.items()})


# -- the traced part of a window ---------------------------------------------

class Profiler:
    """Traces ``TRACE_S`` seconds in the middle of a window, on a thread
    of its own, when asked to."""

    def __init__(self, on: bool, tmp: str):
        self.on, self.dir = on, os.path.join(tmp, "trace")
        self.thread = None
        self.error: Optional[BaseException] = None
        # the benchmark's calls into the program, [name, start_ns, end_ns]
        # on the wall clock: a span that began before the trace started
        # still names the idle gaps inside it
        self.calls: List[list] = []
        self.window_wall_ns = 0
        if on:
            import jax
            import jax.numpy as jnp

            # a tiny device op, run under a host annotation at each end of
            # the traced window: the pairs put the device's events on the
            # host's clock (the planes of a TPU trace need not share one)
            self.mark = jax.jit(_clock_mark)
            self.mark_arg = jnp.zeros((8, 128), jnp.float32)
            self.mark(self.mark_arg).block_until_ready()

    def _clock_mark(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation(trace_reduce.MARK):
            self.mark(self.mark_arg).block_until_ready()

    def start(self, t0: float, seconds: float, span: float) -> None:
        if not self.on:
            return
        span = min(span, seconds)
        at = t0 + max(0.0, (seconds - span) / 2)
        self.thread = threading.Thread(target=self._run, args=(at, span))
        self.thread.start()

    def _run(self, at: float, span: float) -> None:
        import jax

        try:
            time.sleep(max(0.0, at - time.time()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the annotations alone
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                self._clock_mark()
                self.window_wall_ns = time.time_ns()
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    time.sleep(span)
                self._clock_mark()
            finally:
                with phase("trace stop"):
                    jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 - re-raised by reduce()
            self.error = e

    def reduce(self, dump: Optional[str]):
        if not self.on:
            return None
        self.thread.join()
        if self.error is not None:
            raise self.error
        with phase("trace load"):
            raw = trace_reduce.load(self.dir)
        w0 = min(s for n, s, _ in raw["host"] if n == trace_reduce.WINDOW)
        shift = w0 - self.window_wall_ns
        raw["host"] += [[n, a + shift, b - a] for n, a, b in list(self.calls)]
        if dump:
            with open(dump, "w") as f:
                json.dump(raw, f)
        return trace_reduce.reduce(raw)


def _clock_mark(x):
    return x + 1.0


_clock_mark.__name__ = trace_reduce.MARK_PROGRAM


@contextlib.contextmanager
def annotate(run, name: str):
    """Records one call of the benchmark into the program, by name."""
    a = time.time_ns()
    try:
        yield
    finally:
        run.profiler.calls.append([name, a, time.time_ns()])


# -- warm-up -----------------------------------------------------------------

def warm_up(run) -> None:
    """Every shape the mix asks for: its request on a one-camera store of
    its own, written (encoded) and read (decoded) at the cell's sizes."""
    wlay = gen.Layout(run.cfg, cameras=1, gops=gen.warmup_gops(run.mix))
    vss = open_store(os.path.join(run.tmp, "warmup"), run.cfg)
    try:
        build_store(vss, wlay, run.seed)
        vss.read_batch([read_spec(gen.warmup_request(run.mix, wlay))])
    finally:
        vss.close()


# -- correctness ---------------------------------------------------------------

def check(run, out: Dict, shift: int) -> Dict[str, float]:
    """The numbers compared for this run's answers, each ``(request,
    frames or served segments)``: the program's with ``shift`` 0, the
    control's (the reference one tier lower, put in the program's place)
    with ``shift`` 1."""
    lay = run.layout
    cams = [video.Camera(lay.w, lay.h, video.camera_seed(run.seed, cam))
            for cam in range(lay.cameras)]

    def source(cam: int, g: int) -> np.ndarray:
        return cams[cam].frames(g * lay.gop, (g + 1) * lay.gop)

    tiled = tuple(lay.write.get("tiles", (1, 1))) != (1, 1)
    want = reference.Store(source, fps=lay.fps, gop=lay.gop, tier=lay.codec,
                           tiled=tiled)
    ctrl = reference.Store(source, fps=lay.fps, gop=lay.gop, tier=lay.codec,
                           shift=1, tiled=tiled) if shift else None
    reqs = [req for req, _ in out["answers"]]
    for store in (want, ctrl) if shift else (want,):
        store.fill(reqs, REFERENCE_THREADS)
    n = 0
    for req, answer in out["answers"]:
        exp = want.expect(req)
        if shift:
            got = ctrl.expect(req)
        elif isinstance(answer, list):  # served segments
            got = reference.decode_segments(answer)
        else:
            got = answer
        n += reference.frames_differing(got, exp)
    return {"frames_differing": n}


# every comparison is exact: reads are exact against the stored bytes,
# which the plain reference reproduces bit for bit
LIMITS = {"frames_differing": 0}


# -- one run -----------------------------------------------------------------

def rehearsal_cfg(cfg: dict, mix: dict, kinds: Dict[str, dict]) -> dict:
    """``cfg`` at `REHEARSAL`'s sizes, storing enough seconds for the
    mix's longest request off any frame of a GOP."""
    out = {**cfg, **REHEARSAL}
    lay = gen.Layout(out)
    gops = -(-(gen.longest_frames(mix, kinds, lay) + lay.gop - 1) // lay.gop)
    out["stored_seconds"] = max(out["stored_seconds"],
                                gops * lay.gop / lay.fps)
    return out


class Run:
    def __init__(self, args, bench, cell, cfg, mix, tmp, root=ROOT):
        self.args = args
        if getattr(args, "rate", None):
            mix = dict(mix, rate_per_s=args.rate)
        self.bench, self.cell, self.mix = bench, cell, mix
        self.rehearsal = args.cpu_rehearsal
        self.kinds = load_kinds(root, mix)
        self.cfg = rehearsal_cfg(cfg, mix, self.kinds) if self.rehearsal \
            else cfg
        self.layout = gen.Layout(self.cfg)
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.tmp = tmp
        self.closers: List = []
        self.t0 = self.t_end = None
        self.compiles = CompileCounter()
        self.profiler = Profiler(bool(args.trace), tmp)

    def open_window(self, t0: Optional[float] = None) -> None:
        import jax

        jax.config.update("jax_log_compiles", True)  # names any in-window
        self.t0 = time.time() if t0 is None else t0
        self.t_end = self.t0 + self.seconds
        self.setup_s = self.t0 - T_PROCESS
        self.compiles.window = (self.t0, float("inf"))
        self.profiler.start(self.t0, self.seconds, TRACE_S)

    def close_window(self, at: Optional[float] = None) -> None:
        import jax

        jax.config.update("jax_log_compiles", False)
        self.compiles.window = (self.t0, time.time() if at is None else
                                max(at, time.time()))
        print(f"window: closed {time.time() - self.t0:.3f} s after it "
              "opened", file=sys.stderr, flush=True)

    def spans(self, vss) -> List[dict]:
        return [s for s in vss.recent_traces()
                if s["name"] == "read" and self.t0 <= s["t_wall"] < self.t_end]


def run_cell(args, root: str = ROOT) -> Dict:
    """One run of a cell; returns the result object (not printed)."""
    import jax

    bench, cell, cfg, mix = load_cell(root, args.workload)
    backend = jax.default_backend()
    if not args.cpu_rehearsal:
        if backend != "tpu":
            raise BenchError(f"JAX's default backend is {backend!r}, not "
                             "'tpu'; nothing was run")
        if len(jax.devices()) < cell["chips"]:
            raise BenchError(f"{len(jax.devices())} chips, the cell asks for "
                             f"{cell['chips']}")
        bad = [k for k in OVERRIDES if k in os.environ]
        if bad:
            raise BenchError(f"{bad} set; the default kernel selection runs")
    if not args.cpu_rehearsal:  # a CPU-built cache is of no use on a chip
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(root, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401 - the system under test; absent -> exit != 0
    from repro.kernels import ops

    with tempfile.TemporaryDirectory(prefix="vss-bench-") as tmp:
        run = Run(args, bench, cell, cfg, mix, tmp, root)
        jax.monitoring.register_event_duration_secs_listener(run.compiles)
        try:
            out = run_loop(root, run)
            dev = jax.devices()[0]
            mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
            reduced = run.profiler.reduce(args.trace_dump)
        finally:
            jax.monitoring.unregister_event_duration_listener(run.compiles)
            jax.config.update("jax_log_compiles", False)
            with phase("close"):
                for close in run.closers:
                    close()
        off = {f"{op}/{path}": int(n)
               for (op, path), n in ops.dispatch_counts().items()
               if n and path in OFF_KERNEL}
        if off and not args.cpu_rehearsal:
            raise BenchError(f"codec calls off the compiled kernels: {off}")
        print(f"compile events in the window: {run.compiles.inside} "
              f"(whole run: {run.compiles.total})", file=sys.stderr,
              flush=True)
        if out["failed"]:
            print(f"failed: {out['failed']} of {out['attempted']}, e.g. "
                  f"{out['errors']}", file=sys.stderr, flush=True)
        with phase("check"):
            numbers = check(run, out, 0)
        control = check(run, out, 1) if args.control else None
    metrics = {"setup_s": run.setup_s, **out["metrics"]}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    result_metrics = {}
    if args.trace:
        peaks = load_json(root, "bench", "peaks.json")
        ctx = SimpleNamespace(cell=cell, cfg=run.cfg, t0=run.t0,
                              t_end=run.t_end, trace=reduced,
                              device_kind=dev.device_kind, peaks=peaks,
                              **out["ctx"])
        for m in cell_metrics(bench, cell["name"], True):
            v = read_layer(root, m["name"], ctx)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, cell["name"], False):
            result_metrics[m["name"]] = {"value": metrics[m["name"]],
                                         "unit": units[m["name"]]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": all(numbers[k] <= LIMITS[k] for k in numbers),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": result_metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if control is not None:
        result["control"] = {k: {"value": v, "limit": LIMITS[k]}
                             for k, v in control.items()}
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in numbers.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU; never prints a result")
    ap.add_argument("--control", action="store_true",
                    help="also compare the control (the reference one "
                         "codec tier lower) and report its numbers")
    ap.add_argument("--rate", type=float, default=None,
                    help="requests/s of an open loop in place of the mix's "
                         "(for a rate sweep)")
    ap.add_argument("--trace-dump", default=None,
                    help="write the loaded trace (device ops, bench spans) "
                         "as JSON to this path")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    peak_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    print(f"host peak RSS: {peak_kib / 2 ** 20:.3f} GiB", file=sys.stderr)
    for k, v in list(result.get("control", {}).items()):
        print(f"control {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    if args.cpu_rehearsal:
        print(f"cpu rehearsal of {args.workload}: correct="
              f"{result['correct']}; it reports no result")
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
