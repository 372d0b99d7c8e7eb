"""The harness without a chip: it refuses to measure on the CPU, a CPU
rehearsal of each cell prints no result line, and a configuration, a
traffic mix, a request kind, a loop and a per-layer metric added as new
files (with their entries in ``BENCHMARK.json``) run through it
unchanged."""
import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", cells()[0], "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr


@pytest.mark.parametrize("cell", cells())
def test_cpu_rehearsal_prints_no_result_line(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", str(2 ** 40 + 7),
                   "--seconds", "1", "--cpu-rehearsal"])
    out = capsys.readouterr().out
    assert rc == 0
    assert not any(ln.lstrip().startswith("{") for ln in out.splitlines())
    assert "reports no result" in out


SERIAL_LOOP = '''"""One in-process caller reading the open loop's requests one after
another, as fast as they return."""
import os
import time

from bench import gen, stats
from bench.run import build_store, open_store, read_spec


def serial_reads(run):
    vss = open_store(os.path.join(run.tmp, "store"), run.cfg)
    run.closers.append(vss.close)
    build_store(vss, run.layout, run.seed)
    reqs = gen.open_requests(run.mix, run.kinds, run.layout, run.seed)
    vss.read_batch([read_spec(next(reqs)[2])])  # warm-up
    work, kept = [], []
    run.open_window()
    while time.time() < run.t_end:
        req = next(reqs)[2]
        issued = time.time()
        frames = vss.read_batch([read_spec(req)])[0].frames
        work.append((issued, time.time(), frames[..., 0].size / 1e6))
        kept.append((req, frames))
    run.close_window()
    return {"attempted": len(work), "failed": 0, "errors": [],
            "metrics": {"read_Mpx_per_s": stats.window_rate(work, run.t0,
                                                            run.t_end)},
            "ctx": {"spans": run.spans(vss)}, "answers": kept[-4:]}
'''


def test_new_files_run_through_the_harness_unchanged(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    with open(root / "bench" / "configs" / "vroad-2k.json") as f:
        cfg = json.load(f)
    # a writer field no configuration had: a tiled layout
    cfg.update(name="tiny-cam", width=320, height=180, cameras=1,
               stored_seconds=2, tiles=[2, 2])
    (root / "bench" / "configs" / "tiny-cam.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny_scan.json").write_text(json.dumps({
        "loop": "closed_read_batch", "consumers": 1,
        "request": {"gops": 1, "codec": "rgb", "cache": False}}))
    # a request kind no mix had: a crop, strided and pooled, in the
    # lowest tier
    (root / "bench" / "kinds" / "crop_pool.json").write_text(json.dumps({
        "seconds": 1, "codec": "tvc-lo", "downscale": 2, "fps_div": 2,
        "roi": {"sides": [0.5], "grid": 2, "zipf_s": 1.0}}))
    served = {"loop": "open_http", "rate_per_s": 2.0, "block": 4,
              "tenants": 2, "cache": False, "camera_zipf_s": 1.1,
              "start_zipf_s": 1.0,
              "requests": [{"kind": "crop_pool", "share": 0.5},
                           {"kind": "trim", "share": 0.5}]}
    (root / "bench" / "traffic" / "tiny_served.json").write_text(
        json.dumps(served))
    # a loop no mix had, and a mix that runs it
    (root / "bench" / "loops" / "serial_reads.py").write_text(SERIAL_LOOP)
    (root / "bench" / "traffic" / "tiny_serial.json").write_text(json.dumps(
        dict(served, loop="serial_reads",
             requests=[{"kind": "crop_pool", "share": 1.0}])))
    (root / "bench" / "layers" / "reads_per_s.tiny.py").write_text(
        '"""Reads the program traced per second of the window."""\n\n\n'
        "def read(ctx):\n"
        "    return len(ctx.spans) / (ctx.t_end - ctx.t0)\n")
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-cam", "source": "a throw-away configuration",
        "file": "bench/configs/tiny-cam.json",
        "reduced": cfg["reduced"], "why": "checks that new files suffice"})
    tiny = {"tiny.scan": "tiny_scan", "tiny.served": "tiny_served",
            "tiny.serial": "tiny_serial"}
    for name, mix in tiny.items():
        bench["workloads"].append({
            "name": name, "config": "tiny-cam", "traffic": mix,
            "chips": 1, "why": "checks that new files suffice"})
    for m in bench["end_to_end"]:
        if m["name"] == "read_Mpx_per_s":
            m["workloads"] += ["tiny.scan", "tiny.serial"]
        if m["name"].startswith("query_"):
            m["workloads"].append("tiny.served")
    bench["per_layer"].append({
        "name": "reads_per_s.tiny", "unit": "1/s", "better": "higher",
        "source": "program_span", "layer": "read executor",
        "moves": "read_Mpx_per_s", "workloads": list(tiny)})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    written = []
    real_build = run.build_store

    def build_store(vss, lay, seed):
        written.append(lay.write)
        return real_build(vss, lay, seed)

    monkeypatch.setattr(run, "build_store", build_store)
    want = {"tiny.scan": {"read_Mpx_per_s"},
            "tiny.served": {"query_p50_ms", "query_p75_ms"},
            "tiny.serial": {"read_Mpx_per_s"}}
    for name in tiny:
        args = argparse.Namespace(workload=name, seed=5, seconds=1.0,
                                  trace=1, cpu_rehearsal=True, control=False,
                                  trace_dump=None)
        result = run.run_cell(args, root=str(root))
        assert result["correct"], name
        assert written and all(w == {"tiles": (2, 2)} for w in written)
        assert result["checks"]["frames_differing"]["value"] == 0
        assert result["metrics"]["reads_per_s.tiny"]["value"] > 0
        assert "breakdown" in result and "busy_s" in result["device"]
        args.trace = 0
        result = run.run_cell(args, root=str(root))
        assert set(result["metrics"]) == {"setup_s"} | want[name]
