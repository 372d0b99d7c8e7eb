"""The harness without a chip: it refuses to measure on the CPU, a CPU
rehearsal of each cell prints no result line, and a configuration, a
traffic mix and a per-layer metric added as new files (with their
entries in ``BENCHMARK.json``) run through it unchanged."""
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


def test_new_files_run_through_the_harness_unchanged(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    with open(root / "bench" / "configs" / "vroad-2k.json") as f:
        cfg = json.load(f)
    cfg.update(name="tiny-cam", width=320, height=180, cameras=1,
               stored_seconds=2)
    (root / "bench" / "configs" / "tiny-cam.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny_scan.json").write_text(json.dumps({
        "loop": "closed_read_batch", "consumers": 1,
        "request": {"gops": 1, "codec": "rgb", "cache": False}}))
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
    bench["workloads"].append({
        "name": "tiny.scan", "config": "tiny-cam", "traffic": "tiny_scan",
        "chips": 1, "why": "checks that new files suffice"})
    for m in bench["end_to_end"]:
        if m["name"] == "read_Mpx_per_s":
            m["workloads"].append("tiny.scan")
    bench["per_layer"].append({
        "name": "reads_per_s.tiny", "unit": "1/s", "better": "higher",
        "source": "program_span", "layer": "read executor",
        "moves": "read_Mpx_per_s", "workloads": ["tiny.scan"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    args = argparse.Namespace(workload="tiny.scan", seed=5, seconds=1.0,
                              trace=1, cpu_rehearsal=True, control=False,
                              trace_dump=None)
    result = run.run_cell(args, root=str(root))
    assert result["correct"]
    assert result["metrics"]["reads_per_s.tiny"]["value"] > 0
    assert "breakdown" in result and "busy_s" in result["device"]
    args.trace = 0
    result = run.run_cell(args, root=str(root))
    assert set(result["metrics"]) == {"setup_s", "read_Mpx_per_s"}
