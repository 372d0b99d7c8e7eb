"""``BENCHMARK.json`` against the rules a benchmark file must keep: its
keys, the characters of names and units, every metric's cells, and the
files the harness finds by name."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= len(bench["command"]) <= 32
    assert all(line(w) for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    for word in bench["command"]:
        if "/" in word:  # a file of the repo: inside the benchmark's paths
            assert any(word.startswith(p + "/") for p in bench["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert "guarantees" in cfg and "assumed" in cfg
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)


def test_workloads(bench):
    configs = {c["name"] for c in bench["configs"]}
    assert 1 <= len(bench["workloads"]) <= 24
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        assert line(w["why"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == configs, "every configuration has a cell"
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def _metric_keys(m: dict, e2e: bool) -> None:
    base = {"name", "unit", "better", "source"}
    base |= {"bound"} if e2e else {"layer", "moves"}
    assert base <= set(m) <= base | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in (SOURCES_E2E if e2e else SOURCES)


def test_end_to_end_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = bench["end_to_end"]
    assert 1 <= len(e2e) <= 16
    for m in e2e:
        _metric_keys(m, True)
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    setup = [m for m in e2e if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    for cell in cells:  # set-up and one more end-to-end metric
        assert sum(cell in m.get("workloads", cells) for m in e2e) >= 2


def test_per_layer_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    per = bench["per_layer"]
    assert 1 <= len(per) <= 128
    for m in per:
        _metric_keys(m, False)
        assert line(m["layer"])
        assert m["moves"] in e2e
        # each cell that lists the metric reports what it moves
        assert set(m.get("workloads", e2e[m["moves"]])) <= e2e[m["moves"]]
        assert os.path.isfile(os.path.join(ROOT, "bench", "layers",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in m.get("workloads", e2e[m["moves"]]) for m in per)


def test_names_are_unique(bench):
    for key in ("configs", "workloads"):
        names = [x["name"] for x in bench[key]]
        assert len(set(names)) == len(names)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
