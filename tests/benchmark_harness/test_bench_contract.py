"""`BENCHMARK.json` as committed: the shape the driver's contract asks for, and
every name in it backed by the file the harness will look for."""

import json
import os
import re

import pytest

from bench_helpers import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def test_top_level_keys_and_limits(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "benchmark/run.py"]
    assert spec["paths"][0] == "benchmark" and 1 <= len(spec["paths"]) <= 16
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert 1 <= len(spec["configs"]) <= 24 and 1 <= len(spec["workloads"]) <= 24
    assert 1 <= len(spec["end_to_end"]) <= 16 and 1 <= len(spec["per_layer"]) <= 128


def test_configs_have_their_files(spec):
    files = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("benchmark/configs/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert {"suite", "scale_factor", "guarantees", "float_rel_limit", "assumed"} <= set(cfg)
        assert all(k in cfg["reduced"] for k in c["reduced"])
        assert all(1 <= len(c[k]) <= 200 and "\n" not in c[k] for k in ("source", "why"))
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}


def test_cells_name_files_that_exist(spec):
    seen = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        for kind in ("queries", "reference", "datagen"):
            assert os.path.isfile(os.path.join(BENCH, kind, traffic["suite"] + ".py"))
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, len(spec["workloads"]) // 2)


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        # each cell that reads it reports the end-to-end metric it should move
        moved_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", moved_in)) <= moved_in
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:
        reported = [m for m in spec["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(reported) >= 2 and any(m["name"] == "setup_s" for m in reported)
        assert any(cell in m.get("workloads", cells) for m in spec["per_layer"])


def test_files_under_paths_are_named_from_the_characters_of_a_name(spec):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in spec["paths"]:
        for d, _dirs, files in os.walk(os.path.join(REPO, base)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), REPO)), f
