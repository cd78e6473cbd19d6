"""BENCHMARK.json and the data files against the contract's form: allowed
characters, every file a cell names exists, every metric has a reader the
harness knows, every arrow points at a metric its cells report."""

import json
import os
import re

import pytest

import readers
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(ROOT, "BENCHMARK.json")


def one_line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_form(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    assert all(one_line(w) for w in bench["command"])
    assert not any(w.startswith("/") or ".." in w for w in bench["command"])


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) \
            and one_line(c["why"])
        assert PATH.match(c["file"]) and c["file"].startswith("benchmarks/")
        assert c["file"] not in files
        files.add(c["file"])
        spec = load(ROOT, c["file"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        # the file says why for every cut the entry lists, and no other
        assert sorted(spec["reduced"]) == sorted(c["reduced"])
        assert set(spec["guarantees"]) >= {"ranking", "answered_by"}
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_cells_name_files_that_exist(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 2)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert w["config"] in {c["name"] for c in bench["configs"]}
        tr = load(BENCH, "traffic", w["traffic"] + ".json")
        assert tr["driver"] in {"served-closed", "served-open",
                                "batch-closed"}


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    every = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
        spec = load(BENCH, "metrics", m["name"] + ".json")
        assert spec["reader"] in readers.READERS
        assert spec["unit"] == m["unit"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert one_line(m["layer"])
        # every cell that reads this metric reports the metric it moves
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if "_roofline" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2, f"{cell}: setup_s and one more"
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_every_file_under_paths_is_named_from_allowed_characters(bench):
    for root, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".jax_cache",
                                                ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), ROOT)
            assert PATH.match(rel), rel
