"""The benchmark's ``msmarco2m-top1000.batch`` cell, as data and end to end.

The cell is data files (a configuration, three metric files, entries of
``BENCHMARK.json``) over a harness this PR does not touch, so what holds
it is here, in tier-1: the configuration is ``msmarco2m``'s but for the
depth, every ``.batch`` metric ``msmarco2m.batch`` reports is reported by
the new cell too, and a traced rehearsal (the same code at a tiny size on
the CPU: never a number) ends in a result line that is ``correct`` at
1,000 hits a query and carries the new host metrics. The device's own
metric cannot be read on the CPU; its patterns are held to the names the
v5e's trace gave the deep selection (PERF.md section 3).
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL, CONTROL = "msmarco2m-top1000.batch", "msmarco2m.batch"
NEW_METRICS = {"hit_names_ms.batch": "program_span",
               "hit_objects_ms.batch": "program_span",
               "topk_select_ms.batch": "device_trace"}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(ROOT, "BENCHMARK.json")


def test_configuration_is_msmarco2m_but_for_the_depth():
    base = load(BENCH, "configs", "msmarco2m.json")
    deep = load(BENCH, "configs", "msmarco2m-top1000.json")
    assert deep["scoring"].pop("top_k") == 1000
    assert base["scoring"].pop("top_k") == 10
    # every key the harness or the engine reads: shapes, laws, scoring,
    # the engine's fields, the rehearsal's sizes
    for key in ("corpus_seed", "docs", "vocab", "doc_len_mean",
                "doc_len_min", "zipf_a", "query_terms", "scoring",
                "engine_config", "unique_term_capacity", "leader_env",
                "rehearse"):
        assert deep[key] == base[key], key
    assert set(deep) == set(base)
    assert {k: deep["layout"][k] for k in ("chips", "workers", "routers",
                                           "index")} \
        == {k: base["layout"][k] for k in ("chips", "workers", "routers",
                                           "index")}
    # the same cut, so the same reasons; nothing assumed but what
    # msmarco2m assumes
    assert sorted(deep["reduced"]) == sorted(base["reduced"])
    assert deep["reduced"]["docs"].startswith(base["reduced"]["docs"])
    assert {k: v for k, v in deep["assumed"].items() if k != "top_k"} \
        == base["assumed"]
    assert deep["name"] == "msmarco2m-top1000"
    for word in ("top1000.dev", "-hits 1000", "1611.09268"):
        assert word in deep["source"], word
    assert "top-1,000" in deep["guarantees"]["ranking"]


def test_cell_and_configuration_entries(bench):
    cfg, = [c for c in bench["configs"] if c["name"] == "msmarco2m-top1000"]
    assert cfg["file"] == "benchmarks/configs/msmarco2m-top1000.json"
    assert cfg["reduced"] == ["docs", "workers"]
    assert len(cfg["source"]) <= 200 and "-hits 1000" in cfg["source"]
    base, = [c for c in bench["configs"] if c["name"] == "msmarco2m"]
    assert cfg["source"] != base["source"]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("msmarco2m-top1000", "batch", 1)
    # new entries went at the end of their lists: right behind the six
    # configurations and seven cells that PR 38 found (later PRs append)
    assert bench["configs"].index(cfg) == 5
    assert bench["workloads"].index(cell) == 7


def test_every_batch_metric_of_the_control_lists_the_cell(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["batch_qps"]["workloads"].index(CELL) \
        == e2e["batch_qps"]["workloads"].index(CONTROL) + 1
    assert "workloads" not in e2e["setup_s"]        # every cell's
    listed = {m["name"] for m in bench["per_layer"]
              if CONTROL in m.get("workloads", ())}
    assert len(listed) == 11 and "ell_kernel_roofline.batch" in listed
    mine = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert mine == listed | set(NEW_METRICS)
    # no block of this corpus is wider than 256
    assert "ell_kernel_wide_ms.batch" not in mine


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_new_metric_is_a_file_over_a_reader_the_harness_has(bench, name):
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "batch_qps"
    assert entry["source"] == NEW_METRICS[name]
    spec = load(BENCH, "metrics", name + ".json")
    assert (spec["unit"], spec["layer"]) == (entry["unit"], entry["layer"])
    with open(os.path.join(BENCH, "lib", "readers.py")) as f:
        assert f'"{spec["reader"]}":' in f.read()
    if spec["reader"] == "host-annotation":
        # the span is one of the program's one timer
        with open(os.path.join(ROOT, "tfidf_tpu", "engine",
                               "searcher.py")) as f:
            assert f'trace_phase("{spec["span"]}")' in f.read()


# names of the v5e's trace (``XLA Ops`` line, the HLO text; read in the
# parent's traced run of the cell, shortened after the operands): what the
# per-chunk ``lax.top_k(., 1000)`` lowers to, and what it does not
SELECTION = [
    "%sort.9 = (f32[512,128000]{0,1:T(8,128)}, s32[512,128000]{0,1:T(8,128)})"
    " sort(f32[512,128000]{0,1:T(8,128)} %copy.50, s32[512,128000]{0,1:"
    "T(8,128)} %copy.51), dimensions={1}, to_apply=%compare-greater-than.0",
    "%sort.8 = (f32[65536,1024]{0,1:T(8,128)}, s32[65536,1024]{0,1:T(8,128)})"
    " sort(f32[65536,1024]{0,1:T(8,128)} %copy.46, s32[65536,1024]{0,1:"
    "T(8,128)} %iota.32.clone), dimensions={1}, to_apply=%compare-greater",
    "%reshape.14 = f32[65536,1024]{1,0:T(8,128)} reshape(f32[512,131072]"
    "{1,0:T(8,128)} %dynamic-slice_select_fusion)",
    "%slice_add_fusion = s32[65536,1000]{1,0:T(8,128)} fusion(s32[65536,1024]"
    "{1,0:T(8,128)} %copy.49, s32[65536]{0:T(1024)S(1)} %multiply_bitcast",
    "%copy.51 = s32[512,128000]{0,1:T(8,128)} copy(s32[512,128000]{1,0:"
    "T(8,128)} %reshape.17)",
    "%iota.32.clone = s32[65536,1024]{0,1:T(8,128)} iota(), iota_dimension=1",
]
NOT_SELECTION = [
    # the merge of the 20 chunks' winners: a STABLE sort
    "%sort.7 = (f32[512,20000]{0,1:T(8,128)}, s32[512,20000]{0,1:T(8,128)})"
    " sort(f32[512,20000]{0,1:T(8,128)} %bitcast.54, s32[512,20000]{0,1:"
    "T(8,128)S(1)} %iota.14.clone), dimensions={1}, is_stable=true, to_app",
    # the mask, the merge's gather, the kernel
    "%dynamic-slice_select_fusion = f32[512,131072]{1,0:T(8,128)} fusion("
    "f32[512,1048576]{1,0:T(8,128)} %get-tuple-element.13, s32[]{:T(128)S(6)}",
    "%fusion = s32[512000]{0:T(1024)S(1)} fusion(s32[512,20000]{1,0:T(8,128)"
    "S(1)} %copy.28, s32[512000]{0:T(1024)S(1)} %reshape.65), kind=kCustom",
    "%ell_score_v4_w48.1 = f32[512,1048576]{1,0:T(8,128)} custom-call(s32[3]"
    "{0:T(128)S(1)} %add_add_fusion.1), custom_call_target=\"tpu_custom_call\"",
]


def test_deep_selection_patterns_read_the_names_of_the_v5e_trace():
    spec = load(BENCH, "metrics", "topk_select_ms.batch.json")
    assert spec["reader"] == "device-ops" and spec["per_span"] == "score"
    pats = [re.compile(p) for p in spec["patterns"]]
    for name in SELECTION:
        assert any(p.search(name) for p in pats), name
    for name in NOT_SELECTION:
        assert not any(p.search(name) for p in pats), name


def test_traced_rehearsal_is_correct_and_reports_the_new_metrics(bench):
    """``benchmarks/run.py --rehearse`` of the cell: 20,000 documents,
    32 queries a call, 1,000 hits a query, the interpreted kernel.
    ``correct`` compares 64 answers x up to 1,000 hits with the float64
    reference."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--rehearse"], cwd=ROOT, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert "answers_compared = 64" in p.stdout
    got = line["metrics"]
    want = {m["name"]: m for m in bench["per_layer"]
            if CELL in m["workloads"]}
    for name, m in want.items():
        if m["source"] in ("device_trace", "program_counter"):
            # no device plane and no memory_stats on the CPU: the chip's
            continue
        assert name in got, (name, sorted(got))
        assert got[name]["value"] >= 0 and got[name]["unit"] == m["unit"]
    inner = got["hit_names_ms.batch"]["value"] \
        + got["hit_objects_ms.batch"]["value"]
    assert 0 < inner <= got["assemble_ms.batch"]["value"]
