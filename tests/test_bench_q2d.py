"""The benchmark's ``msmarco2m-q2d.batch`` cell, as data and end to end.

The cell is data files (a configuration, three metric files, entries of
``BENCHMARK.json``) over a harness this PR does not touch, so what holds
it is here, in tier-1: the configuration is ``msmarco2m``'s but for the
query's length (and the two sizes that follow from it: the unique-term
capacity and ``max_query_terms``); the length law gives what the
configuration says it gives (host arithmetic on the harness's one
generator: 32 seeds, a call's distinct terms inside (8,192, 16,384], no
query past 128 distinct terms, multiplicities exact in bfloat16); every
``.batch`` metric ``msmarco2m.batch`` reports is reported by the new
cell too; and a traced rehearsal (the same code at a tiny size on the
CPU: never a number) ends in a result line that is ``correct`` and
carries the new host metrics. The device's own metric cannot be read on
the CPU; its patterns are held to the names the v5e's trace gave the
query matrix (PERF.md section 5).
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path.insert(0, os.path.join(BENCH, "lib"))
import data  # noqa: E402  (benchmarks/lib: the cell's generator)

CELL, CONTROL = "msmarco2m-q2d.batch", "msmarco2m.batch"
NEW_METRICS = {"vectorize_analyze_ms.batch": "program_span",
               "vectorize_pack_ms.batch": "program_span",
               "query_matrix_ms.batch": "device_trace"}
SEEDS = range(2147546000, 2147546032)     # large, as the driver's are


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def q2d():
    return load(BENCH, "configs", "msmarco2m-q2d.json")


def test_configuration_is_msmarco2m_but_for_the_query(q2d):
    base = load(BENCH, "configs", "msmarco2m.json")
    # every shape key, to the letter: the same shard, blocks and scoring
    for key in ("corpus_seed", "docs", "vocab", "doc_len_mean",
                "doc_len_min", "zipf_a", "scoring", "layout",
                "leader_env"):
        assert q2d[key] == base[key], key
    assert q2d["scoring"]["top_k"] == 10
    assert q2d["departures"]["ingest"] == base["departures"]["ingest"]
    # what differs: the length law and the two sizes that follow from it
    assert q2d["query_terms"] == {"law": "shifted-poisson", "min": 40,
                                  "max": 256, "mean": 130}
    assert base["query_terms"]["mean"] == 6
    assert (q2d["unique_term_capacity"],
            base["unique_term_capacity"]) == (16384, 1024)
    engine = dict(q2d["engine_config"])
    assert engine.pop("max_query_terms") == 128
    assert engine == base["engine_config"]
    assert "max_query_terms" not in base["engine_config"]   # 32, the default
    # the same cut, so the same reasons
    assert sorted(q2d["reduced"]) == sorted(base["reduced"])
    assert q2d["reduced"]["docs"].startswith(base["reduced"]["docs"])
    assert q2d["reduced"]["workers"] == base["reduced"]["workers"]
    for key in ("zipf_a", "doc_len_mean", "corpus_seed"):
        assert q2d["assumed"][key] == base["assumed"][key], key
    assert q2d["name"] == "msmarco2m-q2d" and len(q2d["source"]) == 196
    for word in ("1611.09268", "2303.07678", "x5", "k1=0.9 b=0.4"):
        assert word in q2d["source"], word
    for word in ("ALL the terms", "multiplicities"):
        assert word in q2d["guarantees"]["ranking"], word
    assert "refused" in q2d["guarantees"]["no_truncation"]
    assert "full batch of 512" in q2d["guarantees"]["answered_by"]
    # the rehearsal keeps the law and the width
    assert "query_terms" not in q2d["rehearse"]
    assert q2d["rehearse"]["engine_config"]["max_query_terms"] == 128


def test_cell_and_configuration_entries(bench, q2d):
    cfg, = [c for c in bench["configs"] if c["name"] == "msmarco2m-q2d"]
    assert cfg["file"] == "benchmarks/configs/msmarco2m-q2d.json"
    assert cfg["reduced"] == ["docs", "workers"]
    assert cfg["source"] == q2d["source"]
    assert len({c["source"] for c in bench["configs"]}) \
        == len(bench["configs"])
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("msmarco2m-q2d", "batch", 1)
    assert len(cell["why"]) <= 200 and len(cfg["why"]) <= 200
    # new entries went at the end of their lists: right behind the seven
    # configurations and nine cells that PR 46 found (later PRs append)
    assert bench["configs"].index(cfg) == 7
    assert bench["workloads"].index(cell) == 9
    # ten cells, two of them on four chips
    assert len(bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in bench["workloads"][:10]) == 2
    assert load(BENCH, "traffic", "batch.json")["queries_per_call"] == 512


def test_every_batch_metric_of_the_control_lists_the_cell(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["batch_qps"]["workloads"]
    assert "workloads" not in e2e["setup_s"]        # every cell's
    listed = {m["name"] for m in bench["per_layer"]
              if CONTROL in m.get("workloads", ())}
    assert len(listed) == 11 and "ell_kernel_roofline.batch" in listed
    mine = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert mine == listed | set(NEW_METRICS)
    # appended: the cell is the last of each list it joined
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            assert m["workloads"][-1] == CELL, m["name"]
    # the three new metrics are the last three entries
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW_METRICS)


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


@pytest.fixture(scope="module")
def drawn(q2d):
    """What the law gives over 32 seeds, a call of 512 at a time (two
    calls a seed: the generator's Python pass a query is the cost)."""
    calls, widest, heaviest, tokens = [], 0, 0, []
    for seed in SEEDS:
        pool = data.make_queries(seed, 1024, vocab=q2d["vocab"],
                                 query_terms=q2d["query_terms"],
                                 zipf_a=q2d["zipf_a"])
        for lo in (0, 512):
            call = pool[lo:lo + 512]
            calls.append(data.distinct_terms(call))
            tokens.append(sum(len(q.split()) for q in call))
        for q in pool:
            counts: dict[str, int] = {}
            for tok in q.split():
                counts[tok] = counts.get(tok, 0) + 1
            widest = max(widest, len(counts))
            heaviest = max(heaviest, max(counts.values()))
    return {"calls": calls, "widest": widest, "heaviest": heaviest,
            "tokens": tokens}


def test_a_call_holds_what_the_configuration_says(q2d, drawn):
    cap = q2d["unique_term_capacity"]
    # the warm-up can pin the capacity for every seed, and no call
    # passes it: (cap / 2, cap]
    assert cap // 2 < min(drawn["calls"]) and max(drawn["calls"]) <= cap
    assert 9_400 < min(drawn["calls"]) and max(drawn["calls"]) < 10_100
    # ~66,566 tokens a call
    assert 65_000 < min(drawn["tokens"]) and max(drawn["tokens"]) < 68_000


def test_no_query_passes_the_width_and_weights_are_bf16_exact(q2d, drawn):
    assert 80 < drawn["widest"] <= q2d["engine_config"]["max_query_terms"]
    # a multiplicity is exact in bfloat16 up to 256: the kernel's
    # three-pass contraction holds for every batch of this law
    assert 30 < drawn["heaviest"] <= 256


def test_the_warm_up_pins_the_capacity(q2d):
    """``data.capacity_batch`` over this law: a batch of the widest
    queries whose distinct terms lie in (8,192, 16,384], so the
    compiled step's capacity is 16,384 before the window opens."""
    pool = data.make_queries(2147546001, 4 * 512, vocab=q2d["vocab"],
                             query_terms=q2d["query_terms"],
                             zipf_a=q2d["zipf_a"])
    warm = data.capacity_batch(pool, 512, q2d["unique_term_capacity"])
    assert len(warm) == 512
    assert 8192 < data.distinct_terms(warm) <= 16384


# names of the v5e's trace (``XLA Ops`` line, the HLO text; read in the
# builder's traced run of the cell): the query matrix, and what is not
QUERY_MATRIX = [
    # the scatter-add of the 65,536 (slot, weight) pairs into the flat
    # [512 x 16385] matrix, and the sort of its keys
    "%fusion = f32[8389120]{0:T(1024)S(1)} fusion(s32[65536]{0:T(1024)S(1)} "
    "%get-tuple-element, f32[65536]{0:T(1024)S(1)} %get-tuple-element.1, "
    "f32[]{:T(128)} %constant.14), kind=kCustom, calls=%fused_computation.3",
    "%sort = (s32[65536]{0:T(1024)S(1)}, f32[65536]{0:T(1024)S(1)}) sort("
    "s32[65536]{0:T(1024)S(1)} %select_bitcast_fusion, f32[65536]{0:T(1024)} "
    "%bitcast.26), dimensions={0}, to_apply=%compare",
    # its view, the exact-in-bfloat16 reduce, the zero column's slice
    "%reshape.20 = f32[16385,512]{1,0:T(8,128)S(1)} reshape(f32[8389120]"
    "{0:T(1024)S(1)} %fusion)",
    "%fusion.1 = pred[]{:T(512)} fusion(f32[16385,512]{1,0:T(8,128)S(1)} "
    "%reshape.20), kind=kLoop, calls=%fused_computation.2",
    "%slice.0 = f32[512,16384]{0,1:T(8,128)S(1)} slice(f32[512,16385]{0,1:"
    "T(8,128)S(1)} %bitcast.18), slice={[0:512], [0:16384]}",
    # the chunk-major relayout for the kernel
    "%copy = f32[512,128,128]{2,0,1:T(8,128)S(1)} copy(f32[512,128,128]"
    "{0,2,1:T(8,128)S(1)} %bitcast.19)",
]
NOT_QUERY_MATRIX = [
    # the kernel takes the relaid matrix for an operand
    "%ell_score_v4_w48.1 = f32[512,1048576]{1,0:T(8,128)} custom-call(s32[3]"
    "{0:T(128)S(1)} %add_add_fusion.1, s32[16384,1]{1,0:T(8,128)S(1)} "
    "%copy.1, f32[128,512,128]{2,1,0:T(8,128)S(1)} %bitcast.25, s32[48,"
    "1048576]{1,0:T(8,128)} %terms_1_.1, f32[48,1048576]{1,0:T(8,128)} "
    "%impacts_1_.1), custom_call_target=\"tpu_custom_call\"",
    # the unique terms' column; the top-k program's own %fusion and sorts
    "%copy.1 = s32[16384,1]{1,0:T(8,128)S(1)} copy(s32[16384,1]{0,1:T(1,128)"
    "S(1)} %bitcast.27)",
    "%fusion = f32[5120,128]{1,0:T(8,128)S(1)} fusion(f32[64,8192,8,128]{3,2,"
    "1,0:T(8,128)} %bitcast.7, s32[5120]{0:T(1024)S(1)} %reshape.191), "
    "kind=kCustom, calls=%fused_computation",
    "%bitcast_reduce_fusion = f32[64,8,1024]{1,2,0:T(8,128)S(1)} fusion("
    "f32[512,1048576]{1,0:T(8,128)} %get-tuple-element.13, s32[]{:T(128)S(6)}"
    " %select_n.15, pred[131072]{0:T(1024)(128)(4,1)S(1)} %fusion.20)",
    "%sort.16 = (f32[512,200]{0,1:T(8,128)S(1)}, s32[512,200]{0,1:T(8,128)"
    "S(1)}) sort(f32[512,200]{0,1:T(8,128)S(1)} %reshape.1, s32[512,200]{0,1:"
    "T(8,128)S(1)} %iota.19.clone), dimensions={1}, is_stable=true",
]


def test_query_matrix_patterns_read_the_names_of_the_v5e_trace():
    spec = load(BENCH, "metrics", "query_matrix_ms.batch.json")
    assert spec["reader"] == "device-ops" and spec["per_span"] == "score"
    pats = [re.compile(p) for p in spec["patterns"]]
    assert QUERY_MATRIX and NOT_QUERY_MATRIX
    for name in QUERY_MATRIX:
        assert any(p.search(name) for p in pats), name
    for name in NOT_QUERY_MATRIX:
        assert not any(p.search(name) for p in pats), name


def test_traced_rehearsal_is_correct_and_reports_the_new_metrics(bench):
    """``benchmarks/run.py --rehearse`` of the cell: 20,000 documents,
    32 queries of the law a call (~1,500 distinct terms: a capacity of
    2,048), the interpreted kernel. ``correct`` compares 64 answers
    with the float64 reference."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147546059", "--seconds", "5", "--trace", "1",
         "--rehearse"], cwd=ROOT, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    # a call of 32 such queries takes the interpreted kernel ~0.8 s
    # (a loaded machine: more), and the sample lies in the first four
    compared = int(re.search(r"answers_compared = (\d+)", p.stdout)[1])
    assert 16 <= compared <= 64
    got = line["metrics"]
    want = {m["name"]: m for m in bench["per_layer"]
            if CELL in m["workloads"]}
    for name, m in want.items():
        if m["source"] in ("device_trace", "program_counter"):
            # no device plane and no memory_stats on the CPU: the chip's
            continue
        assert name in got, (name, sorted(got))
        assert got[name]["value"] >= 0 and got[name]["unit"] == m["unit"]
    inner = got["vectorize_analyze_ms.batch"]["value"] \
        + got["vectorize_pack_ms.batch"]["value"]
    assert 0 < inner <= got["vectorize_ms.batch"]["value"]
    # a Python pass a token: the analysis is most of it
    assert got["vectorize_analyze_ms.batch"]["value"] \
        > got["vectorize_pack_ms.batch"]["value"]
