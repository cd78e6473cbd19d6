"""``correct`` is a comparison shown to fail.

* the CONTROL — the reference itself with every impact in bfloat16, the
  precision below the float32 the configurations state — put in the
  program's place at a size a test run can hold, comes out not correct on
  every seed, and by a margin (PERF.md has the chip-size readings);
* a run with the timed path broken underneath (every score 1% high where
  the engine hands it out) drives the whole harness — everything but the
  look for a chip — and prints ``"correct": false``; the same run unbroken
  prints ``true``.
"""

import json
import subprocess
import sys

import pytest

import data
import oracle
from conftest import BENCH, ROOT

SIZE = dict(corpus_seed=5, docs=50000, vocab=500000, doc_len_mean=55)
LAW = {"law": "shifted-poisson", "min": 2, "max": 12, "mean": 6}


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 977])
def test_bfloat16_control_is_not_correct(seed):
    corpus = data.make_corpus(seed, **SIZE)
    queries = data.make_queries(seed, 64, vocab=SIZE["vocab"],
                                query_terms=LAW)
    ref = oracle.Oracle(corpus, queries, k1=0.9, b=0.4)
    ctl = oracle.Oracle(corpus, queries, k1=0.9, b=0.4,
                        precision="bfloat16")
    sound = oracle.compare(ref, {i: ref.topk(i) for i in range(64)})
    assert sound["correct"]
    v = oracle.compare(ref, {i: ctl.topk(i) for i in range(64)})
    assert not v["correct"]
    worst = max(v["numbers"]["doc_score_rel_err"]["value"],
                v["numbers"]["rank_score_rel_err"]["value"])
    assert worst > 3 * oracle.LIMIT_REL_ERR


def test_wrong_answers_fail_each_number():
    corpus = data.make_corpus(3, **SIZE)
    queries = data.make_queries(3, 8, vocab=SIZE["vocab"], query_terms=LAW)
    ref = oracle.Oracle(corpus, queries, k1=0.9, b=0.4)
    good = {i: ref.topk(i) for i in range(8)}
    dropped = dict(good)
    dropped[0] = good[0][1:]                    # the best document left out
    assert oracle.compare(ref, dropped)["numbers"][
        "hit_count_mismatch"]["value"] == 1
    swapped = dict(good)                        # a document that scores less
    s = ref.scores(0)
    loser = int((s == s[s > 0].min()).nonzero()[0][0])
    swapped[0] = good[0][:-1] + [(f"d{loser}", good[0][-1][1])]
    assert not oracle.compare(ref, swapped)["correct"]
    assert not oracle.compare(ref, {})["correct"]


@pytest.mark.parametrize("cell,broken", [
    ("wiki1m.batch", True), ("wiki1m.batch", False),
    ("msmarco2m.served-sat", True)])
def test_broken_timed_path_is_not_correct(cell, broken):
    argv = [sys.executable, f"{BENCH}/run.py", "--workload", cell,
            "--seed", "2147483659", "--seconds", "3", "--trace", "0",
            "--rehearse"] + (["--break-path"] if broken else [])
    p = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is (not broken)


def test_break_hook_is_refused_outside_a_rehearsal():
    p = subprocess.run(
        [sys.executable, f"{BENCH}/run.py", "--workload", "wiki1m.batch",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--break-path"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and not p.stdout.strip().startswith("{")
