"""The trace reduction on intervals small enough to do by hand, and on
the small recorded trace kept beside this file (``small.xplane.pb``,
recorded by ``record_trace.py``; ``small.xplane.json`` says where)."""

import json
import os

import pytest

import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6     # ns


def test_busy_union_and_gaps():
    busy, gaps = xtrace.union_length(
        [(0, 10), (5, 20), (30, 40), (40, 45), (60, 61)])
    assert busy == 20 + 15 + 1
    assert gaps == [(20, 30), (45, 60)]
    assert xtrace.union_length([]) == (0.0, [])


def test_leaf_events_drop_enclosing_frames():
    ev = [(0, 100, "while"), (10, 40, "kernel"), (50, 90, "topk"),
          (120, 130, "copy")]
    assert [n for _s, _e, n in xtrace.leaf_events(ev)] \
        == ["kernel", "topk", "copy"]


def test_gap_is_charged_to_the_span_covering_most_of_it():
    spans = [(0, 30 * MS, "vectorize"), (30 * MS, 35 * MS, "score"),
             (200 * MS, 210 * MS, "topk")]
    gaps = [(0, 32 * MS), (100 * MS, 150 * MS)]
    by = xtrace.attribute_gaps(gaps, spans)
    assert by == {"vectorize": pytest.approx(0.032),
                  "no span": pytest.approx(0.050)}


def test_summary_of_two_devices():
    dev0 = [(0, 40 * MS, "ell_kernel"), (40 * MS, 50 * MS, "top_k"),
            (80 * MS, 120 * MS, "ell_kernel")]
    dev1 = [(0, 100 * MS, "ell_kernel")]
    host = [(50 * MS, 80 * MS, "vectorize"), (0, 5 * MS, "score"),
            (80 * MS, 85 * MS, "score"), (0, 200 * MS, "other")]
    s = xtrace.summarize([dev0, dev1], host)
    assert s["devices"] == 2
    assert s["window_s"] == pytest.approx(0.120)      # 'other' is no span
    assert s["busy_s"] == pytest.approx((0.090 + 0.100) / 2)
    assert s["device_ops"]["ell_kernel"] == [3, pytest.approx(0.180)]
    assert s["host_spans"]["score"] == [2, pytest.approx(0.010)]
    assert s["idle_gaps"] == {"vectorize": pytest.approx(0.015)}
    assert xtrace.top(s["device_ops"], 1) == [["ell_kernel",
                                               pytest.approx(0.180)]]


def test_recorded_trace():
    with open(os.path.join(HERE, "small.xplane.json")) as f:
        want = json.load(f)
    s = xtrace.reduce_xplane(os.path.join(HERE, "small.xplane.pb"))
    assert s["rehearsal"] is want["rehearsal"]
    assert s["devices"] == want["devices"]
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] == pytest.approx(want["busy_s"])
    assert s["window_s"] == pytest.approx(want["window_s"])
    for span in ("vectorize", "score", "topk"):
        assert s["host_spans"][span][0] == want["batches"]
    for name, (count, seconds) in want["device_ops"].items():
        assert s["device_ops"][name] == [count, pytest.approx(seconds)]
    assert sum(s["idle_gaps"].values()) <= s["window_s"] - s["busy_s"] + 1e-9


def test_readers_on_the_recorded_trace():
    """The metric files' patterns find the kernel and the top-k program
    in a trace of today's program, per dispatched batch."""
    import readers
    from conftest import BENCH

    s = xtrace.reduce_xplane(os.path.join(HERE, "small.xplane.pb"))
    ctx = {"trace": s, "notes": [], "device_kind": "TPU v5 lite",
           "step": {"nnz": 1_200_000, "docs": 20000, "batch": 32,
                    "unique_terms": 80.0}}

    def metric(name):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            return readers.read(json.load(f), ctx)

    kernel_s = sum(sec for name, (_c, sec) in s["device_ops"].items()
                   if "tpu_custom_call" in name)
    assert kernel_s > 0
    assert metric("ell_kernel_ms.batch") == pytest.approx(
        kernel_s / 3 * 1e3)
    topk = [v for k, v in s["device_modules"].items()
            if k.startswith("jit_packed_topk_chunked(")]
    assert len(topk) == 1 and topk[0][0] == 3
    assert metric("topk_ms.batch") == pytest.approx(topk[0][1] / 3 * 1e3)
    share = metric("ell_kernel_roofline.batch")
    assert 0 < share < 100 and "bound" in ctx["notes"][0]
    assert metric("device_idle_pct.batch") == pytest.approx(
        (1 - s["busy_s"] / s["window_s"]) * 100)
    assert metric("vectorize_ms.batch") > 0
    assert readers.read({"reader": "device-ops", "patterns": ["nothing"],
                         "per_span": "score"}, ctx) is None
