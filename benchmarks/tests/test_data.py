"""The generators are functions of the seed alone, and the generator's
arithmetic is what it says, on samples small enough to do by hand."""

import numpy as np
import pytest

import data
import genstats

SMALL = dict(corpus_seed=5, docs=3000, vocab=5000, doc_len_mean=40)
LAW = {"law": "shifted-poisson", "min": 2, "max": 12, "mean": 6}


def test_same_seed_same_inputs_other_seed_other_inputs():
    a = data.make_corpus(2**31 + 7, threads=1, **SMALL)
    b = data.make_corpus(2**31 + 7, threads=8, **SMALL)
    c = data.make_corpus(2**31 + 8, **SMALL)
    for f in ("offsets", "ids", "tfs", "lengths"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.ids, c.ids)
    # ... and another seed is the same set of documents in another order
    assert a.nnz == c.nnz
    assert np.array_equal(np.sort(np.diff(a.offsets)),
                          np.sort(np.diff(c.offsets)))
    assert np.array_equal(np.bincount(a.ids), np.bincount(c.ids))
    qa = data.make_queries(5, 500, vocab=5000, query_terms=LAW)
    assert qa == data.make_queries(5, 500, vocab=5000, query_terms=LAW)
    assert qa != data.make_queries(6, 500, vocab=5000, query_terms=LAW)
    assert len(set(qa)) == 500
    assert all(2 <= len(q.split()) <= 12 for q in qa)
    ta = data.poisson_arrivals(5, 100.0, 10.0)
    assert np.array_equal(ta, data.poisson_arrivals(5, 100.0, 10.0))
    assert not np.array_equal(ta, data.poisson_arrivals(6, 100.0, 10.0))
    assert np.all(np.diff(ta) > 0) and ta[-1] < 10.0
    assert 800 < len(ta) < 1200


def test_corpus_is_sorted_unique_slices():
    c = data.make_corpus(1, **SMALL)
    assert c.offsets[0] == 0 and c.offsets[-1] == c.nnz
    for i in (0, 1, 1500, 2999):
        ids = c.ids[c.offsets[i]:c.offsets[i + 1]]
        assert np.all(np.diff(ids) > 0)
        assert c.tfs[c.offsets[i]:c.offsets[i + 1]].sum() == c.lengths[i]


def test_capacity_batch_pins_the_capacity():
    pool = data.make_queries(3, 4000, vocab=500000, query_terms=LAW)
    b = data.capacity_batch(pool, 512, 1024)
    assert len(b) == 512 and 512 < data.distinct_terms(b) <= 1024
    with pytest.raises(ValueError):
        data.capacity_batch(pool[:8], 8, 1024)


def test_percentile_by_hand():
    assert data.percentile([1, 2, 3, 4, 5], 50) == 3
    assert data.percentile([10, 20], 95) == pytest.approx(19.5)
    assert data.percentile(range(1, 101), 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        data.percentile([], 50)


def rec(pos, ref, send, done, status=200, flags=()):
    return [pos, ref, send, done, status, list(flags)]


def test_open_loop_is_timed_from_the_due_time():
    # window [10, 12): three requests due in it, one due before it
    records = [
        rec(0, 9.5, 9.5, 9.6),                       # due before: not ours
        rec(1, 10.0, 10.0, 10.1),                    # 100 ms, on time
        rec(2, 10.5, 10.9, 11.0),                    # sent 400 ms late
        rec(3, 11.9, 11.9, 12.4),                    # finishes after t1
    ]
    s = genstats.window_stats(records, loop="open", t0=10.0, t1=12.0,
                              fail_ms=30000.0)
    assert s["attempted"] == 3 and s["failed"] == 0
    assert s["completed_qps"] == 1.5
    assert s["latency_p50_ms"] == pytest.approx(500.0)   # 100, 500, 500
    assert s["late_p95_ms"] == pytest.approx(360.0)      # 0, 0, 400 -> p95


def test_closed_loop_counts_replies_inside_the_window():
    records = [
        rec(0, 9.0, 9.0, 9.9),                        # back before t0
        rec(1, 9.8, 9.8, 10.2),                       # back inside
        rec(2, 10.2, 10.2, 10.6, status=503),         # shed
        rec(3, 10.6, 10.6, 11.0, flags=["X-Scatter-Degraded"]),
        rec(4, 11.0, 11.0, 11.4),
        rec(5, 11.8, 11.8, 12.3),                     # back after t1
    ]
    s = genstats.window_stats(records, loop="closed", t0=10.0, t1=12.0,
                              fail_ms=30000.0)
    assert s["attempted"] == 4 and s["failed"] == 2
    assert s["completed_qps"] == 1.0
    # a failed request counts as missing any limit
    assert s["latency_p95_ms"] == pytest.approx(30000.0)


def test_pause_watch_counts_only_the_window_and_the_reader_sums_it():
    import fleet
    import readers

    w = fleet.PauseWatch()            # not started: the list by hand
    w.pauses = [(9.0, 120.0), (10.5, 110.0), (12.0, 60.0), (31.0, 500.0)]
    seen = w.within(10.0, 30.0)
    assert seen == {"count": 2, "total_ms": 170.0}
    spec = {"reader": "host-pause"}
    assert readers.read(spec, {"host_pauses": seen}) == 170.0
    assert readers.read(spec, {}) is None      # nothing gathered
