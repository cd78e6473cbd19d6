"""The serving pipeline executor: ordering, failure isolation,
backpressure, the pipelined-vs-unpipelined parity gate, the packed-wire
fast path, and the breaker/retry interaction when a dispatched scatter
group's worker RPC fails mid-pipeline (ISSUE 3 satellite tests)."""

import threading
import time

import numpy as np
import pytest

from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.engine.pipeline import PipelineExecutor
from tfidf_tpu.utils.config import Config

TEXTS = {
    "a.txt": "the quick brown fox jumps over the lazy dog",
    "b.txt": "lazy dog sleeps in the sun all day",
    "c.txt": "brown dog barks at the quick fox",
    "d.txt": "a completely different document about searching",
    "e.txt": "fox fox fox den",
}

QUERIES = ["fox", "lazy dog", "brown", "searching documents", "quick",
           "sun day", "den", "nothing matches this zzz", "dog fox",
           "the"]


def make_engine(tmp_path, **cfg):
    # force the executor so tier-1 exercises the overlap machinery on
    # CPU ("auto" resolves to inline there — see _use_executor)
    cfg.setdefault("search_pipeline_mode", "executor")
    e = Engine(Config(documents_path=str(tmp_path / "docs"),
                      min_doc_capacity=8, min_nnz_capacity=256,
                      min_vocab_capacity=64, query_batch=4,
                      max_query_terms=8, **cfg))
    for name, text in TEXTS.items():
        e.ingest_text(name, text)
    e.commit()
    return e


# --------------------------------------------------------------------------
# executor unit behavior
# --------------------------------------------------------------------------

def test_results_keep_submit_order_under_out_of_order_completion():
    """Chunk 0's fetch is slow and chunk 2's work is instant; results
    must still come back in submission order (single FIFO fetch
    thread — the ordering guarantee downstream hit assembly needs)."""
    ex = PipelineExecutor(depth=3, name="t")
    try:
        def fetch(i):
            time.sleep(0.05 if i == 0 else 0.0)
            return i

        futs = [ex.submit(lambda i=i: (i,), fetch) for i in range(4)]
        done_order = []
        for f in futs:
            done_order.append(f.result())
        assert done_order == [0, 1, 2, 3]
    finally:
        ex.stop()


def test_fetch_exception_isolated_to_its_chunk():
    ex = PipelineExecutor(depth=2, name="t")
    try:
        def fetch(i):
            if i == 1:
                raise ValueError("fetch exploded")
            return i

        futs = [ex.submit(lambda i=i: (i,), fetch) for i in range(3)]
        assert futs[0].result() == 0
        with pytest.raises(ValueError, match="fetch exploded"):
            futs[1].result()
        # the pipeline keeps serving later chunks and new submissions
        assert futs[2].result() == 2
        assert ex.submit(lambda: (9,), lambda i: i).result() == 9
    finally:
        ex.stop()


def test_dispatch_exception_isolated_to_its_chunk():
    ex = PipelineExecutor(depth=2, name="t")
    try:
        def dispatch(i):
            if i == 0:
                raise RuntimeError("compile failed")
            return (i,)

        futs = [ex.submit(lambda i=i: dispatch(i), lambda i: i)
                for i in range(3)]
        with pytest.raises(RuntimeError, match="compile failed"):
            futs[0].result()
        assert [futs[1].result(), futs[2].result()] == [1, 2]
    finally:
        ex.stop()


def test_depth_bounds_in_flight_chunks():
    """Dispatch-then-drain accounting: at most depth+1 chunks may be
    dispatched-but-unfetched at any instant (HBM budgets depth+1
    packed buffers)."""
    depth = 2
    ex = PipelineExecutor(depth=depth, name="t")
    lock = threading.Lock()
    state = {"in_flight": 0, "max_seen": 0}
    release = threading.Event()
    try:
        def dispatch(i):
            with lock:
                state["in_flight"] += 1
                state["max_seen"] = max(state["max_seen"],
                                        state["in_flight"])
            return (i,)

        def fetch(i):
            release.wait(timeout=10)   # hold fetches until all queued
            with lock:
                state["in_flight"] -= 1
            return i

        futs = [ex.submit(lambda i=i: dispatch(i), fetch)
                for i in range(8)]
        time.sleep(0.2)   # let the dispatch thread run as far as it can
        with lock:
            seen = state["max_seen"]
        release.set()
        assert [f.result() for f in futs] == list(range(8))
        assert seen <= depth + 1, seen
    finally:
        ex.stop()


def test_concurrent_callers_share_one_executor():
    """Two callers' chunks interleave on the shared pipeline without
    mixing results (the worker data plane serves concurrent scatter
    RPCs through exactly this)."""
    ex = PipelineExecutor(depth=2, name="t")
    out = {}
    try:
        def caller(tag):
            futs = [ex.submit(lambda i=i: (tag, i),
                              lambda t, i: (t, i * i))
                    for i in range(16)]
            out[tag] = [f.result() for f in futs]

        threads = [threading.Thread(target=caller, args=(t,))
                   for t in ("a", "b", "c")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
        for tag in ("a", "b", "c"):
            assert out[tag] == [(tag, i * i) for i in range(16)]
    finally:
        ex.stop()


def test_executor_smoke_fake_two_program_workload():
    """Tier-1-safe CPU smoke of the overlap machinery at tiny cost: a
    synthetic 2-program-shaped workload (dispatch sleeps like a device
    queue, fetch like the d2h link) gives the serial loop's results in
    FIFO fetch order, and a DETERMINISTIC overlap witness: chunk 0's
    fetch blocks until chunk 1's dispatch has started, which can only
    complete if dispatch and fetch genuinely run concurrently (a
    serialized pipeline runs into the timeout and fails the
    handshake)."""
    n_chunks, cost_s = 4, 0.002
    record: list = []

    def dispatch(i):
        time.sleep(cost_s)
        record.append(("d", i))
        return (i,)

    def fetch(i):
        time.sleep(cost_s)
        record.append(("f", i))
        return i * i

    serial_out = [fetch(*dispatch(i)) for i in range(n_chunks)]
    del record[:]
    ex = PipelineExecutor(depth=2, name="probe")
    try:
        futures = [ex.submit(lambda i=i: dispatch(i), fetch)
                   for i in range(n_chunks)]
        pipe_out = [f.result() for f in futures]
        assert serial_out == pipe_out == [i * i for i in range(n_chunks)]
        assert [i for s, i in record if s == "f"] == list(range(n_chunks))

        # the witness: an event handshake, no timing
        started_d1 = threading.Event()
        witnessed = threading.Event()

        def d(i):
            if i == 1:
                started_d1.set()
            return (i,)

        def f(i):
            if i == 0 and started_d1.wait(timeout=5.0):
                witnessed.set()
            return i

        for w in [ex.submit(lambda i=i: d(i), f) for i in range(2)]:
            w.result()
        assert witnessed.is_set(), \
            "dispatch and fetch never overlapped — pipeline serialized"
    finally:
        ex.stop()


def test_stop_fails_pending_and_rejects_new():
    ex = PipelineExecutor(depth=1, name="t")
    gate = threading.Event()
    futs = [ex.submit(lambda i=i: (i,),
                      lambda i: (gate.wait(5), i)[1]) for i in range(4)]
    gate.set()
    ex.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        ex.submit(lambda: (0,), lambda i: i)
    # every future is resolved one way or another — nothing hangs
    for f in futs:
        assert f.done() or f.cancelled()


# --------------------------------------------------------------------------
# parity gates
# --------------------------------------------------------------------------

def test_pipelined_results_identical_to_unpipelined(tmp_path):
    """The acceptance gate: depth-3 pipelined search produces hit lists
    bit-identical to the depth-1 (effectively serial) path."""
    deep = make_engine(tmp_path / "deep", search_pipeline_depth=3)
    shallow = make_engine(tmp_path / "shallow", search_pipeline_depth=1)
    a = deep.search_batch(QUERIES, k=5)
    b = shallow.search_batch(QUERIES, k=5)
    assert a == b
    for hits in a[:3]:
        assert hits, "corpus queries must match something"


def test_executor_and_inline_modes_identical(tmp_path):
    """The executor and inline stage runners are the same three stages;
    results must match bit-for-bit, and "auto" must resolve to inline
    on the CPU backend (the executor's thread hand-offs only pay for
    themselves where fetches have real latency)."""
    ex = make_engine(tmp_path / "ex", search_pipeline_mode="executor")
    inl = make_engine(tmp_path / "inl", search_pipeline_mode="inline")
    auto = make_engine(tmp_path / "auto", search_pipeline_mode="auto")
    want = inl.search_batch(QUERIES, k=5)
    assert ex.search_batch(QUERIES, k=5) == want
    assert auto.search_batch(QUERIES, k=5) == want
    assert ex.searcher._use_executor()
    assert not inl.searcher._use_executor()
    assert not auto.searcher._use_executor()   # CPU backend in tests


def test_concurrent_search_calls_parity(tmp_path):
    """Concurrent callers interleaving chunks on the shared executor
    get exactly the single-caller results."""
    engine = make_engine(tmp_path, search_pipeline_depth=2)
    want = engine.search_batch(QUERIES, k=5)
    out = [None] * 6

    def one(slot):
        out[slot] = engine.search_batch(QUERIES, k=5)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for got in out:
        assert got == want


# Every searcher family through the one loop (engine/searcher.py
# SearchLoop): the corpus of a family is TEXTS, then MORE committed on
# top (on the ELL mesh: a live delta), then one document deleted (a
# tombstone). PARENT_ANSWERS are what the tree before PR 45 (7b10ebf)
# returned, family by family, for FAMILY_QUERIES at depth 4 (names and order
# exact; a score to a float32's rounding, since another CPU may sum in
# another order).
MORE = {
    "f.txt": "the fox and the dog are searching for the den",
    "g.txt": "a lazy brown document",
    "h.txt": "quick quick quick",
}
BASE = {**TEXTS, **{f"base{i}.txt": f"filler{i} word{i % 3} fox"
                    for i in range(8)}}
FAMILY_QUERIES = ["fox", "lazy dog", "quick brown", "searching den",
                  "nothing matches this zzz", "the"]
FAMILIES = {
    "local-ell": {},
    "local-coo-cosine": {"model": "tfidf_cosine", "scoring_layout": "coo"},
    "segments": {"index_mode": "segments"},
    "mesh-coo-cosine": {"engine_mode": "mesh", "model": "tfidf_cosine"},
    "mesh-ell": {"engine_mode": "mesh"},
}
PARENT_ANSWERS = {
    "local-coo-cosine": [
        [("e.txt", 0.8222360610961914), ("base0.txt", 0.3138309717178345),
        ("base1.txt", 0.3138309717178345), ("base3.txt", 0.3138309717178345)],
        [("b.txt", 0.5942659974098206), ("a.txt", 0.5741746425628662),
        ("g.txt", 0.4580156207084656), ("f.txt", 0.23703327775001526)],
        [("h.txt", 1.0), ("a.txt", 0.6433948278427124), ("g.txt",
        0.5132321119308472)],
        [("e.txt", 0.5691466927528381), ("f.txt", 0.5312181115150452),
        ("d.txt", 0.3785386383533478)],
        [],
        [("f.txt", 0.7110998630523682), ("a.txt", 0.5741746425628662),
        ("b.txt", 0.2971329987049103)],
    ],
    "local-ell": [
        [("e.txt", 0.2419874370098114), ("base0.txt", 0.17421592772006989),
        ("base1.txt", 0.17421592772006989), ("base2.txt",
        0.17421592772006989)],
        [("b.txt", 1.0524251461029053), ("a.txt", 0.984736979007721),
        ("g.txt", 0.7257594466209412), ("f.txt", 0.4626147747039795)],
        [("h.txt", 1.4295387268066406), ("a.txt", 1.2027466297149658),
        ("g.txt", 0.8864344358444214)],
        [("f.txt", 1.1300649642944336), ("e.txt", 0.8864344358444214),
        ("d.txt", 0.7451490759849548)],
        [],
        [("f.txt", 0.8626722097396851), ("a.txt", 0.7437793612480164),
        ("b.txt", 0.5262125730514526)],
    ],
    "mesh-coo-cosine": [
        [("e.txt", 0.8120247721672058), ("base3.txt", 0.30355560779571533),
        ("base7.txt", 0.30355560779571533), ("base0.txt",
        0.30355560779571533)],
        [("a.txt", 0.5837608575820923), ("b.txt", 0.5785374641418457),
        ("g.txt", 0.47151345014572144), ("f.txt", 0.22744174301624298)],
        [("h.txt", 1.0), ("a.txt", 0.6116501688957214), ("g.txt",
        0.47151345014572144)],
        [("e.txt", 0.5836228728294373), ("f.txt", 0.5593752264976501),
        ("d.txt", 0.3791692554950714)],
        [],
        [("f.txt", 0.6823251843452454), ("a.txt", 0.5558715462684631),
        ("b.txt", 0.27544882893562317)],
    ],
    "mesh-ell": [
        [("e.txt", 0.2419874370098114), ("base3.txt", 0.17421592772006989),
        ("base7.txt", 0.17421592772006989), ("base0.txt",
        0.17421592772006989)],
        [("b.txt", 1.0524251461029053), ("a.txt", 0.984736979007721),
        ("g.txt", 0.7257594466209412), ("f.txt", 0.4626147747039795)],
        [("h.txt", 1.4295387268066406), ("a.txt", 1.2027466297149658),
        ("g.txt", 0.8864344358444214)],
        [("f.txt", 1.1300649642944336), ("e.txt", 0.8864344358444214),
        ("d.txt", 0.7451490759849548)],
        [],
        [("f.txt", 0.8626723289489746), ("a.txt", 0.7437793612480164),
        ("b.txt", 0.5262125730514526)],
    ],
    "segments": [
        [("e.txt", 0.22675864398479462), ("base0.txt", 0.16390442848205566),
        ("base1.txt", 0.16390442848205566), ("base2.txt",
        0.16390442848205566)],
        [("b.txt", 1.0259472131729126), ("a.txt", 0.9608937501907349),
        ("g.txt", 0.7642409801483154), ("f.txt", 0.4127751290798187)],
        [("h.txt", 1.2232588529586792), ("a.txt", 1.0438905954360962),
        ("g.txt", 0.7642409801483154)],
        [("f.txt", 1.1906352043151855), ("e.txt", 0.926945149898529),
        ("d.txt", 0.7817791700363159)],
        [],
        [("f.txt", 0.7638711929321289), ("a.txt", 0.6599483489990234),
        ("b.txt", 0.4686656892299652)],
    ],
}


def family_engine(tmp_path, family: str):
    import jax

    from tfidf_tpu.parallel.mesh import make_mesh

    cfg = Config(documents_path=str(tmp_path / "docs"),
                 min_doc_capacity=8, min_nnz_capacity=256,
                 min_vocab_capacity=64, query_batch=4, max_query_terms=8,
                 search_pipeline_mode="executor", **FAMILIES[family])
    mesh = (make_mesh((4, 1), devices=jax.devices()[:4])
            if family.startswith("mesh") else None)
    e = Engine(cfg, mesh=mesh)
    for docs in (BASE, MORE):
        for name, text in docs.items():
            e.ingest_text(name, text)
        e.commit()
    e.delete("c.txt")
    e.commit()
    return e


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_search_arrays_packs_identical_wire_bytes(tmp_path, family):
    """The serving fast path (search_arrays -> pack_topk_arrays) must
    produce byte-identical wire replies to the hit-list path
    (pack_hit_lists over assembled SearchHits), for every searcher
    family, at a shallow depth and at one deeper than a mesh shard's
    rows; and the hits are the parent's."""
    from tfidf_tpu.cluster.wire import (pack_hit_lists, pack_topk_arrays,
                                        unpack_hit_lists)

    engine = family_engine(tmp_path, family)
    snap = engine.index.snapshot
    if family == "mesh-ell":     # the case is in the snapshot
        assert engine.index.rebuilds == 1 and engine.index.appends == 1
        assert sum(live[-1] for live in snap.shard_live) == len(MORE)
        assert None in snap.doc_names
    deep = 4 * len(snap.doc_names)
    for k in (4, deep):
        hits = engine.search_batch(FAMILY_QUERIES, k=k)
        vals, ids, kk, names = engine.search_batch_arrays(
            FAMILY_QUERIES, k=k)
        assert vals.shape == ids.shape == (len(FAMILY_QUERIES), kk)
        assert names is snap.doc_names
        fast = pack_topk_arrays(vals, ids, names)
        assert fast == pack_hit_lists(hits)
        # and the decoded lists agree with the SearchHit view
        assert unpack_hit_lists(fast) == [
            [(h.name, float(np.float32(h.score))) for h in hl]
            for hl in hits]
    assert all("c.txt" not in [h.name for h in hl] for hl in hits)
    assert len(hits[0]) > 4                  # the deep request is deep
    got = engine.search_batch(FAMILY_QUERIES, k=4)
    want = PARENT_ANSWERS[family]
    assert [[h.name for h in hl] for hl in got] == \
        [[name for name, _s in hl] for hl in want]
    for hl, wl in zip(got, want):
        assert [h.score for h in hl] == pytest.approx(
            [s for _n, s in wl], rel=1e-6)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_search_arrays_empty_cases(tmp_path, family):
    from tfidf_tpu.cluster.wire import pack_topk_arrays, unpack_hit_lists

    engine = family_engine(tmp_path, family)
    vals, ids, kk, names = engine.searcher.search_arrays([], k=5)
    assert vals.shape == (0, 0) and kk == 0
    assert unpack_hit_lists(pack_topk_arrays(vals, ids, names)) == []
    # a query matching nothing packs as an empty hit list
    vals, ids, kk, names = engine.searcher.search_arrays(
        ["zzz qqq nothing"], k=5)
    assert unpack_hit_lists(pack_topk_arrays(vals, ids, names)) == [[]]


def test_worker_wire_entrypoint_matches_hit_list_path(tmp_path):
    """node.worker_search_batch_wire: the arrays fast path and the
    pack_hit_lists fallback produce the same bytes end to end."""
    from tfidf_tpu.cluster.wire import pack_hit_lists

    class _Node:
        # borrow the real methods without a coordination client
        from tfidf_tpu.cluster.node import SearchNode as _S
        _search_batch_guarded = _S._search_batch_guarded
        worker_search_batch = _S.worker_search_batch
        worker_search_batch_wire = _S.worker_search_batch_wire
        _compile_bucket = _S._compile_bucket
        _is_retryable_compute_fault = staticmethod(
            _S._is_retryable_compute_fault)

        def __init__(self, engine, config):
            self.engine = engine
            self.config = config
            self._compile_retry_lock = threading.Lock()
            self._compile_retries_used = {}

        def commit_if_dirty(self):
            pass

    engine = make_engine(tmp_path)
    node = _Node(engine, engine.config)
    fast = node.worker_search_batch_wire(QUERIES, k=5)
    assert fast == pack_hit_lists(engine.search_batch(QUERIES, k=5))


# --------------------------------------------------------------------------
# breaker/retry interaction mid-pipeline
# --------------------------------------------------------------------------

def _resilience(**kw):
    from tfidf_tpu.cluster.resilience import ClusterResilience
    cfg = Config(rpc_max_attempts=3, rpc_backoff_base_s=0.001,
                 rpc_backoff_max_s=0.002, rpc_retry_deadline_s=0.0,
                 breaker_failure_threshold=2, breaker_reset_s=60.0, **kw)
    return ClusterResilience(cfg)


def test_transient_rpc_failure_mid_pipeline_retries_and_succeeds():
    """A dispatched scatter group whose worker RPC fails once with a
    gateway-transient status is retried inside the SAME group; callers
    never see the transient, and groups in flight behind it are
    unaffected."""
    from tfidf_tpu.cluster.batcher import Coalescer
    from tfidf_tpu.cluster.resilience import RpcStatusError

    res = _resilience()
    failures = {"n": 0}
    lock = threading.Lock()

    def scatter(items):
        def rpc():
            with lock:
                if failures["n"] == 0 and "q0" in items:
                    failures["n"] += 1
                    raise RpcStatusError("http://w1/x", 503)
            return [f"ok:{q}" for q in items]

        return res.worker_call("http://w1", rpc)

    co = Coalescer(scatter, max_batch=2, linger_s=0.005, pipeline=2,
                   name="t_scatter")
    try:
        out = {}
        threads = [threading.Thread(
            target=lambda q=f"q{i}": out.__setitem__(q, co.submit(q)))
            for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert out == {f"q{i}": f"ok:q{i}" for i in range(6)}
        assert failures["n"] == 1   # the transient actually fired
        assert res.board.snapshot().get("http://w1") == "closed"
    finally:
        co.stop()


def test_hard_rpc_failure_mid_pipeline_opens_breaker_and_fails_group():
    """Deterministic 500s exhaust no retries (not transient), fail ONLY
    the dispatched group's callers, and open the worker's breaker at
    the threshold while the coalescer keeps serving later groups."""
    from tfidf_tpu.cluster.batcher import Coalescer
    from tfidf_tpu.cluster.resilience import (CircuitOpenError,
                                              RpcStatusError)

    res = _resilience()
    calls = {"n": 0}

    def scatter(items):
        def rpc():
            calls["n"] += 1
            raise RpcStatusError("http://w1/x", 500)

        return res.worker_call("http://w1", rpc)

    co = Coalescer(scatter, max_batch=1, linger_s=0.0, pipeline=2,
                   name="t_scatter2")
    try:
        with pytest.raises(RpcStatusError):
            co.submit("q0")
        with pytest.raises(RpcStatusError):
            co.submit("q1")
        # threshold 2 reached: the breaker now fast-fails the NEXT
        # group without an RPC (counted as circuit_open, not a retry)
        n_before = calls["n"]
        with pytest.raises(CircuitOpenError):
            co.submit("q2")
        assert calls["n"] == n_before
        assert res.board.snapshot()["http://w1"] == "open"
    finally:
        co.stop()


# --------------------------------------------------------------------------
# adaptive linger
# --------------------------------------------------------------------------

def test_adaptive_linger_scales_with_inflight_batches():
    from tfidf_tpu.cluster.batcher import Coalescer

    co = Coalescer(lambda items: items, max_batch=4, linger_s=0.002,
                   pipeline=3, name="t_linger",
                   linger_min_s=0.001, linger_max_s=0.008)
    try:
        # busy fraction is over the pipeline-1 SIBLINGS (the deciding
        # thread is never inside batch_fn itself): 2 siblings here
        assert co._effective_linger_s() == pytest.approx(0.001)
        with co._lock:
            co._dispatching = 1
        assert co._effective_linger_s() == pytest.approx(0.0045)
        with co._lock:   # every sibling busy -> the max IS reachable
            co._dispatching = 2
        assert co._effective_linger_s() == pytest.approx(0.008)
        with co._lock:   # saturation beyond depth clamps at max
            co._dispatching = 5
        assert co._effective_linger_s() == pytest.approx(0.008)
        with co._lock:
            co._dispatching = 0
    finally:
        co.stop()


def test_adaptive_linger_single_dispatcher_keeps_fixed_linger():
    """pipeline=1 has no sibling to read load from: adaptation is moot
    and the tuned fixed linger_s applies (not a collapsed linger_min)."""
    from tfidf_tpu.cluster.batcher import Coalescer

    co = Coalescer(lambda items: items, max_batch=4, linger_s=0.002,
                   pipeline=1, name="t_linger1",
                   linger_min_s=0.0005, linger_max_s=0.008)
    try:
        assert co._effective_linger_s() == pytest.approx(0.002)
    finally:
        co.stop()


def test_fixed_linger_unchanged_without_bounds():
    from tfidf_tpu.cluster.batcher import Coalescer

    co = Coalescer(lambda items: items, max_batch=4, linger_s=0.003,
                   pipeline=2, name="t_linger2")
    try:
        for busy in (0, 1, 2):
            with co._lock:
                co._dispatching = busy
            assert co._effective_linger_s() == pytest.approx(0.003)
        with co._lock:
            co._dispatching = 0
    finally:
        co.stop()
