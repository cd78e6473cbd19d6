"""Observability: distributed tracing, histogram metrics, Prometheus.

The acceptance story (ISSUE 10): a kill-a-worker-mid-scatter request's
trace, fetched via ``GET /api/trace/<id>``, reconstructs the whole
story — the scatter span, per-worker child spans, the failover re-issue
span, and resilience span events; ``/api/metrics?format=prometheus``
parses under a strict text-format checker whose histogram series agree
with the JSON snapshot's live percentiles; histogram quantiles track
``numpy.percentile`` within bucket resolution across adversarial
distributions; and counter/gauge name collisions fail loudly instead of
silently shadowing.
"""

import json
import logging as _pylogging
import math
import re
import time
import urllib.request

import numpy as np
import pytest

from tfidf_tpu.cluster.batcher import Coalescer
from tfidf_tpu.cluster.coordination import CoordinationCore
from tfidf_tpu.cluster.node import http_get
from tfidf_tpu.utils.logging import get_logger
from tfidf_tpu.utils.metrics import (_BUCKET_RATIO, MetricKindError,
                                     Metrics, global_metrics)
from tfidf_tpu.utils.tracing import (TRACE_HEADER, global_tracer,
                                     propagation_headers,
                                     render_trace_tree, span_event,
                                     to_chrome_trace, trace_phase)

from tests.test_replication import (QUERIES, _assert_parity,
                                    _mk_cluster, _oracle, _search,
                                    _stop_all, _upload_docs)


@pytest.fixture
def core():
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


@pytest.fixture(autouse=True)
def _reset_tracer():
    global_tracer.configure(max_spans=4096, sample_rate=1.0)
    global_tracer.clear()
    yield
    global_tracer.configure(max_spans=4096, sample_rate=1.0)
    global_tracer.clear()


# ---------------------------------------------------------------------------
# Histogram quantiles vs numpy.percentile (oracle)
# ---------------------------------------------------------------------------

# one bucket ratio each way covers the estimate's construction error;
# numpy's linear interpolation can land at a bucket edge, so allow two
_QTOL = _BUCKET_RATIO ** 2


def _assert_close_quantile(got_s: float, want_s: float, ctx=""):
    assert want_s / _QTOL <= got_s <= want_s * _QTOL, \
        (ctx, got_s, want_s)


class TestHistogramQuantiles:
    def _check(self, samples, qs=(0.5, 0.95, 0.99), ctx=""):
        m = Metrics()
        for s in samples:
            m.observe("lat", float(s))
        for q in qs:
            want = float(np.percentile(samples, q * 100))
            got = m.quantile("lat", q)
            _assert_close_quantile(got, want, ctx=f"{ctx} q={q}")

    def test_uniform(self, rng):
        self._check(rng.uniform(0.001, 0.2, size=5000), ctx="uniform")

    def test_bimodal(self, rng):
        # fast-path/slow-path serving mix: the mean is meaningless,
        # the p99 sits in the far mode — exactly what buckets must see
        fast = rng.normal(0.002, 0.0003, size=4000).clip(1e-4)
        slow = rng.normal(0.5, 0.05, size=300).clip(1e-4)
        self._check(np.concatenate([fast, slow]), ctx="bimodal")

    def test_heavy_tail(self, rng):
        self._check(rng.lognormal(mean=-5.0, sigma=1.5, size=8000),
                    ctx="lognormal")

    def test_single_sample_is_exact(self):
        m = Metrics()
        m.observe("lat", 0.0421)
        for q in (0.0, 0.5, 0.99, 1.0):
            assert m.quantile("lat", q) == pytest.approx(0.0421)

    def test_extremes_clamp_to_observed(self, rng):
        m = Metrics()
        xs = rng.uniform(0.001, 1.0, size=100)
        for x in xs:
            m.observe("lat", float(x))
        assert m.quantile("lat", 0.0) == pytest.approx(xs.min())
        assert m.quantile("lat", 1.0) == pytest.approx(xs.max())

    def test_overflow_bucket_uses_max(self):
        m = Metrics()
        m.observe("lat", 500.0)   # beyond the last finite bound
        m.observe("lat", 600.0)
        assert m.quantile("lat", 0.99) == pytest.approx(600.0)

    def test_snapshot_percentile_keys(self):
        m = Metrics()
        for i in range(100):
            m.observe("lat", 0.01 * (i + 1))
        snap = m.snapshot()
        for k in ("lat_p50_ms", "lat_p95_ms", "lat_p99_ms"):
            assert k in snap
        assert snap["lat_p50_ms"] <= snap["lat_p95_ms"] \
            <= snap["lat_p99_ms"]
        assert m.quantile("nothing", 0.5) is None


# ---------------------------------------------------------------------------
# Counter/gauge namespaces: collisions fail loudly
# ---------------------------------------------------------------------------

class TestMetricKindCollision:
    def test_gauge_then_counter_raises(self):
        m = Metrics()
        m.set_gauge("depth", 3)
        with pytest.raises(MetricKindError):
            m.inc("depth")

    def test_counter_then_gauge_raises(self):
        m = Metrics()
        m.inc("requests")
        with pytest.raises(MetricKindError):
            m.set_gauge("requests", 1.0)

    def test_real_tree_has_no_collision(self, core, tmp_path):
        """The global registry builds up a real serving run's metrics
        without any emit-side guard firing (the guard would raise into
        the serving path) — pinned by the cluster test below actually
        running; here just assert the registry stayed consistent."""
        snap = global_metrics.snapshot()
        assert isinstance(snap, dict)


# ---------------------------------------------------------------------------
# Prometheus text exposition: strict checker
# ---------------------------------------------------------------------------

_NAME_RE = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE_RE = re.compile(
    rf"^({_NAME_RE})(?:\{{le=\"([^\"]+)\"\}})? "
    r"(-?(?:[0-9.]+(?:[eE][-+]?[0-9]+)?|\+Inf|NaN))$")
_TYPE_RE = re.compile(rf"^# TYPE ({_NAME_RE}) (counter|gauge|histogram)$")


def parse_prometheus_strict(text: str) -> dict:
    """Strict text-format checker: every line is a TYPE declaration or
    a sample; every sample's metric was declared; histogram series are
    cumulative with a ``+Inf`` bucket equal to ``_count``; returns
    {metric: {"type": ..., "samples": [(labels_le, value)], ...}}."""
    metrics: dict = {}
    declared: dict[str, str] = {}
    for line in text.strip().splitlines():
        tm = _TYPE_RE.match(line)
        if tm:
            name, kind = tm.groups()
            assert name not in declared, f"duplicate TYPE for {name}"
            declared[name] = kind
            metrics[name] = {"type": kind, "samples": []}
            continue
        sm = _SAMPLE_RE.match(line)
        assert sm, f"unparseable exposition line: {line!r}"
        name, le, value = sm.groups()
        base = name
        for suf in ("_bucket", "_sum", "_count"):
            if name.endswith(suf) and name[: -len(suf)] in declared \
                    and declared[name[: -len(suf)]] == "histogram":
                base = name[: -len(suf)]
                break
        assert base in declared, f"sample before TYPE: {line!r}"
        metrics[base]["samples"].append((name, le, float(value)
                                         if value != "+Inf"
                                         else math.inf))
    # histogram invariants
    for name, m in metrics.items():
        if m["type"] != "histogram":
            continue
        buckets = [(le, v) for n, le, v in m["samples"]
                   if n == f"{name}_bucket"]
        counts = [v for n, _le, v in m["samples"]
                  if n == f"{name}_count"]
        assert buckets and len(counts) == 1, name
        vals = [v for _le, v in buckets]
        assert vals == sorted(vals), f"{name} buckets not cumulative"
        assert buckets[-1][0] == "+Inf", f"{name} missing +Inf bucket"
        assert buckets[-1][1] == counts[0], \
            f"{name} +Inf bucket != _count"
    return metrics


def _p_from_buckets(buckets: list[tuple[str, float]], q: float) -> float:
    """Replicate the quantile estimate from exposition buckets (the
    operator's histogram_quantile()): geometric interpolation."""
    n = buckets[-1][1]
    target = max(1, math.ceil(q * n))
    prev_cum, prev_bound = 0.0, None
    for le, cum in buckets:
        if cum >= target:
            hi = float(le) if le != "+Inf" else float(buckets[-2][0])
            lo = (float(prev_bound) if prev_bound not in (None, "+Inf")
                  else hi / _BUCKET_RATIO)
            frac = (target - prev_cum) / (cum - prev_cum)
            return lo * (hi / lo) ** frac
        prev_cum, prev_bound = cum, le
    raise AssertionError("empty histogram")


class TestPrometheusExposition:
    def test_render_parses_and_is_consistent(self, rng):
        m = Metrics()
        m.inc("uploads_placed", 7)
        m.set_gauge("queue depth/now", 3.5)   # name needs sanitizing
        for x in rng.lognormal(-4.0, 1.0, size=2000):
            m.observe("scatter_rpc", float(x))
        parsed = parse_prometheus_strict(m.render_prometheus())
        assert parsed["tfidf_uploads_placed_total"]["type"] == "counter"
        assert parsed["tfidf_uploads_placed_total"]["samples"][0][2] == 7
        # sanitized gauge name, distinct from any counter name
        assert "tfidf_queue_depth_now" in parsed
        h = parsed["tfidf_scatter_rpc_seconds"]
        assert h["type"] == "histogram"
        # the exposition's histogram reproduces the JSON snapshot's p99
        # within bucket resolution (the estimate may clamp to observed
        # extremes, which buckets alone cannot)
        buckets = [(le, v) for n, le, v in h["samples"]
                   if n == "tfidf_scatter_rpc_seconds_bucket"]
        want = m.snapshot()["scatter_rpc_p99_ms"] / 1e3
        _assert_close_quantile(_p_from_buckets(buckets, 0.99), want,
                               ctx="prom p99")
        # _sum agrees with the JSON running sum
        s = [v for n, _le, v in h["samples"]
             if n == "tfidf_scatter_rpc_seconds_sum"][0]
        assert s == pytest.approx(m.snapshot()["scatter_rpc_sum_ms"]
                                  / 1e3, rel=1e-6)

    def test_namespaces_stay_distinct_in_exposition(self):
        m = Metrics()
        m.inc("served")
        m.set_gauge("depth", 1.0)
        text = m.render_prometheus()
        assert "tfidf_served_total" in text
        assert re.search(r"^tfidf_depth 1$", text, re.M)


# ---------------------------------------------------------------------------
# Tracing unit tests
# ---------------------------------------------------------------------------

class TestTracingUnit:
    def test_span_nesting_and_events(self):
        with global_tracer.span("outer") as outer:
            assert propagation_headers()[TRACE_HEADER] == outer.trace_id
            span_event("hello", n=1)
            with global_tracer.span("inner",
                                    parent=outer) as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        assert propagation_headers() == {}
        spans = global_tracer.get_trace(outer.trace_id)
        assert [s["name"] for s in spans] == ["outer", "inner"]
        assert spans[0]["events"][0]["name"] == "hello"

    def test_trace_phase_folds_into_active_span(self):
        with global_tracer.span("req") as sp:
            with trace_phase("unittest_phase"):
                pass
        evs = [e["name"] for e in sp.to_dict()["events"]]
        assert "phase.unittest_phase" in evs
        assert global_metrics.get("phase_unittest_phase_count", 0) == 0
        assert global_metrics.snapshot()["phase_unittest_phase_count"] \
            == 1

    def test_ring_is_bounded(self):
        global_tracer.configure(max_spans=32)
        for i in range(200):
            with global_tracer.span(f"s{i}"):
                pass
        assert len(global_tracer.recent(1000)) == 32

    def test_sampling_zero_records_nothing_but_keeps_ids(self):
        global_tracer.configure(sample_rate=0.0)
        with global_tracer.span("unsampled") as sp:
            assert sp.trace_id           # id still minted (log joining)
            sp.event("dropped")
            assert propagation_headers() == {}  # unsampled: no headers
        assert global_tracer.recent(10) == []
        assert not sp.events

    def test_coalescer_links_batch_and_requests_both_ways(self):
        co = Coalescer(lambda items: [x * 2 for x in items],
                       max_batch=4, linger_s=0.0, pipeline=1,
                       name="obs")
        try:
            with global_tracer.span("request") as req:
                assert co.submit(21) == 42
            batch = [s for s in global_tracer.recent(50)
                     if s["name"] == "obs.batch"]
            assert batch, "no batch span recorded"
            b = batch[0]
            # batch links request; request links batch (walkable both
            # directions across the coalescing boundary)
            assert {l["trace_id"] for l in b["links"]} == {req.trace_id}
            reqd = [s for s in global_tracer.recent(50)
                    if s["name"] == "request"][0]
            assert {l["trace_id"] for l in reqd["links"]} \
                == {b["trace_id"]}
            # link-following trace fetch pulls the other trace in
            got = {s["name"]
                   for s in global_tracer.get_trace(req.trace_id)}
            assert {"request", "obs.batch"} <= got
        finally:
            co.stop()

    def test_event_cap_keeps_newest(self):
        from tfidf_tpu.utils.tracing import Span
        with global_tracer.span("stormy") as sp:
            for i in range(Span._MAX_EVENTS + 50):
                sp.event("retry", i=i)
            sp.event("scatter.health", degraded=0)
        evs = sp.to_dict()["events"]
        assert len(evs) == Span._MAX_EVENTS
        # the late decisive event survives the storm; the OLDEST
        # retries are what got dropped
        assert evs[-1]["name"] == "scatter.health"
        assert evs[0]["attrs"]["i"] > 0

    def test_remote_header_respects_sampling_off(self):
        """A client-supplied X-Trace-Id must not buy recording back in
        when the operator turned tracing off (trace_sample_rate=0) —
        untrusted headers would otherwise control ring retention."""
        from tfidf_tpu.utils.tracing import remote_context
        global_tracer.configure(sample_rate=0.0)
        for trusted in (True, False):
            ctx = remote_context("deadbeefdeadbeef", "cafe0123",
                                 trusted=trusted)
            assert ctx is not None and ctx.sampled is False
            with global_tracer.span("worker.process", parent=ctx):
                pass
        assert global_tracer.recent(10) == []
        # untrusted front-door headers under PARTIAL sampling face the
        # local draw like any root — at a 1e-9 rate a client id cannot
        # buy its way to 100% recording (trusted internal propagation
        # stays sampled: the decision was made at the root)
        global_tracer.configure(sample_rate=1e-9)
        draws = [remote_context("deadbeefdeadbeef", "cafe0123",
                                trusted=False).sampled
                 for _ in range(64)]
        assert not any(draws)
        assert remote_context("deadbeefdeadbeef", "cafe0123",
                              trusted=True).sampled is True
        global_tracer.configure(sample_rate=1.0)
        assert remote_context("deadbeefdeadbeef", "cafe0123",
                              trusted=False).sampled is True
        assert remote_context(None, None) is None
        # untrusted ids must match the hex grammar — a hostile header
        # cannot inject arbitrary bytes into the ring / log stream /
        # reply headers (malformed falls back to a fresh root)
        for bad in ("x shed=0 lane=interactive", "A" * 70, "short",
                    "DEADBEEFDEADBEEF", "deadbeef" * 9):
            assert remote_context(bad, None, trusted=False) is None
        assert remote_context("deadbeefdeadbeef", "zz zz",
                              trusted=False) is None
        # the trusted (internal) continuation validates too: the
        # worker endpoints share the public listener, so a hostile
        # header can arrive on either path
        assert remote_context("anything-goes", None,
                              trusted=True) is None
        assert remote_context("deadbeefdeadbeef", None,
                              trusted=True) is not None

    def test_cli_trace_merges_linked_trace_from_worker_rings(
            self, monkeypatch, capsys):
        """Multi-process contract: worker-side continuations live under
        the BATCH trace id in the worker's OWN ring — the CLI's by-id
        fan-out must re-query nodes with the linked trace ids, or the
        timeline silently omits every worker span."""
        import tfidf_tpu.cluster.node as node_mod
        from tfidf_tpu.cli import main as cli_main
        req = {"trace_id": "req1", "span_id": "r1", "parent_id": None,
               "name": "leader.search", "start_s": 1.0,
               "duration_ms": 5.0, "attrs": {}, "events": [],
               "links": [{"trace_id": "batch1", "span_id": "b1"}]}
        # the batch absorbed a SIBLING request too: one-hop link
        # following must not drag it into req1's timeline
        batch = {"trace_id": "batch1", "span_id": "b1",
                 "parent_id": None, "name": "scatter.batch",
                 "start_s": 1.1, "duration_ms": 4.0, "attrs": {},
                 "events": [], "links": [{"trace_id": "req1",
                                          "span_id": "r1"},
                                         {"trace_id": "sibling",
                                          "span_id": "s1"}]}
        sib = {"trace_id": "sibling", "span_id": "s1",
               "parent_id": None, "name": "leader.search",
               "start_s": 1.0, "duration_ms": 5.0,
               "attrs": {"query": "other users secret"},
               "events": [], "links": [{"trace_id": "batch1",
                                        "span_id": "b1"}]}
        wspan = {"trace_id": "batch1", "span_id": "w1",
                 "parent_id": "b1", "name": "worker.process_batch",
                 "start_s": 1.2, "duration_ms": 2.0, "attrs": {},
                 "events": [], "links": []}
        rings = {  # per-node rings, disjoint like real processes
            "http://leader:1": {"req1": [req, batch],
                                "batch1": [req, batch, sib],
                                "sibling": [sib, batch]},
            "http://worker:2": {"batch1": [wspan]},
        }

        def fake_http_get(url, timeout=10.0, origin=None):
            base, _, path = url.partition("/api/")
            if path == "services":
                return json.dumps(["http://worker:2"]).encode()
            tid = path[len("trace/"):]
            return json.dumps(
                {"spans": rings.get(base, {}).get(tid, [])}).encode()

        monkeypatch.setattr(node_mod, "http_get", fake_http_get)
        assert cli_main(["trace", "req1", "--leader",
                         "http://leader:1"]) == 0
        out = capsys.readouterr().out
        assert "worker.process_batch" in out, out
        assert "leader.search" in out and "scatter.batch" in out
        # one hop only: the sibling request the batch also absorbed
        # stays out of this request's timeline
        assert "secret" not in out

    def test_batch_span_inherits_sampling_never_rerolls(self):
        """A batch span exists only because its linked requests won the
        sampling draw — it must inherit that verdict, not re-roll it
        (an independent draw drops a sampled request's whole scatter
        sub-trace with probability 1 - sample_rate). Proven at the
        adversarial extreme: rate 0 with a force-sampled request."""
        global_tracer.configure(sample_rate=0.0)
        co = Coalescer(lambda items: list(items), max_batch=4,
                       linger_s=0.0, pipeline=1, name="obs3")
        try:
            with global_tracer.span("req", sampled=True):
                co.submit("x")
            batch = [s for s in global_tracer.recent(50)
                     if s["name"] == "obs3.batch"]
            assert batch, \
                "batch span re-rolled sampling and was dropped"
        finally:
            co.stop()

    def test_untraced_submit_creates_no_batch_span(self):
        co = Coalescer(lambda items: list(items), max_batch=4,
                       linger_s=0.0, pipeline=1, name="obs2")
        try:
            co.submit("x")
            assert [s for s in global_tracer.recent(50)
                    if s["name"] == "obs2.batch"] == []
        finally:
            co.stop()

    def test_log_records_carry_trace_id(self):
        records = []

        class _Capture(_pylogging.Handler):
            def emit(self, record):
                records.append(record)

        logger = _pylogging.getLogger("tfidf_tpu")
        h = _Capture()
        logger.addHandler(h)
        try:
            log = get_logger("unittest")
            with global_tracer.span("traced") as sp:
                log.warning("inside", foo=1)
            log.warning("outside", foo=2)
        finally:
            logger.removeHandler(h)
        inside = next(r for r in records if "inside" in r.getMessage())
        outside = next(r for r in records
                       if "outside" in r.getMessage())
        assert inside.kv.get("trace") == sp.trace_id
        assert "trace" not in outside.kv

    def test_fault_fire_emits_span_event(self):
        from tfidf_tpu.utils.faults import (FaultInjected,
                                            global_injector)
        global_injector.arm("leader.sweep", action="raise", times=1)
        with global_tracer.span("chaos") as sp:
            with pytest.raises(FaultInjected):
                global_injector.check("leader.sweep")
        evs = [e for e in sp.to_dict()["events"]
               if e["name"] == "fault_injected"]
        assert evs and evs[0]["attrs"]["point"] == "leader.sweep"

    def test_chrome_export_and_render(self):
        with global_tracer.span("root") as root:
            span_event("tick", ms=1)
            with global_tracer.span("child", parent=root):
                pass
        spans = global_tracer.get_trace(root.trace_id)
        chrome = to_chrome_trace(spans)
        assert {e["ph"] for e in chrome["traceEvents"]} == {"X", "i"}
        tree = render_trace_tree(spans)
        assert "root" in tree and "child" in tree and "· tick" in tree
        assert render_trace_tree([]) == "(no spans)"


# ---------------------------------------------------------------------------
# Chaos-trace integration: the story reconstructs from the trace
# ---------------------------------------------------------------------------

def _search_traced(leader, q: str) -> tuple[dict, str]:
    """POST /leader/start returning (result, trace id) — the reply
    header contract every traced response carries."""
    req = urllib.request.Request(
        leader.url + "/leader/start",
        data=json.dumps({"query": q}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read()), r.headers.get(TRACE_HEADER)


def _kill_data_plane(victim):
    """HTTP down, session alive (see tests/test_replication.py): only
    the WITHIN-REQUEST failover read keeps results complete."""
    victim.httpd.shutdown()
    victim.httpd.server_close()
    cls = victim.httpd.RequestHandlerClass

    def dead(handler):
        raise ConnectionResetError("worker killed (test)")
    cls.do_POST = dead
    cls.do_GET = dead


def _fetch_trace(leader, tid: str) -> list[dict]:
    return json.loads(http_get(
        leader.url + f"/api/trace/{tid}"))["spans"]


def _owning_worker(leader, nodes):
    """A worker node that OWNS at least one document under the current
    assignment — killing a non-owner exercises no failover slice (the
    owner assignment already avoids it), so victim choice must follow
    ownership, not list position."""
    live = frozenset(leader.registry.get_all_service_addresses())
    view = leader.placement.owner_assignment(live, frozenset())
    owners = set(view.owner.values())
    return next(nd for nd in nodes[1:] if nd.url in owners)


class TestChaosTrace:
    def test_worker_kill_mid_scatter_trace_reconstructs_story(
            self, core, tmp_path):
        """The acceptance criterion: kill a worker's data plane, search,
        fetch the trace by the reply's X-Trace-Id — it must contain the
        scatter (batch) span, per-worker child spans including the
        failed one, the failover re-issue slice parented under the
        scatter span, and the health annotation."""
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            want = _oracle(tmp_path)
            for q in QUERIES:
                _assert_parity(_search(leader, q), want[q], ctx=q)
            _kill_data_plane(_owning_worker(leader, nodes))
            story = None
            for _ in range(6):   # ownership decides which search pays
                res, tid = _search_traced(leader, "common")
                _assert_parity(res, want["common"], ctx="killed")
                assert tid
                time.sleep(0.1)   # worker-side spans finish async
                spans = _fetch_trace(leader, tid)
                if any(s["name"] == "scatter.slice" for s in spans):
                    story = spans
                    break
            assert story is not None, \
                "no search produced a failover slice"
            by_name: dict[str, list] = {}
            for s in story:
                by_name.setdefault(s["name"], []).append(s)
            # the request span, linked (not parented) to the batch
            req = by_name["leader.search"][0]
            batch = by_name["scatter.batch"][0]
            assert {l["trace_id"] for l in req["links"]} \
                == {batch["trace_id"]}
            assert req["trace_id"] != batch["trace_id"]
            # per-worker child spans PARENTED under the scatter span,
            # one of them errored (the killed worker)
            workers = by_name["scatter.worker"]
            assert len(workers) == 2
            assert all(w["parent_id"] == batch["span_id"]
                       for w in workers)
            assert any("error" in w["attrs"] for w in workers)
            # the failover re-issue, parented correctly, slice-typed
            sl = by_name["scatter.slice"][0]
            assert sl["parent_id"] == batch["span_id"]
            assert sl["attrs"]["kind"] == "failover"
            assert sl["attrs"]["names"] >= 1
            # the degraded flag annotated on the scatter span (failover
            # fully covered the death, so degraded=0 and failovers>0)
            health = [e for e in batch["events"]
                      if e["name"] == "scatter.health"]
            assert health
            assert health[0]["attrs"]["degraded"] == 0
            assert health[0]["attrs"]["failovers"] >= 1
            # the worker-side span of the surviving replica carries the
            # engine's phase events (the request timeline reaches into
            # the engine)
            wspans = by_name.get("worker.process_batch", ())
            assert any(
                any(e["name"].startswith("phase.")
                    for e in w["events"]) for w in wspans)
        finally:
            _stop_all(nodes)

    def test_hedge_win_visible_in_trace(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=3, scatter_hedge_ms=40.0)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            want = _oracle(tmp_path)
            for q in QUERIES:   # warm compiled paths first
                _assert_parity(_search(leader, q), want[q], ctx=q)
            victim = _owning_worker(leader, nodes)
            orig_batch = victim.engine.search_batch
            orig_arrays = victim.engine.search_batch_arrays

            def slow_arrays(queries, k=None):
                time.sleep(2.0)
                return orig_arrays(queries, k=k)

            def slow_batch(queries, k=None, unbounded=False):
                time.sleep(2.0)
                return orig_batch(queries, k=k, unbounded=unbounded)

            victim.engine.search_batch_arrays = slow_arrays
            victim.engine.search_batch = slow_batch
            res, tid = _search_traced(leader, "common")
            _assert_parity(res, want["common"], ctx="hedged")
            victim.engine.search_batch_arrays = orig_arrays
            victim.engine.search_batch = orig_batch
            assert global_metrics.get("scatter_hedge_wins") >= 1
            spans = _fetch_trace(leader, tid)
            batch = next(s for s in spans
                         if s["name"] == "scatter.batch")
            evs = {e["name"] for e in batch["events"]}
            assert "hedge_dispatched" in evs
            assert "hedge_win" in evs
            hedges = [s for s in spans if s["name"] == "scatter.slice"
                      and s["attrs"].get("kind") == "hedge"]
            assert hedges
            assert all(h["parent_id"] == batch["span_id"]
                       for h in hedges)
        finally:
            _stop_all(nodes)

    def test_prometheus_endpoint_matches_json_snapshot(self, core,
                                                       tmp_path):
        """Integration half of the exposition contract: the leader's
        /api/metrics?format=prometheus parses strictly and its
        leader_search histogram p99 agrees with the JSON snapshot's
        leader_search_p99_ms."""
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            for _ in range(3):
                for q in QUERIES:
                    _search(leader, q)
            text = http_get(
                leader.url + "/api/metrics?format=prometheus").decode()
            parsed = parse_prometheus_strict(text)
            alias = http_get(leader.url + "/metrics").decode()
            parse_prometheus_strict(alias)
            h = parsed["tfidf_leader_search_seconds"]
            buckets = [(le, v) for n, le, v in h["samples"]
                       if n == "tfidf_leader_search_seconds_bucket"]
            snap = json.loads(http_get(leader.url + "/api/metrics"))
            want = snap["leader_search_p99_ms"] / 1e3
            got = _p_from_buckets(buckets, 0.99)
            # clamping to observed extremes can only tighten the JSON
            # estimate relative to the raw bucket read
            _assert_close_quantile(got, want, ctx="live prom p99")
            assert snap["leader_search_count"] \
                == [v for n, _le, v in h["samples"]
                    if n == "tfidf_leader_search_seconds_count"][0]
        finally:
            _stop_all(nodes)

    def test_slow_query_log_counts_and_keys_by_trace(self, core,
                                                     tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=3,
                            trace_slow_query_ms=0.0001)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            before = global_metrics.get("slow_queries")
            _res, tid = _search_traced(leader, "common")
            assert tid
            assert global_metrics.get("slow_queries") > before
        finally:
            _stop_all(nodes)

    def test_cli_trace_renders_timeline(self, core, tmp_path,
                                        capsys):
        from tfidf_tpu.cli import main as cli_main
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            _res, tid = _search_traced(leader, "common")
            time.sleep(0.1)
            assert cli_main(["trace", tid, "--leader",
                             leader.url]) == 0
            out = capsys.readouterr().out
            assert "leader.search" in out
            # entry via a WORKER url works too: /api/leader names the
            # leader (it left /api/services on promotion), so the
            # fan-out still reaches the ring that holds the request
            worker_url = nodes[1].url
            got = json.loads(http_get(worker_url + "/api/leader"))
            assert got["leader"] == leader.url
            assert cli_main(["trace", tid, "--leader",
                             worker_url]) == 0
            assert "leader.search" in capsys.readouterr().out
            # recent mode also renders
            assert cli_main(["trace", "--leader", leader.url,
                             "--recent", "50"]) == 0
        finally:
            _stop_all(nodes)

    def test_every_leader_response_carries_trace_id(self, core,
                                                    tmp_path):
        """The documented contract: ANY /leader/* reply's X-Trace-Id
        keys `tfidf_tpu trace` — uploads, deletes, and 429 sheds
        included, not just /leader/start."""
        import urllib.error
        nodes = _mk_cluster(core, tmp_path, n=3,
                            admission_rate_qps=1e-9)
        try:
            leader = nodes[0]
            # burst floors at ONE token per client bucket: distinct
            # client ids admit each mutating request once
            body = json.dumps([{"name": "t.txt",
                                "text": "hello"}]).encode()
            req = urllib.request.Request(
                leader.url + "/leader/upload-batch", data=body,
                headers={"Content-Type": "application/json",
                         "X-Client-Id": "obs-a"})
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.headers.get(TRACE_HEADER)
            req = urllib.request.Request(
                leader.url + "/leader/delete",
                data=json.dumps({"names": ["gone.txt"]}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Client-Id": "obs-b"})
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.headers.get(TRACE_HEADER)
            # client obs-a's bucket is spent (rate ~0): its next
            # request sheds — and the 429 still carries the trace id
            # of the span minted at the admission point
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    leader.url + "/leader/start",
                    data=json.dumps({"query": "x"}).encode(),
                    headers={"Content-Type": "application/json",
                             "X-Client-Id": "obs-a"}),
                    timeout=30)
            assert ei.value.code == 429
            assert ei.value.headers.get(TRACE_HEADER)
            # /leader/download too — both the 404 reply and a real
            # streamed 200 carry the trace id (streams bypass _send)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    leader.url + "/leader/download?path=absent.txt",
                    headers={"X-Client-Id": "obs-c"}), timeout=30)
            assert ei.value.code == 404
            assert ei.value.headers.get(TRACE_HEADER)
            # a handler FAILURE (500) keeps the contract too — the
            # span contextvar is gone by the outer except, but the
            # remembered span still keys the reply
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    leader.url + "/leader/delete",
                    data=b"{not json",
                    headers={"Content-Type": "application/json",
                             "X-Client-Id": "obs-e"}), timeout=30)
            assert ei.value.code == 500
            assert ei.value.headers.get(TRACE_HEADER)
            with urllib.request.urlopen(urllib.request.Request(
                    leader.url + "/leader/download?path=t.txt",
                    headers={"X-Client-Id": "obs-d"}),
                    timeout=30) as r:
                assert r.headers.get(TRACE_HEADER)
                assert r.read() == b"hello"
        finally:
            _stop_all(nodes)

    def test_recent_zero_returns_nothing(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            _search(leader, "common")
            got = json.loads(http_get(
                leader.url + "/api/trace?recent=0"))
            assert got["spans"] == []
            assert global_tracer.recent(0) == []
            assert global_tracer.recent(-5) == []
        finally:
            _stop_all(nodes)

    def test_chrome_export_endpoint(self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            _res, tid = _search_traced(leader, "common")
            time.sleep(0.1)
            chrome = json.loads(http_get(
                leader.url + f"/api/trace/{tid}?format=chrome"))
            assert chrome["traceEvents"]
            assert any(e["name"] == "leader.search"
                       for e in chrome["traceEvents"])
        finally:
            _stop_all(nodes)


# ---------------------------------------------------------------------------
# The stage timer (ISSUE 24): every stage and wait of the query path under
# one name in /api/metrics, on the Dapper span and in a profiler trace
# ---------------------------------------------------------------------------

def _stage_engine(tmp_path, mode, mesh_layout=None):
    """``mesh_layout``: "ell" / "coo" builds the engine as one mesh
    worker over a (4, 1) mesh of the virtual devices instead."""
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config
    cfg = Config(documents_path=str(tmp_path / "docs"),
                 min_doc_capacity=8, min_nnz_capacity=256,
                 min_vocab_capacity=64, query_batch=4,
                 max_query_terms=8, search_pipeline_mode=mode)
    mesh = None
    if mesh_layout:
        import jax

        from tfidf_tpu.parallel.mesh import make_mesh
        cfg = cfg.replace(engine_mode="mesh", mesh_layout=mesh_layout)
        mesh = make_mesh((4, 1), devices=jax.devices()[:4])
    e = Engine(cfg, mesh=mesh)
    for i in range(12):
        e.ingest_text(f"d{i}", f"common word{i} term{i % 3} extra{i % 5}")
    e.commit()
    return e


def _counts(*keys):
    snap = global_metrics.snapshot()
    return {k: snap.get(k, 0) for k in keys}


def _grew(before, after):
    return {k: after[k] - before[k] for k in before}


class TestStageTimer:
    FETCH = ("phase_device_wait_count", "phase_d2h_count",
             "phase_assemble_count")
    CHUNKS = ("dispatch_chunks", "dispatch_queries", "dispatch_slots")
    WAITS = ("phase_dispatch_wait_count", "phase_fetch_wait_count")
    QUERIES = [f"common word{i}" for i in range(10)]    # 3 chunks of <= 4

    @pytest.mark.parametrize("arrays", [False, True])
    def test_every_chunk_is_timed_and_counted(self, tmp_path, arrays):
        e = _stage_engine(tmp_path, "inline")
        keys = self.FETCH + self.CHUNKS + self.WAITS
        before = _counts(*keys)
        if arrays:
            vals, _ids, _kk, _names = e.search_batch_arrays(self.QUERIES)
            assert vals.shape[0] == len(self.QUERIES)
        else:
            assert all(e.search_batch(self.QUERIES))
        d = _grew(before, _counts(*keys))
        assert d["dispatch_chunks"] == 3
        assert [d[k] for k in self.FETCH] == [3, 3, 3]
        assert d["dispatch_queries"] == 10
        assert d["dispatch_slots"] == 4 + 4 + 2      # buckets 4, 4, 2
        assert d["dispatch_queries"] <= d["dispatch_slots"]
        # the inline path has no thread hand-off to wait for
        assert [d[k] for k in self.WAITS] == [0, 0]

    def test_executor_records_both_waits_on_the_span(self, tmp_path):
        e = _stage_engine(tmp_path, "executor")
        keys = self.FETCH + self.WAITS + ("dispatch_chunks",)
        before = _counts(*keys)
        with global_tracer.span("req") as sp:
            assert all(e.search_batch(self.QUERIES))
        d = _grew(before, _counts(*keys))
        assert d == {k: 3 for k in keys}
        evs = [ev["name"] for ev in sp.to_dict()["events"]]
        for name in ("phase.dispatch_wait", "phase.vectorize",
                     "phase.score", "phase.topk", "phase.fetch_wait",
                     "phase.device_wait", "phase.d2h", "phase.assemble"):
            assert evs.count(name) == 3, (name, evs)
        assert not [n for n in evs if n.startswith("pipeline.")]

    @pytest.mark.parametrize("mesh_layout", [None, "ell"])
    def test_hit_stages_nest_inside_assemble(self, tmp_path, mesh_layout):
        """``hit_names`` and ``hit_objects`` (``assemble_hits``): one of
        each a fetched chunk, as timings ``phase_hit_*`` and as events of
        the active span, inside that chunk's ``assemble`` and summing to
        it but for its two views and the poison check."""
        e = _stage_engine(tmp_path, "inline", mesh_layout=mesh_layout)
        keys = tuple(f"phase_{s}_{what}" for what in ("count", "sum_ms")
                     for s in ("assemble", "hit_names", "hit_objects"))
        before = _counts(*keys)
        with global_tracer.span("req") as sp:
            assert all(e.search_batch(self.QUERIES))
        d = _grew(before, _counts(*keys))
        assert [d[k] for k in keys[:3]] == [3, 3, 3]
        stages = [(ev["name"], ev["attrs"]["ms"])
                  for ev in sp.to_dict()["events"]
                  if ev["name"] in ("phase.assemble", "phase.hit_names",
                                    "phase.hit_objects")]
        # a stage's event is written where it ends: the two inner ones,
        # in order, then the chunk's assemble that held them
        assert [n for n, _ms in stages] == [
            "phase.hit_names", "phase.hit_objects", "phase.assemble"] * 3
        for i in range(0, 9, 3):
            names_ms, objects_ms, assemble_ms = (ms for _n, ms in
                                                 stages[i:i + 3])
            inner = names_ms + objects_ms
            # events round to a microsecond; what assemble holds besides
            # is tens of microseconds (2 ms: a loaded host's slack)
            assert inner <= assemble_ms + 0.003, stages[i:i + 3]
            assert assemble_ms - inner < 2.0, stages[i:i + 3]
        assert d["phase_hit_names_sum_ms"] + d["phase_hit_objects_sum_ms"] \
            <= d["phase_assemble_sum_ms"] + 0.01

    def test_hit_counters_say_how_full_the_depth_is(self, tmp_path):
        """``hit_slots``: queries x depth of every fetched chunk;
        ``hits_built``: the hits that left ``assemble_hits``. A query of
        a rare term fills one slot of its five."""
        e = _stage_engine(tmp_path, "inline")
        before = _counts("hit_slots", "hits_built")
        # "common" is in all 12 documents, "word7" in one, "term1" in four
        hits = e.search_batch(["common", "word7", "term1 word7",
                               "nothing"], k=5)
        assert [len(h) for h in hits] == [5, 1, 4, 0]
        assert _grew(before, _counts("hit_slots", "hits_built")) == {
            "hit_slots": 4 * 5, "hits_built": 10}
        # the arrays path builds no hit
        before = _counts("hit_slots", "hits_built")
        e.search_batch_arrays(["common", "word7"], k=5)
        assert _grew(before, _counts("hit_slots", "hits_built")) == {
            "hit_slots": 0, "hits_built": 0}

    MESH = ("phase_vectorize_count", "phase_score_count") + FETCH \
        + CHUNKS + ("mesh_steps",)

    @pytest.mark.parametrize("layout,mode", [
        ("ell", "inline"), ("coo", "inline"), ("ell", "executor")])
    def test_mesh_chunks_are_timed_and_counted(self, tmp_path, layout,
                                               mode):
        """The mesh searcher's stages, as the local searcher's: per
        dispatched chunk one ``vectorize``, one ``score`` (the enqueue of
        the shard_map program), one each of the fetch stages, and one
        ``mesh_steps``."""
        e = _stage_engine(tmp_path, mode, mesh_layout=layout)
        assert type(e.searcher).__name__ == {
            "ell": "MeshEllSearcher", "coo": "MeshSearcher"}[layout]
        before = _counts(*self.MESH)
        with global_tracer.span("req") as sp:
            assert all(e.search_batch(self.QUERIES))
        d = _grew(before, _counts(*self.MESH))
        assert d.pop("dispatch_queries") == 10
        assert d.pop("dispatch_slots") == 4 + 4 + 2
        assert d == {k: 3 for k in d}, d
        assert d["mesh_steps"] == d["dispatch_chunks"]
        evs = [ev["name"] for ev in sp.to_dict()["events"]]
        for name in ("phase.vectorize", "phase.score", "phase.device_wait",
                     "phase.d2h", "phase.assemble"):
            assert evs.count(name) == 3, (name, evs)
        assert "phase.topk" not in evs      # the step takes its own top-k

    @pytest.mark.parametrize("layout", ["ell", "coo"])
    def test_mesh_commit_gauges_and_phases(self, tmp_path, layout):
        """12 documents dealt round-robin over four docs-shards: three
        slots a shard; the rows a shard's step scores are the layout's."""
        e = _stage_engine(tmp_path, "inline", mesh_layout=layout)
        snap = global_metrics.snapshot()
        assert [snap[k] for k in (
            "mesh_docs_shards", "mesh_terms_shards", "mesh_shard_docs_min",
            "mesh_shard_docs_max")] == [4, 1, 3, 3]
        index = e.index.snapshot
        rows = sum(imp.shape[2] for imp in index.base.impact) \
            if layout == "ell" else index.arrays.doc_cap
        assert snap["mesh_shard_rows_padded"] == rows >= 3
        if layout == "ell":     # the rebuild's parts, timed
            assert snap["phase_mesh_build_host_count"] == 2
            assert snap["phase_mesh_build_upload_count"] == 1
            assert snap["phase_mesh_impact_refresh_count"] == 1
        e.ingest_text("d12", "common extra word12")
        e.commit()              # an append: slots move, no rebuild
        snap = global_metrics.snapshot()
        assert (snap["mesh_shard_docs_min"],
                snap["mesh_shard_docs_max"]) == (3, 4)
        if layout == "ell":
            assert snap["phase_mesh_build_upload_count"] == 1
            assert snap["phase_mesh_impact_refresh_count"] == 2

    CONTRACT = ("dispatch_chunks", "kernel_contract_chunks",
                "kernel_contract_chunks_bf16x3")

    @pytest.mark.parametrize("mesh_layout", [None, "ell"],
                             ids=["local", "mesh"])
    def test_contraction_chunks_are_counted(self, tmp_path, mesh_layout):
        """Per chunk dispatched to the kernel: the live 128-row chunks
        of A it contracts, and those it contracts in three bf16 passes.
        An engine's query weights are term multiplicities, exact in
        bfloat16, so the two are equal, on both searchers."""
        e = _stage_engine(tmp_path, "inline", mesh_layout=mesh_layout)
        before = _counts(*self.CONTRACT)
        assert all(e.search_batch(self.QUERIES + ["common common word1"]))
        d = _grew(before, _counts(*self.CONTRACT))
        # three dispatches of a handful of terms: one chunk each
        assert d == {k: 3 for k in self.CONTRACT}, d

    def test_contraction_counters_in_api_metrics(self, core, tmp_path):
        """After a batch served through the front door a worker's
        ``/api/metrics`` carries both counters, equal."""
        nodes = _mk_cluster(core, tmp_path, n=3)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            before = json.loads(http_get(nodes[1].url + "/api/metrics"))
            for q in QUERIES:
                _search(leader, q)
            snap = json.loads(http_get(nodes[1].url + "/api/metrics"))
            grew = [snap[k] - before.get(k, 0) for k in self.CONTRACT]
            assert grew[0] > 0 and grew[1] >= grew[0], grew
            assert grew[1] == grew[2], grew
        finally:
            _stop_all(nodes)

    def test_mesh_program_has_a_fixed_name(self, tmp_path):
        """``jit_mesh_ell_search`` is what ``mesh_step_ms.mesh`` looks for
        on a trace's ``XLA Modules`` line; the scopes name the step's
        parts in the compiled HLO."""
        e = _stage_engine(tmp_path, "inline", mesh_layout="ell")
        snap = e.index.snapshot
        qb, _ = e.searcher._vectorize(self.QUERIES[:4], 4)
        lowered = e.searcher._get_search_fn(3, 3).lower(
            snap.base, snap.delta, snap.df_g, snap.n_docs, snap.avgdl, qb)
        assert "@jit_mesh_ell_search" in lowered.as_text()
        text = lowered.as_text(debug_info=True)
        for scope in ("ell_blocks", "coo_residual", "live_mask", "delta",
                      "shard_topk", "gather_merge"):
            assert f'loc("{scope}/' in text, scope
        # the blocks are ranked where they lie: nothing gathers them
        assert 'loc("rearrange_to_real/' not in text

    def test_coalescer_queue_wait_and_wake(self):
        """Two one-item batches through ONE dispatcher whose batch_fn
        sleeps: the second waits in the queue at least as long as the
        first is in flight, and that is the queue wait — not a linger."""
        hold = 0.15

        def slow(items):
            time.sleep(hold)
            return items

        co = Coalescer(slow, max_batch=1, linger_s=0.0, pipeline=1,
                       name="stg")
        try:
            import concurrent.futures
            with concurrent.futures.ThreadPoolExecutor(2) as pool:
                first = pool.submit(co.submit, 1)
                time.sleep(0.03)       # the first batch is in flight
                second = pool.submit(co.submit, 2)
                assert (first.result(10), second.result(10)) == (1, 2)
        finally:
            co.stop()
        snap = global_metrics.snapshot()
        assert snap["stg_queue_wait_count"] == 2
        assert snap["stg_queue_wait_max_ms"] >= (hold - 0.05) * 1e3
        assert snap["stg_wake_count"] == 2
        assert snap["stg_wake_sum_ms"] >= 0
        assert not [k for k in snap if k.startswith("stg_linger")]

    @pytest.mark.parametrize("under_span", [True, False])
    def test_host_span_carries_the_trace_id(self, monkeypatch,
                                            under_span):
        """No profiler session in tier-1 (slow under xdist): the
        annotation is a recording stub."""
        from tfidf_tpu.utils import tracing
        opened = []

        class Stub:
            def __init__(self, name, **kw):
                opened.append((name, kw))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(tracing._jprof, "TraceAnnotation", Stub)
        if under_span:
            with global_tracer.span("req") as sp:
                with trace_phase("assemble"):
                    pass
            assert opened == [("assemble", {"trace_id": sp.trace_id})]
            global_tracer.configure(sample_rate=0.0)
            with global_tracer.span("unsampled"):
                with trace_phase("assemble"):
                    pass
            assert opened[1] == ("assemble", {})
        else:
            with trace_phase("assemble"):
                pass
            assert opened == [("assemble", {})]

    def test_kernel_has_a_fixed_name(self):
        """``ell_score_v4`` is the label the device trace's reduction
        and PERF.md §3 know the kernel by."""
        import jax
        import jax.numpy as jnp

        from tfidf_tpu.ops.ell import score_block_pallas
        rows, width, B, u_cap = 256, 8, 4, 256
        jaxpr = jax.make_jaxpr(
            lambda imp, term, uniq, qc: score_block_pallas(
                imp, term, uniq, jnp.int32(3), qc))(
            jnp.zeros((width, rows), jnp.float32),
            jnp.zeros((width, rows), jnp.int32),
            jnp.zeros((u_cap,), jnp.int32),
            jnp.zeros((B, u_cap + 1), jnp.float32))
        assert "ell_score_v4" in str(jaxpr)
