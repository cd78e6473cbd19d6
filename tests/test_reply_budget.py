"""The budget of one served reply closes (PR 35).

A reply through the front door is timed first byte to last on the
handler's thread (``leader_client_gap`` + ``leader_search`` +
``leader_reply_write`` = one turn of a closed-loop client),
``leader_search`` is the sum of its five chained stages, the scatter
RPC is cut at the worker's ``Server-Timing`` stamps into three legs
whose sum is ``scatter_rpc`` by construction, the process says what
it costs itself (``ProcessWatch``: CPU share, GIL wait, collector
pauses), and the worker's dispatch thread names its own idle time.
"""

import gc
import http.client
import json
import threading
import time
import urllib.parse

import pytest

from tfidf_tpu.cluster.coordination import CoordinationCore
from tfidf_tpu.engine.pipeline import PipelineExecutor
from tfidf_tpu.utils import tracing
from tfidf_tpu.utils.metrics import Metrics, global_metrics
from tfidf_tpu.utils.tracing import (ProcessWatch, epoch_now,
                                     server_timing, trace_rpc_legs)

from tests.test_cluster import wait_until
from tests.test_replication import _mk_cluster, _stop_all, _upload_docs

FRONT_DOOR = ("leader_pre_submit", "leader_in_batch", "leader_post_wake",
              "leader_reply_write", "leader_search")
COALESCER = ("scatter_queue_wait", "scatter_wake")
LEGS = ("scatter_rpc_out", "scatter_rpc_handle", "scatter_rpc_back")


@pytest.fixture
def core():
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


def _sums(*keys) -> dict[str, tuple[int, float]]:
    """(count, exact sum in seconds) of each timing: the snapshot rounds
    its ``_sum_ms`` to a microsecond, which a 1e-6 comparison of four
    sums cannot afford."""
    with global_metrics._lock:
        return {k: tuple(global_metrics._timings[k][:2])
                if k in global_metrics._timings else (0, 0.0)
                for k in keys}


def _grown(before: dict, after: dict) -> dict[str, tuple[int, float]]:
    return {k: (after[k][0] - before[k][0], after[k][1] - before[k][1])
            for k in after}


def _keep_alive(leader) -> http.client.HTTPConnection:
    u = urllib.parse.urlparse(leader.url)
    return http.client.HTTPConnection(u.hostname, u.port, timeout=30)


def _post(conn, query: str) -> dict:
    conn.request("POST", "/leader/start",
                 body=json.dumps({"query": query}).encode(),
                 headers={"Content-Type": "application/json"})
    r = conn.getresponse()
    body = r.read()
    assert r.status == 200, body
    return json.loads(body)


class TestServedReply:
    def test_twenty_replies_on_one_connection_close_both_budgets(
            self, core, tmp_path):
        """Leader + one worker in this process, 20 distinct searches
        over ONE keep-alive connection (so one handler instance, one
        thread, one RPC a search)."""
        nodes = _mk_cluster(core, tmp_path, n=2)
        try:
            leader = nodes[0]
            _upload_docs(leader)
            conn = _keep_alive(leader)
            _post(conn, "common")        # commit + compile, not counted
            keys = FRONT_DOOR + COALESCER + LEGS + (
                "leader_client_gap", "scatter_rpc", "phase_handle_batch")
            start = _sums(*keys)
            for i in range(20):
                before = _sums(*keys)
                assert _post(conn, f"token{i % 12} word{i % 3}")
                one = _grown(before, _sums(*keys))
                # every stage inside leader_search is observed before
                # the reply goes out: one of each, chained stamp to
                # stamp, so they ARE leader_search
                stages = ("leader_pre_submit", "scatter_queue_wait",
                          "leader_in_batch", "scatter_wake",
                          "leader_post_wake")
                assert [one[k][0] for k in stages] == [1] * 5, one
                whole = one["leader_search"][1]
                assert one["leader_search"][0] == 1
                assert sum(one[k][1] for k in stages) == pytest.approx(
                    whole, rel=1e-9, abs=1e-9)
                assert (one["leader_pre_submit"][1]
                        + one["scatter_queue_wait"][1]
                        + one["scatter_wake"][1]
                        + one["leader_post_wake"][1]) <= whole
                assert all(one[k][1] >= 0 for k in stages)
                # the reply BEFORE this one: its writes and the
                # client's turnaround after them land with this search
                assert one["leader_reply_write"][0] == 1
                assert one["leader_client_gap"][0] == 1
            conn.close()
            got = _grown(start, _sums(*keys))
            for k in keys:
                assert got[k][0] > 0, k
            assert all(got[k][0] == 20 for k in FRONT_DOOR), got
            # the warm-up's reply opened the first gap; the last reply's
            # writes and gap are never observed (the connection closed)
            assert got["leader_client_gap"][0] == 20
            assert got["leader_client_gap"][1] >= 0
            assert got["leader_reply_write"][1] > 0
            # the RPC's three legs ARE the RPC
            n_rpc = got["scatter_rpc"][0]
            assert n_rpc == 20
            assert [got[k][0] for k in LEGS] == [n_rpc] * 3
            assert sum(got[k][1] for k in LEGS) == pytest.approx(
                got["scatter_rpc"][1], rel=1e-6)
            # one process, one clock anchor: no leg under the error of
            # two clock reads, let alone -0.5 ms
            assert got["scatter_rpc_out"][1] / n_rpc > -0.5e-3
            assert got["scatter_rpc_back"][1] / n_rpc > -0.5e-3
            # the worker's own timer of the same branch ends after the
            # reply's last write, the header's at its first
            assert got["scatter_rpc_handle"][1] <= \
                got["phase_handle_batch"][1]
            assert got["scatter_rpc_handle"][1] > 0
        finally:
            _stop_all(nodes)

    def test_first_request_of_a_connection_observes_no_gap(
            self, core, tmp_path):
        nodes = _mk_cluster(core, tmp_path, n=2)
        try:
            _upload_docs(nodes[0])
            keys = ("leader_client_gap", "leader_reply_write",
                    "leader_search")
            before = _sums(*keys)
            for q in ("common", "token3"):
                conn = _keep_alive(nodes[0])
                _post(conn, q)
                conn.close()
            got = _grown(before, _sums(*keys))
            assert got["leader_search"][0] == 2
            assert got["leader_client_gap"][0] == 0
            assert got["leader_reply_write"][0] == 0
        finally:
            _stop_all(nodes)

    @pytest.mark.parametrize("header", [
        None,                                   # an older worker
        "garbage",
        "recv;t=abc,handle;dur=1.0",
        "handle;dur=1.0",
    ])
    def test_reply_without_usable_server_timing_observes_no_leg(
            self, core, tmp_path, monkeypatch, header):
        """The worker's stamp missing or malformed: ``scatter_rpc`` is
        observed as ever, no leg is, nothing is raised and the search
        is answered."""
        import tfidf_tpu.cluster.node as node_mod
        if header is None:
            monkeypatch.setattr(
                node_mod._ScatterClient, "pop_server_timing",
                lambda self: None)
        else:
            monkeypatch.setattr(node_mod, "server_timing",
                                lambda recv_s: header)
        nodes = _mk_cluster(core, tmp_path, n=2)
        try:
            _upload_docs(nodes[0])
            before = _sums("scatter_rpc", *LEGS)
            conn = _keep_alive(nodes[0])
            assert _post(conn, "common token7")
            conn.close()
            got = _grown(before, _sums("scatter_rpc", *LEGS))
            assert got["scatter_rpc"][0] == 1
            assert [got[k][0] for k in LEGS] == [0, 0, 0]
        finally:
            _stop_all(nodes)


class TestRpcLegs:
    def test_header_round_trip(self, monkeypatch):
        m = Metrics()
        monkeypatch.setattr(tracing, "global_metrics", m)
        sent = epoch_now()
        recv = sent + 0.010                 # 10 ms on the way out
        monkeypatch.setattr(tracing, "epoch_now", lambda: recv + 0.040)
        header = server_timing(recv)        # held 40 ms
        trace_rpc_legs("rpc", header, sent, 0.075)
        snap = m.snapshot()
        assert snap["rpc_sum_ms"] == 75.0
        assert snap["rpc_out_sum_ms"] == pytest.approx(10.0, abs=0.01)
        assert snap["rpc_handle_sum_ms"] == pytest.approx(40.0, abs=0.01)
        assert snap["rpc_back_sum_ms"] == pytest.approx(25.0, abs=0.01)

    @pytest.mark.parametrize("header", [
        None, "", "recv;t=,handle;dur=", "recv;t=1e9,handle;dur=-3",
        "miss;desc=cache", "recv;dur=5,handle;t=7"])
    def test_malformed_header_is_ignored_not_raised(self, monkeypatch,
                                                    header):
        m = Metrics()
        monkeypatch.setattr(tracing, "global_metrics", m)
        trace_rpc_legs("rpc", header, epoch_now(), 0.05)
        snap = m.snapshot()
        assert snap["rpc_count"] == 1 and snap["rpc_sum_ms"] == 50.0
        assert not [k for k in snap if k.startswith(
            ("rpc_out", "rpc_handle", "rpc_back"))]


def _spin(seconds: float) -> None:
    """Hold the GIL: pure bytecode, no blocking call."""
    end = time.monotonic() + seconds
    x = 0
    while time.monotonic() < end:
        x += 1


class TestProcessWatch:
    def test_gil_wait_and_cpu_share_under_a_spinning_thread(self):
        m = Metrics()
        w = ProcessWatch(metrics=m)
        w.start()
        try:
            time.sleep(0.4)                 # this thread idle
            idle = m.snapshot()
            t = threading.Thread(target=_spin, args=(0.8,))
            t.start()
            t.join()
            busy = m.snapshot()
        finally:
            w.stop()
        assert idle["gil_wait_count"] >= 20
        n = busy["gil_wait_count"] - idle["gil_wait_count"]
        assert n >= 10
        idle_mean = idle["gil_wait_sum_ms"] / idle["gil_wait_count"]
        busy_mean = (busy["gil_wait_sum_ms"] - idle["gil_wait_sum_ms"]) / n
        # a waker behind a thread that never blocks waits out the
        # interpreter's switch interval (5 ms) before it runs
        assert busy_mean > idle_mean and busy_mean > 1.0, (idle_mean,
                                                           busy_mean)
        cores = ((busy["process_cpu_ms"] - idle["process_cpu_ms"])
                 / (busy["process_wall_ms"] - idle["process_wall_ms"]))
        assert 0.5 < cores < 1.5, cores
        # a spinning loop makes no system call worth the name
        assert (busy["process_sys_ms"] - idle["process_sys_ms"]) \
            < 0.5 * (busy["process_cpu_ms"] - idle["process_cpu_ms"])

    def test_forced_collection_is_one_pause(self):
        m = Metrics()
        w = ProcessWatch(metrics=m)
        was_enabled = gc.isenabled()
        gc.disable()            # only the forced collection below runs
        w.start()
        try:
            time.sleep(0.03)
            before = m.snapshot()
            gc.collect()
            wait_until(lambda: m.snapshot().get("gc_pause_count", 0)
                       > before.get("gc_pause_count", 0))
            after = m.snapshot()
        finally:
            w.stop()
            if was_enabled:
                gc.enable()
        assert after["gc_pause_count"] \
            - before.get("gc_pause_count", 0) == 1
        assert after["gc_collections_gen2"] \
            - before.get("gc_collections_gen2", 0) == 1
        assert after["gc_pause_sum_ms"] > 0

    def test_stop_leaves_no_thread_and_no_hook(self):
        w = ProcessWatch(metrics=Metrics())
        hooks = len(gc.callbacks)
        w.start()
        w.start()                           # a second node of the process
        thread = w._thread
        assert thread.is_alive() and len(gc.callbacks) == hooks + 1
        w.stop()
        assert thread.is_alive() and w._on_gc in gc.callbacks
        w.stop()                            # the last user
        assert not thread.is_alive() and w._thread is None
        assert w._on_gc not in gc.callbacks
        assert len(gc.callbacks) == hooks
        w.stop()                            # past zero: nothing happens
        w.start()                           # and it starts again
        assert w._thread.is_alive() and w._thread is not thread
        w.stop()
        assert w._on_gc not in gc.callbacks

    def test_a_serving_node_holds_the_watch_and_gives_it_back(
            self, core, tmp_path):
        users = tracing.process_watch._users
        nodes = _mk_cluster(core, tmp_path, n=2)
        try:
            assert tracing.process_watch._users == users + 2
            assert tracing.process_watch._thread.is_alive()
            before = global_metrics.snapshot()
            wait_until(lambda: global_metrics.snapshot().get(
                "process_wall_ms", 0) - before.get("process_wall_ms", 0)
                > 20)
        finally:
            _stop_all(nodes)
        assert tracing.process_watch._users == users
        nodes[0].stop()                     # a second stop takes nothing
        assert tracing.process_watch._users == users


class TestDispatchIdle:
    def test_two_chunks_fifty_ms_apart_are_one_idle_span(self,
                                                         monkeypatch):
        """The CPU backend runs searches inline, so the executor is
        built directly. The first chunk finds the thread starting with
        work queued (no idle); the second comes 50 ms after the first
        was done: one observation of that wait."""
        m = Metrics()
        monkeypatch.setattr(tracing, "global_metrics", m)
        ex = PipelineExecutor(depth=2, name="idle-test", idle_s=0.4)
        try:
            assert ex.submit(lambda: (1,), lambda x: x).result(5) == 1
            time.sleep(0.05)
            assert ex.submit(lambda: (2,), lambda x: x).result(5) == 2
            snap = m.snapshot()
            assert snap["phase_dispatch_idle_count"] == 1
            assert 40 <= snap["phase_dispatch_idle_sum_ms"] <= 200
            # the idle EXIT ends the span the second chunk left open
            thread = ex._dispatch_thread
            wait_until(lambda: ex._dispatch_thread is None, timeout=5)
            thread.join(timeout=2)
            assert not thread.is_alive()
            snap = m.snapshot()
            assert snap["phase_dispatch_idle_count"] == 2
            assert 350 <= snap["phase_dispatch_idle_max_ms"] <= 1500
            # and the executor revives, starved no longer
            assert ex.submit(lambda: (3,), lambda x: x).result(5) == 3
            assert m.snapshot()["phase_dispatch_idle_count"] == 2
        finally:
            ex.stop()

    def test_stop_ends_the_open_span(self, monkeypatch):
        m = Metrics()
        monkeypatch.setattr(tracing, "global_metrics", m)
        ex = PipelineExecutor(depth=1, name="idle-stop", idle_s=30.0)
        assert ex.submit(lambda: (1,), lambda x: x).result(5) == 1
        time.sleep(0.02)
        thread = ex._dispatch_thread
        ex.stop()
        assert not thread.is_alive()
        assert m.snapshot()["phase_dispatch_idle_count"] == 1
