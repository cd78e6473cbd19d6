"""Compute-plane chaos suite (ISSUE 20).

Covers the device nemesis at the JAX dispatch seam
(utils/device_nemesis.py), the structured compute-fault classifier
(cluster/resilience.classify_compute_fault), the per-worker
ComputeHealth state machine + host-fallback degraded scoring
(engine/compute_health.py), the OOM batch-backoff ladder, the
poison-query quarantine (cluster/quarantine.py), and the wire surface
they add (X-Compute-Degraded / X-Compute-Fault / X-Poison-Fingerprints
/ X-Poison-Quarantined, /api/ready, /api/quarantine,
/api/device-nemesis).

The load-bearing gate is TestFallbackParity: the host/numpy fallback
must be BIT-identical to the device scoring path (use_pallas=False —
the XLA reference program the kernels are themselves gated against),
across layouts and models.  A fallback that is merely close would turn
"degraded but exact" into a silent correctness lie.

The `make chaos-compute` leg (slow) drives the full live scenario:
zipfian load over a subprocess fleet with an OOM'd worker, a
slow-wedged worker, and a poison query injected mid-run.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tfidf_tpu.cluster.coordination import CoordinationCore, LocalCoordination
from tfidf_tpu.cluster.node import SearchNode, http_get, http_post
from tfidf_tpu.cluster.quarantine import PoisonQuarantine, poison_fingerprint
from tfidf_tpu.cluster.resilience import (RpcStatusError,
                                          classify_compute_fault,
                                          is_retryable)
from tfidf_tpu.engine.compute_health import (DEGRADED, HEALTHY, SICK,
                                             ComputeHealth,
                                             HostFallbackScorer)
from tfidf_tpu.engine.engine import Engine
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.device_nemesis import (DeviceCompileError,
                                            DeviceNemesis, DeviceOOMError,
                                            DevicePoisonedOutput,
                                            DeviceSickError,
                                            DeviceTransientError,
                                            global_device_nemesis)
from tfidf_tpu.utils.metrics import global_metrics


@pytest.fixture(autouse=True)
def _clean_nemesis():
    """Never let an armed rule or sticky sick mode leak across tests —
    the nemesis is process-global by design (the seams consult one
    singleton), so the suite must tear it down the way a chaos run
    does."""
    global_device_nemesis.clear()
    yield
    global_device_nemesis.clear()


CORPUS = {
    "file1.txt": "fast food is fast and cheap",
    "file2.txt": "the cat meowing at night causes trouble",
    "file3.txt": "fast cars go very fast on the road",
    "file4.txt": "cheap food for the cat",
    "file5.txt": "night driving in fast cars",
    "file6.txt": "road food at night is cheap and fast",
}

QUERIES = ["fast food", "cat", "night road", "cheap", "meowing trouble",
           "driving cars fast", "zebra"]


def make_engine(tmp_path, **kw):
    kw.setdefault("use_pallas", False)   # XLA reference path: the
    # program the host mirror is pinned bit-equal to (the Pallas
    # kernels are tolerance-gated against this same reference)
    cfg = Config(documents_path=str(tmp_path / "docs"),
                 index_path=str(tmp_path / "index"),
                 min_nnz_capacity=64, min_doc_capacity=8,
                 min_vocab_capacity=64, query_batch=8,
                 max_query_terms=8, **kw)
    e = Engine(cfg)
    for name, text in CORPUS.items():
        e.ingest_text(name, text)
    e.commit()
    return e


def _post_full(base, path, data, timeout=30.0):
    req = urllib.request.Request(
        base + path, data=data,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _get_full(base, path, timeout=30.0):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


# ---------------------------------------------------------------------------
# device nemesis mechanics
# ---------------------------------------------------------------------------

class TestDeviceNemesis:
    def test_env_format_script_grammar(self):
        n = DeviceNemesis(
            env="score_ell:oom:1.0:min_batch=4,*:delay::delay_s=0.0")
        snap = n.snapshot()
        assert n.armed and not n.sick
        assert [r["kind"] for r in snap["rules"]] == ["oom", "delay"]
        assert snap["rules"][0]["min_batch"] == 4
        assert snap["rules"][1]["site"] == "*"
        # the delay rule sleeps 0s and never raises
        assert n.check("anything") is None
        with pytest.raises(DeviceOOMError):
            n.check("score_ell", batch=4)

    def test_bad_specs_loud(self):
        n = DeviceNemesis(env="")
        with pytest.raises(ValueError):
            n.script("score_ell")              # no kind
        with pytest.raises(ValueError):
            n.script("score_ell:frobnicate")   # unknown kind
        with pytest.raises(ValueError):
            n.script("score_ell:oom:1.0:wat=1")  # unknown option

    def test_glob_sites_and_count_budget(self):
        n = DeviceNemesis(env="")
        n.add_rule("score_*", "transient", count=2)
        with pytest.raises(DeviceTransientError):
            n.check("score_ell")
        with pytest.raises(DeviceTransientError):
            n.check("score_coo")
        # the count budget is spent — the rule goes quiet, not removed
        assert n.check("score_ell") is None
        assert n.snapshot()["rules"][0]["fired"] == 2
        # non-matching site never fired
        n2 = DeviceNemesis(env="")
        n2.add_rule("score_*", "transient")
        assert n2.check("dense") is None

    def test_remove_rule(self):
        n = DeviceNemesis(env="")
        rid = n.add_rule("dense", "compile")
        keep = n.add_rule("dense", "delay", delay_s=0.0)
        assert n.remove_rule(rid) is True
        assert n.remove_rule(rid) is False   # already gone
        assert [r["rid"] for r in n.snapshot()["rules"]] == [keep]
        assert n.check("dense") is None      # compile rule is gone
        assert DeviceNemesis.remove_rule is not None

    def test_sick_is_sticky_until_heal(self):
        n = DeviceNemesis(env="score_ell:sick::count=1")
        with pytest.raises(DeviceSickError):
            n.check("score_ell")
        assert n.sick
        # EVERY seam fails now, count budget notwithstanding
        with pytest.raises(DeviceSickError):
            n.check("dense")
        with pytest.raises(DeviceSickError):
            n.check("upload")
        n.heal()
        assert not n.sick
        assert n.check("dense") is None
        # clear() drops rules AND sick
        n.script("*:sick")
        with pytest.raises(DeviceSickError):
            n.check("score_ell")
        n.clear()
        assert not n.armed and n.check("score_ell") is None

    def test_min_batch_gate(self):
        n = DeviceNemesis(env="")
        n.add_rule("score_ell", "oom", min_batch=8)
        assert n.check("score_ell", batch=4) is None
        with pytest.raises(DeviceOOMError):
            n.check("score_ell", batch=8)

    @pytest.mark.parametrize("widths", [(4,), (4, 2, 8)],
                             ids=["matrix", "ell_blocks"])
    def test_poison_rule_and_row_targeting(self, widths):
        """One ``[B, n]`` matrix, or the ELL scorer's tuple of blocks:
        the same query rows turn NaN in every block."""
        import jax.numpy as jnp

        from tfidf_tpu.utils.device_nemesis import poison_scores
        n = DeviceNemesis(env="score_ell:poison:1.0:min_uniq=2")
        rule = n.check("score_ell")
        assert rule is not None and rule.kind == "poison"
        scores = tuple(jnp.ones((3, w), jnp.float32) for w in widths)
        if len(scores) == 1:
            scores = scores[0]
        weights = jnp.asarray([[1.0, 1.0, 0.0],    # 2 uniq -> poisoned
                               [1.0, 0.0, 0.0],    # 1 uniq -> intact
                               [1.0, 2.0, 3.0]],   # 3 uniq -> poisoned
                              jnp.float32)

        def flat(out):      # blocks side by side: [3, sum(widths)]
            return np.concatenate(
                [np.asarray(o) for o in
                 (out if isinstance(out, tuple) else (out,))], axis=1)
        out = flat(poison_scores(scores, weights, rule.min_uniq))
        assert out.shape == (3, sum(widths))
        assert np.isnan(out[0]).all() and np.isnan(out[2]).all()
        assert (out[1] == 1.0).all()
        # min_uniq=0 poisons everything
        assert np.isnan(flat(poison_scores(scores, weights, 0))).all()

    def test_fire_emits_metric(self):
        before = global_metrics.snapshot().get("device_nemesis_fired", 0)
        n = DeviceNemesis(env="x:transient")
        with pytest.raises(DeviceTransientError):
            n.check("x")
        assert global_metrics.snapshot()["device_nemesis_fired"] \
            == before + 1


# ---------------------------------------------------------------------------
# structured fault classifier (the string-match retry gate's successor)
# ---------------------------------------------------------------------------

class TestClassifier:
    def test_typed_nemesis_exceptions(self):
        assert classify_compute_fault(DeviceOOMError("x")) == "oom"
        assert classify_compute_fault(DeviceCompileError("x")) == "compile"
        assert classify_compute_fault(
            DeviceTransientError("x")) == "transient"
        assert classify_compute_fault(DeviceSickError("x")) == "transient"
        assert classify_compute_fault(
            DevicePoisonedOutput(("q",))) == "poison"

    def test_jax_runtime_error_message_taxonomy(self):
        # jaxlib buries the class in the message. The texts are what
        # the v5e compiler and runtime really say (ISSUE 21's
        # compile-only runs): HBM exhaustion is what the batch ladder
        # can cure; scoped-VMEM exhaustion and a Mosaic refusal are
        # properties of the compiled shape.
        from jax.errors import JaxRuntimeError
        from jax._src.pallas.mosaic.error_handling import MosaicError
        assert classify_compute_fault(JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
            "out of memory in memory space hbm. Used 20.10G of 15.75G "
            "hbm.")) == "oom"
        assert classify_compute_fault(JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space "
            "vmem while allocating on stack for %_lambda_.1 = "
            "f32[1024,131072]{1,0:T(8,128)} custom-call(...). Scoped "
            "allocation with size 18.20M and limit 16.00M exceeded "
            "scoped vmem limit by 2.20M.")) == "compile"
        assert classify_compute_fault(MosaicError(
            "INTERNAL: Mosaic failed to compile TPU kernel: cannot "
            "statically prove that index in dimension 0 is a multiple "
            "of 8")) == "compile"
        assert classify_compute_fault(JaxRuntimeError(
            "INTERNAL: something else")) == "transient"
        # an arbitrary RuntimeError is not a device fault, whatever
        # its text says
        assert classify_compute_fault(RuntimeError(
            "RESOURCE_EXHAUSTED: out of memory")) is None

    def test_non_device_exceptions_are_none(self):
        assert classify_compute_fault(ValueError("nope")) is None
        assert classify_compute_fault(OSError("disk")) is None

    def test_stamped_rpc_error_carries_worker_verdict(self):
        e = RpcStatusError("http://w/x", 500, compute_fault="oom")
        assert classify_compute_fault(e) == "oom"
        # a compute fault is deterministic on the worker's current
        # state: failover, not retry
        assert not is_retryable(e)
        p = RpcStatusError("http://w/x", 500, compute_fault="poison",
                           poison_fps=("aabbccddeeff",))
        assert classify_compute_fault(p) == "poison"
        assert p.poison_fps == ("aabbccddeeff",)
        assert not is_retryable(p)


# ---------------------------------------------------------------------------
# ComputeHealth state machine
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class TestComputeHealth:
    def test_escalation_and_reset(self):
        h = ComputeHealth(degraded_after=2, sick_after=4)
        assert h.state == HEALTHY
        h.note_fault("transient")
        assert h.state == HEALTHY
        h.note_fault("oom")
        assert h.state == DEGRADED
        h.note_fault("transient")
        assert h.state == DEGRADED
        h.note_fault("transient")
        assert h.state == SICK
        h.note_success()
        assert h.state == HEALTHY and h.consecutive_faults == 0
        snap = h.snapshot()
        assert snap["total_faults"] == 4
        assert snap["faults_by_kind"] == {"transient": 3, "oom": 1}

    def test_poison_never_advances_the_machine(self):
        h = ComputeHealth(degraded_after=1, sick_after=2)
        for _ in range(10):
            h.note_fault("poison")
        assert h.state == HEALTHY
        assert h.snapshot()["total_faults"] == 0

    def test_probe_pacing_rations_one_slot_per_interval(self):
        clk = FakeClock()
        h = ComputeHealth(degraded_after=1, sick_after=2,
                          probe_interval_s=5.0, clock=clk)
        h.note_fault("transient")
        h.note_fault("transient")
        assert h.state == SICK
        # between probes: nobody gets the device
        assert not h.should_try_device()
        clk.t += 5.0
        # exactly ONE caller claims the probe slot per interval
        assert h.should_try_device()
        assert not h.should_try_device()
        assert h.snapshot()["recovery_probes"] == 1
        # a successful probe heals
        h.note_success()
        assert h.state == HEALTHY and h.should_try_device()


# ---------------------------------------------------------------------------
# poison-query quarantine
# ---------------------------------------------------------------------------

class TestPoisonQuarantine:
    def test_replica_distinct_threshold(self):
        q = PoisonQuarantine(after=2)
        fp = poison_fingerprint("bad query")
        # one replica, even repeatedly, is possibly just a sick device
        assert not q.note_fault(fp, "http://w1")
        assert not q.note_fault(fp, "http://w1")
        assert not q.is_quarantined(fp)
        # the second DISTINCT replica is the crossing observation
        assert q.note_fault(fp, "http://w2")
        assert q.is_quarantined(fp)
        # crossing fires once — later blame does not re-announce
        assert not q.note_fault(fp, "http://w3")

    def test_fingerprint_is_query_and_plan_scoped(self):
        assert poison_fingerprint("q", "sparse") \
            != poison_fingerprint("q", "dense")
        assert poison_fingerprint("a") != poison_fingerprint("b")
        assert len(poison_fingerprint("a")) == 12

    def test_ttl_expiry_and_touch_refresh(self):
        clk = FakeClock()
        q = PoisonQuarantine(after=1, ttl_s=10.0, clock=clk)
        fp = poison_fingerprint("doom")
        assert q.note_fault(fp, "w1")
        clk.t += 6.0
        # an admission hit refreshes the verdict (actively re-sent
        # poison must not slip back in by persisting past the TTL)
        assert q.is_quarantined(fp)
        clk.t += 6.0
        assert q.is_quarantined(fp)    # 12s after blame, still warm
        clk.t += 11.0
        assert not q.is_quarantined(fp)   # idle past TTL: expired

    def test_lru_bound(self):
        q = PoisonQuarantine(after=1, max_entries=4)
        fps = [poison_fingerprint(f"q{i}") for i in range(6)]
        for fp in fps:
            q.note_fault(fp, "w1")
        snap = q.snapshot()
        assert snap["tracked"] == 4
        kept = {e["fingerprint"] for e in snap["quarantined"]}
        assert kept == set(fps[2:])    # oldest two evicted

    def test_snapshot_and_clear(self):
        q = PoisonQuarantine(after=2, ttl_s=99.0)
        fp = poison_fingerprint("x")
        q.note_fault(fp, "w1")
        q.note_fault(fp, "w2")
        snap = q.snapshot()
        assert snap["after"] == 2 and snap["tracked"] == 1
        (e,) = snap["quarantined"]
        assert e["fingerprint"] == fp
        assert e["replicas"] == ["w1", "w2"]
        assert q.clear() == 1
        assert q.snapshot()["tracked"] == 0
        assert not q.is_quarantined(fp)


# ---------------------------------------------------------------------------
# host-fallback bit-parity gate
# ---------------------------------------------------------------------------

class TestFallbackParity:
    """The acceptance gate: host scoring bit-compares against the
    device (XLA reference) path — same values, same ids, across
    layouts and models."""

    @pytest.mark.parametrize("layout", ["ell", "coo"])
    @pytest.mark.parametrize("model", ["bm25", "tfidf", "tfidf_cosine"])
    def test_bit_parity_arrays(self, tmp_path, layout, model):
        e = make_engine(tmp_path, scoring_layout=layout, model=model)
        dev_vals, dev_ids, dev_kk, dev_names = \
            e.searcher.search_arrays(QUERIES, k=5)
        fb = HostFallbackScorer(e.searcher)
        h_vals, h_ids, h_kk, h_names = fb.search_arrays(QUERIES, k=5)
        assert h_kk == dev_kk and list(h_names) == list(dev_names)
        # BIT equality, not allclose: the fallback's claim is "exact",
        # and ties must break identically for ids to match
        assert np.asarray(dev_vals).tobytes() == h_vals.tobytes()
        assert np.array_equal(np.asarray(dev_ids), h_ids)

    def test_bit_parity_with_ell_residual_spill(self, tmp_path):
        # a tiny width cap forces long docs to spill into the residual
        # COO pass — the mirror must reproduce BOTH planes bit-exactly
        e = make_engine(tmp_path, scoring_layout="ell", ell_width_cap=4)
        snap = e.index.snapshot
        assert snap.res_tf is not None, "no residual spill — test inert"
        dev = e.searcher.search_arrays(QUERIES, k=5)
        host = HostFallbackScorer(e.searcher).search_arrays(QUERIES, k=5)
        assert np.asarray(dev[0]).tobytes() == host[0].tobytes()
        assert np.array_equal(np.asarray(dev[1]), host[1])

    @pytest.mark.parametrize("width_cap", [None, 24],
                             ids=["no_residual", "residual"])
    def test_bit_parity_over_fetched_width_major_blocks(self, tmp_path,
                                                        width_cap):
        """The mirror scores from the blocks AS FETCHED, ``[width,
        rows_cap]`` (PR 43; no host copy turns them): several blocks
        whose width is not their row capacity, with and without a
        residual, the device's hits bit for bit."""
        e = make_engine(tmp_path, scoring_layout="ell",
                        ell_width_cap=width_cap)
        rng = np.random.default_rng(11)
        for i, n in enumerate([40] * 3 + [18] * 10 + [5] * 20):
            words = rng.choice(60, size=n, replace=False)
            e.ingest_text(f"long{i}.txt", " ".join(f"w{w}" for w in words)
                          + " fast cat")
        e.commit()
        snap = e.index.snapshot
        shapes = [tuple(a.shape) for a in snap.ell_impacts]
        assert shapes == ([(48, 8)] if width_cap is None else []) \
            + [(24, 16), (8, 32)]
        assert (snap.res_tf is not None) == (width_cap is not None)
        fb = HostFallbackScorer(e.searcher)
        queries = QUERIES + ["w1 w2 fast", "w7", "w30 w31 cat night"]
        host = fb.search_arrays(queries, k=5)
        mirror = fb._mirror_for(snap)
        assert [a.shape for a in mirror.imps] == shapes \
            == [a.shape for a in mirror.terms]
        dev = e.searcher.search_arrays(queries, k=5)
        assert np.asarray(dev[0]).tobytes() == host[0].tobytes()
        assert np.array_equal(np.asarray(dev[1]), host[1])
        assert np.asarray(dev[0])[-1].max() > 0

    def test_bit_parity_assembled_hits_and_unbounded(self, tmp_path):
        e = make_engine(tmp_path)
        fb = HostFallbackScorer(e.searcher)
        for unbounded in (False, True):
            dev = e.searcher.search(QUERIES, k=4, unbounded=unbounded)
            host = fb.search(QUERIES, k=4, unbounded=unbounded)
            assert [[(h.name, h.score) for h in hits] for hits in dev] \
                == [[(h.name, h.score) for h in hits] for hits in host]

    def test_mirror_built_once_per_snapshot(self, tmp_path):
        e = make_engine(tmp_path)
        fb = HostFallbackScorer(e.searcher)
        before = global_metrics.snapshot().get(
            "compute_fallback_mirror_builds", 0)
        fb.search(["fast"])
        fb.search(["cat"])
        assert global_metrics.snapshot()[
            "compute_fallback_mirror_builds"] == before + 1
        # a new commit invalidates the mirror
        e.ingest_text("file7.txt", "brand new cheap cars document")
        e.commit()
        fb.search(["cheap"])
        assert global_metrics.snapshot()[
            "compute_fallback_mirror_builds"] == before + 2


# ---------------------------------------------------------------------------
# the engine's compute guard: degradation, ladder, poison honesty
# ---------------------------------------------------------------------------

class TestEngineComputeGuard:
    def test_fault_degrades_to_exact_host_serving(self, tmp_path):
        e = make_engine(tmp_path, compute_sick_after=2,
                        compute_probe_interval_s=3600.0)
        baseline = e.search_batch(QUERIES, k=4)
        assert not e.pop_fallback_served()
        global_device_nemesis.script("score_ell:transient")
        for _ in range(3):
            got = e.search_batch(QUERIES, k=4)
            # exact, not approximate — bit-identical hit lists
            assert [[(h.name, h.score) for h in hs] for hs in got] \
                == [[(h.name, h.score) for h in hs] for hs in baseline]
            assert e.pop_fallback_served()
        stats = e.compute_stats()
        assert stats["state"] == SICK
        assert stats["fallback_available"] is True
        # sick: the device is no longer even tried (probe interval is
        # an hour) — the nemesis would raise if it were
        assert global_metrics.snapshot()["compute_fallback_served"] > 0

    def test_recovery_probe_heals(self, tmp_path):
        e = make_engine(tmp_path, compute_degraded_after=1,
                        compute_sick_after=1,
                        compute_probe_interval_s=0.0)
        baseline = e.search_batch(["fast food"], k=3)
        rid = global_device_nemesis.add_rule("score_ell", "transient")
        e.search_batch(["fast food"], k=3)
        assert e.compute_stats()["state"] == SICK
        assert e.pop_fallback_served()
        # device fixed; the next request claims the probe slot
        # (interval 0), runs the device path, and heals the machine
        global_device_nemesis.remove_rule(rid)
        got = e.search_batch(["fast food"], k=3)
        assert [[(h.name, h.score) for h in hs] for hs in got] \
            == [[(h.name, h.score) for h in hs] for hs in baseline]
        assert not e.pop_fallback_served()
        assert e.compute_stats()["state"] == HEALTHY
        assert e.compute_stats()["recovery_probes"] >= 1

    def test_oom_ladder_retries_smaller_batches(self, tmp_path):
        e = make_engine(tmp_path, oom_backoff_min_batch=1)
        qs = QUERIES + ["food cheap"]          # 8 queries -> cap 8
        baseline = e.search_batch(qs, k=4)
        before = global_metrics.snapshot().get("compute_oom_backoff", 0)
        # OOM fires only at batch cap >= 8: the full batch dies, the
        # B/2 rungs (cap 4) succeed
        global_device_nemesis.script("score_ell:oom:1.0:min_batch=8")
        got = e.search_batch(qs, k=4)
        assert [[(h.name, h.score) for h in hs] for hs in got] \
            == [[(h.name, h.score) for h in hs] for hs in baseline]
        assert global_metrics.snapshot()["compute_oom_backoff"] \
            == before + 1
        # the ladder succeeded on device: no fallback involved, and
        # the recovery reset health
        assert not e.pop_fallback_served()
        assert e.compute_stats()["state"] == HEALTHY

    def test_oom_floor_degrades_to_fallback(self, tmp_path):
        e = make_engine(tmp_path, oom_backoff_min_batch=8)
        qs = QUERIES + ["food cheap"]
        baseline = e.search_batch(qs, k=4)
        # every rung >= the floor OOMs -> the ladder dries out and the
        # host mirror serves
        global_device_nemesis.script("score_ell:oom")
        got = e.search_batch(qs, k=4)
        assert [[(h.name, h.score) for h in hs] for hs in got] \
            == [[(h.name, h.score) for h in hs] for hs in baseline]
        assert e.pop_fallback_served()

    def test_poison_is_never_absorbed(self, tmp_path):
        e = make_engine(tmp_path)
        # rows with >= 4 distinct terms are poisoned; the cohort is not
        global_device_nemesis.script("score_ell:poison:1.0:min_uniq=4")
        poison_q = "fast food cheap night"
        with pytest.raises(DevicePoisonedOutput) as ei:
            e.search_batch(["cat", poison_q], k=4)
        # per-query blame: only the offending row is named
        assert ei.value.queries == (poison_q,)
        # a fallback exists, but poison must surface, not degrade
        assert not e.pop_fallback_served()
        # and the health machine did not move (query problem, not a
        # sick device)
        assert e.compute_stats()["state"] == HEALTHY
        assert global_metrics.snapshot()["compute_poison_outputs"] >= 1
        # innocent queries alone still serve on device
        assert e.search_batch(["cat"], k=4)[0]

    def test_fallback_disabled_faults_surface(self, tmp_path):
        e = make_engine(tmp_path, compute_fallback=False)
        global_device_nemesis.script("score_ell:transient")
        with pytest.raises(DeviceTransientError):
            e.search_batch(["fast"], k=3)
        assert e.compute_stats()["fallback_available"] is False

    def test_dense_plane_poison_detected(self, tmp_path):
        import jax.numpy as jnp

        from tfidf_tpu.ops.dense import dense_scores
        q = jnp.ones((2, 4), jnp.float32)
        emb = jnp.ones((3, 4), jnp.float32)
        n = jnp.int32(3)
        clean = np.asarray(dense_scores(q, emb, n))
        assert np.isfinite(clean).all()
        global_device_nemesis.script("dense:poison")
        assert np.isnan(np.asarray(dense_scores(q, emb, n))).all()


# ---------------------------------------------------------------------------
# ops surface on a live node
# ---------------------------------------------------------------------------

@pytest.fixture
def core():
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


def _node_cfg(tmp_path, tag, **kw):
    return Config(documents_path=str(tmp_path / tag / "docs"),
                  index_path=str(tmp_path / tag / "index"),
                  port=0, min_doc_capacity=64,
                  min_nnz_capacity=1 << 12, min_vocab_capacity=1 << 10,
                  query_batch=8, max_query_terms=8, use_pallas=False,
                  **kw)


class TestOpsSurface:
    def test_ready_health_and_quarantine_endpoints(self, core, tmp_path):
        node = SearchNode(
            _node_cfg(tmp_path, "ops", compute_fallback=False,
                      compute_sick_after=2,
                      compute_probe_interval_s=3600.0),
            coord=LocalCoordination(core, 0.1)).start()
        try:
            # healthy: ready, and /api/health carries the compute block
            st, _, body = _get_full(node.url, "/api/ready")
            assert st == 200 and json.loads(body)["ready"] is True
            h = json.loads(http_get(node.url + "/api/health"))
            assert h["compute"]["state"] == HEALTHY
            assert h["compute"]["fallback_available"] is False
            # sick WITHOUT a fallback: not ready (the k8s
            # readinessProbe takes the pod out of Service endpoints),
            # but /api/health still answers — never a liveness failure
            node.engine.compute.note_fault("transient")
            node.engine.compute.note_fault("transient")
            st, hd, body = _get_full(node.url, "/api/ready")
            assert st == 503
            assert hd.get("Retry-After") == "1"
            assert json.loads(body)["ready"] is False
            assert json.loads(http_get(
                node.url + "/api/health"))["compute"]["state"] == SICK
            # recovery restores readiness
            node.engine.compute.note_success()
            st, _, _b = _get_full(node.url, "/api/ready")
            assert st == 200

            # quarantine: GET snapshot + POST clear
            snap = json.loads(http_get(node.url + "/api/quarantine"))
            assert snap["tracked"] == 0
            fp = poison_fingerprint("doom query")
            node.quarantine.note_fault(fp, "http://w1")
            node.quarantine.note_fault(fp, "http://w2")
            snap = json.loads(http_get(node.url + "/api/quarantine"))
            assert [e["fingerprint"]
                    for e in snap["quarantined"]] == [fp]
            got = json.loads(http_post(node.url + "/api/quarantine",
                                       b"{}"))
            assert got == {"cleared": 1}
        finally:
            node.stop()

    def test_sick_with_fallback_stays_ready(self, core, tmp_path):
        node = SearchNode(
            _node_cfg(tmp_path, "rdy", compute_degraded_after=1,
                      compute_sick_after=1,
                      compute_probe_interval_s=3600.0),
            coord=LocalCoordination(core, 0.1)).start()
        try:
            node.engine.compute.note_fault("oom")
            assert node.engine.compute_stats()["state"] == SICK
            # degraded (host-fallback) serving is slower but exact:
            # the pod must STAY in the Service endpoints
            st, _, body = _get_full(node.url, "/api/ready")
            assert st == 200 and json.loads(body)["ready"] is True
        finally:
            node.stop()

    def test_device_nemesis_endpoint_gated_and_scriptable(
            self, core, tmp_path):
        off = SearchNode(_node_cfg(tmp_path, "off"),
                         coord=LocalCoordination(core, 0.1)).start()
        try:
            st, _, _b = _get_full(off.url, "/api/device-nemesis")
            assert st == 403
            st, _, _b = _post_full(off.url, "/api/device-nemesis",
                                   b'{"script": "score_ell:oom"}')
            assert st == 403
            assert not global_device_nemesis.armed   # gate held
        finally:
            off.stop()
        on = SearchNode(_node_cfg(tmp_path, "on",
                                  device_nemesis_api=True),
                        coord=LocalCoordination(core, 0.1)).start()
        try:
            st, _, body = _post_full(
                on.url, "/api/device-nemesis",
                b'{"script": "score_ell:transient::count=1"}')
            assert st == 200
            got = json.loads(body)
            assert got["armed"] is True and len(got["rules"]) == 1
            snap = json.loads(http_get(on.url + "/api/device-nemesis"))
            assert snap["rules"][0]["site"] == "score_ell"
            st, _, body = _post_full(on.url, "/api/device-nemesis",
                                     b'{"clear": true}')
            assert json.loads(body)["armed"] is False
            assert not global_device_nemesis.armed
        finally:
            on.stop()

    def test_cli_status_and_quarantine_commands(self, core, tmp_path,
                                                capsys):
        from tfidf_tpu.cli import main as cli_main
        node = SearchNode(_node_cfg(tmp_path, "cli"),
                          coord=LocalCoordination(core, 0.1)).start()
        try:
            assert cli_main(["status", "--leader", node.url]) == 0
            out = json.loads(capsys.readouterr().out)
            assert out["compute"]["sick_nodes"] == []
            assert "fallback_served_total" in out["compute"]

            fp = poison_fingerprint("cli doom")
            node.quarantine.note_fault(fp, "w1")
            node.quarantine.note_fault(fp, "w2")
            assert cli_main(["quarantine", node.url]) == 0
            snap = json.loads(capsys.readouterr().out)
            assert [e["fingerprint"]
                    for e in snap["quarantined"]] == [fp]
            assert cli_main(["quarantine", node.url, "--clear"]) == 0
            assert json.loads(capsys.readouterr().out) \
                == {"cleared": 1}
        finally:
            node.stop()


# ---------------------------------------------------------------------------
# cluster end-to-end: degraded stamps + quarantine at the front door
# ---------------------------------------------------------------------------

@pytest.fixture
def compute_cluster(core, tmp_path):
    """Leader + two workers, single-copy placement, tuned for fast
    compute-health transitions."""
    nodes = []
    for i in range(3):
        cfg = _node_cfg(tmp_path, f"cc{i}", replication_factor=1,
                        result_order="name",
                        # no result cache: every request must actually
                        # scatter, or the degraded stamp (a per-scatter
                        # verdict) would vanish behind cache hits
                        result_cache_entries=0,
                        router_cache_entries=0,
                        compute_sick_after=2,
                        compute_probe_interval_s=3600.0,
                        poison_quarantine_after=2)
        node = SearchNode(cfg, coord=LocalCoordination(core, 0.1))
        node.start()
        nodes.append(node)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and len(
            nodes[0].registry.get_all_service_addresses()) < 2:
        time.sleep(0.02)
    yield nodes
    for n in nodes:
        try:
            n.stop()
        except Exception:
            pass


POISON_Q = "alpha beta"   # 2 distinct terms, present on EVERY shard


class TestClusterComputePlane:
    def _upload(self, leader):
        docs = [{"name": n, "text": t} for n, t in CORPUS.items()]
        http_post(leader.url + "/leader/upload-batch",
                  json.dumps(docs).encode())

    def _upload_poison_corpus(self, leader):
        # every doc carries BOTH poison terms, so every worker's shard
        # vocabulary sees 2 distinct query terms for POISON_Q — the
        # min_uniq row filter must fire on every replica, not just the
        # one that happened to receive the rare terms
        docs = [{"name": f"p{i}.txt", "text": f"alpha beta tok{i}"}
                for i in range(6)]
        http_post(leader.url + "/leader/upload-batch",
                  json.dumps(docs).encode())

    def test_degraded_worker_stamps_end_to_end(self, compute_cluster):
        leader, w1, w2 = compute_cluster
        self._upload(leader)
        st, hd, body = _post_full(leader.url, "/leader/start",
                                  json.dumps({"query": "fast"}).encode())
        assert st == 200 and "X-Compute-Degraded" not in hd
        baseline = json.loads(body)
        assert baseline
        # wedge ONE worker's device sick (direct state injection — the
        # nemesis is process-global and would hit every in-process
        # node): its share now serves from the host mirror
        w1.engine.compute.note_fault("transient")
        w1.engine.compute.note_fault("transient")
        st, hd, body = _post_full(leader.url, "/leader/start",
                                  json.dumps({"query": "fast"}).encode())
        assert st == 200
        assert hd.get("X-Compute-Degraded") == "1"   # one worker
        # exact, not approximate: same merged scores as the baseline
        assert json.loads(body) == baseline
        # the worker recovers -> the stamp disappears
        w1.engine.compute.note_success()
        st, hd, body = _post_full(leader.url, "/leader/start",
                                  json.dumps({"query": "fast"}).encode())
        assert st == 200 and "X-Compute-Degraded" not in hd
        assert json.loads(body) == baseline

    def test_poison_quarantine_front_door_422(self, compute_cluster):
        leader, w1, w2 = compute_cluster
        self._upload_poison_corpus(leader)
        fp = poison_fingerprint(POISON_Q, "sparse")
        # poison rows with >= 2 distinct terms on every worker device
        # (process-global nemesis; the leader scatters, it does not
        # score) — normal 1-term queries are untouched cohorts
        global_device_nemesis.script("score_ell:poison:1.0:min_uniq=2")
        # first send: both workers return 500 + X-Poison-Fingerprints;
        # two DISTINCT replicas blame the fingerprint -> quarantined
        st, hd, body = _post_full(
            leader.url, "/leader/start",
            json.dumps({"query": POISON_Q}).encode())
        snap = json.loads(http_get(leader.url + "/api/quarantine"))
        assert [e["fingerprint"] for e in snap["quarantined"]] == [fp]
        assert len(snap["quarantined"][0]["replicas"]) == 2
        # second send: refused at the front door, no worker touched
        st, hd, body = _post_full(
            leader.url, "/leader/start",
            json.dumps({"query": POISON_Q}).encode())
        assert st == 422
        assert hd.get("X-Poison-Quarantined") == fp
        got = json.loads(body)
        assert got["fingerprint"] == fp and got["retry_after_s"] > 0
        # a 422 is the never-retried application-rejection class
        assert not is_retryable(RpcStatusError("u", 422))
        # poison is a QUERY verdict: innocent queries still serve, on
        # device, from the same workers
        st, hd, body = _post_full(leader.url, "/leader/start",
                                  json.dumps({"query": "tok1"}).encode())
        assert st == 200 and json.loads(body)
        assert "X-Compute-Degraded" not in hd
        assert w1.engine.compute_stats()["state"] == HEALTHY
        assert w2.engine.compute_stats()["state"] == HEALTHY
        # operator override: clear -> admitted again
        global_device_nemesis.clear()
        assert json.loads(http_post(
            leader.url + "/api/quarantine", b"{}"))["cleared"] == 1
        st, _, body = _post_full(
            leader.url, "/leader/start",
            json.dumps({"query": POISON_Q}).encode())
        assert st == 200 and json.loads(body)


# ---------------------------------------------------------------------------
# the live chaos leg: `make chaos-compute`
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestChaosCompute:
    @pytest.mark.timeout(420)
    def test_oom_wedge_poison_quarantine_recovery(self, tmp_path):
        """``make chaos-compute``: zipfian-ish closed-loop load over a
        subprocess fleet (leader + 3 workers, R=2). Mid-run one worker
        is OOM'd (every dispatch), another is slow-wedged (dispatch
        delay), and a poison query is injected. Every 200 must be
        exact-parity-or-honestly-stamped, no acked write is ever lost,
        the quarantine engages after exactly two distinct replicas
        blame the poison fingerprint (the third poisoned worker is
        never touched by it again), and after the nemeses clear the
        fleet converges back to exact, unmarked device serving."""
        import os
        import signal  # noqa: F401  (parity with sibling chaos jobs)
        import socket
        import subprocess
        import sys

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        env = os.environ.copy()
        env["JAX_PLATFORMS"] = "cpu"
        env["TFIDF_JAX_PLATFORM"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env.pop("TFIDF_DEVICE_NEMESIS", None)
        env.update({
            "TFIDF_REPLICATION_FACTOR": "2",
            "TFIDF_TOP_K": "32",
            "TFIDF_USE_PALLAS": "false",
            "TFIDF_SESSION_TIMEOUT_S": "2.0",
            "TFIDF_HEARTBEAT_INTERVAL_S": "0.3",
            "TFIDF_MIN_DOC_CAPACITY": "64",
            "TFIDF_MIN_NNZ_CAPACITY": "4096",
            "TFIDF_MIN_VOCAB_CAPACITY": "1024",
            "TFIDF_QUERY_BATCH": "8",
            "TFIDF_MAX_QUERY_TERMS": "8",
            "TFIDF_DEVICE_NEMESIS_API": "1",
            "TFIDF_COMPUTE_SICK_AFTER": "3",
            "TFIDF_COMPUTE_PROBE_INTERVAL_S": "0.5",
            "TFIDF_POISON_QUARANTINE_AFTER": "2",
            "TFIDF_OOM_BACKOFF_MIN_BATCH": "8",
            # no result caches: every reply must reflect a live
            # scatter, or cache hits would hide the degraded stamps
            # this scenario asserts on
            "TFIDF_RESULT_CACHE_ENTRIES": "0",
            "TFIDF_ROUTER_CACHE_ENTRIES": "0",
        })
        procs = {}

        def spawn(tag, args):
            p = subprocess.Popen(
                [sys.executable, "-m", "tfidf_tpu", *args],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
            procs[tag] = p
            return p

        def wait_pred(pred, timeout=120.0, interval=0.2):
            deadline = time.monotonic() + timeout
            last = None
            while time.monotonic() < deadline:
                try:
                    if pred():
                        return True
                except Exception as e:
                    last = e
                time.sleep(interval)
            raise AssertionError(f"timed out; last={last!r}")

        coord_port = free_port()
        try:
            spawn("coord", ["coordinator", "--listen",
                            f"127.0.0.1:{coord_port}"])
            wait_pred(lambda: socket.create_connection(
                ("127.0.0.1", coord_port), timeout=1.0).close() or True,
                timeout=60)
            nports = [free_port() for _ in range(4)]
            nurls = [f"http://127.0.0.1:{p}" for p in nports]
            for i, p in enumerate(nports):
                spawn(f"n{i}", [
                    "serve", "--port", str(p), "--host", "127.0.0.1",
                    "--coordinator-address", f"127.0.0.1:{coord_port}",
                    "--documents-path", str(tmp_path / f"ch{i}/docs"),
                    "--index-path", str(tmp_path / f"ch{i}/idx")])
                wait_pred(lambda u=nurls[i]: http_get(
                    u + "/api/status", timeout=5.0))
            leader, workers = nurls[0], nurls[1:]
            wait_pred(lambda: len(json.loads(http_get(
                leader + "/api/services"))) == 3)
            # 24 acked writes; every doc carries "common" so one query
            # enumerates the full corpus (the zero-loss witness)
            docs = {f"ch{i}.txt":
                    f"common token{i} word{i % 3} extra{i % 5}"
                    for i in range(24)}
            resp = json.loads(http_post(
                leader + "/leader/upload-batch",
                json.dumps([{"name": n, "text": t}
                            for n, t in docs.items()]).encode()))
            assert sum(resp["placed"].values()) == 48   # 24 docs x R=2

            # the poison query needs >= 6 distinct POSITIVE-WEIGHT
            # terms in EVERY shard's vocabulary (min_uniq is a
            # per-device row filter over weights>0 — "common" has
            # df=N, idf 0, and would not count): with 24 docs over 3
            # workers every shard holds all of word0-2/extra0-4, while
            # the 1-2 term client queries stay far under the filter
            poison_q = "word0 word1 word2 extra0 extra1 extra2"
            qpool = ["common"] + [f"token{i} word{i % 3}"
                                  for i in range(24)]
            # all-workers-ready barrier, then the exact baseline
            baseline = {}
            for q in qpool + [poison_q]:
                st, hd, body = _post_full(
                    leader, "/leader/start",
                    json.dumps({"query": q}).encode())
                assert st == 200 and "X-Scatter-Degraded" not in hd, \
                    (q, st, hd)
                baseline[q] = json.loads(body)
            assert set(baseline["common"]) == set(docs)   # zero loss

            outcomes = {"exact": 0, "compute_degraded": 0,
                        "degraded": 0, "failed": 0}
            olock = threading.Lock()
            errors: list[str] = []
            stop = threading.Event()

            def client(cid):
                import random
                rng = random.Random(cid)
                while not stop.is_set():
                    q = qpool[int(rng.random() ** 2 * len(qpool))]
                    try:
                        st, hd, body = _post_full(
                            leader, "/leader/start",
                            json.dumps({"query": q}).encode(),
                            timeout=30.0)
                    except Exception:
                        st, hd, body = None, {}, b""
                    if st != 200:
                        verdict = "failed"
                    elif json.loads(body) == baseline[q]:
                        verdict = ("compute_degraded"
                                   if "X-Compute-Degraded" in hd
                                   else "exact")
                    elif "X-Scatter-Degraded" in hd \
                            or "X-Compute-Degraded" in hd:
                        verdict = "degraded"   # honest partials only
                    else:
                        errors.append(
                            f"unmarked non-parity 200 for {q!r}")
                        return
                    with olock:
                        outcomes[verdict] += 1

            threads = [threading.Thread(target=client, args=(c,),
                                        daemon=True) for c in range(4)]
            for t in threads:
                t.start()
            time.sleep(2.0)

            # nemesis 1: every dispatch on w0 OOMs (the ladder dries
            # out at the floor) -> host-fallback degraded serving
            http_post(workers[0] + "/api/device-nemesis",
                      json.dumps({"script": "*:oom"}).encode())
            # nemesis 2: w1 is slow-wedged (200ms per dispatch)
            http_post(workers[1] + "/api/device-nemesis",
                      json.dumps(
                          {"script": "*:delay:1.0:delay_s=0.2"}).encode())
            # the sick worker's share starts riding the host mirror
            wait_pred(lambda: json.loads(http_get(
                workers[0] + "/api/health"))["compute"]["state"]
                == "sick", timeout=60)
            time.sleep(3.0)

            # nemesis 3: a poison query. Rows with >= 6 distinct terms
            # NaN on w1 and w2; w0 serves from the host mirror (its
            # device is already sick) and never poisons.
            for w in (workers[1], workers[2]):
                http_post(w + "/api/device-nemesis", json.dumps(
                    {"script":
                     "score_ell:poison:1.0:min_uniq=6"}).encode())
            fp = poison_fingerprint(poison_q, "sparse")

            def quarantined():
                st, hd, _b = _post_full(
                    leader, "/leader/start",
                    json.dumps({"query": poison_q}).encode(),
                    timeout=30.0)
                return st == 422 \
                    and hd.get("X-Poison-Quarantined") == fp
            wait_pred(quarantined, timeout=60, interval=0.5)
            snap = json.loads(http_get(leader + "/api/quarantine"))
            (entry,) = [e for e in snap["quarantined"]
                        if e["fingerprint"] == fp]
            # the quarantine engaged on exactly TWO distinct replicas —
            # the third (sick, host-serving) worker never produced a
            # poison verdict, and no further replica ever will: every
            # later send is a front-door 422
            assert len(entry["replicas"]) == 2
            assert set(entry["replicas"]) <= {workers[1], workers[2]}

            time.sleep(3.0)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert not errors, errors[:3]
            assert outcomes["exact"] > 20, outcomes
            # the sick worker's shard kept serving (exact host mirror,
            # honestly stamped) — chaos degraded, never lied
            assert outcomes["compute_degraded"] > 0, outcomes

            # zero acked-write loss THROUGH the chaos: the full-corpus
            # query still returns all 24 names (w0's shard via its
            # mirror, the rest on device)
            st, hd, body = _post_full(
                leader, "/leader/start",
                json.dumps({"query": "common"}).encode(), timeout=30.0)
            assert st == 200 and set(json.loads(body)) == set(docs)

            # recovery: clear every nemesis + the quarantine; the sick
            # device heals via its 0.5s probe, stamps disappear, and
            # replies converge to the exact baseline
            for w in workers:
                http_post(w + "/api/device-nemesis",
                          json.dumps({"clear": True}).encode())
            json.loads(http_post(leader + "/api/quarantine", b"{}"))

            def recovered():
                st, hd, body = _post_full(
                    leader, "/leader/start",
                    json.dumps({"query": "common"}).encode(),
                    timeout=30.0)
                return (st == 200
                        and "X-Compute-Degraded" not in hd
                        and "X-Scatter-Degraded" not in hd
                        and json.loads(body) == baseline["common"])
            wait_pred(recovered, timeout=60, interval=0.5)
            # the poison query is admitted and served again
            st, _, body = _post_full(
                leader, "/leader/start",
                json.dumps({"query": poison_q}).encode(), timeout=30.0)
            assert st == 200 and json.loads(body) == baseline[poison_q]
        finally:
            for p in procs.values():
                try:
                    p.kill()
                except Exception:
                    pass
            for p in procs.values():
                try:
                    p.wait(timeout=10)
                except Exception:
                    pass
