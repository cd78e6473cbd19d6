"""Closed-loop SLO autopilot (cluster/autopilot.py).

Controller math is tested in ISOLATION against synthetic sensor feeds
(step / ramp / noise), because a control loop's failure modes —
oscillation, overshoot, runaway — are properties of the math, not of
the cluster around it: hysteresis dead bands, clamp floors/ceilings,
damped steps, direction confirmation, the kill-switch revert, and the
decision-ring bound all get deterministic pins here. The PINNED
DAMPING TEST is the acceptance artifact: under a step-change sensor
feed the applied adjustments never alternate sign within a
constant-target phase (zero oscillation), while still converging to
within the hysteresis band of the target.

Integration tests run a real in-process node: live histogram
observations drive real knob movement, the decision audit is exported
via ``GET /api/autopilot`` and the CLI, a ``tfidf_autopilot_*`` gauge
exists per managed knob, the sweep that changes a knob carries a
``knob_adjusted`` span event, and the runtime kill switch (``POST
/api/autopilot``) restores exact static config.

The slow chaos job (``make chaos-autopilot``) runs a step-change
zipfian closed loop against a real 3-process cluster with a mid-run
worker ``kill -9``: the autopilot converges without oscillation and
admitted-interactive p99 stays bounded.
"""

import json
import threading
import time
import urllib.error

import numpy as np
import pytest

from tfidf_tpu.cluster.admission import AdmissionController
from tfidf_tpu.cluster.autopilot import (Autopilot, CounterWindow,
                                         HedgeController, HistWindow,
                                         LingerController,
                                         SlowTripController,
                                         WatermarkController,
                                         delta_quantile)
from tfidf_tpu.cluster.batcher import Coalescer
from tfidf_tpu.cluster.coordination import (CoordinationCore,
                                            LocalCoordination)
from tfidf_tpu.cluster.node import SearchNode, http_get, http_post
from tfidf_tpu.cluster.resilience import ClusterResilience
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.metrics import BUCKET_BOUNDS_S, global_metrics
from tfidf_tpu.utils.tracing import global_tracer

from tests.test_cluster import wait_until


# ---------------------------------------------------------------------------
# windowed-sensor plumbing
# ---------------------------------------------------------------------------

class TestWindows:
    def test_delta_quantile_oracle_vs_numpy(self):
        """The window-quantile estimate stays within one bucket ratio
        of the true order statistic on uniform and bimodal samples
        (the order statistic, not numpy's default linear
        interpolation: at a bimodal gap the interpolated value lies in
        empty space no sample occupies, which no histogram — or
        serving SLO — should report)."""
        rng = np.random.default_rng(7)
        for samples in (
                rng.uniform(0.001, 0.2, size=2000),
                np.concatenate([rng.normal(0.004, 0.0005, 1000),
                                rng.normal(0.3, 0.02, 1000)]).clip(1e-4)):
            counts = [0] * (len(BUCKET_BOUNDS_S) + 1)
            import bisect
            for s in samples:
                counts[bisect.bisect_left(BUCKET_BOUNDS_S, s)] += 1
            srt = np.sort(samples)
            for q in (0.5, 0.95, 0.99):
                est = delta_quantile(counts, q)
                true = float(srt[int(np.ceil(q * len(srt))) - 1])
                assert est == pytest.approx(true, rel=0.25), (q, est,
                                                              true)

    def test_delta_quantile_empty(self):
        assert delta_quantile([0] * (len(BUCKET_BOUNDS_S) + 1),
                              0.95) is None

    def test_hist_window_returns_only_the_delta(self):
        name = "ap_test_hist_window"
        w = HistWindow(name)
        global_metrics.observe(name, 0.010)
        counts, n = w.advance()
        assert n == 1 and sum(counts) == 1
        # no new samples -> empty window, NOT the cumulative history
        counts, n = w.advance()
        assert n == 0 and sum(counts) == 0
        for _ in range(5):
            global_metrics.observe(name, 0.100)
        counts, n = w.advance()
        assert n == 5 and sum(counts) == 5
        assert delta_quantile(counts, 0.5) == pytest.approx(0.1,
                                                            rel=0.25)

    def test_counter_window(self):
        name = "ap_test_counter_window"
        w = CounterWindow(name)
        global_metrics.inc(name, 3)
        assert w.advance() == 3
        assert w.advance() == 0
        global_metrics.inc(name, 2)
        assert w.advance() == 2


# ---------------------------------------------------------------------------
# controller laws (pure sense() math)
# ---------------------------------------------------------------------------

def _cfg(**kw) -> Config:
    kw.setdefault("autopilot_enabled", True)
    kw.setdefault("autopilot_min_window", 16)
    return Config(**kw)


def _frame(**kw) -> dict:
    f = {"scatter_p95_ms": 0.0, "scatter_n": 0,
         "leader_p99_ms": 0.0, "leader_n": 0,
         "batches": 0.0, "items": 0.0, "sheds": 0.0, "depth": 0.0,
         "max_batch": 128, "worker_ewmas": {}}
    f.update(kw)
    return f


class TestControllerLaws:
    def test_hedge_tracks_p95_plus_epsilon(self):
        c = HedgeController(_cfg(), read=lambda: 0.0,
                            write=lambda v: None)
        assert c.sense(_frame(scatter_p95_ms=80.0, scatter_n=100),
                       0.0)[0] == pytest.approx(90.0)

    def test_hedge_holds_below_min_window(self):
        c = HedgeController(_cfg(), read=lambda: 0.0,
                            write=lambda v: None)
        assert c.sense(_frame(scatter_p95_ms=80.0, scatter_n=3),
                       0.0) is None

    def test_hedge_parks_at_ceiling_under_saturation(self):
        """The Tail-at-Scale caveat: a hedge duplicates load, so while
        queries are queueing (no spare capacity) the controller steers
        the hedge delay to its ceiling instead of the p95 — in-budget
        tail-trimming stops exactly when it would amplify overload.
        Parking is immediate; UNparking is sticky (CALM_SWEEPS
        pressure-free windows), so a flapping saturation edge cannot
        cycle the knob."""
        c = HedgeController(_cfg(), read=lambda: 90.0,
                            write=lambda v: None)
        t, inp = c.sense(_frame(scatter_p95_ms=80.0, scatter_n=100,
                                depth=5.0), 90.0)
        assert t == c.ceiling and inp["parked"] == 1
        # pressure gone: HOLDS through the calm requirement first
        calm = _frame(scatter_p95_ms=80.0, scatter_n=100, depth=0.0)
        for _ in range(HedgeController.CALM_SWEEPS - 1):
            assert c.sense(calm, 90.0) is None
        # sustained calm: back to tracking the tail
        t, _ = c.sense(calm, 90.0)
        assert t == pytest.approx(90.0)
        # one pressure blip re-arms the full calm requirement
        c.sense(_frame(scatter_n=100, depth=2.0), 90.0)
        assert c.sense(calm, 90.0) is None

    def test_watermark_shrinks_over_slo_grows_only_when_shedding(self):
        cfg = _cfg(autopilot_p99_slo_ms=500.0,
                   admission_queue_high_water=100)

        def fresh():
            return WatermarkController(cfg, read=lambda: 100.0,
                                       write=lambda v: None)
        # p99 at 2x the SLO: the tolerated queue halves
        t, _ = fresh().sense(_frame(leader_p99_ms=1000.0,
                                    leader_n=100), 100.0)
        assert t == pytest.approx(50.0)
        # p99 comfortably inside the SLO but sheds happened: grow
        t, _ = fresh().sense(_frame(leader_p99_ms=250.0, leader_n=100,
                                    sheds=5), 100.0)
        assert t == pytest.approx(200.0)
        # in budget, no sheds: nothing to learn
        assert fresh().sense(_frame(leader_p99_ms=250.0,
                                    leader_n=100), 100.0) is None
        # near the SLO (inside the grow guard), even with sheds: hold
        assert fresh().sense(_frame(leader_p99_ms=450.0, leader_n=100,
                                    sheds=5), 100.0) is None

    def test_watermark_peak_hold_blocks_regrow_mid_overload(self):
        """The latency signal is PEAK-HELD over recent windows: under
        zipfian traffic most windows are cache-hit-dominated and calm,
        and one calm window mid-overload must not regrow the watermark
        (re-opening the queue while the tail burns). Growth needs the
        peak itself calm — sustained relief across the hold depth."""
        cfg = _cfg(autopilot_p99_slo_ms=500.0,
                   admission_queue_high_water=100)
        c = WatermarkController(cfg, read=lambda: 100.0,
                                write=lambda v: None)
        t, _ = c.sense(_frame(leader_p99_ms=1000.0, leader_n=100),
                       100.0)
        assert t < 100.0
        # a calm window with sheds right after the bad one: the peak
        # still remembers 1000ms — keep shrinking, never grow
        t, inp = c.sense(_frame(leader_p99_ms=200.0, leader_n=100,
                                sheds=5), 100.0)
        assert inp["peak_p99_ms"] == 1000.0 and t < 100.0
        # after PEAK_WINDOWS calm windows the peak decays: now grow
        for _ in range(WatermarkController.PEAK_WINDOWS):
            out = c.sense(_frame(leader_p99_ms=200.0, leader_n=100,
                                 sheds=5), 100.0)
        t, inp = out
        assert inp["peak_p99_ms"] == 200.0 and t > 100.0

    def test_linger_widens_on_unfilled_pressure_narrows_on_full(self):
        c = LingerController(_cfg(), read=lambda: 8.0,
                             write=lambda v: None)
        # unfilled batches while queries queue: widen
        t, inp = c.sense(_frame(batches=10, items=128, max_batch=64,
                                depth=4.0), 8.0)
        assert t > 8.0 and inp["fill"] == pytest.approx(0.2)
        # unfilled but NO queued pressure: hold (light traffic is not
        # a reason to tax every query's latency ceiling)
        assert c.sense(_frame(batches=10, items=128, max_batch=64,
                              depth=0.0), 8.0) is None
        # batches essentially full: the wait buys nothing, narrow
        t, _ = c.sense(_frame(batches=10, items=608, max_batch=64,
                              depth=4.0), 8.0)
        assert t < 8.0

    def test_slow_trip_needs_two_peers_and_tracks_median(self):
        cfg = _cfg(autopilot_slow_spread_mult=4.0,
                   breaker_slow_min_samples=5)
        c = SlowTripController(cfg, read=lambda: 0.0,
                               write=lambda v: None)
        assert c.sense(_frame(worker_ewmas={"w0": (0.050, 10)}),
                       0.0) is None
        # under-sampled workers are ignored
        assert c.sense(_frame(worker_ewmas={"w0": (0.050, 10),
                                            "w1": (9.0, 2)}),
                       0.0) is None
        t, inp = c.sense(_frame(worker_ewmas={
            "w0": (0.040, 10), "w1": (0.060, 10),
            "w2": (0.050, 10)}), 0.0)
        assert t == pytest.approx(200.0)   # 4 x 50ms median
        assert inp["workers"] == 3


# ---------------------------------------------------------------------------
# the shared discipline: hysteresis / confirmation / damping / clamps
# ---------------------------------------------------------------------------

class _FakeNode:
    """The minimum surface Autopilot needs — real admission controller
    and resilience bundle (the write targets), no HTTP anywhere."""

    def __init__(self, cfg: Config) -> None:
        self.config = cfg
        self.hedge_ms = float(cfg.scatter_hedge_ms)
        self.admission = AdmissionController(cfg, depth_fn=lambda: 0.0)
        self.resilience = ClusterResilience(cfg)
        self.scatter_batcher = None


def _autopilot(**cfg_kw) -> tuple[Autopilot, _FakeNode]:
    cfg = _cfg(**cfg_kw)
    node = _FakeNode(cfg)
    return Autopilot(node), node


def _drive(ap: Autopilot, frames: list[dict]) -> list[list[dict]]:
    """Run one control pass per synthetic frame; returns the applied
    decisions of each pass."""
    feed = iter(frames)
    ap._frame = lambda: next(feed)
    return [ap.run_once() for _ in frames]


def _applied_dirs(ap: Autopilot, knob: str) -> list[int]:
    return [d["direction"] for d in ap.decisions(10_000)
            if d["knob"] == knob and d["applied"]
            and d["reason"] == "adjusted"]


class TestDiscipline:
    def test_hysteresis_dead_band_holds(self):
        ap, node = _autopilot(scatter_hedge_ms=100.0,
                              autopilot_hysteresis=0.15)
        # target 110 is within 15% of current 100: no movement, ever
        _drive(ap, [_frame(scatter_p95_ms=100.0, scatter_n=100)] * 6)
        assert node.hedge_ms == 100.0
        assert all(d["reason"] == "hold:in_band"
                   for d in ap.decisions(100)
                   if d["knob"] == "scatter_hedge_ms")

    def test_direction_confirmation_delays_first_move(self):
        ap, node = _autopilot(scatter_hedge_ms=20.0,
                              autopilot_confirm=2)
        frames = [_frame(scatter_p95_ms=200.0, scatter_n=100)] * 2
        applied = _drive(ap, frames)
        assert applied[0] == []          # sweep 1: confirmation only
        assert len(applied[1]) == 1      # sweep 2: the move lands
        # damped: half of the (210 - 20) error, not the full jump
        assert node.hedge_ms == pytest.approx(115.0)

    def test_damped_convergence_into_band(self):
        ap, node = _autopilot(scatter_hedge_ms=20.0,
                              autopilot_hysteresis=0.15,
                              autopilot_step=0.5)
        _drive(ap, [_frame(scatter_p95_ms=200.0, scatter_n=100)] * 12)
        target = 210.0
        assert abs(target - node.hedge_ms) <= 0.15 * target
        # geometric approach never overshoots the target
        assert node.hedge_ms <= target

    def test_clamps_pin_floor_and_ceiling(self):
        ap, node = _autopilot(scatter_hedge_ms=100.0,
                              autopilot_hedge_floor_ms=50.0,
                              autopilot_hedge_ceiling_ms=300.0)
        _drive(ap, [_frame(scatter_p95_ms=10_000.0,
                           scatter_n=100)] * 20)
        # the knob may NEVER exceed the ceiling, and settles within
        # one hysteresis band of it (the band is relative to current)
        assert 300.0 * 0.85 <= node.hedge_ms <= 300.0
        _drive(ap, [_frame(scatter_p95_ms=0.1, scatter_n=100)] * 20)
        assert 50.0 <= node.hedge_ms <= 50.0 / 0.85

    def test_pinned_damping_no_oscillation_under_step_change(self):
        """THE acceptance pin: a step-change sensor feed (20ms -> 200ms
        -> back to 20ms scatter p95) produces zero sign-alternating
        adjustments within each constant-target phase — the knob walks
        monotonically to each new target and stops inside the
        hysteresis band. Direction changes happen exactly at the two
        genuine target steps, never inside a phase."""
        ap, node = _autopilot(scatter_hedge_ms=25.0,
                              autopilot_hysteresis=0.15,
                              autopilot_step=0.5, autopilot_confirm=2)
        lo = [_frame(scatter_p95_ms=20.0, scatter_n=100)] * 14
        hi = [_frame(scatter_p95_ms=200.0, scatter_n=100)] * 14
        _drive(ap, lo + hi + lo)
        dirs = _applied_dirs(ap, "scatter_hedge_ms")
        assert dirs, "the step change must move the knob"
        flips = sum(1 for a, b in zip(dirs, dirs[1:]) if a != b)
        # two genuine target steps -> at most two direction changes,
        # and NO A/B/A flapping beyond them
        assert flips <= 2, dirs
        # converged back into the band around the low target (the
        # band is relative to the current knob value)
        assert abs(30.0 - node.hedge_ms) <= 0.15 * node.hedge_ms + 0.01

    def test_noise_inside_band_never_moves_the_knob(self):
        ap, node = _autopilot(scatter_hedge_ms=100.0,
                              autopilot_hysteresis=0.15)
        rng = np.random.default_rng(3)
        frames = [_frame(scatter_p95_ms=float(90.0 + rng.uniform(-8, 8)),
                         scatter_n=100) for _ in range(20)]
        _drive(ap, frames)
        assert node.hedge_ms == 100.0

    def test_alternating_noise_beyond_band_blocked_by_confirmation(self):
        """A sensor flapping hard (target far above, then far below,
        every sweep) proposes a new direction each pass — confirmation
        (2 consecutive sweeps) means NOTHING is ever applied: the
        flap cannot reach the knob."""
        ap, node = _autopilot(scatter_hedge_ms=100.0,
                              autopilot_confirm=2)
        frames = []
        for i in range(20):
            p95 = 300.0 if i % 2 == 0 else 20.0
            frames.append(_frame(scatter_p95_ms=p95, scatter_n=100))
        applied = _drive(ap, frames)
        assert all(a == [] for a in applied)
        assert node.hedge_ms == 100.0

    def test_reversal_guard_blocks_marginal_undo(self):
        """After an applied adjustment, undoing it demands an error
        beyond TWICE the hysteresis band: noise that barely clears the
        band cannot walk the knob back, while a genuine step (error >>
        band) reverses after the usual confirmation."""
        ap, node = _autopilot(scatter_hedge_ms=20.0,
                              autopilot_hysteresis=0.15)
        # walk the knob up and let it settle near 210
        _drive(ap, [_frame(scatter_p95_ms=200.0, scatter_n=100)] * 10)
        settled = node.hedge_ms
        assert settled > 150.0
        # a marginal pull-down: ~25% below current clears the band
        # (15%) but not the reversal guard (30%) — never applied
        marginal = settled * 0.75 - 10.0   # target = p95 + 10
        _drive(ap, [_frame(scatter_p95_ms=marginal,
                           scatter_n=100)] * 6)
        assert node.hedge_ms == settled
        assert any(d["reason"] == "hold:reversal_guard"
                   for d in ap.decisions(200))
        # a genuine collapse reverses (error >> 2x band)
        _drive(ap, [_frame(scatter_p95_ms=20.0, scatter_n=100)] * 10)
        assert node.hedge_ms < settled

    def test_raw_agreement_gates_smoothed_drift(self):
        """Target smoothing must not let an alternating sensor sneak
        its MEAN past confirmation: each confirming sweep's raw sample
        must itself point beyond the band in the same direction."""
        ap, _node = _autopilot(scatter_hedge_ms=100.0)
        frames = []
        for i in range(12):
            p95 = 290.0 if i % 2 == 0 else 10.0   # mean well above
            frames.append(_frame(scatter_p95_ms=p95, scatter_n=100))
        applied = _drive(ap, frames)
        assert all(a == [] for a in applied)
        assert any(d["reason"] == "hold:noisy"
                   for d in ap.decisions(200))

    def test_ramp_tracks_monotonically(self):
        ap, node = _autopilot(scatter_hedge_ms=20.0)
        frames = [_frame(scatter_p95_ms=30.0 + 12.0 * i, scatter_n=100)
                  for i in range(16)]
        _drive(ap, frames)
        dirs = _applied_dirs(ap, "scatter_hedge_ms")
        assert dirs and all(d == 1 for d in dirs)
        assert node.hedge_ms > 20.0

    def test_watermark_integer_and_critical_ratio_preserved(self):
        ap, node = _autopilot(admission_queue_high_water=100,
                              admission_queue_critical=400,
                              autopilot_p99_slo_ms=500.0)
        _drive(ap, [_frame(leader_p99_ms=2000.0, leader_n=100)] * 8)
        hw = node.admission.high_water
        assert isinstance(hw, int) and hw < 100
        assert node.admission.critical == max(hw * 4, hw + 1)

    def test_integral_knob_never_deadlocks_on_quantization(self):
        """The minimum-step rule: an integer knob whose damped
        fractional step rounds back onto itself (high_water 4, shrink
        ratio 0.83 -> 3.67 -> rounds to 4) must still move one unit
        toward the target — otherwise the controller silently loses
        authority exactly at small watermarks, where interactive
        shedding is decided."""
        ap, node = _autopilot(admission_queue_high_water=4,
                              admission_queue_critical=16,
                              autopilot_queue_floor=2,
                              autopilot_p99_slo_ms=500.0)
        # peak p99 at 600ms: ratio 0.83 — fractional step would stall
        _drive(ap, [_frame(leader_p99_ms=600.0, leader_n=100)] * 6)
        assert node.admission.high_water == 2   # walked 4 -> 3 -> 2
        assert node.admission.critical == 8

    def test_no_signal_decisions_not_recorded(self):
        ap, _node = _autopilot()
        _drive(ap, [_frame()] * 5)   # idle cluster: nothing to decide
        assert [d for d in ap.decisions(100)
                if d["reason"].startswith("hold:confirm")] == []
        assert all(d["reason"] == "bootstrap:arm_ewma_collection"
                   for d in ap.decisions(100))


# ---------------------------------------------------------------------------
# kill switch + decision ring
# ---------------------------------------------------------------------------

class TestKillSwitchAndRing:
    def test_kill_switch_reverts_every_knob_to_static(self):
        ap, node = _autopilot(scatter_hedge_ms=30.0,
                              admission_queue_high_water=128,
                              admission_queue_critical=512,
                              breaker_slow_threshold_ms=0.0)
        # bootstrap armed EWMA collection (slow threshold = ceiling)
        assert node.resilience.slow_threshold_s > 0
        # move every knob off its static value
        _drive(ap, [_frame(scatter_p95_ms=500.0, scatter_n=100,
                           leader_p99_ms=3000.0, leader_n=100,
                           worker_ewmas={"w0": (0.040, 10),
                                         "w1": (0.060, 10)})] * 6)
        assert node.hedge_ms != 30.0
        assert node.admission.high_water != 128
        snap = ap.set_enabled(False)
        # EXACT static config, instantly, for every managed knob
        assert node.hedge_ms == 30.0
        assert node.admission.high_water == 128
        assert node.admission.critical == 512
        assert node.resilience.slow_threshold_s == 0.0
        assert snap["enabled"] is False
        for k, v in snap["knobs"].items():
            assert v["current"] == v["static"], k
        # the loop is OFF: run_once is a no-op
        ap._frame = lambda: _frame(scatter_p95_ms=500.0, scatter_n=100)
        assert ap.run_once() == []
        assert node.hedge_ms == 30.0
        # the reverts are audited
        reverts = [d for d in ap.decisions(100)
                   if d["reason"] == "revert:kill_switch"]
        assert {d["knob"] for d in reverts} >= {
            "scatter_hedge_ms", "admission_queue_high_water",
            "breaker_slow_threshold_ms"}

    def test_kill_switch_restores_critical_exactly_despite_ratio(self):
        """The critical watermark is re-derived through a float ratio
        while steering, but the kill switch must restore BOTH static
        values verbatim — int(c/h*h) truncation (7/61 -> 60) must
        never survive a revert."""
        ap, node = _autopilot(admission_queue_high_water=7,
                              admission_queue_critical=61,
                              autopilot_p99_slo_ms=500.0,
                              autopilot_queue_floor=2)
        _drive(ap, [_frame(leader_p99_ms=2000.0, leader_n=100)] * 6)
        assert node.admission.high_water != 7
        ap.set_enabled(False)
        assert node.admission.high_water == 7
        assert node.admission.critical == 61

    def test_no_signal_sweep_breaks_confirmation_streak(self):
        """'autopilot_confirm CONSECUTIVE sweeps' means consecutive: a
        proposal from before a traffic gap (no-signal windows) must
        not combine with one fresh noisy window into a move."""
        ap, node = _autopilot(scatter_hedge_ms=20.0,
                              autopilot_confirm=2)
        applied = _drive(ap, [
            _frame(scatter_p95_ms=200.0, scatter_n=100),  # confirm 1
            _frame(scatter_n=0),                          # traffic gap
            _frame(scatter_p95_ms=200.0, scatter_n=100),  # confirm 1!
        ])
        assert applied == [[], [], []]
        assert node.hedge_ms == 20.0

    def test_reenable_restarts_from_static_with_fresh_windows(self):
        ap, node = _autopilot(scatter_hedge_ms=30.0)
        _drive(ap, [_frame(scatter_p95_ms=500.0, scatter_n=100)] * 4)
        ap.set_enabled(False)
        ap.set_enabled(True)
        assert ap.enabled and node.hedge_ms == 30.0
        # no stale trend: the first post-enable sweep must re-confirm
        ap._frame = lambda: _frame(scatter_p95_ms=500.0, scatter_n=100)
        assert ap.run_once() == []   # confirmation sweep, no move yet

    def test_reenable_clears_peak_hold_and_calm_state(self):
        """Subclass sensor memory must not survive a disable/enable
        cycle: a 900ms peak from the pre-disable overload would make
        the first post-enable calm window propose shrinking the
        watermark on a healthy cluster; a pre-disable pressure window
        would keep the hedge park-stuck through the calm gate."""
        ap, _node = _autopilot(admission_queue_high_water=100,
                               admission_queue_critical=400,
                               autopilot_p99_slo_ms=500.0)
        wm = next(c for c in ap.controllers
                  if c.knob == "admission_queue_high_water")
        hg = next(c for c in ap.controllers
                  if c.knob == "scatter_hedge_ms")
        _drive(ap, [_frame(leader_p99_ms=900.0, leader_n=100,
                           depth=3.0, scatter_n=100)] * 2)
        assert len(wm._recent_p99) > 0 and hg._calm == 0
        ap.set_enabled(False)
        ap.set_enabled(True)
        assert len(wm._recent_p99) == 0
        assert hg._calm == hg.CALM_SWEEPS
        # first post-enable calm window: peak is THIS window only —
        # p99 at 200ms proposes no shrink from the stale 900ms era
        out = wm.sense(_frame(leader_p99_ms=200.0, leader_n=100),
                       100.0)
        assert out is None   # in budget, no sheds: nothing to learn

    def test_decision_ring_is_bounded(self):
        ap, _node = _autopilot(autopilot_ring=16)
        for i in range(100):
            ap._record(knob="k", current=0, target=1, new=None,
                       direction=0, applied=False, reason="hold:test",
                       inputs={})
        recs = ap.decisions(10_000)
        assert len(recs) == 16
        # the ring keeps the NEWEST records
        assert recs[-1]["seq"] > 100 - 16
        assert ap.decisions(4) == recs[-4:]
        assert ap.decisions(0) == []


# ---------------------------------------------------------------------------
# integration: a real node, live sensors, HTTP export, CLI, gauges
# ---------------------------------------------------------------------------

@pytest.fixture
def core():
    c = CoordinationCore(session_timeout_s=0.5)
    yield c
    c.close()


_NODE_CFG = dict(
    top_k=16, min_doc_capacity=64, min_nnz_capacity=1 << 12,
    min_vocab_capacity=1 << 10, query_batch=8, max_query_terms=8,
    rpc_max_attempts=1, reconcile_sweep_interval_s=0.2,
    autopilot_enabled=True, autopilot_min_window=8,
    autopilot_interval_ms=50.0)


def _mk_node(core, tmp_path, **kw):
    cfg_kw = dict(_NODE_CFG)
    cfg_kw.update(kw)
    cfg = Config(documents_path=str(tmp_path / "ap" / "documents"),
                 index_path=str(tmp_path / "ap" / "index"),
                 port=0, **cfg_kw)
    return SearchNode(cfg, coord=LocalCoordination(core, 0.1)).start()


class TestNodeIntegration:
    def test_live_histograms_drive_hedge_with_span_and_gauges(
            self, core, tmp_path):
        node = _mk_node(core, tmp_path, scatter_hedge_ms=0.0)
        try:
            ap = node.autopilot
            # feed the REAL sensor pipeline: scatter-leg latencies into
            # the global histogram, one window per control pass
            for _ in range(3):
                for _ in range(40):
                    global_metrics.observe("scatter_rpc", 0.050)
                ap.run_once()
            assert node.hedge_ms > 0.0, \
                "hedge must track the observed scatter p95"
            # within the band of p95 + epsilon (~60ms) after 3 passes,
            # or at least moving toward it
            assert 5.0 <= node.hedge_ms <= 2000.0
            # tfidf_autopilot_* gauge per managed knob
            prom = global_metrics.render_prometheus()
            assert "tfidf_autopilot_scatter_hedge_ms " in prom
            assert "tfidf_autopilot_scatter_hedge_ms_floor " in prom
            assert "tfidf_autopilot_scatter_hedge_ms_ceiling " in prom
            assert "tfidf_autopilot_scatter_hedge_ms_direction " in prom
            assert "tfidf_autopilot_active " in prom
            # the sweep that changed a knob is traced with one
            # knob_adjusted event per change
            spans = [s for s in global_tracer.recent(200)
                     if s["name"] == "autopilot.sweep"]
            assert spans
            events = [e for s in spans for e in s["events"]
                      if e["name"] == "knob_adjusted"]
            assert any(e["attrs"]["knob"] == "scatter_hedge_ms"
                       and "scatter_p95_ms" in e["attrs"]
                       for e in events)
        finally:
            node.stop()

    def test_api_autopilot_get_and_post_kill_switch(self, core,
                                                    tmp_path):
        node = _mk_node(core, tmp_path, scatter_hedge_ms=40.0)
        try:
            ap = node.autopilot
            for _ in range(3):
                for _ in range(40):
                    global_metrics.observe("scatter_rpc", 0.200)
                ap.run_once()
            assert node.hedge_ms != 40.0
            got = json.loads(http_get(node.url
                                      + "/api/autopilot?recent=5"))
            snap = got["autopilot"]
            assert snap["enabled"] is True
            assert "scatter_hedge_ms" in snap["knobs"]
            k = snap["knobs"]["scatter_hedge_ms"]
            assert k["static"] == 40.0 and k["current"] != 40.0
            assert k["adjustments"] >= 1
            assert 0 < len(got["decisions"]) <= 5
            d = got["decisions"][-1]
            assert {"seq", "ts", "knob", "reason",
                    "inputs"} <= set(d)
            # the runtime kill switch over HTTP
            resp = json.loads(http_post(
                node.url + "/api/autopilot",
                json.dumps({"enabled": False}).encode()))
            assert resp["autopilot"]["enabled"] is False
            assert node.hedge_ms == 40.0
            # malformed body is a 400, not a toggle
            with pytest.raises(urllib.error.HTTPError) as ei:
                http_post(node.url + "/api/autopilot",
                          json.dumps({"enabled": "yes"}).encode())
            assert ei.value.code == 400
        finally:
            node.stop()

    def test_cli_status_block_and_autopilot_subcommand(self, core,
                                                      tmp_path,
                                                      capsys):
        from tfidf_tpu.cli import main as cli_main
        node = _mk_node(core, tmp_path)
        try:
            for _ in range(3):
                for _ in range(40):
                    global_metrics.observe("scatter_rpc", 0.100)
                node.autopilot.run_once()
            assert cli_main(["status", "--leader", node.url]) == 0
            out = json.loads(capsys.readouterr().out)
            assert out["autopilot"]["enabled"] is True
            assert "scatter_hedge_ms" in out["autopilot"]["knobs"]
            kb = out["autopilot"]["knobs"]["scatter_hedge_ms"]
            assert {"current", "static", "adjustments"} <= set(kb)
            assert out["autopilot"]["last_decision_age_s"] is not None
            # the dedicated subcommand renders the audit trail
            assert cli_main(["autopilot", "--leader", node.url]) == 0
            txt = capsys.readouterr().out
            assert "autopilot ENABLED" in txt
            assert "scatter_hedge_ms" in txt
            assert "decision(s):" in txt
            # kill switch via the CLI
            assert cli_main(["autopilot", "--leader", node.url,
                             "--disable"]) == 0
            txt = capsys.readouterr().out
            assert "autopilot disabled" in txt
            assert node.autopilot.enabled is False
        finally:
            node.stop()

    def test_static_config_when_disabled(self, core, tmp_path):
        """autopilot_enabled=False (the default) = exact legacy
        behavior: no knob ever moves, no sweep ever runs."""
        node = _mk_node(core, tmp_path, autopilot_enabled=False,
                        scatter_hedge_ms=70.0,
                        breaker_slow_threshold_ms=0.0)
        try:
            for _ in range(40):
                global_metrics.observe("scatter_rpc", 0.300)
            node.autopilot.maybe_run()
            assert node.autopilot.run_once() == []
            assert node.hedge_ms == 70.0
            assert node.resilience.slow_threshold_s == 0.0
            assert global_metrics.get("autopilot_active") == 0.0
        finally:
            node.stop()


# ---------------------------------------------------------------------------
# chaos (slow): step-change zipfian closed loop + mid-run worker kill -9
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestChaosAutopilot:
    @pytest.mark.timeout(300)
    def test_step_change_converges_without_oscillation(self, tmp_path):
        """``make chaos-autopilot``: a real 3-process cluster under the
        zipfian closed loop, load stepped 1x -> 2x with a worker
        ``kill -9`` mid-2x. The autopilot (enabled, fast cadence) must
        make adjustments, never flap (at most one direction change per
        knob beyond the genuine load step), keep admitted-interactive
        p99 bounded, and revert exactly to static config on the kill
        switch."""
        import os
        import random as _random
        import signal
        import socket
        import subprocess
        import sys

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        env = os.environ.copy()
        env["TFIDF_JAX_PLATFORM"] = "cpu"
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env.update({
            "TFIDF_REPLICATION_FACTOR": "2",
            "TFIDF_TOP_K": "64",
            "TFIDF_SESSION_TIMEOUT_S": "1.0",
            "TFIDF_HEARTBEAT_INTERVAL_S": "0.2",
            "TFIDF_RECONCILE_SWEEP_INTERVAL_S": "0.25",
            "TFIDF_MIN_DOC_CAPACITY": "64",
            "TFIDF_MIN_NNZ_CAPACITY": "4096",
            "TFIDF_MIN_VOCAB_CAPACITY": "1024",
            "TFIDF_QUERY_BATCH": "4",
            "TFIDF_MAX_QUERY_TERMS": "8",
            # overload mechanics (as in chaos-overload): small scatter
            # batches leave a queue behind, LOW starting watermarks the
            # controller may rescale
            "TFIDF_SCATTER_BATCH": "2",
            "TFIDF_SCATTER_PIPELINE": "1",
            "TFIDF_ADMISSION_QUEUE_HIGH_WATER": "2",
            "TFIDF_ADMISSION_QUEUE_CRITICAL": "8",
            "TFIDF_RESULT_CACHE_ENTRIES": "256",
            # the autopilot under test: fast cadence, small windows
            "TFIDF_AUTOPILOT_ENABLED": "true",
            "TFIDF_AUTOPILOT_INTERVAL_MS": "500",
            "TFIDF_AUTOPILOT_MIN_WINDOW": "8",
            "TFIDF_AUTOPILOT_P99_SLO_MS": "400",
        })
        coord_port = free_port()
        procs = {}

        def spawn(tag, args):
            p = subprocess.Popen(
                [sys.executable, "-m", "tfidf_tpu", *args],
                env=env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
            procs[tag] = p
            return p

        def wait_pred(pred, timeout=60.0, interval=0.2):
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

        docs = {f"ap{i}.txt": f"common token{i} word{i % 3} "
                              f"extra{i % 5}" for i in range(12)}
        try:
            spawn("coord", ["coordinator", "--listen",
                            f"127.0.0.1:{coord_port}"])
            wait_pred(lambda: socket.create_connection(
                ("127.0.0.1", coord_port), timeout=1.0).close()
                or True)
            ports = [free_port() for _ in range(3)]
            urls = [f"http://127.0.0.1:{p}" for p in ports]
            for i, p in enumerate(ports):
                spawn(f"n{i}", [
                    "serve", "--port", str(p), "--host", "127.0.0.1",
                    "--coordinator-address",
                    f"127.0.0.1:{coord_port}",
                    "--documents-path",
                    str(tmp_path / f"ap{i}" / "docs"),
                    "--index-path",
                    str(tmp_path / f"ap{i}" / "index")])
                wait_pred(lambda u=urls[i]: http_get(
                    u + "/api/status", timeout=5.0), timeout=120)
            leader = urls[0]
            wait_pred(lambda: len(json.loads(http_get(
                leader + "/api/services"))) == 2)
            http_post(leader + "/leader/upload-batch",
                      json.dumps([{"name": n, "text": t}
                                  for n, t in docs.items()]).encode())
            wait_pred(lambda: json.loads(http_post(
                leader + "/leader/start",
                json.dumps({"query": "common"}).encode())),
                timeout=120, interval=1.0)

            qpool = [f"token{i} word{j}" for i in range(12)
                     for j in range(3)] + ["common"]
            rng = _random.Random(11)
            weights = [1.0 / (i + 1) ** 1.1 for i in range(len(qpool))]
            zipf = rng.choices(qpool, weights=weights, k=4000)
            nonce = [0]
            idx = [0]
            lock = threading.Lock()

            def run_phase(n_clients, seconds, mid_phase=None):
                lats, sheds, errors = [], [0], []
                stop_at = time.monotonic() + seconds

                def client(cid):
                    while time.monotonic() < stop_at:
                        with lock:
                            q = zipf[idx[0] % len(zipf)]
                            idx[0] += 1
                            if idx[0] % 5 < 2:
                                nonce[0] += 1
                                q = f"{q} zzuniq{nonce[0]}"
                        t0 = time.monotonic()
                        try:
                            http_post(
                                leader + "/leader/start",
                                json.dumps({"query": q}).encode(),
                                headers={"X-Client-Id": f"c{cid}"},
                                timeout=30.0)
                            with lock:
                                lats.append(time.monotonic() - t0)
                        except urllib.error.HTTPError as e:
                            if e.code == 429:
                                with lock:
                                    sheds[0] += 1
                                time.sleep(min(float(e.headers.get(
                                    "Retry-After", 0.05)), 0.5))
                            else:
                                errors.append(e)
                                return
                        except Exception as e:
                            errors.append(e)
                            return

                threads = [threading.Thread(target=client, args=(i,),
                                            daemon=True)
                           for i in range(n_clients)]
                for t in threads:
                    t.start()
                if mid_phase is not None:
                    time.sleep(seconds / 2)
                    mid_phase()
                for t in threads:
                    t.join(timeout=seconds + 60)
                assert not errors, errors[:3]
                lats.sort()
                return {"n": len(lats), "sheds": sheds[0],
                        "p99": lats[int(len(lats) * 0.99)]
                        if lats else 0.0}

            one_x = run_phase(4, 10.0)
            assert one_x["n"] > 0

            def kill_worker():
                victim = procs.pop("n2")
                os.kill(victim.pid, signal.SIGKILL)
                victim.wait(timeout=10)

            two_x = run_phase(12, 16.0, mid_phase=kill_worker)
            assert two_x["n"] > 0
            # admitted-interactive p99 stays bounded through the step
            # change AND the kill (CI-generous 4x; the quiet-hardware
            # bar is 1.5x)
            assert two_x["p99"] <= max(4.0 * one_x["p99"], 2.0), \
                (one_x, two_x)

            got = json.loads(http_get(
                leader + "/api/autopilot?recent=256"))
            snap = got["autopilot"]
            assert snap["enabled"] is True
            # the loop actually steered something under the step change
            total_adjust = sum(v["adjustments"]
                               for v in snap["knobs"].values())
            assert total_adjust >= 1, snap
            # convergence without oscillation: per knob, applied
            # adjustments may change direction only at genuine
            # load-state transitions — the 1x->2x step, the post-kill
            # settle, and (for the hedge) a park/unpark mode switch
            # at a saturation boundary. A/B/A/B flapping would rack
            # up far more than this bound.
            by_knob = {}
            for d in got["decisions"]:
                if d.get("applied") and d["reason"] == "adjusted":
                    by_knob.setdefault(d["knob"], []).append(
                        d["direction"])
            for knob, dirs in by_knob.items():
                flips = sum(1 for a, b in zip(dirs, dirs[1:])
                            if a != b)
                assert flips <= 3, (knob, dirs)
            # every knob inside its clamps
            for k, v in snap["knobs"].items():
                assert v["floor"] <= v["current"] <= v["ceiling"], (
                    k, v)
            # kill switch restores exact static config, live
            resp = json.loads(http_post(
                leader + "/api/autopilot",
                json.dumps({"enabled": False}).encode()))
            for k, v in resp["autopilot"]["knobs"].items():
                assert v["current"] == v["static"], (k, v)
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
