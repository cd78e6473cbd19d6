"""Compute-plane health + host-fallback degraded scoring (ISSUE 20).

Two pieces the engine composes around every dispatch:

* :class:`ComputeHealth` — a per-worker state machine over the device's
  observed behavior: ``healthy -> degraded -> sick`` on consecutive
  classified compute faults (``cluster.resilience.classify_compute_fault``),
  back to healthy on any success.  Sick means "stop hammering the
  device": the engine serves from the host fallback (when available)
  and re-probes the device once per ``compute_probe_interval_s``.
  Poison verdicts NEVER advance the machine — a poisoned output buffer
  is a *query*-shaped problem (the quarantine's job, cluster/quarantine
  .py), and counting it here would let one bad query walk a healthy
  worker into fallback.

* :class:`HostFallbackScorer` — exact scoring on the host CPU, used when
  the device is sick (or a dispatch just failed).  Replies are EXACT,
  not approximate: the scorer is a bit-for-bit numpy mirror of the
  device program, pinned by the parity gate in
  tests/test_compute_chaos.py.  Two tricks make bit-parity possible:

  - The width reduction of the blocked-ELL layout is reproduced with a
    strided 8-lane vector accumulation followed by a halving-tree
    horizontal sum (:func:`_lane_reduce`) — measured bit-equal to the
    XLA reduction where naive ``.sum()``, sequential, and FMA-emulating
    orders all differ by 1 ULP on a few percent of documents.
  - Per-entry COO/residual model weights are query-INDEPENDENT, so they
    are computed once per snapshot by the same XLA elementwise program
    the device scan runs (``_entry_impacts_jit``) and fetched to host.
    numpy's libm (``log1p``/``log``) differs from XLA's by 1 ULP on a
    few percent of inputs, so recomputing idf on host would silently
    break the parity contract.  This one tiny launch is the only device
    work the fallback ever issues, once per snapshot — if even that
    fails, the worker is beyond degraded serving and leader failover is
    the right tool.

Scope: plain :class:`~tfidf_tpu.engine.index.Snapshot` layouts (blocked
ELL + residual, and COO) under the local engine.  Segmented/tiered
snapshots and the dense plane raise :class:`FallbackUnsupported` — their
device programs (streaming current-stats weights, MXU matmuls) have no
practical bit-exact host mirror, and leader failover already covers a
worker that cannot serve them.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from tfidf_tpu.engine.index import Snapshot
from tfidf_tpu.engine.segments import SegmentedSnapshot
from tfidf_tpu.ops.scoring import bm25_weights, tfidf_weights
from tfidf_tpu.utils.logging import get_logger
from tfidf_tpu.utils.metrics import global_metrics

log = get_logger("engine.compute_health")


# ---------------------------------------------------------------------------
# health state machine
# ---------------------------------------------------------------------------

HEALTHY = "healthy"
DEGRADED = "degraded"
SICK = "sick"


class ComputeHealth:
    """Consecutive-fault escalation with timed recovery probes.

    ``note_fault(kind)`` advances healthy -> degraded (after
    ``degraded_after`` consecutive faults) -> sick (after
    ``sick_after``); ``note_success()`` resets to healthy from any
    state.  While sick, :meth:`should_try_device` returns False except
    for ONE probe per ``probe_interval_s`` — the probe request runs the
    real device path; its success heals the machine, its failure re-arms
    the timer.  Poison is ignored by design (see module docstring).
    """

    def __init__(self, *, degraded_after: int = 2, sick_after: int = 5,
                 probe_interval_s: float = 5.0, clock=time.monotonic
                 ) -> None:
        self.degraded_after = max(1, int(degraded_after))
        self.sick_after = max(self.degraded_after, int(sick_after))
        self.probe_interval_s = float(probe_interval_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._state = HEALTHY
        self._consecutive = 0
        self._total = 0
        self._by_kind: dict[str, int] = {}
        self._probe_at = 0.0
        self._probes = 0

    @property
    def state(self) -> str:
        return self._state

    @property
    def consecutive_faults(self) -> int:
        return self._consecutive

    def note_fault(self, kind: str) -> None:
        if kind == "poison":
            return
        with self._lock:
            self._consecutive += 1
            self._total += 1
            self._by_kind[kind] = self._by_kind.get(kind, 0) + 1
            if self._consecutive >= self.sick_after:
                if self._state != SICK:
                    log.warning("compute plane SICK: serving from host "
                                "fallback where available",
                                consecutive=self._consecutive, kind=kind)
                self._state = SICK
                self._probe_at = self._clock() + self.probe_interval_s
            elif self._consecutive >= self.degraded_after:
                self._state = DEGRADED

    def note_success(self) -> None:
        with self._lock:
            if self._state == SICK:
                log.info("compute plane recovered: device probe "
                         "succeeded", faults_survived=self._total)
            self._consecutive = 0
            self._state = HEALTHY

    def should_try_device(self) -> bool:
        """False only while sick and between probes.  Claims (and
        thereby rations) the probe slot: at most one caller per
        interval gets True while sick."""
        with self._lock:
            if self._state != SICK:
                return True
            now = self._clock()
            if now < self._probe_at:
                return False
            self._probe_at = now + self.probe_interval_s
            self._probes += 1
            return True

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "state": self._state,
                "consecutive_faults": self._consecutive,
                "total_faults": self._total,
                "faults_by_kind": dict(self._by_kind),
                "recovery_probes": self._probes,
            }


class FallbackUnsupported(RuntimeError):
    """The host mirror cannot serve this snapshot/op bit-exactly
    (segmented/tiered snapshots, the dense plane, mesh layouts).  The
    engine re-raises the ORIGINAL device fault instead — an honest 500
    the leader routes around — rather than inventing approximate
    results."""


# ---------------------------------------------------------------------------
# bulk d2h stage
# ---------------------------------------------------------------------------

def _fetch_host(arrays):
    """The fallback's one sanctioned bulk d2h: fetch a snapshot's device
    buffers to host numpy, once per snapshot, OFF the per-query path.
    Local numpy import + a devicecheck.BULK_STAGES entry, exactly like
    checkpoint export — a per-query d2h here would be the implicit-sync
    antipattern the device witness exists to catch."""
    import numpy

    return [None if a is None else numpy.asarray(a) for a in arrays]


# ---------------------------------------------------------------------------
# per-entry impacts (query-independent weights), computed by XLA once
# ---------------------------------------------------------------------------

def _entry_impacts(tf, term, doc, doc_len, df, n_docs, avgdl, doc_norms,
                   *, model: str, k1: float, b: float) -> jax.Array:
    """Per-entry model weights for a COO structure — the same elementwise
    formula ``ops.scoring.score_coo_compiled`` computes in-kernel,
    evaluated standalone.  Elementwise f32 ops are deterministic across
    programs, so these values are bit-identical to what the device scan
    sees (pinned by the parity gate)."""
    df_t = df[term]
    if model == "bm25":
        return bm25_weights(tf, df_t, doc_len[doc], n_docs, avgdl,
                            k1=k1, b=b)
    if model == "tfidf":
        return tfidf_weights(tf, df_t, n_docs)
    if model == "tfidf_cosine":
        w = tfidf_weights(tf, df_t, n_docs)
        norm = doc_norms[doc]
        return w / jnp.where(norm > 0, norm, 1.0)
    raise ValueError(f"unknown model {model!r}")


_entry_impacts_jit = jax.jit(
    _entry_impacts, static_argnames=("model", "k1", "b"))


# ---------------------------------------------------------------------------
# host kernels (bit-exact mirrors)
# ---------------------------------------------------------------------------

_LANES = 8   # vector width of the reduction mirror (see module docstring)


def _lane_reduce(x: np.ndarray) -> np.ndarray:
    """Sum f32 ``x [N, W]`` over W via strided 8-lane accumulation +
    halving-tree horizontal sum — the addition ORDER that matches the
    XLA width reduction bit-for-bit (probe-verified; see module
    docstring)."""
    n, w = x.shape
    pad = (-w) % _LANES
    if pad:
        x = np.concatenate([x, np.zeros((n, pad), np.float32)], axis=1)
    lanes = np.zeros((n, _LANES), np.float32)
    for i in range(x.shape[1] // _LANES):
        lanes = lanes + x[:, i * _LANES:(i + 1) * _LANES]
    v = _LANES
    while v > 1:
        v //= 2
        lanes = lanes[:, :v] + lanes[:, v:2 * v]
    return lanes[:, 0]


def _compile_queries_host(qb, vocab_cap: int):
    """Host mirror of ``ops.scoring._compile_queries``: pure integer
    scatter + f32 adds of weights that are exact by construction
    (np.add.at applies updates in index order, the same order the
    device scatter-add uses)."""
    u_cap = int(qb.uniq.shape[0])
    n_u = int(qb.n_uniq)
    B = int(qb.slots.shape[0])
    uniq = np.asarray(qb.uniq)
    slots = np.asarray(qb.slots)
    weights = np.asarray(qb.weights, np.float32)
    slot_of = np.full(vocab_cap, u_cap, np.int32)
    slot_of[uniq[:n_u]] = np.arange(n_u, dtype=np.int32)
    qc_ext = np.zeros((B, u_cap + 1), np.float32)
    rows = np.repeat(np.arange(B), slots.shape[1])
    np.add.at(qc_ext, (rows, slots.reshape(-1)), weights.reshape(-1))
    qc_ext[:, u_cap] = 0.0   # pad column: inert, like the device's
    return slot_of, qc_ext


_ROW_CHUNK = 4096   # bounds the [rows, W, B] temporary, like doc_chunk


def _score_block_host(imp: np.ndarray, term: np.ndarray,
                      slot_of: np.ndarray,
                      qc_ext: np.ndarray) -> np.ndarray:
    """One ELL block as fetched, width-major ``[W, rows]``: gather +
    lane-reduced contraction over its ``[r, W]`` row chunks (the device
    oracle's order, ``ops.ell._score_block``), ``[B, rows]``."""
    B = qc_ext.shape[0]
    w, rows_cap = imp.shape
    qc_t = np.ascontiguousarray(qc_ext.T)               # [U+1, B]
    out = np.empty((B, rows_cap), np.float32)
    for lo in range(0, rows_cap, _ROW_CHUNK):
        imp_c = imp[:, lo:lo + _ROW_CHUNK].T            # [r, W]
        term_c = term[:, lo:lo + _ROW_CHUNK].T
        qg = qc_t[slot_of[term_c]]                      # [r, W, B]
        x = qg * imp_c[:, :, None]
        r = x.shape[0]
        out[:, lo:lo + r] = _lane_reduce(
            x.transpose(0, 2, 1).reshape(r * B, w)).reshape(r, B).T
    return out


def _score_coo_host(w: np.ndarray, term: np.ndarray, doc: np.ndarray,
                    chunk: int, slot_of: np.ndarray, qc_ext: np.ndarray,
                    doc_cap: int) -> np.ndarray:
    """Chunked segment-sum mirror of ``score_coo_compiled`` over
    precomputed entry weights ``w``: same chunk boundaries, same
    per-chunk partial-sum-then-accumulate structure, np.add.at's
    in-order application matching the device scatter."""
    B = qc_ext.shape[0]
    scores = np.zeros((B, doc_cap), np.float32)
    rows = np.arange(B)[:, None]
    for lo in range(0, w.shape[0], chunk):
        w_c = w[lo:lo + chunk]
        term_c = term[lo:lo + chunk]
        doc_c = doc[lo:lo + chunk]
        contrib = qc_ext[:, slot_of[term_c]] * w_c[None, :]   # [B, C]
        part = np.zeros((B, doc_cap), np.float32)
        np.add.at(part,
                  (np.broadcast_to(rows, contrib.shape),
                   np.broadcast_to(doc_c[None, :], contrib.shape)),
                  contrib)
        scores = scores + part
    return scores


def _host_topk(scores: np.ndarray, num_docs: int,
               kk: int) -> tuple[np.ndarray, np.ndarray]:
    """Mirror of ``ops.topk.exact_topk``: pads masked to -inf, stable
    descending sort (ties -> lower doc id, ``lax.top_k`` order)."""
    doc_cap = scores.shape[1]
    masked = np.where(np.arange(doc_cap)[None, :] < num_docs, scores,
                      np.float32(-np.inf)).astype(np.float32)
    order = np.argsort(-masked, axis=1, kind="stable")[:, :kk]
    vals = np.take_along_axis(masked, order, axis=1)
    return vals, order.astype(np.int32)


def _host_full_ranking(scores: np.ndarray,
                       rank_n: int) -> tuple[np.ndarray, np.ndarray]:
    """Mirror of ``ops.topk.full_ranking`` (stable descending argsort)."""
    s = scores[:, :rank_n]
    order = np.argsort(-s, axis=-1, kind="stable")
    return np.take_along_axis(s, order, axis=-1), order.astype(np.int32)


# ---------------------------------------------------------------------------
# snapshot mirror + scorer
# ---------------------------------------------------------------------------

class _SnapshotMirror:
    """Host-resident copy of one committed Snapshot, ready to score."""

    __slots__ = ("snap", "kind", "imps", "terms", "padded_of_real",
                 "res", "coo", "vocab_cap", "doc_cap", "num_docs")

    def __init__(self, snap: Snapshot, skw: dict) -> None:
        self.snap = snap
        model = skw["model"]
        k1 = float(skw.get("k1", 1.2))
        b = float(skw.get("b", 0.75))
        self.vocab_cap = int(snap.df.shape[0])
        self.doc_cap = int(snap.doc_len.shape[0])
        self.num_docs = snap.num_names   # == n_live for local snapshots
        self.res = self.coo = None
        if snap.is_ell:
            self.kind = "ell"
            fetched = _fetch_host(list(snap.ell_impacts)
                                  + list(snap.ell_terms)
                                  + [snap.ell_live])
            nb = len(snap.ell_impacts)
            self.imps = fetched[:nb]
            self.terms = fetched[nb:2 * nb]
            block_live = fetched[2 * nb]
            self.padded_of_real = self._rearrange_index(block_live)
            if snap.res_tf is not None:
                res_cap = int(snap.res_tf.shape[0])
                (w,) = _fetch_host([_entry_impacts_jit(
                    snap.res_tf, snap.res_term, snap.res_doc,
                    snap.doc_len, snap.df, snap.n_docs, snap.avgdl,
                    snap.doc_norms, model=model, k1=k1, b=b)])
                term, doc = _fetch_host([snap.res_term, snap.res_doc])
                # same chunking as score_ell_with_residual's residual pass
                self.res = (w, term, doc, min(1 << 10, res_cap))
        else:
            self.kind = "coo"
            self.imps = self.terms = ()
            self.padded_of_real = None
            nnz_cap = int(snap.tf.shape[0])
            (w,) = _fetch_host([_entry_impacts_jit(
                snap.tf, snap.term, snap.doc, snap.doc_len, snap.df,
                snap.n_docs, snap.avgdl, snap.doc_norms,
                model=model, k1=k1, b=b)])
            term, doc = _fetch_host([snap.term, snap.doc])
            # same chunking as score_coo_impl's default
            self.coo = (w, term, doc, min(1 << 17, nnz_cap))

    def _rearrange_index(self, block_live: np.ndarray) -> np.ndarray:
        """Mirror of ``ops.ell._rearrange_to_real``'s gather index:
        real doc id -> its row in the padded block concat (the trailing
        zero column for rows past the live count)."""
        row0 = np.concatenate([[0], np.cumsum(block_live)])
        total_pad = int(sum(i.shape[1] for i in self.imps))
        real = np.arange(self.doc_cap)
        padded_of_real = np.full(self.doc_cap, total_pad, np.int32)
        pad0 = 0
        for i, imp in enumerate(self.imps):
            in_b = (real >= row0[i]) & (real < row0[i + 1])
            padded_of_real = np.where(
                in_b, pad0 + real - row0[i], padded_of_real)
            pad0 += imp.shape[1]
        return padded_of_real.astype(np.int32)

    def scores(self, qb) -> np.ndarray:
        """``[B, doc_cap]`` f32 — bit-equal to the device scorer."""
        slot_of, qc_ext = _compile_queries_host(qb, self.vocab_cap)
        B = qc_ext.shape[0]
        if self.kind == "ell":
            parts = [_score_block_host(imp, term, slot_of, qc_ext)
                     for imp, term in zip(self.imps, self.terms)]
            padded = np.concatenate(
                parts + [np.zeros((B, 1), np.float32)], axis=1)
            scores = padded[:, self.padded_of_real]
            if self.res is not None:
                w, term, doc, chunk = self.res
                scores = scores + _score_coo_host(
                    w, term, doc, chunk, slot_of, qc_ext, self.doc_cap)
            return np.ascontiguousarray(scores)
        w, term, doc, chunk = self.coo
        return _score_coo_host(w, term, doc, chunk, slot_of, qc_ext,
                               self.doc_cap)


class HostFallbackScorer:
    """Exact host-CPU serving for a sick device — mirrors the local
    :class:`~tfidf_tpu.engine.searcher.Searcher`'s query pipeline
    (same chunking, same vectorizer, same assembly) with numpy kernels
    that are bit-equal to the device programs.  Honest latency: no
    pipelining, no pretending — a degraded reply is slower and says so
    on the wire (``X-Compute-Degraded``)."""

    def __init__(self, searcher) -> None:
        self.searcher = searcher
        self._lock = threading.Lock()
        self._mirror: _SnapshotMirror | None = None

    def _mirror_for(self, snap) -> _SnapshotMirror:
        if isinstance(snap, SegmentedSnapshot):
            raise FallbackUnsupported(
                "segmented/tiered snapshots have no bit-exact host "
                "mirror (streaming current-stats weights) — leader "
                "failover covers this worker")
        if not isinstance(snap, Snapshot):
            raise FallbackUnsupported(
                f"no host mirror for snapshot type "
                f"{type(snap).__name__}")
        with self._lock:
            m = self._mirror
            if m is None or m.snap is not snap:
                m = _SnapshotMirror(snap,
                                    self.searcher.model.score_kwargs())
                self._mirror = m
                global_metrics.inc("compute_fallback_mirror_builds")
            return m

    def _search_chunks(self, queries: list[str], k: int | None,
                       unbounded: bool, finish) -> tuple:
        """``(names, results)``: the searcher's chunks scored on the
        mirror and ranked on the host, each handed to
        ``finish(snap, queries, vals, ids, kk)``, and the snapshot's
        names by id; ``(None, [])`` where there is nothing to search."""
        s = self.searcher
        snap = s.index.snapshot
        if snap is None or not snap.num_names or not queries:
            return None, []
        m = self._mirror_for(snap)
        k = s.top_k if k is None else k
        cap = s._batch_cap(len(queries))
        out: list = []
        for lo in range(0, len(queries), cap):
            chunk = queries[lo:lo + cap]
            qb, _w = s._vectorize(chunk, cap)
            scores = m.scores(qb)
            if unbounded:
                kk = snap.num_names
                vals, ids = _host_full_ranking(scores, kk)
            else:
                kk = min(k, snap.num_names)
                vals, ids = _host_topk(scores, m.num_docs, kk)
            out.extend(finish(snap, chunk, vals, ids, kk))
        return snap.doc_names, out

    def search(self, queries: list[str], k: int | None = None,
               *, unbounded: bool = False) -> list[list]:
        names, out = self._search_chunks(queries, k, unbounded,
                                         self.searcher._assemble)
        return [[] for _ in queries] if names is None else out

    def search_arrays(self, queries: list[str], k: int | None = None):
        s = self.searcher
        names, parts = self._search_chunks(
            queries, k, False,
            lambda snap, chunk, *block: [s._checked(chunk, *block)])
        return s._arrays_reply(parts, len(queries), names)
