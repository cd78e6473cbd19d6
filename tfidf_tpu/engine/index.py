"""ShardIndex — one worker's index, with commit/snapshot semantics.

The TPU-native replacement for the reference worker's Lucene index
(``worker/Worker.java:54-94``):

* ``add_document`` is an idempotent upsert keyed on document name, like
  ``indexWriter.updateDocument(new Term("path", rel), doc)``
  (``Worker.java:214-219``): re-adding a name tombstones the old entry.
* ``commit()`` publishes an immutable device-resident :class:`Snapshot`;
  searches always run against the last committed snapshot, reproducing
  Lucene's "fresh DirectoryReader sees the last commit, never a torn index"
  behavior (``Worker.java:223``, SURVEY.md §5.2) without any locking on the
  read path.
* ``size_bytes`` is the shard's load metric — the analog of
  ``GET /worker/index-size`` (``Worker.java:147-172``) that drives
  least-loaded upload placement.

Per-document postings are kept host-side as compact numpy pairs (term ids,
frequencies) — the source of truth from which device arrays are rebuilt, so
a lost device snapshot is always recoverable (recovery-by-rebuild,
``Worker.java:77-88``).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from tfidf_tpu.models.base import ScoringModel
from tfidf_tpu.ops.csr import CooShard, next_capacity
from tfidf_tpu.ops.ell import (build_ell_from_coo, cosine_norms_host,
                               ell_impacts, ell_layout_gauges)
from tfidf_tpu.ops.scoring import cosine_norms
from tfidf_tpu.utils.logging import get_logger
from tfidf_tpu.utils.metrics import global_metrics
from tfidf_tpu.utils.tracing import trace_phase

log = get_logger("engine.index")


@dataclass
class DocEntry:
    name: str
    term_ids: np.ndarray   # i32 [k], sorted
    tfs: np.ndarray        # f32 [k]
    length: float          # analyzed token count (pre-quantization)
    live: bool = True


def check_sorted_unique_ids(name: str, ids: np.ndarray) -> None:
    """Enforce the ``add_document_arrays`` contract — term ids strictly
    ascending (sorted AND distinct) — at the ingest seam, where it is
    one vectorized diff per document. Everything downstream assumes it:
    the ELL layouts store one posting per distinct term, and the v4
    A-build's select chain keeps AT MOST ONE match per row, so a
    duplicated id that slipped in here would score differently on the
    kernel vs the XLA path (silently, per block). The analyzer, native
    tokenizer, and dict ingest all produce conforming arrays; this
    catches the raw-array caller that does not."""
    if ids.shape[0] > 1 and not (np.diff(ids) > 0).all():
        raise ValueError(
            f"add_document_arrays({name!r}): term ids must be strictly "
            "ascending (sorted, distinct) — merge duplicate ids into "
            "one entry with the summed tf")


def entries_from_packed(names: list[str], offsets: np.ndarray,
                        term_ids: np.ndarray, tfs: np.ndarray,
                        lengths: np.ndarray):
    """Doc-table construction from packed CSR-style checkpoint arrays
    with per-doc numpy VIEWS (no copies, no per-document ingest work) —
    shared by every index kind's bulk-restore path. Coerces dtypes once
    and returns ``(entries, (offsets, term_ids, tfs, lengths))`` with
    the coerced arrays (the entries are views into THESE)."""
    offsets = np.ascontiguousarray(offsets, np.int64)
    term_ids = np.ascontiguousarray(term_ids, np.int32)
    tfs = np.ascontiguousarray(tfs, np.float32)
    lengths = np.ascontiguousarray(lengths, np.float32)
    lo = offsets[:-1].tolist()
    hi = offsets[1:].tolist()
    lens = lengths.tolist()
    entries = [DocEntry(name=names[i], term_ids=term_ids[lo[i]:hi[i]],
                        tfs=tfs[lo[i]:hi[i]], length=lens[i])
               for i in range(len(names))]
    return entries, (offsets, term_ids, tfs, lengths)


@dataclass
class Snapshot:
    """Immutable device-resident index state — what queries score against.

    Two layouts: COO (``tf``/``term``/``doc`` device arrays, scatter
    scoring) or blocked ELL (``ell_*`` block tuples + COO residual, gather
    scoring — the TPU fast path; the COO fields stay None and never ship
    to device).
    """

    tf: jax.Array | None   # f32 [nnz_cap] (None in ELL layout)
    term: jax.Array | None # i32 [nnz_cap]
    doc: jax.Array | None  # i32 [nnz_cap]
    doc_len: jax.Array     # f32 [doc_cap] (model-transformed, e.g. quantized)
    df: jax.Array          # f32 [vocab_cap]
    doc_norms: jax.Array   # f32 [doc_cap] (zeros unless cosine model)
    n_docs: jax.Array      # f32 scalar
    avgdl: jax.Array       # f32 scalar (from raw lengths, like Lucene)
    num_docs: jax.Array    # i32 scalar (for top-k masking)
    doc_names: list[str] = field(default_factory=list)
    version: int = 0
    nnz: int = 0
    host_coo: CooShard | None = None   # host copy for mesh re-sharding
    # Blocked-ELL fast path (tfidf_tpu.ops.ell): per-commit precomputed
    # impact blocks + term rows, plus a COO residual for overlong docs.
    # width-major, as the kernel reads a block (``ops/ell.py EllBlock``)
    ell_impacts: tuple = ()       # tuple of f32 [width_i, rows_cap_i]
    ell_terms: tuple = ()         # tuple of i32 [width_i, rows_cap_i]
    # live rows per block — TRACED so commits within the same capacity
    # buckets never retrace the query path
    ell_live: jax.Array | None = None     # i32 [n_blocks]
    # the same counts as host integers (both set by _ell_live_fields):
    # the per-dispatch top-k chunk counters read these, never the device
    ell_live_host: tuple = ()
    res_tf: jax.Array | None = None       # f32 [res_cap] (None: no spill)
    res_term: jax.Array | None = None     # i32 [res_cap]
    res_doc: jax.Array | None = None      # i32 [res_cap]
    res_nnz: int = 0                      # live entries of the residual

    @property
    def is_ell(self) -> bool:
        return bool(self.ell_impacts) or self.tf is None

    @property
    def num_names(self) -> int:
        return len(self.doc_names)

    def size_bytes(self) -> int:
        arrays = [self.tf, self.term, self.doc, self.doc_len, self.df,
                  self.res_tf, self.res_term, self.res_doc,
                  *self.ell_impacts, *self.ell_terms]
        return int(sum(a.nbytes for a in arrays if a is not None))


jax.tree_util.register_dataclass(
    Snapshot,
    data_fields=["tf", "term", "doc", "doc_len", "df", "doc_norms",
                 "n_docs", "avgdl", "num_docs", "ell_impacts", "ell_terms",
                 "ell_live", "res_tf", "res_term", "res_doc"],
    meta_fields=["doc_names", "version", "nnz", "host_coo",
                 "ell_live_host", "res_nnz"],
)


def _ell_live_fields(live) -> dict:
    """A snapshot's per-block live row counts, device and host copy."""
    live = np.asarray(live, np.int32)
    return dict(ell_live=jnp.asarray(live),
                ell_live_host=tuple(int(n) for n in live))


def _publish_ell_gauges(shapes, live, res_doc: np.ndarray) -> None:
    """The ``ell_*`` gauges of a snapshot going live."""
    for name, value in ell_layout_gauges(shapes, live, res_doc).items():
        global_metrics.set_gauge(name, value)


class ShardIndex:
    def __init__(self, model: ScoringModel,
                 min_nnz_cap: int = 1 << 16,
                 min_doc_cap: int = 1024,
                 keep_host_coo: bool = False,
                 layout: str = "ell",
                 ell_width_cap: int | None = None) -> None:
        self.model = model
        self.min_nnz_cap = min_nnz_cap
        self.min_doc_cap = min_doc_cap
        self.keep_host_coo = keep_host_coo
        self.layout = layout          # "ell" (gather/MXU path) | "coo"
        self.ell_width_cap = ell_width_cap
        self._docs: list[DocEntry] = []
        self._by_name: dict[str, int] = {}
        self._tombstones = 0
        # packed postings from a bulk load (checkpoint restore): while no
        # mutation has landed since, to_coo() builds the COO with pure
        # vectorized numpy instead of concatenating per-doc arrays
        self._packed: tuple | None = None
        self._packed_gen = -1
        self._write_lock = threading.Lock()   # single-writer, lock-free reads
        # generation counter: bumped on every mutation; commit() compares
        # generations instead of clearing a dirty flag, so a write that lands
        # while a snapshot is being built is never lost.
        self._gen = 1
        self._committed_gen = 0
        self.snapshot: Snapshot | None = None
        self._version = 0

    # ---- write path (mirrors Worker.upload -> addDocToIndex) ----

    def add_document(self, name: str, id_counts: dict[int, int],
                     length: float | None = None) -> None:
        """Upsert by name. ``id_counts`` is the analyzed, vocab-mapped TF map."""
        if id_counts:
            items = sorted(id_counts.items())
            ids = np.fromiter((t for t, _ in items), np.int32, len(items))
            tfs = np.fromiter((f for _, f in items), np.float32, len(items))
        else:
            ids = np.empty(0, np.int32)
            tfs = np.empty(0, np.float32)
        self.add_document_arrays(name, ids, tfs, length)

    def add_document_arrays(self, name: str, ids: np.ndarray,
                            tfs: np.ndarray,
                            length: float | None = None) -> None:
        """Upsert from pre-sorted id/tf arrays (the native ingest path
        produces these directly — no dict round-trip)."""
        ids = np.asarray(ids, np.int32)
        check_sorted_unique_ids(name, ids)
        entry = DocEntry(
            name=name, term_ids=ids,
            tfs=np.asarray(tfs, np.float32),
            length=float(length if length is not None else tfs.sum()))
        with self._write_lock:
            old = self._by_name.get(name)
            if old is not None and self._docs[old].live:
                self._docs[old].live = False
                self._tombstones += 1
            self._by_name[name] = len(self._docs)
            self._docs.append(entry)
            self._gen += 1
        global_metrics.inc("docs_indexed")

    def bulk_load_packed(self, names: list[str], offsets: np.ndarray,
                         term_ids: np.ndarray, tfs: np.ndarray,
                         lengths: np.ndarray) -> None:
        """Checkpoint-restore fast path (VERDICT r3 #5): build the doc
        table directly from the checkpoint's packed CSR-style arrays —
        ``offsets[n+1]``, ``term_ids[nnz]``, ``tfs[nnz]``, ``lengths[n]``
        — with per-doc numpy *views*, no per-document ingest work. The
        packed arrays are kept so the next ``commit`` builds its COO
        fully vectorized too (no 1M-array concatenate). Only valid on an
        empty index; later upserts/deletes work normally (they drop the
        vectorized-commit fast path, not correctness)."""
        entries, (offsets, term_ids, tfs, lengths) = \
            entries_from_packed(names, offsets, term_ids, tfs, lengths)
        n = len(names)
        with self._write_lock:
            if self._docs:
                raise ValueError("bulk_load_packed requires an empty index")
            self._docs = entries
            self._by_name = dict(zip(names, range(n)))
            if len(self._by_name) != n:
                self._docs, self._by_name = [], {}
                raise ValueError("bulk_load_packed: duplicate names")
            self._gen += 1
            self._packed = (offsets, term_ids, tfs, lengths, list(names))
            self._packed_gen = self._gen
        global_metrics.inc("docs_indexed", n)

    def delete_document(self, name: str) -> bool:
        with self._write_lock:
            idx = self._by_name.pop(name, None)
            if idx is None or not self._docs[idx].live:
                return False
            self._docs[idx].live = False
            self._tombstones += 1
            self._gen += 1
            return True

    # ---- stats ----

    def live_names(self) -> list[str]:
        """Names of all live (non-tombstoned) documents — the residue
        anti-entropy pass compares these against the leader's
        placement map (cluster/node.py run_residue_reconcile)."""
        return [d.name for d in self._docs if d.live]

    @property
    def num_live_docs(self) -> int:
        return len(self._by_name)

    @property
    def nnz_live(self) -> int:
        return sum(d.term_ids.shape[0] for d in self._docs if d.live)

    def size_bytes(self) -> int:
        """Load metric for least-loaded placement (index-size analog,
        ``Worker.java:147-172``). Measures live postings content — NOT the
        capacity-bucketed device arrays, whose padded size is identical
        across lightly-loaded shards and would turn the balancer's min into
        a constant tie (every upload landing on one worker)."""
        return int(sum(d.term_ids.nbytes + d.tfs.nbytes
                       for d in self._docs if d.live))


    # ---- commit (publish an immutable snapshot) ----

    def _to_coo_packed(self, vocab_cap: int) -> tuple[CooShard, list[str],
                                                      np.ndarray]:
        """Vectorized COO build from bulk-loaded packed arrays (caller
        holds the write lock; valid only while no mutation landed since
        the bulk load). Produces the same width-sorted layout as the
        general path, via a ragged gather instead of a per-doc
        concatenate — the difference between a ~10s and a sub-second
        host build at 1M docs."""
        offsets, all_ids, all_tfs, lengths, names = self._packed
        n_live = len(names)
        widths = offsets[1:] - offsets[:-1]
        order = np.argsort(-widths, kind="stable")
        w = widths[order]
        nnz = int(w.sum())
        nnz_cap = next_capacity(max(nnz, 1), self.min_nnz_cap)
        doc_cap = next_capacity(max(n_live, 1), self.min_doc_cap)
        tf = np.zeros(nnz_cap, np.float32)
        term = np.zeros(nnz_cap, np.int32)
        doc = np.full(nnz_cap, doc_cap - 1, np.int32)
        if nnz:
            out_off = np.zeros(n_live, np.int64)
            np.cumsum(w[:-1], out=out_off[1:])
            # gather index: position within the output run + source start
            idx = (np.arange(nnz, dtype=np.int64)
                   - np.repeat(out_off, w)
                   + np.repeat(offsets[:-1][order], w))
            tf[:nnz] = all_tfs[idx]
            term[:nnz] = all_ids[idx]
            doc[:nnz] = np.repeat(np.arange(n_live, dtype=np.int32), w)
        df = (np.bincount(term[:nnz], minlength=vocab_cap)[:vocab_cap]
              .astype(np.float32) if nnz else np.zeros(vocab_cap,
                                                       np.float32))
        names_sorted = [names[i] for i in order]
        raw_len = lengths[order] if n_live else np.zeros(0, np.float32)
        doc_len = np.zeros(doc_cap, np.float32)
        doc_len[:n_live] = raw_len
        coo = CooShard(tf=tf, term=term, doc=doc, doc_len=doc_len, df=df,
                       nnz=nnz, num_docs=n_live)
        return coo, names_sorted, raw_len

    def to_coo(self, vocab_cap: int) -> tuple[CooShard, list[str],
                                              np.ndarray]:
        """Rebuild a host COO from live docs. Returns (coo, names, raw_len)."""
        with self._write_lock:
            if self._packed is not None and self._gen == self._packed_gen:
                return self._to_coo_packed(vocab_cap)
            self._packed = None   # mutated since the bulk load: drop it
            live = [d for d in self._docs if d.live]
        n_live = len(live)
        # rows sorted by distinct-term count DESC: the blocked-ELL layout
        # packs same-width rows into dense blocks (tfidf_tpu.ops.ell); the
        # stable sort keeps insertion order within a width for determinism
        sizes0 = np.fromiter((d.term_ids.shape[0] for d in live),
                             np.int64, n_live)
        order = np.argsort(-sizes0, kind="stable")
        live = [live[i] for i in order]
        names = [d.name for d in live]
        sizes = sizes0[order]
        nnz = int(sizes.sum()) if n_live else 0
        nnz_cap = next_capacity(max(nnz, 1), self.min_nnz_cap)
        doc_cap = next_capacity(max(n_live, 1), self.min_doc_cap)
        tf = np.zeros(nnz_cap, np.float32)
        term = np.zeros(nnz_cap, np.int32)
        # padding rows point at doc_cap-1 to keep `doc` non-decreasing (the
        # indices_are_sorted contract of the scoring segment-sums)
        doc = np.full(nnz_cap, doc_cap - 1, np.int32)
        if nnz:
            tf[:nnz] = np.concatenate([d.tfs for d in live])
            term[:nnz] = np.concatenate([d.term_ids for d in live])
            doc[:nnz] = np.repeat(np.arange(n_live, dtype=np.int32), sizes)
        # COO entries are unique (doc, term) pairs, so df = entry count/term.
        df = (np.bincount(term[:nnz], minlength=vocab_cap)[:vocab_cap]
              .astype(np.float32) if nnz else np.zeros(vocab_cap, np.float32))
        raw_len = (np.fromiter((d.length for d in live), np.float32, n_live)
                   if n_live else np.zeros(0, np.float32))
        doc_len = np.zeros(doc_cap, np.float32)
        doc_len[:n_live] = raw_len
        coo = CooShard(tf=tf, term=term, doc=doc, doc_len=doc_len, df=df,
                       nnz=nnz, num_docs=n_live)
        return coo, names, raw_len

    def commit(self, vocab_cap: int) -> Snapshot:
        """Build + publish the device snapshot (Lucene ``commit()`` analog)."""
        gen0 = self._gen
        if self._committed_gen == gen0 and self.snapshot is not None \
                and self.snapshot.df.shape[0] == vocab_cap:
            return self.snapshot
        coo, names, raw_len = self.to_coo(vocab_cap)
        self._version += 1
        n_live = len(names)
        kernel_len = self.model.transform_doc_len(
            coo.doc_len[:n_live].astype(np.float32))
        doc_len_host = np.zeros(coo.doc_cap, np.float32)
        doc_len_host[:n_live] = kernel_len

        df = jnp.asarray(coo.df)
        n_docs = jnp.float32(n_live)
        # avgdl from exact lengths (Lucene: sumTotalTermFreq / docCount)
        total = float(raw_len[:n_live].sum())
        avgdl = jnp.float32(total / n_live if n_live else 1.0)

        if self.layout == "ell":
            # blocked-ELL fast path: only impacts + term rows + the small
            # residual ship to device — the COO never does
            if self.model.needs_norms:
                norms_host = cosine_norms_host(coo, float(n_live))
            else:
                norms_host = np.zeros(coo.doc_cap, np.float32)
            norms = jnp.asarray(norms_host)
            impacts, terms, live = [], [], []
            kw = self.model.score_kwargs()
            # host layout + upload + impacts: the part of a commit that
            # grows with the postings (``phase_ell_build``)
            with trace_phase("ell_build"):
                ell = build_ell_from_coo(
                    coo, width_cap=self.ell_width_cap,
                    min_rows=min(256, self.min_doc_cap))
                for blk in ell.blocks:
                    rows_cap = blk.tf.shape[1]
                    dl_blk = np.zeros(rows_cap, np.float32)
                    dl_blk[:blk.n_rows] = doc_len_host[
                        blk.row0:blk.row0 + blk.n_rows]
                    nrm_blk = np.zeros(rows_cap, np.float32)
                    nrm_blk[:blk.n_rows] = norms_host[
                        blk.row0:blk.row0 + blk.n_rows]
                    # impacts precomputed once per commit (query path =
                    # pure gather + contract, no per-query BM25 math)
                    terms.append(jnp.asarray(blk.term))
                    impacts.append(ell_impacts(
                        jnp.asarray(blk.tf), terms[-1],
                        jnp.asarray(dl_blk), df, n_docs, avgdl,
                        jnp.asarray(nrm_blk), **kw))
                    live.append(blk.n_rows)
            tf = term = doc = None
            ell_kw: dict = dict(
                ell_impacts=tuple(impacts), ell_terms=tuple(terms),
                **_ell_live_fields(live))
            if ell.res_nnz:   # no spill -> no residual scoring pass at all
                ell_kw.update(
                    res_tf=jnp.asarray(ell.res_tf),
                    res_term=jnp.asarray(ell.res_term),
                    res_doc=jnp.asarray(ell.res_doc),
                    res_nnz=ell.res_nnz)
            _publish_ell_gauges([b.tf.shape for b in ell.blocks], live,
                                ell.res_doc[:ell.res_nnz])
        else:
            tf = jnp.asarray(coo.tf)
            term = jnp.asarray(coo.term)
            doc = jnp.asarray(coo.doc)
            if self.model.needs_norms:
                norms = cosine_norms(tf, term, doc, df, n_docs, coo.doc_cap)
            else:
                norms = jnp.zeros(coo.doc_cap, jnp.float32)
            ell_kw = {}
        snap = Snapshot(
            tf=tf, term=term, doc=doc,
            doc_len=jnp.asarray(doc_len_host),
            df=df, doc_norms=norms,
            n_docs=n_docs, avgdl=avgdl,
            num_docs=jnp.int32(n_live),
            doc_names=names, version=self._version, nnz=coo.nnz,
            host_coo=coo if self.keep_host_coo else None,
            **ell_kw,
        )
        self.snapshot = snap
        # only as clean as the generation we actually built from — a write
        # that raced the build leaves the index dirty for the next commit
        self._committed_gen = gen0
        global_metrics.set_gauge("index_nnz", coo.nnz)
        global_metrics.set_gauge("index_docs", n_live)
        global_metrics.set_gauge("index_size_bytes", snap.size_bytes())
        log.info("committed snapshot", version=self._version,
                 docs=n_live, nnz=coo.nnz)
        return snap

    # ---- iteration (for checkpointing) ----

    def live_entries(self) -> list[DocEntry]:
        with self._write_lock:
            return [d for d in self._docs if d.live]

    def live_entries_and_gen(self) -> tuple[list[DocEntry], int]:
        """Entries plus the generation they were read at, atomically —
        the consistency token checkpoint save uses to guarantee the doc
        table and the exported snapshot describe the same corpus."""
        with self._write_lock:
            return [d for d in self._docs if d.live], self._gen

    # ---- snapshot array export/install (checkpoint fast restore) ----

    def export_snapshot_arrays(self) -> tuple[dict, list[str], int] | None:
        """Fetch the committed snapshot's device arrays to host numpy
        for checkpointing. Restore can then re-upload them directly
        (``install_snapshot_arrays``) instead of re-running the O(corpus)
        host COO/ELL layout — at 1M docs that layout is ~35s of the
        restore while the re-upload is under a second (VERDICT r3 #5).
        Returns ``(arrays, snapshot_doc_names, gen)`` or None when
        there is no clean committed snapshot to export; ``gen`` lets the
        caller confirm nothing mutated since it read the doc table."""
        with self._write_lock:
            snap = self.snapshot
            if snap is None or self._committed_gen != self._gen:
                return None
            gen = self._gen
        out: dict[str, np.ndarray] = {
            "doc_len": np.asarray(snap.doc_len),
            "df": np.asarray(snap.df),
            "doc_norms": np.asarray(snap.doc_norms),
            "n_docs": np.float32(snap.n_docs),
            "avgdl": np.float32(snap.avgdl),
            "num_docs": np.int32(snap.num_docs),
            "nnz": np.int64(snap.nnz),
            "version": np.int64(snap.version),
        }
        if snap.is_ell:
            out["n_blocks"] = np.int64(len(snap.ell_impacts))
            for i, (imp, term) in enumerate(zip(snap.ell_impacts,
                                                snap.ell_terms)):
                out[f"ell_imp_{i}"] = np.asarray(imp)
                out[f"ell_term_{i}"] = np.asarray(term)
            out["ell_live"] = np.asarray(snap.ell_live)
            if snap.res_tf is not None:
                out["res_tf"] = np.asarray(snap.res_tf)
                out["res_term"] = np.asarray(snap.res_term)
                out["res_doc"] = np.asarray(snap.res_doc)
        else:
            out["coo_tf"] = np.asarray(snap.tf)
            out["coo_term"] = np.asarray(snap.term)
            out["coo_doc"] = np.asarray(snap.doc)
        return out, list(snap.doc_names), gen

    def install_snapshot_arrays(self, data, doc_names: list[str]) -> None:
        """Publish a snapshot rebuilt from exported arrays (the restore
        fast path). Caller guarantees the host doc table (bulk load)
        holds exactly the same live corpus and that the scoring config
        matches the one the arrays were built under."""
        ell_kw: dict = {}
        tf = term = doc = None
        if "n_blocks" in data:
            nb = int(data["n_blocks"])
            ell_kw = dict(
                ell_impacts=tuple(jnp.asarray(data[f"ell_imp_{i}"])
                                  for i in range(nb)),
                ell_terms=tuple(jnp.asarray(data[f"ell_term_{i}"])
                                for i in range(nb)),
                **_ell_live_fields(data["ell_live"]))
            res_doc = np.zeros(0, np.int32)
            if "res_tf" in data:
                # a live entry has tf > 0; the padding has none
                res_doc = np.asarray(data["res_doc"])[
                    np.asarray(data["res_tf"]) > 0]
                ell_kw.update(res_tf=jnp.asarray(data["res_tf"]),
                              res_term=jnp.asarray(data["res_term"]),
                              res_doc=jnp.asarray(data["res_doc"]),
                              res_nnz=int(res_doc.shape[0]))
            _publish_ell_gauges(
                [data[f"ell_imp_{i}"].shape for i in range(nb)],
                data["ell_live"], res_doc)
        else:
            tf = jnp.asarray(data["coo_tf"])
            term = jnp.asarray(data["coo_term"])
            doc = jnp.asarray(data["coo_doc"])
        with self._write_lock:
            self._version = int(data["version"])
            snap = Snapshot(
                tf=tf, term=term, doc=doc,
                doc_len=jnp.asarray(data["doc_len"]),
                df=jnp.asarray(data["df"]),
                doc_norms=jnp.asarray(data["doc_norms"]),
                n_docs=jnp.float32(data["n_docs"]),
                avgdl=jnp.float32(data["avgdl"]),
                num_docs=jnp.int32(data["num_docs"]),
                doc_names=list(doc_names), version=self._version,
                nnz=int(data["nnz"]),
                **ell_kw,
            )
            self.snapshot = snap
            self._committed_gen = self._gen
        global_metrics.set_gauge("index_nnz", snap.nnz)
        global_metrics.set_gauge("index_docs", len(doc_names))
        global_metrics.set_gauge("index_size_bytes", snap.size_bytes())
        log.info("installed checkpointed snapshot", docs=len(doc_names),
                 nnz=snap.nnz, version=self._version)
