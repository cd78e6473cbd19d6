"""MeshIndex / MeshSearcher — the mesh-sharded SERVING path.

This is the production face of :mod:`tfidf_tpu.parallel.sharded`: a live
index whose committed state is :class:`ShardedArrays` on a
``("docs", "terms")`` device mesh, with the same write API as
:class:`~tfidf_tpu.engine.index.ShardIndex` so the whole Engine surface
(ingest, upload, checkpoint, cluster node) works unchanged on top of it.
One node hosting a MeshIndex subsumes the reference's entire worker pool:
what the Java system does with N HTTP workers and a scatter-gather leader
(``Leader.java:39-92``) happens here inside one jitted ``shard_map``
program — per-shard scoring, ``psum`` global IDF, terms-axis score reduce,
``all_gather`` distributed top-k — with collectives on ICI instead of JSON
over the network.

Lifecycle (the mesh analog of Lucene's segment/commit model,
``Worker.java:88,138``):

* **commit** publishes an immutable :class:`MeshSnapshot`. New documents
  append on-device (``make_sharded_ingest`` — dynamic-update-slice at the
  shard cursors, O(batch)); placement is least-loaded-shard by live
  postings bytes, the ``index-size`` balancing policy of
  ``Leader.java:168-189`` applied at mesh scale.
* **deletes/upserts** tombstone via the snapshot's live mask (Lucene's
  deleted-docs bitmap); postings stay, df/avgdl keep counting them until
  the next re-shard, like Lucene until merge.
* **growth**: when the vocabulary outgrows ``vocab_cap`` or a capacity
  bucket overflows, the index re-shards — a full rebuild from the retained
  host postings onto the same mesh with wider buckets (capacities are
  power-of-two bucketed with headroom, so this is rare and amortized).
* **recovery**: host postings are the source of truth; the device state is
  always reconstructible (recovery-by-rebuild, ``Worker.java:77-88``).

Thread safety: single-writer lock over mutations + commit; searches are
lock-free against a published snapshot. Snapshots stay valid across later
commits because appends only extend per-shard doc lists and rebuilds swap
in fresh list objects — an old snapshot keeps references to the lists it
was built from.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from operator import attrgetter

import numpy as np

from tfidf_tpu.engine.index import DocEntry
from tfidf_tpu.models.base import ScoringModel
from tfidf_tpu.ops.csr import CooShard, next_capacity
from tfidf_tpu.parallel.mesh import make_mesh
from tfidf_tpu.parallel.sharded import (ShardedArrays, build_ingest_batch,
                                        build_sharded_arrays,
                                        make_sharded_ingest,
                                        make_sharded_scores,
                                        make_sharded_search, with_live_mask)
from tfidf_tpu.utils.logging import get_logger
from tfidf_tpu.utils.metrics import global_metrics
from tfidf_tpu.utils.tracing import trace_phase

log = get_logger("parallel.mesh_index")

_entry_name = attrgetter("name")


@dataclass
class MeshSnapshot:
    """Immutable published state: device arrays + the name mapping."""
    arrays: ShardedArrays
    shard_docs: list      # list[list[DocEntry]] — append-only per shard
    # the names by global id (docs_shard * doc_cap + local), None at a
    # slot no document holds; shared with the index and append-only
    # like ``shard_docs`` (see ``MeshIndex._doc_names``)
    doc_names: list
    version: int
    nnz: int
    total_live: int

    @property
    def num_names(self) -> int:
        return self.total_live


class MeshIndex:
    """Mesh-resident shard index with the ShardIndex write API."""

    def __init__(self, model: ScoringModel,
                 mesh=None,
                 min_doc_cap: int = 1024,
                 min_chunk_cap: int = 1 << 14) -> None:
        self.model = model
        self.mesh = mesh if mesh is not None else make_mesh()
        self.D = self.mesh.shape["docs"]
        self.T = self.mesh.shape["terms"]
        self.min_doc_cap = min_doc_cap
        self.min_chunk_cap = min_chunk_cap
        self._write_lock = threading.Lock()
        # committed docs per shard in local-id order (tombstones included —
        # a slot is never reused until a re-shard)
        self._shard_docs: list[list[DocEntry]] = [[] for _ in range(self.D)]
        # the names by global id, what a snapshot's ``doc_names`` is:
        # laid out whole at a re-shard (a fresh list: snapshots from
        # before it keep theirs), filled in place by an append commit —
        # a slot is never reused until a re-shard, and an older
        # snapshot's ids never reach a newer slot
        self._doc_names: list = []
        self._placed: dict[str, tuple[int, int]] = {}
        self._pending: dict[str, DocEntry] = {}   # upsert: latest wins
        self._mask_dirty = False
        self._gen = 1
        self._committed_gen = 0
        self._version = 0
        self.snapshot: MeshSnapshot | None = None
        self._ingest_fn = None
        # observable lifecycle counters (tests + /api/metrics)
        self.rebuilds = 0
        self.appends = 0

    # ---- write path (ShardIndex-compatible) ----

    def add_document(self, name: str, id_counts: dict[int, int],
                     length: float | None = None) -> None:
        if id_counts:
            items = sorted(id_counts.items())
            ids = np.fromiter((t for t, _ in items), np.int32, len(items))
            tfs = np.fromiter((f for _, f in items), np.float32,
                              len(items))
        else:
            ids = np.empty(0, np.int32)
            tfs = np.empty(0, np.float32)
        self.add_document_arrays(name, ids, tfs, length)

    def add_document_arrays(self, name: str, ids: np.ndarray,
                            tfs: np.ndarray,
                            length: float | None = None) -> None:
        from tfidf_tpu.engine.index import check_sorted_unique_ids
        tfs = np.asarray(tfs, np.float32)
        ids = np.asarray(ids, np.int32)
        check_sorted_unique_ids(name, ids)
        entry = DocEntry(
            name=name, term_ids=ids, tfs=tfs,
            length=float(length if length is not None else tfs.sum()))
        with self._write_lock:
            placed = self._placed.pop(name, None)
            if placed is not None:   # upsert: tombstone the committed copy
                s, local = placed
                self._shard_docs[s][local].live = False
                self._mask_dirty = True
            self._pending[name] = entry
            self._gen += 1
        global_metrics.inc("docs_indexed")

    def bulk_load_packed(self, names, offsets, term_ids, tfs,
                         lengths) -> None:
        """Checkpoint-restore fast path: register the packed doc table
        as pending upserts in one pass (per-doc numpy VIEWS, no
        per-document ingest work); the next commit builds the sharded
        arrays in ONE vectorized rebuild. Placement is re-derived
        (round-robin) — scoring is placement-invariant because df/IDF
        are globalized by psum; only parity mode's per-shard statistics
        can differ from the pre-checkpoint placement."""
        from tfidf_tpu.engine.index import entries_from_packed
        entries, (offsets, term_ids, tfs, lengths) = \
            entries_from_packed(names, offsets, term_ids, tfs, lengths)
        with self._write_lock:
            if self._pending or self._placed or any(self._shard_docs):
                raise ValueError(
                    "bulk_load_packed requires an empty index")
            self._pending = {e.name: e for e in entries}
            if len(self._pending) != len(entries):
                self._pending = {}
                raise ValueError("bulk_load_packed: duplicate names")
            self._bulk_load_stats(term_ids, lengths)
            self._gen += 1
        global_metrics.inc("docs_indexed", len(entries))

    def _bulk_load_stats(self, term_ids, lengths) -> None:
        """Hook for subclasses with incremental stat accumulators
        (caller holds the write lock)."""

    def delete_document(self, name: str) -> bool:
        with self._write_lock:
            if self._pending.pop(name, None) is not None:
                self._gen += 1
                return True
            placed = self._placed.pop(name, None)
            if placed is None:
                return False
            s, local = placed
            self._shard_docs[s][local].live = False
            self._mask_dirty = True
            self._gen += 1
            return True

    # ---- stats ----

    @property
    def num_live_docs(self) -> int:
        return len(self._placed) + len(self._pending)

    @property
    def nnz_live(self) -> int:
        n = sum(d.term_ids.shape[0] for d in self._pending.values())
        for sd in self._shard_docs:
            n += sum(d.term_ids.shape[0] for d in sd if d.live)
        return int(n)

    def size_bytes(self) -> int:
        # under the write lock: the leader polls /worker/index-size
        # while upload handlers are adding to _pending, and a dict that
        # grows mid-iteration raises — which the leader reads as a sick
        # worker ("no reachable workers" in the middle of an ingest)
        with self._write_lock:
            n = sum(d.term_ids.nbytes + d.tfs.nbytes
                    for d in self._pending.values())
            for sd in self._shard_docs:
                n += sum(d.term_ids.nbytes + d.tfs.nbytes
                         for d in sd if d.live)
        return int(n)

    def live_entries(self) -> list[DocEntry]:
        with self._write_lock:
            out = []
            for sd in self._shard_docs:
                out.extend(d for d in sd if d.live)
            out.extend(self._pending.values())
            return out


    # ---- commit ----

    def commit(self, vocab_cap: int) -> MeshSnapshot:
        with self._write_lock:
            gen0 = self._gen
            if (self._committed_gen == gen0 and self.snapshot is not None
                    and self.snapshot.arrays.vocab_cap >= vocab_cap):
                return self.snapshot
            pending = list(self._pending.values())
            arrays = self.snapshot.arrays if self.snapshot else None
            if arrays is None or vocab_cap > arrays.vocab_cap:
                arrays = self._rebuild_locked(pending, vocab_cap)
            elif pending:
                try:
                    arrays = self._append_locked(arrays, pending)
                except ValueError as e:
                    # a capacity bucket overflowed: re-shard with wider
                    # buckets (the analog of Lucene growing a new segment
                    # generation; amortized by power-of-two headroom)
                    log.info("capacity overflow; re-sharding",
                             reason=str(e).split(";")[0])
                    arrays = self._rebuild_locked(pending, vocab_cap)
            if self._mask_dirty:
                arrays = with_live_mask(self.mesh, arrays,
                                        self._host_mask(arrays.doc_cap))
                self._mask_dirty = False
            self._pending = {}
            self._version += 1
            snap = MeshSnapshot(
                arrays=arrays, shard_docs=self._shard_docs,
                doc_names=self._doc_names,
                version=self._version, nnz=self.nnz_live,
                total_live=len(self._placed))
            self.snapshot = snap
            self._committed_gen = gen0
        global_metrics.set_gauge("index_docs", snap.total_live)
        global_metrics.set_gauge("index_nnz", snap.nnz)
        global_metrics.set_gauge("mesh_rebuilds", self.rebuilds)
        self._publish_shard_gauges(snap.shard_docs, arrays.doc_cap)
        log.info("committed mesh snapshot", version=snap.version,
                 docs=snap.total_live, nnz=snap.nnz,
                 mesh=dict(self.mesh.shape))
        return snap

    def _publish_shard_gauges(self, shard_docs: list,
                              rows_padded: int) -> None:
        """What a sharded step waits on, as gauges: the mesh's shape, the
        document slots (tombstones included: they are scored too) of the
        emptiest and the fullest docs-shard, and the rows a shard's step
        scores with their padding (equal on every shard)."""
        slots = [len(sd) for sd in shard_docs]
        global_metrics.set_gauge("mesh_docs_shards", self.D)
        global_metrics.set_gauge("mesh_terms_shards", self.T)
        global_metrics.set_gauge("mesh_shard_docs_min", min(slots))
        global_metrics.set_gauge("mesh_shard_docs_max", max(slots))
        global_metrics.set_gauge("mesh_shard_rows_padded", rows_padded)

    def _host_mask(self, doc_cap: int) -> np.ndarray:
        mask = np.zeros((self.D, doc_cap), np.float32)
        for s, sd in enumerate(self._shard_docs):
            for local, d in enumerate(sd):
                if d.live:
                    mask[s, local] = 1.0
        return mask

    def _entries_to_coo(self, entries: list[DocEntry], vocab_cap: int
                        ) -> tuple[CooShard, np.ndarray]:
        """Concatenation-order COO (NOT length-sorted — placement is
        ``i % D``, so order IS the layout; cf. ``shard_documents``).
        Returns (coo with model-transformed lengths, raw lengths)."""
        n = len(entries)
        sizes = np.fromiter((d.term_ids.shape[0] for d in entries),
                            np.int64, n)
        nnz = int(sizes.sum())
        tf = np.zeros(max(nnz, 1), np.float32)
        term = np.zeros(max(nnz, 1), np.int32)
        doc = np.zeros(max(nnz, 1), np.int32)
        if nnz:
            tf[:nnz] = np.concatenate([d.tfs for d in entries])
            term[:nnz] = np.concatenate([d.term_ids for d in entries])
            doc[:nnz] = np.repeat(np.arange(n, dtype=np.int32), sizes)
        df = (np.bincount(term[:nnz], minlength=vocab_cap)[:vocab_cap]
              .astype(np.float32) if nnz
              else np.zeros(vocab_cap, np.float32))
        raw_len = np.fromiter((d.length for d in entries), np.float32, n)
        doc_len = self.model.transform_doc_len(raw_len).astype(np.float32)
        return CooShard(tf=tf[:nnz], term=term[:nnz], doc=doc[:nnz],
                        doc_len=doc_len, df=df, nnz=nnz,
                        num_docs=n), raw_len

    def _rebuild_locked(self, pending: list[DocEntry],
                        vocab_cap: int) -> ShardedArrays:
        """Full re-shard from host postings: drops tombstones, re-tightens
        df, widens capacity buckets — the compaction/merge analog."""
        entries = []
        for sd in self._shard_docs:
            entries.extend(d for d in sd if d.live)
        entries.extend(pending)
        coo, raw_len = self._entries_to_coo(entries, vocab_cap)
        arrays = build_sharded_arrays(
            coo, self.mesh, min_chunk_cap=self.min_chunk_cap,
            min_doc_cap=self.min_doc_cap, raw_doc_len=raw_len)
        # fresh list objects: snapshots taken before this rebuild keep the
        # old lists (and the old arrays), staying internally consistent
        self._shard_docs = [[] for _ in range(self.D)]
        self._placed = {}
        for i, e in enumerate(entries):
            e.live = True
            s = i % self.D
            self._placed[e.name] = (s, len(self._shard_docs[s]))
            self._shard_docs[s].append(e)
        self._doc_names = self._name_table(entries, arrays.doc_cap)
        self._mask_dirty = False
        self.rebuilds += 1
        global_metrics.inc("mesh_reshards")
        return arrays

    def _name_table(self, entries: list[DocEntry], stride: int,
                    perms=None) -> list:
        """The names by global id after a re-shard dealt ``entries``
        round-robin: shard ``s`` holds ``entries[s::D]`` from
        ``s * stride`` on, in insertion order or, with ``perms``, where
        its ELL permutation put them (``perms[s][row]`` = insertion id
        of the document in ELL row ``row``)."""
        names = np.fromiter(map(_entry_name, entries), object,
                            len(entries))
        table = np.full(self.D * stride, None, object)
        for s in range(self.D):
            of_shard = names[s::self.D]
            if perms is not None:
                of_shard = of_shard[perms[s]]
            table[s * stride:s * stride + len(of_shard)] = of_shard
        return table.tolist()

    def _append_locked(self, arrays: ShardedArrays,
                       pending: list[DocEntry]) -> ShardedArrays:
        """On-device append of the pending batch (O(batch), no rebuild).

        Placement: least-loaded shard by live postings bytes — the
        ``GET /worker/index-size`` balancing policy (``Leader.java:168-
        189``) applied per document at mesh scale.
        """
        loads = [sum(d.term_ids.nbytes + d.tfs.nbytes
                     for d in sd if d.live) for sd in self._shard_docs]
        slots = [len(sd) for sd in self._shard_docs]
        per_entries: list[list[DocEntry]] = [[] for _ in range(self.D)]
        for e in pending:
            s = int(np.argmin(loads))
            per_entries[s].append(e)
            loads[s] += e.term_ids.nbytes + e.tfs.nbytes
            slots[s] += 1
            if slots[s] > arrays.doc_cap:
                raise ValueError("docs-shard over doc capacity; re-shard")
        per_docs = [[dict(zip(e.term_ids.tolist(),
                              e.tfs.astype(np.float64).tolist()))
                     for e in es] for es in per_entries]
        per_lens = [
            list(self.model.transform_doc_len(
                np.asarray([e.length for e in es], np.float32))
                .astype(np.float32)) if es else []
            for es in per_entries]
        per_raw = [[e.length for e in es] for es in per_entries]
        max_entries = max((sum(e.term_ids.shape[0] for e in es)
                           for es in per_entries), default=0)
        C = next_capacity(max(-(-max_entries // self.T), 1), 64)
        batch = build_ingest_batch(self.mesh, arrays, per_docs, per_lens, C,
                                   raw_lengths_per_shard=per_raw)
        if self._ingest_fn is None:
            self._ingest_fn = make_sharded_ingest(self.mesh)
        arrays = self._ingest_fn(arrays, *batch)
        for s, es in enumerate(per_entries):
            for e in es:
                local = len(self._shard_docs[s])
                self._placed[e.name] = (s, local)
                self._shard_docs[s].append(e)
                self._doc_names[s * arrays.doc_cap + local] = e.name
        self.appends += 1
        global_metrics.inc("mesh_appends")
        return arrays


from tfidf_tpu.engine.searcher import SearchLoop


class MeshSearcher(SearchLoop):
    """Query execution against MeshSnapshots — the distributed forward
    pass: :class:`~tfidf_tpu.engine.searcher.SearchLoop`'s hooks over
    the sharded step. The ELL mesh layout overrides :meth:`_step`,
    :meth:`_search_unbounded` and :meth:`_on_snapshot`."""

    def __init__(self, index: MeshIndex, analyzer, vocab,
                 model: ScoringModel,
                 *, global_idf: bool = True, **loop) -> None:
        super().__init__(index, analyzer, vocab, model, **loop)
        # global_idf=False reproduces the reference's per-worker statistics
        # (each Lucene shard scores against local df/N, Worker.java:222-241)
        self.global_idf = global_idf
        self._search_fns: dict[tuple[int, int], object] = {}
        self._scores_fn = None

    def _model_kwargs(self) -> dict:
        kw = dict(self.model.score_kwargs())
        kw.pop("model", None)
        return kw

    def _depths(self, k: int, shard_cap: int) -> tuple[int, int]:
        """``(a shard's depth, the merged reply's)`` for a request of
        ``k``: a shard ranks at most its own ``shard_cap`` rows, and a
        request deeper than that takes all of every shard, so the reply
        holds up to that many a shard (``tests/test_deep_topk.py``: k
        past one shard's rows and under the corpus)."""
        kk = min(k, shard_cap)
        return kk, min(k, kk * self.index.D)

    def _get_search_fn(self, k: int, depth: int):
        fn = self._search_fns.get((k, depth))
        if fn is None:
            fn = make_sharded_search(
                self.index.mesh, k=k,
                model=self.model.score_kwargs()["model"],
                global_idf=self.global_idf, packed=True, depth=depth,
                **self._model_kwargs())
            self._search_fns[k, depth] = fn
        return fn

    def _get_scores_fn(self):
        if self._scores_fn is None:
            self._scores_fn = make_sharded_scores(
                self.index.mesh,
                model=self.model.score_kwargs()["model"],
                global_idf=self.global_idf, **self._model_kwargs())
        return self._scores_fn

    def posting_blocks(self) -> list[tuple]:
        """Layout hook, as :meth:`Searcher.posting_blocks`: the COO
        scatter step never rides the Pallas kernel."""
        snap = self.index.snapshot
        return [] if snap is None else [(snap.arrays.tf, False)]

    def _dispatch_chunk(self, snap, qb, n_queries: int, k: int):
        global_metrics.inc("mesh_steps")
        # jax's ENQUEUE of the shard_map program, not the devices'
        # work (that is ``device_wait``, in the fetch stage)
        with trace_phase("score"):
            return self._step(snap, qb, k)

    def _step(self, snap, qb, k: int):
        """Layout hook: launch one chunk's packed top-k (not fetched)."""
        kk, depth = self._depths(k, snap.arrays.doc_cap)
        return self._get_search_fn(kk, depth)(snap.arrays, qb), depth

    def _search_unbounded(self, snap, queries):
        qb, _widest = self._vectorize(queries,
                                      self._batch_cap(len(queries)))
        return self._assemble(snap, queries, *self._rank_all(snap, qb))

    def _rank_all(self, snap: MeshSnapshot, qb):
        """Parity mode: full per-shard score matrices ranked on the host
        (the reference's unbounded Integer.MAX_VALUE results,
        ``Worker.java:230``). O(corpus) per query by definition."""
        scores = np.asarray(self._get_scores_fn()(snap.arrays, qb))
        D, B, doc_cap = scores.shape
        flat = scores.transpose(1, 0, 2).reshape(B, D * doc_cap)
        order = np.argsort(-flat, axis=1, kind="stable")
        vals = np.take_along_axis(flat, order, axis=1)
        return vals, order.astype(np.int64), D * doc_cap
