"""Engine — the per-node facade tying analyzer, vocab, index, and searcher.

One Engine is what a worker node hosts (the role of the whole Lucene +
filesystem stack inside ``worker/Worker.java``): ingest bytes -> text ->
tokens -> vocab ids -> shard index; commit; search; checkpoint; rebuild.

Durability model matches the reference exactly (SURVEY.md §5.4): raw
documents on disk are the source of truth (``${mydocument.path}``); the
index is always reconstructible from them by ``build_from_directory`` (the
boot-time re-walk of ``Worker.java:77-88``); checkpoints are an optimization
over that rebuild, not a requirement for correctness.
"""

from __future__ import annotations

import itertools
import os
import threading

from tfidf_tpu.engine.compute_health import (ComputeHealth,
                                             FallbackUnsupported,
                                             HostFallbackScorer)
from tfidf_tpu.engine.index import ShardIndex
from tfidf_tpu.engine.segments import SegmentedIndex
from tfidf_tpu.engine.searcher import Searcher, SearchHit
from tfidf_tpu.engine.vocab import NativeVocabulary, Vocabulary
from tfidf_tpu.models.base import get_model
from tfidf_tpu.ops.analyzer import (Analyzer, UnsupportedMediaType,
                                    extract_text)
from tfidf_tpu.utils import storage
from tfidf_tpu.utils.config import Config
from tfidf_tpu.utils.logging import Stopwatch, get_logger
from tfidf_tpu.utils.metrics import global_metrics
from tfidf_tpu.utils.tracing import trace_phase

log = get_logger("engine")

# staged-upload temp-name uniquifier (see Engine.stage_bytes)
_STAGE_SEQ = itertools.count()


class Engine:
    def __init__(self, config: Config | None = None, mesh=None) -> None:
        """``mesh`` (optional, engine_mode="mesh" only): an existing
        jax.sharding.Mesh to serve on; defaults to all local devices on
        the "docs" axis (``Config.mesh_shape`` overrides)."""
        self.config = config or Config()
        c = self.config
        # single-writer mutation guard (the reference's
        # ``synchronized(indexWriter)``, Worker.java:136-139); RLock
        # because ingest_bytes -> ingest_text nests
        self._write_lock = threading.RLock()
        self.dense = None    # set below; stays None for mesh layouts
        self.tier = None     # set below for tiered segments mode only
        # compute-plane health (ISSUE 20): every search entry point
        # routes through _run_compute, which classifies device faults,
        # advances this state machine, and — local plain-snapshot mode
        # only — serves from the bit-exact host mirror while sick.
        self.compute = ComputeHealth(
            degraded_after=c.compute_degraded_after,
            sick_after=c.compute_sick_after,
            probe_interval_s=c.compute_probe_interval_s)
        self._fallback: HostFallbackScorer | None = None
        self._fallback_tls = threading.local()
        self.analyzer = Analyzer(
            lowercase=c.lowercase,
            stopwords=frozenset(c.stopwords),
            max_token_length=c.max_token_length)
        self.model = get_model(c.model, k1=c.bm25_k1, b=c.bm25_b,
                               lucene_parity=c.lucene_parity)
        # native C++ ingest fast path (tokenize+count+id-map in one call);
        # non-ASCII documents and unavailable-compiler environments fall
        # back to the pure-Python chain with identical results
        self.native = None
        if c.native_ingest:
            from tfidf_tpu import native as native_mod
            if native_mod.available():
                self.native = native_mod.NativeEngine(
                    lowercase=c.lowercase, stopwords=tuple(c.stopwords),
                    max_token_length=c.max_token_length)
        if self.native is not None:
            self.vocab = NativeVocabulary(
                self.native, min_capacity=c.min_vocab_capacity)
        else:
            self.vocab = Vocabulary(min_capacity=c.min_vocab_capacity)
        if c.engine_mode == "mesh":
            # the distributed serving path: index + searches live on a
            # ("docs","terms") device mesh inside one shard_map program —
            # this subsumes the reference's HTTP worker pool
            # (Leader.java:39-92) with ICI collectives
            from tfidf_tpu.parallel.mesh import make_mesh
            from tfidf_tpu.parallel.mesh_index import (MeshIndex,
                                                       MeshSearcher)
            if mesh is None:
                shape = tuple(c.mesh_shape) if c.mesh_shape else None
                mesh = make_mesh(shape)
            d_x_t = mesh.shape["docs"] * mesh.shape["terms"]
            min_chunk = max(1 << 10, c.min_nnz_capacity // max(1, d_x_t))
            # the ELL base layout cannot express cosine norms, per-shard
            # parity statistics, or unbounded ranking — those configs
            # keep the COO scatter layout
            want_ell = (c.mesh_layout == "ell"
                        and not self.model.needs_norms
                        and not c.lucene_parity
                        and not c.unbounded_results
                        and mesh.shape["terms"] <= 8)
            if want_ell:
                from tfidf_tpu.parallel.mesh_ell_index import (
                    MeshEllIndex, MeshEllSearcher)
                self.index = MeshEllIndex(
                    self.model, mesh=mesh,
                    min_doc_cap=c.min_doc_capacity,
                    min_chunk_cap=min_chunk,
                    ell_width_cap=c.ell_width_cap)
                self.searcher = MeshEllSearcher(
                    self.index, self.analyzer, self.vocab, self.model,
                    query_batch=c.query_batch,
                    max_query_terms=c.max_query_terms,
                    top_k=c.top_k, result_order=c.result_order,
                    pipeline_depth=c.search_pipeline_depth,
                    pipeline_mode=c.search_pipeline_mode)
                return
            self.index = MeshIndex(
                self.model, mesh=mesh,
                min_doc_cap=c.min_doc_capacity,
                min_chunk_cap=min_chunk)
            self.searcher = MeshSearcher(
                self.index, self.analyzer, self.vocab, self.model,
                query_batch=c.query_batch,
                max_query_terms=c.max_query_terms,
                top_k=c.top_k, result_order=c.result_order,
                # parity mode scores each shard against local statistics,
                # as every Java worker does (Worker.java:222-241)
                global_idf=not c.lucene_parity,
                pipeline_depth=c.search_pipeline_depth,
                pipeline_mode=c.search_pipeline_mode)
            return
        if c.index_mode == "segments":
            # tiered postings (ISSUE 18): device-resident hot set +
            # mmap-backed cold tier with block-max skipping. Loud on a
            # cosine model — no sound per-segment upper bound exists
            # there, and silently serving untiered would fake the
            # memory-footprint contract the knob promises.
            if c.tier_enabled:
                from tfidf_tpu.engine.tiering import TierManager
                cold = c.tier_cold_dir or os.path.join(
                    c.index_path, "cold")
                self.tier = TierManager(
                    cold, int(c.tier_hot_budget_mb) << 20,
                    ring_depth=c.tier_ring_depth,
                    skip_margin=c.tier_skip_margin)
            self.index = SegmentedIndex(
                self.model,
                min_doc_cap=c.min_doc_capacity,
                ell_width_cap=c.ell_width_cap,
                max_segments=c.max_segments,
                sync_merge_nnz=c.sync_merge_nnz,
                merge_upload_pace=c.merge_upload_pace,
                merge_workers=c.merge_workers,
                tier=self.tier)
        else:
            self.index = ShardIndex(
                self.model,
                min_nnz_cap=c.min_nnz_capacity,
                min_doc_cap=c.min_doc_capacity,
                layout=c.scoring_layout,
                ell_width_cap=c.ell_width_cap)
        self.searcher = Searcher(
            self.index, self.analyzer, self.vocab, self.model,
            query_batch=c.query_batch, max_query_terms=c.max_query_terms,
            top_k=c.top_k, result_order=c.result_order,
            use_pallas=c.use_pallas,
            pipeline_depth=c.search_pipeline_depth,
            pipeline_mode=c.search_pipeline_mode)
        # host-fallback degraded scoring rides only the local Searcher
        # (mesh modes returned above; segmented snapshots are rejected
        # lazily by the scorer itself with FallbackUnsupported)
        if c.compute_fallback:
            self._fallback = HostFallbackScorer(self.searcher)
        # dense plane (ISSUE 17): a per-doc embedding column beside the
        # sparse postings, mutated by the same ingest/delete calls under
        # the same write lock and committed by the same commit(). Local
        # engine mode only — the mesh layouts return above and get the
        # standalone parallel/mesh_dense.py op instead.
        if c.embedding_enabled:
            from tfidf_tpu.engine.dense import EmbeddingColumn
            from tfidf_tpu.engine.embedder import get_embedder
            self.dense = EmbeddingColumn(
                get_embedder(c.embedding_model, c.embedding_dim),
                min_doc_capacity=c.min_doc_capacity,
                chunk=c.embedding_chunk)

    # ---- ingest (Worker.upload / addDocToIndex analog) ----

    def ingest_text(self, name: str, text: str) -> None:
        # the write lock is the reference's ``synchronized(indexWriter)``
        # (Worker.java:136-139): concurrent HTTP upload handlers reach
        # this path, and neither Vocabulary.add (read-len-then-append)
        # nor the index mutation below is safe under interleaving
        with self._write_lock, trace_phase("analyze"):
            if self.native is not None and self.dense is None:
                res = self.native.analyze(text, add=True)
                if res is not None:
                    # observable fast-path hit rate: the native tokenizer
                    # handles ASCII documents; non-ASCII falls through to
                    # the (bit-identical) Python analyzer below
                    global_metrics.inc("ingest_native_fast_path")
                    ids, tfs, length = res
                    self.index.add_document_arrays(name, ids, tfs, length)
                    return
            # The embedding column needs token STRINGS (the embedder
            # hashes them — vocab ids are per-worker insertion order and
            # would break replica-identical dense scores), so with the
            # dense plane on, every document takes the Python analyzer
            # path and the counts feed both planes from ONE tokenize.
            global_metrics.inc("ingest_python_fallback")
            counts = self.analyzer.counts(text)
            length = float(sum(counts.values()))
            id_counts = self.vocab.map_counts(counts, add=True)
            self.index.add_document(name, id_counts, length=length)
            if self.dense is not None:
                self.dense.upsert(name, counts)

    def ingest_bytes(self, name: str, data: bytes,
                     save_to_disk: bool = False) -> None:
        """Full upload path: optional durable write of the raw document
        (the reference's ``Files.copy`` to ``${mydocument.path}``,
        ``Worker.java:133-134``), then extract + index.

        fsync-before-ack (``config.storage_fsync``): the raw bytes are
        fsynced — group-committed across concurrent upload threads
        (``utils.storage.GroupCommitter``) — BEFORE the rename that
        publishes them, and the parent directory is fsynced before this
        returns, so an acked upload survives whole-cluster power loss.
        The file fsync must precede the rename: an upsert that renamed
        first could replace previously-ACKED bytes with an unflushed
        file a crash then tears. (The batch upload handler uses the
        two-phase :meth:`stage_bytes` / :meth:`publish_staged` pair
        instead — two group-commit rounds per batch rather than
        per-document fsyncs.)

        The write lock spans the publish rename AND the indexing so
        concurrent same-name uploads leave disk and index agreeing on
        one writer's content — otherwise a restart's
        ``build_from_directory`` re-walk could silently flip search
        results to the other writer's version. (The temp-file write and
        its fsync run outside the lock — each writer owns a unique temp
        name, and serializing group-committed fsyncs under the lock
        would defeat the group.)"""
        # extract before any disk work: an UnsupportedMediaType must
        # refuse without leaving bytes on disk, and extraction needs no
        # shared state
        text = extract_text(data)
        if not save_to_disk:
            self.ingest_text(name, text)
            return
        path = self._safe_doc_path(name)
        d = os.path.dirname(path)
        os.makedirs(d, exist_ok=True)
        # unique temp per writer: concurrent uploads of the SAME name
        # sharing one ".part" path race — the loser's rename dies after
        # the winner moved it away
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.part"
        durable = self.config.storage_fsync
        try:
            storage.write_bytes(tmp, data)
            if durable:
                storage.global_committer.sync([tmp])
            with self._write_lock:
                storage.replace(tmp, path)
                self.ingest_text(name, text)
        finally:
            if os.path.exists(tmp):
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        if durable:
            storage.global_committer.sync([d])

    def stage_bytes(self, name: str, data: bytes) -> tuple[str, str, str]:
        """First half of the batched durable upload: extract + write
        the raw bytes to a unique temp, NO fsync, NO indexing yet.
        Returns ``(tmp, final_path, text)`` for :meth:`publish_staged`.
        The batch handler stages every document, group-fsyncs ALL the
        temps in one committer round, then publishes — two fsync
        rounds per batch instead of one per document, which is what
        lets ingest throughput survive fsync-before-ack."""
        text = extract_text(data)
        path = self._safe_doc_path(name)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # globally unique temp: a batch may legally contain the same
        # name twice (last upsert wins), and both stagings must coexist
        tmp = f"{path}.{os.getpid()}.{next(_STAGE_SEQ)}.part"
        storage.write_bytes(tmp, data)
        return tmp, path, text

    def publish_staged(self, name: str, tmp: str, path: str,
                      text: str) -> None:
        """Second half: publish rename + index under the write lock
        (same disk/index agreement contract as ``ingest_bytes``). The
        caller has already fsynced ``tmp`` — renaming an unflushed
        temp over previously-acked bytes is the upsert-tear hazard."""
        with self._write_lock:
            storage.replace(tmp, path)
            self.ingest_text(name, text)

    def discard_staged(self, tmp: str) -> None:
        try:
            if os.path.exists(tmp):
                os.unlink(tmp)
        except OSError:
            pass

    def delete(self, name: str) -> bool:
        with self._write_lock:
            ok = self.index.delete_document(name)
            if self.dense is not None:
                self.dense.delete(name)
            return ok

    def document_names(self) -> list[str] | None:
        """Names of all live indexed documents, or None when the index
        layout does not support listing (mesh layouts) — consumed by
        ``GET /worker/names`` for the leader's residue anti-entropy
        pass (ghost/orphan reconciliation, cluster/node.py)."""
        fn = getattr(self.index, "live_names", None)
        return fn() if fn is not None else None

    def remove_document(self, rel: str) -> bool:
        """Delete a document from BOTH the index and the durable docs
        dir — the shard-recovery reconciliation needs both, or a
        restarted worker's boot re-walk resurrects the moved doc."""
        with self._write_lock:
            ok = self.index.delete_document(rel)
            if self.dense is not None:
                self.dense.delete(rel)
            try:
                path = self._safe_doc_path(rel)
                if os.path.isfile(path):
                    os.unlink(path)
            except PermissionError:
                pass   # traversal-unsafe name cannot exist on disk
            return ok

    def commit(self) -> None:
        with self._write_lock, trace_phase("commit"), Stopwatch() as sw:
            self.index.commit(self.vocab.capacity())
            if self.dense is not None:
                self.dense.commit()
                if self.tier is not None:
                    # the dense snapshot is a carve-out of the same HBM
                    # the hot sparse set competes for (ISSUE 18 satellite:
                    # the hybrid plane must not silently pin the whole
                    # embedding matrix outside the budget accounting)
                    self.tier.set_reserved(
                        int(self.dense.stats()["device_bytes"]))
        log.info("commit", ms=sw.ms, docs=self.index.num_live_docs)

    def build_from_directory(self, docs_path: str | None = None,
                             newer_than: float | None = None) -> int:
        """Recovery-by-rebuild: walk the documents dir, upsert every regular
        file keyed by its relative path, then commit (``Worker.java:77-88``).
        Idempotent — safe to run on a non-empty index.

        ``newer_than`` (unix mtime): skip files older than this — the
        checkpoint-restore boot path re-walks only documents written
        after the checkpoint, keeping the always-reconstructible
        property without re-analyzing the whole corpus."""
        root = docs_path or self.config.documents_path
        n = 0
        if os.path.isdir(root):
            for dirpath, _dirnames, filenames in sorted(os.walk(root)):
                for fn in sorted(filenames):
                    full = os.path.join(dirpath, fn)
                    rel = os.path.relpath(full, root)
                    if newer_than is not None:
                        try:
                            if os.path.getmtime(full) < newer_than:
                                continue
                        except OSError:
                            continue
                    try:
                        with open(full, "rb") as f:
                            self.ingest_text(rel, extract_text(f.read()))
                        n += 1
                    except UnsupportedMediaType as e:
                        # a stray binary in the documents dir must not
                        # kill recovery-by-rebuild
                        log.warning("skipping unsupported file",
                                    path=full, err=str(e))
                    except OSError as e:  # unreadable file: skip, like walk
                        log.warning("skipping unreadable file",
                                    path=full, err=str(e))
        self.commit()
        log.info("rebuilt index from documents dir", root=root, docs=n)
        return n

    # ---- search (Worker.processDocuments analog) ----
    #
    # Every entry point routes through _run_compute (ISSUE 20): device
    # faults are classified (cluster/resilience.classify_compute_fault),
    # advance the ComputeHealth machine, trigger the OOM batch-backoff
    # ladder, and — when a host mirror exists — degrade to bit-exact
    # host scoring instead of failing the request. Poison (NaN output)
    # is NEVER absorbed: it re-raises so the worker handler can stamp
    # X-Compute-Fault: poison and the leader can quarantine the query.

    def _serve_fallback(self, queries, fallback_fn):
        """Run the host mirror; returns ``(served, result)`` —
        ``served`` False means the mirror does not support the active
        snapshot (segmented/mesh) and the caller should keep going."""
        try:
            out = fallback_fn(queries)
        except FallbackUnsupported:
            return False, None
        global_metrics.inc("compute_fallback_served", max(1, len(queries)))
        self._fallback_tls.flag = True
        return True, out

    def pop_fallback_served(self) -> bool:
        """True iff a fallback answer was served on THIS thread since
        the last pop — the worker handler's X-Compute-Degraded stamp
        (thread-local: one HTTP request == one handler thread)."""
        served = getattr(self._fallback_tls, "flag", False)
        self._fallback_tls.flag = False
        return served

    def _oom_ladder(self, queries, device_fn):
        """Alloc-OOM batch backoff: retry the WHOLE query list in
        sub-batches of B/2, B/4, ... down to ``oom_backoff_min_batch``.
        Returns the list of partial results, or None when the floor is
        reached with OOM still firing. Non-OOM faults mid-ladder
        re-raise (the ladder only buys memory, not health)."""
        bsz = len(queries) // 2
        floor = max(1, int(self.config.oom_backoff_min_batch))
        while bsz >= floor:
            global_metrics.inc("compute_oom_backoff")
            log.warning("device OOM: retrying at smaller batch",
                        batch=bsz, queries=len(queries))
            try:
                return [device_fn(queries[lo:lo + bsz])
                        for lo in range(0, len(queries), bsz)]
            except Exception as e:
                from tfidf_tpu.cluster.resilience import \
                    classify_compute_fault
                kind = classify_compute_fault(e)
                if kind != "oom":
                    raise
                self.compute.note_fault(kind)
                bsz //= 2
        return None

    def _run_compute(self, queries, device_fn, fallback_fn, merge):
        """The compute-plane guard every search path shares.

        ``device_fn(qs)`` scores a query sub-list on device;
        ``fallback_fn(qs)`` (or None) is the host mirror; ``merge``
        joins partial results from the OOM ladder. Flow: sick devices
        skip straight to the fallback (one probe per interval still
        tries the device — the recovery path); device faults classify,
        advance health, ladder down on OOM, then degrade or re-raise.
        """
        from tfidf_tpu.cluster.resilience import classify_compute_fault
        fb = fallback_fn if self._fallback is not None else None
        if queries and fb is not None \
                and not self.compute.should_try_device():
            served, out = self._serve_fallback(queries, fb)
            if served:
                return out
        try:
            out = merge([device_fn(queries)])
            if queries:
                self.compute.note_success()
            return out
        except Exception as e:
            kind = classify_compute_fault(e)
            if kind is None:
                raise
            if kind == "poison":
                # poisoned output is a query/data problem, not a sick
                # device: never absorbed, never advances health — the
                # wire stamp + leader quarantine own it
                global_metrics.inc("compute_poison_outputs")
                raise
            self.compute.note_fault(kind)
            if kind == "oom" and len(queries) > 1:
                parts = self._oom_ladder(queries, device_fn)
                if parts is not None:
                    self.compute.note_success()
                    return merge(parts)
            if fb is not None:
                served, out = self._serve_fallback(queries, fb)
                if served:
                    return out
            raise

    def compute_stats(self) -> dict:
        """ComputeHealth summary for /api/health and `status`, plus
        WHERE the compute runs: platform, device kind and count, how
        many of the committed posting blocks ride the fused Pallas
        kernel (and whether that kernel is interpreted), which devices
        hold the index and what each has allocated — so nothing outside
        this process has to infer whether the chip is doing the work."""
        import jax

        from tfidf_tpu.ops.ell import pallas_interpret
        d = self.compute.snapshot()
        d["fallback_available"] = self._fallback is not None
        devs = jax.local_devices()
        blocks = self.searcher.posting_blocks()
        d["platform"] = devs[0].platform
        d["device_kind"] = devs[0].device_kind
        d["device_count"] = jax.device_count()
        d["posting_blocks"] = len(blocks)
        d["kernel_blocks"] = sum(bool(k) for _a, k in blocks)
        d["kernel_interpret"] = pallas_interpret()
        d["index_devices"] = sorted(
            {s.device.id for a, _k in blocks
             for s in a.addressable_shards})
        d["device_memory"] = [
            {"id": dev.id, "bytes_in_use": ms["bytes_in_use"],
             "peak_bytes_in_use": ms["peak_bytes_in_use"]}
            for dev in devs
            if (ms := dev.memory_stats()) is not None]
        return d

    def search(self, query: str, k: int | None = None,
               unbounded: bool = False) -> list[SearchHit]:
        return self.search_batch([query], k=k, unbounded=unbounded)[0]

    def search_batch(self, queries: list[str], k: int | None = None,
                     unbounded: bool = False) -> list[list[SearchHit]]:
        """The hits of every query, each scored over ALL its terms. A
        query of more distinct terms than ``max_query_terms`` raises
        :class:`~tfidf_tpu.engine.searcher.TooManyQueryTerms`, which
        names it: nothing is cut to fit."""
        return self._run_compute(
            queries,
            lambda qs: self.searcher.search(qs, k=k, unbounded=unbounded),
            lambda qs: self._fallback.search(qs, k=k, unbounded=unbounded),
            merge=lambda parts: [hits for p in parts for hits in p])

    @staticmethod
    def _merge_arrays(parts):
        """Join OOM-ladder partials from the arrays path: vals/ids
        concatenate on the query axis; kk and names are
        batch-invariant (same snapshot, same k)."""
        if len(parts) == 1:
            return parts[0]
        import numpy as np
        vals = np.concatenate([np.asarray(p[0]) for p in parts], axis=0)
        ids = np.concatenate([np.asarray(p[1]) for p in parts], axis=0)
        return vals, ids, parts[0][2], parts[0][3]

    def search_batch_arrays(self, queries: list[str],
                            k: int | None = None):
        """Exact top-k as raw result arrays ``(vals, ids, kk, names)``
        for wire packing (the batched-scatter serving fast path — see
        ``SearchLoop.search_arrays``), whatever the searcher family.
        Engine failures surface exactly as they do from
        ``search_batch``."""
        return self._run_compute(
            queries,
            lambda qs: self.searcher.search_arrays(qs, k=k),
            lambda qs: self._fallback.search_arrays(qs, k=k),
            merge=self._merge_arrays)

    # ---- dense plane (ISSUE 17) ----

    def search_dense_batch(self, queries: list[str],
                           k: int | None = None) -> list[list[tuple]]:
        """Exact dense top-k per query as ``[(name, score), ...]``
        (cosine, sorted by (-score, name)). Loud when the dense plane
        is off — a silent sparse fallback would fake hybrid results.
        Health-guarded but never host-served: MXU matmuls have no
        bit-exact host mirror, so dense faults surface to the router's
        failover instead of degrading silently."""
        if self.dense is None:
            raise RuntimeError(
                "dense plane disabled (embedding_enabled=False)")
        kk = int(k) if k is not None else self.config.top_k

        def run(qs):
            counts = [self.analyzer.counts(q) for q in qs]
            return self.dense.search_batch(counts, kk)

        return self._run_compute(
            queries, run, None,
            merge=lambda parts: [r for p in parts for r in p])

    def search_dense_names(self, queries: list[str],
                           names: list[str]) -> list[dict]:
        """Failover-slice dense scores: name->score per query for the
        names this engine holds (absent names are simply missing)."""
        if self.dense is None:
            raise RuntimeError(
                "dense plane disabled (embedding_enabled=False)")

        def run(qs):
            counts = [self.analyzer.counts(q) for q in qs]
            return self.dense.search_names(counts, names)

        return self._run_compute(
            queries, run, None,
            merge=lambda parts: [r for p in parts for r in p])

    def dense_stats(self) -> dict | None:
        """Embedding-column summary for /api/health and `status` — None
        when the dense plane is off."""
        return self.dense.stats() if self.dense is not None else None

    # ---- tiered postings (ISSUE 18) ----

    def tier_stats(self) -> dict:
        """Tier residency/skip summary for /api/health and `status` —
        ``{"enabled": False}`` when tiering is off so callers never
        branch on None."""
        if self.tier is None:
            return {"enabled": False}
        return self.tier.stats()

    # ---- files (Worker.workerDownload analog) ----

    def _safe_doc_path(self, rel: str) -> str:
        """Resolve under documents_path with the same traversal check as the
        reference (``Worker.java:97-121``: normalize + startsWith(base))."""
        base = os.path.abspath(self.config.documents_path)
        target = os.path.abspath(os.path.join(base, rel))
        if not (target == base or target.startswith(base + os.sep)):
            raise PermissionError(f"path escapes documents dir: {rel!r}")
        return target

    def open_document(self, rel: str) -> bytes | None:
        path = self._safe_doc_path(rel)
        if not os.path.isfile(path):
            return None
        with open(path, "rb") as f:
            return f.read()

    def open_document_stream(self, rel: str):
        """(file object, size) for chunked transfer, or None — the
        streaming analog of :meth:`open_document` (the reference serves
        ``FileSystemResource`` streams, ``Worker.java:97-121``; a
        GB-scale document must not be buffered whole per request)."""
        path = self._safe_doc_path(rel)
        if not os.path.isfile(path):
            return None
        return open(path, "rb"), os.path.getsize(path)

    # ---- load metric ----

    def index_size_bytes(self) -> int:
        return self.index.size_bytes()
