"""Benchmark: the BASELINE.md configs on the local chip.

Three configs per BASELINE.md:

* **config 3 (primary, north-star)** — 1M docs / 500k vocab, batched
  multi-query exact top-10. Corpus is synthesized directly as sorted
  (term id, tf) arrays (vectorized, Zipfian) and ingested through
  ``add_document_arrays`` — the same entry the native tokenizer feeds —
  so the measured path is index build -> ELL commit -> device scoring.
* **config 1** — 18k docs / ~60k vocab with the FULL text pipeline
  (analyzer -> vocab -> index), for ingest docs/s through the real
  tokenizer and continuity with round 1.
* **config 4 (shape)** — streaming ingest in ``index_mode="segments"``:
  sustained docs/s over 100k docs with a commit every 10k (commit cost
  O(new docs), which rebuild mode cannot do).

CPU baselines (the ``vs_baseline`` denominator is the STRONGEST one at
the same config — VERDICT r1 #5):

* scipy CSR sparse matmul over precomputed BM25 impacts — the classic
  strong CPU implementation of batched sparse scoring;
* torch sparse-CSR matmul (MKL; multithreaded where cores exist);
* the round-1 vectorized-numpy scorer (config 1 only, for continuity).

This host exposes a single CPU core; the baselines are still the best
single-core sparse kernels available, and per-core numbers are reported.

Emission is ARTIFACT-FIRST (the r5 postmortem: the full-detail stdout
line got tail-truncated and the round's headline numbers were lost):
the full result JSON is written + fsynced + re-read to ``BENCH_OUT``
(default ``BENCH_DETAIL.json``), and stdout then gets exactly ONE
compact line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "detail_file": ..., "headline": {<every config's flagship number>}}
Human-readable detail goes to stderr.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from tfidf_tpu.utils.compile_cache import configure_compile_cache

SEED = 0
TOP_K = 10

# config 3 — the north star
NS_DOCS = 1_000_000
NS_VOCAB = 500_000
NS_AVG_LEN = 120
NS_BATCH = 512      # amortizes the fixed per-batch fetch; the
                    # B-independent A-build makes bigger batches cheap
NS_BATCHES = 4
NS_CPU_BATCH = 32
NS_CPU_BATCHES = 2

# config 1 — full text pipeline
C1_DOCS = 18_000
C1_VOCAB = 60_000
C1_AVG_LEN = 150
C1_BATCH = 4096     # chunk size; chunks pipeline inside one call
                    # (fetch is RTT-bound at small corpora: 1024->6.9k,
                    # 2048->10.5k, 4096->12.6k q/s measured at 18k docs)
C1_BATCHES = 8

# config 4 shape — streaming segments (VERDICT r2 #4: >=1M docs with
# bounded commit latency; MS MARCO is 8.8M of the same shape)
ST_DOCS = 1_000_000
ST_COMMIT_EVERY = 10_000
ST_AVG_LEN = 100

# mesh serving path (engine_mode="mesh" — the shard_map psum/all_gather
# step on however many chips are attached; 1 here)
MESH_DOCS = 50_000
MESH_BATCH = 512
MESH_BATCHES = 2


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# XLA compile accounting over timed windows (ISSUE 19): every validated
# artifact stamps `xla_compiles_during_measurement` — backend compiles
# that landed INSIDE a timed window (warmup excluded by construction:
# the warmup batches run before the window opens). A steady-state
# serving window with a nonzero count means warmup no longer covers the
# served shapes — a jit-cache-discipline regression (the compile storm
# devicecheck guards statically) — and fails the bench loudly rather
# than publishing a number with compile time buried in it.
# --------------------------------------------------------------------------

_WINDOW_COMPILES = {"n": 0}


def _compile_counter():
    """Monotonic per-process XLA compile counter, shared with the
    graftcheck device witness (jax.monitoring has no unregister, so one
    listener total)."""
    from tools.graftcheck.device_witness import (compile_count,
                                                 ensure_compile_listener)
    ensure_compile_listener()
    return compile_count


class _measured_window:
    def __init__(self, what: str, steady_state: bool = False) -> None:
        self.what = what
        self.steady_state = steady_state

    def __enter__(self) -> "_measured_window":
        self._count = _compile_counter()
        self._before = self._count()
        return self

    def __exit__(self, *exc) -> None:
        if exc[0] is not None:
            return
        delta = self._count() - self._before
        _WINDOW_COMPILES["n"] += delta
        if delta:
            log(f"[compile] {delta} XLA compile(s) inside timed window "
                f"{self.what!r}")
        if self.steady_state and delta:
            print(f"BENCH SELF-VALIDATION FAILED: {delta} XLA "
                  f"compile(s) inside steady-state serving window "
                  f"{self.what!r} — warmup no longer covers the served "
                  f"shapes (jit-cache discipline regression; run "
                  f"python -m tools.graftcheck --only devicecheck)",
                  file=sys.stderr)
            sys.exit(1)


# --------------------------------------------------------------------------
# corpus synthesis
# --------------------------------------------------------------------------

def make_doc_arrays(rng, n_docs: int, vocab: int, avg_len: int):
    """Vectorized Zipfian corpus as per-doc sorted (ids, tfs) slices.

    Returns (offsets [n+1], ids [nnz], tfs [nnz], lengths [n]) where doc i
    owns ids[offsets[i]:offsets[i+1]] sorted ascending — exactly the
    ``add_document_arrays`` contract the native tokenizer produces.
    """
    lengths = np.clip(rng.poisson(avg_len, n_docs), 5, None).astype(np.int64)
    total = int(lengths.sum())
    terms = (rng.zipf(1.25, size=total) % vocab).astype(np.int64)
    doc_of = np.repeat(np.arange(n_docs, dtype=np.int64), lengths)
    # unique (doc, term) pairs + counts, all vectorized
    order = np.lexsort((terms, doc_of))
    d = doc_of[order]
    t = terms[order]
    first = np.ones(total, bool)
    first[1:] = (d[1:] != d[:-1]) | (t[1:] != t[:-1])
    idx = np.flatnonzero(first)
    counts = np.diff(np.append(idx, total))
    ud, ut = d[idx], t[idx]
    offsets = np.searchsorted(ud, np.arange(n_docs + 1))
    return (offsets, ut.astype(np.int32), counts.astype(np.float32),
            lengths.astype(np.float32))


def make_texts(rng, n_docs: int, vocab: int, avg_len: int) -> list[str]:
    """Raw-text corpus (exercises the full analyzer/vocab ingest)."""
    zipf = rng.zipf(1.25, size=n_docs * avg_len) % vocab
    lengths = np.clip(rng.poisson(avg_len, n_docs), 10, None)
    lengths = (lengths * (zipf.shape[0] / lengths.sum())).astype(np.int64)
    texts = []
    pos = 0
    for n in lengths:
        ids = zipf[pos:pos + n]
        pos += n
        texts.append(" ".join(f"t{w}" for w in ids))
    return texts


def make_queries(rng, vocab: int, n: int) -> list[str]:
    out = []
    for _ in range(n):
        k = int(rng.integers(2, 5))
        ids = rng.zipf(1.25, size=k) % vocab
        out.append(" ".join(f"t{w}" for w in ids))
    return out


# --------------------------------------------------------------------------
# config 3: north star — 1M docs / 500k vocab
# --------------------------------------------------------------------------

def bench_north_star(rng, corpus=None) -> dict:
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    t0 = time.perf_counter()
    offsets, ids, tfs, lengths = corpus if corpus is not None else \
        make_doc_arrays(rng, NS_DOCS, NS_VOCAB, NS_AVG_LEN)
    nnz = ids.shape[0]
    log(f"[ns] corpus: {NS_DOCS} docs, nnz={nnz}, "
        f"gen {time.perf_counter()-t0:.1f}s")

    engine = Engine(Config(query_batch=NS_BATCH))
    t0 = time.perf_counter()
    for i in range(NS_VOCAB):
        engine.vocab.add(f"t{i}")
    log(f"[ns] vocab registered in {time.perf_counter()-t0:.1f}s")

    t0 = time.perf_counter()
    add = engine.index.add_document_arrays
    for i in range(NS_DOCS):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
    ingest_s = time.perf_counter() - t0
    log(f"[ns] indexed {NS_DOCS} docs in {ingest_s:.1f}s "
        f"({NS_DOCS/ingest_s:.0f} docs/s, direct arrays)")

    t0 = time.perf_counter()
    engine.commit()
    commit_s = time.perf_counter() - t0
    log(f"[ns] commit (COO->blocked ELL->device): {commit_s:.1f}s")

    queries = make_queries(rng, NS_VOCAB, NS_BATCH * (NS_BATCHES + 2))
    # warmup: 2 distinct batches (compiles + ratchets the u_cap floor)
    engine.search_batch(queries[:NS_BATCH], k=TOP_K)
    engine.search_batch(queries[NS_BATCH:2 * NS_BATCH], k=TOP_K)
    # ONE call over NS_BATCHES chunks: the searcher pipelines chunk i+1's
    # device program under chunk i's fetch + hit assembly
    timed = queries[2 * NS_BATCH:(NS_BATCHES + 2) * NS_BATCH]
    with _measured_window("ns-serving", steady_state=True):
        t0 = time.perf_counter()
        engine.search_batch(timed, k=TOP_K)
        qps = len(timed) / (time.perf_counter() - t0)
    log(f"[ns] {len(timed)} queries -> {qps:.1f} q/s "
        f"(batch={NS_BATCH}, pipelined)")

    parity_checked = oracle_topk_parity(engine, offsets, ids, tfs,
                                        lengths, queries[:256], NS_VOCAB)

    cpu = cpu_baselines(offsets, ids, tfs, lengths, queries, NS_VOCAB,
                        n_batches=NS_CPU_BATCHES, batch=NS_CPU_BATCH,
                        numpy_loop=False)
    return {"qps": qps, "ingest_dps": NS_DOCS / ingest_s,
            "commit_s": commit_s, "nnz": int(nnz),
            "parity_checked": parity_checked, **cpu}


def oracle_topk_parity(engine, offsets, ids, tfs, lengths, queries,
                       vocab_size: int) -> bool:
    """Top-10 parity of the device path vs a scipy-CSR oracle on the
    SAME corpus (VERDICT r2 #6): a wrong-but-fast kernel must fail the
    bench loudly, not set a record. Compares score-sets per query
    (modulo tie order) at f32-friendly tolerance."""
    import scipy.sparse as sp

    n_docs = offsets.shape[0] - 1
    row, impact = _impacts(offsets, ids, tfs, lengths)
    M = sp.csr_matrix((impact, (row, ids.astype(np.int64))),
                      shape=(n_docs, vocab_size))
    qmat = _parse_queries(queries, vocab_size)
    scores = np.asarray((M @ sp.csr_matrix(qmat.T)).todense()).T
    got = engine.search_batch(queries, k=TOP_K)
    for i, hits in enumerate(got):
        want = np.sort(scores[i])[::-1][:TOP_K]
        want = want[want > 0]
        have = np.asarray([h.score for h in hits], np.float32)
        assert have.shape[0] == want.shape[0], \
            (i, have.shape, want.shape)
        # rtol covers f32-vs-f64 arithmetic drift (~3e-4 uniform);
        # real bugs (wrong df, wrong doc ids) are orders of magnitude
        np.testing.assert_allclose(have, want, rtol=2e-3, atol=1e-4,
                                   err_msg=f"query {i} top-k mismatch")
        # the returned documents must score what the oracle says they
        # score: re-derive each hit's oracle score by name
        for h in hits:
            d = int(h.name[1:])
            np.testing.assert_allclose(
                h.score, scores[i, d], rtol=2e-3, atol=1e-4,
                err_msg=f"query {i} doc {h.name}")
    log(f"[ns] oracle top-{TOP_K} parity OK on {len(queries)} queries "
        f"at {n_docs} docs")
    return True


# --------------------------------------------------------------------------
# CPU baselines: scipy CSR + torch sparse CSR (strongest wins)
# --------------------------------------------------------------------------

def _impacts(offsets, ids, tfs, lengths):
    """Precomputed per-entry BM25 impacts (generous to the baseline: the
    device side recomputes query weighting per batch)."""
    n_docs = offsets.shape[0] - 1
    counts = np.diff(offsets)
    row = np.repeat(np.arange(n_docs, dtype=np.int32), counts)
    df = np.bincount(ids, minlength=int(ids.max()) + 1).astype(np.float32)
    avgdl = lengths.mean()
    k1, b = 1.2, 0.75
    idf = np.log1p((n_docs - df + 0.5) / (df + 0.5))
    denom = tfs + k1 * (1 - b + b * lengths[row] / avgdl)
    return row, (idf[ids] * tfs / denom).astype(np.float32)


def _parse_queries(queries, vocab_size):
    """Query batch as a dense [B, V] matrix (term multiplicity weights)."""
    B = len(queries)
    qmat = np.zeros((B, vocab_size), np.float32)
    for i, q in enumerate(queries):
        for tok in q.split():
            tid = int(tok[1:])
            if 0 <= tid < vocab_size:
                qmat[i, tid] += 1.0
    return qmat


def cpu_baselines(offsets, ids, tfs, lengths, queries, vocab_size,
                  *, n_batches: int, batch: int,
                  numpy_loop: bool) -> dict:
    import scipy.sparse as sp

    n_docs = offsets.shape[0] - 1
    row, impact = _impacts(offsets, ids, tfs, lengths)
    M = sp.csr_matrix((impact, (row, ids.astype(np.int64))),
                      shape=(n_docs, vocab_size))
    out: dict = {}

    def timed(name, run):
        run(queries[:batch])   # warm
        t0 = time.perf_counter()
        total = 0
        for b in range(1, n_batches + 1):
            chunk = queries[b * batch:(b + 1) * batch]
            run(chunk)
            total += len(chunk)
        qps = total / (time.perf_counter() - t0)
        log(f"[cpu] {name}: {qps:.2f} q/s (batch={batch})")
        out[name] = qps

    def scipy_run(qs):
        qmat = _parse_queries(qs, vocab_size)
        scores = M @ qmat.T                      # [n_docs, B] dense
        k = min(TOP_K, n_docs - 1)
        return np.argpartition(-scores, k, axis=0)[:k]

    timed("scipy_csr_qps", scipy_run)

    try:
        import torch
        Mt = torch.sparse_csr_tensor(
            torch.from_numpy(M.indptr.astype(np.int64)),
            torch.from_numpy(M.indices.astype(np.int64)),
            torch.from_numpy(M.data),
            size=M.shape)

        def torch_run(qs):
            qmat = torch.from_numpy(_parse_queries(qs, vocab_size))
            scores = torch.matmul(Mt, qmat.T)
            return torch.topk(scores, min(TOP_K, n_docs - 1), dim=0)

        timed("torch_csr_qps", torch_run)
    except Exception as e:   # torch sparse availability varies
        log(f"[cpu] torch baseline skipped: {e!r}")

    if numpy_loop:
        def numpy_run(qs):
            qmat = _parse_queries(qs, vocab_size)
            contrib = impact[None, :] * qmat[:, ids]     # [B, nnz]
            scores = np.zeros((len(qs), n_docs), np.float32)
            for i in range(len(qs)):
                np.add.at(scores[i], row, contrib[i])
            return np.argpartition(-scores, TOP_K, axis=1)[:, :TOP_K]

        timed("numpy_loop_qps", numpy_run)

    out["best_cpu_qps"] = max(v for k, v in out.items() if k.endswith("qps"))
    return out


# --------------------------------------------------------------------------
# config 1: full text pipeline at 18k docs
# --------------------------------------------------------------------------

def bench_config1(rng) -> dict:
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    t0 = time.perf_counter()
    texts = make_texts(rng, C1_DOCS, C1_VOCAB, C1_AVG_LEN)
    queries = make_queries(rng, C1_VOCAB, C1_BATCH * (C1_BATCHES + 2))
    log(f"[c1] corpus+queries in {time.perf_counter()-t0:.1f}s")

    engine = Engine(Config(query_batch=C1_BATCH))
    # pass 1 (untimed) warms XLA compiles for these capacity buckets
    for i, text in enumerate(texts):
        engine.ingest_text(f"doc{i}", text)
    engine.commit()
    # pass 2 (timed): steady-state re-ingest (idempotent upserts) + commit
    t0 = time.perf_counter()
    for i, text in enumerate(texts):
        engine.ingest_text(f"doc{i}", text)
    ingest_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine.commit()
    commit_s = time.perf_counter() - t0
    log(f"[c1] text-indexed {C1_DOCS} docs in {ingest_s:.2f}s "
        f"({C1_DOCS/ingest_s:.0f} docs/s), warm commit {commit_s:.2f}s")

    engine.search_batch(queries[:C1_BATCH], k=TOP_K)
    engine.search_batch(queries[C1_BATCH:2 * C1_BATCH], k=TOP_K)
    timed = queries[2 * C1_BATCH:(C1_BATCHES + 2) * C1_BATCH]
    with _measured_window("c1-serving", steady_state=True):
        t0 = time.perf_counter()
        engine.search_batch(timed, k=TOP_K)
        qps = len(timed) / (time.perf_counter() - t0)
    log(f"[c1] {len(timed)} queries -> {qps:.1f} q/s "
        f"(batch={C1_BATCH}, pipelined)")

    # rebuild the same corpus as arrays for the CPU baselines
    entries = engine.index.live_entries()
    offsets = np.zeros(len(entries) + 1, np.int64)
    for i, d in enumerate(entries):
        offsets[i + 1] = offsets[i] + d.term_ids.shape[0]
    ids = np.concatenate([d.term_ids for d in entries])
    tfs = np.concatenate([d.tfs for d in entries])
    lengths = np.asarray([d.length for d in entries], np.float32)
    # queries reference t<id> names; map through the engine's vocab so the
    # baseline sees the same ids
    remap = {}
    for tid in range(len(engine.vocab)):
        term = engine.vocab.term(tid)
        if term.startswith("t") and term[1:].isdigit():
            remap[term] = tid
    q_mapped = [" ".join(f"t{remap[tok]}" for tok in q.split()
                         if tok in remap) for q in queries]
    cpu = cpu_baselines(offsets, ids, tfs, lengths, q_mapped,
                        len(engine.vocab) + 1,
                        n_batches=2, batch=512, numpy_loop=True)
    return {"qps": qps, "text_ingest_dps": C1_DOCS / ingest_s,
            "warm_commit_s": commit_s, **cpu}


# --------------------------------------------------------------------------
# config 4 shape: streaming segments
# --------------------------------------------------------------------------

def bench_streaming(rng, corpus=None) -> dict:
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    offsets, ids, tfs, lengths = corpus if corpus is not None else \
        make_doc_arrays(rng, ST_DOCS, NS_VOCAB, ST_AVG_LEN)
    n_docs = offsets.shape[0] - 1
    engine = Engine(Config(index_mode="segments", query_batch=64))
    t0 = time.perf_counter()
    for i in range(NS_VOCAB):
        engine.vocab.add(f"t{i}")
    log(f"[st] vocab in {time.perf_counter()-t0:.1f}s")

    t0 = time.perf_counter()
    add = engine.index.add_document_arrays
    commit_ms = []
    for i in range(n_docs):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
        if (i + 1) % ST_COMMIT_EVERY == 0:
            c0 = time.perf_counter()
            engine.commit()
            commit_ms.append((time.perf_counter() - c0) * 1e3)
    total_s = time.perf_counter() - t0
    # quiesce: drain the background merge backlog (untimed — it ran off
    # the write path; the sustained rate above is what streaming sees)
    q0 = time.perf_counter()
    for _ in range(32):
        engine.index.wait_for_merges()
        engine.commit()
        if len(engine.index._segments) <= engine.config.max_segments \
                and engine.index._merge_future is None:
            break
    quiesce_s = time.perf_counter() - q0
    cm = np.asarray(commit_ms)
    p50, p99, mx = (float(np.percentile(cm, 50)),
                    float(np.percentile(cm, 99)), float(cm.max()))
    log(f"[st] streamed {n_docs} docs in {total_s:.1f}s "
        f"({n_docs/total_s:.0f} docs/s sustained, {len(commit_ms)} "
        f"commits: p50 {p50:.0f}ms p99 {p99:.0f}ms max {mx:.0f}ms)")
    hits = engine.search("t17 t4242")
    assert hits, "streaming index must answer queries"
    return {"streaming_dps": round(n_docs / total_s, 1),
            "n_docs": n_docs,
            "commit_ms_p50": round(p50, 1),
            "commit_ms_p99": round(p99, 1),
            "commit_ms_max": round(mx, 1),
            "quiesce_s": round(quiesce_s, 1),
            "segments": len(engine.index.snapshot.segments)}


def bench_mesh(rng) -> dict:
    """The distributed serving path (MeshIndex/MeshSearcher) on the real
    chip(s): same step the cluster node serves (VERDICT r1 #1 'bench.py
    exercises it on the real chip'). Reports the cold commit (host ELL
    build + jit compiles, one-time) separately from the steady-state
    commit (append a batch into the COO delta + refresh impacts — the
    serving-path cost)."""
    import jax

    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    offsets, ids, tfs, lengths = make_doc_arrays(
        rng, MESH_DOCS + 200, NS_VOCAB, ST_AVG_LEN)
    engine = Engine(Config(engine_mode="mesh", query_batch=MESH_BATCH))
    for i in range(NS_VOCAB):
        engine.vocab.add(f"t{i}")
    add = engine.index.add_document_arrays
    for i in range(MESH_DOCS):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
    t0 = time.perf_counter()
    engine.commit()
    commit_cold_s = time.perf_counter() - t0
    # steady state: append 100 docs into the delta, commit (first one
    # pays the ingest-program compile; the second is the real cost)
    for j in range(2):
        for i in range(MESH_DOCS + 100 * j, MESH_DOCS + 100 * (j + 1)):
            lo, hi = offsets[i], offsets[i + 1]
            add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
        t0 = time.perf_counter()
        engine.commit()
        commit_steady_s = time.perf_counter() - t0
    queries = make_queries(rng, NS_VOCAB,
                           MESH_BATCH * (MESH_BATCHES + 2))
    engine.search_batch(queries[:MESH_BATCH], k=TOP_K)
    engine.search_batch(queries[MESH_BATCH:2 * MESH_BATCH], k=TOP_K)
    timed = queries[2 * MESH_BATCH:(MESH_BATCHES + 2) * MESH_BATCH]
    with _measured_window("mesh-serving", steady_state=True):
        t0 = time.perf_counter()
        engine.search_batch(timed, k=TOP_K)
        qps = len(timed) / (time.perf_counter() - t0)
    log(f"[mesh] {MESH_DOCS} docs on {len(jax.devices())} device(s): "
        f"{qps:.0f} q/s, commit cold {commit_cold_s:.1f}s / steady "
        f"{commit_steady_s*1e3:.0f}ms")
    # the DISTRIBUTED path gets its own oracle gate: the round-2 wire
    # bug returned wrong doc ids exactly here, and the local-path check
    # would not have seen it. The oracle corpus is the committed state
    # (base + both appended delta batches).
    n_all = MESH_DOCS + 200
    parity = oracle_topk_parity(
        engine, offsets[:n_all + 1], ids[:offsets[n_all]],
        tfs[:offsets[n_all]], lengths[:n_all], queries[:64], NS_VOCAB)
    return {"qps": round(qps, 1), "commit_cold_s": round(commit_cold_s, 1),
            "commit_steady_ms": round(commit_steady_s * 1e3, 1),
            "parity_checked": parity,
            "devices": len(jax.devices()), "n_docs": MESH_DOCS}


# --------------------------------------------------------------------------
# shared cluster-bench plumbing (configs 2 and 2b)
# --------------------------------------------------------------------------

def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http_get(url: str, timeout: float = 10.0) -> bytes:
    import urllib.request
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read()


def _wait_until(pred, timeout: float = 240.0) -> None:
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            if pred():
                return
        except Exception as e:
            last = e
        time.sleep(0.3)
    raise AssertionError(f"timeout; last={last!r}")


class _KeepAlive:
    """One persistent HTTP connection per (thread, port); one retry on a
    dropped keep-alive connection."""

    def __init__(self) -> None:
        import threading
        self._tls = threading.local()

    def post(self, hostport: tuple[str, int], path: str, data: bytes,
             timeout: float = 600.0) -> bytes:
        return self.post_full(hostport, path, data, timeout=timeout)[2]

    def post_full(self, hostport: tuple[str, int], path: str,
                  data: bytes, timeout: float = 600.0,
                  headers: dict | None = None
                  ) -> tuple[int, dict, bytes]:
        """(status, response headers, body) — the overload bench needs
        to see 429 sheds and their Retry-After instead of just bytes."""
        import http.client
        key = f"conn_{hostport[1]}"
        last: Exception | None = None
        for _ in range(2):
            c = getattr(self._tls, key, None)
            try:
                if c is None:
                    import socket as _socket
                    c = http.client.HTTPConnection(*hostport,
                                                   timeout=timeout)
                    # connect inside the try: a transient refusal must
                    # take the retry path, not escape as a bare OSError
                    c.connect()
                    c.sock.setsockopt(_socket.IPPROTO_TCP,
                                      _socket.TCP_NODELAY, 1)
                    setattr(self._tls, key, c)
                h = {"Content-Type": "application/octet-stream"}
                h.update(headers or {})
                c.request("POST", path, body=data, headers=h)
                r = c.getresponse()
                body = r.read()
                hdrs = dict(r.getheaders())
                if r.status == 429 and r.will_close:
                    # the shed path closes the connection (the leader
                    # holds no keep-alive state for a client it just
                    # turned away) — drop ours too
                    c.close()
                    setattr(self._tls, key, None)
                return r.status, hdrs, body
            except Exception as e:
                last = e
                c.close()
                setattr(self._tls, key, None)
        # keep the cause: a timeout, a reset, and an HTTP error need
        # different fixes, and a bare "post failed" hides which happened
        raise RuntimeError(f"post {path} failed") from last


def _kill_all(procs) -> None:
    """SIGTERM first (a node releases its chip and leaves the cluster
    on it), SIGKILL whatever has not exited after 10 s; every child is
    reaped before this returns."""
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()


# --------------------------------------------------------------------------
# config 2: 2-worker cluster, real HTTP scatter-gather (VERDICT r2 #3a)
# --------------------------------------------------------------------------

C2_DOCS = 100_000
C2_VOCAB = 200_000
C2_AVG_LEN = 80
C2_QUERIES = 192
C2_CLIENTS = 8


def bench_cluster(rng) -> dict:
    """End-to-end cluster data plane: a from-scratch coordination
    service + 3 node processes (leader + 2 workers) over real HTTP,
    measuring bulk upload throughput and /leader/start QPS — the
    reference's own serving shape (Leader.java:39-92). Node processes
    are pinned to the CPU backend (a chip belongs to one process, and
    the parent may hold it): this config measures the DATA PLANE
    (scatter-gather, JSON merge, placement), not kernel speed."""
    import concurrent.futures
    import json as _json
    import socket
    import subprocess
    import tempfile

    t0 = time.perf_counter()
    texts = make_texts(rng, C2_DOCS, C2_VOCAB, C2_AVG_LEN)
    queries = make_queries(rng, C2_VOCAB, 2 * C2_QUERIES)
    log(f"[c2] corpus in {time.perf_counter()-t0:.0f}s")

    env = dict(os.environ, TFIDF_JAX_PLATFORM="cpu", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    procs = []
    tmp = tempfile.mkdtemp(prefix="bench_c2_")

    def spawn(args):
        p = subprocess.Popen(
            [sys.executable, "-m", "tfidf_tpu", *args], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        procs.append(p)
        return p

    client = _KeepAlive()
    try:
        coord = _free_port()
        spawn(["coordinator", "--listen", f"127.0.0.1:{coord}"])
        _wait_until(lambda: socket.create_connection(
            ("127.0.0.1", coord), timeout=1).close() or True)
        ports = [_free_port() for _ in range(3)]
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        for i, port in enumerate(ports):
            spawn(["serve", "--port", str(port), "--host", "127.0.0.1",
                   "--coordinator-address", f"127.0.0.1:{coord}",
                   "--documents-path", f"{tmp}/n{i}/docs",
                   "--index-path", f"{tmp}/n{i}/index"])
            _wait_until(lambda u=urls[i]: _http_get(u + "/api/status"))
        leader = urls[0]
        leader_hp = ("127.0.0.1", ports[0])
        _wait_until(lambda: len(_json.loads(
            _http_get(leader + "/api/services"))) == 2)

        groups = [[{"name": f"d{i}.txt", "text": texts[i]}
                   for i in range(lo, min(lo + 500, C2_DOCS))]
                  for lo in range(0, C2_DOCS, 500)]
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(C2_CLIENTS) as ex:
            list(ex.map(
                lambda g: client.post(leader_hp, "/leader/upload-batch",
                                      _json.dumps(g).encode()),
                groups))
        upload_s = time.perf_counter() - t0
        log(f"[c2] uploaded {C2_DOCS} docs via HTTP (batched) in "
            f"{upload_s:.0f}s ({C2_DOCS/upload_s:.0f} docs/s)")

        def start(q):
            return client.post(leader_hp, "/leader/start", q.encode())

        # two warm rounds: the first pays worker XLA compiles for every
        # micro-batch bucket the arrival pattern produces
        for r in range(2):
            with concurrent.futures.ThreadPoolExecutor(C2_CLIENTS) as ex:
                list(ex.map(start, queries[:C2_QUERIES]))
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(C2_CLIENTS) as ex:
            list(ex.map(start, queries[C2_QUERIES:2 * C2_QUERIES]))
        qps = C2_QUERIES / (time.perf_counter() - t0)
        lat0 = time.perf_counter()
        start(queries[0])
        lat_ms = (time.perf_counter() - lat0) * 1e3
        log(f"[c2] /leader/start: {qps:.1f} q/s with {C2_CLIENTS} "
            f"clients, single-query latency {lat_ms:.0f}ms")
        return {"qps": round(qps, 1), "upload_dps": round(
                    C2_DOCS / upload_s, 1),
                "latency_ms": round(lat_ms, 1), "n_docs": C2_DOCS,
                "workers": 2, "backend": "cpu (nodes pinned)"}
    finally:
        _kill_all(procs)


# --------------------------------------------------------------------------
# overload: zipfian closed-loop load generator at 1x / 2x capacity
# (ISSUE 7 tentpole; ROADMAP item 2 — "report p50/p99 under 2x-overload,
# not just peak q/s")
# --------------------------------------------------------------------------

OV_DOCS = 20_000
OV_VOCAB = 50_000
OV_AVG_LEN = 60
OV_QUERY_POOL = 2_048       # distinct queries; zipf skew over the pool
OV_ZIPF_S = 1.1             # skew exponent (web-search-like popularity)
OV_TAIL_UNIQUE = 0.3        # fraction of requests carrying a unique
                            # (never-repeating) query — the long tail a
                            # real user population produces, which no
                            # cache can absorb
OV_CACHE_ENTRIES = 512      # < pool size: sustained misses, LRU churn
OV_BASE_CLIENTS = 8         # closed-loop interactive concurrency that
                            # saturates the 2-worker CPU topology (the
                            # "1x" load)
OV_BULK_CLIENTS = 2         # per-phase bulk-lane clients (X-Priority:
                            # bulk) — first to shed under backpressure
OV_PHASE_S = 12.0


def _zipf_indices(rng, pool: int, n: int, s: float = OV_ZIPF_S):
    w = 1.0 / np.arange(1, pool + 1) ** s
    return rng.choice(pool, size=n, p=w / w.sum())


# utils/metrics.py bucket geometry: the live histogram's quantile
# estimate is within one bucket ratio of truth by construction, so the
# cross-check tolerance is TWO ratio steps (estimate error on both
# sides). The server-side histogram measures HANDLER time while the
# client measures end-to-end, so live may legitimately sit BELOW
# client by transport/queue overhead — the lower bound therefore only
# has teeth once the percentile is large enough that overhead is
# proportionally small; below the floor it is explicitly skipped (and
# reported as such) instead of being silently neutered by slack.
_HIST_BUCKET_RATIO = 1.2
_HIST_LOWER_FLOOR_MS = 50.0


def _live_quantile_crosscheck(client_lats_s: list, live_snap: dict
                              ) -> dict:
    """Compare bench-measured p50/p99 (client side, every admitted
    /leader/start across all phases and lanes) against the leader's
    LIVE histogram quantiles (``leader_search_p50_ms``/``p99_ms`` from
    the /api/metrics snapshot). Raises — failing the artifact emission
    — on disagreement beyond bucket-resolution error: an artifact
    whose live-percentile pipeline cannot reproduce the bench's own
    distribution is reporting numbers nobody should trust. The UPPER
    bound (live must not exceed client) always applies — the server
    cannot see more latency than the client did; the LOWER bound
    applies only above ``_HIST_LOWER_FLOOR_MS``."""
    ls = sorted(client_lats_s)
    if not ls:
        raise RuntimeError("[ov] no admitted latencies to cross-check")
    out = {}
    tol = _HIST_BUCKET_RATIO ** 2
    for label, q in (("p50", 0.5), ("p99", 0.99)):
        client_ms = ls[min(len(ls) - 1, int(len(ls) * q))] * 1e3
        live_ms = float(live_snap.get(f"leader_search_{label}_ms", 0.0))
        lower_checked = client_ms >= _HIST_LOWER_FLOOR_MS
        ok = (live_ms > 0.0 and live_ms <= client_ms * tol
              and (not lower_checked or live_ms >= client_ms / tol))
        out[label] = {"client_ms": round(client_ms, 1),
                      "live_ms": round(live_ms, 1),
                      "lower_bound_checked": lower_checked,
                      "ok": bool(ok)}
    if not all(v["ok"] for v in out.values()):
        raise RuntimeError(
            f"[ov] live histogram quantiles disagree with the bench's "
            f"measured distribution beyond bucket resolution: {out}")
    return out


def bench_overload(rng, autopilot: bool = False,
                   corpus: tuple | None = None) -> dict:
    """Closed-loop zipfian overload against the admission front door
    (cluster/admission.py) — which is a stateless ROUTER
    (cluster/router.py), the deployed topology's query plane: N
    clients per phase, each posting /leader/start as fast as replies
    come back, query popularity zipf-skewed over a fixed pool (the
    result cache's natural prey). Phases run at 1x and 2x the
    saturating concurrency; per phase we report p50/p99 latency of
    ADMITTED interactive queries, shed rate (429s / offered),
    throughput, and cache hit rate. The contract under test: at 2x
    the front door sheds EXPLICITLY (429 + Retry-After, clients honor
    the hint) instead of queueing unboundedly, so admitted-query p99
    stays within ~2x of the 1x p99.

    ``autopilot=True`` runs the SAME workload with the hand-tuned
    admission watermarks REMOVED and the SLO autopilot enabled at fast
    cadence instead (cluster/autopilot.py): the cluster starts from
    generic defaults and must derive its own watermarks/hedge/linger/
    slow-trip values from its live histograms. One extra 2x warm phase
    lets the controllers converge before the measured phases (the
    static run's warm phases pay XLA compiles + cache fill the same
    way); the final knob values + adjustment audit ride the result."""
    import concurrent.futures
    import json as _json
    import socket
    import subprocess
    import tempfile
    import threading

    if corpus is None:
        t0 = time.perf_counter()
        texts = make_texts(rng, OV_DOCS, OV_VOCAB, OV_AVG_LEN)
        queries = make_queries(rng, OV_VOCAB, OV_QUERY_POOL)
        log(f"[ov] corpus in {time.perf_counter()-t0:.0f}s")
    else:
        texts, queries = corpus

    env = dict(os.environ, TFIDF_JAX_PLATFORM="cpu", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update({
        # overload knobs: a small scatter batch bounds per-RPC work, so
        # queue depth (the backpressure signal) reflects genuine
        # oversubscription
        "TFIDF_SCATTER_BATCH": "4",
        "TFIDF_RESULT_CACHE_ENTRIES": str(OV_CACHE_ENTRIES),
        # the ROUTER is the measured front door now (ISSUE 16: the
        # scale-out topology is the deployed one) — its cache gets the
        # same bound as the leader's had, so the lineage is comparable
        "TFIDF_ROUTER_CACHE_ENTRIES": str(OV_CACHE_ENTRIES),
    })
    if autopilot:
        env.update({
            # NO hand-tuned watermarks: the autopilot starts from the
            # generic Config defaults (128/512 — sized for nothing in
            # particular) and must earn the 2x story itself. What IS
            # set is the operator-owned envelope, like deploy/k8s.yaml
            # sets its own: the SLO, the cadence, and the clamp floor
            # scaled to this topology's tiny scatter batch (4 vs the
            # default 128) — with the default floor of 4 the derived
            # critical mark (floor x the static 512/128 ratio = 16)
            # could never engage interactive shedding here, leaving
            # the controller without authority over the one lever
            # that bounds the admitted tail at saturation.
            "TFIDF_AUTOPILOT_ENABLED": "true",
            "TFIDF_AUTOPILOT_INTERVAL_MS": "500",
            "TFIDF_AUTOPILOT_MIN_WINDOW": "8",
            "TFIDF_AUTOPILOT_P99_SLO_MS": "500",
            "TFIDF_AUTOPILOT_QUEUE_FLOOR": "2",
            # the oscillation audit below must see the WHOLE run's
            # decisions — the default 256-record ring could evict
            # early-phase adjustments and understate flapping
            "TFIDF_AUTOPILOT_RING": "8192",
            "TFIDF_RECONCILE_SWEEP_INTERVAL_S": "0.25",
        })
    else:
        env.update({
            # the hand-tuned constants:
            # watermarks sized to the batch — one extra batch queued
            # sheds bulk, two shed interactive
            "TFIDF_ADMISSION_QUEUE_HIGH_WATER": "3",
            "TFIDF_ADMISSION_QUEUE_CRITICAL": "8",
        })
    procs = []
    tmp = tempfile.mkdtemp(prefix="bench_ov_")

    def spawn(args):
        p = subprocess.Popen(
            [sys.executable, "-m", "tfidf_tpu", *args], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        procs.append(p)
        return p

    client = _KeepAlive()
    all_lats: list[float] = []   # every admitted /leader/start latency
    #                              (all phases, both lanes) — compared
    #                              against the leader's LIVE histogram
    #                              quantiles after the run
    try:
        coord = _free_port()
        spawn(["coordinator", "--listen", f"127.0.0.1:{coord}"])
        _wait_until(lambda: socket.create_connection(
            ("127.0.0.1", coord), timeout=1).close() or True)
        ports = [_free_port() for _ in range(3)]
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        for i, port in enumerate(ports):
            spawn(["serve", "--port", str(port), "--host", "127.0.0.1",
                   "--coordinator-address", f"127.0.0.1:{coord}",
                   "--documents-path", f"{tmp}/n{i}/docs",
                   "--index-path", f"{tmp}/n{i}/index"])
            _wait_until(lambda u=urls[i]: _http_get(u + "/api/status"))
        leader = urls[0]
        leader_hp = ("127.0.0.1", ports[0])
        _wait_until(lambda: len(_json.loads(
            _http_get(leader + "/api/services"))) == 2)
        # the router front door: clients talk to the stateless query
        # plane, exactly like the deployed topology (deploy/k8s.yaml)
        # — admission, result cache, and the measured histograms all
        # live at the router now, and the autopilot run steers the
        # ROUTER's knobs (it carries its own control loop)
        rport = _free_port()
        spawn(["router", "--port", str(rport), "--host", "127.0.0.1",
               "--coordinator", f"127.0.0.1:{coord}"])
        front = f"http://127.0.0.1:{rport}"
        front_hp = ("127.0.0.1", rport)
        _wait_until(lambda: _http_get(front + "/api/health"))

        groups = [[{"name": f"d{i}.txt", "text": texts[i]}
                   for i in range(lo, min(lo + 500, OV_DOCS))]
                  for lo in range(0, OV_DOCS, 500)]
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            list(ex.map(
                lambda g: client.post(leader_hp, "/leader/upload-batch",
                                      _json.dumps(g).encode()),
                groups))
        log(f"[ov] uploaded {OV_DOCS} docs in "
            f"{time.perf_counter()-t0:.0f}s")
        # the router's placement view must cover the corpus before the
        # front door is the measured path
        _wait_until(lambda: client.post_full(
            front_hp, "/leader/start", b"warmup")[0] == 200)

        def metrics():
            # the FRONT DOOR's metrics: admission, cache, and the
            # leader_search histogram are all observed at the router
            return _json.loads(_http_get(front + "/api/metrics"))

        def run_phase(mult: int, seconds: float = OV_PHASE_S) -> dict:
            n_inter = OV_BASE_CLIENTS * mult
            n_bulk = OV_BULK_CLIENTS * mult
            # per-lane [admitted lats], [shed count, retry-after sum]
            lats = {"interactive": [], "bulk": []}
            sheds = {"interactive": [0, 0.0], "bulk": [0, 0.0]}
            errors: list[str] = []
            lock = threading.Lock()
            m0 = metrics()
            stop_at = time.monotonic() + seconds

            def one_client(cid: int, lane: str):
                crng = np.random.default_rng(SEED + 1000 * mult + cid)
                idx = _zipf_indices(crng, OV_QUERY_POOL, 4096)
                hdrs_out = {"X-Client-Id": f"ov{lane}{cid}"}
                if lane == "bulk":
                    hdrs_out["X-Priority"] = "bulk"
                i = 0
                while time.monotonic() < stop_at:
                    q = queries[idx[i % len(idx)]]
                    if crng.random() < OV_TAIL_UNIQUE:
                        # score-neutral OOV nonce: a unique query the
                        # cache can never answer (the realistic tail)
                        q = f"{q} zztail{mult}x{cid}x{i}"
                    i += 1
                    t1 = time.monotonic()
                    try:
                        status, hdrs, _body = client.post_full(
                            front_hp, "/leader/start", q.encode(),
                            timeout=60.0, headers=hdrs_out)
                    except Exception as e:
                        errors.append(repr(e))
                        return
                    if status == 200:
                        dt = time.monotonic() - t1
                        with lock:
                            lats[lane].append(dt)
                    elif status == 429:
                        ra = min(float(hdrs.get("Retry-After", 0.05)),
                                 0.5)
                        with lock:
                            sheds[lane][0] += 1
                            sheds[lane][1] += ra
                        time.sleep(ra)   # the polite client backs off
                    else:
                        errors.append(f"status {status}")
                        return

            threads = [threading.Thread(target=one_client,
                                        args=(i, "interactive"),
                                        daemon=True)
                       for i in range(n_inter)]
            threads += [threading.Thread(target=one_client,
                                         args=(i, "bulk"), daemon=True)
                        for i in range(n_bulk)]
            t1 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=seconds + 120)
            wall = time.perf_counter() - t1
            if errors:
                raise RuntimeError(f"[ov] phase {mult}x client "
                                   f"failures: {errors[:3]}")
            m1 = metrics()

            def lane_stats(lane):
                ls = sorted(lats[lane])
                n = len(ls)
                shed_n, ra_sum = sheds[lane]
                offered = n + shed_n
                return {
                    "admitted": n,
                    "shed": shed_n,
                    "shed_rate": round(shed_n / offered, 4)
                    if offered else 0.0,
                    "qps": round(n / wall, 1),
                    "p50_ms": round(ls[n // 2] * 1e3, 1) if n else 0.0,
                    "p99_ms": round(ls[int(n * 0.99)] * 1e3, 1)
                    if n else 0.0,
                    "mean_retry_after_s": round(ra_sum / shed_n, 3)
                    if shed_n else 0.0,
                }

            all_lats.extend(lats["interactive"])
            all_lats.extend(lats["bulk"])
            hits = m1.get("cache_hits", 0) - m0.get("cache_hits", 0)
            misses = m1.get("cache_misses", 0) - m0.get("cache_misses",
                                                        0)
            out = {
                "clients_interactive": n_inter,
                "clients_bulk": n_bulk,
                "interactive": lane_stats("interactive"),
                "bulk": lane_stats("bulk"),
                "cache_hit_rate": round(hits / (hits + misses), 4)
                if (hits + misses) else 0.0,
            }
            it = out["interactive"]
            log(f"[ov] {mult}x ({n_inter}+{n_bulk}b clients): "
                f"{it['qps']} q/s admitted interactive, "
                f"p50 {it['p50_ms']}ms, p99 {it['p99_ms']}ms, "
                f"shed int {it['shed_rate']:.1%} / "
                f"bulk {out['bulk']['shed_rate']:.1%}, "
                f"cache hit {out['cache_hit_rate']:.1%}")
            return out

        # two warm rounds: the first pays worker XLA compiles for every
        # micro-batch bucket the arrival pattern produces, the second
        # fills the cache head
        run_phase(1, seconds=6.0)
        run_phase(1, seconds=6.0)
        if autopilot:
            # convergence warm: one 2x round so the controllers have
            # seen overload before the measured phases (the measured
            # numbers are the CONVERGED steady state, exactly like the
            # static run's warm rounds exclude compile/cache fill) —
            # then a 1x settle round so the measured 1x baseline does
            # not inherit the overload round's residue (open slow-trip
            # breakers, queued work): the ratio's denominator must be
            # a clean steady state, not a recovering one
            run_phase(2, seconds=6.0)
            run_phase(1, seconds=6.0)
        one_x = run_phase(1)
        two_x = run_phase(2)
        m = metrics()
        auto = None
        if autopilot:
            # the FRONT DOOR's control loop is the one under test now
            ap = _json.loads(_http_get(front + "/api/autopilot"
                                               "?recent=8192"))
            snap = ap["autopilot"]
            dirs_by_knob: dict[str, list[int]] = {}
            for d in ap["decisions"]:
                if d.get("applied") and d["reason"] == "adjusted":
                    dirs_by_knob.setdefault(d["knob"], []).append(
                        d["direction"])
            auto = {
                "enabled": snap["enabled"],
                "p99_slo_ms": snap["p99_slo_ms"],
                "knobs": {k: {"current": v["current"],
                              "static": v["static"],
                              "adjustments": v["adjustments"]}
                          for k, v in snap["knobs"].items()},
                "adjustments_total": sum(
                    v["adjustments"] for v in snap["knobs"].values()),
                # oscillation audit: per-knob count of adjacent
                # direction flips among applied adjustments (a genuine
                # load step may flip once; flapping would rack these up)
                "direction_flips": {
                    k: sum(1 for a, b in zip(ds, ds[1:]) if a != b)
                    for k, ds in dirs_by_knob.items()},
            }
            log(f"[ov] autopilot knobs: {auto['knobs']}")
        # cross-validate the LIVE histogram pipeline against the bench's
        # own measurements while the leader is still up: disagreement
        # beyond bucket-resolution error fails the artifact emission
        hist_check = _live_quantile_crosscheck(all_lats, m)
        log(f"[ov] live-histogram cross-check: {hist_check}")
        out = {
            "mode": "autopilot" if autopilot else "static",
            "one_x": one_x, "two_x": two_x,
            "live_histogram_check": hist_check,
            "p99_ratio_2x_vs_1x": round(
                two_x["interactive"]["p99_ms"]
                / one_x["interactive"]["p99_ms"], 2)
            if one_x["interactive"]["p99_ms"] else 0.0,
            "n_docs": OV_DOCS, "query_pool": OV_QUERY_POOL,
            "zipf_s": OV_ZIPF_S, "tail_unique": OV_TAIL_UNIQUE,
            "cache_entries": OV_CACHE_ENTRIES,
            "phase_s": OV_PHASE_S, "workers": 2,
            "front_door": "router",
            "shed_total": int(m.get("admission_shed_total", 0)),
            "backend": "cpu (nodes pinned)",
            # absolute latencies and the 2x ratio are CPU-bound on
            # small hosts (coordinator + leader + 2 workers + router +
            # the client loop timeshare these cores) — compare runs
            # only at equal host_cpus
            "host_cpus": os.cpu_count(),
        }
        if auto is not None:
            out["autopilot"] = auto
        return out
    finally:
        _kill_all(procs)


def overload_main() -> None:
    """Standalone entry (``python bench.py --overload``; ``make
    bench-overload``): the overload
    bench, artifact-first like the full sweep — TWO runs of the same
    closed-loop zipfian workload on the same corpus: the hand-tuned
    static constants, then the SLO
    autopilot deriving every knob from generic defaults. The headline
    value/ratio is the AUTOPILOT run (the round's question: does the
    closed loop match or beat the hand-tuned constants?); the static
    run rides beside it in the artifact as the comparison baseline."""
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    corpus = (make_texts(rng, OV_DOCS, OV_VOCAB, OV_AVG_LEN),
              make_queries(rng, OV_VOCAB, OV_QUERY_POOL))
    log(f"[ov] corpus in {time.perf_counter()-t0:.0f}s (shared by "
        f"both runs)")
    ov_static = bench_overload(rng, autopilot=False, corpus=corpus)
    ov_auto = bench_overload(rng, autopilot=True, corpus=corpus)
    result = {
        "metric": "overload_2x_admitted_interactive_p99_ms_autopilot",
        "value": ov_auto["two_x"]["interactive"]["p99_ms"],
        "unit": "ms",
        # the acceptance ratio: admitted-interactive p99 at 2x vs 1x
        # with the autopilot steering (unbounded queueing would put
        # this in the tens; the r6 leader-front-door run measured 0.76
        # on a multi-core host — on single-digit-core hosts the whole
        # topology timeshares the cores and the ratio reflects CPU
        # saturation, not admission behavior; judge against the
        # static_hand_tuned run in the same artifact, same host)
        "vs_baseline": ov_auto["p99_ratio_2x_vs_1x"],
        "extra": {
            "autopilot": ov_auto,
            "static_hand_tuned": ov_static,
            "p99_ratio_static": ov_static["p99_ratio_2x_vs_1x"],
            "p99_ratio_autopilot": ov_auto["p99_ratio_2x_vs_1x"],
        },
    }
    headline = {
        "ap_p99_1x_ms": ov_auto["one_x"]["interactive"]["p99_ms"],
        "ap_p99_2x_ms": ov_auto["two_x"]["interactive"]["p99_ms"],
        "ap_p99_ratio": ov_auto["p99_ratio_2x_vs_1x"],
        "static_p99_ratio": ov_static["p99_ratio_2x_vs_1x"],
        "ap_shed_int_2x":
            ov_auto["two_x"]["interactive"]["shed_rate"],
        "ap_qps_2x": ov_auto["two_x"]["interactive"]["qps"],
        "ap_adjustments":
            ov_auto.get("autopilot", {}).get("adjustments_total", 0),
        "ap_direction_flips": sum(
            ov_auto.get("autopilot", {}).get("direction_flips",
                                             {}).values()),
        "cache_hit_rate_2x": ov_auto["two_x"]["cache_hit_rate"],
    }
    _emit_validated(result, headline)


# --------------------------------------------------------------------------
# traffic capture / replay: the durable request log
# (utils/storage.py RequestLog, tapped at the router front door) as
# the workload source — capture admitted traffic, then re-drive it at
# its recorded arrival offsets, lanes, and client ids
# --------------------------------------------------------------------------

R10_DOCS = 8_000
R10_VOCAB = 30_000
R10_AVG_LEN = 60
R10_QUERY_POOL = 1_024      # distinct queries; zipf skew over the pool
R10_ZIPF_S = 1.1
R10_TAIL_UNIQUE = 0.15      # unique-query tail no cache can absorb
R10_CACHE = 512
R10_CLIENTS = 8             # measured closed-loop interactive clients
R10_BULK = 2                # measured bulk-lane clients
R10_WARM_S = 5.0
R10_CAPTURE_S = 12.0
R10_REPLAY_SLOTS = 32       # open-loop replay dispatch concurrency


def bench_replay(rng) -> tuple[dict, dict]:
    """Capture, then replay: a zipfian closed-loop workload runs
    through a ROUTER front door with the traffic-capture tap armed
    (``replay_capture_path`` — every ADMITTED ``/leader/start`` lands
    in the CRC-framed request log with its arrival offset, lane, and
    client id). The capture router is then stopped GRACEFULLY (the
    log's flush-on-close contract), the log is decoded, and a FRESH
    router replays it open-loop: each record re-issued at its recorded
    offset with its recorded lane/client, 429s retried per Retry-After
    until admitted. The artifact's fidelity block asserts the replay
    reproduced the log exactly — every captured record admitted, none
    invented — and the headline compares admitted-interactive p99
    under replay against the live capture phase (same backend, same
    corpus; the replay router starts cache-cold, the capture phase ran
    under closed-loop contention — the ratio carries both).

    Warm-up traffic and readiness probes ride through the SAME tap
    (the log is the admitted workload, unfiltered); they are replayed
    like everything else but excluded from the measured latencies by
    client id, on both sides."""
    import concurrent.futures
    import json as _json
    import socket
    import subprocess
    import tempfile
    import threading

    from tfidf_tpu.utils.storage import RequestLog

    t0 = time.perf_counter()
    texts = make_texts(rng, R10_DOCS, R10_VOCAB, R10_AVG_LEN)
    queries = make_queries(rng, R10_VOCAB, R10_QUERY_POOL)
    log(f"[r10] corpus in {time.perf_counter()-t0:.0f}s")

    env = dict(os.environ, TFIDF_JAX_PLATFORM="cpu", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update({
        "TFIDF_SCATTER_BATCH": "4",
        "TFIDF_RESULT_CACHE_ENTRIES": str(R10_CACHE),
        "TFIDF_ROUTER_CACHE_ENTRIES": str(R10_CACHE),
    })
    procs = []
    tmp = tempfile.mkdtemp(prefix="bench_r10_")
    cap_path = os.path.join(tmp, "capture", "requests.log")

    def spawn(args, extra_env=None):
        p = subprocess.Popen(
            [sys.executable, "-m", "tfidf_tpu", *args],
            env=dict(env, **(extra_env or {})),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        procs.append(p)
        return p

    client = _KeepAlive()
    try:
        coord = _free_port()
        spawn(["coordinator", "--listen", f"127.0.0.1:{coord}"])
        _wait_until(lambda: socket.create_connection(
            ("127.0.0.1", coord), timeout=1).close() or True)
        ports = [_free_port() for _ in range(3)]
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        for i, port in enumerate(ports):
            spawn(["serve", "--port", str(port), "--host", "127.0.0.1",
                   "--coordinator-address", f"127.0.0.1:{coord}",
                   "--documents-path", f"{tmp}/n{i}/docs",
                   "--index-path", f"{tmp}/n{i}/index"])
            _wait_until(lambda u=urls[i]: _http_get(u + "/api/status"))
        leader = urls[0]
        leader_hp = ("127.0.0.1", ports[0])
        _wait_until(lambda: len(_json.loads(
            _http_get(leader + "/api/services"))) == 2)

        def mk_router(capture):
            rp = _free_port()
            p = spawn(["router", "--port", str(rp), "--host",
                       "127.0.0.1", "--coordinator",
                       f"127.0.0.1:{coord}"],
                      extra_env=({"TFIDF_REPLAY_CAPTURE_PATH": cap_path}
                                 if capture else None))
            _wait_until(lambda: _http_get(
                f"http://127.0.0.1:{rp}/api/health"))
            return p, ("127.0.0.1", rp)

        cap_proc, front_hp = mk_router(capture=True)

        groups = [[{"name": f"d{i}.txt", "text": texts[i]}
                   for i in range(lo, min(lo + 500, R10_DOCS))]
                  for lo in range(0, R10_DOCS, 500)]
        t1 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            list(ex.map(
                lambda g: client.post(leader_hp, "/leader/upload-batch",
                                      _json.dumps(g).encode()),
                groups))
        log(f"[r10] uploaded {R10_DOCS} docs in "
            f"{time.perf_counter()-t1:.0f}s")
        _wait_until(lambda: client.post_full(
            front_hp, "/leader/start", b"warmup")[0] == 200)

        # closed-loop driver, shared by warm and measured rounds; the
        # "r10m-" client-id prefix marks records whose latencies count
        def one_client(lane, cid, seconds, measured):
            crng = np.random.default_rng(
                SEED + 977 * cid + (1 if lane == "bulk" else 0)
                + (100 if measured else 0))
            idx = _zipf_indices(crng, R10_QUERY_POOL, 4096)
            prefix = "r10m-" if measured else "r10warm-"
            hdrs = {"X-Client-Id": f"{prefix}{lane}{cid}"}
            if lane == "bulk":
                hdrs["X-Priority"] = "bulk"
            lats, sheds = [], 0
            stop_at = time.monotonic() + seconds
            i = 0
            while time.monotonic() < stop_at:
                q = queries[idx[i % len(idx)]]
                if crng.random() < R10_TAIL_UNIQUE:
                    q = f"{q} zzr10{lane}{cid}x{i}"
                i += 1
                t2 = time.monotonic()
                status, h, _b = client.post_full(
                    front_hp, "/leader/start", q.encode(),
                    timeout=60.0, headers=hdrs)
                if status == 200:
                    lats.append(time.monotonic() - t2)
                elif status == 429:
                    sheds += 1
                    time.sleep(min(float(h.get("Retry-After", 0.05)),
                                   0.5))
                else:
                    raise RuntimeError(f"[r10] status {status}")
            return lane, lats, sheds

        def round_(seconds, measured):
            with concurrent.futures.ThreadPoolExecutor(
                    R10_CLIENTS + R10_BULK) as ex:
                futs = [ex.submit(one_client, "interactive", c,
                                  seconds, measured)
                        for c in range(R10_CLIENTS)]
                futs += [ex.submit(one_client, "bulk", c, seconds,
                                   measured) for c in range(R10_BULK)]
                return [f.result() for f in futs]

        round_(R10_WARM_S, measured=False)   # XLA compiles + cache head
        res = round_(R10_CAPTURE_S, measured=True)
        cap_lats = sorted(ls for lane, lats, _ in res
                          if lane == "interactive" for ls in lats)
        cap_sheds = sum(s for _, _, s in res)
        n = len(cap_lats)
        cap_p50 = cap_lats[n // 2] * 1e3 if n else 0.0
        cap_p99 = cap_lats[int(n * 0.99)] * 1e3 if n else 0.0
        log(f"[r10] capture phase: {n} admitted interactive, "
            f"p50 {cap_p50:.1f}ms p99 {cap_p99:.1f}ms, "
            f"{cap_sheds} shed")

        # graceful stop: the capture log's flush-on-close contract is
        # exactly what makes the tail replayable
        cap_proc.terminate()
        cap_proc.wait(timeout=15)
        entries = RequestLog.read(cap_path)
        if not entries:
            raise RuntimeError("[r10] capture log empty")
        log(f"[r10] captured {len(entries)} admitted requests")

        _r_proc, replay_hp = mk_router(capture=False)
        _wait_until(lambda: client.post_full(
            replay_hp, "/leader/start", b"warmup")[0] == 200)

        # open-loop replay at recorded offsets; 429s retried until
        # admitted so the replayed-admitted count is exact
        t_first = entries[0]["t"]
        base = time.monotonic() + 0.5
        lock = threading.Lock()
        stats = {"admitted": 0, "retries_429": 0, "late": 0}
        replay_lats = []

        def replay_one(e):
            due = base + (e["t"] - t_first)
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            else:
                with lock:
                    stats["late"] += 1
            hdrs = {"X-Client-Id": e.get("client") or "r10replay"}
            if e.get("lane") == "bulk":
                hdrs["X-Priority"] = "bulk"
            t2 = time.monotonic()
            while True:
                status, h, _b = client.post_full(
                    replay_hp, "/leader/start", e["query"].encode(),
                    timeout=60.0, headers=hdrs)
                if status == 200:
                    break
                if status == 429:
                    with lock:
                        stats["retries_429"] += 1
                    time.sleep(min(float(h.get("Retry-After", 0.05)),
                                   0.5))
                    continue
                raise RuntimeError(f"[r10] replay status {status}")
            dt = time.monotonic() - t2
            with lock:
                stats["admitted"] += 1
                if (e.get("lane") == "interactive"
                        and str(e.get("client", "")).startswith("r10m-")):
                    replay_lats.append(dt)

        t1 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(
                R10_REPLAY_SLOTS) as ex:
            list(ex.map(replay_one, entries))
        replay_wall = time.perf_counter() - t1
        rl = sorted(replay_lats)
        rn = len(rl)
        rep_p50 = rl[rn // 2] * 1e3 if rn else 0.0
        rep_p99 = rl[int(rn * 0.99)] * 1e3 if rn else 0.0
        log(f"[r10] replay: {stats['admitted']}/{len(entries)} "
            f"admitted in {replay_wall:.0f}s "
            f"({stats['retries_429']} retried 429s), measured "
            f"interactive p50 {rep_p50:.1f}ms p99 {rep_p99:.1f}ms")

        # capture/replay fidelity, asserted before any artifact is
        # worth emitting: every captured record admitted on replay
        fidelity = {
            "captured_records": len(entries),
            "replayed_admitted": stats["admitted"],
            "identical_admitted": stats["admitted"] == len(entries),
            "measured_capture_interactive": n,
            "measured_replay_interactive": rn,
            "replay_retries_429": stats["retries_429"],
            "replay_dispatched_late": stats["late"],
        }
        if not fidelity["identical_admitted"] or rn == 0:
            raise RuntimeError(f"[r10] replay fidelity broken: "
                               f"{fidelity}")

        result = {
            "metric": "replay_admitted_interactive_p99_ms",
            "value": round(rep_p99, 1),
            "unit": "ms",
            # replayed-traffic p99 vs the live capture phase's p99 on
            # the same backend/corpus (cold router cache + open-loop
            # pacing vs closed-loop contention — the ratio carries
            # both, it is not a regression gate)
            "vs_baseline": round(rep_p99 / cap_p99, 2) if cap_p99
            else 0.0,
            "extra": {
                "fidelity": fidelity,
                "capture": {"p50_ms": round(cap_p50, 1),
                            "p99_ms": round(cap_p99, 1),
                            "admitted_interactive": n,
                            "shed": cap_sheds,
                            "phase_s": R10_CAPTURE_S,
                            "clients": R10_CLIENTS,
                            "bulk_clients": R10_BULK},
                "replay": {"p50_ms": round(rep_p50, 1),
                           "p99_ms": round(rep_p99, 1),
                           "wall_s": round(replay_wall, 1),
                           "slots": R10_REPLAY_SLOTS},
                "n_docs": R10_DOCS, "query_pool": R10_QUERY_POOL,
                "zipf_s": R10_ZIPF_S, "tail_unique": R10_TAIL_UNIQUE,
                "cache_entries": R10_CACHE,
                "front_door": "router",
                "backend": "cpu (nodes pinned)",
                # same caveat as the overload artifact: absolute
                # latencies are host-bound; the fidelity block is the
                # portable claim
                "host_cpus": os.cpu_count(),
            },
        }
        headline = {
            "captured": len(entries),
            "replayed_admitted": stats["admitted"],
            "fidelity_identical": fidelity["identical_admitted"],
            "capture_p99_ms": round(cap_p99, 1),
            "replay_p99_ms": round(rep_p99, 1),
            "replay_vs_capture_p99": result["vs_baseline"],
            "replay_retries_429": stats["retries_429"],
        }
        return result, headline
    finally:
        _kill_all(procs)


def replay_main() -> None:
    """Standalone entry (``python bench.py --replay``; ``make
    bench-replay``): the capture/replay bench, artifact-first like
    every other mode."""
    rng = np.random.default_rng(SEED)
    result, headline = bench_replay(rng)
    _emit_validated(result, headline)


# --------------------------------------------------------------------------
# router scale-out: admitted q/s through 1/2/4 stateless routers
# (ISSUE 12 tentpole; ROADMAP item 1 — retire the single-leader
# front-door ceiling)
# --------------------------------------------------------------------------

RT7_DOCS = 4_000
RT7_VOCAB = 30_000
RT7_AVG_LEN = 60
RT7_QUERY_POOL = 512        # distinct queries; zipf skew over the pool
RT7_TAIL_EVERY = 33         # every Nth request carries a unique query
#                             no cache can absorb (a ~3% tail). The
#                             backend stays FIXED (2 workers) across
#                             phases by design — this bench scales the
#                             FRONT DOOR, so the workload is the
#                             cache-headed interactive regime where
#                             the front door is the binding tier (the
#                             worker tier has its own HPA/bench story)
RT7_CACHE = 2_048           # per-ROUTER result cache (>= pool: the
#                             zipf head answers router-side)
RT7_CLIENT_PROCS = 12       # load-generator PROCESSES (one python
#                             process cannot generate enough closed-
#                             loop traffic to saturate even two
#                             routers — the generator must never be
#                             the measured ceiling)
RT7_CLIENT_THREADS = 6      # closed-loop connections per process
RT7_WARM_S = 5.0
RT7_PHASE_S = 10.0
RT7_COUNTS = (1, 2, 4)

# the closed-loop client subprocess: threads hammer ONE router over
# keep-alive connections, honoring 429 Retry-After; only the measure
# window (after warm_s) is recorded. Run via `python -c` with a JSON
# spec file — no pickling, no fork-with-threads, no bench import.
_R7_CLIENT_SRC = r'''
import http.client, json, socket, sys, threading, time
spec = json.load(open(sys.argv[1]))
port, queries = spec["port"], spec["queries"]
warm_end = time.monotonic() + spec["warm_s"]
stop_at = warm_end + spec["measure_s"]
lats, shed, errors = [], [0], []
lock = threading.Lock()

def run(tid, seq):
    conn = None
    i = 0
    while time.monotonic() < stop_at:
        q = queries[seq[i % len(seq)]]
        if spec["tail_every"] and i % spec["tail_every"] == 0:
            q = f"{q} zztail{port}x{tid}x{i}"
        i += 1
        t1 = time.monotonic()
        try:
            if conn is None:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=60)
                conn.connect()
                conn.sock.setsockopt(socket.IPPROTO_TCP,
                                     socket.TCP_NODELAY, 1)
            conn.request("POST", "/leader/start", body=q.encode(),
                         headers={"Content-Type": "text/plain"})
            r = conn.getresponse()
            r.read()
            st, ra = r.status, r.getheader("Retry-After")
            if r.will_close:
                conn.close()
                conn = None
        except Exception as e:
            try:
                conn.close()
            except Exception:
                pass
            conn = None
            errors.append(repr(e))
            return
        t2 = time.monotonic()
        if st == 200:
            if t1 >= warm_end:
                with lock:
                    lats.append(t2 - t1)
        elif st == 429:
            if t1 >= warm_end:
                with lock:
                    shed[0] += 1
            time.sleep(min(float(ra or 0.05), 0.5))
        else:
            errors.append(f"status {st}")
            return

threads = [threading.Thread(target=run, args=(k, s))
           for k, s in enumerate(spec["seqs"])]
for t in threads:
    t.start()
for t in threads:
    t.join()
print(json.dumps({"lats": lats, "shed": shed[0],
                  "errors": errors[:3]}))
'''


def bench_routers(rng, corpus: tuple | None = None) -> dict:
    """Scale-out query plane (cluster/router.py): the same zipfian
    closed-loop interactive workload at EQUAL offered load
    (``RT7_CLIENTS`` clients) through 1, 2, and 4 stateless router
    processes in front of one 2-worker cluster. Each router runs its
    own admission/coalescer/cache/resilience stack against a
    watch-refreshed placement follower view; the contract under test
    is near-linear admitted-q/s scaling with router count (the
    acceptance bar: 2 routers >= 1.6x the 1-router baseline) with
    router results parity-checked against the leader's before any
    phase is measured."""
    import concurrent.futures
    import json as _json
    import socket
    import subprocess
    import tempfile
    import threading

    if corpus is None:
        t0 = time.perf_counter()
        texts = make_texts(rng, RT7_DOCS, RT7_VOCAB, RT7_AVG_LEN)
        queries = make_queries(rng, RT7_VOCAB, RT7_QUERY_POOL)
        log(f"[r7] corpus in {time.perf_counter()-t0:.0f}s")
    else:
        texts, queries = corpus

    env = dict(os.environ, TFIDF_JAX_PLATFORM="cpu", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update({
        "TFIDF_ROUTER_CACHE_ENTRIES": str(RT7_CACHE),
        "TFIDF_ROUTER_REFRESH_MS": "500",
    })
    procs = []
    tmp = tempfile.mkdtemp(prefix="bench_r7_")

    def spawn(args):
        p = subprocess.Popen(
            [sys.executable, "-m", "tfidf_tpu", *args], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        procs.append(p)
        return p

    client = _KeepAlive()
    try:
        coord = _free_port()
        spawn(["coordinator", "--listen", f"127.0.0.1:{coord}"])
        _wait_until(lambda: socket.create_connection(
            ("127.0.0.1", coord), timeout=1).close() or True)
        ports = [_free_port() for _ in range(3)]
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        for i, port in enumerate(ports):
            spawn(["serve", "--port", str(port), "--host", "127.0.0.1",
                   "--coordinator-address", f"127.0.0.1:{coord}",
                   "--documents-path", f"{tmp}/n{i}/docs",
                   "--index-path", f"{tmp}/n{i}/index"])
            _wait_until(lambda u=urls[i]: _http_get(u + "/api/status"))
        leader = urls[0]
        leader_hp = ("127.0.0.1", ports[0])
        _wait_until(lambda: len(_json.loads(
            _http_get(leader + "/api/services"))) == 2)

        groups = [[{"name": f"d{i}.txt", "text": texts[i]}
                   for i in range(lo, min(lo + 500, RT7_DOCS))]
                  for lo in range(0, RT7_DOCS, 500)]
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            list(ex.map(
                lambda g: client.post(leader_hp, "/leader/upload-batch",
                                      _json.dumps(g).encode()),
                groups))
        ingest_s = time.perf_counter() - t0
        # recorded in the artifact since r08: the ingest path now
        # fsyncs-before-ack (group-committed), and this number is the
        # proof the contract costs noise, not throughput
        ingest_dps = round(RT7_DOCS / ingest_s, 1)
        log(f"[r7] uploaded {RT7_DOCS} docs in {ingest_s:.0f}s "
            f"({ingest_dps} docs/s, fsync-before-ack)")

        def run_phase(n_routers: int) -> dict:
            rports = [_free_port() for _ in range(n_routers)]
            rurls = [f"http://127.0.0.1:{p}" for p in rports]
            rprocs = []
            for p in rports:
                rprocs.append(spawn([
                    "router", "--coordinator", f"127.0.0.1:{coord}",
                    "--host", "127.0.0.1", "--port", str(p)]))
            for u in rurls:
                _wait_until(lambda u=u: _json.loads(_http_get(
                    u + "/api/router"))["placement"]["docs"]
                    == RT7_DOCS)
            # correctness gate BEFORE measuring: router results must
            # equal the leader's exactly (same placement world)
            for q in queries[:8]:
                via_leader = _json.loads(client.post(
                    leader_hp, "/leader/start", q.encode()))
                for i, p in enumerate(rports):
                    via_router = _json.loads(client.post(
                        ("127.0.0.1", p), "/leader/start", q.encode()))
                    if via_router != via_leader:
                        raise RuntimeError(
                            f"[r7] router {i} result diverges from "
                            f"the leader for {q!r}")

            # EQUAL offered load every phase: the same client-process
            # fleet, distributed round-robin over however many routers
            # this phase runs
            cprocs = []
            spec_files = []
            for c in range(RT7_CLIENT_PROCS):
                crng = np.random.default_rng(
                    SEED + 1000 * n_routers + c)
                seqs = [
                    _zipf_indices(crng, RT7_QUERY_POOL, 4096).tolist()
                    for _ in range(RT7_CLIENT_THREADS)]
                spec = {"port": rports[c % n_routers],
                        "queries": queries, "seqs": seqs,
                        "warm_s": RT7_WARM_S,
                        "measure_s": RT7_PHASE_S,
                        "tail_every": RT7_TAIL_EVERY}
                path = os.path.join(tmp, f"r7c_{n_routers}_{c}.json")
                with open(path, "w", encoding="utf-8") as f:
                    _json.dump(spec, f)
                spec_files.append(path)
                cprocs.append(subprocess.Popen(
                    [sys.executable, "-c", _R7_CLIENT_SRC, path],
                    env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.DEVNULL))
            lats: list[float] = []
            sheds = 0
            errors: list[str] = []
            for p in cprocs:
                out, _ = p.communicate(
                    timeout=RT7_WARM_S + RT7_PHASE_S + 120)
                got = _json.loads(out)
                lats.extend(got["lats"])
                sheds += got["shed"]
                errors.extend(got["errors"])
            if errors:
                raise RuntimeError(f"[r7] {n_routers}-router phase "
                                   f"client failures: {errors[:3]}")
            wall = RT7_PHASE_S   # each client records exactly this
            #                      window (post-warm); closed loop
            # per-router cache hit rate (process-global metrics are
            # per-process, i.e. per-router — exactly what we want)
            hit_rates = []
            for u in rurls:
                snap = _json.loads(_http_get(u + "/api/router"))
                hit_rates.append(snap["cache"]["hit_rate"])
            _kill_all(rprocs)
            for p in rprocs:
                procs.remove(p)
            ls = sorted(lats)
            n = len(ls)
            out = {
                "routers": n_routers,
                "clients": RT7_CLIENT_PROCS * RT7_CLIENT_THREADS,
                "admitted": n,
                "shed": sheds,
                "admitted_qps": round(n / wall, 1),
                "p50_ms": round(ls[n // 2] * 1e3, 1) if n else 0.0,
                "p99_ms": round(ls[int(n * 0.99)] * 1e3, 1)
                if n else 0.0,
                "cache_hit_rate": round(
                    sum(hit_rates) / len(hit_rates), 4),
            }
            log(f"[r7] {n_routers} router(s): "
                f"{out['admitted_qps']} admitted q/s, "
                f"p50 {out['p50_ms']}ms, p99 {out['p99_ms']}ms, "
                f"cache hit {out['cache_hit_rate']:.1%}, "
                f"shed {out['shed']}")
            return out

        table = {str(r): run_phase(r) for r in RT7_COUNTS}
        q1 = table["1"]["admitted_qps"]
        return {
            "routers": table,
            "scaling_2r_vs_1r": round(
                table["2"]["admitted_qps"] / q1, 4) if q1 else 0.0,
            "scaling_4r_vs_1r": round(
                table["4"]["admitted_qps"] / q1, 4) if q1 else 0.0,
            "parity_checked": True,
            "n_docs": RT7_DOCS, "query_pool": RT7_QUERY_POOL,
            "zipf_s": OV_ZIPF_S,
            "tail_unique": round(1.0 / RT7_TAIL_EVERY, 3),
            "cache_entries": RT7_CACHE, "phase_s": RT7_PHASE_S,
            "workers": 2,
            "ingest_dps": ingest_dps,
            "fsync_before_ack": True,
            "backend": "cpu (nodes pinned)",
        }
    finally:
        _kill_all(procs)


def routers_main() -> None:
    """Standalone entry (``python bench.py --routers``; ``make
    bench-routers``): the
    multi-router scale-out bench, artifact-first like the full sweep.
    The headline value is admitted interactive q/s at 2 routers; the
    acceptance ratio is its scaling factor over the 1-router baseline
    at EQUAL offered load (the bar: >= 1.6x — ISSUE 12)."""
    rng = np.random.default_rng(SEED)
    r7 = bench_routers(rng)
    result = {
        "metric": "router_scaleout_admitted_qps_2r",
        "value": r7["routers"]["2"]["admitted_qps"],
        "unit": "queries/sec",
        # the acceptance ratio: 2-router admitted q/s over the
        # 1-router baseline at equal offered load (bar: >= 1.6)
        "vs_baseline": round(r7["scaling_2r_vs_1r"], 2),
        "extra": r7,
    }
    headline = {
        "qps_1r": r7["routers"]["1"]["admitted_qps"],
        "qps_2r": r7["routers"]["2"]["admitted_qps"],
        "qps_4r": r7["routers"]["4"]["admitted_qps"],
        "scaling_2r": r7["scaling_2r_vs_1r"],
        "scaling_4r": r7["scaling_4r_vs_1r"],
        "p99_2r_ms": r7["routers"]["2"]["p99_ms"],
        "cache_hit_2r": r7["routers"]["2"]["cache_hit_rate"],
    }
    _emit_validated(result, headline)


# --------------------------------------------------------------------------
# realistic-text pipeline at 100k docs (VERDICT r3 #3)
# --------------------------------------------------------------------------

RT_DOCS = 100_000
RT_AVG_LEN = 80
RT_BATCH = 1024
RT_BATCHES = 4
RT_PARITY_QUERIES = 64


def bench_realistic(rng) -> dict:
    """The FULL text pipeline on realistic bytes: extract (HTML /
    charset fallback / binary 415) -> tokenize (native ASCII fast path
    vs Python fallback) -> index -> search, at 100k documents built
    from a real-English lexicon with punctuation, contractions,
    numbers, and a charset/format mix (``tfidf_tpu/utils/textgen.py``).
    Every other config bypasses the analyzer with ``t{i}`` tokens; the
    reference's workload is real text through a real analyzer
    (``Worker.java:190-220``). Oracle top-10 parity is computed from
    the engine's own analyzer output (live_entries), so it validates
    scoring + indexing given the analysis the documents actually got."""
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.ops.analyzer import UnsupportedMediaType
    from tfidf_tpu.utils.config import Config
    from tfidf_tpu.utils.metrics import global_metrics
    from tfidf_tpu.utils.textgen import RealisticCorpus, harvest_lexicon

    t0 = time.perf_counter()
    words, _ = harvest_lexicon()
    gen = RealisticCorpus(rng, words)
    payloads = [gen.make_payload(RT_AVG_LEN) for _ in range(RT_DOCS)]
    kinds = {}
    for _p, k in payloads:
        kinds[k] = kinds.get(k, 0) + 1
    log(f"[rt] {RT_DOCS} realistic docs ({kinds}) from a "
        f"{len(words)}-word lexicon in {time.perf_counter()-t0:.0f}s")

    engine = Engine(Config(query_batch=RT_BATCH))
    m0 = global_metrics.snapshot()
    rejected = 0
    t0 = time.perf_counter()
    for i, (data, _k) in enumerate(payloads):
        try:
            engine.ingest_bytes(f"d{i}.txt", data)
        except UnsupportedMediaType:
            rejected += 1
    ingest_s = time.perf_counter() - t0
    assert rejected == kinds.get("binary", 0), \
        (rejected, kinds.get("binary", 0))
    m1 = global_metrics.snapshot()
    native = (m1.get("ingest_native_fast_path", 0)
              - m0.get("ingest_native_fast_path", 0))
    pyfall = (m1.get("ingest_python_fallback", 0)
              - m0.get("ingest_python_fallback", 0))
    hit_rate = native / max(native + pyfall, 1)
    t0 = time.perf_counter()
    engine.commit()
    commit_s = time.perf_counter() - t0
    log(f"[rt] ingested {RT_DOCS - rejected} docs in {ingest_s:.1f}s "
        f"({(RT_DOCS - rejected)/ingest_s:.0f} docs/s), {rejected} "
        f"binary 415s, native fast path {hit_rate:.1%}, "
        f"commit {commit_s:.1f}s")

    def make_query() -> str:
        k = int(rng.integers(2, 5))
        idx = rng.choice(len(words), size=k, p=gen.p)
        toks = [words[i] for i in idx]
        if rng.random() < 0.3:   # exercise query-side lowercasing
            toks[0] = toks[0].capitalize()
        return " ".join(toks)

    queries = [make_query() for _ in range(RT_BATCH * (RT_BATCHES + 2))]
    engine.search_batch(queries[:RT_BATCH], k=TOP_K)
    engine.search_batch(queries[RT_BATCH:2 * RT_BATCH], k=TOP_K)
    timed = queries[2 * RT_BATCH:(RT_BATCHES + 2) * RT_BATCH]
    with _measured_window("rt-serving", steady_state=True):
        t0 = time.perf_counter()
        engine.search_batch(timed, k=TOP_K)
        qps = len(timed) / (time.perf_counter() - t0)
    log(f"[rt] {len(timed)} queries -> {qps:.1f} q/s (batch={RT_BATCH})")

    # oracle parity from the engine's own analyzer output, through the
    # SAME impact math every other config's oracle uses (_impacts)
    import scipy.sparse as sp
    entries = engine.index.live_entries()
    vocab_n = len(engine.vocab) + 1
    name_row = {e.name: i for i, e in enumerate(entries)}
    offsets = np.zeros(len(entries) + 1, np.int64)
    for i, e in enumerate(entries):
        offsets[i + 1] = offsets[i] + e.term_ids.shape[0]
    ids = np.concatenate([e.term_ids for e in entries])
    tfs = np.concatenate([e.tfs for e in entries])
    lengths = np.asarray([e.length for e in entries], np.float32)
    row_all, impact = _impacts(offsets, ids, tfs, lengths)
    M = sp.csr_matrix((impact, (row_all, ids.astype(np.int64))),
                      shape=(len(entries), vocab_n))
    pq = queries[:RT_PARITY_QUERIES]
    got = engine.search_batch(pq, k=TOP_K)
    analyzer, vocab = engine.analyzer, engine.vocab
    for qi, (q, hits) in enumerate(zip(pq, got)):
        qv = np.zeros(vocab_n, np.float64)
        for tid, n in vocab.map_counts(analyzer.counts(q),
                                       add=False).items():
            qv[tid] += n
        scores = np.asarray(M @ qv).ravel()
        want = np.sort(scores)[::-1][:TOP_K]
        want = want[want > 0]
        have = np.asarray([h.score for h in hits], np.float32)
        assert have.shape[0] == want.shape[0], (qi, q, have, want)
        np.testing.assert_allclose(have, want, rtol=2e-3, atol=1e-4,
                                   err_msg=f"[rt] query {qi} {q!r}")
        for h in hits:
            np.testing.assert_allclose(
                h.score, scores[name_row[h.name]], rtol=2e-3, atol=1e-4,
                err_msg=f"[rt] query {qi} {q!r} doc {h.name}")
    log(f"[rt] oracle top-{TOP_K} parity OK on {len(pq)} queries")
    return {"qps": round(qps, 1),
            "ingest_dps": round((RT_DOCS - rejected) / ingest_s, 1),
            "commit_s": round(commit_s, 1), "n_docs": RT_DOCS,
            "binary_rejected_415": rejected,
            "kinds": kinds,
            "native_fast_path_rate": round(hit_rate, 4),
            "lexicon_words": len(words),
            "parity_checked": True}


# --------------------------------------------------------------------------
# config 2b: cluster data plane with a TPU-BACKED worker (VERDICT r3 #1)
# --------------------------------------------------------------------------

C2T_DOCS = 100_000
C2T_TPU_SHARE = 95_000
C2T_AVG_LEN = 80
C2T_CLIENTS = 1024         # max sweep point; warmup uses this count
C2T_SWEEP = (1024, 768, 512)  # in-run client sweep (host speed varies
                              # 2-3x between runs; only an in-run sweep
                              # isolates the concurrency knob)
C2T_QUERIES = 8192
C2T_QUERY_BATCH = 512      # worker-side engine chunk == scatter batch:
                           # ONE device fetch per scatter RPC (d2h
                           # fetches serialize; fewer+bigger fetches
                           # beat deeper pipelining)
C2T_SCATTER_BATCH = 1024   # leader-side group: 2 worker chunks, fetches overlap
C2T_LINGER_MS = 5.0
C2T_PARITY_QUERIES = 32


def _delta_timing(m0: dict, m1: dict, name: str) -> float:
    """Windowed mean (ms) of a Metrics timing between two snapshots."""
    n = m1.get(f"{name}_count", 0) - m0.get(f"{name}_count", 0)
    s = m1.get(f"{name}_sum_ms", 0.0) - m0.get(f"{name}_sum_ms", 0.0)
    return round(s / n, 3) if n else 0.0


def bench_cluster_tpu(rng) -> dict:
    """The distributed HTTP serving path against a TPU-backed engine —
    the reference's only serving shape (``Leader.java:39-92``) with the
    TPU doing the scoring, driven with REALISTIC text (the reference's
    workload is real files through a real analyzer, Worker.java:125-146):
    the textgen corpus (plain/HTML/latin-1 + a binary fraction that must
    415). A chip belongs to ONE process, so the topology is:
    leader (CPU, scatter-gather only) + worker0 (TPU, ~95% of the
    corpus) + worker1 (CPU, the tail). The phased upload (worker0 alone
    first, then worker1 joins and takes the remainder via least-loaded
    placement) both skews the corpus onto the TPU worker and exercises
    elastic join (SURVEY §5.3).

    Serving runs the round-5 batched scatter: concurrent /leader/start
    queries coalesce into one packed-binary RPC per worker. The config
    reports a per-stage breakdown (linger/RPC/decode/merge at the
    leader, search/pack at the TPU worker) from windowed /api/metrics
    deltas, and a parity gate: /leader/start must equal the sum-merged
    union of direct per-worker /worker/process results (the per-query
    reference shape) for every parity query.

    MUST run before this process initializes a jax backend: a parent
    that holds the chip makes the TPU worker fail at start-up
    (``JAX_PLATFORMS=tpu`` — it never falls back to the CPU). Checked
    at the spawn."""
    import concurrent.futures
    import json as _json
    import socket
    import subprocess
    import tempfile

    from tfidf_tpu.utils.textgen import RealisticCorpus, harvest_lexicon

    client = _KeepAlive()
    post = client.post

    t0 = time.perf_counter()
    words, _ = harvest_lexicon()
    gen = RealisticCorpus(rng, words)
    payloads = [gen.make_payload(C2T_AVG_LEN) for _ in range(C2T_DOCS)]
    kinds: dict[str, int] = {}
    for _p, k in payloads:
        kinds[k] = kinds.get(k, 0) + 1

    def make_query() -> str:
        k = int(rng.integers(2, 5))
        idx = rng.choice(len(words), size=k, p=gen.p)
        return " ".join(words[i] for i in idx)

    queries = [make_query()
               for _ in range((2 + len(C2T_SWEEP)) * C2T_QUERIES)]
    log(f"[c2t] {C2T_DOCS} realistic docs ({kinds}) in "
        f"{time.perf_counter()-t0:.0f}s")

    cpu_env = dict(os.environ, TFIDF_JAX_PLATFORM="cpu",
                   JAX_PLATFORMS="cpu")
    cpu_env.pop("XLA_FLAGS", None)
    # pinned, so a chip it cannot get is fatal instead of a quiet CPU
    # fallback — which this process would cause by holding the chip
    from jax._src import xla_bridge
    assert not xla_bridge.backends_are_initialized(), \
        "bench_cluster_tpu must run before this process touches jax"
    tpu_env = dict(os.environ, JAX_PLATFORMS="tpu")
    for k in ("XLA_FLAGS", "TFIDF_JAX_PLATFORM"):
        tpu_env.pop(k, None)
    for e in (cpu_env, tpu_env):
        e["TFIDF_QUERY_BATCH"] = str(C2T_QUERY_BATCH)
        e["TFIDF_BATCH_LINGER_MS"] = str(C2T_LINGER_MS)
        e["TFIDF_SCATTER_BATCH"] = str(C2T_SCATTER_BATCH)
        e["TFIDF_SCATTER_PIPELINE"] = "2"
        e["TFIDF_FANOUT_WORKERS"] = "32"
        # adaptive linger range (round 6): idle pipeline ships groups
        # at ~0.5ms; a saturated pipeline stretches toward 2x the old
        # fixed linger so groups arrive fuller while the wait hides
        # under in-flight batches
        e["TFIDF_BATCH_LINGER_MIN_MS"] = "0.5"
        e["TFIDF_BATCH_LINGER_MAX_MS"] = str(2 * C2T_LINGER_MS)
        e["TFIDF_SCATTER_LINGER_MIN_MS"] = "0.5"
        e["TFIDF_SCATTER_LINGER_MAX_MS"] = str(2 * C2T_LINGER_MS)
    # the CPU worker chunks big scatter batches finely: one XLA chunk of
    # hundreds of queries on the CPU backend is a straggler that gates
    # every batch (the leader must wait for ALL shards), and the r5
    # sweep measured leader_rpc ~210ms above the TPU worker's search
    # time from exactly this
    cpu_env["TFIDF_QUERY_BATCH"] = "64"

    procs = []
    tmp = tempfile.mkdtemp(prefix="bench_c2t_")
    log(f"[c2t] node logs under {tmp}/node*.log")

    def spawn(args, env):
        errf = open(f"{tmp}/node{len(procs)}.log", "wb")
        p = subprocess.Popen([sys.executable, "-m", "tfidf_tpu", *args],
                             env=env, stdout=subprocess.DEVNULL,
                             stderr=errf)
        procs.append(p)
        return p

    try:
        coord = _free_port()
        spawn(["coordinator", "--listen", f"127.0.0.1:{coord}"], cpu_env)
        _wait_until(lambda: socket.create_connection(
            ("127.0.0.1", coord), timeout=1).close() or True)
        ports = [_free_port() for _ in range(3)]
        urls = [f"http://127.0.0.1:{p}" for p in ports]

        def node_args(i):
            return ["serve", "--port", str(ports[i]), "--host",
                    "127.0.0.1", "--coordinator-address",
                    f"127.0.0.1:{coord}",
                    "--documents-path", f"{tmp}/n{i}/docs",
                    "--index-path", f"{tmp}/n{i}/index"]

        spawn(node_args(0), cpu_env)   # leader first: wins the election
        _wait_until(lambda: _http_get(urls[0] + "/api/status")
                    == b"I am the leader")
        spawn(node_args(1), tpu_env)   # the TPU worker
        _wait_until(lambda: _json.loads(_http_get(urls[0] + "/api/services"))
                    == [urls[1]])

        leader_hp = ("127.0.0.1", ports[0])
        rejected = 0

        def upload_range(lo: int, hi: int) -> int:
            """Upload docs [lo, hi): UTF-8 text in bulk batches, the
            rest (latin-1/binary) through the per-file endpoint, like a
            mixed real-world client. Returns the 415 count."""
            batch: list[dict] = []
            singles: list[tuple[str, bytes]] = []
            for i in range(lo, hi):
                data, kind = payloads[i]
                name = f"d{i}.txt"
                if kind != "binary":
                    try:
                        batch.append({"name": name,
                                      "text": data.decode("utf-8")})
                        continue
                    except UnicodeDecodeError:
                        pass
                singles.append((name, data))
            groups = [batch[g:g + 500] for g in range(0, len(batch), 500)]
            with concurrent.futures.ThreadPoolExecutor(8) as ex:
                list(ex.map(lambda g: post(
                    leader_hp, "/leader/upload-batch",
                    _json.dumps(g).encode()), groups))
                n415 = sum(ex.map(
                    lambda nd: int(b"unsupported media type" in post(
                        leader_hp, f"/leader/upload?name={nd[0]}",
                        nd[1])), singles))
            return n415

        t0 = time.perf_counter()
        rejected += upload_range(0, C2T_TPU_SHARE)
        up1_s = time.perf_counter() - t0
        log(f"[c2t] {C2T_TPU_SHARE} docs -> TPU worker in {up1_s:.0f}s "
            f"({C2T_TPU_SHARE/up1_s:.0f} docs/s), {rejected} binary 415s")

        spawn(node_args(2), cpu_env)   # CPU worker joins late
        _wait_until(lambda: len(_json.loads(
            _http_get(urls[0] + "/api/services"))) == 2)
        rejected += upload_range(C2T_TPU_SHARE, C2T_DOCS)
        assert rejected == kinds.get("binary", 0), \
            (rejected, kinds.get("binary", 0))

        # force each worker's NRT commit + first compile directly: the
        # leader's scatter RPC timeout is 10s, a cold commit is not
        for i in (1, 2):
            t0 = time.perf_counter()
            post(("127.0.0.1", ports[i]), "/worker/process",
                 _json.dumps({"query": queries[0]}).encode(),
                 timeout=900.0)
            log(f"[c2t] worker {i-1} cold commit+compile: "
                f"{time.perf_counter()-t0:.0f}s")
        # warm the FULL scatter-batch bucket on each worker before the
        # client storm: its first compile is seconds, and a failure here
        # is visible in the node logs instead of silently degrading every
        # coalesced batch to [] (r5 run-5 postmortem)
        for i in (1, 2):
            t0 = time.perf_counter()
            raw = post(("127.0.0.1", ports[i]), "/worker/process-batch",
                       _json.dumps({"queries": queries[:C2T_SCATTER_BATCH],
                                    "k": TOP_K}).encode(), timeout=900.0)
            from tfidf_tpu.cluster.wire import unpack_hit_lists
            got = unpack_hit_lists(raw)
            assert sum(bool(x) for x in got) > 0, \
                f"worker {i-1} full-bucket batch returned all-empty"
            log(f"[c2t] worker {i-1} bucket-{C2T_SCATTER_BATCH} warm: "
                f"{time.perf_counter()-t0:.0f}s")

        def start(q):
            return post(leader_hp, "/leader/start", q.encode())

        for r in range(2):   # warm: compiles the batch buckets
            with concurrent.futures.ThreadPoolExecutor(C2T_CLIENTS) as ex:
                list(ex.map(start,
                            queries[r*C2T_QUERIES:(r+1)*C2T_QUERIES]))

        def snap_metrics():
            return (_json.loads(_http_get(urls[0] + "/api/metrics")),
                    _json.loads(_http_get(urls[1] + "/api/metrics")))

        # per-stage breakdown of one served query (VERDICT r4 #1):
        # leader queue wait/RPC/decode/merge from the leader process, batch
        # search/pack from the TPU worker, windowed per sweep point
        def window_breakdown(ml0, mw0, ml1, mw1):
            n_sb = (ml1.get("scatter_batches", 0)
                    - ml0.get("scatter_batches", 0))
            n_si = (ml1.get("scatter_items", 0)
                    - ml0.get("scatter_items", 0))
            return {
                "mean_scatter_batch": round(n_si / max(n_sb, 1), 1),
                "leader_queue_wait_ms": _delta_timing(
                    ml0, ml1, "scatter_queue_wait"),
                "leader_rpc_ms": _delta_timing(ml0, ml1, "scatter_rpc"),
                "leader_decode_ms": _delta_timing(ml0, ml1,
                                                  "scatter_decode"),
                "leader_merge_ms": _delta_timing(ml0, ml1,
                                                 "scatter_merge"),
                "worker_search_ms": _delta_timing(mw0, mw1,
                                                  "worker_batch_search"),
                "worker_pack_ms": _delta_timing(mw0, mw1,
                                                "worker_batch_pack"),
            }

        windows = []
        qoff = 2 * C2T_QUERIES
        for nclients in C2T_SWEEP:
            ml0, mw0 = snap_metrics()
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(nclients) as ex:
                res = list(ex.map(start,
                                  queries[qoff:qoff + C2T_QUERIES]))
            w_qps = C2T_QUERIES / (time.perf_counter() - t0)
            ml1, mw1 = snap_metrics()
            assert sum(bool(_json.loads(r)) for r in res[:64]) >= 32, \
                "mostly-empty results"
            w = {"clients": nclients, "qps": round(w_qps, 1),
                 "breakdown": window_breakdown(ml0, mw0, ml1, mw1)}
            windows.append(w)
            log(f"[c2t] window {w}")
            qoff += C2T_QUERIES
        best = max(windows, key=lambda w: w["qps"])
        qps = best["qps"]
        breakdown = best["breakdown"]

        lat = []
        for q in queries[:32]:
            t0 = time.perf_counter()
            start(q)
            lat.append((time.perf_counter() - t0) * 1e3)

        # parity gate: the batched scatter path must equal the sum-merged
        # union of the per-query reference shape, worker by worker
        for q in queries[:C2T_PARITY_QUERIES]:
            merged: dict[str, float] = {}
            for i in (1, 2):
                hits = _json.loads(post(("127.0.0.1", ports[i]),
                                        "/worker/process",
                                        _json.dumps({"query": q}).encode()))
                for h in hits:
                    nm = h["document"]["name"]
                    merged[nm] = merged.get(nm, 0.0) + float(h["score"])
            want = dict(sorted(merged.items(),
                               key=lambda kv: (-kv[1], kv[0]))[:TOP_K])
            have = _json.loads(start(q))
            assert list(have) == list(want), (q, have, want)
            for nm in want:
                np.testing.assert_allclose(have[nm], want[nm], rtol=1e-5,
                                           err_msg=f"{q!r} {nm}")
        log(f"[c2t] leader-vs-direct merge parity OK on "
            f"{C2T_PARITY_QUERIES} queries")

        # isolate the leader layer: same load straight at the TPU worker
        # through the reference-shaped per-query endpoint
        tpu_hp = ("127.0.0.1", ports[1])

        def direct(q):
            return post(tpu_hp, "/worker/process", q.encode())

        with concurrent.futures.ThreadPoolExecutor(C2T_CLIENTS) as ex:
            list(ex.map(direct, queries[:C2T_QUERIES]))
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(C2T_CLIENTS) as ex:
            list(ex.map(direct, queries[C2T_QUERIES:2 * C2T_QUERIES]))
        direct_qps = C2T_QUERIES / (time.perf_counter() - t0)

        lat_ms = float(np.median(lat))
        log(f"[c2t] /leader/start best: {qps:.1f} q/s "
            f"({best['clients']} clients, mean scatter batch "
            f"{breakdown['mean_scatter_batch']}); direct per-query "
            f"worker {direct_qps:.1f} q/s; lone-query {lat_ms:.0f}ms")
        return {"qps": qps,
                "sweep": windows,
                "direct_worker_qps": round(direct_qps, 1),
                "latency_ms": round(lat_ms, 1),
                "upload_dps_tpu": round(C2T_TPU_SHARE / up1_s, 1),
                "n_docs": C2T_DOCS, "tpu_share": C2T_TPU_SHARE,
                "clients": best["clients"],
                "kinds": kinds, "binary_rejected_415": rejected,
                "breakdown": breakdown,
                "parity_checked": True,
                "workers": 2,
                "backend": "tpu worker + cpu worker, realistic text"}
    finally:
        _kill_all(procs)


# --------------------------------------------------------------------------
# config 5: 5M-term vocabulary stress (VERDICT r2 #3b)
# --------------------------------------------------------------------------

C5_DOCS = 200_000
C5_VOCAB = 5_000_000
C5_AVG_LEN = 120
C5_BATCH = 512


def bench_5m_vocab(rng) -> dict:
    """Extreme-sparsity stress: a bigram/trigram-sized vocabulary
    (5M terms). Exercises df replication at 20MB, the [vocab]-sized
    slot_of scatter in _compile_queries, and the ELL build under a
    vocabulary 25x larger than the north star's."""
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    t0 = time.perf_counter()
    offsets, ids, tfs, lengths = make_doc_arrays(
        rng, C5_DOCS, C5_VOCAB, C5_AVG_LEN)
    log(f"[c5] corpus: {C5_DOCS} docs, {C5_VOCAB} vocab, "
        f"nnz={ids.shape[0]}, gen {time.perf_counter()-t0:.0f}s")
    engine = Engine(Config(query_batch=C5_BATCH))
    t0 = time.perf_counter()
    # register the full 5M-term space (the n-gram dictionary); ids map
    # 1:1 so add_document_arrays can take the corpus ids directly
    for i in range(C5_VOCAB):
        engine.vocab.add(f"t{i}")
    vocab_s = time.perf_counter() - t0
    add = engine.index.add_document_arrays
    t0 = time.perf_counter()
    for i in range(C5_DOCS):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
    ingest_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine.commit()
    commit_s = time.perf_counter() - t0
    queries = make_queries(rng, C5_VOCAB, 4 * C5_BATCH)
    engine.search_batch(queries[:C5_BATCH], k=TOP_K)
    engine.search_batch(queries[C5_BATCH:2 * C5_BATCH], k=TOP_K)
    timed = queries[2 * C5_BATCH:4 * C5_BATCH]
    with _measured_window("c5-serving", steady_state=True):
        t0 = time.perf_counter()
        hits = engine.search_batch(timed, k=TOP_K)
        qps = len(timed) / (time.perf_counter() - t0)
    assert any(hits), "5M-vocab index must answer queries"
    log(f"[c5] vocab {vocab_s:.0f}s, ingest {C5_DOCS/ingest_s:.0f} "
        f"docs/s, commit {commit_s:.1f}s, {qps:.0f} q/s")
    return {"qps": round(qps, 1), "vocab_register_s": round(vocab_s, 1),
            "ingest_dps": round(C5_DOCS / ingest_s, 1),
            "commit_s": round(commit_s, 1), "n_docs": C5_DOCS,
            "vocab": C5_VOCAB}


# --------------------------------------------------------------------------
# --kernel: the r14 kernel-headroom bench (ISSUE 15)
# --------------------------------------------------------------------------
#
# Three measurements behind one artifact, all ASSERTED before emission
# (the probe_msmarco discipline: an artifact must never record its own
# failure silently):
#
# 1. scoring-step ms/batch, A-build v3 vs v4 vs the XLA oracle, with
#    an in-run parity gate (v3==v4 bitwise; both ~= XLA; identical
#    top-10);
# 2. the analytic A-build op-count model — on a box without the chip
#    this is the acceptance evidence (interpret-mode timings measure
#    the interpreter, not the VPU; the backend is stamped so nobody
#    mistakes the CPU control for a hardware number);
# 3. steady-state commit cost, incremental-df vs the full-recompute
#    control, swept across a 4x corpus range on BOTH the mesh-ELL
#    index (the ~1s/commit-at-1M-docs headroom item) and the segments
#    index, with the df_full_recomputes witness pinned at zero for
#    every steady commit.

KB_MESH_SWEEP = (12_500, 25_000, 50_000)   # 4x corpus range
KB_SEG_SWEEP = (12_500, 25_000, 50_000)
KB_VOCAB = 20_000
KB_AVG_LEN = 40
KB_BATCH_DOCS = 500                        # steady-commit batch: the
KB_COMMITS = 8                             # 8-batch total stays under
                                           # delta_rebuild_frac x the
                                           # smallest base corpus, so
                                           # no PLANNED fold lands in
                                           # the steady window either


def kernel_cost_model() -> dict:
    """The A-build op-count model (PERF.md r2 item 2, priced per
    padded entry per uniq lane; total A-build work = this number x
    nnz_padded x ceil(n_uniq/TU)*TU). v3 spends 1 compare + 1 select
    + 1 accumulate add, all on i32/f32 vregs. v4 processes two width
    rows per iteration: within a document row live term ids are
    distinct and pads carry impact 0, so the pair folds into one
    nested select chain and ONE accumulate add (the adds-per-entry
    halve). A count from the kernel's source, not a measured speed."""
    v3 = {"compare": 1.0, "select": 1.0, "accumulate_add": 1.0}
    v4 = {"compare": 1.0, "select": 1.0, "accumulate_add": 0.5}
    return {
        "unit": "vreg_ops_per_padded_entry_per_uniq_lane",
        "scaling": "total = per_entry x nnz_padded x ceil(U/TU)*TU",
        "v3": v3, "v3_total": sum(v3.values()),
        "v4": v4, "v4_total": sum(v4.values()),
        "v4_ratio": round(sum(v3.values()) / sum(v4.values()), 3),
        "halved_components": {
            "accumulate_adds_per_entry": [1.0, 0.5],
        },
    }


def bench_kernel_scoring(rng) -> dict:
    """One eligible block scored by v3 / v4 / the XLA reduce-fusion
    oracle — parity gated, then timed on whatever backend is attached
    (stamped; on CPU both Pallas variants run the interpreter, so the
    ms are a control, not a hardware claim)."""
    import jax
    import jax.numpy as jnp

    from kernel_parity import make_case
    from tfidf_tpu.ops.ell import _score_block, score_block_pallas
    from tfidf_tpu.ops.scoring import _compile_queries

    out = {"backend": jax.default_backend(),
           "mosaic_compiled": jax.default_backend() == "tpu",
           "cases": []}
    for vocab in (30_000, 200_000):
        kw = dict(rows_cap=2048, width=64, n_rows=1900, B=256,
                  n_terms=4, u_req=512, vocab=vocab)
        imp, term, qb = make_case(rng, **kw)
        imp_d, term_d = jnp.asarray(imp), jnp.asarray(term)
        slot_of, qc_ext = _compile_queries(qb, vocab)
        uniq = jnp.asarray(qb.uniq)
        n_uniq = jnp.asarray(qb.n_uniq)

        def timed(fn, reps=3):
            jax.block_until_ready(fn())            # warm/compile
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn())
            return (time.perf_counter() - t0) / reps * 1e3

        runs = {
            "xla_ms": timed(lambda: _score_block(
                imp_d, term_d, slot_of, qc_ext.T, 2048)),
            "v3_ms": timed(lambda: score_block_pallas(
                imp_d, term_d, uniq, n_uniq, qc_ext,
                a_build="v3")),
            "v4_ms": timed(lambda: score_block_pallas(
                imp_d, term_d, uniq, n_uniq, qc_ext,
                a_build="v4")),
        }
        # parity gate BEFORE any number leaves this function
        ref = np.asarray(_score_block(imp_d, term_d, slot_of,
                                      qc_ext.T, 2048))
        v3 = np.asarray(score_block_pallas(
            imp_d, term_d, uniq, n_uniq, qc_ext,
            a_build="v3"))
        v4 = np.asarray(score_block_pallas(
            imp_d, term_d, uniq, n_uniq, qc_ext,
            a_build="v4"))
        assert np.array_equal(v3, v4), "v3/v4 bitwise parity failed"
        max_abs = float(np.max(np.abs(v4 - ref)))
        assert max_abs < 1e-4, f"kernel/XLA delta {max_abs}"
        t_ref = np.argsort(-ref, axis=1, kind="stable")[:, :TOP_K]
        t_v4 = np.argsort(-v4, axis=1, kind="stable")[:, :TOP_K]
        assert (t_ref == t_v4).all(), "top-k drifted vs the oracle"
        out["cases"].append({
            **{k: v for k, v in kw.items()},
            "max_abs_delta_vs_xla": max_abs,
            "v3_v4_bitwise_equal": True,
            "topk_identical": True,
            **{k: round(v, 2) for k, v in runs.items()},
            "v3_over_v4": round(runs["v3_ms"]
                                / max(runs["v4_ms"], 1e-9), 3),
        })
        log(f"[kb] scoring vocab={vocab}: " + " ".join(
            f"{k}={v:.1f}ms" for k, v in runs.items()))
    return out


def _kb_commit_sweep(rng, make_index, sweep, *, settle=None) -> dict:
    """Steady-commit timing: build a base corpus, then KB_COMMITS
    batches of KB_BATCH_DOCS each, committed and timed, for the
    incremental path and the full-recompute control. Returns per-size
    p50s plus the witness deltas (must be zero on the incremental
    path — asserted by the caller before emission)."""
    from tfidf_tpu.engine import Engine  # noqa: F401 (doc anchor)

    out = {"sweep_docs": list(sweep), "batch_docs": KB_BATCH_DOCS,
           "commits": KB_COMMITS, "incremental": {}, "control": {}}
    for label, df_incremental in (("incremental", True),
                                  ("control", False)):
        for n_docs in sweep:
            engine = make_index(df_incremental, n_docs)
            offsets, ids, tfs, lengths = make_doc_arrays(
                rng, n_docs + (KB_COMMITS + 1) * KB_BATCH_DOCS,
                KB_VOCAB, KB_AVG_LEN)
            add = engine.index.add_document_arrays
            for i in range(n_docs):
                lo, hi = offsets[i], offsets[i + 1]
                add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
            engine.commit()
            if settle is not None:
                settle(engine)
            # one WARMUP append commit before the timed window: the
            # mesh index promotes its floor delta to threshold sizing
            # on the first append burst (one amortized overflow
            # rebuild, by design — read-mostly indexes skip it); the
            # steady window must measure steady commits
            for i in range(n_docs, n_docs + KB_BATCH_DOCS):
                lo, hi = offsets[i], offsets[i + 1]
                add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
            engine.commit()
            w0 = engine.index.df_full_recomputes
            times = []
            done = n_docs + KB_BATCH_DOCS
            for _c in range(KB_COMMITS):
                for i in range(done, done + KB_BATCH_DOCS):
                    lo, hi = offsets[i], offsets[i + 1]
                    add(f"d{i}", ids[lo:hi], tfs[lo:hi],
                        float(lengths[i]))
                done += KB_BATCH_DOCS
                t0 = time.perf_counter()
                engine.commit()
                times.append((time.perf_counter() - t0) * 1e3)
            p50 = float(np.percentile(np.asarray(times), 50))
            out[label][str(n_docs)] = {
                "commit_ms_p50": round(p50, 1),
                "commit_ms_max": round(max(times), 1),
                "witness_delta":
                    engine.index.df_full_recomputes - w0,
            }
            log(f"[kb] {label} {n_docs} docs: commit p50 "
                f"{p50:.1f}ms witness_delta="
                f"{engine.index.df_full_recomputes - w0}")
            # only the LARGEST incremental engine is used afterwards
            # (parity + search gates); dropping the rest keeps peak
            # bench memory at one resident index, not six
            if label == "incremental" and n_docs == max(sweep):
                out["_engine"] = engine
            del engine
    return out


def bench_segment_commits(rng) -> dict:
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    def make_index(df_incremental, _n):
        engine = Engine(Config(index_mode="segments", query_batch=8,
                               df_incremental=df_incremental))
        for i in range(KB_VOCAB):
            engine.vocab.add(f"t{i}")
        return engine

    def settle(engine):
        engine.index.wait_for_merges()
        engine.commit()

    out = _kb_commit_sweep(rng, make_index, KB_SEG_SWEEP,
                           settle=settle)
    # witness + parity gates (assert-before-emit)
    for n_docs, rec in out["incremental"].items():
        assert rec["witness_delta"] == 0, \
            f"segments steady commits recomputed df at {n_docs} docs"
    eng = out.pop("_engine")
    snap = eng.index.snapshot
    df_o, count_o, len_o, _live = eng.index._stats_scratch_locked(
        snap.df.shape[0])
    np.testing.assert_array_equal(np.asarray(snap.df), df_o)
    assert float(np.asarray(snap.n_docs)) == float(count_o)
    hits = eng.search_batch([f"t{i} t{i+7}" for i in range(8)], k=5)
    assert any(hits), "segments sweep engine failed the search gate"
    out["df_parity_exact"] = True
    out["search_ok"] = True
    return out


def bench_mesh_commits(rng) -> dict:
    """The VERDICT r5 #8 carry-over at bench scale: steady mesh-ELL
    commit cost, incremental journal vs the O(corpus nnz) recompute
    control, plus a small serving check. On CPU this is the stamped
    control run; on a chip the same fields are re-emitted from
    hardware."""
    import jax

    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    def make_index(df_incremental, _n):
        engine = Engine(Config(engine_mode="mesh", query_batch=32,
                               df_incremental=df_incremental))
        for i in range(KB_VOCAB):
            engine.vocab.add(f"t{i}")
        return engine

    out = _kb_commit_sweep(rng, make_index, KB_MESH_SWEEP)
    out["backend"] = jax.default_backend()
    for n_docs, rec in out["incremental"].items():
        assert rec["witness_delta"] == 0, \
            f"mesh steady commits recomputed df at {n_docs} docs"
    eng = out.pop("_engine")               # the largest-corpus engine
    # exactly TWO rebuilds: the base build + the warmup commit's
    # one-time delta promotion — none inside the steady window (the
    # witness would be meaningless if the delta folded mid-sweep)
    assert eng.index.rebuilds == 2, eng.index.rebuilds
    cap = eng.vocab.capacity()
    inc = eng.index._live_stats(cap)
    scr = eng.index._live_stats_scratch(cap)
    np.testing.assert_array_equal(inc[0], scr[0])
    assert inc[1] == scr[1]
    snap = eng.index.snapshot
    np.testing.assert_array_equal(
        np.asarray(snap.df_g)[:cap], scr[0][:cap])
    out["df_parity_exact"] = True
    # serving gate + a small q/s control (1 warm + 2 timed chunks)
    queries = make_queries(rng, KB_VOCAB, 128)
    eng.search_batch(queries[:32], k=TOP_K)
    t0 = time.perf_counter()
    hits = eng.search_batch(queries[32:96], k=TOP_K)
    qps = 64 / (time.perf_counter() - t0)
    assert any(hits), "mesh sweep engine failed the search gate"
    out["search_ok"] = True
    out["serving_qps_control"] = round(qps, 1)
    return out


def kernel_main() -> None:
    rng = np.random.default_rng(SEED)
    import jax
    backend = jax.default_backend()
    scoring = bench_kernel_scoring(rng)
    cost = kernel_cost_model()
    seg = bench_segment_commits(rng)
    mesh = bench_mesh_commits(rng)

    def p50s(block):
        return {n: rec["commit_ms_p50"]
                for n, rec in block.items()
                if isinstance(rec, dict) and "commit_ms_p50" in rec}
    mesh_inc = p50s(mesh["incremental"])
    mesh_ctl = p50s(mesh["control"])
    lo, hi = str(min(KB_MESH_SWEEP)), str(max(KB_MESH_SWEEP))
    seg_hi = str(max(KB_SEG_SWEEP))      # the sweeps tune independently
    # the acceptance gate: steady mesh commits independent of corpus
    # size across the 4x sweep (generous CPU-noise bound), while the
    # control's recompute term grows with the corpus
    flat_ratio = mesh_inc[hi] / max(mesh_inc[lo], 1e-9)
    assert flat_ratio < 2.5, \
        f"incremental mesh commit grew {flat_ratio:.2f}x over the sweep"
    result = {
        "metric": "kernel_a_build_v4_cost_model_ratio",
        # an op count from the kernel source: v3/v4 vreg-ops per entry
        "value": cost["v4_ratio"],
        "unit": "x_fewer_a_build_vreg_ops",
        # denominator story: measured scoring-step ratio on THIS
        # backend (interpret-mode control on CPU — stamped above)
        "vs_baseline": scoring["cases"][0]["v3_over_v4"],
        "extra": {
            "backend": backend,
            "a_build_cost_model": cost,
            "kernel_scoring": scoring,
            "segments_commit_sweep": seg,
            "mesh_commit_sweep": mesh,
            "mesh_commit_p50_old_vs_new_ms": {
                "corpus_docs": int(hi),
                "old_full_recompute": mesh_ctl[hi],
                "new_incremental": mesh_inc[hi],
                "old_over_new": round(
                    mesh_ctl[hi] / max(mesh_inc[hi], 1e-9), 2),
            },
            "mesh_commit_flat_ratio_4x": round(flat_ratio, 3),
            "witness_steady_deltas_all_zero": True,
            "hardware_note": "on a CPU backend the kernel ms are "
                             "an interpret-mode control; on-chip "
                             "v3-vs-v4 speed: not measured",
        },
    }
    headline = {
        "cost_model_v4_ratio": cost["v4_ratio"],
        "mesh_commit_p50_old_ms": mesh_ctl[hi],
        "mesh_commit_p50_new_ms": mesh_inc[hi],
        "mesh_commit_flat_ratio_4x": round(flat_ratio, 3),
        "seg_commit_p50_new_ms":
            seg["incremental"][seg_hi]["commit_ms_p50"],
        "backend": backend,
    }
    _emit_validated(result, headline)


# --------------------------------------------------------------------------
# hybrid retrieval (BENCH_r11.json): the dense plane beside the sparse
# one (ISSUE 17) — batched dense q/s with the achieved matmul flop
# rate, a sparse/dense/hybrid latency table on the SAME engine and
# query stream, and fused-vs-sparse relevance deltas on the synthetic
# MS MARCO-style slice (tfidf_tpu/utils/textgen.py: real-English
# lexicon, zipfian draws, passage-length docs)
# --------------------------------------------------------------------------

HY_DOCS = 20_000
HY_AVG_LEN = 60
HY_BATCH = 256
HY_BATCHES = 4
HY_REL_QUERIES = 200


def bench_hybrid(rng) -> dict:
    import jax

    from tfidf_tpu.cluster import fusion
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config
    from tfidf_tpu.utils.textgen import RealisticCorpus, harvest_lexicon

    t0 = time.perf_counter()
    words, _ = harvest_lexicon()
    gen = RealisticCorpus(rng, words)
    texts = [gen.make_text(HY_AVG_LEN) for _ in range(HY_DOCS)]
    log(f"[hy] {HY_DOCS} passage docs from a {len(words)}-word "
        f"lexicon in {time.perf_counter()-t0:.0f}s")

    # dim 256 (vs the 64 default): the hash projection's distortion of
    # the true bag cosine shrinks ~1/sqrt(dim), and relevance is the
    # point of this round — dense quality here is the PROJECTION's,
    # the learned-encoder seam stays pluggable (register_embedder)
    cfg = Config(query_batch=HY_BATCH, embedding_dim=256)
    engine = Engine(cfg)
    t0 = time.perf_counter()
    for i, text in enumerate(texts):
        engine.ingest_text(f"d{i}.txt", text)
    engine.commit()
    log(f"[hy] ingest+commit (sparse + {cfg.embedding_dim}-dim "
        f"embedding column) in {time.perf_counter()-t0:.1f}s")

    def make_query() -> str:
        k = int(rng.integers(2, 5))
        idx = rng.choice(len(words), size=k, p=gen.p)
        return " ".join(words[i] for i in idx)

    queries = [make_query() for _ in range(HY_BATCH * (HY_BATCHES + 2))]
    stream = queries[2 * HY_BATCH:]

    def fused_lists(qs, method):
        sp_hits = engine.search_batch(qs, k=TOP_K)
        dn_hits = engine.search_dense_batch(qs, k=TOP_K)
        out = []
        for sh, dh in zip(sp_hits, dn_hits):
            merged = fusion.fuse(
                {h.name: h.score for h in sh}, dict(dh),
                method=method, k=TOP_K, rrf_k=cfg.fusion_rrf_k,
                w_sparse=cfg.fusion_weight_sparse,
                w_dense=cfg.fusion_weight_dense)
            out.append(fusion.rank_list(merged, TOP_K))
        return out

    # warm every executable (sparse ELL, dense matmul) off the clock
    engine.search_batch(queries[:HY_BATCH], k=TOP_K)
    engine.search_dense_batch(queries[:HY_BATCH], k=TOP_K)
    fused_lists(queries[HY_BATCH:2 * HY_BATCH], "rrf")

    def timed(run):
        lats = []
        for b in range(HY_BATCHES):
            batch = stream[b * HY_BATCH:(b + 1) * HY_BATCH]
            t = time.perf_counter()
            run(batch)
            lats.append(time.perf_counter() - t)
        n = HY_BATCH * HY_BATCHES
        return {"qps": round(n / sum(lats), 1),
                "batch_ms_p50": round(
                    float(np.median(lats)) * 1e3, 2),
                "per_query_us": round(sum(lats) / n * 1e6, 1)}

    lat_sparse = timed(lambda b: engine.search_batch(b, k=TOP_K))
    lat_dense = timed(lambda b: engine.search_dense_batch(b, k=TOP_K))
    lat_hybrid = timed(lambda b: fused_lists(b, "rrf"))
    # achieved matmul flop rate from MODEL flops (2 * B * live_docs *
    # dim — padding excluded, so the number cannot flatter the kernel)
    dim = cfg.embedding_dim
    flops_q = 2.0 * HY_DOCS * dim
    gflops = lat_dense["qps"] * flops_q / 1e9
    log(f"[hy] sparse {lat_sparse['qps']} q/s, dense "
        f"{lat_dense['qps']} q/s ({gflops:.2f} GFLOP/s model flops), "
        f"hybrid {lat_hybrid['qps']} q/s (batch={HY_BATCH})")

    # fused-vs-sparse relevance on queries with a KNOWN target doc:
    # 3-4 tokens sampled from one passage; the metric is the target's
    # reciprocal rank in the top-10 (MRR@10) and hit rate (recall@10)
    def relevance(run_lists) -> tuple:
        mrr = hits = 0.0
        for qi, (q, want) in enumerate(rel_queries):
            ranked = rel_results[run_lists][qi]
            names = [n for n, _ in ranked[:TOP_K]]
            if want in names:
                hits += 1.0
                mrr += 1.0 / (names.index(want) + 1)
        n = len(rel_queries)
        return round(mrr / n, 4), round(hits / n, 4)

    rel_queries = []
    doc_ids = rng.choice(HY_DOCS, size=HY_REL_QUERIES, replace=False)
    for d in doc_ids:
        toks = [t for t in texts[int(d)].split()
                if len(t) > 3][:40]
        if len(toks) < 4:
            continue
        pick = rng.choice(len(toks), size=int(rng.integers(3, 5)),
                          replace=False)
        rel_queries.append((" ".join(toks[i] for i in pick),
                            f"d{int(d)}.txt"))
    qs = [q for q, _ in rel_queries]
    rel_results = {
        "sparse": [[(h.name, h.score) for h in hs]
                   for hs in engine.search_batch(qs, k=TOP_K)],
        "dense": engine.search_dense_batch(qs, k=TOP_K),
        "hybrid_rrf": fused_lists(qs, "rrf"),
        "hybrid_wsum": fused_lists(qs, "wsum"),
    }
    rel = {mode: {"mrr_at_10": m, "recall_at_10": r}
           for mode, (m, r) in
           ((mode, relevance(mode)) for mode in rel_results)}
    log(f"[hy] relevance over {len(rel_queries)} known-target "
        f"queries: " + ", ".join(
            f"{m} mrr={v['mrr_at_10']}" for m, v in rel.items()))

    return {
        "docs": HY_DOCS, "batch": HY_BATCH, "top_k": TOP_K,
        "embedding": engine.dense_stats(),
        "latency": {"sparse": lat_sparse, "dense": lat_dense,
                    "hybrid_rrf": lat_hybrid},
        "dense_model_gflops_per_s": round(gflops, 3),
        "relevance": rel,
        "relevance_queries": len(rel_queries),
        "backend": jax.default_backend(),
    }


def hybrid_main() -> None:
    """Standalone entry (``python bench.py --hybrid``; ``make
    bench-hybrid`` sets ``BENCH_OUT=BENCH_r11.json``). The headline is
    the batched dense q/s; ``vs_baseline`` is dense q/s over sparse
    q/s on the SAME engine/stream (how much the new plane costs
    relative to the plane it rides beside). The backend is stamped
    honestly per the r09 precedent — a CPU-control run says ``cpu``
    and the flop rate is MODEL flops, never padded-shape flops."""
    os.environ.setdefault("BENCH_OUT", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r11.json"))
    rng = np.random.default_rng(SEED)
    hy = bench_hybrid(rng)
    result = {
        "metric": "hybrid_dense_batched_qps_20k_docs",
        "value": hy["latency"]["dense"]["qps"],
        "unit": "queries/sec",
        "vs_baseline": round(hy["latency"]["dense"]["qps"]
                             / hy["latency"]["sparse"]["qps"], 3),
        "extra": hy,
    }
    headline = {
        "dense_qps": hy["latency"]["dense"]["qps"],
        "sparse_qps": hy["latency"]["sparse"]["qps"],
        "hybrid_qps": hy["latency"]["hybrid_rrf"]["qps"],
        "dense_model_gflops_per_s": hy["dense_model_gflops_per_s"],
        "mrr_sparse": hy["relevance"]["sparse"]["mrr_at_10"],
        "mrr_dense": hy["relevance"]["dense"]["mrr_at_10"],
        "mrr_hybrid_rrf":
            hy["relevance"]["hybrid_rrf"]["mrr_at_10"],
        "mrr_hybrid_wsum":
            hy["relevance"]["hybrid_wsum"]["mrr_at_10"],
        "backend": hy["backend"],
    }
    _emit_validated(result, headline)


# --------------------------------------------------------------------------
# tiered postings (BENCH_r12.json): beyond-HBM corpora (ISSUE 18) — a
# 30M-doc run whose blocked-ELL footprint provably exceeds the
# configured hot budget, so the bulk of the corpus lives in manifested
# cold spills and streams through the double-buffered upload ring. The
# corpus is TIME-DRIFTING: every doc draws from a shared zipfian head
# vocabulary plus its ingest phase's own discriminative slice — the
# log-structured reality (recent segments answer most queries, old
# segments go topically stale) that segment-granular block-max skipping
# exploits, and the workload where Lucene's tiered merges + skip lists
# earn their keep. Queries are zipfian on BOTH axes: head terms by
# corpus frequency, slice terms by zipfian recency over phases. Gates
# asserted loudly BEFORE emission: exact top-k parity tiered-vs-bypass
# EVERY phase, cumulative cold-segment skip rate > 0.5, flat
# steady-state ingest dps with the df_full_recomputes witness at its
# first-commit value, and corpus device bytes > hot budget.
# --------------------------------------------------------------------------

TI_DOCS = int(os.environ.get("TIER_DOCS", 30_000_000))
TI_PHASE = int(os.environ.get("TIER_PHASE", 1_000_000))
TI_HEAD = 20_000     # shared zipfian head vocabulary
TI_SLICE = 6_000     # per-phase discriminative slice
TI_HEAD_LEN = 6      # head tokens per doc (zipf over TI_HEAD)
TI_SLICE_LEN = 2     # slice tokens per doc (zipf over the phase slice)
TI_BUDGET_MB = int(os.environ.get("TIER_BUDGET_MB", 256))
TI_QUERIES = 64
TI_QBATCH = 8        # dispatch chunk: the skip proof is per CHUNK
                     # (a segment skips only when provably useless for
                     # EVERY query in the chunk), so the measured unit
                     # is small homogeneous chunks — the serving shape
                     # of discriminative tail queries, not the 512-wide
                     # head-traffic batches of the north-star bench
TI_K = 10


def _tier_phase_corpus(rng, phase: int, n_docs: int):
    """One phase's docs: a zipfian head part plus a zipfian slice part,
    each synthesized by :func:`make_doc_arrays` and merged per doc.
    Slice ids are remapped above the head block (monotonic, and every
    slice id exceeds every head id, so per-doc concatenation keeps the
    sorted-unique contract of ``add_document_arrays``)."""
    off_h, ids_h, tfs_h, len_h = make_doc_arrays(
        rng, n_docs, TI_HEAD, TI_HEAD_LEN)
    off_s, ids_s, tfs_s, len_s = make_doc_arrays(
        rng, n_docs, TI_SLICE, TI_SLICE_LEN)
    ids_s = (ids_s.astype(np.int64)
             + TI_HEAD + phase * TI_SLICE).astype(np.int32)
    return (off_h, ids_h, tfs_h, len_h), (off_s, ids_s, tfs_s, len_s)


def _tier_queries(rng, phase: int) -> list[str]:
    """Zipfian discriminative query stream, laid out in ``TI_QBATCH``
    chunks. Each query draws 2-3 slice terms (zipf-local) from a
    zipfian-recency phase — recent slices queried most, the tiering
    bet. The LAST chunk additionally carries a zipfian head term per
    query: head terms live in every segment, so that chunk can only
    skip through a genuine MAXSCORE threshold cut (head bound below
    the slice-driven kk-th candidate), while the pure-slice chunks
    skip mostly on provably-zero term overlap."""
    qs = []
    n_chunks = TI_QUERIES // TI_QBATCH
    for c in range(n_chunks):
        for _ in range(TI_QBATCH):
            back = min(int(rng.zipf(1.5)) - 1, phase)
            p = phase - back
            terms = [f"t{TI_HEAD + p * TI_SLICE + int(rng.zipf(1.25) % TI_SLICE)}"
                     for _ in range(int(rng.integers(2, 4)))]
            if c == n_chunks - 1:
                terms.append(f"t{int(rng.zipf(1.25) % TI_HEAD)}")
            qs.append(" ".join(terms))
    return qs


def bench_tier(rng) -> dict:
    import shutil
    import tempfile

    import jax

    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    n_phases = max(1, TI_DOCS // TI_PHASE)
    work = tempfile.mkdtemp(prefix="bench_tier_")
    # max_segments > n_phases: the segment IS the tiering/skipping unit
    # here — merge economics have their own bench (r08/r09); embedding
    # off because the arrays ingest path bypasses the text pipeline the
    # dense column rides (its bench is r11)
    cfg = Config(index_mode="segments", query_batch=TI_QBATCH,
                 index_path=os.path.join(work, "index"),
                 tier_enabled=True, tier_hot_budget_mb=TI_BUDGET_MB,
                 max_segments=max(64, n_phases + 2),
                 embedding_enabled=False)
    engine = Engine(cfg)
    try:
        t0 = time.perf_counter()
        for i in range(TI_HEAD + n_phases * TI_SLICE):
            engine.vocab.add(f"t{i}")
        log(f"[ti] vocab ({TI_HEAD + n_phases * TI_SLICE} terms) in "
            f"{time.perf_counter() - t0:.1f}s")
        add = engine.index.add_document_arrays
        phase_dps, commit_s, tiered_s_all, skip_rates = [], [], [], []
        skipped_cum = consults_cum = 0
        tiered_qps = bypass_qps = 0.0
        for phase in range(n_phases):
            head, slc = _tier_phase_corpus(rng, phase, TI_PHASE)
            off_h, ids_h, tfs_h, len_h = head
            off_s, ids_s, tfs_s, len_s = slc
            t0 = time.perf_counter()
            for i in range(TI_PHASE):
                hlo, hhi = off_h[i], off_h[i + 1]
                slo, shi = off_s[i], off_s[i + 1]
                add(f"p{phase}_d{i}",
                    np.concatenate([ids_h[hlo:hhi], ids_s[slo:shi]]),
                    np.concatenate([tfs_h[hlo:hhi], tfs_s[slo:shi]]),
                    float(len_h[i] + len_s[i]))
            ingest_s = time.perf_counter() - t0
            phase_dps.append(TI_PHASE / ingest_s)
            t0 = time.perf_counter()
            engine.commit()
            commit_s.append(time.perf_counter() - t0)
            # ---- measured phase: tiered (timed + skip stats), then
            # the bypass oracle for the exact-parity gate ----
            qs = _tier_queries(rng, phase)
            st0 = engine.tier_stats()
            t0 = time.perf_counter()
            tiered_hits = engine.search_batch(qs, k=TI_K)
            tiered_s = time.perf_counter() - t0
            tiered_s_all.append(tiered_s)
            st1 = engine.tier_stats()
            d_skip = st1["segments_skipped"] - st0["segments_skipped"]
            d_cons = (d_skip
                      + st1["hot_hits"] - st0["hot_hits"]
                      + st1["cold_faults"] - st0["cold_faults"])
            skipped_cum += d_skip
            consults_cum += d_cons
            skip_rates.append(d_skip / d_cons if d_cons else 0.0)
            # exact-parity gate vs the score-everything bypass oracle:
            # one pure-slice chunk + the mixed (threshold-cut) chunk —
            # the full-stream parity matrix lives in tests/test_tiering
            par_idx = (list(range(TI_QBATCH))
                       + list(range(TI_QUERIES - TI_QBATCH, TI_QUERIES)))
            engine.searcher.tier_bypass = True
            try:
                par_qs = [qs[i] for i in par_idx]
                bypass_hits = engine.search_batch(par_qs, k=TI_K)
                got = [[(h.name, h.score) for h in tiered_hits[i]]
                       for i in par_idx]
                want = [[(h.name, h.score) for h in hs]
                        for hs in bypass_hits]
                if got != want:
                    print(f"BENCH GATE FAILED: tiered top-k diverged "
                          f"from the untiered oracle at phase {phase}",
                          file=sys.stderr)
                    sys.exit(1)
                if phase == n_phases - 1:
                    # the oracle's final timing run scores EVERYTHING;
                    # its parity pass above already faulted the parity
                    # chunks' segments in, the rest upload here (the
                    # cost an untiered engine pays by construction)
                    t0 = time.perf_counter()
                    engine.search_batch(qs, k=TI_K)
                    bypass_qps = TI_QUERIES / (time.perf_counter() - t0)
                    tiered_qps = TI_QUERIES / tiered_s
            finally:
                engine.searcher.tier_bypass = False
            engine.tier.rebalance()   # re-evict what the oracle pulled in
            if phase % 5 == 0 or phase == n_phases - 1:
                log(f"[ti] phase {phase}: {phase_dps[-1]:.0f} dps, "
                    f"commit {commit_s[-1]:.1f}s, skip "
                    f"{skip_rates[-1]:.2f}, search {tiered_s * 1e3:.0f}ms")
        st = engine.tier_stats()
        device_total = sum(int(s.device_bytes)
                           for s in engine.index._segments)
        skip_rate = skipped_cum / max(consults_cum, 1)
        # ---- gates (all loud): the artifact may not exist unless the
        # run actually proved what it claims ----
        if device_total <= st["budget_bytes"]:
            print("BENCH GATE FAILED: corpus fits the hot budget — "
                  "nothing was proven about tiering", file=sys.stderr)
            sys.exit(1)
        if skip_rate <= 0.5:
            print(f"BENCH GATE FAILED: cold-segment skip rate "
                  f"{skip_rate:.3f} <= 0.5", file=sys.stderr)
            sys.exit(1)
        if engine.index.df_full_recomputes != 1:
            print(f"BENCH GATE FAILED: df_full_recomputes = "
                  f"{engine.index.df_full_recomputes} (tiered steady-"
                  f"state commits must stay incremental)",
                  file=sys.stderr)
            sys.exit(1)
        if phase_dps[-1] < 0.5 * phase_dps[0]:
            print(f"BENCH GATE FAILED: ingest dps decayed "
                  f"{phase_dps[0]:.0f} -> {phase_dps[-1]:.0f}",
                  file=sys.stderr)
            sys.exit(1)
        log(f"[ti] {n_phases * TI_PHASE} docs, {len(engine.index._segments)} "
            f"segments, {device_total >> 20}MB corpus vs "
            f"{st['budget_bytes'] >> 20}MB budget; skip {skip_rate:.3f}, "
            f"hit {st['hit_rate']:.3f}, ring stall {st['ring_stall_s']:.2f}s; "
            f"tiered {tiered_qps:.1f} q/s vs score-everything "
            f"{bypass_qps:.1f} q/s")
        return {
            "docs": n_phases * TI_PHASE, "phases": n_phases,
            "vocab": TI_HEAD + n_phases * TI_SLICE, "top_k": TI_K,
            "budget_mb": TI_BUDGET_MB,
            "segments": len(engine.index._segments),
            "corpus_device_mb": device_total >> 20,
            "device_over_budget_x": round(
                device_total / st["budget_bytes"], 2),
            "tiered_qps": round(tiered_qps, 1),
            "bypass_qps": round(bypass_qps, 1),
            "skip_rate": round(skip_rate, 4),
            "skip_rate_per_phase": [round(r, 3) for r in skip_rates],
            "hot_hit_rate": round(st["hit_rate"], 4),
            "ring_stall_s": round(st["ring_stall_s"], 3),
            "spills": st["spills"], "evictions": st["evictions"],
            "quarantines": st["quarantines"],
            "ingest_dps_per_phase": [round(d, 1) for d in phase_dps],
            "ingest_dps_first": round(phase_dps[0], 1),
            "ingest_dps_last": round(phase_dps[-1], 1),
            "commit_s_per_phase": [round(s, 2) for s in commit_s],
            "df_full_recomputes": engine.index.df_full_recomputes,
            "parity_checked_phases": n_phases,
            "backend": jax.default_backend(),
        }
    finally:
        if engine.tier is not None:
            engine.tier.close()
        shutil.rmtree(work, ignore_errors=True)


def tier_main() -> None:
    """Standalone entry (``python bench.py --tier``; ``make bench-tier``
    sets ``BENCH_OUT=BENCH_r12.json``). The headline is the tiered
    batched q/s on the beyond-budget corpus; ``vs_baseline`` is tiered
    q/s over the score-everything bypass oracle on the SAME engine and
    final query batch — what segment-granular block-max skipping buys
    once the corpus no longer fits the device. Backend stamped honestly
    per the r09 precedent: a CPU run says ``cpu``."""
    os.environ.setdefault("BENCH_OUT", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r12.json"))
    rng = np.random.default_rng(SEED)
    ti = bench_tier(rng)
    result = {
        "metric": "tiered_blockmax_qps_30m_docs_beyond_hbm",
        "value": ti["tiered_qps"],
        "unit": "queries/sec",
        "vs_baseline": round(ti["tiered_qps"]
                             / max(ti["bypass_qps"], 1e-9), 3),
        "extra": ti,
    }
    headline = {
        "tiered_qps": ti["tiered_qps"],
        "bypass_qps": ti["bypass_qps"],
        "skip_rate": ti["skip_rate"],
        "hot_hit_rate": ti["hot_hit_rate"],
        "ring_stall_s": ti["ring_stall_s"],
        "device_over_budget_x": ti["device_over_budget_x"],
        "ingest_dps_first": ti["ingest_dps_first"],
        "ingest_dps_last": ti["ingest_dps_last"],
        "docs": ti["docs"],
        "segments": ti["segments"],
        "backend": ti["backend"],
    }
    _emit_validated(result, headline)


# --------------------------------------------------------------------------
# r20: degraded-mode serving — the host-fallback scorer vs the healthy
# device path on the SAME engine, corpus, and query stream. The number
# that matters operationally is the honest cost of X-Compute-Degraded:
# how much q/s (and p99) a worker gives up when its device goes sick
# and its share rides the numpy mirror. Bit-parity is gated IN-RUN
# (the fallback's contract is "exact, just slower") before any timing
# is trusted.
# --------------------------------------------------------------------------

CP_DOCS = int(os.environ.get("COMPUTE_DOCS", 50_000))
CP_VOCAB = 30_000
CP_AVG_LEN = 60
CP_QUERIES = 256
CP_QBATCH = 32
CP_K = 10
CP_REPS = 3


def bench_compute(rng) -> dict:
    import shutil
    import tempfile

    import jax

    from tfidf_tpu.engine import Engine
    from tfidf_tpu.engine.compute_health import HostFallbackScorer
    from tfidf_tpu.utils.config import Config

    work = tempfile.mkdtemp(prefix="bench_compute_")
    # use_pallas=False: the fallback is pinned bit-equal to the XLA
    # reference program (the kernels are tolerance-gated against the
    # same reference in their own bench) — the parity gate below is
    # only meaningful against that path. Probe interval effectively
    # infinite so the degraded leg never sneaks a device probe into a
    # timed window.
    cfg = Config(index_path=os.path.join(work, "index"),
                 query_batch=CP_QBATCH, embedding_enabled=False,
                 use_pallas=False, compute_sick_after=2,
                 compute_probe_interval_s=1e9)
    engine = Engine(cfg)
    try:
        t0 = time.perf_counter()
        for i in range(CP_VOCAB):
            engine.vocab.add(f"t{i}")
        offsets, ids, tfs, lengths = make_doc_arrays(
            rng, CP_DOCS, CP_VOCAB, CP_AVG_LEN)
        add = engine.index.add_document_arrays
        for i in range(CP_DOCS):
            lo, hi = offsets[i], offsets[i + 1]
            add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
        engine.commit()
        log(f"[cp] ingest+commit {CP_DOCS} docs in "
            f"{time.perf_counter() - t0:.1f}s")
        queries = make_queries(rng, CP_VOCAB, CP_QUERIES)
        batches = [queries[i:i + CP_QBATCH]
                   for i in range(0, CP_QUERIES, CP_QBATCH)]

        # ---- in-run bit-parity gate: device vs host mirror, before
        # any timing is trusted ----
        fb = HostFallbackScorer(engine.searcher)
        d_vals, d_ids, _k, d_names = engine.searcher.search_arrays(
            batches[0], k=CP_K)
        h_vals, h_ids, _k2, h_names = fb.search_arrays(
            batches[0], k=CP_K)
        if (np.asarray(d_vals).tobytes() != h_vals.tobytes()
                or not np.array_equal(np.asarray(d_ids), h_ids)
                or list(d_names) != list(h_names)):
            print("BENCH SELF-VALIDATION FAILED: host fallback is not "
                  "bit-identical to the device path — the degraded "
                  "numbers below would be measuring a DIFFERENT "
                  "function", file=sys.stderr)
            sys.exit(1)
        log("[cp] parity gate: host fallback bit-identical to the "
            "device path")

        def timed_pass(tag: str) -> tuple[float, list]:
            lats = []
            with _measured_window(tag, steady_state=True):
                t0 = time.perf_counter()
                for _ in range(CP_REPS):
                    for b in batches:
                        b0 = time.perf_counter()
                        engine.search_batch(b, k=CP_K)
                        lats.append(time.perf_counter() - b0)
                total = time.perf_counter() - t0
            return CP_REPS * CP_QUERIES / total, lats

        def p(lats, q):
            return round(float(np.percentile(
                np.asarray(lats) * 1e3, q)), 3)

        # ---- healthy leg (device path), warmup excluded ----
        for b in batches[:2]:
            engine.search_batch(b, k=CP_K)
        assert not engine.pop_fallback_served()
        healthy_qps, h_lats = timed_pass("compute.healthy")

        # ---- degraded leg: force the health machine sick — every
        # request rides the host mirror, exactly what a worker serves
        # after its device OOMs to death ----
        for _ in range(cfg.compute_sick_after):
            engine.compute.note_fault("transient")
        engine.pop_fallback_served()
        for b in batches[:2]:          # mirror build + cache warm
            engine.search_batch(b, k=CP_K)
        if not engine.pop_fallback_served():
            print("BENCH SELF-VALIDATION FAILED: degraded leg is NOT "
                  "serving from the host fallback", file=sys.stderr)
            sys.exit(1)
        degraded_qps, d_lats = timed_pass("compute.degraded")
        if not engine.pop_fallback_served():
            print("BENCH SELF-VALIDATION FAILED: fallback flag vanished "
                  "mid-measurement (device probe leaked into the timed "
                  "window)", file=sys.stderr)
            sys.exit(1)

        return {
            "docs": CP_DOCS, "vocab": CP_VOCAB,
            "queries": CP_QUERIES, "query_batch": CP_QBATCH,
            "k": CP_K, "reps": CP_REPS,
            "healthy_qps": round(healthy_qps, 1),
            "healthy_p50_ms": p(h_lats, 50),
            "healthy_p99_ms": p(h_lats, 99),
            "degraded_qps": round(degraded_qps, 1),
            "degraded_p50_ms": p(d_lats, 50),
            "degraded_p99_ms": p(d_lats, 99),
            "degraded_slowdown_x": round(
                healthy_qps / max(degraded_qps, 1e-9), 2),
            "parity": "bit-exact",
            "backend": jax.devices()[0].platform,
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


def compute_main() -> None:
    """Standalone entry (``python bench.py --compute``;
    ``make bench-compute`` sets ``BENCH_OUT=BENCH_r13.json``). The
    headline is the host-fallback (degraded) q/s beside the healthy
    device-path q/s on the same engine and query stream;
    ``vs_baseline`` is degraded over healthy — the fraction of
    throughput a sick-device worker retains while serving honestly
    stamped X-Compute-Degraded replies. Backend stamped honestly per
    the r09 precedent: a CPU run says ``cpu``."""
    os.environ.setdefault("BENCH_OUT", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r13.json"))
    rng = np.random.default_rng(SEED)
    cp = bench_compute(rng)
    result = {
        "metric": "host_fallback_degraded_qps_50k_docs",
        "value": cp["degraded_qps"],
        "unit": "queries/sec",
        "vs_baseline": round(cp["degraded_qps"]
                             / max(cp["healthy_qps"], 1e-9), 3),
        "extra": cp,
    }
    headline = {
        "healthy_qps": cp["healthy_qps"],
        "degraded_qps": cp["degraded_qps"],
        "degraded_slowdown_x": cp["degraded_slowdown_x"],
        "healthy_p99_ms": cp["healthy_p99_ms"],
        "degraded_p99_ms": cp["degraded_p99_ms"],
        "parity": cp["parity"],
        "backend": cp["backend"],
    }
    _emit_validated(result, headline)


def _validated_json(obj: dict, what: str) -> str:
    """Serialize + re-parse + key-check; exit 1 LOUDLY on any problem
    instead of leaving a broken artifact behind (PR-2 self-validation)."""
    line = json.dumps(obj)
    try:
        back = json.loads(line)
    except ValueError as e:
        print(f"BENCH SELF-VALIDATION FAILED: {what} does not re-parse: "
              f"{e}", file=sys.stderr)
        sys.exit(1)
    for key in ("metric", "value", "unit", "vs_baseline"):
        if key not in back:
            print(f"BENCH SELF-VALIDATION FAILED: {what} missing key "
                  f"{key!r}", file=sys.stderr)
            sys.exit(1)
    if not isinstance(back["value"], (int, float)):
        print(f"BENCH SELF-VALIDATION FAILED: {what} 'value' is not "
              "numeric", file=sys.stderr)
        sys.exit(1)
    return line


def _emit_validated(result: dict, headline: dict | None = None) -> None:
    """Artifact-first emission (ISSUE 3 satellite; the r5 failure mode
    was the reverse order): the FULL result JSON is written to the
    artifact file FIRST — ``BENCH_OUT`` when set, else
    ``BENCH_DETAIL.json`` beside this script — fsynced, re-read, and
    re-parsed; only then does stdout get a COMPACT headline line (the
    required metric keys plus every per-config headline number, ~500
    bytes). Driver tail truncation can cut sweep detail only out of a
    durable file now, never out of the parseable summary: a round-5
    driver capture ended up ``"parsed": null`` with the north-star
    numbers truncated away exactly because the one giant detail line
    went to stdout.

    Every artifact also carries ``xla_compiles_during_measurement``: the
    backend-compile count that landed inside timed ``_measured_window``
    blocks (warmup excluded). Steady-state serving windows already hard-
    fail on a nonzero count before reaching here; the stamp makes the
    property auditable from the artifact alone."""
    result.setdefault("xla_compiles_during_measurement",
                      _WINDOW_COMPILES["n"])
    full_line = _validated_json(result, "full result")
    out_path = os.environ.get("BENCH_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json")
    with open(out_path, "w", encoding="utf-8") as f:
        f.write(full_line + "\n")
        f.flush()
        os.fsync(f.fileno())
    try:
        with open(out_path, encoding="utf-8") as f:
            if json.loads(f.read()) != json.loads(full_line):
                raise ValueError("file round-trip mismatch")
    except (ValueError, OSError) as e:
        print(f"BENCH SELF-VALIDATION FAILED: re-reading {out_path!r}: "
              f"{e}", file=sys.stderr)
        sys.exit(1)
    print(f"bench artifact validated: {out_path}", file=sys.stderr)
    summary = {k: result[k]
               for k in ("metric", "value", "unit", "vs_baseline")}
    summary["detail_file"] = os.path.basename(out_path)
    if headline:
        summary["headline"] = headline
    print(_validated_json(summary, "headline"))
    sys.stdout.flush()


def main() -> None:
    rng = np.random.default_rng(SEED)
    # FIRST, before this process initializes a jax backend: the
    # TPU-backed cluster bench, whose worker subprocess must find the
    # chip free (checked at its spawn; every child is reaped before it
    # returns, and every later spawn pins JAX_PLATFORMS=cpu)
    c2t = bench_cluster_tpu(rng)
    # the 1M-doc corpus is shared by the north-star and streaming
    # configs (generation is ~90s; the content is identical anyway)
    corpus_1m = make_doc_arrays(rng, NS_DOCS, NS_VOCAB, NS_AVG_LEN)
    ns = bench_north_star(rng, corpus_1m)
    c1 = bench_config1(rng)
    st = bench_streaming(rng, corpus_1m)
    del corpus_1m
    mesh = bench_mesh(rng)
    c5 = bench_5m_vocab(rng)
    rt = bench_realistic(rng)
    c2 = bench_cluster(rng)

    result = {
        "metric": "bm25_batched_query_qps_1m_docs_500k_vocab",
        "value": round(ns["qps"], 2),
        "unit": "queries/sec",
        # denominator: the STRONGEST CPU implementation at the same
        # 1M-doc config (scipy/torch sparse CSR over precomputed impacts)
        "vs_baseline": round(ns["qps"] / ns["best_cpu_qps"], 2),
        "extra": {
            "north_star": {
                "qps": round(ns["qps"], 2),
                "batch": NS_BATCH,
                "ingest_docs_per_sec": round(ns["ingest_dps"], 1),
                "commit_s": round(ns["commit_s"], 2),
                "nnz": ns["nnz"],
                "parity_checked": ns["parity_checked"],
                "scipy_csr_qps": round(ns.get("scipy_csr_qps", 0), 3),
                "torch_csr_qps": round(ns.get("torch_csr_qps", 0), 3),
            },
            "config1_18k_fulltext": {
                "qps": round(c1["qps"], 2),
                "batch": C1_BATCH,
                "text_ingest_docs_per_sec": round(c1["text_ingest_dps"], 1),
                "warm_commit_s": round(c1["warm_commit_s"], 2),
                "scipy_csr_qps": round(c1.get("scipy_csr_qps", 0), 2),
                "torch_csr_qps": round(c1.get("torch_csr_qps", 0), 2),
                "numpy_loop_qps": round(c1.get("numpy_loop_qps", 0), 2),
                "vs_best_cpu": round(c1["qps"] / c1["best_cpu_qps"], 2),
            },
            "streaming_segments_1m": st,
            "mesh_serving_50k": mesh,
            "config5_5m_vocab": c5,
            "realistic_text_100k": rt,
            "config2_cluster_100k_2workers": c2,
            "config2_tpu_worker": c2t,
            "top_k": TOP_K,
        },
    }
    # every per-config flagship number rides the compact stdout line —
    # the numbers VERDICT r5 lost to tail truncation
    headline = {
        "north_star_qps": round(ns["qps"], 1),
        "config1_qps": round(c1["qps"], 1),
        "streaming_dps": st["streaming_dps"],
        "mesh_qps": mesh["qps"],
        "c5_vocab_qps": c5["qps"],
        "realistic_qps": rt["qps"],
        "cluster_qps": c2["qps"],
        "c2t_qps": c2t["qps"],
        "c2t_direct_worker_qps": c2t["direct_worker_qps"],
    }
    _emit_validated(result, headline)


if __name__ == "__main__":
    configure_compile_cache()
    if "--overload" in sys.argv:
        overload_main()
    elif "--replay" in sys.argv:
        replay_main()
    elif "--routers" in sys.argv:
        routers_main()
    elif "--kernel" in sys.argv:
        kernel_main()
    elif "--hybrid" in sys.argv:
        hybrid_main()
    elif "--tier" in sys.argv:
        tier_main()
    elif "--compute" in sys.argv:
        compute_main()
    else:
        main()
