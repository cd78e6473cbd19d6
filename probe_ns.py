"""North-star steady-state probe: 1M docs / 500k vocab on the chip.

Measures engine.search_batch q/s at several batch sizes using DISTINCT
query sets per timed batch (the serving pattern), after the u-floor
warmup. The ≥50x target needs ~1970 q/s against torch-CSR's 39.4.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from tfidf_tpu.utils.compile_cache import configure_compile_cache

from bench import (NS_AVG_LEN, NS_DOCS, NS_VOCAB, make_doc_arrays,  # noqa: E402
                   make_queries)

N_DOCS = int(os.environ.get("PROBE_DOCS", NS_DOCS))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    offsets, ids, tfs, lengths = make_doc_arrays(
        rng, N_DOCS, NS_VOCAB, NS_AVG_LEN)
    log(f"[gen] {N_DOCS} docs nnz={ids.shape[0]} "
        f"{time.perf_counter()-t0:.0f}s")

    engine = Engine(Config(query_batch=4096))
    for i in range(NS_VOCAB):
        engine.vocab.add(f"t{i}")
    add = engine.index.add_document_arrays
    t0 = time.perf_counter()
    for i in range(N_DOCS):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
    log(f"[ingest] {time.perf_counter()-t0:.0f}s")
    t0 = time.perf_counter()
    engine.commit()
    log(f"[commit] {time.perf_counter()-t0:.0f}s")
    snap = engine.index.snapshot
    log(f"[ell] blocks={[i.shape for i in snap.ell_impacts]}")

    queries = make_queries(rng, NS_VOCAB, 6 * 4096)

    if os.environ.get("PROBE_PIECES"):
        import functools
        import jax
        from tfidf_tpu.engine.searcher import vectorize_queries
        from tfidf_tpu.ops.ell import score_ell_with_residual
        from tfidf_tpu.ops.topk import packed_topk_chunked, unpack_topk

        kw = engine.model.score_kwargs()
        B = int(os.environ.get("PROBE_B", 512))
        qb, _ = vectorize_queries(
            queries[:B], engine.analyzer, engine.vocab, engine.model,
            batch_cap=B, max_terms=32)
        log(f"[pieces] B={B} uniq={int(qb.n_uniq)} "
            f"u_cap={qb.uniq.shape[0]}")
        fn = jax.jit(functools.partial(
            score_ell_with_residual, use_pallas=True, **kw))

        def scores_only():
            s = fn(snap.ell_impacts, snap.ell_terms, snap.ell_live,
                   snap.res_tf, snap.res_term, snap.res_doc,
                   snap.doc_len, snap.df, qb, snap.n_docs, snap.avgdl,
                   snap.doc_norms)
            np.asarray(s[0][:1, :8])
            return s

        def timeit(f, n=3):
            f()
            t0 = time.perf_counter()
            for _ in range(n):
                f()
            return (time.perf_counter() - t0) / n

        dt = timeit(scores_only)
        log(f"[pieces] scores+fetch8: {dt*1e3:.0f}ms")
        s = scores_only()

        def topk_and_fetch():
            unpack_topk(packed_topk_chunked(s, snap.ell_live, k=10))
        dt = timeit(topk_and_fetch)
        log(f"[pieces] topk+packed fetch: {dt*1e3:.0f}ms")

        def fetch8():
            np.asarray(s[0][:1, :8])
        dt = timeit(fetch8)
        log(f"[pieces] bare fetch of 8 floats: {dt*1e3:.0f}ms")
        return

    for B in (512, 1024):
        # warmup: 2 distinct batches (ratchets u_floor, compiles once)
        engine.searcher.query_batch = B
        engine.search_batch(queries[:B], k=10)
        engine.search_batch(queries[B:2 * B], k=10)
        # one call over 4 chunks: the searcher pipelines internally
        t0 = time.perf_counter()
        engine.search_batch(queries[2 * B:6 * B], k=10)
        dt = time.perf_counter() - t0
        log(f"[B={B}] {4*B} q in {dt:.2f}s -> {4*B/dt:.0f} q/s "
            f"pipelined ({dt/4*1e3:.0f} ms/chunk, u_floor="
            f"{engine.searcher._u_floor})")


if __name__ == "__main__":
    configure_compile_cache()
    main()
