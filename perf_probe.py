"""Perf probe for the 1M-doc query step (VERDICT r1 #10).

Separates the batch-scoring pipeline into its pieces on the real chip:
pure device scoring vs top-k vs device->host transfer vs host query
vectorization, across doc_chunk and batch-size variants, and captures a
jax.profiler trace of the steady-state step. Writes findings to stderr;
the PERF.md verdict is derived from this output.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from tfidf_tpu.utils.compile_cache import configure_compile_cache

import jax  # noqa: E402

from bench import NS_AVG_LEN, NS_DOCS, NS_VOCAB, make_doc_arrays  # noqa: E402
from bench import make_queries  # noqa: E402


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def t(fn, n=3, warm=1):
    for _ in range(warm):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def main():
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.engine.searcher import vectorize_queries
    from tfidf_tpu.ops.ell import score_ell_with_residual
    from tfidf_tpu.ops.topk import packed_topk_chunked, unpack_topk
    from tfidf_tpu.utils.config import Config
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    n_docs = int(os.environ.get("PROBE_DOCS", NS_DOCS))
    offsets, ids, tfs, lengths = make_doc_arrays(
        rng, n_docs, NS_VOCAB, NS_AVG_LEN)
    log(f"[gen] {n_docs} docs nnz={ids.shape[0]}")

    engine = Engine(Config(query_batch=2048))
    for i in range(NS_VOCAB):
        engine.vocab.add(f"t{i}")
    add = engine.index.add_document_arrays
    for i in range(n_docs):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
    t0 = time.perf_counter()
    engine.commit()
    log(f"[commit] {time.perf_counter()-t0:.1f}s")
    snap = engine.index.snapshot
    log(f"[ell] blocks={[(i.shape) for i in snap.ell_impacts]} "
        f"res={'none' if snap.res_tf is None else snap.res_tf.shape}")

    queries = make_queries(rng, NS_VOCAB, 4096)

    for B in (256, 1024, 2048):
        qb, _ = vectorize_queries(queries[:B], engine.analyzer, engine.vocab,
                               engine.model, batch_cap=B, max_terms=32)
        log(f"[B={B}] uniq={int(qb.n_uniq)} ucap={qb.uniq.shape[0]}")
        kw = engine.model.score_kwargs()

        for chunk in (512, 2048, 8192):
            fn = jax.jit(lambda *a, ch=chunk, **k: score_ell_with_residual(
                *a, **k, doc_chunk=ch), static_argnames=("model", "k1", "b"))

            def scores_only(ch=chunk, f=fn):
                s = f(snap.ell_impacts, snap.ell_terms, snap.ell_live,
                      snap.res_tf, snap.res_term, snap.res_doc,
                      snap.doc_len, snap.df, qb, snap.n_docs, snap.avgdl,
                      snap.doc_norms, **kw)
                jax.block_until_ready(s)     # the tuple of blocks
                return s

            dt = t(scores_only, n=2)
            log(f"  scores_only chunk={chunk}: {dt*1e3:.0f}ms "
                f"-> {B/dt:.0f} q/s")

        s = scores_only()

        def topk_only():
            p = packed_topk_chunked(s, snap.ell_live, k=10)
            p.block_until_ready()
        log(f"  topk_only: {t(topk_only, n=3)*1e3:.0f}ms")

        def topk_and_fetch():
            unpack_topk(packed_topk_chunked(s, snap.ell_live, k=10))
        log(f"  topk+fetch: {t(topk_and_fetch, n=3)*1e3:.0f}ms")

        def full():
            engine.search_batch(queries[:B], k=10)
        log(f"  full search_batch: {t(full, n=2)*1e3:.0f}ms")

        def vec_only():
            vectorize_queries(queries[:B], engine.analyzer, engine.vocab,
                              engine.model, batch_cap=B, max_terms=32)
        log(f"  host vectorize: {t(vec_only, n=3)*1e3:.0f}ms")

    # trace one steady-state batch
    B = 1024
    qb, _ = vectorize_queries(queries[:B], engine.analyzer, engine.vocab,
                           engine.model, batch_cap=B, max_terms=32)
    engine.search_batch(queries[:B], k=10)
    with jax.profiler.trace("/tmp/tfidf_trace"):
        engine.search_batch(queries[:B], k=10)
    log("[trace] written to /tmp/tfidf_trace")


if __name__ == "__main__":
    configure_compile_cache()
    main()
