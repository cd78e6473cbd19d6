"""Diagnose the mesh-ELL serving path (VERDICT r2 #2).

Builds the exact bench_mesh configuration (50k docs / 500k vocab,
engine_mode="mesh") and splits a search batch into its pieces:
host vectorize, jitted shard_map step (forced by fetch), name_of loop —
plus kernel-eligibility facts (u_cap, B, block rows_caps) and a commit
breakdown. Findings go to stderr; PERF.md gets the verdict.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from tfidf_tpu.utils.compile_cache import configure_compile_cache

import jax  # noqa: E402

from bench import NS_VOCAB, ST_AVG_LEN, make_doc_arrays, make_queries  # noqa: E402

MESH_DOCS = int(os.environ.get("PROBE_DOCS", 50_000))
B = int(os.environ.get("PROBE_B", 256))


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
    from tfidf_tpu.ops.ell import _PL_MAX_B, _PL_TD, _pallas_eligible
    from tfidf_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    offsets, ids, tfs, lengths = make_doc_arrays(
        rng, MESH_DOCS, NS_VOCAB, ST_AVG_LEN)
    engine = Engine(Config(engine_mode="mesh", query_batch=B))
    t0 = time.perf_counter()
    for i in range(NS_VOCAB):
        engine.vocab.add(f"t{i}")
    log(f"[vocab] {time.perf_counter()-t0:.1f}s")
    add = engine.index.add_document_arrays
    t0 = time.perf_counter()
    for i in range(MESH_DOCS):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
    log(f"[ingest] {time.perf_counter()-t0:.1f}s")

    t0 = time.perf_counter()
    engine.commit()
    log(f"[commit cold] {time.perf_counter()-t0:.1f}s")
    # second commit after a single append — the steady-state commit cost
    add("dX", ids[:5], tfs[:5], 5.0)
    t0 = time.perf_counter()
    engine.commit()
    log(f"[commit warm+1] {time.perf_counter()-t0:.1f}s")

    idx = engine.index
    snap = idx.snapshot
    base = snap.base
    log(f"[base] doc_cap={base.doc_cap} "
        f"blocks={[(x.shape, ) for x in base.impact]}")
    log(f"[delta] doc_cap={snap.delta.doc_cap} "
        f"tf={snap.delta.tf.shape}")

    searcher = engine.searcher
    queries = make_queries(rng, NS_VOCAB, B * 4)

    qb, _ = vectorize_queries(queries[:B], engine.analyzer, engine.vocab,
                              engine.model, batch_cap=B, max_terms=32)
    u_cap = qb.uniq.shape[0]
    log(f"[q] B={B} uniq={int(qb.n_uniq)} u_cap={u_cap} "
        f"PL_MAX_B={_PL_MAX_B}")
    for x in base.impact:
        rows_cap = x.shape[1]
        log(f"  block rows_cap={rows_cap} width={x.shape[2]} "
            f"eligible={_pallas_eligible(rows_cap, B, u_cap)} "
            f"(rows%{_PL_TD}={rows_cap % _PL_TD})")

    from tfidf_tpu.ops.topk import unpack_topk
    fn = searcher._get_search_fn(10)

    def step_only():
        unpack_topk(fn(snap.base, snap.delta, snap.df_g, snap.n_docs,
                       snap.avgdl, qb))

    dt = t(step_only, n=3)
    log(f"[step] jitted shard_map step: {dt*1e3:.0f}ms -> {B/dt:.0f} q/s")

    def vec_only():
        vectorize_queries(queries[:B], engine.analyzer, engine.vocab,
                          engine.model, batch_cap=B, max_terms=32)
    log(f"[vec] host vectorize: {t(vec_only, n=3)*1e3:.0f}ms")

    vals, gids = unpack_topk(fn(snap.base, snap.delta, snap.df_g,
                                snap.n_docs, snap.avgdl, qb))

    def names_only():
        for i in range(B):
            for vv, gg in zip(vals[i, :10], gids[i, :10]):
                if np.isfinite(vv) and vv > 0.0:
                    snap.name_of(int(gg))
    log(f"[names] name_of loop: {t(names_only, n=3)*1e3:.0f}ms")

    def full():
        searcher.search(queries[:B], k=10)
    dt = t(full, n=3)
    log(f"[full] searcher.search: {dt*1e3:.0f}ms -> {B/dt:.0f} q/s")

    if os.environ.get("PROBE_ABLATE"):
        import jax.numpy as jnp
        from tfidf_tpu.ops.ell import (_rearrange_to_real, _score_block,
                                       score_block_pallas)
        from tfidf_tpu.ops.scoring import (_compile_queries,
                                           score_coo_compiled)
        from tfidf_tpu.ops.topk import exact_topk

        # on a 1x1 mesh the shard_map step body can run directly on the
        # squeezed arrays — per-piece timings without collective plumbing
        impacts = [x.reshape(x.shape[1:]) for x in base.impact]
        terms = [x.reshape(x.shape[1:]) for x in base.term]
        kw = engine.model.score_kwargs()
        delta = snap.delta

        @jax.jit
        def ell_only(qb):
            slot_of, qc_ext = _compile_queries(qb, snap.df_g.shape[0])
            qc_t = qc_ext.T
            parts = [score_block_pallas(i, t, qb.uniq, qb.n_uniq, qc_ext)
                     for i, t in zip(impacts, terms)]
            return _rearrange_to_real(
                parts, [i.shape[0] for i in impacts],
                base.block_live.reshape(-1), base.doc_cap,
                qc_ext.shape[0])

        @jax.jit
        def ell_xla(qb):
            slot_of, qc_ext = _compile_queries(qb, snap.df_g.shape[0])
            qc_t = qc_ext.T
            parts = [_score_block(i, t, slot_of, qc_t, 2048)
                     for i, t in zip(impacts, terms)]
            return _rearrange_to_real(
                parts, [i.shape[0] for i in impacts],
                base.block_live.reshape(-1), base.doc_cap,
                qc_ext.shape[0])

        @jax.jit
        def res_only(qb):
            slot_of, qc_ext = _compile_queries(qb, snap.df_g.shape[0])
            return score_coo_compiled(
                base.res_tf.reshape(-1), base.res_term.reshape(-1),
                base.res_doc.reshape(-1), base.res_dl.reshape(-1),
                snap.df_g, slot_of, qc_ext, snap.n_docs, snap.avgdl,
                None, model=kw["model"], k1=kw.get("k1", 1.2),
                b=kw.get("b", 0.75),
                chunk=min(1 << 10, base.res_tf.size))

        @jax.jit
        def delta_only(qb):
            slot_of, qc_ext = _compile_queries(qb, snap.df_g.shape[0])
            return score_coo_compiled(
                delta.tf.reshape(-1), delta.term.reshape(-1),
                delta.doc.reshape(-1), delta.doc_len.reshape(-1),
                snap.df_g, slot_of, qc_ext, snap.n_docs, snap.avgdl,
                None, model=kw["model"], k1=kw.get("k1", 1.2),
                b=kw.get("b", 0.75),
                chunk=min(1 << 17, delta.tf.size))

        @jax.jit
        def topk_only(scores):
            return exact_topk(scores, jnp.int32(scores.shape[1]), k=10)

        for name, f in (("ell_kernel", ell_only), ("ell_xla", ell_xla),
                        ("res_coo", res_only), ("delta_coo", delta_only)):
            out = f(qb)
            dt = t(lambda: np.asarray(f(qb)[:, :8]), n=3)
            log(f"[ablate] {name}: {dt*1e3:.0f}ms (shape {out.shape})")
        sc = ell_only(qb)
        sc = jnp.concatenate(
            [sc, jnp.zeros((sc.shape[0], delta.doc_cap))], axis=1)
        dt = t(lambda: np.asarray(topk_only(sc)[0][:, :8]), n=3)
        log(f"[ablate] topk over {sc.shape}: {dt*1e3:.0f}ms")

        # commit breakdown
        t0 = time.perf_counter()
        df_host, n_live, len_sum = idx._live_stats(snap.df_g.shape[0])
        log(f"[commit-ablate] _live_stats: "
            f"{(time.perf_counter()-t0)*1e3:.0f}ms")
        t0 = time.perf_counter()
        df_g = jax.device_put(df_host)
        np.asarray(df_g[:8])
        log(f"[commit-ablate] df device_put+sync: "
            f"{(time.perf_counter()-t0)*1e3:.0f}ms")
        t0 = time.perf_counter()
        b2 = idx._refresh_fn(idx._base, snap.df_g, snap.n_docs,
                             snap.avgdl)
        np.asarray(b2.impact[0][0, :1, :8])
        log(f"[commit-ablate] refresh_fn forced: "
            f"{(time.perf_counter()-t0)*1e3:.0f}ms")
        add("dY", ids[:5], tfs[:5], 5.0)
        t0 = time.perf_counter()
        engine.commit()
        log(f"[commit-ablate] commit warm+1 again: "
            f"{(time.perf_counter()-t0)*1e3:.0f}ms")

    # compare: the local single-device engine on the identical corpus
    eng2 = Engine(Config(query_batch=B))
    for i in range(NS_VOCAB):
        eng2.vocab.add(f"t{i}")
    add2 = eng2.index.add_document_arrays
    for i in range(MESH_DOCS):
        lo, hi = offsets[i], offsets[i + 1]
        add2(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
    t0 = time.perf_counter()
    eng2.commit()
    log(f"[local commit] {time.perf_counter()-t0:.1f}s")

    def full_local():
        eng2.search_batch(queries[:B], k=10)
    dt = t(full_local, n=3)
    log(f"[local full] search_batch: {dt*1e3:.0f}ms -> {B/dt:.0f} q/s")


if __name__ == "__main__":
    configure_compile_cache()
    main()
