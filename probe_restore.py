"""Checkpoint save/restore at 1M docs (VERDICT r3 #5).

Round 3's restore replayed 1M documents through a per-doc Python loop
(39.2s end-to-end); the packed bulk path (engine/checkpoint.py
``_to_coo_packed``) builds the index arrays directly from ``docs.npz``.
This probe measures save + restore + parity at the north-star shape and
records the numbers for PERF.md.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from tfidf_tpu.utils.compile_cache import configure_compile_cache

from bench import NS_VOCAB, make_doc_arrays, make_queries  # noqa: E402

N_DOCS = int(os.environ.get("PROBE_DOCS", 1_000_000))
AVG_LEN = 120


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def run_mode(mode: str, corpus, queries) -> dict:
    """Build -> save -> restore -> parity for one index mode.

    ``mode="segments"`` is the streaming flagship: ingest in 100k-doc
    commit waves (a real segment list + tiered merges), then restore
    through the segment-level fast path (segstate.npz)."""
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.engine.checkpoint import (load_checkpoint,
                                             save_checkpoint)
    from tfidf_tpu.utils.config import Config

    offsets, ids, tfs, lengths = corpus
    cfg = Config(query_batch=64,
                 index_mode="segments" if mode == "segments" else "rebuild")
    engine = Engine(cfg)
    for i in range(NS_VOCAB):
        engine.vocab.add(f"t{i}")
    t0 = time.perf_counter()
    add = engine.index.add_document_arrays
    for i in range(N_DOCS):
        lo, hi = offsets[i], offsets[i + 1]
        add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
        if mode == "segments" and (i + 1) % 100_000 == 0:
            engine.commit()
    engine.commit()
    if mode == "segments":
        engine.index.wait_for_merges()
        engine.commit()
    log(f"[ckpt:{mode}] built {N_DOCS}-doc engine in "
        f"{time.perf_counter()-t0:.0f}s")
    want = engine.search_batch(queries, k=10)

    tmp = tempfile.mkdtemp(prefix=f"probe_ckpt_{mode}_")
    try:
        t0 = time.perf_counter()
        save_checkpoint(engine, tmp)
        save_s = time.perf_counter() - t0
        n_segments = (len(engine.index._segments)
                      if mode == "segments" else None)
        del engine
        t0 = time.perf_counter()
        restored = load_checkpoint(tmp, cfg)
        load_s = time.perf_counter() - t0
        if mode == "segments":
            assert len(restored.index._segments) == n_segments, \
                "restore must reproduce the segment list, not rebuild"
        t0 = time.perf_counter()
        got = restored.search_batch(queries, k=10)
        first_search_s = time.perf_counter() - t0
        for w, g in zip(want, got):
            assert [h.name for h in w] == [h.name for h in g]
            np.testing.assert_allclose([h.score for h in w],
                                       [h.score for h in g], rtol=1e-6)
        out = {"n_docs": N_DOCS,
               "save_s": round(save_s, 1),
               "restore_s": round(load_s, 1),
               "first_search_s": round(first_search_s, 1),
               "parity_checked": True}
        if n_segments is not None:
            out["segments"] = n_segments
        log(f"[ckpt:{mode}] save {save_s:.1f}s, restore {load_s:.1f}s, "
            f"first search {first_search_s:.1f}s, top-10 identical "
            f"on {len(queries)} queries")
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    rng = np.random.default_rng(0)
    corpus = make_doc_arrays(rng, N_DOCS, NS_VOCAB, AVG_LEN)
    queries = make_queries(rng, NS_VOCAB, 64)
    modes = os.environ.get("PROBE_MODES", "shard,segments").split(",")
    out = {"nnz": int(corpus[1].shape[0])}
    for mode in modes:
        out[mode] = run_mode(mode.strip(), corpus, queries)
    print(json.dumps(out))


if __name__ == "__main__":
    configure_compile_cache()
    main()
