"""Search pipeline depth on small corpora (VERDICT r3 weak #3).

At 18k docs the device step is a few ms, so where the device->host
fetch costs more than that, one-deep pipelining caps throughput near
one chunk per fetch. This probe measures QPS vs
``search_pipeline_depth`` at the config-1 shape to pick the default
and document the small-corpus story.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from tfidf_tpu.utils.compile_cache import configure_compile_cache

from bench import (C1_AVG_LEN, C1_DOCS, C1_VOCAB, TOP_K,  # noqa: E402
                   make_doc_arrays, make_queries)

BATCH = 1024
BATCHES = 8


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    offsets, ids, tfs, lengths = make_doc_arrays(rng, C1_DOCS, C1_VOCAB,
                                                 C1_AVG_LEN)
    queries = make_queries(rng, C1_VOCAB, BATCH * (BATCHES + 2))
    out = {}
    for depth in (1, 2, 3, 4, 6):
        engine = Engine(Config(query_batch=BATCH,
                               search_pipeline_depth=depth))
        for i in range(C1_VOCAB):
            engine.vocab.add(f"t{i}")
        add = engine.index.add_document_arrays
        for i in range(C1_DOCS):
            lo, hi = offsets[i], offsets[i + 1]
            add(f"d{i}", ids[lo:hi], tfs[lo:hi], float(lengths[i]))
        engine.commit()
        engine.search_batch(queries[:BATCH], k=TOP_K)
        engine.search_batch(queries[BATCH:2 * BATCH], k=TOP_K)
        timed = queries[2 * BATCH:(BATCHES + 2) * BATCH]
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            engine.search_batch(timed, k=TOP_K)
            best = max(best, len(timed) / (time.perf_counter() - t0))
        log(f"[pipe] depth={depth}: {best:.0f} q/s (best of 3)")
        out[str(depth)] = round(best, 1)
        del engine
    print(json.dumps(out))


if __name__ == "__main__":
    configure_compile_cache()
    main()
