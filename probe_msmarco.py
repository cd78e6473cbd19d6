"""Config-4 at FULL scale: stream 8.8M MS-MARCO-shaped passages.

BASELINE.md config 4 names the 8.8M-passage corpus; bench.py streams 1M
(kept there for runtime). This probe runs the full count once and
records sustained docs/s + commit percentiles + device residency, so
the scale claim is measured, not extrapolated:

    python probe_msmarco.py          # run time on a v5e: not measured

Passages are shorter than the north-star docs (avg ~55 terms — MS MARCO
passages average ~56 words), vocab 500k.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from tfidf_tpu.utils.compile_cache import configure_compile_cache

from bench import NS_VOCAB, make_doc_arrays, make_queries  # noqa: E402

N_DOCS = int(os.environ.get("PROBE_DOCS", 8_800_000))
AVG_LEN = 55
COMMIT_EVERY = 50_000
GEN_CHUNK = 1_000_000


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    from tfidf_tpu.engine import Engine
    from tfidf_tpu.utils.config import Config

    rng = np.random.default_rng(0)
    # query_batch 16: at 8.8M docs the padded score space is ~11M
    # columns, and depth-2 pipelining keeps up to THREE chunks in
    # flight (dispatch-then-drain = depth+1, see
    # searcher._run_pipelined); three [B, 11M] f32 score buffers at
    # B=64 overflow 16GB HBM alongside the resident postings (two
    # already tipped it over by 240MB) — B=16 leaves ~2GB slack
    engine = Engine(Config(
        index_mode="segments", query_batch=16,
        merge_upload_pace=float(os.environ.get("PROBE_PACE", "1.0"))))
    t0 = time.perf_counter()
    for i in range(NS_VOCAB):
        engine.vocab.add(f"t{i}")
    log(f"[vocab] {time.perf_counter()-t0:.0f}s")

    add = engine.index.add_document_arrays
    commit_ms = []          # (ms, merge_was_inflight)
    done = 0
    t_start = time.perf_counter()
    gen_s = 0.0
    while done < N_DOCS:
        n = min(GEN_CHUNK, N_DOCS - done)
        g0 = time.perf_counter()
        offsets, ids, tfs, lengths = make_doc_arrays(
            rng, n, NS_VOCAB, AVG_LEN)
        gen_s += time.perf_counter() - g0
        for i in range(n):
            lo, hi = offsets[i], offsets[i + 1]
            add(f"d{done + i}", ids[lo:hi], tfs[lo:hi],
                float(lengths[i]))
            if (done + i + 1) % COMMIT_EVERY == 0:
                inflight = engine.index._merge_future is not None
                c0 = time.perf_counter()
                engine.commit()
                commit_ms.append(((time.perf_counter() - c0) * 1e3,
                                  inflight))
        done += n
        log(f"[st] {done}/{N_DOCS} docs "
            f"({done/(time.perf_counter()-t_start-gen_s):.0f} docs/s "
            f"excl. corpus gen)")
    total_s = time.perf_counter() - t_start - gen_s
    engine.commit()
    q0 = time.perf_counter()
    for _ in range(32):
        engine.index.wait_for_merges()
        engine.commit()
        if len(engine.index._segments) <= engine.config.max_segments \
                and engine.index._merge_future is None:
            break
    quiesce_s = time.perf_counter() - q0
    # the FIRST commit pays one-time warmup (first big numpy pass +
    # first device transfers); report it separately so the steady-state
    # split isolates the merge-contention question
    first_ms = commit_ms[0][0] if commit_ms else 0.0
    steady = commit_ms[1:]
    cm = np.asarray([m for m, _f in steady] or [0.0])
    cm_merge = np.asarray([m for m, f in steady if f] or [0.0])
    cm_alone = np.asarray([m for m, f in steady if not f] or [0.0])
    # END-OF-RUN SEARCH GATE (ROADMAP item 7 hygiene): a run whose
    # full-scale search fails must FAIL — loudly, without touching the
    # artifact. The artifact once carried an unevidenced
    # `search_ok: false` for nine rounds precisely because this gate
    # used to record its own failure into it and exit 0; an
    # artifact that silently documents a broken run is a bench bug
    # (bench.py --kernel applies the same assert-before-emit
    # discipline). A compile failure still gets one retry; a
    # second failure aborts the probe with a nonzero exit.
    queries = make_queries(rng, NS_VOCAB, 32)
    try:
        hits = engine.search_batch(queries, k=10)
    except Exception as e:
        if "compile" not in repr(e).lower():
            raise
        log(f"[st] search compile flake, retrying once: {e!r}")
        time.sleep(5.0)
        hits = engine.search_batch(queries, k=10)
    if not any(hits):
        sys.exit("[st] FULL-SCALE SEARCH GATE FAILED: no hits at "
                 f"{N_DOCS} docs — refusing to emit an artifact for a "
                 "run that cannot answer queries")
    search_ok = True
    from tfidf_tpu.utils.metrics import global_metrics
    snap = global_metrics.snapshot()
    out = {
        "n_docs": N_DOCS,
        "streaming_dps": round(done / total_s, 1),
        "commit_ms_p50": round(float(np.percentile(cm, 50)), 1),
        "commit_ms_p99": round(float(np.percentile(cm, 99)), 1),
        "commit_ms_max": round(float(cm.max()), 1),
        # the attribution split (VERDICT r3 #4): commits that overlapped
        # a background merge vs commits that ran alone — with paced
        # merge uploads both tails should be bounded
        "commit_first_warmup_ms": round(float(first_ms), 1),
        "commits_with_merge_inflight": int((np.asarray(
            [f for _m, f in steady])).sum()) if steady else 0,
        "commit_merge_inflight_ms_p99": round(float(
            np.percentile(cm_merge, 99)), 1),
        "commit_merge_inflight_ms_max": round(float(cm_merge.max()), 1),
        "commit_alone_ms_p99": round(float(
            np.percentile(cm_alone, 99)), 1),
        "commit_alone_ms_max": round(float(cm_alone.max()), 1),
        "merge_upload_pace": engine.config.merge_upload_pace,
        "merge_build_mean_ms": round(snap.get(
            "merge_build_mean_ms", 0.0), 1),
        "quiesce_s": round(quiesce_s, 1),
        "segments": len(engine.index.snapshot.segments),
        "nnz_live": int(engine.index.nnz_live),
        "search_ok": search_ok,
    }
    log(f"[done] {json.dumps(out)}")
    if N_DOCS >= 8_000_000:
        # only FULL runs update the committed artifact (bracketing runs
        # at smaller N_DOCS print their JSON for the caller to merge),
        # and the update PRESERVES context keys a human merged in
        # (multi-run history, attribution notes) rather than clobbering
        path = os.path.join(os.path.dirname(__file__),
                            "MSMARCO_SCALE.json")
        prior: dict = {}
        try:
            with open(path) as f:
                prior = json.load(f)
        except Exception:
            prior = {}
        out.update({k: v for k, v in prior.items() if k not in out})
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    configure_compile_cache()
    main()
