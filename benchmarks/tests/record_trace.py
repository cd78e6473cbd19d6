"""Records ``small.xplane.pb``, the trace ``test_trace.py`` reduces.

    python benchmarks/tests/record_trace.py <out_dir>      (on the chip)

A 20k-document engine at the wiki1m shape, three 32-query batches under
the same profiler options a traced run uses; writes the ``.xplane.pb``
and, beside it, ``small.xplane.json``: what ``xtrace`` made of it on the
day it was recorded, which the test holds the reduction to.
"""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(BENCH, "lib"))
sys.path.insert(0, os.path.dirname(BENCH))

import data  # noqa: E402
import xtrace  # noqa: E402


def main(out_dir: str) -> None:
    import worker_main
    from tfidf_tpu.utils.config import Config

    job = {"seed": 7, "corpus": dict(corpus_seed=5, docs=20000, vocab=20000,
                                     doc_len_mean=120)}
    engine, _t = worker_main.build_engine(
        job, Config(query_batch=32, embedding_enabled=False))
    queries = data.make_queries(7, 32 * 5, vocab=20000, query_terms={
        "law": "uniform", "min": 2, "max": 4})
    batches = [queries[i * 32:(i + 1) * 32] for i in range(5)]
    for b in batches[:2]:
        engine.search_batch(b, k=10)
    tracer = worker_main.Tracer(out_dir)
    tracer.start()
    for b in batches[2:]:
        engine.search_batch(b, k=10)
    tracer.stop()
    path = xtrace.find_xplane(tracer.dir)
    shutil.copyfile(path, os.path.join(out_dir, "small.xplane.pb"))
    s = xtrace.reduce_xplane(path)
    import jax
    with open(os.path.join(out_dir, "small.xplane.json"), "w") as f:
        json.dump({"recorded_on": jax.devices()[0].device_kind,
                   "batches": 3, "rehearsal": s["rehearsal"],
                   "devices": s["devices"], "busy_s": s["busy_s"],
                   "window_s": s["window_s"],
                   "device_ops": {k: s["device_ops"][k] for k, _ in
                                  xtrace.top(s["device_ops"], 5)}},
                  f, indent=1)
    shutil.rmtree(tracer.dir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
