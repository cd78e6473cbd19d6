"""The one process of a run that holds the chip.

``python worker_main.py <job.json>``; the parent (``run.py``) pins it with
``JAX_PLATFORMS`` so that a chip it cannot get is fatal. It makes the
corpus from the seed (the same generator the parent feeds its reference
from), builds an ``Engine`` from the arrays the way a checkpoint restore
does (``engine/checkpoint.py load_checkpoint``: vocabulary in id order,
``bulk_load_packed``, then ``commit``), and then, by ``mode``:

* ``serve`` — hands the engine to ``SearchNode(cfg, coord_factory=...,
  engine=engine).start(rebuild=False)``, exactly what ``cmd_serve`` does
  with a restored engine (``tfidf_tpu/cli.py``), and obeys one-line JSON
  commands on stdin: ``trace_start``, ``trace_stop``, ``trace_reduce``
  (after the window: the trace as ``xtrace``'s summary), ``report`` (device and memory as jax reports them),
  ``quit``. Only this process can trace the chip, which is why the
  launcher belongs to the benchmark.
* ``batch`` — the library surface: warms the cell's shapes, waits for
  ``go``, then calls ``search_batch`` with one batch after another for
  ``seconds``, back to back, one caller; reports and exits.

Every reply is one JSON line on stdout; logs go to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import data  # noqa: E402  (benchmarks/lib)
import xtrace as trace_mod  # noqa: E402  (benchmarks/lib)


def say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def note(msg: str) -> None:
    print(f"[worker {time.monotonic():.2f}] {msg}", file=sys.stderr,
          flush=True)


def build_engine(job: dict, cfg):
    """Corpus from the seed (on a thread, beside jax's start-up), then
    vocabulary, documents, commit. Returns (engine, timings)."""
    from tfidf_tpu.engine import Engine

    timings: dict[str, float] = {}
    box: dict = {}

    def gen() -> None:
        t = time.monotonic()
        box["corpus"] = data.make_corpus(job["seed"], **job["corpus"])
        timings["corpus_s"] = time.monotonic() - t

    th = threading.Thread(target=gen)
    th.start()
    t = time.monotonic()
    import jax
    devs = jax.devices()        # the TPU start-up; fatal without a chip
    timings["device_start_s"] = time.monotonic() - t
    note(f"devices: {devs}")
    engine = Engine(cfg)
    t = time.monotonic()
    for i in range(job["corpus"]["vocab"]):
        engine.vocab.add(f"t{i}")
    timings["vocab_s"] = time.monotonic() - t
    th.join()
    corpus = box["corpus"]
    t = time.monotonic()
    names = [f"d{i}" for i in range(corpus.n_docs)]
    engine.index.bulk_load_packed(names, corpus.offsets, corpus.ids,
                                  corpus.tfs, corpus.lengths)
    timings["load_s"] = time.monotonic() - t
    del corpus, box
    t = time.monotonic()
    engine.commit()
    timings["commit_s"] = time.monotonic() - t
    note(f"engine built: {timings}")
    return engine, timings


def break_answers(engine) -> None:
    """TEST ONLY (``benchmarks/tests``; refused outside a rehearsal):
    every score the engine hands out is 1% too high, as a broken timed
    path would make it. ``correct`` has to come out false."""
    arrays, batch = engine.search_batch_arrays, engine.search_batch

    def bad_arrays(queries, k=None):
        vals, ids, kk, names = arrays(queries, k=k)
        return vals * 1.01, ids, kk, names

    def bad_batch(queries, k=None, unbounded=False):
        return [[type(h)(h.name, h.score * 1.01) for h in hits]
                for hits in batch(queries, k=k, unbounded=unbounded)]

    engine.search_batch_arrays = bad_arrays
    engine.search_batch = bad_batch


def device_report(engine) -> dict:
    from tfidf_tpu.utils.metrics import global_metrics
    return {"compute": engine.compute_stats(),
            "native_ingest": engine.native is not None,
            "metrics": global_metrics.snapshot()}


class Tracer:
    """jax.profiler around a stretch of the window: host TraceMe events
    on (that is where ``trace_phase``'s annotations land), the Python
    tracer off (it would slow the host path it measures)."""

    def __init__(self, workdir: str) -> None:
        self.dir = os.path.join(workdir, "trace")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        return trace_mod.reduce_xplane(trace_mod.find_xplane(self.dir))


def serve(job: dict, cfg) -> int:
    from tfidf_tpu.cluster.coordination import CoordinationClient
    from tfidf_tpu.cluster.node import SearchNode

    engine, timings = build_engine(job, cfg)
    if job.get("break"):
        break_answers(engine)

    def factory():
        return CoordinationClient(
            cfg.coordinator_address,
            heartbeat_interval_s=cfg.heartbeat_interval_s)

    say({"built": True, "timings": timings})
    # the first node to volunteer is elected: join only once the parent
    # says the leader process holds the election
    if json.loads(sys.stdin.readline())["cmd"] != "serve":
        return 1
    node = SearchNode(cfg, coord_factory=factory, engine=engine) \
        .start(rebuild=False)
    say({"ready": True, "url": node.url})
    tracer = Tracer(job["workdir"])
    try:
        for line in sys.stdin:
            cmd = json.loads(line)
            if cmd["cmd"] == "trace_start":
                tracer.start()
                say({"ok": True, "t": time.monotonic()})
            elif cmd["cmd"] == "trace_stop":
                tracer.stop()
                say({"ok": True, "t": time.monotonic()})
            elif cmd["cmd"] == "trace_reduce":
                say({"ok": True, "trace": tracer.reduce()})
            elif cmd["cmd"] == "report":
                say({"ok": True, **device_report(engine)})
            elif cmd["cmd"] == "quit":
                break
    finally:
        node.stop()
    return 0


def batch(job: dict, cfg) -> int:
    """``job["batches"]``: the pool, a list of query lists; the window
    cycles through it. ``job["warm"]``: the batches to warm up on (the
    capacity batch first). ``job["keep"]``: {position in pool order ->
    sample index} of the answers to hand back."""
    from tfidf_tpu.utils.metrics import global_metrics

    engine, timings = build_engine(job, cfg)
    if job.get("break"):
        break_answers(engine)
    k = job["top_k"]
    t = time.monotonic()
    for qs in job["warm"]:
        engine.search_batch(qs, k=k)
    timings["warm_s"] = time.monotonic() - t
    say({"ready": True, "timings": timings})
    if json.loads(sys.stdin.readline())["cmd"] != "go":
        return 1
    batches = job["batches"]
    width = len(batches[0])
    keep = {int(p): s for p, s in job["keep"].items()}
    kept: dict[int, list] = {}
    tracer = Tracer(job["workdir"]) if job["trace"] else None
    trace_at = job["seconds"] * 0.25
    trace_for = min(job["trace_seconds"], job["seconds"] * 0.5)
    tracing = traced_done = False
    c0 = global_metrics.get("xla_compiles", 0)
    n = 0
    t0 = time.monotonic()
    while True:
        now = time.monotonic() - t0
        if now >= job["seconds"]:
            break
        if tracer is not None and not tracing and not traced_done \
                and now >= trace_at:
            tracer.start()
            tracing, t_trace = True, time.monotonic()
        qs = batches[n % len(batches)]
        hits = engine.search_batch(qs, k=k)
        if n < len(batches):
            for j in range(width):
                s = keep.get(n * width + j)
                if s is not None:
                    kept[s] = [(h.name, h.score) for h in hits[j]]
        n += 1
        if tracing and time.monotonic() - t_trace >= trace_for:
            tracer.stop()
            tracing, traced_done = False, True
    elapsed = time.monotonic() - t0
    if tracing:
        tracer.stop()
    traced = tracer.reduce() if tracer is not None else None
    compiles = int(global_metrics.get("xla_compiles", 0) - c0)
    say({"done": True, "t0": t0, "elapsed_s": elapsed, "batches": n,
         "queries": n * width, "compiles_in_window": compiles,
         "trace": traced,
         "kept": {str(s): v for s, v in kept.items()},
         **device_report(engine)})
    return 0


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        job = json.load(f)
    from tfidf_tpu.utils.compile_cache import configure_compile_cache
    from tfidf_tpu.utils.config import load_config

    if job["rehearse"] is False and job.get("break"):
        raise SystemExit("the break hook exists for the rehearsal test only")
    configure_compile_cache()
    cfg = load_config().replace(**job["config"])
    return serve(job, cfg) if job["mode"] == "serve" else batch(job, cfg)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
