"""Can device->host fetches overlap device compute on this runtime?

VERDICT r5 Weak #3: PERF.md attributed the distributed serving wall to
"fetches from concurrent scatter batches do not overlap", but the claim
was asserted, not isolated. This probe settles it either way with two
experiments, and writes the artifact (``PROBE_OVERLAP.json``):

1. **Device experiment** — two INDEPENDENTLY FETCHABLE device programs
   (disjoint inputs, disjoint outputs). Measured three ways, medians
   over ``iters``:

   * ``serial``: dispatch A, fetch A, dispatch B, fetch B — the shape
     the pre-round-6 worker data plane produced under concurrent
     scatter RPCs (each handler drained its own fetch before the next
     dispatch ran);
   * ``double_buffered``: dispatch A, dispatch B, fetch A, fetch B —
     program B computes while A's result crosses the link;
   * ``threaded``: two threads each dispatch+fetch their own program —
     can the runtime overlap two in-flight transfers at all?

   ``overlap_ratio = serial / overlapped``: ~2.0 means fetch fully
   hides under compute (the wall was software — the round-6 pipeline
   executor recovers the loss); ~1.0 means the runtime serializes the
   transfers (the wall is the link) — either answer converts the
   PERF.md assertion into evidence.

2. **Executor experiment** — the actual ``PipelineExecutor`` over a
   fake 2-stage workload with known costs (dispatch = compute_s,
   fetch = rtt_s, both pure sleeps, no device needed): steady-state
   pipelined time should approach ``max(compute, rtt)`` per chunk vs
   ``compute + rtt`` serial. Also asserts, deterministically (an event
   handshake, no timing), that a fetch really was in flight while a
   later chunk dispatched. This half runs in tier-1 on CPU
   (``tests/test_pipeline.py``) so the overlap machinery is exercised
   on every push.

Run ``make probe-overlap`` (or ``python probe_overlap.py``). NOTE: the
artifact records whatever backend the run found — on a CPU-only host
the device experiment measures shared-memory "transfers" (near-free,
ratios ~1.0 by construction); the verdict needs a run on a chip, which
has not been made (PERF.md: not measured).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

from tfidf_tpu.utils.compile_cache import configure_compile_cache

ARTIFACT = os.path.join(os.path.dirname(__file__), "PROBE_OVERLAP.json")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# experiment 2: the executor itself, fake workload (tier-1-safe)
# --------------------------------------------------------------------------

def executor_workload(n_chunks: int = 8, compute_s: float = 0.015,
                      rtt_s: float = 0.015, depth: int = 2) -> dict:
    """Drive :class:`PipelineExecutor` with a synthetic 2-program-shaped
    workload: dispatch costs ``compute_s`` (serialized, like a device
    queue), fetch costs ``rtt_s`` (the d2h link). Returns timings for a
    serial loop vs the pipelined executor, plus a DETERMINISTIC overlap
    witness: chunk 0's fetch blocks until chunk 1's dispatch has
    started, which can only complete if dispatch and fetch genuinely
    run concurrently (a serialized pipeline deadlocks into the timeout
    and fails the handshake)."""
    from tfidf_tpu.engine.pipeline import PipelineExecutor

    def make_stages(record):
        def dispatch(i):
            time.sleep(compute_s)
            record.append(("d", i))
            return (i,)

        def fetch(i):
            time.sleep(rtt_s)
            record.append(("f", i))
            return i * i

        return dispatch, fetch

    # serial baseline: the pre-round-6 shape (drain before next dispatch)
    rec_serial: list = []
    dispatch, fetch = make_stages(rec_serial)
    t0 = time.perf_counter()
    serial_out = [fetch(*dispatch(i)) for i in range(n_chunks)]
    serial_s = time.perf_counter() - t0

    # pipelined through the executor
    rec_pipe: list = []
    dispatch, fetch = make_stages(rec_pipe)
    ex = PipelineExecutor(depth=depth, name="probe")
    t0 = time.perf_counter()
    futures = [ex.submit(lambda i=i: dispatch(i), fetch)
               for i in range(n_chunks)]
    pipe_out = [f.result() for f in futures]
    pipelined_s = time.perf_counter() - t0

    # deterministic overlap witness (event handshake, no timing)
    started_d1 = threading.Event()
    witnessed = threading.Event()

    def d(i):
        if i == 1:
            started_d1.set()
        return (i,)

    def f(i):
        if i == 0 and started_d1.wait(timeout=5.0):
            witnessed.set()
        return i

    ws = [ex.submit(lambda i=i: d(i), f) for i in range(2)]
    for w in ws:
        w.result()
    ex.stop()

    return {
        "n_chunks": n_chunks,
        "compute_ms": compute_s * 1e3, "rtt_ms": rtt_s * 1e3,
        "depth": depth,
        "serial_s": round(serial_s, 4),
        "pipelined_s": round(pipelined_s, 4),
        "speedup": round(serial_s / pipelined_s, 3),
        "ideal_speedup": round((compute_s + rtt_s)
                               / max(compute_s, rtt_s), 3),
        "results_ok": serial_out == pipe_out
        == [i * i for i in range(n_chunks)],
        "fetch_order_fifo": [i for s, i in rec_pipe if s == "f"]
        == list(range(n_chunks)),
        "overlap_witnessed": witnessed.is_set(),
    }


# --------------------------------------------------------------------------
# experiment 1: two independently fetchable device programs
# --------------------------------------------------------------------------

def device_overlap(n: int = 2048, iters: int = 10) -> dict:
    """Two disjoint jitted programs; measure serial vs double-buffered
    vs threaded dispatch+fetch (medians)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def prog(x):
        return x @ x          # [n, n] result: the fetch moves n*n*4 bytes

    key = jax.random.PRNGKey(0)
    x1 = jax.random.normal(key, (n, n), jnp.float32)
    x2 = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
    # warm compiles + one fetch each
    np.asarray(prog(x1)).sum()
    np.asarray(prog(x2)).sum()

    def median(run):
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            run()
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    t_compute = median(lambda: (prog(x1).block_until_ready(),
                                prog(x2).block_until_ready()))

    def serial():
        np.asarray(prog(x1))
        np.asarray(prog(x2))

    def double_buffered():
        r1 = prog(x1)
        r2 = prog(x2)
        np.asarray(r1)
        np.asarray(r2)

    def threaded():
        outs = [None, None]

        def one(i, x):
            outs[i] = np.asarray(prog(x))

        ts = [threading.Thread(target=one, args=(i, x))
              for i, x in enumerate((x1, x2))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    t_serial = median(serial)
    t_double = median(double_buffered)
    t_threaded = median(threaded)
    dev = jax.devices()[0]
    return {
        "backend": dev.platform, "device": str(dev),
        "n": n, "iters": iters,
        "compute_only_ms": round(t_compute * 1e3, 2),
        "serial_ms": round(t_serial * 1e3, 2),
        "double_buffered_ms": round(t_double * 1e3, 2),
        "threaded_ms": round(t_threaded * 1e3, 2),
        "overlap_ratio_double_buffered": round(t_serial / t_double, 3),
        "overlap_ratio_threaded": round(t_serial / t_threaded, 3),
    }


def main() -> None:
    log("[overlap] executor experiment (fake workload)...")
    executor_workload(n_chunks=2)   # warm thread startup out of the timing
    exec_res = executor_workload(n_chunks=12)
    log(f"[overlap] executor: serial {exec_res['serial_s']}s vs "
        f"pipelined {exec_res['pipelined_s']}s "
        f"(speedup {exec_res['speedup']}x of ideal "
        f"{exec_res['ideal_speedup']}x), overlap_witnessed="
        f"{exec_res['overlap_witnessed']}")
    log("[overlap] device experiment (two independent programs)...")
    dev_res = device_overlap()
    log(f"[overlap] device [{dev_res['backend']}]: serial "
        f"{dev_res['serial_ms']}ms, double-buffered "
        f"{dev_res['double_buffered_ms']}ms (ratio "
        f"{dev_res['overlap_ratio_double_buffered']}), threaded "
        f"{dev_res['threaded_ms']}ms (ratio "
        f"{dev_res['overlap_ratio_threaded']})")
    ratio = max(dev_res["overlap_ratio_double_buffered"],
                dev_res["overlap_ratio_threaded"])
    if dev_res["backend"] != "tpu":
        conclusion = (
            "methodology + CPU control run: transfers on this backend "
            "are shared-memory (near-free), so ratios ~1.0 are expected "
            "and say nothing about a chip — run on one for the "
            "serving-path verdict")
    elif ratio >= 1.3:
        conclusion = ("fetches OVERLAP compute on this runtime: the r5 "
                      "wall was software; the pipeline executor "
                      "recovers it")
    else:
        conclusion = ("fetches SERIALIZE on this runtime: the wall is "
                      "the link, qps ceiling ~= batch/fetch_RTT")
    result = {"experiment": "scatter-batch fetch/compute overlap",
              "device": dev_res, "executor": exec_res,
              "conclusion": conclusion}
    with open(ARTIFACT, "w", encoding="utf-8") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    log(f"[overlap] artifact written: {ARTIFACT}")
    print(json.dumps({"overlap_ratio": ratio,
                      "backend": dev_res["backend"],
                      "executor_speedup": exec_res["speedup"],
                      "overlap_witnessed":
                      exec_res["overlap_witnessed"]}))


if __name__ == "__main__":
    configure_compile_cache()
    main()
