"""The stage metrics of PR 24 (the stages and waits of one timer,
``tfidf_tpu/utils/tracing.py``) come out of a traced rehearsal of every
cell that lists them.

What a CPU rehearsal cannot show is left to the chip: a metric read from
the device's own lines (``source: device_trace`` — there is no ``XLA
Modules`` line on the CPU). The two hand-off waits exist only on the
pipeline executor, which ``search_pipeline_mode="auto"`` turns on for an
accelerator and off for the CPU, so the rehearsal asks for it as a
deployment would, through the environment.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

STAGES = ("leader_search_ms", "coalesce_wait_ms", "reply_wake_ms",
          "worker_handle_ms", "dispatch_wait_ms", "fetch_wait_ms",
          "vectorize_ms", "device_wait_ms", "d2h_ms", "assemble_ms",
          "batch_fill", "score_program_ms", "topk_ms")


def listed(cell: str) -> dict[str, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {m["name"]: m for m in bench["per_layer"]
            if m["name"].rsplit(".", 1)[0] in STAGES
            and cell in m["workloads"]}


@pytest.mark.parametrize("cell,count", [("msmarco2m.served-sat", 13),
                                        ("msmarco2m.served-steady", 13),
                                        ("wiki1m.batch", 6)])
def test_traced_rehearsal_prints_the_stage_metrics(cell, count):
    want = listed(cell)
    # the batch cell's six count PR 23's topk_ms.batch and
    # vectorize_ms.batch, which read the same stages
    assert len(want) == count
    p = subprocess.run(
        [sys.executable, f"{BENCH}/run.py", "--workload", cell, "--seed",
         "2147483659", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "TFIDF_SEARCH_PIPELINE_MODE": "executor"})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    got = line["metrics"]
    for name, m in want.items():
        if m["source"] == "device_trace":
            continue
        assert name in got, (name, sorted(got))
        assert got[name]["value"] >= 0 and got[name]["unit"] == m["unit"]
    for name in got:
        if name.startswith("batch_fill."):
            assert 0 < got[name]["value"] <= 1
