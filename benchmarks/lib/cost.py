"""What the scoring step HAS to do, counted from the corpus and the
batch — never from the program's own layout — and the chip's peaks.

The step scores a batch of ``B`` queries holding ``U`` distinct terms
against every posting of the shard:

* bytes: each posting's term id (4) and impact (4) read once from HBM,
  and the ``[B, docs]`` float32 score space written once. Padding the
  program adds to its blocks is its own cost, not the algorithm's, so it
  is not counted: a tighter layout raises the share.
* operations: the contraction of the ``[B, U]`` query matrix with the
  ``[U, docs]`` term-by-document matrix the kernel builds, ``2 * B * U *
  docs``, charged against the MXU's bf16 peak (the fastest the chip
  could do them). The compare/select work that BUILDS that matrix on the
  VPU has no published peak and is not counted, so the share is a floor
  on how far the kernel is from the chip, not a utilisation.

``roofline_share`` = max(bytes / peak bytes/s, operations / peak op/s) /
measured kernel time. An unknown device kind is an error, not a default.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {_PEAKS}")
    return table[device_kind]


def score_step_cost(*, nnz: int, docs: int, batch: int,
                    unique_terms: float) -> dict:
    return {"bytes": 8.0 * nnz + 4.0 * batch * docs,
            "operations": 2.0 * batch * unique_terms * docs}


def roofline_share(cost: dict, kernel_seconds: float, device_kind: str
                   ) -> dict:
    """Share (0-1) of the roofline one step reached, and which bound set
    the least time."""
    peaks = peaks_for(device_kind)
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = cost["operations"] / peaks["bf16_flops_per_s"]
    least = max(t_bytes, t_ops)
    return {"share": least / kernel_seconds,
            "bound": "bytes" if t_bytes >= t_ops else "operations",
            "least_seconds": least}


def a_build_ops_model() -> dict:
    """``bench.py kernel_cost_model()``'s count, kept as a count: VPU
    vreg operations per padded entry per unique-term lane (v4 folds two
    width rows into one accumulate add). From the kernel's source, not a
    measurement; printed beside the kernel time, never divided by a
    peak."""
    v3 = {"compare": 1.0, "select": 1.0, "accumulate_add": 1.0}
    v4 = {"compare": 1.0, "select": 1.0, "accumulate_add": 0.5}
    return {"unit": "vreg_ops_per_padded_entry_per_uniq_lane",
            "v3_total": sum(v3.values()), "v4_total": sum(v4.values())}
