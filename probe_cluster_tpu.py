"""Probe: config-2b cluster data plane with a TPU-backed worker.

Thin wrapper over :func:`bench.bench_cluster_tpu` (the canonical
implementation and constants live there) so the topology can be
exercised standalone without running the whole bench suite.

IMPORTANT: run this as its own process with no prior jax init in the
parent — a chip belongs to one process, and the TPU worker subprocess
(``JAX_PLATFORMS=tpu``) fails at start-up if the parent holds it.
"""

from __future__ import annotations

import json

import numpy as np

from bench import bench_cluster_tpu

if __name__ == "__main__":
    print(json.dumps(bench_cluster_tpu(np.random.default_rng(7))))
