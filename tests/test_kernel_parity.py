"""Tier-1 interpret-mode kernel parity (ISSUE 15 satellite).

Drives the SAME ``kernel_parity.py`` case machinery the hardware
harness uses, on CPU-scaled shapes in Pallas interpret mode — so every
tier-1 run exercises BOTH A-build variants (v3 single-row; v4 paired
rows incl. the odd-width tail) against the XLA reduce-fusion oracle
plus the v3==v4 bitwise-identity contract, and a kernel regression
fails CI on a CPU box. Whether Mosaic ACCEPTS the kernel is
``tests/test_kernel_compile.py``; its results on a chip are
``chip_smoke.py``'s engine stage.
"""

import os
import sys

import numpy as np
import pytest

_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _root not in sys.path:
    sys.path.insert(0, _root)

from kernel_parity import INTERPRET_CASES as T1_CASES  # noqa: E402
from kernel_parity import run_case  # noqa: E402
from tfidf_tpu.ops.ell import _pallas_eligible, _pl_tiles  # noqa: E402

@pytest.mark.parametrize("i", range(len(T1_CASES)))
def test_interpret_parity(i):
    rng = np.random.default_rng(100 + i)
    r = run_case(f"t1-case{i}", rng, **T1_CASES[i])
    assert r["ok"], r
    assert r["cross_variant_bitwise_equal"], r


def test_eligibility_envelope_shared_across_variants():
    """A config flip between A-build variants must never change WHICH
    blocks ride the kernel — only how A is built (the gate contract)."""
    for rows_cap in (128, 256, 768, 4096, 4097):
        for B in (64, 2048, 4096):
            for u_cap in (256, 512, 640):
                assert (_pallas_eligible(rows_cap, B, u_cap, "v3")
                        == _pallas_eligible(rows_cap, B, u_cap, "v4")), \
                    (rows_cap, B, u_cap)
    # an unknown variant fails LOUDLY — returning False would silently
    # route the whole engine to the XLA path on a config typo
    with pytest.raises(ValueError, match="kernel_a_build"):
        _pallas_eligible(512, 64, 256, "v9")


def test_ingest_rejects_duplicate_or_unsorted_ids():
    """The layout contract the v4 pair fold relies on (distinct term
    ids per row) is enforced at the ingest seam: a raw-array caller
    passing duplicate or unsorted ids must fail loudly there, not
    score differently on the kernel vs the XLA path."""
    from tfidf_tpu.engine.index import ShardIndex
    from tfidf_tpu.engine.segments import SegmentedIndex
    from tfidf_tpu.models import BM25Model
    from tfidf_tpu.parallel.mesh import make_mesh
    from tfidf_tpu.parallel.mesh_ell_index import MeshEllIndex

    model = BM25Model()
    mesh = make_mesh()
    indexes = [ShardIndex(model), SegmentedIndex(model),
               MeshEllIndex(model, mesh=mesh)]
    for ix in indexes:
        ix.add_document_arrays(
            "ok", np.asarray([1, 5, 9], np.int32),
            np.asarray([1, 1, 1], np.float32), 3.0)
        for bad in ([5, 5], [9, 1]):
            with pytest.raises(ValueError, match="strictly ascending"):
                ix.add_document_arrays(
                    "bad", np.asarray(bad, np.int32),
                    np.asarray([1.0, 1.0], np.float32), 2.0)


def test_tile_schedule_divides_capacities():
    """The tile schedule must keep the grid divisibility invariant for
    every eligible shape — a non-divisor tile would silently drop the
    trailing tile."""
    for rows_cap in (256, 768, 1024, 4096, 65536):
        for B in (64, 512, 1024, 2048):
            for u_cap in (256, 512, 1024, 4096):
                if not _pallas_eligible(rows_cap, B, u_cap, "v4"):
                    continue
                td, tu = _pl_tiles(rows_cap, B, u_cap)
                assert rows_cap % td == 0 and u_cap % tu == 0, \
                    (rows_cap, B, u_cap, td, tu)
