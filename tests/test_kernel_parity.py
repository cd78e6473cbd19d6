"""Tier-1 interpret-mode kernel parity (ISSUE 15 satellite).

Drives the SAME ``kernel_parity.py`` case machinery the hardware
harness uses, on CPU-scaled shapes in Pallas interpret mode — so every
tier-1 run holds the kernel (the select chain at widths with and
without a static tail, the pad trap its order is held by) to the XLA
reduce-fusion oracle, and a kernel regression fails CI on a CPU box. Whether Mosaic ACCEPTS the kernel is
``tests/test_kernel_compile.py``; its results on a chip are
``chip_smoke.py``'s engine stage.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _root not in sys.path:
    sys.path.insert(0, _root)

from kernel_parity import INTERPRET_CASES as T1_CASES  # noqa: E402
from kernel_parity import (LONG_INTERPRET_CASE,  # noqa: E402
                           MESH_INTERPRET_CASE,
                           STRETCH_INTERPRET_CASE, TOP_K,
                           TOPK_INTERPRET_CASE, make_case, run_case,
                           run_long_query_case, run_mesh_case,
                           run_stretch_case, run_topk_case,
                           run_trap_case, width_major)
from tfidf_tpu.ops import ell  # noqa: E402
from tfidf_tpu.ops.csr import build_coo  # noqa: E402
from tfidf_tpu.ops.ell import (_pallas_eligible, _pl_tiles,  # noqa: E402
                               _score_block, build_ell_from_coo,
                               score_block_pallas)
from tfidf_tpu.ops.scoring import (QueryBatch,  # noqa: E402
                                   _compile_queries)

@pytest.mark.parametrize("multiplicity", [False, True],
                         ids=["fractional", "multiplicity"])
@pytest.mark.parametrize("i", range(len(T1_CASES)))
def test_interpret_parity(i, multiplicity):
    """Both contractions: fractional weights take the HIGHEST dot,
    multiplicities the three bf16 passes (``r["bf16x3"]``)."""
    rng = np.random.default_rng(100 + i)
    r = run_case(f"t1-case{i}", rng, multiplicity=multiplicity,
                 **T1_CASES[i])
    assert r["ok"], r
    assert r["bf16x3"] is multiplicity
    # a block past the 256 rung, interpreted, is bit-equal to the XLA
    # path whichever contraction it takes (on the chip the fractional
    # one reads 1.2e-7: Mosaic's HIGHEST is six bf16 passes there)
    if T1_CASES[i]["width"] > 256:
        assert r["max_abs_delta"] == 0.0, r


def test_topk_case_of_the_matrix():
    """The top-k case ``kernel_parity.py`` runs on the chip beside the
    kernel's, at a CPU's scale: two-stage against ``lax.top_k``, ids as
    well as values, every live chunk by group maxima."""
    r = run_topk_case(np.random.default_rng(31), **TOPK_INTERPRET_CASE)
    assert r["ok"], r
    assert (r["chunks"], r["skipped"], r["grouped"]) == (1, 0, 1)
    assert r["cell_temp_bytes"] > 0


def test_stretch_case_of_the_matrix():
    """The stretched step's case ``kernel_parity.py`` runs on the chip,
    at a CPU's scale: three blocks a block a stretch against the one
    program pair, packed answers bit-equal, the tied winners across the
    first stretch edge."""
    r = run_stretch_case(np.random.default_rng(32),
                         **STRETCH_INTERPRET_CASE)
    assert r["ok"], r
    assert r["stretches"] == 3 and r["lives"] == [512, 512, 300]


def test_long_query_case_of_the_matrix():
    """The long-query case ``kernel_parity.py`` runs on the chip (a
    batch of ``msmarco2m-q2d``'s expanded queries: ~10,000 distinct
    terms under a capacity of 16,384, ``T`` 128, a multiplicity up to
    53), at a CPU's scale: a capacity of 2,048, so four uniq tiles a doc
    tile and the last of them partly live, on the three-pass
    contraction."""
    r = run_long_query_case(np.random.default_rng(46),
                            **LONG_INTERPRET_CASE)
    assert r["ok"], r
    assert 1024 < r["n_uniq"] < r["u_cap"] == 2048
    assert r["n_uniq"] % 512             # the last uniq tile partly live
    assert 26 <= r["max_weight"] <= 53 and r["dead_rows_zero"]


def test_mesh_case_of_the_matrix():
    """The mesh step's case ``kernel_parity.py`` runs on the chip, at a
    CPU's scale: whole documents on a (4, 1) mesh of the virtual
    devices, every shard a 512- and a 384-wide bucket on the
    (interpreted) kernel and no residual, against the same step on
    ``_score_block``; both weight kinds."""
    r = run_mesh_case(np.random.default_rng(34), devices=jax.devices()[:4],
                      **MESH_INTERPRET_CASE)
    assert r["ok"], r
    assert r["devices"] == 4 and r["residual_nnz"] == 0
    assert r["buckets"] == [[512, 512], [384, 512]]    # [width, rows]
    assert sorted(r["weights"]) == ["fractional", "multiplicity"]


# one sub-tile's build at each of its shapes: no ``_PL_ROWS``-row loop
# (7), one trip and a tail (12), the loop alone (32), the loop and a
# tail of one row (33), the loop and an even tail (38)
TRAP_WIDTHS = (7, 12, 32, 33, 38)


@pytest.mark.parametrize("width", TRAP_WIDTHS)
def test_pad_trap(width):
    """The A-build's ORDER. A pad is ``term 0, impact 0`` and term 0 is
    a real term: in rows of every length from 1 to the width whose
    first entry is a live posting of term 0, the trailing pads match
    the lane of term 0 too, and a chain that walked the width upwards
    would leave their 0.0 there. The kernel is bit-equal to the XLA
    oracle and scores those rows by the live impact."""
    r = run_trap_case(np.random.default_rng(330 + width), rows_cap=256,
                      width=width, B=16, u_req=256)
    assert r["ok"], r
    assert r["oracle_bit_equal"] and r["term0_live"]
    assert r["term0_rows"] >= 128
    # the block the kernel was handed is width-major and its pads
    # trail down the WIDTH axis, rows of every length from 1 up
    assert r["pads_trail_the_width"]


def _all_eqns(jaxpr, out=None):
    """Every equation under ``jaxpr``: the Pallas kernel's body, its
    loops and branches included."""
    out = [] if out is None else out
    for e in jaxpr.eqns:
        out.append(e)
        for v in e.params.values():
            for x in v if isinstance(v, (list, tuple)) else [v]:
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    _all_eqns(x, out)
    return out


def test_a_build_is_one_select_chain():
    """What the kernel's body holds, read off its jaxpr at a width with
    the loop and a tail (38 = 4 x 8 + 6): for each of the 8 + 6 traced
    width rows ONE compare and ONE select of a ``[_PL_SU, td]``
    sub-tile (a shape only the A-build has) and no add of it, and ONE
    load of each posting array a ``rows()`` call: two calls, four
    loads (a ref access is ~2.3 ms of Mosaic lowering a block a
    bucket: PERF.md §6, PR 27)."""
    width, rows_cap, td = 38, 512, 512
    f32, i32 = jnp.float32, jnp.int32
    jaxpr = jax.make_jaxpr(score_block_pallas)(
        jnp.zeros((width, rows_cap), f32), jnp.zeros((width, rows_cap), i32),
        jnp.zeros((_U_CAP,), i32), jnp.int32(5),
        jnp.zeros((_B, _U_CAP + 1), f32), jnp.int32(rows_cap))
    eqns = _all_eqns(jaxpr.jaxpr)

    def count(name, shape):
        return sum(e.primitive.name == name
                   and e.outvars[0].aval.shape == shape for e in eqns)

    traced = ell._PL_ROWS + width % ell._PL_ROWS
    assert count("eq", (_SU, td)) == traced
    assert count("select_n", (_SU, td)) == traced
    assert count("add", (_SU, td)) == 0
    posting_loads = [e.outvars[0].aval.shape for e in eqns
                     if e.primitive.name == "get"
                     and e.invars[0].aval.shape == (width, td)]
    assert sorted(posting_loads) == sorted(
        2 * [(ell._PL_ROWS, td), (width % ell._PL_ROWS, td)])


def _mesh_shards(docs):
    """``build_mesh_ell`` over a (2, 2) mesh of the CPU's devices, as
    each device holds it: the ``tf`` ``[1, width / 2, rows_cap]`` of
    every bucket's every shard, the terms axis's contiguous slice of
    the width axis included."""
    from tfidf_tpu.engine.index import DocEntry
    from tfidf_tpu.parallel.mesh import make_mesh
    from tfidf_tpu.parallel.mesh_ell import build_mesh_ell, place_mesh_ell

    mesh = make_mesh((2, 2), devices=jax.devices()[:4])
    entries = [DocEntry(f"d{i}", np.asarray(sorted(d), np.int32),
                        np.asarray([d[t] for t in sorted(d)], np.float32),
                        float(sum(d.values())))
               for i, d in enumerate(docs)]
    host, _perm = build_mesh_ell([entries[0::2], entries[1::2]], mesh,
                                 lambda x: x, min_rows=8)
    arrays = place_mesh_ell(host, mesh)
    assert all(a.shape[1] == w for a, w in zip(
        arrays.tf, (256, 192, 128, 96, 64, 48, 32, 24, 16, 8)))
    assert all(sh.data.shape[-2] * 2 == a.shape[-2]
               for a in arrays.tf for sh in a.addressable_shards)
    return [np.asarray(sh.data) for a in arrays.tf
            for sh in a.addressable_shards]


def _coo_blocks(docs):
    """``build_ell_from_coo``'s blocks ``[width, rows_cap]`` over the
    same documents, longest first as ``ShardIndex.to_coo`` hands them
    over."""
    docs = sorted(docs, key=len, reverse=True)
    coo = build_coo(docs, vocab_cap=512, min_nnz_cap=1 << 10,
                    min_doc_cap=64)
    blocks = build_ell_from_coo(coo, width_cap=64, min_rows=8).blocks
    assert all(b.tf.shape[0] == b.width for b in blocks)
    return [b.tf for b in blocks]


@pytest.mark.parametrize("blocks_of", [_coo_blocks, _mesh_shards],
                         ids=["build_ell_from_coo", "build_mesh_ell"])
def test_builders_trail_their_pads(blocks_of):
    """The other half of the select chain's contract, at the two
    builders that feed the kernel: in every document of every block
    (on the mesh: of every device's slice of the width) the non-zero
    entries all precede the first pad DOWN THE WIDTH AXIS (the last but
    one: a block is ``[width, rows_cap]``), so the chain, walking the
    width from its last row down, applies a live entry after every pad
    of its document."""
    rng = np.random.default_rng(33)
    docs = []
    for _ in range(120):
        ids = rng.choice(400, size=rng.integers(1, 60), replace=False)
        docs.append({int(t): float(rng.integers(1, 5)) for t in ids})
    docs[3][0] = 2.0            # term 0 itself is live in some rows
    docs[40][0] = 1.0
    blocks = blocks_of(docs)
    assert len(blocks) > 1
    live = 0
    for tf in blocks:
        filled = tf != 0
        assert (filled[..., 1:, :] <= filled[..., :-1, :]).all()
        live += int(filled.sum())
    assert live == sum(len(d) for d in docs)


def test_ingest_rejects_duplicate_or_unsorted_ids():
    """The layout contract the kernel's select chain relies on (distinct term
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
                if not _pallas_eligible(rows_cap, B, u_cap):
                    continue
                for width in ell.ELL_WIDTH_LADDER:
                    td, tu = _pl_tiles(rows_cap, B, u_cap, width)
                    assert rows_cap % td == 0 and u_cap % tu == 0, \
                        (rows_cap, B, u_cap, width, td, tu)
                    # every rung's posting blocks fit their share of VMEM
                    assert ell._PL_ENTRY_VMEM * width * td \
                        <= ell._PL_POSTINGS_VMEM
                    # and no rung to 256 ever shrank a tile
                    assert width > 256 or (td, tu) == _pl_tiles(
                        rows_cap, B, u_cap, 8)


# ---- the sub-tile nest (PR 27): work follows n_uniq -----------------
#
# One block under query batches whose distinct-term count sits on every
# edge of the nest: the 8-row sublane grain, the sub-tile (_PL_SU), the
# 128-row contraction chunk, the 512-lane uniq tile and the whole
# capacity. Its WIDTH takes the four shapes one sub-tile's build can
# have: no ``_PL_ROWS``-row loop at all (7), the loop alone (32), the
# loop plus a tail of one row (33), the loop plus a tail of six (38).

_SU = ell._PL_SU
N_UNIQS = (1, 7, 8, 9, _SU - 1, _SU, _SU + 1, 127, 128, 129, 511, 512,
           513, 1024)
WIDTHS = (7, 32, 33, 38)
_ROWS, _LIVE_ROWS, _B, _U_CAP, _VOCAB = 512, 400, 16, 1024, 4000


def _subtile_case(n_uniq: int, width: int):
    """(impact, term, QueryBatch) with exactly ``n_uniq`` distinct query
    terms in a capacity of 1,024; half of them occur in the block."""
    rng = np.random.default_rng(1000 + n_uniq)
    imp, term, _qb = make_case(rng, rows_cap=_ROWS, width=width,
                               n_rows=_LIVE_ROWS, B=_B, n_terms=4,
                               u_req=_U_CAP, vocab=_VOCAB, ragged=True)
    in_block = np.unique(term[:_LIVE_ROWS][imp[:_LIVE_ROWS] > 0])
    take = rng.permutation(in_block)[:(n_uniq + 1) // 2]
    rest = np.setdiff1d(np.arange(_VOCAB), take)
    uniq = np.sort(np.concatenate(
        [take, rng.permutation(rest)[:n_uniq - take.shape[0]]]))
    assert uniq.shape[0] == n_uniq
    uniq_pad = np.zeros(_U_CAP, np.int32)
    uniq_pad[:n_uniq] = uniq
    slots = rng.integers(0, n_uniq, size=(_B, 8)).astype(np.int32)
    weights = (1.0 + rng.random((_B, 8))).astype(np.float32)
    dead = rng.random((_B, 8)) < 0.3          # padded query slots
    slots[dead], weights[dead] = _U_CAP, 0.0
    return imp, term, QueryBatch(uniq_pad, np.int32(n_uniq), slots,
                                 weights)


def _compiled(q):
    """``(slot_of, qc_ext)`` of the batch, as the scorers take them."""
    return _compile_queries(jax.tree.map(jnp.asarray, q), _VOCAB)


# one compile a width: ``n_uniq`` and the block's contents are traced
_kernel = jax.jit(score_block_pallas)
_xla = jax.jit(_score_block, static_argnums=4)


def _kernel_scores(imp, term, q, *, poison=False):
    """The kernel's ``[B, rows_cap]`` for ``q``; ``poison`` puts term
    ids that DO occur in the block into ``uniq``'s pad rows and a
    non-zero weight into their ``qc`` columns."""
    _slot_of, qc_ext = _compiled(q)
    uniq, n = np.array(q.uniq), int(q.n_uniq)
    if poison:
        uniq[n:] = np.resize(term[:_LIVE_ROWS, :4].ravel(), _U_CAP - n)
        qc_ext = qc_ext.at[:, n:_U_CAP].set(7.0)
    return np.asarray(_kernel(
        width_major(imp), width_major(term), jnp.asarray(uniq),
        jnp.int32(n), qc_ext, jnp.int32(_LIVE_ROWS)))


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("n_uniq", N_UNIQS)
def test_subtile_nest_matches_xla(n_uniq, width):
    """Kernel against the XLA path within the harness's tolerance, for
    every edge of the nest; and what lies in ``uniq``'s pad rows and
    their ``qc`` columns never reaches a score."""
    imp, term, q = _subtile_case(n_uniq, width)
    got = _kernel_scores(imp, term, q)
    slot_of, qc_ext = _compiled(q)
    want = np.asarray(_xla(width_major(imp), width_major(term),
                           slot_of, qc_ext.T, 2048))
    assert np.abs(want).max() > 0
    assert np.abs(got - want)[:, :_LIVE_ROWS].max() < 1e-4
    assert not got[:, _LIVE_ROWS:].any()       # all-pad rows
    k = min(TOP_K, _LIVE_ROWS)
    assert np.array_equal(
        np.argsort(-got[:, :_LIVE_ROWS], axis=1, kind="stable")[:, :k],
        np.argsort(-want[:, :_LIVE_ROWS], axis=1, kind="stable")[:, :k])
    assert np.array_equal(
        got, _kernel_scores(imp, term, q, poison=True))
