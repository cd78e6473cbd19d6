"""Nothing ``_pallas_eligible`` admits may be refused by the v5e compiler.

Runs ``tests/kernel_compile_worker.py`` (compile-only Mosaic through
libtpu's topology client — no chip, seconds) in a subprocess: batch
buckets on every step of the tile schedule x widths from the ELL ladder
incl. 12, the rungs past 256 on every doc tile they take, a mesh-split
width of 1 and an odd one, plus the (4, 1) ``make_mesh_ell_search``
program at the two mesh cells' shapes (``msmarco4m-mesh``'s held to its
accepted programs by digest, both cells' temporaries under those of the
step that rearranged the scores), the served
device step at the benchmark cells' shapes (held to the programs of the
commit before the stretched step, by digest), the stretches of
``msmarco-full``'s step and the 1,000-deep top-k of
``msmarco2m-top1000``. Interpret-mode
parity (``tests/test_kernel_parity.py``) cannot see what this sees: a
kernel the interpreter runs happily and Mosaic rejects.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest


@pytest.fixture(scope="module")
def report():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu (the compile-only TPU client) is not installed")
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "kernel_compile_worker.py")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, worker], env=env,
                       capture_output=True, text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    assert lines, f"no report (rc={p.returncode}):\n{p.stderr[-2000:]}"
    report = json.loads(lines[-1])
    assert p.returncode == (1 if report["failures"] else 0)
    return report


def _failures(report, of_cells: bool) -> str:
    return "\n".join(f for f in report["failures"]
                     if f.startswith("cell ") == of_cells)


def test_every_eligible_shape_compiles_for_v5e(report):
    assert not _failures(report, of_cells=False)
    # a run that compiled nothing proves nothing
    assert report["compiled"] >= 89, report


def test_cells_device_step_compiles_for_v5e(report):
    """The scoring program and the top-k over its blocks, at the block
    lists of the benchmark's three corpora and the batch buckets its cells
    dispatch (``CELL_STEPS`` in the worker) — and neither program holds
    a second copy of the score space."""
    assert not _failures(report, of_cells=True)
    assert report["cells"] == 5, report


@pytest.mark.parametrize("B", (128, 256, 512))
def test_mesh_cell_step_compiles_for_v5e(report, B):
    """The (4, 1) ``jit_mesh_ell_search`` step at the ten per-shard block
    capacities of ``msmarco4m-mesh`` (``MESH_CELL_STEPS`` in the worker),
    at each batch bucket the cell dispatches: kernels and the all_gather
    are in it, and a chip's share of it (``memory_analysis()`` is per
    device) fits beside the 0.53 GB of a shard's index."""
    assert not _failures(report, of_cells=False)
    mine = [m for m in report["mesh_cells"]
            if (m["cell"], m["B"]) == ("msmarco4m-mesh", B)]
    assert len(mine) == 1, report["mesh_cells"]
    print(f"mesh step memory_analysis, B={B}: {mine[0]}")
    assert mine[0]["temp_bytes"] + mine[0]["argument_bytes"] < 8e9
    # no row past 256 distinct terms: the ten buckets, and the program
    # PR 41 left (``program_digest``: instruction for instruction)
    assert mine[0]["kernels"] == ["8", "16", "24", "32", "48", "64", "96",
                                  "128", "192", "256"]
    assert mine[0]["digest"] == PARENT_MESH_STEP_DIGESTS[B]
    # a shard's top-k reads the score blocks in place: the step's text
    # holds no array of the whole score space (``row_order_shapes``:
    # the padded concatenation, ``[B, doc_cap]`` or its transpose) and
    # its temporaries stay UNDER those of the step that gathered one
    assert mine[0]["temp_bytes"] < REARRANGED_MESH_STEP_TEMP_BYTES[
        "msmarco4m-mesh", B]
    assert mine[0]["row_order_shapes"] == [], mine[0]


# ``program_digest`` of ``msmarco4m-mesh``'s step, compiled for v5e:2x2
# from PR 41's tree, the PR that MEANT to change it: a shard's top-k
# over its score blocks in place (``ops.topk.blocks_topk``) where
# commit 64c8812 (PR 40's; its digests ac4051463eac3b17,
# 3a83d306e0c95e20, 168da395b36d85c8 were a193a7b's, PR 39's) gathered
# the blocks into ELL-row order and ran one ``lax.top_k`` over the row.
# The next PR that means to change the mesh step reads the new digests
# off ``python tests/kernel_compile_worker.py`` (``mesh_cells``).
PARENT_MESH_STEP_DIGESTS = {128: "82937132470bc899",
                            256: "d637b8be94d6690c",
                            512: "c7557907eaac58ad"}

# ``memory_analysis().temp_size_in_bytes`` a device of the step of
# commit 64c8812 (PR 41's parent: padded concatenation, its transposed
# copy, the gathered ``[B, doc_cap]``, the unchunked ``lax.top_k``),
# compiled here for v5e:2x2. PR 41's step reads 284,236,288 /
# 554,291,200 / 1,093,693,440 and 2,699,314,688.
REARRANGED_MESH_STEP_TEMP_BYTES = {
    ("msmarco4m-mesh", 128): 1_181_567_488,
    ("msmarco4m-mesh", 256): 2_362_511_872,
    ("msmarco4m-mesh", 512): 4_723_658_752,
    ("msmarco-doc-mesh", 512): 3_906_532_352}


def test_doc_mesh_cell_step_compiles_for_v5e(report):
    """``msmarco-doc-mesh``'s step: the (4, 1) program at the twelve
    per-shard buckets of its ``layout.shard_blocks`` and the one batch
    bucket its cell dispatches. The v5e compiler accepts the kernel at
    384 and 512 wide inside ``shard_map`` (VMEM by ``_pl_tiles``), and a
    chip's share of the step (temporaries 2.7 GB since PR 41 — the
    score blocks, one masked copy of them, the two wide blocks'
    transposes — where the step that gathered the scores into row
    order took 3.9, + 1.9 GB of impacts and terms as compiled here; the
    commit keeps 0.94 GB of ``tf`` beside them) fits half the chip's
    16 GB."""
    assert not _failures(report, of_cells=False)
    mine = [m for m in report["mesh_cells"]
            if m["cell"] == "msmarco-doc-mesh"]
    assert [m["B"] for m in mine] == [512], report["mesh_cells"]
    print(f"doc mesh step memory_analysis: {mine[0]}")
    assert mine[0]["kernels"][-2:] == ["384", "512"] \
        and len(mine[0]["kernels"]) == 12
    assert 3e9 < mine[0]["temp_bytes"] + mine[0]["argument_bytes"] < 8e9
    assert mine[0]["temp_bytes"] < REARRANGED_MESH_STEP_TEMP_BYTES[
        "msmarco-doc-mesh", 512]
    assert mine[0]["row_order_shapes"] == [], mine[0]


# ``program_digest`` of the two programs of every accepted one-chip
# cell's step, compiled for the v5e from commit 2278ade (PR 31's, the
# parent of the PR that brought the stretched step): a corpus that fits
# the chip as ONE stretch must go on running exactly these. A PR that
# MEANS to change the score or the top-k program of these cells reads
# the new digests off ``python tests/kernel_compile_worker.py``
# (``cell_digests``) and says in PERF.md what moved. PR 33 (5ef81a6's
# child) rewrote the kernel's BODY, the A-build's select chain: that is
# the Pallas call's ``backend_config``, which ``program_digest`` leaves
# out, so all ten stood: the XLA programs around the kernel are still
# 2278ade's, and the body is ``tests/test_kernel_parity.py``'s to hold.
PARENT_STEP_DIGESTS = {
    "msmarco2m/128": ("3eb9eb06c0ce11fc", "26f7238d520d063e"),
    "msmarco2m/256": ("eebf4a4db46e6100", "8e264a85a02781ef"),
    "msmarco2m/512": ("73d8af08ecdff80e", "7f2c09dd10adad7f"),
    "wiki1m/512": ("67342a45b6dbfc13", "81be580ebdfbf9d6"),
    "msmarco-doc/512": ("0f36f4b85d1f8092", "785c0c8e26aaaee3"),
}


@pytest.mark.parametrize("cell", sorted(PARENT_STEP_DIGESTS))
def test_accepted_cell_step_is_the_unstretched_pair(report, cell):
    """The score program and the top-k program of an accepted cell's
    step, compiled for the v5e, are the parent's HLO instruction for
    instruction, shapes, layouts and schedule included (what differs in
    the text is where a source line sits: ``program_digest``)."""
    assert not _failures(report, of_cells=True)
    got = report["cell_digests"][cell]
    assert (got["score"], got["topk"]) == PARENT_STEP_DIGESTS[cell]


def test_full_collection_stretches_compile_for_v5e(report):
    """``msmarco-full``'s B=512 step: six stretches over its eleven
    blocks, each stretch's two programs accepted by the v5e compiler,
    and what one stretch allocates (the score program's outputs and
    temporaries, the top-k's) within the budget the plan was made
    under."""
    assert not _failures(report, of_cells=True)
    stretches = report["stretches"]
    assert [s["blocks"] for s in stretches] == [
        [0, 2], [2, 3], [3, 5], [5, 6], [6, 7], [7, 11]]
    held = [s["score_output_bytes"] + s["score_temp_bytes"]
            + s["topk_temp_bytes"] + s["topk_output_bytes"]
            for s in stretches]
    print(f"stretch bytes: {held}, budget {stretches[0]['budget']}")
    assert max(held) <= stretches[0]["budget"]
    # the score space of the whole step would be 15.1 GB
    assert sum(s["score_output_bytes"] for s in stretches) > 15e9


def test_deep_topk_compiles_for_v5e_and_sorts_no_wide_row(report):
    """``packed_topk_chunked(k=1000)`` over ``msmarco2m``'s six blocks at
    B = 512, compiled for the v5e: the selection goes by candidates, so
    no row it sorts is wider than 16,384 (the straight route sorted
    ``[65536, 1024]`` and ``[512, 128000]`` a chunk, nineteen times a
    call), nothing in its text has those shapes or is a copy of the
    score space, no operation became a loop, and its temporaries (568 MB
    as compiled here; the straight route's 951 MB) leave the step's peak
    where the score program set it."""
    assert not _failures(report, of_cells=True)
    deep = report["deep_topk"]
    print(f"deep top-k: {deep}")
    assert (deep["k"], deep["B"]) == (1000, 512)
    assert deep["sorts"] >= 9      # three windows by candidates, at least
    assert deep["widest_sort"] <= 16384
    assert deep["back"] == []
    assert deep["whiles"] == 0
    assert deep["temp_bytes"] < 700e6
