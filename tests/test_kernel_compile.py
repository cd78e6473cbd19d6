"""Nothing ``_pallas_eligible`` admits may be refused by the v5e compiler.

Runs ``tests/kernel_compile_worker.py`` (compile-only Mosaic through
libtpu's topology client — no chip, seconds) in a subprocess: batch
buckets on every step of the tile schedule x widths from the ELL ladder
incl. 12, the rungs past 256 on every doc tile they take, a mesh-split
width of 1 and an odd one, plus the (4, 1) ``make_mesh_ell_search``
program at the two mesh cells' shapes (``msmarco4m-mesh``'s held to its
accepted programs by digest, both cells' temporaries under those of the
step that rearranged the scores), the served
device step at the benchmark cells' shapes (held to the programs of PR
43's tree, which turned the blocks width-major, by digest), the
stretches of ``msmarco-full``'s step, the 1,000-deep top-k of
``msmarco2m-top1000`` and the step of ``msmarco2m-q2d``'s expanded
queries (a unique-term capacity of 16,384, ``T`` 128); and that the two whole-document cells' steps
turn no block (no block-sized ``copy``, 1.6 GB fewer temporaries a
device than the steps that held ``[rows, width]``) while the narrow
cells' temporaries stand where they stood. Interpret-mode
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
    # PR 43 left (``program_digest``: instruction for instruction)
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
# from PR 43's tree, the PR that MEANT to change it: a bucket is held
# ``[D, width, rows_cap]`` under ``P("docs", "terms", None)``, so the
# step's parameters have other shapes, the per-shard reshape of a
# bucket to 128 wide is no longer followed by a bitcast, and the
# physical copies that turned the 128-, 192- and 256-wide buckets
# (256 rows each in this cell) are gone. Commit 5ec209a (PR 41's: a
# shard's top-k over its score blocks in place) read 82937132470bc899,
# d637b8be94d6690c, c7557907eaac58ad; 64c8812 (PR 40's)
# ac4051463eac3b17, 3a83d306e0c95e20, 168da395b36d85c8.
# The next PR that means to change the mesh step reads the new digests
# off ``python tests/kernel_compile_worker.py`` (``mesh_cells``).
PARENT_MESH_STEP_DIGESTS = {128: "c3429c82437ea380",
                            256: "012a07028e4b5f12",
                            512: "8460e52f50a9b03e"}

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
    chip's share of the step (temporaries 1.09 GB since PR 43 — the
    score blocks and one masked copy of them — where PR 41's step,
    which also turned the two wide blocks, took 2.7 and the step that
    gathered the scores into row order 3.9, + 1.9 GB of impacts and
    terms as compiled here; the commit keeps 0.94 GB of ``tf`` beside
    them) fits half the chip's 16 GB."""
    assert not _failures(report, of_cells=False)
    mine = [m for m in report["mesh_cells"]
            if m["cell"] == "msmarco-doc-mesh"]
    assert [m["B"] for m in mine] == [512], report["mesh_cells"]
    print(f"doc mesh step memory_analysis: {mine[0]}")
    assert mine[0]["kernels"][-2:] == ["384", "512"] \
        and len(mine[0]["kernels"]) == 12
    assert 2.9e9 < mine[0]["temp_bytes"] + mine[0]["argument_bytes"] < 8e9
    assert mine[0]["temp_bytes"] < REARRANGED_MESH_STEP_TEMP_BYTES[
        "msmarco-doc-mesh", 512]
    assert mine[0]["row_order_shapes"] == [], mine[0]


# ``program_digest`` of the two programs of every accepted one-chip
# cell's step, compiled for the v5e: a corpus that fits the chip as ONE
# stretch must go on running exactly these. A PR that MEANS to change
# the score or the top-k program of these cells reads the new digests
# off ``python tests/kernel_compile_worker.py`` (``cell_digests``) and
# says in PERF.md what moved. The five TOP-K digests are still commit
# 2278ade's (PR 31's, the parent of the PR that brought the stretched
# step). The five SCORE digests are PR 43's tree's, the PR that meant
# to move them: the index holds a block ``[width, rows_cap]``, so the
# program's parameters have other shapes (``f32[48,1048576]{1,0}``
# where it read ``f32[1048576,48]{0,1}``: the same bytes on the chip)
# and the bitcast behind each is gone; in ``msmarco-doc`` the four
# block-sized copies are gone with 1.61 GB of temporaries, in
# ``wiki1m`` the copy of its 256-row block of 128 became a prefetch.
# 2278ade's, which stood to PR 41: 3eb9eb06c0ce11fc, eebf4a4db46e6100,
# 73d8af08ecdff80e, 67342a45b6dbfc13, 0f36f4b85d1f8092. PR 33 (5ef81a6's
# child) rewrote the kernel's BODY, the A-build's select chain: that is
# the Pallas call's ``backend_config``, which ``program_digest`` leaves
# out; the body is ``tests/test_kernel_parity.py``'s to hold.
PARENT_STEP_DIGESTS = {
    "msmarco2m/128": ("b9e5295c949f3cd0", "26f7238d520d063e"),
    "msmarco2m/256": ("6bc2e7bb299735bb", "8e264a85a02781ef"),
    "msmarco2m/512": ("18845f72eac10f0f", "7f2c09dd10adad7f"),
    "wiki1m/512": ("e56e128280131584", "81be580ebdfbf9d6"),
    "msmarco-doc/512": ("5af72d2b9335d3cb", "785c0c8e26aaaee3"),
}

# ``memory_analysis().temp_size_in_bytes`` a device of the step of
# commit 5ec209a (PR 41's, PR 43's parent), which held every block
# ``[rows_cap, width]`` and turned it in ``score_block_pallas``,
# compiled here for the v5e: the score program of the one-chip cells,
# the whole step of the mesh cells.
ROW_MAJOR_STEP_TEMP_BYTES = {
    "msmarco2m/128": 129_024, "msmarco2m/256": 774_144,
    "msmarco2m/512": 580_608, "wiki1m/512": 580_608,
    "msmarco-doc/512": 1_610_935_296,
    "msmarco4m-mesh/128": 284_236_288, "msmarco4m-mesh/256": 554_291_200,
    "msmarco4m-mesh/512": 1_093_693_440,
    "msmarco-doc-mesh/512": 2_699_314_688}
# ... and what PR 43's tree reads where that is not the parent's to the
# byte. ``msmarco2m``'s three are (every block under 128 wide: the
# turn was a bitcast). ``wiki1m``'s 256-row block of 128 was turned by
# a synchronous copy into fast memory and is now prefetched there
# (``copy-start`` / ``copy-done``), which holds its buffer 64,512
# bytes longer beside 3.29 GB of scores. ``msmarco4m-mesh``'s buckets
# of 128, 192 and 256 (256 rows a shard) lost their copies: 326,656
# bytes fewer at every bucket of the batch.
WIDTH_MAJOR_STEP_TEMP_BYTES = {
    "wiki1m/512": 645_120,
    "msmarco4m-mesh/128": 283_909_632, "msmarco4m-mesh/256": 553_964_544,
    "msmarco4m-mesh/512": 1_093_366_784}


def _step_of(report, cell: str) -> dict:
    """A cell's entry of the report: ``cell_digests`` (one chip) or
    ``mesh_cells``."""
    name, B = cell.split("/")
    if cell in report["cell_digests"]:
        return report["cell_digests"][cell]
    (mine,) = [m for m in report["mesh_cells"]
               if (m["cell"], m["B"]) == (name, int(B))]
    return mine


@pytest.mark.parametrize("cell", ["msmarco-doc/512", "msmarco-doc-mesh/512"])
def test_wide_cell_step_turns_no_block(report, cell):
    """The two whole-document cells: every row in a block 384 or 512
    wide, which the parent's step copied whole on every call (``copy
    s32[524288,384]{0,1}``, 0.164 s of a traced 3 s on one chip). The
    index holds them as the kernel reads them, so the compiled step
    has no ``copy`` / ``transpose`` as large as a block
    (``block_copies`` in the worker; the parent's step shows four a
    device) and its temporaries stand 1.5 GB under the parent's."""
    assert not report["failures"]
    step = _step_of(report, cell)
    print(f"{cell}: temp_bytes {step['temp_bytes']}, the parent's "
          f"{ROW_MAJOR_STEP_TEMP_BYTES[cell]}")
    assert step["block_copies"] == [], step
    assert step["temp_bytes"] \
        <= ROW_MAJOR_STEP_TEMP_BYTES[cell] - 1_500_000_000


@pytest.mark.parametrize("cell", sorted(
    set(ROW_MAJOR_STEP_TEMP_BYTES)
    - {"msmarco-doc/512", "msmarco-doc-mesh/512"}))
def test_narrow_cell_step_keeps_its_temporaries(report, cell):
    """No block of these cells is turned by more than a bitcast (one
    of 256 rows excepted): their compiled steps hold the parent's
    temporaries, to the byte in ``msmarco2m``, and where not, what the
    tables above say and why. Never more than a block of 256 rows
    above the parent's."""
    assert not report["failures"]
    got = _step_of(report, cell)["temp_bytes"]
    parent = ROW_MAJOR_STEP_TEMP_BYTES[cell]
    assert got == WIDTH_MAJOR_STEP_TEMP_BYTES.get(cell, parent)
    assert got <= parent + 256 * 128 * 4


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


def test_long_query_step_compiles_for_v5e(report):
    """``msmarco2m-q2d``'s score program: ``msmarco2m``'s six blocks at
    B = 512 under a query batch of ``u_cap`` 16,384 and ``T`` 128 (no
    shape class past 1,024 / 32 was compiled for the v5e before PR 46).
    The v5e compiler accepts the kernel on every block (the uniq tile
    stays 512: a grid of 32 uniq steps a doc tile, the same VMEM as at
    1,024), the query matrix is in the program in both its shapes, and
    what the program holds beside its 4.57 GB of scores is the matrix
    and its chunk-major copy (33.5 MB each) at most, not a second score
    space."""
    assert not _failures(report, of_cells=True)
    step = report["long_query"]
    print(f"long-query step: {step}")
    assert (step["B"], step["u_cap"], step["T"]) == (512, 16384, 128)
    assert step["kernels"] == 6
    assert step["query_matrix_shapes"] == ["f32[512,16385]",
                                           "f32[128,512,128]"]
    assert 0 <= step["output_bytes"] - 4 * 512 * 2_232_832 < 4096
    assert step["temp_bytes"] < 2 * 4 * 512 * 16385 + 2_000_000
