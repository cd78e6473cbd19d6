"""Compile — never run — the Pallas kernel for TPU v5e, on a CPU box.

libtpu ships a compile-only client: ``get_topology_desc`` hands back the
devices of a ``v5e:2x2`` slice that does not exist, and lowering a jitted
function on ShapeDtypeStructs sharded onto them runs the real Mosaic and
XLA:TPU compilers. That is enough to learn, in seconds and with no chip,
whether the compiler ACCEPTS a kernel — which the interpreter cannot say
(PR 14's packed-i16 variant and its v4 tile schedule passed every
interpret-mode test and were refused here).

Run as a script (``tests/test_kernel_compile.py`` does, in a subprocess:
the compile-only client is process-global state); prints one JSON line
``{"failures": [...], "compiled": N, "cells": M, "mesh_cells": [...],
"cell_digests": {...}, "stretches": [...], "deep_topk": {...},
"long_query": {...}}``.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec, SingleDeviceSharding)

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tfidf_tpu.ops import ell  # noqa: E402

# lower the Mosaic program, not the interpreter the CPU backend selects
ell.pallas_interpret = lambda: False

# 8: a bucket narrower than a bf16 sublane tile (16 rows), which the
# three-pass contraction's packed query operand must survive
BATCHES = (8, 32, 512, 1024, 2048)
# the ladder's ends and its 1.5x rungs, plus what a terms-axis split
# leaves of a rung (8/8 = 1) and an odd width (the lone last row); past
# 256 the rungs whole documents fill (384, 512), the last one a doc
# tile of 512 holds at B <= 512 (1024), the first that halves it (1536)
# and the top, which runs at the narrowest tile (``_pl_tiles``)
WIDTHS = (1, 8, 12, 33, 64, 256, 384, 512, 1024, 1536, 4096)
assert set(WIDTHS) & set(ell.ELL_WIDTH_LADDER) >= {
    8, 12, 64, 256, 384, 512, 1024, 1536, ell.ELL_WIDTH_LADDER[-1]}


def block_shapes(B: int):
    """(rows_cap, u_cap) pairs that reach every tile the schedule can
    pick at batch ``B``: 512-multiples take the big doc/uniq tiles, 768
    rows / 256 slots the small ones (the same once B caps both)."""
    yield 1024, 1024
    if B <= 512:
        yield 768, 256


def compile_block(dev, rows: int, width: int, B: int,
                  u_cap: int) -> None:
    sh = SingleDeviceSharding(dev)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    assert ell._pallas_eligible(rows, B, u_cap)
    fn = jax.jit(ell.score_block_pallas)
    fn.lower(s((width, rows), jnp.float32), s((width, rows), jnp.int32),
             s((u_cap,), jnp.int32), s((), jnp.int32),
             s((B, u_cap + 1), jnp.float32), s((), jnp.int32)).compile()


# The mesh cells: configuration -> the batch buckets its cell dispatches.
# The committed snapshot's shapes per docs-shard of the (4, 1) mesh are
# the configuration file's ``layout.shard_blocks`` (rows_cap of each
# bucket of ``mesh_ell_widths``, the same for every seed:
# tests/test_mesh_block_capacities.py). ``msmarco-doc-mesh``: whole
# documents, so two buckets past 256 (512, 384) before the ten.
MESH_CELL_STEPS = {"msmarco4m-mesh": (128, 256, 512),
                   "msmarco-doc-mesh": (512,)}


def compile_mesh_step(devices) -> list[dict]:
    """The served mesh program — ``make_mesh_ell_search`` on a (4, 1)
    ("docs", "terms") mesh: a small index is built for real on four
    virtual CPU devices, then every array is replaced by its shape on
    the same mesh of v5e devices — and then by the shapes of the mesh
    cell's snapshot (``MESH_CELL_STEPS``), whose per-device
    ``memory_analysis()`` and :func:`program_digest` are returned, a
    dict a batch bucket."""
    import dataclasses

    from tfidf_tpu.engine import Engine
    from tfidf_tpu.ops.scoring import QueryBatch
    from tfidf_tpu.parallel.mesh_ell import (make_mesh_ell_search,
                                             mesh_ell_widths)
    from tfidf_tpu.utils.config import Config

    engine = Engine(Config(engine_mode="mesh", query_batch=32,
                           min_doc_capacity=256))
    rng = np.random.default_rng(0)
    for i in range(400):
        ids = np.unique(rng.integers(0, 500, size=rng.integers(3, 40)))
        engine.index.add_document_arrays(
            f"d{i}", ids.astype(np.int32),
            np.ones(ids.shape[0], np.float32), float(ids.shape[0]))
    engine.commit()
    snap = engine.index.snapshot
    qb, _ = engine.searcher._vectorize(["x"] * 32, 32)
    tpu_mesh = Mesh(np.asarray(devices).reshape(4, 1), ("docs", "terms"))

    def abstract(x, shape=None):
        x = jnp.asarray(x)   # host-side query arrays: replicated
        spec = getattr(x.sharding, "spec", PartitionSpec())
        return jax.ShapeDtypeStruct(
            x.shape if shape is None else shape, x.dtype,
            sharding=NamedSharding(tpu_mesh, spec))

    args = jax.tree.map(abstract, (snap.base, snap.delta, snap.df_g,
                                   snap.n_docs, snap.avgdl, qb))
    make_mesh_ell_search(tpu_mesh, k=10,
                         packed=True).lower(*args).compile()

    def each(like, shapes):
        return tuple(abstract(like, shape) for shape in shapes)

    out = []
    for name, batches in MESH_CELL_STEPS.items():
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "benchmarks", "configs",
                name + ".json")) as f:
            shard = json.load(f)["layout"]["shard_blocks"]
        widths = tuple(shard["widths"])
        assert widths == mesh_ell_widths(widths[0])
        rows, doc_cap = shard["rows"], shard["doc_cap"]
        vocab_cap = shard["vocab_cap"]
        # a bucket is held width-major: [D, width, rows_cap]
        blocks = tuple([4, w, r] for r, w in zip(rows, widths))
        by_rows = tuple([4, r] for r in rows)
        base = dataclasses.replace(
            jax.tree.map(abstract, snap.base),
            tf=each(snap.base.tf[0], blocks),
            term=each(snap.base.term[0], blocks),
            impact=each(snap.base.impact[0], blocks),
            dl=each(snap.base.dl[0], by_rows),
            block_live=abstract(snap.base.block_live, [4, len(widths)]),
            live=abstract(snap.base.live, [4, doc_cap]),
            res_dl=abstract(snap.base.res_dl, [4, doc_cap]),
            doc_cap=doc_cap)
        delta = dataclasses.replace(
            jax.tree.map(abstract, snap.delta),
            df=abstract(snap.delta.df, [4, 1, vocab_cap]),
            vocab_cap=vocab_cap)
        for B in batches:
            q = QueryBatch(
                uniq=abstract(qb.uniq, [1024]), n_uniq=abstract(qb.n_uniq),
                slots=abstract(qb.slots, [B, qb.slots.shape[1]]),
                weights=abstract(qb.weights, [B, qb.weights.shape[1]]))
            step = make_mesh_ell_search(
                tpu_mesh, k=10, packed=True).lower(
                base, delta, abstract(snap.df_g, [vocab_cap]),
                abstract(snap.n_docs), abstract(snap.avgdl), q).compile()
            text = step.as_text()
            assert text.count("tpu_custom_call") >= 4 \
                and "all-gather" in text, "kernels or gather missing"
            m = step.memory_analysis()
            # a shard's whole score space as ONE f32 array: the padded
            # concatenation, or (where no bucket is that wide) the
            # row-order matrix and its transpose
            whole = [f"f32[{B},{sum(rows) + 1}]"] + (
                [] if doc_cap in rows
                else [f"f32[{B},{doc_cap}]", f"f32[{doc_cap},{B}]"])
            out.append({"cell": name, "B": B,
                        "row_order_shapes": [x for x in whole if x in text],
                        "block_copies": block_copies(
                            text, list(zip(rows, widths)), B),
                        "digest": program_digest(step),
                        "kernels": sorted(set(re.findall(
                            r"ell_score_v4_w(\d+)", text)), key=int),
                        "temp_bytes": m.temp_size_in_bytes,
                        "argument_bytes": m.argument_size_in_bytes,
                        "output_bytes": m.output_size_in_bytes})
    return out


# The benchmark cells' committed snapshots: (rows_cap, width) of every
# ELL block and the document capacity, computed from each
# configuration's corpus (benchmarks/configs/*.json through
# benchmarks/lib/data.make_corpus, the ladder and next_capacity: live
# rows 3310 / 1047691 / 870316 / 78448 / 233 / 2 and 1 / 347971 /
# 631529 / 20498 / 1), with the batch buckets the cells dispatch.
CELL_STEPS = {
    "msmarco2m": (((4096, 64), (1048576, 48), (1048576, 32),
                   (131072, 24), (256, 16), (256, 12)),
                  1 << 21, (128, 256, 512)),
    "wiki1m": (((256, 128), (524288, 96), (1048576, 64), (32768, 48),
                (256, 32)), 1 << 20, (512,)),
    # whole documents: 57,590 live rows at width 512, 342,410 at 384
    # (the configuration file's ``layout.blocks``, which
    # tests/test_mesh_block_capacities.py holds to the generator)
    "msmarco-doc": (((65536, 512), (524288, 384)), 1 << 19, (512,)),
}


def block_copies(text: str, blocks, B: int) -> list[str]:
    """The ``copy`` / ``transpose`` instructions of a compiled program
    (fused ones included) whose result is as large as one of
    ``blocks``, ``(rows_cap, width)`` pairs, whichever way it lies and
    whatever axes of 1 lead it: ``s32[524288,384]``, ``f32[1,512,65536]``.
    A step that held a block ``[rows, width]`` turned it for the kernel
    in such copies from 128 wide on, every posting of the block a call
    (PR 43). An f32 ``[B, rows_cap]`` is left out: that is a block of
    SCORES (at B = 512 as large as the 512-wide block of impacts),
    which ``row_order_shapes`` and the temporaries hold; a turned block
    is found by its terms, which are turned with it. Returned as the
    text has them, dtype and shape."""
    sizes = {tuple(sorted(b)) for b in blocks}
    scores = {(B, rows) for rows, _w in blocks}
    found = set()
    for dtype, dims in re.findall(
            r"= ([fs]32)\[([\d,]+)\]\S* (?:copy|transpose)\(", text):
        shape = tuple(int(d) for d in dims.split(",") if d != "1")
        if tuple(sorted(shape)) in sizes \
                and not (dtype == "f32" and shape in scores):
            found.add(f"{dtype}[{dims}]")
    return sorted(found)


def program_digest(program) -> str:
    """A compiled program's HLO text less what moves when a source line
    does: the tables of files, functions and stack frames, the
    ``stack_frame_id`` of every instruction, and a Pallas call's
    ``backend_config`` (the serialized Mosaic module carries its own
    source locations; the kernel's body is held by the parity tests).
    What is left is the program: instructions, shapes, layouts, fusion
    and schedule."""
    head, _, rest = program.as_text().partition("\n\nFileNames\n")
    rest = "\n\n".join(
        part for part in rest.split("\n\n") if not re.match(
            r"\d+ |FunctionNames|FileLocations|StackFrames", part))
    text = re.sub(r"stack_frame_id=\d+|backend_config=.*$", "",
                  head + "\n\n" + rest, flags=re.M)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def compile_step_pair(dev, blocks, doc_cap: int, B: int,
                      stretched: bool = False, *, u_cap: int = 1024,
                      T: int = 32):
    """``(score, topk)``: the two programs of a step over ``blocks``
    compiled for ``dev`` — the whole block list of a snapshot, or with
    ``stretched`` one stretch of it (the top-k then takes its base
    row). ``u_cap`` and ``T`` are the query batch's: the unique-term
    capacity the warm-up pins and ``max_query_terms``, 1,024 and 32 in
    every cell but ``msmarco2m-q2d``."""
    from tfidf_tpu.ops.scoring import QueryBatch
    from tfidf_tpu.ops.topk import packed_topk_chunked
    sh = SingleDeviceSharding(dev)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    f32, i32 = jnp.float32, jnp.int32
    q = QueryBatch(uniq=s((u_cap,), i32), n_uniq=s((), i32),
                   slots=s((B, T), i32), weights=s((B, T), f32))
    live = s((len(blocks),), i32)
    # ``blocks`` are (rows_cap, width); the index holds a block
    # width-major, [width, rows_cap]
    held = [(w, r) for r, w in blocks]
    score = ell._score_ell_batch_jit.lower(
        tuple(s(b, f32) for b in held), tuple(s(b, i32) for b in held),
        live, None, None, None, s((doc_cap,), f32), s((1 << 19,), f32), q,
        s((), f32), s((), f32), s((doc_cap,), f32),
        model="bm25", use_pallas=True).compile()
    topk = packed_topk_chunked.lower(
        tuple(s((B, rows), f32) for rows, _ in blocks), live,
        *((s((), i32),) if stretched else ()), k=10).compile()
    return score, topk


def compile_cell_step(dev, blocks, doc_cap: int, B: int) -> dict:
    """The served device step at a cell's real shapes: the scoring
    program, then the top-k over the blocks it returns. Neither may
    hold a ``[B, padded rows + 1]`` or ``[B, doc_cap]`` f32 array — the
    score space exists once, where the kernel wrote it. Returns the two
    programs' digests (:func:`program_digest`), the score program's
    temporaries and the block-sized copies in it
    (:func:`block_copies`)."""
    score, topk = compile_step_pair(dev, blocks, doc_cap, B)
    assert score.as_text().count("tpu_custom_call") >= min(len(blocks), 4)
    rows = [r for r, _ in blocks]
    gone = [f"f32[{B},{sum(rows) + 1}]", f"f32[{doc_cap},{B}]"]
    if doc_cap not in rows:     # wiki1m: a block is as wide as doc_cap
        gone.append(f"f32[{B},{doc_cap}]")
    for program in (score, topk):
        text = program.as_text()
        for shape in gone:
            assert shape not in text, f"{shape} is back in the step"
    return {"score": program_digest(score), "topk": program_digest(topk),
            "temp_bytes": score.memory_analysis().temp_size_in_bytes,
            "block_copies": block_copies(score.as_text(), blocks, B)}


# what a v5e chip reports as memory_stats()["bytes_limit"] (my chip run,
# PR 32), and the stretches a served worker keeps in flight
# (search_pipeline_depth 2, + 1)
V5E_BYTES_LIMIT = 16_909_336_064
IN_FLIGHT = 3


def compile_full_stretches(dev, B: int = 512) -> list[dict]:
    """``msmarco-full``'s step at bucket ``B``: the stretches its
    eleven blocks (the configuration's ``layout.blocks``) are taken in
    under the budget a v5e worker derives, and for each DISTINCT stretch
    shape the two programs compiled, with ``memory_analysis()``: a
    stretch's outputs and temporaries have to fit the budget the plan
    was made under."""
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "msmarco-full.json")) as f:
        layout = json.load(f)["layout"]["blocks"]
    blocks = list(zip(layout["rows"], layout["widths"]))
    doc_cap = layout["doc_cap"]
    index = sum(8 * r * w for r, w in blocks) + 4 * doc_cap + 4 * (1 << 19)
    budget = ell.stretch_budget(V5E_BYTES_LIMIT, index, IN_FLIGHT)
    plan = ell.plan_stretches([r for r, _w in blocks], B, budget)
    out, seen = [], {}
    for first, stop in plan:
        shape = tuple(blocks[first:stop])
        if shape not in seen:
            score, topk = compile_step_pair(dev, shape, doc_cap, B,
                                            stretched=True)
            assert score.as_text().count("tpu_custom_call") \
                == sum(ell._pallas_eligible(r, B, 1024) for r, _w in shape)
            ms, mt = score.memory_analysis(), topk.memory_analysis()
            seen[shape] = {
                "score_output_bytes": ms.output_size_in_bytes,
                "score_temp_bytes": ms.temp_size_in_bytes,
                "topk_temp_bytes": mt.temp_size_in_bytes,
                "topk_output_bytes": mt.output_size_in_bytes}
        out.append({"blocks": [first, stop], "B": B, "budget": budget,
                    "index_bytes": index, **seen[shape]})
    return out


def compile_deep_topk(dev, k: int = 1000, B: int = 512) -> dict:
    """``msmarco2m-top1000``'s top-k program: ``packed_topk_chunked`` at
    the run file's depth over ``msmarco2m``'s six blocks. What the v5e
    compiler made of it: the width of every row it sorts, whether a
    shape of the straight route's two sorts a chunk (``[65536, 1024]``
    rows cut to 1,000, then ``[512, 128000]``: PERF.md section 3) or a
    copy of the score space is in its text, and its temporaries."""
    from tfidf_tpu.ops.topk import packed_topk_chunked
    sh = SingleDeviceSharding(dev)
    blocks, doc_cap, _batches = CELL_STEPS["msmarco2m"]
    rows = [r for r, _w in blocks]
    program = packed_topk_chunked.lower(
        tuple(jax.ShapeDtypeStruct((B, r), jnp.float32, sharding=sh)
              for r in rows),
        jax.ShapeDtypeStruct((len(rows),), jnp.int32, sharding=sh),
        k=k).compile()
    text = program.as_text()
    sorts = re.findall(r"^\s*(?:ROOT )?%\S+ = \(?[a-z]+\d+\[(\d+),(\d+)\]"
                       r"[^=]* sort\(", text, flags=re.M)
    back = [shape for shape in (
        f"[{B},{128 * k}]", f"[{B * 128},1024]", f"[{B * 128},{k}]",
        f"f32[{B},{sum(rows) + 1}]", f"f32[{B},{doc_cap}]")
        if shape in text]
    return {"k": k, "B": B, "sorts": len(sorts), "back": back,
            "widest_sort": max(int(n) for _b, n in sorts),
            "whiles": len(re.findall(r" while\(", text)),
            "temp_bytes": program.memory_analysis().temp_size_in_bytes}


def compile_long_query_step(dev, B: int = 512, u_cap: int = 16384,
                            T: int = 128) -> dict:
    """``msmarco2m-q2d``'s step: ``msmarco2m``'s six blocks at B = 512
    under a query batch of ``u_cap`` 16,384 and ``T`` 128 (expanded
    queries: ~9,750 distinct terms a call). The v5e compiler has to
    accept the kernel on a grid of 32 uniq tiles a doc tile, and the
    ``[B, u_cap + 1]`` query matrix and its chunk-major copy have to
    fit beside the 4.57 GB score space. Returns the score program's
    ``memory_analysis()``, its kernels and the shapes the query matrix
    takes in its text."""
    blocks, doc_cap, _batches = CELL_STEPS["msmarco2m"]
    score, _topk = compile_step_pair(dev, blocks, doc_cap, B,
                                     u_cap=u_cap, T=T)
    text = score.as_text()
    m = score.memory_analysis()
    return {"B": B, "u_cap": u_cap, "T": T,
            "kernels": text.count("tpu_custom_call"),
            "query_matrix_shapes": [
                shape for shape in (f"f32[{B},{u_cap + 1}]",
                                    f"f32[{u_cap // 128},{B},128]")
                if shape in text],
            "temp_bytes": m.temp_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "argument_bytes": m.argument_size_in_bytes}


def main() -> int:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    failures: list[str] = []
    compiled = 0
    for B in BATCHES:
        for rows, u_cap in block_shapes(B):
            for width in WIDTHS:
                what = f"rows={rows} width={width} B={B} u_cap={u_cap}"
                try:
                    compile_block(topo.devices[0], rows, width, B, u_cap)
                    compiled += 1
                except Exception as e:   # reported, all of them
                    failures.append(
                        f"{what}: {type(e).__name__}: {str(e)[:300]}")
    mesh_cells: list[dict] = []
    try:
        mesh_cells = compile_mesh_step(topo.devices)
        compiled += 1
    except Exception as e:
        failures.append(f"mesh (4,1) step: {type(e).__name__}: "
                        f"{str(e)[:600]}")
    cells = 0
    digests: dict[str, dict] = {}
    for name, (blocks, doc_cap, batches) in CELL_STEPS.items():
        for B in batches:
            try:
                digests[f"{name}/{B}"] = compile_cell_step(
                    topo.devices[0], blocks, doc_cap, B)
                cells += 1
            except Exception as e:
                failures.append(f"cell {name} B={B}: {type(e).__name__}: "
                                f"{str(e)[:600]}")
    stretches: list[dict] = []
    try:
        stretches = compile_full_stretches(topo.devices[0])
    except Exception as e:
        failures.append(f"cell msmarco-full stretches: "
                        f"{type(e).__name__}: {str(e)[:600]}")
    deep: dict = {}
    try:
        deep = compile_deep_topk(topo.devices[0])
    except Exception as e:
        failures.append(f"cell msmarco2m-top1000 top-k: "
                        f"{type(e).__name__}: {str(e)[:600]}")
    long_query: dict = {}
    try:
        long_query = compile_long_query_step(topo.devices[0])
    except Exception as e:
        failures.append(f"cell msmarco2m-q2d step: "
                        f"{type(e).__name__}: {str(e)[:600]}")
    print(json.dumps({"failures": failures, "compiled": compiled,
                      "cells": cells, "mesh_cells": mesh_cells,
                      "cell_digests": digests, "stretches": stretches,
                      "deep_topk": deep, "long_query": long_query}))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
