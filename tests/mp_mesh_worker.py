"""Subprocess body for the REAL multi-process ``jax.distributed`` test.

Each OS process owns 2 virtual CPU devices; ``jax.distributed.initialize``
joins them into one global device view, and the mesh engine runs ingest +
commit + search over a mesh that SPANS the process boundary — the psum of
document frequencies and the top-k all_gather cross processes over the
gloo collective backend, which is exactly the SPMD shape a DCN-connected
TPU pod runs (SURVEY.md §5.8). Every process executes the identical
program on identical inputs and must get the identical (and
local-engine-equivalent) results.

Invoked by tests/test_multihost.py; not a test
module itself.
"""

from __future__ import annotations

import os
import sys

TEXTS = {
    "a.txt": "the quick brown fox jumps over the lazy dog",
    "b.txt": "a fast brown fox and a quick red fox",
    "c.txt": "lorem ipsum dolor sit amet",
    "d.txt": "the dog sleeps all day long",
    "e.txt": "red dogs chase brown foxes at dawn",
    "f.txt": "ipsum lorem amet dolor",
    "g.txt": "quick quick quick brown brown dog",
    "h.txt": "foxes and dogs and foxes again",
    "i.txt": "dawn chorus over the lazy meadow",
    "j.txt": "meadow fox naps in the red dawn",
}

QUERIES = ("fox", "brown dog", "lorem ipsum", "red dawn", "meadow",
           "nosuchterm")


def results(engine):
    return [sorted(((h.name, round(h.score, 4))
                    for h in engine.search(q)),
                   key=lambda nv: (-nv[1], nv[0])) for q in QUERIES]


def main() -> None:
    coord, n, pid, tmp = (sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
                          sys.argv[4])
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax

    from tfidf_tpu.parallel.mesh import initialize_multihost, make_mesh
    assert initialize_multihost(coord, num_processes=n, process_id=pid)
    assert jax.process_count() == n, jax.process_count()
    assert jax.process_index() == pid
    n_dev = len(jax.devices())
    assert n_dev == 2 * n, (n_dev, n)
    assert len(jax.local_devices()) == 2

    from tfidf_tpu.engine.engine import Engine
    from tfidf_tpu.utils.config import Config

    def cfg(sub: str, mode: str, layout: str = "coo") -> Config:
        return Config(documents_path=os.path.join(tmp, f"{sub}{pid}"),
                      engine_mode=mode, mesh_layout=layout,
                      min_doc_capacity=8, min_nnz_capacity=256,
                      min_vocab_capacity=64, query_batch=4,
                      max_query_terms=8)

    local = Engine(cfg("l", "local"))
    # COO layout, all devices on the docs axis (spans both processes)
    mesh_coo = Engine(cfg("mc", "mesh", "coo"),
                      mesh=make_mesh((n_dev, 1)))
    # ELL layout on a (docs, terms) grid: the docs axis crosses the
    # process boundary, terms stays intra-process — the DCN/ICI split
    mesh_ell = Engine(cfg("me", "mesh", "ell"),
                      mesh=make_mesh((n_dev // 2, 2)))
    for e in (local, mesh_coo, mesh_ell):
        for name, text in TEXTS.items():
            e.ingest_text(name, text)
        e.commit()
    want = results(local)
    for label, e in (("coo", mesh_coo), ("ell", mesh_ell)):
        got = results(e)
        assert got == want, (label, got, want)
    # incremental path: append after the first commit, cross-process df
    # must update (psum) and the new doc must be searchable everywhere
    for label, e in (("coo", mesh_coo), ("ell", mesh_ell),
                     ("local", local)):
        e.ingest_text("k.txt", "zebra fox dawn")
        e.commit()
    want2 = results(local)
    for label, e in (("coo", mesh_coo), ("ell", mesh_ell)):
        got2 = results(e)
        assert got2 == want2, (label, got2, want2)
    print(f"MP_MESH_OK pid={pid} procs={jax.process_count()} "
          f"devices={n_dev}", flush=True)


if __name__ == "__main__":
    main()
