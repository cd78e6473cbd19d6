"""The control's readings at a cell's OWN size (by hand, not a test).

    python benchmarks/tests/control_readings.py <config> <seed> [<seed>...]

For each seed: the configuration's corpus and 64 of its queries from the
seed, the float64 reference, and the bfloat16 control put in the
program's place; prints the numbers ``oracle.compare`` reads for it. The
limit in ``oracle.py`` sits below the smallest of these and above the
largest the sound program shows (PERF.md section 2). numpy only.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "lib"))

import data  # noqa: E402
import oracle  # noqa: E402


def main(argv):
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           argv[1] + ".json")) as f:
        cfg = json.load(f)
    for seed in (int(s) for s in argv[2:]):
        corpus = data.make_corpus(seed, **data.corpus_args(cfg))
        queries = data.make_queries(seed, 64, vocab=cfg["vocab"],
                                    query_terms=cfg["query_terms"])
        sc = cfg["scoring"]
        ref = oracle.Oracle(corpus, queries, k1=sc["k1"], b=sc["b"])
        ctl = oracle.Oracle(corpus, queries, k1=sc["k1"], b=sc["b"],
                            precision="bfloat16")
        v = oracle.compare(ref, {i: ctl.topk(i) for i in range(64)})
        print(json.dumps({"config": argv[1], "seed": seed,
                          "correct": v["correct"], **{
                              k: n["value"]
                              for k, n in v["numbers"].items()}}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv)
