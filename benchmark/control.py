"""The lower-precision control, at a cell's own size: the reference put
in the program's place with bfloat16 weights (benchmark/reference.py
Control), answering the queries a run of the cell samples, judged by
the same comparison. It has to come out not correct; its readings set
the upper end of each limit (PERF.md). Not run by the benchmark's runs.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_tally(workload: str, seed: int, overrides=None):
    from benchmark import harness, reference, words
    from benchmark.msmarco import Queries, Shard
    from benchmark.trec import Slice

    _, _, config, traffic = harness.find_cell(workload)
    for key, val in (overrides or {}).items():
        (traffic if key in traffic else config)[key] = val
    k, scoring = int(traffic["k"]), traffic["scoring"]
    if traffic["kind"] == "build":
        sl = Slice(config, seed)
        post = sl.post
        _, rows = sl.topics(int(traffic["topics"]), seed)
        picks = range(len(rows))
    else:
        shard = Shard(config, seed)
        post = shard.post
        n = int(traffic["block_queries"]) * int(traffic["pool_blocks"])
        qs = Queries(shard, n, seed, 2)
        qs.shuffle(words.rng(seed, 9), int(traffic["block_queries"]))
        rows = qs.rows
        picks = words.rng(seed, 6).choice(
            n, min(int(traffic["check_sample"]), n), replace=False)
    bm = config["bm25"]
    ref = reference.Reference(post.df, post.doc, post.tf, post.num_docs,
                              k1=bm["k1"], b=bm["b"])
    ctl = reference.Control(ref)
    tally = reference.Tally()
    for qi in picks:
        reference.check_topk(ref.scores(rows[qi], scoring),
                             ctl.topk(rows[qi], k, scoring), k, tally,
                             f"q{qi}")
    return tally


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    for seed in args.seeds:
        t = control_tally(args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": t.correct(), "checked": t.checked,
                          "checks": t.checks()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
