"""Set-up shared by the serving cells: the generated shard written as an
index, loaded through Scorer.load, and the check of sampled answers."""

from __future__ import annotations

import os
import time

import numpy as np

from . import reference
from .harness import log
from .msmarco import Shard


def load_shard_index(run, seed: int):
    """Generate the shard, write it, and load it as a replica would:
    (shard, scorer, load seconds until its arrays are resident). The
    load seconds leave out the backend compiles (and compile-cache
    reads) inside the load, so that a run on a cold compile cache reads
    as a warm one: a replica's start with its programs compiled."""
    import jax

    from tpu_ir.search import Scorer

    t0 = time.perf_counter()
    with run.span("generate"):
        shard = Shard(run.config, seed)
    index_dir = os.path.join(run.tmp, "index")
    with run.span("write_index"):
        shard.write_index(index_dir)
    log(f"shard: {shard.stats()} generated and written in "
        f"{time.perf_counter() - t0:.3f} s")
    c0 = run.meter.seconds
    t0 = time.perf_counter()
    with run.span("load"):
        scorer = Scorer.load(index_dir)
        jax.block_until_ready([a for a in vars(scorer).values()
                               if isinstance(a, jax.Array)]
                              + list(getattr(scorer, "tier_docs", ()))
                              + list(getattr(scorer, "tier_tfs", ())))
    wall = time.perf_counter() - t0
    compile_s = run.meter.seconds - c0
    log(f"load: {wall - compile_s:.6f} s without compiles ({wall:.6f} s "
        f"wall, {compile_s:.3f} s of backend compiles), layout "
        f"{scorer.layout}")
    return shard, scorer, wall - compile_s


def check_sample(run, shard, answers: dict, rows: np.ndarray, k: int,
                 scoring: str) -> reference.Tally:
    """Compare sampled answers {query index: [(docno, score)]} with the
    float64 reference over the generator's own postings."""
    p = shard.post
    bm = run.config["bm25"]
    ref = reference.Reference(p.df, p.doc, p.tf, p.num_docs,
                              k1=bm["k1"], b=bm["b"])
    tally = reference.Tally()
    t0 = time.perf_counter()
    for qi, got in sorted(answers.items()):
        reference.check_topk(ref.scores(rows[qi], scoring), got, k, tally,
                             f"q{qi}")
    log(f"reference: {tally.checked} answers checked in "
        f"{time.perf_counter() - t0:.3f} s, {tally.wrong} wrong, max rel "
        f"score error {tally.max_rel:.3e}")
    for msg in tally.first:
        log(f"  wrong: {msg}")
    return tally
