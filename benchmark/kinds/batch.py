"""Closed loop of query blocks through Scorer.search_batch.

A fixed pool of query blocks, drawn from the seed, is answered once in
set-up (which compiles every shape the window uses) and then back to
back, cycling over the pool, for the window. queries_per_s is the
queries answered without degradation over the window's wall time.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from .. import serving, words
from ..msmarco import Queries


def run(run) -> None:
    t = run.traffic
    shard, scorer, load_s = serving.load_shard_index(run, run.args.seed)
    run.end_to_end["load_s"] = load_s
    n_block, n_pool = int(t["block_queries"]), int(t["pool_blocks"])
    # the same blocks for every seed (the same shapes to compile); the
    # seed orders the queries inside each block
    qs = Queries(shard, n_block * n_pool, run.args.seed, 2)
    qs.shuffle(words.rng(run.args.seed, 9), n_block)
    blocks = [qs.texts[i * n_block:(i + 1) * n_block]
              for i in range(n_pool)]
    sample = set(words.rng(run.args.seed, 6).choice(
        len(qs.texts), min(int(t["check_sample"]), len(qs.texts)),
        replace=False).tolist())

    def answer(i):
        with run.span("search_batch"):
            return scorer.search_batch(blocks[i], k=t["k"],
                                       scoring=t["scoring"],
                                       return_docids=False)

    with run.span("warmup"):
        for i in range(n_pool):
            answer(i)
    answers, done, failed, i = {}, 0, 0, 0
    with run.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.args.seconds:
            res = answer(i % n_pool)
            base = (i % n_pool) * n_block
            for j, r in enumerate(res):
                if r.degraded:
                    failed += 1
                if base + j in sample:
                    answers[base + j] = list(r)
            done += len(res)
            i += 1
        wall = time.perf_counter() - t0
    run.attempted, run.failed = done, failed
    run.end_to_end["queries_per_s"] = (done - failed) / wall
    run.data["counters"]["queries"] = done
    run.data["work"]["query_bytes"] = query_bytes(
        shard.post.df, qs.rows, t["k"], n_block, i)
    # the peak is read, and the program's state dropped, before the
    # reference runs
    run.read_memory_peak()
    del answer, scorer
    gc.collect()
    run.tally = serving.check_sample(run, shard, answers, qs.rows, t["k"],
                                     t["scoring"])


def query_bytes(df, rows, k: int, n_block: int, blocks_done: int) -> float:
    """Bytes the window's queries require: for each query, 8 bytes (a
    docno and a tf) per posting of each distinct term, and 8 bytes (a
    docno and a score) per answer slot."""
    per_query = np.array([8.0 * df[np.unique(r[r >= 0])].sum() + 8.0 * k
                          for r in rows])
    n_pool = len(rows) // n_block
    per_block = per_query.reshape(n_pool, n_block).sum(axis=1)
    full, rest = divmod(blocks_done, n_pool)
    return float(full * per_block.sum() + per_block[:rest].sum())
