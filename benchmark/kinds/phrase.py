"""Closed loop of quoted-phrase query blocks through Scorer.search_batch
over a positional index.

Set-up generates the archive (benchmark/archive.py), writes it with its
positions, loads it as a replica would, and answers the pool's first
query alone: the program's lifetime counter `phrase.positions` has to
grow by it, or the run stops there with status 1 (a program that
answers phrases with the device idle has nothing to measure in this
cell). Then every block of the pool is answered once, which compiles
every shape the window uses, and the window answers the blocks back to
back, cycling over the pool. queries_per_s is the queries answered
without degradation over the window's wall time; 200 answers of the
window are compared with the float64 phrase reference
(benchmark/phrase_reference.py).
"""

from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

from .. import program, words
from ..archive import Archive, PhraseQueries
from ..harness import log
from ..phrase_reference import PhraseReference, check_phrase
from ..reference import Tally


def phrase_positions() -> int | None:
    """The program's lifetime count of anchor positions its device
    phrase path streamed, or None where it has no such counter."""
    try:
        from tpu_ir.obs import get_registry
    except ImportError:
        return None
    return get_registry().counters().get("phrase.positions")


def guard(answer_one) -> None:
    """Answer one quoted query; exit with status 1 unless the program's
    device phrase path streamed positions for it."""
    before = phrase_positions()
    answer_one()
    after = phrase_positions()
    if before is None or after is None or after <= before:
        print("benchmark: the program answered a phrase query without "
              "its device phrase path (counter phrase.positions "
              f"{before!r} -> {after!r})", file=sys.stderr, flush=True)
        raise SystemExit(1)


def load_archive(run):
    """Generate and write the archive and load it: (archive, scorer,
    load seconds without the backend compiles inside the load)."""
    import jax

    from tpu_ir.search import Scorer

    t0 = time.perf_counter()
    with run.span("generate"):
        arch = Archive(run.config, run.args.seed)
    index_dir = run.tmp + "/index"
    with run.span("write_index"):
        stats = arch.write_index(index_dir)
    log(f"archive: {stats} generated and written in "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    with run.span("sync"):
        flush(index_dir)
    log(f"sync: {time.perf_counter() - t0:.3f} s")
    c0 = run.meter.seconds
    t0 = time.perf_counter()
    with run.span("load"):
        scorer = Scorer.load(index_dir)
        jax.block_until_ready([a for a in vars(scorer).values()
                               if isinstance(a, jax.Array)]
                              + list(getattr(scorer, "tier_docs", ()))
                              + list(getattr(scorer, "tier_tfs", ()))
                              + list(getattr(scorer, "positions", None)
                                     or ()))
    wall = time.perf_counter() - t0
    compile_s = run.meter.seconds - c0
    log(f"load: {wall - compile_s:.6f} s without compiles ({wall:.6f} s "
        f"wall, {compile_s:.3f} s of backend compiles), layout "
        f"{scorer.layout}")
    log(f"load stages (s): {load_stages()}")
    return arch, scorer, wall - compile_s


def flush(index_dir: str) -> None:
    """Write the index's files through to the disk before the timed
    load, as a replica's index written earlier would be: the load then
    does not race the write-back of the index just written."""
    for root, _, files in os.walk(index_dir):
        for name in files:
            fd = os.open(os.path.join(root, name), os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)


def load_stages() -> dict:
    """Seconds of each stage of the process's Scorer.load, as the
    program's registry summed them (absent stages left out)."""
    out = {}
    for name in ("load", "load.read", "load.assemble", "load.layout",
                 "load.cache_write", "load.h2d", "load.positions"):
        sec = program.lifetime_s(name)
        if sec is not None:
            out[name] = round(sec, 3)
    return out


def run(run) -> None:
    t = run.traffic
    arch, scorer, load_s = load_archive(run)
    run.end_to_end["load_s"] = load_s
    n_block, n_pool = int(t["block_queries"]), int(t["pool_blocks"])
    t0 = time.perf_counter()
    qs = PhraseQueries(arch, t, n_block * n_pool, run.args.seed)
    qs.shuffle(words.rng(run.args.seed, 9), n_block)
    log(f"queries: {len(qs.texts)} drawn in {time.perf_counter() - t0:.3f}"
        f" s, e.g. {qs.texts[:3]}")
    blocks = [qs.texts[i * n_block:(i + 1) * n_block]
              for i in range(n_pool)]
    sample = set(words.rng(run.args.seed, 6).choice(
        len(qs.texts), min(int(t["check_sample"]), len(qs.texts)),
        replace=False).tolist())

    def answer(texts):
        with run.span("search_batch"):
            return scorer.search_batch(texts, k=t["k"],
                                       scoring=t["scoring"],
                                       phrase_slop=t["slop"],
                                       return_docids=False)

    with run.span("guard"):
        guard(lambda: answer(blocks[0][:1]))
    with run.span("warmup"):
        for b in blocks:
            answer(b)
    answers, done, failed, i, walls = {}, 0, 0, 0, []
    with run.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.args.seconds:
            tb = time.perf_counter()
            res = answer(blocks[i % n_pool])
            walls.append(time.perf_counter() - tb)
            base = (i % n_pool) * n_block
            for j, r in enumerate(res):
                if r.degraded:
                    failed += 1
                if base + j in sample:
                    answers[base + j] = list(r)
            done += len(res)
            i += 1
        wall = time.perf_counter() - t0
    log(f"blocks: {len(walls)}, s each: min {min(walls):.3f} median "
        f"{np.median(walls):.3f} max {max(walls):.3f} (block "
        f"{int(np.argmax(walls))})")
    run.attempted, run.failed = done, failed
    run.end_to_end["queries_per_s"] = (done - failed) / wall
    run.data["counters"]["queries"] = done
    run.data["work"]["phrase_bytes"] = phrase_bytes(arch, qs, t["k"],
                                                    n_block, i)
    # the peak is read, and the program's state dropped, before the
    # reference runs
    run.read_memory_peak()
    del answer, scorer
    gc.collect()
    run.tally = check(run, arch, qs, answers)


def phrase_bytes(arch: Archive, qs: PhraseQueries, k: int, n_block: int,
                 blocks_done: int) -> float:
    """Bytes the window's queries require: for each query, 8 bytes (a
    docno and a position) per position of each distinct quoted term, 8
    bytes (a docno and a tf) per posting of each distinct query term,
    and 8 bytes (a docno and a score) per answer slot."""
    cf = np.bincount(arch.tokens, minlength=len(arch.words))
    df = arch.df
    per_query = np.array([
        8.0 * cf[np.unique(s)].sum()
        + 8.0 * df[np.unique(np.concatenate([s, p]))].sum() + 8.0 * k
        for s, p in zip(qs.spans, qs.plain)])
    n_pool = len(per_query) // n_block
    per_block = per_query.reshape(n_pool, n_block).sum(axis=1)
    full, rest = divmod(blocks_done, n_pool)
    return float(full * per_block.sum() + per_block[:rest].sum())


def check(run, arch: Archive, qs: PhraseQueries, answers: dict) -> Tally:
    """Compare sampled answers {query index: [(docno, score)]} with the
    float64 phrase reference over the generator's own tokens."""
    t = run.traffic
    bm = run.config["bm25"]
    ref = PhraseReference(arch.tokens, arch.lengths, k1=bm["k1"],
                          b=bm["b"])
    tally = Tally()
    t0 = time.perf_counter()
    for qi, got in sorted(answers.items()):
        docs, scores = ref.answer(
            [qs.spans[qi]], np.concatenate([qs.spans[qi], qs.plain[qi]]),
            t["scoring"], t["slop"])
        check_phrase(docs, scores, got, t["k"], tally, f"q{qi}")
    log(f"reference: {tally.checked} answers checked in "
        f"{time.perf_counter() - t0:.3f} s, {tally.wrong} wrong, max rel "
        f"score error {tally.max_rel:.3e}")
    for msg in tally.first:
        log(f"  wrong: {msg}")
    return tally
