"""Back-to-back streaming builds of one slice through the CLI
(`tpu-ir index --streaming`), then the built index checked.

Set-up writes the slice's TREC text and makes one build of it, loads
that index and answers the topics (which compiles every shape the
window and the check use). The window then rebuilds the same slice,
each build into a fresh directory, until the window's seconds are
spent; build_docs_per_s is the documents of the completed builds over
their summed wall time. After the window the last index is loaded, its
vocabulary, df and postings compared exactly with the generator's, and
the topics answered from it compared with the float64 reference.
"""

from __future__ import annotations

import contextlib
import gc
import io
import json
import os
import shutil
import time

import numpy as np

from .. import reference
from ..harness import log
from ..trec import Slice


def build(run, corpus: str, index_dir: str) -> tuple[float, dict]:
    from tpu_ir import cli
    from tpu_ir.index import format as fmt

    argv = ["index", corpus, index_dir, "--streaming", "--batch-docs",
            str(run.traffic["batch_docs"])]
    t0 = time.perf_counter()
    with run.span("cli.index"), contextlib.redirect_stdout(io.StringIO()):
        rc = cli.main(argv)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise RuntimeError(f"tpu-ir {' '.join(argv)} exited {rc}")
    with open(os.path.join(index_dir, fmt.JOBS_DIR,
                           "TermKGramDocIndexer.json")) as f:
        return wall, json.load(f)


def answer_topics(run, index_dir, texts):
    from tpu_ir.search import Scorer

    scorer = Scorer.load(index_dir)
    res = scorer.search_batch(texts, k=run.traffic["k"],
                              scoring=run.traffic["scoring"],
                              return_docids=False)
    return scorer, res


def run(run) -> None:
    t = run.traffic
    with run.span("generate"):
        sl = Slice(run.config, run.args.seed)
        corpus = os.path.join(run.tmp, "corpus.trec")
        nbytes = sl.write_text(corpus)
    log(f"slice: {len(sl.docids)} docs, {nbytes} bytes, "
        f"{len(sl.post.words)} terms, {len(sl.post.doc)} postings")
    texts, rows = sl.topics(int(t["topics"]), run.args.seed)
    with run.span("warmup"):
        warm = os.path.join(run.tmp, "warm")
        wall, rep = build(run, corpus, warm)
        log(f"warm-up build: {wall:.3f} s, timings "
            f"{json.dumps(rep['timings_s'], sort_keys=True)}")
        if rep["config"].get("tokenizer") != "native":
            raise RuntimeError("the build did not use the native analyzer")
        scorer, _ = answer_topics(run, warm, texts)
        del scorer
        shutil.rmtree(warm)
    walls, reports, last = [], [], None
    with run.window():
        t0 = time.perf_counter()
        j = 0
        while time.perf_counter() - t0 < run.args.seconds:
            out = os.path.join(run.tmp, f"index-{j}")
            wall, rep = build(run, corpus, out)
            walls.append(wall)
            reports.append(rep["timings_s"])
            if last is not None:
                shutil.rmtree(last)
            last, j = out, j + 1
    log("builds (s): " + json.dumps(walls))
    n = len(sl.docids)
    run.attempted, run.failed = len(walls), 0
    run.end_to_end["build_docs_per_s"] = n * len(walls) / sum(walls)
    run.data["counters"]["builds"] = {"walls": walls, "timings": reports}
    with run.span("check.load"):
        scorer, res = answer_topics(run, last, texts)
    run.read_memory_peak()
    got_df = np.asarray(scorer._df_host())
    got_doc, got_tf = (np.asarray(a) for a in scorer._pairs_doc_tf)
    got_terms = list(scorer.vocab.terms)
    answers = {i: list(r) for i, r in enumerate(res)}
    del scorer, res
    gc.collect()
    run.tally = check(run, sl, got_terms, got_df, got_doc, got_tf,
                      answers, rows)


def check(run, sl: Slice, got_terms, got_df, got_doc, got_tf, answers,
          rows) -> reference.Tally:
    """The built index against the generator's postings (exact), and the
    topics' answers against the float64 reference."""
    p = sl.post
    tally = reference.Tally()
    want_terms = sl.terms[p.words].tolist()
    tally.checked += 1
    if got_terms != want_terms:
        tally.fail(f"vocabulary: {len(got_terms)} terms, expected "
                   f"{len(want_terms)}")
    elif not np.array_equal(got_df, p.df):
        tally.fail(f"df differs for {int((got_df != p.df).sum())} terms")
    else:
        term = np.repeat(np.arange(len(p.df)), p.df)
        a = np.lexsort((got_doc, term))
        b = np.lexsort((p.doc, term))
        if not (np.array_equal(got_doc[a], p.doc[b])
                and np.array_equal(got_tf[a], p.tf[b])):
            tally.fail("postings differ from the generator's")
    bm = run.config["bm25"]
    ref = reference.Reference(p.df, p.doc, p.tf, p.num_docs, k1=bm["k1"],
                              b=bm["b"])
    for qi, got in answers.items():
        reference.check_topk(ref.scores(rows[qi], run.traffic["scoring"]),
                             got, run.traffic["k"], tally, f"topic {qi}")
    log(f"reference: {tally.checked} checks, {tally.wrong} wrong, max rel "
        f"score error {tally.max_rel:.3e}")
    for msg in tally.first:
        log(f"  wrong: {msg}")
    return tally
