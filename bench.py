"""Headline benchmark: index a reference-scale synthetic TREC corpus and
answer a 10k batched query load.

Reference baseline (BASELINE.md): the PA1 inverted-index build processed
8,761 TREC docs (23.9 MB) in 51 s on the course Hadoop cluster -> ~172 docs/s.
Query latency was never measured there (interactive REPL only), so docs/sec
indexed is the headline metric and batched queries/sec is reported alongside.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BASELINE_DOCS_PER_SEC = 8761 / 51.0  # reference PA1 job _0010

# word-shape pool: mixed lengths, zipf-ish usage like English text
VOCAB_SIZE = 30_000
DOC_COUNT = 8_761
TARGET_BYTES = 23_950_858


def make_corpus(path: str, seed: int = 0, *, n_docs: int | None = None,
                target_bytes: int | None = None,
                vocab_size: int | None = None) -> int:
    n_docs = DOC_COUNT if n_docs is None else n_docs
    target_bytes = TARGET_BYTES if target_bytes is None else target_bytes
    vocab_size = VOCAB_SIZE if vocab_size is None else vocab_size
    rng = np.random.default_rng(seed)
    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    lengths = rng.integers(3, 11, vocab_size)
    words = np.array(["".join(rng.choice(letters, l)) for l in lengths])
    zipf_p = 1.0 / np.arange(1, vocab_size + 1)
    zipf_p /= zipf_p.sum()

    avg_doc_words = target_bytes // n_docs // 8  # ~8 bytes/word incl space
    n_words_per_doc = rng.integers(avg_doc_words // 2,
                                   avg_doc_words * 3 // 2, n_docs)
    all_ids = rng.choice(vocab_size, int(n_words_per_doc.sum()), p=zipf_p)
    total = 0
    pos = 0
    with open(path, "w") as f:
        for i in range(n_docs):
            n = int(n_words_per_doc[i])
            body = " ".join(words[all_ids[pos : pos + n]])
            pos += n
            rec = (f"<DOC>\n<DOCNO> SYN-{i:06d} </DOCNO>\n<TEXT>\n{body}\n"
                   f"</TEXT>\n</DOC>\n")
            f.write(rec)
            total += len(rec)
    return total


def make_quality_corpus(path: str, n_docs: int, n_queries: int,
                        seed: int = 7, with_prox: bool = False):
    """Passage corpus with GRADED planted relevance that splits the scorers.

    Each query i is two entity terms unique to it, with a relevant passage
    (grade 2) and distractors (grade 1) built so the three scorers come
    apart — the round-1 generator saturated at MRR 1.0 for everything and
    could not detect a regression. Query types cycle:

    - type 0 (verbose doc): relevant has both terms at tf 2 in a normal
      passage; a distractor has both at tf 3 buried in a ~460-word doc.
      TF-IDF has NO length normalization, so the verbose doc's higher tf
      wins; BM25's length norm and the cosine stage's doc norm both
      punish it. => splits TF-IDF below BM25 (and the rerank).
    - type 1 (norm tie): a distractor with the SAME query-term tfs and
      the SAME length as the relevant doc, but its padding is 40 distinct
      rare fillers where the relevant doc repeats one filler. TF-IDF and
      BM25 tie exactly (winner = lower docno, random); the cosine stage's
      doc-norm breaks the tie toward the lighter (relevant) vector.
      => splits BM25/TF-IDF below the rerank.
    - type 2 (legit stronger doc): a grade-1 distractor with both terms at
      tf 3 in a shorter doc beats the relevant doc under EVERY scorer,
      capping all metrics strictly below 1.
    - type 3 (idf canary): query = rare entity + a planted COMMON topic
      word (appears tf 1 in ~4% of the corpus). The relevant doc has the
      rare term once; distractors carry only the common word at higher tf.
      Only df-aware weighting ranks the relevant doc first — flatten idf
      and the common word drowns the query, collapsing TF-IDF and the
      rerank while BM25 (its own idf) stands, which breaks the gate's
      ordering. This is what makes a broken idf FAIL the bench.

    Returns (queries, rel_docnos, grades) — grades[qi] maps docno->grade
    for NDCG. Docids are zero-padded in generation order, so docno ==
    doc index + 1 after sorted numbering.

    `with_prox=True` additionally plants n_queries//4 PROX-TIE pairs and
    returns them as a fourth element (prox_queries, prox_rel_docnos):
    the relevant doc holds the two query entities ADJACENT, a distractor
    holds them separated by its filler run — same tfs, same length, same
    norm, so TF-IDF, BM25 and the cosine rerank all tie EXACTLY and the
    tie breaks by docno order, which is rigged toward the distractor.
    Only the positions-based proximity boost can rank the relevant doc
    first; the measured MRR lift on this subset is the bench's evidence
    that the proximity feature works (VERDICT r2 item 4).
    """
    rng = np.random.default_rng(seed)
    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    bg_vocab = 40_000
    lengths = rng.integers(4, 10, bg_vocab)
    bg_words = np.array(["".join(rng.choice(letters, l)) for l in lengths])
    zipf_p = 1.0 / np.arange(1, bg_vocab + 1)
    zipf_p /= zipf_p.sum()

    def entity(i, which):  # unique, analyzer-stable
        return f"xx{which}{i:05d}ent"

    COMMON = "qqcommontopic"  # planted into ~4% of unplanted docs below

    doc_words: dict[int, list[str]] = {}
    no_bg: set[int] = set()   # docs whose token lists must match exactly
    queries, rel_docnos, grades = [], [], []
    n_prox = max(n_queries // 4, 1) if with_prox else 0
    slots = rng.choice(n_docs, n_queries * 3 + n_prox * 2, replace=False)
    prox_queries: list[str] = []
    prox_rel: list[int] = []
    for pi in range(n_prox):
        a, b = (int(s) for s in slots[n_queries * 3 + 2 * pi:
                                      n_queries * 3 + 2 * pi + 2])
        dis, rel = min(a, b), max(a, b)  # tie breaks toward the distractor
        e1, e2 = entity(pi, "p"), entity(pi, "q")
        K = 30
        doc_words[rel] = [e1, e2] + [f"pp{pi:05d}r"] * K
        doc_words[dis] = [e1] + [f"pp{pi:05d}d"] * K + [e2]
        no_bg.update((rel, dis))
        prox_queries.append(f"{e1} {e2}")
        prox_rel.append(rel + 1)
    for qi in range(n_queries):
        e1, e2 = entity(qi, "a"), entity(qi, "b")
        rel, d1, d2 = (int(s) for s in slots[3 * qi : 3 * qi + 3])
        kind = qi % 4
        if kind == 0:    # verbose doc: 2*(1+ln 3) > 2*(1+ln 2), length ignored
            doc_words[rel] = [e1] * 2 + [e2] * 2
            doc_words[d1] = ([e1] * 3 + [e2] * 3
                             + list(bg_words[rng.integers(0, bg_vocab, 400)]))
            doc_words[d2] = [e2] * 1
        elif kind == 1:  # exact tie broken only by the cosine doc norm
            filler = f"zz{qi:05d}fil"
            doc_words[rel] = ([e1] * 2 + [e2] * 2 + [filler] * 40)
            doc_words[d1] = ([e1] * 2 + [e2] * 2
                             + [f"zz{qi:05d}d{j:02d}" for j in range(40)])
            doc_words[d2] = [e1] * 1  # weak single-term doc
            no_bg.update((rel, d1))
        elif kind == 2:  # legitimately stronger grade-1 distractor
            doc_words[rel] = [e1] * 2 + [e2] * 2
            doc_words[d1] = [e1] * 3 + [e2] * 3
            doc_words[d2] = [e2] * 1
            no_bg.add(d1)
        else:            # idf canary: rare entity vs planted common word
            doc_words[rel] = [e1] * 1
            doc_words[d1] = [COMMON] * 3
            doc_words[d2] = [COMMON] * 2
            queries.append(f"{e1} {COMMON}")
            rel_docnos.append(rel + 1)
            grades.append({rel + 1: 2, d1 + 1: 1, d2 + 1: 1})
            continue
        queries.append(f"{e1} {e2}")
        rel_docnos.append(rel + 1)
        grades.append({rel + 1: 2, d1 + 1: 1, d2 + 1: 1})

    # one vectorized zipf draw for every document's background words
    # (per-doc rng.choice with a 40k-entry p vector is seconds of waste)
    n_bg_per_doc = rng.integers(40, 80, n_docs)
    all_bg = rng.choice(bg_vocab, int(n_bg_per_doc.sum()), p=zipf_p)
    offsets = np.concatenate([[0], np.cumsum(n_bg_per_doc)])
    with open(path, "w") as f:
        for i in range(n_docs):
            planted = doc_words.get(i)
            if i in no_bg:
                words = list(planted)
            else:
                words = list(bg_words[all_bg[offsets[i] : offsets[i + 1]]])
                if planted:
                    pos = rng.integers(0, len(words) + 1, len(planted))
                    for p, w in zip(sorted(pos, reverse=True), planted):
                        words.insert(int(p), w)
                elif i % 25 == 7:  # make COMMON genuinely common (df ~ 4%)
                    words.append(COMMON)
            body = " ".join(words)
            f.write(f"<DOC>\n<DOCNO> MSM-{i:06d} </DOCNO>\n<TEXT>\n{body}\n"
                    f"</TEXT>\n</DOC>\n")
    if with_prox:
        return (queries, np.array(rel_docnos, np.int64), grades,
                (prox_queries, np.array(prox_rel, np.int64)))
    return queries, np.array(rel_docnos, np.int64), grades


def _mrr_at_k(rel_docnos: np.ndarray, got_docnos: np.ndarray) -> float:
    rr = 0.0
    for qi in range(len(rel_docnos)):
        where = np.nonzero(got_docnos[qi] == rel_docnos[qi])[0]
        if len(where):
            rr += 1.0 / (int(where[0]) + 1)
    return round(rr / len(rel_docnos), 4)


def _ndcg_at_k(grades: list, got_docnos: np.ndarray, k: int = 10) -> float:
    """Graded NDCG@k with gains 2^g - 1 (the standard web-search form)."""
    total = 0.0
    for qi, g in enumerate(grades):
        dcg = sum((2.0 ** g.get(int(d), 0) - 1) / np.log2(r + 2)
                  for r, d in enumerate(got_docnos[qi][:k]))
        ideal = sorted(g.values(), reverse=True)[:k]
        idcg = sum((2.0 ** gv - 1) / np.log2(r + 2)
                   for r, gv in enumerate(ideal))
        total += dcg / idcg if idcg > 0 else 0.0
    return round(total / len(grades), 4)


def _mrr_binary(grades: list, got_docnos: np.ndarray) -> float:
    """MRR under trec_eval's binary-relevance convention: the first
    ranked doc with ANY positive grade counts (unlike _mrr_at_k, which
    tracks only the planted grade-2 doc)."""
    rr = 0.0
    for qi, g in enumerate(grades):
        for r, d in enumerate(got_docnos[qi]):
            if g.get(int(d), 0) > 0:
                rr += 1.0 / (r + 1)
                break
    return round(rr / len(grades), 4)


def _eval_loop_roundtrip(tmp: str, index_dir: str, queries, grades,
                         bm25_docnos10,
                         m_eval_cap: int = 300) -> dict:
    """topics -> `tpu-ir search --topics --trec-run` -> run file ->
    evaluate_run(qrels). Returns the loop's metrics plus an "eval_loop"
    verdict that must be "ok": the run-file MRR@10 and (exp-gain) NDCG@10
    must equal the in-process BM25 numbers on the same query subset."""
    import contextlib
    import io

    from tpu_ir.cli import main as cli_main
    from tpu_ir.search.evaluate import evaluate_run, read_qrels, read_run

    m_eval = min(m_eval_cap, len(queries))
    topics = os.path.join(tmp, "topics.trec")
    with open(topics, "w") as f:
        for qi in range(m_eval):
            f.write(f"<top>\n<num> Number: {qi + 1}\n"
                    f"<title> {queries[qi]}\n</top>\n")
    qrels_path = os.path.join(tmp, "qrels.txt")
    with open(qrels_path, "w") as f:
        for qi in range(m_eval):
            for docno, grade in grades[qi].items():
                f.write(f"{qi + 1} 0 MSM-{docno - 1:06d} {grade}\n")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["search", index_dir, "--topics", topics,
                       "--scoring", "bm25", "--k", "10",
                       "--trec-run", "bench"])
    run_path = os.path.join(tmp, "run.txt")
    with open(run_path, "w") as f:
        f.write(buf.getvalue())
    if rc != 0:
        return {"eval_loop": f"search exited {rc}"}
    ev = evaluate_run(read_run(run_path), read_qrels(qrels_path),
                      complete=True, exp_gains=True)
    want_mrr = _mrr_binary(grades[:m_eval], bm25_docnos10[:m_eval])
    want_ndcg = _ndcg_at_k(grades[:m_eval], bm25_docnos10[:m_eval])
    ok = (ev.get("queries") == m_eval
          and abs(ev["mrr"] - want_mrr) < 1e-3
          and abs(ev["ndcg_at_10"] - want_ndcg) < 1e-3)
    return {
        "eval_loop": "ok" if ok else (
            f"mismatch: run mrr={ev.get('mrr')} vs {want_mrr}, "
            f"ndcg={ev.get('ndcg_at_10')} vs {want_ndcg}, "
            f"queries={ev.get('queries')} vs {m_eval}"),
        "eval_loop_queries": m_eval,
        "eval_loop_mrr": ev.get("mrr", -1.0),
        "eval_loop_ndcg_at_10": ev.get("ndcg_at_10", -1.0),
        "eval_loop_map": ev.get("map", -1.0),
    }


def run_stdlib_eval(tmp: str) -> dict:
    """Real-corpus quality run (VERDICT r4 next #3): the in-repo frozen
    collection of CPython stdlib module documentation (data/stdlib/ —
    144 docs of third-party text, 80 hand-judged topics with graded
    qrels) through the full standard loop: index build -> TREC topics ->
    CLI --trec-run run files -> evaluate_run against the qrels. Unlike
    the synthetic msmarco gate, neither the text nor the judgments were
    generated by this framework."""
    import contextlib
    import io

    from tpu_ir.cli import main as cli_main
    from tpu_ir.search.evaluate import evaluate_run, read_qrels, read_run

    data = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "stdlib")
    if not os.path.isdir(data):
        return {"real_eval": "data/stdlib missing"}
    idx = os.path.join(tmp, "stdlib-idx")
    # redirect: the index command prints the metadata JSON, which would
    # pollute the bench's one-JSON-line stdout contract
    with contextlib.redirect_stdout(io.StringIO()):
        rc = cli_main(["index", os.path.join(data, "corpus.trec"), idx,
                       "--backend", "cpu", "--shards", "2",
                       "--no-chargrams"])
    if rc != 0:
        return {"real_eval": f"index exited {rc}"}
    qrels = read_qrels(os.path.join(data, "qrels.txt"))
    out: dict = {"real_eval": "ok", "real_corpus": "cpython-stdlib-docs"}
    for tag, extra in (("bm25", []), ("rerank", ["--rerank", "100"])):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli_main(["search", idx, "--backend", "cpu", "--topics",
                           os.path.join(data, "topics.trec"),
                           "--scoring", "bm25", "--k", "10",
                           "--trec-run", "bench"] + extra)
        if rc != 0:
            return {"real_eval": f"search({tag}) exited {rc}"}
        run_path = os.path.join(tmp, f"stdlib-run-{tag}.txt")
        with open(run_path, "w") as f:
            f.write(buf.getvalue())
        ev = evaluate_run(read_run(run_path), qrels, complete=True,
                          exp_gains=True)
        out[f"real_{tag}_mrr"] = ev["mrr"]
        out[f"real_{tag}_ndcg_at_10"] = ev["ndcg_at_10"]
        out[f"real_{tag}_map"] = ev["map"]
        out["real_queries"] = ev["queries"]
    return out


_STDLIB_EVAL_CODE = """
import json, sys, tempfile
sys.path.insert(0, {bench_dir!r})
import bench
with tempfile.TemporaryDirectory() as tmp:
    out = bench.run_stdlib_eval(tmp)
print("STDLIB_JSON=" + json.dumps(out))
"""


def run_stdlib_eval_subprocess() -> dict:
    """run_stdlib_eval in its own interpreter, CPU-pinned from the env.

    The eval drives the CLI with --backend cpu, and cli._apply_backend
    deliberately repins the WHOLE process (jax_platforms +
    clear_backends) — in-process it would silently migrate
    every subsequent bench measurement off the TPU while the artifact
    still says backend=tpu. Only the JSON crosses back."""
    import subprocess

    bench_dir = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             _STDLIB_EVAL_CODE.format(bench_dir=bench_dir)],
            capture_output=True, text=True, timeout=1800, env=env)
        for line in r.stdout.splitlines():
            if line.startswith("STDLIB_JSON="):
                return json.loads(line.split("=", 1)[1])
        return {"real_eval": f"subprocess produced no result "
                             f"(rc={r.returncode}): {r.stderr[-200:]}"}
    except (subprocess.SubprocessError, OSError, ValueError) as e:
        return {"real_eval": f"subprocess failed: {e}"[:200]}


# floors for the real-corpus eval: far below the measured values
# (BM25 MRR 0.93 / NDCG@10 0.79 at freeze time) but far above what a
# broken analyzer or scoring regression could reach
_REAL_MRR_FLOOR = 0.7
_REAL_NDCG_FLOOR = 0.6

# minimum msmarco query count for the gate's margins to be meaningful
_GATE_MIN_QUERIES = 200


def quality_gate(m: dict) -> list[str]:
    """The discriminative-power contract: every metric strictly inside
    (0, 1) and rerank > BM25 > TF-IDF with real margins. A scoring
    regression (e.g. broken idf) collapses the ordering and fails here."""
    bad = []
    for key in ("tfidf_mrr_at_10", "bm25_mrr_at_10", "rerank_mrr_at_10",
                "tfidf_ndcg_at_10", "bm25_ndcg_at_10", "rerank_ndcg_at_10"):
        if not 0.0 < m[key] < 1.0:
            bad.append(f"{key}={m[key]} outside (0, 1)")
    if not m["tfidf_mrr_at_10"] + 0.05 < m["bm25_mrr_at_10"]:
        bad.append("bm25 does not beat tfidf by >= 0.05 MRR")
    if not m["bm25_mrr_at_10"] + 0.03 < m["rerank_mrr_at_10"]:
        bad.append("rerank does not beat bm25 by >= 0.03 MRR")
    if not m["tfidf_ndcg_at_10"] < m["bm25_ndcg_at_10"] \
            < m["rerank_ndcg_at_10"]:
        bad.append("NDCG ordering tfidf < bm25 < rerank violated")
    if m.get("real_eval") == "ok":
        # the real-corpus floors: hand-judged qrels over third-party
        # text — a collapsed analyzer or idf cannot stay above these
        if m["real_bm25_mrr"] < _REAL_MRR_FLOOR:
            bad.append(f"real-corpus BM25 MRR {m['real_bm25_mrr']} "
                       f"below {_REAL_MRR_FLOOR}")
        if m["real_bm25_ndcg_at_10"] < _REAL_NDCG_FLOOR:
            bad.append(f"real-corpus BM25 NDCG@10 "
                       f"{m['real_bm25_ndcg_at_10']} below "
                       f"{_REAL_NDCG_FLOOR}")
    if "prox_rerank_mrr_prox_subset" in m:
        # the prox-tie pairs tie exactly for every bag-of-words stage and
        # break toward the distractor; a working proximity boost must
        # move the subset's MRR decisively (0.5 -> ~1.0 by construction)
        if not (m["prox_rerank_mrr_prox_subset"]
                >= m["rerank_mrr_prox_subset"] + 0.2):
            bad.append("proximity boost does not lift the prox-tie "
                       "subset MRR by >= 0.2")
    return bad


def run_msmarco(args) -> dict:
    """Retrieval-quality config: graded planted relevance scored by all
    three scorers (TF-IDF / BM25 / two-stage rerank), MRR@10 + NDCG@10
    each, plus top-1000 candidate recall. The quality_gate asserts the
    discriminative ordering rerank > BM25 > TF-IDF with every value
    strictly inside (0, 1) — a scoring regression fails the gate."""
    from tpu_ir.index import build_index
    from tpu_ir.search import Scorer

    n_docs = 50_000
    n_queries = min(args.queries or 2_000, n_docs // 4)  # planted slots
    with tempfile.TemporaryDirectory() as tmp:
        corpus = os.path.join(tmp, "corpus.trec")
        queries, rel_docnos, grades, prox = make_quality_corpus(
            corpus, n_docs, n_queries, with_prox=True)
        index_dir = os.path.join(tmp, "index")
        t0 = time.perf_counter()
        # positions=True: the proximity-lift measurement below needs the
        # format-v2 position runs
        build_index([corpus], index_dir, k=1, chargram_ks=[],
                    num_shards=10, compute_chargrams=False, positions=True)
        build_s = time.perf_counter() - t0

        scorer = Scorer.load(index_dir, layout="auto")
        q_ids = scorer.analyze_queries(queries, max_terms=4)

        metrics: dict[str, float] = {}
        speeds: dict[str, float] = {}
        docnos_by_scoring: dict[str, np.ndarray] = {}
        scorer_scores_by_scoring: dict[str, np.ndarray] = {}
        for scoring in ("tfidf", "bm25"):
            scorer.topk(q_ids, k=10, scoring=scoring)  # compile
            t0 = time.perf_counter()
            scores10, docnos10 = scorer.topk(q_ids, k=10, scoring=scoring)
            dt = time.perf_counter() - t0
            docnos_by_scoring[scoring] = docnos10
            scorer_scores_by_scoring[scoring] = scores10
            metrics[f"{scoring}_mrr_at_10"] = _mrr_at_k(rel_docnos, docnos10)
            metrics[f"{scoring}_ndcg_at_10"] = _ndcg_at_k(grades, docnos10)
            speeds[f"{scoring}_queries_per_sec"] = round(n_queries / dt, 1)
        bm25_docnos10 = docnos_by_scoring["bm25"]

        # MaxScore parity gate (VERDICT r4 next #1 done-bar): pruning must
        # be INVISIBLE — the same top-10, per query, for both scorers.
        # Tie-tolerant: the two paths accumulate f32 in different orders,
        # so docno swaps are allowed only where the score vectors agree
        # within rounding (genuinely tied docs); anything else fails.
        prune_info: dict = {}
        if scorer.layout == "sparse" and scorer.prune:
            prev_prune = scorer.prune
            mismatches = 0
            try:
                scorer.prune = False
                for scoring, docnos10 in docnos_by_scoring.items():
                    s_on, d_on = scorer_scores_by_scoring[scoring], docnos10
                    s_off, d_off = scorer.topk(q_ids, k=10, scoring=scoring)
                    diff = (d_off != d_on).any(axis=1)
                    tied = np.isclose(np.asarray(s_off), np.asarray(s_on),
                                      rtol=1e-4, atol=1e-6).all(axis=1)
                    mismatches += int((diff & ~tied).sum())
            finally:
                scorer.prune = prev_prune
            prune_info = {
                "prune_parity": ("ok" if mismatches == 0
                                 else f"{mismatches} queries differ"),
                **scorer.prune_diag(q_ids),
            }

        # full standard eval loop (VERDICT r2 next #7): TREC topics file
        # -> CLI --trec-run run file -> evaluate_run against qrels. The
        # loop must REPRODUCE the in-process BM25 MRR@10/NDCG@10 on the
        # same query subset exactly — it exercises topics parsing, batch
        # search, run emission, and both eval readers end to end.
        eval_out = _eval_loop_roundtrip(
            tmp, index_dir, queries, grades, bm25_docnos10)
        # real-corpus quality run, next to the synthetic gate: in-repo
        # CPython-docs collection + hand-judged qrels (VERDICT r4 #3).
        # In a SUBPROCESS: the eval pins its process to the CPU backend
        # (the CLI's --backend is process-wide), which would silently
        # move every later msmarco measurement off the TPU
        real_out = run_stdlib_eval_subprocess()
        metrics.update({k: v for k, v in real_out.items()
                        if isinstance(v, float)})
        metrics["real_eval"] = real_out.get("real_eval", "missing")
        eval_out.update(real_out)

        m = min(256, n_queries)
        from tpu_ir.obs import get_registry

        def _blockmax_delta(before, after):
            """Realized block-max skip fraction over a measured window
            (blocks_masked / blocks_considered; None when the kernels
            never engaged — e.g. TPU_IR_BLOCKMAX=0 control runs)."""
            cons = (after.get("blockmax.blocks_considered", 0)
                    - before.get("blockmax.blocks_considered", 0))
            if cons <= 0:
                return None
            masked = (after.get("blockmax.blocks_masked", 0)
                      - before.get("blockmax.blocks_masked", 0))
            return round(masked / cons, 4)

        c0 = dict(get_registry().snapshot()["counters"])
        t0 = time.perf_counter()
        scorer.topk(q_ids[:m], k=1000, scoring="bm25")  # compile
        cold_s = time.perf_counter() - t0
        c1 = dict(get_registry().snapshot()["counters"])
        t0 = time.perf_counter()
        _, docnos1k = scorer.topk(q_ids[:m], k=1000, scoring="bm25")
        cand_s = time.perf_counter() - t0
        c2 = dict(get_registry().snapshot()["counters"])
        skip_cold = _blockmax_delta(c0, c1)
        skip_warm = _blockmax_delta(c1, c2)
        recall1k = float(np.mean([
            rel_docnos[qi] in docnos1k[qi] for qi in range(m)]))

        # stage 2: cosine TF-IDF rerank over BM25 top-1000 candidates
        # (scored over the SAME query set as the single-stage scorers so
        # the MRR/NDCG comparison is apples to apples)
        scorer.rerank_topk(q_ids, k=10, candidates=1000)  # compile
        t0 = time.perf_counter()
        _, rr_docnos = scorer.rerank_topk(q_ids, k=10, candidates=1000)
        rerank_s = time.perf_counter() - t0
        metrics["rerank_mrr_at_10"] = _mrr_at_k(rel_docnos, rr_docnos)
        metrics["rerank_ndcg_at_10"] = _ndcg_at_k(grades, rr_docnos)
        speeds["rerank_queries_per_sec"] = round(n_queries / rerank_s, 1)

        # proximity lift (VERDICT r2 item 4 "measurably improves"): on
        # the prox-tie pairs every bag-of-words stage ties EXACTLY and
        # the tie is rigged toward the distractor; only the positions
        # boost can put the relevant doc first. Plain rerank MRR on the
        # subset should sit near 0.5, prox near 1.0.
        prox_queries, prox_rel = prox
        def subset_mrr(results):
            got = np.array(
                [[dn for dn, _ in r[:10]] + [0] * (10 - min(len(r), 10))
                 for r in results], np.int64)
            return _mrr_at_k(prox_rel, got)
        base = scorer.search_batch(prox_queries, k=10, rerank=1000,
                                   return_docids=False)
        boosted = scorer.search_batch(prox_queries, k=10, rerank=1000,
                                      prox=True, return_docids=False)
        metrics["prox_subset_queries"] = len(prox_queries)
        metrics["rerank_mrr_prox_subset"] = subset_mrr(base)
        metrics["prox_rerank_mrr_prox_subset"] = subset_mrr(boosted)

        # the gate's fixed margins (0.05 / 0.03 MRR) assume all four query
        # types present in balance AND enough queries that per-query MRR
        # quantization (a handful of coin-flip "norm tie" rankings) cannot
        # eat a margin: at n=18 a healthy run fails the 0.03 margin by
        # 0.002. Enforce only from 200 queries (50+ per type, one rank
        # flip moves MRR by <= 0.005); below that, report but don't gate.
        gate = (quality_gate(metrics) if n_queries >= _GATE_MIN_QUERIES
                else [f"skipped: needs >= {_GATE_MIN_QUERIES} queries"])

    return {
        "metric": "rerank_ndcg_at_10",
        "value": metrics["rerank_ndcg_at_10"],
        "unit": "ndcg",
        # vs the reference's own scoring formula (TF-IDF is all it had) on
        # the same corpus: the quality win of the full two-stage pipeline
        "vs_baseline": round(metrics["rerank_ndcg_at_10"]
                             / max(metrics["tfidf_ndcg_at_10"], 1e-9), 3),
        "corpus_docs": n_docs,
        "queries": n_queries,
        # cold build: includes first-time XLA compiles for this config's
        # shapes (the ref config's warmed docs/s is the throughput headline)
        "index_wall_s_cold": round(build_s, 2),
        **metrics,
        **speeds,
        "top1000_queries_per_sec": round(m / cand_s, 1),
        # deep-k headline twins (ISSUE 13): the warmed deep top-k rate
        # under its own name for the sentry, the cold (first-dispatch,
        # compile included) rate, and the realized block-max skip
        # fraction over each window
        "topk1000_qps": round(m / cand_s, 1),
        "topk1000_qps_cold": round(m / cold_s, 1),
        "blockmax_skip_block_fraction": skip_warm,
        "blockmax_skip_block_fraction_cold": skip_cold,
        "top1000_recall": round(recall1k, 4),
        "quality_gate": "ok" if not gate else "; ".join(gate),
        "quality_gate_enforced": n_queries >= _GATE_MIN_QUERIES,
        **eval_out,
        **prune_info,
        **profile_breakdown(),
        "layout": scorer.layout,
        "config": "msmarco",
    }


def _recall_at_10(scorer, q_ids: np.ndarray, got_docnos: np.ndarray) -> float:
    """Exhaustive host-side TF-IDF oracle over the CSR postings."""
    pt, pd, ptf = scorer._pairs
    n = scorer.meta.num_docs
    df = np.asarray(scorer.df)
    hits = total = 0
    for qi in range(q_ids.shape[0]):
        scores = np.zeros(n + 1)
        for tid in q_ids[qi]:
            if tid < 0 or df[tid] == 0:
                continue
            sel = pt == tid
            idf = np.log10(n / df[tid])
            scores[pd[sel]] += (1.0 + np.log(ptf[sel])) * idf
        pos = np.nonzero(scores > 0)[0]
        if len(pos) == 0:
            continue
        expect = min(10, len(pos))
        thr = np.sort(scores[pos])[::-1][expect - 1]
        got = [int(d) for d in got_docnos[qi] if d > 0]
        # tie-tolerant: any doc scoring >= the oracle's 10th-best counts
        hits += sum(1 for d in got if scores[d] >= thr - 1e-9)
        total += expect
    return round(hits / total, 4) if total else 1.0


#: every device array a loaded Scorer may hold, by attribute name. The
#: single definition of "the load is complete" — the cold-load parent,
#: the warm-load child, and experiments/warm_load_profile.py all block
#: on serving_arrays(); hand-copied lists here previously risked the
#: cold/warm split comparing loads of different completeness when the
#: serving layout gains or renames an array.
SERVING_ARRAY_NAMES = ("hot_tfs", "doc_matrix", "hot_rank", "tier_of",
                       "row_of", "tier_docs", "tier_tfs")


def serving_arrays(s):
    """The Scorer's resident device arrays (df/doc_len always; layout
    arrays when the layout defines them)."""
    arrays = [s.df, s.doc_len] + [getattr(s, n, None)
                                  for n in SERVING_ARRAY_NAMES]
    return [a for a in arrays if a is not None]


_WARM_LOAD_CODE = """
import json, sys, time
t0 = time.perf_counter()
import jax
jax.config.update("jax_platforms", "cpu")
jax.devices()  # force backend init so it lands in init_s, not load
sys.path.insert(0, {bench_dir!r})
import bench
from tpu_ir.search import Scorer  # library imports are process cost too
init_s = time.perf_counter() - t0
# transport fingerprint taken INSIDE this process, moments before the
# load: the state the load actually experiences, not the parent's
probe = bench.transport_probe()
t1 = time.perf_counter()
s = Scorer.load({index_dir!r}, layout="auto")
jax.block_until_ready(bench.serving_arrays(s))
index_s = time.perf_counter() - t1
print("WARM_JSON=" + json.dumps({{
    "load_s": round(init_s + index_s, 2),
    "init_s": round(init_s, 2),
    "index_s": round(index_s, 2),
    **bench.load_stage_breakdown(),
    **bench.profile_breakdown(),
    **probe,
}}))
"""


def load_stage_breakdown() -> dict:
    """The load.* stage seconds (verify / read / assemble / layout /
    cache_write / h2d) plus
    effective H2D bandwidth from this process's telemetry registry —
    recorded in every BENCH row and BENCH_HISTORY.jsonl so the
    cold-start trajectory is tracked like throughput (ISSUE 5). Stages
    that never fired report 0.0; keys are flat (load_verify_s, ...,
    load_h2d_mbps) so history rows stay grep/jq-friendly."""
    from tpu_ir.obs import LOAD_STAGES, get_registry

    snap = get_registry().snapshot()
    hists = snap.get("histograms", {})
    out = {}
    for stage in LOAD_STAGES:
        s = hists.get(stage, {})
        out[stage.replace(".", "_") + "_s"] = round(
            s.get("sum_ms", 0.0) / 1e3, 3)
    h2d_bytes = snap.get("counters", {}).get("load.h2d_bytes", 0)
    out["load_h2d_bytes"] = int(h2d_bytes)
    h2d_s = out["load_h2d_s"]
    out["load_h2d_mbps"] = (round(h2d_bytes / (1 << 20) / h2d_s, 1)
                            if h2d_s > 0 and h2d_bytes else -1.0)
    return out


# keys profile_breakdown emits; the warm child's copies ride into the
# BENCH row warm_-prefixed (like the load_* stage split)
PROFILE_KEYS = ("compile_s", "recompiles", "device_time_ms",
                "peak_hbm_bytes")


def profile_breakdown() -> dict:
    """The device-cost profiling fields of a BENCH row (ISSUE 7), from
    this process's registry: total XLA compile seconds (`compile.time`
    sum), recompile count (same-signature compiles — the micro-batching
    ladder's classic silent failure), per-dispatch device time
    (`dispatch.device` p50, the pure compute+wait slice split out of
    the host-measured `device_rtt_ms`), and peak HBM bytes (the
    `device.peak_bytes` gauge; -1 on hosts whose backend reports no
    memory_stats, e.g. CPU)."""
    from tpu_ir.obs import get_registry

    snap = get_registry().snapshot()
    hists = snap.get("histograms", {})
    comp = hists.get("compile.time", {})
    dd = hists.get("dispatch.device", {})
    peak = int(snap.get("gauges", {}).get("device.peak_bytes", 0))
    return {
        "compile_s": round((comp.get("sum_ms") or 0.0) / 1e3, 3),
        "recompiles": int(snap.get("counters", {}).get(
            "compile.recompiles", 0)),
        "device_time_ms": (round(dd["p50_ms"], 3)
                           if dd.get("count") and dd.get("p50_ms")
                           is not None else -1.0),
        "peak_hbm_bytes": peak if peak > 0 else -1,
    }


def _warm_load_subprocess(index_dir: str, cpu: bool,
                          attempts: int = 2) -> dict:
    """Time Scorer.load in fresh interpreters (true process restarts).

    Splits the PROCESS-fixed cost (python + jax import + backend init —
    paid by any jax program, index or not) from the index-load cost
    proper, so a large fixed cost cannot masquerade as a slow load.
    Every child runs the transport probe itself right before loading
    and reports it alongside its timings; the parent takes best-of-N
    and records every run. Values are -1.0 if every child fails.

    CPU only: on a chip the parent already holds the device, and a
    child that needs it fails or hangs, so there the fields read
    "not measured"."""
    if not cpu:
        return {k: "not measured" for k in (
            "scorer_load_warm_s", "warm_process_fixed_s",
            "warm_index_load_s")}
    import subprocess

    bench_dir = os.path.dirname(os.path.abspath(__file__))
    runs = []
    for _ in range(attempts):
        try:
            r = subprocess.run(
                [sys.executable, "-c",
                 _WARM_LOAD_CODE.format(index_dir=index_dir,
                                        bench_dir=bench_dir)],
                capture_output=True, text=True, timeout=3600)
            for line in r.stdout.splitlines():
                if line.startswith("WARM_JSON="):
                    runs.append(json.loads(line.split("=", 1)[1]))
                    break
        except (subprocess.SubprocessError, OSError, ValueError):
            continue
    if not runs:
        return {"scorer_load_warm_s": -1.0, "warm_process_fixed_s": -1.0,
                "warm_index_load_s": -1.0, "warm_runs": []}
    best = min(runs, key=lambda m: m["index_s"])
    return {
        # headline = the best run's numbers (steady-state warm load);
        # warm_runs carries every attempt with its own transport probe
        "scorer_load_warm_s": best["load_s"],
        "warm_process_fixed_s": best["init_s"],
        "warm_index_load_s": best["index_s"],
        "warm_h2d_mbps": best.get("h2d_mbps", -1.0),
        "warm_device_rtt_ms": best.get("device_rtt_ms", -1.0),
        # the child's own load.* stage split and profiling fields
        # (compile seconds / recompiles / peak HBM of a true process
        # restart), warm_-prefixed so the row carries both cold
        # (parent) and warm (child) breakdowns; the child's total
        # load_s is excluded — it already lands above as
        # scorer_load_warm_s, and a warm_load_s twin would double-count
        # the total into the warm_load_* stage keys for any consumer
        # summing them
        **{f"warm_{k}": v for k, v in best.items()
           if (k.startswith("load_") or k in PROFILE_KEYS)
           and k != "load_s"},
        "warm_runs": runs,
    }


def transport_probe() -> dict:
    """Transport fingerprint: H2D / D2H bandwidth on a 32 MB buffer plus
    the scalar-fetch round trip (p50 of 20). Recording them in the bench
    JSON makes a throughput swing that comes from the host-device link
    attributable from the artifact alone."""
    import jax
    import jax.numpy as jnp

    mb = 32
    buf = np.random.default_rng(0).integers(
        0, 255, mb << 20, dtype=np.uint8)

    # scalar round trip first (feeds the h2d estimate). A FRESH scalar
    # each rep: jax.Array caches its fetched numpy value, so re-fetching
    # one array times a dict hit, not the wire.
    base = jnp.zeros((), jnp.int32)
    jax.block_until_ready(base)
    rtts = []
    for i in range(20):
        y = base + i
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        np.asarray(y)
        rtts.append(time.perf_counter() - t0)
    rtt_s = float(np.percentile(rtts, 50))

    # H2D: device_put alone can complete asynchronously on plugin
    # backends (block_until_ready has no transfer to wait on), so force
    # the bytes across with a dependent reduce + scalar fetch and
    # subtract the round trip
    d = jax.device_put(buf)
    s = jnp.sum(d, dtype=jnp.uint32)
    np.asarray(s)                     # warm transfer path + compile
    del d, s
    t0 = time.perf_counter()
    d = jax.device_put(buf)
    s = jnp.sum(d, dtype=jnp.uint32)
    np.asarray(s)
    h2d_s = max(time.perf_counter() - t0 - rtt_s, 1e-9)

    t0 = time.perf_counter()
    np.asarray(d)                     # full-buffer D2H (uncached array)
    d2h_s = time.perf_counter() - t0
    return {
        "h2d_mbps": round(mb / h2d_s, 1),
        "d2h_mbps": round(mb / d2h_s, 1),
        "device_rtt_ms": round(rtt_s * 1e3, 2),
    }


def device_build_control(corpus: str, reps: int = 3) -> dict:
    """Transport-INDEPENDENT build control: the exact device program the
    builder runs (same prep, same shapes, same data), timed with
    block_until_ready and NO result fetch — pure dispatch + device
    compute. If docs/s drops across rounds while this number holds, the
    loss is transport/host, not the device pipeline; if this moves, the
    code regressed. Also reports the host tokenize time separately."""
    import jax
    import jax.numpy as jnp

    from tpu_ir.analysis.native import tokenize_corpus_native
    from tpu_ir.ops import PAD_TERM, PAD_TERM_U16, build_postings_packed_jit

    t0 = time.perf_counter()
    docids, temp_ids, lengths, vocab_list = tokenize_corpus_native([corpus])
    tokenize_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    vocab_arr = np.array(vocab_list, dtype=np.str_)
    order = np.argsort(vocab_arr)
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order))
    flat_term_ids = rank[temp_ids].astype(np.int32)
    docnos = (np.argsort(np.argsort(np.array(docids, dtype=np.str_)))
              + 1).astype(np.int32)
    v = len(vocab_list)
    occurrences = len(flat_term_ids)
    granule = 1 << 18
    cap = max(granule, (occurrences + granule - 1) // granule * granule)
    use16 = v < int(PAD_TERM_U16)
    term_ids = np.full(cap, PAD_TERM_U16 if use16 else PAD_TERM,
                       np.uint16 if use16 else np.int32)
    term_ids[:occurrences] = flat_term_ids
    host_prep_s = time.perf_counter() - t0

    t_dev, l_dev = jnp.asarray(term_ids), jnp.asarray(
        lengths.astype(np.int32))
    d_dev = jnp.asarray(docnos)
    times = []
    for _ in range(reps + 1):  # first rep includes compile; dropped
        t0 = time.perf_counter()
        p = build_postings_packed_jit(t_dev, d_dev, l_dev, vocab_size=v,
                                      num_docs=len(docids))
        jax.block_until_ready((p.pair_doc, p.pair_tf, p.df))
        times.append(time.perf_counter() - t0)
    return {
        "control_tokenize_s": round(tokenize_s, 3),
        "control_host_prep_s": round(host_prep_s, 3),
        "control_device_build_s": round(min(times[1:]), 3),
        "control_device_build_runs": [round(t, 3) for t in times[1:]],
    }


def device_query_control(scorer, q_ids: np.ndarray, reps: int = 3) -> dict:
    """Transport-INDEPENDENT query control with a MaxScore A/B: one query
    block dispatched with block_until_ready and NO result fetch, timed
    with the static cold-only kernel (skip_hot — what the scheduler
    dispatches for hot-free blocks) and with the full kernel. The delta
    is the measured device-side value of the pruning (VERDICT r4 next
    #1); engagement fractions say how many blocks of this query load
    take the skip kernel. Tiered (sparse) layouts only."""
    if scorer.layout != "sparse":
        return {"control_query_layout": scorer.layout}
    import jax

    block = scorer._block_size()
    q_all = np.asarray(q_ids, np.int32)
    # measure a hot-free prefix in dispatch order: skip_hot is only
    # exact (and only ever dispatched) for such blocks. Padded back to
    # `block` rows with PAD queries so the compiled shape matches real
    # dispatches.
    has_hot, n_free, mode = scorer._skip_plan(q_all)
    sched = q_all[scorer._schedule_order(has_hot)]
    out = dict(scorer.prune_diag(q_all))
    out["control_query_block"] = block
    out["control_query_block_hot_free"] = min(block, n_free)
    if mode == "all_full":
        # topk() never dispatches the skip kernel for this load (no
        # hot-free queries, or fewer than MIN_SKIP_GROUP — the shared
        # _skip_plan is the authority), so an A/B here would fabricate
        # a speedup that never materializes
        out["control_query_skip_na"] = True
        return out
    q = np.full((block, q_all.shape[1]), -1, np.int32)
    q[: min(block, n_free)] = sched[: min(block, n_free)]
    for skip, key in ((True, "control_device_query_s"),
                      (False, "control_device_query_noprune_s")):
        times = []
        for _ in range(reps + 1):  # first rep includes compile; dropped
            t0 = time.perf_counter()
            s, d = scorer._topk_device(q, 10, "tfidf", skip_hot=skip)
            jax.block_until_ready((s, d))
            times.append(time.perf_counter() - t0)
        out[key] = round(min(times[1:]), 4)
        out[key + "_runs"] = [round(t, 4) for t in times[1:]]
    return out


def v48_extrapolation(controls: dict, phases: dict, num_docs: int,
                      n_queries: int = 10_000) -> dict:
    """North-star extrapolation computed IN the artifact (VERDICT r4
    next #4): what the <60 s / 1M-doc target looks like on a v4-8
    (4 chips), from THIS run's own measurements.

    - device build: the per-chip ceiling measured by the ref-scale probe
      control (`control_device_build_s`, block_until_ready, no fetch),
      scaled by 4 chips — the build's device program is
      throughput-parallel over doc shards (parallel/sharded_build.py).
    - host phases: taken AS MEASURED on this 1-core container
      (conservative: a real v4-8 host has ~120 cores and the C++
      scanner shards trivially by file chunk).
    - queries: the device-only query control per block, scaled to the
      10k batch over 4 doc-sharded chips (parallel/sharded_tiered.py).

    Every input rides in the same JSON, so the estimate is recomputable
    from the artifact alone."""
    if "control_device_build_s" not in controls:
        return {}
    chip_rate = DOC_COUNT_REF / controls["control_device_build_s"]
    dev_s = num_docs / (chip_rate * 4)
    host_s = sum(v for k, v in phases.items()
                 if k.startswith("phase_") and k != "phase_pass2_combine_s"
                 and isinstance(v, (int, float)))
    out = {
        "v48_chip_docs_per_sec": round(chip_rate, 1),
        "v48_device_build_s_est": round(dev_s, 1),
        "v48_host_phases_s_measured": round(host_s, 1),
        "v48_build_s_est": round(dev_s + host_s, 1),
    }
    q_s, blk = (controls.get("control_device_query_s"),
                controls.get("control_query_block"))
    if q_s and blk:
        out["v48_query_10k_s_est"] = round(
            q_s * (n_queries / blk) / 4, 2)
        out["v48_north_star_s_est"] = round(
            out["v48_build_s_est"] + out["v48_query_10k_s_est"], 1)
    return out


DOC_COUNT_REF = 8_761  # the probe-corpus size the chip ceiling is measured on


def _append_history(out: dict) -> None:
    """Append this run's summary row to the cumulative
    BENCH_HISTORY.jsonl next to this script (timestamp- and
    commit-sha-stamped), so the perf trajectory across PRs is one
    machine-readable file instead of scattered BENCH_*.json snapshots.
    Best-effort: a read-only checkout must not fail the bench. ONE
    stamping/writing implementation, shared with the serve-bench sweep
    (obs/bench_check.append_history_row) so the row schema cannot
    diverge."""
    from tpu_ir.obs.bench_check import append_history_row

    here = os.path.dirname(os.path.abspath(__file__))
    append_history_row(out, path=os.path.join(here, "BENCH_HISTORY.jsonl"))


def _build_phase_timings(index_dir: str) -> dict:
    """Surface the builder's own JobReport phase timings into the bench
    JSON (they were always recorded, never published — VERDICT r2 next #1)."""
    import glob

    for path in glob.glob(os.path.join(index_dir, "jobs",
                                       "TermKGramDocIndexer*.json")):
        with open(path) as f:
            rep = json.load(f)
        return {f"phase_{k}_s": v for k, v in sorted(
            rep.get("timings_s", {}).items())}
    return {}


def _cpu_control_subprocess(timeout_s: int = 900) -> dict:
    """Run the build-only bench on the CPU backend in a subprocess: a
    transport-free, device-free control of the SAME code path; moves
    only when the code does."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--cpu",
             "--build-only"],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in reversed(r.stdout.splitlines()):
            if line.startswith("{"):
                child = json.loads(line)
                return {
                    "cpu_control_docs_per_sec": child.get("value", -1.0),
                    "cpu_control_index_wall_s": child.get(
                        "index_wall_s", -1.0),
                }
    except (subprocess.SubprocessError, OSError, ValueError):
        pass
    return {"cpu_control_docs_per_sec": -1.0,
            "cpu_control_index_wall_s": -1.0}


def pass_metrics(phases: dict, build_s: float) -> dict:
    """Sentry-gated per-phase aliases for a streaming build row: the
    curated METRICS names (build_s / pass1_tokenize_s / pass2_combine_s
    / pass3_reduce_s, direction-aware lower-is-better in
    obs/bench_check.py) lifted out of the phase_* decomposition so the
    regression sentry gates build performance from this PR on."""
    out = {"build_s": round(build_s, 2)}
    for phase in ("pass1_tokenize", "pass2_combine", "pass3_reduce"):
        v = phases.get(f"phase_{phase}_s")
        if isinstance(v, (int, float)):
            out[f"{phase}_s"] = round(v, 2)
    return out


def run_scaling(args, backend: str) -> int:
    """`--scaling N,N,...`: per-phase build scaling sweep (ISSUE 11).

    For each docs count, synthesizes a proportional corpus (~2.7 KB/doc,
    the wiki configs' shape), runs the streaming radix build, and
    records one build_scale-<docs>d row per count — pass1/pass2/pass3
    wall seconds, corpus + spill bytes, pairs — in BENCH_HISTORY.jsonl.
    Linear build scaling is the claim; these rows are the evidence (and
    the bench-check comparability groups that gate it)."""
    from tpu_ir.index.streaming import build_index_streaming
    from tpu_ir.obs import get_registry

    counts = [int(x) for x in args.scaling.split(",") if x]
    radix = args.radix_buckets if args.radix_buckets is not None else 16
    rows = []
    for n_docs in counts:
        with tempfile.TemporaryDirectory() as tmp:
            corpus = os.path.join(tmp, "corpus.trec")
            nbytes = make_corpus(
                corpus, n_docs=n_docs, target_bytes=n_docs * 2_700,
                vocab_size=max(30_000, n_docs // 2))
            index_dir = os.path.join(tmp, "index")
            get_registry().snapshot(reset=True)
            t0 = time.perf_counter()
            build_index_streaming(
                [corpus], index_dir, k=1, num_shards=10,
                compute_chargrams=False, radix_buckets=radix,
                tokenize_procs=args.tokenize_procs)
            build_s = time.perf_counter() - t0
            phases = _build_phase_timings(index_dir)
            snap = get_registry().snapshot()
            # the comparability key carries the BUILD SHAPE (bucket
            # count, pool size) like serve_sweep-<docs>d-c<top> does:
            # bench-check groups rows by config, and a radix run judged
            # against a legacy-row median would breach (or mask) on the
            # mode difference, not a regression
            shape = f"-r{radix}" + (
                f"-p{args.tokenize_procs}" if args.tokenize_procs else "")
            row = {
                "metric": "build_scale",
                "config": f"build_scale-{n_docs}d{shape}",
                "backend": backend,
                "build_only": True,
                "num_docs": n_docs,
                "radix_buckets": radix,
                "tokenize_procs": args.tokenize_procs or 1,
                "corpus_bytes": nbytes,
                "spill_bytes": snap["counters"].get(
                    "build.radix.spill_bytes", 0),
                "docs_per_sec": round(n_docs / build_s, 1),
                **pass_metrics(phases, build_s),
                **phases,
            }
            rows.append(row)
            _append_history(row)
            print(json.dumps(row))
    return 0


def _postings_bytes(index_dir: str) -> tuple[int, int]:
    """(postings part bytes, whole index-dir bytes). The part files are
    the compressible payload the ratio is judged on; the dir total says
    what a worker actually rsyncs."""
    from tpu_ir.index import format as fmt

    meta = fmt.IndexMetadata.load(index_dir)
    parts = sum(os.path.getsize(fmt.part_path(index_dir, s))
                for s in range(meta.num_shards))
    total = 0
    for root, _dirs, files in os.walk(index_dir):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return parts, total


def _measure_compress_variant(index_dir: str, n_queries: int,
                              cpu: bool) -> tuple[dict, tuple]:
    """One side of the --compress A/B: cold load with the load.* stage
    split (serving cache removed first — the point is the from-disk
    path), a true-restart warm load, and the batched BM25 top-10 rate
    with block-max pruning on and off. Returns (row fields, a 64-query
    (scores, docnos) parity sample taken with pruning on)."""
    import jax

    from tpu_ir.index import format as fmt
    from tpu_ir.obs import get_registry
    from tpu_ir.search import Scorer

    meta = fmt.IndexMetadata.load(index_dir)
    parts, total = _postings_bytes(index_dir)
    out = {
        "compressed": bool(getattr(meta, "compressed", False)),
        "tf_dtype": getattr(meta, "tf_dtype", "int32"),
        "tf_lossy": bool(getattr(meta, "tf_lossy", False)),
        "index_bytes": parts,
        "index_dir_bytes": total,
        "bytes_per_doc": round(parts / meta.num_docs, 2),
    }
    shutil.rmtree(os.path.join(index_dir, "serving-tiered"),
                  ignore_errors=True)
    get_registry().snapshot(reset=True)
    # arm the format layer's streamed-bytes meter: on a page-cached CPU
    # container load_read_s barely moves (decode replaces disk wait), so
    # the "reads shrink with the payload" claim is made on BYTES — the
    # quantity that survives to machines where reads cost real time
    fmt.reset_read_bytes()
    t0 = time.perf_counter()
    scorer = Scorer.load(index_dir, layout="auto")
    jax.block_until_ready(serving_arrays(scorer))
    out["scorer_load_cold_s"] = round(time.perf_counter() - t0, 2)
    out["cold_read_bytes"] = int(sum(
        fmt.read_bytes_streamed().values()))
    fmt.reset_read_bytes(arm=False)
    out.update(load_stage_breakdown())
    out.update(_warm_load_subprocess(index_dir, cpu=cpu, attempts=1))
    out.pop("warm_runs", None)

    rng = np.random.default_rng(1)
    q_ids = rng.integers(0, meta.vocab_size, size=(n_queries, 2)).astype(
        np.int32)
    parity = None
    for bm, tag in (("1", "topk_qps_blockmax_on"),
                    ("0", "topk_qps_blockmax_off")):
        os.environ["TPU_IR_BLOCKMAX"] = bm
        try:
            scorer.topk(q_ids, k=10, scoring="bm25")  # compile
            t0 = time.perf_counter()
            scores, docnos = scorer.topk(q_ids, k=10, scoring="bm25")
            out[tag] = round(n_queries / (time.perf_counter() - t0), 1)
            if bm == "1":
                parity = (np.asarray(scores[:64]), np.asarray(docnos[:64]))
        finally:
            os.environ.pop("TPU_IR_BLOCKMAX", None)
    out["query_batch"] = n_queries
    out["layout"] = scorer.layout
    # decode/compress telemetry for this variant's loads + dispatches
    # (zero on the raw side — the counters existing at 0 is the signal
    # that the fused path never engaged)
    for name, v in get_registry().snapshot()["counters"].items():
        if name.startswith(("decode.", "compress.")):
            out[name.replace(".", "_")] = int(v)
    return out, parity


def run_compress_ab(args, backend: str, streaming: bool) -> int:
    """`--compress`: the ISSUE 20 A/B. Build ONE index at the config's
    scale, measure it raw, migrate a copy to the compressed arena
    (tpu-ir migrate-index --compress equivalent), measure that, and
    append BOTH rows to BENCH_HISTORY.jsonl under per-variant configs
    (compress_ab-<docs>d-raw / -compressed) so the bench-check sentry
    gates index_bytes / bytes_per_doc / load_read_s / load_h2d_s per
    variant. In-process acceptance: the postings payload must shrink
    >= 2.5x, and lossless modes must serve the same top-10 (scores
    compared as float32 BITS) as the raw index."""
    from tpu_ir.index import format as fmt
    from tpu_ir.index.migrate import migrate_index

    n_queries = min(args.queries or 2_000, 2_000)
    with tempfile.TemporaryDirectory() as tmp:
        corpus = os.path.join(tmp, "corpus.trec")
        make_corpus(corpus)
        raw_dir = os.path.join(tmp, "index-raw")
        t0 = time.perf_counter()
        if streaming:
            from tpu_ir.index.streaming import build_index_streaming

            radix = (args.radix_buckets if args.radix_buckets is not None
                     else 16)
            build_index_streaming([corpus], raw_dir, k=1, chargram_ks=[],
                                  num_shards=10, radix_buckets=radix,
                                  tokenize_procs=args.tokenize_procs)
        else:
            from tpu_ir.index import build_index

            build_index([corpus], raw_dir, k=1, chargram_ks=[],
                        num_shards=10, compute_chargrams=False)
        build_s = time.perf_counter() - t0

        raw_row, raw_parity = _measure_compress_variant(
            raw_dir, n_queries, args.cpu)

        comp_dir = os.path.join(tmp, "index-comp")
        shutil.copytree(raw_dir, comp_dir)
        shutil.rmtree(os.path.join(comp_dir, "serving-tiered"),
                      ignore_errors=True)
        t0 = time.perf_counter()
        migrate_index(comp_dir, to_version=fmt.COMPRESSED_FORMAT_VERSION,
                      tf_dtype=args.tf_dtype)
        migrate_s = time.perf_counter() - t0
        comp_row, comp_parity = _measure_compress_variant(
            comp_dir, n_queries, args.cpu)

        ratio = round(raw_row["index_bytes"]
                      / max(comp_row["index_bytes"], 1), 2)
        if comp_row["tf_lossy"]:
            parity = "skipped (lossy int8)"
        else:
            s_r, d_r = raw_parity
            s_c, d_c = comp_parity
            bad = int((d_r != d_c).any(axis=1).sum()
                      + (s_r.astype(np.float32).view(np.uint32)
                         != s_c.astype(np.float32).view(np.uint32))
                      .any(axis=1).sum())
            parity = "ok" if bad == 0 else f"{bad} queries differ"
        common = {
            "metric": "compress_ab",
            "backend": backend,
            "num_docs": DOC_COUNT,
            "build_s": round(build_s, 2),
            "compress_ratio": ratio,
            "serving_parity": parity,
        }
        raw_row = {**common,
                   "config": f"compress_ab-{DOC_COUNT}d-raw", **raw_row}
        comp_row = {**common,
                    "config": f"compress_ab-{DOC_COUNT}d-compressed",
                    "migrate_s": round(migrate_s, 2),
                    "raw_index_bytes": raw_row["index_bytes"], **comp_row}
        for row in (raw_row, comp_row):
            _append_history(row)
            print(json.dumps(row))
    bad = []
    if ratio < 2.5:
        bad.append(f"compression ratio {ratio} below the 2.5x floor")
    if (comp_row["cold_read_bytes"] * 2.0
            > raw_row["cold_read_bytes"]):
        bad.append(
            f"cold-load bytes read did not drop with the payload: "
            f"{comp_row['cold_read_bytes']} vs raw "
            f"{raw_row['cold_read_bytes']}")
    if parity not in ("ok", "skipped (lossy int8)"):
        bad.append(f"raw-vs-compressed serving parity broke: {parity}")
    if bad:
        print("bench --compress FAILED: " + "; ".join(bad),
              file=sys.stderr)
        return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU backend (local-mode equivalent)")
    ap.add_argument("--queries", type=int, default=None,
                    help="query-batch size (default: 10000; msmarco: 2000)")
    ap.add_argument("--build-only", action="store_true",
                    help="corpus + warmup + timed builds only (used as the "
                         "CPU control subprocess; skips serving/query/"
                         "control measurements)")
    ap.add_argument("--no-controls", action="store_true",
                    help="skip the transport probe, device-only build "
                         "control, and CPU control subprocess")
    ap.add_argument("--compress", action="store_true",
                    help="compressed-arena A/B (ISSUE 20): build one "
                         "index at the config's scale, measure raw, "
                         "migrate a copy to the compressed arena, "
                         "measure again, and append a raw/compressed "
                         "row PAIR (index_bytes, bytes_per_doc, "
                         "cold/warm load stage split, BM25 top-10 QPS "
                         "with block-max on/off) to BENCH_HISTORY.jsonl; "
                         "fails unless the postings shrink >= 2.5x and "
                         "lossless modes serve bit-identical top-10")
    ap.add_argument("--tf-dtype", choices=["int8", "bf16"], default=None,
                    help="tf quantization for --compress (default: auto "
                         "= int8 when lossless for this index, else "
                         "bf16)")
    ap.add_argument("--scaling", default=None, metavar="DOCS[,DOCS...]",
                    help="per-phase build scaling sweep: for each docs "
                         "count, synthesize a proportional corpus, run "
                         "the streaming radix build, and append a "
                         "build_scale-<docs>d row (pass1/pass2/pass3 "
                         "wall + bytes) to BENCH_HISTORY.jsonl — the "
                         "rows the bench-check sentry gates build perf "
                         "on; skips all query/serving measurement")
    ap.add_argument("--radix-buckets", type=int, default=None,
                    help="radix buckets for streaming builds (default: "
                         "16 for streaming configs and the scaling "
                         "sweep; 0 = legacy per-batch pass 2)")
    ap.add_argument("--tokenize-procs", type=int, default=None,
                    help="tokenizer pool size for the pure-Python "
                         "analyzer path (default: env/1)")
    ap.add_argument("--config",
                    choices=["ref", "wiki100k", "wiki1m", "msmarco"],
                    default="ref",
                    help="ref = reference-scale corpus (8,761 docs / 23 MB); "
                         "wiki100k = 100k docs / ~270 MB, streaming build; "
                         "wiki1m = 1M docs / ~2.7 GB, streaming build (no "
                         "warm-up run — relies on the persistent compile "
                         "cache, so the first-ever run includes compiles); "
                         "msmarco = 50k passages + 2k planted-relevance "
                         "queries, BM25 MRR@10 + top-1000 candidates")
    args = ap.parse_args()
    if args.queries is None and args.config != "msmarco":
        args.queries = 10_000

    global DOC_COUNT, TARGET_BYTES, VOCAB_SIZE
    streaming = False
    if args.config == "wiki100k":
        DOC_COUNT, TARGET_BYTES, VOCAB_SIZE = 100_000, 270_000_000, 200_000
        streaming = True
    elif args.config == "wiki1m":
        DOC_COUNT, TARGET_BYTES, VOCAB_SIZE = (
            1_000_000, 2_700_000_000, 500_000)
        streaming = True

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        jax.devices("tpu")  # no chip: raise, never fall back to the CPU
    backend = jax.devices()[0].platform

    if args.scaling:
        return run_scaling(args, backend)

    if args.compress:
        return run_compress_ab(args, backend, streaming)

    if args.config == "msmarco":
        out = run_msmarco(args)
        out["backend"] = backend
        _append_history(out)
        print(json.dumps(out))
        if out["quality_gate_enforced"] and out["quality_gate"] != "ok":
            return 1
        # the eval loop is a deterministic correctness assertion (same
        # index, same queries, same scorer) — any mismatch fails
        if out.get("eval_loop") != "ok":
            return 1
        # MaxScore pruning must be rank-safe on the gate corpus
        if out.get("prune_parity", "ok") != "ok":
            return 1
        # the real-corpus eval must actually RUN: its floors live in
        # quality_gate but only apply when real_eval == "ok", so an
        # end-to-end breakage of stdlib indexing/search must fail here
        # rather than silently skipping the gate
        if out.get("real_eval") != "ok":
            print(f"bench: real-corpus eval failed: "
                  f"{out.get('real_eval')}", file=sys.stderr)
            return 1
        return 0

    from tpu_ir.index import build_index
    from tpu_ir.search import Scorer

    with tempfile.TemporaryDirectory() as tmp:
        corpus = os.path.join(tmp, "corpus.trec")
        nbytes = make_corpus(corpus)
        index_dir = os.path.join(tmp, "index")

        # warm-up build: compiles/loads every device program at the exact
        # shapes of the timed build (same corpus -> same static shapes),
        # so the timed runs measure steady-state throughput, not XLA
        # compilation or executable-cache deserialization. The timed
        # build repeats and the fastest run is the headline number (all
        # runs are recorded).
        if streaming:
            from tpu_ir.index.streaming import build_index_streaming

            radix = (args.radix_buckets if args.radix_buckets is not None
                     else 16)

            # store=True: the docstore rides pass 1's text spills (zero
            # extra corpus reads — VERDICT r4 next #5); its cost shows up
            # attributed as phase_docstore_s + the pass-1 spill overhead.
            # Streaming configs default to the radix-partitioned pass 2
            # (ISSUE 11) — bit-identical artifacts, so the row stays
            # comparable to its pre-radix history.
            def one_build(out):
                build_index_streaming([corpus], out, k=1,
                                      chargram_ks=[2, 3], num_shards=10,
                                      store=True, radix_buckets=radix,
                                      tokenize_procs=args.tokenize_procs)
        else:
            def one_build(out):
                build_index([corpus], out, k=1, chargram_ks=[2, 3],
                            num_shards=10)

        if args.config != "wiki1m":  # 1M-doc warm-up would double a long run
            warm_dir = os.path.join(tmp, "index-warmup")
            one_build(warm_dir)
            shutil.rmtree(warm_dir)
        runs = []
        phase_sets = []
        # best-of-N: five ref-scale builds cost ~20 s total and give the
        # minimum a fair shot at the steady-state number
        n_runs = 1 if streaming else 5
        for r in range(n_runs):
            out = index_dir if r == n_runs - 1 else os.path.join(
                tmp, f"index-run{r}")
            t0 = time.perf_counter()
            one_build(out)
            runs.append(time.perf_counter() - t0)
            # phases are captured per run so the published decomposition
            # belongs to the SAME run as the headline min — the last run
            # can catch a hiccup and its phases would then sum to more
            # than index_wall_s
            phase_sets.append(_build_phase_timings(out))
            if out != index_dir:
                shutil.rmtree(out)
        build_s = min(runs)
        docs_per_sec = DOC_COUNT / build_s
        phases = phase_sets[runs.index(build_s)]

        # docstore accounting (VERDICT r4 next #5): streaming configs
        # built the store inside the timed build (phase_docstore_s above
        # attributes it); the ref config times the standalone corpus pass
        # the in-memory build uses
        from tpu_ir.index import docstore as ds

        if not streaming and not args.build_only:
            t0 = time.perf_counter()
            ds.build_docstore([corpus], index_dir)
            phases["docstore_build_s"] = round(time.perf_counter() - t0, 3)
        if ds.available(index_dir):
            st = ds.stats(index_dir)
            phases["docstore_raw_bytes"] = st["raw_bytes"]
            phases["docstore_stored_bytes"] = st["stored_bytes"]

        if args.build_only:
            out = {
                "metric": "docs_per_sec_indexed",
                "value": round(docs_per_sec, 1),
                "unit": "docs/s",
                "vs_baseline": round(docs_per_sec / BASELINE_DOCS_PER_SEC,
                                     2),
                "index_wall_s": round(build_s, 2),
                "index_wall_s_runs": [round(r, 2) for r in runs],
                "backend": backend,
                "config": args.config,
                "build_only": True,
                **phases,
                **profile_breakdown(),
            }
            _append_history(out)
            print(json.dumps(out))
            return 0

        # self-attribution controls (VERDICT r2 next #1): transport
        # fingerprint + transport-independent device-only build + a
        # CPU-backend build of the same code — together they say whether
        # a cross-round throughput swing is transport or a regression
        controls: dict = {}
        if not args.no_controls:
            try:
                controls.update(transport_probe())
                # the whole-corpus single-program control only matches the
                # in-memory builder's real shape at ref scale; at wiki
                # scale it would dispatch one ~200M-element program the
                # streaming builder never runs
                if args.config == "ref":
                    controls.update(device_build_control(corpus))
                    if not args.cpu:
                        controls.update(_cpu_control_subprocess())
                elif streaming:
                    # wiki scale: measure the per-chip device ceiling on
                    # a ref-scale PROBE corpus (the streaming build never
                    # runs the whole-corpus single program) and
                    # extrapolate from it (see v48_extrapolation below)
                    probe = os.path.join(tmp, "probe.trec")
                    make_corpus(probe, n_docs=DOC_COUNT_REF,
                                target_bytes=23_950_858, vocab_size=30_000)
                    controls.update(device_build_control(probe))
            except Exception as e:  # noqa: BLE001 — controls are evidence,
                controls["controls_error"] = str(e)[:300]  # not the metric

        # post-build verification gate (VERDICT r1 item 5): the vectorized
        # structural check must hold — and stay fast — at every bench scale
        from tpu_ir.index.verify import verify_index

        t0 = time.perf_counter()
        verify_index(index_dir)  # AssertionError fails the bench loudly
        verify_s = time.perf_counter() - t0

        # cold load: builds the serving-tiered disk cache (tiered corpora);
        # warm load: a REAL process restart against the populated cache —
        # the steady-state serving cold start (VERDICT r1 item 3's metric),
        # including jax init. Measuring it in this process would overlay
        # the new scorer's multi-GB uploads on the one already resident.
        def _await_device(s):
            jax.block_until_ready(serving_arrays(s))

        # serving + query measurements: a failure here is recorded in the
        # row (the timed build is kept) and the bench then exits 1.
        # AssertionError is raised at once (verify/recall correctness).
        load_cold_s = query_s = -1.0
        cold_breakdown = {}
        warm = {}
        lat_ms = np.array([-1.0])
        recall = -1.0
        queries_per_sec = -1.0
        serving_error = None
        try:
            t0 = time.perf_counter()
            scorer = Scorer.load(index_dir, layout="auto")
            _await_device(scorer)
            load_cold_s = time.perf_counter() - t0
            # the cold load's own stage split (verify/read/assemble/h2d),
            # snapshotted before anything else can observe load.* —
            # nothing earlier in this process runs a Scorer.load
            cold_breakdown = load_stage_breakdown()
            warm = _warm_load_subprocess(index_dir, cpu=args.cpu)
            # serving-cache accounting (VERDICT r4 next #7): the cold
            # load above built + persisted the full tier layout, so a
            # warm load's floor is uploading these bytes. Recording the
            # cache size next to the warm child's OWN h2d probe makes
            # "warm load ~= upload time" checkable from the artifact:
            # warm_upload_bound_s is that floor at the measured bandwidth.
            cache_dir = os.path.join(index_dir, "serving-tiered")
            if os.path.isdir(cache_dir):
                cache_bytes = sum(
                    os.path.getsize(os.path.join(cache_dir, f))
                    for f in os.listdir(cache_dir))
                warm["serving_cache_bytes"] = cache_bytes
                if warm.get("warm_h2d_mbps", -1) > 0:
                    # the probe reports MiB/s (32 MiB buffer / seconds),
                    # so the floor divides by MiB too
                    warm["warm_upload_bound_s"] = round(
                        cache_bytes / (warm["warm_h2d_mbps"] * (1 << 20)),
                        2)
            rng = np.random.default_rng(1)
            v = scorer.meta.vocab_size
            q_ids = rng.integers(0, v, size=(args.queries, 2)).astype(
                np.int32)

            # compile once at the measured shape, then measure (topk
            # returns host arrays, so completion is synchronous)
            scorer.topk(q_ids, k=10)
            t0 = time.perf_counter()
            scores, docnos = scorer.topk(q_ids, k=10)
            query_s = time.perf_counter() - t0

            # single-query latency (REPL-shaped load): one [1, L] query
            # per topk call, p50/p99 over 50 calls (the reference REPL's
            # per-query cost was dict lookup + disk seek per term;
            # never measured)
            rows = np.stack([q_ids[i % len(q_ids)] for i in range(50)])
            scorer.topk(rows[:1], k=10)  # compile the B=1 shape
            if scorer.layout == "sparse" and scorer.prune:
                # topk selects a B=1 kernel variant per row CONTENT
                # (hot-free -> static skip kernel, hot -> full); warm
                # every class the timed rows will hit so no compile
                # lands inside the loop
                hh = scorer._has_hot(rows)
                for cls in (False, True):
                    idx = np.flatnonzero(hh == cls)
                    if len(idx):
                        scorer.topk(rows[idx[0]][None, :], k=10)
            lat = []
            for row in rows:
                t0 = time.perf_counter()
                scorer.topk(row[None, :], k=10)
                lat.append(time.perf_counter() - t0)
            lat_ms = np.sort(np.array(lat)) * 1e3

            # recall@10 vs an exhaustive numpy oracle on a query sample
            # (BASELINE.json: "recall@10 vs CPU reference")
            sample = {"ref": 64, "wiki1m": 4}.get(args.config, 8)
            recall = _recall_at_10(scorer, q_ids[:sample], docnos[:sample])
            queries_per_sec = args.queries / query_s

            # device-only query control + MaxScore prune A/B (tiered
            # layouts; VERDICT r4 next #1's "measured reduction in the
            # device-only query control")
            if not args.no_controls:
                try:
                    controls.update(device_query_control(scorer, q_ids))
                except Exception as e:  # noqa: BLE001 — evidence only
                    controls["query_control_error"] = str(e)[:300]
                if streaming:
                    controls.update(v48_extrapolation(
                        controls, phases, DOC_COUNT,
                        n_queries=args.queries))
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 — record, don't discard
            serving_error = f"{type(e).__name__}: {e}"
            print(f"bench: serving/query phase failed after a successful "
                  f"build: {serving_error}", file=sys.stderr)

    # per-stage latency breakdown from the unified telemetry layer:
    # span-derived histograms recorded during this process's build and
    # query phases (build.* per pipeline phase, kernel/dispatch per
    # query block) — BENCH_*.json finally carries WHERE time went, not
    # just the headline throughput
    from tpu_ir.obs import get_registry, querylog

    stage_latency = {
        name: {k: s[k] for k in ("count", "p50_ms", "p95_ms", "p99_ms")}
        for name, s in sorted(
            get_registry().snapshot()["histograms"].items())
        if s["count"]}
    # the query-log view of the bench's own query phases: recorded
    # entries and how many tripped the slow-query trap (ISSUE 8) — a
    # bench row that ran with TPU_IR_SLOW_QUERY_MS set shows offenders
    ql = querylog.summary()

    out = {
        "metric": "docs_per_sec_indexed",
        "value": round(docs_per_sec, 1),
        "stage_latency": stage_latency,
        "querylog_recorded": ql["recorded"],
        "slow_queries": ql["slow_trapped"],
        "unit": "docs/s",
        "vs_baseline": round(docs_per_sec / BASELINE_DOCS_PER_SEC, 2),
        "index_wall_s": round(build_s, 2),
        "index_wall_s_runs": [round(r, 2) for r in runs],
        "corpus_bytes": nbytes,
        "corpus_docs": DOC_COUNT,
        "queries_per_sec": round(queries_per_sec, 1),
        "query_batch": args.queries,
        "query_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "query_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "scorer_load_cold_s": round(load_cold_s, 2),
        # cold-load stage split (load.* histograms: verify/read/assemble/
        # h2d seconds + effective h2d MB/s) — the cold-start trajectory
        # is tracked in BENCH_HISTORY like throughput (ISSUE 5)
        **cold_breakdown,
        # warm load split: total = process-fixed (python+jax init,
        # paid by ANY jax program) + the index load proper
        **warm,
        "verify_s": round(verify_s, 2),
        "recall_at_10": recall,
        # device-cost profiling (ISSUE 7): whole-process compile wall,
        # recompile count, per-dispatch device time split out of
        # device_rtt_ms, and peak HBM — cold-run side of the pair (the
        # warm_ twins above come from the restart child)
        **profile_breakdown(),
        "backend": backend,
        "config": args.config,
        **(pass_metrics(phases, build_s) if streaming else {}),
        **phases,
        **controls,
    }
    if serving_error is not None:
        out["serving_error"] = serving_error[:300]
    _append_history(out)
    print(json.dumps(out))
    return 1 if serving_error is not None else 0


if __name__ == "__main__":
    sys.exit(main())
