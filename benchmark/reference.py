"""The plain reference and the comparison that decides `correct`.

A copy of chip_smoke.py's `Reference` and `check_topk` (PR 21), kept
here so that a change to the program cannot change the yardstick. It
imports nothing of the program and reads nothing the program made: the
postings it scores are the generator's own (benchmark/msmarco.py,
benchmark/trec.py).

Exact float64 scoring over CSR postings: TF-IDF (1 + ln tf) *
log10(N/df) and Okapi BM25 (idf ln(1 + (N - df + .5)/(df + .5)),
saturation tf (k1 + 1)/(tf + k1 (1 - b + b dl/avgdl))).

`Control` is the same reference put in the program's place one
precision below the configuration's float32: every per-posting weight
rounded to bfloat16 before the float32 sum, as one bf16 pass of the
hot-strip matmul would do (PERF.md "How correct is decided").
"""

from __future__ import annotations

import numpy as np

# relative score error an f32 pipeline meets with margin and any bf16
# rounding on the path breaks (PERF.md: set from the readings of sound
# runs and of the bf16 control)
SCORE_TOL = 1e-5


class Reference:
    """Exact float64 scorer over CSR postings (per-term runs of docnos,
    docnos 1-based, df[t] postings for term t)."""

    def __init__(self, df, pair_doc, pair_tf, num_docs, *, k1=0.9, b=0.4):
        self.n = n = int(num_docs)
        self.k1, self.b = float(k1), float(b)
        self.df = np.asarray(df, np.int64)
        self.indptr = np.concatenate([[0], np.cumsum(self.df)])
        self.doc = np.asarray(pair_doc, np.int64)
        self.tf = np.asarray(pair_tf, np.float64)
        dff = self.df.astype(np.float64)
        with np.errstate(divide="ignore"):
            self.idf = np.where(self.df > 0,
                                np.log10(n / np.maximum(dff, 1.0)), 0.0)
        self.bm25_idf = np.where(
            self.df > 0, np.log(1.0 + (n - dff + 0.5) / (dff + 0.5)), 0.0)
        self.doc_len = np.bincount(self.doc, weights=self.tf,
                                   minlength=n + 1)
        self.dl_norm = (1.0 - self.b
                        + self.b * self.doc_len / (self.doc_len.sum() / n))

    def weights(self, t: int, scoring: str):
        """(docnos, float64 weights) of term t's postings."""
        sl = slice(self.indptr[t], self.indptr[t + 1])
        d, tf = self.doc[sl], self.tf[sl]
        if scoring == "tfidf":
            return d, (1.0 + np.log(tf)) * self.idf[t]
        if scoring == "bm25":
            return d, self.bm25_idf[t] * tf * (self.k1 + 1.0) / (
                tf + self.k1 * self.dl_norm[d])
        raise ValueError(f"unknown scoring {scoring!r}")

    def scores(self, row, scoring: str) -> np.ndarray:
        """Dense float64 scores [N+1] of one query (term ids, -1 pads)."""
        out = np.zeros(self.n + 1)
        for t in row:
            if 0 <= t < len(self.df) and self.df[t]:
                d, w = self.weights(int(t), scoring)
                out[d] += w  # docnos are unique within one term's run
        return out


class Control:
    """The reference one precision down (bfloat16 weights, float32
    sums), answering top-k in the program's place."""

    def __init__(self, ref: Reference):
        self.ref = ref

    def topk(self, row, k: int, scoring: str):
        import ml_dtypes

        out = np.zeros(self.ref.n + 1, np.float32)
        for t in row:
            if 0 <= t < len(self.ref.df) and self.ref.df[t]:
                d, w = self.ref.weights(int(t), scoring)
                out[d] += w.astype(ml_dtypes.bfloat16).astype(np.float32)
        pos = np.flatnonzero(out > 0)
        top = pos[np.argsort(-out[pos], kind="stable")[:k]]
        return [(int(d), float(out[d])) for d in top]


class Tally:
    """What a run's comparison found: answers checked, answers wrong,
    and the widest relative score error among the answers checked."""

    def __init__(self):
        self.checked = 0
        self.wrong = 0
        self.max_rel = 0.0
        self.first: list[str] = []

    def fail(self, msg: str) -> None:
        self.wrong += 1
        if len(self.first) < 5:
            self.first.append(msg)

    def checks(self) -> dict:
        """The numbers compared, each beside its limit."""
        return {"answers_wrong": {"value": self.wrong, "limit": 0},
                "max_rel_score_error": {"value": self.max_rel,
                                        "limit": SCORE_TOL}}

    def correct(self) -> bool:
        return (self.checked > 0 and self.wrong == 0
                and self.max_rel <= SCORE_TOL)


def check_topk(ref_scores, got, k: int, tally: Tally, tag: str) -> None:
    """`got` = [(docno, score)] from the system; `ref_scores` the dense
    reference [N+1]. Doc sets equal apart from ties at the k-th score;
    the widest relative score error is kept for the limit."""
    tally.checked += 1
    pos = np.flatnonzero(ref_scores > 0)
    expect = min(k, len(pos))
    docs = np.array([d for d, _ in got], np.int64)
    vals = np.array([s for _, s in got], np.float64)
    if len(docs) != expect:
        tally.fail(f"{tag}: {len(docs)} results, reference has {expect}")
        return
    if expect == 0:
        return
    if len(set(docs.tolist())) != len(docs):
        tally.fail(f"{tag}: duplicate docnos")
        return
    if docs.min() < 1 or docs.max() > len(ref_scores) - 1:
        tally.fail(f"{tag}: docno out of range")
        return
    want = ref_scores[docs]
    rel = np.abs(vals - want) / np.maximum(np.abs(want), 1e-300)
    tally.max_rel = max(tally.max_rel, float(rel.max()))
    kth = np.partition(ref_scores[pos], len(pos) - expect)[len(pos) - expect]
    if want.min() < kth * (1 - SCORE_TOL):
        tally.fail(f"{tag}: returned a doc scoring {want.min()!r} below "
                   f"the reference k-th score {kth!r}")
        return
    must = pos[ref_scores[pos] > kth * (1 + SCORE_TOL)]
    missing = np.setdiff1d(must, docs)
    if len(missing):
        tally.fail(f"{tag}: {len(missing)} docs above the k-th score "
                   f"missing (e.g. {missing[0]})")
