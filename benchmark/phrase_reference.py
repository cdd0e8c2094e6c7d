"""The plain reference of the phrase cell and its comparison.

`PhraseReference` finds ordered windows by scanning the documents' raw
token streams and scores the matched docs in float64. It imports
nothing of the program and reads nothing the program made: the tokens
are the generator's own (benchmark/archive.py). tests/phrase_reference.py
holds a copy of the class for the program's tests.

Semantics (Indri/Galago #1 at slop 0, an ordered window in general): a
doc matches a span t_1..t_m when it holds positions p_1 < ... < p_m of
those terms with p_m - p_1 <= (m - 1) + slop; a query's answer is the
docs that match every span, ranked by BM25 (or TF-IDF) over all the
query's term occurrences, ties by docno; zero scores are kept.

`check_phrase` decides `correct` as reference.check_topk does, over the
matched docs only; `control_topk` is the reference one precision down
(bfloat16 per-term weights, float32 sums), which has to fail it.
"""

from __future__ import annotations

import numpy as np

from .reference import SCORE_TOL, Tally


class PhraseReference:
    """Exact phrase search over doc-major token ids (`lengths` tokens per
    doc, docnos 1-based)."""

    def __init__(self, tokens, lengths, *, k1=0.9, b=0.4):
        self.tokens = np.asarray(tokens)
        self.lengths = np.asarray(lengths, np.int64)
        self.starts = np.concatenate([[0], np.cumsum(self.lengths)])
        self.n = len(self.lengths)
        self.k1, self.b = float(k1), float(b)
        self.avgdl = float(self.lengths.sum()) / max(self.n, 1)
        self._df = None

    def doc_of(self, at: np.ndarray) -> np.ndarray:
        """Docno of each token index."""
        return np.searchsorted(self.starts, at, side="right")

    def matches(self, span, slop: int = 0) -> np.ndarray:
        """Sorted docnos holding `span` as an ordered window."""
        span = [int(t) for t in span]
        m = len(span)
        first = np.flatnonzero(self.tokens == span[0])
        if slop == 0:
            first = first[first + m - 1 < len(self.tokens)]
            for j in range(1, m):
                first = first[self.tokens[first + j] == span[j]]
            d = self.doc_of(first)
            return np.unique(d[d == self.doc_of(first + m - 1)])
        out = []
        for d in np.unique(self.doc_of(first)):
            toks = self.tokens[self.starts[d - 1]:self.starts[d]]
            for p0 in np.flatnonzero(toks == span[0]):
                cur = int(p0)
                for t in span[1:]:
                    nxt = np.flatnonzero(toks[cur + 1:] == t)
                    if not len(nxt):
                        cur = None
                        break
                    cur += 1 + int(nxt[0])
                if cur is not None and cur - p0 <= m - 1 + slop:
                    out.append(d)
                    break
        return np.array(out, np.int64)

    def df(self) -> dict:
        """{token id: docs holding it}, counted once."""
        if self._df is None:
            doc = np.repeat(np.arange(1, self.n + 1), self.lengths)
            pairs = np.unique(self.tokens.astype(np.int64) * (self.n + 1)
                              + doc)
            t, c = np.unique(pairs // (self.n + 1), return_counts=True)
            self._df = dict(zip(t.tolist(), c.tolist()))
        return self._df

    def weights(self, t: int, docs: np.ndarray, scoring: str) -> np.ndarray:
        """float64 weight of term t in each of `docs` (0 where absent)."""
        lens = self.lengths[docs - 1]
        seg = np.concatenate([[0], np.cumsum(lens)])
        at = np.repeat(self.starts[docs - 1] - seg[:-1], lens) + np.arange(
            int(seg[-1]))
        tf = np.add.reduceat((self.tokens[at] == t).astype(np.float64),
                             seg[:-1]) if len(docs) else np.zeros(0)
        df = float(self.df().get(int(t), 0))
        if not df:
            return np.zeros(len(docs))
        if scoring == "bm25":
            idf = np.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
            norm = 1.0 - self.b + self.b * lens / self.avgdl
            w = idf * tf * (self.k1 + 1.0) / (tf + self.k1 * norm)
        elif scoring == "tfidf":
            w = (1.0 + np.log(np.maximum(tf, 1.0))) * np.log10(self.n / df)
        else:
            raise ValueError(f"unknown scoring {scoring!r}")
        return np.where(tf > 0, w, 0.0)

    def answer(self, spans, terms, scoring: str, slop: int = 0):
        """(docnos, float64 scores) of every doc matching all `spans`,
        scored over `terms` (every occurrence), best first, ties by
        docno."""
        docs = None
        for span in spans:
            got = self.matches(span, slop)
            docs = got if docs is None else np.intersect1d(docs, got)
        scores = np.zeros(len(docs))
        for t in terms:
            scores += self.weights(int(t), docs, scoring)
        order = np.lexsort((docs, -scores))
        return docs[order], scores[order]


def check_phrase(ref_docs, ref_scores, got, k: int, tally: Tally,
                 tag: str) -> None:
    """`got` = [(docno, score)] from the system; `ref_docs` /
    `ref_scores` every matched doc from PhraseReference.answer. The
    returned docs are matched docs, as many as min(k, matched); the doc
    set equals the reference's top-k apart from ties at the k-th score,
    and the widest relative score error is kept for the limit."""
    tally.checked += 1
    expect = min(k, len(ref_docs))
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
    srt = np.argsort(ref_docs)
    at = np.minimum(np.searchsorted(ref_docs[srt], docs), len(srt) - 1)
    if (ref_docs[srt][at] != docs).any():
        tally.fail(f"{tag}: returned a doc that does not hold the phrase")
        return
    want = ref_scores[srt][at]
    rel = np.abs(vals - want) / np.maximum(np.abs(want), 1e-300)
    tally.max_rel = max(tally.max_rel, float(rel.max()))
    kth = ref_scores[expect - 1]
    if want.min() < kth * (1 - SCORE_TOL):
        tally.fail(f"{tag}: returned a doc scoring {want.min()!r} below "
                   f"the reference k-th score {kth!r}")
        return
    missing = np.setdiff1d(ref_docs[ref_scores > kth * (1 + SCORE_TOL)],
                           docs)
    if len(missing):
        tally.fail(f"{tag}: {len(missing)} docs above the k-th score "
                   f"missing (e.g. {missing[0]})")


def control_topk(ref: PhraseReference, spans, terms, k: int,
                 scoring: str, slop: int = 0):
    """The reference one precision down: each term's weights rounded to
    bfloat16 and summed in float32, answering in the program's place."""
    import ml_dtypes

    docs, _ = ref.answer(spans, [], scoring, slop)
    docs = np.sort(docs)
    scores = np.zeros(len(docs), np.float32)
    for t in terms:
        scores += ref.weights(int(t), docs, scoring).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    order = np.lexsort((docs, -scores))[:k]
    return [(int(docs[i]), float(scores[i])) for i in order]
