"""A plain reference for the phrase tests: ordered windows found by
scanning each document's raw token stream, the matched docs scored in
float64 with BM25 or TF-IDF over every query term occurrence, best
first, ties by docno, zero scores kept. It imports nothing of the
program; benchmark/phrase_reference.py holds a copy of the class for
the benchmark's comparison."""

from __future__ import annotations

import numpy as np


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
