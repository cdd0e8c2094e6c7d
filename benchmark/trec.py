"""A seeded Robust04-shaped slice of TREC text, its expected postings,
and title-shaped topics.

Documents are TREC SGML (<DOC>, <DOCNO>, <TEXT>) from the four Disks 4&5
sources in their published proportions; words are analyzer-stable
content words (benchmark/words.py) mixed with stopwords, so the
expected postings are counted here without the program's analyzer.
The build indexes the DOCNO line too: a docid such as `fbis3-0000123`
adds the two analyzer-stable terms `fbis3` and `0000123` to its
document, and the expected postings count them. Documents are generated
in sorted-docid order, so document i is docno i + 1.
"""

from __future__ import annotations

import numpy as np

from . import words as W

# a source tag per Disks 4&5 collection, each ending in a digit
_TAGS = {"FBIS": "fbis3", "FR94": "fr94", "FT": "ft9", "LA": "la0"}


class Slice:
    """The slice's documents, expected postings and words.

    Lengths, terms (as ranks of the term law), stopword counts and
    sources come from the configuration's `data_seed`, so every seed
    builds the same multiset (the same vocabulary size, a static shape
    of the build's device programs); the run's seed gives the documents
    another order, the terms other words, and the stopwords other
    places."""

    def __init__(self, cfg: dict, seed: int):
        gd, g = W.rng(cfg["data_seed"], 3), W.rng(seed, 3)
        n = int(cfg["documents"])
        shape = cfg["shape"]
        lengths = W.lognormal_lengths(
            gd, n, shape["tokens_per_doc_mean"],
            shape["tokens_per_doc_sigma"], 1, shape["tokens_per_doc_max"])
        ranks = W.zipf_ranks(gd, int(lengths.sum()),
                             int(shape["rank_support"]), shape["zipf_s"],
                             shape["zipf_q"])
        share = shape["stopword_share"]
        stops = gd.binomial(lengths, share / (1.0 - share))
        self.present = np.unique(ranks)
        self.lengths, ranks, self.stops = W.permute_docs(g, lengths, ranks,
                                                         stops)
        self.scramble = W.Scramble(g)
        self.tokens = self.scramble(ranks)
        sources = cfg["sources"]
        names = sorted(sources)
        p = np.array([sources[k] for k in names], np.float64)
        src = np.sort(gd.choice(len(names), n, p=p / p.sum()))
        tags = [_TAGS[names[s]] for s in src]
        nums = [f"{i:07d}" for i in range(n)]
        self.docids = [f"{t}-{m}" for t, m in zip(tags, nums)]
        self.cfg = cfg
        self.seed = seed
        # the vocabulary is every string the build indexes, sorted: term
        # id = rank; each document's stream is its content words, then
        # its two docid terms
        uniq = np.unique(self.tokens)
        content = np.array(W.word_strings(uniq))
        extra = np.array(sorted(set(tags)) + nums)
        self.terms = np.unique(np.concatenate([content, extra]))
        self._content_rank = np.searchsorted(self.terms, content)
        self._uniq = uniq
        rank_tok = self.word_ranks(self.tokens)
        doc_ranks = np.stack([np.searchsorted(self.terms, tags),
                              np.searchsorted(self.terms, nums)], axis=1)
        ends = np.cumsum(self.lengths)
        stream = np.insert(rank_tok, np.repeat(ends, 2),
                           doc_ranks.ravel())
        self.post = W.Postings(stream, self.lengths + 2)

    def word_ranks(self, word_idx: np.ndarray) -> np.ndarray:
        """Term id (vocabulary rank) of content word indices."""
        return self._content_rank[np.searchsorted(self._uniq, word_idx)]

    def write_text(self, path: str) -> int:
        """Write the slice as TREC text; returns bytes written."""
        g = W.rng(self.seed, 4)
        starts = np.concatenate([[0], np.cumsum(self.lengths)])
        content = self.terms.astype(object)[self.word_ranks(self.tokens)]
        stop = np.array(W.STOPWORDS, dtype=object)
        total = 0
        with open(path, "w") as f:
            for i, docid in enumerate(self.docids):
                words = np.concatenate([
                    content[starts[i]:starts[i + 1]],
                    stop[g.integers(len(stop), size=int(self.stops[i]))]])
                words = words[g.permutation(len(words))]
                lines = "\n".join(" ".join(words[j:j + 12])
                                  for j in range(0, len(words), 12))
                rec = (f"<DOC>\n<DOCNO> {docid} </DOCNO>\n<TEXT>\n"
                       f"{lines}\n</TEXT>\n</DOC>\n")
                f.write(rec)
                total += len(rec)
        return total

    def topics(self, n: int, seed: int) -> tuple[list[str], np.ndarray]:
        """`n` title-shaped topics of content words drawn by collection
        frequency, now and then with a stopword: (texts, term-id rows)."""
        gd, g = W.rng(self.cfg["data_seed"], 5), W.rng(seed, 5)
        tq = self.cfg["topics"]
        counts = gd.integers(tq["min_terms"], tq["max_terms"] + 1, n)
        drawn = W.draw_ranks(gd, counts, self.cfg["shape"], self.present)
        rows = np.full((n, int(tq["max_terms"])), -1, np.int64)
        texts = []
        for i, r in enumerate(drawn):
            w = self.scramble(r)
            rows[i, : len(w)] = self.word_ranks(w)
            texts.append(W.query_text(g, W.word_strings(w),
                                      int(g.random() < tq["stopword_prob"])))
        return texts, rows
