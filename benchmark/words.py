"""Shared pieces of the generators: seeded streams, analyzer-stable
words, a Zipf-Mandelbrot term draw, and postings from a token stream.

Every content word is five lower-case letters (the word's index in
base 26) and one digit. A token of [a-z0-9] ending in a digit passes
the tag tokenizer untouched, is in no stopword list, and matches no
Porter2 suffix rule, so the analyzer maps it to itself: the reference
can count postings without the program's analyzer, and the words'
sorted order is their index order. benchmark/tests checks this against
the program's analyzer.
"""

from __future__ import annotations

import numpy as np

# words the analyzer drops (all in the Terrier stopword list; checked by
# benchmark/tests/test_generators.py)
STOPWORDS = ("what", "is", "the", "of", "how", "to", "a", "in", "does",
             "for", "are", "do", "and", "an", "when", "which", "where",
             "why", "was", "it", "on", "by", "with", "that", "this",
             "from", "be", "as", "at", "or", "has")

_LETTERS = 5


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one named use of a run's seed."""
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def word_strings(idx: np.ndarray) -> list[str]:
    """Analyzer-stable word for each word index (< 26**5 * 10)."""
    idx = np.asarray(idx, np.int64)
    out = np.empty((len(idx), _LETTERS + 1), np.uint8)
    rest = idx // 10
    for j in range(_LETTERS - 1, -1, -1):
        out[:, j] = ord("a") + rest % 26
        rest //= 26
    out[:, _LETTERS] = ord("0") + idx % 10
    return out.view(f"S{_LETTERS + 1}").ravel().astype(str).tolist()


def zipf_ranks(g: np.random.Generator, n: int, support: int, s: float,
               q: float) -> np.ndarray:
    """`n` 0-based ranks from a Zipf-Mandelbrot law p(r) ~ (r + q)^-s
    over `support` ranks (s != 1), drawn by its continuous inverse CDF."""
    u = g.random(n)
    a = (1.0 + q) ** (1.0 - s)
    b = (support + 1.0 + q) ** (1.0 - s)
    x = (a - u * (a - b)) ** (1.0 / (1.0 - s)) - q
    return np.clip(np.floor(x).astype(np.int64) - 1, 0, support - 1)


class Scramble:
    """Word index of each rank: a seeded bijection of [0, 2**26), so
    that a term's id says nothing of its frequency, and each seed gives
    the same terms other words (and so other term ids)."""

    def __init__(self, g: np.random.Generator):
        self.mult = int(g.integers(1, 2**25)) * 2 + 1
        self.off = int(g.integers(2**26))

    def __call__(self, ranks: np.ndarray) -> np.ndarray:
        return (np.asarray(ranks, np.int64) * self.mult + self.off) & (
            2**26 - 1)


def permute_docs(g: np.random.Generator, lengths: np.ndarray,
                 tokens: np.ndarray, *per_doc):
    """The same documents in a seeded order: (lengths, tokens, *per_doc)
    with document i of the result the perm[i]-th of the input."""
    perm = g.permutation(len(lengths))
    starts = np.concatenate([[0], np.cumsum(lengths)])[:-1]
    new_len = lengths[perm]
    new_starts = np.concatenate([[0], np.cumsum(new_len)])[:-1]
    idx = (np.arange(int(new_len.sum()))
           + np.repeat(starts[perm] - new_starts, new_len))
    return (new_len, tokens[idx]) + tuple(a[perm] for a in per_doc)


def draw_ranks(g: np.random.Generator, counts, shape: dict,
               present: np.ndarray) -> list[np.ndarray]:
    """For each query, `counts[i]` distinct ranks drawn from the term law
    (by collection frequency), each among the `present` ranks (sorted)."""
    out = []
    for m in counts:
        picked: list[int] = []
        while len(picked) < m:
            r = int(zipf_ranks(g, 1, int(shape["rank_support"]),
                               shape["zipf_s"], shape["zipf_q"])[0])
            i = np.searchsorted(present, r)
            if i < len(present) and present[i] == r and r not in picked:
                picked.append(r)
        out.append(np.array(picked, np.int64))
    return out


def lognormal_lengths(g: np.random.Generator, n: int, mean: float,
                      sigma: float, lo: int, hi: int) -> np.ndarray:
    """Token counts with the given mean (before clipping)."""
    mu = np.log(mean) - sigma * sigma / 2
    return np.clip(np.rint(g.lognormal(mu, sigma, n)), lo, hi).astype(
        np.int64)


class Postings:
    """CSR postings of a token stream: terms in sorted order, each term's
    run ordered by tf descending then docno (the program's posting
    order). `words` are the word indices of the terms, so term id =
    position in `words`."""

    def __init__(self, word_idx: np.ndarray, lengths: np.ndarray):
        n_docs = len(lengths)
        doc_bits = int(n_docs + 1).bit_length()
        doc = np.repeat(np.arange(1, n_docs + 1, dtype=np.int64), lengths)
        key = (np.asarray(word_idx, np.int64) << doc_bits) | doc
        key.sort()
        starts = np.flatnonzero(np.diff(key, prepend=-1))
        tf = np.diff(np.append(starts, len(key)))
        uk = key[starts]
        pword = uk >> doc_bits
        pdoc = uk & ((1 << doc_bits) - 1)
        tstart = np.flatnonzero(np.diff(pword, prepend=-1))
        self.words = pword[tstart]
        self.df = np.diff(np.append(tstart, len(pword))).astype(np.int64)
        term = np.repeat(np.arange(len(self.words), dtype=np.int64),
                         self.df)
        tf_bits = int(tf.max()).bit_length()
        order = np.argsort(
            (term << (tf_bits + doc_bits))
            | (((1 << tf_bits) - 1 - tf) << doc_bits) | pdoc)
        self.doc = pdoc[order].astype(np.int32)
        self.tf = tf[order].astype(np.int32)
        self.num_docs = n_docs
        self.doc_len = np.zeros(n_docs + 1, np.int64)
        self.doc_len[1:] = lengths

    def term_ids(self, word_idx: np.ndarray) -> np.ndarray:
        """Term id of each word index (all must be present)."""
        return np.searchsorted(self.words, word_idx)


def query_text(g: np.random.Generator, content: list[str],
               n_stop: int) -> str:
    """Content words and `n_stop` stopwords in a shuffled order."""
    words = content + [STOPWORDS[i] for i in
                       g.integers(len(STOPWORDS), size=n_stop)]
    return " ".join(words[i] for i in g.permutation(len(words)))
