"""A seeded Robust04-shaped news archive with planted collocations, its
positional index written through the program's own format writers, and
quoted-phrase title queries.

Documents are analyzed-token streams (benchmark/words.py words, which
the analyzer keeps as they are), so a token's position in its document
is the program's position coordinate. Lengths and the term law are
robust04's (benchmark/configs/robust04-phrase.json). A table of 2-3-term
collocations, drawn by collection frequency, is planted into the
documents by overwriting tokens, each entry as often as a
Zipf-Mandelbrot popularity says. Every number the source does not
publish is listed in the configuration under `assumed`.

Writers used (index/format.py, index/positions.py, collection/):
DocnoMapping.save, Vocab.save, write_pair_shards, write_dictionary,
write_position_shards and IndexMetadata.save_with_checksums, the calls
the in-memory builder makes for a positional index (format v2).
"""

from __future__ import annotations

import os

import numpy as np

from . import words as W


def plant_counts(col: dict, scale: float) -> np.ndarray:
    """Plants per table entry: max_plants * (1 + i / q)^-s for entry i,
    q = table * q_share, times the archive's share of the collection."""
    i = np.arange(int(col["table"]), dtype=np.float64)
    q = col["table"] * col["q_share"]
    c = col["max_plants"] * (1.0 + i / q) ** -col["s"] * scale
    return np.maximum(np.rint(c), 1).astype(np.int64)


class Archive:
    """The archive's documents (as term ids), postings and positions.

    Lengths, the term ranks, the collocation table and its plants come
    from the configuration's `data_seed`, so every seed holds the same
    multiset of documents and answers the same work; the run's seed
    gives the documents another order and the terms other words (so
    other docnos and term ids)."""

    def __init__(self, cfg: dict, seed: int):
        gd, g = W.rng(cfg["data_seed"], 21), W.rng(seed, 21)
        n = int(cfg["documents"])
        shape, col = cfg["shape"], cfg["collocations"]
        lengths = W.lognormal_lengths(
            gd, n, shape["tokens_per_doc_mean"], shape["tokens_per_doc_sigma"],
            1, shape["tokens_per_doc_max"])
        ranks = W.zipf_ranks(gd, int(lengths.sum()),
                             int(shape["rank_support"]), shape["zipf_s"],
                             shape["zipf_q"])
        starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
        # the collocation table, its plants, and the plants overwriting
        # tokens at uniform places in uniform documents
        size = int(col["table"])
        m = np.where(gd.random(size) < col["two_term_share"], 2, 3)
        table = np.full((size, 3), -1, np.int64)
        for i, r in enumerate(W.draw_ranks(gd, m, shape, np.unique(ranks))):
            table[i, : len(r)] = r
        counts = plant_counts(col, n / cfg["published"]["documents"])
        entry = np.repeat(np.arange(size), counts)
        fits = np.flatnonzero(lengths >= 3)
        doc = fits[gd.integers(len(fits), size=len(entry))]
        off = np.floor(gd.random(len(entry))
                       * (lengths[doc] - m[entry] + 1)).astype(np.int64)
        for j in range(3):
            sel = m[entry] > j
            ranks[starts[doc[sel]] + off[sel] + j] = table[entry[sel], j]
        self.table, self.table_len, self.plants = table, m, counts
        self.data_lengths, self.data_ranks = lengths, ranks
        self.present = np.unique(ranks)
        self.lengths, ranks = W.permute_docs(g, lengths, ranks)
        self.scramble = W.Scramble(g)
        words = self.scramble(ranks)
        self.words = np.unique(words)          # term id = index here
        self.tokens = np.searchsorted(self.words, words).astype(np.int32)
        self.num_docs = n
        self.cfg = cfg

    def term_ids(self, ranks: np.ndarray) -> np.ndarray:
        """Term id of each rank (all must be present)."""
        return np.searchsorted(self.words, self.scramble(ranks))

    def docids(self) -> list[str]:
        return [f"robust04-{i:07d}" for i in range(self.num_docs)]

    def index_arrays(self):
        """Postings and position runs in the program's pair order (term
        ascending, tf descending, docno ascending): (df, pair_doc,
        pair_tf, run_term, pos_indptr, pos_delta)."""
        n_tok = len(self.tokens)
        doc = np.repeat(np.arange(1, self.num_docs + 1, dtype=np.int64),
                        self.lengths)
        pos = np.arange(n_tok, dtype=np.int64) - np.repeat(
            np.concatenate([[0], np.cumsum(self.lengths)[:-1]]),
            self.lengths)
        db = int(self.num_docs + 1).bit_length()
        pb = int(self.lengths.max()).bit_length()
        key = ((self.tokens.astype(np.int64) << (db + pb)) | (doc << pb)
               | pos)
        del doc, pos
        key.sort()
        pair_key = key >> pb
        first = np.flatnonzero(np.diff(pair_key, prepend=-1))
        tf = np.diff(np.append(first, n_tok))
        pt = pair_key[first] >> db
        pd = pair_key[first] & ((1 << db) - 1)
        tb = int(tf.max()).bit_length()
        order = np.argsort((pt << (tb + db)) | ((tf.max() - tf) << db) | pd)
        run_tf = tf[order]
        indptr = np.concatenate([[0], np.cumsum(run_tf)])
        gather = (np.repeat(first[order] - indptr[:-1], run_tf)
                  + np.arange(n_tok))
        p = key[gather] & ((1 << pb) - 1)
        delta = np.diff(p, prepend=0)
        delta[indptr[:-1]] = p[indptr[:-1]]
        df = np.bincount(pt, minlength=len(self.words))
        return (df.astype(np.int32), pd[order].astype(np.int32),
                run_tf.astype(np.int32), pt[order].astype(np.int32),
                indptr.astype(np.int64), delta.astype(np.int32))

    def write_index(self, index_dir: str) -> dict:
        """Write the archive as a program index with positions (format
        v2 arenas); returns its sizes."""
        from tpu_ir.collection.docno import DocnoMapping
        from tpu_ir.collection.vocab import Vocab
        from tpu_ir.index import format as fmt
        from tpu_ir.index.positions import write_position_shards

        os.makedirs(index_dir, exist_ok=True)
        df, pair_doc, pair_tf, run_term, indptr, delta = self.index_arrays()
        self.df = df
        terms = W.word_strings(self.words)
        DocnoMapping(self.docids()).save(os.path.join(index_dir, fmt.DOCNOS))
        Vocab(terms).save(os.path.join(index_dir, fmt.VOCAB))
        doc_len = np.zeros(self.num_docs + 1, np.int32)
        doc_len[1:] = self.lengths
        np.save(os.path.join(index_dir, fmt.DOCLEN), doc_len)
        parts = int(self.cfg["index_parts"])
        shard_of, offset_of = fmt.write_pair_shards(index_dir, df, pair_doc,
                                                    pair_tf, parts)
        fmt.write_dictionary(index_dir, terms, shard_of, offset_of)
        write_position_shards(index_dir, run_term, indptr, delta, parts)
        meta = fmt.IndexMetadata(
            num_docs=self.num_docs, vocab_size=len(terms), k=1,
            num_shards=parts, num_pairs=int(len(pair_doc)), chargram_ks=[],
            version=2, has_positions=True,
            format_version=fmt.resolve_format_version())
        meta.save_with_checksums(index_dir)
        return {"documents": self.num_docs, "vocabulary": len(terms),
                "postings": int(len(pair_doc)), "positions": len(self.tokens),
                "max_df": int(df.max()), "table": len(self.table),
                "plants": int(self.plants.sum())}


class PhraseQueries:
    """`n` title queries, each one quoted span plus 0-2 plain terms.

    A span is a collocation of the table, drawn by its plants, or (in
    `window_share` of queries) the tokens of a random document's window
    that 1-10 documents hold. Plain terms are drawn by collection
    frequency. The set (as ranks) comes from the configuration's
    `data_seed`; the run's seed gives the terms their words, and the
    plain terms their places around the span."""

    def __init__(self, arch: Archive, traffic: dict, n: int, seed: int):
        from .phrase_reference import PhraseReference

        qc = traffic["queries"]
        gd, g = W.rng(arch.cfg["data_seed"], 22), W.rng(seed, 22)
        data_ref = PhraseReference(arch.data_ranks, arch.data_lengths)
        lo, hi = qc["window_matches"]
        p = arch.plants / arch.plants.sum()
        starts = np.concatenate([[0], np.cumsum(arch.data_lengths)[:-1]])
        fits = np.flatnonzero(arch.data_lengths >= 3)
        self.spans, self.plain, self.texts = [], [], []
        for _ in range(n):
            if gd.random() < qc["window_share"]:
                while True:
                    m = 2 if gd.random() < qc["two_term_share"] else 3
                    d = fits[gd.integers(len(fits))]
                    o = starts[d] + gd.integers(arch.data_lengths[d] - m + 1)
                    span = arch.data_ranks[o:o + m]
                    if lo <= len(data_ref.matches(span, 0)) <= hi:
                        break
            else:
                e = gd.choice(len(p), p=p)
                span = arch.table[e, : arch.table_len[e]]
            extra = W.draw_ranks(gd, [gd.integers(qc["max_plain_terms"] + 1)],
                                 arch.cfg["shape"], arch.present)[0]
            self.spans.append(arch.term_ids(span))
            self.plain.append(arch.term_ids(extra))
            words = W.word_strings(arch.scramble(span))
            plain = W.word_strings(arch.scramble(extra))
            at = int(g.integers(len(plain) + 1))
            self.texts.append(" ".join(
                plain[:at] + ['"' + " ".join(words) + '"'] + plain[at:]))

    def shuffle(self, g: np.random.Generator, block: int) -> None:
        """Reorder the queries within each run of `block` queries."""
        n = len(self.texts)
        perm = np.concatenate([s + g.permutation(min(block, n - s))
                               for s in range(0, n, block)])
        self.spans = [self.spans[i] for i in perm]
        self.plain = [self.plain[i] for i in perm]
        self.texts = [self.texts[i] for i in perm]
