"""A seeded MS MARCO passage-shaped index shard, written through the
program's own format writers, and its query stream.

The shard is one chip's share of a doc-sharded deployment: passages
with pid % shards == shard, scored with the shard's own N and df (as
Elasticsearch's default query_then_fetch does); the reference scores
the same shard. Passage lengths, the vocabulary and the term law are
the configuration's (benchmark/configs/<config>.json), each number that
the source does not publish listed there under `assumed`.

Writers used (index/format.py, collection/): DocnoMapping.save,
Vocab.save, write_pair_shards, write_dictionary and
IndexMetadata.save_with_checksums, the calls index/merge.py makes.
"""

from __future__ import annotations

import os

import numpy as np

from . import words as W


class Shard:
    """The generated shard and its postings.

    The passages' lengths and terms (as ranks of the term law) are drawn
    from the configuration's `data_seed`, so every seed serves the same
    multiset of postings and compiles the same shapes; the run's seed
    gives the passages another order and the terms other words (so
    other docnos and term ids)."""

    def __init__(self, cfg: dict, seed: int):
        gd, g = W.rng(cfg["data_seed"], 1), W.rng(seed, 1)
        n = int(cfg["passages"])
        shape = cfg["shape"]
        lengths = W.lognormal_lengths(
            gd, n, shape["tokens_per_passage_mean"],
            shape["tokens_per_passage_sigma"], 1,
            shape["tokens_per_passage_max"])
        ranks = W.zipf_ranks(gd, int(lengths.sum()),
                             int(shape["rank_support"]), shape["zipf_s"],
                             shape["zipf_q"])
        self.present = np.unique(ranks)
        self.lengths, ranks = W.permute_docs(g, lengths, ranks)
        self.scramble = W.Scramble(g)
        self.post = W.Postings(self.scramble(ranks), self.lengths)
        shards, shard = int(cfg["deployment_shards"]), int(cfg["shard"])
        self.pids = np.arange(n, dtype=np.int64) * shards + shard
        self.cfg = cfg

    def docids(self) -> list[str]:
        return [f"{p:07d}" for p in self.pids.tolist()]

    def write_index(self, index_dir: str) -> None:
        """Write the shard as a program index (format v2 arenas)."""
        from tpu_ir.collection.docno import DocnoMapping
        from tpu_ir.collection.vocab import Vocab
        from tpu_ir.index import format as fmt

        os.makedirs(index_dir, exist_ok=True)
        p = self.post
        terms = W.word_strings(p.words)
        DocnoMapping(self.docids()).save(os.path.join(index_dir,
                                                      fmt.DOCNOS))
        Vocab(terms).save(os.path.join(index_dir, fmt.VOCAB))
        np.save(os.path.join(index_dir, fmt.DOCLEN),
                p.doc_len.astype(np.int32))
        num_shards = int(self.cfg["index_parts"])
        shard_of, offset_of = fmt.write_pair_shards(
            index_dir, p.df.astype(np.int32), p.doc, p.tf, num_shards)
        fmt.write_dictionary(index_dir, terms, shard_of, offset_of)
        meta = fmt.IndexMetadata(
            num_docs=p.num_docs, vocab_size=len(terms), k=1,
            num_shards=num_shards, num_pairs=int(len(p.doc)),
            chargram_ks=[], format_version=fmt.resolve_format_version())
        meta.save_with_checksums(index_dir)

    def docno(self, docid: str) -> int:
        shards, shard = (int(self.cfg["deployment_shards"]),
                         int(self.cfg["shard"]))
        return (int(docid) - shard) // shards + 1

    def stats(self) -> dict:
        p = self.post
        return {"passages": p.num_docs, "vocabulary": len(p.words),
                "postings": int(len(p.doc)),
                "tokens": int(self.lengths.sum()),
                "max_df": int(p.df.max()),
                "terms_df_over_1pct": int((p.df > p.num_docs / 100).sum())}


class Queries:
    """`n` MS MARCO dev-shaped queries: words per query from a shifted
    Poisson around the dev set's mean, each word a stopword with the
    configured share, content terms drawn by collection frequency. The
    set (as ranks of the term law) comes from the configuration's
    `data_seed` and the stream; the run's seed gives the terms their
    words and the stopwords their places."""

    def __init__(self, shard: Shard, n: int, seed: int, stream: int):
        qs = shard.cfg["queries"]
        gd, g = W.rng(shard.cfg["data_seed"], stream), W.rng(seed, stream)
        words = np.clip(qs["min_words"] + gd.poisson(
            qs["words_mean"] - qs["min_words"], n), qs["min_words"],
            qs["max_words"])
        content = np.clip(gd.binomial(words, 1.0 - qs["stopword_share"]),
                          1, qs["max_content_terms"])
        drawn = W.draw_ranks(gd, content, shard.cfg["shape"],
                             shard.present)
        self.rows = np.full((n, int(qs["max_content_terms"])), -1,
                            np.int64)
        self.texts = []
        for i, r in enumerate(drawn):
            w = shard.scramble(r)
            self.rows[i, : len(w)] = shard.post.term_ids(w)
            self.texts.append(W.query_text(g, W.word_strings(w),
                                           int(words[i] - len(w))))

    def shuffle(self, g: np.random.Generator, block: int | None = None):
        """Reorder the queries (within each run of `block` queries)."""
        n = len(self.texts)
        block = block or n
        perm = np.concatenate([s + g.permutation(min(block, n - s))
                               for s in range(0, n, block)])
        self.rows = self.rows[perm]
        self.texts = [self.texts[i] for i in perm]
