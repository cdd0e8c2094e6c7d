"""The generators against the program's analyzer and index reader: the
words are analyzer-stable, the stopwords are dropped, and an index
written from a generated shard reads back as the generator's postings."""

import numpy as np

from benchmark import harness, words
from benchmark.msmarco import Queries, Shard
from benchmark.trec import Slice

from .conftest import TINY


def _config(name):
    _, _, config, _ = harness.find_cell(name)
    return config


def test_words_are_analyzer_stable_and_stopwords_dropped():
    from tpu_ir.analysis import Analyzer
    from tpu_ir.analysis.stopwords import TERRIER_STOPWORDS

    idx = words.rng(1, 0).integers(0, 2**26, 2000)
    ws = words.word_strings(idx)
    assert Analyzer().analyze(" ".join(ws)) == ws
    assert len(set(ws)) == len(set(idx.tolist()))
    order = np.argsort(idx)
    assert [ws[i] for i in order] == sorted(ws)
    assert set(words.STOPWORDS) <= set(TERRIER_STOPWORDS)


def test_postings_match_a_loop_count():
    g = words.rng(5, 0)
    lengths = g.integers(1, 9, 50)
    toks = g.integers(0, 30, int(lengths.sum()))
    p = words.Postings(toks, lengths)
    want = {}
    pos = 0
    for d, n in enumerate(lengths, start=1):
        for t in toks[pos:pos + n]:
            want[(int(t), d)] = want.get((int(t), d), 0) + 1
        pos += n
    got = {}
    start = 0
    for ti, w in enumerate(p.words):
        for j in range(start, start + p.df[ti]):
            got[(int(w), int(p.doc[j]))] = int(p.tf[j])
        run_tf = p.tf[start:start + p.df[ti]]
        assert (np.diff(run_tf) <= 0).all()  # tf descending in a run
        start += p.df[ti]
    assert got == want
    assert p.doc_len[1:].tolist() == lengths.tolist()


def test_written_shard_reads_back_as_generated(tmp_path):
    from tpu_ir.search import Scorer

    cfg = _config("msmarco-passage.batch-bm25-k1000")
    cfg["passages"] = TINY["msmarco-passage.batch-bm25-k1000"]["passages"]
    shard = Shard(cfg, 2**31 + 7)
    shard.write_index(str(tmp_path / "idx"))
    sc = Scorer.load(str(tmp_path / "idx"))
    assert np.array_equal(np.asarray(sc._df_host()), shard.post.df)
    doc, tf = (np.asarray(a) for a in sc._pairs_doc_tf)
    assert np.array_equal(doc, shard.post.doc)
    assert np.array_equal(tf, shard.post.tf)
    assert np.array_equal(np.asarray(sc.doc_len), shard.post.doc_len)
    ids = sc.mapping.docids
    assert [shard.docno(d) for d in ids[:50]] == list(range(1, 51))
    qs = Queries(shard, 64, 3, 2)
    got = sc.analyze_queries(qs.texts)
    for row, want in zip(got, qs.rows):
        assert sorted(row[row >= 0].tolist()) == sorted(
            want[want >= 0].tolist())


def test_trec_slice_builds_to_the_expected_postings(tmp_path):
    import contextlib
    import io

    from tpu_ir import cli
    from tpu_ir.search import Scorer

    cfg = _config("robust04.build")
    cfg["documents"] = 150
    sl = Slice(cfg, 11)
    sl.write_text(str(tmp_path / "c.trec"))
    with contextlib.redirect_stdout(io.StringIO()):
        assert cli.main(["index", str(tmp_path / "c.trec"),
                         str(tmp_path / "idx"), "--streaming",
                         "--no-chargrams"]) == 0
    sc = Scorer.load(str(tmp_path / "idx"))
    assert list(sc.vocab.terms) == sl.terms[sl.post.words].tolist()
    assert np.array_equal(np.asarray(sc._df_host()), sl.post.df)
    assert np.array_equal(np.asarray(sc.doc_len), sl.post.doc_len)
    texts, rows = sl.topics(20, 3)
    got = sc.analyze_queries(texts)
    for row, want in zip(got, rows):
        assert sorted(row[row >= 0].tolist()) == sorted(
            want[want >= 0].tolist())


def test_same_seed_same_inputs():
    cfg = _config("msmarco-passage.batch-bm25-k1000")
    cfg["passages"] = 500
    a, b = Shard(cfg, 2**33 + 1), Shard(cfg, 2**33 + 1)
    assert np.array_equal(a.post.doc, b.post.doc)
    assert Queries(a, 10, 2**33 + 1, 2).texts == Queries(
        b, 10, 2**33 + 1, 2).texts


def test_seeds_permute_one_multiset():
    """Every seed serves the same work: equal df and length multisets,
    the same query-width profile; only the order and the words differ."""
    cfg = _config("msmarco-passage.batch-bm25-k1000")
    cfg["passages"] = 800
    a, b = Shard(cfg, 5), Shard(cfg, 2**32 + 9)
    assert sorted(a.post.df) == sorted(b.post.df)
    assert sorted(a.lengths) == sorted(b.lengths)
    assert not np.array_equal(a.lengths, b.lengths)
    qa, qb = Queries(a, 40, 5, 2), Queries(b, 40, 2**32 + 9, 2)
    assert ((qa.rows >= 0).sum(1) == (qb.rows >= 0).sum(1)).all()
    assert qa.texts != qb.texts
    tcfg = _config("robust04.build")
    tcfg["documents"] = 60
    sa, sb = Slice(tcfg, 1), Slice(tcfg, 2)
    assert len(sa.terms) == len(sb.terms)
    assert sorted(sa.post.df) == sorted(sb.post.df)
