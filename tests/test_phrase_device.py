"""Phrase queries on the device (ops/phrase.py, Scorer._search_phrases_device)
against the host pipeline (search/phrase.py) and a plain reference that
scans the raw token streams (tests/phrase_reference.py), for BM25 and
TF-IDF, k 10 and 1000, slop 0, 1 and 3.

Every word is five letters and a digit, which the analyzer keeps as it
is, so a document's whitespace tokens are its analyzed tokens and their
order is the position coordinate."""

import random

import numpy as np
import pytest

from phrase_reference import PhraseReference
from tpu_ir import obs
from tpu_ir.index import build_index
from tpu_ir.obs import profiling
from tpu_ir.ops.phrase import PHRASE_CHUNK, PHRASE_GAP

WORDS = ["alpha1", "bravo2", "charl3", "delta4", "echoo5", "foxtr6",
         "newyo7", "golfx8"]
# a window across a document boundary: every doc but the last ends in
# "bound1", every doc but the first starts with "bound2", and the two
# are never adjacent inside one document
QUERIES = [
    '"alpha1 bravo2"',                       # 2 terms
    '"alpha1 bravo2 charl3"',                # 3 terms
    '"delta4 echoo5 foxtr6 alpha1" golfx8',  # 4 terms and a plain term
    '"newyo7 newyo7"',                       # a repeated term
    '"alpha1 absent9"',                      # a term the index lacks
    '"bound1 bound2"',                       # across a doc boundary
    '"farrr1 farrr2"',                       # positions past 2**15
    '"every0"',                              # df == N: TF-IDF scores 0
    '"alpha1 bravo2" "charl3 delta4"',       # two spans
    'charl3 "bravo2 alpha1" bravo2',         # plain terms around a span
]


def _docs(seed: int = 7, n: int = 120):
    rng = random.Random(seed)
    docs = []
    for i in range(n):
        toks = ["every0"] + [rng.choice(WORDS)
                             for _ in range(rng.randint(1, 40))]
        if i:
            toks = ["bound2"] + toks
        if i < n - 1:
            toks = toks + ["bound1"]
        docs.append(toks)
    # one long document: the phrase past position 2**15, its two terms
    # also apart earlier on
    long = ["every0", "farrr2"] + [rng.choice(WORDS)
                                   for _ in range(40_000)]
    long[39_000:39_002] = ["farrr1", "farrr2"]
    long[100] = "farrr1"
    docs[n // 2] = ["bound2"] + long + ["bound1"]
    return docs


@pytest.fixture(scope="module")
def index(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("phrase_device")
    docs = _docs()
    corpus = tmp / "corpus.trec"
    corpus.write_text("".join(
        f"<DOC>\n<DOCNO> {_docid(i)} </DOCNO>\n<TEXT>\n{' '.join(t)}\n"
        "</TEXT>\n</DOC>\n" for i, t in enumerate(docs)))
    out = str(tmp / "idx")
    build_index([str(corpus)], out, k=1, num_shards=3,
                compute_chargrams=False, positions=True)
    return out, docs


@pytest.fixture(scope="module")
def scorer(index):
    from tpu_ir.search import Scorer

    return Scorer.load(index[0])


def _docid(i: int) -> str:
    """A docid the analyzer keeps as one token; docnos follow i."""
    return f"doc{i:05d}"


@pytest.fixture(scope="module")
def reference(index, scorer):
    """The reference over each document's raw tokens: its DOCNO line's
    docid, then its text's words (the analyzer indexes the DOCNO line
    too; the fixture checks that it keeps every token as it is)."""
    docs = index[1]
    ids: dict = {}
    streams = [[ids.setdefault(w, len(ids)) for w in [_docid(i)] + toks]
               for i, toks in enumerate(docs)]
    for i in (0, len(docs) // 2, len(docs) - 1):
        assert scorer._analyzer.analyze(
            f"<DOC>\n<DOCNO> {_docid(i)} </DOCNO>\n<TEXT>\n"
            f"{' '.join(docs[i])}\n</TEXT>\n</DOC>") == [_docid(i)] + docs[i]
    ref = PhraseReference(
        np.concatenate([np.array(s, np.int64) for s in streams]),
        [len(s) for s in streams])
    return ref, ids


def _parse(text, ids):
    spans = [[ids.get(w, -1) for w in p.split()]
             for p in text.split('"')[1::2]]
    terms = [ids.get(w, -1) for w in text.replace('"', " ").split()]
    return spans, [t for t in terms if t >= 0]


def _ref_answer(reference, text, scoring, slop):
    ref, ids = reference
    spans, terms = _parse(text, ids)
    if any(t < 0 for s in spans for t in s):
        return np.zeros(0, np.int64), np.zeros(0)
    return ref.answer(spans, terms, scoring, slop)


def _agree(got, docs, scores, k, tag):
    """`got` holds min(k, matched) matched docs, every doc above the
    reference's k-th score, scores within a relative 1e-5 (an absolute
    1e-6 at zero)."""
    expect = min(k, len(docs))
    assert len(got) == expect, tag
    if not expect:
        return
    want = dict(zip(docs.tolist(), scores.tolist()))
    for d, s in got:
        assert d in want, (tag, d)
        assert abs(s - want[d]) <= max(1e-5 * abs(want[d]), 1e-6), tag
    kth = scores[expect - 1]
    above = {d for d, s in want.items() if s > kth * (1 + 1e-5) + 1e-6}
    assert above <= {d for d, _ in got}, tag


@pytest.mark.parametrize("slop", [0, 1, 3])
@pytest.mark.parametrize("k", [10, 1000])
@pytest.mark.parametrize("scoring", ["bm25", "tfidf"])
def test_device_path_equals_host_path_and_reference(scorer, reference,
                                                    scoring, k, slop):
    reg = obs.get_registry()
    before = reg.get("phrase.queries")
    dev = scorer.search_batch(QUERIES, k=k, scoring=scoring,
                              phrase_slop=slop, return_docids=False)
    # every quoted query with known terms went to the device
    assert reg.get("phrase.queries") - before == len(QUERIES) - 1
    host = scorer.search_batch(QUERIES, k=k, scoring=scoring,
                               phrase_slop=slop, return_docids=False,
                               force_host=True)
    for text, d, h in zip(QUERIES, dev, host):
        docs, scores = _ref_answer(reference, text, scoring, slop)
        tag = (text, scoring, k, slop)
        _agree(list(d), docs, scores, k, tag)
        _agree(list(h), docs, scores, k, tag)
    # the cases mean what they say
    by_text = dict(zip(QUERIES, dev))
    assert not by_text['"alpha1 absent9"']
    assert not by_text['"bound1 bound2"']
    assert len(by_text['"farrr1 farrr2"']) == 1
    zero = by_text['"every0"']
    assert len(zero) == min(k, len(reference[0].lengths))
    if scoring == "tfidf":
        assert all(s == 0.0 for _, s in zero)
        assert [d for d, _ in zero] == sorted(d for d, _ in zero)


@pytest.mark.parametrize("scoring", ["bm25", "tfidf"])
def test_a_slop_wider_than_the_gap_reads_the_doc_bounds(scorer, reference,
                                                        scoring):
    """At slop 40 a window reaches past the empty slots after a doc:
    the match reads the doc's bounds and still never crosses it."""
    slop = PHRASE_GAP + 8
    texts = ['"bound1 bound2"', '"alpha1 golfx8 newyo7"',
             '"every0 bound1"']
    dev = scorer.search_batch(texts, k=1000, scoring=scoring,
                              phrase_slop=slop, return_docids=False)
    host = scorer.search_batch(texts, k=1000, scoring=scoring,
                               phrase_slop=slop, return_docids=False,
                               force_host=True)
    for text, d, h in zip(texts, dev, host):
        docs, scores = _ref_answer(reference, text, scoring, slop)
        _agree(list(d), docs, scores, 1000, (text, slop))
        _agree(list(h), docs, scores, 1000, (text, slop))
    assert not dev[0]


def test_far_positions_match_only_where_adjacent(scorer, reference):
    """The long doc holds farrr1 at position 102 (apart from farrr2) and
    at 39,001 (adjacent): slop 0 finds it by the far occurrence alone."""
    ref, ids = reference
    docs, _ = ref.answer([[ids["farrr1"], ids["farrr2"]]], [], "bm25")
    (got,) = scorer.search_batch(['"farrr1 farrr2"'], k=10,
                                 return_docids=False)
    assert [d for d, _ in got] == docs.tolist()
    long = int(docs[0])
    assert int(ref.lengths[long - 1]) > 2**15


def test_mixed_call_keeps_order(scorer):
    texts = ["alpha1 bravo2", '"alpha1 bravo2"', "charl3",
             '"charl3 delta4" echoo5', "golfx8"]
    got = scorer.search_batch(texts, k=10, scoring="bm25")
    for text, res in zip(texts, got):
        assert res == scorer.search_batch([text], k=10, scoring="bm25")[0]


def test_block_with_no_match(scorer):
    reg = obs.get_registry()
    before = reg.get("phrase.candidates")
    got = scorer.search_batch(['"alpha1 absent9"', '"bound1 bound2"',
                               '"newyo7 every0"'], k=10)
    assert got == [[], [], []]
    assert reg.get("phrase.candidates") == before


def test_index_without_positions_uploads_no_table(tmp_path):
    from tpu_ir.search import Scorer

    corpus = tmp_path / "c.trec"
    corpus.write_text("".join(
        f"<DOC>\n<DOCNO> D-{i} </DOCNO>\n<TEXT>\nalpha1 bravo2 charl3\n"
        "</TEXT>\n</DOC>\n" for i in range(5)))
    out = str(tmp_path / "idx")
    build_index([str(corpus)], out, k=1, num_shards=2,
                compute_chargrams=False)
    obs.clear_traces()
    scorer = Scorer.load(out)
    (load,) = [t for t in obs.recent_traces() if t.name == "load"]
    assert "load.positions" not in [c.name for c in load.children]
    assert scorer.positions is None and scorer._position_table() is None
    assert not any("positions" in k for k in vars(scorer))
    with pytest.raises(ValueError, match="position"):
        scorer.search('"alpha1 bravo2"')


def test_positions_load_stage_and_counters(index):
    from tpu_ir.search import Scorer

    obs.clear_traces()
    scorer = Scorer.load(index[0])
    (load,) = [t for t in obs.recent_traces() if t.name == "load"]
    assert "load.positions" in [c.name for c in load.children]
    table = scorer.positions
    n = sum(len(t) + 1 for t in index[1])
    assert int(table.cf.sum()) == n
    # positions in rows of PHRASE_CHUNK, a row to spare past the last
    assert table.docs.shape == table.at.shape == (n // PHRASE_CHUNK + 2,
                                                  PHRASE_CHUNK)
    # the same positions as doc-major token streams, each doc followed
    # by at least PHRASE_GAP empty slots
    fwd = np.asarray(table.fwd).reshape(-1)
    assert int((fwd >= 0).sum()) == n
    at = np.asarray(table.at).reshape(-1)[:n]
    assert np.array_equal(fwd[at], np.repeat(
        np.arange(len(table.cf)), np.asarray(table.cf)))
    gaps = np.diff(np.asarray(table.doc_start)) - np.asarray(
        scorer.doc_len)
    assert gaps.min() >= PHRASE_GAP
    reg = obs.get_registry()
    start = {n: reg.get(f"phrase.{n}") for n in
             ("queries", "positions", "slots", "candidates")}
    scorer.search_batch(['"alpha1 bravo2"', '"charl3 delta4"'], k=5)
    grew = {n: reg.get(f"phrase.{n}") - v for n, v in start.items()}
    assert grew["queries"] == 2
    assert 0 < grew["positions"] <= grew["slots"]
    assert grew["candidates"] > 0


def test_one_program_serves_two_blocks_of_one_bucket(scorer):
    """Two different blocks whose shapes bucket alike (the same spans,
    so the same anchor lanes and candidates, in another order and with
    other plain terms) reuse the compiled match and top-k programs: no
    new signature, no compile."""
    a = ['"alpha1 bravo2"', 'charl3 "delta4 echoo5"']
    b = ['"delta4 echoo5" golfx8', '"alpha1 bravo2" foxtr6']
    scorer.search_batch(a, k=10)

    def ledger():
        rep = profiling.profile_report()["functions"]
        return {f["name"]: (f["compiles"], len(f["signatures"]))
                for f in rep if f["name"] in ("phrase_match",
                                              "phrase_topk")}

    before = ledger()
    reg = obs.get_registry()
    sent = reg.get("phrase.queries")
    scorer.search_batch(b, k=10)
    assert reg.get("phrase.queries") == sent + 2
    assert ledger() == before


@pytest.mark.parametrize("width", [8, 128])
def test_chunk_reads_unaligned_runs_across_rows(width):
    import jax.numpy as jnp

    from tpu_ir.ops.phrase import _chunk

    flat = np.arange(5 * width, dtype=np.int32) * 3 + 1
    rows = flat.reshape(-1, width)
    starts = np.array([0, 1, width - 1, width, width + 5, 3 * width,
                       4 * width - 1], np.int32)
    got = np.asarray(_chunk(jnp.asarray(rows), jnp.asarray(starts)))
    assert np.array_equal(got, np.stack([flat[s:s + width]
                                         for s in starts]))


def test_match_counts_the_matched_docs_rows(scorer):
    from tpu_ir.ops.phrase import PHRASE_SLICE, phrase_match

    ids = [scorer.vocab.id_or(t) for t in ("alpha1", "bravo2")]
    row_terms = np.full((8, 2), -1, np.int32)
    row_terms[0] = ids
    row_len = np.zeros(8, np.int32)
    row_len[0] = 2
    _, cand_doc, row_count, n_docs, n_slices = phrase_match(
        scorer.positions, row_terms, row_len, np.zeros(8, np.int32),
        n_chunks=scorer._anchor_chunks(np.array(ids)), slop=0)
    docs = np.unique(np.asarray(cand_doc)[:int(np.asarray(row_count)[0])])
    doc_start = np.asarray(scorer.positions.doc_start)
    assert int(n_docs) == len(docs) > 0
    assert int(n_slices) == int(((doc_start[docs + 1] - doc_start[docs])
                                 // PHRASE_SLICE).sum())


@pytest.mark.parametrize("block", [128, 1 << 18])
def test_doc_rows_sums_the_first_matches_block_by_block(block,
                                                        monkeypatch):
    import jax.numpy as jnp

    from tpu_ir.ops import phrase as ops_phrase

    monkeypatch.setattr(ops_phrase, "ROWS_BLOCK", block)
    rng = np.random.default_rng(5)
    doc_start = ops_phrase.PHRASE_SLICE * np.concatenate(
        [[0], np.cumsum(rng.integers(1, 6, 300))])
    table = ops_phrase.PositionTable(*[None] * 5, jnp.asarray(doc_start))
    cd = rng.integers(0, 300, 1024).astype(np.int32)
    rows = np.diff(doc_start) // ops_phrase.PHRASE_SLICE
    for n in (0, 1, 127, 128, 129, 700, 1024):
        first = (rng.random(1024) < 0.7) & (np.arange(1024) < n)
        got = ops_phrase._doc_rows(table, jnp.asarray(cd),
                                   jnp.asarray(first), jnp.int32(n))
        assert int(got) == int(rows[cd[first]].sum())
