"""Device-op tests: postings build, char-gram build, scoring vs a pure-numpy
oracle that follows the reference reducer/scorer semantics."""

import numpy as np
import pytest

import jax.numpy as jnp

from tpu_ir.ops import (
    PAD_TERM,
    build_chargram_index_jit,
    build_postings_jit,
    code_to_gram,
    dense_doc_matrix,
    gram_to_code,
    pack_occurrences,
    pack_term_bytes,
    tfidf_topk_dense,
    tfidf_topk_sparse,
)


def oracle_postings(term_ids, doc_ids):
    """Reference reducer semantics (TermKGramDocIndexer.java:167-213):
    group by (term, doc) summing tf, postings per term sorted tf desc then
    docno asc, df = number of docs."""
    from collections import Counter, defaultdict

    counts = Counter(zip(term_ids, doc_ids))
    by_term = defaultdict(list)
    for (t, d), tf in counts.items():
        by_term[t].append((d, tf))
    out = {}
    for t, posts in by_term.items():
        posts.sort(key=lambda p: (-p[1], p[0]))
        out[t] = posts
    return out


def test_build_postings_matches_oracle():
    rng = np.random.default_rng(0)
    n_tok, vocab, ndocs = 5000, 37, 23
    t = rng.integers(0, vocab, n_tok).astype(np.int32)
    d = rng.integers(1, ndocs + 1, n_tok).astype(np.int32)
    term_ids = np.full(6144, PAD_TERM, np.int32)
    doc_ids = np.zeros(6144, np.int32)
    term_ids[:n_tok] = t
    doc_ids[:n_tok] = d

    p = build_postings_jit(jnp.asarray(term_ids), jnp.asarray(doc_ids),
                           vocab_size=vocab, num_docs=ndocs)
    oracle = oracle_postings(t.tolist(), d.tolist())

    num_pairs = int(p.num_pairs)
    assert num_pairs == sum(len(v) for v in oracle.values())
    indptr = np.asarray(p.indptr)
    pair_doc = np.asarray(p.pair_doc)
    pair_tf = np.asarray(p.pair_tf)
    pair_term = np.asarray(p.pair_term)
    df = np.asarray(p.df)

    for tid in range(vocab):
        lo, hi = indptr[tid], indptr[tid + 1]
        got = list(zip(pair_doc[lo:hi].tolist(), pair_tf[lo:hi].tolist()))
        assert got == oracle.get(tid, []), f"term {tid}"
        assert df[tid] == len(oracle.get(tid, []))
        assert (pair_term[lo:hi] == tid).all()

    # doc lengths
    doc_len = np.asarray(p.doc_len)
    for dn in range(1, ndocs + 1):
        assert doc_len[dn] == int((d == dn).sum())


def test_build_postings_all_padding():
    term_ids = jnp.full((128,), PAD_TERM, jnp.int32)
    doc_ids = jnp.zeros((128,), jnp.int32)
    p = build_postings_jit(term_ids, doc_ids, vocab_size=5, num_docs=3)
    assert int(p.num_pairs) == 0
    assert np.asarray(p.df).sum() == 0


def test_pack_occurrences():
    t, d = pack_occurrences(
        [np.array([3, 1], np.int32), np.array([2], np.int32)],
        np.array([1, 2]), capacity=8)
    assert t.tolist()[:3] == [3, 1, 2]
    assert d.tolist()[:3] == [1, 1, 2]
    assert (t[3:] == PAD_TERM).all()
    with pytest.raises(ValueError):
        pack_occurrences([np.zeros(9, np.int32)], np.array([1]), capacity=8)


def test_round_cap_buckets():
    """Device capacities: >= n, granule-aligned at small sizes, and at
    most 16 distinct buckets per octave at large sizes (each distinct
    capacity is a separate XLA program)."""
    from tpu_ir.ops import round_cap

    for n in (0, 1, 100, 1 << 18, (1 << 18) + 1, 10_600_000, 1 << 30):
        cap = round_cap(n)
        assert cap >= max(n, 1)
        assert cap % (1 << 18) == 0 or cap == 1 << 18
    # one octave at ~16M: every size maps into <= 16 buckets
    caps = {round_cap(n) for n in range(1 << 24, 1 << 25, 1 << 18)}
    assert len(caps) <= 16, sorted(caps)
    # padded waste bounded: granule is 1/16 of the NEXT pow2, so the
    # tail is < n/8 + granule in the worst case (n just above a pow2)
    for n in (10_600_000, 123_456_789, (1 << 24) + 1):
        assert round_cap(n) <= int(n * 1.125) + (1 << 18)


def test_chargram_dispatch_shapes_bucketed(monkeypatch, tmp_path):
    """The chargram device program's input shape must NOT track the
    exact vocab size / longest term: both are corpus-dependent, and an
    exact shape misses the persistent compile cache on every new corpus
    (measured ~100 s of cold compiles at 500k terms vs ~1 s warm).
    Vocabs in the same pow2 bucket share one compiled shape, and the
    padding must not change the artifacts."""
    import tpu_ir.index.builder as builder
    from tpu_ir.index import format as fmt
    from tpu_ir.ops.chargram import build_chargram_index_host

    shapes = []
    orig = builder.build_chargram_index_jit

    def spy(tb, tl, *, k):
        shapes.append(tuple(tb.shape))
        return orig(tb, tl, k=k)

    monkeypatch.setattr(builder, "build_chargram_index_jit", spy)
    terms_a = [f"t{i:05d}" for i in range(900)]
    terms_b = [f"word{i:05d}x" for i in range(700)]
    for name, terms in (("a", terms_a), ("b", terms_b)):
        d = tmp_path / name
        d.mkdir()
        builder.build_chargram_artifacts(str(d), terms, [2])
    assert len(shapes) == 2 and len(set(shapes)) == 1, shapes
    assert shapes[0][0] >= 1024 and shapes[0][0] & (shapes[0][0] - 1) == 0
    # padded rows/columns contribute no windows: artifacts match the
    # unpadded host twin exactly
    z = fmt.load_chargram(str(tmp_path / "b"), 2)
    tb, tl = pack_term_bytes(terms_b, 2)
    hg, hip, hti = build_chargram_index_host(tb, tl, k=2)
    np.testing.assert_array_equal(z["gram_codes"].astype(np.int64),
                                  np.asarray(hg, np.int64))
    np.testing.assert_array_equal(z["indptr"].astype(np.int64),
                                  np.asarray(hip, np.int64))
    np.testing.assert_array_equal(z["term_ids"].astype(np.int64),
                                  np.asarray(hti, np.int64))


def test_chargram_index():
    terms = ["cat", "cart", "dog"]  # ids 0,1,2 assumed pre-sorted? not needed
    k = 2
    tb, tl = pack_term_bytes(terms, k)
    idx = build_chargram_index_jit(jnp.asarray(tb), jnp.asarray(tl), k=k)

    # oracle: $term$ windows
    from collections import defaultdict
    oracle = defaultdict(set)
    for i, term in enumerate(terms):
        padded = f"${term}$"
        for j in range(len(padded) - k + 1):
            oracle[padded[j : j + k]].add(i)

    ng = int(idx.num_grams)
    codes = np.asarray(idx.gram_codes)[:ng]
    indptr = np.asarray(idx.indptr)
    tids = np.asarray(idx.term_ids)
    got = {}
    for g in range(ng):
        gram = code_to_gram(int(codes[g]), k)
        got[gram] = sorted(tids[indptr[g] : indptr[g + 1]].tolist())
    assert got == {g: sorted(v) for g, v in oracle.items()}
    # per-gram term lists are sorted (reference merge keeps lists sorted)
    for g in range(ng):
        seg = tids[indptr[g] : indptr[g + 1]].tolist()
        assert seg == sorted(seg)
    assert (np.diff(codes) > 0).all()  # grams sorted unique
    # round-trip helper
    assert gram_to_code(code_to_gram(int(codes[0]), k), k) == int(codes[0])


def oracle_tfidf(postings_by_term, query_tids, n_docs, k=10):
    """Reference rank() semantics (IntDocVectorsForwardIndex.java:192-223),
    with float idf (the int-division quirk is tested separately)."""
    scores = {}
    for tid in query_tids:
        posts = postings_by_term.get(tid, [])
        dfv = len(posts)
        if dfv == 0:
            continue
        idf = np.log10(n_docs / dfv)
        for d, tf in posts:
            scores[d] = scores.get(d, 0.0) + (1 + np.log(tf)) * idf
    # engine semantics: zero-score docs (idf==0) are not returned
    ranked = sorted(
        ((d, s) for d, s in scores.items() if s > 0),
        key=lambda kv: (-kv[1], kv[0]))[:k]
    return ranked


def _small_index():
    rng = np.random.default_rng(1)
    n_tok, vocab, ndocs = 1500, 200, 17
    t = rng.integers(0, vocab, n_tok).astype(np.int32)
    d = rng.integers(1, ndocs + 1, n_tok).astype(np.int32)
    term_ids = np.full(4096, PAD_TERM, np.int32)
    doc_ids = np.zeros(4096, np.int32)
    term_ids[:n_tok] = t
    doc_ids[:n_tok] = d
    p = build_postings_jit(jnp.asarray(term_ids), jnp.asarray(doc_ids),
                           vocab_size=vocab, num_docs=ndocs)
    oracle = oracle_postings(t.tolist(), d.tolist())
    return p, oracle, vocab, ndocs


def test_tfidf_dense_matches_oracle():
    p, oracle, vocab, ndocs = _small_index()
    mat = dense_doc_matrix(p.pair_term, p.pair_doc, p.pair_tf,
                           vocab_size=vocab, num_docs=ndocs)
    queries = np.array([[0, 5], [3, -1], [28, 2], [7, 7]], np.int32)
    scores, docnos = tfidf_topk_dense(
        jnp.asarray(queries), mat, p.df, jnp.int32(ndocs), k=5)
    scores, docnos = np.asarray(scores), np.asarray(docnos)
    for qi, q in enumerate(queries):
        tids = [x for x in q.tolist() if x >= 0]
        want = oracle_tfidf(oracle, tids, ndocs, k=5)
        got = [(int(dn), float(s)) for s, dn in zip(scores[qi], docnos[qi]) if dn > 0]
        assert len(got) == len(want)
        for (gd, gs), (wd, ws) in zip(got, want):
            assert gs == pytest.approx(ws, rel=1e-4)
        # same doc set at equal scores (tie order may differ)
        assert {g[0] for g in got} == {w[0] for w in want}


def test_tfidf_sparse_matches_dense():
    p, oracle, vocab, ndocs = _small_index()
    mat = dense_doc_matrix(p.pair_term, p.pair_doc, p.pair_tf,
                           vocab_size=vocab, num_docs=ndocs)
    # build padded per-term postings from CSR
    indptr = np.asarray(p.indptr)
    pcap = int(np.max(np.diff(indptr)))
    post_docs = np.zeros((vocab, pcap), np.int32)
    post_tfs = np.zeros((vocab, pcap), np.int32)
    pd, pt = np.asarray(p.pair_doc), np.asarray(p.pair_tf)
    for tid in range(vocab):
        lo, hi = indptr[tid], indptr[tid + 1]
        post_docs[tid, : hi - lo] = pd[lo:hi]
        post_tfs[tid, : hi - lo] = pt[lo:hi]

    queries = np.array([[0, 5], [3, -1], [11, 2]], np.int32)
    s1, d1 = tfidf_topk_dense(jnp.asarray(queries), mat, p.df,
                              jnp.int32(ndocs), k=5)
    s2, d2 = tfidf_topk_sparse(jnp.asarray(queries), jnp.asarray(post_docs),
                               jnp.asarray(post_tfs), p.df, jnp.int32(ndocs),
                               num_docs=ndocs, k=5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4)
    # doc sets match per rank where scores are distinct
    assert (np.asarray(d1) == np.asarray(d2)).mean() > 0.9


def test_compat_int_idf():
    p, oracle, vocab, ndocs = _small_index()
    mat = dense_doc_matrix(p.pair_term, p.pair_doc, p.pair_tf,
                           vocab_size=vocab, num_docs=ndocs)
    # term 2: df=2, so the Java int division gives ndocs//df = 8 and a
    # POSITIVE idf (the old choice, term 4 with df=9, had 17//9 = 1 ->
    # idf exactly 0, every score 0.0, and the comparison loop compared
    # nothing — review r5)
    tid = 2
    dfv = int(np.asarray(p.df)[tid])
    assert ndocs // dfv >= 2, "fixture drift: pick a term with idf > 0"
    q = np.array([[tid, -1]], np.int32)
    s, dn = tfidf_topk_dense(jnp.asarray(q), mat, p.df, jnp.int32(ndocs),
                             k=3, compat_int_idf=True)
    posts = oracle.get(tid, [])
    want = [pair for pair in sorted(
        ((1 + np.log(tf)) * np.log10(ndocs // dfv), d)
        for d, tf in posts)[::-1][:3] if pair[0] > 0]
    got = [float(x) for x in np.asarray(s)[0] if x > 0]
    assert want and len(got) == len(want)  # zip would silently truncate
    for g, (w, _) in zip(got, want):
        assert g == pytest.approx(w, rel=1e-4)


def _tier_regimes(vocab, ndocs):
    """Layout parameter sets spanning: everything-hot, hot-strip starved by
    the budget (forces multi-tier cold coverage of high-df terms), and
    single-tier-dominant (large base cap)."""
    return [
        dict(hot_budget=10**12, base_cap=2, growth=4),   # p99 split, roomy
        dict(hot_budget=1, base_cap=2, growth=2),        # 1 hot row max
        dict(hot_budget=(ndocs + 1) * 2, base_cap=1, growth=4),  # 2 hot rows
        dict(hot_budget=1, base_cap=4096, growth=4),     # one big tier
    ]


def test_tfidf_tiered_matches_dense():
    """The tiered sparse layout must equal the dense path under every
    hot-budget / tier-capacity regime."""
    from tpu_ir.ops.scoring import tfidf_topk_tiered
    from tpu_ir.search.layout import build_tiered_layout

    p, oracle, vocab, ndocs = _small_index()
    mat = dense_doc_matrix(p.pair_term, p.pair_doc, p.pair_tf,
                           vocab_size=vocab, num_docs=ndocs)
    df = np.asarray(p.df)
    pd_, pt_ = np.asarray(p.pair_doc), np.asarray(p.pair_tf)

    queries = np.array([[0, 5, 199], [3, -1, -1], [11, 2, 7]], np.int32)
    s1, d1 = tfidf_topk_dense(jnp.asarray(queries), mat, p.df,
                              jnp.int32(ndocs), k=5)
    for kw in _tier_regimes(vocab, ndocs):
        t = build_tiered_layout(pd_, pt_, df, num_docs=ndocs, **kw)
        s2, d2 = tfidf_topk_tiered(
            jnp.asarray(queries), jnp.asarray(t.hot_rank),
            t.hot_device(), jnp.asarray(t.tier_of),
            jnp.asarray(t.row_of),
            tuple(jnp.asarray(a) for a in t.tier_docs),
            tuple(jnp.asarray(a) for a in t.tier_tfs),
            p.df, jnp.int32(ndocs), num_docs=ndocs, k=5)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-4, err_msg=str(kw))


def test_bm25_tiered_matches_dense():
    """BM25 on the tiered layout must equal bm25_topk_dense under every
    layout regime (the path that unlocks BM25 past the dense budget)."""
    from tpu_ir.ops.scoring import (bm25_topk_dense, bm25_topk_tiered,
                                    dense_tf_matrix)
    from tpu_ir.search.layout import build_tiered_layout

    p, oracle, vocab, ndocs = _small_index()
    tf_mat = dense_tf_matrix(p.pair_term, p.pair_doc, p.pair_tf,
                             vocab_size=vocab, num_docs=ndocs)
    df = np.asarray(p.df)
    pd_, pt_ = np.asarray(p.pair_doc), np.asarray(p.pair_tf)
    rng = np.random.default_rng(7)
    doc_len = np.zeros(ndocs + 1, np.int32)
    doc_len[1:] = rng.integers(5, 50, ndocs)

    queries = np.array([[0, 5, 199], [3, -1, -1], [11, 2, 7]], np.int32)
    s1, d1 = bm25_topk_dense(jnp.asarray(queries), tf_mat, p.df,
                             jnp.asarray(doc_len), jnp.int32(ndocs), k=5)
    for kw in _tier_regimes(vocab, ndocs):
        t = build_tiered_layout(pd_, pt_, df, num_docs=ndocs, **kw)
        s2, d2 = bm25_topk_tiered(
            jnp.asarray(queries), jnp.asarray(t.hot_rank),
            t.hot_device(), jnp.asarray(t.tier_of),
            jnp.asarray(t.row_of),
            tuple(jnp.asarray(a) for a in t.tier_docs),
            tuple(jnp.asarray(a) for a in t.tier_tfs),
            p.df, jnp.asarray(doc_len), jnp.int32(ndocs),
            num_docs=ndocs, k=5)
        # scores only: ulp-level accumulation-order differences between the
        # einsum and per-tier scatter paths may reorder tied docnos
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-4, err_msg=str(kw))


def test_hot_only_scores_exactly_the_hot_strip():
    """hot_only=True (the overload ladder's cheapest device level) must
    score EXACTLY the hot-strip contributions: a mixed hot+cold query
    under hot_only equals the same query with its cold terms removed
    under full scoring, and a cold-only query scores nothing."""
    from tpu_ir.ops.scoring import tfidf_topk_tiered
    from tpu_ir.search.layout import build_tiered_layout

    p, oracle, vocab, ndocs = _small_index()
    df = np.asarray(p.df)
    pd_, pt_ = np.asarray(p.pair_doc), np.asarray(p.pair_tf)
    t = build_tiered_layout(pd_, pt_, df, num_docs=ndocs,
                            hot_budget=10**12, base_cap=2, growth=4)
    hot = np.nonzero(t.hot_rank >= 0)[0]
    cold = np.nonzero((t.hot_rank < 0) & (df > 0))[0]
    assert len(hot) >= 1 and len(cold) >= 1, "regime must split the vocab"
    args = (jnp.asarray(t.hot_rank), t.hot_device(),
            jnp.asarray(t.tier_of), jnp.asarray(t.row_of),
            tuple(jnp.asarray(a) for a in t.tier_docs),
            tuple(jnp.asarray(a) for a in t.tier_tfs),
            p.df, jnp.int32(ndocs))

    q_mixed = np.array([[int(hot[0]), int(cold[0])]], np.int32)
    q_hot = np.array([[int(hot[0]), -1]], np.int32)
    s_ho, d_ho = tfidf_topk_tiered(jnp.asarray(q_mixed), *args,
                                   num_docs=ndocs, k=5, hot_only=True)
    s_ref, d_ref = tfidf_topk_tiered(jnp.asarray(q_hot), *args,
                                     num_docs=ndocs, k=5)
    np.testing.assert_allclose(np.asarray(s_ho), np.asarray(s_ref),
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(d_ho), np.asarray(d_ref))

    q_cold = np.array([[int(cold[0]), -1]], np.int32)
    s0, d0 = tfidf_topk_tiered(jnp.asarray(q_cold), *args,
                               num_docs=ndocs, k=5, hot_only=True)
    assert not np.asarray(d0).any(), "cold-only query must score nothing"

    # skip_hot + hot_only together would score nothing at all — rejected
    with pytest.raises(ValueError):
        tfidf_topk_tiered(jnp.asarray(q_hot), *args, num_docs=ndocs,
                          k=5, hot_only=True, skip_hot=True)


def test_hot_strip_coo_densify():
    """The hot strip is carried as COO postings (the serving cold-start
    fix: COO crosses the H2D link, the dense strip is scattered on device).
    hot_device() must equal the host densification, every hot term's full
    postings list must land in its strip row, and the COO columns must be
    slim (uint16) when the corpus allows."""
    from tpu_ir.search.layout import build_tiered_layout

    p, oracle, vocab, ndocs = _small_index()
    df = np.asarray(p.df)
    pd_, pt_ = np.asarray(p.pair_doc), np.asarray(p.pair_tf)
    for kw in _tier_regimes(vocab, ndocs):
        t = build_tiered_layout(pd_, pt_, df, num_docs=ndocs, **kw)
        dense = t.hot_dense()
        assert dense.shape == (t.num_hot, ndocs + 1)
        np.testing.assert_array_equal(np.asarray(t.hot_device()), dense)
        # every hot term's raw tfs, straight from the CSR columns
        indptr = np.concatenate([[0], np.cumsum(df, dtype=np.int64)])
        for tid in np.nonzero(t.hot_rank >= 0)[0]:
            row = dense[t.hot_rank[tid]]
            sl = slice(indptr[tid], indptr[tid + 1])
            np.testing.assert_array_equal(row[pd_[sl]], pt_[sl])
            assert np.count_nonzero(row) == df[tid]
    # this corpus is small: every column must have taken the uint16 path
    t = build_tiered_layout(pd_, pt_, df, num_docs=ndocs, hot_budget=10**12)
    assert (t.hot_rows.dtype == t.hot_docs.dtype == t.hot_vals.dtype
            == np.uint16)


def test_tiered_ignores_df0_and_out_of_range_terms():
    """Regression: a df=0 vocab term must contribute nothing under tiered
    BM25 (its idf is nonzero, and an unmasked tier_of=0 default would alias
    it onto tier 0 row 0's postings); ditto ids past the vocabulary."""
    from tpu_ir.ops.scoring import (bm25_topk_dense, bm25_topk_tiered,
                                    dense_tf_matrix, tfidf_topk_tiered)
    from tpu_ir.search.layout import build_tiered_layout

    rng = np.random.default_rng(3)
    vocab, ndocs = 210, 17  # ids 200..209 never occur -> df = 0
    t = rng.integers(0, 200, 1500).astype(np.int32)
    d = rng.integers(1, ndocs + 1, 1500).astype(np.int32)
    term_ids = np.full(4096, PAD_TERM, np.int32)
    doc_ids = np.zeros(4096, np.int32)
    term_ids[:1500] = t
    doc_ids[:1500] = d
    p = build_postings_jit(jnp.asarray(term_ids), jnp.asarray(doc_ids),
                           vocab_size=vocab, num_docs=ndocs)
    df = np.asarray(p.df)
    assert df[205] == 0
    lay = build_tiered_layout(np.asarray(p.pair_doc), np.asarray(p.pair_tf),
                              df, num_docs=ndocs)
    args = (jnp.asarray(lay.hot_rank), lay.hot_device(),
            jnp.asarray(lay.tier_of), jnp.asarray(lay.row_of),
            tuple(jnp.asarray(a) for a in lay.tier_docs),
            tuple(jnp.asarray(a) for a in lay.tier_tfs))
    doc_len = np.zeros(ndocs + 1, np.int32)
    doc_len[1:] = rng.integers(5, 50, ndocs)

    queries = jnp.asarray(np.array([[205, -1], [300, -1]], np.int32))
    s, dn = bm25_topk_tiered(queries, *args, p.df, jnp.asarray(doc_len),
                             jnp.int32(ndocs), num_docs=ndocs, k=5)
    assert (np.asarray(s) == 0).all() and (np.asarray(dn) == 0).all()
    s, dn = tfidf_topk_tiered(queries, *args, p.df, jnp.int32(ndocs),
                              num_docs=ndocs, k=5)
    assert (np.asarray(s) == 0).all() and (np.asarray(dn) == 0).all()

    tf_mat = dense_tf_matrix(p.pair_term, p.pair_doc, p.pair_tf,
                             vocab_size=vocab, num_docs=ndocs)
    s, dn = bm25_topk_dense(queries, tf_mat, p.df, jnp.asarray(doc_len),
                            jnp.int32(ndocs), k=5)
    assert (np.asarray(s) == 0).all() and (np.asarray(dn) == 0).all()


def test_build_postings_packed_matches_unpacked():
    """The slim-upload front end (uint16 term ids + on-device doc-column
    reconstruction from (docno, length)) must agree with build_postings."""
    from tpu_ir.ops import PAD_TERM_U16, build_postings_packed_jit

    rng = np.random.default_rng(3)
    vocab, ndocs, cap = 37, 23, 4096
    lengths = rng.integers(0, 40, ndocs).astype(np.int32)  # incl zero-len doc
    docnos = rng.permutation(ndocs).astype(np.int32) + 1
    n_tok = int(lengths.sum())
    t = rng.integers(0, vocab, n_tok).astype(np.int32)
    d = np.repeat(docnos, lengths)

    ref_t = np.full(cap, PAD_TERM, np.int32)
    ref_d = np.zeros(cap, np.int32)
    ref_t[:n_tok] = t
    ref_d[:n_tok] = d
    ref = build_postings_jit(jnp.asarray(ref_t), jnp.asarray(ref_d),
                             vocab_size=vocab, num_docs=ndocs)

    for use16 in (True, False):
        packed = np.full(cap, PAD_TERM_U16 if use16 else PAD_TERM,
                         np.uint16 if use16 else np.int32)
        packed[:n_tok] = t
        got = build_postings_packed_jit(
            jnp.asarray(packed), jnp.asarray(docnos), jnp.asarray(lengths),
            vocab_size=vocab, num_docs=ndocs)
        assert int(got.num_pairs) == int(ref.num_pairs)
        np.testing.assert_array_equal(np.asarray(got.df), np.asarray(ref.df))
        np.testing.assert_array_equal(np.asarray(got.doc_len),
                                      np.asarray(ref.doc_len))
        n = int(ref.num_pairs)
        for name in ("pair_term", "pair_doc", "pair_tf"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name))[:n],
                np.asarray(getattr(ref, name))[:n], err_msg=name)


def test_build_postings_packed_u16_boundary_ids():
    """Term ids right at the uint16 edge (65533/65534) survive the 0xFFFF
    sentinel remap; the sentinel itself is reserved for padding."""
    from tpu_ir.ops import PAD_TERM_U16, build_postings_packed_jit

    vocab = 65535 - 1  # the builder's use16 cutoff: v < 65535
    packed = np.full(256, PAD_TERM_U16, np.uint16)
    packed[:3] = [65533, 0, 65533]
    docnos = np.array([7, 9], np.int32)
    lengths = np.array([2, 1], np.int32)
    p = build_postings_packed_jit(jnp.asarray(packed), jnp.asarray(docnos),
                                  jnp.asarray(lengths),
                                  vocab_size=vocab, num_docs=9)
    assert int(p.num_pairs) == 3
    df = np.asarray(p.df)
    assert df[65533] == 2 and df[0] == 1 and df.sum() == 3


def test_narrow_uint_boundary():
    from tpu_ir.utils.transfer import narrow_uint

    assert narrow_uint(0) == np.uint16
    assert narrow_uint(65535) == np.uint16   # exact fit
    assert narrow_uint(65536) == np.int32
    assert np.array(65535, narrow_uint(65535)) == 65535  # no wraparound


def test_shrink_for_fetch_and_pairs():
    from tpu_ir.utils.transfer import shrink_for_fetch, shrink_pairs

    a = jnp.arange(1 << 16, dtype=jnp.int32)
    out = shrink_for_fetch(a, 100, dtype=np.uint16, granule=64)
    assert out.shape[0] == 128 and out.dtype == np.uint16
    np.testing.assert_array_equal(np.asarray(out)[:100], np.arange(100))
    # no-op path returns the same array
    assert shrink_for_fetch(a, 1 << 16, granule=64) is a

    pd = jnp.full((1 << 10,), 70000, jnp.int32)
    ptf = jnp.full((1 << 10,), 3, jnp.int32)
    spd, stf = shrink_pairs(pd, ptf, 10, num_docs=100_000, tf_max=3,
                            granule=32)
    assert spd.dtype == np.int32     # docnos don't fit uint16
    assert stf.dtype == np.uint16
    assert int(np.asarray(spd)[0]) == 70000


def test_tiered_big_tier_cond_path():
    """Terms in tiers with cap >= 4096 (the lax.cond-gated stages) must
    score identically to the dense path — including blocks where no query
    term lands in the big tier (the skip branch)."""
    from tpu_ir.ops.scoring import tfidf_topk_tiered
    from tpu_ir.search.layout import build_tiered_layout

    rng = np.random.default_rng(9)
    ndocs, vocab = 9000, 50
    # term 0: df 5000 -> tier cap 8192 (cond-gated); term 1: df 6000 but
    # hot (hot strip takes the top-df terms); the rest small
    dfs = [5000, 6000] + [int(x) for x in rng.integers(1, 50, vocab - 2)]
    pt, pd, ptf = [], [], []
    for tid, df_t in enumerate(dfs):
        docs = rng.choice(ndocs, df_t, replace=False) + 1
        tfs = rng.integers(1, 9, df_t)
        order = np.lexsort((docs, -tfs))
        pt.extend([tid] * df_t)
        pd.extend(docs[order].tolist())
        ptf.extend(tfs[order].tolist())
    pt = np.array(pt, np.int32)
    pd = np.array(pd, np.int32)
    ptf = np.array(ptf, np.int32)
    df = np.bincount(pt, minlength=vocab).astype(np.int32)

    tiers = build_tiered_layout(pd, ptf, df, num_docs=ndocs,
                                hot_budget=2 * (ndocs + 1))  # 2 hot rows
    assert max(a.shape[1] for a in tiers.tier_docs) >= 4096

    mat = dense_doc_matrix(jnp.asarray(pt), jnp.asarray(pd),
                           jnp.asarray(ptf), vocab_size=vocab,
                           num_docs=ndocs)
    # queries hitting the big tier, the hot strip, small tiers, and one
    # block-wide big-tier miss (terms 2.. only)
    qs = np.array([[0, 5], [1, 7], [3, 9], [2, 4]], np.int32)
    for q in (qs, qs[2:]):  # second batch: nothing in the big tier
        s1, d1 = tfidf_topk_dense(jnp.asarray(q), mat, jnp.asarray(df),
                                  jnp.int32(ndocs), k=10)
        s2, d2 = tfidf_topk_tiered(
            jnp.asarray(q), jnp.asarray(tiers.hot_rank),
            tiers.hot_device(), jnp.asarray(tiers.tier_of),
            jnp.asarray(tiers.row_of),
            tuple(jnp.asarray(a) for a in tiers.tier_docs),
            tuple(jnp.asarray(a) for a in tiers.tier_tfs),
            jnp.asarray(df), jnp.int32(ndocs), num_docs=ndocs, k=10)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))

class TestChargramHostFallback:
    """3 < k <= 7 grams pack into int64 on host (ops/chargram.py); the
    semantics must match the device path's: '$term$' byte windows, per-gram
    sorted-unique term lists."""

    def test_matches_python_oracle(self):
        from tpu_ir.ops.chargram import (
            build_chargram_index_host, gram_to_code, pack_term_bytes)

        terms = sorted(["alpha", "alphabet", "beta", "albania", "a"])
        tb, tl = pack_term_bytes(terms, 5)
        codes, indptr, tids = build_chargram_index_host(tb, tl, k=5)

        oracle: dict[bytes, set] = {}
        for i, t in enumerate(terms):
            s = b"$" + t.encode() + b"$"
            for j in range(len(s) - 4):
                oracle.setdefault(s[j : j + 5], set()).add(i)
        assert len(codes) == len(oracle)
        for gram, want in oracle.items():
            gi = int(np.searchsorted(codes, gram_to_code(gram, 5)))
            got = tids[indptr[gi] : indptr[gi + 1]].tolist()
            assert got == sorted(want), gram

    def test_k4_non_ascii_routed_to_host_path(self, tmp_path):
        """k=4 would shift a gram's leading byte by 24 bits in int32 —
        negative codes for any non-ASCII byte >= 0x80, unfindable by
        gram_to_code's unsigned lookup. The builder must route k=4 to the
        int64 host twin and wildcard expansion over it must still match
        multi-byte UTF-8 terms end-to-end."""
        from tpu_ir.index import build_index
        from tpu_ir.index import format as fmt
        from tpu_ir.search.wildcard import WildcardLookup

        corpus = tmp_path / "c.trec"
        corpus.write_text(
            "<DOC>\n<DOCNO> U-1 </DOCNO>\n<TEXT>\ncafézzz naïveté plain"
            "\n</TEXT>\n</DOC>\n", encoding="utf-8")
        idx = str(tmp_path / "idx")
        meta = build_index([str(corpus)], idx, chargram_ks=[4],
                           num_shards=2)
        assert meta.chargram_ks == [4]
        z = fmt.load_chargram(idx, 4)
        assert (np.asarray(z["gram_codes"]) >= 0).all()
        lookup = WildcardLookup.load(idx, 4)
        assert "cafézzz" in lookup.expand("café*")
        # and the device program refuses k=4 outright
        tb, tl = pack_term_bytes(["café"], 4)
        with pytest.raises(ValueError):
            build_chargram_index_jit(jnp.asarray(tb), jnp.asarray(tl), k=4)

    def test_k_gt_7_rejected(self):
        """k=8 would let grams with a >=0x80 leading byte (any non-ASCII)
        overflow int64's sign bit and silently break lookups."""
        from tpu_ir.ops.chargram import (
            build_chargram_index_host, pack_term_bytes)

        tb, tl = pack_term_bytes(["word"], 8)
        with pytest.raises(ValueError):
            build_chargram_index_host(tb, tl, k=8)

    def test_non_ascii_grams_roundtrip(self):
        """Multi-byte UTF-8 grams (leading byte >= 0x80) must stay
        positive and matchable at the max host k."""
        from tpu_ir.ops.chargram import (
            build_chargram_index_host, gram_to_code, pack_term_bytes)

        terms = sorted(["caféterm", "naïveword"])
        tb, tl = pack_term_bytes(terms, 7)
        codes, indptr, tids = build_chargram_index_host(tb, tl, k=7)
        assert (codes >= 0).all()
        s = b"$" + terms[0].encode("utf-8") + b"$"
        gram = s[1:8]  # window containing the 2-byte é sequence
        gi = int(np.searchsorted(codes, gram_to_code(gram, 7)))
        assert codes[gi] == gram_to_code(gram, 7)
        assert 0 in tids[indptr[gi] : indptr[gi + 1]]

    def test_builder_integration_and_expand(self, tmp_path):
        """chargram_ks mixing device (<=3) and host (>3) ks builds both
        artifacts, and wildcard expansion works over the k=5 index."""
        from tpu_ir.index import build_index
        from tpu_ir.search.wildcard import WildcardLookup

        corpus = tmp_path / "c.trec"
        corpus.write_text(
            "<DOC>\n<DOCNO> W-1 </DOCNO>\n<TEXT>\nfishing fisher walked"
            "\n</TEXT>\n</DOC>\n")
        idx = str(tmp_path / "idx")
        meta = build_index([str(corpus)], idx, chargram_ks=[2, 5],
                           num_shards=2)
        assert meta.chargram_ks == [2, 5]
        lookup = WildcardLookup.load(idx, 5)
        got = lookup.expand("fish*")
        assert "fisher" in got and "fish" in got  # 'fishing' stems to fish


def test_sparse_drops_out_of_range_term_ids():
    """tfidf_topk_sparse must ignore query ids >= V like its siblings —
    an unmasked id would clamp its gathers to the LAST vocabulary term
    and silently score its postings (review r5)."""
    p, oracle, vocab, ndocs = _small_index()
    indptr = np.asarray(p.indptr)
    pcap = int(np.max(np.diff(indptr)))
    post_docs = np.zeros((vocab, pcap), np.int32)
    post_tfs = np.zeros((vocab, pcap), np.int32)
    pd, pt = np.asarray(p.pair_doc), np.asarray(p.pair_tf)
    for tid in range(vocab):
        lo, hi = indptr[tid], indptr[tid + 1]
        post_docs[tid, : hi - lo] = pd[lo:hi]
        post_tfs[tid, : hi - lo] = pt[lo:hi]
    q_ok = np.array([[0, 5, -1]], np.int32)
    q_oob = np.array([[0, 5, vocab]], np.int32)  # vocab == out of range
    s1, d1 = tfidf_topk_sparse(jnp.asarray(q_ok), jnp.asarray(post_docs),
                               jnp.asarray(post_tfs), p.df,
                               jnp.int32(ndocs), num_docs=ndocs, k=5)
    s2, d2 = tfidf_topk_sparse(jnp.asarray(q_oob), jnp.asarray(post_docs),
                               jnp.asarray(post_tfs), p.df,
                               jnp.int32(ndocs), num_docs=ndocs, k=5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_bm25_b1_empty_doc_no_nan():
    """At b=1.0 an empty doc has dl_norm 0, and an unguarded saturation
    divides 0/0 — the NaN outranks every real score in lax.top_k and
    burns top-k slots (review r5: verified scores like [0., ...] with the
    best real doc dropped). The guarded curve must rank real docs only."""
    from tpu_ir.ops import bm25_topk_dense
    from tpu_ir.ops.scoring import dense_tf_matrix

    # docs 1..2 real, doc 3 EMPTY (no postings, doc_len 0)
    pair_term = jnp.asarray(np.array([0, 0, 1], np.int32))
    pair_doc = jnp.asarray(np.array([1, 2, 1], np.int32))
    pair_tf = jnp.asarray(np.array([2, 1, 1], np.int32))
    tf_mat = dense_tf_matrix(pair_term, pair_doc, pair_tf,
                             vocab_size=2, num_docs=3)
    df = jnp.asarray(np.array([2, 1], np.int32))
    doc_len = jnp.asarray(np.array([0, 3, 1, 0], np.int32))
    q = jnp.asarray(np.array([[0, 1]], np.int32))
    s, d = bm25_topk_dense(q, tf_mat, df, doc_len, jnp.int32(3),
                           k=3, b=1.0)
    s, d = np.asarray(s), np.asarray(d)
    assert np.isfinite(s).all()
    assert d[0, 0] == 1 and s[0, 0] > 0     # best real doc leads
    assert 3 not in d[0]                     # the empty doc never ranks


def test_reduce_weighted_postings_empty_input():
    """A zero-length bucket must return num_pairs 0, not IndexError —
    the guard build_postings always had (review r5)."""
    from tpu_ir.ops.postings import reduce_weighted_postings

    t = jnp.zeros((0,), jnp.int32)
    out = reduce_weighted_postings(t, t, t, vocab_size=5)
    assert int(out[4]) == 0
    assert np.asarray(out[3]).sum() == 0  # df all zero


def test_pack_occurrences_length_mismatch_is_loud():
    """zip truncation used to silently drop whole documents' postings
    when docnos was shorter than the per-doc id lists (review r5)."""
    with pytest.raises(ValueError):
        pack_occurrences(
            [np.zeros(2, np.int32), np.ones(2, np.int32),
             np.full(2, 2, np.int32)],
            np.array([1, 2]), capacity=8)


@pytest.mark.parametrize("which", ["tfidf", "bm25"])
def test_idf_is_f32_accurate_for_terms_in_nearly_every_doc(which):
    """A term in all but a few of N docs has N/df within ulps of 1: the
    f32 idf must still be within a few ulps of the float64 value (the
    chip smoke's 1e-5 relative check on such queries)."""
    from tpu_ir.ops.scoring import bm25_idf_weights, idf_weights

    n = 100_000
    df = np.array([1, 7, n // 3, n - 50, n - 1, n], np.int64)
    if which == "tfidf":
        got = np.asarray(idf_weights(jnp.asarray(df, jnp.int32), n))
        want = np.log10(n / df)
    else:
        got = np.asarray(bm25_idf_weights(jnp.asarray(df, jnp.int32),
                                          jnp.int32(n)))
        want = np.log1p((n - df + 0.5) / (df + 0.5))
    nz = want > 0
    np.testing.assert_allclose(got[nz], want[nz], rtol=1e-6)
    assert (got[~nz] == 0).all()


def test_log_is_f32_accurate():
    """The kernels' own ln (ops/scoring._log): the TPU's f32 log is off
    by up to 5.7e-5 relative, so the weight curves must not use it."""
    from tpu_ir.ops.scoring import _lntf, _log, _log1p

    x = np.concatenate([np.arange(1, 5001), np.geomspace(1e-30, 1e30, 999),
                        [0.70710677, 0.7071068, 1.4142135, 1.4142137]])
    x = x.astype(np.float32)
    want = np.log(x.astype(np.float64))
    got = np.asarray(_log(jnp.asarray(x)), np.float64)
    np.testing.assert_allclose(got, want, rtol=5e-7, atol=1e-7)
    tf = np.arange(0, 5001, dtype=np.float32)
    want = np.where(tf > 0, 1 + np.log(np.maximum(tf, 1).astype(np.float64)),
                    0.0)
    np.testing.assert_allclose(np.asarray(_lntf(jnp.asarray(tf))), want,
                               rtol=3e-7)
    y = np.geomspace(1e-9, 1e6, 2000).astype(np.float32)
    np.testing.assert_allclose(np.asarray(_log1p(jnp.asarray(y))),
                               np.log1p(y.astype(np.float64)), rtol=5e-7)


# -- the cold chunk stream ---------------------------------------------------

STREAM_C = 32  # chunk width small enough for a 1,500-doc corpus
# dfs around the chunk width: term 0 hot; 1 = C; 2 = C + 1; 3 = cap 128;
# 4 and 5 two terms in the 128 tier; 6 = cap 512; 7 the 128 tier again;
# 8-13 small per-tier terms (12 has df 0)
STREAM_DFS = [1200, 32, 33, 128, 100, 120, 512, 40, 5, 3, 1, 7, 0, 2]
STREAM_BIG = np.array([[1, 2, 3, -1],         # dfs C, C+1 and cap
                       [4, 5, 3, 0],          # two terms in one tier + hot
                       [-1, -1, -1, -1],      # a pad row
                       [len(STREAM_DFS) + 5, 12, 9, 1],  # OOV, df 0
                       [6, 6, 7, 8]], np.int32)  # a repeated term
STREAM_SMALL = np.array([[8, 9, 10, -1], [11, 13, -1, -1]], np.int32)


@pytest.fixture(scope="module")
def stream_corpus():
    from tpu_ir.ops.scoring import ColdChunks, dense_tf_matrix
    from tpu_ir.search import layout

    rng = np.random.default_rng(24)
    ndocs = 1500
    pt, pd, ptf = [], [], []
    for tid, df_t in enumerate(STREAM_DFS):
        docs = rng.choice(ndocs, df_t, replace=False) + 1
        tfs = rng.integers(1, 9, df_t)
        order = np.lexsort((docs, -tfs))
        pt += [tid] * df_t
        pd += docs[order].tolist()
        ptf += tfs[order].tolist()
    pt, pd, ptf = (np.array(a, np.int32) for a in (pt, pd, ptf))
    df = np.bincount(pt, minlength=len(STREAM_DFS)).astype(np.int32)
    lay = layout.build_tiered_layout(pd, ptf, df, num_docs=ndocs,
                                     hot_budget=ndocs + 1)  # one hot row
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(layout, "COLD_CHUNK", STREAM_C)
        plan = layout.cold_chunk_plan(lay, df)
    dev = lambda arrs: tuple(jnp.asarray(a) for a in arrs)  # noqa: E731
    tier_docs, tier_tfs = dev(lay.tier_docs), dev(lay.tier_tfs)
    doc_len = np.zeros(ndocs + 1, np.int32)
    doc_len[1:] = rng.integers(5, 60, ndocs)
    return dict(
        ndocs=ndocs, df=df, doc_len=doc_len, plan=plan, lay=lay,
        chunks=ColdChunks(*dev(layout.cold_chunk_table(lay, plan)
                               + plan[1:3])),
        tiered=(jnp.asarray(lay.hot_rank), lay.hot_device(),
                jnp.asarray(lay.tier_of), jnp.asarray(lay.row_of),
                tier_docs, tier_tfs, jnp.asarray(df)),
        tf_mat=dense_tf_matrix(jnp.asarray(pt), jnp.asarray(pd),
                               jnp.asarray(ptf), vocab_size=len(df),
                               num_docs=ndocs),
        mat=dense_doc_matrix(jnp.asarray(pt), jnp.asarray(pd),
                             jnp.asarray(ptf), vocab_size=len(df),
                             num_docs=ndocs))


def _stream_need(corpus, q):
    count = corpus["plan"][2]
    valid = (q >= 0) & (q < len(count))
    return int(np.where(valid, count[np.where(valid, q, 0)], 0).sum())


def _stream_kernels(corpus, scoring, k):
    """(dense, tiered) callables of one query block, the tiered one
    taking the chunk-stream keyword arguments."""
    from tpu_ir.ops.scoring import (bm25_topk_dense, bm25_topk_tiered,
                                    tfidf_topk_tiered)

    c, n = corpus, jnp.int32(corpus["ndocs"])
    if scoring == "bm25":
        dl = jnp.asarray(c["doc_len"])
        return (lambda q: bm25_topk_dense(q, c["tf_mat"], jnp.asarray(
                    c["df"]), dl, n, k=k),
                lambda q, **kw: bm25_topk_tiered(
                    q, *c["tiered"], dl, n, num_docs=c["ndocs"], k=k, **kw))
    return (lambda q: tfidf_topk_dense(q, c["mat"], jnp.asarray(c["df"]),
                                       n, k=k),
            lambda q, **kw: tfidf_topk_tiered(
                q, *c["tiered"], n, num_docs=c["ndocs"], k=k, **kw))


def test_chunk_plan_streams_the_tiers_whole_chunks_divide(stream_corpus):
    """Tiers whose capacity is a multiple of C stream; each streamed
    term starts at its own chunk row and streams ceil(df / C) rows
    holding exactly its postings; hot, small-tier and df-0 terms
    stream nothing."""
    lay, df = stream_corpus["lay"], stream_corpus["df"]
    streamed, row0, count, c = stream_corpus["plan"]
    caps = [a.shape[1] for a in lay.tier_docs]
    assert c == STREAM_C
    assert streamed == [i for i, cap in enumerate(caps) if cap % c == 0]
    assert [caps[i] for i in streamed] == [32, 128, 512]
    docs = np.asarray(stream_corpus["chunks"].docs)
    tfs = np.asarray(stream_corpus["chunks"].tfs)
    for tid, df_t in enumerate(STREAM_DFS):
        t = lay.tier_of[tid]
        if t < 0 or caps[t] % c:
            assert count[tid] == 0, tid
            continue
        assert count[tid] == -(-df_t // c), tid
        rows = slice(row0[tid], row0[tid] + count[tid])
        want = np.zeros(count[tid] * c, np.int64)
        want[:df_t] = lay.tier_docs[t][lay.row_of[tid], :df_t]
        np.testing.assert_array_equal(docs[rows].ravel(), want)
        assert (tfs[rows].ravel()[:df_t] > 0).all()
        assert not tfs[rows].ravel()[df_t:].any()


@pytest.mark.parametrize("scoring", ["bm25", "tfidf"])
@pytest.mark.parametrize("k", [10, 1000])
def test_chunk_stream_matches_dense_and_per_tier_stages(stream_corpus,
                                                        scoring, k):
    """The stream against the dense kernel and the per-tier stages it
    replaces: rank-equal, scores within 1e-6 relative. Capacities: the
    exact need, its bucket and the worst case (the bucket minimum and
    the worst case for a block with no streamed term)."""
    from tpu_ir.ops.scoring import chunk_bucket

    dense, tiered = _stream_kernels(stream_corpus, scoring, k)
    chunks = stream_corpus["chunks"]
    widest = 512 // STREAM_C
    for q in (STREAM_BIG, STREAM_SMALL):
        need = _stream_need(stream_corpus, q)
        caps = ({need, chunk_bucket(need), q.size * widest}
                if need else {chunk_bucket(0), q.size * widest})
        qd = jnp.asarray(q)
        s_d, d_d = (np.asarray(a) for a in dense(qd))
        s_t, d_t = (np.asarray(a) for a in tiered(qd))
        assert s_d[0, 0] > 0
        for cap in caps:
            s, d = (np.asarray(a) for a in tiered(qd, chunks=chunks,
                                                  n_chunks=cap))
            np.testing.assert_array_equal(d, d_d, err_msg=str(cap))
            np.testing.assert_array_equal(d, d_t, err_msg=str(cap))
            np.testing.assert_allclose(s, s_d, rtol=1e-6, err_msg=str(cap))
            np.testing.assert_allclose(s, s_t, rtol=1e-6, err_msg=str(cap))


@pytest.mark.parametrize("scoring", ["bm25", "tfidf"])
def test_chunk_stream_row_is_independent_of_its_block(stream_corpus,
                                                      scoring):
    """A query's floats do not depend on what shares its block or on
    the capacity: solo at its own need, inside the block, and at the
    worst case (the coalescer's closed shapes) agree bit for bit."""
    from tpu_ir.ops.scoring import chunk_bucket

    _, tiered = _stream_kernels(stream_corpus, scoring, 1000)
    chunks = stream_corpus["chunks"]
    need = _stream_need(stream_corpus, STREAM_BIG)
    block = np.asarray(tiered(jnp.asarray(STREAM_BIG), chunks=chunks,
                              n_chunks=chunk_bucket(need))[0])
    worst = np.asarray(tiered(jnp.asarray(STREAM_BIG), chunks=chunks,
                              n_chunks=STREAM_BIG.size * 512 // STREAM_C)[0])
    np.testing.assert_array_equal(block, worst)
    for i, row in enumerate(STREAM_BIG):
        q = row[None, :]
        solo = np.asarray(tiered(jnp.asarray(q), chunks=chunks,
                                 n_chunks=max(_stream_need(
                                     stream_corpus, q), 1))[0])
        np.testing.assert_array_equal(solo[0], block[i], err_msg=str(i))


def test_chunk_capacity_bucket_reuses_one_program(stream_corpus):
    """Blocks whose needs fall in one capacity bucket run one compiled
    program; a need past the bucket compiles one more."""
    from tpu_ir import obs
    from tpu_ir.ops.scoring import CHUNK_MIN_BUCKET, bm25_topk_tiered, \
        chunk_bucket

    _, tiered = _stream_kernels(stream_corpus, "bm25", 10)
    chunks = stream_corpus["chunks"]
    q_a = np.array([[1, -1, -1, -1]], np.int32)     # 1 chunk
    q_b = np.array([[3, 7, -1, -1]], np.int32)      # 4 + 2 chunks
    q_c = np.array([[6, 6, 3, 2]], np.int32)        # 16 + 16 + 4 + 2
    needs = [_stream_need(stream_corpus, q) for q in (q_a, q_b, q_c)]
    assert needs[0] != needs[1] and max(needs[:2]) <= CHUNK_MIN_BUCKET
    assert chunk_bucket(needs[2]) > CHUNK_MIN_BUCKET
    bm25_topk_tiered.clear_cache()
    reg = obs.get_registry()
    seen = []
    for q, need in zip((q_a, q_b, q_c), needs):
        tiered(jnp.asarray(q), chunks=chunks, n_chunks=chunk_bucket(need))
        seen.append(reg.get("compile.count"))
    assert seen[1] == seen[0] and seen[2] == seen[1] + 1, seen
