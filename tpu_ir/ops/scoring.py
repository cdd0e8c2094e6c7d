"""Batched ranked retrieval: TF-IDF / BM25 scoring + top-k, on device.

This replaces the reference's per-query scoring loop
(IntDocVectorsForwardIndex.java:192-223): score(d) = sum over query terms of
(1 + ln tf) * log10(N / df), truncated to the top 10. The reference's O(P^2)
linear-scan accumulation becomes a dense doc-axis accumulator; its
Collections.sort becomes jax.lax.top_k; and queries are scored in batches so
the work is a handful of fused gathers/adds per query block instead of a
Java loop per posting.

Two layouts:
- dense: a [V, D] term-by-doc (1+ln tf) matrix; scoring a query batch is L
  embedding-style row gathers + weighted adds (MXU/VPU friendly, best when
  V*D fits HBM).
- sparse: CSR postings padded per-term to a cap; scoring scatter-adds each
  query term's postings slice. Used when the dense matrix would not fit.

Quirk policy (SURVEY.md §7): the reference computes N/df with Java int
division; `compat_int_idf=True` reproduces that for parity tests, default
computes float idf. Documented deviation: documents whose total score is
exactly 0 (every query term has df == N, so idf == 0) are not returned,
whereas the reference would list them in unspecified order.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs.profiling import profiled_jit

PAD_QTERM = -1

# cold tiers at least this wide run under a whole-block lax.cond skip (the
# stage costs B*L*P_t even when no query term lands in it); narrower tiers
# are nearly always hit, where the cond only adds sync overhead. Only the
# per-tier stage uses it, which the big tiers take only where no chunk
# stream is passed: the sharded layout (parallel/sharded_tiered.py)
COND_TIER_MIN_CAP = 4096

# smallest chunk-stream capacity (in chunks) a dispatch is compiled for:
# below it a block's own postings are too few for another program to pay
CHUNK_MIN_BUCKET = 16


class ColdChunks(NamedTuple):
    """The big cold tiers as one table of fixed-size posting chunks
    (search/layout.py `cold_chunk_table`): every tier whose capacity is
    a multiple of the chunk width C, viewed as [V_t * P_t / C, C] rows
    and concatenated. A term's postings are `count` consecutive rows
    from `row0`; lanes past its df hold tf 0, like tier padding. The
    kernels never read a streamed tier's own arrays when given this, so
    a caller may pass [0, P_t] placeholders for them."""

    docs: jax.Array    # [R, C] docnos in the tiers' dtype, 0 = empty lane
    tfs: jax.Array     # [R, C] raw tfs, 0 = empty lane
    row0: jax.Array    # int32 [V]: the term's first chunk row
    count: jax.Array   # int32 [V]: ceil(df / C) for streamed terms, else 0


def chunk_bucket(need: int) -> int:
    """The static capacity a block needing `need` chunks is dispatched
    at: the next power of two, at least CHUNK_MIN_BUCKET (cf. the query
    width bucketing in Scorer.analyze_queries)."""
    return max(CHUNK_MIN_BUCKET, 1 << max(int(need) - 1, 0).bit_length())


# MaxScore candidate-set width: when the hot-strip stage is pruned, the
# top MAXSCORE_CAND docs by cold partial score are the only ones that get
# exact hot contributions (everything below is provably outside the top-k)
MAXSCORE_CAND = 2048
# pruning engages only when k is comfortably inside the candidate set and
# the doc axis is wide enough for the skipped matmul to matter
_PRUNE_K_FRACTION = 4
_PRUNE_MIN_DOCS = 2 * MAXSCORE_CAND


def _prune_applicable(k: int, num_docs: int, prune: bool) -> bool:
    """Static decision: is MaxScore pruning structurally worthwhile?"""
    return (prune and k * _PRUNE_K_FRACTION <= MAXSCORE_CAND
            and num_docs + 1 >= _PRUNE_MIN_DOCS)


_LN2_HI = 0.693145751953125  # ln 2 in 16 bits: e * _LN2_HI is exact
_LN2_LO = 1.4286068203094172e-06  # ln 2 - _LN2_HI
_SQRT_HALF = 0.7071067811865476
_INV_LN10 = 1.0 / math.log(10.0)


def _two_atanh(s):
    """2 atanh(s) = ln((1 + s)/(1 - s)) for |s| <= 0.1716, as an odd
    series through s^13 (truncation < 1e-10)."""
    z = s * s
    p = jnp.float32(2.0 / 13.0)
    for c in (2.0 / 11.0, 2.0 / 9.0, 2.0 / 7.0, 2.0 / 5.0, 2.0 / 3.0, 2.0):
        p = p * z + c
    return s * p


def _log(x):
    """ln x for positive f32 x, to a few ulps on every backend.

    The TPU's own f32 log measured 5.7e-5 worst relative error over
    x = 1..5000 (chip probe, PR 21), outside the exact-score contract.
    Here x = m * 2^e with m in [sqrt(1/2), sqrt(2)), and ln m = 2 atanh(s)
    with s = (m - 1)/(m + 1) — only +, *, / and bit ops, which the TPU
    rounds as the CPU does."""
    m, e = jnp.frexp(x)
    low = m < _SQRT_HALF
    m = jnp.where(low, m * 2.0, m)
    e = (e - low.astype(e.dtype)).astype(jnp.float32)
    return e * _LN2_HI + (_two_atanh((m - 1.0) / (m + 1.0)) + e * _LN2_LO)


def _log1p(x):
    """ln(1 + x) for f32 x >= 0 without forming 1 + x where that would
    round x away: ln(1 + x) = 2 atanh(x / (2 + x)) while 1 + x < sqrt 2."""
    return jnp.where(x < 2.0 * _SQRT_HALF - 1.0,
                     _two_atanh(x / (2.0 + x)), _log(1.0 + x))


def _lntf(tf):
    """The (1 + ln tf) weight curve; 0 for empty slots.

    The entry cast makes the curve dtype-polymorphic over the hot strip:
    a bf16 strip (compressed arena, integer tfs <= 256 so the narrow
    mantissa is exact) must widen HERE, before jnp.maximum — JAX weak
    typing would otherwise keep the whole expression in bf16 and the
    log would round differently from the fp32 raw path. f32-in is an
    identity cast, so the raw path's traced expression is unchanged."""
    tf = tf.astype(jnp.float32)
    return jnp.where(tf > 0, 1.0 + _log(jnp.maximum(tf, 1.0)), 0.0)


def idf_weights(df: jax.Array, num_docs: int, compat_int_idf: bool = False) -> jax.Array:
    """log10(N/df) per term; df==0 terms get weight 0.

    Computed as log1p((N - df) / df) / ln 10: a term in nearly every doc
    has N/df within a few ulps of 1, and log10 of that rounded f32 ratio
    keeps only the rounding (~1e-3 relative error at df = N - 1,
    N = 1e5); N - df is exact, so log1p stays within a few ulps."""
    dff = df.astype(jnp.float32)
    if compat_int_idf:
        ratio = jnp.floor_divide(
            jnp.int32(num_docs), jnp.maximum(df, 1)).astype(jnp.float32)
        w = _log(jnp.maximum(ratio, 1e-30)) * _INV_LN10
    else:
        n_f = jnp.asarray(num_docs, jnp.float32)
        w = _log1p((n_f - dff) / jnp.maximum(dff, 1.0)) * _INV_LN10
    return jnp.where(df > 0, w, 0.0)


def bm25_idf_weights(df: jax.Array, n: jax.Array) -> jax.Array:
    """Okapi idf log(1 + (N - df + 0.5)/(df + 0.5)); df==0 terms get 0.
    One definition — this expression used to be inlined at four sites
    with inconsistent df==0 masking (the dense copy relied on zero
    tf-matrix rows, a subtlety each copy had to re-reason about)."""
    dff = df.astype(jnp.float32)
    # lint: invariant-ok (a scalar cast)
    n_f = jnp.asarray(n, jnp.float32)
    # log1p, not log(1 + x): for df near N, x ~ 0.5/N and 1 + x would
    # round away most of it (see idf_weights)
    w = _log1p((n_f - dff + 0.5) / (dff + 0.5))
    return jnp.where(df > 0, w, 0.0)


def bm25_saturation(tf, dl_norm, *, k1: float):
    """tf*(k1+1)/(tf + k1*dl_norm), guarded: at b=1.0 an empty doc has
    dl_norm 0 and a tf=0 cell would divide 0/0 — the NaN then outranks
    every real score in lax.top_k (and poisons the hot-strip matmul).
    Entry cast for bf16 hot strips (see _lntf): saturation must be
    computed in fp32 or weak typing narrows the whole ratio to bf16."""
    tf = tf.astype(jnp.float32)
    return tf * (k1 + 1.0) / jnp.maximum(tf + k1 * dl_norm, 1e-9)


def _dense_scatter(pair_term, pair_doc, values, *, vocab_size: int,
                   num_docs: int) -> jax.Array:
    flat = jnp.zeros((vocab_size * (num_docs + 1),), jnp.float32)
    idx = pair_term * (num_docs + 1) + pair_doc
    idx = jnp.where((pair_term >= 0) & (pair_term < vocab_size), idx,
                    vocab_size * (num_docs + 1))
    flat = flat.at[idx].add(values, mode="drop")
    return flat.reshape(vocab_size, num_docs + 1)


def dense_doc_matrix(postings_pair_term, postings_pair_doc, postings_pair_tf,
                     *, vocab_size: int, num_docs: int) -> jax.Array:
    """[V, D+1] matrix of (1+ln tf); column 0 (docno 0) is dead padding."""
    w = _lntf(postings_pair_tf.astype(jnp.float32))
    return _dense_scatter(postings_pair_term, postings_pair_doc, w,
                          vocab_size=vocab_size, num_docs=num_docs)


def dense_tf_matrix(postings_pair_term, postings_pair_doc, postings_pair_tf,
                    *, vocab_size: int, num_docs: int) -> jax.Array:
    """[V, D+1] matrix of raw tf (float32), for BM25 saturation."""
    return _dense_scatter(postings_pair_term, postings_pair_doc,
                          postings_pair_tf.astype(jnp.float32),
                          vocab_size=vocab_size, num_docs=num_docs)


def _tfidf_dense_scores(q_terms, doc_matrix, df, num_docs,
                        compat_int_idf) -> jax.Array:
    """[B, D+1] TF-IDF accumulation on the dense layout — THE expression
    both the production top-k kernel and the explain score-gather variant
    trace, so a gathered explain score is bit-identical to what the
    top-k saw (search/explain.py pins this)."""
    vocab_size = doc_matrix.shape[0]
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = idf_weights(df, num_docs, compat_int_idf)

    safe_q = jnp.where(q_terms >= 0, q_terms, 0)
    q_valid = (q_terms >= 0) & (q_terms < vocab_size)
    q_idf = jnp.where(q_valid, idf[safe_q], 0.0)          # [B, L]
    # no separate row mask: q_idf is already 0 exactly where q_valid is
    # False, and the clamped gather returns finite real rows — a mask
    # here would re-multiply the [B, L, D+1] tensor for nothing
    rows = doc_matrix[safe_q]                              # [B, L, D+1]
    # explicit multiply + reduce over the term axis, NOT an einsum: a
    # dot_general's algorithm (fma fusion, lane order) is chosen per
    # SHAPE, so the same query row could round differently at batch
    # size 1 vs 4 — the coalescing frontend (ISSUE 9) pins coalesced ==
    # solo BIT-exactly, which needs a batch-size-invariant lowering.
    # The [B, L, D+1] intermediate already exists (the gather above),
    # so this costs no extra memory.
    return jnp.sum(rows * q_idf[:, :, None], axis=1)       # [B, D+1]


@partial(profiled_jit, static_argnames=("k", "compat_int_idf"))
def tfidf_topk_dense(
    q_terms: jax.Array,   # int32 [B, L], PAD_QTERM padding
    doc_matrix: jax.Array,  # f32 [V, D+1]
    df: jax.Array,          # int32 [V]
    num_docs: jax.Array,    # int32 scalar (N)
    *,
    k: int = 10,
    compat_int_idf: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Batched TF-IDF top-k. Returns (scores [B,k], docnos [B,k]);
    docno 0 marks an empty slot (fewer than k docs matched)."""
    scores = _tfidf_dense_scores(q_terms, doc_matrix, df, num_docs,
                                 compat_int_idf)
    return _topk_from_scores(scores, k)


@partial(profiled_jit, static_argnames=("compat_int_idf",))
def tfidf_scores_at_dense(
    q_terms: jax.Array,     # int32 [B, L]
    doc_matrix: jax.Array,  # f32 [V, D+1]
    df: jax.Array,          # int32 [V]
    num_docs: jax.Array,    # int32 scalar
    cand: jax.Array,        # int32 [B, C] docnos to read out
    *,
    compat_int_idf: bool = False,
) -> jax.Array:
    """Explain debug variant: the SAME accumulation as tfidf_topk_dense,
    read out at the requested docnos instead of top-k'd — [B, C] f32."""
    scores = _tfidf_dense_scores(q_terms, doc_matrix, df, num_docs,
                                 compat_int_idf)
    return jnp.take_along_axis(scores, cand.astype(jnp.int32), axis=1)


def _bm25_dense_scores(q_terms, tf_matrix, df, doc_len, num_docs,
                       k1, b) -> jax.Array:
    """[B, D+1] BM25 accumulation on the dense layout (see
    _tfidf_dense_scores for the shared-expression contract)."""
    vocab_size = tf_matrix.shape[0]
    n = jnp.asarray(num_docs, jnp.float32)
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = bm25_idf_weights(df, n)
    avg_dl = jnp.sum(doc_len.astype(jnp.float32)) / jnp.maximum(n, 1.0)
    dl_norm = 1.0 - b + b * doc_len.astype(jnp.float32) / jnp.maximum(avg_dl, 1e-9)

    safe_q = jnp.where(q_terms >= 0, q_terms, 0)
    q_valid = (q_terms >= 0) & (q_terms < vocab_size)
    q_idf = jnp.where(q_valid, idf[safe_q], 0.0)           # [B, L]
    tf = tf_matrix[safe_q]                                  # [B, L, D+1]
    # mul + reduce, not einsum: batch-size-invariant rounding (see
    # _tfidf_dense_scores — the coalesced == solo bit-exactness pin)
    sat = bm25_saturation(tf, dl_norm[None, None, :], k1=k1)
    return jnp.sum(sat * q_idf[:, :, None], axis=1)


@partial(profiled_jit, static_argnames=("k", "k1", "b"))
def bm25_topk_dense(
    q_terms: jax.Array,      # int32 [B, L]
    tf_matrix: jax.Array,    # f32 [V, D+1] raw tf
    df: jax.Array,           # int32 [V]
    doc_len: jax.Array,      # int32 [D+1]
    num_docs: jax.Array,     # int32 scalar
    *,
    k: int = 10,
    k1: float = 0.9,
    b: float = 0.4,
) -> tuple[jax.Array, jax.Array]:
    """Batched Okapi BM25 top-k (the scorer variant the reference never had
    but the MS MARCO config needs; SURVEY.md §7 build order)."""
    scores = _bm25_dense_scores(q_terms, tf_matrix, df, doc_len, num_docs,
                                k1, b)
    return _topk_from_scores(scores, k)


@partial(profiled_jit, static_argnames=("k1", "b"))
def bm25_scores_at_dense(
    q_terms: jax.Array,      # int32 [B, L]
    tf_matrix: jax.Array,    # f32 [V, D+1]
    df: jax.Array,           # int32 [V]
    doc_len: jax.Array,      # int32 [D+1]
    num_docs: jax.Array,     # int32 scalar
    cand: jax.Array,         # int32 [B, C]
    *,
    k1: float = 0.9,
    b: float = 0.4,
) -> jax.Array:
    """Explain debug variant of bm25_topk_dense — [B, C] f32 at `cand`."""
    scores = _bm25_dense_scores(q_terms, tf_matrix, df, doc_len, num_docs,
                                k1, b)
    return jnp.take_along_axis(scores, cand.astype(jnp.int32), axis=1)


def _topk_from_scores(scores: jax.Array, k: int):
    scores = scores.at[:, 0].set(-jnp.inf)                   # dead column
    top_scores, top_idx = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    matched = top_scores > 0.0
    return (jnp.where(matched, top_scores, 0.0),
            jnp.where(matched, top_idx, 0).astype(jnp.int32))


def _hot_gemm(w_hot, strip):
    """[B, H] @ [H, D+1] in full f32. At default precision the TPU runs
    an f32 matmul as one bf16 pass (~3 significant digits), which breaks
    exact-score parity with the host scorer and MaxScore's bounds; on
    the CPU the flag changes nothing."""
    # lint: reassoc-ok (THE production MXU matmul — per-row batch
    # invariance is pinned dynamically by the coalesced==solo suite,
    # and a mul+reduce here would materialize [B, H, D+1])
    return jnp.matmul(w_hot, strip, precision=jax.lax.Precision.HIGHEST)


def _tiered_scores(q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs,
                   tier_tfs, q_weight, *, num_docs, hot_weight_fn,
                   cold_weight_fn, hot_cell_fn=None, hot_max_w=None,
                   prune_k=None, with_stats=False, skip_hot=False,
                   skip_cold=False, chunks=None, n_chunks=None):
    """Shared tiered accumulation: hot-strip einsum, one masked
    gather/scatter-add per small df tier, and one chunk stream for the
    big tiers (see search/layout.py for the layout).

    `chunks` (a ColdChunks table, or None) takes every tier whose
    capacity is a multiple of its chunk width off the per-tier stages:
    those tiers cost B*L*P_t each per dispatch whatever lands in them,
    while the stream moves only the chunks the block's own terms hold
    (`_chunk_stream`). `n_chunks` (static, required with `chunks`) is
    the stream's capacity, which must hold the block's need: the Scorer
    passes the block's bucketed need, or where the compiled set must
    stay closed the worst case B*L*(widest streamed capacity / C).

    `hot_weight_fn(strip)` maps the raw-tf hot strip [H, D+1] (doc axis
    last) to per-cell score contributions; `cold_weight_fn(tfs, docs)` does
    the same per padded posting. They are the only difference between
    TF-IDF ((1+ln tf)) and BM25 (saturation with the doc-length norm —
    broadcast over the strip's doc axis / gathered at each posting's
    docno).

    When `prune_k` is set (with `hot_max_w`, the per-hot-row score upper
    bound, and `hot_cell_fn(tfs, docs)`, the per-cell weight for gathered
    candidates), the hot-strip stage runs under batched MaxScore pruning —
    see `_hot_stage_pruned`. The reference scores every posting of every
    query term (IntDocVectorsForwardIndex.java:192-223); this is the
    rank-safe algorithmic improvement on top of the silicon one."""
    vocab_size = hot_rank.shape[0]
    b = q_terms.shape[0]
    safe_q = jnp.where(q_terms >= 0, q_terms, 0)            # [B, L]
    q_valid = (q_terms >= 0) & (q_terms < vocab_size)
    q_w = q_weight[safe_q] * q_valid                         # [B, L]
    rank = hot_rank[safe_q]                                  # [B, L]
    is_hot = (rank >= 0) & q_valid
    h = hot_tfs.shape[0]

    def hot_matmul(s):
        # hot strip as an MXU matmul: scatter each query's term weights
        # into a [B, H] row (duplicate terms sum), then one [B, H] @
        # [H, D+1] matmul against the element-wise-weighted strip. The
        # per-(query, term) row gather it replaces materializes
        # [B, L, D+1] — at 1M docs that is GBs of HBM traffic per
        # dispatch for the same math.
        w_hot = jnp.zeros((b, h), jnp.float32).at[
            jnp.broadcast_to(jnp.arange(b)[:, None], rank.shape),
            jnp.where(is_hot, rank, h),
        ].add(jnp.where(is_hot, q_w, 0.0), mode="drop")      # [B, H]
        return s + _hot_gemm(w_hot, hot_weight_fn(hot_tfs))  # [B, D+1]

    # `skip_cold` (static): the hot-tier-only degraded service level — the
    # overloaded frontend serves just the hot-strip stage (one matmul) and
    # omits every cold-tier gather/scatter, which is where the per-query
    # work grows with corpus size. Scores are a LOWER BOUND on the full
    # model (cold-term contributions are simply absent), so results ride
    # tagged with their service level, never as full answers.
    if skip_cold and skip_hot:
        raise ValueError("skip_cold and skip_hot together score nothing")
    pruning = prune_k is not None and not skip_cold
    # `skip_hot` (static): the caller certified every query in the block
    # is hot-term-free, so the hot stage contributes EXACTLY zero — omit
    # it entirely (no matmul, no cond, no candidate machinery). This is
    # the Scorer's production MaxScore specialization: measured on the
    # runtime-cond variant, the unconditional top-C over [B, D+1] cost
    # more than the matmul it skips on CPU backends; the host already
    # knows which queries have ub = 0, so the skip is free.
    #
    # Accumulation order is COLD-FIRST on every path (ISSUE 13): the
    # block-max kernels must see the cold partial before the hot stage
    # (the running threshold derives from it), and bit-identity between
    # them and this exact kernel requires ONE accumulation order — so
    # the no-prune path moved its hot matmul to the end. This shifts
    # ulp-level rounding vs the pre-13 hot-first kernels; every
    # cross-path pin recomputes both sides, and the explain prefix
    # harness traces this same order.
    scores = jnp.zeros((b, num_docs + 1), jnp.float32)

    tof = tier_of[safe_q]                                    # [B, L]
    row = row_of[safe_q]

    def add_cold(acc_q, slots_q, w_q):
        return acc_q.at[slots_q.ravel()].add(w_q.ravel(), mode="drop")

    if chunks is not None and n_chunks is None:
        raise ValueError("a chunk stream needs its static n_chunks")
    chunk_w = None if chunks is None else chunks.docs.shape[1]
    for i, (tdocs, ttfs) in enumerate(
            () if skip_cold else zip(tier_docs, tier_tfs)):
        if chunk_w is not None and tdocs.shape[1] % chunk_w == 0:
            continue                                 # streamed below
        in_tier = (tof == i) & q_valid & ~is_hot             # [B, L]

        def do_tier(s, in_tier=in_tier, tdocs=tdocs, ttfs=ttfs):
            r = jnp.where(in_tier, row, 0)
            # tier arrays may arrive in slim (uint16) transport dtypes;
            # cast once on device so index arithmetic is plain int32
            docs = tdocs[r].astype(jnp.int32)                # [B, L, P_t]
            tfs = ttfs[r].astype(jnp.float32)
            w = cold_weight_fn(tfs, docs)
            mask = in_tier[..., None]
            w = jnp.where(tfs > 0, w, 0.0) * q_w[..., None] * mask
            slot = jnp.where((tfs > 0) & mask, docs, num_docs + 1)
            return jax.vmap(add_cold)(s, slot, w)

        # a tier's gather/scatter costs B*L*P_t even when nothing lands in
        # it. For the BIG tiers (which dominate that sum and hold few terms,
        # so a block often misses them entirely) the stage runs under a
        # whole-block any() predicate; small tiers are nearly always hit
        # and the cond would only add sync overhead.
        if tdocs.shape[1] >= COND_TIER_MIN_CAP:
            scores = jax.lax.cond(jnp.any(in_tier), do_tier, lambda s: s,
                                  scores)
        else:
            scores = do_tier(scores)

    if chunks is not None and not skip_cold:
        scores = _chunk_stream(
            scores, chunks, safe_q, q_valid & ~is_hot, q_w,
            n_chunks=n_chunks, num_docs=num_docs,
            cold_weight_fn=cold_weight_fn)

    if skip_hot:
        return (scores, jnp.ones((b,), bool)) if with_stats else scores
    if not pruning:
        scores = hot_matmul(scores)
        return (scores, jnp.ones((b,), bool)) if with_stats else scores
    return _hot_stage_pruned(
        scores, hot_tfs, hot_max_w, q_w, rank, is_hot, hot_matmul,
        hot_cell_fn, prune_k=prune_k, with_stats=with_stats)


def _chunk_stream(scores, chunks, safe_q, cold, q_w, *, n_chunks,
                  num_docs, cold_weight_fn):
    """The big cold tiers' postings of a block as one stream of chunks.

    Each (query, slot) whose term is streamed (`cold` and count > 0)
    owns `count` consecutive chunk indices, in row-major slot order;
    chunk i finds its slot by searchsorted over the running count,
    gathers its [C] docs and tfs row, and every chunk is weighted and
    scatter-added into `scores` at (query, doc) in ONE scatter. Work is
    n_chunks * C lanes instead of B * L * P_t per tier. Lanes past a
    term's df hold tf 0 and drop like tier padding; chunk indices past
    the block's need drop whole. A query's updates follow its own slot
    order whatever else shares the block (the coalesced == solo pin).

    The stream is a lax.cond branch, skipped when the block streams
    nothing, so a block of hot or small-tier terms moves no chunk
    lanes whatever its capacity."""
    b, l = safe_q.shape
    term = safe_q.reshape(-1)
    n_s = jnp.where(cold, chunks.count[safe_q], 0).reshape(-1)   # [B*L]
    ends = jnp.cumsum(n_s)
    w_s = q_w.reshape(-1)

    def stream(acc):
        i = jnp.arange(n_chunks, dtype=ends.dtype)
        slot = jnp.minimum(jnp.searchsorted(ends, i, side="right"),
                           b * l - 1)
        live = i < ends[-1]
        row = jnp.where(live, chunks.row0[term[slot]] + i - ends[slot]
                        + n_s[slot], 0)
        docs = chunks.docs[row].astype(jnp.int32)            # [cap, C]
        tfs = chunks.tfs[row].astype(jnp.float32)
        keep = (tfs > 0) & live[:, None]
        w = jnp.where(keep, cold_weight_fn(tfs, docs), 0.0) \
            * w_s[slot][:, None]
        return acc.at[(slot // l)[:, None],
                      jnp.where(keep, docs, num_docs + 1)].add(
                          w, mode="drop")

    return jax.lax.cond(ends[-1] > 0, stream, lambda acc: acc, scores)


def _hot_stage_pruned(partial, hot_tfs, hot_max_w, q_w, rank, is_hot,
                      hot_matmul, hot_cell_fn, *, prune_k, with_stats):
    """Batched rank-safe MaxScore over the hot strip.

    The layout IS the MaxScore partition: hot-strip terms are the
    highest-df (lowest score-bound) lists — the "non-essential" set — and
    the cold tiers (already accumulated exactly into `partial`) are the
    essential lists. Per query:

      tau  = k-th largest cold partial  (lower bound on the true k-th
             full score, since contributions are non-negative)
      ub   = sum over the query's hot terms of q_w * max-weight
             (an upper bound on ANY doc's hot contribution)
      p_C  = C-th largest cold partial  (C = MAXSCORE_CAND)

    If p_C + ub < tau (or ub == 0) for EVERY query in the block, then no
    doc outside the top-C partial candidates can reach the top-k: its
    full score <= partial + ub <= p_C + ub < tau <= true k-th score. The
    whole [B,H]@[H,D+1] hot matmul is then replaced by an exact [B,L,C]
    gather over the candidates — identical top-k, including tie-breaks,
    because every doc scoring >= tau carries its exact full score into
    the same final top-k. One unsafe query sends the block down the full
    matmul (lax.cond), so correctness never depends on the bound being
    tight."""
    b, l = q_w.shape
    # clamped for small doc axes (the diag path; the scoring kernels gate
    # engagement on num_docs + 1 >= 2 * MAXSCORE_CAND before calling)
    c = min(MAXSCORE_CAND, partial.shape[1])
    ub = jnp.sum(jnp.where(is_hot, q_w * hot_max_w[
        jnp.where(is_hot, rank, 0)], 0.0), axis=1)           # [B]
    cand_vals, cand_idx = jax.lax.top_k(partial, c)
    tau = cand_vals[:, min(prune_k, c) - 1]
    p_c = cand_vals[:, -1]
    # the relative margin keeps the bound sound under f32 rounding: the
    # upper bound and the matmul's actual contributions are computed by
    # different f32 expression trees, so the bound can round an ulp below
    # the value it dominates mathematically
    safe_q = (ub <= 0.0) | (p_c + ub * 1.0001 + 1e-6 < tau)  # [B]
    safe = jnp.all(safe_q)

    def pruned(s):
        r_h = jnp.where(is_hot, rank, 0)
        # exact hot contributions for the candidates only: [B, L, C]
        # cells instead of the [H, D+1] strip sweep
        cells = hot_tfs[r_h[:, :, None], cand_idx[:, None, :]]
        w = hot_cell_fn(cells, cand_idx[:, None, :])
        # mul + reduce over L, NOT an einsum (TPU401): a dot_general's
        # algorithm is chosen per shape, so an einsum here could round
        # the same query's candidate sums differently at batch size 1
        # vs 4 — the coalesced == solo pin needs batch-size-invariant
        # lowering (the [B, L, C] intermediate already exists above)
        contrib = jnp.sum(w * jnp.where(is_hot, q_w, 0.0)[:, :, None],
                          axis=1)
        bidx = jnp.broadcast_to(jnp.arange(b)[:, None], cand_idx.shape)
        return s.at[bidx, cand_idx].add(contrib)

    scores = jax.lax.cond(safe, pruned, hot_matmul, partial)
    return (scores, safe_q) if with_stats else scores


# -- block-max pruning (ISSUE 13) -------------------------------------------
# The deep-top-k production path on the tiered layout. The doc axis is
# cut into fixed-width blocks; blockmax.arena (index/blockmax.py) pins a
# per-(hot term, block) score upper bound. The kernel scores the cold
# tiers exactly, takes the running k-th partial score as its threshold,
# and masks every doc block whose best partial plus summed hot bounds
# cannot reach it — a branchless 0/1 lane mask, not a branch — then pays
# the hot-strip stage (the per-dispatch cost: an O(H*D) elementwise
# weighting plus the [B,H]@[H,D+1] matmul) ONLY for the surviving
# blocks' columns. Bit-identity with the exact kernel is structural:
# surviving columns are computed by the same elementwise weighting and
# the same gemm reduction the full-width stage uses (per-column results
# are bit-equal under column restriction — pinned by tests), masked
# docs provably cannot reach the top-k, and the selected columns stay
# doc-ascending so lax.top_k tie order is preserved. A batch whose
# surviving blocks overflow the static budget falls back to the exact
# full-width stage inside the same program (lax.cond) — also
# bit-identical, just unpruned.

# sound-bound safety margins: the ub reduction and the actual hot
# contributions are computed by different f32 expression trees, so the
# mask comparison pads the bound exactly like _hot_stage_pruned does
BLOCKMAX_REL_MARGIN = 1.0001
BLOCKMAX_ABS_MARGIN = 1e-6


def blockmax_cand_blocks(k: int, num_docs: int, width: int) -> int:
    """The static selected-block budget for one block-max dispatch: a
    quarter of the doc axis, floored so the candidate columns can hold
    at least 2k docs (deep k engages instead of tripping the overflow
    fallback) plus a small minimum. TPU_IR_BLOCKMAX_BLOCKS overrides."""
    from ..utils import envvars

    nblk = -(-(num_docs + 1) // width)
    override = envvars.get_int("TPU_IR_BLOCKMAX_BLOCKS")
    if override:
        return min(nblk, override)
    need_k = -(-2 * k // width) + 1
    return min(nblk, max(nblk // 4, need_k, 4))


def _blockmax_topk(q_terms, hot_rank, hot_tfs, tier_of, row_of,
                   tier_docs, tier_tfs, q_weight, hot_blk_bound, *,
                   num_docs, k, width, cand_blocks, hot_weight_fn,
                   cold_weight_fn, hot_cell_fn, chunks=None,
                   n_chunks=None):
    """Shared block-max top-k accumulation (see the section comment).

    `hot_blk_bound` f32 [H, nblk] is the per-mode per-block score upper
    bound (weight_fn of the stored block max tf; BM25 folds the block's
    min doc-length norm — search/scorer.py builds it). Returns
    (scores [B,k], docnos [B,k], stats int64 [3]) with stats =
    (block lanes considered, block lanes masked, fallback flag)."""
    b = q_terms.shape[0]
    d1 = num_docs + 1
    h = hot_tfs.shape[0]
    nblk = hot_blk_bound.shape[1]
    dpad = nblk * width
    cbw = cand_blocks * width
    if k > cbw or k > d1:
        raise ValueError(f"k={k} exceeds the block-max candidate budget "
                         f"({cand_blocks} blocks x {width}, doc axis "
                         f"{d1}); widen TPU_IR_BLOCKMAX_BLOCKS or "
                         "disable blockmax")
    vocab_size = hot_rank.shape[0]
    safe_q = jnp.where(q_terms >= 0, q_terms, 0)
    q_valid = (q_terms >= 0) & (q_terms < vocab_size)
    q_w = q_weight[safe_q] * q_valid                         # [B, L]
    rank = hot_rank[safe_q]
    is_hot = (rank >= 0) & q_valid

    def hot_matmul_w(w_cells):
        # the SAME scatter + gemm expression the exact kernel's hot
        # stage uses — w_cells is the (full or column-restricted)
        # weighted strip
        w_hot = jnp.zeros((b, h), jnp.float32).at[
            jnp.broadcast_to(jnp.arange(b)[:, None], rank.shape),
            jnp.where(is_hot, rank, h),
        ].add(jnp.where(is_hot, q_w, 0.0), mode="drop")      # [B, H]
        # the same contraction as the exact kernel's hot matmul —
        # column-restriction bit-equality with it is exactly what the
        # blockmax parity suite pins, so both sides share _hot_gemm
        return _hot_gemm(w_hot, w_cells)

    # exact cold partial — the identical tier accumulation the exact
    # kernel runs first (cold-first order, see _tiered_scores)
    partial = _tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs,
        tier_tfs, q_weight, num_docs=num_docs,
        hot_weight_fn=hot_weight_fn, cold_weight_fn=cold_weight_fn,
        skip_hot=True, chunks=chunks, n_chunks=n_chunks)     # [B, D+1]

    # running threshold: the k-th best cold partial is a lower bound on
    # the true k-th full score (hot contributions are non-negative).
    # Col 0 is the dead slot, excluded exactly like _topk_from_scores.
    # The k-th value is read as a MIN-reduce over the descending top-k
    # values, not vals[:, -1]: slicing a top_k output whose indices are
    # unused makes XLA CPU rewrite the TopK custom call into a full
    # variadic sort (measured 8 ms -> 410 ms on [64, 50001]).
    pmask = partial.at[:, 0].set(-jnp.inf)
    tau = jnp.min(jax.lax.top_k(pmask, k)[0], axis=1)        # [B]

    # per-(query, block) hot upper bound: sum of each hot query slot's
    # weighted block bound — mul+reduce over L (batch-size-invariant
    # rounding, the ISSUE 9 rule; soundness is margin-padded below)
    brows = hot_blk_bound[jnp.where(is_hot, rank, 0)]        # [B, L, nblk]
    ub = jnp.sum(brows * jnp.where(is_hot, q_w, 0.0)[:, :, None],
                 axis=1)                                     # [B, nblk]

    ppad = jnp.pad(pmask, ((0, 0), (0, dpad - d1)),
                   constant_values=-jnp.inf)
    blk_pmax = ppad.reshape(b, nblk, width).max(axis=2)      # [B, nblk]
    # THE 0/1 block-lane mask: a lane survives iff some doc in it could
    # still reach the top-k (best partial + summed hot bounds >= tau).
    # Blocks holding current top-k partials survive automatically
    # (blk_pmax >= tau with ub >= 0), so the final subset top-k below
    # can never lose a winner.
    need = (blk_pmax + ub * BLOCKMAX_REL_MARGIN
            + BLOCKMAX_ABS_MARGIN >= tau[:, None])           # [B, nblk]
    # rows with NO valid terms (rung/block padding, empty-after-analysis
    # queries) contribute exact 0.0 everywhere and can never surface a
    # doc — but their tau is 0, which would mark every block needed and
    # poison the batch union into the fallback on every padded dispatch.
    # Masking their need rows is bit-safe: their outputs are all-empty
    # under either branch.
    need = need & q_valid.any(axis=1)[:, None]
    needed_any = jnp.any(need, axis=0)                       # [nblk]
    n_needed = jnp.sum(needed_any)
    # selected blocks: the batch-union of surviving lanes (ties and
    # spare budget fill deterministically by block order). Ascending
    # sort keeps candidate columns doc-ascending — lax.top_k tie order.
    sel = jnp.sort(
        jax.lax.top_k(needed_any.astype(jnp.float32), cand_blocks)[1])
    safe = n_needed <= cand_blocks
    cols = (sel[:, None] * width
            + jnp.arange(width)[None, :]).reshape(-1)        # [CBW]
    # blocks_masked reports REALIZED skips: a fallback dispatch ran the
    # exact full-width stage, so its maskable lanes count 0 — operators
    # read masked/considered as the achieved skip fraction (RUNBOOK §20)
    stats = jnp.stack([
        jnp.int32(b * nblk),
        jnp.where(safe,
                  jnp.int32(b * nblk) - jnp.sum(need).astype(jnp.int32),
                  jnp.int32(0)),
        jnp.where(safe, jnp.int32(0), jnp.int32(1))])

    def pruned(_):
        # weight + gemm over the surviving columns only: each column's
        # result is bit-equal to the full-width stage's same column
        # (same elementwise weights, same gemm reduction — pinned)
        cols_c = jnp.minimum(cols, d1 - 1)
        cells = hot_cell_fn(hot_tfs[:, cols_c], cols_c[None, :])
        cand = ppad[:, cols] + hot_matmul_w(cells)           # [B, CBW]
        top_s, idx = jax.lax.top_k(cand, k)
        docnos = cols[idx]
        matched = top_s > 0.0
        return (jnp.where(matched, top_s, 0.0),
                jnp.where(matched, docnos, 0).astype(jnp.int32))

    def full(_):
        # overflow fallback: the exact kernel's hot stage, verbatim
        scores = partial + hot_matmul_w(hot_weight_fn(hot_tfs))
        return _topk_from_scores(scores, k)

    s, d = jax.lax.cond(safe, pruned, full, None)
    return s, d, stats


@partial(profiled_jit, static_argnames=("k", "num_docs", "width",
                                   "cand_blocks", "compat_int_idf",
                                   "hot_preweighted", "n_chunks"))
def tfidf_topk_blockmax(
    q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
    df, n_scalar, hot_blk_bound, *, num_docs: int, width: int,
    cand_blocks: int, k: int = 10, compat_int_idf: bool = False,
    hot_preweighted: bool = False, chunks: ColdChunks | None = None,
    n_chunks: int | None = None,
):
    """Block-max TF-IDF top-k on the tiered layout — the deep-k
    production kernel (see the section comment). Returns
    (scores [B,k], docnos [B,k], stats [3])."""
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = idf_weights(df, n_scalar, compat_int_idf)
    cell_fn = lambda tfs, docs: _lntf(tfs)  # noqa: E731
    return _blockmax_topk(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        idf, hot_blk_bound, num_docs=num_docs, k=k, width=width,
        cand_blocks=cand_blocks,
        hot_weight_fn=_identity_weight if hot_preweighted else _lntf,
        cold_weight_fn=cell_fn,
        hot_cell_fn=((lambda tfs, docs: tfs) if hot_preweighted
                     else cell_fn), chunks=chunks, n_chunks=n_chunks)


@partial(profiled_jit, static_argnames=("k", "num_docs", "width",
                                   "cand_blocks", "k1", "b",
                                   "hot_preweighted", "n_chunks"))
def bm25_topk_blockmax(
    q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
    df, doc_len, n_scalar, hot_blk_bound, *, num_docs: int, width: int,
    cand_blocks: int, k: int = 10, k1: float = 0.9, b: float = 0.4,
    hot_preweighted: bool = False, chunks: ColdChunks | None = None,
    n_chunks: int | None = None,
):
    """Block-max Okapi BM25 top-k on the tiered layout (see
    tfidf_topk_blockmax). The per-block bound operand must dominate the
    saturation weights (the scorer folds each block's min doc-length
    norm into it); the hot cell weights here gather the SAME per-doc
    dl_norm the exact kernel broadcasts, so surviving columns are
    bit-equal to the full-width stage."""
    n = jnp.asarray(n_scalar, jnp.float32)
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = bm25_idf_weights(df, n)
    dlf = doc_len.astype(jnp.float32)
    avg_dl = jnp.sum(dlf) / jnp.maximum(n, 1.0)
    dl_norm = 1.0 - b + b * dlf / jnp.maximum(avg_dl, 1e-9)   # [D+1]
    cell_fn = (lambda tfs, docs: bm25_saturation(tfs, dl_norm[docs],
                                                 k1=k1))
    return _blockmax_topk(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        idf, hot_blk_bound, num_docs=num_docs, k=k, width=width,
        cand_blocks=cand_blocks,
        hot_weight_fn=(_identity_weight if hot_preweighted else
                       lambda tf: bm25_saturation(tf, dl_norm[None, :],
                                                  k1=k1)),
        cold_weight_fn=cell_fn,
        hot_cell_fn=((lambda tfs, docs: tfs) if hot_preweighted
                     else cell_fn), chunks=chunks, n_chunks=n_chunks)


# -- pre-weighted hot strips (ISSUE 13) -------------------------------------
# The tiered hot stage is weight_fn(strip) followed by a gemm; the
# weighting is an O(H * D) elementwise pass over a QUERY-INDEPENDENT
# surface, recomputed every dispatch (measured: the dominant full-kernel
# cost on CPU-class backends — ~5x the gemm it feeds). These kernels
# materialize each scoring mode's weighted strip once; the Scorer caches
# the result on device (budget-gated) and the tiered kernels take it
# through `hot_preweighted=True` with an identity weight fn. Values are
# bit-identical to the in-kernel weighting — the same elementwise
# expression on the same operands, and elementwise chains have no
# reassociation freedom — which the parity suite pins.


def _identity_weight(strip):
    return strip


@profiled_jit
def lntf_strip(hot_tfs: jax.Array) -> jax.Array:
    """(1 + ln tf) over the raw-tf hot strip — the TF-IDF (and cosine
    rerank) hot weighting, materialized."""
    return _lntf(hot_tfs)


@partial(profiled_jit, static_argnames=("k1", "b"))
def bm25_strip(hot_tfs: jax.Array, doc_len: jax.Array, n_scalar: jax.Array,
               *, k1: float = 0.9, b: float = 0.4) -> jax.Array:
    """BM25 saturation over the raw-tf hot strip with the doc-length
    norm broadcast — the same expression _bm25_tiered_scores' hot
    weighting traces, materialized."""
    n = jnp.asarray(n_scalar, jnp.float32)
    dlf = doc_len.astype(jnp.float32)
    avg_dl = jnp.sum(dlf) / jnp.maximum(n, 1.0)
    dl_norm = 1.0 - b + b * dlf / jnp.maximum(avg_dl, 1e-9)
    return bm25_saturation(hot_tfs, dl_norm[None, :], k1=k1)


def _tfidf_tiered_scores(q_terms, hot_rank, hot_tfs, tier_of, row_of,
                         tier_docs, tier_tfs, df, n_scalar, hot_max_tf, *,
                         num_docs, prune_k, compat_int_idf, prune,
                         skip_hot, hot_only, hot_preweighted=False,
                         chunks=None, n_chunks=None) -> jax.Array:
    """[B, D+1] tiered TF-IDF accumulation — shared verbatim between the
    production top-k kernel and the explain score-gather variant
    (prune_k is the production kernel's k; the prune gate and candidate
    machinery must see the same value to trace the same program)."""
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = idf_weights(df, n_scalar, compat_int_idf)

    # the runtime-bounded prune variant gathers RAW cells, so it and the
    # pre-weighted strip are mutually exclusive (production passes
    # neither hot_max_tf nor prune there — this is belt and braces)
    do_prune = (not skip_hot and not hot_only and not hot_preweighted
                and _prune_applicable(prune_k, num_docs, prune)
                and hot_max_tf is not None)
    # one weight model for cold postings AND pruned hot candidates: the
    # rank-safety contract depends on the two staying identical
    cell_fn = lambda tfs, docs: _lntf(tfs)  # noqa: E731
    return _tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        idf, num_docs=num_docs,
        hot_weight_fn=_identity_weight if hot_preweighted else _lntf,
        cold_weight_fn=cell_fn,
        hot_cell_fn=cell_fn if do_prune else None,
        hot_max_w=_lntf(hot_max_tf.astype(jnp.float32)) if do_prune else None,
        prune_k=prune_k if do_prune else None, skip_hot=skip_hot,
        skip_cold=hot_only, chunks=chunks, n_chunks=n_chunks)


@partial(profiled_jit, static_argnames=("k", "num_docs", "compat_int_idf",
                                   "prune", "skip_hot", "hot_only",
                                   "hot_preweighted", "n_chunks"))
def tfidf_topk_tiered(
    q_terms: jax.Array,        # int32 [B, L]
    hot_rank: jax.Array,       # int32 [V]: row in hot_tfs, or -1 (cold)
    hot_tfs: jax.Array,        # f32 [H, D+1] dense raw-tf rows, hot terms
    tier_of: jax.Array,        # int32 [V] tier index for cold terms
    row_of: jax.Array,         # int32 [V] row within the tier
    tier_docs: tuple,          # of int32 [V_t, P_t]
    tier_tfs: tuple,           # of int32 [V_t, P_t]
    df: jax.Array,             # int32 [V]
    n_scalar: jax.Array,       # int32 scalar (N)
    hot_max_tf: jax.Array | None = None,  # f32/int [H] max tf per hot row
    *,
    num_docs: int,
    k: int = 10,
    compat_int_idf: bool = False,
    prune: bool = False,
    skip_hot: bool = False,
    hot_only: bool = False,
    hot_preweighted: bool = False,
    chunks: ColdChunks | None = None,
    n_chunks: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """TF-IDF top-k on the tiered sparse layout (search/layout.py): the
    budget-capped hot strip bounds dense memory, geometric tier capacities
    bound padding waste, and every shape stays static under jit.

    INVARIANT (all tiered kernels): the traced `n_scalar` and the static
    `num_docs` must be the same N. The pair exists because the sharded
    path's accumulator width (dblk) genuinely differs from the global N
    its idf needs; on the single-device kernels a divergence would not
    error — idf/avg_dl would use one N and the accumulator/prune gate
    the other, silently mis-scaling every score.

    `skip_hot=True` (static) omits the hot-strip stage entirely — exact
    when the caller certified no query term is hot (the Scorer's
    scheduled MaxScore path). `prune=True` (with `hot_max_tf`) is the
    runtime-bounded variant (`_hot_stage_pruned`) for mixed blocks.
    `hot_only=True` (static) is the opposite degradation: score ONLY the
    hot strip (the overload ladder's cheapest device level; results are
    partial and must be tagged by the caller). `hot_preweighted=True`
    (static) declares `hot_tfs` ALREADY weighted (lntf_strip) — the hot
    stage skips its per-dispatch elementwise pass; bit-identical.
    `chunks`/`n_chunks` stream the big cold tiers (`_tiered_scores`)."""
    scores = _tfidf_tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        df, n_scalar, hot_max_tf, num_docs=num_docs, prune_k=k,
        compat_int_idf=compat_int_idf, prune=prune, skip_hot=skip_hot,
        hot_only=hot_only, hot_preweighted=hot_preweighted, chunks=chunks,
        n_chunks=n_chunks)
    return _topk_from_scores(scores, k)


@partial(profiled_jit, static_argnames=("num_docs", "prune_k",
                                   "compat_int_idf", "prune", "skip_hot",
                                   "hot_only", "n_chunks"))
def tfidf_scores_at_tiered(
    q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
    df, n_scalar, cand, hot_max_tf=None, *, num_docs: int,
    prune_k: int = 10, compat_int_idf: bool = False, prune: bool = False,
    skip_hot: bool = False, hot_only: bool = False,
    chunks: ColdChunks | None = None, n_chunks: int | None = None,
) -> jax.Array:
    """Explain debug variant of tfidf_topk_tiered: the same accumulation
    (same static flags, `prune_k` = the production k so the prune gate
    and candidate set trace identically), read out at `cand` [B, C]."""
    scores = _tfidf_tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        df, n_scalar, hot_max_tf, num_docs=num_docs, prune_k=prune_k,
        compat_int_idf=compat_int_idf, prune=prune, skip_hot=skip_hot,
        hot_only=hot_only, chunks=chunks, n_chunks=n_chunks)
    return jnp.take_along_axis(scores, cand.astype(jnp.int32), axis=1)


@partial(profiled_jit, static_argnames=("k", "num_docs", "k1", "b", "prune",
                                   "skip_hot", "hot_only",
                                   "hot_preweighted", "n_chunks"))
def bm25_topk_tiered(
    q_terms: jax.Array,        # int32 [B, L]
    hot_rank: jax.Array,       # int32 [V]
    hot_tfs: jax.Array,        # f32 [H, D+1] raw tf
    tier_of: jax.Array,        # int32 [V]
    row_of: jax.Array,         # int32 [V]
    tier_docs: tuple,          # of int32 [V_t, P_t]
    tier_tfs: tuple,           # of int32 [V_t, P_t]
    df: jax.Array,             # int32 [V]
    doc_len: jax.Array,        # int32 [D+1] (slot 0 dead)
    n_scalar: jax.Array,       # int32 scalar (N)
    hot_max_tf: jax.Array | None = None,  # f32/int [H] max tf per hot row
    *,
    num_docs: int,
    k: int = 10,
    k1: float = 0.9,
    b: float = 0.4,
    prune: bool = False,
    skip_hot: bool = False,
    hot_only: bool = False,
    hot_preweighted: bool = False,
    chunks: ColdChunks | None = None,
    n_chunks: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Okapi BM25 on the tiered sparse layout — the scorer variant that
    makes BM25 usable past the dense-matrix budget (MS MARCO-scale corpora).
    Hot terms: saturation over dense raw-tf rows with the [D+1] length norm
    broadcast. Cold terms: per-posting saturation with the length norm
    gathered at each posting's docno.

    `prune=True` (with `hot_max_tf`) enables rank-safe MaxScore pruning of
    the hot-strip stage. The BM25 upper bound uses the saturation curve at
    (max tf, min doc-length norm): saturation is increasing in tf and
    decreasing in dl_norm, so sat(tf, d) <= sat(max_tf, dl_min) for every
    posting of the row. `hot_preweighted=True` (static) declares
    `hot_tfs` ALREADY saturated (bm25_strip) — bit-identical, minus the
    per-dispatch elementwise pass. `chunks`/`n_chunks` as in
    tfidf_topk_tiered."""
    scores = _bm25_tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        df, doc_len, n_scalar, hot_max_tf, num_docs=num_docs, prune_k=k,
        k1=k1, b=b, prune=prune, skip_hot=skip_hot, hot_only=hot_only,
        hot_preweighted=hot_preweighted, chunks=chunks, n_chunks=n_chunks)
    return _topk_from_scores(scores, k)


# -- donated-query twins (ISSUE 9) ------------------------------------------
# The coalescing serving frontend dispatches one padded query batch per
# kernel call; the int32 [B, L] query block is freshly uploaded per call
# and never read again host-side, so its device buffer is DONATED
# (SNIPPETS.md pjit donate_argnums pattern) — XLA may alias it into the
# outputs instead of holding both live. The index-side operands stay
# resident and undonated. These are separate entry points (not a flag on
# the production kernels) because the rerank pipeline REUSES its query
# array across two kernel calls — donating there would be use-after-free.


def _donated_query_twin(kernel, **jit_kwargs):
    """Twin of a profiled_jit kernel with arg 0 (the query block)
    donated; identical math — same traced function object."""
    return profiled_jit(kernel.__wrapped__, label=kernel.label + "_dq",
                        donate_argnums=(0,), **jit_kwargs)


tfidf_topk_dense_dq = _donated_query_twin(
    tfidf_topk_dense, static_argnames=("k", "compat_int_idf"))
bm25_topk_dense_dq = _donated_query_twin(
    bm25_topk_dense, static_argnames=("k", "k1", "b"))
tfidf_topk_tiered_dq = _donated_query_twin(
    tfidf_topk_tiered, static_argnames=("k", "num_docs", "compat_int_idf",
                                        "prune", "skip_hot", "hot_only",
                                        "hot_preweighted", "n_chunks"))
bm25_topk_tiered_dq = _donated_query_twin(
    bm25_topk_tiered, static_argnames=("k", "num_docs", "k1", "b", "prune",
                                       "skip_hot", "hot_only",
                                       "hot_preweighted", "n_chunks"))
tfidf_topk_blockmax_dq = _donated_query_twin(
    tfidf_topk_blockmax, static_argnames=("k", "num_docs", "width",
                                          "cand_blocks", "compat_int_idf",
                                          "hot_preweighted", "n_chunks"))
bm25_topk_blockmax_dq = _donated_query_twin(
    bm25_topk_blockmax, static_argnames=("k", "num_docs", "width",
                                         "cand_blocks", "k1", "b",
                                         "hot_preweighted", "n_chunks"))


def _bm25_tiered_scores(q_terms, hot_rank, hot_tfs, tier_of, row_of,
                        tier_docs, tier_tfs, df, doc_len, n_scalar,
                        hot_max_tf, *, num_docs, prune_k, k1, b, prune,
                        skip_hot, hot_only, hot_preweighted=False,
                        chunks=None, n_chunks=None) -> jax.Array:
    """[B, D+1] tiered BM25 accumulation — shared verbatim between the
    production top-k kernel and the explain score-gather variant."""
    n = jnp.asarray(n_scalar, jnp.float32)
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = bm25_idf_weights(df, n)
    dlf = doc_len.astype(jnp.float32)
    avg_dl = jnp.sum(dlf) / jnp.maximum(n, 1.0)
    dl_norm = 1.0 - b + b * dlf / jnp.maximum(avg_dl, 1e-9)  # [D+1]

    do_prune = (not skip_hot and not hot_only and not hot_preweighted
                and _prune_applicable(prune_k, num_docs, prune)
                and hot_max_tf is not None)
    if do_prune:
        # slot 0 is the dead column (doc_len 0 -> the global minimum of
        # dl_norm); exclude it so the bound reflects real documents
        # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
        # the explain variants pin this exact traced expression — hoisting
        # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
        dl_min = jnp.min(dl_norm[1:])
        # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
        # the explain variants pin this exact traced expression — hoisting
        # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
        hot_max_w = bm25_saturation(hot_max_tf.astype(jnp.float32),
                                    dl_min, k1=k1)
    else:
        hot_max_w = None

    # one weight model for cold postings AND pruned hot candidates: the
    # rank-safety contract depends on the two staying identical
    cell_fn = (lambda tfs, docs: bm25_saturation(tfs, dl_norm[docs],
                                                 k1=k1))
    return _tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        idf, num_docs=num_docs,
        # hot_weight_fn sees the whole [H, D+1] strip (doc axis last)
        hot_weight_fn=(_identity_weight if hot_preweighted else
                       lambda tf: bm25_saturation(tf, dl_norm[None, :],
                                                  k1=k1)),
        cold_weight_fn=cell_fn,
        hot_cell_fn=cell_fn if do_prune else None,
        hot_max_w=hot_max_w,
        prune_k=prune_k if do_prune else None, skip_hot=skip_hot,
        skip_cold=hot_only, chunks=chunks, n_chunks=n_chunks)


@partial(profiled_jit, static_argnames=("num_docs", "prune_k", "k1", "b",
                                   "prune", "skip_hot", "hot_only",
                                   "n_chunks"))
def bm25_scores_at_tiered(
    q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
    df, doc_len, n_scalar, cand, hot_max_tf=None, *, num_docs: int,
    prune_k: int = 10, k1: float = 0.9, b: float = 0.4,
    prune: bool = False, skip_hot: bool = False, hot_only: bool = False,
    chunks: ColdChunks | None = None, n_chunks: int | None = None,
) -> jax.Array:
    """Explain debug variant of bm25_topk_tiered — [B, C] f32 at `cand`
    (see tfidf_scores_at_tiered for the shared-accumulation contract)."""
    scores = _bm25_tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        df, doc_len, n_scalar, hot_max_tf, num_docs=num_docs,
        prune_k=prune_k, k1=k1, b=b, prune=prune, skip_hot=skip_hot,
        hot_only=hot_only, chunks=chunks, n_chunks=n_chunks)
    return jnp.take_along_axis(scores, cand.astype(jnp.int32), axis=1)


@partial(profiled_jit, static_argnames=("k", "num_docs", "compat_int_idf"))
def tfidf_prune_diag(
    q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
    df, n_scalar, hot_max_tf, *, num_docs: int, k: int = 10,
    compat_int_idf: bool = False,
) -> jax.Array:
    """Diagnostic: per-query MaxScore safety flags [B] for a TF-IDF block
    (True = the query alone would permit pruning; the block prunes iff all
    are True). Used by tests and the bench's engagement report — the
    scoring kernels keep their (scores, docnos) signature."""
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = idf_weights(df, n_scalar, compat_int_idf)
    cell_fn = lambda tfs, docs: _lntf(tfs)  # noqa: E731
    _, safe = _tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        idf, num_docs=num_docs, hot_weight_fn=_lntf,
        cold_weight_fn=cell_fn, hot_cell_fn=cell_fn,
        hot_max_w=_lntf(hot_max_tf.astype(jnp.float32)),
        prune_k=k, with_stats=True)
    return safe


def _topk_over_candidates(cand_scores, cand_docnos, k):
    """Top-k over per-candidate scores [B, C]; docno 0 marks empty slots."""
    cand = jnp.where(cand_docnos > 0, cand_scores, -jnp.inf)
    top_scores, idx = jax.lax.top_k(cand, min(k, cand.shape[-1]))
    docnos = jnp.take_along_axis(cand_docnos, idx, axis=1)
    matched = top_scores > 0.0
    return (jnp.where(matched, top_scores, 0.0),
            jnp.where(matched, docnos, 0).astype(jnp.int32))


@partial(profiled_jit, static_argnames=("k",))
def cosine_rerank_dense(
    q_terms: jax.Array,     # int32 [B, L]
    doc_matrix: jax.Array,  # f32 [V, D+1] (1+ln tf)
    df: jax.Array,          # int32 [V]
    doc_norm: jax.Array,    # f32 [D+1] ||d|| under (1+ln tf)*idf weights
    cand_docnos: jax.Array,  # int32 [B, C] stage-1 candidates (0 = empty)
    num_docs: jax.Array,    # int32 scalar
    *,
    k: int = 10,
) -> tuple[jax.Array, jax.Array]:
    """Stage-2 reranker: cosine-normalized TF-IDF over stage-1 candidates.

    score(q, d) = sum over query-term slots of idf(t)^2 * (1 + ln tf(t, d)),
    divided by ||d|| under (1 + ln tf) * idf doc weights. Duplicate query
    terms contribute once per slot — deliberately matching the first-stage
    scorers and the reference's per-slot accumulation
    (IntDocVectorsForwardIndex.java:192-223). The reference has no rerank;
    this is the MS MARCO-shaped candidates->rerank composition. Work is
    B*L*C, not B*L*D: only the candidates' matrix cells are gathered."""
    scores = _cosine_dense_scores(q_terms, doc_matrix, df, doc_norm,
                                  cand_docnos, num_docs)
    return _topk_over_candidates(scores, cand_docnos, k)


def _cosine_dense_scores(q_terms, doc_matrix, df, doc_norm, cand_docnos,
                         num_docs) -> jax.Array:
    """[B, C] per-candidate cosine scores — shared between the production
    rerank kernel and the explain variant (same candidate-set shape =>
    the same traced program => bit-identical per-candidate floats)."""
    vocab_size = doc_matrix.shape[0]
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = idf_weights(df, num_docs)
    safe_q = jnp.where(q_terms >= 0, q_terms, 0)
    q_valid = (q_terms >= 0) & (q_terms < vocab_size)
    q_idf = jnp.where(q_valid, idf[safe_q], 0.0)             # [B, L]
    # one fused gather of exactly the candidate columns: [B, L, C]
    cand_tf = doc_matrix[safe_q[:, :, None],
                         cand_docnos.astype(jnp.int32)[:, None, :]]
    # mul + reduce, not einsum: batch-size-invariant rounding (see
    # _tfidf_dense_scores — the coalesced == solo bit-exactness pin)
    scores = jnp.sum(cand_tf * (q_idf * q_idf)[:, :, None], axis=1)
    return scores / jnp.maximum(doc_norm[cand_docnos], 1e-30)


@profiled_jit
def cosine_scores_at_dense(q_terms, doc_matrix, df, doc_norm, cand_docnos,
                           num_docs) -> jax.Array:
    """Explain debug variant of cosine_rerank_dense: the per-candidate
    cosine scores in CANDIDATE order ([B, C]), no top-k reorder. Callers
    must pass the SAME candidate matrix shape the production rerank used
    so the traced reduction is identical."""
    return _cosine_dense_scores(q_terms, doc_matrix, df, doc_norm,
                                cand_docnos, num_docs)


@partial(profiled_jit, static_argnames=("k", "num_docs", "hot_preweighted",
                                   "n_chunks"))
def cosine_rerank_tiered(
    q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
    df, doc_norm, n_scalar, cand_docnos, *, num_docs: int, k: int = 10,
    hot_preweighted: bool = False, chunks: ColdChunks | None = None,
    n_chunks: int | None = None,
):
    """cosine_rerank_dense on the tiered sparse layout (large corpora).
    The tiered accumulation is doc-axis-wide by construction, so this path
    scores [B, D+1] and then gathers the candidates. `hot_preweighted`
    takes the cached (1 + ln tf) strip (lntf_strip — the SAME weighting
    this kernel applies; the TF-IDF top-k shares the cache)."""
    cand_scores = _cosine_tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        df, doc_norm, n_scalar, cand_docnos, num_docs=num_docs,
        hot_preweighted=hot_preweighted, chunks=chunks, n_chunks=n_chunks)
    return _topk_over_candidates(cand_scores, cand_docnos, k)


def _cosine_tiered_scores(q_terms, hot_rank, hot_tfs, tier_of, row_of,
                          tier_docs, tier_tfs, df, doc_norm, n_scalar,
                          cand_docnos, *, num_docs, hot_preweighted=False,
                          chunks=None, n_chunks=None) -> jax.Array:
    """[B, C] per-candidate tiered cosine scores — shared between the
    production rerank kernel and the explain variant."""
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = idf_weights(df, n_scalar)
    scores = _tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        idf * idf, num_docs=num_docs,
        hot_weight_fn=_identity_weight if hot_preweighted else _lntf,
        cold_weight_fn=lambda tfs, docs: _lntf(tfs), chunks=chunks,
        n_chunks=n_chunks)
    # gather the C candidates FIRST, then normalize: dividing the full
    # [B, D+1] matrix before a [B, C] gather is ~D/C times the divides
    # plus a full-width temporary per rerank block (elementwise divide
    # commutes with take_along_axis, like cosine_rerank_dense)
    cand = cand_docnos.astype(jnp.int32)
    return (jnp.take_along_axis(scores, cand, axis=1)
            / jnp.maximum(doc_norm[cand], 1e-30))


@partial(profiled_jit, static_argnames=("num_docs", "n_chunks"))
def cosine_scores_at_tiered(q_terms, hot_rank, hot_tfs, tier_of, row_of,
                            tier_docs, tier_tfs, df, doc_norm, n_scalar,
                            cand_docnos, *, num_docs: int,
                            chunks: ColdChunks | None = None,
                            n_chunks: int | None = None) -> jax.Array:
    """Explain debug variant of cosine_rerank_tiered: per-candidate
    cosine scores in candidate order ([B, C]), no top-k reorder."""
    return _cosine_tiered_scores(
        q_terms, hot_rank, hot_tfs, tier_of, row_of, tier_docs, tier_tfs,
        df, doc_norm, n_scalar, cand_docnos, num_docs=num_docs,
        chunks=chunks, n_chunks=n_chunks)


@partial(profiled_jit, static_argnames=("k", "num_docs", "compat_int_idf"))
def tfidf_topk_sparse(
    q_terms: jax.Array,        # int32 [B, L]
    post_docs: jax.Array,      # int32 [V, P] padded per-term postings (docnos)
    post_tfs: jax.Array,       # int32 [V, P] padded tfs (0 = empty slot)
    df: jax.Array,             # int32 [V]
    n_scalar: jax.Array,       # int32 scalar (N)
    *,
    num_docs: int,
    k: int = 10,
    compat_int_idf: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Sparse scoring: scatter each query term's postings into a doc-axis
    accumulator. Work is B*L*P instead of B*L*D."""
    # lint: invariant-ok (O(V)/O(D) weight-vector prep, fused in-trace;
    # the explain variants pin this exact traced expression — hoisting
    # would fork it. The O(H*D) strip class IS cached: _hot_wstrip)
    idf = idf_weights(df, n_scalar, compat_int_idf)

    # both bounds, like every sibling kernel: an id >= V would clamp all
    # its gathers to the last vocabulary term and silently score it
    q_valid = (q_terms >= 0) & (q_terms < post_docs.shape[0])
    safe_q = jnp.where(q_valid, q_terms, 0)                # [B, L]
    docs = post_docs[safe_q]                                # [B, L, P]
    tfs = post_tfs[safe_q].astype(jnp.float32)              # [B, L, P]
    w = _lntf(tfs) * idf[safe_q][..., None] * q_valid[..., None]
    slot = jnp.where((tfs > 0) & q_valid[..., None], docs, num_docs + 1)

    def score_one(slots_q, w_q):
        acc = jnp.zeros((num_docs + 1,), jnp.float32)
        return acc.at[slots_q.ravel()].add(w_q.ravel(), mode="drop")

    scores = jax.vmap(score_one)(slot, w)                   # [B, D+1]
    return _topk_from_scores(scores, k)
