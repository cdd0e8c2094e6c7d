"""Phrase queries on the device: one ordered-window match over a block's
anchor positions, then BM25 / TF-IDF over the matched docs only, then
their top-k.

The positional index lives on the device as one `PositionTable`, in two
views of the same positions: every term's positions, doc-major
((docno, position) order), concatenated in term-id order with each
term's start and count (cf); and every document's token stream (the
term at each position, doc-major). Both are flat: on a Zipf vocabulary
most terms have a handful of positions, so per-term rows of a fixed
width would pad by more than the positions themselves.

A block of quoted queries is dispatched as rows, one per quoted span:

- `phrase_match`: each row streams the positions of its anchor term
  (the span's rarest term, by cf) in chunks of PHRASE_CHUNK lanes, as
  the cold chunk stream of ops/scoring.py does for postings: a chunk
  starts anywhere in a table row, so it is read as the two rows it
  spans (row gathers) shifted into place by a barrel shift of lane
  rotations. (A gather of unaligned 128-wide slices lowers to a loop
  of one dynamic slice a chunk, some 10**5 device ops a block.) Around an anchor at doc d, position x and span index a,
  the doc's own tokens from x - span to x + span are read, and the
  chain runs backward (each earlier term takes its largest position
  before the current one) and forward (each later term its smallest
  position after the current one) within them. A window exists iff
  some anchor's chain spans at most span = (m - 1) + slop: the backward
  chain maximizes the window's first position and the forward chain
  minimizes its last, for that anchor, and every window holds some
  anchor position. At slop 0 it is exact adjacency. Positions outside
  the doc are masked, so a window never crosses a document boundary,
  and positions are compared per document, so no docno x position key
  can overflow int32. Surviving anchors are compacted to one (row,
  docno) entry per matched doc, in (row, docno) order, with each row's
  count, and the token-stream rows the distinct matched docs hold
  (which size phrase_topk's row stream; the host reads them with the
  counts, one sync a block).
- `phrase_topk`: over the first `cap` candidates (the host buckets the
  count), a doc stays only as its query's first span's candidate and
  only if every other span of the query matched it too (binary search
  in the same sorted candidate list). Each query term's tf in the doc
  is counted in the doc's token stream, streamed as slices of
  PHRASE_SLICE tokens, and weighted by the shared BM25 or TF-IDF
  curves; each query's candidates fill one row of a [B, width] matrix,
  and its top-k is lax.top_k of that row. Zero-score matches stay,
  after the positive ones, docno ascending. (No sort: a TPU sort of a
  million candidates takes minutes to compile.)

Work is the block's anchor positions (bucketed to a power of two) times
2 * span + 1 token reads, and the matched docs' tokens: never a [B, D+1]
score buffer, and no per-lane binary search through a posting run (a
random gather a step, the slow access on the chip).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs.profiling import profiled_jit
from .scoring import _lntf, bm25_idf_weights, bm25_saturation, idf_weights

# lanes per anchor chunk: a row streams ceil(cf(anchor) / PHRASE_CHUNK)
# chunks of its anchor term's positions
PHRASE_CHUNK = 128
# tokens per row of the doc-major token stream: each doc starts a row
# and holds ceil((length + PHRASE_GAP) / PHRASE_SLICE) rows, so at least
# PHRASE_GAP empty slots (-1) separate one doc's tokens from the next's
PHRASE_SLICE = 128
PHRASE_GAP = 32
# compacted matches per step of the loop that sums their docs' rows
ROWS_BLOCK = 1 << 18


class PositionTable(NamedTuple):
    """The positional index on the device (search/layout.py
    `position_table` builds it on the host): every term's positions,
    and the same positions as each document's token stream."""

    docs: jax.Array       # int32 [T / C + 1, C]: docno of each
    #                       position, in rows of C = PHRASE_CHUNK
    at: jax.Array         # int32 [T / C + 1, C]: its index in fwd
    start: jax.Array      # int32 [V]: the term's first index in docs/at
    cf: jax.Array         # int32 [V]: the term's position count
    fwd: jax.Array        # int32 [F / S, S]: term at each position,
    #                       doc-major, -1 in the gaps (S = PHRASE_SLICE)
    doc_start: jax.Array  # int32 [D + 2]: doc d's first index in fwd


def _take(a, i):
    return a.at[i].get(mode="clip")


def _lower_bound(major, minor, lo, hi, a, b, n_iter: int):
    """Per lane: the first index i in [lo, hi) whose (major[i],
    minor[i]) is not below (a, b), else hi; (major, minor) ascend in
    [lo, hi). `n_iter` (static) must be at least the bit length of the
    widest hi - lo."""
    def body(_, c):
        lo, hi = c
        mid = lo + (hi - lo) // 2
        ma, mb = _take(major, mid), _take(minor, mid)
        below = (ma < a) | ((ma == a) & (mb < b))
        go = lo < hi
        return (jnp.where(go & below, mid + 1, lo),
                jnp.where(go & ~below, mid, hi))

    shape = jnp.shape(a)
    lo, _ = jax.lax.fori_loop(
        0, n_iter, body, (jnp.broadcast_to(lo, shape).astype(jnp.int32),
                          jnp.broadcast_to(hi, shape).astype(jnp.int32)))
    return lo


def _cumsum(x):
    """Inclusive running sum of a non-negative int32 1-D array whose
    total fits int32, exact: sums within rows of 128 as matmuls with a
    triangle of ones (the 12-bit halves apart, so every f32 partial sum
    stays below 2**24), the rows' totals summed the same way. The
    chip's compiler takes up to minutes over a single 1-D cumsum of
    10**4-10**6 elements (and over some 2-D shapes of it), about a
    second over this."""
    n = x.shape[0]
    rows = jnp.concatenate([x, jnp.zeros((-n % 128,), x.dtype)]).reshape(
        -1, 128)
    tri = jnp.triu(jnp.ones((128, 128), jnp.float32))

    def part(v):
        return jnp.dot(v.astype(jnp.float32), tri,
                       precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)

    inner = part(rows & 4095) + (part(rows >> 12) << 12)
    if rows.shape[0] > 1:
        total = _cumsum(inner[:, -1])           # through each row's end
        inner += jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  total[:-1]])[:, None]
    return inner.reshape(-1)[:n]


def _compact(flag, *cols):
    """Lanes with `flag` moved to the front in order, zeros after:
    (cols..., count)."""
    n = flag.shape[0]
    at = jnp.where(flag, _cumsum(flag.astype(jnp.int32)) - 1, n)
    out = tuple(jnp.zeros((n,), c.dtype).at[at].set(c, mode="drop")
                for c in cols)
    return out + (jnp.sum(flag, dtype=jnp.int32),)


def _chunk(rows, start):
    """[len(start), C]: the flat run rows.reshape(-1)[s : s + C] for each
    start s, from rows [R, C] (C a power of two): the two rows each run
    spans, read by row gathers, then shifted left by s % C lanes in
    log2(C) steps of a static lane rotation each. Rows past the end
    read as the last."""
    c = rows.shape[1]
    r0, shift = start // c, start % c
    pair = jnp.concatenate([_take(rows, r0), _take(rows, r0 + 1)], axis=1)
    for bit in range(c.bit_length() - 1):
        step = 1 << bit
        pair = jnp.where(((shift >> bit) & 1)[:, None] == 1,
                         jnp.concatenate([pair[:, step:], pair[:, :step]],
                                         axis=1), pair)
    return pair[:, :c]


@partial(profiled_jit, static_argnames=("n_chunks", "slop"))
def phrase_match(table: PositionTable, row_terms, row_len, row_anchor, *,
                 n_chunks: int, slop: int):
    """The ordered-window match of a block's rows (one per quoted span).

    row_terms int32 [R, M] (the span's term ids, -1 past its length),
    row_len int32 [R] (m; 0 marks a pad row), row_anchor int32 [R] (the
    anchor's index in the span). `n_chunks` (static) must hold the
    block's sum of ceil(cf(anchor) / PHRASE_CHUNK). Returns (cand_row,
    cand_doc, row_count, n_docs, n_slices): the matched (row, docno)
    pairs, in (row, docno) order, padded with (R, 0) to n_chunks *
    PHRASE_CHUNK entries, each row's count, the count of distinct
    (row, docno) pairs, and the rows of the token stream (PHRASE_SLICE
    tokens each) their docs hold, which phrase_topk's `n_slices` has to
    hold. A doc matched at several anchors is listed once for each, the
    repeats right after the first (phrase_topk drops them)."""
    c = PHRASE_CHUNK
    n_rows, m_max = row_terms.shape
    rows = jnp.arange(n_rows)
    anchor_t = jnp.maximum(row_terms[rows, row_anchor], 0)
    need = jnp.where(row_len > 0, (table.cf[anchor_t] + c - 1) // c, 0)
    ends = jnp.cumsum(need)
    i = jnp.arange(n_chunks, dtype=ends.dtype)
    r = jnp.minimum(jnp.searchsorted(ends, i, side="right"), n_rows - 1)
    first_off = (i - ends[r] + need[r]) * c
    # each chunk is C positions of the anchor term's run (the table has
    # a row past its last position, so a chunk never runs off its end);
    # what is per row is read per chunk and repeated over its lanes
    at = table.start[anchor_t[r]] + first_off
    d = _chunk(table.docs, at).reshape(-1)
    f = _chunk(table.at, at).reshape(-1)
    live = ((first_off[:, None] + jnp.arange(c, dtype=ends.dtype))
            < table.cf[anchor_t[r]][:, None]) & (i < ends[-1])[:, None]
    live = live.reshape(-1)

    def lanes(v):
        return jnp.repeat(v, c)

    a, m = lanes(row_anchor[r]), lanes(row_len[r])
    terms = [lanes(row_terms[r, j]) for j in range(m_max)]
    fwd = table.fwd.reshape(-1)
    span = m_max - 1 + slop

    def read(p):
        """The token at stream index p of the anchor's doc: PHRASE_GAP
        empty slots around every doc keep reads within span of the
        anchor inside it; past that the doc's bounds are read too."""
        tok = _take(fwd, p)
        if span <= PHRASE_GAP:
            return tok
        return jnp.where((p >= table.doc_start[d])
                         & (p < table.doc_start[d + 1]), tok, -1)

    if slop == 0:
        # exact adjacency: term j sits at f - a + j
        ok = live
        for j in range(m_max):
            ok &= (j >= m) | (read(f - a + j) == terms[j])
        match = ok
    else:
        # every window of at most span + 1 tokens holding the anchor
        # lies in [f - span, f + span]. One 1-D gather an offset (a
        # [lanes, w] slice gather would be laid out with w padded to
        # 128 lanes)
        near = [(f + o, read(f + o)) for o in range(-span, span + 1)]

        def term(j):
            t = jnp.full_like(f, -1)
            for jj in range(m_max):
                t = jnp.where(j == jj, terms[jj], t)
            return t

        ok, cur = live, f
        for st in range(1, m_max):   # backward: the largest index < cur
            j = a - st
            t = term(j)
            best = jnp.full_like(f, -1)
            for p, tok in near:
                best = jnp.where((tok == t) & (p < cur),
                                 jnp.maximum(best, p), best)
            act = j >= 0
            ok &= ~act | (best >= 0)
            cur = jnp.where(act, best, cur)
        first, cur = cur, f
        for st in range(1, m_max):   # forward: the smallest index > cur
            j = a + st
            t = term(j)
            none = f + span + 1
            best = none
            for p, tok in near:
                best = jnp.where((tok == t) & (p > cur),
                                 jnp.minimum(best, p), best)
            act = j < m
            ok &= ~act | (best < none)
            cur = jnp.where(act, best, cur)
        match = ok & (cur - first <= m - 1 + slop)
    # lanes run in (row, docno, position) order, so the matches compact
    # to that order: a doc matched at several anchors repeats, each
    # repeat right after its first (phrase_topk drops the repeats)
    cr, cd, n = _compact(match, lanes(r).astype(jnp.int32), d)
    k = jnp.arange(cr.shape[0])
    cr = jnp.where(k < n, cr, n_rows)
    first = ~_repeat(cr, cd) & (k < n)
    return (cr, cd, jnp.diff(jnp.searchsorted(
        cr, jnp.arange(n_rows + 1, dtype=cr.dtype))).astype(jnp.int32),
        jnp.sum(first, dtype=jnp.int32), _doc_rows(table, cd, first, n))


def _doc_rows(table: PositionTable, cd, first, n):
    """Token-stream rows of the docs cd[k] with first[k] (all k < n):
    summed a block of ROWS_BLOCK entries at a time, in a loop that stops
    past n, so the gathers cover the matches and not every lane."""
    rows = jnp.diff(table.doc_start) // PHRASE_SLICE
    w = min(ROWS_BLOCK, cd.shape[0])     # both powers of two: w divides

    def body(c):
        i, total = c
        d = jax.lax.dynamic_slice(cd, (i * w,), (w,))
        f = jax.lax.dynamic_slice(first, (i * w,), (w,))
        return i + 1, total + jnp.sum(jnp.where(f, _take(rows, d), 0),
                                      dtype=jnp.int32)

    _, total = jax.lax.while_loop(lambda c: c[0] * w < n, body,
                                  (jnp.int32(0), jnp.int32(0)))
    return total


def _repeat(cr, cd):
    """Compacted matches that repeat the (row, docno) before them."""
    k = jnp.arange(cr.shape[0])
    return (k > 0) & (cr == jnp.roll(cr, 1)) & (cd == jnp.roll(cd, 1))


@partial(profiled_jit, static_argnames=("cap", "n_slices", "width", "k",
                                        "scoring", "k1", "b",
                                        "compat_int_idf"))
def phrase_topk(table: PositionTable, cand_row, cand_doc, row_count,
                row_query, query_rows, query_terms, df, doc_len, n_scalar,
                *, cap: int, n_slices: int, width: int, k: int,
                scoring: str = "bm25", k1: float = 0.9, b: float = 0.4,
                compat_int_idf: bool = False):
    """Score a block's matched docs and take each query's top-k.

    `cand_row` / `cand_doc` / `row_count` are phrase_match's output, of
    which the first `cap` (static, at least the count) are read;
    `n_slices` (static) holds phrase_match's row count and `width` (static)
    any query's first-span count. row_query int32 [R] (each row's
    query), query_rows int32 [B, P] (a query's rows, its first span
    first, -1 pads), query_terms int32 [B, L] (every query term
    occurrence, quoted or not, -1 pads).

    The tfs come from the matched docs' own token streams, read row by
    row (one row gather each, PHRASE_SLICE tokens): each row counts its
    query's terms, and a doc's tf is the difference of the counts'
    running sums at its first and last row. A query's candidates, its
    first span's, are contiguous and docno-ascending: they are laid out
    as one [B, width] row each, and lax.top_k (lower index first among
    equal scores) gives the ties by docno. Returns (scores f32 [B, k],
    docnos int32 [B, k]), docno 0 = empty."""
    cand_row, d = cand_row[:cap], cand_doc[:cap]
    n_cand = jnp.sum(row_count)
    n_q, n_span = query_rows.shape
    i = jnp.arange(cap)
    valid = (i < n_cand) & ~_repeat(cand_row, d)
    r = jnp.where(valid, cand_row, 0)
    q = row_query[r]
    valid &= query_rows[q, 0] == r
    for p in range(1, n_span):      # every other span matched the doc too
        r2 = query_rows[q, p]
        at = _lower_bound(cand_row, d, 0, n_cand, r2, d,
                          max(cap, 1).bit_length())
        found = ((at < n_cand) & (_take(cand_row, at) == r2)
                 & (_take(d, at) == d))
        valid &= (r2 < 0) | found
    # the row stream: candidate c owns its doc's rows [first, first + n)
    row0 = table.doc_start[d] // PHRASE_SLICE
    n_c = jnp.where(valid, table.doc_start[d + 1] // PHRASE_SLICE - row0, 0)
    end = _cumsum(n_c)
    si = jnp.arange(n_slices, dtype=end.dtype)
    # a row's owner: the k-th candidate that holds rows, k counted by a
    # running sum of marks at their first rows
    holds = n_c > 0
    owners, _ = _compact(holds, i.astype(jnp.int32))
    kth = _cumsum(jnp.zeros((n_slices,), jnp.int32).at[
        jnp.where(holds, end - n_c, n_slices)].add(1, mode="drop")) - 1
    owner = owners[jnp.maximum(kth, 0)]
    live = si < end[-1]
    toks = table.fwd[jnp.where(live, si + (row0 - end + n_c)[owner], 0)]
    q_row = q[owner]
    # [L, n_slices]: each row's count of each query term slot
    counts = jnp.stack([
        jnp.where(live, jnp.sum(toks == query_terms[q_row, j][:, None],
                                axis=1), 0)
        for j in range(query_terms.shape[1])])
    # one running sum over the slots' rows laid end to end: a doc's tf
    # is its difference across the doc's rows
    run = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           _cumsum(counts.reshape(-1))])
    slot = n_slices * jnp.arange(counts.shape[0])[:, None]
    tfs = (run[slot + end] - run[slot + end - n_c]).astype(
        jnp.float32)                                         # [L, cap]
    if scoring == "bm25":
        # lint: invariant-ok (one O(D) sum a dispatch, the average length
        # the tiered kernels also derive in-trace)
        n = jnp.asarray(n_scalar, jnp.float32)
        # lint: invariant-ok (as above)
        avg_dl = jnp.sum(doc_len.astype(jnp.float32)) / jnp.maximum(n, 1.0)
        dl_norm = (1.0 - b + b * doc_len[d].astype(jnp.float32)
                   / jnp.maximum(avg_dl, 1e-9))

        def weight(tf, t_df):
            return bm25_saturation(tf, dl_norm, k1=k1) * bm25_idf_weights(
                t_df, n_scalar)
    elif scoring == "tfidf":
        def weight(tf, t_df):
            return _lntf(tf) * idf_weights(t_df, n_scalar, compat_int_idf)
    else:
        raise ValueError(f"unknown scoring {scoring!r}")
    score = jnp.zeros((cap,), jnp.float32)
    for j in range(query_terms.shape[1]):
        t = query_terms[q, j]
        tf = tfs[j]
        score += jnp.where((t >= 0) & (tf > 0),
                           weight(tf, df[jnp.maximum(t, 0)]), 0.0)
    first = jnp.cumsum(row_count) - row_count          # [R] row starts
    col = i - first[r]
    at_q = jnp.where(valid, q, n_q)
    mat = jnp.full((n_q, width), -jnp.inf).at[at_q, col].set(
        score, mode="drop")
    docs = jnp.zeros((n_q, width), jnp.int32).at[at_q, col].set(
        d, mode="drop")
    top, at = jax.lax.top_k(mat, min(k, width))
    ok = top > -jnp.inf
    pad = ((0, 0), (0, k - top.shape[1]))
    return (jnp.pad(jnp.where(ok, top, 0.0), pad),
            jnp.pad(jnp.where(ok, jnp.take_along_axis(docs, at, axis=1), 0),
                    pad))
