"""Exact per-term score decomposition — the query-level "why" lens.

Lucene answers "why did this doc rank here" with `Explanation` trees; the
reference engine (a batch Hadoop pipeline) had nothing. This module is
the TPU-native version of that lens, built so the numbers are not a
re-derivation that can drift from the production kernels but the
kernels' OWN floats:

- Every score readout comes from a debug *scores-at-docs* variant of the
  production kernel (ops/scoring.py `*_scores_at_*`,
  parallel/sharded_tiered.py `sharded_tiered_scores_at`) that traces the
  IDENTICAL accumulation expression and merely gathers the requested
  docnos instead of running top-k — so the gathered score for a returned
  hit is bit-identical to the score the production dispatch ranked it by.

- Per-term contributions are *marginal deltas in accumulation order*:
  the query's L slots become an (L+1)-row prefix batch (row j holds the
  first j term ids, the rest PAD), scored in ONE dispatch; slot l's
  contribution is float64(S_l) - float64(S_{l-1}). PAD slots contribute
  exact 0.0 to every accumulation stage, so S_l is the kernel's own
  partial sum — and the float64 telescoped total collapses exactly to
  S_L, the production score. That identity is the hard contract
  tests/test_explain.py pins bit-exactly across dense/tiered/sharded
  layouts and the hot_only / skip_hot / prune kernel variants (the
  score-bound bookkeeping argument WAND-style pruning correctness
  proofs lean on, here applied to the whole scoring stack).

Metadata (tf, df, idf, length norm, tier placement, prune/skip decision,
rerank delta) rides alongside from the host-side arrays. The per-term tf
lookup needs the CSR postings columns; on the serving-cache fast path
those assemble lazily on first use (same documented one-time cost as the
host fallback scorer — see Scorer._topk_host) and `tf` is None when a
Scorer was built from serving arrays only.
"""

from __future__ import annotations

import numpy as np

from ..obs import trace as obs_trace

# BM25 constants — THE shared pair (search/phrase.py re-exports the same)
K1 = 0.9
B = 0.4


def _idf_host(scorer, scoring: str) -> np.ndarray:
    """Host copy of the exact idf vector the kernels use (computed by the
    same ops functions on device, fetched once and cached per model)."""
    from ..ops.scoring import bm25_idf_weights, idf_weights

    import jax.numpy as jnp

    key = (scoring, scorer.compat_int_idf)
    cache = getattr(scorer, "_explain_idf_cache", None)
    if cache is None:
        cache = scorer._explain_idf_cache = {}
    if key not in cache:
        n = scorer.meta.num_docs
        if scoring == "bm25":
            w = bm25_idf_weights(scorer.df, jnp.float32(n))
        else:
            w = idf_weights(scorer.df, jnp.int32(n),
                            scorer.compat_int_idf)
        cache[key] = np.asarray(w)
    return cache[key]


def _csr_for_tf(scorer):
    """(indptr, pair_doc, pair_tf) for host tf lookups, or None when the
    Scorer carries no postings columns (serving arrays only). The O(V)
    indptr cumsum is cached on the scorer — an explain touches it once,
    not once per (term, doc)."""
    try:
        pd, ptf = scorer._pairs_doc_tf
    except RuntimeError:
        return None
    indptr = getattr(scorer, "_explain_indptr_cache", None)
    if indptr is None:
        indptr = np.concatenate(
            [[0], np.cumsum(scorer._df_host(), dtype=np.int64)])
        scorer._explain_indptr_cache = indptr
    return indptr, pd, ptf


def _tf_in_doc(csr, tid: int, docno: int) -> int | None:
    """Raw tf of term `tid` in `docno` from the host CSR columns."""
    if csr is None:
        return None
    indptr, pd, ptf = csr
    run = pd[int(indptr[tid]) : int(indptr[tid + 1])]
    hits = np.nonzero(run == docno)[0]
    if not len(hits):
        return 0
    return int(ptf[int(indptr[tid]) + int(hits[0])])


def _placement(scorer, tid: int, docno: int) -> dict:
    """Where the term's postings live in the serving layout (the tier
    lens: hot strip vs which cold tier; plus the owning shard on the
    distributed layout)."""
    if scorer.layout == "dense":
        return {"placement": "dense"}
    if scorer.layout == "sharded":
        lay = scorer._sharded
        shard = max((int(docno) - 1) // lay.dblk, 0)
        hr = _host_cache(scorer, "_explain_sh_hot_rank", lay.hot_rank)
        tof = _host_cache(scorer, "_explain_sh_tier_of", lay.tier_of)
        if hr[shard, tid] >= 0:
            place = "hot"
        elif tof[shard, tid] >= 0:
            place = f"tier:{int(tof[shard, tid])}"
        else:
            place = "absent"
        return {"placement": place, "shard": shard}
    hr = scorer._hot_rank_host()
    if hr[tid] >= 0:
        return {"placement": "hot"}
    tof = _host_cache(scorer, "_explain_tier_of", scorer.tier_of)
    if tof[tid] >= 0:
        return {"placement": f"tier:{int(tof[tid])}"}
    return {"placement": "absent"}


def _host_cache(scorer, attr: str, device_array) -> np.ndarray:
    a = getattr(scorer, attr, None)
    if a is None:
        a = np.asarray(device_array)
        setattr(scorer, attr, a)
    return a


def _scores_at(scorer, q: np.ndarray, docs: np.ndarray, *, scoring: str,
               skip_hot: bool = False, hot_only: bool = False
               ) -> np.ndarray:
    """[B, C] f32 production-kernel scores at docnos `docs`, via the
    debug gather variants (shared accumulation with the top-k kernels)."""
    import jax.numpy as jnp

    from ..ops.scoring import (
        bm25_scores_at_dense,
        bm25_scores_at_tiered,
        tfidf_scores_at_dense,
        tfidf_scores_at_tiered,
    )

    qd = jnp.asarray(q, jnp.int32)
    cand = jnp.asarray(docs, jnp.int32)
    n = jnp.int32(scorer.meta.num_docs)
    # the same cold chunk stream the production kernels ran
    chunk_kw = ({} if hot_only or scorer.layout != "sparse"
                else scorer._chunk_kwargs(q, True))
    if scorer.layout == "sharded":
        from ..parallel.sharded_tiered import sharded_tiered_scores_at

        out = sharded_tiered_scores_at(
            qd, scorer._sharded, scorer._df_mesh, scorer.meta.num_docs,
            cand, mesh=scorer._mesh, scoring=scoring,
            compat_int_idf=scorer.compat_int_idf, hot_only=hot_only)
    elif scorer.layout == "dense":
        if scoring == "bm25":
            out = bm25_scores_at_dense(qd, scorer._ensure_tf_matrix(),
                                       scorer.df, scorer.doc_len, n, cand)
        else:
            out = tfidf_scores_at_dense(
                qd, scorer.doc_matrix, scorer.df, n, cand,
                compat_int_idf=scorer.compat_int_idf)
    elif scoring == "bm25":
        out = bm25_scores_at_tiered(
            qd, scorer.hot_rank, scorer.hot_tfs, scorer.tier_of,
            scorer.row_of, scorer.tier_docs, scorer.tier_tfs, scorer.df,
            scorer.doc_len, n, cand, num_docs=scorer.meta.num_docs,
            skip_hot=skip_hot, hot_only=hot_only, **chunk_kw)
    else:
        out = tfidf_scores_at_tiered(
            qd, scorer.hot_rank, scorer.hot_tfs, scorer.tier_of,
            scorer.row_of, scorer.tier_docs, scorer.tier_tfs, scorer.df,
            n, cand, num_docs=scorer.meta.num_docs,
            compat_int_idf=scorer.compat_int_idf, skip_hot=skip_hot,
            hot_only=hot_only, **chunk_kw)
    return np.asarray(out)


def _cosine_scores_at(scorer, q: np.ndarray, cand: np.ndarray
                      ) -> np.ndarray:
    """[B, C] per-candidate cosine (rerank stage-2) scores in candidate
    order, via the debug variants of the production rerank kernels."""
    import jax.numpy as jnp

    from ..ops.scoring import cosine_scores_at_dense, cosine_scores_at_tiered

    qd = jnp.asarray(q, jnp.int32)
    cd = jnp.asarray(cand, jnp.int32)
    n = jnp.int32(scorer.meta.num_docs)
    if scorer.layout == "sharded":
        from ..parallel.sharded_tiered import sharded_tiered_cosine_at

        out = sharded_tiered_cosine_at(
            qd, scorer._sharded, scorer._df_mesh, scorer.meta.num_docs,
            scorer._ensure_sharded_norm(), cd, mesh=scorer._mesh)
    elif scorer.layout == "dense":
        out = cosine_scores_at_dense(qd, scorer.doc_matrix, scorer.df,
                                     scorer._doc_norms(), cd, n)
    else:
        out = cosine_scores_at_tiered(
            qd, scorer.hot_rank, scorer.hot_tfs, scorer.tier_of,
            scorer.row_of, scorer.tier_docs, scorer.tier_tfs, scorer.df,
            scorer._doc_norms(), n, cd, num_docs=scorer.meta.num_docs,
            **scorer._chunk_kwargs(q, True))
    return np.asarray(out)


def _prefix_batch(ids: list[int], width: int) -> np.ndarray:
    """The (L+1)-row prefix query batch (row j = first j ids, rest PAD),
    row count padded to a power of two so explain dispatches reuse a
    small compile ladder (the analyze_queries width-bucketing argument,
    applied to the batch axis)."""
    rows = len(ids) + 1
    cap = 1 << max(rows - 1, 0).bit_length()
    qp = np.full((cap, width), -1, np.int32)
    for j in range(1, rows):
        qp[j, :j] = ids[:j]
    return qp


def _telescope(prefix_scores: np.ndarray) -> list[float]:
    """Marginal per-slot contributions: float64 deltas of consecutive
    prefix scores. Their sum collapses exactly (term-by-term
    cancellation in float64) to prefix_scores[-1] - prefix_scores[0]."""
    s = prefix_scores.astype(np.float64)
    return [float(s[j] - s[j - 1]) for j in range(1, len(s))]


def explain_hits(scorer, text: str, docnos, *, scoring: str = "tfidf",
                 rerank: int | None = None, hot_only: bool = False,
                 ) -> list[dict]:
    """Explain dicts for `docnos` (iterable of ints) under one query —
    one combined prefix-batch dispatch for all docs (plus one candidate
    generation + one cosine dispatch when `rerank` is set).

    Each dict decomposes the score the production pipeline would report
    for that (query, doc): per-slot marginal contributions under the
    final ranking model (BM25/TF-IDF for plain top-k, the cosine stage
    for rerank), with tf/df/idf/length-norm/tier metadata per term and
    the query-level prune/skip dispatch decision."""
    docnos = [int(d) for d in docnos]
    with obs_trace("explain", docs=len(docnos), scoring=scoring,
                   rerank=rerank or 0):
        return _explain_hits(scorer, text, docnos, scoring=scoring,
                             rerank=rerank, hot_only=hot_only)


def _explain_hits(scorer, text, docnos, *, scoring, rerank, hot_only):
    q = scorer.analyze_queries([text])
    ids = [int(t) for t in q[0] if t >= 0]
    width = q.shape[1]
    n_docs = scorer.meta.num_docs

    # the dispatch decision the production topk() scheduler would make
    # for this query (search/scorer.py::_skip_plan): hot-free queries on
    # the tiered layout run the static cold-only kernel
    skip_hot = False
    dispatch = {"layout": scorer.layout, "hot_only": bool(hot_only),
                "skip_hot": False, "prune_scheduling": False}
    if scorer.layout == "sparse" and scorer.prune and not hot_only:
        has_hot = bool(scorer._has_hot(q)[0]) if ids else False
        skip_hot = not has_hot
        dispatch.update({"prune_scheduling": True, "has_hot_terms": has_hot,
                         "skip_hot": skip_hot})

    qp = _prefix_batch(ids, width)
    docs_ok = [d for d in docnos if 1 <= d <= n_docs]
    cand = np.tile(np.asarray(docs_ok, np.int32)[None, :] if docs_ok
                   else np.zeros((1, 1), np.int32), (len(qp), 1))

    stage1 = None
    if docs_ok:
        if rerank:
            # production two-stage pipeline: stage 1 regenerates the BM25
            # candidate set exactly as _rerank_primary does, stage 2 reads
            # the cosine scores out of a candidate matrix of the SAME
            # shape — identical traced reduction, identical floats
            _, cand_d = scorer._topk_device(np.asarray(q, np.int32),
                                            rerank, "bm25", fit_chunks=True)
            cand_row = np.asarray(cand_d)[:1]            # [1, C]
            cand_full = np.tile(cand_row, (len(qp), 1))
            prefix = _cosine_scores_at(scorer, qp, cand_full)  # [B*, C]
            stage1 = _scores_at(scorer, q, np.asarray([docs_ok], np.int32),
                                scoring="bm25")[0]
            # map each explained doc to its column in the candidate set
            col_of = {int(d): j for j, d in
                      reversed(list(enumerate(cand_row[0])))}
        else:
            prefix = _scores_at(scorer, qp, cand, scoring=scoring,
                                skip_hot=skip_hot, hot_only=hot_only)
    idf = _idf_host(scorer, "tfidf" if rerank else scoring)
    csr = _csr_for_tf(scorer)
    df_host = scorer._df_host()
    doc_len = np.asarray(scorer.doc_len)
    avg_dl = float(doc_len.astype(np.float64).sum()) / max(n_docs, 1)
    norms = None
    if rerank:
        norms = scorer._doc_norms_host()

    out = []
    for d in docnos:
        entry = {
            "query": text,
            "docno": d,
            "docid": None,
            "scoring": "cosine_rerank" if rerank else scoring,
            "layout": scorer.layout,
            "dispatch": dispatch,
            "score": 0.0,
            "contribution_sum": 0.0,
            "terms": [],
        }
        try:
            entry["docid"] = scorer.mapping.get_docid(d)
        except Exception:  # noqa: BLE001 — ids are a nicety, not the lens
            pass
        if not 1 <= d <= n_docs:
            entry["error"] = f"docno {d} out of range 1..{n_docs}"
            out.append(entry)
            continue
        entry["doc_len"] = int(doc_len[d])
        if scoring == "bm25" and not rerank:
            entry["avg_doc_len"] = round(avg_dl, 4)
            entry["dl_norm"] = float(
                1.0 - B + B * float(doc_len[d]) / max(avg_dl, 1e-9))
            entry["k1"], entry["b"] = K1, B
        if rerank:
            j = col_of.get(d)
            if j is None:
                # the doc never made the stage-1 candidate set (explain
                # of an arbitrary doc, not a returned hit): read its
                # cosine score through a 1-candidate gather — right
                # value, but not the production candidate-matrix shape
                solo = np.tile(np.asarray([[d]], np.int32), (len(qp), 1))
                col = _cosine_scores_at(scorer, qp, solo)[:, 0]
                entry["rerank"] = {"in_candidates": False,
                                   "candidates": rerank}
            else:
                col = prefix[:, j]
                entry["rerank"] = {
                    "in_candidates": True,
                    "candidates": rerank,
                    "stage1_score": float(stage1[docs_ok.index(d)])
                    if d in docs_ok else None,
                }
            entry["doc_norm"] = float(norms[d])
        else:
            col = prefix[:, docs_ok.index(d)]
        col = col[: len(ids) + 1]
        contribs = _telescope(col)
        entry["score"] = float(col[len(ids)])
        entry["contribution_sum"] = float(np.sum(
            np.asarray(contribs, np.float64)))
        if rerank and entry["rerank"].get("stage1_score") is not None:
            entry["rerank"]["delta"] = float(
                np.float64(entry["score"])
                - np.float64(entry["rerank"]["stage1_score"]))
        for slot, tid in enumerate(ids):
            t = {
                "slot": slot,
                "term": scorer.vocab.term(tid),
                "term_id": tid,
                "df": int(df_host[tid]),
                "idf": float(idf[tid]),
                "tf": _tf_in_doc(csr, tid, d),
                "contribution": contribs[slot],
            }
            t.update(_placement(scorer, tid, d))
            entry["terms"].append(t)
        out.append(entry)
    return out
