"""The Scorer: load index artifacts to device once, answer query batches.

Replaces the reference's query engine (IntDocVectorsForwardIndex.java:93-322)
whose per-term flow was dictionary hashtable -> SequenceFile seek -> read one
postings record -> O(P^2) score accumulation. Here the whole index lives on
device; a query batch is analyzed host-side into an int32 [B, L] term-id
array and scored in one jit call (dense MXU-friendly layout when it fits,
padded-CSR sparse layout otherwise).

Query analysis uses the identical pipeline as indexing (reference parity:
IntDocVectorsForwardIndex.java:276,295), including k-gram composition when
the index was built with k > 1.
"""

from __future__ import annotations

import logging
import os
import re
import threading
import time
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from .. import faults
from ..analysis.native import make_analyzer
from ..obs import trace as obs_trace
from ..collection import KGRAM_SEP, DocnoMapping, Vocab, kgram_terms
from ..index import format as fmt
from ..ops import bm25_topk_dense, dense_doc_matrix, tfidf_topk_dense
from ..ops.scoring import ColdChunks, dense_tf_matrix
from ..utils.report import recovery_counters
from ..utils.transfer import issue_host_copies, stream_to_device
from .layout import build_tiered_layout, cold_chunk_plan, cold_chunk_table

# dense [V, D+1] matrix budget in elements (f32); above this use sparse CSR
DENSE_BUDGET = 500_000_000

# a whitespace-delimited query token containing a glob metacharacter
_WILDCARD_RE = re.compile(r"\S*[*?]\S*")

# fuzzy tokens: 'salmn~' (1 edit) or 'color~2'; the '~' must FOLLOW a
# token (a leading '~5' is just text). The distance is a SINGLE digit
# (Lucene-style 0-2): with \d* a query like '5~10' would swallow the
# literal term '10' as a distance
_FUZZY_RE = re.compile(r"(\S+?)~(\d?)(?=[\s.,;:!)\]}]|$)")

# punctuation the analyzer would strip from a literal token; removed from
# glob-token edges too so 'fish*,' or '(fish*)' means the pattern 'fish*'
_EDGE_PUNCT = "".join(c for c in
                      r"""!"#$%&'()+,-./:;<=>@[\]^_`{|}~""" if c not in "*?")

# interior punctuation splits a glob token the way the analyzer splits a
# literal one ('salmon,fish*' = literal 'salmon' + pattern 'fish*'); '.' and
# "'" are kept inside parts to preserve acronym/apostrophe analysis
_GLOB_SPLIT_RE = re.compile(
    "[" + re.escape("".join(c for c in _EDGE_PUNCT if c not in ".'")) + "]+")

logger = logging.getLogger(__name__)


def _rtt_dominated_backend() -> bool:
    """True on the TPU, taken as the backend where the fixed cost of a
    dispatch dominates per-row kernel cost (batch rows ride nearly free
    on the MXU) — the regime in which folding a small hot-free group
    into the full dispatch beats paying for a second dispatch. On CPU
    the hot-strip matmul dominates instead, so the split wins. Whether
    the TPU side of this choice holds on a local chip is not measured
    yet (ROADMAP S5)."""
    import jax

    return jax.default_backend() == "tpu"


def _donation_enabled() -> bool:
    """Whether coalesced dispatches should use the donated-query kernel
    twins (ops/scoring.py `*_dq`). TPU_IR_BATCH_DONATE: "auto" donates
    only on backends that implement input-output aliasing (TPU) — on CPU
    jax warns and ignores the donation, pure noise; "1"/"0" force it for
    A/B runs and the parity test."""
    from ..utils import envvars

    mode = envvars.get_choice("TPU_IR_BATCH_DONATE")
    if mode == "auto":
        import jax

        return jax.default_backend() == "tpu"
    return mode == "1"


def _pow2(n: int, floor: int = 8) -> int:
    """`n` rounded up to a power of two, at least `floor`: the bucketed
    static sizes of the phrase path's rows and queries."""
    return max(floor, 1 << max(int(n) - 1, 0).bit_length())


class SearchResult(list):
    """List of (docno, score) or (docid, score) tuples for one query.

    `degraded` is True when the results came from a fallback path (score
    deadline expired, the device was lost mid-dispatch, or the serving
    frontend's circuit breaker bypassed the device entirely): still
    correct ranking per the host scoring model, but not the primary
    pipeline — callers surfacing results to users should tag them.

    `level` is the service level the request was answered at ("full"
    unless a serving frontend stepped its degradation ladder down:
    "no_rerank" dropped the rerank/snippet stages, "hot_only" scored only
    the hot tier). Set per-request by tpu_ir.serving.ServingFrontend;
    plain Scorer calls always serve "full".

    `explain` (None unless `search_batch(..., explain_k=N)` asked for
    it) holds one score-decomposition dict per top-N hit
    (search/explain.py); degraded responses carry None — their scores
    came from the host fallback, not the device kernels the explain
    decomposes.

    `breaker_vote` (serving-internal): inside a coalesced shared batch,
    exactly one result carries True — the serving frontend feeds the
    circuit breaker one verdict per DISPATCH, not per slot.

    `partial` (the scatter-gather tier, serving/router.py): True when at
    least one doc shard missed its deadline on every replica, so the
    merged top-k covers only the healthy shards — a correct subset, not
    the full index. `shards_ok` / `missing_shards` name the shard ids
    that did / did not contribute; `hedges` counts hedged dispatches the
    request fired. Rides the PR-2 tagging ladder: every routed response
    is exactly one of full / degraded / partial / rejected."""

    degraded: bool = False
    level: str = "full"
    explain: list | None = None
    breaker_vote: bool = True
    partial: bool = False
    shards_ok: tuple = ()
    missing_shards: tuple = ()
    hedges: int = 0
    # the index GENERATION that answered (the live-index subsystem,
    # index/segments.py): 0 for plain batch-built indexes; stamped by
    # the serving frontend and the scatter-gather router so a response
    # served across a rolling generation swap is attributable to
    # exactly one corpus snapshot
    generation: int = 0
    # distributed-trace id (obs/disttrace.py): stamped by whichever
    # admission edge minted the context (router or unrouted frontend),
    # None when tracing is disabled — the join key for
    # `tpu-ir trace <id>` and the /trace/<id> waterfall
    trace_id: str | None = None


def compute_doc_norms(pair_term, pair_doc, pair_tf, df,
                      num_docs: int) -> np.ndarray:
    """f32 [D+1] doc-vector norms under (1+ln tf)*idf weighting (the
    cosine rerank denominator), from the host CSR columns. Accumulated in
    bounded chunks: one float64 pass over 250M pairs would allocate
    several multi-GB temporaries on this 1-core container.

    `pair_term=None` derives each chunk's term ids from the CSR row
    starts (cumsum of df) via one searchsorted — the columns are in
    global CSR order, so the ~1 GB materialized pair_term column at 250M
    pairs is never needed here (ISSUE 5 satellite)."""
    from ..ops import idf_weights

    # the same idf the rerank kernels use (single source of truth);
    # the rerank model is float idf regardless of compat mode
    idf = np.asarray(idf_weights(jnp.asarray(df), num_docs),
                     dtype=np.float32)
    indptr = (None if pair_term is not None
              else np.cumsum(np.asarray(df, np.int64)))
    sq = np.zeros(num_docs + 1, np.float64)
    step = 1 << 24
    for lo in range(0, len(pair_doc), step):
        sl = slice(lo, min(lo + step, len(pair_doc)))
        if pair_term is not None:
            terms = pair_term[sl]
        else:
            # pair i's term is the df-run it falls in: the first row
            # start STRICTLY greater than i (side='right' skips empty
            # runs whose start equals i)
            terms = np.searchsorted(indptr,
                                    np.arange(sl.start, sl.stop,
                                              dtype=np.int64),
                                    side="right").astype(np.int64)
        w = (1.0 + np.log(np.maximum(pair_tf[sl], 1)
                          .astype(np.float32))) * idf[terms]
        sq += np.bincount(pair_doc[sl], weights=w * w,
                          minlength=num_docs + 1)
    return np.sqrt(sq[: num_docs + 1]).astype(np.float32)


class Scorer:
    # class-level defaults so minimal Scorers (tests build them with
    # object.__new__ over synthetic layouts) get the no-deadline behavior
    deadline_s: float | None = None
    # shard-worker doc restriction (scatter-gather tier); None = whole
    # index. Set by __init__(doc_range=...), consulted by _topk_host.
    doc_range: tuple | None = None
    # index generation this scorer serves (live indexes; 0 = a plain
    # batch-built dir). Stamped by load_generation(); responses carry it
    # (SearchResult.generation) through the frontend and router.
    generation: int = 0
    # the live dir load_generation() resolved from (reload target)
    _live_dir: str | None = None
    # (the old single-threaded `degraded_last` alias is GONE — ISSUE 9:
    # under coalesced shared batches only the per-request tagged path
    # (topk_tagged / rerank_topk_tagged -> SearchResult.degraded) is a
    # correct source; the alias was racy the moment two queries ran
    # concurrently and PR 2 kept it for compat only.)
    # guards lazy expensive state (_pairs assembly, rerank norms, the
    # dense tf matrix, wildcard lookups) under concurrent serving; an
    # RLock because the norms path re-enters _pairs. __init__ gives each
    # instance its own (two co-hosted indexes must not serialize each
    # other's multi-second lazy loads); the class-level fallback covers
    # minimal object.__new__ Scorers in tests.
    _lazy_lock = threading.RLock()
    # block-max state defaults, so minimal object.__new__ Scorers (and
    # non-tiered layouts) read "no bounds" instead of AttributeError
    _hot_blk_max: np.ndarray | None = None
    _blockmax_width: int = 0

    def __init__(
        self,
        *,
        vocab: Vocab,
        mapping: DocnoMapping,
        pair_term: np.ndarray | None = None,
        pair_doc: np.ndarray | None = None,
        pair_tf: np.ndarray | None = None,
        df: np.ndarray,
        doc_len: np.ndarray,
        meta: fmt.IndexMetadata,
        layout: str = "auto",
        compat_int_idf: bool = False,
        index_dir: str | None = None,
        tiers=None,
        doc_norms: np.ndarray | None = None,
        pairs_loader=None,
        sharded_layout=None,
        prune: bool = True,
        deadline_s: float | None = None,
        doc_range: tuple | None = None,
    ):
        """`pair_*` may be omitted on the tiered path when prebuilt `tiers`
        (+ cached `doc_norms`) are supplied — the serving-cache fast path;
        `pairs_loader` then lazily assembles the CSR columns if something
        still needs them (the bench's exhaustive oracle does).

        `deadline_s` bounds every score dispatch: a batch that has not
        returned within the deadline (or whose device is lost) falls back
        to the host CPU scorer and is tagged degraded, instead of hanging
        the serving process (degraded-mode serving; "The Tail at Scale").

        `doc_range=(lo, hi)` (1-based inclusive global docids) makes this
        a SHARD WORKER scorer for the scatter-gather serving tier
        (serving/router.py): the loaded layout keeps its full geometry
        but every posting outside the range is tf-zeroed
        (layout.restrict_tiers), so in-range docs score BIT-identically
        to the unrestricted scorer while out-of-range docs score exact
        0.0 and never surface — the property the router's exact top-k
        merge rides on. Global statistics (df, N, doc lengths, rerank
        norms) stay global by construction."""
        self.vocab = vocab
        self.mapping = mapping
        self.meta = meta
        self.compat_int_idf = compat_int_idf
        self.deadline_s = deadline_s
        self._lazy_lock = threading.RLock()
        # rank-safe MaxScore pruning of the tiered hot-strip stage
        # (ops/scoring.py::_hot_stage_pruned); results are identical with
        # it off — the toggle exists for the bench's device-control A/B
        self.prune = prune
        self._analyzer = make_analyzer()
        # enables wildcards + the serving-layout disk cache
        self._index_dir: str | None = index_dir
        self._wildcard = None
        self._wildcard_tried = False
        self._phrase = None  # lazy PhraseIndex (format-v2 positions)
        # the pair_term slot may be None: the verified load path keeps it
        # lazy (derivable from df — at 250M pairs it is ~1 GB nobody on
        # the tiered serving path reads); _pairs materializes on demand
        self._pairs_cols = (None if pair_doc is None
                            else (pair_term, pair_doc, pair_tf))
        self._pairs_loader = pairs_loader
        self._norms_np = doc_norms
        v, d = meta.vocab_size, meta.num_docs
        self.df = jnp.asarray(np.ascontiguousarray(df))
        self.doc_len = jnp.asarray(doc_len)

        if layout == "auto":
            layout = "dense" if v * (d + 1) <= DENSE_BUDGET else "sparse"
        if layout not in ("dense", "sparse", "sharded"):
            # explicit rejection so a typo (or the round-1 "pallas" layout,
            # retired after hardware measurement — NOTES.md "Pallas
            # verdict") cannot silently fall through to the tiered path
            raise ValueError(f"unknown layout {layout!r}; expected "
                             "'auto', 'dense', 'sparse' or 'sharded'")
        self.layout = layout
        self.doc_range = None
        if doc_range is not None:
            lo, hi = int(doc_range[0]), int(doc_range[1])
            if lo < 1 or hi > d:
                raise ValueError(f"doc_range {doc_range!r} outside the "
                                 f"index's 1..{d} docid space")
            self.doc_range = (lo, hi)
            if layout == "dense" and pair_tf is not None:
                # mask the tf column itself: doc_matrix, the lazy BM25
                # tf matrix and the host fallback all derive from the
                # pair columns, so one mask restricts every dense path
                # (out-of-range docs' norms are polluted by the zeroed
                # entries, but no out-of-range doc is ever a candidate)
                pdoc = np.asarray(pair_doc).astype(np.int64)
                pair_tf = np.array(pair_tf)
                pair_tf[(pdoc < lo) | (pdoc > hi)] = 0
                self._pairs_cols = (pair_term, pair_doc, pair_tf)
        self._tf_matrix = None  # built lazily on first BM25 call
        if self._pairs_cols is None and (
                layout == "dense"
                or (layout == "sharded" and sharded_layout is None)
                or (layout == "sparse" and tiers is None)):
            raise ValueError(f"layout {layout!r} needs the postings "
                             "columns or a prebuilt serving layout")
        if layout == "dense":
            if pair_term is None:
                pair_term = self._pair_term()  # dense scatter needs it
            self.doc_matrix = dense_doc_matrix(
                jnp.asarray(pair_term), jnp.asarray(pair_doc),
                jnp.asarray(pair_tf), vocab_size=v, num_docs=d)
        elif layout == "sharded":
            # distributed serving: the tiered layout's doc axis sharded
            # over the mesh (parallel/sharded_tiered.py) — total memory is
            # the single-device tiered layout spread across devices, so the
            # corpora that need distribution actually fit; TF-IDF, BM25 and
            # rerank all run on it
            import jax

            from ..parallel import make_mesh, make_sharded_tiered, put_sharded

            n_dev = len(jax.devices())
            self._mesh = make_mesh(n_dev)
            lay = sharded_layout
            if lay is None:
                if pair_term is None:
                    pair_term = self._pair_term()  # per-shard df bincount
                lay = make_sharded_tiered(
                    pair_term, pair_doc, pair_tf, np.asarray(df),
                    np.asarray(doc_len), num_docs=d, num_shards=n_dev)
            if self.doc_range is not None:
                from ..parallel.sharded_tiered import (
                    restrict_sharded_layout,
                )

                lay = restrict_sharded_layout(lay, *self.doc_range)
            self._sharded = put_sharded(lay, self._mesh)
            self._sharded_norm = None  # built lazily for rerank
            # df replicated over the mesh ONCE: multi-process serving
            # would otherwise re-upload the [V] array per query block
            # (replicated_global is idempotent and a single-process
            # pass-through, so the dispatch calls stay unchanged)
            from ..parallel.sharded_tiered import replicated_global

            self._df_mesh = replicated_global(self.df, self._mesh)
        else:
            # tiered sparse: budget-capped dense strip for the hottest
            # terms + geometric-capacity padded tiers for the rest
            # (search/layout.py) — raw tf everywhere so the same arrays
            # serve TF-IDF and BM25. With an index dir, the built layout
            # (+ df + rerank norms) is persisted as the serving cache; a
            # later load with a cache hit passes `tiers` in and never
            # touches the shards (Scorer.load fast path).
            if tiers is None:
                tiers = build_tiered_layout(pair_doc, pair_tf, df,
                                            num_docs=d)
            if self.doc_range is not None:
                from .layout import restrict_tiers

                tiers = restrict_tiers(tiers, *self.doc_range)
            # every upload streams through the double-buffered chunked
            # path (utils/transfer.py::stream_to_device), each call its
            # own load.h2d span: disk page-ins of mmap'd cache sections
            # overlap the in-flight transfers instead of one monolithic
            # blocking device_put per array
            self.hot_rank = stream_to_device(tiers.hot_rank,
                                             label="hot_rank")
            # the dense strip is materialized ON DEVICE from the COO
            # hot postings — at 1M docs that uploads a few hundred MB
            # instead of the ~2 GB dense matrix over the H2D link
            # (the serving cold-start bottleneck; layout.hot_device)
            self.hot_tfs = tiers.hot_device(dtype=self._strip_dtype(tiers))
            # (no hot_max_tf here: the runtime-bounded prune kernels
            # that take it are not the production path — the
            # scheduled static skip needs only hot_rank; tests
            # compute it locally)
            # block-max bounds (ISSUE 13): per-(hot row, doc block) max
            # tf from the layout/cache; each scoring mode's f32 bound
            # table is derived lazily on first engaged dispatch
            self._hot_blk_max = (None if tiers.hot_blk_max is None
                                 else np.asarray(tiers.hot_blk_max))
            self._blockmax_width = int(tiers.blockmax_width or 0)
            self._blockmax_tables: dict = {}
            self.tier_of = stream_to_device(tiers.tier_of,
                                            label="tier_of")
            self.row_of = stream_to_device(tiers.row_of, label="row_of")
            # the big tiers are uploaded as one table of posting chunks
            # the kernels stream per block (layout.cold_chunk_table);
            # in their tier slots stay [0, P_t] placeholders, which
            # keep the tier indices and capacities and hold no bytes
            plan = cold_chunk_plan(tiers, np.asarray(df))
            streamed = () if plan is None else plan[0]
            self.tier_docs, self.tier_tfs = (
                tuple(jnp.zeros((0, a.shape[1]), a.dtype) if i in streamed
                      else stream_to_device(a, label=f"{name}_{i}")
                      for i, a in enumerate(arrs))
                for name, arrs in (("tier_docs", tiers.tier_docs),
                                   ("tier_tfs", tiers.tier_tfs)))
            if plan is not None:
                with obs_trace("load.layout", layout="cold_chunks"):
                    docs, tfs = cold_chunk_table(tiers, plan)
                self.cold_chunks = ColdChunks(
                    stream_to_device(docs, label="cold_chunk_docs"),
                    stream_to_device(tfs, label="cold_chunk_tfs"),
                    jnp.asarray(plan[1]), jnp.asarray(plan[2]))
                self._chunk_count_host = plan[2]
                # rows a term of the widest streamed tier can hold
                self._chunk_widest = max(tiers.tier_docs[i].shape[1]
                                         for i in streamed) // plan[3]

    # -- loading -----------------------------------------------------------

    @classmethod
    def load(cls, index_dir: str, *, layout: str = "auto",
             compat_int_idf: bool = False, prune: bool = True,
             deadline_s: float | None = None,
             verify_integrity: bool = True,
             doc_range: tuple | None = None) -> "Scorer":
        """Load an index dir for serving, the whole load one `load` span
        with its stages as children: load.read (side artifacts and part
        shards, load.verify inside), load.assemble, load.layout,
        load.cache_write (cache miss only), load.h2d, and for an index
        with positions load.positions (the device position table of the
        phrase path; an index without positions uploads none)."""
        if layout not in ("auto", "dense", "sparse", "sharded"):
            # fail before any IO — a typo'd layout should not cost the
            # minutes-long shard read + CSR assembly of a large index
            raise ValueError(f"unknown layout {layout!r}; expected "
                             "'auto', 'dense', 'sparse' or 'sharded'")
        with obs_trace("load", layout=layout):
            scorer = cls._load(index_dir, layout=layout,
                               compat_int_idf=compat_int_idf, prune=prune,
                               deadline_s=deadline_s,
                               verify_integrity=verify_integrity,
                               doc_range=doc_range)
            scorer._position_table()
            return scorer

    @classmethod
    def _load(cls, index_dir: str, *, layout: str, compat_int_idf: bool,
              prune: bool, deadline_s: float | None,
              verify_integrity: bool,
              doc_range: tuple | None) -> "Scorer":
        from .. import enable_compilation_cache

        # the serving path compiles ~20 programs (layout scatter, top-k
        # kernels); without the persistent cache a fresh serving process
        # pays them all again — measured 24.4 s of a 25.4 s ref-scale
        # warm load was backend_compile_and_load (builders already
        # enable this; the serving process must too)
        enable_compilation_cache()
        meta = fmt.IndexMetadata.load(index_dir)
        # the embedded server's /doctor introspects the index dirs this
        # process actually serves (obs/server.py keeps the last few)
        from ..obs.server import register_index_dir

        register_index_dir(index_dir)
        with obs_trace("load.read", files="side"):
            if verify_integrity:
                # side artifacts are small — verify their recorded checksums
                # on every load. Part shards are verified BY the reads that
                # consume them (verify-while-read inside _assemble_csr and
                # the lazy pairs_loader — one streamed pass, not the old
                # verify-then-read double scan). A serving-cache HIT skips
                # part checks: any filesystem-API change to a part (rebuild,
                # migrate, overwrite) bumps size/mtime_ns and misses into
                # the verified path, but stat revalidation deliberately does
                # NOT re-prove content, so stat-preserving media bit-rot
                # rides a hit undetected until shard bytes actually stream
                # (layout.py::_part_stat; TPU_IR_CACHE_REVALIDATE=crc forces
                # content-proven hits).
                with obs_trace("load.verify", files="side"):
                    fmt.verify_checksums(
                        index_dir, meta,
                        names=[fmt.DOCLEN, fmt.DOCNOS, fmt.VOCAB])
            vocab = Vocab.load(os.path.join(index_dir, fmt.VOCAB))
            mapping = DocnoMapping.load(os.path.join(index_dir,
                                                     fmt.DOCNOS))
            doc_len = np.load(os.path.join(index_dir, fmt.DOCLEN))

        def load_pairs_verified():
            """Lazy CSR assembly for the cache fast path — parts may have
            rotted since the cache key was computed, so their recorded
            CRCs are verified as the shards stream in (same structured-
            error surface as the eager path)."""
            return cls._assemble_csr(index_dir, meta,
                                     verify=verify_integrity)[1]

        v, d = meta.vocab_size, meta.num_docs
        resolved = layout
        if resolved == "auto":
            resolved = ("dense" if v * (d + 1) <= DENSE_BUDGET
                        else "sparse")
        if resolved == "sparse":
            # serving-cache fast path: a hit (keyed on part-file CRCs)
            # yields tiers + df + rerank norms with NO shard read or CSR
            # assembly — those were the dominant warm-load costs at 250M
            # pairs. The columns stay available lazily for oracles.
            from .layout import load_serving_cache

            cached = load_serving_cache(index_dir, meta=meta)
            if cached is not None:
                tiers, df, norms = cached
                return cls(
                    vocab=vocab, mapping=mapping,
                    df=np.asarray(df), doc_len=doc_len, meta=meta,
                    layout="sparse", compat_int_idf=compat_int_idf,
                    index_dir=index_dir, tiers=tiers,
                    doc_norms=np.asarray(norms),
                    pairs_loader=load_pairs_verified, prune=prune,
                    deadline_s=deadline_s, doc_range=doc_range)
        elif resolved == "sharded":
            # same fast path for distributed serving, per mesh size
            import jax

            from ..parallel.sharded_tiered import load_sharded_serving_cache

            n_dev = len(jax.devices())
            cached = load_sharded_serving_cache(index_dir, meta=meta,
                                                num_shards=n_dev)
            if cached is not None:
                lay, df, norms = cached
                return cls(
                    vocab=vocab, mapping=mapping,
                    df=np.asarray(df), doc_len=doc_len, meta=meta,
                    layout="sharded", compat_int_idf=compat_int_idf,
                    index_dir=index_dir, sharded_layout=lay,
                    doc_norms=np.asarray(norms),
                    pairs_loader=load_pairs_verified, prune=prune,
                    deadline_s=deadline_s, doc_range=doc_range)

        # the eager shard read: recorded CRCs are folded into the SAME
        # streamed pass that reads the bytes (verify-while-read), so
        # corruption still surfaces as ONE structured IntegrityError
        # naming the file — without the old second scan. The metadata
        # digest pins CONTENT, not just well-formedness: a stale or
        # swapped-in part from another build parses perfectly and would
        # serve a silently wrong index.
        # memory-lean worker (ISSUE 20): on a COMPRESSED index a
        # doc-range worker forwards its range into shard decode, so
        # posting blocks outside the range never have their payload
        # bytes read — the per-worker footprint shrinks with the range
        # instead of tf-zeroing a full-size assembly. Raw indexes keep
        # the full read (restrict_tiers zeroes after layout build).
        lean_range = doc_range if meta.compressed else None
        df, (pair_doc, pair_tf) = cls._assemble_csr(
            index_dir, meta, verify=verify_integrity,
            doc_range=lean_range)
        pair_term = None  # derived lazily from df when something needs it
        tiers = norms = None
        sharded_layout = None
        # cache miss: build + persist here in load(), where the arrays
        # provably came from the index files the cache key CRCs — a
        # direct-constructed Scorer (caller-supplied arrays) never writes
        # the cache, so it cannot poison later loads. The norms pass (a
        # full sweep over the postings) is eager ONLY for the cache write;
        # on a read-only index dir both are skipped and norms stay lazy
        # (rerank-only), instead of repaying the pass every restart for a
        # save that silently fails.
        from .layout import serving_cache_writable

        save_cache = serving_cache_writable(index_dir)
        if lean_range is not None:
            # the assembly above holds dead slots for everything outside
            # this worker's range — a cache written from it would poison
            # every later full-index load
            save_cache = False
        if resolved == "sharded":
            import jax

            from ..ops.postings import pair_term_from_df
            from ..parallel.sharded_tiered import (
                make_sharded_tiered,
                save_sharded_serving_cache,
            )

            with obs_trace("load.layout", layout="sharded"):
                pair_term = pair_term_from_df(df)  # per-shard df bincounts
                sharded_layout = make_sharded_tiered(
                    pair_term, pair_doc, pair_tf, np.asarray(df),
                    np.asarray(doc_len), num_docs=meta.num_docs,
                    num_shards=len(jax.devices()))
            if save_cache:
                with obs_trace("load.cache_write", layout="sharded"):
                    norms = compute_doc_norms(pair_term, pair_doc, pair_tf,
                                              df, meta.num_docs)
                    # one writer on a shared index dir: every process
                    # builds the same layout, process 0 persists it
                    if jax.process_index() == 0:
                        save_sharded_serving_cache(
                            index_dir, sharded_layout, df, norms,
                            meta=meta, num_shards=len(jax.devices()))
        elif resolved == "sparse":
            from ..index.blockmax import load_block_bounds
            from .layout import save_serving_cache

            with obs_trace("load.layout", layout="sparse"):
                # the builders' block-max bounds artifact saves the
                # bounds pass; corrupt copies quarantine and the pass
                # recomputes (bounds are derived data — never a load
                # failure)
                bounds = load_block_bounds(index_dir, meta,
                                           quarantine_corrupt=True)
                tiers = build_tiered_layout(pair_doc, pair_tf, df,
                                            num_docs=meta.num_docs,
                                            block_bounds=bounds)
            if save_cache:
                with obs_trace("load.cache_write", layout="sparse"):
                    # pair_term stays lazy: the norms pass derives each
                    # chunk's term ids from the df row starts instead of
                    # materializing the ~1 GB column
                    norms = compute_doc_norms(None, pair_doc, pair_tf,
                                              df, meta.num_docs)
                    save_serving_cache(index_dir, tiers, df, norms,
                                       meta=meta)
        return cls(
            vocab=vocab, mapping=mapping,
            pair_term=pair_term, pair_doc=pair_doc,
            pair_tf=pair_tf, df=df, doc_len=doc_len, meta=meta,
            layout=layout, compat_int_idf=compat_int_idf,
            index_dir=index_dir, tiers=tiers, doc_norms=norms,
            sharded_layout=sharded_layout, prune=prune,
            deadline_s=deadline_s, doc_range=doc_range)

    @classmethod
    def load_generation(cls, live_dir: str, generation: int | None = None,
                        **load_kwargs) -> "Scorer":
        """Load one GENERATION of a live index (index/segments.py) — or
        a plain index dir, which serves as generation 0. The generation
        must be servable (one canonical segment, no tombstones:
        `tpu-ir ingest --compact` produces one); the returned scorer is
        stamped with its generation and remembers the live dir, so
        `reload_generation()` can follow the corpus as new generations
        land. `load_kwargs` pass through to Scorer.load (and are
        replayed on reload — a worker's layout/deadline/doc_range
        follow it across swaps unless overridden)."""
        from ..index import segments as seg

        index_dir, gen = seg.resolve_serving(live_dir, generation)
        scorer = cls.load(index_dir, **load_kwargs)
        scorer.generation = int(gen)
        scorer._live_dir = os.path.abspath(live_dir) \
            if seg.is_live(live_dir) else None
        scorer._load_kwargs = dict(load_kwargs)
        return scorer

    def reload_generation(self, generation: int | None = None,
                          **override_kwargs) -> "Scorer":
        """A NEW Scorer over the (given or current) generation of this
        scorer's live dir, loaded with the same kwargs as the original
        (overridable — a shard worker passes its recomputed doc_range,
        since the doc partition follows num_docs across generations).

        Deliberately a functional swap, not in-place mutation: the
        query path reads a dozen attributes per request, and mutating
        them under a running request would tear it (old vocab, new
        layout — silently wrong floats, exactly what the soak's
        bit-exactness invariant exists to catch). The OLD scorer stays
        fully valid — in-flight requests finish on the arrays they
        already hold — and the publish is the caller's single reference
        swap (ServingFrontend.reload_generation)."""
        if self._live_dir is None:
            raise ValueError("this scorer was not loaded from a live "
                             "index dir (use Scorer.load_generation)")
        kwargs = {**getattr(self, "_load_kwargs", {}), **override_kwargs}
        return type(self).load_generation(self._live_dir, generation,
                                          **kwargs)

    @staticmethod
    def _assemble_csr(index_dir: str, meta, verify: bool = False,
                      doc_range: tuple | None = None):
        """Shard files -> (df, (pair_doc, pair_tf)) in global CSR order:
        a shard holds contiguous per-term runs, so every run's
        destination is the global indptr slice of its TERM ID — no sort
        needed (a stable argsort over the pair columns costs ~2 min at
        250M pairs on one core; this is a few vectorized passes), and
        no dependence on the runs' order WITHIN the part: the canonical
        layout (terms globally ascending) and the bucket-segmented
        radix_parts layout (terms ascending only within each bucket
        segment — index/streaming.write_bucketed_shard) assemble to the
        same global CSR through the same scatter. pair_term is NOT
        materialized — it is derivable from df alone (both layouts keep
        one contiguous run per term) and nothing on the assembly path
        reads it.

        Shards load concurrently through a thread pool
        (TPU_IR_LOAD_THREADS; numpy releases the GIL on large reads, so
        disk, CRC fold and zip decompression overlap across shards).
        `verify=True` folds each part's recorded CRC into its ONE
        streamed read (fmt.load_shard_verified) — the verify-then-read
        double scan is gone for v1 npz and v2 arenas alike; v2 arenas
        additionally read zero-copy (np.frombuffer views / mmap).

        `doc_range=(lo, hi)` (1-based inclusive, the shard-worker
        restriction) is forwarded to compressed-shard decode: posting
        blocks wholly outside the range come back as (doc=0, tf=0) dead
        slots WITHOUT their payload bytes ever being read (memory-lean
        worker, ISSUE 20) — raw shards ignore it (restrict_tiers zeroes
        them after layout build, same as always)."""
        from concurrent.futures import ThreadPoolExecutor

        v = meta.vocab_size
        n_threads = max(1, min(fmt.load_threads(), meta.num_shards))
        # decode's range is half-open over the 1-based docid space
        dr = (int(doc_range[0]), int(doc_range[1]) + 1) \
            if doc_range is not None else None

        def read_one(s: int):
            if verify:
                return fmt.load_shard_verified(index_dir, s, meta,
                                               doc_range=dr)
            # unverified eager load: arenas still map zero-copy
            return fmt.load_shard(index_dir, s, mmap=True, doc_range=dr)

        with obs_trace("load.read", shards=meta.num_shards,
                       threads=n_threads, verify=verify):
            if n_threads > 1:
                with ThreadPoolExecutor(
                        max_workers=n_threads,
                        thread_name_prefix="tpu-ir-load") as ex:
                    shards = list(ex.map(read_one,
                                         range(meta.num_shards)))
            else:
                shards = [read_one(s) for s in range(meta.num_shards)]

        with obs_trace("load.assemble", shards=meta.num_shards):
            df = np.zeros(v, np.int32)
            for z in shards:
                df[z["term_ids"]] = z["df"]
            indptr = np.concatenate([[0], np.cumsum(df, dtype=np.int64)])
            total = int(indptr[-1])
            pair_doc = np.empty(total, np.int32)
            pair_tf = np.empty(total, np.int32)
            for z in shards:
                lens = np.diff(z["indptr"]).astype(np.int64)
                n = int(lens.sum())
                if n == 0:
                    continue
                ends = np.cumsum(lens)
                within = np.arange(n, dtype=np.int64) - np.repeat(
                    ends - lens, lens)
                dest = np.repeat(indptr[z["term_ids"]], lens) + within
                pair_doc[dest] = z["pair_doc"]
                pair_tf[dest] = z["pair_tf"]
        return df, (pair_doc, pair_tf)

    # -- query pipeline ----------------------------------------------------

    # max vocabulary terms a single wildcard pattern may expand to
    WILDCARD_LIMIT = 64

    def _wildcard_lookups(self):
        """Lazy WildcardLookups (largest chargram k first), or [] when the
        index has no char-gram artifacts / wasn't loaded from a directory.
        The char-gram index always covers the TOKEN vocabulary: for k=1
        that is the index vocabulary itself (shared), for k>1 the builder's
        tokens.txt sidecar — expansions then compose into k-gram terms
        (see _analyze_wildcard_kgram)."""
        if not self._wildcard_tried:
            with self._lazy_lock:
                if not self._wildcard_tried:
                    self._load_wildcard_lookups()
        return self._wildcard or []

    def _load_wildcard_lookups(self) -> None:
        """One-time wildcard-lookup load (call under _lazy_lock); sets
        _wildcard_tried LAST so a concurrent reader can never observe
        tried=True with the lookups still unloaded."""
        try:
            if self._index_dir and self.meta.chargram_ks:
                from ..collection import Vocab
                from ..index.builder import TOKENS_VOCAB
                from .wildcard import WildcardLookup

                if self.meta.k == 1:
                    shared = self.vocab  # index vocab IS the token vocab
                else:
                    # load the tokens.txt sidecar ONCE and share it —
                    # one lookup per chargram k would otherwise re-read
                    # the same multi-MB file per k
                    tok = os.path.join(self._index_dir, TOKENS_VOCAB)
                    shared = Vocab.load(tok) if os.path.exists(tok) \
                        else None
                self._wildcard = [
                    WildcardLookup.load(self._index_dir, ck, vocab=shared)
                    for ck in sorted(self.meta.chargram_ks, reverse=True)]
        finally:
            self._wildcard_tried = True

    def _pattern_tokens(self, pattern: str) -> list[str] | None:
        """Token-vocabulary expansions of one glob pattern via the largest
        chargram k whose grams cover it; None when no lookup covers the
        pattern (too short for every k, e.g. bare '*')."""
        for lookup in self._wildcard_lookups():
            if lookup.pattern_grams(pattern):
                # k>1 truncation keeps the lexicographically-first LIMIT
                # matches — exactly the prefix a limited expand returns —
                # so a vocabulary-scale pattern ('a*' over 1M terms) never
                # materializes its full match list; k=1 needs every match
                # for the df-ranked truncation
                limit = (None if self.meta.k == 1
                         else self.WILDCARD_LIMIT + 1)
                terms = lookup.expand(pattern, limit=limit)
                if len(terms) > self.WILDCARD_LIMIT:
                    terms = self._truncate_expansion(pattern, terms)
                return terms
        return None

    def _truncate_expansion(self, pattern: str, terms: list[str]) -> list[str]:
        """Pinned truncation semantics for over-limit expansions.

        k=1 (the chargram index covers the INDEX vocabulary, so df is on
        hand): keep the WILDCARD_LIMIT highest-df matches — the terms that
        contribute most documents to the OR — with ties broken by
        ascending term id, and return them in that (df desc, id asc)
        order. k>1 (expansions live in the token sidecar vocabulary,
        which carries no df): keep the lexicographically-first
        WILDCARD_LIMIT matches (`WildcardLookup.expand` returns sorted
        term order). Both rules are deterministic under index rebuilds;
        tests pin them so a layout change cannot silently reorder
        wildcard results."""
        if self.meta.k != 1:
            # the limited expand hands us LIMIT+1 terms — enough to know
            # the expansion overflowed, not how far
            logger.warning(
                "pattern %r matches more than %d terms; expansion "
                "truncated to the lexicographically-first %d",
                pattern, self.WILDCARD_LIMIT, self.WILDCARD_LIMIT)
            return terms[: self.WILDCARD_LIMIT]
        logger.warning(
            "pattern %r matches %d terms; expansion truncated to %d",
            pattern, len(terms), self.WILDCARD_LIMIT)
        df = self._df_host()
        ids = np.array([self.vocab.id_or(t) for t in terms])
        order = np.lexsort((ids, -df[ids]))[: self.WILDCARD_LIMIT]
        return [terms[i] for i in order.tolist()]

    def _df_host(self) -> np.ndarray:
        if not hasattr(self, "_df_host_cache"):
            self._df_host_cache = np.asarray(self.df)
        return self._df_host_cache

    def _fuzzy_lookup_for(self, token: str, max_edits: int):
        """The chargram lookup fuzzy expansion should consult: the
        largest k whose count bound stays positive. Big k = fewest
        candidates, but past len(q)+3-k-edits*k < 1 the filter floors
        at 1 shared gram and short terms lose 1-edit neighbors that
        share NO k-gram ('cat'/'cut' at k=3) — then a smaller k is the
        correct index. One definition for BOTH the k=1 and the k>1
        composition paths, so their recall can never drift apart."""
        lookups = self._wildcard_lookups()
        return next(
            (lk for lk in lookups
             if len(token) + 3 - lk.k - max_edits * lk.k >= 1),
            lookups[-1])

    def _fuzzy_terms(self, token: str, max_edits: int) -> list[str]:
        """Pinned fuzzy expansion of one token over the index vocabulary:
        matches within `max_edits` Levenshtein edits, keeping at most
        WILDCARD_LIMIT ordered (distance asc, df desc, term id asc) — the
        same truncation contract as wildcards, with distance outranking
        df so a 1-edit rarity never loses its slot to a 2-edit stopword-
        grade term."""
        lookup = self._fuzzy_lookup_for(token, max_edits)
        matches = lookup.fuzzy(token, max_edits=max_edits)
        if not matches:
            return []
        ids = np.array([self.vocab.id_or(t) for t, _ in matches])
        dist = np.array([d for _, d in matches])
        df = self._df_host()
        order = np.lexsort((ids, -df[ids], dist))[: self.WILDCARD_LIMIT]
        if len(matches) > self.WILDCARD_LIMIT:
            logger.warning(
                "fuzzy token %r~%d matches %d terms; expansion truncated "
                "to %d", token, max_edits, len(matches),
                self.WILDCARD_LIMIT)
        return [matches[i][0] for i in order.tolist()]

    def _expand_fuzzy(self, text: str) -> tuple[str, list[int]]:
        """Pull 'token~[d]' fuzzy tokens out of a query; returns the text
        with them removed plus the term ids of their expansions (an OR,
        same semantics as wildcard expansion)."""
        extra: list[int] = []

        def repl(m: re.Match) -> str:
            from .wildcard import MAX_FUZZY_EDITS

            tok = m.group(1).strip(_EDGE_PUNCT).lower()
            if not tok or "*" in tok or "?" in tok:
                return m.group(0)  # mixed glob+fuzzy: leave to the glob path
            # '~0' = exact vocabulary probe (Lucene), '~' alone = 1 edit
            d = min(int(m.group(2)) if m.group(2) else 1, MAX_FUZZY_EDITS)
            for t in self._fuzzy_terms(tok, d):
                tid = self.vocab.id_or(t)
                if tid >= 0:
                    extra.append(tid)
            return " "

        return _FUZZY_RE.sub(repl, text), extra

    def _expand_wildcards(self, text: str) -> tuple[str, list[int]]:
        """Pull glob tokens ('te*', 'ho?se') out of a query; return the text
        with them removed plus the term-ids of their vocabulary expansions
        (an OR over expansions — the wildcard query semantics the reference's
        char-k-gram index was built for but never wired into search;
        SURVEY.md §0 pipeline 2)."""
        extra: list[int] = []

        def expand_part(part: str) -> None:
            # use the largest chargram k whose grams cover the pattern; a
            # pattern too short for every k (e.g. '*') is skipped rather than
            # falling back to a full-vocabulary scan in the query hot path
            terms = self._pattern_tokens(part.lower())
            for t in terms or []:
                tid = self.vocab.id_or(t)
                if tid >= 0:
                    extra.append(tid)

        def repl(m: re.Match) -> str:
            token = m.group(0).strip(_EDGE_PUNCT)
            literals = []
            for part in _GLOB_SPLIT_RE.split(token):
                # a trailing '?' is question punctuation, not a glob:
                # 'river?' means the literal term 'river'
                part = part.rstrip("?")
                if not part:
                    continue
                if ("*" not in part and "?" not in part
                        # with no char-gram index, leave the part to the
                        # literal analyzer (which splits on metacharacters)
                        or not self._wildcard_lookups()):
                    literals.append(part)
                else:
                    expand_part(part)
            return " ".join(literals) if literals else " "

        return _WILDCARD_RE.sub(repl, text), extra

    def _fuzzy_tokens(self, token: str, max_edits: int) -> list[str]:
        """Token-vocabulary fuzzy expansions for the k>1 composition
        path. The chargram sidecar there covers tokens.txt, which carries
        no df, so the truncation rule is (distance asc, term asc) — the
        deterministic fuzzy analogue of the k>1 wildcard rule, and
        WildcardLookup.fuzzy's native order. Note the `limit` truncates
        the ORDERED result; the candidate scan itself still filters the
        full match set (ADVICE r4), so a high-df token pays the whole
        bincount + Levenshtein cost either way."""
        lookup = self._fuzzy_lookup_for(token, max_edits)
        matches = lookup.fuzzy(token, max_edits=max_edits,
                               limit=self.WILDCARD_LIMIT + 1)
        if len(matches) > self.WILDCARD_LIMIT:
            logger.warning(
                "fuzzy token %r~%d matches more than %d terms; expansion "
                "truncated", token, max_edits, self.WILDCARD_LIMIT)
            matches = matches[: self.WILDCARD_LIMIT]
        return [t for t, _ in matches]

    def _analyze_expansion_kgram(self, text: str) -> list[int]:
        """k>1 wildcard/fuzzy semantics: expand each glob or fuzzy token
        over the TOKEN vocabulary (tokens.txt), then compose candidate
        k-gram index terms from every k-slot window — the cartesian
        product over the window's expansion sets, capped at
        WILDCARD_LIMIT candidates per window. Each window is an OR over
        its candidates (same semantics as the k=1 expansion); unknown
        composed grams are dropped like any dictionary miss."""
        import itertools

        from .wildcard import MAX_FUZZY_EDITS

        slots: list[list[str]] = []
        for raw in text.split():
            fm = (None if "*" in raw or "?" in raw
                  else _FUZZY_RE.search(raw))
            if fm is not None:
                # fuzzy token -> one expansion slot (mirrors the k=1
                # _expand_fuzzy extraction rules: edge punct stripped,
                # '~0' = exact vocabulary probe, distance capped)
                tok = fm.group(1).strip(_EDGE_PUNCT).lower()
                if tok:
                    d = min(int(fm.group(2)) if fm.group(2) else 1,
                            MAX_FUZZY_EDITS)
                    slots.append(self._fuzzy_tokens(tok, d))
                    continue
                # empty after punct strip: literal analysis, like k=1
            if "*" in raw or "?" in raw:
                token = raw.strip(_EDGE_PUNCT)
                for part in _GLOB_SPLIT_RE.split(token):
                    part = part.rstrip("?")
                    if not part:
                        continue
                    if "*" not in part and "?" not in part:
                        for t in self._analyzer.analyze(part):
                            slots.append([t])
                    else:
                        # no expansion = a slot no window matches through
                        slots.append(self._pattern_tokens(part.lower())
                                     or [])
            else:
                # literal tokens go through the standard analyzer (may
                # yield 0..n tokens, e.g. stopwords vanish)
                for t in self._analyzer.analyze(raw):
                    slots.append([t])
        k = self.meta.k
        row: list[int] = []
        seen: set[int] = set()
        for i in range(max(len(slots) - k + 1, 0)):
            window = slots[i : i + k]
            if any(not s for s in window):
                continue
            # cap the window's cartesian product at WILDCARD_LIMIT combos
            # by budgeting each multi-candidate slot the same share —
            # itertools.product varies the LAST slot fastest, so a plain
            # islice would exhaust the limit on the first expansion of a
            # leading glob and silently drop every other one
            n_multi = sum(1 for s in window if len(s) > 1)
            if n_multi:
                # exact integer root: float ** (1/n) truncates (64**(1/3)
                # -> 3.9999... -> int 3, i.e. 27 of the budgeted 64 combos)
                per_slot = max(
                    int(self.WILDCARD_LIMIT ** (1.0 / n_multi)), 1)
                while (per_slot + 1) ** n_multi <= self.WILDCARD_LIMIT:
                    per_slot += 1
                window = [s[:per_slot] if len(s) > 1 else s
                          for s in window]
            for combo in itertools.islice(
                    itertools.product(*window), self.WILDCARD_LIMIT):
                tid = self.vocab.id_or(KGRAM_SEP.join(combo))
                if tid >= 0 and tid not in seen:
                    seen.add(tid)
                    row.append(tid)
        return row

    def analyze_queries(
        self, texts: Sequence[str], max_terms: int | None = None,
        width_floor: int | None = None,
    ) -> np.ndarray:
        """Analyze query texts into an int32 [B, L] id array (PAD -1).

        Unknown terms (not in the vocabulary) are dropped, like the
        reference's dictionary miss path (IntDocVectorsForwardIndex.java:
        150-153 returns null -> term skipped). Glob tokens expand to an OR
        over matching vocabulary terms via the char-k-gram index.

        `width_floor` pads L up to at least that many slots before the
        pow2 bucketing (never truncates): the coalescing frontend pins
        every batch to ONE precompilable width, so batch content cannot
        mint per-batch compile shapes (-1 slots score exact 0.0 — the
        explain suite pins PAD exactness, so a wider row is bit-exact)."""
        rows = []
        for text in texts:
            extra: list[int] = []
            has_fuzzy = "~" in text and _FUZZY_RE.search(text) is not None
            if has_fuzzy and not self._wildcard_lookups():
                # loud, not silent: without char-gram artifacts the '~'
                # falls to the analyzer's punctuation handling and the
                # user would otherwise never learn why 'salmn~' found
                # nothing
                logger.warning(
                    "query %r contains a fuzzy token but the index has "
                    "no char-gram artifacts; '~' is treated as "
                    "punctuation (rebuild with chargrams for fuzzy)",
                    text)
            if has_fuzzy and self.meta.k == 1 and self._wildcard_lookups():
                # fuzzy tokens ('salmn~', 'color~2') expand to an OR over
                # near-miss vocabulary terms
                text, extra = self._expand_fuzzy(text)
            has_glob = "*" in text or "?" in text
            if ((has_glob or has_fuzzy) and self.meta.k > 1
                    and self._wildcard_lookups()):
                # k>1: glob AND fuzzy tokens expand over the token
                # sidecar vocabulary and compose into k-gram windows
                rows.append(self._analyze_expansion_kgram(text))
                continue
            if has_glob:
                text, wc_extra = self._expand_wildcards(text)
                extra += wc_extra
            toks = self._analyzer.analyze(text)
            grams = kgram_terms(toks, self.meta.k)
            ids = [self.vocab.id_or(g) for g in grams]
            row = [i for i in ids if i >= 0]
            # expansions are an OR: drop ids already contributed by literal
            # terms (or another pattern) so nothing is scored twice
            seen = set(row)
            row += [i for i in dict.fromkeys(extra) if i not in seen]
            rows.append(row)
        cap = max_terms or max((len(r) for r in rows), default=1)
        cap = max(cap, 1)
        if max_terms is None:
            if width_floor:
                cap = max(cap, int(width_floor))
            # bucket the width to a power of two so the set of compiled
            # programs stays small (wildcard expansion would otherwise mint
            # a fresh width — and a fresh XLA compile — per query shape)
            cap = 1 << (cap - 1).bit_length()
        out = np.full((len(rows), cap), -1, np.int32)
        for i, r in enumerate(rows):
            out[i, : min(len(r), cap)] = r[:cap]
        return out

    # max elements of the [B_block, D+1] score accumulator per dispatch
    SCORE_BUDGET = 250_000_000
    # the big cold tiers as one chunk table (ops.scoring.ColdChunks),
    # built at load on the tiered layout when a tier is wide enough
    cold_chunks = None
    # minimum hot-free group size worth its own (matmul-skipping)
    # dispatch when the batch is mixed
    MIN_SKIP_GROUP = 32

    def _blocked_dispatch(self, block: int, dispatch, *arrays_pads):
        """Run a per-block device dispatch over padded query-row blocks.

        `arrays_pads` are (array [B, W], pad_value) pairs sliced in lockstep;
        batches larger than `block` are padded to whole blocks so every
        dispatch reuses one compiled shape. All blocks are dispatched before
        any result is fetched, and the score / docno copies run concurrently
        — the device transport has a large fixed per-fetch latency, so
        overlapping transfers is worth more than any compute tuning here.

        Profiling (ISSUE 7): the D2H copies are issued async first (the
        overlap above, unchanged), then the wait for device completion is
        timed as the `dispatch.device` span — with the shim's
        dispatch.trace/dispatch.compile this decomposes the fixed
        per-dispatch RTT, up to the results' arrival on the host — and
        one memory gauge sample lands after every dispatch (device
        bytes_in_use/peak + host RSS)."""
        import jax

        from ..obs import profiling

        b = arrays_pads[0][0].shape[0]
        if b == 0:
            return np.zeros((0, 0), np.float32), np.zeros((0, 0), np.int32)
        if b > block:
            padded = (b + block - 1) // block * block
            padded_arrays = []
            for a, pad_value in arrays_pads:
                ap = np.full((padded, a.shape[1]), pad_value, a.dtype)
                ap[:b] = a
                padded_arrays.append(ap)
            outs = [dispatch(*(ap[i : i + block] for ap in padded_arrays))
                    for i in range(0, padded, block)]
        else:
            outs = [dispatch(*(a for a, _ in arrays_pads))]
        flat_outs = [a for pair in outs for a in pair]
        issue_host_copies(flat_outs)  # in flight before the wait, as before
        with obs_trace("dispatch.device", blocks=len(outs)):
            jax.block_until_ready(flat_outs)
            # the host copies issued above land here: their tail is a
            # wait on the device transport too
            flat = [np.asarray(a) for a in flat_outs]
        profiling.sample_memory()
        parts = [(flat[i], flat[i + 1]) for i in range(0, len(flat), 2)]
        if len(parts) == 1:
            return parts[0]
        return (np.concatenate([p[0] for p in parts])[:b],
                np.concatenate([p[1] for p in parts])[:b])

    def topk(
        self, q_terms: np.ndarray, k: int = 10, scoring: str = "tfidf",
        deadline_s: float | None = None, *, hot_only: bool = False,
        force_host: bool = False,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Score an id batch. Returns (scores [B,k], docnos [B,k], 0=empty).

        Large batches are scored in query blocks so the per-dispatch score
        accumulator stays within SCORE_BUDGET elements regardless of corpus
        size (the reference had no batching at all; SURVEY.md §3.3).

        Degraded-mode serving: with a per-batch deadline (`deadline_s`
        here or on the Scorer), a dispatch that overruns it — or dies
        with a device loss — falls back down the serving chain (resident
        device layout -> host CPU scoring over the postings columns) and
        the batch is flagged via the tagged return (topk_tagged /
        SearchResult.degraded),
        so the engine returns bounded-latency answers instead of hanging
        ("The Tail at Scale"). A deadline of None with no fault plan
        installed takes the primary path with zero added work.

        MaxScore scheduling (prune on, tiered layout): queries WITHOUT
        hot-strip terms have a hot-stage upper bound of exactly 0 — the
        host knows this before dispatch — so they are stably packed into
        their own blocks and scored by the STATIC cold-only kernel
        (skip_hot: no hot matmul, no runtime machinery, bit-identical
        scores); only the blocks that actually contain hot query terms
        pay the hot-strip stage. Results return in the caller's order.
        (The runtime-bounded lax.cond variant exists in the kernels but
        measured slower than the matmul it skips on CPU — its top-C over
        [B, D+1] is not free — so the production path is this zero-
        overhead static specialization.)

        `hot_only=True` scores just the hot strip on the tiered/sharded
        layouts (the overload ladder's cheapest device level; partial
        scores — tag the results). `force_host=True` answers from the
        host CPU backend directly with NO device dispatch and no deadline
        thread — the circuit-breaker-open serving path."""
        s, d, _ = self.topk_tagged(q_terms, k=k, scoring=scoring,
                                   deadline_s=deadline_s,
                                   hot_only=hot_only,
                                   force_host=force_host)
        return s, d

    def topk_tagged(
        self, q_terms: np.ndarray, k: int = 10, scoring: str = "tfidf",
        deadline_s: float | None = None, *, hot_only: bool = False,
        force_host: bool = False, donate: bool = False,
        uniform: tuple | None = None,
    ) -> tuple[np.ndarray, np.ndarray, bool]:
        """topk() with the per-request degraded flag threaded through the
        return value: (scores, docnos, degraded). This is THE thread-safe
        surface (ISSUE 9 retired the racy scorer-level `degraded_last`
        alias — under coalesced shared batches the tagged return is the
        only correct source).

        `donate=True` routes the dispatch through the donated-query
        kernel twins (ops/scoring.py `*_dq`): the [B, L] query block's
        device buffer is donated to XLA — the coalescing frontend's
        per-batch upload never needs it back. Applied only where
        supported (dense/tiered device path on a donating backend).

        `uniform=(rungs...)` (the coalesced serving path) replaces the
        content-dependent pow2 group padding of MaxScore scheduling
        with LADDER-RUNG padding: the hot-free and hot groups each pad
        to the smallest rung that fits, so the whole compiled-program
        set is rungs x {skip, full} per scoring model — precompilable
        at frontend start, and batch content can never mint a fresh
        XLA shape mid-serving. Group membership still follows the
        scheduler's exact plan (skip kernel pinned bit-identical on
        hot-free rows), so results cannot differ."""
        q = np.asarray(q_terms, np.int32)
        out = self._dispatch_degradable(
            lambda: self._topk_primary(q, k, scoring, hot_only=hot_only,
                                       donate=donate, uniform=uniform),
            lambda: self._topk_host(q, k, scoring),
            deadline_s, "score dispatch",
            "answering from the host CPU backend", force_host=force_host)
        # ledger the batch's block-max mask decisions AFTER its results
        # were fetched (never serializing the dispatch overlap)
        self._drain_blockmax_stats()
        return out

    def _dispatch_degradable(self, primary, fallback, deadline_s,
                             label, consequence, force_host=False):
        """The degraded-serving wrapper shared by topk() and
        rerank_topk(): run `primary` under the per-batch deadline; on
        expiry or device loss, count + log the event and answer with
        `fallback`. Any other exception re-raises — a program/shape bug
        must never silently degrade. With no deadline and no fault plan
        installed this is a plain call.

        Returns (result..., degraded): the per-request degraded flag is
        appended to the primary/fallback (scores, docnos) tuple — the
        ONLY degradation source; under coalesced shared batches a
        scorer-level "last outcome" field would be cross-request state.

        `force_host=True` skips the device path entirely — the serving
        frontend's open circuit breaker routes here so a known-down
        device costs host-fallback latency, not a deadline per request."""
        if force_host:
            recovery_counters().incr("forced_host_batches")
            with obs_trace("fallback", label=label, forced=True):
                return fallback() + (True,)
        deadline = self.deadline_s if deadline_s is None else deadline_s
        if deadline is None and faults.active() is None:
            with obs_trace("dispatch", label=label):
                return primary() + (False,)
        reason = None
        try:
            # the dispatch span covers the whole deadline window; an
            # expiry/device-loss escapes THROUGH it (error recorded on
            # the span) before the except arms classify it below
            with obs_trace("dispatch", label=label, deadline_s=deadline):
                return (faults.run_with_deadline(primary, deadline)
                        + (False,))
        except faults.ScoreDeadlineExceeded as e:
            recovery_counters().incr("deadline_expired")
            reason = str(e)
        except Exception as e:
            if not faults.is_device_loss(e):
                raise
            recovery_counters().incr("device_loss")
            reason = f"device loss: {e}"
        recovery_counters().incr("degraded_batches")
        logger.warning("%s degraded (%s); %s", label, reason, consequence)
        with obs_trace("fallback", label=label, reason=reason):
            return fallback() + (True,)

    def _topk_primary(self, q: np.ndarray, k: int, scoring: str,
                      hot_only: bool = False, donate: bool = False,
                      uniform: tuple | None = None):
        """The device scoring path (all layouts + MaxScore scheduling;
        `uniform=(rungs...)` = rung-padded scheduled groups — the
        coalesced static-shape serving path, see topk_tagged)."""
        block = self._block_size()
        if (uniform and not hot_only and self.layout == "sparse"
                and self.prune):
            return self._topk_uniform(q, k, scoring, uniform,
                                      donate=donate)
        if hot_only or self.layout != "sparse" or not self.prune:
            # hot_only: no MaxScore scheduling — the cold stages it
            # schedules around are statically absent. Rung-padded
            # batches keep the worst-case chunk capacity, so their
            # shapes stay a function of the rung
            return self._blocked_dispatch(
                block, lambda qb: self._topk_device(
                    qb, k, scoring, hot_only=hot_only, donate=donate,
                    fit_chunks=uniform is None),
                (q, -1))
        with obs_trace("search.schedule", queries=len(q)):
            has_hot, n_free, mode = self._skip_plan(q)
        if mode == "all_skip":
            self._ledger_skip_plan(len(q), n_free,
                                   -(-len(q) // block), 0)
            return self._blocked_dispatch(
                block,
                lambda qb: self._topk_device(qb, k, scoring,
                                             skip_hot=True,
                                             donate=donate,
                                             fit_chunks=True), (q, -1))
        if mode == "all_full":
            # too few hot-free queries to pay an extra dispatch for
            self._ledger_skip_plan(len(q), n_free, 0,
                                   -(-len(q) // block))
            return self._blocked_dispatch(
                block, lambda qb: self._topk_device(qb, k, scoring,
                                                    donate=donate,
                                                    fit_chunks=True),
                (q, -1))
        self._ledger_skip_plan(len(q), n_free, -(-n_free // block),
                               -(-(len(q) - n_free) // block))
        with obs_trace("search.schedule", queries=len(q)):
            order = self._schedule_order(has_hot)
            inv = np.argsort(order, kind="stable")
            qs = q[order]
        s1, d1 = self._group_dispatch(qs[:n_free], block,
                                      lambda qb: self._topk_device(
                                          qb, k, scoring, skip_hot=True,
                                          donate=donate, fit_chunks=True))
        s2, d2 = self._group_dispatch(qs[n_free:], block,
                                      lambda qb: self._topk_device(
                                          qb, k, scoring, donate=donate,
                                          fit_chunks=True))
        return (np.concatenate([s1, s2])[inv],
                np.concatenate([d1, d2])[inv])

    def _topk_host(self, q: np.ndarray, k: int, scoring: str):
        """Degraded-mode terminal fallback: score the batch on the host
        CPU from the CSR postings columns — no device, no jit, bounded
        latency. Same scoring models (and tie-break: score desc, docno
        asc) as the device kernels, accumulated in float32 per posting
        slice; tiny float differences vs the fused device einsums are
        possible, which is why results ride tagged `degraded`.

        Known cost on the serving-cache fast path: the cache carries no
        CSR columns, so the FIRST degraded batch of such a Scorer pays
        the lazy shard-read + assembly (`_pairs`) once — slow, but finite
        and off the lost/hung device; every later degraded batch reuses
        the assembled columns."""
        from .phrase import B as _b, K1 as _k1  # THE shared BM25 constants

        if self._pairs_cols is None:
            logger.warning(
                "degraded fallback is assembling the postings columns "
                "from the part shards (one-time; the serving cache does "
                "not carry them)")
        pd, ptf = self._pairs_doc_tf
        df = self._df_host().astype(np.int64)
        indptr = np.concatenate([[0], np.cumsum(df, dtype=np.int64)])
        n = self.meta.num_docs
        doc_len = np.asarray(self.doc_len).astype(np.float32)
        if scoring == "bm25":
            dff = df.astype(np.float32)
            idf = np.where(df > 0,
                           np.log1p((n - dff + 0.5) / (dff + 0.5)),
                           0.0).astype(np.float32)
            avg = float(doc_len.sum()) / max(n, 1)
            dl_norm = 1.0 - _b + _b * doc_len / max(avg, 1e-9)
        else:
            if self.compat_int_idf:
                ratio = (n // np.maximum(df, 1)).astype(np.float32)
                idf = np.log10(np.maximum(ratio, 1e-30))
            else:
                # the device formula (ops/scoring.py idf_weights)
                idf = np.log1p((n - df) / np.maximum(df, 1)) / np.log(10.0)
            idf = np.where(df > 0, idf, 0.0).astype(np.float32)
        out_s = np.zeros((len(q), k), np.float32)
        out_d = np.zeros((len(q), k), np.int32)
        scores = np.zeros(n + 1, np.float32)
        for qi, row in enumerate(q):
            scores[:] = 0.0
            for tid in row:
                if tid < 0 or tid >= len(df) or df[tid] == 0:
                    continue
                sl = slice(int(indptr[tid]), int(indptr[tid + 1]))
                tf = ptf[sl].astype(np.float32)
                if scoring == "bm25":
                    w = idf[tid] * tf * (_k1 + 1.0) / np.maximum(
                        tf + _k1 * dl_norm[pd[sl]], 1e-9)
                else:
                    w = (1.0 + np.log(np.maximum(tf, 1.0))) * idf[tid]
                # docnos are unique within one term's postings run, so
                # fancy-index += accumulates correctly across terms
                scores[pd[sl]] += w
            if self.doc_range is not None:
                # shard-worker restriction: the sparse layout's pair
                # columns stay GLOBAL (the device layout is what's
                # masked), so the host fallback must apply the range
                # itself or a degraded batch would leak docs another
                # shard owns into this worker's results
                lo, hi = self.doc_range
                scores[:lo] = 0.0
                scores[hi + 1:] = 0.0
            top = np.argsort(-scores[1:], kind="stable")[:k] + 1
            keep = scores[top] > 0.0
            m = int(keep.sum())  # desc order => positives are a prefix
            out_s[qi, :m] = scores[top[:m]]
            out_d[qi, :m] = top[:m]
        return out_s, out_d

    def _skip_plan(self, q: np.ndarray):
        """The MaxScore scheduling decision, single source for topk()
        and prune_diag(): (has_hot [B], n_free, mode) with mode one of
        'all_skip' (every query hot-free), 'all_full' (too few to pay an
        extra dispatch), 'split' (grouped dispatch)."""
        has_hot = self._has_hot(q)
        n_free = int((~has_hot).sum())
        if n_free == len(q):
            mode = "all_skip"
        elif n_free < self.MIN_SKIP_GROUP:
            mode = "all_full"
        else:
            mode = "split"
        return has_hot, n_free, mode

    def _ledger_skip_plan(self, n_queries: int, n_free: int,
                          skip_blocks: int, full_blocks: int) -> None:
        """Raw MaxScore-scheduling counters (ISSUE 13 satellite): the
        derived fractions prune_diag reports stay, but operators scrape
        the raw terms from /profile, `tpu-ir stats` and Prometheus."""
        from ..obs import get_registry

        reg = get_registry()
        reg.incr("prune.queries", n_queries)
        reg.incr("prune.queries_hot_free", n_free)
        reg.incr("prune.blocks_total", skip_blocks + full_blocks)
        reg.incr("prune.blocks_skip_hot", skip_blocks)

    def _topk_uniform(self, q: np.ndarray, k: int, scoring: str,
                      rungs: tuple, *, donate: bool = False):
        """The coalesced static-shape dispatch (ISSUE 9): the exact
        MaxScore partition (hot-free rows — including the rung pad rows,
        which are all -1 — never pay the hot-strip matmul), with each
        group padded to the smallest LADDER rung that fits instead of a
        content-dependent pow2 bucket. The compiled-program universe is
        `rungs x {skip, full}` per scoring model, walked once by the
        frontend's precompile, so no serving batch ever waits on XLA."""
        block = self._block_size()
        with obs_trace("search.schedule", queries=len(q)):
            has_hot = self._has_hot(q)
            n_free = int((~has_hot).sum())

        def skip_fn(qb):
            return self._topk_device(qb, k, scoring, skip_hot=True,
                                     donate=donate)

        def full_fn(qb):
            return self._topk_device(qb, k, scoring, donate=donate)

        if n_free == len(q):
            self._ledger_skip_plan(len(q), n_free,
                                   -(-len(q) // block), 0)
            return self._rung_dispatch(q, block, rungs, skip_fn)
        # all-PAD rows (rung padding, empty-after-analysis queries)
        # score exact 0.0 under EITHER kernel — when they are the only
        # "hot-free" content, a separate skip dispatch would burn a
        # whole per-dispatch round trip scoring nothing but padding
        real_free = int((~has_hot & ~(q < 0).all(axis=1)).sum())
        if real_free == 0:
            self._ledger_skip_plan(len(q), n_free, 0,
                                   -(-len(q) // block))
            return self._rung_dispatch(q, block, rungs, full_fn)
        if real_free < self.MIN_SKIP_GROUP and _rtt_dominated_backend():
            # the MIN_SKIP_GROUP economy, serving edition — but only
            # where it holds: on an RTT-dominated backend (TPU) the
            # second dispatch costs a full round trip while the hot
            # matmul rides nearly free on the MXU, so small hot-free
            # groups fold into the full dispatch (bit-identical,
            # pinned). On CPU the inequality flips — the matmul is the
            # dominant cost and the extra dispatch is ~nothing — so
            # there the split always wins and the fold is skipped.
            self._ledger_skip_plan(len(q), n_free, 0,
                                   -(-len(q) // block))
            return self._rung_dispatch(q, block, rungs, full_fn)
        # the same dispatch-block unit _topk_primary ledgers (ceil of
        # real group rows over the block size): the scraped fractions
        # must measure one thing whichever dispatch path served
        self._ledger_skip_plan(len(q), n_free,
                               max(-(-n_free // block), 1),
                               max(-(-(len(q) - n_free) // block), 1))
        with obs_trace("search.schedule", queries=len(q)):
            order = self._schedule_order(has_hot)
            inv = np.argsort(order, kind="stable")
            qs = q[order]
        s1, d1 = self._rung_dispatch(qs[:n_free], block, rungs, skip_fn)
        s2, d2 = self._rung_dispatch(qs[n_free:], block, rungs, full_fn)
        return (np.concatenate([s1, s2])[inv],
                np.concatenate([d1, d2])[inv])

    def _rung_dispatch(self, qg: np.ndarray, block: int, rungs: tuple,
                       dispatch):
        """Dispatch one scheduled group padded to its ladder rung (cf.
        _group_dispatch, whose pow2 buckets depend on batch content)."""
        b = len(qg)
        pad_to = next((r for r in rungs if r >= b), b)
        if pad_to <= b:
            return self._blocked_dispatch(block, dispatch, (qg, -1))
        qp = np.full((pad_to, qg.shape[1]), -1, np.int32)
        qp[:b] = qg
        s, d = self._blocked_dispatch(block, dispatch, (qp, -1))
        return s[:b], d[:b]

    def _group_dispatch(self, qg: np.ndarray, block: int, dispatch):
        """Dispatch one schedule group, padding its row count to a
        power-of-two bucket: group sizes are CONTENT-dependent (how many
        queries were hot-free), and an unpadded dispatch would mint a
        fresh XLA compile per distinct size (cf. the query-width
        bucketing in analyze_queries)."""
        b = len(qg)
        cap = 1 << max(b - 1, 0).bit_length()
        if cap < block:
            pad_to = cap          # pow2 bucket below the block size
        else:
            # pad to whole blocks: _blocked_dispatch sends any tail
            # smaller than `block` at its raw shape, which for a
            # content-dependent group size would mint a fresh compile
            pad_to = -(-b // block) * block
        if pad_to == b:
            return self._blocked_dispatch(block, dispatch, (qg, -1))
        qp = np.full((pad_to, qg.shape[1]), -1, np.int32)
        qp[:b] = qg
        s, d = self._blocked_dispatch(block, dispatch, (qp, -1))
        return s[:b], d[:b]

    def _block_size(self) -> int:
        """Queries per dispatch block: one [block, doc-axis] f32 score
        accumulator stays within SCORE_BUDGET elements."""
        return max(1, self.SCORE_BUDGET // self._doc_axis_width())

    def _has_hot(self, q: np.ndarray) -> np.ndarray:
        """Bool [B]: does the query reference any hot-strip term? (The
        MaxScore partition, computed host-side: hot-free queries have a
        hot-stage upper bound of exactly 0.)"""
        hot_rank = self._hot_rank_host()
        # mirror the kernels' q_valid mask: out-of-vocabulary ids score
        # zero there and must not crash the host-side gather here
        valid = (q >= 0) & (q < len(hot_rank))
        return ((hot_rank[np.where(valid, q, 0)] >= 0) & valid).any(axis=1)

    @staticmethod
    def _schedule_order(has_hot: np.ndarray) -> np.ndarray:
        """THE schedule: stable order putting hot-term-free (ub = 0)
        queries first. Single source for topk()'s grouped dispatch, the
        bench's device query control, and the scheduling tests."""
        return np.argsort(has_hot, kind="stable")

    def _prune_schedule(self, q: np.ndarray) -> np.ndarray:
        """Schedule order for a raw query batch (see _schedule_order)."""
        return self._schedule_order(self._has_hot(q))

    def _hot_rank_host(self) -> np.ndarray:
        if not hasattr(self, "_hot_rank_host_cache"):
            self._hot_rank_host_cache = np.asarray(self.hot_rank)
        return self._hot_rank_host_cache

    def prune_diag(self, q_terms: np.ndarray) -> dict:
        """MaxScore engagement report for a query batch on the tiered
        layout, matching what topk() actually dispatches (via the shared
        _skip_plan): the fraction of queries with zero hot-stage bound
        (hot-free) and the fraction of scheduled blocks that run the
        static cold-only kernel."""
        if self.layout != "sparse":
            return {"prune_layout": self.layout}
        if not self.prune:
            return {"prune_applicable": False}
        q = np.asarray(q_terms, np.int32)
        block = self._block_size()
        _, n_free, mode = self._skip_plan(q)
        if mode == "all_skip":
            skip_blocks, full_blocks = -(-len(q) // block), 0
        elif mode == "all_full":
            skip_blocks, full_blocks = 0, -(-len(q) // block)
        else:
            skip_blocks = -(-n_free // block)
            full_blocks = -(-(len(q) - n_free) // block)
        total = max(skip_blocks + full_blocks, 1)
        return {
            "prune_hot_free_query_fraction": round(
                n_free / max(len(q), 1), 4),
            "prune_skip_block_fraction": round(skip_blocks / total, 4),
            "prune_block_queries": block,
        }

    def _doc_axis_width(self) -> int:
        """Per-device score-accumulator width: the full doc axis, or one
        doc block on the sharded layout (each device only holds dblk+1)."""
        if self.layout == "sharded":
            return self._sharded.dblk + 1
        return self.meta.num_docs + 1

    # -- block-max pruning (ISSUE 13) -----------------------------------

    def _blockmax_plan(self, k: int, scoring: str):
        """Static engagement decision for one full (hot-containing)
        tiered dispatch: (bound_table, width, cand_blocks) or None.
        Deterministic per (k, scoring, layout, knobs), so the coalescing
        frontend's precompile walks the same program the serving path
        dispatches. Results are bit-identical engaged or not — the knob
        (TPU_IR_BLOCKMAX) exists for A/B runs and rollback."""
        if (self.layout != "sparse" or not self.prune
                or self._hot_blk_max is None
                or not self._blockmax_width
                or scoring not in ("tfidf", "bm25")):
            return None
        from ..utils import envvars

        if envvars.get_choice("TPU_IR_BLOCKMAX") == "0":
            return None
        from ..ops.scoring import blockmax_cand_blocks

        width = self._blockmax_width
        nblk = self._hot_blk_max.shape[1]
        cand = blockmax_cand_blocks(k, self.meta.num_docs, width)
        # engage only when the mask can actually skip work (a budget at
        # or above the block count degenerates to the full stage plus
        # machinery) and the candidate columns can hold the top-k
        if (cand + 2 > nblk or k > cand * width
                or k > self.meta.num_docs + 1):
            return None
        return self._blockmax_bound_table(scoring), width, cand

    def _strip_dtype(self, tiers) -> str:
        """Device dtype for the dense hot strip — "bfloat16" when the
        index is compressed (or TPU_IR_COMPRESS=1 opts serving in) AND
        every hot tf round-trips bf16 exactly (integers <= 256 fit the
        8-bit mantissa; quantized-int8 tfs satisfy this by
        construction), so the strip holds half the HBM with scores
        still bit-identical: the kernels widen to fp32 at the
        weight-curve entry (ops/scoring._lntf, bm25_saturation) and an
        exactly-representable tf widens to the exact same fp32 value
        the raw path computed with. An index whose tfs do NOT
        round-trip falls back to fp32 LOUDLY — silent narrowing would
        be a ranking change, not a memory optimization."""
        from ..utils import envvars

        if not (getattr(self.meta, "compressed", False)
                or envvars.get_choice("TPU_IR_COMPRESS") == "1"):
            return "float32"
        import ml_dtypes

        f32 = np.asarray(tiers.hot_vals).astype(np.float32)
        if np.array_equal(
                f32, f32.astype(ml_dtypes.bfloat16).astype(np.float32)):
            return "bfloat16"
        logger.warning(
            "compressed index requested a bf16 hot strip but %d hot tfs "
            "do not round-trip bf16 exactly; serving the strip in fp32 "
            "(bit-exact, no HBM saving)",
            int((f32 != f32.astype(ml_dtypes.bfloat16)
                 .astype(np.float32)).sum()))
        return "float32"

    def _hot_wstrip(self, scoring: str):
        """The device-cached PRE-WEIGHTED hot strip for a scoring mode
        (ops/scoring.py lntf_strip / bm25_strip), or None when disabled
        (TPU_IR_BLOCKMAX_STRIP_CACHE) or over the memory budget. The
        weighting is query-independent, yet the in-kernel hot stage
        recomputes it per dispatch — an O(H * D) elementwise pass that
        measures ~5x the gemm it feeds on CPU backends; caching it turns
        the hot stage into the gemm alone. Values are bit-identical
        (same elementwise expression, no reassociation freedom — pinned
        by the block-max parity suite). TF-IDF and the cosine rerank
        share the (1 + ln tf) strip; BM25 gets its saturated twin."""
        if self.layout != "sparse":
            return None
        from ..utils import envvars

        mode = envvars.get_choice("TPU_IR_BLOCKMAX_STRIP_CACHE")
        if mode == "0":
            return None
        h, d1 = self.hot_tfs.shape
        if mode == "auto":
            from .layout import HOT_BUDGET

            # each cached mode costs one more strip-sized buffer; stay
            # within half the hot budget per strip so the raw strip plus
            # both mode twins cannot exceed 2x the budgeted footprint
            if h * d1 > HOT_BUDGET // 2:
                return None
        cache = self.__dict__.setdefault("_wstrip_cache", {})
        key = "bm25" if scoring == "bm25" else "tfidf"
        if key in cache:
            return cache[key]
        from ..ops.scoring import bm25_strip, lntf_strip

        # computed OUTSIDE the lazy lock (device dispatch — lint TPU202);
        # a racing loser's copy is garbage-collected, never corruption.
        # A bf16 resident strip (compressed arena, _strip_dtype) widens
        # FIRST: its integer tfs are bf16-exact, so the widened strip is
        # bit-identical to the raw path's fp32 strip and the cached
        # weighted twin stays inside the compression parity contract
        # (the eager standalone strip build has no FMA-contraction
        # freedom; the in-kernel weighting does, so raw-with-wstrip vs
        # compressed-without would drift one ulp on BM25). Engagement is
        # dtype-independent (same h*d1 budget test), so raw and
        # compressed always make the SAME wstrip decision.
        hot = self.hot_tfs
        if hot.dtype != jnp.float32:
            hot = hot.astype(jnp.float32)
        if key == "bm25":
            from .phrase import B as _b, K1 as _k1

            # the SAME k1/b the kernels are called with (and the bound
            # table is built from) — one parameterization everywhere
            # lint: shape-universe-ok (one strip build per generation —
            # the shape is index state, not batch content; TPU501's
            # steady-state contract is about per-request dispatches)
            strip = bm25_strip(hot, self.doc_len,
                               jnp.int32(self.meta.num_docs),
                               k1=_k1, b=_b)
        else:
            # lint: shape-universe-ok (one strip build per generation)
            strip = lntf_strip(hot)
        with self._lazy_lock:
            return cache.setdefault(key, strip)

    def _blockmax_bound_table(self, scoring: str):
        """The per-mode f32 [H, nblk] per-block score upper bound the
        block-max kernels consume: weight_fn of the stored block max tf
        — (1 + ln tf) for TF-IDF; for BM25 the saturation curve at the
        block's MINIMUM doc-length norm (saturation increases in tf and
        decreases in dl_norm, so the pair dominates every posting in
        the block). Device-resident, built once per mode (double-checked
        publish, computed outside the lock — lint TPU202)."""
        tables = self.__dict__.setdefault("_blockmax_tables", {})
        if scoring in tables:
            return tables[scoring]
        max_tf = np.asarray(self._hot_blk_max, np.float32)
        if scoring == "tfidf":
            bound = np.where(max_tf > 0,
                             1.0 + np.log(np.maximum(max_tf, 1.0)), 0.0)
        else:
            from .phrase import B as _b, K1 as _k1

            width = self._blockmax_width
            d = self.meta.num_docs
            nblk = max_tf.shape[1]
            dlf = np.asarray(self.doc_len).astype(np.float32)
            avg = float(dlf.sum()) / max(d, 1)
            dl_norm = 1.0 - _b + _b * dlf / max(avg, 1e-9)
            # dead slot 0 and the pad tail must not drag the block min
            # down (a lower dl_norm only loosens the bound, but slot 0's
            # zero length would loosen block 0 for nothing)
            padded = np.full(nblk * width, np.inf, np.float32)
            padded[1: d + 1] = dl_norm[1: d + 1]
            dl_min = padded.reshape(nblk, width).min(axis=1)
            dl_min = np.where(np.isfinite(dl_min), dl_min, 0.0)
            sat = max_tf * (_k1 + 1.0) / np.maximum(
                max_tf + _k1 * dl_min[None, :], 1e-9)
            bound = np.where(max_tf > 0, sat, 0.0)
        table = stream_to_device(np.ascontiguousarray(bound, np.float32),
                                 label="hot_blk_bound")
        with self._lazy_lock:
            return tables.setdefault(scoring, table)

    def _note_blockmax_stats(self, stats) -> None:
        """Queue one dispatch's (considered, masked, fallback) device
        triple; drained AFTER the batch's results are fetched so the
        stats read never serializes the dispatch overlap (its host copy
        is issued now, to arrive with the results)."""
        stats.copy_to_host_async()
        with self._lazy_lock:
            self.__dict__.setdefault("_blockmax_pending", []).append(stats)

    def _drain_blockmax_stats(self) -> None:
        from ..obs import get_registry

        with self._lazy_lock:
            pending = self.__dict__.get("_blockmax_pending") or []
            self.__dict__["_blockmax_pending"] = []
        if not pending:
            return
        reg = get_registry()
        for stats in pending:
            considered, masked, fallback = (int(x) for x in
                                            np.asarray(stats))
            reg.incr("blockmax.blocks_considered", considered)
            reg.incr("blockmax.blocks_masked", masked)
            if fallback:
                reg.incr("blockmax.fallback_dispatches")
            else:
                reg.incr("blockmax.saved_dispatches")

    def _topk_device(self, q_terms: np.ndarray, k: int, scoring: str,
                     skip_hot: bool = False, hot_only: bool = False,
                     donate: bool = False, fit_chunks: bool = False):
        """Dispatch one query block; returns device arrays without
        waiting. `skip_hot` statically omits the tiered hot-strip stage
        (exact only for blocks the scheduler certified hot-free);
        `hot_only` statically omits the cold tiers instead (the overload
        ladder's cheapest level — partial scores, results must be
        tagged). On the dense layout hot_only is a no-op: there is no
        cheaper stage to keep, so it serves the full matrix.
        `fit_chunks` sizes the cold chunk stream to this block's own
        postings (a compile per capacity bucket); without it the shape
        is a function of the block's shape alone (`_chunk_kwargs`).

        The "kernel" span times the jit call + injected hangs for THIS
        block (the dispatch is async on real hardware — completion cost
        lands in the parent dispatch span's fetch); in a profiler
        capture it carries the layout and scoring as metadata."""
        with obs_trace("kernel", layout=self.layout, scoring=scoring,
                       rows=int(len(q_terms))):
            return self._topk_device_raw(q_terms, k, scoring,
                                         skip_hot=skip_hot,
                                         hot_only=hot_only,
                                         donate=donate,
                                         fit_chunks=fit_chunks)

    def _chunk_kwargs(self, q_terms: np.ndarray, fit: bool) -> dict:
        """The cold chunk stream's kernel arguments for one tiered
        dispatch of host query ids `q_terms`, {} without a chunk table.

        `fit=True` passes the static capacity this block needs (its
        streamed terms' ceil(df / C) summed, ops.scoring.chunk_bucket).
        `fit=False` passes the worst case, every slot on the widest
        streamed tier, so callers whose compiled set must stay closed
        (the coalescer's rungs and precompile) mint no content-dependent
        shape. Each dispatch that streams counts in cold.chunk_postings
        (real postings streamed) and cold.chunk_slots (capacity x C
        lanes dispatched)."""
        if self.cold_chunks is None:
            return {}
        from ..ops.scoring import chunk_bucket

        q = np.asarray(q_terms)
        count = self._chunk_count_host
        valid = (q >= 0) & (q < len(count))
        safe = np.where(valid, q, 0)
        n = np.where(valid, count[safe], 0)
        need = int(n.sum())
        worst = q.size * self._chunk_widest
        cap = min(chunk_bucket(need), worst) if fit else worst
        if need:   # a block that streams nothing runs no stream
            from ..obs import get_registry

            reg = get_registry()
            reg.incr("cold.chunk_postings",
                     int(self._df_host()[safe][n > 0].sum()))
            reg.incr("cold.chunk_slots",
                     cap * int(self.cold_chunks.docs.shape[1]))
        return {"chunks": self.cold_chunks, "n_chunks": cap}

    def _topk_device_raw(self, q_terms: np.ndarray, k: int, scoring: str,
                         skip_hot: bool = False, hot_only: bool = False,
                         donate: bool = False, fit_chunks: bool = False):
        faults.maybe_hang("score.hang")
        if faults.should_fire("score.device_loss") is not None:
            raise faults.DeviceLoss("injected device loss")
        donate = donate and _donation_enabled() and self.layout != "sharded"
        cold = ({} if hot_only or self.layout != "sparse"
                else self._chunk_kwargs(q_terms, fit_chunks))
        q = jnp.asarray(q_terms)
        n = jnp.int32(self.meta.num_docs)
        if self.layout == "sharded":
            from ..parallel import sharded_tiered_topk

            # num_docs rides as the python int: the sharded path wraps it
            # into a (possibly multi-process) global scalar itself, and a
            # jnp scalar would cost a host sync per block there
            s, d = sharded_tiered_topk(
                q, self._sharded, self._df_mesh, self.meta.num_docs,
                mesh=self._mesh, k=k,
                scoring=scoring, compat_int_idf=self.compat_int_idf,
                hot_only=hot_only)
        elif scoring == "bm25":
            if self.layout == "dense":
                from ..ops.scoring import bm25_topk_dense_dq

                fn = bm25_topk_dense_dq if donate else bm25_topk_dense
                s, d = fn(q, self._ensure_tf_matrix(),
                          self.df, self.doc_len, n, k=k)
            elif (plan := None if (skip_hot or hot_only)
                    else self._blockmax_plan(k, scoring)) is not None:
                # block-max pruning (ISSUE 13): the full-group deep-k
                # production path — bit-identical to the exact kernel,
                # the hot stage paid only for surviving doc blocks
                from ..ops.scoring import (
                    bm25_topk_blockmax,
                    bm25_topk_blockmax_dq,
                )

                from .phrase import B as _b, K1 as _k1

                bound, width, cand = plan
                ws = self._hot_wstrip(scoring)
                fn = bm25_topk_blockmax_dq if donate else bm25_topk_blockmax
                # k1/b ride explicitly from THE shared constants: the
                # bound table (_blockmax_bound_table) is built from
                # phrase.K1/B, and a kernel saturating with different
                # constants would silently break bound domination
                s, d, stats = fn(
                    q, self.hot_rank,
                    ws if ws is not None else self.hot_tfs, self.tier_of,
                    self.row_of, self.tier_docs, self.tier_tfs, self.df,
                    self.doc_len, n, bound, num_docs=self.meta.num_docs,
                    width=width, cand_blocks=cand, k=k, k1=_k1, b=_b,
                    hot_preweighted=ws is not None, **cold)
                self._note_blockmax_stats(stats)
            else:
                from ..ops.scoring import bm25_topk_tiered, bm25_topk_tiered_dq
                from .phrase import B as _b, K1 as _k1

                # the pre-weighted strip serves every variant that runs
                # the hot stage; the cold-only skip kernel keeps the raw
                # strip operand (the stage is statically absent)
                ws = (None if skip_hot
                      else self._hot_wstrip(scoring))
                fn = bm25_topk_tiered_dq if donate else bm25_topk_tiered
                s, d = fn(
                    q, self.hot_rank,
                    ws if ws is not None else self.hot_tfs, self.tier_of,
                    self.row_of, self.tier_docs, self.tier_tfs, self.df,
                    self.doc_len, n, num_docs=self.meta.num_docs, k=k,
                    k1=_k1, b=_b, skip_hot=skip_hot, hot_only=hot_only,
                    hot_preweighted=ws is not None, **cold)
        elif self.layout == "dense":
            from ..ops.scoring import tfidf_topk_dense_dq

            fn = tfidf_topk_dense_dq if donate else tfidf_topk_dense
            s, d = fn(q, self.doc_matrix, self.df, n, k=k,
                      compat_int_idf=self.compat_int_idf)
        elif (plan := None if (skip_hot or hot_only)
                else self._blockmax_plan(k, scoring)) is not None:
            from ..ops.scoring import (
                tfidf_topk_blockmax,
                tfidf_topk_blockmax_dq,
            )

            bound, width, cand = plan
            ws = self._hot_wstrip(scoring)
            fn = tfidf_topk_blockmax_dq if donate else tfidf_topk_blockmax
            s, d, stats = fn(
                q, self.hot_rank,
                ws if ws is not None else self.hot_tfs, self.tier_of,
                self.row_of, self.tier_docs, self.tier_tfs, self.df, n,
                bound, num_docs=self.meta.num_docs, width=width,
                cand_blocks=cand, k=k,
                compat_int_idf=self.compat_int_idf,
                hot_preweighted=ws is not None, **cold)
            self._note_blockmax_stats(stats)
        else:
            from ..ops.scoring import tfidf_topk_tiered, tfidf_topk_tiered_dq

            ws = None if skip_hot else self._hot_wstrip(scoring)
            fn = tfidf_topk_tiered_dq if donate else tfidf_topk_tiered
            s, d = fn(
                q, self.hot_rank,
                ws if ws is not None else self.hot_tfs, self.tier_of,
                self.row_of, self.tier_docs, self.tier_tfs, self.df, n,
                num_docs=self.meta.num_docs, k=k,
                compat_int_idf=self.compat_int_idf, skip_hot=skip_hot,
                hot_only=hot_only, hot_preweighted=ws is not None, **cold)
        return s, d

    def _ensure_tf_matrix(self):
        """Lazy dense [V, D+1] raw-tf matrix (BM25 on the dense layout;
        the explain debug kernels share it). Built OUTSIDE the lazy
        lock: dense_tf_matrix is a device dispatch, and a lock held
        across it stalls every concurrent lazy-state reader behind the
        upload (lint TPU202). Two racing threads may both build; the
        loser's copy is garbage-collected — bounded waste, never
        corruption (publish is one reference assignment under the
        lock)."""
        if self._tf_matrix is None:
            pt, pd, ptf = self._pairs
            tf_matrix = dense_tf_matrix(
                jnp.asarray(pt), jnp.asarray(pd), jnp.asarray(ptf),
                vocab_size=self.meta.vocab_size,
                num_docs=self.meta.num_docs)
            with self._lazy_lock:
                if self._tf_matrix is None:
                    self._tf_matrix = tf_matrix
        return self._tf_matrix

    def _ensure_pairs(self):
        """The 3-slot host CSR column tuple (pair_term-or-None, pair_doc,
        pair_tf) — assembled lazily on the serving-cache fast path, where
        nothing on the query path needs it (norms ride in the cache; only
        the dense layouts and exhaustive oracles do). Double-checked
        under the lazy lock: two concurrent degraded batches must not
        both pay (or interleave) the shard read."""
        if self._pairs_cols is None:
            with self._lazy_lock:
                if self._pairs_cols is None:
                    if self._pairs_loader is None:
                        raise RuntimeError(
                            "postings columns unavailable: Scorer was "
                            "built from serving arrays only")
                    cols = self._pairs_loader()
                    if len(cols) == 2:  # (pair_doc, pair_tf): term lazy
                        cols = (None,) + tuple(cols)
                    self._pairs_cols = cols
        return self._pairs_cols

    def _pair_term(self) -> np.ndarray:
        """The materialized pair_term column, built on demand from df
        (np.repeat over the CSR runs — ~1 GB at 250M pairs, which is why
        the load path leaves it lazy; ISSUE 5 satellite). Cached back
        into the column tuple so oracles pay it once."""
        cols = self._ensure_pairs()
        if cols[0] is None:
            with self._lazy_lock:
                cols = self._pairs_cols
                if cols[0] is None:
                    from ..ops.postings import pair_term_from_df

                    cols = ((pair_term_from_df(self._df_host()),)
                            + tuple(cols[1:]))
                    self._pairs_cols = cols
        return self._pairs_cols[0]

    @property
    def _pairs_doc_tf(self):
        """(pair_doc, pair_tf) WITHOUT materializing pair_term — the host
        fallback scorer walks postings by indptr slices and never reads
        the term column."""
        cols = self._ensure_pairs()
        return cols[1], cols[2]

    @property
    def _pairs(self):
        """Host CSR columns (pair_term, pair_doc, pair_tf); materializes
        pair_term — callers that only need doc/tf use _pairs_doc_tf."""
        pt = self._pair_term()
        cols = self._pairs_cols
        return pt, cols[1], cols[2]

    def _doc_norms_host(self) -> np.ndarray:
        """Host rerank norms; from the serving cache when present, else
        computed from the (lazily assembled) CSR columns. The phrase
        pipeline stops here — its host cosine never needs the device
        copy, which at 10M docs would be a ~40 MB upload for nothing."""
        if self._norms_np is None:
            # compute_doc_norms dispatches device work per chunk: run it
            # outside the lazy lock, publish the result under it (lint
            # TPU202 — see _topk_device_raw's tf_matrix note). _pairs_doc_tf
            # re-enters the RLock internally for the CSR assembly.
            pd, ptf = self._pairs_doc_tf
            # term ids derive from the df row starts per chunk —
            # no materialized pair_term column needed
            norms = compute_doc_norms(None, pd, ptf, self._df_host(),
                                      self.meta.num_docs)
            with self._lazy_lock:
                if self._norms_np is None:
                    self._norms_np = norms
        return self._norms_np

    def _doc_norms(self):
        """Device copy of the rerank norms (the batch rerank kernels)."""
        if getattr(self, "_norms", None) is None:
            # upload outside the lazy lock, publish under it (TPU202)
            norms = jnp.asarray(
                np.ascontiguousarray(self._doc_norms_host()), jnp.float32)
            with self._lazy_lock:
                if getattr(self, "_norms", None) is None:
                    self._norms = norms
        return self._norms

    def _ensure_sharded_norm(self):
        """Lazy sharded [S, dblk+1] rerank doc norms on the mesh (the
        sharded rerank + its explain variant). Host norms feed
        shard_slices directly — _doc_norms() would upload a device copy
        only to fetch it back. The sharded device_put runs OUTSIDE the
        lazy lock; only the reference assignment is under it (TPU202 —
        see _ensure_tf_matrix's note)."""
        if self._sharded_norm is None:
            from ..parallel import shard_slices
            from ..parallel.sharded_tiered import put_doc_sharded

            norms_np = np.ascontiguousarray(self._doc_norms_host())
            sharded_norm = put_doc_sharded(
                shard_slices(norms_np,
                             num_docs=self.meta.num_docs,
                             num_shards=self._mesh.devices.size),
                self._mesh)
            with self._lazy_lock:
                if self._sharded_norm is None:
                    self._sharded_norm = sharded_norm
        return self._sharded_norm

    def cosine_scores_at(self, texts: Sequence[str],
                         cand: np.ndarray) -> np.ndarray:
        """[B, C] cosine rerank-stage scores at global docids `cand` —
        the scatter-gather router's stage-2 RPC (serving/router.py).

        Delegates to the shared explain gather (_cosine_scores_at): the
        SAME accumulation the production rerank kernel traces, at the
        same candidate-matrix shape, so per-candidate floats are
        bit-identical to what a single-process rerank would have seen.
        On a doc-range-restricted worker, candidates outside the range
        score exact 0.0 (their postings are masked) — the router takes
        each candidate's value from its owning shard."""
        from .explain import _cosine_scores_at

        texts = list(texts)
        q = self.analyze_queries(texts)
        cand = np.asarray(cand, np.int32)
        if cand.ndim == 1:
            cand = np.broadcast_to(cand[None, :],
                                   (len(texts), cand.shape[0]))
        return _cosine_scores_at(self, q, cand)

    def rerank_topk(
        self, q_terms: np.ndarray, k: int = 10, candidates: int = 1000,
        deadline_s: float | None = None, *, force_host: bool = False,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Two-stage retrieval: BM25 top-`candidates`, then cosine TF-IDF
        (see ops/scoring.py::cosine_rerank_dense for the exact model)
        restricted to those candidates. The reference
        has no second stage; this is the MS MARCO-style composition on the
        same resident index.

        Under a deadline the whole two-stage dispatch is bounded; on
        expiry/device loss the batch degrades to single-stage host BM25
        (the rerank is a quality refinement — dropping it under duress is
        the intended degradation, tagged via the rerank_topk_tagged
        return / SearchResult.degraded)."""
        s, d, _ = self.rerank_topk_tagged(q_terms, k=k,
                                          candidates=candidates,
                                          deadline_s=deadline_s,
                                          force_host=force_host)
        return s, d

    def rerank_topk_tagged(
        self, q_terms: np.ndarray, k: int = 10, candidates: int = 1000,
        deadline_s: float | None = None, *, force_host: bool = False,
    ) -> tuple[np.ndarray, np.ndarray, bool]:
        """rerank_topk() with the per-request degraded flag threaded
        through the return value (see topk_tagged)."""
        q = np.asarray(q_terms, np.int32)
        out = self._dispatch_degradable(
            lambda: self._rerank_primary(q, k, candidates),
            lambda: self._topk_host(q, k, "bm25"),
            deadline_s, "rerank dispatch",
            "answering with host BM25, rerank stage dropped",
            force_host=force_host)
        # the BM25 candidate stage may have dispatched through block-max
        self._drain_blockmax_stats()
        return out

    def _rerank_primary(self, q_terms: np.ndarray, k: int, candidates: int):
        from ..ops import cosine_rerank_dense
        from ..ops.scoring import cosine_rerank_tiered

        n = jnp.int32(self.meta.num_docs)
        if self.layout == "sharded":
            # both stages run inside one SPMD program; the global doc norms
            # ride to the mesh in sharded [S, dblk+1] form (built once)
            from ..parallel import sharded_tiered_rerank

            self._ensure_sharded_norm()

            def dispatch(q):
                # same per-block injection sites as _topk_device: the
                # sharded rerank is the one dispatch that never routes
                # through it, and an uninjectable path is an untestable
                # degradation (the tiered/sharded fallback matrix caught
                # exactly this gap)
                with obs_trace("kernel", layout="sharded",
                               scoring="rerank", rows=int(len(q))):
                    faults.maybe_hang("score.hang")
                    if faults.should_fire(
                            "score.device_loss") is not None:
                        raise faults.DeviceLoss("injected device loss")
                    return sharded_tiered_rerank(
                        jnp.asarray(q), self._sharded, self._df_mesh,
                        self.meta.num_docs, self._sharded_norm,
                        mesh=self._mesh, k=k, candidates=candidates)

            return self._blocked_dispatch(
                self._block_size(), dispatch,
                (np.asarray(q_terms, np.int32), -1))
        norms = self._doc_norms()

        # both stages run inside one block so the candidate matrix never
        # round-trips through the host (at B=10k, C=1000 that would be
        # 2 x 40 MB over the transport whose bandwidth is the critical
        # path). Stage 1 (BM25) always scores the full doc axis, so its
        # budget dominates the block size.
        def dispatch(q):
            qd = jnp.asarray(q)
            _, cand_d = self._topk_device(q, candidates, "bm25",
                                          fit_chunks=True)
            if self.layout == "dense":
                return cosine_rerank_dense(
                    qd, self.doc_matrix, self.df, norms, cand_d, n, k=k)
            # the cosine stage weights the hot strip with the SAME
            # (1 + ln tf) curve as TF-IDF, so it rides the cached strip
            ws = self._hot_wstrip("tfidf")
            return cosine_rerank_tiered(
                qd, self.hot_rank,
                ws if ws is not None else self.hot_tfs, self.tier_of,
                self.row_of, self.tier_docs, self.tier_tfs, self.df,
                norms, n, cand_d, num_docs=self.meta.num_docs, k=k,
                hot_preweighted=ws is not None,
                **self._chunk_kwargs(q, True))

        return self._blocked_dispatch(
            self._block_size(), dispatch,
            (np.asarray(q_terms, np.int32), -1))

    def search_batch(
        self, texts: Sequence[str], k: int = 10, scoring: str = "tfidf",
        return_docids: bool = True, rerank: int | None = None,
        prox: bool = False, phrase_slop: int = 0, *,
        deadline_s: float | None = None, force_host: bool = False,
        hot_only: bool = False, explain_k: int = 0,
        explain_ks: Sequence[int] | None = None,
        pad_to: int | None = None, width_floor: int | None = None,
        rung_ladder: tuple | None = None,
        donate_queries: bool = False,
        slot_meta: Sequence[dict] | None = None,
    ) -> list[SearchResult]:
        """Ranked retrieval for query texts. `rerank=N` switches to the
        two-stage pipeline: BM25 top-N candidates, cosine TF-IDF rerank;
        `prox=True` adds the positions-based proximity boost to the rerank
        (search/phrase.py). Queries containing double-quoted spans run as
        phrase queries (ordered window, `phrase_slop` extra token gaps) —
        both need a format-v2 index built with positions. The quoted
        queries of a call are matched and scored on the device together
        (`_search_phrases_device`, ops/phrase.py), in blocks; with
        `force_host` (the circuit breaker), `rerank` or `prox` they run
        the host pipeline instead (`_search_phrase`, search/phrase.py).

        Serving knobs (tpu_ir.serving.ServingFrontend is the intended
        caller): `deadline_s` bounds this batch's device dispatch,
        `force_host` answers from the host backend with no device
        dispatch (circuit breaker open), `hot_only` scores only the hot
        tier on tiered/sharded layouts. Each SearchResult's `degraded`
        flag is tagged from THIS request's outcome (thread-safe — the
        tagged dispatch return is the only degradation source). Phrase
        queries take `force_host` only: their device blocks have no
        deadline and no hot-only level.

        `explain_k=N` attaches a per-term score decomposition for each
        query's top-N hits (SearchResult.explain; search/explain.py) —
        exact kernel floats, extra debug dispatches, so a forensics
        knob, not a default. Degraded responses and phrase/prox results
        carry explain=None.

        Batch-entry knobs (ISSUE 9 — the coalescing frontend is the
        intended caller; per-request semantics are tagged PER SLOT, not
        batch-wide): `explain_ks` overrides explain_k per query;
        `pad_to=R` pads the analyzed query-row axis to R rows of -1
        before dispatch (the compiled-rung ladder — results for the pad
        rows are never materialized as SearchResults); `rung_ladder`
        additionally makes the MaxScore schedule pad its groups to
        ladder rungs (topk_tagged `uniform` — the closed shape
        universe); `width_floor`
        pins the analyzed width (see analyze_queries); `donate_queries`
        uses the donated-query kernel twins on the plain topk path;
        `slot_meta[i]` merges per-slot fields (service level, queue
        wait, occupancy) into query i's querylog entry. Phrase queries
        cannot ride a padded batch (they have their own dispatch)."""
        if prox and not rerank:
            raise ValueError("the proximity boost is stage 3 of the "
                             "two-stage rerank; pass rerank=N (--rerank) "
                             "together with prox (--prox)")
        texts = list(texts)
        plain = [t for t in texts if '"' not in t]
        if len(plain) != len(texts) and (
                pad_to is not None or explain_ks is not None
                or slot_meta is not None):
            # the per-slot lists index the PLAIN batch — a phrase query
            # in the middle would silently shift every later slot's
            # explain depth and querylog attribution
            raise ValueError("a coalesced batch (pad_to / explain_ks / "
                             "slot_meta) cannot contain phrase queries "
                             "— the coalescing frontend routes them "
                             "solo")
        plain_out = []
        if plain:
            with obs_trace("search", queries=len(plain)):
                plain_out = self._search_batch_plain(
                    plain, k=k, scoring=scoring,
                    return_docids=return_docids, rerank=rerank, prox=prox,
                    deadline_s=deadline_s, force_host=force_host,
                    hot_only=hot_only, explain_k=explain_k,
                    explain_ks=explain_ks, pad_to=pad_to,
                    width_floor=width_floor, rung_ladder=rung_ladder,
                    donate_queries=donate_queries, slot_meta=slot_meta)
        quoted = [t for t in texts if '"' in t]
        if quoted and not (force_host or rerank or prox):
            with obs_trace("search", queries=len(quoted), phrase=True):
                quoted_out = iter(self._search_phrases_device(
                    quoted, k=k, scoring=scoring, slop=phrase_slop,
                    return_docids=return_docids))
        else:
            quoted_out = (self._search_phrase(
                t, k=k, scoring=scoring, slop=phrase_slop,
                return_docids=return_docids, rerank=rerank, prox=prox)
                for t in quoted)
        plain_iter = iter(plain_out)
        return [next(quoted_out) if '"' in t else next(plain_iter)
                for t in texts]

    def _search_batch_plain(
        self, texts: Sequence[str], *, k: int, scoring: str,
        return_docids: bool, rerank: int | None, prox: bool,
        deadline_s: float | None = None, force_host: bool = False,
        hot_only: bool = False, explain_k: int = 0,
        explain_ks: Sequence[int] | None = None,
        pad_to: int | None = None, width_floor: int | None = None,
        rung_ladder: tuple | None = None,
        donate_queries: bool = False,
        slot_meta: Sequence[dict] | None = None,
    ) -> list[SearchResult]:
        t0 = time.perf_counter()
        with obs_trace("search.analyze", queries=len(texts)):
            q = self.analyze_queries(texts, width_floor=width_floor)
            if pad_to is not None and pad_to > len(q):
                # the coalescing rung ladder: pad the ROW axis with -1
                # rows (score exact 0.0, top-k all-empty) so every
                # dispatch reuses one of the precompiled batch shapes;
                # the pad rows' outputs are sliced off below — no
                # SearchResult, no querylog entry, no caller sees them
                q = np.vstack([q, np.full((pad_to - len(q), q.shape[1]),
                                          -1, np.int32)])
        t_analyzed = time.perf_counter()
        if rerank:
            from .phrase import PROX_DEPTH

            kk = max(k, min(PROX_DEPTH, rerank)) if prox else k
            scores, docnos, degraded = self.rerank_topk_tagged(
                q, k=kk, candidates=rerank, deadline_s=deadline_s,
                force_host=force_host)
            if prox:
                scores, docnos = self._apply_proximity(
                    texts, np.asarray(scores[: len(texts)]),
                    np.asarray(docnos[: len(texts)]), k)
        else:
            scores, docnos, degraded = self.topk_tagged(
                q, k=k, scoring=scoring, deadline_s=deadline_s,
                hot_only=hot_only, force_host=force_host,
                donate=donate_queries,
                uniform=(rung_ladder if pad_to is not None else None))
        t_dispatched = time.perf_counter()
        out = []
        for qi in range(len(texts)):
            res = SearchResult()
            # surface the fallback to callers: a degraded batch's results
            # are real rankings from the host backend, but SLAs/metrics
            # must be able to tell them apart from the primary pipeline.
            # Tagged from the per-request flag the tagged dispatch
            # returned, which no other thread's batch can overwrite.
            res.degraded = degraded
            for s, dn in zip(scores[qi], docnos[qi]):
                if dn <= 0:
                    continue
                key = self.mapping.get_docid(int(dn)) if return_docids else int(dn)
                res.append((key, float(s)))
            out.append(res)
        # the request's serving latency, captured BEFORE the optional
        # explain block: the forensics knob's debug dispatches must not
        # inflate total_ms and trip the slow-query trap on requests
        # whose actual serving was fast
        total_s = time.perf_counter() - t0
        if (explain_k or explain_ks) and not degraded and not prox:
            # prox rescoring happens on the host AFTER the kernels — its
            # final scores are not a kernel decomposition target
            from .explain import explain_hits

            for qi, text in enumerate(texts):
                # per-slot forensics depth inside a shared batch (tag,
                # don't drop): only the slots that ASKED pay the debug
                # dispatches
                ek = explain_ks[qi] if explain_ks is not None else explain_k
                top = [int(dn) for dn in docnos[qi][:ek] if dn > 0]
                if top:
                    out[qi].explain = explain_hits(
                        self, text, top, scoring=scoring, rerank=rerank,
                        hot_only=hot_only)
        self._querylog_record(
            texts, q, docnos, out, k=k, scoring=scoring, rerank=rerank,
            hot_only=hot_only, force_host=force_host, degraded=degraded,
            prox=prox, analyze_s=t_analyzed - t0,
            dispatch_s=t_dispatched - t_analyzed, total_s=total_s,
            slot_meta=slot_meta)
        return out

    def _querylog_record(self, texts, q, docnos, results, *, k, scoring,
                         rerank, hot_only, force_host, degraded, prox,
                         analyze_s, dispatch_s, total_s,
                         slot_meta=None) -> None:
        """One query-log entry per query of this batch (obs/querylog.py):
        terms (hash when redacted), level, the batch's stage-latency
        split, batch id (the per-request attribution key inside a shared
        batch), top-k docids + scores, and the MaxScore scheduling
        decision. The slow-query trap's explain capture is deferred
        behind the flight recorder's rate gate via a callable.

        `slot_meta[qi]` (the coalescing frontend) merges per-slot fields
        into entry qi — each slot's TRUE service level, queue_wait_ms
        and batch_occupancy — overriding the batch-wide defaults (the
        leader thread's request_context is not the followers')."""
        from ..obs import querylog

        if not querylog.enabled() or not texts:
            return
        batch_id = querylog.next_batch_id()
        mode = has_hot = None
        if self.layout == "sparse" and self.prune and not hot_only:
            # re-derived once per batch (one [B, L] host gather) — the
            # dispatch path's identical decision is not threaded back
            # out through the tagged-return plumbing just to save it
            has_hot, _, mode = self._skip_plan(q)
        level = "hot_only" if hot_only else "full"
        stage = {"analyze_ms": round(analyze_s * 1e3, 3),
                 "dispatch_ms": round(dispatch_s * 1e3, 3),
                 "total_ms": round(total_s * 1e3, 3)}
        for qi, text in enumerate(texts):
            ids = [int(t) for t in q[qi] if t >= 0]
            entry = {
                "query_hash": querylog.query_hash(ids),
                "n_terms": len(ids),
                "level": level,
                "degraded": bool(degraded),
                "forced_host": bool(force_host),
                "scoring": scoring,
                "rerank": rerank,
                "prox": bool(prox),
                "k": k,
                "batch_id": batch_id,
                "batch_size": len(texts),
                # batch-level attribution: every entry of the batch
                # carries the batch's split, joined by batch_id — the
                # shared-padded-batch lens ROADMAP 3 needs
                **stage,
                "top": [[key, round(float(s), 6)]
                        for key, s in results[qi][:10]],
            }
            if slot_meta is not None:
                entry.update(slot_meta[qi])
            if not querylog.redacted():
                entry["terms"] = [self.vocab.term(t) for t in ids]
            if mode is not None:
                entry["prune"] = {"dispatch_mode": mode,
                                  "has_hot": bool(has_hot[qi])}
            explain_fn = None
            top_dn = [int(dn) for dn in docnos[qi][:1] if dn > 0]
            if qi == 0 and top_dn and not degraded and not prox:
                # the trap's force-capture target: the batch's first
                # query's top hit (batch latency is attributed batch-
                # wide, so any member stands for the offender)
                def explain_fn(text=text, dn=top_dn):
                    from .explain import explain_hits

                    return explain_hits(self, text, dn, scoring=scoring,
                                        rerank=rerank, hot_only=hot_only)
            querylog.record(entry, explain_fn=explain_fn)

    def explain(self, text: str, key, *, is_docid: bool = True,
                scoring: str = "tfidf", rerank: int | None = None,
                hot_only: bool = False) -> dict:
        """Lucene-explain for one (query, doc): the exact per-term score
        decomposition of what the production kernels computed —
        tf/df/idf/length-norm per term, tier placement, the prune/skip
        dispatch decision, marginal per-slot contributions whose float64
        sum reproduces the kernel score bit-exactly, and the rerank
        stage split when `rerank` is set (search/explain.py)."""
        from .explain import explain_hits

        docno = self.mapping.get_docno(key) if is_docid else int(key)
        return explain_hits(self, text, [docno], scoring=scoring,
                            rerank=rerank, hot_only=hot_only)[0]

    # -- positions-backed retrieval (format v2) ---------------------------

    def _phrase_index(self):
        if self._phrase is None:
            if self._index_dir is None:
                raise ValueError("phrase/proximity queries need an index "
                                 "directory (Scorer built from arrays)")
            from .phrase import PhraseIndex

            self._phrase = PhraseIndex(self._index_dir, meta=self.meta)
        return self._phrase

    # the device phrase path (ops/phrase.py): the position table, built
    # at load for an index with positions, and the anchor lanes one
    # dispatch block may stream (blocks split where a call needs more)
    positions = None
    PHRASE_LANE_BUDGET = 1 << 23

    def _position_table(self):
        """The device position table of the phrase path, or None for an
        index without positions (nothing is built or uploaded then).
        Scorer.load builds it as its `load.positions` stage; a Scorer
        constructed over an index dir builds it on first use."""
        if (self.positions is None and self.meta.has_positions
                and self._index_dir is not None):
            from ..ops.phrase import PositionTable
            from .layout import position_table

            with obs_trace("load.positions"):
                host = position_table(self._index_dir, self.meta,
                                      np.asarray(self.doc_len))
                table = PositionTable(*(
                    stream_to_device(a, label=f"positions_{n}")
                    for a, n in zip(host, PositionTable._fields)))
            with self._lazy_lock:
                if self.positions is None:
                    self._pos_cf = host[3]
                    self.positions = table
        return self.positions

    def _query_term_sequence(self, text: str) -> list[str]:
        """The query's analyzed index-term sequence (k-grams composed) —
        the coordinate system position runs are stored in."""
        return kgram_terms(self._analyzer.analyze(text), self.meta.k)

    def _search_phrase(self, text: str, *, k: int, scoring: str, slop: int,
                       return_docids: bool, rerank: int | None = None,
                       prox: bool = False) -> SearchResult:
        """One phrase query on the host pipeline (search/phrase.py), the
        path of `force_host`, `rerank` and `prox`: every quoted span must
        match as an ordered window; matching docs are ranked by the
        standard scoring model over ALL query terms, as on the device
        path (`_search_phrases_device`). `rerank`/`prox`
        compose exactly as on the plain path: BM25 selects the top-N
        matched docs, cosine TF-IDF rescores them, proximity boosts the
        top of that — so a batch mixing quoted and plain queries runs ONE
        pipeline, not two."""
        from .phrase import (
            PROX_ALPHA,
            PROX_DEPTH,
            cosine_score_host,
            score_docs_host,
            split_phrases,
        )

        # extract phrases BEFORE touching the position artifacts: a stray
        # or empty quote ('19" rack') is a plain query on any index, v1
        # included — only a real phrase needs format v2
        _, phrases = split_phrases(text)
        analyzed = [(p, self._query_term_sequence(p)) for p in phrases]
        analyzed = [(p, toks) for p, toks in analyzed if toks]
        if not analyzed:
            return self._search_batch_plain(
                [text.replace('"', ' ')], k=k, scoring=scoring,
                return_docids=return_docids, rerank=rerank, prox=prox)[0]
        t0 = time.perf_counter()
        pidx = self._phrase_index()
        matched: set[int] | None = None
        for _, toks in analyzed:
            docs = set(pidx.match_window(toks, slop=slop))
            matched = docs if matched is None else matched & docs
            if not matched:
                return self._querylog_phrase(text, SearchResult(), t0,
                                             k=k, scoring=scoring,
                                             rerank=rerank)
        all_terms = self._query_term_sequence(text.replace('"', ' '))
        if rerank:
            # stage 1: BM25 over the matched docs, keep top-`rerank`
            docnos, scores = score_docs_host(
                all_terms, sorted(matched), dictionary=pidx._dict,
                num_docs=self.meta.num_docs,
                doc_len=np.asarray(self.doc_len), scoring="bm25",
                term_lookup=pidx._term)
            keep = np.lexsort((docnos, -scores))[:rerank]
            # stage 2: cosine TF-IDF rescoring of the candidates
            docnos, scores = cosine_score_host(
                all_terms, docnos[keep], dictionary=pidx._dict,
                num_docs=self.meta.num_docs,
                doc_norms=self._doc_norms_host(),
                term_lookup=pidx._term)
            if prox and len(all_terms) > 1:
                # stage 3: positional proximity boost, bounded like the
                # plain path (top PROX_DEPTH candidates by stage-2 score)
                scores = scores.astype(np.float64)
                for i in np.lexsort((docnos, -scores))[:PROX_DEPTH]:
                    if scores[i] > 0:
                        scores[i] *= 1.0 + PROX_ALPHA * pidx.proximity_bonus(
                            all_terms, int(docnos[i]))
        else:
            docnos, scores = score_docs_host(
                all_terms, sorted(matched), dictionary=pidx._dict,
                num_docs=self.meta.num_docs,
                doc_len=np.asarray(self.doc_len),
                scoring=scoring, compat_int_idf=self.compat_int_idf,
                term_lookup=pidx._term)
        order = np.lexsort((docnos, -scores))[:k]
        res = SearchResult()
        for i in order:
            # unlike the plain path, zero-score docs are KEPT: every doc
            # here satisfies the user's explicit phrase constraint, and a
            # query whose terms all have df == N (idf 0 — "to be or not
            # to be") must still return its exact matches. The lexsort
            # already ranks them after positive scores, docno ascending
            # (found by the differential fuzz, seed 291).
            dn = int(docnos[i])
            key = self.mapping.get_docid(dn) if return_docids else dn
            res.append((key, float(scores[i])))
        return self._querylog_phrase(text, res, t0, k=k, scoring=scoring,
                                     rerank=rerank)

    def _search_phrases_device(self, texts: Sequence[str], *, k: int,
                               scoring: str, slop: int,
                               return_docids: bool) -> list[SearchResult]:
        """Quoted queries answered on the device, with `_search_phrase`'s
        semantics: every quoted span must match as an ordered window (at
        `slop` extra gaps), and the matched docs are ranked by `scoring`
        over all query terms, zero scores kept, ties by docno. Each span
        is a row of ops/phrase.py `phrase_match` (its rarest term's
        positions streamed as anchors); queries are cut into blocks of
        at most PHRASE_LANE_BUDGET anchor lanes (`_phrase_block`), all
        in one `dispatch` span: per block one `phrase.match` span (the
        match, then a `dispatch.device` sync for its candidate and row
        counts, which size the scoring stage) and one `kernel` span
        (`phrase_topk` over the candidates), then one `dispatch.device`
        for the answers. A span with a term the
        index lacks matches nothing and is not dispatched. Counters:
        phrase.queries (queries dispatched), phrase.positions (anchor
        positions streamed), phrase.slots (anchor lanes dispatched) and
        phrase.candidates (matched (span, doc) pairs)."""
        from ..ops.phrase import PHRASE_CHUNK
        from .phrase import split_phrases

        t0 = time.perf_counter()
        out: list = [None] * len(texts)
        todo = []   # (index, [span term ids], [query term ids])
        with obs_trace("search.analyze", queries=len(texts)):
            for i, text in enumerate(texts):
                spans = [self._query_term_sequence(p)
                         for p in split_phrases(text)[1]]
                spans = [toks for toks in spans if toks]
                if not spans:
                    # a stray or empty quote: a plain query on any index
                    out[i] = self._search_batch_plain(
                        [text.replace('"', ' ')], k=k, scoring=scoring,
                        return_docids=return_docids, rerank=None,
                        prox=False)[0]
                    continue
                if self._position_table() is None:
                    self._phrase_index()   # raises: no positions
                ids = [[self.vocab.id_or(t) for t in toks]
                       for toks in spans]
                if any(t < 0 or self._pos_cf[t] == 0
                       for span in ids for t in span):
                    out[i] = self._querylog_phrase(
                        text, SearchResult(), t0, k=k, scoring=scoring,
                        rerank=None)
                    continue
                todo.append((i, ids, [
                    self.vocab.id_or(t) for t in
                    self._query_term_sequence(text.replace('"', ' '))]))
        # blocks of whole queries within the anchor-lane budget
        blocks, cur, lanes = [], [], 0
        for q in todo:
            need = sum(self._anchor_chunks(s) for s in q[1]) * PHRASE_CHUNK
            if cur and lanes + need > self.PHRASE_LANE_BUDGET:
                blocks.append(cur)
                cur, lanes = [], 0
            cur.append(q)
            lanes += need
        if cur:
            blocks.append(cur)
        with obs_trace("dispatch", label="phrase dispatch",
                       blocks=len(blocks)):
            pending = [self._phrase_block(b, k=k, scoring=scoring,
                                          slop=slop) for b in blocks]
            with obs_trace("dispatch.device", blocks=len(pending)):
                fetched = [(np.asarray(s), np.asarray(d))
                           for s, d in pending]
        for block, (scores, docnos) in zip(blocks, fetched):
            for qi, (i, _, _) in enumerate(block):
                res = SearchResult()
                for sc, dn in zip(scores[qi], docnos[qi]):
                    if dn > 0:  # zero-score matches are kept
                        key = (self.mapping.get_docid(int(dn))
                               if return_docids else int(dn))
                        res.append((key, float(sc)))
                out[i] = self._querylog_phrase(texts[i], res, t0, k=k,
                                               scoring=scoring,
                                               rerank=None)
        return out

    def _anchor_chunks(self, span) -> int:
        """Chunks a span's row streams: its rarest term's positions."""
        from ..ops.phrase import PHRASE_CHUNK

        return -(-int(self._pos_cf[span].min()) // PHRASE_CHUNK)

    def _phrase_block(self, block, *, k: int, scoring: str, slop: int):
        """Dispatch one block of `_search_phrases_device`'s queries
        [(index, [span term ids], [query term ids])]: the match, one
        sync for its per-span candidate counts and the token-stream rows
        of the matched docs (they size the scoring stage), then the
        scoring and top-k. Returns the (scores, docnos) device
        arrays without waiting for them."""
        import jax

        from ..obs import get_registry
        from ..ops.phrase import PHRASE_CHUNK, phrase_match, phrase_topk
        from ..ops.scoring import chunk_bucket
        from .phrase import B as _b, K1 as _k1

        rows = [(qi, s) for qi, (_, spans, _) in enumerate(block)
                for s in spans]
        n_q = _pow2(len(block))
        row_terms = np.full((_pow2(len(rows)), max(len(s) for _, s in rows)),
                            -1, np.int32)
        row_len = np.zeros(len(row_terms), np.int32)
        row_anchor = np.zeros(len(row_terms), np.int32)
        row_query = np.zeros(len(row_terms), np.int32)
        query_rows = np.full((n_q, max(len(q[1]) for q in block)), -1,
                             np.int32)
        query_terms = np.full((n_q, _pow2(max(len(q[2]) for q in block))),
                              -1, np.int32)
        anchors = 0
        for r, (qi, span) in enumerate(rows):
            cfs = self._pos_cf[span]
            row_terms[r, : len(span)] = span
            row_len[r] = len(span)
            row_anchor[r] = int(np.argmin(cfs))
            row_query[r] = qi
            query_rows[qi, list(query_rows[qi]).index(-1)] = r
            anchors += int(cfs.min())
        for qi, (_, _, terms) in enumerate(block):
            query_terms[qi, : len(terms)] = terms
        n_chunks = chunk_bucket(sum(self._anchor_chunks(s)
                                    for _, s in rows))
        # rows, queries and widths are pow2-bucketed, n_chunks and cap
        # chunk-bucketed; phrase queries never ride the coalescer's
        # precompiled ladder (they route solo)
        with obs_trace("phrase.match", rows=len(rows), n_chunks=n_chunks):
            # lint: shape-universe-ok (bucketed shapes, see above)
            cand_row, cand_doc, row_count, n_docs, slices = phrase_match(
                self.positions, row_terms, row_len, row_anchor,
                n_chunks=n_chunks, slop=slop)
            with obs_trace("dispatch.device", blocks=1):
                counts, n_docs, slices = jax.device_get(
                    (row_count, n_docs, slices))
            cap = min(chunk_bucket(int(counts.sum())),
                      n_chunks * PHRASE_CHUNK)
        with obs_trace("kernel", layout="phrase", scoring=scoring,
                       rows=len(block)):
            # lint: shape-universe-ok (bucketed shapes, see above)
            out = phrase_topk(
                self.positions, cand_row, cand_doc, row_count, row_query,
                query_rows, query_terms, self.df, self.doc_len,
                jnp.int32(self.meta.num_docs), cap=cap,
                n_slices=chunk_bucket(int(slices)),
                width=_pow2(counts[query_rows[:len(block), 0]].max(), 16),
                k=k, scoring=scoring, k1=_k1, b=_b,
                compat_int_idf=self.compat_int_idf)
        reg = get_registry()
        reg.incr("phrase.queries", len(block))
        reg.incr("phrase.positions", anchors)
        reg.incr("phrase.slots", n_chunks * PHRASE_CHUNK)
        reg.incr("phrase.candidates", int(n_docs))
        return out

    def _querylog_phrase(self, text, res, t0, *, k, scoring, rerank):
        """Query-log entry for one host-scored phrase query (slim form:
        no device stage split, no explain trap target — the phrase
        pipeline never touches the kernels the explain decomposes)."""
        from ..obs import querylog

        if querylog.enabled():
            total_ms = round((time.perf_counter() - t0) * 1e3, 3)
            terms = self._query_term_sequence(text.replace('"', ' '))
            ids = [self.vocab.id_or(t) for t in terms]
            entry = {
                "query_hash": querylog.query_hash([i for i in ids
                                                   if i >= 0]),
                "n_terms": len(terms),
                "level": "full",
                "degraded": False,
                "phrase": True,
                "scoring": scoring,
                "rerank": rerank,
                "k": k,
                "batch_id": querylog.next_batch_id(),
                "batch_size": 1,
                "total_ms": total_ms,
                "top": [[key, round(float(s), 6)] for key, s in res[:10]],
            }
            if not querylog.redacted():
                entry["terms"] = terms
            querylog.record(entry)
        return res

    def _apply_proximity(self, texts, scores, docnos, k: int):
        """Stage 3 of the rerank: boost each candidate by the query's
        positional proximity in it — score * (1 + PROX_ALPHA * bonus),
        bonus = sum over adjacent query-term pairs of 1/(1+min_gap)
        (search/phrase.py). Host work bounded by PROX_DEPTH candidates."""
        from .phrase import PROX_ALPHA

        pidx = self._phrase_index()
        b, kk = scores.shape
        out_s = np.zeros((b, k), np.float32)
        out_d = np.zeros((b, k), np.int32)
        for qi, text in enumerate(texts):
            terms = self._query_term_sequence(text)
            row_s = scores[qi].astype(np.float64).copy()
            for j in range(kk):
                dn = int(docnos[qi, j])
                if dn > 0 and row_s[j] > 0 and len(terms) > 1:
                    row_s[j] *= 1.0 + PROX_ALPHA * pidx.proximity_bonus(
                        terms, dn)
            order = np.lexsort((docnos[qi], -row_s))[:k]
            valid = row_s[order] > 0
            out_s[qi, : valid.sum()] = row_s[order][valid]
            out_d[qi, : valid.sum()] = docnos[qi][order][valid]
        return out_s, out_d

    # -- snippets (document store sidecar) --------------------------------

    def _docstore(self):
        if getattr(self, "_store", None) is None:
            if self._index_dir is None:
                raise ValueError("snippets need an index directory "
                                 "(Scorer built from arrays)")
            from ..index.docstore import DocStore

            self._store = DocStore(self._index_dir)
        return self._store

    def snippet(self, query_text: str, key, *, is_docid: bool = True,
                width: int | None = None) -> str:
        """Highlighted text window for one result (search/snippets.py).
        Matching is token-level through the indexing analyzer, so k-gram
        and quoted queries highlight their component words."""
        from .snippets import SNIPPET_WORDS, make_snippet

        docno = self.mapping.get_docno(key) if is_docid else int(key)
        toks = set(self._analyzer.analyze(query_text.replace('"', ' ')))
        return make_snippet(self._docstore().get(docno), toks,
                            self._analyzer,
                            width=width or SNIPPET_WORDS)

    def search(self, text: str, k: int = 10, scoring: str = "tfidf",
               return_docids: bool = True, rerank: int | None = None,
               prox: bool = False, phrase_slop: int = 0) -> SearchResult:
        return self.search_batch([text], k=k, scoring=scoring,
                                 return_docids=return_docids, rerank=rerank,
                                 prox=prox, phrase_slop=phrase_slop)[0]
