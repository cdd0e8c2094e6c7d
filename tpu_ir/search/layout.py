"""Host-side construction of the tiered sparse scoring layout.

The serving problem past the dense-matrix budget: postings lists are ragged
with dfs spanning 1 .. ~0.1*N, and every jit program needs static shapes. A
single padded [V, P] layout pays V*P where P must cover the largest df, and
the earlier hot/cold split (hot terms as dense doc-axis rows) stops scaling
once H*(D+1) outgrows HBM — at 1M docs each dense row is 4 MB, so even a few
thousand hot terms overflow.

This layout bounds both:

- **hot strip**: the highest-df terms become dense [H, D+1] raw-tf rows,
  with H capped by an element budget (HOT_BUDGET // (D+1)), so the strip
  never outgrows its budget no matter the corpus.
- **df tiers**: every other term goes to a padded [V_t, P_t] tier whose
  capacity is the term's df rounded up to a power of `growth` — geometric
  capacities bound padding waste at `growth`x while keeping the number of
  compiled gather/scatter stages at log_growth(max_df).

The reference has no analog (its postings lists are Java ArrayLists read one
term at a time, IntDocVectorsForwardIndex.java:148-184); this is the
TPU-native answer to "SequenceFile seek per term" — everything resident,
shapes static, scoring a query block = one hot einsum + one masked
gather/scatter-add per small tier + one chunk stream for the big tiers.

- **chunk stream**: a tier's stage moves B*L*P_t slots whatever lands in
  it, so on one device the big tiers (capacity a multiple of COLD_CHUNK)
  are served as one table of COLD_CHUNK-wide rows instead
  (`cold_chunk_table`, uploaded at load in their place); a block then
  moves only the chunks its own terms hold (ops/scoring.py
  `_chunk_stream`). The sharded layout keeps every tier's own stage.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# dense hot-strip budget in f32 elements (~2 GB)
HOT_BUDGET = 500_000_000
# first tier capacity and geometric growth factor between tiers
BASE_CAP = 2
GROWTH = 4
# width of the big tiers' posting chunks: it divides every capacity
# BASE_CAP * GROWTH**i from 2048 up, so each such tier splits into rows
# of whole chunks
COLD_CHUNK = 2048


class TieredPostings(NamedTuple):
    """Host (numpy) arrays; the Scorer moves them to device.

    The hot strip is carried as COO postings (hot_rows/hot_docs/hot_vals),
    NOT as the dense [H, D+1] matrix: at 1M docs the dense strip is ~2 GB
    while the postings behind it are a few hundred MB, and the H2D link is
    the serving cold-start bottleneck — so the dense strip is materialized
    ON DEVICE by a jitted scatter (`hot_device`), and only the COO columns
    ever cross the transport (or sit in the serving cache)."""

    hot_rank: np.ndarray   # int32 [V]: row in the hot strip, or -1
    hot_rows: np.ndarray   # [nnz] strip row per hot posting (uint16/int32)
    hot_docs: np.ndarray   # [nnz] docno per hot posting (uint16/int32)
    hot_vals: np.ndarray   # [nnz] raw tf per hot posting (uint16/int32)
    num_hot: int           # H >= 1 (one all-zero row when nothing is hot)
    hot_width: int         # D + 1
    tier_of: np.ndarray    # int32 [V]: tier index (-1 for hot/df=0 terms)
    row_of: np.ndarray     # int32 [V]: row within the tier (0 likewise)
    tier_docs: tuple       # each int32 [V_t, P_t], docnos, 0 = empty slot
    tier_tfs: tuple        # each int32 [V_t, P_t], tfs, 0 = empty slot
    # block-max pruning (ISSUE 13): per-(hot row, doc block) max raw tf
    # — int [H, nblk] at `blockmax_width` doc columns per block, or None
    # when bounds were unavailable (pre-13 serving caches). The scorer
    # derives each scoring mode's per-block score upper bound from it.
    hot_blk_max: np.ndarray | None = None
    blockmax_width: int = 0

    def hot_dense(self) -> np.ndarray:
        """Densify the hot strip on HOST — for the sharded stacker and
        tests; the serving path uses `hot_device` instead."""
        out = np.zeros((self.num_hot, self.hot_width), np.float32)
        out[np.asarray(self.hot_rows, np.int64),
            np.asarray(self.hot_docs, np.int64)] = self.hot_vals
        return out

    def hot_device(self, dtype: str = "float32"):
        """Densify the hot strip ON DEVICE: upload the COO columns (the
        postings, not the strip) via the chunked double-buffered streamer
        — when they arrive as serving-cache mmaps, disk page-ins overlap
        the in-flight transfers — and scatter under jit. `dtype` selects
        the resident strip dtype: "bfloat16" halves the HBM footprint
        for compressed indexes whose tfs round-trip bf16 exactly (the
        scorer checks that before asking); the kernels widen to fp32 at
        the weight-curve entry, so scores stay bit-identical. The
        scatter, its compile included, is the device half of the layout
        build: a `load.layout` span."""
        from ..obs import trace as obs_trace
        from ..utils.transfer import stream_to_device

        coo = (stream_to_device(self.hot_rows),
               stream_to_device(self.hot_docs),
               stream_to_device(self.hot_vals))
        with obs_trace("load.layout", layout="hot_strip"):
            return _densify_hot(*coo, num_hot=self.num_hot,
                                width=self.hot_width, dtype=dtype)


@partial(jax.jit, static_argnames=("num_hot", "width", "dtype"))
def _densify_hot(rows, docs, vals, *, num_hot: int, width: int,
                 dtype: str = "float32"):
    """jit scatter: COO hot postings -> dense [H, D+1] raw-tf strip.
    Each (term, doc) pair appears at most once, so set == add semantics."""
    strip = jnp.zeros((num_hot, width), dtype)
    return strip.at[rows.astype(jnp.int32), docs.astype(jnp.int32)].set(
        vals.astype(dtype))


def cold_chunk_plan(tiers: TieredPostings, df: np.ndarray):
    """The chunk table's plan: (streamed tier indices, row0, count,
    chunk), or None when no tier's capacity is a multiple of the chunk
    width COLD_CHUNK. row0[v] is term v's first row in the concatenated
    [R, chunk] table and count[v] = ceil(df[v] / chunk) the rows it
    streams (0 for hot, absent and small-tier terms). Only tier shapes
    are read, so a serving-cache mmap costs no page-ins here."""
    chunk = COLD_CHUNK
    caps = np.array([a.shape[1] for a in tiers.tier_docs], np.int64)
    streamed = [i for i, c in enumerate(caps) if c % chunk == 0]
    if not streamed:
        return None
    per_row = np.where(caps % chunk == 0, caps // chunk, 0)
    nrows = np.array([a.shape[0] for a in tiers.tier_docs], np.int64)
    base = np.concatenate([[0], np.cumsum(nrows * per_row)])[:-1]
    tof = np.asarray(tiers.tier_of)
    t = np.where(tof >= 0, tof, 0)
    on = (tof >= 0) & (per_row[t] > 0)
    row0 = np.where(on, base[t] + np.asarray(tiers.row_of) * per_row[t], 0)
    count = np.where(on, np.minimum(-(-np.asarray(df, np.int64) // chunk),
                                    per_row[t]), 0)
    return streamed, row0.astype(np.int32), count.astype(np.int32), chunk


def cold_chunk_table(tiers: TieredPostings, plan) -> tuple:
    """The streamed tiers as host (docs, tfs) tables: each [V_t, P_t]
    tier viewed as [V_t * P_t / chunk, chunk] rows (a free reshape) and
    concatenated in plan order. The tiers share one dtype (`_slim` sizes
    them on global bounds), so this is a copy, not a conversion. The
    Scorer uploads these in place of the streamed tiers."""
    streamed, _, _, chunk = plan
    return tuple(np.concatenate([np.asarray(arrs[i]).reshape(-1, chunk)
                                 for i in streamed])
                 for arrs in (tiers.tier_docs, tiers.tier_tfs))


def position_table(index_dir: str, meta, doc_len: np.ndarray) -> tuple:
    """The positional index as the host arrays of ops/phrase.py
    PositionTable (docs, at, start, cf, fwd, doc_start): each part's
    delta-coded position runs (index/positions.py) decoded to absolute
    positions and reordered from the pair order (tf descending) to
    (docno, position) order, term by term, the terms concatenated by id
    (`start[t]` term t's first index, `cf[t]` its position count); and
    the same positions as one doc-major token stream `fwd` of rows of
    PHRASE_SLICE, doc d from row doc_start[d] / PHRASE_SLICE with at
    least PHRASE_GAP empty slots (-1) after its doc_len[d] tokens.
    `at` is each position's index in that stream. docs/at are laid out
    in rows of PHRASE_CHUNK with at least one row past the last
    position, so the two rows an anchor chunk spans always exist.
    Shards are decoded concurrently, each into its own terms' slices."""
    from concurrent.futures import ThreadPoolExecutor

    from ..index import format as fmt
    from ..index.positions import positions_name, realign_runs
    from ..ops.phrase import PHRASE_CHUNK, PHRASE_GAP, PHRASE_SLICE

    def read(s: int):
        z = fmt.load_shard(index_dir, s, mmap=True)
        with np.load(os.path.join(index_dir, positions_name(s))) as pz:
            return z, pz["pos_indptr"], pz["pos_delta"]

    threads = max(1, min(fmt.load_threads(), meta.num_shards))
    with ThreadPoolExecutor(max_workers=threads) as ex:
        shards = list(ex.map(read, range(meta.num_shards)))
    cf = np.zeros(meta.vocab_size, np.int64)
    for z, indptr, _ in shards:
        row_term = np.repeat(np.asarray(z["term_ids"], np.int64),
                             np.diff(z["indptr"]))
        cf += np.bincount(row_term, weights=np.diff(indptr),
                          minlength=len(cf)).astype(np.int64)
    start = np.concatenate([[0], np.cumsum(cf)])
    doc_len = np.asarray(doc_len, np.int64)
    doc_start = PHRASE_SLICE * np.concatenate([[0], np.cumsum(
        -(-(doc_len + PHRASE_GAP) // PHRASE_SLICE))])
    if max(start[-1], doc_start[-1]) >= 2**31:
        raise ValueError("the positions overflow the int32 offsets of "
                         "the device position table")
    n_rows = int(start[-1]) // PHRASE_CHUNK + 2
    docs = np.zeros(n_rows * PHRASE_CHUNK, np.int32)
    at = np.zeros(n_rows * PHRASE_CHUNK, np.int32)
    fwd = np.full(int(doc_start[-1]), -1, np.int32)

    def place(shard) -> None:
        z, indptr, delta = shard
        run_len = np.diff(indptr)
        if not len(run_len):
            return
        row_term = np.repeat(np.asarray(z["term_ids"], np.int64),
                             np.diff(z["indptr"]))
        row_doc = np.asarray(z["pair_doc"], np.int64)
        # absolute positions: a segmented cumsum of each run's deltas
        c = np.cumsum(delta, dtype=np.int64)
        first = indptr[:-1]
        pos = c - np.repeat(c[first] - delta[first], run_len)
        order = np.argsort(row_term * (meta.num_docs + 1) + row_doc,
                           kind="stable")
        _, gather = realign_runs(first[order], run_len[order])
        terms = row_term[order]
        # a term's rows are contiguous in `order`, so its offset within
        # the shard's run is the cumsum up to its first row
        tstart = np.flatnonzero(np.diff(terms, prepend=-1))
        base = np.repeat(np.concatenate([[0], np.cumsum(run_len[order])])[
            tstart], np.diff(np.append(tstart, len(terms))))
        dest = (np.repeat(start[terms] - base, run_len[order])
                + np.arange(int(run_len.sum())))
        d = np.repeat(row_doc[order], run_len[order])
        p = pos[gather]
        if (p >= doc_len[d]).any():
            raise ValueError("a position lies past its document's length")
        docs[dest] = d
        at[dest] = doc_start[d] + p
        fwd[doc_start[d] + p] = np.repeat(terms.astype(np.int32),
                                          run_len[order])

    with ThreadPoolExecutor(max_workers=threads) as ex:
        list(ex.map(place, shards))
    return (docs.reshape(n_rows, PHRASE_CHUNK),
            at.reshape(n_rows, PHRASE_CHUNK), start[:-1].astype(np.int32),
            cf.astype(np.int32), fwd.reshape(-1, PHRASE_SLICE),
            doc_start.astype(np.int32))


def _slim(a: np.ndarray, hi: int) -> np.ndarray:
    """uint16 when every value fits, else int32 — halves transport bytes
    for the common case (strip rows, tfs, small-corpus docnos)."""
    return a.astype(np.uint16 if hi < 65536 else np.int32)


def _scatter_rows(tids: np.ndarray, indptr: np.ndarray, counts: np.ndarray):
    """Vectorized source indices for packing terms' postings into rows:
    returns (row_index, within_row, source_index) for every posting of
    `tids` — pure index computation, the callers gather the columns."""
    total = int(counts.sum())
    rows = np.repeat(np.arange(len(tids), dtype=np.int64), counts)
    # offset of each posting within its term's run
    ends = np.cumsum(counts)
    within = np.arange(total, dtype=np.int64) - np.repeat(ends - counts,
                                                          counts)
    src = np.repeat(indptr[tids], counts) + within
    return rows, within, src


def plan_tiers(
    df: np.ndarray,
    *,
    num_docs: int,
    hot_budget: int = HOT_BUDGET,
    base_cap: int = BASE_CAP,
    growth: int = GROWTH,
):
    """The ASSIGNMENT half of build_tiered_layout: which terms get a
    hot-strip row (the p99-df threshold decides who *wants* one, the
    element budget decides how many *get* one — largest dfs win), the
    geometric tier-capacity ladder, and each cold term's rung.

    Returns (hot_tids, cold_tids, caps, want): sorted hot term ids, the
    cold term ids, the capacity ladder, and `want[i]` = the ladder rung
    of cold_tids[i]. Shared between the layout builder and `tpu-ir
    doctor`'s tier-occupancy report (index/doctor.py) so the health
    report describes the layout serving actually uses, by construction."""
    d = num_docs
    nonzero_df = df[df > 0]
    pcap = max(int(np.percentile(nonzero_df, 99)) if len(nonzero_df) else 1,
               1)
    hot_tids = np.nonzero(df > pcap)[0]
    max_hot = max(int(hot_budget // (d + 1)), 1)
    if len(hot_tids) > max_hot:
        order = np.argsort(df[hot_tids], kind="stable")[::-1]
        hot_tids = np.sort(hot_tids[order[:max_hot]])
    is_hot = np.zeros(len(df), bool)
    is_hot[hot_tids] = True
    cold = np.nonzero(~is_hot & (df > 0))[0]
    caps: list[int] = []
    want = np.zeros(0, np.int64)
    if len(cold):
        caps = [base_cap]
        while caps[-1] < int(df[cold].max()):
            caps.append(caps[-1] * growth)
        want = np.searchsorted(caps, df[cold], side="left")
    return hot_tids, cold, caps, want


def build_tiered_layout(
    pair_doc: np.ndarray,
    pair_tf: np.ndarray,
    df: np.ndarray,
    *,
    num_docs: int,
    hot_budget: int = HOT_BUDGET,
    base_cap: int = BASE_CAP,
    growth: int = GROWTH,
    block_bounds: tuple | None = None,
) -> TieredPostings:
    """Build the layout from global-CSR-ordered postings columns.

    `pair_doc`/`pair_tf` must be sorted by term id with per-term runs of
    length `df[tid]` (the Scorer.load order).

    `block_bounds` = (tids, max_tf, width) from blockmax.arena
    (index/blockmax.py): per-term per-doc-block max tf the builders
    recorded. When supplied AND covering this layout's hot set, the hot
    rows' bounds are sliced from it; otherwise they are recomputed from
    the postings (identical values — the artifact saves the pass, it
    never changes the result)."""
    from ..index import blockmax as bmx

    v = len(df)
    d = num_docs
    indptr = np.concatenate([[0], np.cumsum(df, dtype=np.int64)])

    hot_tids, cold, caps, want = plan_tiers(
        df, num_docs=num_docs, hot_budget=hot_budget, base_cap=base_cap,
        growth=growth)
    hot_rank = np.full(v, -1, np.int32)
    hot_rank[hot_tids] = np.arange(len(hot_tids), dtype=np.int32)

    num_hot = max(len(hot_tids), 1)
    if len(hot_tids):
        rows, _, src = _scatter_rows(hot_tids, indptr, df[hot_tids])
        hot_rows = _slim(rows, num_hot)
        hot_docs = _slim(pair_doc[src], d + 1)
        hot_vals = _slim(pair_tf[src], int(pair_tf[src].max(initial=0)) + 1)
    else:
        hot_rows = np.zeros(0, np.uint16)
        hot_docs = np.zeros(0, np.uint16)
        hot_vals = np.zeros(0, np.uint16)

    # cold tiers: capacity = df rounded up to base_cap * growth^i.
    # tier_of = -1 for terms with no postings (df == 0) and for hot terms:
    # a 0 default would alias them onto tier 0 row 0 — harmless only for
    # weight functions that are zero at df == 0, which BM25's idf is not.
    tier_of = np.full(v, -1, np.int32)
    row_of = np.zeros(v, np.int32)
    tier_docs: list[np.ndarray] = []
    tier_tfs: list[np.ndarray] = []
    max_tf = int(pair_tf.max(initial=0))
    if len(cold):
        for i in range(len(caps)):
            tids = cold[want == i]
            if not len(tids):
                continue  # skip empty tiers entirely
            cap = caps[i]
            docs = np.zeros((len(tids), cap), np.int32)
            tfs = np.zeros((len(tids), cap), np.int32)
            rows, within, src = _scatter_rows(tids, indptr, df[tids])
            docs[rows, within] = pair_doc[src]
            tfs[rows, within] = pair_tf[src]
            tier_of[tids] = len(tier_docs)
            row_of[tids] = np.arange(len(tids), dtype=np.int32)
            # slim dtypes cross the H2D link and sit in the serving cache;
            # the jit programs cast/gather from any int dtype (the scatter
            # sentinel num_docs+1 still fits: uint16 only when d+1 < 65536)
            tier_docs.append(_slim(docs, d + 1))
            tier_tfs.append(_slim(tfs, max_tf + 1))
    if not tier_docs:  # every term hot (or empty): keep one dummy tier
        tier_docs.append(np.zeros((1, 1), np.int32))
        tier_tfs.append(np.zeros((1, 1), np.int32))

    # block-max bounds for the hot rows: sliced from the builders'
    # blockmax.arena when it covers this hot set, else recomputed from
    # the postings (one vectorized maximum-scatter over the hot runs)
    width = bmx.block_width()
    hot_blk_max = None
    if block_bounds is not None and len(hot_tids):
        btids, bmax, bwidth = block_bounds
        pos = np.searchsorted(btids, hot_tids)
        if (len(btids) and pos.max(initial=0) < len(btids)
                and np.array_equal(np.asarray(btids)[pos], hot_tids)):
            hot_blk_max = np.asarray(bmax)[pos].astype(np.int32)
            width = int(bwidth)
    if hot_blk_max is None:
        if len(hot_tids):
            hot_blk_max = bmx.compute_block_max(
                hot_tids, pair_doc, pair_tf, indptr, num_docs=d,
                width=width)
        else:
            hot_blk_max = np.zeros((1, bmx.num_blocks(d, width)),
                                   np.int32)

    return TieredPostings(hot_rank, hot_rows, hot_docs, hot_vals,
                          num_hot, d + 1, tier_of, row_of,
                          tuple(tier_docs), tuple(tier_tfs),
                          hot_blk_max, width)


def shard_doc_ranges(num_docs: int, num_shards: int) -> list:
    """The scatter-gather tier's doc partition: contiguous 1-based
    inclusive [lo, hi] docid ranges, one per shard, matching the block
    math of parallel/sharded_tiered.shard_slices (dblk = ceil(D/S), so
    trailing shards past num_docs own an empty range, hi < lo). Docid 0
    is the dead slot and belongs to nobody."""
    if num_shards < 1:
        raise ValueError("num_shards must be >= 1")
    dblk = -(-num_docs // num_shards)
    return [(s * dblk + 1, min((s + 1) * dblk, num_docs))
            for s in range(num_shards)]


def restrict_tiers(tiers: TieredPostings, lo: int, hi: int) -> TieredPostings:
    """A doc-range-restricted COPY of a tiered layout: postings whose
    docno falls outside [lo, hi] have their tf zeroed, everything else —
    hot_rank, tier geometry, array shapes, posting positions — is left
    BYTE-IDENTICAL. Shape preservation is the whole point: the scoring
    kernels trace the exact same programs as the unrestricted layout, and
    a doc inside the range keeps every one of its postings at the same
    position, so its score is BIT-IDENTICAL to the full single-process
    scorer's (a zeroed tf contributes exact 0.0 — the same PAD-exactness
    the explain suite pins). Docs outside the range score exactly 0.0 and
    fall out of the top-k with the empty-slot mask. This is what makes
    the router's exact merge provably correct (DESIGN §14): per-doc
    scores do not depend on the partition at all.

    Inputs may be read-only serving-cache mmaps — only the tf columns
    are copied; index/geometry arrays are shared as-is."""
    hot_docs = np.asarray(tiers.hot_docs)
    hot_vals = np.array(tiers.hot_vals)  # copy: may be a read-only mmap
    out_of_range = (hot_docs.astype(np.int64) < lo) | (
        hot_docs.astype(np.int64) > hi)
    hot_vals[out_of_range] = 0
    tier_tfs = []
    for td, tt in zip(tiers.tier_docs, tiers.tier_tfs):
        td64 = np.asarray(td).astype(np.int64)
        tf = np.array(tt)
        tf[(td64 < lo) | (td64 > hi)] = 0
        tier_tfs.append(tf)
    # block-max bounds compose with the restriction: a doc block wholly
    # outside [lo, hi] has every hot tf zeroed above, so its bound drops
    # to exact 0; boundary blocks keep the GLOBAL bound — an
    # overestimate over the surviving postings, which is sound (bounds
    # must only dominate) and merely a hair less tight at the two edges
    hot_blk_max = tiers.hot_blk_max
    if hot_blk_max is not None and tiers.blockmax_width:
        w = int(tiers.blockmax_width)
        nblk = hot_blk_max.shape[1]
        starts = np.arange(nblk, dtype=np.int64) * w
        outside = (starts + w - 1 < lo) | (starts > hi)
        hot_blk_max = np.array(hot_blk_max)
        hot_blk_max[:, outside] = 0
    return tiers._replace(hot_vals=hot_vals, tier_tfs=tuple(tier_tfs),
                          hot_blk_max=hot_blk_max)


# serving-cache format version; bump when the layout semantics change
# (v2: hot strip cached as COO postings instead of the dense matrix;
#  v3: keyed by part-file CRCs — a cache HIT needs no shard read or CSR
#  assembly at all — and df + rerank doc-norms ride in the cache;
#  v4: key CRCs carry fmt.file_checksum's tagged string form, shared with
#  the metadata integrity checksums;
#  v5: arrays persist in ONE page-aligned arena file (cache.arena,
#  index/format.py) instead of N .npy files — mmap-identical reads, one
#  open; the manifest additionally records part (size, mtime_ns) stats so
#  an UNCHANGED index revalidates without re-streaming every part's CRC;
#  v6: the hot strip's block-max bounds (hot_blk_max [H, nblk] +
#  manifest blockmax_width) ride in the cache, so warm loads serve
#  block-max pruning with zero postings IO;
#  v7: the key folds in the index's serving INTERPRETATION — format
#  version, tf dtype/lossiness, and each part's arena section
#  (name, dtype) signature. The part-CRC key certifies bytes, not
#  meaning: a compressed-arena migration that lands byte-for-byte
#  re-runs (or a raw<->compressed flip with preserved mtimes) changes
#  how those bytes must be decoded without changing any stat the v6
#  fast path compares, so v6's stat-first revalidation could serve a
#  stale strip dtype. Dtype signatures are header-only reads (~1 page
#  per part), so the fast path stays stat-cheap)
_CACHE_VERSION = 7


def _part_stat(index_dir: str, meta) -> list:
    """[name, size, mtime_ns] per part file — the cheap revalidation
    stamp. Any write through the filesystem API (in-place rebuilds
    included) lands a new mtime_ns, so a stat match means the files are
    the ones the CRC key certified at cache-write time; on any mismatch
    the reader falls back to the full CRC key compare, so a
    mtime-restoring copy still revalidates by content. What a stat match
    can NOT see is sub-filesystem corruption (media bit-rot that
    preserves size and mtime_ns): that rot surfaces only when shard
    bytes actually stream (the lazy verified pairs loader), not on the
    zero-part-IO cache hit itself — operators who want every warm load
    to re-prove part content set TPU_IR_CACHE_REVALIDATE=crc and pay
    one streamed CRC pass per part (read_cache_manifest)."""
    import os

    from ..index import format as fmt

    out = []
    for s in range(meta.num_shards):
        path = fmt.part_path(index_dir, s)
        st = os.stat(path)
        out.append([os.path.basename(path), st.st_size, st.st_mtime_ns])
    return out


def _section_signature(index_dir: str, meta) -> list:
    """Per-part serving-interpretation signature: the arena header's
    (section name, dtype) pairs — "npz" for v1 parts, which have exactly
    one interpretation. Header-only reads (no payload IO). This is what
    lets the cache key distinguish raw from compressed parts that a
    stat (or even a whole-file CRC of a byte-identical re-migration)
    cannot: the section list IS the decode contract."""
    import os

    from ..index import format as fmt

    out = []
    for s in range(meta.num_shards):
        path = fmt.part_path(index_dir, s)
        if path.endswith(".npz"):
            out.append([os.path.basename(path), "npz"])
            continue
        header, _ = fmt.read_arena_header(path)
        out.append([os.path.basename(path),
                    [[sec["name"], sec["dtype"]]
                     for sec in header["sections"]]])
    return out


def _serving_cache_key(index_dir: str, meta, hot_budget, base_cap,
                       growth, part_crcs: dict | None = None) -> dict:
    """Content-addressed key over the part FILES (streamed CRC32, ~1 s/GB
    from page cache), so an in-place rebuild misses even when every df is
    unchanged — without paying the shard-load + CSR assembly the old
    column-CRC key required (~minutes at 250M pairs, the dominant warm-load
    cost the cache exists to remove). The digest is fmt.file_checksum —
    the SAME helper metadata checksums use — because Scorer.load's
    "cache hit implies parts verified" shortcut is only sound while the
    two stay one implementation. `part_crcs` ({name: digest}) supplies
    digests a verified load already folded, skipping the re-stream."""
    import os

    from ..index import format as fmt

    files = []
    for s in range(meta.num_shards):
        path = fmt.part_path(index_dir, s)
        name = os.path.basename(path)
        crc = (part_crcs or {}).get(name) or fmt.file_checksum(path)
        files.append([name, os.path.getsize(path), crc])
    return {
        "version": _CACHE_VERSION,
        "num_docs": meta.num_docs,
        "vocab_size": meta.vocab_size,
        "num_pairs": meta.num_pairs,
        "part_files": files,
        # v7: the serving interpretation — see the version changelog.
        "format_version": meta.format_version,
        "tf_dtype": getattr(meta, "tf_dtype", "int32"),
        "tf_lossy": bool(getattr(meta, "tf_lossy", False)),
        "section_dtypes": _section_signature(index_dir, meta),
        "hot_budget": hot_budget,
        "base_cap": base_cap,
        "growth": growth,
    }


def serving_cache_writable(index_dir: str) -> bool:
    """Whether a serving-cache save can possibly succeed — callers skip
    eager cache-only work (the norms pass) on read-only index dirs, where
    every process restart would otherwise repay it for a save that
    silently fails."""
    import os

    return os.access(index_dir, os.W_OK)


def cache_revalidate_mode() -> str:
    """The validated TPU_IR_CACHE_REVALIDATE setting: 'stat' (default;
    trust unchanged name+size+mtime) or 'crc' (re-stream every part and
    content-prove each cache hit). An integrity knob must not fail open,
    so a bogus value raises instead of silently keeping the weaker stat
    shortcut — cache loaders call this BEFORE their unreadable-cache
    try/except so the error escapes to the operator. Now a thin wrapper
    over the declared-knob registry (utils/envvars.py) — this function
    was the template the registry's get_choice generalizes."""
    from ..utils import envvars

    return envvars.get_choice("TPU_IR_CACHE_REVALIDATE")


def read_cache_manifest(index_dir: str, cache_name: str, key,
                        part_stat=None):
    """(manifest dict, arr loader) on a key match, else None. The shared
    half of the cache protocol: both the tiered and the sharded serving
    caches (parallel/sharded_tiered.py) speak exactly this format, so
    version/manifest changes live in one place.

    `key` may be a callable (accepting an optional part_crcs dict)
    computed ONLY when needed: the manifest's recorded part (size,
    mtime_ns) stats are compared first (one stat per part —
    microseconds). On a stat match the key is REBUILT from the
    manifest's own recorded per-file digests — zero part IO — and still
    compared, so drift in the non-file key fields (hot_budget, cache
    version, metadata counts) misses like it always did; only on a stat
    mismatch (or absent `part_stat`) is the streamed-CRC key computed.
    A fresh index with no cache returns None without touching a single
    part byte. TPU_IR_CACHE_REVALIDATE=crc disables the stat shortcut
    for operators who want every hit content-proven (stat revalidation
    cannot see bit-rot that preserves size+mtime, see _part_stat).

    Array loader: cache v5 serves sections zero-copy out of one mmap'd
    cache.arena. Older .npy-per-array caches never reach the loader —
    their key (older `version` field) misses above and the cache is
    rebuilt."""
    import json
    import os

    from ..index import format as fmt

    cache_dir = os.path.join(index_dir, cache_name)
    manifest = os.path.join(cache_dir, "manifest.json")
    if not os.path.exists(manifest):
        return None
    with open(manifest) as f:
        m = json.load(f)
    if cache_revalidate_mode() == "crc":
        part_stat = None
    stat_now = part_stat() if callable(part_stat) else part_stat
    if (callable(key) and stat_now is not None
            and m.get("part_stat") == stat_now):
        # unchanged files (names+sizes+mtimes): recompute the key with
        # the manifest's own digests instead of re-streaming every part
        recorded = {f[0]: f[2]
                    for f in m.get("key", {}).get("part_files", [])}
        if m["key"] != key(recorded):
            return None
    elif m["key"] != (key() if callable(key) else key):
        return None

    # cache v5: every array is a section of ONE mmap'd arena. No .npy
    # fallback: the key embeds _CACHE_VERSION, so any pre-arena cache
    # misses above and is rebuilt — a matching manifest implies a v5
    # writer, which always emits cache.arena.
    sections = fmt.load_arena(os.path.join(cache_dir, "cache.arena"),
                              mmap=True)

    def arr(name):
        return sections[name]

    return m, arr


def write_cache_atomic(index_dir: str, cache_name: str,
                       arrays: dict, manifest: dict) -> None:
    """Atomic cache persist (tmp dir + rename): every array packed into
    ONE page-aligned arena file (cache.arena — the same zero-copy format
    v2 part files use, per-section CRCs included) plus manifest.json,
    then the directory swaps in. Any OSError — from key computation IO
    included if the caller defers it into `manifest` via a callable —
    degrades to no cache, never an exception."""
    import json
    import os
    import shutil
    import tempfile

    from ..index import format as fmt

    cache_dir = os.path.join(index_dir, cache_name)
    tmp = None
    try:
        if callable(manifest):
            manifest = manifest()
        tmp = tempfile.mkdtemp(dir=index_dir, prefix=f".{cache_name}-")
        fmt.write_arena(os.path.join(tmp, "cache.arena"),
                        {n: np.asarray(a) for n, a in arrays.items()})
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        shutil.rmtree(cache_dir, ignore_errors=True)
        os.replace(tmp, cache_dir)
    except OSError:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def load_serving_cache(
    index_dir: str,
    *,
    meta,
    hot_budget: int = HOT_BUDGET,
    base_cap: int = BASE_CAP,
    growth: int = GROWTH,
):
    """Serving-cache hit: (TieredPostings, df, doc_norms) — every array
    memory-mapped out of one arena, NO shard IO — or None on any
    miss/corruption. Revalidation is stat-first: an unchanged index
    (names + sizes + mtimes) hits without re-streaming part CRCs, so the
    warm load is mmap + upload only; any stat drift falls back to the
    full content-addressed CRC key (TPU_IR_CACHE_REVALIDATE=crc forces
    that full compare on every load)."""
    cache_revalidate_mode()  # a bogus knob raises HERE, not into except
    try:
        hit = read_cache_manifest(
            index_dir, "serving-tiered",
            lambda part_crcs=None: _serving_cache_key(
                index_dir, meta, hot_budget, base_cap, growth,
                part_crcs=part_crcs),
            part_stat=lambda: _part_stat(index_dir, meta))
        if hit is None:
            return None
        m, arr = hit
        tiers = TieredPostings(
            arr("hot_rank"), arr("hot_rows"), arr("hot_docs"),
            arr("hot_vals"), m["num_hot"], m["hot_width"],
            arr("tier_of"), arr("row_of"),
            tuple(arr(f"tier_docs_{i}") for i in range(m["num_tiers"])),
            tuple(arr(f"tier_tfs_{i}") for i in range(m["num_tiers"])),
            arr("hot_blk_max"), m["blockmax_width"])
        return tiers, arr("df"), arr("doc_norms")
    except (OSError, KeyError, ValueError):
        return None  # unreadable/stale cache: caller rebuilds


def save_serving_cache(
    index_dir: str,
    tiers: TieredPostings,
    df: np.ndarray,
    doc_norms: np.ndarray,
    *,
    meta,
    hot_budget: int = HOT_BUDGET,
    base_cap: int = BASE_CAP,
    growth: int = GROWTH,
) -> None:
    """Persist the serving arrays under `index_dir/serving-tiered/`."""
    arrays = {
        "hot_rank": tiers.hot_rank, "hot_rows": tiers.hot_rows,
        "hot_docs": tiers.hot_docs, "hot_vals": tiers.hot_vals,
        "tier_of": tiers.tier_of, "row_of": tiers.row_of,
        "df": np.asarray(df, np.int32),
        "doc_norms": np.asarray(doc_norms, np.float32),
        # cache v6: block-max bounds ride along (an all-zero [1, nblk]
        # row when the layout has no hot terms — same convention as the
        # dummy tier)
        "hot_blk_max": np.asarray(
            tiers.hot_blk_max if tiers.hot_blk_max is not None
            else np.zeros((1, 1), np.int32), np.int32),
    }
    for i, (d, t) in enumerate(zip(tiers.tier_docs, tiers.tier_tfs)):
        arrays[f"tier_docs_{i}"] = d
        arrays[f"tier_tfs_{i}"] = t
    # key computation reads every part file (unless the load already
    # folded their CRCs — metadata digests are reused when recorded); a
    # vanished/unreadable one must degrade like any other failed write
    # (deferred via callable)
    write_cache_atomic(
        index_dir, "serving-tiered", arrays,
        lambda: {"key": _serving_cache_key(
                     index_dir, meta, hot_budget, base_cap, growth,
                     part_crcs=getattr(meta, "checksums", None)),
                 "part_stat": _part_stat(index_dir, meta),
                 "num_tiers": len(tiers.tier_docs),
                 "num_hot": tiers.num_hot,
                 "hot_width": tiers.hot_width,
                 "blockmax_width": int(tiers.blockmax_width)})
