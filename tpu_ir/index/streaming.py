"""Streaming (out-of-core) index build for corpora that don't fit in memory.

Architecture mirrors Hadoop's spill-and-merge (the reference's substrate),
with the per-batch combine as a device op:

  pass 1 (map): stream the corpus in byte chunks through the native (C++)
    scanner — record split, analysis, and an incremental corpus-wide vocab
    all happen in C++; each chunk's delta (temp term ids + doc lens) is
    drained immediately and spilled as int arrays. Python never touches a
    token string (the pure-Python fallback tokenizer keeps the same
    temp-id interface). Memory = the vocab + one chunk.
  between passes: docno mapping (sorted docids) + vocab argsort; a rank
    array remaps temp ids -> sorted ids with one vectorized gather.
  pass 2 (combine + spill): re-read each id batch, remap via rank,
    pre-aggregate (term, doc, tf) on device (the combiner), and spill each
    batch's pairs partitioned by term shard (term_id % S).
  pass 3 (order + write): per term shard, concatenate its spills and
    lexsort into the reference posting order -> part-NNNNN file. A host
    sort, deliberately: batches partition documents so there is nothing to
    merge, and the spills start and end on host disk. Peak memory is one
    shard's pairs, never the whole index.

RADIX MODE (ISSUE 11, `radix_buckets`/TPU_IR_RADIX_BUCKETS > 0) moves the
partition to where Hadoop put it — spill time — and the pass-2 global
combine disappears:

  pass 1 additionally radix-partitions each batch's occurrence stream by
    destination bucket (temp_id % B; stable across resume because temp
    ids are pinned by the manifest) as the spills are written
    (rpairs-RRR-BBBBB.npz, documents run-length packed), on a pipeline
    thread one batch behind the tokenizer;
  pass 2 becomes B embarrassingly-parallel per-bucket LOCAL device
    reduces: read bucket R's spills (a prefetch thread keeps the host one
    bucket ahead of the device), remap temp->sorted ids, one device
    group-by, split the result by final term shard — no global sort, no
    token-spill re-read. A bucket is a function of the TERM alone, so
    per-bucket tf aggregation is exact and final.
  pass 3 is unchanged (spills arrive keyed by bucket instead of batch),
    so radix artifacts are bit-identical to the legacy streaming build
    AND the one-shot builder — fuzz-pinned across bucket counts, resume
    points and meshes (tests/test_radix.py). TPU_IR_RADIX_PARTS skips the
    pass-3 sort and writes bucket-segmented parts instead (readers accept
    both layouts; bytes differ — see write_bucketed_shard).

With `spmd_devices=N`, pass 2 runs as the mesh program instead: legacy
mode doc-deals each batch across the N devices and runs the combiner +
all_to_all shuffle + term-shard reduce in one jit
(parallel/sharded_build.py — the splits -> shuffle -> reducers pipeline of
TermKGramDocIndexer.java:227-283, with the corpus streamed from disk);
radix mode round-robins buckets across devices and reduces N buckets per
dispatch with ZERO collectives (radix_bucket_reduce — the partition
already did the routing), donating the occurrence upload on TPU backends.
Either way the artifacts are byte-identical to the single-device
streaming build at the same shard count.

Crash resume: every spill and part file is written atomically (temp +
rename), pass 1 ends by writing a manifest (docids, native vocab,
per-batch occurrence counts, config signature), and a restart resumes from
the last complete artifact — token spills are never re-tokenized, complete
pass-2 batches are never recombined, complete pass-3 shards are never
re-sorted. Spills from a different config (corpus bytes, k, shards, spmd)
are discarded. This generalizes the reference's resume-by-artifact
(BuildIntDocVectorsForwardIndex.java:186-194) to the pass DAG *within* one
job, per SURVEY §5; `overwrite=True` restores delete-up-front.

This is the scaling path for the Wikipedia-1M / MS MARCO configs
(BASELINE.json); the in-memory builder (builder.py) stays the fast path for
reference-scale corpora.
"""

from __future__ import annotations

import logging
import os
import shutil
import time
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults
from ..analysis.native import NativeChunkedTokenizer, make_chunked_tokenizer
from ..collection import DocnoMapping, Vocab
from ..obs import trace as obs_trace
from ..obs.progress import report_progress, tracked
from ..ops import PAD_TERM, PAD_TERM_U16, build_postings_packed_jit
from ..ops.postings import pair_term_from_df
from ..utils import JobReport, fetch_to_host
from ..utils.transfer import narrow_uint, shrink_pairs, shrink_rows_for_fetch
from . import format as fmt
from .builder import build_chargram_artifacts


from ..ops.postings import round_cap as _round_cap


logger = logging.getLogger(__name__)

PASS1_MANIFEST = "pass1.npz"

_CORRUPT_NPZ = fmt.CORRUPT_NPZ
_readable_npz = fmt.readable_npz


def _config_sig(corpus_paths: Sequence[str], k: int, num_shards: int,
                spmd_devices: int | None,
                positions: bool = False,
                store: bool = False,
                radix_buckets: int = 0,
                radix_parts: bool = False,
                extra: Sequence[str] = ()) -> np.ndarray:
    """Build-config signature stored in the pass-1 manifest: a resume is
    only valid against spills produced by the SAME corpus files and build
    shape (the reference's resume-by-artifact skips outputs the same way,
    BuildIntDocVectorsForwardIndex.java:186-194 — generalized here to the
    pass DAG within one job per SURVEY §5). `extra` carries additional
    shape facts (the multi-host build pins process index/count and batch
    size, which all change the spill layout). `radix_buckets` is folded
    in so a radix-config change (bucket count, or radix on/off) can
    never resume over spills partitioned the other way — the bucket id
    is baked into every pass-1 spill's NAME and CONTENT."""
    parts = [f"k={k}", f"shards={num_shards}", f"spmd={spmd_devices or 0}",
             f"pos={int(positions)}", f"store={int(store)}",
             f"radix={radix_buckets}", f"rparts={int(radix_parts)}",
             *extra]
    for p in corpus_paths:
        ap = os.path.abspath(p)
        if os.path.exists(ap):
            st = os.stat(ap)
            size, mtime = st.st_size, st.st_mtime_ns
        else:
            size, mtime = -1, -1
        # mtime guards against a REGENERATED corpus of identical size
        # (fixed-width synthetic docs make that collision easy): stale
        # token spills must not resume over new content
        parts.append(f"{ap}:{size}:{mtime}")
    return np.array(parts, dtype=np.str_)


def radix_spill_name(bucket: int, batch: int) -> str:
    """Pass-1 bucketed pair spill for (radix bucket, tokenize batch):
    the occurrence stream of every term whose temp id hashes to
    `bucket`, run-length packed per document. The bucket id leads so an
    `ls` groups a bucket's inputs the way pass 2 reads them."""
    return f"rpairs-{bucket:03d}-{batch:05d}.npz"


class _ResumeState:
    """Complete pass-1 state recovered from a matching manifest: the
    docids (corpus order), the native vocab (temp-id order), the batch
    count + per-batch stats — and, for a radix build, the bucket count
    its spills were partitioned by plus every doc's occurrence count
    (doc_len no longer falls out of re-reading token spills, because
    radix mode writes pair spills instead)."""

    def __init__(self, docids, vocab, n_batches, batch_occ,
                 radix_buckets=0, doc_lens=None):
        self.docids = docids
        self.vocab = vocab
        self.n_batches = n_batches
        self.batch_occ = batch_occ
        self.radix_buckets = radix_buckets
        self.doc_lens = doc_lens


def _pass1_spill_paths(spill_dir: str, b: int, radix_buckets: int):
    """Batch b's pass-1 spill files, manifest-CRC order: the token spill
    (legacy) or its per-bucket rpairs spills (radix)."""
    if radix_buckets:
        return [os.path.join(spill_dir, radix_spill_name(r, b))
                for r in range(radix_buckets)]
    return [os.path.join(spill_dir, f"tokens-{b:05d}.npz")]


def _load_resume_state(spill_dir: str, sig: np.ndarray):
    """Returns a _ResumeState when the spill dir holds a complete pass-1
    state for this exact config, else None. Manifest + spills are
    written atomically, so existence implies completeness; the manifest
    additionally records each pass-1 spill's CRC, and a mismatch (bit
    rot, torn disk) discards the whole pass-1 state — a corrupt token or
    bucketed pair spill cannot be rebuilt without re-tokenizing, so the
    only safe recovery is a fresh pass 1."""
    path = os.path.join(spill_dir, PASS1_MANIFEST)
    if not os.path.exists(path):
        return None
    try:
        with np.load(path, allow_pickle=False) as z:
            if (len(z["sig"]) != len(sig)
                    or not (z["sig"] == sig).all()):
                return None
            n_batches = int(z["n_batches"])
            radix = (int(z["radix_buckets"])
                     if "radix_buckets" in z.files else 0)
            spill_crc = (z["spill_crc"].tolist()
                         if "spill_crc" in z.files else None)
            if (spill_crc is not None
                    and len(spill_crc) != n_batches * max(radix, 1)):
                return None  # torn/foreign manifest: CRC inventory short
            i = 0
            for b in range(n_batches):
                for spill in _pass1_spill_paths(spill_dir, b, radix):
                    if not os.path.exists(spill):
                        return None
                    if (spill_crc is not None
                            and fmt.file_checksum(spill) != spill_crc[i]):
                        from ..utils.report import recovery_counters

                        recovery_counters().incr(
                            "spill_integrity_discards")
                        logger.warning(
                            "pass-1 spill %s fails its manifest checksum;"
                            " discarding the pass-1 resume state", spill)
                        return None
                    i += 1
            return _ResumeState(
                z["docids"].tolist(), z["vocab"].tolist(), n_batches,
                z["batch_occ"], radix_buckets=radix,
                doc_lens=z["doc_lens"] if "doc_lens" in z.files else None)
    except _CORRUPT_NPZ:
        return None


def _batch_pairs_done(spill_dir: str, b: int, num_shards: int,
                      positions: bool = False,
                      validate: bool = False) -> bool:
    """Whether batch b's per-shard pair (and position) spills all exist.
    With `validate` (the resume path), each spill is additionally read in
    full — a corrupt spill deletes the whole batch's spills and reports
    the batch as not done, so ONLY that batch recomputes (the smallest
    recovery scope a pair-spill corruption allows)."""
    paths = [os.path.join(spill_dir, f"pairs-{s:03d}-{b:05d}.npz")
             for s in range(num_shards)]
    if positions:
        paths += [os.path.join(spill_dir, f"pos-{s:03d}-{b:05d}.npz")
                  for s in range(num_shards)]
    if not all(os.path.exists(p) for p in paths):
        return False
    if validate and not all(_readable_npz(p) for p in paths):
        from ..utils.report import recovery_counters

        recovery_counters().incr("spill_integrity_discards")
        logger.warning("batch %d has a corrupt pair/position spill; "
                       "recomputing the batch", b)
        for p in paths:
            if os.path.exists(p):
                os.unlink(p)
        return False
    return True


def reduce_shard_spills(spill_dir: str, index_dir: str, row: int,
                        n_batches: int, vocab_size: int,
                        shard_of: np.ndarray,
                        positions: bool = False) -> tuple[np.ndarray, int]:
    """Pass 3 for ONE term shard: concatenate its pair spills, lexsort into
    the reference posting order (term asc, tf desc, doc asc), write the
    part file. Returns (rdf int32 [V], num_pairs). Shared by the
    single-process streaming build and the multi-host build so the
    byte-identical-artifacts guarantee rests on one implementation.

    A pure sort, NOT a merge: batches partition whole documents, so a
    (term, doc) pair exists in exactly one batch and per-batch combining
    already produced final tfs. The spills start and end on host disk, so
    a host lexsort beats shipping hundreds of MB through the device and
    back on any backend.

    With `positions`, each batch's pos-RRR-BBBBB.npz spill (runs aligned
    with that batch's pair spill rows) rides the same permutation, and
    the shard's positions file is written BEFORE the part file — part
    existence is the resume marker, so positions must never trail it."""
    with obs_trace("build.spill_reduce", shard=row, batches=n_batches):
        rdf, npairs = _reduce_shard_spills(spill_dir, index_dir, row,
                                           n_batches, vocab_size, shard_of,
                                           positions)
    # JobTracker progress: one reduce "task" done (the caller declared
    # the phase total = its shard count)
    report_progress("pass3_reduce", advance=1, shards_reduced=1,
                    pairs=npairs)
    return rdf, npairs


def _reduce_shard_spills(spill_dir, index_dir, row, n_batches, vocab_size,
                         shard_of, positions):
    terms, docs, tfs = [], [], []
    deltas, rlens = [], []
    for b in range(n_batches):
        path = os.path.join(spill_dir, f"pairs-{row:03d}-{b:05d}.npz")
        with np.load(path) as z:
            terms.append(z["term"])
            docs.append(z["doc"])
            tfs.append(z["tf"])
        if positions:
            with np.load(os.path.join(
                    spill_dir, f"pos-{row:03d}-{b:05d}.npz")) as pz:
                deltas.append(pz["pos_delta"])
                rlens.append(np.diff(pz["pos_indptr"]))
    t = np.concatenate(terms) if terms else np.zeros(0, np.int32)
    d = np.concatenate(docs) if docs else np.zeros(0, np.int32)
    w = np.concatenate(tfs) if tfs else np.zeros(0, np.int32)
    # tf negated as int64: spills may ride as uint16
    order = np.lexsort((d, -w.astype(np.int64), t))
    t, d, w = t[order], d[order], w[order]
    rdf = np.bincount(t, minlength=vocab_size).astype(np.int32)
    tids = np.nonzero(shard_of == row)[0].astype(np.int32)
    lens = rdf[tids].astype(np.int64)
    local_indptr = np.concatenate([[0], np.cumsum(lens)])
    if positions:
        from .positions import positions_name, realign_runs

        all_delta = (np.concatenate(deltas) if deltas
                     else np.zeros(0, np.int32))
        all_len = (np.concatenate(rlens).astype(np.int64) if rlens
                   else np.zeros(0, np.int64))
        starts = np.concatenate([[0], np.cumsum(all_len)])[:-1]
        out_indptr, gather = realign_runs(starts[order], all_len[order])
        fmt.savez_atomic(
            os.path.join(index_dir, positions_name(row)),
            pos_indptr=out_indptr.astype(np.int64),
            pos_delta=all_delta[gather].astype(np.int32))
    fmt.save_shard(index_dir, row, term_ids=tids, indptr=local_indptr,
                   pair_doc=d, pair_tf=w, df=rdf[tids])
    return rdf, len(t)


def write_radix_spills(spill_dir: str, b: int, ids: np.ndarray,
                       lengths: np.ndarray, doc_ofs: int,
                       radix_buckets: int) -> list[str]:
    """Radix-partition one tokenize batch's occurrence stream by
    destination bucket (temp_id % B — stable for the whole build because
    temp ids are pinned by the pass-1 manifest) and spill each bucket's
    share atomically. Documents ride as RUNS (global doc ordinal + run
    length): partitioning preserves emission order, so one doc's
    occurrences within a bucket stay contiguous, and the run encoding
    both shrinks the spill and feeds build_postings_packed's upload-slim
    device reconstruction in pass 2. Returns the spill CRCs in bucket
    order (the manifest's verification order)."""
    from ..obs import get_registry

    reg = get_registry()
    flat_ord = np.repeat(
        np.arange(doc_ofs, doc_ofs + len(lengths), dtype=np.int64),
        lengths.astype(np.int64)).astype(np.int32)
    bucket = ids % np.int32(radix_buckets)
    # counting-sort the occurrences by bucket: one stable O(n) partition
    # pass instead of B boolean scans over the whole batch
    order = np.argsort(bucket, kind="stable")
    ids_p = ids[order].astype(np.int32)
    ord_p = flat_ord[order]
    counts = np.bincount(bucket, minlength=radix_buckets)
    starts = np.concatenate([[0], np.cumsum(counts)])
    crcs = []
    for r in range(radix_buckets):
        lo, hi = int(starts[r]), int(starts[r + 1])
        t_r, o_r = ids_p[lo:hi], ord_p[lo:hi]
        if len(o_r):
            run_start = np.concatenate(
                [[0], np.flatnonzero(np.diff(o_r) != 0) + 1])
            run_docs = o_r[run_start]
            run_lens = np.diff(np.concatenate(
                [run_start, [len(o_r)]])).astype(np.int32)
        else:
            run_docs = np.zeros(0, np.int32)
            run_lens = np.zeros(0, np.int32)
        path = os.path.join(spill_dir, radix_spill_name(r, b))
        crcs.append(fmt.savez_atomic(path, term=t_r, doc=run_docs,
                                     len=run_lens))
        reg.incr("build.radix.bucket_spills")
        reg.incr("build.radix.spill_bytes", int(os.path.getsize(path)))
    return crcs


def write_bucketed_shard(spill_dir: str, index_dir: str, row: int,
                         num_buckets: int, vocab_size: int, *,
                         offset_of: np.ndarray | None = None
                         ) -> tuple[np.ndarray, int]:
    """Pass 3 for ONE term shard in the BUCKET-SEGMENTED layout
    (TPU_IR_RADIX_PARTS): each pass-2 bucket spill already holds final
    postings in final per-term order (term asc within its bucket, tf
    desc / doc asc within each term — the device reduce's lexsort), so
    the part file is the CONCATENATION of its bucket segments and the
    global per-shard sort is skipped entirely. Term ids are unique
    across the part (a term lives in exactly one bucket) but only
    ascending within each segment; readers assemble by term id, not
    file order, so the layout round-trips through Scorer/_assemble_csr,
    verify, inspect and migrate-index unchanged — but the part BYTES
    (and the dictionary) differ from the canonical layout.

    `offset_of` (int64 [V], optional) is filled with each term's
    postings start inside its part — what write_dictionary must record
    for this layout."""
    with obs_trace("build.spill_reduce", shard=row, buckets=num_buckets,
                   segmented=True):
        tids_l, df_l, doc_l, tf_l = [], [], [], []
        for r in range(num_buckets):
            path = os.path.join(spill_dir, f"pairs-{row:03d}-{r:05d}.npz")
            with np.load(path) as z:
                t, d, w = z["term"], z["doc"], z["tf"]
            if not len(t):
                continue
            # t ascends within the spill, so unique() preserves order
            ut, counts = np.unique(t, return_counts=True)
            tids_l.append(ut.astype(np.int32))
            df_l.append(counts.astype(np.int32))
            doc_l.append(d)
            tf_l.append(w)
        tids = (np.concatenate(tids_l) if tids_l
                else np.zeros(0, np.int32))
        df_part = (np.concatenate(df_l) if df_l
                   else np.zeros(0, np.int32))
        indptr = np.concatenate(
            [[0], np.cumsum(df_part, dtype=np.int64)])
        pair_doc = (np.concatenate(doc_l) if doc_l
                    else np.zeros(0, np.int32))
        pair_tf = (np.concatenate(tf_l) if tf_l
                   else np.zeros(0, np.int32))
        fmt.save_shard(index_dir, row, term_ids=tids, indptr=indptr,
                       pair_doc=pair_doc, pair_tf=pair_tf, df=df_part)
        if offset_of is not None:
            offset_of[tids] = indptr[:-1]
        rdf = np.zeros(vocab_size, np.int32)
        rdf[tids] = df_part
    return rdf, len(pair_doc)


def run_pass1_spills(tok, spill_dir: str, batch_docs: int, store: bool,
                     report, *, text_path_fn, batch_stat,
                     radix_buckets: int = 0):
    """THE pass-1 spill loop (chunked tokenize -> batch -> atomic spill),
    shared by the single-process streaming build and the multi-host build
    so the crash-resume invariants live exactly once:

    - text spill FIRST: a batch's token/rpairs spills are its resume
      marker, so its text twin must never trail them (index/docstore.py
      assembles the store from text spills after pass 3 — zero extra
      corpus reads);
    - the CALLER writes its manifest LAST (atomic) to certify the pass.

    `text_path_fn(b)` names batch b's text spill (the two builders place
    them differently); `batch_stat(ids, lengths)` is the per-batch int
    recorded for pass 2 (total occurrences single-process; the
    per-device occupancy cap multi-host).

    With `radix_buckets` > 0 each batch spills as per-bucket (term, doc
    run) pair files instead of one token spill (write_radix_spills), and
    the partition+spill work runs on a pipeline thread one batch behind
    the tokenizer (prefetch_iter) — tokenize N+1 overlaps spill-write N.

    Returns (docids, vocab_list, n_batches, stats, spill_crcs,
    doc_lens) — the CRCs go in the caller's manifest so a resume can
    verify the spills' bytes; doc_lens (int64, corpus order) is every
    doc's occurrence count, which radix pass 2 can no longer recover
    from token spills."""
    from ..utils.transfer import prefetch_iter
    from .docstore import write_text_spill

    acc_ids: list[np.ndarray] = []
    acc_lens: list[np.ndarray] = []
    acc_texts: list[bytes] = []
    acc_docids: list[str] = []
    acc_docs = 0
    all_docids: list[str] = []
    stats: list[int] = []
    spill_crcs: list[str] = []
    all_lens: list[np.ndarray] = []
    n_written = 0

    def spill_batch(b: int, ids, lengths, texts, docids, doc_ofs):
        """Write batch b's spills (consumer side of the pipeline)."""
        nonlocal n_written
        with obs_trace("build.spill", batch=b, docs=len(lengths),
                       radix=radix_buckets):
            if store:
                write_text_spill(text_path_fn(b), texts, docids)
            if radix_buckets:
                spill_crcs.extend(write_radix_spills(
                    spill_dir, b, ids, lengths, doc_ofs, radix_buckets))
            else:
                spill = os.path.join(spill_dir, f"tokens-{b:05d}.npz")
                # the returned CRC is computed pre-rename, so post-write
                # corruption of the spill can never match the manifest
                # that records it
                spill_crcs.append(fmt.savez_atomic(spill, ids=ids,
                                                   lengths=lengths))
        report_progress("pass1_tokenize", advance=1,
                        docs_parsed=len(lengths),
                        spills_written=max(radix_buckets, 1) + int(store),
                        occurrences=len(ids))
        n_written = b + 1
        faults.maybe_crash("crash.pass1", f"b={b + 1}")

    def batches():
        """Producer: drain the tokenizer into batch-sized arrays. Yields
        (b, ids, lengths, texts, docids, doc_ofs) where doc_ofs is the
        global ordinal of the batch's first document."""
        nonlocal acc_docs
        state = {"b": 0, "doc_ofs": 0}

        def flush():
            nonlocal acc_docs
            if not acc_docs:
                return None
            ids = np.concatenate(acc_ids)
            lengths = np.concatenate(acc_lens)
            all_lens.append(lengths.astype(np.int64))
            stats.append(int(batch_stat(ids, lengths)))
            out = (state["b"], ids, lengths, list(acc_texts),
                   list(acc_docids), state["doc_ofs"])
            acc_ids.clear()
            acc_lens.clear()
            acc_texts.clear()
            acc_docids.clear()
            acc_docs = 0
            state["b"] += 1
            state["doc_ofs"] += len(lengths)
            return out

        for delta in tok.deltas():
            if store:
                docids_d, ids_d, lens_d, texts_d = delta
                acc_texts.extend(texts_d)
                acc_docids.extend(docids_d)
            else:
                docids_d, ids_d, lens_d = delta
            report.incr("Count.DOCS", len(docids_d))
            all_docids.extend(docids_d)
            acc_ids.append(ids_d)
            acc_lens.append(lens_d)
            acc_docs += len(docids_d)
            if acc_docs >= batch_docs:
                item = flush()
                if item is not None:
                    yield item
        item = flush()
        if item is not None:
            yield item

    it = batches()
    if radix_buckets:
        # double-buffered: the tokenizer (producer thread) runs one
        # pipeline-depth ahead of the partition+spill consumer
        it = prefetch_iter(it, name="pass1-spill")
    try:
        for args in it:
            spill_batch(*args)
        vocab_list = tok.vocab()
    finally:
        # close the pipeline BEFORE the tokenizer: generator close waits
        # for the producer thread to exit, so tok.close() can never free
        # the native corpus handle while the thread is still inside
        # tok.deltas() (a consumer-side crash would otherwise race a
        # C++ use-after-free instead of surfacing the structured error)
        it.close()
        tok.close()
    doc_lens = (np.concatenate(all_lens) if all_lens
                else np.zeros(0, np.int64))
    return (all_docids, vocab_list, n_written, stats, spill_crcs,
            doc_lens)


def build_index_streaming(corpus_paths, index_dir,
                          **kwargs) -> fmt.IndexMetadata:
    """The public streaming build, run as a tracked job: /jobs (and the
    `--track` server) shows pass-1/2/3 progress live with the JobTracker
    counters (docs parsed, spills written, shards reduced), and a build
    that dies marks its job failed instead of leaving a ghost. All
    parameters pass through to the implementation below (they are
    keyword-only there)."""
    name = os.path.basename(os.path.normpath(os.fspath(index_dir)))
    with tracked("build", f"streaming:{name}",
                 phases=("pass1_tokenize", "pass2_combine",
                         "pass3_reduce", "finalize"),
                 config={"k": kwargs.get("k", 1),
                         "spmd_devices": kwargs.get("spmd_devices"),
                         "num_shards": kwargs.get("num_shards"),
                         "radix_buckets": kwargs.get("radix_buckets"),
                         "streaming": True}):
        return _build_index_streaming(corpus_paths, index_dir, **kwargs)


def _build_index_streaming(
    corpus_paths: Sequence[str] | str,
    index_dir: str,
    *,
    k: int = 1,
    chargram_ks: Iterable[int] = (2, 3),
    num_shards: int = 10,
    # 50k (was 20k): device time is batch-size-neutral (measured, NOTES
    # r2) but every batch pays fixed dispatch/fetch round trips — fewer,
    # larger batches cut that fixed cost 2.5x at 1M docs. Memory per
    # batch stays ~tens of MB.
    batch_docs: int = 50_000,
    compute_chargrams: bool = True,
    keep_spills: bool = False,
    spmd_devices: int | None = None,
    overwrite: bool = False,
    positions: bool = False,
    store: bool = False,
    radix_buckets: int | None = None,
    radix_parts: bool | None = None,
    tokenize_procs: int | None = None,
) -> fmt.IndexMetadata:
    from ..utils import envvars

    if isinstance(corpus_paths, (str, os.PathLike)):
        corpus_paths = [corpus_paths]
    chargram_ks = list(chargram_ks)
    if radix_buckets is None:
        radix_buckets = envvars.get_int("TPU_IR_RADIX_BUCKETS")
    radix_buckets = int(radix_buckets or 0)
    if radix_buckets and positions:
        # position runs need each doc's flat token order, which the
        # radix partition destroys; the legacy per-batch combine keeps it
        logger.warning("radix partitioning is unavailable with "
                       "positions=True; using the per-batch pass 2")
        radix_buckets = 0
    if radix_parts is None:
        radix_parts = envvars.get_bool("TPU_IR_RADIX_PARTS")
    radix_parts = bool(radix_parts) and radix_buckets > 0
    if spmd_devices:
        # each device's reduce output IS one term shard (Hadoop's
        # reducer-count = partition-count identity)
        num_shards = spmd_devices
    os.makedirs(index_dir, exist_ok=True)
    if overwrite:
        for name in os.listdir(index_dir):
            if name != fmt.JOBS_DIR:
                p = os.path.join(index_dir, name)
                if os.path.isfile(p):
                    os.unlink(p)
                elif name == "_spill":
                    shutil.rmtree(p, ignore_errors=True)
    if fmt.artifact_exists(index_dir, fmt.METADATA):
        return fmt.IndexMetadata.load(index_dir)

    from .. import enable_compilation_cache

    enable_compilation_cache()

    # ---- crash resume: a leftover spill dir from an interrupted build is
    # reusable when its pass-1 manifest matches this exact config; stale or
    # mismatched state (and any half-written artifacts) is discarded ----
    spill_dir = os.path.join(index_dir, "_spill")
    # radix_parts is part of the signature too: a resume across a
    # TPU_IR_RADIX_PARTS flip would otherwise keep some shards in one
    # layout, rebuild the rest in the other, and write a dictionary
    # whose offsets are wrong for every resumed-shard term
    sig = _config_sig(corpus_paths, k, num_shards, spmd_devices, positions,
                      store, radix_buckets=radix_buckets,
                      radix_parts=radix_parts)
    resume_state = _load_resume_state(spill_dir, sig)
    if resume_state is None and os.path.isdir(spill_dir):
        shutil.rmtree(spill_dir, ignore_errors=True)
    if resume_state is None:
        # no trustworthy spills -> any part/side files are from a crashed
        # or differently-configured run; clear them so pass 3 cannot
        # mistake them for its own completed output
        for name in os.listdir(index_dir):
            if name != fmt.JOBS_DIR:
                p = os.path.join(index_dir, name)
                if os.path.isfile(p):
                    os.unlink(p)
    os.makedirs(spill_dir, exist_ok=True)
    report = JobReport("TermKGramDocIndexer", config={
        "k": k, "num_shards": num_shards, "streaming": True,
        "batch_docs": batch_docs, "spmd_devices": spmd_devices,
        "store": store, "radix_buckets": radix_buckets,
        "radix_parts": radix_parts, "resumed": resume_state is not None})

    # ---- pass 1: chunked tokenize -> spill temp-id batches ----
    # (each spill batch covers a contiguous docid range; pass 2 walks the
    # same order, so batch b's docids are all_docids[ofs : ofs + len(lens)])
    if resume_state is not None:
        all_docids = resume_state.docids
        vocab_list = resume_state.vocab
        n_batches = resume_state.n_batches
        batch_occ = resume_state.batch_occ
        all_doc_lens = resume_state.doc_lens
        report.incr("Count.DOCS", len(all_docids))
        report.set_counter("pass1_resumed_batches", n_batches)
        report_progress("pass1_tokenize", advance=n_batches,
                        total=n_batches, docs_parsed=len(all_docids),
                        resumed_batches=n_batches)
    else:
        tok = make_chunked_tokenizer(corpus_paths, k=k, with_text=store,
                                     procs=tokenize_procs)
        # which analyzer ran is part of the build record: the Python
        # fallback is ~10x slower and must never pass unnoticed
        report.config["tokenizer"] = (
            "native" if isinstance(tok, NativeChunkedTokenizer) else "python")
        with report.phase("pass1_tokenize"):
            (all_docids, vocab_list, n_batches, occ_per_batch,
             spill_crcs, all_doc_lens) = run_pass1_spills(
                    tok, spill_dir, batch_docs, store, report,
                    text_path_fn=lambda b: os.path.join(
                        spill_dir, f"text-{b:05d}.npz"),
                    batch_stat=lambda ids, lengths: len(ids),
                    radix_buckets=radix_buckets)
        batch_occ = np.array(occ_per_batch, dtype=np.int64)
        # manifest LAST: its existence certifies pass 1 (docids in corpus
        # order, the native vocab in temp-id order, per-batch occurrence
        # counts, per-doc occurrence counts, the radix bucket count the
        # spills were partitioned by, per-spill CRCs) so a restart never
        # re-tokenizes — and never trusts a spill whose bytes rotted
        # under it
        fmt.savez_atomic(
            os.path.join(spill_dir, PASS1_MANIFEST), sig=sig,
            docids=np.array(all_docids, dtype=np.str_),
            vocab=np.array(vocab_list, dtype=np.str_),
            n_batches=np.int64(n_batches), batch_occ=batch_occ,
            radix_buckets=np.int64(radix_buckets),
            doc_lens=np.asarray(all_doc_lens, dtype=np.int64),
            spill_crc=np.array(spill_crcs, dtype=np.str_))

    num_docs = len(all_docids)
    if num_docs == 0:
        raise ValueError(f"no <DOC> records found in {corpus_paths}")

    # ---- between passes: docno mapping + vocab (temp -> sorted rank) ----
    with report.phase("docno_mapping"):
        mapping = DocnoMapping.build(all_docids)
        if len(mapping) != num_docs:
            raise ValueError("duplicate docids in corpus")
        mapping.save(os.path.join(index_dir, fmt.DOCNOS))
        sorted_docids = np.array(mapping.docids, dtype=np.str_)
    with report.phase("vocab"):
        vocab_arr = np.array(vocab_list, dtype=np.str_)
        order = np.argsort(vocab_arr)
        rank = np.empty(len(order), np.int32)
        rank[order] = np.arange(len(order), dtype=np.int32)
        vocab = Vocab(vocab_arr[order].tolist())
        vocab.save(os.path.join(index_dir, fmt.VOCAB))
        v = len(vocab)
        report.set_counter("reduce_output_groups", v)

    # ---- pass 2: combine per batch (legacy) or reduce per radix bucket,
    # spill pairs per term shard ----
    doc_len = np.zeros(num_docs + 1, np.int64)
    occurrences = int(batch_occ.sum())
    resuming = resume_state is not None

    if radix_buckets:
        # every doc's final docno, indexed by its global ordinal (ONE
        # vectorized searchsorted for the whole corpus instead of one
        # per batch); with bucketed pair spills pass 2 never re-walks
        # token spills, so doc_len comes straight from the manifest-
        # recorded per-doc occurrence counts
        docno_of = (np.searchsorted(
            sorted_docids, np.array(all_docids, dtype=np.str_)) + 1
        ).astype(np.int32)
        doc_len[docno_of] = np.asarray(all_doc_lens, dtype=np.int64)

    def iter_buckets():
        """Radix pass-2 input: (r, term_ids, docnos, run_lens) per
        bucket that still needs its per-shard pair spills — the same
        tuple shape iter_batches yields, so ONE device loop serves both
        (documents ride as runs; build_postings_packed re-expands them
        on device). Runs on the prefetch thread: the host reads/remaps
        bucket N+1 while the device reduces bucket N.

        Resume: a bucket whose pass-2 spills all exist is complete
        (atomic writes) and is skipped without reading its inputs;
        validation quarantines a corrupt pass-2 spill's WHOLE BUCKET
        only — the smallest recovery scope the layout allows. A corrupt
        pass-1 rpairs spill cannot be rebuilt without re-tokenizing and
        surfaces as one structured IntegrityError instead."""
        for r in range(radix_buckets):
            done = resuming and _batch_pairs_done(
                spill_dir, r, num_shards, validate=True)
            if done:
                report.incr("pass2_resumed_buckets", 1)
                report_progress("pass2_combine", advance=1,
                                resumed_buckets=1)
                continue
            terms, rdocs, rlens = [], [], []
            for b in range(n_batches):
                spill = os.path.join(spill_dir, radix_spill_name(r, b))
                try:
                    with np.load(spill) as z:
                        terms.append(z["term"])
                        rdocs.append(z["doc"])
                        rlens.append(z["len"])
                except _CORRUPT_NPZ as e:
                    raise faults.IntegrityError(
                        spill, f"bucketed pair spill unreadable ({e}); "
                        "re-run the build — the restart re-tokenizes "
                        "the corpus") from e
            t = (rank[np.concatenate(terms)] if terms
                 else np.zeros(0, np.int32))
            d = (docno_of[np.concatenate(rdocs)] if rdocs
                 else np.zeros(0, np.int32))
            ln = (np.concatenate(rlens).astype(np.int32) if rlens
                  else np.zeros(0, np.int32))
            yield r, t, d, ln

    def iter_batches():
        """Yield (b, term_ids, docnos, lengths) per spill batch that still
        needs its pair spills; maintains doc_len as it walks. On resume, a
        batch whose per-shard pair spills all exist is complete (they are
        written atomically) and is skipped without reading its token ids —
        only `lengths` loads, to rebuild doc_len."""
        ofs = 0
        for b in range(n_batches):
            spill = os.path.join(spill_dir, f"tokens-{b:05d}.npz")
            try:
                with np.load(spill) as z:
                    lengths = z["lengths"]
                    done = resuming and _batch_pairs_done(
                        spill_dir, b, num_shards, positions, validate=True)
                    flat = None if done else z["ids"]
            except _CORRUPT_NPZ as e:
                # a token spill that rotted between its write and this
                # read: surface ONE structured error (not a zip
                # traceback); the restart's manifest-CRC check then
                # discards the pass-1 state and re-tokenizes
                raise faults.IntegrityError(
                    spill, f"token spill unreadable ({e}); re-run the "
                    "build — the restart re-tokenizes the corpus") from e
            docids = np.array(all_docids[ofs : ofs + len(lengths)],
                              dtype=np.str_)
            ofs += len(lengths)
            docnos = (np.searchsorted(sorted_docids, docids) + 1).astype(
                np.int32)
            # a doc's length IS its post-analysis occurrence count
            doc_len[docnos] = lengths
            if done:
                report.incr("pass2_resumed_batches", 1)
                report_progress("pass2_combine", advance=1,
                                resumed_batches=1)
                continue
            term_ids = rank[flat]
            if positions:
                # position runs depend only on host data — spill them at
                # dispatch time, overlapping the device program. The run
                # rows align with this batch's pair spill rows (same
                # (term asc, tf desc, doc asc) order on both sides).
                from .positions import batch_position_runs, split_runs_by_shard

                rt, pi_, pd_ = batch_position_runs(term_ids, docnos,
                                                   lengths)
                for s_, indptr_, delta_ in split_runs_by_shard(
                        rt, pi_, pd_, num_shards):
                    fmt.savez_atomic(
                        os.path.join(spill_dir,
                                     f"pos-{s_:03d}-{b:05d}.npz"),
                        pos_indptr=indptr_, pos_delta=delta_)
            yield b, term_ids, docnos, lengths

    def pass2_single_device(batch_iter, unit="batches"):
        # depth-1 dispatch/collect pipeline: batch b+1's host prep + device
        # program overlap batch b's D2H copies; the pair columns are sliced
        # + narrowed on device before the copy (see builder.py — D2H is
        # on the critical path). In radix mode the iterator additionally
        # runs on a prefetch thread, so disk reads + temp-id remaps for
        # item N+1 overlap the device reduce of item N AND the D2H
        # collect of item N-1 — the double-buffered pipeline.
        #
        # Its parts are report phases nested inside pass2_combine (which
        # keeps timing the whole): pass2_upload (padding + uploads),
        # pass2_device_wait (the waits on device programs: this item's
        # df, then its shrink — queued behind the NEXT item's group-by,
        # dispatched before this collect), pass2_fetch (the pair copy to
        # the host) and pass2_spill (term ids, per-shard split, spill
        # writes).
        use16 = v < int(PAD_TERM_U16)
        buckets = unit == "buckets"

        def collect_batch(b, p, tf_max, t0):
            with report.phase("pass2_device_wait"):
                df_b, tfm = fetch_to_host(p.df, tf_max)
                npairs = int(df_b.sum())
                shrunk = jax.block_until_ready(shrink_pairs(
                    p.pair_doc, p.pair_tf, npairs, num_docs=num_docs,
                    tf_max=int(tfm)))
            with report.phase("pass2_fetch"):
                pd, ptf = fetch_to_host(*shrunk)
            with report.phase("pass2_spill"):
                pt = pair_term_from_df(df_b)
                pd = pd[:npairs]
                ptf = ptf[:npairs]
                shard = pt % num_shards
                for s in range(num_shards):
                    sel = shard == s
                    fmt.savez_atomic(
                        os.path.join(spill_dir,
                                     f"pairs-{s:03d}-{b:05d}.npz"),
                        term=pt[sel], doc=pd[sel], tf=ptf[sel])
            report_progress("pass2_combine", advance=1,
                            spills_written=num_shards, pairs=npairs)
            if buckets:
                from ..obs import get_registry

                reg = get_registry()
                reg.observe("build.radix.bucket_pairs", float(npairs))
                reg.observe("build.radix.bucket_s",
                            time.perf_counter() - t0)
            faults.maybe_crash("crash.pass2", f"b={b}")

        pending = None
        for b, term_ids, docnos, lengths in batch_iter:
            t0 = time.perf_counter()
            with report.phase("pass2_upload"):
                cap = _round_cap(len(term_ids))
                t_pad = np.full(cap, PAD_TERM_U16 if use16 else PAD_TERM,
                                np.uint16 if use16 else np.int32)
                t_pad[: len(term_ids)] = term_ids
                # docnos/lengths are padded to a bucketed doc capacity
                # (zero-length repeats are no-ops) so batches of similar
                # size share one compiled program shape; batches can
                # overshoot batch_docs by up to one tokenizer chunk
                doc_cap = _round_cap(len(lengths), 1 << 14)
                d_pad = np.zeros(doc_cap, np.int32)
                l_pad = np.zeros(doc_cap, np.int32)
                d_pad[: len(docnos)] = docnos
                l_pad[: len(docnos)] = lengths
                args = (jnp.asarray(t_pad), jnp.asarray(d_pad),
                        jnp.asarray(l_pad))
            p = build_postings_packed_jit(*args, vocab_size=v,
                                          num_docs=num_docs)
            tf_max = jnp.max(p.pair_tf)
            for a in (p.df, tf_max):
                a.copy_to_host_async()
            if pending is not None:
                collect_batch(*pending)
            pending = (b, p, tf_max, t0)
        if pending is not None:
            collect_batch(*pending)

    def pass2_spmd():
        # the Hadoop pipeline proper: doc-dealt map shards, combiner +
        # all_to_all shuffle + term-shard reduce in one jit per batch
        # (parallel/sharded_build.py), each device's reduced output
        # spilling straight to its term shard's file. Streamed input +
        # mesh shuffle is how scale and distribution compose.
        from ..parallel import make_mesh, sharded_build_postings
        from ..parallel.sharded_build import deal_occurrences

        s = spmd_devices
        mesh = make_mesh(s)
        for b, term_ids, docnos, lengths in iter_batches():
            flat_doc = np.repeat(docnos, lengths.astype(np.int64)).astype(
                np.int32)
            t_arr, d_arr, dps = deal_occurrences(term_ids, flat_doc,
                                                 docnos, s)
            out = sharded_build_postings(
                t_arr, d_arr, dps, vocab_size=v, total_docs=num_docs,
                mesh=mesh)
            # shrink + narrow ON DEVICE before the D2H copy, like the
            # single-device path: the [S, C] result arrays are padded to
            # the worst-case capacity and fetching them whole moves ~S x
            # the real bytes over the transport that owns this phase
            npairs, tf_max = fetch_to_host(out.num_pairs,
                                           jnp.max(out.pair_tf))
            valid = int(npairs.max()) if len(npairs) else 1
            pt, pd, ptf = fetch_to_host(
                shrink_rows_for_fetch(out.pair_term, valid,
                                      dtype=narrow_uint(v - 1),
                                      valid_rows=out.num_pairs),
                shrink_rows_for_fetch(out.pair_doc, valid,
                                      dtype=narrow_uint(num_docs),
                                      valid_rows=out.num_pairs),
                shrink_rows_for_fetch(out.pair_tf, valid,
                                      dtype=narrow_uint(int(tf_max)),
                                      valid_rows=out.num_pairs))
            for sh in range(s):
                n_sh = int(npairs[sh])
                fmt.savez_atomic(
                    os.path.join(spill_dir, f"pairs-{sh:03d}-{b:05d}.npz"),
                    term=pt[sh][:n_sh], doc=pd[sh][:n_sh],
                    tf=ptf[sh][:n_sh])
            report_progress("pass2_combine", advance=1, spills_written=s,
                            pairs=int(npairs.sum()))
            faults.maybe_crash("crash.pass2", f"b={b}")

    def pass2_spmd_radix():
        # buckets partitioned ACROSS devices: rounds of S buckets, each
        # device running the whole local reduce for its own bucket (no
        # collective — a bucket's pairs never leave the device that
        # reduced them) with donated input buffers (the SNIPPETS pjit
        # donation pattern: the occurrence upload is dead after the
        # reduce consumes it, so XLA reuses its pages for the output).
        from ..parallel import make_mesh
        from ..parallel.sharded_build import radix_bucket_reduce

        s = spmd_devices
        mesh = make_mesh(s)
        use16 = v < int(PAD_TERM_U16)
        round_items: list = []

        def reduce_round(items):
            t_cap = _round_cap(max(len(t_) for _, t_, _, _ in items))
            d_cap = _round_cap(max(len(l_) for _, _, _, l_ in items),
                               1 << 14)
            t_arr = np.full((s, t_cap),
                            PAD_TERM_U16 if use16 else PAD_TERM,
                            np.uint16 if use16 else np.int32)
            d_arr = np.zeros((s, d_cap), np.int32)
            l_arr = np.zeros((s, d_cap), np.int32)
            for i, (_, t_, d_, l_) in enumerate(items):
                t_arr[i, : len(t_)] = t_
                d_arr[i, : len(d_)] = d_
                l_arr[i, : len(l_)] = l_
            out = radix_bucket_reduce(t_arr, d_arr, l_arr, vocab_size=v,
                                      total_docs=num_docs, mesh=mesh)
            npairs, tf_max = fetch_to_host(out.num_pairs,
                                           jnp.max(out.pair_tf))
            valid = int(npairs.max()) if len(npairs) else 1
            pt, pd, ptf = fetch_to_host(
                shrink_rows_for_fetch(out.pair_term, valid,
                                      dtype=narrow_uint(v - 1),
                                      valid_rows=out.num_pairs),
                shrink_rows_for_fetch(out.pair_doc, valid,
                                      dtype=narrow_uint(num_docs),
                                      valid_rows=out.num_pairs),
                shrink_rows_for_fetch(out.pair_tf, valid,
                                      dtype=narrow_uint(int(tf_max)),
                                      valid_rows=out.num_pairs))
            from ..obs import get_registry

            reg = get_registry()
            for i, (r, _, _, _) in enumerate(items):
                if r < 0:  # tail-round pad row, owns no bucket
                    continue
                n_r = int(npairs[i])
                t_row = pt[i][:n_r].astype(np.int32)
                d_row, w_row = pd[i][:n_r], ptf[i][:n_r]
                shard = t_row % num_shards
                for sh in range(num_shards):
                    sel = shard == sh
                    fmt.savez_atomic(
                        os.path.join(spill_dir,
                                     f"pairs-{sh:03d}-{r:05d}.npz"),
                        term=t_row[sel], doc=d_row[sel], tf=w_row[sel])
                reg.observe("build.radix.bucket_pairs", float(n_r))
                report_progress("pass2_combine", advance=1,
                                spills_written=num_shards, pairs=n_r)
                faults.maybe_crash("crash.pass2", f"b={r}")

        from ..utils.transfer import prefetch_iter

        for item in prefetch_iter(iter_buckets(), name="bucket-read"):
            round_items.append(item)
            if len(round_items) == s:
                reduce_round(round_items)
                round_items = []
        if round_items:
            # tail round: pad to the mesh width with empty buckets
            while len(round_items) < s:
                round_items.append((-1, np.zeros(0, np.int32),
                                    np.zeros(0, np.int32),
                                    np.zeros(0, np.int32)))
            reduce_round(round_items)

    report_progress("pass2_combine", total=radix_buckets or n_batches,
                    unit="buckets" if radix_buckets else "batches")
    with report.phase("pass2_combine"):
        if radix_buckets and spmd_devices:
            pass2_spmd_radix()
        elif radix_buckets:
            from ..utils.transfer import prefetch_iter

            pass2_single_device(
                prefetch_iter(iter_buckets(), name="bucket-read"),
                unit="buckets")
        elif spmd_devices:
            pass2_spmd()
        else:
            pass2_single_device(iter_batches())
    report.set_counter("map_output_records", occurrences)

    # ---- pass 3: per-shard reduce -> part files ----
    # (reduce_shard_spills: pure host sort per shard; the device keeps the
    # role it wins at — the per-batch shuffle+reduce. With radix buckets
    # the "batch" index of the pass-2 spills is the bucket id; with
    # radix_parts the sort is skipped entirely and parts come out
    # bucket-segmented, with the dictionary offsets derived from the
    # actual part layout instead of the canonical term order.)
    n_units = radix_buckets or n_batches
    df = np.zeros(v, np.int32)
    num_pairs_total = 0
    shard_of = fmt.shard_assignment(v, num_shards)
    offset_of_parts = np.zeros(v, np.int64) if radix_parts else None
    report_progress("pass3_reduce", total=num_shards)
    with report.phase("pass3_reduce"):
        for s in range(num_shards):
            # whichever format the crashed run wrote (a resume may run
            # under a different TPU_IR_FORMAT_VERSION pin than the
            # original build — an existing part of EITHER format is
            # this shard's final output)
            part = fmt.part_path(index_dir, s)
            if positions:
                # positions are written before the part, so an existing
                # part implies its positions file too; a missing one
                # (defensive) forces recompute of both, and an UNREADABLE
                # one is quarantined first — resuming over it would bake
                # its corrupt bytes into the metadata checksums and every
                # later phrase query would die on them
                from .positions import positions_name

                ppath = os.path.join(index_dir, positions_name(s))
                if not os.path.exists(ppath):
                    part = ""  # treat as absent
                elif not fmt.readable_npz(ppath):
                    qpath = fmt.quarantine(index_dir, positions_name(s))
                    logger.warning(
                        "corrupt positions file quarantined to %s; "
                        "rebuilding shard %d from its spills", qpath, s)
                    report.incr("Fault.QUARANTINED_PARTS", 1)
                    part = ""
            z = None
            if resuming and part and os.path.exists(part):
                # parts are written atomically and only after every pass-2
                # spill exists, so an existing part IS this shard's final
                # output; recover its df/pair contributions without
                # re-sorting. A part that fails its full read (zipfile
                # CRC-checks every entry) is CORRUPT: quarantine it and
                # rebuild ONLY this shard from its surviving spills —
                # never the whole index.
                try:
                    z = fmt.load_shard(index_dir, s)
                except _CORRUPT_NPZ:
                    qpath = fmt.quarantine(index_dir,
                                           os.path.basename(part))
                    logger.warning(
                        "corrupt part file quarantined to %s; rebuilding "
                        "shard %d from its spills", qpath, s)
                    report.incr("Fault.QUARANTINED_PARTS", 1)
            if z is not None:
                rdf = np.zeros(v, np.int32)
                rdf[z["term_ids"]] = z["df"]
                npairs = len(z["pair_doc"])
                if offset_of_parts is not None:
                    offset_of_parts[z["term_ids"]] = \
                        np.asarray(z["indptr"][:-1], np.int64)
                report.incr("pass3_resumed_shards", 1)
                report_progress("pass3_reduce", advance=1,
                                resumed_shards=1)
            elif radix_parts:
                rdf, npairs = write_bucketed_shard(
                    spill_dir, index_dir, s, radix_buckets, v,
                    offset_of=offset_of_parts)
                report_progress("pass3_reduce", advance=1,
                                shards_reduced=1, pairs=npairs)
            else:
                rdf, npairs = reduce_shard_spills(
                    spill_dir, index_dir, s, n_units, v, shard_of,
                    positions=positions)
            faults.maybe_crash("crash.pass3", f"s={s}")
            num_pairs_total += npairs
            df[:] += rdf
    report.set_counter("num_pairs", num_pairs_total)

    report_progress("finalize")
    with report.phase("dictionary"):
        np.save(os.path.join(index_dir, fmt.DOCLEN),
                doc_len.astype(np.int32))
        if offset_of_parts is not None:
            # bucket-segmented parts: a term's postings start where its
            # part actually put them, not where the canonical sorted
            # order would — the dictionary must point into the real file
            offset_of = offset_of_parts
        else:
            _, offset_of = fmt.shard_local_offsets(df, num_shards)
        fmt.write_dictionary(index_dir, vocab.terms, shard_of, offset_of)
        dict_report = JobReport("BuildIntDocVectorsForwardIndex")
        dict_report.set_counter("Dictionary.Size", v)
        dict_report.save(os.path.join(index_dir, fmt.JOBS_DIR))

    if store:
        # assemble the document store from the pass-1 TEXT SPILLS — the
        # corpus itself is never re-read (VERDICT r4 next #5; contrast
        # docstore.build_docstore's standalone corpus pass). Arrival
        # order is the pass-1 delta order; each spill carries its own
        # docids, docnos come from the mapping.
        from .docstore import iter_text_spill_docnos, write_docstore

        with report.phase("docstore"):
            def records():
                for b in range(n_batches):
                    yield from iter_text_spill_docnos(
                        os.path.join(spill_dir, f"text-{b:05d}.npz"),
                        sorted_docids)

            stats = write_docstore(index_dir, records(), num_docs)
            report.set_counter("docstore_raw_bytes", stats["raw_bytes"])
            report.set_counter("docstore_stored_bytes",
                               stats["stored_bytes"])

    built_chargrams = bool(compute_chargrams and chargram_ks and k == 1)
    if built_chargrams:
        with report.phase("chargrams"):
            build_chargram_artifacts(index_dir, vocab.terms, chargram_ks)

    # spill removal, then metadata: its checksums over every artifact
    # and the block-max bounds artifact it writes
    with report.phase("finalize"):
        if not keep_spills:
            shutil.rmtree(spill_dir, ignore_errors=True)
        meta = fmt.IndexMetadata(
            num_docs=num_docs, vocab_size=v, k=k, num_shards=num_shards,
            num_pairs=num_pairs_total,
            chargram_ks=chargram_ks if built_chargrams else [],
            version=2 if positions else fmt.FORMAT_VERSION,
            has_positions=bool(positions),
            format_version=fmt.resolve_format_version())
        meta.save_with_checksums(index_dir)
    report.save(os.path.join(index_dir, fmt.JOBS_DIR))
    return meta
