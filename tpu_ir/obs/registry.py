"""TelemetryRegistry: the one process-wide home for counters + histograms.

PR 1–2 grew three separate counter surfaces — `recovery_counters()`,
`serving_counters()` (both utils/report.py) and the fault plan's fire
counts — each with its own snapshot/reset story, none with any latency
distribution. This registry unifies them: every process-wide counter
lives here under a dotted namespace (`recovery.*`, `serving.*`,
`fault.*`), every latency histogram lives here under its span/stage name,
and one `snapshot(reset=...)` is the single scrape surface for
`tpu-ir stats` / `tpu-ir metrics` / the flight recorder. The old
functions survive as thin prefix views (utils/report.py), so existing
callers and the `tpu-ir stats` JSON shape keep working.

Declared names: the registry pre-registers a `fault.<site>` counter for
every fault-injection site threaded through the stack and a latency
histogram for every serving stage and service level, so a failure path
or ladder level with NO telemetry is structurally impossible —
tests/test_obs.py introspects the source for injection sites and the
frontend for levels and asserts both land in the declared sets.

Capture ledger: while a jax profiler capture records, every increment
and observation also adds to per-capture totals (`capture_totals()`),
so the program's own counts and span seconds for the window an xplane
covers can be read next to it.
"""

from __future__ import annotations

import sys
import threading
import uuid

from .histogram import LatencyHistogram, summary_from_counts

# Version of the snapshot dict shape (and of the spooled/allgathered
# serializable form in obs/aggregate.py). Bump on any change a downstream
# parser could trip over; scrapers reject snapshots from a future schema
# instead of mis-parsing them.
SNAPSHOT_SCHEMA = 1

# Every fault-injection site name threaded through the build and serve
# paths (faults.should_fire / maybe_crash / maybe_hang call sites). A new
# site MUST be added here — the registry pre-registers its fire counter,
# and the static-analysis test fails any site found in source but not
# declared (no silently untelemetered failure path).
FAULT_SITES = (
    "spill_write",         # index/format.py: transient spill/part write
    "artifact_truncate",   # index/format.py: torn artifact write
    "crash.builder",       # index/builder.py: death before metadata
    "crash.pass1",         # index/streaming.py: death mid-tokenize
    "crash.pass2",         # index/streaming.py: death mid-postings
    "crash.pass3",         # index/streaming.py: death mid-reduce
    "shuffle_overflow",    # parallel/sharded_build.py: all_to_all drop
    "score.hang",          # search/scorer.py: hung device dispatch
    "score.device_loss",   # search/scorer.py: device lost mid-dispatch
    "tokenize.pool",       # analysis/pool.py: tokenizer pool chunk failure
    # Durable ingest (ISSUE 17) — every declared death point on the
    # write path; the SIGKILL crash-fuzz matrix in tests/test_wal.py
    # kills a child at each one and proves bit-identical recovery.
    "ingest.wal_append",   # index/wal.py: death before a record is framed
    "ingest.wal_torn",     # index/wal.py: death mid-append (torn tail)
    "ingest.wal_retire",   # index/wal.py: death mid WAL-segment retirement
    "ingest.flush_build",  # index/ingest.py: death mid delta-segment build
    "ingest.commit_between",  # index/segments.py: between manifest write
    #                           and the CURRENT pointer rename
    "ingest.merge",        # index/segments.py: death mid-merge, pre-commit
)

# Serving-stage span names (the per-request span tree) — each gets a
# declared latency histogram so `tpu-ir serve-bench` always reports the
# full stage breakdown, observed or not.
REQUEST_STAGES = (
    "admission_wait",  # time from arrival to holding an execution slot
    "ladder",          # service-level decision
    "breaker",         # circuit-breaker consultation
    "dispatch",        # whole device dispatch (deadline window included)
    "kernel",          # one jit'd scoring call (per query block)
    "fallback",        # host-CPU degraded scoring
)

# Service levels the degradation ladder can emit; each gets a
# `request.<level>` end-to-end latency histogram (shed = time-to-shed).
SERVICE_LEVELS = ("full", "no_rerank", "hot_only", "shed")

# Scorer cold/warm-load pipeline stages (ISSUE 5): checksum folding,
# shard reads, CSR assembly, host-to-device streaming. Declared so
# `tpu-ir metrics` and the bench's load breakdown always report the full
# stage set, observed or not; load.h2d pairs with the load.h2d_bytes
# counter for an effective-MB/s readout. load.verify nests inside
# load.read (the CRC folds into the streamed read); load.layout (block
# bounds + tiered layout build on the host, the hot strip's scatter on
# the device) and load.cache_write (rerank norms + serving-cache write,
# cache-miss only) complete the cold load, and `load` spans the whole
# Scorer.load.
LOAD_STAGES = ("load.verify", "load.read", "load.assemble", "load.layout",
               "load.cache_write", "load.h2d", "load.positions")

# Scorer query-path spans, one per batch: the whole search (analysis,
# schedule, dispatch, result assembly, query log), its query analysis,
# the MaxScore hot/cold partition, and the device phrase path's match of
# one block (ops/phrase.py phrase_match, up to its candidate count).
SEARCH_STAGES = ("search", "search.analyze", "search.schedule",
                 "phrase.match")

# Recovery-event counter names (the `recovery.` namespace, incremented
# via utils/report.recovery_counters()). Declared so the lint contract
# pass (TPU303) can reject an increment of an undeclared name — a typo'd
# counter would otherwise silently split its event stream.
RECOVERY_COUNTER_NAMES = (
    "retries", "retry_exhausted", "overflow_retries", "degraded_batches",
    "deadline_expired", "device_loss", "forced_host_batches",
    "integrity_failures", "quarantined", "quarantine_evicted",
    "spill_integrity_discards",
)

# Serving-frontend counter names (the `serving.` namespace; the dynamic
# families served_<level>, shed_<reason>, level_step_<dir> are declared
# as their expansions over SERVICE_LEVELS / shed reasons / directions).
SERVING_COUNTER_NAMES = (
    "submitted", "degraded", "breaker_opened", "breaker_probes",
    "served_breaker_host",
    "served_full", "served_no_rerank", "served_hot_only",
    # result-cache tier (ISSUE 15): requests answered from the
    # frontend's exact-hit cache — no admission slot, no dispatch
    "served_cache",
    "shed_level", "shed_queue_full", "shed_queue_timeout",
    "level_step_down", "level_step_up",
    # live index (ISSUE 12): one frontend published a new generation's
    # scorer (+ coalescer) without dropping in-flight requests
    "generation_swap",
)

# Dispatch sub-stages the device-cost profiler (obs/profiling.py)
# subdivides the scorer's "dispatch" span into (ISSUE 7): tracing +
# lowering, XLA backend compilation, and device execution up to
# block_until_ready — the decomposition of the fixed per-dispatch RTT.
DISPATCH_STAGES = ("dispatch.trace", "dispatch.compile", "dispatch.device")

# Compile-observability counters: every jit compile event through the
# profiling shim, and the subset that re-compiled an already-seen
# abstract signature (the recompile-storm signal).
COMPILE_COUNTER_NAMES = ("compile.count", "compile.recompiles")

# Query-log counters (obs/querylog.py, ISSUE 8): entries recorded into
# the sampled ring, and the subset the slow-query trap force-captured.
QUERYLOG_COUNTER_NAMES = ("querylog.recorded", "querylog.slow")

# Coalescing-scheduler counters (serving/batching.py, ISSUE 9): batches
# that actually packed >1 concurrent query into one padded dispatch, and
# batches flushed with a single occupant (idle arrivals dispatch
# immediately — the solo-latency guarantee).
BATCH_COUNTER_NAMES = ("batch.coalesced", "batch.solo_flush")

# Scatter-gather router counters (serving/router.py, ISSUE 10): the
# one-logical-index-over-N-shard-workers fan-out. requests/served_* and
# shed follow the frontend taxonomy at the ROUTER scope (a routed
# response is exactly one of full/degraded/partial/rejected);
# hedge_fired/hedge_won instrument tail-latency hedging; replica_failed
# and shard_lost count failover events; breaker_opened is the
# per-replica breaker's transition count (the frontend counter of the
# same name is per-process, this one is per-replica-channel).
ROUTER_COUNTER_NAMES = (
    "router.requests", "router.served_full", "router.served_degraded",
    "router.served_partial", "router.shed",
    "router.hedge_fired", "router.hedge_won",
    "router.replica_failed", "router.shard_lost",
    "router.breaker_opened", "router.worker_respawn",
    # live-index rolling upgrades (ISSUE 12): requests whose fan-out saw
    # MORE than one index generation — the router merges only the
    # winning generation's responses and tags the rest missing, so this
    # counts the mixed-generation window's width in requests
    "router.mixed_generation",
)

# Live index subsystem (ISSUE 12): incremental ingest (index/ingest.py),
# tombstone-applying tiered merges (index/segments.py), and the
# zero-downtime generation swap (serving/generation.py). docs_* count
# API-level mutations; flushes/segments_built the delta-segment commits;
# merge.runs one policy-driven compaction step, merge.segments_merged
# its inputs, merge.docs_dropped tombstones physically applied;
# generation.commits every manifest+CURRENT flip.
INGEST_COUNTER_NAMES = (
    "ingest.docs_added", "ingest.docs_updated", "ingest.docs_deleted",
    "ingest.flushes", "ingest.segments_built",
    "merge.runs", "merge.segments_merged", "merge.docs_dropped",
    "generation.commits",
    # Durable ingest (ISSUE 17, index/wal.py): wal_appends one per
    # acknowledged mutation framed into the log, wal_fsyncs the batched
    # durability barriers actually paid (appends/fsyncs is the batching
    # ratio), wal_torn_tail_truncated the mid-append death scars
    # truncated loudly on reopen, wal_segments_retired log segments
    # deleted once a committed watermark fully covered them;
    # replayed the records re-applied past the manifest watermark on
    # writer open (nonzero == a crash was recovered); lease_acquired /
    # lease_takeovers / lease_conflicts the single-writer lease verdicts
    # (takeover = stale-or-dead holder displaced, conflict = a live
    # second writer refused with WriterLeaseHeld).
    "ingest.wal_appends", "ingest.wal_fsyncs",
    "ingest.wal_torn_tail_truncated", "ingest.wal_segments_retired",
    "ingest.replayed",
    "ingest.lease_acquired", "ingest.lease_takeovers",
    "ingest.lease_conflicts",
)

# Radix-partitioned streaming build (ISSUE 11): pass-1 bucketed pair
# spills and the pass-2 per-bucket device reduces. bucket_spills counts
# spill files written, spill_bytes their on-disk size (the per-phase
# bytes the scaling sweep records), tokenize.pool_chunks the chunks the
# multiprocess tokenizer analyzed out-of-process, and pipeline_stalls
# the times the device had to WAIT on the host prefetch (a high count
# says raise TPU_IR_PIPE_DEPTH or bucket count).
BUILD_COUNTER_NAMES = (
    "build.radix.bucket_spills", "build.radix.spill_bytes",
    "build.radix.pipeline_stalls", "build.tokenize.pool_chunks",
)

# Dynamic pruning (ISSUE 13). prune.*: the raw terms behind the derived
# fractions Scorer.prune_diag reports — queries scheduled, the hot-free
# subset (hot-stage upper bound exactly 0, dispatched through the static
# cold-only kernel), and dispatch blocks total / cold-only.
# blockmax.*: the block-max kernels' mask decisions — doc-block lanes
# considered and masked (the skip fraction's raw terms), dispatches
# whose bounds let the pruned hot stage run (saved), and dispatches
# whose surviving blocks overflowed the candidate budget and fell back
# to the exact full-width stage in-kernel.
PRUNE_COUNTER_NAMES = (
    "prune.queries", "prune.queries_hot_free",
    "prune.blocks_total", "prune.blocks_skip_hot",
    "blockmax.blocks_considered", "blockmax.blocks_masked",
    "blockmax.saved_dispatches", "blockmax.fallback_dispatches",
)

# Cold chunk stream (ops/scoring.py _chunk_stream): postings the big cold
# tiers' chunks hold for the dispatched blocks' terms, and the lanes
# dispatched for them (capacity x chunk width); postings / slots is the
# stream's fill.
COLD_CHUNK_COUNTER_NAMES = ("cold.chunk_postings", "cold.chunk_slots")

# Device phrase path (ops/phrase.py, Scorer._search_phrases_device):
# quoted queries dispatched, anchor positions streamed, anchor lanes
# dispatched (n_chunks x chunk width; positions / slots is the fill),
# and matched (span, doc) candidates.
PHRASE_COUNTER_NAMES = ("phrase.queries", "phrase.positions",
                        "phrase.slots", "phrase.candidates")

# Generation-keyed exact-hit result cache (ISSUE 15,
# serving/result_cache.py): hit/miss the lookup verdicts (hit_fraction =
# hit / (hit + miss)), evict the LRU displacements under the bounded
# capacity, stale_generation the entries invalidated because the serving
# generation moved past them (unreachable by key the moment the
# generation bumped — the count is accounting for the purge, never the
# invalidation mechanism).
CACHE_COUNTER_NAMES = (
    "cache.hit", "cache.miss", "cache.evict", "cache.stale_generation",
)

# Elastic ShardSet membership protocol (ISSUE 16, serving/autoscale.py +
# shardset.py): scale.up / scale.down count replicas that ENTERED /
# LEFT the dispatch grid (one per (shard, replica) membership change,
# so a whole-fleet grow on S shards counts S); scale.drain_inflight the
# peak in-flight requests a draining replica was observed finishing
# (drain-not-drop accounting: these requests completed, none dropped);
# scale.cooldown_skipped decisions the autoscaler WANTED to take but
# suppressed inside the cooldown window — the flap-damper's readout.
SCALE_COUNTER_NAMES = (
    "scale.up", "scale.down", "scale.drain_inflight",
    "scale.cooldown_skipped",
)

# Distributed request tracing + SLO layer (ISSUE 18, obs/disttrace.py).
# disttrace.minted counts contexts born here (router admission or an
# unrouted frontend search); adopted the contexts read off an incoming
# traceparent header (worker side — an adopted trace always exports, the
# sampling verdict belongs to the minting process); spans_exported span
# records shipped off-process (RPC piggyback or spool); spans_dropped
# records discarded because a store ring was full; kept_tail roots kept
# by the tail rule (slow/partial/degraded/hedged/error), kept_sampled
# roots kept by the 1-in-N dice, dropped_sampled roots the dice
# discarded; stitched whole-trace assemblies served (live /trace/<id> or
# post-mortem from spools). slo.good / slo.bad classify every finished
# request against TPU_IR_SLO_P99_MS + the availability target;
# slo.burn_breach counts multi-window budget-burn trips (each one also
# flight-records).
DISTTRACE_COUNTER_NAMES = (
    "disttrace.minted", "disttrace.adopted",
    "disttrace.spans_exported", "disttrace.spans_dropped",
    "disttrace.kept_tail", "disttrace.kept_sampled",
    "disttrace.dropped_sampled", "disttrace.stitched",
    "slo.good", "slo.bad", "slo.burn_breach",
)

# Telemetry time machine (ISSUE 19, obs/timeseries.py).
# timeseries.samples counts base-rate windows taken off the registry,
# timeseries.rollups exact fine->coarse tier merges, and
# timeseries.anomaly MAD z-score detections on the curated series
# (each detection also writes a rate-limited "anomaly" flight record).
# forecast.fits counts sinusoid fits that PASSED the quality gate and
# published the forecast_occupancy gauge; forecast.scaleups the
# autoscaler scale-ups whose deciding signal was the forecast (reason
# "forecast" — growth started before the burst, not after the queue).
TIMESERIES_COUNTER_NAMES = (
    "timeseries.samples", "timeseries.rollups", "timeseries.anomaly",
    "forecast.fits", "forecast.scaleups",
)

# Compressed quantized arena (ISSUE 20, index/compress.py).
# compress.shards / compress.bytes_in / compress.bytes_out account each
# shard encode at migrate/build-hook time (the ratio doctor reports is
# recomputed from disk, not from these). decode.blocks_decoded /
# blocks_skipped count posting groups a shard decode unpacked vs skipped
# (doc-range workers: skipped grows with what the range excludes);
# decode.bytes / bytes_skipped the payload bytes behind each — the
# memory-lean pin reads bytes_skipped directly.
COMPRESS_COUNTER_NAMES = (
    "compress.shards", "compress.bytes_in", "compress.bytes_out",
    "decode.blocks_decoded", "decode.blocks_skipped",
    "decode.bytes", "decode.bytes_skipped",
)

DECLARED_COUNTERS = tuple(f"fault.{s}" for s in FAULT_SITES) + (
    # bytes streamed host-to-device across all uploads (pairs with the
    # load.h2d histogram for an effective-MB/s readout)
    "load.h2d_bytes",
) + (COMPILE_COUNTER_NAMES + QUERYLOG_COUNTER_NAMES + BATCH_COUNTER_NAMES
     + ROUTER_COUNTER_NAMES + BUILD_COUNTER_NAMES + INGEST_COUNTER_NAMES
     + PRUNE_COUNTER_NAMES + COLD_CHUNK_COUNTER_NAMES
     + PHRASE_COUNTER_NAMES + CACHE_COUNTER_NAMES + SCALE_COUNTER_NAMES
     + DISTTRACE_COUNTER_NAMES + TIMESERIES_COUNTER_NAMES
     + COMPRESS_COUNTER_NAMES)
# "request" (the root span, all levels pooled) rides alongside the
# per-level request.<level> histograms — same observations, two cuts
DECLARED_HISTOGRAMS = ("request", "load") + REQUEST_STAGES + LOAD_STAGES \
    + SEARCH_STAGES + tuple(
    f"request.{lv}" for lv in SERVICE_LEVELS) + DISPATCH_STAGES + (
    # wall time per compile event (trace + backend compile)
    "compile.time",
    # one score-explain computation (search/explain.py — the (L+1)-row
    # prefix dispatch plus metadata assembly)
    "explain",
    # one slow-query force-capture (span tree + explain + flight dump)
    "querylog.slow_capture",
    # one compressed shard decode (ISSUE 20): unpack + canonical-order
    # restore wall seconds — deliberately OUTSIDE the load.read span so
    # load_read_s keeps measuring bytes-off-disk and drops with them
    "decode.block",
    # coalescing scheduler (ISSUE 9): batch occupancy per dispatched
    # batch (a COUNT observed on the latency bucket scale — 1..64 lands
    # exactly; p50 occupancy > 1 is the "coalescing engaged" proof) and
    # per-slot queue wait (enqueue -> dispatch start, seconds)
    "batch.occupancy",
    "batch.wait",
    # scatter-gather router (ISSUE 10): end-to-end routed request
    # latency, per-shard worker round trips (hedges observe too — each
    # completed replica call is one RTT sample), and the host-side
    # exact top-k merge cost
    "router.request",
    "router.shard_rtt",
    "router.merge",
    # radix streaming build (ISSUE 11): valid pairs each pass-2 bucket
    # reduce produced (bucket-balance readout — a skewed distribution
    # shows up as a wide histogram) and the wall seconds one bucket's
    # read->remap->reduce->spill round took
    "build.radix.bucket_pairs",
    "build.radix.bucket_s",
    # live index (ISSUE 12): one buffer->delta-segment flush (build +
    # commit), one tombstone-applying tiered merge step, and one serving
    # generation swap (new-generation load + precompile + publish — the
    # requests-keep-flowing wall, not a downtime window)
    "ingest.flush",
    "merge.run",
    "generation.swap",
    # durable ingest (ISSUE 17): wall seconds one WAL replay took on
    # writer open (records past the watermark re-applied to the buffer),
    # and the ingest+serve soak's freshness lag — flush commit to the
    # FIRST query answered from a servable generation containing it
    # (seconds on the wire, reported in ms like every histogram)
    "ingest.replay",
    "ingest.freshness",
    # result-cache tier (ISSUE 15): one cache lookup (key build + LRU
    # probe) — the cost a hit pays INSTEAD of the fan-out/dispatch, so
    # p50 here vs router.request/request.full is the cache's win
    "cache.lookup",
    # elastic membership (ISSUE 16): wall seconds one drain took
    # (draining-state entry -> process exit; the summary reports it in
    # ms like every histogram) and wall seconds one scale-up's spawn +
    # precompile/residency warm-up took before the replica entered the
    # dispatch grid — the warm-start gate's cost, paid OUTSIDE traffic
    "scale.drain_ms",
    "scale.warmup_ms",
    # distributed tracing (ISSUE 18): wall seconds one whole-trace
    # stitch took (live ingest_remote merge or post-mortem spool walk)
    "disttrace.stitch",
    # durable-ingest spans (ISSUE 18 satellite over ISSUE 17): every
    # span name observed outside obs/ must be declared — one WAL record
    # framed+written, one batched fsync barrier actually paid, one
    # replay pass on writer open, and the segment-build half of a flush
    # (ingest.flush above times the whole flush including commit)
    "ingest.wal_append",
    "ingest.wal_fsync",
    "ingest.wal_replay",
    "ingest.flush_build",
)

# Gauges: point-in-time values (memory levels, cache sizes) — unlike
# counters they neither accumulate nor reset-to-interval; the merge
# policy says how N process snapshots fold into one cluster value:
# "last" = the newest snapshot's value wins (current level), "max" =
# the cluster-wide peak survives (high-water marks). obs/aggregate.py
# reads this map; an undeclared gauge merges "last".
GAUGE_MERGE = {
    "device.bytes_in_use": "last",   # device HBM currently allocated
    "device.peak_bytes": "max",      # high-water HBM across the run
    "host.rss_bytes": "last",        # process resident set size
    "host.peak_rss_bytes": "max",    # high-water RSS across the run
    "compile.signatures": "last",    # distinct (fn, signature) pairs seen
    # live index (ISSUE 12): the generation a process last committed or
    # swapped to, and that generation's segment/tombstone topology —
    # "last" merges: the levels are per-process currents, not peaks
    "generation.current": "last",
    "generation.segments": "last",
    "generation.tombstones": "last",
    # durable ingest (ISSUE 18 satellite): flush-commit -> first
    # servable-query freshness lag, surfaced live in /healthz (the
    # ingest.freshness histogram keeps the distribution; this gauge is
    # the current level a scrape reads without a soak)
    "ingest.freshness_lag_ms": "last",
    # SLO burn-rate tracker (ISSUE 18, obs/disttrace.py): current
    # multi-window budget-burn multiples — 1.0 burns the budget exactly
    # at the allowed rate; the breach rule requires BOTH windows over
    # threshold so a single spike can't page
    "slo.burn_fast": "last",
    "slo.burn_slow": "last",
    # telemetry time machine (ISSUE 19): the admission occupancy the
    # autoscaler computed on its last tick (the raw series the diurnal
    # fit reads), and the fit's output — predicted occupancy
    # TPU_IR_SCALE_LEAD_S in the future, the third scale-up signal.
    # Both are per-process currents, so "last" merges.
    "router.occupancy": "last",
    "forecast_occupancy": "last",
}
DECLARED_GAUGES = tuple(sorted(GAUGE_MERGE))


def _prom_name(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


# jax.profiler.TraceAnnotation (jaxlib's TraceMe), found once jax has
# been imported by someone else: obs never imports jax itself
_TRACE_ME = None


def trace_me():
    """The TraceMe class of the jax already imported, or None."""
    global _TRACE_ME
    if _TRACE_ME is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        _TRACE_ME = getattr(prof, "TraceAnnotation", None)
    return _TRACE_ME


def capture_active() -> bool:
    """Whether a profiler capture (jax.profiler.start_trace, `--profile
    DIR`, or a capture through the profiler server) is recording now:
    one TraceMe.is_enabled() call, and False while jax is not
    imported."""
    tm = _TRACE_ME or trace_me()
    return tm is not None and tm.is_enabled()


class TelemetryRegistry:
    """Process-wide counters + latency histograms, one snapshot/reset
    API. All methods are thread-safe; the hot-path cost of an increment
    or observation is one dict lookup plus one locked add (the existing
    counter lock discipline — no new locking model)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {n: 0 for n in DECLARED_COUNTERS}
        self._gauges: dict[str, float] = {n: 0.0 for n in DECLARED_GAUGES}
        # gauges a caller actually SET this interval: the local snapshot
        # reports every declared gauge (presence contract), but only set
        # ones cross process boundaries — a process that never sampled
        # memory must not last-wins-zero the cluster's real levels
        self._gauges_set: set[str] = set()
        self._hists: dict[str, LatencyHistogram] = {
            n: LatencyHistogram() for n in DECLARED_HISTOGRAMS}
        # seq: strictly monotonic per scrape/reset, NEVER zeroed — two
        # snapshots with the same run_id order by seq, so a concurrent
        # scraper can tell "newer scrape" from "state was reset" without
        # heuristics on counter values. resets counts every zeroing event
        # (snapshot(reset=True) and reset()): a scraper seeing it change
        # between two of its own scrapes knows a third party drained the
        # interval it thought it owned. run_id identifies this process
        # lifetime (spool dedup across restarts/pid reuse).
        self._seq = 0
        self._resets = 0
        self.run_id = uuid.uuid4().hex
        # the capture ledger: totals of what was observed while a
        # profiler capture recorded (capture_totals). _cap_on is the
        # verdict of the last capture check; the first check that finds
        # a capture after one that found none starts a fresh ledger.
        self._cap_on = False
        self._cap_counters: dict[str, int] = {}
        self._cap_hists: dict[str, list] = {}

    @property
    def seq(self) -> int:
        """The last-issued scrape/reset sequence number — a read, NOT a
        scrape: it neither bumps seq nor copies any state (liveness
        probes poll this; a full snapshot per /healthz would be waste)."""
        with self._lock:
            return self._seq

    # -- counters ----------------------------------------------------------

    def incr(self, name: str, amount: int = 1) -> None:
        captured = self.capture_check()
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + amount
            if captured:
                self._cap_counters[name] = (self._cap_counters.get(name, 0)
                                            + amount)

    def get(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def counter_names(self) -> tuple:
        with self._lock:
            return tuple(self._counters)

    def counters(self, prefix: str = "") -> dict[str, int]:
        """Counter snapshot; with a prefix, only matching counters, the
        prefix stripped (the RecoveryCounters-alias view)."""
        with self._lock:
            if not prefix:
                return dict(self._counters)
            n = len(prefix)
            return {k[n:]: v for k, v in self._counters.items()
                    if k.startswith(prefix)}

    def reset_counters(self, prefix: str = "") -> None:
        """Zero counters under `prefix` ('' = all). Declared counters are
        kept at 0 (presence is the contract), undeclared ones dropped.
        A zeroing event like any other: bumps seq/resets in the same
        lock hold, so scrapers detect even partial (prefix) drains."""
        with self._lock:
            for k in list(self._counters):
                if k.startswith(prefix):
                    if k in DECLARED_COUNTERS:
                        self._counters[k] = 0
                    else:
                        del self._counters[k]
            self._seq += 1
            self._resets += 1

    # -- gauges ------------------------------------------------------------

    def set_gauge(self, name: str, value: float) -> None:
        """Set a point-in-time level (bytes in use, RSS, cache size)."""
        with self._lock:
            self._gauges[name] = float(value)
            self._gauges_set.add(name)

    def update_gauge_max(self, name: str, value: float) -> None:
        """Raise a high-water-mark gauge to `value` if it is higher —
        the peak-memory idiom (a level sample must never WALK a peak
        back down)."""
        with self._lock:
            self._gauges_set.add(name)
            if float(value) > self._gauges.get(name, 0.0):
                self._gauges[name] = float(value)

    def get_gauge(self, name: str) -> float:
        with self._lock:
            return self._gauges.get(name, 0.0)

    def gauges(self) -> dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    # -- histograms --------------------------------------------------------

    def histogram(self, name: str) -> LatencyHistogram:
        h = self._hists.get(name)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(name, LatencyHistogram())
        return h

    def observe(self, name: str, seconds: float,
                captured: bool | None = None) -> None:
        """One observation. `captured` is the verdict of a capture check
        the caller already made (a span checks once, when it opens);
        None checks here."""
        self.histogram(name).observe(seconds)
        if captured or (captured is None and self.capture_check()):
            with self._lock:
                tot = self._cap_hists.setdefault(name, [0, 0.0])
                tot[0] += 1
                tot[1] += seconds

    # -- the capture ledger ------------------------------------------------

    def capture_check(self) -> bool:
        """Whether a profiler capture is recording (capture_active). The
        first check that finds one after a check that found none clears
        the capture ledger, so two captures with no telemetry between
        them share one ledger."""
        tm = _TRACE_ME or trace_me()  # capture_active(), inlined
        if tm is None or not tm.is_enabled():
            if self._cap_on:
                self._cap_on = False
            return False
        if not self._cap_on:
            with self._lock:
                if not self._cap_on:
                    self._cap_counters.clear()
                    self._cap_hists.clear()
                    self._cap_on = True
        return True

    def capture_totals(self) -> dict:
        """What the newest profiler capture observed: {"capturing": is
        one recording now, "counters": {name: total}, "histograms":
        {name: {"count": n, "sum_s": seconds}}} — the program's own
        totals for the window an xplane covers."""
        capturing = capture_active()
        with self._lock:
            return {"capturing": capturing,
                    "counters": dict(self._cap_counters),
                    "histograms": {n: {"count": c, "sum_s": s}
                                   for n, (c, s) in self._cap_hists.items()}}

    def histogram_names(self) -> tuple:
        with self._lock:
            return tuple(self._hists)

    def hist_state(self) -> dict[str, tuple[list[int], float]]:
        """{name: (bucket counts, total seconds)} — the before-image for
        delta summaries (serve-bench reports per-run percentiles without
        resetting process-wide state)."""
        with self._lock:
            hists = dict(self._hists)
        return {n: h.state() for n, h in hists.items()}

    def delta_summary(self, before: dict, always: tuple = ()) -> dict:
        """Per-histogram summaries of observations made SINCE `before`
        (a hist_state() snapshot). Names in `always` are reported even
        with zero new observations — the serve-bench stage contract."""
        out = {}
        for name, (counts, sum_s) in self.hist_state().items():
            b_counts, b_sum = before.get(name, ([0] * len(counts), 0.0))
            d = [a - b for a, b in zip(counts, b_counts)]
            if sum(d) > 0 or name in always:
                out[name] = summary_from_counts(d, sum_s - b_sum)
        return out

    # -- the scrape surface ------------------------------------------------

    def _collect(self, reset: bool):
        """One read of everything — counters under a single lock hold
        (read-and-zero when resetting), histograms via state()/drain().
        The shared core of snapshot() and prometheus_text(): every
        scrape surface gets the same atomicity, so with reset=True a
        concurrent increment or observation lands in exactly one
        interval, never in none. Returns (counters, hist states, meta):
        meta carries the schema version, this scrape's seq, the reset
        count and the process run_id — assigned under the same lock
        hold as the counter read, so seq order IS counter-state order."""
        with self._lock:
            self._seq += 1
            if reset:
                self._resets += 1
            meta = {"schema": SNAPSHOT_SCHEMA, "seq": self._seq,
                    "resets": self._resets, "run_id": self.run_id}
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            gauges_set = set(self._gauges_set)
            if reset:
                for k in list(self._counters):
                    if k in DECLARED_COUNTERS:
                        self._counters[k] = 0
                    else:
                        del self._counters[k]
                # gauges reset with everything else: declared levels
                # return to 0 (presence is the contract, and the next
                # sample restores the live level), undeclared ones drop
                for k in list(self._gauges):
                    if k in DECLARED_GAUGES:
                        self._gauges[k] = 0.0
                    else:
                        del self._gauges[k]
                self._gauges_set.clear()
            hists = dict(self._hists)
        states = {n: (h.drain() if reset else h.state())
                  for n, h in hists.items()}
        return counters, gauges, gauges_set, states, meta

    def collect_state(self, reset: bool = False) -> dict:
        """The SERIALIZABLE raw snapshot: counters plus raw histogram
        bucket counts (not percentile summaries), stamped with schema /
        seq / resets / run_id. This is the cross-process exchange unit —
        obs/aggregate.py spools it, allgathers it, and merges N of them
        bucket-wise; summaries don't merge, bucket counts do. Gauges
        here carry only the names a caller actually SET: an idle
        process's declared-at-0.0 defaults must not last-wins-zero the
        cluster's real levels in the merge."""
        counters, gauges, gauges_set, states, meta = self._collect(reset)
        return {**meta,
                "counters": counters,
                "gauges": {k: v for k, v in gauges.items()
                           if k in gauges_set},
                "histograms": {n: {"counts": list(c), "sum_s": s}
                               for n, (c, s) in states.items()}}

    def snapshot(self, reset: bool = False) -> dict:
        """Everything, one dict: {"schema": ..., "seq": ..., "resets":
        ..., "counters": {...}, "histograms": {name: summary}}.
        `reset=True` is the per-interval scrape — the explicit
        between-runs reset `tpu-ir stats`/serve-bench lacked (see
        _collect for the no-lost-update guarantee)."""
        counters, gauges, _set, states, meta = self._collect(reset)
        return {**meta,
                "counters": counters,
                "gauges": gauges,
                "histograms": {n: summary_from_counts(c, s)
                               for n, (c, s) in states.items()}}

    def reset(self) -> None:
        with self._lock:
            # counter zeroing and the seq/resets bump in ONE lock hold:
            # a concurrent scrape must never observe drained counters
            # with an unchanged resets stamp (that window is exactly the
            # undetected third-party reset `resets` exists to expose)
            for k in list(self._counters):
                if k in DECLARED_COUNTERS:
                    self._counters[k] = 0
                else:
                    del self._counters[k]
            for k in list(self._gauges):
                if k in DECLARED_GAUGES:
                    self._gauges[k] = 0.0
                else:
                    del self._gauges[k]
            self._gauges_set.clear()
            self._cap_counters.clear()
            self._cap_hists.clear()
            self._seq += 1
            self._resets += 1
            hists = dict(self._hists)
        # histograms are zeroed IN PLACE and never deleted: histogram()
        # hands out long-lived references (span exits hold them), and an
        # observe racing a reset must land in the live object — counted
        # in the next interval — not in a dropped orphan
        for h in hists.values():
            h.reset()

    def prometheus_text(self, reset: bool = False) -> str:
        """Prometheus text exposition: counters as one labeled family,
        histograms in the native cumulative-bucket format. Every family
        carries its `# HELP`/`# TYPE` metadata pair (HELP first, the
        order scrapers expect) so nothing is left to inference.
        `reset=True` drains atomically, same as snapshot(reset=True)."""
        from .histogram import BOUNDS

        counters, gauges, _set, states, _ = self._collect(reset)
        lines = [
            "# HELP tpu_ir_events_total Monotonic event counters; one "
            "series per declared dotted name (label \"name\"), zeroed "
            "only by an explicit reset.",
            "# TYPE tpu_ir_events_total counter",
        ]
        for name, v in sorted(counters.items()):
            lines.append(f'tpu_ir_events_total{{name="{name}"}} {v}')
        lines.append(
            "# HELP tpu_ir_gauge Point-in-time levels; one series per "
            "declared dotted name (label \"name\"), merge policy per "
            "GAUGE_MERGE.")
        lines.append("# TYPE tpu_ir_gauge gauge")
        for name, v in sorted(gauges.items()):
            lines.append(f'tpu_ir_gauge{{name="{name}"}} {v!r}')
        lines.append(
            "# HELP tpu_ir_stage_latency_seconds Stage wall time on "
            "fixed log2 buckets; one series set per declared histogram "
            "(label \"stage\"), cumulative le buckets.")
        lines.append("# TYPE tpu_ir_stage_latency_seconds histogram")
        for name in sorted(states):
            counts, sum_s = states[name]
            stage = _prom_name(name)
            cum = 0
            for i, c in enumerate(counts):
                cum += c
                le = repr(BOUNDS[i]) if i < len(BOUNDS) else "+Inf"
                lines.append(
                    f'tpu_ir_stage_latency_seconds_bucket'
                    f'{{stage="{stage}",le="{le}"}} {cum}')
            lines.append(
                f'tpu_ir_stage_latency_seconds_sum{{stage="{stage}"}} '
                f'{sum_s!r}')
            lines.append(
                f'tpu_ir_stage_latency_seconds_count{{stage="{stage}"}} '
                f'{cum}')
        return "\n".join(lines) + "\n"


_REGISTRY = TelemetryRegistry()


def get_registry() -> TelemetryRegistry:
    """The process-wide TelemetryRegistry singleton."""
    return _REGISTRY
