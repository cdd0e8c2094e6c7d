"""Sampled query log + slow-query trap: per-request forensics in a ring.

The PR 3/4/7 telemetry explains *where time goes* (spans, histograms,
compile splits); nothing so far records *which queries* a process served
or freezes the full picture of the ones that were slow. This module is
that per-request ledger:

- **The ring**: every query answered by the Scorer lands one bounded
  entry — analyzed terms (or a stable hash when redacted), service
  level, per-stage latency split, batch id (the link a padded shared
  batch needs for per-request attribution — ROADMAP 3), top-k docids +
  scores, and the MaxScore prune/skip decision. `TPU_IR_QUERYLOG_RING`
  bounds it; `TPU_IR_QUERYLOG_SAMPLE=N` keeps every N-th entry (slow
  queries always record, sampling bounds ring churn, not the trap).

- **Redaction**: with `TPU_IR_QUERYLOG_REDACT=1` entries carry only
  `query_hash` (a stable CRC over the analyzed terms) — enough to
  correlate repeats and join against a flight record, nothing a human
  can read back. The hash is always present either way.

- **The slow-query trap**: a request slower than `TPU_IR_SLOW_QUERY_MS`
  is force-captured: its full span tree (read from the still-open trace
  stack), a score explain for its top hit (computed by the caller-
  supplied callable ONLY when the flight recorder's per-reason rate
  limit admits a dump — a storm of slow queries must not double the
  load with explain dispatches), the entry itself, and a
  `slow_query` flight-recorder artifact. The last-K captures
  (`TPU_IR_QUERYLOG_SLOW_KEEP`) stay readable via `tpu-ir querylog`,
  `/querylog`, and ride compactly in every flight-record header.

Counters (`querylog.recorded`, `querylog.slow`) and the
`querylog.slow_capture` histogram are declared in the registry
(obs/registry.py) so the lint TPU303/TPU305 contracts cover them.
Steady-state overhead is pinned <= 5% on the serve soak
(tests/test_querylog.py), mirroring PR 3's <= 10% tracing pin.
"""

from __future__ import annotations

import collections
import threading
import time
import zlib

from ..utils import envvars
from .trace import current_tree, recent_traces
from .recorder import flight_dump
from .registry import get_registry

_lock = threading.Lock()
_tls = threading.local()

_ENABLED = envvars.get_bool("TPU_IR_QUERYLOG")
_SAMPLE_N = envvars.get_int("TPU_IR_QUERYLOG_SAMPLE")
_REDACT = envvars.get_bool("TPU_IR_QUERYLOG_REDACT")
_SLOW_MS = envvars.get_float("TPU_IR_SLOW_QUERY_MS")
_RING = collections.deque(maxlen=envvars.get_int("TPU_IR_QUERYLOG_RING"))
_SLOW = collections.deque(
    maxlen=envvars.get_int("TPU_IR_QUERYLOG_SLOW_KEEP"))
_seq = 0
_batch_seq = 0
_slow_times: collections.deque = collections.deque(maxlen=1024)
# compact, IMMUTABLE per-offender stamps for flight-record headers —
# appended before the dump (so the artifact reporting a slow query
# lists that query) while the full capture publishes to _SLOW only
# once fully formed (readers json.dumps these concurrently; a dict
# mutated after publication races serialization)
_slow_headers: collections.deque = collections.deque(maxlen=64)

_HEADER_KEYS = ("query_hash", "level", "total_ms", "analyze_ms",
                "dispatch_ms", "time", "batch_id",
                # coalesced-serving attribution (ISSUE 9): how long the
                # slow offender waited to coalesce and how full its
                # shared batch was — the first two questions a slow
                # query inside a batch raises
                "queue_wait_ms", "batch_occupancy",
                # the distributed-trace join key (ISSUE 18): a slow
                # offender's flight header points at the ONE stitched
                # waterfall that explains it (`tpu-ir trace <id>`)
                "trace_id")


def configure(enabled: bool | None = None, sample: int | None = None,
              ring_capacity: int | None = None,
              redact: bool | None = None, slow_ms: float | None = None,
              slow_keep: int | None = None) -> None:
    """Runtime overrides of the TPU_IR_QUERYLOG* env knobs (tests,
    REPLs) — the obs.trace.configure idiom."""
    global _ENABLED, _SAMPLE_N, _REDACT, _SLOW_MS, _RING, _SLOW
    if enabled is not None:
        _ENABLED = enabled
    if sample is not None:
        _SAMPLE_N = max(1, sample)
    if redact is not None:
        _REDACT = redact
    if slow_ms is not None:
        _SLOW_MS = max(0.0, slow_ms)
    with _lock:
        if ring_capacity is not None:
            _RING = collections.deque(_RING, maxlen=max(1, ring_capacity))
        if slow_keep is not None:
            _SLOW = collections.deque(_SLOW, maxlen=max(1, slow_keep))


def enabled() -> bool:
    return _ENABLED


def slow_query_ms() -> float:
    """The slow-query threshold in ms; 0 disables the trap."""
    return _SLOW_MS


def redacted() -> bool:
    return _REDACT


def next_batch_id() -> int:
    """Process-monotonic id stamped on every entry of one search_batch
    dispatch — the join key for per-request attribution inside a shared
    (padded) batch."""
    global _batch_seq
    with _lock:
        _batch_seq += 1
        return _batch_seq


def query_hash(terms) -> str:
    """Stable hex digest over the analyzed term sequence — the
    redaction-safe identity entries and flight headers correlate on."""
    blob = "\x1f".join(str(t) for t in terms).encode("utf-8")
    return f"{zlib.crc32(blob) & 0xFFFFFFFF:08x}"


class request_context:
    """Thread-local annotations for entries recorded under this context
    — the ServingFrontend wraps the scorer call so entries carry the
    ladder's true service level (the scorer alone only knows flags)."""

    __slots__ = ("_fields", "_saved")

    def __init__(self, **fields):
        self._fields = fields
        self._saved = None

    def __enter__(self):
        self._saved = getattr(_tls, "fields", None)
        _tls.fields = self._fields
        return self

    def __exit__(self, *exc):
        _tls.fields = self._saved
        return False


def context_fields() -> dict:
    return getattr(_tls, "fields", None) or {}


def record(entry: dict, explain_fn=None) -> dict:
    """Record one per-query entry; returns it (seq/ts/slow stamped).

    Sampling keeps every N-th entry in the ring; a SLOW entry (total_ms
    at/above the trap threshold) always records, grows a full capture
    (span tree + explain when the rate limit admits the dump) in the
    slow ring, and writes a `slow_query` flight artifact. `explain_fn`
    is called only inside an admitted dump — never on the sampled-out
    or rate-limited path."""
    if not _ENABLED:
        return entry
    global _seq
    reg = get_registry()
    entry.setdefault("time",
                     time.strftime("%Y-%m-%dT%H:%M:%S"))
    entry.update(context_fields())
    if "trace_id" not in entry or entry["trace_id"] is None:
        # the coalescer stamps a follower's id via slot_meta (the entry
        # is recorded on the LEADER's thread); everyone else gets the
        # thread-local context — None stays None (tracing off)
        from . import disttrace

        tid = disttrace.current_trace_id()
        if tid is not None:
            entry["trace_id"] = tid
        else:
            entry.pop("trace_id", None)
    slow = (_SLOW_MS > 0.0
            and float(entry.get("total_ms", 0.0)) >= _SLOW_MS)
    if slow:
        # stamped BEFORE the ring append: a published entry is never
        # mutated again (concurrent scrapes json.dumps live references)
        entry["slow"] = True
    with _lock:
        _seq += 1
        entry["seq"] = _seq
        keep = slow or (_seq % _SAMPLE_N == 0)
        if keep:
            _RING.append(entry)
    if keep:
        # the counter counts KEPT entries — what a scraper can actually
        # read back, not sampled-out ghosts
        reg.incr("querylog.recorded")
    if slow:
        reg.incr("querylog.slow")
        with _lock:
            _slow_times.append(time.monotonic())
        _capture_slow(entry, explain_fn)
    return entry


def _capture_slow(entry: dict, explain_fn) -> None:
    """Force-capture a slow offender: span tree + (rate-limited) explain
    + flight record. Never raises — the trap runs on the serving path."""
    t0 = time.perf_counter()
    capture = dict(entry)
    try:
        tree = current_tree()
        if tree is not None:
            # the still-open tree: the ServingFrontend's "request"
            # root, or a plain Scorer.search_batch's "search" span
            capture["span_tree"] = tree
        else:
            # a record made outside any span: take the newest closed
            # root from the ring (best-effort: under concurrency it can
            # belong to a neighboring request)
            recent = recent_traces()
            if recent:
                capture["span_tree"] = recent[-1].to_dict()
                capture["span_tree_source"] = "ring"
    except Exception:  # noqa: BLE001 — the trap must not fail a request
        pass
    # the compact header stamp publishes BEFORE the dump (so the flight
    # artifact reporting this query lists it) and is never mutated; the
    # full capture publishes to _SLOW only once fully formed below —
    # concurrent scrapes serialize published dicts, so nothing published
    # may change size afterwards
    with _lock:
        _slow_headers.append({k: capture[k] for k in _HEADER_KEYS
                              if k in capture})

    def extra():
        # evaluated by flight_dump ONLY when the per-reason rate limit
        # admits this dump: the explain dispatches (an (L+1)-row debug
        # kernel call) are the expensive part of the capture
        if explain_fn is not None:
            try:
                capture["explain"] = explain_fn()
            except Exception as e:  # noqa: BLE001
                capture["explain_error"] = repr(e)
        return {"slow_query": capture}

    try:
        capture["flight_record"] = flight_dump("slow_query", extra=extra)
    except Exception:  # noqa: BLE001
        capture["flight_record"] = None
    with _lock:
        _SLOW.append(capture)
    get_registry().observe("querylog.slow_capture",
                           time.perf_counter() - t0)


def recent(n: int | None = None) -> list[dict]:
    """Ring contents, oldest first (`n` newest when given)."""
    with _lock:
        out = list(_RING)
    return out[-n:] if n else out


def slow_recent(n: int | None = None) -> list[dict]:
    """Slow-query captures, oldest first (`n` newest when given)."""
    with _lock:
        out = list(_SLOW)
    return out[-n:] if n else out


def slow_header_entries(limit: int = 8) -> list[dict]:
    """Compact last-K slow entries for flight-record headers (query
    hash, level, stage split) — a breach dump is self-contained without
    a separate /querylog scrape. Reads the immutable header stamps, so
    a dump racing an in-flight capture serializes safely AND includes
    the offender that triggered it."""
    with _lock:
        out = list(_slow_headers)
    return out[-limit:]


def slow_last_60s() -> int:
    """Slow queries trapped in the trailing minute (the /healthz
    liveness window, sibling of recompiles_last_60s)."""
    cutoff = time.monotonic() - 60.0
    with _lock:
        return sum(1 for t in _slow_times if t >= cutoff)


def summary() -> dict:
    """The scrape-surface header: config + counts (entries ride
    separately via recent()/slow_recent())."""
    reg = get_registry()
    with _lock:
        ring_len, slow_len = len(_RING), len(_SLOW)
        ring_cap, slow_cap = _RING.maxlen, _SLOW.maxlen
    return {
        "enabled": _ENABLED,
        "sample": _SAMPLE_N,
        "redact": _REDACT,
        "slow_query_ms": _SLOW_MS,
        "ring": {"entries": ring_len, "capacity": ring_cap},
        "slow": {"entries": slow_len, "capacity": slow_cap,
                 "last_60s": slow_last_60s()},
        "recorded": reg.get("querylog.recorded"),
        "slow_trapped": reg.get("querylog.slow"),
    }


def clear() -> None:
    """Drop entries + captures (test isolation via obs.reset_all)."""
    global _seq
    with _lock:
        _RING.clear()
        _SLOW.clear()
        _slow_headers.clear()
        _slow_times.clear()
        _seq = 0
