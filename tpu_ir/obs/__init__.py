"""tpu_ir.obs — the unified telemetry layer (ISSUE 3).

One subsystem, three instruments, zero new dependencies:

- **Spans** (trace.py): `trace(name)` context managers building
  per-request / per-build span trees, held in a bounded ring of recent
  traces. `TPU_IR_TRACE=0` disables everything at one flag test.
- **Histograms** (histogram.py) + **registry** (registry.py): fixed
  log-bucket latency histograms and all process-wide counters
  (`recovery.*`, `serving.*`, `fault.*`) behind one
  `TelemetryRegistry.snapshot(reset=...)`.
- **Flight recorder** (recorder.py): on a soak invariant breach, breaker
  open, or structured build error, the last-N traces + a registry
  snapshot are dumped to a JSONL artifact — the JobTracker failure
  page, reborn.

ISSUE 4 adds the cluster-scope top layer:

- **Jobs** (progress.py): JobTracker-style job/phase progress tracking
  (`start_job` / `report_progress`), a bounded last-K job history.
- **Aggregation** (aggregate.py): serializable registry snapshots
  merged across processes — live via multihost collectives, post-mortem
  via the `TPU_IR_TELEMETRY_DIR` file spool.
- **HTTP server** (server.py): `/metrics`, `/healthz`, `/jobs`,
  `/flight` on a stdlib ThreadingHTTPServer
  (`tpu-ir serve-bench --metrics-port`, build `--track PORT`).

Scrape surfaces: `tpu-ir metrics` (JSON / Prometheus text; `--cluster`
for the spool-merged view), `tpu-ir trace-dump`, `tpu-ir stats`
(superset of the PR 2 shape), the latency sections of
`tpu-ir serve-bench` / `bench.py`, and the HTTP endpoints above.
RUNBOOK "Reading the telemetry" / "Live monitoring" are the operator's
guides.
"""

from . import progress
from .histogram import LatencyHistogram, bucket_index
from .progress import current_job, report_progress, start_job
from .recorder import flight_dir, flight_dump, reset_rate_limit
from .registry import (
    DECLARED_GAUGES,
    DECLARED_HISTOGRAMS,
    DISPATCH_STAGES,
    FAULT_SITES,
    GAUGE_MERGE,
    LOAD_STAGES,
    REQUEST_STAGES,
    SERVICE_LEVELS,
    SNAPSHOT_SCHEMA,
    TelemetryRegistry,
    capture_active,
    get_registry,
)
from .trace import (
    Span,
    attach,
    capture_totals,
    clear_traces,
    configure,
    current_span,
    enabled,
    recent_traces,
    record_span,
    trace,
)
from . import profiling  # noqa: E402 — needs trace/registry bound above
from .profiling import (
    profile_report,
    profiled_jit,
    recompiles_last_60s,
    sample_memory,
)
from . import querylog  # noqa: E402 — needs recorder/registry bound above
from . import disttrace  # noqa: E402 — registers the root-close hook
from . import timeseries  # noqa: E402 — needs registry/recorder above


def reset_all() -> None:
    """Full telemetry reset: registry counters + histograms, the trace
    ring, the query log, the distributed-trace store + SLO windows, the
    job history, and the flight recorder's rate limiter. The
    test-isolation hook (tests/conftest.py autouse fixture) — one
    process-wide telemetry state must not leak between tests or between
    runs. (The registry's seq/resets stamps stay monotonic through this
    — that IS their contract.)"""
    get_registry().reset()
    clear_traces()
    progress.clear_jobs()
    reset_rate_limit()
    profiling.reset_profile()
    querylog.clear()
    disttrace.reset()
    timeseries.reset()


__all__ = [
    "LatencyHistogram", "bucket_index",
    "flight_dir", "flight_dump", "reset_rate_limit",
    "TelemetryRegistry", "get_registry", "SNAPSHOT_SCHEMA",
    "FAULT_SITES", "REQUEST_STAGES", "SERVICE_LEVELS",
    "DECLARED_HISTOGRAMS", "DECLARED_GAUGES", "DISPATCH_STAGES",
    "GAUGE_MERGE",
    "progress", "start_job", "report_progress", "current_job",
    "Span", "trace", "attach", "current_span", "recent_traces",
    "clear_traces", "configure", "enabled", "capture_active",
    "capture_totals", "record_span", "reset_all",
    "profiling", "profiled_jit", "profile_report", "sample_memory",
    "recompiles_last_60s",
    "querylog",
    "disttrace",
    "timeseries",
]
