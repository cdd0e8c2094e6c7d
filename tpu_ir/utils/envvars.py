"""The TPU_IR_* environment-variable registry: one declaration per knob.

Before ISSUE 6, 15 `TPU_IR_*` env vars were read at 15 ad-hoc
`os.environ.get` sites across nine modules — each with its own parsing,
its own (sometimes absent) validation, and no single place an operator
could ask "what knobs exist?". PR 5's `cache_revalidate_mode()` showed
the right shape for ONE var (validated, fails loudly on a bogus value,
documented); this module generalizes it to all of them:

- every variable is DECLARED here once: name, type, default, allowed
  choices, the RUNBOOK section that documents it, and a one-line
  description;
- typed accessors (`get_str/get_int/get_float/get_bool/get_choice`)
  parse + validate in one place — a malformed value raises a
  `ValueError` naming the variable instead of a bare int() traceback
  (or worse, a silent fall-back to the default); numeric values below a
  declared minimum clamp to it (the pre-registry sites' `max(1, ...)`
  idiom — several accessors run at import time, where raising would
  kill every command before argument parsing);
- `markdown_table()` renders the registry as the RUNBOOK's env-var
  table, so the documentation is GENERATED from the declarations and
  the lint contract pass (tpu_ir/lint/contracts.py, rule TPU302) pins
  the two against drift in either direction;
- the lint pass TPU301 rejects any raw `os.environ` read of a
  `TPU_IR_*` name outside this file, so a new knob cannot ship
  undeclared.

Deliberately dependency-free (os + dataclasses only): the linter loads
this module straight from its file path, keeping `tpu-ir lint` a
pure-CPU, no-JAX command.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

_UNSET = object()


@dataclass(frozen=True)
class EnvVar:
    """One declared knob. `kind` drives parsing/validation; `default` is
    the PARSED default (what accessors return when the var is unset or
    set to the empty string). `runbook` anchors the RUNBOOK section that
    explains the knob — the generated table links it."""

    name: str
    kind: str                 # "str" | "int" | "float" | "bool" | "choice"
    default: object
    description: str
    runbook: str              # RUNBOOK.md section anchor, e.g. "§7"
    choices: tuple = ()       # for kind == "choice"
    # for int/float: values below this are CLAMPED to it, not rejected —
    # the pre-registry read sites clamped (`max(1, ...)`), and several
    # accessors run at module import time, where a raise would take the
    # whole CLI down before argument parsing
    minimum: float | None = None


REGISTRY: dict[str, EnvVar] = {}


def _declare(name: str, kind: str, default, description: str, runbook: str,
             *, choices: tuple = (), minimum: float | None = None) -> None:
    REGISTRY[name] = EnvVar(name, kind, default, description, runbook,
                            choices=choices, minimum=minimum)


# -- the declarations (one line per knob an operator can set) ---------------

_declare("TPU_IR_FAULTS", "str", None,
         "fault-injection plan spec (site[@match]:rule entries, seed=N)",
         "§7")
_declare("TPU_IR_QUARANTINE_KEEP", "int", 8,
         "corrupt artifacts kept in .quarantine/ before eviction", "§7",
         minimum=0)
_declare("TPU_IR_TRACE", "bool", True,
         "0 disables spans AND every latency histogram (one flag test)",
         "§9")
_declare("TPU_IR_TRACE_SAMPLE", "int", 1,
         "keep every N-th root trace in the flight-recorder ring", "§9",
         minimum=1)
_declare("TPU_IR_TRACE_RING", "int", 64,
         "capacity of the recent-traces ring buffer", "§9", minimum=1)
_declare("TPU_IR_FLIGHT_DIR", "str", None,
         "flight-recorder artifact directory (default: system temp)", "§9")
_declare("TPU_IR_FLIGHT_INTERVAL", "float", 30.0,
         "min seconds between two dumps for one reason (rate limit)", "§9",
         minimum=0.0)
_declare("TPU_IR_JOB_HISTORY", "int", 16,
         "finished jobs kept for /jobs (the JobTracker last-K pages)",
         "§10", minimum=1)
_declare("TPU_IR_TELEMETRY_DIR", "str", None,
         "telemetry spool directory enabling cross-process merge", "§10")
_declare("TPU_IR_SPOOL_INTERVAL", "float", 5.0,
         "seconds between background spool refreshes (SpoolWriter)", "§10",
         minimum=0.1)
_declare("TPU_IR_FORMAT_VERSION", "int", 2,
         "artifact format writers emit (1 = npz rollback pin, 2 = arenas)",
         "§12", choices=(1, 2))
_declare("TPU_IR_COMPRESS", "choice", "0",
         "compress part shards at build finalize (bit-packed docids + "
         "quantized tf, format v3): 1 compresses through the "
         "save_with_checksums hook, 0 leaves raw arenas (migrate-index "
         "--compress converts in place either way)", "§26",
         choices=("0", "1"))
_declare("TPU_IR_TF_DTYPE", "choice", "auto",
         "term-frequency quantization for compressed shards: auto "
         "(int8 LUT when lossless, else bf16), int8 (LUT, lossy above "
         "256 distinct values — floor-quantized so blockmax bounds "
         "stay safe), bf16 (always lossless via exception list)", "§26",
         choices=("auto", "int8", "bf16"))
_declare("TPU_IR_LOAD_THREADS", "int", None,
         "concurrent verified shard loads (default min(8, cores))", "§12",
         minimum=1)
_declare("TPU_IR_H2D_CHUNK_BYTES", "int", 64 << 20,
         "host-to-device streaming chunk size in bytes", "§12", minimum=1)
_declare("TPU_IR_CACHE_REVALIDATE", "choice", "stat",
         "serving-cache revalidation: stat (trust size+mtime) or crc "
         "(re-stream and content-prove every hit)", "§12",
         choices=("stat", "crc"))
_declare("TPU_IR_PROFILE", "bool", True,
         "0 disables the jit compile/recompile profiler (one flag test)",
         "§14")
_declare("TPU_IR_PROFILE_COST", "bool", True,
         "0 skips the per-signature cost_analysis probe (FLOPs/bytes)",
         "§14")
_declare("TPU_IR_PROFILE_RECOMPILE_LIMIT", "int", 3,
         "compiles of ONE signature before a recompile-storm flight dump",
         "§14", minimum=1)
_declare("TPU_IR_BENCH_CHECK_WINDOW", "int", 8,
         "trailing comparable BENCH_HISTORY rows the sentry medians over",
         "§14", minimum=1)
_declare("TPU_IR_BENCH_CHECK_MIN_ROWS", "int", 3,
         "comparable prior rows required before bench-check enforces",
         "§14", minimum=1)
_declare("TPU_IR_BENCH_CHECK_TOLERANCE", "float", 0.3,
         "relative degradation vs the window median that breaches "
         "bench-check", "§14", minimum=0.0)
_declare("TPU_IR_BATCH_WAIT_MS", "float", 0.0,
         "max extra ms a promoted batch leader waits to fill toward the "
         "next rung (0 = dispatch immediately; idle solo queries never "
         "wait)", "§16", minimum=0.0)
_declare("TPU_IR_BATCH_LADDER", "str", "1,4,16,64",
         "compiled batch-size rungs the coalescer pads to (bounds "
         "recompilation; largest rung caps batch occupancy). When UNSET, "
         "CPU-class backends drop rungs above 16 — padded rows cost real "
         "compute there; setting the variable overrides the probe", "§16")
_declare("TPU_IR_BATCH_WIDTH", "int", 8,
         "query-width floor (padded term slots) for coalesced batches — "
         "one precompilable width; longer queries bump to their pow2 "
         "bucket (kernel cost scales with width on CPU — keep it near "
         "the real query-length ceiling)", "§16", minimum=1)
_declare("TPU_IR_BATCH_DONATE", "choice", "auto",
         "donate the query-side device buffer on coalesced topk "
         "dispatches: auto (TPU backends only), 1 (force), 0 (off)",
         "§16", choices=("auto", "0", "1"))
_declare("TPU_IR_RADIX_BUCKETS", "int", 16,
         "radix buckets the streaming pass-1 partitions its pair spills "
         "into (0 = legacy per-batch pass-2 combine; >0 turns pass 2 "
         "into per-bucket local device reduces). Default 16: the radix "
         "path is the library default after its PR 11 soak — every "
         "bucket count is fuzz-pinned bit-identical to legacy, so 0 is "
         "a rollback pin, not a safety valve", "§18", minimum=0)
_declare("TPU_IR_TOKENIZE_PROCS", "int", 1,
         "worker processes for the pure-Python tokenizer (1 = in-process;"
         " N>1 analyzes chunks in a pool, byte-identical to serial)",
         "§18", minimum=1)
_declare("TPU_IR_PIPE_DEPTH", "int", 2,
         "build pipeline depth: spill batches / pass-2 buckets the host "
         "prepares ahead of the device (1 = no overlap)", "§18",
         minimum=1)
_declare("TPU_IR_RADIX_PARTS", "bool", False,
         "1 writes bucket-segmented part files straight from the pass-2 "
         "bucket reduces (skips the pass-3 global per-shard sort; parts "
         "are NOT byte-identical to the canonical layout — readers "
         "accept both)", "§18")
_declare("TPU_IR_QUERYLOG", "bool", True,
         "0 disables the sampled query log AND the slow-query trap",
         "§15")
_declare("TPU_IR_QUERYLOG_RING", "int", 256,
         "capacity of the per-process query-log ring", "§15", minimum=1)
_declare("TPU_IR_QUERYLOG_SAMPLE", "int", 1,
         "keep every N-th query entry in the ring (slow queries always "
         "record)", "§15", minimum=1)
_declare("TPU_IR_QUERYLOG_REDACT", "bool", False,
         "1 stores only a stable hash of the analyzed query terms "
         "(privacy: no readable query text in telemetry)", "§15")
_declare("TPU_IR_QUERYLOG_SLOW_KEEP", "int", 16,
         "slow-query captures (span tree + explain) kept in memory",
         "§15", minimum=1)
_declare("TPU_IR_SLOW_QUERY_MS", "float", 0.0,
         "requests at/above this latency are force-captured (explain + "
         "span tree + flight record); 0 disables the trap", "§15",
         minimum=0.0)
_declare("TPU_IR_INGEST_BUFFER_DOCS", "int", 1000,
         "buffered documents that auto-flush the IngestWriter into one "
         "delta segment", "§19", minimum=1)
_declare("TPU_IR_INGEST_KEEP_GENERATIONS", "int", 8,
         "generation manifests gc() keeps; unreferenced segment dirs "
         "are deleted with the manifests that named them", "§19",
         minimum=1)
_declare("TPU_IR_MERGE_FACTOR", "int", 4,
         "segments in one size tier that trigger a tiered merge step "
         "(merge debt threshold)", "§19", minimum=2)
_declare("TPU_IR_MERGE_TIER_RATIO", "float", 8.0,
         "geometric doc-count ratio between merge tiers (each doc is "
         "rewritten about log_ratio(N) times over its lifetime)", "§19",
         minimum=2.0)
_declare("TPU_IR_BLOCKMAX", "choice", "auto",
         "block-max pruning of the tiered hot-strip stage: auto/1 "
         "engage when bounds exist and the doc axis is wide enough, 0 "
         "disables (results are bit-identical either way — the toggle "
         "exists for A/B runs and incident rollback)", "§20",
         choices=("auto", "0", "1"))
_declare("TPU_IR_BLOCKMAX_WIDTH", "int", 512,
         "doc-axis block width for block-max score bounds; fixed per "
         "blockmax.arena artifact at write time (readers use the stored "
         "width). Smaller blocks = tighter bounds but a larger bounds "
         "table and more mask lanes", "§20", minimum=64)
_declare("TPU_IR_BLOCKMAX_STRIP_CACHE", "choice", "auto",
         "device-cache each scoring mode's pre-weighted hot strip "
         "(lntf/saturation of the raw strip — query-independent, yet "
         "recomputed per dispatch in-kernel): auto caches within the "
         "memory budget, 1 forces, 0 disables. Bit-identical either "
         "way; one more strip-sized device buffer per cached mode",
         "§20", choices=("auto", "0", "1"))
_declare("TPU_IR_BLOCKMAX_BLOCKS", "int", 0,
         "doc blocks one block-max dispatch scores exactly (the static "
         "candidate budget); 0 sizes it automatically from k, the block "
         "width and the doc-axis length. Batches whose surviving blocks "
         "overflow the budget fall back to the exact full-width stage "
         "in-kernel (bit-identical, counted as blockmax.fallback)",
         "§20", minimum=0)
_declare("TPU_IR_MERGE_AUTO", "bool", True,
         "0 decouples compaction from flush: IngestWriter stops running "
         "the tiered merge policy inline after every flush — drive "
         "merges explicitly with `tpu-ir compact` (ingest latency stops "
         "paying merge cost; merge debt accumulates until drained)",
         "§19")
_declare("TPU_IR_CACHE_RESULTS", "int", 0,
         "entry capacity of the generation-keyed exact-hit result cache "
         "(router fan-out cache + the serving frontend's single-process "
         "variant); 0 disables both. Hits are bit-identical to the miss "
         "path and invalidate by key on a generation swap", "§21",
         minimum=0)
_declare("TPU_IR_WORKLOAD", "choice", "uniform",
         "traffic shape for soaks and serve-bench: uniform (the legacy "
         "seeded mixed workload) or zipf (rank-skewed term draw over "
         "the index vocabulary — the 'millions of users' shape)", "§21",
         choices=("uniform", "zipf"))
_declare("TPU_IR_WORKLOAD_SKEW", "float", 1.1,
         "Zipf exponent s for --workload zipf: term rank r drawn with "
         "probability proportional to 1/r^s (0 = uniform control; web "
         "query logs measure ~0.7-1.2)", "§21", minimum=0.0)
_declare("TPU_IR_WORKLOAD_BURST", "float", 0.0,
         "diurnal burst amplitude for the workload arrival schedule: 0 "
         "= flat arrivals; b > 0 modulates inter-arrival pacing "
         "sinusoidally so peak-rate traffic runs ~(1+b)x the trough",
         "§21", minimum=0.0)
_declare("TPU_IR_HOT_RESIDENCY", "choice", "auto",
         "pre-warm the hot-postings residency set (block-max strips / "
         "dense tf matrix) at worker load, fed by the doctor's df-skew "
         "report: auto engages when the top-df decile holds most "
         "postings, 1 forces, 0 disables", "§21",
         choices=("auto", "0", "1"))
_declare("TPU_IR_ROUTER_DEADLINE_MS", "float", 500.0,
         "per-shard deadline for one routed request: a shard that "
         "answers on no replica within it ships the response partial",
         "§17", minimum=1.0)
_declare("TPU_IR_ROUTER_HEDGE_MS", "float", 25.0,
         "hedge-delay floor: a second replica is tried once the primary "
         "exceeds max(this, the shard's trailing p99); 0 disables "
         "hedging", "§17", minimum=0.0)
_declare("TPU_IR_ROUTER_CONNECT_MS", "float", 250.0,
         "TCP connect timeout for one shard-worker RPC attempt", "§17",
         minimum=1.0)
_declare("TPU_IR_ROUTER_HEALTH_TTL_S", "float", 2.0,
         "max age of cached per-worker /healthz payloads in the "
         "router's aggregated health view", "§17", minimum=0.0)
_declare("TPU_IR_AUTOSCALE", "bool", False,
         "1 runs the elastic-capacity autoscaler over the shard fleet "
         "(serve-bench --autoscale and embedders): sustained admission "
         "pressure adds a warm replica per shard, sustained idleness "
         "drains one away (drain-not-drop — in-flight requests finish "
         "before the process exits)", "§22")
_declare("TPU_IR_SCALE_MIN_REPLICAS", "int", 1,
         "autoscaler floor: replicas per shard it will never drain "
         "below (the always-on capacity that serves the trough)", "§22",
         minimum=1)
_declare("TPU_IR_SCALE_MAX_REPLICAS", "int", 4,
         "autoscaler ceiling: replicas per shard it will never grow "
         "past (bounds spawn cost and memory under a runaway burst)",
         "§22", minimum=1)
_declare("TPU_IR_SCALE_COOLDOWN_S", "float", 5.0,
         "minimum seconds between autoscaler membership changes: the "
         "flap damper — a diurnal wave shorter than twice this value "
         "cannot make the fleet oscillate (suppressed decisions count "
         "as scale.cooldown_skipped)", "§22", minimum=0.0)
_declare("TPU_IR_WAL", "bool", True,
         "0 disables the ingest write-ahead log AND the writer lease "
         "(durability off: a crash loses every buffered write, and "
         "nothing enforces single-writer) — a rollback pin, not a "
         "tuning knob", "§23")
_declare("TPU_IR_WAL_FSYNC_DOCS", "int", 32,
         "appended WAL records between fsyncs (the Lucene-translog "
         "durability/throughput dial: 1 fsyncs every acknowledged "
         "mutation; a HOST power loss can lose at most one batch — a "
         "process crash loses nothing either way)", "§23", minimum=1)
_declare("TPU_IR_WAL_FSYNC_MS", "float", 50.0,
         "max milliseconds an appended WAL record waits for its batched "
         "fsync (bounds the host-power-loss window in time the way "
         "_FSYNC_DOCS bounds it in records)", "§23", minimum=0.0)
_declare("TPU_IR_WAL_LEASE_TTL_S", "float", 10.0,
         "writer-lease heartbeat TTL: a lease whose heartbeat is older "
         "than this (or whose holder pid is dead) is stale and taken "
         "over on the next writer open; a fresh lease from a live pid "
         "refuses the second writer with WriterLeaseHeld", "§23",
         minimum=0.5)
_declare("TPU_IR_DISTTRACE", "bool", True,
         "0 disables distributed request tracing (traceparent minting, "
         "propagation, span export, stitching) — the per-process span "
         "rings under TPU_IR_TRACE keep working; this kills only the "
         "cross-process layer", "§24")
_declare("TPU_IR_TRACE_TAIL", "bool", True,
         "0 disables tail-keeping: slow/partial/degraded/hedged/error "
         "traces stop being force-kept and fall under the same "
         "1-in-TPU_IR_TRACE_SAMPLE dice as everything else — a "
         "load-shedding pin, not a tuning knob", "§24")
_declare("TPU_IR_SLO_P99_MS", "float", 250.0,
         "the latency SLO: a served request slower than this is a BAD "
         "request for the sliding-window burn-rate tracker (/slo) and "
         "its trace is tail-kept; also the disttrace slow-keep "
         "threshold", "§24", minimum=1.0)
_declare("TPU_IR_TIMESERIES", "bool", True,
         "0 disables the telemetry time machine wholesale — no history "
         "store, no background sampler, /timeseries reports disabled, "
         "the anomaly detector and the forecast signal go dark; the "
         "one-switch rollback for ISSUE 19", "§25")
_declare("TPU_IR_TS_SAMPLE_S", "float", 10.0,
         "seconds between background registry samples: tier-0 window "
         "width, and with the fixed tier factors (x1/x6/x60) the whole "
         "retention ladder — 10 s gives 1 h / 4 h / 24 h", "§25",
         minimum=0.05)
_declare("TPU_IR_TS_ANOMALY_Z", "float", 8.0,
         "robust MAD z-score above which a curated series' newest point "
         "is an anomaly (timeseries.anomaly counter + rate-limited "
         "'anomaly' flight record); 0 disables the detector", "§25",
         minimum=0.0)
_declare("TPU_IR_SCALE_LEAD_S", "float", 30.0,
         "the forecast horizon: the diurnal fit publishes predicted "
         "occupancy this many seconds ahead as forecast_occupancy, so "
         "a forecast-armed autoscaler starts growing one lead window "
         "before the predicted burst", "§25", minimum=0.0)


def _raw(name: str) -> str | None:
    """The raw value, with unset and empty-string both meaning 'use the
    default' (the long-standing `or default` idiom at the old sites)."""
    if name not in REGISTRY:
        raise KeyError(f"undeclared environment variable {name!r}: add it "
                       "to tpu_ir/utils/envvars.py REGISTRY")
    v = os.environ.get(name)
    return v if v else None


def _bad(name: str, value: str, expected: str) -> ValueError:
    return ValueError(f"{name}={value!r}: expected {expected}")


def get_str(name: str, default=_UNSET) -> str | None:
    v = _raw(name)
    if v is None:
        return REGISTRY[name].default if default is _UNSET else default
    return v


def get_int(name: str, default=_UNSET) -> int | None:
    decl = REGISTRY.get(name)
    v = _raw(name)
    if v is None:
        return decl.default if default is _UNSET else default
    try:
        out = int(v)
    except ValueError:
        raise _bad(name, v, "an integer") from None
    if decl.choices and out not in decl.choices:
        raise _bad(name, v, f"one of {decl.choices}")
    if decl.minimum is not None and out < decl.minimum:
        return int(decl.minimum)
    return out


def get_float(name: str, default=_UNSET) -> float | None:
    decl = REGISTRY.get(name)
    v = _raw(name)
    if v is None:
        return decl.default if default is _UNSET else default
    try:
        out = float(v)
    except ValueError:
        raise _bad(name, v, "a number") from None
    if decl.minimum is not None and out < decl.minimum:
        return float(decl.minimum)
    return out


def get_bool(name: str, default=_UNSET) -> bool:
    """The documented 0/1 convention: "0" (exactly) is False for
    default-True flags; any non-empty value is True for default-False
    flags — matching the original `!= "0"` / `== "1"`-ish reads so no
    operator setting changes meaning."""
    decl = REGISTRY.get(name)
    v = _raw(name)
    if v is None:
        return decl.default if default is _UNSET else default
    if decl.default is True:
        return v != "0"
    return v not in ("0", "false", "False")


def get_choice(name: str) -> str:
    """Validated closed-set value (the `cache_revalidate_mode` template):
    case/space-normalized; a value outside the declared choices raises —
    an integrity knob must not fail open to its weaker default."""
    decl = REGISTRY[name]
    v = _raw(name)
    if v is None:
        return decl.default
    out = v.strip().lower()
    if out == "":
        return decl.default
    if out not in decl.choices:
        raise _bad(name, v, f"one of {decl.choices}")
    return out


def is_set(name: str) -> bool:
    """Whether the operator explicitly set the (declared) variable to a
    non-empty value — the hook adaptive defaults use to yield ("auto
    unless overridden": the batch ladder's CPU backend probe must not
    second-guess an explicit TPU_IR_BATCH_LADDER)."""
    return _raw(name) is not None


def declared_names() -> tuple:
    """Every declared TPU_IR_* name, sorted — the contract surface the
    lint pass (TPU301/TPU302) and the RUNBOOK table check against."""
    return tuple(sorted(REGISTRY))


def markdown_table() -> str:
    """The RUNBOOK env-var table, generated from the declarations.
    RUNBOOK §13 embeds this between `<!-- envvar-table -->` markers; the
    lint contract pass re-renders it and fails on any drift, so the
    docs cannot silently rot."""
    rows = ["| variable | type | default | doc | description |",
            "|---|---|---|---|---|"]
    for name in declared_names():
        d = REGISTRY[name]
        if d.kind == "bool":
            default = "1" if d.default else "0"
        elif d.default is None:
            default = "(unset)"
        else:
            default = str(d.default)
        kind = (f"choice{d.choices}" if d.kind == "choice" else d.kind)
        rows.append(f"| `{name}` | {kind} | `{default}` | {d.runbook} | "
                    f"{d.description} |")
    return "\n".join(rows)
