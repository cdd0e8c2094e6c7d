"""Device-cost profiling: compile tracking, dispatch splits, memory.

The serving p50 sat at ~110 ms with `device_rtt_ms` ~100 ms at every
corpus size, and nothing in the telemetry stack could say how much of
that was XLA compilation, host dispatch, transfer, or device compute —
the JobTracker-style counters (PR 3/4) only see the host. This module
is the missing device-cost lens, three instruments in one:

- **Compile observability** — `profiled_jit` is a drop-in `jax.jit`
  replacement used by every compiled entry point (ops/scoring.py,
  ops/postings.py, utils/transfer.py, parallel/sharded_tiered.py). It
  keys every call by its ABSTRACT signature (arg shapes/dtypes + static
  values), detects actual compiles via the jit cache size, records each
  one into the `compile.count` counter and `compile.time` histogram,
  captures `cost_analysis()` FLOPs/bytes per executable (one extra
  lower+compile per new signature; a persistent-compilation-cache hit
  when that is enabled — TPU_IR_PROFILE_COST=0 skips it), and counts a
  `compile.recompiles` event whenever one signature compiles AGAIN — a
  fresh-jit-per-call or cache-thrash bug. More than
  TPU_IR_PROFILE_RECOMPILE_LIMIT compiles of one signature dumps a
  rate-limited `recompile_storm` flight record.
- **Dispatch split** — a `jax.monitoring` duration listener attributes
  jax's own jaxpr-trace and backend-compile events to the profiled call
  in flight, emitted as `dispatch.trace` / `dispatch.compile` sub-spans
  inside the scorer's span tree; the scorer adds `dispatch.device`
  (dispatch → block_until_ready) so the fixed RTT finally decomposes.
- **Memory gauges** — `sample_memory()` reads `device.memory_stats()`
  (bytes_in_use / peak) and the host RSS into the registry's Gauge
  primitive after each dispatch and each H2D stream, so `/metrics`,
  `/profile` and the bench rows carry live + peak memory.

`TPU_IR_PROFILE=0` reduces `profiled_jit.__call__` to one flag test and
the raw jit call. Everything here is import-light (no jax at module
import) so `tpu-ir lint` and the obs package stay JAX-free to load.
"""

from __future__ import annotations

import collections
import threading
import time

from ..utils import envvars
from .registry import get_registry
from .trace import record_span

_ENABLED = envvars.get_bool("TPU_IR_PROFILE")
_COST = envvars.get_bool("TPU_IR_PROFILE_COST")
_STORM_N = envvars.get_int("TPU_IR_PROFILE_RECOMPILE_LIMIT")


def configure(enabled: bool | None = None, cost: bool | None = None,
              recompile_limit: int | None = None) -> None:
    """Runtime overrides of the TPU_IR_PROFILE* env knobs (tests)."""
    global _ENABLED, _COST, _STORM_N
    if enabled is not None:
        _ENABLED = enabled
    if cost is not None:
        _COST = cost
    if recompile_limit is not None:
        _STORM_N = max(1, recompile_limit)


def enabled() -> bool:
    return _ENABLED


# -- the jax.monitoring listener (trace vs backend-compile attribution) -----

# jax records these internally around every compilation; the listener
# folds them into the profiled call currently on this thread, so the
# split costs nothing when no profiled call is in flight.
_EVENT_MAP = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_tls = threading.local()
_install_lock = threading.Lock()
_listener_installed = False
# True once the duration listener actually registered: compile DETECTION
# then runs on the thread-local event accumulator — a concurrent
# thread's compile fires events on ITS thread, so warm calls racing a
# compiling thread can never be misattributed (the cache-size delta,
# kept as the no-monitoring fallback, is process-global and could)
_listener_active = False


def _listener(name: str, dur_s: float, **kwargs) -> None:
    acc = getattr(_tls, "acc", None)
    key = _EVENT_MAP.get(name)
    if acc is not None and key is not None:
        acc[key] = acc.get(key, 0.0) + dur_s


def _ensure_listener() -> None:
    """Register the duration listener once per process — lazily, from
    ProfiledJit creation, so importing this module never imports jax."""
    global _listener_installed, _listener_active
    if _listener_installed:
        return
    with _install_lock:
        # claim-then-register: the flag flips under the lock so exactly
        # one caller proceeds to the registration OUTSIDE it (jax calls
        # under a lock are a TPU202 hazard); a failed registration
        # stays claimed — the shim falls back to cache-size deltas and
        # wall-time attribution
        if _listener_installed:
            return
        _listener_installed = True
    try:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_listener)
        _listener_active = True
    except Exception:  # noqa: BLE001 — older jax: fall back to wall
        pass


# -- the per-function compile ledger ----------------------------------------

_store_lock = threading.Lock()
# label -> {"signatures": {sig_key: stats}, "compiles": n, "recompiles": n}
_functions: dict[str, dict] = {}
# monotonic timestamps of recompile events (the /healthz 60 s window)
_recompile_ts: collections.deque = collections.deque(maxlen=4096)


def _sig_atom(a) -> tuple:
    shape = getattr(a, "shape", None)
    if shape is not None and hasattr(a, "dtype"):
        return ("arr", tuple(shape), str(a.dtype))
    if isinstance(a, (tuple, list)):
        return ("seq", tuple(_sig_atom(x) for x in a))
    return ("static", repr(a))


def signature_key(args: tuple, kwargs: dict) -> tuple:
    """The abstract signature jit keys compilation on, approximated
    host-side: (shape, dtype) per array leaf, repr for static values.
    Hashable; stable across calls with identical abstract inputs."""
    return (tuple(_sig_atom(a) for a in args),
            tuple((k, _sig_atom(kwargs[k])) for k in sorted(kwargs)))


def render_signature(sig: tuple) -> str:
    """Human-readable form of a signature key ('f32[64,8], k=10')."""

    def one(atom) -> str:
        kind = atom[0]
        if kind == "arr":
            return f"{atom[2]}[{','.join(str(d) for d in atom[1])}]"
        if kind == "seq":
            return "(" + ", ".join(one(x) for x in atom[1]) + ")"
        return atom[1]

    args, kwargs = sig
    parts = [one(a) for a in args]
    parts += [f"{k}={one(v)}" for k, v in kwargs]
    return ", ".join(parts)


def _record_compile(label: str, sig: tuple, wall_ns: int, acc: dict,
                    cost: dict | None) -> None:
    reg = get_registry()
    trace_s = acc.get("trace", 0.0)
    compile_s = acc.get("compile", 0.0)
    if trace_s == 0.0 and compile_s == 0.0:
        # no monitoring events (old jax): attribute the whole cold call
        compile_s = wall_ns / 1e9
    if trace_s > 0.0:
        record_span("dispatch.trace", int(trace_s * 1e9), fn=label)
    record_span("dispatch.compile", int(compile_s * 1e9), fn=label)
    reg.incr("compile.count")
    reg.observe("compile.time", trace_s + compile_s)
    with _store_lock:
        fn = _functions.setdefault(
            label, {"signatures": {}, "compiles": 0, "recompiles": 0})
        st = fn["signatures"].setdefault(sig, {
            "compiles": 0, "total_compile_s": 0.0, "last_compile_s": 0.0,
            "trace_s": 0.0, "flops": None, "bytes_accessed": None})
        st["compiles"] += 1
        st["total_compile_s"] = round(
            st["total_compile_s"] + trace_s + compile_s, 6)
        st["last_compile_s"] = round(trace_s + compile_s, 6)
        st["trace_s"] = round(st["trace_s"] + trace_s, 6)
        if cost:
            st.update(cost)
        fn["compiles"] += 1
        recompiled = st["compiles"] > 1
        if recompiled:
            fn["recompiles"] += 1
            _recompile_ts.append(time.monotonic())
        storm = st["compiles"] > _STORM_N
        n_sigs = sum(len(f["signatures"]) for f in _functions.values())
        sig_compiles = st["compiles"]
    reg.set_gauge("compile.signatures", n_sigs)
    if recompiled:
        reg.incr("compile.recompiles")
    if storm:
        # the classic silent perf killer: ONE signature compiling over
        # and over (a fresh jax.jit per call, a thrashing cache). The
        # recorder's per-reason rate limit keeps a storm from flooding
        # the disk with its own evidence.
        from .recorder import flight_dump

        flight_dump("recompile_storm", extra={
            "fn": label,
            "signature": render_signature(sig),
            "compiles": sig_compiles,
            "limit": _STORM_N,
        })


class ProfiledJit:
    """A jitted callable with compile observability. Call it exactly
    like the `jax.jit(fn)` it wraps — execution goes through the real
    jit (identical semantics, donation included); the wrapper only
    watches the jit cache and jax's monitoring events."""

    def __init__(self, fun, label: str, jit_kwargs: dict):
        import jax

        self._jit = jax.jit(fun, **jit_kwargs)
        self.label = label
        self.__wrapped__ = fun
        self.__name__ = label
        self.__doc__ = getattr(fun, "__doc__", None)
        self._seen: set = set()   # signatures called through THIS wrapper
        _ensure_listener()

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def clear_cache(self) -> None:
        """Drop the underlying jit cache (tests monkeypatching traced
        globals rely on this). The seen-signature set clears with it:
        the next call of any signature recompiles FOR CAUSE and must
        re-probe cost — but it is not a recompile *event* (the cache
        was emptied deliberately, not thrashed), so the ledger entry
        for the wrapped function resets too."""
        self._jit.clear_cache()
        self._seen.clear()
        with _store_lock:
            _functions.pop(self.label, None)

    def _cache_size(self) -> int:
        try:
            return self._jit._cache_size()
        except Exception:  # noqa: BLE001 — jax internals moved: fall back
            return -1      # to first-seen-signature detection

    @staticmethod
    def _abstract(a):
        """The ShapeDtypeStruct twin of one argument: arrays become
        specs (no data — safe even when the real call DONATED the
        buffer), statics pass through, sequences map recursively (a
        NamedTuple keeps its type, so the kernel sees its fields)."""
        shape = getattr(a, "shape", None)
        if shape is not None and hasattr(a, "dtype"):
            import jax

            return jax.ShapeDtypeStruct(tuple(shape), a.dtype)
        if isinstance(a, tuple) and hasattr(a, "_fields"):
            return type(a)(*(ProfiledJit._abstract(x) for x in a))
        if isinstance(a, (tuple, list)):
            return tuple(ProfiledJit._abstract(x) for x in a)
        return a

    def _cost_probe(self, args: tuple, kwargs: dict,
                    ) -> tuple[dict | None, dict]:
        """Per-executable FLOPs / bytes-accessed from XLA's own cost
        model: one AOT lower+compile over ShapeDtypeStruct specs (the
        jaxpr trace is a cache hit right after the real call; the
        backend compile dedupes against the persistent compilation
        cache when enabled). Never lets a probe failure near the
        dispatch. Also returns the probe's own monitoring durations as
        a backfill for stages the real call got from jax caches."""
        prev = getattr(_tls, "acc", None)
        _tls.acc = acc = {}
        try:
            spec_args = tuple(self._abstract(a) for a in args)
            spec_kwargs = {k: self._abstract(v) for k, v in kwargs.items()}
            compiled = self._jit.lower(*spec_args, **spec_kwargs).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if not isinstance(ca, dict):
                return None, acc
            return {
                "flops": float(ca.get("flops", -1.0)),
                "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
            }, acc
        except Exception:  # noqa: BLE001 — cost is garnish, never a crash
            return None, acc
        finally:
            _tls.acc = prev

    def __call__(self, *args, **kwargs):
        if not _ENABLED:
            return self._jit(*args, **kwargs)
        # steady-state overhead discipline: the cached-signature path
        # costs one thread-local swap and two timestamps — signature
        # hashing happens ONLY when a compile was detected (measured:
        # hashing ~20 tiered-kernel args per dispatch was the dominant
        # shim cost)
        before = -1 if _listener_active else self._cache_size()
        prev = getattr(_tls, "acc", None)
        _tls.acc = acc = {}
        t0 = time.perf_counter_ns()
        try:
            out = self._jit(*args, **kwargs)
        finally:
            _tls.acc = prev
        wall_ns = time.perf_counter_ns() - t0
        if _listener_active:
            # trace/compile events fired on THIS thread during THIS
            # call = a compile of this signature (tracing always runs
            # for a new signature, even on a persistent-cache hit);
            # immune to concurrent compiles on other threads, which
            # land in their own thread-local accumulators
            compiled = bool(acc)
        else:
            after = self._cache_size()
            if after >= 0 and before >= 0:
                compiled = after > before
            else:
                # no cache introspection either (jax internals moved):
                # fall back to first-seen signatures
                compiled = signature_key(args, kwargs) not in self._seen
        if compiled:
            sig = signature_key(args, kwargs)
            first = sig not in self._seen
            self._seen.add(sig)
            cost = None
            if first and _COST:
                # after the real call on purpose: the probe traces over
                # ShapeDtypeStruct specs (jaxpr cache hit, donation-safe)
                cost, probe_acc = self._cost_probe(args, kwargs)
                for key, v in probe_acc.items():
                    acc.setdefault(key, v)
            _record_compile(self.label, sig, wall_ns, acc, cost)
        return out


def profiled_jit(fun=None, *, label: str | None = None, **jit_kwargs):
    """Drop-in `jax.jit` replacement with compile observability: use as
    `@partial(profiled_jit, static_argnames=(...))` or
    `name = profiled_jit(fn, static_argnames=(...))` — every jit kwarg
    passes straight through. The lint AST index recognizes it as a jit
    wrapper, so TPU101-104 hazard analysis of wrapped bodies and their
    static-argument taint is unchanged."""
    if fun is None:
        return lambda f: profiled_jit(f, label=label, **jit_kwargs)
    return ProfiledJit(fun, label or getattr(fun, "__name__", "<fn>"),
                       jit_kwargs)


# -- memory sampling --------------------------------------------------------


def _host_rss_bytes() -> int:
    """Resident set size of this process, without psutil: /proc on
    linux, ru_maxrss (the peak — close enough for a gauge) elsewhere."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    try:
        import resource
        import sys

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss units are platform-defined: KiB on linux/BSD, BYTES
        # on macOS — the one platform that actually reaches this
        # fallback (no /proc); scaling it would inflate the gauge 1024x
        return peak if sys.platform == "darwin" else peak * 1024
    except Exception:  # noqa: BLE001 — exotic platform: no sample
        return 0


def _device_stats() -> dict | None:
    """`memory_stats()` of device 0, or None (CPU backend returns None;
    an UNINITIALIZED backend is never touched — jax.devices() from here
    would otherwise initialize one, and take a chip, for a metrics read)."""
    import sys

    if "jax" not in sys.modules:
        return None
    try:
        import jax
        import jax._src.xla_bridge as xb

        if not xb.backends_are_initialized():
            return None
        return jax.devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — stats are garnish
        return None


_sample_lock = threading.Lock()
_last_sample = 0.0
SAMPLE_MIN_INTERVAL_S = 0.05


def sample_memory(min_interval_s: float | None = None) -> None:
    """One memory sample into the gauges: called by the scorer after
    each device dispatch and by stream_to_device after each upload.
    Rate-limited (default one sample per 50 ms) so a hot single-query
    loop never pays the /proc read per dispatch — the device-side peak
    gauge still cannot miss a spike, because `peak_bytes_in_use` is the
    backend's OWN high-water accumulator, not ours. Pass
    `min_interval_s=0` to force a sample (tests, one-shot snapshots).
    A no-op with profiling disabled."""
    global _last_sample
    if not _ENABLED:
        return
    interval = (SAMPLE_MIN_INTERVAL_S if min_interval_s is None
                else min_interval_s)
    now = time.monotonic()
    with _sample_lock:
        if now - _last_sample < interval:
            return
        _last_sample = now
    reg = get_registry()
    rss = _host_rss_bytes()
    if rss:
        reg.set_gauge("host.rss_bytes", rss)
        reg.update_gauge_max("host.peak_rss_bytes", rss)
    stats = _device_stats()
    if stats:
        in_use = stats.get("bytes_in_use")
        if in_use is not None:
            reg.set_gauge("device.bytes_in_use", in_use)
        peak = stats.get("peak_bytes_in_use", in_use)
        if peak is not None:
            reg.update_gauge_max("device.peak_bytes", peak)


def memory_snapshot() -> dict:
    """Point-in-time memory readout for flight-record headers and the
    /profile report: host RSS plus device memory_stats when a device
    backend is up (None on CPU / uninitialized)."""
    out: dict = {"host_rss_bytes": _host_rss_bytes(), "device": None}
    stats = _device_stats()
    if stats:
        out["device"] = {
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }
    return out


# -- the report surfaces ----------------------------------------------------


def recompiles_last_60s(window_s: float = 60.0) -> int:
    """Recompile events in the trailing window — the /healthz field an
    alerting rule can watch for a storm in progress."""
    cutoff = time.monotonic() - window_s
    with _store_lock:
        return sum(1 for ts in _recompile_ts if ts >= cutoff)


def compile_cache_snapshot() -> dict:
    """The compact compile-ledger totals stamped into flight-record
    headers: enough to see a storm in a post-mortem without the full
    per-signature report."""
    cutoff = time.monotonic() - 60.0
    with _store_lock:
        return {
            "functions": len(_functions),
            "signatures": sum(len(f["signatures"])
                              for f in _functions.values()),
            "compiles": sum(f["compiles"] for f in _functions.values()),
            "recompiles": sum(f["recompiles"]
                              for f in _functions.values()),
            "recompiles_last_60s": sum(
                1 for ts in _recompile_ts if ts >= cutoff),
        }


def profile_report() -> dict:
    """THE profiling view (`tpu-ir profile`, GET /profile): per-function
    per-signature compile counts with wall time and cost_analysis
    FLOPs/bytes, the dispatch time split (trace/compile/device) and
    compile.time histograms, the memory gauges, the recompile window,
    and the newest profiler capture's totals. Per-process, like `tpu-ir
    stats` — meaningful from a serving or bench process, empty from a
    fresh CLI."""
    reg = get_registry()
    snap = reg.snapshot()
    hists = snap.get("histograms", {})
    with _store_lock:
        functions = []
        for label in sorted(_functions):
            fn = _functions[label]
            sigs = [{
                "signature": render_signature(sig),
                **stats,
            } for sig, stats in fn["signatures"].items()]
            sigs.sort(key=lambda s: -s["total_compile_s"])
            functions.append({
                "name": label,
                "compiles": fn["compiles"],
                "recompiles": fn["recompiles"],
                "signatures": sigs,
            })
    dispatch = {
        name: hists[name]
        for name in ("compile.time", "dispatch.trace", "dispatch.compile",
                     "dispatch.device", "dispatch", "kernel")
        if name in hists}
    return {
        "enabled": _ENABLED,
        "functions": functions,
        "compile_counters": {
            "compile.count": snap["counters"].get("compile.count", 0),
            "compile.recompiles": snap["counters"].get(
                "compile.recompiles", 0),
        },
        "recompiles_last_60s": recompiles_last_60s(),
        "dispatch": dispatch,
        # the coalescing scheduler's view (ISSUE 9): batches packed vs
        # solo-flushed, the occupancy distribution (1..top-rung on the
        # bucket scale) and per-slot coalesce wait — read next to the
        # dispatch split above to see what each kernel call amortized
        "batching": {
            "batch.coalesced": snap["counters"].get("batch.coalesced", 0),
            "batch.solo_flush": snap["counters"].get("batch.solo_flush",
                                                     0),
            **{name: hists[name]
               for name in ("batch.occupancy", "batch.wait")
               if name in hists},
        },
        # dynamic pruning (ISSUE 13): the raw scheduling terms behind
        # prune_diag's derived fractions, and the block-max kernels'
        # mask ledger — blocks_masked / blocks_considered is the
        # realized skip fraction, fallback vs saved the engagement rate
        "pruning": {
            name: snap["counters"].get(name, 0)
            for name in ("prune.queries", "prune.queries_hot_free",
                         "prune.blocks_total", "prune.blocks_skip_hot",
                         "blockmax.blocks_considered",
                         "blockmax.blocks_masked",
                         "blockmax.saved_dispatches",
                         "blockmax.fallback_dispatches")
        },
        # the result-cache tier (ISSUE 15): hit/miss/evict/stale
        # counters + derived hit fraction, the lookup-cost histogram,
        # and every live cache's control-plane snapshot — read next to
        # the dispatch split to see what each hit SKIPPED paying
        "cache": _cache_section(snap, hists),
        # the program's own totals for the newest profiler capture
        # (registry.capture_totals), read next to its xplane
        "capture": reg.capture_totals(),
        "gauges": snap.get("gauges", {}),
        "memory": memory_snapshot(),
    }


def _cache_section(snap: dict, hists: dict) -> dict:
    from ..serving.result_cache import cache_counters, live_caches

    out = dict(cache_counters())
    if "cache.lookup" in hists:
        out["cache.lookup"] = hists["cache.lookup"]
    out["caches"] = [c.snapshot() for c in live_caches()]
    return out


def reset_profile() -> None:
    """Forget the compile ledger and recompile window (test isolation —
    wired into obs.reset_all). Wrapper instances keep their own
    seen-signature sets: the underlying jit caches persist too, so a
    signature that stays cached correctly records no new compile."""
    global _last_sample
    with _store_lock:
        _functions.clear()
        _recompile_ts.clear()
    with _sample_lock:
        # the registry reset zeroed the gauges; the next dispatch must
        # re-sample immediately, not wait out the rate limit
        _last_sample = 0.0
