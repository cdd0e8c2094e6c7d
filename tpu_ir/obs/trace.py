"""Flight-recorder tracing: per-request span trees in a bounded ring.

The reference engine's observability was the Hadoop JobTracker page — a
frozen table of counters per job. Counters say WHAT happened; they never
say where one request spent its time or what the last requests before a
breach looked like. This module is the missing half:

- `trace(name, **attrs)` — a context manager recording one span:
  monotonic start, duration, thread id, free-form attrs, the exception
  (if one escaped), and child spans. Nesting via a thread-local stack
  builds the tree; the serving path's tree is
  request -> (ladder, admission_wait, breaker, dispatch -> kernel*,
  fallback), the build path's is build.<phase> per JobReport phase.
- Every span's duration also lands in the TelemetryRegistry histogram of
  the same name — spans and latency distributions are one instrument.
- Completed ROOT spans go into a process-wide bounded ring buffer
  (`recent_traces()`), the flight recorder's source: on an invariant
  breach the last N request trees are right there, no log scraping.

Overhead discipline: `TPU_IR_TRACE=0` turns `trace()` into a single
flag test returning a shared no-op (pinned near-free by a tight-loop
test); enabled, a span costs two perf_counter_ns calls, one small object
and one locked histogram increment. `TPU_IR_TRACE_SAMPLE=N` keeps every
N-th root trace in the ring (histograms always record — sampling bounds
ring churn, not measurement).

Cross-thread spans: faults.run_with_deadline re-parents its worker
thread onto the caller's current span via `attach()`, so a deadlined
dispatch's kernel spans stay inside the request tree instead of
surfacing as orphan roots.

Profiler captures: while a jax.profiler capture records (`--profile
DIR`, jax.profiler.start_trace, or the profiler server), every span
also opens a TraceMe of its own name on its own thread, carrying its
scalar attrs as metadata, so the program's spans sit on the xplane's
host plane on the same clock as the device ops they wait on; its
duration also lands in the registry's capture ledger
(`capture_totals()`). The check is one TraceMe.is_enabled() call per
span, made only once jax is imported. `record_span` (timed after the
fact) is not mirrored: jax's own compile events are in the capture.
"""

from __future__ import annotations

import collections
import threading
import time

from ..utils import envvars
from .registry import get_registry, trace_me

_tls = threading.local()
_ring_lock = threading.Lock()

_ENABLED = envvars.get_bool("TPU_IR_TRACE")
_SAMPLE_N = envvars.get_int("TPU_IR_TRACE_SAMPLE")
_RING = collections.deque(maxlen=envvars.get_int("TPU_IR_TRACE_RING"))
_root_seq = 0
_capture_check = get_registry().capture_check
_observe = get_registry().observe
# Root-close hooks: callables fired with every COMPLETED root span,
# unconditionally — BEFORE and independent of the ring's 1-in-N
# sampling, because a subscriber (obs/disttrace.py) applies its own
# keep/drop policy (tail-keeping must see the roots sampling would
# discard). Hooks must never raise and must be cheap: they run inline
# on the request thread at root close.
_root_hooks: list = []


def configure(enabled: bool | None = None, sample: int | None = None,
              ring_capacity: int | None = None) -> None:
    """Runtime overrides of the TPU_IR_TRACE* env knobs (tests, REPLs)."""
    global _ENABLED, _SAMPLE_N, _RING
    if enabled is not None:
        _ENABLED = enabled
    if sample is not None:
        _SAMPLE_N = max(1, sample)
    if ring_capacity is not None:
        with _ring_lock:
            _RING = collections.deque(_RING, maxlen=max(1, ring_capacity))


def enabled() -> bool:
    return _ENABLED


class Span:
    """One timed region; also the context manager that records it."""

    __slots__ = ("name", "attrs", "start_ns", "dur_ns", "thread_id",
                 "thread_name", "wall_time", "children", "error",
                 "_is_root", "_tm")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.start_ns = 0
        self.dur_ns = 0
        t = threading.current_thread()
        self.thread_id = t.ident or 0
        self.thread_name = t.name
        self.wall_time = 0.0
        self.children: list[Span] = []
        self.error: str | None = None
        self._is_root = False
        self._tm = None  # the open TraceMe while a capture records

    def set(self, key: str, value) -> None:
        """Annotate the span (service level, breaker state, ...)."""
        self.attrs[key] = value

    def __enter__(self) -> "Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._is_root = not stack
        if self._is_root:
            self.wall_time = time.time()
        stack.append(self)
        if _capture_check():
            self._tm = _capture_span(self.name, self.attrs)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur_ns = time.perf_counter_ns() - self.start_ns
        tm = self._tm
        if tm is not None:
            tm.__exit__(None, None, None)
            self._tm = None
        if exc is not None:
            self.error = repr(exc)
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            parent.children.append(self)
        _observe(self.name, self.dur_ns / 1e9, tm is not None)
        if self._is_root:
            _push_root(self)
        return False

    def to_dict(self) -> dict:
        """JSON-ready tree. Copies child/attr containers first: an
        abandoned deadline thread may still be appending to a parent
        that is already being serialized."""
        out = {
            "name": self.name,
            "start_ns": self.start_ns,
            "dur_us": round(self.dur_ns / 1e3, 3),
            "thread_id": self.thread_id,
            "thread": self.thread_name,
        }
        attrs = dict(self.attrs)
        if attrs:
            out["attrs"] = attrs
        if self.error is not None:
            out["error"] = self.error
        if self.wall_time:
            out["time"] = time.strftime(
                "%Y-%m-%dT%H:%M:%S", time.localtime(self.wall_time))
        children = tuple(self.children)
        if children:
            out["children"] = [c.to_dict() for c in children]
        return out


class _NullSpan:
    """The disabled-tracing singleton: enter/exit/set are no-ops."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        pass


_NULL = _NullSpan()


def trace(name: str, **attrs):
    """Open a span (context manager). With tracing disabled this is one
    flag test and a shared no-op object — safe on any hot path."""
    if not _ENABLED:
        return _NULL
    return Span(name, attrs)


def record_span(name: str, dur_ns: int, **attrs) -> None:
    """Record an externally-timed region as a completed child span of
    this thread's current span, plus the histogram observation of the
    same name — the profiling shim's compile/trace sub-spans, whose
    durations come from jax's own monitoring events rather than a
    context manager. Follows trace()'s discipline: with tracing
    disabled this is one flag test and nothing is recorded."""
    if not _ENABLED:
        return
    get_registry().observe(name, dur_ns / 1e9)
    span = Span(name, attrs)
    span.dur_ns = int(dur_ns)
    span.start_ns = time.perf_counter_ns() - span.dur_ns
    stack = getattr(_tls, "stack", None)
    parent = stack[-1] if stack else None
    if parent is not None:
        parent.children.append(span)
    else:
        _push_root(span)


def current_span() -> Span | None:
    """This thread's innermost open span (None outside any trace), the
    handle `attach()` re-parents worker threads onto."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def current_root() -> Span | None:
    """This thread's OUTERMOST open span — the request root the
    slow-query trap (obs/querylog.py) serializes while the request is
    still in flight (its tree won't reach the ring until it closes;
    to_dict() copies child lists, so a mid-flight snapshot is safe)."""
    stack = getattr(_tls, "stack", None)
    return stack[0] if stack else None


def current_tree() -> dict | None:
    """current_root() as a JSON-ready tree with the spans still open
    under it on this thread nested in, each the last child of the one
    before and marked "open" — a mid-flight snapshot of where the
    thread is (to_dict alone shows only closed children)."""
    stack = getattr(_tls, "stack", None)
    if not stack:
        return None
    tree = node = stack[0].to_dict()
    for span in stack[1:]:
        child = span.to_dict()
        child["open"] = True
        node.setdefault("children", []).append(child)
        node = child
    return tree


class _Attach:
    __slots__ = ("_parent", "_saved")

    def __init__(self, parent):
        self._parent = parent
        self._saved = None

    def __enter__(self):
        self._saved = getattr(_tls, "stack", None)
        _tls.stack = [self._parent] if self._parent is not None else []
        return self

    def __exit__(self, *exc):
        _tls.stack = self._saved if self._saved is not None else []
        return False


def attach(parent: Span | None):
    """Context manager making `parent` (a span from ANOTHER thread) the
    current span on this thread — spans opened inside become its
    children instead of orphan roots. attach(None) just isolates."""
    return _Attach(parent)


def add_root_hook(fn) -> None:
    """Subscribe `fn(span)` to every completed root span (idempotent:
    re-adding the same callable is a no-op). The hook fires before ring
    sampling — subscribers see ALL roots."""
    if fn not in _root_hooks:
        _root_hooks.append(fn)


def remove_root_hook(fn) -> None:
    if fn in _root_hooks:
        _root_hooks.remove(fn)


def _push_root(span: Span) -> None:
    global _root_seq
    for hook in tuple(_root_hooks):
        try:
            hook(span)
        except Exception:  # noqa: BLE001 — a hook bug must not fail the
            pass  # request whose root just closed
    with _ring_lock:
        _root_seq += 1
        if _root_seq % _SAMPLE_N == 0:
            _RING.append(span)


def recent_traces() -> list[Span]:
    """The ring's current contents, oldest first."""
    with _ring_lock:
        return list(_RING)


def clear_traces() -> None:
    with _ring_lock:
        _RING.clear()


def _capture_span(name: str, attrs: dict):
    """Open the TraceMe that puts a span into the running capture: its
    own name, its str/int/float attrs as metadata."""
    tm = trace_me()(name, **{k: v for k, v in attrs.items()
                            if isinstance(v, (str, int, float))})
    tm.__enter__()
    return tm


def capture_totals() -> dict:
    """The program's own totals for the newest profiler capture (see
    TelemetryRegistry.capture_totals): every span and histogram
    observation and every counter increment made while it recorded."""
    return get_registry().capture_totals()

