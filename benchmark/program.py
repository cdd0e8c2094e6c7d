"""What the program records about itself, for the per-layer readers:
the totals of the newest profiler capture (`tpu_ir.obs.capture_totals`,
the traced window's), the registry's lifetime histogram sums (the
load, which runs once per process, in set-up), and the phases of the
window's build reports. A program that lacks a span, a counter or a
phase reads as None, never as an error."""

from __future__ import annotations


def _capture() -> dict | None:
    try:
        from tpu_ir import obs
    except ImportError:
        return None
    totals = getattr(obs, "capture_totals", None)
    return totals() if totals is not None else None


def capture_s(name: str) -> float | None:
    """Seconds the capture's spans of `name` summed, or None."""
    cap = _capture()
    h = (cap or {}).get("histograms", {}).get(name)
    return h["sum_s"] if h and h["count"] else None


def capture_count(name: str) -> int | None:
    """The capture's total of counter `name`, or None."""
    cap = _capture()
    if cap is None or name not in cap.get("counters", {}):
        return None
    return cap["counters"][name]


def lifetime_s(name: str) -> float | None:
    """Seconds the process's spans of `name` summed, or None where it
    has none."""
    try:
        from tpu_ir.obs import get_registry
    except ImportError:
        return None
    state = get_registry().hist_state().get(name)
    if state is None or not sum(state[0]):
        return None
    return state[1]


def load_share(data, stage: str) -> float | None:
    """Share of the `load` span (Scorer.load) in one of its stages."""
    if data["trace"] is None:
        return None
    load, part = lifetime_s("load"), lifetime_s(stage)
    if not load or part is None:
        return None
    return 100.0 * part / load


def build_share(data, phases: tuple) -> float | None:
    """Share of the window's build wall time in build report phases."""
    b = data["counters"].get("builds")
    if data["trace"] is None or not b or not b["walls"]:
        return None
    if not any(p in t for t in b["timings"] for p in phases):
        return None
    return 100.0 * sum(t.get(p, 0.0) for t in b["timings"]
                       for p in phases) / sum(b["walls"])
