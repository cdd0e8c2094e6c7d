"""The yardstick's trace side: profiler capture, the reduction from an
xplane trace to device busy time, per-module device time, per-op device
time and named idle gaps, and the peaks table.

Busy is the union of the intervals in which an XLA op ran on a device,
clipped to the measured window (the host span `bench.window`), averaged
over the devices that ran anything. Module time is the sum of the
`XLA Modules` events of a module name (`jit_<function>`, the numeric
suffix dropped). On the CPU backend, used by benchmark/tests only, the
ops are the host events that carry an `hlo_module` stat.

On the v5e the device plane's clock ran about 1 ms ahead of the host's
in the recorded trace (tests/data/tiny_tpu.xplane.pb): clipping to the
host's window span loses at most that much of a 30 s window.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def peaks(device_kind: str) -> dict:
    """The device's published peaks; a device not in the table is an
    error, not a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce_trace(path: str) -> dict:
    """Reduce one .xplane.pb file to the numbers the metric readers use:
    window_s, busy_s, devices, module_s {name: s}, op_s {name: s},
    gaps [(seconds, host span name)] longest first, spans {name: s}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host_spans = []
    per_dev_ops: dict[str, list] = defaultdict(list)
    per_dev_mods: dict[str, list] = defaultdict(list)
    cpu_ops = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    per_dev_ops[plane.name] += [
                        (e.start_ns, e.end_ns, e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    per_dev_mods[plane.name] += [
                        (e.start_ns, e.end_ns, _SUFFIX.sub("", e.name))
                        for e in line.events]
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.end_ns)
                elif e.name.startswith("bench."):
                    host_spans.append((e.start_ns, e.end_ns, e.name))
                else:
                    stats = dict(e.stats)
                    if "hlo_module" in stats and "hlo_op" in stats:
                        cpu_ops.append((e.start_ns, e.end_ns, e.name,
                                        stats["hlo_module"]))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    w0, w1 = window
    if not per_dev_ops and cpu_ops:  # CPU backend (tests only)
        per_dev_ops["/host:CPU"] = [(s, e, n) for s, e, n, _ in cpu_ops]
        by_mod = defaultdict(list)
        for s, e, _, m in cpu_ops:
            by_mod[m].append((s, e))
        per_dev_mods["/host:CPU"] = [
            (s, e, m) for m, iv in by_mod.items() for s, e in _union(iv)]
    op_s: dict[str, float] = defaultdict(float)
    module_s: dict[str, float] = defaultdict(float)
    busy = []
    merged_all = []
    for dev, ops in per_dev_ops.items():
        iv = []
        for s, e, name in ops:
            s, e = _clip(s, e, w0, w1)
            if e > s:
                iv.append((s, e))
                op_s[name] += (e - s) / len(per_dev_ops) * 1e-9
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged))
        merged_all.append(merged)
    for dev, mods in per_dev_mods.items():
        for s, e, name in mods:
            s, e = _clip(s, e, w0, w1)
            if e > s:
                module_s[name] += (e - s) / max(len(per_dev_mods), 1) * 1e-9
    gaps = []
    if merged_all:
        edges = [w0] + [x for s, e in merged_all[0] for x in (s, e)] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _span_at(host_spans, (a + b) / 2)))
    gaps.sort(reverse=True)
    spans: dict[str, float] = defaultdict(float)
    for s, e, name in host_spans:
        s, e = _clip(s, e, w0, w1)
        if e > s:
            spans[name] += (e - s) * 1e-9
    n_dev = len(busy)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": (sum(busy) / n_dev * 1e-9) if n_dev else 0.0,
        "devices": n_dev,
        "module_s": dict(module_s),
        "op_s": dict(op_s),
        "gaps": [(g * 1e-9, name) for g, name in gaps],
        "spans": dict(spans),
    }


def _span_at(spans, t) -> str:
    """Innermost benchmark span covering time t, or 'no span'."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "no span"


def breakdown(red: dict) -> dict:
    """The ten device ops that took most time, and the ten longest idle
    gaps named by what the host was doing."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[name, g] for g, name in red["gaps"][:10]]}


def idle_by_span(red: dict) -> list:
    """Idle seconds summed by the host span they fell in."""
    by_name: dict[str, float] = defaultdict(float)
    for g, name in red["gaps"]:
        by_name[name] += g
    return sorted(([n, s] for n, s in by_name.items()),
                  key=lambda kv: -kv[1])[:10]


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one xplane file under {trace_dir}, "
                         f"found {len(found)}")
    return found[0]
