"""What every cell shares: the cell's files found by name, the device
check, the measured window (with the profiler on in a traced run), the
per-layer metric readers, and the result line.

A cell is an entry of BENCHMARK.json's `workloads`: it names a
configuration (benchmark/configs/<config>.json) and a traffic mix
(benchmark/traffic/<traffic>.json). The mix's `kind` names the driver
(benchmark/kinds/<kind>.py), and each per-layer metric is read by
benchmark/metrics/<metric>.py. A new configuration, mix or metric is a
new file and a new entry; no file here changes.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def log(msg: str) -> None:
    """An earlier line of standard output (never the result line)."""
    print(msg, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, config, traffic) for a workload name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, cfg["file"])
    traffic = load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def metrics_for(bench: dict, cell: dict, kind: str) -> list[dict]:
    """The cell's end-to-end (`kind`='end_to_end') or per-layer metrics:
    those that list the cell, or list no cells and move a metric the
    cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def read_metric(name: str, data: dict):
    """Run benchmark/metrics/<name>.py's read(data): a number, or None
    where it finds nothing to read."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(data)


class CompileMeter:
    """Backend compiles (persistent-cache reads included) seen by JAX,
    from jax.monitoring (chip_smoke.py's meter, PR 21)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                self.programs += 1


class Run:
    """One run of one cell: its arguments and files, the scratch
    directory, and what the driver reports."""

    def __init__(self, args, t_process: float, allow_cpu: bool = False,
                 overrides: dict | None = None):
        self.args = args
        self.t_process = t_process
        self.bench, self.cell, self.config, self.traffic = find_cell(
            args.workload)
        for key, val in (overrides or {}).items():
            (self.traffic if key in self.traffic else self.config)[key] = val
        self.allow_cpu = allow_cpu
        self.device = device_info(self.cell["chips"], allow_cpu)
        self.meter = CompileMeter()
        self.tmp = tempfile.mkdtemp(prefix="bench-")
        self.end_to_end: dict[str, float] = {}
        self.data: dict = {"counters": {}, "work": {}, "trace": None,
                           "peaks": None}
        self.attempted = self.failed = 0
        self.tally = None
        self.window_s = None
        self.compiles_in_window = None
        self._trace_dir = os.path.join(self.tmp, "trace")

    def span(self, name: str):
        """A host span `bench.<name>` in the profiler's trace; with the
        profiler off it costs one check."""
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    @contextlib.contextmanager
    def window(self):
        """The measured window: compiles inside it are counted, and with
        --trace 1 the profiler records it."""
        import jax

        from . import tracing

        self.end_to_end["setup_s"] = time.perf_counter() - self.t_process
        log(f"set-up: {self.end_to_end['setup_s']:.6f} s, backend compiles "
            f"{self.meter.programs} ({self.meter.seconds:.3f} s)")
        c0 = self.meter.programs
        if self.args.trace:
            jax.profiler.start_trace(self._trace_dir)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            if self.args.trace:
                jax.profiler.stop_trace()
            self.compiles_in_window = self.meter.programs - c0
            log(f"window: {self.window_s:.6f} s, compiles inside it: "
                f"{self.compiles_in_window}")

    def read_memory_peak(self) -> None:
        import jax

        peak = 0
        for d in jax.local_devices()[: self.cell["chips"]]:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.device["memory_peak_bytes"] = peak

    def result(self) -> dict:
        """The last line: metrics of the cell's end-to-end set (untraced)
        or per-layer set (traced), and the numbers compared, last."""
        from . import tracing

        out = {"correct": bool(self.tally is not None
                               and self.tally.correct()),
               "attempted": int(self.attempted),
               "failed": int(self.failed)}
        metrics = {}
        if self.args.trace:
            red = tracing.reduce_trace(tracing.find_xplane(self._trace_dir))
            self.data["trace"] = red
            self.data["peaks"] = (tracing.peaks(self.device["kind"])
                                  if self.device["platform"] == "tpu"
                                  else None)
            self.device["busy_s"] = red["busy_s"]
            self.device["window_s"] = red["window_s"]
            log("trace: " + json.dumps({
                k: red[k] for k in ("window_s", "busy_s", "devices")}))
            log("trace modules (s): " + json.dumps(dict(sorted(
                red["module_s"].items(), key=lambda kv: -kv[1])[:20])))
            log("idle by host span (s): "
                + json.dumps(tracing.idle_by_span(red)))
            for m in metrics_for(self.bench, self.cell, "per_layer"):
                val = read_metric(m["name"], self.data)
                if val is not None:
                    metrics[m["name"]] = {"value": float(val),
                                          "unit": m["unit"]}
            out["breakdown"] = tracing.breakdown(red)
        else:
            for m in metrics_for(self.bench, self.cell, "end_to_end"):
                metrics[m["name"]] = {"value": float(self.end_to_end[
                    m["name"]]), "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = self.device
        out["checks"] = (self.tally.checks() if self.tally is not None
                         else {})
        return out

    def close(self) -> None:
        shutil.rmtree(self.tmp, ignore_errors=True)


def device_info(chips: int, allow_cpu: bool) -> dict:
    """The devices JAX found; no TPU, or too few chips, ends the run
    with no result (only benchmark/tests pass allow_cpu)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        print(f"benchmark: no TPU: JAX found {platform} "
              f"({devices[0].device_kind})", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": 0}


def driver(kind: str):
    return importlib.import_module(f"benchmark.kinds.{kind}")
