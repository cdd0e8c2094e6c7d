"""Acceptance suite for the unified telemetry layer (ISSUE 3).

Pins the four contracts of tpu_ir.obs:

- histogram bucket math: boundary membership, percentile estimates
  within one bucket of exact, merge == histogram of concatenation;
- span trees: nesting, thread ids, cross-thread re-parenting through
  the deadline dispatcher, the bounded/sampled trace ring, and the
  TPU_IR_TRACE=0 near-no-op + the <=10% serving-overhead guard;
- coverage-by-construction: every fault-injection site found in the
  SOURCE has a declared fault.<site> counter, every service level the
  ladder can emit has a declared request.<level> histogram (no silently
  untelemetered failure path);
- the flight recorder: a forced soak invariant breach writes a JSONL
  artifact holding the offending request's full span tree plus a
  registry snapshot.
"""

import json
import math
import random
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import tpu_ir
import tpu_ir.faults as faults
from tpu_ir import obs
from tpu_ir.index.streaming import build_index_streaming
from tpu_ir.obs.histogram import (
    BOUNDS,
    NUM_BUCKETS,
    LatencyHistogram,
    bucket_index,
)
from tpu_ir.search import Scorer
from tpu_ir.serving import ServingConfig, ServingFrontend, run_soak
from tpu_ir.serving.soak import make_queries
from tpu_ir.utils.report import JobReport, recovery_counters

WORDS = ("salmon fishing river bears honey quick brown fox lazy dog "
         "market investor asset bond stock season rain forest".split())


@pytest.fixture(autouse=True)
def _restore_trace_config():
    """Tests below flip the runtime trace knobs; put the defaults back
    (the registry/ring themselves are reset by conftest's autouse
    telemetry fixture)."""
    yield
    obs.configure(enabled=True, sample=1, ring_capacity=64)
    faults.clear()


@pytest.fixture(scope="module")
def index_dir(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("obs")
    body = []
    for i in range(120):
        text = " ".join(WORDS[(i + j) % len(WORDS)]
                        for j in range(3 + (i % 7)))
        body.append(f"<DOC>\n<DOCNO> D-{i:04d} </DOCNO>\n<TEXT>\n"
                    f"{text}\n</TEXT>\n</DOC>\n")
    corpus = tmp / "corpus.trec"
    corpus.write_text("".join(body))
    out = str(tmp / "idx")
    build_index_streaming([str(corpus)], out, k=1, num_shards=3,
                          batch_docs=40, chargram_ks=[])
    return out


@pytest.fixture(scope="module")
def scorer(index_dir):
    s = Scorer.load(index_dir, layout="sparse")
    # warm every compile class the tests dispatch, so span timings and
    # the overhead guard measure serving, not XLA compilation
    s.search_batch(["salmon fishing"], k=5, scoring="bm25")
    s.search_batch(["salmon fishing"], k=5, scoring="tfidf")
    s.search_batch(["salmon fishing"], k=5, rerank=25)
    return s


# ---------------------------------------------------------------------------
# histogram bucket math
# ---------------------------------------------------------------------------


def test_bucket_boundaries_land_in_their_bucket():
    """Bucket i is (BOUNDS[i-1], BOUNDS[i]]: an exact boundary value
    belongs to the bucket it bounds, the next float up to the next."""
    assert bucket_index(0.0) == 0
    assert bucket_index(-1.0) == 0          # garbage clamps, never raises
    for i, b in enumerate(BOUNDS):
        assert bucket_index(b) == i
        assert bucket_index(math.nextafter(b, math.inf)) == \
            min(i + 1, NUM_BUCKETS - 1)
    assert bucket_index(1e9) == NUM_BUCKETS - 1   # overflow bucket


def test_percentiles_within_one_bucket_of_exact():
    rng = random.Random(42)
    h = LatencyHistogram()
    samples = [rng.lognormvariate(-7.0, 2.0) for _ in range(5000)]
    for s in samples:
        h.observe(s)
    for q in (50, 95, 99):
        est = h.percentile(q)
        exact = float(np.percentile(samples, q))
        assert abs(bucket_index(est) - bucket_index(exact)) <= 1, \
            f"p{q}: estimate {est} vs exact {exact}"


def test_merge_equals_histogram_of_concatenation():
    rng = random.Random(7)
    a = [rng.expovariate(100.0) for _ in range(800)]
    b = [rng.lognormvariate(-4.0, 1.5) for _ in range(1200)]
    ha, hb, hc = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    for s in a:
        ha.observe(s)
    for s in b:
        hb.observe(s)
    for s in a + b:
        hc.observe(s)
    ha.merge(hb)
    counts_m, sum_m = ha.state()
    counts_c, sum_c = hc.state()
    assert counts_m == counts_c
    assert sum_m == pytest.approx(sum_c)
    assert ha.summary()["count"] == len(a) + len(b)


def test_empty_histogram_summary_is_well_formed():
    s = LatencyHistogram().summary()
    assert s["count"] == 0
    assert s["p50_ms"] is None and s["p99_ms"] is None


# ---------------------------------------------------------------------------
# spans + the trace ring
# ---------------------------------------------------------------------------


def test_span_tree_nesting_thread_ids_and_histograms():
    with obs.trace("outer", kind="test") as root:
        root.set("extra", 1)
        with obs.trace("mid"):
            with obs.trace("leaf"):
                pass
        with obs.trace("mid2"):
            pass
    traces = obs.recent_traces()
    assert len(traces) == 1
    t = traces[0]
    assert t.name == "outer" and t.attrs == {"kind": "test", "extra": 1}
    assert [c.name for c in t.children] == ["mid", "mid2"]
    assert t.children[0].children[0].name == "leaf"
    assert t.thread_id == threading.get_ident()
    assert t.dur_ns >= t.children[0].dur_ns >= 0
    d = t.to_dict()
    assert d["children"][0]["children"][0]["name"] == "leaf"
    assert "time" in d            # roots carry a wall-clock stamp
    # every span's duration also landed in the same-named histogram
    reg = obs.get_registry()
    for name in ("outer", "mid", "leaf", "mid2"):
        assert reg.histogram(name).count == 1


def test_span_records_escaping_exception():
    with pytest.raises(ValueError):
        with obs.trace("doomed"):
            raise ValueError("the reason")
    t = obs.recent_traces()[-1]
    assert t.name == "doomed" and "the reason" in t.error


def test_deadline_worker_spans_attach_to_caller_tree():
    """faults.run_with_deadline runs fn on a worker thread; its spans
    must re-parent onto the caller's request span, not surface as
    orphan roots."""
    def work():
        with obs.trace("inner"):
            time.sleep(0.005)
        return 42

    with obs.trace("req") as root:
        assert faults.run_with_deadline(work, deadline_s=5.0) == 42
    traces = obs.recent_traces()
    assert [t.name for t in traces] == ["req"]   # no orphan root
    inner = traces[0].children[0]
    assert inner.name == "inner"
    assert inner.thread_id != root.thread_id


def test_trace_ring_is_bounded_and_sampled():
    obs.configure(ring_capacity=8)
    for i in range(20):
        with obs.trace(f"r{i}"):
            pass
    names = [t.name for t in obs.recent_traces()]
    assert names == [f"r{i}" for i in range(12, 20)]
    obs.clear_traces()
    obs.configure(sample=3, ring_capacity=64)
    for i in range(9):
        with obs.trace(f"s{i}"):
            pass
    assert len(obs.recent_traces()) == 3        # every 3rd root kept
    # histograms record regardless of ring sampling
    assert obs.get_registry().histogram("s1").count == 1


def test_disabled_tracing_is_near_noop():
    """TPU_IR_TRACE=0: trace() is one flag test returning a shared
    no-op — a tight loop must be effectively free (generous bound) and
    leave no state anywhere."""
    obs.configure(enabled=False)
    with obs.trace("off") as sp:     # the null span still quacks
        sp.set("k", "v")
    assert obs.recent_traces() == []
    assert obs.get_registry().histogram("off").count == 0
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.trace("off"):
            pass
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"{n} disabled spans took {dt:.3f}s"


def test_disabled_tracing_silences_request_histograms(scorer):
    """TPU_IR_TRACE=0 turns off ALL latency histograms — the span-fed
    stage ones AND the frontend's direct request.<level> observes —
    while the serving counters keep counting (the documented split)."""
    obs.configure(enabled=False)
    frontend = ServingFrontend(scorer)
    res = frontend.search("salmon fishing", k=5)
    assert res.level == "full"
    reg = obs.get_registry()
    assert reg.histogram("request.full").count == 0
    assert reg.histogram("dispatch").count == 0
    assert reg.get("serving.submitted") == 1     # counters stay live


def test_tracing_overhead_within_ten_percent_of_disabled(scorer):
    """The overhead guard: a 200-query CPU serving soak with tracing
    enabled (default sampling) stays within 10% of tracing-disabled
    (plus a small absolute slack so scheduler noise on a loaded CI box
    cannot flake a sub-second measurement)."""
    reqs = make_queries(scorer, 200, seed=7)
    frontend = ServingFrontend(scorer, ServingConfig(
        max_concurrency=4, max_queue=16))

    def soak_once() -> float:
        t0 = time.perf_counter()
        for r in reqs:
            frontend.search(r["text"], k=r["k"], scoring=r["scoring"],
                            rerank=r["rerank"])
        return time.perf_counter() - t0

    soak_once()                      # warm every query shape
    timings = {}
    for enabled in (True, False):
        obs.configure(enabled=enabled)
        timings[enabled] = min(soak_once() for _ in range(2))
    obs.configure(enabled=True)
    assert timings[True] <= timings[False] * 1.10 + 0.15, (
        f"tracing overhead too high: traced {timings[True]:.3f}s vs "
        f"untraced {timings[False]:.3f}s")


# ---------------------------------------------------------------------------
# registry: unification, reset, exports
# ---------------------------------------------------------------------------


def test_counter_aliases_are_registry_views():
    reg = obs.get_registry()
    recovery_counters().incr("retries", 3)
    assert reg.get("recovery.retries") == 3
    assert recovery_counters().snapshot()["retries"] == 3
    reg.incr("recovery.quarantined")
    assert recovery_counters().get("quarantined") == 1
    # the alias reset clears ONLY its namespace
    reg.incr("serving.submitted", 5)
    recovery_counters().reset()
    assert recovery_counters().snapshot() == {}
    assert reg.get("serving.submitted") == 5


def test_snapshot_reset_stops_bleed_through():
    reg = obs.get_registry()
    reg.incr("serving.submitted", 4)
    reg.observe("dispatch", 0.01)
    first = reg.snapshot(reset=True)
    assert first["counters"]["serving.submitted"] == 4
    assert first["histograms"]["dispatch"]["count"] == 1
    second = reg.snapshot()
    assert "serving.submitted" not in second["counters"]
    assert second["histograms"]["dispatch"]["count"] == 0
    # declared names survive a reset at zero (presence is the contract)
    assert "fault.score.hang" in second["counters"]


def test_fault_fires_land_in_registry():
    faults.install(faults.parse_plan("score.device_loss:first@2"))
    faults.should_fire("score.device_loss")
    faults.should_fire("score.device_loss")
    faults.should_fire("score.device_loss")   # spec exhausted: no fire
    assert obs.get_registry().get("fault.score.device_loss") == 2


def test_jobreport_phases_feed_build_histograms():
    rep = JobReport("UnitTestJob")
    with rep.phase("tokenize"):
        time.sleep(0.001)
    with rep.phase("tokenize"):
        pass
    assert obs.get_registry().histogram("build.tokenize").count == 2
    assert rep.timings_s["tokenize"] > 0
    roots = [t.name for t in obs.recent_traces()]
    assert roots.count("build.tokenize") == 2


def test_prometheus_exposition_shape():
    reg = obs.get_registry()
    reg.incr("serving.submitted", 2)
    reg.observe("dispatch", 0.003)
    text = reg.prometheus_text()
    assert '# TYPE tpu_ir_events_total counter' in text
    assert 'tpu_ir_events_total{name="serving.submitted"} 2' in text
    assert '# TYPE tpu_ir_stage_latency_seconds histogram' in text
    # every family carries a # HELP line immediately before its # TYPE
    lines = text.splitlines()
    for family in ("tpu_ir_events_total", "tpu_ir_gauge",
                   "tpu_ir_stage_latency_seconds"):
        help_ln = [i for i, ln in enumerate(lines)
                   if ln.startswith(f"# HELP {family} ")]
        assert len(help_ln) == 1, f"missing # HELP for {family}"
        assert lines[help_ln[0] + 1].startswith(f"# TYPE {family} ")
    assert 'le="+Inf"}' in text
    assert 'tpu_ir_stage_latency_seconds_count{stage="dispatch"} 1' in text
    # buckets are cumulative: +Inf count equals the _count line
    disp = [ln for ln in text.splitlines() if 'stage="dispatch"' in ln]
    inf = [ln for ln in disp if 'le="+Inf"' in ln][0]
    assert inf.rsplit(" ", 1)[1] == "1"


# ---------------------------------------------------------------------------
# coverage by construction (the static-analysis-style tests)
# ---------------------------------------------------------------------------

# PR 3's regex-based source scans for fault-site and service-level
# coverage now live in tpu_ir/lint/contracts.py (ISSUE 6) as AST-precise
# contract passes shared with `tpu-ir lint`; these tests are thin
# wrappers pinning (a) the passes still SEE the package (a rotted scan
# reports nothing, which must fail here, not pass silently) and (b) the
# runtime registry honors what the passes verified statically.


@pytest.fixture(scope="module")
def _lint_index():
    from tpu_ir.lint import PackageIndex

    pkg = Path(tpu_ir.__file__).parent
    return PackageIndex(str(pkg), rel_root=str(pkg.parent))


def test_every_injection_site_in_source_is_declared_and_registered(
        _lint_index):
    """Every fault-injection call site found in the source must be in
    obs.FAULT_SITES AND have a pre-registered fault.<site> counter — a
    failure path cannot exist untelemetered. (Logic: lint TPU304.)"""
    from tpu_ir.lint import contracts

    found = contracts.collect_fault_sites(_lint_index)
    assert found, "no injection sites found — the lint scan rotted"
    violations = [f for f in contracts.check(_lint_index)
                  if f.rule == "TPU304"]
    assert not violations, violations
    names = set(obs.get_registry().counter_names())
    for site in obs.FAULT_SITES:
        assert f"fault.{site}" in names


def test_every_service_level_has_a_request_histogram(_lint_index):
    """Every LEVEL_* the frontend's ladder can emit must appear in the
    declared histogram label set (request.<level>) and be registered.
    (Logic: lint TPU305's service-level drift check.)"""
    from tpu_ir.lint import contracts

    levels = contracts.collect_service_levels(_lint_index)
    assert levels == set(obs.SERVICE_LEVELS)
    violations = [f for f in contracts.check(_lint_index)
                  if f.rule == "TPU305"]
    assert not violations, violations
    registered = set(obs.get_registry().histogram_names())
    for lv in levels:
        assert f"request.{lv}" in obs.DECLARED_HISTOGRAMS
        assert f"request.{lv}" in registered


def test_request_stage_histograms_are_declared():
    registered = set(obs.get_registry().histogram_names())
    for stage in ("admission_wait", "ladder", "breaker", "dispatch",
                  "kernel", "fallback"):
        assert stage in obs.REQUEST_STAGES
        assert stage in registered


# ---------------------------------------------------------------------------
# the serving span tree + latency breakdown
# ---------------------------------------------------------------------------


def test_request_span_tree_and_level_histogram(scorer):
    frontend = ServingFrontend(scorer)
    res = frontend.search("salmon fishing", k=5)
    assert res.level == "full"
    req = [t for t in obs.recent_traces() if t.name == "request"][-1]
    child_names = [c.name for c in req.children]
    assert child_names[:4] == ["ladder", "admission_wait", "breaker",
                               "search"]
    # the scorer's batch span: analysis, then the dispatch it schedules
    search_names = [c.name for c in req.children[3].children]
    assert search_names[0] == "search.analyze"
    assert "dispatch" in search_names
    disp = req.children[3].children[search_names.index("dispatch")]
    assert any(c.name == "kernel" for c in disp.children)
    assert req.attrs["level"] == "full"
    reg = obs.get_registry()
    assert reg.histogram("request.full").count == 1
    assert reg.histogram("admission_wait").count == 1


def test_soak_reports_stage_latency_breakdown(scorer):
    report = run_soak(
        scorer, threads=4, queries=40, seed=3, fault_spec=None,
        config=ServingConfig(max_concurrency=4, max_queue=16,
                             deadline_s=5.0),
        timeout_s=60.0)
    lat = report["latency"]
    # the acceptance stages are always present, observed or not
    for stage in ("admission_wait", "dispatch", "kernel", "fallback"):
        assert stage in lat
        for key in ("count", "p50_ms", "p95_ms", "p99_ms"):
            assert key in lat[stage]
    assert lat["dispatch"]["count"] == 40
    assert lat["dispatch"]["p50_ms"] > 0
    assert lat["fallback"]["count"] == 0        # healthy run
    assert lat["request.full"]["count"] == 40
    assert "flight_record" not in report        # no breach, no dump


def test_soak_breach_writes_flight_record_with_span_tree(
        scorer, tmp_path):
    """The acceptance criterion: a forced soak invariant breach produces
    a flight-recorder JSONL containing the offending request's full span
    tree (plus header + telemetry snapshot)."""
    orig = scorer.search_batch
    calls = {"n": 0}

    def flaky(texts, **kw):
        # only frontend-originated calls carry force_host; the soak's
        # serial reference phase must stay clean
        if "force_host" in kw:
            calls["n"] += 1
            if calls["n"] % 5 == 0:
                raise RuntimeError("injected unstructured boom")
        return orig(texts, **kw)

    scorer.search_batch = flaky
    try:
        report = run_soak(
            scorer, threads=4, queries=30, seed=1, fault_spec=None,
            config=ServingConfig(max_concurrency=4, max_queue=16,
                                 deadline_s=5.0),
            timeout_s=60.0, flight_dir=str(tmp_path))
    finally:
        scorer.search_batch = orig
    assert report["errors"] > 0
    path = report["flight_record"]
    assert path and Path(path).exists()
    recs = [json.loads(line) for line in open(path)]
    assert recs[0]["record"] == "header"
    assert recs[0]["reason"] == "soak_invariant_breach"
    assert recs[0]["extra"]["errors"] == report["errors"]
    assert recs[-1]["record"] == "telemetry"
    assert "counters" in recs[-1]["telemetry"]
    offenders = [r["trace"] for r in recs if r["record"] == "trace"
                 and "boom" in r["trace"].get("error", "")]
    assert offenders, "the offending request's trace is not in the dump"
    names = {c["name"] for c in offenders[0]["children"]}
    assert {"ladder", "admission_wait", "breaker"} <= names


def test_breaker_open_triggers_rate_limited_dump(scorer, tmp_path,
                                                 monkeypatch):
    monkeypatch.setenv("TPU_IR_FLIGHT_DIR", str(tmp_path))
    frontend = ServingFrontend(scorer, ServingConfig(
        breaker_threshold=2, deadline_s=5.0))
    faults.install(faults.parse_plan("score.device_loss:first@8"))
    for _ in range(3):
        res = frontend.search("salmon fishing", k=5)
        assert res.degraded
    faults.clear()
    dumps = list(tmp_path.glob("flight-*breaker_open.jsonl"))
    assert len(dumps) == 1      # opened once -> one dump, rate-limited


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------


def test_metrics_cli_json_and_reset(capsys):
    from tpu_ir.cli import main

    reg = obs.get_registry()
    reg.incr("serving.submitted", 7)
    reg.observe("dispatch", 0.002)
    assert main(["metrics"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["counters"]["serving.submitted"] == 7
    assert out["histograms"]["dispatch"]["count"] == 1
    assert main(["metrics", "--reset"]) == 0
    capsys.readouterr()
    assert reg.get("serving.submitted") == 0


def test_metrics_cli_prometheus(capsys):
    from tpu_ir.cli import main

    obs.get_registry().incr("serving.submitted", 3)
    assert main(["metrics", "--prom"]) == 0
    text = capsys.readouterr().out
    assert 'tpu_ir_events_total{name="serving.submitted"} 3' in text
    assert "# TYPE tpu_ir_stage_latency_seconds histogram" in text


def test_trace_dump_cli(tmp_path, capsys):
    from tpu_ir.cli import main

    with obs.trace("cli-root"):
        with obs.trace("cli-child"):
            pass
    out_file = tmp_path / "dump.jsonl"
    assert main(["trace-dump", "--out", str(out_file)]) == 0
    meta = json.loads(capsys.readouterr().out)
    assert meta["traces"] == 1
    recs = [json.loads(line) for line in out_file.open()]
    # same artifact shape as a breach dump: header first, traces, snapshot
    assert recs[0]["record"] == "header"
    assert recs[0]["reason"] == "manual_trace_dump"
    assert recs[1]["record"] == "trace"
    assert recs[1]["trace"]["name"] == "cli-root"
    assert recs[1]["trace"]["children"][0]["name"] == "cli-child"
    assert recs[-1]["record"] == "telemetry"
    # stdout form: one JSON object per line
    assert main(["trace-dump"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert all(json.loads(ln) for ln in lines)


def test_stats_cli_reset_flag(capsys):
    from tpu_ir.cli import main

    recovery_counters().incr("retries", 2)
    assert main(["stats", "--reset"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["recovery"]["retries"] == 2
    assert main(["stats"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["recovery"] == {}
