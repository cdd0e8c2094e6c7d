"""The per-layer readers of what the program records about itself: the
capture totals of its spans and counters, its load stages and its
pass-2 build phases. Each gives the expected number on synthetic
totals and None without a trace or on an older program that lacks
what it reads; and a tiny traced run of each cell on the CPU prints
every metric that run exercises."""

import pytest

from benchmark import harness

from .test_runs import run_cell

BATCH = "msmarco-passage.batch-bm25-k1000"
BUILD = "robust04.build"
CAPTURE = ("scorer_host_ms_per_query.batch", "analysis_ms_per_query.batch",
           "blockmax_masked_share.batch")
LOAD = tuple(f"load_{s}_share.batch" for s in
             ("read", "assemble", "layout", "cache_write", "h2d"))
PASS2 = ("pass2_device_wait_share.build", "pass2_transfer_share.build",
         "pass2_spill_share.build")
NEW = CAPTURE + LOAD + PASS2
TRACE = {"window_s": 1.0}


def _data(**counters):
    return {"counters": counters, "work": {}, "trace": TRACE,
            "peaks": None}


@pytest.fixture
def capture(monkeypatch):
    """Synthetic capture totals in place of the program's."""
    from tpu_ir import obs

    totals = {"capturing": False, "counters": {}, "histograms": {}}
    monkeypatch.setattr(obs, "capture_totals", lambda: totals)
    return totals


@pytest.fixture
def registry(monkeypatch):
    """A fresh registry in place of the process's."""
    from tpu_ir import obs

    reg = obs.TelemetryRegistry()
    monkeypatch.setattr(obs, "get_registry", lambda: reg)
    return reg


def test_every_new_metric_is_declared_for_its_one_cell():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert got[name]["workloads"] == [BATCH if name.endswith(".batch")
                                          else BUILD]


@pytest.mark.parametrize("name", NEW)
def test_reader_without_a_trace_reads_none(name, capture, registry):
    registry.observe("load", 2.0)
    registry.observe("load.read", 1.0)
    capture["histograms"]["search"] = {"count": 1, "sum_s": 1.0}
    data = _data(queries=10, builds={"walls": [10.0],
                                     "timings": [{"pass2_spill": 1.0}]})
    data["trace"] = None
    assert harness.read_metric(name, data) is None


def test_capture_readers_on_synthetic_totals(capture):
    capture["histograms"].update({
        "search": {"count": 4, "sum_s": 2.0},
        "dispatch.device": {"count": 8, "sum_s": 1.5},
        "search.analyze": {"count": 4, "sum_s": 0.1}})
    capture["counters"].update({"blockmax.blocks_considered": 200,
                                "blockmax.blocks_masked": 50})
    data = _data(queries=1000)
    assert harness.read_metric("scorer_host_ms_per_query.batch",
                               data) == pytest.approx(0.5)
    assert harness.read_metric("analysis_ms_per_query.batch",
                               data) == pytest.approx(0.1)
    assert harness.read_metric("blockmax_masked_share.batch",
                               data) == pytest.approx(25.0)
    # block-max that never engaged reads as nothing, not as 0%
    capture["counters"]["blockmax.blocks_considered"] = 0
    assert harness.read_metric("blockmax_masked_share.batch", data) is None


def test_load_readers_on_synthetic_histograms(registry):
    registry.observe("load", 10.0)
    for stage, s in (("load.read", 3.0), ("load.assemble", 2.0),
                     ("load.layout", 1.5), ("load.cache_write", 2.5),
                     ("load.h2d", 0.5), ("load.h2d", 0.25)):
        registry.observe(stage, s)
    data = _data()
    want = {"read": 30.0, "assemble": 20.0, "layout": 15.0,
            "cache_write": 25.0, "h2d": 7.5}
    for stage, share in want.items():
        assert harness.read_metric(f"load_{stage}_share.batch",
                                   data) == pytest.approx(share)


def test_load_readers_read_none_without_the_stage_or_the_load(registry):
    data = _data()
    registry.observe("load.read", 1.0)
    assert harness.read_metric("load_read_share.batch", data) is None
    registry.observe("load", 2.0)
    assert harness.read_metric("load_read_share.batch",
                               data) == pytest.approx(50.0)
    # a cache hit writes no cache: the stage has nothing to read
    assert harness.read_metric("load_cache_write_share.batch",
                               data) is None


def test_pass2_readers_on_synthetic_build_reports():
    data = _data(builds={"walls": [10.0, 10.0], "timings": [
        {"pass2_combine": 5.0, "pass2_upload": 0.5,
         "pass2_device_wait": 2.0, "pass2_fetch": 0.5, "pass2_spill": 1.0},
        {"pass2_combine": 5.0, "pass2_upload": 0.5,
         "pass2_device_wait": 2.0, "pass2_fetch": 1.5, "pass2_spill": 1.0},
    ]})
    assert harness.read_metric("pass2_device_wait_share.build",
                               data) == pytest.approx(20.0)
    assert harness.read_metric("pass2_transfer_share.build",
                               data) == pytest.approx(15.0)
    assert harness.read_metric("pass2_spill_share.build",
                               data) == pytest.approx(10.0)


def test_readers_on_a_program_without_the_new_spans(monkeypatch, registry):
    """The program before these spans: no capture_totals, no `load`
    span, no pass-2 sub-phases. Every new reader reads None."""
    from tpu_ir import obs

    monkeypatch.delattr(obs, "capture_totals")
    registry.observe("load.read", 1.0)
    registry.observe("load.h2d", 1.0)
    data = _data(queries=10, builds={"walls": [10.0],
                                     "timings": [{"pass2_combine": 3.0}]})
    for name in NEW:
        assert harness.read_metric(name, data) is None, name


def _new_metrics(res):
    return {n: m["value"] for n, m in res["metrics"].items() if n in NEW}


def test_tiny_traced_batch_run_prints_its_program_metrics(capsys,
                                                          monkeypatch):
    """At the tiny size the auto layout is dense, which has no serving
    layout, cache or streamed uploads; the sparse (tiered) layout the
    full cell uses is forced here. Block-max stays off: 3,000 passages
    hold too few 512-doc blocks for a top-1000 candidate budget to
    skip any, so blockmax_masked_share.batch reads None."""
    from tpu_ir import obs
    from tpu_ir.search import scorer

    monkeypatch.setattr(scorer, "DENSE_BUDGET", 0)
    obs.get_registry().reset()
    res = run_cell(BATCH, capsys, trace=1)
    assert res["correct"]
    got = _new_metrics(res)
    assert set(got) == set(CAPTURE + LOAD) - {"blockmax_masked_share.batch"}
    assert 0 < got["analysis_ms_per_query.batch"] <= \
        got["scorer_host_ms_per_query.batch"]
    assert 0 < sum(got[n] for n in LOAD) <= 100.0


def test_tiny_traced_build_run_prints_its_program_metrics(capsys):
    res = run_cell(BUILD, capsys, trace=1)
    assert res["correct"]
    got = _new_metrics(res)
    assert set(got) == set(PASS2)
    assert all(v > 0 for v in got.values())
    assert sum(got.values()) <= res["metrics"]["pass2_share.build"]["value"]
