"""The reader of the cold chunk stream's fill: the capture's totals of
the program's `cold.chunk_postings` over `cold.chunk_slots`. It gives
the share on synthetic totals, None without a trace, without the
counters (as on a program with no chunk stream) or where nothing was
streamed, and a tiny traced batch run whose cold tiers stream prints
it."""

import pytest

from benchmark import harness

from .test_program_metrics import BATCH, _data
from .test_runs import run_cell

NAME = "cold_chunk_fill_share.batch"


@pytest.fixture
def capture(monkeypatch):
    """Synthetic capture totals in place of the program's."""
    from tpu_ir import obs

    totals = {"capturing": False, "counters": {}, "histograms": {}}
    monkeypatch.setattr(obs, "capture_totals", lambda: totals)
    return totals


def test_the_fill_is_declared_for_the_batch_cell():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in bench["per_layer"]}
    assert got[NAME]["workloads"] == [BATCH]
    assert got[NAME]["moves"] == "queries_per_s"
    assert got[NAME]["layer"] == "kernels"


def test_fill_reader_on_synthetic_totals(capture):
    capture["counters"].update({"cold.chunk_slots": 4096,
                                "cold.chunk_postings": 2458})
    assert harness.read_metric(NAME, _data()) == \
        pytest.approx(60.009765625)
    # a program that streamed nothing, or has no stream at all
    capture["counters"]["cold.chunk_slots"] = 0
    assert harness.read_metric(NAME, _data()) is None
    del capture["counters"]["cold.chunk_slots"]
    assert harness.read_metric(NAME, _data()) is None


def test_fill_reader_without_a_trace_reads_none(capture):
    capture["counters"].update({"cold.chunk_slots": 4096,
                                "cold.chunk_postings": 2458})
    data = _data()
    data["trace"] = None
    assert harness.read_metric(NAME, data) is None


def test_fill_reader_on_a_program_without_capture_totals(monkeypatch):
    from tpu_ir import obs

    monkeypatch.delattr(obs, "capture_totals")
    assert harness.read_metric(NAME, _data()) is None


def test_tiny_traced_batch_run_prints_the_chunk_fill(capsys, monkeypatch):
    """The tiny shard's tiers are too narrow for the program's chunk
    width; at an 8-posting width its cold tiers stream, and the traced
    run reports the stream's fill."""
    from tpu_ir import obs
    from tpu_ir.search import layout, scorer

    monkeypatch.setattr(scorer, "DENSE_BUDGET", 0)
    monkeypatch.setattr(layout, "COLD_CHUNK", 8)
    obs.get_registry().reset()
    res = run_cell(BATCH, capsys, trace=1)
    assert res["correct"]
    fill = res["metrics"][NAME]["value"]
    assert 0 < fill <= 100
