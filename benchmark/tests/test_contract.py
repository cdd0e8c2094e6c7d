"""BENCHMARK.json against the contract's shape, and the files the
harness finds by name: every configuration, traffic mix and per-layer
metric reader exists, and a reader with nothing to read returns None."""

import json
import os
import re

import pytest

from benchmark import harness

from .conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.load_json(ROOT, "BENCHMARK.json")


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]


def test_names_units_and_keys():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in metrics])
    for kind in ("configs", "workloads"):
        ns = [x["name"] for x in BENCH[kind]]
        assert len(ns) == len(set(ns))
    assert len({m["name"] for m in metrics}) == len(metrics)
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        cfg = harness.load_json(ROOT, c["file"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert BENCH["end_to_end"][0]["name"] == "setup_s"


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_files_and_metrics(w):
    bench, cell, config, traffic = harness.find_cell(w["name"])
    e2e = harness.metrics_for(bench, cell, "end_to_end")
    layer = harness.metrics_for(bench, cell, "per_layer")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer and {m["moves"] for m in layer} <= {m["name"] for m in e2e}
    empty = {"counters": {}, "work": {}, "trace": None, "peaks": None}
    for m in layer:
        assert harness.read_metric(m["name"], empty) is None
    assert os.path.exists(os.path.join(harness.BENCH_DIR, "kinds",
                                       traffic["kind"] + ".py"))


def test_score_readers_count_only_the_scoring_modules():
    data = {"counters": {"queries": 10},
            "work": {"query_bytes": 819e9 * 1e-3},
            "trace": {"module_s": {"jit_bm25_topk_tiered": 0.01,
                                   "jit_other": 5.0}},
            "peaks": {"hbm_bytes_per_s": 819e9}}
    assert harness.read_metric("score_ms_per_query.batch",
                               data) == pytest.approx(1.0)
    assert harness.read_metric("score_roofline.batch",
                               data) == pytest.approx(10.0)
    data["trace"]["module_s"] = {"jit_other": 5.0}
    assert harness.read_metric("score_ms_per_query.batch", data) is None
    assert harness.read_metric("score_roofline.batch", data) is None


def test_metric_files_follow_names():
    have = {f[:-3] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                    "metrics"))
            if f.endswith(".py")}
    assert have >= {m["name"] for m in BENCH["per_layer"]}
    json.dumps(BENCH)
