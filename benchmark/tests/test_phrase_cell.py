"""The phrase cell (robust04-phrase.batch-phrase-k1000) at a tiny size on
the CPU: the archive generator (planted collocations, a seed that
permutes but keeps the multiset, an index that reads back as its
tokens), the guard that stops a program answering phrases without its
device path, whole runs (sound, traced, with the timed path broken,
and the bfloat16 control), and the per-layer readers."""

import json
import time

import numpy as np
import pytest

from benchmark import harness, run
from benchmark.archive import Archive, PhraseQueries, plant_counts
from benchmark.phrase_reference import (
    PhraseReference,
    check_phrase,
    control_topk,
)
from benchmark.reference import Tally

CELL = "robust04-phrase.batch-phrase-k1000"
TINY = {"documents": 2000, "block_queries": 16, "pool_blocks": 2,
        "check_sample": 20}
READERS = ("phrase_ms_per_query.phrase", "phrase_roofline.phrase",
           "phrase_fill_share.phrase", "load_positions_share.phrase")
# the device's idle share and the scorer's host time in the phrase cell
CELL_READERS = ("device_idle.phrase", "scorer_host_ms_per_query.phrase")


def _cell():
    _, _, config, traffic = harness.find_cell(CELL)
    config.update(documents=TINY["documents"])
    return config, traffic


def run_cell(capsys, seed=2**31 + 29, trace=0):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace)], allow_cpu=True,
                  overrides=dict(TINY), t_process=time.perf_counter())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_plant_law_at_full_scale():
    config, _ = _cell()
    c = plant_counts(config["collocations"], 1.0)
    assert c.max() == 10000 and 95 <= np.median(c) <= 110
    assert 5 <= c.min() <= 15 and (np.diff(c) <= 0).all()


def test_planted_collocations_match_at_least_their_plants():
    config, _ = _cell()
    a = Archive(config, 2**31 + 1)
    ref = PhraseReference(a.data_ranks, a.data_lengths)
    for e in np.flatnonzero(a.plants >= 8)[:10]:
        span = a.table[e, : a.table_len[e]]
        # plants may land twice in a doc or overwrite each other
        assert len(ref.matches(span)) >= 0.8 * a.plants[e]


def test_a_seed_permutes_but_keeps_the_multiset():
    config, traffic = _cell()
    a, b = Archive(config, 11), Archive(config, 2**33 + 5)
    assert not np.array_equal(a.lengths, b.lengths)
    assert np.array_equal(np.sort(a.lengths), np.sort(b.lengths))
    assert np.array_equal(np.sort(np.bincount(a.tokens)),
                          np.sort(np.bincount(b.tokens)))
    counts = []
    for arch, seed in ((a, 11), (b, 2**33 + 5)):
        qs = PhraseQueries(arch, traffic, 24, seed)
        ref = PhraseReference(arch.tokens, arch.lengths)
        counts.append(sorted(len(ref.matches(s)) for s in qs.spans))
        assert all('"' in t for t in qs.texts)
    assert counts[0] == counts[1] and max(counts[0]) > 0


def test_written_index_reads_back_as_the_tokens(tmp_path):
    from tpu_ir.search import Scorer

    config, _ = _cell()
    a = Archive(config, 2**31 + 3)
    a.write_index(str(tmp_path / "idx"))
    s = Scorer.load(str(tmp_path / "idx"))
    table = s.positions
    docs = np.asarray(table.docs).reshape(-1)
    where = np.asarray(table.at).reshape(-1)
    start, cf = np.asarray(table.start), np.asarray(table.cf)
    doc_start = np.asarray(table.doc_start)
    assert np.array_equal(cf, np.bincount(a.tokens))
    # every term's positions, doc-major, are where its tokens are, and
    # the token stream holds each doc's tokens in order
    for t in np.unique(a.tokens)[::97]:
        at = np.flatnonzero(a.tokens == t)
        d = np.searchsorted(np.cumsum(a.lengths), at, side="right") + 1
        p = at - np.concatenate([[0], np.cumsum(a.lengths)])[d - 1]
        sl = slice(start[t], start[t] + cf[t])
        assert np.array_equal(docs[sl], d)
        assert np.array_equal(where[sl], doc_start[d] + p)
    fwd = np.asarray(table.fwd).reshape(-1)
    first = np.concatenate([[0], np.cumsum(a.lengths)])
    for d in (1, len(a.lengths) // 2, len(a.lengths)):
        assert np.array_equal(
            fwd[doc_start[d]:doc_start[d] + a.lengths[d - 1]],
            a.tokens[first[d - 1]:first[d]])


def test_sound_run_is_correct(capsys):
    res = run_cell(capsys)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"setup_s", "queries_per_s", "load_s"} == set(res["metrics"])
    assert res["checks"]["max_rel_score_error"]["value"] < 1e-5


def test_traced_run_reports_its_readers(capsys):
    res = run_cell(capsys, trace=1)
    assert res["correct"]
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    # the roofline reads the chip's peaks: none on the CPU
    assert set(res["metrics"]) == (set(READERS) | set(CELL_READERS)) - {
        "phrase_roofline.phrase"}
    assert 0 < res["metrics"]["phrase_fill_share.phrase"]["value"] <= 100
    assert 0 < res["metrics"]["load_positions_share.phrase"]["value"] < 100
    assert 0 <= res["metrics"]["device_idle.phrase"]["value"] < 100
    assert res["metrics"]["scorer_host_ms_per_query.phrase"]["value"] > 0


def test_an_altered_answer_is_not_correct(capsys, monkeypatch):
    from tpu_ir.ops import phrase as ops_phrase

    orig = ops_phrase.phrase_topk

    def altered(*a, **kw):
        scores, docnos = orig(*a, **kw)
        return scores.at[:, 0].multiply(1.001), docnos

    monkeypatch.setattr(ops_phrase, "phrase_topk", altered)
    assert not run_cell(capsys)["correct"]


def test_a_left_out_match_is_not_correct(capsys, monkeypatch):
    from tpu_ir.ops import phrase as ops_phrase

    orig = ops_phrase.phrase_topk

    def dropped(*a, **kw):
        scores, docnos = orig(*a, **kw)
        return scores, docnos.at[::2, 0].set(0)

    monkeypatch.setattr(ops_phrase, "phrase_topk", dropped)
    assert not run_cell(capsys)["correct"]


def test_guard_stops_a_program_without_the_counter(capsys, monkeypatch):
    from tpu_ir import obs

    reg = obs.get_registry()
    counters = type(reg).counters

    def without(self, prefix=""):
        out = counters(self, prefix)
        out.pop("phrase.positions", None)
        return out

    monkeypatch.setattr(type(reg), "counters", without)
    with pytest.raises(SystemExit) as e:
        run_cell(capsys)
    assert e.value.code == 1
    out, err = capsys.readouterr()
    assert "window:" not in out and "phrase.positions" in err


def test_guard_stops_a_host_only_phrase_path(capsys, monkeypatch):
    from tpu_ir.search import Scorer

    search_batch = Scorer.search_batch
    monkeypatch.setattr(Scorer, "search_batch",
                        lambda self, texts, **kw: search_batch(
                            self, texts, force_host=True, **kw))
    with pytest.raises(SystemExit) as e:
        run_cell(capsys)
    assert e.value.code == 1
    assert "window:" not in capsys.readouterr().out


def test_bfloat16_control_is_not_correct():
    config, traffic = _cell()
    a = Archive(config, 2**31 + 3)
    qs = PhraseQueries(a, traffic, 40, 2**31 + 3)
    bm = config["bm25"]
    ref = PhraseReference(a.tokens, a.lengths, k1=bm["k1"], b=bm["b"])
    tally = Tally()
    for i, (s, p) in enumerate(zip(qs.spans, qs.plain)):
        terms = np.concatenate([s, p])
        docs, scores = ref.answer([s], terms, "bm25")
        check_phrase(docs, scores, control_topk(ref, [s], terms, 1000,
                                                "bm25"), 1000, tally,
                     f"q{i}")
    assert tally.checked == 40 and not tally.correct()
    assert tally.max_rel > 1e-4


def test_reference_check_catches_a_doc_outside_the_matches():
    docs = np.array([3, 1, 2])
    scores = np.array([2.0, 1.5, 1.0])
    for got, k, ok in (([(3, 2.0), (1, 1.5), (2, 1.0)], 3, True),
                       ([(3, 2.0), (1, 1.5), (4, 1.0)], 3, False),
                       ([(3, 2.0), (1, 1.5)], 3, False),
                       ([(3, 2.0), (1, 1.5)], 2, True),
                       ([(3, 2.0), (2, 1.0)], 2, False)):
        t = Tally()
        check_phrase(docs, scores, got, k, t, "q")
        assert t.correct() == ok, (got, k)


@pytest.fixture
def capture(monkeypatch):
    """Synthetic capture totals in place of the program's."""
    from tpu_ir import obs

    totals = {"capturing": False, "counters": {}, "histograms": {}}
    monkeypatch.setattr(obs, "capture_totals", lambda: totals)
    return totals


def _data(**kw):
    data = {"counters": {"queries": 1000}, "work": {}, "peaks": None,
            "trace": {"window_s": 1.0, "module_s": {
                "jit_phrase_match": 0.3, "jit_phrase_topk": 0.2,
                "jit_other": 5.0}}}
    data.update(kw)
    return data


def test_readers_are_declared_for_the_new_cell_only():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    got = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS + CELL_READERS:
        assert got[name]["workloads"] == [CELL]
    for m in bench["end_to_end"]:
        if m["name"] in ("queries_per_s", "load_s"):
            assert m["workloads"][-1] == CELL


def test_device_readers_on_synthetic_traces():
    data = _data(work={"phrase_bytes": 819e9 * 0.05},
                 peaks={"hbm_bytes_per_s": 819e9})
    assert harness.read_metric("phrase_ms_per_query.phrase",
                               data) == pytest.approx(0.5)
    assert harness.read_metric("phrase_roofline.phrase",
                               data) == pytest.approx(10.0)
    data["trace"]["module_s"] = {"jit_other": 5.0}
    for name in READERS[:2]:
        assert harness.read_metric(name, data) is None


def test_fill_reader_on_synthetic_totals(capture):
    capture["counters"].update({"phrase.slots": 4096,
                                "phrase.positions": 1024})
    assert harness.read_metric("phrase_fill_share.phrase",
                               _data()) == pytest.approx(25.0)
    capture["counters"]["phrase.slots"] = 0
    assert harness.read_metric("phrase_fill_share.phrase", _data()) is None


def test_load_reader_on_a_synthetic_registry(monkeypatch):
    from tpu_ir import obs

    reg = obs.TelemetryRegistry()
    monkeypatch.setattr(obs, "get_registry", lambda: reg)
    reg.observe("load", 4.0)
    reg.observe("load.positions", 1.0)
    assert harness.read_metric("load_positions_share.phrase",
                               _data()) == pytest.approx(25.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_none_without_a_trace(name, capture):
    capture["counters"].update({"phrase.slots": 1, "phrase.positions": 1})
    data = _data(work={"phrase_bytes": 1.0},
                 peaks={"hbm_bytes_per_s": 819e9})
    data["trace"] = None
    assert harness.read_metric(name, data) is None


@pytest.mark.parametrize("name", READERS[2:])
def test_readers_on_a_program_without_the_span_or_counter(name,
                                                          monkeypatch):
    """The parent's program: no phrase counters, no load.positions."""
    from tpu_ir import obs

    totals = {"capturing": False, "counters": {"cold.chunk_slots": 8},
              "histograms": {}}
    monkeypatch.setattr(obs, "capture_totals", lambda: totals)
    reg = obs.TelemetryRegistry()
    reg._hists.pop("load.positions", None)
    reg.observe("load", 4.0)
    monkeypatch.setattr(obs, "get_registry", lambda: reg)
    assert harness.read_metric(name, _data()) is None


def test_idle_reader_on_a_synthetic_trace():
    data = _data()
    data["trace"].update(busy_s=0.25, devices=1)
    assert harness.read_metric("device_idle.phrase",
                               data) == pytest.approx(75.0)
    data["trace"]["devices"] = 0
    assert harness.read_metric("device_idle.phrase", data) is None


def test_host_scorer_reader_on_synthetic_totals(capture):
    capture["histograms"].update({
        "search": {"count": 4, "sum_s": 3.0},
        "dispatch.device": {"count": 8, "sum_s": 2.0}})
    assert harness.read_metric("scorer_host_ms_per_query.phrase",
                               _data()) == pytest.approx(1.0)
    capture["histograms"].clear()
    assert harness.read_metric("scorer_host_ms_per_query.phrase",
                               _data()) is None


@pytest.mark.parametrize("name", CELL_READERS)
def test_cell_readers_read_none_without_a_trace(name, capture):
    capture["histograms"]["search"] = {"count": 1, "sum_s": 1.0}
    data = _data()
    data["trace"] = None
    assert harness.read_metric(name, data) is None
