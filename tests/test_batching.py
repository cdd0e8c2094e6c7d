"""Continuous micro-batching acceptance suite (ISSUE 9).

The contract: a query scored inside ANY coalesced padded batch returns
scores, docids and tie-break order IDENTICAL to its solo dispatch —
across layouts, scoring models, and degradation variants — while the
scheduler actually coalesces concurrent callers (occupancy > 1), keeps
per-request semantics tagged per slot, never makes an idle solo caller
wait, and keeps the compiled-program universe CLOSED (steady-state
serving performs zero XLA compiles after the frontend's ladder
precompile).
"""

import threading
import time
import warnings

import numpy as np
import pytest

from tpu_ir.index import build_index
from tpu_ir.obs import get_registry, querylog
from tpu_ir.search import Scorer
from tpu_ir.serving import (
    BatchKey,
    CoalescingScheduler,
    ServingConfig,
    ServingFrontend,
    run_concurrency_sweep,
)

WORDS = ("salmon fishing river bears honey quick brown fox lazy dog "
         "market investor asset bond stock season rain forest".split())

# mixed shapes: hot+cold, cold-only, duplicates, unknown terms, empty —
# the same adversarial spread the explain matrix uses
QUERIES = [
    "common salmon",
    "salmon fishing river",
    "honey bears",
    "salmon salmon fishing",
    "zzznope salmon",
    "common",
    "stock market investor",
]

LADDER = (1, 4, 16)
WIDTH = 8


@pytest.fixture(scope="module")
def index_dir(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("batching")
    body = []
    for i in range(150):
        # "common" in every doc -> a real hot-strip row (df = N)
        text = "common " + " ".join(WORDS[(i + j) % len(WORDS)]
                                    for j in range(3 + i % 7))
        body.append(f"<DOC>\n<DOCNO> D-{i:04d} </DOCNO>\n<TEXT>\n"
                    f"{text}\n</TEXT>\n</DOC>\n")
    corpus = tmp / "corpus.trec"
    corpus.write_text("".join(body))
    out = str(tmp / "idx")
    build_index([str(corpus)], out, num_shards=3, compute_chargrams=False)
    return out


@pytest.fixture(scope="module")
def scorers(index_dir):
    out = {
        "dense": Scorer.load(index_dir, layout="dense"),
        "sparse": Scorer.load(index_dir, layout="sparse"),
        "sharded": Scorer.load(index_dir, layout="sharded"),
    }
    hr = np.asarray(out["sparse"].hot_rank)
    assert (hr >= 0).sum() >= 1, "fixture must have a non-empty hot strip"
    return out


def _solo(scorer, text, **kw):
    kw.setdefault("k", 5)
    return scorer.search_batch([text], **kw)[0]


def _batched(scorer, texts, **kw):
    """The exact coalesced-dispatch shape the scheduler uses: padded to
    the smallest rung, pinned width, rung-padded scheduled groups."""
    rung = next(r for r in LADDER if r >= len(texts))
    return scorer.search_batch(texts, k=5, pad_to=rung, width_floor=WIDTH,
                               rung_ladder=LADDER, **kw)


# ---------------------------------------------------------------------------
# bit-exactness: coalesced == solo, across the full matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["dense", "sparse", "sharded"])
@pytest.mark.parametrize("scoring", ["tfidf", "bm25"])
def test_coalesced_batch_bit_exact_per_layout_and_scoring(
        scorers, layout, scoring):
    s = scorers[layout]
    solo = [_solo(s, t, scoring=scoring) for t in QUERIES]
    for size in (1, 3, len(QUERIES)):
        batched = _batched(s, QUERIES[:size], scoring=scoring)
        assert len(batched) == size
        for got, want, text in zip(batched, solo[:size], QUERIES):
            # full tuples: docids AND float scores AND order, bit-exact
            assert list(got) == list(want), (layout, scoring, text)


@pytest.mark.parametrize("layout", ["sparse", "sharded"])
def test_coalesced_batch_bit_exact_hot_only(scorers, layout):
    s = scorers[layout]
    solo = [_solo(s, t, scoring="tfidf", hot_only=True) for t in QUERIES]
    batched = _batched(s, QUERIES, scoring="tfidf", hot_only=True)
    for got, want, text in zip(batched, solo, QUERIES):
        assert list(got) == list(want), (layout, text)


def test_coalesced_batch_bit_exact_prune_off(index_dir):
    s = Scorer.load(index_dir, layout="sparse", prune=False)
    solo = [_solo(s, t, scoring="bm25") for t in QUERIES]
    batched = _batched(s, QUERIES, scoring="bm25")
    for got, want, text in zip(batched, solo, QUERIES):
        assert list(got) == list(want), text


def test_coalesced_batch_bit_exact_rerank(scorers):
    s = scorers["sparse"]
    solo = [_solo(s, t, rerank=25) for t in QUERIES]
    batched = _batched(s, QUERIES, rerank=25)
    for got, want, text in zip(batched, solo, QUERIES):
        assert list(got) == list(want), text


@pytest.mark.parametrize("scoring", ["tfidf", "bm25"])
def test_coalesced_batch_bit_exact_with_the_chunk_stream(
        index_dir, scorers, monkeypatch, scoring):
    """With an 8-posting chunk width every cold tier of 8 or more slots
    streams: coalesced (worst-case capacity) == solo (capacity fitted
    to the block) bit for bit, explain decomposes the streamed
    scores exactly, the stream agrees with the per-tier stages, and the
    dispatches count their postings and lanes."""
    from tpu_ir.search import layout

    monkeypatch.setattr(layout, "COLD_CHUNK", 8)
    s = Scorer.load(index_dir, layout="sparse")
    assert s.cold_chunks is not None and s.cold_chunks.docs.shape[1] == 8
    reg = get_registry()
    before = (reg.get("cold.chunk_postings"), reg.get("cold.chunk_slots"))
    solo = [_solo(s, t, scoring=scoring) for t in QUERIES]
    streamed = reg.get("cold.chunk_postings") - before[0]
    lanes = reg.get("cold.chunk_slots") - before[1]
    assert 0 < streamed <= lanes
    for size in (1, 3, len(QUERIES)):
        batched = _batched(s, QUERIES[:size], scoring=scoring)
        for got, want, text in zip(batched, solo[:size], QUERIES):
            assert list(got) == list(want), (scoring, text)
    plain = scorers["sparse"]
    assert plain.cold_chunks is None
    for got, text in zip(solo, QUERIES):
        want = _solo(plain, text, scoring=scoring)
        assert [d for d, _ in got] == [d for d, _ in want], text
        np.testing.assert_allclose([v for _, v in got],
                                   [v for _, v in want], rtol=1e-6)
    res = s.search_batch(QUERIES[:3], k=5, scoring=scoring,
                         explain_k=3)
    for r in res:
        for e, (_, score) in zip(r.explain, r):
            assert e["contribution_sum"] == e["score"] == score


def test_donated_query_twins_bit_exact(scorers, monkeypatch):
    """TPU_IR_BATCH_DONATE=1 forces the donated-query kernel twins even
    on CPU (where XLA ignores the donation with a warning): identical
    math, identical floats."""
    monkeypatch.setenv("TPU_IR_BATCH_DONATE", "1")
    s = scorers["sparse"]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # "donated buffers not usable"
        batched = _batched(s, QUERIES, scoring="bm25",
                           donate_queries=True)
    monkeypatch.setenv("TPU_IR_BATCH_DONATE", "0")
    solo = [_solo(s, t, scoring="bm25") for t in QUERIES]
    for got, want, text in zip(batched, solo, QUERIES):
        assert list(got) == list(want), text


def test_explain_ks_per_slot(scorers):
    """explain depth is tagged per slot: only the slots that asked get
    a decomposition, and it matches the solo explain bit-exactly."""
    s = scorers["sparse"]
    batched = _batched(s, QUERIES[:3], scoring="tfidf",
                       explain_ks=[2, 0, 1])
    assert batched[0].explain is not None and len(batched[0].explain) == 2
    assert batched[1].explain is None
    assert batched[2].explain is not None and len(batched[2].explain) == 1
    for e, (key, score) in zip(batched[0].explain, batched[0]):
        assert e["contribution_sum"] == e["score"] == score


# ---------------------------------------------------------------------------
# the scheduler: coalescing, solo fast path, key separation, errors
# ---------------------------------------------------------------------------


def test_scheduler_coalesces_concurrent_callers(scorers):
    s = scorers["sparse"]
    fe = ServingFrontend(s, ServingConfig(
        max_concurrency=8, max_queue=16, coalesce=True,
        batch_ladder=LADDER, batch_width=WIDTH))
    solo = {t: list(_solo(s, t, scoring="bm25", k=10)) for t in QUERIES}
    before = get_registry().get("batch.coalesced")
    errors = []
    barrier = threading.Barrier(8)

    def client(ci):
        try:
            barrier.wait(10)
            for i in range(12):
                t = QUERIES[(ci + i) % len(QUERIES)]
                res = fe.search(t, scoring="bm25")
                assert list(res) == solo[t], t
                assert res.level == "full" and not res.degraded
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors
    snap = fe.batcher.snapshot()
    assert snap["max_occupancy"] > 1, "coalescing never engaged"
    assert snap["coalesced"] + snap["solo_flush"] == snap["batches"]
    assert get_registry().get("batch.coalesced") > before
    assert fe.stats()["batching"]["max_occupancy"] == snap["max_occupancy"]


def test_idle_solo_query_never_pays_the_wait(scorers):
    """An idle arrival dispatches IMMEDIATELY — the bounded coalescing
    wait applies only to promoted leaders, so the solo path cannot
    regress by the wait bound."""
    s = scorers["sparse"]
    fe = ServingFrontend(s, ServingConfig(
        max_concurrency=4, coalesce=True, coalesce_wait_ms=500.0,
        batch_ladder=LADDER, batch_width=WIDTH))
    fe.search(QUERIES[0], scoring="bm25")  # warm
    t0 = time.perf_counter()
    fe.search(QUERIES[1], scoring="bm25")
    elapsed_ms = (time.perf_counter() - t0) * 1e3
    assert elapsed_ms < 400.0, (
        f"idle solo query paid the coalescing wait ({elapsed_ms:.1f} ms)")
    assert fe.batcher.snapshot()["solo_flush"] >= 2


def test_incompatible_keys_do_not_share_a_batch(scorers):
    """Requests whose BatchKey differs (here: scoring model) must never
    coalesce into one kernel call — they dispatch as separate batches,
    each still correct."""
    s = scorers["sparse"]
    sched = CoalescingScheduler(s, ladder=LADDER, width=WIDTH)
    solo_tf = list(_solo(s, QUERIES[0], scoring="tfidf", k=10))
    solo_bm = list(_solo(s, QUERIES[0], scoring="bm25", k=10))
    results = {}
    barrier = threading.Barrier(2)

    def go(scoring):
        barrier.wait(10)
        results[scoring] = sched.submit(
            QUERIES[0], k=10, scoring=scoring, rerank=None,
            hot_only=False, force_host=False, level="full")

    threads = [threading.Thread(target=go, args=(sc,), daemon=True)
               for sc in ("tfidf", "bm25")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert list(results["tfidf"]) == solo_tf
    assert list(results["bm25"]) == solo_bm
    snap = sched.snapshot()
    assert snap["batches"] == 2 and snap["max_occupancy"] == 1


def test_batch_error_reaches_every_caller(scorers, monkeypatch):
    """A dispatch that raises delivers the error to EVERY slot of the
    batch — no caller hangs, no result vanishes."""
    s = scorers["sparse"]
    sched = CoalescingScheduler(s, ladder=LADDER, width=WIDTH)
    boom = RuntimeError("injected batch failure")

    def exploding(*a, **kw):
        raise boom

    monkeypatch.setattr(s, "search_batch", exploding)
    outcomes = []
    barrier = threading.Barrier(3)

    def go(i):
        barrier.wait(10)
        try:
            sched.submit(QUERIES[i], k=5, scoring="tfidf", rerank=None,
                         hot_only=False, force_host=False, level="full")
            outcomes.append("ok")
        except RuntimeError as e:
            outcomes.append(str(e))

    threads = [threading.Thread(target=go, args=(i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert outcomes == ["injected batch failure"] * 3
    assert sched.snapshot()["queued"] == 0
    assert not sched.snapshot()["dispatching"]


def test_phrase_queries_route_solo(scorers):
    sched = CoalescingScheduler(scorers["sparse"], ladder=LADDER,
                                width=WIDTH)
    with pytest.raises(ValueError):
        sched.submit('"salmon fishing"', k=5, scoring="tfidf",
                     rerank=None, hot_only=False, force_host=False,
                     level="full")
    # and the scorer-level guard: per-slot lists index the PLAIN batch,
    # so a phrase query mixed into a slot-tagged batch must be rejected
    # loudly, not silently shift every later slot's attribution
    with pytest.raises(ValueError):
        scorers["sparse"].search_batch(['"salmon fishing"', "honey"],
                                       explain_ks=[0, 1])
    assert BatchKey(5, "tfidf", None, False, False) != \
        BatchKey(5, "bm25", None, False, False)


def test_all_hot_batch_skips_the_pad_only_dispatch(scorers):
    """A batch whose every REAL query is hot must not pay a second
    dispatch just to score its rung-pad rows: one full-kernel call,
    results still bit-exact."""
    s = scorers["sparse"]
    texts = ["common", "common", "common"]  # df == N -> hot strip
    solo = [_solo(s, t, scoring="tfidf") for t in texts]
    calls = []
    orig = s._topk_device

    def counting(q, k, scoring, **kw):
        calls.append(len(q))
        return orig(q, k, scoring, **kw)

    s._topk_device = counting
    try:
        batched = _batched(s, texts, scoring="tfidf")
    finally:
        del s._topk_device
    assert len(calls) == 1, f"expected one dispatch, saw rows={calls}"
    for got, want in zip(batched, solo):
        assert list(got) == list(want)


# ---------------------------------------------------------------------------
# the closed compile universe + querylog wiring + sweep
# ---------------------------------------------------------------------------


def test_precompiled_ladder_closes_the_shape_universe(index_dir):
    """After the frontend's ladder precompile, steady-state coalesced
    serving performs ZERO jit compiles — stronger than the zero-
    recompiles acceptance pin: batch content (occupancy, scheduling
    split, query mix) cannot mint a single new XLA program."""
    _assert_steady_state_compiles_nothing(
        Scorer.load(index_dir, layout="sparse"))


@pytest.mark.parametrize("prune", [True, False])
def test_precompiled_ladder_closes_the_shape_universe_with_the_chunk_stream(
        index_dir, monkeypatch, prune):
    """The same closed universe when the big cold tiers stream as chunks
    (an 8-posting width, so the fixture's tiers stream): rung-padded
    batches dispatch at the worst-case chunk capacity whatever their
    content, on the MaxScore-scheduled path and with pruning off."""
    from tpu_ir.search import layout

    monkeypatch.setattr(layout, "COLD_CHUNK", 8)
    s = Scorer.load(index_dir, layout="sparse", prune=prune)
    assert s.cold_chunks is not None
    _assert_steady_state_compiles_nothing(s)


def _assert_steady_state_compiles_nothing(s):
    fe = ServingFrontend(s, ServingConfig(
        max_concurrency=6, max_queue=16, coalesce=True,
        batch_ladder=LADDER, batch_width=WIDTH))
    reg = get_registry()
    compiles_before = reg.get("compile.count")
    errors = []

    def client(ci):
        try:
            for i in range(10):
                fe.search(QUERIES[(ci + i) % len(QUERIES)],
                          scoring=("bm25" if i % 2 else "tfidf"))
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors
    assert reg.get("compile.count") == compiles_before, (
        "steady-state coalesced serving compiled a new program")
    assert reg.get("compile.recompiles") == 0


def test_querylog_entries_carry_batch_attribution(scorers):
    """Every coalesced entry records queue_wait_ms + batch_occupancy,
    entries of one shared batch join on batch_id, and degradation is
    uniform within a batch (no slot charged a batch-mate's outcome)."""
    querylog.clear()
    s = scorers["sparse"]
    fe = ServingFrontend(s, ServingConfig(
        max_concurrency=6, max_queue=16, coalesce=True,
        batch_ladder=LADDER, batch_width=WIDTH))
    barrier = threading.Barrier(6)

    def client(ci):
        barrier.wait(10)
        for i in range(6):
            fe.search(QUERIES[(ci + i) % len(QUERIES)], scoring="bm25")

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    entries = [e for e in querylog.recent() if "batch_occupancy" in e]
    assert entries, "no coalesced entries recorded"
    by_batch: dict = {}
    occupancies = set()
    for e in entries:
        assert "queue_wait_ms" in e and e["queue_wait_ms"] >= 0.0
        assert e["level"] == "full"
        assert e["batch_occupancy"] >= 1
        occupancies.add(e["batch_occupancy"])
        by_batch.setdefault(e["batch_id"], []).append(e)
    assert any(o > 1 for o in occupancies), "no shared batch recorded"
    for batch_id, grp in by_batch.items():
        assert len({bool(g["degraded"]) for g in grp}) == 1, (
            f"mixed degraded verdicts inside batch {batch_id}")
        assert len({g["batch_occupancy"] for g in grp}) == 1
        # occupancy is the number of REAL slots in the shared dispatch
        assert len(grp) <= grp[0]["batch_occupancy"]


def test_concurrency_sweep_reports_and_guards(scorers):
    """The serve-bench sweep instrument: per-level latency/QPS/occupancy
    rows, a solo-RTT reference, and the zero-recompile pin."""
    rep = run_concurrency_sweep(
        scorers["sparse"], levels=(1, 4), queries_per_level=24, seed=1,
        scoring="bm25")
    assert rep["solo_rtt_ms"] > 0
    assert [lv["concurrency"] for lv in rep["levels"]] == [1, 4]
    for lv in rep["levels"]:
        assert lv["errors"] == 0
        assert lv["served"] > 0
        assert lv["qps"] > 0
        assert lv["p99_ms"] >= lv["p50_ms"] > 0
        assert lv["recompiles"] == 0
        assert lv["occupancy"]["count"] == lv["coalesced"] + lv["solo_flush"]
    assert rep["levels"][0]["occupancy_mean"] == 1.0


def test_serve_bench_sweep_cli(index_dir, tmp_path, monkeypatch, capsys):
    """`tpu-ir serve-bench --concurrency 1,2` runs the sweep, prints the
    report, and appends the sentry row to BENCH_HISTORY.jsonl."""
    import json

    from tpu_ir.cli import main

    monkeypatch.chdir(tmp_path)
    (tmp_path / "BENCH_HISTORY.jsonl").write_text("")
    rc = main(["serve-bench", index_dir, "--backend", "cpu",
               "--layout", "sparse", "--queries", "16",
               "--concurrency", "1,2", "--seed", "3"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert len(out["levels"]) == 2
    row = out["history_row"]
    # the config key carries sweep shape + corpus size (comparability
    # grouping), headlined by the LARGEST level regardless of order
    assert row["config"].startswith("serve_sweep-")
    assert row["config"].endswith("-c2")
    assert row["concurrency"] == 2
    assert {"batched_qps", "batched_p99_ms", "solo_p50_ms",
            "batch_occupancy_mean", "solo_rtt_ms",
            "recompiles"} <= set(row)
    lines = [json.loads(ln) for ln in
             (tmp_path / "BENCH_HISTORY.jsonl").read_text().splitlines()]
    assert len(lines) == 1 and lines[0]["config"] == row["config"]
    assert "ts" in lines[0]
