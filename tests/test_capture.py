"""Program spans inside a jax profiler capture (tpu_ir/obs/trace.py) and
the capture ledger (TelemetryRegistry.capture_totals).

Pins: a span opened while a capture records lands on a `/host` plane of
the xplane, on the clock of the XLA ops it waits on (its interval holds
theirs); nested spans nest; spans outside a capture, or with
TPU_IR_TRACE=0, stay out of it; capture_totals() counts only what a
capture saw and starts afresh with the next; the scorer's load, search
and the streaming build's pass-2 phases are spans where the work is.
Captures run on the CPU backend and are read back with ProfileData.
"""

import glob
import inspect

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from tpu_ir import obs
from tpu_ir.index.streaming import build_index_streaming
from tpu_ir.search import Scorer
from tpu_ir.utils import envvars

WORDS = ("salmon fishing river bears honey quick brown fox lazy dog "
         "market investor asset bond stock season rain forest".split())


@pytest.fixture(autouse=True)
def _trace_on():
    yield
    obs.configure(enabled=True)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("capture")
    body = []
    for i in range(150):
        text = " ".join(WORDS[(i + j) % len(WORDS)]
                        for j in range(3 + (i % 7)))
        body.append(f"<DOC>\n<DOCNO> D-{i:04d} </DOCNO>\n<TEXT>\n"
                    f"{text}\n</TEXT>\n</DOC>\n")
    path = tmp / "corpus.trec"
    path.write_text("".join(body))
    return str(path)


@pytest.fixture(scope="module")
def index_dir(corpus, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("capture_idx") / "idx")
    build_index_streaming([corpus], out, k=1, num_shards=2, batch_docs=50,
                          chargram_ks=[])
    return out


@jax.jit
def _work(x):
    return jnp.sin(x @ x.T).sum()


def _captured(tmp_path, body):
    """Run body() inside a capture; its host-plane events, by name:
    {name: [(start_ns, end_ns, line, stats)]}, and the XLA op events."""
    x = jnp.ones((128, 128))
    _work(x).block_until_ready()  # compiled outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        body(x)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans, ops = {}, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    ops.append((e.start_ns, e.end_ns, stats["hlo_module"]))
                spans.setdefault(e.name, []).append(
                    (e.start_ns, e.end_ns, line.name, stats))
    return spans, ops


def test_span_holds_the_xla_ops_it_waits_on(tmp_path):
    def body(x):
        with obs.trace("x", rows=128, layout="sparse"):
            _work(x).block_until_ready()

    spans, ops = _captured(tmp_path, body)
    ((s, e, _line, stats),) = spans["x"]
    mine = [(a, b) for a, b, m in ops if "_work" in m]
    assert mine, "no XLA op of the jitted call in the capture"
    assert all(s <= a and b <= e for a, b in mine)
    # str/int/float attrs ride along as TraceMe metadata
    assert stats["layout"] == "sparse" and int(stats["rows"]) == 128


def test_nested_spans_nest_on_their_thread(tmp_path):
    def body(x):
        with obs.trace("outer"):
            with obs.trace("inner"):
                _work(x).block_until_ready()

    spans, _ = _captured(tmp_path, body)
    ((os_, oe, oline, _),) = spans["outer"]
    ((is_, ie, iline, _),) = spans["inner"]
    assert oline == iline
    assert os_ <= is_ and ie <= oe


def test_spans_outside_a_capture_are_absent(tmp_path):
    x = jnp.ones((8, 8))
    with obs.trace("before"):
        _work(x).block_until_ready()

    def body(x):
        with obs.trace("during"):
            pass

    spans, _ = _captured(tmp_path, body)
    with obs.trace("after"):
        pass
    assert "during" in spans
    assert "before" not in spans and "after" not in spans
    assert not obs.capture_active()


def test_capture_totals_count_inside_and_clear_at_the_next_capture(
        tmp_path):
    reg = obs.get_registry()
    with obs.trace("x"):
        pass
    reg.incr("prune.queries", 5)

    def body(x):
        with obs.trace("x"):
            with obs.trace("y"):
                pass
        reg.incr("prune.queries", 2)
        reg.observe("batch.wait", 0.25)
        assert obs.capture_totals()["capturing"]

    _captured(tmp_path / "a", body)
    reg.incr("prune.queries", 7)  # after the capture: not in its ledger
    with obs.trace("x"):
        pass
    tot = obs.capture_totals()
    assert not tot["capturing"]
    assert tot["counters"] == {"prune.queries": 2}
    assert {n: h["count"] for n, h in tot["histograms"].items()} == {
        "x": 1, "y": 1, "batch.wait": 1}
    assert tot["histograms"]["batch.wait"]["sum_s"] == 0.25
    # the lifetime registry saw everything
    assert reg.get("prune.queries") == 14
    assert reg.histogram("x").count == 3

    def body2(x):
        reg.incr("prune.queries_hot_free")

    _captured(tmp_path / "b", body2)
    tot = obs.capture_totals()
    assert tot["counters"] == {"prune.queries_hot_free": 1}
    assert tot["histograms"] == {}
    # the operator's view of the same ledger
    assert obs.profile_report()["capture"]["counters"] == tot["counters"]


def test_trace_disabled_records_nothing_in_a_capture(tmp_path):
    obs.configure(enabled=False)

    def body(x):
        with obs.trace("off"):
            _work(x).block_until_ready()

    spans, _ = _captured(tmp_path, body)
    assert "off" not in spans
    assert "off" not in obs.capture_totals()["histograms"]
    assert obs.get_registry().histogram("off").count == 0


def test_the_opt_in_kernel_annotation_is_gone():
    assert not hasattr(obs, "kernel_annotation")
    assert "TPU_IR_JAX_TRACE" not in envvars.declared_names()
    assert "jax_annotations" not in inspect.signature(
        obs.configure).parameters


def test_load_stages_are_children_of_the_load_span(corpus, tmp_path):
    out = str(tmp_path / "idx")  # fresh: the serving cache misses
    build_index_streaming([corpus], out, k=1, num_shards=2, batch_docs=50,
                          chargram_ks=[])
    obs.clear_traces()
    Scorer.load(out, layout="sparse")
    (load,) = [t for t in obs.recent_traces() if t.name == "load"]
    names = [c.name for c in load.children]
    for stage in ("load.read", "load.assemble", "load.layout",
                  "load.cache_write", "load.h2d"):
        assert stage in names, stage
    # load.verify folds into the reads
    for read in (c for c in load.children if c.name == "load.read"):
        assert all(c.name == "load.verify" for c in read.children)
    stage_s = sum(c.dur_ns for c in load.children)
    assert stage_s <= load.dur_ns


def test_search_span_holds_analysis_schedule_and_device_wait(index_dir,
                                                             tmp_path):
    scorer = Scorer.load(index_dir, layout="sparse")
    scorer.search_batch(["salmon fishing", "bears"], k=5, scoring="bm25")
    obs.clear_traces()
    scorer.search_batch(["salmon fishing", "bears"], k=5, scoring="bm25")
    (search,) = obs.recent_traces()
    assert search.name == "search"
    names = [c.name for c in search.children]
    assert names[0] == "search.analyze" and "dispatch" in names
    disp = search.children[names.index("dispatch")]
    flat = [c.name for c in disp.children]
    assert flat[0] == "search.schedule"
    assert "kernel" in flat and "dispatch.device" in flat

    def body(x):
        scorer.search_batch(["salmon fishing"], k=5, scoring="bm25")

    spans, _ = _captured(tmp_path, body)
    for name in ("search", "search.analyze", "search.schedule", "dispatch",
                 "kernel", "dispatch.device"):
        assert name in spans, name
    ((_, _, _, stats),) = spans["kernel"]
    assert stats["layout"] == "sparse" and stats["scoring"] == "bm25"


def test_pass2_phases_nest_inside_pass2_combine(corpus, tmp_path):
    import json

    out = str(tmp_path / "idx")
    build_index_streaming([corpus], out, k=1, num_shards=2, batch_docs=50,
                          chargram_ks=[], radix_buckets=4)
    with open(f"{out}/jobs/TermKGramDocIndexer.json") as f:
        t = json.load(f)["timings_s"]
    parts = ("pass2_upload", "pass2_device_wait", "pass2_fetch",
             "pass2_spill")
    assert all(p in t for p in parts)
    assert sum(t[p] for p in parts) <= t["pass2_combine"]
    (combine,) = [s for s in obs.recent_traces()
                  if s.name == "build.pass2_combine"]
    kids = {c.name for c in combine.children}
    assert {f"build.{p}" for p in parts} <= kids
    # four buckets: each part once per bucket
    assert obs.get_registry().histogram("build.pass2_spill").count == 4
    # the metadata write (checksums, block-max bounds) is a phase too
    assert t["finalize"] > 0
