"""Whole runs of each cell at a tiny size on the CPU, sound and with the
timed path broken underneath: a sound run is correct, and each fault the
cell can have (an answer altered where it is produced, half of a batch
left out, a posting altered by the build) makes `correct` false. The
lower-precision control (bfloat16 weights in the program's place) fails
too. And without a chip, run.py exits non-zero and prints no result."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import control, run

from .conftest import ROOT, TINY

CELLS = sorted(TINY)
SEARCH_CELLS = [c for c in CELLS if not c.endswith(".build")]


def run_cell(cell, capsys, seed=2**31 + 17, seconds=1.0, trace=0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], allow_cpu=True,
                  overrides=dict(TINY[cell]), t_process=time.perf_counter())
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, capsys):
    res = run_cell(cell, capsys)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"setup_s"} < set(res["metrics"])
    assert res["checks"]["max_rel_score_error"]["value"] < 1e-5


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell, capsys):
    res = run_cell(cell, capsys, trace=1)
    assert res["correct"]
    assert res["metrics"] and "setup_s" not in res["metrics"]
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10


def _altered(orig):
    def topk_tagged(self, *a, **kw):
        scores, docnos, degraded = orig(self, *a, **kw)
        scores = np.array(scores, copy=True)
        scores[:, 0] *= 1.001  # one answer altered where it is produced
        return scores, docnos, degraded
    return topk_tagged


def _half_left_out(orig):
    def topk_tagged(self, *a, **kw):
        scores, docnos, degraded = orig(self, *a, **kw)
        docnos = np.array(docnos, copy=True)
        docnos[len(docnos) // 2:] = 0  # half of the batch left out
        return scores, docnos, degraded
    return topk_tagged


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_in_the_timed_path_is_not_correct(cell, fault, capsys,
                                                monkeypatch):
    from tpu_ir.search.scorer import Scorer

    monkeypatch.setattr(Scorer, "topk_tagged", fault(Scorer.topk_tagged))
    assert not run_cell(cell, capsys)["correct"]


def test_build_with_an_altered_posting_is_not_correct(capsys, monkeypatch):
    from tpu_ir.index import format as fmt

    orig = fmt.save_shard

    def save_shard(index_dir, shard, **kw):
        if shard == 0 and len(kw["pair_tf"]):
            kw["pair_tf"] = np.array(kw["pair_tf"], copy=True)
            kw["pair_tf"][0] += 1
        return orig(index_dir, shard, **kw)

    monkeypatch.setattr(fmt, "save_shard", save_shard)
    assert not run_cell("robust04.build", capsys)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(cell):
    t = control.control_tally(cell, 2**31 + 3, overrides=dict(TINY[cell]))
    assert t.checked > 0 and not t.correct()
    assert t.max_rel > 1e-4


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
