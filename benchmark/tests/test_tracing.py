"""The trace reduction on small recorded traces: one recorded here on
the CPU, one recorded on a TPU v5e (data/tiny_tpu.xplane.pb: three runs
of a jitted 512x512 matmul and sort inside the `bench.window` span)."""

import os

import pytest

from benchmark import tracing

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_overlaps():
    assert tracing._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3],
                                                               [5, 9]]


def test_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = tracing.reduce_trace(tracing.find_xplane(str(tmp_path)))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert "jit__lambda" in red["module_s"]
    assert sum(red["module_s"].values()) <= red["window_s"]
    gaps = red["gaps"]
    assert gaps and all(g > 0 for g, _ in gaps)
    assert abs(red["busy_s"] + sum(g for g, _ in gaps)
               - red["window_s"]) < 1e-6
    b = tracing.breakdown(red)
    assert set(b) == {"device_ops", "idle_gaps"}


def test_tpu_trace():
    path = os.path.join(DATA, "tiny_tpu.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded TPU trace")
    red = tracing.reduce_trace(path)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    mods = red["module_s"]
    assert mods and all(not k.endswith(")") for k in mods)
    assert sum(mods.values()) <= red["window_s"] * 1.0001
    assert abs(red["busy_s"] + sum(g for g, _ in red["gaps"])
               - red["window_s"]) < 1e-6


def test_peaks_table():
    p = tracing.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        tracing.peaks("no such device")
