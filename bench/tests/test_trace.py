"""Check of the trace reduction (``bench/trace.py``) and of the roofline
arithmetic (``bench/roofline.py``): on hand-made intervals, and on a
small trace recorded here from the CPU backend, whose operations run on
host threads.  Nothing here is a device reading.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_trace.py
"""
import time
import types

import pytest

from bench import roofline
from bench import trace as btrace


def test_union_merges_and_clips():
    assert btrace.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 11) == \
        [[1, 4], [5, 11]]
    assert btrace.union([(0, 1)], 2, 3) == []


def test_gaps_complement_busy():
    busy = btrace.union([(2, 4), (6, 7)], 0, 10)
    assert btrace.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert btrace.gaps([], 0, 10) == [(0, 10)]


def test_reduce_on_made_up_trace():
    raw = {"spans": [("bench.window", 0, 100), ("bench.wait", 0, 40),
                     ("bench.drain", 40, 100), ("bench.fetch", 90, 100)],
           "ops": {"/device:TPU:0": [("fusion.1", 45, 60),
                                     ("fusion.2", 55, 80),
                                     ("fusion.1", 120, 130)]}}
    r = btrace.reduce(raw)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(35e-9)          # 45..80
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(25e-9)]
    gaps = dict(r["idle_gaps"])
    # idle 0..45 and 80..100, split over the innermost span under each part
    assert gaps["bench.wait"] == pytest.approx(40e-9)
    assert gaps["bench.drain"] == pytest.approx(15e-9)
    assert gaps["bench.fetch"] == pytest.approx(10e-9)


def test_segments_name_the_innermost_span():
    spans = [("bench.window", 0, 50), ("bench.drain", 10, 40),
             ("bench.fetch", 30, 35), ("bench.wait", 45, 60)]
    assert btrace.segments(spans, 0, 50) == [
        (0, 10, "host"), (10, 30, "bench.drain"), (30, 35, "bench.fetch"),
        (35, 40, "bench.drain"), (40, 45, "host"), (45, 50, "bench.wait")]


def test_roofline_and_idle_arithmetic():
    t = types.SimpleNamespace(
        name="q", spec={"kind": "k"},
        kind=types.SimpleNamespace(least_bytes=lambda s, c, z: 819e6))
    run = types.SimpleNamespace(
        trace={"busy_s": 0.004, "window_s": 0.01, "n_devices": 1},
        peaks={"hbm_bytes_per_s": 819e9}, templates=[t], config={},
        sizes={}, counters={"program_calls": {"q": 2}})
    # 2 calls x 819 MB at 819 GB/s = 2 ms of 4 ms busy
    assert roofline.share(run, "k") == pytest.approx(50.0)
    assert roofline.idle(run) == pytest.approx(60.0)
    run.counters = {"program_calls": {"q": 0}}
    assert roofline.share(run, "k") is None        # nothing to read
    run.peaks = None
    assert roofline.share(run, "k") is None        # no peak: no share


def test_mfu_arithmetic():
    t = types.SimpleNamespace(
        name="q", spec={"kind": "k"},
        kind=types.SimpleNamespace(flops=lambda s, c, z: 197e9))
    run = types.SimpleNamespace(
        trace={"busy_s": 0.004, "window_s": 0.01, "n_devices": 1},
        peaks={"bf16_flops_per_s": 197e12}, templates=[t], config={},
        sizes={}, counters={"program_calls": {"q": 3}})
    # 3 calls x 197 GFLOP at 197 TFLOP/s = 3 ms of a 10 ms window
    assert roofline.mfu(run, "k") == pytest.approx(30.0)
    run.counters = {"program_calls": {}}
    assert roofline.mfu(run, "k") is None


def test_train_query_operations():
    from bench import harness
    from bench.queries import train_glm
    _, _, config, traffic, _ = harness.load_cell("higgs.train")
    spec = traffic["queries"][0]
    # 11M rows x 4 models x 28 features x (4 x 1 epoch + 2)
    assert train_glm.flops(spec, config, {"higgs": 11_000_000}) == \
        11_000_000 * 4 * 28 * 6


def test_reduce_on_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        return jnp.sum(jnp.sin(x) * jnp.cos(x))

    x = jnp.arange(1 << 20, dtype=jnp.float32)
    work(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(btrace.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("bench.drain"):
                work(x).block_until_ready()
    jax.profiler.stop_trace()
    raw = btrace.read(btrace.latest_xplane(str(tmp_path)), device="cpu")
    r = btrace.reduce(raw)
    assert 0.06 <= r["window_s"] < 5
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    gaps = dict(r["idle_gaps"])
    # the three sleeps are the longest idle stretches
    assert max(gaps, key=gaps.get) == "bench.wait"
    assert gaps["bench.wait"] >= 0.05
    # a TPU reading of the same file finds no device plane
    assert btrace.read(btrace.latest_xplane(str(tmp_path)))["ops"] == {}
