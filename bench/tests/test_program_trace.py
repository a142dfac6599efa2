"""Check of the idle split by program layer (``bench/program_trace.py``):
on hand-made nested intervals, and on a small train query served on the
CPU backend under the profiler, whose operations run on host threads.
Nothing here is a device reading.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_program_trace.py
"""
import types

import numpy as np
import pytest

from bench import program_trace as pt
from bench import trace as btrace

# the window 0..100; the device busy 50..70 (the epoch) and 75..80 (the
# losses); a submit, a drain holding the program's nested spans, a fetch,
# and 98..100 under no span at all
SPANS = [("bench.window", 0, 100), ("bench.submit", 0, 10),
         ("bench.drain", 10, 90), ("bench.fetch", 90, 98)]
PROGRAM = [("serve.submit", 2, 8), ("serve.drain", 12, 88),
           ("exec.execute", 14, 86), ("exec.run_train", 16, 84),
           ("trainer.stage", 18, 30), ("trainer.epoch_step", 30, 70),
           ("trainer.loss_step", 70, 82)]
JAX = [("PjitFunction(epoch_step)", 31, 69),
       ("PjitFunction(epoch_step)", 31, 69),
       ("lower_sharding_computation", 35, 50),
       ("trace_to_jaxpr_dynamic", 36, 40),
       ("PjitFunction(sgd_ref)", 32, 34),
       ("shard_args", 20, 22),
       ("PjitFunction(add)", 88, 89)]       # outside every trainer span
RAW = {"spans": SPANS,
       "ops": {"/device:TPU:0": [("while", 50, 70), ("loss", 75, 80)]}}
PROG = {"program": PROGRAM, "jax": JAX,
        "marks": [("trainer.trace", 33, 33), ("trainer.trace", 71, 71),
                  ("trainer.trace", 120, 120)]}


def test_jax_phases_stop_two_names_below_the_span():
    got = pt.jax_phases(PROGRAM, JAX)
    assert sorted(n for n, _, _ in got) == sorted([
        "trainer.epoch_step/PjitFunction(epoch_step)",
        "trainer.epoch_step/PjitFunction(epoch_step)",
        "trainer.epoch_step/lower_sharding_computation",
        "trainer.epoch_step/PjitFunction(sgd_ref)",
        "trainer.stage/shard_args"])


def test_layer_split_sums_to_the_idle_total():
    split = pt.program_idle(RAW, PROG)
    gaps = {n: v * 1e9 for n, v in split["gaps"]}
    assert gaps == pytest.approx({
        "bench.submit": 4, "serve.submit": 6, "bench.drain": 2 + 2,
        "serve.drain": 2 + 2, "exec.execute": 2 + 2,
        "exec.run_train": 2 + 2,
        "trainer.stage": 10, "trainer.stage/shard_args": 2,
        "trainer.epoch_step": 1,
        "trainer.epoch_step/PjitFunction(epoch_step)": 2,
        "trainer.epoch_step/PjitFunction(sgd_ref)": 2,
        "trainer.epoch_step/lower_sharding_computation": 15,
        "trainer.loss_step": 5 + 2, "bench.fetch": 8, "host": 2})
    layers = {k: v * 1e9 for k, v in split["layers"].items()}
    assert layers == pytest.approx(
        {"serve": 10, "exec": 8, "trainer": 39, "client": 18})
    # the device was idle 75 of the window's 100
    assert sum(layers.values()) == pytest.approx(75)
    assert btrace.reduce(RAW)["busy_s"] * 1e9 == pytest.approx(25)


def test_traces_count_marks_inside_the_window():
    assert pt.traces(RAW, PROG) == 2


def test_no_program_span_reads_nothing():
    """A program that opens no span (one older than the spans) gives no
    reading, so its line leaves the metrics out."""
    empty = {"program": [], "jax": JAX, "marks": []}
    assert pt.program_idle(RAW, empty) is None
    assert pt.traces(RAW, empty) is None
    run = types.SimpleNamespace(trace=None)
    assert pt.idle_share(run, "trainer") is None
    assert pt.for_run(run) is None


def test_split_on_recorded_cpu_trace(tmp_path):
    """One served train query under the profiler: the program's spans
    nest inside the benchmark's, the layers and the client add up to the
    window's idle time, and each of the trainer's two steps traces once."""
    import jax
    from repro.columnar.table import Table
    from repro.core.sgd_glm import HyperParams
    from repro.query import Catalog, Executor, Q, QueryServer
    from repro.query import telemetry as tm

    rng = np.random.default_rng(0)
    cols = {f"f{i}": rng.normal(size=2048).astype(np.float32)
            for i in range(4)}
    cols["y"] = (cols["f0"] > 0).astype(np.float32)
    srv = QueryServer(Executor(Catalog.from_tables(
        Table.from_arrays("g", cols)), telemetry=tm.Telemetry(False)))
    q = Q.scan("g").train_glm(["f0", "f1", "f2", "f3"], "y",
                              [HyperParams(0.1, 0.0)], epochs=1)
    srv.query(q)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(btrace.WINDOW):
        with jax.profiler.TraceAnnotation("bench.submit"):
            qid = srv.submit(q)
        with jax.profiler.TraceAnnotation("bench.drain"):
            out = srv.drain()
    jax.profiler.stop_trace()
    assert qid in out
    path = btrace.latest_xplane(str(tmp_path))
    raw = btrace.read(path, device="cpu")
    prog = pt.read_program(path)
    names = {n for n, _, _ in prog["program"]}
    assert {"serve.submit", "serve.drain", "exec.execute", "exec.run_train",
            "trainer.stage", "trainer.epoch_step",
            "trainer.loss_step"} <= names
    assert pt.traces(raw, prog) == 2
    split = pt.program_idle(raw, prog)
    r = btrace.reduce(raw)
    assert sum(split["layers"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9, abs=1e-12)
    assert split["layers"]["trainer"] > 0
    assert any(n.startswith("trainer.epoch_step/PjitFunction(")
               for n, _ in split["gaps"])
