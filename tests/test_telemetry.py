"""Telemetry subsystem: disabled-path zero-overhead guarantees, Chrome
trace schema/nesting, bandwidth-ledger drift exactness on a plan whose
cardinality estimates are provably exact, consolidated executor metrics
(back-compat properties included), and honest serving sojourns."""
import json
import sys
import time

import numpy as np
import pytest

import jax

from repro.columnar.table import Table
from repro.core.sgd_glm import HyperParams
from repro.query import (
    Catalog, CostModel, Executor, Q, QueryServer,
)
from repro.query import telemetry as tm


def _exact_catalog(n=1 << 14, domain=128):
    """Data on which the optimizer's uniform-domain selectivity estimate
    is EXACT: ``v`` cycles 0..domain-1 with every value equally frequent
    (and n a multiple of the domain), so a range predicate's estimated
    row count equals its measured row count — making the ledger's
    drift_bytes exactly 1.0 on every operator."""
    v = (np.arange(n, dtype=np.int32) % domain).astype(np.int32)
    w = np.ones(n, dtype=np.int32)
    t = Table.from_arrays("t", {"v": v, "w": w})
    return Catalog.from_tables(t), v


def _scan_filter_sum(lo=10, hi=41):
    return Q.scan("t", ("v", "w")).filter("v", lo, hi).sum("w")


# --------------------------------------------------------------------------- #
# disabled path

def test_disabled_records_nothing():
    tel = tm.Telemetry(enabled=False)
    cat, _ = _exact_catalog(1 << 12)
    ex = Executor(cat, telemetry=tel)
    for _ in range(3):
        ex.execute(_scan_filter_sum())
        ex.execute(_scan_filter_sum(), mode="eager")
    assert tel.tracer.events == []
    assert tel.ledger.rows == []
    assert tel.tracer.dropped == 0


def test_disabled_span_is_shared_singleton():
    """A disabled span is only the profiler's annotation: it records no
    event, keeps none of its attributes, and nothing but its caller holds
    it, so no span outlives its query."""
    tel = tm.Telemetry(enabled=False)
    with tm.request(7), tel.span("a", k=1) as sp:
        assert sp.set(path="x") is sp
    assert sp.args is None
    assert tel.tracer.events == [] and tel.tracer.dropped == 0
    assert sys.getrefcount(sp) == 2            # the local and the argument


def test_disabled_no_container_growth():
    """No telemetry container grows with query count when disabled."""
    tel = tm.Telemetry(enabled=False)
    cat, _ = _exact_catalog(1 << 12)
    ex = Executor(cat, telemetry=tel)
    ex.execute(_scan_filter_sum())           # warm compile caches
    sizes = (len(tel.tracer.events), len(tel.ledger.rows))
    for i in range(10):
        ex.execute(_scan_filter_sum(1, 20 + i))
    assert (len(tel.tracer.events), len(tel.ledger.rows)) == sizes


# --------------------------------------------------------------------------- #
# enabled: Chrome trace schema + nesting

def _interval(e):
    return e["ts"], e["ts"] + e["dur"]


def _contains(outer, inner, slack=1.0):
    o0, o1 = _interval(outer)
    i0, i1 = _interval(inner)
    return o0 - slack <= i0 and i1 <= o1 + slack


def test_chrome_trace_schema_and_nesting(tmp_path):
    tel = tm.Telemetry(enabled=True)
    cat, _ = _exact_catalog(1 << 12)
    ex = Executor(cat, telemetry=tel)
    ex.execute(_scan_filter_sum())
    path = tel.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert events, "enabled run must emit events"
    for e in events:
        assert set(("name", "ph", "pid", "tid", "ts")) <= set(e)
        assert e["ph"] in ("X", "i")
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    # the span hierarchy the ISSUE names: execute > plan > optimize and
    # physical costing — nested by interval containment on one tid
    execute = by_name["exec.execute"][0]
    plan = by_name["exec.plan"][0]
    for name in ("exec.optimize", "exec.cost_physical"):
        assert _contains(plan, by_name[name][0])
    assert _contains(execute, plan)
    assert execute["args"]["path"] == "batch"


def _train_catalog(m=500, seed=0):
    rng = np.random.default_rng(seed)
    cols = {f"f{i}": rng.normal(size=m).astype(np.float32)
            for i in range(3)}
    cols["y"] = (cols["f0"] - cols["f1"] > 0).astype(np.float32)
    return Catalog.from_tables(Table.from_arrays("g", cols))


def _train_q(epochs=1):
    return Q.scan("g").train_glm(["f0", "f1", "f2"], "y",
                                 [HyperParams(0.1, 0.0),
                                  HyperParams(0.05, 0.01)], epochs=epochs)


def _host_events(trace_dir, layers):
    """``(name, start, end, stats)`` of the profiler trace's host events
    whose names start with one of ``layers`` and a dot."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.split(".")[0] in layers]


def test_program_spans_nest_in_the_profiler_trace(tmp_path):
    """With the Chrome sink off, one served train query's spans land in
    the profiler's trace on the host clock, nested inside a benchmark
    annotation, each tagged with the query's id."""
    ex = Executor(_train_catalog(), telemetry=tm.Telemetry(enabled=False))
    srv = QueryServer(ex)
    srv.query(_train_q())                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        qid = srv.submit(_train_q())
        with jax.profiler.TraceAnnotation("bench.drain"):
            out = srv.drain()
    finally:
        jax.profiler.stop_trace()
    assert qid in out
    evs = _host_events(tmp_path, ("bench", "serve", "exec", "trainer"))
    one = {n: (s, e, st) for n, s, e, st in evs}
    chain = ["bench.drain", "serve.drain", "exec.execute", "exec.run_train",
             "trainer.epoch_step"]
    for outer, inner in zip(chain, chain[1:]):
        assert one[outer][0] <= one[inner][0] <= one[inner][1] \
            <= one[outer][1], (outer, inner)
    program = [st for n, s, e, st in evs
               if n.split(".")[0] in ("serve", "exec", "trainer")]
    assert {"serve.submit", "trainer.stage", "trainer.loss_step"} <= set(one)
    assert {st.get("qid") for st in program} == {qid}


@pytest.mark.parametrize("path", ["fused", "stream", "train", "serve"])
def test_fences_only_with_the_ledger(path, monkeypatch):
    """With the ledger off, no span path waits on the device; with it on,
    each measured path fences as before."""
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    def run(tel):
        if path == "train":
            ex = Executor(_train_catalog(), telemetry=tel)
            ex.execute(_train_q(), morsel_rows=208)
            return
        cat, _ = _exact_catalog()
        ex = Executor(cat, telemetry=tel)
        if path == "fused":
            ex.execute(_scan_filter_sum())
        elif path == "stream":
            ex.execute(_scan_filter_sum(), mode="stream",
                       morsel_rows=1 << 12)
        else:
            srv = QueryServer(ex, streaming=True, morsel_rows=1 << 12)
            for lo in (3, 3, 4):     # the first advance warms the stream
                srv.submit(_scan_filter_sum(lo, 60))
                srv.drain()

    run(tm.Telemetry(enabled=False))           # compile unpatched
    monkeypatch.setattr(jax, "block_until_ready", counting)
    run(tm.Telemetry(enabled=False))
    assert calls == []
    tel = tm.Telemetry(enabled=True)
    run(tel)
    assert calls and tel.ledger.rows


def test_trainer_trace_count_repeats():
    """Each call traces the trainer's two steps anew (fresh closures),
    and the count repeats on a second run of the same query."""
    ex = Executor(_train_catalog(), telemetry=tm.Telemetry(enabled=False))
    counts = []
    for _ in range(2):
        before = ex.metrics.value("trainer.traces")
        ex.execute(_train_q(epochs=2), morsel_rows=208)
        counts.append(ex.metrics.value("trainer.traces") - before)
    # 500 rows in morsels of 208, 208 and 96 (the tail, padded to the
    # minibatch).  Each step traces 3 times whatever the epochs: its first
    # call takes the fresh zeros carry, not yet committed to a device,
    # so the second 208-row morsel traces again; then the 96-row tail
    assert counts == [6, 6]


def _epoch_step_impls(tel):
    return [e["args"]["impl"] for e in tel.tracer.events
            if e["name"] == "trainer.epoch_step"]


def test_trainer_runs_the_xla_loop_on_cpu():
    """On the CPU the trainer takes the XLA loop: no kernel dispatch is
    counted and every epoch step's span says so."""
    tel = tm.Telemetry(enabled=True)
    ex = Executor(_train_catalog(), telemetry=tel)
    ex.execute(_train_q(epochs=2), morsel_rows=208)
    assert ex.metrics.value("trainer.sgd_kernel_calls") == 0
    assert _epoch_step_impls(tel) == ["xla"] * 6


def test_trainer_counts_each_kernel_dispatch(monkeypatch):
    """With the kernel's rule forced (and the kernel interpreted, as the
    CPU needs), each epoch step of each morsel dispatches the kernel once,
    counted and named on its span, and the feature-major path trains and
    scores as the XLA loop does."""
    from functools import partial
    from repro.columnar import engine
    want = Executor(_train_catalog()).execute(_train_q(epochs=2),
                                              morsel_rows=208)
    monkeypatch.setattr(engine, "sgd_kernel_applies", lambda mesh: True)
    monkeypatch.setattr(engine, "sgd_block",
                        partial(engine.sgd_block, interpret=True))
    tel = tm.Telemetry(enabled=True)
    ex = Executor(_train_catalog(), telemetry=tel)
    got = ex.execute(_train_q(epochs=2), morsel_rows=208)
    # 500 rows in morsels of 208, 208 and 96, two epochs
    assert ex.metrics.value("trainer.sgd_kernel_calls") == 6
    assert _epoch_step_impls(tel) == ["pallas"] * 6
    for g, w in zip(got.value, want.value):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5)


def test_trace_bounded_by_max_events():
    tel = tm.Telemetry(enabled=True)
    tel.tracer.max_events = 10
    for i in range(25):
        tel.instant("e", i=i)
    assert len(tel.tracer.events) == 10
    assert tel.tracer.dropped == 15
    assert tel.tracer.chrome_trace()["otherData"]["dropped_events"] == 15


# --------------------------------------------------------------------------- #
# the bandwidth ledger

def test_eager_ledger_drift_bytes_exact():
    """On exact-estimate data the eager path's measured bytes reproduce
    the cost model's predicted bytes operator for operator: drift_bytes
    == 1.0 for EVERY costed op in the plan."""
    tel = tm.Telemetry(enabled=True)
    cat, v = _exact_catalog()
    ex = Executor(cat, telemetry=tel)
    q = _scan_filter_sum(10, 41)
    r = ex.execute(q, mode="eager")
    assert int(r.value) == int(((v >= 10) & (v <= 41)).sum())
    phys_ops = sorted(p.op for p in _walk(ex.plan(
        q.node if hasattr(q, "node") else q)[1]))
    assert sorted(row.op for row in tel.ledger.rows) == phys_ops
    for row in tel.ledger.rows:
        assert row.mode == "eager" and not row.attributed
        assert row.drift_bytes == pytest.approx(1.0, rel=1e-6), row.op
        assert row.measured_s >= 0.0
        assert row.predicted_s > 0.0


def _walk(p):
    yield p
    for c in p.children:
        yield from _walk(c)


def test_fused_ledger_covers_every_costed_operator():
    tel = tm.Telemetry(enabled=True)
    cat, _ = _exact_catalog(1 << 12)
    ex = Executor(cat, telemetry=tel)
    q = _scan_filter_sum()
    ex.execute(q)                                 # fused batch path
    node = q.node if hasattr(q, "node") else q
    n_ops = len(list(_walk(ex.plan(node)[1])))
    fused = [r for r in tel.ledger.rows if r.mode == "fused"]
    assert len(fused) == n_ops
    assert all(r.attributed for r in fused)
    assert all(r.measured_bytes > 0 for r in fused)


def test_stream_ledger_and_morsel_metrics():
    tel = tm.Telemetry(enabled=True)
    cat, v = _exact_catalog()
    ex = Executor(cat, telemetry=tel)
    q = _scan_filter_sum(0, 63)
    r = ex.execute(q, mode="stream", morsel_rows=1 << 12)
    assert int(r.value) == int(((v >= 0) & (v <= 63)).sum())
    assert r.mode == "stream"
    # op="promote" rows (spill-promotion traffic when a placement cap
    # forces columns below the device tier, e.g. the tiered CI leg) are
    # individually fenced, not plan-attributed — exclude them
    streamed = [row for row in tel.ledger.rows
                if row.mode == "stream" and row.op != "promote"]
    assert streamed and all(row.attributed for row in streamed)
    snap = ex.metrics_snapshot()
    assert snap["pipeline.morsels"] >= 2
    assert snap["pipeline.transfer_wait_s"] >= 0.0
    assert snap["pipeline.compute_s"] > 0.0
    names = {e["name"] for e in tel.tracer.events}
    assert "pipeline.morsel_step" in names
    assert "exec.run_stream" in names


def test_calibration_overlay_feeds_cost_model():
    """The ledger's overlay is consumable where a calibration file is:
    recalibration is the documented one-liner."""
    tel = tm.Telemetry(enabled=True)
    cat, _ = _exact_catalog(1 << 12)
    ex = Executor(cat, telemetry=tel)
    ex.execute(_scan_filter_sum(), mode="eager")
    overlay = tel.ledger.calibration_overlay(ex.cost_model)
    assert overlay["backend"] == "ledger"
    assert "xla" in overlay["backends"]
    b = overlay["backends"]["xla"]
    assert 0.0 < b["stream_eff"] <= 1.0
    model = CostModel(ex.cost_model.n_engines, calibration=overlay)
    assert model.calibrated_from == "ledger"
    assert model.stream_eff["xla"] == pytest.approx(b["stream_eff"])
    # and the online form: fold measurements into a LIVE model
    ex.cost_model._apply_calibration(overlay)
    assert ex.cost_model.calibrated_from == "ledger"


def test_drift_report_and_top_drift():
    tel = tm.Telemetry(enabled=True)
    cat, _ = _exact_catalog(1 << 12)
    ex = Executor(cat, telemetry=tel)
    ex.execute(_scan_filter_sum(), mode="eager")
    rep = tel.ledger.report()
    for op in ("scan", "filter", "aggregate"):
        assert op in rep
    top = tel.ledger.top_drift(2)
    assert len(top) == 2
    assert abs(top[0]["drift_time"] - 1.0) >= \
        abs(top[1]["drift_time"] - 1.0)
    assert tm.Telemetry(enabled=True).ledger.report() \
        == "bandwidth ledger: no measurements recorded"


# --------------------------------------------------------------------------- #
# consolidated executor metrics

def test_counters_consolidated_with_backcompat_names():
    tel = tm.Telemetry(enabled=False)
    cat, _ = _exact_catalog(1 << 12)
    ex = Executor(cat, telemetry=tel)
    q = _scan_filter_sum()
    ex.execute(q)
    ex.execute(q)
    # old attribute names read through to the registry
    assert ex.cache_misses == 1 and ex.cache_hits == 1
    assert ex.metrics.value("exec.plan_cache_misses") == 1
    assert ex.metrics.value("exec.plan_cache_hits") == 1
    # external writers still work (serve.py does ``ex.result_hits += 1``)
    ex.result_hits += 1
    assert ex.metrics.value("exec.result_cache_hits") == 1
    snap = ex.metrics_snapshot()
    assert snap["exec.plan_cache_hits"] == 1
    ex.reset_metrics()
    assert ex.cache_hits == 0 and ex.result_hits == 0
    # stats_dict's legacy keys survive the consolidation
    sd = ex.stats_dict()
    assert sd["plan_cache_hits"] == 0
    assert "trace_count" in sd


def test_private_registries_do_not_mix():
    cat, _ = _exact_catalog(1 << 12)
    tel = tm.Telemetry(enabled=False)
    ex1 = Executor(cat, telemetry=tel)
    ex2 = Executor(cat, telemetry=tel)
    ex1.execute(_scan_filter_sum())
    assert ex1.cache_misses == 1
    assert ex2.cache_misses == 0


# --------------------------------------------------------------------------- #
# serving sojourns

def test_server_sojourn_includes_queue_wait():
    """A query's latency is admission -> completion, not the amortized
    kernel time: sleeping between submit and drain must show up."""
    cat, _ = _exact_catalog(1 << 12)
    ex = Executor(cat, telemetry=tm.Telemetry(enabled=False))
    srv = QueryServer(ex)
    wait = 0.05
    # two compatible selections force the micro-batch path; the third is
    # a lone single through the executor
    srv.submit(_scan_filter_sum(1, 10))
    srv.submit(_scan_filter_sum(2, 20))
    srv.submit(Q.scan("t", ("v", "w")).filter("v", 0, 5)
               .aggregate("count", "v"))
    time.sleep(wait)
    srv.drain()
    assert len(srv.history) == 3
    for rec in srv.history:
        assert rec.t_complete > rec.t_submit > 0.0
        assert rec.latency_s >= wait
        assert rec.latency_s == pytest.approx(
            rec.t_complete - rec.t_submit)
    assert {r.path for r in srv.history} == {"microbatch", "exec"}
    snap = ex.metrics_snapshot()
    assert snap["serve.sojourn_s.count"] == 3
    assert snap["serve.sojourn_s.p50"] >= wait
    assert snap["serve.batch_size.max"] == 3


def test_streaming_server_sojourns_are_stamped():
    cat, _ = _exact_catalog()
    ex = Executor(cat, telemetry=tm.Telemetry(enabled=False))
    srv = QueryServer(ex, streaming=True, morsel_rows=1 << 12)
    srv.submit(_scan_filter_sum(5, 60))
    srv.submit(_scan_filter_sum(5, 60))      # dedup rider
    out = srv.drain()
    assert len(out) == 2
    for rec in srv.history:
        assert rec.t_complete > rec.t_submit
        assert rec.latency_s == pytest.approx(
            rec.t_complete - rec.t_submit)
    assert {r.path for r in srv.history} == {"stream", "dedup"}


# --------------------------------------------------------------------------- #
# registry mechanics

def test_metrics_registry_snapshot_and_histograms():
    m = tm.MetricsRegistry()
    m.inc("a")
    m.inc("a", 4)
    m.set("g", 7)
    for x in (1.0, 2.0, 3.0, 4.0):
        m.observe("h", x)
    snap = m.snapshot()
    assert snap["a"] == 5 and snap["g"] == 7
    assert snap["h.count"] == 4
    assert snap["h.mean"] == pytest.approx(2.5)
    assert snap["h.max"] == 4.0
    m.reset()
    assert m.snapshot() == {}


def test_global_telemetry_swap():
    tel = tm.Telemetry(enabled=True)
    tm.set_global(tel)
    try:
        assert tm.get() is tel
        cat, _ = _exact_catalog(1 << 12)
        ex = Executor(cat)                   # no explicit telemetry
        assert ex.tel is tel
    finally:
        tm.set_global(None)
