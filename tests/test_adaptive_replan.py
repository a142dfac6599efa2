"""Adaptive re-plan differential suite.

Pins the three staleness bugs the adaptive serving loop exposed, and the
loop's own invariants:

* calibration application is IDEMPOTENT (same overlay twice changes no
  price) and a partial overlay re-baselines against the pristine
  defaults instead of compounding into already-overlaid constants;
* the generate→apply→regenerate cycle is STABLE — the overlay is
  anchored on measurements against the raw bandwidth curve, never on the
  model's current (possibly already-overlaid) efficiency;
* ``Executor.recost()`` bumps the cost epoch, which participates in
  every plan-cache key, so re-costed decisions can never silently reuse
  a stale compiled plan;
* a mid-stream recalibration PINS in-flight members to their original
  compiled pipeline (new admissions form new groups) and results stay
  bit-identical to a cache-disabled oracle before/during/after;
* drift returns toward 1.0 after the overlay is applied;
* QoS: priority-ordered admission keeps the high-priority tenant's p95
  at or below the low-priority one's under saturation; backpressure
  defers best-effort admissions (and only them) and never loses a query;
  per-tenant cache shares cap one tenant's resident bytes without
  touching another's.
"""
import numpy as np
import pytest

from repro.columnar.table import Table
from repro.query import (
    AdaptivePolicy, Catalog, CostModel, Executor, Q, QueryServer,
    SemanticCache, TenantSpec,
)
from repro.query.cost import (
    PALLAS_STREAM_EFF, XLA_CALL_OVERHEAD, XLA_STREAM_EFF,
)
from repro.query import telemetry as tm


def _make_catalog(r, n=4096, n_small=512, vmax=100):
    big = Table.from_arrays("big", {
        "k": r.integers(0, 1000, size=n).astype(np.int32),
        "v": r.integers(0, vmax, size=n).astype(np.int32),
        "w": r.integers(1, 50, size=n).astype(np.int32)})
    small = Table.from_arrays("small", {
        "k": np.asarray(r.choice(1000, size=n_small, replace=False),
                        np.int32)})
    return Catalog.from_tables(big, small), big, small


def _overlay(eff_xla=0.5, overhead=5e-6):
    return {"backend": "test", "backends": {
        "xla": {"stream_eff": eff_xla, "call_overhead_s": overhead,
                "achieved_gbps": 1.0}}}


def _prices(model):
    """Everything calibration can touch, plus a representative priced
    decision (the morsel-size choice is the most calibration-sensitive
    output of the model)."""
    return (dict(model.stream_eff), dict(model.call_overhead),
            model.h2d_gbps,
            model.choose_morsel_rows(1 << 20, 3, impl="xla"))


# --------------------------------------------------------------------------- #
# satellite 1: idempotent calibration application

def test_calibration_apply_is_idempotent():
    m = CostModel(4)
    ov = _overlay()
    m.apply_calibration(ov)
    once = _prices(m)
    m.apply_calibration(ov)
    assert _prices(m) == once
    assert m.n_calibrations == 2
    assert m.stream_eff["xla"] == 0.5


def test_partial_overlay_rebaselines_to_pristine_defaults():
    """An overlay covering only ``pallas`` must NOT leave a previous
    overlay's xla numbers behind — application always re-baselines
    against the uncalibrated constants."""
    m = CostModel(4)
    m.apply_calibration(_overlay(eff_xla=0.3))
    assert m.stream_eff["xla"] == 0.3
    m.apply_calibration({"backend": "test", "backends": {
        "pallas": {"stream_eff": 0.6, "call_overhead_s": 1e-5}}})
    assert m.stream_eff["xla"] == XLA_STREAM_EFF
    assert m.call_overhead["xla"] == XLA_CALL_OVERHEAD
    assert m.stream_eff["pallas"] == 0.6
    assert m.stream_eff["pallas"] != PALLAS_STREAM_EFF


def test_overlay_regenerate_is_stable():
    """generate → apply → regenerate from the SAME ledger rows yields
    the same overlay (the compounding bug: deriving eff from the model's
    current, already-overlaid efficiency divided it by the drift ratio
    on every cycle)."""
    m = CostModel(4)
    led = tm.BandwidthLedger(enabled=True)
    bw = m.bandwidth_gbps("partitioned") * 1e9
    nbytes = 1 << 22
    for _ in range(6):
        led.record(op="filter", impl="xla", placement="partitioned",
                   predicted_bytes=nbytes,
                   predicted_s=nbytes / (bw * m.stream_eff["xla"]),
                   measured_bytes=nbytes,
                   measured_s=nbytes / (bw * 0.2), mode="stream")
    ov1 = led.calibration_overlay(m)
    assert ov1["backends"]["xla"]["stream_eff"] == pytest.approx(0.2,
                                                                 abs=1e-3)
    m.apply_calibration(ov1)
    ov2 = led.calibration_overlay(m)
    assert ov2["backends"]["xla"]["stream_eff"] == \
        ov1["backends"]["xla"]["stream_eff"]
    before = _prices(m)
    m.apply_calibration(ov2)
    assert _prices(m) == before


def test_drift_returns_toward_one_after_recalibration():
    """Synthetic rows with the model 4x optimistic: after folding the
    overlay back in, re-predicting the same measurements drifts ~1.0."""
    m = CostModel(4)
    led = tm.BandwidthLedger(enabled=True)
    bw = m.bandwidth_gbps("partitioned") * 1e9
    nbytes = 1 << 22
    true_eff = m.stream_eff["xla"] / 4.0
    meas_s = nbytes / (bw * true_eff)
    for _ in range(4):
        led.record(op="filter", impl="xla", placement="partitioned",
                   predicted_bytes=nbytes,
                   predicted_s=nbytes / (bw * m.stream_eff["xla"]),
                   measured_bytes=nbytes, measured_s=meas_s,
                   mode="stream")
    agg, _ = led.window_drift(0)
    drift_before = agg["xla"]["drift_time"]
    assert drift_before == pytest.approx(4.0, rel=1e-3)
    m.apply_calibration(led.calibration_overlay(m))
    pred_after = nbytes / (bw * m.stream_eff["xla"])
    drift_after = meas_s / pred_after
    assert abs(drift_after - 1.0) < abs(drift_before - 1.0)
    assert drift_after == pytest.approx(1.0, rel=5e-3)


def test_window_drift_cursor_semantics():
    led = tm.BandwidthLedger(enabled=True)
    agg, nxt = led.window_drift(0, min_rows=2)
    assert agg is None and nxt == 0
    for i in range(3):
        led.record(op="filter", impl="xla", placement="partitioned",
                   predicted_bytes=10.0, predicted_s=1.0,
                   measured_bytes=10.0, measured_s=2.0)
    agg, nxt = led.window_drift(0, min_rows=2)
    assert nxt == 3 and agg["xla"]["n"] == 3
    assert agg["xla"]["drift_time"] == pytest.approx(2.0)
    # cursor: no new rows -> window not ready, cursor unmoved
    agg2, nxt2 = led.window_drift(nxt, min_rows=1)
    assert agg2 is None and nxt2 == nxt


# --------------------------------------------------------------------------- #
# satellite 2: cost-model epoch in the plan-cache key

def test_recost_bumps_epoch_and_replans(rng):
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat)
    q = Q.scan("big").filter("v", 10, 60).sum("w")
    _, phys0 = ex.plan(q.node)
    key0 = ex._cache_key(*ex.plan(q.node))
    assert ex.cost_epoch == 0
    # an overlay that craters the streaming efficiency makes compute
    # dominate -> the priced morsel size must move
    ex.recost(_overlay(eff_xla=1e-3, overhead=5e-3))
    assert ex.cost_epoch == 1
    key1 = ex._cache_key(*ex.plan(q.node))
    assert key0 != key1
    _, phys1 = ex.plan(q.node)
    assert phys1 is not phys0
    assert ex.stats_dict()["recost_count"] == 1


def test_recost_with_empty_overlay_still_invalidates(rng):
    """Even a no-op overlay must roll the epoch: the caller asked for a
    re-cost boundary, and compiled plans may not cross it."""
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat)
    q = Q.scan("big").filter("v", 10, 60).sum("w")
    k0 = ex._cache_key(*ex.plan(q.node))
    ex.recost({})
    assert ex._cache_key(*ex.plan(q.node)) != k0


def test_recost_results_unchanged(rng):
    """Re-costing changes prices and plans, never answers."""
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat)
    qs = [Q.scan("big").filter("v", 10, 60).sum("w"),
          Q.scan("big").join(Q.scan("small"), on="k")
           .filter("v", 30, 49).sum("w")]
    want = [ex.execute(q).value for q in qs]
    ex.recost(_overlay(eff_xla=0.01))
    got = [ex.execute(q).value for q in qs]
    assert got == want
    got_stream = [ex.execute(q, mode="stream", morsel_rows=700).value
                  for q in qs]
    assert got_stream == want


# --------------------------------------------------------------------------- #
# satellite 3: mid-stream re-plan pins in-flight pipelines

def test_mid_stream_recalibration_differential(rng):
    """Mutate the calibration mid-circle: in-flight members finish on
    their pinned pipeline, later admissions use the re-costed one, and
    every answer is bit-identical to a cache-disabled oracle."""
    cat, *_ = _make_catalog(rng)
    oracle = Executor(Catalog.from_tables(*cat.tables.values()),
                      semantic_cache=None)
    ex = Executor(cat)
    srv = QueryServer(ex, streaming=True, morsel_rows=512)
    pre = [Q.scan("big").filter("v", 10, 60).sum("w"),
           Q.scan("big").filter("v", 20, 39).mean("w")]
    post = [Q.scan("big").filter("v", 5, 80).sum("w"),
            Q.scan("big").filter("v", 0, 25).count("w")]
    qids = {}
    for q in pre:
        qids[srv.submit(q)] = q
    results = {}
    results.update(srv.pump())
    results.update(srv.pump())          # mid-circle
    groups_before = {id(g) for s in srv._streams.values()
                     for g in s.groups.values()}
    ex.recost(_overlay(eff_xla=0.02, overhead=1e-3))
    for q in post:
        qids[srv.submit(q)] = q
    while len(results) < len(qids):
        results.update(srv.pump())
    # post-recost admissions formed NEW groups (epoch is in the compile
    # key), the pre-recost group survived untouched
    groups_after = {id(g) for s in srv._streams.values()
                    for g in s.groups.values()}
    assert groups_before <= groups_after
    assert len(groups_after) > len(groups_before)
    for qid, q in qids.items():
        assert results[qid] == oracle.execute(q).value, q.node


def test_stream_respecs_when_idle_after_recost(rng):
    """A drained stream re-prices its morsel spec at the new epoch; a
    stream with members in flight keeps the spec its circles started
    under."""
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat)
    srv = QueryServer(ex, streaming=True)
    q = Q.scan("big").filter("v", 10, 60).sum("w")
    srv.submit(q)
    srv.drain()
    stream = srv._streams["big"]
    assert stream.epoch == 0
    ex.recost(_overlay(eff_xla=1e-3, overhead=5e-3))
    srv.submit(Q.scan("big").filter("v", 5, 50).sum("w"))
    srv.drain()
    assert srv._streams["big"].epoch == ex.cost_epoch
    assert srv._streams["big"] is not stream


# --------------------------------------------------------------------------- #
# tentpole: the drift trigger

def _breaching_rows(ledger, n, drift=3.0):
    for _ in range(n):
        ledger.record(op="filter", impl="xla", placement="partitioned",
                      predicted_bytes=1e6, predicted_s=1e-3,
                      measured_bytes=1e6, measured_s=1e-3 * drift,
                      mode="serve")


def test_drift_trigger_fires_after_k_windows(rng):
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat, telemetry=tm.Telemetry(enabled=True))
    srv = QueryServer(ex, streaming=True,
                      policy=AdaptivePolicy(drift_threshold=0.5,
                                            k_windows=2,
                                            min_window_rows=2))
    _breaching_rows(ex.tel.ledger, 4)
    srv._maybe_recalibrate()            # window 1: breach, streak=1
    assert srv.n_recalibrations == 0 and ex.cost_epoch == 0
    _breaching_rows(ex.tel.ledger, 4)
    srv._maybe_recalibrate()            # window 2: breach -> recalibrate
    assert srv.n_recalibrations == 1
    assert ex.cost_epoch == 1
    assert ex.cost_model.calibrated_from == "ledger"
    # the evidence window restarted: old-model rows never feed the next
    # overlay, and the streak reset
    assert srv._overlay_start == len(ex.tel.ledger.rows)
    assert srv._breach_streak == 0


def test_drift_trigger_streak_resets_on_clean_window(rng):
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat, telemetry=tm.Telemetry(enabled=True))
    srv = QueryServer(ex, streaming=True,
                      policy=AdaptivePolicy(drift_threshold=0.5,
                                            k_windows=2,
                                            min_window_rows=2))
    _breaching_rows(ex.tel.ledger, 4, drift=3.0)
    srv._maybe_recalibrate()
    _breaching_rows(ex.tel.ledger, 4, drift=1.0)   # clean window
    srv._maybe_recalibrate()
    _breaching_rows(ex.tel.ledger, 4, drift=3.0)
    srv._maybe_recalibrate()
    assert srv.n_recalibrations == 0 and ex.cost_epoch == 0


def test_serving_streams_feed_ledger(rng):
    """The streaming pump records fenced per-morsel rows (mode="serve")
    — without them the adaptive loop would be blind to the serving
    path."""
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat, telemetry=tm.Telemetry(enabled=True))
    srv = QueryServer(ex, streaming=True, morsel_rows=1024)
    srv.submit(Q.scan("big").filter("v", 10, 60).sum("w"))
    srv.drain()
    serve_rows = [r for r in ex.tel.ledger.rows if r.mode == "serve"]
    assert serve_rows
    # predictions are scaled to one morsel: a full circle's predicted
    # seconds sum to ~the whole-plan prediction, not n_morsels times it
    assert all(r.predicted_s < 1.0 for r in serve_rows)


# --------------------------------------------------------------------------- #
# tentpole: QoS admission, backpressure, tenant cache shares

def test_priority_ordering_under_saturation(rng):
    """High-priority admissions run first in every batch, so their
    sojourn p95 stays at or below the best-effort tenant's."""
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat)
    srv = QueryServer(ex)
    srv.register_tenant(TenantSpec("hi", priority=10, slo_p95_s=5.0))
    srv.register_tenant(TenantSpec("lo", priority=0))
    for i in range(8):
        srv.submit(Q.scan("big").filter("v", i, 60 + i).sum("w"),
                   tenant="lo")
        srv.submit(Q.scan("big").filter("v", i, 61 + i).sum("w"),
                   tenant="hi")
    srv.drain()
    hi = [r for r in srv.history if r.tenant == "hi"]
    lo = [r for r in srv.history if r.tenant == "lo"]
    assert max(r.t_complete for r in hi) <= max(r.t_complete for r in lo)
    st = srv.stats()["tenants"]
    assert st["hi"]["latency_p95_s"] <= st["lo"]["latency_p95_s"]


def test_deadline_breaks_priority_ties():
    recs = [  # same priority, scrambled deadlines
        type("R", (), {"priority": 1, "deadline": d, "t_submit": i})()
        for i, d in enumerate([3.0, 1.0, 2.0])]
    out = QueryServer._admission_order(recs)
    assert [r.deadline for r in out] == [1.0, 2.0, 3.0]


def test_backpressure_defers_best_effort_only(rng):
    """With an SLO breach in the recent window, below-top-priority
    admissions are deferred (counted, requeued) — but every query still
    completes with the right answer."""
    cat, *_ = _make_catalog(rng)
    oracle = Executor(Catalog.from_tables(*cat.tables.values()),
                      semantic_cache=None)
    ex = Executor(cat)
    srv = QueryServer(ex, streaming=True, morsel_rows=1024)
    srv.register_tenant(TenantSpec("hi", priority=10, slo_p95_s=1e-9))
    srv.register_tenant(TenantSpec("lo", priority=0))
    warm = Q.scan("big").filter("v", 40, 50).sum("w")
    srv.submit(warm, tenant="hi")
    srv.drain()                          # seeds the recent-sojourn window
    qids = {}
    for i in range(3):
        qids[srv.submit(Q.scan("big").filter("v", i, 70 + i).sum("w"),
                        tenant="lo")] = i
        qids[srv.submit(Q.scan("big").filter("v", i, 71 + i).sum("w"),
                        tenant="hi")] = i
    out = srv.drain()
    assert srv.n_backpressured > 0
    # nothing lost, nothing wrong
    for rec in srv.history:
        want = oracle.execute(rec.node).value
        assert rec.result == want
    assert set(qids) <= set(out)
    # only best-effort records were ever deferred
    assert all(r.n_deferred == 0 for r in srv.history
               if r.tenant == "hi")


@pytest.mark.requires_cache
def test_tenant_cache_shares_cap_resident_bytes():
    cache = SemanticCache(budget_bytes=10_000)
    cache.set_tenant_shares({"a": 1.0, "b": 3.0})
    assert cache.tenant_cap_bytes("a") == 2_500
    assert cache.tenant_cap_bytes("b") == 7_500
    assert cache.tenant_cap_bytes(None) is None
    # b fills its share; a cannot displace b's bytes past a's own cap
    for i in range(3):
        assert cache.put(("b", i), i, kind="result", n_bytes=2_000,
                         recompute_s=1.0, tenant="b")
    assert cache.put(("a", 0), 0, kind="result", n_bytes=2_000,
                     recompute_s=1.0, tenant="a")
    # over a's cap: a higher-scored same-tenant put self-evicts a's OWN
    # lower-scored entry — never b's
    assert cache.put(("a", 1), 1, kind="result", n_bytes=1_000,
                     recompute_s=100.0, tenant="a")
    assert ("a", 0) not in cache
    assert all(("b", i) in cache for i in range(3))
    st = cache.stats_dict()
    assert st["semantic_cache_tenant_bytes"]["a"] == 1_000
    assert st["semantic_cache_tenant_bytes"]["b"] == 6_000
    # a LOW-scored over-cap put cannot free its own share (its only
    # victim is priced higher) and must be rejected, not displace b
    assert not cache.put(("a", 2), 2, kind="result", n_bytes=2_500,
                         recompute_s=1e-6, tenant="a")
    st = cache.stats_dict()
    assert st["semantic_cache_tenant_bytes"]["a"] == 1_000
    assert st["semantic_cache_tenant_bytes"]["b"] == 6_000
    # a single entry larger than the tenant's whole cap is rejected
    assert not cache.put(("a", 3), 3, kind="result", n_bytes=3_000,
                         recompute_s=100.0, tenant="a")


@pytest.mark.requires_cache
def test_register_tenant_pushes_shares_to_shared_cache(rng):
    cat, *_ = _make_catalog(rng)
    cache = SemanticCache(budget_bytes=8_000)
    ex = Executor(cat, tenant="hi", semantic_cache=cache)
    srv = QueryServer(ex, semantic_cache=cache)
    srv.register_tenant(TenantSpec("hi", priority=1, cache_share=3.0))
    srv.register_tenant(TenantSpec("lo", priority=0, cache_share=1.0))
    # default tenant (share 1.0) is registered too: hi gets 3/5
    assert cache.tenant_cap_bytes("hi") == int(8_000 * 3 / 5)
    # executor-attributed puts carry the tenant
    srv.submit(Q.scan("big").filter("v", 10, 60).sum("w"), tenant="hi")
    srv.drain()
    tb = cache.stats_dict()["semantic_cache_tenant_bytes"]
    assert tb.get("hi", 0) > 0


# --------------------------------------------------------------------------- #
# soak: a mis-calibrated model served static and adaptive

# "datasheet" calibration: near-ideal streaming and near-free dispatch,
# so the model prices tiny morsels as free until the ledger corrects it
DATASHEET = {"backend": "datasheet", "backends": {
    "xla": {"stream_eff": 0.99, "call_overhead_s": 1e-9}}}


@pytest.mark.parametrize("adaptive", [False, True],
                         ids=["static", "adaptive"])
def test_two_tenant_soak_matches_oracle(adaptive):
    """Two tenants stream rounds of distinct filter+sum queries over a
    mis-calibrated model, the best-effort one under backpressure; every
    answer, before, during and after a drift-triggered re-plan, equals a
    cache-free oracle's, and the adaptive server's final overlay applied
    twice changes no price."""
    n = 1 << 12
    v = (np.arange(n) % 128).astype(np.int32)
    w = np.ones(n, np.int32)

    def catalog():
        return Catalog.from_tables(Table.from_arrays(
            "t", {"v": v.copy(), "w": w.copy()}))

    ex = Executor(catalog(), telemetry=tm.Telemetry(enabled=True))
    ex.cost_model.apply_calibration(DATASHEET)
    policy = AdaptivePolicy(drift_threshold=1.0, k_windows=2,
                            min_window_rows=4) if adaptive else None
    # 8 morsels a query: advances after a pipeline's first feed the ledger
    srv = QueryServer(ex, streaming=True, morsel_rows=512, policy=policy)
    srv.register_tenant(TenantSpec("prio", priority=10, slo_p95_s=1e-9,
                                   cache_share=2.0))
    srv.register_tenant(TenantSpec("bulk", priority=0, cache_share=1.0))
    queries = {}
    for i in range(18):
        q = Q.scan("t", ("v", "w")).filter("v", i % 96, i % 96 + 31) \
             .sum("w")
        queries[srv.submit(q, tenant="prio" if i % 2 == 0 else "bulk",
                           deadline_s=5.0)] = q
        if i % 6 == 5:
            srv.drain()
    oracle = Executor(catalog())
    results = {rec.qid: rec.result for rec in srv.history}
    assert set(results) == set(queries)
    for qid, q in queries.items():
        assert results[qid] == oracle.execute(q).value, q.node
    assert srv.n_backpressured > 0
    if adaptive:
        assert srv.n_recalibrations > 0
        overlay = ex.tel.ledger.calibration_overlay(ex.cost_model)
        ex.cost_model.apply_calibration(overlay)
        once = _prices(ex.cost_model)
        ex.cost_model.apply_calibration(overlay)
        assert _prices(ex.cost_model) == once
