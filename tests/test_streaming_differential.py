"""Streaming-vs-wholecolumn differential suite.

Every query shape from ``test_query_exec.py`` runs through the batch
(whole-column fused/eager) path AND the morsel-streaming path, across a
morsel-size sweep that includes sizes not dividing the table length, and
must produce bit-identical results (integer aggregates are exact; the
mean carry accumulates exactly representable f32 partial sums).  Also
pins the streaming-only capabilities: datasets larger than one
placement's capacity, the fused duplicate-build pair-list aggregate, the
cost-based build-side choice, streamed GLM training, and the streaming
serve drain.
"""
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.columnar import engine
from repro.columnar.table import MorselSpec, Table
from repro.query import (
    Catalog, Executor, PlacementCapacityError, Q, QueryServer, analyze,
)
from repro.query.optimize import choose_build_side, optimize
from repro.query import telemetry as tm

# n = 4096; 1000 does not divide it, 4096 is one morsel, 9999 over-covers
MORSEL_SWEEP = (256, 1000, 4096, 9999)


def _make_catalog(r, n=4096, n_small=512, vmax=100):
    big = Table.from_arrays("big", {
        "k": r.integers(0, 1000, size=n).astype(np.int32),
        "v": r.integers(0, vmax, size=n).astype(np.int32),
        "w": r.integers(1, 50, size=n).astype(np.int32)})
    small = Table.from_arrays("small", {
        "k": np.asarray(r.choice(1000, size=n_small, replace=False),
                        np.int32)})
    dup = Table.from_arrays("dup", {
        "k": r.integers(0, 50, size=256).astype(np.int32)})
    return Catalog.from_tables(big, small, dup), big, small, dup


def _queries():
    return [
        Q.scan("big").filter("v", 10, 60).sum("w"),
        Q.scan("big").filter("v", 20, 39).count("w"),
        Q.scan("big").filter("v", 20, 39).mean("w"),
        Q.scan("big").join(Q.scan("small"), on="k")
         .filter("v", 30, 49).sum("w"),
        Q.scan("big").join(Q.scan("small"), on="k")
         .filter("v", 0, 99).count("k"),
        Q.scan("big").join(Q.scan("dup"), on="k")
         .filter("v", 10, 60).sum("w"),
        Q.scan("big").join(Q.scan("dup"), on="k").count("k"),
    ]


def test_streamed_equals_batch_across_morsel_sizes(rng):
    """Bit-identical batch/streamed results for every query shape, at
    every morsel size, including n not divisible by the morsel."""
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat)
    for q in _queries():
        want = ex.execute(q).value
        for mr in MORSEL_SWEEP:
            got = ex.execute(q, mode="stream", morsel_rows=mr).value
            assert got == want, (q.node, mr, got, want)


@settings(max_examples=6, deadline=None)
@given(lo=st.integers(0, 80), width=st.integers(0, 60),
       morsel=st.integers(100, 5000), seed=st.integers(0, 2 ** 16))
def test_streamed_join_matches_numpy(lo, width, morsel, seed):
    """Property: streamed join+filter aggregates equal a NumPy oracle at
    arbitrary morsel granularity."""
    r = np.random.default_rng(seed)
    cat, big, small, _ = _make_catalog(r)
    ex = Executor(cat)
    q = (Q.scan("big").join(Q.scan("small"), on="k")
          .filter("v", lo, lo + width).sum("w"))
    got = ex.execute(q, mode="stream", morsel_rows=morsel).value
    k = np.asarray(big.column("k"))
    v = np.asarray(big.column("v"))
    w = np.asarray(big.column("w"))
    m = (v >= lo) & (v <= lo + width) & np.isin(
        k, np.asarray(small.column("k")))
    assert int(got) == int(w[m].sum())


def test_duplicate_build_side_stays_fused(rng):
    """Satellite: the FUSED path no longer lowers duplicate-build joins
    eagerly — the pair-list aggregate compiles (plan-cache entry, one
    trace) and matches the eager pair-list lowering exactly."""
    cat, big, _, dup = _make_catalog(rng)
    ex = Executor(cat)
    q = (Q.scan("big").join(Q.scan("dup"), on="k")
          .filter("v", 10, 60).sum("w"))
    got = ex.execute(q).value
    assert ex.cache_misses == 1 and ex.trace_count == 1   # fused, not eager
    again = ex.execute(q)
    assert again.cache_hit and ex.trace_count == 1
    naive = ex.execute(q, optimized=False).value
    k = np.asarray(big.column("k"))
    v = np.asarray(big.column("v"))
    w = np.asarray(big.column("w"))
    cnt = np.asarray([(np.asarray(dup.column("k")) == key).sum()
                      for key in k])
    m = (v >= 10) & (v <= 60)
    assert int(got) == int(naive) == int((w * cnt * m).sum())


def test_duplicate_build_mean_and_build_column_aggregate(rng):
    """Bucket prefix sums serve aggregates over a duplicate build side's
    own columns (one value per matched pair)."""
    r = rng
    big = Table.from_arrays("big", {
        "k": r.integers(0, 40, size=1024).astype(np.int32)})
    dup = Table.from_arrays("dup", {
        "k": r.integers(0, 40, size=256).astype(np.int32),
        "x": r.integers(1, 9, size=256).astype(np.int32)})
    cat = Catalog.from_tables(big, dup)
    ex = Executor(cat)
    k = np.asarray(big.column("k"))
    dk = np.asarray(dup.column("k"))
    dx = np.asarray(dup.column("x"))
    pair_x = np.concatenate([dx[dk == key] for key in k]) \
        if len(k) else np.zeros(0, np.int32)
    q = Q.scan("big").join(Q.scan("dup"), on="k").sum("x")
    want = int(pair_x.sum())
    assert int(ex.execute(q).value) == want
    assert int(ex.execute(q, optimized=False).value) == want
    for mr in (100, 1024):
        assert int(ex.execute(q, mode="stream",
                              morsel_rows=mr).value) == want
    qm = Q.scan("big").join(Q.scan("dup"), on="k").mean("x")
    assert ex.execute(qm).value == pytest.approx(float(pair_x.mean()),
                                                 rel=1e-6)


def test_larger_than_placement_completes_only_streamed(rng):
    """Acceptance: with a placement capacity below the probe column size
    the naive/forced-eager paths refuse; the optimized batch path spills
    through the tier hierarchy, and morsel streaming completes — both
    agreeing with the unconstrained result."""
    cat, big, small, _ = _make_catalog(rng)
    q = (Q.scan("big").join(Q.scan("small"), on="k")
          .filter("v", 10, 60).sum("w"))
    want = Executor(cat).execute(q).value
    cap = big.column("k").nbytes // 4
    ex = Executor(cat, placement_capacity_bytes=cap)
    # the optimized batch path no longer refuses: it reroutes through a
    # cost-priced spill plan (host tier here) and streams, bit-identical
    spilled = ex.execute(q)
    assert int(spilled.value) == int(want)
    assert spilled.mode == "stream"
    assert any(cat.tables["big"].column_tier(c) != "device"
               for c in ("k", "v", "w"))
    with pytest.raises(PlacementCapacityError):
        ex.execute(q, optimized=False)
    got = ex.execute(q, mode="stream", morsel_rows=cap // (4 * 3)).value
    assert int(got) == int(want)
    # a single morsel bigger than the capacity must refuse too
    with pytest.raises(PlacementCapacityError):
        ex.execute(q, mode="stream", morsel_rows=big.num_rows)


def test_choose_build_side_keeps_unique_fusable_side(rng):
    """Satellite: with the cost model, a provably-unique build side is
    not swapped away for a marginally smaller duplicate-keyed side (the
    cardinality rule would swap)."""
    dup = Table.from_arrays("dup", {
        "k": rng.integers(0, 50, size=900).astype(np.int32)})
    uni = Table.from_arrays("uni", {
        "k": np.arange(0, 1024, dtype=np.int32)})
    cat = Catalog.from_tables(dup, uni)
    q = Q.scan("dup").join(Q.scan("uni"), on="k").count("k")
    from repro.query import CostModel
    by_card = choose_build_side(q.node, cat.stats)
    assert by_card.child.right.table == "dup"        # cardinality swaps
    by_cost = choose_build_side(q.node, cat.stats, CostModel(4))
    assert by_cost.child.right.table == "uni"        # cost keeps unique


def test_choose_build_side_still_swaps_when_multipass_looms(rng):
    """The cost path still prefers a duplicate-keyed build when the
    unique side would need many HT_CAPACITY passes."""
    from repro.core.join import HT_CAPACITY
    from repro.query import CostModel
    n_uni = 8 * HT_CAPACITY
    uni = Table.from_arrays("uni", {
        "k": np.arange(n_uni, dtype=np.int32)})
    dup = Table.from_arrays("dup", {
        "k": rng.integers(0, 512, size=1024).astype(np.int32)})
    cat = Catalog.from_tables(uni, dup)
    q = Q.scan("uni").join(Q.scan("dup"), on="k").count("k")
    out = choose_build_side(q.node, cat.stats, CostModel(4))
    assert out.child.right.table == "dup"            # dup still builds


def test_morsel_spec_alignment_and_views(rng):
    """Morsel views cover the table exactly once, pad the ragged tail,
    and align to the channel plan's engine count."""
    cat, big, *_ = _make_catalog(rng)
    ex = Executor(cat)
    spec = MorselSpec.for_plan(big.num_rows, 1000,
                               ex.plans["partitioned"])
    n_eng = ex.plans["partitioned"].n_engines
    assert spec.rows % n_eng == 0
    seen = 0
    for cols, n_valid in big.morsels(spec, ["v"]):
        assert cols["v"].shape[0] == spec.rows
        seen += n_valid
    assert seen == big.num_rows
    # streamed total equals whole-column sum (pad rows masked out)
    total = sum(
        float(np.asarray(cols["v"])[:n_valid].sum())
        for cols, n_valid in big.morsels(spec, ["v"]))
    assert total == float(np.asarray(big.column("v")).sum())


def test_engine_streaming_operators_direct(rng):
    """The engine-level streaming operator surface (join_build /
    join_probe_morsel / bucket_sums / select_range_morsel /
    aggregate_sum_stream) composes by hand into the same answer as the
    whole-column engine sequence."""
    import jax.numpy as jnp
    cat, big, _, dup = _make_catalog(rng)
    ex = Executor(cat)
    build = engine.join_build(dup, "k", unique=False,
                              plan=ex.plans["replicated"])
    spec = MorselSpec.for_plan(big.num_rows, 700, ex.plans["partitioned"])
    carry = jnp.zeros((), jnp.int32)
    for cols, n_valid in big.morsels(spec, ["k", "v", "w"]):
        mask = jnp.arange(spec.rows) < n_valid
        mask = engine.select_range_morsel(cols["v"], 10, 60, mask)
        start, cnt = engine.join_probe_morsel(build, cols["k"])
        carry = engine.aggregate_sum_stream(carry, cols["w"],
                                            mask & (cnt > 0), cnt)
    k = np.asarray(big.column("k"))
    v = np.asarray(big.column("v"))
    w = np.asarray(big.column("w"))
    match = np.asarray([(np.asarray(dup.column("k")) == key).sum()
                        for key in k])
    m = (v >= 10) & (v <= 60)
    assert int(carry) == int((w * match * m).sum())
    # bucket prefix sums: per-probe sums over the build side's buckets
    build2 = engine.join_build(dup, "k", ("k",), unique=False)
    start, cnt = engine.join_probe_morsel(build2, big.column("k"))
    bsums = engine.bucket_sums(build2.csums["k"], start, cnt)
    assert int(jnp.sum(bsums)) == int((k * match).sum())


def test_train_glm_stream_matches_whole_column(rng):
    """Streamed epochs (params carried through epoch x morsel order)
    reproduce the whole-column SGD sequence."""
    from repro.core.sgd_glm import HyperParams
    m, d = 512, 3
    big = Table.from_arrays("glm", {
        "f0": rng.normal(size=m).astype(np.float32),
        "f1": rng.normal(size=m).astype(np.float32),
        "f2": rng.normal(size=m).astype(np.float32),
        "y": rng.integers(0, 2, size=m).astype(np.float32)})
    cat = Catalog.from_tables(big)
    ex = Executor(cat)
    grid = [HyperParams(0.1, 0.0), HyperParams(0.05, 0.01)]
    xs_full, losses_full = engine.train_glm(
        big, ["f0", "f1", "f2"], "y", grid, ex.plans["partitioned"],
        epochs=3)
    xs_stream, losses_stream = engine.train_glm_stream(
        big, ["f0", "f1", "f2"], "y", grid, ex.plans["partitioned"],
        epochs=3, morsel_rows=128, telemetry=tm.Telemetry(False))
    np.testing.assert_allclose(np.asarray(xs_stream),
                               np.asarray(xs_full), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(losses_stream),
                               np.asarray(losses_full), rtol=1e-4)


def test_streaming_server_matches_batch_server(rng):
    """The incremental pipeline drain returns exactly what the admission-
    batch server returns, including mid-flight joiners and dedup."""
    cat, big, small, _ = _make_catalog(rng)
    v = np.asarray(big.column("v"))
    w = np.asarray(big.column("w"))
    k = np.asarray(big.column("k"))
    isin = np.isin(k, np.asarray(small.column("k")))
    srv = QueryServer(Executor(cat), streaming=True, morsel_rows=512)
    bounds = [(0, 9), (10, 40), (20, 60), (0, 99)]
    qids = [srv.submit(Q.scan("big").join(Q.scan("small"), on="k")
                        .filter("v", lo, hi).sum("w"))
            for lo, hi in bounds]
    for _ in range(2):
        srv.pump()                      # stream in flight...
    late = srv.submit(Q.scan("big").join(Q.scan("small"), on="k")
                       .filter("v", 5, 15).sum("w"))     # ...joins mid-flight
    dup = srv.submit(Q.scan("big").join(Q.scan("small"), on="k")
                      .filter("v", 0, 9).sum("w"))       # dedup vs in-flight
    res = srv.drain()
    for qid, (lo, hi) in zip(qids + [late], bounds + [(5, 15)]):
        m = (v >= lo) & (v <= hi) & isin
        assert int(res[qid]) == int(w[m].sum())
    assert res[dup] == res[qids[0]]
    s = srv.stats()
    assert s["n_deduped"] == 1
    assert s["n_streamed"] == 5
    assert len(res) == 6


def test_mid_flight_group_join_keeps_lone_member_carry(rng):
    """Regression: a query streaming ALONE in its group must not lose its
    accumulated carry when a second compatible query attaches mid-flight
    (writeback previously dropped the single-member stacked carry)."""
    cat, big, small, _ = _make_catalog(rng)
    v = np.asarray(big.column("v"))
    w = np.asarray(big.column("w"))
    k = np.asarray(big.column("k"))
    isin = np.isin(k, np.asarray(small.column("k")))
    srv = QueryServer(Executor(cat), streaming=True, morsel_rows=512)
    q1 = srv.submit(Q.scan("big").join(Q.scan("small"), on="k")
                     .filter("v", 10, 60).sum("w"))
    for _ in range(3):
        srv.pump()                       # q1 accumulates alone
    q2 = srv.submit(Q.scan("big").join(Q.scan("small"), on="k")
                     .filter("v", 20, 80).sum("w"))   # same group, joins
    res = srv.drain()
    for qid, (lo, hi) in ((q1, (10, 60)), (q2, (20, 80))):
        m = (v >= lo) & (v <= hi) & isin
        assert int(res[qid]) == int(w[m].sum()), (lo, hi)


def test_analyze_rejects_filter_on_multimatch_column(rng):
    """A filter above a duplicate-keyed join that reads a build column
    needs the materialized pair list: not streamable, falls back."""
    big = Table.from_arrays("big", {
        "k": rng.integers(0, 40, size=1024).astype(np.int32)})
    dup = Table.from_arrays("dup", {
        "k": rng.integers(0, 40, size=256).astype(np.int32),
        "x": rng.integers(1, 9, size=256).astype(np.int32)})
    cat = Catalog.from_tables(big, dup)
    node = (Q.scan("big").join(Q.scan("dup"), on="k")
             .filter("x", 2, 5).sum("x")).node
    assert analyze(optimize(node, cat.stats), cat.stats) is None
    # and the executor still answers it correctly (eager pair list)
    ex = Executor(cat)
    got = ex.execute(node, optimized=False).value
    k = np.asarray(big.column("k"))
    dk = np.asarray(dup.column("k"))
    dx = np.asarray(dup.column("x"))
    pair_x = np.concatenate([dx[dk == key] for key in k])
    m = (pair_x >= 2) & (pair_x <= 5)
    assert int(got) == int(pair_x[m].sum())
