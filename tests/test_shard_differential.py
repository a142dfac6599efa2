"""Sharded execution pinned bit-identical to the single-device oracle.

The ``placement="sharded"`` axis must never change an answer: every
aggregate (including f32 means, computed from exact integer partial
sums) and every materialized row order (join pair lists are canonicalized
to probe-row-major) is compared against the classic 1-device executor.
The degenerate mesh=1 executor must be byte-for-byte the old one — same
fingerprints, same compiled-plan cache keys, same EXPLAIN output."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.columnar.table import Column, Table
from repro.core import channels
from repro.core import join as join_core
from repro.distributed import sharding as shardlib
from repro.query import logical as L
from repro.query.cost import (
    ColumnStats, CostModel, SEL_CORRECTION_CLAMP, TableStats,
    clamp_correction, estimate_rows, plan_physical,
)
from repro.query.exec import Catalog, Executor
from repro.query.logical import Q

requires_mesh = pytest.mark.requires_mesh


def _need_devices(n: int) -> None:
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices, have {len(jax.devices())}")


def _tables(rng, n=4096, m=512, dom=200):
    li = Table("lineitem", {
        "qty": Column(jnp.asarray(rng.integers(0, 50, n), jnp.int32),
                      "qty"),
        "price": Column(jnp.asarray(rng.integers(1, 100, n), jnp.int32),
                        "price"),
        "pk": Column(jnp.asarray(rng.integers(0, dom, n), jnp.int32),
                     "pk"),
    })
    # duplicate-keyed build side: the multi-match pair-list join
    part = Table("part", {
        "pk": Column(jnp.asarray(rng.integers(0, dom, m), jnp.int32),
                     "pk"),
        "w": Column(jnp.asarray(rng.integers(1, 10, m), jnp.int32), "w"),
    })
    return li, part


QUERIES = (
    Q.scan("lineitem").filter("qty", 10, 39).sum("price"),
    Q.scan("lineitem").filter("qty", 0, 25).mean("price"),
    Q.scan("lineitem").join(Q.scan("part"), "pk")
     .filter("qty", 5, 44).sum("w"),
    Q.scan("lineitem").filter("qty", 10, 19).count("price"),
)


@requires_mesh
@pytest.mark.parametrize("shards", [2, 3, 8])
@pytest.mark.parametrize("mode", ["batch", "stream", "eager"])
def test_sharded_matches_single_device(rng, shards, mode):
    """Filter/join/sum over 1..8 shards — including shard counts that do
    NOT divide the row count (3 over 4096) — equal the 1-device oracle
    exactly, in every lowering mode."""
    _need_devices(shards)
    li, part = _tables(rng)
    ex1 = Executor(Catalog.from_tables(li, part))
    exn = Executor(Catalog.from_tables(li, part), shards=shards)
    for q in QUERIES:
        assert exn.execute(q, mode=mode).value \
            == ex1.execute(q, mode=mode).value


@requires_mesh
def test_sharded_non_dividing_rows(rng):
    """Row counts the shard count does not divide fall back to the
    unsharded pipeline/replicated placement — same answers."""
    _need_devices(2)
    n_sh = min(len(jax.devices()), 8)
    li, part = _tables(rng, n=4097)
    ex1 = Executor(Catalog.from_tables(li, part))
    exn = Executor(Catalog.from_tables(li, part), shards=n_sh)
    for q in QUERIES:
        for mode in ("batch", "stream", "eager"):
            assert exn.execute(q, mode=mode).value \
                == ex1.execute(q, mode=mode).value


@requires_mesh
@pytest.mark.parametrize("shards", [2, 8])
def test_sharded_filtered_build_side_join(rng, shards):
    """When the filtered table is the SMALLER join input it becomes the
    build side, and its selection runs under a replicated (non-
    partitioned) plan.  On a multi-device base mesh that plan has
    n_engines > 1, and ``select_distributed``'s non-partitioned branch
    is the Fig. 5 congested-crossbar BASELINE (every engine rescans the
    first shard with per-engine offsets) — a throughput analogue, only
    correct at n_engines == 1.  Regression: a 6500-row selection came
    back as 6496 (2 shards) / 6624 (8 shards), silently corrupting the
    join.  ``select_range`` must compute non-partitioned selections
    exactly instead."""
    _need_devices(shards)
    n, m, dom = 4096, 8192, 512
    t = Table("t", {
        "v": Column(jnp.asarray(rng.integers(0, 100, n), jnp.int32), "v"),
        "pk": Column(jnp.asarray(rng.integers(0, dom, n), jnp.int32),
                     "pk")})
    s = Table("s", {
        "pk": Column(jnp.asarray(rng.integers(0, dom, m), jnp.int32),
                     "pk"),
        "u": Column(jnp.asarray(rng.integers(1, 10, m), jnp.int32), "u")})
    ex1 = Executor(Catalog.from_tables(t, s))
    exn = Executor(Catalog.from_tables(t, s), shards=shards)
    q = Q.scan("t").join(Q.scan("s"), "pk").filter("v", 10, 89).sum("u")
    # the filter alone must already be exact on the sharded executor
    q_cnt = Q.scan("t").filter("v", 10, 89).count("pk")
    v = np.asarray(t.column("v"))
    n_keep = int(((v >= 10) & (v <= 89)).sum())
    assert exn.execute(q_cnt, mode="eager").value == n_keep
    for mode in ("eager", "batch", "stream"):
        assert exn.execute(q, mode=mode).value \
            == ex1.execute(q, mode=mode).value


@requires_mesh
def test_sharded_project_row_order_bit_identical(rng):
    """Materializing paths: the shuffle join's pair list is canonicalized
    to probe-row-major order, so projected ROW ORDER matches the oracle
    bit for bit, duplicates included."""
    _need_devices(2)
    n_sh = min(len(jax.devices()), 8)
    li, part = _tables(rng)
    ex1 = Executor(Catalog.from_tables(li, part))
    exn = Executor(Catalog.from_tables(li, part), shards=n_sh)
    q = Q.scan("lineitem").join(Q.scan("part"), "pk") \
         .filter("qty", 5, 44).project("price", "w")
    t1 = ex1.execute(q, mode="eager").value
    tn = exn.execute(q, mode="eager").value
    for c in ("price", "w"):
        np.testing.assert_array_equal(np.asarray(t1.column(c)),
                                      np.asarray(tn.column(c)))


@requires_mesh
def test_sharded_results_equal_naive_oracle(rng):
    _need_devices(2)
    n_sh = min(len(jax.devices()), 8)
    li, part = _tables(rng)
    exn = Executor(Catalog.from_tables(li, part), shards=n_sh)
    for q in QUERIES:
        assert exn.execute(q).value \
            == exn.execute(q, optimized=False).value


def test_mesh1_degenerate_is_byte_identical(rng):
    """shards=1 (and shards=None) must produce byte-for-byte the plans,
    fingerprints, cache keys and EXPLAIN output of the pre-sharding
    executor — the layout only ever joins a key when n_shards > 1."""
    li, part = _tables(rng)
    exa = Executor(Catalog.from_tables(li, part))
    exb = Executor(Catalog.from_tables(li, part), shards=1)
    assert exa.shard_layout is None and exb.shard_layout is None
    for q in QUERIES:
        assert exa.fingerprint_of(q.node) == exb.fingerprint_of(q.node)
        na, pa = exa.plan(q.node)
        nb, pb = exb.plan(q.node)
        assert exa._cache_key(na, pa) == exb._cache_key(nb, pb)
        assert exa.explain(q) == exb.explain(q)
    # and fingerprint(layout=None) is the unsharded hash exactly
    node = QUERIES[0].node
    assert L.fingerprint(node) == L.fingerprint(node, layout=None)
    assert L.fingerprint(node) != L.fingerprint(node,
                                                layout=("shard_layout", 8))


@requires_mesh
def test_shard_layout_splits_fingerprint_and_cache_key(rng):
    """A 1-device and an n-device plan must never alias: fingerprints
    and compiled-plan cache keys differ as soon as a layout is active."""
    _need_devices(2)
    li, part = _tables(rng)
    exa = Executor(Catalog.from_tables(li, part))
    exn = Executor(Catalog.from_tables(li, part), shards=2)
    q = QUERIES[0]
    assert exa.fingerprint_of(q.node) != exn.fingerprint_of(q.node)
    na, pa = exa.plan(q.node)
    nn, pn = exn.plan(q.node)
    assert exa._cache_key(na, pa) != exn._cache_key(nn, pn)


def _join_stats(probe: int, build: int):
    return {
        "l": TableStats(probe, ("pk", "v"),
                        {"pk": ColumnStats(0, build - 1,
                                           min(build, probe)),
                         "v": ColumnStats(0, 99, 100)}),
        "s": TableStats(build, ("pk", "w"),
                        {"pk": ColumnStats(0, build - 1,
                                           max(build // 2, 1)),
                         "w": ColumnStats(0, 9, 10)}),
    }


def test_shuffle_broadcast_crossover_follows_cost_model():
    """The planner picks shuffle-repartition over broadcast EXACTLY where
    the channel-priced alternatives cross: broadcast for builds within
    one HT_CAPACITY pass, shuffle once per-shard builds collapse rescan
    passes.  Pure cost-model arithmetic — no devices needed."""
    model = CostModel(1, n_shards=8)
    q = L.Aggregate(L.Join(L.Scan("l", ("pk", "v")),
                           L.Scan("s", ("pk", "w")), "pk"), "sum", "v")
    seen = set()
    for build in (256, 1024, 4096, 8192, 16384, 65536, 262144):
        phys = plan_physical(q, _join_stats(1 << 16, build), model)
        j = phys.children[0]
        assert j.shard_strategy is not None
        alt_b = j.alternatives["shard/broadcast"]
        alt_s = j.alternatives["shard/shuffle"]
        expect = "shuffle" if alt_s < alt_b else "broadcast"
        assert j.shard_strategy == expect, (build, alt_b, alt_s)
        seen.add(j.shard_strategy)
    # the sweep must actually cross — both strategies win somewhere
    assert seen == {"broadcast", "shuffle"}


def test_shuffle_join_is_one_compiled_program(rng, caplog):
    """Run eagerly, a ``shard_map`` dispatches every op of its body as a
    program of its own, compiled anew on each call: hundreds of compiles
    per shuffle join.  The shuffle join is one jitted program, so a
    repeat call with the same shapes compiles nothing, and its pairs are
    the exact join."""
    s = rng.integers(0, 1024, 300).astype(np.int32)
    l = rng.integers(0, 1024, 1000).astype(np.int32)
    args = (jnp.asarray(s), jnp.asarray(l), shardlib.ShardLayout(1))
    join_core.join_shuffle_multi(*args)
    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        caplog.clear()
        l_idx, s_idx, totals, pair_over, (_, _, shuf_over) = \
            join_core.join_shuffle_multi(*args)
    assert not [r for r in caplog.records if "Compiling" in r.getMessage()]
    assert not bool(shuf_over) and not bool(jnp.any(pair_over))
    valid = np.asarray(l_idx) >= 0
    got = sorted(zip(np.asarray(l_idx)[valid], np.asarray(s_idx)[valid]))
    li, si = np.nonzero(l[:, None] == s[None, :])
    assert got == sorted(zip(li, si))
    assert int(jnp.sum(totals)) == li.size


def test_mesh1_never_prices_shard_strategies():
    model = CostModel(4)            # n_shards defaults to 1
    q = L.Aggregate(L.Join(L.Scan("l", ("pk", "v")),
                           L.Scan("s", ("pk", "w")), "pk"), "sum", "v")
    phys = plan_physical(q, _join_stats(1 << 16, 4096), model)
    j = phys.children[0]
    assert j.shard_strategy is None
    assert "shard/broadcast" not in j.alternatives
    assert "shard/shuffle" not in j.alternatives


# --------------------------------------------------------------------------- #
# satellite: drift_bytes -> selectivity correction feedback


def test_selectivity_correction_scales_and_clamps():
    stats = {"t": TableStats(10000, ("a",),
                             {"a": ColumnStats(0, 99, 100)})}
    f = L.Filter(L.Scan("t", ("a",)), "a", 0, 9)      # sel = 0.1
    base = estimate_rows(f, stats)
    doubled = estimate_rows(f, stats, {("t", "a"): 2.0})
    assert doubled == pytest.approx(2 * base)
    # out-of-range factors clamp instead of swinging estimates wildly
    lo, hi = SEL_CORRECTION_CLAMP
    assert clamp_correction(100.0) == hi
    assert clamp_correction(0.001) == lo
    wild = estimate_rows(f, stats, {("t", "a"): 100.0})
    assert wild == pytest.approx(hi * base)
    # corrections never push selectivity past 1.0
    wide = L.Filter(L.Scan("t", ("a",)), "a", 0, 98)
    capped = estimate_rows(wide, stats, {("t", "a"): 4.0})
    assert capped == pytest.approx(10000.0)


def test_recost_folds_ledger_corrections_into_model(rng):
    """The PR-7 leftover, closed: measured-over-predicted byte ratios
    from the ledger's filter rows land in ``CostModel.sel_corrections``
    on the next ``recost()`` and shift the planner's estimates."""
    from repro.query import telemetry as tm
    li, part = _tables(rng)
    ex = Executor(Catalog.from_tables(li, part),
                  telemetry=tm.Telemetry(enabled=True))
    ex.tel.ledger.record(
        op="filter", impl="xla", placement="partitioned",
        predicted_bytes=1000.0, predicted_s=1e-6,
        measured_bytes=2000.0, measured_s=1e-6, mode="eager",
        table="lineitem", column="qty")
    ex.recost({})
    assert ex.cost_model.sel_corrections[("lineitem", "qty")] \
        == pytest.approx(2.0)
    # the correction flows into the next physical plan's estimates
    q = Q.scan("lineitem").filter("qty", 10, 19).sum("price")
    _, phys = ex.plan(q.node)
    flt = phys.children[0]
    plain = estimate_rows(flt.logical, ex.catalog.stats)
    assert flt.est_rows_out == pytest.approx(2 * plain)


# --------------------------------------------------------------------------- #
# satellite: MultiJoinResult contract for the distributed pair list


def test_join_distributed_multi_result_overflow_contract(host_mesh, rng):
    """``join_distributed_multi_result`` reconciles the per-shard padded
    slices with ``kernels/join/ops.MultiJoinResult``: the total is exact
    even when shards overflow (overflowed=True), and a retry at that
    exact capacity yields the full contiguous pair list."""
    plan = channels.plan(host_mesh, "model", "partitioned")
    n_l = 512 * plan.n_engines
    s = jnp.asarray(rng.integers(0, 40, 100), jnp.int32)
    l = jnp.asarray(rng.integers(0, 40, n_l), jnp.int32)

    res = join_core.join_distributed_multi_result(
        s, l, plan, max_out_per_shard=4)
    sh, lh = np.asarray(s), np.asarray(l)
    expect = sorted((li_, si_) for li_, lk in enumerate(lh)
                    for si_, sk in enumerate(sh) if lk == sk)
    assert int(res.total) == len(expect)        # exact despite overflow
    assert bool(res.overflowed)

    # per-shard totals skew with the key distribution: the whole total
    # is always a sufficient per-shard capacity
    res2 = join_core.join_distributed_multi_result(
        s, l, plan, max_out_per_shard=len(expect) + 8)
    assert not bool(res2.overflowed)
    assert int(res2.total) == len(expect)
    n = int(res2.total)
    l_idx, s_idx = np.asarray(res2.l_idx), np.asarray(res2.s_idx)
    # contiguous prefix + -1 tail: the MultiJoinResult layout contract
    assert (l_idx[:n] >= 0).all() and (l_idx[n:] == -1).all()
    assert (s_idx[:n] >= 0).all() and (s_idx[n:] == -1).all()
    assert sorted(zip(l_idx[:n].tolist(), s_idx[:n].tolist())) == expect
