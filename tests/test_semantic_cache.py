"""Semantic result/subplan cache: fingerprints, invalidation, eviction.

Pins the three correctness contracts of the caching subsystem:
fingerprint discrimination (semantically different plans never collide,
semantically equal spellings do), invalidation (a table mutation bumps
the version, making every dependent entry unreachable — post-mutation
results are bit-identical to cache-disabled execution), and budgeted
eviction (the byte budget holds, and the cost model keeps what is
expensive to rebuild rather than what is big).
"""
import numpy as np
import pytest

from repro.columnar.table import Table
from repro.query import (
    Catalog, CostModel, Executor, Q, QueryServer, SemanticCache,
    common_subplans, fingerprint, optimize,
)


def _make_catalog(r, n=4096, n_small=512, vmax=100):
    big = Table.from_arrays("big", {
        "k": r.integers(0, 1000, size=n).astype(np.int32),
        "v": r.integers(0, vmax, size=n).astype(np.int32),
        "w": r.integers(1, 50, size=n).astype(np.int32)})
    small = Table.from_arrays("small", {
        "k": np.asarray(r.choice(1000, size=n_small, replace=False),
                        np.int32),
        "x": r.integers(0, 9, size=n_small).astype(np.int32)})
    return Catalog.from_tables(big, small), big, small


def _join_sum(lo=30, hi=49):
    return (Q.scan("big").join(Q.scan("small"), on="k")
             .filter("v", lo, hi).sum("w"))


# --------------------------------------------------------------------------- #
# fingerprints

def test_equal_spellings_collide():
    """Filter-chain permutations and agg-rooted join swaps are the same
    query; their fingerprints must match.  Join sides commute only when
    both sides' column sets are explicit and non-key-disjoint (the
    optimizer's pruning always makes them explicit)."""
    a = Q.scan("big").filter("v", 0, 10).filter("w", 1, 5).sum("k").node
    b = Q.scan("big").filter("w", 1, 5).filter("v", 0, 10).sum("k").node
    assert fingerprint(a) == fingerprint(b)
    ja = (Q.scan("big", ["k", "v"]).join(Q.scan("small", ["k"]), on="k")
           .sum("v").node)
    jb = (Q.scan("small", ["k"]).join(Q.scan("big", ["k", "v"]), on="k")
           .sum("v").node)
    assert fingerprint(ja) == fingerprint(jb)


def test_join_swap_with_overlapping_columns_never_collides(rng):
    """Regression: the join merge is left-wins, so when BOTH sides carry
    a same-named non-key column the sides do NOT commute — sum(x) reads
    the left side's x and the two orientations have different answers."""
    a = Table.from_arrays("a", {
        "k": np.arange(8, dtype=np.int32),
        "x": np.full(8, 1, np.int32)})
    b = Table.from_arrays("b", {
        "k": np.arange(8, dtype=np.int32),
        "x": np.full(8, 100, np.int32)})
    cat = Catalog.from_tables(a, b)
    q1 = Q.scan("a").join(Q.scan("b"), on="k").sum("x")
    q2 = Q.scan("b").join(Q.scan("a"), on="k").sum("x")
    ex = Executor(cat, cache_bytes=32 << 20)
    v1 = ex.execute(q1).value
    r2 = ex.execute(q2)
    plain = Executor(cat)
    assert v1 == plain.execute(q1).value
    assert r2.value == plain.execute(q2).value
    assert v1 != r2.value                       # orientations really differ
    assert not r2.result_cache_hit              # and never share an entry
    assert ex.fingerprint_of(q1.node) != ex.fingerprint_of(q2.node)
    # implicit (columns=None) scans are conservative: no commutation
    ia = Q.scan("a").join(Q.scan("b"), on="k").count("k").node
    ib = Q.scan("b").join(Q.scan("a"), on="k").count("k").node
    assert fingerprint(ia) != fingerprint(ib)


def test_different_semantics_never_collide():
    """Structurally similar but semantically different plans: swapped
    join sides under a row-producing root, shifted/inverted predicate
    bounds, different aggregates, different columns."""
    pa = (Q.scan("big").join(Q.scan("small"), on="k")
           .project("k", "v").node)
    pb = (Q.scan("small").join(Q.scan("big"), on="k")
           .project("k", "v").node)
    assert fingerprint(pa) != fingerprint(pb)      # row order differs
    f = Q.scan("big").filter("v", 10, 20).sum("w")
    assert fingerprint(f.node) != fingerprint(
        Q.scan("big").filter("v", 20, 10).sum("w").node)   # inverted
    assert fingerprint(f.node) != fingerprint(
        Q.scan("big").filter("v", 10, 21).sum("w").node)   # widened
    assert fingerprint(f.node) != fingerprint(
        Q.scan("big").filter("w", 10, 20).sum("w").node)   # other column
    assert fingerprint(f.node) != fingerprint(
        Q.scan("big").filter("v", 10, 20).count("w").node)  # other agg
    assert fingerprint(f.node) != fingerprint(
        Q.scan("big").filter("v", 10, 20).mean("w").node)


def test_fingerprint_embeds_table_versions():
    n = Q.scan("big").filter("v", 0, 10).sum("w").node
    assert fingerprint(n, {"big": 0}) != fingerprint(n, {"big": 1})
    # versions of unreferenced tables are irrelevant
    assert fingerprint(n, {"big": 0}) == fingerprint(n, {"big": 0,
                                                         "other": 7})


# --------------------------------------------------------------------------- #
# result reuse + invalidation

@pytest.mark.requires_cache
def test_result_cache_hit_skips_execution(rng):
    cat, *_ = _make_catalog(rng)
    ex = Executor(cat, cache_bytes=32 << 20)
    q = _join_sum()
    r1 = ex.execute(q)
    assert not r1.result_cache_hit
    r2 = ex.execute(q)
    assert r2.result_cache_hit and r2.value == r1.value
    # the streamed path shares the same semantic key
    r3 = ex.execute(q, mode="stream")
    assert r3.result_cache_hit and r3.value == r1.value
    assert ex.result_hits == 2


@pytest.mark.requires_cache
@pytest.mark.parametrize("op", ["sum", "count", "mean"])
def test_cached_templates_answer_as_cache_off(rng, op):
    """Join+filter+aggregate templates (distinct bounds, so distinct
    fingerprints) served from a warm cache answer exactly as an executor
    with no cache computes them."""
    cat, *_ = _make_catalog(rng)
    cached = Executor(cat, cache_bytes=32 << 20)
    plain = Executor(cat)
    templates = [
        Q.scan("big").join(Q.scan("small"), on="k")
         .filter("v", lo, lo + 6).aggregate(op, "w")
        for lo in (1, 30, 70)]
    for q in templates:
        assert not cached.execute(q).result_cache_hit
    for q in templates:
        hit = cached.execute(q)
        assert hit.result_cache_hit
        assert hit.value == plain.execute(q).value


@pytest.mark.requires_cache
def test_mutation_invalidates_differential(rng):
    """Acceptance: a base-table mutation provably invalidates dependent
    entries — post-mutation results are bit-identical to cache-disabled
    execution (and to a numpy oracle), never the stale cached value."""
    cat, big, small = _make_catalog(rng)
    ex = Executor(cat, cache_bytes=32 << 20)
    q = _join_sum()
    stale = ex.execute(q).value
    assert ex.execute(q).result_cache_hit
    new_w = rng.integers(51, 99, size=big.num_rows).astype(np.int32)
    cat.update_column("big", "w", new_w)
    res = ex.execute(q)
    assert not res.result_cache_hit
    plain = Executor(cat).execute(q).value            # cache-disabled
    k = np.asarray(big.column("k"))
    v = np.asarray(big.column("v"))
    m = (v >= 30) & (v <= 49) & np.isin(k, np.asarray(small.column("k")))
    want = int(new_w[m].sum())
    assert int(res.value) == int(plain) == want
    assert int(res.value) != int(stale)
    # the sweep reclaimed the dependent entries' bytes
    assert ex.cache.invalidated > 0


def test_mutation_invalidates_join_build(rng):
    """A mutation to the BUILD side table must re-sort the bucket build,
    not replay the cached one."""
    cat, big, small = _make_catalog(rng)
    ex = Executor(cat, cache_bytes=32 << 20)
    q = _join_sum(0, 99)
    ex.execute(q)
    half = np.asarray(
        rng.choice(1000, size=small.num_rows, replace=False), np.int32)
    cat.update_column("small", "k", half)
    got = ex.execute(q)
    assert not got.result_cache_hit
    assert int(got.value) == int(Executor(cat).execute(q).value)


def test_stale_entries_unreachable_even_without_sweep(rng):
    """Even a cache that was never swept cannot serve stale state: the
    version inside the fingerprint changes the key itself."""
    cat, big, _ = _make_catalog(rng)
    ex = Executor(cat, cache_bytes=32 << 20)
    q = Q.scan("big").filter("v", 10, 60).sum("w")
    ex.execute(q)
    fp_before = ex.fingerprint_of(q.node)
    big.update_column("w", rng.integers(1, 50,
                                        size=big.num_rows).astype(np.int32))
    fp_after = ex.fingerprint_of(q.node)    # direct mutation, no catalog
    assert fp_before != fp_after


# --------------------------------------------------------------------------- #
# budgeted admission / eviction

def test_eviction_respects_budget_and_value_density():
    model = CostModel(4)
    cache = SemanticCache(budget_bytes=1000, model=model)
    # an expensive-to-rebuild small entry...
    assert cache.put("gold", "g", kind="result", n_bytes=200,
                     recompute_s=1.0, tables=("t",))
    # ...a big but trivially recomputed one fills the rest
    assert cache.put("bulk", "b", kind="subplan", n_bytes=800,
                     recompute_s=1e-6, tables=("t",))
    assert cache.used_bytes == 1000
    # a mid-value entry displaces the low-density bulk, never the gold
    assert cache.put("mid", "m", kind="result", n_bytes=500,
                     recompute_s=0.1, tables=("t",))
    assert "gold" in cache and "mid" in cache and "bulk" not in cache
    assert cache.used_bytes <= 1000
    assert cache.evicted == 1
    # an entry worse than everything resident is rejected outright
    assert not cache.put("junk", "j", kind="subplan", n_bytes=900,
                         recompute_s=1e-9, tables=("t",))
    assert "junk" not in cache and cache.rejected >= 1
    # over-budget candidates never churn the cache
    assert not cache.put("huge", "h", kind="result", n_bytes=2000,
                         recompute_s=9.0, tables=("t",))
    assert "gold" in cache and "mid" in cache


def test_invalidate_table_sweeps_dependents():
    cache = SemanticCache(budget_bytes=1 << 20, model=CostModel(1))
    cache.put("a", 1, kind="result", n_bytes=10, recompute_s=1.0,
              tables=("big", "small"))
    cache.put("b", 2, kind="result", n_bytes=10, recompute_s=1.0,
              tables=("small",))
    cache.put("c", 3, kind="result", n_bytes=10, recompute_s=1.0,
              tables=("other",))
    assert cache.invalidate_table("small") == 2
    assert "c" in cache and cache.used_bytes == 10


@pytest.mark.requires_cache
def test_executor_under_tight_budget_stays_correct(rng):
    """A budget too small for every working-set entry must degrade to
    recomputation, never to wrong answers."""
    cat, big, _ = _make_catalog(rng)
    ex = Executor(cat, cache_bytes=256)        # a few scalars at most
    v = np.asarray(big.column("v"))
    w = np.asarray(big.column("w"))
    for lo in (0, 10, 20, 30, 40, 0, 10, 20):
        got = ex.execute(Q.scan("big").filter("v", lo, lo + 9)
                          .sum("w")).value
        m = (v >= lo) & (v <= lo + 9)
        assert int(got) == int(w[m].sum())
    assert ex.cache.used_bytes <= 256


# --------------------------------------------------------------------------- #
# subplan reuse (optimizer CSE + eager intermediates)

def test_common_subplans_extraction(rng):
    cat, *_ = _make_catalog(rng)
    qs = [(Q.scan("big").join(Q.scan("small"), on="k")
            .filter("v", 10, 60).sum("w")).node,
          (Q.scan("big").join(Q.scan("small"), on="k")
            .filter("v", 10, 60).mean("w")).node]
    opts = [optimize(n, cat.stats) for n in qs]
    shared = common_subplans(opts)
    assert shared, "the filtered join prefix is shared"
    assert all(c >= 2 for c in shared.values())
    # a batch with nothing in common shares nothing
    assert not common_subplans([
        Q.scan("big").filter("v", 0, 9).sum("w").node,
        Q.scan("big").filter("w", 1, 5).count("k").node])


@pytest.mark.requires_cache
def test_eager_subplan_reuse_across_different_roots(rng):
    """Two Project-rooted queries over the same filtered join reuse the
    materialized intermediate (subplan hit on the second run)."""
    cat, big, small = _make_catalog(rng)
    ex = Executor(cat, cache_bytes=64 << 20)
    q1 = (Q.scan("big").join(Q.scan("small"), on="k")
           .filter("v", 0, 50).project("k", "w"))
    q2 = (Q.scan("big").join(Q.scan("small"), on="k")
           .filter("v", 0, 50).project("k", "w", "x"))
    t1 = ex.execute(q1).value
    before = ex.subplan_hits
    t2 = ex.execute(q2).value
    assert ex.subplan_hits > before
    assert set(t2.columns) == {"k", "w", "x"}
    np.testing.assert_array_equal(np.asarray(t1.column("w")),
                                  np.asarray(t2.column("w")))


@pytest.mark.requires_cache
def test_server_serves_cached_and_hints_shared(rng):
    cat, big, _ = _make_catalog(rng)
    srv = QueryServer(Executor(cat, cache_bytes=32 << 20))
    q = _join_sum()
    first = srv.query(q)
    second = srv.query(q)                      # separate drain
    assert first == second
    assert srv.n_cached == 1
    recs = {r.qid: r for r in srv.history}
    assert any(r.path == "cached" for r in recs.values())
    # CSE hints fire when a batch shares a subtree
    srv.submit(Q.scan("big").filter("v", 5, 25).sum("w"))
    srv.submit(Q.scan("big").filter("v", 5, 25).count("w"))
    srv.drain()
    assert srv.n_subplan_shared > 0


@pytest.mark.requires_cache
def test_streamed_completion_feeds_result_cache(rng):
    """A query that completed by STREAMING admits its result, so the
    next submission finishes at admission instead of re-streaming."""
    cat, big, small = _make_catalog(rng)
    srv = QueryServer(Executor(cat, cache_bytes=32 << 20),
                      streaming=True, morsel_rows=512)
    q = _join_sum(10, 60)
    first = srv.query(q)
    assert srv.n_streamed == 1
    second = srv.query(q)
    assert second == first
    assert srv.n_cached == 1 and srv.n_streamed == 1   # no second stream


def test_mid_flight_mutation_restarts_member(rng):
    """A mutation while a query is streaming mid-circle must not let a
    mixed pre/post-mutation carry surface (or poison the cache): the
    member restarts against fresh data, and the answer matches
    cache-disabled execution on the NEW data."""
    cat, big, small = _make_catalog(rng)
    srv = QueryServer(Executor(cat, cache_bytes=32 << 20),
                      streaming=True, morsel_rows=512)
    q = _join_sum(0, 99)
    qid = srv.submit(q)
    srv.pump()
    srv.pump()                                 # mid-circle
    new_w = rng.integers(51, 99, size=big.num_rows).astype(np.int32)
    cat.update_column("big", "w", new_w)
    dup = srv.submit(q)                        # post-mutation duplicate
    res = srv.drain()
    want = int(Executor(cat).execute(q).value)
    assert int(res[qid]) == want
    assert int(res[dup]) == want
    # and a resubmission is served the CORRECT cached value
    assert int(srv.query(q)) == want


def test_build_side_mutation_on_streaming_server(rng):
    """Regression: a mutation to the JOIN BUILD table must reach the
    streaming server's groups — a group outliving the mutation holds
    stale build arrays unless attach refreshes them.  Covers both the
    mid-flight restart and a fresh query after completion, with and
    without the semantic cache."""
    for cache_bytes in (32 << 20, None):
        cat, big, small = _make_catalog(rng)
        srv = QueryServer(Executor(cat, cache_bytes=cache_bytes),
                          streaming=True, morsel_rows=512)
        q = _join_sum(0, 99)
        qid = srv.submit(q)
        srv.pump()
        srv.pump()                             # mid-circle
        new_k = np.asarray(
            rng.choice(1000, size=small.num_rows, replace=False), np.int32)
        cat.update_column("small", "k", new_k)
        res = srv.drain()
        want = int(Executor(cat).execute(q).value)
        assert int(res[qid]) == want, cache_bytes
        # a fresh query through the (now completed) group: fresh builds
        assert int(srv.query(q)) == want, cache_bytes


@pytest.mark.requires_cache
def test_streaming_server_dedups_by_fingerprint(rng):
    """Semantically-equal spellings dedup against an in-flight member
    even when the trees differ structurally."""
    cat, big, small = _make_catalog(rng)
    srv = QueryServer(Executor(cat, cache_bytes=32 << 20),
                      streaming=True, morsel_rows=512)
    qa = (Q.scan("big").join(Q.scan("small"), on="k")
           .filter("v", 10, 30).filter("w", 1, 20).sum("w"))
    qb = (Q.scan("big").join(Q.scan("small"), on="k")
           .filter("w", 1, 20).filter("v", 10, 30).sum("w"))
    ia = srv.submit(qa)
    srv.pump()
    ib = srv.submit(qb)                        # joins as a dedup
    res = srv.drain()
    assert res[ia] == res[ib]
    assert srv.n_deduped == 1
    k = np.asarray(big.column("k"))
    v = np.asarray(big.column("v"))
    w = np.asarray(big.column("w"))
    m = ((v >= 10) & (v <= 30) & (w >= 1) & (w <= 20)
         & np.isin(k, np.asarray(small.column("k"))))
    assert int(res[ia]) == int(w[m].sum())


# --------------------------------------------------------------------------- #
# satellites: H2D overlap thread + Project-rooted streaming serve

def test_overlap_thread_bit_identical(rng):
    """The background-transfer driver and the single-threaded
    double-buffered loop fold morsels in the same order: results are
    bit-identical (the determinism-debugging contract of the flag)."""
    cat, *_ = _make_catalog(rng)
    q = _join_sum(10, 60)
    on = Executor(cat, overlap_transfers=True)
    off = Executor(cat, overlap_transfers=False)
    for mr in (256, 1000, 4096):
        a = on.execute(q, mode="stream", morsel_rows=mr).value
        b = off.execute(q, mode="stream", morsel_rows=mr).value
        assert a == b


def test_project_rooted_streaming_serve(rng):
    """Streaming serve now admits Project-rooted queries: per-morsel
    outputs materialize into chunks reassembled in table order —
    bit-identical to the eager lowering, even when joining mid-flight."""
    cat, big, small = _make_catalog(rng)
    srv = QueryServer(Executor(cat, cache_bytes=32 << 20),
                      streaming=True, morsel_rows=512)
    qp = (Q.scan("big").join(Q.scan("small"), on="k")
           .filter("v", 10, 60).project("k", "w", "x"))
    qagg = _join_sum(10, 60)
    i_agg = srv.submit(qagg)
    srv.pump()
    srv.pump()
    i_proj = srv.submit(qp)                    # project joins mid-flight
    res = srv.drain()
    eager = Executor(cat).execute(qp).value
    got = res[i_proj]
    assert set(got.columns) == {"k", "w", "x"}
    for c in ("k", "w", "x"):
        np.testing.assert_array_equal(np.asarray(got.column(c)),
                                      np.asarray(eager.column(c)))
    k = np.asarray(big.column("k"))
    v = np.asarray(big.column("v"))
    w = np.asarray(big.column("w"))
    m = (v >= 10) & (v <= 60) & np.isin(k,
                                        np.asarray(small.column("k")))
    assert int(res[i_agg]) == int(w[m].sum())
    assert srv.stats()["n_streamed"] == 2


def test_project_streaming_rejects_duplicate_builds(rng):
    """A duplicate-keyed build multiplies rows — Project-rooted plans
    over it must fall back to the eager path, still correct."""
    big = Table.from_arrays("big", {
        "k": rng.integers(0, 40, size=1024).astype(np.int32),
        "v": rng.integers(0, 100, size=1024).astype(np.int32)})
    dup = Table.from_arrays("dup", {
        "k": rng.integers(0, 40, size=256).astype(np.int32),
        "x": rng.integers(1, 9, size=256).astype(np.int32)})
    cat = Catalog.from_tables(big, dup)
    from repro.query import analyze_project
    node = (Q.scan("big").join(Q.scan("dup"), on="k")
             .project("k", "x")).node
    assert analyze_project(optimize(node, cat.stats), cat.stats) is None
    srv = QueryServer(Executor(cat), streaming=True, morsel_rows=512)
    qid = srv.submit(node)
    res = srv.drain()
    want = Executor(cat).execute(node).value
    assert res[qid].num_rows == want.num_rows
