"""Predicate subsumption: differential/property suite.

The refinement path serves a narrower range predicate by AND-ing a
cached SUPERSET bitmap with the residual range mask instead of
re-streaming the base column.  Boundary semantics (closed intervals,
``lo == hi``, empty and inverted ranges, values sitting exactly on a
bound) are where silent wrong-answer bugs live, so every property here
is a three-way differential:

  (a) the naive oracle (``optimized=False`` — never touches the cache),
  (b) cold optimized execution (fresh cache, admission misses),
  (c) warm execution through a deliberately-seeded superset bitmap —
      which must BOTH be bit-identical to (a)/(b) AND actually report a
      subsumption hit whenever the cost model prices refinement below
      recomputation (and must NOT take the refine path when it loses).

Distributions cover uniform, zipf-skewed duplicates, adversarial
constant blocks with boundary-sitting values, and bands that make most
queries empty.
"""
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.columnar.table import Table
from repro.query import (
    Catalog, CostModel, Executor, Q, SemanticCache, fingerprint,
    selection_interval, subsumption_key,
)

N_ROWS = 2048          # divisible by engines*block: the kernel path runs
DOMAIN = 1000


def _values(seed: int, dist: int, n: int = N_ROWS) -> np.ndarray:
    r = np.random.default_rng(seed)
    if dist == 0:        # uniform over the whole domain
        v = r.integers(0, DOMAIN, size=n)
    elif dist == 1:      # zipf-skewed duplicates, clipped into domain
        v = np.minimum(r.zipf(1.3, size=n), DOMAIN - 1)
    elif dist == 2:      # adversarial: constant blocks + exact-boundary
        # values, so off-by-one range bugs always have a witness row
        block = np.repeat(r.integers(0, DOMAIN, size=8), n // 8)
        v = np.concatenate([block, r.integers(0, DOMAIN,
                                              size=n - block.size)])
        v[:: max(n // 64, 1)] = r.integers(0, 4) * (DOMAIN // 4)
    else:                # narrow band: most predicates select nothing
        v = r.integers(DOMAIN // 2, DOMAIN // 2 + 20, size=n)
    return v.astype(np.int32)


def _catalog(seed: int, dist: int):
    r = np.random.default_rng(seed + 1)
    t = Table.from_arrays("t", {
        "v": _values(seed, dist),
        "w": r.integers(1, 50, size=N_ROWS).astype(np.int32),
        "k": r.integers(0, 100, size=N_ROWS).astype(np.int32)})
    return Catalog.from_tables(t), t


def _assert_tables_equal(a, b):
    assert set(a.columns) == set(b.columns)
    for c in a.columns:
        np.testing.assert_array_equal(np.asarray(a.column(c)),
                                      np.asarray(b.column(c)))


def _proj(lo, hi):
    return Q.scan("t").filter("v", lo, hi).project("k", "w")


def _expected_refine(ex: Executor, cached_rows: int) -> bool:
    """Mirror the executor's own pricing decision, so the hit assertion
    can never drift from the model (both sides share impl/placement)."""
    return ex.cost_model.refine_wins(cached_rows, N_ROWS)


@pytest.mark.requires_cache
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), dist=st.integers(0, 3),
       lo_w=st.integers(0, 600), width_w=st.integers(40, 280),
       off=st.integers(0, 200), width_n=st.integers(0, 150))
def test_warm_narrower_range_bit_identical(seed, dist, lo_w, width_w,
                                           off, width_n):
    """Random filter chains over random distributions: the warm path
    (narrow served through a seeded superset) is bit-identical to the
    naive oracle and the cold optimized run, and reports a subsumption
    hit exactly when the model prices refinement as the winner."""
    hi_w = lo_w + width_w
    lo_n = min(lo_w + off, hi_w)
    hi_n = min(lo_n + width_n, hi_w)
    cat, _ = _catalog(seed, dist)
    oracle = Executor(cat).execute(_proj(lo_n, hi_n),
                                   optimized=False).value
    cold = Executor(cat, cache_bytes=32 << 20).execute(
        _proj(lo_n, hi_n)).value
    warm_ex = Executor(cat, cache_bytes=32 << 20)
    warm_ex.execute(_proj(lo_w, hi_w))            # seed the superset
    seeded = warm_ex.cache.peek(
        ("bitmap", "t", 0, "v", int(lo_w), int(hi_w)))
    assert seeded is not None, "the wide run must admit its bitmap"
    warm = warm_ex.execute(_proj(lo_n, hi_n)).value
    _assert_tables_equal(oracle, cold)
    _assert_tables_equal(oracle, warm)
    # the wide range asked again is answered whole by the result cache,
    # before any subsumption is tried
    want_hit = (lo_n, hi_n) != (lo_w, hi_w) \
        and _expected_refine(warm_ex, int(seeded.value.shape[0]))
    assert (warm_ex.subsumption_hits == 1) == want_hit, \
        (warm_ex.subsumption_hits, int(seeded.value.shape[0]))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 16), dist=st.integers(0, 3),
       lo=st.integers(0, 900), width=st.integers(0, 300))
def test_cold_optimized_matches_oracle_any_distribution(seed, dist, lo,
                                                        width):
    """Cache-independent differential (also runs in the REPRO_CACHE=0
    leg): optimized execution equals the naive oracle and a numpy
    reference on every distribution."""
    cat, t = _catalog(seed, dist)
    q = Q.scan("t").filter("v", lo, lo + width).project("k", "w")
    ex = Executor(cat)
    got = ex.execute(q).value
    ref = ex.execute(q, optimized=False).value
    _assert_tables_equal(got, ref)
    v = np.asarray(t.column("v"))
    m = (v >= lo) & (v <= lo + width)
    np.testing.assert_array_equal(np.asarray(got.column("w")),
                                  np.asarray(t.column("w"))[m])


# --------------------------------------------------------------------------- #
# boundary semantics

@pytest.mark.requires_cache
def test_closed_interval_boundaries_survive_refinement():
    """Rows sitting EXACTLY on the narrow bounds: ``[lo, hi]`` is closed
    on both ends, so refining from a superset must keep lo- and
    hi-valued rows, and the half-open spelling ``(lo, hi)`` emulated as
    ``[lo+1, hi-1]`` must drop them."""
    v = np.asarray([10, 50, 50, 100, 150, 200, 200, 250], np.int32)
    t = Table.from_arrays("t", {"v": v,
                                "w": np.arange(8, dtype=np.int32),
                                "k": np.arange(8, dtype=np.int32)})
    cat = Catalog.from_tables(t)
    ex = Executor(cat, cache_bytes=32 << 20)
    ex.execute(_proj(0, 400))                     # superset: everything
    closed = ex.execute(_proj(50, 200)).value
    np.testing.assert_array_equal(np.asarray(closed.column("w")),
                                  [1, 2, 3, 4, 5, 6])
    open_ = ex.execute(_proj(51, 199)).value
    np.testing.assert_array_equal(np.asarray(open_.column("w")),
                                  [3, 4])
    oracle = Executor(cat)
    _assert_tables_equal(closed,
                         oracle.execute(_proj(50, 200),
                                        optimized=False).value)
    _assert_tables_equal(open_,
                         oracle.execute(_proj(51, 199),
                                        optimized=False).value)


@pytest.mark.requires_cache
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 16), dist=st.integers(0, 3),
       point=st.integers(0, 999))
def test_lo_equals_hi_point_query(seed, dist, point):
    """``lo == hi`` is a legal (single-point) closed interval — refined
    from a superset it must equal the oracle exactly."""
    cat, t = _catalog(seed, dist)
    ex = Executor(cat, cache_bytes=32 << 20)
    ex.execute(_proj(max(point - 60, 0), point + 60))
    got = ex.execute(_proj(point, point)).value
    ref = Executor(cat).execute(_proj(point, point),
                                optimized=False).value
    _assert_tables_equal(got, ref)
    v = np.asarray(t.column("v"))
    assert got.num_rows == int((v == point).sum())


@pytest.mark.requires_cache
def test_empty_and_inverted_ranges():
    """An empty result (no row in range) and an inverted interval
    (``lo > hi``) must both refine to exactly zero rows — an inverted
    request is contained in ANY superset by convention."""
    r = np.random.default_rng(7)
    # two sparse bands with a gap: the superset is selective (refine
    # wins) but the narrow range falls entirely into the gap
    v = np.where(np.arange(N_ROWS) % 8 == 0,
                 np.where(np.arange(N_ROWS) % 16 == 0, 420, 680),
                 r.integers(0, 300, size=N_ROWS)).astype(np.int32)
    t = Table.from_arrays("t", {
        "v": v, "w": r.integers(1, 50, size=N_ROWS).astype(np.int32),
        "k": r.integers(0, 100, size=N_ROWS).astype(np.int32)})
    cat = Catalog.from_tables(t)
    ex = Executor(cat, cache_bytes=32 << 20)
    ex.execute(_proj(400, 700))                   # superset: both bands
    empty = ex.execute(_proj(500, 600)).value     # the gap: no rows
    assert empty.num_rows == 0
    assert ex.subsumption_hits == 1
    inverted = ex.execute(_proj(650, 450)).value  # lo > hi
    assert inverted.num_rows == 0
    oracle = Executor(cat)
    _assert_tables_equal(
        inverted, oracle.execute(_proj(650, 450), optimized=False).value)


# --------------------------------------------------------------------------- #
# the lookup contract

def test_tightest_superset_rule_unit():
    """The interval index returns the SMALLEST containing interval, not
    the first admitted; non-containing and wrong-version entries never
    match."""
    cache = SemanticCache(1 << 20, model=CostModel(1))
    for key, (lo, hi) in {"wide": (0, 500), "mid": (100, 300),
                          "off": (400, 900)}.items():
        cache.put(key, key, kind="bitmap", n_bytes=8, recompute_s=1.0,
                  tables=("t",), interval=("t", "v", 0, lo, hi))
    entry, bounds = cache.lookup_superset("t", "v", 0, 150, 250)
    assert entry.key == "mid" and bounds == (100, 300)
    assert cache.lookup_superset("t", "v", 0, 50, 450)[0].key == "wide"
    assert cache.lookup_superset("t", "v", 0, 450, 600)[0].key == "off"
    assert cache.lookup_superset("t", "v", 1, 150, 250) is None  # version
    assert cache.lookup_superset("t", "w", 0, 150, 250) is None  # column
    assert cache.lookup_superset("t", "v", 0, 0, 901) is None    # no sup
    # the inverted (empty) request matches anything; tightest wins
    assert cache.lookup_superset("t", "v", 0, 9, 3)[0].key == "mid"
    # eviction unregisters from the index
    cache.invalidate_table("t")
    assert cache.lookup_superset("t", "v", 0, 150, 250) is None
    assert cache.stats_dict()["semantic_cache_interval_buckets"] == 0


@pytest.mark.requires_cache
def test_executor_refines_from_tightest_superset(rng):
    """A narrowing ladder refines each rung from the nearest ancestor:
    with [0,500] and [100,300] both cached, [150,250] must touch the
    tighter bitmap (fewer bytes streamed), not the wide one."""
    cat, _ = _catalog(3, 0)
    ex = Executor(cat, cache_bytes=32 << 20)
    ex.execute(_proj(0, 320))                     # ~32% of rows: selective
    ex.execute(_proj(100, 300))                   # refines from [0,320]
    assert ex.subsumption_hits == 1
    before = ex.refine_bytes_streamed
    ex.execute(_proj(150, 250))
    assert ex.subsumption_hits == 2
    mid = ex.cache.peek(("bitmap", "t", 0, "v", 100, 300))
    wide = ex.cache.peek(("bitmap", "t", 0, "v", 0, 320))
    assert mid.hits >= 1                          # the tight one served
    streamed = ex.refine_bytes_streamed - before
    assert streamed == 3 * mid.value.nbytes
    assert streamed < 3 * wide.value.nbytes


def test_subsumption_key_family():
    """All range variants of one selection plan share the subsumption
    key; different residuals, columns, or versions do not — and the key
    is distinct from the exact fingerprint's behavior (which embeds the
    bounds)."""
    a = _proj(10, 20).node
    b = _proj(400, 900).node
    assert subsumption_key(a) == subsumption_key(b)
    assert fingerprint(a) != fingerprint(b)
    c = Q.scan("t").filter("v", 10, 20).project("k").node     # residual
    assert subsumption_key(a) != subsumption_key(c)
    d = Q.scan("t").filter("w", 10, 20).project("k", "w").node  # column
    assert subsumption_key(a) != subsumption_key(d)
    assert subsumption_key(a, {"t": 1}) != subsumption_key(a, {"t": 0})
    assert subsumption_key(Q.scan("t").sum("w").node) is None
    si = selection_interval(a)
    assert (si.table, si.column, si.lo, si.hi) == ("t", "v", 10, 20)
    assert si.contains(12, 18) and si.contains(10, 20)
    assert not si.contains(9, 18) and si.contains(19, 12)     # inverted


# --------------------------------------------------------------------------- #
# refinement variants + pricing gate

@pytest.mark.requires_cache
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 16), chunk=st.integers(1, 50))
def test_chunked_refine_variant_bit_identical(seed, chunk):
    """The streamed/morsel refinement (bounded index slices) equals the
    eager one for every chunk size — including chunks that do not divide
    the bitmap and single-row chunks."""
    cat, t = _catalog(seed, 0)
    ex = Executor(cat, cache_bytes=32 << 20)
    ex.execute(_proj(0, 400))
    entry = ex.cache.peek(("bitmap", "t", 0, "v", 0, 400))
    col = t.column("v")
    eager = ex._refine_bitmap(col, entry.value, 100, 300)
    sliced = ex._refine_bitmap(col, entry.value, 100, 300,
                               chunk_rows=chunk)
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(sliced))


@pytest.mark.requires_cache
def test_capacity_posture_refines_in_chunks(rng):
    """With a placement capacity set (the out-of-core posture) the
    executor refines morsel-style; answers stay bit-identical."""
    cat, _ = _catalog(11, 0)
    cap = N_ROWS * 4                              # columns just fit
    ex = Executor(cat, cache_bytes=32 << 20, placement_capacity_bytes=cap)
    assert ex._refine_chunk() == cap // 8
    ex.execute(_proj(0, 320))
    got = ex.execute(_proj(100, 300)).value
    assert ex.subsumption_hits == 1
    ref = Executor(cat).execute(_proj(100, 300), optimized=False).value
    _assert_tables_equal(got, ref)


@pytest.mark.requires_cache
def test_refine_only_when_priced_cheaper(rng):
    """A near-full superset (bitmap ~ every row) must NOT be refined —
    streaming 3x the bitmap would cost more than one base-column scan —
    and the recomputed narrow answer is still exact."""
    cat, t = _catalog(13, 0)
    ex = Executor(cat, cache_bytes=32 << 20)
    ex.execute(_proj(0, DOMAIN))                  # superset: all rows
    entry = ex.cache.peek(("bitmap", "t", 0, "v", 0, DOMAIN))
    assert not ex.cost_model.refine_wins(int(entry.value.shape[0]),
                                         N_ROWS)
    got = ex.execute(_proj(100, 300)).value
    assert ex.subsumption_hits == 0               # priced out
    ref = Executor(cat).execute(_proj(100, 300), optimized=False).value
    _assert_tables_equal(got, ref)


@pytest.mark.requires_cache
def test_aggregate_routed_onto_warmed_bitmap(rng):
    """A fused aggregate pipeline abandons its full-column scan when a
    selective bitmap is cached: the eager gather path serves it via
    subsumption, bit-identical to both the fused run and the oracle."""
    cat, t = _catalog(17, 0)
    ex = Executor(cat, cache_bytes=32 << 20)
    q = Q.scan("t").filter("v", 120, 280).sum("w")
    fused = ex.execute(q).value                   # no bitmap yet: fused
    assert ex.subsumption_hits == 0
    ex.execute(_proj(100, 300))                   # warm the superset
    q2 = Q.scan("t").filter("v", 130, 270).sum("w")
    routed = ex.execute(q2).value
    assert ex.subsumption_hits == 1
    oracle = Executor(cat)
    assert routed == oracle.execute(q2, optimized=False).value
    assert fused == oracle.execute(q, optimized=False).value
    v, w = np.asarray(t.column("v")), np.asarray(t.column("w"))
    assert int(routed) == int(w[(v >= 130) & (v <= 270)].sum())


@pytest.mark.requires_cache
def test_mutation_unreaches_supersets(rng):
    """A version bump makes every cached superset unreachable: the next
    narrow query recomputes (no subsumption hit) and matches a
    cache-disabled executor on the new data."""
    cat, t = _catalog(19, 0)
    ex = Executor(cat, cache_bytes=32 << 20)
    ex.execute(_proj(0, 400))
    cat.update_column("t", "v", _values(999, 0))
    got = ex.execute(_proj(100, 300)).value
    assert ex.subsumption_hits == 0
    _assert_tables_equal(got,
                         Executor(cat).execute(_proj(100, 300)).value)
    # and the interval bucket for the old version was swept, not leaked
    assert ex.cache.lookup_superset("t", "v", 0, 100, 300) is None
