"""Differential/property harness for the duplicate-capable hash join.

Every join surface (``hash_join_multi`` kernel, ``join_distributed_multi``
operator, the executor's plan) is checked against a NumPy sort-merge
oracle for EXACT multiset-of-pairs equality, over generated key
distributions: unique, duplicate-heavy, Zipf-skewed, and adversarial
(all-equal keys, empty sides, single-key build).  With hypothesis
installed (CI) each property runs its full ``max_examples``; the
deterministic ``_hyp`` fallback runs a shrunk seeded sample so tier-1
stays fast without the dependency.
"""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.columnar.table import Table
from repro.core import join as join_core
from repro.core.channels import plan as make_plan
from repro.kernels.join import ref
from repro.kernels.join.ops import (
    MAX_DROPPED, hash_join, hash_join_multi, materialize_pairs,
)
from repro.query import Catalog, Executor, Q, optimize
from repro.query.logical import Join, Scan, walk


# --------------------------------------------------------------------------- #
# oracle + generators

def sort_merge_pairs(s: np.ndarray, l: np.ndarray) -> np.ndarray:
    """NumPy sort-merge join: the exact (l_idx, s_idx) pair multiset,
    returned lexicographically sorted."""
    if s.size == 0 or l.size == 0:
        return np.empty((0, 2), np.int64)
    order = np.argsort(s, kind="stable")
    ss = s[order]
    start = np.searchsorted(ss, l, side="left")
    end = np.searchsorted(ss, l, side="right")
    l_idx = np.repeat(np.arange(l.size), end - start)
    if l_idx.size == 0:
        return np.empty((0, 2), np.int64)
    s_idx = order[np.concatenate(
        [np.arange(a, b) for a, b in zip(start, end)])]
    pairs = np.stack([l_idx, s_idx], axis=1)
    return pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]


def pairs_of(l_idx, s_idx) -> np.ndarray:
    """Compacted, lex-sorted pair multiset from a -1-padded pair list."""
    l_idx, s_idx = np.asarray(l_idx), np.asarray(s_idx)
    keep = l_idx >= 0
    pairs = np.stack([l_idx[keep], s_idx[keep]], axis=1).astype(np.int64)
    return pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]


DISTS = ("unique", "dup_heavy", "zipf", "all_equal", "single_key")
N_S_SIZES = (1, 16, 64, 120)          # quantized: bounds jit recompiles
N_L = 256


def make_keys(dist: str, r: np.random.Generator, n_s: int, n_l: int):
    if dist == "unique":
        dom = 10 * max(n_s, 1)
        s = r.choice(dom, size=n_s, replace=False)
        l = r.integers(0, dom, size=n_l)
    elif dist == "dup_heavy":
        dom = max(n_s // 4, 1)
        s = r.integers(0, dom, size=n_s)
        l = r.integers(0, 2 * dom, size=n_l)
    elif dist == "zipf":
        s = np.minimum(r.zipf(1.5, size=n_s), 200) - 1
        l = np.minimum(r.zipf(1.5, size=n_l), 200) - 1
    elif dist == "all_equal":
        s = np.full(n_s, 7)
        l = np.where(r.random(n_l) < 0.5, 7, 9)
    elif dist == "single_key":
        s = np.full(1, 5)
        l = r.integers(0, 10, size=n_l)
    else:
        raise ValueError(dist)
    return s.astype(np.int32), l.astype(np.int32)


def _pow2_at_least(n: int) -> int:
    return ref.next_pow2(max(n, 64))


# --------------------------------------------------------------------------- #
# kernel-level properties (both impls)

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("dist", DISTS)
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2 ** 16), size_i=st.integers(0, 3))
def test_multi_join_matches_sort_merge_oracle(impl, dist, seed, size_i):
    """hash_join_multi == sort-merge oracle, exactly, as a pair multiset —
    for every distribution and both the XLA and the (interpreted) Pallas
    probe.  cap=4 forces the overflow pass on duplicate-heavy chains."""
    r = np.random.default_rng(seed)
    s, l = make_keys(dist, r, N_S_SIZES[size_i], N_L)
    expected = sort_merge_pairs(s, l)
    max_out = _pow2_at_least(len(expected) + 1)
    res = hash_join_multi(jnp.asarray(s), jnp.asarray(l), max_out=max_out,
                          impl=impl, block=N_L, cap=4, interpret=True)
    assert int(res.total) == len(expected)
    assert not bool(res.overflowed)
    np.testing.assert_array_equal(pairs_of(res.l_idx, res.s_idx), expected)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), dist_i=st.integers(0, 4))
def test_pallas_and_xla_emit_identical_pair_lists(seed, dist_i):
    """Not just the same multiset: both impls emit pairs in the identical
    (probe row, bucket position) order, padding included."""
    r = np.random.default_rng(seed)
    s, l = make_keys(DISTS[dist_i], r, 64, N_L)
    max_out = _pow2_at_least(len(sort_merge_pairs(s, l)) + 1)
    a = hash_join_multi(jnp.asarray(s), jnp.asarray(l), max_out=max_out,
                        impl="xla")
    b = hash_join_multi(jnp.asarray(s), jnp.asarray(l), max_out=max_out,
                        impl="pallas", block=N_L, cap=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(a.l_idx), np.asarray(b.l_idx))
    np.testing.assert_array_equal(np.asarray(a.s_idx), np.asarray(b.s_idx))
    assert int(a.total) == int(b.total)


@pytest.mark.parametrize("factor", [1, 4, 16])
def test_multi_join_total_per_duplicate_factor(factor):
    """Every build key repeated ``factor`` times and every probe key
    present: each probe row emits exactly ``factor`` pairs, so the pair
    total is probe rows x factor, with no overflow."""
    r = np.random.default_rng(factor)
    n_distinct = 64
    s = np.repeat(np.arange(n_distinct, dtype=np.int32), factor)
    l = r.integers(0, n_distinct, size=4 * N_L).astype(np.int32)
    n_pairs = l.size * factor
    res = hash_join_multi(jnp.asarray(s), jnp.asarray(l),
                          max_out=ref.next_pow2(n_pairs + 1), impl="xla")
    assert int(res.total) == n_pairs
    assert not bool(res.overflowed)


def test_empty_sides():
    for n_s, n_l in ((0, 256), (8, 0), (0, 0)):
        s = jnp.asarray(np.arange(n_s, dtype=np.int32))
        l = jnp.asarray(np.arange(n_l, dtype=np.int32))
        res = hash_join_multi(s, l, max_out=64)
        assert int(res.total) == 0 and not bool(res.overflowed)
        assert not (np.asarray(res.l_idx) >= 0).any()


def test_pair_list_truncation_keeps_prefix_and_exact_total():
    """Overflowing the pair list keeps the FIRST max_out pairs (global
    (probe row, bucket) order), flags it, and still reports the exact
    total — nothing is silently lost."""
    s = jnp.zeros((16,), jnp.int32)
    l = jnp.zeros((16,), jnp.int32)          # 16 x 16 = 256 pairs
    res = hash_join_multi(s, l, max_out=64)
    assert int(res.total) == 256 and bool(res.overflowed)
    got = pairs_of(res.l_idx, res.s_idx)
    assert len(got) == 64
    np.testing.assert_array_equal(got, sort_merge_pairs(
        np.zeros(16, np.int32), np.zeros(16, np.int32))[:64])


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_unique_fast_path_agrees_with_multi(seed):
    """On unique build keys the paper's open-addressing fast path and the
    sorted-bucket multi path return the same pair multiset."""
    r = np.random.default_rng(seed)
    s, l = make_keys("unique", r, 120, N_L)
    res = hash_join(jnp.asarray(s), jnp.asarray(l),
                    table_size=ref.next_pow2(4 * s.size), probe_depth=8)
    assert not bool(res.overflowed)
    s_idx = np.asarray(res.s_idx)
    hit = s_idx >= 0
    fast = np.stack([np.nonzero(hit)[0], s_idx[hit]], axis=1)
    expected = sort_merge_pairs(s, l)
    np.testing.assert_array_equal(
        fast[np.lexsort((fast[:, 1], fast[:, 0]))], expected)


def test_materialize_pairs_gathers_values():
    s = np.asarray([3, 3, 9], np.int32)
    l = np.asarray([9, 3, 1], np.int32)
    res = hash_join_multi(jnp.asarray(s), jnp.asarray(l), max_out=64)
    l_out, s_out = materialize_pairs(res.l_idx, res.s_idx,
                                     jnp.asarray(l) * 10,
                                     jnp.asarray(s) * 100)
    keep = np.asarray(res.l_idx) >= 0
    np.testing.assert_array_equal(np.asarray(l_out)[keep], [90, 30, 30])
    np.testing.assert_array_equal(np.asarray(s_out)[keep], [900, 300, 300])


# --------------------------------------------------------------------------- #
# distributed operator

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2 ** 16), dist_i=st.integers(0, 4))
def test_join_distributed_multi_matches_oracle(host_mesh, seed, dist_i):
    r = np.random.default_rng(seed)
    n_l = 256 * host_mesh.shape["model"]
    s, l = make_keys(DISTS[dist_i], r, 120, n_l)
    expected = sort_merge_pairs(s, l)
    p = make_plan(host_mesh, "model", "partitioned")
    l_idx, s_idx, totals, over = join_core.join_distributed_multi(
        jnp.asarray(s), jnp.asarray(l), p,
        max_out_per_shard=_pow2_at_least(len(expected) + 1))
    assert int(np.asarray(totals).sum()) == len(expected)
    assert not bool(np.asarray(over).any())
    np.testing.assert_array_equal(pairs_of(l_idx, s_idx), expected)


def test_join_distributed_multi_pallas_impl(host_mesh):
    """The distributed operator's interpreted-Pallas probe (counts-only
    kernel + offset emission) matches the oracle too."""
    r = np.random.default_rng(5)
    n_l = 512 * host_mesh.shape["model"]
    s = r.integers(0, 80, size=200).astype(np.int32)
    l = r.integers(0, 100, size=n_l).astype(np.int32)
    expected = sort_merge_pairs(s, l)
    p = make_plan(host_mesh, "model", "partitioned")
    l_idx, s_idx, totals, over = join_core.join_distributed_multi(
        jnp.asarray(s), jnp.asarray(l), p, impl="pallas", block=256,
        interpret=True, max_out_per_shard=_pow2_at_least(len(expected) + 1))
    assert int(np.asarray(totals).sum()) == len(expected)
    assert not bool(np.asarray(over).any())
    np.testing.assert_array_equal(pairs_of(l_idx, s_idx), expected)


def test_join_distributed_multi_multipass(host_mesh):
    """Build side beyond HT_CAPACITY: the multi-pass rescan (Fig. 8b
    regime) still emits the exact pair multiset."""
    r = np.random.default_rng(11)
    n_s = join_core.HT_CAPACITY + 77          # 2 passes, ragged tail
    s = r.integers(0, 3000, size=n_s).astype(np.int32)
    l = r.integers(0, 3000, size=1024 * host_mesh.shape["model"]) \
         .astype(np.int32)
    expected = sort_merge_pairs(s, l)
    p = make_plan(host_mesh, "model", "partitioned")
    l_idx, s_idx, totals, over = join_core.join_distributed_multi(
        jnp.asarray(s), jnp.asarray(l), p,
        max_out_per_shard=_pow2_at_least(len(expected) + 1))
    assert int(np.asarray(totals).sum()) == len(expected)
    assert not bool(np.asarray(over).any())
    np.testing.assert_array_equal(pairs_of(l_idx, s_idx), expected)


# --------------------------------------------------------------------------- #
# regression: the fast path's drop buffer overflow is SURFACED, and the
# multi path recovers the lost matches

def test_drop_buffer_overflow_is_surfaced():
    r = np.random.default_rng(3)
    # adversarial build: load factor 1.0 with probe_depth=1 drops far more
    # keys than the MAX_DROPPED slow-path buffer can recover
    s = np.asarray(r.choice(10 ** 6, 2048, replace=False), np.int32)
    l = np.asarray(r.permutation(s), np.int32)
    res = hash_join(jnp.asarray(s), jnp.asarray(l), table_size=2048,
                    probe_depth=1)
    assert int(res.dropped) > MAX_DROPPED
    assert bool(res.overflowed)               # the bug fix: flagged, not silent
    assert int(res.total) < 2048              # matches really were lost
    # the duplicate-capable path never drops: exact on the same input
    multi = hash_join_multi(jnp.asarray(s), jnp.asarray(l), max_out=2048)
    assert int(multi.total) == 2048 and not bool(multi.overflowed)


def test_no_overflow_below_buffer_capacity():
    r = np.random.default_rng(4)
    s = np.asarray(r.choice(10 ** 6, 512, replace=False), np.int32)
    l = np.asarray(r.integers(0, 10 ** 6, 1024), np.int32)
    res = hash_join(jnp.asarray(s), jnp.asarray(l),
                    table_size=ref.next_pow2(4 * 512), probe_depth=8)
    assert not bool(res.overflowed)


# --------------------------------------------------------------------------- #
# cross-layer equivalence: kernel == distributed operator == executor,
# including the formerly-refused duplicate-build-side plan

def _dup_catalog():
    r = np.random.default_rng(7)
    big = Table.from_arrays("big", {
        "k": r.integers(0, 600, size=4096).astype(np.int32),
        "w": r.integers(1, 50, size=4096).astype(np.int32)})
    dup_small = Table.from_arrays("dup_small", {
        "k": r.integers(0, 50, size=512).astype(np.int32)})
    return Catalog.from_tables(big, dup_small), big, dup_small


def test_optimizer_selects_duplicate_build_side():
    cat, _, _ = _dup_catalog()
    q = Q.scan("big").join(Q.scan("dup_small"), on="k").sum("w")
    node = optimize(q.node, cat.stats)
    join = [n for n in walk(node) if isinstance(n, Join)][0]
    assert isinstance(join.right, Scan)
    assert join.right.table == "dup_small"    # formerly refused (duplicates)
    assert join.left.table == "big"


def test_cross_layer_duplicate_join_equivalence(host_mesh):
    """One fixed-seed query through four layers — executor (optimized AND
    naive), join_distributed_multi, raw hash_join_multi — returns the
    same aggregate, equal to the sort-merge oracle's."""
    cat, big, dup_small = _dup_catalog()
    k = np.asarray(big.column("k"))
    w = np.asarray(big.column("w"))
    sk = np.asarray(dup_small.column("k"))
    expected_pairs = sort_merge_pairs(sk, k)
    expected_sum = int(w[expected_pairs[:, 0]].sum())

    # layer 1: executor, optimized (duplicate build side) and naive
    ex = Executor(cat)
    q = Q.scan("big").join(Q.scan("dup_small"), on="k").sum("w")
    assert int(ex.execute(q).value) == expected_sum
    assert int(ex.execute(q, optimized=False).value) == expected_sum

    # layer 2: distributed operator
    p = make_plan(host_mesh, "model", "partitioned")
    l_idx, s_idx, totals, over = join_core.join_distributed_multi(
        jnp.asarray(sk), jnp.asarray(k), p,
        max_out_per_shard=ref.next_pow2(len(expected_pairs) + 1))
    assert not bool(np.asarray(over).any())
    got = pairs_of(l_idx, s_idx)
    np.testing.assert_array_equal(got, expected_pairs)
    assert int(w[got[:, 0]].sum()) == expected_sum

    # layer 3: raw kernel
    res = hash_join_multi(jnp.asarray(sk), jnp.asarray(k),
                          max_out=ref.next_pow2(len(expected_pairs) + 1))
    got = pairs_of(res.l_idx, res.s_idx)
    np.testing.assert_array_equal(got, expected_pairs)
    assert int(w[got[:, 0]].sum()) == expected_sum


@settings(max_examples=6, deadline=None)
@given(lo=st.integers(0, 40), width=st.integers(0, 20),
       seed=st.integers(0, 2 ** 16))
def test_executor_duplicate_join_with_filter_matches_numpy(lo, width, seed):
    """Property over the whole stack: filtered duplicate-keyed join
    aggregates match a pure NumPy evaluation."""
    r = np.random.default_rng(seed)
    big = Table.from_arrays("big", {
        "k": r.integers(0, 200, size=2048).astype(np.int32),
        "v": r.integers(0, 60, size=2048).astype(np.int32),
        "w": r.integers(1, 9, size=2048).astype(np.int32)})
    dup = Table.from_arrays("dup", {
        "k": r.integers(0, 40, size=256).astype(np.int32)})
    cat = Catalog.from_tables(big, dup)
    ex = Executor(cat)
    q = (Q.scan("big").join(Q.scan("dup"), on="k")
          .filter("v", lo, lo + width).sum("w"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        got = ex.execute(q).value
        naive = ex.execute(q, optimized=False).value
    k, v, w = (np.asarray(big.column(c)) for c in ("k", "v", "w"))
    match_cnt = np.asarray([(np.asarray(dup.column("k")) == key).sum()
                            for key in k])
    mask = (v >= lo) & (v <= lo + width)
    expected = int((w * match_cnt * mask).sum())
    assert int(got) == int(naive) == expected
