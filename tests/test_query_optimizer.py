"""Optimizer rewrites + bandwidth cost model unit tests."""
import json

import jax
import numpy as np
import pytest

from repro.columnar.table import Table
from repro.core.channels import device_peaks
from repro.core.join import HT_CAPACITY
from repro.query import (
    Aggregate, Catalog, CostModel, Filter, FilterProject, Join, Project, Q,
    Scan, column_placements, estimate_rows, load_calibration, optimize,
    plan_physical,
)
from repro.query.optimize import (
    choose_build_side, fuse_filter_project, prune_columns, push_down_filters,
)


@pytest.fixture()
def catalog(rng):
    n = 4096
    big = Table.from_arrays("big", {
        "k": rng.permutation(n).astype(np.int32),       # unique join key
        "v": rng.integers(0, 100, size=n).astype(np.int32),
        "w": rng.integers(0, 100, size=n).astype(np.int32)})
    small = Table.from_arrays("small", {
        "k": np.arange(0, 512, dtype=np.int32),
        "x": np.arange(0, 512, dtype=np.int32)})
    return Catalog.from_tables(big, small)


def test_filter_pushes_below_join(catalog):
    q = (Q.scan("big").join(Q.scan("small"), on="k")
          .filter("v", 10, 20).sum("w"))
    out = push_down_filters(q.node, catalog.stats)
    # Aggregate -> Join -> (Filter(big scan), small scan)
    assert isinstance(out, Aggregate)
    assert isinstance(out.child, Join)
    assert isinstance(out.child.left, Filter)
    assert out.child.left.column == "v"
    assert isinstance(out.child.left.child, Scan)


def test_filter_on_join_key_stays_put(catalog):
    # the key exists on BOTH sides: ambiguous, must not move
    q = (Q.scan("big").join(Q.scan("small"), on="k")
          .filter("k", 10, 20).sum("w"))
    out = push_down_filters(q.node, catalog.stats)
    assert isinstance(out.child, Filter)


def test_projection_pruning_narrows_scans(catalog):
    q = (Q.scan("big").join(Q.scan("small"), on="k").sum("w"))
    out = prune_columns(q.node, catalog.stats)
    scans = {n.table: n for n in _walk(out) if isinstance(n, Scan)}
    assert scans["big"].columns == ("k", "w")      # v never read
    assert scans["small"].columns == ("k",)        # x never read


def test_build_side_swaps_to_smaller(catalog):
    # small (512) written as the PROBE side: the optimizer must swap
    q = Q.scan("small").join(Q.scan("big"), on="k").sum("x")
    out = choose_build_side(q.node, catalog.stats)
    join = out.child
    assert isinstance(join.left, Scan) and join.left.table == "big"
    assert join.right.table == "small"


def test_duplicate_keyed_side_becomes_build_side(rng):
    """The multi-match kernel lifts the old uniqueness veto: the smaller
    side builds even when its key carries duplicates (formerly refused),
    and the physical plan prices it as the multi-match op."""
    dup = Table.from_arrays("dup", {
        "k": rng.integers(0, 50, size=1024).astype(np.int32)})
    uni = Table.from_arrays("uni", {
        "k": np.arange(0, 2048, dtype=np.int32)})
    cat = Catalog.from_tables(dup, uni)
    q = Q.scan("uni").join(Q.scan("dup"), on="k").count("k")
    out = choose_build_side(q.node, cat.stats)
    join = out.child
    assert join.left.table == "uni"        # larger side probes
    assert join.right.table == "dup"       # smaller duplicate side builds
    phys = plan_physical(out, cat.stats, CostModel(4))
    ops = {p.op for p in _walk_phys(phys)}
    assert "join_multi" in ops             # priced as the duplicate probe


def test_chain_length_prices_duplicate_probe(rng):
    """A duplicate-heavy build side costs more than a unique one of the
    same row count: the expected chain length multiplies the probe work
    and the pair-list output is materialized bytes."""
    from repro.query.cost import expected_chain_length
    dup = Table.from_arrays("dup", {
        "k": rng.integers(0, 32, size=1024).astype(np.int32)})
    uni = Table.from_arrays("uni", {
        "k": np.arange(0, 1024, dtype=np.int32)})
    big = Table.from_arrays("probe", {
        "k": rng.integers(0, 1024, size=8192).astype(np.int32),
        "w": rng.integers(0, 9, size=8192).astype(np.int32)})
    cat = Catalog.from_tables(dup, uni, big)
    chain = expected_chain_length(Q.scan("dup").node, "k", cat.stats)
    assert chain > 8.0                         # ~1024/32 duplicates per key
    assert expected_chain_length(Q.scan("uni").node, "k",
                                 cat.stats) == pytest.approx(1.0)
    model = CostModel(4)
    q_dup = Q.scan("probe").join(Q.scan("dup"), on="k").sum("w")
    q_uni = Q.scan("probe").join(Q.scan("uni"), on="k").sum("w")
    cost_dup = [p for p in _walk_phys(plan_physical(q_dup.node, cat.stats,
                                                    model))
                if p.op == "join_multi"][0].cost_s
    cost_uni = [p for p in _walk_phys(plan_physical(q_uni.node, cat.stats,
                                                    model))
                if p.op == "join"][0].cost_s
    assert cost_dup > cost_uni


def test_filter_project_fusion(catalog):
    q = Q.scan("big").filter("v", 0, 50).project("w")
    out = fuse_filter_project(q.node)
    assert isinstance(out, FilterProject)
    assert out.columns == ("w",) and out.column == "v"


def test_optimize_composes_all_rules(catalog):
    # filter keeps ~70% of big: the filtered side is still the larger one,
    # so big probes and small builds after the swap
    q = (Q.scan("small").join(Q.scan("big"), on="k")
          .filter("v", 10, 80).sum("w"))
    out = optimize(q.node, catalog.stats)
    join = out.child
    assert isinstance(join, Join)
    # swapped: big probes, small builds; filter pushed onto big's side
    assert isinstance(join.left, Filter) and join.left.column == "v"
    assert join.right.table == "small"
    assert join.right.columns == ("k",)


def test_estimate_rows_selectivity(catalog):
    full = estimate_rows(Q.scan("big").node, catalog.stats)
    half = estimate_rows(Q.scan("big").filter("v", 0, 49).node,
                         catalog.stats)
    assert full == 4096
    assert 0.3 * full < half < 0.7 * full


# --------------------------------------------------------------------------- #
# cost model

def test_partitioned_beats_congested_and_build_is_replicated(catalog):
    q = (Q.scan("big").join(Q.scan("small"), on="k")
          .filter("v", 10, 80).sum("w"))
    node = optimize(q.node, catalog.stats)
    model = CostModel(16)        # a 16-engine mesh: placement matters
    phys = plan_physical(node, catalog.stats, model)
    placements = column_placements(phys)
    assert placements[("big", "k")] == "partitioned"
    assert placements[("small", "k")] == "replicated"
    for p in _walk_phys(phys):
        if "xla/congested" in p.alternatives and \
                "xla/partitioned" in p.alternatives:
            assert p.alternatives["xla/partitioned"] < \
                p.alternatives["xla/congested"]


def test_multipass_join_block_count(catalog, rng):
    n_build = 3 * HT_CAPACITY + 17
    t = Table.from_arrays("huge_build", {
        "k": np.arange(n_build, dtype=np.int32)})
    cat = Catalog.from_tables(catalog.tables["big"], t)
    q = Q.scan("big").join(Q.scan("huge_build"), on="k").sum("w")
    # pin the join order (skip optimize): huge_build stays the build side
    phys = plan_physical(prune_columns(q.node, cat.stats), cat.stats,
                         CostModel(4))
    join = [p for p in _walk_phys(phys) if p.op == "join"][0]
    assert join.n_passes == 4


def test_impl_crossover_xla_small_pallas_large():
    model = CostModel(4, allow_pallas=True)
    tiny = model.stream_cost(1 << 10, impl="pallas", placement="partitioned")
    tiny_x = model.stream_cost(1 << 10, impl="xla", placement="partitioned")
    big = model.stream_cost(1 << 30, impl="pallas", placement="partitioned")
    big_x = model.stream_cost(1 << 30, impl="xla", placement="partitioned")
    assert tiny_x < tiny          # launch overhead dominates small inputs
    assert big < big_x            # streaming efficiency dominates large


def test_filter_kernel_priced_only_where_the_executor_runs_it(catalog):
    """explain names what runs: the eager executor hands the selection
    kernel only base columns partitioned into whole SELECT_BLOCK blocks
    per engine, and takes the XLA mask everywhere else."""
    model = CostModel(4, allow_pallas=True)

    def pallas_alts(p):
        return sorted(a for a in p.alternatives if a.startswith("pallas"))

    # big: 4096 rows = 4 engines x 1024
    big = plan_physical(Q.scan("big").filter("v", 0, 9).node,
                        catalog.stats, model)
    assert pallas_alts(big) == ["pallas/partitioned"]
    # small: 512 rows do not fill one block per engine
    small = plan_physical(Q.scan("small").filter("x", 0, 9).node,
                          catalog.stats, model)
    assert small.impl == "xla" and pallas_alts(small) == []
    # a filtered build side is replicated: the executor masks it
    join = plan_physical(Q.scan("small").join(
        Q.scan("big").filter("v", 0, 9), on="k").node, catalog.stats, model)
    (build_filter,) = [p for p in _walk_phys(join) if p.op == "filter"]
    assert build_filter.placement == "replicated"
    assert pallas_alts(build_filter) == []


def test_fpga_hardware_model_prices_alternatives():
    model = CostModel(32, hardware="fpga", allow_pallas=False)
    part = model.bandwidth_gbps("partitioned")
    cong = model.bandwidth_gbps("congested")
    assert part == pytest.approx(190.0, rel=0.02)   # paper Fig. 2 anchor
    assert cong == pytest.approx(14.0, rel=0.05)
    assert model.stream_cost(1 << 26, impl="xla", placement="partitioned") \
        < model.stream_cost(1 << 26, impl="xla", placement="congested")


def test_load_calibration_refuses_another_device(tmp_path):
    """A calibration prices only the device it was measured on: a file
    from another backend is refused, and a recorded device kind must
    match the running device's."""
    dev = jax.devices()[0]
    other = "tpu" if dev.platform != "tpu" else "cpu"
    path = tmp_path / "calibration.json"
    path.write_text(json.dumps({"backend": other, "backends": {}}))
    with pytest.raises(ValueError, match=f"backend '{other}'"):
        load_calibration(str(path))
    path.write_text(json.dumps({"backend": dev.platform, "backends": {}}))
    assert load_calibration(str(path))["backend"] == dev.platform
    path.write_text(json.dumps({"backend": dev.platform,
                                "device_kind": "TPU v4", "backends": {}}))
    with pytest.raises(ValueError, match="TPU v4"):
        load_calibration(str(path))


def test_peak_rates_come_from_the_device_kind():
    assert device_peaks("TPU v5 lite").hbm_gbps == 819.0
    assert CostModel(1).peaks == device_peaks(jax.devices()[0].device_kind)
    with pytest.raises(ValueError, match="no peak rates"):
        device_peaks("TPU v0")


def _walk(node):
    yield node
    for c in node.children():
        yield from _walk(c)


def _walk_phys(p):
    yield p
    for c in p.children:
        yield from _walk_phys(c)
