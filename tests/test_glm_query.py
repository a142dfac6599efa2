"""Differential suite for the in-engine GLM path (paper §VI, workload 3).

The invariant everything here leans on: on-device f32 SGD is
deterministic, and the streamed trainer reproduces the whole-column
minibatch sequence EXACTLY — pad rows contribute zero gradient and the
final morsel pads only to the next minibatch multiple — so weights are
compared with ``assert_array_equal`` (bit-identity), not allclose.
Losses fold row terms in a different order, so they keep a tolerance.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.columnar.table import Column, Table
from repro.core.sgd_glm import HyperParams
from repro.query import logical as L
from repro.query.cost import CostModel, plan_physical
from repro.query.exec import Catalog, Executor, PlacementCapacityError
from repro.query.serve import QueryServer
from repro.query.tiering import TierBudgets

FEATS = ("f0", "f1", "f2")
GRID = (HyperParams(0.1, 0.0), HyperParams(0.05, 0.01))


def make_table(m: int, seed: int = 0, with_key: bool = True) -> Table:
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(m, len(FEATS))).astype(np.float32)
    w = np.array([1.0, -2.0, 0.5], np.float32)
    y = (1.0 / (1.0 + np.exp(-(a @ w))) > 0.5).astype(np.float32)
    cols = {f: Column(jnp.asarray(a[:, i]), f)
            for i, f in enumerate(FEATS)}
    cols["y"] = Column(jnp.asarray(y), "y")
    if with_key:
        cols["k"] = Column(jnp.arange(m, dtype=jnp.int32), "k")
    return Table("train", cols)


def train_q(kind="logreg", epochs=3, grid=GRID):
    return L.Q.scan("train").train_glm(list(FEATS), "y", list(grid),
                                       kind=kind, epochs=epochs)


def fresh_executor(m: int = 512, seed: int = 0, **kw) -> Executor:
    return Executor(Catalog.from_tables(make_table(m, seed)), **kw)


# --------------------------------------------------------------------------- #
# streamed trainer == whole-column oracle, bit-identical


@pytest.mark.parametrize("m", [512, 500, 97, 10])
@pytest.mark.parametrize("kind", ["logreg", "ridge"])
def test_streamed_train_matches_eager_bitwise(m, kind):
    """The tentpole invariant: the morsel-streamed epoch loop reproduces
    the eager whole-column SGD weights exactly — including row counts
    that divide neither the morsel nor the minibatch."""
    ex = fresh_executor(m)
    q = train_q(kind=kind)
    streamed = ex.execute(q)
    assert streamed.mode == "stream"
    eager = ex.execute(q, optimized=False)       # the naive oracle
    np.testing.assert_array_equal(np.asarray(streamed.value[0]),
                                  np.asarray(eager.value[0]))
    np.testing.assert_allclose(np.asarray(streamed.value[1]),
                               np.asarray(eager.value[1]),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("morsel_rows", [64, 96, 130, 512])
def test_streamed_train_morsel_size_invariant(morsel_rows):
    """Weights are independent of the streaming granularity (the carry
    threads the same global minibatch sequence through any morsel cut,
    aligned down to a minibatch multiple)."""
    ex = fresh_executor(500)
    q = train_q()
    base = ex.execute(q, morsel_rows=None)
    got = ex.execute(q, morsel_rows=morsel_rows)
    np.testing.assert_array_equal(np.asarray(base.value[0]),
                                  np.asarray(got.value[0]))


def test_filtered_train_matches_eager_bitwise():
    """A filter below the train root materializes once, then streams:
    same weights as the fully eager filtered train."""
    ex = fresh_executor(512)
    q = (L.Q.scan("train").filter("k", 0, 399)
         .train_glm(list(FEATS), "y", list(GRID), epochs=3))
    streamed = ex.execute(q)
    assert streamed.mode == "stream"
    eager = ex.execute(q, optimized=False)
    np.testing.assert_array_equal(np.asarray(streamed.value[0]),
                                  np.asarray(eager.value[0]))


def test_eager_mode_follows_planned_placement():
    """The satellite bugfix: forced-eager training runs under the
    placement the cost model chose (explain() and execution agree), not
    a hard-coded partitioned plan."""
    ex = fresh_executor(512)
    q = train_q()
    r = ex.execute(q, mode="eager")
    assert r.physical.op == "train_glm"
    assert r.physical.placement in ex.plans          # an executable plan
    assert r.physical.placement in r.explain()
    # and the choice is the priced argmin over the alternatives
    alts = r.physical.alternatives
    best = min(alts, key=alts.get)
    assert best.split("/")[1] == r.physical.placement \
        or best.startswith("shard/")


# --------------------------------------------------------------------------- #
# fingerprints: models are discriminated by everything that shapes them


def test_model_fingerprints_discriminate():
    ex = fresh_executor(512)
    variants = [
        train_q(),
        train_q(epochs=4),
        train_q(kind="ridge"),
        train_q(grid=(HyperParams(0.1, 0.0),)),
        (L.Q.scan("train").filter("k", 0, 255)
         .train_glm(list(FEATS), "y", list(GRID), epochs=3)),
    ]
    fps = [ex.fingerprint_of(v.node) for v in variants]
    assert len(set(fps)) == len(fps)
    # and a mutation moves every one of them
    ex.catalog.update_column(
        "train", "y",
        jnp.asarray(1.0 - np.asarray(
            ex.catalog.tables["train"].column("y"))))
    assert ex.fingerprint_of(variants[0].node) != fps[0]


# --------------------------------------------------------------------------- #
# cached-model serving


@pytest.mark.requires_cache
def test_score_after_train_hits_cached_model():
    ex = fresh_executor(512, cache_bytes=1 << 24)
    q = train_q()
    trained = ex.execute(q)
    score = L.Q.scan("train").score_glm(q)
    r = ex.execute(score)
    assert ex.model_hits == 1
    # the served scores ARE the cached best model applied to the rows
    xs, losses = trained.value
    x = np.asarray(xs)[int(np.argmin(np.asarray(losses)))]
    feats = np.stack([np.asarray(ex.catalog.tables["train"].column(f))
                      for f in FEATS], axis=1)
    np.testing.assert_allclose(
        np.asarray(r.value.column("score")),
        1.0 / (1.0 + np.exp(-(feats @ x))), rtol=1e-5, atol=1e-6)


@pytest.mark.requires_cache
def test_score_without_train_trains_then_serves():
    """A score submitted before any train triggers exactly one fresh
    train (admitted as a model), and the next score serves warm."""
    ex = fresh_executor(512, cache_bytes=1 << 24)
    score = L.Q.scan("train").filter("k", 100, 400).score_glm(train_q())
    r1 = ex.execute(score)
    assert ex.model_hits == 0                  # cold: trained inline
    ex.execute(L.Q.scan("train").filter("k", 0, 50).score_glm(train_q()))
    assert ex.model_hits == 1                  # warm: same model serves
    assert r1.value.num_rows == 301


@pytest.mark.requires_cache
def test_mutation_invalidates_cached_model():
    ex = fresh_executor(512, cache_bytes=1 << 24)
    q = train_q()
    ex.execute(q)
    score = L.Q.scan("train").score_glm(q)
    ex.execute(score)
    assert ex.model_hits == 1
    y = np.asarray(ex.catalog.tables["train"].column("y"))
    ex.catalog.update_column("train", "y", jnp.asarray(1.0 - y))
    r = ex.execute(score)                      # fingerprint moved: retrain
    assert ex.model_hits == 1
    # and the fresh score reflects the mutated labels, differentially:
    oracle = ex.execute(score, optimized=False)
    np.testing.assert_array_equal(np.asarray(r.value.column("score")),
                                  np.asarray(oracle.value.column("score")))


def test_score_raw_fingerprint_requires_cached_model():
    ex = fresh_executor(512, cache_bytes=1 << 24)
    score = L.Q.scan("train").score("deadbeef", list(FEATS))
    with pytest.raises(KeyError):
        ex.execute(score)


@pytest.mark.requires_cache
def test_served_dashboard_reports_model_hits():
    ex = fresh_executor(512, cache_bytes=1 << 24)
    srv = QueryServer(ex)
    q = train_q()
    srv.submit(q)
    srv.drain()
    score = L.Q.scan("train").filter("k", 0, 255).score_glm(q)
    srv.submit(score)
    out = srv.drain()
    st = srv.stats()
    assert st["n_model_hits"] == 1
    assert next(iter(out.values())).num_rows == 256
    # cache accounting knows how many bytes the models occupy
    assert ex.cache.stats_dict()[
        "semantic_cache_bytes_by_kind"].get("model", 0) > 0


# --------------------------------------------------------------------------- #
# tiered placement: over-budget training sets stream out of core


def test_over_budget_train_spills_and_matches_oracle():
    m = 4096
    oracle = Executor(Catalog.from_tables(make_table(m))) \
        .execute(train_q(epochs=2), optimized=False).value
    col_bytes = m * 4
    budgets = TierBudgets(device=col_bytes // 4,       # 4x over budget
                          host=1 << 22, disk=1 << 26)
    ex = Executor(Catalog.from_tables(make_table(m)), tier_budgets=budgets)
    r = ex.execute(train_q(epochs=2))
    assert r.mode == "stream"
    assert ex.stats_dict()["spilled_columns"] > 0
    tiers = {ex.catalog.tables["train"].columns[c].tier
             for c in FEATS + ("y",)}
    assert tiers != {"device"}
    np.testing.assert_array_equal(np.asarray(r.value[0]),
                                  np.asarray(oracle[0]))


def test_over_budget_train_still_fails_beyond_disk():
    m = 4096
    budgets = TierBudgets(device=m, host=m, disk=m)    # nothing fits
    ex = Executor(Catalog.from_tables(make_table(m)), tier_budgets=budgets)
    with pytest.raises(PlacementCapacityError):
        ex.execute(train_q(epochs=2))


# --------------------------------------------------------------------------- #
# sharded planning and execution


@pytest.mark.requires_mesh
def test_sharded_pricing_offers_replicated_alternative():
    ex = fresh_executor(512, shards=2)
    _, phys = ex.plan(train_q().node)
    assert "shard/replicated" in phys.alternatives
    assert "xla/congested" in phys.alternatives


def test_sharded_train_prices_replication_below_congested():
    """Fig. 10a's replication trade on an 8-shard mesh, in the model: for
    an 8-model search the planner offers ``shard/replicated`` and prices
    it below every job streaming one congested copy.  Pure cost-model
    arithmetic — no devices needed."""
    grid = [HyperParams(0.1 / (i + 1), 0.001 * i) for i in range(8)]
    cat = Catalog.from_tables(make_table(8192))
    phys = plan_physical(train_q(epochs=2, grid=grid).node, cat.stats,
                         CostModel(8, n_shards=8))
    alts = phys.alternatives
    assert alts["shard/replicated"] < alts["xla/congested"], alts


@pytest.mark.requires_mesh
def test_sharded_train_matches_single_device_bitwise():
    oracle = fresh_executor(512).execute(train_q(), optimized=False).value
    ex = fresh_executor(512, shards=2)
    r = ex.execute(train_q())
    np.testing.assert_array_equal(np.asarray(r.value[0]),
                                  np.asarray(oracle[0]))
