"""The streamed trainer's staging program and its wide kernel form.

``engine.stage_morsel`` slices, casts and lays out every column of a
morsel in one jitted program, traced once per shape; on one TPU device a
table of ``sgd.WIDE_FROM_ROWS - 1`` features or more trains with
``sgd_block_wide``.  The CPU runs the kernel in interpret mode where a
test forces the kernel's rule.
"""
import os
import sys
from functools import partial

import jax.numpy as jnp
import numpy as np
import pytest

from repro.columnar import engine
from repro.columnar.table import Table
from repro.core.sgd_glm import HyperParams
from repro.kernels.sgd import sgd
from repro.query import Catalog, Executor, Q, QueryServer
from repro.query import telemetry as tm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _columns(rng, m, n, dtype=np.float32):
    cols = [rng.normal(size=m).astype(dtype) for _ in range(n)]
    cols.append((cols[0] > 0).astype(dtype))
    return [jnp.asarray(c) for c in cols]


@pytest.mark.parametrize("layout", ["rows", "features", "wide"])
@pytest.mark.parametrize("start,rows,rows_pad", [(0, 96, 96), (64, 40, 48)])
def test_stage_morsel_equals_the_per_column_stack(rng, layout, start, rows,
                                                  rows_pad):
    """Bit for bit what staging one column at a time gave: each column's
    rows from ``start``, cast to float32, zero-padded to the minibatch,
    stacked in the step's layout (the wide one padded to 128 rows)."""
    n = 130
    cols = _columns(rng, 160, n)
    cols[1] = cols[1].astype(jnp.bfloat16)          # a cast to float32
    got = engine.stage_morsel(tuple(cols), start, rows=rows,
                              rows_pad=rows_pad, layout=layout)
    vals = [jnp.pad(c[start:start + rows].astype(jnp.float32),
                    (0, rows_pad - rows)) for c in cols]
    if layout == "rows":
        want = (jnp.stack(vals[:-1], axis=1), vals[-1])
    else:
        want = jnp.stack(vals, axis=0)
        if layout == "wide":
            want = jnp.pad(want, ((0, sgd.wide_rows(n) - n - 1), (0, 0)))
        want = (want,)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _catalog(m, n, seed=0):
    rng = np.random.default_rng(seed)
    cols = {f"f{i}": rng.normal(size=m).astype(np.float32) for i in range(n)}
    cols["y"] = (cols["f0"] - cols["f1"] > 0).astype(np.float32)
    return Catalog.from_tables(Table.from_arrays("g", cols))


def _query(n, grid, epochs=1):
    return Q.scan("g").train_glm([f"f{i}" for i in range(n)], "y",
                                 [HyperParams(lr, l2) for lr, l2 in grid],
                                 epochs=epochs)


def test_staging_traces_once_across_queries():
    """Two queries of one shape, two grids: the staging program traces on
    the first and is reused by the second; each staging is a
    ``trainer.stage`` span with its columns and bytes, and the bytes are
    counted in ``trainer.staged_bytes``."""
    m, n = 1040, 5          # a shape no other test stages
    tel = tm.Telemetry(enabled=True)
    ex = Executor(_catalog(m, n), telemetry=tel)
    sizes = [engine.stage_morsel._cache_size()]
    for grid in ([(0.1, 0.0)], [(0.05, 0.01), (0.02, 0.0)]):
        ex.execute(_query(n, grid, epochs=2))
        sizes.append(engine.stage_morsel._cache_size())
    assert sizes[1] == sizes[0] + 1 and sizes[2] == sizes[1]
    stages = [e["args"] for e in tel.tracer.events
              if e["name"] == "trainer.stage"]
    # one morsel, staged once a query for both epochs and the losses
    assert len(stages) == 2
    assert {(a["cols"], a["bytes"]) for a in stages} == {(n + 1,
                                                          4 * (n + 1) * m)}
    assert ex.metrics.value("trainer.staged_bytes") == 2 * 4 * (n + 1) * m


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_one_morsel_is_staged_once_for_every_step(monkeypatch, impl):
    """One morsel is staged once a call and its copy reused by the other
    two epochs and the loss pass, and the weights and losses are still
    ``train_glm``'s over the whole columns; three morsels are staged for
    every step, as before, and none is reused.  ``pallas`` forces the
    kernel's rule and interprets ``sgd_block``."""
    m, n, epochs = 512, 3, 3
    feats = [f"f{i}" for i in range(n)]
    cat = _catalog(m, n, seed=1)
    t, plan = cat.tables["g"], Executor(cat).plans["partitioned"]
    grid = [HyperParams(0.1, 0.0), HyperParams(0.05, 0.01)]
    xs_full, losses_full = engine.train_glm(t, feats, "y", grid, plan,
                                            epochs=epochs)
    if impl == "pallas":
        monkeypatch.setattr(engine, "sgd_kernel_applies", lambda mesh: True)
        monkeypatch.setattr(engine, "sgd_block",
                            partial(engine.sgd_block, interpret=True))

    def run(morsel_rows):
        tel, reg = tm.Telemetry(enabled=True), tm.MetricsRegistry()
        out = engine.train_glm_stream(t, feats, "y", grid, plan,
                                      epochs=epochs, morsel_rows=morsel_rows,
                                      telemetry=tel, metrics=reg)
        names = [e["name"] for e in tel.tracer.events]
        return out, names, reg

    (xs, losses), names, reg = run(None)
    assert names.count("trainer.stage") == 1
    assert names.count("trainer.epoch_step") == epochs
    assert reg.value("trainer.stage_reuses") == epochs
    np.testing.assert_allclose(np.asarray(xs), np.asarray(xs_full),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(losses), np.asarray(losses_full),
                               rtol=1e-4)

    _, names, reg = run(208)            # morsels of 208, 208 and 96
    assert names.count("trainer.stage") == 3 * (epochs + 1)
    assert reg.value("trainer.stage_reuses") == 0


def test_served_wide_path_matches_the_bench_reference(monkeypatch):
    """``QueryServer`` -> ``train_glm`` over a 2,048 x 300 table on the
    wide kernel (its rule forced, interpreted) equals the chip benchmark's
    float32 ``highest``-precision reference within the ``eps.train``
    cell's limits."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import json

    from bench import harness
    from bench.queries import train_glm as ref_kind

    m, n = 2048, 300
    cat = _catalog(m, n, seed=3)
    # epsilon's first grid, its learning rates scaled to 300 features
    grid = [(lr * 2000 / n, l2) for lr, l2 in
            [(0.0028, 0.0), (0.0007, 0.001), (0.00028, 0.0001),
             (0.000014, 0.0)]]
    monkeypatch.setattr(engine, "sgd_kernel_applies", lambda mesh: True)
    monkeypatch.setattr(engine, "sgd_block_wide",
                        partial(engine.sgd_block_wide, interpret=True))
    tel = tm.Telemetry(enabled=True)
    srv = QueryServer(Executor(cat, telemetry=tel))
    qid = srv.submit(_query(n, grid, epochs=2).node)
    got = ref_kind.fetch(srv.drain()[qid])
    assert {e["args"]["impl"] for e in tel.tracer.events
            if e["name"] == "trainer.epoch_step"} == {"pallas_wide"}

    t = cat.tables["g"]
    data = harness.Data({"g": {c: t.columns[c].data for c in t.columns}})
    spec = {"table": "g", "label": "y", "minibatch": 16, "epochs": 2}
    feats = [f"f{i}" for i in range(n)]
    want = ref_kind.Reference(spec, data).answer(
        {"grid": grid, "features": feats})
    with open(os.path.join(ROOT, "bench", "traffic", "eps_train.json")) as f:
        limits = json.load(f)["check"]["limits"]
    errs = ref_kind.compare([got], [want])
    assert errs["weight_rel_err"] <= limits["weight_rel_err"], errs
    assert errs["loss_rel_err"] <= limits["loss_rel_err"], errs
    # the models moved: a weight vector left at zero would fail above
    assert np.all(np.linalg.norm(want[0], axis=1) > 0)
