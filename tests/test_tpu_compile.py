"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler refuses what interpret mode accepts: blocks not aligned
to the lane tiling, scalar outputs outside SMEM, programs that do not fit
the device.  These tests compile, on one described v5e chip and at the
widths ``chip_smoke.py`` runs, every Pallas kernel the planner can
choose, plus the fused filter+join+aggregate step over the 2^27-row fact
table, whose footprint must fit the chip's 16 GiB.  Nothing runs, so they
say nothing about results or times.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.columnar import engine
from repro.columnar.table import Column, Table
from repro.kernels.selection import ops as sel_ops
from repro.kernels.sgd.sgd import sgd_block, sgd_block_wide, wide_rows
from repro.query import logical as L
from repro.query import pipeline as pl
from repro.query.cost import ColumnStats, CostModel, TableStats, PALLAS_OPS
from repro.query.optimize import optimize

ROWS = 1 << 27                  # chip_smoke's lineitem
ORDERS = 1 << 23                # its unique-key dimension
SUPP_ROWS, SUPP_KEYS = 1 << 16, 1 << 12
HBM_BYTES = 16 << 30            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host, with JAX's persistent compilation cache
    off: a program compiled for a chip that is not attached cannot be
    read back from it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip."""
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, *shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("block", [1024, 4096])
def test_selection_kernel_compiles(one_chip, block):
    """The eager filter's kernel at the fact table's width: 1024 is the
    engine's block (``engine.select_range``), 4096 the kernel default."""
    fn = jax.jit(lambda x: sel_ops.select(x, 3, 9, block=block,
                                          impl="pallas"))
    assert _has_kernel(fn.lower(_sds(one_chip, ROWS)).compile())


@pytest.mark.parametrize("kind", ["logreg", "ridge"])
def test_sgd_kernel_compiles(one_chip, kind):
    """The streamed trainer's K-model kernel at the benchmark cell's
    shape: 11,000,000 feature-major rows of 28 features and the label,
    4 models, minibatches of 16, lr/l2 as operands."""
    m, d, k = 11_000_000, 28, 4
    fn = jax.jit(lambda data, lr, l2, x: sgd_block(data, lr, l2, x,
                                                   minibatch=16, kind=kind))
    compiled = fn.lower(_sds(one_chip, d + 1, m, dtype=jnp.float32),
                        _sds(one_chip, k, dtype=jnp.float32),
                        _sds(one_chip, k, dtype=jnp.float32),
                        _sds(one_chip, k, d, dtype=jnp.float32)).compile()
    assert _has_kernel(compiled)
    assert "sgd_block" in compiled.as_text()


@pytest.mark.parametrize("kind", ["logreg", "ridge"])
def test_sgd_wide_kernel_compiles(one_chip, kind):
    """The wide form at the ``eps.train`` cell's shape: 400,000
    feature-major rows of 2,000 features and the label, padded to 2,048
    rows, 4 models, 10 epochs folded into the grid.  Its blocks, the
    transposed tile and the models have to fit VMEM."""
    m, d, k = 400_000, 2000, 4
    fn = jax.jit(lambda data, lr, l2, x: sgd_block_wide(
        data, lr, l2, x, minibatch=16, epochs=10, kind=kind))
    compiled = fn.lower(_sds(one_chip, wide_rows(d), m, dtype=jnp.float32),
                        _sds(one_chip, k, dtype=jnp.float32),
                        _sds(one_chip, k, dtype=jnp.float32),
                        _sds(one_chip, k, d, dtype=jnp.float32)).compile()
    assert _has_kernel(compiled)
    assert "sgd_block_wide" in compiled.as_text()


def test_trainer_takes_the_kernel_on_one_tpu_device(topo):
    """The streamed trainer's dispatch rule: a plan over one TPU device
    runs the kernel; a plan over the CPU, or over several devices (the
    dataset replicated, a custom call GSPMD would have to partition),
    runs the XLA loop."""
    def mesh(devices):
        return Mesh(np.array(devices), ("engine",))

    assert engine.sgd_kernel_applies(mesh(topo.devices[:1]))
    assert not engine.sgd_kernel_applies(mesh(topo.devices[:4]))
    assert not engine.sgd_kernel_applies(mesh(jax.devices("cpu")[:1]))


def test_planner_offers_pallas_only_where_a_kernel_compiles():
    """The join probes' kernels are refused by the TPU compiler (1-D
    in-kernel gather), so joins price XLA alone even where pallas is
    allowed; filters keep the kernel compiled above."""
    model = CostModel(1, allow_pallas=True)
    assert model.impls("filter") == ("xla", "pallas")
    for op in ("join", "join_multi", "scan", "aggregate", "train_glm"):
        assert model.impls(op) == ("xla",), op
    assert PALLAS_OPS == {"filter", "filter_project"}


def _stats():
    return {
        "lineitem": TableStats(ROWS, ("orderkey", "quantity", "suppkey"), {
            "orderkey": ColumnStats(0, 2 * ORDERS - 1, 2 * ORDERS),
            "quantity": ColumnStats(1, 50, 50),
            "suppkey": ColumnStats(0, SUPP_KEYS - 1, SUPP_KEYS)}),
        "orders": TableStats(ORDERS, ("orderkey", "o_prio"), {
            "orderkey": ColumnStats(0, 2 * ORDERS - 2, ORDERS),
            "o_prio": ColumnStats(1, 5, 5)}),
        "supp": TableStats(SUPP_ROWS, ("suppkey", "s_w"), {
            "suppkey": ColumnStats(0, SUPP_KEYS - 1, 3000),
            "s_w": ColumnStats(0, 3, 4)}),
    }


@pytest.mark.parametrize("dim,on,value,n_build", [
    ("orders", "orderkey", "o_prio", ORDERS),        # unique keys
    ("supp", "suppkey", "s_w", SUPP_ROWS),           # duplicate keys
])
def test_fused_join_step_fits_one_chip(one_chip, dim, on, value, n_build):
    """The whole-table fused step (the batch path's single morsel) over
    2^27 probe rows compiles for one chip and fits its HBM."""
    stats = _stats()
    q = (L.Q.scan("lineitem").join(L.Q.scan(dim), on=on)
         .filter("quantity", 30, 49).sum(value))
    opt = optimize(q.node, stats, CostModel(1))
    splan = pl.analyze(opt, stats)
    assert splan is not None, "the plan must lower onto the fused pipeline"
    cp = pl.compile_pipeline(splan, ROWS, jnp.int32)
    (b,) = splan.breakers

    def build(keys, vals):
        t = Table(dim, {on: Column(keys, on), value: Column(vals, value)})
        return engine.join_build(t, b.on, b.value_cols,
                                 unique=b.unique).flat()

    builds = [_sds(one_chip, *a.shape, dtype=a.dtype) for a in
              jax.eval_shape(build, _sds(None, n_build), _sds(None, n_build))]
    args = ([_sds(one_chip, len(L.literals(opt))),
             _sds(one_chip), _sds(one_chip)] + builds
            + [_sds(one_chip, ROWS) for _ in splan.stream_cols])
    mem = cp.step.lower(*args).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, (used, mem)
