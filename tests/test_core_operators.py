"""Scale-out operators + columnar engine + channel planner."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.columnar import engine, udf
from repro.columnar.table import Table
from repro.core.channels import fpga_bandwidth_model, plan, tpu_bandwidth_model
from repro.core.join import join_distributed
from repro.core.selection import select_distributed
from repro.core.sgd_glm import HyperParams, blockwise_train, hyperparam_search
from repro.core.shim import VMEM_BYTES, plan_matmul_block, plan_stream_block
from repro.kernels.sgd.ref import loss_ref, sgd_ref


def test_fig2_bandwidth_model_reproduces_paper_points():
    # Fig. 2 anchor points from the paper text
    assert fpga_bandwidth_model(32, 256, 200) == pytest.approx(190.0, rel=.02)
    assert fpga_bandwidth_model(32, 256, 300) == pytest.approx(282.0, rel=.02)
    assert fpga_bandwidth_model(32, 0, 200) == pytest.approx(14.0, rel=.05)
    assert fpga_bandwidth_model(32, 0, 300) == pytest.approx(21.0, rel=.05)
    # collapse is monotone in separation
    bws = [fpga_bandwidth_model(32, s, 200) for s in (0, 64, 128, 256)]
    assert bws == sorted(bws)


def test_tpu_partitioned_vs_congested():
    assert tpu_bandwidth_model(16, True) > 10 * tpu_bandwidth_model(16, False)


def test_shim_plans_fit_vmem():
    for n in (1 << 12, 1 << 20, 1 << 26):
        p = plan_stream_block(n, 4)
        assert p.fits and p.block[0] % 1024 == 0
    for mnk in ((4096, 4096, 4096), (128, 128, 128), (8192, 512, 65536)):
        p = plan_matmul_block(*mnk)
        assert p.vmem_bytes <= VMEM_BYTES
        assert all(b % 128 == 0 for b in p.block)


def test_select_distributed(host_mesh, rng):
    p = plan(host_mesh, "model")
    x = jnp.asarray(rng.integers(0, 1000, size=4096), jnp.int32)
    idx, counts = select_distributed(x, 100, 200, p, block=512)
    exp = ((np.asarray(x) >= 100) & (np.asarray(x) <= 200))
    assert int(counts.sum()) == int(exp.sum())
    got = np.asarray(idx)
    np.testing.assert_array_equal(np.sort(got[got >= 0]), np.nonzero(exp)[0])


def test_select_range_on_explicit_mesh_runs_outside_mesh_context(rng):
    """``jax.make_mesh`` builds Explicit axes.  A column placed on one
    carries the axis in its type, and the eager selection's compaction
    (``jnp.nonzero``) then refused it outside a mesh context.  Placement
    goes through the channel plan's Auto-axis mesh, so it runs."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    v = rng.integers(0, 1000, size=4096).astype(np.int32)
    t = Table.from_arrays("t", {"v": v}).place(plan(mesh, "model"))
    sel = engine.select_range(t, "v", 100, 200)
    np.testing.assert_array_equal(np.asarray(sel.column("idx")),
                                  np.flatnonzero((v >= 100) & (v <= 200)))


@settings(max_examples=8, deadline=None)
@given(n_s=st.integers(10, 12000), seed=st.integers(0, 2**16))
def test_join_distributed_multipass(host_mesh, n_s, seed):
    """Covers both the single-pass and the Fig. 8b multi-pass regime."""
    r = np.random.default_rng(seed)
    p = plan(host_mesh, "model")
    s = jnp.asarray(r.choice(10**6, size=n_s, replace=False), jnp.int32)
    l = jnp.asarray(r.integers(0, 10**6, size=4096), jnp.int32)
    s_idx, total = join_distributed(s, l, p)
    expected = np.isin(np.asarray(l), np.asarray(s))
    assert int(total) == int(expected.sum())


def test_hyperparam_search_fig10(host_mesh, rng):
    p = plan(host_mesh, "model")
    m, n = 256, 64
    w = rng.normal(size=n)
    a = jnp.asarray(rng.uniform(-1, 1, size=(m, n)), jnp.float32)
    b = jnp.asarray((np.asarray(a) @ w > 0).astype(np.float32))
    grid = [HyperParams(lr, l2) for lr in (0.01, 0.1) for l2 in (0.0, 1e-3)]
    xs, losses = hyperparam_search(a, b, grid, p, epochs=4)
    assert xs.shape == (4, n) and losses.shape == (4,)
    # the search finds a config better than the worst by a margin
    assert float(losses.min()) < float(losses.max())
    assert float(losses.min()) < 0.6


def test_blockwise_scan_converges(rng):
    m, n = 256, 64
    w = rng.normal(size=n)
    a = jnp.asarray(rng.uniform(-1, 1, size=(m, n)), jnp.float32)
    b = jnp.asarray(np.asarray(a) @ w, jnp.float32)
    x = blockwise_train(a, b, jnp.zeros(n), lr=0.05, l2=0.0, block_rows=64,
                        epochs_per_block=2, passes=3)
    assert float(loss_ref(a, b, x, kind="ridge")) < \
        0.25 * float(loss_ref(a, b, jnp.zeros(n), kind="ridge"))


def test_columnar_pipeline(host_mesh, rng):
    p = plan(host_mesh, "model")
    n = 4096
    t = Table.from_arrays("t", {
        "k": rng.integers(0, 500, size=n).astype(np.int32),
        "v": rng.integers(1, 10, size=n).astype(np.int32)}).place(p)
    small = Table.from_arrays("s", {"k": np.arange(0, 1000, 2,
                                                   dtype=np.int32)})
    sel = udf.call("select_range", t, "v", 5, 9)
    assert sel.num_rows == int((np.asarray(t.column("v")) >= 5).sum())
    j = udf.call("join", t, small, "k")
    exp = int((np.asarray(t.column("k")) % 2 == 0).sum())
    assert j.num_rows == exp
    proj = engine.gather(t, j.column("l_idx"), ["v"])
    assert engine.aggregate_sum(proj, "v") > 0
