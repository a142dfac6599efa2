"""Training substrate: optimizer, checkpoint/restart, fault tolerance,
gradient compression, sharding rules, data pipeline determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.configs import SHAPES, get_arch, smoke_config
from repro.distributed import compression
from repro.models.sharding import resolve, tree_sds, validate_divisibility
from repro.launch.mesh import make_production_mesh  # noqa: F401 (not built here)
from repro.models import registry
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, Pipeline, synthetic_batch
from repro.train.fault_tolerance import (
    Heartbeat, StragglerDetector, plan_elastic_mesh, run_with_restarts,
)
from repro.train.optimizer import AdamW, PaperSGD, global_norm
from repro.train.train_loop import make_train_step


def test_adamw_reduces_loss(host_mesh):
    cfg = smoke_config(get_arch("stablelm-3b"))
    rules = resolve(cfg, host_mesh)
    mb = registry.bundle(cfg)
    opt = AdamW(lr=5e-3, warmup=5)
    with jax.set_mesh(host_mesh):
        params = mb.materialize_params(jax.random.key(0), tp=1)
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(mb, rules, opt))
        dc = DataConfig(cfg.vocab_size, 64, 4, seed=3)
        losses = []
        for i in range(25):
            params, opt_state, m = step(params, opt_state,
                                        synthetic_batch(dc, 0))  # same batch
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.5          # overfits one batch


def test_paper_sgd_optimizer_updates(host_mesh):
    cfg = smoke_config(get_arch("mamba2-780m"))
    rules = resolve(cfg, host_mesh)
    mb = registry.bundle(cfg)
    opt = PaperSGD(lr=0.01)
    with jax.set_mesh(host_mesh):
        params = mb.materialize_params(jax.random.key(0), tp=1)
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(mb, rules, opt))
        dc = DataConfig(cfg.vocab_size, 32, 2, seed=1)
        p2, _, m = step(params, opt_state, synthetic_batch(dc, 0))
        assert float(m["grad_norm"]) > 0
        diff = global_norm(jax.tree.map(lambda a, b: a - b, params, p2))
        assert float(diff) > 0


def test_checkpoint_roundtrip_and_retention(tmp_path):
    tree = {"w": jnp.arange(12.0).reshape(3, 4),
            "opt": {"m": jnp.ones((2,)), "count": jnp.asarray(7)}}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, tree, extra={"step": s}, keep=3)
    assert ckpt.latest_step(tmp_path) == 5
    restored, man = ckpt.restore(tmp_path, tree)
    assert man["extra"]["step"] == 5
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    # retention kept only 3
    import os
    kept = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert len(kept) == 3


def test_run_with_restarts_resumes_exactly(tmp_path):
    calls = []

    def step_fn(step, state):
        calls.append(step)
        return {"step": jnp.asarray(step + 1),
                "acc": state["acc"] + (step + 1)}

    state = {"step": jnp.asarray(0), "acc": jnp.asarray(0)}
    final, stats = run_with_restarts(
        step_fn, state, n_steps=30, ckpt_dir=str(tmp_path), ckpt_every=5,
        fail_at=[7, 22])
    assert int(final["step"]) == 30
    assert int(final["acc"]) == sum(range(1, 31))     # no lost/dup updates
    assert stats.restarts == 2
    assert stats.wasted_steps == 4                     # 7->5 and 22->20


def test_heartbeat_and_straggler():
    hb = Heartbeat(timeout_s=10)
    hb.beat("w0", t=100.0)
    hb.beat("w1", t=95.0)
    assert hb.dead(now=108.0) == ["w1"]
    sd = StragglerDetector(min_steps=4)
    for i in range(10):
        for w in ("a", "b", "c", "d"):
            sd.observe(w, 1.0 if w != "d" else 2.5)
    assert sd.stragglers() == ["d"]


def test_elastic_plan_respects_divisibility():
    p = plan_elastic_mesh(240, arch_divisors=(48, 16384))
    assert p.model == 16 and p.data == 15
    p = plan_elastic_mesh(240, arch_divisors=(28,))   # 28 heads -> tp=4
    assert 28 % p.model == 0 and p.chips <= 240


def test_elastic_restore_onto_host_mesh(host_mesh, tmp_path):
    """Save 'sharded' params, restore with explicit shardings for the
    current mesh (elastic re-sharding path)."""
    cfg = smoke_config(get_arch("llama3-8b"))
    rules = resolve(cfg, host_mesh)
    mb = registry.bundle(cfg)
    with jax.set_mesh(host_mesh):
        params = mb.materialize_params(jax.random.key(0), tp=1)
        ckpt.save(tmp_path, 1, params)
        from repro.models.sharding import tree_shardings
        shardings = tree_shardings(mb.init_specs(1), rules)
        restored, _ = ckpt.restore(tmp_path, params, shardings=shardings)
        n1 = float(global_norm(params))
        n2 = float(global_norm(restored))
        assert abs(n1 - n2) < 1e-3


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_int8_compression_error_feedback_unbiased(seed):
    """Property: with error feedback, the ACCUMULATED dequantized signal
    tracks the accumulated true gradient (residual stays bounded)."""
    r = np.random.default_rng(seed)
    g_total = np.zeros(64, np.float32)
    q_total = np.zeros(64, np.float32)
    res = jnp.zeros(64, jnp.float32)
    for _ in range(20):
        g = jnp.asarray(r.normal(size=64), jnp.float32)
        (q, scale), res = compression.compress_tree(g, res)
        q_total += np.asarray(compression.dequantize_int8(q, scale))
        g_total += np.asarray(g)
    # residual is bounded by one quantization step's worth
    assert float(jnp.abs(res).max()) < 0.2
    np.testing.assert_allclose(q_total, g_total, atol=0.2)


def test_data_pipeline_deterministic_resume():
    dc = DataConfig(1000, 32, 4, seed=9)
    p1 = Pipeline(dc)
    batches = [p1.next() for _ in range(5)]
    state = p1.state()
    p2 = Pipeline.resume(dc, {"step": 3, "seed": 9})
    b3 = p2.next()
    np.testing.assert_array_equal(np.asarray(b3["tokens"]),
                                  np.asarray(batches[3]["tokens"]))
    assert state["step"] == 5


def test_sharding_divisibility_all_archs(host_mesh):
    """Every arch's parameter tree passes divisibility validation on the
    production mesh shape (checked abstractly, no devices needed)."""
    import jax as _jax
    from repro.configs import all_archs
    # emulate the production mesh's shape logic with the host mesh axes
    for name, cfg in sorted(all_archs().items()):
        mb = registry.bundle(cfg)
        rules = resolve(cfg, host_mesh)
        problems = validate_divisibility(mb.init_specs(1), rules)
        assert not problems, (name, problems)
