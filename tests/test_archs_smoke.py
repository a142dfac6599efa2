"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward/train step on CPU, asserting output shapes and no NaNs; plus
serve-path consistency (prefill+decode == full forward) for cache archs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, SMOKE_SHAPE, ShapeConfig, all_archs, \
    get_arch, smoke_config
from repro.models.sharding import resolve
from repro.models import registry
from repro.models.common import logits_fn

ARCHS = sorted(all_archs())


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(host_mesh, arch):
    cfg = smoke_config(get_arch(arch))
    rules = resolve(cfg, host_mesh)
    mb = registry.bundle(cfg)
    with jax.set_mesh(host_mesh):
        params = mb.materialize_params(jax.random.key(0), tp=1)
        batch = registry.make_batch(cfg, SMOKE_SHAPE, rules,
                                    jax.random.key(1))
        loss, metrics = mb.loss_fn(params, batch, rules)
        assert loss.shape == ()
        assert not bool(jnp.isnan(loss))
        assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_serve_smoke(host_mesh, arch):
    cfg = smoke_config(get_arch(arch))
    rules = resolve(cfg, host_mesh)
    mb = registry.bundle(cfg)
    pshape = ShapeConfig("p", 32, 2, "prefill")
    dshape = ShapeConfig("d", 32, 2, "decode")
    with jax.set_mesh(host_mesh):
        params = mb.materialize_params(jax.random.key(0), tp=1)
        pb = registry.make_batch(cfg, pshape, rules, jax.random.key(1))
        caches = registry.make_cache(cfg, pshape, rules)
        logits, caches = mb.prefill_fn(params, pb, caches, rules)
        assert logits.shape[:2] == (2, 1)
        assert not bool(jnp.isnan(logits).any())
        db = registry.make_batch(cfg, dshape, rules, jax.random.key(2))
        dl, _ = mb.decode_fn(params, db, caches, rules)
        assert dl.shape[:2] == (2, 1)
        assert not bool(jnp.isnan(dl).any())


@pytest.mark.parametrize("arch", ["llama3-8b", "mamba2-780m", "stablelm-3b",
                                  "internlm2-20b", "qwen2-vl-7b"])
def test_prefill_decode_matches_full_forward(host_mesh, arch):
    """Teacher-forced consistency (MoE archs excluded: capacity drops make
    full-batch routing differ from incremental — verified separately)."""
    from repro.models import transformer
    cfg = smoke_config(get_arch(arch))
    rules = resolve(cfg, host_mesh)
    mb = registry.bundle(cfg)
    S = 16
    with jax.set_mesh(host_mesh):
        params = mb.materialize_params(jax.random.key(0), tp=1)
        toks = jax.random.randint(jax.random.key(1), (2, S), 0,
                                  cfg.vocab_size, jnp.int32)
        batch_extra = {}
        if cfg.family == "vlm":
            # fewer patches than the prefill prompt length (S - 1)
            ve = 0.02 * jax.random.normal(
                jax.random.key(2), (2, min(cfg.n_vision_patches, S // 2),
                                    cfg.d_model))
            batch_extra["vision_embeds"] = ve.astype(jnp.bfloat16)
        x, _, _ = transformer.forward(cfg, params, toks, rules, remat=False,
                                      **batch_extra)
        full_logits = logits_fn(params, x[:, -1:], cfg, rules)
        pshape = ShapeConfig("p", S, 2, "prefill")
        caches = registry.make_cache(cfg, pshape, rules)
        pb = {"tokens": toks[:, :S - 1], **batch_extra}
        _, caches = mb.prefill_fn(params, pb, caches, rules)
        dl, _ = mb.decode_fn(
            params, {"tokens": toks[:, S - 1:],
                     "pos": jnp.asarray(S - 1, jnp.int32)}, caches, rules)
        assert float(jnp.abs(full_logits - dl).max()) < 1e-3


@pytest.mark.parametrize("arch", ["llama3-8b", "jamba-v0.1-52b"])
def test_scan_equals_unrolled(host_mesh, arch):
    """The dry-run's exact_counts unrolled path is numerically identical to
    the production scan path."""
    cfg = smoke_config(get_arch(arch))
    rules = resolve(cfg, host_mesh)
    mb = registry.bundle(cfg)
    with jax.set_mesh(host_mesh):
        params = mb.materialize_params(jax.random.key(0), tp=1)
        batch = registry.make_batch(cfg, SMOKE_SHAPE, rules,
                                    jax.random.key(1))
        l1, _ = mb.loss_fn(params, batch, rules, exact_counts=False)
        l2, _ = mb.loss_fn(params, batch, rules, exact_counts=True)
        assert abs(float(l1) - float(l2)) < 1e-3   # bf16 reduction-order noise
