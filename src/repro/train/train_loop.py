"""Train/serve step factories — the functions the dry-run lowers and the
launcher executes.  The same ``train_step`` compiles on the single-CPU smoke
mesh and the 512-chip production mesh; only the shardings differ.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models.sharding import ShardingRules, tree_sds
from repro.models import registry
from repro.train.optimizer import AdamW, PaperSGD


def make_train_step(mb: registry.ModelBundle, rules: ShardingRules, opt,
                    **loss_kw) -> Callable:
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            mb.loss_fn, has_aux=True)(params, batch, rules, **loss_kw)
        new_params, new_state, gnorm = opt.update(grads, opt_state, params)
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["grad_norm"] = gnorm
        return new_params, new_state, metrics
    return train_step


def make_prefill_step(mb: registry.ModelBundle, rules: ShardingRules,
                      **kw) -> Callable:
    def prefill_step(params, batch, caches):
        return mb.prefill_fn(params, batch, caches, rules, **kw)
    return prefill_step


def make_decode_step(mb: registry.ModelBundle, rules: ShardingRules,
                     **kw) -> Callable:
    def decode_step(params, batch, caches):
        logits, new_caches = mb.decode_fn(params, batch, caches, rules, **kw)
        # greedy token for the serving loop (sampling is the caller's)
        next_tok = jnp.argmax(logits[..., :mb.cfg.vocab_size], axis=-1)
        return next_tok.astype(jnp.int32), logits, new_caches
    return decode_step


def step_and_specs(cfg: ArchConfig, shape: ShapeConfig, rules: ShardingRules,
                   *, optimizer=None, **kw):
    """(step_fn, example_args as ShapeDtypeStructs) for one dry-run cell."""
    mb = registry.bundle(cfg)
    tp = rules.mesh.shape.get("model", 1)
    params_sds = tree_sds(mb.init_specs(tp), rules)
    batch_sds = registry.batch_specs(cfg, shape, rules)

    if shape.kind == "train":
        opt = optimizer or AdamW()
        opt_sds = tree_sds(opt.init_specs(mb.init_specs(tp)), rules)
        fn = make_train_step(mb, rules, opt, **kw)
        return fn, (params_sds, opt_sds, batch_sds)
    cache_sds = registry.cache_specs_sds(cfg, shape, rules)
    if shape.kind == "prefill":
        fn = make_prefill_step(mb, rules, **kw)
    else:
        fn = make_decode_step(mb, rules, **kw)
    return fn, (params_sds, batch_sds, cache_sds)
