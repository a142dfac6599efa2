"""Model registry: one entry point per assigned architecture.

``bundle(cfg)`` returns the functional model (init/loss/prefill/decode) plus
``input_specs`` that build ShapeDtypeStruct stand-ins for every model input —
weak-type-correct, shardable, no device allocation — exactly what the
multi-pod dry-run lowers against.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models.sharding import (
    LogicalArray, ShardingRules, tree_sds, tree_shardings,
)
from repro.models import encdec, transformer
from repro.models.common import materialize


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ArchConfig
    init_specs: Callable          # (tp) -> LogicalArray tree
    loss_fn: Callable             # (params, batch, rules) -> (loss, metrics)
    prefill_fn: Callable          # (params, batch, caches, rules) -> (logits, caches)
    decode_fn: Callable           # (params, batch, caches, rules) -> (logits, caches)
    cache_specs: Callable         # (batch, max_len, tp, shape) -> LogicalArray tree
    count_units: Callable         # (shape, rules) -> [(name, fn, args, mult)]

    def materialize_params(self, rng, tp: int = 1):
        return materialize(self.init_specs(tp), rng)


def bundle(cfg: ArchConfig) -> ModelBundle:
    if cfg.is_enc_dec:
        return ModelBundle(
            cfg=cfg,
            init_specs=partial(encdec.init_params, cfg),
            loss_fn=partial(encdec.loss_fn, cfg),
            prefill_fn=partial(encdec.prefill_fn, cfg),
            decode_fn=partial(encdec.decode_fn, cfg),
            cache_specs=lambda b, s, tp, shape: encdec.cache_specs(
                cfg, b, s, tp, enc_len=shape.seq_len),
            count_units=partial(encdec.count_units, cfg),
        )
    return ModelBundle(
        cfg=cfg,
        init_specs=partial(transformer.init_params, cfg),
        loss_fn=partial(transformer.loss_fn, cfg),
        prefill_fn=partial(transformer.prefill_fn, cfg),
        decode_fn=partial(transformer.decode_fn, cfg),
        cache_specs=lambda b, s, tp, shape: transformer.cache_specs(
            cfg, b, s, tp),
        count_units=partial(transformer.count_units, cfg),
    )


# --------------------------------------------------------------------------- #
# input specs (ShapeDtypeStruct stand-ins, sharded)
# --------------------------------------------------------------------------- #

def _tok_spec(rules: ShardingRules, b: int, s: int):
    return jax.ShapeDtypeStruct((b, s), jnp.int32,
                                sharding=rules.named("batch", None))


def batch_specs(cfg: ArchConfig, shape: ShapeConfig, rules: ShardingRules):
    """ShapeDtypeStructs for the step's data inputs."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        specs = {"tokens": _tok_spec(rules, b, s),
                 "targets": _tok_spec(rules, b, s)}
    elif shape.kind == "prefill":
        specs = {"tokens": _tok_spec(rules, b, s)}
    else:  # decode: one new token against a seq_len KV cache
        specs = {"tokens": _tok_spec(rules, b, 1),
                 "pos": jax.ShapeDtypeStruct((), jnp.int32)}

    if cfg.family == "vlm" and shape.kind != "decode":
        specs["vision_embeds"] = jax.ShapeDtypeStruct(
            (b, min(cfg.n_vision_patches, s), cfg.d_model), jnp.bfloat16,
            sharding=rules.named("batch", None, None))
        pos_shape = (b, s, 3)
        specs["positions"] = jax.ShapeDtypeStruct(
            pos_shape, jnp.int32, sharding=rules.named("batch", None, None))
    if cfg.is_enc_dec and shape.kind != "decode":
        specs["frames"] = jax.ShapeDtypeStruct(
            (b, s, cfg.d_model), jnp.bfloat16,
            sharding=rules.named("batch", None, None))
    return specs


def cache_specs_sds(cfg: ArchConfig, shape: ShapeConfig, rules: ShardingRules):
    """Cache ShapeDtypeStructs for serve steps (None for train)."""
    if shape.kind == "train":
        return None
    tp = rules.mesh.shape.get("model", 1)
    mb = bundle(cfg)
    tree = mb.cache_specs(shape.global_batch, shape.seq_len, tp, shape)
    return tree_sds(tree, rules)


def make_batch(cfg: ArchConfig, shape: ShapeConfig, rules: ShardingRules,
               rng: jax.Array):
    """Real (small) arrays matching batch_specs — for smoke tests."""
    specs = batch_specs(cfg, shape, rules)
    out = {}
    for k, sds in specs.items():
        if sds.dtype == jnp.int32:
            if k == "pos":
                out[k] = jnp.asarray(shape.seq_len - 1, jnp.int32)
            elif k == "positions":
                base = jnp.arange(sds.shape[1], dtype=jnp.int32)
                out[k] = jnp.broadcast_to(base[None, :, None], sds.shape)
            else:
                rng, sub = jax.random.split(rng)
                out[k] = jax.random.randint(sub, sds.shape, 0,
                                            cfg.vocab_size, jnp.int32)
        else:
            rng, sub = jax.random.split(rng)
            out[k] = (0.02 * jax.random.normal(sub, sds.shape,
                                               jnp.float32)).astype(sds.dtype)
    return out


def make_cache(cfg: ArchConfig, shape: ShapeConfig, rules: ShardingRules):
    """Zero-filled caches for smoke tests."""
    specs = cache_specs_sds(cfg, shape, rules)
    if specs is None:
        return None
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
