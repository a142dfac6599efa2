"""Optimizers: AdamW (production default, f32 master + moments, ZeRO-sharded
by construction since params are TPxFSDP-sharded) and the paper's plain
minibatch SGD with L2 (Algorithm 3) as a selectable LM optimizer.

No optax dependency — hand-rolled, pytree-native.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models.sharding import LogicalArray


def _like(spec_tree, dtype):
    return jax.tree.map(
        lambda la: LogicalArray(la.shape, la.logical, dtype),
        spec_tree, is_leaf=lambda x: isinstance(x, LogicalArray))


def global_norm(tree):
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    warmup: int = 100

    def init_specs(self, param_specs) -> dict:
        return {
            "master": _like(param_specs, jnp.float32),
            "m": _like(param_specs, jnp.float32),
            "v": _like(param_specs, jnp.float32),
            "count": LogicalArray((), (), jnp.int32),
        }

    def init(self, params) -> dict:
        f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
        zeros = lambda t: jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), t)
        return {"master": f32(params), "m": zeros(params), "v": zeros(params),
                "count": jnp.zeros((), jnp.int32)}

    def _schedule(self, count):
        warm = jnp.minimum(count.astype(jnp.float32) / max(self.warmup, 1), 1.0)
        return self.lr * warm

    def update(self, grads, state, params):
        count = state["count"] + 1
        lr = self._schedule(count)
        g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        gnorm = global_norm(g32)
        if self.clip_norm is not None:
            scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(gnorm, 1e-9))
            g32 = jax.tree.map(lambda g: g * scale, g32)

        b1c = 1.0 - self.b1 ** count.astype(jnp.float32)
        b2c = 1.0 - self.b2 ** count.astype(jnp.float32)

        def upd(g, m, v, p):
            m = self.b1 * m + (1.0 - self.b1) * g
            v = self.b2 * v + (1.0 - self.b2) * jnp.square(g)
            mh = m / b1c
            vh = v / b2c
            step = mh / (jnp.sqrt(vh) + self.eps)
            if self.weight_decay and p.ndim >= 2:
                step = step + self.weight_decay * p
            return m, v, p - lr * step

        flat_g, treedef = jax.tree.flatten(g32)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        flat_p = treedef.flatten_up_to(state["master"])
        new = [upd(g, m, v, p) for g, m, v, p in
               zip(flat_g, flat_m, flat_v, flat_p)]
        new_m = treedef.unflatten([x[0] for x in new])
        new_v = treedef.unflatten([x[1] for x in new])
        new_master = treedef.unflatten([x[2] for x in new])
        new_params = jax.tree.map(
            lambda mp, p: mp.astype(p.dtype), new_master, params)
        return new_params, {"master": new_master, "m": new_m, "v": new_v,
                            "count": count}, gnorm


@dataclasses.dataclass(frozen=True)
class PaperSGD:
    """Algorithm 3: x <- x - alpha * (g + 2*lambda*x)."""

    lr: float = 0.05
    l2: float = 0.0
    clip_norm: Optional[float] = None

    def init_specs(self, param_specs) -> dict:
        return {"count": LogicalArray((), (), jnp.int32)}

    def init(self, params) -> dict:
        return {"count": jnp.zeros((), jnp.int32)}

    def update(self, grads, state, params):
        g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        gnorm = global_norm(g32)
        if self.clip_norm is not None:
            scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(gnorm, 1e-9))
            g32 = jax.tree.map(lambda g: g * scale, g32)

        def upd(p, g):
            step = g + 2.0 * self.l2 * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - self.lr * step).astype(p.dtype)

        new_params = jax.tree.map(upd, params, g32)
        return new_params, {"count": state["count"] + 1}, gnorm
