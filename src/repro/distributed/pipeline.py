"""GPipe-style pipeline parallelism over a mesh axis.

Stages are laid out over the ``stage`` axis; microbatches stream through a
``collective_permute`` ring inside a ``shard_map``.  The schedule is the
classic fill-drain: with M microbatches and P stages the bubble fraction is
(P-1)/(M+P-1); utilization is reported by ``bubble_fraction`` so launch
configs can budget M.

This is an optional axis for depth-dominated models (the dry-run table's
default cells use DP x TP; PP composes by folding the ``pod`` axis into
stages for cross-pod depth partitioning, where its point-to-point traffic
pattern suits the lower DCN bandwidth).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(mesh, axis: str, stage_fn: Callable, params_stacked,
                   x, n_micro: int):
    """Run x (B, ...) through n_stages = mesh.shape[axis] stages.

    stage_fn(stage_params, microbatch) -> microbatch (same shape).
    params_stacked: pytree with leading dim n_stages (sharded over axis).
    Returns the pipeline output (B, ...).
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0
    mb = b // n_micro

    def worker(params_local, x_local):
        # params_local: leading dim 1 (this stage's params)
        p_stage = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        micro = x_local.reshape((n_micro, mb) + x_local.shape[1:])

        n_ticks = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, out = carry
            # stage 0 injects microbatch t (if any); others use the ring buf
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            x_in = jnp.where(stage == 0, micro[inject], buf)
            y = stage_fn(p_stage, x_in)
            # mask ticks where this stage has no real work (fill/drain)
            active = (t >= stage) & (t < n_micro + stage)
            y = jnp.where(active, y, buf)
            # the LAST stage writes its finished microbatch to out
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            write = (stage == n_stages - 1) & active
            out = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0),
                lambda o: o, out)
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, out), None

        buf0 = jnp.zeros_like(micro[0])
        out0 = jnp.zeros_like(micro)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0),
                                   jnp.arange(n_ticks))
        # only the last stage holds real output; broadcast it around the ring
        src = n_stages - 1
        out = jax.lax.ppermute(
            out, axis, [(src, i) for i in range(n_stages)])
        return out.reshape((b,) + x_local.shape[1:])

    fn = shard_map(worker, mesh=mesh,
                   in_specs=(P(axis), P()), out_specs=P(),
                   check_vma=False)
    return fn(params_stacked, x)
