"""Query-layer shard layouts (device = HBM pseudo-channel, Figs. 5-7).

The paper scales bandwidth by striping data across HBM pseudo-channels;
the query stack's analogue is a 1-D striping of row streams across n
devices, where each device plays one pseudo-channel of the paper's
channel-count sweep.  ``ShardLayout`` is that striping: it is part of a
plan's identity (its ``key()`` joins the plan fingerprint and executor
cache key, so a 1-device and an 8-device plan never alias).
``hash_shard`` and ``partition_to_shards`` are the repartitioning
contract of the shuffle join (``core/join.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

QUERY_SHARD_AXIS = "shard"


@dataclasses.dataclass(frozen=True)
class ShardLayout:
    """A query-layer striping: ``n_shards`` devices, one channel each."""

    n_shards: int
    axis: str = QUERY_SHARD_AXIS

    def __post_init__(self) -> None:
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")

    @property
    def mesh(self) -> Mesh:
        return shard_mesh(self.n_shards, self.axis)

    def key(self) -> tuple:
        """Hashable identity folded into fingerprints and cache keys."""
        return ("shard_layout", self.n_shards, self.axis)


@functools.lru_cache(maxsize=None)
def shard_mesh(n_shards: int, axis: str = QUERY_SHARD_AXIS) -> Mesh:
    """1-D mesh over the first ``n_shards`` devices (memoized: meshes are
    compared by identity in jit caches, so each layout gets ONE mesh)."""
    devs = jax.devices()
    if n_shards > len(devs):
        raise ValueError(
            f"ShardLayout wants {n_shards} devices but only {len(devs)} "
            "exist (set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return Mesh(np.array(devs[:n_shards]), (axis,))


def hash_shard(keys: jax.Array, n_shards: int) -> jax.Array:
    """Shard owner of each key: plain modulo.

    This IS the repartitioning contract — both join sides must use the
    same function so matching keys land on the same shard.  Keys are
    validated non-negative by the eager engine layer, so modulo is a
    total function here."""
    return (keys % jnp.int32(n_shards)).astype(jnp.int32)


def partition_to_shards(shard_ids: jax.Array,
                        values: Sequence[jax.Array],
                        n_shards: int, cap: int,
                        fills: Sequence[jax.Array]
                        ) -> Tuple[Tuple[jax.Array, ...], jax.Array,
                                   jax.Array]:
    """Scatter rows into fixed-capacity per-shard buckets (the shuffle).

    ``values`` are (N,) arrays sharing ``shard_ids``; each is scattered
    with ONE stable permutation into its ``fills[i]`` template of shape
    (n_shards, cap) — the template's contents are the pad pattern (e.g.
    distinct negative sentinels for a join build side).  Rows beyond a
    shard's ``cap`` are dropped (``mode='drop'``), but ``counts`` stays
    exact via bincount, so one retry with the measured capacity always
    suffices.  Returns (buckets, counts (n_shards,), overflowed)."""
    n = shard_ids.shape[0]
    order = jnp.argsort(shard_ids, stable=True)
    sid = shard_ids[order]
    counts = jnp.bincount(shard_ids, length=n_shards).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n, dtype=jnp.int32) - starts[sid]
    buckets = tuple(f.at[sid, pos].set(v[order], mode="drop")
                    for f, v in zip(fills, values))
    return buckets, counts, jnp.any(counts > cap)
