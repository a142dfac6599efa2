"""Scale-out range selection (paper §IV) over the device mesh.

Each device is one "engine": it scans its local column shard (its own HBM
channel) with the selection kernel and emits a lane-aligned index line plus
match counts.  The host is the paper's control unit — engines run
asynchronously under one shard_map; the only synchronization is the final
count reduction, matching the paper's software-side barriers.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.channels import ChannelPlan
from repro.kernels.selection import ops as sel_ops
from repro.kernels.selection.selection import DEFAULT_BLOCK


def select_distributed(x, lo, hi, plan: ChannelPlan, *,
                       block: int = DEFAULT_BLOCK, impl: str = "xla",
                       interpret: bool = False):
    """x: (N,) int32 placed per ``plan``. Returns (idx lines (N,), per-engine
    counts (n_engines,)). Indices are GLOBAL (engine offset applied)."""
    mesh, axis = plan.mesh, plan.axis
    n = x.shape[0]
    n_eng = plan.n_engines
    assert n % (n_eng * block) == 0, (n, n_eng, block)
    shard = n // n_eng

    def engine(x_local):
        eng = jax.lax.axis_index(axis)
        idx, counts = sel_ops.select(x_local, lo, hi, block=block, impl=impl,
                                     interpret=interpret)
        idx = jnp.where(idx >= 0, idx + eng * shard, -1)
        return idx, jnp.sum(counts)[None]

    in_spec = P(axis) if plan.placement == "partitioned" else P()
    fn = shard_map(engine, mesh=mesh, in_specs=(in_spec,),
                   out_specs=(P(axis), P(axis)), check_vma=False)
    if plan.placement == "partitioned":
        return fn(x)
    # congested mode: every engine scans the SAME first shard (crossbar
    # congestion analogue used by the Fig. 5 non-partitioned baseline)
    return fn(x[:shard] if x.shape[0] == n else x)


@partial(jax.jit, static_argnames=("selectivity_bins",))
def selectivity_histogram(x, selectivity_bins: int = 10):
    """Helper for Fig. 6 experiments: value histogram to pick ranges with a
    target selectivity."""
    return jnp.histogram(x, bins=selectivity_bins)[0]
