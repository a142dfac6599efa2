"""Traffic-generator analogue (paper §II, Fig. 1/2).

The paper instruments each AXI3 port with a configurable traffic generator.
Here: a Pallas streaming-copy kernel is the per-engine TG (each grid step
moves one VMEM block HBM->VMEM->HBM), `shard_map` scales it out one engine
per chip, and `core.channels.fpga_bandwidth_model` reproduces the paper's
published curve for validation (benchmarks/fig2).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.channels import ChannelPlan
from repro.core.shim import plan_stream_block


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] + 1          # read + write: the TG's rw traffic


def stream_copy_pallas(x, *, block: int = 0, interpret: bool = False):
    """The traffic generator: streams x through VMEM in blocks."""
    n = x.shape[0]
    if block == 0:
        block = plan_stream_block(n, x.dtype.itemsize).block[0]
    block = min(block, n)
    assert n % block == 0
    return pl.pallas_call(
        _copy_kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=interpret,
    )(x)


def stream_copy_distributed(x, plan: ChannelPlan, *, impl: str = "xla",
                            interpret: bool = False):
    """One TG per engine over the mesh."""
    def engine(x_local):
        if impl == "pallas":
            return stream_copy_pallas(x_local, interpret=interpret)
        return x_local + 1

    axis = plan.axis
    return shard_map(engine, mesh=plan.mesh, in_specs=(P(axis),),
                     out_specs=P(axis), check_vma=False)(x)


def measure_gbps(fn, x, *, iters: int = 5) -> float:
    """Wall-clock GB/s of an rw-stream op on THIS host (CPU numbers — used
    only for relative partitioned-vs-congested comparisons, never as TPU
    projections; those come from the roofline model)."""
    y = fn(x)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fn(x)
    jax.block_until_ready(y)
    dt = (time.perf_counter() - t0) / iters
    return 2 * x.nbytes / dt / 1e9
