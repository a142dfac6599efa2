"""Pallas TPU kernel for range selection (paper §IV, Fig. 4).

TPU adaptation of the paper's engine: the ingress pipeline (DMA read ->
16-wide compare) becomes a VMEM-blocked streaming grid — each grid step
pulls one ``block`` of the column HBM->VMEM (Pallas double-buffers
automatically), compares against [lo, hi] on the VPU (8x128 lanes == the
paper's PARALLELISM, x64), and the egress pipeline writes the index line
with -1 dummies (the paper's dummy-element trick keeps lanes aligned) plus
a per-block match count.  Grid steps are independent — the scale-out
"multiple engines" axis is the grid (on-chip) times shard_map (across
chips, see core/selection.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 4096      # int32 elements per grid step: 16 KiB in VMEM


def _selection_kernel(lo_ref, hi_ref, x_ref, idx_ref, cnt_ref):
    i = pl.program_id(0)
    block = x_ref.shape[0]
    x = x_ref[...]
    lo = lo_ref[0]
    hi = hi_ref[0]
    base = i * block
    iota = jax.lax.broadcasted_iota(jnp.int32, (block,), 0) + base
    mask = (x >= lo) & (x <= hi)
    idx_ref[...] = jnp.where(mask, iota, -1)
    cnt_ref[...] = jnp.full(cnt_ref.shape, jnp.sum(mask.astype(jnp.int32)),
                            jnp.int32)


def select_pallas(x, lo, hi, *, block: int = DEFAULT_BLOCK,
                  interpret: bool = False):
    """x: (N,) int32, N % block == 0. Returns (idx (N,) with -1 dummies,
    counts (N/block,)).

    On a TPU ``block`` must be a multiple of 128 (the lane tiling of a
    rank-1 block).  Each block's count leaves the kernel as one lane-dense
    (1, 128) line — a rank-1 block of one element is not tileable — and
    the bounds ride in SMEM as scalars."""
    n = x.shape[0]
    assert n % block == 0, (n, block)
    nb = n // block
    lo = jnp.asarray([lo], x.dtype)
    hi = jnp.asarray([hi], x.dtype)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    idx, cnt = pl.pallas_call(
        _selection_kernel,
        grid=(nb,),
        in_specs=[
            smem,                                              # lo
            smem,                                              # hi
            pl.BlockSpec((block,), lambda i: (i,)),            # column block
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),            # index line
            pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),    # block count
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, 128), jnp.int32),
        ],
        interpret=interpret,
    )(lo, hi, x)
    return idx, cnt[:, 0, 0]
