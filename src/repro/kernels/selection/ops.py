"""Jit'd wrappers around the selection kernel with an XLA fallback.

``impl="pallas"`` targets TPU (validated in interpret mode on CPU);
``impl="xla"`` is the pure-jnp reference path — the same role the CPU
baselines play in the paper.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.selection import ref
from repro.kernels.selection.selection import DEFAULT_BLOCK, select_pallas


@partial(jax.jit, static_argnames=("block", "impl", "interpret"))
def select(x, lo, hi, *, block: int = DEFAULT_BLOCK, impl: str = "xla",
           interpret: bool = False):
    """Range selection -> (padded index lines (N,), per-block counts)."""
    if impl == "pallas":
        return select_pallas(x, lo, hi, block=block, interpret=interpret)
    idx, counts = ref.select_blocked(x, lo, hi, block)
    return idx.reshape(-1), counts


@partial(jax.jit, static_argnames=("block", "impl", "interpret"))
def select_count(x, lo, hi, *, block: int = DEFAULT_BLOCK, impl: str = "xla",
                 interpret: bool = False):
    _, counts = select(x, lo, hi, block=block, impl=impl, interpret=interpret)
    return jnp.sum(counts)


def compact(idx_lines, counts):
    """Materialize the compacted index array from padded kernel output
    (the DBMS-facing form; the padded form is what the engine streams)."""
    flat = idx_lines.reshape(-1)
    order = jnp.argsort(flat == -1, stable=True)
    return flat[order], jnp.sum(counts)
