"""Scale-out GLM training with SGD (paper §VI) — hyper-parameter search.

The paper's killer use case (Fig. 10a): K models trained on the SAME
dataset with different hyper-parameters, one engine per job, the dataset
REPLICATED so every engine streams its own HBM channel.  Here: vmap over
the hyper-parameter axis x shard_map over devices; each device holds a
replica of the dataset in its local HBM (the paper's replication), or —
non-replicated mode — reads a single remote copy (Fig. 10a's flat line).

Datasets larger than a channel use the paper's block-wise scan (CoCoA):
train multiple epochs per resident block, then rotate blocks.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.channels import ChannelPlan
from repro.kernels.sgd import ops as sgd_ops
from repro.kernels.sgd import ref as sgd_ref


@dataclasses.dataclass(frozen=True)
class HyperParams:
    lr: float
    l2: float


def pad_to_minibatch(a, b, minibatch: int):
    """Zero-pad (a, b) to the next multiple of ``minibatch``.

    Zero feature rows contribute exactly zero to the minibatch gradient
    numerator ``aᵀ(link(a@x) - b)`` for both ridge and logreg (every
    product term carries a zero feature), while the divisor stays the
    nominal minibatch — i.e. the tail rows are folded into one final
    partial minibatch of zero-weight rows.  Losses must still be
    computed over the UNPADDED rows (a logreg pad row would add
    ``-log(0.5)`` per row)."""
    m = a.shape[0]
    pad = (-m) % minibatch
    if pad == 0:
        return a, b
    a = jnp.concatenate([a, jnp.zeros((pad, a.shape[1]), a.dtype)])
    b = jnp.concatenate([b, jnp.zeros((pad,), b.dtype)])
    return a, b


def hyperparam_search(a, b, grid: Sequence[HyperParams], plan: ChannelPlan,
                      *, minibatch: int = 16, epochs: int = 10,
                      kind: str = "logreg", impl: str = "xla",
                      interpret: bool = False):
    """Train len(grid) models in parallel; jobs round-robin over engines.

    a (m, n) f32, b (m,): replicated per plan.  Returns xs (K, n) and final
    losses (K,).
    """
    mesh, axis = plan.mesh, plan.axis
    n_eng = plan.n_engines
    k = len(grid)
    jobs_per_eng = -(-k // n_eng)
    k_pad = jobs_per_eng * n_eng
    lrs = jnp.array([g.lr for g in grid] + [grid[0].lr] * (k_pad - k),
                    jnp.float32).reshape(n_eng, jobs_per_eng)
    l2s = jnp.array([g.l2 for g in grid] + [grid[0].l2] * (k_pad - k),
                    jnp.float32).reshape(n_eng, jobs_per_eng)
    n = a.shape[1]
    # non-dividing row counts: train on the zero-padded dataset (the tail
    # folds into one partial minibatch of zero-weight rows), score the loss
    # on the original rows only
    a_t, b_t = pad_to_minibatch(a, b, minibatch)

    def engine(lr_local, l2_local):
        # one engine trains its jobs sequentially on its LOCAL dataset copy
        def one(lr, l2):
            x0 = jnp.zeros((n,), jnp.float32)
            # lr/l2 are traced per-job values: fold into data, not statics
            x = _sgd_dynamic(a_t, b_t, x0, lr, l2, minibatch=minibatch,
                             epochs=epochs, kind=kind)
            return x, sgd_ref.loss_ref(a, b, x, l2=l2, kind=kind)

        xs, losses = jax.lax.map(lambda args: one(*args),
                                 (lr_local[0], l2_local[0]))
        return xs[None], losses[None]

    fn = shard_map(engine, mesh=mesh, in_specs=(P(axis), P(axis)),
                   out_specs=(P(axis), P(axis)), check_vma=False)
    xs, losses = fn(lrs, l2s)
    return (xs.reshape(k_pad, n)[:k], losses.reshape(k_pad)[:k])


@partial(jax.jit, static_argnames=("minibatch", "epochs", "kind"))
def _sgd_dynamic(a, b, x0, lr, l2, *, minibatch, epochs, kind):
    """SGD with traced (non-static) lr/l2 — the oracle loop parameterized."""
    m, n = a.shape
    nb = m // minibatch
    ab = a.reshape(nb, minibatch, n)
    bb = b.reshape(nb, minibatch)

    def mb_step(x, inp):
        ai, bi = inp
        z = ai @ x
        if kind == "logreg":
            z = jax.nn.sigmoid(z)
        g = ai.T @ (z - bi) / minibatch
        return x - lr * (g + 2.0 * l2 * x), None

    def epoch(x, _):
        x, _ = jax.lax.scan(mb_step, x, (ab, bb))
        return x, None

    x, _ = jax.lax.scan(epoch, x0, None, length=epochs)
    return x


def blockwise_train(a, b, x0, *, lr: float, l2: float, block_rows: int,
                    epochs_per_block: int, passes: int = 1,
                    minibatch: int = 16, kind: str = "ridge"):
    """CoCoA-style block-wise scan for datasets larger than a channel
    (paper §VI): a block is resident for several epochs, then rotated."""
    m, n = a.shape
    assert m % block_rows == 0
    nblk = m // block_rows
    x = x0
    for _ in range(passes):
        for i in range(nblk):
            ai = jax.lax.dynamic_slice_in_dim(a, i * block_rows, block_rows)
            bi = jax.lax.dynamic_slice_in_dim(b, i * block_rows, block_rows)
            x = sgd_ref.sgd_ref(ai, bi, x, lr=lr, l2=l2, minibatch=minibatch,
                                epochs=epochs_per_block, kind=kind)
    return x
