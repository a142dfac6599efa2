"""Pallas TPU kernel for pipelined minibatch SGD of K GLMs (paper §VI,
Fig. 9).

TPU adaptation of the paper's dataflow engine: the K models' weights stay
on chip for the WHOLE call (the paper keeps its model in on-chip
registers/BRAM), in the output block, which every grid step maps to the
same place.  The dataset streams HBM->VMEM feature-major, ``block_rows``
rows per sequential grid step: features on sublanes, rows on lanes, the
label as the last feature row.  Pallas double-buffers the next block while
this one computes (the ingress FIFO of Fig. 9).

Inside a block a ``fori_loop`` walks 128-row tiles, and each tile's
``128 // minibatch`` minibatches update the K models one after another,
in table order.  A model is held broadcast over the 128 lanes, one
feature per sublane, so the three stages are plain VPU work in float32,
for the K models at once: Dot (a sublane sum of tile x weights, one value
per row), ScalarEngine (the link, minus the label, kept on the
minibatch's lanes only) and Update (a lane sum per feature: the
minibatch's gradient, already on every lane for the next step).  One step
costs a few dozen vector instructions, with no loop trip of XLA's and no
launch between steps.  ``dimension_semantics=("arbitrary",)`` keeps the
grid in order: the RAW dependency the paper preserves.

Exactly ``m // minibatch`` updates run per epoch: the number of
minibatches arrives as a scalar-prefetch operand and bounds the last
block's loop, so the lanes that pad the last block apply no update (a
pure-pad minibatch would still apply the l2 shrinkage).  ``lr`` and ``l2``
are operands, one value per model, so one compiled kernel serves every
grid of K models at a shape.  Epochs are folded into the grid (step
e*nb + i reads block i), mirroring the paper's iterative rescans of the
HBM-resident dataset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 8192       # rows per grid step: 64 tiles of 128 lanes


def _link(kind: str, z):
    return jax.nn.sigmoid(z) if kind == "logreg" else z


def _zero_unless(keep, x):
    """``x`` where ``keep``, else 0.  The kernel keeps to ``lax`` for this
    and for its integer division: ``jnp.where``, ``//`` and ``%`` trace to
    nested jits, which the trainer lowers again on every call."""
    keep = lax.broadcast_in_dim(keep, x.shape,
                                tuple(range(x.ndim - keep.ndim, x.ndim)))
    return lax.select(keep, x, jnp.zeros_like(x))


def _sgd_kernel(nmb_ref, lr_ref, l2_ref, data_ref, x0_ref, x_ref, *,
                kind: str, minibatch: int, n_blocks: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        x_ref[...] = x0_ref[...]

    k, n1, _ = x_ref.shape
    block_rows = data_ref.shape[1]
    per_tile = LANES // minibatch
    per_block = block_rows // minibatch
    todo = jnp.minimum(nmb_ref[0] - lax.rem(step, n_blocks) * per_block,
                       per_block)
    lr = lr_ref[...]                                   # (k, 1, 1)
    l2x2 = 2.0 * l2_ref[...]
    feature = lax.broadcasted_iota(jnp.int32, (n1, LANES), 0) < n1 - 1
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    lane_mb = lax.div(lane, minibatch)            # each lane's minibatch
    inv_mb = 1.0 / minibatch      # exact: a minibatch divides 128 lanes

    def load(t):
        """Tile t of the block, each row on its lane: its features (the
        label row zeroed) and its labels.  Lanes past the block's last
        minibatch are not data, so they are zeroed too."""
        tile = data_ref[:, pl.ds(pl.multiple_of(t * LANES, LANES), LANES)]
        valid = lane < (todo - t * per_tile) * minibatch
        return (_zero_unless(feature & valid, tile),
                _zero_unless(valid, tile[n1 - 1:, :]))

    def update(i, a, b, x):
        """Minibatch i of the tile (a, b) applied to the k models x."""
        z = jnp.sum(a * x, axis=1, keepdims=True)              # Dot
        d = _zero_unless(lane_mb == i, _link(kind, z) - b)     # ScalarEngine
        g = jnp.sum(a * d, axis=2, keepdims=True) * inv_mb
        return x - lr * (g + l2x2 * x)                         # Update

    def tile(t, x):
        # one update in the loop body, not one per minibatch of the tile:
        # the trainer lowers this kernel again on every call
        a, b = load(t)
        return lax.fori_loop(
            0, jnp.minimum(per_tile, todo - t * per_tile),
            lambda i, x: update(i, a, b, x), x)

    x_ref[...] = lax.fori_loop(0, lax.div(todo + per_tile - 1, per_tile),
                               tile, x_ref[...])


@functools.partial(jax.jit, static_argnames=(
    "minibatch", "epochs", "kind", "block_rows", "interpret"))
def sgd_block(data, lr, l2, x0, *, minibatch: int = 16, epochs: int = 1,
              kind: str = "ridge", block_rows: int = BLOCK_ROWS,
              interpret: bool = False):
    """Train K models side by side over one feature-major dataset.

    data: (n + 1, m) float32, rows 0..n-1 the features and row n the
    label, m a multiple of ``minibatch``; lr, l2: (K,) float32; x0: (K, n)
    float32.  Returns the trained (K, n) weights: ``epochs`` passes of
    ``m // minibatch`` updates each, ``x <- x - lr * (g + 2 l2 x)`` with g
    the minibatch's mean gradient."""
    n1, m = data.shape
    k, n = x0.shape
    if n1 != n + 1:
        raise ValueError(f"data has {n1} rows, expected {n} features + "
                         "the label")
    if m % minibatch or LANES % minibatch:
        raise ValueError(f"{m} rows in minibatches of {minibatch}: need a "
                         "whole number of minibatches, each dividing "
                         f"{LANES} rows")
    block_rows = min(block_rows, pl.cdiv(m, LANES) * LANES)
    n_blocks = pl.cdiv(m, block_rows)
    kernel = functools.partial(_sgd_kernel, kind=kind, minibatch=minibatch,
                               n_blocks=n_blocks)
    # a model's lr and l2 broadcast along its weights
    hyper = pl.BlockSpec((k, 1, 1), lambda i, nmb: (0, 0, 0))
    # each model one feature per sublane, broadcast over the lanes; the
    # label's row stays zero
    models = pl.BlockSpec((k, n1, LANES), lambda i, nmb: (0, 0, 0))
    x0 = jnp.broadcast_to(jnp.pad(x0, ((0, 0), (0, 1)))[:, :, None],
                          (k, n1, LANES))
    x = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(epochs * n_blocks,),
            in_specs=[hyper, hyper,
                      pl.BlockSpec((n1, block_rows),
                                   lambda i, nmb: (0, lax.rem(i, n_blocks))),
                      models],
            out_specs=models),
        out_shape=jax.ShapeDtypeStruct((k, n1, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),     # sequential: RAW dep
        interpret=interpret,
        name="sgd_block",
    )(jnp.full((1,), m // minibatch, jnp.int32),
      lr.astype(jnp.float32).reshape(k, 1, 1),
      l2.astype(jnp.float32).reshape(k, 1, 1), data, x0.astype(jnp.float32))
    return x[:, :n, 0]


# -- the wide form ---------------------------------------------------------- #
#
# ``sgd_block`` holds each model broadcast over 128 lanes and does a whole
# 128-row tile's arithmetic for each minibatch of 16, eight times the
# minibatch's own, and its (K, n + 1, 128) models plus an (n + 1, 8192)
# block no longer fit VMEM once n reaches a few hundred.  The wide form transposes each 128-row tile once, in VMEM,
# to rows on sublanes and features on lanes, so a minibatch is its own 16
# rows and a model one (1, features) row.

# Feature rows (features + label) from which the trainer takes the wide
# form.  Timed alone on one v5e (K = 4, 400,000 rows), us a minibatch
# step, sgd_block / sgd_block_wide: 28 features 0.192 / 0.298, 64 0.319 /
# 0.298, 127 0.458 / 0.299, 256 0.856 / 0.327; at 2,000 sgd_block does
# not fit VMEM, the wide form 0.490.
WIDE_FROM_ROWS = 65
# bytes of one data block of the wide form; Pallas holds two
WIDE_BLOCK_BYTES = 4 << 20


def wide(n_features: int) -> bool:
    """Whether the trainer runs ``sgd_block_wide`` (else ``sgd_block``)
    for ``n_features`` features: the one place the form is chosen."""
    return n_features + 1 >= WIDE_FROM_ROWS


def wide_rows(n_features: int) -> int:
    """Rows of the wide form's feature-major data: the features, the
    label, and zero rows up to a whole number of 128-lane groups."""
    return pl.cdiv(n_features + 1, LANES) * LANES


def _sgd_wide_kernel(nmb_ref, lr_ref, l2_ref, data_ref, x0_ref, x_ref,
                     rows_ref, label_ref, *, kind: str, minibatch: int,
                     n_blocks: int, label: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        x_ref[...] = x0_ref[...]

    block_rows = data_ref.shape[1]
    per_tile = LANES // minibatch
    per_block = block_rows // minibatch
    todo = jnp.minimum(nmb_ref[0] - lax.rem(step, n_blocks) * per_block,
                       per_block)
    lr = lr_ref[...]                                   # (k, 1, 1)
    l2x2 = 2.0 * l2_ref[...]
    inv_mb = 1.0 / minibatch
    group = label - label % LANES              # the label's lane group
    lane = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    is_label, is_feature = lane == label % LANES, lane != label % LANES

    def tile(t, x):
        """Tile t of the block, transposed to rows on sublanes; its labels
        moved out to their own column, and zeroed in the features."""
        rows_ref[...] = data_ref[
            :, pl.ds(pl.multiple_of(t * LANES, LANES), LANES)].T
        near = rows_ref[:, group:group + LANES]
        label_ref[...] = jnp.sum(_zero_unless(is_label, near), axis=1,
                                 keepdims=True)
        rows_ref[:, group:group + LANES] = _zero_unless(is_feature, near)
        return lax.fori_loop(
            0, jnp.minimum(per_tile, todo - t * per_tile), update, x)

    def update(i, x):
        """Minibatch i of the tile applied to the k models x, (k, 1, n)."""
        at = pl.ds(pl.multiple_of(i * minibatch, minibatch), minibatch)
        a = rows_ref[at, :][None]                      # (1, mb, n)
        z = jnp.sum(a * x, axis=2, keepdims=True)      # Dot, (k, mb, 1)
        d = _link(kind, z) - label_ref[at, :][None]    # ScalarEngine
        g = jnp.sum(a * d, axis=1, keepdims=True) * inv_mb
        return x - lr * (g + l2x2 * x)                 # Update

    x_ref[...] = lax.fori_loop(0, lax.div(todo + per_tile - 1, per_tile),
                               tile, x_ref[...])


@functools.partial(jax.jit, static_argnames=(
    "minibatch", "epochs", "kind", "block_bytes", "interpret"))
def sgd_block_wide(data, lr, l2, x0, *, minibatch: int = 16,
                   epochs: int = 1, kind: str = "ridge",
                   block_bytes: int = WIDE_BLOCK_BYTES,
                   interpret: bool = False):
    """``sgd_block`` for wide tables: the same contract, updates and
    order, the products and sums in float32 on the VPU.

    data: (``wide_rows(n)``, m) float32, rows 0..n-1 the features, row n
    the label and the rest zero, as ``engine.stage_morsel`` lays it out;
    lr, l2: (K,); x0: (K, n).  Blocks of ``block_bytes`` (a multiple of
    128 rows) stream HBM->VMEM."""
    k, n = x0.shape
    rows = wide_rows(n)
    m = data.shape[1]
    if data.shape[0] != rows:
        raise ValueError(f"data has {data.shape[0]} rows, expected {n} "
                         f"features + the label, padded to {rows}")
    if m % minibatch or LANES % minibatch:
        raise ValueError(f"{m} rows in minibatches of {minibatch}: need a "
                         "whole number of minibatches, each dividing "
                         f"{LANES} rows")
    block_rows = max(block_bytes // (4 * rows) // LANES, 1) * LANES
    block_rows = min(block_rows, pl.cdiv(m, LANES) * LANES)
    n_blocks = pl.cdiv(m, block_rows)
    kernel = functools.partial(_sgd_wide_kernel, kind=kind,
                               minibatch=minibatch, n_blocks=n_blocks,
                               label=n)
    hyper = pl.BlockSpec((k, 1, 1), lambda i, nmb: (0, 0, 0))
    # each model one row, features on the lanes; the label's and the pad
    # lanes stay zero
    models = pl.BlockSpec((k, 1, rows), lambda i, nmb: (0, 0, 0))
    x = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(epochs * n_blocks,),
            in_specs=[hyper, hyper,
                      pl.BlockSpec((rows, block_rows),
                                   lambda i, nmb: (0, lax.rem(i, n_blocks))),
                      models],
            out_specs=models,
            scratch_shapes=[pltpu.VMEM((LANES, rows), jnp.float32),
                            pltpu.VMEM((LANES, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((k, 1, rows), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),     # sequential: RAW dep
        interpret=interpret,
        name="sgd_block_wide",
    )(jnp.full((1,), m // minibatch, jnp.int32),
      lr.astype(jnp.float32).reshape(k, 1, 1),
      l2.astype(jnp.float32).reshape(k, 1, 1), data,
      jnp.pad(x0.astype(jnp.float32), ((0, 0), (0, rows - n)))[:, None])
    return x[:, 0, :n]
