"""Pallas TPU kernel for pipelined minibatch SGD (paper §VI, Fig. 9).

TPU adaptation of the paper's dataflow engine: the model x lives in VMEM
scratch for the WHOLE run (the paper keeps it in on-chip registers/BRAM);
the dataset streams HBM->VMEM one minibatch block per sequential grid step
(Pallas double-buffers the incoming block while the previous one computes —
the ingress FIFO of Fig. 9).  Dot / ScalarEngine / Update are the three
fused stages inside the kernel body.  Grid iteration order IS the RAW
dependency the paper preserves: ``dimension_semantics=("arbitrary",)``
forbids reordering, so convergence matches the oracle bit-for-bit modulo
float addition order.

Epochs are folded into the grid (step e*nb + i reads block i), mirroring
the paper's iterative rescans of the HBM-resident dataset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sgd_kernel(a_ref, b_ref, x0_ref, xout_ref, x_vmem, *,
                lr: float, l2: float, kind: str, nb: int, epochs: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        x_vmem[...] = x0_ref[...]

    a = a_ref[...]                                   # (B, n) minibatch block
    b = b_ref[...]                                   # (B, 1)
    x = x_vmem[...]                                  # (1, n)
    z = jax.lax.dot_general(a, x, (((1,), (1,)), ((), ())),      # Dot
                            preferred_element_type=jnp.float32)  # (B, 1)
    if kind == "logreg":
        z = jax.nn.sigmoid(z)                        # ScalarEngine
    d = z - b
    g = jax.lax.dot_general(d, a, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) / a.shape[0]
    x = x - lr * (g + 2.0 * l2 * x)                  # Update (RAW preserved)
    x_vmem[...] = x

    @pl.when(step == nb * epochs - 1)
    def _emit():
        xout_ref[...] = x


def sgd_pallas(a, b, x0, *, lr: float, l2: float = 0.0, minibatch: int = 16,
               epochs: int = 1, kind: str = "ridge",
               interpret: bool = False):
    """a: (m, n) f32; b: (m,); x0: (n,). Returns trained x (n,).

    Labels and the model travel as 2-D (m, 1) / (1, n) blocks: the TPU
    lowering tiles rank-1 blocks by 128 lanes, which a 16-row minibatch
    of labels is not."""
    m, n = a.shape
    assert m % minibatch == 0
    nb = m // minibatch
    kernel = functools.partial(_sgd_kernel, lr=lr, l2=l2, kind=kind,
                               nb=nb, epochs=epochs)
    x = pl.pallas_call(
        kernel,
        grid=(nb * epochs,),
        in_specs=[
            pl.BlockSpec((minibatch, n), lambda i: (i % nb, 0)),
            pl.BlockSpec((minibatch, 1), lambda i: (i % nb, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),     # sequential: RAW dep
        interpret=interpret,
    )(a, b.reshape(m, 1), x0.reshape(1, n))
    return x.reshape(n)
