"""Jit'd wrapper for the SGD GLM trainer with XLA fallback."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.sgd import ref
from repro.kernels.sgd.sgd import sgd_block


@partial(jax.jit, static_argnames=("lr", "l2", "minibatch", "epochs", "kind",
                                   "impl", "interpret"))
def sgd_train(a, b, x0, *, lr: float, l2: float = 0.0, minibatch: int = 16,
              epochs: int = 1, kind: str = "ridge", impl: str = "xla",
              interpret: bool = False):
    if impl == "pallas":
        # the kernel's single-model case, on the feature-major layout
        data = jnp.concatenate([a.T, b[None]], axis=0)
        x = sgd_block(data, jnp.full((1,), lr), jnp.full((1,), l2),
                      x0[None], minibatch=minibatch, epochs=epochs,
                      kind=kind, interpret=interpret)
        return x[0]
    return ref.sgd_ref(a, b, x0, lr=lr, l2=l2, minibatch=minibatch,
                       epochs=epochs, kind=kind)
