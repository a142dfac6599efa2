"""Jit'd flash-attention wrapper over (B, S, H, D) model-layout tensors."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.flash_attention import flash_attention


@partial(jax.jit, static_argnames=("causal", "impl", "interpret", "block_q",
                                   "block_kv"))
def attend(q, k, v, *, causal: bool = True, impl: str = "xla",
           interpret: bool = False, block_q: int = 128, block_kv: int = 128):
    """q (B,S,H,D); k/v (B,S,H,D) (kv already expanded to q heads)."""
    b, s, h, d = q.shape
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    if impl == "pallas":
        of = flash_attention(qf, kf, vf, causal=causal, block_q=block_q,
                             block_kv=block_kv, interpret=interpret)
    else:
        of = ref.attention_ref(qf.astype(jnp.float32),
                               kf.astype(jnp.float32),
                               vf.astype(jnp.float32), causal=causal)
    return of.reshape(b, h, s, d).transpose(0, 2, 1, 3).astype(q.dtype)
