"""Jit'd SSD wrapper over model-layout tensors with XLA fallback."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ssd.ssd import ssd_pallas
from repro.models.mamba import ssd_chunked


@partial(jax.jit, static_argnames=("chunk", "impl", "interpret"))
def ssd(x, dt, a_log, b, c, d_skip, *, chunk: int = 128, impl: str = "xla",
        interpret: bool = False):
    """Model layout: x (B,S,nh,hd); dt (B,S,nh); b/c (B,S,ng,ds).
    Returns (y (B,S,nh,hd), h_final (B,nh,hd,ds))."""
    if impl == "xla":
        return ssd_chunked(x, dt, a_log, b, c, d_skip, chunk=chunk)
    bsz, s, nh, hd = x.shape
    ng, ds = b.shape[-2], b.shape[-1]
    rep = nh // ng
    xf = x.transpose(0, 2, 1, 3).reshape(bsz * nh, s, hd)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * nh, s)
    bf = jnp.repeat(b, rep, axis=2).transpose(0, 2, 1, 3).reshape(
        bsz * nh, s, ds)
    cf = jnp.repeat(c, rep, axis=2).transpose(0, 2, 1, 3).reshape(
        bsz * nh, s, ds)
    af = jnp.tile(a_log, bsz)
    df = jnp.tile(d_skip, bsz)
    y, h = ssd_pallas(xf, dtf, af, bf, cf, df, chunk=chunk,
                      interpret=interpret)
    y = y.reshape(bsz, nh, s, hd).transpose(0, 2, 1, 3)
    h = h.reshape(bsz, nh, hd, ds)
    return y.astype(x.dtype), h
