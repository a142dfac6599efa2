"""Context-parallel decode attention (explicit flash-decoding combine).

The pjit models rely on XLA SPMD to partition decode attention over the
sequence-sharded cache.  This module is the EXPLICIT shard_map version —
each device computes attention over its local KV slice and the partial
results merge with the log-sum-exp trick:

    out = sum_i exp(m_i - m) * l_i * out_i / sum_i exp(m_i - m) * l_i

Used for the jamba long_500k path and as the reference semantics for the
sharded-softmax the compiler derives; the test asserts both agree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

NEG_INF = -1e30


def cp_decode_attention(mesh, axis: str, q, k, v, k_valid):
    """q (B,H,1,D) replicated over `axis`; k/v (B,S,H,D) sharded on S over
    `axis`; k_valid (B,S) bool sharded likewise.  Returns (B,H,1,D)."""

    def local(q_l, k_l, v_l, valid_l):
        scale = q_l.shape[-1] ** -0.5
        s = jnp.einsum("bhqd,bkhd->bhqk", q_l.astype(jnp.float32),
                       k_l.astype(jnp.float32)) * scale
        s = jnp.where(valid_l[:, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)                          # (B,H,1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bhqd", p, v_l.astype(jnp.float32))
        # LSE-combine across the sequence shards
        m_g = jax.lax.pmax(m, axis)
        w = jnp.exp(m - m_g) * l
        denom = jax.lax.psum(w, axis)
        num = jax.lax.psum(o * jnp.exp(m - m_g)[..., None], axis)
        return (num / jnp.maximum(denom, 1e-30)[..., None]).astype(q_l.dtype)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(), P(None, axis), P(None, axis),
                             P(None, axis)),
                   out_specs=P(), check_vma=False)
    return fn(q, k, v, k_valid)


def cp_decode_reference(q, k, v, k_valid):
    """Unsharded oracle."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(k_valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
