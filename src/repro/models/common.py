"""Shared model primitives: params-as-LogicalArray, norms, RoPE/M-RoPE,
embeddings with padded vocab, gated/plain MLPs, losses.

All matmuls run in the param dtype (bf16 by default); softmax, norms and the
final loss accumulate in f32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sharding import LogicalArray, ShardingRules

PARAM_DTYPE = jnp.bfloat16


def la(shape, logical, dtype=PARAM_DTYPE) -> LogicalArray:
    assert len(shape) == len(logical), (shape, logical)
    return LogicalArray(tuple(int(s) for s in shape), tuple(logical), dtype)


# --------------------------------------------------------------------------- #
# materialization (smoke tests / real training)
# --------------------------------------------------------------------------- #

def materialize(tree, rng: jax.Array, init_scale: float = 0.02):
    """Turn a LogicalArray tree into real arrays (fan-in scaled normal)."""
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, LogicalArray))
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, leaf in zip(keys, leaves):
        if not isinstance(leaf, LogicalArray):
            out.append(leaf)
            continue
        shape = leaf.shape
        if len(shape) <= 1:
            # 1-D params are biases / norm scales; norms use the (1 + scale)
            # formulation so zero-init is the identity.
            out.append(jnp.zeros(shape, leaf.dtype))
        else:
            fan_in = float(np.prod(shape[:-1])) or 1.0
            scale = min(init_scale, 1.0 / np.sqrt(fan_in))
            init = scale * jax.random.normal(key, shape, jnp.float32)
            out.append(init.astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------------- #

def rmsnorm(x, scale, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, bias=None, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * (1.0 + scale.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(cfg, x, scale):
    return rmsnorm(x, scale) if cfg.norm == "rmsnorm" else layernorm(x, scale)


# --------------------------------------------------------------------------- #
# RoPE (standard, partial, and qwen2-vl M-RoPE)
# --------------------------------------------------------------------------- #

def _rope_freqs(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x, positions, theta: float, rotary_pct: float = 1.0,
               mrope_sections: Optional[tuple[int, int, int]] = None):
    """x: (B, S, H, D). positions: (B, S) int32, or (B, S, 3) for M-RoPE."""
    d = x.shape[-1]
    rot = int(d * rotary_pct)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = _rope_freqs(rot, theta)                       # (half,)

    if mrope_sections is not None:
        # positions (B, S, 3); each frequency index belongs to a (t,h,w) section
        assert positions.ndim == 3, "M-RoPE needs (B,S,3) positions"
        sec = jnp.concatenate([
            jnp.full((n,), i, jnp.int32)
            for i, n in enumerate(mrope_sections)])        # (half,)
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),
            jnp.broadcast_to(sec[None, None, :], positions.shape[:2] + (half,)),
            axis=-1)                                       # (B, S, half)
        angles = pos * freqs[None, None, :]
    else:
        if positions.ndim == 3:
            positions = positions[..., 0]
        angles = positions.astype(jnp.float32)[..., None] * freqs[None, None, :]

    cos = jnp.cos(angles)[:, :, None, :]                   # (B, S, 1, half)
    sin = jnp.sin(angles)[:, :, None, :]
    x1 = x_rot[..., :half].astype(jnp.float32)
    x2 = x_rot[..., half:].astype(jnp.float32)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
    if x_pass.shape[-1]:
        y = jnp.concatenate([y, x_pass], axis=-1)
    return y


# --------------------------------------------------------------------------- #
# embeddings / logits with padded vocab
# --------------------------------------------------------------------------- #

def embed_params(cfg, tp: int) -> dict:
    pv = cfg.padded_vocab(tp)
    p = {"embed": la((pv, cfg.d_model), ("vocab", "fsdp"))}
    if not cfg.tie_embeddings:
        p["unembed"] = la((cfg.d_model, pv), ("fsdp", "vocab"))
    return p


def embed_tokens(p, tokens, rules: ShardingRules):
    x = jnp.take(p["embed"], tokens, axis=0)
    return rules.constrain(x, "batch", None, None)


def logits_fn(p, x, cfg, rules: ShardingRules):
    table = p.get("unembed")
    if table is None:
        table = p["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, table,
                        preferred_element_type=jnp.float32)
    return rules.constrain(logits, "batch", None, "vocab")


# --------------------------------------------------------------------------- #
# MLP
# --------------------------------------------------------------------------- #

def mlp_params(cfg, d_ff: int) -> dict:
    if cfg.gated_ffn:
        # gate and up fused into one (d, 2, f) projection: one MXU pass and
        # one weight all-gather instead of two (hillclimb #6)
        return {
            "w_in": la((cfg.d_model, 2, d_ff), ("fsdp", None, "mlp")),
            "w_down": la((d_ff, cfg.d_model), ("mlp", "fsdp")),
        }
    return {
        "w_up": la((cfg.d_model, d_ff), ("fsdp", "mlp")),
        "w_down": la((d_ff, cfg.d_model), ("mlp", "fsdp")),
    }


def _act(cfg, x):
    return jax.nn.silu(x) if cfg.activation == "silu" else jax.nn.gelu(x)


def mlp_apply(cfg, p, x, rules: ShardingRules):
    if cfg.gated_ffn:
        gu = jnp.einsum("bsd,dcf->bscf", x, p["w_in"])
        h = _act(cfg, gu[:, :, 0]) * gu[:, :, 1]
    else:
        h = _act(cfg, jnp.einsum("bsd,df->bsf", x, p["w_up"]))
    h = rules.constrain(h, "batch", None, "mlp")
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return rules.constrain(out, "batch", None, None)


# --------------------------------------------------------------------------- #
# loss
# --------------------------------------------------------------------------- #

def cross_entropy(logits, targets, vocab_size: int, z_loss: float = 0.0):
    """logits (B,S,Vp) f32; targets (B,S) int32. Padded vocab cols masked."""
    logits = logits.astype(jnp.float32)
    pv = logits.shape[-1]
    if pv > vocab_size:
        mask = jnp.arange(pv) < vocab_size
        logits = jnp.where(mask[None, None, :], logits, -1e30)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    loss = lse - gold
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return jnp.mean(loss)
