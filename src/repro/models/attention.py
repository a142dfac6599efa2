"""Attention: GQA/MHA with head padding, dense + chunked (flash-style) paths,
KV cache prefill/decode, cross-attention, and a flash-decoding cache layout.

Sharding (DESIGN.md):
  * q heads always padded to a multiple of the tensor axis and sharded over
    ``model`` (zero-weight heads are numerically inert);
  * MHA archs pad KV alongside Q; GQA KV heads stay replicated;
  * the KV cache's *sequence* dim is sharded over ``model`` for serve steps
    (flash-decoding): each chip owns a contiguous KV slice of its local HBM —
    the paper's channel-partitioning discipline applied to the cache — and
    decode attention reduces across chips via XLA's partial-softmax psum.

The chunked path processes all queries at once and statically unrolls over
KV blocks with a running (max, sum, acc) — no (S x S) materialization, and
every FLOP appears in ``cost_analysis`` (no while loops).  Like all
block-masked XLA fallbacks it computes causally-dead blocks too (~2x optimal
FLOPs); the Pallas kernel in ``repro.kernels.flash_attention`` skips them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.sharding import ShardingRules
from repro.models.common import apply_rope, la

DENSE_MAX_SEQ = 2_048
MAX_SCORE_BLOCK_BYTES = 1.5e9      # per-device transient budget for chunked
NEG_INF = -1e30


def attn_params(cfg: ArchConfig, tp: int, cross: bool = False) -> dict:
    hp = cfg.padded_heads(tp)
    kvp = cfg.padded_kv_heads(tp)
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "wq": la((d, hp, hd), ("fsdp", "heads", "head_dim")),
        "wk": la((d, kvp, hd), ("fsdp", "kv_heads", "head_dim")),
        "wv": la((d, kvp, hd), ("fsdp", "kv_heads", "head_dim")),
        "wo": la((hp, hd, d), ("heads", "head_dim", "fsdp")),
    }


@dataclasses.dataclass
class KVCache:
    k: jax.Array          # (B, S_max, KV, hd) — seq dim sharded over model
    v: jax.Array
    pos: jax.Array        # () int32 — current length


def _expand_kv(k, q_heads: int, rules: ShardingRules):
    """Repeat kv heads to match (padded) q heads; result shards like q heads."""
    kv = k.shape[-2]
    if kv != q_heads:
        k = jnp.repeat(k, q_heads // kv, axis=-2)
    return rules.constrain(k, "batch", None, "heads", "head_dim")


def _dense_attn(q, k, v, q_pos, k_pos, causal: bool):
    """q (B,Sq,H,D); k,v (B,Sk,H,D). Scores in f32."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = q_pos[:, None, :, None] >= k_pos[:, None, None, :] if causal else \
        (k_pos[:, None, None, :] < jnp.iinfo(jnp.int32).max)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def _pick_chunk(b_local: int, h_local: int, sq: int) -> int:
    """Largest KV block with per-device score transient under budget."""
    for chunk in (4096, 2048, 1024, 512):
        if b_local * h_local * sq * chunk * 4 <= MAX_SCORE_BLOCK_BYTES:
            return chunk
    return 256


def _chunked_attn(q, k, v, q_pos, k_pos, causal: bool, chunk: int):
    """All queries at once; static unrolled loop over KV blocks with running
    (m, l, acc).  Exact-counting (no while loops) and O(Sq*chunk) transients."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = d ** -0.5
    nk = -(-sk // chunk)
    pad_k = nk * chunk - sk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_k)),
                        constant_values=jnp.iinfo(jnp.int32).max)

    m = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    for i in range(nk):
        ki = jax.lax.slice_in_dim(k, i * chunk, (i + 1) * chunk, axis=1)
        vi = jax.lax.slice_in_dim(v, i * chunk, (i + 1) * chunk, axis=1)
        kpi = jax.lax.slice_in_dim(k_pos, i * chunk, (i + 1) * chunk, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, ki,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = q_pos[:, None, :, None] >= kpi[:, None, None, :]
        else:
            mask = kpi[:, None, None, :] < jnp.iinfo(jnp.int32).max
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        # p in bf16 for the PV matmul (values in [0,1]; acc stays f32) —
        # hillclimb: the (B,H,Sq,chunk) probability tensor is the largest
        # attention intermediate, halving it halves fallback-attn traffic
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(jnp.bfloat16),
            vi.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        m = m_new
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention(cfg: ArchConfig, p: dict, x, positions, rules: ShardingRules,
              *, causal: bool = True, cache: Optional[KVCache] = None,
              cross_kv: Optional[tuple] = None, use_rope: bool = True,
              attn_impl: str = "auto"):
    """Self- or cross-attention over x (B, S, d_model).

    cache: serve-step KV cache (self-attention only).  Prefill (s > 1)
    computes attention from the freshly-projected K/V and writes the cache
    (each model shard stores its own sequence slice); decode (s == 1) reads
    the sequence-sharded cache and lets SPMD combine partial softmaxes.
    Returns (out, new_cache).
    """
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = rules.constrain(q, "batch", None, "heads", "head_dim")
    q_pos = positions if positions.ndim == 2 else positions[..., 0]

    new_cache = None
    if cross_kv is not None:
        if use_rope:
            raise ValueError("cross attention is position-free here")
        k, v, k_pos = cross_kv
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        k = rules.constrain(k, "batch", None, "kv_heads", "head_dim")
        v = rules.constrain(v, "batch", None, "kv_heads", "head_dim")
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct,
                           cfg.mrope_sections)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct,
                           cfg.mrope_sections)
        k_pos = q_pos
        if cache is not None:
            k_upd = jax.lax.dynamic_update_slice(
                cache.k, k.astype(cache.k.dtype), (0, cache.pos, 0, 0))
            v_upd = jax.lax.dynamic_update_slice(
                cache.v, v.astype(cache.v.dtype), (0, cache.pos, 0, 0))
            k_upd = rules.constrain(k_upd, "batch", "kv_seq", "kv_heads",
                                    "head_dim")
            v_upd = rules.constrain(v_upd, "batch", "kv_seq", "kv_heads",
                                    "head_dim")
            new_cache = KVCache(k_upd, v_upd, cache.pos + s)
            if s == 1:
                # decode: attend over the sequence-sharded cache
                k, v = k_upd, v_upd
                sk = k.shape[1]
                k_pos = jnp.broadcast_to(
                    jnp.arange(sk, dtype=jnp.int32)[None, :], (b, sk))
                k_pos = jnp.where(k_pos < cache.pos + s, k_pos,
                                  jnp.iinfo(jnp.int32).max)
            # prefill (s > 1): attend over the fresh, batch-sharded k/v

    hp = q.shape[-2]
    k = _expand_kv(k, hp, rules)
    v = _expand_kv(v, hp, rules)

    sk = k.shape[1]
    if attn_impl == "auto":
        dp = 1
        for a in rules.batch:
            dp *= rules.mesh.shape.get(a, 1)
        h_shards = rules.mesh.shape.get("model", 1) if rules.heads else 1
        b_local = max(b // max(dp, 1), 1)
        h_local = max(hp // h_shards, 1)
        dense_bytes = b_local * h_local * s * sk * 4
        if s == 1 or (max(s, sk) <= DENSE_MAX_SEQ and
                      dense_bytes < MAX_SCORE_BLOCK_BYTES):
            attn_impl = "dense"
        else:
            attn_impl = f"chunked:{_pick_chunk(b_local, h_local, s)}"
    if attn_impl == "dense":
        out = _dense_attn(q, k, v, q_pos, k_pos, causal)
    else:
        chunk = int(attn_impl.split(":")[1]) if ":" in attn_impl else 1024
        out = _chunked_attn(q, k, v, q_pos, k_pos, causal, chunk)

    out = rules.constrain(out, "batch", None, "heads", "head_dim")
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return rules.constrain(y, "batch", None, None), new_cache


def init_cache(cfg: ArchConfig, batch: int, max_len: int, tp: int,
               dtype=jnp.bfloat16) -> dict:
    kvp, hd = cfg.padded_kv_heads(tp), cfg.head_dim
    ax = ("batch", "kv_seq", "kv_heads", "head_dim")
    return {
        "k": la((batch, max_len, kvp, hd), ax, dtype),
        "v": la((batch, max_len, kvp, hd), ax, dtype),
    }


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "pos"], meta_fields=[])
