"""Whisper-style encoder-decoder (audio family).

The conv frontend is a STUB per the assignment: the encoder consumes
precomputed frame embeddings (B, S_enc, d_model) from ``input_specs``.
Positional information is sinusoidal (computed, not stored) so parameter
shapes never depend on the input shape.  Attention is absolute-position
(no RoPE), pre-LayerNorm, non-gated GELU MLPs — per arXiv:2212.04356
(biases omitted; noted in DESIGN.md).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.sharding import LogicalArray, ShardingRules
from repro.models import attention as attn_mod
from repro.models.attention import KVCache, attention, attn_params
from repro.models.common import (
    apply_norm, cross_entropy, embed_params, embed_tokens, la, logits_fn,
    mlp_apply, mlp_params,
)
from repro.models.transformer import _cache_leaves, _stack


def _sinusoid(s: int, d: int, offset=0):
    pos = jnp.arange(s, dtype=jnp.float32) + offset
    inv = jnp.exp(-jnp.arange(0, d, 2, dtype=jnp.float32) / d * jnp.log(10000.0))
    ang = pos[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_layer_params(cfg: ArchConfig, tp: int) -> dict:
    return {
        "norm1": la((cfg.d_model,), (None,)),
        "attn": attn_params(cfg, tp),
        "norm2": la((cfg.d_model,), (None,)),
        "ffn": mlp_params(cfg, cfg.d_ff),
    }


def _dec_layer_params(cfg: ArchConfig, tp: int) -> dict:
    return {
        "norm1": la((cfg.d_model,), (None,)),
        "self_attn": attn_params(cfg, tp),
        "norm_x": la((cfg.d_model,), (None,)),
        "cross_attn": attn_params(cfg, tp),
        "norm2": la((cfg.d_model,), (None,)),
        "ffn": mlp_params(cfg, cfg.d_ff),
    }


def init_params(cfg: ArchConfig, tp: int) -> dict:
    params = dict(embed_params(cfg, tp))
    params["encoder"] = _stack(_enc_layer_params(cfg, tp), cfg.n_encoder_layers)
    params["decoder"] = _stack(_dec_layer_params(cfg, tp), cfg.num_layers)
    params["enc_norm"] = la((cfg.d_model,), (None,))
    params["final_norm"] = la((cfg.d_model,), (None,))
    return params


def encode(cfg: ArchConfig, params, frames, rules: ShardingRules, *,
           remat: bool, attn_impl: str = "auto", exact_counts: bool = False):
    b, s, _ = frames.shape
    x = frames + _sinusoid(s, cfg.d_model)[None].astype(frames.dtype)
    x = rules.constrain(x, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(x, pj):
        h = apply_norm(cfg, x, pj["norm1"])
        mix, _ = attention(cfg, pj["attn"], h, positions, rules,
                           causal=False, use_rope=False, attn_impl=attn_impl)
        x = x + mix
        h = apply_norm(cfg, x, pj["norm2"])
        return x + mlp_apply(cfg, pj["ffn"], h, rules), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    if exact_counts:
        for i in range(cfg.n_encoder_layers):
            x, _ = body(x, jax.tree.map(lambda a: a[i], params["encoder"]))
    else:
        x, _ = jax.lax.scan(body, x, params["encoder"])
    return apply_norm(cfg, x, params["enc_norm"])


def _cross_kv(cfg, params, enc_out, rules):
    """Per-decoder-layer cross K/V from encoder output (stacked over layers),
    as one batched einsum so the dry-run counts it exactly."""
    k = jnp.einsum("bsd,ldhk->lbshk", enc_out, params["decoder"]["cross_attn"]["wk"])
    v = jnp.einsum("bsd,ldhk->lbshk", enc_out, params["decoder"]["cross_attn"]["wv"])
    return {"k": k, "v": v}


def decode_trunk(cfg: ArchConfig, params, tokens, rules, *, cross_kv=None,
                 enc_out=None, self_caches=None, cache_pos=None, remat: bool,
                 attn_impl: str = "auto", exact_counts: bool = False):
    """Decoder stack.  Training passes ``enc_out`` (cross-K/V recomputed per
    layer inside the scan body so only one layer's worth is ever live);
    serving passes the precomputed stacked ``cross_kv`` cache instead."""
    b, s = tokens.shape
    x = embed_tokens(params, tokens, rules)
    off = cache_pos if cache_pos is not None else 0
    x = x + _sinusoid(s, cfg.d_model, offset=off)[None].astype(x.dtype)
    positions = jnp.arange(s, dtype=jnp.int32)[None] + off
    positions = jnp.broadcast_to(positions, (b, s))
    enc_len = (cross_kv["k"].shape[2] if cross_kv is not None
               else enc_out.shape[1])
    k_pos = jnp.broadcast_to(
        jnp.arange(enc_len, dtype=jnp.int32)[None], (b, enc_len))
    have_cache = self_caches is not None

    def body(x, xs):
        pj, ckv, cache_leaf = xs
        h = apply_norm(cfg, x, pj["norm1"])
        cache_j = KVCache(cache_leaf["k"], cache_leaf["v"], cache_pos) \
            if have_cache else None
        mix, nc = attention(cfg, pj["self_attn"], h, positions, rules,
                            causal=True, use_rope=False, cache=cache_j,
                            attn_impl=attn_impl)
        x = x + mix
        h = apply_norm(cfg, x, pj["norm_x"])
        if ckv is None:
            ck = jnp.einsum("bsd,dhk->bshk", enc_out, pj["cross_attn"]["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", enc_out, pj["cross_attn"]["wv"])
            ck = rules.constrain(ck, "batch", None, "kv_heads", "head_dim")
            cv = rules.constrain(cv, "batch", None, "kv_heads", "head_dim")
        else:
            ck, cv = ckv["k"], ckv["v"]
        cross, _ = attention(cfg, pj["cross_attn"], h, positions, rules,
                             causal=False, use_rope=False,
                             cross_kv=(ck, cv, k_pos),
                             attn_impl=attn_impl)
        x = x + cross
        h = apply_norm(cfg, x, pj["norm2"])
        x = x + mlp_apply(cfg, pj["ffn"], h, rules)
        return x, (_cache_leaves(nc) if have_cache else None)

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    caches_xs = self_caches if have_cache else None
    if exact_counts:
        ys = []
        for i in range(cfg.num_layers):
            xs_i = jax.tree.map(lambda a: a[i],
                                (params["decoder"], cross_kv, caches_xs))
            x, y = body(x, xs_i)
            ys.append(y)
        new_caches = jax.tree.map(lambda *ls: jnp.stack(ls), *ys) \
            if have_cache else None
    else:
        x, new_caches = jax.lax.scan(
            body, x, (params["decoder"], cross_kv, caches_xs))
    return apply_norm(cfg, x, params["final_norm"]), \
        (new_caches if have_cache else None)


def loss_fn(cfg: ArchConfig, params, batch, rules: ShardingRules, *,
            attn_impl: str = "auto", exact_counts: bool = False, **kw):
    enc_out = encode(cfg, params, batch["frames"], rules, remat=True,
                     attn_impl=attn_impl, exact_counts=exact_counts)
    x, _ = decode_trunk(cfg, params, batch["tokens"], rules, enc_out=enc_out,
                        remat=True, attn_impl=attn_impl,
                        exact_counts=exact_counts)
    logits = logits_fn(params, x, cfg, rules)
    loss = cross_entropy(logits, batch["targets"], cfg.vocab_size)
    return loss, {"ce": loss, "aux": jnp.zeros((), jnp.float32)}


def prefill_fn(cfg: ArchConfig, params, batch, caches, rules: ShardingRules,
               *, attn_impl: str = "auto", exact_counts: bool = False, **kw):
    enc_out = encode(cfg, params, batch["frames"], rules, remat=False,
                     attn_impl=attn_impl, exact_counts=exact_counts)
    ckv = _cross_kv(cfg, params, enc_out, rules)
    x, new_self = decode_trunk(
        cfg, params, batch["tokens"], rules, cross_kv=ckv,
        self_caches=caches["self"], cache_pos=jnp.zeros((), jnp.int32),
        remat=False, attn_impl=attn_impl, exact_counts=exact_counts)
    logits = logits_fn(params, x[:, -1:], cfg, rules)
    return logits, {"self": new_self, "cross": ckv}


def decode_fn(cfg: ArchConfig, params, batch, caches, rules: ShardingRules,
              *, attn_impl: str = "auto", exact_counts: bool = False, **kw):
    x, new_self = decode_trunk(
        cfg, params, batch["tokens"], rules, cross_kv=caches["cross"],
        self_caches=caches["self"], cache_pos=batch["pos"],
        remat=False, attn_impl=attn_impl, exact_counts=exact_counts)
    logits = logits_fn(params, x, cfg, rules)
    return logits, {"self": new_self, "cross": caches["cross"]}


def count_units(cfg: ArchConfig, shape, rules: ShardingRules):
    """Stitched-count units (see transformer.count_units): one encoder layer
    and one decoder layer, each compiled standalone by the dry-run."""
    from repro.models.sharding import tree_sds
    from repro.models import attention as attn_mod

    tp = rules.mesh.shape.get("model", 1)
    b = shape.global_batch
    s_dec = shape.seq_len if shape.kind != "decode" else 1
    s_enc = shape.seq_len
    d = cfg.d_model
    kvp, hd = cfg.padded_kv_heads(tp), cfg.head_dim

    x_enc = jax.ShapeDtypeStruct((b, s_enc, d), jnp.bfloat16,
                                 sharding=rules.named("batch", None, None))
    x_dec = jax.ShapeDtypeStruct((b, s_dec, d), jnp.bfloat16,
                                 sharding=rules.named("batch", None, None))
    enc_pj = tree_sds(_enc_layer_params(cfg, tp), rules)
    dec_pj = tree_sds(_dec_layer_params(cfg, tp), rules)

    pos_enc = jnp.broadcast_to  # built inside units (traced consts)

    units = []
    remat_train = shape.kind == "train"

    def enc_unit_fwd(x, pj):
        positions = jnp.broadcast_to(
            jnp.arange(s_enc, dtype=jnp.int32)[None], (b, s_enc))
        h = apply_norm(cfg, x, pj["norm1"])
        mix, _ = attention(cfg, pj["attn"], h, positions, rules,
                           causal=False, use_rope=False)
        x = x + mix
        h = apply_norm(cfg, x, pj["norm2"])
        return x + mlp_apply(cfg, pj["ffn"], h, rules)

    def dec_unit_fwd(x, pj, enc_out=None, ckv=None, cache_leaf=None,
                     cache_pos=None):
        ss = x.shape[1]
        off = cache_pos if cache_pos is not None else 0
        positions = jnp.broadcast_to(
            jnp.arange(ss, dtype=jnp.int32)[None] + off, (b, ss))
        k_pos = jnp.broadcast_to(
            jnp.arange(s_enc, dtype=jnp.int32)[None], (b, s_enc))
        h = apply_norm(cfg, x, pj["norm1"])
        cache_j = KVCache(cache_leaf["k"], cache_leaf["v"],
                          jnp.asarray(off, jnp.int32)) \
            if cache_leaf is not None else None
        mix, nc = attention(cfg, pj["self_attn"], h, positions, rules,
                            causal=True, use_rope=False, cache=cache_j)
        x = x + mix
        h = apply_norm(cfg, x, pj["norm_x"])
        if ckv is None:
            ck = jnp.einsum("bsd,dhk->bshk", enc_out, pj["cross_attn"]["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", enc_out, pj["cross_attn"]["wv"])
        else:
            ck, cv = ckv["k"], ckv["v"]
        cross, _ = attention(cfg, pj["cross_attn"], h, positions, rules,
                             causal=False, use_rope=False,
                             cross_kv=(ck, cv, k_pos))
        x = x + cross
        h = apply_norm(cfg, x, pj["norm2"])
        x = x + mlp_apply(cfg, pj["ffn"], h, rules)
        return x, (_cache_leaves(nc) if nc is not None else None)

    if shape.kind == "train":
        def enc_unit(x, pj):
            f = jax.checkpoint(enc_unit_fwd,
                               policy=jax.checkpoint_policies.nothing_saveable)
            return jax.value_and_grad(
                lambda x, pj: jnp.sum(f(x, pj).astype(jnp.float32)),
                argnums=(0, 1))(x, pj)

        def dec_unit(x, enc_out, pj):
            def f(x, enc_out, pj):
                y, _ = dec_unit_fwd(x, pj, enc_out=enc_out)
                return jnp.sum(y.astype(jnp.float32))
            f = jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)
            return jax.value_and_grad(f, argnums=(0, 1, 2))(x, enc_out, pj)

        units.append(("enc_layer_train", enc_unit, (x_enc, enc_pj),
                      cfg.n_encoder_layers - 1))
        units.append(("dec_layer_train", dec_unit, (x_dec, x_enc, dec_pj),
                      cfg.num_layers - 1))
        return units

    # serve units
    cache_leaf_sds = tree_sds(attn_mod.init_cache(cfg, b, shape.seq_len, tp),
                              rules)
    ckv_ax = ("batch", None, "kv_heads", "head_dim")
    ckv_sds = tree_sds(
        {"k": la((b, s_enc, kvp, hd), ckv_ax, jnp.bfloat16),
         "v": la((b, s_enc, kvp, hd), ckv_ax, jnp.bfloat16)}, rules)
    cache_pos_val = 0 if shape.kind == "prefill" else shape.seq_len - 1

    if shape.kind == "prefill":
        def enc_unit(x, pj):
            return enc_unit_fwd(x, pj)
        units.append(("enc_layer", enc_unit, (x_enc, enc_pj),
                      cfg.n_encoder_layers - 1))

        def dec_unit(x, enc_out, pj, cache_leaf):
            return dec_unit_fwd(x, pj, enc_out=enc_out,
                                cache_leaf=cache_leaf,
                                cache_pos=cache_pos_val)
        units.append(("dec_layer", dec_unit,
                      (x_dec, x_enc, dec_pj, cache_leaf_sds),
                      cfg.num_layers - 1))
    else:
        def dec_unit(x, pj, ckv, cache_leaf):
            return dec_unit_fwd(x, pj, ckv=ckv, cache_leaf=cache_leaf,
                                cache_pos=cache_pos_val)
        units.append(("dec_layer", dec_unit,
                      (x_dec, dec_pj, ckv_sds, cache_leaf_sds),
                      cfg.num_layers - 1))
    return units


def cache_specs(cfg: ArchConfig, batch: int, max_len: int, tp: int,
                enc_len: int):
    self_kv = _stack(attn_mod.init_cache(cfg, batch, max_len, tp),
                     cfg.num_layers)
    kv, hd = cfg.padded_kv_heads(tp), cfg.head_dim
    ax = ("batch", None, "kv_heads", "head_dim")
    cross = _stack(
        {"k": la((batch, enc_len, kv, hd), ax, jnp.bfloat16),
         "v": la((batch, enc_len, kv, hd), ax, jnp.bfloat16)},
        cfg.num_layers)
    return {"self": self_kv, "cross": cross}
