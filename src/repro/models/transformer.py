"""Decoder-only LM covering the dense / moe / hybrid / ssm / vlm families.

Layers are organized as ``n_super`` superblocks of period ``P`` =
lcm(attn_every, moe_every): the layer schedule repeats with period P, so the
parameter pytree stacks each position's params over superblocks and a single
``lax.scan`` covers the whole depth — HLO stays O(P) regardless of depth,
which keeps 512-way SPMD compiles tractable and mirrors MaxText's scanned
layers.  Remat wraps the superblock body.

Caches (KV for attention positions, conv+state for SSM positions) are stacked
the same way and scanned alongside the params.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models.sharding import LogicalArray, ShardingRules
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models.attention import KVCache, attention, attn_params
from repro.models.common import (
    apply_norm, cross_entropy, embed_params, embed_tokens, la, logits_fn,
    mlp_apply, mlp_params,
)
from repro.models.mamba import SSMCache, mamba_block, ssm_params
from repro.models.moe import moe_apply, moe_params


def _period(cfg: ArchConfig) -> int:
    p = 1
    if cfg.family in ("hybrid", "ssm"):
        p = math.lcm(p, cfg.attn_every if cfg.family == "hybrid" else 1)
    if cfg.n_experts:
        p = math.lcm(p, cfg.moe_every)
    assert cfg.num_layers % p == 0, (cfg.name, cfg.num_layers, p)
    return p


def _position_params(cfg: ArchConfig, tp: int, j: int) -> dict:
    d: dict[str, Any] = {"norm1": la((cfg.d_model,), (None,))}
    if cfg.layer_is_attn(j):
        d["attn"] = attn_params(cfg, tp)
    else:
        d["ssm"] = ssm_params(cfg)
    if cfg.family != "ssm":
        d["norm2"] = la((cfg.d_model,), (None,))
        if cfg.layer_is_moe(j):
            d["moe"] = moe_params(cfg, tp)
        else:
            d["ffn"] = mlp_params(cfg, cfg.d_ff)
    return d


def _stack(tree, n: int):
    """Add a leading superblock dim to every LogicalArray leaf."""
    return jax.tree.map(
        lambda x: LogicalArray((n,) + x.shape, (None,) + x.logical, x.dtype),
        tree, is_leaf=lambda x: isinstance(x, LogicalArray))


def init_params(cfg: ArchConfig, tp: int) -> dict:
    p = _period(cfg)
    n_super = cfg.num_layers // p
    layers = tuple(_stack(_position_params(cfg, tp, j), n_super)
                   for j in range(p))
    params = dict(embed_params(cfg, tp))
    params["layers"] = layers
    params["final_norm"] = la((cfg.d_model,), (None,))
    return params


def _block(cfg, pj, x, positions, rules, cache_j, j, *, attn_impl="auto"):
    h = apply_norm(cfg, x, pj["norm1"])
    if "attn" in pj:
        mix, new_c = attention(cfg, pj["attn"], h, positions, rules,
                               causal=True, cache=cache_j,
                               attn_impl=attn_impl)
    else:
        mix, new_c = mamba_block(cfg, pj["ssm"], h, rules, cache=cache_j)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if cfg.family != "ssm":
        h = apply_norm(cfg, x, pj["norm2"])
        if "moe" in pj:
            y, aux = moe_apply(cfg, pj["moe"], h, rules)
        else:
            y = mlp_apply(cfg, pj["ffn"], h, rules)
        x = x + y
    return x, new_c, aux


def _make_cache_obj(cache_leaves, pos):
    if cache_leaves is None:
        return None
    if "k" in cache_leaves:
        return KVCache(cache_leaves["k"], cache_leaves["v"], pos)
    return SSMCache(cache_leaves["conv"], cache_leaves["state"])


def _cache_leaves(obj):
    if obj is None:
        return None
    if isinstance(obj, KVCache):
        return {"k": obj.k, "v": obj.v}
    return {"conv": obj.conv, "state": obj.state}


REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_saveable,
    "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def scan_body_factory(cfg: ArchConfig, rules: ShardingRules, positions,
                      cache_pos, have_cache: bool, attn_impl: str,
                      remat: bool, remat_policy: str = "nothing"):
    """One superblock step (carry, xs) -> (carry, ys).  Shared between the
    rolled scan, the unrolled exact-count path, and the stitched flop-count
    unit the dry-run compiles standalone."""
    p = _period(cfg)

    def body(carry, xs):
        x, aux = carry
        layer_ps, layer_cs = xs
        new_cs = []
        for j in range(p):
            cache_j = _make_cache_obj(layer_cs[j], cache_pos) if have_cache \
                else None
            x, nc, a = _block(cfg, layer_ps[j], x, positions, rules, cache_j,
                              j, attn_impl=attn_impl)
            new_cs.append(_cache_leaves(nc))
            aux = aux + a
        return (x, aux), tuple(new_cs)

    if remat:
        body = jax.checkpoint(body, policy=REMAT_POLICIES[remat_policy])
    return body


def forward(cfg: ArchConfig, params: dict, tokens, rules: ShardingRules, *,
            positions=None, caches=None, cache_pos=None,
            vision_embeds=None, remat: bool = True, attn_impl: str = "auto",
            exact_counts: bool = False, remat_policy: str = "nothing"):
    """Shared trunk. tokens (B,S). Returns (x_final, new_caches, aux_sum).

    exact_counts=True unrolls the superblock scan into a Python loop so the
    dry-run's ``cost_analysis`` sees every layer (a while-loop body is
    counted once).  Math is identical; tests assert both paths agree.
    """
    p = _period(cfg)
    n_super = cfg.num_layers // p
    b, s = tokens.shape

    x = embed_tokens(params, tokens, rules)
    if vision_embeds is not None:
        x = jax.lax.dynamic_update_slice(
            x, vision_embeds.astype(x.dtype), (0, 0, 0))
    if positions is None:
        base = jnp.arange(s, dtype=jnp.int32)[None, :] + (
            cache_pos if cache_pos is not None else 0)
        positions = jnp.broadcast_to(base, (b, s))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[..., None], (b, s, 3))

    have_cache = caches is not None
    body = scan_body_factory(cfg, rules, positions, cache_pos, have_cache,
                             attn_impl, remat, remat_policy)

    layer_caches = caches if have_cache else tuple(None for _ in range(p))
    carry = (x, jnp.zeros((), jnp.float32))
    if exact_counts:
        ys = []
        for i in range(n_super):
            xs_i = jax.tree.map(lambda a: a[i],
                                (params["layers"], layer_caches))
            carry, y = body(carry, xs_i)
            ys.append(y)
        new_caches = jax.tree.map(lambda *ls: jnp.stack(ls), *ys) \
            if have_cache else None
        (x, aux) = carry
    else:
        (x, aux), new_caches = jax.lax.scan(
            body, carry, (params["layers"], layer_caches))
        new_caches = new_caches if have_cache else None

    x = apply_norm(cfg, x, params["final_norm"])
    return x, (new_caches if have_cache else None), aux


def loss_fn(cfg: ArchConfig, params, batch, rules: ShardingRules, *,
            aux_weight: float = 0.01, attn_impl: str = "auto",
            exact_counts: bool = False, remat_policy: str = "nothing"):
    x, _, aux = forward(cfg, params, batch["tokens"], rules,
                        positions=batch.get("positions"),
                        vision_embeds=batch.get("vision_embeds"),
                        remat=True, attn_impl=attn_impl,
                        exact_counts=exact_counts, remat_policy=remat_policy)
    logits = logits_fn(params, x, cfg, rules)
    loss = cross_entropy(logits, batch["targets"], cfg.vocab_size)
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


def prefill_fn(cfg: ArchConfig, params, batch, caches, rules: ShardingRules,
               *, attn_impl: str = "auto", exact_counts: bool = False):
    """Populate caches from a full prompt; return last-token logits."""
    x, new_caches, _ = forward(
        cfg, params, batch["tokens"], rules,
        positions=batch.get("positions"),
        vision_embeds=batch.get("vision_embeds"),
        caches=caches, cache_pos=jnp.zeros((), jnp.int32),
        remat=False, attn_impl=attn_impl, exact_counts=exact_counts)
    logits = logits_fn(params, x[:, -1:], cfg, rules)
    return logits, new_caches


def decode_fn(cfg: ArchConfig, params, batch, caches, rules: ShardingRules,
              *, attn_impl: str = "auto", exact_counts: bool = False):
    """One decode step. batch: tokens (B,1), pos () int32."""
    pos = batch["pos"]
    b = batch["tokens"].shape[0]
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))
    x, new_caches, _ = forward(
        cfg, params, batch["tokens"], rules, positions=positions,
        caches=caches, cache_pos=pos, remat=False, attn_impl=attn_impl,
        exact_counts=exact_counts)
    logits = logits_fn(params, x, cfg, rules)
    return logits, new_caches


# --------------------------------------------------------------------------- #
# stitched flop counting (dry-run): the rolled scan's while body is counted
# once by cost_analysis, so the dry-run also compiles ONE superblock body
# standalone and adds (n_super - 1) x its counts.  Tests cross-check this
# against the fully unrolled exact_counts path.
# --------------------------------------------------------------------------- #

def count_units(cfg: ArchConfig, shape, rules: ShardingRules,
                remat_policy: str = "nothing"):
    """Returns [(name, fn, args_sds, multiplier)] for the dry-run to compile."""
    from repro.models.sharding import tree_sds   # local to avoid cycle
    from repro.models import attention as attn_mod
    from repro.models import mamba as mamba_mod

    p = _period(cfg)
    n_super = cfg.num_layers // p
    if n_super <= 1:
        return []
    tp = rules.mesh.shape.get("model", 1)
    b = shape.global_batch
    s = shape.seq_len if shape.kind != "decode" else 1

    x_sds = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16,
                                 sharding=rules.named("batch", None, None))
    lps_tree = tuple(_position_params(cfg, tp, j) for j in range(p))
    lps_sds = tree_sds(lps_tree, rules)

    def positions_for(bb, ss):
        off = shape.seq_len - 1 if shape.kind == "decode" else 0
        base = jnp.arange(ss, dtype=jnp.int32)[None, :] + off
        pos = jnp.broadcast_to(base, (bb, ss))
        if cfg.mrope_sections is not None:
            pos = jnp.broadcast_to(pos[..., None], (bb, ss, 3))
        return pos

    if shape.kind == "train":
        def unit(x, lps):
            body = scan_body_factory(cfg, rules, positions_for(b, s), None,
                                     False, "auto", remat=True,
                                     remat_policy=remat_policy)

            def f(x, lps):
                (y, aux), _ = body((x, jnp.zeros((), jnp.float32)),
                                   (lps, tuple(None for _ in range(p))))
                return jnp.sum(y.astype(jnp.float32)) + aux

            # value_and_grad (not grad): the scan in the real train step keeps
            # the primal carry, so the unit must count the primal fwd too.
            val, (gx, glps) = jax.value_and_grad(f, argnums=(0, 1))(x, lps)
            return val, gx, glps

        return [("superblock_train", unit, (x_sds, lps_sds), n_super - 1)]

    # serve steps: fwd-only unit with cache slice
    cache_pos_val = 0 if shape.kind == "prefill" else shape.seq_len - 1
    lcs_tree = []
    for j in range(p):
        if cfg.layer_is_attn(j):
            lcs_tree.append(attn_mod.init_cache(cfg, b, shape.seq_len, tp))
        elif cfg.family in ("hybrid", "ssm"):
            lcs_tree.append(mamba_mod.init_ssm_cache_spec(cfg, b))
        else:
            lcs_tree.append(None)
    lcs_sds = tree_sds(tuple(lcs_tree), rules)

    def unit(x, lps, lcs):
        body = scan_body_factory(
            cfg, rules, positions_for(b, s),
            jnp.asarray(cache_pos_val, jnp.int32), True, "auto", remat=False)
        (y, _), new_cs = body((x, jnp.zeros((), jnp.float32)), (lps, lcs))
        return y, new_cs

    return [(f"superblock_{shape.kind}", unit, (x_sds, lps_sds, lcs_sds),
             n_super - 1)]


# --------------------------------------------------------------------------- #
# cache construction
# --------------------------------------------------------------------------- #

def cache_specs(cfg: ArchConfig, batch: int, max_len: int, tp: int):
    """Stacked cache LogicalArrays per superblock position."""
    p = _period(cfg)
    n_super = cfg.num_layers // p
    out = []
    for j in range(p):
        if cfg.layer_is_attn(j):
            leaf = attn_mod.init_cache(cfg, batch, max_len, tp)
        elif cfg.family in ("hybrid", "ssm"):
            leaf = mamba_mod.init_ssm_cache_spec(cfg, batch)
        else:
            leaf = None
        out.append(_stack(leaf, n_super) if leaf is not None else None)
    return tuple(out)
