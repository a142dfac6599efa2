"""Mixture-of-Experts layer with GShard-style capacity dispatch.

This is the LM-side analogue of the paper's hash join (DESIGN.md §2): a small
random-access structure (the router's expert assignment) is resolved on-chip,
then the large side (the token stream) is gathered into per-expert buffers and
streamed through the expert matmuls at full bandwidth.  Tokens beyond expert
capacity are dropped (GShard semantics); the aux loss keeps the router
balanced, exactly like the paper keeps its channels balanced.

Dispatch is computed per batch row (group) so the position-in-expert cumsum
never crosses a sharded dimension — no collectives are generated by routing
itself; EP/expert-TP placement is decided purely by the sharding rules:
  * EP   (E % tp == 0): expert dim of weights/buffers -> "model"; each model
    shard computes its own experts, psum combines (same collective as TP FFN).
  * expert-TP          : every expert's d_ff -> "model" (granite-moe 40e).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.sharding import ShardingRules
from repro.models.common import la, mlp_apply, mlp_params, _act


def moe_params(cfg: ArchConfig, tp: int = 1) -> dict:
    e, d, f = cfg.padded_experts(tp), cfg.d_model, cfg.moe_d_ff
    p = {
        "router": la((d, e), (None, None), jnp.float32),
        "w_in": la((e, d, 2, f), ("experts", "fsdp", None, "moe_mlp")),
        "w_down": la((e, f, d), ("experts", "moe_mlp", "fsdp")),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_params(cfg, cfg.n_shared_experts * cfg.moe_d_ff)
    return p


def capacity(cfg: ArchConfig, seq: int,
             capacity_factor: Optional[float] = None) -> int:
    if capacity_factor is None:
        # top-1 routers ride the aux loss harder; Switch uses cf=1.0 there —
        # hillclimb #5: every (B,E,C,*) buffer shrinks 20% for llama4
        capacity_factor = 1.0 if cfg.top_k == 1 else 1.25
    c = int(-(-capacity_factor * seq * cfg.top_k // cfg.n_experts))
    return max(c, 1)


def moe_apply(cfg: ArchConfig, p: dict, x, rules: ShardingRules, *,
              capacity_factor: Optional[float] = None):
    """x: (B, S, d). Returns (y, aux_loss)."""
    b, s, d = x.shape
    e_real, k = cfg.n_experts, cfg.top_k
    e = p["w_in"].shape[0]                                # padded expert count
    c = capacity(cfg, s, capacity_factor)                 # density uses e_real

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    if e > e_real:                                        # mask padded experts
        logits = jnp.where(jnp.arange(e)[None, None, :] < e_real,
                           logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)               # (B,S,E) f32
    gate, ids = jax.lax.top_k(probs, k)                   # (B,S,k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    # Load-balance aux loss (Switch/GShard): E * sum_e f_e * p_e — via a
    # per-row segment count, NOT a (B,S,k,E) one-hot (hillclimb #1: the
    # one-hot/cumsum routing tensors dominated bytes AND collectives).
    flat_ids = ids.reshape(b, s * k)

    def _counts_row(e_r):
        return jnp.zeros((e,), jnp.float32).at[e_r].add(1.0)

    tokens_per_expert = jnp.mean(jax.vmap(_counts_row)(flat_ids),
                                 axis=0) / (s * 1.0)
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = e_real * jnp.sum(tokens_per_expert * mean_prob)

    # Position of each assignment within its expert, per batch row, via a
    # stable sort over (S*k,) instead of a cumsum over (S*k, E).
    def _pos_row(e_r):
        order = jnp.argsort(e_r, stable=True)             # (S*k,)
        sorted_e = e_r[order]
        # rank within equal-expert run: index - start-of-run
        idx = jnp.arange(s * k, dtype=jnp.int32)
        is_start = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), sorted_e[1:] != sorted_e[:-1]])
        run_start = jax.lax.associative_scan(
            jnp.maximum, jnp.where(is_start, idx, 0))
        rank_sorted = idx - run_start
        return jnp.zeros((s * k,), jnp.int32).at[order].set(rank_sorted)

    pos = jax.vmap(_pos_row)(flat_ids).reshape(b, s, k)
    keep = (pos < c)                                               # capacity drop
    gate = gate * keep.astype(gate.dtype)

    # ---- dispatch: scatter tokens into (B, E, C, d) expert buffers.
    # Alongside the data we scatter slot->token and slot->gate maps so the
    # combine can run shard-LOCALLY on the expert axis (a gather from the
    # E-sharded output would force an all-gather of the whole buffer; the
    # scatter-add formulation costs one activation psum, like a TP FFN).
    flat_e = ids.reshape(b, s * k)
    flat_pos = jnp.where(keep, pos, c).reshape(b, s * k)  # dropped -> row c (cut)
    tok_ids = jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32)[None, :, None], (b, s, k)
    ).reshape(b, s * k)
    src = x if k == 1 else jnp.repeat(x, k, axis=1)       # (B, S*k, d)

    # vmap over batch keeps batch a *batching* dim of the scatters — SPMD
    # partitions them over `data`; explicit batch index arrays would force
    # XLA to replicate the global activations (measured: ~95GB/layer of
    # f32 all-reduces before this formulation).
    # slot_gate kept in the activation dtype: an f32 gate would promote the
    # combine scatter and its psum to f32 (hillclimb #1: measured 2x wire)
    def _dispatch_row(src_r, e_r, pos_r, tok_r, gate_r):
        buf = jnp.zeros((e, c + 1, d), x.dtype).at[e_r, pos_r].add(src_r)
        st = jnp.full((e, c + 1), s, jnp.int32).at[e_r, pos_r].min(tok_r)
        sg = jnp.zeros((e, c + 1), x.dtype).at[e_r, pos_r].add(gate_r)
        return buf[:, :c], st[:, :c], sg[:, :c]

    buf, slot_tok, slot_gate = jax.vmap(_dispatch_row)(
        src, flat_e, flat_pos, tok_ids, gate.reshape(b, s * k).astype(x.dtype))

    buf = rules.constrain(buf, "batch", "experts", None, None)
    slot_tok = rules.constrain(slot_tok, "batch", "experts", None)
    slot_gate = rules.constrain(slot_gate, "batch", "experts", None)

    # ---- expert FFN (gated; gate+up fused into one grouped matmul) ------- #
    gu = jnp.einsum("becd,edgf->becgf", buf, p["w_in"])
    h = _act(cfg, gu[..., 0, :]) * gu[..., 1, :]
    h = rules.constrain(h, "batch", "experts", None, "moe_mlp")
    out = jnp.einsum("becf,efd->becd", h, p["w_down"])
    out = rules.constrain(out, "batch", "experts", None, None)

    # ---- combine: expert-local scatter-add into token space + psum -------- #
    out = out * slot_gate[..., None].astype(out.dtype)

    def _combine_row(out_r, tok_r):                      # (E,C,d), (E,C)
        return jnp.zeros((s + 1, d), x.dtype).at[tok_r].add(out_r)[:s]

    y = jax.vmap(_combine_row)(out, slot_tok)
    y = rules.constrain(y, "batch", None, None)

    if cfg.n_shared_experts:
        y = y + mlp_apply(cfg, p["shared"], x, rules)
    return y, aux
