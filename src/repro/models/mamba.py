"""Mamba-2 block (SSD, state-space duality — arXiv:2405.21060).

Training/prefill uses the chunked SSD algorithm: intra-chunk quadratic term +
inter-chunk state recurrence (lax.scan).  Decode is a single state update.
This pure-XLA path is the oracle for the Pallas kernel in
``repro.kernels.ssd``; the chunk length matches the kernel block size.

Sharding: SSD heads shard over ``model`` (mamba2: 48 heads / 16 = 3);
B/C are per-group (ngroups=1) and stay replicated; d_model projections are
FSDP-sharded like every other weight.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.sharding import ShardingRules
from repro.models.common import la

# 128 (not 256): the intra-chunk (B,NC,nh,Q,Q) tensors scale with Q per
# token — hillclimb #3 halved SSD memory traffic by halving the chunk
SSD_CHUNK = 128


def ssm_params(cfg: ArchConfig) -> dict:
    d, di, ds = cfg.d_model, cfg.d_inner, cfg.ssm_state
    nh, ng, w = cfg.n_ssm_heads, cfg.ssm_groups, cfg.ssm_conv_width
    return {
        "w_x": la((d, di), ("fsdp", "ssm_heads")),
        "w_z": la((d, di), ("fsdp", "ssm_heads")),
        "w_b": la((d, ng * ds), ("fsdp", None)),
        "w_c": la((d, ng * ds), ("fsdp", None)),
        "w_dt": la((d, nh), ("fsdp", "ssm_heads")),
        "dt_bias": la((nh,), ("ssm_heads",), jnp.float32),
        "a_log": la((nh,), ("ssm_heads",), jnp.float32),
        "d_skip": la((nh,), ("ssm_heads",), jnp.float32),
        "conv_w": la((w, di + 2 * ng * ds), (None, None)),
        "norm": la((di,), ("ssm_heads",)),
        "w_out": la((di, d), ("ssm_heads", "fsdp")),
    }


class SSMCache(NamedTuple):
    conv: jax.Array     # (B, w-1, di + 2*ng*ds) — rolling conv inputs
    state: jax.Array    # (B, nh, hd, ds) f32


def _causal_conv(u, w):
    """Depthwise causal conv via stacked shifts. u (B,S,C), w (W,C)."""
    width = w.shape[0]
    acc = u * w[-1][None, None, :]
    for i in range(1, width):
        shifted = jnp.pad(u, ((0, 0), (i, 0), (0, 0)))[:, :-i]
        acc = acc + shifted * w[-1 - i][None, None, :]
    return acc


def _segsum(a):
    """Stable 'segment sum': out[..., i, j] = sum_{j<t<=i} a[..., t] (i >= j)."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, a_log, b, c, d_skip, chunk: int = SSD_CHUNK):
    """SSD forward. x (B,S,nh,hd); dt (B,S,nh) f32 (post-softplus);
    b, c (B,S,ng,ds); returns y (B,S,nh,hd) and final state (B,nh,hd,ds)."""
    bsz, s, nh, hd = x.shape
    ng, ds = b.shape[-2], b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    rep = nh // ng

    # Large intra-chunk tensors ((B,NC,nh,Q,Q) masks/scores and the (…,hd)
    # operands) are kept in bf16 with f32 einsum accumulation — hillclimb #3
    # halved SSD memory traffic; decay exponents stay f32 for stability.
    # the x path stays bf16 end-to-end (an f32 entry cast here makes every
    # BACKWARD cotangent of the conv/projection chain f32 — hillclimb #4)
    cdt = jnp.bfloat16
    a = -jnp.exp(a_log)[None, None, :] * dt                  # (B,S,nh) log-decay
    xdt = x.astype(cdt) * dt[..., None].astype(cdt)

    # chunk views
    ac = a.reshape(bsz, nc, chunk, nh)
    xc = xdt.reshape(bsz, nc, chunk, nh, hd)
    bc = jnp.repeat(b, rep, axis=2).reshape(bsz, nc, chunk, nh, ds).astype(cdt)
    cc = jnp.repeat(c, rep, axis=2).reshape(bsz, nc, chunk, nh, ds).astype(cdt)

    cum = jnp.cumsum(ac, axis=2)                              # (B,NC,Q,nh) f32

    # ---- intra-chunk (quadratic within chunk) ---------------------------- #
    l = jnp.exp(_segsum(ac.transpose(0, 1, 3, 2))).astype(cdt)  # (B,NC,nh,Q,Q)
    scores = (jnp.einsum("bnqhs,bnkhs->bnhqk", cc, bc,
                         preferred_element_type=jnp.float32)
              .astype(cdt) * l)
    y_intra = jnp.einsum("bnhqk,bnkhd->bnqhd", scores, xc,
                         preferred_element_type=jnp.float32)

    # ---- chunk-final states ---------------------------------------------- #
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum).astype(cdt)  # (B,NC,Q,nh)
    states = jnp.einsum("bnqhs,bnqhd,bnqh->bnhds", bc, xc, decay_to_end,
                        preferred_element_type=jnp.float32)

    # ---- inter-chunk recurrence (associative scan: parallel on TPU and
    # fully visible to cost_analysis, unlike a while loop) ------------------ #
    total = jnp.exp(cum[:, :, -1, :])                         # (B,NC,nh)

    def combine(a, b_):
        (ha, ta), (hb, tb) = a, b_
        return ha * tb[..., None, None] + hb, ta * tb

    h_inc, _ = jax.lax.associative_scan(
        combine, (states, total), axis=1)                     # inclusive
    h_last = h_inc[:, -1]
    # exclusive prefix: state entering each chunk
    h_prevs = jnp.concatenate(
        [jnp.zeros_like(h_inc[:, :1]), h_inc[:, :-1]], axis=1)

    # h_prevs indexed as [b, n, h, d(=hd), s(=ds)]
    y_inter = jnp.einsum("bnqhs,bnhds,bnqh->bnqhd",
                         cc, h_prevs.astype(cdt), jnp.exp(cum).astype(cdt),
                         preferred_element_type=jnp.float32)
    y = (y_intra + y_inter).reshape(bsz, s, nh, hd)
    y = y + d_skip[None, None, :, None].astype(jnp.float32) * \
        x.astype(jnp.float32)
    return y.astype(x.dtype), h_last


def ssd_decode_step(x, dt, a_log, b, c, d_skip, state):
    """One-token SSD update. x (B,1,nh,hd); state (B,nh,hd,ds) f32."""
    xf = x[:, 0].astype(jnp.float32)                          # (B,nh,hd)
    dt0 = dt[:, 0]                                            # (B,nh)
    da = jnp.exp(-jnp.exp(a_log)[None, :] * dt0)              # (B,nh)
    rep = x.shape[2] // b.shape[2]
    b0 = jnp.repeat(b[:, 0], rep, axis=1).astype(jnp.float32)  # (B,nh,ds)
    c0 = jnp.repeat(c[:, 0], rep, axis=1).astype(jnp.float32)
    upd = (dt0[..., None] * xf)[..., None] * b0[:, :, None, :]  # (B,nh,hd,ds)
    state = state * da[:, :, None, None] + upd
    y = jnp.einsum("bhds,bhs->bhd", state, c0) + d_skip[None, :, None] * xf
    return y[:, None].astype(x.dtype), state


def mamba_block(cfg: ArchConfig, p: dict, x, rules: ShardingRules,
                cache: Optional[SSMCache] = None):
    """Full Mamba-2 mixer. x (B,S,d_model). Returns (y, new_cache)."""
    bsz, s, _ = x.shape
    di, ds = cfg.d_inner, cfg.ssm_state
    nh, ng, w = cfg.n_ssm_heads, cfg.ssm_groups, cfg.ssm_conv_width
    hd = cfg.ssm_head_dim

    xs = jnp.einsum("bsd,de->bse", x, p["w_x"])
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    bb = jnp.einsum("bsd,de->bse", x, p["w_b"])
    cc = jnp.einsum("bsd,de->bse", x, p["w_c"])
    dt_raw = jnp.einsum("bsd,dh->bsh", x, p["w_dt"]).astype(jnp.float32)
    xs = rules.constrain(xs, "batch", None, "ssm_heads")
    z = rules.constrain(z, "batch", None, "ssm_heads")

    conv_in = jnp.concatenate([xs, bb, cc], axis=-1)          # (B,S,di+2*ng*ds)
    if cache is not None:
        full = jnp.concatenate([cache.conv.astype(conv_in.dtype), conv_in], axis=1)
        conv_out = _causal_conv(full, p["conv_w"])[:, w - 1:]
        new_conv = full[:, -(w - 1):]
    else:
        conv_out = _causal_conv(conv_in, p["conv_w"])
        new_conv = conv_in[:, -(w - 1):]
    conv_out = jax.nn.silu(conv_out)

    xs = conv_out[..., :di].reshape(bsz, s, nh, hd)
    bb = conv_out[..., di:di + ng * ds].reshape(bsz, s, ng, ds)
    cc = conv_out[..., di + ng * ds:].reshape(bsz, s, ng, ds)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"][None, None, :])

    if cache is not None and s == 1:
        y, new_state = ssd_decode_step(xs, dt, p["a_log"], bb, cc,
                                       p["d_skip"], cache.state)
    else:
        chunk = SSD_CHUNK if s % SSD_CHUNK == 0 else (s if s < SSD_CHUNK else 1)
        if s % SSD_CHUNK and s > SSD_CHUNK:
            # pad to a chunk multiple (masked by zero dt contribution)
            pad = SSD_CHUNK - s % SSD_CHUNK
            xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            bb = jnp.pad(bb, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cc = jnp.pad(cc, ((0, 0), (0, pad), (0, 0), (0, 0)))
            y, new_state = ssd_chunked(xs, dt, p["a_log"], bb, cc, p["d_skip"])
            y = y[:, :s]
        else:
            y, new_state = ssd_chunked(xs, dt, p["a_log"], bb, cc,
                                       p["d_skip"], chunk=chunk)

    y = y.reshape(bsz, s, di)
    y = rules.constrain(y, "batch", None, "ssm_heads")

    # gated RMS norm (mamba2's z-gating) — bf16 tensor path, f32 statistics
    yg = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(yg.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    scale = (jax.lax.rsqrt(var + 1e-5) *
             (1.0 + p["norm"].astype(jnp.float32)[None, None, :]))
    y = (yg * scale.astype(yg.dtype)).astype(x.dtype)

    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    out = rules.constrain(out, "batch", None, None)
    new_cache = SSMCache(conv=new_conv, state=new_state) if cache is not None \
        else None
    return out, new_cache


def init_ssm_cache_spec(cfg: ArchConfig, batch: int) -> dict:
    di, ds = cfg.d_inner, cfg.ssm_state
    nh, ng, w = cfg.n_ssm_heads, cfg.ssm_groups, cfg.ssm_conv_width
    return {
        "conv": la((batch, w - 1, di + 2 * ng * ds),
                   ("batch", None, None), jnp.bfloat16),
        "state": la((batch, nh, cfg.ssm_head_dim, ds),
                    ("batch", "ssm_heads", None, None), jnp.float32),
    }
