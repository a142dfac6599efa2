"""Jit'd end-to-end joins (build + probe + materialize) with XLA fallback.

Two entry points:

* ``hash_join`` — the paper's unique-S fast path (open addressing, at most
  one match per probe row).  Its exactness bound is now SURFACED: the
  result carries ``overflowed``, true when the bounded build dropped more
  keys than the slow-path buffer can recover (those matches are lost).
* ``hash_join_multi`` — duplicate-capable multi-match join over the
  sorted-bucket layout.  Emits the exact multiset of (l_idx, s_idx) pairs
  as a fixed-capacity pair list; ``total`` is always the exact pair count,
  ``overflowed`` flags a truncated list (first ``max_out`` pairs kept, in
  (probe row, bucket position) order).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.join import ref
from repro.kernels.join.join import (
    DEFAULT_BLOCK, DEFAULT_MATCH_CAP, probe_multi_pallas, probe_pallas,
)


MAX_DROPPED = 256     # slow-path buffer for keys the bounded build dropped


class JoinResult(NamedTuple):
    """Unique-S join output: one line per probe row."""
    s_idx: jax.Array          # (N_L,) matched build index or -1
    total: jax.Array          # scalar: number of matches found
    dropped: jax.Array        # scalar: build keys the bounded build dropped
    overflowed: jax.Array     # scalar bool: dropped > MAX_DROPPED — the
                              # slow-path buffer overflowed and matches for
                              # the excess keys were silently LOST


class MultiJoinResult(NamedTuple):
    """Multi-match join output: a (l_idx, s_idx) pair list."""
    l_idx: jax.Array          # (max_out,) probe-side row or -1 padding
    s_idx: jax.Array          # (max_out,) build-side row or -1 padding
    total: jax.Array          # scalar: EXACT pair count (even if > max_out)
    overflowed: jax.Array     # scalar bool: total > max_out (list truncated)


@partial(jax.jit, static_argnames=("table_size", "probe_depth", "block",
                                   "impl", "interpret"))
def hash_join(s_keys, l_keys, *, table_size: int, probe_depth: int = 4,
              block: int = DEFAULT_BLOCK, impl: str = "xla",
              interpret: bool = False):
    """End-to-end naively-partitioned hash join (Algorithm 2), unique S.

    Build uses the (cheap, small-S) vectorized sequential-equivalent build;
    probe is the accelerated phase, exactly like the paper.  Keys the
    bounded build could not place (rare at load factor <= 0.5) take a
    direct-compare side path so the join is exact up to MAX_DROPPED drops;
    beyond that ``overflowed`` is set and callers must retry with a larger
    table (or the duplicate-capable ``hash_join_multi``, which never
    drops).  Returns ``JoinResult``.
    """
    ht_keys, ht_vals, placed = ref.build_table(s_keys, table_size,
                                               probe_depth)
    if impl == "pallas":
        s_idx, _ = probe_pallas(ht_keys, ht_vals, l_keys, block=block,
                                probe_depth=probe_depth, interpret=interpret)
    else:
        s_idx, _ = ref.probe_ref(ht_keys, ht_vals, l_keys, probe_depth)

    # slow path: gather (up to MAX_DROPPED) unplaced keys, compare directly
    n_s = s_keys.shape[0]
    drop_rank = jnp.cumsum((~placed).astype(jnp.int32)) - 1
    # overflow beyond MAX_DROPPED goes to the trash slot (sliced off) rather
    # than overwriting the last real buffer entry
    slot = jnp.where(~placed & (drop_rank < MAX_DROPPED), drop_rank,
                     MAX_DROPPED)
    drop_keys = jnp.full((MAX_DROPPED + 1,), -(2 ** 30), jnp.int32) \
        .at[slot].set(s_keys)[:MAX_DROPPED]
    drop_vals = jnp.full((MAX_DROPPED + 1,), -1, jnp.int32) \
        .at[slot].set(jnp.arange(n_s, dtype=jnp.int32))[:MAX_DROPPED]
    eq = l_keys[:, None] == drop_keys[None, :]          # (N_L, MAX_DROPPED)
    any_hit = jnp.any(eq, axis=1)
    which = jnp.argmax(eq, axis=1)
    s_idx = jnp.where((s_idx < 0) & any_hit, drop_vals[which], s_idx)

    total = jnp.sum((s_idx >= 0).astype(jnp.int32))
    dropped = jnp.sum((~placed).astype(jnp.int32))
    return JoinResult(s_idx, total, dropped, dropped > MAX_DROPPED)


@partial(jax.jit, static_argnames=("max_out", "cap", "block", "impl",
                                   "interpret"))
def hash_join_multi(s_keys, l_keys, *, max_out: int,
                    cap: int = DEFAULT_MATCH_CAP,
                    block: int = DEFAULT_BLOCK, impl: str = "xla",
                    interpret: bool = False):
    """Duplicate-capable multi-match join: the exact (l_idx, s_idx) pair
    multiset of ``s_keys ⋈ l_keys``, materialized into a (max_out,) pair
    list ordered by (probe row, bucket position).

    The XLA path emits with the exact gather formulation (no cap).  The
    Pallas path emits up to ``cap`` matches per probe row in-kernel; an
    XLA overflow pass materializes the tail of longer chains, so both
    paths produce identical pair lists.  Returns ``MultiJoinResult``.

    Key domain: int32 in (-2**30, 2**31 - 1) exclusive — the top value is
    the Pallas table's pad sentinel and the bottom range is reserved for
    the distributed operator's pass-padding sentinels; keys outside it
    can produce phantom matches on one impl but not the other.
    """
    n_s, n_l = s_keys.shape[0], l_keys.shape[0]
    if n_s == 0 or n_l == 0:
        empty = jnp.full((max_out,), -1, jnp.int32)
        return MultiJoinResult(empty, empty, jnp.zeros((), jnp.int32),
                               jnp.zeros((), jnp.bool_))
    s_sorted, order = ref.bucket_build(s_keys)
    if impl == "pallas":
        mat, start, counts = probe_multi_pallas(
            s_sorted, order, l_keys, cap=cap, block=block,
            interpret=interpret)
        l_idx, s_idx, total = _assemble_capped(mat, order, start, counts,
                                               max_out, cap)
    else:
        start, counts = ref.bucket_probe(s_sorted, l_keys)
        l_buf = jnp.full((max_out,), -1, jnp.int32)
        s_buf = jnp.full((max_out,), -1, jnp.int32)
        l_idx, s_idx, total = ref.emit_pairs_into(
            l_buf, s_buf, order, start, counts, out_base=0)
    return MultiJoinResult(l_idx, s_idx, total, total > max_out)


def _assemble_capped(mat, order, start, counts, max_out: int, cap: int):
    """Pair list from the kernel's capped egress + an overflow pass.

    In-cap matches scatter straight from the kernel's (N_L, cap) matrix to
    their global pair rank; chains longer than the cap get their tail
    materialized by the same gather formulation the XLA path uses,
    restricted to the residual counts — so the cap is a bus width, not a
    correctness limit."""
    n_l = counts.shape[0]
    base = jnp.cumsum(counts) - counts
    total = jnp.sum(counts)
    rows = jnp.arange(n_l, dtype=jnp.int32)
    l_buf = jnp.full((max_out + 1,), -1, jnp.int32)   # +1 = trash slot
    s_buf = jnp.full((max_out + 1,), -1, jnp.int32)
    for k in range(cap):                               # in-cap egress lines
        pos = base + k
        ok = (k < counts) & (pos < max_out)
        tpos = jnp.where(ok, pos, max_out)
        l_buf = l_buf.at[tpos].set(jnp.where(ok, rows, -1))
        s_buf = s_buf.at[tpos].set(jnp.where(ok, mat[:, k], -1))
    # overflow pass: ragged chain tails (match k >= cap)
    res = jnp.maximum(counts - cap, 0)
    rbase = jnp.cumsum(res) - res
    rtotal = jnp.sum(res)
    t = jnp.arange(max_out, dtype=jnp.int32)
    i = jnp.clip(jnp.searchsorted(rbase, t, side="right").astype(jnp.int32)
                 - 1, 0, n_l - 1)
    k2 = t - rbase[i]
    pos = base[i] + cap + k2
    sval = order[jnp.clip(start[i] + cap + k2, 0, order.shape[0] - 1)]
    ok = (t < rtotal) & (pos < max_out)
    tpos = jnp.where(ok, pos, max_out)
    l_buf = l_buf.at[tpos].set(jnp.where(ok, i, -1))
    s_buf = s_buf.at[tpos].set(jnp.where(ok, sval, -1))
    return l_buf[:max_out], s_buf[:max_out], total


def materialize(s_idx, l_values, s_values):
    """The paper's materialization: emit matched (S_out, L_out) columns with
    dummies where s_idx == -1 (lane-aligned like the FPGA's assemble)."""
    hit = s_idx >= 0
    s_out = jnp.where(hit, s_values[jnp.clip(s_idx, 0, None)], -1)
    l_out = jnp.where(hit, l_values, -1)
    return s_out, l_out


def materialize_pairs(l_idx, s_idx, l_values, s_values):
    """Multi-match materialization: gather the value columns for a pair
    list (the BAT-pair contract), -1 where the list is padding."""
    hit = l_idx >= 0
    l_out = jnp.where(hit, l_values[jnp.clip(l_idx, 0, None)], -1)
    s_out = jnp.where(hit, s_values[jnp.clip(s_idx, 0, None)], -1)
    return l_out, s_out
