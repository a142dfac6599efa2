"""Scale-out hash join (paper §V) over the device mesh.

MonetDB's naive partitioning maps 1:1 onto the mesh: L is range-partitioned
across engines (each streams its own channel), S's hash table is REPLICATED
per engine — the paper replicates it per probe pipeline in URAM; across
chips the replication is a broadcast, within a chip VMEM's vector gather
replaces the 16 physical copies (DESIGN.md).  When S exceeds the on-chip
table capacity the operator falls back to multi-pass probing (rescanning L
per S block), reproducing the linear regime of Fig. 8b.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.channels import ChannelPlan
from repro.distributed import sharding as shardlib
from repro.kernels.join import join as join_join
from repro.kernels.join import ref as join_ref
from repro.kernels.join import ops as join_ops
from repro.kernels.join.join import DEFAULT_BLOCK

HT_CAPACITY = 8192            # tuples per pass — the paper's URAM budget


def join_distributed(s_keys, l_keys, plan: ChannelPlan, *,
                     table_size: int = 4 * HT_CAPACITY,
                     probe_depth: int = 8, block: int = DEFAULT_BLOCK,
                     impl: str = "xla", interpret: bool = False):
    """s_keys (N_S,) replicated; l_keys (N_L,) partitioned per plan.
    Returns (s_idx per L position (N_L,), total matches).

    Multi-pass when N_S > HT_CAPACITY: L is rescanned once per S block —
    the linear runtime increase of Fig. 8b.
    """
    mesh, axis = plan.mesh, plan.axis
    n_s = s_keys.shape[0]
    n_passes = -(-n_s // HT_CAPACITY)
    pad_s = n_passes * HT_CAPACITY - n_s
    if pad_s:
        # distinct negative sentinels: build_table needs unique keys, and a
        # block of identical pads would flood the bounded build's drop buffer
        # and silently evict genuinely dropped keys (missed matches)
        pads = -(2 ** 30) - jnp.arange(pad_s, dtype=jnp.int32)
        s_keys = jnp.concatenate([s_keys, pads])

    def engine(l_local):
        s_idx = jnp.full(l_local.shape, -1, jnp.int32)
        dropped_max = jnp.zeros((), jnp.int32)
        for p in range(n_passes):                     # rescan L per S block
            s_blk = jax.lax.dynamic_slice_in_dim(
                s_keys, p * HT_CAPACITY, HT_CAPACITY)
            res = join_ops.hash_join(
                s_blk, l_local, table_size=table_size,
                probe_depth=probe_depth, block=block, impl=impl,
                interpret=interpret)
            idx_p = res.s_idx
            s_idx = jnp.where((s_idx < 0) & (idx_p >= 0),
                              idx_p + p * HT_CAPACITY, s_idx)
            dropped_max = jnp.maximum(dropped_max,
                                      res.dropped.astype(jnp.int32))
        count = jnp.sum((s_idx >= 0).astype(jnp.int32))
        return s_idx, count[None], dropped_max[None]

    fn = shard_map(engine, mesh=mesh, in_specs=(P(axis),),
                   out_specs=(P(axis), P(axis), P(axis)), check_vma=False)
    s_idx, counts, dropped = fn(l_keys)
    if not isinstance(dropped, jax.core.Tracer):
        # eager callers get the exactness bound surfaced; under jit the
        # check is skipped (no host sync inside a trace)
        worst = int(jnp.max(dropped))
        if worst > join_ops.MAX_DROPPED:
            warnings.warn(
                f"hash-join build dropped {worst} keys in one pass, more "
                f"than the MAX_DROPPED={join_ops.MAX_DROPPED} slow-path "
                "buffer: overflowing keys match nothing (undercount). "
                "Increase table_size or probe_depth.", RuntimeWarning,
                stacklevel=2)
    return s_idx, jnp.sum(counts)


def join_distributed_multi(s_keys, l_keys, plan: ChannelPlan, *,
                           max_out_per_shard: int = None,
                           block: int = DEFAULT_BLOCK,
                           impl: str = "xla", interpret: bool = False):
    """Duplicate-capable scale-out join: s_keys (N_S,) replicated (may hold
    duplicate keys), l_keys (N_L,) partitioned per plan.  Keys must be in
    [0, 2**31 - 2]: negative values collide with the multi-pass padding
    sentinels below and 2**31 - 1 is the Pallas table pad (the eager
    engine layer validates this; jitted callers must guarantee it).

    Every engine probes its L shard against the sorted-bucket layout of S
    and materializes its slice of the GLOBAL (l_idx, s_idx) pair multiset
    into a fixed per-shard pair list (output compaction happens per shard:
    each shard's pairs are contiguous, -1-padded to ``max_out_per_shard``).
    Multi-pass beyond HT_CAPACITY rescans L per S block, appending each
    pass's pairs at a running offset — the Fig. 8b linear regime, now with
    variable-cardinality output.

    Returns (l_idx (N_SHARDS*max_out,) with GLOBAL probe positions,
    s_idx likewise, per-shard exact pair totals (N_SHARDS,), per-shard
    overflow flags (N_SHARDS,)).  ``total`` stays exact even when a shard's
    list overflows, so callers can re-run with the right capacity.
    """
    mesh, axis = plan.mesh, plan.axis
    n_shards = mesh.shape[axis]
    n_s = s_keys.shape[0]
    shard = l_keys.shape[0] // n_shards
    if max_out_per_shard is None:
        max_out_per_shard = max(2 * shard, 64)
    max_out = max_out_per_shard
    n_passes = -(-n_s // HT_CAPACITY) if n_s else 0
    pad_s = n_passes * HT_CAPACITY - n_s
    if pad_s:
        # negative sentinels sort below every real (non-negative) key and
        # can never equal a probe key, so padded buckets are never matched
        pads = -(2 ** 30) - jnp.arange(pad_s, dtype=jnp.int32)
        s_keys = jnp.concatenate([s_keys, pads])

    def engine(l_local):
        shard_id = jax.lax.axis_index(axis)
        l_buf = jnp.full((max_out,), -1, jnp.int32)
        s_buf = jnp.full((max_out,), -1, jnp.int32)
        total = jnp.zeros((), jnp.int32)
        for p in range(n_passes):                     # rescan L per S block
            s_blk = jax.lax.dynamic_slice_in_dim(
                s_keys, p * HT_CAPACITY, HT_CAPACITY)
            s_sorted, order = join_ref.bucket_build(s_blk)
            if impl == "pallas":
                # counts-only kernel: the offset emission below gathers the
                # pairs itself, so no match-matrix egress is computed
                start, counts = join_join.probe_counts_pallas(
                    s_sorted, l_local, block=block, interpret=interpret)
            else:
                start, counts = join_ref.bucket_probe(s_sorted, l_local)
            l_buf, s_buf, t_p = join_ref.emit_pairs_into(
                l_buf, s_buf, order, start, counts, out_base=total,
                l_base=shard_id * shard, s_base=p * HT_CAPACITY)
            total = total + t_p
        return l_buf, s_buf, total[None], (total > max_out)[None]

    fn = shard_map(engine, mesh=mesh, in_specs=(P(axis),),
                   out_specs=(P(axis), P(axis), P(axis), P(axis)),
                   check_vma=False)
    return fn(l_keys)


def join_distributed_multi_result(s_keys, l_keys, plan: ChannelPlan, *,
                                  max_out_per_shard: int = None,
                                  block: int = DEFAULT_BLOCK,
                                  impl: str = "xla", interpret: bool = False
                                  ) -> join_ops.MultiJoinResult:
    """``join_distributed_multi`` under the ``MultiJoinResult`` contract.

    The raw distributed operator returns per-shard pair slices (each
    contiguous, -1-padded to its own capacity) plus per-shard totals and
    overflow flags; the single-device ``hash_join_multi`` returns ONE
    contiguous pair list with a scalar exact ``total`` and ``overflowed``.
    This wrapper reconciles the two so the planner can treat both shapes
    interchangeably: pairs are compacted to a single contiguous prefix,
    ``total`` is the exact global pair count (sum of the per-shard exact
    totals — correct even when a shard's list overflowed), and
    ``overflowed`` is true iff ANY shard truncated its list (the prefix
    then holds only the pairs that fit).  Eager-only: it host-syncs the
    totals to size the compaction.
    """
    l_buf, s_buf, totals, over = join_distributed_multi(
        s_keys, l_keys, plan, max_out_per_shard=max_out_per_shard,
        block=block, impl=impl, interpret=interpret)
    cap = int(l_buf.shape[0])
    n_kept = int(jnp.sum((l_buf >= 0).astype(jnp.int32)))
    (pos,) = jnp.nonzero(l_buf >= 0, size=n_kept, fill_value=cap)
    pad = jnp.full((1,), -1, jnp.int32)
    l_idx = jnp.full((cap,), -1, jnp.int32) \
        .at[:n_kept].set(jnp.concatenate([l_buf, pad])[pos])
    s_idx = jnp.full((cap,), -1, jnp.int32) \
        .at[:n_kept].set(jnp.concatenate([s_buf, pad])[pos])
    return join_ops.MultiJoinResult(
        l_idx, s_idx, jnp.sum(totals), jnp.any(over))


def _bucket_cap(n_rows: int, n_shards: int) -> int:
    """Default per-shard bucket capacity for one shuffled side: 2x the
    uniform-hash expectation plus slack, so typical skew fits without a
    retry.  Exact counts from the shuffle size the retry when it doesn't."""
    return 2 * (-(-n_rows // n_shards)) + 64 if n_rows else 64


def _round_build_cap(cap: int) -> int:
    """Build bucket capacities above one hash-table pass must be a whole
    number of HT_CAPACITY blocks: the pass loop slices fixed blocks, and a
    ragged tail would clamp the last slice onto already-scanned rows
    (duplicate pairs)."""
    return cap if cap <= HT_CAPACITY else -(-cap // HT_CAPACITY) * HT_CAPACITY


# one program per (shapes, capacities): run eagerly, shard_map would
# dispatch every op of the body on its own, each compiled apart
@functools.partial(jax.jit, static_argnames=(
    "layout", "s_cap", "l_cap", "max_out_per_shard", "block", "impl",
    "interpret"))
def join_shuffle_multi(s_keys, l_keys, layout: "shardlib.ShardLayout", *,
                       s_cap: int = None, l_cap: int = None,
                       max_out_per_shard: int = None,
                       block: int = DEFAULT_BLOCK,
                       impl: str = "xla", interpret: bool = False):
    """Shuffle-repartitioned duplicate-capable join (the costed alternative
    to broadcasting the build side).

    Both sides are hash-partitioned by ``shardlib.hash_shard`` into fixed-
    capacity per-shard buckets — the shuffle, whose bytes the cost model
    prices on the interconnect channel — carrying their GLOBAL row ids
    through the repartition.  Each shard then runs the sorted-bucket
    multi-pass join purely locally on its bucket: matching keys hash to
    the same shard, so the union of per-shard pair multisets is exactly
    the global join.  The payoff the cost model prices: each shard builds
    only its ~1/n slice of S, so a build side that forces ceil(N_S /
    HT_CAPACITY) probe rescans under broadcast needs only ceil(N_S / n /
    HT_CAPACITY) passes here (Fig. 8b's linear regime, divided by the
    channel count).

    Returns ``(l_idx, s_idx, totals, pair_overflow, shuffle)`` where
    l_idx/s_idx are flat (n_shards * max_out_per_shard,) pair lists of
    GLOBAL row ids (-1 padding, per-shard slices contiguous), ``totals``
    per-shard exact pair counts, ``pair_overflow`` per-shard truncation
    flags, and ``shuffle = (s_counts, l_counts, overflowed)`` the exact
    per-shard shuffle cardinalities — if ``overflowed``, bucket rows were
    dropped and the caller must retry with the measured capacities.
    """
    n = layout.n_shards
    mesh, axis = layout.mesh, layout.axis
    n_s, n_l = s_keys.shape[0], l_keys.shape[0]
    s_cap = _round_build_cap(s_cap if s_cap is not None
                             else _bucket_cap(n_s, n))
    l_cap = l_cap if l_cap is not None else _bucket_cap(n_l, n)
    max_out = max_out_per_shard if max_out_per_shard is not None \
        else max(2 * l_cap, 64)

    # build pads: distinct negative sentinels (bucket_build requires unique
    # keys); probe pads: -1, which can never match a build entry (real keys
    # are >= 0 and build pads are <= -(2**30))
    s_fill = (-(2 ** 30)
              - jnp.arange(n * s_cap, dtype=jnp.int32).reshape(n, s_cap))
    ids_fill = jnp.full((n, s_cap), -1, jnp.int32)
    (s_bkeys, s_bids), s_counts, s_over = shardlib.partition_to_shards(
        shardlib.hash_shard(s_keys, n),
        (s_keys, jnp.arange(n_s, dtype=jnp.int32)), n, s_cap,
        (s_fill, ids_fill))
    l_fill = jnp.full((n, l_cap), -1, jnp.int32)
    (l_bkeys, l_bids), l_counts, l_over = shardlib.partition_to_shards(
        shardlib.hash_shard(l_keys, n),
        (l_keys, jnp.arange(n_l, dtype=jnp.int32)), n, l_cap,
        (l_fill, jnp.full((n, l_cap), -1, jnp.int32)))

    n_passes = -(-s_cap // HT_CAPACITY)
    blk = min(HT_CAPACITY, s_cap)

    def engine(s_loc, l_loc):
        shard_id = jax.lax.axis_index(axis)
        s_local, l_local = s_loc[0], l_loc[0]
        l_buf = jnp.full((max_out,), -1, jnp.int32)
        s_buf = jnp.full((max_out,), -1, jnp.int32)
        total = jnp.zeros((), jnp.int32)
        for p in range(n_passes):             # rescan the LOCAL probe bucket
            s_blk = jax.lax.dynamic_slice_in_dim(s_local, p * blk, blk)
            s_sorted, order = join_ref.bucket_build(s_blk)
            if impl == "pallas":
                start, counts = join_join.probe_counts_pallas(
                    s_sorted, l_local, block=block, interpret=interpret)
            else:
                start, counts = join_ref.bucket_probe(s_sorted, l_local)
            # emitted indices are BUCKET positions into the flat (n*cap,)
            # shuffled id arrays; global ids are gathered outside
            l_buf, s_buf, t_p = join_ref.emit_pairs_into(
                l_buf, s_buf, order, start, counts, out_base=total,
                l_base=shard_id * l_cap, s_base=shard_id * s_cap + p * blk)
            total = total + t_p
        return l_buf, s_buf, total[None], (total > max_out)[None]

    fn = shard_map(engine, mesh=mesh, in_specs=(P(axis), P(axis)),
                   out_specs=(P(axis),) * 4, check_vma=False)
    l_buf, s_buf, totals, pair_over = fn(s_bkeys, l_bkeys)
    valid = l_buf >= 0
    l_idx = jnp.where(valid, l_bids.reshape(-1)[jnp.clip(l_buf, 0)], -1)
    s_idx = jnp.where(valid, s_bids.reshape(-1)[jnp.clip(s_buf, 0)], -1)
    return (l_idx, s_idx, totals, pair_over,
            (s_counts, l_counts, s_over | l_over))
