"""Logical-axis sharding rules (the paper's channel-ownership discipline).

The paper's central lesson is that bandwidth is only real when every compute
engine streams from its *own* physical memory channel (Fig. 2: 190 GB/s
ideally partitioned vs 14 GB/s congested).  On a TPU mesh the physical
channels are the per-chip HBM stacks, and "partitioning the address space"
becomes assigning every logical tensor dimension a mesh-axis owner.  This
module is that assignment, per architecture.

Logical axes used by the model code:

  batch      activations' batch dim            -> (pod, data)
  seq        sequence dim                      -> None (or data under CP)
  embed      d_model on activations            -> None
  heads      q-head dim                        -> model (when divisible)
  kv_heads   kv-head dim                       -> model (when divisible)
  qkv        fused q/k/v output dim of weights -> model
  mlp        d_ff dim                          -> model
  vocab      vocabulary dim                    -> model
  experts    expert dim                        -> model (EP) or None (expert-TP)
  fsdp       weight shard dim (ZeRO-3 style)   -> data
  stages     layer-stack dim                   -> None (pipeline optional)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Resolved logical->physical mapping for one (arch, mesh) pair."""

    mesh: Mesh
    batch: tuple[str, ...]
    seq: Optional[str]                 # context parallelism when set
    kv_seq: Optional[str]              # KV-cache sequence dim (flash-decoding)
    heads: Optional[str]
    kv_heads: Optional[str]
    mlp: Optional[str]
    vocab: Optional[str]
    experts: Optional[str]
    moe_mlp: Optional[str]             # expert d_ff dim (expert-TP only)
    fsdp: Optional[str]
    ssm_heads: Optional[str]
    head_dim: Optional[str]            # rope-free head_dim TP (whisper)

    def spec(self, *logical: Optional[str]) -> P:
        """Build a PartitionSpec from logical axis names."""
        out = []
        for ax in logical:
            if ax is None:
                out.append(None)
            elif ax == "batch":
                out.append(self.batch if self.batch else None)
            else:
                out.append(getattr(self, ax))
        return P(*out)

    def named(self, *logical: Optional[str]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical))

    def constrain(self, x, *logical: Optional[str]):
        return jax.lax.with_sharding_constraint(x, self.named(*logical))


def resolve(cfg: ArchConfig, mesh: Mesh, shape=None, *,
            context_parallel_decode: bool = False,
            fsdp: bool = True) -> ShardingRules:
    """Per-arch rules implementing DESIGN.md's padding/replication policy.

    ``shape`` (a ShapeConfig) refines the rules per step kind: serve steps
    shard the KV-cache sequence dim over ``model`` (flash-decoding layout,
    the paper's channel partitioning applied to the cache), and batch
    sharding is dropped when the global batch does not divide the dp axes
    (long_500k's batch=1).
    """
    tp = mesh.shape.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    has_data = "data" in mesh.axis_names

    kv_seq = None
    if shape is not None:
        dp_size = 1
        for a in dp_axes:
            dp_size *= mesh.shape.get(a, 1)
        if shape.global_batch % max(dp_size, 1):
            dp_axes = ()
        if shape.kind in ("prefill", "decode") and tp > 1 \
                and shape.seq_len % tp == 0 and cfg.kv_tp(tp) != tp:
            # flash-decoding cache layout; not needed (and conflicting) when
            # the kv heads themselves shard over the model axis
            kv_seq = "model"

    attn_tp = cfg.attn_tp(tp)
    heads = "model" if (tp > 1 and attn_tp == tp) else None
    kv_heads = "model" if (tp > 1 and cfg.kv_tp(tp) == tp) else None
    mlp = "model" if tp > 1 else None
    vocab = "model" if tp > 1 else None
    # EP owns the model axis for expert weights (experts divide it); otherwise
    # expert-TP shards each expert's d_ff over the model axis instead.
    experts = "model" if (cfg.n_experts and cfg.expert_parallel(tp)) else None
    moe_mlp = "model" if (cfg.n_experts and tp > 1 and experts is None) else None
    # SSD heads shard over model when divisible (mamba2: 48 % 16 == 0).
    ssm_heads = "model" if (cfg.ssm_state and tp > 1 and cfg.n_ssm_heads % tp == 0) else None

    seq = "data" if (context_parallel_decode and has_data) else None

    return ShardingRules(
        mesh=mesh,
        batch=dp_axes,
        seq=seq,
        kv_seq=kv_seq,
        heads=heads,
        kv_heads=kv_heads,
        mlp=mlp,
        vocab=vocab,
        experts=experts,
        moe_mlp=moe_mlp,
        fsdp="data" if (fsdp and has_data) else None,
        ssm_heads=ssm_heads,
        head_dim="model" if (tp > 1 and cfg.head_dim_tp(tp) == tp) else None,
    )


# --------------------------------------------------------------------------- #
# Parameter pytree sharding: every leaf carries a logical spec produced by the
# model init; this maps them to NamedShardings for pjit in/out shardings.
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class LogicalArray:
    """Shape + logical axes carried through abstract init (no allocation)."""

    shape: tuple[int, ...]
    logical: tuple[Optional[str], ...]
    dtype: object

    def sds(self, rules: ShardingRules) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype,
                                    sharding=rules.named(*self.logical))


def tree_shardings(tree, rules: ShardingRules):
    """Map a pytree of LogicalArray to NamedShardings."""
    return jax.tree.map(
        lambda la: rules.named(*la.logical), tree,
        is_leaf=lambda x: isinstance(x, LogicalArray))


def tree_sds(tree, rules: ShardingRules):
    return jax.tree.map(
        lambda la: la.sds(rules), tree,
        is_leaf=lambda x: isinstance(x, LogicalArray))


def validate_divisibility(tree, rules: ShardingRules) -> list[str]:
    """Check every sharded dim divides its mesh-axis product; returns problems."""
    problems: list[str] = []

    def _check(path, la):
        spec = rules.spec(*la.logical)
        for dim, axes in zip(la.shape, spec):
            if axes is None:
                continue
            axes_t = axes if isinstance(axes, tuple) else (axes,)
            k = 1
            for a in axes_t:
                k *= rules.mesh.shape.get(a, 1)
            if dim % k:
                problems.append(f"{path}: dim {dim} not divisible by {k} ({axes})")

    jax.tree_util.tree_map_with_path(
        lambda p, la: _check(jax.tree_util.keystr(p), la), tree,
        is_leaf=lambda x: isinstance(x, LogicalArray))
    return problems
