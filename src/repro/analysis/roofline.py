"""Three-term roofline from a compiled dry-run artifact (TPU v5e targets).

All inputs are per-device quantities (cost_analysis of the post-SPMD module);
terms are seconds-per-step on the target hardware:

  compute    = flops / PEAK_FLOPS
  memory     = bytes_accessed / HBM_BW
  collective = collective_operand_bytes / ICI_BW

MODEL_FLOPS is the textbook 6*N*D (dense) / 6*N_active*D (MoE) per train
step, 2*N*D_new for serve steps — the "useful work" yardstick; the ratio
MODEL_FLOPS / HLO_FLOPs exposes remat/masking/capacity waste.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.channels import V5E

PEAK_FLOPS = V5E.flops                 # bf16 / chip
HBM_BW = V5E.hbm_gbps * 1e9            # bytes/s / chip
ICI_BW = V5E.ici_gbps * 1e9            # bytes/s / link


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_dev: float
    bytes_per_dev: float
    coll_operand_bytes: float
    coll_wire_bytes: float
    model_flops_global: float

    @property
    def t_compute(self) -> float:
        return self.flops_per_dev / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_dev / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_operand_bytes / ICI_BW

    @property
    def t_collective_wire(self) -> float:
        return self.coll_wire_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Step-time lower bound under perfect overlap of the three engines."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        hlo_global = self.flops_per_dev * self.chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization achievable at the roofline bound."""
        denom = self.t_bound * self.chips * PEAK_FLOPS
        return self.model_flops_global / denom if denom else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "coll_operand_bytes": self.coll_operand_bytes,
            "coll_wire_bytes": self.coll_wire_bytes,
            "model_flops_global": self.model_flops_global,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu_bound,
        }


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """Global useful FLOPs per step."""
    n_active = cfg.param_count(active=True)
    if shape.kind == "train":
        return 6.0 * n_active * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.tokens
    # decode: one new token per sequence (+ KV/state reads are a memory cost)
    return 2.0 * n_active * shape.global_batch


def from_measurements(cfg: ArchConfig, shape: ShapeConfig, mesh_name: str,
                      chips: int, flops_per_dev: float, bytes_per_dev: float,
                      coll_operand: float, coll_wire: float) -> Roofline:
    return Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        flops_per_dev=flops_per_dev, bytes_per_dev=bytes_per_dev,
        coll_operand_bytes=coll_operand, coll_wire_bytes=coll_wire,
        model_flops_global=model_flops(cfg, shape),
    )
