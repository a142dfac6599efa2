"""Channel-aware placement planner — the paper's Fig. 2 lesson as code.

On the AD9H7 the 8 GiB HBM is 32 pseudo-channels x 256 MiB; peak bandwidth
needs every port on its own channel.  On a TPU mesh the "channels" are the
per-chip HBM stacks: this module assigns column shards to devices
(round-robin, contiguous ranges — the paper's `offset = S x 1MiB x (id-1)`
formula generalized), and can deliberately emit the CONGESTED placement
(every engine reading the same chip's shard) used by the Fig. 5 "non-
partitioned" baselines.

It also carries the paper's analytical bandwidth model, calibrated to the
AD9H7 microbenchmark numbers (the published Fig. 2 curves), which the
planner uses to predict layout quality on TPU.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# --- paper hardware model (AD9H7, 2 stacks x 16 pseudo channels) ----------- #
N_PORTS = 32
CHANNEL_MIB = 256
PORT_GBPS_200 = 190.0 / 32      # per-port ideal at 200 MHz (meas. Fig. 2)
PORT_GBPS_300 = 282.0 / 32
# a hammered channel sustains more than one port's share but far less than
# the aggregate: calibrated to the paper's S=0 points (14 / 21 GB/s)
CHANNEL_GBPS_200 = 14.0
CHANNEL_GBPS_300 = 21.0

# --- device peak rates, keyed by jax's ``device_kind`` -------------------- #


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks the cost model prices with."""
    hbm_gbps: float          # HBM bandwidth, GB/s
    ici_gbps: float          # chip-to-chip bandwidth per link, GB/s
    flops: float             # bf16 FLOP/s
    source: str


V5E = DevicePeaks(
    hbm_gbps=819.0, ici_gbps=50.0, flops=197e12,
    source="Google Cloud documentation, 'TPU v5e': 819 GB/s HBM, "
           "197 TFLOP/s bf16, 1,600 Gbit/s ICI per chip over 4 links")

PEAKS = {
    "TPU v5 lite": V5E,      # what jax reports for a v5e chip
    # the CPU backend has no peak worth pricing: tests and CPU rehearsals
    # price as a v5e, so a plan made there is the plan the chip would make
    "cpu": dataclasses.replace(V5E, source="test entry: the v5e numbers"),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks of one device kind; a kind without an entry is an error, not
    a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r}: add its "
            "published HBM/ICI/FLOP peaks to repro.core.channels.PEAKS"
        ) from None


def fpga_bandwidth_model(n_ports: int, separation_mib: int,
                         clock_mhz: int = 200) -> float:
    """Aggregate GB/s for the Fig. 2 microbenchmark: n_ports traffic
    generators, each offset by ``separation_mib`` MiB.  Ports whose address
    ranges land on the same physical channel share that channel's bandwidth.
    """
    port_bw = PORT_GBPS_200 if clock_mhz == 200 else PORT_GBPS_300
    chan_bw = CHANNEL_GBPS_200 if clock_mhz == 200 else CHANNEL_GBPS_300
    # which channel does each port's offset land in?
    chans = [((i * separation_mib) // CHANNEL_MIB) % N_PORTS
             for i in range(n_ports)]
    load = np.bincount(chans, minlength=N_PORTS)
    total = 0.0
    for ch, n in enumerate(load):
        if n:
            total += min(n * port_bw, chan_bw)
    return total


def tpu_bandwidth_model(n_engines: int, partitioned: bool,
                        peaks: DevicePeaks = V5E) -> float:
    """TPU analogue: engines = chips.  Partitioned -> each chip streams its
    local HBM; congested -> every chip pulls the same chip's shard over ICI
    (the crossbar-congestion analogue)."""
    if partitioned:
        return n_engines * peaks.hbm_gbps
    return min(peaks.hbm_gbps,
               n_engines * peaks.ici_gbps / max(n_engines - 1, 1))


Placement = Literal["partitioned", "congested", "replicated"]


@dataclasses.dataclass(frozen=True)
class ChannelPlan:
    """Placement of a 1-D column across the mesh's 'engine' axis.

    The plan holds its mesh with Auto axes whatever the caller built
    (``jax.make_mesh`` makes Explicit ones): an array placed on an
    Explicit axis carries that axis in its type, and eager ops outside a
    mesh context (``jnp.nonzero``, ``jnp.searchsorted``) then refuse it."""

    mesh: Mesh
    axis: str
    placement: Placement

    def __post_init__(self) -> None:
        if any(t != AxisType.Auto for t in self.mesh.axis_types):
            object.__setattr__(self, "mesh", Mesh(
                self.mesh.devices, self.mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(self.mesh.axis_names)))

    @property
    def n_engines(self) -> int:
        return self.mesh.shape[self.axis]

    def sharding(self) -> NamedSharding:
        if self.placement == "partitioned":
            return NamedSharding(self.mesh, P(self.axis))
        return NamedSharding(self.mesh, P())     # replicated / congested

    def place(self, x: jax.Array) -> jax.Array:
        return jax.device_put(x, self.sharding())

    def predicted_gbps(self) -> float:
        kind = self.mesh.devices.flat[0].device_kind
        return tpu_bandwidth_model(self.n_engines,
                                   self.placement == "partitioned",
                                   device_peaks(kind))

    def align_morsel_rows(self, rows: int) -> int:
        """Round a morsel row count up to a multiple of the engine count so
        every morsel splits evenly into per-channel shards (one shard per
        pseudo-channel — the paper's `S x 1MiB x (id-1)` offsets applied at
        morsel rather than whole-column granularity)."""
        n = self.n_engines
        return max(-(-int(rows) // n) * n, n)


def plan(mesh: Mesh, axis: str = "data",
         placement: Placement = "partitioned") -> ChannelPlan:
    return ChannelPlan(mesh, axis, placement)
