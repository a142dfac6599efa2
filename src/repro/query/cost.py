"""Bandwidth-aware cost model — the optimizer's pricing of physical
alternatives.

The paper's lesson (Fig. 2/5, and the related HBM benchmarking work) is
that *placement* and *access pattern* decide achieved bandwidth, not peak
numbers: partitioned columns stream every channel, a congested layout
collapses to crossbar bandwidth, and a build side must be replicated per
engine.  This module prices each (impl, placement, pass-count) alternative
of every physical operator with ``channels.tpu_bandwidth_model`` /
``channels.fpga_bandwidth_model`` plus the running device's peak rates
(``channels.PEAKS``), so the executor can pick placement per column
instead of requiring callers to pre-``place()`` tables.
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Dict, Optional, Tuple

import jax

from repro.columnar.engine import SELECT_BLOCK
from repro.core.channels import (
    device_peaks, fpga_bandwidth_model, tpu_bandwidth_model,
)
from repro.core.join import HT_CAPACITY
from repro.query import logical as L

BYTES_PER_VALUE = 4                 # int32/float32 columns

# streaming efficiencies + fixed launch overheads (sec) per operator —
# the crossover that makes the xla/pallas choice size-dependent.  These
# are the DEFAULTS; numbers measured on the running device override them
# when passed in explicitly
# (``CostModel(calibration=load_calibration(path))``).
XLA_STREAM_EFF = 0.70
PALLAS_STREAM_EFF = 0.92
XLA_CALL_OVERHEAD = 2e-6
PALLAS_CALL_OVERHEAD = 12e-6

# host->device staging bandwidth for per-morsel placement transfers (the
# double-buffered jax.device_put the streaming executor overlaps with
# compute); PCIe-gen4-x16-class default, recalibrated alongside the
# stream efficiencies
H2D_GBPS = 16.0

# fixed cost of dispatching one morsel's staging transfer (slicing the
# host columns + the device_put round trip), independent of its size —
# the term that makes tiny morsels a loss out of core: 128 one-KB-row
# morsels pay this 128x where 12 budget-sized morsels pay it 12x.
# Without it the overlap formula below is flat in morsel size and the
# argmin degenerates to the smallest candidate.
STAGE_OVERHEAD_S = 1.2e-4

# memory-hierarchy tiers below the device placement (the paper's
# HBM <-> DDR4 hierarchy generalized one rung further to disk).  A
# column/cache entry lives on exactly one tier; promotion crosses the
# interconnect back toward the device.  DDR4-2400-ish single-channel
# host DRAM and NVMe-class sequential disk reads; all three are
# calibration overlay keys alongside h2d_gbps.
D2H_GBPS = 16.0            # device -> host demotion (same PCIe link)
HOST_DRAM_GBPS = 19.2      # host DRAM streaming (paper's DDR4 channel)
DISK_GBPS = 2.0            # sequential NVMe read into page cache

# tier ordering, top (fastest, smallest) to bottom: the spill planner
# fills in this order and the cache evicts only from the last entry
TIERS = ("device", "host", "disk")

# operators whose ``pallas`` alternative dispatches a kernel that compiles
# for the TPU (tests/test_tpu_compile.py).  The join probes are absent:
# the TPU compiler refuses the multi-match probes' 1-D in-kernel gather
# ("Only 2D gather is supported") and the unique probe's rank-1 count
# block of one element, so joins price and run on XLA only
PALLAS_OPS = frozenset({"filter", "filter_project"})


def load_calibration(path: str) -> dict:
    """Measured stream efficiencies / call overheads from a JSON file in
    the shape ``CostModel.calibration_snapshot`` returns.  A calibration
    prices only the device it was measured on, so a file whose
    ``backend`` — or ``device_kind``, where it records one — differs from
    the running device's is refused with ``ValueError``."""
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "backends" not in data:
        raise ValueError(f"{path} is not a calibration file")
    dev = jax.devices()[0]
    if data.get("backend") != dev.platform:
        raise ValueError(
            f"{path} was measured on backend {data.get('backend')!r}, "
            f"not on {dev.platform!r}: recalibrate on this device "
            "or pass no calibration")
    if "device_kind" in data and data["device_kind"] != dev.device_kind:
        raise ValueError(
            f"{path} was measured on a {data['device_kind']!r}, not on a "
            f"{dev.device_kind!r}: recalibrate on this device or pass no "
            "calibration")
    return data


# --------------------------------------------------------------------------- #
# catalog statistics

@dataclasses.dataclass
class ColumnStats:
    lo: int
    hi: int
    n_distinct: Optional[int] = None

    @property
    def domain(self) -> int:
        return max(int(self.hi) - int(self.lo) + 1, 1)


@dataclasses.dataclass
class TableStats:
    num_rows: int
    columns: Tuple[str, ...]
    ranges: Dict[str, ColumnStats]


def selectivity(stats: ColumnStats, lo: int, hi: int) -> float:
    """Uniform-domain estimate of a range predicate's selectivity."""
    span = min(hi, stats.hi) - max(lo, stats.lo) + 1
    return min(max(span, 0) / stats.domain, 1.0)


# measured/predicted selectivity correction factors are clamped so a
# single bad ledger window can never swing a plan by more than 4x in
# either direction (satellite of the adaptive-replan loop)
SEL_CORRECTION_CLAMP = (0.25, 4.0)


def clamp_correction(factor: float) -> float:
    lo, hi = SEL_CORRECTION_CLAMP
    return min(max(float(factor), lo), hi)


def estimate_rows(node: L.Node, stats: Dict[str, TableStats],
                  corrections: Optional[Dict[Tuple[str, str], float]] = None
                  ) -> float:
    """Cardinality estimate — drives build/probe side selection and the
    multi-pass join block count.

    ``corrections`` maps (table, column) to a measured-over-predicted
    bytes ratio from the bandwidth ledger (``Executor.recost`` folds them
    in from ``BandwidthLedger.selectivity_corrections``): the uniform-
    domain selectivity of a filter over that column is scaled by the
    clamped factor, closing the PR-7 loop from observed drift back into
    cardinality estimates — not just bandwidth constants."""
    if isinstance(node, L.Scan):
        return float(stats[node.table].num_rows)
    if isinstance(node, (L.Filter, L.FilterProject)):
        base = estimate_rows(node.child, stats, corrections)
        cs = _column_stats(node.child, node.column, stats)
        sel = selectivity(cs, node.lo, node.hi) if cs else 0.33
        if corrections:
            scan = probe_base_scan(node.child)
            f = corrections.get((scan.table, node.column)) if scan else None
            if f is not None:
                sel = min(sel * clamp_correction(f), 1.0)
        return base * sel
    if isinstance(node, L.Join):
        l = estimate_rows(node.left, stats, corrections)
        r = estimate_rows(node.right, stats, corrections)
        cs = _column_stats(node.right, node.on, stats)
        ls = _column_stats(node.left, node.on, stats)
        # expected matches per probe row ~ |build| / |key domain|: exceeds
        # 1 when the build side carries duplicates (multi-match output).
        # Only probe rows whose key lands in the build domain can match —
        # without the overlap fraction the estimate depends on which side
        # probes, and the build-side chooser compares orientations
        matches = r / cs.domain if cs else 0.1
        if cs and ls:
            overlap = min(cs.hi, ls.hi) - max(cs.lo, ls.lo) + 1
            frac = min(max(overlap, 0) / ls.domain, 1.0)
        else:
            frac = 1.0
        return l * matches * frac
    if isinstance(node, L.Project):
        return estimate_rows(node.child, stats, corrections)
    if isinstance(node, L.ScoreGLM):
        # one prediction per input row
        return estimate_rows(node.child, stats, corrections)
    if isinstance(node, (L.Aggregate, L.TrainGLM)):
        return 1.0
    raise TypeError(node)


def _column_stats(node: L.Node, column: str,
                  stats: Dict[str, TableStats]) -> Optional[ColumnStats]:
    for n in L.walk(node):
        if isinstance(n, L.Scan):
            t = stats.get(n.table)
            if t and column in t.ranges:
                return t.ranges[column]
    return None


def key_is_unique(node: L.Node, column: str,
                  stats: Dict[str, TableStats]) -> bool:
    """Whether ``column`` is (provably) duplicate-free in ``node``'s output.

    No longer a correctness gate — the multi-match sorted-bucket kernel
    joins duplicate build keys exactly — but still a physical-planning
    hint: provably-unique build keys take the paper's open-addressing
    II=1 fast path (op "join"); everything else takes the multi-match
    path (op "join_multi") whose output cardinality the cost model prices
    via the expected chain length.  Scans check catalog distinct counts;
    filters/projections preserve uniqueness; a join output is
    conservatively treated as non-unique.
    """
    if isinstance(node, L.Scan):
        t = stats.get(node.table)
        cs = t.ranges.get(column) if t else None
        return bool(cs and cs.n_distinct is not None
                    and cs.n_distinct == t.num_rows)
    if isinstance(node, (L.Filter, L.FilterProject, L.Project)):
        return key_is_unique(node.child, column, stats)
    return False


def expected_chain_length(node: L.Node, column: str,
                          stats: Dict[str, TableStats]) -> float:
    """Average bucket (duplicate-chain) size of ``column`` in ``node``'s
    output — the multi-match probe's per-row work and output multiplier."""
    rows = max(estimate_rows(node, stats), 1.0)
    cs = _column_stats(node, column, stats)
    if cs is None:
        return 1.0
    distinct = cs.n_distinct if cs.n_distinct else min(rows, cs.domain)
    return max(rows / max(float(distinct), 1.0), 1.0)


# --------------------------------------------------------------------------- #
# the model

class CostModel:
    """Prices one physical operator alternative at a time.

    ``hardware="tpu"`` uses the mesh/ICI analogue with the running
    device's peaks (an unknown device kind raises); ``hardware="fpga"`` prices with the paper's calibrated AD9H7
    channel model (32 ports, 256 MiB separation when partitioned, 0 when
    congested) — the same decision procedure on either bandwidth curve.
    """

    def __init__(self, n_engines: int, *, n_shards: int = 1,
                 hardware: str = "tpu",
                 allow_pallas: Optional[bool] = None,
                 calibration: Optional[dict] = None):
        self.n_engines = n_engines
        self.peaks = device_peaks(jax.devices()[0].device_kind)
        # explicit shard_map striping width (device = pseudo-channel);
        # 1 = the classic single-pipeline plans, byte-for-byte unchanged
        self.n_shards = max(int(n_shards), 1)
        # (table, column) -> measured/predicted bytes ratio fed back from
        # the bandwidth ledger by Executor.recost (clamped at use)
        self.sel_corrections: Dict[Tuple[str, str], float] = {}
        self.hardware = hardware
        if allow_pallas is None:
            # interpret-mode pallas on CPU is emulation, never a win
            allow_pallas = jax.default_backend() == "tpu"
        self.allow_pallas = allow_pallas
        self.stream_eff = {"xla": XLA_STREAM_EFF,
                           "pallas": PALLAS_STREAM_EFF}
        self.call_overhead = {"xla": XLA_CALL_OVERHEAD,
                              "pallas": PALLAS_CALL_OVERHEAD}
        self.h2d_gbps = H2D_GBPS
        self.stage_overhead_s = STAGE_OVERHEAD_S
        # memory-hierarchy tier channels (device HBM aggregate is
        # bandwidth_gbps(placement); these price the rungs below it)
        self.d2h_gbps = D2H_GBPS
        self.host_gbps = HOST_DRAM_GBPS
        self.disk_gbps = DISK_GBPS
        # the PRISTINE per-backend constants, captured before any overlay
        # ever touches the live dicts: every calibration application
        # re-baselines against these, so applying the same overlay twice
        # (or overlapping online overlays) can never compound
        self._baseline = {"stream_eff": dict(self.stream_eff),
                          "call_overhead": dict(self.call_overhead),
                          "h2d_gbps": self.h2d_gbps,
                          "stage_overhead_s": self.stage_overhead_s,
                          "d2h_gbps": self.d2h_gbps,
                          "host_gbps": self.host_gbps,
                          "disk_gbps": self.disk_gbps}
        self.calibrated_from = None
        self.n_calibrations = 0
        if calibration:
            self._apply_calibration(calibration)

    def apply_calibration(self, calibration: dict) -> None:
        """Public recalibration surface (the executor's ``recost()`` entry
        point): idempotent overlay application — see
        ``_apply_calibration``."""
        self._apply_calibration(calibration)

    def _apply_calibration(self, calibration: dict) -> None:
        """Overlay measured per-backend numbers on the PRISTINE constants.

        Application is IDEMPOTENT: the live dicts are reset to the
        baseline captured at construction before the overlay lands, so an
        overlay describes an absolute state, never a delta on top of a
        previous overlay.  Repeatedly applying the same overlay (the
        serve-side recalibration loop can fire on overlapping evidence)
        therefore leaves every price unchanged, and a backend the overlay
        does not mention re-baselines to its pristine default rather than
        inheriting a stale earlier overlay.  Efficiencies are clamped to
        (0, 1]; a partial calibration (e.g. no pallas off-TPU) is fine."""
        self.stream_eff = dict(self._baseline["stream_eff"])
        self.call_overhead = dict(self._baseline["call_overhead"])
        self.h2d_gbps = self._baseline["h2d_gbps"]
        self.stage_overhead_s = self._baseline["stage_overhead_s"]
        self.d2h_gbps = self._baseline["d2h_gbps"]
        self.host_gbps = self._baseline["host_gbps"]
        self.disk_gbps = self._baseline["disk_gbps"]
        for impl, meas in calibration.get("backends", {}).items():
            if impl not in self.stream_eff:
                continue
            eff = meas.get("stream_eff")
            if eff and eff > 0:
                self.stream_eff[impl] = min(float(eff), 1.0)
            over = meas.get("call_overhead_s")
            if over and over > 0:
                self.call_overhead[impl] = float(over)
        for key in ("h2d_gbps", "d2h_gbps", "host_gbps", "disk_gbps",
                    "stage_overhead_s"):
            v = calibration.get(key)
            if v and v > 0:
                setattr(self, key, float(v))
        self.calibrated_from = calibration.get("backend", "measured")
        self.n_calibrations += 1

    def calibration_snapshot(self) -> dict:
        """The model's CURRENT constants in the calibration shape
        ``load_calibration`` reads — what the persistence layer writes so
        a warm-started server re-applies exactly the calibration state
        this process converged to (including any drift-triggered ledger
        overlays)."""
        snap = {"backend": self.calibrated_from or jax.default_backend(),
                "backends": {impl: {"stream_eff": self.stream_eff[impl],
                                    "call_overhead_s":
                                        self.call_overhead[impl]}
                             for impl in self.stream_eff}}
        for key in ("h2d_gbps", "d2h_gbps", "host_gbps", "disk_gbps",
                    "stage_overhead_s"):
            snap[key] = getattr(self, key)
        return snap

    def impls(self, op: str) -> Tuple[str, ...]:
        """Implementations priced for ``op``: pallas only where allowed and
        where the op has a kernel that compiles."""
        if self.allow_pallas and op in PALLAS_OPS:
            return ("xla", "pallas")
        return ("xla",)

    def bandwidth_gbps(self, placement: str) -> float:
        """Aggregate streaming bandwidth of one operator under a placement.

        ``placement`` also accepts the sub-device tiers ("host", "disk"):
        a column resident there streams at the tier channel's bandwidth
        regardless of hardware model — checked FIRST so the hardware
        dispatch below never sees a tier name it doesn't price."""
        if placement == "host":
            return self.host_gbps
        if placement == "disk":
            return self.disk_gbps
        if self.hardware == "fpga":
            if placement == "sharded":
                # the paper's channel-count sweep (Figs. 5-7): aggregate
                # bandwidth of n_shards separated pseudo-channels
                return fpga_bandwidth_model(self.n_shards, 256)
            sep = {"partitioned": 256, "replicated": 256, "congested": 0}
            bw = fpga_bandwidth_model(32, sep[placement])
            # replicated = one engine's share of the separated layout
            return bw / 32 if placement == "replicated" else bw
        if placement == "sharded":
            # one device per shard streaming its own HBM, summed — the
            # TPU analogue of the channel-count sweep
            return tpu_bandwidth_model(self.n_shards, True, self.peaks)
        if placement == "partitioned":
            return tpu_bandwidth_model(self.n_engines, True, self.peaks)
        if placement == "congested":
            return tpu_bandwidth_model(self.n_engines, False, self.peaks)
        return self.peaks.hbm_gbps     # replicated: one engine, local HBM

    def shuffle_cost(self, n_bytes: float) -> float:
        """Seconds to hash-repartition ``n_bytes`` across the shard mesh.

        Under a uniform hash, (n_shards-1)/n_shards of every shard's rows
        leave the device; those bytes cross the interconnect — a SEPARATE,
        much narrower channel than local HBM (the cross-channel collapse
        of the HLS/HBM studies).  This is the price the shuffle-vs-
        broadcast join decision trades against rescan passes."""
        if self.n_shards <= 1:
            return 0.0
        return n_bytes * (self.n_shards - 1) / self.n_shards \
            / (self.peaks.ici_gbps * 1e9)

    def shard_broadcast_cost(self, n_bytes: float) -> float:
        """Replicating a build side to every SHARD over the interconnect
        (the broadcast strategy's repartition-analogue term)."""
        if self.n_shards <= 1:
            return 0.0
        return n_bytes * (self.n_shards - 1) / (self.peaks.ici_gbps * 1e9)

    def stream_cost(self, n_bytes: float, *, impl: str, placement: str,
                    n_passes: int = 1, flops: float = 0.0) -> float:
        """Seconds to stream ``n_bytes`` under (impl, placement), roofline-
        combined with any compute the operator does."""
        eff = self.stream_eff.get(impl, XLA_STREAM_EFF)
        over = self.call_overhead.get(impl, XLA_CALL_OVERHEAD)
        bw = self.bandwidth_gbps(placement) * 1e9 * eff
        t_mem = n_passes * n_bytes / bw
        t_compute = flops / self.peaks.flops
        return max(t_mem, t_compute) + over * n_passes

    def broadcast_cost(self, n_bytes: float) -> float:
        """Replicating a build side / dataset to every engine over ICI."""
        if self.n_engines <= 1:
            return 0.0
        return n_bytes * (self.n_engines - 1) / (self.peaks.ici_gbps * 1e9)

    # -- semantic-cache pricing (recompute-cost vs residency-bytes) --------- #

    def cache_score(self, recompute_s: float, n_bytes: int,
                    hits: int = 0) -> float:
        """Value density of a materialized entry: seconds of recompute
        avoided per resident byte, scaled by observed reuse.  The
        semantic cache admits and evicts by this score, so an expensive-
        to-rebuild join build outlives a bigger but trivially-recomputed
        selection even when both fit."""
        return max(recompute_s, 0.0) * (1.0 + hits) \
            / max(float(n_bytes), 1.0)

    # -- tier pricing (device <-> host <-> disk hierarchy) ------------------ #

    def promotion_cost(self, n_bytes: float, src_tier: str) -> float:
        """Seconds to move ``n_bytes`` from ``src_tier`` back onto the
        device: host pays the H2D staging link, disk pays the sequential
        read AND the staging link (serial within one prefetch-thread
        stage; the streaming driver overlaps the whole stage with
        compute, exactly like today's H2D overlap)."""
        if src_tier == "device":
            return 0.0
        t = n_bytes / (self.h2d_gbps * 1e9)
        if src_tier == "disk":
            t += n_bytes / (self.disk_gbps * 1e9)
        return t

    def demotion_cost(self, n_bytes: float, dst_tier: str) -> float:
        """Seconds to push ``n_bytes`` down to ``dst_tier`` (D2H copy,
        plus the disk write when demoting all the way down)."""
        if dst_tier == "device":
            return 0.0
        t = n_bytes / (self.d2h_gbps * 1e9)
        if dst_tier == "disk":
            t += n_bytes / (self.disk_gbps * 1e9)
        return t

    def tier_score(self, recompute_s: float, n_bytes: int,
                   hits: int = 0, tier: str = "device") -> float:
        """``cache_score`` generalized per tier: a hit on a lower-tier
        entry still pays the promotion back up, so its value density is
        the NET seconds avoided per resident byte.  This is the single
        currency the cache's demote-vs-evict decision and the spill
        planner's tier choice both price in."""
        net = max(recompute_s, 0.0) - self.promotion_cost(
            float(max(n_bytes, 1)), tier)
        return max(net, 0.0) * (1.0 + hits) / max(float(n_bytes), 1.0)

    def refine_price(self, cached_rows: float, *, impl: str = "xla",
                     placement: str = "partitioned") -> float:
        """Seconds to serve a selection by REFINING a cached superset
        bitmap instead of rescanning the base column: stream the cached
        index vector, gather the predicate column at those positions,
        and write the surviving subset — three bitmap-proportional
        streams.  Compare with ``stream_cost`` of the base column under
        the same (impl, placement): subsumption wins exactly when the
        cached bitmap is narrow enough (< 1/3 of the base rows with
        equal efficiencies), which is the paper's bandwidth arbitrage —
        bytes moved decide, not operator count."""
        n_bytes = 3.0 * max(float(cached_rows), 0.0) * BYTES_PER_VALUE
        return self.stream_cost(n_bytes, impl=impl, placement=placement)

    def refine_wins(self, cached_rows: float, base_rows: float, *,
                    impl: str = "xla",
                    placement: str = "partitioned") -> bool:
        """Whether refining a ``cached_rows``-entry superset bitmap beats
        recomputing the selection from the ``base_rows``-row column.
        Both sides are priced under the same (impl, placement), so
        efficiency and call overhead cancel and the decision reduces to
        bytes streamed (3*cached < base) — the SAME verdict under any
        impl, which is what lets the fused-path router and the eager
        path's gate price with different impls yet never disagree."""
        return self.refine_price(cached_rows, impl=impl,
                                 placement=placement) \
            < self.stream_cost(max(float(base_rows), 1.0)
                               * BYTES_PER_VALUE,
                               impl=impl, placement=placement)

    def build_price(self, n_rows: float, n_value_cols: int = 0) -> float:
        """Recompute cost of a sorted-bucket join build: the O(n log n)
        key sort plus prefix sums over each carried value column, plus
        the per-engine replication broadcast — what a cache hit on a
        ``JoinBuild`` saves a streamed plan."""
        n_rows = max(float(n_rows), 1.0)
        sort_bytes = n_rows * BYTES_PER_VALUE * max(
            math.log2(max(n_rows, 2.0)), 1.0)
        value_bytes = n_rows * BYTES_PER_VALUE * (1 + n_value_cols)
        return (self.stream_cost(sort_bytes + value_bytes, impl="xla",
                                 placement="replicated")
                + self.broadcast_cost(n_rows * BYTES_PER_VALUE
                                      * (2 + n_value_cols)))

    # -- morsel pricing (streaming pipeline) -------------------------------- #

    def morsel_cost(self, total_rows: float, morsel_rows: int, n_cols: int,
                    *, impl: str = "xla", placement: str = "partitioned",
                    flops_per_row: float = 0.0,
                    include_transfer: bool = True,
                    src_tier: str = "host") -> float:
        """Seconds to stream ``total_rows`` in double-buffered morsels: the
        next morsel's promotion transfer (H2D from host, disk read + H2D
        from disk — ``promotion_cost``) overlaps the current morsel's
        compute, so steady state pays max(transfer, compute) per morsel
        and the pipeline ends add the smaller term once.  Per-dispatch
        overhead rides on the compute term — the pressure toward larger
        morsels that transfer overlap pushes against.
        ``include_transfer=False`` prices the in-memory regime where
        morsel placements are cached across executions (no promotion per
        run), which pushes toward large morsels; ``src_tier`` names the
        tier the stream source is resident on (default "host", the
        classic H2D regime)."""
        n_morsels = max(-(-int(total_rows) // int(morsel_rows)), 1)
        m_bytes = morsel_rows * BYTES_PER_VALUE * n_cols
        # each staged morsel pays a fixed dispatch/slicing cost on top of
        # its proportional transfer — the out-of-core pressure toward
        # budget-sized morsels (the in-memory regime caches placements,
        # so it keeps the pure bandwidth/overlap trade)
        t_x = (self.promotion_cost(m_bytes, src_tier)
               + self.stage_overhead_s) if include_transfer else 0.0
        t_c = self.stream_cost(m_bytes, impl=impl, placement=placement,
                               flops=flops_per_row * morsel_rows)
        return n_morsels * max(t_x, t_c) + min(t_x, t_c)

    def choose_morsel_rows(self, total_rows: float, n_cols: int, *,
                           impl: str = "xla", align: Optional[int] = None,
                           flops_per_row: float = 0.0,
                           include_transfer: bool = True,
                           src_tier: str = "host") -> int:
        """argmin of ``morsel_cost`` over power-of-two candidates, aligned
        to the engine count so one morsel shards evenly per pseudo-channel.
        Small morsels drown in dispatch overhead, huge ones serialize the
        first transfer behind nothing — the sweet spot is plan-dependent,
        which is why the optimizer prices it per plan."""
        align = align or self.n_engines
        total = max(int(total_rows), 1)
        best_rows, best_cost = None, math.inf
        candidates = []
        k = 10                                      # start at 1024-ish rows
        while (1 << k) * align < total * 2:
            candidates.append((1 << k) * align)
            k += 1
        candidates.append(-(-total // align) * align)   # whole input
        for rows in candidates:
            c = self.morsel_cost(total, rows, n_cols, impl=impl,
                                 flops_per_row=flops_per_row,
                                 include_transfer=include_transfer,
                                 src_tier=src_tier)
            if c < best_cost:
                best_rows, best_cost = rows, c
        return best_rows


# --------------------------------------------------------------------------- #
# physical planning

@dataclasses.dataclass
class PhysNode:
    """A logical node annotated with the chosen physical alternative."""
    op: str
    logical: L.Node
    impl: str
    placement: str
    n_passes: int
    est_rows_out: float
    cost_s: float
    gbps: float
    alternatives: Dict[str, float]
    children: Tuple["PhysNode", ...] = ()
    morsel_rows: Optional[int] = None     # streaming pipeline granularity
    n_bytes: float = 0.0                  # predicted bytes moved (priced)
    shard_strategy: Optional[str] = None  # joins under sharding:
                                          # "broadcast" | "shuffle"

    @property
    def total_cost_s(self) -> float:
        return self.cost_s + sum(c.total_cost_s for c in self.children)

    def describe(self) -> str:
        morsel = f" morsel={self.morsel_rows}" if self.morsel_rows else ""
        strat = f" strategy={self.shard_strategy}" if self.shard_strategy \
            else ""
        return (f"impl={self.impl} placement={self.placement} "
                f"passes={self.n_passes} est_rows={self.est_rows_out:.0f} "
                f"cost={self.cost_s * 1e6:.1f}us bw={self.gbps:.0f}GB/s"
                f"{morsel}{strat}")


def _choose(model: CostModel, op: str, n_bytes: float,
            placements: Tuple[str, ...], *, n_passes: int = 1,
            flops: float = 0.0,
            pallas_placements: Optional[Tuple[str, ...]] = None):
    """argmin over impl x placement for ``op``; returns (impl, placement,
    cost, alts).  ``pallas_placements``, where given, are the only
    placements on which the kernel would run."""
    alts = {}
    for impl in model.impls(op):
        for pl in placements:
            if impl == "pallas" and pallas_placements is not None \
                    and pl not in pallas_placements:
                continue
            alts[f"{impl}/{pl}"] = model.stream_cost(
                n_bytes, impl=impl, placement=pl, n_passes=n_passes,
                flops=flops)
    best = min(alts, key=alts.get)
    impl, pl = best.split("/")
    return impl, pl, alts[best], alts


def _stream_placements(model: CostModel) -> Tuple[str, ...]:
    """Stream-role placement alternatives: an active shard layout replaces
    the GSPMD 'partitioned' layout with the explicit shard_map striping
    (mesh=1 plans stay byte-for-byte what they were)."""
    if model.n_shards > 1:
        return ("sharded", "congested")
    return ("partitioned", "congested")


def plan_physical(node: L.Node, stats: Dict[str, TableStats],
                  model: CostModel, *, role: str = "stream") -> PhysNode:
    """Annotate a (logically optimized) plan with per-operator impl,
    per-column placement, pass counts, and costs.

    ``role`` is the placement context a parent imposes: the build side of a
    join and a TrainGLM dataset are ``"build"`` (must be replicated, the
    paper's URAM/Fig. 10a replication); everything else streams.
    """
    corr = model.sel_corrections or None
    rows = estimate_rows(node, stats, corr)

    if isinstance(node, L.Scan):
        n_cols = len(L.output_columns(node, {t: s.columns
                                             for t, s in stats.items()}))
        n_bytes = stats[node.table].num_rows * BYTES_PER_VALUE * n_cols
        if role == "build":
            # replication is not free even on one engine: the source
            # column is read once (its channel's stream) before the
            # inter-engine broadcast — omitting this made the optimizer
            # hide a large build side's entire scan behind role="build"
            cost = model.broadcast_cost(n_bytes) + model.stream_cost(
                n_bytes, impl="xla", placement="replicated")
            return PhysNode("scan", node, "xla", "replicated", 1, rows,
                            cost, model.bandwidth_gbps("replicated"),
                            {"xla/replicated": cost}, n_bytes=n_bytes)
        impl, pl, cost, alts = _choose(model, "scan", n_bytes,
                                       _stream_placements(model))
        return PhysNode("scan", node, impl, pl, 1, rows, cost,
                        model.bandwidth_gbps(pl), alts, n_bytes=n_bytes)

    if isinstance(node, (L.Filter, L.FilterProject)):
        child = plan_physical(node.child, stats, model, role=role)
        in_rows = estimate_rows(node.child, stats, corr)
        n_out_cols = len(node.columns) if isinstance(node, L.FilterProject) \
            else 1
        n_bytes = in_rows * BYTES_PER_VALUE + rows * BYTES_PER_VALUE \
            * n_out_cols
        placements = ("replicated",) if role == "build" \
            else _stream_placements(model)
        op = "filter_project" if isinstance(node, L.FilterProject) \
            else "filter"
        # the executor runs the selection kernel only on a base column it
        # partitions into whole SELECT_BLOCK blocks per engine
        # (Executor._filter_table); every other selection takes the XLA
        # mask, so only there is the kernel an alternative
        engines = {"partitioned": model.n_engines, "sharded": model.n_shards}
        kernel_pls = tuple(
            p for p in placements
            if role != "build" and isinstance(node.child, L.Scan)
            and p in engines
            and in_rows % (engines[p] * SELECT_BLOCK) == 0)
        impl, pl, cost, alts = _choose(model, op, n_bytes, placements,
                                       pallas_placements=kernel_pls)
        return PhysNode(op, node, impl, pl, 1, rows, cost,
                        model.bandwidth_gbps(pl), alts, (child,),
                        n_bytes=n_bytes)

    if isinstance(node, L.Join):
        left = plan_physical(node.left, stats, model, role="stream")
        right = plan_physical(node.right, stats, model, role="build")
        build_rows = estimate_rows(node.right, stats, corr)
        probe_rows = estimate_rows(node.left, stats, corr)
        n_passes = max(-(-int(build_rows) // HT_CAPACITY), 1)
        unique = key_is_unique(node.right, node.on, stats)
        chain = 1.0 if unique \
            else expected_chain_length(node.right, node.on, stats)
        if unique:
            # open-addressing fast path: one egress line per probe row,
            # plus the one-time hash-table build over the build rows
            # (written once across all passes, so divided back out)
            n_bytes = (probe_rows * BYTES_PER_VALUE
                       + build_rows * BYTES_PER_VALUE / n_passes)
            op = "join"
        else:
            # multi-match probe: per-row work scales with the expected
            # duplicate-chain length, and the variable-cardinality pair
            # list (l_idx, s_idx) is materialized output.  Only the probe
            # stream is rescanned per pass; the pair list and the sorted-
            # bucket build (an O(n log n) sort of the build rows) are paid
            # once, so their bytes are divided by n_passes before
            # stream_cost multiplies everything back up
            out_pairs = rows
            sort_bytes = build_rows * BYTES_PER_VALUE * max(
                math.log2(max(build_rows, 2.0)), 1.0)
            n_bytes = (probe_rows * BYTES_PER_VALUE * max(chain, 1.0)
                       + (2 * out_pairs * BYTES_PER_VALUE + sort_bytes)
                       / n_passes)
            op = "join_multi"
        # the probe runs wherever the probe stream already lives (fused /
        # streamed probes read the scan's placement; the build side is
        # replicated by construction) — pricing an independent join
        # placement would optimize a decision execution never consults
        probe_pl = left.placement if left.placement != "replicated" \
            else _stream_placements(model)[0]
        impl, pl, cost, alts = _choose(model, op, n_bytes, (probe_pl,),
                                       n_passes=n_passes)
        shard_strategy = None
        if model.n_shards > 1 and pl == "sharded":
            # two ways to co-locate build and probe rows on a shard:
            #   broadcast — replicate the build to every shard over the
            #     interconnect; each shard probes against the FULL build
            #     (ceil(build / HT_CAPACITY) probe rescans, n redundant
            #     build sorts);
            #   shuffle — hash-repartition BOTH sides; each shard builds
            #     only its ~1/n slice, collapsing the rescan passes, at
            #     the price of (n-1)/n of every byte crossing the
            #     interconnect.
            # The crossover is the paper's channel-pricing trade: rescan
            # bytes at aggregate HBM bandwidth vs shuffle bytes on the
            # narrow interconnect channel.
            n = float(model.n_shards)
            build_bytes = build_rows * BYTES_PER_VALUE
            probe_bytes = probe_rows * BYTES_PER_VALUE
            passes_sh = max(-(-int(max(build_rows / n, 1.0))
                              // HT_CAPACITY), 1)

            def _strategy_bytes(local_build, passes, n_copies):
                # aggregate bytes in stream_cost's accounting: one-time
                # build terms are divided by the pass count that
                # multiplies them back up; ``n_copies`` = how many shards
                # redo the build work (n under broadcast, aggregate 1x
                # across shards under shuffle)
                if unique:
                    return (probe_bytes + n_copies * local_build
                            * BYTES_PER_VALUE / passes)
                sort_b = n_copies * local_build * BYTES_PER_VALUE * max(
                    math.log2(max(local_build, 2.0)), 1.0)
                return (probe_bytes * max(chain, 1.0)
                        + (2 * rows * BYTES_PER_VALUE + sort_b) / passes)

            alt_b = model.shard_broadcast_cost(build_bytes) \
                + model.stream_cost(_strategy_bytes(build_rows, n_passes, n),
                                    impl=impl, placement="sharded",
                                    n_passes=n_passes)
            alt_s = model.shuffle_cost(probe_bytes + build_bytes) \
                + model.stream_cost(
                    _strategy_bytes(build_rows / n, passes_sh, n),
                    impl=impl, placement="sharded", n_passes=passes_sh)
            alts["shard/broadcast"] = alt_b
            alts["shard/shuffle"] = alt_s
            if alt_s < alt_b:
                shard_strategy, cost, n_passes = "shuffle", alt_s, passes_sh
            else:
                shard_strategy, cost = "broadcast", alt_b
        return PhysNode(op, node, impl, pl, n_passes, rows, cost,
                        model.bandwidth_gbps(pl), alts, (left, right),
                        n_bytes=n_bytes, shard_strategy=shard_strategy)

    if isinstance(node, L.Project):
        child = plan_physical(node.child, stats, model, role=role)
        n_bytes = rows * BYTES_PER_VALUE * len(node.columns)
        impl, pl, cost, alts = _choose(model, "project", n_bytes,
                                       _stream_placements(model)[:1])
        return PhysNode("project", node, impl, pl, 1, rows, cost,
                        model.bandwidth_gbps(pl), alts, (child,),
                        n_bytes=n_bytes)

    if isinstance(node, L.Aggregate):
        child = plan_physical(node.child, stats, model, role=role)
        in_rows = estimate_rows(node.child, stats, corr)
        n_bytes = in_rows * BYTES_PER_VALUE
        impl, pl, cost, alts = _choose(model, "aggregate", n_bytes,
                                       _stream_placements(model)[:1])
        # streaming granularity for the whole pipeline this aggregate
        # roots: priced on the probe-spine base scan (the stream source)
        base = probe_base_scan(node.child)
        morsel_rows = None
        if base is not None and base.table in stats:
            n_cols = len(base.columns) if base.columns is not None \
                else len(stats[base.table].columns)
            # one morsel must cut evenly both across the host engines and
            # across the shard mesh
            align = math.lcm(model.n_engines, model.n_shards) \
                if model.n_shards > 1 else None
            morsel_rows = model.choose_morsel_rows(
                stats[base.table].num_rows, max(n_cols, 1), impl=impl,
                align=align)
        return PhysNode("aggregate", node, impl, pl, 1, 1.0, cost,
                        model.bandwidth_gbps(pl), alts, (child,),
                        morsel_rows=morsel_rows, n_bytes=n_bytes)

    if isinstance(node, L.TrainGLM):
        child = plan_physical(node.child, stats, model, role="build")
        in_rows = estimate_rows(node.child, stats)
        k = len(node.grid)
        d = len(node.features)
        dataset = in_rows * BYTES_PER_VALUE * (d + 1)
        epoch_bytes = dataset * node.epochs * k
        # each engine streams its LOCAL replica (Fig. 10a); without
        # replication every job reads one remote copy — the flat line
        flops = 6.0 * node.epochs * k * in_rows * d
        alts = {
            "xla/replicated": model.broadcast_cost(dataset)
            + model.stream_cost(epoch_bytes, impl="xla",
                                placement="partitioned", flops=flops),
            "xla/congested": model.stream_cost(
                epoch_bytes, impl="xla", placement="congested", flops=flops),
        }
        shard_strategy = None
        if model.n_shards > 1:
            # Fig. 10a on the shard mesh: pay the interconnect once to
            # replicate the training set to every shard, then every epoch
            # streams the LOCAL replica at sharded aggregate bandwidth —
            # priced against the congested baseline where all K jobs
            # contend for a single remote copy
            alts["shard/replicated"] = model.shard_broadcast_cost(dataset) \
                + model.stream_cost(epoch_bytes, impl="xla",
                                    placement="sharded", flops=flops)
        best = min(alts, key=alts.get)
        impl, pl = best.split("/")
        if impl == "shard":
            impl, pl, shard_strategy = "xla", "sharded", best.split("/")[1]
        # streaming granularity for the epoch loop: each epoch re-streams
        # the training set, so the morsel argmin prices the per-pass
        # feature+label bytes with the per-row SGD flops
        base = probe_base_scan(node.child)
        morsel_rows = None
        if base is not None and base.table in stats:
            align = math.lcm(model.n_engines, model.n_shards) \
                if model.n_shards > 1 else None
            morsel_rows = model.choose_morsel_rows(
                stats[base.table].num_rows, d + 1, impl=impl, align=align,
                flops_per_row=6.0 * k * d)
        # est_rows_out is a CARDINALITY (one weight vector row per grid
        # entry would still collapse to a scalar-ish result; the planner
        # treats training like an aggregate root) — the grid size lives
        # in the priced bytes/flops, not the selectivity slot
        return PhysNode("train_glm", node, impl, pl, 1, 1.0,
                        alts[best], model.bandwidth_gbps(pl), alts, (child,),
                        morsel_rows=morsel_rows, n_bytes=epoch_bytes,
                        shard_strategy=shard_strategy)

    if isinstance(node, L.ScoreGLM):
        child = plan_physical(node.child, stats, model, role=role)
        d = len(node.features)
        in_rows = estimate_rows(node.child, stats, corr)
        # one pass over the feature columns plus the written score column;
        # the cached weight vector is noise
        n_bytes = in_rows * BYTES_PER_VALUE * d + rows * BYTES_PER_VALUE
        impl, pl, cost, alts = _choose(model, "score_glm", n_bytes,
                                       _stream_placements(model)[:1],
                                       flops=2.0 * in_rows * d)
        return PhysNode("score_glm", node, impl, pl, 1, rows, cost,
                        model.bandwidth_gbps(pl), alts, (child,),
                        n_bytes=n_bytes)

    raise TypeError(node)


def probe_base_scan(node: L.Node) -> Optional[L.Scan]:
    """The Scan feeding a pipeline's probe spine — the stream source the
    morsel driver cuts into partition-granular slices.  Follows probe-side
    children (Join.left) down to the leaf."""
    while not isinstance(node, L.Scan):
        if isinstance(node, (L.Filter, L.FilterProject, L.Project,
                             L.Aggregate, L.TrainGLM, L.ScoreGLM)):
            node = node.child
        elif isinstance(node, L.Join):
            node = node.left
        else:
            return None
    return node


def join_orientation_cost(join: L.Join, stats: Dict[str, TableStats],
                          model: CostModel) -> float:
    """Total priced cost of one build/probe orientation of ``join`` —
    includes the build side's replication broadcast, its sort/hash build
    bytes, the chain-length-scaled probe stream, and multi-pass rescans.
    ``optimize.choose_build_side`` compares the two orientations with this
    instead of raw cardinality, so a provably-unique (fusable) build side
    is no longer swapped away for a marginally smaller duplicate-keyed
    one."""
    return plan_physical(join, stats, model).total_cost_s


def column_placements(phys: PhysNode) -> Dict[Tuple[str, str], str]:
    """(table, column) -> chosen placement, read off the scan leaves — the
    decision callers previously had to make by hand with ``place()``."""
    out: Dict[Tuple[str, str], str] = {}

    def visit(p: PhysNode):
        if p.op == "scan":
            node = p.logical
            cols = node.columns or ()
            for c in cols:
                out[(node.table, c)] = p.placement
            if not cols:
                out[(node.table, "*")] = p.placement
        for c in p.children:
            visit(c)

    visit(phys)
    return out
