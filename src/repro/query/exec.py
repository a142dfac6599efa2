"""Physical executor: lowers optimized plans onto the columnar engine.

Three lowering paths:

* **fused/jitted** — aggregate-rooted select/join pipelines compile to one
  jitted executable (the degenerate single-morsel pipeline): filters are
  masks, join probes binary-search cached sorted-bucket builds (exact for
  duplicate build keys — match counts weight the aggregate, bucket prefix
  sums serve build-column aggregates), and nothing compacted is ever
  materialized.  Executables are cached by plan *signature* (structure +
  shapes + physical decisions, predicate constants masked), so repeated
  queries — even with different range bounds — reuse one compilation.
* **streaming** (``mode="stream"``) — the same pipeline driven morsel by
  morsel (``query/pipeline.py``): join builds and the final aggregate are
  the pipeline breakers; the next morsel's placement transfer double-
  buffers against the current morsel's compute.  Streams datasets larger
  than one placement's capacity, which the other paths cannot touch.
* **eager** — Project-rooted and TrainGLM plans lower step by step onto
  ``columnar/engine.py`` operators, materializing BAT-style intermediates
  exactly like the hand-written pipelines did.

Placement is decided per column by the cost model and applied (and cached)
here — callers hand the catalog *unplaced* host tables.
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.columnar import engine
from repro.columnar.table import Column, MorselSpec, Table
from repro.core.channels import ChannelPlan, plan as make_plan
from repro.distributed.sharding import ShardLayout
from repro.launch.mesh import make_host_mesh
from repro.query import logical as L
from repro.query import pipeline as pl
from repro.query import telemetry as tm
from repro.query.cache import SemanticCache, cache_disabled
from repro.query.cost import (
    BYTES_PER_VALUE, ColumnStats, CostModel, PhysNode, TableStats,
    column_placements, key_is_unique, plan_physical,
)
from repro.query.optimize import optimize
from repro.query.tiering import (
    SpillPlan, TierBudgets, default_spill_dir, plan_spill,
)

_TIER_RANK = {"device": 0, "host": 1, "disk": 2}


class PlacementCapacityError(RuntimeError):
    """A whole-column placement exceeds the configured per-placement
    capacity (the paper's 256 MiB pseudo-channel budget).  Optimized
    plans with a streamable spine no longer fail here — the executor
    reroutes them through a priced device/host/disk spill plan — so this
    survives only where spilling cannot help: the naive oracle and
    forced-eager paths under an explicit capacity, a single morsel
    larger than the budget, and working sets that overflow even the
    disk tier."""


class Catalog:
    """Named, *unplaced* host tables + the statistics the optimizer uses."""

    def __init__(self):
        self.tables: Dict[str, Table] = {}
        self.stats: Dict[str, TableStats] = {}

    def register(self, table: Table) -> "Catalog":
        self.tables[table.name] = table
        ranges = {}
        for name, col in table.columns.items():
            if jnp.issubdtype(col.dtype, jnp.integer):
                host = jax.device_get(col.data)
                ranges[name] = ColumnStats(int(host.min()), int(host.max()),
                                           int(np.unique(host).size))
        self.stats[table.name] = TableStats(
            table.num_rows, tuple(table.columns), ranges)
        return self

    @staticmethod
    def from_tables(*tables: Table) -> "Catalog":
        cat = Catalog()
        for t in tables:
            cat.register(t)
        return cat

    def update_column(self, table: str, column: str, data) -> None:
        """The mutation surface: replace a base column, bump the table's
        version (invalidating every dependent fingerprint), and refresh
        the statistics the optimizer plans against."""
        self.tables[table].update_column(column, data)
        self.register(self.tables[table])

    def versions(self) -> Dict[str, int]:
        """table -> mutation counter, the fingerprint dependency map."""
        return {name: t.version for name, t in self.tables.items()}


@dataclasses.dataclass
class Result:
    value: object
    physical: Optional[PhysNode]
    cache_hit: bool
    wall_s: float
    mode: str = "batch"                 # batch | stream
    result_cache_hit: bool = False      # served from the semantic cache

    def explain(self) -> str:
        if self.physical is None:
            return "(naive: no physical plan)"
        return _explain(self.physical)


def _explain(p: PhysNode, indent: int = 0) -> str:
    lines = [f"{'  ' * indent}{p.op}: {p.describe()}"]
    for c in p.children:
        lines.append(_explain(c, indent + 1))
    return "\n".join(lines)


def _counter(name: str, doc: str):
    """Back-compat surface for the consolidated metrics: the old ad-hoc
    attributes (``ex.cache_hits`` etc.) keep reading and writing, but the
    value now lives in the executor's MetricsRegistry under ``name``."""

    def fget(self):
        return int(self.metrics.value(name))

    def fset(self, value):
        self.metrics.set(name, value)

    return property(fget, fset, doc=doc)


class Executor:
    """optimize -> cost -> lower -> run, with a compiled-plan cache."""

    # consolidated counters (satellite of the telemetry PR): one registry,
    # old attribute names preserved as properties — external code that
    # reads or bumps them (serve.py, tests, benchmarks) is unaffected
    cache_hits = _counter("exec.plan_cache_hits",
                          "compiled-plan cache hits")
    cache_misses = _counter("exec.plan_cache_misses",
                            "compiled-plan cache misses")
    result_hits = _counter("exec.result_cache_hits",
                           "semantic cache: whole results")
    subplan_hits = _counter("exec.subplan_cache_hits",
                            "semantic cache: eager intermediates")
    build_hits = _counter("exec.build_cache_hits",
                          "semantic cache: join builds")
    model_hits = _counter("exec.model_cache_hits",
                          "semantic cache: trained GLM weights")
    subsumption_hits = _counter("exec.subsumption_hits",
                                "selections served by refinement")
    refine_bytes_streamed = _counter(
        "exec.refine_bytes_streamed",
        "bitmap bytes the refine path read")
    refine_bytes_avoided = _counter(
        "exec.refine_bytes_avoided",
        "base-column bytes refinement did NOT read")
    trace_count = _counter("exec.trace_count",
                           "bumped inside traced bodies only")

    def __init__(self, catalog: Catalog, mesh=None, axis: str = "model",
                 cost_model: Optional[CostModel] = None,
                 placement_capacity_bytes: Optional[int] = None,
                 cache_bytes: Optional[int] = None,
                 semantic_cache: Optional[SemanticCache] = None,
                 overlap_transfers: Optional[bool] = None,
                 telemetry: Optional[tm.Telemetry] = None,
                 tenant: Optional[str] = None,
                 shards: Optional[int] = None,
                 tier_budgets: Optional[TierBudgets] = None):
        self.catalog = catalog
        # tenant label every semantic-cache admission carries: with
        # per-tenant byte-budget shares configured on a SHARED cache,
        # this executor's entries are accounted against (and capped by)
        # its tenant's share
        self.tenant = tenant
        # cost-model epoch: bumped by every recost(); part of the
        # compiled-plan cache key, so physical plans priced under a
        # superseded model are never silently reused
        self.cost_epoch = 0
        # spans + bandwidth ledger are shared (default: the process
        # global, REPRO_TRACE-gated); the metrics registry is PRIVATE so
        # multi-tenant counters never mix
        self.tel = telemetry if telemetry is not None else tm.get()
        self.metrics = tm.MetricsRegistry()
        self.reset_metrics()
        # sharded placement axis (device = pseudo-channel): shards=None
        # keeps every plan, fingerprint and cache key byte-identical to
        # the single-device executor this grew out of
        n_sh = max(int(shards), 1) if shards else 1
        self.shard_layout: Optional[ShardLayout] = \
            ShardLayout(n_sh) if n_sh > 1 else None
        if self.shard_layout is not None:
            _ = self.shard_layout.mesh      # fail fast on missing devices
            if mesh is None:
                # ONE device set everywhere: the base mesh collapses onto
                # the shard mesh's devices, so replicated builds and
                # congested streams can feed the same jitted step as
                # shard-placed morsels (jit rejects mixed device sets)
                mesh = jax.sharding.Mesh(
                    np.array(jax.devices()[:n_sh]).reshape(1, n_sh),
                    ("data", "model"))
        # no mesh and no shards: ONE device.  More devices are asked for
        # with ``shards`` or an explicit mesh, never taken because they
        # happen to be visible
        self.mesh = mesh if mesh is not None \
            else make_host_mesh(jax.devices()[:1])
        self.axis = axis
        n_eng = self.mesh.shape[axis]
        # default model: the fixed constants.  A measured calibration is
        # passed in explicitly (CostModel(calibration=load_calibration(
        # path))), never picked up from the working directory
        self.cost_model = cost_model or CostModel(n_eng, n_shards=n_sh)
        if self.shard_layout is not None \
                and self.cost_model.n_shards != n_sh:
            # a caller-supplied model prices what this executor runs
            self.cost_model.n_shards = n_sh
        # tiered placement posture.  ``tier_budgets`` (or the explicit
        # device capacity, or the REPRO_PLACEMENT_CAP / REPRO_HOST_CAP /
        # REPRO_DISK_CAP environment) bounds each memory tier; the device
        # budget drives SPILL ROUTING of optimized over-capacity plans.
        # The hard ``placed()`` gate stays keyed to an EXPLICIT capacity
        # (constructor arg or tier_budgets.device): an environment-only
        # posture forces the spill paths without making the naive oracle
        # or forced-eager observability paths refuse to measure.
        self._cap_explicit = placement_capacity_bytes is not None \
            or (tier_budgets is not None
                and tier_budgets.device is not None)
        self.tier_budgets = tier_budgets if tier_budgets is not None \
            else TierBudgets.from_env(placement_capacity_bytes)
        self.placement_capacity_bytes = self.tier_budgets.device
        self._spill_dir: Optional[str] = None
        # semantic result/subplan cache: opt-in (``cache_bytes`` budget,
        # or a shared SemanticCache instance) so differential baselines
        # and throughput benchmarks measure real execution by default
        self.cache: Optional[SemanticCache] = None
        if semantic_cache is not None:
            self.install_cache(semantic_cache)
        elif cache_bytes:
            self.install_cache(SemanticCache(cache_bytes,
                                             model=self.cost_model))
        if overlap_transfers is None:
            overlap_transfers = os.environ.get(
                "REPRO_OVERLAP", "1").lower() not in ("0", "off", "no")
        self.overlap_transfers = overlap_transfers
        self.plans: Dict[str, ChannelPlan] = {
            p: make_plan(self.mesh, axis, p)
            for p in ("partitioned", "replicated", "congested")}
        if self.shard_layout is not None:
            # one engine per mesh device: the shard axis IS the paper's
            # pseudo-channel axis, so the sharded plan partitions over it
            self.plans["sharded"] = ChannelPlan(
                self.shard_layout.mesh, self.shard_layout.axis,
                "partitioned")
        self._compiled: Dict[tuple, object] = {}
        self._planned: Dict[L.Node, tuple] = {}
        self._fps: Dict[L.Node, str] = {}
        # plan -> extracted SelectionInterval (or None): version-free,
        # so never invalidated — the fused-path router consults it per
        # execution
        self._sints: Dict[L.Node, Optional[L.SelectionInterval]] = {}
        self._placed: Dict[Tuple[str, str, str], jax.Array] = {}
        self._builds: Dict[tuple, tuple] = {}
        self._morsels: Dict[tuple, jax.Array] = {}
        self._morsel_cache_rows: Dict[str, int] = {}
        self._seen_versions: Dict[str, int] = catalog.versions()

    _COUNTERS = (
        "exec.plan_cache_hits", "exec.plan_cache_misses",
        "exec.result_cache_hits", "exec.subplan_cache_hits",
        "exec.build_cache_hits", "exec.model_cache_hits",
        "exec.subsumption_hits",
        "exec.refine_bytes_streamed", "exec.refine_bytes_avoided",
        "exec.trace_count", "exec.refine_routed")

    def reset_metrics(self) -> None:
        """Zero every counter and histogram (the registry keeps its
        identity, so held references stay valid)."""
        self.metrics.reset()
        for name in self._COUNTERS:
            self.metrics.set(name, 0)

    def metrics_snapshot(self) -> dict:
        """Flat snapshot of the consolidated metrics registry — counters
        verbatim, histograms as ``name.{count,mean,p50,p95,max}`` — plus
        the semantic cache's accounting when one is installed."""
        out = self.metrics.snapshot()
        if self.cache is not None:
            out.update(self.cache.stats_dict())
        return out

    def install_cache(self, cache: Optional[SemanticCache]) -> None:
        """Attach a semantic cache — possibly one SHARED with other
        executors over the same catalog.  This is the ONE surface that
        owns the REPRO_CACHE=0 kill-switch (CI's cache-off leg): under
        it, installation is a no-op everywhere, so no caller can
        re-enable caching around the gate."""
        if cache is None or cache_disabled():
            return
        self.cache = cache
        # register the current versions as the cache's drift baseline:
        # a later mutation then sweeps shared entries even if THIS
        # executor is the first tenant to notice it
        cache.sync_versions(self.catalog.versions())

    # -- versioned invalidation ---------------------------------------------- #

    def _sync_versions(self) -> None:
        """Notice table mutations since the last call and purge every
        device-state cache derived from stale data: placements, morsel
        slices, join builds, memoized plans (statistics changed), and the
        semantic cache's dependent entries.  Fingerprints embed versions,
        so even an unswept entry could never be *served* — the sweep only
        reclaims bytes and device memory."""
        drifted = False
        for name, t in self.catalog.tables.items():
            if self._seen_versions.get(name) == t.version:
                continue
            drifted = True
            if name in self._seen_versions:
                self.catalog.register(t)           # refresh statistics
                self._placed = {k: v for k, v in self._placed.items()
                                if k[0] != name}
                self._morsels = {k: v for k, v in self._morsels.items()
                                 if k[0] != name}
                self._morsel_cache_rows.pop(name, None)
                self._builds = {k: v for k, v in self._builds.items()
                                if k[0].table != name}
                self._planned.clear()              # stats feed every plan
                self._fps.clear()
            self._seen_versions[name] = t.version
        # the cache tracks versions itself (it may be SHARED by several
        # executors over this catalog): whichever tenant notices a
        # mutation first sweeps the dependent entries — and the
        # subsumption interval buckets — for everyone.  Gated on local
        # drift so the hot path never takes the shared lock (every
        # tenant's own detector fires off the same catalog counters;
        # install_cache registered the baseline)
        if drifted and self.cache is not None:
            self.cache.sync_versions(self.catalog.versions())

    # -- shard layout --------------------------------------------------------- #

    @property
    def n_shards(self) -> int:
        return self.shard_layout.n_shards if self.shard_layout else 1

    def _layout_key(self) -> Optional[tuple]:
        """Shard-layout element of every plan-derived key: None on a
        1-device executor, so those keys stay byte-identical to the
        pre-sharding executor's."""
        return self.shard_layout.key() if self.shard_layout else None

    # -- online re-costing ---------------------------------------------------- #

    def recost(self, calibration: Optional[dict] = None) -> int:
        """Fold a calibration overlay into the live cost model and bump
        the cost-model EPOCH — the serve-side recalibration entry point.

        ``calibration`` (usually ``ledger.calibration_overlay(model)``, or
        a fresh ``load_calibration(path)``) overlays measured prices;
        ``None`` folds only the ledger's selectivity corrections.
        Application is idempotent (the model re-baselines against its
        pristine constants), and every cost-derived memo is flushed:
        memoized (opt, phys) plans and fingerprints re-derive, and the
        epoch's presence in ``_cache_key`` keeps compiled executables,
        stream pipelines and their physical decisions from being served
        across the re-cost boundary.  Already-running streams are NOT
        touched — in-flight members finish on the pipeline they were
        admitted with; only subsequent plans see the new prices."""
        if calibration:
            self.cost_model.apply_calibration(calibration)
        # cardinality feedback (the PR-7 leftover): per-(table, column)
        # measured/predicted byte ratios from the ledger scale the
        # selectivity estimates the NEXT plans are priced with — clamped
        # at use so one anomalous window cannot swing estimates 10x
        corrections = self.tel.ledger.selectivity_corrections()
        if corrections:
            self.cost_model.sel_corrections.update(corrections)
        self.cost_epoch += 1
        self._planned.clear()
        self._fps.clear()
        self.metrics.inc("exec.recost_count")
        self.metrics.set("exec.cost_epoch", self.cost_epoch)
        self.tel.instant("exec.recost", epoch=self.cost_epoch,
                         calibrated_from=self.cost_model.calibrated_from)
        return self.cost_epoch

    def fingerprint_of(self, node: L.Node) -> str:
        """Semantic fingerprint of the OPTIMIZED form of ``node`` against
        current table versions — the result-cache key (memoized; the memo
        is flushed whenever any table version moves)."""
        self._sync_versions()
        fp = self._fps.get(node)
        if fp is None:
            opt, _ = self.plan(node)
            fp = L.fingerprint(opt, self.catalog.versions(),
                               layout=self._layout_key())
            self._fps[node] = fp
        return fp

    # -- placement ---------------------------------------------------------- #

    def placed(self, table: str, column: str, placement: str) -> jax.Array:
        """Column array under a placement, cached — the per-column ``place()``
        decision the cost model now owns."""
        key = (table, column, placement)
        if key not in self._placed:
            data = self.catalog.tables[table].column(column)
            cap = self.placement_capacity_bytes if self._cap_explicit \
                else None
            if cap is not None and data.nbytes > cap:
                n_bytes = int(data.nbytes)
                # floor-aligned suggested granularity for a 3-column
                # stream (predicate + two carried values, the common
                # shape); generalize as budget // (4 * n_stream_cols)
                n_eng = self.plans["partitioned"].n_engines
                suggest = max((int(cap) // (BYTES_PER_VALUE * 3))
                              // n_eng * n_eng, n_eng)
                raise PlacementCapacityError(
                    f"working set over placement budget: column "
                    f"{table}.{column} ({placement}) is {n_bytes} bytes "
                    f"against the {int(cap)}-byte placement capacity "
                    f"({n_bytes / cap:.1f}x over).  Remedy: execute with "
                    f'mode="stream" and morsel_rows <= capacity // '
                    f"(4 * n_stream_cols) — e.g. morsel_rows={suggest} "
                    f"for a 3-column stream — so each morsel fits one "
                    "placement; or configure host/disk tier budgets "
                    "(TierBudgets / REPRO_HOST_CAP / REPRO_DISK_CAP) so "
                    "the spill planner can demote it.  Build/replicated "
                    "columns and eagerly-lowered plans need every placed "
                    "column to fit one placement")
            plan = self.plans.get(placement)
            if plan is None or (plan.placement == "partitioned"
                                and data.shape[0] % plan.n_engines != 0):
                # non-dividing rows cannot device_put under P(axis):
                # replicate instead (shard_map re-shards on entry, so
                # results are unchanged — only locality is lost)
                plan = self.plans["replicated"]
            self._placed[key] = plan.place(data)
        return self._placed[key]

    def _placed_table(self, node: L.Scan, placement: str) -> Table:
        cols = node.columns or tuple(self.catalog.tables[node.table].columns)
        return Table(node.table,
                     {c: Column(self.placed(node.table, c, placement), c)
                      for c in cols},
                     self.plans[placement])

    # -- entry points ------------------------------------------------------- #

    def execute(self, q, *, optimized: bool = True, mode: str = "batch",
                morsel_rows: Optional[int] = None) -> Result:
        """Run a logical plan.  ``mode="batch"`` is the whole-column path
        (fused single-morsel pipeline, or eager engine operators);
        ``mode="stream"`` drives the same pipeline morsel by morsel with
        double-buffered placement transfers, falling back to batch when
        the plan has no streamable probe spine; ``mode="eager"`` forces
        the step-by-step engine lowering under the SAME physical plan —
        the observability surface where every operator can be fenced and
        measured individually (the bandwidth ledger's per-op rows)."""
        node = q.node if isinstance(q, L.Q) else q
        t0 = time.perf_counter()
        with self.tel.span("exec.execute", mode=mode,
                           optimized=optimized) as sp:
            self._sync_versions()      # every path, incl. the naive oracle
            if not optimized:
                if mode == "stream":
                    raise ValueError(
                        "mode='stream' lowers through the optimizer's "
                        "physical plan; it cannot combine with "
                        "optimized=False")
                # the naive path is the differential oracle: it never
                # reads or feeds the semantic cache
                sp.set(path="naive")
                return Result(self._run_eager(node, None), None, False,
                              time.perf_counter() - t0)
            orig = node
            node, phys = self.plan(node)
            if self.cache is not None:
                fp = self.fingerprint_of(orig)
                entry = self.cache.get(("result", fp))
                if entry is not None:
                    self.metrics.inc("exec.result_cache_hits")
                    sp.set(path="result_cache",
                           outcome="hit", reason="fingerprint_match")
                    return Result(entry.value, phys, True,
                                  time.perf_counter() - t0, mode=mode,
                                  result_cache_hit=True)
                sp.set(outcome="miss")
            # tiered placement: an over-budget working set gets a spill
            # plan (columns demoted to host/disk, priced by the model)
            # instead of a hard refusal; a batch-mode plan with a
            # streamable spine reroutes onto the morsel driver, which
            # promotes lower-tier morsels through the prefetch thread
            spill = self._maybe_spill(node)
            # TrainGLM roots lower onto the morsel-streamed trainer (the
            # paper's workload 3): per-epoch passes with the model
            # weights as the only cross-morsel carry — bit-identical to
            # the whole-column eager path, so forced-eager stays the
            # observability oracle while batch/stream never materialize
            # the training set on device at once
            if mode != "eager":
                tplan = pl.analyze_train(node, self.catalog.stats)
                if tplan is not None:
                    sp.set(path="train_stream")
                    value = self._run_train(node, phys, tplan,
                                            morsel_rows, spill=spill)
                    self._admit_result(orig, node, phys, value)
                    return Result(value, phys, False,
                                  time.perf_counter() - t0, mode="stream")
            if mode == "batch" and spill is not None:
                splan = pl.analyze(node, self.catalog.stats)
                if splan is not None:
                    sp.set(path="spill_stream")
                    value, hit = self._run_stream(node, phys, splan,
                                                  morsel_rows, spill=spill)
                    self._admit_result(orig, node, phys, value)
                    return Result(value, phys, hit,
                                  time.perf_counter() - t0, mode="stream")
                pplan = pl.analyze_project(node, self.catalog.stats)
                if pplan is not None:
                    sp.set(path="spill_stream_project")
                    value = self._run_stream_project(node, phys, pplan,
                                                     morsel_rows,
                                                     spill=spill)
                    self._admit_result(orig, node, phys, value)
                    return Result(value, phys, False,
                                  time.perf_counter() - t0, mode="stream")
            if mode == "stream":
                splan = pl.analyze(node, self.catalog.stats)
                if splan is not None:
                    sp.set(path="stream")
                    value, hit = self._run_stream(node, phys, splan,
                                                  morsel_rows, spill=spill)
                    self._admit_result(orig, node, phys, value)
                    return Result(value, phys, hit,
                                  time.perf_counter() - t0, mode="stream")
                sp.set(reason="no_streamable_spine")
            if mode == "eager":
                sp.set(path="eager")
                value = self._run_eager(node, phys)
                self._admit_result(orig, node, phys, value)
                return Result(value, phys, False,
                              time.perf_counter() - t0, mode="eager")
            sp.set(path="batch")
            value, hit = self._run(node, phys)
            self._admit_result(orig, node, phys, value)
            return Result(value, phys, hit, time.perf_counter() - t0)

    def _admit_result(self, orig: L.Node, opt: L.Node, phys: PhysNode,
                      value) -> None:
        """Offer a finished result to the semantic cache, priced by the
        physical plan's modeled recompute cost."""
        if self.cache is None:
            return
        fp = self.fingerprint_of(orig)
        self.cache.put(("result", fp), value,
                       kind="result", n_bytes=_value_nbytes(value),
                       recompute_s=phys.total_cost_s,
                       tables=L.tables_of(opt), tenant=self.tenant)
        if isinstance(opt, L.TrainGLM):
            # trained weights double as a SERVABLE MODEL: ScoreGLM plans
            # resolve them by this fingerprint, which embeds the training
            # tables' versions — a mutation strands the entry and the
            # next score retrains.  A tiny residency (K x d floats) buys
            # back the full epochs x dataset recompute, so eviction
            # fights strongly favor keeping models
            self.cache.put(("model", fp), value, kind="model",
                           n_bytes=_value_nbytes(value),
                           recompute_s=phys.total_cost_s,
                           tables=L.tables_of(opt), tenant=self.tenant)

    def plan(self, node: L.Node):
        """optimize + plan_physical, memoized by the (hashable) logical
        node — hot repeated queries skip replanning entirely (the cost-
        priced build-side choice runs plan_physical per orientation, so
        replanning every execution tripled the planning work).  Syncs
        table versions first, so a mutation flushes the memo before any
        stale statistics could be replayed."""
        self._sync_versions()
        if node in self._planned:
            return self._planned[node]
        with self.tel.span("exec.plan") as sp:
            with self.tel.span("exec.optimize"):
                opt = optimize(node, self.catalog.stats, self.cost_model)
            with self.tel.span("exec.cost_physical"):
                phys = plan_physical(opt, self.catalog.stats,
                                     self.cost_model)
            sp.set(predicted_s=phys.total_cost_s)
        self._planned[node] = (opt, phys)
        return opt, phys

    def explain(self, q) -> str:
        node = q.node if isinstance(q, L.Q) else q
        return _explain(self.plan(node)[1])

    # -- fused/jitted path (single-morsel pipeline) -------------------------- #

    def _run(self, node: L.Node, phys: PhysNode):
        """Aggregate-rooted pipelines — including duplicate-keyed build
        sides, whose pair-list aggregate stays fused: per-probe match
        counts weight the reduction and bucket prefix sums serve build-
        column aggregates, so nothing is lowered eagerly — compile to one
        executable and run it as a single whole-table morsel."""
        splan = pl.analyze(node, self.catalog.stats)
        if splan is None:
            return self._run_eager(node, phys), False
        if self._route_to_refine(node, splan):
            # a cached (superset) bitmap makes the eager gather path
            # cheaper than the fused full-column scan: the selection is
            # served by refinement instead of re-streaming the base column
            self.metrics.inc("exec.refine_routed")
            self.tel.instant("exec.route_refine",
                             reason="cached_bitmap_priced_below_scan")
            return self._run_eager(node, phys), False
        key = self._cache_key(node, phys)
        if key in self._compiled:
            self.metrics.inc("exec.plan_cache_hits")
            hit = True
        else:
            self.metrics.inc("exec.plan_cache_misses")
            self._compiled[key] = self._compile(node, phys, splan,
                                                rows=None)
            hit = False
        cp, specs = self._compiled[key]
        arrays = [self.placed(t, c, p) for t, c, p in specs]
        builds = self._breaker_arrays(splan.breakers)
        lits = jnp.asarray(L.literals(node), jnp.int32)
        ledger = self.tel.ledger.enabled
        with self.tel.span("exec.run_fused", compiled_hit=hit) as sp:
            if ledger:
                # fenced measurement: settle async input transfers first,
                # then time dispatch-to-completion of the fused step — the
                # one measurement the ledger apportions across the plan's
                # operators
                jax.block_until_ready(arrays)
                jax.block_until_ready(builds)
                t0 = time.perf_counter()
            carry = cp.step(lits, cp.init_carry(), jnp.int32(cp.rows),
                            *builds, *arrays)
            if ledger:
                jax.block_until_ready(carry)
                dt = time.perf_counter() - t0
                moved = sum(a.nbytes for a in arrays) \
                    + sum(b.nbytes for b in builds)
                sp.set(measured_s=dt, measured_bytes=moved)
                self.tel.ledger.record_plan(phys, dt, moved, mode="fused",
                                            shards=self.n_shards)
            return cp.finalize(carry), hit

    def _route_to_refine(self, node: L.Node, splan: pl.StreamPlan) -> bool:
        """Whether a breaker-free aggregate pipeline should abandon its
        fused full-column scan for the eager path because the semantic
        cache holds a selection bitmap (exact or superset) it can refine
        at lower priced cost.  Routing is purely a performance decision:
        both paths produce bit-identical answers, and the eager lowering
        performs the actual (exact-first, then tightest-superset) lookup."""
        if self.cache is None or splan.breakers:
            return False
        if node not in self._sints:
            self._sints[node] = L.selection_interval(node)
        si = self._sints[node]
        if si is None or si.table not in self.catalog.tables:
            return False
        version = self.catalog.tables[si.table].version
        n_rows = self.catalog.stats[si.table].num_rows
        gate = self._refine_gate(n_rows, "xla")
        exact = self.cache.peek(("bitmap", si.table, version, si.column,
                                 si.lo, si.hi))
        if exact is not None:
            # serving from the exact bitmap streams only the selected
            # positions; use the same pricing comparison as refinement
            return gate(exact)
        return self.cache.peek_superset(si.table, si.column, version,
                                        si.lo, si.hi, accept=gate) \
            is not None

    def _cache_key(self, node: L.Node, phys: PhysNode) -> tuple:
        shapes = tuple(sorted(
            (t, self.catalog.stats[t].num_rows)
            for t in {n.table for n in L.walk(node)
                      if isinstance(n, L.Scan)}))
        decisions = tuple(
            (p.op, p.impl, p.placement, p.n_passes, p.shard_strategy)
            for p in _walk_phys(phys)) if phys else ()
        # cost_epoch: a recost() invalidates every compiled plan even
        # when the physical decisions happen to coincide — morsel-rows
        # and pricing context are not part of ``decisions``.  The shard
        # layout joins the key so a 1-device and an 8-device plan can
        # never alias one compiled executable
        return (L.signature(node), shapes, decisions,
                self.cost_model.n_engines, self.cost_epoch,
                self._layout_key())

    def _compile(self, node: L.Node, phys: Optional[PhysNode],
                 splan: pl.StreamPlan, *, rows: Optional[int]):
        """Compile a pipeline for this plan shape at one granularity
        (``rows=None``: the whole base table, the batch path).  Literals
        (range bounds) are traced scalars: same-shape queries with
        different constants share the compilation."""
        placements = column_placements(phys) if phys else {}

        def placement_of(table: str, col: str) -> str:
            return placements.get((table, col),
                                  placements.get((table, "*"),
                                                 "partitioned"))

        specs = tuple((splan.base_scan.table, c,
                       placement_of(splan.base_scan.table, c))
                      for c in splan.stream_cols)
        if rows is None:
            rows = self.catalog.stats[splan.base_scan.table].num_rows
        # per-join impl decisions (nodes hash structurally; identical
        # subplans share identical decisions)
        decisions = {p.logical: p for p in _walk_phys(phys)} if phys else {}
        impls = tuple(decisions[j].impl if j in decisions else "xla"
                      for j in splan.join_nodes)

        def bump():
            self.metrics.inc("exec.trace_count")

        cp = pl.compile_pipeline(splan, rows, self._agg_dtype(splan),
                                 impls=impls, trace_marker=bump,
                                 shard=self.shard_layout)
        return cp, specs

    def _agg_dtype(self, splan: pl.StreamPlan):
        name = splan.node.column
        base = self.catalog.tables[splan.base_scan.table]
        if name in base.columns:
            return base.columns[name].dtype
        for b in splan.breakers:
            cols = self.catalog.tables[b.table].columns
            if name in cols:
                return cols[name].dtype
        return jnp.int32

    def _breaker_arrays(self, breakers) -> list:
        """Flattened, cached join-build state (the pipeline breakers).
        Build columns replicate through ``placed()`` — the same per-column
        decision surface (and capacity gate) as every other placement.

        With a semantic cache, builds live there instead of the private
        dict: byte-budgeted (an evicted build is rebuilt, not leaked),
        version-keyed (a mutated build table misses instead of serving a
        stale sort), and shared with every consumer of the cache — a
        cached build lets a streamed plan skip its entire build phase."""
        flat: list = []
        for b in breakers:
            version = self.catalog.tables[b.table].version
            if self.cache is not None:
                ckey = ("build", b.table, version, b.on, b.value_cols,
                        b.unique)
                entry = self.cache.get(ckey)
                if entry is not None:
                    self.metrics.inc("exec.build_cache_hits")
                    flat.extend(entry.value)
                    continue
                arrays = self._make_build(b)
                self.cache.put(
                    ckey, arrays, kind="build",
                    n_bytes=sum(a.nbytes for a in arrays),
                    recompute_s=self.cost_model.build_price(
                        self.catalog.stats[b.table].num_rows,
                        len(b.value_cols)),
                    tables=(b.table,), tenant=self.tenant)
                flat.extend(arrays)
                continue
            key = (b, version)
            if key not in self._builds:
                self._builds[key] = self._make_build(b)
            flat.extend(self._builds[key])
        return flat

    def _make_build(self, b: pl.BreakerSpec) -> tuple:
        cols = {b.on: Column(self.placed(b.table, b.on, "replicated"),
                             b.on)}
        for c in b.value_cols:
            cols[c] = Column(self.placed(b.table, c, "replicated"), c)
        return engine.join_build(Table(b.table, cols), b.on,
                                 b.value_cols, unique=b.unique).flat()

    # -- streaming path (morsel-driven pipeline) ----------------------------- #

    def _maybe_spill(self, node: L.Node) -> Optional[SpillPlan]:
        """Tier assignment for ``node``'s streamed working set when it
        exceeds the device budget — the replacement for the hard
        capacity refusal.  Returns None when every stream column fits
        the device tier (or no budget / no streamable spine exists);
        otherwise plans the hierarchy greedily in the cache-score
        currency, DEMOTES the over-budget catalog columns to their
        assigned tiers (host numpy / disk memmap — values unchanged, so
        table versions do not move), and raises only when the working
        set overflows even the disk budget."""
        budget = self.tier_budgets.device
        if budget is None:
            return None
        splan = pl.analyze(node, self.catalog.stats)
        if splan is not None:
            table, cols = splan.base_scan.table, splan.stream_cols
            breakers = splan.breakers
        else:
            pplan = pl.analyze_project(node, self.catalog.stats)
            if pplan is not None:
                table, cols = pplan.base_scan.table, pplan.stream_cols
                breakers = pplan.breakers
            else:
                # scan-rooted training sets spill too: epochs stream
                # morsels straight off the (demoted) catalog columns, so
                # an over-budget dataset trains out of core instead of
                # dying in placed().  Filtered trains are excluded — they
                # materialize a compacted (smaller) transient set first
                tplan = pl.analyze_train(node, self.catalog.stats)
                if tplan is None or tplan.filtered:
                    return None
                table, cols = tplan.base_scan.table, tplan.stream_cols
                breakers = ()
        tab = self.catalog.tables[table]
        sizes = [((table, c), int(tab.columns[c].nbytes)) for c in cols]
        if not any(n > budget for _, n in sizes):
            return None
        # build-side bytes are device residents by construction (the
        # replicated URAM analogue): carve them out of the device budget
        # before stream columns compete for it
        reserved = 0
        for b in breakers:
            bt = self.catalog.tables[b.table]
            reserved += sum(int(bt.columns[c].nbytes)
                            for c in (b.on, *b.value_cols))
        plan = plan_spill(sizes, self.tier_budgets, self.cost_model,
                          reserved_device=reserved)
        if plan.overflow_bytes:
            total = sum(n for _, n in sizes)
            raise PlacementCapacityError(
                f"working set of {total} bytes over table '{table}' "
                f"overflows the whole tier hierarchy: {plan.describe()} "
                f"(budgets device={self.tier_budgets.device} "
                f"host={self.tier_budgets.host} "
                f"disk={self.tier_budgets.disk}, "
                f"{plan.overflow_bytes} bytes have no tier).  Raise a "
                "tier budget or reduce the query's streamed column set")
        if self._spill_dir is None:
            self._spill_dir = default_spill_dir()
        for (t, c), tier in plan.tiers.items():
            if tier != "device":
                self.catalog.tables[t].demote_column(c, tier,
                                                     self._spill_dir)
        self.metrics.set("exec.spilled_columns", sum(
            1 for t in plan.tiers.values() if t != "device"))
        self.tel.instant("exec.spill", table=table,
                         plan=plan.describe())
        return plan

    def _spill_src_tier(self, spill: Optional[SpillPlan]) -> str:
        """The slowest tier a spill plan streams from — what prices the
        per-morsel promotion term when the model chooses granularity."""
        if spill is None:
            return "host"
        worst = "device"
        for t in spill.tiers.values():
            if _TIER_RANK[t] > _TIER_RANK[worst]:
                worst = t
        return worst if worst != "device" else "host"

    def _run_stream(self, node: L.Node, phys: PhysNode,
                    splan: pl.StreamPlan, morsel_rows: Optional[int],
                    spill: Optional[SpillPlan] = None):
        """Drive the pipeline morsel by morsel.  The cost model priced the
        morsel granularity onto the physical root; the driver double-
        buffers morsel ``i+1``'s placement transfer against morsel ``i``'s
        compute — including host/disk promotion under a spill plan, whose
        read + H2D both run inside the prefetch thread.  With a placement
        capacity set, morsels are never cached (out-of-core streaming);
        without one, placed morsels are reused across executions exactly
        like whole-column placements."""
        table = splan.base_scan.table
        cap = self.placement_capacity_bytes
        n_cols = len(splan.stream_cols)
        # the phys annotation prices the out-of-core posture (H2D per
        # morsel); with no capacity limit morsels are cached across
        # executions, so the spec re-chooses without the transfer term
        target = morsel_rows or (
            phys.morsel_rows if phys and cap is not None else None)
        spec = self.morsel_spec(table, target, n_cols=n_cols,
                                src_tier=self._spill_src_tier(spill))
        if morsel_rows is None and cap is not None:
            # a model-chosen granularity is CLAMPED under the device
            # budget (the model sized it against the whole table, not the
            # capacity); an explicit override keeps the strict refusal in
            # stream_pipeline instead
            spec = self._clamp_spec(spec, n_cols, cap)
        cp, builds, hit = self.stream_pipeline(node, phys, splan, spec)
        cache_ok = cap is None
        lits = jnp.asarray(L.literals(node), jnp.int32)
        promote = {"host": [0, 0.0], "disk": [0, 0.0]}
        get = lambda i: self._stream_morsel(table, cp.stream_cols,   # noqa: E731
                                            spec, i, cache_ok,
                                            promote=promote)
        ledger = self.tel.ledger.enabled
        with self.tel.span("exec.run_stream", n_morsels=spec.n_morsels,
                           morsel_rows=spec.rows, compiled_hit=hit) as sp:
            if ledger:
                jax.block_until_ready(builds)
                t0 = time.perf_counter()
            carry = pl.drive(cp, spec.n_morsels, get, builds, lits,
                             prefetch=self.overlap_transfers,
                             telemetry=self.tel,
                             metrics=self.metrics if self.tel.enabled
                             else None)
            if ledger:
                jax.block_until_ready(carry)
                dt = time.perf_counter() - t0
                moved = self.catalog.stats[table].num_rows * 4 \
                    * len(cp.stream_cols) + sum(b.nbytes for b in builds)
                sp.set(measured_s=dt, measured_bytes=moved)
                self.tel.ledger.record_plan(phys, dt, moved, mode="stream",
                                            shards=self.n_shards)
                self._record_promotions(promote, mode="stream")
            return cp.finalize(carry), hit

    def _clamp_spec(self, spec: MorselSpec, n_cols: int,
                    cap: int) -> MorselSpec:
        """Shrink a model-chosen morsel spec until one morsel's placed
        bytes fit the device budget, floor-aligned to the engine count
        (``for_plan`` rounds UP, which can push a near-budget target
        over)."""
        if spec.rows * BYTES_PER_VALUE * n_cols <= cap:
            return spec
        n_eng = self.plans["partitioned"].n_engines
        rows = max((int(cap) // (BYTES_PER_VALUE * max(n_cols, 1)))
                   // n_eng * n_eng, n_eng)
        return MorselSpec(spec.total_rows, rows)

    def _record_promotions(self, promote: Dict[str, list],
                           *, mode: str) -> None:
        """Ledger rows for spill-promotion traffic: op="promote" per
        source tier, measured inside the morsel fetch (prefetch thread),
        predicted by the model's tier channel — the drift pair the
        recalibration loop folds back into h2d/disk bandwidth."""
        for tier, (n_bytes, seconds) in promote.items():
            if not n_bytes:
                continue
            self.tel.ledger.record(
                op="promote", impl="promote", placement=tier,
                predicted_bytes=float(n_bytes),
                predicted_s=self.cost_model.promotion_cost(
                    float(n_bytes), tier),
                measured_bytes=float(n_bytes), measured_s=seconds,
                mode=mode, tier=tier)

    def _run_stream_project(self, node: L.Node, phys: Optional[PhysNode],
                            pplan: pl.ProjectStreamPlan,
                            morsel_rows: Optional[int],
                            spill: Optional[SpillPlan] = None) -> Table:
        """Project-rooted spilled execution: drive the compiled project
        step morsel by morsel, compacting each morsel's survivors into a
        host-side chunk (morsel order = table order, so the concatenated
        result is bit-identical to the eager materialization — the same
        reassembly the serving streams' project members do)."""
        table = pplan.base_scan.table
        cap = self.placement_capacity_bytes
        n_cols = len(pplan.stream_cols)
        spec = self.morsel_spec(table, morsel_rows, n_cols=n_cols,
                                src_tier=self._spill_src_tier(spill))
        if morsel_rows is None and cap is not None:
            spec = self._clamp_spec(spec, n_cols, cap)
        cpj, builds = self.project_pipeline(node, phys, pplan, spec)
        lits = jnp.asarray(L.literals(node), jnp.int32)
        promote = {"host": [0, 0.0], "disk": [0, 0.0]}
        chunks = []
        t0 = time.perf_counter()
        for i in range(spec.n_morsels):
            arrays, n_valid = self._stream_morsel(
                table, cpj.stream_cols, spec, i, False, promote=promote)
            mask, outs = cpj.step(lits, n_valid, *builds, *arrays)
            live = np.asarray(mask)
            chunks.append({c: np.asarray(a)[live]
                           for c, a in zip(cpj.out_cols, outs)})
        value = Table("proj", {
            c: Column(jnp.asarray(np.concatenate([ch[c] for ch in chunks])),
                      c) for c in cpj.out_cols})
        if self.tel.ledger.enabled:
            dt = time.perf_counter() - t0
            moved = self.catalog.stats[table].num_rows * BYTES_PER_VALUE \
                * n_cols + sum(b.nbytes for b in builds)
            self.tel.ledger.record_plan(phys, dt, moved, mode="stream",
                                        shards=self.n_shards)
            self._record_promotions(promote, mode="stream")
        return value

    def _run_train(self, node: L.TrainGLM, phys: Optional[PhysNode],
                   tplan: pl.TrainStreamPlan,
                   morsel_rows: Optional[int],
                   spill: Optional[SpillPlan] = None):
        """TrainGLM-rooted streamed execution (paper §VI, workload 3):
        every epoch streams the training set morsel by morsel through the
        K-model SGD step with the weights as the only cross-morsel carry,
        so the result is bit-identical to the whole-column eager path
        while the dataset is never device-resident at once.  A filter
        under the train root materializes the selected rows ONCE (the
        pipeline breaker: streamed compaction would make minibatch
        boundaries data-dependent) and epochs stream off that transient
        table; a bare scan streams straight off the catalog table,
        tier-aware — which is what lets an over-budget training set ride
        the spill plan's host/disk demotions instead of raising."""
        if tplan.filtered:
            child_phys = phys.children[0] if phys and phys.children \
                else None
            source = self._run_eager(node.child, child_phys)
        else:
            source = self.catalog.tables[tplan.base_scan.table]
        cap = self.placement_capacity_bytes
        n_cols = len(tplan.stream_cols)
        target = morsel_rows or (phys.morsel_rows if phys else None)
        if target is not None and morsel_rows is None and cap is not None:
            target = self._clamp_spec(
                MorselSpec(source.num_rows, target), n_cols, cap).rows
        cplan = self.plans.get(phys.placement if phys else "partitioned",
                               self.plans["partitioned"])
        ledger = self.tel.ledger.enabled
        promote = {"host": [0, 0.0], "disk": [0, 0.0]}

        def on_morsel(n_bytes, seconds, tier):
            if tier != "device":
                acc = promote.setdefault(tier, [0, 0.0])
                acc[0] += n_bytes
                acc[1] += seconds
                self.metrics.inc(f"exec.promote_bytes.{tier}", n_bytes)

        with self.tel.span("exec.run_train", epochs=node.epochs,
                           k=len(node.grid),
                           morsel_rows=target or source.num_rows) as sp:
            t0 = time.perf_counter()
            value = engine.train_glm_stream(
                source, list(node.features), node.label, list(node.grid),
                cplan, kind=node.kind, epochs=node.epochs,
                morsel_rows=target,
                on_morsel=on_morsel if ledger else None,
                telemetry=self.tel, metrics=self.metrics)
            if ledger:
                jax.block_until_ready(value)
                dt = time.perf_counter() - t0
                # mirror the cost formula with actual cardinality (the
                # same convention as _eager_measured_bytes) so ledger
                # drift isolates estimation error from bandwidth-model
                # error
                moved = source.num_rows * BYTES_PER_VALUE * n_cols \
                    * node.epochs * len(node.grid)
                sp.set(measured_s=dt, measured_bytes=moved)
                self.tel.ledger.record_plan(phys, dt, moved, mode="stream",
                                            shards=self.n_shards)
                self._record_promotions(promote, mode="stream")
            return value

    def morsel_spec(self, table: str, target: Optional[int] = None,
                    n_cols: int = 2, src_tier: str = "host") -> MorselSpec:
        """Morsel granularity for a stream over ``table``: the cost
        model's per-plan choice (or an explicit override), aligned by the
        partitioned channel plan.  ``n_cols`` sizes the per-morsel
        transfer when the model has to choose; ``src_tier`` prices it at
        the spill plan's resident tier (disk promotion pushes toward
        larger morsels than plain H2D)."""
        total = self.catalog.stats[table].num_rows
        if target is None:
            target = self.cost_model.choose_morsel_rows(
                total, max(n_cols, 1),
                include_transfer=self.placement_capacity_bytes is not None,
                src_tier=src_tier)
        return MorselSpec.for_plan(total, target, self.plans["partitioned"])

    def stream_pipeline(self, node: L.Node, phys: Optional[PhysNode],
                        splan: pl.StreamPlan, spec: MorselSpec):
        """Compiled per-morsel step + breaker arrays for one plan at one
        granularity — shared with external drivers (the serving front-
        end's cooperative morsel streams).  Enforces the placement
        capacity at morsel granularity."""
        key = ("stream", spec.rows) + self._cache_key(node, phys)
        if key in self._compiled:
            self.metrics.inc("exec.plan_cache_hits")
            hit = True
        else:
            self.metrics.inc("exec.plan_cache_misses")
            self._compiled[key] = self._compile(node, phys, splan,
                                                rows=spec.rows)
            hit = False
        cp, _ = self._compiled[key]
        builds = self._breaker_arrays(splan.breakers)
        # the strict one-morsel gate holds only under an EXPLICIT
        # capacity (the caller asked for the hard budget); an env-posture
        # budget clamps model-chosen specs instead (_clamp_spec) and lets
        # explicit overrides through
        cap = self.placement_capacity_bytes if self._cap_explicit else None
        if cap is not None:
            m_bytes = spec.rows * 4 * len(cp.stream_cols)
            if m_bytes > cap:
                n_eng = self.plans["partitioned"].n_engines
                fit = max((int(cap) // (4 * len(cp.stream_cols)))
                          // n_eng * n_eng, n_eng)
                raise PlacementCapacityError(
                    f"one morsel ({spec.rows} rows x "
                    f"{len(cp.stream_cols)} cols = {m_bytes} bytes) "
                    f"exceeds the {int(cap)}-byte placement capacity: "
                    f"lower morsel_rows to <= {fit}")
        return cp, builds, hit

    def project_pipeline(self, node: L.Node, phys: Optional[PhysNode],
                         pplan: pl.ProjectStreamPlan, spec: MorselSpec):
        """Compiled Project-rooted per-morsel step + breaker arrays —
        the serving streams' path for materializing queries: each morsel
        yields a compacted output chunk instead of folding a carry."""
        key = ("proj", spec.rows) + self._cache_key(node, phys)
        if key in self._compiled:
            self.metrics.inc("exec.plan_cache_hits")
        else:
            self.metrics.inc("exec.plan_cache_misses")
            decisions = {p.logical: p
                         for p in _walk_phys(phys)} if phys else {}
            impls = tuple(decisions[j].impl if j in decisions else "xla"
                          for j in pplan.join_nodes)

            def bump():
                self.metrics.inc("exec.trace_count")

            self._compiled[key] = pl.compile_project_pipeline(
                pplan, spec.rows, impls=impls, trace_marker=bump,
                shard=self.shard_layout)
        cpj = self._compiled[key]
        return cpj, self._breaker_arrays(pplan.breakers)

    def _stream_morsel(self, table: str, cols: Tuple[str, ...],
                       spec: MorselSpec, i: int, cache_ok: bool,
                       promote: Optional[Dict[str, list]] = None):
        """One morsel's columns, placed partitioned (each morsel shards one
        slice per pseudo-channel).  ``device_put`` is dispatched here, so
        calling this for morsel ``i+1`` before stepping morsel ``i``
        overlaps the transfer with compute — and a host/disk-resident
        column's numpy/memmap slice (the actual disk read) happens here
        too, so spill promotion rides the same overlap.  ``promote``
        accumulates ``tier -> [bytes, seconds]`` for promoted (non-device)
        columns, measured around the fetch.  Cached PER COLUMN, so
        overlapping column sets (the serving streams' shifting unions)
        share one placement per column slice."""
        start, stop = spec.bounds(i)
        arrays = []
        # ONE cached granularity per table (first comer wins): other
        # sizes bypass the cache instead of pinning a full extra device
        # copy per size — or thrash-evicting each other when two drivers
        # alternate granularities against the same table
        canonical = self._morsel_cache_rows.setdefault(table, spec.rows) \
            if cache_ok else None
        cache_ok = cache_ok and canonical == spec.rows
        missing = [c for c in cols
                   if not (cache_ok
                           and (table, c, spec.rows, i) in self._morsels)]
        tab = self.catalog.tables[table]
        tiers = {c: tab.columns[c].tier for c in missing}
        promoted = promote is not None \
            and any(t != "device" for t in tiers.values())
        # timing needs a fence, which would serialize the prefetch
        # overlap — only pay it when the ledger wants the rows
        timing = promoted and self.tel.ledger.enabled
        t0 = time.perf_counter() if timing else 0.0
        data = tab.morsel(spec, i, missing)[0] if missing else {}
        # one pytree device_put for all missing columns: the dispatch
        # overhead (cost model: stage_overhead_s) is paid once per morsel
        # instead of once per column.  On a single-device sharding an
        # uncached morsel skips the explicit put entirely — the jitted
        # step commits numpy operands on call through the C++ conversion
        # path, several times cheaper than a python device_put round
        # trip; cached morsels keep the put so reuse stays transfer-free
        direct = not cache_ok and self.mesh.size == 1
        if direct or not data:
            staged = data
        else:
            sh = self.plans["partitioned"].sharding()
            if self.shard_layout is not None \
                    and spec.rows % self.shard_layout.n_shards == 0:
                # morsels feed shard_map pipelines: place each slice
                # along the shard axis so the per-device step reads
                # local bytes
                sh = self.plans["sharded"].sharding()
            staged = dict(zip(data, jax.device_put(list(data.values()),
                                                   sh)))
        for c in cols:
            key = (table, c, spec.rows, i)
            if c in data:
                arr = staged[c]
                if cache_ok:
                    self._morsels[key] = arr
            else:
                arr = self._morsels[key]
            arrays.append(arr)
        if promoted:
            if timing:
                # settle the H2D dispatches so the stamp bounds the full
                # promotion (read + stage)
                jax.block_until_ready(arrays)
            dt = time.perf_counter() - t0 if timing else 0.0
            moved = {}
            for c, tier in tiers.items():
                if tier != "device" and c in data:
                    n = int(getattr(data[c], "nbytes", 0))
                    moved[tier] = moved.get(tier, 0) + n
                    self.metrics.inc(f"exec.promote_bytes.{tier}", n)
            total = sum(moved.values()) or 1
            for tier, n in moved.items():
                acc = promote.setdefault(tier, [0, 0.0])
                acc[0] += n
                acc[1] += dt * n / total
        # np scalar, not jnp: same int32[] signature under jit without a
        # ~30us per-morsel jax dispatch to build the scalar
        return tuple(arrays), np.int32(stop - start)

    # -- eager path (engine.* operators, BAT-style intermediates) ----------- #

    def _run_eager(self, node: L.Node, phys: Optional[PhysNode]):
        placements = column_placements(phys) if phys else {}
        # subplan caching (optimized runs only): materialized BAT-style
        # intermediates — selections, join products — are offered to the
        # semantic cache under ORDER-SENSITIVE fingerprints (row order is
        # part of a materialized table's identity), priced by the
        # physical plan's per-operator recompute cost
        decisions = {p.logical: p for p in _walk_phys(phys)} if phys \
            else {}
        versions = self.catalog.versions() if self.cache is not None \
            else None
        # bandwidth-ledger attribution: the eager lowering is the ONE
        # path where every operator can be fenced individually.  Each
        # evaluated node gets a frame; a node's exclusive time is its
        # inclusive (fenced) time minus its children's inclusive times,
        # and measured bytes mirror the cost model's formulas with
        # ACTUAL cardinalities — so drift isolates estimation error
        # (bytes) from bandwidth-model error (time)
        ledger_on = self.tel.ledger.enabled and phys is not None
        frames: list = []        # per live node: [child_incl_s, child_outs]

        def traced_eval(n):
            if not ledger_on:
                return eval_node(n)
            frames.append([0.0, []])
            t0 = time.perf_counter()
            out = _fence_value(eval_node(n))
            incl = time.perf_counter() - t0
            child_s, child_outs = frames.pop()
            d = decisions.get(n)
            if d is not None:
                self.tel.complete(f"op.{d.op}", t0, incl, impl=d.impl,
                                  placement=d.placement)
                self.tel.ledger.record(
                    op=d.op, impl=d.impl, placement=d.placement,
                    predicted_bytes=d.n_bytes, predicted_s=d.cost_s,
                    measured_bytes=_eager_measured_bytes(d, out,
                                                         child_outs),
                    measured_s=max(incl - child_s, 0.0), mode="eager")
            if frames:
                frames[-1][0] += incl
                frames[-1][1].append((n, out))
            return out

        def scan_placement(n: L.Scan) -> str:
            cols = n.columns or ("*",)
            return placements.get((n.table, cols[0]),
                                  placements.get((n.table, "*"),
                                                 "partitioned"))

        def impl_of(n: L.Node) -> str:
            if phys is None:
                return "xla"
            for p in _walk_phys(phys):
                if p.logical is n:
                    return p.impl
            return "xla"

        def eval_cached(n) -> Table:
            if self.cache is None or phys is None or \
                    not isinstance(n, (L.Filter, L.FilterProject, L.Join)):
                return traced_eval(n)
            key = ("subplan",
                   L.fingerprint(n, versions, order_sensitive=True))
            entry = self.cache.get(key)
            if entry is not None:
                self.metrics.inc("exec.subplan_cache_hits")
                # served, not executed: no ledger row, but the parent's
                # measured-bytes mirror still needs this child's actual
                # cardinality
                if ledger_on and frames:
                    frames[-1][1].append((n, entry.value))
                return entry.value
            t = traced_eval(n)
            d = decisions.get(n)
            self.cache.put(
                key, t, kind="subplan",
                n_bytes=sum(c.data.nbytes for c in t.columns.values()),
                recompute_s=d.total_cost_s if d is not None else 0.0,
                tables=L.tables_of(n), tenant=self.tenant)
            return t

        def eval_node(n) -> Table:
            if isinstance(n, L.Scan):
                return self._placed_table(n, scan_placement(n))
            if isinstance(n, L.Filter):
                t = eval_cached(n.child)
                return self._filter_table(t, n.column, n.lo, n.hi,
                                          tuple(t.columns),
                                          impl=impl_of(n),
                                          cache_ok=phys is not None)
            if isinstance(n, L.FilterProject):
                t = eval_cached(n.child)
                return self._filter_table(t, n.column, n.lo, n.hi,
                                          n.columns, impl=impl_of(n),
                                          cache_ok=phys is not None)
            if isinstance(n, L.Join):
                lt = eval_cached(n.left)
                rt = eval_cached(n.right)
                d = decisions.get(n)
                if d is not None and d.shard_strategy == "shuffle" \
                        and self.shard_layout is not None:
                    # the costed alternative to broadcasting the build:
                    # hash-partition both sides across the device mesh
                    # and join each bucket locally.  Pair order is
                    # canonicalized, so the result is bit-identical to
                    # the broadcast join
                    pairs = engine.join_shuffle(lt, rt, n.on,
                                                self.shard_layout,
                                                impl=impl_of(n))
                else:
                    if lt.plan is None:
                        # non-dividing intermediates cannot device_put
                        # under P(axis) on a multi-device mesh; the
                        # congested (replicated) placement always can
                        pname = "partitioned" if lt.num_rows \
                            % self.plans["partitioned"].n_engines == 0 \
                            else "congested"
                        lt = lt.place(self.plans[pname])
                    pairs = engine.join(
                        lt, rt, n.on, impl=impl_of(n),
                        unique=key_is_unique(n.right, n.on,
                                             self.catalog.stats))
                cols = {}
                for c in lt.columns:
                    cols[c] = Column(jnp.take(lt.column(c),
                                              pairs.column("l_idx"),
                                              axis=0), c)
                for c in rt.columns:
                    if c not in cols:
                        cols[c] = Column(jnp.take(rt.column(c),
                                                  pairs.column("r_idx"),
                                                  axis=0), c)
                return Table("join", cols)
            if isinstance(n, L.Project):
                t = eval_cached(n.child)
                return Table("proj", {c: t.columns[c] for c in n.columns})
            if isinstance(n, L.Aggregate):
                t = eval_cached(n.child)
                col = t.column(n.column)
                if n.op == "sum":
                    return int(jnp.sum(col)) if jnp.issubdtype(
                        col.dtype, jnp.integer) else float(jnp.sum(col))
                if n.op == "count":
                    return int(col.shape[0])
                if n.op == "mean":
                    if col.shape[0] == 0:     # match the fused path: 0, not NaN
                        return 0.0
                    return float(jnp.mean(col.astype(jnp.float32)))
                raise ValueError(n.op)
            if isinstance(n, L.TrainGLM):
                t = eval_cached(n.child)
                # the plan the cost model actually chose, not a
                # hard-coded partitioned mesh: explain() and execution
                # now agree.  partitioned/replicated/congested share one
                # mesh+axis (results identical — only transfer pricing
                # differs); "sharded" rides the shard mesh, where the
                # per-engine job partition preserves per-model bitwise
                # results
                d = decisions.get(n)
                cplan = self.plans.get(
                    d.placement if d is not None else "partitioned",
                    self.plans["partitioned"])
                return engine.train_glm(t, list(n.features), n.label,
                                        list(n.grid), cplan,
                                        kind=n.kind, epochs=n.epochs)
            if isinstance(n, L.ScoreGLM):
                t = eval_cached(n.child)
                xs, losses = self._resolve_model(n, phys)
                idx = int(n.select) if n.select >= 0 \
                    else int(jnp.argmin(losses))
                x = xs[idx]
                a = jnp.stack([t.column(f).astype(jnp.float32)
                               for f in n.features], axis=1)
                z = a @ x
                s = jax.nn.sigmoid(z) if n.kind == "logreg" else z
                return Table("score", {"score": Column(s, "score")})
            raise TypeError(n)

        return traced_eval(node)

    def _resolve_model(self, n: L.ScoreGLM,
                       phys: Optional[PhysNode]) -> tuple:
        """Weights for a ScoreGLM: the semantic cache under the defining
        train plan's fingerprint (versions embedded, so any training-
        table mutation strands the entry), else train fresh through the
        normal execute path — which admits the model for the next score.
        The naive oracle (``phys is None``) neither reads nor feeds the
        cache: it always trains inline."""
        fp = n.model_fp or (self.fingerprint_of(n.train)
                            if n.train is not None else "")
        if phys is not None and self.cache is not None and fp:
            entry = self.cache.get(("model", fp))
            if entry is not None:
                self.metrics.inc("exec.model_cache_hits")
                self.tel.instant("exec.model_hit", fingerprint=fp[:16])
                return entry.value
        if n.train is None:
            raise KeyError(
                f"score_glm: no cached model under fingerprint {fp!r} "
                "and no defining train plan to fall back to — train "
                "first (with a semantic cache installed) or score with "
                "the TrainGLM plan instead of a raw fingerprint")
        if phys is None:
            return self._run_eager(n.train, None)
        return self.execute(n.train).value

    def _filter_table(self, t: Table, column: str, lo: int, hi: int,
                      keep: Tuple[str, ...], *, impl: str = "xla",
                      block: int = engine.SELECT_BLOCK,
                      cache_ok: bool = True) -> Table:
        # selection bitmaps over BASE tables are cacheable: the compacted
        # index column is the selection's whole cost, and the key embeds
        # the table version so a mutated column can never replay.
        # ``cache_ok=False`` is the naive differential-oracle path, which
        # must neither read nor feed the semantic cache
        bkey = interval = None
        if cache_ok and self.cache is not None \
                and t.name in self.catalog.tables:
            version = self.catalog.tables[t.name].version
            interval = (t.name, column, version, int(lo), int(hi))
            bkey = ("bitmap", t.name, version, column, int(lo), int(hi))
            entry = self.cache.get(bkey)
            if entry is not None:
                self.metrics.inc("exec.subplan_cache_hits")
                idx = entry.value
                return engine.gather(t, idx,
                                     [c for c in keep if c in t.columns],
                                     name=f"{t.name}.sel")
            # exact miss: predicate SUBSUMPTION — refine the tightest
            # cached superset bitmap instead of rescanning the base
            # column.  The pricing gate rides inside the lookup as its
            # accept predicate, so a superset too wide to be worth
            # refining (bitmap stream dearer than the column scan) is
            # never counted as a hit or touched for recency — only a
            # bitmap refinement actually uses registers anywhere
            sup = self.cache.lookup_superset(
                t.name, column, version, int(lo), int(hi),
                accept=self._refine_gate(t.num_rows, impl))
            if sup is not None:
                cached_idx = sup[0].value
                idx = self._refine_bitmap(t.column(column), cached_idx,
                                          lo, hi,
                                          chunk_rows=self._refine_chunk())
                self.metrics.inc("exec.subsumption_hits")
                self.metrics.inc("exec.refine_bytes_streamed",
                                 3 * cached_idx.nbytes)
                self.metrics.inc("exec.refine_bytes_avoided",
                                 t.num_rows * 4)
                self.tel.instant("cache.refine",
                                 table=t.name, column=column,
                                 cached_rows=int(cached_idx.shape[0]))
                # the refined (narrower) bitmap joins the ladder
                self._admit_bitmap(bkey, idx, interval, t, impl)
                return engine.gather(
                    t, idx, [c for c in keep if c in t.columns],
                    name=f"{t.name}.sel")
        # the table's OWN plan decides the shard count (a sharded-placed
        # table splits over the shard mesh, not the base mesh)
        n_eng = t.plan.n_engines if t.plan is not None \
            else self.mesh.shape[self.axis]
        if t.plan is not None and t.num_rows % (n_eng * block) == 0:
            sel = engine.select_range(t, column, lo, hi, impl=impl,
                                      block=block)
            idx = sel.column("idx")
        else:
            # intermediates of arbitrary length: direct mask + shared
            # compaction (the selection kernel needs block-aligned shards)
            col = t.column(column)
            mask = (col >= lo) & (col <= hi)
            idx = engine.compact_positions(mask, int(jnp.sum(mask)))
        if bkey is not None:
            self._admit_bitmap(bkey, idx, interval, t, impl)
        return engine.gather(t, idx, [c for c in keep if c in t.columns],
                             name=f"{t.name}.sel")

    def _refine_gate(self, base_rows: int, impl: str):
        """The accept predicate for superset lookups: a candidate bitmap
        qualifies only when refining it is priced below re-streaming the
        base column."""
        return lambda e: self.cost_model.refine_wins(
            int(e.value.shape[0]), base_rows, impl=impl)

    def _admit_bitmap(self, bkey, idx, interval, t: Table,
                      impl: str) -> None:
        """One admission surface for scanned AND refined bitmaps: both
        are priced at the full base-column recompute, so eviction fights
        treat them identically (a refined entry is no cheaper to lose —
        its superset parent may be gone by rebuild time)."""
        self.cache.put(
            bkey, idx, kind="bitmap", n_bytes=idx.nbytes,
            recompute_s=self.cost_model.stream_cost(
                t.num_rows * 4, impl=impl, placement="partitioned"),
            tables=(t.name,), interval=interval, tenant=self.tenant)

    def _refine_chunk(self) -> Optional[int]:
        """Refinement granularity: None (eager, one gather) in the
        in-memory posture; with a placement capacity set, the bitmap is
        refined morsel-style in bounded slices (index + gathered values
        = 8 bytes per cached row must fit the capacity)."""
        cap = self.placement_capacity_bytes
        if cap is None:
            return None
        return max(int(cap // 8), 1)

    def _refine_bitmap(self, col: jax.Array, cached_idx: jax.Array,
                       lo: int, hi: int, *,
                       chunk_rows: Optional[int] = None) -> jax.Array:
        """AND a cached superset bitmap with the residual range mask:
        gather the predicate column at the cached positions and keep the
        survivors.  ``cached_idx`` is ascending, and compaction preserves
        order, so the refined bitmap is bit-identical to a from-scratch
        selection — including row order, which the gather downstream
        inherits.  ``chunk_rows`` is the streamed/morsel variant: one
        bounded slice of the cached index at a time (the out-of-core
        posture where even the bitmap must not be resident at once);
        per-chunk compaction concatenates to exactly the eager answer
        because chunks partition the ascending index."""
        n = int(cached_idx.shape[0])
        if chunk_rows is None or chunk_rows >= n:
            vals = jnp.take(col, cached_idx, axis=0)
            mask = (vals >= lo) & (vals <= hi)
            keep = engine.compact_positions(mask, int(jnp.sum(mask)))
            return jnp.take(cached_idx, keep, axis=0)
        parts = []
        for s in range(0, n, chunk_rows):
            sub = cached_idx[s:s + chunk_rows]
            vals = jnp.take(col, sub, axis=0)
            mask = (vals >= lo) & (vals <= hi)
            keep = engine.compact_positions(mask, int(jnp.sum(mask)))
            parts.append(jnp.take(sub, keep, axis=0))
        return jnp.concatenate(parts)

    def stats_dict(self) -> dict:
        total = self.cache_hits + self.cache_misses
        out = {
            "plan_cache_hits": self.cache_hits,
            "plan_cache_misses": self.cache_misses,
            "plan_cache_hit_rate": self.cache_hits / total if total else 0.0,
            "trace_count": self.trace_count,
            "placed_columns": len(self._placed),
            "cached_builds": len(self._builds),
            "cached_morsels": len(self._morsels),
            "cost_model_calibrated_from": self.cost_model.calibrated_from,
            "cost_epoch": self.cost_epoch,
            "n_shards": self.n_shards,
            "recost_count": int(self.metrics.value("exec.recost_count")),
            "result_cache_hits": self.result_hits,
            "subplan_cache_hits": self.subplan_hits,
            "build_cache_hits": self.build_hits,
            "model_cache_hits": self.model_hits,
            "subsumption_hits": self.subsumption_hits,
            "refine_bytes_streamed": self.refine_bytes_streamed,
            "refine_bytes_avoided": self.refine_bytes_avoided,
            "spilled_columns": int(
                self.metrics.value("exec.spilled_columns")),
            "promote_bytes_host": int(
                self.metrics.value("exec.promote_bytes.host")),
            "promote_bytes_disk": int(
                self.metrics.value("exec.promote_bytes.disk")),
            "tier_budgets": {"device": self.tier_budgets.device,
                             "host": self.tier_budgets.host,
                             "disk": self.tier_budgets.disk},
        }
        if self.cache is not None:
            out.update(self.cache.stats_dict())
        return out


def _walk_phys(p: PhysNode):
    yield p
    for c in p.children:
        yield from _walk_phys(c)


def _fence_value(value):
    """Settle async dispatch so a wall-clock stamp bounds *execution*."""
    if isinstance(value, Table):
        for c in value.columns.values():
            jax.block_until_ready(c.data)
    elif isinstance(value, (tuple, list)):
        for v in value:
            _fence_value(v)
    elif hasattr(value, "block_until_ready"):
        value.block_until_ready()
    return value


def _rows_of(value) -> float:
    """Actual output cardinality of an eager operator's materialization."""
    if isinstance(value, Table):
        return float(value.num_rows)
    return 1.0


def _eager_measured_bytes(d: PhysNode, out, child_outs) -> float:
    """Bytes an eager operator ACTUALLY moved — the cost model's n_bytes
    formulas (plan_physical) evaluated with measured cardinalities instead
    of estimates.  drift_bytes = predicted/measured therefore isolates the
    optimizer's cardinality-estimation error: 1.0 exactly when estimates
    were exact, independent of any bandwidth mis-model (which shows up in
    drift_time instead)."""
    B = BYTES_PER_VALUE
    rows_out = _rows_of(out)
    kids = [_rows_of(v) for _, v in child_outs]
    in_rows = kids[0] if kids else rows_out
    if d.op == "scan":
        n_cols = len(out.columns) if isinstance(out, Table) else 1
        return rows_out * B * n_cols
    if d.op in ("filter", "filter_project"):
        n_out_cols = len(d.logical.columns) if d.op == "filter_project" \
            else 1
        return in_rows * B + rows_out * B * n_out_cols
    if d.op == "join":
        probe = kids[0] if kids else rows_out
        build = kids[1] if len(kids) > 1 else probe
        return probe * B + build * B / d.n_passes
    if d.op == "join_multi":
        probe = max(kids[0] if kids else rows_out, 1.0)
        build = kids[1] if len(kids) > 1 else probe
        chain = max(rows_out / probe, 1.0)
        sort_bytes = build * B * max(math.log2(max(build, 2.0)), 1.0)
        return probe * B * chain \
            + (2 * rows_out * B + sort_bytes) / d.n_passes
    if d.op == "project":
        return rows_out * B * len(d.logical.columns)
    if d.op == "aggregate":
        return in_rows * B
    if d.op == "train_glm":
        n = d.logical
        dataset = in_rows * B * (len(n.features) + 1)
        return dataset * n.epochs * len(n.grid)
    if d.op == "score_glm":
        return in_rows * B * len(d.logical.features) + rows_out * B
    return float(d.n_bytes)     # unknown op: mirror the prediction


def _value_nbytes(value) -> int:
    """Residency size of a cached result: device bytes for tables and
    array tuples, a nominal few words for scalars."""
    if isinstance(value, Table):
        return sum(c.data.nbytes for c in value.columns.values())
    if isinstance(value, tuple):
        return sum(_value_nbytes(v) for v in value)
    return int(getattr(value, "nbytes", 16))


def sql_like_query(executor: Executor, q, **kw):
    """UDF surface: run a logical plan through optimize->cost->exec."""
    return executor.execute(q, **kw).value
