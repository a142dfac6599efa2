"""Query-serving front-end — the ROADMAP's many-clients path.

Two serving disciplines share one ``submit()`` surface:

* **admission batches** (default): ``drain()`` processes the pending set
  as one batch — dedup of structurally identical plans, micro-batching
  of compatible selections into ONE vmapped executable, everything else
  through the executor's plan cache.  No result is visible until the
  whole batch finishes.
* **incremental pipeline drain** (``streaming=True``): the server keeps
  cooperative morsel streams (one per base table).  ``pump()`` admits
  whatever is pending — new queries join the in-flight stream at the
  next morsel boundary, sharing its placement transfers — then advances
  every stream one morsel.  A member completes after one full circle
  over the table (commutative carries make the start offset irrelevant),
  so results surface continuously instead of at batch boundaries:
  latency is admission-to-completion, not admission-batch wall time.

Per-query latency, throughput, dedup/stream counters, and the executor's
plan-cache hit rate come back from ``stats()``.

**Adaptive serving** (``policy=AdaptivePolicy(...)``): the server closes
the measure→re-cost→re-plan loop.  Streaming pumps fence each morsel
advance and feed the bandwidth ledger (``record_plan(..., scale=1/
n_morsels)``); ``_maybe_recalibrate`` watches windowed drift
(``BandwidthLedger.window_drift``) and, after K consecutive breaching
windows, folds ``ledger.calibration_overlay(model)`` into the cost model
via ``Executor.recost()`` — bumping the cost epoch so every plan-cache
key rolls over.  In-flight streaming members stay PINNED to their
original compiled pipeline (groups are keyed by compiled-object
identity, and recost never touches live groups); only subsequently
admitted queries see the re-costed plans, so a mid-stream recalibration
can never mix morsel chunks from two physical plans.

**QoS admission** (``register_tenant(TenantSpec(...))``): submissions
carry a tenant; admission is ordered by (priority desc, deadline,
submit time) in both disciplines, tenants get fair byte-budget shares of
the shared ``SemanticCache`` (``cache_share`` weights →
``SemanticCache.set_tenant_shares``), and the streaming pump applies
backpressure — when the recent sojourn p95 breaches the strictest
registered SLO, below-top-priority admissions are deferred to a later
pump (bounded by a starvation guard) so the high-priority tenant's tail
recovers first.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.columnar.table import Column, Table
from repro.query import logical as L
from repro.query import pipeline as pl
from repro.query import telemetry as tm
from repro.query.exec import Executor
from repro.query.optimize import common_subplans


@dataclasses.dataclass
class QueryRecord:
    qid: int
    node: L.Node
    result: object = None
    latency_s: float = 0.0
    path: str = "exec"     # exec | dedup | microbatch | stream | cached
    # monotonic (time.perf_counter) admission/completion stamps; every
    # completion path sets both, and latency_s is ALWAYS the sojourn
    # t_complete - t_submit — queue wait included, never amortized away
    t_submit: float = 0.0
    t_complete: float = 0.0
    # QoS: owning tenant, its priority at submit, absolute deadline
    # (t_submit + deadline_s; inf = none), and how many pumps
    # backpressure has deferred this record (starvation guard input)
    tenant: str = "default"
    priority: int = 0
    deadline: float = float("inf")
    n_deferred: int = 0


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's QoS contract.  ``priority`` orders admission (higher
    first); ``slo_p95_s`` is the sojourn-p95 target backpressure defends
    (None = best-effort); ``cache_share`` is this tenant's relative
    weight of the shared semantic-cache byte budget (see
    ``SemanticCache.set_tenant_shares``)."""
    name: str
    priority: int = 0
    slo_p95_s: Optional[float] = None
    cache_share: float = 1.0


@dataclasses.dataclass(frozen=True)
class AdaptivePolicy:
    """When to fold ledger evidence back into the cost model.  A window
    is one ``window_drift`` call with at least ``min_window_rows`` new
    ledger rows; it BREACHES when any impl's ``|drift_time - 1|``
    exceeds ``drift_threshold``.  After ``k_windows`` consecutive
    breaches the server applies ``calibration_overlay`` via
    ``Executor.recost()`` (epoch bump → plan caches roll over) and
    restarts the evidence window, so rows measured against the old model
    never contaminate the next overlay."""
    drift_threshold: float = 0.5
    k_windows: int = 2
    min_window_rows: int = 8


def _microbatch_key(node: L.Node) -> Optional[tuple]:
    """Aggregate(op, col, Filter(Scan(t), fcol, ?, ?)) -> grouping key."""
    if isinstance(node, L.Aggregate) and isinstance(node.child, L.Filter) \
            and isinstance(node.child.child, L.Scan):
        scan = node.child.child
        return (scan.table, scan.columns, node.child.column, node.op,
                node.column)
    return None


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class _StreamMember:
    """One query riding a cooperative morsel stream.  ``carry`` is only
    authoritative while its group is unstacked (dirty); a clean group
    keeps every member's carry stacked on device between pumps."""

    def __init__(self, rec: QueryRecord, lits, remaining: int,
                 fp: Optional[str] = None,
                 dep_versions: Optional[Dict[str, int]] = None):
        self.rec = rec
        self.lits = lits
        self.carry = None
        self.remaining = remaining
        self.fp = fp                    # semantic fingerprint (dedup key)
        # table versions at attach: a mid-flight mutation makes the
        # partially-folded carry meaningless, so the server restarts any
        # member whose snapshot drifts
        self.dep_versions = dep_versions or {}
        self.dups: List[QueryRecord] = []


class _ProjectMember:
    """One Project-rooted query riding a morsel stream: each advance
    compacts the morsel's surviving rows into a host-side chunk keyed by
    ABSOLUTE morsel index, so a member that joined mid-circle still
    reassembles its output in table order — bit-identical to the eager
    materialization."""

    def __init__(self, rec: QueryRecord, cpj, builds, lits, remaining: int,
                 fp: Optional[str],
                 dep_versions: Optional[Dict[str, int]] = None,
                 phys=None):
        self.rec = rec
        self.cpj = cpj
        self.builds = builds
        self.lits = lits
        self.chunks: Dict[int, Dict[str, np.ndarray]] = {}
        self.remaining = remaining
        self.fp = fp
        self.dep_versions = dep_versions or {}
        self.dups: List[QueryRecord] = []
        self.phys = phys               # pinned physical plan (ledger rows)
        self.n_advances = 0            # ledger warmup gate (jit skew)

    def finalize(self) -> Table:
        order = sorted(self.chunks)
        cols = {}
        for c in self.cpj.out_cols:
            cols[c] = Column(jnp.asarray(np.concatenate(
                [self.chunks[i][c] for i in order])), c)
        return Table("proj", cols)


class _Group:
    """Members sharing one compiled pipeline: they differ only in their
    literal vectors and carries, so every pump runs the whole group as
    ONE vmapped step over stacked (lits, carry) — micro-batching join
    pipelines the admission-batch server can only execute one by one.
    Stacks are rebuilt only when membership changes, never per morsel."""

    def __init__(self, cp, builds, phys=None):
        self.cp = cp
        self.builds = builds
        # the physical plan this group was attached under — PINNED for
        # the group's lifetime: a mid-stream recost produces new compiled
        # pipelines (epoch is in the compile key), so later admissions
        # form NEW groups while this one finishes on its original plan,
        # and its ledger rows keep attributing against the plan that
        # actually priced the work
        self.phys = phys
        self.members: List[_StreamMember] = []
        self.lits = None                  # stacked, padded to size bucket
        self.carry = None
        self.size = 0
        self.n_advances = 0               # ledger warmup gate (jit skew)

    def writeback(self):
        """Unstack the group carry into the members (before membership
        changes invalidate lane order).  A lone member's live carry is
        held unstacked in ``self.carry`` and must be copied back too."""
        if self.carry is not None:
            if self.size == 1:
                self.members[0].carry = self.carry
            else:
                for i, m in enumerate(self.members):
                    m.carry = jax.tree_util.tree_map(
                        lambda x, i=i: x[i], self.carry)
        self.lits = self.carry = None
        self.size = 0
        # membership changed: the next advance may land in a new vmap
        # size bucket (fresh compile), so the ledger warmup gate resets
        self.n_advances = 0

    def restack(self):
        n = len(self.members)
        self.size = max(_next_pow2(n), 1)
        pad = [self.members[-1]] * (self.size - n)
        if self.size == 1:
            self.lits = self.members[0].lits
            self.carry = self.members[0].carry
            return
        self.lits = jnp.stack([m.lits for m in self.members + pad])
        self.carry = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[m.carry for m in self.members + pad])


class _MorselStream:
    """Circular shared scan over one base table: members join at the
    current morsel and complete after one full wrap (aggregate carries
    commute, so the start offset never changes the result).  All groups
    of one advance share a single placement transfer of the union of
    their stream columns."""

    def __init__(self, server: "QueryServer", table: str, spec):
        self.server = server
        self.table = table
        self.spec = spec
        self.pos = 0
        self.epoch = 0                 # cost epoch the spec was priced at
        self.groups: Dict[int, _Group] = {}
        self.proj_members: List[_ProjectMember] = []

    def members(self):
        for g in self.groups.values():
            yield from g.members
        yield from self.proj_members

    def attach(self, rec: QueryRecord, cp, builds, lits,
               fp: Optional[str] = None,
               dep_versions: Optional[Dict[str, int]] = None,
               phys=None) -> _StreamMember:
        g = self.groups.get(id(cp))
        if g is None:
            g = self.groups[id(cp)] = _Group(cp, builds, phys)
        else:
            # the group can outlive a build-side mutation (same compiled
            # pipeline, new version-keyed build arrays): always take the
            # caller's fresh builds — any member folded against the old
            # ones was already detached by the restart sweep
            g.builds = builds
        g.writeback()
        m = _StreamMember(rec, lits, self.spec.n_morsels, fp,
                          dep_versions)
        m.carry = cp.init_carry()
        g.members.append(m)
        return m

    def attach_project(self, rec: QueryRecord, cpj, builds, lits,
                       fp: Optional[str],
                       dep_versions: Optional[Dict[str, int]] = None,
                       phys=None) -> _ProjectMember:
        m = _ProjectMember(rec, cpj, builds, lits, self.spec.n_morsels,
                           fp, dep_versions, phys)
        self.proj_members.append(m)
        return m

    def advance(self) -> Dict[int, object]:
        """Process one morsel for every member — one dispatch per group."""
        if not any(g.members for g in self.groups.values()) \
                and not self.proj_members:
            return {}
        ex = self.server.executor
        # the serving stream's ledger feed: fence this advance and record
        # one measured slice against 1/n_morsels of each pinned plan's
        # prediction.  Timing only when the ledger is on: without it the
        # advance takes no sync and no clock
        ledger_on = ex.tel.ledger.enabled
        pipes = [g for g in self.groups.values() if g.members] \
            + list(self.proj_members)
        # warmup gate: an advance whose pipelines include a first-step
        # (still-compiling) member would record jit time as bandwidth —
        # poisoned evidence that makes the recalibration loop oscillate
        warm = all(p.n_advances > 0 for p in pipes)
        live_phys = [p.phys for p in pipes]
        t0 = time.perf_counter() if ledger_on else 0.0
        union = tuple(sorted(
            {c for g in self.groups.values() if g.members
             for c in g.cp.stream_cols}
            | {c for m in self.proj_members for c in m.cpj.stream_cols}))
        cache_ok = ex.placement_capacity_bytes is None
        arrays, n_valid = ex._stream_morsel(self.table, union, self.spec,
                                            self.pos, cache_ok)
        by_col = dict(zip(union, arrays))
        done: Dict[int, object] = {}
        for g in self.groups.values():
            if not g.members:
                continue
            if g.carry is None:
                g.restack()
            cols = tuple(by_col[c] for c in g.cp.stream_cols)
            if g.size == 1:
                g.carry = g.cp.step(g.lits, g.carry, n_valid, *g.builds,
                                    *cols)
            else:
                fn = self.server._vstep(g.cp, g.size)
                g.carry = fn(g.lits, g.carry, n_valid, *g.builds, *cols)
            for m in g.members:
                m.remaining -= 1
            if any(m.remaining <= 0 for m in g.members):
                self._complete(g, done)
        still = []
        for m in self.proj_members:
            cols = tuple(by_col[c] for c in m.cpj.stream_cols)
            mask, outs = m.cpj.step(m.lits, n_valid, *m.builds, *cols)
            live = np.asarray(mask)
            m.chunks[self.pos] = {
                c: np.asarray(arr)[live]
                for c, arr in zip(m.cpj.out_cols, outs)}
            m.remaining -= 1
            if m.remaining > 0:
                still.append(m)
            else:
                self._complete_project(m, done)
        self.proj_members = still
        for p in pipes:
            p.n_advances += 1
        if ledger_on and warm and live_phys:
            for g in self.groups.values():
                if g.carry is not None:
                    jax.block_until_ready(g.carry)
            dt = time.perf_counter() - t0
            moved = int(sum(a.nbytes for a in arrays))
            # one fenced measurement for the whole advance, split evenly
            # across the co-scheduled pipelines (they shared the morsel
            # transfer); each records against ITS pinned plan
            share = 1.0 / len(live_phys)
            for phys in live_phys:
                ex.tel.ledger.record_plan(
                    phys, dt * share, moved * share, mode="serve",
                    scale=1.0 / self.spec.n_morsels,
                    shards=ex.n_shards)
        self.pos = (self.pos + 1) % self.spec.n_morsels
        return done

    def _complete_project(self, m: _ProjectMember,
                          done: Dict[int, object]):
        self._finish_member(m, m.finalize(), done)

    def _complete(self, g: _Group, done: Dict[int, object]):
        g.writeback()
        still = []
        for m in g.members:
            if m.remaining > 0:
                still.append(m)
                continue
            self._finish_member(m, g.cp.finalize(m.carry), done)
        g.members = still

    def _finish_member(self, m, result, done: Dict[int, object]):
        """Shared completion bookkeeping for aggregate and project
        members: stamp latencies, fan the result out to dedup riders,
        and offer it to the result cache — the next submission of this
        query then finishes at admission.  The fingerprint guard skips
        admission if any dependency version moved mid-flight (the
        restart sweep normally catches that first; this is the
        completion-time check)."""
        m.rec.result = result
        self.server._complete_rec(m.rec, "stream")
        self.server.history.append(m.rec)
        self.server.n_streamed += 1
        done[m.rec.qid] = result
        for dup in m.dups:
            dup.result = result
            self.server._complete_rec(dup)
            self.server.history.append(dup)
            done[dup.qid] = result
        ex = self.server.executor
        if ex.cache is not None and \
                m.fp == ex.fingerprint_of(m.rec.node):
            opt, phys = ex.plan(m.rec.node)
            ex._admit_result(m.rec.node, opt, phys, result)


class QueryServer:
    """Accepts many concurrent queries; serves them in admission batches
    (default) or as an incremental morsel-pipeline drain
    (``streaming=True``)."""

    def __init__(self, executor: Executor, *, streaming: bool = False,
                 morsel_rows: Optional[int] = None,
                 semantic_cache=None,
                 policy: Optional[AdaptivePolicy] = None,
                 backpressure_window: int = 64,
                 persist_path: Optional[str] = None):
        self.executor = executor
        # an EXTERNAL SemanticCache shared across several executors (and
        # their servers) over one catalog: installed on this server's
        # executor, so every tenant's warm results/bitmaps/builds serve
        # everyone else's admissions.  The cache's own version tracking
        # (``SemanticCache.sync_versions``, driven by each executor's
        # version sync) is the drift guard — one tenant's
        # ``Catalog.update_column`` invalidates the shared entries for
        # all of them, whoever notices first.  ``install_cache`` owns
        # the REPRO_CACHE kill-switch, so the CI cache-off leg cannot be
        # re-enabled from here
        executor.install_cache(semantic_cache)
        self.streaming = streaming
        self.morsel_rows = morsel_rows
        self._lock = threading.Lock()
        self._pending: List[QueryRecord] = []
        self._next_qid = 0
        self.history: List[QueryRecord] = []
        self.n_submitted = 0
        self.n_deduped = 0
        self.n_microbatched = 0
        self.n_streamed = 0
        self.n_cached = 0               # served whole from the semantic cache
        self.n_subplan_shared = 0       # CSE-hinted shared subtrees
        self.n_batches = 0
        self._batched_fns: Dict[tuple, object] = {}
        self.batched_cache_hits = 0
        self._total_drain_s = 0.0
        self._streams: Dict[str, _MorselStream] = {}
        self._vsteps: Dict[tuple, object] = {}
        # -- adaptive re-costing + QoS state --------------------------------- #
        self.policy = policy
        self.tenants: Dict[str, TenantSpec] = {
            "default": TenantSpec("default")}
        self.backpressure_window = int(backpressure_window)
        self._recent: List[float] = []   # sojourns, backpressure window
        self._ledger_pos = 0             # window_drift cursor
        self._overlay_start = 0          # first row measured vs current model
        self._breach_streak = 0
        self.n_recalibrations = 0
        self.n_backpressured = 0
        # -- warm-start persistence (PR 9) ------------------------------------ #
        # a snapshot path makes the server RECYCLABLE: construction
        # replays any existing snapshot (host-tier cache entries +
        # calibration — stale/corrupt files are rejected by the loader),
        # and ``save_state()`` writes the current state back atomically.
        self.persist_path = persist_path
        self.warm_started: Optional[dict] = None
        if persist_path and os.path.exists(persist_path) \
                and self.executor.cache is not None:
            self.warm_started = self.warm_start(persist_path)

    # -- warm-start persistence --------------------------------------------- #

    def save_state(self, path: Optional[str] = None) -> Optional[dict]:
        """Snapshot the semantic cache + calibration to ``path`` (default
        the constructor's ``persist_path``).  Returns the save summary,
        or None when there is nothing to persist (no cache / no path)."""
        from repro.query import persist as _persist
        path = path or self.persist_path
        ex = self.executor
        if not path or ex.cache is None:
            return None
        return _persist.save_state(
            path, ex.cache, cost_model=ex.cost_model,
            table_versions=ex.catalog.versions())

    def warm_start(self, path: str) -> dict:
        """Replay a snapshot into this server's cache and cost model.
        Entries land in the cache's host tier (promoted on first touch);
        entries whose tables drifted since the snapshot are dropped."""
        from repro.query import persist as _persist
        ex = self.executor
        summary = _persist.warm_start(
            path, ex.cache, cost_model=ex.cost_model,
            table_versions=ex.catalog.versions())
        if summary.get("restored") and ex.cache is not None:
            # the snapshot's entries were admitted against the versions
            # this catalog holds NOW — seed the drift guard so the next
            # sync_versions doesn't treat them as unseen
            ex.cache.sync_versions(ex.catalog.versions())
        return summary

    def _complete_rec(self, rec: QueryRecord,
                      path: Optional[str] = None) -> None:
        """ONE completion stamp for every serving path: monotonic
        t_complete, honest sojourn latency (admission to completion,
        queue wait included), and the sojourn histogram observation."""
        now = time.perf_counter()
        rec.t_complete = now
        rec.latency_s = now - rec.t_submit
        if path is not None:
            rec.path = path
        self.executor.metrics.observe("serve.sojourn_s", rec.latency_s)
        self._recent.append(rec.latency_s)
        if len(self._recent) > self.backpressure_window:
            del self._recent[:-self.backpressure_window]

    def _vstep(self, cp, size: int):
        """Vmapped per-morsel step for a group of ``size`` compatible
        members (size-bucketed to powers of two, like the legacy micro-
        batcher, so the compile cache stays small)."""
        key = (id(cp), size)
        if key not in self._vsteps:
            axes = (0, 0, None) + (None,) * (cp.n_build_arrays
                                             + len(cp.stream_cols))
            self._vsteps[key] = jax.jit(jax.vmap(cp.raw_step,
                                                 in_axes=axes))
        return self._vsteps[key]

    # -- client surface ----------------------------------------------------- #

    def register_tenant(self, spec: TenantSpec) -> None:
        """Install (or replace) a tenant's QoS contract and push the
        updated ``cache_share`` weights into the shared semantic cache's
        per-tenant byte caps."""
        self.tenants[spec.name] = spec
        if self.executor.cache is not None:
            self.executor.cache.set_tenant_shares(
                {t.name: t.cache_share for t in self.tenants.values()})

    def submit(self, q, *, tenant: str = "default",
               deadline_s: Optional[float] = None) -> int:
        with self.executor.tel.span("serve.submit") as sp:
            node = q.node if isinstance(q, L.Q) else q
            spec = self.tenants.get(tenant) or TenantSpec(tenant)
            now = time.perf_counter()
            deadline = now + deadline_s if deadline_s is not None \
                else float("inf")
            with self._lock:
                qid = self._next_qid
                self._next_qid += 1
                self._pending.append(QueryRecord(
                    qid, node, t_submit=now, tenant=tenant,
                    priority=spec.priority, deadline=deadline))
                self.n_submitted += 1
                depth = len(self._pending)
            sp.set(qid=qid)
            self.executor.metrics.set("serve.queue_depth", depth)
            self.executor.metrics.observe("serve.queue_depth_at_submit",
                                          depth)
        return qid

    def query(self, q):
        """Convenience: submit one query and drain immediately."""
        qid = self.submit(q)
        return self.drain()[qid]

    # -- incremental pipeline drain (streaming mode) ------------------------- #

    def pump(self) -> Dict[int, object]:
        """One serving increment: admit everything pending — dedup against
        in-flight members, attach streamable plans to the table's morsel
        stream (joining mid-flight), execute the rest now — then advance
        every stream one morsel.  Returns newly completed results, so
        callers see completions continuously rather than per admission
        batch."""
        self._restart_stale_members()
        with self._lock:
            batch, self._pending = self._pending, []
        batch = self._admission_order(batch)
        batch = self._apply_backpressure(batch)
        with self.executor.tel.span("serve.pump", admitted=len(batch)):
            done = self._pump_batch(batch)
        self._maybe_recalibrate()
        return done

    @staticmethod
    def _admission_order(batch: List[QueryRecord]) -> List[QueryRecord]:
        """QoS ordering: priority first (descending), earliest deadline
        next, then submission order — a stable sort, so same-tenant
        FIFO is preserved."""
        return sorted(batch,
                      key=lambda r: (-r.priority, r.deadline, r.t_submit))

    def _recent_p95(self) -> Optional[float]:
        if not self._recent:
            return None
        lat = sorted(self._recent)
        return lat[int(0.95 * (len(lat) - 1))]

    def _slo_target(self) -> Optional[float]:
        """The strictest registered SLO — the tail backpressure defends."""
        slos = [t.slo_p95_s for t in self.tenants.values()
                if t.slo_p95_s is not None]
        return min(slos) if slos else None

    def _apply_backpressure(self, batch: List[QueryRecord]
                            ) -> List[QueryRecord]:
        """Streaming-pump load shedding: while the recent sojourn p95
        breaches the strictest registered SLO, defer every admission
        whose priority is strictly below the highest priority PRESENT in
        this batch (so the top class always admits — no livelock), up to
        a per-record starvation bound.  Deferred records go back to the
        front of the queue; their sojourn clock keeps running, so
        deferral is never latency-laundering."""
        slo = self._slo_target()
        if not batch or slo is None:
            return batch
        p95 = self._recent_p95()
        if p95 is None or p95 <= slo:
            return batch
        top = max(r.priority for r in batch)
        keep, defer = [], []
        for r in batch:
            if r.priority >= top or r.n_deferred >= 8:
                keep.append(r)
            else:
                r.n_deferred += 1
                defer.append(r)
        if defer:
            self.n_backpressured += len(defer)
            self.executor.metrics.inc("serve.backpressured", len(defer))
            with self._lock:
                self._pending = defer + self._pending
        return keep

    def _maybe_recalibrate(self) -> None:
        """The drift trigger: one windowed ledger read per pump/drain;
        ``k_windows`` consecutive breaches fold the measured overlay into
        the cost model through ``Executor.recost()`` (epoch bump), then
        restart the evidence window so old-model rows never feed the
        next overlay."""
        pol = self.policy
        ex = self.executor
        if pol is None or not ex.tel.ledger.enabled:
            return
        agg, nxt = ex.tel.ledger.window_drift(
            self._ledger_pos, min_rows=pol.min_window_rows)
        if agg is None:
            return
        self._ledger_pos = nxt
        worst = max((abs(a["drift_time"] - 1.0) for a in agg.values()
                     if a["predicted_s"] > 0), default=0.0)
        if worst <= pol.drift_threshold:
            self._breach_streak = 0
            return
        self._breach_streak += 1
        if self._breach_streak < pol.k_windows:
            return
        overlay = ex.tel.ledger.calibration_overlay(
            ex.cost_model, start=self._overlay_start)
        if overlay.get("backends") and \
                not self._overlay_is_noop(overlay):
            ex.recost(overlay)
            self.n_recalibrations += 1
            ex.metrics.inc("serve.recalibrations")
            ex.tel.instant("serve.recalibrate", worst_drift=worst,
                           epoch=ex.cost_epoch)
            # the evidence window restarts only on an actual recost:
            # rows measured against the old model never feed the next
            # overlay
            self._overlay_start = self._ledger_pos
        self._breach_streak = 0

    def _overlay_is_noop(self, overlay: dict) -> bool:
        """Whether applying ``overlay`` would leave the cost model's
        prices essentially unchanged (every mentioned backend's
        efficiency within 20% of the live value).  Re-costing on a no-op
        overlay would churn the epoch — recompiling every plan — without
        changing a single decision; persistent residual drift the model
        cannot express (e.g. overhead mispricing) must not re-trigger
        forever."""
        eff = self.executor.cost_model.stream_eff
        for impl, meas in overlay.get("backends", {}).items():
            cur = eff.get(impl)
            new = meas.get("stream_eff")
            if cur is None or not new:
                continue
            if abs(new - cur) / max(cur, 1e-12) > 0.2:
                return False
        return True

    def _pump_batch(self, batch: List[QueryRecord]) -> Dict[int, object]:
        t0 = time.perf_counter()
        if batch:
            self.executor.metrics.observe("serve.batch_size", len(batch))
        self._hint_shared(batch)
        done: Dict[int, object] = {}
        ran: Dict[L.Node, QueryRecord] = {}   # non-streamable dedup
        for rec in batch:
            src = self._find_inflight(rec.node)
            if src is not None:
                rec.path = "dedup"
                self.n_deduped += 1
                src.dups.append(rec)
                continue
            prior = ran.get(rec.node)
            if prior is not None:
                self.n_deduped += 1
                rec.result = prior.result
                self._complete_rec(rec, "dedup")
                self.history.append(rec)
                done[rec.qid] = rec.result
                continue
            if self._serve_cached(rec, done):
                continue
            with tm.request(rec.qid):
                if self._try_attach(rec):
                    continue
                res = self.executor.execute(rec.node)
            rec.result = res.value
            self._complete_rec(rec)
            self.history.append(rec)
            done[rec.qid] = rec.result
            ran[rec.node] = rec
        for stream in self._streams.values():
            done.update(stream.advance())
        self._total_drain_s += time.perf_counter() - t0
        return done

    def _serve_cached(self, rec: QueryRecord, done: Dict[int, object]
                      ) -> bool:
        """Whole-result semantic-cache hit: the query completes at
        admission, before it could occupy a stream or an executor call."""
        ex = self.executor
        if ex.cache is None:
            return False
        entry = ex.cache.get(("result", ex.fingerprint_of(rec.node)))
        if entry is None:
            return False
        ex.result_hits += 1
        rec.result = entry.value
        self._complete_rec(rec, "cached")
        self.n_cached += 1
        self.history.append(rec)
        done[rec.qid] = rec.result
        return True

    def _hint_shared(self, batch: List[QueryRecord]) -> None:
        """Optimizer CSE over the admitted batch: subtrees repeated
        across these queries are certain to be reused, so they are
        hinted to the semantic cache (admitted as if already hit) before
        the first member executes."""
        ex = self.executor
        if ex.cache is None or len(batch) < 2:
            return
        opts = [ex.plan(rec.node)[0] for rec in batch]
        # only node kinds the executor actually caches as subplans —
        # hinting anything else would be a dead key
        shared = [n for n in common_subplans(opts)
                  if isinstance(n, (L.Filter, L.FilterProject, L.Join))]
        if not shared:
            return
        versions = ex.catalog.versions()
        ex.cache.hint(
            ("subplan", L.fingerprint(n, versions, order_sensitive=True))
            for n in shared)
        self.n_subplan_shared += len(shared)

    def _find_inflight(self, node: L.Node) -> Optional[_StreamMember]:
        """In-flight dedup at SEMANTIC level: a submitted query joins an
        in-flight member when their canonical fingerprints match, not
        just when the trees are structurally identical — filter-order
        permutations and agg-rooted join swaps share one stream slot."""
        ex = self.executor
        fp = ex.fingerprint_of(node) if ex.cache is not None else None
        for stream in self._streams.values():
            for m in stream.members():
                if m.rec.node == node or (fp is not None and m.fp == fp):
                    return m
        return None

    def _try_attach(self, rec: QueryRecord) -> bool:
        ex = self.executor
        node, phys = ex.plan(rec.node)        # memoized per logical node
        fp = ex.fingerprint_of(rec.node) if ex.cache is not None else None
        versions = ex.catalog.versions()
        deps = {t: versions.get(t, 0) for t in L.tables_of(node)}
        splan = pl.analyze(node, ex.catalog.stats)
        if splan is not None:
            table = splan.base_scan.table
            stream = self._stream_for(table, phys,
                                      len(splan.stream_cols))
            cp, builds, _ = ex.stream_pipeline(node, phys, splan,
                                               stream.spec)
            lits = jnp.asarray(L.literals(node), jnp.int32)
            stream.attach(rec, cp, builds, lits, fp, deps, phys=phys)
            return True
        pplan = pl.analyze_project(node, ex.catalog.stats)
        if pplan is None:
            return False
        table = pplan.base_scan.table
        stream = self._stream_for(table, phys, len(pplan.stream_cols))
        cpj, builds = ex.project_pipeline(node, phys, pplan, stream.spec)
        lits = jnp.asarray(L.literals(node), jnp.int32)
        stream.attach_project(rec, cpj, builds, lits, fp, deps, phys=phys)
        return True

    def _restart_stale_members(self) -> None:
        """A table mutation mid-flight invalidates every member whose
        dependency snapshot drifted: their partially-folded carries mix
        pre- and post-mutation morsels, and their compiled builds are
        stale.  Such members are detached and REQUEUED ahead of the next
        admission batch, so they re-plan, re-attach against fresh builds
        and statistics, and restart their circle — and any structural
        dedup against them can only ever see current-version state."""
        ex = self.executor
        versions = ex.catalog.versions()

        def stale(m) -> bool:
            return any(versions.get(t, 0) != v
                       for t, v in m.dep_versions.items())

        requeue: List[QueryRecord] = []
        for stream in self._streams.values():
            for g in stream.groups.values():
                hit = [m for m in g.members if stale(m)]
                if not hit:
                    continue
                g.writeback()
                for m in hit:
                    g.members.remove(m)
                    requeue.append(m.rec)
                    requeue.extend(d for d in m.dups)
            hit_p = [m for m in stream.proj_members if stale(m)]
            for m in hit_p:
                stream.proj_members.remove(m)
                requeue.append(m.rec)
                requeue.extend(d for d in m.dups)
        if requeue:
            with self._lock:
                self._pending = requeue + self._pending

    def _stream_for(self, table: str, phys, n_cols: int) -> _MorselStream:
        ex = self.executor
        stream = self._streams.get(table)
        if stream is not None and stream.epoch != ex.cost_epoch and \
                not any(True for _ in stream.members()):
            # the stream's morsel spec was priced under a previous cost
            # epoch; with nothing in flight it can be re-specced to the
            # re-costed morsel size.  A stream with live members keeps
            # its spec — their remaining-circle counts are pinned to it
            stream = None
        if stream is None:
            spec = ex.morsel_spec(table, self.morsel_rows
                                  or (phys.morsel_rows if phys else None),
                                  n_cols=n_cols)
            stream = self._streams[table] = _MorselStream(self, table, spec)
            stream.epoch = ex.cost_epoch
        return stream

    def _inflight(self) -> bool:
        return any(s.proj_members or
                   any(g.members for g in s.groups.values())
                   for s in self._streams.values())

    def _drain_streaming(self) -> Dict[int, object]:
        out: Dict[int, object] = {}
        while True:
            out.update(self.pump())
            with self._lock:
                idle = not self._pending
            if idle and not self._inflight():
                return out

    # -- serving (admission batches) ----------------------------------------- #

    def drain(self) -> Dict[int, object]:
        """Process every pending query; returns qid -> result."""
        if self.streaming:
            return self._drain_streaming()
        with self._lock:
            batch, self._pending = self._pending, []
        if not batch:
            return {}
        # a lone query's id tags the whole drain; a larger batch's spans
        # take each record's id where it executes
        with tm.request(batch[0].qid if len(batch) == 1 else None), \
                self.executor.tel.span("serve.drain", batch=len(batch)):
            return self._drain_batch(batch)

    def _drain_batch(self, batch: List[QueryRecord]) -> Dict[int, object]:
        t0 = time.perf_counter()
        # QoS ordering only: drain() must complete the whole batch, so
        # backpressure (deferral) is a streaming-pump discipline
        batch = self._admission_order(batch)
        self.executor.metrics.observe("serve.batch_size", len(batch))
        self._hint_shared(batch)

        # 1. dedup identical plans (frozen nodes hash structurally)
        first_of: Dict[L.Node, QueryRecord] = {}
        dups: List[Tuple[QueryRecord, QueryRecord]] = []
        unique: List[QueryRecord] = []
        for rec in batch:
            if rec.node in first_of:
                rec.path = "dedup"
                dups.append((rec, first_of[rec.node]))
                self.n_deduped += 1
            else:
                first_of[rec.node] = rec
                unique.append(rec)

        # 2. micro-batch compatible selections over the same column
        groups: Dict[tuple, List[QueryRecord]] = {}
        singles: List[QueryRecord] = []
        for rec in unique:
            key = _microbatch_key(rec.node)
            if key is None:
                singles.append(rec)
            else:
                groups.setdefault(key, []).append(rec)
        for key, recs in groups.items():
            if len(recs) == 1:
                singles.extend(recs)
                continue
            self._run_microbatch(key, recs)

        # 3. the rest, one executor call each (plan cache still applies;
        # a semantic-cache hit skips execution entirely)
        for rec in singles:
            with tm.request(rec.qid):
                res = self.executor.execute(rec.node)
            rec.result = res.value
            if res.result_cache_hit:
                rec.path = "cached"
                self.n_cached += 1
            self._complete_rec(rec)

        for rec, src in dups:
            rec.result = src.result
            self._complete_rec(rec)

        self._total_drain_s += time.perf_counter() - t0
        self.history.extend(batch)
        self._maybe_recalibrate()
        return {rec.qid: rec.result for rec in batch}

    def _run_microbatch(self, key: tuple, recs: List[QueryRecord]):
        table, cols, fcol, op, acol = key
        los = [r.node.child.lo for r in recs]
        his = [r.node.child.hi for r in recs]
        size = _next_pow2(len(recs))
        los += [los[-1]] * (size - len(recs))     # pad to the size bucket
        his += [his[-1]] * (size - len(recs))
        fn_key = (key, size)
        if fn_key in self._batched_fns:
            self.batched_cache_hits += 1
        else:
            self._batched_fns[fn_key] = self._build_batched(op)
        fn = self._batched_fns[fn_key]
        fdata = self.executor.placed(table, fcol, "partitioned")
        adata = self.executor.placed(table, acol, "partitioned")
        out = jax.device_get(fn(jnp.asarray(los, jnp.int32),
                                jnp.asarray(his, jnp.int32), fdata, adata))
        self.n_batches += 1
        self.executor.metrics.observe("serve.microbatch_size", len(recs))
        for i, rec in enumerate(recs):
            rec.result = out[i].item()
            # sojourn, not the batch-amortized kernel time: a query's
            # latency is admission -> completion even when a vmapped
            # batch computed it alongside others
            self._complete_rec(rec, "microbatch")
            self.n_microbatched += 1

    @staticmethod
    def _build_batched(op: str):
        def one(lo, hi, fcol, acol):
            mask = (fcol >= lo) & (fcol <= hi)
            if op == "sum":
                return jnp.sum(jnp.where(mask, acol, 0))
            if op == "count":
                return jnp.sum(mask.astype(jnp.int32))
            if op == "mean":
                s = jnp.sum(jnp.where(mask, acol, 0).astype(jnp.float32))
                c = jnp.sum(mask.astype(jnp.float32))
                return s / jnp.maximum(c, 1.0)
            raise ValueError(op)

        return jax.jit(jax.vmap(one, in_axes=(0, 0, None, None)))

    # -- reporting ---------------------------------------------------------- #

    def stats(self) -> dict:
        lat = sorted(r.latency_s for r in self.history)
        n = len(self.history)
        out = {
            "n_queries": n,
            "n_deduped": self.n_deduped,
            "n_microbatched": self.n_microbatched,
            "n_streamed": self.n_streamed,
            "n_cached": self.n_cached,
            "n_subplan_shared": self.n_subplan_shared,
            "n_microbatches": self.n_batches,
            "batched_kernel_cache_hits": self.batched_cache_hits,
            "total_serve_s": self._total_drain_s,
            "queries_per_s": n / self._total_drain_s
            if self._total_drain_s else 0.0,
            "latency_mean_s": sum(lat) / n if n else 0.0,
            "latency_p50_s": lat[int(0.50 * (n - 1))] if n else 0.0,
            "latency_p95_s": lat[int(0.95 * (n - 1))] if n else 0.0,
            "latency_max_s": lat[-1] if lat else 0.0,
            "n_recalibrations": self.n_recalibrations,
            "n_backpressured": self.n_backpressured,
            # warm-model serving (paper §VI): scores answered from cached
            # GLM weights instead of a per-query retrain
            "n_model_hits": self.executor.model_hits,
        }
        by_tenant: Dict[str, dict] = {}
        for rec in self.history:
            by_tenant.setdefault(rec.tenant, []).append(rec.latency_s)
        out["tenants"] = {}
        for t, ls in by_tenant.items():
            ls.sort()
            k = len(ls)
            spec = self.tenants.get(t)
            out["tenants"][t] = {
                "n": k,
                "latency_mean_s": sum(ls) / k,
                "latency_p95_s": ls[int(0.95 * (k - 1))],
                "priority": spec.priority if spec else 0,
                "slo_p95_s": spec.slo_p95_s if spec else None,
            }
        out.update(self.executor.stats_dict())
        return out
