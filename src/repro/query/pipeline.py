"""Morsel-driven streaming pipelines — the paper's §V lesson end to end.

The eager executor materializes whole-column intermediates between
operators (BAT algebra).  This module compiles an aggregate-rooted
physical plan into a *pipeline*: the probe spine (scan -> filters ->
join probes -> aggregate) becomes ONE jitted per-morsel step function
with a small carry, and the plan's pipeline breakers — join builds, the
final aggregate — are the only points where state wider than a morsel
exists.  The driver streams partition-granular morsels
(``columnar.table.MorselSpec``, sized by the cost model, aligned to the
channel plan) and double-buffers the next morsel's placement transfer
(``jax.device_put``) against the current morsel's compute, so sustained
throughput comes from many channel-aligned streams rather than one
monolithic scan — and datasets larger than a single placement complete,
which the eager path cannot do at all.

Layout of a compiled step's arguments::

    step(lits, carry, n_valid, *build_flat, *morsel_cols) -> carry

``build_flat`` is the deterministic flattening of every breaker's
``engine.JoinBuild`` (sorted keys, order, then value/csum arrays);
``morsel_cols`` are the base scan's columns for one morsel, padded to
``rows`` with rows ``>= n_valid`` masked out.  Join probes binary-search
the sorted-bucket build (exact for duplicate keys: per-row match counts
multiply into a running *weight*, and build-column aggregates read
bucket prefix sums), so the streamed pair multiset matches the eager
pair-list operator bit for bit.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.columnar import engine
from repro.distributed.sharding import ShardLayout
from repro.kernels.join import ref as join_ref
from repro.query import logical as L
from repro.query import telemetry as tm
from repro.query.cost import TableStats, key_is_unique


@dataclasses.dataclass(frozen=True)
class BreakerSpec:
    """One pipeline breaker: a join build consumed whole before the probe
    stream starts.  ``value_cols`` are the build columns the plan reads
    above the join (sorted for a deterministic flat layout)."""
    table: str
    on: str
    value_cols: Tuple[str, ...]
    unique: bool

    @property
    def n_arrays(self) -> int:
        return 2 + len(self.value_cols)


@dataclasses.dataclass
class StreamPlan:
    """Analysis product: the probe spine's stream source and breakers.
    ``join_nodes`` parallels ``breakers`` so the compiler can look up each
    join's physical decisions (impl) on the annotated plan."""
    node: L.Aggregate
    base_scan: L.Scan
    stream_cols: Tuple[str, ...]
    breakers: Tuple[BreakerSpec, ...]
    join_nodes: Tuple[L.Join, ...] = ()


def _analyze_spine(node: L.Node, stats: Dict[str, TableStats]):
    """Shared probe-spine analysis: Scan -> (Filter|FilterProject|
    Project)* with Joins whose build side is a Scan.  Returns
    (base_scan, breakers, join_nodes, dup_contributed, refs_above) or
    None when the shape does not stream."""
    table_columns = {t: s.columns for t, s in stats.items()}
    breakers = []
    join_nodes = []
    dup_contributed = set()
    refs_above: list = []               # filter/join-key columns, root-down
    base_scan: list = [None]
    ok = [True]

    def visit(n: L.Node):
        if not ok[0]:
            return
        if isinstance(n, L.Scan):
            base_scan[0] = n
            return
        if isinstance(n, (L.Filter, L.FilterProject)):
            refs_above.append(n.column)
            visit(n.child)
            return
        if isinstance(n, L.Project):
            visit(n.child)
            return
        if isinstance(n, L.Join):
            if not isinstance(n.right, L.Scan) or \
                    n.right.table not in stats:
                ok[0] = False
                return
            refs_above.append(n.on)
            visit(n.left)               # post-order: breakers in eval order
            if not ok[0]:
                return
            lcols = set(L.output_columns(n.left, table_columns))
            rcols = L.output_columns(n.right, table_columns)
            contributed = tuple(sorted(c for c in rcols
                                       if c not in lcols and c != n.on))
            unique = key_is_unique(n.right, n.on, stats)
            if not unique:
                dup_contributed.update(contributed)
            breakers.append(BreakerSpec(n.right.table, n.on, contributed,
                                        unique))
            join_nodes.append(n)
            return
        ok[0] = False

    visit(node)
    if not ok[0] or base_scan[0] is None or base_scan[0].table not in stats:
        return None
    return (base_scan[0], tuple(breakers), tuple(join_nodes),
            dup_contributed, refs_above)


def analyze(node: L.Node, stats: Dict[str, TableStats]
            ) -> Optional[StreamPlan]:
    """Whether a plan lowers onto a morsel pipeline, and its shape if so.

    Streamable plans are aggregate-rooted probe spines: Scan ->
    (Filter|FilterProject|Project)* with Joins whose build side is a
    Scan.  Duplicate-keyed build sides are fine (bucket-weighted
    aggregation) as long as their non-key columns are only read by the
    final aggregate — a filter or join key above that reads a
    multi-match column would need the materialized pair list, which is
    exactly what a pipeline breaker avoids.
    """
    if not isinstance(node, L.Aggregate):
        return None
    spine = _analyze_spine(node.child, stats)
    if spine is None:
        return None
    scan, breakers, join_nodes, dup_contributed, refs_above = spine
    # multi-match columns may feed the aggregate, nothing per-row above
    if dup_contributed & set(refs_above):
        return None
    stream_cols = scan.columns if scan.columns is not None \
        else tuple(stats[scan.table].columns)
    return StreamPlan(node, scan, tuple(stream_cols), breakers, join_nodes)


@dataclasses.dataclass
class ProjectStreamPlan:
    """A Project-rooted probe spine: the streamed form materializes one
    compacted output chunk per morsel instead of folding a carry.  Only
    unique-keyed build sides qualify — a multi-match join multiplies
    rows, which a per-row output mask cannot express."""
    node: L.Node                         # Project | FilterProject root
    base_scan: L.Scan
    stream_cols: Tuple[str, ...]
    breakers: Tuple[BreakerSpec, ...]
    join_nodes: Tuple[L.Join, ...]
    out_cols: Tuple[str, ...]


def analyze_project(node: L.Node, stats: Dict[str, TableStats]
                    ) -> Optional[ProjectStreamPlan]:
    """Whether a Project-rooted plan lowers onto a morsel pipeline whose
    per-morsel product is a compacted chunk of the output table."""
    if not isinstance(node, (L.Project, L.FilterProject)):
        return None
    spine = _analyze_spine(node, stats)
    if spine is None:
        return None
    scan, breakers, join_nodes, _, _ = spine
    if any(not b.unique for b in breakers):
        return None
    stream_cols = scan.columns if scan.columns is not None \
        else tuple(stats[scan.table].columns)
    return ProjectStreamPlan(node, scan, tuple(stream_cols), breakers,
                             join_nodes, tuple(node.columns))


@dataclasses.dataclass
class TrainStreamPlan:
    """A TrainGLM-rooted pipeline: every epoch streams the training set
    morsel-by-morsel with the K model weight vectors as the carry
    (``engine.train_glm_stream`` — CoCoA block rotation with block =
    morsel).  ``filtered`` plans materialize the selected rows once (a
    pipeline breaker: streaming compaction would make the minibatch
    boundaries data-dependent) and stream the epochs over the
    materialized set; bare scans stream straight off the catalog table,
    tier-aware, which is what lets an over-budget training set ride the
    tiered spill path instead of raising."""
    node: L.TrainGLM
    base_scan: L.Scan
    stream_cols: Tuple[str, ...]      # features + label on the base table
    filtered: bool


def analyze_train(node: L.Node, stats: Dict[str, TableStats]
                  ) -> Optional[TrainStreamPlan]:
    """Whether a TrainGLM-rooted plan lowers onto the epoch x morsel
    stream: Scan -> (Filter|FilterProject|Project)* with no joins (a
    joined training-set derivation falls back to the eager path)."""
    if not isinstance(node, L.TrainGLM):
        return None
    spine = _analyze_spine(node.child, stats)
    if spine is None:
        return None
    scan, breakers, _join_nodes, _dup, _refs = spine
    if breakers:
        return None
    cols = tuple(node.features) + (node.label,)
    avail = set(scan.columns) if scan.columns is not None \
        else set(stats[scan.table].columns)
    if not set(cols) <= avail:
        return None
    filtered = any(isinstance(n, (L.Filter, L.FilterProject))
                   for n in L.walk(node.child))
    return TrainStreamPlan(node, scan, cols, filtered)


@dataclasses.dataclass
class CompiledPipeline:
    """One plan shape compiled at one morsel granularity.  ``raw_step`` is
    the untransformed body — external drivers vmap it over many queries'
    (lits, carry) pairs to serve a whole group of compatible queries with
    one dispatch per morsel."""
    base_table: str
    stream_cols: Tuple[str, ...]
    breakers: Tuple[BreakerSpec, ...]
    rows: int
    step: Callable
    raw_step: Callable
    init_carry: Callable[[], object]
    finalize: Callable[[object], object]
    shard: Optional[ShardLayout] = None   # set when step is shard_mapped

    @property
    def n_build_arrays(self) -> int:
        return sum(b.n_arrays for b in self.breakers)


def compile_pipeline(splan: StreamPlan, rows: int, agg_dtype, *,
                     impls: Tuple[str, ...] = (),
                     trace_marker: Optional[Callable] = None,
                     shard: Optional[ShardLayout] = None
                     ) -> CompiledPipeline:
    """Lower a streamable plan into one jitted per-morsel step.

    ``rows`` is static (morsels are uniform, the tail zero-padded with
    ``n_valid`` masking); literals stay traced scalars so range bounds
    never force a recompile; the carry is donated so every morsel reuses
    the accumulator's buffer (no growth with stream length).  ``impls``
    (parallel to the breakers) carries the cost model's per-join impl
    decision: ``pallas`` probes use the binary-search counts kernel when
    the morsel shape admits it, everything else the XLA searchsorted.

    With ``shard`` (and ``rows`` divisible by the shard count) the step
    body is ``shard_map``-wrapped over the layout's mesh: every device
    evaluates the spine on its contiguous 1/n slice of the morsel (its
    pseudo-channel), builds stay replicated, and the carry reductions
    become ``psum``s of per-shard partial sums.  Integer carries psum
    exactly, and the mean's f32 partials over int inputs are exactly
    representable, so sharded results stay BIT-IDENTICAL to the
    single-device fold.
    """
    from repro.kernels.join.join import DEFAULT_BLOCK, probe_counts_pallas

    sharded = shard is not None and shard.n_shards > 1 \
        and rows % shard.n_shards == 0
    n_loc = rows // shard.n_shards if sharded else rows
    axis = shard.axis if sharded else None

    node = splan.node
    breakers = splan.breakers
    probe_impls = tuple(
        impls[i] if i < len(impls) and impls[i] == "pallas"
        and n_loc % DEFAULT_BLOCK == 0 else "xla"
        for i in range(len(breakers)))
    agg_is_int = jnp.issubdtype(agg_dtype, jnp.integer)
    # carry dtypes: 64-bit accumulators when x64 is enabled; under the
    # default x32 the integer carries are exact up to 2^31 total (and the
    # mean's f32 partial sums up to 2^24) — the regime every test and the
    # batch path share, which is what makes streamed results bit-identical
    x64 = jax.config.read("jax_enable_x64")
    int_acc = jnp.int64 if x64 else jnp.int32
    f_acc = jnp.float64 if x64 else jnp.float32

    if node.op == "sum":
        acc_dtype = int_acc if agg_is_int else f_acc
        init = lambda: jnp.zeros((), acc_dtype)            # noqa: E731
        fin = (lambda c: int(jax.device_get(c))) if agg_is_int \
            else (lambda c: float(jax.device_get(c)))
    elif node.op == "count":
        init = lambda: jnp.zeros((), int_acc)              # noqa: E731
        fin = lambda c: int(jax.device_get(c))             # noqa: E731
    elif node.op == "mean":
        init = lambda: (jnp.zeros((), f_acc),              # noqa: E731
                        jnp.zeros((), f_acc))
        fin = lambda c: float(jax.device_get(               # noqa: E731
            c[0] / jnp.maximum(c[1], 1.0)))
    else:
        raise ValueError(node.op)

    n_build = sum(b.n_arrays for b in breakers)

    def _rsum(x, dtype):
        # cast BEFORE the reduction (the per-morsel sum must run in the
        # carry's accumulator dtype); under sharding the partial sums are
        # psum'd across shards — exact for the integer/int-valued-f32
        # carries, hence bit-identical to the single-device fold
        s = jnp.sum(x.astype(dtype))
        return jax.lax.psum(s, axis) if sharded else s

    def step(lits, carry, n_valid, *arrays):
        if trace_marker is not None:
            trace_marker()                  # python side effect: trace count
        build_flat = arrays[:n_build]
        morsel = arrays[n_build:]
        # each shard sees its contiguous 1/n block: offset the validity
        # window into GLOBAL row coordinates
        off = jax.lax.axis_index(axis) * n_loc if sharded else 0
        valid = off + jnp.arange(n_loc, dtype=jnp.int32) < n_valid
        lit_pos = [0]
        breaker_pos = [0]

        def next_lit():
            v = lits[lit_pos[0]]
            lit_pos[0] += 1
            return v

        def next_breaker():
            i = breaker_pos[0]
            breaker_pos[0] += 1
            off = sum(b.n_arrays for b in breakers[:i])
            b = breakers[i]
            s_sorted, order = build_flat[off], build_flat[off + 1]
            vals = dict(zip(b.value_cols, build_flat[off + 2:off + 2
                                                     + len(b.value_cols)]))
            return b, probe_impls[i], s_sorted, order, vals

        def eval_node(n):
            """-> (cols, mask, weight, buckets): per-row values, the live-
            row mask, the multi-match multiplicity product, and bucket-sum
            pairs for duplicate-build columns."""
            if isinstance(n, L.Scan):
                cols = dict(zip(splan.stream_cols, morsel))
                return (cols, valid,
                        jnp.ones((n_loc,), jnp.int32), {})
            if isinstance(n, (L.Filter, L.FilterProject)):
                cols, mask, weight, buckets = eval_node(n.child)
                lo, hi = next_lit(), next_lit()
                mask = engine.select_range_morsel(cols[n.column], lo, hi,
                                                  mask)
                if isinstance(n, L.FilterProject):
                    cols = {k: cols[k] for k in n.columns if k in cols}
                return cols, mask, weight, buckets
            if isinstance(n, L.Project):
                cols, mask, weight, buckets = eval_node(n.child)
                return ({k: cols[k] for k in n.columns if k in cols},
                        mask, weight, buckets)
            if isinstance(n, L.Join):
                cols, mask, weight, buckets = eval_node(n.left)
                b, impl, s_sorted, order, vals = next_breaker()
                keys = cols[n.on]
                if impl == "pallas":
                    start, cnt = probe_counts_pallas(s_sorted, keys,
                                                     interpret=False)
                else:
                    start, cnt = join_ref.bucket_probe(s_sorted, keys)
                mask = mask & (cnt > 0)
                if b.unique:
                    safe = jnp.clip(start, 0, s_sorted.shape[0] - 1)
                    s_idx = order[safe]
                    for c in b.value_cols:
                        cols[c] = vals[c][s_idx]
                else:
                    weight = weight * cnt
                    for c in b.value_cols:
                        buckets[c] = (engine.bucket_sums(vals[c], start,
                                                         cnt), cnt)
                return cols, mask, weight, buckets
            raise TypeError(n)

        cols, mask, weight, buckets = eval_node(node.child)
        w_live = jnp.where(mask, weight, 0)
        if node.op == "count":
            return carry + _rsum(w_live, carry.dtype)
        if node.column in cols:
            val = cols[node.column]
            contrib = val * w_live.astype(val.dtype)
        else:
            bsum, cnt = buckets[node.column]
            others = w_live // jnp.maximum(cnt, 1)
            contrib = bsum * others.astype(bsum.dtype)
        if node.op == "sum":
            return carry + _rsum(contrib, carry.dtype)
        # mean: exact partial sums in the accumulator dtype (int inputs
        # stay exactly representable, so the result is bit-identical to
        # the whole-column evaluation)
        s, c = carry
        return (s + _rsum(contrib, s.dtype),
                c + _rsum(w_live, c.dtype))

    raw = step
    if sharded:
        # lits / carry / n_valid / builds replicated, morsel columns split
        # into contiguous per-device blocks; the carry (psum'd inside) is
        # replicated on the way out.  P() is a pytree prefix, so the
        # mean's tuple carry is covered.
        raw = shard_map(
            step, mesh=shard.mesh,
            in_specs=(P(), P(), P()) + (P(),) * n_build
            + (P(axis),) * len(splan.stream_cols),
            out_specs=P(), check_vma=False)
    donate = (1,) if jax.default_backend() != "cpu" else ()
    return CompiledPipeline(
        splan.base_scan.table, splan.stream_cols, breakers, rows,
        jax.jit(raw, donate_argnums=donate), raw, init, fin,
        shard=shard if sharded else None)


@dataclasses.dataclass
class CompiledProject:
    """A Project-rooted plan shape compiled at one morsel granularity.
    ``step`` maps one morsel to (mask, out_arrays): the live-row mask
    after every filter and unique-join probe, and the projected columns
    with joined build values gathered per row.  The driver compacts each
    morsel's live rows into a chunk; chunks concatenated in morsel order
    reproduce the eager output's row order exactly."""
    base_table: str
    stream_cols: Tuple[str, ...]
    breakers: Tuple[BreakerSpec, ...]
    rows: int
    out_cols: Tuple[str, ...]
    step: Callable
    raw_step: Callable
    shard: Optional[ShardLayout] = None   # set when step is shard_mapped

    @property
    def n_build_arrays(self) -> int:
        return sum(b.n_arrays for b in self.breakers)


def compile_project_pipeline(pplan: ProjectStreamPlan, rows: int, *,
                             impls: Tuple[str, ...] = (),
                             trace_marker: Optional[Callable] = None,
                             shard: Optional[ShardLayout] = None
                             ) -> CompiledProject:
    """Lower a Project-rooted streamable plan into one jitted per-morsel
    step producing (mask, out_cols).  Same argument layout and literal
    discipline as ``compile_pipeline`` — range bounds stay traced, so the
    serving streams share one compilation across member bounds.

    With ``shard``, each device evaluates its contiguous 1/n block and
    the per-shard (mask, cols) blocks concatenate back into the global
    morsel row order (out_specs=P(axis)), so the driver's compaction —
    and therefore the output table — is unchanged byte for byte."""
    from repro.kernels.join.join import DEFAULT_BLOCK, probe_counts_pallas

    sharded = shard is not None and shard.n_shards > 1 \
        and rows % shard.n_shards == 0
    n_loc = rows // shard.n_shards if sharded else rows
    axis = shard.axis if sharded else None

    breakers = pplan.breakers
    probe_impls = tuple(
        impls[i] if i < len(impls) and impls[i] == "pallas"
        and n_loc % DEFAULT_BLOCK == 0 else "xla"
        for i in range(len(breakers)))
    n_build = sum(b.n_arrays for b in breakers)

    def step(lits, n_valid, *arrays):
        if trace_marker is not None:
            trace_marker()
        build_flat = arrays[:n_build]
        morsel = arrays[n_build:]
        off = jax.lax.axis_index(axis) * n_loc if sharded else 0
        valid = off + jnp.arange(n_loc, dtype=jnp.int32) < n_valid
        lit_pos = [0]
        breaker_pos = [0]

        def next_lit():
            v = lits[lit_pos[0]]
            lit_pos[0] += 1
            return v

        def next_breaker():
            i = breaker_pos[0]
            breaker_pos[0] += 1
            off = sum(b.n_arrays for b in breakers[:i])
            b = breakers[i]
            s_sorted, order = build_flat[off], build_flat[off + 1]
            vals = dict(zip(b.value_cols, build_flat[off + 2:off + 2
                                                     + len(b.value_cols)]))
            return b, probe_impls[i], s_sorted, order, vals

        def eval_node(n):
            if isinstance(n, L.Scan):
                return dict(zip(pplan.stream_cols, morsel)), valid
            if isinstance(n, (L.Filter, L.FilterProject)):
                cols, mask = eval_node(n.child)
                lo, hi = next_lit(), next_lit()
                mask = engine.select_range_morsel(cols[n.column], lo, hi,
                                                  mask)
                if isinstance(n, L.FilterProject):
                    cols = {k: cols[k] for k in n.columns if k in cols}
                return cols, mask
            if isinstance(n, L.Project):
                cols, mask = eval_node(n.child)
                return {k: cols[k] for k in n.columns if k in cols}, mask
            if isinstance(n, L.Join):
                cols, mask = eval_node(n.left)
                b, impl, s_sorted, order, vals = next_breaker()
                keys = cols[n.on]
                if impl == "pallas":
                    start, cnt = probe_counts_pallas(s_sorted, keys,
                                                     interpret=False)
                else:
                    start, cnt = join_ref.bucket_probe(s_sorted, keys)
                mask = mask & (cnt > 0)
                safe = jnp.clip(start, 0, s_sorted.shape[0] - 1)
                s_idx = order[safe]
                for c in b.value_cols:
                    cols[c] = vals[c][s_idx]
                return cols, mask
            raise TypeError(n)

        cols, mask = eval_node(pplan.node)
        return mask, tuple(cols[c] for c in pplan.out_cols)

    raw = step
    if sharded:
        raw = shard_map(
            step, mesh=shard.mesh,
            in_specs=(P(), P()) + (P(),) * n_build
            + (P(axis),) * len(pplan.stream_cols),
            out_specs=(P(axis), P(axis)), check_vma=False)
    return CompiledProject(
        pplan.base_scan.table, pplan.stream_cols, breakers, rows,
        pplan.out_cols, jax.jit(raw), raw,
        shard=shard if sharded else None)


def _account_morsel(metrics, t0: float, t1: float, t2: float) -> None:
    """One morsel's split on the host clock: transfer-wait (t0..t1 —
    blocked on staging) vs compute dispatch (t1..t2).  With perfect H2D
    overlap the wait term collapses toward zero."""
    if metrics is None:
        return
    metrics.inc("pipeline.morsels")
    metrics.inc("pipeline.transfer_wait_s", t1 - t0)
    metrics.inc("pipeline.compute_s", t2 - t1)
    metrics.observe("pipeline.morsel_wait_s", t1 - t0)
    metrics.observe("pipeline.morsel_step_s", t2 - t1)


def drive(cp: CompiledPipeline, n_morsels: int, get_morsel, build_flat,
          lits, carry=None, *, prefetch: bool = True,
          telemetry=None, metrics=None):
    """Run the morsel loop with transfer/compute overlap.

    With ``prefetch`` (the default) a background thread pulls morsels
    ahead of the python dispatch loop through a small bounded queue, so
    the host-side slicing + ``jax.device_put`` staging of morsel ``i+1``
    runs while the main thread is still dispatching morsel ``i`` — H2D
    genuinely overlaps python dispatch, not just device compute.
    ``prefetch=False`` (or ``REPRO_OVERLAP=0`` via the executor) falls
    back to the single-threaded double-buffered loop for determinism
    debugging; both orders fold morsels identically, so results are
    bit-identical either way.

    Each morsel's wait for its staging runs under a
    ``pipeline.morsel_wait`` span and its step's dispatch under
    ``pipeline.morsel_step``, spans of ``telemetry`` (the process-global
    one when omitted).  ``metrics`` (optional) also sums the two on the
    host clock — the direct measurement of how effective the H2D overlap
    is."""
    tel = telemetry if telemetry is not None else tm.get()
    clock = time.perf_counter
    carry = cp.init_carry() if carry is None else carry
    if prefetch and n_morsels > 1:
        buf: queue.Queue = queue.Queue(maxsize=2)
        failure: list = []
        stop = threading.Event()

        def put(item) -> bool:
            # bounded-wait put so a consumer that aborted (step raised)
            # can always unblock the stage thread via ``stop`` — no
            # thread or staged device buffers leak on the error path
            while not stop.is_set():
                try:
                    buf.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def stage():
            try:
                for i in range(n_morsels):
                    if not put(get_morsel(i)):
                        return
            except BaseException as e:            # noqa: BLE001
                failure.append(e)
                put(None)

        t = threading.Thread(target=stage, daemon=True)
        t.start()
        try:
            for i in range(n_morsels):
                t0 = clock()
                with tel.span("pipeline.morsel_wait", morsel=i,
                              path="prefetch"):
                    item = buf.get()
                if item is None:
                    break
                cur_arrays, n_valid = item
                t1 = clock()
                with tel.span("pipeline.morsel_step", morsel=i,
                              path="prefetch"):
                    carry = cp.step(lits, carry, n_valid, *build_flat,
                                    *cur_arrays)
                _account_morsel(metrics, t0, t1, clock())
        finally:
            stop.set()
            t.join()
        if failure:
            raise failure[0]
        return carry
    t0 = clock()
    with tel.span("pipeline.morsel_wait", morsel=0, path="double_buffer"):
        nxt = get_morsel(0)
    t_stage = clock() - t0
    for i in range(n_morsels):
        cur_arrays, n_valid = nxt
        t0 = clock()
        if i + 1 < n_morsels:
            with tel.span("pipeline.morsel_wait", morsel=i + 1,
                          path="double_buffer"):
                nxt = get_morsel(i + 1)
        t1 = clock()
        # in the double-buffered loop the NEXT morsel's staging is the
        # serial (non-overlapped) transfer term for this step
        with tel.span("pipeline.morsel_step", morsel=i,
                      path="double_buffer"):
            carry = cp.step(lits, carry, n_valid, *build_flat, *cur_arrays)
        _account_morsel(metrics, t0 - t_stage, t1, clock())
        t_stage = 0.0
    return carry
