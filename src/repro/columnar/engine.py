"""Physical operators over the column store, backed by the accelerated cores.

This is the integration layer the paper builds into MonetDB: operators take
and return Tables; the FPGA roles are played by the mesh engines
(core.selection / core.join / core.sgd_glm), selected per operator exactly
like MonetDB's optimizer picks the UDF implementation.
"""
from __future__ import annotations

import contextvars
import dataclasses
import functools
import time
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.columnar.table import Column, MorselSpec, Table
from repro.core import join as join_core
from repro.core import selection as sel_core
from repro.core import sgd_glm
from repro.core.channels import ChannelPlan
from repro.kernels.join import ref as join_ref
from repro.kernels.sgd import ref as sgd_ref
from repro.kernels.sgd import sgd as sgd_kernels
from repro.kernels.sgd.sgd import sgd_block, sgd_block_wide


# rows per engine block of the eager selection (``Executor._filter_table``
# hands the kernel only base columns that split into whole blocks)
SELECT_BLOCK = 1024


def compact_positions(valid: jax.Array, n: int) -> jax.Array:
    """Positions of the first ``n`` True entries, ascending.

    Shared compaction for selection and join outputs: O(N) nonzero with a
    static output size instead of the old O(N log N) full argsort over all
    lanes."""
    (pos,) = jnp.nonzero(valid, size=n, fill_value=0)
    return pos.astype(jnp.int32)


def scan(table: Table, columns: Sequence[str]) -> Table:
    return Table(table.name, {c: table.columns[c] for c in columns},
                 table.plan)


def select_range(table: Table, column: str, lo: int, hi: int, *,
                 impl: str = "xla", block: int = SELECT_BLOCK) -> Table:
    """Range selection -> materialized index column (with count).
    ``block`` halves itself until the per-engine shard tiles evenly, so
    the same call works on a 1-engine and an 8-engine mesh.  The kernel
    (``impl="pallas"``) needs blocks of whole 128-lane rows."""
    assert table.plan is not None, "place() the table first"
    n_eng = table.plan.n_engines
    if table.plan.placement != "partitioned" or \
            table.num_rows % n_eng != 0:
        # non-partitioned plans on a multi-device mesh must NOT go
        # through select_distributed: its congested mode is the Fig. 5
        # crossbar baseline (every engine rescans the first shard with
        # per-engine offsets), a throughput analogue — not a correct
        # selection unless n_engines == 1
        col = table.column(column)
        mask = (col >= lo) & (col <= hi)
        n = int(jnp.sum(mask))
        idx = compact_positions(mask, n).astype(jnp.int32)
        return Table(f"{table.name}.sel", {"idx": Column(idx, "idx")})
    while block > 1 and table.num_rows % (n_eng * block) != 0:
        block //= 2
    if impl == "pallas" and block % 128:
        raise ValueError(
            f"the selection kernel needs 128-lane blocks: {table.num_rows} "
            f"rows over {n_eng} engines split into {block}-row blocks")
    idx, counts = sel_core.select_distributed(
        table.column(column), lo, hi, table.plan, block=block, impl=impl)
    flat = idx.reshape(-1)
    n = int(jnp.sum(counts))
    compacted = flat[compact_positions(flat >= 0, n)]
    return Table(f"{table.name}.sel", {"idx": Column(compacted, "idx")})


def join(left: Table, right: Table, on: str, *, impl: str = "xla",
         unique: Optional[bool] = None) -> Table:
    """Inner join: right is the (build) side.  Returns the full multiset of
    matched index pairs (l_idx, r_idx) — MonetDB's join produces exactly
    such BAT pairs.  Duplicate build keys emit one pair per match (the
    multi-match sorted-bucket kernel); ``unique=True`` keeps the paper's
    unique-S open-addressing fast path (at most one match per probe row,
    identical pairs when the keys really are unique)."""
    assert left.plan is not None
    n_build = right.num_rows
    if n_build > join_core.HT_CAPACITY:
        passes = -(-n_build // join_core.HT_CAPACITY)
        warnings.warn(
            f"join build side '{right.name}' has {n_build} rows > "
            f"HT_CAPACITY={join_core.HT_CAPACITY}: multi-pass join will "
            f"rescan the probe side {passes}x (Fig. 8b linear regime)",
            RuntimeWarning, stacklevel=2)
    if unique:
        l_keys = _pad_probe(left.column(on), left.plan.n_engines)
        s_idx, total = join_core.join_distributed(
            right.column(on), l_keys, left.plan, impl=impl)
        n = int(total)
        l_idx = compact_positions(s_idx >= 0, n)
        r_idx = s_idx[l_idx]
    else:
        l_idx, r_idx = _join_pairs(right.column(on), left.column(on),
                                   left.plan, impl=impl)
    return Table("join", {"l_idx": Column(l_idx, "l_idx"),
                          "r_idx": Column(r_idx, "r_idx")})


def _pad_probe(l_keys: jax.Array, n_engines: int) -> jax.Array:
    """Pad the probe side to a multiple of the plan's engine count — the
    distributed kernels shard_map it over the mesh axis, which needs even
    shards.  -1 sentinels match nothing: real build keys are validated
    non-negative and the multi-pass build pads are <= -(2**30)."""
    rem = (-int(l_keys.shape[0])) % max(int(n_engines), 1)
    if rem:
        l_keys = jnp.concatenate(
            [l_keys, jnp.full((rem,), -1, l_keys.dtype)])
    return l_keys


def _check_key_domain(s_keys: jax.Array, l_keys: jax.Array) -> None:
    # the kernels reserve key values for pad sentinels (negative range for
    # multi-pass padding, 2**31-1 for the Pallas table pad); this is the
    # eager layer, so reject out-of-domain catalog data instead of
    # silently corrupting pairs
    for name, keys in (("build", s_keys), ("probe", l_keys)):
        if keys.shape[0] and (int(jnp.min(keys)) < 0
                              or int(jnp.max(keys)) >= 2 ** 31 - 1):
            raise ValueError(
                f"join {name} keys must be in [0, 2**31 - 2]: values "
                "outside it collide with the kernel pad sentinels")


def _join_pairs(s_keys: jax.Array, l_keys: jax.Array, plan, *,
                impl: str = "xla"):
    """Compacted (l_idx, s_idx) pair columns from the distributed multi-
    match join.  The per-shard pair totals are exact even when a shard's
    fixed pair list overflows, so one retry with the measured capacity
    always suffices."""
    _check_key_domain(s_keys, l_keys)
    l_keys = _pad_probe(l_keys, plan.n_engines)
    out = join_core.join_distributed_multi(s_keys, l_keys, plan, impl=impl)
    l_buf, s_buf, totals, overflow = out
    if bool(jnp.any(overflow)):
        need = int(jnp.max(totals))
        l_buf, s_buf, totals, overflow = join_core.join_distributed_multi(
            s_keys, l_keys, plan, impl=impl,
            max_out_per_shard=max(need, 64))
        assert not bool(jnp.any(overflow))
    n = int(jnp.sum(totals))
    pos = compact_positions(l_buf >= 0, n)
    return l_buf[pos], s_buf[pos]


def join_shuffle(left: Table, right: Table, on: str, layout, *,
                 impl: str = "xla") -> Table:
    """Inner join by shuffle repartitioning (the planner's costed
    alternative to broadcasting the build side): both sides hash-partition
    by key across ``layout``'s device mesh, each shard joins its bucket
    locally.  Produces pairs bit-identical to ``join``: the raw emission
    is shard-major, but a final stable sort by probe row restores the
    single-device (probe row, bucket position) order — all matches of one
    probe row live on one shard (same key, same hash), and the stable
    partition + stable build sort keep equal-key matches in ascending
    global build order, exactly like the unsharded kernel.  Shuffle-bucket
    or pair-list overflows retry with the exact measured capacities, so
    the result is always complete."""
    s_keys, l_keys = right.column(on), left.column(on)
    _check_key_domain(s_keys, l_keys)
    kw = {}
    for _ in range(3):
        l_buf, s_buf, totals, pair_over, (s_counts, l_counts, shuf_over) = \
            join_core.join_shuffle_multi(s_keys, l_keys, layout, impl=impl,
                                         **kw)
        if not (bool(shuf_over) or bool(jnp.any(pair_over))):
            break
        # counts/totals are exact even on overflow: one sizing pass each
        # for the shuffle buckets and the pair lists always converges
        l_cap = max(int(jnp.max(l_counts)), 8)
        kw = dict(s_cap=max(int(jnp.max(s_counts)), 8), l_cap=l_cap,
                  max_out_per_shard=max(int(jnp.max(totals)), 2 * l_cap, 64))
    else:
        raise AssertionError("join_shuffle failed to converge on capacity")
    n = int(jnp.sum(totals))
    pos = compact_positions(l_buf >= 0, n)
    l_sel, s_sel = l_buf[pos], s_buf[pos]
    order = jnp.argsort(l_sel, stable=True)
    return Table("join", {"l_idx": Column(l_sel[order], "l_idx"),
                          "r_idx": Column(s_sel[order], "r_idx")})


def gather(table: Table, idx: jax.Array, columns: Sequence[str],
           name: str = "proj") -> Table:
    cols = {c: Column(jnp.take(table.column(c), idx, axis=0), c)
            for c in columns}
    return Table(name, cols)


def aggregate_sum(table: Table, column: str) -> float:
    return float(jnp.sum(table.column(column)))


def train_glm(table: Table, features: Sequence[str], label: str,
              grid, plan: ChannelPlan, *, kind: str = "logreg",
              epochs: int = 5, impl: str = "xla"):
    """In-database ML (paper §VI): hyper-parameter search over GLMs on
    columns of a table — the doppioDB-style UDF."""
    a = jnp.stack([table.column(f).astype(jnp.float32) for f in features],
                  axis=1)
    b = table.column(label).astype(jnp.float32)
    return sgd_glm.hyperparam_search(a, b, grid, plan, kind=kind,
                                     epochs=epochs, impl=impl)


# --------------------------------------------------------------------------- #
# streaming (morsel-driven) operators
#
# The eager operators above materialize whole-column intermediates (BAT
# style).  The streaming forms below are partition-granular: state that
# outlives one morsel is explicit.  A JoinBuild is the product of a
# pipeline breaker — probe morsels stream against it; aggregate carries
# accumulate across morsels; train_glm_stream threads model parameters
# through epoch x morsel order so it reproduces the whole-column SGD
# minibatch sequence exactly when morsels align with minibatches.

@dataclasses.dataclass
class JoinBuild:
    """Sorted-bucket build state.  ``s_sorted``/``order`` are the layout of
    ``kernels/join/ref.bucket_build``; probe morsels binary-search their
    bucket.  ``values`` holds raw build columns for unique-key gathers,
    ``csums`` exclusive prefix sums over the key-sorted column for exact
    duplicate-bucket sums (the fused pair-list aggregate)."""
    on: str
    unique: bool
    s_sorted: jax.Array
    order: jax.Array
    values: Dict[str, jax.Array]
    csums: Dict[str, jax.Array]

    @property
    def n_build(self) -> int:
        return int(self.s_sorted.shape[0])

    def flat(self) -> Tuple[jax.Array, ...]:
        """Deterministic flattening for jitted step signatures."""
        return (self.s_sorted, self.order,
                *(self.values[c] for c in sorted(self.values)),
                *(self.csums[c] for c in sorted(self.csums)))


def join_build(right: Table, on: str, value_cols: Sequence[str] = (), *,
               unique: bool = False,
               plan: Optional[ChannelPlan] = None) -> JoinBuild:
    """Pipeline breaker: consume the whole build side once, producing the
    state probe morsels stream against.  With ``plan``, every array is
    replicated across the mesh (the paper's per-engine build replication)."""
    keys = right.column(on)
    s_sorted, order = join_ref.bucket_build(keys)
    values: Dict[str, jax.Array] = {}
    csums: Dict[str, jax.Array] = {}
    for c in value_cols:
        col = right.column(c)
        if unique:
            values[c] = col
        else:
            sc = col[order]
            csums[c] = jnp.concatenate(
                [jnp.zeros((1,), sc.dtype), jnp.cumsum(sc)])
    if plan is not None:
        rep = NamedSharding(plan.mesh, P())
        put = lambda a: jax.device_put(a, rep)           # noqa: E731
        s_sorted, order = put(s_sorted), put(order)
        values = {k: put(v) for k, v in values.items()}
        csums = {k: put(v) for k, v in csums.items()}
    return JoinBuild(on, unique, s_sorted, order, values, csums)


def join_probe_morsel(build: JoinBuild, keys: jax.Array):
    """Probe one morsel of keys: (start, count) of each key's bucket in the
    sorted build side — exact multi-match counts, no capacity cap."""
    return join_ref.bucket_probe(build.s_sorted, keys)


def bucket_sums(csum: jax.Array, start: jax.Array, count: jax.Array):
    """Sum of a build column over each probe row's bucket, via the
    exclusive prefix sums a JoinBuild carries."""
    return csum[start + count] - csum[start]


def select_range_morsel(col: jax.Array, lo, hi,
                        mask: jax.Array) -> jax.Array:
    """Streaming range selection: narrow the morsel's row mask in place —
    no index materialization between pipeline stages."""
    return mask & (col >= lo) & (col <= hi)


def aggregate_sum_stream(carry, values: jax.Array, mask: jax.Array,
                         weight: Optional[jax.Array] = None):
    """Fold one morsel into a running sum.  ``weight`` is the per-row match
    multiplicity contributed by duplicate-keyed joins upstream."""
    w = mask.astype(values.dtype) if weight is None else \
        jnp.where(mask, weight, 0).astype(values.dtype)
    return carry + jnp.sum(values * w)


def sgd_kernel_applies(mesh) -> bool:
    """Whether ``train_glm_stream`` runs its SGD loop as a Pallas kernel
    (``kernels/sgd/sgd.sgd_block``, or ``sgd_block_wide`` for a wide
    table, ``sgd.wide``): on a mesh of one TPU device.  The CPU runs the
    XLA loop, and so does a mesh of several devices, where the dataset is
    replicated and GSPMD would have to partition a custom call.  The
    paths stage the morsel in different layouts (``stage_morsel``)."""
    return mesh.devices.size == 1 and mesh.devices.flat[0].platform == "tpu"


@functools.partial(jax.jit, static_argnames=("rows", "rows_pad", "layout"))
def stage_morsel(cols, start, *, rows: int, rows_pad: int, layout: str):
    """One morsel of the training columns (the label last) as the step
    programs take it, in one program: ``rows`` rows of each column from
    ``start``, cast to float32, zero rows up to ``rows_pad``, and
    ``layout``: ``"rows"`` gives ``(rows_pad, features)`` and the label
    (the XLA loop), ``"features"`` gives ``(features + 1, rows_pad)`` with
    the label as the last row (``sgd_block``), ``"wide"`` the same with
    zero rows up to ``sgd.wide_rows`` (``sgd_block_wide``)."""
    vals = [jnp.pad(lax.dynamic_slice_in_dim(c, start, rows)
                    .astype(jnp.float32), (0, rows_pad - rows))
            for c in cols]
    if layout == "rows":
        return jnp.stack(vals[:-1], axis=1), vals[-1]
    if layout == "features":
        return (jnp.stack(vals, axis=0),)
    # XLA splits a concatenate of thousands of operands into nested ones,
    # each byte written twice, so the rows go in place; eight at a time,
    # since a row alone is one sublane of every (8, 128) tile it touches
    data = jnp.zeros((sgd_kernels.wide_rows(len(cols) - 1), rows_pad),
                     jnp.float32)
    for i in range(0, len(vals), 8):
        data = lax.dynamic_update_slice_in_dim(
            data, jnp.stack(vals[i:i + 8]), i, 0)
    return (data,)


# the telemetry and metrics of the ``train_glm_stream`` call that is
# dispatching the steps: a trace of a step reports to that call
_TRACE_SINK: contextvars.ContextVar = contextvars.ContextVar(
    "trainer_trace_sink", default=None)


def _traced():
    # runs in the steps' Python bodies, so once per trace; the span
    # marks each trace in a profiler trace too
    sink = _TRACE_SINK.get()
    if sink is None:
        return
    telemetry, metrics = sink
    with telemetry.span("trainer.trace"):
        if metrics is not None:
            metrics.inc("trainer.traces")


@functools.partial(jax.jit,
                   static_argnames=("step", "impl", "kind", "minibatch"))
def epoch_step(xs, lrs, l2s, *arrays, step, impl: str, kind: str,
               minibatch: int):
    """One epoch of the K models ``xs`` over one staged morsel: the
    kernel ``step`` (``sgd_block`` or ``sgd_block_wide``) over the
    feature-major morsel, or for ``impl="xla"`` ``step`` (``sgd_ref``)
    under ``vmap`` over ``(rows, features)`` and the label.  ``step`` is
    static, so a step function patched after a first trace gets a
    program of its own."""
    _traced()
    if impl != "xla":
        return step(arrays[0], lrs, l2s, xs, minibatch=minibatch, kind=kind)
    a_m, b_m = arrays

    def one(x, lr, l2):
        return step(a_m, b_m, x, lr=lr, l2=l2, minibatch=minibatch,
                    epochs=1, kind=kind)
    return jax.vmap(one)(xs, lrs, l2s)


@functools.partial(jax.jit, static_argnames=("impl", "kind"))
def loss_step(acc, xs, n_valid, *arrays, impl: str, kind: str):
    """``acc`` plus each model's summed loss over the first ``n_valid``
    rows of one staged morsel, in ``epoch_step``'s layout."""
    _traced()

    def row_loss(z, b):
        if kind == "logreg":
            p = jax.nn.sigmoid(z)
            eps = 1e-7
            return -(b * jnp.log(p + eps) + (1 - b) * jnp.log(1 - p + eps))
        return 0.5 * jnp.square(z - b)

    if impl != "xla":
        (d_m,) = arrays
        valid = (jnp.arange(d_m.shape[1]) < n_valid).astype(jnp.float32)
        # the label row, and a wide layout's zero rows, meet zero weights
        n = xs.shape[1]
        z = jnp.pad(xs, ((0, 0), (0, d_m.shape[0] - n))) @ d_m
        return acc + jnp.sum(row_loss(z, d_m[n]) * valid, axis=1)
    a_m, b_m = arrays
    valid = (jnp.arange(a_m.shape[0]) < n_valid).astype(jnp.float32)
    return acc + jax.vmap(
        lambda x: jnp.sum(row_loss(a_m @ x, b_m) * valid))(xs)


def train_glm_stream(table: Table, features: Sequence[str], label: str,
                     grid, plan: ChannelPlan, *, kind: str = "logreg",
                     epochs: int = 5, minibatch: int = 16,
                     morsel_rows: Optional[int] = None,
                     on_morsel=None, telemetry, metrics=None):
    """Morsel-streamed hyper-parameter search: each epoch streams the
    morsels in table order with the K models' parameters as the carry, so
    the minibatch update sequence — and therefore the trained weights —
    matches ``train_glm`` exactly when morsels align with minibatches
    (CoCoA-style block rotation with block = morsel).

    Non-dividing row counts zero-pad ONLY the final morsel up to the next
    minibatch multiple (never to a full morsel: a pure-pad minibatch
    would still apply the l2 shrinkage step and perturb the weights).
    Zero feature rows contribute exactly zero to the gradient numerator,
    so the streamed minibatch sequence equals the eager path's
    ``sgd_glm.pad_to_minibatch`` sequence on any row count; losses mask
    the pad rows and divide by the true row count.

    Morsels come from ``Table.morsel``, so host/disk-resident (spilled)
    columns stream tier-aware: the numpy slice + H2D promotion happens
    per morsel staged and the training set never has to fit on device
    whole.  ``on_morsel(n_bytes, seconds, tier)`` observes each
    promotion, and fences each staging to time it.

    On one TPU chip (``sgd_kernel_applies``) each morsel is staged
    feature-major, ``(features + 1, rows)`` with the label as the last
    row, and the SGD loop is a Pallas kernel: ``sgd_block``, or for a wide
    table (``kernels/sgd/sgd.wide``, from the feature count)
    ``sgd_block_wide`` over the same layout padded to whole 128-lane
    groups; elsewhere the morsel is ``(rows, features)`` plus the label
    and the loop is ``sgd_ref`` under XLA.  All apply one update per
    minibatch of the morsel's rows padded to the minibatch; the kernels
    sum their products in float32 in another order, so their weights
    match ``train_glm`` to float32 rounding, not bit for bit.  Staging is
    one program, ``stage_morsel``, traced once per morsel shape.  The
    steps are module-level programs, ``epoch_step`` and ``loss_step``,
    each traced once per morsel shape, ``kind``, ``minibatch``, kernel
    form and step function, and reused by every later call: the grid's
    rates and the carry are operands.

    A training set of one morsel is staged once a call, and every epoch
    and the loss pass read that one copy.  Several morsels are staged
    anew for each epoch and for the loss pass, so only two staged
    morsels need fit on the device at once: on the kernel path each
    step that takes a fresh morsel first waits for the step before it.

    Spans of ``telemetry`` (any object whose ``span(name, **attrs)`` is
    a context manager): ``trainer.stage`` per staging (with ``cols`` and
    the staged ``bytes``; ``stage_morsel`` and ``device_put``), so once
    a call for one morsel, ``trainer.epoch_step`` per morsel and epoch
    and ``trainer.loss_step`` per morsel, each around the step's
    dispatch, including any trace or compile of its program;
    ``trainer.epoch_step`` carries ``impl="pallas"``, ``"pallas_wide"``
    or ``"xla"``.  Each trace of either step opens a ``trainer.trace``
    span and counts one ``trainer.traces`` in ``metrics`` of the call
    that traced it, so a call of a shape already traced counts none;
    each kernel dispatch counts one ``trainer.sgd_kernel_calls``, each
    staging its bytes in ``trainer.staged_bytes``, and each step handed
    the morsel already staged one ``trainer.stage_reuses`` (``epochs`` a
    call for one morsel, none for several)."""
    m = table.num_rows
    if morsel_rows is None:
        morsel_rows = m
    morsel_rows = max((min(morsel_rows, m) // minibatch) * minibatch,
                      minibatch)
    spec = MorselSpec(m, morsel_rows)
    cols = tuple(features) + (label,)
    k = len(grid)
    rep = NamedSharding(plan.mesh, P())      # dataset replication (Fig. 10a)
    # committed like the staged morsels, so the first epoch runs the
    # program the later ones do
    xs, lrs, l2s, acc = jax.device_put(
        (np.zeros((k, len(features)), np.float32),
         np.array([g.lr for g in grid], np.float32),
         np.array([g.l2 for g in grid], np.float32),
         np.zeros((k,), np.float32)), rep)
    kernel = sgd_kernel_applies(plan.mesh)
    wide = kernel and sgd_kernels.wide(len(features))
    impl = "pallas_wide" if wide else ("pallas" if kernel else "xla")
    layout = "wide" if wide else ("features" if kernel else "rows")
    # looked up on each call: a step patched since the last call runs
    step = sgd_block_wide if wide else (sgd_block if kernel
                                        else sgd_ref.sgd_ref)
    on_device = all(table.column_tier(c) == "device" for c in cols)

    def morsel_arrays(i):
        start, stop = spec.bounds(i)
        n_valid = stop - start
        # keep only up to the next minibatch multiple past the valid rows
        rows_pad = -(-n_valid // minibatch) * minibatch
        n_rows = (sgd_kernels.wide_rows(len(features)) if wide
                  else len(cols))
        n_bytes = 4 * n_rows * rows_pad
        with telemetry.span("trainer.stage", morsel=i, cols=len(cols),
                            bytes=n_bytes):
            t0 = time.perf_counter()
            if on_device:
                # sliced inside the staging program: no dispatch per column
                srcs = tuple(table.columns[c].data for c in cols)
            else:
                data, _ = table.morsel(spec, i, cols)
                srcs, start = tuple(data[c] for c in cols), 0
            arrays = stage_morsel(srcs, start, rows=n_valid,
                                  rows_pad=rows_pad, layout=layout)
            arrays = tuple(jax.device_put(x, rep) for x in arrays)
            if metrics is not None:
                metrics.inc("trainer.staged_bytes", n_bytes)
            if on_morsel is not None:
                jax.block_until_ready(arrays)
                tiers = {table.column_tier(c) for c in cols}
                worst = "disk" if "disk" in tiers else \
                    ("host" if "host" in tiers else "device")
                on_morsel(sum(x.nbytes for x in arrays),
                          time.perf_counter() - t0, worst)
            return arrays, n_valid

    kept = None

    def step_arrays(i):
        # the step's morsel and whether it was staged for this step: a
        # lone morsel is staged for the first step and kept for the rest
        nonlocal kept
        if kept is not None:
            if metrics is not None:
                metrics.inc("trainer.stage_reuses")
            return kept, False
        staged = morsel_arrays(i)
        if spec.n_morsels == 1:
            kept = staged
        return staged, True

    sink = _TRACE_SINK.set((telemetry, metrics))
    try:
        for e in range(epochs):
            for i in range(spec.n_morsels):
                (arrays, _), fresh = step_arrays(i)
                with telemetry.span("trainer.epoch_step", morsel=i, epoch=e,
                                    impl=impl):
                    if kernel and metrics is not None:
                        metrics.inc("trainer.sgd_kernel_calls")
                    prev, xs = xs, epoch_step(
                        xs, lrs, l2s, *arrays, step=step, impl=impl,
                        kind=kind, minibatch=minibatch)
                if kernel and fresh:
                    # the host stages ahead of the chip; waiting for the
                    # step before this one keeps two staged morsels in its
                    # HBM, not one for every step queued (3.3 GB each at
                    # epsilon's width)
                    jax.block_until_ready(prev)
        for i in range(spec.n_morsels):
            (arrays, n_valid), _ = step_arrays(i)
            with telemetry.span("trainer.loss_step", morsel=i):
                acc = loss_step(acc, xs, jnp.int32(n_valid), *arrays,
                                impl=impl, kind=kind)
    finally:
        _TRACE_SINK.reset(sink)
    losses = acc / m + l2s * jnp.sum(jnp.square(xs), axis=1)
    return xs, losses
