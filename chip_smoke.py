"""Drive the query engine's main path once on a TPU and check every answer
against a plain numpy reference written here.

    python chip_smoke.py                  # one chip: every phase, full size
    python chip_smoke.py --chips 4        # four chips: the sharded path only
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 65536    # rehearsal

Data comes from ``--seed`` (numpy), in the shapes of
``examples/analytics_pipeline.py``: a ``lineitem`` fact table of 2^27 rows
(four int32 columns, 2 GiB on the device), an ``orders`` dimension of
2^23 unique keys, a Zipf duplicate-key ``supp`` dimension, and a GLM
table of 2^20 rows x 8 float32 features.  Every query enters through
``Executor.execute`` or ``QueryServer``.  Integer answers must match
exactly; GLM losses and scores within the tolerances below.

Per phase the script prints wall time, each query's first-call (compile
included) and warm time, ``Executor.explain``, and the device's peak
memory.  Its last line is ``{"ok": true, "device": {...}}`` only on a TPU
at full size with every answer right; otherwise it exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
FULL_ROWS = 1 << 27            # lineitem rows: 4 int32 columns = 2 GiB
SUPP_KEYS = 1 << 12            # suppkey domain
SUPP_ROWS = 1 << 16            # duplicate-key dimension (Zipf keys)
NATION_ROWS = 256              # unique-key dimension small enough to broadcast
GLM_FEATURES = 8
GLM_EPOCHS = 2
MINIBATCH = 16                 # TrainGLM's minibatch
CACHE_BYTES = 256 << 20        # semantic cache budget of the served phases
# float32 SGD on the chip runs its dots at the TPU's default matmul
# precision (bfloat16 products, float32 sums), which moved weights,
# losses and scores by 2e-4 to 4e-4 on a v5e: the limits sit about 10x
# above that.  The grid's slow model (lr 1e-3) is still far from
# converged after GLM_EPOCHS, so a skipped epoch or half the rows moves
# its weights by ~0.19 and its loss by ~0.08, and a lost l2 term moves
# the l2 models' weights by ~3: each fails these limits
LOSS_RTOL = 3e-3
SCORE_ATOL = 4e-3
WEIGHT_RTOL = 3e-3             # relative to the weight vector's norm
INT32_MAX = 2 ** 31 - 1


class Mismatch(AssertionError):
    pass


def expect(name: str, got, want) -> None:
    if got != want:
        raise Mismatch(f"{name}: got {got!r}, want {want!r}")
    print(f"  ok {name} = {want!r}")


# --------------------------------------------------------------------------- #
# data (numpy, from the seed)

def make_data(rows: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    n_orders = max(rows // 16, 1024)                  # 2^23 at full size
    lineitem = {
        "orderkey": rng.integers(0, 2 * n_orders, rows, dtype=np.int32),
        "quantity": rng.integers(1, 51, rows, dtype=np.int32),
        "price": rng.integers(100, 10_000, rows, dtype=np.int32),
        "suppkey": rng.integers(0, SUPP_KEYS, rows, dtype=np.int32),
    }
    # even keys exist, odd ones do not: half the fact rows find an order
    orders = {
        "orderkey": rng.permutation(
            np.arange(0, 2 * n_orders, 2, dtype=np.int32)),
        "o_prio": rng.integers(1, 6, n_orders, dtype=np.int32),
    }
    supp = {
        "suppkey": ((rng.zipf(1.2, SUPP_ROWS) - 1) % SUPP_KEYS)
        .astype(np.int32),
        "s_w": rng.integers(0, 4, SUPP_ROWS, dtype=np.int32),
    }
    nation = {
        "suppkey": rng.choice(SUPP_KEYS, NATION_ROWS, replace=False)
        .astype(np.int32),
        "n_w": rng.integers(1, 10, NATION_ROWS, dtype=np.int32),
    }
    glm_rows = max(min(rows >> 7, 1 << 20), 4096)     # 2^20 at full size
    a = rng.uniform(-1, 1, (glm_rows, GLM_FEATURES)).astype(np.float32)
    w = rng.normal(size=GLM_FEATURES).astype(np.float32)
    noise = rng.normal(scale=0.5, size=glm_rows).astype(np.float32)
    glm = {f"f{i}": a[:, i].copy() for i in range(GLM_FEATURES)}
    glm["y"] = (a @ w + noise > 0).astype(np.float32)
    return {"lineitem": lineitem, "orders": orders, "supp": supp,
            "nation": nation, "glm": glm}


# --------------------------------------------------------------------------- #
# numpy references (independent of the engine)

def _fits(name: str, v: int) -> int:
    # sums accumulate in the column's int32: the smoke only asks
    # questions whose true answers fit it
    if not 0 <= v <= INT32_MAX:
        raise ValueError(f"smoke design error: {name} = {v} overflows int32")
    return v


def ref_filter_sum(li, fcol, lo, hi, acol) -> int:
    m = (li[fcol] >= lo) & (li[fcol] <= hi)
    return _fits("filter_sum", int(li[acol][m].sum(dtype=np.int64)))


def ref_filter_count(li, fcol, lo, hi) -> int:
    return int(((li[fcol] >= lo) & (li[fcol] <= hi)).sum())


def ref_unique_join_sum(li, dim, on, fcol, lo, hi, acol) -> int:
    """sum(dim.acol) over li JOIN dim USING (on) WHERE fcol in [lo, hi];
    ``dim.on`` is unique and ``dim.acol`` >= 1, so 0 marks no match."""
    lut = np.zeros(int(max(li[on].max(), dim[on].max())) + 1, np.int64)
    lut[dim[on]] = dim[acol]
    m = (li[fcol] >= lo) & (li[fcol] <= hi)
    return _fits("unique_join_sum", int(lut[li[on][m]].sum()))


def ref_dup_join(li, dim, on, fcol, lo, hi, acol) -> tuple:
    """(count, sum(dim.acol)) over the pair multiset of li JOIN dim."""
    cnt = np.bincount(dim[on], minlength=SUPP_KEYS).astype(np.int64)
    wsum = np.bincount(dim[on], weights=dim[acol],
                       minlength=SUPP_KEYS).astype(np.int64)
    k = li[on][(li[fcol] >= lo) & (li[fcol] <= hi)]
    return (_fits("dup_join_count", int(cnt[k].sum())),
            _fits("dup_join_sum", int(wsum[k].sum())))


def ref_join_rows(li, dim, on, fcol, lo, hi, lcol, dcol) -> tuple:
    """(li.lcol, dim.dcol) of every joined pair, sorted."""
    probe = np.flatnonzero((li[fcol] >= lo) & (li[fcol] <= hi))
    order = np.argsort(dim[on], kind="stable")
    skeys = dim[on][order]
    lk = li[on][probe]
    start = np.searchsorted(skeys, lk, "left")
    cnt = np.searchsorted(skeys, lk, "right") - start
    first = np.repeat(np.cumsum(cnt) - cnt, cnt)
    within = np.arange(int(cnt.sum())) - first
    s_rows = order[np.repeat(start, cnt) + within]
    return _sorted_rows(li[lcol][np.repeat(probe, cnt)], dim[dcol][s_rows])


def _sorted_rows(a, b) -> tuple:
    a, b = np.asarray(a), np.asarray(b)
    o = np.lexsort((b, a))
    return a[o], b[o]


def _sigmoid(z):
    return np.float32(1) / (np.float32(1) + np.exp(-z))


def ref_glm(a, b, grid, epochs: int):
    """float32 minibatch SGD (logistic regression), every model in grid
    order, in the engine's update order; returns (weights, losses)."""
    m, d = a.shape
    lr = np.array([g.lr for g in grid], np.float32)[:, None]
    l2 = np.array([g.l2 for g in grid], np.float32)[:, None]
    x = np.zeros((len(grid), d), np.float32)
    ab = a.reshape(-1, MINIBATCH, d)
    bb = b.reshape(-1, MINIBATCH, 1)
    inv = np.float32(1.0 / MINIBATCH)
    for _ in range(epochs):
        for ai, bi in zip(ab, bb):
            g = (_sigmoid(ai @ x.T) - bi).T @ ai * inv
            x = x - lr * (g + np.float32(2) * l2 * x)
    p = _sigmoid(a @ x.T)
    eps = np.float32(1e-7)
    j = -(b[:, None] * np.log(p + eps)
          + (1 - b[:, None]) * np.log(1 - p + eps))
    losses = j.mean(axis=0, dtype=np.float64) \
        + l2[:, 0] * np.square(x).sum(axis=1)
    return x, losses


# --------------------------------------------------------------------------- #
# reporting

class Phases:
    def __init__(self, device):
        self.device = device

    def peak_bytes(self):
        stats = self.device.memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"== phase {name}", flush=True)
        t0 = time.perf_counter()
        yield
        print(f"== phase {name} done: wall_s={time.perf_counter() - t0:.3f} "
              f"peak_bytes_in_use={self.peak_bytes()}", flush=True)


def _ready(value):
    import jax
    from repro.columnar.table import Table
    if isinstance(value, Table):
        jax.block_until_ready([c.data for c in value.columns.values()])
    else:
        jax.block_until_ready(value)
    return value


def timed(ex, q, label: str, *, warm: bool = True, explain: bool = True,
          **kw):
    """Execute ``q`` (twice with ``warm``: the first call compiles) and
    print its times and the planner's choices; returns the Result."""
    if explain:
        print(f"  explain {label}:")
        for line in ex.explain(q).splitlines():
            print(f"    {line}")
    t0 = time.perf_counter()
    res = ex.execute(q, **kw)
    _ready(res.value)
    first = time.perf_counter() - t0
    msg = f"  time {label}: first_s={first:.4f}"
    if warm:
        t0 = time.perf_counter()
        res = ex.execute(q, **kw)
        _ready(res.value)
        msg += f" warm_s={time.perf_counter() - t0:.4f}"
    print(msg, flush=True)
    return res


# --------------------------------------------------------------------------- #
# one chip: every phase

def one_chip(data: dict, ph: Phases) -> None:
    import jax
    from repro.columnar.table import Table
    from repro.core.sgd_glm import HyperParams
    from repro.launch.mesh import make_host_mesh
    from repro.query import (
        Catalog, CostModel, Executor, Q, QueryServer, SemanticCache,
    )
    li, orders, supp = data["lineitem"], data["orders"], data["supp"]
    nation = data["nation"]

    with ph.phase("load"):
        cat = Catalog.from_tables(
            *(Table.from_arrays(n, data[n])
              for n in ("lineitem", "orders", "supp", "nation", "glm")))
        # one explicit device, and the cost model's fixed constants: no
        # calibration file is read from the working directory
        ex = Executor(cat, mesh=make_host_mesh(jax.devices()[:1]),
                      cost_model=CostModel(1))
        print(f"  lineitem rows={len(li['price'])} "
              f"orders rows={len(orders['orderkey'])} "
              f"supp rows={len(supp['suppkey'])} "
              f"glm rows={len(data['glm']['y'])}")

    with ph.phase("filter_aggregate"):
        for lo, hi in ((1000, 2999), (5000, 6999)):
            q = Q.scan("lineitem").filter("price", lo, hi).sum("quantity")
            want = ref_filter_sum(li, "price", lo, hi, "quantity")
            for mode in ("batch", "stream"):
                r = timed(ex, q, f"sum[{lo},{hi}] {mode}", mode=mode,
                          explain=lo == 1000 and mode == "batch")
                expect(f"filter_sum[{lo},{hi}] {mode}", r.value, want)
        q = Q.scan("lineitem").filter("quantity", 10, 19).count("price")
        r = timed(ex, q, "count")
        expect("filter_count", r.value,
               ref_filter_count(li, "quantity", 10, 19))

    # the joins' probes (a binary search per fact row) take tens of
    # seconds each at this size, so every join query runs once
    with ph.phase("unique_join"):
        q = (Q.scan("lineitem").join(Q.scan("orders"), on="orderkey")
             .filter("quantity", 30, 49).sum("o_prio"))
        r = timed(ex, q, "fk_join", warm=False)
        expect("fk_join_sum", r.value, ref_unique_join_sum(
            li, orders, "orderkey", "quantity", 30, 49, "o_prio"))

    q_dup = (Q.scan("lineitem").join(Q.scan("supp"), on="suppkey")
             .filter("quantity", 1, 10))
    with ph.phase("duplicate_join"):
        cnt, total = ref_dup_join(li, supp, "suppkey", "quantity", 1, 10,
                                  "s_w")
        r = timed(ex, q_dup.count("s_w"), "dup_join count", warm=False)
        expect("dup_join_count", r.value, cnt)
        r = timed(ex, q_dup.sum("s_w"), "dup_join sum", warm=False)
        expect("dup_join_sum", r.value, total)

    with ph.phase("project"):
        q = Q.scan("lineitem").filter("price", 100, 199) \
            .project("orderkey", "quantity")
        t = timed(ex, q, "filter project").value
        m = (li["price"] >= 100) & (li["price"] <= 199)
        for c in ("orderkey", "quantity"):
            got = np.asarray(t.column(c))
            if not np.array_equal(got, li[c][m]):
                raise Mismatch(f"filter_project.{c}: {got.size} rows, "
                               f"want {int(m.sum())} in table order")
        print(f"  ok filter_project rows = {int(m.sum())}")
        q = (Q.scan("lineitem").filter("price", 100, 199)
             .join(Q.scan("nation"), on="suppkey")
             .project("orderkey", "n_w"))
        t = timed(ex, q, "join project", warm=False).value
        got = _sorted_rows(t.column("orderkey"), t.column("n_w"))
        want = ref_join_rows(li, nation, "suppkey", "price", 100, 199,
                             "orderkey", "n_w")
        if not all(np.array_equal(g, w) for g, w in zip(got, want)):
            raise Mismatch(f"join_project: {got[0].size} rows, want "
                           f"{want[0].size}")
        print(f"  ok join_project rows = {want[0].size}")

    # the served phases run with the semantic cache on
    ex.install_cache(SemanticCache(CACHE_BYTES, model=ex.cost_model))

    with ph.phase("glm"):
        glm = data["glm"]
        feats = [f"f{i}" for i in range(GLM_FEATURES)]
        grid = [HyperParams(0.1, 0.0), HyperParams(0.05, 1e-2),
                HyperParams(1e-3, 1e-3)]
        train = Q.scan("glm").train_glm(feats, "y", grid, epochs=GLM_EPOCHS)
        a = np.stack([glm[f] for f in feats], axis=1)
        t0 = time.perf_counter()
        x_ref, loss_ref = ref_glm(a, glm["y"], grid, GLM_EPOCHS)
        print(f"  numpy reference: {time.perf_counter() - t0:.3f}s")
        xs, losses = timed(ex, train, "train_glm", warm=False).value
        xs, losses = np.asarray(xs), np.asarray(losses)
        w_err = float(np.max(np.linalg.norm(xs - x_ref, axis=1)
                             / np.linalg.norm(x_ref, axis=1)))
        l_err = float(np.max(np.abs(losses - loss_ref) / loss_ref))
        print(f"  losses={losses.tolist()} reference={loss_ref.tolist()}")
        print(f"  weight rel err={w_err:.3e} (<= {WEIGHT_RTOL}) "
              f"loss rel err={l_err:.3e} (<= {LOSS_RTOL})")
        if not (w_err <= WEIGHT_RTOL and l_err <= LOSS_RTOL):
            raise Mismatch("train_glm weights/losses off the reference")
        hits = ex.model_hits
        score = Q.scan("glm").score_glm(train)
        s = np.asarray(timed(ex, score, "score_glm", warm=False)
                       .value.column("score"))
        expect("score_glm served from the cached model",
               ex.model_hits - hits, 1)
        best = int(np.argmin(loss_ref))
        s_err = float(np.max(np.abs(s - _sigmoid(a @ x_ref[best]))))
        print(f"  score max abs err={s_err:.3e} (<= {SCORE_ATOL})")
        if not s_err <= SCORE_ATOL:
            raise Mismatch("score_glm off the reference")

    with ph.phase("serve"):
        srv = QueryServer(ex)
        ranges = ((1000, 2999), (3000, 4999), (5000, 6999), (7000, 8999))
        sums = [Q.scan("lineitem").filter("price", lo, hi).sum("quantity")
                for lo, hi in ranges]
        q_cnt = Q.scan("lineitem").filter("quantity", 10, 19).count("price")
        q_dsum = q_dup.sum("s_w")
        batch = sums + [sums[0], q_dsum, q_cnt, q_dsum]
        want = [ref_filter_sum(li, "price", lo, hi, "quantity")
                for lo, hi in ranges]
        want += [want[0],
                 ref_dup_join(li, supp, "suppkey", "quantity", 1, 10,
                              "s_w")[1],
                 ref_filter_count(li, "quantity", 10, 19)]
        want.append(want[5])

        def serve(qs, label):
            t0 = time.perf_counter()
            qids = [srv.submit(q) for q in qs]
            out = srv.drain()
            print(f"  drain {label}: {len(qs)} queries in "
                  f"{time.perf_counter() - t0:.4f}s", flush=True)
            recs = {r.qid: r for r in srv.history}
            return [out[i] for i in qids], [recs[i].path for i in qids]

        got, _ = serve(batch, "cold")
        for i, (g, w) in enumerate(zip(got, want)):
            expect(f"serve[{i}]", g, w)
        st = srv.stats()
        print(f"  stats: deduped={st['n_deduped']} "
              f"microbatched={st['n_microbatched']} cached={st['n_cached']}")
        if st["n_deduped"] < 2:
            raise Mismatch(f"expected 2 deduplicated queries, got "
                           f"{st['n_deduped']}")
        got, paths = serve([q_dsum, q_cnt], "warm")
        expect("serve warm results", got, [want[5], want[6]])
        expect("serve warm paths", paths, ["cached"] * 2)
        # a dimension update invalidates what read it, and only that
        rng = np.random.default_rng(len(li["price"]))
        supp["s_w"] = rng.integers(0, 4, len(supp["s_w"]), dtype=np.int32)
        t0 = time.perf_counter()
        cat.update_column("supp", "s_w", supp["s_w"])
        print(f"  update_column supp.s_w: {time.perf_counter() - t0:.3f}s")
        got, paths = serve([q_dsum, q_cnt], "after update")
        expect("serve after update", got, [
            ref_dup_join(li, supp, "suppkey", "quantity", 1, 10, "s_w")[1],
            want[6]])
        if paths[0] == "cached" or paths[1] != "cached":
            raise Mismatch(f"after update: paths {paths}, want the join on "
                           "supp recomputed and the lineitem count cached")
        print(f"  ok after update: paths {paths}")


# --------------------------------------------------------------------------- #
# four chips: the sharded path against one device and numpy

def four_chips(data: dict, ph: Phases, n: int) -> None:
    import jax
    from repro.columnar.table import Table
    from repro.launch.mesh import make_host_mesh
    from repro.query import Catalog, CostModel, Executor, Q, column_placements
    li, supp, nation = data["lineitem"], data["supp"], data["nation"]

    with ph.phase("load"):
        cat = Catalog.from_tables(
            *(Table.from_arrays(t, data[t])
              for t in ("lineitem", "supp", "nation")))
        ex1 = Executor(cat, mesh=make_host_mesh(jax.devices()[:1]),
                       cost_model=CostModel(1))
        exn = Executor(cat, shards=n)

    def both(q, mode, want, label, warm=True):
        for name, ex in ((f"{n} shards", exn), ("1 device", ex1)):
            r = timed(ex, q, f"{label} {mode} {name}", mode=mode,
                      warm=warm, explain=ex is exn)
            expect(f"{label} {mode} {name}", r.value, want)

    with ph.phase("sharded_filter_aggregate"):
        q = Q.scan("lineitem").filter("price", 1000, 2999).sum("quantity")
        want = ref_filter_sum(li, "price", 1000, 2999, "quantity")
        for mode in ("batch", "stream"):
            both(q, mode, want, "filter_sum")
        _, phys = exn.plan(q.node)
        for (t, c), p in sorted(column_placements(phys).items()):
            if t != "lineitem" or c == "*":
                continue
            devs = exn.placed(t, c, p).sharding.device_set
            expect(f"lineitem.{c} ({p}) spans devices", len(devs), n)

    strategies = {}
    for dim, acol, label in (("nation", "n_w", "small_build_join"),
                             ("supp", "s_w", "large_build_join")):
        with ph.phase(f"sharded_{label}"):
            q = (Q.scan("lineitem").join(Q.scan(dim), on="suppkey")
                 .filter("price", 100, 199).sum(acol))
            want = ref_dup_join(li, data[dim], "suppkey", "price", 100, 199,
                                acol)[1]
            strategies[label] = set(
                re.findall(r"strategy=(\w+)", exn.explain(q)))
            print(f"  strategies {label}: {sorted(strategies[label])}")
            # the fused step broadcasts every build: it runs the small
            # build's planned strategy.  The shuffle runs only in the
            # eager lowering, which repartitions both sides; it has the
            # one-device batch answer above to match: one call, sharded
            both(q, "batch", want, label, warm=False)
            if "shuffle" in strategies[label]:
                r = timed(exn, q, f"{label} eager {n} shards",
                          mode="eager", warm=False, explain=False)
                expect(f"{label} eager {n} shards", r.value, want)
    expect("small build strategy", strategies["small_build_join"],
           {"broadcast"})
    expect("large build strategy", strategies["large_build_join"],
           {"shuffle"})


# --------------------------------------------------------------------------- #

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path, on four chips")
    ap.add_argument("--rows", type=int, default=None,
                    help="lineitem rows (default 2^27); a smaller size "
                         "is a rehearsal and prints no result")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and args.rows is None:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "pass --rows N to rehearse the phases at N rows",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    rows = args.rows or FULL_ROWS
    print(f"device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__} rows={rows} "
          f"seed={args.seed} compile_cache={use_compile_cache()}",
          flush=True)

    ph = Phases(dev)
    with ph.phase("generate"):
        data = make_data(rows, args.seed)
    if args.chips == 4:
        four_chips(data, ph, args.chips)
    else:
        one_chip(data, ph)

    if dev.platform != "tpu" or rows < FULL_ROWS:
        print(f"chip_smoke: every phase matched, but on {dev.platform!r} at "
              f"{rows} rows this is a rehearsal: no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
