"""Morsel-pipeline benchmark -> BENCH_pipeline.json.

Measures the three acceptance points of the streaming execution path:

  * **streamed vs eager throughput** — the same fused join+filter+sum
    query through the whole-column (batch) path and the morsel-driven
    pipeline, plus a morsel-size sweep; in-memory streaming must sit
    within ~10% of the batch path (morsel placements are cached, so the
    only delta is per-morsel dispatch).
  * **serve latency under concurrent load** — submit-to-result sojourn
    percentiles for a trickle of join queries (legacy micro-batching
    cannot batch these) through the admission-batch server vs the
    incremental pipeline drain, whose members share one scan and run as
    vmapped groups, joining mid-flight.
  * **larger-than-placement execution** — with a placement capacity
    below the probe table's size the eager paths must refuse
    (PlacementCapacityError) while morsel streaming completes.

    PYTHONPATH=src python benchmarks/bench_pipeline.py [--smoke]
"""
from __future__ import annotations

import json
import sys
import time
import warnings


def _timeit(fn, iters: int = 5, repeats: int = 3) -> float:
    fn()                               # warmup (compile)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e6                                    # us


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    return sorted_vals[int(q * (len(sorted_vals) - 1))]


def main(out_path: str = "BENCH_pipeline.json", *, n_rows: int = 1 << 17,
         smoke: bool = False) -> dict:
    sys.path.insert(0, "src")
    import numpy as np
    from repro.columnar.table import Table
    from repro.query import (
        Catalog, Executor, PlacementCapacityError, Q, QueryServer,
    )

    if smoke:
        n_rows = 1 << 14
    rng = np.random.default_rng(0)
    lineitem = Table.from_arrays("lineitem", {
        "orderkey": rng.integers(0, 40_000, size=n_rows).astype(np.int32),
        "quantity": rng.integers(1, 50, size=n_rows).astype(np.int32),
        "price": rng.integers(100, 10_000, size=n_rows).astype(np.int32),
    })
    orders = Table.from_arrays("orders", {
        "orderkey": np.asarray(rng.choice(40_000, size=4096, replace=False),
                               np.int32)})
    # 4x the fact table: the serving workload's heavy scans stream this
    history = Table.from_arrays("history", {
        "orderkey": rng.integers(0, 40_000,
                                 size=4 * n_rows).astype(np.int32),
        "quantity": rng.integers(1, 50, size=4 * n_rows).astype(np.int32),
        "price": rng.integers(100, 10_000,
                              size=4 * n_rows).astype(np.int32),
    })
    catalog = Catalog.from_tables(lineitem, orders, history)
    report: dict = {"n_rows": n_rows}

    def make_executor(**kw):
        return Executor(catalog, **kw)

    # --- streamed vs eager throughput + morsel sweep ------------------------
    ex = make_executor()
    q = (Q.scan("lineitem").join(Q.scan("orders"), on="orderkey")
          .filter("quantity", 40, 49).sum("price"))
    v_batch = ex.execute(q).value
    # eager and default-streamed interleave in one block: on a shared CPU
    # host, block-to-block frequency drift otherwise dwarfs the delta
    run_batch = lambda: ex.execute(q).value                  # noqa: E731
    run_stream = lambda: ex.execute(q, mode="stream").value  # noqa: E731
    run_batch(), run_stream()                                # warm both
    batch_us, default_us = float("inf"), float("inf")
    for _ in range(8):
        t0 = time.perf_counter()
        for _ in range(5):
            run_batch()
        batch_us = min(batch_us, (time.perf_counter() - t0) / 5 * 1e6)
        t0 = time.perf_counter()
        for _ in range(5):
            run_stream()
        default_us = min(default_us, (time.perf_counter() - t0) / 5 * 1e6)
    sweep = {}
    for frac in (16, 4, 1):
        mr = max(n_rows // frac, 1024)
        us = _timeit(lambda: ex.execute(q, mode="stream",
                                        morsel_rows=mr).value)
        sweep[str(mr)] = round(us, 1)
        assert int(ex.execute(q, mode="stream",
                              morsel_rows=mr).value) == int(v_batch)
    assert int(ex.execute(q, mode="stream").value) == int(v_batch)
    best_us = min(list(sweep.values()) + [default_us])
    report["throughput"] = {
        "eager_us": round(batch_us, 1),
        "streamed_default_us": round(default_us, 1),
        "streamed_best_us": round(best_us, 1),
        # the acceptance ratio: cost-model-chosen granularity vs eager,
        # measured interleaved (the sweep sizes bypass the morsel cache,
        # so they carry per-run slicing costs the default does not)
        "streamed_vs_eager": round(batch_us / default_us, 3),
        # the granularity the executor actually streams at by default
        # (in-memory: transfer-free pricing; phys.morsel_rows keeps the
        # out-of-core posture)
        "default_morsel_rows": ex.morsel_spec("lineitem", None,
                                              n_cols=3).rows,
        "out_of_core_morsel_rows":
            ex.execute(q, mode="stream").physical.morsel_rows,
        "rows_per_s_streamed": round(n_rows / (best_us * 1e-6)),
    }
    report["morsel_sweep_us"] = sweep

    # --- serve sojourn percentiles: admission batches vs pipeline drain -----
    # Heterogeneous concurrent load: every wave admits one HEAVY query (a
    # full scan-join over the 4x ``history`` table) ahead of many light
    # join queries with per-query bounds.  The admission-batch server
    # executes singles sequentially, so every light query queues behind
    # the heavy scan (head-of-line blocking) and nothing surfaces until
    # its drain returns; the pipeline drain interleaves both tables'
    # morsel streams — lights complete their own short circles (as ONE
    # vmapped step group per morsel) while the heavy scan is still
    # streaming.
    n_waves, wave = (4, 16) if smoke else (8, 32)
    bounds = [(int(lo), int(lo) + 5) for lo in
              rng.integers(1, 40, size=n_waves * wave)]

    def light(lo, hi):
        return (Q.scan("lineitem").join(Q.scan("orders"), on="orderkey")
                 .filter("quantity", lo, hi).sum("price"))

    def heavy(lo):
        return (Q.scan("history").join(Q.scan("orders"), on="orderkey")
                 .filter("quantity", lo, 49).sum("price"))

    def serve_workload(streaming: bool) -> dict:
        srv = QueryServer(make_executor(), streaming=streaming,
                          morsel_rows=n_rows // 8)

        def run_round() -> dict:
            submit_t, complete_t, lights = {}, {}, set()
            t0 = time.perf_counter()
            it = iter(bounds)
            for w in range(n_waves):
                qid = srv.submit(heavy(1 + w))      # heavy admitted first
                submit_t[qid] = time.perf_counter()
                for _ in range(wave - 1):
                    lo, hi = next(it)
                    qid = srv.submit(light(lo, hi))
                    submit_t[qid] = time.perf_counter()
                    lights.add(qid)
                # the server's continuous loop: a few increments between
                # arrival waves (streaming members progress morsel by
                # morsel; the batch server drains whole admission sets)
                for _ in range(8 if streaming else 1):
                    done = srv.pump() if streaming else srv.drain()
                    now = time.perf_counter()
                    for q_ in done:
                        complete_t[q_] = now
            while len(complete_t) < len(submit_t):
                done = srv.pump() if streaming else srv.drain()
                now = time.perf_counter()
                for q_ in done:
                    complete_t[q_] = now
            wall = time.perf_counter() - t0
            soj = sorted(complete_t[q_] - submit_t[q_] for q_ in submit_t)
            soj_l = sorted(complete_t[q_] - submit_t[q_] for q_ in lights)
            return {
                "wall_ms": round(wall * 1e3, 2),
                "queries_per_s": round(len(soj) / wall, 1),
                "sojourn_p50_ms": round(_percentile(soj, 0.50) * 1e3, 2),
                "sojourn_p95_ms": round(_percentile(soj, 0.95) * 1e3, 2),
                "sojourn_max_ms": round(soj[-1] * 1e3, 2) if soj else 0.0,
                "light_p50_ms": round(_percentile(soj_l, 0.50) * 1e3, 2),
            }

        run_round()                      # warm round: compiles + caches
        return run_round()

    batch_serve = serve_workload(streaming=False)
    stream_serve = serve_workload(streaming=True)
    report["serving"] = {
        "queries": n_waves * wave,
        "admission_batch": batch_serve,
        "pipeline_drain": stream_serve,
        "p50_improvement_x": round(
            batch_serve["sojourn_p50_ms"]
            / max(stream_serve["sojourn_p50_ms"], 1e-6), 2),
    }

    # --- larger than one placement: stream-only execution -------------------
    cap = lineitem.column("orderkey").nbytes // 4       # a quarter-table
    ex_cap = make_executor(placement_capacity_bytes=cap)
    # the optimized batch path now spills instead of refusing (PR 9) —
    # probe the refusal on the forced-eager path, which stays gated
    eager_refused = False
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            ex_cap.execute(q, mode="eager").value
    except PlacementCapacityError:
        eager_refused = True
    # 3 streamed columns; floor-aligned to the engine count so the
    # spec's round-UP alignment cannot push one morsel over the capacity
    n_eng = ex_cap.plans["partitioned"].n_engines
    morsel_rows = max((cap // (4 * 3)) // n_eng * n_eng, n_eng)
    v_oop = ex_cap.execute(q, mode="stream",
                           morsel_rows=morsel_rows).value   # compile
    t0 = time.perf_counter()
    v_oop = ex_cap.execute(q, mode="stream", morsel_rows=morsel_rows).value
    oop_s = time.perf_counter() - t0
    assert int(v_oop) == int(v_batch), (v_oop, v_batch)
    report["out_of_placement"] = {
        "capacity_bytes": int(cap),
        "column_bytes": int(lineitem.column("orderkey").nbytes),
        "eager_refused": eager_refused,
        "streamed_ok": True,
        "streamed_ms": round(oop_s * 1e3, 2),
    }

    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(smoke="--smoke" in sys.argv)
