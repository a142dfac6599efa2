"""Query subsystem benchmark -> BENCH_query.json.

Measures the three things the subsystem exists for:
  * optimized vs naive plan speedup (predicate pushdown + fusion + jit vs
    executing the plan exactly as written, BAT-style),
  * plan-cache behaviour on repeated queries (hit rate, zero re-traces),
  * serving throughput at 1 / 8 / 64 concurrent clients (dedup +
    micro-batched selections).

    PYTHONPATH=src python benchmarks/bench_query.py
"""
from __future__ import annotations

import json
import sys
import time
import warnings


def _timeit(fn, iters: int = 3) -> float:
    fn()                               # warmup (compile)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6      # us


def main(out_path: str = "BENCH_query.json", *, n_rows: int = 1 << 17,
         smoke: bool = False) -> dict:
    sys.path.insert(0, "src")
    import numpy as np
    from repro.columnar.table import Table
    from repro.query import Catalog, Executor, Q, QueryServer
    from repro.query.exec import _walk_phys

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
    catalog = Catalog.from_tables(lineitem, orders)
    report: dict = {"n_rows": n_rows}

    # --- optimized vs naive: the plan is WRITTEN badly (filter above join) --
    ex = Executor(catalog)
    q = (Q.scan("lineitem").join(Q.scan("orders"), on="orderkey")
          .filter("quantity", 40, 49).sum("price"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        naive_us = _timeit(lambda: ex.execute(q, optimized=False).value)
        opt_us = _timeit(lambda: ex.execute(q).value)
        v_naive = ex.execute(q, optimized=False).value
    v_opt = ex.execute(q).value
    assert int(v_opt) == int(v_naive), (v_opt, v_naive)
    report["plan_speedup"] = {
        "naive_us": round(naive_us, 1),
        "optimized_us": round(opt_us, 1),
        "speedup_x": round(naive_us / opt_us, 2),
    }
    phys = ex.execute(q).physical
    report["decisions"] = [
        {"op": p.op, "impl": p.impl, "placement": p.placement,
         "passes": p.n_passes, "predicted_gbps": round(p.gbps, 1)}
        for p in _walk_phys(phys)]

    # --- plan cache over repeated queries with varying constants ------------
    ex2 = Executor(catalog)
    n_rep = 5 if smoke else 20
    for i in range(n_rep):
        lo = int(rng.integers(1, 40))
        ex2.execute(Q.scan("lineitem").filter("quantity", lo, lo + 9)
                     .sum("price"))
    s = ex2.stats_dict()
    report["plan_cache"] = {
        "queries": n_rep,
        "hits": s["plan_cache_hits"],
        "misses": s["plan_cache_misses"],
        "hit_rate": round(s["plan_cache_hit_rate"], 3),
        "trace_count": s["trace_count"],
    }

    # --- serving throughput at 1 / 8 / 64 concurrent clients ----------------
    report["serving"] = {}
    for clients in (1, 8, 64):
        srv = QueryServer(Executor(catalog))
        # one warmup drain so compile time doesn't hide the steady state
        for _ in range(2):
            for c in range(clients):
                lo = int(rng.integers(1, 40))
                srv.submit(Q.scan("lineitem").filter("quantity", lo, lo + 4)
                            .sum("price"))
            t0 = time.perf_counter()
            srv.drain()
            wall = time.perf_counter() - t0
        st = srv.stats()
        report["serving"][str(clients)] = {
            "queries_per_s": round(clients / wall, 1),
            "drain_wall_ms": round(wall * 1e3, 2),
            "microbatched": st["n_microbatched"],
            "deduped": st["n_deduped"],
            "latency_mean_ms": round(st["latency_mean_s"] * 1e3, 2),
        }

    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(smoke="--smoke" in sys.argv)
