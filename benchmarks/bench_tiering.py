"""Tiered-placement benchmark -> BENCH_tiering.json.

Measures (and HARD-GATES) the three acceptance points of the device <->
host <-> disk hierarchy (PR 9):

  * **over-capacity sweep** — the same join+filter+sum query with the
    working set at 1x/2x/4x/8x of the device placement budget: 1x runs
    in place, everything above reroutes through the cost-priced spill
    plan and must stay bit-identical to the unconstrained single-tier
    oracle.  Gate (a): the 4x point completes via spill with slowdown
    <= 3x against in-placement streamed execution.
  * **cold vs warm restart** — a serve lifetime (fresh catalog,
    Executor and QueryServer) runs the workload and snapshots its
    semantic cache + calibration; a second lifetime starts from the
    snapshot and replays the same workload.  Both run in this process:
    a child would need the accelerator this process holds.  Gate (b):
    warm p50 sojourn >= 5x lower than cold.
  * **demote vs evict** — the same thrashing key cycle against an
    evict-only cache and a demoting cache with the SAME device budget
    (the host tier is otherwise-free DRAM).  Gate (c): the demoting
    cache's hit rate is strictly higher.

    PYTHONPATH=src python benchmarks/bench_tiering.py [--smoke]
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_SERVE_QUERIES = 12


def _timeit(fn, iters: int = 3, repeats: int = 3) -> float:
    fn()                               # warmup (compile)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3                                    # ms


def _make_catalog(n_rows: int):
    import numpy as np
    from repro.columnar.table import Table
    from repro.query import Catalog
    rng = np.random.default_rng(0)
    lineitem = Table.from_arrays("lineitem", {
        "orderkey": rng.integers(0, 40_000, size=n_rows).astype(np.int32),
        "quantity": rng.integers(1, 50, size=n_rows).astype(np.int32),
        "price": rng.integers(100, 10_000, size=n_rows).astype(np.int32),
    })
    # the dimension table stays small: build/replicated columns must be
    # device-resident (only STREAM columns spill), so the sweep's 8x
    # point still needs the build side inside the device budget
    orders = Table.from_arrays("orders", {
        "orderkey": np.asarray(rng.choice(40_000, size=512,
                                          replace=False), np.int32)})
    return Catalog.from_tables(lineitem, orders)


def _serve_queries():
    """A replayed dashboard workload: every query joins (the expensive
    cold-path recompute a warm-started result cache skips entirely)."""
    from repro.query import Q
    qs = []
    for i in range(N_SERVE_QUERIES):
        lo = 5 + 3 * i
        qs.append(Q.scan("lineitem").join(Q.scan("orders"), on="orderkey")
                  .filter("quantity", lo, lo + 20).sum("price"))
    qs.append(Q.scan("lineitem").join(Q.scan("orders"), on="orderkey")
              .filter("quantity", 10, 40).sum("price"))
    return qs


def _percentile(vals, q):
    s = sorted(vals)
    if not s:
        return 0.0
    return s[int(q * (len(s) - 1))]


def serve_phase(phase: str, persist_path: str, n_rows: int) -> dict:
    """One serve lifetime: build the SAME deterministic catalog, serve
    the replay workload, snapshot on the cold phase.  Every lifetime
    builds its own executor, so the warm phase compiles its plans anew
    and starts with an empty device cache."""
    from repro.query import Executor, QueryServer, SemanticCache
    cat = _make_catalog(n_rows)
    srv = QueryServer(
        Executor(cat), persist_path=persist_path,
        semantic_cache=SemanticCache(64 << 20,
                                     host_budget_bytes=256 << 20))
    for q in _serve_queries():
        srv.submit(q)
        srv.drain()                    # per-query sojourn, no batch fuse
    p50_ms = _percentile([r.latency_s for r in srv.history], 0.5) * 1e3
    if phase == "cold":
        srv.save_state()
    return {"phase": phase, "p50_ms": p50_ms,
            "n_queries": len(srv.history),
            "cache_hits": srv.executor.cache.hits,
            "restored": (srv.warm_started or {}).get("restored", 0)}


def main(out_path: str = "BENCH_tiering.json", *, n_rows: int = 1 << 17,
         smoke: bool = False) -> dict:
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    import numpy as np
    from repro.query import (
        Catalog, Executor, Q, SemanticCache, TierBudgets,
    )

    if smoke:
        n_rows = 1 << 14
    report: dict = {"n_rows": n_rows, "smoke": smoke}

    q = (Q.scan("lineitem").join(Q.scan("orders"), on="orderkey")
          .filter("quantity", 10, 40).sum("price"))

    # one-tier oracle + the in-placement streamed baseline
    oracle_cat = _make_catalog(n_rows)
    col_bytes = int(oracle_cat.tables["lineitem"].columns["price"].nbytes)
    ex_oracle = Executor(oracle_cat)
    want = int(ex_oracle.execute(q).value)
    stream_ms = _timeit(
        lambda: ex_oracle.execute(q, mode="stream").value)
    report["oracle"] = {"value": want, "column_bytes": col_bytes,
                        "in_placement_stream_ms": round(stream_ms, 2)}

    # --- over-capacity sweep: working set at R x the device budget ----------
    sweep = []
    for ratio in (1, 2, 4, 8):
        cap = col_bytes // ratio
        cat = _make_catalog(n_rows)
        ex = Executor(cat, placement_capacity_bytes=cap)
        got = ex.execute(q)
        identical = int(got.value) == want
        ms = _timeit(lambda: ex.execute(q).value)
        st = ex.stats_dict()
        tiers = {c: cat.tables["lineitem"].column_tier(c)
                 for c in ("orderkey", "quantity", "price")}
        sweep.append({
            "over_capacity_x": ratio,
            "capacity_bytes": cap,
            "spilled": st["spilled_columns"] > 0,
            "identical": identical,
            "ms": round(ms, 2),
            "slowdown_vs_stream_x": round(ms / max(stream_ms, 1e-9), 2),
            "tiers": tiers,
            "promote_bytes_host": st["promote_bytes_host"],
            "promote_bytes_disk": st["promote_bytes_disk"],
        })
        assert identical, (ratio, int(got.value), want)
    assert not sweep[0]["spilled"], "1x must fit in place"
    assert all(s["spilled"] for s in sweep[1:]), "over-capacity must spill"
    report["sweep"] = sweep

    # gate (a): 4x over placement, spilled, bit-identical, <= 3x slower
    # than the in-placement streamed run
    g4 = next(s for s in sweep if s["over_capacity_x"] == 4)
    gate_a = {"identical": g4["identical"],
              "slowdown_vs_stream_x": g4["slowdown_vs_stream_x"],
              "pass": g4["identical"]
              and g4["slowdown_vs_stream_x"] <= 3.0}
    report["gate_a_spill_4x"] = gate_a
    assert gate_a["pass"], gate_a

    # --- gate (b): cold vs warm restart (two server lifetimes) --------------
    # fixed size, even at smoke scale: the gate compares recompute
    # against the fixed serve overhead a warm hit still pays (lookup +
    # admission + history bookkeeping), so the table must be big enough
    # that recompute dwarfs that overhead
    serve_rows = 1 << 17
    with tempfile.TemporaryDirectory() as td:
        snap = os.path.join(td, "server_state.npz")
        cold = serve_phase("cold", snap, serve_rows)
        assert os.path.exists(snap), "cold phase must leave a snapshot"
        warm = serve_phase("warm", snap, serve_rows)
    speedup = cold["p50_ms"] / max(warm["p50_ms"], 1e-9)
    gate_b = {"cold_p50_ms": round(cold["p50_ms"], 3),
              "warm_p50_ms": round(warm["p50_ms"], 3),
              "warm_restored_entries": warm["restored"],
              "warm_cache_hits": warm["cache_hits"],
              "speedup_x": round(speedup, 2),
              "pass": speedup >= 5.0 and warm["restored"] > 0}
    report["gate_b_warm_restart"] = gate_b
    assert gate_b["pass"], gate_b

    # --- gate (c): demote-instead-of-evict vs evict-only --------------------
    def thrash(cache):
        for _ in range(5):
            for i, k in enumerate(("k0", "k1", "k2")):
                if cache.get(k) is None:
                    cache.put(k, np.arange(200), kind="result",
                              n_bytes=800, recompute_s=float(i + 1))
        return cache.stats_dict()["semantic_cache_hit_rate"]

    evict_rate = thrash(SemanticCache(1000))
    demote_rate = thrash(SemanticCache(1000, host_budget_bytes=3000))
    gate_c = {"evict_only_hit_rate": round(evict_rate, 3),
              "demote_hit_rate": round(demote_rate, 3),
              "pass": demote_rate > evict_rate}
    report["gate_c_demote_vs_evict"] = gate_c
    assert gate_c["pass"], gate_c

    with open(os.path.join(_ROOT, out_path), "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    return report


def tiering_smoke():
    """run.py --smoke entry: hard-gates all three acceptance points at
    smoke scale; rows feed the CSV like every other figure."""
    r = main(smoke=True)
    g4 = r["gate_a_spill_4x"]
    gb = r["gate_b_warm_restart"]
    gc = r["gate_c_demote_vs_evict"]
    return [
        ("tiering_spill_4x", r["sweep"][2]["ms"] * 1e3,
         f"slowdown={g4['slowdown_vs_stream_x']}x identical="
         f"{g4['identical']}"),
        ("tiering_warm_restart", gb["warm_p50_ms"] * 1e3,
         f"speedup={gb['speedup_x']}x restored="
         f"{gb['warm_restored_entries']}"),
        ("tiering_demote_hit_rate", 0.0,
         f"demote={gc['demote_hit_rate']} evict="
         f"{gc['evict_only_hit_rate']}"),
    ]


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(smoke="--smoke" in sys.argv)
