"""Semantic-cache benchmark -> BENCH_cache.json.

Measures the acceptance points of the result/subplan caching subsystem
on a Zipf-repeated analytics workload (the repeated-dashboard shape the
ROADMAP's many-user north star implies):

  * **hit-rate sweep** — result-cache hit rate vs the Zipf skew of the
    template distribution (hot templates repeat; the tail stays cold).
  * **warm vs cold latency, cached vs disabled throughput** — the same
    workload served three ways: cold cache (admission misses), warm
    cache (fingerprint hits skip execution), and cache-disabled (the
    plan/compile cache still applies, so the delta is result reuse, not
    compilation reuse).  Acceptance: warm >= 3x disabled throughput.
  * **eviction pressure** — a budget far below the working set must
    degrade toward recomputation smoothly (correct answers, bounded
    bytes), not thrash or fail.
  * **mutation differential** — a base-table mutation mid-workload must
    produce results bit-identical to cache-disabled execution.
  * **subsumption sweep** — a narrowing range ladder over one hot
    column: every rung after the first must be served by REFINING the
    previous rung's bitmap (subsumption hit), streaming bitmap bytes
    instead of base-column bytes; refine latency vs recompute latency
    is reported, and refine is chosen only where ``refine_price`` wins.
  * **shared cache (2 executors)** — tenant A warms results and a
    superset bitmap, tenant B must hit/refine through the SAME
    ``SemanticCache``; a mutation by B must leave A bit-identical to
    cache-disabled execution.

    PYTHONPATH=src python benchmarks/bench_cache.py [--smoke]
"""
from __future__ import annotations

import json
import sys
import time


def main(out_path: str = "BENCH_cache.json", *, n_rows: int = 1 << 16,
         smoke: bool = False, write: bool = True) -> dict:
    sys.path.insert(0, "src")
    import numpy as np
    from repro.columnar.table import Table
    from repro.query import Catalog, Executor, Q, QueryServer, SemanticCache

    if smoke:
        n_rows = 1 << 13
    n_templates, n_queries = (12, 60) if smoke else (32, 300)
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
    report: dict = {"n_rows": n_rows, "n_templates": n_templates,
                    "n_queries": n_queries}

    def make_executor(cat=catalog, **kw):
        return Executor(cat, **kw)

    # distinct join+filter+aggregate templates (distinct bounds => distinct
    # fingerprints; one shared compilation since bounds are traced)
    ops = ("sum", "count", "mean")
    templates = [
        getattr(Q.scan("lineitem").join(Q.scan("orders"), on="orderkey")
                 .filter("quantity", 1 + i, 1 + i + 6), "aggregate")(
                     ops[i % 3], "price")
        for i in range(n_templates)]

    def zipf_workload(s: float):
        p = 1.0 / np.arange(1, n_templates + 1) ** s
        p /= p.sum()
        idx = rng.choice(n_templates, size=n_queries, p=p)
        return [templates[i] for i in idx]

    def serve(workload, ex) -> dict:
        """Sequential serving (one drain per query): intra-batch dedup
        cannot fold repeats, so every saved execution is the cache's."""
        srv = QueryServer(ex)
        lat = []
        t0 = time.perf_counter()
        for q in workload:
            t = time.perf_counter()
            srv.query(q)
            lat.append(time.perf_counter() - t)
        wall = time.perf_counter() - t0
        lat.sort()
        return {
            "wall_ms": round(wall * 1e3, 2),
            "queries_per_s": round(len(workload) / wall, 1),
            "latency_p50_us": round(lat[len(lat) // 2] * 1e6, 1),
            "latency_p95_us": round(lat[int(0.95 * (len(lat) - 1))] * 1e6,
                                    1),
            "n_cached": srv.n_cached,
        }

    # --- hit-rate sweep over Zipf skew --------------------------------------
    sweep = {}
    for s in (0.6, 1.0, 1.4):
        ex = make_executor(cache_bytes=64 << 20)
        wl = zipf_workload(s)
        for q in wl:                      # compile + admit (cold)
            ex.execute(q)
        stats = ex.stats_dict()
        sweep[str(s)] = {
            "result_hit_rate": round(
                stats["result_cache_hits"] / len(wl), 3),
            "semantic_hit_rate": round(
                stats["semantic_cache_hit_rate"], 3),
            "entries": stats["semantic_cache_entries"],
            "used_bytes": stats["semantic_cache_used_bytes"],
        }
    report["zipf_hit_rate_sweep"] = sweep

    # --- warm vs cold vs disabled -------------------------------------------
    workload = zipf_workload(1.2)
    ex_cached = make_executor(cache_bytes=64 << 20)
    cold = serve(workload, ex_cached)
    warm = serve(workload, ex_cached)
    ex_plain = make_executor()
    serve(workload, ex_plain)             # warm its compile cache
    disabled = serve(workload, ex_plain)
    # differential: every template answer matches the disabled executor
    mismatches = sum(
        1 for q in templates
        if ex_cached.execute(q).value != ex_plain.execute(q).value)
    speedup = warm["queries_per_s"] / max(disabled["queries_per_s"], 1e-9)
    report["serving"] = {
        "cold": cold,
        "warm": warm,
        "disabled": disabled,
        "warm_vs_disabled_x": round(speedup, 2),
        "warm_vs_cold_x": round(
            warm["queries_per_s"] / max(cold["queries_per_s"], 1e-9), 2),
        "value_mismatches": mismatches,
        "meets_3x_acceptance": bool(speedup >= 3.0),
    }

    # --- eviction pressure ---------------------------------------------------
    # materializing (Project-rooted) queries under a budget far below the
    # working set: answers stay exact while the cache churns
    proj_templates = [
        Q.scan("lineitem").filter("quantity", 1 + i, 1 + i + 4)
         .project("orderkey", "price")
        for i in range(8)]
    ex_tight = make_executor(cache_bytes=64 << 10)      # 64 KiB
    t0 = time.perf_counter()
    reps = 2 if smoke else 4
    for _ in range(reps):
        for q in proj_templates:
            ex_tight.execute(q)
    tight_wall = time.perf_counter() - t0
    stats = ex_tight.stats_dict()
    report["eviction_pressure"] = {
        "budget_bytes": 64 << 10,
        "queries": reps * len(proj_templates),
        "queries_per_s": round(reps * len(proj_templates) / tight_wall, 1),
        "used_bytes": stats["semantic_cache_used_bytes"],
        "evicted": stats["semantic_cache_evicted"],
        "rejected": stats["semantic_cache_rejected"],
        "within_budget": stats["semantic_cache_used_bytes"] <= (64 << 10),
    }

    # --- mutation invalidation differential ----------------------------------
    q = templates[0]
    stale = ex_cached.execute(q).value
    catalog.update_column(
        "lineitem", "price",
        rng.integers(100, 10_000, size=n_rows).astype(np.int32))
    after_cached = ex_cached.execute(q)
    after_plain = make_executor().execute(q).value
    report["mutation_differential"] = {
        "served_stale": bool(after_cached.result_cache_hit),
        "post_mutation_identical_to_disabled":
            after_cached.value == after_plain,
        "value_changed": after_cached.value != stale,
        "invalidated_entries": ex_cached.cache.invalidated,
    }

    # --- predicate subsumption: narrowing range ladder -----------------------
    # each rung halves the previous width (same lo), so the tightest
    # cached superset is always the previous rung: N-1 refinements, each
    # streaming ~3x the parent bitmap instead of the full base column
    cat2 = Catalog.from_tables(lineitem, orders)
    widths = [2800, 1400, 700, 350, 175] if smoke \
        else [2800, 1400, 700, 350, 175, 87, 43]
    ladder = [Q.scan("lineitem").filter("price", 100, 100 + w)
               .project("orderkey", "quantity") for w in widths]
    ex_sub = make_executor(cat2, cache_bytes=64 << 20)
    ex_plain2 = make_executor(cat2)
    for q in ladder:                      # warm compile caches (both)
        ex_plain2.execute(q)
    t0 = time.perf_counter()
    for q in ladder:
        ex_plain2.execute(q)
    t_recompute = time.perf_counter() - t0
    ex_sub.execute(ladder[0])             # seed the widest bitmap
    t0 = time.perf_counter()
    for q in ladder[1:]:
        ex_sub.execute(q)
    t_refine = time.perf_counter() - t0
    n_refines = len(ladder) - 1
    refine_speedup = (t_recompute * n_refines / len(ladder)) \
        / max(t_refine, 1e-9)
    report["subsumption"] = {
        "ladder_widths": widths,
        "subsumption_hits": ex_sub.subsumption_hits,
        "subsumption_hit_rate": round(
            ex_sub.subsumption_hits / n_refines, 3),
        "refine_wall_ms": round(t_refine * 1e3, 2),
        "recompute_wall_ms": round(t_recompute * 1e3, 2),
        "refine_vs_recompute_speedup": round(refine_speedup, 2),
        "bitmap_bytes_streamed": ex_sub.refine_bytes_streamed,
        "column_bytes_avoided": ex_sub.refine_bytes_avoided,
        "bytes_moved_ratio": round(
            ex_sub.refine_bytes_streamed
            / max(ex_sub.refine_bytes_avoided, 1), 4),
        "refine_only_when_priced": bool(
            ex_sub.subsumption_hits == n_refines),
    }

    # --- shared cache: two executors, one budget -----------------------------
    shared = SemanticCache(64 << 20, model=ex_sub.cost_model)
    ex_a = make_executor(cat2, semantic_cache=shared)
    ex_b = make_executor(cat2, semantic_cache=shared)
    shared_templates = templates[:8]
    for q in shared_templates:            # tenant A warms
        ex_a.execute(q)
    t0 = time.perf_counter()
    for q in shared_templates:            # tenant B must hit
        ex_b.execute(q)
    t_b = time.perf_counter() - t0
    ex_a.execute(ladder[0])               # A's superset bitmap...
    ex_b.execute(ladder[1])               # ...refines B's narrower range
    cross_hits = ex_b.result_hits
    # mutation by B: A's next read differential vs cache-disabled
    cat2.update_column(
        "lineitem", "quantity",
        rng.integers(1, 50, size=n_rows).astype(np.int32))
    a_after = ex_a.execute(shared_templates[0])
    plain_after = make_executor(cat2).execute(shared_templates[0]).value
    report["shared_cache"] = {
        "templates": len(shared_templates),
        "cross_executor_hits": cross_hits,
        "cross_executor_hit_rate": round(
            cross_hits / len(shared_templates), 3),
        "tenant_b_wall_ms": round(t_b * 1e3, 2),
        "cross_executor_subsumption_hits": ex_b.subsumption_hits,
        "post_mutation_identical_to_disabled":
            a_after.value == plain_after,
        "post_mutation_served_stale": bool(a_after.result_cache_hit),
        "shared_invalidated_entries": shared.invalidated,
    }

    if write:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    return report


def cache_figures():
    """run.py hook: (name, us_per_call, derived) rows, always FULL scale —
    run.py's --smoke mode skips this hook (CI smoke coverage comes from
    ``bench_cache.py --smoke`` directly), so the committed
    BENCH_cache.json is never clobbered with smoke data."""
    rep = main()
    s = rep["serving"]
    rows = [
        ("cache_warm_serving", 1e6 / max(s["warm"]["queries_per_s"], 1e-9),
         f"{s['warm_vs_disabled_x']}x_vs_disabled,"
         f"p50={s['warm']['latency_p50_us']}us"),
        ("cache_disabled_serving",
         1e6 / max(s["disabled"]["queries_per_s"], 1e-9),
         f"{s['disabled']['queries_per_s']}q/s"),
    ]
    for skew, r in rep["zipf_hit_rate_sweep"].items():
        rows.append((f"cache_hit_rate_zipf_{skew}", 0.0,
                     f"hit_rate={r['result_hit_rate']}"))
    m = rep["mutation_differential"]
    rows.append(("cache_mutation_differential", 0.0,
                 f"identical={m['post_mutation_identical_to_disabled']},"
                 f"stale_served={m['served_stale']}"))
    rows.extend(_subsumption_rows(rep))
    return rows


def _subsumption_rows(rep):
    s = rep["subsumption"]
    sh = rep["shared_cache"]
    return [
        ("cache_subsumption_ladder", 0.0,
         f"hit_rate={s['subsumption_hit_rate']},"
         f"refine_speedup={s['refine_vs_recompute_speedup']}x,"
         f"bytes_ratio={s['bytes_moved_ratio']}"),
        ("cache_shared_two_executors", 0.0,
         f"cross_hit_rate={sh['cross_executor_hit_rate']},"
         f"mutation_identical="
         f"{sh['post_mutation_identical_to_disabled']}"),
    ]


def subsumption_smoke():
    """run.py --smoke hook: the subsumption sweep + shared-cache
    scenario at smoke scale.  Never writes BENCH_cache.json (the
    committed file stays full-scale; ``bench_cache.py --smoke`` is the
    CI entry point that does write its own)."""
    return _subsumption_rows(main(smoke=True, write=False))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(smoke="--smoke" in sys.argv)
