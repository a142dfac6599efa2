"""Multi-tenant QoS + adaptive-serving soak, written to ``BENCH_qos.json``.

Scenario: the "datasheet optimism" gap both HBM benchmarking studies
measure — the cost model starts from a DOCTORED calibration (near-ideal
stream efficiency, near-zero dispatch overhead), so it prices tiny
morsels as free and the streaming server grinds through dispatch
overhead.  Two servers serve the same two-tenant workload over it:

* **static**: no adaptive policy — the skewed model is never corrected;
* **adaptive**: ``AdaptivePolicy`` watches the serve-mode ledger rows
  (fenced per-morsel measurements), detects the drift, folds the
  measured overlay back via ``Executor.recost()``, and idle streams
  re-spec to the honestly-priced (much larger) morsel size.

Reported / gated:

(a) adaptive steady-state p95 sojourn (median across steady rounds of
    each round's p95 — robust to a single noisy round) beats static on
    the same rounds (the measure→re-cost→re-plan loop pays for itself);
(b) the high-priority tenant's steady-state p95 meets its SLO while the
    best-effort tenant absorbs the backpressure deferrals;
(c) applying the final overlay twice changes no price (idempotence);
(d) every result — static, adaptive, before/during/after the
    re-plan — is bit-identical to a cache-free oracle executor.

(c) and (d) are hard gates (nonzero exit); (a)/(b) are hard-gated at
full scale and reported at ``--smoke`` scale (CI boxes are too noisy to
gate tail latencies on).
"""
import json
import sys
import time

DOCTORED = {"backend": "doctored-datasheet", "backends": {
    "xla": {"stream_eff": 0.99, "call_overhead_s": 1e-9}}}


def _workload(i, span=31):
    """Distinct filter bounds per query: the result cache can never
    short-circuit, so every sojourn prices real streaming work."""
    from repro.query import Q
    lo = i % 96
    return Q.scan("t", ("v", "w")).filter("v", lo, lo + span).sum("w")


def _p95(xs):
    xs = sorted(xs)
    return xs[int(0.95 * (len(xs) - 1))] if xs else 0.0


def _soak(cat, *, adaptive, rounds, per_round, slo_box):
    """One server, ``rounds`` closed-loop admission rounds of
    ``per_round`` queries (alternating prio/bulk tenants).  Returns
    (per-round per-tenant latencies, server, executor, first round at
    which a recalibration had fired)."""
    import numpy as np  # noqa: F401  (kept: symmetric imports per soak)
    from repro.query import (AdaptivePolicy, Executor, QueryServer,
                             TenantSpec)
    from repro.query import telemetry as tm

    ex = Executor(cat, telemetry=tm.Telemetry(enabled=True))
    ex.cost_model.apply_calibration(DOCTORED)
    policy = AdaptivePolicy(drift_threshold=1.0, k_windows=2,
                            min_window_rows=4) if adaptive else None
    srv = QueryServer(ex, streaming=True, policy=policy)
    per_round_lat = []
    recal_round = None
    seen_recals = 0
    qid_node = {}
    for rnd in range(rounds):
        if rnd == 1 and slo_box[0] is not None:
            # SLO derived from the skewed round 0: the recalibrated
            # server should beat it easily, the static one should not
            srv.register_tenant(TenantSpec(
                "prio", priority=10, slo_p95_s=slo_box[0],
                cache_share=2.0))
            srv.register_tenant(TenantSpec("bulk", priority=0,
                                           cache_share=1.0))
        h0 = len(srv.history)
        for j in range(per_round):
            tenant = "prio" if j % 2 == 0 else "bulk"
            qid = srv.submit(_workload(rnd * per_round + j),
                             tenant=tenant, deadline_s=5.0)
            qid_node[qid] = _workload(rnd * per_round + j)
        srv.drain()
        lat = {"prio": [], "bulk": []}
        for rec in srv.history[h0:]:
            lat.setdefault(rec.tenant, []).append(rec.latency_s)
        per_round_lat.append(lat)
        if srv.n_recalibrations > seen_recals:
            # LAST round that recalibrated: the steady-state window must
            # exclude every warmup recost (evidence measured while the
            # previous epoch's pipelines were still compiling)
            recal_round = rnd
            seen_recals = srv.n_recalibrations
        if rnd == 0 and slo_box[0] is None:
            slo_box[0] = _p95([r.latency_s for r in srv.history]) / 3.0
    results = {qid: rec.result
               for rec in srv.history for qid in [rec.qid]}
    return per_round_lat, srv, ex, recal_round, qid_node, results


def main(out_path="BENCH_qos.json", *, smoke=False, write=True):
    sys.path.insert(0, "src")
    import numpy as np
    from repro.columnar.table import Table
    from repro.query import Catalog, Executor

    n = 1 << 14 if smoke else 1 << 16
    rounds = 6 if smoke else 10
    per_round = 8 if smoke else 16
    v = (np.arange(n, dtype=np.int32) % 128).astype(np.int32)
    w = np.ones(n, dtype=np.int32)

    def fresh_cat():
        return Catalog.from_tables(Table.from_arrays(
            "t", {"v": v.copy(), "w": w.copy()}))

    # shared SLO: derived once from the static server's skewed round 0,
    # then reused for the adaptive soak (identical contracts)
    slo_box = [None]
    t0 = time.perf_counter()
    s_lat, s_srv, s_ex, _, s_nodes, s_res = _soak(
        fresh_cat(), adaptive=False, rounds=rounds, per_round=per_round,
        slo_box=slo_box)
    a_lat, a_srv, a_ex, recal_round, a_nodes, a_res = _soak(
        fresh_cat(), adaptive=True, rounds=rounds, per_round=per_round,
        slo_box=slo_box)
    wall_s = time.perf_counter() - t0

    # -- (d) differential: every answer vs a cache-free oracle --------- #
    oracle = Executor(fresh_cat())
    diff_clean = True
    for nodes, res in ((s_nodes, s_res), (a_nodes, a_res)):
        for qid, q in nodes.items():
            if res.get(qid) != oracle.execute(q).value:
                diff_clean = False

    # -- (c) idempotence: the final overlay applied twice -------------- #
    overlay = a_ex.tel.ledger.calibration_overlay(a_ex.cost_model)
    a_ex.cost_model.apply_calibration(overlay)
    p1 = (dict(a_ex.cost_model.stream_eff),
          dict(a_ex.cost_model.call_overhead), a_ex.cost_model.h2d_gbps)
    a_ex.cost_model.apply_calibration(overlay)
    p2 = (dict(a_ex.cost_model.stream_eff),
          dict(a_ex.cost_model.call_overhead), a_ex.cost_model.h2d_gbps)
    idempotent = p1 == p2

    # -- (a)/(b) steady-state tails ------------------------------------ #
    # steady window: rounds after the adaptive server recalibrated AND
    # compiled its re-planned pipelines (the first post-recost round
    # pays one-time jit cost); the static side is compared on the SAME
    # rounds.  Falls back to the last half when no recalibration fired.
    steady_from = (recal_round + 2) if recal_round is not None \
        else rounds // 2
    steady_from = min(steady_from, rounds - 1)

    def tail(per_round_lat, tenant):
        # median across steady rounds of each round's p95: one noisy
        # round (GC pause, recompile) otherwise owns the pooled p95 on
        # both sides and the comparison degenerates to max-vs-max
        ps = sorted(_p95(lat.get(tenant, []))
                    for lat in per_round_lat[steady_from:])
        return ps[len(ps) // 2] if ps else 0.0

    slo = slo_box[0]
    static_prio = tail(s_lat, "prio")
    static_bulk = tail(s_lat, "bulk")
    adapt_prio = tail(a_lat, "prio")
    adapt_bulk = tail(a_lat, "bulk")
    adaptive_improves = adapt_prio < static_prio
    prio_meets_slo = slo is not None and adapt_prio <= slo
    bulk_absorbed = a_srv.n_backpressured > 0

    report = {
        "workload": {
            "n_rows": n, "rounds": rounds, "per_round": per_round,
            "smoke": smoke, "tenants": {"prio": {"priority": 10,
                                                 "cache_share": 2.0},
                                        "bulk": {"priority": 0,
                                                 "cache_share": 1.0}},
            "scenario": "doctored optimistic calibration (skewed "
                        "bandwidth) vs drift-triggered recalibration",
        },
        "slo_p95_s": round(slo, 6) if slo else None,
        "steady_from_round": steady_from,
        "last_recalibration_round": recal_round,
        "round_p95_s": {
            "static": [round(_p95(l["prio"] + l["bulk"]), 6)
                       for l in s_lat],
            "adaptive": [round(_p95(l["prio"] + l["bulk"]), 6)
                         for l in a_lat],
        },
        "n_recalibrations": a_srv.n_recalibrations,
        "cost_epoch": a_ex.cost_epoch,
        "n_backpressured": a_srv.n_backpressured,
        "static": {
            "prio_p95_s": round(static_prio, 6),
            "bulk_p95_s": round(static_bulk, 6),
        },
        "adaptive": {
            "prio_p95_s": round(adapt_prio, 6),
            "bulk_p95_s": round(adapt_bulk, 6),
        },
        "p95_speedup_static_over_adaptive": round(
            static_prio / adapt_prio, 3) if adapt_prio else None,
        "applied_overlay": overlay,
        "gates": {
            "differential_clean": diff_clean,
            "overlay_idempotent": idempotent,
            "adaptive_improves_p95": adaptive_improves,
            "prio_meets_slo": prio_meets_slo,
            "bulk_absorbs_backpressure": bulk_absorbed,
        },
        "wall_s": round(wall_s, 2),
    }
    if write:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {out_path}")
    print(f"recal@round={recal_round} epoch={a_ex.cost_epoch} "
          f"backpressured={a_srv.n_backpressured}")
    print(f"steady p95 prio: static={static_prio * 1e3:.1f}ms "
          f"adaptive={adapt_prio * 1e3:.1f}ms "
          f"(slo={slo * 1e3:.1f}ms)" if slo else "no slo derived")
    print("gates:", report["gates"])

    hard = ["differential_clean", "overlay_idempotent"]
    if not smoke:
        hard += ["adaptive_improves_p95", "prio_meets_slo",
                 "bulk_absorbs_backpressure"]
    failed = [g for g in hard if not report["gates"][g]]
    if failed:
        print(f"GATE FAILURES: {failed}", file=sys.stderr)
        if write:
            sys.exit(1)
        raise AssertionError(f"bench_qos gates failed: {failed}")
    return report


def _rows(rep):
    return [
        ("qos_static_prio_p95", rep["static"]["prio_p95_s"] * 1e6,
         f"bulk_p95_us={rep['static']['bulk_p95_s'] * 1e6:.0f}"),
        ("qos_adaptive_prio_p95", rep["adaptive"]["prio_p95_s"] * 1e6,
         f"speedup={rep['p95_speedup_static_over_adaptive']}x,"
         f"recal_round={rep['last_recalibration_round']},"
         f"backpressured={rep['n_backpressured']}"),
        ("qos_gates", 0.0,
         ";".join(f"{k}={v}" for k, v in rep["gates"].items())),
    ]


def qos_smoke():
    """run.py --smoke hook: correctness gates hard-fail, tail-latency
    gates are reported (CI boxes are too noisy to gate p95 on)."""
    return _rows(main(smoke=True, write=True))


def qos_figures():
    """run.py full-scale hook: all five gates enforced."""
    return _rows(main(smoke=False, write=True))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(smoke="--smoke" in sys.argv)
