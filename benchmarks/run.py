# One function per paper table. Prints ``name,us_per_call,derived`` CSV and
# writes machine-readable BENCH_paper_figures.json.
#
#   PYTHONPATH=src python benchmarks/run.py [--smoke] [--only substr]
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    # robust to invocation from any cwd (python benchmarks/run.py / -m)
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    sys.path.insert(0, _ROOT)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks.paper_figures import ALL
    from benchmarks.bench_cache import cache_figures, subsumption_smoke
    from benchmarks.bench_join_duplicates import join_duplicates
    from benchmarks.bench_glm import glm_smoke
    from benchmarks.bench_qos import qos_figures, qos_smoke
    from benchmarks.bench_shard import shard_figures, shard_smoke
    from benchmarks.bench_tiering import tiering_smoke
    from benchmarks.calibrate import calibrate
    smoke = "--smoke" in sys.argv

    # measured stream efficiencies / overheads of this device for the cost
    # model (read back with repro.query.cost.load_calibration(path))
    calibrate(os.path.join(_ROOT, "BENCH_calibration.json"), smoke=smoke)
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1]

    # join_duplicates / cache_figures run full-scale only: smoke mode
    # keeps the two fast figures, and the bench_*.py --smoke entry points
    # cover the smoke case
    fns = ALL + [join_duplicates, cache_figures, qos_figures,
                 shard_figures]
    if smoke:
        # subsumption_smoke exercises the refine path + shared cache at
        # smoke scale without clobbering the committed BENCH_cache.json;
        # qos_smoke hard-gates the adaptive-replan correctness
        # invariants; shard_smoke re-execs itself under 8 forced host
        # devices and hard-gates
        # scaling monotonicity, the shuffle/broadcast crossover, and
        # sharded-vs-oracle bit-identity; tiering_smoke hard-gates the
        # over-capacity spill sweep, the kill-and-restart warm start
        # (real child processes), and demote-vs-evict hit rates;
        # glm_smoke hard-gates streamed-vs-eager training bit-identity,
        # warm-model serving speedup, and the Fig. 10a sharded
        # replication trade
        fns = [fn for fn in ALL if fn.__name__ in
               ("fig2_bandwidth", "tab3_roofline")] + \
              [subsumption_smoke, qos_smoke, shard_smoke, tiering_smoke,
               glm_smoke]
    if only:
        fns = [fn for fn in fns if only in fn.__name__]

    results = []
    failed = []
    print("name,us_per_call,derived")
    for fn in fns:
        try:
            rows = fn()
        except Exception as e:                    # noqa: BLE001
            print(f"{fn.__name__},0,ERROR:{type(e).__name__}:{e}")
            results.append({"name": fn.__name__, "error":
                            f"{type(e).__name__}: {e}"})
            failed.append(fn.__name__)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
            results.append({"name": name, "us_per_call": round(us, 1),
                            "derived": derived})

    with open(os.path.join(_ROOT, "BENCH_paper_figures.json"), "w") as f:
        json.dump({"smoke": smoke, "rows": results}, f, indent=2)
    if failed:
        sys.exit(f"benchmark phases failed: {', '.join(failed)}")


if __name__ == '__main__':
    main()
