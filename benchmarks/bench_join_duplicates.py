"""Duplicate-join benchmark -> BENCH_join_duplicates.json.

Measures the two things the multi-match kernel exists for:

  * probe+materialize throughput vs duplicate factor (1x/4x/16x chains):
    longer chains emit more pairs per probe row, so Mrows/s of probe input
    degrades while Mpairs/s of emitted output grows,
  * the optimizer win the kernel unlocks: the formerly-REFUSED plan had to
    build on the big unique side (multi-pass HT_CAPACITY rescans, Fig. 8b
    linear regime) because the small side carried duplicate keys; the new
    optimizer builds on the small duplicate side (one pass).  Both plans
    emit the identical pair multiset — the speedup is recorded.

    PYTHONPATH=src python benchmarks/bench_join_duplicates.py [--smoke]
"""
from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _timeit(fn, iters: int = 3) -> float:
    import jax
    jax.block_until_ready(fn())               # warmup (compile)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6      # us


def main(out_path: str = None, *, smoke: bool = False) -> dict:
    # anchored on the repo root, robust to any invoking cwd (like run.py)
    if out_path is None:
        out_path = os.path.join(_ROOT, "BENCH_join_duplicates.json")
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    import jax.numpy as jnp
    import numpy as np
    from repro.core.channels import plan as make_plan
    from repro.core.join import (
        HT_CAPACITY, join_distributed, join_distributed_multi,
    )
    from repro.kernels.join.ops import hash_join_multi
    from repro.kernels.join.ref import next_pow2
    from repro.launch.mesh import make_host_mesh
    from repro.query import Catalog, Q, optimize
    from repro.query.logical import Join, walk
    from repro.columnar.table import Table

    rng = np.random.default_rng(0)
    n_l = 1 << (14 if smoke else 17)
    n_distinct = 1 << (9 if smoke else 11)
    report: dict = {"n_probe_rows": n_l, "n_distinct_build_keys": n_distinct}

    # --- probe throughput vs duplicate factor (chain length) --------------- #
    report["duplicate_factor_sweep"] = {}
    l = jnp.asarray(rng.integers(0, n_distinct, size=n_l), np.int32)
    for factor in (1, 4, 16):
        s = jnp.asarray(np.repeat(np.arange(n_distinct, dtype=np.int32),
                                  factor))
        n_pairs = int(n_l * factor)           # every probe key is present
        max_out = next_pow2(n_pairs + 1)
        us = _timeit(lambda: hash_join_multi(
            s, l, max_out=max_out, impl="xla"))
        total = int(hash_join_multi(s, l, max_out=max_out, impl="xla").total)
        assert total == n_pairs, (total, n_pairs)
        report["duplicate_factor_sweep"][f"{factor}x"] = {
            "build_rows": int(s.shape[0]),
            "pairs_emitted": total,
            "us_per_join": round(us, 1),
            "probe_mrows_per_s": round(n_l / us, 2),
            "pairs_mrows_per_s": round(total / us, 2),
        }

    # --- optimized vs formerly-refused build side -------------------------- #
    # query: big (unique key, > HT_CAPACITY) JOIN small (duplicate keys).
    # refused plan: duplicates may not build -> big builds, multi-pass.
    # new plan: small duplicate side builds -> one bucketed pass.
    n_big = 8 * HT_CAPACITY if not smoke else 2 * HT_CAPACITY
    n_small = 4096 if not smoke else 1024
    key_dom = 1024
    big_keys = jnp.asarray(np.arange(n_big, dtype=np.int32))
    small_keys = jnp.asarray(rng.integers(0, key_dom, size=n_small), np.int32)
    mesh = make_host_mesh()
    p = make_plan(mesh, "model", "partitioned")

    # every small key lands in big's arange key space exactly once, so the
    # exact pair count is n_small on either plan
    exp_pairs = n_small
    max_out = next_pow2(n_small + 64)
    import jax
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        # jit the closures: time execution, not per-call shard_map tracing
        refused = jax.jit(lambda: join_distributed(big_keys, small_keys, p))
        dup_build = jax.jit(lambda: join_distributed_multi(
            small_keys, big_keys, p, max_out_per_shard=max_out))
        us_refused = _timeit(refused)
        out = dup_build()
        us_new = _timeit(dup_build)
    total_new = int(np.asarray(out[2]).sum())
    assert total_new == exp_pairs, (total_new, exp_pairs)
    speedup = us_refused / us_new

    # the optimizer really does pick the duplicate side now
    big_t = Table.from_arrays("big", {
        "k": np.arange(n_big, dtype=np.int32),
        "w": rng.integers(0, 9, size=n_big).astype(np.int32)})
    small_t = Table.from_arrays("small_dup", {
        "k": np.asarray(small_keys)})
    cat = Catalog.from_tables(big_t, small_t)
    node = optimize(Q.scan("big").join(Q.scan("small_dup"), on="k")
                    .sum("w").node, cat.stats)
    join_node = [n for n in walk(node) if isinstance(n, Join)][0]
    build_side = join_node.right.table

    report["build_side_swap"] = {
        "probe_rows_refused_plan": n_small,
        "build_rows_refused_plan": n_big,
        "passes_refused_plan": -(-n_big // HT_CAPACITY),
        "us_refused_plan": round(us_refused, 1),
        "us_duplicate_build_plan": round(us_new, 1),
        "pairs_emitted": total_new,
        "speedup": round(speedup, 2),
        "optimizer_build_side": build_side,
        "optimizer_selects_duplicate_side": build_side == "small_dup",
    }

    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return report


def join_duplicates():
    """run.py hook: (name, us_per_call, derived) rows, always FULL scale —
    run.py's --smoke mode skips this hook entirely (CI gets its smoke
    coverage from ``bench_join_duplicates.py --smoke`` directly), so the
    committed BENCH_join_duplicates.json is never clobbered with smoke
    data by a run.py invocation."""
    rep = main()
    rows = []
    for factor, r in rep["duplicate_factor_sweep"].items():
        rows.append((f"join_dup_probe_{factor}", r["us_per_join"],
                     f"{r['probe_mrows_per_s']}Mrows/s,"
                     f"{r['pairs_mrows_per_s']}Mpairs/s"))
    b = rep["build_side_swap"]
    rows.append(("join_dup_build_swap", b["us_duplicate_build_plan"],
                 f"speedup={b['speedup']}x,"
                 f"build={b['optimizer_build_side']}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print(json.dumps(main(smoke="--smoke" in sys.argv), indent=2))
