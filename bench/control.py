"""The control of a cell's check: the plain reference computed one step
below the precision the configuration states, put in the program's
place, and held to the cell's limits.  It has to fail them, or the check
could not tell a lower-precision program from a sound one.

    python bench/control.py --workload higgs.train --seeds 3,4,5

For each seed it makes the cell's tables, draws the window's queries,
answers each distinct one with the kind's ``control`` and with its
``Reference``, and prints the numbers compared beside the cell's limits.
``train_glm`` states float32: its control trains in bfloat16.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell_name: str, seed: int, rows_div: int = 1) -> dict:
    from bench import data as bdata
    from bench import harness
    _, _, config, traffic, templates = harness.load_cell(cell_name)
    names = sorted({t for tp in templates for t in tp.kind.tables(tp.spec)})
    tables = {n: bdata.make_table(config["tables"][n], seed, n, rows_div)
              for n in names}
    sizes = {n: int(next(iter(c.values())).shape[0])
             for n, c in tables.items()}
    queries = harness.draw_queries(templates, config, sizes, seed,
                                   int(traffic.get("max_queries", 256)),
                                   "window")
    distinct = list({harness.query_key(q): q for q in queries}.values())
    data = harness.Data(tables)
    values = {}
    for t in templates:
        mine = [q.params for q in distinct if q.template is t]
        if not mine:
            continue
        t0 = time.perf_counter()
        got = t.kind.control(t.spec, data, mine)
        ref = t.kind.Reference(t.spec, data)
        want = [ref.answer(p) for p in mine]
        for k, v in t.kind.compare(got, want).items():
            values[k] = max(values.get(k, v), v)
        harness.log(f"control {t.name}: {len(mine)} answers in "
                    f"{time.perf_counter() - t0:.3f}s")
    limits = traffic["check"]["limits"]
    return {k: {"value": v, "limit": limits[k], "fails": v > limits[k]}
            for k, v in values.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows-div", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # libtpu would log under a fixed /tmp path shared by every run
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    harness.configure_jax(cache=args.rows_div == 1)
    import jax
    dev = jax.devices()[0]
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(args.workload, seed, args.rows_div)
        fails = any(v["fails"] for v in r.values())
        failed_all = failed_all and fails
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": dev.device_kind, "control": r,
                          "control_fails": fails}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
