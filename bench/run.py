"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload higgs.train --seed 7 --seconds 30 --trace 0

The cell, its configuration and its traffic are named in
``BENCHMARK.json``.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, each
number compared with the reference beside its limit.  The same checks are
the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.  ``--rows-div N`` rehearses on any device
with every table's rows divided by N: it runs the whole cell and prints
its checks, but no result, and exits 1.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows-div", type=int, default=1,
                    help="rehearsal: divide every table's rows by this")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # libtpu would log under a fixed /tmp path shared by every run
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    if args.workload not in cells:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    rehearsal = args.rows_div > 1
    harness.configure_jax(cache=not rehearsal)
    import jax
    harness.log(f"setup: jax imported at "
                f"{time.perf_counter() - T_START:.3f}s")
    devices = jax.devices()
    harness.log(f"setup: {len(devices)} {devices[0].platform} devices up at "
                f"{time.perf_counter() - T_START:.3f}s")
    chips = int(cells[args.workload]["chips"])
    if not rehearsal and devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform "
              f"{devices[0].platform!r}); --rows-div N rehearses",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    import repro  # noqa: F401  (the system under test must be present)
    harness.log(f"setup: repro imported at "
                f"{time.perf_counter() - T_START:.3f}s")

    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START,
                           rows_div=args.rows_div)
    for name, c in out["checks"].items():
        harness.log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    if rehearsal:
        harness.log(f"bench: rehearsal at 1/{args.rows_div} of the rows on "
                    f"{devices[0].platform!r}: correct={out['correct']}, "
                    "no result")
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
