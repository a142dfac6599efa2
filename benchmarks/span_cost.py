"""Cost of the program's always-on spans, per span, on this host.

Every ``Telemetry.span`` opens a ``jax.profiler.TraceAnnotation``.  This
times, over ``--n`` iterations (median of ``--repeats``), what the
served path pays for them, each less an empty ``with`` of the same loop:

  * ``span`` — ``Telemetry(False).span(name)`` inside a request context
    (so it carries ``qid=``), as ``exec.execute`` opens it;
  * ``span_attrs`` — the same with two attributes, as
    ``pipeline.morsel_step`` opens it per morsel;
  * ``span_no_qid`` — outside any request context;
  * ``request`` — ``telemetry.request(qid)``, set around each record;
  * ``morsel`` — ``pipeline.drive``'s per-morsel bookkeeping: its two
    spans, three clock reads and ``_account_morsel`` without metrics;
  * ``span_recording`` — ``span`` while a ``jax.profiler`` session with
    default options records (``--n`` / 10 iterations).

    PYTHONPATH=src python benchmarks/span_cost.py [--n 200000]

Prints one JSON object: microseconds per iteration, and the device.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import statistics
import tempfile
import time

import jax

from repro.query import pipeline
from repro.query import telemetry as tm


def _per_iter_us(body, n: int, repeats: int) -> float:
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        body(n)
        times.append((time.perf_counter() - t0) / n * 1e6)
    return statistics.median(times)


def measure(n: int, repeats: int) -> dict:
    tel = tm.Telemetry(False)
    clock = time.perf_counter

    def empty(k):
        for _ in range(k):
            with contextlib.nullcontext():
                pass

    def span(k):
        with tm.request(7):
            for _ in range(k):
                with tel.span("exec.execute"):
                    pass

    def span_attrs(k):
        with tm.request(7):
            for i in range(k):
                with tel.span("pipeline.morsel_step", morsel=i,
                              path="prefetch"):
                    pass

    def span_no_qid(k):
        for _ in range(k):
            with tel.span("exec.execute"):
                pass

    def request(k):
        for i in range(k):
            with tm.request(i):
                pass

    def morsel(k):
        with tm.request(7):
            for i in range(k):
                t0 = clock()
                with tel.span("pipeline.morsel_wait", morsel=i,
                              path="prefetch"):
                    pass
                t1 = clock()
                with tel.span("pipeline.morsel_step", morsel=i,
                              path="prefetch"):
                    pass
                pipeline._account_morsel(None, t0, t1, clock())

    base = _per_iter_us(empty, n, repeats)
    out = {"empty_with": base}
    for name, body in (("span", span), ("span_attrs", span_attrs),
                       ("span_no_qid", span_no_qid), ("request", request),
                       ("morsel", morsel)):
        out[name] = _per_iter_us(body, n, repeats) - base
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["span_recording"] = _per_iter_us(
                span, max(n // 10, 1), repeats) - base
        finally:
            jax.profiler.stop_trace()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()
    dev = jax.devices()[0]
    us = measure(args.n, args.repeats)
    print(json.dumps({"n": args.n, "repeats": args.repeats,
                      "us_per_iter": us,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}))


if __name__ == "__main__":
    main()
