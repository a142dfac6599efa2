"""Faults planted in the timed path under a cell's run, so the check that
decides ``correct`` can be seen to fail: an answer altered where it is
produced (one model's answer given as another's), a step that returns
its state unchanged, and half of each minibatch left out with the mean
gradient taken over the rest.  Every cell runs on one chip, so no cell
has an exchange between chips to leave out.

    python bench/faults.py --workload higgs.train --seeds 3,4,5 --seconds 2

For each seed and fault it runs the whole cell (set-up, warm-up, the
window through ``QueryServer``, the check) with the fault planted, and
prints the numbers compared beside the cell's limits.  The benchmark's
own runs never run this; ``bench/tests/test_checks.py`` plants the same
faults at a size a test run holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def answer_altered(patch) -> None:
    """The first two models' weights and losses swapped where the trainer
    returns them: each is another model's answer."""
    from repro.columnar import engine
    real = engine.train_glm_stream

    def train(*a, **k):
        xs, losses = real(*a, **k)
        swap = np.array([1, 0] + list(range(2, xs.shape[0])))
        return xs[swap], losses[swap]
    patch.setattr(engine, "train_glm_stream", train)


def state_unchanged(patch) -> None:
    from repro.kernels.sgd import ref as sgd_ref
    patch.setattr(sgd_ref, "sgd_ref", lambda a, b, x0, **k: x0)


def half_batch(patch) -> None:
    from repro.kernels.sgd import ref as sgd_ref
    real = sgd_ref.sgd_ref

    def half(a, b, x0, *, minibatch=16, **k):
        m, n = a.shape
        h = minibatch // 2
        a = a.reshape(m // minibatch, minibatch, n)[:, :h].reshape(-1, n)
        b = b.reshape(m // minibatch, minibatch)[:, :h].reshape(-1)
        return real(a, b, x0, minibatch=h, **k)
    patch.setattr(sgd_ref, "sgd_ref", half)


FAULTS = {"answer_altered": answer_altered,
          "state_unchanged": state_unchanged,
          "half_batch": half_batch}


class Patch:
    """``setattr`` that ``undo`` reverts, as pytest's ``monkeypatch``."""

    def __init__(self):
        self._undo = []

    def setattr(self, obj, name: str, value) -> None:
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self) -> None:
        while self._undo:
            setattr(*self._undo.pop())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2)
    ap.add_argument("--faults", default=",".join(FAULTS),
                    help="comma-separated names of the faults to plant")
    ap.add_argument("--rows-div", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # libtpu would log under a fixed /tmp path shared by every run
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    harness.configure_jax(cache=args.rows_div == 1)
    import jax
    dev = jax.devices()[0]
    caught_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in args.faults.split(","):
            patch = Patch()
            FAULTS[fault](patch)
            try:
                out = harness.run_cell(args.workload, seed, args.seconds,
                                       False, t_start=time.perf_counter(),
                                       rows_div=args.rows_div)
            finally:
                patch.undo()
            caught_all = caught_all and not out["correct"]
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "fault": fault, "device": dev.device_kind,
                              "correct": out["correct"],
                              "checks": out["checks"]}), flush=True)
    return 0 if caught_all else 1


if __name__ == "__main__":
    sys.exit(main())
