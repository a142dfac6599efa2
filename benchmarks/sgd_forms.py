"""The two forms of the streamed trainer's SGD kernel, timed alone: the
measurement that sets ``kernels/sgd/sgd.WIDE_FROM_ROWS``.

For each feature count it makes a feature-major table of ``--rows`` rows
from a seed, then times ``sgd_block`` (the models broadcast over 128
lanes) and ``sgd_block_wide`` (each 128-row tile transposed to rows on
sublanes), K models, logistic, one epoch, minibatches of 16: the median
of ``--repeats`` calls after one warm call, each ended by
``block_until_ready``.  A form the chip's compiler refuses at a width
(``sgd_block``'s VMEM at a few hundred features) is reported with its
error.  Both forms' weights are compared with each other where both run.

    PYTHONPATH=src python benchmarks/sgd_forms.py --features 28,256,2000

Prints one JSON object per width and form: microseconds per minibatch
step, and the device.  A time means something only from a TPU.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sgd import sgd


def _time(fn, repeats: int) -> float:
    jax.block_until_ready(fn())
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--features", default="28,256,2000")
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    m, k = args.rows, args.k
    lr = jnp.linspace(1e-4, 2e-3, k, dtype=jnp.float32)
    l2 = jnp.zeros((k,), jnp.float32)
    for n in (int(s) for s in args.features.split(",")):
        key = jax.random.key(n)
        data = jax.random.normal(key, (n + 1, m), jnp.float32)
        data = data.at[n].set((data[0] > 0).astype(jnp.float32))
        wide_data = jnp.pad(data, ((0, sgd.wide_rows(n) - n - 1), (0, 0)))
        x0 = jnp.zeros((k, n), jnp.float32)
        # sgd_block's default block of 8192 rows overflows VMEM past
        # about 200 features; 4096 rows still fit at 256
        block_rows = sgd.BLOCK_ROWS if n < 200 else 4096
        forms = {
            "sgd_block": lambda: sgd.sgd_block(
                data, lr, l2, x0, kind="logreg", block_rows=block_rows),
            "sgd_block_wide": lambda: sgd.sgd_block_wide(
                wide_data, lr, l2, x0, kind="logreg"),
        }
        weights = {}
        for name, fn in forms.items():
            out = {"features": n, "rows": m, "k": k, "form": name,
                   "device": dev.device_kind}
            try:
                t = _time(fn, args.repeats)
                weights[name] = np.asarray(fn())
                out["us_per_step"] = t / (m // 16) * 1e6
                out["s_per_epoch"] = t
            except Exception as e:      # noqa: BLE001 - report, go on
                out["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(out), flush=True)
        if len(weights) == 2:
            a, b = weights.values()
            print(json.dumps({"features": n, "max_abs_diff": float(
                np.max(np.abs(a - b))), "max_abs": float(np.max(np.abs(b)))}),
                flush=True)
        del data, wide_data
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
