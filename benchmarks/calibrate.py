"""Measure per-backend streaming numbers -> BENCH_calibration.json.

The cost model ships with fixed xla/pallas stream efficiencies and call
overheads (the paper's TPU-calibrated constants).  This benchmark
replaces them with numbers measured on THIS machine:

  * ``stream_eff``      — achieved / model-predicted bandwidth of a
                          memory-bound streaming reduce per impl,
  * ``call_overhead_s`` — dispatch latency of a trivially small jitted
                          call per impl,
  * ``h2d_gbps``        — host->device placement bandwidth (the morsel
                          transfer the streaming executor double-buffers).

``repro.query.cost.load_calibration(path)`` reads the file and refuses it
on any other backend or device kind; ``CostModel(..., calibration=...)``
overlays it on the constants.  The
pallas impl is only measured where it is real (TPU) — interpret-mode
emulation numbers would poison the model.

    PYTHONPATH=src python benchmarks/calibrate.py [--smoke]
"""
from __future__ import annotations

import json
import sys
import time


def _timed(fn, *args, iters: int = 5) -> float:
    fn(*args)                                   # warmup / compile
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def calibrate(out_path: str = "BENCH_calibration.json", *,
              smoke: bool = False) -> dict:
    sys.path.insert(0, "src")
    import jax
    import jax.numpy as jnp
    from repro.core.bandwidth import stream_copy_pallas
    from repro.query.cost import CostModel

    n = 1 << 20 if smoke else 1 << 23            # 4 MiB / 32 MiB stream
    x = jnp.arange(n, dtype=jnp.int32)
    import numpy as np
    host = np.arange(n, dtype=np.int32)
    backend = jax.default_backend()
    # every stream below runs on one device: price it as one engine
    model = CostModel(1)
    predicted = model.bandwidth_gbps("partitioned")

    backends = {}
    impls = [("xla", jax.jit(jnp.sum))]
    if backend == "tpu":
        impls.append(("pallas", jax.jit(stream_copy_pallas)))
    for impl, fn in impls:
        dt = _timed(fn, x)
        achieved = x.nbytes / dt / 1e9
        tiny = jnp.zeros((8,), jnp.int32)
        over = _timed(fn, tiny, iters=50)
        backends[impl] = {
            "achieved_gbps": round(achieved, 2),
            "predicted_gbps": round(predicted, 2),
            "stream_eff": round(min(achieved / predicted, 1.0), 4),
            "call_overhead_s": over,
        }

    t_h2d = _timed(lambda a: jax.device_put(a, jax.devices()[0]), host)
    report = {
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "n_bytes": int(x.nbytes),
        "h2d_gbps": round(host.nbytes / t_h2d / 1e9, 2),
        "backends": backends,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    return report


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    calibrate(smoke="--smoke" in sys.argv)
