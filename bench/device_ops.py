"""Device time of named operations in a traced run's profile, summed over
every operation inside the ``bench.window`` span: ``bench/trace.reduce``
keeps only the top ten for the breakdown, and one program can run as
several operations (a staging of 2,001 columns shows as eight
concatenates).

- the SGD kernel: operations whose instruction is named ``sgd_block``
  (``sgd_block`` or ``sgd_block_wide``, the names the kernels give their
  ``pallas_call``);
- staging: operations of the program ``jit_stage_morsel``
  (``columnar/engine.stage_morsel``).

Each reading is ``None`` where no such operation ran in the window: a
program without the kernel or the staging program, or a run not traced.
"""
from __future__ import annotations

import os

from bench import harness
from bench import trace as btrace

KERNEL = "sgd_block"
STAGE = "jit_stage_morsel"

_MEMO: dict = {}


def is_kernel(name: str) -> bool:
    return KERNEL in name.rsplit(":", 1)[-1]


def is_stage(name: str) -> bool:
    return name.split(":", 1)[0] == STAGE


def op_seconds(raw: dict, match):
    """Seconds of the first device's operations that ``match`` their
    name, each clipped to the ``bench.window`` span; ``None`` where none
    ran there."""
    if not raw["ops"]:
        return None
    spans = [(s, e) for n, s, e in raw["spans"] if n == btrace.WINDOW]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    total, found = 0, False
    for name, s, e in raw["ops"][sorted(raw["ops"])[0]]:
        d = min(e, hi) - max(s, lo)
        if d > 0 and match(name):
            total += d
            found = True
    return total * 1e-9 if found else None


def kernel_least_bytes(spec: dict, config: dict, sizes: dict) -> int:
    """Bytes one ``train_glm`` call's SGD kernel has to read: the features
    and the label once per epoch, for all K models at once."""
    from bench.queries import train_glm
    n_features = len(train_glm.features(spec, config))
    return 4 * (n_features + 1) * sizes[spec["table"]] * int(spec["epochs"])


def kernel_roofline(raw: dict, need_bytes: float, hbm_bytes_per_s: float):
    """Percent of the HBM roofline: ``need_bytes`` at the peak, over the
    kernel's device time in the window."""
    t = op_seconds(raw, is_kernel)
    if t is None or not need_bytes:
        return None
    return 100.0 * need_bytes / hbm_bytes_per_s / t


def stage_share(raw: dict, busy_s: float):
    """Percent of the device's busy time in the window spent staging."""
    t = op_seconds(raw, is_stage)
    if t is None or busy_s <= 0:
        return None
    return 100.0 * t / busy_s


def for_run(run):
    """``bench/trace.read`` of a traced run's profile, read once per run;
    ``None`` where the run was not traced."""
    if run.trace is None:
        return None
    path = btrace.latest_xplane(harness.TRACE_DIR)
    key = (path, os.path.getmtime(path))
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = btrace.read(path)
    return _MEMO[key]


def train_calls_bytes(run) -> int:
    """The SGD kernel's least bytes over the ``train_glm`` calls the
    harness counted in the traced window."""
    calls = run.counters.get("program_calls", {})
    return sum(calls.get(t.name, 0)
               * kernel_least_bytes(t.spec, run.config, run.sizes)
               for t in run.templates if t.spec["kind"] == "train_glm")
