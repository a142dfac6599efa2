"""Share of the HBM roofline over the traced window: the least bytes the
program calls in the window had to read, at the chip's peak bandwidth,
over the device's busy time in the window.  ``mfu`` is the share of the
chip's peak FLOP/s over the whole window, idle time included.

The calls are counted by the harness per query template (one call per
query), and their bytes come from the kind's ``least_bytes``, computed
from the table sizes.  The device runs nothing
but these programs in the window, so its busy time is their time.
"""
from __future__ import annotations


def share(run, kind: str):
    """Percent of the roofline for the calls of ``kind`` in the traced
    window, or ``None`` where there is no trace, no peak or no call."""
    tr = run.trace
    if tr is None or run.peaks is None or tr["busy_s"] <= 0:
        return None
    calls = run.counters.get("program_calls", {})
    need = 0
    for t in run.templates:
        if t.spec["kind"] == kind and calls.get(t.name):
            need += calls[t.name] * t.kind.least_bytes(t.spec, run.config,
                                                       run.sizes)
    if not need:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / tr["busy_s"]


def mfu(run, kind: str):
    """Percent of the chip's peak bf16 FLOP/s that the calls of ``kind``
    reached over the whole traced window, idle time included; ``None``
    where there is no trace, no peak or no call."""
    tr = run.trace
    if tr is None or run.peaks is None or tr["window_s"] <= 0:
        return None
    calls = run.counters.get("program_calls", {})
    need = sum(calls.get(t.name, 0) * t.kind.flops(t.spec, run.config,
                                                   run.sizes)
               for t in run.templates if t.spec["kind"] == kind)
    if not need:
        return None
    return 100.0 * need / run.peaks["bf16_flops_per_s"] / tr["window_s"]


def idle(run):
    """Percent of the traced window in which no operation ran on the
    device."""
    tr = run.trace
    if tr is None or tr["window_s"] <= 0 or not tr["n_devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
