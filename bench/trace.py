"""Reduction of a ``jax.profiler`` trace to the numbers the benchmark
reports: device busy time, the window, the device's top operations, and
idle gaps named by what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes,
read with ``jax.profiler.ProfileData``.  Every event there is in
nanoseconds on one clock.  A device is a plane named ``/device:<KIND>:<n>``;
its operations are the events of its ``XLA Ops`` line, each named by its
instruction and by the program (``XLA Modules`` line) it ran in.  The CPU
backend has no device plane: its operations run on host threads and
carry an ``hlo_op`` stat.  ``device="cpu"`` reads those, and only the
check of this reduction uses it; no CPU reading is ever reported as a
device metric.

Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
events, named ``bench.<what>``; ``bench.window`` marks the traced window.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

WINDOW = "bench.window"


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _op_name(hlo: str, start: float, modules: list, starts: list) -> str:
    """``<module>:<instruction>`` from an ``XLA Ops`` event, whose name is
    the instruction's whole HLO text, e.g. ``jit_step:%fusion.3``."""
    op = hlo.split(" = ", 1)[0].strip()
    i = bisect.bisect_right(starts, start) - 1
    if i >= 0 and modules[i][0] <= start <= modules[i][1]:
        return f"{modules[i][2]}:{op}"
    return op


def read(path: str, device: str = "tpu") -> dict:
    """Device operations per device and the host's ``bench.*`` spans, as
    ``(name, start_ns, end_ns)`` lists."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops = defaultdict(list)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and device != "cpu":
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 ev.name.split("(")[0])
                for ev in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ()))
            starts = [m[0] for m in modules]
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines
                       else ()):
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                ops[plane.name].append((_op_name(ev.name, s, modules,
                                                 starts), s, e))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif device == "cpu" and ev.duration_ns > 0 \
                            and "hlo_op" in _stats(ev):
                        ops["/host:CPU"].append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns))
    return {"ops": dict(ops), "spans": spans}


def union(intervals, lo: float, hi: float) -> list:
    """Disjoint, sorted intervals covering ``intervals`` clipped to
    ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The intervals of ``[lo, hi]`` that ``busy`` leaves uncovered."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def segments(spans: list, lo: float, hi: float) -> list:
    """``[lo, hi]`` cut into ``(start, end, name)`` pieces, each named by
    the innermost host span over it (``host`` where none is); the spans
    nest, as annotations on one thread do."""
    out, stack, at = [], [], lo

    def upto(t):
        nonlocal at
        t = min(t, hi)
        if t > at:
            out.append((at, t, stack[-1][1] if stack else "host"))
            at = t

    for a, neg_b, name in sorted((a, -b, n) for n, a, b in spans
                                 if n != WINDOW):
        while stack and stack[-1][0] <= a:
            upto(stack[-1][0])
            stack.pop()
        upto(a)
        stack.append((-neg_b, name))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    upto(hi)
    return out


def idle_by_span(gap_list: list, segs: list) -> dict:
    """Seconds of each gap, split over the host spans under it."""
    out, j = defaultdict(float), 0
    for s, e in gap_list:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            d = min(e, segs[k][1]) - max(s, segs[k][0])
            if d > 0:
                out[segs[k][2]] += d
            k += 1
    return out


def reduce(raw: dict, top: int = 10) -> dict:
    """Busy and window seconds, the top device operations by summed time,
    and the idle seconds under each host span (of the first device), all
    inside the ``bench.window`` span.  ``busy_s`` is averaged over the
    devices.
    """
    windows = [(s, e) for n, s, e in raw["spans"] if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    per_device, by_op, idle = [], defaultdict(float), {}
    busy_all = []
    for dev, evs in sorted(raw["ops"].items()):
        busy = union([(s, e) for _, s, e in evs], lo, hi)
        per_device.append(sum(e - s for s, e in busy))
        busy_all.append(busy)
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_op[name] += d
    if busy_all:
        idle = idle_by_span(gaps(busy_all[0], lo, hi),
                            segments(raw["spans"], lo, hi))
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": (sum(per_device) / len(per_device) * ns
                   if per_device else 0.0),
        "n_devices": len(per_device),
        "n_ops": sum(len(v) for v in raw["ops"].values()),
        "device_ops": [[n, v * ns] for n, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v * ns] for n, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }
