"""The program's own spans in the traced run's profile: the device's idle
time split by the layer the host was in, and the trainer's traces.

``bench/trace.read`` keeps the benchmark's ``bench.*`` spans.  This module
reads the same ``.xplane.pb`` for the spans the program opens at its
layer boundaries (names starting ``serve.``, ``exec.``, ``pipeline.`` or
``trainer.``) and for JAX's own annotations (its ``TraceMe`` events:
``PjitFunction(f)``, ``lower_sharding_computation``, ...; the Python
tracer's ``$file:line`` events are left out), taken only from the host
threads that hold a ``bench.*`` span, so that they nest.  The program
marks each trace of a trainer step with a ``trainer.trace`` span.

Where the program opens no span at all, every reading is ``None``.
"""
from __future__ import annotations

import bisect
import json
import os

from bench import harness
from bench import trace as btrace

PROGRAM = ("serve.", "exec.", "pipeline.", "trainer.")
# the layer of a program span's first word; anything else is the client
LAYERS = {"serve": "serve", "exec": "exec", "pipeline": "exec",
          "trainer": "trainer"}
TRACE_MARK = "trainer.trace"

_MEMO: dict = {}


def read_program(path: str) -> dict:
    """``(name, start_ns, end_ns)`` lists of the program's spans
    (``program``, without the trace marks), JAX's annotations (``jax``)
    and the trace marks (``marks``), from the threads that hold a
    ``bench.*`` span."""
    from jax.profiler import ProfileData
    out = {"program": [], "jax": [], "marks": []}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = {"program": [], "jax": [], "marks": []}
            has_bench = False
            for ev in line.events:
                name = ev.name
                iv = (name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if name.startswith("bench."):
                    has_bench = True
                elif name == TRACE_MARK:
                    mine["marks"].append(iv)
                elif name.startswith(PROGRAM):
                    mine["program"].append(iv)
                elif not name.startswith("$"):
                    mine["jax"].append(iv)
            if has_bench:
                for k, v in mine.items():
                    out[k] += v
    return out


def jax_phases(program: list, jax_events: list, depth: int = 2) -> list:
    """JAX's annotations inside ``trainer.*`` spans, each renamed
    ``<span>/<annotation>``, down to ``depth`` distinct names below the
    span: a step's dispatch ``PjitFunction(f)`` (its own time is tracing
    the Python body and loading or compiling the program) and what runs
    directly under it, such as ``lower_sharding_computation``, a nested
    jit's trace or ``ExecuteReplicated.__call__``."""
    owners = sorted((s, e, n) for n, s, e in program
                    if n.startswith("trainer."))
    starts = [o[0] for o in owners]
    out, stack = [], []        # (end, distinct names, owner, name)
    for name, s, e in sorted(jax_events, key=lambda ev: (ev[1], -ev[2])):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or e > owners[i][1]:
            continue
        owner = owners[i]
        while stack and (stack[-1][0] <= s or stack[-1][2] is not owner):
            stack.pop()
        n = stack[-1][1] + (name != stack[-1][3]) if stack else 1
        stack.append((e, n, owner, name))
        if n <= depth:
            out.append((f"{owner[2]}/{name}", s, e))
    return out


def window(raw: dict) -> tuple:
    spans = [(s, e) for n, s, e in raw["spans"] if n == btrace.WINDOW]
    return min(s for s, _ in spans), max(e for _, e in spans)


def program_idle(raw: dict, prog: dict):
    """The idle seconds of the ``bench.window`` span (first device), each
    piece named by the innermost program span over it, under a
    ``trainer.*`` span by JAX's phase (``jax_phases``), and by the
    benchmark's span where no program span is open: ``gaps`` lists every
    name, ``layers`` sums them per layer (``LAYERS``, and ``client`` for
    the rest).  ``raw`` is ``bench/trace.read``'s.  ``None`` where the
    trace holds no program span or no device operation."""
    if not prog["program"] or not raw["ops"]:
        return None
    lo, hi = window(raw)
    evs = raw["ops"][sorted(raw["ops"])[0]]
    idle = btrace.idle_by_span(
        btrace.gaps(btrace.union([(s, e) for _, s, e in evs], lo, hi),
                    lo, hi),
        btrace.segments(raw["spans"] + prog["program"]
                        + jax_phases(prog["program"], prog["jax"]),
                        lo, hi))
    layers = dict.fromkeys(("serve", "exec", "trainer", "client"), 0.0)
    for name, v in idle.items():
        layers[LAYERS.get(name.split(".")[0], "client")] += v * 1e-9
    return {"layers": layers,
            "gaps": [[n, v * 1e-9] for n, v in sorted(
                idle.items(), key=lambda kv: -kv[1])]}


def traces(raw: dict, prog: dict):
    """Trace marks inside the ``bench.window`` span; ``None`` where the
    trace holds no program span."""
    if not prog["program"]:
        return None
    lo, hi = window(raw)
    return sum(1 for _, s, _ in prog["marks"] if lo <= s <= hi)


def for_run(run):
    """``{"split": program_idle, "traces": traces}`` of a traced run's
    profile, read once per run; ``None`` where the run was not traced.
    The split's names go to standard error: the result line has no place
    for them."""
    if run.trace is None:
        return None
    path = btrace.latest_xplane(harness.TRACE_DIR)
    key = (path, os.path.getmtime(path))
    if key not in _MEMO:
        raw, prog = btrace.read(path), read_program(path)
        _MEMO.clear()
        _MEMO[key] = {"split": program_idle(raw, prog),
                      "traces": traces(raw, prog)}
        split = _MEMO[key]["split"]
        if split is not None:
            harness.log("trace: idle by layer " + json.dumps(split["layers"])
                        + "; by program span " + json.dumps(split["gaps"]))
    return _MEMO[key]


def idle_share(run, layer: str):
    """Percent of the traced window in which no operation ran on the
    device while the host was in ``layer``; ``None`` where the program
    annotated no layer."""
    got = for_run(run)
    if got is None or got["split"] is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * got["split"]["layers"][layer] / run.trace["window_s"]
