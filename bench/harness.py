"""One run of one cell: set-up, the measured window, the check against
the plain reference, and, with ``trace``, the profiler's reading.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own that this module finds by name:

- ``bench/configs/<config>.json``: the tables (``bench/data.py``);
- ``bench/traffic/<mix>.json``: the loop (``closed``: one client) and
  the query templates, each of a kind ``bench/queries/<kind>.py`` that
  draws its literals, builds the program's query, and holds the
  reference;
- ``bench/metrics/<metric>.py``: ``read(run)`` gives the metric's value
  from what the run recorded, or ``None`` where it finds nothing.

The system under test is the program's ``QueryServer`` over an
``Executor`` on the cell's chips, with the semantic cache off: every
query enters through ``submit`` and ``drain``, and the answer checked is
the one the client got back.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from bench import data as bdata
from bench import trace as btrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(*path) -> dict:
    with open(os.path.join(*path)) as f:
        return json.load(f)


def load_metric(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Data:
    """The cell's tables: on the device as made, on the host on demand
    (for the references, after the window)."""

    def __init__(self, device: dict):
        self.device = device
        self._host = {}

    def host(self, table: str, col: str) -> np.ndarray:
        key = (table, col)
        if key not in self._host:
            self._host[key] = np.asarray(self.device[table][col])
        return self._host[key]


@dataclass
class Template:
    name: str
    spec: dict
    kind: object


@dataclass
class Query:
    template: Template
    params: dict
    due: float = 0.0          # submitted at, on the host clock
    done: float = math.nan
    answer: object = None
    error: Optional[str] = None


@dataclass
class Run:
    """What a run recorded; the metric readers read it."""
    cell: dict
    config: dict
    traffic: dict
    sizes: dict
    templates: list
    peaks: Optional[dict]
    setup_s: float = 0.0
    window_s: float = 0.0
    queries: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    trace: Optional[dict] = None

    @property
    def completed(self) -> list:
        return [q for q in self.queries
                if not math.isnan(q.done) and q.error is None]


class CompileCounter:
    """Counts JAX's compile requests, how many the persistent cache
    served, and the traces, so a run can show what compiled inside its
    window."""

    def __init__(self):
        import jax
        self.requests = self.hits = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
        elif name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.requests, self.hits, self.traces


def configure_jax(cache: bool = True) -> None:
    """The persistent compile cache at a fixed path inside the checkout,
    keeping every program, so only a checkout's first run compiles."""
    import jax
    if not cache:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def settle_heap() -> None:
    """Collect, then freeze what set-up left on the heap out of the
    collector: a full collection over the millions of objects that
    importing, compiling and drawing the traffic leave would otherwise
    stall the window for hundreds of milliseconds.  The window's own
    garbage is still collected."""
    gc.collect()
    gc.freeze()


def draw_queries(templates: list, config: dict, sizes: dict, seed: int,
                 n: int, salt: str) -> list:
    """``n`` queries: each template's share of them (rounded so the total
    is ``n``), the templates taken in turn, each template's own queries in
    the order its kind drew them."""
    rng = bdata.np_rng(seed, salt)
    shares = np.array([float(t.spec.get("share", 1)) for t in templates])
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[: n - counts.sum()] += 1
    per = {t.name: [Query(t, p) for p in t.kind.draw(
        t.spec, config, sizes, rng, int(c))]
        for t, c in zip(templates, counts)}
    out = []
    while any(per.values()):
        for t in templates:
            if per[t.name]:
                out.append(per[t.name].pop(0))
    return out


@dataclass
class Cell:
    """A cell made ready: its files read, its tables made, and the
    program's server built over them."""
    name: str
    bench: dict
    spec: dict
    config: dict
    traffic: dict
    templates: list
    devices: list
    peaks: Optional[dict]
    tables: dict
    sizes: dict
    ex: object
    srv: object


def load_cell(cell_name: str) -> tuple:
    """``BENCHMARK.json``, the cell's entry, its configuration and traffic
    files, and the traffic's query templates with their kinds."""
    bench = load_json(ROOT, "BENCHMARK.json")
    spec = next(w for w in bench["workloads"] if w["name"] == cell_name)
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == spec["config"])
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(BENCH, "traffic", spec["traffic"] + ".json")
    templates = [Template(q["name"], q, importlib.import_module(
        f"bench.queries.{q['kind']}")) for q in traffic["queries"]]
    return bench, spec, config, traffic, templates


def prepare(cell_name: str, seed: int, t_start: float,
            rows_div: int = 1) -> Cell:
    """Read the cell's files, make its own tables from the seed, and build
    the system under test: a ``QueryServer`` over an ``Executor`` on the
    cell's chips, with no semantic cache."""
    import jax

    bench, spec, config, traffic, templates = load_cell(cell_name)
    devices = jax.devices()[: int(spec["chips"])]
    peaks = None
    if devices[0].platform == "tpu":
        from bench.peaks import peaks_for
        peaks = peaks_for(devices[0].device_kind)

    names = sorted({t for tp in templates for t in tp.kind.tables(tp.spec)})
    tables = {n: bdata.make_table(config["tables"][n], seed, n, rows_div)
              for n in names}
    jax.block_until_ready(tables)
    sizes = {n: int(next(iter(c.values())).shape[0])
             for n, c in tables.items()}
    log(f"setup: tables {sizes} made at "
        f"{time.perf_counter() - t_start:.3f}s")

    from repro.columnar.table import Table
    from repro.launch.mesh import make_host_mesh
    from repro.query import Catalog, CostModel, Executor, QueryServer
    cat = Catalog.from_tables(*(Table.from_arrays(n, c)
                                for n, c in tables.items()))
    if len(devices) > 1:
        ex = Executor(cat, shards=len(devices))
    else:
        ex = Executor(cat, mesh=make_host_mesh(devices),
                      cost_model=CostModel(1))
    srv = QueryServer(ex)
    log(f"setup: catalog registered at "
        f"{time.perf_counter() - t_start:.3f}s")
    return Cell(cell_name, bench, spec, config, traffic, templates, devices,
                peaks, tables, sizes, ex, srv)


def schedule(c: Cell, seed: int):
    """The window's queries, drawn from the seed, and the program's query
    nodes for them: ``max_queries``, of which the window runs as many as
    fit."""
    if c.traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {c.traffic['loop']!r}")
    queries = draw_queries(c.templates, c.config, c.sizes, seed,
                           int(c.traffic.get("max_queries", 256)), "window")
    nodes = [q.template.kind.build(q.template.spec, q.params).node
             for q in queries]
    return queries, nodes


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, rows_div: int = 1) -> dict:
    """Run one cell and return its result line (a dict).  The caller has
    checked the chip.  Device metrics come only from a TPU with a known
    peak."""
    import jax

    counter = CompileCounter()
    c = prepare(cell_name, seed, t_start, rows_div)
    bench, traffic, templates = c.bench, c.traffic, c.templates
    srv, ex, dev, peaks = c.srv, c.ex, c.devices[0], c.peaks
    devices, tables = c.devices, c.tables
    run = Run(c.spec, c.config, traffic, c.sizes, templates, peaks)
    queries, nodes = schedule(c, seed)
    warm_up(srv, queries)
    log(f"setup: warm at {time.perf_counter() - t_start:.3f}s")

    # -- the window --------------------------------------------------------- #
    tracing = bool(trace)
    if tracing:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    trace_s = float(traffic.get("trace_seconds", seconds))
    c0 = counter.snapshot()
    tc0 = ex.trace_count
    settle_heap()
    closed_loop(srv, queries, nodes, run, t_start, tracing, trace_s, seconds)
    req, hits, traces = (b - a for a, b in zip(c0, counter.snapshot()))
    log(f"window: {len(run.queries)} queries in {run.window_s:.4f}s; "
        f"exec.trace_count {ex.trace_count - tc0}; {req} compile requests, "
        f"{hits} of them served by the persistent cache; {traces} traces")

    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    # -- the check, with the program's state freed -------------------------- #
    del c, srv, ex, nodes
    gc.unfreeze()
    gc.collect()
    checks, correct = _check(run, Data(tables), seed)

    # -- metrics ------------------------------------------------------------ #
    if tracing:
        t0 = time.perf_counter()
        raw = btrace.read(btrace.latest_xplane(TRACE_DIR),
                          "cpu" if dev.platform == "cpu" else "tpu")
        run.trace = btrace.reduce(raw)
        log(f"trace: {run.trace['n_ops']} device ops read in "
            f"{time.perf_counter() - t0:.3f}s")
    group = "per_layer" if tracing else "end_to_end"
    metrics = {}
    for m in bench[group]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        if m["source"] == "device_trace" and peaks is None:
            continue            # never a device metric from another device
        value = load_metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    failed = sum(1 for q in run.queries if q.error is not None
                 or math.isnan(q.done))
    out = {"correct": correct, "attempted": len(run.queries),
           "failed": failed, "metrics": metrics, "device": device}
    if tracing:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    return out


class _Traced:
    """The traced part of a window: from the window's start until
    ``seconds`` have passed at the end of a query, under a
    ``bench.window`` span; closing it stops the profiler, so the rest of
    the window is not traced.  It counts the program calls per template
    inside it."""

    def __init__(self, run: Run, on: bool, seconds: float):
        self.run, self.on, self.seconds = run, on, seconds
        self.calls = {t.name: 0 for t in run.templates}
        self._window = self.span(btrace.WINDOW)
        self._window.__enter__()

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def count(self, name: str) -> None:
        if self.on:
            self.calls[name] += 1

    def close_after(self, elapsed: float) -> None:
        if self.on and elapsed >= self.seconds:
            self.close()

    def close(self) -> None:
        if not self.on:
            return
        import jax
        self._window.__exit__(None, None, None)
        self.run.counters = {"program_calls": dict(self.calls)}
        jax.profiler.stop_trace()
        self.on = False


def closed_loop(srv, queries, nodes, run, t_start, tracing, trace_s,
                seconds):
    """One client: each query is submitted when the previous answer is on
    the host.  Queries run until ``seconds`` have passed since the
    window's start; the one running then finishes, and the window ends
    with it."""
    clock = time.perf_counter
    t0 = clock()
    run.setup_s = t0 - t_start
    tr = _Traced(run, tracing, trace_s)
    done = []
    for i, q in enumerate(queries):
        q.due = clock()
        with tr.span("bench.submit"):
            qid = srv.submit(nodes[i])
        with tr.span("bench.drain"):
            try:
                out = srv.drain()
                err = None
            except Exception as e:
                out, err = {}, f"{type(e).__name__}: {e}"
        with tr.span("bench.fetch"):
            if err is None and qid in out:
                q.answer = q.template.kind.fetch(out[qid])
            else:
                q.error = err or "no answer"
        q.done = clock()
        done.append(q)
        tr.count(q.template.name)
        tr.close_after(q.done - t0)
        if q.done - t0 >= seconds:
            break
    tr.close()
    run.queries = done
    run.window_s = done[-1].done - t0


def query_key(q: Query) -> str:
    return q.template.name + json.dumps(q.params, sort_keys=True)


def warm_up(srv, window: list) -> None:
    """Every program the window will run, through the server itself: each
    distinct query of the window once, in the window's order.  A train
    query's grid is compiled into its program, so only the window's own
    literals warm what the window runs."""
    seen = set()
    for q in window:
        if query_key(q) in seen:
            continue
        seen.add(query_key(q))
        srv.submit(q.template.kind.build(q.template.spec, q.params).node)
        for v in srv.drain().values():
            q.template.kind.fetch(v)


def _check(run: Run, data: Data, seed: int):
    """Compare the answers the clients got with the plain reference, on
    one answer of each distinct query of the window, drawn from the seed:
    every compiled program the window ran is checked, and repeats of one
    query add no reference time.  Returns the numbers compared, each with
    its limit, and whether every number is within its limit."""
    limits = run.traffic["check"]["limits"]
    answered = [q for q in run.queries if q.error is None
                and not math.isnan(q.done)]
    missing = len(run.queries) - len(answered)
    groups = {}
    for q in answered:
        groups.setdefault(query_key(q), []).append(q)
    rng = bdata.np_rng(seed, "check")
    sample = [g[rng.integers(len(g))] for g in groups.values()]
    values = {"missing_answers": missing}
    t0 = time.perf_counter()
    for t in run.templates:
        mine = [q for q in sample if q.template is t]
        if not mine:
            continue
        ref = t.kind.Reference(t.spec, data)
        want = [ref.answer(q.params) for q in mine]
        got = [q.answer for q in mine]
        for k, v in t.kind.compare(got, want).items():
            values[k] = max(values.get(k, v), v)
    values["checked_answers"] = len(sample)
    log(f"check: {len(sample)} answers against the reference in "
        f"{time.perf_counter() - t0:.3f}s")
    checks, correct = {}, bool(sample) or not run.queries
    for k, v in values.items():
        if k == "checked_answers":
            continue
        lim = limits[k]
        checks[k] = {"value": v, "limit": lim}
        correct = correct and v <= lim
    return checks, correct
