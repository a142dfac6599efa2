"""Warm-start persistence — SemanticCache snapshots that survive restarts.

The paper's serving story pays a cold-start tax twice over: the semantic
cache re-materializes every bitmap/result from scratch, and the cost
model re-converges its measured calibration overlay from a fresh ledger.
This module serializes both — the cache's serializable residents plus a
calibration-shaped snapshot of the model's current constants
(``CostModel.calibration_snapshot``) — into ONE ``.npz`` file, so a
recycled ``QueryServer`` warms instantly instead of replaying its whole
history.

Format: a single ``np.savez`` archive holding a ``manifest`` JSON string
and one flat array per serialized buffer.  Entry keys are stored as
``repr(key)`` and recovered with ``ast.literal_eval`` — only keys that
round-trip exactly (result fingerprints, bitmap interval keys: tuples of
str/int) are persisted; build/subplan entries key on live dataclasses
and are deliberately skipped (they rebuild cheaply and their values hold
device-layout state).  Values may be scalars, arrays, tuples of arrays,
or ``columnar.Table``s.

Staleness is rejected at TWO granularities:

* whole file — missing/corrupt archives, unparsable manifests, and
  ``format`` mismatches load as None (never raise into the serve path);
* per entry — every entry carries its dependency tables; an entry whose
  saved table version disagrees with the loading catalog's CURRENT
  version (or whose table no longer exists) is dropped, so a snapshot
  taken before a mutation can never serve stale bytes.

Restored entries land in the cache's HOST tier (``SemanticCache.restore``)
— they arrive as host buffers from disk anyway, and first-touch
promotion moves the hot ones back onto the device tier on demand.
"""
from __future__ import annotations

import ast
import json
import os
import tempfile
from typing import Mapping, Optional

import numpy as np

from repro.columnar.table import Column, Table

FORMAT_VERSION = 1


# --------------------------------------------------------------------------- #
# value (de)serialization

def _encode_value(value, arrays: dict, prefix: str):
    """Encode one cache value into a JSON spec, appending flat numpy
    buffers to ``arrays``.  Returns None when the value holds something
    we don't serialize (objects, callables, ...)."""
    if isinstance(value, (bool, int, float, str)) or value is None:
        return {"t": "scalar", "v": value}
    if isinstance(value, (np.integer, np.floating, np.bool_)):
        return {"t": "scalar", "v": value.item()}
    if isinstance(value, Table):
        cols = {}
        for name, col in value.columns.items():
            ref = f"{prefix}_c{len(arrays)}"
            arrays[ref] = np.asarray(col.data)
            cols[name] = ref
        return {"t": "table", "name": value.name,
                "version": int(value.version), "cols": cols}
    if isinstance(value, (tuple, list)):
        items = []
        for i, v in enumerate(value):
            spec = _encode_value(v, arrays, f"{prefix}_i{i}")
            if spec is None:
                return None
            items.append(spec)
        return {"t": "tuple", "items": items}
    try:
        arr = np.asarray(value)
    except Exception:
        return None
    if arr.dtype == object:
        return None
    if arr.ndim == 0:
        return {"t": "scalar", "v": arr.item()}
    ref = f"{prefix}_a"
    arrays[ref] = arr
    return {"t": "array", "ref": ref}


def _decode_value(spec, npz):
    t = spec["t"]
    if t == "scalar":
        return spec["v"]
    if t == "array":
        return np.asarray(npz[spec["ref"]])
    if t == "tuple":
        return tuple(_decode_value(s, npz) for s in spec["items"])
    assert t == "table", t
    cols = {name: Column(np.asarray(npz[ref]), name, "host")
            for name, ref in spec["cols"].items()}
    return Table(spec["name"], cols, None, int(spec["version"]))


def _key_repr(key) -> Optional[str]:
    """``repr`` a cache key iff ``ast.literal_eval`` recovers it exactly
    — the persistable-key gate (tuples of str/int pass; dataclasses,
    live nodes, and anything repr-lossy are skipped)."""
    r = repr(key)
    try:
        back = ast.literal_eval(r)
    except (ValueError, SyntaxError):
        return None
    return r if back == key else None


# --------------------------------------------------------------------------- #
# save / load

def save_state(path: str, cache, *, cost_model=None,
               table_versions: Optional[Mapping[str, int]] = None) -> dict:
    """Snapshot ``cache``'s serializable residents (every tier — the
    load side re-tiers into host) plus the cost model's calibration to
    ``path``.  Atomic: written to a temp file in the target directory
    and renamed over, so a killed process never leaves a torn snapshot.
    Returns a summary dict (``saved``, ``skipped``, ``path``)."""
    arrays: dict = {}
    entries = []
    skipped = 0
    with cache._lock:
        residents = list(cache._entries.values())
    for i, e in enumerate(residents):
        krepr = _key_repr(e.key)
        spec = (_encode_value(e.value, arrays, f"e{i}")
                if krepr is not None else None)
        if spec is None:
            skipped += 1
            continue
        entries.append({
            "key": krepr, "kind": e.kind, "n_bytes": int(e.n_bytes),
            "recompute_s": float(e.recompute_s),
            "tables": list(e.tables), "hits": int(e.hits),
            "interval": list(e.interval) if e.interval else None,
            "tenant": e.tenant, "value": spec})
    manifest = {
        "format": FORMAT_VERSION,
        "table_versions": {str(k): int(v) for k, v in
                           (table_versions or {}).items()},
        "calibration": (cost_model.calibration_snapshot()
                        if cost_model is not None else None),
        "entries": entries,
    }
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, manifest=np.frombuffer(
                json.dumps(manifest).encode(), dtype=np.uint8), **arrays)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return {"path": path, "saved": len(entries), "skipped": skipped}


def load_state(path: str,
               table_versions: Optional[Mapping[str, int]] = None
               ) -> Optional[dict]:
    """Parse a snapshot into ``{"calibration": ..., "entries": [...]}``
    without touching any cache.  Returns None for missing, corrupt, or
    format-mismatched files; entries whose dependency tables drifted
    from ``table_versions`` (or vanished) are dropped individually and
    counted in ``"stale"``."""
    try:
        npz = np.load(path, allow_pickle=False)
        manifest = json.loads(bytes(np.asarray(npz["manifest"])).decode())
    except (OSError, ValueError, KeyError, json.JSONDecodeError):
        return None
    if not isinstance(manifest, dict) \
            or manifest.get("format") != FORMAT_VERSION:
        return None
    saved_versions = manifest.get("table_versions", {})
    current = {str(k): int(v) for k, v in (table_versions or {}).items()}
    out, stale = [], 0
    for ent in manifest.get("entries", ()):
        try:
            key = ast.literal_eval(ent["key"])
            deps = tuple(ent["tables"])
            if table_versions is not None and any(
                    t not in current
                    or current[t] != saved_versions.get(t)
                    for t in deps):
                stale += 1
                continue
            value = _decode_value(ent["value"], npz)
        except (ValueError, SyntaxError, KeyError, AssertionError):
            stale += 1
            continue
        interval = tuple(ent["interval"]) if ent.get("interval") else None
        out.append({"key": key, "value": value, "kind": ent["kind"],
                    "n_bytes": int(ent["n_bytes"]),
                    "recompute_s": float(ent["recompute_s"]),
                    "tables": deps, "hits": int(ent.get("hits", 0)),
                    "interval": interval, "tenant": ent.get("tenant")})
    return {"calibration": manifest.get("calibration"),
            "entries": out, "stale": stale}


def warm_start(path: str, cache, *, cost_model=None,
               table_versions: Optional[Mapping[str, int]] = None) -> dict:
    """Load a snapshot and replay it: entries into ``cache.restore``
    (host tier first), calibration onto ``cost_model``.  Safe no-op
    summary on a missing/corrupt/stale file."""
    state = load_state(path, table_versions)
    if state is None:
        return {"restored": 0, "stale": 0, "calibrated": False,
                "loaded": False}
    restored = 0
    for ent in state["entries"]:
        if cache.restore(ent["key"], ent["value"], kind=ent["kind"],
                         n_bytes=ent["n_bytes"],
                         recompute_s=ent["recompute_s"],
                         tables=ent["tables"], interval=ent["interval"],
                         tenant=ent["tenant"], hits=ent["hits"]):
            restored += 1
    calibrated = False
    cal = state["calibration"]
    if cost_model is not None and isinstance(cal, dict):
        cost_model.apply_calibration(cal)
        calibrated = True
    return {"restored": restored, "stale": state["stale"],
            "calibrated": calibrated, "loaded": True}
