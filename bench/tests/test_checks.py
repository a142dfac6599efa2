"""The check that decides ``correct``, exercised on the CPU at a size a
test run holds: a sound run passes it; a run with the timed path broken
underneath fails it, once for each fault the cell can have
(``bench/faults.py``); and the control (the reference one precision step
down) fails it too.

The runs skip the harness's look for a chip (``run_cell`` is called
directly) and drive the rest of a run: set-up, warm-up, the window
through ``QueryServer``, and the check.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_checks.py
"""
import time

import pytest

from bench import control, faults, harness

ROWS_DIV = {"higgs.train": 1024}
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    harness.configure_jax(cache=False)


def run(cell: str, seconds: float = 0.5) -> dict:
    return harness.run_cell(cell, SEED, seconds, False,
                            t_start=time.perf_counter(),
                            rows_div=ROWS_DIV[cell])


@pytest.mark.parametrize("cell", sorted(ROWS_DIV))
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", sorted(ROWS_DIV))
def test_fault_fails_the_check(cell, fault, monkeypatch):
    faults.FAULTS[fault](monkeypatch)
    out = run(cell)
    assert not out["correct"], out["checks"]


def test_check_covers_every_grid():
    """One answer of each distinct grid is compared, so a fault in one
    grid's program cannot hide behind the others."""
    _, _, config, traffic, templates = harness.load_cell("higgs.train")
    qs = harness.draw_queries(templates, config, {"higgs": 1024}, SEED, 41,
                              "window")
    run = harness.Run({}, config, traffic, {"higgs": 1024}, templates, None)
    for q in qs:
        q.done, q.answer = 1.0, q.params["grid"]
    run.queries = qs
    seen = []

    class Kind:
        class Reference:
            def __init__(self, spec, data):
                pass

            def answer(self, p):
                seen.append(p["grid"])
                return p["grid"]

        @staticmethod
        def compare(got, want):
            return {"weight_rel_err": float(got != want)}

    for t in templates:
        t.kind = Kind
    checks, correct = harness._check(run, None, SEED)
    n_grids = len(traffic["queries"][0]["grids"])
    assert correct and checks["weight_rel_err"]["value"] == 0
    assert len(seen) == len({str(g) for g in seen}) == n_grids


@pytest.mark.parametrize("cell,rows_div", [("higgs.train", 64)])
def test_control_fails_the_check(cell, rows_div):
    """The reference one precision step down, at the largest size a test
    holds: bfloat16 training for the float32 cell."""
    r = control.readings(cell, SEED, rows_div)
    assert any(v["fails"] for v in r.values()), r
