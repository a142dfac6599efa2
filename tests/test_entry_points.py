"""The repository's entry points, run as a user runs them.

* ``import repro.query`` loads no part of the LM modules left under
  ``configs/``, ``models/``, ``train/`` and ``distributed/compression``:
  the query engine is layered below them and must stay so.
* The README's two examples run to their end (each asserts its own
  results: the declarative query equals the hand-written engine sequence).
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LM_MODULES = ("repro.configs", "repro.models", "repro.train",
              "repro.distributed.compression")


def _run(args, cwd, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_query_engine_imports_no_lm_module(tmp_path):
    probe = ("import sys, repro.query; "
             f"print([m for m in sys.modules if m.startswith({LM_MODULES!r})])")
    out = _run(["-c", probe], tmp_path)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "[]", out.stdout


@pytest.mark.parametrize("script", ["quickstart.py", "analytics_pipeline.py"])
def test_example_runs(script, tmp_path):
    out = _run([os.path.join(ROOT, "examples", script)], tmp_path)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
