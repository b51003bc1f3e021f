"""Tier-1 copies of the looped cell's benchmark tests (``benchmark/tests/``
is outside the gate): the work count and the three readers on synthetic
traces, loaded from ``benchmark/tests/test_looped_readers.py`` so that they
are written once, and the cell's ``--rehearse`` run end to end on the CPU."""

import importlib.util
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench_test_looped_readers",
    os.path.join(ROOT, "benchmark", "tests", "test_looped_readers.py"))
_readers = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_readers)
globals().update({name: getattr(_readers, name) for name in dir(_readers)
                  if name.startswith("test_")})


def test_the_cells_rehearsal_runs_is_correct_and_cleans_up():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)      # one CPU device, as the benchmark's tests
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         _readers.CELL, "--seed", str(2**31 + 7), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["rehearsal"] is True
    assert "loop_recompute_pct" in result["metrics"]
    assert all(m["value"] is None for m in result["metrics"].values())
    (verdict,) = [x for x in lines if x.get("phase") == "correct"]
    assert verdict["grad_rel_err"]["value"] <= verdict["grad_rel_err"]["limit"]
    (plan,) = [x for x in lines if x.get("phase") == "loop_plan"]
    assert plan["plan"]["recomputed_applications"] == \
        plan["plan"]["applications"] == 8
    assert not os.path.exists(os.path.join(ROOT, ".bench_work",
                                           _readers.CELL, "models"))
