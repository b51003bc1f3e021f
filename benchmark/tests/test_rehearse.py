"""The harness end to end on the CPU, at the configurations' tiny
``rehearse`` sizes: the driver's command line, the last line's keys, the
comparison with the reference, and what a run leaves behind."""

import json

import pytest

from helpers import ROOT, last_json, leftovers, run_cell

MANIFEST = json.load(open(f"{ROOT}/BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_checks_and_cleans_up(cell, trace):
    rc, lines, err = run_cell(ROOT, cell, "--rehearse", trace=trace)
    assert rc == 0, err[-3000:]
    result = last_json(lines)
    assert KEYS <= set(result) <= KEYS | {"breakdown", "checks"}
    # every number compared beside its limit, last in the line and as the
    # last lines of stderr
    assert list(result)[-1] == "checks" and result["checks"]
    assert all({"value"} < set(c) <= {"value", "limit", "at_least"}
               for c in result["checks"].values())
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("run.py: correct=True ") for line in tail)
    assert {line.split()[2] for line in tail} == set(result["checks"])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    # a rehearsal names its platform and prints no number under a metric
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["rehearsal"] is True
    assert result["metrics"] and all(
        m["value"] is None for m in result["metrics"].values())
    names = set(result["metrics"])
    kind = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in MANIFEST[kind]
              if cell in m.get("workloads", [cell])}
    assert names <= listed
    if not trace:
        assert names == listed and "setup_s" in names
    # every number compared is printed beside its limit
    (verdict,) = [json.loads(x) for x in lines
                  if '"phase": "correct"' in x]
    assert any("limit" in v for v in verdict.values()
               if isinstance(v, dict))
    assert leftovers(ROOT) == []


def test_without_a_tpu_it_fails_and_prints_no_result():
    rc, lines, err = run_cell(ROOT, CELLS[0])
    assert rc != 0 and lines == []
    assert "no TPU" in err
