"""BENCHMARK.json against the limits of the builder's contract that a file
can be checked for, and the files it names."""

import json
import os
import re
import shutil

from helpers import ROOT, run_cell

B = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_sizes():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert 1 <= len(B["workloads"]) <= 24 and 1 <= len(B["configs"]) <= 24
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in B["paths"])
    assert B["command"][-1].startswith(B["paths"][0] + "/")


def _one_line(s):
    return 1 <= len(s) <= 200 and s.isprintable() and s.isascii()


def test_configs_and_cells():
    names = [c["name"] for c in B["configs"]]
    assert len(set(names)) == len(names)
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert all(_one_line(c[k]) for k in ("source", "why"))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert all(NAME.match(k) and not k.endswith(("_dim", "_rank"))
                   for k in c["reduced"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "reference", cfg["reference"] + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _one_line(w["why"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    assert {c["name"] for c in B["configs"]} == {w["config"]
                                                 for w in B["workloads"]}
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 4)


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(set(names)) == len(names)
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    layers = set()
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["source"] in SOURCES
        assert _one_line(m["layer"])
        layers.add(m["layer"])
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".py"))
        # the cells it lists report the end-to-end metric it moves
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", []):
            assert cell in moved.get("workloads", cells)
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    # every cell reports set-up, another end-to-end metric and a per-layer one
    for cell in cells:
        assert any(cell in m.get("workloads", cells)
                   for m in B["end_to_end"] if m["name"] != "setup_s")
        assert any(cell in m.get("workloads", cells) for m in B["per_layer"])
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    assert all(layer in perf for layer in layers)


def test_a_bare_copy_fails_and_prints_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, lines, err = run_cell(str(tmp_path), B["workloads"][0]["name"],
                              "--rehearse", env={"PYTHONPATH": ""})
    assert rc != 0 and lines == []
    assert "penroz_tpu" in err
