"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell as new files plus entries in ``BENCHMARK.json`` — no file that is there
is edited.  Shown in a temporary copy, run as a rehearsal; the cell added is
a serving one, so this is also the end-to-end test of ``kinds/serve_open.py``
(no serving cell is in ``BENCHMARK.json`` yet: PERF.md, Open questions).
Where the manifest already has a metric the new cell reports, the cell's
name joins that entry's ``workloads``."""

import filecmp
import json
import os
import shutil

from helpers import ROOT, last_json, run_cell


def test_new_config_mix_metric_and_cell_are_files_only(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.relpath(os.path.join(d, f), bench)
              for d, _, fs in os.walk(bench) for f in fs}

    # a configuration: another GPT-2 width, its own file
    cfg = json.load(open(bench / "configs" / "gpt2-large-hf.json"))
    cfg.update(name="gpt2-medium-hf", n_embd=1024, n_head=16, n_layer=24,
               source="https://huggingface.co/openai-community/gpt2-medium "
                      "config.json")
    cfg["rehearse"].update(n_embd=32, n_head=2, n_layer=1)
    json.dump(cfg, open(bench / "configs" / "gpt2-medium-hf.json", "w"))
    # a traffic mix: the same generator, other parameters
    mix = json.load(open(bench / "traffic" / "chat_steady.json"))
    mix["rehearse"].update(rate_per_s=6.0)
    json.dump(mix, open(bench / "traffic" / "chat_brisk.json", "w"))
    # a per-layer metric: a reader of its own
    (bench / "metrics" / "streamed_gaps.py").write_text(
        '"""Load generator: gaps between streamed tokens seen in the '
        'window."""\n\n\ndef read(art):\n'
        '    return art.get("measured", {}).get("gaps")\n')
    manifest = json.load(open(tmp_path / "BENCHMARK.json"))
    manifest["configs"].append({
        "name": "gpt2-medium-hf", "source": cfg["source"],
        "file": "benchmark/configs/gpt2-medium-hf.json", "reduced": [],
        "why": "a third width of the family"})
    manifest["workloads"].append({
        "name": "gpt2m-chat-brisk", "config": "gpt2-medium-hf",
        "traffic": "chat_brisk", "chips": 1, "why": "shows the harness "
        "takes a new cell as files"})
    cell = ["gpt2m-chat-brisk"]
    # the serving metrics: their readers and the generator's numbers are
    # files of the benchmark already; a cell that reports them adds entries,
    # or its name to the entries that are there

    def enter(section, entry):
        have = [m for m in manifest[section] if m["name"] == entry["name"]]
        if have:
            have[0]["workloads"] = have[0]["workloads"] + cell
        else:
            manifest[section].append({**entry, "workloads": cell})

    for name, unit, better in (("serve_tokens_per_s", "tokens/s", "higher"),
                               ("itl_ms.p90", "ms", "lower"),
                               ("ttft_ms.p50", "ms", "lower")):
        enter("end_to_end", {"name": name, "unit": unit, "better": better,
                             "bound": 0.1, "source": "host_clock"})
    enter("per_layer", {
        "name": "tick_ms.p50", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "model runtime",
        "moves": "serve_tokens_per_s"})
    enter("per_layer", {
        "name": "streamed_gaps", "unit": "gaps", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "serve_tokens_per_s"})
    json.dump(manifest, open(tmp_path / "BENCHMARK.json", "w"))

    env = {"PYTHONPATH": ROOT}      # the program, which the copy lacks
    rc, lines, err = run_cell(str(tmp_path), "gpt2m-chat-brisk",
                              "--rehearse", trace=1, env=env)
    assert rc == 0, err[-3000:]
    result = last_json(lines)
    assert result["correct"] is True
    assert "streamed_gaps" in result["metrics"]
    assert "tick_ms.p50" in result["metrics"]
    rc, lines, err = run_cell(str(tmp_path), "gpt2m-chat-brisk",
                              "--rehearse", trace=0, env=env)
    assert rc == 0, err[-3000:]
    assert set(last_json(lines)["metrics"]) == {
        "serve_tokens_per_s", "itl_ms.p90", "ttft_ms.p50", "setup_s"}

    # nothing that was there was touched
    for rel in before:
        assert filecmp.cmp(os.path.join(ROOT, "benchmark", rel),
                           bench / rel, shallow=False), rel
