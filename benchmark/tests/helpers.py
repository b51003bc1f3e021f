"""Running the one command as the driver does, from a test."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cell(root, workload, *extra, seed=2**31 + 5, seconds=1, trace=0,
             env=None, timeout=600):
    """(return code, stdout lines, stderr) of ``run.py`` started from
    ``root``."""
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("JAX_COMPILATION_CACHE_DIR", None)
    full_env.update(env or {})
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), *extra],
        cwd=root, env=full_env, capture_output=True, text=True,
        timeout=timeout)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr


def last_json(lines):
    return json.loads(lines[-1])


def leftovers(root):
    """What a run may not leave behind: checkpoints and shards, in the
    checkout or in shared memory."""
    import glob
    import hashlib
    tag = hashlib.sha1(os.path.abspath(root).encode()).hexdigest()[:12]
    found = glob.glob(os.path.join(root, ".bench_work", "*", "*"))
    found += glob.glob(f"/dev/shm/penroz_bench_{tag}*")
    found += glob.glob(os.path.join(root, "models", "model_bench*"))
    found += glob.glob(os.path.join(root, "data", "benchtoks*"))
    return found


def tree_with_a_serving_cell(tmp_path) -> str:
    """A copy of the benchmark in ``tmp_path`` whose manifest also holds a
    serving cell, made of files that are there (``gpt2-large-hf`` under
    ``chat_steady``) plus entries: none is in ``BENCHMARK.json`` yet
    (PERF.md, Open questions).  Returns the cell's name; run it with
    ``PYTHONPATH=ROOT``, since the copy lacks the program."""
    import shutil
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "gpt2-large-hf.json")))
    manifest["configs"].append({
        "name": "gpt2-large-hf", "source": cfg["source"],
        "file": "benchmark/configs/gpt2-large-hf.json", "reduced": [],
        "why": "the serving path"})
    manifest["workloads"].append({
        "name": "gpt2l-chat-steady", "config": "gpt2-large-hf",
        "traffic": "chat_steady", "chips": 1, "why": "a serving cell"})
    manifest["end_to_end"].append({
        "name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["gpt2l-chat-steady"]})
    json.dump(manifest, open(tmp_path / "BENCHMARK.json", "w"))
    return "gpt2l-chat-steady"
