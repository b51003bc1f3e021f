"""REAL multi-host training: two OS processes, cross-process collectives.

Round 1 recorded multi-host as "only mock-tested (unavoidable here)".
It is avoidable: ``jax.distributed`` works
on the CPU backend across local processes, so these tests launch two
workers with the production env wiring (coordinator address + process ids,
two virtual CPU devices each → a 4-device global mesh) and drive the full
``train_model`` / ``evaluate_model`` stack — gradient psum across
processes, rank-strided loaders, ``all_reduce_mean``, and (FSDP case)
cross-host shard-file checkpointing all execute for real.

The subprocess env is rebuilt from scratch (``JAX_*``/``XLA_*``/``PENROZ_*``
dropped, ``JAX_PLATFORMS=cpu`` forced) so the workers never reach for an
accelerator the pytest process may have been launched next to.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# CI tier: real multi-process jax.distributed runs (slowest shard).
pytestmark = pytest.mark.multihost

_LAYERS = [
    {"summation": [
        {"embedding": {"num_embeddings": 64, "embedding_dim": 32},
         "normal": {"mean": 0.0, "std": 0.02}},
        {"position": {"num_embeddings": 16, "embedding_dim": 32},
         "normal": {"mean": 0.0, "std": 0.02}}]},
    {"residual": [
        {"sequential": [
            {"layernorm": {"normalized_shape": 32}},
            {"linear": {"in_features": 32, "out_features": 96}},
            {"attention": {"num_heads": 4, "dropout": 0.0}},
            {"linear": {"in_features": 32, "out_features": 32}}]},
        {"sequential": [
            {"layernorm": {"normalized_shape": 32}},
            {"linear": {"in_features": 32, "out_features": 64}},
            {"gelu": {}},
            {"linear": {"in_features": 64, "out_features": 32}}]}]},
    {"layernorm": {"normalized_shape": 32}},
    {"linear": {"in_features": 32, "out_features": 64, "bias": False}},
    {"softmaxlast": {"dim": -1}},
]
_OPT = {"adamw": {"lr": 1e-3, "betas": [0.9, 0.95], "eps": 1e-8}}


def _cache_env() -> dict:
    """Hand the workers whatever persistent compile cache this pytest
    process runs with — none by default (conftest's cache is opt-in), and
    ``subprocess`` rejects a ``None`` value."""
    import jax
    path = jax.config.jax_compilation_cache_dir
    return {"JAX_COMPILATION_CACHE_DIR": path} if path else {}


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(port: int, proc_id: int, extra: dict,
                devices: int = 2) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "PALLAS_", "PENROZ_",
                                "TURBO_", "PAGED_"))}
    env.update({
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": "2",
        "JAX_PROCESS_ID": str(proc_id),
        **_cache_env(),
    })
    env.update(extra)
    return env


def _run_pair(tmp_path, model_id: str, extra_env: dict, epochs: int = 2,
              devices_per_proc: int = 2, layers=None):
    data_dir = tmp_path / "data"
    data_dir.mkdir(exist_ok=True)
    rng = np.random.default_rng(0)
    np.save(data_dir / "mh_000000",
            rng.integers(0, 64, 8000).astype(np.uint16))
    cfg = {"workdir": str(tmp_path), "model_id": model_id, "dataset": "mh",
           "layers": layers or _LAYERS, "optimizer": _OPT, "epochs": epochs,
           "batch_size": 8, "block_size": 16, "step_size": 8}
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tests", "_multihost_worker.py"),
         json.dumps(cfg)],
        env=_worker_env(port, i, extra_env, devices=devices_per_proc),
        cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(2)]
    outs = []
    for p in procs:
        try:
            # 600s: these workers compile real multi-process programs on a
            # shared CPU that may concurrently run other suites/benches —
            # 420s flaked under load (r04) with both workers healthy.
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"
    return outs


def _assert_reassembles(tmp_path, model_id: str):
    """A fresh single (non-distributed) process must reassemble the
    cross-host-sharded checkpoint into finite full arrays."""
    code = (
        "import os, numpy as np\n"
        f"os.chdir({str(tmp_path)!r})\n"
        "from penroz_tpu.utils import checkpoint\n"
        f"checkpoint.SHM_PATH = os.path.join({str(tmp_path)!r}, 'shm')\n"
        "from penroz_tpu.models.model import NeuralNetworkModel\n"
        f"m = NeuralNetworkModel.deserialize({model_id!r})\n"
        "assert m.status['code'] == 'Trained', m.status\n"
        "for k, v in m.params.items():\n"
        "    assert np.isfinite(np.asarray(v, np.float32)).all(), k\n"
        "print('reassembled', len(m.params))\n")
    env = _worker_env(_free_port(), 0, {})
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID"):
        env.pop(k)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "reassembled" in out.stdout


def test_real_two_process_dp_training(tmp_path):
    """Two processes, 4-device global DP mesh: gradient sync across OS
    processes keeps the replicas bit-identical, and the eval cost
    all_reduce_mean agrees on both hosts."""
    _run_pair(tmp_path, "mhdp", {})
    d0 = np.load(tmp_path / "proc0.npz")
    d1 = np.load(tmp_path / "proc1.npz")
    # same eval cost on every host (the reference's ddp_all_reduce contract,
    # neural_net_model.py:352-354)
    assert float(d0["cost"]) == pytest.approx(float(d1["cost"]), abs=1e-6)
    # replicas did not diverge: cross-process grad psum really synced them
    keys = [k for k in d0.files if k != "cost"]
    assert keys, "workers dumped no params"
    for k in keys:
        np.testing.assert_array_equal(d0[k], d1[k])
    # per-rank log separation (reference ddp.py:87-114 analog): every
    # process mirrored its records into its own rank-tagged file
    for rank in (0, 1):
        path = tmp_path / "logs" / f"penroz_rank{rank}.log"
        assert path.exists(), f"missing per-rank log {path}"
        content = path.read_text()
        assert f"[rank{rank}/2]" in content
        assert f"Per-rank logging for process {rank}/2" in content
        # training records landed in the file, not just the banner
        assert "Epoch" in content or "Training" in content, content[-500:]


def test_real_two_process_fsdp_checkpoint(tmp_path):
    """FSDP across processes: params are cross-host sharded, every process
    writes its shard file, and a fresh single process reassembles the full
    checkpoint (the saves_shards-over-all-items path, for real)."""
    _run_pair(tmp_path, "mhfsdp", {"PENROZ_FSDP": "1"})
    shard_files = list(tmp_path.glob("models/*.shard*.ckpt"))
    assert len(shard_files) == 2, \
        f"expected one shard file per process, got {shard_files}"
    # a fresh single process must reassemble the cross-host-sharded state
    _assert_reassembles(tmp_path, "mhfsdp")


def test_real_tensor_parallel_across_hosts(tmp_path):
    """One device per process, PENROZ_MESH_MODEL=2: the model axis itself
    spans the two OS processes, so every TP all-gather/reduce-scatter and
    the per-host shard-file checkpointing run cross-process for real (the
    round-1 'pure DP only' multi-host restriction, exercised end-to-end)."""
    _run_pair(tmp_path, "mhtp", {"PENROZ_MESH_MODEL": "2"},
              devices_per_proc=1)
    # TP-sharded params cross hosts → per-process shard files
    shard_files = list(tmp_path.glob("models/*.shard*.ckpt"))
    assert len(shard_files) == 2
    # both hosts agree on the eval cost
    d0 = np.load(tmp_path / "proc0.npz")
    d1 = np.load(tmp_path / "proc1.npz")
    assert float(d0["cost"]) == pytest.approx(float(d1["cost"]), abs=1e-6)


_PIPE_BLOCK = {"residual": [
    {"sequential": [
        {"layernorm": {"normalized_shape": 32}},
        {"linear": {"in_features": 32, "out_features": 96}},
        {"attention": {"num_heads": 4, "dropout": 0.0}},
        {"linear": {"in_features": 32, "out_features": 32}}]}]}

_PIPE_LAYERS = [
    {"summation": [
        {"embedding": {"num_embeddings": 64, "embedding_dim": 32},
         "normal": {"mean": 0.0, "std": 0.02}},
        {"position": {"num_embeddings": 16, "embedding_dim": 32},
         "normal": {"mean": 0.0, "std": 0.02}}]},
    _PIPE_BLOCK, _PIPE_BLOCK,
    {"layernorm": {"normalized_shape": 32}},
    {"linear": {"in_features": 32, "out_features": 64, "bias": False}},
    {"softmaxlast": {"dim": -1}},
]


def _single_process_costs(tmp_path, model_id: str, epochs: int = 2):
    """Reference run: same data/config on one process, single device."""
    code = (
        "import os, json, numpy as np\n"
        f"os.chdir({str(tmp_path)!r})\n"
        "from penroz_tpu.utils import checkpoint\n"
        f"checkpoint.SHM_PATH = os.path.join({str(tmp_path)!r}, 'shm')\n"
        "os.makedirs(checkpoint.SHM_PATH, exist_ok=True)\n"
        "from penroz_tpu.models.dsl import Mapper\n"
        "from penroz_tpu.models.model import NeuralNetworkModel\n"
        f"layers = json.loads({json.dumps(json.dumps(_PIPE_LAYERS))})\n"
        f"opt = json.loads({json.dumps(json.dumps(_OPT))})\n"
        f"m = NeuralNetworkModel({model_id!r}, Mapper(layers, opt))\n"
        "m.to_device('cpu')\n"
        f"m.train_model('mh', shard=0, epochs={epochs}, batch_size=8, "
        "block_size=16, step_size=8)\n"
        "assert m.status['code'] == 'Trained', m.status\n"
        "print(json.dumps([p['cost'] for p in m.progress]))\n")
    env = _worker_env(_free_port(), 0, {"PENROZ_TRAIN_MESH": "0"},
                      devices=1)
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID"):
        env.pop(k)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_real_pipeline_stages_across_hosts(tmp_path):
    """PENROZ_MESH_PIPE=2 over two OS processes (2 virtual devices each):
    the pipe axis is outermost, so stage 0 lives entirely on process 0 and
    stage 1 on process 1 — every GPipe ppermute handoff crosses the
    process boundary for real.  Per-epoch costs must match a single-device
    run on the identical data (the schedule is the same math), and a fresh
    single process must be able to load the resulting checkpoint."""
    _run_pair(tmp_path, "mhpipe", {"PENROZ_MESH_PIPE": "2"},
              layers=_PIPE_LAYERS)
    d0 = np.load(tmp_path / "proc0.npz")
    d1 = np.load(tmp_path / "proc1.npz")
    assert float(d0["cost"]) == pytest.approx(float(d1["cost"]), abs=1e-6)

    # training costs == single-device run on the same data (no DP across
    # hosts: both processes fed identical batches)
    ref_costs = _single_process_costs(tmp_path, "mhpipe_ref")
    code = (
        "import os, json\n"
        f"os.chdir({str(tmp_path)!r})\n"
        "from penroz_tpu.utils import checkpoint\n"
        f"checkpoint.SHM_PATH = os.path.join({str(tmp_path)!r}, 'shm')\n"
        "from penroz_tpu.models.model import NeuralNetworkModel\n"
        "m = NeuralNetworkModel.deserialize('mhpipe')\n"
        "assert m.status['code'] == 'Trained', m.status\n"
        "import numpy as np\n"
        "for k, v in m.params.items():\n"
        "    assert np.isfinite(np.asarray(v)).all(), k\n"
        "print(json.dumps([p['cost'] for p in m.progress]))\n")
    env = _worker_env(_free_port(), 0, {})
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID"):
        env.pop(k)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=str(tmp_path), capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    pipe_costs = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(pipe_costs) == len(ref_costs) and pipe_costs
    for a, b in zip(pipe_costs, ref_costs):
        assert a == pytest.approx(b, rel=2e-4), (pipe_costs, ref_costs)


def test_real_pipeline_with_fsdp_across_hosts(tmp_path):
    """PENROZ_MESH_PIPE=2 + PENROZ_FSDP=1 over two OS processes: stages
    span the processes AND the stacked param storage data-shards within
    each stage's host — the ZeRO×PP composition exercised with real
    cross-process collectives, shard-file checkpointing included."""
    _run_pair(tmp_path, "mhpipez",
              {"PENROZ_MESH_PIPE": "2", "PENROZ_FSDP": "1"},
              layers=_PIPE_LAYERS)
    d0 = np.load(tmp_path / "proc0.npz")
    d1 = np.load(tmp_path / "proc1.npz")
    assert float(d0["cost"]) == pytest.approx(float(d1["cost"]), abs=1e-6)
    assert np.isfinite(float(d0["cost"]))
    # the pipe-stacked, FSDP-sharded state really went through the
    # shard-file path (one file per process), not a whole-blob fallback
    shard_files = list(tmp_path.glob("models/*.shard*.ckpt"))
    assert len(shard_files) == 2, shard_files
    _assert_reassembles(tmp_path, "mhpipez")
