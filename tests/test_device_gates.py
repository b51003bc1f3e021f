"""The gates that decide where a program runs say so, or raise.

Bring-up on the chip found each of these answering a question it could not
answer with the CPU: ``"tpu"`` resolving to a CPU device, a kernel gate
returning False on any exception, a benchmark falling back to the CPU and
dividing by an assumed peak, a compile cache nobody outside could place,
router replicas all built on device 0.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from penroz_tpu.models import model as model_mod
from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import NeuralNetworkModel
from penroz_tpu.ops import attention as A
from penroz_tpu.parallel import mesh as mesh_lib
from penroz_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SGD = {"sgd": {"lr": 0.1}}


# -- device strings ---------------------------------------------------------

@pytest.mark.parametrize("device", ["tpu", "TPU", "cuda", "gpu",
                                    "accelerator"])
def test_resolve_device_never_answers_an_accelerator_with_the_cpu(device):
    with pytest.raises(ValueError, match="no such accelerator"):
        model_mod._resolve_device(device)


def test_resolve_device_cpu_unknown_and_none():
    assert model_mod._resolve_device("cpu").platform == "cpu"
    assert model_mod._resolve_device(None) is None
    with pytest.raises(ValueError, match="Unknown device"):
        model_mod._resolve_device("tpuu")


def test_train_worker_unreachable_device_ends_in_error(
        workdir, toy_gpt_layers, toy_shards, monkeypatch):
    """PENROZ_TRAIN_WORKER=1: the child cannot reach the device it was
    asked for (here: no TPU; on a chip host: the serving parent holds it)
    — /progress/ must read Error naming the device, not stay Created."""
    monkeypatch.setenv("PENROZ_TRAIN_WORKER", "1")
    NeuralNetworkModel("wdev", Mapper(toy_gpt_layers, SGD)).serialize(
        sync_flush=True)
    out = NeuralNetworkModel.train_model_on_device("wdev", "tpu", "toy", 0,
                                                   1, 4, 16, 1)
    assert out.status["code"] == "Error"
    assert "'tpu'" in out.status["message"]


def test_worker_that_dies_before_recording_anything_is_marked_error(
        workdir, toy_gpt_layers, monkeypatch):
    """The runtime would not start in the child at all (status still
    Created when it exits): the parent's post-mortem records the death."""
    monkeypatch.setenv("PENROZ_TRAIN_WORKER", "1")
    monkeypatch.setattr(sys, "executable", "false")  # exits 1, writes nothing
    NeuralNetworkModel("wdead", Mapper(toy_gpt_layers, SGD)).serialize(
        sync_flush=True)
    out = NeuralNetworkModel.train_model_on_device("wdead", "cpu", "toy", 0,
                                                   1, 4, 16, 1)
    assert out.status["code"] == "Error"
    assert "worker died" in out.status["message"]


# -- kernel gates -----------------------------------------------------------

def test_tpu_platform_is_an_exact_test_and_takes_both_hint_forms():
    q = jnp.zeros((1, 2, 128, 64))
    mesh = mesh_lib.make_mesh(jax.devices()[:2])
    assert A._tpu_platform(q, "tpu")
    assert A._tpu_platform(q, A.Placement("tpu", mesh))
    for other in ("cpu", "gpu", "TPU", "tpu v5 lite", A.Placement("cpu", mesh)):
        assert not A._tpu_platform(q, other)
    assert A.platform_of(A.Placement("tpu", mesh)) == "tpu"
    assert A.platform_of("cpu") == "cpu" and A.platform_of(None) is None


def test_kernel_gate_that_cannot_tell_raises(monkeypatch):
    """No placement to read (a tracer, no hint, no default device) and a
    backend that will not initialise: the gate raises instead of quietly
    picking the jnp reference."""
    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    pinned = jax.config.jax_default_device
    jax.config.update("jax_default_device", None)
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            A._use_flash(jax.ShapeDtypeStruct((1, 2, 128, 64), jnp.float32),
                         jax.ShapeDtypeStruct((1, 2, 128, 64), jnp.float32))
    finally:
        jax.config.update("jax_default_device", pinned)


def test_placement_hint_follows_the_params(workdir, toy_gpt_layers):
    """One device: the plain platform string.  Params on a mesh of more
    than one device: a Placement naming that mesh, so a meshed and an
    unmeshed engine never share a traced program."""
    from penroz_tpu.parallel import sharding as sharding_lib
    model = NeuralNetworkModel("hint", Mapper(toy_gpt_layers, SGD))
    assert model._placement == "cpu"
    mesh = mesh_lib.make_mesh(jax.devices()[:4], model=2)
    model.params = sharding_lib.shard_params(model.params, mesh)
    assert model._placement == A.Placement("cpu", mesh)
    assert model._platform == "cpu"
    one = mesh_lib.make_mesh(jax.devices()[:1])
    model.params = sharding_lib.shard_params(model.params, one)
    assert model._placement == "cpu"


# -- router replicas --------------------------------------------------------

def test_serve_mesh_gives_each_replica_its_own_devices():
    devices = jax.devices()          # 8 virtual CPU devices
    for replica in range(4):
        mesh = mesh_lib.serve_mesh(model=2, devices=devices, replica=replica)
        assert list(np.asarray(mesh.devices).flat) == \
            devices[2 * replica:2 * replica + 2]
    stages = mesh_lib.serve_stage_meshes(2, model=2, devices=devices,
                                         replica=1)
    assert [list(np.asarray(m.devices).flat) for m in stages] == \
        [devices[4:6], devices[6:8]]


def test_serve_mesh_replica_beyond_the_host_shares_the_first_devices():
    """Too few devices for the replica's own range: it collapses onto
    replica 0's (the CPU parity layout) instead of failing."""
    devices = jax.devices()[:2]
    mesh = mesh_lib.serve_mesh(model=2, devices=devices, replica=1)
    assert list(np.asarray(mesh.devices).flat) == devices


# -- compile cache ----------------------------------------------------------

def test_compile_cache_env_wins_and_nothing_is_set_in_code(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV, "/placed/from/outside")
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.cache_dir() == "/placed/from/outside"
    assert compile_cache.configure() == "/placed/from/outside"
    assert updates == []


def test_compile_cache_default_is_fixed_inside_the_checkout(monkeypatch,
                                                            tmp_path):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir() == compile_cache.DEFAULT_DIR
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                        str(tmp_path / ".jax_cache"))
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    path = compile_cache.configure()
    assert os.path.isdir(path)
    assert updates == [("jax_compilation_cache_dir", path)]


def test_only_the_helper_sets_the_compile_cache_dir():
    offenders = []
    for root, dirs, files in os.walk(REPO):
        # what git tracks: not caches, logs/ or chiprun_out/ (.gitignore)
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "logs", "__pycache__")]
        for name in files:
            path = os.path.join(root, name)
            if not name.endswith(".py") or path in (
                    compile_cache.__file__, os.path.abspath(__file__)):
                continue
            with open(path) as fh:
                if "jax_compilation_cache_dir\"," in fh.read():
                    offenders.append(os.path.relpath(path, REPO))
    assert offenders == []


# -- bench.py ---------------------------------------------------------------

class _Device:
    def __init__(self, kind):
        self.device_kind = kind


def test_peak_flops_known_kinds_and_no_default():
    import bench
    assert bench.peak_flops(_Device("TPU v5 lite")) == 197e12
    assert bench.peak_flops(_Device("TPU v5p")) == 459e12
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(ValueError, match="no published bf16 peak"):
            bench.peak_flops(_Device(kind))


def test_bench_phase_failure_is_recorded_and_fails_the_run(monkeypatch,
                                                           tmp_path):
    import bench
    monkeypatch.setattr(bench, "PARTIAL_PATH", str(tmp_path / "partial.json"))
    monkeypatch.setattr(bench, "_partial", {})
    monkeypatch.setattr(bench, "_failed_phases", {})

    def boom():
        raise RuntimeError("kernel refused")

    bench._phase("decode", boom)
    bench._phase("fine", lambda: bench.emit(x=1))
    assert "kernel refused" in bench._failed_phases["decode"]
    import json
    with open(bench.PARTIAL_PATH) as fh:
        partial = json.load(fh)
    assert partial["x"] == 1 and "decode" in partial["failed_phases"]


def test_bench_without_a_chip_exits_nonzero_and_measures_nothing(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PENROZ_BENCH")}
    env.update(JAX_PLATFORMS="cpu",
               PENROZ_BENCH_PARTIAL=str(tmp_path / "partial.json"))
    out = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                         env=env, cwd=str(tmp_path), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU attached" in out.stderr
    assert out.stdout.strip() == ""
    assert not (tmp_path / "partial.json").exists()


def test_chip_smoke_without_a_chip_exits_nonzero_with_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         env=env, cwd=str(tmp_path), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
