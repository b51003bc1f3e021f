"""The gates that decide where a program runs say so, or raise.

Bring-up on the chip found each of these answering a question it could not
answer with the CPU: ``"tpu"`` resolving to a CPU device, a kernel gate
returning False on any exception, a benchmark falling back to the CPU and
dividing by an assumed peak, a compile cache nobody outside could place,
router replicas all built on device 0.

What the pre-chip serving benchmark's smokes held, and where it is held now
-----------------------------------------------------------------------------
PR 31 deleted the serving benchmark script of the rounds before the chip, the
shell loop that drove its ``--chaos`` scenario over every fault site, and the
test file that ran them (12 tests, every one ``slow``: the gate never ran
them).  Each smoke asserted a timing ratio on the CPU at toy widths,
which went with it, and a parity or an accounting invariant, which tier 1
holds (every test named below runs under ``-m 'not slow'``; "+" marks an
assertion PR 31 added to that test because no tier-1 test had it):

==================  ====================================  =====================
smoke (flag)        parity / invariant                    tier-1 test
==================  ====================================  =====================
--shared-prefix     greedy parity cache on/off, hits,     test_decode_scheduler::test_prefix_cache_hit_miss_parity
                    hit rate, /metrics hit counters (+)   (+ ..._eviction_then_rematch_parity, test_kv_cache::test_radix_*)
--speculative       parity with drafts; drafted and       test_decode_scheduler::test_spec_parity_matrix (8 cases),
                    accepted counts and their /metrics    ::test_spec_real_drafter_parity,
                    twins (+); 1.0 tokens/step when       ::test_spec_adversarial_drafter_zero_accept_keeps_parity,
                    nothing is accepted                   ::test_spec_stop_token_inside_accepted_draft
--multi-adapter     per-row adapter parity, 2 live        test_lora_serving::test_mixed_adapter_superstep_parity[8]
                    adapters, per-adapter tokens (+)
--overload          admitted streams unchanged under      test_decode_scheduler::test_queue_full_sheds_while_inflight_keeps_parity
                    shedding; 429 + Retry-After;          (+ /metrics twin), ::test_http_queue_full_429_with_retry_after,
                    rejections counted (+ /metrics)       test_qos::test_per_class_bound_sheds_only_that_class
--chaos, and        clean statuses only, reset, replay    test_memledger::test_chaos_fault_sites_leave_clean_ledger[step|prefill_chunk|verify],
its loop over sites parity, strict ledger after a fault;  test_qos::test_preempt_crash_recovers_with_no_leaked_pins[1|8] (+ the
                    qos.preempt at superstep 8 (+ case)   superstep-8 case), test_decode_scheduler::test_http_breaker_503_readyz_and_probe_recovery,
                                                          and one test per remaining site: test_router (disagg.*),
                                                          test_tierstore (tier.*), test_journal, test_streams,
                                                          test_pipeline_serving (pipe.*), test_ssm_serving (ssm.*)
--replicas          same tokens from every replica;       test_router::test_router_greedy_parity_matrix (8 cases),
                    affinity steers a family; a refusing  ::test_router_prefix_affinity_steers_family_to_one_replica,
                    replica is passed over                ::test_router_failover_then_probe_readmission
--disagg            same tokens across the hand-off;      test_router::test_router_disagg_greedy_parity_matrix (8 cases;
                    exports == imports, no failures;      + the /metrics hand-off counter per transport)
                    the prefill replica never decodes
--disagg-elastic    parity on both transports; a flip     same matrix [d2d|host],
                    happens and is counted                test_router::test_router_elastic_shrink_flips_idle_prefill_to_decode
--multistep         superstep parity with step-by-step;   test_decode_scheduler::test_superstep_parity_matrix (12 cases),
                    exact dispatch counts (+ /metrics)    ::test_superstep_dispatch_accounting
--mixed-slo         class admission; a preempted row      test_qos::test_interactive_backlog_outdrains_batch_flood,
                    resumes to the same tokens from its   ::test_preempt_resume_parity_matrix[int8-8],
                    cached pages; only the offender shed  ::test_quota_sheds_offender_only
--ragged            unified tick parity with phased;      test_decode_scheduler::test_unified_parity_matrix (8 cases),
                    chunks and drafts in a fused block    ::test_unified_tick_fuses_chunks_and_drafts,
                                                          test_ragged_attention::test_ragged_kernel_matches_reference_interpret
--sessions          hibernate / resume parity from HBM,   test_tierstore::test_hibernate_resume_parity_matrix[int8-step8]
                    host and disk; promotions counted     (+ /metrics twins), ::test_cross_replica_wake_without_session_id,
                    (+ /metrics)                          ::test_disk_wake_survives_engine_reset
==================  ====================================  =====================

``--memory``, ``--pipeline``, ``--restart`` and ``--hybrid`` had no smoke;
their parity tests are test_memledger, test_pipeline_serving, test_journal
and test_ssm_serving.
"""

import os
import shutil
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


# -- the benchmark and the smoke: no chip, no number -------------------------
# Read from tests/, never edited from here: benchmark/ is the yardstick.

def test_benchmark_peaks_known_kinds_and_no_default():
    from benchmark.lib import peaks
    for kind in ("TPU v5 lite", "TPU v5e"):
        assert peaks.peaks_for(kind)["flops_bf16"] == 197e12
        assert peaks.peaks_for(kind)["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            peaks.peaks_for(kind)


def test_benchmark_without_a_chip_exits_nonzero_and_measures_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "gpt2s-train-1chip", "--seed", "1", "--seconds", "51"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    # run.py makes the cell's (still empty) work directory before it asks
    # for the device; .gitignore lists .bench_work/
    shutil.rmtree(os.path.join(REPO, ".bench_work", "gpt2s-train-1chip"),
                  ignore_errors=True)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_chip_smoke_phase_failure_is_recorded_and_fails_the_run(
        monkeypatch, capsys):
    """A phase that raises does not stop the run, and cannot pass it: the
    ``end`` line names it in ``failed_phases`` and ``main`` returns
    non-zero."""
    import json

    import chip_smoke

    def one_phase_raises(sz, seed, device, on_tpu, phase):
        def boom():
            raise RuntimeError("kernel refused")
        phase("kernels", boom)
        phase("fine", lambda: chip_smoke.emit(phase="fine", x=1))

    one = jax.devices()[:1]          # the tests' 8 virtual devices: --chips 1
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    monkeypatch.setattr(chip_smoke, "run_one_chip", one_phase_raises)
    assert chip_smoke.main(["--tiny"]) != 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    end = next(line for line in lines if line.get("phase") == "end")
    assert end["failed_phases"] == ["kernels"]
    failed = next(line for line in lines if line.get("ok") is False
                  and line.get("phase") == "kernels")
    assert "kernel refused" in failed["error"]
    assert {"phase": "fine", "x": 1} in lines    # later phases still ran
    assert lines[-1]["ok"] is False


def test_chip_smoke_without_a_chip_exits_nonzero_with_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         env=env, cwd=str(tmp_path), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
