"""Parallelism tests on the virtual 8-device CPU mesh: real sharded
compilation + execution (the reference only mocks its launcher —
SURVEY.md §4 calls out this upgrade)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from penroz_tpu.parallel import dist, mesh as mesh_lib, sharding

# CI tier: heavier compiles (see pyproject markers / ci.yml shards).
pytestmark = pytest.mark.runtime


def test_virtual_device_count(cpu_devices):
    assert len(cpu_devices) == 8


def test_make_mesh_shapes(cpu_devices):
    mesh = mesh_lib.make_mesh(cpu_devices)
    assert mesh.shape == {"data": 8, "model": 1, "sequence": 1, "expert": 1,
                          "pipe": 1}
    mesh = mesh_lib.make_mesh(cpu_devices, model=2, sequence=2)
    assert mesh.shape == {"data": 2, "model": 2, "sequence": 2, "expert": 1,
                          "pipe": 1}
    mesh = mesh_lib.make_mesh(cpu_devices, model=2, expert=2, pipe=2)
    assert mesh.shape == {"data": 1, "model": 2, "sequence": 1, "expert": 2,
                          "pipe": 2}
    with pytest.raises(ValueError):
        mesh_lib.make_mesh(cpu_devices, model=3)


def test_param_spec_rules(cpu_devices):
    mesh = mesh_lib.make_mesh(cpu_devices, model=2)
    # column-parallel: expanding projection
    assert sharding.param_spec("w.qkv", (96, 32), mesh) == P("model", None)
    # row-parallel: contracting projection
    assert sharding.param_spec("w.out", (32, 96), mesh) == P(None, "model")
    # square → replicated
    assert sharding.param_spec("w.sq", (32, 32), mesh) == P()
    # vector → replicated
    assert sharding.param_spec("w.b", (32,), mesh) == P()
    # embedding-like table shards the vocab dim
    assert sharding.param_spec("layers.0.weight", (50304, 64), mesh) == \
        P("model", None)
    # indivisible dims → replicated
    assert sharding.param_spec("w.odd", (33, 7), mesh) == P()


def test_data_parallel_grad_equivalence(cpu_devices):
    """Grads from a data-sharded step == single-device grads."""
    mesh = mesh_lib.make_mesh(cpu_devices[:4])

    def loss(w, x):
        return jnp.mean((x @ w) ** 2)

    w = jnp.asarray(np.random.default_rng(0).normal(size=(8, 4)),
                    jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(16, 8)),
                    jnp.float32)
    g_single = jax.grad(loss)(w, x)

    w_repl = jax.device_put(w, mesh_lib.replicated(mesh))
    x_shard = jax.device_put(x, mesh_lib.batch_sharding(mesh))
    g_sharded = jax.jit(jax.grad(loss))(w_repl, x_shard)
    np.testing.assert_allclose(np.asarray(g_single), np.asarray(g_sharded),
                               rtol=1e-5)


def test_tensor_parallel_forward_equivalence(cpu_devices):
    """Column-sharded matmul output == replicated matmul output."""
    mesh = mesh_lib.make_mesh(cpu_devices, model=2)
    w = jnp.asarray(np.random.default_rng(0).normal(size=(64, 16)),
                    jnp.float32)  # column-parallel (out, in)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 16)),
                    jnp.float32)
    expected = x @ w.T
    w_tp = jax.device_put(w, sharding.param_shardings({"w.big": w}, mesh)["w.big"])
    out = jax.jit(lambda w, x: x @ w.T)(w_tp, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-4)


def test_dryrun_multichip_entrypoint():
    import __graft_entry__ as entrypoints
    entrypoints.dryrun_multichip(8)


def test_dryrun_multichip_hermetic_env(monkeypatch):
    """The public dryrun must never touch the parent's jax backend: it
    re-execs in a child with JAX_PLATFORMS=cpu, the forced device count,
    and the compile cache the one placement rule names."""
    import __graft_entry__ as entrypoints
    captured = {}

    def fake_run(cmd, env=None, **kwargs):
        captured["cmd"] = cmd
        captured["env"] = env

        class Result:
            returncode = 0
            stdout = ""
            stderr = ""
        return Result()

    # Poison the parent env the way a TPU-default process would.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=2 --foo=bar")
    monkeypatch.delenv("_PENROZ_DRYRUN_CHILD", raising=False)
    monkeypatch.setattr("subprocess.run", fake_run)
    entrypoints.dryrun_multichip(4)

    env = captured["env"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["_PENROZ_DRYRUN_CHILD"] == "1"
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=2" not in env["XLA_FLAGS"]
    assert "--foo=bar" in env["XLA_FLAGS"]
    assert "dryrun_multichip(4)" in captured["cmd"][-1]


def test_graft_entry_compiles():
    import __graft_entry__ as entrypoints
    fn, args = entrypoints.entry()
    # single forward on tiny slice would be heavy (124M params on CPU);
    # compile-check via eval_shape only, as the driver does single-chip.
    out = jax.eval_shape(fn, *args)
    assert out.shape == ()


def test_ring_attention_matches_reference(cpu_devices):
    from penroz_tpu.ops.attention import causal_attention_reference
    from penroz_tpu.parallel.ring_attention import ring_attention
    mesh = mesh_lib.make_mesh(cpu_devices, sequence=8, model=1)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 4, 64, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 2, 64, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 2, 64, 16)).astype(np.float32))
    ref = causal_attention_reference(q, k, v)
    out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_gradients(cpu_devices):
    from penroz_tpu.ops.attention import causal_attention_reference
    from penroz_tpu.parallel.ring_attention import ring_attention
    mesh = mesh_lib.make_mesh(cpu_devices, sequence=4, model=1)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 2, 32, 8)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 32, 8)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 32, 8)).astype(np.float32))
    g_ring = jax.grad(lambda *a: ring_attention(*a, mesh).sum(),
                      argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: causal_attention_reference(*a).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_train_epoch_with_ring_attention(cpu_devices, toy_gpt_layers):
    """Full jitted train epoch with sequence parallelism enabled."""
    import jax.numpy as jnp
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import CompiledArch
    mesh = mesh_lib.make_mesh(cpu_devices[:4], sequence=4, model=1)
    optim = {"sgd": {"lr": 0.1}}
    mapper = Mapper(toy_gpt_layers, optim)
    arch = CompiledArch.get(mapper.layers)
    params, buffers = mapper.init_params(arch.mods, seed=0)
    opt_state = mapper.to_optimizer().init(params)
    epoch_fn = arch.train_epoch_fn(optim, 1, False, None, sp_mesh=mesh)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 64, (1, 2, 16), dtype=np.int32))
    y = jnp.asarray(rng.integers(0, 64, (1, 2, 16), dtype=np.int32))
    _, _, _, cost_sp, _ = epoch_fn(params, opt_state, buffers, x, y,
                                   jax.random.key(0))
    # compare against the non-sequence-parallel epoch
    params2, buffers2 = mapper.init_params(arch.mods, seed=0)
    opt_state2 = mapper.to_optimizer().init(params2)
    epoch_plain = arch.train_epoch_fn(optim, 1, False, None)
    _, _, _, cost_plain, _ = epoch_plain(params2, opt_state2, buffers2, x, y,
                                         jax.random.key(0))
    np.testing.assert_allclose(float(cost_sp), float(cost_plain), rtol=1e-5)


def test_train_model_uses_data_parallel_mesh(workdir, toy_gpt_layers,
                                             toy_shards, monkeypatch):
    """train_model shards the micro-batch over all 8 virtual devices and
    matches the single-device run numerically (same data, same init)."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    optim = {"sgd": {"lr": 0.1}}
    dp = NeuralNetworkModel("dp8", Mapper(toy_gpt_layers, optim)).to_device("cpu")
    single = NeuralNetworkModel("dp1", Mapper(toy_gpt_layers, optim)).to_device("cpu")
    mesh = dp._training_mesh(micro_batch=8, block_size=16)
    assert mesh is not None and mesh.shape["data"] == 8
    dp.train_model("toy", shard=0, epochs=2, batch_size=8, block_size=16,
                   step_size=8)
    monkeypatch.setenv("PENROZ_TRAIN_MESH", "0")
    single.train_model("toy", shard=0, epochs=2, batch_size=8, block_size=16,
                       step_size=8)
    assert dp.status["code"] == "Trained"
    np.testing.assert_allclose(dp.progress[-1]["cost"],
                               single.progress[-1]["cost"], rtol=1e-4)
    for k in dp.params:
        np.testing.assert_allclose(np.asarray(dp.params[k], np.float32),
                                   np.asarray(single.params[k], np.float32),
                                   atol=1e-5)


def test_evaluate_model_uses_data_parallel_mesh(workdir, toy_gpt_layers,
                                                toy_shards, monkeypatch):
    """/evaluate/ shards the eval batch over all 8 virtual devices and
    matches the single-device cost (reference evaluates DDP-sharded across
    all workers: neural_net_model.py:319-354; pre-round-4 this path used
    one device per process regardless of host capacity)."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    optim = {"sgd": {"lr": 0.1}}
    dp = NeuralNetworkModel("evdp",
                            Mapper(toy_gpt_layers, optim)).to_device("cpu")
    mesh = dp._eval_mesh(8, 16)
    assert mesh is not None and mesh.shape["data"] == 8
    cost_dp = dp.evaluate_model("toy", None, 0, 2, 8, 16, 1)
    monkeypatch.setenv("PENROZ_TRAIN_MESH", "0")
    single = NeuralNetworkModel("evs1",
                                Mapper(toy_gpt_layers, optim)).to_device("cpu")
    cost_single = single.evaluate_model("toy", None, 0, 2, 8, 16, 1)
    np.testing.assert_allclose(cost_dp, cost_single, rtol=1e-5)


def test_evaluate_model_sequence_parallel(workdir, toy_gpt_layers,
                                          toy_shards, monkeypatch):
    """Sequence-parallel eval (PENROZ_MESH_SEQUENCE=2): the block is
    sharded over the seq axis and the ring attention reproduces the
    single-device cost — the seq-axis chips shard real work instead of
    replicating it."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    optim = {"sgd": {"lr": 0.1}}
    monkeypatch.setenv("PENROZ_MESH_SEQUENCE", "2")
    sp = NeuralNetworkModel("evsp",
                            Mapper(toy_gpt_layers, optim)).to_device("cpu")
    mesh = sp._eval_mesh(8, 16)
    assert mesh is not None and mesh.shape["sequence"] == 2
    cost_sp = sp.evaluate_model("toy", None, 0, 2, 8, 16, 1)
    monkeypatch.delenv("PENROZ_MESH_SEQUENCE")
    monkeypatch.setenv("PENROZ_TRAIN_MESH", "0")
    single = NeuralNetworkModel("evsp1",
                                Mapper(toy_gpt_layers, optim)).to_device("cpu")
    cost_single = single.evaluate_model("toy", None, 0, 2, 8, 16, 1)
    np.testing.assert_allclose(cost_sp, cost_single, rtol=1e-5)


def test_eval_mesh_folds_pipe_axis_into_data(workdir, toy_gpt_layers,
                                             monkeypatch):
    """A pipelined training config (PENROZ_MESH_PIPE>1) evaluates with the
    pipe chips folded into data parallelism — a forward-only cost has no
    pipeline schedule to run, so those chips would otherwise idle."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    monkeypatch.setenv("PENROZ_MESH_PIPE", "2")
    model = NeuralNetworkModel(
        "evpipe", Mapper(toy_gpt_layers, {"sgd": {"lr": 0.1}})).to_device("cpu")
    mesh = model._eval_mesh(8, 16)
    assert mesh is not None and mesh.shape["data"] == 8
    assert model._eval_mesh(3, 16) is None  # indivisible batch: fallback


def test_training_mesh_fallback_on_indivisible_batch(workdir, toy_gpt_layers):
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    model = NeuralNetworkModel(
        "fb", Mapper(toy_gpt_layers, {"sgd": {"lr": 0.1}})).to_device("cpu")
    assert model._training_mesh(micro_batch=3, block_size=16) is None


def test_all_reduce_mean_single_process_identity():
    assert dist.all_reduce_mean(3.5) == 3.5


def test_all_reduce_mean_gathers_across_processes(monkeypatch):
    from jax.experimental import multihost_utils
    monkeypatch.setattr(dist, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        lambda x: np.asarray([2.0, 4.0], np.float32))
    assert dist.all_reduce_mean(2.0) == 3.0


def test_process_topology_single_host():
    assert dist.process_count() == 1
    assert dist.process_index() == 0
    assert dist.master_proc()
    assert not dist.is_distributed()
    assert dist.initialize() is False  # no cluster env → no-op


def test_global_batch_single_process_equals_shard_batch(cpu_devices):
    mesh = mesh_lib.make_mesh(cpu_devices[:4])
    x = jnp.asarray(np.arange(2 * 8 * 4).reshape(2, 8, 4))
    a = sharding.shard_batch(x, mesh, leading_steps=True)
    b = sharding.global_batch(x, mesh, leading_steps=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.sharding == b.sharding


def test_global_batch_multihost_lifts_local_rows(cpu_devices, monkeypatch):
    """Under world=2 the local (steps, B, T) rows become a global array of
    (steps, 2B, T) via make_array_from_process_local_data."""
    import jax
    mesh = mesh_lib.make_mesh(cpu_devices)
    monkeypatch.setattr(dist, "process_count", lambda: 2)
    captured = {}

    def fake_make_array(sharding_, local, global_shape):
        captured["sharding"] = sharding_
        captured["local_shape"] = local.shape
        captured["global_shape"] = global_shape
        return "global-array"

    monkeypatch.setattr(jax, "make_array_from_process_local_data",
                        fake_make_array)
    x = np.zeros((2, 4, 8), np.int32)
    out = sharding.global_batch(x, mesh, leading_steps=True)
    assert out == "global-array"
    assert captured["local_shape"] == (2, 4, 8)
    assert captured["global_shape"] == (2, 8, 8)
    from jax.sharding import PartitionSpec as P
    assert captured["sharding"].spec == P(None, "data", None)


def test_alltoall_attention_matches_reference(cpu_devices):
    """Ulysses all-to-all SP == causal oracle, incl. GQA and windows."""
    from penroz_tpu.ops.attention import causal_attention_reference
    from penroz_tpu.parallel.alltoall_attention import alltoall_attention
    mesh = mesh_lib.make_mesh(cpu_devices, sequence=4, model=1)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 8, 64, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 4, 64, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 4, 64, 16)).astype(np.float32))
    ref = causal_attention_reference(q, k, v)
    out = alltoall_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # sliding window band
    ref_w = causal_attention_reference(q, k, v, window=24)
    out_w = alltoall_attention(q, k, v, mesh, causal=True, window=24)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(ref_w),
                               atol=1e-5)


def test_alltoall_attention_gradients(cpu_devices):
    from penroz_tpu.ops.attention import causal_attention_reference
    from penroz_tpu.parallel.alltoall_attention import alltoall_attention
    mesh = mesh_lib.make_mesh(cpu_devices, sequence=4, model=1)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 4, 32, 8)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 4, 32, 8)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 4, 32, 8)).astype(np.float32))
    g_a2a = jax.grad(lambda *a: alltoall_attention(*a, mesh).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: causal_attention_reference(*a).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_a2a, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_alltoall_attention_guards(cpu_devices):
    from penroz_tpu.parallel import alltoall_attention as a2a
    mesh = mesh_lib.make_mesh(cpu_devices, sequence=4, model=1)
    q = jnp.zeros((1, 6, 32, 8))  # 6 heads not divisible by 4
    with pytest.raises(ValueError, match="divisible"):
        a2a.alltoall_attention(q, q, q, mesh)
    assert not a2a.alltoall_supported(6, 6, mesh)
    assert a2a.alltoall_supported(8, 4, mesh)
    with pytest.raises(ValueError, match="causal"):
        a2a.alltoall_attention(jnp.zeros((1, 4, 32, 8)),
                               jnp.zeros((1, 4, 32, 8)),
                               jnp.zeros((1, 4, 32, 8)), mesh, causal=False)


def test_train_epoch_with_alltoall_sp(cpu_devices, toy_gpt_layers):
    """Full jitted train epoch under Ulysses SP == ring SP numerically."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import CompiledArch
    mesh = mesh_lib.make_mesh(cpu_devices[:4], sequence=4, model=1)
    optim = {"sgd": {"lr": 0.1}}
    mapper = Mapper(toy_gpt_layers, optim)
    arch = CompiledArch.get(mapper.layers)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 64, (1, 2, 16), dtype=np.int32))
    y = jnp.asarray(rng.integers(0, 64, (1, 2, 16), dtype=np.int32))
    outs = {}
    for mode in ("ring", "alltoall"):
        # fresh state per mode — the epoch fn donates params/opt_state
        params, buffers = mapper.init_params(arch.mods, seed=0)
        opt_state = mapper.to_optimizer().init(params)
        fn = arch.train_epoch_fn(optim, 1, False, None, sp_mesh=mesh,
                                 sp_mode=mode)
        p, _, _, cost, _ = fn(params, opt_state, buffers, x, y,
                              jax.random.key(0))
        outs[mode] = (p, float(cost))
    param_names = list(outs["ring"][0])
    assert outs["ring"][1] == pytest.approx(outs["alltoall"][1], abs=1e-5)
    for kname in param_names:
        np.testing.assert_allclose(np.asarray(outs["ring"][0][kname]),
                                   np.asarray(outs["alltoall"][0][kname]),
                                   atol=1e-5)


def test_wus_opt_state_specs(cpu_devices):
    """ZeRO-1 weight-update sharding (arXiv:2004.13336): moment leaves gain
    the data axis on a dim the TP layout leaves free; indivisible shapes and
    step counters stay replicated."""
    import optax
    mesh = mesh_lib.make_mesh(cpu_devices, model=2)  # data=4, model=2
    params = {"w.qkv": jnp.zeros((96, 32)),   # column-parallel
              "w.sq": jnp.zeros((32, 32)),    # replicated square
              "w.b": jnp.zeros((32,)),        # vector
              "w.odd": jnp.zeros((33, 7))}    # indivisible
    state = optax.adamw(1e-3).init(params)
    tree = sharding.opt_state_sharding_tree(state, params, mesh, wus=True)
    mu = tree[0].mu
    assert mu["w.qkv"].spec == P("model", "data")
    assert mu["w.sq"].spec == P("data", None)
    assert mu["w.b"].spec == P("data")
    assert mu["w.odd"].spec == P()
    # the scalar step count stays replicated
    assert tree[0].count.spec == P()
    # wus=False keeps the round-1 behavior (TP layout only)
    tree_off = sharding.opt_state_sharding_tree(state, params, mesh)
    assert tree_off[0].mu["w.sq"].spec == P()
    # a dim held by a trivial size-1 model axis is free for the data axis
    # (pure-DP mesh: param_spec still emits P('model', None) there)
    dp_mesh = mesh_lib.make_mesh(cpu_devices)  # data=8, model=1
    assert sharding._data_axis_spec(sharding.param_spec("w.q", (16, 4), dp_mesh),
                              (16, 4), dp_mesh) == P("data", None)


def test_train_model_wus_matches_replicated(workdir, toy_gpt_layers,
                                            toy_shards, monkeypatch):
    """PENROZ_WUS=1 training == replicated-moment training numerically
    (same mesh, so gradient reduction order is identical and the only
    change is where the elementwise AdamW update runs), while each device
    holds only 1/data of the moments."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    optim = {"adamw": {"lr": 1e-3, "betas": [0.9, 0.95], "eps": 1e-8}}
    wus = NeuralNetworkModel("wus8",
                             Mapper(toy_gpt_layers, optim)).to_device("cpu")
    plain = NeuralNetworkModel("wusoff",
                               Mapper(toy_gpt_layers, optim)).to_device("cpu")
    monkeypatch.setenv("PENROZ_WUS", "1")
    wus.train_model("toy", shard=0, epochs=2, batch_size=8, block_size=16,
                    step_size=8)
    monkeypatch.delenv("PENROZ_WUS")
    plain.train_model("toy", shard=0, epochs=2, batch_size=8, block_size=16,
                      step_size=8)
    assert wus.status["code"] == "Trained"
    for k in wus.params:
        np.testing.assert_allclose(np.asarray(wus.params[k], np.float32),
                                   np.asarray(plain.params[k], np.float32),
                                   atol=1e-5)
    # the out_shardings pin forced the fresh params back to the parameter
    # layout — without it GSPMD leaves them data-sharded after the update
    assert all(v.sharding.is_fully_replicated for v in wus.params.values())
    # moments stayed data-sharded through the donating epoch calls: each
    # device's shard of a divisible moment leaf is 1/8 of the full array
    mu = jax.tree.leaves(wus.opt_state)
    sharded = [leaf for leaf in mu
               if hasattr(leaf, "sharding") and leaf.ndim >= 1
               and "data" in (leaf.sharding.spec or ())]
    assert sharded, "no moment leaf kept the data axis"
    for leaf in sharded:
        shard = leaf.addressable_shards[0]
        assert np.prod(shard.data.shape) == leaf.size // 8


def test_fsdp_param_specs(cpu_devices):
    """ZeRO-3: params themselves gain the data axis on a free dim; TP dims
    are preserved; indivisible shapes stay as the TP layout alone."""
    mesh = mesh_lib.make_mesh(cpu_devices, model=2)  # data=4, model=2
    params = {"w.qkv": jnp.zeros((96, 32)), "w.sq": jnp.zeros((32, 32)),
              "w.b": jnp.zeros((32,)), "w.odd": jnp.zeros((33, 7))}
    sh = sharding.param_shardings(params, mesh, fsdp=True)
    assert sh["w.qkv"].spec == P("model", "data")
    assert sh["w.sq"].spec == P("data", None)
    assert sh["w.b"].spec == P("data")
    assert sh["w.odd"].spec == P()
    # fsdp=False unchanged
    assert sharding.param_shardings(params, mesh)["w.sq"].spec == P()


def test_train_model_fsdp_matches_replicated(workdir, toy_gpt_layers,
                                             toy_shards, monkeypatch):
    """PENROZ_FSDP=1 training == replicated training numerically, with the
    params themselves living 1/data-sharded on device."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    optim = {"adamw": {"lr": 1e-3, "betas": [0.9, 0.95], "eps": 1e-8}}
    fsdp = NeuralNetworkModel("fsdp8",
                              Mapper(toy_gpt_layers, optim)).to_device("cpu")
    plain = NeuralNetworkModel("fsdpoff",
                               Mapper(toy_gpt_layers, optim)).to_device("cpu")
    monkeypatch.setenv("PENROZ_FSDP", "1")
    fsdp.train_model("toy", shard=0, epochs=2, batch_size=8, block_size=16,
                     step_size=8)
    monkeypatch.delenv("PENROZ_FSDP")
    plain.train_model("toy", shard=0, epochs=2, batch_size=8, block_size=16,
                      step_size=8)
    assert fsdp.status["code"] == "Trained"
    for k in fsdp.params:
        np.testing.assert_allclose(np.asarray(fsdp.params[k], np.float32),
                                   np.asarray(plain.params[k], np.float32),
                                   atol=1e-5)
    # the params stayed FSDP-sharded (not replicated back): divisible leaves
    # hold 1/8 per device
    sharded = [v for v in fsdp.params.values()
               if v.ndim >= 1 and not v.sharding.is_fully_replicated]
    assert sharded, "no param leaf is data-sharded under FSDP"
    for v in sharded:
        assert v.addressable_shards[0].data.size == v.size // 8
    # FSDP implies WUS: the AdamW moments are 1/data-sharded as well
    assert any(getattr(leaf, "ndim", 0) >= 1
               and not leaf.sharding.is_fully_replicated
               for leaf in jax.tree.leaves(fsdp.opt_state)), \
        "FSDP did not shard the optimizer moments (implied WUS lost)"
    # serialize → deserialize reassembles full arrays regardless
    fsdp.serialize(sync_flush=True)
    restored = NeuralNetworkModel.deserialize("fsdp8")
    for k in fsdp.params:
        np.testing.assert_array_equal(np.asarray(restored.params[k]),
                                      np.asarray(fsdp.params[k]))


def test_multihost_training_mesh(workdir, toy_gpt_layers, monkeypatch):
    """process_count>1 yields a global mesh; the TP/SP/EP env knobs carve
    axes out of the global device set (sharded checkpointing lifted the
    round-1 pure-DP restriction)."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    model = NeuralNetworkModel("mh", Mapper(toy_gpt_layers,
                                            {"sgd": {"lr": 0.1}}))
    model.to_device("cpu")  # pin to the virtual 8-device CPU backend
    monkeypatch.setattr(dist, "process_count", lambda: 2)
    mesh = model._training_mesh(micro_batch=4, block_size=16)
    assert mesh is not None
    assert mesh.shape["data"] == 8
    assert mesh.shape["model"] == 1
    monkeypatch.setenv("PENROZ_MESH_MODEL", "2")
    mesh = model._training_mesh(micro_batch=4, block_size=16)
    assert mesh.shape["model"] == 2
    assert mesh.shape["data"] == 4
    # indivisible global micro-batch must raise, not silently train
    # divergent unsynced replicas
    with pytest.raises(ValueError, match="divisible"):
        model._training_mesh(micro_batch=3, block_size=16)


def test_ring_attention_window_matches_reference(cpu_devices):
    """Windowed ring attention == windowed oracle, incl. windows smaller
    than one ring chunk (whole ring steps fully masked per row — the
    online-rescaling self-healing path) and spanning several chunks."""
    from penroz_tpu.ops.attention import causal_attention_reference
    from penroz_tpu.parallel.ring_attention import ring_attention
    mesh = mesh_lib.make_mesh(cpu_devices, sequence=8, model=1)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 4, 64, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 2, 64, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 2, 64, 16)).astype(np.float32))
    for window in (4, 8, 17, 40, 64):
        ref = causal_attention_reference(q, k, v, window=window)
        out = ring_attention(q, k, v, mesh, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, err_msg=f"window={window}")


def test_ring_attention_window_gradients(cpu_devices):
    from penroz_tpu.ops.attention import causal_attention_reference
    from penroz_tpu.parallel.ring_attention import ring_attention
    mesh = mesh_lib.make_mesh(cpu_devices, sequence=4, model=1)
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(1, 2, 32, 8)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 32, 8)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 32, 8)).astype(np.float32))
    g_ring = jax.grad(lambda *a: ring_attention(*a, mesh, window=6).sum(),
                      argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: causal_attention_reference(
        *a, window=6).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_ring_attention_window_requires_causal(cpu_devices):
    from penroz_tpu.parallel.ring_attention import ring_attention
    mesh = mesh_lib.make_mesh(cpu_devices, sequence=4, model=1)
    q = jnp.zeros((1, 2, 32, 8), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        ring_attention(q, q, q, mesh, causal=False, window=8)


def test_barrier_private_api_pin():
    """dist.barrier depends on jax._src.distributed.global_state.client
    (no public coordination-service API exists).  Pin the attribute so a
    JAX upgrade that moves it fails HERE, loudly, instead of silently
    degrading the train-end fence to its fallback path."""
    from jax._src import distributed
    assert hasattr(distributed.global_state, "client")


def test_barrier_fallback_logs_loudly(monkeypatch):
    """When the private client is unavailable the barrier must NOT
    silently no-op (that reintroduces the lazy comm-group timeout race);
    it falls back to the public sync_global_devices and logs an error.
    (The error is asserted by spying the logger method, not caplog —
    other tests in the suite reconfigure logging handlers/propagation,
    which silently empties caplog.)"""
    import logging
    from penroz_tpu.parallel import dist
    import jax._src.distributed as jd
    monkeypatch.setattr(dist, "process_count", lambda: 2)
    monkeypatch.setattr(jd.global_state, "client", None)
    called = []
    from jax.experimental import multihost_utils
    monkeypatch.setattr(multihost_utils, "sync_global_devices",
                        lambda name: called.append(name))
    errors = []
    logger = logging.getLogger("penroz_tpu.parallel.dist")
    monkeypatch.setattr(logger, "error",
                        lambda msg, *a: errors.append(msg % a))
    dist.barrier("unit_test_fence")
    assert called == ["penroz_unit_test_fence"]
    assert any("coordination-service client unavailable" in e
               for e in errors)


def test_ring_attention_alibi_matches_reference(cpu_devices):
    """Ring attention with ALiBi == the single-device biased oracle: the
    global q/k positions the ring tracks for causal masks drive the
    slope*(k-q) bias identically on every rotation step."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from penroz_tpu.parallel.ring_attention import ring_attention
    from penroz_tpu.ops import attention as A
    mesh = mesh_lib.make_mesh(cpu_devices[:4], sequence=4)
    B, Hq, Hkv, T, D = 2, 4, 2, 32, 8
    rng = np.random.default_rng(31)
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), jnp.float32)
    slopes = A.alibi_slopes(Hq)
    want = A.causal_attention_reference(q, k, v, alibi=slopes)
    spec = NamedSharding(mesh, P(None, None, "sequence"))
    qs, ks, vs = (jax.device_put(t, spec) for t in (q, k, v))
    got = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, mesh, causal=True, alibi=slopes))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def test_ring_attention_alibi_with_window(cpu_devices):
    """ALiBi composes with the sliding-window band (MPT-style configs)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from penroz_tpu.parallel.ring_attention import ring_attention
    from penroz_tpu.ops import attention as A
    mesh = mesh_lib.make_mesh(cpu_devices[:4], sequence=4)
    B, H, T, D = 1, 4, 32, 8
    rng = np.random.default_rng(32)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    slopes = A.alibi_slopes(H)
    want = A.causal_attention_reference(q, k, v, window=12, alibi=slopes)
    spec = NamedSharding(mesh, P(None, None, "sequence"))
    qs, ks, vs = (jax.device_put(t, spec) for t in (q, k, v))
    got = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, mesh, causal=True, window=12, alibi=slopes))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def test_sp_alibi_module_path_and_ulysses_fallback(cpu_devices,
                                                   monkeypatch):
    """An ALiBi attention module under a sequence mesh runs ring SP (bias
    == single-device math); requesting Ulysses falls back to ring with a
    trace-time warning (its head re-partition would make the slope table
    device-dynamic).  The warning is asserted via a logger-method spy —
    caplog silently empties when other suite tests reconfigure logging
    handlers (same hazard as the barrier-fallback test)."""
    import logging
    from penroz_tpu.ops import modules as M
    from penroz_tpu.ops import attention as A
    mesh = mesh_lib.make_mesh(cpu_devices[:4], sequence=4)
    attn = M.CausalSelfAttention(num_heads=4, head_dim=8, alibi=True)
    attn.bind("attn")
    rng = np.random.default_rng(33)
    B, T, d = 2, 32, 32
    qkv = jnp.asarray(rng.normal(size=(B, T, 3 * d)), jnp.float32)
    want = np.asarray(attn.apply(qkv, M.Ctx({})))
    from jax.sharding import NamedSharding
    qkv_s = jax.device_put(qkv, NamedSharding(mesh, P(None, "sequence")))
    got = jax.jit(lambda x: attn.apply(
        x, M.Ctx({}, sp_mesh=mesh, sp_mode="ring")))(qkv_s)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    warned = []
    logger = logging.getLogger("penroz_tpu.ops.modules")
    monkeypatch.setattr(logger, "warning",
                        lambda msg, *a: warned.append(msg % a if a else msg))
    got2 = jax.jit(lambda x: attn.apply(
        x, M.Ctx({}, sp_mesh=mesh, sp_mode="alltoall")))(qkv_s)
    np.testing.assert_allclose(np.asarray(got2), want, atol=2e-5)
    assert any("falling back to ring" in m for m in warned)


def test_ring_attention_softcap_and_scale(cpu_devices):
    """Ring attention with Gemma-2 soft-capping + scale override == the
    single-device oracle (tanh is elementwise, so per-rotation-step
    capping equals capping the full score matrix)."""
    from jax.sharding import NamedSharding
    from penroz_tpu.parallel.ring_attention import ring_attention
    from penroz_tpu.ops import attention as A
    mesh = mesh_lib.make_mesh(cpu_devices[:4], sequence=4)
    B, H, T, D = 1, 2, 32, 8
    rng = np.random.default_rng(43)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32) * 4
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    want = A.causal_attention_reference(q, k, v, softcap=2.0, scale=0.2)
    spec = NamedSharding(mesh, P(None, None, "sequence"))
    qs, ks, vs = (jax.device_put(t, spec) for t in (q, k, v))
    got = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, mesh, causal=True, softcap=2.0, scale=0.2))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
