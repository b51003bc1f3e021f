"""Model runtime tests (mirrors the reference's test_neural_net_model.py
strategy): DSL init tables, forward/output/eval/generate behavior, a real
training integration with serialize/deserialize round-trip, error statuses,
and bf16 dtype restoration."""

import jax.numpy as jnp
import numpy as np
import pytest

from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import NeuralNetworkModel

# CI tier: heavier compiles (see pyproject markers / ci.yml shards).
pytestmark = pytest.mark.runtime

SGD = {"sgd": {"lr": 0.1}}
ADAMW = {"adamw": {"lr": 1e-3, "betas": [0.9, 0.95], "eps": 1e-8}}

MLP_LAYERS = [
    {"flatten": {}},
    {"linear": {"in_features": 8, "out_features": 16},
     "xavier_uniform": {}, "zeros": {}},
    {"batchnorm1d": {"num_features": 16}},
    {"tanh": {}},
    {"linear": {"in_features": 16, "out_features": 4}},
    {"softmax": {"dim": -1}},
]


@pytest.mark.parametrize("layers,expected_params", [
    ([{"linear": {"in_features": 3, "out_features": 2}}], 8),
    ([{"embedding": {"num_embeddings": 10, "embedding_dim": 4}}], 40),
    (MLP_LAYERS, 8 * 16 + 16 + 2 * 16 + 16 * 4 + 4),
])
def test_param_counts(workdir, layers, expected_params):
    model = NeuralNetworkModel("m", Mapper(layers, SGD))
    assert model.num_params == expected_params


def test_state_dict_keys_include_buffers(workdir):
    model = NeuralNetworkModel("m", Mapper(MLP_LAYERS, SGD))
    sd = model.state_dict()
    assert "layers.2.running_mean" in sd
    assert "layers.2.num_batches_tracked" in sd
    assert "layers.1.weight" in sd


def test_compute_output_softmax_and_cost(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("m", Mapper(toy_gpt_layers, SGD))
    out, cost = model.compute_output([[1, 2, 3]], [[2, 3, 4]])
    out = np.asarray(out)
    assert out.shape == (1, 64)
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-4)
    assert cost is not None and cost > 0


def test_compute_output_no_target(workdir):
    model = NeuralNetworkModel("m", Mapper(
        [{"linear": {"in_features": 2, "out_features": 2}}], SGD))
    out, cost = model.compute_output([[1.0, 2.0]])
    assert cost is None
    assert len(out[0]) == 2


def test_compute_output_mse(workdir):
    model = NeuralNetworkModel("m", Mapper(
        [{"linear": {"in_features": 2, "out_features": 2}}], SGD))
    _, cost = model.compute_output([[1.0, 2.0]], [[0.0, 0.0]])
    assert cost > 0


def test_serialize_roundtrip_params_and_optimizer(workdir, toy_gpt_layers,
                                                 toy_shards):
    model = NeuralNetworkModel("rt", Mapper(toy_gpt_layers, ADAMW))
    model.train_model("toy", shard=0, epochs=2, batch_size=2, block_size=16,
                      step_size=1)
    model.serialize(sync_flush=True)
    loaded = NeuralNetworkModel.deserialize("rt")
    assert loaded.status["code"] == "Trained"
    for key, val in model.params.items():
        np.testing.assert_array_equal(np.asarray(val),
                                      np.asarray(loaded.params[key]))
    # optimizer moments survive the round trip
    import jax
    orig = jax.tree.leaves(model.opt_state)
    back = jax.tree.leaves(loaded.opt_state)
    assert len(orig) == len(back)
    for a, b in zip(orig, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_changes_params_and_records_progress(workdir, toy_gpt_layers,
                                                   toy_shards):
    model = NeuralNetworkModel("tr", Mapper(toy_gpt_layers, ADAMW))
    before = {k: np.asarray(v).copy() for k, v in model.params.items()}
    model.train_model("toy", shard=0, epochs=3, batch_size=4, block_size=16,
                      step_size=2)
    changed = any(not np.array_equal(before[k], np.asarray(v))
                  for k, v in model.params.items())
    assert changed
    assert len(model.progress) == 3
    entry = model.progress[-1]
    assert set(entry) >= {"epoch", "cost", "durationInSecs", "speedPerSec",
                          "weight_upd_ratio"}
    assert entry["epoch"] == 3
    assert len(entry["weight_upd_ratio"]) == len(model.arch.param_order)
    assert model.avg_cost is not None
    assert len(model.avg_cost_history) == 1
    assert model.status["code"] == "Trained"
    # stats recorded on the final epoch
    assert model.stats is not None
    assert len(model.stats["weights"]) == len(model.arch.param_order)
    sat = model.stats["layers"][0]["activation"]["saturated"]
    assert 0.0 <= sat <= 1.0


def test_train_reference_microbatch_semantics(workdir, toy_gpt_layers,
                                              toy_shards, monkeypatch):
    """Pin the reference's buffer math (neural_net_model.py:581-586,
    629-631): buffer_size = batch_size*block_size, one full
    (batch_size, block_size) buffer per micro-step, rank-strided by
    buffer_size*world — so an epoch consumes num_steps*buffer_size
    tokens."""
    from penroz_tpu.data import loaders as loaders_mod
    from penroz_tpu.models import model as model_mod
    constructed = []
    batches = []

    class SpyLoader(loaders_mod.Loader):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            constructed.append(kwargs)

        def next_batch(self, target_offset=1):
            x, y = super().next_batch(target_offset)
            batches.append(len(x))
            return x, y

    monkeypatch.setattr(loaders_mod, "Loader", SpyLoader)
    epoch_shapes = []
    orig_epoch_fn = model_mod.CompiledArch.train_epoch_fn

    def spy_epoch_fn(self, *args, **kwargs):
        fn = orig_epoch_fn(self, *args, **kwargs)

        def wrapped(params, opt_state, buffers, xs, ys, rng):
            epoch_shapes.append(tuple(xs.shape))
            return fn(params, opt_state, buffers, xs, ys, rng)
        return wrapped

    monkeypatch.setattr(model_mod.CompiledArch, "train_epoch_fn",
                        spy_epoch_fn)
    model = NeuralNetworkModel("mb", Mapper(toy_gpt_layers, SGD))
    model.train_model("toy", shard=0, epochs=2, batch_size=4, block_size=16,
                      step_size=2)
    buffer_size = 4 * 16
    num_steps = 2  # batch_size // (step_size * world)
    assert constructed[0]["buffer_size"] == buffer_size
    assert constructed[0]["begin_idx"] == 0
    assert constructed[0]["idx_offset"] == buffer_size
    # every micro-step pulled one full buffer; epochs*num_steps pulls total
    assert batches == [buffer_size] * (2 * num_steps)
    # micro-batch viewed as (batch_size, block_size), reference :629-631
    assert epoch_shapes == [(num_steps, 4, 16)] * 2
    # speed accounting counts buffer_size tokens per epoch (:684)
    assert model.progress[-1]["speedPerSec"] == pytest.approx(
        buffer_size / model.progress[-1]["durationInSecs"], rel=1e-6)


def test_train_resets_progress_and_stats(workdir, toy_gpt_layers,
                                         toy_shards):
    """Each train run starts fresh (reference :597-601): progress and
    stats reset, epoch numbering restarts at 1."""
    model = NeuralNetworkModel("rst", Mapper(toy_gpt_layers, SGD))
    model.train_model("toy", shard=0, epochs=3, batch_size=2, block_size=16,
                      step_size=1)
    assert [p["epoch"] for p in model.progress] == [1, 2, 3]
    first_history = len(model.avg_cost_history)
    model.train_model("toy", shard=0, epochs=2, batch_size=2, block_size=16,
                      step_size=1)
    assert [p["epoch"] for p in model.progress] == [1, 2]
    assert model.stats is not None
    # avg-cost history accumulates across runs (reference :727-733)
    assert len(model.avg_cost_history) == first_history + 1


def test_compute_stats_multihost_uses_local_copy(workdir, toy_gpt_layers):
    """Params spanning hosts (not fully addressable, fully replicated)
    must not skip stats: the instrumented pass runs on a process-local
    copy of the params (the reference always produces stats on master,
    neural_net_model.py:705-709)."""
    model = NeuralNetworkModel("mhstats", Mapper(toy_gpt_layers, SGD))

    class FakeGlobalArray:
        def __init__(self, arr):
            self._arr = np.asarray(arr)
            self.is_fully_addressable = False
            self.is_fully_replicated = True
            self.dtype = self._arr.dtype
            self.shape = self._arr.shape

        def __array__(self, dtype=None, copy=None):
            return (self._arr if dtype is None
                    else self._arr.astype(dtype))

    model.params = {k: FakeGlobalArray(v) for k, v in model.params.items()}
    rng = np.random.default_rng(0)
    x = rng.integers(0, 64, (2, 16)).astype(np.int32)
    y = np.roll(x, -1, -1)
    stats = model._compute_stats(x, y)
    assert stats is not None
    assert len(stats["layers"]) > 0
    assert len(stats["weights"]) == len(model.arch.param_order)


def test_train_mesh_optout_raises_under_multihost(workdir, toy_gpt_layers,
                                                  monkeypatch):
    from penroz_tpu.parallel import dist
    model = NeuralNetworkModel("optout", Mapper(toy_gpt_layers, SGD))
    monkeypatch.setenv("PENROZ_TRAIN_MESH", "0")
    monkeypatch.setattr(dist, "process_count", lambda: 2)
    with pytest.raises(RuntimeError, match="multi-host"):
        model._training_mesh(micro_batch=4, block_size=16)


def test_train_missing_dataset_sets_error_status(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("err", Mapper(toy_gpt_layers, SGD))
    model.serialize(sync_flush=True)
    with pytest.raises(Exception):
        NeuralNetworkModel.train_model_on_device(
            "err", "cpu", "nonexistent-ds", 0, 1, 2, 16, 1)
    loaded = NeuralNetworkModel.deserialize("err")
    assert loaded.status["code"] == "Error"


def test_evaluate_model(workdir, toy_gpt_layers, toy_shards):
    model = NeuralNetworkModel("ev", Mapper(toy_gpt_layers, SGD))
    cost = model.evaluate_model("toy", None, 0, 2, 2, 16, 1)
    assert np.isfinite(cost) and cost > 0


def test_evaluate_reference_buffer_and_allreduce(workdir, toy_gpt_layers,
                                                 toy_shards, monkeypatch):
    """Eval loads one (batch_size, block_size) buffer per epoch
    (reference :319-343) and reduces the mean cost across processes
    (:352-354)."""
    from penroz_tpu.data import loaders as loaders_mod
    from penroz_tpu.parallel import dist
    constructed = []
    pulls = []

    class SpyLoader(loaders_mod.Loader):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            constructed.append(kwargs)

        def next_batch(self, target_offset=1):
            pulls.append(target_offset)
            return super().next_batch(target_offset)

    monkeypatch.setattr(loaders_mod, "Loader", SpyLoader)
    reduced = []

    def spy_reduce(v):
        reduced.append(v)
        return v

    monkeypatch.setattr(dist, "all_reduce_mean", spy_reduce)
    model = NeuralNetworkModel("evp", Mapper(toy_gpt_layers, SGD))
    cost = model.evaluate_model("toy", None, 0, 3, 4, 16, 2)
    assert constructed[0]["buffer_size"] == 4 * 16
    assert constructed[0]["idx_offset"] == 4 * 16
    assert pulls == [1, 1, 1]  # one buffer per epoch
    assert reduced == [cost]


def test_evaluate_with_target_dataset(workdir, toy_gpt_layers, toy_shards):
    model = NeuralNetworkModel("ev2", Mapper(toy_gpt_layers, SGD))
    cost = model.evaluate_model("toy", "toy", 0, 1, 2, 16, 1)
    assert np.isfinite(cost)


def test_generate_greedy_deterministic(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("g", Mapper(toy_gpt_layers, SGD))
    a = model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=4,
                              temperature=0.0)
    b = model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=4,
                              temperature=0.0)
    assert a == b
    assert len(a) == 6
    assert a[:2] == [1, 2]


def test_generate_top_k_and_ranges(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("g2", Mapper(toy_gpt_layers, SGD))
    tokens = model.generate_tokens([[1]], block_size=16, max_new_tokens=5,
                                   temperature=0.8, top_k=5)
    assert len(tokens) == 6
    assert all(0 <= t < 64 for t in tokens)


def test_generate_stop_token(workdir):
    # constant-logits model: bias forces token 3 to always win at temp 0
    layers = [{"embedding": {"num_embeddings": 8, "embedding_dim": 4},
               "normal": {"mean": 0.0, "std": 0.001}},
              {"linear": {"in_features": 4, "out_features": 8}},
              {"softmaxlast": {"dim": -1}}]
    model = NeuralNetworkModel("g3", Mapper(layers, SGD))
    bias = np.zeros(8, np.float32)
    bias[3] = 100.0
    model.params["layers.1.bias"] = jnp.asarray(bias)
    tokens = model.generate_tokens([[0]], block_size=8, max_new_tokens=10,
                                   temperature=0.0, stop_token=3)
    assert tokens == [0, 3]


def test_generate_stream_matches_count(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("g4", Mapper(toy_gpt_layers, SGD))
    tokens = list(model.generate_tokens_stream([[1, 2]], block_size=16,
                                               max_new_tokens=3))
    assert len(tokens) == 3


def test_generate_gqa_rope_cached_decode(workdir, monkeypatch):
    """Gemma-style attention (GQA num_kv_heads < heads, RoPE positions)
    through the functional KV cache: batch == stream at T=0, overflow
    re-prefill works, and the int8 cache path agrees within quant
    tolerance of nothing-exploding (finite, right count)."""
    d, heads, kv = 16, 4, 2
    layers = [
        {"embedding": {"num_embeddings": 32, "embedding_dim": d}},
        {"residual": [
            {"sequential": [
                {"rmsnorm": {"normalized_shape": d}},
                {"linear": {"in_features": d,
                            "out_features": d + 2 * (d // heads) * kv},
                 "normal": {"mean": 0.0, "std": 0.05}},
                {"attention": {"num_heads": heads, "num_kv_heads": kv,
                               "rope_theta": 10000.0, "dropout": 0.0}},
                {"linear": {"in_features": d, "out_features": d}}]}]},
        {"linear": {"in_features": d, "out_features": 32, "bias": False}},
        {"softmaxlast": {"dim": -1}}]
    model = NeuralNetworkModel("gqa", Mapper(layers, SGD))
    batch = model.generate_tokens([[1, 2, 3]], block_size=8,
                                  max_new_tokens=9, temperature=0.0)
    assert len(batch) == 12  # overflow at block_size=8 re-prefilled
    stream = list(model.generate_tokens_stream([[1, 2, 3]], block_size=8,
                                               max_new_tokens=9,
                                               temperature=0.0))
    assert stream == batch[3:]
    monkeypatch.setenv("TURBO_QUANT_KV_CACHE", "1")
    quant = model.generate_tokens([[1, 2, 3]], block_size=8,
                                  max_new_tokens=9, temperature=0.0)
    assert len(quant) == 12 and all(0 <= t < 32 for t in quant)


def test_compute_output_flat_tokens_clear_error(workdir, toy_gpt_layers):
    """A flat token list on a sequence model must 400 with a message naming
    the expected shape, not an opaque unpack error from inside the stack."""
    model = NeuralNetworkModel("shp", Mapper(toy_gpt_layers, SGD))
    with pytest.raises(ValueError, match=r"2-D \(batch, length\)"):
        model.compute_output([1, 2, 3])
    with pytest.raises(ValueError, match="inconsistent lengths"):
        model.compute_output([[1, 2, 3], [4, 5]])
    out, cost = model.compute_output([[1, 2, 3]])
    assert cost is None and len(out) == 1


def test_generate_dispatch_count(workdir, toy_gpt_layers, monkeypatch):
    """96 tokens at budget 128 must cost exactly ONE prefill + ONE chunk
    dispatch (pow-2 ceiling with overshoot), not a descending pow-2
    cascade — each extra dispatch is a full device round-trip."""
    model = NeuralNetworkModel("gdc", Mapper(toy_gpt_layers, SGD))
    calls = []
    orig = type(model.arch).decode_chunk

    def counting(self, *a, chunk, **kw):
        calls.append(chunk)
        return orig(self, *a, chunk=chunk, **kw)

    monkeypatch.setattr(type(model.arch), "decode_chunk", counting)
    monkeypatch.setenv("PENROZ_DECODE_CHUNK", "128")  # pin the budget
    # block_size leaves room for the 128 ceiling (prompt occupies 2 slots)
    tokens = model.generate_tokens([[1, 2]], block_size=256,
                                   max_new_tokens=96, temperature=0.0)
    assert len(tokens) == 98
    assert calls == [128]  # one chunk dispatch, 33 overshot steps discarded


def test_generate_tail_overshoot_chunking(workdir, toy_gpt_layers,
                                          monkeypatch):
    """A tail shorter than its pow-2 ceiling dispatches the ceiling chunk
    and discards the overshoot — token count and greedy results must be
    exact, and stream (ramped chunks) must equal batch under T=0."""
    monkeypatch.setenv("PENROZ_DECODE_CHUNK", "16")
    model = NeuralNetworkModel("g4o", Mapper(toy_gpt_layers, SGD))
    # 11 new tokens = prefill(1) + chunks 8+2 under the old descending
    # decomposition; now prefill(1) + one 16-chunk with 6 discarded.
    batch = model.generate_tokens([[1, 2]], block_size=64,
                                  max_new_tokens=11, temperature=0.0)
    assert len(batch) == 13
    stream = list(model.generate_tokens_stream([[1, 2]], block_size=64,
                                               max_new_tokens=11,
                                               temperature=0.0))
    assert stream == batch[2:]


def test_generate_context_overflow_reprefills(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("g5", Mapper(toy_gpt_layers, SGD))
    # block_size 4 < prompt+generated: exercises crop-and-reprefill
    tokens = model.generate_tokens([[1, 2, 3]], block_size=4,
                                   max_new_tokens=6, temperature=0.0)
    assert len(tokens) == 9


def test_generate_with_turbo_quant(workdir, toy_gpt_layers, monkeypatch):
    monkeypatch.setenv("TURBO_QUANT_KV_CACHE", "1")
    model = NeuralNetworkModel("g6", Mapper(toy_gpt_layers, SGD))
    tokens = model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=3,
                                   temperature=0.0)
    assert len(tokens) == 5


def test_kv_cache_consistency_greedy(workdir, toy_gpt_layers):
    """Greedy decode with KV cache == greedy decode recomputing full context."""
    model = NeuralNetworkModel("g7", Mapper(toy_gpt_layers, SGD))
    cached = model.generate_tokens([[5, 6, 7]], block_size=16,
                                   max_new_tokens=5, temperature=0.0)
    # recompute without cache by feeding the full context each step
    context = [5, 6, 7]
    for _ in range(5):
        out, _ = model.compute_output([context[-16:]])
        context.append(int(np.argmax(out[0])))
    assert cached == context


def test_bf16_roundtrip(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("bf", Mapper(toy_gpt_layers, SGD))
    model.to(dtype=jnp.bfloat16)
    assert model.dtype == jnp.bfloat16
    model.serialize(sync_flush=True)
    loaded = NeuralNetworkModel.deserialize("bf")
    assert loaded.dtype == jnp.bfloat16
    out, cost = loaded.compute_output([[1, 2]], [[2, 3]])
    assert np.isfinite(cost)
    tokens = loaded.generate_tokens([[1]], block_size=16, max_new_tokens=2)
    assert len(tokens) == 3


def test_deserialize_missing_raises_keyerror(workdir):
    with pytest.raises(KeyError):
        NeuralNetworkModel.deserialize("missing-model")


def test_delete_removes_checkpoint(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("del", Mapper(toy_gpt_layers, SGD))
    model.serialize(sync_flush=True)
    NeuralNetworkModel.deserialize("del")
    NeuralNetworkModel.delete("del")
    with pytest.raises(KeyError):
        NeuralNetworkModel.deserialize("del")


def test_shm_cache_miss_repopulates(workdir, toy_gpt_layers):
    import os
    from penroz_tpu.utils import checkpoint as ckpt
    model = NeuralNetworkModel("cm", Mapper(toy_gpt_layers, SGD))
    model.serialize(sync_flush=True)
    os.remove(ckpt.shm_model_path("cm"))
    loaded = NeuralNetworkModel.deserialize("cm")  # repopulates from durable
    assert loaded.num_params == model.num_params
    assert os.path.exists(ckpt.shm_model_path("cm"))


def test_mlp_training_per_position(workdir, toy_shards):
    """Makemore-style MLP path: per-position embedding/tanh stack + CE."""
    layers = [
        {"embedding": {"num_embeddings": 64, "embedding_dim": 8}},
        {"linear": {"in_features": 8, "out_features": 32}},
        {"tanh": {}},
        {"linear": {"in_features": 32, "out_features": 64}},
        {"softmax": {"dim": -1}},
    ]
    model = NeuralNetworkModel("mlp", Mapper(layers, SGD))
    model.train_model("toy", shard=0, epochs=2, batch_size=4, block_size=16,
                      step_size=4)
    assert model.status["code"] == "Trained"
    assert np.isfinite(model.progress[-1]["cost"])


def test_generate_paged_matches_contiguous(workdir, toy_gpt_layers,
                                           monkeypatch):
    """Greedy decode with PAGED_KV_CACHE=1 must match the contiguous cache
    token-for-token (BASELINE config: paged-KV /generate/)."""
    model = NeuralNetworkModel("gp", Mapper(toy_gpt_layers, SGD))
    plain = model.generate_tokens([[1, 2, 3]], block_size=16,
                                  max_new_tokens=6, temperature=0.0)
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    model2 = NeuralNetworkModel("gp2", Mapper(toy_gpt_layers, SGD))
    model2.params = model.params
    paged = model2.generate_tokens([[1, 2, 3]], block_size=16,
                                   max_new_tokens=6, temperature=0.0)
    assert paged == plain


def test_generate_paged_overflow_reprefills(workdir, toy_gpt_layers,
                                            monkeypatch):
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    model = NeuralNetworkModel("gp3", Mapper(toy_gpt_layers, SGD))
    tokens = model.generate_tokens([[1, 2, 3]], block_size=8,
                                   max_new_tokens=10, temperature=0.0)
    assert len(tokens) == 13




def test_batched_generate_matches_single(workdir, toy_gpt_layers):
    """Ragged batched greedy generation == per-prompt single-sequence
    generation, for prompts of different lengths (the per-sequence cache
    lengths / RoPE offsets / masks must reproduce the B=1 math exactly).

    Also pins the path donation-clean: the prefill donates the KV pool, and
    the scalar length leaf must alias through into the ragged output state
    (KVState keeps the scalar slot next to ragged_lengths) — a "donated
    buffers were not usable" UserWarning here is a donation regression."""
    model = NeuralNetworkModel("bg", Mapper(toy_gpt_layers, SGD))
    prompts = [[1, 2, 3, 4, 5], [7, 8], [9, 10, 11]]
    import warnings
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*[Dd]onated buffers.*")
        batched = model.generate_tokens_batched(prompts, block_size=16,
                                                max_new_tokens=6,
                                                temperature=0.0)
    for p, out in zip(prompts, batched):
        single = model.generate_tokens([p], block_size=16, max_new_tokens=6,
                                       temperature=0.0)
        assert out == single, (p, out, single)


# the whole env-cache matrix rides the slow lane (tier1_budget): the
# plain batched-vs-single parity test above stays fast, and every cache
# layout is pinned by the kv_cache unit suite + scheduler parity matrices
@pytest.mark.slow
@pytest.mark.parametrize("paged,quant", [("1", "0"), ("0", "1"), ("1", "1")])
def test_batched_generate_matches_single_env_caches(workdir, toy_gpt_layers,
                                                    monkeypatch, paged,
                                                    quant):
    """Batched ≡ single parity holds under the paged / int8 / int8-paged
    cache variants too — every pool supports ragged per-sequence lengths
    (allocator, appends, kernels/oracles)."""
    monkeypatch.setenv("PAGED_KV_CACHE", paged)
    monkeypatch.setenv("TURBO_QUANT_KV_CACHE", quant)
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    model = NeuralNetworkModel(f"bgc{paged}{quant}",
                               Mapper(toy_gpt_layers, SGD))
    prompts = [[1, 2, 3, 4, 5], [7, 8], [9, 10, 11]]
    batched = model.generate_tokens_batched(prompts, block_size=16,
                                            max_new_tokens=6,
                                            temperature=0.0)
    for p, out in zip(prompts, batched):
        single = model.generate_tokens([p], block_size=16, max_new_tokens=6,
                                       temperature=0.0)
        assert out == single, (paged, quant, p, out, single)


def test_batched_generate_stop_token_and_validation(workdir, toy_gpt_layers,
                                                    monkeypatch):
    model = NeuralNetworkModel("bg2", Mapper(toy_gpt_layers, SGD))
    # a stop token freezes only that row; others keep generating
    ref = model.generate_tokens_batched([[1, 2], [3, 4, 5]], block_size=16,
                                        max_new_tokens=5, temperature=0.0)
    stop = ref[0][2]  # first generated token of row 0
    out = model.generate_tokens_batched([[1, 2], [3, 4, 5]], block_size=16,
                                        max_new_tokens=5, temperature=0.0,
                                        stop_token=int(stop))
    cut0 = ref[0].index(stop) + 1
    assert out[0] == ref[0][:cut0]  # row 0 halted at its stop token
    # row 1 halts at ITS OWN first stop occurrence (or not at all) — by
    # greedy determinism this proves row 0's stop never froze row 1 early
    gen1 = ref[1][3:]
    if stop in gen1:
        cut1 = 3 + gen1.index(stop) + 1
        assert out[1] == ref[1][:cut1]
    else:
        assert out[1] == ref[1]
    # max_new_tokens=0 generates nothing (single-path parity)
    assert model.generate_tokens_batched([[1, 2]], block_size=16,
                                         max_new_tokens=0,
                                         temperature=0.0) == [[1, 2]]
    with pytest.raises(ValueError, match="block_size"):
        model.generate_tokens_batched([[1] * 14], block_size=16,
                                      max_new_tokens=6, temperature=0.0)
    with pytest.raises(ValueError, match="at least one token"):
        model.generate_tokens_batched([[1], []], block_size=16,
                                      max_new_tokens=2, temperature=0.0)
    # batch-size cap guards the HTTP-reachable KV allocation (ADVICE r2)
    monkeypatch.setenv("PENROZ_MAX_GENERATE_BATCH", "2")
    with pytest.raises(ValueError, match="at most 2 prompts"):
        model.generate_tokens_batched([[1], [2], [3]], block_size=16,
                                      max_new_tokens=1, temperature=0.0)
    # unparseable cap falls back to the default instead of 400ing clients
    monkeypatch.setenv("PENROZ_MAX_GENERATE_BATCH", "not-a-number")
    assert model.generate_tokens_batched([[1, 2]], block_size=16,
                                         max_new_tokens=0,
                                         temperature=0.0) == [[1, 2]]


def test_batched_generate_sampled_ranges(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("bg3", Mapper(toy_gpt_layers, SGD))
    outs = model.generate_tokens_batched([[1], [2, 3]], block_size=16,
                                         max_new_tokens=4, temperature=0.9,
                                         top_k=8)
    assert len(outs) == 2
    assert outs[0][:1] == [1] and outs[1][:2] == [2, 3]
    for o in outs:
        assert all(0 <= t < 64 for t in o)


def test_batched_generate_matches_single_rope_gqa(workdir):
    """Batched == single for a RoPE+GQA stack (per-sequence rotary offsets
    through the ragged decode path)."""
    d, heads, kv, vocab = 32, 4, 2, 64
    layers = ([{"embedding": {"num_embeddings": vocab, "embedding_dim": d},
                "normal": {"mean": 0.0, "std": 0.05}}]
              + [{"transformerblock": {
                  "attn_block": {"sequential": [
                      {"rmsnorm": {"normalized_shape": d}},
                      {"linear": {"in_features": d,
                                  "out_features": (heads + 2 * kv) * 8,
                                  "bias": False}},
                      {"attention": {"num_heads": heads, "num_kv_heads": kv,
                                     "rope_theta": 10000.0, "head_dim": 8}},
                      {"linear": {"in_features": heads * 8,
                                  "out_features": d, "bias": False}}]},
                  "mlp_block": {"sequential": [
                      {"rmsnorm": {"normalized_shape": d}},
                      {"gatedmlp": {"in_features": d,
                                    "intermediate_size": 2 * d}}]},
                  "post_norm_on_residual": False}} for _ in range(2)]
              + [{"rmsnorm": {"normalized_shape": d}},
                 {"linear": {"in_features": d, "out_features": vocab,
                             "bias": False}},
                 {"softmaxlast": {"dim": -1}}])
    model = NeuralNetworkModel("bgrope", Mapper(layers, SGD))
    prompts = [[5, 6, 7, 8], [11, 12]]
    batched = model.generate_tokens_batched(prompts, block_size=16,
                                            max_new_tokens=5,
                                            temperature=0.0)
    for p, out in zip(prompts, batched):
        single = model.generate_tokens([p], block_size=16, max_new_tokens=5,
                                       temperature=0.0)
        assert out == single, (p, out, single)


def test_batched_generate_matches_single_sliding_window(workdir):
    """Batched == single for a sliding-window attention stack (per-sequence
    ragged masks combined with the window band)."""
    d, heads, vocab = 32, 4, 64
    layers = ([{"embedding": {"num_embeddings": vocab, "embedding_dim": d},
                "normal": {"mean": 0.0, "std": 0.05}}]
              + [{"residual": [
                  {"sequential": [
                      {"rmsnorm": {"normalized_shape": d}},
                      {"linear": {"in_features": d, "out_features": 3 * d,
                                  "bias": False}},
                      {"attention": {"num_heads": heads,
                                     "rope_theta": 10000.0,
                                     "sliding_window": 6}},
                      {"linear": {"in_features": d, "out_features": d,
                                  "bias": False}}]}]} for _ in range(2)]
              + [{"rmsnorm": {"normalized_shape": d}},
                 {"linear": {"in_features": d, "out_features": vocab,
                             "bias": False}},
                 {"softmaxlast": {"dim": -1}}])
    model = NeuralNetworkModel("bgwin", Mapper(layers, SGD))
    prompts = [[5, 6, 7, 8, 9, 10, 11], [21, 22]]
    batched = model.generate_tokens_batched(prompts, block_size=16,
                                            max_new_tokens=6,
                                            temperature=0.0)
    for p, out in zip(prompts, batched):
        single = model.generate_tokens([p], block_size=16, max_new_tokens=6,
                                       temperature=0.0)
        assert out == single, (p, out, single)


def test_decode_priority_yield(monkeypatch):
    """The between-epoch decode-priority window waits while decodes are
    pending (bounded by PENROZ_DECODE_PRIORITY_MS), no-ops when idle, and
    never pauses under multi-host (a one-sided stall)."""
    import time as _time
    from penroz_tpu.models import model as model_mod

    # idle: returns immediately
    t0 = _time.monotonic()
    model_mod._yield_to_decodes()
    assert _time.monotonic() - t0 < 0.05

    # pending: waits until the decode finishes
    monkeypatch.setenv("PENROZ_DECODE_PRIORITY_MS", "2000")
    import threading

    def decode():
        with model_mod.decode_priority():
            _time.sleep(0.15)

    th = threading.Thread(target=decode)
    th.start()
    # poll until the decode registers — a fixed sleep flakes on loaded
    # hosts where the thread may not have started within the window
    deadline = _time.monotonic() + 2.0
    while model_mod.decode_pending() == 0 and _time.monotonic() < deadline:
        _time.sleep(0.002)
    assert model_mod.decode_pending() > 0
    t0 = _time.monotonic()
    model_mod._yield_to_decodes()
    waited = _time.monotonic() - t0
    th.join()
    assert 0.05 < waited < 1.5, waited

    # cap: a stuck decode cannot starve training past the budget
    monkeypatch.setenv("PENROZ_DECODE_PRIORITY_MS", "100")
    with model_mod.decode_priority():
        t0 = _time.monotonic()
        model_mod._yield_to_decodes()
        waited = _time.monotonic() - t0
    assert 0.05 < waited < 1.0, waited

    # multi-host: never pauses (one-sided stall of peer collectives)
    from penroz_tpu.parallel import dist
    monkeypatch.setattr(dist, "process_count", lambda: 2)
    with model_mod.decode_priority():
        t0 = _time.monotonic()
        model_mod._yield_to_decodes()
        assert _time.monotonic() - t0 < 0.05


def test_generate_mesh_tp_parity(workdir, toy_gpt_layers, monkeypatch):
    """Mesh-aware /generate/: TP-sharded greedy decode emits exactly the
    single-device token sequence, and the params really are mesh-placed
    (sharded over >1 device) while it runs."""
    model = NeuralNetworkModel("gmesh", Mapper(toy_gpt_layers, SGD))
    want = model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=6,
                                 temperature=0.0)
    monkeypatch.setenv("PENROZ_MESH_MODEL", "2")
    got = model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=6,
                                temperature=0.0)
    assert got == want
    n_devs = {len(v.sharding.device_set) for v in model.params.values()}
    assert 2 in n_devs  # at least the big matmuls shard over the mesh


def test_generate_batched_mesh_tp_parity(workdir, toy_gpt_layers,
                                         monkeypatch):
    """Batched ragged decode under the decode mesh == unmeshed batched."""
    model = NeuralNetworkModel("gmeshb", Mapper(toy_gpt_layers, SGD))
    want = model.generate_tokens_batched([[1, 2, 3], [4]], block_size=16,
                                         max_new_tokens=5, temperature=0.0)
    monkeypatch.setenv("PENROZ_MESH_MODEL", "2")
    got = model.generate_tokens_batched([[1, 2, 3], [4]], block_size=16,
                                        max_new_tokens=5, temperature=0.0)
    assert got == want


def test_generate_mesh_skipped_for_paged_cache(workdir, toy_gpt_layers,
                                               monkeypatch):
    """Paged/int8 cache layouts have no mesh story yet: the decode mesh
    gate must leave them on the proven single-device path."""
    model = NeuralNetworkModel("gmeshp", Mapper(toy_gpt_layers, SGD))
    monkeypatch.setenv("PENROZ_MESH_MODEL", "2")
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    assert model._decode_mesh() is None
    tokens = model.generate_tokens([[1, 2]], block_size=16,
                                   max_new_tokens=3, temperature=0.0)
    assert len(tokens) == 5


# heaviest single test in the file; the microstep loop's scheduling
# behaviour stays pinned by test_train_microstepped_yields_between_micro_steps
@pytest.mark.slow
def test_train_microstepped_matches_fused(workdir, toy_gpt_layers,
                                          toy_shards, monkeypatch):
    """Decode-priority micro-step dispatch is numerics-identical to the
    fused epoch program: same fold_in stream, same fp32 accumulation
    order, shared finalize body.  Tolerance-level (not bitwise) equality:
    the standalone micro program and the scanned epoch body fuse
    differently under XLA."""
    from penroz_tpu.models import model as model_mod
    monkeypatch.setenv("PENROZ_DECODE_PRIORITY_MS", "1")
    fused = NeuralNetworkModel("mfull", Mapper(toy_gpt_layers, ADAMW))
    fused.train_model("toy", shard=0, epochs=2, batch_size=4, block_size=16,
                      step_size=1)
    chunked = NeuralNetworkModel("mchunk", Mapper(toy_gpt_layers, ADAMW))
    with model_mod.decode_priority():  # forces the micro-step path
        chunked.train_model("toy", shard=0, epochs=2, batch_size=4,
                            block_size=16, step_size=1)
    assert chunked.status["code"] == "Trained"
    for k in fused.params:
        np.testing.assert_allclose(np.asarray(chunked.params[k]),
                                   np.asarray(fused.params[k]),
                                   rtol=1e-3, atol=1e-5, err_msg=k)
    want = [p["cost"] for p in fused.progress]
    got = [p["cost"] for p in chunked.progress]
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_train_microstepped_yields_between_micro_steps(workdir,
                                                       toy_gpt_layers,
                                                       toy_shards,
                                                       monkeypatch):
    """With a decode pending, the trainer opens a priority window between
    every grad-accum micro-step (num_steps - 1 extra windows per epoch),
    bounding a decode's wait to one micro-step instead of one epoch."""
    from penroz_tpu.models import model as model_mod
    monkeypatch.setenv("PENROZ_DECODE_PRIORITY_MS", "1")
    calls = []
    monkeypatch.setattr(model_mod, "_yield_to_decodes",
                        lambda: calls.append(1))
    model = NeuralNetworkModel("myld", Mapper(toy_gpt_layers, ADAMW))
    with model_mod.decode_priority():
        # batch 4 x block 16 / (step 1 x block 16) = 4 micro-steps
        model.train_model("toy", shard=0, epochs=2, batch_size=4,
                          block_size=16, step_size=1)
    # 2 epochs x (1 between-epoch + 3 between-micro) windows
    assert len(calls) == 2 * 4, calls


def test_train_worker_process_completes(workdir, toy_gpt_layers, toy_shards,
                                        monkeypatch):
    """PENROZ_TRAIN_WORKER=1 trains in a child process; state round-trips
    through the checkpoint stream and the parent sees Trained."""
    monkeypatch.setenv("PENROZ_TRAIN_WORKER", "1")
    model = NeuralNetworkModel("wrk", Mapper(toy_gpt_layers, ADAMW))
    model.serialize(sync_flush=True)
    out = NeuralNetworkModel.train_model_on_device("wrk", None, "toy", 0,
                                                   2, 4, 16, 1)
    assert out.status["code"] == "Trained"
    assert len(out.progress) == 2
    assert np.isfinite(out.progress[-1]["cost"])


def test_train_worker_crash_contained(workdir, toy_gpt_layers, toy_shards,
                                      monkeypatch):
    """Kill the training worker mid-run: the parent marks the model Error
    (same contract as the startup orphan sweep, applied immediately) and
    keeps serving /generate/ from the last checkpoint — the reference's
    process-isolation robustness property (main.py:461-464)."""
    import threading
    import time as _time
    from penroz_tpu.models import model as model_mod
    monkeypatch.setenv("PENROZ_TRAIN_WORKER", "1")
    model = NeuralNetworkModel("wrkk", Mapper(toy_gpt_layers, ADAMW))
    model.serialize(sync_flush=True)
    result = {}

    def run():
        result["model"] = NeuralNetworkModel.train_model_on_device(
            "wrkk", None, "toy", 0, 2000, 4, 16, 1)

    th = threading.Thread(target=run)
    th.start()
    deadline = _time.monotonic() + 120
    proc = None
    while _time.monotonic() < deadline:  # wait for the run to really start
        proc = model_mod._TRAIN_WORKERS.get("wrkk")
        if proc is not None:
            try:
                if NeuralNetworkModel.deserialize(
                        "wrkk").status["code"] == "Training":
                    break
            except Exception:  # noqa: BLE001 — checkpoint mid-write
                pass
        _time.sleep(0.1)
    assert proc is not None, "worker never spawned"
    proc.kill()
    th.join(timeout=120)
    assert not th.is_alive()
    out = result["model"]
    assert out.status["code"] == "Error"
    assert "worker died" in out.status["message"]
    tokens = out.generate_tokens([[1, 2]], block_size=16, max_new_tokens=3,
                                 temperature=0.0)
    assert len(tokens) == 5


def test_generate_mesh_preserves_training_layout(workdir, toy_gpt_layers,
                                                 monkeypatch):
    """A decode arriving while params are already mesh-placed (e.g. ZeRO-3
    training layout) must not reshard them onto the decode submesh —
    gathering FSDP storage could OOM the models FSDP exists for, and
    layout flapping would recompile the training step per interleave."""
    import jax
    from penroz_tpu.parallel import mesh as mesh_lib
    from penroz_tpu.parallel import sharding as sharding_lib
    model = NeuralNetworkModel("gkeep", Mapper(toy_gpt_layers, SGD))
    mesh = mesh_lib.make_mesh(jax.local_devices())  # data=8
    model.params = sharding_lib.shard_params(model.params, mesh, fsdp=True)
    before = {k: v.sharding for k, v in model.params.items()}
    monkeypatch.setenv("PENROZ_MESH_MODEL", "2")
    tokens = model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=3,
                                   temperature=0.0)
    assert len(tokens) == 5
    assert {k: v.sharding for k, v in model.params.items()} == before


def test_generate_batched_dp_mesh_parity(workdir, toy_gpt_layers,
                                         monkeypatch):
    """PENROZ_DECODE_DP=1: batched decode rows shard over the data axis
    (pure DP — no TP configured) and greedy outputs stay identical."""
    model = NeuralNetworkModel("gdp", Mapper(toy_gpt_layers, SGD))
    prompts = [[1, 2, 3], [4], [5, 6], [7]]
    want = model.generate_tokens_batched(prompts, block_size=16,
                                         max_new_tokens=5, temperature=0.0)
    monkeypatch.setenv("PENROZ_DECODE_DP", "1")
    assert model._decode_mesh(batch=4) is not None
    assert model._decode_mesh() is None  # single-stream: no DP axis
    got = model.generate_tokens_batched(prompts, block_size=16,
                                        max_new_tokens=5, temperature=0.0)
    assert got == want


def test_generate_batched_dp_with_tp_parity(workdir, toy_gpt_layers,
                                            monkeypatch):
    """DP x TP decode mesh: rows over `data`, weights/KV heads over
    `model`, same greedy tokens."""
    model = NeuralNetworkModel("gdptp", Mapper(toy_gpt_layers, SGD))
    prompts = [[1, 2, 3], [4]]
    want = model.generate_tokens_batched(prompts, block_size=16,
                                         max_new_tokens=4, temperature=0.0)
    monkeypatch.setenv("PENROZ_DECODE_DP", "1")
    monkeypatch.setenv("PENROZ_MESH_MODEL", "2")
    mesh = model._decode_mesh(batch=2)
    assert mesh is not None and mesh.shape["data"] == 2 \
        and mesh.shape["model"] == 2
    got = model.generate_tokens_batched(prompts, block_size=16,
                                        max_new_tokens=4, temperature=0.0)
    assert got == want


def test_generate_alibi_paged_matches_contiguous(workdir, monkeypatch):
    """ALiBi attention through the PAGED cache (block tables + in-jit
    allocator) must produce the same greedy tokens as the contiguous
    cache — the bias rides the cache positions in both layouts."""
    d, heads, vocab = 16, 4, 32
    layers = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d}},
        {"residual": [
            {"sequential": [
                {"layernorm": {"normalized_shape": d}},
                {"linear": {"in_features": d, "out_features": 3 * d},
                 "normal": {"mean": 0.0, "std": 0.2}},
                {"attention": {"num_heads": heads, "dropout": 0.0,
                               "alibi": True}},
                {"linear": {"in_features": d, "out_features": d}}]}]},
        {"linear": {"in_features": d, "out_features": vocab,
                    "bias": False}},
        {"softmaxlast": {"dim": -1}}]
    model = NeuralNetworkModel("alibip", Mapper(layers, SGD))
    want = model.generate_tokens([[1, 2, 3]], block_size=256,
                                 max_new_tokens=6, temperature=0.0)
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    got = model.generate_tokens([[1, 2, 3]], block_size=256,
                                max_new_tokens=6, temperature=0.0)
    assert got == want


@pytest.mark.parametrize("batch, step, world, want", [
    (12, 1, 1, 12),         # the GPT-2 cell: a micro-step a row
    (2, 1, 1, 2),
    (5, 2, 1, 2),           # floored, as the reference floors
    (8, 2, 2, 2),           # two hosts halve it
    (1, 1, 1, 1),
    (2, 3, 1, 1),           # never under one
    (1, 0.25, 1, 4),        # more micro-steps than the batch has rows
    (2, 0.5, 1, 4),
    (1, 0.1, 1, 10),        # 1 // 0.1 is 9.0
])
def test_accumulation_steps(batch, step, world, want):
    from penroz_tpu.models.model import accumulation_steps
    got = accumulation_steps(batch, step, world)
    assert got == want and isinstance(got, int)


def test_accumulation_steps_refuses_a_step_size_of_zero():
    from penroz_tpu.models.model import accumulation_steps
    from penroz_tpu.serve.schemas import TrainingRequest
    with pytest.raises(ValueError, match="step_size must be positive"):
        accumulation_steps(1, 0)
    body = {"model_id": "m", "device": "cpu", "dataset_id": "toy",
            "shard": 0, "epochs": 1, "batch_size": 1, "block_size": 16}
    assert TrainingRequest(**body, step_size=0.25).step_size == 0.25
    assert TrainingRequest(**body, step_size=2).step_size == 2
    with pytest.raises(ValueError):
        TrainingRequest(**body, step_size=0)


def test_fractional_step_size_accumulates_more_micro_steps_than_rows(
        workdir, toy_gpt_layers, toy_shards, monkeypatch):
    """``batch_size`` 1 with ``step_size`` 0.25: an optimizer step is four
    micro-steps of one row, each its own buffer from the loader."""
    from penroz_tpu.models.model import CompiledArch
    seen = []
    make = CompiledArch.train_epoch_fn

    def spying(arch, optimizer_config, num_steps, *args, **kwargs):
        fn = make(arch, optimizer_config, num_steps, *args, **kwargs)

        def epoch(params, opt_state, buffers, xs, ys, rng):
            seen.append((num_steps, np.asarray(xs).copy()))
            return fn(params, opt_state, buffers, xs, ys, rng)
        return epoch

    monkeypatch.setattr(CompiledArch, "train_epoch_fn", spying)
    model = NeuralNetworkModel("acc", Mapper(toy_gpt_layers, ADAMW))
    model.train_model("toy", shard=0, epochs=2, batch_size=1, block_size=16,
                      step_size=0.25)
    assert model.status["code"] == "Trained"
    assert [(n, xs.shape) for n, xs in seen] == [(4, (4, 1, 16))] * 2
    rows = np.concatenate([xs.reshape(4, 16) for _, xs in seen])
    shard = np.load(workdir / "data" / "toy_000000.npy")
    np.testing.assert_array_equal(rows.reshape(-1), shard[:8 * 16])
