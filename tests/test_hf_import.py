"""HuggingFace import tests with locally constructed torch models (offline).

Goes beyond the reference's key-set assertions (test_neural_net_model.py HF
mocks): imports weights through the real mapping path and checks our JAX
forward produces the same logits as the torch model."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")

from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import NeuralNetworkModel

# CI tier: heavier compiles (see pyproject markers / ci.yml shards).
pytestmark = pytest.mark.runtime


def _tiny_gpt2():
    from transformers import GPT2Config, GPT2LMHeadModel
    config = GPT2Config(vocab_size=96, n_positions=32, n_embd=16, n_layer=2,
                        n_head=2, activation_function="gelu_new",
                        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)
    torch.manual_seed(0)
    return config, GPT2LMHeadModel(config).eval()


def _tiny_gemma2():
    from transformers import Gemma2Config, Gemma2ForCausalLM
    config = Gemma2Config(vocab_size=96, hidden_size=16, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          head_dim=8, intermediate_size=32,
                          max_position_embeddings=64, rope_theta=10000.0,
                          attn_logit_softcapping=None,
                          final_logit_softcapping=None,
                          query_pre_attn_scalar=8, sliding_window=64,
                          attention_dropout=0.0,
                          hidden_activation="gelu_pytorch_tanh")
    torch.manual_seed(0)
    return config, Gemma2ForCausalLM(config).eval()


def _save_checkpoint(workdir, torch_model, name) -> str:
    """Serialize the oracle model as a real safetensors checkpoint dir —
    every import test then exercises the torch-free load path end to end
    (config.json + model.safetensors, tied weights omitted by HF)."""
    ckpt = str(workdir / f"hf_{name}")
    torch_model.to(torch.bfloat16).save_pretrained(ckpt,
                                                   safe_serialization=True)
    return ckpt


def _import_model(workdir, config, torch_model, model_id):
    del config  # read back from the checkpoint's config.json
    ckpt = _save_checkpoint(workdir, torch_model, model_id)
    return NeuralNetworkModel.from_huggingface(model_id, ckpt)


def test_gpt2_import_logit_parity(workdir):
    config, torch_model = _tiny_gpt2()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "gpt2-tiny")
    assert model.status["code"] == "Imported"
    import jax.numpy as jnp
    assert model.dtype == jnp.bfloat16

    acts, cost, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                              jnp.asarray(tokens, jnp.int32),
                                              skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    # bf16 weights end-to-end: compare softmax-invariant shifted logits
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    # argmax parity position-by-position
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8


def test_gpt2_import_roundtrip_and_generate(workdir):
    config, torch_model = _tiny_gpt2()
    _import_model(workdir, config, torch_model, "gpt2-rt")
    loaded = NeuralNetworkModel.deserialize("gpt2-rt")
    assert loaded.status["code"] == "Imported"
    tokens = loaded.generate_tokens([[1, 2, 3]], block_size=16,
                                    max_new_tokens=4, temperature=0.0)
    assert len(tokens) == 7
    assert all(0 <= t < 96 for t in tokens)


def test_gemma2_import_logit_parity(workdir):
    config, torch_model = _tiny_gemma2()
    tokens = np.array([[3, 17, 42, 8]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "gemma-tiny")
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.2)


def test_import_rejects_mismatched_state_dict(workdir):
    """A checkpoint missing a param key fails loudly (strict key-set
    equality, reference load_state_dict(strict=True) analog)."""
    from safetensors.numpy import load_file, save_file
    _, torch_model = _tiny_gpt2()
    ckpt = _save_checkpoint(workdir, torch_model, "broken")
    path = f"{ckpt}/model.safetensors"
    sd = load_file(path)
    sd.pop("transformer.h.1.mlp.c_proj.bias")
    save_file(sd, path)
    with pytest.raises(KeyError):
        NeuralNetworkModel.from_huggingface("broken", ckpt)


def test_import_is_torch_free(workdir, monkeypatch):
    """/import/ of a local safetensors GPT-2 succeeds with torch import
    blocked (safetensors→numpy direct load, SURVEY §2.3; torch remains
    only this file's oracle)."""
    import sys
    import transformers.configuration_utils as tcu
    _, torch_model = _tiny_gpt2()
    ckpt = _save_checkpoint(workdir, torch_model, "notorch")
    # None in sys.modules makes any fresh `import torch` raise ImportError;
    # is_torch_available must lie too or transformers eagerly converts the
    # config.json torch_dtype string (it skips that in a real no-torch env)
    monkeypatch.setitem(sys.modules, "torch", None)
    monkeypatch.setattr(tcu, "is_torch_available", lambda: False)
    model = NeuralNetworkModel.from_huggingface("notorch", ckpt)
    assert model.status["code"] == "Imported"
    tokens = model.generate_tokens([[1, 2, 3]], block_size=16,
                                   max_new_tokens=3, temperature=0.0)
    assert len(tokens) == 6


def test_import_unprefixed_base_model_checkpoint(workdir):
    """The original ``gpt2`` hub checkpoints were saved from the bare base
    model — keys lack the ``transformer.`` prefix and carry extra mask
    buffers; the loader canonicalizes them (hf_loader._normalize)."""
    from safetensors.numpy import load_file, save_file
    _, torch_model = _tiny_gpt2()
    ckpt = _save_checkpoint(workdir, torch_model, "rawgpt2")
    path = f"{ckpt}/model.safetensors"
    sd = load_file(path)
    raw = {k.removeprefix("transformer."): v for k, v in sd.items()
           if not k.startswith("lm_head.")}
    raw["h.0.attn.bias"] = np.tril(np.ones((32, 32), np.float32))[None, None]
    save_file(raw, path)
    model = NeuralNetworkModel.from_huggingface("rawgpt2", ckpt)
    assert model.status["code"] == "Imported"
    assert model.params["layers.0.0.weight"].shape == (96, 16)


def test_bin_only_checkpoint_without_torch_is_clear_error(workdir,
                                                          monkeypatch):
    import sys
    from penroz_tpu.models import hf_loader
    _, torch_model = _tiny_gpt2()
    ckpt = str(workdir / "binonly")
    torch_model.save_pretrained(ckpt, safe_serialization=False)
    monkeypatch.setitem(sys.modules, "torch", None)
    with pytest.raises(RuntimeError, match="safetensors"):
        hf_loader.load_state_dict(ckpt)


def _tiny_llama():
    from transformers import LlamaConfig, LlamaForCausalLM
    config = LlamaConfig(vocab_size=96, hidden_size=16, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=4, intermediate_size=32,
                         max_position_embeddings=64, rope_theta=10000.0,
                         attention_dropout=0.0, hidden_act="silu",
                         attention_bias=False, mlp_bias=False,
                         tie_word_embeddings=False)
    torch.manual_seed(0)
    return config, LlamaForCausalLM(config).eval()


def _tiny_qwen2():
    from transformers import Qwen2Config, Qwen2ForCausalLM
    config = Qwen2Config(vocab_size=96, hidden_size=16, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         intermediate_size=32, max_position_embeddings=64,
                         rope_theta=10000.0, attention_dropout=0.0,
                         hidden_act="silu", tie_word_embeddings=True)
    torch.manual_seed(0)
    return config, Qwen2ForCausalLM(config).eval()


def test_llama_import_logit_parity(workdir):
    """Llama family (beyond reference parity): straight RMSNorm copy, no
    embedding scale, untied lm_head, GQA + RoPE."""
    config, torch_model = _tiny_llama()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "llama-tiny")
    assert model.status["code"] == "Imported"
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8


def test_qwen2_import_logit_parity_and_generate(workdir):
    """Qwen2: hardcoded QKV bias (concat-mapped), no o bias, tied lm_head."""
    config, torch_model = _tiny_qwen2()
    tokens = np.array([[5, 9, 63, 2]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "qwen-tiny")
    import jax.numpy as jnp
    assert "layers.1.attn_block.1.bias" in model.params  # qkv bias mapped
    assert "layers.1.attn_block.3.bias" not in model.params  # o has none
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    gen = NeuralNetworkModel.deserialize("qwen-tiny").generate_tokens(
        [[1, 2, 3]], block_size=16, max_new_tokens=4, temperature=0.0)
    assert len(gen) == 7 and all(0 <= t < 96 for t in gen)


def test_llama3_rope_scaling_logit_parity(workdir):
    """Llama 3.1-style rope_scaling (llama3 inverse-frequency rescale) must
    match the torch implementation's logits, not just import."""
    from transformers import LlamaConfig, LlamaForCausalLM
    config = LlamaConfig(vocab_size=96, hidden_size=16, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=4, intermediate_size=32,
                         max_position_embeddings=128, rope_theta=10000.0,
                         attention_dropout=0.0, tie_word_embeddings=False,
                         rope_scaling={"rope_type": "llama3", "factor": 8.0,
                                       "low_freq_factor": 1.0,
                                       "high_freq_factor": 4.0,
                                       "original_max_position_embeddings": 16})
    torch.manual_seed(0)
    torch_model = LlamaForCausalLM(config).eval()
    # positions past original_max_position_embeddings exercise the rescale
    tokens = np.arange(24, dtype=np.int64)[None, :] % 96
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "llama31-tiny")
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)


def test_llama_unsupported_rope_scaling_rejected():
    """Non-llama3 active scaling types (yarn, dynamic) must fail the import
    loudly — importing with them ignored would silently produce wrong
    logits."""
    from transformers import LlamaConfig
    config = LlamaConfig(vocab_size=96, hidden_size=16, num_hidden_layers=1,
                         num_attention_heads=4, num_key_value_heads=2,
                         head_dim=4, intermediate_size=32,
                         rope_scaling={"rope_type": "yarn", "factor": 4.0})
    with pytest.raises(ValueError, match="rope_scaling"):
        Mapper.from_hf_config(config)


def test_dsl_rope_scaling_validated_at_build():
    """rope_scaling is validated where the DSL reaches the module (POST
    /model/ → 400), not only in the HF importer — a yarn dict must not
    silently run the llama3 formula."""
    from penroz_tpu.ops.modules import CausalSelfAttention
    with pytest.raises(ValueError, match="not supported"):
        CausalSelfAttention(num_heads=2, rope_theta=1e4,
                            rope_scaling={"rope_type": "yarn", "factor": 4.0})
    with pytest.raises(ValueError, match="missing keys"):
        CausalSelfAttention(num_heads=2, rope_theta=1e4,
                            rope_scaling={"rope_type": "llama3"})


def test_mistral_sliding_window_logit_parity(workdir):
    """Mistral imports with REAL windowed attention: logits must match
    torch at sequence lengths beyond the sliding window (the reference
    keeps all attention full causal and would diverge here)."""
    from transformers import MistralConfig, MistralForCausalLM
    config = MistralConfig(vocab_size=96, hidden_size=16, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=4, intermediate_size=32,
                           max_position_embeddings=128, rope_theta=10000.0,
                           attention_dropout=0.0, sliding_window=8,
                           tie_word_embeddings=False)
    torch.manual_seed(0)
    torch_model = MistralForCausalLM(config).eval()
    tokens = (np.arange(24, dtype=np.int64)[None, :] * 7) % 96  # 24 > 8
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "mistral-tiny")
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    # windowed decode path works too
    gen = NeuralNetworkModel.deserialize("mistral-tiny").generate_tokens(
        [[1, 2, 3]], block_size=32, max_new_tokens=12, temperature=0.0)
    assert len(gen) == 15


def test_gemma2_sliding_layers_logit_parity(workdir):
    """Gemma-2 layer_types: sliding layers get windowed attention, full
    layers stay full — parity vs torch past the window."""
    from transformers import Gemma2Config, Gemma2ForCausalLM
    config = Gemma2Config(vocab_size=96, hidden_size=16, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          head_dim=8, intermediate_size=32,
                          max_position_embeddings=64, rope_theta=10000.0,
                          attn_logit_softcapping=None,
                          final_logit_softcapping=None,
                          query_pre_attn_scalar=8, sliding_window=8,
                          layer_types=["sliding_attention", "full_attention"],
                          attention_dropout=0.0,
                          hidden_activation="gelu_pytorch_tanh")
    torch.manual_seed(0)
    torch_model = Gemma2ForCausalLM(config).eval()
    tokens = (np.arange(20, dtype=np.int64)[None, :] * 5) % 96  # 20 > 8
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "gemma2-sw")
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.2)


def test_qwen2_max_window_layers_gating():
    """Qwen2 use_sliding_window windows only the layers HF marks
    'sliding_attention' (max_window_layers full layers first), not all."""
    from transformers import Qwen2Config
    config = Qwen2Config(vocab_size=96, hidden_size=16, num_hidden_layers=4,
                         num_attention_heads=4, num_key_value_heads=2,
                         intermediate_size=32, use_sliding_window=True,
                         sliding_window=8, max_window_layers=2)
    layers = Mapper.from_hf_config(config)
    blocks = [l["transformerblock"] for l in layers if "transformerblock" in l]
    windows = [b["attn_block"]["sequential"][2]["attention"]
               .get("sliding_window") for b in blocks]
    expected = [8 if lt == "sliding_attention" else None
                for lt in config.layer_types]
    assert windows == expected
    assert None in windows  # some layers stay full...
    assert 8 in windows     # ...and some are windowed


def test_rope_scaling_numeric_validation():
    """Degenerate llama3 scaling numbers NaN every logit via the band
    smoothing's (high - low) division — reject at build time."""
    from penroz_tpu.ops.modules import CausalSelfAttention
    base = {"rope_type": "llama3", "factor": 8.0,
            "original_max_position_embeddings": 8192}
    with pytest.raises(ValueError, match="high_freq_factor"):
        CausalSelfAttention(num_heads=2, rope_theta=1e4,
                            rope_scaling={**base, "low_freq_factor": 2.0,
                                          "high_freq_factor": 2.0})
    with pytest.raises(ValueError, match="factor must be"):
        CausalSelfAttention(num_heads=2, rope_theta=1e4,
                            rope_scaling={**base, "factor": 0.5})


def _tiny_neox(parallel=True):
    from transformers import GPTNeoXConfig, GPTNeoXForCausalLM
    config = GPTNeoXConfig(vocab_size=96, hidden_size=32,
                           num_hidden_layers=2, num_attention_heads=2,
                           intermediate_size=64, rotary_pct=0.25,
                           max_position_embeddings=64,
                           use_parallel_residual=parallel,
                           hidden_act="gelu", attention_dropout=0.0,
                           hidden_dropout=0.0, tie_word_embeddings=False)
    torch.manual_seed(0)
    return config, GPTNeoXForCausalLM(config).eval()


def test_neox_import_logit_parity(workdir):
    """GPT-NeoX/Pythia: parallel-residual blocks, partial rotary
    (rotary_pct), per-head-interleaved QKV de-interleaved, untied
    embed_out (beyond reference parity)."""
    config, torch_model = _tiny_neox()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "neox-tiny")
    assert model.status["code"] == "Imported"
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8


def test_neox_sequential_residual_logit_parity(workdir):
    """use_parallel_residual=False checkpoints get the ordinary
    sequential-residual block and still match torch."""
    config, torch_model = _tiny_neox(parallel=False)
    tokens = np.array([[5, 1, 60, 22]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "neox-seq")
    import jax.numpy as jnp
    assert "parallelresidual" not in str(model.layers_dsl)
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)


def _greedy_rollout(model, ctx, steps, block=16):
    """Token-by-token UNCACHED argmax continuation of ``ctx`` (the oracle
    the KV-cached greedy generate must match)."""
    import jax.numpy as jnp
    ctx = list(ctx)
    for _ in range(steps):
        acts, _, _, _ = model.arch.jit_forward(
            model.params, model.buffers,
            jnp.asarray([ctx[-block:]], jnp.int32), skip_softmax=True)
        logits = np.asarray(acts[-1], np.float32)
        if logits.ndim == 3:
            logits = logits[:, -1, :]
        ctx.append(int(logits.argmax(-1)[0]))
    return ctx


def test_neox_cached_generate_matches_uncached(workdir):
    """Partial rotary must behave identically through the KV-cached decode
    path (rope offset applied to the rotary dims only): greedy cached
    generation must equal a token-by-token UNCACHED argmax rollout."""
    import jax.numpy as jnp
    config, torch_model = _tiny_neox()
    model = _import_model(workdir, config, torch_model, "neox-gen")
    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert len(toks) == 9
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def test_neox_rope_scaling_rejected():
    """Active rope_scaling on gpt_neox is unsupported — reject at DSL build
    rather than importing with it silently ignored (wrong logits)."""
    from penroz_tpu.models.dsl import Mapper

    class Cfg:
        model_type = "gpt_neox"
        hidden_size = 32
        num_hidden_layers = 1
        num_attention_heads = 2
        vocab_size = 96
        rope_scaling = {"type": "linear", "factor": 2.0}

    with pytest.raises(ValueError, match="rope_scaling"):
        Mapper.from_hf_config(Cfg())


def test_neox_attention_bias_false_logit_parity(workdir):
    """attention_bias=False checkpoints carry no qkv/dense biases; the DSL
    must build bias-free linears and still match torch."""
    from transformers import GPTNeoXConfig, GPTNeoXForCausalLM
    config = GPTNeoXConfig(vocab_size=96, hidden_size=32,
                           num_hidden_layers=2, num_attention_heads=2,
                           intermediate_size=64, rotary_pct=0.25,
                           max_position_embeddings=64,
                           use_parallel_residual=True, hidden_act="gelu",
                           attention_bias=False, attention_dropout=0.0,
                           hidden_dropout=0.0, tie_word_embeddings=False)
    torch.manual_seed(1)
    torch_model = GPTNeoXForCausalLM(config).eval()
    tokens = np.array([[7, 30, 2, 19]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "neox-nobias")
    import jax.numpy as jnp
    assert "layers.1.0.1.bias" not in model.params
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)


def _tiny_phi():
    from transformers import PhiConfig, PhiForCausalLM
    config = PhiConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                       num_attention_heads=2, intermediate_size=64,
                       partial_rotary_factor=0.5,
                       max_position_embeddings=64, hidden_act="gelu_new",
                       attention_dropout=0.0, resid_pdrop=0.0,
                       embd_pdrop=0.0, tie_word_embeddings=False)
    torch.manual_seed(0)
    return config, PhiForCausalLM(config).eval()


def test_phi_import_logit_parity(workdir):
    """Phi-1/1.5/2: parallel attn+MLP branches sharing ONE input LayerNorm
    (residual -> ln -> summation nesting), partial rotary, biased
    projections and a biased lm_head (beyond reference parity)."""
    config, torch_model = _tiny_phi()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "phi-tiny")
    assert model.status["code"] == "Imported"
    assert "summation" in str(model.layers_dsl)
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8


# the cached-vs-uncached generate seam is pinned fast by the NeoX variant
@pytest.mark.slow
def test_phi_cached_generate_matches_uncached(workdir):
    """Phi partial rotary + biased fused QKV through the KV-cached decode
    path: greedy cached generation == uncached argmax rollout."""
    import jax.numpy as jnp
    config, torch_model = _tiny_phi()
    model = _import_model(workdir, config, torch_model, "phi-gen")
    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert len(toks) == 9
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def test_phi_qk_layernorm_rejected():
    from transformers import PhiConfig
    from penroz_tpu.models.dsl import Mapper
    config = PhiConfig(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                       num_attention_heads=2, qk_layernorm=True)
    with pytest.raises(ValueError, match="qk_layernorm"):
        Mapper.from_hf_config(config)
    tied = PhiConfig(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                     num_attention_heads=2, tie_word_embeddings=True)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        Mapper.from_hf_config(tied)
    # partial_rotary_factor=0.0 disables rope instead of being coerced
    norope = PhiConfig(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                       num_attention_heads=2, partial_rotary_factor=0.0)
    dsl = Mapper.from_hf_config(norope)
    assert "rope_theta" not in __import__("json").dumps(dsl)


def _tiny_qwen3():
    from transformers import Qwen3Config, Qwen3ForCausalLM
    config = Qwen3Config(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                         num_attention_heads=2, num_key_value_heads=1,
                         head_dim=16, intermediate_size=64,
                         max_position_embeddings=64, rope_theta=10000.0,
                         attention_dropout=0.0, tie_word_embeddings=False,
                         use_sliding_window=False)
    torch.manual_seed(0)
    return config, Qwen3ForCausalLM(config).eval()


def test_qwen3_import_logit_parity_and_generate(workdir):
    """Qwen3: llama family + per-head RMS qk-norm (learned (head_dim,)
    weights applied before RoPE) and GQA; cached greedy generation must
    match the uncached argmax rollout through the normalized path."""
    import jax.numpy as jnp
    config, torch_model = _tiny_qwen3()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "qwen3-tiny")
    assert model.status["code"] == "Imported"
    assert any("q_norm" in k for k in model.params), model.params.keys()
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def _tiny_mixtral():
    from transformers import MixtralConfig, MixtralForCausalLM
    config = MixtralConfig(vocab_size=96, hidden_size=32,
                           num_hidden_layers=2, num_attention_heads=2,
                           num_key_value_heads=1, intermediate_size=48,
                           num_local_experts=4, num_experts_per_tok=2,
                           max_position_embeddings=64, rope_theta=10000.0,
                           sliding_window=None, attention_dropout=0.0,
                           router_aux_loss_coef=0.02,
                           tie_word_embeddings=False)
    torch.manual_seed(0)
    return config, MixtralForCausalLM(config).eval()


# slow lane (tier1_budget): MoE forward math stays fast via test_moe and
# the qwen2-moe import gate; stacked-expert import parity rides slow
@pytest.mark.slow
def test_mixtral_import_logit_parity_and_generate(workdir):
    """Mixtral: sparse-MoE MLPs land on our stacked-expert module (dense
    dispatch reproduces HF's softmax->top-k->renormalize routing exactly);
    per-expert w1/w3/w2 stack onto gate/up/down, router gate copies, and
    router_aux_loss_coef rescales (x top_k / n_layers) onto our per-layer Switch form."""
    config, torch_model = _tiny_mixtral()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "mixtral-tiny")
    assert model.status["code"] == "Imported"
    assert any("router.weight" in k for k in model.params)
    # router_aux_loss_coef normalized to HF semantics:
    # 0.02 * top_k(2) / n_layers(2) = 0.02
    assert '"aux_loss_coef": 0.02' in __import__("json").dumps(
        model.layers_dsl)
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def _tiny_olmo2():
    from transformers import Olmo2Config, Olmo2ForCausalLM
    config = Olmo2Config(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                         num_attention_heads=2, num_key_value_heads=1,
                         intermediate_size=64, max_position_embeddings=64,
                         rope_theta=10000.0, attention_dropout=0.0,
                         tie_word_embeddings=False)
    torch.manual_seed(0)
    return config, Olmo2ForCausalLM(config).eval()


# slow lane (tier1_budget): OLMo v1 keeps the family's import parity
# fast; olmo2's unique qk-norm wiring is also pinned by qwen3
@pytest.mark.slow
def test_olmo2_import_logit_parity_and_generate(workdir):
    """OLMo-2: post-norm-only blocks (branch-tail rmsnorms, no input
    norms) and FLAT q/k RMS normalization over the whole projection before
    the head split — cached greedy generate must match the uncached argmax
    rollout through that path."""
    config, torch_model = _tiny_olmo2()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "olmo2-tiny")
    assert model.status["code"] == "Imported"
    assert any("q_norm" in k for k in model.params)
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def test_olmo2_rope_scaling_rejected():
    from transformers import Olmo2Config
    from penroz_tpu.models.dsl import Mapper
    config = Olmo2Config(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                         num_attention_heads=2,
                         rope_scaling={"type": "linear", "factor": 2.0})
    with pytest.raises(ValueError, match="olmo2 rope_scaling"):
        Mapper.from_hf_config(config)


def _tiny_olmo(clip_qkv=None):
    from transformers import OlmoConfig, OlmoForCausalLM
    config = OlmoConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                        num_attention_heads=2, num_key_value_heads=1,
                        intermediate_size=64, max_position_embeddings=64,
                        rope_theta=10000.0, attention_dropout=0.0,
                        clip_qkv=clip_qkv, tie_word_embeddings=False)
    torch.manual_seed(0)
    return config, OlmoForCausalLM(config).eval()


@pytest.mark.parametrize("clip_qkv", [None, pytest.param(0.5, marks=pytest.mark.slow)])
def test_olmo_import_logit_parity(workdir, clip_qkv):
    """OLMo v1: NON-PARAMETRIC LayerNorms (no weights to map at all) and
    optional clip_qkv (fused QKV output clamped to ±clip via the clamp
    DSL entry, shifting the branch's item indices)."""
    config, torch_model = _tiny_olmo(clip_qkv=clip_qkv)
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    tag = "olmo-clip" if clip_qkv else "olmo-tiny"
    model = _import_model(workdir, config, torch_model, tag)
    assert model.status["code"] == "Imported"
    assert not any("layernorm" in k.lower() or ".0.0." in k
                   for k in model.params), \
        [k for k in model.params if ".0.0." in k]
    assert ('"clamp"' in __import__("json").dumps(model.layers_dsl)) == \
        (clip_qkv is not None)
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def _tiny_stablelm(use_qkv_bias=True):
    from transformers import StableLmConfig, StableLmForCausalLM
    config = StableLmConfig(vocab_size=96, hidden_size=32,
                            num_hidden_layers=2, num_attention_heads=2,
                            num_key_value_heads=1, intermediate_size=64,
                            partial_rotary_factor=0.5,
                            max_position_embeddings=64,
                            use_qkv_bias=use_qkv_bias,
                            attention_dropout=0.0,
                            tie_word_embeddings=False)
    torch.manual_seed(0)
    return config, StableLmForCausalLM(config).eval()


# partial-rotary + qkv-bias import seams stay fast via the phi3/qwen3 tests
@pytest.mark.slow
@pytest.mark.parametrize("use_qkv_bias", [True, False])
def test_stablelm_import_logit_parity_and_generate(workdir, use_qkv_bias):
    """StableLM: llama-shaped blocks with LayerNorm (weight+bias) norms,
    partial rotary, qkv bias on and off (the DSL bias flag is config-
    driven while the mapper keys off presence — both must stay in sync);
    cached greedy == uncached rollout."""
    config, torch_model = _tiny_stablelm(use_qkv_bias=use_qkv_bias)
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model,
                          f"stablelm-{'b' if use_qkv_bias else 'nb'}")
    assert model.status["code"] == "Imported"
    assert any(k.endswith("attn_block.0.bias") for k in model.params)
    assert any(k.endswith("attn_block.1.bias")
               for k in model.params) == use_qkv_bias
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def test_stablelm_variant_rejections():
    from transformers import StableLmConfig
    from penroz_tpu.models.dsl import Mapper
    par = StableLmConfig(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                         num_attention_heads=2, use_parallel_residual=True)
    with pytest.raises(ValueError, match="use_parallel_residual"):
        Mapper.from_hf_config(par)
    qk = StableLmConfig(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                        num_attention_heads=2, qk_layernorm=True)
    with pytest.raises(ValueError, match="qk_layernorm"):
        Mapper.from_hf_config(qk)


def _tiny_gptj():
    from transformers import GPTJConfig, GPTJForCausalLM
    config = GPTJConfig(vocab_size=96, n_positions=64, n_embd=32, n_layer=2,
                        n_head=2, rotary_dim=8, n_inner=None,
                        activation_function="gelu_new", resid_pdrop=0.0,
                        embd_pdrop=0.0, attn_pdrop=0.0,
                        tie_word_embeddings=False)
    torch.manual_seed(0)
    return config, GPTJForCausalLM(config).eval()


# parallel-residual rotary import stays fast via the NeoX cached-generate test
@pytest.mark.slow
def test_gptj_import_logit_parity_and_generate(workdir):
    """GPT-J: parallel branches sharing one ln_1, bias-free projections,
    biased head, and partial INTERLEAVED rotary — handled entirely at
    import by de-interleaving each head's q/k rows into the half-split
    layout (q·k dot products are permutation-invariant, so no runtime
    rope variant exists); cached greedy == uncached rollout."""
    config, torch_model = _tiny_gptj()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "gptj-tiny")
    assert model.status["code"] == "Imported"
    assert "summation" in str(model.layers_dsl)
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def _tiny_falcon(new_arch=False):
    from transformers import FalconConfig, FalconForCausalLM
    kwargs = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                  num_attention_heads=2, bias=False, alibi=False,
                  attention_dropout=0.0, hidden_dropout=0.0,
                  max_position_embeddings=64, tie_word_embeddings=True)
    if new_arch:
        kwargs.update(new_decoder_architecture=True, num_kv_heads=1)
    else:
        kwargs.update(multi_query=True, parallel_attn=True,
                      new_decoder_architecture=False)
    config = FalconConfig(**kwargs)
    torch.manual_seed(0)
    return config, FalconForCausalLM(config).eval()


@pytest.mark.parametrize("new_arch", [False, pytest.param(True, marks=pytest.mark.slow)])
def test_falcon_import_logit_parity_and_generate(workdir, new_arch):
    """Falcon, both decoder architectures: 7B-style MQA with one shared
    input_layernorm feeding parallel branches, and 40B-style GQA with
    separate ln_attn/ln_mlp (NeoX parallelresidual); fused
    query_key_value de-fused per architecture; tied head."""
    config, torch_model = _tiny_falcon(new_arch=new_arch)
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    tag = "falcon-new" if new_arch else "falcon-7b"
    model = _import_model(workdir, config, torch_model, tag)
    assert model.status["code"] == "Imported"
    dsl_s = str(model.layers_dsl)
    assert ("parallelresidual" in dsl_s) == new_arch
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def test_falcon_variant_rejections():
    from transformers import FalconConfig
    from penroz_tpu.models.dsl import Mapper
    ali = FalconConfig(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                       num_attention_heads=2, alibi=True)
    with pytest.raises(ValueError, match="alibi"):
        Mapper.from_hf_config(ali)
    seqv = FalconConfig(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                        num_attention_heads=2, parallel_attn=False,
                        new_decoder_architecture=False)
    with pytest.raises(ValueError, match="parallel_attn"):
        Mapper.from_hf_config(seqv)


def _tiny_bigcode(multi_query=True):
    from transformers import GPTBigCodeConfig, GPTBigCodeForCausalLM
    config = GPTBigCodeConfig(vocab_size=96, n_positions=64, n_embd=32,
                              n_layer=2, n_head=2, multi_query=multi_query,
                              activation_function="gelu_pytorch_tanh",
                              attn_pdrop=0.0, resid_pdrop=0.0,
                              embd_pdrop=0.0, tie_word_embeddings=True)
    torch.manual_seed(0)
    return config, GPTBigCodeForCausalLM(config).eval()


# multi-query import seam stays fast via the old-arch Falcon test
@pytest.mark.slow
@pytest.mark.parametrize("multi_query", [True, False])
def test_bigcode_import_logit_parity_and_generate(workdir, multi_query):
    """GPT-BigCode (StarCoder): the GPT-2 structure with multi-query
    attention — the MQA-fused c_attn is already our [q; k; v] layout —
    and plain nn.Linear weights (no Conv1D transpose); tied head."""
    config, torch_model = _tiny_bigcode(multi_query=multi_query)
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    tag = f"bigcode-{'mq' if multi_query else 'mh'}"
    model = _import_model(workdir, config, torch_model, tag)
    assert model.status["code"] == "Imported"
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    # 0.05: tight enough to catch a scrambled per-head QKV layout (the
    # multi_query=False mis-interleave measured ~0.075 at this scale)
    # while covering bf16 checkpoint noise (~0.002 when correct)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.05)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def _tiny_phi3(partial_rotary_factor=1.0):
    from transformers import Phi3Config, Phi3ForCausalLM
    config = Phi3Config(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                        num_attention_heads=2, num_key_value_heads=1,
                        intermediate_size=64, max_position_embeddings=64,
                        rope_theta=10000.0, attention_dropout=0.0,
                        partial_rotary_factor=partial_rotary_factor,
                        pad_token_id=0,  # default 32000 >= tiny vocab
                        tie_word_embeddings=False)
    torch.manual_seed(0)
    return config, Phi3ForCausalLM(config).eval()


# slow lane (tier1_budget): phi (shared-norm parallel branches) and neox
# (partial rotary) keep the family's import seams fast
@pytest.mark.slow
@pytest.mark.parametrize("partial_rotary_factor", [pytest.param(1.0, marks=pytest.mark.slow), 0.5])
def test_phi3_import_logit_parity_and_generate(workdir,
                                               partial_rotary_factor):
    """Phi-3: llama block structure with PRE-FUSED projections — qkv_proj
    already in our [q; k; v] layout, gate_up_proj split in half onto
    gate/up; GQA, RMSNorm, silu.  partial_rotary_factor<1 (the Phi-4-mini
    config shape) must rotate only that fraction of each head's dims."""
    config, torch_model = _tiny_phi3(partial_rotary_factor)
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    tag = f"phi3-r{int(partial_rotary_factor * 100)}"
    model = _import_model(workdir, config, torch_model, tag)
    assert model.status["code"] == "Imported"
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.05)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def _tiny_opt(enable_bias=True):
    from transformers import OPTConfig, OPTForCausalLM
    config = OPTConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                       num_attention_heads=2, ffn_dim=64,
                       max_position_embeddings=64, do_layer_norm_before=True,
                       word_embed_proj_dim=32, enable_bias=enable_bias,
                       activation_function="relu", dropout=0.0,
                       attention_dropout=0.0, layerdrop=0.0)
    torch.manual_seed(11)
    return config, OPTForCausalLM(config).eval()


# learned-positional import seam stays fast via the GPT-2 import test
@pytest.mark.slow
def test_opt_import_logit_parity_and_generate(workdir):
    """OPT: model.decoder layout, separate-then-fused biased QKV, ReLU
    MLPs, and the LEARNED position table's +2 row offset folded away at
    import (table[2:] == 0-based lookups under full attention masks) —
    cached greedy must equal the uncached rollout (positions ride the
    cache-length offset)."""
    config, torch_model = _tiny_opt()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "opt-tiny")
    assert model.status["code"] == "Imported"
    # position table lost its 2 offset rows
    assert model.params["layers.0.1.weight"].shape[0] == 64
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def test_opt_unsupported_variants_refused(workdir):
    """OPT-350m's post-norm ordering and embed projections must refuse
    loudly instead of importing wrong logits."""
    from penroz_tpu.models.dsl import Mapper
    from types import SimpleNamespace
    base = dict(model_type="opt", hidden_size=32, num_hidden_layers=1,
                num_attention_heads=2, vocab_size=96, ffn_dim=64,
                max_position_embeddings=64)
    with pytest.raises(ValueError, match="do_layer_norm_before"):
        Mapper.from_hf_config(SimpleNamespace(**base,
                                              do_layer_norm_before=False))
    with pytest.raises(ValueError, match="word_embed_proj_dim"):
        Mapper.from_hf_config(SimpleNamespace(**base,
                                              do_layer_norm_before=True,
                                              word_embed_proj_dim=16))


def _tiny_bloom():
    from transformers import BloomConfig, BloomForCausalLM
    config = BloomConfig(vocab_size=96, hidden_size=32, n_layer=2,
                         n_head=4, hidden_dropout=0.0,
                         attention_dropout=0.0)
    torch.manual_seed(13)
    return config, BloomForCausalLM(config).eval()


# alibi import seam stays fast via the Falcon-RW alibi test
@pytest.mark.slow
def test_bloom_import_logit_parity_and_generate(workdir):
    """BLOOM: no positional embedding at all — ALiBi logit biases carry
    position — plus the embedding LayerNorm and the per-head-interleaved
    fused QKV de-interleaved at import.  Cached greedy must equal the
    uncached rollout (the bias rides the cache positions)."""
    config, torch_model = _tiny_bloom()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "bloom-tiny")
    assert model.status["code"] == "Imported"
    # bare embedding + embedding-LayerNorm — no position table exists
    assert "layers.0.weight" in model.params
    assert model.params["layers.1.weight"].ndim == 1
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def test_bloom_configless_import_refused_with_named_field():
    """Config-less BLOOM state-dict mapping needs n_head for the per-head
    fused-QKV de-interleave: the key sniff (word_embeddings_layernorm)
    dispatches fine without a config, so the refusal must be a descriptive
    ValueError naming the missing field — not the bare AttributeError a
    ``getattr(None, 'n_head')`` would die with later (mirrors the GPT-2
    Conv1D-sniff refusal convention)."""
    import numpy as np
    from penroz_tpu.models.dsl import Mapper
    sd = {"transformer.word_embeddings_layernorm.weight": np.ones(8),
          "transformer.word_embeddings.weight": np.ones((16, 8))}
    with pytest.raises(ValueError, match="n_head"):
        Mapper.map_hf_state_dict_to_custom(sd, 1, config=None)


def test_bloom_post_layernorm_residual_refused():
    from penroz_tpu.models.dsl import Mapper
    from types import SimpleNamespace
    cfg = SimpleNamespace(model_type="bloom", hidden_size=32, n_layer=1,
                          n_head=4, vocab_size=96,
                          apply_residual_connection_post_layernorm=True)
    with pytest.raises(ValueError, match="post_layernorm"):
        Mapper.from_hf_config(cfg)


def test_opt_dropout_knobs_wired_separately():
    """attention_dropout drives the attention probs; `dropout` the
    embedding and both residual streams (opt-125m ships 0.1/0.0 — wiring
    them together silently diverges fine-tuning from HF)."""
    from penroz_tpu.models.dsl import Mapper
    from types import SimpleNamespace
    cfg = SimpleNamespace(model_type="opt", hidden_size=32,
                          num_hidden_layers=1, num_attention_heads=2,
                          vocab_size=96, ffn_dim=64,
                          max_position_embeddings=64,
                          do_layer_norm_before=True, word_embed_proj_dim=32,
                          enable_bias=True, activation_function="relu",
                          dropout=0.1, attention_dropout=0.0)
    layers = Mapper.from_hf_config(cfg)
    blk = layers[2]["residual"]
    attn_entry = blk[0]["sequential"][2]["attention"]
    assert attn_entry["dropout"] == 0.0
    assert blk[0]["sequential"][-1] == {"dropout": {"p": 0.1}}
    assert blk[1]["sequential"][-1] == {"dropout": {"p": 0.1}}
    assert layers[1] == {"dropout": {"p": 0.1}}


def _tiny_mpt(clip_qkv=None):
    from transformers import MptConfig, MptForCausalLM
    config = MptConfig(d_model=32, n_heads=4, n_layers=2, vocab_size=96,
                       expansion_ratio=4,
                       attn_config={"alibi": True, "clip_qkv": clip_qkv,
                                    "attn_pdrop": 0.0})
    torch.manual_seed(17)
    return config, MptForCausalLM(config).eval()


# slow lane (tier1_budget): falcon-rw keeps ALiBi import parity fast
@pytest.mark.slow
@pytest.mark.parametrize("clip_qkv", [None, pytest.param(4.0, marks=pytest.mark.slow)])
def test_mpt_import_logit_parity_and_generate(workdir, clip_qkv):
    """MPT: ALiBi (MPT's slope·(k−T+1) absolute form is softmax-shift-
    equivalent to our slope·(k−q)), weight-only LayerNorms, bias-free
    projections, Wqkv already in our fused layout, optional clip_qkv
    clamp shifting the branch indices."""
    config, torch_model = _tiny_mpt(clip_qkv=clip_qkv)
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    tag = "mpt-clip" if clip_qkv else "mpt-tiny"
    model = _import_model(workdir, config, torch_model, tag)
    assert model.status["code"] == "Imported"
    assert not any(k.endswith(".bias") for k in model.params)  # no_bias
    import json as _json
    assert ('"clamp"' in _json.dumps(model.layers_dsl)) == \
        (clip_qkv is not None)
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def test_mpt_unsupported_variants_refused():
    from penroz_tpu.models.dsl import Mapper
    from types import SimpleNamespace
    base = dict(model_type="mpt", d_model=32, n_layers=1, vocab_size=96)
    with pytest.raises(ValueError, match="alibi"):
        Mapper.from_hf_config(SimpleNamespace(
            **base, n_heads=4, attn_config={"alibi": False}))
    with pytest.raises(ValueError, match="power-of-two"):
        Mapper.from_hf_config(SimpleNamespace(
            **base, n_heads=6, attn_config={"alibi": True}))
    with pytest.raises(ValueError, match="qk_ln"):
        Mapper.from_hf_config(SimpleNamespace(
            **base, n_heads=4, attn_config={"alibi": True, "qk_ln": True}))


def _tiny_qwen2_moe(norm_topk=False):
    from transformers import Qwen2MoeConfig, Qwen2MoeForCausalLM
    config = Qwen2MoeConfig(vocab_size=96, hidden_size=32,
                            num_hidden_layers=2, num_attention_heads=4,
                            num_key_value_heads=2, intermediate_size=64,
                            moe_intermediate_size=48,
                            shared_expert_intermediate_size=80,
                            num_experts=4, num_experts_per_tok=2,
                            norm_topk_prob=norm_topk,
                            decoder_sparse_step=1, mlp_only_layers=[],
                            max_position_embeddings=64,
                            attention_dropout=0.0)
    torch.manual_seed(19)
    return config, Qwen2MoeForCausalLM(config).eval()


# MoE import seam stays fast via the Mixtral test
@pytest.mark.slow
@pytest.mark.parametrize("norm_topk", [False, True])
def test_qwen2_moe_import_logit_parity_and_generate(workdir, norm_topk):
    """Qwen2-MoE: fine-grained routed experts (norm_topk_prob both ways —
    the default False keeps raw softmax mass on the selected experts)
    plus the always-on shared expert behind a sigmoid token gate; qwen2
    qkv biases; cached greedy == uncached rollout."""
    config, torch_model = _tiny_qwen2_moe(norm_topk=norm_topk)
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    tag = f"q2moe-{'n' if norm_topk else 'r'}"
    model = _import_model(workdir, config, torch_model, tag)
    assert model.status["code"] == "Imported"
    assert any("shared_expert_gate" in k for k in model.params)
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


def test_qwen2_moe_sparse_step_refused():
    from penroz_tpu.models.dsl import Mapper
    config, _ = _tiny_qwen2_moe()
    config.decoder_sparse_step = 2
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        Mapper.from_hf_config(config)


def test_gemma2_softcapping_and_query_scale_parity(workdir):
    """Gemma-2's attn/final logit soft-capping and query_pre_attn_scalar
    scaling — set AGGRESSIVELY here (caps ~ logit magnitude, scalar far
    from head_dim) so the nonlinearity and the scale actually bite: a
    build that drops either would fail this parity while passing the
    neutralized `_tiny_gemma2` test."""
    from transformers import Gemma2Config, Gemma2ForCausalLM
    config = Gemma2Config(vocab_size=96, hidden_size=16, num_hidden_layers=2,
                          num_attention_heads=2, num_key_value_heads=1,
                          head_dim=8, intermediate_size=32,
                          max_position_embeddings=64, rope_theta=10000.0,
                          attn_logit_softcapping=2.0,
                          final_logit_softcapping=1.5,
                          query_pre_attn_scalar=64, sliding_window=64,
                          attention_dropout=0.0,
                          hidden_activation="gelu_pytorch_tanh")
    torch.manual_seed(5)
    torch_model = Gemma2ForCausalLM(config).eval()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "gemma2-cap")
    assert model.status["code"] == "Imported"
    import json as _json
    assert '"softcap"' in _json.dumps(model.layers_dsl)
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    # capped logits are small and bounded — compare directly, no centering
    np.testing.assert_allclose(ours, ref_logits, atol=0.02)

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)


# slow lane (tier1_budget): gemma2 parity + softcap/query-scale + sliding
# layers stay fast as the family's architectural twin
@pytest.mark.slow
def test_gemma3_import_logit_parity_and_generate(workdir):
    """Gemma-3: per-head q/k RMS norms (zero-centered weights, +1 at
    import), rope_local_base_freq on sliding layers, LINEAR rope scaling
    on global layers, query_pre_attn_scalar scaling, sandwich norms —
    every field set to a value that would show if dropped."""
    from transformers import Gemma3TextConfig, Gemma3ForCausalLM
    config = Gemma3TextConfig(
        vocab_size=96, hidden_size=16, num_hidden_layers=2,
        num_attention_heads=2, num_key_value_heads=1, head_dim=8,
        intermediate_size=32, max_position_embeddings=64,
        rope_theta=1_000_000.0, rope_local_base_freq=10_000.0,
        rope_scaling={"rope_type": "linear", "factor": 8.0},
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=16, query_pre_attn_scalar=64,
        attention_dropout=0.0, hidden_activation="gelu_pytorch_tanh")
    torch.manual_seed(7)
    torch_model = Gemma3ForCausalLM(config).eval()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "gemma3-tiny")
    assert model.status["code"] == "Imported"
    assert any(k.endswith("q_norm.weight") for k in model.params)
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=32,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6, block=32)


def test_falcon_rw_alibi_import_logit_parity_and_generate(workdir):
    """falcon-rw (RefinedWeb): ALiBi + sequential pre-LN blocks + the
    BLOOM-style per-head-interleaved fused QKV — previously refused,
    supported since ALiBi attention landed.  Other alibi combos keep the
    loud refusal."""
    from transformers import FalconConfig, FalconForCausalLM
    config = FalconConfig(vocab_size=96, hidden_size=32,
                          num_hidden_layers=2, num_attention_heads=4,
                          alibi=True, multi_query=False,
                          parallel_attn=False,
                          new_decoder_architecture=False, bias=True,
                          attention_dropout=0.0, hidden_dropout=0.0)
    torch.manual_seed(21)
    torch_model = FalconForCausalLM(config).eval()
    tokens = np.array([[3, 17, 42, 8, 11]], np.int64)
    with torch.no_grad():
        ref_logits = torch_model(torch.tensor(tokens)).logits.float().numpy()

    model = _import_model(workdir, config, torch_model, "falcon-rw")
    assert model.status["code"] == "Imported"
    import jax.numpy as jnp
    acts, _, _, _ = model.arch.jit_forward(model.params, model.buffers,
                                           jnp.asarray(tokens, jnp.int32),
                                           skip_softmax=True)
    ours = np.asarray(acts[-1], np.float32)
    ref_c = ref_logits - ref_logits.mean(-1, keepdims=True)
    ours_c = ours - ours.mean(-1, keepdims=True)
    np.testing.assert_allclose(ours_c, ref_c, atol=0.15)
    assert (ours.argmax(-1) == ref_logits.argmax(-1)).mean() >= 0.8

    toks = model.generate_tokens([[1, 2, 3]], block_size=16,
                                 max_new_tokens=6, temperature=0.0)
    assert toks == _greedy_rollout(model, [1, 2, 3], 6)

    # non-rw alibi combos stay refused
    from penroz_tpu.models.dsl import Mapper
    bad = FalconConfig(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                       num_attention_heads=4, alibi=True, multi_query=True)
    with pytest.raises(ValueError, match="falcon-rw"):
        Mapper.from_hf_config(bad)
