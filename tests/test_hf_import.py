"""HF Llama checkpoint import: converted params must reproduce the live
HuggingFace model's logits (which pins the RoPE convention permutation, all
transposes, GQA head mapping, norm placement, and the lm head)."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402

from dmlcloud_tpu.models.hf import llama_params_from_hf, transformer_config_from_hf  # noqa: E402
from dmlcloud_tpu.models.transformer import DecoderLM  # noqa: E402


def _tiny_hf(tie=False, kv_heads=2):
    hf_cfg = transformers.LlamaConfig(
        vocab_size=61,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=kv_heads,
        max_position_embeddings=64,
        rope_theta=10000.0,
        tie_word_embeddings=tie,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    return hf_cfg, model


@pytest.mark.parametrize("tie,kv_heads", [(False, 2), (False, 4), (True, 2)])
def test_logits_match_hf(tie, kv_heads):
    hf_cfg, hf_model = _tiny_hf(tie=tie, kv_heads=kv_heads)
    cfg = transformer_config_from_hf(hf_cfg, dtype=jnp.float32)
    assert cfg.tie_embeddings == tie
    params = llama_params_from_hf(hf_model.state_dict(), cfg)

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, hf_cfg.vocab_size, size=(2, 11))

    with torch.no_grad():
        want = hf_model(torch.from_numpy(tokens)).logits.numpy()
    got = DecoderLM(cfg).apply({"params": params}, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)


def test_generate_from_hf_weights():
    """Converted weights drive the KV-cache decode loop: greedy generation
    equals HF's own greedy generation."""
    hf_cfg, hf_model = _tiny_hf()
    cfg = transformer_config_from_hf(hf_cfg, dtype=jnp.float32)
    params = llama_params_from_hf(hf_model.state_dict(), cfg)

    from dmlcloud_tpu.models.generate import generate

    rng = np.random.RandomState(1)
    prompt = rng.randint(0, hf_cfg.vocab_size, size=(1, 7))
    with torch.no_grad():
        want = hf_model.generate(
            torch.from_numpy(prompt), max_new_tokens=8, do_sample=False,
            pad_token_id=0, eos_token_id=None,
        ).numpy()[:, 7:]
    got = generate(DecoderLM(cfg), params, jnp.asarray(prompt), max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_missing_weight_raises():
    hf_cfg, hf_model = _tiny_hf()
    cfg = transformer_config_from_hf(hf_cfg)
    sd = dict(hf_model.state_dict())
    sd.pop("model.layers.0.self_attn.q_proj.weight")
    with pytest.raises(KeyError, match="q_proj"):
        llama_params_from_hf(sd, cfg)


def test_unconverted_weight_raises():
    hf_cfg, hf_model = _tiny_hf()
    cfg = transformer_config_from_hf(hf_cfg)
    sd = dict(hf_model.state_dict())
    sd["model.layers.0.unexpected.weight"] = torch.zeros(2)
    with pytest.raises(ValueError, match="unconverted"):
        llama_params_from_hf(sd, cfg)


def test_mistral_config_and_logits():
    """Mistral = same architecture + sliding_window; converted weights must
    match the HF Mistral forward (whose eager attention applies the window)."""
    hf_cfg = transformers.MistralConfig(
        vocab_size=61,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        sliding_window=6,
        attn_implementation="eager",
    )
    torch.manual_seed(2)
    hf_model = transformers.MistralForCausalLM(hf_cfg).eval()
    cfg = transformer_config_from_hf(hf_cfg, dtype=jnp.float32)
    assert cfg.sliding_window == 6
    params = llama_params_from_hf(hf_model.state_dict(), cfg)

    tokens = np.random.RandomState(3).randint(0, 61, size=(2, 13))
    with torch.no_grad():
        want = hf_model(torch.from_numpy(tokens)).logits.numpy()
    got = DecoderLM(cfg).apply({"params": params}, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)


def test_export_round_trips_through_hf():
    """params -> HF state dict -> load into a live HF model -> logits match;
    and importing the exported dict reproduces the original params."""
    from dmlcloud_tpu.models.hf import hf_state_dict_from_params

    hf_cfg, hf_model = _tiny_hf()
    cfg = transformer_config_from_hf(hf_cfg, dtype=jnp.float32)
    params = llama_params_from_hf(hf_model.state_dict(), cfg)

    sd = hf_state_dict_from_params(params, cfg)
    fresh = transformers.LlamaForCausalLM(hf_cfg).eval()
    fresh.load_state_dict({k: torch.from_numpy(v) for k, v in sd.items()})

    tokens = np.random.RandomState(4).randint(0, 61, size=(1, 10))
    with torch.no_grad():
        want = hf_model(torch.from_numpy(tokens)).logits.numpy()
        got = fresh(torch.from_numpy(tokens)).logits.numpy()
    np.testing.assert_allclose(got, want, atol=1e-5)

    # exact param round trip (same treedef => leaves align positionally)
    back = llama_params_from_hf(sd, cfg)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(back)
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decoupled_head_dim():
    """Mistral-Nemo-style configs set head_dim independently of
    hidden_size // num_heads."""
    hf_cfg = transformers.MistralConfig(
        vocab_size=61, hidden_size=40, intermediate_size=64, num_hidden_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=64, sliding_window=None, attn_implementation="eager",
    )
    torch.manual_seed(3)
    hf_model = transformers.MistralForCausalLM(hf_cfg).eval()
    cfg = transformer_config_from_hf(hf_cfg, dtype=jnp.float32)
    assert cfg.head_dim == 16 and cfg.hidden_dim == 40
    params = llama_params_from_hf(hf_model.state_dict(), cfg)
    tokens = np.random.RandomState(5).randint(0, 61, size=(1, 9))
    with torch.no_grad():
        want = hf_model(torch.from_numpy(tokens)).logits.numpy()
    got = DecoderLM(cfg).apply({"params": params}, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)


def test_tied_export_loads_strict():
    from dmlcloud_tpu.models.hf import hf_state_dict_from_params

    hf_cfg, hf_model = _tiny_hf(tie=True)
    cfg = transformer_config_from_hf(hf_cfg, dtype=jnp.float32)
    params = llama_params_from_hf(hf_model.state_dict(), cfg)
    sd = hf_state_dict_from_params(params, cfg)
    fresh = transformers.LlamaForCausalLM(hf_cfg).eval()
    fresh.load_state_dict({k: torch.from_numpy(v) for k, v in sd.items()})  # strict
    tokens = np.random.RandomState(6).randint(0, 61, size=(1, 8))
    with torch.no_grad():
        want = hf_model(torch.from_numpy(tokens)).logits.numpy()
        got = fresh(torch.from_numpy(tokens)).logits.numpy()
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_beam_search_matches_hf():
    """Same weights, same K: our jitted beam search must produce HF
    generate(num_beams=K)'s tokens."""
    from dmlcloud_tpu.models.generate import beam_search

    hf_cfg, hf_model = _tiny_hf()
    cfg = transformer_config_from_hf(hf_cfg, dtype=jnp.float32)
    params = llama_params_from_hf(hf_model.state_dict(), cfg)
    prompt = np.random.RandomState(7).randint(0, 61, size=(2, 6))

    toks, _ = beam_search(DecoderLM(cfg), params, jnp.asarray(prompt), max_new_tokens=8, num_beams=4)
    with torch.no_grad():
        want = hf_model.generate(
            torch.from_numpy(prompt), max_new_tokens=8, num_beams=4, do_sample=False,
            pad_token_id=0, eos_token_id=None, length_penalty=1.0, early_stopping=False,
        ).numpy()[:, 6:]
    np.testing.assert_array_equal(np.asarray(toks), want)


@pytest.mark.parametrize(
    "rope_scaling",
    [
        {"rope_type": "linear", "factor": 2.0},
        {
            "rope_type": "llama3",
            "factor": 4.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 32,
        },
        {"rope_type": "yarn", "factor": 4.0, "original_max_position_embeddings": 16, "beta_fast": 4.0, "beta_slow": 1.0},
        {"rope_type": "yarn", "factor": 8.0, "original_max_position_embeddings": 16, "beta_fast": 2.0, "beta_slow": 1.0,
         "attention_factor": 1.3},
    ],
    ids=["linear", "llama3", "yarn", "yarn-with-attention-factor"],
)
def test_rope_scaled_logits_match_hf(rope_scaling):
    """Llama-3 / linear / YaRN rope scaling must reproduce HF's scaled rotary
    geometry, not silently fall back to plain RoPE."""
    hf_cfg = transformers.LlamaConfig(
        vocab_size=61, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        rope_scaling=dict(rope_scaling), attn_implementation="eager",
    )
    torch.manual_seed(4)
    hf_model = transformers.LlamaForCausalLM(hf_cfg).eval()
    cfg = transformer_config_from_hf(hf_cfg, dtype=jnp.float32)
    assert cfg.rope_scaling is not None
    params = llama_params_from_hf(hf_model.state_dict(), cfg)
    tokens = np.random.RandomState(8).randint(0, 61, size=(2, 40))  # long enough to scale
    with torch.no_grad():
        want = hf_model(torch.from_numpy(tokens)).logits.numpy()
    got = DecoderLM(cfg).apply({"params": params}, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-4, rtol=3e-4)


def test_unsupported_rope_scaling_raises():
    class FakeCfg:
        vocab_size, num_hidden_layers, num_attention_heads = 61, 1, 4
        num_key_value_heads, hidden_size, intermediate_size = 2, 32, 64
        max_position_embeddings, rope_theta = 64, 10000.0
        tie_word_embeddings, sliding_window = False, None
        head_dim = 8
        rope_scaling = {"rope_type": "longrope", "factor": 2.0}

    with pytest.raises(ValueError, match="longrope"):
        transformer_config_from_hf(FakeCfg())
    FakeCfg.rope_scaling = {"rope_type": "yarn", "factor": 2.0}  # YaRN without the context it was stretched from
    with pytest.raises(ValueError, match="original_max_position_embeddings"):
        transformer_config_from_hf(FakeCfg())


def test_rope_scaling_without_type_key_raises():
    from dmlcloud_tpu.models.hf import _rope_scaling_from_hf

    with pytest.raises(ValueError, match="rope_type"):
        _rope_scaling_from_hf({"factor": 8.0})
    assert _rope_scaling_from_hf(None) is None
    assert _rope_scaling_from_hf({"rope_type": "default"}) is None
