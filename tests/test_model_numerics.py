"""Numerics parity: our JAX Llama vs HuggingFace transformers LlamaForCausalLM.

Mirrors the role of the reference's unit tier (SURVEY.md §4.1) but for the
in-repo engine the reference doesn't have: proves the TPU-native model is
the same function as the canonical implementation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from production_stack_tpu.models import ModelConfig, llama, make_slot_cache
from production_stack_tpu.models.hf_loader import params_from_state_dict

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def tiny_pair():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = transformers.LlamaForCausalLM(hf_cfg).eval().to(torch.float32)
    cfg = ModelConfig(
        name="tiny-hf", vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=3, num_heads=4, num_kv_heads=2, max_position_embeddings=128,
        dtype=jnp.float32,
    )
    params = params_from_state_dict(cfg, hf_model.state_dict())
    return cfg, params, hf_model


def test_forward_train_matches_hf(tiny_pair):
    cfg, params, hf_model = tiny_pair
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 24))
    with torch.no_grad():
        ref = hf_model(torch.tensor(toks)).logits.numpy()
    ours = np.asarray(llama.forward_train(params, cfg, jnp.asarray(toks)))
    np.testing.assert_allclose(ours, ref, atol=1e-2, rtol=0)


def test_incremental_decode_matches_hf(tiny_pair):
    cfg, params, hf_model = tiny_pair
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, size=(1, 10))

    cache, tables = make_slot_cache(cfg.num_layers, 1, 64, cfg.num_kv_heads, cfg.head_dim_,
                       dtype=jnp.float32)
    pos = jnp.arange(10)[None, :]
    logits, cache, _ = llama.forward(params, cfg, jnp.asarray(prompt), pos,
                                     cache)

    seq = list(prompt[0])
    for step in range(5):
        nxt = int(np.argmax(np.asarray(logits)[0, -1]))
        seq.append(nxt)
        with torch.no_grad():
            ref = hf_model(torch.tensor([seq])).logits[0, -1].numpy()
        logits, cache, _ = llama.forward(
            params, cfg, jnp.asarray([[nxt]]),
            jnp.asarray([[len(seq) - 1]]), cache)
        np.testing.assert_allclose(
            np.asarray(logits)[0, 0], ref, atol=1e-2, rtol=0)


def test_gqa_grouping_consistent():
    """GQA einsum path equals explicit KV-head repetition."""
    cfg = ModelConfig(name="t", vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_layers=1, num_heads=4,
                      num_kv_heads=1, dtype=jnp.float32,
                      max_position_embeddings=64)
    cfg_mha = ModelConfig(name="t", vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_layers=1, num_heads=4,
                          num_kv_heads=4, dtype=jnp.float32,
                          max_position_embeddings=64)
    key = jax.random.PRNGKey(0)
    params = llama.init_params(cfg, key)
    # replicate kv weights across the 4 heads -> MHA equivalent
    params_mha = jax.tree.map(lambda x: x, params)
    params_mha["layers"] = dict(params["layers"])
    params_mha["layers"]["k"] = jnp.tile(params["layers"]["k"], (1, 1, 4))
    params_mha["layers"]["v"] = jnp.tile(params["layers"]["v"], (1, 1, 4))
    toks = jax.random.randint(key, (2, 8), 0, 64)
    out_gqa = llama.forward_train(params, cfg, toks)
    out_mha = llama.forward_train(params_mha, cfg_mha, toks)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha),
                               atol=1e-4, rtol=1e-4)


@pytest.fixture(scope="module")
def tiny_qwen2_pair():
    hf_cfg = transformers.Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(1)
    hf_model = transformers.Qwen2ForCausalLM(hf_cfg).eval().to(torch.float32)
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), name="tiny-qwen2",
                                     dtype=jnp.float32)
    assert cfg.attention_bias, "Qwen2 config must enable qkv biases"
    params = params_from_state_dict(cfg, hf_model.state_dict())
    return cfg, params, hf_model


def test_qwen2_forward_matches_hf(tiny_qwen2_pair):
    """Qwen2 family: q/k/v projection biases (SURVEY §2: the reference
    serves any vLLM-supported family; bias-attention models were
    previously unrepresentable here)."""
    cfg, params, hf_model = tiny_qwen2_pair
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 20))
    with torch.no_grad():
        ref = hf_model(torch.tensor(toks)).logits.numpy()
    ours = np.asarray(llama.forward_train(params, cfg, jnp.asarray(toks)))
    np.testing.assert_allclose(ours, ref, atol=1e-2, rtol=0)


def test_qwen2_incremental_decode_matches_full(tiny_qwen2_pair):
    cfg, params, hf_model = tiny_qwen2_pair
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, size=(1, 16))
    full = np.asarray(llama.forward_train(params, cfg, jnp.asarray(toks)))
    cache, tables = make_slot_cache(cfg.num_layers, 1, 32, cfg.num_kv_heads,
                       cfg.head_dim_, dtype=jnp.float32)
    outs = []
    for t in range(toks.shape[1]):
        logits, cache, _ = llama.forward(
            params, cfg, jnp.asarray(toks[:, t:t + 1]),
            jnp.asarray([[t]]), cache)
        outs.append(np.asarray(logits)[:, 0])
    np.testing.assert_allclose(np.stack(outs, axis=1), full,
                               atol=1e-3, rtol=0)


@pytest.fixture(scope="module")
def tiny_gemma_pair():
    hf_cfg = transformers.GemmaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, max_position_embeddings=128, rms_norm_eps=1e-5,
        rope_theta=10000.0, hidden_activation="gelu_pytorch_tanh",
        attn_implementation="eager",
    )
    torch.manual_seed(2)
    hf_model = transformers.GemmaForCausalLM(hf_cfg).eval().to(torch.float32)
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), name="tiny-gemma",
                                     dtype=jnp.float32)
    assert cfg.rms_norm_offset and cfg.embed_scale
    assert cfg.tie_word_embeddings
    assert cfg.activation == "gelu_tanh"
    params = params_from_state_dict(cfg, hf_model.state_dict())
    return cfg, params, hf_model


def test_gemma_forward_matches_hf(tiny_gemma_pair):
    """Gemma family: GeGLU MLP, sqrt(hidden) embedding scale, RMSNorm
    with unit offset, tied embeddings, MQA (1 kv head), head_dim !=
    hidden/heads."""
    cfg, params, hf_model = tiny_gemma_pair
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 20))
    with torch.no_grad():
        ref = hf_model(torch.tensor(toks)).logits.numpy()
    ours = np.asarray(llama.forward_train(params, cfg, jnp.asarray(toks)))
    np.testing.assert_allclose(ours, ref, atol=1e-2, rtol=0)


@pytest.fixture(scope="module")
def tiny_mixtral_pair():
    hf_cfg = transformers.MixtralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        num_local_experts=4, num_experts_per_tok=2,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(3)
    hf_model = transformers.MixtralForCausalLM(hf_cfg).eval().to(
        torch.float32)
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), name="tiny-mixtral",
                                     dtype=jnp.float32)
    assert cfg.num_experts == 4 and cfg.num_experts_per_tok == 2
    params = params_from_state_dict(cfg, hf_model.state_dict())
    return cfg, params, hf_model


def test_mixtral_forward_matches_hf(tiny_mixtral_pair):
    """Mixtral family: top-2-of-E routed MLP (fp32 softmax over all
    experts, renormalized top-k). Token counts here stay on the exact
    all-expert path, so parity with HF (which never drops) must be
    exact up to float tolerance."""
    cfg, params, hf_model = tiny_mixtral_pair
    rng = np.random.default_rng(4)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 20))
    with torch.no_grad():
        ref = hf_model(torch.tensor(toks)).logits.numpy()
    ours = np.asarray(llama.forward_train(params, cfg, jnp.asarray(toks)))
    np.testing.assert_allclose(ours, ref, atol=1e-2, rtol=0)


def test_mixtral_incremental_decode_matches_full(tiny_mixtral_pair):
    cfg, params, hf_model = tiny_mixtral_pair
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg.vocab_size, size=(1, 12))
    full = np.asarray(llama.forward_train(params, cfg, jnp.asarray(toks)))
    cache, tables = make_slot_cache(cfg.num_layers, 1, 32, cfg.num_kv_heads,
                       cfg.head_dim_, dtype=jnp.float32)
    outs = []
    for t in range(toks.shape[1]):
        logits, cache, _ = llama.forward(
            params, cfg, jnp.asarray(toks[:, t:t + 1]),
            jnp.asarray([[t]]), cache)
        outs.append(np.asarray(logits)[:, 0])
    np.testing.assert_allclose(np.stack(outs, axis=1), full,
                               atol=1e-3, rtol=0)


@pytest.fixture(scope="module")
def tiny_qwen2_moe_pair():
    hf_cfg = transformers.Qwen2MoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=48, shared_expert_intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=False,
        decoder_sparse_step=1, mlp_only_layers=[],
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(4)
    hf_model = transformers.Qwen2MoeForCausalLM(hf_cfg).eval().to(
        torch.float32)
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(),
                                     name="tiny-qwen2-moe",
                                     dtype=jnp.float32)
    assert cfg.num_experts == 4 and not cfg.norm_topk_prob
    assert cfg.moe_intermediate_size == 48
    assert cfg.shared_expert_size == 96 and cfg.attention_bias
    params = params_from_state_dict(cfg, hf_model.state_dict())
    return cfg, params, hf_model


def test_qwen2_moe_forward_matches_hf(tiny_qwen2_moe_pair):
    """Qwen2-MoE family: raw (non-renormalized) top-k routing weights,
    narrow per-expert FFN, and a sigmoid-gated always-on shared
    expert."""
    cfg, params, hf_model = tiny_qwen2_moe_pair
    rng = np.random.default_rng(6)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 20))
    with torch.no_grad():
        ref = hf_model(torch.tensor(toks)).logits.numpy()
    ours = np.asarray(llama.forward_train(params, cfg, jnp.asarray(toks)))
    np.testing.assert_allclose(ours, ref, atol=1e-2, rtol=0)


def test_qwen2_moe_dense_interleaving_rejected():
    with pytest.raises(ValueError, match="sparse"):
        ModelConfig.from_hf_config({
            "model_type": "qwen2_moe", "vocab_size": 64,
            "hidden_size": 32, "intermediate_size": 64,
            "num_hidden_layers": 4, "num_attention_heads": 2,
            "num_experts": 4, "decoder_sparse_step": 2,
        })


def test_qwen2_moe_incremental_decode_matches_full(tiny_qwen2_moe_pair):
    """The exact T==1 decode path (shared expert + raw top-k weights)
    under the KV-cache forward — what production serving runs."""
    cfg, params, hf_model = tiny_qwen2_moe_pair
    rng = np.random.default_rng(7)
    toks = rng.integers(0, cfg.vocab_size, size=(1, 12))
    full = np.asarray(llama.forward_train(params, cfg, jnp.asarray(toks)))
    cache, tables = make_slot_cache(cfg.num_layers, 1, 32, cfg.num_kv_heads,
                       cfg.head_dim_, dtype=jnp.float32)
    outs = []
    for t in range(toks.shape[1]):
        logits, cache, _ = llama.forward(
            params, cfg, jnp.asarray(toks[:, t:t + 1]),
            jnp.asarray([[t]]), cache)
        outs.append(np.asarray(logits)[:, 0])
    np.testing.assert_allclose(np.stack(outs, axis=1), full,
                               atol=1e-3, rtol=0)
