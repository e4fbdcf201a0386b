"""Weight-only int8 quantization (models/quant.py).

Covers the quantize/dequant identities, end-to-end model closeness in
fp32, engine serving with --quantization int8 (dense and MoE), and
tp-sharded parity of the quantized pytree.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from production_stack_tpu.models import ModelConfig, llama, quant
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
from production_stack_tpu.parallel.sharding import shard_params

CFG = ModelConfig(name="t", vocab_size=128, hidden_size=64,
                  intermediate_size=128, num_layers=2, num_heads=8,
                  num_kv_heads=4, max_position_embeddings=256,
                  dtype=jnp.float32)


def test_quantize_tensor_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 64, 32)) * 0.3
    q = quant.quantize_tensor(w)
    assert q["w8"].dtype == jnp.int8
    assert q["scale"].shape == (3, 32)
    deq = q["w8"].astype(jnp.float32) * q["scale"][:, None, :]
    # symmetric per-channel: error <= scale/2 per element
    err = np.abs(np.asarray(deq - w))
    bound = np.asarray(q["scale"][:, None, :]) / 2 + 1e-7
    assert (err <= bound).all()


def test_dequant_matmul_matches_dequantized_weight():
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 32)) * 0.2
    q = quant.quantize_tensor(w)
    got = quant.dequant_matmul(x, q)
    want = x @ (q["w8"].astype(jnp.float32) * q["scale"][None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_quantized_forward_close_to_fp32():
    """Logits drift from 8-bit weights stays small; greedy argmax on a
    random tiny model agrees for most positions."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              CFG.vocab_size)
    ref = np.asarray(llama.forward_train(params, CFG, toks))
    qp = quant.quantize_params(params)
    got = np.asarray(llama.forward_train(qp, CFG, toks))
    assert np.isfinite(got).all()
    # int8 weight error is ~0.4% per channel; logits stay close
    denom = np.maximum(np.abs(ref).max(), 1.0)
    assert np.abs(got - ref).max() / denom < 0.05
    agree = (got.argmax(-1) == ref.argmax(-1)).mean()
    assert agree > 0.9, f"greedy agreement {agree}"


def test_quantized_tied_embeddings_lm_head():
    cfg = ModelConfig(name="t-tied", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=1, num_heads=4,
                      num_kv_heads=2, max_position_embeddings=128,
                      tie_word_embeddings=True, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 0,
                              cfg.vocab_size)
    ref = np.asarray(llama.forward_train(params, cfg, toks))
    got = np.asarray(llama.forward_train(quant.quantize_params(params),
                                         cfg, toks))
    denom = np.maximum(np.abs(ref).max(), 1.0)
    assert np.abs(got - ref).max() / denom < 0.05


def test_quantized_moe_forward_runs():
    cfg = ModelConfig(name="t-moe", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=8,
                      num_kv_heads=4, max_position_embeddings=256,
                      num_experts=4, num_experts_per_tok=2,
                      dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qp = quant.quantize_params(params)
    assert not quant.is_quantized(qp["layers"]["router"])  # router stays fp
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 100), 0,
                              cfg.vocab_size)
    ref = np.asarray(llama.forward_train(params, cfg, toks))
    got = np.asarray(llama.forward_train(qp, cfg, toks))
    assert np.isfinite(got).all()
    denom = np.maximum(np.abs(ref).max(), 1.0)
    assert np.abs(got - ref).max() / denom < 0.08


def test_quantized_tp_sharded_matches_single_device():
    mesh = build_mesh(MeshConfig(dp=1, tp=8))
    params = quant.quantize_params(llama.init_params(CFG,
                                                     jax.random.PRNGKey(0)))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0,
                              CFG.vocab_size)
    expected = llama.forward_train(params, CFG, toks)
    sharded = shard_params(mesh, params)
    got = jax.jit(lambda p, t: llama.forward_train(p, CFG, t))(sharded, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)


def test_engine_serves_quantized():
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    opts = SamplingOptions(temperature=0.0, max_tokens=8, ignore_eos=True)
    out = LLMEngine(EngineConfig(
        model="debug-tiny", max_model_len=128, max_num_seqs=2,
        prefill_chunk=32, prefill_buckets=(32,),
        quantization="int8")).generate("quantized probe", opts)
    assert isinstance(out, str) and len(out) > 0

    with pytest.raises(ValueError, match="quantization"):
        EngineConfig(model="debug-tiny", quantization="fp8")
