"""Parallelism tests on the 8-device virtual CPU mesh.

Exercises exactly the sharding/collective paths a v5e-8 slice would
serve on: tp param sharding, the tp serving engine against the
unsharded one, and the dp cliff.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from production_stack_tpu.models import ModelConfig, llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
from production_stack_tpu.parallel.sharding import shard_params


CFG = ModelConfig(name="t", vocab_size=128, hidden_size=64,
                  intermediate_size=128, num_layers=2, num_heads=8,
                  num_kv_heads=4, max_position_embeddings=256,
                  dtype=jnp.float32)


def test_mesh_factoring():
    assert MeshConfig.for_devices(8) == MeshConfig(dp=4, tp=2)
    assert MeshConfig.for_devices(8, tp=4) == MeshConfig(dp=2, tp=4)
    assert MeshConfig.for_devices(1) == MeshConfig(dp=1, tp=1)
    with pytest.raises(ValueError, match="does not divide"):
        MeshConfig.for_devices(8, tp=3)
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(dp=3, tp=1))
    assert build_mesh(MeshConfig(dp=2, ep=2, tp=2)).shape == {
        "dp": 2, "ep": 2, "tp": 2}


def test_tp_sharded_forward_matches_single_device():
    mesh = build_mesh(MeshConfig(dp=1, tp=8))
    key = jax.random.PRNGKey(0)
    params = llama.init_params(CFG, key)
    toks = jax.random.randint(key, (2, 16), 0, CFG.vocab_size)

    expected = llama.forward_train(params, CFG, toks)
    sharded = shard_params(mesh, params)
    got = jax.jit(lambda p, t: llama.forward_train(p, CFG, t))(sharded, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)


def test_tp_serving_engine_matches_unsharded():
    """Greedy generation through the engine must be identical with and
    without a tp=2 serving mesh (debug-tiny has 2 KV heads)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    opts = SamplingOptions(temperature=0.0, max_tokens=8)
    base = EngineConfig(model="debug-tiny", max_model_len=128, max_num_seqs=2,
                        prefill_chunk=32, prefill_buckets=(16, 32))
    plain = LLMEngine(base).generate("tensor parallel probe", opts)

    tp_cfg = EngineConfig(model="debug-tiny", max_model_len=128,
                          max_num_seqs=2, prefill_chunk=32,
                          prefill_buckets=(16, 32), tensor_parallel_size=2)
    sharded = LLMEngine(tp_cfg).generate("tensor parallel probe", opts)
    assert plain == sharded

    with pytest.raises(ValueError, match="num_kv_heads"):
        LLMEngine(EngineConfig(model="debug-tiny", max_model_len=128,
                               max_num_seqs=2, prefill_chunk=32,
                               prefill_buckets=(16, 32),
                               tensor_parallel_size=8))


def test_dp_mesh_gather_cliff_is_explicit():
    """A dp>1 serving mesh forfeits the paged pallas kernel (block axis
    sharded — ops/pallas_paged.mesh_tp_only). When flash would actually
    be used, constructing the runner must REFUSE unless the config
    acknowledges the ~3x-KV-traffic gather fallback; tp-only meshes are
    untouched. (flash_enabled() is false on the CPU test backend, so
    the cliff is forced visible here via the explicit override.)"""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.ops import pallas_paged

    import jax
    mesh = build_mesh(MeshConfig(dp=2, tp=2), jax.devices()[:4])
    cfg = dict(model="debug-tiny", max_model_len=128, max_num_seqs=4,
               prefill_chunk=32, prefill_buckets=(32,))
    pallas_paged.set_flash_enabled(True)
    try:
        with pytest.raises(ValueError, match="gathered-view"):
            LLMEngine(EngineConfig(**cfg), mesh=mesh)
        # acknowledged: constructs (with a logged warning)
        eng = LLMEngine(EngineConfig(dp_gather_attention_ok=True, **cfg),
                        mesh=mesh)
        assert eng is not None
        # tp-only meshes never trip the guard
        tp_mesh = build_mesh(MeshConfig(dp=1, tp=2),
                             jax.devices()[:2])
        LLMEngine(EngineConfig(**cfg), mesh=tp_mesh)
    finally:
        pallas_paged.set_flash_enabled(None)
