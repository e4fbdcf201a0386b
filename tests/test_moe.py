"""MoE op + Mixtral-family tests (CPU, 8-device virtual mesh).

Covers the routing/dispatch math in ops/moe.py against an independent
per-token reference, expert-parallel sharded parity, and EP serving
through the engine. HF numerics parity for Mixtral lives in
tests/test_model_numerics.py next to the other families.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from production_stack_tpu.models import ModelConfig, llama
from production_stack_tpu.ops import moe
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
from production_stack_tpu.parallel.sharding import shard_params

MOE_CFG = ModelConfig(name="t-moe", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=8,
                      num_kv_heads=4, max_position_embeddings=256,
                      num_experts=4, num_experts_per_tok=2,
                      dtype=jnp.float32)


def _rand_moe(key, N=96, h=32, E=4, i=64):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (N, h), jnp.float32)
    rw = jax.random.normal(ks[1], (h, E), jnp.float32) * 0.2
    g = jax.random.normal(ks[2], (E, h, i), jnp.float32) * 0.1
    u = jax.random.normal(ks[3], (E, h, i), jnp.float32) * 0.1
    d = jax.random.normal(ks[4], (E, i, h), jnp.float32) * 0.1
    return x, rw, g, u, d


def _reference_moe(x, rw, g, u, d, k, capacity=None, valid=None):
    """Per-token numpy loop: softmax-all, top-k, renormalize, run the
    selected experts one by one. Independent of ops/moe.py's vectorized
    dispatch. capacity simulates per-expert slots filled in token-major
    assignment order (the dispatch path's ranking); valid marks padding
    rows that contribute nothing and consume no capacity."""
    x, rw, g, u, d = map(np.asarray, (x, rw, g, u, d))
    N = x.shape[0]
    E = g.shape[0]
    out = np.zeros_like(x)
    counts = np.zeros(E, np.int64)
    for t in range(N):
        if valid is not None and not valid[t]:
            continue
        logits = x[t] @ rw
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p)[:k]
        w = p[top] / p[top].sum()
        for wi, e in zip(w, top):
            if capacity is not None:
                if counts[e] >= capacity:
                    continue          # dropped: rides the residual
                counts[e] += 1
            hidden = (x[t] @ g[e])
            hidden = hidden / (1 + np.exp(-hidden)) * (x[t] @ u[e])
            out[t] += wi * (hidden @ d[e])
    return out


def test_route_weights_normalized():
    x, rw, *_ = _rand_moe(jax.random.PRNGKey(0))
    w, idx = moe.route(x, rw, top_k=2)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    assert np.asarray(idx).min() >= 0 and np.asarray(idx).max() < 4
    # top-k indices are distinct per token
    assert (np.asarray(idx)[:, 0] != np.asarray(idx)[:, 1]).all()


def test_exact_path_matches_reference():
    x, rw, g, u, d = _rand_moe(jax.random.PRNGKey(1))
    got = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1000)
    np.testing.assert_allclose(np.asarray(got),
                               _reference_moe(x, rw, g, u, d, 2),
                               atol=1e-4, rtol=1e-4)


def test_dispatch_path_matches_reference():
    x, rw, g, u, d = _rand_moe(jax.random.PRNGKey(2))
    # capacity_factor 1.6 -> capacity < N (dispatch branch) but above the
    # realized max expert load for this seed, so no token is dropped
    got = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1,
                      capacity_factor=1.6)
    cap = moe.capacity_for(x.shape[0], 4, 2, 1.6)
    assert cap < x.shape[0], "capacity must not force the exact branch"
    np.testing.assert_allclose(np.asarray(got),
                               _reference_moe(x, rw, g, u, d, 2),
                               atol=1e-4, rtol=1e-4)


def test_dispatch_with_drops_matches_reference():
    """Over-capacity assignments drop in token-major rank order — the
    numpy reference simulates the same fill and must agree exactly."""
    x, rw, g, u, d = _rand_moe(jax.random.PRNGKey(3))
    got = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1,
                      capacity_factor=0.5)
    cap = moe.capacity_for(x.shape[0], 4, 2, 0.5)
    ref = _reference_moe(x, rw, g, u, d, 2, capacity=cap)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), ref, atol=1e-4, rtol=1e-4)


def test_padding_never_routes_or_steals_capacity():
    """Padding rows (valid=False) contribute zero output AND consume no
    expert capacity — real tokens see the same result as if the padding
    did not exist."""
    x, rw, g, u, d = _rand_moe(jax.random.PRNGKey(6))
    N = x.shape[0]
    valid = np.zeros(N, bool)
    valid[: N // 3] = True          # 2/3 of the batch is padding
    cap = moe.capacity_for(N, 4, 2, 0.5)
    got = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1,
                      capacity_factor=0.5, valid=jnp.asarray(valid))
    ref = _reference_moe(x, rw, g, u, d, 2, capacity=cap, valid=valid)
    np.testing.assert_allclose(np.asarray(got), ref, atol=1e-4, rtol=1e-4)
    assert (np.asarray(got)[~valid] == 0).all()
    # exact path masks padding too
    got_exact = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1000,
                            valid=jnp.asarray(valid))
    assert (np.asarray(got_exact)[~valid] == 0).all()


def test_exact_flag_overrides_capacity():
    """exact=True (the decode path) never drops, whatever N/capacity."""
    x, rw, g, u, d = _rand_moe(jax.random.PRNGKey(7))
    got = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1,
                      capacity_factor=0.5, exact=True)
    np.testing.assert_allclose(np.asarray(got),
                               _reference_moe(x, rw, g, u, d, 2),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("rows,bucket", [(1, 256), (1, 128), (2, 256)])
def test_small_prefill_reckons_capacity_on_the_full_batch(rows, bucket):
    """Qwen1.5-MoE's routing shape (60 experts, top-4, factor 2) with a
    router rigged so that EVERY token's first choice is expert 0: a
    one-row chunk sends ``bucket`` tokens there, more than
    capacity_for(bucket, ...) (40 at 256) holds. Reckoned on the full
    batch's 16 x bucket tokens (what runner._prefill_impl passes) and
    clamped to N, the capacity covers every token, the chunk takes the
    exact path and loses none; reckoned on its own N it loses most."""
    E, k, N = 60, 4, rows * bucket
    x, rw, g, u, d = _rand_moe(jax.random.PRNGKey(8), N=N, E=E)
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 1.0)
    rw = rw.at[0, 0].set(50.0)          # expert 0 wins every token
    top_p, top_i = moe.route(x, rw, k, renormalize=False)
    assert (np.asarray(top_i)[:, 0] == 0).all()
    assert moe.capacity_for(N, E, k, 2.0) < N
    exact = moe._moe_exact(x, top_p, top_i, g, u, d, jax.nn.silu)
    got = moe.moe_mlp(x, rw, g, u, d, top_k=k, renormalize=False,
                      capacity_tokens=16 * bucket)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               atol=1e-5, rtol=1e-5)
    own = moe.moe_mlp(x, rw, g, u, d, top_k=k, renormalize=False)
    lost = np.abs(np.asarray(own) - np.asarray(exact)).max(-1) > 1e-3
    assert lost.sum() >= N - moe.capacity_for(N, E, k, 2.0)


def test_capacity_for():
    assert moe.capacity_for(512, 8, 2, 1.0) == 128
    assert moe.capacity_for(512, 8, 2, 100.0) == 512   # clamped to N
    assert moe.capacity_for(8, 8, 2, 1.0) == 8         # floor of 8
    assert moe.capacity_for(100, 8, 2, 1.0) % 8 == 0   # 8-aligned


def test_moe_forward_train_finite():
    params = llama.init_params(MOE_CFG, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                              MOE_CFG.vocab_size)
    logits = llama.forward_train(params, MOE_CFG, toks)
    assert logits.shape == (2, 48, MOE_CFG.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_ep_sharded_forward_matches_single_device():
    """ep=4 x tp=2 mesh: expert weights shard over ep, logits must match
    the unsharded forward exactly (no drops at these sizes: N=32 tokens
    stay on the exact all-expert path)."""
    mesh = build_mesh(MeshConfig(dp=1, ep=4, tp=2))
    params = llama.init_params(MOE_CFG, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                              MOE_CFG.vocab_size)

    expected = llama.forward_train(params, MOE_CFG, toks)
    sharded = shard_params(mesh, params)
    got = jax.jit(lambda p, t: llama.forward_train(p, MOE_CFG, t))(
        sharded, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)


def test_ep_serving_engine_matches_unsharded():
    """Greedy generation through the engine: identical output with and
    without an ep=2 serving mesh on the debug-moe preset."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    opts = SamplingOptions(temperature=0.0, max_tokens=8)
    base = EngineConfig(model="debug-moe", max_model_len=128,
                        max_num_seqs=2, prefill_chunk=32,
                        prefill_buckets=(16, 32))
    plain = LLMEngine(base).generate("expert parallel probe", opts)

    ep_cfg = EngineConfig(model="debug-moe", max_model_len=128,
                          max_num_seqs=2, prefill_chunk=32,
                          prefill_buckets=(16, 32),
                          expert_parallel_size=2)
    sharded = LLMEngine(ep_cfg).generate("expert parallel probe", opts)
    assert plain == sharded


def test_ep_validation():
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine

    with pytest.raises(ValueError, match="dense"):
        LLMEngine(EngineConfig(model="debug-tiny", max_model_len=64,
                               expert_parallel_size=2))
    with pytest.raises(ValueError, match="divide"):
        LLMEngine(EngineConfig(model="debug-moe", max_model_len=64,
                               expert_parallel_size=3))


def test_lora_mlp_targets_rejected_on_moe():
    """MoE expert FFNs bypass the LoRA proj() hook; asking for gate/up/
    down adapters on a MoE model must fail loudly, not silently no-op."""
    from production_stack_tpu.models import lora

    lcfg = lora.LoRAConfig(targets=("q", "gate"))
    with pytest.raises(ValueError, match="MoE"):
        lora.init_adapter(MOE_CFG, lcfg, jax.random.PRNGKey(0))
    # attention targets stay fine
    ad = lora.init_adapter(MOE_CFG, lora.LoRAConfig(targets=("q", "v")),
                           jax.random.PRNGKey(0))
    assert set(ad) == {"q", "v"}


def test_moe_capacity_factor_plumbs_to_model():
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine

    eng = LLMEngine(EngineConfig(model="debug-moe", max_model_len=64,
                                 moe_capacity_factor=3.5))
    assert eng.model_cfg.moe_capacity_factor == 3.5


def test_encode_moe_ignores_padding_content():
    """encode() (the embeddings path) masks padding: with right-padded
    batches, changing the pad tokens' content must not change any valid
    position's hidden state — pads neither route nor steal capacity.
    Uses a low capacity factor so the droppy dispatch branch is live."""
    cfg = ModelConfig(name="t-moe8", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=8,
                      num_kv_heads=4, max_position_embeddings=256,
                      num_experts=8, num_experts_per_tok=2,
                      moe_capacity_factor=0.8, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    T = 120
    lengths = np.array([T, 40])
    toks = rng.integers(0, cfg.vocab_size, (2, T))
    mask = np.arange(T)[None, :] < lengths[:, None]

    toks_a = toks.copy()
    toks_b = toks.copy()
    toks_b[~mask] = 7    # different garbage in the pad region

    h_a = np.asarray(llama.encode(params, cfg, jnp.asarray(toks_a),
                                  token_valid=jnp.asarray(mask)))
    h_b = np.asarray(llama.encode(params, cfg, jnp.asarray(toks_b),
                                  token_valid=jnp.asarray(mask)))
    np.testing.assert_array_equal(h_a[mask], h_b[mask])
