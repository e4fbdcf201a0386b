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
from production_stack_tpu.models import quant
from production_stack_tpu.ops import moe, pallas_paged
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


def _reference_moe(x, rw, g, u, d, k, capacity=None, valid=None,
                   renormalize=True):
    """Per-token numpy loop: softmax-all, top-k, renormalize, run the
    selected experts one by one. Independent of ops/moe.py's vectorized
    dispatch. capacity simulates per-expert slots filled in token-major
    assignment order (the dispatch path's ranking); valid marks padding
    rows that contribute nothing and consume no capacity."""
    x, rw, u, d = map(np.asarray, (x, rw, u, d))
    g = None if g is None else np.asarray(g)
    N = x.shape[0]
    E = u.shape[0]
    out = np.zeros_like(x)
    counts = np.zeros(E, np.int64)
    for t in range(N):
        if valid is not None and not valid[t]:
            continue
        logits = x[t] @ rw
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p)[:k]
        w = p[top] / p[top].sum() if renormalize else p[top]
        for wi, e in zip(w, top):
            if capacity is not None:
                if counts[e] >= capacity:
                    continue          # dropped: rides the residual
                counts[e] += 1
            out[t] += wi * (_numpy_expert(x[t], g, u, e) @ d[e])
    return out


def _numpy_expert(x_t, g, u, e):
    """One expert's intermediate values for one token: silu(x gate) x
    (x up), or relu(x up)^2 where the experts have no gate (g None)."""
    if g is None:
        return np.maximum(x_t @ u[e], 0.0) ** 2
    hidden = x_t @ g[e]
    return hidden / (1 + np.exp(-hidden)) * (x_t @ u[e])


def _ungate(case: str, g, u, d):
    """The stacks and the activation of a case: its name says
    ``ungated`` where the experts are ``down(relu(up(x))^2)``, two
    matrices (Nemotron-H's)."""
    if case.startswith("ungated"):
        return (None, u, d), moe.relu2
    return (g, u, d), jax.nn.silu


def test_route_weights_normalized():
    x, rw, *_ = _rand_moe(jax.random.PRNGKey(0))
    w, idx = moe.route(x, rw, top_k=2)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    assert np.asarray(idx).min() >= 0 and np.asarray(idx).max() < 4
    # top-k indices are distinct per token
    assert (np.asarray(idx)[:, 0] != np.asarray(idx)[:, 1]).all()


GATES = ["gated", "ungated-relu2"]


@pytest.mark.parametrize("case", GATES)
def test_exact_path_matches_reference(case):
    x, rw, *w = _rand_moe(jax.random.PRNGKey(1))
    (g, u, d), act = _ungate(case, *w)
    got, _ = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1000,
                         act=act)
    np.testing.assert_allclose(np.asarray(got),
                               _reference_moe(x, rw, g, u, d, 2),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", GATES)
def test_dispatch_path_matches_reference(case):
    x, rw, *w = _rand_moe(jax.random.PRNGKey(2))
    (g, u, d), act = _ungate(case, *w)
    # capacity_factor 1.6 -> capacity < N (dispatch branch) but above the
    # realized max expert load for this seed, so no token is dropped
    got, _ = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1,
                         capacity_factor=1.6, act=act)
    cap = moe.capacity_for(x.shape[0], 4, 2, 1.6)
    assert cap < x.shape[0], "capacity must not force the exact branch"
    np.testing.assert_allclose(np.asarray(got),
                               _reference_moe(x, rw, g, u, d, 2),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", GATES)
def test_dispatch_with_drops_matches_reference(case):
    """Over-capacity assignments drop in token-major rank order — the
    numpy reference simulates the same fill and must agree exactly."""
    x, rw, *w = _rand_moe(jax.random.PRNGKey(3))
    (g, u, d), act = _ungate(case, *w)
    got, _ = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1,
                         capacity_factor=0.5, act=act)
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
    got, _ = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1,
                      capacity_factor=0.5, valid=jnp.asarray(valid))
    ref = _reference_moe(x, rw, g, u, d, 2, capacity=cap, valid=valid)
    np.testing.assert_allclose(np.asarray(got), ref, atol=1e-4, rtol=1e-4)
    assert (np.asarray(got)[~valid] == 0).all()
    # exact path masks padding too
    got_exact, _ = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1000,
                            valid=jnp.asarray(valid))
    assert (np.asarray(got_exact)[~valid] == 0).all()


def test_exact_flag_overrides_capacity():
    """exact=True (the decode path) never drops, whatever N/capacity."""
    x, rw, g, u, d = _rand_moe(jax.random.PRNGKey(7))
    got, _ = moe.moe_mlp(x, rw, g, u, d, top_k=2, dense_threshold=1,
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
    got, _ = moe.moe_mlp(x, rw, g, u, d, top_k=k, renormalize=False,
                      capacity_tokens=16 * bucket)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               atol=1e-5, rtol=1e-5)
    own, _ = moe.moe_mlp(x, rw, g, u, d, top_k=k, renormalize=False)
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


# ---------------------------------------------------------------------
# the list path (ops/moe.py): the decode step's expert matmuls walk the
# experts its valid rows were routed to, in place in the weight stacks.
# The Pallas kernel runs in interpret mode here.
# ---------------------------------------------------------------------

@pytest.fixture
def kernels_on():
    pallas_paged.set_flash_enabled(True)
    yield
    pallas_paged.set_flash_enabled(None)


def _rigged_stacks(key, routing, *, N=16, E=60, k=4, h=128, i=256, L=2,
                   weights="int8"):
    """x [N, h] bf16, a router and [L, E, ...] expert stacks at the
    Qwen cell's routing shape and small widths. ``routing`` rigs the
    router: feature n of row n is large, and the router's row n points
    at the experts that row is to choose ("random": nothing rigged;
    "same4": every row the experts 0-3; "all": row n the experts
    4n..4n+3 mod E, which 16 rows spread over all 60)."""
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (N, h), jnp.float32)
    rw = jax.random.normal(ks[1], (h, E), jnp.float32) * 0.2
    if routing != "random":
        x = x.at[jnp.arange(N), jnp.arange(N)].set(30.0)
        for n in range(N):
            first = 0 if routing == "same4" else 4 * n
            rw = rw.at[n, (first + jnp.arange(k)) % E].add(5.0)
    stacks = [jax.random.normal(kk, shape, jnp.float32) * 0.1
              for kk, shape in zip(ks[2:], ((L, E, h, i), (L, E, h, i),
                                           (L, E, i, h)))]
    stacks = [w.astype(jnp.bfloat16) for w in stacks]
    if weights == "int8":
        stacks = [quant.quantize_tensor(w) for w in stacks]
    return x.astype(jnp.bfloat16), rw.astype(jnp.bfloat16), stacks


def _float32(w):
    """A raw or int8 weight as the float32 array it stands for."""
    if quant.is_quantized(w):
        return (np.asarray(w["w8"], np.float32)
                * np.asarray(w["scale"], np.float32)[..., None, :])
    return np.asarray(w.astype(jnp.float32))


LIST_CASES = {
    # name: (routing, rows, valid rows, weights, renormalize)
    "int8": ("random", 16, 16, "int8", False),
    "bf16-weights": ("random", 16, 16, "bf16", False),
    "one-row": ("random", 1, 1, "int8", False),
    "parked-row": ("random", 16, 15, "int8", False),
    "no-valid-row": ("random", 16, 0, "int8", False),
    "same-4-experts": ("same4", 16, 16, "int8", False),
    "all-60-hit": ("all", 16, 16, "int8", False),
    "renormalized": ("random", 16, 15, "int8", True),
    # experts without a gate (two matrices a slot, relu^2)
    "ungated-int8": ("random", 16, 15, "int8", True),
    "ungated-bf16-weights": ("random", 16, 16, "bf16", False),
    "ungated-all-60-hit": ("all", 16, 16, "int8", False),
}


@pytest.mark.parametrize("case", LIST_CASES)
def test_list_path_matches_exact_and_reference(kernels_on, case):
    """moe_mlp handed the whole stacks and a layer (the list path)
    against _moe_exact on that layer's slice and against the float32
    per-token reference; the experts it reports reading are the
    distinct experts its valid rows chose."""
    routing, N, n_valid, weights, renorm = LIST_CASES[case]
    E, k, layer = 60, 4, 1
    x, rw, stacks = _rigged_stacks(jax.random.PRNGKey(11), routing, N=N,
                                   weights=weights)
    valid = jnp.arange(N) < n_valid
    stacks, act = _ungate(case, *stacks)
    got, read = jax.jit(lambda x, *w: moe.moe_mlp(
        x, rw, *w, top_k=k, valid=valid, renormalize=renorm, act=act,
        exact=True, layer=jnp.int32(layer)))(x, *stacks)

    top_p, top_i = moe.route(x, rw, k, renormalize=renorm)
    chosen = np.unique(np.asarray(top_i)[:n_valid])
    assert int(read.experts_read) == len(chosen)
    assert int(read.expert_rows) == len(chosen) * N
    assert len(chosen) == {"same4": 4, "all": 60}.get(routing, len(chosen))
    one = [jax.tree_util.tree_map(lambda a: a[layer], w) for w in stacks]
    exact = moe._moe_exact(x, top_p * valid[:, None], top_i, *one, act)
    ref = _reference_moe(x.astype(jnp.float32), rw.astype(jnp.float32),
                         *(w if w is None else _float32(w) for w in one),
                         k, valid=np.asarray(valid), renormalize=renorm)
    got = np.asarray(got.astype(jnp.float32))
    scale = max(np.abs(ref).max(), 1e-6)
    # bf16 activations on both paths; the list path rounds less often
    assert np.abs(got - np.asarray(exact.astype(jnp.float32))).max() \
        < 0.03 * scale
    assert np.abs(got - ref).max() < 0.02 * scale
    assert (got[n_valid:] == 0).all()


@pytest.mark.parametrize("case", ["all-valid", "parked-rows",
                                  "one-row", "none-valid",
                                  "more-assignments-than-experts"])
def test_experts_hit_lists_distinct_experts_of_valid_rows(case):
    rng = np.random.default_rng(5)
    N, k, E = {"one-row": (1, 4, 60),
               "more-assignments-than-experts": (16, 2, 8)}.get(
                   case, (16, 4, 60))
    top_i = np.stack([rng.choice(E, k, replace=False) for _ in range(N)])
    valid = np.ones(N, bool)
    if case == "parked-rows":
        valid[[3, 15]] = False
    if case == "none-valid":
        valid[:] = False
    ids, count = jax.jit(lambda t, v: moe.experts_hit(t, v, E))(
        jnp.asarray(top_i, jnp.int32), jnp.asarray(valid))
    want = np.unique(top_i[valid])
    assert ids.shape == (min(E, N * k),) and ids.dtype == jnp.int32
    assert int(count) == len(want)
    np.testing.assert_array_equal(np.asarray(ids)[:len(want)], want)
    assert (np.asarray(ids)[len(want):] == 0).all()
    # valid=None: every row counts
    _, count_all = moe.experts_hit(jnp.asarray(top_i, jnp.int32), None, E)
    assert int(count_all) == len(np.unique(top_i))


QWEN = (2048, 1408)            # Qwen1.5-MoE-A2.7B's experts [h, i]
MIXTRAL = (4096, 14336)        # Mixtral-8x7B's


@pytest.mark.parametrize("what,rows,positions,widths,mesh,kernels,want", [
    ("qwen decode, 16 rows", 16, 1, QWEN, None, True, True),
    ("qwen decode, one row", 1, 1, QWEN, None, True, True),
    ("qwen decode, 64 rows", 64, 1, QWEN, None, True, True),
    ("qwen decode, 128 rows", 128, 1, QWEN, None, True, False),
    ("qwen prefill chunk", 1, 256, QWEN, None, True, False),
    ("qwen one-row prefill of 16 tokens", 1, 16, QWEN, None, True, False),
    ("qwen speculative window", 4, 4, QWEN, None, True, False),
    ("mixtral decode, 16 rows", 16, 1, MIXTRAL, None, True, False),
    ("mixtral decode, 4 rows", 4, 1, MIXTRAL, None, True, False),
    ("mixtral decode, 2 rows", 2, 1, MIXTRAL, None, True, False),
    ("mixtral decode, one row", 1, 1, MIXTRAL, None, True, False),
    ("ep mesh", 16, 1, QWEN, dict(dp=1, ep=2, tp=1), True, False),
    ("tp mesh", 16, 1, QWEN, dict(dp=1, ep=1, tp=2), True, False),
    ("mesh of one device", 16, 1, QWEN, dict(dp=1, ep=1, tp=1), True,
     True),
    ("kernels off (the CPU)", 16, 1, QWEN, None, False, False),
])
def test_list_path_rule(what, rows, positions, widths, mesh, kernels,
                        want):
    """The path follows from the rows and positions, the experts'
    widths and the mesh (and whether Pallas kernels run at all): no
    option selects it."""
    pallas_paged.set_flash_enabled(kernels)
    try:
        if mesh is not None:
            mesh = build_mesh(MeshConfig(**mesh),
                              devices=jax.devices()[:np.prod(
                                  list(mesh.values()))])
        assert moe.list_path(rows, positions, *widths, jnp.int8,
                             jnp.bfloat16, mesh) is want
    finally:
        pallas_paged.set_flash_enabled(None)


@pytest.mark.parametrize("widths,weights,fits", [
    (QWEN, jnp.int8, True), (QWEN, jnp.bfloat16, True),
    (MIXTRAL, jnp.int8, False), (MIXTRAL, jnp.bfloat16, False),
    ((4096, 1408), jnp.bfloat16, False)])
def test_list_path_needs_experts_that_fit_vmem(kernels_on, widths,
                                               weights, fits):
    """Two slots of an expert's three matrices as stored, and one
    converted to the activation dtype, in half of the kernel's VMEM
    limit: what does not fit keeps the exact path, which compiles."""
    need = moe.list_scratch_bytes(*widths, weights, jnp.bfloat16)
    h, i = widths
    assert need == h * i * (6 * jnp.dtype(weights).itemsize
                            + (2 if weights == jnp.int8 else 0))
    assert (need <= pallas_paged.VMEM_LIMIT_BYTES // 2) is fits
    assert moe.list_path(16, 1, *widths, weights, jnp.bfloat16) is fits


def test_list_path_needs_widths_that_tile(kernels_on):
    assert moe.list_path(16, 1, 2048, 1408, jnp.int8, jnp.bfloat16)
    assert not moe.list_path(16, 1, 2048, 1400, jnp.int8, jnp.bfloat16)
    assert not moe.list_path(16, 1, 64, 128, jnp.int8, jnp.bfloat16)


def test_moe_mlp_refuses_stacks_where_the_rule_says_no(kernels_on):
    """Whole stacks and a layer are the list path's operands: handed
    them at a size ``list_path`` refuses (more rows than a decode
    batch), or with exact=False, moe_mlp raises instead of walking a
    list nobody chose."""
    x, rw, stacks = _rigged_stacks(jax.random.PRNGKey(3), "random",
                                   N=moe.DENSE_THRESHOLD + 8)
    with pytest.raises(AssertionError, match="handed whole stacks"):
        moe.moe_mlp(x, rw, *stacks, top_k=4, layer=jnp.int32(0))
    with pytest.raises(AssertionError, match="handed whole stacks"):
        moe.moe_mlp(x[:16], rw, *stacks, top_k=4, exact=False,
                    layer=jnp.int32(0))
    # nor a speculative window's few positions a row (grouped_path)
    with pytest.raises(AssertionError, match="handed whole stacks"):
        moe.moe_mlp(x[:16], rw, *stacks, top_k=4, layer=jnp.int32(0),
                    positions=4)


def test_forward_takes_the_list_path_in_place(kernels_on):
    """llama.forward over one position per row of a many-expert model:
    the logits of the list path against those of the exact path (the
    kernels off), and the experts it reports reading against the
    layers x experts the exact path reads."""
    cfg = ModelConfig(name="t-moe16", vocab_size=128, hidden_size=128,
                      intermediate_size=128, num_layers=2, num_heads=2,
                      num_kv_heads=2, max_position_embeddings=64,
                      num_experts=16, num_experts_per_tok=2,
                      dtype=jnp.float32)
    from production_stack_tpu.models import make_slot_cache
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B = 2
    toks = jnp.asarray([[5], [9]], jnp.int32)
    pos = jnp.zeros((B, 1), jnp.int32)

    def run():
        cache, tables = make_slot_cache(
            cfg.num_layers, B, 64, cfg.num_kv_heads, cfg.head_dim_,
            dtype=jnp.float32)
        return llama.forward(params, cfg, toks, pos, cache,
                             block_tables=tables)

    assert moe.list_path(B, 1, 128, 128, jnp.float32, jnp.float32)
    logits, _, read = run()
    pallas_paged.set_flash_enabled(False)
    want, _, read_all = run()
    read, read_all = read.experts_read, read_all.experts_read
    assert int(read_all) == cfg.num_layers * cfg.num_experts
    assert 2 * 2 <= int(read) <= 2 * B * 2 < int(read_all)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("B,T", [(1, 16), (4, 4)],
                         ids=["one-row-prefill", "speculative-window"])
def test_forward_of_several_positions_never_walks_the_list(kernels_on, B,
                                                           T):
    """A short prefill chunk and a speculative window hold as few
    tokens as a decode batch does, and neither takes the list path,
    which is the decode step's (one position a row): the chunk runs
    grouped (ops/moe.grouped_path: more positions a row than the
    decode attention kernel takes), the window keeps the exact path
    and reads every expert."""
    cfg = ModelConfig(name="t-moe16", vocab_size=128, hidden_size=128,
                      intermediate_size=128, num_layers=2, num_heads=2,
                      num_kv_heads=2, max_position_embeddings=64,
                      num_experts=16, num_experts_per_tok=2,
                      dtype=jnp.float32)
    from production_stack_tpu.models import make_slot_cache
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cache, tables = make_slot_cache(
        cfg.num_layers, B, 64, cfg.num_kv_heads, cfg.head_dim_,
        dtype=jnp.float32)
    toks = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    jaxpr = str(jax.make_jaxpr(lambda: llama.forward(
        params, cfg, toks, pos, cache, block_tables=tables))())
    assert "moe_list_experts" not in jaxpr
    grouped = T > pallas_paged.DECODE_T_MAX
    assert ("moe_grouped_experts" in jaxpr) is grouped
    _, _, read = llama.forward(params, cfg, toks, pos, cache,
                               block_tables=tables)
    every = cfg.num_layers * cfg.num_experts
    if grouped:     # 16 tokens x top-2: 2 to 16 experts a layer
        assert cfg.num_layers * 2 <= int(read.experts_read) <= every
    else:
        assert int(read.experts_read) == every


# ---------------------------------------------------------------------
# the grouped path (ops/moe.py): a prefill chunk's experts multiply only
# the rows routed to them, sorted by expert, in place in the stacks.
# The Pallas kernel runs in interpret mode here.
# ---------------------------------------------------------------------

def _reference_routed(x, top_p, top_i, g, u, d, valid):
    """Per-token numpy loop over the experts each token chose, at the
    weights the router gave: float32, independent of ops/moe.py."""
    x, top_p, top_i, u, d = map(np.asarray, (x, top_p, top_i, u, d))
    g = None if g is None else np.asarray(g)
    out = np.zeros_like(x)
    for t in np.flatnonzero(valid):
        for w, e in zip(top_p[t], top_i[t]):
            out[t] += w * (_numpy_expert(x[t], g, u, e) @ d[e])
    return out


GROUPED_CASES = {
    # name: (rows, tokens, E, k, dtype, weights, routing, real tokens
    #        of each row (None: all), router score)
    "128-f32-raw-8-top2": (1, 128, 8, 2, "float32", "raw", "random",
                           None, "softmax"),
    "128-bf16-int8-8-top2": (1, 128, 8, 2, "bfloat16", "int8", "random",
                             None, "softmax"),
    "256-bf16-int8-60-top4": (1, 256, 60, 4, "bfloat16", "int8",
                              "random", None, "softmax"),
    "256-bf16-raw-60-top4": (1, 256, 60, 4, "bfloat16", "raw", "random",
                             None, "softmax"),
    "256-f32-int8-64-top4": (1, 256, 64, 4, "float32", "int8", "random",
                             None, "softmax"),
    "512-bf16-int8-64-top4-sigmoid": (1, 512, 64, 4, "bfloat16", "int8",
                                      "random", None, "sigmoid"),
    # right padding: 131 real tokens, group sizes no multiple of a tile
    "256-right-padded": (1, 256, 60, 4, "bfloat16", "int8", "random",
                         [131], "softmax"),
    # the lead-in's burst in small: 16 rows, each padded, four parked
    "16x64-padded-and-parked": (16, 64, 60, 4, "bfloat16", "int8",
                                "random",
                                [64, 1, 33, 17, 64, 50, 9, 40, 64, 64, 2,
                                 31, 0, 0, 0, 0], "softmax"),
    # every token on the same two experts: 200 rows each (two passes,
    # the second half empty), 62 experts with no row
    "every-token-on-one-pair": (1, 256, 64, 2, "bfloat16", "int8",
                                "same", [200], "softmax"),
    # experts 8..63 never chosen
    "experts-with-no-row": (1, 128, 64, 2, "float32", "raw", "first8",
                            None, "softmax"),
    "no-valid-token": (1, 128, 8, 2, "bfloat16", "int8", "random", [0],
                       "softmax"),
    # experts without a gate (relu^2), Nemotron-H's router
    "ungated-128-f32-raw-8-top2": (1, 128, 8, 2, "float32", "raw",
                                   "random", None, "softmax"),
    "ungated-256-bf16-int8-64-top4-sigmoid": (
        1, 256, 64, 4, "bfloat16", "int8", "random", [131], "sigmoid"),
    "ungated-every-token-on-one-pair": (1, 256, 64, 2, "bfloat16", "int8",
                                        "same", [200], "softmax"),
}


def _grouped_case(name, h=128, i=256, L=2):
    """The operands of one case: x [N, h], the router (and GLM's bias
    and scale where the score is sigmoid), [L, E, ...] stacks, the
    tokens' valid mask."""
    rows, tokens, E, k, dtype, weights, routing, real, score = \
        GROUPED_CASES[name]
    N = rows * tokens
    dtype = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 6)
    x = jax.random.normal(ks[0], (N, h), jnp.float32)
    rw = jax.random.normal(ks[1], (h, E), jnp.float32) * 0.2
    if routing == "same":        # feature 0 large, pointing at 0..k-1
        x = x.at[:, 0].set(30.0)
        rw = rw.at[0, :k].add(5.0)
    if routing == "first8":      # experts 8.. pushed out of every top-k
        rw = rw.at[:, 8:].set(0.0)
        x = x.at[:, 0].set(30.0)
        rw = rw.at[0, 8:].set(-5.0)
    stacks = [(jax.random.normal(kk, dims, jnp.float32) * 0.1).astype(dtype)
              for kk, dims in zip(ks[2:5], ((L, E, h, i), (L, E, h, i),
                                            (L, E, i, h)))]
    if weights == "int8":
        stacks = [quant.quantize_tensor(w) for w in stacks]
    # every token real: no mask at all, as a caller without padding
    valid = None if real is None else (
        jnp.arange(tokens)[None, :]
        < jnp.asarray(real)[:, None]).reshape(N)
    router = dict(router_score=score)
    if score == "sigmoid":
        router.update(router_bias=0.1 * jax.random.normal(ks[5], (E,)),
                      routed_scale=1.8)
    return x.astype(dtype), rw.astype(dtype), stacks, valid, k, router


@pytest.mark.parametrize("case", GROUPED_CASES)
def test_grouped_path_matches_exact_and_reference(kernels_on, case):
    """moe_mlp handed the whole stacks, a layer and the tokens a row
    (the grouped path) against _moe_exact on that layer's slice and
    against the float32 per-token reference; its work: the experts
    that had a row, and the rows they multiplied in passes of
    GROUPED_ROWS."""
    x, rw, stacks, valid, k, router = _grouped_case(case)
    rows, tokens, E = GROUPED_CASES[case][:3]
    layer = 1
    stacks, act = _ungate(case, *stacks)
    got, work = jax.jit(lambda x, *w: moe.moe_mlp(
        x, rw, *w, top_k=k, valid=valid, layer=jnp.int32(layer),
        positions=tokens, act=act, **router))(x, *stacks)
    assert got.dtype == x.dtype and got.shape == x.shape

    top_p, top_i = moe.route(
        x, rw, k, score=router["router_score"],
        bias=router.get("router_bias"),
        scale=router.get("routed_scale", 1.0))
    v = np.ones(len(x), bool) if valid is None else np.asarray(valid)
    per_expert = np.bincount(np.asarray(top_i)[v].reshape(-1),
                             minlength=E)
    assert int(work.experts_read) == (per_expert > 0).sum()
    R = moe.GROUPED_ROWS
    assert int(work.expert_rows) == (-(-per_expert // R) * R).sum()
    if case.endswith("every-token-on-one-pair"):
        assert sorted(per_expert[per_expert > 0]) == [200, 200]
    if case == "experts-with-no-row":
        assert (per_expert[8:] == 0).all() and per_expert.sum() == 256

    one = [jax.tree_util.tree_map(lambda a: a[layer], w) for w in stacks]
    exact = moe._moe_exact(x, top_p * v[:, None], top_i, *one, act)
    ref = _reference_routed(x.astype(jnp.float32), top_p, top_i,
                            *(w if w is None else _float32(w)
                              for w in one), v)
    got = np.asarray(got.astype(jnp.float32))
    scale = max(np.abs(ref).max(), 1e-6)
    loose = x.dtype == jnp.bfloat16
    assert np.abs(got - np.asarray(exact.astype(jnp.float32))).max() \
        <= (0.03 if loose else 1e-5) * scale
    assert np.abs(got - ref).max() <= (0.02 if loose else 1e-5) * scale
    assert (got[~v] == 0).all()


def test_grouped_path_drops_nothing_where_the_dispatch_does(kernels_on):
    """Every token on the same two experts, 200 rows each: the capacity
    dispatch at capacity_factor 2.0 keeps 16 rows an expert (2 x 256 x
    2 / 64) and drops the rest; the grouped path keeps the exact
    path's sum for every token."""
    x, rw, stacks, valid, k, router = _grouped_case(
        "every-token-on-one-pair")
    one = [jax.tree_util.tree_map(lambda a: a[1], w) for w in stacks]
    dropped, _ = moe.moe_mlp(x, rw, *one, top_k=k, valid=valid,
                             capacity_factor=2.0, exact=False)
    grouped, _ = moe.moe_mlp(x, rw, *stacks, top_k=k, valid=valid,
                             layer=jnp.int32(1), positions=256)
    exact, _ = moe.moe_mlp(x, rw, *one, top_k=k, valid=valid, exact=True)
    v = np.asarray(valid)

    def rows_off(y):
        return (np.abs(np.asarray(y.astype(jnp.float32))
                       - np.asarray(exact.astype(jnp.float32))).max(-1)
                > 0.05 * np.abs(np.asarray(exact, np.float32)).max())[v]
    assert moe.capacity_for(256, 64, 2, 2.0) == 16
    assert rows_off(dropped).sum() >= 200 - 16
    assert rows_off(grouped).sum() == 0


# the chip's share of the experts on the grouped path: the compacted
# list of the assignments that land here, a block of them a round
HELD_CASES = {
    # name: (held choices of each token: "even" draws them at the even
    #        share, a number gives every token that many, a list the
    #        leading tokens theirs and the others none; real tokens
    #        (None: all); dtype; weights; tiles)
    # 128 tokens top-4, 8 of a router's 64 experts held: 512
    # assignments, a block of 2 x 64 -> 128
    "near-even-one-round": ("even", None, "float32", "raw", 1),
    "every-assignment-here-4-rounds": (4, None, "float32", "raw", 1),
    "none-here-no-round": (0, None, "float32", "raw", 1),
    "total-exactly-a-block": ([4] * 32, None, "float32", "raw", 1),
    "total-a-block-and-one": ([4] * 32 + [1], None, "float32", "raw", 1),
    # tokens 100.. are padding whose choices are all held here
    "padding-that-would-land-here": ([2] * 100 + [4] * 28, 100, "float32",
                                     "raw", 1),
    "bf16-int8-two-rounds": ([3] * 70, None, "bfloat16", "int8", 1),
    "tiled-two-rounds": ([4] * 40, None, "bfloat16", "int8", 2),
    # experts without a gate: the rounds and ``moe_held_sum`` alike
    "ungated-f32-two-rounds": ([4] * 32 + [1], None, "float32", "raw", 1),
    "ungated-bf16-int8-two-rounds": ([3] * 70, None, "bfloat16", "int8",
                                     1),
    "ungated-tiled-two-rounds": ([4] * 40, None, "bfloat16", "int8", 2),
}


def _held_case(name, N=128, k=4, E=8, h=128, i=256, L=2):
    """x [N, h], the routing as moe_mlp hands it over (an expert held
    elsewhere named E, its weight zero; an invalid token's weights
    zero), [L, E, ...] stacks, the valid mask."""
    here, real, dtype, weights, _ = HELD_CASES[name]
    rng = np.random.default_rng(len(name))
    if isinstance(here, str):       # 8 of 64: an eighth of the choices
        here = rng.binomial(k, E / 64, N)
    elif isinstance(here, int):
        here = [here] * N
    here = np.concatenate([here, np.zeros(N - len(here), int)]).astype(int)
    top_i = np.full((N, k), E, np.int32)
    for t in range(N):
        top_i[t, rng.permutation(k)[:here[t]]] = rng.permutation(E)[:here[t]]
    valid = None if real is None else np.arange(N) < real
    top_p = rng.uniform(0.05, 0.5, (N, k)).astype(np.float32)
    top_p[top_i == E] = 0.0
    if valid is not None:
        top_p[~valid] = 0.0
    dtype = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 4)
    x = jax.random.normal(ks[0], (N, h), jnp.float32).astype(dtype)
    stacks = [(jax.random.normal(kk, dims, jnp.float32) * 0.1).astype(dtype)
              for kk, dims in zip(ks[1:], ((L, E, h, i), (L, E, h, i),
                                           (L, E, i, h)))]
    if weights == "int8":
        stacks = [quant.quantize_tensor(w) for w in stacks]
    return x, jnp.asarray(top_p), jnp.asarray(top_i), stacks, valid


@pytest.mark.parametrize("case", HELD_CASES)
def test_held_grouped_path_matches_exact_and_reference(
        kernels_on, monkeypatch, case):
    """``_moe_grouped`` told that the router scores 64 experts where
    the stacks hold 8: the sum over the held experts alone, against
    _moe_exact on the layer's slice and the float32 per-token
    reference, at every filling of the rounds; ``Work`` counts the
    assignments kept, the rounds, and over the rounds the experts
    that had a row and the passes' rows."""
    x, top_p, top_i, stacks, valid = _held_case(case)
    N, k = top_i.shape
    E, tiles = 8, HELD_CASES[case][4]
    stacks, act = _ungate(case, *stacks)
    gated = stacks[0] is not None
    if tiles > 1:       # two slots of a HALF of an expert fit, no more
        monkeypatch.setattr(
            moe, "_LIST_VMEM_SHARE",
            1.5 * moe.list_scratch_bytes(128, 128, jnp.int8, jnp.bfloat16,
                                         gated)
            / pallas_paged.VMEM_LIMIT_BYTES)
    assert moe.expert_tiles(128, 256, moe.stored_dtype(stacks[1]),
                            x.dtype, gated) == tiles
    B = moe.held_block(N, k, E, 64)
    assert B == 128
    layer = 1
    got, work = jax.jit(lambda x, *w: moe._moe_grouped(
        x, top_p, top_i, *w, act,
        None if valid is None else jnp.asarray(valid), jnp.int32(layer),
        64))(x, *stacks)
    assert got.dtype == x.dtype and got.shape == x.shape

    v = np.ones(N, bool) if valid is None else valid
    flat = np.asarray(top_i).reshape(-1)
    kept = flat[(flat < E) & np.repeat(v, k)]     # in token order
    assert int(work.held_rows) == len(kept)
    assert int(work.rounds) == -(-len(kept) // B)
    blocks = [np.bincount(kept[r:r + B], minlength=E)
              for r in range(0, len(kept), B)]
    assert int(work.experts_read) == sum((b > 0).sum() for b in blocks)
    R = moe.GROUPED_ROWS
    assert int(work.expert_rows) == sum((-(-b // R) * R).sum()
                                        for b in blocks)
    want_rounds = {"near-even-one-round": 1, "none-here-no-round": 0,
                   "every-assignment-here-4-rounds": 4,
                   "total-exactly-a-block": 1, "total-a-block-and-one": 2,
                   "padding-that-would-land-here": 2,
                   "bf16-int8-two-rounds": 2, "tiled-two-rounds": 2,
                   "ungated-f32-two-rounds": 2,
                   "ungated-bf16-int8-two-rounds": 2,
                   "ungated-tiled-two-rounds": 2}
    assert int(work.rounds) == want_rounds[case]

    one = [jax.tree_util.tree_map(lambda a: a[layer], w) for w in stacks]
    exact = moe._moe_exact(x, top_p, top_i, *one, act)
    ref = _reference_routed(
        x.astype(jnp.float32), top_p, np.minimum(np.asarray(top_i), E - 1),
        *(w if w is None else _float32(w) for w in one),
        v)                                        # (weight 0 elsewhere)
    got = np.asarray(got.astype(jnp.float32))
    scale = max(np.abs(ref).max(), 1e-6)
    loose = x.dtype == jnp.bfloat16
    assert np.abs(got - np.asarray(exact.astype(jnp.float32))).max() \
        <= (0.03 if loose else 1e-5) * scale
    assert np.abs(got - ref).max() <= (0.02 if loose else 1e-5) * scale
    assert (got[~v] == 0).all()
    if not len(kept):
        assert (got == 0).all()


# the rounds' sum by token alone (``_held_sum``), against the row
# scatter-add it took the place of
HELD_SUM_CASES = {
    # name: (tokens, block, entries of each token in order (the tokens
    #        after them have none), dtype, planes)
    # 384 tokens are three tiles of 128, a block of 512 four slabs
    "near-even": (384, 512, "even", "bfloat16", 1),
    "a-token-with-no-entry": (384, 512, [1, 0, 2, 0, 0, 3] * 64, "bfloat16",
                              1),
    # token 40's ten entries are the block's 120..129
    "a-token-straddles-two-slabs": (384, 512, [3] * 40 + [10] + [1] * 300,
                                    "bfloat16", 1),
    "a-tile-with-an-empty-window": (384, 512, [2] * 128 + [0] * 128
                                    + [1] * 128, "bfloat16", 1),
    "a-leading-tile-with-an-empty-window": (384, 512, [0] * 200 + [2] * 184,
                                            "bfloat16", 1),
    "total-0": (384, 512, [], "bfloat16", 1),
    "total-the-block": (384, 512, [4] * 128, "bfloat16", 1),
    "total-a-whole-slab": (384, 512, [1] * 256, "bfloat16", 1),
    "two-planes": (384, 512, "even", "bfloat16", 2),
    "float32-rows": (384, 512, "even", "float32", 1),
    "a-chunk-shorter-than-a-tile": (72, 128, [1, 2] * 36, "bfloat16", 1),
    "a-chunk-of-one-tile-and-a-part": (200, 256, [1] * 200, "bfloat16", 2),
}


@pytest.mark.parametrize("case", HELD_SUM_CASES)
def test_held_sum_kernel_matches_the_scatter_add(kernels_on, case):
    """``_held_sum`` (one pass over the round's block) against the row
    scatter-add of the weighed rows: equal to float32 reassociation,
    1e-6 of the largest entry, with weights whose low mantissa bits
    matter (rounded to bfloat16 they miss that by a hundredfold), on
    top of a sum that already holds something; rows of tokens with no
    entry come back as they went in, and what lies past the live
    entries adds nothing."""
    N, B, counts, dtype, planes = HELD_SUM_CASES[case]
    h = 256
    rng = np.random.default_rng(len(case))
    if isinstance(counts, str):
        counts = rng.binomial(10, 0.125, N)
    counts = np.concatenate([counts, np.zeros(N - len(counts), int)]
                            ).astype(int)
    live = int(counts.sum())
    assert live <= B and B % moe.GROUPED_ROWS == 0
    tok = np.full(B, N, np.int32)
    tok[:live] = np.repeat(np.arange(N), counts)
    weight = rng.uniform(0.01, 1.0, B).astype(np.float32)
    weight[live:] = 0.0
    ys = [jnp.asarray(rng.normal(size=(B, h)), jnp.float32).astype(dtype)
          for _ in range(planes)]
    T = moe._held_sum_tile(N)
    Np = -(-N // T) * T
    acc = rng.normal(size=(Np, h)).astype(np.float32)

    def scattered(weight):
        y = sum(p.astype(jnp.float32) for p in ys)
        return np.asarray(jnp.asarray(acc).at[jnp.asarray(tok)].add(
            y * jnp.asarray(weight)[:, None], mode="drop",
            indices_are_sorted=True))

    got = np.asarray(jax.jit(lambda acc, *ys: moe._held_sum(
        acc, ys, jnp.asarray(tok), jnp.asarray(weight), N))(acc, *ys))
    want = scattered(weight)
    assert got.shape == want.shape and got.dtype == np.float32
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-6 * scale
    none = np.flatnonzero(counts == 0)
    assert (got[none] == acc[none]).all() and (got[N:] == acc[N:]).all()
    if live and dtype == "bfloat16":
        rounded = np.asarray(jnp.asarray(weight).astype(jnp.bfloat16)
                             .astype(jnp.float32))
        assert np.abs(scattered(rounded) - want).max() > 1e-4 * scale


def test_held_sum_is_one_named_call_in_place():
    """The rounds' sum is the Pallas call ``moe_held_sum`` (the name a
    capture's breakdown and tests/test_chip_compile.py tell it by), a
    grid step a tile of 128 tokens, and the sum goes in and out through
    one buffer, as the rounds' carry does."""
    acc = jnp.zeros((256, 256), jnp.float32)
    ys = jnp.zeros((256, 256), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda acc, ys: moe._held_sum(
        acc, [ys], jnp.zeros((256,), jnp.int32),
        jnp.zeros((256,), jnp.float32), 256))(acc, ys).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "moe_held_sum"
    assert tuple(call.params["input_output_aliases"]) == ((1, 0),)
    assert call.params["grid_mapping"].grid == (2,)
    assert not {"scatter-add", "gather", "while"} & {
        e.primitive.name for e in jaxpr.eqns}


def _primitives(jaxpr):
    """The primitives of a traced program by name, those of the loops'
    and calls' bodies too, a Pallas kernel's own left out."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _primitives(sub)
    return names


def test_grouped_path_runs_no_rounds_where_every_expert_is_held(
        kernels_on):
    """The router scores exactly the stacks' experts: no loop over
    rounds, no sum by token, and ``Work`` counts none; told of a wider
    router the same call traces the loop, whose sum by token is a
    second Pallas call and no scatter."""
    x, top_p, top_i, stacks, _ = _held_case("near-even-one-round")
    top_i = jnp.minimum(top_i, 7)

    def traced(router_experts):
        return _primitives(jax.make_jaxpr(lambda x, *w: moe._moe_grouped(
            x, top_p, top_i, *w, jax.nn.silu, None, jnp.int32(0),
            router_experts))(x, *stacks).jaxpr)

    for own in (0, 8):
        names = traced(own)
        assert names.count("pallas_call") == 1
        assert not {"while", "scatter-add"} & set(names)
    held = traced(64)
    assert {"while", "pallas_call"} <= set(held)
    assert "scatter-add" not in held and held.count("pallas_call") == 2
    _, work = moe._moe_grouped(x, top_p, top_i, *stacks, jax.nn.silu, None,
                               jnp.int32(0))
    assert int(work.held_rows) == 0 and int(work.rounds) == 0
    assert int(work.expert_rows) >= 128


@pytest.mark.parametrize("what,rows,positions,widths,mesh,kernels,want", [
    ("qwen one-row prefill, 256 tokens", 1, 256, QWEN, None, True, True),
    ("qwen one-row prefill, 128 tokens", 1, 128, QWEN, None, True, True),
    ("qwen chunk of 512", 1, 512, QWEN, None, True, True),
    ("qwen 16-row burst", 16, 256, QWEN, None, True, True),
    ("glm one-row prefill", 1, 256, (2048, 1536), None, True, True),
    ("eight rows of 16 tokens", 8, 16, QWEN, None, True, True),
    ("one row of 64 tokens", 1, 64, QWEN, None, True, True),
    ("the smallest chunk bucket", 1, 16, QWEN, None, True, True),
    ("speculative window", 4, 4, QWEN, None, True, False),
    ("the widest speculative window", 16, 8, QWEN, None, True, False),
    ("decode step, 16 rows", 16, 1, QWEN, None, True, False),
    ("decode step, 128 rows", 128, 1, QWEN, None, True, False),
    ("mixtral prefill", 1, 256, MIXTRAL, None, True, False),
    ("ep mesh", 1, 256, QWEN, dict(dp=1, ep=2, tp=1), True, False),
    ("tp mesh", 1, 256, QWEN, dict(dp=1, ep=1, tp=2), True, False),
    ("mesh of one device", 1, 256, QWEN, dict(dp=1, ep=1, tp=1), True,
     True),
    ("kernels off (the CPU)", 1, 256, QWEN, None, False, False),
])
def test_grouped_path_rule(what, rows, positions, widths, mesh, kernels,
                           want):
    """As list_path's: the path follows from the rows and positions,
    the experts' widths and the mesh; and never both rules at once."""
    pallas_paged.set_flash_enabled(kernels)
    try:
        if mesh is not None:
            mesh = build_mesh(MeshConfig(**mesh),
                              devices=jax.devices()[:np.prod(
                                  list(mesh.values()))])
        shape = (rows, positions, *widths, jnp.int8, jnp.bfloat16, mesh)
        assert moe.grouped_path(*shape) is want
        assert not (want and moe.list_path(*shape))
    finally:
        pallas_paged.set_flash_enabled(None)


@pytest.mark.parametrize("rows,positions,kernels,widths,want", [
    (16, 1, True, QWEN, "list"), (1, 256, True, QWEN, "grouped"),
    (16, 256, True, QWEN, "grouped"), (4, 4, True, QWEN, "exact"),
    (128, 1, True, QWEN, "exact"), (16, 1, False, QWEN, "exact"),
    # kernels off: a one-row chunk is covered by the capacity the full
    # batch reckons (552 >= 256), the 16-row burst is not
    (1, 256, False, QWEN, "exact"), (16, 256, False, QWEN, "dispatch"),
    (16, 1, True, MIXTRAL, "exact"), (16, 256, True, MIXTRAL, "dispatch"),
])
def test_moe_path_names_the_strategy(rows, positions, kernels, widths,
                                     want):
    """moe_path, what engine/runner.py records per executable, for a
    60-expert top-4 model at the cells' capacity (16 rows x the chunk,
    factor 2.0)."""
    pallas_paged.set_flash_enabled(kernels)
    try:
        assert moe.moe_path(rows, positions, 60, 4, *widths, jnp.int8,
                            jnp.bfloat16, None, capacity_factor=2.0,
                            capacity_tokens=16 * positions) == want
    finally:
        pallas_paged.set_flash_enabled(None)


def test_forward_takes_the_grouped_path_in_place(kernels_on):
    """llama.forward over a prefill chunk of a many-expert model (two
    rows of 64 tokens, one right-padded): the logits of the grouped
    path against those of the exact path (the kernels off, and a
    capacity factor of E / k so that capacity covers every token), the
    kernel's name in the program, and the work it reports against the
    exact path's layers x experts x tokens."""
    cfg = ModelConfig(name="t-moe16", vocab_size=128, hidden_size=128,
                      intermediate_size=128, num_layers=2, num_heads=2,
                      num_kv_heads=2, max_position_embeddings=64,
                      num_experts=16, num_experts_per_tok=2,
                      moe_capacity_factor=8.0, dtype=jnp.float32)
    from production_stack_tpu.models import make_slot_cache
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    B, T = 2, 64
    toks = (jnp.arange(B * T, dtype=jnp.int32).reshape(B, T) * 7) % 128
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    valid = jnp.arange(T)[None, :] < jnp.asarray([64, 40])[:, None]

    def run():
        cache, tables = make_slot_cache(
            cfg.num_layers, B, 64, cfg.num_kv_heads, cfg.head_dim_,
            dtype=jnp.float32)
        return llama.forward(params, cfg, toks, pos, cache,
                             block_tables=tables, token_valid=valid)

    assert moe.grouped_path(B, T, 128, 128, jnp.float32, jnp.float32)
    assert "moe_grouped_experts" in str(jax.make_jaxpr(run)())
    logits, _, work = run()
    pallas_paged.set_flash_enabled(False)
    want, _, work_all = run()
    assert int(work_all.experts_read) == cfg.num_layers * cfg.num_experts
    assert int(work_all.expert_rows) == (cfg.num_layers * cfg.num_experts
                                         * B * T)
    assert int(work.experts_read) <= int(work_all.experts_read)
    routed = cfg.num_layers * 104 * cfg.num_experts_per_tok
    assert routed <= int(work.expert_rows) < int(work_all.expert_rows)
    np.testing.assert_allclose(np.asarray(logits)[np.asarray(valid)],
                               np.asarray(want)[np.asarray(valid)],
                               atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------
# route: GLM-4.7-Flash's score, selection bias, renormalisation, scale
# ---------------------------------------------------------------------

def _route_numpy(x, w, k, bias, scale):
    """noaux_tc in ten lines: sigmoid scores, the top k of scores +
    bias, the chosen scores WITHOUT the bias, renormalised, scaled."""
    sc = 1.0 / (1.0 + np.exp(-(x.astype(np.float64)
                               @ w.astype(np.float64))))
    top_i = np.argsort(-(sc + bias), axis=-1, kind="stable")[:, :k]
    top_p = np.take_along_axis(sc, top_i, axis=-1)
    top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    return scale * top_p, top_i


def _route_parent(x, router_w, top_k, renormalize=True):
    """ops/moe.route as it stood before the score, bias and scale
    arguments (PR 34), verbatim."""
    logits = jnp.einsum("nh,he->ne", x, router_w,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i.astype(jnp.int32)


def _router_inputs(n=48, h=64, e=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (n, h), jnp.float32),
            0.3 * jax.random.normal(ks[1], (h, e), jnp.float32),
            0.1 * jax.random.normal(ks[2], (e,), jnp.float32))


def test_route_sigmoid_bias_renormalise_scale_matches_numpy():
    x, w, bias = _router_inputs()
    top_p, top_i = moe.route(x, w, 4, renormalize=True, score="sigmoid",
                             bias=bias, scale=1.8)
    want_p, want_i = _route_numpy(np.asarray(x), np.asarray(w), 4,
                                  np.asarray(bias, np.float64), 1.8)
    assert np.array_equal(np.asarray(top_i), want_i)
    np.testing.assert_allclose(np.asarray(top_p), want_p, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(top_p).sum(-1), 1.8, rtol=1e-6)


def test_route_bias_moves_the_selection_and_never_the_weights():
    """A bias that lifts expert 0 over everything puts it into every
    row's chosen set; its WEIGHT is still its bias-free score."""
    x, w, _ = _router_inputs(seed=1)
    none = jnp.zeros((16,), jnp.float32)
    lift = none.at[0].set(10.0)
    p0, i0 = moe.route(x, w, 4, renormalize=False, score="sigmoid",
                       bias=none)
    p1, i1 = moe.route(x, w, 4, renormalize=False, score="sigmoid",
                       bias=lift)
    assert not np.array_equal(np.asarray(i0), np.asarray(i1))
    assert np.all(np.asarray(i1)[:, 0] == 0)
    sc = jax.nn.sigmoid(x @ w)
    np.testing.assert_array_equal(
        np.asarray(p1), np.asarray(jnp.take_along_axis(sc, i1, -1)))
    assert float(p1.max()) < 1.0            # no 10.0 leaked in
    # a zero bias selects what no bias selects
    pn, i_n = moe.route(x, w, 4, renormalize=False, score="sigmoid")
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i_n))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(pn))


@pytest.mark.parametrize("renormalize", [True, False],
                         ids=["mixtral", "qwen"])
def test_route_softmax_is_bit_equal_to_the_parents(renormalize):
    """Mixtral's (renormalised) and Qwen's (raw) routing did not move
    by a bit when route learnt the sigmoid, the bias and the scale."""
    x, w, _ = _router_inputs(n=256, seed=2)
    for dtype in (jnp.float32, jnp.bfloat16):
        got = jax.jit(lambda a, b: moe.route(
            a, b, 4, renormalize=renormalize))(x.astype(dtype),
                                               w.astype(dtype))
        want = jax.jit(lambda a, b: _route_parent(
            a, b, 4, renormalize))(x.astype(dtype), w.astype(dtype))
        for g, t in zip(got, want):
            assert g.dtype == t.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(t))


def test_route_refuses_an_unknown_score():
    x, w, _ = _router_inputs()
    with pytest.raises(ValueError, match="router score"):
        moe.route(x, w, 2, score="tanh")


# ---------------------------------------------------------------------
# experts read in tiles (ops/moe.expert_tiles): where two slots of an
# expert's matrices miss the kernels' share of VMEM, the list and the
# grouped kernel take the expert in tiles of its intermediate width.
# ---------------------------------------------------------------------

GLM47 = (2048, 1536)           # GLM-4.7-Flash's experts [h, i]
GLM5 = (6144, 2048)            # GLM-5's
NEMOTRON = (2688, 1856)        # Nemotron-3-Nano's, as published
NEMOTRON_STORED = (2688, 1920)  # and as the stacks are stored


@pytest.mark.parametrize("widths,weights,share,tiles", [
    (QWEN, jnp.int8, 0.5, 1), (GLM47, jnp.int8, 0.5, 1),
    (QWEN, jnp.bfloat16, 0.5, 1), (GLM5, jnp.int8, 0.5, 2),
    (GLM5, jnp.bfloat16, 0.5, 4), (MIXTRAL, jnp.int8, 0.5, 0),
    # a smaller share (what the tests below set) tiles GLM-4.7-Flash's
    # 12 lanes of 128; Qwen's 11 split into no power of two
    (GLM47, jnp.int8, 0.2, 2), (GLM47, jnp.int8, 0.1, 4),
    (QWEN, jnp.int8, 0.2, 0), ((2048, 1400), jnp.int8, 0.5, 0),
    # Nemotron-3-Nano's: 1856 = 14.5 lanes tiles nowhere; stored 1920
    # wide the expert comes whole
    (NEMOTRON, jnp.int8, 0.5, 0), (NEMOTRON_STORED, jnp.int8, 0.5, 1)])
def test_expert_tiles_rule(monkeypatch, widths, weights, share, tiles):
    """The fewest equal tiles, a power of two of them and each whole
    lanes wide, of which two slots fit the kernels' share of VMEM."""
    monkeypatch.setattr(moe, "_LIST_VMEM_SHARE", share)
    assert moe.expert_tiles(*widths, weights, jnp.bfloat16) == tiles
    if tiles:
        h, i = widths
        assert moe.list_scratch_bytes(h, i // tiles, weights, jnp.bfloat16) \
            <= share * pallas_paged.VMEM_LIMIT_BYTES
        assert i % (tiles * 128) == 0


def test_moe_path_names_the_tiled_kernels(kernels_on):
    args = (jnp.int8, jnp.bfloat16)
    assert moe.moe_path(8, 1, 256, 8, *GLM5, *args) == "list_tiled2"
    assert moe.moe_path(1, 2048, 256, 8, *GLM5, *args) == "grouped_tiled2"
    assert moe.moe_path(16, 1, 64, 4, *GLM47, *args) == "list"
    assert moe.moe_path(1, 256, 60, 4, *QWEN, *args) == "grouped"
    assert moe.moe_path(4, 1, 8, 2, *MIXTRAL, *args) == "exact"
    # experts without a gate hold two matrices a slot, and
    # Nemotron-3-Nano's stored width runs both kernels, untiled
    assert moe.list_scratch_bytes(*GLM5, *args, gated=False) \
        == 6144 * 2048 * (4 + 2)
    assert moe.moe_path(8, 1, 128, 6, *NEMOTRON_STORED, *args,
                        gated=False) == "list"
    assert moe.moe_path(1, 2048, 128, 6, *NEMOTRON_STORED, *args,
                        gated=False) == "grouped"
    assert moe.moe_path(8, 1, 128, 6, *NEMOTRON, *args,
                        gated=False) == "exact"


@pytest.mark.parametrize("widths", [QWEN, GLM47], ids=["qwen", "glm47"])
@pytest.mark.parametrize("path", ["list", "grouped"])
def test_tiled_kernels_equal_the_untiled_ones(kernels_on, monkeypatch,
                                              path, widths):
    """Interpret mode at Qwen1.5-MoE's and GLM-4.7-Flash's widths, four
    int8 experts: the list and the grouped kernel reading an expert in
    2 and 4 tiles (a smaller share of VMEM makes the rule say so) give
    what they give reading it whole. Qwen's 1408 = 11 lanes split into
    no power of two, so the rule never tiles it: its kernels are the
    untiled ones at any share that admits them."""
    h, i = widths
    E, k, L = 4, 2, 1
    N, positions = (8, 1) if path == "list" else (160, 160)
    ks = jax.random.split(jax.random.PRNGKey(h + i), 5)
    x = jax.random.normal(ks[0], (N, h), jnp.float32).astype(jnp.bfloat16)
    rw = (jax.random.normal(ks[1], (h, E), jnp.float32) * 0.05
          ).astype(jnp.bfloat16)
    stacks = [quant.quantize_tensor(
        (jax.random.normal(kk, dims, jnp.float32) * 0.02
         ).astype(jnp.bfloat16))
        for kk, dims in zip(ks[2:], ((L, E, h, i), (L, E, h, i),
                                     (L, E, i, h)))]

    def run(share):
        monkeypatch.setattr(moe, "_LIST_VMEM_SHARE", share)
        tiles = moe.expert_tiles(h, i, jnp.int8, jnp.bfloat16)
        out, work = jax.jit(lambda x, *w: moe.moe_mlp(
            x, rw, *w, top_k=k, layer=jnp.int32(0), positions=positions,
            exact=True if path == "list" else None))(x, *stacks)
        return tiles, np.asarray(out.astype(jnp.float32)), work

    tiles, whole, work = run(0.5)
    assert tiles == 1
    if widths == QWEN:
        assert moe.expert_tiles(h, i // 2 * 2, jnp.int8, jnp.bfloat16) == 1
        monkeypatch.setattr(moe, "_LIST_VMEM_SHARE", 0.2)
        assert moe.expert_tiles(h, i, jnp.int8, jnp.bfloat16) == 0
        return
    scale = np.abs(whole).max()
    for share, want in ((0.2, 2), (0.1, 4)):
        tiles, tiled, tiled_work = run(share)
        assert tiles == want
        # the tiles' sums in float32, rounded to bfloat16 where the
        # whole expert's are (the grouped kernel: once a tile)
        assert np.abs(tiled - whole).max() <= 0.02 * scale
        assert int(tiled_work.experts_read) == int(work.experts_read)
        assert int(tiled_work.expert_rows) == int(work.expert_rows)


def test_the_held_table_tool_rehearses_on_the_cpu():
    """tools/moe_prefill_table.py --held at tiny widths, the kernels
    in interpret mode (a process of its own: the tool sets the block's
    shares and the kernels' switch for itself): a row for each block
    size and routing, whose rounds are what its kept assignments fill,
    and with the selection forced here more land here than as routed;
    the rows of the program's own block time a round's sum by token
    alone, the scatter-add beside the kernel, on the host's clock here
    and saying so, and the two agree to float32 reassociation."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "moe_prefill_table.py"),
         "--held", "--allow-cpu", "--repeat", "1", "--layers", "1",
         "--stack", "1", "--held-models", "glm5-share", "--sum-rounds", "2"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = json.loads(proc.stdout.strip().splitlines()[-1])["rows"]
    assert [(r["routing"], r["shares"]) for r in rows] == [
        (routing, shares) for routing in ("as_routed", "all_here")
        for shares in (1, 2, 4)]
    for r in rows:
        _, _, kept, rounds = r["work_first_layer"]
        assert rounds == -(-kept // r["block"]) and r["us"] > 0
    assert rows[3]["work_first_layer"][2] > rows[0]["work_first_layer"][2]
    for r in rows:
        assert ("sum_kernel_us" in r) is (r["shares"] == 2)
        if r["shares"] == 2:
            assert r["sum_clock"] == "host"
            assert r["sum_live"] == min(r["work_first_layer"][2], r["block"])
            assert r["sum_scatter_us"] > 0 and r["sum_kernel_us"] > 0
            assert r["sum_largest_difference"] <= 1e-6 * r["sum_largest"]
