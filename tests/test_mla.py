"""Latent attention (MLA), the layer plan and the latent pool at a tiny
GLM-4.7-Flash-shaped size on the CPU, against the benchmark's plain
reference (chipbench/references/glm4_moe_lite.py: expanded attention,
every expert evaluated, float32).

The program runs in float32 here, so that the comparison is tight
(TOLERANCE) and each planted fault of ``test_the_tolerance_sees`` falls
outside it; one case runs it in bfloat16, as served, against the
tolerance of tests/chipbench (TINY_TOLERANCE there).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import glm4_moe_lite as ref
from production_stack_tpu.models import kv as kv_pool
from production_stack_tpu.models import llama
from production_stack_tpu.models.config import ModelConfig, get_config
from production_stack_tpu.ops import moe, pallas_paged

BS, CHUNK = 16, 24       # KV block and prefill chunk of these tests
PROMPT, STEPS = 37, 8    # the prompt crosses two blocks and one chunk
# float32 program against float32 reference on the same weights: the
# two differ by the order of their sums (absorbed against expanded
# attention, a list of experts against all of them) and measure
# 1e-6 to 4e-6 on these logits; every fault below measures over 1e-3
TOLERANCE = 1e-4
# the program in bfloat16 (as served): tests/chipbench's TINY_TOLERANCE
SERVED_TOLERANCE = 0.02

# kv_lora_rank 96 is no other width of the model: the fault that skips
# the inner RMSNorm finds it by its width
CFG = dataclasses.replace(get_config("debug-mla"), kv_lora_rank=96,
                          dtype=jnp.float32)


def hf_of(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    return dict(
        num_attention_heads=cfg.num_heads, rms_norm_eps=cfg.rms_norm_eps,
        kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, n_routed_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        first_k_dense_replace=cfg.first_dense_layers,
        num_hidden_layers=cfg.num_layers)


def tokens_of(seed: int = 3) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, (1, PROMPT + STEPS))


def served_logprobs(cfg, params, tokens, pool_dtype=None) -> jnp.ndarray:
    """Log-probabilities after every position [T, V] as the serving
    path computes them: the prompt prefilled in chunks of CHUNK through
    the latent pool, then STEPS teacher-forced decode steps."""
    B, T = tokens.shape
    cache = kv_pool.cache_for(cfg, B * 4 + 1, BS,
                              pool_dtype or cfg.dtype)
    assert cache.layout == kv_pool.LATENT and cache.v is None
    tables = kv_pool.linear_tables(B, 4 * BS, BS)
    out = []
    spans = [(s, min(s + CHUNK, PROMPT)) for s in range(0, PROMPT, CHUNK)]
    spans += [(t, t + 1) for t in range(PROMPT, T)]
    for start, end in spans:
        pos = jnp.broadcast_to(jnp.arange(start, end), (B, end - start))
        logits, cache, _ = llama.forward(
            params, cfg, jnp.asarray(tokens[:, start:end]), pos, cache,
            block_tables=tables)
        out.append(logits[0])
    return jax.nn.log_softmax(jnp.concatenate(out).astype(jnp.float32))


def chunked_logprobs(cfg, params, tokens, chunk, padded=False,
                     tap=None) -> jnp.ndarray:
    """Log-probabilities after every position [T, V] of a prompt
    prefilled through the pool in chunks of ``chunk``; ``padded``: the
    last chunk padded to the chunk's length, its padding marked not
    valid, as the engine pads a chunk to its bucket. tap(start, logits)
    sees every chunk's forward."""
    B, T = tokens.shape
    MB = -(-(T + chunk) // BS)
    cache = kv_pool.cache_for(cfg, B * MB + 1, BS, cfg.dtype)
    tables = kv_pool.linear_tables(B, MB * BS, BS)
    out = []
    for start in range(0, T, chunk):
        real = min(chunk, T - start)
        n = chunk if padded else real
        toks = np.zeros((B, n), tokens.dtype)
        toks[:, :real] = tokens[:, start:start + real]
        logits, cache, _ = llama.forward(
            params, cfg, jnp.asarray(toks),
            jnp.broadcast_to(jnp.arange(start, start + n), (B, n)), cache,
            block_tables=tables,
            token_valid=jnp.broadcast_to(jnp.arange(n) < real, (B, n)))
        out.append(logits[0, :real])
    return jax.nn.log_softmax(jnp.concatenate(out).astype(jnp.float32))


def worst(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)))


@pytest.fixture
def kernels(request, monkeypatch):
    """Run the Pallas kernels (both paged kernels' latent case, the
    experts' list kernel) in interpret mode, or leave the CPU's
    jax.numpy paths."""
    monkeypatch.setattr(pallas_paged, "_override", request.param)
    return request.param


@pytest.mark.parametrize("kernels", [False, True], indirect=True,
                         ids=["jnp", "pallas_interpret"])
@pytest.mark.parametrize("weights", [None, "int8"], ids=["plain", "int8"])
def test_prefill_then_decode_agrees_with_the_reference(weights, kernels):
    """(a) two prefill chunks and eight decode steps through the latent
    pool against the reference's one full forward pass."""
    params = llama.init_params(CFG, jax.random.PRNGKey(1),
                               quantization=weights)
    tokens = tokens_of()
    if kernels:
        assert pallas_paged.attention_path(
            1, CFG.num_heads, 128, BS,
            value_dim=CFG.kv_lora_rank) == "pallas_paged_decode_latent"
        assert pallas_paged.attention_path(
            CHUNK, CFG.num_heads, 128, BS,
            value_dim=CFG.kv_lora_rank) == "pallas_paged_latent"
    got = served_logprobs(CFG, params, tokens)
    want = ref.logprobs(params, hf_of(CFG), tokens[0])
    assert worst(got, want) < TOLERANCE


def test_served_precision_agrees_with_the_reference():
    """(a) as served: bfloat16 activations and pool, int8 weights."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = llama.init_params(cfg, jax.random.PRNGKey(1),
                               quantization="int8")
    tokens = tokens_of()
    got = served_logprobs(cfg, params, tokens)
    want = ref.logprobs(params, hf_of(cfg), tokens[0])
    top = jnp.argsort(-want, axis=-1)[:, :20]
    assert worst(jnp.take_along_axis(got, top, -1),
                 jnp.take_along_axis(want, top, -1)) < SERVED_TOLERANCE


# a chunk of LONG positions a row is past the rule's cut at these
# widths (2 x 128 + 2 x 96 = 448 operations a query absorbed, 2 x 48 +
# 2 x 32 = 160 expanded and 2 x 96 x 64 = 12 288 a key once: 42.7),
# CHUNK (24) is under it
LONG = 48


def test_the_rule_expands_only_chunks_past_its_cut():
    """ops/pallas_paged.attention_path at GLM's widths (the pool 640
    wide, 512 of it the value; heads of 192 + 64 and 256): 256
    positions a row attend absorbed, 512 and 2048 expanded (the cut is
    358), with the selection's suffix behind either name; decode
    windows, a caller that gives no head widths, the kernels off and a
    pool of K and V never do."""
    glm = dict(value_dim=512, head_dims=(192, 64, 256))
    pallas_paged.set_flash_enabled(True)
    try:
        path = pallas_paged.attention_path
        assert [path(t, 64, 640, 64, **glm) for t in
                (1, 8, 256, 358, 359, 512, 2048)] == [
            "pallas_paged_decode_latent"] * 2 + [
            "pallas_paged_latent"] * 2 + [
            "pallas_paged_latent_expanded"] * 3
        assert path(2048, 64, 640, 64, selects=True, **glm) \
            == "pallas_paged_latent_expanded_sparse"
        assert path(256, 20, 640, 64, selects=True, **glm) \
            == "pallas_paged_latent_sparse"
        assert path(2048, 64, 640, 64, value_dim=512) \
            == "pallas_paged_latent"
        # K and V per kv head: the same call without a value_dim
        assert path(512, 4, 128, 64) == "pallas_paged"
        assert path(512, 4, 128, 64, head_dims=(192, 64, 256)) \
            == "pallas_paged"
        assert not pallas_paged.expanded_cheaper(358, 640, 512,
                                                 (192, 64, 256))
        assert pallas_paged.expanded_cheaper(359, 640, 512,
                                             (192, 64, 256))
        latents = jnp.zeros((1, 3, 1, 64, 640), jnp.bfloat16)
        assert kv_pool.expands(2048, 64, latents, 512, (192, 64, 256))
        assert not kv_pool.expands(256, 20, latents, 512, (192, 64, 256))
        pallas_paged.set_flash_enabled(False)
        assert path(2048, 64, 640, 64, **glm) == pallas_paged.JNP_GATHER
        assert not kv_pool.expands(2048, 64, latents, 512, (192, 64, 256))
    finally:
        pallas_paged.set_flash_enabled(None)


@pytest.mark.parametrize("kernels", [True], indirect=True,
                         ids=["pallas_interpret"])
@pytest.mark.parametrize("weights", [None, "int8"], ids=["plain", "int8"])
@pytest.mark.parametrize("padded", [False, True],
                         ids=["short-tail", "padded-tail"])
def test_expanded_chunks_agree_with_absorbed_and_the_reference(
        monkeypatch, kernels, weights, padded):
    """A prompt of 100 tokens prefilled in chunks of LONG through the
    latent pool: the chunks attend EXPANDED (the prefill kernel makes
    each head's keys and values from the cached latents), across block
    boundaries (16) and a chunk boundary, the third chunk's 4 tokens
    alone (the decode kernel, absorbed, over latents that expanded
    chunks cached) or padded to LONG (expanded, its padding not valid). Against the same
    prompt with the rule switched off (absorbed throughout) and against
    the reference's one full forward pass."""
    params = llama.init_params(CFG, jax.random.PRNGKey(4),
                               quantization=weights)
    tokens = np.random.default_rng(7).integers(0, CFG.vocab_size, (1, 100))
    dims = (CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.v_head_dim)
    assert [pallas_paged.attention_path(
        t, CFG.num_heads, 128, BS, value_dim=CFG.kv_lora_rank,
        head_dims=dims) for t in (CHUNK, LONG)] == [
            "pallas_paged_latent", "pallas_paged_latent_expanded"]
    calls = []
    kernel = pallas_paged.paged_attention

    def counted(*a, **kw):
        calls.append(kw.get("expand") is not None)
        return kernel(*a, **kw)
    monkeypatch.setattr(pallas_paged, "paged_attention", counted)
    expanded = chunked_logprobs(CFG, params, tokens, LONG, padded)
    # (a tail of 4 positions is a decode window's shape: that kernel)
    assert calls and all(calls)
    monkeypatch.setattr(pallas_paged, "expanded_cheaper",
                        lambda *a: False)
    jax.clear_caches()
    absorbed = chunked_logprobs(CFG, params, tokens, LONG, padded)
    assert worst(expanded, absorbed) < TOLERANCE
    want = ref.logprobs(params, hf_of(CFG), tokens[0])
    assert worst(expanded, want) < TOLERANCE


def test_absorbed_agrees_with_expanded_on_the_same_latents():
    """(b) the program's two forms: ``forward`` attends absorbed over
    the pool, ``forward_train`` expands keys and values per head."""
    params = llama.init_params(CFG, jax.random.PRNGKey(2),
                               quantization="int8")
    tokens = tokens_of(5)
    absorbed = served_logprobs(CFG, params, tokens)
    expanded = jax.nn.log_softmax(llama.forward_train(
        params, dataclasses.replace(CFG, moe_capacity_factor=4.0),
        jnp.asarray(tokens))[0])
    assert worst(absorbed, expanded) < TOLERANCE


def test_layer_plan_of_one_dense_and_three_expert_layers():
    """(d) layer 0 has a dense MLP and no router, layers 1-3 experts
    and no dense MLP, and scanned layer j writes pool layer j + 1."""
    cfg = dataclasses.replace(CFG, num_layers=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    dense, scanned = params["dense_layers"], params["layers"]
    assert "router" not in dense and "router_bias" not in dense
    assert dense["gate"].shape == (1, cfg.hidden_size,
                                   cfg.intermediate_size)
    assert scanned["router"].shape == (3, cfg.hidden_size, 8)
    assert scanned["gate"].shape == (3, 8, cfg.hidden_size,
                                     cfg.moe_intermediate_size)
    assert float(jnp.abs(scanned["router_bias"]).max()) > 0.01
    assert "s_gate_w" not in scanned
    assert cfg.num_params == sum(
        x.size for x in jax.tree.leaves(params))

    written = []
    real = kv_pool.append

    def recording(pool, k, v, tables, starts, valid, layer, **kw):
        written.append(int(layer))
        return real(pool, k, v, tables, starts, valid, layer, **kw)

    tokens = jnp.asarray(tokens_of()[:, :5])
    cache = kv_pool.cache_for(cfg, 5, BS, cfg.dtype)
    with pytest.MonkeyPatch.context() as mp, jax.disable_jit():
        mp.setattr(kv_pool, "append", recording)
        _, cache, read = llama.forward(
            params, cfg, tokens, jnp.arange(5)[None], cache,
            block_tables=kv_pool.linear_tables(1, 4 * BS, BS))
    assert written == [0, 1, 2, 3]
    assert int(read.experts_read) == 3 * 8           # three expert layers, all read
    # every layer's block 1 holds five written vectors and nothing else
    filled = jnp.any(cache.k[:, 1, 0] != 0, axis=-1)        # [L, Bs]
    assert filled.tolist() == [[True] * 5 + [False] * (BS - 5)] * 4
    used = cfg.latent_dim
    assert cache.k.shape[-1] == 128 and not jnp.any(cache.k[..., used:])


REAL_ROUTE = moe.route


def _leaky_route(x, w, k, renormalize=True, score="softmax", bias=None,
                 scale=1.0):
    """The bias added to the WEIGHTS too."""
    _, top_i = REAL_ROUTE(x, w, k, score=score, bias=bias)
    scores = jax.nn.sigmoid(jnp.einsum(
        "nh,he->ne", x, w, preferred_element_type=jnp.float32)) + bias
    top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    return (scale * top_p / (jnp.sum(top_p, -1, keepdims=True) + 1e-20),
            top_i)


def _no_inner_norm(x, w, eps, offset=0.0):
    if x.shape[-1] == CFG.kv_lora_rank:
        return x
    return _no_inner_norm.real(x, w, eps, offset=offset)


FAULTS = ["bf16_latents", "no_inner_rmsnorm", "bias_in_the_weights",
          "no_routing_scale", "gated_shared_expert"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_tolerance_sees(fault, monkeypatch):
    """(e) each fault planted in the served path in turn puts it
    outside the tolerance of (a)."""
    cfg, pool_dtype = CFG, None
    params = llama.init_params(cfg, jax.random.PRNGKey(1),
                               quantization="int8")
    if fault == "bf16_latents":
        pool_dtype = jnp.bfloat16
    elif fault == "no_inner_rmsnorm":
        _no_inner_norm.real = llama.rms_norm
        monkeypatch.setattr(llama, "rms_norm", _no_inner_norm)
    elif fault == "bias_in_the_weights":
        monkeypatch.setattr(moe, "route", _leaky_route)
    elif fault == "no_routing_scale":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif fault == "gated_shared_expert":
        cfg = dataclasses.replace(cfg, shared_expert_gate=True)
        layers = dict(params["layers"])
        layers["s_gate_w"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(9), (cfg.num_layers - 1,
                                    cfg.hidden_size, 1))
        params = {**params, "layers": layers}
    tokens = tokens_of()
    got = served_logprobs(cfg, params, tokens, pool_dtype)
    want = ref.logprobs(params, hf_of(CFG), tokens[0])
    assert worst(got, want) > 10 * TOLERANCE


# ---------------------------------------------------------------------
# what assumes K and V per head refuses the latent layout by name
# ---------------------------------------------------------------------

def _tiny_runner(**engine):
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.runner import ModelRunner
    ecfg = EngineConfig(model="debug-mla", max_num_seqs=2,
                        max_model_len=64, kv_block_size=16, **engine)
    return ModelRunner(get_config("debug-mla"), ecfg)


def test_int8_kv_cache_refuses_the_latent_pool():
    with pytest.raises(ValueError, match="latent.*no int8 form"):
        _tiny_runner(kv_dtype="int8")


def test_chunk_transfer_refuses_the_latent_pool():
    runner = _tiny_runner()
    assert runner.cache.layout == "latent"
    assert runner.cache.bytes_per_token == 3 * 256 * 2
    with pytest.raises(ValueError, match="extract_chunk.*'latent'"):
        runner.extract_chunk(0, 0, 16)
    with pytest.raises(ValueError, match="inject_chunk.*'latent'"):
        runner.inject_chunk(0, 0, np.zeros((3, 16, 1, 48)),
                            np.zeros((3, 16, 1, 48)))


def test_kv_connector_refuses_the_latent_pool():
    from production_stack_tpu.kvcache.connector import (KVConnector,
                                                        KVTransferConfig)
    runner = _tiny_runner()
    tcfg = KVTransferConfig.from_dict({"kv_role": "kv_both",
                                       "local_cpu_gb": 0.01})
    with pytest.raises(ValueError, match="KV transfer.*'latent'"):
        KVConnector(runner, runner.model_cfg, runner.engine_cfg, tcfg)


def test_a_sharding_mesh_refuses_the_latent_model():
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(tp=2), jax.devices()[:2])
    with pytest.raises(ValueError, match="one chip only"):
        ModelRunner(get_config("debug-mla"),
                    EngineConfig(model="debug-mla", max_num_seqs=2,
                                 max_model_len=64, kv_block_size=16),
                    mesh=mesh)


def test_hf_loader_refuses_the_family_by_name():
    from production_stack_tpu.models import hf_loader
    with pytest.raises(NotImplementedError, match="glm4_moe_lite"):
        hf_loader.params_from_state_dict(get_config("debug-mla"), {})


def test_from_hf_config_maps_glm4_moe_lite():
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "chipbench", "configs",
                           "glm-4.7-flash-int8-l13.json")) as f:
        conf = json.load(f)
    cfg = ModelConfig.from_hf_config(dict(conf), name="glm-4.7-flash")
    # the preset draws every leaf at one sd; the benchmark's file
    # states another for the routed experts' output projection
    assert get_config("glm-4.7-flash").routed_down_init_std is None
    assert cfg == dataclasses.replace(
        get_config("glm-4.7-flash"), num_layers=13,
        routed_down_init_std=conf["assumed"]["routed_down_init_std"])
    # ISSUE 35's arithmetic: 8.34 B at 13 layers, 29.9 B whole
    assert round(cfg.num_params / 1e9, 2) == 8.34
    assert round(get_config("glm-4.7-flash").num_params / 1e9, 1) == 29.9
    assert (cfg.latent_dim, kv_pool.latent_pool_width(cfg.latent_dim)
            ) == (576, 640)
    for key, value in (("n_group", 2), ("topk_method", "greedy"),
                       ("q_lora_rank", None), ("attention_bias", True)):
        with pytest.raises(ValueError, match="glm4_moe_lite"):
            ModelConfig.from_hf_config({**conf, key: value})


@pytest.mark.parametrize("stated", [None, 0.004])
def test_init_draws_the_routed_down_projection_at_the_stated_sd(stated):
    """One sd, 0.02, for every weight, unless the configuration states
    one for the routed experts' output projection: that leaf alone
    follows it."""
    cfg = dataclasses.replace(get_config("debug-mla"), dtype=jnp.float32,
                              routed_down_init_std=stated)
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    sd = jax.tree.map(lambda a: float(jnp.std(a)), params)
    assert sd["layers"]["down"] == pytest.approx(stated or 0.02, rel=0.05)
    for group, leaf in (("layers", "gate"), ("layers", "up"),
                        ("layers", "s_down"), ("layers", "o"),
                        ("dense_layers", "down")):
        assert sd[group][leaf] == pytest.approx(0.02, rel=0.05)
