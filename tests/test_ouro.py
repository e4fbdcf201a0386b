"""A looped model (Ouro, ``ouro``; LoopLM): the whole layer stack run
``loop_steps`` times on every token with the same weights, the final
norm closing each pass, each pass over pool layers of its own, an exit
gate on each pass's normed stream.

CPU, tiny sizes (``debug-ouro``: 3 layers run 4 times over 12 pool
layers), float32 at full matmul precision. The program's path through
the paged pool (prefill in chunks, then decode steps) is held to the
plain reference's full forward pass (chipbench/references/ouro.py, which
imports nothing of the program) to 1e-4; five references that each
depart in one place stand apart from it; the mapping from the published
keys, the counts (2 667 974 657 parameters, 192 pool layers, 1 572 864 B
a token) and every refusal; the engine: turnover and the counters, a
pool too small for its rows, an abort, and what the model runs with
(prefix caching, the KV connector, n-gram speculation, an int8 pool) or
refuses by name (a mesh, LoRA adapters).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import ouro as ref
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.runner import ModelRunner
from production_stack_tpu.engine.scheduler import SamplingOptions
from production_stack_tpu.models import hf_loader
from production_stack_tpu.models import kv as kv_pool
from production_stack_tpu.models import llama
from production_stack_tpu.models.config import ModelConfig, get_config

CFG = dataclasses.replace(get_config("debug-ouro"), dtype=jnp.float32)
# debug-ouro under the published keys, for the reference
HF = dict(model_type="ouro", hidden_size=128, num_hidden_layers=3,
          num_attention_heads=4, num_key_value_heads=4, head_dim=32,
          intermediate_size=384, hidden_act="silu", rms_norm_eps=1e-6,
          rope_theta=1000000.0, vocab_size=512, total_ut_steps=4,
          early_exit_threshold=1.0)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def worst(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def live_params(cfg=CFG, seed=3, quantization=None):
    """Seeded weights with every norm and the gate's bias moved off its
    initial value (a norm that ignored its weight would pass at one)."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed),
                               quantization=quantization)
    key = jax.random.PRNGKey(seed + 100)
    for name in ("attn_norm", "mlp_norm", "post_attn_norm",
                 "post_mlp_norm"):
        key, sub = jax.random.split(key)
        leaf = params["layers"][name]
        params["layers"][name] = leaf + 0.3 * jax.random.normal(
            sub, leaf.shape, leaf.dtype)
    key, sub = jax.random.split(key)
    params["final_norm"] = params["final_norm"] + 0.3 * jax.random.normal(
        sub, params["final_norm"].shape, params["final_norm"].dtype)
    params["exit_gate_bias"] = jnp.float32(-1.0)
    return params


TOKS = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (85,), 0,
                                     512)).tolist()


def _served_logprobs(params, toks, chunk=32, prefill_to=75, cfg=CFG,
                     work_out=None, kv_dtype=None):
    """Row 0 of a batch of two (row 1 parked): the prompt's first
    ``prefill_to`` tokens in chunks of ``chunk`` (the last one padded in
    its bucket), the rest as decode steps through the paged pool ->
    log-probabilities after every position [T, V]."""
    B, Bs, MB = 2, 8, 16
    cache = kv_pool.cache_for(cfg, B * MB + 1, Bs, kv_dtype or cfg.dtype)
    assert cache.k.shape[0] == cfg.num_layers * cfg.loop_steps
    tables = kv_pool.linear_tables(B, MB * Bs, Bs)
    fwd = jax.jit(lambda p, t, pos, c, tv: llama.forward(
        p, cfg, t, pos, c, block_tables=tables, token_valid=tv,
        kv_len=128))
    out = []
    for c0 in range(0, prefill_to, chunk):
        n = min(chunk, prefill_to - c0)
        t = np.zeros((B, chunk), np.int32)
        t[0, :n] = toks[c0:c0 + n]
        pos = np.stack([np.arange(chunk) + c0, np.arange(chunk) + 10000])
        tv = np.zeros((B, chunk), bool)
        tv[0, :n] = True
        logits, cache, work = fwd(params, jnp.asarray(t), jnp.asarray(pos),
                                  cache, jnp.asarray(tv))
        out.append(logits[0, :n])
        if work_out is not None:
            work_out.append(("prefill", n, jax.device_get(work)))
    for i in range(prefill_to, len(toks)):
        logits, cache, work = fwd(
            params, jnp.asarray([[toks[i]], [0]], jnp.int32),
            jnp.asarray([[i], [10000]]), cache,
            jnp.asarray([[True], [False]]))
        out.append(logits[0, :1])
        if work_out is not None:
            work_out.append(("decode", 1, jax.device_get(work)))
    return jax.nn.log_softmax(jnp.concatenate(out, 0), -1)


# ---------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------

@pytest.mark.parametrize("chunk,prefill_to", [(32, 75), (16, 41), (64, 64)],
                         ids=["two-boundaries-padded-tail",
                              "several-boundaries", "one-chunk"])
def test_prefill_in_chunks_then_decode_is_the_reference_forward(
        chunk, prefill_to):
    """Prefill in chunks (one and several boundaries, a padded tail),
    then decode steps through the 12-layer pool: the log-probabilities
    after every position are the reference's full forward pass (four
    full causal passes over the prompt, no cache) to 1e-4; a step's
    passes and exit mass are counted, and the shares of a token add up
    to one."""
    params = live_params()
    work = []
    got = _served_logprobs(params, TOKS, chunk, prefill_to, work_out=work)
    want = ref.logprobs(params, HF, [TOKS])[0]
    assert worst(got, want) < 1e-4
    _, lam = ref.hidden_states(params, HF, ref._padded([TOKS]))
    mass = ref.exit_mass(lam)[:, 0]                      # [4, T]
    assert worst(mass.sum(0), jnp.ones(mass.shape[1])) < 1e-5
    # what a forward counted: llama.Work, of a dense looped model its
    # ``loop`` member alone
    assert all(w._replace(loop=None) == llama.Work() for _, _, w in work)
    kind, n, last = work[-1]
    assert (kind, int(last.loop.passes_run), int(last.loop.row_steps)) \
        == ("decode", 4, 1)
    assert worst(last.loop.exit_mass, mass[:, len(TOKS) - 1]) < 1e-5
    kind, n, first = work[0]
    assert (int(first.loop.passes_run), int(first.loop.row_steps)) \
        == (4 * n, n)
    assert worst(first.loop.exit_mass, mass[:, :n].sum(1)) < 1e-3


def test_forward_train_loops_alike():
    params = live_params()
    got = jax.nn.log_softmax(llama.forward_train(
        params, CFG, jnp.asarray([TOKS], jnp.int32)), -1)[0]
    assert worst(got, ref.logprobs(params, HF, [TOKS])[0]) < 1e-4


def test_int8_weights_are_the_reference_on_the_same_leaves():
    """int8 leaves (q, k, v, o, gate, up, down, the embedding per row,
    the head); the four norms a layer, the final norm and the exit gate
    stay as they are."""
    params = live_params(quantization="int8")
    layers = params["layers"]
    for name in ("q", "k", "v", "o", "gate", "up", "down"):
        assert layers[name]["w8"].dtype == jnp.int8, name
    assert params["embed"]["w8"].dtype == params["lm_head"]["w8"].dtype \
        == jnp.int8
    assert params["embed"]["scale"].shape == (512,)
    for name in ("attn_norm", "mlp_norm", "post_attn_norm",
                 "post_mlp_norm"):
        assert layers[name].shape == (3, 128), name
    assert params["exit_gate"].dtype == jnp.float32
    assert params["exit_gate"].shape == (128,)
    got = _served_logprobs(params, TOKS[:60], prefill_to=50)
    assert worst(got, ref.logprobs(params, HF, [TOKS[:60]])[0]) < 1e-4


@pytest.mark.parametrize("breakage", [
    dict(total_ut_steps=3), dict(kv_control="first_pass"),
    dict(kv_control="last_pass"), dict(norm_control="off"),
    dict(sandwich_control="off")],
    ids=["three-passes", "every-pass-on-pass-0s-pool-layers",
         "the-last-passs-kv-for-all", "no-norm-between-passes",
         "no-sandwich-norms"])
def test_a_reference_that_departs_in_one_place_stands_apart(breakage):
    """What the comparison can see: each breakage moves the
    log-probabilities by a thousand times the tolerance or more."""
    params = live_params()
    got = _served_logprobs(params, TOKS[:60], prefill_to=50)
    broken = ref.logprobs(params, {**HF, **breakage}, [TOKS[:60]])[0]
    assert worst(got, broken) > 0.1


def test_a_lower_precision_stands_apart_too():
    params = live_params()
    want = ref.logprobs(params, HF, [TOKS[:60]])[0]
    fp8 = ref.logprobs(params, {**HF, "round_to": "float8_e4m3fn"},
                       [TOKS[:60]])[0]
    bf16 = ref.logprobs(params, {**HF, "round_to": "bfloat16"},
                        [TOKS[:60]])[0]
    assert worst(want, bf16) < worst(want, fp8) / 5
    assert worst(want, fp8) > 0.1


# ---------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------

def _catalog():
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Ouro-2.6B")["config"]


def test_the_mapping_reads_the_catalogs_keys():
    cfg = ModelConfig.from_hf_config(
        dict(_catalog(), architectures=["OuroForCausalLM"]), name="ouro")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim_, cfg.intermediate_size,
            cfg.vocab_size) == (48, 2048, 16, 16, 128, 5632, 49152)
    assert (cfg.loop_steps, cfg.exit_gate, cfg.sandwich_norms) \
        == (4, True, True)
    assert (cfg.rms_norm_offset, cfg.embed_scale, cfg.attn_logit_softcap,
            cfg.final_logit_softcap, cfg.query_pre_attn_scalar,
            cfg.sliding_window, cfg.rope_scaling, cfg.attention_bias,
            cfg.tie_word_embeddings) == (False, False, None, None, None,
                                         None, None, False, False)
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.activation,
            cfg.max_position_embeddings) == (1e6, 1e-6, "silu", 65536)
    # the mapping reads published keys alone: a benchmark file's
    # ``assumed`` block changes nothing of the model
    assert ModelConfig.from_hf_config(
        {**_catalog(), "assumed": {"sandwich_norm_init": 0.5}},
        name="ouro") == cfg
    # random weights only: a looped stack's sandwich norms start at
    # llama.LOOPED_SANDWICH_NORM_INIT, every other norm at one, and
    # Gemma-2's sandwich norms as its others
    layers = llama.init_params(CFG, jax.random.PRNGKey(0))["layers"]
    assert llama.LOOPED_SANDWICH_NORM_INIT == 0.1
    assert max(worst(layers["post_attn_norm"], 0.1),
               worst(layers["post_mlp_norm"], 0.1)) < 1e-6
    assert worst(layers["attn_norm"], 1.0) == 0 \
        == worst(layers["mlp_norm"], 1.0)
    gemma2 = llama.init_params(get_config("debug-gemma2"),
                               jax.random.PRNGKey(0))["layers"]
    assert worst(gemma2["post_attn_norm"], gemma2["attn_norm"]) == 0
    # the architecture alone names the family too
    by_arch = ModelConfig.from_hf_config(
        {k: v for k, v in _catalog().items() if k != "model_type"}
        | {"architectures": ["OuroForCausalLM"]})
    assert by_arch.loop_steps == 4


def test_the_counts_at_the_published_sizes():
    """48 x (4 x 2048 x 2048 + 3 x 2048 x 5632 + 4 x 2048) + 2 x 49152
    x 2048 + 2048 + 2049: a weight counts once, a pool layer a pass."""
    cfg = ModelConfig.from_hf_config(_catalog(), name="ouro")
    assert cfg.num_params == 2_667_974_657 == (
        48 * (4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048)
        + 2 * 49152 * 2048 + 2048 + 2049)
    assert (cfg.attn_layers, cfg.pool_layers, cfg.reader_layers) \
        == (48, 192, 192)
    cache = jax.eval_shape(lambda: kv_pool.cache_for(cfg, 3, 64))
    assert cache.k.shape == cache.v.shape == (192, 3, 16, 64, 128)
    assert kv_pool.KVCache.bytes_per_token.fget(cache) == 1_572_864
    params = jax.eval_shape(lambda: llama.init_params(
        cfg, jax.random.PRNGKey(0), quantization="int8"))
    held = sum(a.size for path, a in
               jax.tree_util.tree_leaves_with_path(params)
               if "scale" not in str(path))
    assert held == cfg.num_params
    # every other model: a pool layer a layer, one pass
    tiny = get_config("debug-tiny")
    assert (tiny.loop_steps, tiny.pool_layers) == (1, tiny.num_layers)
    # Gemma-2's sandwich norms count too (2.61 B published)
    assert get_config("gemma-2-2b").num_params == 2_614_341_888


@pytest.mark.parametrize("change,names", [
    (dict(early_exit_threshold=0.9), "early_exit_threshold"),
    (dict(sliding_window=4096, use_sliding_window=True),
     "sliding window"),
    (dict(layer_types=["full_attention", "sliding_attention"]),
     "layer_types"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(rope_scaling={"rope_type": "linear", "factor": 2.0}),
     "rope_scaling"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(num_key_value_heads=5), "num_key_value_heads"),
    (dict(total_ut_steps=0), "total_ut_steps")])
def test_the_mapping_refuses_what_the_tree_does_not_build(change, names):
    with pytest.raises(ValueError) as err:
        ModelConfig.from_hf_config({**_catalog(), **change})
    assert names in str(err.value) and "ouro" in str(err.value)


def test_grouped_kv_heads_are_built():
    """Grouped K/V heads other than the published 16 / 16 run the same
    path (the paged kernels' groups): 4 / 2 here."""
    cfg = dataclasses.replace(CFG, num_kv_heads=2)
    hf = dict(HF, num_key_value_heads=2)
    params = live_params(cfg)
    got = _served_logprobs(params, TOKS[:50], prefill_to=40, cfg=cfg)
    assert worst(got, ref.logprobs(params, hf, [TOKS[:50]])[0]) < 1e-4


def test_an_unknown_family_names_ouro_among_the_supported():
    with pytest.raises(ValueError, match="ouro"):
        ModelConfig.from_hf_config({"model_type": "nope"})


@pytest.mark.parametrize("kw", [
    dict(layer_pattern=("gdn", "attn")), dict(num_experts=4),
    dict(sliding_window=16), dict(kv_lora_rank=8),
    dict(rms_norm_offset=True), dict(tie_word_embeddings=True)],
    ids=["pattern", "experts", "window", "latent", "norm-offset", "tied"])
def test_a_loop_over_anything_but_dense_attention_layers_is_refused(kw):
    """What the pass loop does not apply (a norm's offset between the
    passes, a tied head's bytes) is refused with the rest, naming the
    key."""
    with pytest.raises(ValueError, match="looped model") as err:
        dataclasses.replace(CFG, **kw)
    if next(iter(kw)) in ("rms_norm_offset", "tie_word_embeddings"):
        assert next(iter(kw)) in str(err.value)


def _lowered(cfg, T=1):
    B, Bs, MB = 2, 16, 4
    params = jax.eval_shape(lambda: llama.init_params(
        cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: kv_pool.cache_for(cfg, B * MB + 1, Bs))
    ints = jax.ShapeDtypeStruct((B, T), jnp.int32)
    return jax.jit(lambda p, c, tb, t, pos: llama.forward(
        p, cfg, t, pos, c, block_tables=tb, kv_len=64)).lower(
        params, cache, jax.ShapeDtypeStruct((B, MB), jnp.int32), ints,
        ints).as_text()


def test_one_pass_is_the_plain_model_and_a_pass_is_traced_once():
    """``loop_steps`` 1 lowers to the text of the same model built
    without the field (no pass loop, no gate: what every other family
    lowered to before the loop was there stays, byte for byte: PERF.md
    section 6, PR 56); four passes and seven lower to texts with the
    same loops, one traced layer body and one traced pass each."""
    plain = {f.name: getattr(CFG, f.name)
             for f in dataclasses.fields(CFG)
             if f.name not in ("loop_steps", "exit_gate")}
    one = _lowered(dataclasses.replace(CFG, loop_steps=1, exit_gate=False))
    assert one == _lowered(ModelConfig(**plain))
    assert "loop_pass" not in one and one.count("stablehlo.while") == 1
    four, seven = (_lowered(dataclasses.replace(CFG, loop_steps=n))
                   for n in (4, 7))
    assert four.count("stablehlo.while") == 2 \
        == seven.count("stablehlo.while")
    assert four.count("stablehlo.dot_general") \
        == seven.count("stablehlo.dot_general")


def test_the_loader_maps_the_familys_tensor_names():
    """A made-up state dict under the published names
    (``input_layernorm_2``, ``post_attention_layernorm_2``,
    ``early_exit_gate``) loads to the tree that made it."""
    params = live_params()
    names = {"attn_norm": "input_layernorm", "q": "self_attn.q_proj",
             "k": "self_attn.k_proj", "v": "self_attn.v_proj",
             "o": "self_attn.o_proj",
             "post_attn_norm": "input_layernorm_2",
             "mlp_norm": "post_attention_layernorm",
             "gate": "mlp.gate_proj", "up": "mlp.up_proj",
             "down": "mlp.down_proj",
             "post_mlp_norm": "post_attention_layernorm_2"}
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "model.norm.weight": np.asarray(params["final_norm"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T,
          "model.early_exit_gate.weight":
              np.asarray(params["exit_gate"])[None, :],
          "model.early_exit_gate.bias":
              np.asarray(params["exit_gate_bias"])[None]}
    for ours, theirs in names.items():
        for i in range(CFG.num_layers):
            w = np.asarray(params["layers"][ours][i])
            sd[f"model.layers.{i}.{theirs}.weight"] = w.T if w.ndim == 2 \
                else w
    loaded = hf_loader.params_from_state_dict(CFG, sd)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.shape == b.shape and worst(a, b) == 0
    # the naming of the sandwich norms is the CHECKPOINT's, read off its
    # tensor names: the same state dict loads into a model without a
    # gate (which then takes no gate's leaves)
    gateless = hf_loader.params_from_state_dict(
        dataclasses.replace(CFG, loop_steps=1, exit_gate=False), sd)
    assert "exit_gate" not in gateless
    assert worst(gateless["layers"]["post_mlp_norm"],
                 params["layers"]["post_mlp_norm"]) == 0


# ---------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------

def _engine(**kw):
    cfg = dict(model="debug-ouro", max_num_seqs=4, max_model_len=256,
               kv_pool_tokens=1024, prefill_chunk=32, kv_block_size=8,
               dtype="float32", kv_dtype="float32", seed=3)
    return LLMEngine(EngineConfig(**{**cfg, **kw}))


def _run(eng, between=None, limit=900):
    for n in range(limit):
        if not eng.has_work:
            break
        eng.step()
        if between is not None:
            between(n)


PROMPTS = [list(map(int, np.random.default_rng(0).integers(0, 256, n)))
           for n in (150, 40, 90, 200, 33, 70)]
GREEDY = SamplingOptions(max_tokens=12, temperature=0.0, ignore_eos=True)
LONGER = SamplingOptions(max_tokens=40, temperature=0.0, ignore_eos=True)


@pytest.fixture(scope="module")
def alone():
    """Each prompt served alone: 40 tokens and their log-probabilities
    (some 10 s, booked to whichever engine test asks first: six
    requests through an engine of its own, four executables)."""
    out = {}
    with jax.default_matmul_precision("highest"):
        eng = _engine()
        for i, p in enumerate(PROMPTS):
            sid = eng.add_request(p, LONGER)
            _run(eng)
            seq = eng.seqs[sid]
            out[i] = (list(seq.output_tokens), list(seq.output_logprobs))
    return out


def _same(eng, sid, want, n=None):
    seq = eng.seqs[sid]
    n = n or len(seq.output_tokens)
    assert n and list(seq.output_tokens)[:n] == want[0][:n]
    assert np.allclose(seq.output_logprobs[:n], want[1][:n], atol=2e-3)


def test_the_engine_serves_the_reference(alone):
    """The engine's own path (scheduler, block manager, prefill in
    chunks of 32, decode windows): the log-probabilities and the greedy
    tokens of a 150-token prompt are the reference's."""
    params = _engine().runner.params
    lps = ref.logprobs(params, HF, [PROMPTS[0] + alone[0][0]])[0]
    n = len(PROMPTS[0])
    for j, (tok, lp) in enumerate(zip(*alone[0])):
        assert int(jnp.argmax(lps[n - 1 + j])) == tok
        assert abs(float(lps[n - 1 + j, tok]) - lp) < 1e-3


def test_turnover_and_the_counters(alone):
    """Six requests of different lengths through four slots read as
    they read alone; ``GET /debug/perf`` counts twelve pool layers for
    three weight layers, a token's bytes in all of them, four passes a
    row-step with an exit mass that adds up to one, and a step's bytes
    with the layers' weights four times. (Over 10 s on a loaded
    machine: two engines start and compile their executables.)"""
    eng = _engine()
    ids = [eng.add_request(p, GREEDY) for p in PROMPTS]
    _run(eng)
    for i, sid in enumerate(ids):
        _same(eng, sid, alone[i], 12)
    pool = eng.block_mgr.frag_report()
    assert (pool["layout"], pool["pool_layers"], pool["weight_layers"],
            pool["reader_layers"]) == ("kv_heads", 12, 3, 12)
    assert pool["bytes_per_token"] == 12 * 2 * 4 * 32 * 4
    loop = eng.device_report()["loop"]
    assert (loop["passes"], loop["weight_layers"], loop["pool_layers"]) \
        == (4, 3, 12)
    assert loop["row_steps"] > 0
    assert loop["passes_run"] == 4 * loop["row_steps"]
    assert abs(sum(loop["exit_mass"]) - 1.0) < 1e-4
    assert all(0 < m < 1 for m in loop["exit_mass"])
    totals = eng.eff.report()
    assert totals["kv_position_bytes"] == pool["bytes_per_token"]
    tree = eng.runner.params
    size = lambda t: sum(a.size * a.dtype.itemsize  # noqa: E731
                         for a in jax.tree.leaves(t))
    assert totals["step_bytes"] == {
        "weights": size(tree) + 3 * size(tree["layers"])
        - size(tree["lm_head"]), "head": size(tree["lm_head"]),
        "kv_per_position": pool["bytes_per_token"], "passes": 4}
    # a plain model reports neither block
    plain = LLMEngine(EngineConfig(model="debug-tiny", max_num_seqs=2,
                                   max_model_len=64))
    assert "loop" not in plain.device_report()
    assert "step_bytes" not in plain.eff.report()
    assert plain.block_mgr.frag_report()["weight_layers"] == 2


def test_a_pool_too_small_for_its_rows_preempts_and_resumes(alone):
    """Four rows whose contexts outgrow a pool of 45 blocks (360
    tokens): the youngest are preempted under pool pressure and
    recompute; every request's stream is what it was when served
    alone, and nothing is left allocated. (Over 10 s on a loaded
    machine: 160 tokens and their recomputation through four passes.)"""
    eng = _engine(kv_pool_tokens=360)
    ids = [eng.add_request(PROMPTS[i], LONGER) for i in (0, 2, 5, 1)]
    _run(eng)
    assert eng.metrics.preemptions._value.get() >= 1
    for i, sid in zip((0, 2, 5, 1), ids):
        assert len(eng.seqs[sid].output_tokens) == 40
        _same(eng, sid, alone[i])
    assert eng.block_mgr.active_blocks == 0


def test_an_abort_frees_its_blocks_and_moves_nobody(alone):
    eng = _engine()
    ids = [eng.add_request(PROMPTS[i], LONGER) for i in (3, 0, 2)]
    did = {}

    def between(n):
        if "abort" not in did and all(
                eng.seqs[s].output_tokens for s in ids):
            did["abort"] = True
            eng.abort(ids[1])

    _run(eng, between)
    assert did and len(eng.seqs[ids[1]].output_tokens) < 40
    for i, sid in zip((3, 2), (ids[0], ids[2])):
        _same(eng, sid, alone[i])
    assert eng.block_mgr.active_blocks == 0


def test_prefix_caching_over_every_pool_layer(alone):
    """A block id names a block of all 12 pool layers: a second request
    with the same prompt attaches the first's blocks and reads the
    same."""
    eng = _engine(enable_prefix_caching=True)
    first = eng.add_request(PROMPTS[0], GREEDY)
    _run(eng)
    second = eng.add_request(PROMPTS[0], GREEDY)
    _run(eng)
    assert eng.block_mgr.hits > 0
    for sid in (first, second):
        _same(eng, sid, alone[0], 12)


def test_the_kv_connector_carries_every_pool_layer(alone):
    """extract_chunk / inject_chunk over 12 pool layers: the second
    request's prompt comes back from the host tier and reads the same."""
    eng = _engine(kv_transfer_config={"local_cpu_gb": 0.25,
                                      "chunk_size": 32})
    try:
        assert eng.connector._chunk_shape[0] == 12
        first = eng.add_request(PROMPTS[0], GREEDY)
        _run(eng)
        eng.connector.flush()
        second = eng.add_request(PROMPTS[0], GREEDY)
        _run(eng)
        assert eng.connector.hit_tokens == 128
        for sid in (first, second):
            _same(eng, sid, alone[0], 12)
    finally:
        eng.close()


def test_ngram_speculation_reads_the_same(alone):
    """A verify forward of draft + 1 positions a row runs the passes as
    a prefill chunk does; a repetitive prompt accepts drafts."""
    prompt = (PROMPTS[1][:8] * 12)[:90]
    plain = _engine(window_adapt=False)
    sid = plain.add_request(prompt, LONGER)
    _run(plain)
    want = (list(plain.seqs[sid].output_tokens),
            list(plain.seqs[sid].output_logprobs))
    eng = _engine(speculative_ngram_tokens=3, window_adapt=False)
    got = eng.add_request(prompt, LONGER)
    other = eng.add_request(PROMPTS[0], LONGER)
    _run(eng)
    _same(eng, got, want)
    _same(eng, other, alone[0])
    loop = eng.device_report()["loop"]
    assert loop["passes_run"] == 4 * loop["row_steps"] > 0


def test_an_int8_pool_of_every_pool_layer():
    """The int8 pool (payload and scales, 12 layers): the served
    log-probabilities stay within int8's rounding of the reference.
    (Over 10 s on a loaded machine: the int8 pool's own executables,
    the library's and the engine's.)"""
    params = live_params()
    got = _served_logprobs(params, TOKS[:60], prefill_to=50,
                           kv_dtype=jnp.int8)
    want = ref.logprobs(params, HF, [TOKS[:60]])[0]
    assert 1e-6 < worst(got, want) < 0.1
    eng = _engine(kv_dtype="int8")
    assert eng.runner.cache.ks.shape[0] == 12
    assert eng.block_mgr.frag_report()["bytes_per_token"] \
        == 12 * 2 * 4 * (32 + 4)
    sid = eng.add_request(PROMPTS[1], GREEDY)
    _run(eng)
    assert len(eng.seqs[sid].output_tokens) == 12


def test_a_mesh_and_lora_are_refused_by_name():
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(dp=1, tp=2), jax.devices()[:2])
    with pytest.raises(ValueError, match="mesh.*looped model"):
        ModelRunner(get_config("debug-ouro"), EngineConfig(
            model="debug-ouro", max_num_seqs=2, max_model_len=128),
            mesh=mesh)
    with pytest.raises(ValueError, match="LoRA.*looped model"):
        ModelRunner(get_config("debug-ouro"), EngineConfig(
            model="debug-ouro", max_num_seqs=2, max_model_len=128,
            lora_adapters={"a": "random:1"}))
