"""Nemotron-H (``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B): blocks
that are ONE sublayer each (a Mamba-2 mixer over state pages, grouped-
query attention with no rotary embedding over a K/V pool of the
attention blocks alone, or the expert layer: ungated relu^2 experts
behind a sigmoid router, stored wider than published), on the CPU at
tiny sizes with seeded weights (``debug-nemotron``: hidden 128, 12
blocks M E M * E M E M * E M E in three runs, Mamba-2 of 8 heads of 32
in 2 groups with a state of 16, 4 / 2 attention heads of 32, 8 experts
top-3 of width 48 stored at 128, block 8, chunk 32).

- the model through both caches (prefill in several chunks with a
  padded last one, then decode steps beside a parked row) against the
  full forward pass of chipbench/references/nemotron_h.py (the
  recurrence a token at a time), logits not tokens, float32, to 1e-4;
- the share test: the four shares of a router's experts, the shared
  expert counted once, add up to the uncut layer of the reference;
- the kernels (interpret mode: ops/mamba2.py's two, ops/moe.py's list
  and grouped ones on experts without a gate) serve what the
  ``jax.numpy`` forms do, from inside a plan run;
- the engine: turnover, a preemption with recompute, an abort, the
  counters of ``GET /debug/perf``;
- every refusal by name; the configuration's mapping from the catalog's
  keys; the runs the pattern is spelled in.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import nemotron_h as ref
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.runner import ModelRunner
from production_stack_tpu.engine.scheduler import SamplingOptions
from production_stack_tpu.models import kv as kv_pool
from production_stack_tpu.models import llama
from production_stack_tpu.models.config import (ModelConfig, get_config,
                                                plan_runs)
from production_stack_tpu.ops import moe

CFG = dataclasses.replace(get_config("debug-nemotron"), dtype=jnp.float32)
PATTERN = "MEM*EMEM*EME"
# debug-nemotron under the published keys, for the reference
HF = dict(model_type="nemotron_h", hybrid_override_pattern=PATTERN,
          num_hidden_layers=12, hidden_size=128, mamba_num_heads=8,
          mamba_head_dim=32, n_groups=2, ssm_state_size=16, conv_kernel=4,
          num_attention_heads=4, num_key_value_heads=2, head_dim=32,
          n_routed_experts=8, num_experts_per_tok=3,
          moe_intermediate_size=48,
          moe_shared_expert_intermediate_size=96, n_shared_experts=1,
          norm_topk_prob=True, routed_scaling_factor=2.5,
          layer_norm_epsilon=1e-5, vocab_size=512)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def worst(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def live_params(cfg=CFG, seed=3, quantization=None):
    """Seeded weights with every norm, bias and skip term moved off its
    initial value (a norm that ignored its weight, or a ``D x`` never
    added, would pass at the initialisation)."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed),
                               quantization=quantization)
    key = jax.random.PRNGKey(seed + 100)
    out = {}
    for group, tree in params.items():
        if not isinstance(tree, dict) or "w8" in tree:
            tree = {None: tree}
        new = {}
        for name, leaf in tree.items():
            small = (not isinstance(leaf, dict) and leaf.ndim <= 2
                     and name != "A_log"
                     and group not in ("embed", "lm_head"))
            if small:
                key, sub = jax.random.split(key)
                leaf = leaf + 0.2 * jax.random.normal(sub, leaf.shape,
                                                      leaf.dtype)
            new[name] = leaf
        out[group] = new[None] if None in new else new
    return out


def _tables(B, MB):
    return jnp.concatenate(
        [1 + jnp.arange(B * MB).reshape(B, MB),
         jnp.array([[2], [1]])[:B]], axis=1).astype(jnp.int32)


def _served_logprobs(params, toks, chunk=32, prefill_to=75, cfg=CFG,
                     work_out=None):
    """Row 0 of a batch of two (row 1 parked): the prompt's first
    ``prefill_to`` tokens in chunks of ``chunk`` (the last one padded
    in its bucket), the rest as decode steps -> log-probabilities after
    every position [T, V]."""
    B, Bs, MB = 2, 8, 32
    T = len(toks)
    cache = kv_pool.cache_for(cfg, B * MB + 1, Bs, cfg.dtype, state_pages=3)
    tables = _tables(B, MB)
    fwd = jax.jit(lambda p, t, pos, c, tv: llama.forward(
        p, cfg, t, pos, c, block_tables=tables, token_valid=tv,
        kv_len=256))
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
    for i in range(prefill_to, T):
        logits, cache, work = fwd(
            params, jnp.asarray([[toks[i]], [0]], jnp.int32),
            jnp.asarray([[i], [10000]]), cache,
            jnp.asarray([[True], [False]]))
        out.append(logits[0, :1])
        if work_out is not None:
            work_out.append(("decode", 1, jax.device_get(work)))
    # the parked row wrote the trash page alone
    assert float(jnp.abs(cache.state[:, 1]).max()) == 0
    assert float(jnp.abs(cache.state[:, 2]).max()) > 0
    return jax.nn.log_softmax(jnp.concatenate(out, 0), -1)


TOKS = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (85,), 0,
                                     512)).tolist()


@pytest.mark.parametrize("chunk,prefill_to", [(32, 75), (16, 41), (64, 64)],
                         ids=["two-boundaries-padded-tail", "several",
                              "one-chunk"])
def test_prefill_in_chunks_then_decode_is_the_reference_forward(
        chunk, prefill_to):
    """85 tokens: whole chunks, a chunk padded in its bucket after a
    carried state (every chunk shorter than the scan's 128: the kernels'
    own chunk boundaries are tests/test_mamba2.py's), then decode steps,
    against the reference's ONE pass (the recurrence token by token, a
    full softmax under a mask, every expert of the router). 1e-4 on a
    log-probability: float32 against float32 (3e-6 seen); the reference
    with its activations rounded to bfloat16 between blocks stands over
    30 times farther, so a bfloat16 product anywhere on the served path
    would show."""
    params = live_params()
    got = _served_logprobs(params, TOKS, chunk, prefill_to)
    want = ref.logprobs(params, HF, TOKS)
    assert worst(got, want) < 1e-4
    rounded = ref.logprobs(params, {**HF, "round_to": "bfloat16"}, TOKS)
    assert worst(rounded, want) > 30 * 1e-4


def test_int8_weights_are_the_reference_on_the_same_leaves():
    params = live_params(quantization="int8")
    for group, name in (("mamba2_layers", "in_proj"),
                        ("mamba2_layers", "out_proj"), ("gqa_layers", "q"),
                        ("moe_layers", "up"), ("moe_layers", "s_down")):
        assert set(params[group][name]) == {"w8", "scale"}
    for group, name in (("mamba2_layers", "conv"), ("moe_layers", "router"),
                        ("mamba2_layers", "gate_norm"), ("layers", "norm")):
        assert not isinstance(params[group][name], dict)
    got = _served_logprobs(params, TOKS[:50], prefill_to=44)
    assert worst(got, ref.logprobs(params, HF, TOKS[:50])) < 1e-4


@pytest.mark.parametrize("breakage", [
    {"gate_control": "off"}, {"skip_control": "off"},
    {"routed_scaling_factor": 1.0}, {"num_experts_per_tok": 2}])
def test_a_reference_that_departs_in_one_place_stands_apart(breakage):
    """What the chip check's ``lean`` switches off, one at a time (the
    gated norm's gate, ``D x``, the routing scale, an expert a token):
    each moves the log-probabilities twenty times the tolerance and
    more."""
    params = live_params()
    want = ref.logprobs(params, HF, TOKS)
    assert worst(ref.logprobs(params, {**HF, **breakage}, TOKS),
                 want) > 20 * 1e-4


def test_the_stored_width_is_zero_beyond_the_published_one():
    """Experts of 48 stored 128 wide: zero columns of ``up``, zero rows
    of ``down`` (relu(0)^2 = 0), in float32 and under int8 alike; the
    tree holds num_params parameters and the padding."""
    for quantization in (None, "int8"):
        params = llama.init_params(CFG, jax.random.PRNGKey(0),
                                   quantization=quantization)
        up, down = (params["moe_layers"][n] for n in ("up", "down"))
        if quantization:
            up, down = up["w8"], down["w8"]
        assert up.shape == (5, 8, 128, 128) == down.shape
        assert float(jnp.abs(up[..., 48:]).max()) == 0
        assert float(jnp.abs(down[..., 48:, :]).max()) == 0
        assert float(jnp.abs(up[..., :48]).max()) > 0
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    padding = 5 * 8 * 2 * 128 * (128 - 48)
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == CFG.num_params + padding


def test_the_family_is_read_off_the_plans_kinds():
    """What says "every block is ONE sublayer" is the plan's kinds, not
    the Mamba-2 mixer's geometry: a plan with no Mamba-2 block is still
    counted and built a sublayer a block, a two-part plan that sets the
    geometry is not, and a plan that mixes the two is refused."""
    assert CFG.sublayer_plan
    assert not get_config("debug-yoco").sublayer_plan
    assert not get_config("debug-tiny").sublayer_plan
    bare = dataclasses.replace(
        CFG, num_layers=4, mamba_heads=0,
        layer_plan=((("attn", "moe"), 2),))
    assert bare.sublayer_plan and bare.moe_stored_size == 128
    params = llama.init_params(bare, jax.random.PRNGKey(0))
    assert "gqa_layers" in params and "mamba2_layers" in params
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == bare.num_params + 2 * 8 * 2 * 128 * (128 - 48)
    two_part = dataclasses.replace(get_config("debug-yoco"),
                                   mamba_heads=8)
    assert two_part.num_params == get_config("debug-yoco").num_params
    assert two_part.moe_stored_size == two_part.intermediate_size
    with pytest.raises(ValueError, match="mixes.*mamba2.*swa"):
        dataclasses.replace(
            get_config("debug-yoco"),
            layer_plan=((("mamba2", "swa"), 1),))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: a router over 16 experts, four chips holding 4
    each (offsets 0, 4, 8, 12). What the program's expert layer gives
    for each share (ops/moe.moe_mlp with ``expert_offset``: the held
    experts' part alone), summed, with the shared expert counted ONCE,
    is what the reference's uncut 16-expert layer gives."""
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 8)
    N, h, R, mi, si, k = 24, 128, 16, 48, 96, 3
    u = jax.random.normal(ks[0], (N, h), jnp.float32)
    lp = {"router": 0.3 * jax.random.normal(ks[1], (h, R)),
          "router_bias": 0.1 * jax.random.normal(ks[2], (R,)),
          "up": 0.1 * jax.random.normal(ks[3], (R, h, mi)),
          "down": 0.1 * jax.random.normal(ks[4], (R, mi, h)),
          "s_up": 0.1 * jax.random.normal(ks[5], (h, si)),
          "s_down": 0.1 * jax.random.normal(ks[6], (si, h))}
    hf = {**HF, "n_routed_experts": R, "num_experts_per_tok": k}
    whole = ref.routed(hf, lp, u) + ref.shared(lp, u)
    parts = 0.0
    for chip in range(4):
        held = slice(4 * chip, 4 * chip + 4)
        y, work = moe.moe_mlp(
            u, lp["router"], None, lp["up"][held], lp["down"][held],
            top_k=k, act=moe.relu2, exact=True, router_score="sigmoid",
            router_bias=lp["router_bias"], routed_scale=2.5,
            expert_offset=4 * chip)
        parts = parts + y
        # and the reference handed the same share says the same
        share = {**lp, "up": lp["up"][held], "down": lp["down"][held]}
        assert worst(y, ref.routed(hf, share, u, 4 * chip)) < 1e-5
    assert worst(parts + ref.shared(lp, u), whole) < 1e-5
    assert float(jnp.abs(whole).max()) > 0.1


@pytest.fixture
def kernels_on(monkeypatch):
    monkeypatch.setenv("PSTPU_FLASH", "1")


def test_the_kernels_serve_what_the_jnp_forms_do(kernels_on):
    """The same model, its state 128 wide as published (a group's B and
    C whole vectors of lanes: what ops/mamba2.py's kernels tile), with
    the Pallas kernels in interpret mode: both of ops/mamba2.py's (a
    prefill chunk's mixer between its projections ONE kernel, across a
    dispatch boundary and a padded tail), and ops/moe.py's list and
    grouped kernels on experts WITHOUT a gate, their stacks read in
    place from inside a plan run (the attention heads of 32 are not
    whole lanes: the paged kernels stay off, tests/test_pallas_paged.py
    holds them). Against the reference, and the experts' work counted
    from inside the run: a decode step reads at most top-3 experts a
    layer in 5 layers, a chunk multiplies whole passes of 128 rows. The
    debug preset's own state of 16 is a shape the kernels refuse: it
    runs the ``jax.numpy`` forms, kernels or no."""
    from production_stack_tpu.ops import mamba2
    assert mamba2.mamba2_path(1, 256, 8, 2, 16) == mamba2.RECURRENT + "_jnp"
    assert mamba2.mamba2_path(32, 256, 8, 2, 16) == "mamba2_chunk_scan_jnp"
    cfg = dataclasses.replace(CFG, mamba_d_state=128)
    assert mamba2.mamba2_path(1, 256, 8, 2, 128) == mamba2.RECURRENT
    assert mamba2.mamba2_path(32, 256, 8, 2, 128) == mamba2.CHUNKED
    assert moe.moe_path(2, 1, 8, 3, 128, 128, jnp.float32, jnp.float32,
                        gated=False) == "list"
    assert moe.moe_path(2, 32, 8, 3, 128, 128, jnp.float32, jnp.float32,
                        gated=False) == "grouped"
    params = live_params(cfg)
    work = []
    got = _served_logprobs(params, TOKS[:50], prefill_to=44, cfg=cfg,
                           work_out=work)
    assert worst(got, ref.logprobs(
        params, {**HF, "ssm_state_size": 128}, TOKS[:50])) < 1e-4
    for kind, n, w in work:
        if kind == "decode":
            assert 5 <= int(w.experts_read) <= 5 * 3
            assert int(w.expert_rows) == int(w.experts_read) * 2
        else:
            assert 5 <= int(w.experts_read) <= 5 * 8
            assert int(w.expert_rows) % 128 == 0
            assert int(w.expert_rows) >= 128 * int(w.experts_read)


def test_a_share_of_the_experts_inside_a_plan_run(kernels_on):
    """One of two chips' share (4 of the router's 8, from expert 4 on)
    served through the kernels (the grouped path's rounds and
    ``moe_held_sum``, PR 53's, from inside a plan run) against the
    reference handed the same share; ``Work`` counts the rounds."""
    cfg = dataclasses.replace(CFG, num_experts=4, router_experts=8,
                              expert_offset=4)
    hf = {**HF, "n_routed_experts": 4,
          "deployment": {"chips_per_layer": 2, "chip_index": 1,
                         "router_experts": 8}}
    params = live_params(cfg)
    assert params["moe_layers"]["router"].shape == (5, 128, 8)
    assert params["moe_layers"]["up"].shape == (5, 4, 128, 128)
    work = []
    got = _served_logprobs(params, TOKS[:50], prefill_to=44, cfg=cfg,
                           work_out=work)
    assert worst(got, ref.logprobs(params, hf, TOKS[:50])) < 1e-4
    prefill = [w for kind, _, w in work if kind == "prefill"]
    assert all(int(w.rounds) >= 1 and int(w.held_rows) > 0
               for w in prefill)
    # about half of 32 x 3 assignments a layer land on the share
    assert 0 < int(prefill[0].held_rows) < 5 * 32 * 3


# ---------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------

def _engine(**kw):
    cfg = dict(model="debug-nemotron", max_num_seqs=4, max_model_len=256,
               kv_pool_tokens=1024, prefill_chunk=32, kv_block_size=8,
               dtype="float32", kv_dtype="float32", seed=3)
    return LLMEngine(EngineConfig(**{**cfg, **kw}))


def _run(eng, between=None, limit=600):
    for n in range(limit):
        if not eng.has_work:
            break
        eng.step()
        if between is not None:
            between(n)


PROMPTS = [list(map(int, np.random.default_rng(0).integers(0, 256, n)))
           for n in (150, 40, 90, 200, 33, 70)]
GREEDY = SamplingOptions(max_tokens=12, temperature=0.0, ignore_eos=True)


@pytest.fixture(scope="module")
def alone():
    """Each prompt served alone: its tokens and log-probabilities."""
    out = {}
    with jax.default_matmul_precision("highest"):
        eng = _engine()
        for i, p in enumerate(PROMPTS):
            sid = eng.add_request(p, GREEDY)
            _run(eng)
            seq = eng.seqs[sid]
            out[i] = (list(seq.output_tokens), list(seq.output_logprobs))
    return out


def _same(eng, sid, want):
    seq = eng.seqs[sid]
    n = len(want[0])
    assert list(seq.output_tokens)[:n] == want[0]
    assert np.allclose(seq.output_logprobs[:n], want[1], atol=2e-3)


def test_the_engine_serves_the_reference(alone):
    """The engine's own path (scheduler, block manager, state pages,
    prefill in chunks of 32, decode windows): the log-probabilities and
    the greedy tokens of a 150-token prompt are the reference's."""
    params = _engine().runner.params
    lps = ref.logprobs(params, HF, PROMPTS[0] + alone[0][0])
    n = len(PROMPTS[0])
    for j, (tok, lp) in enumerate(zip(*alone[0])):
        assert int(jnp.argmax(lps[n - 1 + j])) == tok
        assert abs(float(lps[n - 1 + j, tok]) - lp) < 1e-3


def test_turnover_and_the_counters(alone):
    """Six requests of different lengths through four slots and four
    pages read as they read alone; ``GET /debug/perf`` counts two pool
    layers for twelve blocks, the page's bytes, the mixers' and the
    experts' paths an executable, and the experts' work from inside
    the plan run."""
    eng = _engine()
    ids = [eng.add_request(p, GREEDY) for p in PROMPTS]
    _run(eng)
    for i, sid in enumerate(ids):
        _same(eng, sid, alone[i])
    pool = eng.block_mgr.frag_report()
    assert pool["layout"] == "kv+state"
    assert pool["pool_layers"] == 2
    assert pool["state_pages"] == {"total": 4, "live": 0}
    # (a float32 pool here: the convolution's inputs take 4 bytes)
    assert pool["state_bytes_per_slot"] \
        == eng.runner.cache.state_bytes_per_slot == 5 * (
            4 * 16 * 256 + 4 * 3 * 320)
    assert pool["bytes_per_token"] == 2 * 2 * 2 * 32 * 4
    totals = eng.eff.report()
    assert totals["state"]["scan_tokens"] == sum(map(len, PROMPTS))
    # every real prompt token chose 3 experts in each of 5 layers
    assert totals["prefill"]["routed_rows"] \
        == sum(map(len, PROMPTS)) * 3 * 5
    assert totals["moe"]["experts_resident"] \
        == totals["state"]["steps"] * 5 * 8
    assert 0 < totals["moe"]["experts_read"] \
        <= totals["moe"]["experts_resident"]
    device = eng.device_report()
    paths = device["mixer_paths"]
    assert {v for k, v in paths.items() if k.startswith("decode")} \
        == {"mamba2_recurrent_step_jnp"}
    assert {v for k, v in paths.items() if k.startswith("prefill")} \
        == {"mamba2_chunk_scan_jnp"}
    assert set(paths) == set(device["attention_paths"]) \
        == set(device["moe_paths"])
    assert set(device["moe_paths"].values()) <= {"exact", "dispatch"}


def test_a_preemption_and_a_resume_change_nothing(alone):
    """A running sequence is preempted (blocks and page go back; it
    recomputes from position 0 into whatever page it is handed next):
    it reads as it read alone."""
    eng = _engine()
    longer = SamplingOptions(max_tokens=60, temperature=0.0,
                             ignore_eos=True)
    ids = [eng.add_request(PROMPTS[i], longer) for i in (3, 0, 2)]
    did = {}

    def between(n):
        running = sorted(eng.scheduler.running.values(),
                         key=lambda s: s.slot)
        if "preempt" not in did and len(running) == 3 and all(
                s.output_tokens for s in running):
            while eng._inflight:
                eng._retire_window("decode")
            victim = sorted(eng.scheduler.running.values(),
                            key=lambda s: s.slot)[-1]
            did["preempt"] = victim.seq_id
            with eng._lock:
                eng._preempt(victim)
            assert victim.state_page == 0

    _run(eng, between)
    assert "preempt" in did
    for i, sid in zip((3, 0, 2), ids):
        _same(eng, sid, alone[i])
    assert eng.block_mgr.live_pages == 0


def test_an_abort_frees_its_page_and_moves_nobody(alone):
    """A request aborted mid-decode gives its blocks and its page back;
    the others read as they read alone, and a later request takes the
    page."""
    eng = _engine()
    longer = SamplingOptions(max_tokens=40, temperature=0.0,
                             ignore_eos=True)
    ids = [eng.add_request(PROMPTS[i], longer) for i in (1, 4, 5)]
    did = {}

    def between(n):
        if "abort" not in did and all(
                eng.seqs[s].output_tokens for s in ids):
            did["abort"] = ids[1]
            eng.abort(ids[1])
            did["late"] = eng.add_request(PROMPTS[2], GREEDY)

    _run(eng, between)
    assert "abort" in did
    _same(eng, ids[0], alone[1])
    _same(eng, ids[2], alone[5])
    _same(eng, did["late"], alone[2])
    assert eng.block_mgr.live_pages == 0


# ---------------------------------------------------------------------
# what is refused, by name; the mapping
# ---------------------------------------------------------------------

@pytest.mark.parametrize("kw,names", [
    (dict(enable_prefix_caching=True), "prefix caching"),
    (dict(kv_transfer_config={"kv_role": "kv_both"}), "KV connector"),
    (dict(speculative_ngram_tokens=3), "n-gram speculation"),
    (dict(checkpoint="/nowhere"), "checkpoint loader"),
    (dict(lora_adapters={"a": "random:1"}), "LoRA"),
    (dict(kv_dtype="int8"), "int8 KV pool")])
def test_what_the_model_cannot_run_with_is_refused_by_name(kw, names):
    with pytest.raises(ValueError) as err:
        ModelRunner(get_config("debug-nemotron"), EngineConfig(
            model="debug-nemotron", max_num_seqs=2, max_model_len=128,
            **kw))
    assert names in str(err.value) and "state pages" in str(err.value)


def test_a_mesh_and_a_forward_without_caches_are_refused_by_name():
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(dp=1, tp=2), jax.devices()[:2])
    with pytest.raises(ValueError, match="mesh.*state pages"):
        ModelRunner(get_config("debug-nemotron"), EngineConfig(
            model="debug-nemotron", max_num_seqs=2, max_model_len=128),
            mesh=mesh)
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="without caches"):
        llama.encode(params, CFG, jnp.zeros((1, 8), jnp.int32))


def _catalog():
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"]
                == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")["config"]


SHARE = {"n_routed_experts": 32, "vocab_size": 32768,
         "deployment": {"chips_per_layer": 4, "chip_index": 0,
                        "router_experts": 128, "pipeline_stages": 1}}


def test_the_mapping_reads_the_catalogs_keys():
    cfg = ModelConfig.from_hf_config(_catalog(), name="nemotron")
    M, E, A = "mamba2", "moe", "attn"
    assert cfg.layer_plan == (((M, E, M, E, M, A, E), 5), ((M, E), 3),
                              ((M, A, E), 1), ((M, E), 4))
    spelled = [k for period, reps in cfg.layer_plan
               for _ in range(reps) for k in period]
    assert "".join({M: "M", E: "E", A: "*"}[k] for k in spelled) \
        == _catalog()["hybrid_override_pattern"]
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim_) == (52, 2688, 32, 2, 128)
    assert (cfg.mamba_d_inner, cfg.mamba_heads, cfg.mamba_groups,
            cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_conv_channels) == (4096, 64, 8, 128, 4, 6144)
    assert (cfg.num_experts, cfg.router_experts_, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.moe_stored_size,
            cfg.shared_expert_size) == (128, 128, 6, 1856, 1920, 3712)
    assert (cfg.router_score, cfg.router_bias, cfg.routed_scaling_factor,
            cfg.norm_topk_prob, cfg.shared_expert_gate, cfg.expert_gate,
            cfg.activation) == ("sigmoid", True, 2.5, True, False, False,
                                "relu2")
    assert (cfg.attn_layers, cfg.mamba_layers, cfg.expert_layers,
            cfg.self_layers) == (6, 23, 23, 52)
    # the ordinary pool heads, not differential pairs
    assert (cfg.pool_kv_heads, cfg.pool_head_dim) == (2, 128)
    assert cfg.state_bytes_per_seq == 23 * (64 * 64 * 128 * 4
                                            + 3 * 6144 * 2) == 49_082_368
    assert not cfg.tie_word_embeddings
    # 31.6 B published; 31.578 B from the equations
    assert abs(cfg.num_params - 31.578e9) < 0.01e9
    share = ModelConfig.from_hf_config({**_catalog(), **SHARE})
    assert (share.num_experts, share.router_experts_,
            share.expert_offset) == (32, 128, 0)
    assert abs(share.num_params - 9.018e9) < 0.01e9
    # a decoder-hybrid-decoder still pairs its heads; every other model
    # is one run with its own
    yoco = get_config("debug-yoco")
    assert (yoco.pool_kv_heads, yoco.pool_head_dim) == (2, 16)
    assert yoco.state_bytes_per_seq == 3 * (4 * 4 * 128 + 2 * 3 * 128)


@pytest.mark.parametrize("letters,runs", [
    ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
     (("MEMEM*E", 5), ("ME", 3), ("M*E", 1), ("ME", 4))),
    (PATTERN, (("ME", 1), ("M*EME", 2))),
    ("MMMM", (("M", 4),)), ("M*E", (("M*E", 1),))])
def test_the_pattern_is_spelled_in_the_fewest_traced_sublayers(letters,
                                                               runs):
    got = plan_runs(letters)
    assert tuple(("".join(p), r) for p, r in got) == runs
    assert "".join("".join(p) * r for p, r in got) == letters


def test_num_params_counts_the_share_it_holds():
    assert CFG.num_params == (
        5 * (128 * (256 + 320 + 8) + 256 * 128 + 320 * 5 + 3 * 8 + 256)
        + 2 * (2 * 128 * 128 + 2 * 128 * 64)
        + 5 * (128 * 8 + 8 + 2 * 128 * 96 + 8 * 2 * 128 * 48)
        + 12 * 128 + 2 * 512 * 128 + 128)


@pytest.mark.parametrize("change,names", [
    ({"hybrid_override_pattern": "ME-" * 17 + "M"}, "dense MLP block"),
    ({"num_hidden_layers": 50}, "hybrid_override_pattern"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"n_group": 2}, "grouped routing"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"sliding_window": 512}, "sliding_window"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"n_groups": 7}, "n_groups"),
    ({"n_routed_experts": 32, "deployment": {
        "chips_per_layer": 3, "router_experts": 128}}, "deployment")])
def test_the_mapping_refuses_what_the_tree_does_not_build(change, names):
    with pytest.raises(ValueError, match=names):
        ModelConfig.from_hf_config({**_catalog(), **change})
