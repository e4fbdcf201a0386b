"""A decoder-hybrid-decoder (Phi-4-mini-flash, ``phi4flash``): Mamba
layers over state pages beside differential attention over a K/V pool
of FEWER layers than attend, gated memory units and a prefill in two
depths, on the CPU at tiny sizes with seeded weights (``debug-yoco``:
hidden 64, 8 / 4 heads of 8, a state of 4, window 16, 8 layers = 2 + 1
+ 1 periods, block 8, chunk 32).

- the model through both caches (prefill in several chunks with a
  padded last one, then decode steps beside a parked row; contexts past
  the window, a block and a chunk boundary) against the full forward
  pass of chipbench/references/phi4flash.py, logits not tokens,
  float32, to 1e-4;
- the two-depth prefill against every layer on every position;
- the pool layer the cross layers read is the full layer's; the memory
  is the last Mamba layer's pre-gate output;
- the engine: turnover, a preemption with recompute, rows of different
  lengths, the counters of ``GET /debug/perf``;
- every refusal by name; the configuration's mapping from the catalog's
  keys.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import phi4flash as ref
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.runner import ModelRunner
from production_stack_tpu.engine.scheduler import SamplingOptions
from production_stack_tpu.models import kv as kv_pool
from production_stack_tpu.models import llama
from production_stack_tpu.models.config import ModelConfig, get_config

CFG = dataclasses.replace(get_config("debug-yoco"), dtype=jnp.float32)
# debug-yoco under the published keys, for the reference
HF = dict(model_type="phi4flash", num_hidden_layers=8, hidden_size=64,
          intermediate_size=256, num_attention_heads=8,
          num_key_value_heads=4, sliding_window=16, mb_per_layer=2,
          layer_norm_eps=1e-5, vocab_size=512, tie_word_embeddings=True)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def worst(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def live_params(cfg=CFG, seed=3, quantization=None):
    """Seeded weights with every norm, bias, lambda and skip term moved
    off its initial value (a norm that ignored its weight, or a bias
    never added, would pass at the initialisation)."""
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
                     and name != "A_log" and group != "embed")
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
                     two_depths=False, cache_out=None):
    """Row 0 of a batch of two (row 1 parked): the prompt's first
    ``prefill_to`` tokens in chunks of ``chunk`` (the last one padded
    in its bucket), the rest as decode steps -> log-probabilities after
    every position [T, V]; with ``two_depths`` the chunks run as the
    engine's prefill does and only the last prompt position's
    log-probabilities come out of the prefill."""
    B, Bs, MB = 2, 8, 32
    T = len(toks)
    cache = kv_pool.cache_for(cfg, B * MB + 1, Bs, cfg.dtype, state_pages=3)
    tables = _tables(B, MB)
    fwd = jax.jit(lambda p, t, pos, c, tv, last, fin: llama.forward(
        p, cfg, t, pos, c, block_tables=tables, token_valid=tv,
        kv_len=256, last=last, finishing=fin)[:2])
    out = []
    for c0 in range(0, prefill_to, chunk):
        n = min(chunk, prefill_to - c0)
        t = np.zeros((B, chunk), np.int32)
        t[0, :n] = toks[c0:c0 + n]
        pos = np.stack([np.arange(chunk) + c0, np.arange(chunk) + 10000])
        tv = np.zeros((B, chunk), bool)
        tv[0, :n] = True
        final = c0 + chunk >= prefill_to
        logits, cache = fwd(
            params, jnp.asarray(t), jnp.asarray(pos), cache,
            jnp.asarray(tv),
            jnp.array([n - 1, 0]) if two_depths else None,
            jnp.asarray(final) if two_depths else None)
        if not two_depths:
            out.append(logits[0, :n])
        elif final:
            out.append(logits[0, :1])
    for i in range(prefill_to, T):
        logits, cache = fwd(
            params, jnp.asarray([[toks[i]], [0]], jnp.int32),
            jnp.asarray([[i], [10000]]), cache,
            jnp.asarray([[True], [False]]), None, None)
        out.append(logits[0, :1])
    # the parked row wrote the trash page alone
    assert float(jnp.abs(cache.state[:, 1]).max()) == 0
    assert float(jnp.abs(cache.state[:, 2]).max()) > 0
    if cache_out is not None:
        cache_out.append(cache)
    return jax.nn.log_softmax(jnp.concatenate(out, 0), -1)


TOKS = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (85,), 0,
                                     512)).tolist()


def test_prefill_in_chunks_then_decode_is_the_reference_forward():
    """85 tokens: two whole chunks of 32, a chunk of 11 padded to 32
    after a carried state, ten decode steps: past the window of 16,
    nine block boundaries and two chunk boundaries, against the
    reference's ONE pass (the token-by-token recurrence, full softmax
    under masks, every layer on every position). 1e-4 on a
    log-probability: float32 against float32 (1e-6 seen); the reference
    with its activations rounded to bfloat16 between blocks stands over
    30 times farther, so a bfloat16 product anywhere on the served path
    would show (the Mamba state ALONE in bfloat16 moves these logits by
    1e-6 at weights of sd 0.02: tests/test_mamba.py holds the state's
    precision at the kernel, where it reads a hundred times the
    tolerance)."""
    params = live_params()
    got = _served_logprobs(params, TOKS)
    want = ref.logprobs(params, HF, TOKS)
    assert worst(got, want) < 1e-4
    rounded = ref.logprobs(params, {**HF, "round_to": "bfloat16"}, TOKS)
    assert worst(rounded, want) > 30 * 1e-4


def test_int8_weights_are_the_reference_on_the_same_leaves():
    params = live_params(quantization="int8")
    assert set(params["mamba_layers"]["in_proj"]) == {"w8", "scale"}
    assert not isinstance(params["mamba_layers"]["x_proj"], dict)
    got = _served_logprobs(params, TOKS[:50], prefill_to=44)
    assert worst(got, ref.logprobs(params, HF, TOKS[:50])) < 1e-4


@pytest.mark.parametrize("breakage", [
    {"gmu_control": "off"}, {"lambda_control": "zero"},
    {"sliding_window": 8}])
def test_a_reference_that_departs_in_one_place_stands_apart(breakage):
    """What the chip check's ``lean`` switches off, one at a time: each
    moves the log-probabilities twenty times the tolerance and more."""
    params = live_params()
    want = ref.logprobs(params, HF, TOKS)
    assert worst(ref.logprobs(params, {**HF, **breakage}, TOKS),
                 want) > 20 * 1e-4


def test_two_depths_give_the_last_position_of_the_full_depth():
    """The engine's prefill: layers 0-5 on every position, layers 6-7,
    the final norm and the head on the row's last prompt position, in
    the chunk that ends the prompt alone. The same last-position
    log-probabilities, the same caches, the same decode steps after
    them, as every layer on every position."""
    params = live_params()
    full, two = [], []
    a = _served_logprobs(params, TOKS, cache_out=full)
    b = _served_logprobs(params, TOKS, two_depths=True, cache_out=two)
    assert b.shape[0] == 1 + 10
    assert worst(a[74:], b) < 1e-5
    for x, y in zip(full[0], two[0]):
        if x is not None:
            assert worst(x, y) < 1e-5
    assert CFG.self_layers == 6 and CFG.num_layers == 8


def test_a_chunk_that_ends_no_prompt_skips_the_second_depth():
    """``finishing`` False: the cross layers do not run (the logits are
    the first depth's hidden state through the head: not those of a
    run with it True), and the caches are the same either way."""
    params = live_params()
    cache = kv_pool.cache_for(CFG, 65, 8, CFG.dtype, state_pages=3)
    tables = _tables(1, 64)
    toks = jnp.asarray([TOKS[:32]], jnp.int32)
    pos = jnp.arange(32)[None, :]
    run = jax.jit(lambda c, fin: llama.forward(
        params, CFG, toks, pos, c, block_tables=tables, kv_len=256,
        last=jnp.array([31]), finishing=fin)[:2])
    on, c_on = run(cache, jnp.asarray(True))
    off, c_off = run(cache, jnp.asarray(False))
    assert on.shape == off.shape == (1, 1, 512)
    assert worst(on, off) > 1e-2
    for x, y in zip(c_on, c_off):
        if x is not None:
            assert worst(x, y) == 0


def _decode_after_prefill(params, poison=None):
    """One decode step at position 40 after a 40-token prefill; with
    ``poison`` (a pool layer) that layer's K and V overwritten between
    the two -> the step's logits."""
    cache = kv_pool.cache_for(CFG, 65, 8, CFG.dtype, state_pages=3)
    tables = _tables(1, 64)
    _, cache, _ = llama.forward(
        params, CFG, jnp.asarray([TOKS[:40]], jnp.int32),
        jnp.arange(40)[None, :], cache, block_tables=tables, kv_len=256)
    if poison is not None:
        cache = cache._replace(k=cache.k.at[poison].add(0.5),
                               v=cache.v.at[poison].add(0.5))
    # the step's own layers 1, 3, 5 append before they read, so the
    # poison of a layer with K/V of its own reaches its output too:
    # compare from the residual stream the cross layer alone changes
    logits, _, _ = llama.forward(
        params, CFG, jnp.asarray([[TOKS[40]]], jnp.int32),
        jnp.asarray([[40]]), cache, block_tables=tables, kv_len=256)
    return logits


def test_the_pool_has_the_layers_that_own_their_keys():
    """Three pool layers for four readers: window, window, full, and
    the cross layer reads pool layer 2, the full layer's. The cross
    layer's only way to see a pool layer is through ``shared``: with
    the full layer's projections zeroed on the decode step (so that
    layer 5 itself adds nothing of its own) a poisoned layer 2 still
    moves the logits, and a poisoned layer 0 moves them only through
    layer 1."""
    assert CFG.attn_layers == 3 and CFG.reader_layers == 4
    cache = kv_pool.cache_for(CFG, 9, 8, CFG.dtype, state_pages=2)
    assert cache.k.shape == (3, 9, 2, 8, 16)
    assert cache.bytes_per_token == 3 * 2 * 4 * 8 * 4
    params = live_params()
    # layers 1 and 3 (window) and 5 (full) silenced: their o projection
    # and bias zero, so what a pool layer holds reaches the logits
    # through the cross layer (7) alone
    quiet = {**params, "diff_layers": {
        **params["diff_layers"],
        "o": jnp.zeros_like(params["diff_layers"]["o"]),
        "o_bias": jnp.zeros_like(params["diff_layers"]["o_bias"])}}
    clean = _decode_after_prefill(quiet)
    assert worst(_decode_after_prefill(quiet, poison=0), clean) == 0
    assert worst(_decode_after_prefill(quiet, poison=1), clean) == 0
    assert worst(_decode_after_prefill(quiet, poison=2), clean) > 1e-3


def test_the_memory_is_the_last_mamba_layers_pre_gate_output():
    """The gated memory unit multiplies ``M`` = layer 4's ``y`` before
    its ``silu(z)`` gate: with that layer's ``out_proj`` zeroed the
    layer adds nothing to the residual stream, and the logits still
    move with its ``D`` (the skip term inside ``y``), through the
    memory alone; the reference agrees on both."""
    params = live_params()
    mam = params["mamba_layers"]
    cut = {**params, "mamba_layers": {
        **mam, "out_proj": mam["out_proj"].at[2].set(0.0)}}
    moved = {**cut, "mamba_layers": {
        **cut["mamba_layers"], "D": mam["D"].at[2].add(1.0)}}
    a = _served_logprobs(cut, TOKS[:40], prefill_to=36)
    b = _served_logprobs(moved, TOKS[:40], prefill_to=36)
    assert worst(a, b) > 1e-3
    assert worst(a, ref.logprobs(cut, HF, TOKS[:40])) < 1e-4
    assert worst(b, ref.logprobs(moved, HF, TOKS[:40])) < 1e-4
    # layers 0 and 2 leave no memory: their D moves nothing that way
    other = {**cut, "mamba_layers": {
        **cut["mamba_layers"], "D": mam["D"].at[0].add(1.0),
        "out_proj": cut["mamba_layers"]["out_proj"].at[0].set(0.0)}}
    zeroed = {**cut, "mamba_layers": {
        **cut["mamba_layers"],
        "out_proj": cut["mamba_layers"]["out_proj"].at[0].set(0.0)}}
    assert worst(_served_logprobs(other, TOKS[:40], prefill_to=36),
                 _served_logprobs(zeroed, TOKS[:40], prefill_to=36)) == 0


def test_the_mamba_kernels_serve_what_the_jnp_form_does(monkeypatch):
    """The same model with ops/mamba.py's kernels in interpret mode
    (the paged attention kernels want whole lanes, which heads of 16
    are not: they stay on the gathered path, and
    tests/test_sliding_window.py holds them at this model's shapes)."""
    from production_stack_tpu.ops import mamba
    params = live_params()
    want = _served_logprobs(params, TOKS[:50], prefill_to=44)
    monkeypatch.setattr(
        mamba, "mamba_path",
        lambda T: mamba.RECURRENT if T <= 8 else mamba.CHUNKED)
    got = _served_logprobs(params, TOKS[:50], prefill_to=44)
    assert worst(got, want) < 1e-5


# ---------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------

def _engine(**kw):
    cfg = dict(model="debug-yoco", max_num_seqs=4, max_model_len=256,
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
    """The engine's own path (scheduler, block manager, two-depth
    prefill in chunks of 32, decode windows): the first token's
    log-probability and the greedy tokens of a 150-token prompt are
    the reference's."""
    params = _engine().runner.params
    lps = ref.logprobs(params, HF, PROMPTS[0] + alone[0][0])
    n = len(PROMPTS[0])
    for j, (tok, lp) in enumerate(zip(*alone[0])):
        assert int(jnp.argmax(lps[n - 1 + j])) == tok
        assert abs(float(lps[n - 1 + j, tok]) - lp) < 1e-3


def test_turnover_and_the_counters(alone):
    """Six requests of different lengths through four slots and four
    pages read as they read alone; ``GET /debug/perf`` counts nine... of
    this model three pool layers for four readers, the two depths, the
    shared layer's reads and the Mamba paths."""
    eng = _engine()
    ids = [eng.add_request(p, GREEDY) for p in PROMPTS]
    _run(eng)
    for i, sid in enumerate(ids):
        _same(eng, sid, alone[i])
    pool = eng.block_mgr.frag_report()
    assert pool["layout"] == "kv+state"
    assert pool["pool_layers"] == 3 and pool["reader_layers"] == 4
    assert pool["state_pages"] == {"total": 4, "live": 0}
    # (a float32 pool here: the convolution's inputs take 4 bytes)
    assert pool["state_bytes_per_slot"] \
        == eng.runner.cache.state_bytes_per_slot == 3 * (
            4 * 4 * 128 + 4 * 3 * 128)
    assert pool["bytes_per_token"] == 3 * 2 * 4 * 8 * 4
    totals = eng.eff.report()
    prefill, shared = totals["prefill"], totals["shared_kv"]
    # one position a row ran layers 6-7, in the dispatches that ended
    # a prompt alone; every chunk's bucket ran 0-5
    assert len(PROMPTS) <= prefill["cross_positions"] \
        < prefill["self_positions"] // 32
    assert prefill["self_positions"] == prefill["real"] + prefill["pad"]
    assert shared["reads"] > 0
    assert shared["keys_read"] >= sum(map(len, PROMPTS))
    assert totals["state"]["scan_tokens"] == sum(map(len, PROMPTS))
    paths = eng.device_report()["mixer_paths"]
    assert {v for k, v in paths.items() if k.startswith("decode")} \
        == {"mamba_recurrent_step_jnp"}
    assert {v for k, v in paths.items() if k.startswith("prefill")} \
        == {"mamba_chunk_scan_jnp"}
    assert set(paths) == set(eng.device_report()["attention_paths"])


def test_a_preemption_and_a_resume_change_nothing(alone):
    """A running sequence is preempted (blocks and page go back; it
    recomputes from position 0, two depths again, into whatever page
    it is handed next): it reads as it read alone."""
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
        ModelRunner(get_config("debug-yoco"), EngineConfig(
            model="debug-yoco", max_num_seqs=2, max_model_len=128, **kw))
    assert names in str(err.value) and "state pages" in str(err.value)


def test_a_mesh_and_a_forward_without_caches_are_refused_by_name():
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    mesh = build_mesh(MeshConfig(dp=1, tp=2), jax.devices()[:2])
    with pytest.raises(ValueError, match="mesh.*state pages"):
        ModelRunner(get_config("debug-yoco"), EngineConfig(
            model="debug-yoco", max_num_seqs=2, max_model_len=128),
            mesh=mesh)
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="without caches"):
        llama.encode(params, CFG, jnp.zeros((1, 8), jnp.int32))


def _catalog():
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows
                if r["name"] == "Phi-4-mini-flash-reasoning")["config"]


def test_the_mapping_reads_the_catalogs_keys():
    cfg = ModelConfig.from_hf_config(_catalog(), name="phi")
    assert cfg.layer_plan == ((("mamba", "swa"), 8),
                              (("mamba_mem", "full"), 1),
                              (("gmu", "cross"), 7))
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim_) == (32, 2560, 40, 20, 64)
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_dt_rank) == (5120, 16, 4, 160)
    assert cfg.sliding_window == 512 and cfg.tie_word_embeddings
    assert (cfg.attn_layers, cfg.reader_layers, cfg.mamba_layers,
            cfg.self_layers) == (9, 16, 9, 18)
    assert (cfg.pool_kv_heads, cfg.pool_head_dim) == (10, 128)
    assert cfg.state_bytes_per_seq == 3_225_600
    assert abs(cfg.num_params - 3.853e9) < 0.01 * 3.853e9
    # every other model is one run, and its pool its own heads
    for name in ("debug-tiny", "debug-gdn", "debug-brumby", "debug-mla"):
        one = get_config(name)
        assert len(one.plan_) == 1 and one.self_layers == one.num_layers
        assert one.pool_kv_heads == one.num_kv_heads
        assert one.reader_layers == one.attn_layers


def test_num_params_counts_the_tree():
    params = llama.init_params(CFG, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == CFG.num_params


@pytest.mark.parametrize("change,names", [
    ({"mb_per_layer": 3}, "mb_per_layer"),
    ({"num_hidden_layers": 30}, "mb_per_layer"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"sliding_window": None}, "sliding_window"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"num_key_value_heads": 5}, "pairs")])
def test_the_mapping_refuses_what_the_tree_does_not_build(change, names):
    with pytest.raises(ValueError, match=names):
        ModelConfig.from_hf_config({**_catalog(), **change})
