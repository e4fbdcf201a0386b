"""Learned sparse attention (GLM-5, ``glm_moe_dsa``) at a tiny size on
the CPU: the selection's two steps (ops/dsa.py) against plain
jax.numpy and numpy, the serving path through BOTH pools (the latent
pool and the index pool, models/kv.py) against the benchmark's plain
reference (chipbench/references/glm_moe_dsa.py), the chip's share of
the experts against the uncut layer, and what the tree refuses by name.

The program runs in float32 where it is held to the reference
(TOLERANCE), so a served selection must equal the reference's exactly.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import glm_moe_dsa as ref
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.scheduler import SamplingOptions
from production_stack_tpu.models import kv as kv_pool
from production_stack_tpu.models import llama
from production_stack_tpu.models.config import ModelConfig, get_config
from production_stack_tpu.ops import dsa, moe, pallas_paged

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS, CHUNK = 16, 24       # KV block and prefill chunk of these tests
PROMPT, STEPS = 70, 8    # contexts of 70-78: 4-5 times index_topk 16
TOLERANCE = 1e-4         # float32 against float32: tests/test_mla.py
CFG = dataclasses.replace(get_config("debug-dsa"), dtype=jnp.float32)


@pytest.fixture
def kernels(request, monkeypatch):
    """The Pallas kernels in interpret mode, or the CPU's jax.numpy."""
    monkeypatch.setattr(pallas_paged, "_override", request.param)
    return request.param


ON = pytest.mark.parametrize("kernels", [True], indirect=True,
                             ids=["pallas_interpret"])


# ---------------------------------------------------------------------
# ops/dsa.py
# ---------------------------------------------------------------------

def _numpy_select(scores, last, topk):
    """The topk positions s <= last of largest score, ties to the
    lower position: a sort by (-score, position) a row."""
    out = np.zeros(scores.shape, bool)
    for r, row in enumerate(scores):
        live = np.arange(len(row)) <= last[r]
        order = sorted(np.flatnonzero(live), key=lambda s: (-row[s], s))
        out[r, order[:topk]] = True
    return out


@ON
@pytest.mark.parametrize("case,S,topk", [
    ("random", 256, 16), ("ties", 384, 32), ("all-equal", 128, 16),
    ("fewer-live-than-topk", 256, 64), ("negative-and-zero", 256, 16),
    ("topk-2048-of-4096", 4096, 2048)])
def test_select_is_the_exact_topk_with_ties_to_the_lower_position(
        kernels, case, S, topk):
    rng = np.random.default_rng(len(case))
    R = 16
    scores = rng.normal(size=(R, S)).astype(np.float32)
    last = rng.integers(topk, S, R)
    if case == "ties":          # eight distinct values: ties everywhere
        scores = np.round(scores * 2) / 2
    if case == "all-equal":
        scores[:] = 0.25
    if case == "fewer-live-than-topk":
        last = rng.integers(0, topk, R)
    if case == "negative-and-zero":
        scores = -np.abs(scores)
        scores[:, ::3] = 0.0
        scores[:, 1::3] = -0.0
    last[0], last[1] = S - 1, 0
    want = _numpy_select(scores, last, topk)
    got = dsa.select(jnp.asarray(scores), jnp.asarray(last, jnp.int32),
                     topk)
    assert got.dtype == jnp.bfloat16
    assert (np.asarray(got, np.float32) > 0).tolist() == want.tolist()
    pallas_paged.set_flash_enabled(False)       # and the jax.numpy form
    plain = dsa.select(jnp.asarray(scores), jnp.asarray(last, jnp.int32),
                       topk)
    pallas_paged.set_flash_enabled(True)
    assert (np.asarray(plain, np.float32) > 0).tolist() == want.tolist()


@ON
@pytest.mark.parametrize("B,T,S", [(2, 1, 256), (1, 32, 384)],
                         ids=["decode", "chunk"])
def test_index_scores_kernel_agrees_with_jax_numpy(kernels, B, T, S):
    heads, width = 4, 32
    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (B, T, heads, width), jnp.float32)
    w = jax.random.normal(ks[1], (B, T, heads), jnp.float32)
    keys = jax.random.normal(ks[2], (B, S, width), jnp.float32)
    got = dsa.index_scores(q, w, keys)
    want = np.einsum("bths,bth->bts", np.maximum(np.einsum(
        "bthd,bsd->bths", *map(np.asarray, (q, keys))), 0), np.asarray(w))
    assert got.shape == (B, T, S)
    assert np.abs(np.asarray(got) - want).max() < 1e-4


@pytest.mark.parametrize("T,contexts", [
    (1, (40, 70)), (1, (16, 17)), (1, (1, 128)), (24, (60, 128)),
    (2, (50, 90))], ids=["decode", "decode-block-edge", "decode-ends",
                         "chunk", "two-positions"])
def test_attend_under_a_mask_reads_the_marked_positions_alone(
        monkeypatch, T, contexts):
    """models/kv.attend with ``select``: the decode kernel's mask for
    one position a row, the prefill kernel's for anything wider, both
    against the masked jax.numpy attention over the gathered view."""
    B, H, W, V, MB = len(contexts), 4, 256, 128, 8
    rng = np.random.default_rng(T + sum(contexts))
    latents = jnp.asarray(rng.normal(size=(2, B * MB + 1, 1, BS, W)),
                          jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, T, H, W)), jnp.float32)
    tables = kv_pool.linear_tables(B, MB * BS, BS)
    starts = jnp.asarray([n - T for n in contexts], jnp.int32)
    positions = starts[:, None] + jnp.arange(T)[None]
    mask = rng.random((B, T, MB * BS)) < 0.3
    mask[np.arange(B)[:, None], np.arange(T)[None],
         np.asarray(positions)] = True
    mask &= np.arange(MB * BS)[None, None] <= np.asarray(positions)[..., None]
    select = jnp.asarray(mask, jnp.float32)
    out = {}
    for on in (True, False):
        monkeypatch.setattr(pallas_paged, "_override", on)
        out[on] = kv_pool.attend(
            q, (latents,), tables, starts, positions, MB * BS, 1,
            window=None, scale=W ** -0.5, softcap=None, value_dim=V,
            select=select)
    assert out[True].shape == (B, T, H, V)
    assert worst(out[True], out[False]) < TOLERANCE


# ---------------------------------------------------------------------
# the serving path through both pools against the plain reference
# ---------------------------------------------------------------------

def hf_of(cfg: ModelConfig) -> dict:
    """The published keys the reference reads, from a ModelConfig."""
    chips = cfg.router_experts_ // cfg.num_experts
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
        num_hidden_layers=cfg.num_layers,
        index_n_heads=cfg.index_n_heads,
        index_head_dim=cfg.index_head_dim, index_topk=cfg.index_topk,
        deployment=dict(router_experts=cfg.router_experts_,
                        chips_per_layer=chips,
                        chip_index=cfg.expert_offset // cfg.num_experts))


def tokens_of(seed: int = 3) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, (1, PROMPT + STEPS))


def served_logprobs(cfg, params, tokens, prompt=PROMPT):
    """Log-probabilities after every position [T, V] as the serving
    path computes them: the prompt prefilled in chunks of CHUNK through
    the latent pool and the index pool, then teacher-forced decode
    steps, each selecting index_topk of its context."""
    B, T = tokens.shape
    MB = -(-T // BS) + 1
    cache = kv_pool.cache_for(cfg, B * MB + 1, BS, cfg.dtype)
    assert cache.layout == kv_pool.LATENT_INDEX and cache.v is None
    assert cache.idx.shape == (cfg.num_layers, B * MB + 1, 1, BS,
                               cfg.index_head_dim)
    tables = kv_pool.linear_tables(B, MB * BS, BS)
    out = []
    spans = [(s, min(s + CHUNK, prompt)) for s in range(0, prompt, CHUNK)]
    spans += [(t, t + 1) for t in range(prompt, T)]
    for start, end in spans:
        pos = jnp.broadcast_to(jnp.arange(start, end), (B, end - start))
        logits, cache, _ = llama.forward(
            params, cfg, jnp.asarray(tokens[:, start:end]), pos, cache,
            block_tables=tables)
        out.append(logits[0])
    return jax.nn.log_softmax(jnp.concatenate(out).astype(jnp.float32))


def worst(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)))


@pytest.mark.parametrize("kernels,weights", [
    (False, None), (False, "int8"), (True, "int8")], indirect=["kernels"],
    ids=["jnp-plain", "jnp-int8", "pallas_interpret-int8"])
def test_prefill_then_decode_agrees_with_the_reference(weights, kernels):
    """Three prefill chunks and eight decode steps through both pools
    against the reference's one full forward pass: the logits. Chunks
    and decode steps alike attend under the selection's mask."""
    params = llama.init_params(CFG, jax.random.PRNGKey(1),
                               quantization=weights)
    tokens = tokens_of()
    if kernels:
        assert [pallas_paged.attention_path(
            t, CFG.num_heads, 256, BS, value_dim=CFG.kv_lora_rank,
            selects=True) for t in (1, CHUNK)] == [
                "pallas_paged_decode_latent_sparse",
                "pallas_paged_latent_sparse"]
    got = served_logprobs(CFG, params, tokens)
    want = ref.logprobs(params, hf_of(CFG), tokens[0])
    assert worst(got, want) < TOLERANCE


# a chunk of LONG positions a row is past the rule's cut at these
# widths (the pool 256 wide, 128 of it the value: 768 operations a
# query absorbed, 160 expanded and 2 x 128 x 64 = 16 384 a key once:
# 26.9); CHUNK (24) is under it
LONG = 48


@ON
@pytest.mark.parametrize("padded", [False, True],
                         ids=["short-tail", "padded-tail"])
def test_expanded_chunks_select_as_absorbed_and_the_reference(
        monkeypatch, kernels, padded):
    """A prompt of 100 tokens prefilled in chunks of LONG through both
    pools: the chunks attend EXPANDED under the selection's marks (16
    of up to 100 positions a query), across block boundaries and a
    chunk boundary, the tail of 4 alone (absorbed) or padded to LONG. The selections (ops/dsa.tap) and the logits against
    the same prompt with the rule switched off, the logits against the
    reference's full forward pass."""
    from tests.test_mla import chunked_logprobs
    params = llama.init_params(CFG, jax.random.PRNGKey(6),
                               quantization="int8")
    tokens = np.random.default_rng(9).integers(0, CFG.vocab_size, (1, 100))
    dims = (CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.v_head_dim)
    assert [pallas_paged.attention_path(
        t, CFG.num_heads, 256, BS, value_dim=CFG.kv_lora_rank,
        selects=True, head_dims=dims) for t in (CHUNK, LONG)] == [
            "pallas_paged_latent_sparse",
            "pallas_paged_latent_expanded_sparse"]
    calls = []
    kernel = pallas_paged.paged_attention

    def counted(*a, **kw):
        calls.append((a[0].shape[1], kw.get("expand") is not None,
                      kw.get("select") is not None))
        return kernel(*a, **kw)
    monkeypatch.setattr(pallas_paged, "paged_attention", counted)
    seen = {}

    def tap(layer, positions, mask):
        for t, row in zip(np.asarray(positions)[0],
                          np.asarray(mask)[0] > 0):
            seen.setdefault((int(layer), int(t)), []).append(
                np.flatnonzero(row).tolist())
    monkeypatch.setattr(dsa, "tap", tap)
    expanded = chunked_logprobs(CFG, params, tokens, LONG, padded)
    # (under marks a tail of 4 is the prefill kernel's too: absorbed)
    assert {c for c in calls} == {(LONG, True, True)} | (
        set() if padded else {(4, False, True)})
    monkeypatch.setattr(pallas_paged, "expanded_cheaper",
                        lambda *a: False)
    jax.clear_caches()
    absorbed = chunked_logprobs(CFG, params, tokens, LONG, padded)
    jax.effects_barrier()
    assert worst(expanded, absorbed) < TOLERANCE
    real = {k: v for k, v in seen.items() if k[1] < 100}
    assert len(real) == CFG.num_layers * 100
    assert all(len(v) == 2 and v[0] == v[1] and 0 < len(v[0]) <= 16
               for v in real.values())
    want = ref.logprobs(params, hf_of(CFG), tokens[0])
    assert worst(expanded, want) < TOLERANCE


def test_the_served_selection_is_the_references(monkeypatch):
    """Every selection the serving path makes (ops/dsa.tap), in every
    layer, for every query of the last chunk and of the decode steps,
    is the set the reference selects."""
    params = llama.init_params(CFG, jax.random.PRNGKey(2))
    tokens = tokens_of(5)
    seen = {}

    def tap(layer, positions, mask):
        for t, row in zip(np.asarray(positions)[0],
                          np.asarray(mask)[0] > 0):
            seen[int(layer), int(t)] = np.flatnonzero(row).tolist()
    monkeypatch.setattr(dsa, "tap", tap)
    served_logprobs(CFG, params, tokens)
    jax.effects_barrier()
    watch = list(range(PROMPT - 10, PROMPT + STEPS))
    _, sets = ref.logprobs(params, hf_of(CFG), tokens[0], watch=watch)
    sets = np.asarray(sets)
    assert sets.shape[:2] == (CFG.num_layers, len(watch))
    for layer in range(CFG.num_layers):
        for n, t in enumerate(watch):
            want = np.flatnonzero(sets[layer, n]).tolist()
            assert len(want) == CFG.index_topk
            assert seen[layer, t] == want, (layer, t)


def test_a_short_context_attends_everything_and_still_writes_its_keys():
    """While the kv bucket holds no more than index_topk positions the
    layer is plain latent attention (models/kv.selects) and its index
    keys are cached all the same."""
    assert not kv_pool.selects(16, 8, BS, CFG.index_topk)
    assert kv_pool.selects(32, 8, BS, CFG.index_topk)
    assert kv_pool.selects(None, 8, BS, CFG.index_topk)
    assert not kv_pool.selects(None, 8, BS, 0)
    params = llama.init_params(CFG, jax.random.PRNGKey(1))
    cache = kv_pool.cache_for(CFG, 3, BS, CFG.dtype)
    tokens = jnp.asarray(tokens_of()[:, :12])
    _, cache, _ = llama.forward(
        params, CFG, tokens, jnp.arange(12)[None], cache,
        block_tables=kv_pool.linear_tables(1, 2 * BS, BS), kv_len=16)
    written = np.abs(np.asarray(cache.idx[:, 1, 0])).sum(-1) > 0
    assert written[:, :12].all() and not written[:, 12:].any()


def test_a_reference_with_another_selection_disagrees():
    """The control of tools/dsa_chip_check.py at a tiny size: the first
    index_topk positions for the selection fall far outside the
    tolerance."""
    params = llama.init_params(CFG, jax.random.PRNGKey(1))
    tokens = tokens_of()
    got = served_logprobs(CFG, params, tokens)
    wrong = ref.logprobs(params, {**hf_of(CFG), "select_control": "first"},
                         tokens[0])
    assert worst(got, wrong) > 100 * TOLERANCE


@pytest.mark.parametrize("served_as,leans", [
    ({}, False), ({"routed_scaling_factor": 1.0}, True),
    ({"num_experts_per_tok": 2}, True)],
    ids=["as-the-file", "served-at-scale-1", "served-top-2"])
def test_the_chip_check_sees_what_the_held_experts_add(
        monkeypatch, capsys, tmp_path, served_as, leans):
    """tools/dsa_chip_check.py at its tiny size, whole: the served path
    as the file states it leans to the true reference against the
    top-k and the routing-scale controls and every control fails; a
    served path that routes with the control's number instead (the
    tool's model built from a file with that one key changed) leans to
    that control, fails, and the tool exits 1."""
    import importlib.util
    from chipbench import engine_child
    spec = importlib.util.spec_from_file_location(
        "dsa_chip_check", os.path.join(ROOT, "tools", "dsa_chip_check.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    built = engine_child.model_config
    # (float32, as everything here that is held to the reference: in
    # bfloat16 at 128 wide the rounding lies a third of the way along
    # the line by itself)
    monkeypatch.setattr(
        engine_child, "model_config", lambda hf, name: dataclasses.replace(
            built({**hf, **served_as}, name), dtype=jnp.float32))
    monkeypatch.setattr(tool, "ROOT", str(tmp_path))   # (its output)
    rc = tool.main([
        "--tiny", "--allow-cpu", "--rows", "2", "--contexts", "60", "100",
        "--decode-steps", "2", "--share", "0.5",
        "--control", "top:num_experts_per_tok=2",
        "--control", "scale1:routed_scaling_factor=1.0"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    key = "scale1" if "routed_scaling_factor" in served_as else "top"
    assert abs(out["controls"][key]["lean"] - (1.0 if leans else 0.0)) < 0.01
    assert out["served"]["passes"] is not leans
    assert rc == (1 if leans else 0)
    if not leans:
        assert all(c["fails"] == ["lean"] and abs(c["lean"]) < 0.01
                   for c in out["controls"].values())


# ---------------------------------------------------------------------
# the chip's share of the experts
# ---------------------------------------------------------------------

@pytest.mark.parametrize("preset,E,k,held", [
    ("debug-dsa", 16, 4, 1), ("debug-gdn", 64, 4, 8)],
    ids=["sigmoid_16_chips_of_1", "softmax_8_chips_of_8"])
def test_sixteen_shares_add_up_to_the_uncut_layer(preset, E, k, held):
    """A layer of E routed experts top-4 behind one router, cut over
    E / held chips of ``held`` experts each (16 sigmoid-routed experts
    over 16 chips with an ungated shared expert; 64 softmax-routed,
    renormalised experts over 8 chips with a gated one): the routed
    parts that the shares give, with the shared expert counted once,
    add up to what the uncut layer gives; each share reads at most its
    own experts."""
    N, h = 40, 128
    whole = dataclasses.replace(
        get_config(preset), dtype=jnp.float32, num_experts=E,
        router_experts=0, expert_offset=0, num_experts_per_tok=k,
        num_layers=4 if preset == "debug-gdn" else 2)
    params = llama.init_params(whole, jax.random.PRNGKey(4))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, N, h), jnp.float32)

    def block(cfg, lp):
        out, _, work = llama._mlp_block(cfg, x, lp, None, None, None, None,
                                        None, None)
        return out - x, work

    uncut, work = block(whole, lp)
    assert int(work.experts_read) == E
    shared = block(dataclasses.replace(whole, routed_scaling_factor=0.0),
                   lp)[0]
    assert float(jnp.abs(shared).max()) > 0
    assert float(jnp.abs(uncut - shared).max()) > 0
    total = shared
    for chip in range(E // held):
        cfg = dataclasses.replace(whole, num_experts=held,
                                  router_experts=E,
                                  expert_offset=chip * held)
        mine = {**lp, **{n: lp[n][chip * held:(chip + 1) * held]
                         for n in ("gate", "up", "down")}}
        part, work = block(cfg, mine)
        assert int(work.experts_read) <= held
        total = total + part - shared
    assert worst(total, uncut) < 1e-4 * float(jnp.abs(uncut).max())


@pytest.mark.parametrize("chips", [4, 8], ids=["4_chips_of_4",
                                                "8_chips_of_2"])
def test_shares_on_the_grouped_path_add_up_to_the_uncut_layer(chips):
    """A prefill chunk of 128 tokens through 16 sigmoid-routed experts
    top-4 on the grouped path (the kernels in interpret mode): the
    layer whole (every expert held: today's path, no rounds) against
    the sum of what ``chips`` shares of it give, each working through
    the assignments that landed on it in rounds of its block; the
    shares' ``held_rows`` add up to every assignment."""
    E, k, N, h, i = 16, 4, 128, 128, 128
    held = E // chips
    ks = jax.random.split(jax.random.PRNGKey(chips), 6)
    x = jax.random.normal(ks[0], (N, h), jnp.float32)
    rw = jax.random.normal(ks[1], (h, E), jnp.float32) * 0.3
    bias = 0.1 * jax.random.normal(ks[2], (E,), jnp.float32)
    stacks = [jax.random.normal(kk, dims, jnp.float32) * 0.1
              for kk, dims in zip(ks[3:], ((1, E, h, i), (1, E, h, i),
                                           (1, E, i, h)))]
    kw = dict(top_k=k, layer=jnp.int32(0), positions=N,
              router_score="sigmoid", router_bias=bias, routed_scale=2.5)
    pallas_paged.set_flash_enabled(True)
    try:
        uncut, work = moe.moe_mlp(x, rw, *stacks, **kw)
        assert int(work.rounds) == 0 and int(work.held_rows) == 0
        total, landed = jnp.zeros_like(uncut), 0
        for chip in range(chips):
            mine = [w[:, chip * held:(chip + 1) * held] for w in stacks]
            part, work = moe.moe_mlp(x, rw, *mine,
                                     expert_offset=chip * held, **kw)
            block = moe.held_block(N, k, held, E)
            assert int(work.rounds) == -(-int(work.held_rows) // block)
            assert int(work.experts_read) <= held * int(work.rounds)
            total, landed = total + part, landed + int(work.held_rows)
    finally:
        pallas_paged.set_flash_enabled(None)
    assert landed == N * k
    assert worst(total, uncut) < 1e-5 * float(jnp.abs(uncut).max())


@pytest.mark.parametrize("path", ["exact", "dispatch", "list", "grouped"])
def test_every_expert_path_adds_only_the_held_experts_part(path):
    """Experts 4-7 of a router of 8 held here (debug-dsa's share): each
    strategy of ops/moe.py gives the sum over the held experts alone at
    the router's weights, and counts held experts only."""
    E_all, held, first, k, h, i = 8, 4, 4, 2, 128, 128
    N, positions = (16, 1) if path in ("exact", "list") else (64, 64)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (N, h), jnp.float32)
    rw = jax.random.normal(ks[1], (h, E_all), jnp.float32) * 0.3
    stacks = [jax.random.normal(kk, dims, jnp.float32) * 0.1
              for kk, dims in zip(ks[2:], ((1, E_all, h, i), (1, E_all, h, i),
                                           (1, E_all, i, h)))]
    mine = [w[:, first:first + held] for w in stacks]
    top_p, top_i = moe.route(x, rw, k)
    want = np.zeros((N, h), np.float32)
    for t in range(N):
        for w, e in zip(np.asarray(top_p[t]), np.asarray(top_i[t])):
            if first <= e < first + held:
                g, u, d = (np.asarray(s[0, e]) for s in stacks)
                a = np.asarray(x[t]) @ g
                want[t] += w * ((a / (1 + np.exp(-a))
                                 * (np.asarray(x[t]) @ u)) @ d)
    hit = {int(e) for e in np.asarray(top_i).ravel()
           if first <= e < first + held}
    kw = dict(top_k=k, expert_offset=first, positions=positions)
    if path in ("list", "grouped"):
        pallas_paged.set_flash_enabled(True)
        try:
            got, work = moe.moe_mlp(x, rw, *mine, layer=jnp.int32(0),
                                    exact=True if path == "list" else None,
                                    **kw)
        finally:
            pallas_paged.set_flash_enabled(None)
        assert int(work.experts_read) == len(hit)
    else:
        got, work = moe.moe_mlp(
            x, rw, *(w[0] for w in mine), exact=path == "exact",
            capacity_factor=8.0, **kw)
        assert int(work.experts_read) == held
    assert np.abs(np.asarray(got) - want).max() < 1e-4


# ---------------------------------------------------------------------
# the engine: both pools under one block table
# ---------------------------------------------------------------------

def _engine(**kw):
    base = dict(model="debug-dsa", max_model_len=128, max_num_seqs=4,
                prefill_chunk=32, prefill_buckets=(32,), decode_window=4,
                kv_block_size=16)
    base.update(kw)
    return LLMEngine(EngineConfig(**base))


def _run_all(eng, prompts, max_tokens=16):
    opts = SamplingOptions(temperature=0.0, max_tokens=max_tokens,
                           ignore_eos=True)
    ids = [eng.add_request(list(p), opts) for p in prompts]
    pending, guard = set(ids), 0
    while pending:
        pending -= {o.seq_id for o in eng.step() if o.finished}
        guard += 1
        assert guard < 2000, "engine did not converge"
    return [list(eng.seqs[i].output_tokens) for i in ids]


def test_preemption_and_block_reuse_leave_no_stale_index_key():
    """A pool too small for every admitted sequence preempts and hands
    blocks on, latents and index keys at once (one block id names a
    block of both pools); greedy outputs match an unconstrained run.
    A repeated prompt then attaches the finished one's blocks by
    reference and decodes the same tokens."""
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(1, 250, size=40)) for _ in range(4)]
    # two slots: a prefill of either row count holds at most 64 tokens,
    # which every expert strategy computes exactly (no capacity drops
    # to tell a burst from a lone chunk); float32 activations AND
    # pools: a bfloat16 pool rounds a key that differs in its last
    # float32 bit to another value, and a selection then falls to
    # another position
    exact = dict(max_num_seqs=2, dtype="float32", kv_dtype="float32")
    want = _run_all(_engine(**exact), prompts, max_tokens=40)
    # 8 blocks of 16: two rows of 40 + 40 tokens want 10
    tight = _engine(**exact, kv_pool_tokens=128,
                    enable_prefix_caching=True)
    assert _run_all(tight, prompts, max_tokens=40) == want
    assert tight.metrics.preemptions._value.get() > 0
    again = _run_all(tight, prompts[:1], max_tokens=40)
    assert again == want[:1] and tight.block_mgr.hits >= 1


def test_debug_perf_counts_both_pools_and_the_selection():
    eng = _engine(max_num_seqs=2)
    _run_all(eng, [list(range(1, 51))], max_tokens=8)
    pool = eng.block_mgr.frag_report()
    mc = eng.model_cfg
    # 3 layers x (256 latent values padded to whole lanes + 32 index
    # values) x 2 bytes
    assert pool["layout"] == "latent+index"
    assert pool["index_bytes_per_token"] == 3 * 32 * 2
    assert pool["bytes_per_token"] == 3 * (256 + 32) * 2
    totals = eng.eff.report()
    sparse = totals["sparse"]
    # 50 prompt tokens and the 7 decode steps' queries that ran: every
    # query's context scored, 16 of it attended past the 16th token
    ran = sparse["prefill"]["queries"] + sparse["decode"]["queries"]
    assert sparse["prefill"]["queries"] == 50 and ran >= 57
    assert sparse["keys_scored"] == sparse["keys_in_context"]
    first = sum(min(p + 1, 16) for p in range(50))
    assert sparse["prefill"]["keys_attended"] == first
    assert sparse["keys_attended"] == first + 16 * sparse["decode"][
        "queries"]
    assert totals["moe"]["experts_resident"] % (mc.num_layers
                                                * mc.num_experts) == 0
    paths = eng.runner.attention_paths
    assert paths and all(v == "jnp_gather" for v in paths.values())
    # two chunks (32 + 18 tokens), on the one path the CPU has
    assert totals["prefill"]["chunks_by_path"] == {"jnp_gather": 2}


@ON
def test_debug_perf_counts_the_chunks_by_their_attention_path(kernels):
    """totals.prefill.chunks_by_path: two prompts of 40 tokens in
    chunks of 32 and 8. The 32 bucket is past the rule's cut at these
    widths (26.9) and its executables attend expanded, the 16 bucket's
    absorbed; each chunk counts under the path of the executable that
    ran it (device.attention_paths), with ``_sparse`` where its kv
    bucket selects."""
    eng = _engine(max_num_seqs=2, prefill_buckets=(16, 32))
    _run_all(eng, [list(range(1, 41)), list(range(3, 43))], max_tokens=2)
    by_path = eng.eff.report()["prefill"]["chunks_by_path"]
    prefills = {k: v for k, v in eng.runner.attention_paths.items()
                if k.startswith("prefill|")}
    assert set(by_path) <= set(prefills.values())
    assert {k.split("|")[1]: "_expanded" in v
            for k, v in prefills.items()} == {"16": False, "32": True}
    assert sum(n for p, n in by_path.items() if "_expanded" in p) == 2
    assert sum(n for p, n in by_path.items() if "_expanded" not in p) == 2
    assert all(p.startswith("pallas_paged_latent") for p in by_path)


# ---------------------------------------------------------------------
# the configuration: mapping, counts, refusals by name
# ---------------------------------------------------------------------

def _glm5() -> dict:
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "glm-5-int8-l7-e16.json")) as f:
        return json.load(f)


def test_from_hf_config_maps_glm_moe_dsa_and_its_share():
    cfg = ModelConfig.from_hf_config(_glm5(), name="glm-5")
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        32, 128, 2048)
    assert (cfg.num_experts, cfg.router_experts_, cfg.expert_offset) == (
        16, 256, 0)
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.vocab_size) == (
        7, 1, 19360)
    assert cfg.rope_theta == 1000000 and cfg.rope_scaling is None
    assert cfg.routed_scaling_factor == 2.5 and cfg.mla
    # ISSUE 40's reckoning: 400.9 + 6 x 817.7 + 237.9 M
    assert round(cfg.num_params / 1e9, 2) == 5.55
    shapes = jax.eval_shape(lambda: llama.init_params(
        cfg, jax.random.PRNGKey(0), quantization="int8"))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
               if a.dtype == jnp.int8 or a.ndim >= 3
               or a.shape == (cfg.hidden_size,))
    assert abs(held - cfg.num_params) < 0.001 * cfg.num_params
    third = ModelConfig.from_hf_config(
        {**_glm5(), "deployment": {"chips_per_layer": 16, "chip_index": 3,
                                   "router_experts": 256}})
    assert third.expert_offset == 48
    # GLM-4.7-Flash's file maps as before: no indexer, every expert
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "glm-4.7-flash-int8-l13.json")) as f:
        flash = ModelConfig.from_hf_config(json.load(f))
    assert (flash.index_topk, flash.router_experts, flash.expert_offset,
            flash.router_experts_) == (0, 0, 0, 64)


@pytest.mark.parametrize("change,message", [
    ({"deployment": {"chips_per_layer": 8, "chip_index": 0,
                     "router_experts": 256}}, "does not make the router"),
    ({"deployment": {"chips_per_layer": 16, "chip_index": 16,
                     "router_experts": 256}}, "chip_index 16"),
    ({"index_n_heads": 0}, "index_topk without"),
    ({"n_group": 8}, "grouped routing"),
    ({"topk_method": "greedy"}, "topk_method")])
def test_from_hf_config_refuses_by_name(change, message):
    with pytest.raises(ValueError, match=message):
        ModelConfig.from_hf_config({**_glm5(), **change})


def test_what_the_tree_does_not_build_with_an_indexer_is_refused():
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.kvcache.connector import (KVConnector,
                                                        KVTransferConfig)
    from production_stack_tpu.models import hf_loader
    small = dict(model="debug-dsa", max_model_len=64, max_num_seqs=2,
                 kv_block_size=16)
    with pytest.raises(ValueError, match="speculative decoding"):
        ModelRunner(get_config("debug-dsa"), EngineConfig(
            **small, speculative_ngram_tokens=2))
    with pytest.raises(ValueError, match="no int8 form"):
        ModelRunner(get_config("debug-dsa"),
                    EngineConfig(**small, kv_dtype="int8"))
    runner = ModelRunner(get_config("debug-dsa"), EngineConfig(**small))
    assert runner.cache.layout == "latent+index"
    with pytest.raises(ValueError, match="latent\\+index"):
        runner.extract_chunk(0, 0, 16)
    with pytest.raises(ValueError, match="latent\\+index"):
        KVConnector(runner, runner.model_cfg, runner.engine_cfg,
                    KVTransferConfig.from_dict({"kv_role": "kv_both",
                                                "local_cpu_gb": 0.01}))
    with pytest.raises(NotImplementedError, match="glm4_moe_lite"):
        hf_loader.params_from_state_dict(get_config("debug-dsa"), {})
    with pytest.raises(ValueError, match="more than index_topk"):
        llama.encode(runner.params, get_config("debug-dsa"),
                     jnp.zeros((1, 40), jnp.int32))
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2, 1),
                ("dp", "tp", "ep"))
    with pytest.raises(ValueError, match="one chip only"):
        ModelRunner(get_config("debug-dsa"), EngineConfig(**small),
                    mesh=mesh)


def test_a_launcher_that_warms_the_full_batch_runs_wide_chunks_by_row():
    """A chunk bucket wider than a full-batch dispatch is built for
    (EngineConfig.FULL_BATCH_CHUNK_TOKENS) is dispatched a row at a
    time whatever is due, and runner.prefill, asked for all rows at
    once (a benchmark's warm-up), serves them by the one-row
    executable. Chunks up to 512 tokens dispatch as before, at any
    number of slots."""
    cfg = EngineConfig(model="debug-dsa", max_num_seqs=8,
                       max_model_len=16384, prefill_chunk=2048)
    assert cfg.prefill_rows_for(8, 2048) == 1
    assert cfg.prefill_rows_for(8, 1024) == 1
    assert cfg.prefill_rows_for(8, 512) == 8
    assert cfg.prefill_rows_for(2, 512) == 1
    for seqs in (16, 32, 64):
        many = EngineConfig(model="debug-tiny", max_num_seqs=seqs)
        assert many.prefill_rows_for(seqs, 512) == seqs
        assert many.prefill_rows_for(seqs) == seqs
    from production_stack_tpu.engine.runner import ModelRunner
    from production_stack_tpu.engine.sampler import SamplingParams
    small = EngineConfig(model="debug-dsa", max_model_len=64,
                         max_num_seqs=4, kv_block_size=16,
                         prefill_chunk=32)
    small.FULL_BATCH_CHUNK_TOKENS = 16          # a chunk of 32 passes it
    runner = ModelRunner(get_config("debug-dsa"), small)
    ids, lps, tops, rows = runner.prefill(
        np.ones((4, 32), np.int32), np.zeros((4,), np.int32),
        np.full((4,), 20, np.int32), SamplingParams.filled(4), 64)
    assert ids.shape == (4,) and lps.shape == (4,) and tops is None
    assert {k[0] for k in runner._prefill_fns} == {1}
    # the experts' counts, the four dispatches' summed: rows
    # multiplied, assignments kept, rounds (kernels off here: none)
    assert rows.shape == (3,) and int(rows[0]) > 0


def test_rehearsal_of_the_cell_at_a_tiny_file(tmp_path):
    """The benchmark's new cell in shape on the CPU, end to end through
    router and engine (tests/chipbench/rehearsal/BENCHMARK.sparse.json):
    both pools behind the program's normal server entry point, the
    probe against chipbench/references/glm_moe_dsa.py with the
    selection at work (contexts of 33-137 tokens keep 16), and the
    counter metrics in a traced line (no device metric from a CPU run).
    From a tree of links, so that the run keeps its ``.chipbench/``
    (warm list, engine log, trace) to itself: the rehearsals under
    tests/chipbench/ share the repo's and read each other's engine log
    when the suite's workers run two side by side."""
    base = os.path.join(ROOT, "tests", "chipbench", "rehearsal")
    for name in ("chipbench", "production_stack_tpu", "tests",
                 "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--manifest",
         os.path.join(base, "BENCHMARK.sparse.json"), "--data", base,
         "--rehearse", "--workload", "tiny-dsa-closed", "--seed",
         str(2**31 + 78), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["why"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["probe"]["ok"] and len(line["probe"]["rows"]) == 3
    got = line["metrics"]
    # 3 layers x 32 index values x 2 bytes
    assert got["index_bytes_per_token"]["value"] == 3 * 32 * 2
    assert 0 < got["sparse_attended_share"]["value"] < 100
    assert got["compiles_in_window"]["value"] == 0
    assert not set(got) & {"sparse_decode_step_roofline",
                           "sparse_prefill_chunk_roofline",
                           "indexer_kernel_roofline",
                           "sparse_attention_kernel_roofline",
                           "device_idle_share"}
