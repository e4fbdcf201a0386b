"""Sliding-window attention (Mistral v0.1 / Gemma-2-style local
attention): jnp path vs HF transformers parity, pallas kernel parity
in interpret mode, and engine e2e on the debug-sliding preset."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from production_stack_tpu.models import ModelConfig, llama, make_slot_cache
from production_stack_tpu.models.kv import write_chunk, gather_view
from tests.whole_pool import WHOLE, call as _call


def test_hf_mistral_sliding_parity():
    """Our windowed forward == transformers MistralForCausalLM (eager)
    on a context LONGER than the window, so the window actually
    bites."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from production_stack_tpu.models.hf_loader import params_from_state_dict

    W = 16
    hf_cfg = transformers.MistralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128,
        rms_norm_eps=1e-5, rope_theta=10000.0, sliding_window=W,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = transformers.MistralForCausalLM(hf_cfg).eval().to(
        torch.float32)
    cfg = ModelConfig(
        name="tiny-mistral", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=128, sliding_window=W,
        dtype=jnp.float32)
    params = params_from_state_dict(cfg, hf_model.state_dict())

    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 3 * W))
    with torch.no_grad():
        ref = hf_model(torch.tensor(toks)).logits.numpy()
    ours = np.asarray(llama.forward_train(params, cfg,
                                          jnp.asarray(toks)))
    np.testing.assert_allclose(ours, ref, atol=1e-2, rtol=0)
    # sanity: the window changed the function (vs the unwindowed cfg)
    import dataclasses
    full = np.asarray(llama.forward_train(
        params, dataclasses.replace(cfg, sliding_window=None),
        jnp.asarray(toks)))
    assert np.abs(full - ref).max() > 1e-3


def test_hf_config_parses_sliding_window():
    from production_stack_tpu.models.config import ModelConfig as MC
    cfg = MC.from_hf_config({
        "model_type": "mistral", "vocab_size": 32000,
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_hidden_layers": 32, "num_attention_heads": 32,
        "num_key_value_heads": 8, "sliding_window": 4096})
    assert cfg.sliding_window == 4096
    cfg = MC.from_hf_config({
        "model_type": "mistral", "vocab_size": 32000,
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_hidden_layers": 32, "num_attention_heads": 32,
        "num_key_value_heads": 8, "sliding_window": None})
    assert cfg.sliding_window is None


@pytest.mark.parametrize("T,pairs", [
    pytest.param(1, False, id="1"), pytest.param(5, False, id="5"),
    pytest.param(48, False, id="48"), pytest.param(1, True, id="1-pairs"),
    pytest.param(5, True, id="5-pairs"),
    pytest.param(48, True, id="48-pairs")])
@WHOLE
def test_paged_kernels_windowed_parity(T, pairs, layer):
    """Both pallas kernels with a window (interpret, CPU) match the
    windowed jnp reference through shuffled tables. ``pairs``: the
    shapes differential attention serves them at (Phi-4-mini-flash's,
    scaled down: models/llama._diff_attention): a pool head holds
    [k1 | k2], keys of hd side by side, and ONE value of 2 hd; four
    query heads a pool head, q1 padded to [q1 | 0] and q2 to [0 | q2];
    the reference attends each query head over ITS key of hd and the
    value of 2 hd, two query groups a key."""
    from production_stack_tpu.ops.attention import attention_with_cache
    from production_stack_tpu.ops.pallas_paged import (
        paged_attention, paged_decode_attention)

    B, Hkv, G, Bs, D, W = (2, 2, 4, 16, 64, 24) if pairs \
        else (2, 2, 2, 16, 32, 24)
    H = Hkv * G
    lens = [70, 40]
    key = jax.random.PRNGKey(T)
    MB = -(-(max(lens) + T + 1) // Bs) + 1
    n_blocks = B * MB + 1
    k_pool = jax.random.normal(key, (n_blocks, Hkv, Bs, D), jnp.float32)
    v_pool = jax.random.normal(jax.random.fold_in(key, 1),
                               (n_blocks, Hkv, Bs, D), jnp.float32)
    perm = np.asarray(jax.random.permutation(
        jax.random.fold_in(key, 2), n_blocks - 1)[:B * MB]) + 1
    tables = jnp.asarray(perm.reshape(B, MB), jnp.int32)
    starts = jnp.asarray(lens, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 3),
                          (B, T, H, D), jnp.float32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    newk = jax.random.normal(jax.random.fold_in(key, 4),
                             (B, T, Hkv, D), jnp.float32)
    newv = jax.random.normal(jax.random.fold_in(key, 5),
                             (B, T, Hkv, D), jnp.float32)
    k_pool = write_chunk(k_pool, newk, tables, positions)
    v_pool = write_chunk(v_pool, newv, tables, positions)
    nb = -(-(max(lens) + T) // Bs)

    k_att = gather_view(k_pool, tables, nb)
    v_att = gather_view(v_pool, tables, nb)
    scale = D ** -0.5
    if pairs:
        hd, scale = D // 2, (D // 2) ** -0.5
        small = q[..., :hd]             # the heads' own queries of hd
        half = (jnp.arange(H) % 2)[:, None] == jnp.arange(2)[None, :]
        q = (small[..., None, :] * half[:, :, None]).reshape(B, T, H, D)
        # query head h reads key (h % 2) of pool head h // G
        k_own = jnp.stack(
            [k_att[:, :, h // G, (h % 2) * hd:(h % 2 + 1) * hd]
             for h in range(H)], axis=2)
        want = attention_with_cache(
            small, k_own, jnp.repeat(v_att, G, axis=2), positions,
            scale=scale, sliding_window=W)
    else:
        want = attention_with_cache(q, k_att, v_att, positions,
                                    sliding_window=W)
    fn = paged_decode_attention if T <= 8 else paged_attention
    got = _call(fn, q, k_pool, v_pool, tables, starts, nb=nb, window=W,
                interpret=True, layer=layer, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_engine_e2e_sliding_window():
    """debug-sliding (window 64) through the full engine: generation
    past the window runs, is deterministic, and DIFFERS from the same
    weights without a window once the context exceeds it."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    def run(model):
        cfg = EngineConfig(model=model, max_model_len=256,
                           max_num_seqs=2, prefill_chunk=32,
                           prefill_buckets=(32,), decode_window=4)
        eng = LLMEngine(cfg)
        opts = SamplingOptions(temperature=0.0, max_tokens=40,
                               ignore_eos=True)
        sid = eng.add_request(list(range(3, 103)), opts)   # 100 > 64
        guard = 0
        while True:
            for out in eng.step():
                if out.seq_id == sid and out.finished:
                    return eng.seqs[sid].output_tokens
            guard += 1
            assert guard < 500

    a = run("debug-sliding")
    b = run("debug-sliding")
    assert a == b and len(a) == 40
    # same seed => same random weights; only the window differs
    c = run("debug-tiny")
    assert a != c


def test_hf_llama31_rope_scaling_parity():
    """Our llama3 rope warp == transformers' _compute_llama3_parameters
    on a tiny Llama with rope_scaling, past the original max positions
    so the warp matters."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from production_stack_tpu.models.hf_loader import params_from_state_dict

    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=256,
        rms_norm_eps=1e-5, rope_theta=10000.0,
        rope_scaling={"rope_type": "llama3", "factor": 4.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 64},
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(1)
    hf_model = transformers.LlamaForCausalLM(hf_cfg).eval().to(
        torch.float32)
    cfg = ModelConfig(
        name="tiny-llama31", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=256,
        rope_scaling=("llama3", 4.0, 1.0, 4.0, 64),
        dtype=jnp.float32)
    params = params_from_state_dict(cfg, hf_model.state_dict())

    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 150))  # > orig 64
    import torch as _t
    with _t.no_grad():
        ref = hf_model(_t.tensor(toks)).logits.numpy()
    ours = np.asarray(llama.forward_train(params, cfg,
                                          jnp.asarray(toks)))
    np.testing.assert_allclose(ours, ref, atol=1e-2, rtol=0)
    # the warp changed the function vs unscaled rope
    import dataclasses
    plain = np.asarray(llama.forward_train(
        params, dataclasses.replace(cfg, rope_scaling=None),
        jnp.asarray(toks)))
    assert np.abs(plain - ref).max() > 1e-3


def test_hf_config_parses_rope_scaling():
    from production_stack_tpu.models.config import ModelConfig as MC
    base = {"model_type": "llama", "vocab_size": 128256,
            "hidden_size": 4096, "intermediate_size": 14336,
            "num_hidden_layers": 32, "num_attention_heads": 32,
            "num_key_value_heads": 8}
    cfg = MC.from_hf_config({**base, "rope_scaling": {
        "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
        "high_freq_factor": 4.0,
        "original_max_position_embeddings": 8192}})
    assert cfg.rope_scaling == ("llama3", 8.0, 1.0, 4.0, 8192.0)
    cfg = MC.from_hf_config({**base, "rope_scaling": {
        "type": "linear", "factor": 2.0}})
    assert cfg.rope_scaling == ("linear", 2.0)
    with pytest.raises(ValueError):
        MC.from_hf_config({**base, "rope_scaling": {
            "rope_type": "yarn", "factor": 2.0}})


def test_rolling_kv_frees_behind_window():
    """Every-layer-windowed models (debug-sliding, W=64) free KV
    blocks behind the window as generation advances: a pool FAR
    smaller than the worst case serves a long generation without
    preemption, and the stream is identical to a big-pool run."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    def run(pool_tokens):
        cfg = EngineConfig(model="debug-sliding", max_model_len=512,
                           max_num_seqs=2, prefill_chunk=32,
                           prefill_buckets=(32,), decode_window=4,
                           kv_block_size=16,
                           kv_pool_tokens=pool_tokens,
                           # float32: the stream's closest greedy
                           # choice stands 1.6e-4 apart, a rounding
                           # 1e-6. In bfloat16 the two are level and
                           # one run in eight under load takes another
                           # token, big pool or small (PR 45)
                           dtype="float32", kv_dtype="float32")
        eng = LLMEngine(cfg)
        opts = SamplingOptions(temperature=0.0, max_tokens=300,
                               ignore_eos=True)
        # TWO concurrent long sequences: worst case 2 x 332 = 664
        # tokens of KV against a pool EngineConfig clamps to 512 (one
        # max_model_len) — only rolling lets both finish unpreempted
        sids = [eng.add_request(list(range(3 + j, 35 + j)), opts)
                for j in range(2)]
        pending = set(sids)
        guard = 0
        while pending:
            pending -= {o.seq_id for o in eng.step() if o.finished}
            guard += 1
            assert guard < 4000
        metrics = eng.metrics.render().decode()
        preempt = 0.0
        for line in metrics.splitlines():
            if line.startswith("vllm:num_preemptions_total"):
                preempt = float(line.rsplit(" ", 1)[1])
        return ([eng.seqs[s].output_tokens for s in sids],
                max(eng.seqs[s].rolled_blocks for s in sids), preempt)

    small_toks, rolled, preemptions = run(512)
    big_toks, _, _ = run(None)
    assert rolled > 0, "no blocks rolled behind the window"
    # the feature's point: the small pool serves BOTH generations by
    # ROLLING, not by preempt/recompute churn
    assert preemptions == 0, preemptions
    assert small_toks == big_toks
    assert all(len(t) == 300 for t in small_toks)


def test_rolling_kv_skips_finish_registration():
    """PROMPT blocks register at prefill time (live sharing — they are
    contiguous and final when written, even if later rolled away), but
    a rolled sequence must NOT register its output chain at finish:
    the chain's early blocks are gone, so those keys would be
    unreachable at best."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    cfg = EngineConfig(model="debug-sliding", max_model_len=512,
                       max_num_seqs=2, prefill_chunk=32,
                       prefill_buckets=(32,), decode_window=4,
                       kv_block_size=16, enable_prefix_caching=True)
    eng = LLMEngine(cfg)
    opts = SamplingOptions(temperature=0.0, max_tokens=200,
                           ignore_eos=True)
    sid = eng.add_request(list(range(3, 35)), opts)    # 2 full blocks
    keys_after_prefill = None
    done = False
    guard = 0
    while not done:
        for out in eng.step():
            if out.seq_id == sid and out.finished:
                done = True
        if keys_after_prefill is None and eng.seqs[sid].output_tokens:
            keys_after_prefill = set(eng.block_mgr._by_key)
        guard += 1
        assert guard < 2000
    assert eng.seqs[sid].rolled_blocks > 0
    assert len(keys_after_prefill) == 2    # the prompt's full blocks
    assert set(eng.block_mgr._by_key) == keys_after_prefill, \
        "rolled sequence registered output-chain keys at finish"
