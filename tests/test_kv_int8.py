"""int8 KV cache (models/kv.py quantized pool): quantization error
bounds, paged write/read roundtrips, forward-logits closeness vs the
bf16 cache, tier extract/inject re-quantization, and engine e2e.

The reference ecosystem's analog is vLLM's quantized KV cache
(--kv-cache-dtype fp8); on TPU the natural payload is int8 with
per-(token, head) scales (MXU/VPU native, models/kv.quantize_chunk).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from production_stack_tpu.models.kv import (
    KVCache, append_chunk, append_chunk_q, gather_view, gather_view_q,
    make_cache, quantize_chunk, write_chunk, write_chunk_q)
from tests.whole_pool import WHOLE, call as _call


def test_quantize_chunk_error_bound():
    """Symmetric per-vector int8: |dequant - x| <= amax/127 (half a
    quantization step would be /254; rounding gives one full step at
    the clip boundary)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 64),
                          jnp.float32)
    q, s = quantize_chunk(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 5, 3)
    deq = q.astype(jnp.float32) * s[..., None]
    bound = np.asarray(jnp.max(jnp.abs(x), axis=-1) / 127.0)
    err = np.asarray(jnp.max(jnp.abs(deq - x), axis=-1))
    assert (err <= bound + 1e-7).all()


def test_write_gather_roundtrip_q():
    """write_chunk_q + gather_view_q reproduce the written vectors
    within the per-vector quantization bound, at the right virtual
    positions, through shuffled tables."""
    L, N, Hkv, Bs, D = 1, 16, 2, 8, 32
    cache = make_cache(L, N, Bs, Hkv, D, dtype=jnp.int8)
    rng = np.random.default_rng(1)
    tables = jnp.asarray(
        1 + rng.permutation(N - 1)[:8].reshape(2, 4), jnp.int32)
    B, T = 2, 5
    positions = jnp.asarray([[3, 4, 5, 6, 7], [10, 11, 12, 13, 14]],
                            jnp.int32)
    new = jax.random.normal(jax.random.PRNGKey(2), (B, T, Hkv, D),
                            jnp.float32)
    layer, scales = write_chunk_q(cache.k[0], cache.ks[0], new, tables,
                                  positions)
    view = gather_view_q(layer, scales, tables, nb=4, dtype=jnp.float32)
    for b in range(B):
        for t in range(T):
            got = np.asarray(view[b, int(positions[b, t])])
            want = np.asarray(new[b, t])
            bound = np.abs(want).max(axis=-1, keepdims=True) / 127 + 1e-6
            assert (np.abs(got - want) <= bound).all()


KV_DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.int8], ids=["kv_bf16", "kv_int8"])


@KV_DTYPES
@pytest.mark.parametrize("T", [1, 5, 16, 40])
def test_append_chunk_is_write_chunk_on_that_layer(T, dtype):
    """append_chunk on the whole pool (the serving path's write: whole
    blocks gathered, merged, scattered back) leaves in every block a
    table references what write_chunk leaves on that layer's slice,
    and nothing anywhere else: other layers untouched, and tokens that
    are invalid, parked past the capacity or before position 0 land in
    no referenced block. decode step, speculative window across a
    block boundary, one whole block, a prefill chunk over four."""
    L, N, Hkv, Bs, D, MB, B = 3, 21, 2, 16, 8, 5, 4
    rng = np.random.default_rng(T)
    tables = jnp.asarray(
        1 + rng.permutation(N - 1).reshape(B, MB), jnp.int32)
    cache = make_cache(L, N, Bs, Hkv, D, dtype=dtype)
    # a pool that already holds something, in every layer
    cache = KVCache(*(jnp.asarray(rng.integers(-99, 99, a.shape), a.dtype)
                      for a in cache if a is not None))
    new = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
    # row 0 mid-block; row 1 runs over the capacity MB*Bs; row 2 is
    # parked at it; row 3 starts before position 0
    starts = jnp.asarray([Bs - 2, MB * Bs - T // 2 - 1, MB * Bs, -2],
                         jnp.int32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    valid = jnp.asarray(rng.random((B, T)) < 0.8)
    layer = jnp.int32(1)

    for v in (valid, None):
        if cache.quantized:
            got = append_chunk_q(cache.k, cache.ks, new, tables, starts,
                                 v, layer)
            want = write_chunk_q(cache.k[1], cache.ks[1], new, tables,
                                 positions, v)
            before = (cache.k, cache.ks)
        else:
            got = (append_chunk(cache.k, new, tables, starts, v, layer),)
            want = (write_chunk(cache.k[1], new, tables, positions, v),)
            before = (cache.k,)
        for g, w, b in zip(got, want, before):
            g, w, b = np.asarray(g), np.asarray(w), np.asarray(b)
            # block 0 is the trash block: written or not, never read
            np.testing.assert_array_equal(g[1, 1:], w[1:])
            np.testing.assert_array_equal(g[[0, 2]], b[[0, 2]])


@KV_DTYPES
def test_layer_scan_carries_the_pool(dtype):
    """The structure that keeps the pool one buffer on the chip
    (models/kv.py: carried, never stacked): in llama.forward's layer
    scan every pool-shaped array is a carry, and none goes in as a
    per-layer input or comes out as a stacked output. XLA cannot alias
    a scan's xs with its ys: handed through them, a layer's pool was
    copied per layer and the whole pool per step."""
    from production_stack_tpu.models import llama
    from production_stack_tpu.models.config import get_config

    cfg = get_config("debug-tiny")
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: make_cache(
        cfg.num_layers, 9, 8, cfg.num_kv_heads, cfg.head_dim_, dtype))
    tokens = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: llama.forward(p, cfg, t, t, c)
    )(params, tokens, cache)

    pools = {a.shape for a in cache if a is not None}
    per_layer = {s[1:] for s in pools}
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == cfg.num_layers]
    assert len(scans) == 1
    scan, = scans
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shapes = [v.aval.shape for v in scan.invars]
    carried = shapes[n_consts:n_consts + n_carry]
    assert sorted(s for s in carried if s in pools) == sorted(
        a.shape for a in cache if a is not None)
    # neither closed over nor sliced per layer on the way in
    for s in shapes[:n_consts] + shapes[n_consts + n_carry:]:
        assert s not in pools, s
    # nor stacked per layer on the way out
    for v in scan.outvars[n_carry:]:
        assert v.aval.shape not in pools, v.aval
    for v in scan.params["jaxpr"].jaxpr.outvars[n_carry:]:
        assert v.aval.shape not in per_layer, v.aval


def test_forward_logits_close_to_bf16_cache():
    """A chunked forward through the int8 pool stays close to the
    fp32-cache logits: the per-vector quant error is ~0.4% of each
    K/V vector's amax, and attention averages it further."""
    from production_stack_tpu.models import llama
    from production_stack_tpu.models.config import get_config

    cfg = get_config("debug-tiny")
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    B, T = 2, 24
    Bs = 8
    n_blocks = 2 * (-(-64 // Bs)) + 1
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(1, cfg.vocab_size, (B, T)),
        jnp.int32)
    positions = jnp.tile(jnp.arange(T)[None, :], (B, 1))
    from production_stack_tpu.models.kv import linear_tables
    tables = linear_tables(B, 64, Bs)

    def run(dtype):
        cache = make_cache(cfg.num_layers, n_blocks, Bs,
                           cfg.num_kv_heads, cfg.head_dim_, dtype=dtype)
        logits, _, _ = llama.forward(params, cfg, tokens, positions, cache,
                                     block_tables=tables, kv_len=32)
        return np.asarray(logits, np.float32)

    ref = run(jnp.float32)
    got = run(jnp.int8)
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 0.05 * scale


def test_engine_e2e_int8_kv():
    """Full engine (chunked prefill, fused windows, slot recycling)
    on the int8 pool: correct token counts, deterministic greedy
    repeats."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    cfg = EngineConfig(model="debug-tiny", max_model_len=128,
                       max_num_seqs=2, prefill_chunk=32,
                       prefill_buckets=(32,), decode_window=4,
                       kv_dtype="int8")
    eng = LLMEngine(cfg)
    opts = SamplingOptions(temperature=0.0, max_tokens=12,
                           ignore_eos=True)
    ids = [eng.add_request(list(range(3 + i, 13 + i)), opts)
           for i in range(3)]   # 3 requests on 2 slots
    done = set()
    steps = 0
    while len(done) < 3:
        done.update(o.seq_id for o in eng.step() if o.finished)
        steps += 1
        assert steps < 500
    outs = [eng.seqs[i].output_tokens for i in ids]
    assert all(len(o) == 12 for o in outs)
    # greedy determinism on the quantized cache
    eng2 = LLMEngine(cfg)
    ids2 = [eng2.add_request(list(range(3 + i, 13 + i)), opts)
            for i in range(3)]
    done = set()
    while len(done) < 3:
        done.update(o.seq_id for o in eng2.step() if o.finished)
    assert [eng2.seqs[i].output_tokens for i in ids2] == outs


def test_extract_inject_roundtrip_int8():
    """Tier extract returns dequantized full-precision chunks; inject
    re-quantizes — a roundtrip stays within one quantization step of
    the injected values."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    cfg = EngineConfig(model="debug-tiny", max_model_len=128,
                       max_num_seqs=2, prefill_chunk=32,
                       prefill_buckets=(32,), decode_window=4,
                       kv_dtype="int8")
    eng = LLMEngine(cfg)
    opts = SamplingOptions(temperature=0.0, max_tokens=4, ignore_eos=True)
    sid = eng.add_request(list(range(5, 37)), opts)
    while not eng.seqs[sid].output_tokens:
        eng.step()
    slot = eng.seqs[sid].slot
    k, v = eng.runner.extract_chunk(slot, 0, 16)
    k = np.asarray(jax.device_get(k), np.float32)
    assert k.shape[1] == 16 and np.isfinite(k).all()
    # inject the extracted chunk back and re-extract: values survive a
    # quantize->dequantize roundtrip within one step per vector
    eng.runner.inject_chunk(slot, 0, jnp.asarray(k), jnp.asarray(
        np.asarray(jax.device_get(v), np.float32)))
    k2, _ = eng.runner.extract_chunk(slot, 0, 16)
    k2 = np.asarray(jax.device_get(k2), np.float32)
    bound = np.abs(k).max(axis=-1, keepdims=True) / 127 + 1e-3
    assert (np.abs(k2 - k) <= bound).all()


def _int8_pool_setup(key, B, n_blocks, Bs, Hkv, D, lens, T):
    """Random int8 pool (quantized from normal K/V), shuffled tables,
    plus the dense fp32 reference view."""
    kk, kv, kt = jax.random.split(key, 3)
    MB = max(-(-(int(max(lens)) + T + 1) // Bs), 1) + 1
    kf = jax.random.normal(kk, (n_blocks, Hkv, Bs, D), jnp.float32)
    vf = jax.random.normal(kv, (n_blocks, Hkv, Bs, D), jnp.float32)
    # quantize whole pools through the same per-vector recipe (axes:
    # [N, Hkv, Bs, D] -> amax over D)
    k8, ks = quantize_chunk(kf.transpose(0, 2, 1, 3))
    v8, vs = quantize_chunk(vf.transpose(0, 2, 1, 3))
    k8 = k8.transpose(0, 2, 1, 3)
    v8 = v8.transpose(0, 2, 1, 3)
    ks = ks.transpose(0, 2, 1)
    vs = vs.transpose(0, 2, 1)
    perm = np.asarray(
        jax.random.permutation(kt, n_blocks - 1)[:B * MB]) + 1
    tables = jnp.asarray(perm.reshape(B, MB), jnp.int32)
    return k8, v8, ks, vs, tables


@pytest.mark.parametrize("T", [1, 5, 48])
@WHOLE
def test_paged_kernels_int8_parity(T, layer):
    """Both pallas kernels in int8 mode (interpret, CPU) match the
    dequantized jnp reference exactly-ish: same dequantized values
    feed both paths, so tolerance is fp accumulation only."""
    from production_stack_tpu.ops.attention import attention_with_cache
    from production_stack_tpu.ops.pallas_paged import (
        paged_attention, paged_decode_attention)

    B, Hkv, G, Bs, D = 2, 2, 2, 16, 32
    H = Hkv * G
    lens = [40, 23]
    key = jax.random.PRNGKey(T)
    k8, v8, ks, vs, tables = _int8_pool_setup(
        key, B, n_blocks=64, Bs=Bs, Hkv=Hkv, D=D, lens=lens, T=T)
    starts = jnp.asarray(lens, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 9),
                          (B, T, H, D), jnp.float32)
    nb = -(-(max(lens) + T) // Bs)

    k_att = gather_view_q(k8, ks, tables, nb, dtype=jnp.float32)
    v_att = gather_view_q(v8, vs, tables, nb, dtype=jnp.float32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    want = attention_with_cache(q, k_att, v_att, positions)

    fn = paged_decode_attention if T <= 8 else paged_attention
    got = _call(fn, q, k8, v8, tables, starts, nb=nb, interpret=True,
                k_scales=ks, v_scales=vs, layer=layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@WHOLE
def test_paged_sharded_int8_parity(layer):
    """int8 kernels under a 2-device tp mesh (scales shard with the
    head axis)."""
    from jax.sharding import Mesh
    from production_stack_tpu.ops.attention import attention_with_cache
    from production_stack_tpu.ops.pallas_paged import (
        paged_attention_sharded)

    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("tp",))
    B, Hkv, G, Bs, D, T = 2, 2, 2, 16, 32, 1
    H = Hkv * G
    lens = [30, 17]
    key = jax.random.PRNGKey(21)
    k8, v8, ks, vs, tables = _int8_pool_setup(
        key, B, n_blocks=32, Bs=Bs, Hkv=Hkv, D=D, lens=lens, T=T)
    starts = jnp.asarray(lens, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 5),
                          (B, T, H, D), jnp.float32)
    nb = -(-(max(lens) + T) // Bs)
    k_att = gather_view_q(k8, ks, tables, nb, dtype=jnp.float32)
    v_att = gather_view_q(v8, vs, tables, nb, dtype=jnp.float32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    want = attention_with_cache(q, k_att, v_att, positions)
    got = _call(paged_attention_sharded, q, k8, v8, tables, starts,
                mesh, nb=nb, interpret=True, k_scales=ks, v_scales=vs,
                layer=layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_engine_int8_kv_with_flash_kernel():
    """Engine e2e with BOTH int8 KV and the paged kernels forced on
    (interpret, CPU): streams match the jnp int8 path exactly —
    the kernels read the same int8 blocks + scales."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions
    from production_stack_tpu.ops import pallas_paged

    def run(force_flash):
        pallas_paged.set_flash_enabled(force_flash)
        try:
            cfg = EngineConfig(model="debug-tiny", max_model_len=128,
                               max_num_seqs=2, prefill_chunk=32,
                               prefill_buckets=(16, 32), decode_window=4,
                               kv_block_size=16, kv_dtype="int8")
            eng = LLMEngine(cfg)
            opts = SamplingOptions(temperature=0.0, max_tokens=8)
            return [eng.generate(p, opts)
                    for p in ("int8 kernel probe", "second row")]
        finally:
            pallas_paged.set_flash_enabled(None)

    assert run(True) == run(False)


def test_mixed_kv_dtype_tier_handoff(tmp_path):
    """int8-KV producer -> bf16-KV consumer through a disk tier: the
    tier namespace is keyed on the WIRE dtype (always full precision),
    so chunks produced by a quantized engine are found and injected by
    a full-precision one (and greedy tokens agree within quant noise:
    here we assert the HIT, token equality is config-dependent)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    def cfg(role, kvd):
        return EngineConfig(
            model="debug-tiny", max_model_len=128, max_num_seqs=2,
            prefill_chunk=32, prefill_buckets=(32,), decode_window=4,
            dtype="float32", kv_dtype=kvd,
            kv_transfer_config={"kv_role": role, "chunk_size": 32,
                                "local_cpu_gb": 0,
                                "local_disk_path": str(tmp_path)})

    opts = SamplingOptions(temperature=0.0, max_tokens=4, ignore_eos=True)
    prompt = list(range(40, 104))

    producer = LLMEngine(cfg("kv_producer", "int8"))
    sid = producer.add_request(prompt, opts)
    while not producer.seqs[sid].output_tokens or \
            producer.scheduler.num_running:
        producer.step()
    producer.connector.flush()
    producer.close()

    consumer = LLMEngine(cfg("kv_consumer", "bfloat16"))
    sid = consumer.add_request(prompt, opts)
    while not consumer.seqs[sid].output_tokens or \
            consumer.scheduler.num_running:
        consumer.step()
    assert consumer.connector.hit_tokens > 0, (
        "bf16 consumer missed the int8 producer's tier chunks — wire "
        "namespace regressed to the pool dtype")
    consumer.close()
