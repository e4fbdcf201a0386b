"""Numerics parity of the paged flash kernel (ops/pallas_paged.py)
against the dense jnp path (gather_view + attention_with_cache) —
interpret mode on CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from production_stack_tpu.models.kv import make_cache, write_chunk, gather_view
from production_stack_tpu.ops.attention import attention_with_cache
from production_stack_tpu.ops.pallas_paged import (
    mesh_tp_only, paged_attention, paged_attention_sharded,
    paged_decode_attention)
from tests.whole_pool import WHOLE, call as _call


def _random_paged(key, B, n_blocks, Bs, Hkv, D, lens, t_extra=8,
                  dtype=jnp.float32):
    """A single-layer pool with SHUFFLED block assignment + tables."""
    kk, kv, kt = jax.random.split(key, 3)
    MB = max(-(-(int(max(lens)) + t_extra + 1) // Bs), 1) + 1
    k_pool = jax.random.normal(kk, (n_blocks, Hkv, Bs, D), dtype)
    v_pool = jax.random.normal(kv, (n_blocks, Hkv, Bs, D), dtype)
    # each row gets MB distinct non-trash blocks, shuffled across rows
    perm = np.asarray(
        jax.random.permutation(kt, n_blocks - 1)[:B * MB]) + 1
    tables = perm.reshape(B, MB).astype(np.int32)
    return k_pool, v_pool, jnp.asarray(tables)


def _reference(q, k_pool, v_pool, tables, starts, nb):
    k_att = gather_view(k_pool, tables, nb)
    v_att = gather_view(v_pool, tables, nb)
    T = q.shape[1]
    positions = starts[:, None] + jnp.arange(T)[None, :]
    return attention_with_cache(q, k_att, v_att, positions)


F32, BF16 = jnp.float32, jnp.bfloat16


def _paged_case(kernel, seed, T, G, Bs, D, Hkv, dtype, layer):
    """One kernel call against the dense path on a shuffled pool of
    three rows, the chunk's own K/V written first (write-then-attend).
    bf16 inputs (the serving dtype) are held to a bf16 tolerance: both
    sides accumulate in float32."""
    B, H = 3, Hkv * G
    key = jax.random.PRNGKey(seed)
    lens = [70, 33, 51]
    k_pool, v_pool, tables = _random_paged(
        key, B, n_blocks=64, Bs=Bs, Hkv=Hkv, D=D, lens=lens, t_extra=T,
        dtype=dtype)
    starts = jnp.asarray(lens, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 7), (B, T, H, D), dtype)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    newk = jax.random.normal(jax.random.fold_in(key, 8),
                             (B, T, Hkv, D), dtype)
    newv = jax.random.normal(jax.random.fold_in(key, 9),
                             (B, T, Hkv, D), dtype)
    k_pool = write_chunk(k_pool, newk, tables, positions)
    v_pool = write_chunk(v_pool, newv, tables, positions)

    # nb NOT a multiple of the decode kernel's blocks-per-step: the
    # ragged last group must mask correctly
    nb = -(-(max(lens) + T) // Bs)
    got = _call(kernel, q, k_pool, v_pool, tables, starts, nb=nb,
                interpret=True, layer=layer)
    want = _reference(q, k_pool, v_pool, tables, starts, nb)
    tol = 2e-2 if dtype == BF16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("T,G,Bs,D,Hkv,dtype", [
    (1, 4, 16, 32, 2, F32),      # decode window step, GQA
    (1, 1, 16, 32, 2, F32),      # decode, MHA (G == 1)
    (5, 4, 16, 32, 2, F32),      # speculative window (draft + 1)
    (48, 2, 16, 64, 2, F32),     # prefill chunk, ragged block boundary
    (40, 4, 16, 32, 1, F32),     # one kv head: row // G over every head
    (24, 2, 16, 128, 2, F32),    # head dim 128, as both benchmark cells
    (32, 2, 16, 64, 2, BF16),    # the serving dtype
])
@WHOLE
def test_paged_matches_dense(T, G, Bs, D, Hkv, dtype, layer):
    _paged_case(paged_attention, T * 1000 + G, T, G, Bs, D, Hkv, dtype,
                layer)


@pytest.mark.parametrize("T,G,Bs,D,Hkv,dtype", [
    (1, 4, 16, 32, 2, F32),      # decode window step, GQA
    (1, 1, 16, 32, 2, F32),      # decode, MHA (G == 1)
    (5, 4, 16, 32, 2, F32),      # speculative window (draft + 1)
    (8, 2, 16, 64, 2, F32),      # DECODE_T_MAX boundary
    (5, 4, 16, 32, 1, F32),      # one kv head
    (1, 4, 16, 128, 2, F32),     # head dim 128, as both benchmark cells
    (4, 2, 16, 64, 2, BF16),     # the serving dtype
])
@WHOLE
def test_paged_decode_matches_dense(T, G, Bs, D, Hkv, dtype, layer):
    """The wide decode kernel (all kv heads + R blocks per grid step)
    matches the dense jnp path on the same shuffled pools."""
    _paged_case(paged_decode_attention, T * 77 + G, T, G, Bs, D, Hkv,
                dtype, layer)


def test_paged_decode_short_row_isolation():
    """A short row must not read long rows' blocks through the group
    clamp (per-row jmax in the decode kernel's index maps)."""
    B, Hkv, G, Bs, D, T = 2, 2, 2, 16, 32, 1
    H = Hkv * G
    key = jax.random.PRNGKey(11)
    k_pool, v_pool, tables = _random_paged(
        key, B, n_blocks=32, Bs=Bs, Hkv=Hkv, D=D, lens=[90, 5])
    starts = jnp.asarray([90, 5], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, T, H, D), jnp.float32)
    positions = starts[:, None]
    newk = jax.random.normal(jax.random.fold_in(key, 2),
                             (B, T, Hkv, D), jnp.float32)
    newv = jax.random.normal(jax.random.fold_in(key, 3),
                             (B, T, Hkv, D), jnp.float32)
    k_pool = write_chunk(k_pool, newk, tables, positions)
    v_pool = write_chunk(v_pool, newv, tables, positions)
    nb = -(-(90 + T) // Bs)
    got = paged_decode_attention(q, k_pool, v_pool, tables, starts,
                                 nb=nb, interpret=True)
    want = _reference(q, k_pool, v_pool, tables, starts, nb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@WHOLE
def test_paged_decode_sharded_tp_parity(layer):
    """paged_attention_sharded routes short windows through the decode
    kernel; parity on a 2-device tp mesh."""
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("tp",))
    B, Hkv, G, Bs, D, T = 2, 2, 2, 16, 32, 1
    H = Hkv * G
    key = jax.random.PRNGKey(13)
    k_pool, v_pool, tables = _random_paged(
        key, B, n_blocks=24, Bs=Bs, Hkv=Hkv, D=D, lens=[20, 44])
    starts = jnp.asarray([20, 44], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 6),
                          (B, T, H, D), jnp.float32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    newk = jax.random.normal(jax.random.fold_in(key, 7),
                             (B, T, Hkv, D), jnp.float32)
    newv = jax.random.normal(jax.random.fold_in(key, 8),
                             (B, T, Hkv, D), jnp.float32)
    k_pool = write_chunk(k_pool, newk, tables, positions)
    v_pool = write_chunk(v_pool, newv, tables, positions)
    nb = -(-(44 + T) // Bs)
    got = _call(paged_attention_sharded, q, k_pool, v_pool, tables,
                starts, mesh, nb=nb, interpret=True, layer=layer)
    want = _reference(q, k_pool, v_pool, tables, starts, nb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_rows_independent_of_other_rows_length():
    """A short row's output must not see long rows' kv blocks (per-row
    causal clamp in the index map)."""
    B, Hkv, G, Bs, D, T = 2, 2, 2, 16, 32, 1
    H = Hkv * G
    key = jax.random.PRNGKey(0)
    k_pool, v_pool, tables = _random_paged(
        key, B, n_blocks=32, Bs=Bs, Hkv=Hkv, D=D, lens=[90, 5])
    starts = jnp.asarray([90, 5], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, T, H, D), jnp.float32)
    positions = starts[:, None]
    newk = jax.random.normal(jax.random.fold_in(key, 2),
                             (B, T, Hkv, D), jnp.float32)
    newv = jax.random.normal(jax.random.fold_in(key, 3),
                             (B, T, Hkv, D), jnp.float32)
    k_pool = write_chunk(k_pool, newk, tables, positions)
    v_pool = write_chunk(v_pool, newv, tables, positions)
    nb = -(-(90 + T) // Bs)
    got = paged_attention(q, k_pool, v_pool, tables, starts, nb=nb,
                          interpret=True)
    want = _reference(q, k_pool, v_pool, tables, starts, nb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_small_block_q_splits():
    """Forcing q-block splitting (block_q < T) keeps parity."""
    B, Hkv, G, Bs, D, T = 2, 1, 2, 16, 32, 40
    H = Hkv * G
    key = jax.random.PRNGKey(3)
    k_pool, v_pool, tables = _random_paged(
        key, B, n_blocks=32, Bs=Bs, Hkv=Hkv, D=D, lens=[10, 60], t_extra=T)
    starts = jnp.asarray([10, 60], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 4),
                          (B, T, H, D), jnp.float32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    newk = jax.random.normal(jax.random.fold_in(key, 5),
                             (B, T, Hkv, D), jnp.float32)
    newv = jax.random.normal(jax.random.fold_in(key, 6),
                             (B, T, Hkv, D), jnp.float32)
    k_pool = write_chunk(k_pool, newk, tables, positions)
    v_pool = write_chunk(v_pool, newv, tables, positions)
    nb = -(-(60 + T) // Bs)
    got = paged_attention(q, k_pool, v_pool, tables, starts, nb=nb,
                          block_q=16, interpret=True)
    want = _reference(q, k_pool, v_pool, tables, starts, nb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@WHOLE
def test_paged_sharded_tp_parity(layer):
    """shard_map over the head axis on the 8-device CPU mesh matches
    the unsharded kernel."""
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("tp",))
    assert mesh_tp_only(mesh)
    B, Hkv, G, Bs, D, T = 2, 2, 2, 16, 32, 8
    H = Hkv * G
    key = jax.random.PRNGKey(5)
    k_pool, v_pool, tables = _random_paged(
        key, B, n_blocks=24, Bs=Bs, Hkv=Hkv, D=D, lens=[20, 44])
    starts = jnp.asarray([20, 44], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 6),
                          (B, T, H, D), jnp.float32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    newk = jax.random.normal(jax.random.fold_in(key, 7),
                             (B, T, Hkv, D), jnp.float32)
    newv = jax.random.normal(jax.random.fold_in(key, 8),
                             (B, T, Hkv, D), jnp.float32)
    k_pool = write_chunk(k_pool, newk, tables, positions)
    v_pool = write_chunk(v_pool, newv, tables, positions)
    nb = -(-(44 + T) // Bs)
    got = _call(paged_attention_sharded, q, k_pool, v_pool, tables,
                starts, mesh, nb=nb, interpret=True, layer=layer)
    want = paged_attention(q, k_pool, v_pool, tables, starts, nb=nb,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_mesh_tp_only_gate():
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:4])
    assert mesh_tp_only(Mesh(devs.reshape(4), ("tp",)))
    assert mesh_tp_only(Mesh(devs.reshape(4, 1), ("tp", "dp")))
    assert not mesh_tp_only(Mesh(devs.reshape(2, 2), ("tp", "dp")))
    assert not mesh_tp_only(None)


@pytest.mark.parametrize("engine_kw,prompts,ignore_eos", [
    # two rows through prefill chunks, decode windows and slot
    # recycling, on small blocks
    (dict(max_model_len=128, prefill_chunk=32, prefill_buckets=(16, 32),
          kv_block_size=16),
     ["paged kernel probe", "second row"], False),
    # one prompt of three prefill chunks, on the default block size
    (dict(max_model_len=256, prefill_chunk=64, prefill_buckets=(64,)),
     [list(range(1, 150))], True),
], ids=["two_short_rows", "multi_chunk_prompt"])
def test_engine_paged_kernel_matches_gather_path(engine_kw, prompts,
                                                 ignore_eos):
    """The full engine with the paged kernel FORCED on, in interpret
    mode on CPU, must reproduce the gathered-copy jnp path's greedy
    outputs (fp32 online softmax vs dense softmax: same tokens on a
    tiny model)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions
    from production_stack_tpu.ops import pallas_paged

    def run(force_flash):
        pallas_paged.set_flash_enabled(force_flash)
        try:
            eng = LLMEngine(EngineConfig(
                model="debug-tiny", max_num_seqs=2, decode_window=4,
                **engine_kw))
            opts = SamplingOptions(temperature=0.0, max_tokens=8,
                                   ignore_eos=ignore_eos)
            outs = []
            for prompt in prompts:
                if isinstance(prompt, str):
                    prompt = eng.tokenizer.encode(prompt)
                sid = eng.add_request(prompt, opts)
                steps = 0
                while not any(o.seq_id == sid and o.finished
                              for o in eng.step()):
                    steps += 1
                    assert steps < 500
                outs.append(list(eng.seqs[sid].output_tokens))
            return outs
        finally:
            pallas_paged.set_flash_enabled(None)

    assert run(True) == run(False)


def test_env_blocks_per_step_validation(monkeypatch):
    """PSTPU_DECODE_BLOCKS_PER_STEP must never crash import or reach
    the decode grid math as 0/negative: malformed values warn and fall
    back to the default."""
    import pytest

    from production_stack_tpu.ops.pallas_paged import _env_blocks_per_step

    monkeypatch.delenv("PSTPU_DECODE_BLOCKS_PER_STEP", raising=False)
    assert _env_blocks_per_step() == 4
    monkeypatch.setenv("PSTPU_DECODE_BLOCKS_PER_STEP", "8")
    assert _env_blocks_per_step() == 8
    monkeypatch.setenv("PSTPU_DECODE_BLOCKS_PER_STEP", "banana")
    with pytest.warns(RuntimeWarning, match="not an integer"):
        assert _env_blocks_per_step() == 4
    for bad in ("0", "-3"):
        monkeypatch.setenv("PSTPU_DECODE_BLOCKS_PER_STEP", bad)
        with pytest.warns(RuntimeWarning, match="must be >= 1"):
            assert _env_blocks_per_step() == 4
