"""Numerics parity of the paged flash kernel (ops/pallas_paged.py)
against the dense jnp path (gather_view + attention_with_cache) —
interpret mode on CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from production_stack_tpu.models.kv import (
    gather_view, gather_view_q, make_cache, quantize_chunk, write_chunk)
from production_stack_tpu.ops.attention import attention_with_cache
from production_stack_tpu.ops.pallas_paged import (
    decode_blocks_per_step, mesh_tp_only, paged_attention,
    paged_attention_sharded, paged_decode_attention)
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


PARKED = -1     # a row parked at start = MB * Bs


def _pool_case(kernel, seed, T, G, Bs, D, Hkv, dtype, layer, *, starts,
               nb, window=0, softcap=0.0, int8=False, **kernel_kw):
    """A kernel against the dense path on a shuffled pool, one row per
    entry of ``starts`` (PARKED: a row that holds no request), every
    row's table as wide as the kv bucket ``nb`` and one block more,
    every block of the pool random: a row's dead blocks hold other
    rows' values, and nothing may read them. The int8 pool is the
    float pool quantized per token; both sides read the same
    dequantized values. Returns (got, want, live rows)."""
    B, H, MB = len(starts), Hkv * G, nb + 1
    key = jax.random.PRNGKey(seed)
    kk, kv, kt, kq = jax.random.split(key, 4)
    n_blocks = B * MB + 1
    k_pool = jax.random.normal(kk, (n_blocks, Hkv, Bs, D), dtype)
    v_pool = jax.random.normal(kv, (n_blocks, Hkv, Bs, D), dtype)
    tables = jnp.asarray(np.asarray(
        jax.random.permutation(kt, n_blocks - 1)).reshape(B, MB) + 1,
        jnp.int32)
    starts = jnp.asarray([MB * Bs if s == PARKED else s for s in starts],
                         jnp.int32)
    q = jax.random.normal(kq, (B, T, H, D), dtype)
    kw = dict(window=window, softcap=softcap, **kernel_kw)
    if int8:
        # [N, Hkv, Bs, D]: quantize_chunk takes the amax over D
        k_pool, ks = quantize_chunk(k_pool.astype(F32))
        v_pool, vs = quantize_chunk(v_pool.astype(F32))
        kw.update(k_scales=ks, v_scales=vs)
        k_att = gather_view_q(k_pool, ks, tables, nb, dtype=dtype)
        v_att = gather_view_q(v_pool, vs, tables, nb, dtype=dtype)
    else:
        k_att = gather_view(k_pool, tables, nb)
        v_att = gather_view(v_pool, tables, nb)
    got = np.asarray(_call(kernel, q, k_pool, v_pool, tables, starts,
                           nb=nb, interpret=True, layer=layer, **kw),
                     np.float32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    want = np.asarray(attention_with_cache(
        q, k_att, v_att, positions, sliding_window=window or None,
        logit_softcap=softcap or None), np.float32)
    return got, want, np.asarray(starts) < MB * Bs


@pytest.mark.parametrize("T,G,Bs,D,Hkv,dtype,more", [
    (1, 4, 16, 32, 2, F32, {}),      # decode window step, GQA
    (1, 1, 16, 32, 2, F32, {}),      # decode, MHA (G == 1)
    (5, 4, 16, 32, 2, F32, {}),      # speculative window (draft + 1)
    (48, 2, 16, 64, 2, F32, {}),     # prefill chunk, ragged block boundary
    (40, 4, 16, 32, 1, F32, {}),     # one kv head: row // G over every head
    (24, 2, 16, 128, 2, F32, {}),    # head dim 128, as both benchmark cells
    (32, 2, 16, 64, 2, BF16, {}),    # the serving dtype
    # a grid step takes a PANEL of pool blocks (prefill_tiles): at
    # blocks of 16 a bucket of 16 or 24 blocks goes in panels of 8 (128
    # keys), one of 12 in panels of 4. Chunks in q blocks of 32 whose
    # first q block sees less than one panel (a start inside it) and
    # whose last sees two or three; a short row beside them
    (160, 2, 16, 32, 2, F32, dict(starts=[70, 200, 3], nb=24, block_q=32)),
    (160, 2, 16, 32, 2, BF16, dict(starts=[70, 200, 3], nb=24,
                                   block_q=32)),
    (48, 4, 16, 32, 2, F32, dict(starts=[70, 33, 140], nb=12)),
    (48, 4, 16, 64, 1, BF16, dict(starts=[70, 33, 140], nb=12)),
    # the cells' block of 64 and heads of 128, a panel of 8 blocks of
    # which a row fills two and a half
    (32, 4, 64, 128, 2, BF16, dict(starts=[130, 40], nb=8)),
    # a sliding window whose lower edge falls inside a panel (and, on
    # the last row, holds the whole row), whole chunk and in q blocks
    (48, 2, 16, 32, 2, F32, dict(starts=[200, 100, 20], nb=16,
                                 window=40)),
    (96, 2, 16, 32, 2, F32, dict(starts=[150, 260, 20], nb=24, window=72,
                                 block_q=32)),
    # Gemma-2's cap on the raw scores, alone and under a window
    (48, 2, 16, 32, 2, F32, dict(starts=[200, 100, 20], nb=16,
                                 softcap=3.0)),
    (48, 2, 16, 32, 2, BF16, dict(starts=[200, 100, 20], nb=16,
                                  softcap=3.0, window=40)),
    # the int8 pool: its per-token scales on the score columns and the
    # probabilities, panel by panel
    (48, 4, 16, 32, 2, F32, dict(starts=[200, 100, 20], nb=16,
                                 int8=True)),
    (48, 2, 16, 32, 2, F32, dict(starts=[70, 33, 140], nb=12, int8=True,
                                 window=40, softcap=3.0)),
    (32, 4, 64, 128, 2, BF16, dict(starts=[130, 40], nb=8, int8=True)),
    # one block a step (what the kernel took before the panels) and a
    # forced panel narrower than the rule's: the same result
    (48, 2, 16, 32, 2, F32, dict(starts=[200, 100, 20], nb=16,
                                 panel_blocks=1)),
    (48, 2, 16, 32, 2, F32, dict(starts=[200, 100, 20], nb=16,
                                 panel_blocks=2, block_q=16)),
])
@WHOLE
def test_paged_matches_dense(T, G, Bs, D, Hkv, dtype, more, layer):
    """The prefill kernel (and, for the short windows, what it answers
    where the decode kernel would run) matches the dense jnp path on
    shuffled pools. bf16 inputs (the serving dtype) are held to a bf16
    tolerance: both sides accumulate in float32 and hand the
    probabilities to the second product in the values' dtype."""
    if not more:
        return _paged_case(paged_attention, T * 1000 + G, T, G, Bs, D,
                           Hkv, dtype, layer)
    got, want, _ = _pool_case(paged_attention, T * 1000 + G, T, G, Bs, D,
                              Hkv, dtype, layer, **more)
    tol = 2e-2 if dtype == BF16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("T,G,D,nb,want", [
    # N's chunk of 2048 at 8 groups of 256, its four kv buckets: q
    # blocks of 512 against panels of 512 keys
    (2048, 8, 256, 256, (512, 8)), (2048, 8, 256, 128, (512, 8)),
    (2048, 8, 256, 64, (512, 8)), (2048, 8, 256, 32, (512, 8)),
    # M's and Q's one-row prefills in the 512 bucket: the whole chunk
    # one q block, the whole bucket one panel; M's full-batch chunk
    (128, 4, 128, 8, (128, 8)), (256, 4, 128, 8, (256, 8)),
    (128, 1, 128, 8, (128, 8)), (256, 1, 128, 8, (256, 8)),
    (512, 4, 128, 32, (512, 8)),
    # Gemma-2's 256-wide heads (2 groups), a chunk of 512
    (512, 2, 256, 16, (512, 8)),
    # a bucket no panel of 8 divides; one of a single block
    (256, 4, 128, 12, (256, 4)), (64, 4, 128, 1, (64, 1)),
    # heads so many a kv head that 16 positions against 512 keys miss
    # the working set: the panel narrows, the q block stays
    (2048, 448, 64, 256, (16, 4)),
])
def test_prefill_tiles_follow_the_shapes(monkeypatch, T, G, D, nb, want):
    """(q block, pool blocks a step) of the K/V prefill kernel are a
    function of the trace-time shapes: the panel divides the kv bucket
    and holds at most _SELECT_PANEL_TOKENS keys, the q block's working
    set holds against it, and every shape ``attention_path`` calls
    viable at the smallest q block and one block a step has its tiles,
    so that what is ``pallas_paged`` by that answer stays so."""
    from production_stack_tpu.ops import pallas_paged
    Bs = 64
    monkeypatch.setattr(pallas_paged, "_override", True)
    assert pallas_paged.attention_path(T, G, D, Bs) == "pallas_paged"
    block_q, R = pallas_paged.prefill_tiles(T, G, D, nb, Bs)
    if want is not None:
        assert (block_q, R) == want
    assert nb % R == 0 and (R == 1 or R * Bs
                            <= pallas_paged._SELECT_PANEL_TOKENS)
    assert min(T, pallas_paged._MIN_BLOCK_Q) <= block_q <= T
    assert (pallas_paged._work_bytes(block_q, G, D, R * Bs)
            <= pallas_paged._PANEL_WORK_BYTES)
    # the latent pool's absorbed case keeps one block a step and the q
    # block it had (G's 20 heads: 64 positions, GLM-5's 64 heads: 16)
    assert pallas_paged.prefill_tiles(T, 20, 640, nb, Bs, 512) == (
        min(T, 64), 1)
    assert pallas_paged.prefill_tiles(T, 64, 640, nb, Bs, 512) == (16, 1)


def _decode_case(seed, T, G, Bs, D, Hkv, dtype, layer, **more):
    """The decode kernel against the dense path (``_pool_case``)."""
    got, want, live = _pool_case(paged_decode_attention, seed, T, G, Bs,
                                 D, Hkv, dtype, layer, **more)
    tol = 2e-2 if dtype == BF16 else 2e-5
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    # a parked row costs nothing and says nothing: finite zeros
    assert not got[~live].any()


@pytest.mark.parametrize("T,G,Bs,D,Hkv,dtype,more", [
    (1, 4, 16, 32, 2, F32, {}),      # decode window step, GQA
    (1, 1, 16, 32, 2, F32, {}),      # decode, MHA (G == 1)
    (5, 4, 16, 32, 2, F32, {}),      # speculative window (draft + 1)
    (8, 2, 16, 64, 2, F32, {}),      # DECODE_T_MAX boundary
    (5, 4, 16, 32, 1, F32, {}),      # one kv head
    (1, 4, 16, 128, 2, F32, {}),     # head dim 128, as both benchmark cells
    (4, 2, 16, 64, 2, BF16, {}),     # the serving dtype
    # the two cells' head geometries in the serving dtype: under the
    # 512 bucket (nb 8, one chunk of R = 8) a row of five live blocks,
    # one of one, one of all eight, and a parked row
    (1, 4, 64, 128, 2, BF16, dict(starts=[300, 40, 510, PARKED], nb=8)),
    (1, 1, 64, 128, 4, BF16, dict(starts=[300, 40, 510, PARKED], nb=8)),
    # the same rows in float32, where a dead block read shows at 2e-5
    (1, 4, 16, 32, 2, F32, dict(starts=[75, 10, 127, PARKED], nb=8)),
    # nb not a multiple of any R above 1, the last row on the last block
    (1, 2, 16, 32, 2, F32, dict(starts=[100, 3, 111], nb=7)),
    (1, 2, 16, 32, 2, F32, dict(starts=[9], nb=1)),
    # blocks long enough that a row takes several chunks (R = 2 at
    # Bs 256, R = 4 at Bs 128): rows of 3, 1 and 4 chunks, the next
    # chunk and the next row's first copied under the current one
    (1, 2, 256, 32, 2, F32, dict(starts=[1500, 300, 2047, PARKED], nb=8)),
    (5, 1, 256, 32, 2, F32, dict(starts=[1020, 250, 2043], nb=8)),
    (1, 4, 128, 32, 2, F32, dict(starts=[700, 100, 1023, PARKED], nb=8,
                                 int8=True)),
    (1, 2, 128, 32, 2, F32, dict(starts=[900, 200, 1023], nb=8,
                                 window=300)),
    # speculative windows across a block boundary and at a row's end
    (5, 1, 16, 32, 2, F32, dict(starts=[60, 14, 123, PARKED], nb=8)),
    (8, 4, 16, 32, 2, F32, dict(starts=[57, 0, 120], nb=8)),
    # the int8 pool with its per-token scales
    (1, 4, 16, 32, 2, F32, dict(starts=[75, 10, 127, PARKED], nb=8,
                                int8=True)),
    (1, 4, 64, 128, 2, BF16, dict(starts=[300, 40, 510], nb=8,
                                  int8=True)),
    # sliding windows whose first live block lies inside a chunk (and,
    # on the second row, whose window holds the whole row)
    (1, 2, 16, 32, 2, F32, dict(starts=[100, 20, 127], nb=8, window=40)),
    (5, 2, 16, 32, 2, F32, dict(starts=[100, 20, 123, PARKED], nb=8,
                                window=24)),
    # Gemma-2's cap on the raw scores, alone and under a window
    (1, 2, 16, 32, 2, F32, dict(starts=[75, 10, 127], nb=8,
                                softcap=3.0)),
    (4, 2, 16, 32, 2, F32, dict(starts=[75, 10, 124], nb=8,
                                softcap=3.0, window=40, int8=True)),
])
@WHOLE
def test_paged_decode_matches_dense(T, G, Bs, D, Hkv, dtype, more, layer):
    """The decode kernel (every live block of a row, R at a time,
    copied in by the kernel itself) matches the dense jnp path on
    shuffled pools."""
    _decode_case(T * 77 + G, T, G, Bs, D, Hkv, dtype, layer,
                 **{"starts": [70, 33, 51], "nb": 5, **more})


@pytest.mark.parametrize("lens", [[90, 5], [5, 90], [40, 127, 17]])
def test_paged_decode_short_row_isolation(lens):
    """A short row must read no block of a long row, before or after it
    in the batch, nor its own dead blocks (its chunks end at its last
    live block; the slots still hold what the row before copied)."""
    B, Hkv, G, Bs, D, T = len(lens), 2, 2, 16, 32, 1
    H = Hkv * G
    key = jax.random.PRNGKey(11)
    k_pool, v_pool, tables = _random_paged(
        key, B, n_blocks=32, Bs=Bs, Hkv=Hkv, D=D, lens=lens)
    starts = jnp.asarray(lens, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, T, H, D), jnp.float32)
    positions = starts[:, None]
    newk = jax.random.normal(jax.random.fold_in(key, 2),
                             (B, T, Hkv, D), jnp.float32)
    newv = jax.random.normal(jax.random.fold_in(key, 3),
                             (B, T, Hkv, D), jnp.float32)
    k_pool = write_chunk(k_pool, newk, tables, positions)
    v_pool = write_chunk(v_pool, newv, tables, positions)
    nb = -(-(max(lens) + T) // Bs)
    got = paged_decode_attention(q, k_pool, v_pool, tables, starts,
                                 nb=nb, interpret=True)
    want = _reference(q, k_pool, v_pool, tables, starts, nb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # what a row's dead blocks and the other rows' blocks hold must not
    # reach it: poison every block the first row does not own or has
    # not reached, and it answers the same to the bit
    own = np.asarray(tables[0, :lens[0] // Bs + 1])
    poison = np.ones(k_pool.shape[0], bool)
    poison[own] = False
    bad = jnp.where(jnp.asarray(poison)[:, None, None, None], 1e4, 0.0)
    got_bad = paged_decode_attention(q, k_pool + bad, v_pool + bad,
                                     tables, starts, nb=nb,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(got_bad[0]),
                                  np.asarray(got[0]))


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


@pytest.mark.parametrize("dtype,int8,masked,block_q,nb_extra", [
    (F32, False, False, 0, 0),     # the whole chunk one q block
    (F32, True, False, 16, 1),     # q blocks of 16: T = 40 padded to 48
    (F32, True, True, 0, 3),       # under the selection's marks
    (F32, False, True, 16, 2),
    (BF16, True, True, 0, 0),      # the serving dtype
], ids=["plain", "int8-qblocks", "int8-marks", "marks-qblocks", "bf16"])
def test_paged_expanded_latent_matches_dense(dtype, int8, masked, block_q,
                                             nb_extra):
    """The prefill kernel's EXPANDED case over a latent pool (a
    shuffled pool of two layers, three rows whose chunks of 40 cross
    block boundaries at different offsets): each head's keys and values
    made inside the kernel from the cached [c | k_rope | 0], against
    ops/attention.py on keys and values expanded with plain jax.numpy
    from the gathered view. nb_extra: dead blocks past the last query
    (the clamp); with several blocks a step where the kv bucket divides
    (nb 8: four a step)."""
    B, T, H, Bs = 3, 40, 4, 16
    r, dn, dr, dv, W = 96, 32, 16, 24, 128
    lens = [70, 33, 8]
    key = jax.random.PRNGKey(11 + block_q)
    ks = iter(jax.random.split(key, 8))
    nb = -(-(max(lens) + T) // Bs) + nb_extra
    pool = jax.random.normal(next(ks), (2, B * nb + 1, 1, Bs, W), dtype)
    pool = pool.at[..., r + dr:].set(0)
    perm = np.asarray(jax.random.permutation(next(ks), B * nb)) + 1
    tables = jnp.asarray(perm.reshape(B, nb).astype(np.int32))
    starts = jnp.asarray(lens, jnp.int32)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    q = jax.random.normal(next(ks), (B, T, H, dn + dr), dtype)
    if int8:
        w = jax.random.randint(next(ks), (r, H, dn + dv), -127, 128
                               ).astype(jnp.int8)
        ch = jax.random.uniform(next(ks), (H, dn + dv), jnp.float32,
                                5e-4, 2e-3)
    else:
        w = (0.1 * jax.random.normal(next(ks), (r, H, dn + dv))
             ).astype(dtype)
        ch = None
    select = None
    if masked:
        marks = np.asarray(jax.random.uniform(next(ks), (B, T, nb * Bs))
                           ) < 0.4
        marks[np.arange(B)[:, None], np.arange(T)[None],
              np.asarray(positions)] = True
        select = jnp.asarray(marks, dtype)
    scale = (dn + dr) ** -0.5
    got = paged_attention(
        q, pool, None, tables, starts, nb=nb, interpret=True, scale=scale,
        layer=jnp.int32(1), value_dim=r, select=select, block_q=block_q,
        expand=(w[..., :dn], w[..., dn:]) + (
            (ch[:, :dn], ch[:, dn:]) if int8 else (None, None)))
    assert got.shape == (B, T, H, dv)

    lat = gather_view(pool, tables, nb, layer=1)[:, :, 0].astype(F32)
    kvh = jnp.einsum("bsr,rhd->bshd", lat[..., :r], w.astype(F32))
    if int8:
        kvh = kvh * ch
    k = jnp.concatenate([kvh[..., :dn], jnp.broadcast_to(
        lat[:, :, None, r:r + dr], kvh.shape[:3] + (dr,))], -1)
    want = attention_with_cache(q.astype(F32), k, kvh[..., dn:], positions,
                                scale=scale, select=select)
    tol = 3e-2 if dtype == BF16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


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


@pytest.mark.parametrize("itemsize", [2, 1], ids=["bf16", "int8"])
@pytest.mark.parametrize("heads_kv", [2, 8, 16])
@pytest.mark.parametrize("nb", [1, 8, 32])
def test_decode_blocks_per_step_follows_the_shapes(nb, heads_kv,
                                                   itemsize):
    """R, the blocks a chunk of the decode kernel takes, is a function
    of the trace-time shapes: at least one block, never more than the
    kv bucket holds, a score panel of whole 128-lane registers where
    the bucket allows one, and two slots of K and V panels inside the
    VMEM the module budgets for a kernel's working set."""
    from production_stack_tpu.ops import pallas_paged
    Bs, D = 64, 128
    R = decode_blocks_per_step(nb, heads_kv, Bs, D, itemsize)
    assert 1 <= R <= nb
    assert (R * Bs) % 128 == 0 or R == nb
    slots = 2 * 2 * R * heads_kv * Bs * D * itemsize
    assert slots <= pallas_paged._VMEM_WORK_BYTES
    # the cells: Mistral's 8 kv heads take the whole 512 bucket in one
    # chunk, Qwen's 16 half of it; the int8 pool twice the blocks
    if nb >= 8:
        assert R == {(8, 2): 8, (16, 2): 4}.get((heads_kv, itemsize), 8)
    # wider heads or longer blocks: fewer blocks, still at least one
    assert decode_blocks_per_step(nb, 64, 256, 256, 4) == 1


# ---------------------------------------------------------------------
# the pool's write by rows (append_rows) against write_chunk and
# against the whole-block rewrite (append_chunk)
# ---------------------------------------------------------------------

# the cells' pool geometries (kv heads, head dim): Mistral, Ouro,
# Phi-4-mini-flash's paired heads, Nemotron-3-Nano, and the latent pool
# at GLM's 576 values padded as latent_pool_width says
M_KV, O_KV, P_KV, H_KV = (8, 128), (16, 128), (10, 128), (2, 128)
ALL_REAL = None


def _append_case(*, T, starts, geometry=(2, 32), Bs=16, dtype=BF16,
                 valid=ALL_REAL, latent=False, scanned=False, MB=3,
                 layers=3, seed=0):
    """(pools by rows, pools by blocks, one layer by write_chunk,
    tables, the layer): the same random pool and the same new rows
    through ``append_rows`` (interpret mode), ``append_chunk`` and
    ``write_chunk``. A row of ``starts`` that is PARKED stands at
    MB*Bs. ``scanned``: the layer is the counter of a lax.scan that
    writes EVERY layer, a traced operand as the layer loop gives it."""
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.ops.pallas_paged import append_rows
    Hkv, D = geometry
    B = len(starts)
    key = jax.random.PRNGKey(seed)
    n_blocks = B * MB + 1
    n = 1 if latent else 2
    pools = tuple(
        jax.random.normal(jax.random.fold_in(key, i),
                          (layers, n_blocks, Hkv, Bs, D), F32).astype(dtype)
        for i in range(n))
    news = tuple(
        jax.random.normal(jax.random.fold_in(key, 10 + i),
                          (B, T, Hkv, D), F32).astype(dtype)
        for i in range(n))
    tables = jnp.asarray(np.asarray(jax.random.permutation(
        jax.random.fold_in(key, 20), n_blocks - 1)).reshape(B, MB) + 1,
        jnp.int32)
    starts = jnp.asarray([MB * Bs if s == PARKED else s for s in starts],
                         jnp.int32)
    if valid is not None:
        valid = jnp.asarray(valid, bool)
    layer = layers - 2

    def by_rows(pools, layer):
        return append_rows(pools, news, tables, starts, valid, layer,
                           interpret=True)

    def by_blocks(pools, layer):
        return tuple(kv_pool.append_chunk(p, x, tables, starts, valid,
                                          layer)
                     for p, x in zip(pools, news))

    if scanned:
        def every_layer(write):
            return jax.jit(lambda pools: jax.lax.scan(
                lambda c, i: (write(c, i), None), pools,
                jnp.arange(layers))[0])(pools)
        got, want = every_layer(by_rows), every_layer(by_blocks)
    else:
        got = by_rows(pools, jnp.int32(layer))
        want = by_blocks(pools, layer)
    positions = starts[:, None] + jnp.arange(T)[None, :]
    written = tuple(write_chunk(p[layer], x, tables, positions, valid)
                    for p, x in zip(pools, news))
    return got, want, written, tables, layer


def _same_bits(a, b) -> bool:
    return bool(jnp.array_equal(
        jax.lax.bitcast_convert_type(a, jnp.uint16 if a.dtype == BF16
                                     else jnp.uint32),
        jax.lax.bitcast_convert_type(b, jnp.uint16 if b.dtype == BF16
                                     else jnp.uint32)))


@pytest.mark.parametrize("case", [
    dict(T=1, starts=[5, 40]),
    dict(T=3, starts=[5, 17]),
    dict(T=8, starts=[3, 33]),
    # a window across a block boundary (two blocks a row) and across a
    # tile boundary inside a block (two slabs of one block)
    dict(T=3, starts=[15, 30]),
    dict(T=8, starts=[12, 28], Bs=32),
    dict(T=8, starts=[60, 9], Bs=64, MB=2),
    # a row at offset 0 and one at offset Bs - 1
    dict(T=1, starts=[16, 31]),
    dict(T=3, starts=[32, 15]),
    # parked rows beside a live one, and every row parked
    dict(T=1, starts=[PARKED, 7, PARKED]),
    dict(T=8, starts=[PARKED, PARKED]),
    # invalid tokens inside a window; a row with none valid
    dict(T=8, starts=[4, 20],
         valid=[[1, 1, 0, 1, 0, 1, 1, 0], [0, 1, 1, 1, 1, 1, 1, 1]]),
    dict(T=3, starts=[4, 20], valid=[[0, 0, 0], [1, 0, 1]]),
    # two rows that share trash block 0: one parked, one all invalid,
    # both routed to it by the whole-block rewrite
    dict(T=1, starts=[PARKED, 9, 11], valid=[[1], [0], [1]]),
    # a window whose tail runs past the row's capacity MB * Bs
    dict(T=8, starts=[44, 2]),
    # a position before 0 (no caller sends one; the contract names it)
    dict(T=3, starts=[-2, 6]),
    # float32: a tile of 8 rows; a block of 8: the slab is the block
    dict(T=8, starts=[5, 20], dtype=F32),
    dict(T=8, starts=[5, 14], Bs=8, dtype=F32),
    dict(T=3, starts=[7, 3], Bs=8),
    # the layer as a traced operand inside lax.scan, every layer written
    dict(T=1, starts=[5, 40], scanned=True),
    dict(T=3, starts=[15, PARKED], scanned=True),
    # the latent pool: one array, one head of 576 values padded to 640
    dict(T=1, starts=[5, 40], geometry=(1, 640), latent=True),
    dict(T=4, starts=[14, PARKED], geometry=(1, 640), latent=True),
    # the cells' geometries, block 64
    dict(T=1, starts=[63, 64], geometry=M_KV, Bs=64, MB=2),
    dict(T=1, starts=[100, 17], geometry=O_KV, Bs=64, MB=2),
    dict(T=1, starts=[70, 127], geometry=P_KV, Bs=64, MB=2),
    dict(T=3, starts=[62, 5], geometry=H_KV, Bs=64, MB=2),
], ids=lambda c: "-".join(f"{k}{v}" for k, v in c.items()
                          if k != "valid").replace(" ", ""))
def test_append_rows_leaves_what_write_chunk_leaves(case):
    """``append_rows`` (the decode window's write: a tile of the block
    each row lands in, one kernel for K and V) against ``write_chunk``
    and against ``append_chunk`` (the whole-block rewrite it takes the
    place of): every block a table references holds the same BITS on
    the written layer, every other layer is untouched, and nothing at
    all is written for a token that is invalid, parked, negative or
    past the row's capacity (the whole-block rewrite may leave anything
    in trash block 0; the rows leave it as it was)."""
    got, want, written, tables, layer = _append_case(**case)
    live = np.unique(np.asarray(tables))
    assert 0 not in live
    for g, w, c in zip(got, want, written):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert _same_bits(g[:, live], w[:, live])
        assert _same_bits(g[layer][live], c[live])


def test_append_rows_then_read_through_cut_tables():
    """Phi-4-mini-flash's window layers append through the FULL tables
    and attend through tables CUT to the blocks their window touches,
    with the starts shifted (models/llama._diff_attention): the decode
    kernel reads, through cut tables, the same values from a pool
    written by rows as from one written by blocks (10 pool heads of
    128, four query heads a pool head)."""
    from production_stack_tpu.ops.pallas_paged import paged_decode_attention
    Bs, window = 64, 100
    got, want, _, tables, layer = _append_case(
        T=1, starts=[200, 130], geometry=P_KV, Bs=Bs, MB=4, layers=2)
    starts = jnp.asarray([200, 130], jnp.int32)
    few = 3
    lo = jnp.maximum(starts - (window - 1), 0) // Bs
    cut = jnp.take_along_axis(
        tables, jnp.clip(lo[:, None] + jnp.arange(few), 0, 3), axis=1)
    q = jax.random.normal(jax.random.PRNGKey(5), (2, 1, 40, 128), BF16)
    outs = [paged_decode_attention(
        q, k, v, cut, starts - lo * Bs, nb=few, interpret=True,
        window=window, layer=jnp.int32(layer)) for k, v in (got, want)]
    assert _same_bits(*outs)


@pytest.mark.parametrize("T,rows,geometry,mesh,quantized,gate,want", [
    (1, 16, O_KV, None, False, True, "rows"),
    (8, 16, O_KV, None, False, True, "rows"),       # T = DECODE_T_MAX
    (9, 16, O_KV, None, False, True, "blocks"),     # a prefill chunk
    (2048, 1, O_KV, None, False, True, "blocks"),
    (1, 16, O_KV, None, True, True, "blocks"),      # the int8 pool's scales
    (1, 16, O_KV, "tp", False, True, "blocks"),     # a mesh
    (1, 16, O_KV, None, False, False, "blocks"),    # the kernels off
    # a batch whose slabs one call cannot hold in flight: K and V, one
    # slab a row at one position, two at more (256 DMA semaphores)
    (1, 128, O_KV, None, False, True, "rows"),
    (1, 256, O_KV, None, False, True, "blocks"),
    (8, 64, O_KV, None, False, True, "rows"),
    (8, 128, M_KV, None, False, True, "blocks"),
    # 32 heads of 128: 32 MiB of slabs and news bind before the semaphores
    (1, 64, (32, 128), None, False, True, "rows"),
    (1, 128, (32, 128), None, False, True, "blocks"),
    # the latent pool alone: one array, half the semaphores a row
    (1, 256, (1, 640), None, False, True, "rows"),
    (8, 256, (1, 640), None, False, True, "blocks"),
], ids=lambda v: str(v).replace(" ", ""))
def test_kv_append_path_follows_what_the_call_observes(T, rows, geometry,
                                                       mesh, quantized,
                                                       gate, want):
    """``rows`` only for a decode or speculative window, on one device,
    over a pool without scales, with the kernels on and a batch one
    call can hold: everything else keeps the whole-block rewrite. The
    rule reads shapes alone (engine/runner asks it of the pool it
    holds, models/kv.append of the traced one)."""
    from production_stack_tpu.ops import pallas_paged
    from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
    Hkv, D = geometry
    payload = jax.ShapeDtypeStruct((2, 33, Hkv, 64, D),
                                   jnp.int8 if quantized else BF16)
    pools = (payload,) * (1 if Hkv == 1 else 2)
    if quantized:
        pools += (jax.ShapeDtypeStruct((2, 33, Hkv, 64), F32),) * 2
    pallas_paged.set_flash_enabled(gate)
    try:
        m = mesh and build_mesh(MeshConfig(dp=1, tp=2), jax.devices()[:2])
        assert pallas_paged.kv_append_path(pools, rows, T, m) == want
    finally:
        pallas_paged.set_flash_enabled(None)


def test_engine_greedy_stream_by_rows_matches_by_blocks(monkeypatch):
    """One greedy stream of 40 tokens in decode windows of 4 across a
    block boundary of 16 (and a second row beside it), float32, the
    kernels in interpret mode: the engine whose decode windows append
    by rows gives the stream of the engine whose every append rewrites
    blocks (the parent's path), token for token; and ``device.
    kv_appends`` of GET /debug/perf says which executables did which.
    (About 15 s: two engines, 80 tokens each through the interpreter.)"""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions
    from production_stack_tpu.ops import pallas_paged

    def run(rows: bool):
        with monkeypatch.context() as mp:
            mp.setattr(pallas_paged, "_override", True)
            if not rows:
                mp.setattr(pallas_paged, "kv_append_path",
                           lambda *a, **kw: pallas_paged.KV_APPEND_BLOCKS)
            eng = LLMEngine(EngineConfig(
                model="debug-tiny", dtype="float32", max_num_seqs=2,
                decode_window=4, max_model_len=128, prefill_chunk=32,
                prefill_buckets=(16, 32), kv_block_size=16))
            opts = SamplingOptions(temperature=0.0, max_tokens=40,
                                   ignore_eos=True)
            sids = [eng.add_request(list(range(3, 3 + n)), opts)
                    for n in (10, 21)]
            done, steps = set(), 0
            while len(done) < len(sids):
                done |= {o.seq_id for o in eng.step() if o.finished}
                steps += 1
                assert steps < 500
            device = eng.device_report()
            # keyed as the attention paths are, executable by executable
            assert set(device["kv_appends"]) == set(
                device["attention_paths"])
            return ([list(eng.seqs[s].output_tokens) for s in sids],
                    device["kv_appends"])

    (by_rows, appends), (by_blocks, _) = run(True), run(False)
    assert by_rows == by_blocks and [len(t) for t in by_rows] == [40, 40]
    kinds = {k.split("|")[0]: v for k, v in appends.items()}
    assert kinds == {"decode": "rows", "prefill": "blocks"}
