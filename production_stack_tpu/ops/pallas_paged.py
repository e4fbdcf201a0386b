"""Paged flash attention for TPU: block-table-aware online-softmax GQA.

ONE kernel for both serving phases:

- **prefill** chunks (T up to the chunk bucket) — no per-layer
  gathered K/V copy and no head-major relayout copy of it;
- **decode / speculative windows** (T = 1 or draft+1, inside the
  lax.scan of engine/runner.py) — replaces the gather-view + dense jnp
  path, which materialized a [B, kv, Hkv, D] copy of the live cache
  per layer per step: ~3x the minimal KV HBM traffic, the dominant
  cost of long-context decode.

K/V pool blocks ``[N, Hkv, Bs, D]`` (models/kv.py, head-major: the
per-(block, head) panel is a contiguous [Bs, D] tile) are streamed
straight from HBM through *scalar-prefetched* block tables: the grid's
innermost dimension walks a row's blocks, the BlockSpec index map reads
``tables[b, j]`` to point the next DMA at the right block, and each KV
byte a row needs is read exactly once. Per-row causal skipping falls
out of the index map: blocks past a row's last query position clamp to
an already-resident index (Pallas elides the re-fetch) and their grid
steps are `pl.when`-masked away, so decode cost scales with each row's
LIVE prefix, not the kv bucket.

The serving path hands in the WHOLE pool ``[L, N, Hkv, Bs, D]`` and a
``layer`` index, a third scalar-prefetched operand that the index maps
put in front (``(layer, tables[b, j], ...)``): no layer's pool is
sliced out of the buffer the step program carries (models/kv.py
"carried, never stacked"). A bare 4-D layer is the same call on a pool
of one layer.

Grid ``(B, Hkv, NQ, nb)``; per step the q block [BQ, G, D] for one kv
head and one pool block's [Bs, D] K and V panels live in VMEM. Online
(max, sum, acc) statistics persist in VMEM scratch across the
``nb``-axis (sequential "arbitrary" dimension), initialized at j == 0
and emitted at j == nb - 1 — the classic flash accumulation, with GQA
rows flattened as t*G + g so K/V are never broadcast to query heads.

Sharded serving: under a tp-only mesh the kernel runs inside
``shard_map`` over the head axis (q heads and pool heads both shard by
tp; tables/starts replicate) — embarrassingly parallel, no collectives.
Meshes that shard the pool's block axis (dp > 1) keep the jnp gather
path, whose collectives XLA inserts.

Which implementation a cached attention takes is decided HERE, by
``attention_path``: it owns every fact the decision consults (the
run-time gate, ``paged_viable``, ``mesh_tp_only``, ``DECODE_T_MAX``).
models/kv.py asks it at trace time and engine/runner.py when it
compiles an executable; nobody else reads those facts.

The reference repo ships no kernels (attention lives in the external
vLLM engine, SURVEY.md §2.9); this is TPU-first work. Numerics are
pinned against the dense jnp path in tests/test_pallas_paged.py via
interpret mode on CPU.
"""

import functools
import os
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Per-kernel scoped-VMEM budget: XLA may place a chunk-sized kernel
# OUTPUT on the scoped-VMEM stack (a batch-8 512-chunk bf16 output is
# ~17 MB), and the default 16 MiB budget then fails the compile even
# though the kernel's own working set is small. v5e/v5p cores carry
# 128 MiB VMEM — raise the budget so chunk-sized outputs may live
# on-chip; outputs too big for it simply land in HBM.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024

# runtime gate: PSTPU_FLASH=1/0 forces; "auto" (default) enables the
# compiled kernels on TPU and leaves CPU/other backends on the jnp path
# (interpret mode is for tests, far too slow for serving).
_override = None


def set_flash_enabled(value) -> None:
    """Force-enable/disable (True/False) or restore auto (None): tests
    run the kernels in interpret mode on the CPU through this."""
    global _override
    _override = value


def flash_enabled() -> bool:
    if _override is not None:
        return _override
    env = os.environ.get("PSTPU_FLASH", "auto").lower()
    if env in ("1", "true", "on"):
        return True
    if env in ("0", "false", "off"):
        return False
    return jax.default_backend() == "tpu"


def needs_interpret() -> bool:
    """Interpret everywhere but real TPU (kernel targets TPU tiling)."""
    return jax.default_backend() != "tpu"


def mode() -> str:
    """"compiled" | "interpret" | "off": how the Pallas attention
    kernels run in this process, for logs and GET /debug/perf."""
    if not flash_enabled():
        return "off"
    return "interpret" if needs_interpret() else "compiled"


# VMEM ceiling for the per-grid-step working set (q + acc + scores,
# fp32): conservative slice of the ~16 MB/core budget, leaving room
# for Pallas' double-buffered K/V panels and the output block.
_VMEM_WORK_BYTES = 8 * 1024 * 1024


def paged_viable(T: int, groups: int, head_dim: int,
                 block_size: int) -> bool:
    """Can a [T*G, D] q panel + accumulator + one [T*G, Bs] score
    block hold in VMEM? (Decode windows always can; only very long
    prefill chunks on wide-GQA models cannot.)"""
    rows = max(T * groups, 8)
    work = rows * head_dim * 4 * 2 + rows * block_size * 4 * 2 \
        + rows * head_dim * 2
    return work <= _VMEM_WORK_BYTES


def _whole_pool(layer, k_pool, v_pool, k_scales, v_scales):
    """(layer [1] int32, pools and scales with a leading layer axis): a
    bare layer [N, Hkv, Bs, D] becomes a pool of one (a bitcast), so
    the kernels have one indexing."""
    pools = (k_pool, v_pool, k_scales, v_scales)
    if layer is None:
        layer = 0
        pools = tuple(p if p is None else p[None] for p in pools)
    return (jnp.asarray(layer, jnp.int32).reshape(1),) + pools


def _paged_kernel(tabs_ref, starts_ref, layer_ref, q_ref, k_ref, v_ref,
                  *refs,
                  block_q: int, groups: int,
                  block_size: int, nb: int, scale: float,
                  quant: bool = False, window: int = 0,
                  softcap: float = 0.0):
    """One (batch row, kv head, q block, pool block) grid step.

    tabs_ref   (SMEM) [B, MB]      block tables
    starts_ref (SMEM) [B]          absolute position of q[:, 0]
    layer_ref  (SMEM) [1]          the pool's layer (index maps only)
    q_ref   [1, BQ, 1, G, D]       this kv-head's query block
    k_ref   [1, 1, 1, Bs, D]       pool block tabs[b, min(j, jmax)]
    v_ref   [1, 1, 1, Bs, D]
    refs    (quant only: ks/vs dequant scales [1, 1, Hkv, Bs] fp32 —
            every kv head of the block, this step reads row h,)
            out [1, BQ, 1, G, D], scratch m/l/acc (online softmax
            state across j)
    """
    if quant:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    out_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    rows = block_q * groups
    D = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = starts_ref[b]
    # last block this q block can see (same formula as the index map's
    # clamp): beyond it the DMA re-targets a resident block and the
    # step is skipped entirely
    max_pos = start + qi * block_q + (block_q - 1)
    jmax = jax.lax.div(max_pos, block_size)
    # sliding window: blocks wholly before the EARLIEST query row's
    # window are skipped the same way (window == 0 means full causal)
    jmin = (jax.lax.div(
        jnp.maximum(start + qi * block_q - (window - 1), 0), block_size)
        if window else 0)

    @pl.when((j <= jmax) & (j >= jmin))
    def _compute():
        # absolute position of each q row (rows ordered t*G + g)
        row_ids = jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // groups
        q_pos = start + qi * block_q + row_ids                # [rows, 1]
        q = q_ref[0].reshape(rows, D).astype(jnp.float32) * scale
        k_blk = k_ref[0, 0, 0].astype(jnp.float32)            # [Bs, D]
        v_blk = v_ref[0, 0, 0].astype(jnp.float32)
        if quant:
            # int8 pool: dequantize the panel in VMEM (per-token scale)
            k_blk = k_blk * ks_ref[0, 0, h][:, None]
            v_blk = v_blk * vs_ref[0, 0, h][:, None]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [rows, Bs]
        if softcap:
            # Gemma-2 tanh cap on RAW scores, before -inf masking
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        live = k_pos <= q_pos
        if window:
            live = live & (k_pos > q_pos - window)
        s = jnp.where(live, s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1,
                                            keepdims=True))
        p = jnp.exp(s - m_new)                                # [rows, Bs]
        correction = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * correction + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [rows, D]

    @pl.when(j == nb - 1)
    def _emit():
        # fully-masked (padding/parked) rows have l == 0; keep finite
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = out.reshape(block_q, 1, groups, D).astype(
            out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("nb", "block_q", "interpret",
                                    "window", "scale", "softcap"))
def paged_attention(q, k_pool, v_pool, tables, starts, *, nb: int,
                    block_q: int = 0, interpret: bool = False,
                    k_scales=None, v_scales=None, window: int = 0,
                    scale: float = None, softcap: float = 0.0,
                    layer=None):
    """Causal GQA over paged K/V, positions contiguous per row.

    q [B, T, H, D]; k/v pool [N, Hkv, Bs, D], or with ``layer`` (an
    int32 scalar, traced) the whole pool [L, N, Hkv, Bs, D] of which
    that layer is read in place; tables [B, MB] int32;
    starts [B] = absolute position of q[:, 0] (every call site —
    prefill chunks, decode windows, speculative windows — queries
    contiguous positions start..start+T-1). A query at position p
    attends virtual positions <= p through its table row; the pool
    must already contain the chunk's own K/V (write-then-attend, as
    in models/kv.py). Rows parked at start >= MB*Bs return garbage
    the caller discards, exactly like the jnp path.

    k_scales/v_scales [(L,) N, Hkv, Bs] fp32 activate the int8-pool
    mode: panels stream from HBM as int8 (half the bytes) and
    dequantize in VMEM next to the dot.
    """
    B, T, H, D = q.shape
    layer, k_pool, v_pool, k_scales, v_scales = _whole_pool(
        layer, k_pool, v_pool, k_scales, v_scales)
    Hkv, Bs = k_pool.shape[2], k_pool.shape[3]
    G = H // Hkv
    MB = tables.shape[1]
    if scale is None:
        scale = D ** -0.5
    quant = k_scales is not None
    if not block_q:
        # whole chunk per q block while VMEM allows: K/V are streamed
        # once per (batch, head) instead of once per q block
        block_q = T
        while block_q > 16 and not paged_viable(block_q, G, D, Bs):
            block_q //= 2
    block_q = min(block_q, T)
    pad_t = (-T) % block_q
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    Tp = T + pad_t
    nq = Tp // block_q

    # q as [B, Tp, Hkv, G, D]: BlockSpec carves per-(b, kv-head) panels
    # out of the native layout, (G, D) minor
    q5 = q.reshape(B, Tp, Hkv, G, D)

    def kv_index(b, h, qi, j, tabs, sts, lyr):
        # clamp out-of-range blocks (past-causal above, before the
        # sliding window below) onto the nearest visible one: the index
        # stops changing, so Pallas skips the DMA re-fetch and pl.when
        # skips the compute
        jmax = jax.lax.div(sts[b] + qi * block_q + (block_q - 1),
                           Bs)
        jj = jnp.minimum(jnp.minimum(j, jmax),
                         jnp.int32(MB - 1))
        if window:
            jmin = jax.lax.div(
                jnp.maximum(sts[b] + qi * block_q - (window - 1), 0), Bs)
            jj = jnp.maximum(jj, jnp.minimum(jmin, jnp.int32(MB - 1)))
        jj = jnp.maximum(jj, 0)
        return (lyr[0], tabs[b, jj], h, 0, 0)

    def scale_index(b, h, qi, j, tabs, sts, lyr):
        # the whole head axis rides in the block: the TPU lowering
        # wants a block's second-minor dim to be a multiple of 8 or the
        # full axis, and one head's [1, Bs] row is neither
        return kv_index(b, h, qi, j, tabs, sts, lyr)[:2] + (0, 0)

    def q_index(b, h, qi, j, tabs, sts, lyr):
        return (b, qi, h, 0, 0)

    grid = (B, Hkv, nq, nb)
    kernel = functools.partial(
        _paged_kernel, block_q=block_q, groups=G, block_size=Bs,
        nb=nb, scale=scale, quant=quant, window=window,
        softcap=softcap)
    rows = block_q * G
    in_specs = [
        pl.BlockSpec((1, block_q, 1, G, D), q_index),
        pl.BlockSpec((1, 1, 1, Bs, D), kv_index),
        pl.BlockSpec((1, 1, 1, Bs, D), kv_index),
    ]
    operands = [q5, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, Hkv, Bs), scale_index)] * 2
        operands += [k_scales, v_scales]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, 1, G, D), q_index),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Tp, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(starts, jnp.int32),
      layer, *operands)

    return out.reshape(B, Tp, H, D)[:, :T]


# ---------------------------------------------------------------------
# decode-specialized kernel: all kv heads + several pool blocks per
# grid step.
#
# The general kernel's grid is (B, Hkv, NQ, nb) with ONE 64-token block
# per step — for decode (T = 1) each step is a [G, D] x [D, Bs] dot,
# so small that fixed per-grid-step cost (DMA issue, program dispatch)
# dominates: at batch 32, kv 768, 22 layers that is ~34k grid steps per
# decode step and the measured device time is ~3x the HBM floor. Here
# the grid is (B, ceil(nb / R)): each step fetches one [Hkv, Bs, D]
# K and V panel per sub-block (all kv heads ride one DMA — they are
# contiguous in the pool's [N, Hkv, Bs, D] layout) and statically
# unrolls Hkv x R small dots, cutting grid steps by Hkv*R (16x for
# TinyLlama geometry) while reading exactly the same KV bytes.
# ---------------------------------------------------------------------

# decode/spec windows have T <= spec+1 << this; prefill chunks go to
# the general kernel
DECODE_T_MAX = 8
# KV pool blocks fetched+processed per decode-kernel grid step. More
# blocks per step = fewer grid steps (less per-step overhead) but a
# bigger VMEM working set (R panels of [Hkv, Bs, D] K and V each).
# Env-tunable for hardware sweeps: PSTPU_DECODE_BLOCKS_PER_STEP.


def _env_blocks_per_step(default: int = 4) -> int:
    """Validated at import: a malformed or non-positive value must not
    crash module import or reach the decode-kernel grid math — warn and
    serve on the default instead."""
    raw = os.environ.get("PSTPU_DECODE_BLOCKS_PER_STEP")
    if raw is None:
        return default
    try:
        value = int(raw)
    except (TypeError, ValueError):
        warnings.warn(
            f"PSTPU_DECODE_BLOCKS_PER_STEP={raw!r} is not an integer; "
            f"falling back to {default}", RuntimeWarning)
        return default
    if value < 1:
        warnings.warn(
            f"PSTPU_DECODE_BLOCKS_PER_STEP={value} must be >= 1; "
            f"falling back to {default}", RuntimeWarning)
        return default
    return value


_BLOCKS_PER_STEP = _env_blocks_per_step()


def _paged_decode_kernel(tabs_ref, starts_ref, layer_ref, q_ref, *refs,
                         T: int,
                         heads_kv: int, groups: int, block_size: int,
                         ngrp: int, R: int, scale: float,
                         quant: bool = False, window: int = 0,
                         softcap: float = 0.0):
    """One (batch row, block group) grid step.

    tabs_ref   (SMEM) [B, MB]     block tables
    starts_ref (SMEM) [B]         absolute position of q[:, 0]
    layer_ref  (SMEM) [1]         the pool's layer (index maps only)
    q_ref   [1, Hkv, T*G, D]      all heads' queries (rows = t*G + g)
    refs    R k panels [1, 1, Hkv, Bs, D], R v panels, (quant only:
            R ks + R vs dequant scales [1, 1, Hkv, Bs] fp32,) out
            [1, Hkv, T*G, D], scratch m/l [Hkv*T*G, 1], acc
            [Hkv*T*G, D] — online softmax state across the group axis.
    """
    k_refs = refs[:R]
    v_refs = refs[R:2 * R]
    refs = refs[2 * R:]
    if quant:
        ks_refs = refs[:R]
        vs_refs = refs[R:2 * R]
        refs = refs[2 * R:]
    out_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    jg = pl.program_id(1)
    rows = T * groups
    D = q_ref.shape[-1]

    @pl.when(jg == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = starts_ref[b]
    jmax = jax.lax.div(start + (T - 1), block_size)
    # sliding window: whole groups before the earliest query's window
    # are skipped (window == 0 means full causal)
    jmin = (jax.lax.div(jnp.maximum(start - (window - 1), 0), block_size)
            if window else 0)

    @pl.when((jg * R <= jmax) & (jg * R + (R - 1) >= jmin))
    def _compute():
        # row r (within a head) queries position start + r // G
        row_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // groups
        for h in range(heads_kv):
            q = q_ref[0, h].astype(jnp.float32) * scale      # [rows, D]
            sl = slice(h * rows, (h + 1) * rows)
            m_prev = m_ref[sl]
            l_prev = l_ref[sl]
            acc_prev = acc_ref[sl]
            for i in range(R):
                j = jg * R + i
                k_blk = k_refs[i][0, 0, h].astype(jnp.float32)  # [Bs, D]
                v_blk = v_refs[i][0, 0, h].astype(jnp.float32)
                if quant:
                    k_blk = k_blk * ks_refs[i][0, 0, h][:, None]
                    v_blk = v_blk * vs_refs[i][0, 0, h][:, None]
                s = jax.lax.dot_general(
                    q, k_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [rows, Bs]
                if softcap:
                    s = softcap * jnp.tanh(s / softcap)
                k_pos = j * block_size + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_size), 1)
                live = (k_pos <= row_pos) & (j <= jmax)
                if window:
                    live = live & (k_pos > row_pos - window)
                s = jnp.where(live, s, _NEG_INF)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1,
                                                    keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_prev = l_prev * corr + jnp.sum(p, axis=-1,
                                                 keepdims=True)
                acc_prev = acc_prev * corr + jax.lax.dot_general(
                    p, v_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [rows, D]
                m_prev = m_new
            m_ref[sl] = m_prev
            l_ref[sl] = l_prev
            acc_ref[sl] = acc_prev

    @pl.when(jg == ngrp - 1)
    def _emit():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = out.reshape(heads_kv, rows, D).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("nb", "interpret",
                                             "window", "scale",
                                             "softcap"))
def paged_decode_attention(q, k_pool, v_pool, tables, starts, *,
                           nb: int, interpret: bool = False,
                           k_scales=None, v_scales=None,
                           window: int = 0,
                           scale: float = None, softcap: float = 0.0,
                           layer=None):
    """paged_attention specialized for short query windows (T <=
    DECODE_T_MAX): same contract, same result, far fewer grid steps.

    q [B, T, H, D]; k/v pool [N, Hkv, Bs, D], or with ``layer`` the
    whole pool [L, N, Hkv, Bs, D]; tables [B, MB] int32; starts [B].
    See paged_attention for semantics. k_scales/v_scales
    [(L,) N, Hkv, Bs] fp32 activate the int8-pool mode (panels stream
    as int8, dequantized in VMEM — half the KV bytes of the bf16 pool).
    """
    B, T, H, D = q.shape
    layer, k_pool, v_pool, k_scales, v_scales = _whole_pool(
        layer, k_pool, v_pool, k_scales, v_scales)
    Hkv, Bs = k_pool.shape[2], k_pool.shape[3]
    G = H // Hkv
    MB = tables.shape[1]
    if scale is None:
        scale = D ** -0.5
    quant = k_scales is not None
    R = min(_BLOCKS_PER_STEP, nb)
    ngrp = -(-nb // R)
    rows = T * G

    # [B, T, Hkv, G, D] -> [B, Hkv, T*G, D]: rows ordered t*G + g per
    # head, matching the kernel's row_pos formula
    qh = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(B, Hkv, rows, D)

    def kv_index(i):
        def index(b, jg, tabs, sts, lyr):
            jmax = jax.lax.div(sts[b] + (T - 1), jnp.int32(Bs))
            jj = jnp.minimum(jnp.minimum(jg * R + i, jmax),
                             jnp.int32(MB - 1))
            if window:
                jmin = jax.lax.div(
                    jnp.maximum(sts[b] - (window - 1), 0), jnp.int32(Bs))
                jj = jnp.maximum(jj, jnp.minimum(jmin,
                                                 jnp.int32(MB - 1)))
            return (lyr[0], tabs[b, jnp.maximum(jj, 0)], 0, 0, 0)
        return index

    def q_index(b, jg, tabs, sts, lyr):
        return (b, 0, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, T=T, heads_kv=Hkv, groups=G,
        block_size=Bs, ngrp=ngrp, R=R, scale=scale, quant=quant,
        window=window, softcap=softcap)
    kv_specs = [pl.BlockSpec((1, 1, Hkv, Bs, D), kv_index(i))
                for i in range(R)]
    in_specs = [
        pl.BlockSpec((1, Hkv, rows, D), q_index),
        *kv_specs, *kv_specs,
    ]
    operands = [qh, *([k_pool] * R), *([v_pool] * R)]
    if quant:
        def sc_index(i):
            ki = kv_index(i)
            return lambda *a: ki(*a)[:4]

        sc_specs = [pl.BlockSpec((1, 1, Hkv, Bs), sc_index(i))
                    for i in range(R)]
        in_specs += [*sc_specs, *sc_specs]
        operands += [*([k_scales] * R), *([v_scales] * R)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, ngrp),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hkv, rows, D), q_index),
            scratch_shapes=[
                pltpu.VMEM((Hkv * rows, 1), jnp.float32),
                pltpu.VMEM((Hkv * rows, 1), jnp.float32),
                pltpu.VMEM((Hkv * rows, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(starts, jnp.int32),
      layer, *operands)

    # [B, Hkv, T*G, D] -> [B, T, H, D]
    out = out.reshape(B, Hkv, T, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, D)


def paged_attention_sharded(q, k_pool, v_pool, tables, starts, mesh, *,
                            nb: int, interpret: bool = False,
                            k_scales=None, v_scales=None,
                            window: int = 0,
                            scale: float = None, softcap: float = 0.0,
                            layer=None):
    """paged_attention under a tp-only mesh: shard_map over the head
    axis (q heads and pool kv heads both shard by tp, tables/starts
    and the layer index replicated) — shard-local, no collectives.
    Caller guarantees the mesh has no other axis of size > 1
    (mesh_tp_only). Short windows (decode/spec) take the wide decode
    kernel, like the unsharded path. int8 pools pass their
    [(L,) N, Hkv, Bs] scales, sharded over the same head axis."""
    from jax.sharding import PartitionSpec as P

    base = (paged_decode_attention if q.shape[1] <= DECODE_T_MAX
            else paged_attention)
    # the whole pool's layer axis leads, unsharded
    lead = (None,) * (k_pool.ndim - 4)
    kv_spec = P(*lead, None, "tp", None, None)
    sc_spec = P(*lead, None, "tp", None)

    def fn(qq, kk, vv, tt, ss, ks, vs, lyr):
        return base(qq, kk, vv, tt, ss, nb=nb, interpret=interpret,
                    k_scales=ks, v_scales=vs, window=window,
                    scale=scale, softcap=softcap, layer=lyr)

    # None (no scales, no layer) is an empty pytree: its spec is unused
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, None, "tp", None), kv_spec, kv_spec, P(), P(),
                  sc_spec, sc_spec, P()),
        out_specs=P(None, None, "tp", None),
        check_vma=False)(q, k_pool, v_pool, tables, starts,
                         k_scales, v_scales, layer)


def mesh_tp_only(mesh) -> bool:
    """True when every mesh axis except 'tp' has size 1 — the
    configuration where the kernel can run shard-local per head."""
    return mesh is not None and all(
        size == 1 for name, size in mesh.shape.items() if name != "tp")


# the gathered-copy jax.numpy attention (ops/attention.py): the test
# reference, and the serving path only where attention_path says so
JNP_GATHER = "jnp_gather"


def attention_path(T: int, groups: int, head_dim: int, block_size: int,
                   mesh=None) -> str:
    """Which cached-attention implementation a forward over T query
    positions per row takes, for ``groups`` query heads per kv head.
    Decided here, by shape, BEFORE anything compiles — a kernel the
    compiler then refuses is an error, not a reason to take another
    path (engine/runner.py records this per executable; GET /debug/perf
    shows it).

    ``pallas_paged_decode``: short windows (decode / speculative
    verify) on the wide kernel — all kv heads + several pool blocks per
    grid step, ~16x fewer grid steps than the general one.
    ``pallas_paged``: prefill chunks on the general paged kernel.
    ``*_sharded``: either, shard-local per head under a tp-only mesh.
    ``jnp_gather``: the kernel is off (PSTPU_FLASH / not a TPU), the
    chunk's working set misses VMEM (paged_viable), or the mesh shards
    the pool's block axis."""
    if not (flash_enabled()
            and paged_viable(T, groups, head_dim, block_size)
            and (mesh is None or mesh_tp_only(mesh))):
        return JNP_GATHER
    kernel = "pallas_paged_decode" if T <= DECODE_T_MAX else "pallas_paged"
    return kernel + ("_sharded" if mesh is not None else "")
