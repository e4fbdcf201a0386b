"""Paged flash attention for TPU: block-table-aware online-softmax GQA.

Two kernels that read the pool, one contract (and one that writes it:
``append_rows``, a decode window's new K/V rows in place, a tile of the
block a row; prefill chunks, the int8 pool, meshes and the CPU keep
models/kv.append_chunk's whole-block rewrite: ``kv_append_path``, its
account further down):

- ``paged_attention``, **prefill** chunks (T up to the chunk bucket) —
  no per-layer gathered K/V copy and no head-major relayout copy of it;
- ``paged_decode_attention``, **decode / speculative windows** (T = 1
  or draft+1 <= DECODE_T_MAX, inside the lax.scan of engine/runner.py)
  — replaces the gather-view + dense jnp path, which materialized a
  [B, kv, Hkv, D] copy of the live cache per layer per step: ~3x the
  minimal KV HBM traffic, the dominant cost of long-context decode.
  Its account stands above it, further down.

K/V pool blocks ``[N, Hkv, Bs, D]`` (models/kv.py, head-major: the
per-(block, head) panel is a contiguous [Bs, D] tile) are streamed
straight from HBM through *scalar-prefetched* block tables, and each
KV byte a row needs is read exactly once a q block. In the prefill
kernel the grid's innermost dimension walks a row's blocks, a PANEL of
R of them a step (``prefill_tiles``), and the BlockSpec index maps read
``tables[b, j*R + i]`` to point the step's DMAs at the right blocks;
blocks past a q block's last query position clamp to the last live
one and grid steps wholly past it are `pl.when`-masked away. Pallas'
pipeline skips a copy only when an operand's block index is the one
it had the grid step before, so each of the R operands fetches the
clamped block once more where a row's dead blocks begin: nothing to a
prefill chunk, whose q blocks see most of the bucket, and why the
decode kernel (one position a row, rows of every length) issues its
own copies, for live blocks alone.

The serving path hands in the WHOLE pool ``[L, N, Hkv, Bs, D]`` and a
``layer`` index, a third scalar-prefetched operand that the index maps
put in front (``(layer, tables[b, j], ...)``): no layer's pool is
sliced out of the buffer the step program carries (models/kv.py
"carried, never stacked"). A bare 4-D layer is the same call on a pool
of one layer.

Prefill grid ``(B, Hkv, NQ, nb / R)``; per step the q block [BQ, G, D]
for one kv head and a panel of R pool blocks' [R*Bs, D] K and V live in
VMEM, and go to the MXU as stored (bf16 in every cell; float32
products, float32 softmax). Online (max, sum, acc) statistics persist
in VMEM scratch across the last axis (sequential "arbitrary"
dimension), initialized at j == 0 and emitted at the last step — the
classic flash accumulation, with GQA rows flattened as t*G + g so K/V
are never broadcast to query heads.

The latent pool (one cached vector ``[c | k_rope]`` a token, every
query head on it) has two cases of the prefill kernel. ABSORBED: the
queries carry W_uk, all heads' rows share a key block, the values are
the keys' leading columns. EXPANDED, for a chunk of enough positions a
row that it multiplies less (``expanded_cheaper``): grid ``(B, H, NQ,
panels)``, one head's whole chunk of queries against a panel of
several pool blocks whose keys and values of that head are made in
VMEM from the cached latents with the head's slice of W_kvb, once a
(head, q block, panel); nothing per head is written to HBM and the
pool is the same.

Sharded serving: under a tp-only mesh the kernel runs inside
``shard_map`` over the head axis (q heads and pool heads both shard by
tp; tables/starts replicate) — embarrassingly parallel, no collectives.
Meshes that shard the pool's block axis (dp > 1) keep the jnp gather
path, whose collectives XLA inserts.

Which implementation a cached attention takes is decided HERE, by
``attention_path``: it owns every fact the decision consults (the
run-time gate, ``paged_viable``, ``mesh_tp_only``, ``DECODE_T_MAX``);
``kv_append_path`` decides the same way how the new rows are written.
models/kv.py asks them at trace time and engine/runner.py when it
compiles an executable; nobody else reads those facts.

The reference repo ships no kernels (attention lives in the external
vLLM engine, SURVEY.md §2.9); this is TPU-first work. Numerics are
pinned against the dense jnp path in tests/test_pallas_paged.py via
interpret mode on CPU.
"""

import functools
import os

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
# for Pallas' double-buffered K/V panels and the output block. What a
# path must fit to be viable at all, the latent case's q block and the
# decode kernel's chunk are reckoned against it.
_VMEM_WORK_BYTES = 8 * 1024 * 1024
# and the same reckoning's ceiling for a q block of the K/V prefill
# kernel against a PANEL of pool blocks, within VMEM_LIMIT_BYTES: set
# from tools/kv_prefill_table.py on the v5e (PERF.md, PR 43). At 8
# groups of 256 against 512 keys a q block of 512 positions (4096
# rows: 27 MB by this reckoning) ran a 16 384-key call in 3.46 ms, one
# of 256 in 3.70, one of 128 in 4.32; 1024 was not measured.
_PANEL_WORK_BYTES = 28 * 1024 * 1024


def _work_bytes(T: int, groups: int, head_dim: int, panel: int,
                value_dim: int = 0) -> int:
    """What one grid step's own arrays take: a [T*G, D] q panel (as
    float32 and as stored), the float32 accumulator and one
    [T*G, panel] block of float32 scores and of probabilities."""
    rows = max(T * groups, 8)
    return (rows * (head_dim + (value_dim or head_dim)) * 4
            + rows * panel * 4 * 2 + rows * head_dim * 2)


def paged_viable(T: int, groups: int, head_dim: int,
                 block_size: int, value_dim: int = 0) -> bool:
    """Can a [T*G, D] q panel + accumulator + one [T*G, Bs] score
    block hold in VMEM? T is what ONE grid step holds: a decode
    window's positions, or the smallest q block of a prefill chunk
    against ONE pool block (_MIN_BLOCK_Q positions: a path is viable
    where that is, ``attention_path``; ``prefill_tiles`` then widens
    both as far as the shapes allow). value_dim: the accumulator's
    width where it is not the keys' (the latent pool)."""
    return _work_bytes(T, groups, head_dim, block_size,
                       value_dim) <= _VMEM_WORK_BYTES


# the smallest q block the prefill kernel cuts a chunk into
_MIN_BLOCK_Q = 16
# the sparse case of the prefill kernel (a mask of selected positions
# over the latent pool): the queries a q block holds and the keys a
# grid step takes; 32 x 64 heads against 512 keys are 10 MB of scores,
# probabilities and accumulator (one setting run on the chip: PERF.md)
_SELECT_BLOCK_Q = 32
_SELECT_PANEL_TOKENS = 512
# the expanded case of the prefill kernel (a long chunk over the latent
# pool, keys and values made per head from the cached latents): one
# head's queries a q block, so the whole chunk up to this many, because
# a key panel is expanded once a (head, q block, panel); the panel the
# sparse case's
_EXPAND_BLOCK_Q = 2048


def _panel_blocks(nb: int, block_size: int) -> int:
    """The most pool blocks a grid step of the prefill kernel takes: as
    many as divide the kv bucket, up to _SELECT_PANEL_TOKENS keys."""
    return next(r for r in (8, 4, 2, 1)
                if r * block_size <= _SELECT_PANEL_TOKENS and nb % r == 0)


def prefill_tiles(T: int, groups: int, head_dim: int, nb: int,
                  block_size: int, value_dim: int = 0) -> tuple:
    """(q block, R): how the prefill kernel cuts a chunk of T positions
    a row against a kv bucket of ``nb`` pool blocks, read off the
    shapes alone. The K/V pool: R blocks a grid step, the widest panel
    up to _SELECT_PANEL_TOKENS keys that divides the bucket, and the
    largest q block (the chunk, halved) whose working set holds beside
    it (_PANEL_WORK_BYTES): K and V stream once a q block, a step's
    overhead is paid once a panel. The panel narrows before the q
    block falls under _MIN_BLOCK_Q, so whatever is viable at one block
    a step (``attention_path``) has its tiles. The latent pool's
    absorbed case without a mask: one block a step and the q block
    ``paged_viable`` allows, as it was sized (PERF.md section 7)."""
    R = 1 if value_dim else _panel_blocks(nb, block_size)
    limit = _VMEM_WORK_BYTES if value_dim else _PANEL_WORK_BYTES

    def holds(block_q: int) -> bool:
        return _work_bytes(block_q, groups, head_dim, R * block_size,
                           value_dim) <= limit

    while True:
        block_q = T
        while block_q > _MIN_BLOCK_Q and not holds(block_q):
            block_q //= 2
        if R == 1 or holds(block_q):
            return block_q, R
        R //= 2


def _online_softmax(s, v, m_ref, l_ref, acc_ref, v_scale=None) -> None:
    """One key panel's masked scores s [rows, P] (float32) and values v
    [P, Dv] folded into the running (max, sum, accumulator) of the
    prefill kernels: the flash accumulation, float32, the probabilities
    to the MXU in the values' dtype. v_scale [1, P]: the int8 pool's
    per-token dequantization, on the probabilities."""
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                # [rows, P]
    correction = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [rows, Dv]


def _whole_pool(layer, k_pool, v_pool, k_scales, v_scales):
    """(layer [1] int32, pools and scales with a leading layer axis): a
    bare layer [N, Hkv, Bs, D] becomes a pool of one (a bitcast), so
    the kernels have one indexing."""
    pools = (k_pool, v_pool, k_scales, v_scales)
    if layer is None:
        layer = 0
        pools = tuple(p if p is None else p[None] for p in pools)
    return (jnp.asarray(layer, jnp.int32).reshape(1),) + pools


def _paged_kernel(tabs_ref, starts_ref, layer_ref, q_ref, k_ref, *refs,
                  block_q: int, groups: int,
                  block_size: int, nb: int, scale: float,
                  quant: bool = False, window: int = 0,
                  softcap: float = 0.0, value_dim: int = 0,
                  select: bool = False, R: int = 1):
    """One (batch row, kv head, q block, key panel) grid step.

    tabs_ref   (SMEM) [B, MB]      block tables
    starts_ref (SMEM) [B]          absolute position of q[:, 0]
    layer_ref  (SMEM) [1]          the pool's layer (index maps only)
    q_ref   [1, BQ, 1, G, D]       this kv-head's query block
    k_ref.. R of [1, 1, 1, Bs, D]  pool blocks tabs[b, min(j*R + i, jmax)]
                                   side by side as one [R*Bs, D] panel
    refs    R of v_ref [1, 1, 1, Bs, D] likewise, (quant only: the
            panel's k and v dequant scales [1, 1, Hkv, R*Bs] fp32,
            gathered through the tables by the wrapper — every kv head
            of the panel, this step reads row h,)
            out [1, BQ, 1, G, D], scratch m/l/acc (online softmax
            state across j)

    A grid step takes R pool blocks (static; ``prefill_tiles``):
    contexts of thousands of tokens in steps of one 64-token block
    leave the MXU half empty and pay a grid step's overhead 256 times
    a q block. Both dots take their operands as stored (bf16 to the
    MXU in every cell, float32 products; the int8 pool converted to
    q's dtype, which is exact, its per-token scales on the score
    columns and the probabilities), as the decode kernel's do; scale,
    soft cap, masks and the online softmax are float32, and the
    probabilities meet V in V's dtype, as in ops/attention.py.

    The latent pool (value_dim > 0, static): no v_ref — the values are
    the first value_dim columns of the K panel — q_ref [1, BQ*G, D]
    and out [1, BQ*G, value_dim] come with their rows flattened by the
    wrapper (one kv head).

    select (static; the latent pool, learned sparse attention): one
    more operand after the pools, sel_ref [1, 1, 1, BQ, R*Bs] of 0 / 1:
    the positions of this step's pool blocks that each query of the
    block attends (models/kv.attend_selected); the others are masked
    like the positions past the query.
    """
    def panel(block_refs):
        return (block_refs[0][0, 0, 0] if R == 1 else jnp.concatenate(
            [ref[0, 0, 0] for ref in block_refs], axis=0))  # [R*Bs, D]

    k_refs = (k_ref,) + refs[:R - 1]
    refs = refs[R - 1:]
    block_size = R * block_size                 # the step's key panel
    if not value_dim:
        v_refs, refs = refs[:R], refs[R:]
    if select:
        sel_ref, refs = refs[0], refs[1:]
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
    # last panel this q block can see: beyond it (and, inside it, past
    # the index map's clamp per block) the DMA re-targets a resident
    # block and the step is skipped entirely
    max_pos = start + qi * block_q + (block_q - 1)
    jmax = jax.lax.div(max_pos, block_size)
    # sliding window: panels wholly before the EARLIEST query row's
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
        k_blk = panel(k_refs)
        v_scale = None
        if value_dim:
            v_blk = k_blk[:, :value_dim]
            q = q_ref[0]
        else:
            q = q_ref[0].reshape(rows, D)
            v_blk = panel(v_refs)
            if quant:
                k_blk, v_blk = k_blk.astype(q.dtype), v_blk.astype(q.dtype)
                v_scale = vs_ref[0, 0, pl.ds(h, 1), :]        # [1, R*Bs]
        s = jax.lax.dot_general(
            q.astype(k_blk.dtype), k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [rows, R*Bs]
        if quant:
            s = s * ks_ref[0, 0, pl.ds(h, 1), :]
        if softcap:
            # Gemma-2 tanh cap on RAW scores, before -inf masking
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        live = k_pos <= q_pos
        if window:
            live = live & (k_pos > q_pos - window)
        if select:
            # the query's row of marks for each of its heads' rows
            # (t*G + g): a product with the 0 / 1 matrix that repeats
            # it. A row all of whose positions so far are masked
            # gathers weight 1 on each, which the first marked one's
            # correction wipes
            mine = (row_ids == jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q), 1)).astype(sel_ref.dtype)
            live = live & (jax.lax.dot_general(
                mine, sel_ref[0, 0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) > 0.5)
        _online_softmax(jnp.where(live, s, _NEG_INF), v_blk,
                        m_ref, l_ref, acc_ref, v_scale)

    @pl.when(j == nb - 1)
    def _emit():
        # fully-masked (padding/parked) rows have l == 0; keep finite
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        if not value_dim:
            out = out.reshape(block_q, 1, groups, D)
        out_ref[0] = out.astype(out_ref.dtype)


def _expanded_kernel(tabs_ref, starts_ref, layer_ref, q_ref, wk_ref,
                     wv_ref, *refs, block_q: int, block_size: int,
                     nb: int, scale: float, value_dim: int,
                     scaled: bool, select: bool, R: int):
    """One (batch row, head, q block, key panel) grid step of the
    EXPANDED case over the latent pool: the panel's keys and values of
    this head are made from its cached latents, here, and attended per
    head.

    q_ref   [1, 1, BQ, Dq]         the head's queries [q_nope | q_rope]
    wk_ref  [1, W, Dq]             the head's key map: a cached vector
                                   [c | k_rope | 0] -> [k_nope | k_rope]
                                   (W_uk on c's rows, the identity on
                                   k_rope's)
    wv_ref  [1, value_dim, Dv]     the head's value map W_uv
    refs    R pool blocks [1, 1, 1, Bs, W] side by side as one panel,
            (scaled: the maps' per-channel scales [1, 1, Dq], [1, 1, Dv]
            fp32: int8 weights go to the MXU as the pool's dtype and
            the scales multiply the products,) (select: the queries'
            marks [1, BQ, R*Bs] of 0 / 1,) out [1, 1, BQ, Dv], scratch
            m/l/acc.

    Operands go to the MXU as stored (bf16), products are float32: the
    absorbed case's precision. Per (query, key) a head pays 2 Dq + 2 Dv
    operations where absorbed pays 2 W + 2 value_dim, and per key the
    two maps once a q block: ``expanded_cheaper``.
    """
    k_refs, refs = refs[:R], refs[R:]
    if scaled:
        sk_ref, sv_ref, refs = refs[0], refs[1], refs[2:]
    if select:
        sel_ref, refs = refs[0], refs[1:]
    out_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    panel = R * block_size

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    first = starts_ref[b] + qi * block_q        # the block's first query
    jmax = jax.lax.div(first + (block_q - 1), panel)

    @pl.when(j <= jmax)
    def _compute():
        lat = (k_refs[0][0, 0, 0] if R == 1 else jnp.concatenate(
            [ref[0, 0, 0] for ref in k_refs], axis=0))      # [P, W]
        k = jax.lax.dot_general(
            lat, wk_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [P, Dq]
        v = jax.lax.dot_general(
            lat[:, :value_dim], wv_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [P, Dv]
        if scaled:
            k, v = k * sk_ref[0], v * sv_ref[0]
        k, v = k.astype(lat.dtype), v.astype(lat.dtype)
        s = jax.lax.dot_general(
            q_ref[0, 0].astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [BQ, P]
        q_pos = first + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        k_pos = j * panel + jax.lax.broadcasted_iota(
            jnp.int32, (1, panel), 1)
        live = k_pos <= q_pos
        if select:
            # (the v5e compares no bf16: the marks as float32)
            live = live & (sel_ref[0].astype(jnp.float32) > 0.5)
        _online_softmax(jnp.where(live, s, _NEG_INF), v,
                        m_ref, l_ref, acc_ref)

    @pl.when(j == nb - 1)
    def _emit():
        out_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                         ).astype(out_ref.dtype)


def _expanded_attention(q, pool, tables, starts, layer, expand, *,
                        nb: int, block_q: int, interpret: bool,
                        scale: float, value_dim: int, select):
    """paged_attention's expanded case (its text): q [B, T, H, Dq] the
    heads' queries [q_nope | q_rope], pool [L, N, 1, Bs, W] the latent
    pool -> [B, T, H, Dv]."""
    w_uk, w_uv, s_k, s_v = expand
    B, T, H, D = q.shape
    Bs, W = pool.shape[3], pool.shape[4]
    MB = tables.shape[1]
    dn, Dv = w_uk.shape[-1], w_uv.shape[-1]
    dr = D - dn
    cdt = pool.dtype
    # the key map [H, W, Dq]: W_uk on c's rows, the identity on k_rope's
    # (a bf16 value times 1 summed in float32 is itself), the pool's
    # padding on rows of zeros: keys come out of ONE product, rope part
    # in place, where a concatenation at column dn would shift lanes
    wk = jnp.zeros((H, W, D), cdt)
    wk = wk.at[:, :value_dim, :dn].set(w_uk.transpose(1, 0, 2).astype(cdt))
    wk = wk.at[:, value_dim:value_dim + dr, dn:].set(jnp.eye(dr, dtype=cdt))
    wv = w_uv.transpose(1, 0, 2).astype(cdt)                # [H, r, Dv]
    scaled = s_k is not None
    R = _panel_blocks(nb, Bs)
    block_q = min(block_q or _EXPAND_BLOCK_Q, T)
    pad_t = (-T) % block_q
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    Tp = T + pad_t
    nq = Tp // block_q

    def head_index(b, h, qi, j, tabs, sts, lyr):
        return (b, h, qi, 0)

    def weight_index(b, h, qi, j, tabs, sts, lyr):
        return (h, 0, 0)

    def kv_index(b, h, qi, j, tabs, sts, lyr, i=0):
        # blocks past the q block's last query clamp onto the last
        # visible one; pl.when skips their arithmetic
        jmax = jax.lax.div(sts[b] + qi * block_q + (block_q - 1), Bs)
        jj = jnp.minimum(jnp.minimum(j * R + i, jmax), jnp.int32(MB - 1))
        return (lyr[0], tabs[b, jnp.maximum(jj, 0)], 0, 0, 0)

    in_specs = [pl.BlockSpec((1, 1, block_q, D), head_index),
                pl.BlockSpec((1, W, D), weight_index),
                pl.BlockSpec((1, value_dim, Dv), weight_index)]
    operands = [q.transpose(0, 2, 1, 3), wk, wv]
    for i in range(R):
        in_specs.append(pl.BlockSpec((1, 1, 1, Bs, W),
                                     functools.partial(kv_index, i=i)))
        operands.append(pool)
    if scaled:
        in_specs += [pl.BlockSpec((1, 1, D), weight_index),
                     pl.BlockSpec((1, 1, Dv), weight_index)]
        operands += [
            jnp.concatenate([s_k.astype(jnp.float32),
                             jnp.ones((H, dr), jnp.float32)],
                            axis=-1)[:, None],
            s_v.astype(jnp.float32)[:, None]]
    if select is not None:
        if pad_t:
            select = jnp.pad(select, ((0, 0), (0, pad_t), (0, 0)))
        in_specs.append(pl.BlockSpec(
            (1, block_q, R * Bs),
            lambda b, h, qi, j, tabs, sts, lyr: (b, qi, j)))
        operands.append(select)
    kernel = functools.partial(
        _expanded_kernel, block_q=block_q, block_size=Bs, nb=nb // R,
        scale=scale, value_dim=value_dim, scaled=scaled,
        select=select is not None, R=R)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, nq, nb // R),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, block_q, Dv), head_index),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(starts, jnp.int32),
      layer, *operands)
    return out.transpose(0, 2, 1, 3)[:, :T]


@functools.partial(jax.jit,
                   static_argnames=("nb", "block_q", "panel_blocks",
                                    "interpret", "window", "scale",
                                    "softcap", "value_dim"))
def paged_attention(q, k_pool, v_pool, tables, starts, *, nb: int,
                    block_q: int = 0, panel_blocks: int = 0,
                    interpret: bool = False,
                    k_scales=None, v_scales=None, window: int = 0,
                    scale: float = None, softcap: float = 0.0,
                    layer=None, value_dim: int = 0, select=None,
                    expand=None):
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
    mode: panels stream from HBM as int8 (half the bytes) and go to the
    dots as q's dtype (exact); the per-token scales multiply the score
    columns and the probabilities.

    block_q, panel_blocks (static; 0: by ``prefill_tiles``): the
    positions a q block holds and the pool blocks a grid step takes,
    for tests and tools/kv_prefill_table.py; the serving path gives
    neither.

    The latent pool: k_pool [(L,) N, 1, Bs, W], v_pool None and
    value_dim (static) the leading columns of a key that are its value;
    q [B, T, H, W] the absorbed queries -> [B, T, H, value_dim].

    select [B, T, nb*Bs] of 0 / 1 (the latent pool only): the virtual
    positions each query attends, of those at or before it (learned
    sparse attention: models/kv.attend_selected). Every live block is
    still read; the marks mask the scores.

    expand (the latent pool only): ``(w_uk [value_dim, H, nope], w_uv
    [value_dim, H, Dv], their per-channel scales [H, nope], [H, Dv] or
    None, None)``, the two halves of W_kvb: the EXPANDED case. q
    [B, T, H, nope + rope] are then the heads' own queries
    ``[q_nope | q_rope]``, each key panel's ``k_nope`` and ``v`` are
    made per head from the cached ``c`` inside the kernel (int8 maps as
    the pool's dtype, the scales on the products), every head attends
    ``[k_nope | k_rope]`` -> [B, T, H, Dv]; the same pool, tables,
    causal clamp and marks (``_expanded_kernel``). Which chunks take
    it: ``attention_path``.
    """
    B, T, H, D = q.shape
    layer, k_pool, v_pool, k_scales, v_scales = _whole_pool(
        layer, k_pool, v_pool, k_scales, v_scales)
    if expand is not None:
        assert value_dim and not (window or softcap), \
            "expand: the latent pool, full causal"
        return _expanded_attention(
            q, k_pool, tables, starts, layer, expand, nb=nb,
            block_q=block_q, interpret=interpret,
            scale=D ** -0.5 if scale is None else scale,
            value_dim=value_dim, select=select)
    Hkv, Bs = k_pool.shape[2], k_pool.shape[3]
    G = H // Hkv
    tables = jnp.asarray(tables, jnp.int32)
    MB = tables.shape[1]
    assert select is None or value_dim, "select: the latent pool only"
    if scale is None:
        scale = D ** -0.5
    quant = k_scales is not None
    if select is not None:
        # the sparse case reads long contexts: _SELECT_BLOCK_Q queries
        # against the widest panel a step
        tiles = (_SELECT_BLOCK_Q, _panel_blocks(nb, Bs))
    else:
        tiles = prefill_tiles(T, G, D, nb, Bs, value_dim)
    block_q, R = block_q or tiles[0], panel_blocks or tiles[1]
    assert nb % R == 0, (nb, R)
    block_q = min(block_q, T)
    pad_t = (-T) % block_q
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    Tp = T + pad_t
    nq = Tp // block_q

    # q as [B, Tp, Hkv, G, D]: BlockSpec carves per-(b, kv-head) panels
    # out of the native layout, (G, D) minor. The latent pool's one kv
    # head: [B, Tp*G, D], the rows (t*G + g) the kernel wants, as q lies
    Dv = value_dim or D
    q5 = (q.reshape(B, Tp * G, D) if value_dim
          else q.reshape(B, Tp, Hkv, G, D))

    def kv_index(b, h, qi, j, tabs, sts, lyr, i=0):
        # clamp out-of-range blocks (past-causal above, before the
        # sliding window below) onto the nearest visible one: the index
        # stops changing, so Pallas skips the DMA re-fetch and pl.when
        # skips the compute. (i: which of a step's R blocks)
        jmax = jax.lax.div(sts[b] + qi * block_q + (block_q - 1),
                           Bs)
        jj = jnp.minimum(jnp.minimum(j * R + i if R > 1 else j, jmax),
                         jnp.int32(MB - 1))
        if window:
            jmin = jax.lax.div(
                jnp.maximum(sts[b] + qi * block_q - (window - 1), 0), Bs)
            jj = jnp.maximum(jj, jnp.minimum(jmin, jnp.int32(MB - 1)))
        jj = jnp.maximum(jj, 0)
        return (lyr[0], tabs[b, jj], h, 0, 0)

    def scale_index(b, h, qi, j, tabs, sts, lyr):
        # a panel's scales, the whole head axis in the block: the TPU
        # lowering wants a block's second-minor dim to be a multiple of
        # 8 or the full axis, and one head's [1, R*Bs] row is neither
        return (b, j, 0, 0)

    def q_index(b, h, qi, j, tabs, sts, lyr):
        return (b, qi, 0) if value_dim else (b, qi, h, 0, 0)

    grid = (B, Hkv, nq, nb // R)
    kernel = functools.partial(
        _paged_kernel, block_q=block_q, groups=G, block_size=Bs,
        nb=nb // R, scale=scale, quant=quant, window=window,
        softcap=softcap, value_dim=value_dim, select=select is not None,
        R=R)
    rows = block_q * G
    q_block, out_block = (
        ((1, rows, D), (1, rows, Dv)) if value_dim
        else ((1, block_q, 1, G, D),) * 2)
    in_specs = [pl.BlockSpec(q_block, q_index)]
    operands = [q5]
    for pool in (k_pool,) if value_dim else (k_pool, v_pool):
        for i in range(R):                  # the step's blocks of it
            in_specs.append(pl.BlockSpec((1, 1, 1, Bs, D),
                                         functools.partial(kv_index, i=i)))
            operands.append(pool)
    if select is not None:
        # [B, T, nb*Bs] -> a [BQ, Bs] tile per (q block, pool block),
        # whole in its two minor dimensions
        if pad_t:
            select = jnp.pad(select, ((0, 0), (0, pad_t), (0, 0)))
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, block_q, R * Bs),
            lambda b, h, qi, j, tabs, sts, lyr: (b, qi, j, 0, 0)))
        operands.append(select.reshape(B, nq, block_q, nb // R, R * Bs
                                       ).transpose(0, 1, 3, 2, 4))
    if quant:
        # the rows' scales panel by panel as the score columns lie (the
        # decode kernel's operand: its text says why gathered here)
        in_specs += [pl.BlockSpec((1, 1, Hkv, R * Bs), scale_index)] * 2
        operands += [_chunked_scales(sc, layer, tables, nb // R, R)
                     for sc in (k_scales, v_scales)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(out_block, q_index),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            q5.shape[:-1] + (Dv,), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tables, jnp.asarray(starts, jnp.int32), layer, *operands)

    return out.reshape(B, Tp, H, Dv)[:, :T]


# ---------------------------------------------------------------------
# decode-specialized kernel: one grid step per batch row, which walks
# the row's LIVE blocks in chunks of R and copies them in itself.
#
# Grid ``(B,)``. The K and V pools stay in HBM; q, the output (and the
# int8 pool's scales, below) are BlockSpec operands. A grid step reads
# the row's ``start`` and table row from SMEM, reckons the live block
# range [lo, hi] (causal above, the sliding window below) and loops
# over the chunks lo // R .. hi // R, a dynamic trip count; chunk g
# holds blocks g*R .. g*R + R - 1. Per chunk it
#   - starts one DMA per live block and pool: a block's [Hkv, Bs, D]
#     panel is contiguous in the [L, N, Hkv, Bs, D] pool, so all kv
#     heads ride one descriptor. The copies land in one of two VMEM
#     slots [R, Hkv, Bs, D]; the next chunk (or the next row's first
#     chunk) is started before the current one is waited for, so the
#     copies run under the arithmetic;
#   - per kv head, multiplies q [T*G, D] with the chunk's K panel
#     [R*Bs, D] as both lie in memory (bf16 x bf16, or the int8 pool
#     converted to q's dtype, which is exact) into float32 scores, and
#     stores them as rows h*T*G.. of ONE [Hkv*T*G, R*Bs] panel;
#   - scales, (soft-caps,) masks and updates the online-softmax
#     statistics of every head in one pass over that panel: one max,
#     one exp, one sum and one correction per chunk, on full vector
#     registers, float32;
#   - per kv head, multiplies the probabilities, cast to the dots'
#     operand dtype as ops/attention.py does, with the V panel
#     [R*Bs, D] into the float32 accumulator.
#
# What costs nothing: a block past hi or before lo is never copied,
# and is computed only as masked columns of a row's ragged first or
# last chunk (the V slots are zeroed once per call, so a masked
# probability never meets an uninitialized V). A parked row
# (start >= MB*Bs) has no chunk: no copy, no arithmetic, zeros out.
#
# The int8 pool's per-token scales multiply the score columns and the
# probabilities ([T*G, R*Bs]), never a [Bs, D] panel. The TPU compiler
# cannot slice a float32 HBM array whose last dimension is under 128
# for a DMA, so the wrapper gathers the rows' scales through the
# tables into [B, chunks, Hkv, R*Bs] (2 KB a block beside the block's
# 64 KB), and they ride in as one BlockSpec block per row.
#
# R (``decode_blocks_per_step``) follows from the shapes: as many
# blocks as bring _DECODE_CHUNK_BYTES of K and V, so that a chunk's
# copies outlast what starting and waiting for them costs, within
# _DECODE_PANEL_TOKENS score columns and the row's nb blocks.
# ---------------------------------------------------------------------

# decode/spec windows have T <= spec+1 << this; prefill chunks go to
# the general kernel
DECODE_T_MAX = 8
# K and V bytes a chunk aims to bring: on the v5e, chunks of 1 and 2 MiB
# ran a call within 4 % of each other and 0.5 MiB 16-26 % slower
# (PERF.md, PR 30); two slots of it lie in VMEM
_DECODE_CHUNK_BYTES = _VMEM_WORK_BYTES // 4
# and the widest score panel a chunk computes, in tokens (columns):
# wider was not measured
_DECODE_PANEL_TOKENS = 512


def decode_blocks_per_step(nb: int, heads_kv: int, block_size: int,
                           head_dim: int, kv_itemsize: int,
                           latent: bool = False) -> int:
    """R, the pool blocks one chunk of the decode kernel takes: a
    function of the trace-time shapes alone (the kv bucket, the kv
    heads a block holds, its tokens and head dim, the pool's dtype,
    and whether a block is K and V or the latent pool's one panel)."""
    block_bytes = ((1 if latent else 2) * heads_kv * block_size
                   * head_dim * kv_itemsize)
    return max(1, min(_DECODE_CHUNK_BYTES // block_bytes,
                      _DECODE_PANEL_TOKENS // block_size, nb))


def _paged_decode_kernel(tabs_ref, starts_ref, layer_ref, q_ref, k_hbm,
                         *refs,
                         T: int, heads_kv: int, groups: int,
                         block_size: int, nb: int, R: int, scale: float,
                         quant: bool = False, window: int = 0,
                         softcap: float = 0.0, value_dim: int = 0,
                         select: bool = False):
    """One batch row: every live block of it, R at a time.

    tabs_ref   (SMEM) [B, MB]     block tables
    starts_ref (SMEM) [B]         absolute position of q[:, 0]
    layer_ref  (SMEM) [1]         the pool's layer
    q_ref   [1, Hkv, T*G, D]      all heads' queries (rows = t*G + g)
    k_hbm, v_hbm (HBM) [L, N, Hkv, Bs, D]    the pools
    refs    (quant only: the row's k and v scales
            [1, chunks, Hkv, R*Bs] fp32,) out [1, Hkv, T*G, D];
            scratch: the K and V slots [2, R, Hkv, Bs, D], DMA
            semaphores [2 slots, K and V], the score/probability panel
            [Hkv*T*G, R*Bs], m/l [Hkv*T*G, 1], acc and the chunk's p.V
            [Hkv*T*G, D], all fp32, and (SMEM) [2]: the slot the row's
            first chunk lies in and whether the row before already
            started its copies.

    The latent pool (value_dim > 0, static): no v_hbm and no V slots —
    a block's one [1, Bs, W] panel is copied once, and its first
    value_dim columns are the values; out, acc and p.V are value_dim
    wide. select (static; learned sparse attention, one query a row):
    one more operand after the pools, the row's marks
    [1, chunks, 1, R*Bs] of 0 / 1, chunk by chunk as the score columns
    lie: a position not marked is masked like one past the query (its
    block is copied all the same).
    """
    hbm = (k_hbm,)
    if not value_dim:
        hbm, refs = hbm + refs[:1], refs[1:]
    if select:
        sel_ref, refs = refs[0], refs[1:]
    if quant:
        ks_ref, vs_ref = refs[:2]
        refs = refs[2:]
    out_ref, *refs = refs
    bufs, refs = refs[:len(hbm)], refs[len(hbm):]
    sems, s_ref, m_ref, l_ref, acc_ref, pv_ref, state_ref = refs
    pools = tuple(zip(hbm, bufs))       # (K, V), or the latents alone
    k_buf, v_buf = bufs[0], bufs[-1]    # the latents' values: their keys
    Dv = value_dim or q_ref.shape[-1]
    b = pl.program_id(0)
    B, MB = tabs_ref.shape
    rows = T * groups
    Bs, D = block_size, q_ref.shape[-1]
    cols = R * Bs
    layer = layer_ref[0]
    cdt = q_ref.dtype if quant else k_buf.dtype     # the dots' operands

    def live_range(row):
        """(first live block, last, first chunk, chunks) of a row; no
        chunk where it is parked or its window lies past the kv
        bucket."""
        start = starts_ref[row]
        hi = jnp.minimum(jax.lax.div(start + (T - 1), Bs), nb - 1)
        lo = (jax.lax.div(jnp.maximum(start - (window - 1), 0), Bs)
              if window else 0)
        g0 = jax.lax.div(lo, R)
        nch = jnp.where((start >= MB * Bs) | (lo > hi), 0,
                        jax.lax.div(hi, R) - g0 + 1)
        return lo, hi, g0, nch

    def copies(row, lo, hi, g, slot, wait=False):
        """Start (or wait for) the copy of every live block of the
        row's chunk g, from both pools into ``slot``."""
        for i in range(R):
            j = g * R + i

            @pl.when((j >= lo) & (j <= hi))
            def _():
                blk = tabs_ref[row, j]
                for o, (hbm, buf) in enumerate(pools):
                    cp = pltpu.make_async_copy(
                        hbm.at[layer, blk], buf.at[slot, i],
                        sems.at[slot, o])
                    cp.wait() if wait else cp.start()

    @pl.when(b == 0)
    def _first_row():
        state_ref[0] = 0
        state_ref[1] = 0
        # a dead block's columns are masked to probability 0, which
        # must not meet whatever the V slots (the latent pool's one
        # set of slots) held before the call
        v_buf[...] = jnp.zeros_like(v_buf)

    lo, hi, g0, nch = live_range(b)
    nxt = jnp.minimum(b + 1, B - 1)
    nlo, nhi, ng0, nnch = live_range(nxt)
    nnch = jnp.where(b + 1 < B, nnch, 0)
    slot0 = state_ref[0]

    @pl.when((nch > 0) & (state_ref[1] == 0))
    def _start_first():
        copies(b, lo, hi, g0, slot0)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # row r (within a head) queries position start + r // G
    row_pos = starts_ref[b] + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (heads_kv * rows, 1), 0),
        rows) // groups
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)

    def chunk(c, carry):
        g = g0 + c
        slot = jax.lax.rem(slot0 + c, 2)

        @pl.when(c + 1 < nch)
        def _next_chunk():
            copies(b, lo, hi, g + 1, 1 - slot)

        @pl.when((c + 1 == nch) & (nnch > 0))
        def _next_row():
            copies(nxt, nlo, nhi, ng0, 1 - slot)

        copies(b, lo, hi, g, slot, wait=True)

        for h in range(heads_kv):
            k = k_buf[slot, :, h].reshape(cols, D).astype(cdt)
            s = jax.lax.dot_general(
                q_ref[0, h].astype(cdt), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [rows, cols]
            if quant:
                s = s * ks_ref[0, g, h:h + 1, :]
            s_ref[h * rows:(h + 1) * rows, :] = s
        s = s_ref[...] * scale                       # [Hkv*rows, cols]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = g * cols + col
        live = (k_pos <= row_pos) & (k_pos < (hi + 1) * Bs)
        if window:
            live = live & (k_pos > row_pos - window)
        if select:
            live = live & (sel_ref[0, g] > 0.5)
        s = jnp.where(live, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1,
                                                 keepdims=True)
        s_ref[...] = p
        for h in range(heads_kv):
            p_h = s_ref[h * rows:(h + 1) * rows, :]
            if quant:
                p_h = p_h * vs_ref[0, g, h:h + 1, :]
            v = v_buf[slot, :, h].reshape(cols, D)
            if value_dim:
                v = v[:, :value_dim]
            v = v.astype(cdt)
            pv_ref[h * rows:(h + 1) * rows, :] = jax.lax.dot_general(
                p_h.astype(cdt), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [rows, D]
        acc_ref[...] = acc_ref[...] * corr + pv_ref[...]
        return carry

    jax.lax.fori_loop(0, nch, chunk, 0)

    @pl.when(nch > 0)
    def _hand_over():
        state_ref[0] = jax.lax.rem(slot0 + nch, 2)
        state_ref[1] = (nnch > 0).astype(jnp.int32)

    # a parked row has l == 0 and acc == 0: finite zeros
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    out_ref[0] = out.reshape(heads_kv, rows, Dv).astype(out_ref.dtype)


def _chunked_scales(scales, layer, tables, ngrp: int, R: int):
    """The rows' per-token scales, gathered through the tables:
    [L, N, Hkv, Bs] -> [B, ngrp, Hkv, R*Bs], chunk g holding blocks
    g*R .. g*R + R - 1 of each row side by side as the kernel's score
    columns lie."""
    B, MB = tables.shape
    _, _, Hkv, Bs = scales.shape
    blocks = tables[:, jnp.minimum(jnp.arange(ngrp * R), MB - 1)]
    got = scales[layer[0], blocks]               # [B, ngrp*R, Hkv, Bs]
    got = got.reshape(B, ngrp, R, Hkv, Bs).transpose(0, 1, 3, 2, 4)
    return got.reshape(B, ngrp, Hkv, R * Bs)


@functools.partial(jax.jit, static_argnames=("nb", "interpret",
                                             "window", "scale",
                                             "softcap", "value_dim"))
def paged_decode_attention(q, k_pool, v_pool, tables, starts, *,
                           nb: int, interpret: bool = False,
                           k_scales=None, v_scales=None,
                           window: int = 0,
                           scale: float = None, softcap: float = 0.0,
                           layer=None, value_dim: int = 0, select=None):
    """paged_attention specialized for short query windows (T <=
    DECODE_T_MAX): same contract, same result, work in proportion to
    the rows' live tokens.

    q [B, T, H, D]; k/v pool [N, Hkv, Bs, D], or with ``layer`` the
    whole pool [L, N, Hkv, Bs, D]; tables [B, MB] int32; starts [B].
    See paged_attention for semantics, but a row parked at
    start >= MB*Bs returns zeros. k_scales/v_scales
    [(L,) N, Hkv, Bs] fp32 activate the int8-pool mode (panels stream
    as int8, half the KV bytes of the bf16 pool; the scales multiply
    scores and probabilities). The latent pool: k_pool
    [(L,) N, 1, Bs, W], v_pool None, value_dim its value columns, q the
    absorbed queries [B, T, H, W] -> [B, T, H, value_dim]; each live
    block is copied once. select [B, nb*Bs] of 0 / 1 (the latent pool,
    T = 1): the positions each row's query attends, of those at or
    before it (models/kv.attend_selected): every live block is read,
    the marks mask the scores.
    """
    B, T, H, D = q.shape
    layer, k_pool, v_pool, k_scales, v_scales = _whole_pool(
        layer, k_pool, v_pool, k_scales, v_scales)
    Hkv, Bs = k_pool.shape[2], k_pool.shape[3]
    G = H // Hkv
    assert select is None or (value_dim and T == 1), \
        "select: the latent pool, one query a row"
    if scale is None:
        scale = D ** -0.5
    quant = k_scales is not None
    R = decode_blocks_per_step(nb, Hkv, Bs, D, k_pool.dtype.itemsize,
                               latent=bool(value_dim))
    Dv = value_dim or D
    ngrp = -(-nb // R)
    rows = T * G
    tables = jnp.asarray(tables, jnp.int32)

    # [B, T, Hkv, G, D] -> [B, Hkv, T*G, D]: rows ordered t*G + g per
    # head, matching the kernel's row_pos formula
    qh = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(B, Hkv, rows, D)

    def row_index(b, tabs, sts, lyr):
        return (b, 0, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, T=T, heads_kv=Hkv, groups=G,
        block_size=Bs, nb=nb, R=R, scale=scale, quant=quant,
        window=window, softcap=softcap, value_dim=value_dim,
        select=select is not None)
    pools = [k_pool] if value_dim else [k_pool, v_pool]
    in_specs = [pl.BlockSpec((1, Hkv, rows, D), row_index)] + [
        pl.BlockSpec(memory_space=pltpu.HBM) for _ in pools]
    operands = [qh, *pools]
    if select is not None:
        # the row's marks chunk by chunk (float32: a [1, R*Bs] row of
        # it is a whole sublane tile's lanes, as the scales' are)
        marks = jnp.pad(select.astype(jnp.float32),
                        ((0, 0), (0, ngrp * R * Bs - nb * Bs)))
        in_specs.append(pl.BlockSpec((1, ngrp, 1, R * Bs), row_index))
        operands.append(marks.reshape(B, ngrp, 1, R * Bs))
    if quant:
        in_specs += [pl.BlockSpec((1, ngrp, Hkv, R * Bs), row_index)] * 2
        operands += [_chunked_scales(s, layer, tables, ngrp, R)
                     for s in (k_scales, v_scales)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hkv, rows, Dv), row_index),
            scratch_shapes=[
                *(pltpu.VMEM((2, R, Hkv, Bs, D), p.dtype) for p in pools),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((Hkv * rows, R * Bs), jnp.float32),
                pltpu.VMEM((Hkv * rows, 1), jnp.float32),
                pltpu.VMEM((Hkv * rows, 1), jnp.float32),
                pltpu.VMEM((Hkv * rows, Dv), jnp.float32),
                pltpu.VMEM((Hkv * rows, Dv), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a row hands the next its first chunk, already in flight
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tables, jnp.asarray(starts, jnp.int32), layer, *operands)

    # [B, Hkv, T*G, Dv] -> [B, T, H, Dv]
    out = out.reshape(B, Hkv, T, G, Dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, H, Dv)


# ---------------------------------------------------------------------
# the pool's WRITE for short windows: a row's new tokens land as ROWS.
#
# models/kv.append_chunk lands a chunk by gathering the blocks it
# touches, merging and scattering them back whole: the right grain for a
# prefill chunk, which fills most of the blocks it rewrites. A decode
# step's ONE new token a row paid the same three or four XLA operations
# a pool and layer, each with its launch and its gap, and moved a whole
# [Hkv, Bs, D] block in and out for one [Hkv, D] row (PERF.md, PR 58:
# 67 us a layer application at 16 rows x 16 heads, 12.8 of a 44 ms step
# over 192 layer applications).
#
# ``append_rows`` is ONE kernel call a layer for every pool of it (K and
# V, or the latents): no grid, the pools stay in HBM and come back as
# the same buffers (``input_output_aliases``), the new rows come in
# through VMEM. A window of T <= DECODE_T_MAX positions touches at most
# two SLABS a row: a slab is the S = 8 rows of a block, all kv heads
# ([Hkv, 8, D]), that one tile of the pool's layout IN HBM holds (a
# tile there is 8 rows by 128 lanes for float32 and for bfloat16 alike,
# T(8,128)(2,1): two rows a 32-bit word, four words deep), the least a
# copy into HBM can address: the chip's compiler refuses a slice of one bfloat16 row
# (tests/test_chip_compile.py compiles the slab for a described v5e;
# a float32 pool's bare row it accepts: not taken, no cell has one).
# The first build took the 16 rows a bfloat16 tile holds in VMEM and
# moved 4 MB a call at Ouro's shapes, 7.9 us, all of it the copies
# (PERF.md, PR 58). The kernel
#   - reckons every (row, slab)'s block and offset from the tables and
#     starts in SMEM, and whether any token lands there (valid, at a
#     position in [0, MB*Bs)), and starts the copy of every such slab
#     into VMEM, all before it waits for any;
#   - waits for a slab, stores the window's rows into it under their
#     mask (float32 and back, which is exact: the v5e selects no
#     bfloat16) and starts the copy back;
#   - waits for the copies back.
# The three passes are ``lax.fori_loop`` over the slabs and the only
# Python loops run over the window's T positions and the call's pools:
# the kernel's text does not grow with rows, heads or blocks, because a
# Pallas kernel is lowered once a call site of every executable a start
# builds (PERF.md section 5, "WHAT setup_s IS MADE OF").
#
# A slab with no live token is neither read nor written: parked rows,
# padding and window tails past capacity cost two scalar compares, and
# trash block 0 is never touched. Two live rows never write one slab (a
# sequence writes only blocks it owns alone), and the aliased pool
# orders this call before the read that follows it.
# ---------------------------------------------------------------------

KV_APPEND_ROWS = "rows"
KV_APPEND_BLOCKS = "blocks"
# S, the rows of a block one slab holds: a tile of the pool in HBM (the
# account above). EngineConfig takes no block that 8 does not divide
APPEND_SLAB_ROWS = 8
# what one call of ``append_rows`` may hold at once, every row's slabs
# in flight: DMA semaphores (the core has 512 words of them, "sflag":
# a call that asks for more does not compile, tests/test_chip_compile.py)
# and bytes of VMEM for the slabs and the new rows, beside
# _PANEL_WORK_BYTES under VMEM_LIMIT_BYTES
_APPEND_SEMAPHORES = 256
_APPEND_WORK_BYTES = 32 * 1024 * 1024


def _append_slabs(T: int) -> int:
    """K, the slabs a row's window of T <= 8 positions can touch."""
    return 1 if T == 1 else 2


def _append_holds(pools, rows: int, T: int) -> bool:
    """Does one call's scratch fit the core (the two bounds above)?
    A row holds K slabs [Hkv, 8, D] and its news [Hkv, T, D] of every
    pool, each padded to the sublanes of a VMEM tile (8 float32, 16
    bfloat16), and K semaphores a pool: 393 KB and 4 at 16 heads of 128
    and a speculative window, 64 rows; one position a row, 128 rows."""
    K = _append_slabs(T)
    item = pools[0].dtype.itemsize
    tile = 32 // item

    def padded(n):
        return -(-n // tile) * tile

    held = sum(rows * p.shape[2] * p.shape[4] * item
               * (K * padded(APPEND_SLAB_ROWS) + padded(T)) for p in pools)
    return (len(pools) * rows * K <= _APPEND_SEMAPHORES
            and held <= _APPEND_WORK_BYTES)


def kv_append_path(pools, rows: int, T: int, mesh) -> str:
    """How a forward of ``rows`` rows of T positions lands its new K/V
    (or latents) in ``pools``, a layer's arrays as models/kv.append is
    handed them ([L, N, Hkv, Bs, D] each, the int8 pool's scales
    [L, N, Hkv, Bs] behind them; arrays or their shapes), decided here
    by what the call can observe, as ``attention_path`` is. The ONE
    rule: models/kv.append asks it at trace time and engine/runner
    ``_compile`` asks it of the same four things for GET /debug/perf
    ``device.kv_appends``.

    ``rows`` (``append_rows``) for a decode or speculative window
    (T <= DECODE_T_MAX) where the kernels run (``flash_enabled``), on
    one device, over a pool with no scales, of a batch whose slabs one
    call can hold in flight (``_append_holds``); ``blocks``
    (models/kv.append_chunk's whole-block rewrite) for everything
    else: a prefill chunk mostly fills the blocks it rewrites; the int8
    pool's scale rows [.., Bs] are no tile a copy can address; under a
    mesh the pool is sharded and XLA places the rewrite (a kernel over
    it outside shard_map would gather it); a batch past the bound
    would not compile (no cell has one: 8 to 16 rows; an operator's
    256 keeps the executable the parent built); with the kernels off
    (the CPU) there is no kernel to call. Where this says ``rows`` the
    read that follows takes the decode kernel (``attention_path``: the
    same gate, and at T <= 8 its working set holds at any head geometry
    a cell has); the pool ends the same bytes on either."""
    payload = [p for p in pools if len(p.shape) == 5]
    if (T <= DECODE_T_MAX and mesh is None and len(payload) == len(pools)
            and flash_enabled() and _append_holds(payload, rows, T)):
        return KV_APPEND_ROWS
    return KV_APPEND_BLOCKS


def _append_rows_kernel(tabs_ref, starts_ref, valid_ref, layer_ref, *refs,
                        T: int, K: int, block_size: int, npools: int):
    """Every row's window into every pool of one layer.

    tabs_ref   (SMEM) [B, MB]     block tables
    starts_ref (SMEM) [B]         position of each row's first new token
    valid_ref  (SMEM) [B, T]      1 where the token is real
    layer_ref  (SMEM) [1]         the pool's layer
    refs    npools of new [B, Hkv, T, D] (VMEM), the pools (HBM; the
            aliased inputs, not touched), the pools again as outputs
            (HBM) [L, N, Hkv, Bs, D]; scratch: npools of slabs
            [B*K, Hkv, S, D], DMA semaphores [npools, B*K] and (SMEM)
            [3, B*K]: whether slab i is live, its block and its first
            row in the block.

    Slab i = row i // K's k-th (i % K) from the one its first position
    lies in; K = 1 for one position a row, else 2 (T <= S).
    """
    news = refs[:npools]
    pools = refs[2 * npools:3 * npools]
    slabs = refs[3 * npools:4 * npools]
    sems, plan = refs[4 * npools:]
    B, MB = tabs_ref.shape
    Bs, S = block_size, APPEND_SLAB_ROWS
    cap = MB * Bs
    layer = layer_ref[0]

    def window(i):
        """(row, its start, the slab's first position) of slab i."""
        b = jax.lax.div(i, K)
        start = starts_ref[b]
        first = (jax.lax.div(jnp.maximum(start, 0), S)
                 + jax.lax.rem(i, K)) * S
        return b, start, first

    def lands(b, start, first, t):
        """Is token t of row b real and inside the slab at ``first``?"""
        p = start + t
        return ((valid_ref[b, t] != 0) & (p >= first) & (p < first + S)
                & (p < cap))

    def copy(i, o, back: bool):
        blk = plan[1, i]
        at = pools[o].at[layer, blk].at[
            :, pl.ds(pl.multiple_of(plan[2, i], S), S), :]
        here = slabs[o].at[i]
        return pltpu.make_async_copy(*((here, at) if back else (at, here)),
                                     sems.at[o, i])

    def fetch(i, carry):
        b, start, first = window(i)
        live = lands(b, start, first, 0)
        for t in range(1, T):
            live = live | lands(b, start, first, t)
        plan[0, i] = live.astype(jnp.int32)
        plan[1, i] = tabs_ref[b, jnp.minimum(jax.lax.div(first, Bs),
                                             MB - 1)]
        plan[2, i] = jax.lax.rem(first, Bs)

        @pl.when(live)
        def _():
            for o in range(npools):
                copy(i, o, back=False).start()
        return carry

    def merge(i, carry):
        @pl.when(plan[0, i] != 0)
        def _():
            b, start, first = window(i)
            # the slab row each token takes: none where it does not land
            rows = [jnp.where(lands(b, start, first, t),
                              start + t - first, -1) for t in range(T)]
            for o in range(npools):
                copy(i, o, back=False).wait()
                slab = slabs[o][i].astype(jnp.float32)      # [Hkv, S, D]
                new = news[o][b].astype(jnp.float32)        # [Hkv, T, D]
                row = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 1)
                for t in range(T):
                    slab = jnp.where(row == rows[t], new[:, t:t + 1, :],
                                     slab)
                slabs[o][i] = slab.astype(slabs[o].dtype)
                copy(i, o, back=True).start()
        return carry

    def settle(i, carry):
        @pl.when(plan[0, i] != 0)
        def _():
            for o in range(npools):
                copy(i, o, back=True).wait()
        return carry

    for step in (fetch, merge, settle):
        jax.lax.fori_loop(0, B * K, step, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def append_rows(pools, news, tables, starts, valid, layer, *,
                interpret: bool = False):
    """The new tokens of a short window as rows of layer ``layer`` of
    the whole pools, in place (the account above).

    pools: the layer's arrays [L, N, Hkv, Bs, D] — (K, V), or the latent
    pool alone (Hkv 1, D its padded width); widths and heads may differ
    between them, dtype and block size may not. news: as many [B, T,
    Hkv, D], row b's tokens at positions starts[b] .. starts[b] + T - 1,
    T <= DECODE_T_MAX, Bs a multiple of 8, and no more rows than
    ``kv_append_path`` allows a call (the core's DMA semaphores). tables [B, MB] int32; valid [B, T] bool
    or None (every token real); layer an int32 scalar (traced).

    -> the pools, the same buffers. Every block a table references
    holds on that layer what models/kv.write_chunk would leave: tokens
    that are invalid, negative or at MB*Bs and beyond are written
    nowhere at all.

    (Jitted, as the reading kernels are, for the trace cache: a start
    builds the decode window at nine kv buckets and every one calls
    this at the same shapes, in a model with a layer plan at several
    sites. The kernel is traced once a process and lowered once a
    build, not once a call site.)"""
    B, T = news[0].shape[:2]
    Bs, dtype = pools[0].shape[3], pools[0].dtype
    S, K, n = APPEND_SLAB_ROWS, _append_slabs(T), len(pools)
    assert T <= DECODE_T_MAX and Bs % S == 0, (T, Bs)
    assert all(p.dtype == dtype and p.shape[3] == Bs for p in pools)
    valid = (jnp.ones((B, T), jnp.int32) if valid is None
             else valid.astype(jnp.int32))
    # [B, T, Hkv, D] -> [B, Hkv, T, D]: a token's row as the block's
    # head-major panels take it
    news = [x.astype(dtype).transpose(0, 2, 1, 3) for x in news]
    kernel = functools.partial(_append_rows_kernel, T=T, K=K,
                               block_size=Bs, npools=n)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * n
            + [hbm] * n,
            out_specs=[hbm] * n,
            scratch_shapes=[
                *(pltpu.VMEM((B * K, p.shape[2], S, p.shape[4]), dtype)
                  for p in pools),
                pltpu.SemaphoreType.DMA((n, B * K)),
                pltpu.SMEM((3, B * K), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # (operands count from the scalars: 4 of them, then the news)
        input_output_aliases={4 + n + o: o for o in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="kv_append_rows",
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(starts, jnp.int32),
      valid, jnp.asarray(layer, jnp.int32).reshape(1), *news, *pools)
    return tuple(out)


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
    (mesh_tp_only). Short windows (decode/spec) take the decode
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


def expanded_cheaper(T: int, head_dim: int, value_dim: int,
                     head_dims) -> bool:
    """Does a forward of T query positions a row over the latent pool
    (vectors ``head_dim`` wide as cached, ``value_dim`` of them the
    value) multiply less with the heads' keys and values made from the
    latents (``head_dims``: a head's nope, rope and value widths) than
    absorbed? Operations a key and head: absorbed 2 head_dim + 2
    value_dim a query; expanded 2 (nope + rope) + 2 v a query and
    making the key and value, 2 value_dim (nope + v), once. At GLM's
    widths (640 / 512; 192, 64, 256) the cut falls at 358 queries."""
    nope, rope, v = head_dims
    return (T * (2 * head_dim + 2 * value_dim)
            > T * (2 * (nope + rope) + 2 * v)
            + 2 * value_dim * (nope + v))


def attention_path(T: int, groups: int, head_dim: int, block_size: int,
                   mesh=None, value_dim: int = 0,
                   selects: bool = False, head_dims=None) -> str:
    """Which cached-attention implementation a forward over T query
    positions per row takes, for ``groups`` query heads per kv head.
    Decided here, by shape, BEFORE anything compiles — a kernel the
    compiler then refuses is an error, not a reason to take another
    path (engine/runner.py records this per executable; GET /debug/perf
    shows it).

    ``pallas_paged_decode``: short windows (decode / speculative
    verify) on the decode kernel — one grid step a row, all kv heads
    and several live pool blocks a chunk, copied in by the kernel.
    ``pallas_paged``: prefill chunks on the general paged kernel, cut
    into q blocks against panels of pool blocks (``prefill_tiles``)
    where the whole chunk's working set misses VMEM (2048 positions x
    8 query heads a kv head x 256 against 512 keys are 109 MB; a q
    block of 512 is 27): what must fit is the smallest q block against
    one pool block, so wide-GQA long chunks stay on the kernel at
    every kv bucket and pay only K and V streamed once a q block.
    ``*_sharded``: either, shard-local per head under a tp-only mesh.
    ``*_latent``: either, over the latent pool (value_dim > 0: every
    query head on the one cached vector a token, head_dim wide, of
    which value_dim columns are the value; no mesh). Twenty heads of
    576 make a 256-token chunk's q panel miss VMEM whole, so what must
    fit is the smallest q block the prefill kernel cuts it into.
    ``*_latent_sparse``: the same two where the layer selects what it
    attends (``selects``: models/kv.selects, the kv bucket holds more
    positions than the indexer keeps): the decode kernel over the
    selected positions' latents alone, the prefill kernel under a
    mask of them (models/kv.attend_selected).
    ``pallas_paged_latent_expanded`` (``_sparse``): a prefill chunk
    over the latent pool long enough that making each head's keys and
    values from the cached latents costs less than attending absorbed
    (``expanded_cheaper``; ``head_dims``, a head's nope, rope and value
    widths, from models/llama._mla_attention and engine/runner.py): the
    prefill kernel's expanded case, same pool, clamp and marks.
    ``jnp_gather``: the kernel is off (PSTPU_FLASH / not a TPU), even
    the smallest q block's working set misses VMEM (paged_viable), or
    the mesh shards the pool's block axis."""
    if value_dim:
        if not (flash_enabled() and mesh is None and paged_viable(
                min(T, _MIN_BLOCK_Q), groups, head_dim, block_size,
                value_dim)):
            return JNP_GATHER
        if T <= DECODE_T_MAX:
            kernel = "pallas_paged_decode_latent"
        elif head_dims and expanded_cheaper(T, head_dim, value_dim,
                                            head_dims):
            kernel = "pallas_paged_latent_expanded"
        else:
            kernel = "pallas_paged_latent"
        return kernel + ("_sparse" if selects else "")
    if not (flash_enabled()
            and paged_viable(min(T, _MIN_BLOCK_Q), groups, head_dim,
                             block_size)
            and (mesh is None or mesh_tp_only(mesh))):
        return JNP_GATHER
    kernel = "pallas_paged_decode" if T <= DECODE_T_MAX else "pallas_paged"
    return kernel + ("_sharded" if mesh is not None else "")
