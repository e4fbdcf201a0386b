"""Pallas flash attention for TPU: blockwise online-softmax GQA.

Replaces the jnp cache attention (ops/attention.py) on the *prefill* hot
path, where materializing [B, Hkv, G, T, S] fp32 scores costs O(T*S) HBM
traffic per layer: this kernel streams K/V blocks through VMEM, keeps
online (max, sum, acc) statistics, and never materializes the score
matrix. Decode (T == 1) stays on the jnp path — its score matrix is a
[B, Hkv, G, 1, kv] sliver that XLA already fuses well, and the fused
multi-step decode executable (engine/runner.py) cannot host a per-step
pallas_call more cheaply than the einsum it replaces.

Kernel layout (one q block per grid step, K/V streamed in an inner loop):
- grid (B, Hkv, Tq_blocks); per step the q block [BQ, G, D] and this
  kv-head's full K/V [S, D] live in VMEM. Queries are sliced through
  BlockSpec index maps on the native [B, T, H, D] layout; K/V are
  relayouted to head-major [B, Hkv, S, D] outside the kernel so a
  per-head panel's minor dims are (S, D) — the shape Mosaic's
  last-two-dims tiling rule can block. flash_viable() bounds S*D so
  both K and V fit the ~16 MB VMEM budget; larger caches fall back to
  the jnp path.
- inner lax.fori_loop walks K/V in BK-sized blocks with the classic
  flash update; the loop's upper bound is data-dependent on the block's
  max query position, so fully-masked (future) K blocks are skipped —
  causal work scales with the live prefix, not S. BK is shrunk (halved)
  until it divides S: every block read is in bounds, no clamped-slice
  mislabeling on ragged tails.
- GQA: the q block keeps its [BQ, G, D] shape and flattens to rows
  t*G + g inside VMEM, so a row's position is row // G and K/V are
  never replicated to H query heads.

Sharded serving note: the kernel is only used on unsharded (single-chip)
executables — pallas_call has no GSPMD partitioning rule, so tp/dp
meshes keep the jnp einsum path, which XLA partitions with the usual
collectives (engine/runner.py gates this via models/llama.py forward's
``use_flash``).

The reference repo ships no kernels (attention lives in the external
vLLM engine, SURVEY.md §2.9); this is TPU-first work. Numerics are
pinned against the dense jnp path in tests/test_pallas_attention.py,
which runs the same kernel in interpret mode on CPU.
"""

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# VMEM budget for the per-grid-step K + V panels ([S, D] each, bf16):
# stay well under the ~16 MB/core so q/acc/scratch fit too.
_VMEM_PANEL_BYTES = 4 * 1024 * 1024

# Per-kernel scoped-VMEM budget (shared by pallas_paged.py): XLA may
# place a chunk-sized kernel OUTPUT on the scoped-VMEM stack (a batch-8
# 512-chunk bf16 output is ~17 MB), and the default 16 MiB budget then
# fails the compile even though the kernel's own working set is small.
# v5e/v5p cores carry 128 MiB VMEM — raise the budget so chunk-sized
# outputs may live on-chip; outputs too big for it simply land in HBM.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024

# runtime gate: PSTPU_FLASH=1/0 forces; "auto" (default) enables the
# compiled kernel on TPU and leaves CPU/other backends on the jnp path
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


def flash_viable(S: int, D: int, itemsize: int = 2) -> bool:
    """Can this kv-length/head-dim keep a K and a V panel in VMEM?"""
    return S * D * itemsize <= _VMEM_PANEL_BYTES


def needs_interpret() -> bool:
    """Interpret everywhere but real TPU (kernel targets TPU tiling)."""
    return jax.default_backend() != "tpu"


def mode() -> str:
    """"compiled" | "interpret" | "off": how the Pallas attention
    kernels run in this process, for logs and GET /debug/perf."""
    if not flash_enabled():
        return "off"
    return "interpret" if needs_interpret() else "compiled"


def _flash_kernel(starts_ref, q_ref, k_ref, v_ref, out_ref, *,
                  block_q: int, block_k: int, groups: int, scale: float):
    """One (batch, kv-head, q-block) grid step.

    q_ref   [1, BQ, 1, G, D]  queries for this kv-head's G query heads
    k_ref   [1, 1, S, D]      this kv-head's full key cache (head-major)
    v_ref   [1, 1, S, D]
    starts_ref (SMEM) [B]     per-batch-row position of q row t=0
    out_ref [1, BQ, 1, G, D]
    """
    b = pl.program_id(0)
    qi = pl.program_id(2)
    S = k_ref.shape[2]
    rows = block_q * groups
    D = q_ref.shape[-1]

    start = starts_ref[b]
    # absolute position of each q row (rows ordered t*G + g): row // G
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // groups
    q_pos = start + qi * block_q + row_ids                    # [rows, 1]

    q = q_ref[0].reshape(rows, D).astype(jnp.float32) * scale

    # causal bound: K blocks fully beyond this q block's last position
    # contribute nothing — skip them (dynamic fori_loop upper bound).
    # block_k divides S (wrapper guarantees), so every read is in bounds.
    max_pos = start + qi * block_q + (block_q - 1)
    n_blocks = jnp.minimum(
        jax.lax.div(max_pos, block_k) + 1, S // block_k)

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(
            jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [rows, BK]
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                                 # [rows, BK]
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * correction + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [rows, D]
        return m_new, l_new, acc_new

    m0 = jnp.full((rows, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    acc0 = jnp.zeros((rows, D), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    # fully-masked (padding) rows have l == 0; keep them finite
    out = acc / jnp.maximum(l, 1e-30)
    out_ref[0] = out.reshape(block_q, 1, groups, D).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def flash_attention_with_cache(q, k_cache, v_cache, starts, *,
                               block_q: int = 128, block_k: int = 512,
                               interpret: bool = False):
    """Drop-in for ops/attention.attention_with_cache on contiguous
    positions. q [B,T,H,D]; k/v [B,S,Hkv,D]; starts [B] = absolute
    position of q[:, 0]. Query token at position p attends cache slots
    s <= p (the cache already contains the chunk's own K/V). Rows whose
    position exceeds S-1 are padding and return garbage, as in the jnp
    path.
    """
    B, T, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = D ** -0.5

    block_q = min(block_q, T)
    # BK must divide S so the last block read stays in bounds (a clamped
    # dynamic slice would silently re-read earlier keys under later
    # position labels). kv buckets are 512-multiples or max_model_len;
    # halving terminates quickly for any S.
    block_k = min(block_k, S)
    while S % block_k:
        block_k //= 2
    # pad T to a block multiple; padded rows mask to zero and are sliced
    pad_t = (-T) % block_q
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, pad_t), (0, 0), (0, 0)))
    Tp = T + pad_t

    # view q as [B, Tp, Hkv, G, D]: its BlockSpec carves per-(b, kv-head)
    # panels straight out of the native layout (full G and D in the
    # minor dims keeps Mosaic's last-two-dims tiling rule satisfied).
    q5 = q.reshape(B, Tp, Hkv, G, D)
    # K/V go in head-major [B, Hkv, S, D]: a per-head panel then has
    # (S, D) as its last two dims (S a multiple of 8, D native), which
    # Mosaic can tile — carving 1 of Hkv out of [B, S, Hkv, D] cannot
    # be. The swap is a real full-cache copy (the scatter output is
    # also carried as cache state, so it cannot fuse away): ~2*B*Hkv*
    # S*D bf16 of extra HBM traffic per layer per chunk, well under 1%
    # of the chunk's FFN matmul time at flash-viable sizes.
    k_hm = jnp.swapaxes(k_cache, 1, 2)
    v_hm = jnp.swapaxes(v_cache, 1, 2)

    grid = (B, Hkv, Tp // block_q)
    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, groups=G, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, 1, G, D),
                         lambda b, h, i: (b, i, h, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, G, D),
                               lambda b, h, i: (b, i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Tp, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(starts, jnp.int32), q5, k_hm, v_hm)

    return out.reshape(B, Tp, H, D)[:, :T]
