"""The selection of learned sparse attention (DeepSeek-V3.2's indexer,
as GLM-5's ``glm_moe_dsa`` follows it): which cached positions a query
attends.

A query at position t scores every position s <= t,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),

with ``index_n_heads`` index queries qI[t, j] of ``index_head_dim``
values, ONE index key kI[s] a position (cached in the index pool,
models/kv.py) and the head weights w[t, j] (the caller folds the two
scales, index_n_heads^-0.5 and index_head_dim^-0.5, into w), and keeps
the ``index_topk`` positions of largest I, ties to the lower position;
all of them while t < index_topk. Two steps, each one function here:

``index_scores``  I for a chunk's queries over the row's gathered index
    keys: one Pallas call (``dsa_index_scores``), a [T x heads, width]
    by [width, S] product a tile whose ReLU, weighting and sum over the
    heads happen in VMEM, so the [T, heads, S] products never exist.
``select``        the exact top-k as a mask, with no sort: the k-th
    largest score by bisection on the scores' bits (32 counts), then
    the cut among its ties by bisection on the position (one count a
    bit of S), one Pallas call (``dsa_select``) over blocks of rows
    with the scores in VMEM. A ``lax.top_k`` of [rows, 16k] is a sort
    of the whole row (PERF.md, PR 39: 138 us for a 256 x 60 one).

Off the kernels (the CPU, where pallas_paged.flash_enabled says no) the
same two in jax.numpy, ``select`` by a stable sort: what the kernels
are tested against (tests/test_dsa.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops import pallas_paged

_INT_MIN = -2 ** 31

# a tool's or a test's tap on the selection (tools/dsa_chip_check.py):
# a function (layer, positions [B, T], mask [B, T, S]) that
# models/kv.attend_selected hands every selection it makes, as a host
# callback of executables traced while it is set. The engine never
# sets it.
tap = None


def _divisor(n: int, options) -> int:
    """The first of ``options`` that divides n, else n itself."""
    return next((o for o in options if n % o == 0), n)


def _index_scores_kernel(q_ref, w_ref, k_ref, out_ref, *, heads: int):
    """q_ref [1, TB*heads, D], w_ref [1, TB*heads, 1] fp32, k_ref
    [1, SB, D] -> out_ref [1, TB, SB] fp32."""
    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.maximum(s, 0.0) * w_ref[0]                  # [TB*heads, SB]
    tb = out_ref.shape[1]
    out_ref[0] = jnp.sum(s.reshape(tb, heads, s.shape[-1]), axis=1)


def index_scores(q: jnp.ndarray, w: jnp.ndarray,
                 keys: jnp.ndarray) -> jnp.ndarray:
    """q [B, T, heads, D], w [B, T, heads] fp32 (scales folded in),
    keys [B, S, D] -> I [B, T, S] fp32: every query against every key
    of its row, whatever their positions (``select`` knows which are
    live)."""
    B, T, heads, D = q.shape
    S = keys.shape[1]
    if not pallas_paged.flash_enabled():
        s = jnp.einsum("bthd,bsd->bths", q, keys.astype(q.dtype),
                       preferred_element_type=jnp.float32)
        return jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=2)
    tb = _divisor(T, (32, 16, 8))
    sb = _divisor(S, (1024, 512, 256, 128))
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, heads=heads),
        grid=(B, T // tb, S // sb),
        in_specs=[
            pl.BlockSpec((1, tb * heads, D), lambda b, t, s: (b, t, 0)),
            pl.BlockSpec((1, tb * heads, 1), lambda b, t, s: (b, t, 0)),
            pl.BlockSpec((1, sb, D), lambda b, t, s: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, tb, sb), lambda b, t, s: (b, t, s)),
        out_shape=jax.ShapeDtypeStruct((B, T, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
        interpret=pallas_paged.needs_interpret(),
        name="dsa_index_scores",
    )(q.reshape(B, T * heads, D), w.reshape(B, T * heads, 1
                                            ).astype(jnp.float32),
      keys.astype(q.dtype))


def _select_kernel(scores_ref, last_ref, out_ref, *, topk: int):
    """scores_ref [RB, S] fp32, last_ref [RB, 1] int32 (a row's last
    live position) -> out_ref [RB, S]: 1 where selected, else 0."""
    S = scores_ref.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    live = pos <= last_ref[...]
    # the scores' bits as integers of the same order: a negative
    # float's magnitude bits flipped; -0.0 first made +0.0
    scores = scores_ref[...]
    bits = pltpu.bitcast(jnp.where(scores == 0.0, 0.0, scores), jnp.int32)
    key = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    key = jnp.where(live, key, _INT_MIN)

    def count(hit):
        return jnp.sum(hit.astype(jnp.float32), axis=1, keepdims=True)

    # the largest thr that at least topk keys reach, bit by bit from
    # the sign down (fewer live than topk: it stays the lowest integer
    # and every live position is above it)
    k = float(topk)
    thr = jnp.where(count(key >= 0) >= k, 0, _INT_MIN)
    for bit in range(30, -1, -1):
        cand = thr | (1 << bit)
        thr = jnp.where(count(key >= cand) >= k, cand, thr)
    above = key > thr
    tie = (key == thr) & live
    need = k - count(above)
    # of the ties the ``need`` lowest positions: the largest cut that
    # at most ``need`` of them lie below
    cut = jnp.zeros_like(thr)
    for bit in range(S.bit_length() - 1, -1, -1):
        cand = cut | (1 << bit)
        cut = jnp.where(count(tie & (pos < cand)) <= need, cand, cut)
    chosen = live & (above | (tie & (pos < cut)))
    out_ref[...] = chosen.astype(out_ref.dtype)


def select(scores: jnp.ndarray, last: jnp.ndarray, topk: int,
           dtype=jnp.bfloat16) -> jnp.ndarray:
    """scores [R, S] fp32, last [R] int32 -> [R, S] in ``dtype``, 1 at
    the ``topk`` positions s <= last[r] of largest score (ties to the
    lower position; every such position where there are fewer), else
    0."""
    R, S = scores.shape
    if not pallas_paged.flash_enabled():
        live = jnp.arange(S)[None, :] <= last[:, None]
        # a stable sort by falling score breaks ties by position
        order = jnp.argsort(jnp.where(live, -scores, jnp.inf), axis=1,
                            stable=True)
        rank = jnp.argsort(order, axis=1)
        return (live & (rank < topk)).astype(dtype)
    # rows a block: whole tiles of ``dtype``, 2 MiB of scores at most
    rb = _divisor(R, [r for r in (64, 32, 16) if r * S * 4 <= 2 ** 21])
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk),
        grid=(R // rb,),
        in_specs=[pl.BlockSpec((rb, S), lambda r: (r, 0)),
                  pl.BlockSpec((rb, 1), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((rb, S), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, S), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
        interpret=pallas_paged.needs_interpret(),
        name="dsa_select",
    )(scores, last.astype(jnp.int32).reshape(R, 1))
