"""Gated DeltaNet: the gated delta rule over a state page.

A Gated DeltaNet layer keeps, a sequence and value head, ONE matrix
``S [Dk (key), Dv (value)]`` in float32, whatever the context. With the
token's log-decay ``g_t <= 0``, write strength ``beta_t``, key ``k_t``
(l2-normalised), value ``v_t`` and query ``q_t`` (l2-normalised, times
``Dk ** -0.5``):

    S = exp(g_t) S;  m = k_t^T S;  d = beta_t (v_t - m)
    S = S + k_t d^T;  o_t = q_t^T S

Two implementations, chosen by shape alone (``gdn_path``), both taking
the state from and leaving it in its page of the state pool
``[layers, pages, Hv, Dk, Dv]`` (models/kv.py), which a step program
carries as it carries the K/V pool:

``gdn_recurrent``  T <= DECODE_T_MAX positions a row (a decode step):
    the rule as written, one pass over the row's page. On the TPU the
    kernel ``gdn_recurrent_step``: a grid step a row copies the page's
    32 matrices in, decays, multiplies, updates and copies them back to
    the SAME page (the pool is aliased to the kernel's result: nothing
    else of it is touched); bound by ``rows x 2 x page bytes`` a layer.
``gdn_chunk``  longer (a prefill chunk): the chunkwise form. The T
    positions are cut into chunks of ``CHUNK`` tokens. Within a chunk,
    with ``G_i`` the log-decays summed from the chunk's first token to
    token i (float32; only differences ``G_i - G_j``, i >= j, are ever
    exponentiated, so nothing overflows), the writes ``d_i`` solve the
    unit lower-triangular system

        (I + L) D = beta V - (beta exp(G) K) S_0,
        L[i, j] = beta_i exp(G_i - G_j) (k_i . k_j),  j < i

    (the WY / UT transform: ``T = (I + L)^-1`` by forward substitution
    in blocks of 16, neighbours merged level by level, ``u = T (beta
    V)``, ``w = T (beta exp(G) K)``, ``D = u - w S_0``), and between
    chunks the state is carried:

        D = u - w S;  o = (q exp(G)) S + (Q K^T . decay) D
        S = exp(G_C) S + (k exp(G_C - G))^T D

    operands in the activations' dtype (as the inputs come; ``w``,
    ``attn = Q K^T . decay``, ``qg``, ``kd`` and D rounded to it once),
    float32 products, ``G``, the decays, L, T and ``u`` float32, the
    products that make T's merges, ``u`` and ``w`` at float32 accuracy,
    the state float32 throughout. On the TPU ONE kernel,
    ``gdn_chunk_scan``, a grid step a (row, value head, up to eight
    PAIRS of chunks): it reads q, k, v where the projections left them
    (``[B, T, heads x D]``, the key head ``h // (Hv // Hk)`` by the
    block's index map) and writes ``o`` the same way, and everything
    between is made in VMEM and never written to HBM. Two chunks share
    each 128 x 128 matrix of the transform, block-diagonal under a
    mask; the diagonal blocks of a step's pairs are substituted at
    once, row i of every block one tile (``_solve_stacked``: the
    vector unit's multiply-adds, the same recurrence in the same order
    as the ``jax.numpy`` loop); the head's matrix stays in VMEM from
    the page's copy-in at the row's first step to its copy-back at the
    last. PERF.md (PR 51) has what each part costs.

A chunk whose first position is 0 (``fresh``) starts from a zero state
inside the kernel: no page is ever cleared by the host. Positions that
are not real advance nothing: the caller hands them ``g = 0`` and
``beta = 0``, and a row that is not real names the trash page.

Where the kernels are off (``pallas_paged.flash_enabled``: the CPU) the
same two forms run in ``jax.numpy`` (the chunkwise one as
``_chunk_prep``, every chunk's operands at once under the scope
``gdn_chunk_prep``, then ``_scan_jnp``); tests/test_gdn.py holds each
to the sequential rule of chipbench/references/qwen3_next.py and, in
interpret mode, the kernels to the ``jax.numpy`` forms.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops import pallas_paged
from production_stack_tpu.ops.pallas_paged import DECODE_T_MAX

# tokens a chunk of the chunkwise form holds: the kernel's own constant.
# 64 is the published kernels' (flash-linear-attention's chunk size); 32
# and 128 were not measured on the chip (PERF.md, PR 42)
CHUNK = 64
# the diagonal blocks the unit lower-triangular inverse solves by
# forward substitution, row by row; above it blocks are merged by
# products
_SOLVE_BLOCK = 16
# tokens whose matrices of the transform the kernel makes at once: two
# chunks, block-diagonal under a mask (the MXU's 128 rows)
_PAIR = 2 * CHUNK
# pairs a grid step of the kernel takes at most: row i of each pair's
# folded diagonal blocks is a sublane of one tile
_GROUP = 8

RECURRENT = "gdn_recurrent"
CHUNKED = "gdn_chunk"


def gdn_path(T: int) -> str:
    """Which implementation a forward of T positions a row runs:
    decided by shape, before anything compiles (a kernel the compiler
    then refuses is an error, not a reason to take the other)."""
    return RECURRENT if T <= DECODE_T_MAX else CHUNKED


def _expand_heads(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    """x [B, T, Hk, D] -> [B, T, heads, D]: value head h reads key head
    h // (heads / Hk)."""
    rep = heads // x.shape[2]
    return x if rep == 1 else jnp.repeat(x, rep, axis=2)


# ---------------------------------------------------------------------
# the recurrent form
# ---------------------------------------------------------------------

def _recurrent_kernel(ids_ref, layer_ref, fresh_ref, qt_ref, kt_ref,
                      bv_ref, beta_ref, dec_ref, s_ref, o_ref, so_ref,
                      *, T: int, heads: int):
    """One row: its page's ``heads`` matrices through T positions.

    qt_ref, kt_ref [1, T, Dk, Hv] float32: a head's query and key are a
    COLUMN (lane h), which broadcasts over the matrix's value columns;
    bv_ref (beta v), beta_ref, dec_ref (exp(g)) [1, T, Hv, Dv] float32:
    a head's are a ROW, which broadcasts over its key rows. s_ref /
    so_ref [1, 1, Hv, Dk, Dv]: the page, in and out (the same bytes)."""
    b = pl.program_id(0)
    keep = 1.0 - fresh_ref[b].astype(jnp.float32)
    for h in range(heads):
        S = s_ref[0, 0, h] * keep                            # [Dk, Dv]
        for t in range(T):
            k = kt_ref[0, t, :, h:h + 1]                     # [Dk, 1]
            q = qt_ref[0, t, :, h:h + 1]
            S = S * dec_ref[0, t, h:h + 1, :]
            m = jnp.sum(k * S, axis=0, keepdims=True)        # [1, Dv]
            d = bv_ref[0, t, h:h + 1, :] - beta_ref[0, t, h:h + 1, :] * m
            S = S + k * d
            o_ref[0, t, h:h + 1, :] = jnp.sum(q * S, axis=0, keepdims=True)
        so_ref[0, 0, h] = S


def _recurrent_jnp(q, k, v, g, beta, state, ids, layer, fresh):
    S = state[layer, ids]                                # [B,Hv,Dk,Dv]
    S = jnp.where(fresh[:, None, None, None], 0.0, S)
    outs = []
    for t in range(q.shape[1]):
        kt, qt = k[:, t], q[:, t]                        # [B,Hv,Dk]
        S = S * jnp.exp(g[:, t])[..., None, None]
        m = jnp.sum(kt[..., None] * S, axis=2)           # [B,Hv,Dv]
        d = beta[:, t][..., None] * (v[:, t] - m)
        S = S + kt[..., None] * d[:, :, None, :]
        outs.append(jnp.sum(qt[..., None] * S, axis=2))
    return jnp.stack(outs, axis=1), state.at[layer, ids].set(S)


def _recurrent(q, k, v, g, beta, state, ids, layer, fresh):
    """q, k, v [B, T, Hv, D] float32, g, beta [B, T, Hv] float32 ->
    (o [B, T, Hv, Dv] float32, the state pool)."""
    if not pallas_paged.flash_enabled():
        return _recurrent_jnp(q, k, v, g, beta, state, ids, layer, fresh)
    B, T, Hv, Dk = q.shape
    Dv = v.shape[-1]
    page = pl.BlockSpec((1, 1, Hv, Dk, Dv),
                        lambda b, ids, lyr, fr: (lyr[0], ids[b], 0, 0, 0))
    col = pl.BlockSpec((1, T, Dk, Hv), lambda b, ids, lyr, fr: (b, 0, 0, 0))
    row = pl.BlockSpec((1, T, Hv, Dv), lambda b, ids, lyr, fr: (b, 0, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_recurrent_kernel, T=T, heads=Hv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[col, col, row, row, row, page],
            out_specs=[row, page]),
        out_shape=[jax.ShapeDtypeStruct((B, T, Hv, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetch arguments: the pool is the
        # ninth, and the second result
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
        interpret=pallas_paged.needs_interpret(),
        name="gdn_recurrent_step",
    )(ids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32),
      q.transpose(0, 1, 3, 2), k.transpose(0, 1, 3, 2),
      beta[..., None] * v, jnp.broadcast_to(beta[..., None], v.shape),
      jnp.broadcast_to(jnp.exp(g)[..., None], v.shape), state)
    return o, state


# ---------------------------------------------------------------------
# the chunkwise form
# ---------------------------------------------------------------------

def _solve_rows_jnp(At: jnp.ndarray) -> jnp.ndarray:
    """The substitution as XLA operations: the CPU's path, and what
    tests/test_gdn.py holds the kernel to."""
    for i in range(1, At.shape[0]):
        row = At[i]                                     # [b, blocks]
        At = At.at[i].set(row + jnp.sum(row[:, None, :] * At, axis=0))
    return At


def _unit_lower_inverse(L: jnp.ndarray) -> jnp.ndarray:
    """(I + L)^-1 for L [..., n, n] STRICTLY lower triangular, float32,
    n a power-of-two multiple of _SOLVE_BLOCK. The diagonal blocks of
    _SOLVE_BLOCK, all at once, by forward substitution (row i of the
    inverse from the rows before it: backward stable, where the series
    I - L + L^2 - ... cancels catastrophically for keys that repeat);
    then neighbours are merged, level by level, by
    [[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]."""
    n, b = L.shape[-1], min(_SOLVE_BLOCK, L.shape[-1])
    hi = jax.lax.Precision.HIGHEST
    A = -jnp.stack([L[..., i:i + b, i:i + b] for i in range(0, n, b)],
                   axis=-3)                             # [..., n/b, b, b]
    # the blocks go on the MINOR axis ([row, column, blocks]: whole
    # lanes); as [..., b, b] the substitution ran at a sixth of its
    # lanes (PERF.md, PR 42)
    lead = A.shape[:-2]
    At = _solve_rows_jnp(jnp.moveaxis(A.reshape((-1, b, b)), 0, -1))
    A = (jnp.moveaxis(At, -1, 0).reshape(lead + (b, b))
         + jnp.eye(b, dtype=L.dtype))
    blocks, size = [A[..., i, :, :] for i in range(n // b)], b
    while len(blocks) > 1:
        merged = []
        for p in range(0, len(blocks), 2):
            t11, t22, at = blocks[p], blocks[p + 1], p * size
            t21 = -jnp.einsum(
                "...ab,...bc,...cd->...ad", t22,
                L[..., at + size:at + 2 * size, at:at + size], t11,
                precision=hi)
            merged.append(jnp.concatenate([
                jnp.concatenate([t11, jnp.zeros_like(t11)], -1),
                jnp.concatenate([t21, t22], -1)], axis=-2))
        blocks, size = merged, 2 * size
    return blocks[0]


def _chunk_prep(q, k, v, g, beta):
    """The per-chunk operands of the scan, every chunk at once.
    q, k [B, T, Hv, Dk], v [B, T, Hv, Dv] in the activations' dtype,
    g, beta [B, T, Hv] float32, T a multiple of CHUNK -> (qg, w, kdT
    [B,Hv,N,C,Dk] / [.., Dk, C], u [B,Hv,N,C,Dv], attn [B,Hv,N,C,C] in
    that dtype but u, float32; dec [B,Hv,N] float32 = exp(G_C))."""
    B, T, Hv, Dk = q.shape
    dt, N, C = q.dtype, T // CHUNK, CHUNK
    f32 = jnp.float32

    def chunks(x):          # [B, T, Hv, ...] -> [B, Hv, N, C, ...]
        return jnp.moveaxis(x.reshape((B, N, C) + x.shape[2:]), 3, 1)
    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g), chunks(beta)                      # [B,Hv,N,C]
    G = jnp.cumsum(g, axis=-1)
    i = jnp.arange(C)
    lower = i[:, None] >= i[None, :]
    # exp of differences only, and only where they are <= 0
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    kk = jnp.einsum("...ik,...jk->...ij", k, k, preferred_element_type=f32)
    L = jnp.where(i[:, None] > i[None, :],
                  beta[..., None] * decay * kk, 0.0)
    # T, u and w in float32 (small products): u enters no product
    # and stays float32; w is rounded once, as an operand of the scan
    Tm, hi = _unit_lower_inverse(L), jax.lax.Precision.HIGHEST
    u = jnp.einsum("...ij,...jv->...iv", Tm, beta[..., None] * v,
                   precision=hi)
    w = jnp.einsum("...ij,...jk->...ik", Tm,
                   (beta * jnp.exp(G))[..., None] * k,
                   precision=hi).astype(dt)
    attn = (jnp.einsum("...ik,...jk->...ij", q, k,
                       preferred_element_type=f32) * decay).astype(dt)
    qg = (q * jnp.exp(G)[..., None]).astype(dt)
    kd = (k * jnp.exp(G[..., -1:] - G)[..., None]).astype(dt)
    return qg, w, kd.swapaxes(-1, -2), u, attn, jnp.exp(G[..., -1])





def _scan_jnp(qg, w, kdT, u, attn, dec, state, ids, layer, fresh):
    dt = qg.dtype
    S0 = jnp.where(fresh[:, None, None, None], 0.0, state[layer, ids])

    def step(S, xs):
        qg, w, kdT, u, attn, dec = xs
        Sb = S.astype(dt)
        f32 = jnp.float32
        d = u - jnp.einsum("bhck,bhkv->bhcv", w, Sb,
                           preferred_element_type=f32)
        db = d.astype(dt)
        o = (jnp.einsum("bhck,bhkv->bhcv", qg, Sb,
                        preferred_element_type=f32)
             + jnp.einsum("bhcj,bhjv->bhcv", attn, db,
                          preferred_element_type=f32))
        S = S * dec[..., None, None] + jnp.einsum(
            "bhkc,bhcv->bhkv", kdT, db, preferred_element_type=f32)
        return S, o

    S, o = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(x, 2, 0) for x in (qg, w, kdT, u, attn, dec)))
    return jnp.moveaxis(o, 0, 2), state.at[layer, ids].set(S)


def _solve_stacked(a_ref, e_ref, x_ref):
    """The forward substitution of every diagonal block of a grid step
    at once. a_ref [16 * _GROUP, _PAIR] float32: row ``i * _GROUP + p``
    holds row i of the eight NEGATED strictly lower diagonal blocks of
    pair p, a block after the other on the lanes (block, column); so
    row i of every block of the step is ONE [_GROUP, _PAIR] tile.
    x_ref, the same layout, receives ``(I + L)^-1 - I``: row i = its
    own entries plus, for every j < i, entry (i, j) times the finished
    row j, the recurrence of ``_solve_rows_jnp`` in its order. Entry
    (i, j) of a block has to multiply that block's sixteen lanes of row
    j: e_ref[j] holds it spread over them, made on the MXU against a
    0 / 1 matrix from the entries split into three bfloat16 terms, which
    reproduces a float32 exactly; every multiply-add of the recurrence
    itself is the vector unit's, float32."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    n = a_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    to = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    by = _SOLVE_BLOCK.bit_length() - 1
    same = (lane >> by) == (to >> by)
    a = a_ref[...]
    terms = []
    for _ in range(3):
        terms.append(a.astype(bf16))
        a = a - terms[-1].astype(f32)
    for j in range(_SOLVE_BLOCK - 1):
        spread = (same & ((lane & (_SOLVE_BLOCK - 1)) == j)).astype(bf16)
        e_ref[j] = sum(jnp.dot(t, spread, preferred_element_type=f32)
                       for t in terms)
    x_ref[0:_GROUP] = a_ref[0:_GROUP]
    for i in range(1, _SOLVE_BLOCK):
        rows = slice(i * _GROUP, (i + 1) * _GROUP)
        acc = a_ref[rows]
        for j in range(i):
            acc = acc + e_ref[j, rows] * x_ref[j * _GROUP:(j + 1) * _GROUP]
        x_ref[rows] = acc


def _rule_kernel(ids_ref, layer_ref, fresh_ref, q_ref, k_ref, v_ref, g_ref,
                 beta_ref, s_ref, o_ref, so_ref, acc_ref, l_ref, decay_ref,
                 a_ref, e_ref, x_ref, *, pairs: int, groups: int):
    """One (row, value head, group of ``pairs`` pairs of chunks): the
    whole rule over ``pairs * _PAIR`` positions, nothing of it written
    to HBM but ``o`` and, at the row's last group, the page.

    q_ref, k_ref [1, pairs * _PAIR, Dk] (the key head the value head
    reads) and v_ref [.., Dv] in the activations' dtype, read where the
    projections left them; g_ref, beta_ref [1, pairs * _PAIR, Hv]
    float32 (every head's: this one's is picked by lane); s_ref /
    so_ref [1, 1, 1, Dk, Dv] the head's matrix of the page. Two chunks
    share every [_PAIR, _PAIR] matrix of the transform (L, the decays,
    the inverse, ``attn``), block-diagonal by chunk under a mask: the
    MXU takes 128 rows for the time of 64. Three passes over the pairs:
    L and its folded diagonal blocks; their substitution, every pair's
    at once; the merges, ``u``, ``w``, ``attn``, ``qg``, ``kd`` and the
    recurrence, the head's matrix in acc_ref from the page's copy-in
    at the first group to its copy-back at the last."""
    b, h, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    f32, dt = jnp.float32, q_ref.dtype
    P, C, hi = _PAIR, CHUNK, jax.lax.Precision.HIGHEST
    # float32 operands (tests, tools) multiply at full precision: the
    # caller's default_matmul_precision does not reach into the kernel
    exact = hi if dt == f32 else None
    row = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)

    def same(size):     # in one block of ``size`` (a power of two)
        by = size.bit_length() - 1
        return (row >> by) == (col >> by)
    chunk, eye = same(C), row == col
    same16, same32 = same(_SOLVE_BLOCK), same(2 * _SOLVE_BLOCK)
    mine = jax.lax.broadcasted_iota(
        jnp.int32, (P, g_ref.shape[-1]), 1) == h

    def dot(x, y, dims=((1,), (0,)), precision=exact):
        return jax.lax.dot_general(x, y, (dims, ((), ())),
                                   precision=precision,
                                   preferred_element_type=f32)

    def second(x, size):
        """The rows of the second block of each two blocks of size."""
        return jnp.concatenate(
            [x[i:i + size] for i in range(size, P, 2 * size)], axis=0)

    def spaced(x, size):
        """``second``'s rows back in their places, zeros between."""
        gap = jnp.zeros((size, P), f32)
        return jnp.concatenate([y for i in range(0, P // 2, size)
                                for y in (gap, x[i:i + size])], axis=0)

    def columns(at):
        """The pair's G (log-decays summed from each chunk's first
        token) as a column and as a row, and beta as a column."""
        g = jnp.sum(jnp.where(mine, g_ref[0, at, :], 0.0), axis=1,
                    keepdims=True)
        beta = jnp.sum(jnp.where(mine, beta_ref[0, at, :], 0.0), axis=1,
                       keepdims=True)
        Grow = jnp.sum(jnp.where(chunk & (row <= col), g, 0.0), axis=0,
                       keepdims=True)
        Gcol = jnp.sum(jnp.where(eye, Grow, 0.0), axis=1, keepdims=True)
        return Gcol, Grow, beta

    @pl.when(n == 0)
    def _load():
        acc_ref[...] = s_ref[0, 0, 0] * (
            1.0 - fresh_ref[b].astype(f32))

    if pairs < _GROUP:
        a_ref[...] = jnp.zeros_like(a_ref)
    for p in range(pairs):
        at = slice(p * P, (p + 1) * P)
        Gcol, Grow, beta = columns(at)
        # exp of differences only, and only where they are <= 0
        keep = chunk & (row >= col)
        decay = jnp.where(keep, jnp.exp(jnp.where(keep, Gcol - Grow, 0.0)),
                          0.0)
        k = k_ref[0, at, :]
        L = jnp.where(chunk & (row > col),
                      beta * decay * dot(k, k, ((1,), (1,))), 0.0)
        l_ref[p], decay_ref[p] = L, decay
        # row i of the pair's eight diagonal blocks, negated
        blocks = jnp.where(same16, -L, 0.0)
        a_ref[pl.ds(p, _SOLVE_BLOCK, stride=_GROUP)] = sum(
            blocks[i:i + _SOLVE_BLOCK]
            for i in range(0, P, _SOLVE_BLOCK))

    _solve_stacked(a_ref, e_ref, x_ref)

    for p in range(pairs):
        at = slice(p * P, (p + 1) * P)
        Gcol, _, beta = columns(at)
        L, decay = l_ref[p], decay_ref[p]
        q, k, v = q_ref[0, at, :], k_ref[0, at, :], v_ref[0, at, :]
        solved = x_ref[pl.ds(p, _SOLVE_BLOCK, stride=_GROUP)]
        Tm = jnp.where(same16, jnp.concatenate(
            [solved] * (P // _SOLVE_BLOCK), axis=0), 0.0) + eye.astype(f32)
        # neighbours merge, level by level: with T the inverses of the
        # diagonal blocks and B what L holds beside them, T - T B T;
        # only the second block of each two has rows in B and in T B T,
        # and the products take those rows alone
        for size, beside in ((_SOLVE_BLOCK, same32 & ~same16),
                             (2 * _SOLVE_BLOCK, ~same32)):
            BT = dot(second(jnp.where(beside, L, 0.0), size), Tm,
                     precision=hi)
            Tm = Tm - spaced(dot(second(Tm, size), spaced(BT, size),
                                 precision=hi), size)
        eG = jnp.exp(Gcol)
        uw = dot(Tm, jnp.concatenate(
            [beta * v.astype(f32), (beta * eG) * k.astype(f32)], axis=1),
            precision=hi)
        Dv = v.shape[-1]
        u, w = uw[:, :Dv], uw[:, Dv:].astype(dt)
        attn = (dot(q, k, ((1,), (1,))) * decay).astype(dt)
        qg = (q.astype(f32) * eG).astype(dt)
        last = jnp.where(row[:, :1] < C, Gcol[C - 1:C], Gcol[P - 1:P])
        kdT = (k.astype(f32) * jnp.exp(last - Gcol)).T.astype(dt)
        # the recurrence, a chunk after the other; the products against
        # a whole pair's rows take the other chunk's as zeros
        S = acc_ref[...]
        none, ds, outs = jnp.zeros((C, Dv), dt), [], []
        for c in range(2):
            rows = slice(c * C, (c + 1) * C)
            Sb = S.astype(dt)
            outs.append(dot(qg[rows], Sb))
            ds.append((u[rows] - dot(w[rows], Sb)).astype(dt))
            both = [none, none]
            both[c] = ds[-1]
            S = (S * jnp.exp(Gcol[(c + 1) * C - 1:(c + 1) * C])
                 + dot(kdT, jnp.concatenate(both, axis=0)))
        o_ref[0, at, :] = (jnp.concatenate(outs, axis=0)
                           + dot(attn, jnp.concatenate(ds, axis=0)))
        acc_ref[...] = S

    @pl.when(n == groups - 1)
    def _store():
        so_ref[0, 0, 0] = acc_ref[...]


def _group_pairs(pairs: int) -> int:
    """Pairs of chunks a grid step takes: the most, up to _GROUP, that
    divide the row's (a block past the end of the row would be read as
    it lies, and its g and beta would advance the state)."""
    return max(d for d in range(1, _GROUP + 1) if pairs % d == 0)


def _chunked(q, k, v, g, beta, state, ids, layer, fresh):
    """q, k [B, T, Hk, Dk], v [B, T, Hv, Dv] in the activations' dtype,
    g, beta [B, T, Hv] float32 -> (o [B, T, Hv, Dv] float32, the
    pool)."""
    B, T, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    kernels = pallas_paged.flash_enabled()
    pad = (-T) % (_PAIR if kernels else CHUNK)
    if pad:     # g = 0, beta = 0: the padding advances nothing
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    Tp = T + pad
    if not kernels:
        with jax.named_scope("gdn_chunk_prep"):
            operands = _chunk_prep(_expand_heads(q, Hv),
                                   _expand_heads(k, Hv), v, g, beta)
        o, state = _scan_jnp(*operands, state, ids, layer, fresh)
        # [B, Hv, N, C, Dv] -> [B, T, Hv, Dv]
        o = jnp.moveaxis(o, 1, 3).reshape(B, Tp, Hv, Dv)
        return o[:, :T], state
    pairs = _group_pairs(Tp // _PAIR)
    rows, rep = pairs * _PAIR, Hv // Hk
    groups = Tp // rows

    def head(width, of):
        return pl.BlockSpec(
            (1, rows, width),
            lambda b, h, n, ids, lyr, fr: (b, n, of(h)))
    page = pl.BlockSpec(
        (1, 1, 1, Dk, Dv),
        lambda b, h, n, ids, lyr, fr: (lyr[0], ids[b], h, 0, 0))
    f32 = jnp.float32
    o, state = pl.pallas_call(
        functools.partial(_rule_kernel, pairs=pairs, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, Hv, groups),
            # q, k, v and o as the projections lay them, [B, T, H * D]:
            # a block is (row, group, head), the key head h // rep
            in_specs=[head(Dk, lambda h: h // rep),
                      head(Dk, lambda h: h // rep),
                      head(Dv, lambda h: h),
                      head(Hv, lambda h: 0), head(Hv, lambda h: 0), page],
            out_specs=[head(Dv, lambda h: h), page],
            scratch_shapes=[
                pltpu.VMEM((Dk, Dv), f32),
                pltpu.VMEM((pairs, _PAIR, _PAIR), f32),
                pltpu.VMEM((pairs, _PAIR, _PAIR), f32),
                pltpu.VMEM((_SOLVE_BLOCK * _GROUP, _PAIR), f32),
                pltpu.VMEM((_SOLVE_BLOCK - 1, _SOLVE_BLOCK * _GROUP, _PAIR),
                           f32),
                pltpu.VMEM((_SOLVE_BLOCK * _GROUP, _PAIR), f32)]),
        out_shape=[jax.ShapeDtypeStruct((B, Tp, Hv * Dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalar-prefetch arguments: the pool is the
        # ninth, and the second result
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
        interpret=pallas_paged.needs_interpret(),
        name="gdn_chunk_scan",
    )(ids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), q.reshape(B, Tp, Hk * Dk),
      k.reshape(B, Tp, Hk * Dk), v.reshape(B, Tp, Hv * Dv), g, beta, state)
    return o.reshape(B, Tp, Hv, Dv)[:, :T], state


def mix(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
        beta: jnp.ndarray, state: jnp.ndarray, ids: jnp.ndarray, layer,
        fresh: jnp.ndarray):
    """The gated delta rule over T positions a row, from and to the
    rows' pages of layer ``layer`` of the state pool.

    q, k [B, T, Hk, Dk] (normalised, q scaled), v [B, T, Hv, Dv], g
    (log-decay, 0 where the position is not real) and beta (0 there)
    [B, T, Hv] float32; state [layers, pages, Hv, Dk, Dv] float32; ids
    [B] the rows' pages (the trash page for a row that is not real);
    fresh [B] bool: the row starts at position 0, from a zero state.
    -> (o [B, T, Hv, Dv] float32, the pool, updated in place)."""
    if gdn_path(q.shape[1]) == RECURRENT:
        with jax.named_scope("gdn_step"):
            f32, Hv = jnp.float32, v.shape[2]
            return _recurrent(_expand_heads(q, Hv).astype(f32),
                              _expand_heads(k, Hv).astype(f32),
                              v.astype(f32), g, beta, state, ids, layer,
                              fresh)
    with jax.named_scope("gdn_scan"):
        return _chunked(q, k, v, g, beta, state, ids, layer, fresh)


def causal_conv(x: jnp.ndarray, weight: jnp.ndarray, prev: jnp.ndarray,
                valid_len: jnp.ndarray, bias=None):
    """The depthwise causal convolution of a Gated DeltaNet layer (or,
    with ``bias`` [Ch], of a Mamba layer), then SiLU. x [B, T, Ch] the
    chunk's inputs, weight [taps, Ch] (the last
    tap multiplies the token itself), prev [B, taps - 1, Ch] the inputs
    before the chunk (the convolution's state), valid_len [B] how many
    of the T positions are real (they lead the chunk) -> (y [B, T, Ch],
    the new state: the last taps - 1 REAL inputs)."""
    taps = weight.shape[0]
    T = x.shape[1]
    full = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    y = sum(full[:, j:j + T].astype(jnp.float32)
            * weight[j].astype(jnp.float32) for j in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    new = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, taps - 1, axis=0))(full, valid_len)
    return jax.nn.silu(y).astype(x.dtype), new.astype(prev.dtype)
