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
    in blocks, ``u = T (beta V)``, ``w = T (beta exp(G) K)``,
    ``D = u - w S_0``), made for every chunk at once under the scope
    ``gdn_chunk_prep``: on the TPU the substitution of the diagonal
    blocks is the kernel ``gdn_chunk_solve`` (a lane tile of blocks a
    grid step, read and written once, its fifteen row updates on the
    tile in VMEM); the products around it (L itself, the
    merges of neighbouring blocks, ``u``, ``w``) are XLA's. Between
    chunks the state is carried: on the TPU the kernel
    ``gdn_chunk_scan``, a grid step a (row, head, chunk) with the head's
    matrix in VMEM from the page's copy-in at the first chunk to its
    copy-back at the last:

        D = u - w S;  o = (q exp(G)) S + (Q K^T . decay) D
        S = exp(G_C) S + (k exp(G_C - G))^T D

    bfloat16 operands (as the inputs come), float32 products, the state
    float32 throughout.

A chunk whose first position is 0 (``fresh``) starts from a zero state
inside the kernel: no page is ever cleared by the host. Positions that
are not real advance nothing: the caller hands them ``g = 0`` and
``beta = 0``, and a row that is not real names the trash page.

Where the kernels are off (``pallas_paged.flash_enabled``: the CPU) the
same two forms, the substitution among them, run in ``jax.numpy``;
tests/test_gdn.py holds each to the sequential rule of
chipbench/references/qwen3_next.py and, in interpret mode, the kernels
to the ``jax.numpy`` forms.
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
# diagonal blocks a grid step of the substitution kernel holds, one a
# lane: [16, 16, 512] float32 is 512 KB in and as much out
_SOLVE_LANES = 512

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


def _solve_kernel(a_ref, o_ref):
    """One lane tile of blocks, [b, b, lanes] float32 in VMEM: row i
    from the finished rows j < i of o_ref (the entries at j >= i are
    zeros of a strictly lower triangle and are left out). No product
    goes to the MXU: every multiply-add is the vector unit's, float32,
    so no matmul precision of the caller's reaches in here."""
    o_ref[0] = a_ref[0]
    for i in range(1, a_ref.shape[0]):
        acc = a_ref[i]                                  # [b, lanes]
        for j in range(i):
            acc = acc + a_ref[i, j:j + 1, :] * o_ref[j]
        o_ref[i] = acc


def _solve_rows(At: jnp.ndarray) -> jnp.ndarray:
    """At [b, b, blocks] float32, the NEGATED strictly lower diagonal
    blocks with the blocks on the minor axis (whole lanes) -> the same
    of their ``(I + L)^-1 - I``. Row i of a block's inverse = its own
    entries plus, for every j < i, entry j times the finished row j:
    forward substitution, the same recurrence in the same order in
    both forms. The kernel's operand is row-major by the call's own
    constraint; for the loop inside ``_chunk_prep`` XLA's layout
    assignment puts the blocks on the major axis, a sixteenth of the
    lanes, and neither ``optimization_barrier`` nor
    ``with_layout_constraint`` around the loop moves it (PERF.md,
    PR 49)."""
    if not pallas_paged.flash_enabled():
        return _solve_rows_jnp(At)
    b, _, blocks = At.shape
    lanes = min(_SOLVE_LANES, blocks)
    tile = pl.BlockSpec((b, b, lanes), lambda t: (0, 0, t))
    return pl.pallas_call(
        _solve_kernel, grid=(pl.cdiv(blocks, lanes),),
        in_specs=[tile], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(At.shape, At.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
        interpret=pallas_paged.needs_interpret(),
        name="gdn_chunk_solve",
    )(At)


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
    At = _solve_rows(jnp.moveaxis(A.reshape((-1, b, b)), 0, -1))
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


def _scan_kernel(ids_ref, layer_ref, fresh_ref, qg_ref, w_ref, kdt_ref,
                 u_ref, attn_ref, dec_ref, s_ref, o_ref, so_ref, acc_ref,
                 *, chunks: int):
    """One (row, head, chunk): the head's matrix stays in acc_ref from
    the page's copy-in at the first chunk to its copy-back at the
    last."""
    b, n = pl.program_id(0), pl.program_id(2)

    @pl.when(n == 0)
    def _load():
        acc_ref[...] = s_ref[0, 0, 0] * (
            1.0 - fresh_ref[b].astype(jnp.float32))

    S = acc_ref[...]                                         # [Dk, Dv]
    dt = qg_ref.dtype
    Sb = S.astype(dt)

    def dot(a, c):
        return jax.lax.dot_general(a, c, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    d = u_ref[0, 0, 0] - dot(w_ref[0, 0, 0], Sb)
    db = d.astype(dt)
    o_ref[0, 0, 0] = dot(qg_ref[0, 0, 0], Sb) + dot(attn_ref[0, 0, 0], db)
    S = S * dec_ref[0, 0, pl.ds(n, 1), :] + dot(kdt_ref[0, 0, 0], db)
    acc_ref[...] = S

    @pl.when(n == chunks - 1)
    def _store():
        so_ref[0, 0, 0] = S


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


def _chunked(q, k, v, g, beta, state, ids, layer, fresh):
    """q, k, v [B, T, Hv, D] in the activations' dtype, g, beta
    [B, T, Hv] float32 -> (o [B, T, Hv, Dv] float32, the pool)."""
    B, T, Hv, Dk = q.shape
    Dv = v.shape[-1]
    pad = (-T) % CHUNK
    if pad:     # g = 0, beta = 0: the padding advances nothing
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    N, C = (T + pad) // CHUNK, CHUNK
    with jax.named_scope("gdn_chunk_prep"):
        qg, w, kdT, u, attn, dec = _chunk_prep(q, k, v, g, beta)
    if not pallas_paged.flash_enabled():
        o, state = _scan_jnp(qg, w, kdT, u, attn, dec, state, ids, layer,
                             fresh)
    else:
        def at(b, h, n, ids, lyr, fr):
            return (b, h, n, 0, 0)
        page = pl.BlockSpec(
            (1, 1, 1, Dk, Dv),
            lambda b, h, n, ids, lyr, fr: (lyr[0], ids[b], h, 0, 0))
        o, state = pl.pallas_call(
            functools.partial(_scan_kernel, chunks=N),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(B, Hv, N),
                in_specs=[pl.BlockSpec((1, 1, 1, C, Dk), at),
                          pl.BlockSpec((1, 1, 1, C, Dk), at),
                          pl.BlockSpec((1, 1, 1, Dk, C), at),
                          pl.BlockSpec((1, 1, 1, C, Dv), at),
                          pl.BlockSpec((1, 1, 1, C, C), at),
                          # a head's decays of every chunk, as rows
                          pl.BlockSpec(
                              (1, 1, N, Dv),
                              lambda b, h, n, ids, lyr, fr: (b, h, 0, 0)),
                          page],
                out_specs=[pl.BlockSpec((1, 1, 1, C, Dv), at), page],
                scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((B, Hv, N, C, Dv), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            input_output_aliases={9: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
            interpret=pallas_paged.needs_interpret(),
            name="gdn_chunk_scan",
        )(ids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
          fresh.astype(jnp.int32), qg, w, kdT, u, attn,
          jnp.broadcast_to(dec[..., None], dec.shape + (Dv,)), state)
    # [B, Hv, N, C, Dv] -> [B, T, Hv, Dv]
    o = jnp.moveaxis(o, 1, 3).reshape(B, N * C, Hv, Dv)
    return o[:, :T], state


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
    Hv = v.shape[2]
    q, k = _expand_heads(q, Hv), _expand_heads(k, Hv)
    if gdn_path(q.shape[1]) == RECURRENT:
        with jax.named_scope("gdn_step"):
            f32 = jnp.float32
            return _recurrent(q.astype(f32), k.astype(f32), v.astype(f32),
                              g, beta, state, ids, layer, fresh)
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
