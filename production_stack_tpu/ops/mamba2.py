"""Mamba-2's state-space scan over a state page.

A Mamba-2 layer (Dao and Gu, arXiv 2405.21060) keeps, a sequence and
head, ONE float32 state ``h [P (head_dim), N (state)]`` whatever the
context. With the token's input ``x_t [P]`` (after the convolution), its
step ``dt_t > 0``, the layer's ``A < 0`` (ONE scalar a head) and the
input and output maps ``B_t, C_t [N]`` of the head's GROUP (heads /
groups heads read one group's):

    h = exp(dt_t A) h + dt_t x_t (x) B_t;   y_t = h C_t

The decay is a scalar a head, so a chunk of Q tokens has a closed form
in matrix products (the state-space duality): with ``cs`` the running
sum of ``dt A`` inside the chunk, ``L[t, s] = exp(cs_t - cs_s)`` for
``s <= t``,

    Y = (L o C B^T) (dt X) + exp(cs) o (C h_0^T)
    h_Q = exp(cs_Q) h_0 + (exp(cs_Q - cs) o dt X)^T B

The state pool is ``[layers, pages, N, D]`` float32 (models/kv.py), D =
heads x head_dim channels ON THE LANES, channel ``c`` of head ``c //
head_dim``; the state's rows are the N entries of ``B`` and ``C``: a
decay is a ROW of the tile (it broadcasts over the state's rows), a
token's ``B_t`` a COLUMN, and ``y_t = sum_n h[n, :] C_t[n]`` a sum over
sublanes, which lands lane-dense. A grid step's tile is one GROUP's
channels (D / groups), whose heads share ``B`` and ``C``. Two kernels,
chosen by shape alone (``mamba2_path``), both taking the state from and
leaving it in its page, which a step program carries as it carries the
K/V pool:

``mamba2_recurrent_step``  T <= DECODE_T_MAX positions a row (a decode
    step): a grid step a (group, row) copies the group's tile of the
    row's page in, runs the T positions on the vector unit and copies
    it back to the SAME page (the pool is aliased to the kernel's
    result); bound by ``rows x 2 x page bytes`` a layer.
``mamba2_chunk_scan``  longer (a prefill chunk): the T positions in
    chunks of ``CHUNK`` tokens, a grid step a (group, row, chunk), the
    group's tile of the state in VMEM from the page's copy-in at the
    first chunk to its copy-back at the last; inside, 128 lanes of
    channels (128 / head_dim heads) at a time, the closed form above as
    products on the matrix unit: ``C B^T`` once a group and chunk, and a
    head's ``(L o C B^T) dt X`` over its slice's 128 lanes (the unit is
    128 columns wide: a head of 64 costs what two would), of which its
    own lanes are kept. ``L`` is made in registers and never written
    anywhere. The model's ``chunk_size`` is this kernel's CHUNK at the
    published 128; the result does not depend on it.

A chunk whose first position is 0 (``fresh``) starts from a zero state
inside the kernel: no page is ever cleared by the host. Positions that
are not real advance nothing: the caller hands them ``dt = 0`` (a decay
of one, a write of zero), and a row that is not real names the trash
page: ops/mamba.py's conventions.

Where the kernels are off (``pallas_paged.flash_enabled``: the CPU) or
the shapes do not tile (``_tiles``) the same rule runs as a ``lax.scan``
over tokens in ``jax.numpy`` (``mamba2_recurrent_step_jnp`` /
``mamba2_chunk_scan_jnp``); tests/test_mamba2.py holds the kernels, in
interpret mode, to it.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops import pallas_paged
from production_stack_tpu.ops.pallas_paged import DECODE_T_MAX

# tokens a grid step of the chunk scan takes: the matrix unit's rows
CHUNK = 128
_LANES = 128

RECURRENT = "mamba2_recurrent_step"
CHUNKED = "mamba2_chunk_scan"


def _tiles(channels: int, heads: int, groups: int, state: int) -> bool:
    """Do the kernels take these shapes: a group's channels whole
    vectors of 128 lanes, whole heads a vector, the state's rows whole
    sublanes."""
    hd = channels // heads
    return ((channels // groups) % _LANES == 0 and _LANES % hd == 0
            and state % 8 == 0)


def mamba2_path(T: int, channels: int, heads: int, groups: int,
                state: int) -> str:
    """Which implementation a forward of T positions a row runs:
    decided by shape, before anything compiles; ``*_jnp`` where the
    kernels are off or the shapes do not tile."""
    path = RECURRENT if T <= DECODE_T_MAX else CHUNKED
    on = pallas_paged.flash_enabled() and _tiles(channels, heads, groups,
                                                state)
    return path if on else path + "_jnp"


def _scan_jnp(xdt, a, Bm, Cm, state, ids, layer, fresh):
    """The recurrence a token at a time. xdt [B, T, D] (dt x), a
    [B, T, H] (dt A), Bm, Cm [B, T, G, N], all float32."""
    D, H, G = xdt.shape[-1], a.shape[-1], Bm.shape[2]
    h0 = jnp.where(fresh[:, None, None], 0.0, state[layer, ids])

    def step(h, xs):
        x_t, a_t, b_t, c_t = xs     # [B, D], [B, H], [B, G, N] x 2
        decay = jnp.repeat(jnp.exp(a_t), D // H, axis=-1)       # [B, D]
        b_t, c_t = (jnp.repeat(jnp.swapaxes(m, 1, 2), D // G, axis=-1)
                    for m in (b_t, c_t))                        # [B, N, D]
        h = decay[:, None, :] * h + x_t[:, None, :] * b_t
        return h, jnp.sum(h * c_t, axis=1)

    h, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(m, 1, 0) for m in (xdt, a, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), state.at[layer, ids].set(h)


def _step_kernel(ids_ref, layer_ref, fresh_ref, x_ref, da_ref, bt_ref,
                 ct_ref, s_ref, y_ref, so_ref, *, tokens: int):
    """One (group, row): T positions on the vector unit. x_ref, da_ref
    [1, T, tile] float32 (dt x; exp(dt A) a channel): a token's are a
    ROW; bt_ref, ct_ref [1, 1, N, T]: a token's are a COLUMN; s_ref /
    so_ref [1, 1, N, tile]: the page's tile, in and out (the same
    bytes)."""
    b = pl.program_id(1)
    h = s_ref[0, 0] * (1.0 - fresh_ref[b].astype(jnp.float32))
    for t in range(tokens):
        h = (da_ref[0, t:t + 1, :] * h
             + x_ref[0, t:t + 1, :] * bt_ref[0, 0, :, t:t + 1])
        y_ref[0, t:t + 1, :] = jnp.sum(h * ct_ref[0, 0, :, t:t + 1],
                                       axis=0, keepdims=True)
    so_ref[0, 0] = h


def _chunk_kernel(ids_ref, layer_ref, fresh_ref, x_ref, ct_ref, ch_ref,
                  c_ref, bt_ref, s_ref, y_ref, so_ref, acc_ref, *,
                  head_dim: int, chunks: int):
    """One (group, row, chunk). x_ref [1, Q, tile] (dt x, the dots'
    dtype); ct_ref [1, 1, 1, Q, Hg], ch_ref [1, 1, 1, Hg, Q] float32:
    the running sum of dt A inside the chunk, a head's as a COLUMN and
    as a ROW; c_ref [1, 1, 1, Q, N]; bt_ref [1, 1, 1, N, Q] (B transposed);
    s_ref / so_ref [1, 1, N, tile]; acc_ref the state between chunks."""
    b, n = pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)
    def _load():
        acc_ref[...] = s_ref[0, 0] * (
            1.0 - fresh_ref[b].astype(jnp.float32))

    f32 = jnp.float32
    Q, tile = x_ref.shape[1], x_ref.shape[2]
    cdt = x_ref.dtype
    # float32 operands (tests) multiply at full precision
    dot = functools.partial(
        jnp.dot, preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST if cdt == f32 else None)
    Cq, Bt = c_ref[0, 0, 0], bt_ref[0, 0, 0]
    CB = dot(Cq, Bt)                                          # [Q, Q]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    per = _LANES // head_dim            # heads a vector of lanes holds
    for s in range(tile // _LANES):
        lanes = slice(s * _LANES, (s + 1) * _LANES)
        xs = x_ref[0, :, lanes]                               # [Q, 128]
        h0 = acc_ref[:, lanes]                                # [N, 128]
        y = jnp.zeros((Q, _LANES), f32)
        grow = jnp.zeros((Q, _LANES), f32)     # exp(cs_t) a channel
        left = jnp.zeros((Q, _LANES), f32)     # exp(cs_Q - cs_s)
        decay = jnp.zeros((1, _LANES), f32)    # exp(cs_Q)
        for i in range(per):
            head = s * per + i
            col = ct_ref[0, 0, 0, :, head:head + 1]           # [Q, 1]
            row = ch_ref[0, 0, 0, head:head + 1, :]           # [1, Q]
            last = col[Q - 1:Q, :]
            L = jnp.where(causal, jnp.exp(jnp.minimum(col - row, 0.0)),
                          0.0)
            mine = (lane >= i * head_dim) & (lane < (i + 1) * head_dim)
            y = jnp.where(mine, dot((L * CB).astype(cdt), xs), y)
            grow = jnp.where(mine, jnp.exp(col), grow)
            left = jnp.where(mine, jnp.exp(last - col), left)
            decay = jnp.where(mine, jnp.exp(last), decay)
        y_ref[0, :, lanes] = y + dot(Cq, h0.astype(cdt)) * grow
        acc_ref[:, lanes] = decay * h0 + dot(
            Bt, (xs.astype(f32) * left).astype(cdt))

    @pl.when(n == chunks - 1)
    def _store():
        so_ref[0, 0] = acc_ref[...]


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, Bm: jnp.ndarray,
             Cm: jnp.ndarray, A: jnp.ndarray, state: jnp.ndarray,
             ids: jnp.ndarray, layer, fresh: jnp.ndarray):
    """The scan over T positions a row, from and to the rows' pages of
    layer ``layer`` of the state pool.

    x [B, T, D] (the activations' dtype: what the chunked form's
    products take), dt [B, T, H] float32 (0 where the position is not
    real), Bm, Cm [B, T, G, N], A [H] float32 (negative); state [layers,
    pages, N, D] float32; ids [B] the rows' pages (the trash page for a
    row that is not real); fresh [B] bool: the row starts at position
    0, from a zero state. -> (y [B, T, D] float32, WITHOUT the skip
    term D x; the pool, updated in place). Scopes
    mamba2_recurrent_step / mamba2_chunk_scan."""
    B, T, D = x.shape
    H, G, N = dt.shape[-1], Bm.shape[2], Bm.shape[3]
    hd, tile, f32 = D // H, D // G, jnp.float32
    path = mamba2_path(T, D, H, G, N)
    with jax.named_scope(path.removesuffix("_jnp")):
        a = dt * A                                            # [B, T, H]
        xdt = x.astype(f32) * jnp.repeat(dt, hd, axis=-1)
        if path.endswith("_jnp"):
            return _scan_jnp(xdt, a, Bm.astype(f32), Cm.astype(f32), state,
                             ids, layer, fresh)
        prefetch = (ids.astype(jnp.int32),
                    jnp.asarray(layer, jnp.int32).reshape(1),
                    fresh.astype(jnp.int32))
        page = pl.BlockSpec(
            (1, 1, N, tile),
            lambda j, b, *_: (_[-2][0], _[-3][b], 0, j))
        params = dict(
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES,
                dimension_semantics=("arbitrary",) * (
                    2 if path == RECURRENT else 3)),
            interpret=pallas_paged.needs_interpret(), name=path)
        if path == RECURRENT:
            row = pl.BlockSpec((1, T, tile), lambda j, b, *_: (b, 0, j))
            col = pl.BlockSpec((1, 1, N, T), lambda j, b, *_: (b, j, 0, 0))

            def cols(m):    # [B, T, G, N] -> [B, G, N, T]
                return jnp.moveaxis(m.astype(f32), 1, 3)
            y, state = pl.pallas_call(
                functools.partial(_step_kernel, tokens=T),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=3, grid=(G, B),
                    in_specs=[row, row, col, col, page],
                    out_specs=[row, page]),
                out_shape=[jax.ShapeDtypeStruct(x.shape, f32),
                           jax.ShapeDtypeStruct(state.shape, state.dtype)],
                # operands count the scalar-prefetch arguments: the
                # pool is the eighth, and the second result
                input_output_aliases={7: 1}, **params,
            )(*prefetch, xdt, jnp.repeat(jnp.exp(a), hd, axis=-1),
              cols(Bm), cols(Cm), state)
            return y, state
        Q = CHUNK
        pad = (-T) % Q
        if pad:     # dt = 0: the padding advances nothing
            xdt, a, Bm, Cm = (
                jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2))
                for m in (xdt, a, Bm, Cm))
        chunks = (T + pad) // Q
        # the running sum of dt A inside each chunk, by group and head
        cs = jnp.cumsum(a.reshape(B, chunks, Q, G, H // G), axis=2)
        cs_col = jnp.moveaxis(cs, 3, 2)               # [B, n, G, Q, Hg]
        cs_row = jnp.swapaxes(cs_col, 3, 4)           # [B, n, G, Hg, Q]
        Bm, Cm = (m.astype(x.dtype).reshape(B, chunks, Q, G, N)
                  for m in (Bm, Cm))
        rows = pl.BlockSpec((1, Q, tile), lambda j, b, n, *_: (b, n, j))

        def by_group(*shape):
            return pl.BlockSpec((1, 1, 1) + shape,
                                lambda j, b, n, *_: (b, n, j, 0, 0))
        y, state = pl.pallas_call(
            functools.partial(_chunk_kernel, head_dim=hd, chunks=chunks),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(G, B, chunks),
                in_specs=[rows, by_group(Q, H // G), by_group(H // G, Q),
                          by_group(Q, N), by_group(N, Q), page],
                out_specs=[rows, page],
                scratch_shapes=[pltpu.VMEM((N, tile), f32)]),
            out_shape=[jax.ShapeDtypeStruct(xdt.shape, f32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # the pool is the ninth operand, and the second result
            input_output_aliases={8: 1}, **params,
        )(*prefetch, xdt.astype(x.dtype), cs_col, cs_row,
          jnp.moveaxis(Cm, 3, 2),                     # [B, n, G, Q, N]
          jnp.transpose(Bm, (0, 1, 3, 4, 2)),         # [B, n, G, N, Q]
          state)
        return y[:, :T], state
