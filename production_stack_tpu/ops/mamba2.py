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
``mamba2_chunk_scan``  longer (a prefill chunk; ``chunk_mix``): the
    mixer BETWEEN ITS TWO PROJECTIONS, the T positions in chunks of
    ``CHUNK`` tokens, a grid step a (group, row, chunk). That tile, one
    group's channels of one row for one chunk, is the unit of
    everything around the scan too: the convolution is a channel's, B
    and C the group's, the gated RMSNorm over the group's channels. So
    the kernel reads ``in_proj``'s float32 output where the projection
    left it (z, x, B and C of a group are whole column blocks of it),
    convolves x, B and C in VMEM (bias, SiLU; the taps - 1 inputs
    before a chunk carried in a scratch from grid step to grid step,
    from the row's convolution page at the first), makes ``dt x``, and
    runs the closed form above as products on the matrix unit, 128
    lanes of channels (128 / head_dim heads) at a time: ``C B^T`` once
    a group and chunk, a head's ``(L o C B^T) dt X`` over its slice's
    128 lanes (the unit is 128 columns wide: a head of 64 costs what
    two would), of which its own lanes are kept, and the state's
    ``B^T (..)`` by contracting the tokens of both operands, so B is
    never transposed in memory. ``L`` is made in registers and never
    written anywhere. Then the skip ``D x``, the gate ``silu(z)`` and
    the norm over the tile's lanes in float32, and ONE write in the
    activations' dtype that ``out_proj`` reads. The group's tile of the
    state stays in VMEM from the page's copy-in at the first chunk to
    its copy-back at the last. What stays in XLA: ``dt`` and its
    running sum inside each chunk (eight columns a group are no lane
    block) and the convolution's new state (``conv_tail``: three rows).
    The model's ``chunk_size`` is this kernel's CHUNK at the published
    128; the result does not depend on it.

A chunk whose first position is 0 (``fresh``) starts from a zero state
inside the kernel: no page is ever cleared by the host. Positions that
are not real advance nothing: the caller hands them ``dt = 0`` (a decay
of one, a write of zero), and a row that is not real names the trash
page: ops/mamba.py's conventions.

Where the kernels are off (``pallas_paged.flash_enabled``: the CPU) or
the shapes do not tile (``_tiles``) the same rule runs as a ``lax.scan``
over tokens in ``jax.numpy`` (``mamba2_recurrent_step_jnp`` /
``mamba2_chunk_scan_jnp``: ``ssd_scan``, the scan alone, with the
convolution, the gate and the norm as XLA operations around it in
models/llama._mamba2_mixer); tests/test_mamba2.py holds the kernels, in
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
# rows of the fused kernel's small operands, whole sublanes: the inputs
# before a chunk (the last taps - 1 count); the taps, the bias, D and
# the norm's weight
_HALO = 8
_VEC = 8

RECURRENT = "mamba2_recurrent_step"
CHUNKED = "mamba2_chunk_scan"


def _tiles(channels: int, heads: int, groups: int, state: int) -> bool:
    """Do the kernels take these shapes: a group's channels whole
    vectors of 128 lanes, whole heads a vector, and a group's B and C
    (``state`` wide) whole vectors too, column blocks of ``in_proj``'s
    output beside x's."""
    hd = channels // heads
    return ((channels // groups) % _LANES == 0 and _LANES % hd == 0
            and state % _LANES == 0 and channels % state == 0)


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


def _conv_silu(cur, halo, vec, taps: int):
    """The depthwise causal convolution, its bias and SiLU over a
    chunk's tile, ops/gdn.causal_conv's sums in its order. cur [Q, W]
    float32 the chunk's inputs, halo [_HALO, W] the inputs before it
    (the last ``taps - 1`` rows count), vec [.., W]: rows 0..taps-1 the
    taps (the last multiplies the token itself), row ``taps`` the
    bias."""
    Q = cur.shape[0]
    full = jnp.concatenate([halo, cur], axis=0)
    y = full[_HALO - taps + 1:_HALO - taps + 1 + Q] * vec[0:1]
    for j in range(1, taps):
        at = _HALO - taps + 1 + j
        y = y + full[at:at + Q] * vec[j:j + 1]
    return jax.nn.silu(y + vec[taps:taps + 1])


def _chunk_kernel(ids_ref, layer_ref, fresh_ref, z_ref, x_ref, b_ref,
                  c_ref, px_ref, pb_ref, pc_ref, vx_ref, vb_ref, vc_ref,
                  cd_ref, ch_ref, s_ref, y_ref, so_ref, acc_ref, halo_ref,
                  g_ref, *, head_dim: int, chunks: int, taps: int,
                  eps: float, cdt):
    """One (group, row, chunk): a Mamba-2 mixer between its two
    projections. z_ref, x_ref [1, Q, tile], b_ref, c_ref [1, Q, N]:
    the group's columns of ``in_proj``'s float32 output, where the
    projection left them; px_ref, pb_ref, pc_ref [1, _HALO, ..]: the
    inputs before the call (the row's convolution page); vx_ref,
    vb_ref, vc_ref [_VEC, ..]: the convolution's taps and bias, and on
    x's columns the skip's D and the norm's weight a channel; cd_ref
    [1, 1, 1, Q, 2 Hg] float32: a head's running sum of dt A inside the
    chunk and its dt, as COLUMNS; ch_ref [1, 1, 1, Hg, Q]: the running
    sum as a ROW; s_ref / so_ref [1, 1, N, tile] the page's tile; y_ref
    [1, Q, tile] what ``out_proj`` reads. acc_ref the state and
    halo_ref [_HALO, tile + 2 N] the last inputs, between chunks; g_ref
    [Q, tile] the gated output before its norm."""
    b, n = pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32
    Q, tile, N = x_ref.shape[1], x_ref.shape[2], b_ref.shape[2]
    # x, B, C: their lanes of the halo, their inputs, the inputs before
    # the call, their taps
    parts = ((slice(0, tile), x_ref, px_ref, vx_ref),
             (slice(tile, tile + N), b_ref, pb_ref, vb_ref),
             (slice(tile + N, tile + 2 * N), c_ref, pc_ref, vc_ref))

    @pl.when(n == 0)
    def _load():
        acc_ref[...] = s_ref[0, 0] * (
            1.0 - fresh_ref[b].astype(f32))
        for at, _, before, _ in parts:
            halo_ref[:, at] = before[0]

    # float32 operands (tests) multiply at full precision
    dot = functools.partial(
        jax.lax.dot_general, preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST if cdt == f32 else None)
    nn, nt, tn = ((((1,), (0,)), ((), ())), (((1,), (1,)), ((), ())),
                  (((0,), (0,)), ((), ())))
    xc, Bq, Cq = (_conv_silu(ref[0], halo_ref[:, at], vec[...], taps)
                  for at, ref, _, vec in parts)
    Bq, Cq = Bq.astype(cdt), Cq.astype(cdt)
    for at, ref, _, _ in parts:
        halo_ref[:, at] = ref[0, Q - _HALO:, :]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    CB = jnp.where(causal, dot(Cq, Bq, nt), 0.0)              # [Q, Q]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    per = _LANES // head_dim            # heads a vector of lanes holds
    mine = [(lane >= i * head_dim) & (lane < (i + 1) * head_dim)
            for i in range(per)]
    Hg = tile // head_dim
    ss = jnp.zeros((Q, 1), f32)
    for s in range(tile // _LANES):
        lanes = slice(s * _LANES, (s + 1) * _LANES)
        xs = xc[:, lanes]                                     # [Q, 128]
        h0 = acc_ref[:, lanes]                                # [N, 128]
        y = jnp.zeros((Q, _LANES), f32)
        # a channel's running sum of dt A and its dt: its head's
        cs = jnp.zeros((Q, _LANES), f32)
        step = jnp.zeros((Q, _LANES), f32)
        for i in range(per):
            head = s * per + i
            cs = jnp.where(mine[i], cd_ref[0, 0, 0, :, head:head + 1], cs)
            step = jnp.where(
                mine[i], cd_ref[0, 0, 0, :, Hg + head:Hg + head + 1], step)
        last = cs[Q - 1:Q, :]
        grow, left, decay = jnp.exp(cs), jnp.exp(last - cs), jnp.exp(last)
        # dt x, rounded where the products take it
        xdt = (xs.astype(cdt).astype(f32) * step).astype(cdt)
        for i in range(per):
            head = s * per + i
            col = cd_ref[0, 0, 0, :, head:head + 1]           # [Q, 1]
            row = ch_ref[0, 0, 0, head:head + 1, :]           # [1, Q]
            L = jnp.exp(jnp.minimum(col - row, 0.0))   # CB is zero above
            y = jnp.where(mine[i], dot((L * CB).astype(cdt), xdt, nn), y)
        y = y + dot(Cq, h0.astype(cdt), nn) * grow
        acc_ref[:, lanes] = decay * h0 + dot(
            Bq, (xdt.astype(f32) * left).astype(cdt), tn)
        # the skip and the gate, float32 from the convolution's own
        gated = (y + vx_ref[taps + 1:taps + 2, lanes] * xs) * jax.nn.silu(
            z_ref[0, :, lanes])
        g_ref[:, lanes] = gated
        ss = ss + jnp.sum(gated * gated, axis=-1, keepdims=True)
    y_ref[0] = (g_ref[...] * jax.lax.rsqrt(ss / tile + eps)
                * vx_ref[taps + 2:taps + 3, :]).astype(y_ref.dtype)

    @pl.when(n == chunks - 1)
    def _store():
        so_ref[0, 0] = acc_ref[...]


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, Bm: jnp.ndarray,
             Cm: jnp.ndarray, A: jnp.ndarray, state: jnp.ndarray,
             ids: jnp.ndarray, layer, fresh: jnp.ndarray):
    """The scan ALONE over T positions a row, from and to the rows'
    pages of layer ``layer`` of the state pool: a decode step's kernel,
    and the ``jax.numpy`` form of either path (a prefill chunk where
    the kernels are on goes to ``chunk_mix`` and never comes here).

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
        if path != RECURRENT:
            return _scan_jnp(xdt, a, Bm.astype(f32), Cm.astype(f32), state,
                             ids, layer, fresh)
        row = pl.BlockSpec((1, T, tile), lambda j, b, *_: (b, 0, j))
        col = pl.BlockSpec((1, 1, N, T), lambda j, b, *_: (b, j, 0, 0))

        def cols(m):    # [B, T, G, N] -> [B, G, N, T]
            return jnp.moveaxis(m.astype(f32), 1, 3)
        y, state = pl.pallas_call(
            functools.partial(_step_kernel, tokens=T),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(G, B),
                in_specs=[row, row, col, col, _page(N, tile)],
                out_specs=[row, _page(N, tile)]),
            out_shape=[jax.ShapeDtypeStruct(x.shape, f32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # operands count the scalar-prefetch arguments: the
            # pool is the eighth, and the second result
            input_output_aliases={7: 1},
            **_call_params(path, 2, pallas_paged.needs_interpret()),
        )(*_prefetch(ids, layer, fresh), xdt,
          jnp.repeat(jnp.exp(a), hd, axis=-1), cols(Bm), cols(Cm), state)
        return y, state


def _prefetch(ids, layer, fresh):
    return (ids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
            fresh.astype(jnp.int32))


def _page(N: int, tile: int):
    """A group's tile of a row's page: the grid's first two indices are
    (group, row), the prefetched (ids, layer, fresh) come last."""
    return pl.BlockSpec((1, 1, N, tile),
                        lambda j, b, *_: (_[-2][0], _[-3][b], 0, j))


def _call_params(name: str, grid_dims: int, interpret: bool) -> dict:
    return dict(
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",) * grid_dims),
        interpret=interpret, name=name)


def conv_tail(zxd: jnp.ndarray, at: int, prev: jnp.ndarray,
              valid_len: jnp.ndarray):
    """The convolution's new state as ops/gdn.causal_conv leaves it:
    the last taps - 1 REAL inputs of ``prev ++ x``, x the columns of
    zxd [B, T, ..] from ``at`` on, read from the 2 (taps - 1) rows that
    can hold them and not from a joined copy. prev [B, taps - 1, Ch],
    valid_len [B] (T >= taps - 1) -> [B, taps - 1, Ch] in prev's
    dtype."""
    k, ch = prev.shape[1:]
    start = jnp.clip(valid_len - k, 0, zxd.shape[1] - k)

    def one(row, pr, s, n):
        near = jnp.concatenate([pr.astype(row.dtype), jax.lax.dynamic_slice(
            row, (s, at), (k, ch))])
        return jax.lax.dynamic_slice_in_dim(near, n - s, k, 0)
    return jax.vmap(one)(zxd, prev, start, valid_len).astype(prev.dtype)


def chunk_mix(zxd: jnp.ndarray, dt: jnp.ndarray, prev: jnp.ndarray,
              conv_w: jnp.ndarray, conv_b: jnp.ndarray, A: jnp.ndarray,
              skip: jnp.ndarray, norm_w: jnp.ndarray, eps: float,
              state: jnp.ndarray, ids: jnp.ndarray, layer,
              fresh: jnp.ndarray, dtype):
    """A prefill chunk's mixer between its two projections as ONE
    kernel (``mamba2_chunk_scan``; only where ``mamba2_path`` says
    CHUNKED): the convolution with its bias and SiLU, ``dt x``, the
    scan from and to the rows' pages, the skip, the gate and the group
    RMSNorm, a (group, row, chunk) a grid step, float32 wherever the
    unfused form is.

    zxd [B, T, 2 D + 2 G N + H] float32: ``in_proj``'s output, columns
    z, x, every group's B, every group's C, dt, read IN PLACE (z, x, B
    and C of a group are whole column blocks of it); dt [B, T, H]
    float32, ready (0 where the position is not real); prev [B,
    taps - 1, D + 2 G N] the convolution's inputs before the chunk
    (zero for a fresh row); conv_w [taps, D + 2 G N], conv_b [D + 2 G
    N]; A, skip [H] float32; norm_w [D]; state, ids, layer, fresh as
    ``ssd_scan``; dtype what the products take and ``out_proj`` reads.
    -> (y [B, T, D] dtype, the pool updated in place).

    Jitted under its shapes, so the blocks of a model trace the kernel
    once and not a block each (as ops/pallas_paged.py's calls)."""
    return _chunk_mix(zxd, dt, prev, conv_w, conv_b, A, skip, norm_w, state,
                      ids, layer, fresh, eps=eps, dtype=jnp.dtype(dtype),
                      interpret=pallas_paged.needs_interpret())


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "interpret"))
def _chunk_mix(zxd, dt, prev, conv_w, conv_b, A, skip, norm_w, state, ids,
               layer, fresh, *, eps: float, dtype, interpret: bool):
    B, T, _ = zxd.shape
    H, N, D = dt.shape[-1], state.shape[2], state.shape[3]
    ch, taps, f32 = prev.shape[-1], conv_w.shape[0], jnp.float32
    G = (ch - D) // (2 * N)
    hd, tile, Hg, Q = D // H, D // G, H // G, CHUNK
    assert taps + 3 <= _VEC and taps - 1 <= _HALO, taps
    with jax.named_scope(CHUNKED):
        pad = (-T) % Q
        if pad:     # dt = 0: the padding advances nothing
            zxd, dt = (jnp.pad(m, ((0, 0), (0, pad), (0, 0)))
                       for m in (zxd, dt))
        chunks = (T + pad) // Q
        # a head's dt and the running sum of dt A inside each chunk
        dt = dt.reshape(B, chunks, Q, G, Hg)
        cs = jnp.cumsum(dt * A.reshape(G, Hg), axis=2)
        cols = jnp.moveaxis(jnp.concatenate([cs, dt], axis=-1), 3, 2)
        rows = jnp.transpose(cs, (0, 1, 3, 4, 2))     # [B, n, G, Hg, Q]
        # the taps, the bias, and on x's columns D and the norm's weight
        on_x = jnp.stack([jnp.repeat(skip, hd), norm_w.astype(f32)])
        vec = jnp.concatenate([
            conv_w.astype(f32), conv_b.astype(f32)[None],
            jnp.pad(on_x, ((0, _VEC - taps - 3), (0, ch - D)))])
        before = jnp.pad(prev.astype(f32),
                         ((0, 0), (_HALO - taps + 1, 0), (0, 0)))

        # x's, B's and C's column blocks of an array [.., D + 2 G N]
        # wide (x in blocks of ``tile``, B and C of N), ``lead`` more
        # columns before them
        def blocks(rows_, index, lead=0):
            return [pl.BlockSpec(rows_ + (w,), functools.partial(
                index, at=(lead + off) // w))
                for w, off in ((tile, 0), (N, D), (N, D + G * N))]

        def chunk(j, b, n, *_, at):
            return b, n, at + j

        def row(j, b, n, *_, at):
            return b, 0, at + j

        def whole(j, b, n, *_, at):
            return 0, at + j

        def by_group(*shape):
            return pl.BlockSpec((1, 1, 1) + shape,
                                lambda j, b, n, *_: (b, n, j, 0, 0))
        out = pl.BlockSpec((1, Q, tile), lambda j, b, n, *_: (b, n, j))
        y, state = pl.pallas_call(
            functools.partial(_chunk_kernel, head_dim=hd, chunks=chunks,
                              taps=taps, eps=eps, cdt=dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(G, B, chunks),
                in_specs=[out, *blocks((1, Q), chunk, lead=D),
                          *blocks((1, _HALO), row),
                          *blocks((_VEC,), whole),
                          by_group(Q, 2 * Hg), by_group(Hg, Q),
                          _page(N, tile)],
                out_specs=[out, _page(N, tile)],
                scratch_shapes=[pltpu.VMEM((N, tile), f32),
                                pltpu.VMEM((_HALO, tile + 2 * N), f32),
                                pltpu.VMEM((Q, tile), f32)]),
            out_shape=[jax.ShapeDtypeStruct((B, T + pad, D), dtype),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # operands count the scalar-prefetch arguments: the pool
            # is the sixteenth, and the second result
            input_output_aliases={15: 1},
            **_call_params(CHUNKED, 3, interpret),
        )(*_prefetch(ids, layer, fresh), zxd, zxd, zxd, zxd, before,
          before, before, vec, vec, vec, cols, rows, state)
        return y[:, :T], state
