"""Mamba's selective scan over a state page.

A Mamba layer (Gu and Dao, arXiv 2312.00752) keeps, a sequence, ONE
float32 state ``h [N (state), D (channels)]`` whatever the context.
With the token's input ``x_t [D]`` (after the convolution), step
``dt_t [D] > 0``, input and output maps ``B_t, C_t [N]`` and the
layer's ``A [N, D] < 0``:

    h = exp(dt_t * A) . h + (dt_t x_t) (x) B_t;   y_t = C_t . h

a DIAGONAL recurrence: every (state, channel) entry decays and is
written on its own, so the state is laid out with the channels on the
lanes, ``[N, D]``, and the rule is the vector unit's from end to end
(no matrix product in it).

Two kernels, one body (``_scan_kernel``), chosen by shape alone
(``mamba_path``), both taking the state from and leaving it in its page
of the state pool ``[layers, pages, N, D]`` (models/kv.py), which a
step program carries as it carries the K/V pool:

``mamba_recurrent_step``  T <= DECODE_T_MAX positions a row (a decode
    step): a grid step a (channel tile, row) copies the tile of the
    row's page in, runs the T positions and copies it back to the SAME
    page (the pool is aliased to the kernel's result); bound by
    ``rows x 2 x page bytes`` a layer.
``mamba_chunk_scan``  longer (a prefill chunk): the T positions in
    blocks of ``BLOCK`` tokens, a grid step a (channel tile, row,
    block), the tile of the state in VMEM from the page's copy-in at
    the first block to its copy-back at the last. ``exp(dt A)`` is made
    in registers a token and never written anywhere.

A chunk whose first position is 0 (``fresh``) starts from a zero state
inside the kernel: no page is ever cleared by the host. Positions that
are not real advance nothing: the caller hands them ``dt = 0`` (a decay
of one, a write of zero), and a row that is not real names the trash
page.

Where the kernels are off (``pallas_paged.flash_enabled``: the CPU) the
same rule runs as a ``lax.scan`` over tokens in ``jax.numpy``
(``mamba_recurrent_step_jnp`` / ``mamba_chunk_scan_jnp``);
tests/test_mamba.py holds the kernels, in interpret mode, to it.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops import pallas_paged
from production_stack_tpu.ops.pallas_paged import DECODE_T_MAX

# tokens a grid step of the chunk scan runs, unrolled: their B_t and C_t
# are columns of one [N, BLOCK] tile
BLOCK = 64
# channels (lanes) a grid step holds: [16, 1024] float32 is sixteen
# vector registers of state
_TILES = (1024, 512, 256, 128)

RECURRENT = "mamba_recurrent_step"
CHUNKED = "mamba_chunk_scan"


def mamba_path(T: int) -> str:
    """Which implementation a forward of T positions a row runs:
    decided by shape, before anything compiles; ``*_jnp`` where the
    kernels are off."""
    path = RECURRENT if T <= DECODE_T_MAX else CHUNKED
    return path if pallas_paged.flash_enabled() else path + "_jnp"


def _scan_kernel(ids_ref, layer_ref, fresh_ref, x_ref, dt_ref, bt_ref,
                 ct_ref, a_ref, s_ref, y_ref, so_ref, acc_ref, *,
                 tokens: int, blocks: int):
    """One (channel tile, row, token block). x_ref, dt_ref
    [1, tokens, tile] float32: a token's are a ROW, which broadcasts
    over the state's rows; bt_ref, ct_ref [1, 1, N, tokens]: a token's
    are a COLUMN, which broadcasts over the lanes. a_ref [N, tile];
    s_ref / so_ref [1, 1, N, tile]: the page's tile, in and out (the
    same bytes); acc_ref the state between blocks."""
    b, n = pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)
    def _load():
        acc_ref[...] = s_ref[0, 0] * (
            1.0 - fresh_ref[b].astype(jnp.float32))

    h, A = acc_ref[...], a_ref[...]
    for t in range(tokens):
        dt = dt_ref[0, t:t + 1, :]                           # [1, tile]
        h = (jnp.exp(dt * A) * h
             + (dt * x_ref[0, t:t + 1, :]) * bt_ref[0, 0, :, t:t + 1])
        y_ref[0, t:t + 1, :] = jnp.sum(h * ct_ref[0, 0, :, t:t + 1],
                                       axis=0, keepdims=True)
    acc_ref[...] = h

    @pl.when(n == blocks - 1)
    def _store():
        so_ref[0, 0] = h


def _scan_jnp(x, dt, Bm, Cm, A, state, ids, layer, fresh):
    h0 = jnp.where(fresh[:, None, None], 0.0, state[layer, ids])

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs            # [B, D], [B, D], [B, N] x 2
        h = (jnp.exp(dt_t[:, None, :] * A) * h
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    h, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), state.at[layer, ids].set(h)


def selective_scan(x: jnp.ndarray, dt: jnp.ndarray, Bm: jnp.ndarray,
                   Cm: jnp.ndarray, A: jnp.ndarray, state: jnp.ndarray,
                   ids: jnp.ndarray, layer, fresh: jnp.ndarray):
    """The selective scan over T positions a row, from and to the rows'
    pages of layer ``layer`` of the state pool.

    x, dt [B, T, D] float32 (dt 0 where the position is not real), Bm,
    Cm [B, T, N] float32, A [N, D] float32 (negative); state [layers,
    pages, N, D] float32; ids [B] the rows' pages (the trash page for a
    row that is not real); fresh [B] bool: the row starts at position
    0, from a zero state. -> (y [B, T, D] float32, WITHOUT the skip
    term D x; the pool, updated in place). Scopes mamba_recurrent_step
    / mamba_chunk_scan."""
    B, T, D = x.shape
    N = A.shape[0]
    path = mamba_path(T)
    with jax.named_scope(path.removesuffix("_jnp")):
        if path.endswith("_jnp"):
            return _scan_jnp(x, dt, Bm, Cm, A, state, ids, layer, fresh)
        tokens = T if path == RECURRENT else BLOCK
        pad = (-T) % tokens
        if pad:     # dt = 0: the padding advances nothing
            x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                             for a in (x, dt, Bm, Cm))
        blocks = (T + pad) // tokens
        tile = next((t for t in _TILES if D % t == 0), D)

        def cols(a):    # [B, T, N] -> [B, blocks, N, tokens]
            return a.reshape(B, blocks, tokens, N).swapaxes(2, 3)
        row = pl.BlockSpec((1, tokens, tile),
                           lambda j, b, n, ids, lyr, fr: (b, n, j))
        col = pl.BlockSpec((1, 1, N, tokens),
                           lambda j, b, n, ids, lyr, fr: (b, n, 0, 0))
        page = pl.BlockSpec(
            (1, 1, N, tile),
            lambda j, b, n, ids, lyr, fr: (lyr[0], ids[b], 0, j))
        y, state = pl.pallas_call(
            functools.partial(_scan_kernel, tokens=tokens, blocks=blocks),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(D // tile, B, blocks),
                in_specs=[row, row, col, col,
                          pl.BlockSpec((N, tile),
                                       lambda j, b, n, ids, lyr, fr: (0, j)),
                          page],
                out_specs=[row, page],
                scratch_shapes=[pltpu.VMEM((N, tile), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # operands count the scalar-prefetch arguments: the pool is
            # the ninth, and the second result
            input_output_aliases={8: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
            interpret=pallas_paged.needs_interpret(),
            name=path,
        )(ids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
          fresh.astype(jnp.int32), x, dt, cols(Bm), cols(Cm), A, state)
        return y[:, :T], state
