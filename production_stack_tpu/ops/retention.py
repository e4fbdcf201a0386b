"""Power retention of degree 2: gated power attention over a state page.

A power retention layer (Brumby, ``brumby``; Manifest AI,
arXiv:2507.04239) keeps, a sequence and key-value head, ONE state in
float32 whatever the context: ``S [F, D]`` and the normaliser ``z [F]``
over the ``F = D (D + 1) / 2`` monomials of the SYMMETRIC SQUARE of the
key, ``phi(a) . phi(b) = (a . b)^2`` (8256 for heads of 128). With the
token's gate ``g_t = exp(log g_t) in (0, 1]`` (one a key-value head),
key ``k_t``, value ``v_t`` and the queries ``q_t`` of the head's group
(scaled by the caller):

    S = g_t S + phi(k_t) v_t^T;   z = g_t z + phi(k_t)
    y_t = phi(q_t)^T S / (phi(q_t)^T z + eps)

``phi`` is never made in HBM. Its monomials are laid out by CIRCULAR
DISTANCE: row ``d`` (0 .. D/2) holds ``w_d x_i x_{(i - d) mod D}`` for
``i`` in 0 .. D-1 (``w_0 = 1``, else ``sqrt 2``; the last row, ``d =
D/2``, names every pair twice and keeps ``i < D/2``), which a kernel
makes from a row of ``x`` by one lane rotation and two products. The
state pool (models/kv.py) stores a head's ``S`` as ``[F, D]`` in tiles
of that layout: rows ``d D + v`` (d < D/2) hold ``S[d, v, i]`` with
``i`` on the lanes, and the last ``D/2`` rows the half tile, two values
``v`` and ``v + D/2`` a row (``_unpack_tail``); ``z`` as ``[F Hkv / D,
D]``, row ``d Hkv + j`` head j's ``z[d, :]`` and the last ``Hkv / 2``
rows the half rows of heads ``r`` and ``r + Hkv/2`` side by side. Both
are exactly the ``F (D + 1)`` numbers a head keeps.

Three implementations, chosen by shape alone (``retention_path``), all
taking the state from and leaving it in its page, which a step program
carries as it carries a K/V pool:

``retention_window``  one position a row in a decode window that fuses
    W >= 4 steps (``windowed``): the rule re-associated over the
    window. With ``S0``, ``z0`` the page as the window found it,
    ``G_t`` the log-gates summed from the window's first step (all <=
    0) and ``(k_j, v_j)`` the window's own keys and values,

        num_t = exp(G_t) phi(q_t)^T S0 + sum_{j<=t} exp(G_t - G_j) (q_t . k_j)^2 v_j
        den_t = exp(G_t) phi(q_t)^T z0 + sum_{j<=t} exp(G_t - G_j) (q_t . k_j)^2
        y_t = num_t / (den_t + eps)

    and after the last step ``S = exp(G_W) S0 + sum_j exp(G_W - G_j)
    phi(k_j) v_j^T``, ``z`` alike: the state is READ W times and
    written once (W + 2 pages a row for the recurrent form's 2 W). A
    step (``retain_in_window``) is the kernel ``retention_recurrent_
    step`` in a form that copies a head's ``S`` in, multiplies it by
    the group's queries and copies nothing back, plus the two sums over
    the window's keys in ``jax.numpy`` (W x G dot products a head, at
    full precision); the window's k, v and ``G`` (``Window``, float32,
    10.5 MB at Brumby's cut) ride the step scan's carry and never
    leave the executable; ``fold_window`` after the scan is the kernel
    ``retention_window_fold``, a grid step a (layer, row, head): ``S``
    copied in, decayed, the W outer products added on the VPU, copied
    back to the SAME page. At every executable boundary a page holds
    ``S`` and ``z`` of the recurrent rule after the row's last token.
``retention_recurrent``  else up to DECODE_T_MAX positions a row (a
    decode window of 1 or 2 steps, a short forward): the rule as
    written, and what the tests hold the window form to. On the TPU
    the kernel ``retention_recurrent_step`` in the form that writes, a
    grid step a (row, key-value head):
    the head's 4.2 MB of ``S`` are copied in, decayed, updated and
    multiplied by the group's queries on the VPU, eight values of ``v``
    against the 128 lanes of ``i`` a register (so that a monomial row
    broadcasts along sublanes, for free in its load), and copied back
    to the SAME page; ONE read of the group's ``S`` serves its queries.
``retention_chunk``  longer (a prefill chunk): the same sum a CHUNK of
    tokens at a time. Within a chunk the attention form, ``a_ts =
    exp(G_t - G_s) (q_t . k_s)^2`` for ``s <= t`` (``G`` the log-gates
    summed from the chunk's first token; only differences ``<= 0`` are
    exponentiated); across chunks ``S`` and ``z``: on the TPU the kernel
    ``retention_chunk_scan``, a grid step a (row, head, chunk) with the
    head's state in VMEM from the page's copy-in at the first chunk to
    its copy-back at the last, a monomial row ``d`` of the whole chunk
    against the state's tile ``d`` on the MXU. Operands as the inputs
    come (bfloat16 on the chip), float32 products, the state float32
    throughout.

A chunk whose first position is 0 (``fresh``) starts from a zero state
inside the kernel: no page is ever cleared by the host. Positions that
are not real advance nothing: the caller hands them ``log g = 0`` and
``k = 0``, and a row that is not real names the trash page.

Where the kernels are off (``pallas_paged.flash_enabled``: the CPU) or
the shapes are not theirs (heads of 128, key-value heads in eights) the
same three forms run in ``jax.numpy`` under names that end in ``_jnp``;
tests/test_retention.py holds each to the attention form of
chipbench/references/brumby.py and, in interpret mode, the kernels to
the ``jax.numpy`` forms.
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops import pallas_paged
from production_stack_tpu.ops.pallas_paged import DECODE_T_MAX

# tokens a chunk of the chunked form holds: the kernel's own constant.
# 128 makes the group's monomial rows a 640-row operand of the MXU at
# Brumby's 5 queries a key-value head; 64 and 256 were not measured
CHUNK = 128
EPS = 1e-6

RECURRENT = "retention_recurrent"
CHUNKED = "retention_chunk"
WINDOWED = "retention_window"
_SQRT2 = math.sqrt(2.0)
# registers of a head's tile (8 values of v against the lanes of i) that
# the window form's two kernels take through one trip of their loops,
# under one load of the monomial rows: independent chains for the
# vector unit. On the v5e, 16 rows of a layer (PERF.md, PR 48): the
# step that reads 1.78 / 1.05 / 0.86 / 0.86 ms at 1 / 2 / 4 / 8
# registers, the fold 2.43 / 1.77 / 1.71 ms at 1 / 2 / 4
READ_SUBS = 4
FOLD_SUBS = 4


def features(head_dim: int) -> int:
    """Monomials of the symmetric square of a head's key."""
    return head_dim * (head_dim + 1) // 2


def kernels_fit(head_dim: int, kv_heads: int) -> bool:
    """Are the shapes the kernels': whole lanes a head, key-value heads
    in whole registers."""
    return head_dim == 128 and kv_heads % 8 == 0


def pages_moved(steps: int, windowed: bool) -> int:
    """State pages a row's decode window of ``steps`` steps moves
    between HBM and the kernels, a layer: the recurrent form reads and
    writes the page every step; the window form reads it every step and
    its fold reads and writes it once."""
    return steps + 2 if windowed else 2 * steps


def windowed(T: int, steps: int) -> bool:
    """Does a decode window of ``steps`` steps of T positions a row
    take the window form: where that moves at most three quarters of
    the recurrent form's bytes (4 steps: 6 pages for 8; at 2 the two
    are level, and the fold is one more call)."""
    return T == 1 and 4 * pages_moved(steps, True) \
        <= 3 * pages_moved(steps, False)


def retention_path(T: int, head_dim: int = 128, kv_heads: int = 8,
                   steps: int = 1) -> str:
    """Which implementation a forward of T positions a row runs, in an
    executable that fuses ``steps`` of them (a decode window; 1: a
    prefill chunk, a forward alone): decided by shape, before anything
    compiles (a kernel the compiler then refuses is an error, not a
    reason to take the other)."""
    path = (WINDOWED if windowed(T, steps)
            else RECURRENT if T <= DECODE_T_MAX else CHUNKED)
    on = pallas_paged.flash_enabled() and kernels_fit(head_dim, kv_heads)
    return path if on else path + "_jnp"


# ---------------------------------------------------------------------
# the layout, in jax.numpy
# ---------------------------------------------------------------------

def _weights(D: int) -> jnp.ndarray:
    """w [D/2 + 1, D]: the monomials' weights by (distance, i)."""
    d = jnp.arange(D // 2 + 1)[:, None]
    i = jnp.arange(D)[None, :]
    w = jnp.where(d == 0, 1.0, _SQRT2)
    return jnp.where((d == D // 2) & (i >= D // 2), 0.0, w
                     ).astype(jnp.float32)


def phi_rows(x: jnp.ndarray) -> jnp.ndarray:
    """x [..., D] -> the monomials [..., D/2 + 1, D] by (distance, i);
    the D/2 entries beyond the last row's half are zero."""
    D = x.shape[-1]
    idx = (jnp.arange(D)[None, :] - jnp.arange(D // 2 + 1)[:, None]) % D
    return _weights(D) * x[..., None, :] * x[..., idx]


def _unpack_tail(t: jnp.ndarray) -> jnp.ndarray:
    """The half tile as stored, [..., n, D] (row r holds values r, lanes
    below D/2, and r + n, lanes above), -> a whole tile [..., 2n, D],
    zero beyond lane D/2."""
    h = t.shape[-1] // 2
    lo = jnp.concatenate([t[..., :h], t[..., h:]], axis=-2)
    return jnp.concatenate([lo, jnp.zeros_like(lo)], axis=-1)


def _pack_tail(u: jnp.ndarray) -> jnp.ndarray:
    n, h = u.shape[-2] // 2, u.shape[-1] // 2
    return jnp.concatenate([u[..., :n, :h], u[..., n:, :h]], axis=-1)


def unpack_state(S: jnp.ndarray, z: jnp.ndarray):
    """Pages as stored, S [B, Hkv, F, D] and z [B, F Hkv / D, D], ->
    (S [B, Hkv, D/2 + 1, D (v), D (i)], z [B, Hkv, D/2 + 1, D])."""
    B, H, _, D = S.shape
    h = D // 2
    tiles = S[:, :, :h * D].reshape(B, H, h, D, D)
    S = jnp.concatenate(
        [tiles, _unpack_tail(S[:, :, h * D:])[:, :, None]], axis=2)
    rows = z[:, :h * H].reshape(B, h, H, D).transpose(0, 2, 1, 3)
    z = jnp.concatenate(
        [rows, _unpack_tail(z[:, h * H:])[:, :, None]], axis=2)
    return S, z


def pack_state(S: jnp.ndarray, z: jnp.ndarray):
    """``unpack_state`` undone."""
    B, H, _, D, _ = S.shape
    h = D // 2
    S = jnp.concatenate([S[:, :, :h].reshape(B, H, h * D, D),
                         _pack_tail(S[:, :, h])], axis=2)
    z = jnp.concatenate(
        [z[:, :, :h].transpose(0, 2, 1, 3).reshape(B, h * H, D),
         _pack_tail(z[:, :, h])], axis=1)
    return S, z


# ---------------------------------------------------------------------
# the recurrent form
# ---------------------------------------------------------------------

def _recurrent_jnp(q, k, v, logg, state, norm, ids, layer, fresh):
    keep = jnp.where(fresh, 0.0, 1.0)
    S, z = unpack_state(state[layer, ids] * keep[:, None, None, None],
                        norm[layer, ids] * keep[:, None, None])
    ys = []
    for t in range(q.shape[1]):
        g = jnp.exp(logg[:, t])                          # [B,Hkv]
        pk = phi_rows(k[:, t])                           # [B,Hkv,d,i]
        S = (S * g[..., None, None, None]
             + v[:, t][:, :, None, :, None] * pk[:, :, :, None, :])
        z = z * g[..., None, None] + pk
        pq = phi_rows(q[:, t])                           # [B,Hkv,G,d,i]
        num = jnp.einsum("bhgdi,bhdvi->bhgv", pq, S)
        den = jnp.einsum("bhgdi,bhdi->bhg", pq, z)
        ys.append(num / (den[..., None] + EPS))
    S, z = pack_state(S, z)
    return (jnp.stack(ys, axis=1), state.at[layer, ids].set(S),
            norm.at[layer, ids].set(z))


def _tail_mask(shape, D: int) -> jnp.ndarray:
    """1.0 on the lanes below D/2, 0.0 above."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane < D // 2).astype(jnp.float32)


def _whole_tile(stored):
    """In a kernel: the half tile as stored, [D/2, D], -> a whole tile
    [D (v), D (i)], zero beyond lane D/2 (``_unpack_tail``)."""
    h, D = stored.shape
    return jnp.concatenate([stored, pltpu.roll(stored, h, axis=1)],
                           axis=0) * _tail_mask((D, D), D)


def _half_tile(tile):
    """``_whole_tile`` undone: [D, D] -> [D/2, D] as stored."""
    D = tile.shape[1]
    h = D // 2
    mask = _tail_mask((h, D), D)
    return tile[:h] * mask + pltpu.roll(tile[h:], h, axis=1) * (1.0 - mask)


def _recurrent_kernel(ids_ref, layer_ref, fresh_ref, q_ref, k_ref, v_ref,
                      dec_ref, s_ref, z_ref, num_ref, den_ref, so_ref,
                      zo_ref, pk_ref, pq_ref, acc_ref,
                      *, T: int, G: int, H: int, D: int):
    """One (row, key-value head): the head's ``S`` through T positions,
    and at the row's first head every head's ``z`` and monomial rows.

    q_ref [1, T, G, H, D], k_ref and dec_ref (the gate, along the
    lanes) [1, T, H, D]: a REGISTER holds one vector of every head, so
    that a rotation makes a monomial row of all of them; v_ref [1, T,
    1, D, 1] the head's values down the sublanes. pk_ref [T, (D/2 + 1)
    H, D] and pq_ref [T, G, ...]: the monomial rows, row ``d H + j``
    head j's. acc_ref [G, D, D]: a query's products before the lanes
    are summed."""
    b, j = pl.program_id(0), pl.program_id(1)
    h = D // 2
    keep = 1.0 - fresh_ref[b].astype(jnp.float32)
    lo = _tail_mask((1, D), D)

    @pl.when(j == 0)
    def _rows_and_norm():
        zo_ref[0, 0] = z_ref[0, 0] * keep
        row = jax.lax.broadcasted_iota(jnp.int32, (H, D), 0)
        for t in range(T):
            K, g = k_ref[0, t], dec_ref[0, t]
            Q = [q_ref[0, t, r] for r in range(G)]

            def distance(d, den):
                w = jnp.where(d == 0, 1.0, _SQRT2)
                at = pl.ds(pl.multiple_of(d * H, H), H)
                pk = K * pltpu.roll(K, d, axis=1) * w
                pk_ref[t, at, :] = pk
                zn = g * zo_ref[0, 0, at, :] + pk
                zo_ref[0, 0, at, :] = zn
                out = []
                for r in range(G):
                    pq = Q[r] * pltpu.roll(Q[r], d, axis=1) * w
                    pq_ref[t, r, at, :] = pq
                    out.append(den[r] + pq * zn)
                return tuple(out)
            den = jax.lax.fori_loop(
                0, h, distance,
                tuple(jnp.zeros((H, D), jnp.float32) for _ in range(G)))
            # the half row: heads r and r + H/2 share a stored row
            pk = K * pltpu.roll(K, h, axis=1) * (_SQRT2 * lo)
            pk_ref[t, h * H:, :] = pk
            pqs = [Q[r] * pltpu.roll(Q[r], h, axis=1) * (_SQRT2 * lo)
                   for r in range(G)]
            for r in range(G):
                pq_ref[t, r, h * H:, :] = pqs[r]
            den = list(den)
            for s in range(H // 2):
                stored = zo_ref[0, 0, h * H + s:h * H + s + 1, :]
                a = g[s:s + 1] * stored + pk[s:s + 1]
                c = (g[s + H // 2:s + H // 2 + 1] * stored
                     + pltpu.roll(pk[s + H // 2:s + H // 2 + 1], h, axis=1))
                zo_ref[0, 0, h * H + s:h * H + s + 1, :] = (
                    a * lo + c * (1.0 - lo))
                for head, zn in ((s, a * lo),
                                 (s + H // 2,
                                  pltpu.roll(c * (1.0 - lo), h, axis=1))):
                    for r in range(G):
                        den[r] = den[r] + jnp.where(
                            row == head, pqs[r][head:head + 1] * zn, 0.0)
            for r in range(G):
                den_ref[0, t, r] = den[r]

    # the head's tiles, a register (8 values of v against the lanes of
    # i) through every distance: the monomial rows ride in on loads
    # that broadcast along the sublanes
    gs = [dec_ref[0, t, pl.ds(j, 1), :] for t in range(T)]   # [1, D]
    vs = [jnp.broadcast_to(v_ref[0, t, 0], (D, D)) for t in range(T)]

    def advance(tile, rows, at, accs):
        """``tile`` [n, D] through the T positions at monomial row
        ``at`` (of head j); accs[t][r] gains the queries' products."""
        for t in range(T):
            pk = pk_ref[t, pl.ds(at, 1), :]
            tile = tile * gs[t] + rows[t] * pk
            for r in range(G):
                accs[t][r] = accs[t][r] + tile * pq_ref[t, r,
                                                        pl.ds(at, 1), :]
        return tile

    for sub in range(D // 8):
        at_v = slice(sub * 8, sub * 8 + 8)
        rows = [vs[t][at_v] for t in range(T)]

        def distance(d, flat):
            accs = [[flat[t * G + r] for r in range(G)] for t in range(T)]
            here = pl.ds(pl.multiple_of(d * D + sub * 8, 8), 8)
            so_ref[0, 0, 0, here, :] = advance(
                s_ref[0, 0, 0, here, :] * keep, rows, d * H + j, accs)
            return tuple(a for per_t in accs for a in per_t)
        flat = jax.lax.fori_loop(
            0, h, distance,
            tuple(jnp.zeros((8, D), jnp.float32) for _ in range(T * G)))
        for t in range(T):
            for r in range(G):
                acc_ref[t, r, at_v, :] = flat[t * G + r]
    # the half tile: value v in the lanes below D/2, v + D/2 above
    tile = _whole_tile(s_ref[0, 0, 0, h * D:, :] * keep)
    accs = [[jnp.zeros((D, D), jnp.float32) for _ in range(G)]
            for _ in range(T)]
    so_ref[0, 0, 0, h * D:, :] = _half_tile(
        advance(tile, vs, h * H + j, accs))
    # the lanes summed, a query's values back along the lanes: ones
    # against the accumulator's transpose, on the MXU at full precision
    ones = jnp.ones((8, D), jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (8, D), 0)
    for t in range(T):
        out = jnp.zeros((8, D), jnp.float32)
        for r in range(G):
            y = jax.lax.dot_general(
                ones, acc_ref[t, r] + accs[t][r],
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)          # [8, D (v)]
            out = jnp.where(row == r, y, out)
        num_ref[0, t, 0] = out


def _recurrent(q, k, v, logg, state, norm, ids, layer, fresh):
    """q [B, T, Hkv, G, D], k, v [B, T, Hkv, D], logg [B, T, Hkv], all
    float32 -> (y [B, T, Hkv, G, D] float32, the two pools)."""
    B, T, H, G, D = q.shape
    if retention_path(T, D, H).endswith("_jnp"):
        return _recurrent_jnp(q, k, v, logg, state, norm, ids, layer, fresh)
    if G > 8:
        raise ValueError(f"retention_recurrent_step serves at most 8 "
                         f"query heads a key-value head, not {G}")
    F, rows = state.shape[-2], (D // 2 + 1) * H

    def by_row(*tail):
        return lambda b, j, ids, lyr, fr: (b,) + tail
    page = pl.BlockSpec((1, 1, 1, F, D),
                        lambda b, j, ids, lyr, fr: (lyr[0], ids[b], j, 0, 0))
    zpage = pl.BlockSpec((1, 1, norm.shape[-2], D),
                         lambda b, j, ids, lyr, fr: (lyr[0], ids[b], 0, 0))
    heads = pl.BlockSpec((1, T, H, D), by_row(0, 0, 0))
    den_spec = pl.BlockSpec((1, T, G, H, D), by_row(0, 0, 0, 0))
    num, den, state, norm = pl.pallas_call(
        functools.partial(_recurrent_kernel, T=T, G=G, H=H, D=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, H),
            in_specs=[den_spec, heads,
                      pl.BlockSpec((1, T, 1, D, 1),
                                   lambda b, j, ids, lyr, fr:
                                   (b, 0, j, 0, 0)),
                      heads, page, zpage],
            out_specs=[pl.BlockSpec((1, T, 1, 8, D),
                                    lambda b, j, ids, lyr, fr:
                                    (b, 0, j, 0, 0)),
                       den_spec, page, zpage],
            scratch_shapes=[pltpu.VMEM((T, rows, D), jnp.float32),
                            pltpu.VMEM((T, G, rows, D), jnp.float32),
                            pltpu.VMEM((T, G, D, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, T, H, 8, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, G, H, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
        # operands count the scalar-prefetch arguments: the pools are
        # the eighth and ninth, the third and fourth results
        input_output_aliases={7: 2, 8: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
        interpret=pallas_paged.needs_interpret(),
        name="retention_recurrent_step",
    )(ids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32),
      q.transpose(0, 1, 3, 2, 4), k, v[..., None],
      jnp.broadcast_to(jnp.exp(logg)[..., None], k.shape), state, norm)
    den = jnp.sum(den, axis=-1).transpose(0, 1, 3, 2)        # [B,T,H,G]
    return num[:, :, :, :G] / (den[..., None] + EPS), state, norm


# ---------------------------------------------------------------------
# the window form
# ---------------------------------------------------------------------

class Window(NamedTuple):
    """What a decode window of W steps keeps beside the pages, from its
    first step to its fold: it is made inside the executable and gone
    at its end, so that a page holds ``S`` and ``z`` alone wherever the
    host can see one."""
    k: jnp.ndarray      # [L, B, W, Hkv, D] float32; 0: not real, not yet
    v: jnp.ndarray      # [L, B, W, Hkv, D] float32
    G: jnp.ndarray      # [L, B, W, Hkv]: log-gates summed from step 0
    ids: jnp.ndarray    # [B] the rows' pages, as of the first step
    keep: jnp.ndarray   # [B] float32: 0.0 where the row started at 0
    step: jnp.ndarray   # int32: steps taken


def open_window(layers: int, steps: int, kv_heads: int, head_dim: int,
                ids: jnp.ndarray, fresh: jnp.ndarray) -> Window:
    """ids [B]: the rows' pages (the trash page for a row that is not
    real at the window's first step); fresh [B] bool: the row's first
    position is 0 (positions advance by one a step, so no later one
    is), and it starts from a zero state."""
    B = ids.shape[0]
    kv = jnp.zeros((layers, B, steps, kv_heads, head_dim), jnp.float32)
    return Window(kv, kv, jnp.zeros(kv.shape[:-1], jnp.float32),
                  ids.astype(jnp.int32), jnp.where(fresh, 0.0, 1.0),
                  jnp.int32(0))


def _read_jnp(q, state, norm, ids, layer):
    S, z = unpack_state(state[layer, ids], norm[layer, ids])
    pq = phi_rows(q)                                     # [B,Hkv,G,d,i]
    return (jnp.einsum("bhgdi,bhdvi->bhgv", pq, S),
            jnp.einsum("bhgdi,bhdi->bhg", pq, z))


def _read_kernel(ids_ref, layer_ref, q_ref, s_ref, z_ref, num_ref,
                 den_ref, pq_ref, acc_ref, *, G: int, H: int, D: int):
    """One (row, key-value head) of a step that only READS: the head's
    ``S`` against the group's queries' monomial rows, and at the row's
    first head every head's ``z`` against them. Nothing decays, nothing
    is added, no tile is stored.

    q_ref [1, G, H, D]: a register holds one query of every head (as
    ``_recurrent_kernel``); pq_ref [G, (D/2 + 1) H, D] the monomial
    rows, row ``d H + j`` head j's; acc_ref [G, D, D] a query's
    products before the lanes are summed."""
    j = pl.program_id(1)
    h = D // 2
    lo = _tail_mask((1, D), D)

    @pl.when(j == 0)
    def _rows_and_norm():
        row = jax.lax.broadcasted_iota(jnp.int32, (H, D), 0)
        Q = [q_ref[0, r] for r in range(G)]

        def distance(d, den):
            w = jnp.where(d == 0, 1.0, _SQRT2)
            at = pl.ds(pl.multiple_of(d * H, H), H)
            zs = z_ref[0, 0, at, :]
            out = []
            for r in range(G):
                pq = Q[r] * pltpu.roll(Q[r], d, axis=1) * w
                pq_ref[r, at, :] = pq
                out.append(den[r] + pq * zs)
            return tuple(out)
        den = list(jax.lax.fori_loop(
            0, h, distance,
            tuple(jnp.zeros((H, D), jnp.float32) for _ in range(G))))
        # the half row: heads s and s + H/2 share a stored row
        pqs = [Q[r] * pltpu.roll(Q[r], h, axis=1) * (_SQRT2 * lo)
               for r in range(G)]
        for r in range(G):
            pq_ref[r, h * H:, :] = pqs[r]
        for s in range(H // 2):
            stored = z_ref[0, 0, h * H + s:h * H + s + 1, :]
            for head, zs in ((s, stored * lo),
                             (s + H // 2,
                              pltpu.roll(stored * (1.0 - lo), h, axis=1))):
                for r in range(G):
                    den[r] = den[r] + jnp.where(
                        row == head, pqs[r][head:head + 1] * zs, 0.0)
        for r in range(G):
            den_ref[0, r] = den[r]

    # READ_SUBS registers of the head's tile a load of the G monomial
    # rows (which broadcast along the sublanes in their loads)
    group = 8 * READ_SUBS
    for sg in range(D // group):
        def distance(d, flat):
            rows = [pq_ref[r, pl.ds(d * H + j, 1), :] for r in range(G)]
            flat = list(flat)
            for s in range(READ_SUBS):
                here = pl.ds(
                    pl.multiple_of(d * D + sg * group + s * 8, 8), 8)
                tile = s_ref[0, 0, 0, here, :]
                for r in range(G):
                    flat[r * READ_SUBS + s] += tile * rows[r]
            return tuple(flat)
        flat = jax.lax.fori_loop(
            0, h, distance,
            tuple(jnp.zeros((8, D), jnp.float32)
                  for _ in range(G * READ_SUBS)))
        for r in range(G):
            for s in range(READ_SUBS):
                at_v = sg * group + s * 8
                acc_ref[r, at_v:at_v + 8, :] = flat[r * READ_SUBS + s]
    # the half tile: value v in the lanes below D/2, v + D/2 above
    tile = _whole_tile(s_ref[0, 0, 0, h * D:, :])
    # the lanes summed, a query's values back along the lanes: ones
    # against the accumulator's transpose, on the MXU at full precision
    ones = jnp.ones((8, D), jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (8, D), 0)
    out = jnp.zeros((8, D), jnp.float32)
    for r in range(G):
        half = tile * pq_ref[r, pl.ds(h * H + j, 1), :]
        y = jax.lax.dot_general(
            ones, acc_ref[r] + half, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)              # [8, D (v)]
        out = jnp.where(row == r, y, out)
    num_ref[0, 0] = out


def _read(q, state, norm, ids, layer):
    """q [B, Hkv, G, D] float32 against the rows' pages as they stand:
    -> (phi(q)^T S [B, Hkv, G, D], phi(q)^T z [B, Hkv, G])."""
    B, H, G, D = q.shape
    if retention_path(1, D, H).endswith("_jnp"):
        return _read_jnp(q, state, norm, ids, layer)
    if G > 8:
        raise ValueError(f"retention_recurrent_step serves at most 8 "
                         f"query heads a key-value head, not {G}")
    F, rows = state.shape[-2], (D // 2 + 1) * H
    den_spec = pl.BlockSpec((1, G, H, D), lambda b, j, ids, lyr: (b, 0, 0, 0))
    num, den = pl.pallas_call(
        functools.partial(_read_kernel, G=G, H=H, D=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H),
            in_specs=[den_spec,
                      pl.BlockSpec((1, 1, 1, F, D),
                                   lambda b, j, ids, lyr:
                                   (lyr[0], ids[b], j, 0, 0)),
                      pl.BlockSpec((1, 1, norm.shape[-2], D),
                                   lambda b, j, ids, lyr:
                                   (lyr[0], ids[b], 0, 0))],
            out_specs=[pl.BlockSpec((1, 1, 8, D),
                                    lambda b, j, ids, lyr: (b, j, 0, 0)),
                       den_spec],
            scratch_shapes=[pltpu.VMEM((G, rows, D), jnp.float32),
                            pltpu.VMEM((G, D, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, 8, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, G, H, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
        interpret=pallas_paged.needs_interpret(),
        # the name a decode executable is told by, one call a layer and
        # step (chipbench: the configuration's harness.decode_step)
        name="retention_recurrent_step",
    )(ids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q.transpose(0, 2, 1, 3), state, norm)
    return num[:, :, :G], jnp.sum(den, axis=-1).transpose(0, 2, 1)


def retain_in_window(q, k, v, logg, keys, values, gates, window: Window,
                     state, norm, layer):
    """One step of a decode window, the pages only READ: q [B, 1, H, D]
    (scaled), k (ZERO where the position is not real), v [B, 1, Hkv, D],
    logg [B, 1, Hkv] float32 (0 where it is not real); keys, values [B,
    W, Hkv, D] and gates [B, W, Hkv]: this layer's slices of ``window``
    as the steps before left them. With ``S0``, ``z0`` the page and
    ``G_t`` the log-gates summed from the window's first step to this
    one,

        num = exp(G_t) phi(q)^T S0 + sum_{j<=t} exp(G_t - G_j) (q . k_j)^2 v_j
        den = exp(G_t) phi(q)^T z0 + sum_{j<=t} exp(G_t - G_j) (q . k_j)^2

    which is the recurrent rule's quotient, re-associated; everything
    float32, the dot products at full precision. -> (y [B, 1, H, D]
    float32, the three slices with this step's k, v and G_t in)."""
    B, _, H, D = q.shape
    Hkv, W = k.shape[2], keys.shape[1]
    f32, exact = jnp.float32, jax.lax.Precision.HIGHEST
    step = window.step
    q = q[:, 0].reshape(B, Hkv, H // Hkv, D).astype(f32)
    before = jax.lax.dynamic_index_in_dim(
        gates, jnp.maximum(step - 1, 0), axis=1, keepdims=False)
    Gt = jnp.where(step > 0, before, 0.0) + logg[:, 0]       # [B, Hkv]
    keys, values, gates = (
        jax.lax.dynamic_update_slice_in_dim(a, x.astype(f32), step, axis=1)
        for a, x in ((keys, k), (values, v), (gates, Gt[:, None])))
    # exp of differences only, and only where they are <= 0
    taken = (jnp.arange(W) <= step)[None, :, None]
    decay = jnp.where(taken, jnp.exp(jnp.where(
        taken, Gt[:, None] - gates, 0.0)), 0.0)              # [B, W, Hkv]
    scores = jnp.einsum("bhgd,bwhd->bhgw", q, keys, precision=exact)
    a = scores * scores * decay.transpose(0, 2, 1)[:, :, None, :]
    num = jnp.einsum("bhgw,bwhd->bhgd", a, values, precision=exact)
    den = jnp.sum(a, axis=-1)
    num0, den0 = _read(q, state, norm, window.ids, layer)
    base = window.keep[:, None] * jnp.exp(Gt)                # [B, Hkv]
    y = ((base[..., None, None] * num0 + num)
         / ((base[..., None] * den0 + den)[..., None] + EPS))
    return y.reshape(B, 1, H, D), keys, values, gates


def _fold_operands(window: Window):
    """-> (ku [L, B, W, Hkv, D]: k_j times exp((G_W - G_j) / 2), the
    monomials are quadratic, so they carry exp(G_W - G_j); decay [L, B,
    Hkv]: keep exp(G_W), what is left of the page's own state)."""
    last = window.G[:, :, -1]
    ku = window.k * jnp.exp(0.5 * (last[:, :, None] - window.G))[..., None]
    return ku, window.keep[None, :, None] * jnp.exp(last)


def _fold_jnp(ku, v, decay, state, norm, ids):
    L, B, W, H, D = ku.shape
    S, z = unpack_state(state[:, ids].reshape((L * B,) + state.shape[2:]),
                        norm[:, ids].reshape((L * B,) + norm.shape[2:]))
    pk = phi_rows(ku.reshape(L * B, W, H, D))            # [n,W,Hkv,d,i]
    decay = decay.reshape(L * B, H)
    S = (S * decay[..., None, None, None]
         + jnp.einsum("nwhv,nwhdi->nhdvi", v.reshape(L * B, W, H, D), pk))
    z = z * decay[..., None, None] + jnp.sum(pk, axis=1)
    S, z = pack_state(S, z)
    return (state.at[:, ids].set(S.reshape((L, B) + S.shape[1:])),
            norm.at[:, ids].set(z.reshape((L, B) + z.shape[1:])))


def _fold_kernel(ids_ref, ku_ref, v_ref, dec_ref, s_ref, z_ref, so_ref,
                 zo_ref, pk_ref, *, W: int, H: int, D: int):
    """One (layer, row, key-value head): the head's ``S`` decayed by
    the window's gates and the window's W outer products added, copied
    back to the SAME page; at the row's first head every head's ``z``
    alike.

    ku_ref [1, 1, W, H, D] and dec_ref [1, 1, H, D] (the decay, along
    the lanes): a register holds one vector of every head; v_ref [1, 1,
    1, W, D] the head's values, a step a row; pk_ref [W, (D/2 + 1) H,
    D] the keys' monomial rows, row ``d H + j`` head j's."""
    j = pl.program_id(2)
    h = D // 2
    lo = _tail_mask((1, D), D)

    @pl.when(j == 0)
    def _rows_and_norm():
        a = dec_ref[0, 0]
        K = [ku_ref[0, 0, t] for t in range(W)]

        def distance(d, _):
            w = jnp.where(d == 0, 1.0, _SQRT2)
            at = pl.ds(pl.multiple_of(d * H, H), H)
            zs = a * z_ref[0, 0, at, :]
            for t in range(W):
                pk = K[t] * pltpu.roll(K[t], d, axis=1) * w
                pk_ref[t, at, :] = pk
                zs = zs + pk
            zo_ref[0, 0, at, :] = zs
            return 0
        jax.lax.fori_loop(0, h, distance, 0)
        # the half row: heads s and s + H/2 share a stored row
        total = jnp.zeros((H, D), jnp.float32)
        for t in range(W):
            pk = K[t] * pltpu.roll(K[t], h, axis=1) * (_SQRT2 * lo)
            pk_ref[t, h * H:, :] = pk
            total = total + pk
        for s in range(H // 2):
            u = s + H // 2
            stored = z_ref[0, 0, h * H + s:h * H + s + 1, :]
            lower = a[s:s + 1] * stored + total[s:s + 1]
            upper = (a[u:u + 1] * stored
                     + pltpu.roll(total[u:u + 1], h, axis=1))
            zo_ref[0, 0, h * H + s:h * H + s + 1, :] = (
                lower * lo + upper * (1.0 - lo))

    g = dec_ref[0, 0, pl.ds(j, 1), :]                        # [1, D]
    # a step's values down the sublanes, the same in every lane: its
    # row broadcast along the sublanes, transposed (as an operand [D,
    # 1] a value would take a whole tile of HBM)
    vs = [jnp.broadcast_to(v_ref[0, 0, 0, t:t + 1, :], (D, D)).T
          for t in range(W)]
    group = 8 * FOLD_SUBS
    for sg in range(D // group):
        mine = [[vs[t][sg * group + s * 8:sg * group + s * 8 + 8]
                 for s in range(FOLD_SUBS)] for t in range(W)]

        def distance(d, _):
            rows = [pk_ref[t, pl.ds(d * H + j, 1), :] for t in range(W)]
            for s in range(FOLD_SUBS):
                here = pl.ds(
                    pl.multiple_of(d * D + sg * group + s * 8, 8), 8)
                tile = s_ref[0, 0, 0, here, :] * g
                for t in range(W):
                    tile = tile + mine[t][s] * rows[t]
                so_ref[0, 0, 0, here, :] = tile
            return 0
        jax.lax.fori_loop(0, h, distance, 0)
    # the half tile: value v in the lanes below D/2, v + D/2 above
    tile = _whole_tile(s_ref[0, 0, 0, h * D:, :]) * g
    for t in range(W):
        tile = tile + vs[t] * pk_ref[t, pl.ds(h * H + j, 1), :]
    so_ref[0, 0, 0, h * D:, :] = _half_tile(tile)


def fold_window(window: Window, state: jnp.ndarray, norm: jnp.ndarray):
    """The window's end: every layer's pages of the window's rows take
    the rank-W update, ``S = keep exp(G_W) S0 + sum_j exp(G_W - G_j)
    phi(k_j) v_j^T`` and ``z`` alike, in place: what W steps of the
    recurrent rule would have left there. -> the two pools."""
    L, B, W, H, D = window.k.shape
    with jax.named_scope("ret_fold"):
        ku, decay = _fold_operands(window)
        if retention_path(1, D, H).endswith("_jnp"):
            return _fold_jnp(ku, window.v, decay, state, norm, window.ids)
        F = state.shape[-2]
        page = pl.BlockSpec((1, 1, 1, F, D),
                            lambda l, b, j, ids: (l, ids[b], j, 0, 0))
        zpage = pl.BlockSpec((1, 1, norm.shape[-2], D),
                             lambda l, b, j, ids: (l, ids[b], 0, 0))
        return pl.pallas_call(
            functools.partial(_fold_kernel, W=W, H=H, D=D),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(L, B, H),
                in_specs=[pl.BlockSpec((1, 1, W, H, D),
                                       lambda l, b, j, ids: (l, b, 0, 0, 0)),
                          pl.BlockSpec((1, 1, 1, W, D),
                                       lambda l, b, j, ids:
                                       (l, b, j, 0, 0)),
                          pl.BlockSpec((1, 1, H, D),
                                       lambda l, b, j, ids: (l, b, 0, 0)),
                          page, zpage],
                out_specs=[page, zpage],
                scratch_shapes=[
                    pltpu.VMEM((W, (D // 2 + 1) * H, D), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
            # operands count the scalar-prefetch argument: the pools are
            # the fifth and sixth
            input_output_aliases={4: 0, 5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
            interpret=pallas_paged.needs_interpret(),
            name="retention_window_fold",
        )(window.ids, ku, window.v.transpose(0, 1, 3, 2, 4),
          jnp.broadcast_to(decay[..., None], decay.shape + (D,)), state,
          norm)


# ---------------------------------------------------------------------
# the chunked form
# ---------------------------------------------------------------------

def _chunk_operands(q, k, v, logg):
    """The per-chunk operands, every chunk at once. q [B, T, Hkv, G,
    D], k, v [B, T, Hkv, D] in the activations' dtype, logg [B, T, Hkv]
    float32, T a multiple of CHUNK -> q, qe [B, Hkv, N, G C, D] (rows
    by query, then token; qe times exp(G_t / 2): the monomials are
    quadratic, so they carry exp(G_t)), k, ku, v [B, Hkv, N, C, D] (ku
    times exp((G_C - G_s) / 2)), vT [B, Hkv, N, D, C], decay [B, Hkv,
    N, C, C] float32 (exp(G_t - G_s) at s <= t, else 0), last [B, Hkv,
    N] float32 = exp(G_C)."""
    B, T, H, G, D = q.shape
    dt, N, C = q.dtype, T // CHUNK, CHUNK

    def chunks(x):          # [B, T, H, ...] -> [B, H, N, C, ...]
        return jnp.moveaxis(x.reshape((B, N, C) + x.shape[2:]), 3, 1)
    q, k, v, logg = chunks(q), chunks(k), chunks(v), chunks(logg)
    Gs = jnp.cumsum(logg, axis=-1)                           # [B,H,N,C]
    i = jnp.arange(C)
    lower = i[:, None] >= i[None, :]
    # exp of differences only, and only where they are <= 0
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, Gs[..., :, None] - Gs[..., None, :], 0.0)), 0.0)
    qe = (q * jnp.exp(0.5 * Gs)[..., None, None]).astype(dt)
    ku = (k * jnp.exp(0.5 * (Gs[..., -1:] - Gs))[..., None]).astype(dt)

    def by_query(x):        # [B,H,N,C,G,D] -> [B,H,N,G*C,D]
        return jnp.swapaxes(x, 3, 4).reshape(B, H, N, G * C, D)
    return (by_query(q), by_query(qe), k, ku, v, jnp.swapaxes(v, -1, -2),
            decay, jnp.exp(Gs[..., -1]))


def _scan_jnp(q, qe, k, ku, v, decay, last, state, norm, ids, layer,
              fresh):
    B, H, N, GC, D = q.shape
    C, f32 = k.shape[3], jnp.float32
    G = GC // C
    keep = jnp.where(fresh, 0.0, 1.0)
    S0, z0 = unpack_state(state[layer, ids] * keep[:, None, None, None],
                          norm[layer, ids] * keep[:, None, None])

    def step(carry, xs):
        S, z = carry
        q, qe, k, ku, v, decay, last = xs
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=f32)
        a = scores * scores * jnp.tile(decay, (1, 1, G, 1))
        pq = phi_rows(qe.astype(f32))                    # [B,H,GC,d,i]
        num = (jnp.einsum("bhqk,bhkv->bhqv", a.astype(q.dtype), v,
                          preferred_element_type=f32)
               + jnp.einsum("bhqdi,bhdvi->bhqv", pq.astype(q.dtype),
                            S.astype(q.dtype), preferred_element_type=f32))
        den = jnp.sum(a, axis=-1) + jnp.einsum("bhqdi,bhdi->bhq", pq, z)
        pk = phi_rows(ku.astype(f32))                    # [B,H,C,d,i]
        S = S * last[..., None, None, None] + jnp.einsum(
            "bhcv,bhcdi->bhdvi", v, pk.astype(q.dtype),
            preferred_element_type=f32)
        z = z * last[..., None, None] + jnp.sum(pk, axis=2)
        return (S, z), num / (den[..., None] + EPS)

    (S, z), y = jax.lax.scan(step, (S0, z0), tuple(
        jnp.moveaxis(x, 2, 0) for x in (q, qe, k, ku, v, decay, last)))
    S, z = pack_state(S, z)
    return (jnp.moveaxis(y, 0, 2), state.at[layer, ids].set(S),
            norm.at[layer, ids].set(z))


def _scan_kernel(ids_ref, layer_ref, fresh_ref, q_ref, qe_ref, k_ref,
                 ku_ref, v_ref, vt_ref, decay_ref, last_ref, s_ref, z_ref,
                 y_ref, so_ref, zo_ref, S, zs, num, den,
                 *, chunks: int, G: int, H: int, D: int):
    """One (row, head, chunk): the head's state stays in S [(D/2 + 1)
    D, D] (the half tile unpacked to a whole one) and zs [D/2 + 1, D]
    from the page's copy-in at the first chunk to its copy-back at the
    last. num, den [G C, D]: the chunk's sums before the quotient."""
    b, j, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    h, C = D // 2, k_ref.shape[3]
    f32, dt = jnp.float32, q_ref.dtype
    keep = 1.0 - fresh_ref[b].astype(f32)
    lo = _tail_mask((1, D), D)
    # head j's half row of z: stored row j mod H/2, its lower or upper
    # lanes
    half_row = h * H + j % (H // 2)
    upper = j >= H // 2

    @pl.when((j == 0) & (n == 0))
    def _norm_in():
        zo_ref[0, 0] = z_ref[0, 0] * keep

    @pl.when(n == 0)
    def _load():
        S[:h * D, :] = s_ref[0, 0, 0, :h * D, :] * keep
        S[h * D:, :] = _whole_tile(s_ref[0, 0, 0, h * D:, :] * keep)

        def row(d, _):
            zs[pl.ds(d, 1), :] = zo_ref[0, 0, pl.ds(d * H + j, 1), :]
            return 0
        jax.lax.fori_loop(0, h, row, 0)
        stored = zo_ref[0, 0, pl.ds(half_row, 1), :]
        zs[h:h + 1, :] = jnp.where(upper, pltpu.roll(stored, h, axis=1),
                                   stored) * lo

    # float32 operands (tests, tools) multiply at full precision, the
    # chip's bfloat16 in one pass: said here, so that a caller's
    # default_matmul_precision does not reach into the kernel
    exact = (jax.lax.Precision.HIGHEST if dt == f32
             else jax.lax.Precision.DEFAULT)

    def nt(a, c):
        return jax.lax.dot_general(a, c, (((1,), (1,)), ((), ())),
                                   precision=exact,
                                   preferred_element_type=f32)

    def nn(a, c):
        return jax.lax.dot_general(a, c, (((1,), (0,)), ((), ())),
                                   precision=exact,
                                   preferred_element_type=f32)
    # inside the chunk: the attention form
    scores = nt(q_ref[0, 0, 0], k_ref[0, 0, 0])              # [G C, C]
    a = scores * scores * jnp.concatenate([decay_ref[0, 0, 0]] * G, axis=0)
    num[...] = nn(a.astype(dt), v_ref[0, 0, 0])
    den[...] = jnp.zeros_like(den)
    inside = jnp.sum(a, axis=1, keepdims=True)               # [G C, 1]
    # across chunks: monomial row d of the whole chunk against tile d
    qe, ku, vt = (qe_ref[0, 0, 0].astype(f32), ku_ref[0, 0, 0].astype(f32),
                  vt_ref[0, 0, 0])
    g = last_ref[0, 0, pl.ds(n, 1), :]                       # [1, D]

    def distance(d, w, at):
        pq = qe * pltpu.roll(qe, d, axis=1) * w
        pk = ku * pltpu.roll(ku, d, axis=1) * w
        tile = S[at, :]                                      # [D (v), D (i)]
        num[...] += nt(pq.astype(dt), tile.astype(dt))
        den[...] += pq * zs[pl.ds(d, 1), :]
        S[at, :] = tile * g + nn(vt, pk.astype(dt))
        zs[pl.ds(d, 1), :] = (zs[pl.ds(d, 1), :] * g
                              + jnp.sum(pk, axis=0, keepdims=True))

    def body(d, _):
        distance(d, jnp.where(d == 0, 1.0, _SQRT2),
                 pl.ds(pl.multiple_of(d * D, D), D))
        return 0
    jax.lax.fori_loop(0, h, body, 0)
    distance(h, _SQRT2 * lo, slice(h * D, (h + 1) * D))
    total = inside + jnp.sum(den[...], axis=1, keepdims=True)
    y_ref[0, 0, 0] = num[...] / (total + EPS)

    @pl.when(n == chunks - 1)
    def _store():
        so_ref[0, 0, 0, :h * D, :] = S[:h * D, :]
        so_ref[0, 0, 0, h * D:, :] = _half_tile(S[h * D:, :])

        def row(d, _):
            zo_ref[0, 0, pl.ds(d * H + j, 1), :] = zs[pl.ds(d, 1), :]
            return 0
        jax.lax.fori_loop(0, h, row, 0)
        stored = zo_ref[0, 0, pl.ds(half_row, 1), :]
        mine = zs[h:h + 1, :] * lo
        zo_ref[0, 0, pl.ds(half_row, 1), :] = jnp.where(
            upper, stored * lo + pltpu.roll(mine, h, axis=1),
            mine + stored * (1.0 - lo))


def _chunked(q, k, v, logg, state, norm, ids, layer, fresh):
    """q [B, T, Hkv, G, D], k, v [B, T, Hkv, D] in the activations'
    dtype, logg [B, T, Hkv] float32 -> (y [B, T, Hkv, G, D] float32,
    the two pools)."""
    B, T, H, G, D = q.shape
    pad = (-T) % CHUNK
    if pad:     # log g = 0, k = 0: the padding advances nothing
        q, k, v, logg = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, logg))
    N, C = (T + pad) // CHUNK, CHUNK
    with jax.named_scope("ret_chunk_prep"):
        q, qe, k, ku, v, vT, decay, last = _chunk_operands(q, k, v, logg)
    if retention_path(T, D, H).endswith("_jnp"):
        y, state, norm = _scan_jnp(q, qe, k, ku, v, decay, last, state,
                                   norm, ids, layer, fresh)
    else:
        F = state.shape[-2]

        def at(b, j, n, ids, lyr, fr):
            return (b, j, n, 0, 0)
        page = pl.BlockSpec(
            (1, 1, 1, F, D),
            lambda b, j, n, ids, lyr, fr: (lyr[0], ids[b], j, 0, 0))
        zpage = pl.BlockSpec(
            (1, 1, norm.shape[-2], D),
            lambda b, j, n, ids, lyr, fr: (lyr[0], ids[b], 0, 0))
        y, state, norm = pl.pallas_call(
            functools.partial(_scan_kernel, chunks=N, G=G, H=H, D=D),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(B, H, N),
                in_specs=[pl.BlockSpec((1, 1, 1, G * C, D), at),
                          pl.BlockSpec((1, 1, 1, G * C, D), at),
                          pl.BlockSpec((1, 1, 1, C, D), at),
                          pl.BlockSpec((1, 1, 1, C, D), at),
                          pl.BlockSpec((1, 1, 1, C, D), at),
                          pl.BlockSpec((1, 1, 1, D, C), at),
                          pl.BlockSpec((1, 1, 1, C, C), at),
                          # a head's gates of every chunk, as rows
                          pl.BlockSpec(
                              (1, 1, N, D),
                              lambda b, j, n, ids, lyr, fr: (b, j, 0, 0)),
                          page, zpage],
                out_specs=[pl.BlockSpec((1, 1, 1, G * C, D), at), page,
                           zpage],
                scratch_shapes=[
                    pltpu.VMEM(((D // 2 + 1) * D, D), jnp.float32),
                    pltpu.VMEM((D // 2 + 8, D), jnp.float32),
                    pltpu.VMEM((G * C, D), jnp.float32),
                    pltpu.VMEM((G * C, D), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct((B, H, N, G * C, D),
                                            jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
            input_output_aliases={11: 1, 12: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=pallas_paged.VMEM_LIMIT_BYTES),
            interpret=pallas_paged.needs_interpret(),
            name="retention_chunk_scan",
        )(ids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
          fresh.astype(jnp.int32), q, qe, k, ku, v, vT, decay,
          jnp.broadcast_to(last[..., None], last.shape + (D,)), state,
          norm)
    # [B, H, N, G C, D] -> [B, T, H, G, D]
    y = y.reshape(B, H, N, G, C, D).transpose(0, 2, 4, 1, 3, 5)
    return y.reshape(B, N * C, H, G, D)[:, :T], state, norm


def retain(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           logg: jnp.ndarray, state: jnp.ndarray, norm: jnp.ndarray,
           ids: jnp.ndarray, layer, fresh: jnp.ndarray):
    """Power retention over T positions a row, from and to the rows'
    pages of layer ``layer`` of the state pools.

    q [B, T, H, D] (scaled; query head h reads key-value head h // (H /
    Hkv)), k (ZERO where the position is not real), v [B, T, Hkv, D],
    logg [B, T, Hkv] float32 (the log-gate, 0 where the position is not
    real); state [layers, pages, Hkv, F, D] and norm [layers, pages, F
    Hkv / D, D] float32 (module text); ids [B] the rows' pages (the
    trash page for a row that is not real); fresh [B] bool: the row
    starts at position 0, from a zero state. -> (y [B, T, H, D]
    float32, the two pools, updated in place)."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    q = q.reshape(B, T, Hkv, H // Hkv, D)
    if T <= DECODE_T_MAX:
        with jax.named_scope("ret_step"):
            f32 = jnp.float32
            y, state, norm = _recurrent(
                q.astype(f32), k.astype(f32), v.astype(f32), logg, state,
                norm, ids, layer, fresh)
    else:
        with jax.named_scope("ret_scan"):
            y, state, norm = _chunked(q, k, v, logg, state, norm, ids,
                                      layer, fresh)
    return y.reshape(B, T, H, D), state, norm
