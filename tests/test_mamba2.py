"""ops/mamba2.py: the Mamba-2 mixer's two kernels, in interpret mode on
the CPU, against the ``jax.numpy`` form and against the token-by-token
recurrence as chipbench/references/nemotron_h.py writes it (a head's
state [head_dim, state], ONE decay a head, B and C by group), through
shuffled pages of a state pool of several layers, with a fresh row, a
carried state, chunk boundaries inside a call and a padded tail.

A decode step's kernel (``mamba2_recurrent_step``) is the scan alone;
a prefill chunk's (``mamba2_chunk_scan``, ``chunk_mix``) is the mixer
between its two projections: the convolution on ``in_proj``'s output,
the scan, the skip, the gate and the group norm. Both are held to the
UNFUSED form: ops/gdn.causal_conv, ``ssd_scan``'s ``jax.numpy`` scan,
then the gate and ops/norms.rms_norm, as models/llama._mamba2_mixer
writes it for every path but the fused one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops import gdn, mamba2, pallas_paged
from production_stack_tpu.ops.norms import rms_norm
from production_stack_tpu.ops.pallas_paged import DECODE_T_MAX

H, P, G, N = 8, 32, 2, 128      # heads, head_dim, groups, state
D = H * P
TAPS, EPS = 4, 1e-5


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(T, B=3, seed=0, real=None, heads=H, head_dim=P, groups=G,
            state=N):
    """What a mixer holds after ``in_proj``: its output ``zxd`` (columns
    z, x, every group's B, every group's C, dt), dt ready (0 where a
    position is not real), the convolution's page, the layer's
    parameters and a state pool of three layers."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    d = heads * head_dim
    ch = d + 2 * groups * state
    zxd = jax.random.normal(k[0], (B, T, d + ch + heads))
    valid = jnp.arange(T)[None, :] < jnp.asarray(
        [T] * B if real is None else real)[:, None]
    return dict(
        zxd=zxd, valid=valid,
        dt=jnp.where(valid[..., None],
                     jax.nn.softplus(zxd[..., d + ch:] - 2), 0.0),
        prev=jax.random.normal(k[1], (B, TAPS - 1, ch)),
        conv=jax.random.uniform(k[2], (TAPS, ch), minval=-0.5, maxval=0.5),
        bias=jax.random.uniform(k[3], (ch,), minval=-0.5, maxval=0.5),
        A=-jnp.exp(jax.random.normal(k[4], (heads,))),
        skip=jax.random.normal(k[5], (heads,)),
        norm=1 + 0.1 * jax.random.normal(k[6], (d,)),
        pool=jax.random.normal(k[7], (3, 7, state, d)),
        heads=heads, groups=groups, state=state)


def _conv(a, fresh, dtype=jnp.float32):
    """The unfused form up to the scan's operands: (x float32, x, B, C
    as the scan takes them, the convolution's new state)."""
    heads, g, n = a["heads"], a["groups"], a["state"]
    B, T, _ = a["zxd"].shape
    d = a["pool"].shape[-1]
    prev = jnp.where(fresh[:, None, None], 0, a["prev"])
    xbc, new = gdn.causal_conv(
        a["zxd"][..., d:d + d + 2 * g * n], a["conv"], prev,
        jnp.sum(a["valid"], axis=1, dtype=jnp.int32), bias=a["bias"])
    Bm, Cm = (xbc[..., d + j * g * n:d + (j + 1) * g * n].reshape(
        B, T, g, n).astype(dtype) for j in (0, 1))
    return xbc[..., :d], xbc[..., :d].astype(dtype), Bm, Cm, new


def _gate_norm(a, y, xs, dtype=jnp.float32):
    heads, g = a["heads"], a["groups"]
    B, T, d = xs.shape
    y = (y + jnp.repeat(a["skip"], d // heads) * xs) * jax.nn.silu(
        a["zxd"][..., :d])
    return rms_norm(y.reshape(B, T, g, d // g),
                    a["norm"].reshape(g, d // g), EPS).reshape(
                        B, T, d).astype(dtype)


def _mixer(a, ids, layer, fresh, on, dtype=jnp.float32):
    """The mixer between its projections with the kernels on or off ->
    (out_proj's input, the pool, the convolution's new state). On, a
    call longer than a decode step is ``chunk_mix``, the ONE kernel."""
    T, d = a["zxd"].shape[1], a["pool"].shape[-1]
    was = pallas_paged._override
    try:
        pallas_paged.set_flash_enabled(on)

        def run(a):
            if on and T > DECODE_T_MAX:
                prev = jnp.where(fresh[:, None, None], 0, a["prev"])
                y, pool = mamba2.chunk_mix(
                    a["zxd"], a["dt"], prev, a["conv"], a["bias"], a["A"],
                    a["skip"], a["norm"], EPS, a["pool"], ids, layer,
                    fresh, dtype)
                return y, pool, mamba2.conv_tail(
                    a["zxd"], d, prev,
                    jnp.sum(a["valid"], axis=1, dtype=jnp.int32))
            xs, x, Bm, Cm, new = _conv(a, fresh, dtype)
            y, pool = mamba2.ssd_scan(x, a["dt"], Bm, Cm, a["A"],
                                      a["pool"], ids, layer, fresh)
            return _gate_norm(a, y, xs, dtype), pool, new
        arrays = {k: v for k, v in a.items() if hasattr(v, "shape")}
        return jax.jit(lambda arrays: run({**a, **arrays}))(arrays)
    finally:
        pallas_paged.set_flash_enabled(was)


def _both(a, ids, layer, fresh):
    return [_mixer(a, ids, layer, fresh, on) for on in (False, True)]


def _worst(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _sequential(x, dt, Bm, Cm, A, h, dtype=jnp.float32):
    """One row, token by token, a head at a time as the reference
    writes it: h [heads, head_dim, state]; -> (y [T, D], h)."""
    heads = A.shape[0]
    per = heads // Bm.shape[1]
    ys = []
    for t in range(x.shape[0]):
        x_t = x[t].reshape(heads, -1)
        b_t, c_t = (jnp.repeat(m[t], per, axis=0) for m in (Bm, Cm))
        h = (jnp.exp(dt[t] * A)[:, None, None] * h
             + (dt[t][:, None] * x_t)[:, :, None] * b_t[:, None, :])
        h = h.astype(dtype).astype(jnp.float32)
        ys.append(jnp.sum(h * c_t[:, None, :], axis=-1).reshape(-1))
    return jnp.stack(ys), h


def _by_head(page, heads=H):
    """A page [state, D] (the channels on the lanes) as the reference
    holds it: [heads, head_dim, state]."""
    n, d = page.shape
    return page.T.reshape(heads, d // heads, n)


@pytest.mark.parametrize("T,real", [(1, None), (5, None), (8, None),
                                    (100, [100, 60, 1]),
                                    (128, None),
                                    (300, [300, 130, 129])])
def test_the_kernels_are_the_jnp_form_through_shuffled_pages(T, real):
    """T = 1, 5, 8: ``mamba2_recurrent_step``; 100 (shorter than the
    scan's chunk of 128, padded to it; a row of ONE real position, whose
    new convolution state is two rows of its page and one of the call),
    128 and 300 (three chunks, the last padded; the convolution's halo
    crosses both boundaries; a row whose real positions end one past a
    chunk boundary): ``mamba2_chunk_scan``, the mixer between its
    projections as one kernel. Pages 5, 2, 6 of layer 1 of a pool of
    three layers; row 1 fresh. Both forms leave every other page and
    layer as it was. 2e-4 on values of up to 12 (the normed output) and
    60 (the state): float32 against float32 with the sums in another
    order (1e-5 seen); the convolution's new state is the same bytes."""
    a = _inputs(T, real=real)
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([False, True, False])
    (y0, s0, c0), (y1, s1, c1) = _both(a, ids, 1, fresh)
    assert mamba2.mamba2_path(T, D, H, G, N).endswith("_jnp")
    assert _worst(y0, y1) < 2e-4
    assert _worst(s0, s1) < 2e-4
    assert np.array_equal(np.asarray(c0), np.asarray(c1))
    pool = a["pool"]
    keep = np.ones(pool.shape[:2], bool)
    keep[1, [5, 2, 6]] = False
    for s in (s0, s1):
        assert np.array_equal(np.asarray(s)[keep], np.asarray(pool)[keep])
    # against the recurrence written token by token, a head at a time
    xs, x, Bm, Cm, _ = _conv(a, fresh)
    ys = []
    for b in range(3):
        h0 = jnp.zeros((H, P, N)) if fresh[b] \
            else _by_head(pool[1, ids[b]])
        y, h = _sequential(x[b], a["dt"][b], Bm[b], Cm[b], a["A"], h0)
        ys.append(y)
        assert _worst(h, _by_head(s1[1, ids[b]])) < 5e-4
    assert _worst(_gate_norm(a, jnp.stack(ys), xs), y1) < 5e-4


def test_a_padded_tail_advances_nothing():
    """Row 1's 130 real positions of 300 (two positions into the second
    chunk): its page after the call is its page after those 130
    alone, and its convolution page their last three inputs."""
    a = _inputs(300, real=[300, 130, 129])
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([False, False, False])
    _, x, Bm, Cm, _ = _conv(a, fresh)
    for y, s, c in _both(a, ids, 0, fresh):
        _, h = _sequential(x[1, :130], a["dt"][1, :130], Bm[1, :130],
                           Cm[1, :130], a["A"], _by_head(a["pool"][0, 2]))
        assert _worst(h, _by_head(s[0, 2])) < 5e-4
        assert np.array_equal(np.asarray(c[1]),
                              np.asarray(a["zxd"][1, 127:130, D:D + 768]))


@pytest.mark.parametrize("cut", [160, 128, 2])
def test_the_state_carries_across_calls_as_across_chunks(cut):
    """A prompt in two calls (a dispatch boundary: the state goes to
    its page, the last three inputs to the convolution's, and both come
    back) reads as in one: cut inside a chunk, on a chunk boundary, and
    after two positions (the second call's halo is one row of the
    FIRST call's page and two of its inputs)."""
    a = _inputs(256, seed=5)
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([True, True, True])
    whole, s_whole, c_whole = _mixer(a, ids, 2, fresh, True)

    def part(lo, hi, pool, prev):
        return {**a, "zxd": a["zxd"][:, lo:hi], "dt": a["dt"][:, lo:hi],
                "valid": a["valid"][:, lo:hi], "pool": pool, "prev": prev}
    first, s, c = _mixer(part(0, cut, a["pool"], a["prev"]), ids, 2, fresh,
                         True)
    second, s, c = _mixer(part(cut, 256, s, c), ids, 2, ~fresh, True)
    assert _worst(jnp.concatenate([first, second], 1), whole) < 2e-4
    assert _worst(s, s_whole) < 2e-4
    assert np.array_equal(np.asarray(c), np.asarray(c_whole))


@pytest.mark.parametrize("heads,groups,rows", [(4, 1, 1), (64, 8, 2)])
def test_the_published_head_geometry_in_interpret_mode(heads, groups,
                                                       rows):
    """Heads of 64 over a state of 128 (two heads a vector of lanes,
    the state's rows a whole tile): one group of four heads, a quarter
    of a group; and the published mixer whole, 64 heads in 8 groups of
    512 channels, ``in_proj`` 10304 wide, two rows, the second fresh."""
    a = _inputs(140, B=rows, seed=2, heads=heads, head_dim=64,
                groups=groups, state=128)
    ids, fresh = jnp.array([3, 1][:rows]), jnp.array([False, True][:rows])
    (y0, s0, c0), (y1, s1, c1) = _both(a, ids, 0, fresh)
    assert a["zxd"].shape[-1] == (772 if heads == 4 else 10304)
    assert _worst(y0, y1) < 1e-3
    assert _worst(s0, s1) < 1e-3                        # values to 150
    assert np.array_equal(np.asarray(c0), np.asarray(c1))


def test_bfloat16_activations_round_where_the_unfused_form_does():
    """At the served precision (bfloat16 into the products and out to
    ``out_proj``, float32 between) the kernel stands as close to the
    unfused form at bfloat16 as that stands to itself at float32:
    ``L o C B^T`` is rounded where the ``jax.numpy`` scan rounds
    nothing, and no more."""
    a = _inputs(200, seed=4)
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([False, True, False])
    bf = jnp.bfloat16
    y32, s32, _ = _mixer(a, ids, 0, fresh, False)
    y0, s0, _ = _mixer(a, ids, 0, fresh, False, bf)
    y1, s1, c1 = _mixer(a, ids, 0, fresh, True, bf)
    assert y1.dtype == bf and s1.dtype == jnp.float32
    assert _worst(y1, y0) < 2 * _worst(y0, y32) + 0.05
    assert _worst(s1, s0) < 2 * _worst(s0, s32) + 0.05


def test_a_bfloat16_state_stands_apart():
    """The configuration says the state is float32: the recurrence
    with ``h`` rounded to bfloat16 after every token reads over ten
    times farther from the kernel than the tolerance above."""
    a = _inputs(200, seed=3)
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([True, True, True])
    _, (y1, _, _) = _both(a, ids, 0, fresh)
    xs, x, Bm, Cm, _ = _conv(a, fresh)
    y, _ = _sequential(x[0], a["dt"][0], Bm[0], Cm[0], a["A"],
                       jnp.zeros((H, P, N)), jnp.bfloat16)
    row = {**a, "zxd": a["zxd"][:1]}
    assert _worst(_gate_norm(row, y[None], xs[:1]), y1[:1]) > 5e-3


def test_the_path_is_chosen_by_shape():
    was = pallas_paged._override
    try:
        pallas_paged.set_flash_enabled(True)
        assert [mamba2.mamba2_path(T, 4096, 64, 8, 128)
                for T in (1, 8, 9, 2048)] == [
            "mamba2_recurrent_step", "mamba2_recurrent_step",
            "mamba2_chunk_scan", "mamba2_chunk_scan"]
        # a group's channels that are no whole vectors of lanes, heads
        # that do not divide one, a group's B and C that are no whole
        # vectors (no column blocks of in_proj's output): the jnp form,
        # kernels or no
        assert mamba2.mamba2_path(1, 96, 3, 1, 16).endswith("_jnp")
        assert mamba2.mamba2_path(64, 384, 2, 1, 16).endswith("_jnp")
        assert mamba2.mamba2_path(64, 256, 8, 2, 16) \
            == "mamba2_chunk_scan_jnp"
        assert mamba2.mamba2_path(64, 384, 6, 1, 256) \
            == "mamba2_chunk_scan_jnp"
        assert mamba2.mamba2_path(64, 256, 8, 2, 128) == "mamba2_chunk_scan"
        pallas_paged.set_flash_enabled(False)
        assert [mamba2.mamba2_path(T, 4096, 64, 8, 128) for T in (1, 9)] \
            == ["mamba2_recurrent_step_jnp", "mamba2_chunk_scan_jnp"]
    finally:
        pallas_paged.set_flash_enabled(was)
