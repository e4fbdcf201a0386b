"""ops/mamba2.py: the Mamba-2 scan's two kernels, in interpret mode on
the CPU, against the ``jax.numpy`` form and against the token-by-token
recurrence as chipbench/references/nemotron_h.py writes it (a head's
state [head_dim, state], ONE decay a head, B and C by group), through
shuffled pages of a state pool of several layers, with a fresh row, a
carried state, chunk boundaries inside a call and a padded tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops import mamba2, pallas_paged

H, P, G, N = 8, 32, 2, 16       # heads, head_dim, groups, state
D = H * P


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(T, B=3, seed=0, real=None, heads=H, head_dim=P, groups=G,
            state=N):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    d = heads * head_dim
    x = jax.random.normal(k[0], (B, T, d))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, heads)) - 2)
    if real is not None:        # positions that are not real: dt = 0
        dt = jnp.where((jnp.arange(T)[None, :]
                        < jnp.asarray(real)[:, None])[..., None], dt, 0.0)
    Bm = jax.random.normal(k[2], (B, T, groups, state))
    Cm = jax.random.normal(k[3], (B, T, groups, state))
    A = -jnp.exp(jax.random.normal(k[4], (heads,)))
    pool = jax.random.normal(k[5], (3, 7, state, d))
    return x, dt, Bm, Cm, A, pool


def _both(args, ids, layer, fresh):
    out = []
    was = pallas_paged._override
    try:
        for on in (False, True):
            pallas_paged.set_flash_enabled(on)
            out.append(jax.jit(
                lambda *a: mamba2.ssd_scan(*a, ids, layer, fresh))(*args))
    finally:
        pallas_paged.set_flash_enabled(was)
    return out


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
    scan's chunk of 128, padded to it), 128 and 300 (three chunks, the
    last padded; a row whose real positions end one past a chunk
    boundary): ``mamba2_chunk_scan``, the closed form in matrix
    products. Pages 5, 2, 6 of layer 1 of a pool of three layers; row 1
    fresh. Both forms leave every other page and layer as it was. 2e-4
    on values of up to 60: float32 against float32 with the sums in
    another order (5e-5 seen)."""
    args = _inputs(T, real=real)
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([False, True, False])
    (y0, s0), (y1, s1) = _both(args, ids, 1, fresh)
    assert mamba2.mamba2_path(T, D, H, G, N).endswith("_jnp")
    assert float(jnp.max(jnp.abs(y0 - y1))) < 2e-4
    assert float(jnp.max(jnp.abs(s0 - s1))) < 2e-4
    pool = args[-1]
    keep = np.ones(pool.shape[:2], bool)
    keep[1, [5, 2, 6]] = False
    for s in (s0, s1):
        assert np.array_equal(np.asarray(s)[keep], np.asarray(pool)[keep])
    # against the recurrence written token by token, a head at a time
    x, dt, Bm, Cm, A, pool = args
    for b in range(3):
        h0 = jnp.zeros((H, P, N)) if fresh[b] \
            else _by_head(pool[1, ids[b]])
        y, h = _sequential(x[b], dt[b], Bm[b], Cm[b], A, h0)
        assert float(jnp.max(jnp.abs(y - y1[b]))) < 5e-4
        assert float(jnp.max(jnp.abs(h - _by_head(s1[1, ids[b]])))) < 5e-4


def test_a_padded_tail_advances_nothing():
    """Row 1's 130 real positions of 300 (two positions into the second
    chunk): its page after the call is its page after those 130
    alone."""
    args = _inputs(300, real=[300, 130, 129])
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([False, False, False])
    for y, s in _both(args, ids, 0, fresh):
        x, dt, Bm, Cm, A, pool = args
        _, h = _sequential(x[1, :130], dt[1, :130], Bm[1, :130],
                           Cm[1, :130], A, _by_head(pool[0, 2]))
        assert float(jnp.max(jnp.abs(h - _by_head(s[0, 2])))) < 5e-4


def test_the_state_carries_across_calls_as_across_chunks():
    """A prompt in two calls (a dispatch boundary: the state goes to
    its page and comes back) reads as in one."""
    args = _inputs(256, seed=5)
    x, dt, Bm, Cm, A, pool = args
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([True, True, True])
    was = pallas_paged._override
    try:
        pallas_paged.set_flash_enabled(True)
        whole, s_whole = mamba2.ssd_scan(*args, ids, 2, fresh)
        cut = 160
        first, s = mamba2.ssd_scan(x[:, :cut], dt[:, :cut], Bm[:, :cut],
                                   Cm[:, :cut], A, pool, ids, 2, fresh)
        second, s = mamba2.ssd_scan(x[:, cut:], dt[:, cut:], Bm[:, cut:],
                                    Cm[:, cut:], A, s, ids, 2, ~fresh)
    finally:
        pallas_paged.set_flash_enabled(was)
    assert float(jnp.max(jnp.abs(
        jnp.concatenate([first, second], 1) - whole))) < 2e-4
    assert float(jnp.max(jnp.abs(s - s_whole))) < 2e-4


def test_the_published_head_geometry_in_interpret_mode():
    """Heads of 64 over a state of 128 (two heads a vector of lanes,
    the state's rows a whole tile), one group of four heads: the
    published sizes of a head, a quarter of a group."""
    args = _inputs(140, B=1, seed=2, heads=4, head_dim=64, groups=1,
                   state=128)
    ids, fresh = jnp.array([3]), jnp.array([False])
    (y0, s0), (y1, s1) = _both(args, ids, 0, fresh)
    assert float(jnp.max(jnp.abs(y0 - y1))) < 1e-3     # values to 150
    assert float(jnp.max(jnp.abs(s0 - s1))) < 1e-3


def test_a_bfloat16_state_stands_apart():
    """The configuration says the state is float32: the recurrence
    with ``h`` rounded to bfloat16 after every token reads over ten
    times farther from the kernel than the tolerance above."""
    args = _inputs(200, seed=3)
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([True, True, True])
    _, (y1, _) = _both(args, ids, 0, fresh)
    x, dt, Bm, Cm, A, pool = args
    y, _ = _sequential(x[0], dt[0], Bm[0], Cm[0], A, jnp.zeros((H, P, N)),
                       jnp.bfloat16)
    assert float(jnp.max(jnp.abs(y - y1[0]))) > 5e-3


def test_the_path_is_chosen_by_shape():
    was = pallas_paged._override
    try:
        pallas_paged.set_flash_enabled(True)
        assert [mamba2.mamba2_path(T, 4096, 64, 8, 128)
                for T in (1, 8, 9, 2048)] == [
            "mamba2_recurrent_step", "mamba2_recurrent_step",
            "mamba2_chunk_scan", "mamba2_chunk_scan"]
        # a group's channels that are no whole vectors of lanes, heads
        # that do not divide one: the jnp form, kernels or no
        assert mamba2.mamba2_path(1, 96, 3, 1, 16).endswith("_jnp")
        assert mamba2.mamba2_path(64, 384, 2, 1, 16).endswith("_jnp")
        pallas_paged.set_flash_enabled(False)
        assert [mamba2.mamba2_path(T, 4096, 64, 8, 128) for T in (1, 9)] \
            == ["mamba2_recurrent_step_jnp", "mamba2_chunk_scan_jnp"]
    finally:
        pallas_paged.set_flash_enabled(was)
