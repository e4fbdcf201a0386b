"""ops/mamba.py: the selective scan's two kernels, in interpret mode on
the CPU, against the ``jax.numpy`` form and against the token-by-token
recurrence of chipbench/references/phi4flash.py, through shuffled pages
of a state pool of several layers, with a fresh row, a carried state
and a padded tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops import mamba, pallas_paged


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(T, B=3, D=256, N=4, seed=0, real=None):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(k[0], (B, T, D))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, D)) - 2)
    if real is not None:        # positions that are not real: dt = 0
        dt = jnp.where((jnp.arange(T)[None, :]
                        < jnp.asarray(real)[:, None])[..., None], dt, 0.0)
    Bm = jax.random.normal(k[2], (B, T, N))
    Cm = jax.random.normal(k[3], (B, T, N))
    A = -jnp.exp(jax.random.normal(k[4], (N, D)))
    state = jax.random.normal(k[5], (3, 7, N, D))
    return x, dt, Bm, Cm, A, state


def _both(args, ids, layer, fresh):
    out = []
    was = pallas_paged._override
    try:
        for on in (False, True):
            pallas_paged.set_flash_enabled(on)
            out.append(jax.jit(
                lambda *a: mamba.selective_scan(*a, ids, layer, fresh))(
                    *args))
    finally:
        pallas_paged.set_flash_enabled(was)
    return out


def _sequential(x, dt, Bm, Cm, A, h, dtype=jnp.float32):
    """One row, token by token: the rule as the reference writes it."""
    ys = []
    for t in range(x.shape[0]):
        h = (jnp.exp(dt[t][None, :] * A) * h
             + (dt[t] * x[t])[None, :] * Bm[t][:, None])
        h = h.astype(dtype).astype(jnp.float32)
        ys.append(jnp.sum(h * Cm[t][:, None], axis=0))
    return jnp.stack(ys), h


@pytest.mark.parametrize("T,real", [(1, None), (5, None), (8, None),
                                    (100, [100, 60, 1]),
                                    (192, [192, 130, 64])])
def test_the_kernels_are_the_jnp_form_through_shuffled_pages(T, real):
    """T = 1, 5, 8: ``mamba_recurrent_step``; 100 (padded to two
    blocks of 64) and 192 with rows whose tails are not real:
    ``mamba_chunk_scan``. Pages 5, 2, 6 of layer 1 of a pool of three
    layers; row 1 fresh. Both forms leave every other page and layer
    as it was. 1e-5: float32 against float32, the sums in another
    order."""
    args = _inputs(T, real=real)
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([False, True, False])
    (y0, s0), (y1, s1) = _both(args, ids, 1, fresh)
    assert mamba.mamba_path(T).endswith("_jnp")     # kernels off again
    assert float(jnp.max(jnp.abs(y0 - y1))) < 1e-5
    assert float(jnp.max(jnp.abs(s0 - s1))) < 1e-5
    state = args[-1]
    keep = np.ones(state.shape[:2], bool)
    keep[1, [5, 2, 6]] = False
    for s in (s0, s1):
        assert np.array_equal(np.asarray(s)[keep], np.asarray(state)[keep])
    # against the recurrence written token by token, a row at a time
    x, dt, Bm, Cm, A, state = args
    for b in range(3):
        h0 = jnp.zeros_like(state[1, 0]) if fresh[b] \
            else state[1, ids[b]]
        y, h = _sequential(x[b], dt[b], Bm[b], Cm[b], A, h0)
        assert float(jnp.max(jnp.abs(y - y1[b]))) < 1e-4
        assert float(jnp.max(jnp.abs(h - s1[1, ids[b]]))) < 1e-4


def test_a_padded_tail_advances_nothing():
    """Row 1's 60 real positions of 100: its page after the chunk is
    its page after those 60 alone."""
    args = _inputs(100, real=[100, 60, 1])
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([False, False, False])
    for y, s in _both(args, ids, 0, fresh):
        x, dt, Bm, Cm, A, state = args
        _, h = _sequential(x[1, :60], dt[1, :60], Bm[1, :60], Cm[1, :60],
                           A, state[0, 2])
        assert float(jnp.max(jnp.abs(h - s[0, 2]))) < 1e-4


def test_a_bfloat16_state_stands_apart():
    """The configuration says the state is float32: the recurrence
    with ``h`` rounded to bfloat16 after every token reads a hundred
    times farther from the kernel than the tolerance above."""
    args = _inputs(100, seed=3)
    ids, fresh = jnp.array([5, 2, 6]), jnp.array([True, True, True])
    _, (y1, _) = _both(args, ids, 0, fresh)
    x, dt, Bm, Cm, A, state = args
    y, _ = _sequential(x[0], dt[0], Bm[0], Cm[0], A,
                       jnp.zeros_like(state[0, 0]), jnp.bfloat16)
    assert float(jnp.max(jnp.abs(y - y1[0]))) > 1e-3


def test_the_path_is_chosen_by_shape():
    was = pallas_paged._override
    try:
        pallas_paged.set_flash_enabled(True)
        assert [mamba.mamba_path(T) for T in (1, 8, 9, 2048)] == [
            "mamba_recurrent_step", "mamba_recurrent_step",
            "mamba_chunk_scan", "mamba_chunk_scan"]
        pallas_paged.set_flash_enabled(False)
        assert [mamba.mamba_path(T) for T in (1, 9)] == [
            "mamba_recurrent_step_jnp", "mamba_chunk_scan_jnp"]
    finally:
        pallas_paged.set_flash_enabled(was)
