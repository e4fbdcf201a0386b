"""The paged kernels' tests make every call twice over: on one bare
layer of the pool [N, Hkv, Bs, D], as the kernels' own tests always
did, and as the serving path makes it, on the WHOLE pool
[L, N, Hkv, Bs, D] with the layer as an operand (models/kv.py: the
pool is carried, never stacked)."""

import numpy as np
import pytest

import jax.numpy as jnp

WHOLE = pytest.mark.parametrize("layer", [None, 1],
                                ids=["layer_4d", "whole_pool"])


def whole(pool, layer, layers=3):
    """`pool` [N, ...] as layer `layer` of a whole pool whose other
    layers hold its blocks in another order (layer None: as it is)."""
    if layer is None:
        return pool
    return jnp.stack([jnp.roll(pool, i - layer, axis=0)
                      for i in range(layers)])


def call(fn, q, k_pool, v_pool, *args, layer, **kw):
    """fn on the bare layer or, with `layer`, on the whole pool: then
    the result must be that of the bare layer to the bit."""
    scales = {k: whole(kw.pop(k), layer)
              for k in ("k_scales", "v_scales") if k in kw}
    got = fn(q, whole(k_pool, layer), whole(v_pool, layer), *args,
             layer=layer, **scales, **kw)
    if layer is not None:
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(call(fn, q, k_pool, v_pool, *args, layer=None,
                             **{k: v[layer] for k, v in scales.items()},
                             **kw)))
    return got
