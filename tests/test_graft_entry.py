"""Driver-contract tests for __graft_entry__ (the harness compile-checks
entry() single-chip and runs dryrun_multichip(n) on a virtual CPU mesh)."""

import jax
import jax.numpy as jnp


def test_dryrun_multichip_after_backend_init():
    # simulate the driver's actual usage: some jax work already
    # initialized backends before dryrun_multichip forces the n-device
    # CPU platform (exercises the clear-and-retry path)
    assert float(jnp.ones(3).sum()) == 3.0
    import __graft_entry__ as g
    g.dryrun_multichip(8)
    assert len(jax.devices()) >= 8


def test_entry_shapes(monkeypatch, tmp_path):
    # with the variable set, entry() sets no cache directory in code:
    # the session's JAX config stays as it was
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    import __graft_entry__ as g
    fn, args = g.entry()
    logits, cache = jax.eval_shape(fn, *args)
    assert logits.shape[0] == 4 and logits.shape[1] == 1
    assert logits.shape[2] == 8192
