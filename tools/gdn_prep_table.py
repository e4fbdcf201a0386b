#!/usr/bin/env python3
"""The delta rule's chunkwise form alone, on the chip: one layer of
``ops/gdn.mix`` over one row, the WHOLE rule beside its per-chunk
transform (ops/gdn.py; PERF.md, PR 49 and PR 51).

The shapes ``qwen3next-longctx-closed`` serves: 16 key / 32 value heads
of 128, bfloat16 inputs with the keys and queries normalised, log-decays
and write strengths as the layer draws them, one row of 2048 tokens (a
prefill chunk), of 512, and of 256 (the probe's one-row bucket), from
and to a page of a float32 state pool. q, k, v go in and ``o`` comes
out as ``[1, T, heads x 128]``, the layout the projections leave and
read, so a relayout the rule makes of them is the rule's own. Two
forms a shape:

- ``jnp``: the kernels off: ``_chunk_prep`` + ``_scan_jnp`` as XLA
  operations, the CPU's path and what tests/test_gdn.py holds the
  kernels to;
- ``kernel``: the tree's kernels on. Since PR 51 ONE kernel,
  ``gdn_chunk_scan``, which reads q, k, v in place and makes the
  transform in VMEM; copied into a tree from before it, ``_chunk_prep``
  with the substitution kernel ``gdn_chunk_solve``, then the scan
  kernel: the "before".

Every time is the DEVICE's, from a profiler capture of ``--repeat``
calls (chipbench/xplane.py reads it), so neither the host's dispatch
nor a timing loop's own copies are in it:

- ``rule_ms``: the median run of the jitted ``mix`` (transform,
  substitution, scan and every copy between them);
- ``ops_us``: its operations by base name, [calls a run, microseconds
  a run], the 24 that took most;
- ``prep_ms``: where the form runs ``_chunk_prep``, the median run of
  that alone (the transform without the scan).

``largest_difference``: the kernels' ``o`` and page against the
``jnp`` form's, beside the largest entry of each. A rehearsal on the
CPU has no device plane: its times are the host's clock around a call
(``"clock": "host"``).

One JSON line last.
"""

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

KEY_HEADS, VALUE_HEADS, HEAD_DIM = 16, 32, 128
TOKENS = (2048, 512, 256)


def device_times(step, carry, calls):
    """``calls`` runs of ``carry = step(carry)`` under the profiler ->
    (median device ms a run, {base name: [calls a run, us a run]},
    "device"); where the capture holds no device plane
    (the CPU) the host's clock around a run, no operations, "host"."""
    import jax
    import numpy as np

    from chipbench import xplane
    carry = jax.block_until_ready(step(carry))
    times = []
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                t0 = time.perf_counter()
                carry = jax.block_until_ready(step(carry))
                times.append(time.perf_counter() - t0)
        try:
            modules = xplane.reduce_file(
                xplane.find_xplane(trace_dir))["modules"]
        except ValueError:
            return round(1e3 * float(np.median(times)), 4), {}, "host"
    module = max(modules.values(), key=lambda m: m["total_s"])
    ops = sorted(module["ops"].items(), key=lambda kv: -kv[1][1])[:24]
    ops = {name: [n / calls, round(1e6 * sec / calls, 1)]
           for name, (n, sec) in ops}
    return round(1e3 * module["median_s"], 4), ops, "device"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeat", type=int, default=5,
                    help="calls in a capture")
    ap.add_argument("--tokens", type=int, nargs="*", default=[],
                    help="tokens of the row (default: 2048, 512, 256)")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="2 key / 4 value heads, 256 and 128 tokens (a "
                         "CPU rehearsal)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from production_stack_tpu.ops import gdn, pallas_paged

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("gdn_prep_table: JAX found no accelerator", file=sys.stderr)
        return 3
    # the tree's kernels: one since PR 51, two before it
    one_kernel = not hasattr(gdn, "_solve_rows")
    hk, hv, tokens = (2, 4, (256, 128)) if args.tiny \
        else (KEY_HEADS, VALUE_HEADS, TOKENS)
    tokens = args.tokens or tokens
    bf16, f32, D = jnp.bfloat16, jnp.float32, HEAD_DIM

    def worst(a, b):
        return float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32))))

    rows = []
    for T in tokens:
        ks = jax.random.split(jax.random.PRNGKey(T), 6)
        q, k = (jax.random.normal(key, (1, T, hk, D)) for key in ks[:2])
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[2], (1, T, hv, D))
        # as models/llama's layer makes them: A = exp(A_log) in (0, 16),
        # g = -A softplus(a + dt_bias), beta = sigmoid(b)
        A = jax.random.uniform(ks[3], (hv,), minval=0.0, maxval=16.0)
        g = -A * jax.nn.softplus(jax.random.normal(ks[3], (1, T, hv)) + 1.0)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, hv)))
        flat = tuple(x.astype(bf16).reshape(1, T, -1) for x in (q, k, v))
        pool = jax.random.normal(ks[5], (1, 2, hv, D, D))
        ids, fresh = jnp.array([1]), jnp.array([False])

        def rule(pool, q, k, v, g, beta):
            o, pool = gdn.mix(
                q.reshape(1, T, hk, D), k.reshape(1, T, hk, D),
                v.reshape(1, T, hv, D), g, beta, pool, ids, jnp.int32(0),
                fresh)
            return o.reshape(1, T, hv * D), pool

        def prep(q, k, v, g, beta):
            return gdn._chunk_prep(
                gdn._expand_heads(q.reshape(1, T, hk, D), hv),
                gdn._expand_heads(k.reshape(1, T, hk, D), hv),
                v.reshape(1, T, hv, D), g, beta)

        want = {}
        for form in ("jnp", "kernel"):
            row = {"tokens": T, "heads": [hk, hv], "form": form,
                   "kernels": [] if form == "jnp" else
                   ["gdn_chunk_scan"] if one_kernel else
                   ["gdn_chunk_solve", "gdn_chunk_scan"]}
            pallas_paged.set_flash_enabled(form == "kernel")
            got = {}
            try:
                # a function of its own a form: jit keys its cache on
                # the function, not on the kernels' switch
                run = jax.jit(lambda *a: rule(*a), donate_argnums=0)
                got["o"], page = run(pool + 0.0, *flat, g, beta)
                got["state"] = page[0, 1]
                # a run takes the page the run before it left
                row["rule_ms"], row["ops_us"], row["clock"] = device_times(
                    lambda page: run(page, *flat, g, beta)[1], page,
                    args.repeat)
                if form == "jnp" or not one_kernel:
                    alone = jax.jit(lambda *a: prep(*a))
                    row["prep_ms"] = device_times(
                        lambda _: alone(*flat, g, beta), None,
                        args.repeat)[0]
            except Exception as e:      # what the compiler refuses
                row["refused"] = str(e).splitlines()[0][:200]
                got = {}
            finally:
                pallas_paged.set_flash_enabled(None)
            if form == "jnp":
                want = got
            elif want and got:
                row["largest_difference"] = {
                    n: worst(got[n], want[n]) for n in got}
                row["largest_entry"] = {
                    n: float(jnp.max(jnp.abs(want[n].astype(f32))))
                    for n in got}
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "one_kernel": one_kernel, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
