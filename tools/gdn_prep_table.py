#!/usr/bin/env python3
"""The delta rule's per-chunk transform alone, on the chip: the table
behind the substitution kernel ``gdn_chunk_solve`` (ops/gdn.py;
PERF.md, PR 49).

One layer of ``ops/gdn._chunk_prep`` (L, the unit lower-triangular
inverse, ``u`` and ``w``, the scan's other operands) over one row at
the shape ``qwen3next-longctx-closed`` serves (2048 tokens, 32 value
heads of 128, bfloat16 inputs with the keys and queries normalised,
log-decays and write strengths as the layer draws them) and at 512
tokens, in both forms of the substitution of its diagonal blocks
(``[16, 16, blocks]`` float32: 4096 and 1024 blocks): ``xla`` (the
``jax.numpy`` loop, the CPU's path) and ``kernel`` (the tile in VMEM).
Every time is the DEVICE's, from a profiler capture of ``--repeat``
calls (chipbench/xplane.py reads it), so neither the host's dispatch
nor a timing loop's own copies are in it:

- ``prep_ms``: the median run of the jitted ``_chunk_prep``;
- ``solve_ms``: of that, the substitution's own operations a run: the
  kernel, or XLA's ``dynamic-update-slice``, ``multiply_reduce_fusion``
  and ``slice_add_fusion`` (fifteen of each);
- ``solve_alone_ms``: ``_solve_rows`` jitted by itself on the blocks
  (XLA then lays the array out row-major and updates it in place, which
  inside ``_chunk_prep`` it does not: PERF.md, PR 49);
- ``ops_us``: the transform's operations by base name, [calls a run,
  microseconds a run], the 24 that took most.

``largest_difference``: the kernel's against the loop's, of the solved
blocks and of ``u`` and ``w``, beside the largest entry of each.
Copied into a tree without the kernel it times that tree's one form:
the "before". A rehearsal on the CPU has no device plane: its times
are the host's clock around a call (``"clock": "host"``).

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

HEADS, HEAD_DIM = 32, 128
TOKENS = (2048, 512)
# XLA's operations of the substitution, by base name (chipbench/xplane)
XLA_SOLVE_OPS = ("dynamic-update-slice", "multiply_reduce_fusion",
                 "slice_add_fusion")


def device_times(fn, operands, calls):
    """``calls`` runs of the jitted fn under the profiler -> (median
    device ms a run, {base name: [calls a run, us a run]}, "device");
    where the capture holds no device plane (the CPU) the host's clock
    around a run, no operations, "host"."""
    import jax
    import numpy as np

    from chipbench import xplane
    jax.block_until_ready(fn(*operands))
    times = []
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*operands))
                times.append(time.perf_counter() - t0)
        try:
            modules = xplane.reduce_file(
                xplane.find_xplane(trace_dir))["modules"]
        except ValueError:
            return round(1e3 * float(np.median(times)), 4), {}, "host"
    module = max(modules.values(), key=lambda m: m["total_s"])
    ops = {name: [n / calls, round(1e6 * sec / calls, 1)]
           for name, (n, sec) in module["ops"].items()}
    return round(1e3 * module["median_s"], 4), ops, "device"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeat", type=int, default=5,
                    help="calls in a capture")
    ap.add_argument("--tokens", type=int, nargs="*", default=[],
                    help="tokens of the row (default: 2048 and 512)")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="4 heads, 128 and 64 tokens (a CPU rehearsal)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from production_stack_tpu.ops import gdn, pallas_paged

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("gdn_prep_table: JAX found no accelerator", file=sys.stderr)
        return 3
    has_kernel = hasattr(gdn, "_solve_rows")
    heads, tokens = (4, (128, 64)) if args.tiny else (HEADS, TOKENS)
    tokens = args.tokens or tokens

    def worst(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    rows = []
    for T in tokens:
        ks = jax.random.split(jax.random.PRNGKey(T), 5)
        q, k = (jax.random.normal(key, (1, T, heads, HEAD_DIM))
                for key in ks[:2])
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * HEAD_DIM ** -0.5
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        v = jax.random.normal(ks[2], (1, T, heads, HEAD_DIM))
        # as models/llama's layer makes them: A = exp(A_log) in (0, 16),
        # g = -A softplus(a + dt_bias), beta = sigmoid(b)
        A = jax.random.uniform(ks[3], (heads,), minval=0.0, maxval=16.0)
        g = -A * jax.nn.softplus(
            jax.random.normal(ks[3], (1, T, heads)) + 1.0)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, heads)))
        prep_in = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) \
            + (g, beta)
        At = None
        if has_kernel:      # what _chunk_prep hands the substitution
            seen, was = [], gdn._solve_rows
            gdn._solve_rows = lambda a: (seen.append(a),
                                         gdn._solve_rows_jnp(a))[1]
            try:
                gdn._chunk_prep(*prep_in)
            finally:
                gdn._solve_rows = was
            At = seen[0]
        want = {}
        for form in ("xla", "kernel") if has_kernel else ("tree",):
            row = {"tokens": T, "heads": heads,
                   "blocks": heads * T // gdn._SOLVE_BLOCK,
                   "form": form}
            if form != "tree":
                pallas_paged.set_flash_enabled(form == "kernel")
            got = {}
            try:
                prep = jax.jit(lambda *a: gdn._chunk_prep(*a))
                _, got["w"], _, got["u"], _, _ = prep(*prep_in)
                row["prep_ms"], ops, row["clock"] = device_times(
                    prep, prep_in, args.repeat)
                mine = ("gdn_chunk_solve",) if form == "kernel" \
                    else XLA_SOLVE_OPS
                row["solve_ms"] = round(sum(
                    ops.get(n, [0, 0.0])[1] for n in mine) / 1e3, 4)
                row["ops_us"] = ops
                if At is not None:
                    solve = jax.jit(lambda a: gdn._solve_rows(a))
                    got["blocks"] = solve(At)
                    row["solve_alone_ms"] = device_times(
                        solve, (At,), args.repeat)[0]
            except Exception as e:      # what the compiler refuses
                row["refused"] = str(e).splitlines()[0][:200]
                got = {}
            finally:
                pallas_paged.set_flash_enabled(None)
            if form == "xla":
                want = got
            elif want and got:
                row["largest_difference"] = {
                    n: worst(got[n], want[n]) for n in got}
                row["largest_entry"] = {
                    n: float(jnp.max(jnp.abs(
                        want[n].astype(jnp.float32)))) for n in got}
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "kernel": has_kernel, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
