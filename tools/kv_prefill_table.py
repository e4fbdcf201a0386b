#!/usr/bin/env python3
"""The K/V prefill kernel alone, on the chip, by q block and key panel:
the table behind the rule that sizes its grid (ops/pallas_paged.
prefill_tiles; PERF.md, PR 43).

``paged_attention`` over a bfloat16 K/V pool, as models/kv.attend calls
it (the whole pool and a layer index), at the shapes the cells serve:

- N (qwen3next-longctx-closed): one row of 2048 tokens, 2 kv heads x 8
  groups x 256, the chunk at the END of contexts of 2048 / 4096 / 8192 /
  16 384 (the kv bucket is the context);
- M (mistral7b-decode-closed): one row of 128 / 256 tokens, 8 kv heads x
  4 groups x 128, contexts of 128-512 in the 512 bucket;
- Q (qwen15moe-decode-closed): the same at 16 kv heads x 1 group x 128.

For every q block of 128 / 256 / 512 and panel of 64 / 256 / 512 keys
that the shape admits: milliseconds a call (median of ``--repeat``
timings of ``--inner`` calls chained inside one program, so that the
host's dispatch is not in the number) and TFLOP/s over the causal
products' operations. ``rule`` marks the pair the program runs there.
On a tree whose kernel takes one pool block a step (no
``prefill_tiles``) only the panel of 64 runs: the table's "before".

One JSON line last.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BS = 64
BLOCK_QS = (128, 256, 512)
PANELS = (64, 256, 512)
# (cell, kv heads, groups, head dim, tokens a row, context, kv bucket)
SHAPES = (
    [("N", 2, 8, 256, 2048, c, c) for c in (2048, 4096, 8192, 16384)]
    + [(cell, hkv, g, 128, T, c, 512)
       for cell, hkv, g in (("M", 8, 4), ("Q", 16, 1))
       for T in (128, 256) for c in (128, 256, 512) if c >= T])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--inner", type=int, default=8,
                    help="calls chained inside one timed program")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="two shapes cut to a few blocks (a CPU "
                         "rehearsal)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.ops import pallas_paged as pp

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("kv_prefill_table: JAX found no accelerator",
              file=sys.stderr)
        return 3
    interpret = pp.needs_interpret()
    panels_run = hasattr(pp, "prefill_tiles")
    shapes, block_qs, panels = SHAPES, BLOCK_QS, PANELS
    if args.tiny:
        shapes = [("N", 2, 8, 256, 64, 256, 256),
                  ("M", 8, 4, 128, 32, 64, 512)]
        block_qs, panels = (16, 32), (64, 256)

    rows = []
    for cell, Hkv, G, D, T, context, bucket in shapes:
        nb = bucket // BS
        keys = jax.random.split(jax.random.PRNGKey(Hkv + T + context), 3)
        k_pool, v_pool = (jax.random.normal(
            k, (1, nb + 1, Hkv, BS, D)).astype(jnp.bfloat16)
            for k in keys[:2])
        tables = kv_pool.linear_tables(1, bucket, BS)
        starts = jnp.full((1,), context - T, jnp.int32)
        q = jax.random.normal(keys[2], (1, T, Hkv * G, D)
                              ).astype(jnp.bfloat16)
        # the causal products: 4 D operations a (query, visible key, head)
        flop = 4 * D * Hkv * G * T * (context - T + (T + 1) / 2)
        if panels_run:
            rule = pp.prefill_tiles(T, G, D, nb, BS)
        else:                   # the q block the old loop chose
            bq = T
            while bq > pp._MIN_BLOCK_Q and not pp.paged_viable(
                    bq, G, D, BS):
                bq //= 2
            rule = (bq, 1)
        first = None
        pairs = [(bq, p) for bq in sorted({min(b, T) for b in block_qs})
                 for p in panels
                 if p <= bucket and nb % (p // BS) == 0
                 and (panels_run or p == BS)]
        if (rule[0], rule[1] * BS) not in pairs:
            pairs.append((rule[0], rule[1] * BS))
        for bq, panel in pairs:
            kw = dict(nb=nb, interpret=interpret, layer=jnp.int32(0),
                      block_q=bq)
            if panels_run:
                kw["panel_blocks"] = panel // BS

            def chained(q, k_pool, v_pool, tables, starts, kw=kw):
                def body(_, x):
                    return pp.paged_attention(x, k_pool, v_pool, tables,
                                              starts, **kw)
                return jax.lax.fori_loop(0, args.inner, body, q)

            run = jax.jit(chained)
            call = (q, k_pool, v_pool, tables, starts)
            row = {"cell": cell, "kv_heads": Hkv, "groups": G,
                   "head_dim": D, "tokens": T, "context": context,
                   "bucket": bucket, "block_q": bq, "panel": panel,
                   "rule": (bq, panel) == (rule[0], rule[1] * BS)}
            try:
                run(*call).block_until_ready()
            except Exception as e:      # what the compiler refuses
                row["refused"] = str(e).splitlines()[0][:200]
                rows.append(row)
                print(json.dumps(row), file=sys.stderr, flush=True)
                continue
            times = []
            for _ in range(args.repeat):
                t0 = time.perf_counter()
                run(*call).block_until_ready()
                times.append(time.perf_counter() - t0)
            ms = 1e3 * float(np.median(times)) / args.inner
            once = pp.paged_attention(*call, **kw).astype(jnp.float32)
            first = once if first is None else first
            row.update(ms=round(ms, 4),
                       tflops=round(flop / ms / 1e9, 2),
                       largest_difference=float(
                           jnp.max(jnp.abs(once - first))))
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "panels": panels_run, "inner": args.inner,
                      "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
