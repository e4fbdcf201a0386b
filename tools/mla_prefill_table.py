#!/usr/bin/env python3
"""Absorbed against expanded latent attention for a PREFILL chunk, on
the chip, at GLM-4.7-Flash's and GLM-5's widths: the table behind the
rule that chooses between them (ops/pallas_paged.expanded_cheaper;
PERF.md, PR 35 and PR 41).

Both forms start from what the layer has either way (the chunk's
q_nope / q_rope, the latent pool with the chunk already written, W_kvb
in int8 with its per-channel scales, as served) and end at the heads'
outputs [B, T, heads, 256]; both are the calls models/llama.
_mla_attention makes:

- absorbed: q_lat = q_nope W_uk^T, the paged prefill kernel's latent
  case over the pool's [c | k_rope] vectors (scores over the pool's
  640 columns, values the first 512), then W_uv; under a mask, its
  sparse case;
- expanded: the prefill kernel's expanded case: each key panel's
  k_nope and v made per head from the cached c inside the kernel,
  [q_nope | q_rope] against [k_nope | k_rope] per head, under the same
  mask.

Rows: G's widths (20 heads) at T = 256 / 512, GLM-5's (64 heads) at
T = 256 / 512 and, the chunk its cell serves, T = 2048 against
contexts of 2048-16 384 with and without a mask of about 2048
positions a query. ``rule`` is what the program would run there.

One JSON line last, milliseconds a call (median of ``--repeat``).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

R, DN, DR, DV, BS = 512, 192, 64, 256, 64
TOPK = 2048
# (heads, rows, tokens a row, context, masked)
SHAPES = (
    [(20, B, 256, c, False) for B in (1, 16) for c in (256, 512)]
    + [(20, 1, 512, 512, False), (20, 1, 512, 2048, False),
       (64, 1, 256, 2048, False), (64, 1, 512, 2048, False)]
    + [(64, 1, 2048, c, m) for c in (2048, 4096, 8192, 16384)
       for m in (False, True) if m <= (c > TOPK)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeat", type=int, default=10)
    ap.add_argument("--block-q", type=int, default=0,
                    help="the expanded case's q block (0: as served)")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="the first and the last shape cut to a few "
                         "blocks (a CPU rehearsal)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.ops import pallas_paged as pp

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("mla_prefill_table: JAX found no accelerator",
              file=sys.stderr)
        return 3
    interpret = pp.needs_interpret()
    W = kv_pool.latent_pool_width(R + DR)
    scale = (DN + DR) ** -0.5

    def absorbed(q_nope, q_rope, pool, tables, starts, w8, ch, mask, nb):
        B, T, NH = q_nope.shape[:3]
        q_nope = (q_nope.astype(jnp.float32) * ch[:, :DN]
                  ).astype(q_nope.dtype)
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope,
                           w8[..., :DN].astype(q_nope.dtype))
        q = jnp.concatenate(
            [q_lat, q_rope,
             jnp.zeros((B, T, NH, W - R - DR), q_lat.dtype)], -1)
        ctx = pp.paged_attention(q, pool, None, tables, starts, nb=nb,
                                 interpret=interpret, scale=scale,
                                 layer=jnp.int32(0), value_dim=R,
                                 select=mask)
        out = jnp.einsum("bthr,rhd->bthd", ctx,
                         w8[..., DN:].astype(ctx.dtype))
        return out * ch[:, DN:].astype(out.dtype)

    def expanded(q_nope, q_rope, pool, tables, starts, w8, ch, mask, nb):
        return pp.paged_attention(
            jnp.concatenate([q_nope, q_rope], -1), pool, None, tables,
            starts, nb=nb, interpret=interpret, scale=scale,
            layer=jnp.int32(0), value_dim=R, select=mask,
            block_q=args.block_q,
            expand=(w8[..., :DN], w8[..., DN:], ch[:, :DN], ch[:, DN:]))

    shapes = SHAPES
    if args.tiny:
        shapes = [(4, 1, 32, 128, False), (4, 1, 64, 256, True)]
    rows = []
    for NH, B, T, context, masked in shapes:
        nb = context // BS
        keys = iter(jax.random.split(jax.random.PRNGKey(NH + B + T), 8))
        w8 = jax.random.randint(next(keys), (R, NH, DN + DV), -127, 128
                                ).astype(jnp.int8)
        ch = jax.random.uniform(next(keys), (NH, DN + DV), jnp.float32,
                                1e-4, 3e-4)
        pool = jax.random.normal(
            next(keys), (1, B * nb + 1, 1, BS, W)).astype(jnp.bfloat16)
        pool = pool.at[..., R + DR:].set(0)
        tables = kv_pool.linear_tables(B, context, BS)
        starts = jnp.full((B,), context - T, jnp.int32)
        q_nope = jax.random.normal(next(keys), (B, T, NH, DN)).astype(
            jnp.bfloat16)
        q_rope = jax.random.normal(next(keys), (B, T, NH, DR)).astype(
            jnp.bfloat16)
        mask = None
        if masked:      # about TOPK (tiny: a quarter) of the positions
            share = 0.25 if args.tiny else TOPK / context
            mask = (jax.random.uniform(next(keys), (B, T, context))
                    < share).astype(jnp.bfloat16).at[:, :, 0].set(1)
        row = {"heads": NH, "rows": B, "tokens": T, "context": context,
               "masked": masked,
               "rule": "expanded" if pp.expanded_cheaper(
                   T, W, R, (DN, DR, DV)) else "absorbed"}
        outs = {}
        for name, fn in (("absorbed", absorbed), ("expanded", expanded)):
            run = jax.jit(fn, static_argnames="nb")
            call = (q_nope, q_rope, pool, tables, starts, w8, ch, mask)
            outs[name] = run(*call, nb=nb).block_until_ready()
            times = []
            for _ in range(args.repeat):
                t0 = time.perf_counter()
                run(*call, nb=nb).block_until_ready()
                times.append(time.perf_counter() - t0)
            row[name + "_ms"] = round(1e3 * float(np.median(times)), 4)
        row["largest_difference"] = float(jnp.max(jnp.abs(
            outs["absorbed"].astype(jnp.float32)
            - outs["expanded"].astype(jnp.float32))))
        row["largest_output"] = float(jnp.max(jnp.abs(
            outs["absorbed"].astype(jnp.float32))))
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "block_q": args.block_q, "rows": rows}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
