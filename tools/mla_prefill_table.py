#!/usr/bin/env python3
"""Absorbed against expanded latent attention for a PREFILL chunk, on
the chip, at GLM-4.7-Flash's widths: the table behind the one form
models/llama._mla_attention keeps (PERF.md, PR 35).

Both forms start from what the layer has either way (the chunk's
q_nope / q_rope, the latent pool with the chunk already written) and
end at the heads' outputs [B, T, 20, 256]:

- absorbed (what the program runs): q_lat = q_nope W_uk^T, the paged
  kernel's latent case over the pool's [c | k_rope] vectors (scores
  over 576 columns, values the first 512), then W_uv;
- expanded: the context's latents gathered out of the pool through the
  tables, [k_nope | v] = c W_kvb for every cached token, K and V per
  head laid out as a pool of their own, and the paged kernel's ordinary
  case on them (20 kv heads, head dim 256).

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

NH, R, DN, DR, DV, BS = 20, 512, 192, 64, 256, 64


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeat", type=int, default=20)
    ap.add_argument("--allow-cpu", action="store_true")
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
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    w_kvb = (0.02 * jax.random.normal(next(keys), (R, NH, DN + DV))
             ).astype(jnp.bfloat16)

    def absorbed(q_nope, q_rope, pool, tables, starts, nb):
        B, T = q_nope.shape[:2]
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, w_kvb[..., :DN])
        q = jnp.concatenate(
            [q_lat, q_rope,
             jnp.zeros((B, T, NH, W - R - DR), q_lat.dtype)], -1)
        ctx = pp.paged_attention(q, pool, None, tables, starts, nb=nb,
                                 interpret=interpret, scale=scale,
                                 layer=jnp.int32(0), value_dim=R)
        return jnp.einsum("bthr,rhd->bthd", ctx, w_kvb[..., DN:])

    def expanded(q_nope, q_rope, pool, tables, starts, nb):
        B = q_nope.shape[0]
        lat = kv_pool.gather_view(pool, tables, nb, layer=0)[:, :, 0]
        kvh = jnp.einsum("bsr,rhd->bshd", lat[..., :R], w_kvb)
        k = jnp.concatenate(
            [kvh[..., :DN], jnp.broadcast_to(
                lat[:, :, None, R:R + DR], kvh.shape[:3] + (DR,))], -1)

        def as_pool(x):      # [B, nb*Bs, H, D] -> [B*nb, H, Bs, D]
            return x.reshape(B * nb, BS, NH, x.shape[-1]).transpose(
                0, 2, 1, 3)
        own = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
        return pp.paged_attention(
            jnp.concatenate([q_nope, q_rope], -1), as_pool(k),
            as_pool(kvh[..., DN:]), own, starts, nb=nb,
            interpret=interpret, scale=scale)

    rows = []
    for B, T, context in ((1, 256, 256), (1, 256, 512),
                          (16, 256, 256), (16, 256, 512)):
        nb = context // BS
        pool = jax.random.normal(
            jax.random.PRNGKey(B),
            (1, B * nb + 1, 1, BS, W)).astype(jnp.bfloat16)
        tables = kv_pool.linear_tables(B, context, BS)
        starts = jnp.full((B,), context - T, jnp.int32)
        kq = jax.random.split(jax.random.PRNGKey(T + B), 2)
        q_nope = jax.random.normal(kq[0], (B, T, NH, DN)).astype(
            jnp.bfloat16)
        q_rope = jax.random.normal(kq[1], (B, T, NH, DR)).astype(
            jnp.bfloat16)
        row = {"rows": B, "tokens": T, "context": context}
        outs = {}
        for name, fn in (("absorbed", absorbed), ("expanded", expanded)):
            run = jax.jit(fn, static_argnames="nb")
            outs[name] = run(q_nope, q_rope, pool, tables, starts,
                             nb=nb).block_until_ready()
            times = []
            for _ in range(args.repeat):
                t0 = time.perf_counter()
                run(q_nope, q_rope, pool, tables, starts,
                    nb=nb).block_until_ready()
                times.append(time.perf_counter() - t0)
            row[name + "_ms"] = round(1e3 * float(np.median(times)), 4)
        row["largest_difference"] = float(jnp.max(jnp.abs(
            outs["absorbed"].astype(jnp.float32)
            - outs["expanded"].astype(jnp.float32))))
        rows.append(row)
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind, "rows": rows}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
