#!/usr/bin/env python3
"""The experts of a PREFILL chunk, kernel alone, on the chip: the table
behind ``ops/moe.grouped_path`` (PERF.md, PR 39).

At Qwen1.5-MoE-A2.7B's and GLM-4.7-Flash's expert shapes (int8 stacks,
top-4, the routing each publishes; GLM's selection bias drawn at sd
0.1 as the benchmark's configuration assumes) and a list of chunk
shapes (rows x tokens, the share of them that is real), microseconds a
layer of

- ``exact``: every expert over every token (``_moe_exact``), the
  layer's experts handed over as the layer scan's xs, as
  models/llama.forward hands them;
- ``dispatch``: the capacity dispatch at the capacity the engine
  reckons (``capacity_for`` on 16 rows x the tokens, factor 2.0), and
  the assignments it drops;
- ``grouped``: ``_moe_grouped`` on the stacks in place, at several pass
  heights (``GROUPED_ROWS``);
- ``ragged``: ``jax.lax.ragged_dot`` over rows sorted by expert, the
  layer's int8 matrices converted to bfloat16 for it;
- ``floor``: the bytes of the experts that had a row over the chip's
  819 GB/s.

Each is the median of ``--repeat`` calls of a jitted chain of
``--layers`` layers (the output of one the input of the next), over
the chain's length. One JSON line last.

``--held`` (PERF.md, PR 44; the table behind
``ops/moe.HELD_BLOCK_SHARES``): a chip's SHARE of an expert layer as
the model, Qwen3-Next's (64 of 512 softmax-routed experts top-10 of
2048 x 512) and GLM-5's (16 of 256 sigmoid-routed top-8 of 6144 x
2048, read in two tiles), one chunk of 2048 tokens: microseconds a
layer of ``moe_mlp``'s grouped path with blocks of 1, 2 and 4 even
shares of the chunk's assignments a round, at the routing the weights
give (an eighth, a sixteenth of the assignments land here) and with
every assignment forced onto the held experts (a selection bias: the
worst case, N k / block rounds), with what ``Work`` counted. Copied
into a tree whose grouped path has no rounds it times that tree's one
path (``"shares": 0``: the "before"). ``--outputs DIR``: the first
layer's sum of each row is kept there, and compared with what a run
before this one left (the other tree's, in the same call). The rows of
the program's own block (two shares) also time a round's SUM BY TOKEN
alone on the first round's block of that routing (PERF.md, PR 53):
``sum_scatter_us`` the row scatter-add of the weighed rows (the form
to PR 52), ``sum_kernel_us`` the kernel ``moe_held_sum``
(``ops/moe._held_sum``; left out in a tree that has none), device
microseconds a round from a profiler capture (``sum_clock``
``device``; a rehearsal on the CPU reads the host's clock and says
``host``), ``sum_live`` the block's live entries and
``sum_largest_difference`` between the two after ``--sum-rounds``
rounds, beside ``sum_largest``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

MODELS = {
    # name: (experts, hidden, inter, router score, bias sd, renormalize,
    #        routed scale)
    "qwen15moe": (60, 2048, 1408, "softmax", 0.0, False, 1.0),
    "glm47flash": (64, 2048, 1536, "sigmoid", 0.1, True, 1.8),
}
TOP_K = 4
SHAPES = ((1, 16, 1.0), (1, 64, 1.0), (1, 128, 1.0), (1, 256, 1.0),
          (1, 256, 0.75), (1, 512, 1.0), (16, 128, 1.0), (16, 256, 1.0),
          (16, 256, 0.625))
HBM_BYTES_PER_S = 819e9
HELD = {
    # name: (router's experts, held, hidden, inter, top-k, router score,
    #        routed scale)
    "qwen3next-share": (512, 64, 2048, 512, 10, "softmax", 1.0),
    "glm5-share": (256, 16, 6144, 2048, 8, "sigmoid", 2.5),
}
HELD_TOKENS = 2048


def held_table(args, timed, platform):
    """The rows of ``--held`` (module text)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import quant
    from production_stack_tpu.ops import moe

    L = args.stack
    layers = jnp.arange(args.layers, dtype=jnp.int32) % L
    rounds_built = hasattr(moe, "HELD_BLOCK_SHARES")
    table = []
    for name in args.held_models.split(","):
        E_all, E, h, inter, k, score, scale = HELD[name]
        N = HELD_TOKENS
        if platform == "cpu":
            E_all, E, h, inter, N = E_all // 8, E // 8, 128, 256, 256
        keys = iter(jax.random.split(jax.random.PRNGKey(len(name)), 8))
        stacks = [quant.quantize_tensor(
            (0.02 * jax.random.normal(next(keys), dims, jnp.float32)
             ).astype(jnp.bfloat16))
            for dims in ((L, E, h, inter), (L, E, h, inter),
                         (L, E, inter, h))]
        router = (0.02 * jax.random.normal(next(keys), (h, E_all))
                  ).astype(jnp.bfloat16)
        x = jax.random.normal(next(keys), (N, h)).astype(jnp.bfloat16)
        # the selection alone moves: every choice a held expert
        forced = jnp.where(jnp.arange(E_all) < E, 10.0, 0.0)
        for routing, bias in (("as_routed", None), ("all_here", forced)):

            def chain(shares, bias=bias):
                def run(x, *stacks):
                    if shares:
                        moe.HELD_BLOCK_SHARES = shares

                    def body(x, layer):
                        y, work = moe.moe_mlp(
                            x, router, *stacks, top_k=k, layer=layer,
                            positions=N, router_score=score,
                            router_bias=bias, routed_scale=scale)
                        # the next layer's input: the residual stream
                        # at the input's scale (a token with nothing
                        # here keeps its row: the share's output alone
                        # is zeros there, which every router sends to
                        # the same experts)
                        r32 = x.astype(jnp.float32) + y.astype(jnp.float32)
                        nxt = (r32 * jax.lax.rsqrt(jnp.mean(
                            r32 * r32, axis=-1, keepdims=True) + 1e-6)
                            ).astype(jnp.bfloat16)
                        return nxt, (y, jnp.stack(work))
                    return jax.lax.scan(body, x, layers)[1]
                return jax.jit(run)

            for shares in ((1, 2, 4) if rounds_built else (0,)):
                (got, work), us = timed(chain(shares), x, *stacks)
                row = {"model": name, "tokens": N, "routing": routing,
                       "shares": shares, "us": round(us, 1),
                       "work_first_layer": np.asarray(work[0]).tolist(),
                       "work_all_layers": np.asarray(work).sum(0).tolist()}
                if shares:
                    row["block"] = moe.held_block(N, k, E, E_all)
                if shares == 2:
                    row.update(sum_alone(
                        args, timed, platform, x, router, k, E, score, bias,
                        scale, row["block"],
                        moe.expert_tiles(h, inter, jnp.int8, jnp.bfloat16)))
                if args.outputs and shares in (0, 2):
                    first = np.asarray(got[0].astype(jnp.float32))
                    path = os.path.join(args.outputs,
                                        f"{name}.{routing}.npy")
                    if os.path.exists(path):
                        before = np.load(path)
                        row["largest_difference_from_before"] = float(
                            np.abs(first - before).max())
                        row["largest_before"] = float(np.abs(before).max())
                    else:
                        os.makedirs(args.outputs, exist_ok=True)
                        np.save(path, first)
                if args.profile and shares in (0, 2):
                    row["top_ops_us_a_layer"] = top_ops(
                        os.path.join(args.profile, f"{name}.{routing}"),
                        chain(shares), (x, *stacks), args.layers)
                table.append(row)
                print(json.dumps(row), file=sys.stderr, flush=True)
        del stacks
    return table


def sum_alone(args, timed, platform, x, router, k, E, score, bias, scale,
              B, planes):
    """The columns of a round's sum by token alone (module text): the
    first round's block of this routing, ``planes`` planes of random
    rows, ``--sum-rounds`` rounds a call each adding the block to the
    sum it carries."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.ops import moe

    N, h = x.shape
    top_p, top_i = moe.route(x, router, k, score=score, bias=bias,
                             scale=scale)
    here = np.flatnonzero(np.asarray(top_i).reshape(-1) < E)[:B]
    tok = np.full(B, N, np.int32)
    tok[:len(here)] = here // k
    weight = np.zeros(B, np.float32)
    weight[:len(here)] = np.asarray(top_p).reshape(-1)[here]
    keys = jax.random.split(jax.random.PRNGKey(B), planes)
    ys = [jax.random.normal(key, (B, h)).astype(jnp.bfloat16)
          for key in keys]
    tok, weight = jnp.asarray(tok), jnp.asarray(weight)
    rounds = args.sum_rounds

    def scatter(acc, *ys):
        y = ys[0].astype(jnp.float32)
        for plane in ys[1:]:
            y = y + plane.astype(jnp.float32)
        return acc.at[tok].add(y * weight[:, None], mode="drop",
                               indices_are_sorted=True)

    def kernel(acc, *ys):
        return moe._held_sum(acc, ys, tok, weight, N)

    def chain(one_round):
        return jax.jit(lambda *ys: jax.lax.fori_loop(
            0, rounds, lambda _, acc: one_round(acc, *ys),
            jnp.zeros((N, h), jnp.float32)))

    def device_us(fn, calls=3):
        """Device microseconds a round: the busy time of a capture of
        ``calls`` calls (the chip), else the host's clock."""
        got, us = timed(fn, *ys)
        us *= args.layers / rounds
        if platform != "cpu":
            busy = captured(tempfile.mkdtemp(prefix="moe_sum_"), fn, ys,
                            calls)["busy_s"]
            us = 1e6 * busy / (calls * rounds)
        return np.asarray(got), round(us, 1)

    before, scatter_us = device_us(chain(scatter))
    cols = {"sum_live": len(here), "sum_scatter_us": scatter_us,
            "sum_clock": "host" if platform == "cpu" else "device",
            "sum_largest": float(np.abs(before).max())}
    if hasattr(moe, "_held_sum"):
        after, cols["sum_kernel_us"] = device_us(chain(kernel))
        cols["sum_largest_difference"] = float(np.abs(after - before).max())
    return cols


def captured(trace_dir, fn, operands, calls):
    """What chipbench/xplane.py reads off a capture of ``calls`` runs
    of fn (compiled before it); ValueError where the capture holds no
    device plane (the CPU)."""
    import jax

    from chipbench import xplane
    jax.block_until_ready(fn(*operands))
    with jax.profiler.trace(trace_dir):
        for _ in range(calls):
            jax.block_until_ready(fn(*operands))
    return xplane.reduce_file(xplane.find_xplane(trace_dir))


def top_ops(trace_dir, fn, operands, layers, calls=3):
    """The device operations of ``calls`` runs of fn that took most of
    their own time, in microseconds a layer."""
    try:
        top = captured(trace_dir, fn, operands, calls)["top_ops"]
    except ValueError:      # a rehearsal on the CPU: no device plane
        return []
    return [[name, round(1e6 * sec / (calls * layers), 1)]
            for name, sec in top]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeat", type=int, default=10)
    ap.add_argument("--layers", type=int, default=6,
                    help="layers in the timed chain")
    ap.add_argument("--stack", type=int, default=2,
                    help="layers of weights resident")
    ap.add_argument("--models", default=",".join(MODELS))
    ap.add_argument("--pass-rows", default="16,64,128,256")
    ap.add_argument("--out", default="")
    ap.add_argument("--held", action="store_true",
                    help="the table of a chip's share of the experts "
                    "(module text) instead of the whole-layer one")
    ap.add_argument("--held-models", default=",".join(HELD))
    ap.add_argument("--profile", default="",
                    help="--held: capture three calls of each row here "
                    "and list the operations that took most of a layer")
    ap.add_argument("--outputs", default="",
                    help="--held: keep the first layer's sums here, "
                    "compare with what is there")
    ap.add_argument("--sum-rounds", type=int, default=24,
                    help="--held: rounds a call of the sum by token alone")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU: widths 128 x 256, the "
                    "kernels in interpret mode, the first four shapes")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.models import quant
    from production_stack_tpu.ops import moe

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("moe_prefill_table: JAX found no accelerator",
              file=sys.stderr)
        return 3
    shapes = SHAPES
    if dev.platform == "cpu":
        from production_stack_tpu.ops import pallas_paged
        pallas_paged.set_flash_enabled(True)
        shapes = SHAPES[:4]
    act = jax.nn.silu
    L = args.stack

    def timed(fn, *operands):
        out = jax.block_until_ready(fn(*operands))
        times = []
        for _ in range(args.repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(time.perf_counter() - t0)
        return out, 1e6 * float(np.median(times)) / args.layers

    table = held_table(args, timed, dev.platform) if args.held else []
    for name in [] if args.held else args.models.split(","):
        E, h, inter, score, bias_sd, renorm, scale = MODELS[name]
        if dev.platform == "cpu":
            h, inter = 128, 256
        keys = iter(jax.random.split(jax.random.PRNGKey(len(name)), 8))
        stacks = [quant.quantize_tensor(
            (0.02 * jax.random.normal(next(keys), dims, jnp.float32)
             ).astype(jnp.bfloat16))
            for dims in ((L, E, h, inter), (L, E, h, inter),
                         (L, E, inter, h))]
        router = (0.02 * jax.random.normal(next(keys), (h, E))
                  ).astype(jnp.bfloat16)
        bias = (bias_sd * jax.random.normal(next(keys), (E,))
                if bias_sd else None)
        expert_bytes = sum(
            x.size * x.dtype.itemsize
            for w in stacks for x in jax.tree.leaves(w)) // (L * E)
        layers = jnp.arange(args.layers, dtype=jnp.int32) % L

        for rows, tokens, real in shapes:
            N = rows * tokens
            x = jax.random.normal(jax.random.PRNGKey(N),
                                  (N, h)).astype(jnp.bfloat16)
            valid = (jnp.arange(tokens) < int(real * tokens))
            valid = jnp.broadcast_to(valid, (rows, tokens)).reshape(N)

            def routed(x):
                top_p, top_i = moe.route(x, router, TOP_K,
                                         renormalize=renorm, score=score,
                                         bias=bias, scale=scale)
                return top_p * valid[:, None], top_i

            def renormed(y):
                """The next layer's input: one layer's output at the
                input's scale."""
                y = y.astype(jnp.float32)
                return (y * jax.lax.rsqrt(jnp.mean(
                    y * y, axis=-1, keepdims=True) + 1e-6)
                    ).astype(jnp.bfloat16)

            capacity = min(N, moe.capacity_for(16 * tokens, E, TOP_K, 2.0))

            def chain_sliced(one_layer):
                """The layer's experts as the scan's xs."""
                def run(x, *stacks):
                    def body(x, layer):
                        w = [jax.tree.map(lambda a: a[layer], s)
                             for s in stacks]
                        top_p, top_i = routed(x)
                        y = one_layer(x, top_p, top_i, w)
                        return renormed(y), y
                    return jax.lax.scan(body, x, layers)[1]
                return jax.jit(run)

            def chain_in_place(R):
                """The stacks whole, the layer an index; passes of R
                rows (read when the chain is traced)."""
                def run(x, *stacks):
                    moe.GROUPED_ROWS = R

                    def body(x, layer):
                        top_p, top_i = routed(x)
                        y, work = moe._moe_grouped(
                            x, top_p, top_i, *stacks, act, valid, layer)
                        return renormed(y), (y, work.expert_rows)
                    return jax.lax.scan(body, x, layers)[1]
                return jax.jit(run)

            def ragged(x, top_p, top_i, w):
                flat_e = jnp.where(jnp.repeat(valid, TOP_K),
                                   top_i.reshape(-1), E)
                order = jnp.argsort(flat_e, stable=True)
                sizes = jnp.bincount(flat_e, length=E + 1)[:E]
                xs = x[order // TOP_K]
                es = jnp.minimum(flat_e[order], E - 1)

                def dot(a, m):
                    y = jax.lax.ragged_dot(
                        a, m["w8"].astype(a.dtype), sizes,
                        preferred_element_type=jnp.float32)
                    return y * m["scale"][es]
                a = (act(dot(xs, w[0])) * dot(xs, w[1])).astype(x.dtype)
                ys = dot(a, w[2])
                back = jnp.zeros_like(ys).at[order].set(ys)
                return jnp.sum((back * top_p.reshape(-1)[:, None]
                                ).reshape(N, TOP_K, h), axis=1
                               ).astype(x.dtype)

            top_p, top_i = routed(x)
            chosen = np.asarray(top_i)[np.asarray(valid)]
            hit = len(np.unique(chosen))
            per_expert = np.bincount(chosen.reshape(-1), minlength=E)
            row = {"model": name, "rows": rows, "tokens": tokens,
                   "real": real, "experts_hit": hit,
                   "rows_an_expert_max": int(per_expert.max()),
                   "routed_rows": int(per_expert.sum()),
                   "floor_us": round(
                       1e6 * hit * expert_bytes / HBM_BYTES_PER_S, 1)}
            rank = np.zeros(E, np.int64)
            dropped = 0
            for e in chosen.reshape(-1):
                dropped += rank[e] >= capacity
                rank[e] += 1
            row["dispatch_capacity"] = capacity
            row["dispatch_dropped"] = int(dropped)

            want, row["exact_us"] = timed(chain_sliced(
                lambda x, p, i, w: moe._moe_exact(x, p, i, *w, act)),
                x, *stacks)
            if rows > 1:
                _, row["dispatch_us"] = timed(chain_sliced(
                    lambda x, p, i, w: moe._moe_dispatch(
                        x, p, i, *w, act, capacity, valid=valid)),
                    x, *stacks)
            for R in (int(r) for r in args.pass_rows.split(",")):
                try:
                    (got, multiplied), us = timed(chain_in_place(R),
                                                  x, *stacks)
                except Exception as e:    # a height the compiler refuses
                    row[f"grouped_{R}_error"] = str(e)[:200]
                    continue
                row[f"grouped_{R}_us"] = us
                row[f"grouped_{R}_expert_rows"] = int(multiplied[0])
                # the chain's first layer: the same input on both
                row[f"grouped_{R}_largest_difference"] = float(
                    jnp.max(jnp.abs(got[0].astype(jnp.float32)
                                    - want[0].astype(jnp.float32))))
            try:
                _, row["ragged_us"] = timed(chain_sliced(ragged),
                                            x, *stacks)
            except Exception as e:
                row["ragged_error"] = str(e)[:200]
            for key in list(row):
                if key.endswith("_us"):
                    row[key] = round(row[key], 1)
            table.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)
        del stacks
    line = json.dumps({"platform": dev.platform,
                       "device_kind": dev.device_kind, "rows": table})
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
