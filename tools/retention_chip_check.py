#!/usr/bin/env python3
"""The serving path of a model whose only cache is state pages (power
retention layers: ops/retention.py) against the plain reference, on the
chip, at Brumby-14B's published widths (the cut of
chipbench/configs/brumby-14b-int8-l10.json): what the benchmark's probe
cannot see.

The probe of ``chipbench`` asks ONE token of three prompts of at most a
few hundred tokens: one chunk boundary at most, no decode step on a
state that thousands of tokens built. This script compares, for
``--rows`` rows whose contexts are spread over ``--contexts LO HI``
(8k-16k): each row's prompt prefilled through its state page in chunks
of ``--chunks`` tokens (2048 and 512 by turns of the rows: the chunked
rule carrying ``S`` and ``z`` across 4-31 chunk boundaries and a padded
last chunk), then ``--decode-steps`` teacher-forced decode steps of all
rows in one batch of 16 beside parked rows, in windows of 8 as the
engine fuses them (the window form: the step kernel only reads the
pages, the window's own keys answer beside them, and
``retention_window_fold`` writes the same pages once between windows),
against the reference's ONE full forward pass
over each row's whole sequence (the attention form: a ``[T, T]`` matrix
a head in blocks of 1024 queries, no state at all):

- ``logits``: |served - reference| over the reference's top-20
  log-probabilities, the largest at EVERY row-step within
  ``--tolerance``. 0.3 is the probe's own limit (chipbench/
  reference.py: about twice what two right implementations differ by in
  bfloat16), and the file's ``harness.probe`` holds the cell to it.

``lean``: the first row served once more
into a spare page with the STATE KEPT IN BFLOAT16 (``S`` and ``z``
rounded after every chunk of ``--lean-chunk`` 256 tokens and every
decode window: what a bfloat16 state page would hold at the cell's
chunk and window; the products stay float32). At the cell's bfloat16
activations it is a REPORT: the activations' own rounding reads 0.13-0.16 here and
a bfloat16 state 0.15 (my chip runs, PR 47: the errors of 8256
monomials are independent and average out), so no tolerance on the
logits tells the two apart. ``--dtype float32`` serves the same path
with float32 activations and every product at full precision (the
kernels' float32 case, int8 weights as they are), where the state's precision is the only thing under
float32: there ``--tolerance`` defaults to 0.02 (36 x the 0.00055 the
served path reads at 8k and 32 steps; a bfloat16 state reads 0.027
rounded once a window of 8, my chip run, PR 48, and 0.07 rounded every
step, PR 47: 48 and 133 x the served path) and lean is a CONTROL
that must fail ``logits`` or stand ``FARTHER`` (3) times as far from
the reference as the served path does on that row. ``--control
NAME:KEY=JSON`` (repeatable) reads the same served numbers against the
reference with one key changed (``nogate:ret_control="no_gate"``,
``nonorm:ret_control="no_norm"``, ``deg1:ret_control="degree_one"``);
such a control must fail ``logits`` held as the reference.
``--report`` reads alike and decides nothing (``bf16:round_to=
"bfloat16"``).

One JSON line last (and in chiprun_out/retention_chip_check.json); exit
0 only if the served path passes and every control fails. On the chip
only (``--tiny --allow-cpu`` rehearses; ``PSTPU_FLASH=1`` with heads of
128 runs the kernels in interpret mode):

    python3 tools/retention_chip_check.py --rows 2 \\
        --control 'nogate:ret_control="no_gate"'
    python3 tools/retention_chip_check.py --rows 1 \\
        --contexts 8192 8192 --dtype float32
"""

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = os.path.join(ROOT, "chipbench", "configs",
                      "brumby-14b-int8-l10.json")
TOP = 20
BATCH = 16
WINDOW = 8
FARTHER = 3.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--contexts", type=int, nargs=2, default=(8192, 16000),
                    metavar=("LO", "HI"))
    ap.add_argument("--chunks", type=int, nargs="+", default=(2048, 512))
    ap.add_argument("--decode-steps", type=int, default=32,
                    help=f"in windows of {WINDOW}")
    ap.add_argument("--lean-chunk", type=int, default=256,
                    help="the lean pass's prefill chunk: the state is "
                         "rounded once a chunk, as a bfloat16 page would "
                         "be at the cell's chunk")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--report", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--dtype", choices=("bfloat16", "float32"),
                    default="bfloat16",
                    help="the activations' dtype: the cell's, or "
                         "float32, where the state's precision is the "
                         "only thing under float32 and lean must fail")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="default 0.3 at bfloat16, 0.02 at float32")
    ap.add_argument("--tiny", action="store_true",
                    help="a toy's sizes (rehearsal)")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.engine_child import model_config
    from chipbench.references import brumby as ref
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    from production_stack_tpu.ops import retention

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("retention_chip_check: JAX found no accelerator",
              file=sys.stderr)
        return 3
    with open(CONFIG) as f:
        hf = json.load(f)
    chunks = list(args.chunks)
    lean_chunk = args.lean_chunk // (16 if args.tiny else 1)
    if args.tiny:
        hf.update(hidden_size=128, intermediate_size=128, head_dim=32,
                  num_attention_heads=4, num_key_value_heads=2,
                  vocab_size=512, num_hidden_layers=2)
        chunks = [c // 16 for c in chunks]
    if args.layers:
        hf["num_hidden_layers"] = args.layers
    cfg = dataclasses.replace(model_config(hf, hf["name"]),
                              dtype=jnp.dtype(args.dtype))
    if args.tolerance is None:
        args.tolerance = 0.3 if args.dtype == "bfloat16" else 0.02
    lo, hi = args.contexts
    R, N = args.rows, args.decode_steps
    if N % WINDOW or not retention.windowed(1, WINDOW):
        ap.error(f"--decode-steps: whole windows of {WINDOW} steps")
    t0 = time.monotonic()
    params = llama.init_params(cfg, jax.random.PRNGKey(args.seed),
                               quantization=hf["quantization"])
    rng = np.random.default_rng(args.seed)
    # a page a row, one more for the lean pass, and the trash page
    cache = kv_pool.cache_for(cfg, R + 2, 0)
    tables = np.zeros((BATCH, 1), np.int32)
    tables[:R + 1, 0] = 1 + np.arange(R + 1)
    tables = jnp.asarray(tables)
    parked = cfg.max_position_embeddings

    def forward(cache, params, tables, tokens, starts, lengths):
        T = tokens.shape[1]
        positions = starts[:, None] + jnp.arange(T)[None, :]
        valid = ((jnp.arange(T)[None, :] < lengths[:, None])
                 & (starts < parked)[:, None])
        logits, cache, _ = llama.forward(
            params, cfg, tokens, positions, cache, block_tables=tables,
            token_valid=valid)
        last = jnp.take_along_axis(
            logits, jnp.clip(lengths - 1, 0, T - 1)[:, None, None], axis=1)
        return jax.nn.log_softmax(last[:, 0], axis=-1), cache

    def window(cache, params, tables, tokens, starts):
        """One decode window as engine/runner.py fuses it, its tokens
        [BATCH, WINDOW] given: -> ([WINDOW, BATCH, V] log-probabilities,
        the cache after the window's fold)."""
        win = llama.open_window(cfg, tables, starts, WINDOW,
                                starts < parked)

        def body(carry, toks):
            cache, win, pos = carry
            logits, cache, _, win = llama.forward_in_window(
                params, cfg, toks[:, None], pos[:, None], cache, win,
                block_tables=tables, token_valid=(pos < parked)[:, None])
            return ((cache, win, pos + 1),
                    jax.nn.log_softmax(logits[:, 0], axis=-1))
        (cache, win, _), lps = jax.lax.scan(body, (cache, win, starts),
                                            tokens.T)
        return lps, llama.close_window(cache, win)

    # the parameters are an ARGUMENT: closed over, 4.9 GB of weights
    # become constants of the lowering (tools/dsa_chip_check.py)
    step = jax.jit(forward, donate_argnums=0)
    steps = jax.jit(window, donate_argnums=0)
    if args.dtype == "float32":
        # float32 activations multiply in bfloat16 passes on the TPU
        # unless told otherwise (0.07 against the reference at 8k, my
        # chip run, PR 47): the state check multiplies at full precision
        jax.config.update("jax_default_matmul_precision", "highest")

    @jax.jit
    def lean(cache):
        """The state as a bfloat16 page would hold it."""
        def rounded(a):
            # (a float32 -> bfloat16 -> float32 convert pair is
            # simplified away by the TPU's compiler, and the control
            # then reads what the served path reads to the last digit)
            return jax.lax.reduce_precision(a, 8, 7)
        return cache._replace(state=rounded(cache.state),
                              norm=rounded(cache.norm))

    def prefill(cache, row, tokens, chunk, after=None):
        for start in range(0, len(tokens), chunk):
            part = tokens[start:start + chunk]
            bucket = next(b for b in (chunk // 8, chunk // 4, chunk // 2,
                                      chunk) if b >= len(part))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(part)] = part
            _, cache = step(cache, params, tables[row:row + 1],
                            jnp.asarray(padded),
                            jnp.asarray([start], jnp.int32),
                            jnp.asarray([len(part)], jnp.int32))
            if after is not None:
                cache = after(cache)
        return cache

    def decode(cache, rows, after=None):
        """N teacher-forced steps of ``rows`` {batch row: sequence row}
        beside parked rows -> [N] arrays [BATCH, V]."""
        out = []
        for t in range(0, N, WINDOW):
            tokens = np.zeros((BATCH, WINDOW), np.int32)
            starts = np.full((BATCH,), parked, np.int32)
            for b, r in rows.items():
                tokens[b] = seqs[r][lens[r] + t:lens[r] + t + WINDOW]
                starts[b] = lens[r] + t
            lps, cache = steps(cache, params, tables, jnp.asarray(tokens),
                               jnp.asarray(starts))
            if after is not None:
                cache = after(cache)
            out += list(np.asarray(lps))
        return out, cache

    lens = np.linspace(lo, hi, R).astype(int)
    seqs = [rng.integers(0, 256, n + N) for n in lens]
    used = [chunks[r % len(chunks)] for r in range(R)]
    for r in range(R):
        cache = prefill(cache, r, seqs[r][:lens[r]], used[r])
    served, cache = decode(cache, {r: r for r in range(R)})
    # the lean pass: row 0 again, in the spare page R, the state rounded
    cache = prefill(cache, R, seqs[0][:lens[0]], lean_chunk, after=lean)
    leaned, cache = decode(cache, {R: 0}, after=lean)
    served_s = round(time.monotonic() - t0, 1)

    def gaps(mine, want, at):
        return [float(np.abs(mine[t][at][top] - want[t][top]).max())
                for t in range(N)
                for top in [np.argsort(-want[t])[:TOP]]]

    def read(ref_hf, rows):
        """``logits`` against one reference, and its log-probabilities
        at the watched row-steps."""
        worst, wants = [], []
        for r in rows:
            watch = [int(lens[r]) - 1 + t for t in range(1, N + 1)]
            want = np.asarray(ref.logprobs(params, ref_hf, seqs[r],
                                           at=watch))
            wants.append(want)
            worst += gaps(served, want, r)
        return {"top20_abs_logprob_diff": {
                    "mean": float(np.mean(worst)),
                    "largest": float(np.max(worst))},
                "logits": bool(np.max(worst) <= args.tolerance)}, wants

    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "layers": hf["num_hidden_layers"], "seed": args.seed,
           "rows": R, "steps": N, "contexts": [int(n) for n in lens],
           "chunks": used, "dtype": args.dtype,
           "tolerance": args.tolerance,
           "mixer_paths": [retention.retention_path(
               t, cfg.head_dim_, cfg.num_kv_heads, steps=w)
               for t, w in ((1, WINDOW), (used[0], 1))],
           "retention_chunk": retention.CHUNK, "served_seconds": served_s,
           "controls": {}, "reports": {}}
    out["served"], true = read(hf, range(R))
    bad, good = gaps(leaned, true[0], R), gaps(served, true[0], 0)
    got = {"row": 0, "context": int(lens[0]),
           "top20_abs_logprob_diff": {"mean": float(np.mean(bad)),
                                      "largest": float(np.max(bad))},
           "logits": bool(np.max(bad) <= args.tolerance),
           "served_largest": float(np.max(good))}
    got["fails"] = ([] if got["logits"] else ["logits"]) + (
        ["farther"] if np.max(bad) > FARTHER * np.max(good) else [])
    # at the cell's bfloat16 the activations' rounding (0.13 here)
    # hides the state's: lean is then a report; at float32 it must fail
    out["controls" if args.dtype == "float32" else "reports"]["lean"] = got
    for kind, items in (("controls", args.control),
                        ("reports", args.report)):
        for item in items:
            name, setting = item.split(":", 1)
            key, value = setting.split("=", 1)
            got, _ = read({**hf, key: json.loads(value)}, range(1))
            got["fails"] = [] if got["logits"] else ["logits"]
            out[kind][name] = got
    out["served"]["passes"] = bool(out["served"]["logits"])
    out["ok"] = out["served"]["passes"] and all(
        c["fails"] for c in out["controls"].values())
    out["seconds"] = round(time.monotonic() - t0, 1)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "retention_chip_check.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
