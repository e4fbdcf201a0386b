#!/usr/bin/env python3
"""The hybrid serving path (Gated DeltaNet layers over state pages,
gated attention over the K/V pool) against the plain reference, on the
chip, at Qwen3-Next's published widths (the chip's share of
chipbench/configs/qwen3-next-80b-a3b-int8-l24-e64.json): what the
benchmark's probe cannot see.

The probe of ``chipbench`` asks ONE token of three prompts, the longest
of about 2120 tokens: one chunk boundary, no decode step, no padded
tail after a carried state. This script compares, for ``--rows`` rows
whose contexts are spread over ``--contexts LO HI`` (8k-16k): each
row's prompt prefilled in chunks of 2048 through both caches (the
chunked delta rule carrying its state page across 3-7 chunk
boundaries and a padded last chunk, the prefill kernel in q blocks,
the grouped experts), then ``--decode-steps`` teacher-forced decode
steps of all rows in one batch of 8 beside parked rows (the recurrent
kernel on the same pages, the decode kernel, the list kernel) against
the reference's ONE full forward pass over each row's whole sequence
(the sequential recurrence, attention in blocks):

- ``logits``: |served - reference| over the reference's top-20
  log-probabilities, the largest at EVERY row-step within
  ``--tolerance`` 0.3: the probe's own statistic and the probe's own
  limit (chipbench/reference.py: about twice what two right
  implementations differ by in bfloat16);
- ``lean``, for what a limit on the distance cannot see: against a
  control c the served log-probabilities s (the whole vocabulary,
  every watched row-step) are placed on the line from the reference r
  to c: lean = <s - r, c - r> / <c - r, c - r>, 0 at the reference, 1
  at the control; the limit is 0.5 (tools/dsa_chip_check.py).

``--control NAME:KEY=JSON`` (repeatable) reads the same served numbers
against the reference with one key changed; a control must FAIL: held
as the reference it breaks ``logits``, or the served path leans to the
true reference and away from it. ``nodecay:gdn_control="no_decay"`` (g
= 0), ``beta1:gdn_control="beta_one"``,
``noconv:gdn_control="no_conv_carry"`` (the convolution forgets its
inputs at every 2048-token chunk boundary),
``nogate:attn_control="no_gate"``,
``rotall:attn_control="rotary_all"``, ``top8:num_experts_per_tok=8``.
``--report NAME:KEY=JSON`` reads alike and decides nothing
(``bf16state:gdn_control="state_bf16"``: what a bfloat16 state would
read; ``bf16:round_to="bfloat16"``: the reference with its residual
stream rounded to the served precision between blocks, the noise that
precision alone makes). ``--pad-advances`` serves the first row whose
last chunk is padded once more with the padded positions marked real
(a served path on which padding advances the state), which must break
``logits`` or stand ``FARTHER`` (3) times as far from the reference as
the served path does on that row.

One JSON line last (and in chiprun_out/gdn_chip_check.json); exit 0
only if the served path passes and every control fails. On the chip
only (``--allow-cpu`` rehearses at ``--tiny``):

    python3 tools/gdn_chip_check.py --rows 3 --decode-steps 4 \\
        --control-rows 1 --pad-advances \\
        --control 'nodecay:gdn_control="no_decay"' \\
        --control 'beta1:gdn_control="beta_one"' \\
        --control 'noconv:gdn_control="no_conv_carry"' \\
        --control 'nogate:attn_control="no_gate"' \\
        --control 'rotall:attn_control="rotary_all"' \\
        --control top8:num_experts_per_tok=8 \\
        --report 'bf16state:gdn_control="state_bf16"'
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = os.path.join(ROOT, "chipbench", "configs",
                      "qwen3-next-80b-a3b-int8-l24-e64.json")
TOP = 20
BATCH = 8
FARTHER = 3.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=None,
                    help="whole periods of four (default: the file's)")
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--contexts", type=int, nargs=2, default=(8192, 16000),
                    metavar=("LO", "HI"))
    ap.add_argument("--decode-steps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--report", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--control-rows", type=int, default=1,
                    help="rows the controls are read on, the shortest "
                         "first (a reference pass of 16k tokens takes "
                         "minutes)")
    ap.add_argument("--pad-advances", action="store_true")
    ap.add_argument("--routed-down-std", type=float, default=None,
                    help="draw the routed experts' down_proj at this sd "
                         "(default: the file's assumed."
                         "routed_down_init_std)")
    ap.add_argument("--tolerance", type=float, default=0.3)
    ap.add_argument("--tiny", action="store_true",
                    help="the debug-gdn preset's sizes (rehearsal)")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.engine_child import model_config
    from chipbench.references import qwen3_next as ref
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    from production_stack_tpu.ops import gdn, pallas_paged

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("gdn_chip_check: JAX found no accelerator", file=sys.stderr)
        return 3
    with open(CONFIG) as f:
        hf = json.load(f)
    chunk, bs = 2048, 64
    if args.tiny:
        hf.update(hidden_size=128, moe_intermediate_size=128,
                  shared_expert_intermediate_size=128, head_dim=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  linear_num_key_heads=2, linear_num_value_heads=4,
                  vocab_size=512, num_hidden_layers=8, num_experts=4,
                  num_experts_per_tok=4,
                  deployment={"chips_per_layer": 4, "chip_index": 1,
                              "router_experts": 16})
        hf["assumed"] = {}
        chunk, bs = 32, 16
    if args.layers:
        hf["num_hidden_layers"] = args.layers
    if args.routed_down_std is not None:
        hf["assumed"] = {**hf["assumed"],
                         "routed_down_init_std": args.routed_down_std}
    hf["conv_chunk"] = chunk        # (the no_conv_carry control's)
    cfg = model_config(hf, hf["name"])
    lo, hi = args.contexts
    R, N = args.rows, args.decode_steps
    max_len = -(-(hi + N + 1) // chunk) * chunk
    MB = max_len // bs
    t0 = time.monotonic()
    params = llama.init_params(cfg, jax.random.PRNGKey(args.seed),
                               quantization=hf["quantization"])
    rng = np.random.default_rng(args.seed)
    # one more row's blocks and page: the --pad-advances pass
    cache = kv_pool.cache_for(cfg, (R + 1) * MB + 1, bs, cfg.dtype,
                              state_pages=R + 2)
    tables = np.zeros((BATCH, MB + 1), np.int32)
    tables[:R + 1, :MB] = 1 + np.arange((R + 1) * MB).reshape(R + 1, MB)
    tables[:R + 1, MB] = 1 + np.arange(R + 1)       # the state pages
    tables = jnp.asarray(tables)
    buckets = [b for b in (512, 1024, 2048, 4096, 8192, 16384, 32768)
               if b < max_len] + [max_len]

    def kv_bucket(n):
        return next(b for b in buckets if n <= b)

    def forward(cache, params, tables, tokens, starts, lengths, kv_len):
        T = tokens.shape[1]
        positions = starts[:, None] + jnp.arange(T)[None, :]
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        logits, cache, _ = llama.forward(
            params, cfg, tokens, positions, cache, block_tables=tables,
            kv_len=kv_len, token_valid=valid,
            moe_capacity_tokens=BATCH * T)
        last = jnp.take_along_axis(
            logits, jnp.clip(lengths - 1, 0, T - 1)[:, None, None], axis=1)
        return jax.nn.log_softmax(last[:, 0], axis=-1), cache

    step = jax.jit(forward, static_argnums=6, donate_argnums=0)

    def prefill(cache, row, tokens, pad_real=False):
        """``pad_real``: the fault --pad-advances injects: the last
        chunk's padded positions marked real."""
        for start in range(0, len(tokens), chunk):
            part = tokens[start:start + chunk]
            bucket = next(b for b in (chunk // 8, chunk // 4, chunk // 2,
                                      chunk) if b >= len(part))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(part)] = part
            lps, cache = step(cache, params, tables[row:row + 1],
                              jnp.asarray(padded),
                              jnp.asarray([start], jnp.int32),
                              jnp.asarray([bucket if pad_real
                                           else len(part)], jnp.int32),
                              kv_bucket(start + bucket))
        return cache

    def decode(cache, rows):
        """N teacher-forced steps of ``rows`` {batch row: sequence row}
        beside parked rows -> [N] arrays [BATCH, V]."""
        out, parked = [], MB * bs
        for t in range(N):
            tokens = np.zeros((BATCH, 1), np.int32)
            starts = np.full((BATCH,), parked, np.int32)
            lengths = np.zeros((BATCH,), np.int32)
            for b, r in rows.items():
                tokens[b, 0] = seqs[r][lens[r] + t]
                starts[b], lengths[b] = lens[r] + t, 1
            lps, cache = step(cache, params, tables, jnp.asarray(tokens),
                              jnp.asarray(starts), jnp.asarray(lengths),
                              kv_bucket(int(lens.max()) + N))
            out.append(np.asarray(lps))
        return out, cache

    lens = np.linspace(lo, hi, R).astype(int)
    seqs = [rng.integers(0, 256, n + N) for n in lens]
    for r in range(R):
        cache = prefill(cache, r, seqs[r][:lens[r]])
    served, cache = decode(cache, {r: r for r in range(R)})
    faulty = None
    padded_rows = [r for r in range(R) if lens[r] % chunk]
    if args.pad_advances and padded_rows:   # again, in the spare row R
        pr = padded_rows[0]
        cache = prefill(cache, R, seqs[pr][:lens[pr]], pad_real=True)
        faulty, cache = decode(cache, {R: pr})
    served_s = round(time.monotonic() - t0, 1)

    def read(ref_hf, rows, mine=None, at=None):
        """``logits`` against one reference, and its log-probabilities
        at the watched row-steps [rows, N, V]."""
        gaps, wants = [], []
        for r in rows:
            watch = [int(lens[r]) + t for t in range(N)]
            want = np.asarray(ref.logprobs(params, ref_hf, seqs[r]))[watch]
            wants.append(want)
            for t in range(N):
                top = np.argsort(-want[t])[:TOP]
                got = (mine or served)[t][r if at is None else at]
                gaps.append(float(np.abs(got[top] - want[t][top]).max()))
        return {"top20_abs_logprob_diff": {
                    "mean": float(np.mean(gaps)),
                    "largest": float(np.max(gaps))},
                "logits": bool(np.max(gaps) <= args.tolerance)
                }, np.stack(wants)

    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "layers": hf["num_hidden_layers"], "seed": args.seed,
           "rows": R, "steps": N,
           "routed_down_init_std": cfg.routed_down_init_std or 0.02,
           "contexts": [int(n) for n in lens], "tolerance": args.tolerance,
           "attention_paths": [pallas_paged.attention_path(
               t, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim_, bs)
               for t in (1, chunk)],
           "mixer_paths": [gdn.gdn_path(t) for t in (1, chunk)],
           "gdn_chunk": gdn.CHUNK, "served_seconds": served_s,
           "controls": {}, "reports": {}}
    out["served"], true = read(hf, range(R))
    rows = range(min(R, args.control_rows))
    for kind, items in (("controls", args.control),
                        ("reports", args.report)):
        for item in items:
            name, setting = item.split(":", 1)
            key, value = setting.split("=", 1)
            got, theirs = read({**hf, key: json.loads(value)}, rows)
            mine = np.stack([[served[t][r] for t in range(N)]
                             for r in rows])
            away = (theirs - true[:len(rows)]).astype(np.float64)
            off = (mine - true[:len(rows)]).astype(np.float64)
            lean = float((off * away).sum()
                         / max((away * away).sum(), 1e-30))
            got.update(lean=lean, control_rms=float(
                np.sqrt((away ** 2).mean())))
            got["fails"] = ([] if got["logits"] else ["logits"]) + (
                ["lean"] if lean < 0.5 else [])
            out[kind][name] = got
    if faulty is not None:
        # the served path with the fault against the TRUE reference
        def gaps(mine, at):
            return [float(np.abs(mine[t][at][top] - true[pr][t][top]).max())
                    for t in range(N)
                    for top in [np.argsort(-true[pr][t])[:TOP]]]
        bad, good = gaps(faulty, R), gaps(served, pr)
        got = {"row": pr, "context": int(lens[pr]),
               "top20_abs_logprob_diff": {"mean": float(np.mean(bad)),
                                          "largest": float(np.max(bad))},
               "logits": bool(np.max(bad) <= args.tolerance),
               "served_largest": float(np.max(good))}
        got["fails"] = ([] if got["logits"] else ["logits"]) + (
            ["farther"] if got["top20_abs_logprob_diff"]["largest"]
            > FARTHER * got["served_largest"] else [])
        out["controls"]["pad_advances"] = got
    out["served"]["passes"] = bool(
        out["served"]["logits"]
        and all(c.get("lean", 0.0) < 0.5
                for c in out["controls"].values()))
    out["ok"] = out["served"]["passes"] and all(
        c["fails"] for c in out["controls"].values())
    out["seconds"] = round(time.monotonic() - t0, 1)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gdn_chip_check.json"),
              "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
