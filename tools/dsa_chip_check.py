#!/usr/bin/env python3
"""The sparse-attention serving path against the plain reference, on
the chip, at GLM-5's published widths (the chip's share of
chipbench/configs/glm-5-int8-l7-e16.json): what the benchmark's probe
cannot see.

The probe of ``chipbench`` asks ONE token of three prompts, the longest
of about 2120 tokens, and with random weights attention is nearly flat:
a wrong selection of 2048 positions barely moves a logit. This script
compares, for ``--rows`` rows whose contexts are spread over
``--contexts LO HI`` (8k-16k),

- **decode steps** (``logits``): each row's prompt prefilled in chunks
  of 2048 through both pools (the prefill kernel under the selection's
  mask, the grouped experts), then ``--decode-steps`` teacher-forced
  decode steps of all rows in one batch of 8 beside parked rows (index
  scores, selection, the decode kernel under the mask, the list kernel
  in tiles) against the reference's one full forward pass over each
  row's whole sequence: |served - reference| over the reference's
  top-20 log-probabilities, the largest at EVERY row-step within
  ``--tolerance`` 0.3: the probe's own statistic and the probe's own
  limit (chipbench/reference.py: about twice what two right
  implementations differ by in bfloat16);
- **the selected sets themselves** (``selection``): of the positions
  the reference selects for each decode query, in every layer, the
  share the served path chose too (``ops/dsa.tap``), the least over
  layers and steps at or above ``--share`` 0.85. bfloat16 scores
  against float32 ones swap positions at the threshold and nothing
  else may differ: the least share measured by layer was 93.2-99.3 %
  (PERF.md, PR 40), a wrong rule reads what chance gives, 2048 of the
  context (12-26 %), and the limit stands between with room on both
  sides;
- **what the held experts add** (``lean``): a sixteenth of a layer's
  experts at the file's ``assumed.routed_down_init_std`` moves a
  log-probability by less than bfloat16 does, so no limit on the
  distance to the reference sees top-4 for top-8 or a routing scale
  of 1.0. Against a control c the served log-probabilities s (the
  whole vocabulary, every watched row-step) are therefore placed on
  the line from the reference r to c: lean = <s - r, c - r> /
  <c - r, c - r>, 0 at the reference, 1 at the control; rounding that
  is not along c - r averages out over the row-steps' hidden values.
  The limit is 0.5, the point as far from one as from the other (s is
  nearer r than c exactly when lean < 0.5): nothing measured goes
  into it. What the line resolves is printed beside it
  (``control_rms``: how far the control stands from the reference, a
  log-probability; ``rest_rms``: how far the served path stands from
  its place on the line; ``lean_se``: the standard error that gives
  if it is independent of the line, which rounding is not quite:
  both pass the final norm and the head, and at the 128-wide
  rehearsal size bfloat16 alone leans 0.3, float32 0.000).

``--control NAME:KEY=JSON`` (repeatable) reads the same served numbers
against the reference with one key changed. A control must FAIL: held
as the reference it breaks ``logits`` or ``selection``, or the served
path leans to the true reference and away from it (lean < 0.5).
``fp8:round_to="float8_e4m3fn"`` (activations in the nearest precision
below), ``first:select_control="first"`` (the first 2048 positions for
the selection), ``top4:num_experts_per_tok=4``,
``scale1:routed_scaling_factor=1.0``. The served path passes only if
it holds ``logits`` and ``selection`` against the true reference and
leans to it against every control; the exit code is 0 only if it
passes and every control fails.

One JSON line last (and in chiprun_out/dsa_chip_check.json). On the
chip only (``--allow-cpu`` rehearses at ``--tiny``):

    python3 tools/dsa_chip_check.py --rows 3 --decode-steps 4 \
        --control-rows 1 \
        --control 'fp8:round_to="float8_e4m3fn"' \
        --control 'first:select_control="first"' \
        --control top4:num_experts_per_tok=4 \
        --control scale1:routed_scaling_factor=1.0
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
                      "glm-5-int8-l7-e16.json")
TOP = 20
BATCH = 8


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=None,
                    help="1 dense + (layers - 1) expert layers "
                         "(default: the file's)")
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--contexts", type=int, nargs=2, default=(8192, 16000),
                    metavar=("LO", "HI"))
    ap.add_argument("--decode-steps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--control-rows", type=int, default=2,
                    help="rows the controls are read on, the shortest "
                         "first (a reference pass of 16k tokens takes "
                         "minutes)")
    ap.add_argument("--tolerance", type=float, default=0.3)
    ap.add_argument("--share", type=float, default=0.85)
    ap.add_argument("--tiny", action="store_true",
                    help="the debug-dsa preset's sizes (rehearsal)")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.engine_child import model_config
    from chipbench.references import glm_moe_dsa as ref
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    from production_stack_tpu.ops import dsa, pallas_paged

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("dsa_chip_check: JAX found no accelerator", file=sys.stderr)
        return 3
    with open(CONFIG) as f:
        hf = json.load(f)
    chunk, bs = 2048, 64
    if args.tiny:
        hf.update(hidden_size=128, intermediate_size=256, q_lora_rank=64,
                  kv_lora_rank=128, qk_nope_head_dim=32,
                  qk_rope_head_dim=16, v_head_dim=32,
                  num_attention_heads=4, moe_intermediate_size=128,
                  index_n_heads=4, index_head_dim=32, index_topk=16,
                  vocab_size=512, num_hidden_layers=3, n_routed_experts=4,
                  deployment={"chips_per_layer": 4, "chip_index": 1,
                              "router_experts": 16},
                  num_experts_per_tok=4)
        chunk, bs = 32, 16
    if args.layers:
        hf["num_hidden_layers"] = args.layers
    cfg = model_config(hf, hf["name"])
    lo, hi = args.contexts
    R, N = args.rows, args.decode_steps
    max_len = -(-(hi + N + 1) // chunk) * chunk
    MB = max_len // bs
    t0 = time.monotonic()
    params = llama.init_params(cfg, jax.random.PRNGKey(args.seed),
                               quantization=hf["quantization"])
    rng = np.random.default_rng(args.seed)
    cache = kv_pool.cache_for(cfg, R * MB + 1, bs, cfg.dtype)
    tables = np.zeros((BATCH, MB), np.int32)
    tables[:R] = 1 + np.arange(R * MB).reshape(R, MB)
    tables = jnp.asarray(tables)
    buckets = [b for b in (512, 1024, 2048, 4096, 8192, 16384, 32768)
               if b < max_len] + [max_len]

    def kv_bucket(n):
        return next(b for b in buckets if n <= b)

    # the served path's selections, a layer at a time in the order the
    # layers ran (ops/dsa.tap): kept for the decode steps alone
    taps, keep = [], [False]

    def tap(layer, positions, mask):
        if keep[0]:
            taps.append((int(layer), np.asarray(positions)[:, 0],
                         np.asarray(mask, np.float32)[:, 0] > 0))

    def forward(cache, params, tables, tokens, starts, lengths, kv_len):
        T = tokens.shape[1]
        positions = starts[:, None] + jnp.arange(T)[None, :]
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        logits, cache, _ = llama.forward(
            params, cfg, tokens, positions, cache, block_tables=tables,
            kv_len=kv_len, token_valid=valid,
            moe_capacity_tokens=BATCH * T)
        last = jnp.take_along_axis(
            logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
        return jax.nn.log_softmax(last[:, 0], axis=-1), cache

    step = jax.jit(forward, static_argnums=6, donate_argnums=0)

    def prefill(cache, row, tokens):
        for start in range(0, len(tokens), chunk):
            part = tokens[start:start + chunk]
            bucket = next(b for b in (chunk // 8, chunk // 4, chunk // 2,
                                      chunk) if b >= len(part))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(part)] = part
            _, cache = step(cache, params, tables[row:row + 1],
                            jnp.asarray(padded),
                            jnp.asarray([start], jnp.int32),
                            jnp.asarray([len(part)], jnp.int32),
                            kv_bucket(start + bucket))
        return cache

    lens = np.linspace(lo, hi, R).astype(int)
    seqs = [rng.integers(0, 256, n + N) for n in lens]
    for r in range(R):
        cache = prefill(cache, r, seqs[r][:lens[r]])
    # (set now: the prefill executables, traced above, hand it nothing)
    dsa.tap = tap
    served, keep[0] = [], True
    parked = MB * bs
    for t in range(N):
        tokens = np.zeros((BATCH, 1), np.int32)
        starts = np.full((BATCH,), parked, np.int32)
        lengths = np.zeros((BATCH,), np.int32)
        for r in range(R):
            tokens[r, 0] = seqs[r][lens[r] + t]
            starts[r], lengths[r] = lens[r] + t, 1
        lps, cache = step(cache, params, tables, jnp.asarray(tokens),
                          jnp.asarray(starts), jnp.asarray(lengths),
                          kv_bucket(int(lens.max()) + N))
        served.append(np.asarray(lps))
    jax.effects_barrier()
    keep[0], dsa.tap = False, None
    L = hf["num_hidden_layers"]
    # taps: N steps x L layers, each (layer, positions [B], mask [B, S])
    chosen = {(t, layer): mask for t in range(N)
              for layer, _, mask in taps[t * L:(t + 1) * L]}
    served_s = round(time.monotonic() - t0, 1)

    def read(ref_hf, rows):
        """``logits`` and ``selection`` against one reference, and its
        log-probabilities at the watched row-steps [rows, N, V]."""
        gaps, shares, wants = [], {layer: [] for layer in range(L)}, []
        for r in rows:
            watch = [int(lens[r]) + t for t in range(N)]
            want, sets = ref.logprobs(params, ref_hf, seqs[r], watch=watch)
            want, sets = np.asarray(want)[watch], np.asarray(sets)
            wants.append(want)
            for t in range(N):
                top = np.argsort(-want[t])[:TOP]
                gaps.append(float(
                    np.abs(served[t][r][top] - want[t][top]).max()))
                for layer in range(L):
                    mine = chosen.get((t, layer))
                    if mine is None:
                        continue
                    theirs = sets[layer, t]
                    shares[layer].append(float(
                        (mine[r, :theirs.size] & theirs).sum()
                        / max(theirs.sum(), 1)))
        per_layer = {str(k): round(min(v), 5) for k, v in shares.items()
                     if v}
        worst_share = min(per_layer.values()) if per_layer else None
        logits_ok = bool(np.max(gaps) <= args.tolerance)
        selection_ok = bool(worst_share is None
                            or worst_share >= args.share)
        return {"top20_abs_logprob_diff": {
                    "mean": float(np.mean(gaps)),
                    "largest": float(np.max(gaps))},
                "selected_share_least_by_layer": per_layer,
                "logits": logits_ok,
                "selection": selection_ok}, np.stack(wants)

    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "layers": L, "seed": args.seed, "rows": R, "steps": N,
           "routed_down_init_std": cfg.routed_down_init_std or 0.02,
           "contexts": [int(n) for n in lens],
           "tolerance": args.tolerance, "share": args.share,
           "attention_paths": [pallas_paged.attention_path(
               t, cfg.num_heads, cache.k.shape[-1], bs,
               value_dim=cfg.kv_lora_rank, selects=True,
               head_dims=(cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim))
               for t in (1, chunk)],
           "served_seconds": served_s, "controls": {}}
    out["served"], true = read(hf, range(R))
    for item in args.control:
        name, setting = item.split(":", 1)
        key, value = setting.split("=", 1)
        rows = range(min(R, args.control_rows))
        got, theirs = read({**hf, key: json.loads(value)}, rows)
        # where the served log-probabilities stand on the line from
        # the reference (0) to this control (1)
        mine = np.stack([[served[t][r] for t in range(N)] for r in rows])
        away = (theirs - true[:len(rows)]).astype(np.float64)
        off = (mine - true[:len(rows)]).astype(np.float64)
        lean = float((off * away).sum() / max((away * away).sum(), 1e-30))
        # what the line resolves: the control's distance from the
        # reference beside the served path's distance from its own
        # place on the line (a log-probability, root mean square), and
        # the standard error that distance gives the lean if it is
        # noise independent of the line
        rest = off - lean * away
        got.update(lean=lean, control_rms=float(np.sqrt((away ** 2).mean())),
                   rest_rms=float(np.sqrt((rest ** 2).mean())),
                   lean_se=float(np.sqrt((rest ** 2).mean() / max(
                       (away * away).sum(), 1e-30))))
        got["fails"] = [k for k in ("logits", "selection") if not got[k]]
        if got["lean"] < 0.5:
            got["fails"].append("lean")
        out["controls"][name] = got
    out["served"]["passes"] = bool(
        out["served"]["logits"] and out["served"]["selection"]
        and all(c["lean"] < 0.5 for c in out["controls"].values()))
    out["ok"] = out["served"]["passes"] and all(
        c["fails"] for c in out["controls"].values())
    out["seconds"] = round(time.monotonic() - t0, 1)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "dsa_chip_check.json"),
              "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
