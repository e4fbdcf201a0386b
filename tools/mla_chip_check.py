#!/usr/bin/env python3
"""The latent-attention serving path against the plain reference, on
the chip, at GLM-4.7-Flash's published widths: what the benchmark's
probe cannot see, and how often it would fail.

The probe of ``chipbench`` asks ONE token of three prompts, and prefill
answers it (PERF.md section 7). This script compares

- ``--decode-steps N``: N teacher-forced DECODE steps of ``--rows`` live
  rows and a parked one through the latent pool (the absorbed decode
  kernel, the experts' list kernel) against the reference's one full
  forward pass over each row's whole sequence: |served - reference|
  over the reference's top-20 log-probabilities at every step;
- ``--prompts K``: the next-token log-probabilities after K prompts of
  ``--prompt-len`` tokens, prefilled in chunks of 256 as the engine
  prefills them (one row, the exact expert path), the way the probe
  compares them (over the SERVED top-20): how the probe's number is
  distributed over prompts. With ``--served-selections`` each prompt
  is read a second time against the reference GIVEN the served path's
  top-k choices in every expert layer: what is left then is
  arithmetic, and what went was tie-breaks in a selection.

``--routed-down-std`` draws the routed experts' output projection at
another sd than the file's ``assumed.routed_down_init_std`` (0.02 is
every other leaf's); ``--reference round_to='"float8_e4m3fn"'`` keeps
the reference's activations in a lower precision (a control: what the
comparison reads of a precision below the one the file states).

One JSON line last. On the chip only (the CPU has tests/test_mla.py):

    python3 tools/mla_chip_check.py --layers 3 --rows 15 --decode-steps 12
    python3 tools/mla_chip_check.py --layers 13 --prompts 12 --prompt-len 329
    python3 tools/mla_chip_check.py --layers 13 --prompts 16 \
        --routed-down-std 0.02 --served-selections
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
                      "glm-4.7-flash-int8-l13.json")
CHUNK, BS, ROWS_MAX, KV_LEN = 256, 64, 16, 512
TOP = 20


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=3,
                    help="1 dense + (layers - 1) expert layers")
    ap.add_argument("--rows", type=int, default=15)
    ap.add_argument("--decode-steps", type=int, default=0)
    ap.add_argument("--prompts", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, default=329)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--reference", action="append", default=[],
                    metavar="KEY=JSON",
                    help="a key of the configuration as the REFERENCE "
                         "alone reads it (e.g. routed_scaling_factor=1.0): "
                         "what the comparison reads when the two "
                         "disagree on that much")
    ap.add_argument("--routed-down-std", type=float, default=None,
                    help="sd of the routed experts' down projection "
                         "(default: the file's assumed."
                         "routed_down_init_std)")
    ap.add_argument("--served-selections", action="store_true",
                    help="with --prompts: also read each prompt against "
                         "the reference given the served top-k choices")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.engine_child import model_config
    from chipbench.references import glm4_moe_lite as ref
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    from production_stack_tpu.ops import moe, pallas_paged

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("mla_chip_check: JAX found no accelerator", file=sys.stderr)
        return 3
    with open(CONFIG) as f:
        hf = {**json.load(f), "num_hidden_layers": args.layers}
    if args.routed_down_std is not None:
        hf["assumed"] = {**hf["assumed"],
                         "routed_down_init_std": args.routed_down_std}
    cfg = model_config(hf, hf["name"])
    wrong = {k: json.loads(v) for k, v in
             (item.split("=", 1) for item in args.reference)}
    ref_hf = {**hf, **wrong}
    t0 = time.monotonic()
    params = llama.init_params(cfg, jax.random.PRNGKey(args.seed),
                               quantization=hf["quantization"])
    rng = np.random.default_rng(args.seed)
    MB = -(-(CHUNK * 2) // BS)
    cache = kv_pool.cache_for(cfg, ROWS_MAX * MB + 1, BS)
    tables = kv_pool.linear_tables(ROWS_MAX, MB * BS, BS)
    parked = MB * BS

    # the served path's top-k choices, an expert layer at a time in
    # the order the layers ran: ops/moe.moe_mlp's route, tapped
    chosen_log = []
    if args.served_selections:
        route = moe.route

        def tapped(*a, **kw):
            top_p, top_i = route(*a, **kw)
            jax.debug.callback(
                lambda ids: chosen_log.append(np.asarray(ids)), top_i,
                ordered=True)
            return top_p, top_i
        moe.route = tapped

    @jax.jit
    def step(params, cache, tables, tokens, starts, lengths):
        """runner._prefill_impl / _decode_impl's forward: rows at
        ``starts`` with ``lengths`` real tokens (0: parked)."""
        T = tokens.shape[1]
        positions = starts[:, None] + jnp.arange(T)[None, :]
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        logits, cache, _ = llama.forward(
            params, cfg, tokens, positions, cache, block_tables=tables,
            kv_len=KV_LEN, token_valid=valid,
            moe_capacity_tokens=ROWS_MAX * T)
        last = jnp.take_along_axis(
            logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
        return jax.nn.log_softmax(last[:, 0], axis=-1), cache

    def prefill(cache, row, tokens):
        """One row's prompt in chunks of CHUNK, each in its bucket."""
        lps = None
        del chosen_log[:]
        for start in range(0, len(tokens), CHUNK):
            chunk = tokens[start:start + CHUNK]
            bucket = next(b for b in (64, 128, 256) if b >= len(chunk))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(chunk)] = chunk
            lps, cache = step(params, cache, tables[row:row + 1],
                              jnp.asarray(padded),
                              jnp.asarray([start], jnp.int32),
                              jnp.asarray([len(chunk)], jnp.int32))
        return lps[0], cache

    def served_choices(T):
        """[expert layers, T, k] out of the last prefill's taps: one
        [bucket, k] a layer and chunk, chunks in order."""
        jax.effects_barrier()
        Le = args.layers - cfg.first_dense_layers
        chunks = [chosen_log[i:i + Le]
                  for i in range(0, len(chosen_log), Le)]
        per_layer = [np.concatenate([
            c[j][:min(CHUNK, T - n * CHUNK)] for n, c in enumerate(chunks)])
            for j in range(Le)]
        return np.stack(per_layer)

    def gaps(served, want, over):
        return np.abs(np.take_along_axis(served, over, -1)
                      - np.take_along_axis(want, over, -1))

    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "layers": args.layers, "seed": args.seed,
           "routed_down_init_std": cfg.routed_down_init_std or 0.02,
           "reference_reads": wrong,
           "attention_paths": [
               pallas_paged.attention_path(
                   t, cfg.num_heads, cache.k.shape[-1], BS,
                   value_dim=cfg.kv_lora_rank,
                   head_dims=(cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                              cfg.v_head_dim)) for t in (1, CHUNK)]}

    if args.prompts:
        rows = []
        for _ in range(args.prompts):
            tokens = rng.integers(0, 256, args.prompt_len)
            served, cache = prefill(cache, 0, tokens)
            want = np.asarray(ref.logprobs(params, ref_hf, tokens)[-1])
            served = np.asarray(served)
            top = np.argsort(-served)[:TOP]
            rows.append({
                "max_abs_logprob_diff": float(gaps(served, want,
                                                   top).max()),
                "shared_top": len(set(top.tolist())
                                  & set(np.argsort(-want)[:TOP].tolist()))})
            if args.served_selections:
                given = np.asarray(ref.logprobs(
                    params, ref_hf, tokens,
                    chosen=served_choices(len(tokens)))[-1])
                rows[-1]["given_served_selections"] = float(
                    gaps(served, given, top).max())

        def spread(key):
            diffs = sorted(r[key] for r in rows)
            return {"median": diffs[len(diffs) // 2],
                    "largest": diffs[-1],
                    "over_0.3": sum(d > 0.3 for d in diffs)}
        out["probe_like"] = {"prompt_len": args.prompt_len, "rows": rows,
                             **spread("max_abs_logprob_diff")}
        if args.served_selections:
            out["probe_like"]["given_served_selections"] = spread(
                "given_served_selections")

    if args.decode_steps:
        R, N = args.rows, args.decode_steps
        lens = rng.integers(64, CHUNK + 1, R)
        seqs = [rng.integers(0, 256, n + N) for n in lens]
        for r in range(R):
            _, cache = prefill(cache, r, seqs[r][:lens[r]])
        want = [np.asarray(ref.logprobs(params, ref_hf, s)) for s in seqs]
        worst = []
        for t in range(N):
            tokens = np.zeros((ROWS_MAX, 1), np.int32)
            starts = np.full((ROWS_MAX,), parked, np.int32)
            lengths = np.zeros((ROWS_MAX,), np.int32)
            for r in range(R):
                tokens[r, 0] = seqs[r][lens[r] + t]
                starts[r], lengths[r] = lens[r] + t, 1
            served, cache = step(params, cache, tables,
                                 jnp.asarray(tokens), jnp.asarray(starts),
                                 jnp.asarray(lengths))
            served = np.asarray(served)
            for r in range(R):
                w = want[r][lens[r] + t]
                worst.append(gaps(served[r], w,
                                  np.argsort(-w)[:TOP]))
        g = np.concatenate(worst)
        per_row_step = np.asarray([x.max() for x in worst])
        out["decode_steps"] = {
            "rows": R, "parked": ROWS_MAX - R, "steps": N,
            "prompt_lengths": [int(n) for n in lens],
            "top20_abs_logprob_diff": {
                "mean": float(g.mean()),
                "p99": float(np.percentile(g, 99)),
                "largest": float(g.max())},
            "row_steps_over_0.3": int((per_row_step > 0.3).sum()),
            "row_steps": int(per_row_step.size)}
    out["seconds"] = round(time.monotonic() - t0, 1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
