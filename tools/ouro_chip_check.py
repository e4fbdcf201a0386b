#!/usr/bin/env python3
"""A looped model's serving path (Ouro: 48 layers run four times over
192 pool layers, sandwich norms, the final norm after every pass)
against the plain reference, on the chip, at Ouro-2.6B's published
widths (chipbench/configs/ouro-2.6b-int8.json, whole): what the
benchmark's probe cannot see.

The probe of ``chipbench`` asks ONE token of a few prompts: no decode
step through the cache. This script compares, for a batch of 16 rows
(``--long`` of them with contexts spread over ``--long-contexts LO HI``,
past two chunk boundaries, the others over ``--contexts LO HI``, the
cell's 64-256): each row's prompt prefilled in chunks of ``--chunk``
256 tokens (a padded last chunk) through the paged pool, every pass
appending to and attending over its own 48 pool layers, then
``--decode-steps`` teacher-forced decode steps of ALL rows in one batch
of 16 (the paged decode kernel, 192 calls a step) against the
reference's ONE full forward pass over each row's whole sequence (four
full causal passes, no cache):

- ``logits``: |served - reference| over the reference's top-20
  log-probabilities, the largest at EVERY row-step (the prompt's last
  position among them) within ``--tolerance`` (the file's
  ``harness.probe.logprob_gap_limit``: the probe's own statistic);
- ``lean`` (tools/gdn_chip_check.py): where the served
  log-probabilities stand on the line from the reference (0) to a
  control (1); the limit is 0.5;
- ``paths``: both executables on a kernel's attention path
  (``pallas_paged*``), never ``jnp_gather``.

``--control NAME:KEY=JSON`` (repeatable) reads the same served numbers
against the reference with those keys changed; a control must FAIL:
held as the reference it breaks ``logits``, or the served path leans to
the true reference and away from it. ``fp8:round_to="float8_e4m3fn"``
is the lower-precision control; ``three:total_ut_steps=3`` (a pass left
out) and ``shared:kv_control="last_pass"`` (every pass reading the last
pass's K and V, the paper's cache sharing) are this model's own lean
controls. ``--report`` reads alike and decides nothing.

One JSON line last (and in chiprun_out/ouro_chip_check.json); exit 0
only if the served path passes and every control fails. On the chip
only (``--tiny --allow-cpu`` rehearses in seconds):

    python3 tools/ouro_chip_check.py \\
        --control 'fp8:round_to="float8_e4m3fn"' \\
        --control 'three:total_ut_steps=3' \\
        --control 'shared:kv_control="last_pass"'
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = os.path.join(ROOT, "chipbench", "configs", "ouro-2.6b-int8.json")
TOP = 20
BATCH = 16


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--contexts", type=int, nargs=2, default=(64, 256),
                    metavar=("LO", "HI"))
    ap.add_argument("--long", type=int, default=2,
                    help="rows whose prompts pass two chunk boundaries")
    ap.add_argument("--long-contexts", type=int, nargs=2,
                    default=(530, 700), metavar=("LO", "HI"))
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--report", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="default: the file's probe limit")
    ap.add_argument("--tiny", action="store_true",
                    help="the debug-ouro preset's sizes (rehearsal)")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import harness_key
    from chipbench.engine_child import model_config
    from chipbench.probe_seeds import keyed
    from chipbench.references import ouro as ref
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    from production_stack_tpu.ops import pallas_paged

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("ouro_chip_check: JAX found no accelerator", file=sys.stderr)
        return 3
    with open(CONFIG) as f:
        hf = json.load(f)
    tolerance = args.tolerance or harness_key.of(hf)["probe"][
        "logprob_gap_limit"]
    chunk, bs = args.chunk, 64
    (lo, hi), (llo, lhi) = args.contexts, args.long_contexts
    if args.tiny:
        hf.update(hidden_size=128, num_hidden_layers=3,
                  num_attention_heads=4, num_key_value_heads=4,
                  head_dim=32, intermediate_size=384, vocab_size=512,
                  layer_types=["full_attention"] * 3)
        chunk, bs = chunk // 8, 8
        lo, hi, llo, lhi = (n // 8 for n in (lo, hi, llo, lhi))
    cfg = model_config(hf, hf["name"])
    N, R = args.decode_steps, BATCH
    t0 = time.monotonic()
    params = llama.init_params(cfg, jax.random.PRNGKey(args.seed),
                               quantization=hf["quantization"])
    rng = np.random.default_rng(args.seed)
    lens = np.concatenate([
        np.linspace(llo, lhi, args.long),
        np.linspace(lo, hi, R - args.long)]).astype(int)
    seqs = [rng.integers(0, min(cfg.vocab_size, 49152), n + N)
            for n in lens]
    # a row holds the blocks its whole sequence needs and no more: at
    # 1.5 MiB a token the chip has no room for sixteen worst cases
    need = [-(-(n + N + 1) // bs) for n in lens]
    MB = max(-(-(max(lens) + N + 1) // chunk) * chunk // bs, max(need))
    tables = np.zeros((R, MB), np.int32)
    at = 1
    for r, n in enumerate(need):
        tables[r, :n] = at + np.arange(n)
        at += n
    cache = kv_pool.cache_for(cfg, at, bs, cfg.dtype)
    tables = jnp.asarray(tables)
    buckets = [b for b in (512, 1024) if b < MB * bs] + [MB * bs]
    if args.tiny:
        buckets = [MB * bs]

    def kv_bucket(n):
        return next(b for b in buckets if n <= b)

    def forward(cache, params, tables, tokens, starts, lengths, kv_len):
        """As engine/runner._prefill_impl calls it; -> the
        log-probabilities after each row's last real position."""
        T = tokens.shape[1]
        positions = starts[:, None] + jnp.arange(T)[None, :]
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        logits, cache, _ = llama.forward(
            params, cfg, tokens, positions, cache, block_tables=tables,
            kv_len=kv_len, token_valid=valid)
        last = jnp.take_along_axis(
            logits, jnp.clip(lengths - 1, 0, T - 1)[:, None, None], axis=1)
        return jax.nn.log_softmax(last[:, 0], axis=-1), cache

    step = jax.jit(forward, static_argnums=6, donate_argnums=0)

    def prefill(cache, row, tokens):
        """-> (the log-probabilities after the prompt's last position,
        the cache): row ``row`` in chunks, the last one padded."""
        for start in range(0, len(tokens), chunk):
            part = tokens[start:start + chunk]
            padded = np.zeros((1, chunk), np.int32)
            padded[0, :len(part)] = part
            lps, cache = step(cache, params, tables[row:row + 1],
                              jnp.asarray(padded),
                              jnp.asarray([start], jnp.int32),
                              jnp.asarray([len(part)], jnp.int32),
                              kv_bucket(start + chunk))
        return np.asarray(lps[0]), cache

    firsts = []
    for r in range(R):
        first, cache = prefill(cache, r, seqs[r][:lens[r]])
        firsts.append(first)
    served = [np.stack(firsts)]
    for t in range(N):          # all rows in one batch of 16
        tokens = np.asarray([[seqs[r][lens[r] + t]] for r in range(R)],
                            np.int32)
        lps, cache = step(cache, params, tables, jnp.asarray(tokens),
                          jnp.asarray(lens + t, jnp.int32),
                          jnp.ones((R,), jnp.int32),
                          kv_bucket(int(lens.max()) + N + 1))
        served.append(np.asarray(lps))
    served = np.stack(served, 1)                    # [R, N + 1, V]
    pool = {"pool_layers": int(cache.k.shape[0]),
            "pool_blocks": int(cache.k.shape[1]),
            "kv_bytes_per_token": int(cache.bytes_per_token)}
    del cache       # 8 GB the reference's passes need more
    served_s = round(time.monotonic() - t0, 1)
    watch = [[int(lens[r]) - 1 + t for t in range(N + 1)]
             for r in range(R)]

    def read(ref_hf):
        """``logits`` against one reference, and its log-probabilities
        at the watched row-steps [R, N + 1, V]."""
        want = np.stack([np.asarray(w) for w in ref.logprobs(
            params, ref_hf, [list(map(int, s)) for s in seqs], at=watch)])
        top = np.argsort(-want, axis=-1)[..., :TOP]
        gaps = np.abs(np.take_along_axis(served, top, -1)
                      - np.take_along_axis(want, top, -1)).max(-1)
        return {"top20_abs_logprob_diff": {
                    "mean": float(gaps.mean()),
                    "largest": float(gaps.max()),
                    "largest_by_row": [float(g) for g in gaps.max(1)]},
                "logits": bool(gaps.max() <= tolerance)}, want

    paths = [pallas_paged.attention_path(
        t, cfg.num_heads // cfg.pool_kv_heads, cfg.pool_head_dim, bs)
        for t in (1, chunk)]
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "layers": cfg.num_layers, "passes": cfg.loop_steps,
           **pool,
           "num_params": cfg.num_params, "seed": args.seed, "rows": R,
           "watched_positions": N + 1, "chunk": chunk,
           "contexts": [int(n) for n in lens], "tolerance": tolerance,
           "attention_paths": paths, "served_seconds": served_s,
           "controls": {}, "reports": {}}
    out["served"], true = read(hf)
    for kind, items in (("controls", args.control),
                        ("reports", args.report)):
        for name, keys in keyed(items).items():
            got, theirs = read({**hf, **keys})
            away = (theirs - true).astype(np.float64)
            off = (served - true).astype(np.float64)
            lean = float((off * away).sum()
                         / max((away * away).sum(), 1e-30))
            got.update(keys=keys, lean=lean, control_rms=float(
                np.sqrt((away ** 2).mean())))
            # a control FAILS where, held as the reference, it breaks
            # the limit, or where the served path stands by the true
            # reference and not by it
            got["fails"] = ([] if got["logits"] else ["logits"]) + (
                ["lean"] if lean < 0.5 else [])
            out[kind][name] = got
    on_kernels = all(p.startswith("pallas_paged") for p in paths)
    out["served"]["paths"] = on_kernels or bool(args.tiny)
    out["served"]["passes"] = bool(
        out["served"]["logits"] and out["served"]["paths"]
        and all(c["lean"] < 0.5 for c in out["controls"].values()))
    out["ok"] = out["served"]["passes"] and all(
        c["fails"] for c in out["controls"].values())
    out["seconds"] = round(time.monotonic() - t0, 1)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ouro_chip_check.json"),
              "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
