#!/usr/bin/env python3
"""Nemotron-H's serving path (blocks that are ONE sublayer: Mamba-2
mixers over state pages, grouped-query attention over a K/V pool of six
layers, ungated relu^2 experts of which the chip holds a share) against
the plain reference, on the chip, at NVIDIA-Nemotron-3-Nano-30B-A3B's
published widths (chipbench/configs/nemotron-3-nano-30b-a3b-int8-e32.json,
all 52 blocks): what the benchmark's probe cannot see.

The probe of ``chipbench`` asks ONE token of three prompts, the longest
of about 2120 tokens: one chunk boundary, no decode step, no context of
the cell's length. This script compares, for ``--rows`` rows whose
contexts are spread over ``--contexts LO HI`` (8k-16k): each row's
prompt prefilled in chunks of ``--chunks`` tokens, a row after the
other taking the next size (2048 and 512: the chunked scan carrying its
2 MB page a layer across 3-31 dispatch boundaries and a padded last
chunk, the held experts' rounds between them), then ``--decode-steps``
teacher-forced decode steps of all rows in one batch of 8 beside parked
rows (the recurrent kernel on the same pages, the list kernel on the
experts the rows hit) against the reference's ONE full forward pass
over each row's whole sequence (the token-by-token recurrence,
attention a key-value head at a time in blocks of queries, every held
expert over every token):

- ``logits``: |served - reference| over the reference's top-20
  log-probabilities, the largest at EVERY row-step (the prompt's last
  position among them) within ``--tolerance`` 0.3: the probe's own
  statistic and the default limit (chipbench/reference.py);
- ``lean`` (tools/gdn_chip_check.py): where the served
  log-probabilities stand on the line from the reference (0) to a
  control (1); the limit is 0.5.

``--control NAME:KEY=JSON`` (repeatable; one name may gather several
keys) reads the same served numbers against the reference with those
keys changed; a control must FAIL: held as the reference it breaks
``logits``, or the served path leans to the true reference and away
from it. ``fp8:round_to="float8_e4m3fn"`` is the lower-precision
control; ``lean:gate_control="off"`` and
``lean:routed_scaling_factor=1.0`` together are the ``lean`` model: the
gated group norm without its gate, the routed experts unscaled.
``--report`` reads alike and decides nothing
(``bf16state:state_control="bf16"``, ``bf16:round_to="bfloat16"``).

One JSON line last (and in chiprun_out/mamba2_chip_check.json); exit 0
only if the served path passes and every control fails. On the chip
only (``--allow-cpu`` rehearses at ``--tiny``):

    python3 tools/mamba2_chip_check.py --rows 2 --decode-steps 32 \\
        --control 'fp8:round_to="float8_e4m3fn"' \\
        --control 'lean:gate_control="off"' \\
        --control 'lean:routed_scaling_factor=1.0'
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
                      "nemotron-3-nano-30b-a3b-int8-e32.json")
TOP = 20
BATCH = 8
FARTHER = 3.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--contexts", type=int, nargs=2, default=(8192, 16000),
                    metavar=("LO", "HI"))
    ap.add_argument("--chunks", type=int, nargs="+", default=(2048, 512),
                    help="prefill chunk sizes, a row after the other")
    ap.add_argument("--decode-steps", type=int, default=32)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--control", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--report", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--control-rows", type=int, default=1,
                    help="rows the controls are read on, the shortest "
                         "first (a reference pass of 16k tokens takes "
                         "minutes)")
    ap.add_argument("--tolerance", type=float, default=0.3)
    ap.add_argument("--tiny", action="store_true",
                    help="the debug-nemotron preset's sizes (rehearsal)")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.engine_child import model_config
    from chipbench.references import nemotron_h as ref
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    from production_stack_tpu.ops import mamba2, moe, pallas_paged

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("mamba2_chip_check: JAX found no accelerator",
              file=sys.stderr)
        return 3
    with open(CONFIG) as f:
        hf = json.load(f)
    chunks, bs = list(args.chunks), 64
    if args.tiny:
        hf.update(hybrid_override_pattern="MEM*EMEM*EME",
                  num_hidden_layers=12, hidden_size=128, mamba_num_heads=8,
                  mamba_head_dim=32, n_groups=2, ssm_state_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  head_dim=32, n_routed_experts=4, num_experts_per_tok=3,
                  moe_intermediate_size=48,
                  moe_shared_expert_intermediate_size=96, vocab_size=512,
                  deployment={"chips_per_layer": 2, "chip_index": 1,
                              "router_experts": 8})
        chunks, bs = [c // 64 for c in chunks], 8
    chunk = max(chunks)
    cfg = model_config(hf, hf["name"])
    lo, hi = args.contexts
    R, N = args.rows, args.decode_steps
    max_len = -(-(hi + N + 1) // chunk) * chunk
    MB = max_len // bs
    t0 = time.monotonic()
    params = llama.init_params(cfg, jax.random.PRNGKey(args.seed),
                               quantization=hf["quantization"])
    rng = np.random.default_rng(args.seed)
    cache = kv_pool.cache_for(cfg, R * MB + 1, bs, cfg.dtype,
                              state_pages=R + 1)
    tables = np.zeros((BATCH, MB + 1), np.int32)
    tables[:R, :MB] = 1 + np.arange(R * MB).reshape(R, MB)
    tables[:R, MB] = 1 + np.arange(R)               # the state pages
    tables = jnp.asarray(tables)
    buckets = [b for b in (512, 1024, 2048, 4096, 8192, 16384, 32768)
               if b < max_len] + [max_len]

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
        the cache); row ``row`` in chunks of its size."""
        size = chunks[row % len(chunks)]
        for start in range(0, len(tokens), size):
            part = tokens[start:start + size]
            bucket = next(b for b in (size // 8, size // 4, size // 2,
                                      size) if b >= len(part))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(part)] = part
            lps, cache = step(cache, params, tables[row:row + 1],
                              jnp.asarray(padded),
                              jnp.asarray([start], jnp.int32),
                              jnp.asarray([len(part)], jnp.int32),
                              kv_bucket(start + bucket))
        return np.asarray(lps[0]), cache

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
    firsts = []
    for r in range(R):
        first, cache = prefill(cache, r, seqs[r][:lens[r]])
        firsts.append(first)
    steps, cache = decode(cache, {r: r for r in range(R)})
    # what is watched: the prompt's last position, then every decode
    # step
    full = np.zeros((BATCH,) + firsts[0].shape, np.float32)
    full[:R] = np.stack(firsts)
    served = [full] + steps
    N += 1
    served_s = round(time.monotonic() - t0, 1)

    def read(ref_hf, rows, mine=None, at=None):
        """``logits`` against one reference, and its log-probabilities
        at the watched row-steps [rows, N, V]."""
        gaps, wants = [], []
        for r in rows:
            watch = [int(lens[r]) - 1 + t for t in range(N)]
            want = np.asarray(ref.logprobs(params, ref_hf, seqs[r],
                                           at=watch))
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
           "rows": R, "watched_positions": N,
           "contexts": [int(n) for n in lens], "tolerance": args.tolerance,
           "attention_paths": [pallas_paged.attention_path(
               t, cfg.num_heads // cfg.pool_kv_heads, cfg.pool_head_dim, bs)
               for t in (1, chunk)],
           "mixer_paths": [mamba2.mamba2_path(
               t, cfg.mamba_d_inner, cfg.mamba_heads, cfg.mamba_groups,
               cfg.mamba_d_state) for t in (1, chunk)],
           "moe_paths": [moe.moe_path(
               rows, t, cfg.router_experts_, cfg.num_experts_per_tok,
               cfg.hidden_size, cfg.moe_stored_size, jnp.int8, cfg.dtype,
               gated=cfg.expert_gate) for rows, t in ((BATCH, 1),
                                                      (1, chunk))],
           "chunks": chunks,
           "served_seconds": served_s,
           "controls": {}, "reports": {}}
    out["served"], true = read(hf, range(R))
    rows = range(min(R, args.control_rows))
    def keyed(items):
        """NAME:KEY=JSON, a name's keys gathered -> name -> keys."""
        named = {}
        for item in items:
            name, setting = item.split(":", 1)
            key, value = setting.split("=", 1)
            named.setdefault(name, {})[key] = json.loads(value)
        return named

    for kind, items in (("controls", args.control),
                        ("reports", args.report)):
        for name, keys in keyed(items).items():
            got, theirs = read({**hf, **keys}, rows)
            mine = np.stack([[served[t][r] for t in range(N)]
                             for r in rows])
            away = (theirs - true[:len(rows)]).astype(np.float64)
            off = (mine - true[:len(rows)]).astype(np.float64)
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
    out["served"]["passes"] = bool(
        out["served"]["logits"]
        and all(c.get("lean", 0.0) < 0.5
                for c in out["controls"].values()))
    out["ok"] = out["served"]["passes"] and all(
        c["fails"] for c in out["controls"].values())
    out["seconds"] = round(time.monotonic() - t0, 1)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "mamba2_chip_check.json"),
              "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
