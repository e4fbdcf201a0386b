"""Serving-engine benchmark: prints ONE JSON line with decode throughput.

Measures end-to-end continuous-batching generation throughput (output
tokens/sec) of the TPU-native engine on a TinyLlama-1.1B-geometry model
(random weights — throughput is weight-value-independent), batch 32
(the paged engine's best verified config; --batch 8 for the legacy
compatibility point), 128-token prompts, 128 generated tokens per
request, greedy.

One process: it owns the chip for the run, prints the device it found
(``platform``, ``device_kind``, ``device_count``) in its one JSON line,
and exits non-zero when JAX finds no accelerator or the run fails. There
is no CPU fallback: a CPU number under this metric's name measures the
host, not the system. ROADMAP S1/D1 replace this single closed-loop cell
with the benchmark proper.

vs_baseline: ratio against the value recorded in BENCH_REF.json for this
(mode, platform) pair — first run of a pair records the baseline (ratio
1.0); later rounds show the improvement factor. The reference repo
publishes no absolute numbers (see BASELINE.md), so the trajectory is
measured against ourselves.

Usage: python bench.py [--small] [--batch N] [--gen-len N]
                       [--quantization int8] [--spec N] [--kv-pool-frac F]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
REF_PATH = os.path.join(REPO, "BENCH_REF.json")


def parse_cli(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="debug-tiny geometry (quick check of the "
                         "harness, not a serving size)")
    ap.add_argument("--batch", type=int, default=None,
                    help="concurrent batch slots (default: 32 full mode "
                         "— the paged engine's best verified config — "
                         "8 small mode)")
    ap.add_argument("--gen-len", type=int, default=0,
                    help="tokens generated per request (0 = mode default)")
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests (0 = 2x batch)")
    ap.add_argument("--prompt-len", type=int, default=0,
                    help="prompt tokens per request (0 = mode default)")
    ap.add_argument("--quantization", choices=["int8"], default=None)
    ap.add_argument("--kv-cache-dtype",
                    choices=["bfloat16", "float32", "int8"],
                    default=None,
                    help="KV cache precision (int8 halves long-context "
                         "decode KV HBM traffic)")
    ap.add_argument("--spec", type=int, default=0,
                    help="n-gram speculative draft length (0 = off)")
    ap.add_argument("--prompt-repeat", type=int, default=0,
                    help="build each prompt by tiling a short per-"
                         "request phrase this many times (repetitive "
                         "multi-round-QA-like histories — the workload "
                         "n-gram speculation is FOR; 0 = the synthetic "
                         "near-random default, adversarial for spec)")
    ap.add_argument("--kv-pool-frac", type=float, default=1.0,
                    help="KV pool size as a fraction of the worst-case "
                         "batch*max_model_len reservation (paged KV)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill chunk size (0 = mode default; "
                         "long-context TTFT sweeps)")
    ap.add_argument("--window", type=int, default=0,
                    help="fused decode-window length (0 = mode default; "
                         "per window the host pays one dispatch + one "
                         "sync, so longer windows amortize dispatch "
                         "latency)")
    ap.add_argument("--pipeline-depth", type=int, default=0,
                    help="decode windows queued on the device at once "
                         "(0 = config default 2)")
    ap.add_argument("--cold", action="store_true",
                    help="skip the untimed warm pass (measure a cold "
                         "engine, lazy compiles land in the timed region)")
    return ap.parse_args(argv)


def run_bench(args) -> dict:
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    batch = args.batch or (8 if args.small else 32)
    if args.small:
        cfg_kw = dict(model="debug-tiny", max_model_len=512,
                      max_num_seqs=batch, prefill_chunk=128,
                      decode_window=16)
        prompt_len, gen_len = 64, 32
    else:
        # decode_window 32: one dispatch + one host sync per 32 tokens
        # per slot; 128-token answers pack into exactly 4 windows
        cfg_kw = dict(model="tinyllama-1.1b", max_model_len=1024,
                      max_num_seqs=batch, prefill_chunk=512,
                      decode_window=32, prefill_buckets=(128, 512))
        prompt_len, gen_len = 128, 128
    if args.prompt_len:
        prompt_len = args.prompt_len
    if args.gen_len:
        gen_len = args.gen_len
    # the cache must hold prompt + generation; grow it to the covering
    # multiple of 256 for long-context / long-generation sweeps. A
    # power-of-two covering doubles the KV pool for just-past-a-bucket
    # spans (8320 -> 16384 pins ~3 GB of pool instead of ~1.5 and blew
    # HBM at batch 8 x 8k bf16); the top kv bucket lands on
    # max_model_len either way, so attention cost stays ~ live prefix.
    span = prompt_len + gen_len
    if span > cfg_kw["max_model_len"]:
        cfg_kw["max_model_len"] = -(-span // 256) * 256
    if args.prefill_chunk:
        cfg_kw["prefill_chunk"] = args.prefill_chunk
        cfg_kw["prefill_buckets"] = (args.prefill_chunk,)
    if args.window:
        cfg_kw["decode_window"] = args.window
    n_requests = args.requests or 2 * batch
    if args.quantization:
        cfg_kw["quantization"] = args.quantization
    if args.kv_cache_dtype:
        cfg_kw["kv_dtype"] = args.kv_cache_dtype
    if args.spec:
        cfg_kw["speculative_ngram_tokens"] = args.spec
    if args.kv_pool_frac < 1.0:
        worst = cfg_kw["max_num_seqs"] * cfg_kw["max_model_len"]
        cfg_kw["kv_pool_tokens"] = int(worst * args.kv_pool_frac)
    if args.pipeline_depth:
        cfg_kw["pipeline_depth"] = args.pipeline_depth
    cfg = EngineConfig(**cfg_kw)

    eng = LLMEngine(cfg)
    compile_s = eng.runner.warmup()

    opts = SamplingOptions(temperature=0.0, max_tokens=gen_len,
                           ignore_eos=True)
    if args.prompt_repeat:
        # repetitive histories (multi-round QA re-sends the growing
        # conversation every round): a short per-request phrase tiled
        # across the prompt, so n-gram lookup finds real continuations
        rng_tokens = []
        for i in range(n_requests):
            phrase = [(13 * i + j) % 1000 + 1
                      for j in range(max(4, prompt_len
                                         // max(1, args.prompt_repeat)))]
            tiled = (phrase * (prompt_len // len(phrase) + 1))[:prompt_len]
            rng_tokens.append(tiled)
    else:
        rng_tokens = [[(7 * i + j) % 1000 + 1 for j in range(prompt_len)]
                      for i in range(n_requests)]

    def run_pass():
        ids = [eng.add_request(toks, opts) for toks in rng_tokens]
        done = set()
        while len(done) < len(ids):
            for out in eng.step():
                if out.finished:
                    done.add(out.seq_id)
        return ids

    warm_s = 0.0
    if not args.cold:
        # untimed warm pass over the exact workload: warmup() compiles
        # the hot executables, but sweep configs (long-context kv
        # buckets, spec/guided variants) can still compile lazily —
        # that belongs to warm_s, not the measurement
        t0 = time.time()
        run_pass()
        warm_s = time.time() - t0

    t0 = time.time()
    ids = run_pass()
    wall = time.time() - t0

    out_tokens = sum(len(eng.seqs[i].output_tokens) for i in ids)
    in_tokens = sum(len(t) for t in rng_tokens)
    spec_stats = {}
    if cfg.speculative_ngram_tokens:
        steps = eng.metrics.spec_macro_steps._value.get()
        accepted = eng.metrics.spec_accepted_tokens._value.get()
        spec_stats = {
            # accepted draft tokens per macro-step (0..spec): the
            # workload-dependent quantity that decides whether
            # speculation pays for its (spec+1)-wide verify forwards
            "spec_acceptance": round(accepted / steps, 4) if steps
            else 0.0,
            "spec_macro_steps": int(steps),
        }
    return {
        **spec_stats,
        "output_tokens_per_s": out_tokens / wall,
        "total_tokens_per_s": (out_tokens + in_tokens) / wall,
        "wall_s": wall,
        "compile_s": compile_s,
        "warm_s": warm_s,
        # pre-r4 baselines were recorded cold (lazy compiles could land
        # in the timed region); compare vs_baseline across methodologies
        # with that in mind
        "methodology": "cold" if args.cold else "warm",
        "out_tokens": out_tokens,
        "model": cfg.model,
        "batch_slots": cfg.max_num_seqs,
        "prompt_len": prompt_len,
        "gen_len": gen_len,
        "quantization": cfg.quantization,
        "kv_dtype": cfg.kv_dtype,
        "speculative": cfg.speculative_ngram_tokens,
        "decode_window": cfg.decode_window,
    }


def record_line(args, stats: dict, devices) -> dict:
    value = round(stats["output_tokens_per_s"], 2)
    batch = stats["batch_slots"]
    # baselines keyed by (mode, platform, batch) so vs_baseline always
    # compares a config against ITS OWN prior record — batch 32 against
    # the verified round-4 batch-32 number, never against the round-1
    # batch-8 cold point. Legacy (pre-r5) entries were unkeyed by batch
    # and recorded at batch 8; fall back to them for batch-8 runs.
    mode = "small" if args.small else "full"
    platform = devices[0].platform
    key = f"{mode}-{platform}-b{batch}"
    refs = {}
    if os.path.exists(REF_PATH):
        try:
            with open(REF_PATH) as f:
                refs = json.load(f)
        except (OSError, json.JSONDecodeError, ValueError):
            refs = {}
    ref = refs.get(key)
    if ref is None and batch == 8:
        ref = refs.get(f"{mode}-{platform}")
    standard = (not args.quantization
                and not args.kv_cache_dtype
                and not args.spec and not args.gen_len
                and not args.prompt_len and not args.requests
                and not args.prefill_chunk and not args.cold
                and not args.window and not args.prompt_repeat
                and not args.pipeline_depth
                and args.kv_pool_frac == 1.0)
    if ref is None and standard:
        # only standard configs may set the baseline for a pair
        refs[key] = ref = value
        try:
            with open(REF_PATH, "w") as f:
                json.dump(refs, f)
        except OSError:
            pass
    return {
        "metric": "engine decode throughput (TinyLlama-1.1B geometry, "
                  f"batch {batch}, {stats['prompt_len']}+"
                  f"{stats['gen_len']} tok, single chip)"
        if not args.small else "engine decode throughput (debug-tiny)",
        "value": value,
        "unit": "out_tok/s",
        "vs_baseline": round(value / ref, 3) if ref else 1.0,
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "detail": {k: (round(v, 2) if isinstance(v, float) else v)
                   for k, v in stats.items()},
    }


def main() -> None:
    args = parse_cli()
    from production_stack_tpu.utils import place_compile_cache
    place_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        sys.exit("bench.py measures the accelerator and JAX found only "
                 "the CPU: nothing measured, no number printed")
    print(json.dumps(record_line(args, run_bench(args), devices)))


if __name__ == "__main__":
    main()
