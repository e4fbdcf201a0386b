"""Steady-state decode microbenchmark: device ms per fused step.

bench.py measures the end-to-end engine (prefill + decode + host token
processing + dispatch latency); this tool isolates the DEVICE cost of
the decode window so the two can be compared — the gap is host
overhead, the device number is what roofline arithmetic should use.

It builds a real engine, prefills a batch to the requested live
context, then calls runner.decode() back-to-back without converting
results (each window chains on the device-carried state; one
block_until_ready at the end), reporting ms/step, out tok/s, and the
effective weight-streaming bandwidth:

    weight_bytes_per_step / step_time  vs  ~819 GB/s (v5e HBM)

Decode is weight-bandwidth-bound until KV traffic bites, so this is
the number to push toward the roofline (BASELINE.md).

Usage:
    python -m benchmarks.engine_steady [--batch 8] [--window 32]
        [--ctx 128] [--iters 8] [--quantization int8] [--spec N]

The reference publishes no comparable number (its engine is external
vLLM, SURVEY.md §1 L2); this measures the in-repo engine only.
"""

import argparse
import json
import time

from production_stack_tpu.utils import place_compile_cache


def main() -> None:
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--window", type=int, default=32)
    ap.add_argument("--ctx", type=int, default=128,
                    help="live prefix per row before timing starts")
    ap.add_argument("--iters", type=int, default=8,
                    help="timed decode windows")
    ap.add_argument("--quantization", choices=["int8"], default=None)
    ap.add_argument("--kv-cache-dtype",
                    choices=["bfloat16", "float32", "int8"], default=None)
    ap.add_argument("--spec", type=int, default=0)
    ap.add_argument("--model", default="tinyllama-1.1b")
    ap.add_argument("--block", type=int, default=0,
                    help="KV pool block size in tokens (0 = config "
                         "default; long-context grid-overhead sweeps)")
    args = ap.parse_args()

    import jax
    import numpy as np

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    # +4 windows of slack: priming leaves up to cfg.pipeline_depth
    # optimistic windows in flight past the processed tokens, plus the
    # warm window and the host-side rounding of the priming loop —
    # under-covering would clamp the tail windows' KV writes onto the
    # trash block and make their reads artificially cache-hot. With
    # speculation every macro-step emits up to spec+1 tokens (the same
    # horizon factor the engine uses, engine._dispatch_decode).
    span = args.ctx + args.window * (args.iters + 4) * (args.spec + 1)
    need = -(-span // 256) * 256    # covering multiple of 256
    cfg_kw = dict(model=args.model, max_model_len=max(512, need),
                  max_num_seqs=args.batch, prefill_chunk=512,
                  decode_window=args.window,
                  quantization=args.quantization,
                  speculative_ngram_tokens=args.spec)
    if args.kv_cache_dtype:
        cfg_kw["kv_dtype"] = args.kv_cache_dtype
    if args.block:
        cfg_kw["kv_block_size"] = args.block
    cfg = EngineConfig(**cfg_kw)
    eng = LLMEngine(cfg)
    compile_s = eng.runner.warmup()

    opts = SamplingOptions(temperature=0.0, max_tokens=span,
                           ignore_eos=True)
    prompts = [[(11 * i + j) % 1000 + 1 for j in range(args.ctx)]
               for i in range(args.batch)]
    ids = [eng.add_request(p, opts) for p in prompts]
    # drive through prefill + one decode window so every slot carries
    # device decode state and the executable is warm for this bucket
    while min(len(eng.seqs[i].output_tokens) for i in ids) < 1:
        eng.step()

    runner = eng.runner
    # the engine only extends block tables per dispatched window; the
    # direct runner.decode() calls below bypass that, so cover the full
    # timed span up front — otherwise KV writes past coverage alias
    # trash block 0 and the measured reads are artificially cache-hot
    for i in ids:
        if not eng._ensure_blocks(eng.seqs[i], span):
            raise SystemExit("KV pool too small for the timed span")
    from production_stack_tpu.engine.sampler import SamplingParams
    sampling = SamplingParams.filled(args.batch, temperature=0.0)
    kv_len = cfg.kv_bucket_for(span)
    dec = dict(steps=args.window, kv_len=kv_len, greedy=True)
    if args.spec:
        # speculation is per-row (engine._dispatch_decode builds this
        # from eligibility); here every row is plain greedy
        dec["spec"] = args.spec
        dec["spec_ok"] = np.ones((args.batch,), bool)
    # warm this exact executable (larger kv bucket than engine used)
    out = runner.decode(sampling, **dec)
    jax.block_until_ready(out[0])

    pos0 = float(np.asarray(runner._dec_pos).mean())   # pre-timing sync
    t0 = time.time()
    last = None
    for _ in range(args.iters):
        last = runner.decode(sampling, **dec)
    jax.block_until_ready(last[0])
    dt = time.time() - t0
    pos1 = float(np.asarray(runner._dec_pos).mean())

    steps = args.iters * args.window
    weight_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(eng.runner.params))
    # KV bytes READ per decode step: each row's live prefix (the paged
    # kernel skips blocks past it), K+V, every layer — the term that
    # dominates weight streaming at long context, so effective GB/s
    # stays meaningful for the 8k/32k rows. avg_live is the MEASURED
    # mean device position at the timed region's midpoint (captured
    # from the device carry outside the timed region), so priming
    # windows, pipeline depth, and speculative multi-token steps are
    # all accounted for exactly.
    mcfg = eng.model_cfg
    kv_item = eng.runner.cache.k.dtype.itemsize
    avg_live = int((pos0 + pos1) / 2)
    sw = mcfg.sliding_window
    if sw and mcfg.alternating_sliding:
        # gemma-2: even layers windowed, odd global
        win_layers = mcfg.num_layers - mcfg.num_layers // 2
        read_tokens = (win_layers * min(avg_live, sw)
                       + (mcfg.num_layers - win_layers) * avg_live)
    elif sw:
        read_tokens = mcfg.num_layers * min(avg_live, sw)
    else:
        read_tokens = mcfg.num_layers * avg_live
    kv_bytes = (args.batch * read_tokens
                * mcfg.num_kv_heads * mcfg.head_dim_ * 2 * kv_item)
    step_s = dt / steps
    print(json.dumps({
        "ms_per_step": round(step_s * 1e3, 3),
        # measured from device positions, so speculative macro-steps
        # (1..spec+1 tokens each) count their actual emissions
        "out_tok_per_s": round(args.batch * (pos1 - pos0) / dt, 2),
        "weight_gb_per_step": round(weight_bytes / 1e9, 3),
        "kv_gb_per_step": round(kv_bytes / 1e9, 3),
        "effective_gb_per_s": round(
            (weight_bytes + kv_bytes) / step_s / 1e9, 1),
        "platform": jax.devices()[0].platform,
        "batch": args.batch, "window": args.window, "ctx": args.ctx,
        "kv_bucket": kv_len, "iters": args.iters,
        "quantization": args.quantization, "spec": args.spec,
        "kv_dtype": cfg.kv_dtype,
        "kv_block": cfg.kv_block_size,
        "compile_s": round(compile_s, 1),
    }))


if __name__ == "__main__":
    main()
