"""The benchmark's launcher for the engine: the one process that holds
the chip.

It calls the program's normal server entry point
(``production_stack_tpu.engine.server.main``) with the configuration's
arguments, and adds only what a benchmark needs and the program does
not offer yet (each is listed in PERF.md for a later PR to move inside):

- the model configuration comes from the benchmark's own file of
  published sizes, not from a preset of the program;
- the warm-up compiles exactly the shapes the cell's traffic reaches
  (``--warm`` file), where the server's own warms a fixed grid;
- ``POST /chipbench/probe`` runs the plain float32 reference on this
  process's weights (only the process that holds the chip can);
- ``POST /chipbench/trace/start|stop|reduce`` hold ``jax.profiler`` for
  a few seconds and reduce the trace (only this process can trace the
  chip).

Exits 3 before building anything when JAX finds no accelerator, or
fewer devices than asked, unless ``--allow-cpu`` (rehearsals, tests).
"""

import argparse
import asyncio
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DEVICE_TAG = "CHIPBENCH_DEVICE "
READY_TAG = "CHIPBENCH_WARM "


def model_config(conf: dict, name: str):
    """The program's ModelConfig from the configuration file's
    published ``config.json`` keys (the program's own mapping of HF
    keys, so the file holds the source's names and numbers)."""
    from production_stack_tpu.models.config import ModelConfig
    return ModelConfig.from_hf_config(dict(conf), name=name)


def shapes_reached(cfg, reach: dict) -> dict:
    """The executables the cell's traffic reaches, from the engine's
    own bucket rules (EngineConfig) and what the parent says of the
    traffic: ``decode`` rows [batch, window, kv], ``prefill`` rows
    [bucket, kv].

    Decode (engine._dispatch_decode): at the first kv bucket the
    adaptive dispatch walks (batch bucket x window bucket); above it
    the full geometry is pinned. A window reaches ahead of the longest
    context by the queued windows (pipeline_depth) plus one.
    Prefill (engine._do_prefill): a chunk of ``c`` tokens at ``start``
    runs in bucket_for(c) at kv_bucket_for(start + bucket)."""
    S, W = cfg.max_model_len, cfg.decode_window
    kv0 = cfg.kv_len_buckets[0]
    batches = reach.get("decode_batch_buckets") or cfg.decode_batch_buckets
    decode = [[b, w, kv0] for b in batches
              for w in cfg.decode_window_buckets]
    top = cfg.kv_bucket_for(min(
        reach["max_context"] + cfg.pipeline_depth * W + 1, S))
    decode += [[cfg.max_num_seqs, W, kv] for kv in cfg.kv_len_buckets[1:]
               if kv <= top]
    prefill = set()
    for length in reach["prompt_lengths"]:
        for start in range(0, length, cfg.prefill_chunk):
            bucket = cfg.bucket_for(min(cfg.prefill_chunk, length - start))
            prefill.add((bucket, cfg.kv_bucket_for(min(start + bucket, S))))
    return {"decode": decode, "prefill": sorted(map(list, prefill))}


def warm(runner, shapes: dict) -> dict:
    """Compile (or load from the persistent cache) and run once every
    executable in ``shapes``, all greedy, as the traffic is. The calls
    are those of ModelRunner.warmup(), over this list instead of its
    fixed grid."""
    import jax
    import numpy as np

    from production_stack_tpu.engine.sampler import SamplingParams
    cfg = runner.engine_cfg
    B, S = cfg.max_num_seqs, cfg.max_model_len
    sampling = SamplingParams.filled(B)
    t0 = time.monotonic()
    for b, w, kv in shapes.get("decode", []):
        runner.set_decode_state(np.zeros((b,), np.int32),
                                np.full((b,), S, np.int32))
        runner.decode(sampling, steps=w, kv_len=kv, greedy=True)
    for bucket, kv in shapes.get("prefill", []):
        runner.prefill(np.zeros((B, bucket), np.int32),
                       np.full((B,), S, np.int32),
                       np.ones((B,), np.int32), sampling, kv)
    jax.block_until_ready(runner.cache.k)
    return {"decode": len(shapes.get("decode", [])),
            "prefill": len(shapes.get("prefill", [])),
            "seconds": round(time.monotonic() - t0, 3)}


def add_routes(app, engine, conf: dict, trace_dir: str) -> None:
    from aiohttp import web

    state = {"t0": None, "t0_unix": None}

    async def probe(request: web.Request) -> web.Response:
        """Body: {"prompts": [[ids]], "ids": [[ids]]}: for each prompt
        the reference's log-probabilities of the next token at ``ids``
        and its own top-20 ids. With ``"control": {key: value}`` the
        reference is run with those keys laid over the configuration's
        (``chipbench/probe_seeds.py``: the reference in a lower
        precision, the limit's upper reading; no benchmark run sends
        it)."""
        body = await request.json()
        ref = importlib.import_module(
            "chipbench.references." + conf["reference"])
        hf = {**conf, **(body.get("control") or {})}

        def compute():
            return ref.next_token_logprobs(
                engine.engine.runner.params, hf,
                body["prompts"], body["ids"])
        t0 = time.monotonic()
        out = await asyncio.to_thread(compute)
        return web.json_response(
            {"rows": out, "seconds": round(time.monotonic() - t0, 3)})

    async def chat_ids(request: web.Request) -> web.Response:
        """The token ids the chat endpoint makes of ``messages`` (the
        probe's prompts go through the program's own template)."""
        body = await request.json()
        tok = engine.tokenizer
        return web.json_response({"ids": tok.encode(
            tok.apply_chat_template(body["messages"]))})

    async def trace_start(request: web.Request) -> web.Response:
        import jax
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        state["t0"], state["t0_unix"] = time.monotonic(), time.time()
        return web.json_response({"started": True})

    async def trace_stop(request: web.Request) -> web.Response:
        import jax
        held_s = time.monotonic() - state["t0"]
        await asyncio.to_thread(jax.profiler.stop_trace)
        return web.json_response({"held_s": held_s,
                                  "started_unix": state["t0_unix"]})

    async def trace_reduce(request: web.Request) -> web.Response:
        """Reduce the trace here, so that the parent stays off JAX; the
        parent asks after the measured window has closed, because the
        reduction competes with the step loop for this process."""
        from chipbench import xplane
        path = xplane.find_xplane(trace_dir)
        try:
            reduced = await asyncio.to_thread(xplane.reduce_file, path)
        except ValueError as e:     # no device in the trace (the CPU)
            reduced = {"error": str(e)}
        shutil.rmtree(trace_dir, ignore_errors=True)
        return web.json_response(reduced)

    app.router.add_post("/chipbench/probe", probe)
    app.router.add_post("/chipbench/chat_ids", chat_ids)
    app.router.add_post("/chipbench/trace/start", trace_start)
    app.router.add_post("/chipbench/trace/stop", trace_stop)
    app.router.add_post("/chipbench/trace/reduce", trace_reduce)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--warm", required=True,
                    help="JSON file: what the traffic reaches")
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    with open(args.config_file) as f:
        conf = json.load(f)
    with open(args.warm) as f:
        reach = json.load(f)

    import jax
    devices = jax.devices()
    print(DEVICE_TAG + json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}), flush=True)
    if devices[0].platform == "cpu" and not args.allow_cpu:
        print("chipbench: JAX found no accelerator", file=sys.stderr)
        return 3
    if devices[0].platform != "cpu" and len(devices) < args.chips:
        print(f"chipbench: {len(devices)} devices, the cell asks "
              f"{args.chips}", file=sys.stderr)
        return 3
    # every executable goes into the persistent cache, however quickly
    # it compiled: a run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from production_stack_tpu.engine import server
    from production_stack_tpu.models import config as model_configs

    name = conf["name"]
    model_configs.PRESETS[name] = model_config(conf, name)

    inner_engine, inner_app = server.AsyncLLMEngine, server.build_app

    def engine_then_warm(cfg, *a, **kw):
        engine = inner_engine(cfg, *a, **kw)
        runner = engine.engine.runner
        # ``shapes``: the list itself (chipbench/probe_seeds.py warms
        # nothing: the probe's prompts compile on first use, as in a run)
        shapes = reach.get("shapes") or shapes_reached(runner.engine_cfg,
                                                       reach)
        print(READY_TAG + json.dumps({**warm(runner, shapes), **shapes}),
              flush=True)
        return engine

    def app_with_routes(engine, *a, **kw):
        app = inner_app(engine, *a, **kw)
        add_routes(app, engine, conf, args.trace_dir)
        return app

    server.AsyncLLMEngine = engine_then_warm
    server.build_app = app_with_routes
    server.main(["--model", name, "--host", "127.0.0.1",
                 "--port", str(args.port), "--seed", str(args.seed),
                 "--no-warmup", *conf["engine_args"]])
    return 0


if __name__ == "__main__":
    sys.exit(main())
