#!/usr/bin/env python3
"""Which ``pstpu.*`` intervals a profiler capture holds, by thread.

    python3 tools/capture_names.py --dir <capture directory>
    python3 tools/capture_names.py --serve debug-tiny

(On a TPU a preset whose widths the attention kernels refuse, as
debug-tiny's, is served with ``PSTPU_FLASH=0``.) ``--dir`` reads a capture that ``POST /debug/profile`` wrote (its
answer names the directory). ``--serve`` makes one: an engine server of
that model preset in this process, four streamed chat requests, a
capture of ``--seconds`` started before them. Either way one JSON line
is printed last: per host thread that carries any, the ``pstpu.*``
names with their counts and summed milliseconds, and how many device
operations the capture holds. The step timeline's phases
(``pstpu.<phase>``, ``pstpu.step``) lie on the engine thread's line,
the loop timeline's two (``pstpu.loop.dispatch``, ``pstpu.loop.write``)
on the event loop's, all on the device trace's clock
(docs/observability.md "Names on the device").
"""

import argparse
import asyncio
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def names_in(capture_dir: str) -> dict:
    from jax.profiler import ProfileData

    from chipbench.xplane import find_xplane
    path = find_xplane(capture_dir)
    threads, device_ops = {}, 0
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            if plane.name.startswith("/device:"):
                if line.name == "XLA Ops":
                    device_ops += sum(1 for _ in line.events)
                continue
            thread = f"{line.name}#{i}"     # threads share the name
            for ev in line.events:
                if ev.name.startswith("pstpu."):
                    row = threads.setdefault(thread, {}).setdefault(
                        ev.name, [0, 0.0])
                    row[0] += 1
                    row[1] += ev.duration_ns * 1e-6
    return {"file": path, "device_ops": device_ops,
            "threads": {t: {n: [c, round(ms, 3)]
                            for n, (c, ms) in sorted(names.items())}
                        for t, names in threads.items()}}


async def _serve(model: str, seconds: float) -> str:
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.server import build_app
    engine = AsyncLLMEngine(EngineConfig(
        model=model, max_model_len=256, max_num_seqs=4))

    async def chat(client, i, tokens):
        r = await client.post("/v1/chat/completions", json={
            "model": model, "max_tokens": tokens, "temperature": 0.0,
            "ignore_eos": True, "stream": True, "logprobs": True,
            "messages": [{"role": "user", "content": f"count to {i}"}]})
        assert r.status == 200, await r.text()
        await r.read()

    async with TestClient(TestServer(build_app(engine))) as client:
        # every shape the captured streams reach is compiled first
        await asyncio.gather(*[chat(client, i, 200) for i in range(1, 5)])
        capture = asyncio.ensure_future(client.post(
            "/debug/profile", json={"seconds": seconds}))
        await asyncio.sleep(0.2)                # the profiler has started
        await asyncio.gather(*[chat(client, i, 200) for i in range(1, 5)])
        r = await capture
        assert r.status == 200, await r.text()
        return (await r.json())["dir"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dir", default=None)
    ap.add_argument("--serve", default=None, metavar="MODEL")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    if (args.dir is None) == (args.serve is None):
        ap.error("one of --dir and --serve")
    capture = args.dir or asyncio.run(_serve(args.serve, args.seconds))
    print(json.dumps(names_in(capture)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
