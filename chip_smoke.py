#!/usr/bin/env python3
"""The quickest proof that the serving stack still starts on the chip.

Drives the main path once, through the entry points a user would call:
``python -m production_stack_tpu.engine.server --model mistral-7b`` behind
``python -m production_stack_tpu.router.app --service-discovery static``,
at Mistral-7B widths (hidden 4096, ffn 14336, 32 q / 8 kv heads, head dim
128, vocab 32000 — never cut), random weights from ``--seed``, the byte
tokenizer. No checkpoint, no network.

    python chip_smoke.py            # one chip; what the driver runs
    python chip_smoke.py --chips 4  # ONLY the tensor-parallel path

One chip, engine children strictly one after the other (a chip belongs
to one process at a time; this parent never imports JAX, so it never
holds it):

1. cold start — the engine child warms up its executable grid with the
   persistent compile cache empty; stopped once it answers;
2. cached start — the same child again, identical flags: the warm-up now
   reads the cache, and must be shorter;
3. serve — the router in front of that second child: /v1/models, 8
   concurrent chat completions (half streaming, one prompt longer than
   the prefill chunk, one with logprobs), then /health, /load and the
   /metrics counters that must have moved;
4. kernel against reference — the same ``max_tokens=1, logprobs``
   prompts to the second child (Pallas paged attention) and to a third
   started with ``PSTPU_FLASH=0 --no-warmup`` (the gathered-copy
   jax.numpy attention): their top log-probabilities must agree.

``--chips 4`` runs an engine with ``--tensor-parallel-size 4`` and a
one-chip engine of the same seed and depth, sends both the log-prob
prompts, and fails unless the log-probs agree and every one of the four
devices holds its share of the weights and the KV pool.

What each child runs on is read from the ``device`` block of its
``GET /debug/perf``. Any phase that fails, any executable off the
attention path its phase expects, or a platform other than ``tpu``, ends
the run non-zero. Earlier lines are one JSON object per phase; the last
line is only ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

import argparse
import asyncio
import json
import math
import os
import re
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import aiohttp  # noqa: E402

# neither import touches JAX: the children own the chip
from production_stack_tpu.loadgen import orchestrator as orch  # noqa: E402
from production_stack_tpu.utils import compile_cache_dir  # noqa: E402

MODEL = "mistral-7b"
# Depth: all 32 layers. In bf16 the model is 14.5 GB against 16 GB of
# HBM; with int8 weights (built and quantized leaf by leaf,
# llama.init_params) it is 7.3 GB beside the 2 GiB bf16 KV pool of the
# server's default geometry (8 slots x 2048 tokens). KV stays bf16.
DEPTH_NOTE = ("all 32 layers, --quantization int8: bf16 weights are "
              "14.5 GB of the chip's 16 GB, int8 weights 7.3 GB beside "
              "a 2 GiB bf16 KV pool")
# The server's defaults otherwise (max-model-len 2048, 8 slots, prefill
# chunk 512, decode window 8), so the warm-up is the default 39
# executables.
ENGINE_FLAGS = ["--quantization", "int8", "--seed", "0"]
PREFILL_CHUNK = 512          # the server default the long prompt exceeds
BLOCK = 64                   # the default KV block size
EXPECT_PLATFORM = "tpu"
KERNEL_MODE = "compiled"     # not "interpret": that is the CPU's
# llama.attention_path's names, as regexes
KERNEL, KERNEL_SHARDED = ("pallas_paged(_decode)?",
                          "pallas_paged(_decode)?_sharded")
JNP = "jnp_gather"

# fixed, ignored paths (chiprun brings chiprun_out/ back)
LOG_DIR = os.path.join(REPO, "chiprun_out", "smoke-logs")

# One process-wide budget: the contract is exit within 1200 s.
DEADLINE_S = 1150
START_S = 780                # spawn to /health, for any one child
STOP_S = 90                  # per signal, for a child to be gone

# Top log-probabilities of one prompt on two attention implementations
# (or two shardings) of the same int8 weights. Both are correct to bf16:
# activations carry 8 bits of mantissa, the two sides accumulate in
# float32 in a different order (and the jax.numpy path rounds its
# softmax weights to bf16, which the kernels keep in float32), so some
# attention outputs differ by one bf16 step — and 32 layers of RANDOM
# weights amplify such a step instead of averaging it away. The
# tolerance is therefore calibrated, not derived: about twice the
# largest difference measured on the v5e in PR 21 between two sides
# that are both right — tp=4 against tp=1, which differ only in the
# order of their sums, 0.10 to 0.17 over the three prompts; kernel
# against jax.numpy 0.09 to 0.16, with no step up at a block or a chunk
# boundary. The same comparison at debug-tiny on the CPU
# measures 0.0035, and a kernel whose causal mask is off by one position
# measures 0.043 there, 12 x the noise. It is a smoke check on the
# path, not a numerics test of the kernels — those are
# tests/test_pallas_paged.py and tests/test_kv_int8.py.
LOGPROB_TOL = {"kernel_vs_jnp": 0.3, "tp4_vs_tp1": 0.3}
TOP_K = 20                   # the API's maximum
MIN_SHARED = 10              # tokens both top-20 lists must share


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------
# what the device block must say
# ---------------------------------------------------------------------

def device_problems(device: Dict, *, chips: int,
                    paths: Optional[str] = None) -> List[str]:
    """Why this ``device`` block fails the run ([] = it does not): the
    platform and the device count asked for and, where ``paths`` is
    given (a regex over llama.attention_path's names), the kernels'
    mode and every compiled executable on an attention path of that
    name — a jax.numpy path where the kernel was expected fails here."""
    problems = []
    if device.get("platform") != EXPECT_PLATFORM:
        problems.append(f"platform is {device.get('platform')!r}, not "
                        f"{EXPECT_PLATFORM!r}")
    if device.get("count") != chips:
        problems.append(f"{device.get('count')} devices, not {chips}")
    if paths is None:
        return problems
    mode = "off" if paths == JNP else KERNEL_MODE
    if device.get("pallas_attention") != mode:
        problems.append(f"pallas_attention is "
                        f"{device.get('pallas_attention')!r}, not "
                        f"{mode!r}")
    executables = device.get("attention_paths") or {}
    if not executables:
        problems.append("no executable compiled")
    for exe, path in sorted(executables.items()):
        if not re.fullmatch(paths, path):
            problems.append(f"executable {exe} took attention path "
                            f"{path!r}, expected {paths!r}")
    return problems


def shard_problems(device: Dict, weight_bytes: int) -> List[str]:
    """Do all the engine's devices hold a share? Each must have at
    least 0.8 of an even split of the weights in use, and none more
    than twice the mean (code that has never seen a second chip may put
    everything on the first)."""
    used = [d.get("bytes_in_use") for d in device["engine_devices"]]
    if any(u is None for u in used):
        return [f"no memory_stats() for some device: {used}"]
    floor = 0.8 * weight_bytes / len(used)
    mean = sum(used) / len(used)
    return ([f"device {i} holds {u} bytes < {floor:.0f}"
             for i, u in enumerate(used) if u < floor]
            + [f"device {i} holds {u} bytes > 2x mean {mean:.0f}"
               for i, u in enumerate(used) if u > 2 * mean])


def report(ok: bool, device: Dict) -> int:
    """The last line, and the exit code."""
    print(json.dumps({"ok": ok, "device": {
        "platform": device.get("platform"),
        "kind": device.get("device_kind"),
        "count": device.get("count")}}), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------------
# children
# ---------------------------------------------------------------------

# the engine's one line at start (engine/engine.py)
_DEVICE_LINE = re.compile(r"engine device: platform=(\w+) "
                          r"device_kind='([^']*)' devices=\d+ "
                          r"\(process sees (\d+)\)")


def cache_entries() -> int:
    path = compile_cache_dir()
    if not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))


def log_tail(proc: orch.Proc, n: int = 40) -> str:
    with open(proc.log_path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


class Child:
    """One engine-server child, from launch to a verified stop."""

    def __init__(self, name: str, flags: List[str], seen: Dict,
                 env: Optional[Dict[str, str]] = None):
        self.name = name
        self.flags, self.env = ENGINE_FLAGS + flags, env or {}
        self.seen = seen       # the device as last reported, for main()
        self.cache_before = cache_entries()
        self.t0 = time.monotonic()
        # platform "": the child takes whatever JAX finds — the chip on
        # a machine that has one. launch_engine refuses a second
        # chip-owning child while one is alive.
        self.proc = orch.launch_engine(
            MODEL, orch.free_port(), log_dir=LOG_DIR, platform="",
            geometry=self.flags, env=env)
        self.url = self.proc.url
        self.start_s = None

    async def ready(self, session: aiohttp.ClientSession) -> None:
        """Wait for /health. The engine says what it runs on in one log
        line before it builds the weights: a child that found no chip
        is stopped there, not after minutes of CPU initialisation."""
        deadline, said = self.t0 + START_S, None
        while True:
            if self.proc.popen.poll() is not None:
                raise SmokeFailure(
                    f"{self.name}: engine exited "
                    f"{self.proc.popen.returncode} before serving\n"
                    + log_tail(self.proc))
            if said is None:
                with open(self.proc.log_path, errors="replace") as f:
                    said = _DEVICE_LINE.search(f.read())
                if said:
                    self.seen.update(platform=said.group(1),
                                     device_kind=said.group(2),
                                     count=int(said.group(3)))
                    require(said.group(1) == EXPECT_PLATFORM,
                            f"{self.name}: engine runs on platform "
                            f"{said.group(1)!r}, not "
                            f"{EXPECT_PLATFORM!r}")
            try:
                async with session.get(f"{self.url}/health") as r:
                    if r.status == 200:
                        break
            except aiohttp.ClientError:
                pass
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"{self.name}: not serving after {START_S}s\n"
                    + log_tail(self.proc))
            await asyncio.sleep(0.5)
        self.start_s = round(time.monotonic() - self.t0, 1)

    async def perf(self, session: aiohttp.ClientSession) -> Dict:
        async with session.get(f"{self.url}/debug/perf") as r:
            require(r.status == 200, f"/debug/perf {r.status}")
            perf = await r.json()
        self.seen.update(perf["device"])
        return perf

    async def started(self, session, *, chips: int,
                      paths: Optional[str] = None) -> Dict:
        """ready() + the start-up facts, printed and checked (``paths``
        as device_problems; None for a child that has not warmed up)."""
        await self.ready(session)
        perf = await self.perf(session)
        dev, totals = perf["device"], perf["totals"]
        emit(self.name, model=MODEL, depth=DEPTH_NOTE,
             flags=self.flags, env=self.env, start_s=self.start_s,
             compiles=totals["compiles_total"],
             compile_s=totals["compile_s_total"],
             cache_dir=compile_cache_dir(),
             cold=self.cache_before == 0,
             cache_entries_before=self.cache_before,
             cache_entries_after=cache_entries(),
             platform=dev["platform"], device_kind=dev["device_kind"],
             count=dev["count"], pallas_attention=dev["pallas_attention"],
             weight_bytes=totals["weight_bytes"],
             bytes_in_use=[d["bytes_in_use"]
                           for d in dev["engine_devices"]])
        problems = device_problems(dev, chips=chips, paths=paths)
        require(not problems, f"{self.name}: " + "; ".join(problems))
        return perf

    def stop(self) -> None:
        """SIGTERM, and patience: the chip is free for the next child
        only once this one is gone, and a process that held four chips
        was still exiting 15 s after the signal (PR 21: orch._stop's
        10 s + kill gave up on it)."""
        popen, t0 = self.proc.popen, time.monotonic()
        if popen.poll() is None:
            popen.terminate()
            try:
                popen.wait(timeout=STOP_S)
            except subprocess.TimeoutExpired:
                popen.kill()
                popen.wait(timeout=STOP_S)
        emit(self.name + "_stopped",
             stop_s=round(time.monotonic() - t0, 1))


# ---------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------

def chat_body(content: str, max_tokens: int, **extra) -> Dict:
    return {"model": MODEL, "max_tokens": max_tokens, "ignore_eos": True,
            "messages": [{"role": "user", "content": content}], **extra}


def check_logprobs(entries: List[Dict], n: int, who: str) -> None:
    require(len(entries) == n, f"{who}: {len(entries)} logprob entries, "
                               f"asked {n}")
    for e in entries:
        for lp in [e["logprob"]] + [t["logprob"]
                                    for t in e["top_logprobs"]]:
            require(math.isfinite(lp) and lp <= 1e-6,
                    f"{who}: log-prob {lp}")


async def one_chat(session, base: str, i: int, body: Dict) -> Dict:
    """One chat completion through ``base``; returns what was observed
    (ttft_s is the first streamed chunk, or the whole reply when not
    streaming)."""
    who = f"request {i}"
    want = body["max_tokens"]
    t0 = time.monotonic()
    async with session.post(f"{base}/v1/chat/completions",
                            json=body) as r:
        if r.status != 200:
            raise SmokeFailure(f"{who}: HTTP {r.status} "
                               f"{(await r.text())[:300]}")
        if not body.get("stream"):
            data = await r.json()
            ttft = time.monotonic() - t0
            choice, usage = data["choices"][0], data["usage"]
            if body.get("logprobs"):
                check_logprobs(choice["logprobs"]["content"], want, who)
        else:
            ttft, usage, choice, line = None, None, {}, ""
            async for raw in r.content:
                line = raw.decode().strip()
                if not line.startswith("data:") or line == "data: [DONE]":
                    continue
                chunk = json.loads(line[5:])
                if chunk.get("choices"):
                    if ttft is None:
                        ttft = time.monotonic() - t0
                    if chunk["choices"][0].get("finish_reason"):
                        choice = chunk["choices"][0]
                usage = chunk.get("usage") or usage
            require(ttft is not None and usage is not None,
                    f"{who}: stream ended without chunks or usage, "
                    f"last line {line[:300]!r}")
    require(usage["completion_tokens"] == want,
            f"{who}: {usage['completion_tokens']} completion tokens, "
            f"asked {want}")
    require(choice.get("finish_reason") == "length",
            f"{who}: finish_reason {choice.get('finish_reason')!r}")
    return {"i": i, "stream": bool(body.get("stream")),
            "prompt_tokens": usage["prompt_tokens"],
            "completion_tokens": usage["completion_tokens"],
            "ttft_s": round(ttft, 3),
            "total_s": round(time.monotonic() - t0, 3)}


def counter(text: str, name: str) -> float:
    """Sum of a Prometheus counter's samples over its label sets."""
    return sum(float(m.group(1)) for m in re.finditer(
        rf"^{re.escape(name)}(?:{{[^}}]*}})? (\S+)$", text, re.M))


async def get_text(session, url: str) -> str:
    async with session.get(url) as r:
        require(r.status == 200, f"{url}: HTTP {r.status}")
        return await r.text()


async def serve_phase(session, engine: Child) -> None:
    """Router in front of the engine; the request mix of the issue."""
    router = orch.launch_router([engine.url], MODEL, orch.free_port(),
                                routing="roundrobin", log_dir=LOG_DIR)
    try:
        await orch.wait_healthy(router.url, 60, require_endpoints=1)
        models = json.loads(await get_text(session,
                                           f"{router.url}/v1/models"))
        require(MODEL in [m["id"] for m in models["data"]],
                f"/v1/models lists {models}")
        before = await get_text(session, f"{engine.url}/metrics")
        totals = (await engine.perf(session))["totals"]
        compiles_before = totals["compiles_total"]
        compile_s_before = totals["compile_s_total"]

        long_prompt = "The quick brown fox jumps over the lazy dog. " * 16
        require(len(long_prompt) > PREFILL_CHUNK, "long prompt too short")
        bodies = []
        for i in range(8):
            body = chat_body(f"Request {i}: say something.",
                             max_tokens=(16, 24, 32, 12)[i % 4],
                             stream=i % 2 == 0)
            if body["stream"]:
                body["stream_options"] = {"include_usage": True}
            if i >= 4:
                body["temperature"] = 0.0   # half greedy, half sampled
            bodies.append(body)
        # one prompt past the prefill chunk: its second chunk prefills
        # while the other rows already decode
        bodies[1]["messages"][0]["content"] = long_prompt
        bodies[3].update(logprobs=True, top_logprobs=5)
        results = await asyncio.gather(*(
            one_chat(session, router.url, i, b)
            for i, b in enumerate(bodies)))
        require(results[1]["prompt_tokens"] > PREFILL_CHUNK,
                f"long prompt is {results[1]['prompt_tokens']} tokens")
        for res in results:
            emit("request", **res)

        for base in (engine.url, router.url):
            await get_text(session, f"{base}/health")
        load = json.loads(await get_text(session, f"{engine.url}/load"))
        require(load["max_num_seqs"] == 8
                and load["perf"]["token_steps"]["real"] > 0,
                f"/load after the requests: {load}")
        await get_text(session, f"{router.url}/metrics")
        after = await get_text(session, f"{engine.url}/metrics")
        moved = {name: counter(after, name) - counter(before, name)
                 for name in ("vllm:generation_tokens_total",
                              "vllm:prompt_tokens_total")}
        asked = sum(b["max_tokens"] for b in bodies)
        require(moved["vllm:generation_tokens_total"] == asked
                and moved["vllm:prompt_tokens_total"]
                == sum(r["prompt_tokens"] for r in results),
                f"/metrics counters moved by {moved}, asked {asked}")
        # what warm-up left cold (larger kv buckets, top-k variants)
        # compiled inside these requests: their TTFTs include it
        totals = (await engine.perf(session))["totals"]
        emit("serve", requests=len(results), generation_tokens=asked,
             counters_moved=moved,
             compiles_while_serving=totals["compiles_total"]
             - compiles_before,
             compile_s_while_serving=round(
                 totals["compile_s_total"] - compile_s_before, 1))
    finally:
        orch._stop([router])


def logprob_prompts() -> List[str]:
    """Short; one past a KV block boundary; one past a prefill-chunk
    boundary (the chat template adds ~25 byte-tokens to each)."""
    return ["Hello.",
            "x" * (BLOCK + 8),
            "The rain in Spain stays mainly in the plain. "
            * (PREFILL_CHUNK // 45 + 2)]


async def top_logprobs(session, base: str) -> List[Dict]:
    """[{token bytes -> logprob}] of the first generated token, per
    prompt, one request at a time (the same batch composition on every
    engine)."""
    out = []
    for prompt in logprob_prompts():
        body = chat_body(prompt, 1, temperature=0.0, logprobs=True,
                         top_logprobs=TOP_K)
        async with session.post(f"{base}/v1/chat/completions",
                                json=body) as r:
            require(r.status == 200, f"logprob prompt: HTTP {r.status}")
            data = await r.json()
        entries = data["choices"][0]["logprobs"]["content"]
        check_logprobs(entries, 1, "logprob prompt")
        out.append({"prompt_tokens": data["usage"]["prompt_tokens"],
                    "top": {tuple(t["bytes"]): t["logprob"]
                            for t in entries[0]["top_logprobs"]}})
    return out


def compare_logprobs(a: List[Dict], b: List[Dict], which: str) -> None:
    """Compare log-probs, not sampled tokens: over the tokens both
    top-K lists name, the largest difference must be inside the
    tolerance (whose reason is at LOGPROB_TOL). Every prompt is
    printed before the first failure is raised."""
    tol, problems = LOGPROB_TOL[which], []
    for pa, pb in zip(a, b, strict=True):
        shared = pa["top"].keys() & pb["top"].keys()
        worst = max((abs(pa["top"][t] - pb["top"][t]) for t in shared),
                    default=float("inf"))
        emit(which, prompt_tokens=pa["prompt_tokens"],
             shared_top_tokens=len(shared),
             max_abs_logprob_diff=round(worst, 5), tolerance=tol)
        if pa["prompt_tokens"] != pb["prompt_tokens"]:
            problems.append("prompt lengths differ")
        if len(shared) < MIN_SHARED:
            problems.append(f"the top-{TOP_K} lists of the "
                            f"{pa['prompt_tokens']}-token prompt share "
                            f"only {len(shared)} tokens")
        if not worst <= tol:
            problems.append(f"log-probs of the {pa['prompt_tokens']}"
                            f"-token prompt differ by {worst} > {tol}")
    require(not problems, f"{which}: " + "; ".join(problems))


# ---------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------

async def one_chip(session, seen: Dict) -> None:
    cold = Child("cold_start", [], seen)
    try:
        await cold.started(session, chips=1, paths=KERNEL)
    finally:
        cold.stop()
    # (a machine may come with the cache of an earlier call: then the
    # first start is not a cold one, and says so — "cold": false)
    require(cache_entries() > 0,
            f"the first start left nothing in {compile_cache_dir()}")

    warm = Child("cached_start", [], seen)
    try:
        await warm.started(session, chips=1, paths=KERNEL)
        if cold.cache_before == 0:
            # only an empty cache makes the first start a cold one
            require(warm.start_s < cold.start_s,
                    f"cached start took {warm.start_s}s, cold "
                    f"{cold.start_s}s")
        await serve_phase(session, warm)
        kernel_lps = await top_logprobs(session, warm.url)
        perf = await warm.perf(session)
        dev = perf["device"]
        problems = device_problems(dev, chips=1, paths=KERNEL)
        require(not problems, "after serving: " + "; ".join(problems))
        emit("served_device", attention_paths=dev["attention_paths"],
             compiles=perf["totals"]["compiles_total"],
             compile_s=perf["totals"]["compile_s_total"],
             peak_bytes_in_use=[d["peak_bytes_in_use"]
                                for d in dev["engine_devices"]],
             bytes_limit=[d["bytes_limit"]
                          for d in dev["engine_devices"]])
    finally:
        warm.stop()

    ref = Child("reference_start", ["--no-warmup"], seen,
                env={"PSTPU_FLASH": "0"})
    try:
        await ref.started(session, chips=1)
        ref_lps = await top_logprobs(session, ref.url)
        ref_dev = (await ref.perf(session))["device"]
        problems = device_problems(ref_dev, chips=1, paths=JNP)
        require(not problems, "reference: " + "; ".join(problems))
        emit("reference_device",
             attention_paths=ref_dev["attention_paths"])
    finally:
        ref.stop()
    compare_logprobs(kernel_lps, ref_lps, "kernel_vs_jnp")


async def four_chips(session, chips: int, seen: Dict) -> None:
    async def engine(name: str, flags: List[str], paths: str):
        child = Child(name, flags + ["--no-warmup"], seen)
        try:
            await child.started(session, chips=chips)
            lps = await top_logprobs(session, child.url)
            # a few decode windows too, so the wide kernel runs
            res = await one_chat(session, child.url, 0, chat_body(
                "Decode a little.", 12, temperature=0.0))
            emit("request", engine=name, **res)
            perf = await child.perf(session)
            problems = device_problems(perf["device"], chips=chips,
                                       paths=paths)
            require(not problems, f"{name}: " + "; ".join(problems))
            emit(name + "_device",
                 attention_paths=perf["device"]["attention_paths"],
                 bytes_in_use=[d["bytes_in_use"] for d in
                               perf["device"]["engine_devices"]])
            return lps, perf
        finally:
            child.stop()

    # the one-chip engine first, so the device this run reports last
    # is the tensor-parallel engine's
    one_lps, _ = await engine("one_chip_start", [], KERNEL)
    tp_lps, tp_perf = await engine(
        "tp_start", ["--tensor-parallel-size", str(chips)],
        KERNEL_SHARDED)
    spans = tp_perf["device"]["engine_devices"]
    require(len(spans) == chips, f"the engine spans {spans}")
    problems = shard_problems(tp_perf["device"],
                              tp_perf["totals"]["weight_bytes"])
    require(not problems, "sharding: " + "; ".join(problems))
    compare_logprobs(tp_lps, one_lps, "tp4_vs_tp1")


async def run(chips: int, seen: Dict) -> None:
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=5,
                                    sock_read=300)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        if chips == 1:
            await one_chip(session, seen)
        else:
            await four_chips(session, chips, seen)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run ONLY the tensor-parallel path and the "
                         "one-chip engine it is compared with")
    args = ap.parse_args(argv)

    def interrupted(signum, frame):
        # raised inside whatever phase runs: its finally stops the
        # children before the run ends
        raise SmokeFailure(f"signal {signum} after "
                           f"{time.monotonic() - t0:.0f}s (the budget "
                           f"is {DEADLINE_S}s)")

    t0 = time.monotonic()
    signal.signal(signal.SIGALRM, interrupted)
    signal.signal(signal.SIGTERM, interrupted)
    signal.alarm(DEADLINE_S)
    seen: Dict = {}     # the device, as the children last reported it
    try:
        asyncio.run(run(args.chips, seen))
    except SmokeFailure as e:
        # a check that did not hold is the run's verdict; any other
        # exception is a fault of the program and keeps its traceback
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return report(False, seen)
    finally:
        signal.alarm(0)
    return report(True, seen)


if __name__ == "__main__":
    sys.exit(main())
