#!/usr/bin/env python3
"""One run of one cell:

    python3 -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent never imports JAX: the engine child holds the chip. It
starts the router and one engine (``chipbench/engine_child.py`` around
the program's own server entry point), waits for ``/health``, runs the
logit probe, offers the cell's traffic client -> router -> engine over
HTTP, cuts a steady window of ``--seconds`` out of it, stops the
children, waits until they are gone, and prints one JSON line last.

No accelerator, fewer chips than the cell asks, or a checkout without
the program: exit code other than 0 and no result line.
``--rehearse`` (tests and CPU rehearsals only, never the driver) lets
the child run on the CPU and marks the line ``"rehearsal": true``; a
rehearsal carries no device metric.
"""

import argparse
import asyncio
import collections
import contextlib
import importlib.util
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

T_PROCESS_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import aiohttp  # noqa: E402

from chipbench import harness_key, reference, traffic, window  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from chipbench.engine_child import DEVICE_TAG  # noqa: E402
from chipbench.stats import median, percentile  # noqa: E402

# what a run leaves behind, all inside the checkout and ignored by git:
# the warm list, and under runs/<workload>.<seed>.<trace>/ (run_dir) the
# children's logs and the profiler's capture of that run alone, so that
# two runs side by side (rehearsals under several test workers) never
# read each other's engine log
STATE_DIR = os.path.join(ROOT, ".chipbench")
START_S = 1100        # engine spawn to /health, first (compiling) run
STOP_S = 90           # per signal, for a child to be gone (PERF.md s6:
#                       5-7 s on one chip, 12-13 s on four)
TRACE_AT = 0.3        # where in the window the profiler is held
TRACE_S = 3.0         # and for how long
UNIX_OFFSET = time.time() - time.monotonic()


class RunFailure(Exception):
    """The run cannot give a result; exit non-zero, print no line."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------
# children
# ---------------------------------------------------------------------

class Child:
    def __init__(self, name: str, cmd: List[str], url: str, where: str,
                 env: Optional[Dict[str, str]] = None):
        os.makedirs(os.path.join(where, "logs"), exist_ok=True)
        self.name, self.url = name, url
        self.log_path = os.path.join(where, "logs", name + ".log")
        with open(self.log_path, "wb") as log:
            self.popen = subprocess.Popen(
                cmd, stdout=log, stderr=subprocess.STDOUT, cwd=ROOT,
                env={**os.environ, **(env or {})})

    def log(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def tail(self, n: int = 30) -> str:
        return "\n".join(self.log().splitlines()[-n:])

    def stop(self) -> float:
        """SIGTERM, then patience: the chip is free only once the
        process is gone, and SIGKILL does not hurry an exit under way."""
        t0 = time.monotonic()
        if self.popen.poll() is None:
            self.popen.terminate()
            try:
                self.popen.wait(timeout=STOP_S)
            except subprocess.TimeoutExpired:
                self.popen.kill()
                self.popen.wait(timeout=STOP_S)
        return time.monotonic() - t0


def run_dir(workload: str, seed: int, trace: int) -> str:
    return os.path.join(STATE_DIR, "runs", f"{workload}.{seed}.{trace}")


def start_engine(cell: mf.Cell, seed: int, reach: Dict, where: str,
                 rehearse: bool) -> Child:
    os.makedirs(STATE_DIR, exist_ok=True)
    warm_file = os.path.join(STATE_DIR, "warm.json")
    with open(warm_file, "w") as f:
        json.dump(reach, f)
    port = free_port()
    cmd = [sys.executable, os.path.join(HERE, "engine_child.py"),
           "--config-file", cell.config_file, "--port", str(port),
           "--seed", str(seed), "--chips", str(cell.chips),
           "--warm", warm_file,
           "--trace-dir", os.path.join(where, "trace")]
    env = {}
    if rehearse:
        cmd.append("--allow-cpu")
        env["JAX_PLATFORMS"] = "cpu"
    return Child("engine", cmd, f"http://127.0.0.1:{port}", where, env)


def start_router(engine_url: str, model: str, where: str) -> Child:
    port = free_port()
    cmd = [sys.executable, "-m", "production_stack_tpu.router.app",
           "--host", "127.0.0.1", "--port", str(port),
           "--service-discovery", "static",
           "--static-backends", engine_url, "--static-models", model,
           "--routing-logic", "roundrobin",
           "--engine-stats-interval", "5"]
    return Child("router", cmd, f"http://127.0.0.1:{port}", where)


async def get_json(session, url: str, **kw) -> Dict:
    async with session.get(url, **kw) as r:
        if r.status != 200:
            raise RunFailure(f"GET {url}: HTTP {r.status}")
        return await r.json()


async def post_json(session, url: str, body: Dict) -> Dict:
    async with session.post(url, json=body) as r:
        if r.status != 200:
            raise RunFailure(f"POST {url}: HTTP {r.status} "
                             f"{(await r.text())[:300]}")
        return await r.json()


async def wait_engine(session, child: Child, chips: int,
                      rehearse: bool) -> Dict:
    """Until /health answers. The child says what it runs on before it
    builds anything: a child that found no chip has exited by then."""
    deadline, device = time.monotonic() + START_S, None
    while True:
        if device is None:
            for line in child.log().splitlines():
                if line.startswith(DEVICE_TAG):
                    device = json.loads(line[len(DEVICE_TAG):])
        if child.popen.poll() is not None:
            raise RunFailure(f"engine child exited "
                             f"{child.popen.returncode}\n{child.tail()}")
        try:
            async with session.get(child.url + "/health") as r:
                if r.status == 200:
                    break
        except (aiohttp.ClientError, OSError, asyncio.TimeoutError):
            pass
        if time.monotonic() > deadline:
            raise RunFailure(f"engine not serving after {START_S}s\n"
                             f"{child.tail()}")
        await asyncio.sleep(0.25)
    if device is None:
        raise RunFailure("the engine child never said what it runs on")
    if not rehearse and (device["platform"] == "cpu"
                         or device["count"] < chips):
        raise RunFailure(f"the engine runs on {device}")
    return device


async def wait_router(session, child: Child) -> None:
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if child.popen.poll() is not None:
            raise RunFailure(f"router exited\n{child.tail()}")
        try:
            async with session.get(child.url + "/health") as r:
                if r.status == 200 and (await r.json()).get(
                        "endpoints", 0) >= 1:
                    return
        except (aiohttp.ClientError, OSError, asyncio.TimeoutError):
            pass
        await asyncio.sleep(0.2)
    raise RunFailure(f"router not ready\n{child.tail()}")


# ---------------------------------------------------------------------
# the logit probe
# ---------------------------------------------------------------------

def probe_contents(seed: int, block: int, chunk: int) -> List[str]:
    """Three seeded prompts that, with the chat template's ~25 tokens,
    end before a KV block boundary, past one, and past a prefill-chunk
    boundary."""
    rng = random.Random(seed ^ 0x9E3779B9)
    letters = "abcdefghijklmnopqrstuvwxyz ,."
    return ["".join(rng.choice(letters) for _ in range(n))
            for n in (block // 2, block + 11, chunk + 48)]


async def run_probe(session, engine_url: str, cell: mf.Cell, seed: int,
                    controls: Optional[Dict[str, Dict]] = None) -> Dict:
    """The three prompts served, the reference over them, the verdict
    within the configuration's limits. ``controls`` (name -> keys laid
    over the configuration's; ``chipbench/probe_seeds.py`` alone gives
    them, a run never) adds ``detail``: what was served and what the
    reference said, and for each control the reference run with those
    keys IN THE PROGRAM'S PLACE: its own top-20 held against the plain
    reference by the same comparison."""
    t0 = time.monotonic()
    args = cell.config["engine_args"]
    key = harness_key.of(cell.config)["probe"]
    limits = (key["logprob_gap_limit"], key["mean_logprob_gap_limit"])

    def arg(flag, default):
        return int(args[args.index(flag) + 1]) if flag in args else default

    async def reference_at(rows, control=None):
        """The reference (or a control) at what ``rows`` served."""
        return await post_json(
            session, engine_url + "/chipbench/probe",
            {"prompts": prompts, "ids": [r["ids"] for r in rows],
             "control": control})
    served, prompts = [], []
    for content in probe_contents(seed, arg("--kv-block-size", 64),
                                  arg("--prefill-chunk", 512)):
        messages = [{"role": "user", "content": content}]
        ids = (await post_json(session, engine_url + "/chipbench/chat_ids",
                               {"messages": messages}))["ids"]
        data = await post_json(
            session, engine_url + "/v1/chat/completions",
            {"model": cell.config["name"], "messages": messages,
             "max_tokens": 1, "temperature": 0.0, "ignore_eos": True,
             "logprobs": True, "top_logprobs": reference.TOP})
        top = data["choices"][0]["logprobs"]["content"][0]["top_logprobs"]
        prompts.append(ids)
        served.append({"prompt_tokens": data["usage"]["prompt_tokens"],
                       "ids": [reference.token_id(t) for t in top],
                       "logprobs": [t["logprob"] for t in top]})
    ref = await reference_at(served)
    out = reference.compare(served, ref["rows"], *limits)
    out["seconds"] = time.monotonic() - t0
    out["reference_seconds"] = ref["seconds"]
    if controls is not None:
        out["detail"] = {"served": served, "reference": ref["rows"],
                         "controls": {}}
        for name, keys in controls.items():
            own = (await reference_at(served, keys))["rows"]
            stand_in = [{"prompt_tokens": r["prompt_tokens"],
                         "ids": r["top_ids"],
                         "logprobs": r["top_logprobs"]} for r in own]
            plain = (await reference_at(stand_in))["rows"]
            out["detail"]["controls"][name] = {
                "keys": keys, "served": stand_in, "reference": plain,
                **reference.compare(stand_in, plain, *limits)}
    return out


# ---------------------------------------------------------------------
# load
# ---------------------------------------------------------------------

class Load:
    """The clients of one run: every request's record, and the tasks."""

    def __init__(self, session, url: str, model: str, plan: traffic.Plan):
        self.session, self.url, self.model = session, url, model
        self.plan = plan
        self.records: List[Dict] = []
        self.tasks: List[asyncio.Task] = []

    async def request(self, req: traffic.Planned, due: float) -> Dict:
        rec = {"index": req.index, "lead_in": req.lead_in, "due": due,
               "sent": None, "token_times": [], "done": False,
               "status": None, "error": None, "trace_id": None,
               "max_tokens": req.output_tokens,
               "prompt_tokens": req.prompt_tokens, "usage": None,
               "ended": False, "cut": False}
        self.records.append(rec)
        body = {"model": self.model,
                "prompt": self.plan.prompt_ids(req),
                "max_tokens": req.output_tokens, "temperature": 0.0,
                "ignore_eos": True, "stream": True,
                # the chosen token's log-probability makes every token
                # a visible SSE event: with random weights the byte
                # tokenizer renders most ids as no text at all
                "logprobs": 0,
                "stream_options": {"include_usage": True}}
        saw_done = False
        try:
            rec["sent"] = time.monotonic()
            async with self.session.post(self.url + "/v1/completions",
                                         json=body) as r:
                rec["status"] = r.status
                rec["trace_id"] = r.headers.get("x-trace-id")
                if r.status != 200:
                    rec["error"] = (await r.text())[:200]
                    return rec
                async for raw in r.content:
                    if not raw.startswith(b"data:"):
                        continue
                    now = time.monotonic()
                    payload = raw[5:].strip()
                    if payload == b"[DONE]":
                        saw_done = True
                        continue
                    chunk = json.loads(payload)
                    for choice in chunk.get("choices") or ():
                        lp = choice.get("logprobs")
                        n = len(lp["tokens"]) if lp else 0
                        rec["token_times"].extend([now] * n)
                    if chunk.get("usage"):
                        rec["usage"] = chunk["usage"]
            rec["done"] = (
                saw_done and len(rec["token_times"]) == req.output_tokens
                and (rec["usage"] or {}).get("completion_tokens")
                == req.output_tokens
                and rec["usage"]["prompt_tokens"] == req.prompt_tokens)
            if not rec["done"]:
                rec["error"] = (f"stream ended: done={saw_done} tokens="
                                f"{len(rec['token_times'])} usage="
                                f"{rec['usage']}")
        except asyncio.CancelledError:
            rec["cut"] = True       # by this harness, at the run's end
            raise
        except Exception as e:   # a failed request is a datum
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            rec["ended"] = True
        return rec

    async def closed(self) -> float:
        """When the window of a closed loop opens: each client runs
        its lead-in request, then takes planned requests from the
        shared stream; the window opens when the last client has sent
        its first planned request."""
        stream, n = self.plan.stream(), self.plan.clients
        started, opened = set(), asyncio.Event()

        async def client(i: int):
            await self.request(self.plan.lead_in[i], time.monotonic())
            while True:
                req = next(stream)
                started.add(i)
                if len(started) == n:
                    opened.set()
                await self.request(req, time.monotonic())

        self.tasks = [asyncio.create_task(client(i)) for i in range(n)]
        await opened.wait()
        return time.monotonic()

    async def open(self, seconds: float, lead_in_s: float) -> float:
        """The schedule starts now; the window opens ``lead_in_s``
        later. Requests are sent when due, whatever is in flight."""
        t_sched = time.monotonic()
        t0 = t_sched + lead_in_s

        async def dispatch():
            for req in self.plan.stream():
                due = t_sched + req.due_s
                if due >= t0 + seconds:
                    return
                delay = due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                self.tasks.append(
                    asyncio.create_task(self.request(req, due)))

        self.tasks.append(asyncio.create_task(dispatch()))
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        return t0

    def in_flight(self) -> int:
        return sum(1 for r in self.records
                   if r["sent"] is not None and not r["ended"])

    async def cancel(self) -> None:
        for t in self.tasks:
            t.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)


def reach_of(cell: mf.Cell, plan: traffic.Plan) -> Dict:
    """What the cell's traffic reaches, for the child to turn into the
    list of executables to warm: every prompt length, the longest
    context, and (from the cell's file) a restriction of the decode
    batch buckets."""
    prompts = sorted(set(plan.prompts)
                     | {r.prompt_tokens for r in plan.lead_in})
    return {"prompt_lengths": prompts,
            "max_context": max(plan.prompts) + max(plan.outputs),
            "decode_batch_buckets":
                cell.params.get("decode_batch_buckets")}


# ---------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------

def counter(text: str, name: str) -> float:
    return sum(float(m.group(1)) for m in re.finditer(
        rf"^{re.escape(name)}(?:{{[^}}]*}})? (\S+)$", text, re.M))


async def text_of(session, url: str) -> str:
    async with session.get(url) as r:
        return await r.text()


def reconcile(records: List[Dict], engine_traces: Dict,
              router_traces: Dict, moved: Dict) -> List[str]:
    """Why the program's own counts disagree with the clients' ([] =
    they agree): every request a client saw complete is in the engine's
    and the router's trace ring as ok, with exactly its prompt and
    output tokens; and the engine's counters moved by at least what the
    clients received (requests cut at the window's end were generating
    still)."""
    problems = []
    by_id = {side: {t["trace_id"]: t for t in body["traces"]}
             for side, body in (("engine", engine_traces),
                                ("router", router_traces))}
    complete = [r for r in records if r["done"]]
    for side, traces in by_id.items():
        ring = (engine_traces if side == "engine"
                else router_traces)["ring_entries"]
        if len(traces) >= ring:
            problems.append(f"{side} trace ring is full ({ring}): "
                            f"requests cannot be reconciled")
            continue
        for r in complete:
            t = traces.get(r["trace_id"])
            if t is None or t["status"] != "ok":
                problems.append(f"request {r['index']}: {side} trace "
                                f"{'missing' if t is None else t['status']}")
            elif side == "engine" and (
                    t["attrs"].get("output_tokens") != r["max_tokens"]
                    or t["attrs"].get("prompt_tokens")
                    != r["prompt_tokens"]):
                problems.append(f"request {r['index']}: engine counted "
                                f"{t['attrs']}, the client "
                                f"{r['prompt_tokens']}+{r['max_tokens']}")
    received = sum(len(r["token_times"]) for r in records)
    if moved["generation_tokens"] < received:
        problems.append(f"engine generation counter moved "
                        f"{moved['generation_tokens']}, clients received "
                        f"{received}")
    sent_ok = sum(1 for r in records if r["status"] == 200)
    if moved["router_requests"] is not None \
            and moved["router_requests"] < sent_ok:
        problems.append(f"router counted {moved['router_requests']} "
                        f"requests, clients had {sent_ok} answered")
    return problems[:10]


@contextlib.asynccontextmanager
async def stack(cell: mf.Cell, seed: int, plan: traffic.Plan,
                rehearse: bool, run: Dict):
    """Router and engine up, healthy and probed; both stopped and gone
    on the way out, whatever happened inside."""
    engine = start_engine(cell, seed, reach_of(cell, plan), run["dir"],
                          rehearse)
    router = start_router(engine.url, cell.config["name"], run["dir"])
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=10,
                                    sock_read=300)
    try:
        async with aiohttp.ClientSession(
                timeout=timeout,
                connector=aiohttp.TCPConnector(limit=0)) as session:
            run["device"] = await wait_engine(session, engine, cell.chips,
                                              rehearse)
            run["engine_ready_s"] = time.monotonic() - T_PROCESS_START
            await wait_router(session, router)
            run["probe"] = await run_probe(session, engine.url, cell, seed)
            yield session, engine, router
    finally:
        run["stop_s"] = max(c.stop() for c in (router, engine))


async def run_cell(cell: mf.Cell, seed: int, seconds: float, trace: bool,
                   rehearse: bool, where: str) -> Dict:
    plan = traffic.make_plan(cell.traffic, seed, seconds,
                             cell.params.get("rate_rps"))
    run: Dict = {"cell": cell.name, "seed": seed, "seconds": seconds,
                 "config_file": cell.config_file, "dir": where}
    async with stack(cell, seed, plan, rehearse, run) as (
            session, engine, router):
        load = Load(session, router.url, cell.config["name"], plan)
        metrics0 = await text_of(session, engine.url + "/metrics")
        rmetrics0 = await text_of(session, router.url + "/metrics")
        if plan.loop == "closed":
            t0 = await load.closed()
        else:
            t0 = await load.open(seconds, plan.lead_in_s)
        t1 = t0 + seconds
        run["setup_s"] = t0 - T_PROCESS_START
        run["window"] = {"t0": t0, "t1": t1, "t0_unix": t0 + UNIX_OFFSET,
                         "t1_unix": t1 + UNIX_OFFSET}
        run["perf_open"] = await get_json(
            session, engine.url + "/debug/perf?limit=1")
        run["in_flight_open"] = load.in_flight()
        run["load_samples"], run["trace"] = [], None
        if trace:
            # once a second the engine's /load; once, the profiler
            trace_at = t0 + TRACE_AT * seconds
            while time.monotonic() < t1 - 0.5:
                if run["trace"] is None and time.monotonic() >= trace_at:
                    await post_json(
                        session, engine.url + "/chipbench/trace/start", {})
                    await asyncio.sleep(TRACE_S)
                    run["trace"] = await post_json(
                        session, engine.url + "/chipbench/trace/stop", {})
                run["load_samples"].append(await get_json(
                    session, engine.url + "/load"))
                await asyncio.sleep(1.0)
        await asyncio.sleep(max(0.0, t1 - time.monotonic()))
        run["in_flight_close"] = load.in_flight()
        run["perf_close"] = await get_json(
            session, engine.url + "/debug/perf?limit=100000")
        await load.cancel()
        await asyncio.sleep(0.5)        # cut requests seal their traces
        run["engine_traces"] = await get_json(
            session, engine.url + "/debug/traces?limit=100000")
        run["router_traces"] = await get_json(
            session, router.url + "/debug/traces?limit=100000")
        metrics1 = await text_of(session, engine.url + "/metrics")
        rmetrics1 = await text_of(session, router.url + "/metrics")
        if run["trace"] is not None:
            run["trace"].update(await post_json(
                session, engine.url + "/chipbench/trace/reduce", {}))
        run["records"] = load.records
        name, rname = ("vllm:generation_tokens_total",
                       "vllm:router_requests_total")
        run["moved"] = {
            "generation_tokens":
                counter(metrics1, name) - counter(metrics0, name),
            "router_requests":
                counter(rmetrics1, rname) - counter(rmetrics0, rname)
                if rname in rmetrics1 else None}
    return run


async def run_sweep(cell: mf.Cell, seed: int, seconds: float,
                    rates: List[float], rehearse: bool, where: str) -> None:
    """One engine start, the open-loop rate stepped inside it: for each
    rate one line with what decides whether the cell sustains it (the
    generator's lag and the requests in flight must not grow over the
    step). The builder reads the knee off these lines once and writes
    0.8 of it into chipbench/cells/<cell>.json; no run searches."""
    plans = [traffic.make_plan(cell.traffic, seed, seconds, r)
             for r in rates]
    run: Dict = {"dir": where}
    async with stack(cell, seed, plans[-1], rehearse, run) as (
            session, engine, router):
        for rate, plan in zip(rates, plans):
            load = Load(session, router.url, cell.config["name"], plan)
            t0 = await load.open(seconds, 0.0)
            flight = []
            for k in range(1, 5):
                await asyncio.sleep(max(
                    0.0, t0 + k * seconds / 4 - time.monotonic()))
                flight.append(load.in_flight())
            rec, t1 = load.records, t0 + seconds
            half = t0 + seconds / 2
            print(json.dumps({
                "rate_rps": rate, "in_flight_by_quarter": flight,
                "sent": sum(1 for r in rec if r["sent"] is not None),
                "lag_p95_ms": percentile(window.lag_ms(rec, t0, t1), 95),
                "ttft_p50_ms_first_half":
                    median(window.ttft_ms(rec, t0, half)),
                "ttft_p50_ms_second_half":
                    median(window.ttft_ms(rec, half, t1)),
                "tpot_p50_ms": median(window.tpot_ms(rec, t0, t1)),
                "out_tokens_per_s":
                    window.out_tokens(rec, t0, t1) / seconds,
                "failed": sum(1 for r in rec if r["ended"]
                              and not r["done"] and not r["cut"]),
            }), flush=True)
            await load.cancel()
            for _ in range(120):        # until the engine is empty
                state = await get_json(session, engine.url + "/load")
                if not state["running"] and not state["queue_depth"]:
                    break
                await asyncio.sleep(0.5)
    print(json.dumps({"sweep_done": True, "device": run["device"],
                      "probe_ok": run["probe"]["ok"]}), flush=True)


# ---------------------------------------------------------------------
# the line
# ---------------------------------------------------------------------

def verdict(run: Dict) -> Dict:
    """``correct``, ``attempted``, ``failed``, why, and each number
    compared beside its limit (``compared``: name -> [number, limit];
    a logit gap may reach its limit, the top lists must share at least
    theirs, the counts must be 0). Of the probe's two gaps those are
    compared for which the configuration's file states a limit."""
    records = run["records"]
    sent = [r for r in records if r["sent"] is not None]
    failed = [r for r in sent
              if r["ended"] and not r["done"] and not r["cut"]]
    why, compared = [], {"requests_failed": [len(failed), 0]}
    if failed:
        why.append(f"{len(failed)} requests failed, first: "
                   f"{failed[0]['status']} {failed[0]['error']}")
    if not run["probe"]["ok"]:
        why.append(f"logit probe: mean gap "
                   f"{run['probe'].get('mean_abs_logprob_diff')} of "
                   f"{run['probe'].get('mean_limit')}; "
                   f"{run['probe']['rows']}")
    probe = run["probe"]
    if probe.get("mean_limit") is not None:
        compared["probe_mean_logprob_gap"] = [
            probe["mean_abs_logprob_diff"], probe["mean_limit"]]
    for i, row in enumerate(probe["rows"]):
        if probe["tolerance"] is not None:
            compared[f"probe{i}_logprob_gap"] = [
                row["max_abs_logprob_diff"], probe["tolerance"]]
        compared[f"probe{i}_shared_top"] = [row["shared_top"],
                                            reference.MIN_SHARED]
    if not run.get("rehearsal"):
        off = harness_key.kernels_off(
            run["perf_close"]["device"],
            harness_key.read(run["config_file"]))
        compared["executables_off_kernels"] = [len(off), 0]
        if off:
            why.append(f"executables off the kernels their "
                       f"configuration names, table[executable]: {off}")
    unreconciled = reconcile(records, run["engine_traces"],
                             run["router_traces"], run["moved"])
    compared["counts_unreconciled"] = [len(unreconciled), 0]
    why += unreconciled
    return {"correct": not why, "attempted": len(sent),
            "failed": len(failed), "why": why, "compared": compared}


def device_block(run: Dict) -> Dict:
    dev = run["perf_close"]["device"]
    peaks = [d["peak_bytes_in_use"] for d in dev["engine_devices"]
             if d.get("peak_bytes_in_use") is not None]
    out = {"platform": dev["platform"], "kind": dev["device_kind"],
           "count": dev["count"],
           "memory_peak_bytes": max(peaks) if peaks else None}
    if run.get("trace") and "busy_s" in run["trace"]:
        out["busy_s"] = run["trace"]["busy_s"]
        out["window_s"] = run["trace"]["window_s"]
    return out


def read_metric(spec: Dict, run: Dict, data_dirs: List[str]):
    path = mf.find("readers", spec["reader"], data_dirs, ext=".py")
    for d in (os.path.join(HERE, "readers"), os.path.dirname(path)):
        if d not in sys.path:       # readers import their siblings
            sys.path.insert(0, d)
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_reader_" + spec["reader"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run, **spec.get("args", {}))


def result_line(cell: mf.Cell, run: Dict, trace: bool,
                data_dirs: List[str]) -> Dict:
    w = run["window"]
    e2e = window.end_to_end(run["records"], w["t0"], w["t1"])
    e2e["setup_s"] = run["setup_s"]
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    end_to_end = {k: {"value": v, "unit": units[k]}
                  for k, v in e2e.items() if k in units and v is not None}
    line = {**verdict(run), "metrics": {}, "device": device_block(run)}
    if trace:
        for spec in cell.per_layer:
            value = read_metric(spec, run, data_dirs)
            if value is not None:
                line["metrics"][spec["name"]] = {"value": value,
                                                 "unit": spec["unit"]}
        line["end_to_end"] = end_to_end
        if not run.get("rehearsal") and "busy_s" not in (run["trace"]
                                                          or {}):
            raise RunFailure(f"the traced run read no device time: "
                             f"{run['trace']}")
        if "top_ops" in (run["trace"] or {}):
            line["breakdown"] = {
                "device_ops": run["trace"]["top_ops"][:10],
                "idle_gaps": run["trace"]["idle_gaps"][:10]}
    else:
        line["metrics"] = end_to_end
    line["probe"] = {k: run["probe"].get(k) for k in
                     ("ok", "mean_abs_logprob_diff", "rows", "seconds",
                      "reference_seconds")}
    line["notes"] = {
        "stop_s": run["stop_s"], "engine_ready_s": run["engine_ready_s"],
        "in_flight_open": run["in_flight_open"],
        "in_flight_close": run["in_flight_close"],
        "requests_finished_in_window": len(
            window.tpot_ms(run["records"], w["t0"], w["t1"])),
        "itl_samples": len(window.itl_ms(run["records"], w["t0"],
                                         w["t1"])),
        "ttft_samples": len(window.ttft_ms(run["records"], w["t0"],
                                           w["t1"])),
        "lag_p50_ms": median(window.lag_ms(run["records"], w["t0"],
                                           w["t1"])),
        "compiles_in_window": sum(
            1 for c in run["perf_close"]["compiles"]
            if w["t0_unix"] <= c["at_unix"] < w["t1_unix"]),
        "executables": [run["perf_open"]["totals"]["compiles_total"],
                        run["perf_close"]["totals"]["compiles_total"]],
        "decode_shapes_in_window": sorted(
            [list(k), n] for k, n in collections.Counter(
                (d["batch"], d["steps"], d["kv_len"])
                for d in run["perf_close"]["windows"]
                if w["t0_unix"] <= d["at_unix"] < w["t1_unix"]).items())}
    if run.get("trace"):
        # which bound the roofline share is of, and from what rows
        line["notes"]["roofline"] = run.get("notes", {}).get(
            "decode_step_roofline")
        line["notes"]["trace_modules"] = run["trace"].get("modules")
    if run.get("rehearsal"):
        line["rehearsal"] = True
    line["compared"] = line.pop("compared")     # the line's last key
    return line


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--data", action="append", default=[],
                    help="a directory searched for traffic/, cells/, "
                         "metrics/, readers/ before chipbench/ (tests)")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU; the line says rehearsal")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated open-loop rates: step through "
                         "them in one engine start, --seconds each, and "
                         "print a line per rate (the builder's tool for "
                         "finding a cell's knee; prints no result line)")
    ap.add_argument("--dump", default=None,
                    help="write the whole run record to this file")
    args = ap.parse_args(argv)
    if importlib.util.find_spec("production_stack_tpu") is None:
        print("chipbench: the program (production_stack_tpu) is not in "
              "this checkout", file=sys.stderr)
        return 2
    try:
        cell = mf.Cell(mf.load(args.manifest), args.workload, args.data)
    except mf.ManifestError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2

    def interrupted(signum, frame):
        raise RunFailure(f"signal {signum}")
    signal.signal(signal.SIGTERM, interrupted)
    # weights and traffic both come from the seed; the engine's is an
    # int32, the driver's seeds are wider
    seed = args.seed % 0x7FFFFFFF
    where = run_dir(args.workload, args.seed, args.trace)
    try:
        if args.sweep:
            asyncio.run(run_sweep(
                cell, seed, args.seconds,
                [float(r) for r in args.sweep.split(",")], args.rehearse,
                where))
            return 0
        run = asyncio.run(run_cell(cell, seed, args.seconds,
                                   bool(args.trace), args.rehearse, where))
        run["rehearsal"] = args.rehearse
        line = result_line(cell, run, bool(args.trace), args.data)
    except RunFailure as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    if args.dump:
        with open(args.dump, "w") as f:
            json.dump(run, f)
    print(json.dumps(line), flush=True)
    for name, (value, limit) in line["compared"].items():
        print(f"chipbench: compared {name} = {value}, limit {limit}",
              file=sys.stderr)
    if line["correct"]:     # a run at fault keeps its children's logs
        shutil.rmtree(where, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
