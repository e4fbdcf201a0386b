"""Engine HTTP server tests via aiohttp TestClient (in-process, CPU)."""

import asyncio
import json

import pytest

from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.server import build_app


@pytest.fixture(scope="module")
def engine():
    cfg = EngineConfig(model="debug-tiny", max_model_len=128, max_num_seqs=2,
                       prefill_chunk=32, prefill_buckets=(16, 32))
    eng = AsyncLLMEngine(cfg)
    eng.engine.runner.warmup()
    return eng


def _with_client(engine, coro):
    async def runner():
        app = build_app(engine)
        async with TestClient(TestServer(app)) as client:
            return await coro(client)
    return asyncio.run(runner())


def test_models_and_health(engine):
    async def body(client):
        r = await client.get("/v1/models")
        assert r.status == 200
        data = await r.json()
        assert data["data"][0]["id"] == "debug-tiny"
        r = await client.get("/health")
        assert r.status == 200
        r = await client.get("/version")
        assert (await r.json())["version"]
    _with_client(engine, body)


def test_debug_perf_device_block(engine):
    """GET /debug/perf says what the engine runs on, as JAX reports it,
    and which attention path every compiled executable took — here the
    CPU, where the Pallas kernels are off."""
    import jax

    async def body(client):
        r = await client.get("/debug/perf")
        assert r.status == 200
        dev = (await r.json())["device"]
        assert dev["platform"] == "cpu" == jax.devices()[0].platform
        assert dev["device_kind"] == jax.devices()[0].device_kind
        assert dev["count"] == len(jax.devices())
        # one device holds the unsharded engine; the CPU backend has
        # no memory_stats()
        assert [d["bytes_in_use"] for d in dev["engine_devices"]] == [None]
        assert dev["pallas_attention"] == "off"
        paths = dev["attention_paths"]
        # every warmed executable, keyed like totals.compiles
        assert {"decode|8|128|2", "prefill|16|128|2",
                "prefill|32|128|2"} <= set(paths)
        assert set(paths.values()) == {"jnp_gather"}
    _with_client(engine, body)


def test_chat_completion(engine):
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 5, "temperature": 0.0})
        assert r.status == 200
        data = await r.json()
        assert data["object"] == "chat.completion"
        assert data["usage"]["completion_tokens"] == 5
        assert data["choices"][0]["finish_reason"] == "length"
    _with_client(engine, body)


def test_chat_completion_stream(engine):
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 5, "stream": True})
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        raw = (await r.read()).decode()
        events = [line[len("data: "):] for line in raw.splitlines()
                  if line.startswith("data: ")]
        assert events[-1] == "[DONE]"
        chunks = [json.loads(e) for e in events[:-1]]
        assert chunks[0]["choices"][0]["delta"]["role"] == "assistant"
        assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    _with_client(engine, body)


def test_chat_stream_include_usage(engine):
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 5, "stream": True,
            "stream_options": {"include_usage": True}})
        assert r.status == 200
        raw = (await r.read()).decode()
        events = [line[len("data: "):] for line in raw.splitlines()
                  if line.startswith("data: ")]
        assert events[-1] == "[DONE]"
        chunks = [json.loads(e) for e in events[:-1]]
        # OpenAI stream_options semantics: with include_usage, every
        # non-final chunk carries "usage": null; the tail chunk carries
        # only usage (empty choices)
        assert all(c.get("usage") is None and "usage" in c
                   for c in chunks[:-1])
        tail = chunks[-1]
        assert tail["choices"] == []
        assert tail["usage"]["completion_tokens"] == 5
        assert tail["usage"]["prompt_tokens"] > 0
    _with_client(engine, body)


def test_completions_and_token_api(engine):
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "abc", "max_tokens": 4,
            "temperature": 0.0})
        assert r.status == 200
        data = await r.json()
        assert data["object"] == "text_completion"

        r = await client.post("/tokenize", json={"prompt": "abc"})
        toks = (await r.json())["tokens"]
        r = await client.post("/detokenize", json={"tokens": toks})
        assert (await r.json())["prompt"] == "abc"
    _with_client(engine, body)


def test_bad_requests(engine):
    async def body(client):
        r = await client.post("/v1/chat/completions", json={"model": "x"})
        assert r.status == 400
        assert "error" in await r.json()
        r = await client.post("/v1/chat/completions", data=b"not json",
                              headers={"Content-Type": "application/json"})
        assert r.status == 400
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny", "n": 0,
            "messages": [{"role": "user", "content": "x"}]})
        assert r.status == 400
    _with_client(engine, body)


def test_metrics_exposition(engine):
    async def body(client):
        r = await client.get("/metrics")
        text = (await r.read()).decode()
        for name in ("vllm:num_requests_running", "vllm:num_requests_waiting",
                     "vllm:gpu_cache_usage_perc", "tpu:hbm_kv_usage_perc",
                     "vllm:time_to_first_token_seconds"):
            assert name in text, f"missing metric {name}"
    _with_client(engine, body)


def test_chat_logprobs(engine):
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "lp"}],
            "max_tokens": 4, "temperature": 0.0,
            "logprobs": True, "top_logprobs": 1})
        assert r.status == 200
        content = (await r.json())["choices"][0]["logprobs"]["content"]
        assert len(content) == 4
        for entry in content:
            assert entry["logprob"] <= 0.0
            assert isinstance(entry["token"], str)
            assert entry["top_logprobs"][0]["logprob"] == entry["logprob"]
        # without the flag the field is null
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "lp"}],
            "max_tokens": 2, "temperature": 0.0})
        assert (await r.json())["choices"][0]["logprobs"] is None
    _with_client(engine, body)


def test_chat_logprobs_stream(engine):
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "lp"}],
            "max_tokens": 3, "temperature": 0.0,
            "stream": True, "logprobs": True})
        assert r.status == 200
        text = await r.text()
        got = []
        for line in text.splitlines():
            if line.startswith("data: ") and line != "data: [DONE]":
                chunk = json.loads(line[6:])
                for c in chunk.get("choices", []):
                    if c.get("logprobs"):
                        got.extend(c["logprobs"]["content"])
        assert len(got) == 3
        assert all(e["logprob"] <= 0.0 for e in got)
    _with_client(engine, body)


def test_completions_logprobs(engine):
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "legacy lp",
            "max_tokens": 4, "temperature": 0.0, "logprobs": 1})
        assert r.status == 200
        lp = (await r.json())["choices"][0]["logprobs"]
        assert len(lp["tokens"]) == 4 and len(lp["token_logprobs"]) == 4
        assert all(v <= 0.0 for v in lp["token_logprobs"])
        assert len(lp["top_logprobs"]) == 4
        # logprobs=0: token logprobs, no alternatives
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "legacy lp",
            "max_tokens": 2, "temperature": 0.0, "logprobs": 0})
        lp = (await r.json())["choices"][0]["logprobs"]
        assert len(lp["token_logprobs"]) == 2
        assert lp["top_logprobs"] is None
    _with_client(engine, body)


def test_greedy_logprob_is_max(engine):
    """Greedy decode: every chosen token is the argmax, so its logprob
    must be the distribution's max — cross-checked against a direct
    forward pass on the same prompt."""
    import numpy as np
    import jax.numpy as jnp
    from production_stack_tpu.models import llama

    eng = engine.engine
    seq_ids = eng.tokenizer.encode("probe")
    from production_stack_tpu.engine.scheduler import SamplingOptions
    opts = SamplingOptions(temperature=0.0, max_tokens=3, ignore_eos=True)
    sid = eng.add_request(list(seq_ids), opts)
    done = False
    while not done:
        for out in eng.step():
            if out.seq_id == sid and out.finished:
                done = True
    seq = eng.seqs[sid]
    assert len(seq.output_logprobs) == 3
    # recompute: forward over prompt + outputs, compare chosen logprob
    cfg = eng.model_cfg
    toks = list(seq_ids) + seq.output_tokens
    logits = llama.forward_train(eng.runner.params, cfg,
                                 jnp.asarray([toks]))
    full = np.asarray(logits)
    for i, (tok_id, lp) in enumerate(zip(seq.output_tokens,
                                         seq.output_logprobs)):
        pos = len(seq_ids) - 1 + i
        row = full[0, pos]
        expect = row[tok_id] - (np.log(np.exp(row - row.max()).sum())
                                + row.max())
        assert abs(lp - expect) < 5e-2, (i, lp, expect)
        assert tok_id == int(row.argmax())


def test_stop_token_excluded_from_logprobs(engine):
    """A token that stopped the sequence is excluded from content, so it
    gets no logprobs entry (OpenAI alignment)."""
    async def body(client):
        # learn the greedy first token for this prompt
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "stop probe",
            "max_tokens": 1, "temperature": 0.0, "logprobs": 0})
        first = (await r.json())["choices"][0]["logprobs"]["tokens"]
        assert len(first) == 1
        # re-run with that token as a stop token: finishes immediately
        # with reason=stop and an EMPTY logprobs block
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "stop probe"}],
            "max_tokens": 4, "temperature": 0.0, "logprobs": True,
            "stop_token_ids": []})
        base = (await r.json())["choices"][0]
        tok_ids = engine.engine.seqs[
            list(engine.engine.seqs)[-1]].output_tokens
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "stop probe"}],
            "max_tokens": 4, "temperature": 0.0, "logprobs": True,
            "stop_token_ids": [tok_ids[-1]]})
        data = (await r.json())["choices"][0]
        assert data["finish_reason"] == "stop"
        stopped = data["logprobs"]["content"]
        # generation halts at the FIRST occurrence of the stop token;
        # that token is absent from logprobs, earlier ones keep entries
        expected = tok_ids.index(tok_ids[-1])
        assert len(stopped) == expected
        assert stopped == base["logprobs"]["content"][:expected]
    _with_client(engine, body)


def test_n_greater_than_one(engine):
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "pick"}],
            "max_tokens": 4, "temperature": 0.0, "n": 3})
        assert r.status == 200
        data = await r.json()
        choices = data["choices"]
        assert [c["index"] for c in choices] == [0, 1, 2]
        # greedy: all n identical by definition
        assert len({c["message"]["content"] for c in choices}) == 1
        assert data["usage"]["completion_tokens"] == 12

        # streaming: chunks tagged with their choice index
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "pick", "max_tokens": 3,
            "temperature": 0.0, "n": 2, "stream": True})
        text = await r.text()
        seen = set()
        for line in text.splitlines():
            if line.startswith("data: ") and line != "data: [DONE]":
                for c in json.loads(line[6:]).get("choices", []):
                    seen.add(c["index"])
        assert seen == {0, 1}
    _with_client(engine, body)


def test_seeded_sampling_reproducible(engine):
    """Same seed + same prompt + temperature>0 => identical output,
    regardless of what else ran in between; different seed differs."""
    async def ask(client, seed):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "seeded run",
            "max_tokens": 12, "temperature": 1.0, "seed": seed})
        assert r.status == 200
        return (await r.json())["choices"][0]["text"]

    async def body(client):
        a1 = await ask(client, 7)
        # interleave unrelated traffic so the engine key stream advances
        await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "noise", "max_tokens": 5,
            "temperature": 1.0})
        a2 = await ask(client, 7)
        b = await ask(client, 1234)
        assert a1 == a2, "same seed must reproduce"
        assert a1 != b, "different seeds should diverge"
    _with_client(engine, body)


def test_completions_echo_with_prompt_logprobs(engine):
    """Legacy echo=true: the prompt text prefixes the completion, and
    with logprobs the prompt's teacher-forced logprobs are prepended
    (first token null, OpenAI format)."""
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "echo me", "max_tokens": 3,
            "temperature": 0.0, "echo": True, "logprobs": 0})
        assert r.status == 200
        choice = (await r.json())["choices"][0]
        assert choice["text"].startswith("echo me")
        lp = choice["logprobs"]
        n_prompt = len((await (await client.post(
            "/tokenize", json={"prompt": "echo me"})).json())["tokens"])
        assert len(lp["tokens"]) == n_prompt + 3
        assert lp["token_logprobs"][0] is None          # position 0
        assert all(v is not None and v <= 0.0
                   for v in lp["token_logprobs"][1:])
        # echo without logprobs: just the text prefix
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": "echo me", "max_tokens": 2,
            "temperature": 0.0, "echo": True})
        choice = (await r.json())["choices"][0]
        assert choice["text"].startswith("echo me")
        assert choice["logprobs"] is None
    _with_client(engine, body)


def test_completions_batched_prompts(engine):
    """Legacy batched prompts: choices indexed prompt-major x n."""
    async def body(client):
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": ["first", "second"],
            "max_tokens": 3, "temperature": 0.0, "n": 2})
        assert r.status == 200
        data = await r.json()
        assert [c["index"] for c in data["choices"]] == [0, 1, 2, 3]
        assert data["usage"]["completion_tokens"] == 12
        # greedy: both samples of one prompt agree; prompts may differ
        assert data["choices"][0]["text"] == data["choices"][1]["text"]
        assert data["choices"][2]["text"] == data["choices"][3]["text"]

        # echo with a batch: each choice carries its OWN prompt
        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": ["alpha", "bravo"],
            "max_tokens": 2, "temperature": 0.0, "echo": True})
        choices = (await r.json())["choices"]
        assert choices[0]["text"].startswith("alpha")
        assert choices[1]["text"].startswith("bravo")

        r = await client.post("/v1/completions", json={
            "model": "debug-tiny", "prompt": ["x"] * 100, "n": 2,
            "max_tokens": 1})
        assert r.status == 400   # len(prompt) * n cap
        # empty prompts (top-level or nested) are rejected, not hung
        for bad in ([], [[]], [[1, 2], []]):
            r = await client.post("/v1/completions", json={
                "model": "debug-tiny", "prompt": bad, "max_tokens": 1})
            assert r.status == 400, bad
    _with_client(engine, body)


def test_api_key_enforcement(engine):
    """ENGINE_API_KEY semantics (VERDICT r3 missing #1): /v1/* without
    the Bearer -> 401; with it -> 200; /health, /metrics, /version stay
    open for probes and the Prometheus scraper."""
    async def runner():
        app = build_app(engine, api_key="sekrit")
        async with TestClient(TestServer(app)) as client:
            # no credentials -> 401 on the OpenAI surface
            r = await client.post("/v1/chat/completions", json={
                "model": "debug-tiny",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 2})
            assert r.status == 401
            body = await r.json()
            assert body["error"]["code"] == 401
            r = await client.get("/v1/models")
            assert r.status == 401
            # wrong key -> 401
            r = await client.get(
                "/v1/models",
                headers={"Authorization": "Bearer wrong"})
            assert r.status == 401
            # right key -> 200, end to end through generation
            hdr = {"Authorization": "Bearer sekrit"}
            r = await client.get("/v1/models", headers=hdr)
            assert r.status == 200
            r = await client.post("/v1/chat/completions", headers=hdr,
                                  json={
                                      "model": "debug-tiny",
                                      "messages": [{"role": "user",
                                                    "content": "hi"}],
                                      "max_tokens": 2,
                                      "temperature": 0.0})
            assert r.status == 200
            assert (await r.json())["choices"][0]["message"]["content"]
            # probe/scrape endpoints exempt (K8s probes and Prometheus
            # carry no credentials)
            for path in ("/health", "/metrics", "/version"):
                r = await client.get(path)
                assert r.status == 200, path
    asyncio.run(runner())


def test_api_key_from_env(engine, monkeypatch):
    """build_app with api_key=None reads ENGINE_API_KEY (the chart's
    secret delivery path)."""
    async def runner():
        app = build_app(engine)
        async with TestClient(TestServer(app)) as client:
            r = await client.get("/v1/models")
            assert r.status == 401
            r = await client.get(
                "/v1/models",
                headers={"Authorization": "Bearer env-key"})
            assert r.status == 200
    monkeypatch.setenv("ENGINE_API_KEY", "env-key")
    asyncio.run(runner())


def test_client_disconnect_aborts_generation(engine):
    """A client that vanishes mid-stream (or while queued) must have
    its engine-side generation aborted — the server runs with
    aiohttp handler_cancellation, so the disconnect cancels the
    handler, closing the stream generator whose finally aborts the
    sequence (async_engine.stream). Without it, orphaned requests
    keep the engine busy for clients that left long ago."""
    async def body(client):
        resp = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": "never stops"}],
            "max_tokens": 120, "temperature": 0.0, "stream": True,
            "ignore_eos": True})
        assert resp.status == 200
        await resp.content.readany()   # generation is live
        sched = engine.engine.scheduler
        assert sched.num_running + sched.num_waiting >= 1
        resp.close()                   # hard disconnect, no drain
        for _ in range(200):
            if sched.num_running == 0 and sched.num_waiting == 0:
                break
            await asyncio.sleep(0.05)
        assert sched.num_running == 0 and sched.num_waiting == 0
    _with_client(engine, body)


def test_disconnect_while_queued_aborts(engine):
    """A request whose client disconnects while it is still WAITING
    (both slots busy, no token ever written to it — so the SSE
    write-failure path can never fire) must still be aborted via
    handler cancellation."""
    async def body(client):
        sched = engine.engine.scheduler
        busy = [await client.post("/v1/chat/completions", json={
            "model": "debug-tiny",
            "messages": [{"role": "user", "content": f"hold {i}"}],
            "max_tokens": 200, "temperature": 0.0, "stream": True,
            "ignore_eos": True}) for i in range(2)]   # fill both slots
        for r in busy:
            await r.content.readany()
        # SSE responses are prepared lazily (headers ride with the
        # first payload so pre-stream sheds stay structured 503/504):
        # post() for a queued request does not return until admission,
        # so drive it as a task and cancel it while still WAITING
        queued_task = asyncio.ensure_future(client.post(
            "/v1/chat/completions", json={
                "model": "debug-tiny",
                "messages": [{"role": "user",
                              "content": "stuck in queue"}],
                "max_tokens": 5, "temperature": 0.0, "stream": True}))
        for _ in range(100):
            if sched.num_waiting >= 1:
                break
            await asyncio.sleep(0.05)
        assert sched.num_waiting >= 1
        queued_task.cancel()           # leave while still queued
        try:
            await queued_task
        except asyncio.CancelledError:
            pass
        for _ in range(200):
            if sched.num_waiting == 0:
                break
            await asyncio.sleep(0.05)
        assert sched.num_waiting == 0
        for r in busy:                 # cleanup: abort the fillers
            r.close()
        for _ in range(200):
            if sched.num_running == 0:
                break
            await asyncio.sleep(0.05)
        assert sched.num_running == 0
    _with_client(engine, body)


def test_loop_responsive_while_engine_lock_held(engine):
    """Admission waits on the engine lock (held across whole steps,
    including multi-second lazy compiles) must NOT block the event
    loop: while a chat request is stuck behind the lock, /health still
    answers (r5 soak regression: connect-refused storms during
    compile bursts because submit() took the lock on the loop)."""
    import threading
    import time as _time

    async def body(client):
        release = threading.Event()
        held = threading.Event()

        def hold_lock():
            with engine.engine._lock:
                held.set()
                release.wait(timeout=10)

        t = threading.Thread(target=hold_lock, daemon=True)
        t.start()
        assert held.wait(timeout=5)
        try:
            chat = asyncio.create_task(client.post(
                "/v1/chat/completions", json={
                    "model": "debug-tiny",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 3, "temperature": 0.0}))
            await asyncio.sleep(0.2)     # chat is now parked on the lock
            t0 = _time.monotonic()
            r = await client.get("/health")
            dt = _time.monotonic() - t0
            assert r.status == 200
            assert dt < 1.0, f"/health took {dt:.2f}s with lock held"
        finally:
            release.set()
        r = await chat
        assert r.status == 200           # and the parked request finishes
    _with_client(engine, body)


def test_submit_rejects_duplicate_seq_id(engine):
    """A caller-supplied seq_id that collides with a live stream must be
    rejected, not silently replace the live stream's result queue (the
    error-path pop would then tear down the wrong registration)."""
    async def body():
        original = asyncio.Queue()
        engine._queues["dup-seq"] = original
        try:
            from production_stack_tpu.engine.scheduler import SamplingOptions
            with pytest.raises(ValueError, match="live stream"):
                await engine.submit(
                    [1, 2, 3], SamplingOptions(max_tokens=2),
                    seq_id="dup-seq")
            assert engine._queues["dup-seq"] is original
        finally:
            engine._queues.pop("dup-seq", None)
    asyncio.run(body())


def test_stream_disconnect_abort_survives_shutdown_pool():
    """Disconnect cleanup races server shutdown: once stop() has shut
    the lock pool down, the finally-block abort must fall back to an
    inline call instead of losing the abort to a RuntimeError."""
    from concurrent.futures import ThreadPoolExecutor

    from production_stack_tpu.engine.async_engine import AsyncLLMEngine

    eng = AsyncLLMEngine.__new__(AsyncLLMEngine)   # only what stream() touches
    aborted = []

    class _MiniEngine:
        def abort(self, seq_id):
            aborted.append(seq_id)

    eng.engine = _MiniEngine()
    eng._queues = {}
    eng._lock_pool = ThreadPoolExecutor(max_workers=1)
    eng._lock_pool.shutdown()

    async def fake_submit(prompt_tokens, options, model=None,
                          deadline=None):
        q = asyncio.Queue()
        eng._queues["s1"] = q
        return "s1", q

    eng.submit = fake_submit

    async def body():
        gen = eng.stream([1], None)
        first = asyncio.ensure_future(gen.__anext__())
        await asyncio.sleep(0.05)      # parked on q.get(): a live stream
        first.cancel()                 # the client vanishes
        with pytest.raises(asyncio.CancelledError):
            await first
        await gen.aclose()
    asyncio.run(body())
    assert aborted == ["s1"]           # abort landed inline, not lost


def test_submit_cancel_abort_survives_shutdown_pool():
    """The same race inside submit(): the client cancels while
    add_request is parked on the engine lock, then stop() shuts the
    pool down before the call settles — the cleanup callback must abort
    inline instead of losing the abort to the pool's RuntimeError."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    aborted = []
    release = threading.Event()

    class _MiniEngine:
        def add_request(self, *a, **k):
            release.wait(5)            # the slow engine-lock hold

        def abort(self, seq_id):
            aborted.append(seq_id)

    eng = AsyncLLMEngine.__new__(AsyncLLMEngine)
    eng.engine = _MiniEngine()
    eng._queues = {}
    eng._lock_pool = ThreadPoolExecutor(max_workers=1)

    async def body():
        task = asyncio.ensure_future(eng.submit(
            [1], SamplingOptions(max_tokens=2), seq_id="s2"))
        await asyncio.sleep(0.05)      # parked inside the executor call
        task.cancel()                  # the client vanishes
        with pytest.raises(asyncio.CancelledError):
            await task
        eng._lock_pool.shutdown(wait=False)  # server shutdown begins...
        release.set()                  # ...then add_request settles on
        await asyncio.sleep(0.2)       # the gone pool; callback runs
    asyncio.run(body())
    assert aborted == ["s2"]           # abort landed inline, not lost


def test_engine_trace_spans_and_propagation(engine):
    """Engine-side tracing (tracing.py): an inbound traceparent is
    continued (same trace id on x-trace-id and in /debug/traces, spans
    parented on the router's span id), and the recorded span set
    attributes the request's time — preprocess / queue_wait / prefill /
    decode phases plus the tokenize event."""
    from production_stack_tpu import tracing

    async def body(client):
        tid = tracing.new_trace_id()
        sid = tracing.new_span_id()
        r = await client.post(
            "/v1/chat/completions",
            json={"model": "debug-tiny", "max_tokens": 4,
                  "messages": [{"role": "user", "content": "trace me"}]},
            headers={"traceparent": tracing.format_traceparent(tid, sid)})
        assert r.status == 200
        assert r.headers["x-trace-id"] == tid
        r = await client.get("/debug/traces", params={"trace_id": tid})
        rows = (await r.json())["traces"]
        assert len(rows) == 1
        t = rows[0]
        assert t["parent_id"] == sid
        phases = {s["name"] for s in t["spans"] if s["kind"] == "phase"}
        assert {"preprocess", "queue_wait", "prefill", "decode",
                "postprocess"} <= phases
        events = {s["name"] for s in t["spans"] if s["kind"] == "event"}
        assert "tokenize" in events
        assert t["attrs"]["output_tokens"] == 4
        # phases cover the request: unattributed stays a sliver
        assert t["unattributed_ms"] <= 0.25 * t["duration_ms"] + 5.0
        # the engine-side phase histograms advanced too (/metrics)
        r = await client.get("/metrics")
        text = await r.text()
        assert "tpu:engine_phase_seconds_bucket" in text
        assert 'phase="decode"' in text

    _with_client(engine, body)


def test_engine_shed_trace_sealed(engine):
    """A 400 (no sequence ever created) still seals a trace — the ring
    must never hold half-open traces for refused requests."""
    async def body(client):
        r = await client.post(
            "/v1/chat/completions",
            json={"model": "debug-tiny", "n": 0,
                  "messages": [{"role": "user", "content": "x"}]})
        assert r.status == 400
        tid = r.headers["x-trace-id"]
        r = await client.get("/debug/traces", params={"trace_id": tid})
        rows = (await r.json())["traces"]
        assert len(rows) == 1
        assert rows[0]["status"] == "http_400"
        assert [s["name"] for s in rows[0]["spans"]
                if s["kind"] == "phase"] == ["preprocess"]

    _with_client(engine, body)


def test_debug_traces_requires_api_key(engine):
    """/debug/traces carries per-request data, so unlike the probe
    endpoints it sits BEHIND ENGINE_API_KEY enforcement."""
    async def runner():
        app = build_app(engine, api_key="sekrit")
        async with TestClient(TestServer(app)) as client:
            r = await client.get("/debug/traces")
            assert r.status == 401
            r = await client.get("/health")      # probes stay open
            assert r.status == 200
            r = await client.get(
                "/debug/traces",
                headers={"Authorization": "Bearer sekrit"})
            assert r.status == 200
    asyncio.run(runner())


def test_admin_lora_load_and_evict(engine):
    """Runtime adapter admin surface: load 200 + catalog, idempotent
    reload, failed load = structured 503 + Retry-After (shed, never a
    breaker signal), evict 200 then 404."""
    async def body(client):
        r = await client.post("/admin/lora/load",
                              json={"name": "ad-srv", "src": "random:5"})
        assert r.status == 200, await r.text()
        data = await r.json()
        assert data["loaded"] is True and "ad-srv" in data["models"]
        r = await client.get("/v1/models")
        assert "ad-srv" in {c["id"] for c in (await r.json())["data"]}
        r = await client.post("/admin/lora/load",
                              json={"name": "ad-srv", "src": "random:5"})
        assert (await r.json())["loaded"] is False
        r = await client.post("/admin/lora/load",
                              json={"name": "ad-bad",
                                    "src": "/no/such/adapter.npz"})
        assert r.status == 503
        assert "Retry-After" in r.headers
        assert (await r.json())["error"]["type"] == "overloaded_error"
        r = await client.post("/admin/lora/load", json={"name": "x"})
        assert r.status == 400
        r = await client.post("/admin/lora/evict", json={"name": "ad-srv"})
        assert r.status == 200, await r.text()
        r = await client.post("/admin/lora/evict", json={"name": "ad-srv"})
        assert r.status == 404
    _with_client(engine, body)
