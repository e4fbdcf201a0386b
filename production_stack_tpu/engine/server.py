"""OpenAI-compatible HTTP server for the TPU engine (aiohttp).

Surface parity with what the reference's router expects from each engine
pod (reference: src/vllm_router/service_discovery.py:131-155 queries
/v1/models; stats/engine_stats.py scrapes /metrics; helm probes hit
/health): /v1/completions, /v1/chat/completions (streaming SSE and
non-streaming), /v1/models, /health, /metrics, /version, /tokenize,
/detokenize.

Built on aiohttp (no FastAPI dependency): handlers parse with pydantic
models from protocol.py and stream via chunked responses.
"""

import argparse
import asyncio
import json
import math
import tempfile
import time
from contextlib import aclosing
from typing import List, Optional

from aiohttp import web
from pydantic import ValidationError

from production_stack_tpu import protocol as proto
from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import (AdmissionRejected,
                                                DeadlineExceeded)
from production_stack_tpu.engine.scheduler import SamplingOptions
from production_stack_tpu.tracing import (TraceRecorder,
                                          debug_traces_handler)
from production_stack_tpu.utils import (init_logger, place_compile_cache,
                                        set_ulimit)
from production_stack_tpu.version import __version__

logger = init_logger(__name__)

ENGINE_KEY = web.AppKey("engine", AsyncLLMEngine)
TRACER_KEY = web.AppKey("tracer", TraceRecorder)
# held while POST /debug/profile captures: one capture at a time
PROFILE_LOCK_KEY = web.AppKey("profile_lock", asyncio.Lock)
# the longest capture POST /debug/profile takes (a trace of a busy chip
# grows by tens of MB a second)
PROFILE_MAX_S = 60.0

# paths whose requests get an engine-side trace (tracing.py): the
# generation endpoints the router's span chain continues into
TRACED_PATHS = frozenset({"/v1/chat/completions", "/v1/completions"})

# relative per-request budget in milliseconds; the router injects its
# own --request-timeout here when the client sent none (docs/router.md
# "Overload protection")
DEADLINE_HEADER = "x-request-deadline-ms"
# marks an engine 504 as "the CLIENT's deadline elapsed" — the router
# relays it without a breaker signal or failover (retrying a request
# whose budget is spent helps nobody)
DEADLINE_MARKER = "x-deadline-expired"


def _stash_timing(request: web.Request, out) -> None:
    """Capture a terminal StepOutput's phase timeline for the trace
    middleware (one attribute write on the stream path; the LAST
    finishing choice wins for n>1 requests)."""
    if out.finished and out.timing is not None:
        request["seq_timing"] = out.timing


def _seal_engine_trace(tracer: TraceRecorder, trace, request: web.Request,
                       status: str) -> None:
    """Build the engine-side span set from what the handlers stashed:

    - ``preprocess``: HTTP entry -> engine arrival (parse, chat
      template, tokenize, guided compile, KV-tier prefetch) — tokenize
      and kv_prefetch ride inside it as EVENT spans so the phase sum
      never double-counts;
    - ``queue_wait`` / ``prefill`` / ``decode``: from the terminal
      StepOutput's timing stamps (engine._seq_timing);
    - ``postprocess``: last engine output -> response done.

    Requests that never produced a sequence (400s, sheds, deadline
    504s) get a single ``preprocess`` phase covering their whole life.

    The waits a request sits through INSIDE those phases ride along as
    EVENT spans (scheduler.RequestWaits): ``lock_wait`` and
    ``sched_wait`` in ``queue_wait``, ``prefill_wait`` in ``prefill``,
    ``first_token_emit`` and ``first_token_write`` in ``decode``,
    ``emit_lag`` in ``postprocess``.

    XLA compiles that overlapped this request's life are attached as
    ``xla_compile`` EVENT spans (engine/efficiency.py keeps the bounded
    compile-event ring): a compile stalls every in-flight request, so a
    request whose tail latency was a compile must say so in
    ``/debug/traces`` instead of showing unattributed decode time.
    """
    now = time.monotonic()
    engine = request.app.get(ENGINE_KEY)
    if engine is not None:
        for (start, dur, kind, window, kv, batch) in \
                engine.engine.eff.compile_events_between(trace.t0, now):
            trace.add_event("xla_compile", start, dur,
                            attrs={"kind": kind, "window": window,
                                   "kv_bucket": kv, "batch": batch})
    timing = request.get("seq_timing")
    tok_s = request.get("trace_tokenize_s")
    if timing is not None:
        arrival = timing["arrival"]
        admit = timing["admit"]
        end = timing["end"]
        trace.add_phase("preprocess", trace.t0, arrival)
        if admit is None:
            # never admitted (WAITING-dropped: deadline / queue-delay
            # shed): the whole engine-side life is queue wait — it must
            # NOT render as prefill, or a shed storm's traces point the
            # operator at the wrong phase
            trace.add_phase("queue_wait", arrival, end)
        else:
            # queue_wait_s is cumulative across admissions (preemption
            # re-queues); render it anchored at arrival so the span
            # layout stays readable while the durations stay honest
            qw = timing.get("queue_wait_s") or max(0.0, admit - arrival)
            trace.add_span("queue_wait", arrival, qw, "phase")
            first = timing["first_token"] if timing["first_token"] \
                is not None else end
            trace.add_phase("prefill", admit, max(admit, first))
            trace.add_phase("decode", max(admit, first), end)
        trace.add_phase("postprocess", end, now)
        if timing.get("kv_prefetch_wait_s"):
            trace.add_event(
                "kv_prefetch", None, timing["kv_prefetch_wait_s"],
                attrs={"cached_tokens": timing.get("kv_cached_tokens",
                                                   0)})
        _add_wait_events(trace, timing)
        trace.attrs["prompt_tokens"] = timing.get("prompt_tokens")
        trace.attrs["output_tokens"] = timing.get("output_tokens")
    else:
        trace.add_phase("preprocess", trace.t0, now)
    if tok_s:
        trace.add_event("tokenize", None, tok_s)
    tracer.finish(trace, status)


def _add_wait_events(trace, timing: dict) -> None:
    """The request's waits as event spans, each from the stamps that
    exist (a request dropped while waiting has no prefill, one served
    without AsyncLLMEngine no emit)."""
    waits = timing.get("waits")
    if waits is None:
        return

    def event(name, start, end, **attrs):
        if start is not None and end is not None:
            trace.add_event(name, start, max(0.0, end - start),
                            attrs=attrs or None)

    event("lock_wait", timing["arrival"], waits.locked)
    event("sched_wait", waits.locked, waits.first_look,
          refused_passes=waits.refused_passes,
          reason=waits.refused_reason)
    event("prefill_wait", timing["admit"], waits.prefill_call,
          chunks=waits.prefill_chunks)
    event("first_token_emit", timing["first_token"], waits.first_emit)
    # a streamed response: from its queue to the socket; emit_lag is
    # the last token's whole way out, to its write where there was one
    event("first_token_write", waits.first_emit, waits.first_write)
    event("emit_lag", timing["end"], waits.last_write or waits.last_emit)


def _trace_middleware(tracer: TraceRecorder):
    @web.middleware
    async def record_trace(request: web.Request, handler):
        if request.path not in TRACED_PATHS:
            return await handler(request)
        trace = tracer.begin(request.headers.get("traceparent"),
                             name=request.path)
        request["trace"] = trace
        try:
            resp = await handler(request)
        except BaseException:
            _seal_engine_trace(tracer, trace, request, "exception")
            raise
        if not resp.prepared:
            resp.headers["x-trace-id"] = trace.trace_id
        status = request.get("trace_status") or (
            "ok" if resp.status < 400 else f"http_{resp.status}")
        _seal_engine_trace(tracer, trace, request, status)
        return resp
    return record_trace


def _error(status: int, message: str,
           err_type: str = "invalid_request_error") -> web.Response:
    body = proto.ErrorResponse(
        error=proto.ErrorInfo(message=message, type=err_type,
                              code=status))
    return web.json_response(body.model_dump(), status=status)


class _QueueDelayShed(Exception):
    """The scheduler shed this request for exceeding max_queue_delay_ms
    while WAITING (finish_reason "queue_delay")."""


def _deadline_from(request: web.Request):
    """Parse x-request-deadline-ms into an absolute monotonic deadline.
    Returns (deadline_or_None, error_response_or_None)."""
    raw = request.headers.get(DEADLINE_HEADER)
    if raw is None:
        return None, None
    try:
        ms = float(raw)
    except ValueError:
        return None, _error(400, f"{DEADLINE_HEADER} must be a number "
                                 f"of milliseconds (got {raw!r})")
    if not math.isfinite(ms):
        return None, _error(400, f"{DEADLINE_HEADER} must be finite")
    if ms <= 0:
        # already expired on arrival: answer 504 before any engine work
        return None, _deadline_error()
    return time.monotonic() + ms / 1e3, None


def _deadline_error() -> web.Response:
    resp = _error(504, "request deadline expired while waiting for "
                       "admission (x-request-deadline-ms elapsed before "
                       "the engine could start it)",
                  err_type="timeout_error")
    resp.headers[DEADLINE_MARKER] = "1"
    return resp


def _shed_error(engine: AsyncLLMEngine,
                message: Optional[str] = None) -> web.Response:
    """Structured 503 + Retry-After: the overload shed the router's
    resilience layer recognizes as shed-not-sick."""
    retry_s = max(1.0, engine.engine.estimated_queue_delay_s())
    resp = _error(503, message or "engine overloaded: request shed; "
                                  "retry after the indicated delay",
                  err_type="overloaded_error")
    resp.headers["Retry-After"] = str(int(math.ceil(retry_s)))
    return resp


def _load_headers(engine: AsyncLLMEngine) -> dict:
    """The per-response load report (cheap, lock-free): every reply
    carries the engine's pressure signals so callers (and the router)
    see load without an extra round trip."""
    report = engine.engine.load_report()
    return {
        "x-engine-queue-depth": str(report["queue_depth"]),
        "x-engine-running": str(report["running"]),
        "x-engine-free-kv-blocks": str(report["free_kv_blocks"]),
        "x-engine-est-queue-delay-ms": str(report["est_queue_delay_ms"]),
    }


def _check_overload_finish(out) -> None:
    """Translate a WAITING-dropped sequence's terminal StepOutput
    (engine.step's expire pass: no token, no text) into the structured
    error the client contract promises."""
    if not out.finished or out.new_token is not None or out.text_delta:
        return
    if out.finish_reason == "deadline":
        raise DeadlineExceeded()
    if out.finish_reason == "queue_delay":
        raise _QueueDelayShed()


# request key: the outputs that carry a RequestWaits (first-token and
# terminal ones) whose payload _sse_stream has not written yet
UNWRITTEN = "sse_unwritten"


async def _guarded_payloads(request, merged, lead_payloads, chunk_for):
    """Shared streaming shape for the chat/completions SSE paths: pull
    the FIRST engine output off ``merged`` before emitting the
    ``lead_payloads`` (role/echo chunks), so an admission shed or a
    WAITING-deadline drop surfaces pre-yield and _sse_stream can still
    answer a structured 503/504 instead of a truncated stream; then
    relay ``chunk_for(i, out)`` payloads. A drop arriving AFTER the
    response started (another choice's shed, or a preempted sequence's
    deadline) is NOT an error: the transport is healthy, so that choice
    simply terminates with its finish_reason chunk ("deadline" /
    "queue_delay") while its siblings stream on to [DONE].

    Building a payload is booked on the loop timeline
    (``totals.loop.serialize_s``)."""
    acct = request.app[ENGINE_KEY].engine.eff.loop

    def serialized(i, out):
        with acct.serializing():
            payload = chunk_for(i, out)
        if out.waits is not None:
            request.setdefault(UNWRITTEN, []).append(out)
        return payload

    try:
        head = await merged.__anext__()
    except StopAsyncIteration:
        head = None
    if head is not None:
        _check_overload_finish(head[1])
    for payload in lead_payloads:
        yield payload
    if head is not None:
        payload = serialized(*head)
        if payload is not None:
            yield payload
        async for i, out in merged:
            payload = serialized(i, out)
            if payload is not None:
                yield payload


def _logit_bias(req) -> Optional[dict]:
    """OpenAI logit_bias {token-id-string: bias} -> {int: float},
    bounded by the device-side slot width (sampler.LOGIT_BIAS_K)."""
    raw = getattr(req, "logit_bias", None)
    if not raw:
        return None
    # OpenAI documents a 300-entry cap; the device slot width
    # (sampler.LOGIT_BIAS_K) covers it, so the API-parity bound is the
    # binding one here
    if len(raw) > 300:
        raise ValueError(
            f"logit_bias supports at most 300 entries (got {len(raw)})")
    try:
        return {int(k): float(v) for k, v in raw.items()}
    except (TypeError, ValueError):
        raise ValueError("logit_bias keys must be token ids and values "
                         "numbers")


def _top_logprobs(req) -> int:
    """How many per-token alternatives the request wants: chat's
    top_logprobs, or legacy completions' integer logprobs=N (OpenAI
    caps both at 20, rejects negatives, and requires chat's
    logprobs=true alongside top_logprobs)."""
    tl = getattr(req, "top_logprobs", None)
    if tl is not None and not 0 <= tl <= 20:
        raise ValueError(
            f"top_logprobs must be in [0, 20] (got {tl})")
    if tl and not getattr(req, "logprobs", None):
        raise ValueError(
            "top_logprobs requires logprobs to be set to true")
    tl = tl or 0
    if not tl:
        lp = getattr(req, "logprobs", None)
        if isinstance(lp, int) and not isinstance(lp, bool) and lp > 0:
            tl = lp
    if tl > 20:
        raise ValueError(f"top_logprobs supports at most 20 (got {tl})")
    return int(tl)


def _sampling_options(req, max_tokens: Optional[int]) -> SamplingOptions:
    stop = req.stop if isinstance(req.stop, list) else (
        [req.stop] if req.stop else [])
    return SamplingOptions(
        temperature=req.temperature,
        top_p=req.top_p,
        top_k=req.top_k,
        max_tokens=max_tokens if max_tokens is not None else 128,
        stop=stop,
        stop_token_ids=req.stop_token_ids or [],
        ignore_eos=req.ignore_eos,
        seed=req.seed,
        guided_regex=_guided_pattern(req),
        presence_penalty=req.presence_penalty,
        frequency_penalty=req.frequency_penalty,
        repetition_penalty=req.repetition_penalty,
        min_p=req.min_p,
        min_tokens=req.min_tokens,
        priority=req.priority,
        logit_bias=_logit_bias(req),
        top_logprobs=_top_logprobs(req),
    )


async def _precompile_guided(engine, options) -> None:
    """Compile the request's grammar (LRU-cached) BEFORE streaming, in
    a thread: a bad pattern becomes a 400 here instead of a 500
    mid-stream, and a first-time compile (a full-vocab token lift,
    seconds on large vocabularies) never blocks the event loop."""
    if not options.guided_regex:
        return
    from production_stack_tpu.engine import guided
    await asyncio.get_running_loop().run_in_executor(
        None, guided.compile_grammar, options.guided_regex,
        engine.tokenizer)


def _guided_pattern(req) -> Optional[str]:
    """vLLM-style guided decoding knobs -> one regex (or None)."""
    if getattr(req, "guided_regex", None):
        return req.guided_regex
    if getattr(req, "guided_choice", None):
        from production_stack_tpu.engine import guided
        return guided.choice_regex(req.guided_choice)
    if getattr(req, "guided_json", None) is not None:
        from production_stack_tpu.engine import guided
        # schema errors surface as RegexError -> 400 at validation
        return guided.json_schema_regex(req.guided_json)
    rf = getattr(req, "response_format", None)
    if rf:
        kind = rf.get("type")
        if kind == "json_schema":
            from production_stack_tpu.engine import guided
            spec = rf.get("json_schema") or {}
            schema = spec.get("schema", spec)   # OpenAI nests .schema
            return guided.json_schema_regex(schema)
        if kind == "json_object":
            raise ValueError(
                "response_format json_object (free-form JSON) is not "
                "supported: a DFA cannot express unbounded-depth JSON. "
                "Use response_format json_schema or guided_json with a "
                "schema.")
        if kind not in (None, "text"):
            raise ValueError(f"unsupported response_format type {kind!r}")
    return None


def _choice_options(options, i: int):
    """Per-choice SamplingOptions: a seeded request varies the seed by
    choice index, otherwise n identical seeds would return n identical
    completions (noise depends only on (seed, position))."""
    if i == 0 or options.seed is None:
        return options
    import dataclasses
    return dataclasses.replace(options, seed=options.seed + i)


async def _gather_cancelling(coros):
    """gather() where one failure cancels the siblings so they free
    their engine slots instead of generating into a discarded response
    (asyncio.TaskGroup semantics, but available on Python 3.10)."""
    tasks = [asyncio.ensure_future(c) for c in coros]
    try:
        return await asyncio.gather(*tasks)
    except BaseException:
        for t in tasks:
            t.cancel()
        # wait for the cancellations to land (TaskGroup semantics):
        # siblings must have freed their engine slots before the error
        # response goes out, and their exceptions must be retrieved
        await asyncio.gather(*tasks, return_exceptions=True)
        raise


def _choice_jobs(prompts, options, n):
    """The OpenAI choice grid: every (prompt, sample) pair gets a
    choice index prompt_idx * n + sample_idx. Returns
    [(index, prompt_ids, per-choice options)]."""
    return [(p * n + j, pids, _choice_options(options, j))
            for p, pids in enumerate(prompts) for j in range(n)]


def _merged_streams(engine, jobs, model, deadline=None):
    """Run the jobs [(choice_index, prompt_ids, options)] concurrently
    and yield (choice_index, StepOutput) in completion order — the
    OpenAI n>1 / batched-prompt streaming shape (each chunk carries its
    choice index). A pump failure propagates to the consumer (and
    cancels its siblings via the generator's finally); closing the
    generator cancels all pumps and frees their slots."""
    async def gen():
        q: asyncio.Queue = asyncio.Queue()

        async def pump(idx, pids, opts):
            try:
                async with aclosing(engine.stream(
                        list(pids), opts, model=model,
                        deadline=deadline)) as it:
                    async for out in it:
                        await q.put((idx, out))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                await q.put((idx, e))
                return
            await q.put((idx, None))

        tasks = [asyncio.ensure_future(pump(*job)) for job in jobs]
        try:
            done = 0
            while done < len(jobs):
                i, out = await q.get()
                if out is None:
                    done += 1
                    continue
                if isinstance(out, BaseException):
                    raise out
                yield i, out
        finally:
            for t in tasks:
                t.cancel()
    return gen()


async def _sse_stream(request: web.Request, gen) -> web.StreamResponse:
    """Relay an SSE generator, preparing the response lazily: the 200
    and its headers go out with the FIRST payload, so an admission shed
    or a deadline expiry that surfaces before any byte is written
    becomes a clean structured 503/504 instead of a truncated stream.
    (Raised after bytes have been relayed, the same failures can only
    truncate — the connection is dropped.)

    Every payload's write is booked on the loop timeline
    (``totals.loop.write_s``, ``payloads``), and its return stamps
    ``first_write`` / ``last_write`` of the requests whose first or
    last token the payload carried (scheduler.RequestWaits)."""
    engine = request.app[ENGINE_KEY]
    acct = engine.engine.eff.loop
    resp: Optional[web.StreamResponse] = None

    async def ensure_prepared() -> web.StreamResponse:
        nonlocal resp
        if resp is None:
            headers = {"Content-Type": "text/event-stream",
                       "Cache-Control": "no-cache",
                       "X-Accel-Buffering": "no",
                       **_load_headers(engine)}
            trace = request.get("trace")
            if trace is not None:
                # streams take their trace id at prepare time (the
                # middleware can no longer add headers then)
                headers["x-trace-id"] = trace.trace_id
            resp = web.StreamResponse(status=200, headers=headers)
            await resp.prepare(request)
        return resp

    try:
        async for payload in gen:
            await ensure_prepared()
            with acct.writing() as write:
                await resp.write(f"data: {payload}\n\n".encode())
            for out in request.pop(UNWRITTEN, ()):
                if out.waits.first_write is None:
                    out.waits.first_write = write.t1
                if out.finished:
                    out.waits.last_write = write.t1
        await ensure_prepared()
        with acct.writing():
            await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
    except (ConnectionResetError, ConnectionError):
        # client went away mid-stream; generator cleanup aborts the request
        request["trace_status"] = "client_disconnect"
        await gen.aclose()
        if resp is None:
            resp = web.Response(status=500)     # never reaches the client
    except AdmissionRejected as e:
        await gen.aclose()
        if resp is None:
            return _shed_error(engine, str(e))
        resp.force_close()
    except DeadlineExceeded:
        await gen.aclose()
        if resp is None:
            return _deadline_error()
        resp.force_close()
    except _QueueDelayShed:
        await gen.aclose()
        if resp is None:
            return _shed_error(engine)
        resp.force_close()
    return resp


# ---------------------------------------------------------------- handlers

def _lp_skip(out) -> bool:
    """OpenAI alignment: a token that STOPPED the sequence (EOS / stop
    token / stop string) is excluded from the returned text, so it gets
    no logprobs entry either. (Earlier tokens of a multi-token stop
    string were already emitted before the match — a known, bounded
    deviation.) Length-finished tokens are real content and stay."""
    return out.finished and out.finish_reason == "stop"


def _chat_lp_entry(tok, token_id: int, logprob, want_top: bool,
                   alts=None):
    """One chat-logprobs content entry. `alts` [(token_id, logprob)]
    are the device-computed top-K alternatives of the same raw model
    distribution the chosen logprob reports (engine/runner.py); paths
    that don't produce them (e.g. speculative windows never run with
    alternatives requested) fall back to the chosen entry. Token
    text/bytes come from the tokenizer's own token representation so
    multi-byte-split pieces stay distinct."""
    text, raw = tok.id_to_token(token_id)
    lp = logprob if logprob is not None else 0.0
    entry = proto.ChatLogprobToken(token=text, logprob=lp, bytes=raw)
    if want_top:
        if alts:
            tops = []
            for tid, tlp in alts:
                ttext, traw = tok.id_to_token(int(tid))
                tops.append(proto.ChatLogprobTop(
                    token=ttext, logprob=float(tlp), bytes=traw))
            entry.top_logprobs = tops
        else:
            entry.top_logprobs = [proto.ChatLogprobTop(
                token=text, logprob=lp, bytes=raw)]
    return entry


def _completion_logprobs(tok, token_ids, logprobs, want_top: bool,
                         alts_list=None) -> "proto.CompletionLogprobs":
    """Legacy completions logprobs block. alts_list (parallel to
    token_ids) holds [(id, logprob)] device-computed top-N
    alternatives; entries without them fall back to the chosen
    token."""
    texts = [tok.id_to_token(t)[0] for t in token_ids]
    lps = [lp if lp is not None else 0.0 for lp in logprobs]
    top = None
    if want_top:
        top = []
        for i, (text, lp) in enumerate(zip(texts, lps)):
            alts = alts_list[i] if alts_list else None
            if alts:
                top.append({tok.id_to_token(int(t))[0]: float(l)
                            for t, l in alts})
            else:
                top.append({text: lp})
    return proto.CompletionLogprobs(tokens=texts, token_logprobs=lps,
                                    top_logprobs=top)


async def _prompt_echo_blocks(engine, tok, prompts, req):
    """[(prompt_text, CompletionLogprobs-or-None)] per prompt for
    legacy echo=true: the prompt text prefixes the completion; with
    logprobs requested, teacher-forced prompt logprobs for ALL prompts
    are computed in ONE padded batched device call (position 0 reports
    null, OpenAI format). Each block is shared by its n choices."""
    import numpy as np
    texts = [tok.decode(p) for p in prompts]
    if req.logprobs is None:
        return [(t, None) for t in texts]
    runner = engine.engine.runner
    T = max(len(p) for p in prompts)
    arr = np.zeros((len(prompts), T), np.int32)
    for r, p in enumerate(prompts):
        arr[r, :len(p)] = p

    def compute():
        # rows are padded to a shared bucket: slice each to its len-1
        out = np.asarray(runner.prompt_logprobs(arr))
        return [out[r, :len(p) - 1].tolist()
                for r, p in enumerate(prompts)]

    all_lps = await asyncio.get_running_loop().run_in_executor(
        None, compute)
    blocks = []
    for text, pids, lps in zip(texts, prompts, all_lps):
        pieces = [tok.id_to_token(t)[0] for t in pids]
        token_lps = [None] + [float(v) for v in lps]
        top = None
        if req.logprobs > 0:
            top = [None] + [{pc: lp} for pc, lp in
                            zip(pieces[1:], token_lps[1:])]
        blocks.append((text, proto.CompletionLogprobs(
            tokens=pieces, token_logprobs=token_lps, top_logprobs=top)))
    return blocks


def _merge_echo_lp(echo_lp, lp_block):
    """Prepend the prompt's logprobs block to a completion's."""
    if echo_lp is None:
        return lp_block
    merged = proto.CompletionLogprobs(
        tokens=echo_lp.tokens + lp_block.tokens,
        token_logprobs=echo_lp.token_logprobs + lp_block.token_logprobs,
        top_logprobs=(echo_lp.top_logprobs + lp_block.top_logprobs
                      if echo_lp.top_logprobs is not None
                      and lp_block.top_logprobs is not None else None))
    return merged


async def chat_completions(request: web.Request) -> web.StreamResponse:
    engine = request.app[ENGINE_KEY]
    try:
        req = proto.ChatCompletionRequest(**await request.json())
    except (ValidationError, json.JSONDecodeError) as e:
        return _error(400, f"invalid request: {e}")
    if not 1 <= req.n <= 128:
        return _error(400, "n must be between 1 and 128")
    try:
        engine.engine.resolve_model(req.model or None)
    except ValueError as e:
        return _error(404, str(e))
    deadline, bad = _deadline_from(request)
    if bad is not None:
        return bad
    if engine.engine.admission_full():
        # cheap-shed fast path: refuse before tokenization/template
        # work — under a shed storm the 503s must cost near-nothing
        return _shed_error(engine)

    tok = engine.tokenizer
    t_tok = time.monotonic()
    prompt = tok.apply_chat_template(
        [m.model_dump() for m in req.messages])
    prompt_ids = tok.encode(prompt)
    request["trace_tokenize_s"] = time.monotonic() - t_tok
    if len(prompt_ids) >= engine.engine.cfg.max_model_len:
        return _error(400, f"prompt has {len(prompt_ids)} tokens, which "
                           f"exceeds max_model_len "
                           f"{engine.engine.cfg.max_model_len}")
    max_tokens = req.max_completion_tokens or req.max_tokens
    try:
        options = _sampling_options(req, max_tokens)
        await _precompile_guided(engine, options)
    except ValueError as e:
        return _error(400, f"invalid guided decoding constraint: {e}")
    rid = proto._gen_id("chatcmpl")

    if req.stream:
        include_usage = bool(req.stream_options
                             and req.stream_options.include_usage)

        async def gen():
            # OpenAI chunk shape: with include_usage every chunk carries
            # "usage": null until the final usage chunk; without it the
            # field is omitted entirely
            exclude = None if include_usage else {"usage"}
            num_tokens = 0

            def chunk_for(i, out):
                nonlocal num_tokens
                _stash_timing(request, out)
                if out.new_token is not None:
                    num_tokens += 1
                lp_block = None
                if (req.logprobs and out.new_token is not None
                        and not _lp_skip(out)):
                    lp_block = proto.ChatLogprobs(content=[
                        _chat_lp_entry(tok, out.new_token,
                                       out.logprob,
                                       bool(req.top_logprobs),
                                       out.top_alts)])
                # a token can produce no text yet (partial UTF-8 in
                # the detokenizer) — its logprob entry must still
                # be delivered
                if out.text_delta or out.finished or lp_block:
                    chunk = proto.ChatCompletionChunk(
                        id=rid, model=req.model,
                        choices=[proto.ChatCompletionChunkChoice(
                            index=i,
                            delta=proto.DeltaMessage(
                                content=out.text_delta or None),
                            finish_reason=out.finish_reason if out.finished
                            else None,
                            logprobs=lp_block)])
                    return chunk.model_dump_json(exclude=exclude)
                return None

            role_chunks = [
                proto.ChatCompletionChunk(
                    id=rid, model=req.model,
                    choices=[proto.ChatCompletionChunkChoice(
                        index=i,
                        delta=proto.DeltaMessage(role="assistant",
                                                 content=""))]
                ).model_dump_json(exclude=exclude)
                for i in range(req.n)]
            # aclosing => a dropped consumer deterministically runs
            # every stream's cleanup (slot aborts), not at GC's leisure
            async with aclosing(_merged_streams(
                    engine, _choice_jobs([prompt_ids], options, req.n),
                    req.model or None, deadline)) as it:
                async for payload in _guarded_payloads(
                        request, it, role_chunks, chunk_for):
                    yield payload
            if include_usage:
                # OpenAI semantics: one final chunk, empty choices, usage
                tail = proto.ChatCompletionChunk(
                    id=rid, model=req.model, choices=[],
                    usage=proto.UsageInfo(
                        prompt_tokens=len(prompt_ids),
                        completion_tokens=num_tokens,
                        total_tokens=len(prompt_ids) + num_tokens))
                yield tail.model_dump_json()
        return await _sse_stream(request, gen())

    async def collect_one(i: int):
        parts: List[str] = []
        lp_entries: List = []
        finish_reason = None
        tokens = 0
        async with aclosing(engine.stream(
                list(prompt_ids), _choice_options(options, i),
                model=req.model or None, deadline=deadline)) as it:
            async for out in it:
                _check_overload_finish(out)
                _stash_timing(request, out)
                parts.append(out.text_delta)
                if out.new_token is not None:
                    tokens += 1
                    if req.logprobs and not _lp_skip(out):
                        lp_entries.append(_chat_lp_entry(
                            tok, out.new_token, out.logprob,
                            bool(req.top_logprobs), out.top_alts))
                if out.finished:
                    finish_reason = out.finish_reason
        choice = proto.ChatCompletionChoice(
            index=i,
            message=proto.ChatChoiceMessage(content="".join(parts)),
            finish_reason=finish_reason,
            logprobs=(proto.ChatLogprobs(content=lp_entries)
                      if req.logprobs else None))
        return choice, tokens

    try:
        results = await _gather_cancelling(
            [collect_one(i) for i in range(req.n)])
    except AdmissionRejected as e:
        return _shed_error(engine, str(e))
    except DeadlineExceeded:
        return _deadline_error()
    except _QueueDelayShed:
        return _shed_error(engine)
    num_tokens = sum(t for _, t in results)
    resp = proto.ChatCompletionResponse(
        id=rid, model=req.model,
        choices=[c for c, _ in results],
        usage=proto.UsageInfo(
            prompt_tokens=len(prompt_ids),
            completion_tokens=num_tokens,
            total_tokens=len(prompt_ids) + num_tokens))
    return web.json_response(resp.model_dump())


async def completions(request: web.Request) -> web.StreamResponse:
    engine = request.app[ENGINE_KEY]
    try:
        req = proto.CompletionRequest(**await request.json())
    except (ValidationError, json.JSONDecodeError) as e:
        return _error(400, f"invalid request: {e}")
    if not 1 <= req.n <= 128:
        return _error(400, "n must be between 1 and 128")
    try:
        engine.engine.resolve_model(req.model or None)
    except ValueError as e:
        return _error(404, str(e))
    deadline, bad = _deadline_from(request)
    if bad is not None:
        return bad
    if engine.engine.admission_full():
        return _shed_error(engine)

    tok = engine.tokenizer
    prompt = req.prompt
    # cap the choice grid BEFORE tokenizing a potentially huge batch on
    # the event loop ([int] prompts are one prompt, not a batch)
    if (isinstance(prompt, list) and prompt
            and isinstance(prompt[0], (str, list))
            and len(prompt) * req.n > 128):
        return _error(400, "len(prompt) * n must be <= 128")
    try:
        t_tok = time.monotonic()
        prompts = _as_token_lists(engine, prompt)
        request["trace_tokenize_s"] = time.monotonic() - t_tok
    except ValueError as e:
        return _error(400, str(e))
    if not prompts or any(not p for p in prompts):
        return _error(400, "prompt must not be (or contain) empty input")
    if len(prompts) * req.n > 128:
        return _error(400, "len(prompt) * n must be <= 128")
    for pids in prompts:
        if len(pids) >= engine.engine.cfg.max_model_len:
            return _error(400, f"prompt has {len(pids)} tokens, which "
                               f"exceeds max_model_len "
                               f"{engine.engine.cfg.max_model_len}")
    try:
        options = _sampling_options(req, req.max_tokens)
        await _precompile_guided(engine, options)
    except ValueError as e:
        return _error(400, f"invalid guided decoding constraint: {e}")
    rid = proto._gen_id("cmpl")

    # echo blocks are computed BEFORE any response starts: first-time
    # compiles and failures become a clean 500/400 here instead of a
    # truncated SSE stream (same policy as _precompile_guided)
    echo_blocks = []
    if req.echo:
        echo_blocks = await _prompt_echo_blocks(engine, tok, prompts, req)

    if req.stream:
        include_usage = bool(req.stream_options
                             and req.stream_options.include_usage)

        async def gen():
            exclude = None if include_usage else {"usage"}
            num_tokens = 0

            def chunk_for(i, out):
                nonlocal num_tokens
                _stash_timing(request, out)
                if out.new_token is not None:
                    num_tokens += 1
                lp_block = None
                if (req.logprobs is not None
                        and out.new_token is not None
                        and not _lp_skip(out)):
                    lp_block = _completion_logprobs(
                        tok, [out.new_token], [out.logprob],
                        req.logprobs > 0, [out.top_alts])
                if out.text_delta or out.finished or lp_block:
                    chunk = proto.CompletionChunk(
                        id=rid, model=req.model,
                        choices=[proto.CompletionChunkChoice(
                            index=i,
                            text=out.text_delta,
                            finish_reason=out.finish_reason if out.finished
                            else None,
                            logprobs=lp_block)])
                    return chunk.model_dump_json(exclude=exclude)
                return None

            echo_chunks = [
                proto.CompletionChunk(
                    id=rid, model=req.model,
                    choices=[proto.CompletionChunkChoice(
                        index=p * req.n + j, text=echo_text,
                        logprobs=echo_lp)]
                ).model_dump_json(exclude=exclude)
                for p, (echo_text, echo_lp) in enumerate(echo_blocks)
                for j in range(req.n)]
            async with aclosing(_merged_streams(
                    engine, _choice_jobs(prompts, options, req.n),
                    req.model or None, deadline)) as it:
                async for payload in _guarded_payloads(
                        request, it, echo_chunks, chunk_for):
                    yield payload
            if include_usage:
                n_prompt = sum(len(p) for p in prompts)
                tail = proto.CompletionChunk(
                    id=rid, model=req.model, choices=[],
                    usage=proto.UsageInfo(
                        prompt_tokens=n_prompt,
                        completion_tokens=num_tokens,
                        total_tokens=n_prompt + num_tokens))
                yield tail.model_dump_json()
        return await _sse_stream(request, gen())

    async def collect_one(idx: int, pids, opts):
        parts: List[str] = []
        out_ids: List[int] = []
        out_lps: List = []
        out_alts: List = []
        tokens = 0
        finish_reason = None
        async with aclosing(engine.stream(
                list(pids), opts, model=req.model or None,
                deadline=deadline)) as it:
            async for out in it:
                _check_overload_finish(out)
                _stash_timing(request, out)
                parts.append(out.text_delta)
                if out.new_token is not None:
                    tokens += 1
                    if not _lp_skip(out):
                        out_ids.append(out.new_token)
                        out_lps.append(out.logprob)
                        out_alts.append(out.top_alts)
                if out.finished:
                    finish_reason = out.finish_reason
        lp_block = (_completion_logprobs(tok, out_ids, out_lps,
                                         req.logprobs > 0, out_alts)
                    if req.logprobs is not None else None)
        echo_text = ""
        if req.echo:
            echo_text, echo_lp = echo_blocks[idx // req.n]
            lp_block = (_merge_echo_lp(echo_lp, lp_block)
                        if lp_block is not None else None)
        choice = proto.CompletionChoice(
            index=idx,
            text=echo_text + "".join(parts),
            finish_reason=finish_reason,
            logprobs=lp_block)
        return choice, tokens

    try:
        results = await _gather_cancelling(
            [collect_one(*job)
             for job in _choice_jobs(prompts, options, req.n)])
    except AdmissionRejected as e:
        return _shed_error(engine, str(e))
    except DeadlineExceeded:
        return _deadline_error()
    except _QueueDelayShed:
        return _shed_error(engine)
    num_tokens = sum(t for _, t in results)
    n_prompt = sum(len(p) for p in prompts)
    resp = proto.CompletionResponse(
        id=rid, model=req.model,
        choices=[c for c, _ in results],
        usage=proto.UsageInfo(
            prompt_tokens=n_prompt, completion_tokens=num_tokens,
            total_tokens=n_prompt + num_tokens))
    return web.json_response(resp.model_dump())


def _as_token_lists(engine, raw, tok=None) -> List[List[int]]:
    """OpenAI-style `input`/`prompt`: str | [str] | [int] | [[int]].
    `tok` picks the tokenizer: completions pass the chat tokenizer
    (default); pooling endpoints pass engine.embedding_tokenizer (the
    encoder checkpoint's own when one is configured)."""
    tok = tok or engine.tokenizer
    if isinstance(raw, str):
        return [tok.encode(raw)]
    if not isinstance(raw, list):
        raise ValueError("input must be str, [str], [int], or [[int]]")
    if raw and all(isinstance(x, int) and not isinstance(x, bool)
                   for x in raw):
        return [list(raw)]
    out: List[List[int]] = []
    for item in raw:
        if isinstance(item, str):
            out.append(tok.encode(item))
        elif isinstance(item, list) and all(
                isinstance(x, int) and not isinstance(x, bool)
                for x in item):
            out.append(list(item))
        else:
            raise ValueError("input must be str, [str], [int], or [[int]]")
    return out


def _check_pool_model(engine, model) -> Optional[web.Response]:
    """Pooling endpoints serve only the BASE model: embeddings pool raw
    hidden states, which the LoRA path does not color (adapters would
    need an adapter-aware encode). Unknown models 404, adapters 400."""
    try:
        adapter_id = engine.engine.resolve_model(model or None)
    except ValueError as e:
        return _error(404, str(e))
    if adapter_id != 0:
        return _error(400, f"model {model!r} is a LoRA adapter; "
                           f"embeddings/rerank/score serve the base "
                           f"model only")
    return None


async def _pooled(request: web.Request, token_lists: List[List[int]]):
    """Run the embedding batch off the event loop (device-blocking)."""
    engine = request.app[ENGINE_KEY]
    max_len = engine.engine.max_embed_len
    for toks in token_lists:
        if not toks:
            raise ValueError("empty input")
        if len(toks) > max_len:
            raise ValueError(f"input has {len(toks)} tokens, which "
                             f"exceeds the embedding length cap "
                             f"{max_len}")
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(
        None, engine.engine.embed_tokens, token_lists)


async def embeddings(request: web.Request) -> web.Response:
    """OpenAI-compatible /v1/embeddings (reference surface:
    src/vllm_router/routers/main_router.py:42-160 proxies this path to
    the engine). With --embedding-model, vectors come from a real
    bidirectional encoder (models/encoder.py); otherwise they are
    mean-pooled hidden states of the causal chat model — an API-shape
    approximation whose quality is unvalidated, declared to clients via
    the non-standard "embedding_source" field (docs/router.md)."""
    engine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
        bad = _check_pool_model(engine, body.get("model"))
        if bad is not None:
            return bad
        token_lists = _as_token_lists(
            engine, body.get("input"),
            tok=engine.engine.embedding_tokenizer)
        if not token_lists:
            return _error(400, "missing 'input'")
        vecs = await _pooled(request, token_lists)
    except (ValueError, TypeError, json.JSONDecodeError) as e:
        return _error(400, f"invalid request: {e}")
    n_tokens = sum(len(t) for t in token_lists)
    return web.json_response({
        "object": "list",
        "model": body.get("model") or engine.model_name,
        "embedding_source": engine.engine.embedding_source,
        "data": [{"object": "embedding", "index": i,
                  "embedding": vec.tolist()}
                 for i, vec in enumerate(vecs)],
        "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
    })


def _cosine(a, b):
    import numpy as np
    num = float(np.dot(a, b))
    den = float(np.linalg.norm(a) * np.linalg.norm(b)) or 1e-12
    return num / den


async def rerank(request: web.Request) -> web.Response:
    """/v1/rerank: order documents by embedding similarity to the query
    (bi-encoder scoring over the served model's hidden states)."""
    engine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
        bad = _check_pool_model(engine, body.get("model"))
        if bad is not None:
            return bad
        query = body.get("query")
        docs = body.get("documents")
        if not isinstance(query, str) or not isinstance(docs, list) \
                or not docs or not all(isinstance(d, str) for d in docs):
            return _error(400, "need 'query' (str) and 'documents' "
                               "(non-empty list of str)")
        token_lists = _as_token_lists(
            engine, [query] + list(docs),
            tok=engine.engine.embedding_tokenizer)
        vecs = await _pooled(request, token_lists)
    except (ValueError, TypeError, json.JSONDecodeError) as e:
        return _error(400, f"invalid request: {e}")
    q, dvecs = vecs[0], vecs[1:]
    scored = sorted(
        ({"index": i, "document": {"text": d},
          "relevance_score": _cosine(q, v)}
         for i, (d, v) in enumerate(zip(docs, dvecs))),
        key=lambda r: r["relevance_score"], reverse=True)
    top_n = body.get("top_n")
    if isinstance(top_n, int) and top_n > 0:
        scored = scored[:top_n]
    return web.json_response({
        "id": proto._gen_id("rerank"),
        "model": body.get("model") or engine.model_name,
        "results": scored,
        "usage": {"total_tokens": sum(len(t) for t in token_lists)},
    })


async def score(request: web.Request) -> web.Response:
    """/v1/score: similarity of text_1 against each text_2 entry."""
    engine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
        bad = _check_pool_model(engine, body.get("model"))
        if bad is not None:
            return bad
        t1, t2 = body.get("text_1"), body.get("text_2")
        if isinstance(t2, str):
            texts = [t2]
        elif isinstance(t2, list) and t2 and all(isinstance(x, str)
                                                 for x in t2):
            texts = list(t2)
        else:
            texts = None
        if not isinstance(t1, str) or texts is None:
            return _error(400, "need 'text_1' (str) and 'text_2' "
                               "(str or non-empty list of str)")
        token_lists = _as_token_lists(
            engine, [t1] + texts,
            tok=engine.engine.embedding_tokenizer)
        vecs = await _pooled(request, token_lists)
    except (ValueError, TypeError, json.JSONDecodeError) as e:
        return _error(400, f"invalid request: {e}")
    base = vecs[0]
    return web.json_response({
        "id": proto._gen_id("score"),
        "model": body.get("model") or engine.model_name,
        "data": [{"index": i, "score": _cosine(base, v)}
                 for i, v in enumerate(vecs[1:])],
        "usage": {"total_tokens": sum(len(t) for t in token_lists)},
    })


async def list_models(request: web.Request) -> web.Response:
    engine = request.app[ENGINE_KEY]
    served = engine.engine.served_models
    base = served[0]
    cards = proto.ModelList(data=[
        proto.ModelCard(id=name, root=base if i else None,
                        parent=base if i else None)
        for i, name in enumerate(served)])
    return web.json_response(cards.model_dump())


async def health(request: web.Request) -> web.Response:
    return web.json_response({"status": "ok"})


async def load(request: web.Request) -> web.Response:
    """Cheap load report (queue depth, running seqs, free KV blocks,
    estimated queue delay, advertised capacity) — lock-free, so it
    answers even while the engine lock is held across a compile. The
    same numbers ride on every reply as x-engine-* headers and on
    /metrics as tpu: gauges."""
    engine = request.app[ENGINE_KEY]
    return web.json_response(engine.engine.load_report())


async def version(request: web.Request) -> web.Response:
    return web.json_response({"version": __version__})


async def debug_perf(request: web.Request) -> web.Response:
    """``GET /debug/perf``: the engine-efficiency ring — recent
    window-level real/pad/dead breakdowns, the step timeline's recent
    steps (seconds per phase), the loop timeline's recent lag samples
    (``loop``), recent XLA compile events (each build by its parts),
    the ``startup`` block (the start by its marks, the builds before
    and after ``serving``),
    cumulative totals + rates, the KV block pool's fragmentation
    census, and the ``device`` block (platform, device kind and count,
    bytes in use per device, the attention path of every compiled
    executable — LLMEngine.device_report). Aggregate-only data, but
    served under the same auth posture as /debug/traces (the /debug
    namespace is operator surface, not probe surface). Query param ``limit=N`` bounds the
    rings returned (default 50)."""
    engine = request.app[ENGINE_KEY]
    eng = engine.engine
    try:
        limit = max(1, int(request.query.get("limit", "50")))
    except ValueError:
        limit = 50
    return web.json_response({
        "device": eng.device_report(),
        "startup": eng.eff.startup_report(),
        "totals": eng.eff.report(),
        "rates": eng.eff.rates(),
        "windows": eng.eff.recent_windows(limit),
        "steps": eng.eff.recent_steps(limit),
        "loop": eng.eff.loop.recent(limit),
        "compiles": eng.eff.recent_compiles(limit),
        "kv_pool": eng.block_mgr.frag_report(),
    })


async def debug_profile(request: web.Request) -> web.Response:
    """``POST /debug/profile {"seconds": n}``: hold ``jax.profiler``
    for ``n`` seconds (default 3, at most PROFILE_MAX_S) and answer
    with the directory the capture was written to (under the process's
    temporary directory; open it with TensorBoard's profile plugin or
    ``jax.profiler.ProfileData``). The capture holds the device's
    executables by name (``jit_decode_window``, ``jit_prefill_chunk``),
    their ``jax.named_scope`` paths, and the step timeline's phases as
    ``pstpu.*`` intervals on the host plane. 409 while another capture
    runs. Operator surface: same auth posture as the rest of /debug."""
    try:
        body = await request.json()
    except ValueError:
        body = {}
    try:
        seconds = float(body.get("seconds", 3.0))
    except (AttributeError, TypeError, ValueError):
        return _error(400, 'body must be {"seconds": <number>}')
    if not 0.0 < seconds <= PROFILE_MAX_S:
        return _error(400, f"seconds must be in (0, {PROFILE_MAX_S:g}]")
    lock = request.app[PROFILE_LOCK_KEY]
    if lock.locked():
        return _error(409, "a profile capture is already running")
    async with lock:
        import jax
        out_dir = tempfile.mkdtemp(prefix="pstpu-profile-")
        try:
            jax.profiler.start_trace(out_dir)
        except RuntimeError as e:
            # the profiler is held from elsewhere in this process (a
            # client of jax.profiler.start_server, a launcher's hook)
            return _error(409, f"the profiler is busy: {e}")
        started_unix = time.time()
        try:
            await asyncio.sleep(seconds)
        finally:
            await asyncio.to_thread(jax.profiler.stop_trace)
    return web.json_response({"dir": out_dir, "seconds": seconds,
                              "started_unix": round(started_unix, 4)})


async def metrics(request: web.Request) -> web.Response:
    engine = request.app[ENGINE_KEY]
    return web.Response(body=engine.engine.render_metrics(),
                        content_type="text/plain")


async def admin_kvplane_migrate_out(request: web.Request) -> web.Response:
    """kvplane planner entry point: evict victim sequences to the KV
    tier store and free their blocks. The victims' chunks are published
    before preemption, so a re-admission here (or a warm on the
    destination replica) injects instead of recomputing — a miss at
    worst, never corruption. Body: {"max_seqs": n, "target_blocks": n}."""
    engine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
    except Exception:
        body = {}
    max_seqs = int(body.get("max_seqs", 2))
    target_blocks = int(body.get("target_blocks", 0))
    # migrate_out takes the engine lock and then flushes the KV writer
    # (blocking I/O) — keep it off the event loop
    result = await asyncio.to_thread(
        engine.engine.migrate_out, max_seqs=max_seqs,
        target_blocks=target_blocks)
    status = 409 if "error" in result else 200
    return web.json_response(result, status=status)


async def admin_kvplane_warm(request: web.Request) -> web.Response:
    """kvplane planner destination side: pull the named chunk keys
    through the tier stack so the fastest tier holds them before the
    migrated traffic lands. Body: {"keys": ["<hex>", ...]}."""
    engine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
    except Exception:
        body = {}
    keys = body.get("keys") or []
    if not isinstance(keys, list):
        return _error(400, "keys must be a list of hex strings")
    result = await asyncio.to_thread(engine.engine.warm_chunks, keys)
    return web.json_response(result)


async def admin_lora_load(request: web.Request) -> web.Response:
    """Load a LoRA adapter at runtime and start serving it as its own
    model id. Body: {"name": "sql-adapter", "src": "random:7"|"/path.npz"}.

    Failure semantics are the r9 shed!=sick contract at the adapter
    stage: a failed load (bad source, OOM during restack) answers a
    structured 503 + Retry-After — "not now", NEVER a breaker signal —
    because the engine itself is healthy and serving its other models.
    The router's resilience layer already classifies exactly this shape
    as shed. Idempotent re-loads answer 200 with loaded=false."""
    engine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
    except Exception:
        body = {}
    name = str(body.get("name") or "").strip()
    src = str(body.get("src") or "").strip()
    if not name or not src:
        return _error(400, "adapter load needs {'name': ..., 'src': "
                           "'random:SEED' or '/path/to/adapter.npz'}")
    try:
        # restack + device swap holds the engine lock — keep it off
        # the event loop like every other lock-taking admin verb
        loaded = await asyncio.to_thread(
            engine.engine.load_adapter, name, src)
    except Exception as e:
        logger.warning("adapter load %s from %s failed: %s", name, src, e)
        resp = _error(503, f"adapter {name!r} failed to load: {e}; "
                           f"the engine is healthy and still serving "
                           f"its current models — retry later",
                      err_type="overloaded_error")
        resp.headers["Retry-After"] = "5"
        return resp
    return web.json_response({
        "loaded": loaded, "name": name,
        "models": list(engine.engine.served_models)})


async def admin_lora_evict(request: web.Request) -> web.Response:
    """Stop serving adapter ``name`` (body: {"name": ...}). Unknown
    adapter answers 404; the stacked row is tombstoned so in-flight
    requests on the adapter finish normally."""
    engine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
    except Exception:
        body = {}
    name = str(body.get("name") or "").strip()
    if not name:
        return _error(400, "adapter evict needs {'name': ...}")
    try:
        await asyncio.to_thread(engine.engine.evict_adapter, name)
    except KeyError as e:
        return _error(404, str(e.args[0]) if e.args else
                      f"adapter {name!r} is not loaded",
                      err_type="not_found_error")
    return web.json_response({
        "evicted": name, "models": list(engine.engine.served_models)})


async def tokenize(request: web.Request) -> web.Response:
    engine = request.app[ENGINE_KEY]
    body = await request.json()
    ids = engine.tokenizer.encode(body.get("prompt", ""))
    return web.json_response({"tokens": ids, "count": len(ids)})


async def detokenize(request: web.Request) -> web.Response:
    engine = request.app[ENGINE_KEY]
    body = await request.json()
    return web.json_response(
        {"prompt": engine.tokenizer.decode(body.get("tokens", []))})


# ---------------------------------------------------------------- app

# probe/scrape endpoints stay open when an API key is enforced: K8s
# probes and the Prometheus scraper carry no credentials (reference
# parity: the stack's engines enforce VLLM_API_KEY on the OpenAI surface
# while /health keeps answering probes,
# helm/templates/deployment-vllm-multi.yaml:143-150 + probe blocks)
AUTH_EXEMPT_PATHS = frozenset({"/health", "/metrics", "/version",
                               "/load"})
# NOTE: the /debug namespace (/debug/traces, /debug/perf,
# /debug/profile) is deliberately NOT exempt — /debug/traces carries
# per-request data
# (trace ids, timings, token counts) and /debug/perf shares the
# operator-surface posture; readers on a secured deployment present
# the engine key


def _auth_middleware(api_key: str):
    import secrets as _secrets

    # compare bytes: compare_digest on str raises TypeError for
    # non-ASCII input, which would turn a malformed credential into a
    # 500 instead of a 401
    expected = f"Bearer {api_key}".encode("utf-8", "surrogateescape")

    @web.middleware
    async def check_auth(request: web.Request, handler):
        if request.path in AUTH_EXEMPT_PATHS:
            return await handler(request)
        provided = request.headers.get("Authorization", "").encode(
            "utf-8", "surrogateescape")
        if not _secrets.compare_digest(provided, expected):
            return _error(401, "invalid or missing API key "
                               "(Authorization: Bearer ...)")
        return await handler(request)

    return check_auth


def build_app(engine: AsyncLLMEngine,
              api_key: Optional[str] = None,
              trace_ring_entries: int = 2048,
              trace_sample_rate: float = 1.0) -> web.Application:
    """api_key None reads ENGINE_API_KEY from the environment (the
    chart's secret delivery, helm/templates/deployment-engine.yaml);
    empty/unset disables enforcement."""
    import os
    if api_key is None:
        api_key = os.environ.get("ENGINE_API_KEY", "")
    tracer = TraceRecorder("engine", ring_entries=trace_ring_entries,
                           sample_rate=trace_sample_rate)
    middlewares = [_auth_middleware(api_key)] if api_key else []
    if middlewares:
        logger.info("API-key enforcement on: all endpoints require "
                    "Bearer auth except %s",
                    ", ".join(sorted(AUTH_EXEMPT_PATHS)))
    @web.middleware
    async def stamp_load_headers(request: web.Request, handler):
        # every reply carries the engine's pressure signals (SSE
        # streams get theirs at prepare time in _sse_stream; a
        # response already prepared by its handler cannot take more
        # headers)
        resp = await handler(request)
        if not resp.prepared:
            for k, v in _load_headers(engine).items():
                resp.headers[k] = v
        return resp
    middlewares = [*middlewares, stamp_load_headers,
                   _trace_middleware(tracer)]

    app = web.Application(client_max_size=32 * 1024 * 1024,
                          middlewares=middlewares)
    app[ENGINE_KEY] = engine
    app[TRACER_KEY] = tracer
    app[PROFILE_LOCK_KEY] = asyncio.Lock()
    app.router.add_get("/debug/traces",
                       debug_traces_handler(lambda: tracer))
    app.router.add_get("/debug/perf", debug_perf)
    app.router.add_post("/debug/profile", debug_profile)
    app.router.add_post("/v1/chat/completions", chat_completions)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/v1/embeddings", embeddings)
    app.router.add_post("/v1/rerank", rerank)
    app.router.add_post("/v2/rerank", rerank)
    app.router.add_post("/v1/score", score)
    app.router.add_get("/v1/models", list_models)
    app.router.add_get("/health", health)
    app.router.add_get("/load", load)
    app.router.add_get("/version", version)
    app.router.add_get("/metrics", metrics)
    app.router.add_post("/tokenize", tokenize)
    app.router.add_post("/detokenize", detokenize)
    app.router.add_post("/admin/kvplane/migrate_out",
                        admin_kvplane_migrate_out)
    app.router.add_post("/admin/kvplane/warm", admin_kvplane_warm)
    app.router.add_post("/admin/lora/load", admin_lora_load)
    app.router.add_post("/admin/lora/evict", admin_lora_evict)

    async def on_startup(app):
        # warmup (if any) was done before the loop started
        engine.start(asyncio.get_event_loop(), warmup=False)

    async def on_cleanup(app):
        engine.stop()
        # flush queued KV-tier saves + close tier sockets (pod rotation
        # must not drop the write-behind queue)
        engine.engine.close()

    app.on_startup.append(on_startup)
    app.on_cleanup.append(on_cleanup)
    return app


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser("pstpu-engine",
                                description="TPU-native OpenAI-compatible "
                                            "serving engine")
    p.add_argument("--model", default="debug-tiny")
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--checkpoint", default=None,
                   help="HF checkpoint dir (random weights if omitted)")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8100)
    p.add_argument("--max-model-len", type=int, default=2048)
    p.add_argument("--dtype", choices=["bfloat16", "float32"],
                   default="bfloat16")
    p.add_argument("--max-num-seqs", type=int, default=8)
    p.add_argument("--max-waiting-seqs", type=int, default=None,
                   help="bounded admission: shed (503 + Retry-After) "
                        "once this many sequences queue un-admitted, "
                        "instead of queuing forever (default: "
                        "unbounded)")
    p.add_argument("--max-queue-delay-ms", type=float, default=None,
                   help="shed (503 + Retry-After) a request still "
                        "waiting for admission after this long "
                        "(default: never)")
    p.add_argument("--prefill-chunk", type=int, default=512)
    p.add_argument("--decode-window", type=int, default=8,
                   help="tokens generated per fused device dispatch: "
                        "higher = throughput (one host sync per window), "
                        "lower = smoother streaming cadence")
    p.add_argument("--kv-len-buckets", default=None,
                   help="comma-separated attention-length buckets "
                        "(default: powers of two up to max-model-len)")
    p.add_argument("--no-window-adapt", action="store_true",
                   help="disable continuous batching across fused "
                        "windows: every decode dispatch computes "
                        "max-num-seqs x decode-window token positions "
                        "whatever the batch holds (the pre-r17 "
                        "behavior; the effwatch A/B control)")
    p.add_argument("--decode-batch-buckets", default=None,
                   help="comma-separated decode batch buckets the "
                        "adaptive dispatch may shrink to (default: "
                        "powers of two up to max-num-seqs); each "
                        "bucket is a warmed executable per window "
                        "bucket, so keep the set small")
    p.add_argument("--decode-window-buckets", default=None,
                   help="comma-separated decode window-length buckets "
                        "(default: powers of two up to decode-window)")
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--pipeline-parallel-size", type=int, default=1,
                   help="multi-slice DCN passthrough knob (must be 1; "
                        "see EngineConfig)")
    p.add_argument("--expert-parallel-size", type=int, default=1,
                   help="shard a MoE model's experts over the mesh's ep "
                        "axis (must divide num_experts; composes with "
                        "--tensor-parallel-size)")
    p.add_argument("--speculative-ngram-tokens", type=int, default=0,
                   help="n-gram (prompt-lookup) speculative decoding "
                        "draft length; eligible rows (greedy, unguided, "
                        "unshaped) emit up to N+1 verified tokens per "
                        "decode step (0 = off)")
    p.add_argument("--pipeline-depth", type=int, default=2,
                   help="decode windows queued on the device at once; "
                        "3 hides one more host round-trip behind "
                        "device work at the cost of admission latency")
    p.add_argument("--dp-gather-attention-ok", action="store_true",
                   help="acknowledge serving on a dp>1 mesh WITHOUT "
                        "the paged attention kernel (gathered-view "
                        "fallback, ~3x decode KV traffic); without "
                        "this flag such a mesh refuses to construct")
    p.add_argument("--quantization", choices=["int8"], default=None,
                   help="weight-only int8: halves decode weight-"
                        "streaming HBM traffic (norms/biases/router "
                        "stay in --dtype)")
    p.add_argument("--kv-cache-dtype", choices=["bfloat16", "float32",
                                                "int8"],
                   default="bfloat16",
                   help="KV cache precision; int8 stores per-(token, "
                        "head)-scaled int8 blocks — halves long-context "
                        "decode KV HBM traffic (models/kv.py)")
    p.add_argument("--moe-capacity-factor", type=float, default=None,
                   help="MoE prefill capacity factor (ops/moe.py): >= "
                        "num_experts/top_k disables token dropping at "
                        "dense-compute cost; default keeps the model "
                        "family value")
    p.add_argument("--embedding-model", default=None,
                   help="real embedding model for /v1/embeddings + "
                        "rerank/score (models/encoder.py): an encoder "
                        "preset name or a HF BertModel checkpoint dir. "
                        "Default: mean-pooled causal hidden states, "
                        "flagged embedding_source=causal-mean-pool")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-warmup", action="store_true")
    p.add_argument("--chat-template", default=None,
                   help="Jinja file overriding the tokenizer chat template")
    p.add_argument("--enable-prefix-caching", action="store_true",
                   help="retain finished sequences' full KV blocks in "
                        "the paged pool and attach them to matching "
                        "prompts by reference — zero-copy prefix hits "
                        "(the reference's --enable-prefix-caching)")
    p.add_argument("--kv-block-size", type=int, default=64,
                   help="paged-KV block size in tokens (models/kv.py)")
    p.add_argument("--kv-pool-tokens", type=int, default=None,
                   help="total KV pool capacity in tokens (default: "
                        "max-num-seqs * max-model-len worst case). A "
                        "smaller pool admits by LIVE context and "
                        "preempts under pressure — more concurrent "
                        "long-context slots in the same HBM")
    p.add_argument("--lora-adapters", default=None,
                   help="comma-separated name=source pairs; source is an "
                        ".npz adapter checkpoint (models/lora.py) or "
                        "random:SEED. Each adapter is served as its own "
                        "model id (reference: --enable-lora, "
                        "deployment-vllm-multi.yaml:65-67)")
    p.add_argument("--lora-rank", type=int, default=8)
    p.add_argument("--lora-alpha", type=float, default=16.0)
    p.add_argument("--lora-targets", default="q,v",
                   help="comma-separated projections to adapt "
                        "(q,k,v,o,gate,up,down)")
    p.add_argument("--hbm-peak-gbps", type=float, default=None,
                   help="per-chip HBM peak bandwidth the "
                        "tpu:engine_mbu_perc gauge normalizes effective "
                        "bytes/s against (GB/s). Default: looked up by "
                        "the device's kind (engine/efficiency.py; v5e "
                        "819); a kind the table does not know reports "
                        "no MBU")
    p.add_argument("--perf-ring-entries", type=int, default=256,
                   help="window-level efficiency breakdowns kept in "
                        "memory (bounded ring on GET /debug/perf)")
    p.add_argument("--trace-ring-entries", type=int, default=2048,
                   help="completed request traces kept in memory "
                        "(bounded ring served on GET /debug/traces)")
    p.add_argument("--trace-sample-rate", type=float, default=1.0,
                   help="fraction of DIRECT requests traced into the "
                        "ring; an inbound traceparent's sampled flag "
                        "(the router's decision) always wins")
    p.add_argument("--kv-transfer-config", default=None,
                   help="JSON dict enabling KV tiering, e.g. "
                        '\'{"kv_role": "kv_both", "local_cpu_gb": 4, '
                        '"remote_url": "tpukv://cache:8100"}\' '
                        "(the reference engine's --kv-transfer-config "
                        "equivalent; see kvcache/connector.py)")
    p.add_argument("--no-kvplane-defrag", action="store_true",
                   help="disable the between-windows free-list defrag "
                        "pass the engine runs after fragmented "
                        "allocation failures (docs/kv-tiering.md)")
    return p.parse_args(argv)


def main(argv=None) -> None:
    main_unix = time.time()     # the ``startup`` block's first mark
    args = parse_args(argv)
    logger.info("compile cache: %s", place_compile_cache())
    set_ulimit()
    kv_transfer = json.loads(args.kv_transfer_config) \
        if args.kv_transfer_config else None
    cfg = EngineConfig(
        model=args.model, tokenizer=args.tokenizer,
        chat_template=args.chat_template,
        checkpoint=args.checkpoint, max_model_len=args.max_model_len,
        dtype=args.dtype, kv_dtype=args.kv_cache_dtype,
        max_num_seqs=args.max_num_seqs, prefill_chunk=args.prefill_chunk,
        max_waiting_seqs=args.max_waiting_seqs,
        max_queue_delay_ms=args.max_queue_delay_ms,
        hbm_peak_gbps=args.hbm_peak_gbps,
        perf_ring_entries=args.perf_ring_entries,
        decode_window=args.decode_window,
        window_adapt=not args.no_window_adapt,
        decode_batch_buckets=tuple(
            int(x) for x in args.decode_batch_buckets.split(","))
        if args.decode_batch_buckets else (),
        decode_window_buckets=tuple(
            int(x) for x in args.decode_window_buckets.split(","))
        if args.decode_window_buckets else (),
        kv_len_buckets=tuple(int(x) for x in args.kv_len_buckets.split(","))
        if args.kv_len_buckets else (),
        enable_prefix_caching=args.enable_prefix_caching,
        kvplane_defrag=not args.no_kvplane_defrag,
        kv_block_size=args.kv_block_size,
        kv_pool_tokens=args.kv_pool_tokens,
        tensor_parallel_size=args.tensor_parallel_size,
        pipeline_parallel_size=args.pipeline_parallel_size,
        expert_parallel_size=args.expert_parallel_size,
        moe_capacity_factor=args.moe_capacity_factor,
        quantization=args.quantization,
        speculative_ngram_tokens=args.speculative_ngram_tokens,
        pipeline_depth=args.pipeline_depth,
        dp_gather_attention_ok=args.dp_gather_attention_ok,
        seed=args.seed,
        embedding_model=args.embedding_model,
        kv_transfer_config=kv_transfer,
        lora_adapters=dict(pair.split("=", 1)
                           for pair in args.lora_adapters.split(","))
        if args.lora_adapters else None,
        lora_rank=args.lora_rank, lora_alpha=args.lora_alpha,
        lora_targets=tuple(args.lora_targets.split(",")))
    engine = AsyncLLMEngine(cfg)
    if not args.no_warmup:
        engine.engine.runner.warmup()
    eff = engine.engine.eff
    eff.mark("main", main_unix)
    eff.mark("engine_built")

    async def _serve():
        app = build_app(engine,
                        trace_ring_entries=args.trace_ring_entries,
                        trace_sample_rate=args.trace_sample_rate)
        # cancel handlers when the peer disconnects (aiohttp >= 3.9
        # defaults this OFF): a request whose client has gone must
        # abort its engine-side generation even if it is still QUEUED —
        # without this, disconnects are only noticed at SSE write time,
        # and a backlog of orphaned requests keeps the engine busy for
        # clients that left minutes ago. Cancellation closes the stream
        # generator, whose finally aborts the sequence
        # (async_engine.stream).
        runner = web.AppRunner(app, handler_cancellation=True)
        await runner.setup()
        site = web.TCPSite(runner, args.host, args.port)
        await site.start()
        eff.mark("serving")
        logger.info("engine serving %s on %s:%d", cfg.model, args.host,
                    args.port)
        logger.info(eff.startup_sentence())
        while True:
            await asyncio.sleep(3600)

    asyncio.run(_serve())


if __name__ == "__main__":
    main()
