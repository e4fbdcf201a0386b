"""The loop timeline (ISSUE 38): the event-loop thread's account
(``totals.loop`` and the ``loop`` ring of GET /debug/perf), the token's
way from the request's queue to the socket (``first_token_write``,
``emit_lag``'s end), and processor seconds beside wall seconds in the
step timeline of a real engine.

Tiers:
- unit: LoopAccounting with injected clocks;
- engine: a streamed and a collected chat request through a real
  debug-tiny AsyncLLMEngine behind the aiohttp server.
"""

import asyncio
import json
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.async_engine import LAG_PROBE_S
from production_stack_tpu.engine.efficiency import (STEP_PHASES,
                                                    EngineEffAccounting,
                                                    LoopAccounting)

LOOP_TOTALS = {"wall_s", "cpu_s", "dispatch_s", "serialize_s", "write_s",
               "payloads"}


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


# ------------------------------------------------------------ unit tier

def test_totals_loop_is_part_of_the_one_report():
    acct = EngineEffAccounting(now_fn=_Clock())
    assert isinstance(acct.loop, LoopAccounting)
    assert set(acct.report()["loop"]) == LOOP_TOTALS
    assert not any(acct.report()["loop"].values())
    assert acct.loop.recent() == []


def test_a_sample_books_the_overshoot_and_the_threads_seconds():
    wall, cpu = _Clock(), _Clock(5.0)
    acct = LoopAccounting(now_fn=wall, cpu_fn=cpu, wall_fn=lambda: 1e9)
    acct.sample(None)                   # the probe starts: nothing booked
    assert acct.recent() == [] and acct.report()["wall_s"] == 0
    wall.t += 0.1004
    cpu.t += 0.012
    acct.sample(0.1)
    wall.t += 0.150                     # the loop was held for 50 ms
    cpu.t += 0.140
    acct.sample(0.1)
    first, second = acct.recent()
    assert first["lag_s"] == pytest.approx(0.0004, abs=1e-6)
    assert second["lag_s"] == pytest.approx(0.05, abs=1e-6)
    assert second["cpu_s"] == pytest.approx(0.14)
    assert set(second) == {"at", "at_unix", "lag_s", "cpu_s"}
    assert second["at"] == wall.t and second["at_unix"] == 1e9
    report = acct.report()
    assert report["wall_s"] == pytest.approx(0.2504)
    assert report["cpu_s"] == pytest.approx(0.152)
    # a probe that starts again (the engine was stopped and started)
    # books nothing for the time in between
    wall.t += 60.0
    acct.sample(None)
    assert len(acct.recent()) == 2
    assert acct.report()["wall_s"] == pytest.approx(0.2504)


def test_the_ring_is_bounded_and_limited():
    wall = _Clock()
    acct = LoopAccounting(ring_entries=4, now_fn=wall, cpu_fn=_Clock())
    acct.sample(None)
    for _ in range(9):
        wall.t += 0.1
        acct.sample(0.1)
    assert len(acct.recent(100)) == 4 and len(acct.recent(2)) == 2


def test_the_three_pieces_of_a_tokens_way_out():
    wall = _Clock()
    seen = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("in", self.name))

        def __exit__(self, *exc):
            seen.append(("out", self.name))

    acct = LoopAccounting(now_fn=wall, cpu_fn=_Clock(), annotate=Note)
    with acct.dispatching() as span:
        assert span.t0 == wall.t
        wall.t += 0.003
    with acct.serializing():
        wall.t += 0.005
    for _ in range(2):
        with acct.writing() as write:
            wall.t += 0.007
        assert write.t1 == wall.t
    assert acct.report() == {
        "wall_s": 0.0, "cpu_s": 0.0, "dispatch_s": 0.003,
        "serialize_s": 0.005, "write_s": 0.014, "payloads": 2}
    # the two names a profiler capture holds; building JSON has none
    assert seen == [("in", "pstpu.loop.dispatch"),
                    ("out", "pstpu.loop.dispatch")] + 2 * [
                        ("in", "pstpu.loop.write"),
                        ("out", "pstpu.loop.write")]


# ---------------------------------------------------------- engine tier

@pytest.fixture(scope="module")
def engine():
    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.config import EngineConfig
    return AsyncLLMEngine(EngineConfig(
        model="debug-tiny", max_model_len=128, max_num_seqs=2,
        prefill_chunk=16, prefill_buckets=(16,)))


BODY = {"model": "debug-tiny", "max_tokens": 12, "temperature": 0.0,
        "ignore_eos": True,
        "messages": [{"role": "user", "content": "which way out"}]}


@pytest.fixture(scope="module")
def served(engine):
    """One streamed and one collected chat request, the loop held for
    longer than the probe's period in between; their traces,
    /debug/perf after them, and the streamed sequence's stamps."""
    from production_stack_tpu.engine.server import build_app

    async def trace_of(client, r):
        r = await client.get(
            f"/debug/traces?trace_id={r.headers['x-trace-id']}")
        (trace,) = (await r.json())["traces"]
        return trace

    async def body():
        async with TestClient(TestServer(build_app(engine))) as client:
            r = await client.post("/v1/chat/completions",
                                  json={**BODY, "stream": True})
            assert r.status == 200
            lines = [ln for ln in (await r.text()).splitlines()
                     if ln.startswith("data: ")]
            streamed = await trace_of(client, r)
            waits = list(engine.engine.seqs.values())[-1].waits
            await asyncio.sleep(2.5 * LAG_PROBE_S)
            held = time.monotonic()
            time.sleep(LAG_PROBE_S + 0.05)      # nothing else can run
            await asyncio.sleep(1.5 * LAG_PROBE_S)
            r = await client.post("/v1/chat/completions", json=BODY)
            assert r.status == 200
            collected = await trace_of(client, r)
            perf = await (await client.get(
                "/debug/perf?limit=1000")).json()
            few = await (await client.get("/debug/perf?limit=2")).json()
            return streamed, collected, perf, few, lines, waits, held
    return asyncio.run(body())


def _spans(trace):
    return {s["name"]: s for s in trace["spans"]}


def _end(span):
    return span["start_ms"] + span["duration_ms"]


def test_totals_loop_keys_and_cpu_within_wall(served):
    _, _, perf, _, _, _, _ = served
    loop = perf["totals"]["loop"]
    assert set(loop) == LOOP_TOTALS
    assert 0 < loop["cpu_s"] <= loop["wall_s"]
    # the probe has run since the server started: ten samples a second
    assert loop["wall_s"] == pytest.approx(
        sum(LAG_PROBE_S + e["lag_s"] for e in perf["loop"]), abs=0.05)


def test_every_payload_is_counted_and_timed(served):
    _, _, perf, _, lines, _, _ = served
    loop = perf["totals"]["loop"]
    assert lines[-1] == "data: [DONE]"
    tokens = [json.loads(ln[6:]) for ln in lines[:-1]]
    # a token that completes no character yet makes no payload
    assert 2 <= len(tokens) <= 1 + 12 and tokens[0]["choices"][0][
        "delta"]["role"] == "assistant"
    # the streamed request's payloads alone: a collected response is
    # written by aiohttp after the handler has returned
    assert loop["payloads"] == len(lines)
    assert loop["write_s"] > 0 and loop["serialize_s"] > 0
    assert loop["dispatch_s"] > 0
    busy = loop["write_s"] + loop["serialize_s"] + loop["dispatch_s"]
    assert busy < loop["wall_s"]


def test_a_held_loop_reads_a_lag_sample(served):
    _, _, perf, few, _, _, held = served
    # the probe's wake-up that fell due while the loop was held (a
    # period and 50 ms, so one did) overshot by 50 ms at least; the
    # samples before it, on an idle loop, by next to nothing
    late = [e["lag_s"] for e in perf["loop"]
            if held < e["at"] < held + 2 * LAG_PROBE_S + 0.05]
    assert late and max(late) >= 0.04
    idle = [e["lag_s"] for e in perf["loop"]
            if held - 2 * LAG_PROBE_S < e["at"] < held]
    assert idle and max(idle) < 0.04
    assert all(set(e) == {"at", "at_unix", "lag_s", "cpu_s"}
               for e in perf["loop"])
    # the ring's last two, in order; where one probe sample fell between
    # the two reads, the last and that one
    assert len(few["loop"]) == 2
    assert (few["loop"] == perf["loop"][-2:]
            or few["loop"][0] == perf["loop"][-1])


def test_first_token_write_lies_inside_decode(served):
    streamed, _, _, _, _, waits, _ = served
    spans = _spans(streamed)
    write, emit = spans["first_token_write"], spans["first_token_emit"]
    assert write["kind"] == "event"
    # from the queue to the socket: it starts where first_token_emit ends
    assert write["start_ms"] == pytest.approx(_end(emit), abs=0.002)
    assert spans["decode"]["start_ms"] <= write["start_ms"]
    assert _end(write) <= _end(spans["decode"])
    assert write["duration_ms"] == pytest.approx(
        1e3 * (waits.first_write - waits.first_emit), abs=0.002)
    assert waits.first_emit <= waits.first_write < waits.last_write


def test_a_streamed_requests_emit_lag_ends_at_its_last_write(served):
    streamed, _, _, _, _, waits, _ = served
    spans = _spans(streamed)
    lag = spans["emit_lag"]
    assert waits.last_emit < waits.last_write
    assert _end(lag) - _end(spans["first_token_write"]) == pytest.approx(
        1e3 * (waits.last_write - waits.first_write), abs=0.005)
    assert spans["postprocess"]["start_ms"] == pytest.approx(
        lag["start_ms"], abs=0.002)
    assert _end(lag) <= _end(spans["postprocess"]) + 0.002


def test_a_collected_response_has_no_write_stamps(served):
    _, collected, _, _, _, _, _ = served
    spans = _spans(collected)
    assert "first_token_write" not in spans
    assert {"first_token_emit", "emit_lag"} <= set(spans)


@pytest.mark.parametrize("phase", STEP_PHASES)
def test_phases_partition_the_wall_with_both_clocks(served, phase):
    _, _, perf, _, _, _, _ = served
    step = perf["totals"]["step"]
    assert set(step["phase_s"]) == set(step["cpu_s"]) == set(
        step["offcpu_s"]) == set(STEP_PHASES)
    assert sum(step["phase_s"].values()) == pytest.approx(
        step["wall_s"], abs=2e-5)
    assert 0 <= step["cpu_s"][phase] <= step["phase_s"][phase]
    assert step["offcpu_s"][phase] >= 0
    assert step["cpu_s"][phase] + step["offcpu_s"][phase] == pytest.approx(
        step["phase_s"][phase], abs=1e-6)


def test_the_engine_thread_waits_off_the_processor(served):
    """The wait for work is a condition variable, a sync a blocking
    call: both read as off-processor seconds; the walk of a window is
    the thread's own Python and reads as on it."""
    _, _, perf, _, _, _, _ = served
    step = perf["totals"]["step"]
    assert step["offcpu_s"]["no_work"] > 0.9 * step["phase_s"]["no_work"]
    assert step["phase_s"]["no_work"] > 0.3
    assert step["cpu_s"]["decode_process"] > 0
    assert all("offcpu_s" in e for e in perf["steps"])
    assert sum(step["dispatch_depth"].values()) > 0


def test_the_probe_stops_with_the_engine_and_starts_again(engine):
    async def body():
        loop = asyncio.get_running_loop()
        before = len(engine.engine.eff.loop.recent(10000))
        engine.start(loop, warmup=False)
        await asyncio.sleep(2.5 * LAG_PROBE_S)
        probe = engine._lag_probe
        assert probe is not None and not probe.done()
        engine.stop()
        await asyncio.sleep(0.02)
        assert probe.cancelled() and engine._lag_probe is None
        after = engine.engine.eff.loop.recent(10000)
        assert len(after) - before in (1, 2, 3)
        # started again, it books nothing for the time it was stopped
        assert max(e["lag_s"] for e in after[before:]) < 0.09
    asyncio.run(body())


def test_a_capture_holds_both_threads_spans(engine):
    """POST /debug/profile while a request streams: the loop timeline's
    two names lie on the host plane beside the step timeline's phases,
    on a thread line of their own (tools/capture_names.py reads them)."""
    import importlib.util
    import os
    import shutil

    from production_stack_tpu.engine.server import build_app
    spec = importlib.util.spec_from_file_location(
        "capture_names", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "capture_names.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    async def body():
        async with TestClient(TestServer(build_app(engine))) as client:
            # the same request first, outside the capture: on a worker
            # that was handed this test alone the engine is cold, and
            # its compiles outlast the 0.8 s (PR 47)
            r = await client.post("/v1/chat/completions", json={
                **BODY, "stream": True, "max_tokens": 40})
            await r.read()
            capture = asyncio.ensure_future(client.post(
                "/debug/profile", json={"seconds": 0.8}))
            await asyncio.sleep(0.2)
            r = await client.post("/v1/chat/completions", json={
                **BODY, "stream": True, "max_tokens": 40})
            assert r.status == 200
            await r.read()
            r = await capture
            assert r.status == 200
            return (await r.json())["dir"]
    capture_dir = asyncio.run(body())
    try:
        threads = tool.names_in(capture_dir)["threads"]
    finally:
        shutil.rmtree(capture_dir, ignore_errors=True)
    loop = [names for names in threads.values()
            if "pstpu.loop.write" in names]
    step = [names for names in threads.values() if "pstpu.step" in names]
    assert len(loop) == 1 and len(step) == 1 and loop[0] is not step[0]
    assert set(loop[0]) == {"pstpu.loop.dispatch", "pstpu.loop.write"}
    assert {"pstpu.decode_dispatch", "pstpu.decode_sync",
            "pstpu.decode_process"} <= set(step[0])
    assert loop[0]["pstpu.loop.write"][0] >= 3
