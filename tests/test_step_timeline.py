"""The step timeline (ISSUE 24): the engine thread's seconds by phase,
the device's starved seconds, the request's waits as trace events, and
the names the device work carries.

Tiers:
- unit — EngineEffAccounting's timeline with an injected clock: one
  scripted step with a known duration per phase;
- engine — a request through a real debug-tiny AsyncLLMEngine behind
  the aiohttp server: the five wait events on its trace, the ``step``
  block and ``steps`` ring of /debug/perf, POST /debug/profile;
- names — what ``runner.decode`` / ``runner.prefill`` lower to.
"""

import asyncio
import glob
import os
import re
import shutil

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.efficiency import (STEP_PHASES,
                                                    EngineEffAccounting)

# ------------------------------------------------------------ unit tier


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


# the scripted step: every phase gets a duration of its own, so that a
# second booked under the wrong name shows in that name's case
PHASE_SECONDS = {name: 0.001 * (i + 1) * (i + 2)
                 for i, name in enumerate(STEP_PHASES)}


def _scripted(acct: EngineEffAccounting, clock: _Clock) -> None:
    """no_work, a gap, then one step() shaped like the engine's: nested
    phases, a compile inside the decode dispatch, and seconds of the
    step outside any phase (they are housekeeping's)."""
    d = PHASE_SECONDS

    def spend(name):
        with acct.phase(name):
            clock.t += d[name]

    spend("no_work")
    clock.t += d["between_steps"]
    with acct.step():
        spend("expire")
        spend("schedule")
        spend("drain_sync")
        spend("drain_process")
        with acct.phase("prefill_host"):
            clock.t += d["prefill_host"] / 2
            spend("prefill_dispatch")
            with acct.phase("prefill_process"):
                clock.t += d["prefill_process"] / 4
                spend("prefill_sync")
                clock.t += 3 * d["prefill_process"] / 4
            clock.t += d["prefill_host"] / 2
        with acct.phase("decode_host"):
            clock.t += d["decode_host"]
            with acct.phase("decode_dispatch", dispatches=True):
                t0 = clock.t
                clock.t += d["compile"]
                acct.compile_started("decode", 8, 128, 2)
                acct.compile_finished("decode", 8, 128, t0, d["compile"],
                                      2)
                clock.t += d["decode_dispatch"]
        spend("decode_sync")
        spend("decode_process")
        clock.t += d["housekeeping"] / 2
        with acct.phase("housekeeping"):
            clock.t += d["housekeeping"] / 2


@pytest.fixture(scope="module")
def scripted_report():
    clock = _Clock()
    acct = EngineEffAccounting(now_fn=clock)
    t0 = clock.t
    _scripted(acct, clock)
    return acct.report()["step"], clock.t - t0, acct


@pytest.mark.parametrize("phase", STEP_PHASES)
def test_phase_seconds_land_under_their_own_name(scripted_report, phase):
    step, _, _ = scripted_report
    assert step["phase_s"][phase] == pytest.approx(PHASE_SECONDS[phase],
                                                   abs=2e-6)


def test_phases_partition_the_wall(scripted_report):
    step, elapsed, acct = scripted_report
    assert set(step["phase_s"]) == set(STEP_PHASES)
    assert step["steps"] == 1
    assert step["wall_s"] == pytest.approx(elapsed, abs=1e-6)
    assert sum(step["phase_s"].values()) == pytest.approx(
        step["wall_s"], abs=1e-5)
    # the ring's entry is the step alone: the wait for work is not in it
    (entry,) = acct.recent_steps()
    in_step = sum(v for k, v in PHASE_SECONDS.items()
                  if k not in ("no_work", "between_steps"))
    assert entry["wall_s"] == pytest.approx(in_step, abs=1e-6)
    assert "no_work" not in entry["phase_s"]
    assert entry["phase_s"]["between_steps"] == pytest.approx(
        PHASE_SECONDS["between_steps"], abs=1e-6)
    assert entry["at_unix"] > 1e9


def _starved_after(device: str, work: bool) -> dict:
    """One step ends with its window synced; then either the queue is
    ``empty`` or a window is ``inflight``; the loop then waits for work
    or goes straight on; the next step spends 0.03 s in the schedule
    and 0.05 s up to its dispatch's return."""
    clock = _Clock()
    acct = EngineEffAccounting(now_fn=clock)
    with acct.step():
        with acct.phase("decode_dispatch", dispatches=True):
            clock.t += 0.01
        with acct.phase("decode_sync"):
            clock.t += 0.5
        if device == "empty":
            acct.device_idle()
        with acct.phase("decode_process"):
            clock.t += 0.02
    if not work:
        with acct.phase("no_work"):
            clock.t += 3.0
    clock.t += 0.004
    with acct.step():
        with acct.phase("schedule"):
            clock.t += 0.03
        with acct.phase("decode_host"):
            clock.t += 0.04
            with acct.phase("decode_dispatch", dispatches=True):
                clock.t += 0.01
        # busy again: nothing after the dispatch's return counts
        with acct.phase("decode_sync"):
            clock.t += 0.5
    return acct.report()["step"]


# before the first dispatch of all returns the device has nothing: its
# 0.01 s are starved in every case
@pytest.mark.parametrize("device,work,expect", [
    # nothing outstanding and work waiting: from the sync's return to
    # the next dispatch's, by the phase the seconds fell in
    ("empty", True, {"decode_process": 0.02, "between_steps": 0.004,
                     "schedule": 0.03, "decode_host": 0.04,
                     "decode_dispatch": 0.01 + 0.01}),
    # a window still in flight: the device has work, nobody starves
    ("inflight", True, {"decode_dispatch": 0.01}),
    # nothing outstanding but no work either: the wait is not counted;
    # what arrives then is, up to its dispatch
    ("empty", False, {"decode_process": 0.02, "between_steps": 0.004,
                      "schedule": 0.03, "decode_host": 0.04,
                      "decode_dispatch": 0.01 + 0.01}),
], ids=["idle_with_work", "window_in_flight", "no_work"])
def test_starved_seconds(device, work, expect):
    step = _starved_after(device, work)
    got = {k: v for k, v in step["starved_by_phase"].items() if v}
    assert got == pytest.approx(expect, abs=1e-6)
    assert step["starved_s"] == pytest.approx(sum(expect.values()),
                                              abs=1e-6)
    if not work:
        assert step["phase_s"]["no_work"] == pytest.approx(3.0)


def _starved_around_a_prefill_entry(behind: bool) -> dict:
    """A chunk is dispatched; either a window is queued behind it
    (``behind``) or not; its result is synced (``prefill_sync``, inside
    the entry's ``prefill_process``); the next step dispatches a
    window."""
    clock = _Clock()
    acct = EngineEffAccounting(now_fn=clock)
    with acct.step():
        with acct.phase("prefill_host"):
            with acct.phase("prefill_dispatch", dispatches=True):
                clock.t += 0.01
        if behind:
            with acct.phase("decode_host"):
                with acct.phase("decode_dispatch", dispatches=True):
                    clock.t += 0.01
        with acct.phase("prefill_process"):
            with acct.phase("prefill_sync"):
                clock.t += 0.03
            if not behind:
                acct.device_idle()      # the queue ran dry
            clock.t += 0.02
    clock.t += 0.004
    with acct.step():
        with acct.phase("decode_host"):
            clock.t += 0.04
            with acct.phase("decode_dispatch", dispatches=True):
                clock.t += 0.01
    return acct.report()["step"]


# before the first dispatch of all returns the device has nothing: its
# 0.01 s are starved in both cases
@pytest.mark.parametrize("behind,expect", [
    # a window is queued behind the chunk: its sync leaves the device
    # with work, and nothing is booked
    (True, {"prefill_dispatch": 0.01}),
    # nothing behind it: from the sync's return to the next dispatch's
    (False, {"prefill_dispatch": 0.01, "prefill_process": 0.02,
             "between_steps": 0.004, "decode_host": 0.04,
             "decode_dispatch": 0.01}),
], ids=["window_behind_the_chunk", "queue_ran_dry"])
def test_starved_seconds_around_a_prefill_entry(behind, expect):
    step = _starved_around_a_prefill_entry(behind)
    got = {k: v for k, v in step["starved_by_phase"].items() if v}
    assert got == pytest.approx(expect, abs=1e-6)
    assert step["starved_s"] == pytest.approx(sum(expect.values()),
                                              abs=1e-6)
    assert sum(step["phase_s"].values()) == pytest.approx(
        step["wall_s"], abs=1e-6)


@pytest.mark.parametrize("ring,limit,expect", [
    (4, 50, [6, 7, 8, 9]),      # the ring keeps the newest ring_entries
    (16, 3, [7, 8, 9]),         # limit cuts what a read returns
    (16, 50, list(range(10))),
], ids=["ring_bound", "limit", "all"])
def test_steps_ring_bound_and_limit(ring, limit, expect):
    clock = _Clock(0.0)
    acct = EngineEffAccounting(now_fn=clock, ring_entries=ring)
    for i in range(10):
        clock.t = float(i)
        with acct.step():
            clock.t += 0.25
    assert [e["at"] for e in acct.recent_steps(limit)] == expect
    assert acct.report()["step"]["steps"] == 10


def test_window_entries_carry_host_and_sync_seconds():
    acct = EngineEffAccounting(now_fn=_Clock())
    acct.note_window(steps=8, positions=1, batch=4, live_rows=2,
                     kv_len=128, real=16, pad=16, dead=0, window_s=0.4,
                     host_s=0.0123456789, sync_s=0.3)
    (w,) = acct.recent_windows()
    assert w["host_s"] == 0.012346 and w["sync_s"] == 0.3
    assert w["window_s"] == 0.4


def test_annotate_receives_every_phase_by_name():
    """The engine passes jax.profiler.TraceAnnotation; anything that
    makes a context manager from a name will do."""
    seen = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("in", self.name))

        def __exit__(self, *exc):
            seen.append(("out", self.name))

    acct = EngineEffAccounting(now_fn=_Clock(), annotate=Note)
    with acct.step():
        with acct.phase("schedule"):
            pass
    assert seen == [("in", "pstpu.step"), ("in", "pstpu.schedule"),
                    ("out", "pstpu.schedule"), ("out", "pstpu.step")]


# ---------------------------------------------------------- engine tier

@pytest.fixture(scope="module")
def engine():
    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.config import EngineConfig
    return AsyncLLMEngine(EngineConfig(
        model="debug-tiny", max_model_len=128, max_num_seqs=2,
        prefill_chunk=16, prefill_buckets=(16,)))


def _with_client(engine, coro, **build_kw):
    from production_stack_tpu.engine.server import build_app

    async def runner():
        app = build_app(engine, **build_kw)
        async with TestClient(TestServer(app)) as client:
            return await coro(client)
    return asyncio.run(runner())


@pytest.fixture(scope="module")
def served(engine):
    """One chat request (a prompt of three prefill chunks) through the
    server: its trace, and /debug/perf after it."""
    async def body(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "debug-tiny", "max_tokens": 6, "temperature": 0.0,
            "ignore_eos": True,
            "messages": [{"role": "user",
                          "content": "where do my first seconds go"}]})
        assert r.status == 200
        trace_id = r.headers["x-trace-id"]
        r = await client.get(f"/debug/traces?trace_id={trace_id}")
        (trace,) = (await r.json())["traces"]
        perf = await (await client.get("/debug/perf?limit=1000")).json()
        few = await (await client.get("/debug/perf?limit=2")).json()
        return trace, perf, few
    return _with_client(engine, body)


# event -> the phase that holds it
WAIT_EVENTS = {"lock_wait": "queue_wait", "sched_wait": "queue_wait",
               "prefill_wait": "prefill", "first_token_emit": "decode",
               "emit_lag": "postprocess"}


@pytest.mark.parametrize("event", WAIT_EVENTS)
def test_request_wait_events(served, event):
    trace, _, _ = served
    spans = {s["name"]: s for s in trace["spans"]}
    assert event in spans, sorted(spans)
    ev, holder = spans[event], spans[WAIT_EVENTS[event]]
    assert ev["kind"] == "event" and holder["kind"] == "phase"
    assert 0.0 <= ev["duration_ms"] <= holder["duration_ms"] + 1e-3
    # it lies inside the phase that holds it, not just beside it
    assert ev["start_ms"] >= holder["start_ms"] - 1e-3
    assert (ev["start_ms"] + ev["duration_ms"]
            <= holder["start_ms"] + holder["duration_ms"] + 1e-3)
    if event == "sched_wait":
        assert ev["attrs"]["refused_passes"] == 0   # a slot was free
    if event == "prefill_wait":
        assert ev["attrs"]["chunks"] >= 2


def test_events_leave_the_phase_sum_alone(served):
    trace, _, _ = served
    phases = sum(s["duration_ms"] for s in trace["spans"]
                 if s["kind"] == "phase")
    assert trace["unattributed_ms"] == pytest.approx(
        trace["duration_ms"] - phases, abs=0.01)
    spans = {s["name"]: s for s in trace["spans"]}
    # queue_wait is still arrival -> admit: the two waits and the
    # refused passes (none here) lie inside it
    assert (spans["lock_wait"]["duration_ms"]
            + spans["sched_wait"]["duration_ms"]
            <= spans["queue_wait"]["duration_ms"] + 1e-3)


def test_debug_perf_step_block_and_ring(served):
    _, perf, few = served
    step = perf["totals"]["step"]
    assert set(step["phase_s"]) == set(STEP_PHASES)
    assert step["steps"] >= 3           # three chunks, then windows
    assert sum(step["phase_s"].values()) == pytest.approx(
        step["wall_s"], rel=0.01)
    assert step["phase_s"]["no_work"] > 0   # the loop waited for us
    for name in ("prefill_dispatch", "prefill_sync", "decode_dispatch",
                 "decode_sync", "decode_process", "schedule"):
        assert step["phase_s"][name] > 0, name
    assert 0 <= step["starved_s"] <= step["wall_s"]
    assert len(perf["steps"]) == step["steps"]
    assert len(few["steps"]) == 2
    entry = perf["steps"][-1]
    assert {"at", "at_unix", "wall_s", "phase_s", "starved_s"} <= set(entry)
    assert set(entry["phase_s"]) <= set(STEP_PHASES)
    w = perf["windows"][-1]
    assert 0 < w["host_s"] < 5 and 0 <= w["sync_s"] <= w["window_s"] + 1e-3


@pytest.fixture(scope="module")
def turnover():
    """A real engine (no server) through one turnover of a slot with
    windows in flight: three rows decode, a fourth request's chunks go
    behind the queue, a row ends. ``totals.step`` before the fourth
    request, after its first token, and at the end, with the shortest
    the in-flight queue was in between."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    def greedy(n):
        return SamplingOptions(temperature=0.0, max_tokens=n,
                               ignore_eos=True)
    eng = LLMEngine(EngineConfig(
        model="debug-tiny", max_model_len=256, max_num_seqs=4,
        prefill_chunk=32, prefill_buckets=(32,), decode_window=4))
    longs = [eng.add_request(list(range(20 + 10 * i, 30 + 10 * i)),
                             greedy(60)) for i in range(3)]
    while min(len(eng.seqs[s].output_tokens) for s in longs) < 6:
        eng.step()
    assert eng._inflight
    before = eng.eff.report()["step"]
    new = eng.add_request([2 + (7 * i) % 190 for i in range(70)], greedy(5))
    shortest = 99
    while not eng.seqs[new].output_tokens:
        eng.step()
        shortest = min(shortest, len(eng._inflight))
    joined = eng.eff.report()["step"]
    while eng.has_work:
        eng.step()
    return before, joined, eng.eff.report()["step"], shortest


def test_phases_partition_the_wall_with_prefill_entries_in_flight(turnover):
    before, joined, end, shortest = turnover
    assert joined["prefill_behind"] - before["prefill_behind"] == 3
    assert joined["prefill_drained"] == before["prefill_drained"]
    assert shortest >= 1
    for step in (before, joined, end):
        assert set(step["phase_s"]) == set(STEP_PHASES)
        assert sum(step["phase_s"].values()) == pytest.approx(
            step["wall_s"], rel=1e-3)
    moved = {k for k in STEP_PHASES
             if joined["phase_s"][k] > before["phase_s"][k]}
    assert {"prefill_host", "prefill_dispatch", "prefill_sync",
            "prefill_process", "decode_sync"} <= moved
    assert not {"drain_sync", "drain_process"} & moved


def test_no_second_is_starved_while_the_queue_holds_an_entry(turnover):
    """Across the join the queue never ran dry, so the device had work
    all the time; once every row has ended it does run dry, and the
    first dispatch had nothing before it."""
    before, joined, end, _ = turnover
    assert before["starved_s"] > 0
    assert joined["starved_s"] == before["starved_s"]
    assert joined["starved_by_phase"] == before["starved_by_phase"]
    assert end["starved_s"] >= joined["starved_s"]


def _moe_totals_after_each(model: str, prompts, whole: bool = False,
                           **geometry) -> list:
    """``totals`` of /debug/perf (``whole``: all of it) after each of
    ``prompts`` has been answered by a fresh engine serving ``model``."""
    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.config import EngineConfig
    eng = AsyncLLMEngine(EngineConfig(**{**dict(
        model=model, max_model_len=128, max_num_seqs=2,
        prefill_chunk=16, prefill_buckets=(16,)), **geometry}))

    async def body(client):
        seen = []
        for prompt in prompts:
            r = await client.post("/v1/completions", json={
                "model": model, "max_tokens": 6, "temperature": 0.0,
                "ignore_eos": True, "prompt": prompt})
            assert r.status == 200
            perf = await (await client.get("/debug/perf")).json()
            seen.append(perf if whole else perf["totals"])
        return seen
    return _with_client(eng, body)


def test_debug_perf_counts_the_experts_a_decode_step_read():
    """``totals.moe`` of a MoE engine: both sums grow with decode steps,
    resident by steps x layers x experts, and read never passes it (on
    the CPU the kernels are off and every step reads every expert)."""
    first, second = _moe_totals_after_each(
        "debug-moe", ["count my experts", "and once more"])
    for totals in (first, second):
        moe = totals["moe"]
        assert 0 < moe["experts_read"] <= moe["experts_resident"]
    # debug-moe: 2 layers x 4 experts, whole windows of steps
    grew = (second["moe"]["experts_resident"]
            - first["moe"]["experts_resident"])
    assert grew > 0 and grew % (2 * 4) == 0
    assert second["moe"]["experts_read"] > first["moe"]["experts_read"]


def test_debug_perf_has_no_expert_count_for_a_dense_model(served):
    _, perf, _ = served
    assert "moe" not in perf["totals"]
    assert perf["totals"]["decode"]["windows"] > 0


def test_debug_perf_expert_count_follows_the_list_path():
    """A model wide enough for the list path (ops/moe.list_path; the
    kernels forced on, in interpret mode): a decode step of two rows
    reads at most 2 x top-2 of its 16 experts a layer, and
    ``experts_read`` says so."""
    import jax.numpy as jnp
    from production_stack_tpu.models import config as model_configs
    from production_stack_tpu.ops import pallas_paged
    name = "t-moe16-wide"
    model_configs.PRESETS[name] = model_configs.ModelConfig(
        name=name, vocab_size=512, hidden_size=128,
        intermediate_size=128, num_layers=2, num_heads=2,
        num_kv_heads=2, max_position_embeddings=256, num_experts=16,
        num_experts_per_tok=2, dtype=jnp.float32)
    pallas_paged.set_flash_enabled(True)
    try:
        (totals,) = _moe_totals_after_each(name, ["walk the list"])
    finally:
        pallas_paged.set_flash_enabled(None)
        del model_configs.PRESETS[name]
    moe = totals["moe"]
    steps_layers = moe["experts_resident"] // 16
    assert steps_layers > 0
    # one live row (and a parked one): 1 to 2 experts a layer and step
    assert steps_layers <= moe["experts_read"] <= 2 * steps_layers


def test_debug_perf_names_the_experts_path_of_every_executable():
    """``device.moe_paths`` of a MoE engine: one entry an executable,
    keyed as ``attention_paths`` is; on the CPU the kernels are off and
    a chunk of 16 tokens is fewer than the dense threshold: exact."""
    (perf,) = _moe_totals_after_each("debug-moe", ["which path"],
                                     whole=True)
    paths = perf["device"]["moe_paths"]
    assert paths and set(paths) == set(perf["device"]["attention_paths"])
    assert {k.split("|")[0] for k in paths} >= {"decode", "prefill"}
    assert set(paths.values()) == {"exact"}


def test_debug_perf_names_no_experts_path_for_a_dense_model(served):
    _, perf, _ = served
    assert perf["device"]["moe_paths"] == {}
    assert perf["device"]["attention_paths"]
    assert not {"expert_rows", "routed_rows", "held_rows",
                "expert_rounds"} & set(perf["totals"]["prefill"])


def test_debug_perf_counts_the_rows_a_prefills_experts_multiplied():
    """``totals.prefill`` of a MoE engine: ``routed_rows`` is the real
    tokens x top-k x layers, ``expert_rows`` what the experts
    multiplied; on the exact path (the CPU) that is every expert over
    every position computed, padding and all."""
    first, second = _moe_totals_after_each(
        "debug-moe", ["count my rows", "and these rows too"])
    for totals in (first, second):
        pre = totals["prefill"]
        # debug-moe: 2 layers x 4 experts, top-2
        assert pre["routed_rows"] == pre["real"] * 2 * 2
        assert pre["expert_rows"] == (pre["real"] + pre["pad"]) * 4 * 2
        # no rounds outside the grouped path of a held share
        assert pre["held_rows"] == 0 and pre["expert_rounds"] == 0
    assert second["prefill"]["routed_rows"] > first["prefill"]["routed_rows"]


def test_debug_perf_follows_the_grouped_path():
    """A model wide enough for the kernels (forced on, in interpret
    mode) and a chunk bucket of 128 tokens: the prefill executable's
    experts run grouped, the decode executable's walk the list, and
    the experts multiplied passes of GROUPED_ROWS rows: at most one
    pass more than the routed rows fill, for each expert and layer."""
    import jax.numpy as jnp
    from production_stack_tpu.models import config as model_configs
    from production_stack_tpu.ops import moe, pallas_paged
    name = "t-moe16-wide"
    model_configs.PRESETS[name] = model_configs.ModelConfig(
        name=name, vocab_size=512, hidden_size=128,
        intermediate_size=128, num_layers=2, num_heads=2,
        num_kv_heads=2, max_position_embeddings=256, num_experts=16,
        num_experts_per_tok=2, dtype=jnp.float32)
    pallas_paged.set_flash_enabled(True)
    try:
        (perf,) = _moe_totals_after_each(
            name, ["group my rows by expert"], whole=True,
            max_model_len=256, prefill_chunk=128, prefill_buckets=(128,))
    finally:
        pallas_paged.set_flash_enabled(None)
        del model_configs.PRESETS[name]
    paths = perf["device"]["moe_paths"]
    by_kind = {}
    for key, path in paths.items():
        by_kind.setdefault(key.split("|")[0], set()).add(path)
    assert by_kind == {"prefill": {"grouped"}, "decode": {"list"}}
    pre = perf["totals"]["prefill"]
    assert pre["routed_rows"] == pre["real"] * 2 * 2 > 0
    R = moe.GROUPED_ROWS
    assert pre["expert_rows"] % R == 0
    assert R * 2 <= pre["expert_rows"] <= (
        pre["routed_rows"] // R + 2 * 16) * R
    # every expert the router scores is held: nothing is compacted
    assert pre["held_rows"] == 0 and pre["expert_rounds"] == 0


def test_debug_perf_counts_the_rounds_of_a_held_share():
    """debug-dsa holds experts 4-7 of a router of 8 (two expert layers,
    top-2), the kernels forced on in interpret mode, a chunk bucket of
    128 tokens: the prefill's experts run grouped over the assignments
    that landed here, ``held_rows`` of the ``routed_rows``, in one
    round a layer and dispatch (a block is all 256 of the chunk's
    assignments: twice the even share)."""
    from production_stack_tpu.ops import moe, pallas_paged
    pallas_paged.set_flash_enabled(True)
    try:
        (perf,) = _moe_totals_after_each(
            "debug-dsa", ["which of my rows land on this chip"], whole=True,
            max_model_len=256, prefill_chunk=128, prefill_buckets=(128,))
    finally:
        pallas_paged.set_flash_enabled(None)
    assert {path for key, path in perf["device"]["moe_paths"].items()
            if key.startswith("prefill")} == {"grouped"}
    pre = perf["totals"]["prefill"]
    assert moe.held_block(128, 2, 4, 8) == 256
    assert pre["routed_rows"] == pre["real"] * 2 * 2 > 0
    assert 0 < pre["held_rows"] < pre["routed_rows"]
    assert pre["expert_rounds"] == 2 * pre["dispatches"]
    R = moe.GROUPED_ROWS
    assert pre["expert_rows"] % R == 0
    assert pre["held_rows"] <= pre["expert_rows"] <= 2 * 4 * R


def test_debug_profile_captures_and_refuses_a_second(engine):
    async def body(client):
        r = await client.post("/debug/profile", json={"seconds": 99999})
        assert r.status == 400
        first = asyncio.ensure_future(
            client.post("/debug/profile", json={"seconds": 0.6}))
        await asyncio.sleep(0.2)
        r = await client.post("/debug/profile", json={"seconds": 0.1})
        assert r.status == 409
        r = await first
        assert r.status == 200
        out = await r.json()
        assert out["seconds"] == 0.6
        assert glob.glob(os.path.join(out["dir"], "plugins", "profile",
                                      "*", "*.xplane.pb"))
        shutil.rmtree(out["dir"])
        # and the hook is free again
        r = await client.post("/debug/profile", json={"seconds": 0.05})
        assert r.status == 200
        shutil.rmtree((await r.json())["dir"])
    _with_client(engine, body)


def test_debug_profile_behind_api_key(engine):
    async def body(client):
        r = await client.post("/debug/profile", json={"seconds": 0.05})
        assert r.status == 401
    _with_client(engine, body, api_key="sk")


# ----------------------------------------------------------- names tier

def _lowered(preset: str) -> dict:
    """kind -> the compiled HLO text of the executable
    ``runner.decode`` / ``runner.prefill`` made for it."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sampler import SamplingParams
    B, S = 2, 64
    runner = LLMEngine(EngineConfig(
        model=preset, max_model_len=S, max_num_seqs=B, prefill_chunk=16,
        prefill_buckets=(16,))).runner
    texts, inner = {}, runner._compile

    def spy(cache, key, make_fn, args, **kw):
        texts[kw["kind"]] = make_fn().lower(*args).compile().as_text()
        return inner(cache, key, make_fn, args, **kw)
    runner._compile = spy
    sampling = SamplingParams.filled(B)
    runner.set_decode_state(np.zeros((B,), np.int32),
                            np.full((B,), S, np.int32))
    runner.decode(sampling, steps=2, kv_len=S, greedy=True)
    runner.prefill(np.zeros((B, 16), np.int32), np.full((B,), S, np.int32),
                   np.ones((B,), np.int32), sampling, S)
    return texts


@pytest.fixture(scope="module")
def lowered():
    made = {}

    def get(preset):
        if preset not in made:
            made[preset] = _lowered(preset)
        return made[preset]
    return get


@pytest.mark.parametrize("preset,kind,module,scope", [
    ("debug-tiny", "decode", "jit_decode_window", "kv_write"),
    ("debug-tiny", "decode", "jit_decode_window", "attention"),
    ("debug-tiny", "decode", "jit_decode_window", "sample"),
    ("debug-tiny", "prefill", "jit_prefill_chunk", "kv_write"),
    ("debug-tiny", "prefill", "jit_prefill_chunk", "attention"),
    ("debug-tiny", "prefill", "jit_prefill_chunk", "mlp"),
    ("debug-moe", "decode", "jit_decode_window", "moe_experts"),
    ("debug-moe", "prefill", "jit_prefill_chunk", "moe_router"),
])
def test_executables_and_scopes_carry_their_names(lowered, preset, kind,
                                                  module, scope):
    text = lowered(preset)[kind]
    assert re.match(r"HloModule " + module + r"\b", text), text[:80]
    # the scope is a path element of some operation's op_name, under
    # the layer scan
    assert re.search(r'op_name="[^"]*/layers/[^"]*/' + scope + r'[/"]', text) \
        or (scope == "sample"
            and re.search(r'op_name="[^"]*/sample[/"]', text))
