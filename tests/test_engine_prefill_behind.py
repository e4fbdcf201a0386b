"""A prefill joins the device queue instead of draining it (ISSUE 36).

The in-flight queue holds decode windows and prefill entries in
dispatch order; a turnover of a slot (one row leaves, one joins) edits
the decode carry by slot on the device and leaves the queue as it is.
Held here, on the CPU at debug-tiny sizes:

- the streams are token for token those of the drain-first path (the
  parent's: every prefill empties the queue, every finish makes the
  next dispatch upload the host mirrors), greedy and seeded;
- a finish alone does not empty the queue;
- a first token that ends its request, an abort and an expiry between
  dispatch and retirement, a prompt of several chunks;
- each reason of the rule (``LLMEngine._prefill_drains``) drains and
  is counted under its name in ``totals.step`` of GET /debug/perf.

Engines are shared by configuration within the module (each test
leaves its engine idle): a fresh one compiles every executable again.
"""

import random
import time

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.efficiency import DRAIN_REASONS
from production_stack_tpu.engine.engine import (LLMEngine, _Prefill,
                                                _Window)
from production_stack_tpu.engine.scheduler import SamplingOptions, SeqStatus

S = 256
CHUNK = 32
# three requests that keep three of the four slots decoding
LONG = [list(range(23 + 10 * i, 33 + 10 * i)) for i in range(3)]
PROMPT = list(range(40, 60))


def _drain_first(eng: LLMEngine) -> LLMEngine:
    """The parent's step loop on this engine: a prefill empties the
    queue whatever the rule says, and a parked slot leaves the carry to
    the next upload of the host mirrors."""
    eng._prefill_drains = lambda works: "reshape"
    park = eng._park_slot

    def park_then_upload(slot):
        eng._decode_dirty = True
        park(slot)
    eng._park_slot = park_then_upload
    return eng


@pytest.fixture(scope="module")
def engines():
    """engines(depth=2, window=4, drain_first=False, **engine_kw): the
    module's idle engine of that configuration."""
    made = {}

    def get(depth=2, window=4, drain_first=False, **kw):
        key = (depth, window, drain_first, tuple(sorted(kw.items())))
        if key not in made:
            eng = LLMEngine(EngineConfig(
                model="debug-tiny", max_model_len=S, max_num_seqs=4,
                prefill_chunk=CHUNK, prefill_buckets=(CHUNK,),
                decode_window=window, pipeline_depth=depth, **kw))
            made[key] = _drain_first(eng) if drain_first else eng
        assert not made[key].has_work
        return made[key]
    return get


def _greedy(n):
    return SamplingOptions(temperature=0.0, max_tokens=n, ignore_eos=True)


def _step_until(eng, cond, limit=500):
    outs = []
    for _ in range(limit):
        outs += eng.step()
        if cond():
            return outs
    raise AssertionError("condition not reached")


def _finish(eng, ids):
    return _step_until(
        eng, lambda: all(eng.seqs[i].status is SeqStatus.FINISHED
                         for i in ids), limit=3000)


@pytest.fixture(scope="module")
def reference(engines):
    """reference(prompt, options, **engine_kw): the drain-first path's
    sequence for one request served alone (each computed once)."""
    made = {}

    def reference(prompt, options, **kw):
        key = (tuple(prompt), repr(options), tuple(sorted(kw.items())))
        if key not in made:
            eng = engines(drain_first=True, **kw)
            sid = eng.add_request(prompt, options)
            _finish(eng, [sid])
            made[key] = eng.seqs[sid]
        return made[key]
    return reference


# -------------------------------------------------------- churny loop

def _options(sampling: str, i: int, max_tokens: int) -> SamplingOptions:
    if sampling == "greedy":
        return _greedy(max_tokens)
    return SamplingOptions(temperature=0.9, seed=1000 + i,
                           max_tokens=max_tokens, ignore_eos=True)


def _churn(eng: LLMEngine, sampling: str, total: int = 20) -> list:
    """A closed loop of ``max_num_seqs`` clients: a request that ends
    is replaced at once, prompts of 5-70 tokens (one to three chunks),
    1-14 output tokens, so that rows leave and join every few steps.
    Returns the tokens of each request, as they were streamed."""
    rng = random.Random(7)
    streams, done, ids = {}, set(), []

    def add():
        prompt = [rng.randrange(2, 200) for _ in range(rng.randrange(5, 70))]
        ids.append(eng.add_request(prompt, _options(
            sampling, len(ids), rng.randrange(1, 15))))
    for _ in range(eng.cfg.max_num_seqs):
        add()
    steps = 0
    while len(done) < total:
        for out in eng.step():
            if out.new_token is not None:
                streams.setdefault(out.seq_id, []).append(out.new_token)
            if out.finished:
                done.add(out.seq_id)
                if len(ids) < total:
                    add()
        steps += 1
        assert steps < 4000
    assert all(streams[i] == eng.seqs[i].output_tokens for i in ids)
    return [streams[i] for i in ids]


@pytest.fixture(scope="module")
def drain_first_streams(engines):
    """Seeded noise is a function of (seed, position) and greedy of
    nothing, so one drain-first run at one geometry is the reference of
    every depth and window."""
    return {sampling: _churn(engines(drain_first=True), sampling)
            for sampling in ("greedy", "seeded")}


@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
@pytest.mark.parametrize("window", [1, 4, 8])
@pytest.mark.parametrize("depth", [1, 2])
def test_churny_loop_streams_what_the_drain_first_path_streams(
        engines, drain_first_streams, depth, window, sampling):
    eng = engines(depth, window)
    before = eng.eff.report()
    assert _churn(eng, sampling) == drain_first_streams[sampling]
    after = eng.eff.report()
    behind = after["step"]["prefill_behind"] - before["step"]["prefill_behind"]
    drained = (sum(after["step"]["prefill_drained"].values())
               - sum(before["step"]["prefill_drained"].values()))
    # the first burst finds no carry of its batch, and a batch this
    # small changes its bucket now and then (reshape); the other rows
    # join behind the queue
    assert behind > 0
    assert behind + drained == (after["prefill"]["dispatches"]
                                - before["prefill"]["dispatches"])


# ------------------------------------------ one row leaves, one joins

def _running(engines, rows=3, **kw):
    """The engine with ``rows`` long requests decoding and windows in
    flight; with three, a fourth row joins the carry's batch bucket as
    it is. Returns (engine, their ids)."""
    eng = engines(**kw)
    longs = [eng.add_request(p, _greedy(80)) for p in LONG[:rows]]
    _step_until(eng, lambda: all(len(eng.seqs[s].output_tokens) >= 6
                                 for s in longs))
    assert eng._inflight and not eng._decode_dirty
    return eng, longs


def _longs_are_whole(eng, longs, reference, **kw):
    _finish(eng, longs)
    for sid, prompt in zip(longs, LONG):
        assert eng.seqs[sid].output_tokens == reference(
            prompt, _greedy(80), **kw).output_tokens


def test_a_finish_alone_does_not_empty_the_queue(engines, reference,
                                                 monkeypatch):
    """No request waits: the row that ends is parked ON THE DEVICE, the
    host mirrors are not uploaded again, and the next window is
    dispatched ahead of the one being walked. (Four rows, three left:
    the same batch bucket; fewer and a reshape would be due.)"""
    eng, longs = _running(engines)
    short = eng.add_request(list(range(3, 13)), _greedy(6))
    _step_until(eng, lambda: eng.seqs[short].output_tokens)
    slot = eng.seqs[short].slot
    uploads = []
    inner = eng.runner.set_decode_state
    monkeypatch.setattr(eng.runner, "set_decode_state", lambda *a, **kw: (
        uploads.append(a[0].shape), inner(*a, **kw))[1])
    _step_until(eng, lambda: eng.seqs[short].status is SeqStatus.FINISHED)
    assert not eng._decode_dirty
    assert any(isinstance(e, _Window) for e in eng._inflight)
    assert int(np.asarray(eng.runner._dec_pos)[slot]) == S
    assert int(np.asarray(eng.runner._dec_tokens)[slot]) == 0
    assert eng._slot_pos[slot] == S        # the mirror too
    dry = []
    for _ in range(6):
        eng.step()
        dry.append(len(eng._inflight))
    assert min(dry) >= 1, dry               # never ran dry
    assert not uploads                      # the carry was not replaced
    _longs_are_whole(eng, longs, reference)


@pytest.mark.parametrize("how", ["max_tokens", "stop_id"])
def test_a_first_token_that_ends_its_request(engines, reference, how):
    first = reference(PROMPT, _greedy(3)).output_tokens[0]
    opts = (_greedy(1) if how == "max_tokens" else SamplingOptions(
        temperature=0.0, max_tokens=9, ignore_eos=True,
        stop_token_ids=[first]))
    eng, longs = _running(engines)
    before = eng.eff.report()["step"]["prefill_behind"]
    sid = eng.add_request(PROMPT, opts)
    outs = _finish(eng, [sid])
    assert eng.eff.report()["step"]["prefill_behind"] == before + 1
    mine = [o for o in outs if o.seq_id == sid]
    assert [o.new_token for o in mine] == [first]
    assert mine[0].finished and mine[0].finish_reason == (
        "length" if how == "max_tokens" else "stop")
    assert eng.seqs[sid].output_tokens == [first]
    # the row is parked again and the other streams are whole
    _longs_are_whole(eng, longs, reference)


def _dispatch_behind(eng, prompt, options):
    """Add a request and step ONCE: its chunk is dispatched behind the
    windows in flight and not retired yet (at pipeline_depth 3 two
    windows are ahead of it and the step retires one)."""
    sid = eng.add_request(prompt, options)
    eng.step()
    entries = [e for e in eng._inflight if isinstance(e, _Prefill)]
    assert entries and entries[-1].group[0].seq.seq_id == sid
    assert not eng.seqs[sid].output_tokens
    return sid


def test_abort_between_dispatch_and_retirement(engines, reference):
    eng, longs = _running(engines, depth=3)
    sid = _dispatch_behind(eng, PROMPT, _greedy(30))
    slot = eng.seqs[sid].slot
    assert eng.seqs[sid].status is SeqStatus.RUNNING
    assert eng.abort(sid)
    assert eng._slot_pos[slot] == S
    # the slot serves the next request as any other
    again = eng.add_request(PROMPT, _greedy(5))
    outs = _finish(eng, [again])
    assert not [o for o in outs if o.seq_id == sid]
    assert eng.seqs[sid].output_tokens == []
    assert eng.seqs[again].output_tokens == reference(
        PROMPT, _greedy(5)).output_tokens
    _longs_are_whole(eng, longs, reference)


def test_expiry_between_dispatch_and_retirement(engines, reference):
    """A waiting request's deadline passes while another's chunk is in
    flight: it is dropped, the queue is left as it is, and the streams
    that run are whole."""
    eng, longs = _running(engines, depth=3)
    sid = _dispatch_behind(eng, PROMPT, _greedy(12))
    queued = len(eng._inflight)
    late = eng.add_request(list(range(70, 90)), _greedy(4),
                           deadline=time.monotonic() - 1.0)
    dropped = [o for o in eng.step() if o.seq_id == late]
    assert dropped and dropped[0].finish_reason == "deadline"
    assert len(eng._inflight) >= queued - 1     # one retired, none drained
    _finish(eng, [sid])
    assert eng.seqs[sid].output_tokens == reference(
        PROMPT, _greedy(12)).output_tokens
    _longs_are_whole(eng, longs, reference)


def test_a_prompt_of_several_chunks_never_drains(engines, reference,
                                                 monkeypatch):
    """Three chunks: the first two join nothing and go behind the
    queue, the last one joins its first token to the carry."""
    prompt = [2 + (i * 7) % 190 for i in range(2 * CHUNK + 9)]
    eng, longs = _running(engines)
    edits = []
    inner = eng.runner.edit_carry
    monkeypatch.setattr(eng.runner, "edit_carry", lambda slots, toks, pos: (
        edits.append(list(np.asarray(slots))), inner(slots, toks, pos))[1])
    before = eng.eff.report()["step"]
    sid = eng.add_request(prompt, _greedy(10))
    depth = []
    while not eng.seqs[sid].output_tokens:
        eng.step()
        depth.append(len(eng._inflight))
    after = eng.eff.report()["step"]
    assert after["prefill_behind"] - before["prefill_behind"] == 3
    assert after["prefill_drained"] == before["prefill_drained"]
    assert min(depth) >= 1
    assert edits == [[eng.seqs[sid].slot]]      # one join, no park
    _finish(eng, [sid])
    assert eng.seqs[sid].output_tokens == reference(
        prompt, _greedy(10)).output_tokens
    _longs_are_whole(eng, longs, reference)


# ------------------------------------------------------------ the rule

def _short_by(eng) -> int:
    """Blocks the running rows lack for the longest window that could
    be queued now (what LLMEngine._pool_short sums)."""
    ahead, _ = eng._device_leads()
    return sum(max(0, eng.block_mgr.blocks_for(min(
        s.next_position + ahead + eng.cfg.decode_window + 1, S))
        - len(s.block_ids)) for s in eng.scheduler.running.values())


@pytest.mark.parametrize("reason", DRAIN_REASONS)
def test_each_reason_drains_and_is_counted(engines, reference,
                                           monkeypatch, reason):
    opts, kw, rows, hog = _greedy(8), {}, 3, []
    if reason == "guided":
        opts = SamplingOptions(temperature=0.0, max_tokens=8,
                               guided_regex=r"(aa|bb)")
    elif reason == "shaped":
        opts = SamplingOptions(temperature=0.0, max_tokens=8,
                               ignore_eos=True, presence_penalty=0.5)
    elif reason == "speculation":
        kw = dict(speculative_ngram_tokens=2)
    elif reason == "pressure":
        kw = dict(kv_block_size=8)
    elif reason == "reshape":
        rows = 1    # a carry of batch 1: a second row is a larger bucket
    eng, longs = _running(engines, rows, **kw)
    sid = None
    if reason == "reshape":
        assert eng._carry_batch == 1
    elif reason == "resume":
        # preempted with windows in flight, as kvplane's migrate_out
        # does: its rows in them are discarded, it comes back through a
        # prefill of prompt + emitted output
        sid = longs[0]
        emitted = list(eng.seqs[sid].output_tokens)
        eng._preempt(eng.seqs[sid])
    elif reason == "pressure":
        # a window behind the chunk needs blocks the pool has not got:
        # take all but the prompt's own out of it. The set-up waits on
        # that state (the rows short of blocks for the window behind,
        # the prompt's own and more still free, every long row still
        # running), not on a number of steps, and holds it to be there
        # once the blocks are taken
        own = eng.block_mgr.blocks_for(len(PROMPT) + 1)
        for _ in range(500):
            if (_short_by(eng) > 0 and eng.block_mgr.available > own
                    and len(eng.scheduler.running) == rows):
                break
            eng.step()
        hog = eng.block_mgr.alloc(eng.block_mgr.available - own)
        assert hog and eng.block_mgr.available == own
        assert _short_by(eng) > 0 and eng._inflight
        assert not eng._decode_dirty and eng._carry_batch == 4
    step = eng.eff.report()["step"]
    if sid is None:
        sid = eng.add_request(PROMPT, opts)
    seen = []
    inner = eng._do_prefill
    monkeypatch.setattr(eng, "_do_prefill", lambda works, drained: (
        seen.append((drained, len(eng._inflight))),
        inner(works, drained))[1])
    _step_until(eng, lambda: seen)
    assert seen[0] == (reason, 0)       # the queue was empty before it
    now = eng.eff.report()["step"]
    assert {k: now["prefill_drained"][k] - v for k, v in
            step["prefill_drained"].items()} == {
                k: int(k == reason) for k in DRAIN_REASONS}
    assert now["prefill_behind"] == step["prefill_behind"]
    assert eng.eff.recent_steps(1)[0]["drained_" + reason] == 1
    eng.block_mgr.free(hog)
    _finish(eng, [sid])
    if reason == "resume":
        assert eng.seqs[sid].output_tokens[:len(emitted)] == emitted
    else:
        assert eng.seqs[sid].output_tokens == reference(
            PROMPT, opts, **kw).output_tokens
    _longs_are_whole(eng, longs, reference, **kw)


def test_no_option_selects_and_a_step_behind_books_no_drain(engines):
    assert not [f for f in EngineConfig.__dataclass_fields__
                if "behind" in f or "drain" in f]
    eng, longs = _running(engines)
    phases = eng.eff.report()["step"]["phase_s"]
    sid = eng.add_request(PROMPT, _greedy(6))
    _finish(eng, [sid])
    after = eng.eff.report()["step"]["phase_s"]
    assert after["drain_sync"] == phases["drain_sync"]
    assert after["drain_process"] == phases["drain_process"]
    for name in ("prefill_host", "prefill_dispatch", "prefill_sync",
                 "prefill_process"):
        assert after[name] > phases[name], name
    _finish(eng, longs)


def test_debug_perf_carries_the_counters():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.server import build_app
    engine = AsyncLLMEngine(EngineConfig(
        model="debug-tiny", max_model_len=128, max_num_seqs=2,
        prefill_chunk=16, prefill_buckets=(16,)))

    async def body():
        async with TestClient(TestServer(build_app(engine))) as client:
            for _ in range(2):
                r = await client.post("/v1/completions", json={
                    "model": "debug-tiny", "max_tokens": 4,
                    "temperature": 0.0, "ignore_eos": True,
                    "prompt": "where does the queue go"})
                assert r.status == 200
            return await (await client.get("/debug/perf?limit=1000")).json()
    perf = asyncio.run(body())
    step = perf["totals"]["step"]
    assert set(step["prefill_drained"]) == set(DRAIN_REASONS)
    made = step["prefill_behind"] + sum(step["prefill_drained"].values())
    assert made == perf["totals"]["prefill"]["dispatches"] >= 2
    in_ring = sum(v for e in perf["steps"] for k, v in e.items()
                  if k == "prefill_behind" or k.startswith("drained_"))
    assert in_ring == made
