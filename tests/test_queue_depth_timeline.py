"""The device queue's depth as the device reports it, and the starved
seconds repaired with it (ISSUE 38).

Since a prefill joins the device queue (ISSUE 36) an entry is always
queued behind the one being synced, so the rule "starved from a sync
that emptied the host's list" never fires again, whether or not the
device has finished what the host still counts as outstanding. The
timeline now asks the device (``queue_depth``, the engine's
``jax.Array.is_ready`` walk) at the close of every phase, at the open
of every step and at the open of every dispatching phase.

Tiers:
- unit: EngineEffAccounting with an injected clock and depth callable;
- engine: a debug-tiny LLMEngine on the CPU with real arrays: a host
  that sleeps past a window's end dispatches into a dry queue, one that
  does not finds its windows still running.
"""

import time

import pytest

from production_stack_tpu.engine.efficiency import (DEPTH_KEYS,
                                                    HOST_WORK_PHASES,
                                                    STEP_PHASES,
                                                    EngineEffAccounting)


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


class _Device:
    """A device queue by script: ``depth`` is what the device has not
    finished; the test moves it as the device would."""

    def __init__(self, depth=0):
        self.depth = depth
        self.asked = 0

    def __call__(self):
        self.asked += 1
        return self.depth


def _acct(depth=0, **kw):
    clock, device = _Clock(), _Device(depth)
    return (EngineEffAccounting(now_fn=clock, cpu_fn=clock,
                                queue_depth=device, **kw), clock, device)


def _dispatch(acct, clock, device, seconds=0.001, name="decode_dispatch"):
    with acct.phase(name, dispatches=True):
        clock.t += seconds
    device.depth += 1


# ------------------------------------------------------------ unit tier

def test_the_four_depth_keys_are_always_there():
    acct, _, _ = _acct()
    assert acct.report()["step"]["dispatch_depth"] == dict.fromkeys(
        DEPTH_KEYS, 0)
    assert DEPTH_KEYS == ("0", "1", "2", "3_or_more")
    # ... and without a device to ask nothing is booked, nothing raised
    bare = EngineEffAccounting(now_fn=_Clock())
    with bare.step(), bare.phase("decode_dispatch", dispatches=True):
        pass
    assert bare.report()["step"]["dispatch_depth"] == dict.fromkeys(
        DEPTH_KEYS, 0)


@pytest.mark.parametrize("found, key", [(0, "0"), (1, "1"), (2, "2"),
                                        (3, "3_or_more"), (7, "3_or_more")])
def test_a_dispatch_is_counted_at_the_depth_it_found(found, key):
    acct, clock, device = _acct(found)
    with acct.step():
        _dispatch(acct, clock, device)
    want = dict.fromkeys(DEPTH_KEYS, 0)
    want[key] = 1
    assert acct.report()["step"]["dispatch_depth"] == want
    (entry,) = acct.recent_steps()
    assert entry.get("dry_dispatches") == (1 if found == 0 else None)


def test_prefill_dispatches_are_counted_too():
    acct, clock, device = _acct(1)
    with acct.step():
        _dispatch(acct, clock, device, name="prefill_dispatch")
        _dispatch(acct, clock, device)
    assert acct.report()["step"]["dispatch_depth"] == {
        "0": 0, "1": 1, "2": 1, "3_or_more": 0}


def test_idle_from_the_first_boundary_that_sees_the_queue_finished():
    """Two entries stay on the host's list all along (no sync empties
    it): the device finishes them in the middle of decode_process. The
    boundary that ends that phase is the first to see depth 0; from it
    to the end of the next dispatch the device is starved, and the
    dispatching phase clears the state."""
    acct, clock, device = _acct(0)
    with acct.step():
        _dispatch(acct, clock, device)
        _dispatch(acct, clock, device)
    assert acct._idle_since is None
    before = acct.report()["step"]["starved_s"]
    with acct.step():
        with acct.phase("decode_sync"):
            clock.t += 0.010
        assert acct._idle_since is None         # the device is at work
        with acct.phase("decode_process"):
            clock.t += 0.002
            device.depth = 0                    # ... finishes here
            clock.t += 0.003
        assert acct._idle_since == pytest.approx(clock.t)
        with acct.phase("decode_host"):
            clock.t += 0.004
            _dispatch(acct, clock, device, 0.001)
            assert acct._idle_since is None
            clock.t += 0.002
    step = acct.report()["step"]
    # late by the 3 ms of the phase it finished in, and no more
    assert step["starved_s"] - before == pytest.approx(0.005, abs=1e-6)
    assert step["starved_by_phase"]["decode_host"] == pytest.approx(0.004)
    # this dispatch's millisecond, and the lead-in's first (its second
    # found the first one running)
    assert step["starved_by_phase"]["decode_dispatch"] == pytest.approx(
        0.001 + 0.001, abs=1e-6)
    assert step["dispatch_depth"]["0"] == 2     # the lead-in's and this
    assert acct.recent_steps(1)[0]["starved_s"] == pytest.approx(0.005)


def test_never_idle_inside_the_wait_for_work():
    """Nothing waits, so nobody starves: the wait for work neither sets
    the idle stamp nor asks the device, and what it leaves set it moves
    up to its own end."""
    acct, clock, device = _acct(0)
    with acct.step():
        _dispatch(acct, clock, device)
        with acct.phase("decode_sync"):
            clock.t += 0.01
            device.depth = 0
    asked = device.asked
    assert acct._idle_since is not None         # the sync's end saw 0
    acct._idle_since = None                     # as if it had not
    for _ in range(3):
        with acct.phase("no_work"):
            clock.t += 0.2
        assert acct._idle_since is None
    assert device.asked == asked
    before = acct.report()["step"]["starved_s"]
    with acct.step():                           # work arrived
        assert acct._idle_since is not None
        clock.t += 0.004
        _dispatch(acct, clock, device, 0.001)
    assert acct.report()["step"]["starved_s"] - before == pytest.approx(
        0.005, abs=1e-6)
    assert acct.report()["step"]["starved_by_phase"].get("no_work") is None


def _two_windows_a_step(with_device: bool) -> float:
    """The loop since ISSUE 36: a window is always queued behind the one
    being synced, and once a sync empties the host's list (the tail of
    a burst). The device runs dry for 6 ms in every walk."""
    clock, device = _Clock(), _Device()
    acct = EngineEffAccounting(now_fn=clock, cpu_fn=clock,
                               queue_depth=device if with_device else None)
    with acct.step():
        _dispatch(acct, clock, device)
        _dispatch(acct, clock, device)
    for _ in range(5):
        with acct.step():
            with acct.phase("decode_sync"):
                clock.t += 0.010
                device.depth = 1
            with acct.phase("decode_process"):
                device.depth = 0            # the queued window was short
                clock.t += 0.006
            with acct.phase("decode_host"):
                _dispatch(acct, clock, device)
                _dispatch(acct, clock, device)
    with acct.step():
        for _ in range(2):
            with acct.phase("decode_sync"):
                clock.t += 0.010
                device.depth -= 1
        acct.device_idle()                  # the list is empty
        with acct.phase("decode_process"):
            clock.t += 0.006
        with acct.phase("decode_host"):
            _dispatch(acct, clock, device)
    return acct.report()["step"]["starved_s"]


def test_never_less_than_the_sync_emptied_rule_alone():
    blind = _two_windows_a_step(with_device=False)
    seeing = _two_windows_a_step(with_device=True)
    # the old rule sees the burst's tail and the lead-in alone
    assert blind == pytest.approx(0.006 + 0.001 + 0.001, abs=1e-6)
    # asked at every boundary, each walk's boundary sees the dry queue:
    # 1 ms of dispatch a step more, late by the walk it ran dry in
    assert seeing >= blind
    assert seeing == pytest.approx(blind + 5 * 0.001, abs=1e-6)


# every phase a duration of its own, as test_step_timeline has them
PHASE_SECONDS = {name: 0.001 * (i + 1) * (i + 2)
                 for i, name in enumerate(STEP_PHASES)}


def _scripted(acct, clock) -> None:
    """test_step_timeline's scripted step (nested phases, a compile
    inside the decode dispatch, seconds of the step outside any phase),
    with the compile's start reported before its seconds pass, as the
    runner reports it."""
    d = PHASE_SECONDS

    def spend(name):
        with acct.phase(name):
            clock.t += d[name]

    spend("no_work")
    clock.t += d["between_steps"]
    with acct.step():
        for name in ("expire", "schedule", "drain_sync", "drain_process"):
            spend(name)
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
                acct.compile_started("decode", 8, 128, 2)
                clock.t += d["compile"]
                acct.compile_finished("decode", 8, 128, t0, d["compile"],
                                      2)
                clock.t += d["decode_dispatch"]
        spend("decode_sync")
        spend("decode_process")
        clock.t += d["housekeeping"] / 2
        with acct.phase("housekeeping"):
            clock.t += d["housekeeping"] / 2


@pytest.fixture(scope="module")
def three_quarters_on_the_processor():
    """A thread clock that runs at 3/4 of the wall clock."""
    clock = _Clock()
    acct = EngineEffAccounting(now_fn=clock,
                               cpu_fn=lambda: 0.75 * clock.t)
    _scripted(acct, clock)
    return acct


@pytest.mark.parametrize("phase", STEP_PHASES)
def test_cpu_and_offcpu_seconds_add_up_to_the_phase(
        three_quarters_on_the_processor, phase):
    """Processor seconds beside wall seconds: a quarter of every phase
    is off the processor; nested spans and a compile are taken out of a
    phase's processor seconds as they are of its wall seconds, and the
    gap between two steps is read at the same two stamps."""
    step = three_quarters_on_the_processor.report()["step"]
    assert set(step["cpu_s"]) == set(step["offcpu_s"]) == set(STEP_PHASES)
    assert step["phase_s"][phase] == pytest.approx(PHASE_SECONDS[phase],
                                                   abs=2e-6)
    assert step["cpu_s"][phase] == pytest.approx(
        0.75 * PHASE_SECONDS[phase], abs=2e-6)
    assert step["cpu_s"][phase] + step["offcpu_s"][phase] == pytest.approx(
        step["phase_s"][phase], abs=1e-9)


def test_a_steps_entry_sums_the_host_phases_off_the_processor(
        three_quarters_on_the_processor):
    (entry,) = three_quarters_on_the_processor.recent_steps()
    assert "decode_sync" not in HOST_WORK_PHASES
    assert entry["offcpu_s"] == pytest.approx(0.25 * sum(
        v for k, v in PHASE_SECONDS.items() if k in HOST_WORK_PHASES),
        abs=1e-5)


def test_a_thread_clock_ahead_of_the_wall_reads_no_negative_seconds():
    clock = _Clock()
    acct = EngineEffAccounting(now_fn=clock, cpu_fn=lambda: 1.5 * clock.t)
    with acct.step(), acct.phase("schedule"):
        clock.t += 0.01
    step = acct.report()["step"]
    assert step["cpu_s"]["schedule"] == step["phase_s"]["schedule"]
    assert step["offcpu_s"]["schedule"] == 0.0


def test_a_thread_clock_that_ticks_coarsely_is_right_over_many_spans():
    """Where the chip's machines run (gVisor) ``time.thread_time``
    moves in ticks of 10 ms: a span of 2 ms reads 0 or 10. Nothing is
    clamped span by span, so 500 spans of 2 ms, all on the processor,
    sum to their second within a tick, and a phase that waits half its
    time reads half."""
    clock = _Clock()

    def coarse():
        return int((clock.t - 100.0) / 0.01) * 0.01      # whole ticks

    acct = EngineEffAccounting(now_fn=clock, cpu_fn=coarse)
    for _ in range(500):
        with acct.step(), acct.phase("decode_process"):
            clock.t += 0.002
    step = acct.report()["step"]
    assert step["phase_s"]["decode_process"] == pytest.approx(1.0)
    assert step["offcpu_s"]["decode_process"] <= 0.0101
    assert step["cpu_s"]["decode_process"] + step["offcpu_s"][
        "decode_process"] == pytest.approx(1.0, abs=1e-9)
    # a single step is only as fine as the tick, and never negative
    assert all(e["offcpu_s"] >= 0 for e in acct.recent_steps(500))


# ---------------------------------------------------------- engine tier

@pytest.fixture(scope="module")
def engine():
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions
    eng = LLMEngine(EngineConfig(
        model="debug-tiny", max_model_len=512, max_num_seqs=4,
        prefill_chunk=32, prefill_buckets=(32,), decode_window=8,
        pipeline_depth=2))
    # compile everything the runs below dispatch
    sid = eng.add_request(list(range(5, 25)), SamplingOptions(
        temperature=0.0, max_tokens=40, ignore_eos=True))
    while eng.seqs[sid].finish_reason is None:
        eng.step()
    return eng


def _run(eng, sleep_s: float, tokens: int = 160):
    """One request decoded to its end, the host pausing ``sleep_s``
    after every step; returns what the counters moved by from the
    second window on (the first dispatch of a run finds nothing
    queued, by definition)."""
    from production_stack_tpu.engine.scheduler import SamplingOptions
    sid = eng.add_request(list(range(7, 27)), SamplingOptions(
        temperature=0.0, max_tokens=tokens, ignore_eos=True))
    while len(eng.seqs[sid].output_tokens) < 16:
        eng.step()
    before = eng.eff.report()["step"]
    n0 = len(eng.seqs[sid].output_tokens)
    while eng.seqs[sid].finish_reason is None:
        eng.step()
        if eng._queue_tail is not None and sleep_s:
            eng._queue_tail.block_until_ready()
            time.sleep(sleep_s)
    after = eng.eff.report()["step"]
    depth = {k: after["dispatch_depth"][k] - before["dispatch_depth"][k]
             for k in DEPTH_KEYS}
    return depth, after["starved_s"] - before["starved_s"], \
        len(eng.seqs[sid].output_tokens) - n0


def test_depth_is_the_devices_own_answer(engine):
    assert engine._device_queue_depth() == 0 and not engine._inflight
    from production_stack_tpu.engine.scheduler import SamplingOptions
    sid = engine.add_request(list(range(9, 29)), SamplingOptions(
        temperature=0.0, max_tokens=64, ignore_eos=True))
    while len(engine.seqs[sid].output_tokens) < 8:
        engine.step()
    assert engine._inflight
    engine._queue_tail.block_until_ready()
    # on the host's list still, finished on the device
    assert engine._device_queue_depth() == 0 and engine._inflight
    assert engine._queue_tail is None
    while engine.seqs[sid].finish_reason is None:
        engine.step()


def test_a_host_that_sleeps_past_a_windows_end_dispatches_dry(engine):
    depth, starved, tokens = _run(engine, sleep_s=0.02)
    dispatches = sum(depth.values())
    assert dispatches >= tokens // 8 - 2
    # every step finds its windows finished: its first dispatch is dry
    assert depth["0"] >= dispatches // 2 - 1, depth
    # a lower bound: from the boundary that first saw the queue dry
    # (the step's open: the pause lies between two steps) to the end
    # of the dispatch, not the pause itself
    assert starved > 0
    dry = [e for e in engine.eff.recent_steps(200)
           if e.get("dry_dispatches")]
    assert len(dry) >= depth["0"] - 1


def test_a_host_that_keeps_up_finds_its_windows_running(engine):
    """The control: with a window queued behind the one being synced
    the device is still at work when the next is dispatched. On a CPU
    whose worker threads are shared with five other test files a window
    can end early now and then, so the claim is the share, against the
    sleeping host's on the same engine."""
    slept, _, _ = _run(engine, sleep_s=0.02)
    kept, starved, _ = _run(engine, sleep_s=0.0)
    assert sum(kept.values()) > 0
    assert kept["0"] / sum(kept.values()) < slept["0"] / sum(slept.values())
    assert kept["0"] <= sum(kept.values()) // 3, kept


def test_the_device_is_asked_at_most_once_a_millisecond():
    """A step closes thirty phases, most of them microseconds long; on
    the chip one answer of the device costs several. Closes less than
    LOOK_EVERY_S after the last question do not ask; a dispatch always
    does (it is counted)."""
    from production_stack_tpu.engine.efficiency import LOOK_EVERY_S
    acct, clock, device = _acct(1)
    with acct.step():
        _dispatch(acct, clock, device)
        asked = device.asked
        for _ in range(20):
            with acct.phase("schedule"):
                clock.t += LOOK_EVERY_S / 10
        assert device.asked - asked == 2
        device.depth = 0
        with acct.phase("expire"):
            clock.t += LOOK_EVERY_S
        assert acct._idle_since == pytest.approx(clock.t)
        asked = device.asked
        _dispatch(acct, clock, device, LOOK_EVERY_S / 10)
        assert device.asked == asked + 1
