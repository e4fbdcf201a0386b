"""An engine's start, span by span (ISSUE 52): what a build is made of
(traced, lowered, then compiled OR loaded), what JAX built outside any
build, and the marks of a start.

Tiers:
- unit: ``jax.monitoring``'s events handed to efficiency.BUILD_EVENTS by
  hand, with a build open on an EngineEffAccounting and with none;
- engine: a tiny CPU engine, where the REAL events reach the rows of
  the builds that made them, and a hundred steps from a warm table
  reach nothing;
- server: the ``startup`` block of GET /debug/perf behind
  ``server.main``'s marks, every key the endpoint had still there.
"""

import asyncio
import threading

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine import efficiency
from production_stack_tpu.engine.efficiency import (BUILD_EVENTS,
                                                    BUILD_SECONDS,
                                                    STARTUP_MARKS,
                                                    EngineEffAccounting)

TRACE, LOWER, BACKEND = efficiency.BUILD_PARTS      # jax.monitoring's names
ASKED, HIT = efficiency.CACHE_EVENTS
LOAD, SAVED = efficiency.CACHE_SECONDS


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _acct(**kw):
    return EngineEffAccounting(now_fn=_Clock(5.0), wall_fn=_Clock(1000.0),
                               process_start_unix=(990.0, "test"), **kw)


def timed(event, seconds, inside=()):
    """``event`` as JAX says it: that it began, whatever it timed
    inside itself, then how long it took."""
    BUILD_EVENTS.began(event, 0.0)
    for inner, inner_s in inside:
        timed(inner, inner_s)
    BUILD_EVENTS.lasted(event, seconds)


def feed(cache):
    """One build's events: a trace of 0.5 s with a jitted function of
    0.1 s traced inside it, a lowering of 0.3 s that traced 0.05 s more,
    and 2 s in the back end, where the persistent cache missed, hit or
    was not asked."""
    timed(TRACE, 0.5, inside=[(TRACE, 0.1)])
    timed(LOWER, 0.3, inside=[(TRACE, 0.05)])
    BUILD_EVENTS.began(BACKEND, 0.0)
    if cache != "none":
        BUILD_EVENTS.happened(ASKED)
    if cache == "hit":
        BUILD_EVENTS.happened(HIT)
        BUILD_EVENTS.lasted(SAVED, 40.0)
        BUILD_EVENTS.lasted(LOAD, 1.5)
    BUILD_EVENTS.lasted(BACKEND, 2.0)


def build(acct, cache, key=("decode", 8, 512, 4), wall=3.0):
    kind, window, kv, batch = key
    acct.compile_started(kind, window, kv, batch)
    feed(cache)
    acct.compile_finished(kind, window, kv, started_at=5.0, dur_s=wall,
                          batch=batch)


# ------------------------------------------------------------ unit tier

@pytest.mark.parametrize("cache,hit", [("miss", False), ("hit", True),
                                       ("none", None)])
def test_events_land_in_the_open_builds_row_and_in_the_totals(cache, hit):
    acct = _acct()
    build(acct, cache)
    r = acct.report()
    row = r["compiles"]["decode|8|512|4"]
    assert (row["count"], row["seconds"]) == (1, 3.0)   # as they were
    # nested seconds are booked once: 0.5 holds its inner 0.1, and the
    # 0.05 traced while lowering is trace's, not lowering's
    assert row["trace_s"] == pytest.approx(0.55)
    assert row["lower_s"] == pytest.approx(0.25)
    assert row["backend_s"] == pytest.approx(2.0)
    assert row["other_s"] == pytest.approx(0.2)
    assert row["cache_hit"] is hit
    assert row["cache_load_s"] == (1.5 if hit else 0.0)
    assert row["saved_s"] == (40.0 if hit else 0.0)
    (entry,) = acct.recent_compiles()
    assert entry["duration_s"] == 3.0 and entry["kind"] == "decode"
    assert {k: entry[k] for k in row if k not in ("count", "seconds")} \
        == {k: v for k, v in row.items() if k not in ("count", "seconds")}
    b = r["builds"]
    assert (b["count"], b["hits"], b["misses"]) == (
        1, int(hit is True), int(hit is False))
    assert b["backend_hit_s"] == (2.0 if hit else 0.0)
    assert b["backend_miss_s"] == (0.0 if hit else 2.0)
    assert b["cache_load_s"] == row["cache_load_s"]
    assert b["unattributed"] == dict.fromkeys(
        ("events", "seconds", "trace_s", "lower_s", "backend_s", "hits",
         "misses"), 0)


def test_a_second_build_of_a_key_adds_to_its_row():
    acct = _acct()
    build(acct, "miss")
    build(acct, "hit")
    row = acct.report()["compiles"]["decode|8|512|4"]
    assert (row["count"], row["seconds"]) == (2, 6.0)
    assert row["trace_s"] == pytest.approx(1.1)
    assert row["backend_s"] == pytest.approx(4.0)
    assert row["cache_hit"] is True         # the latest build's
    assert len(acct.recent_compiles()) == 2


def test_an_event_with_no_build_open_is_unattributed_and_nowhere_else():
    acct = _acct()
    build(acct, "miss")
    before = acct.report()
    feed("hit")     # the weights' init, a jit at its first call, ...
    feed("miss")
    r = acct.report()
    assert r["compiles"] == before["compiles"]
    assert {k: v for k, v in r["builds"].items() if k != "unattributed"} \
        == {k: v for k, v in before["builds"].items()
            if k != "unattributed"}
    u = r["builds"]["unattributed"]
    assert u == {"events": 10, "seconds": pytest.approx(5.6),
                 "trace_s": pytest.approx(1.1),
                 "lower_s": pytest.approx(0.5),
                 "backend_s": pytest.approx(4.0), "hits": 1, "misses": 1}


def test_events_before_any_accounting_are_kept_for_the_next():
    """An engine draws its weights before its accounting exists: what
    JAX says meanwhile is the next accounting's, not lost."""
    events = efficiency.BuildEvents()       # nobody follows it yet
    events.began(BACKEND, 0.0)
    events.happened(ASKED)
    events.lasted(BACKEND, 9.0)
    events.began(TRACE, 0.0)
    events.lasted(TRACE, 0.5)
    acct, later = _acct(), _acct()
    events.follow(acct)
    events.follow(later)
    assert acct.report()["builds"]["unattributed"] == {
        "events": 2, "seconds": 9.5, "trace_s": 0.5, "lower_s": 0.0,
        "backend_s": 9.0, "hits": 0, "misses": 1}
    assert later.report()["builds"]["unattributed"]["events"] == 0
    events.began(LOWER, 0.0)
    events.lasted(LOWER, 0.25)      # both are alive: both are told
    for a in (acct, later):
        assert a.report()["builds"]["unattributed"]["lower_s"] == 0.25


def test_another_threads_events_stay_out_of_the_open_build():
    acct = _acct()
    acct.compile_started("prefill", 64, 256, 2)
    other = threading.Thread(target=feed, args=("miss",))
    other.start()
    other.join(timeout=30)
    assert not other.is_alive()
    timed(TRACE, 0.25)
    acct.compile_finished("prefill", 64, 256, started_at=5.0, dur_s=1.0,
                          batch=2)
    b = acct.report()["builds"]
    assert (b["trace_s"], b["backend_miss_s"]) == (0.25, 0.0)
    assert b["unattributed"]["events"] == 5
    assert b["unattributed"]["backend_s"] == pytest.approx(2.0)


SEQUENCES = {
    "one of each": ["miss", "hit", "none"],
    "hits alone": ["hit"] * 4,
    "events between builds": ["miss", None, "hit", None, None, "none"],
}


@pytest.mark.parametrize("name", SEQUENCES)
def test_the_parts_of_the_builds_add_up_to_their_wall(name):
    acct = _acct()
    walls = 0.0
    for i, cache in enumerate(SEQUENCES[name]):
        if cache is None:
            feed("miss")
            continue
        wall = 2.9 + 0.37 * i
        walls += wall
        build(acct, cache, key=("decode", 8, 512 << i, 4), wall=wall)
    r = acct.report()
    b = r["builds"]
    assert set(b) == {"count", "hits", "misses", "unattributed",
                      *BUILD_SECONDS}
    assert b["wall_s"] == pytest.approx(walls)
    assert b["wall_s"] == pytest.approx(
        b["trace_s"] + b["lower_s"] + b["backend_miss_s"]
        + b["backend_hit_s"] + b["other_s"], abs=1e-9)
    assert b["wall_s"] == pytest.approx(r["compile_s_total"])
    assert b["count"] == r["compiles_total"] == len(r["compiles"])
    asked = [c for c in SEQUENCES[name] if c in ("miss", "hit")]
    assert b["hits"] + b["misses"] == len(asked)
    for row in r["compiles"].values():
        assert row["seconds"] == pytest.approx(
            row["trace_s"] + row["lower_s"] + row["backend_s"]
            + row["other_s"])


def test_before_serving_is_frozen_and_the_two_sides_add_up():
    acct = _acct()
    assert acct.startup_report()["before_serving"] is None
    assert acct.startup_report()["after_serving"] is None
    build(acct, "miss")
    feed("none")
    acct.mark("serving")
    frozen = acct.startup_report()["before_serving"]
    assert frozen == acct.report()["builds"]
    build(acct, "hit", key=("prefill", 64, 256, 1), wall=4.0)   # the probe
    feed("hit")
    s, now = acct.startup_report(), acct.report()["builds"]
    assert s["before_serving"] == frozen
    after = s["after_serving"]
    assert (after["count"], after["hits"], after["wall_s"]) == (1, 1, 4.0)
    assert after["unattributed"]["hits"] == 1
    for key, value in now.items():
        if key == "unattributed":
            for k, v in value.items():
                assert v == pytest.approx(frozen[key][k] + after[key][k])
        else:
            assert value == pytest.approx(frozen[key] + after[key])


@pytest.mark.parametrize("mark", STARTUP_MARKS)
def test_a_mark_is_null_until_reached_and_then_never_moves(mark):
    wall = _Clock(1000.0)
    acct = EngineEffAccounting(wall_fn=wall,
                               process_start_unix=(990.0, "test"))
    s = acct.startup_report()
    assert s["marks"] == dict.fromkeys(STARTUP_MARKS)
    assert (s["process_start_unix"], s["process_start_source"]) == (
        990.0, "test")
    assert s["spans"] == {"weights_s": None, "cache_alloc_s": None}
    if mark == "main":      # stamped before the accounting existed
        acct.mark(mark, at_unix=992.5)
    else:
        wall.t = 992.5
        acct.mark(mark)
    wall.t = 1100.0
    acct.mark(mark)
    acct.mark(mark, at_unix=5.0)
    marks = acct.startup_report()["marks"]
    assert marks.pop(mark) == 2.5
    assert set(marks.values()) == {None}


def test_the_process_start_is_the_operating_systems():
    import time

    from production_stack_tpu import IMPORTED_UNIX
    started, source = efficiency.process_start(IMPORTED_UNIX)
    assert source == "proc_stat"        # Linux gives one here
    assert 0 <= IMPORTED_UNIX - started < 600
    assert started < time.time()
    # a start time after the package's import cannot be this process's
    assert efficiency.process_start(started - 60.0) == (
        started - 60.0, "package_import")
    acct = EngineEffAccounting()
    assert acct.process_start_source == "proc_stat"
    assert acct.process_start_unix == pytest.approx(started, abs=0.1)


# ---------------------------------------------------------- engine tier

@pytest.fixture(scope="module")
def async_engine():
    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.config import EngineConfig
    return AsyncLLMEngine(EngineConfig(
        model="debug-tiny", max_model_len=256, max_num_seqs=2,
        prefill_chunk=16, prefill_buckets=(16,), decode_window=4))


@pytest.fixture(scope="module")
def engine(async_engine):
    """The LLMEngine inside, stepped by the tests themselves: its
    thread starts with the server tier's app."""
    return async_engine.engine


TOKENS = 220        # a request: some sixty steps through four kv buckets


def _generate(engine, tokens):
    from production_stack_tpu.engine.scheduler import SamplingOptions
    engine.generate("what is a start made of", SamplingOptions(
        temperature=0.0, max_tokens=tokens, ignore_eos=True))


@pytest.fixture(scope="module")
def warmed(engine):
    """The engine after one request: its table holds every executable
    a request of that shape reaches."""
    assert engine.eff.startup_report()["marks"]["first_request"] is None
    _generate(engine, TOKENS)
    return engine.eff.report()


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_real_events_reach_the_rows_of_the_builds_that_made_them(
        warmed, kind):
    rows = {k: v for k, v in warmed["compiles"].items()
            if k.startswith(kind + "|")}
    assert rows
    for row in rows.values():
        assert row["trace_s"] > 0 and row["lower_s"] > 0
        assert row["backend_s"] > 0
        # conftest.py gives the run a persistent cache: it was asked
        assert row["cache_hit"] in (True, False)
        assert row["seconds"] == pytest.approx(
            row["trace_s"] + row["lower_s"] + row["backend_s"]
            + row["other_s"], abs=1e-3)
        assert 0 <= row["other_s"] < row["seconds"]


def test_the_real_totals_add_up_and_the_weights_are_unattributed(
        engine, warmed):
    b = warmed["builds"]
    assert b["count"] == warmed["compiles_total"] >= 3
    assert b["hits"] + b["misses"] == b["count"]
    assert b["wall_s"] == pytest.approx(warmed["compile_s_total"], abs=1e-3)
    # llama.init_params' jits ran before any build was open
    assert b["unattributed"]["events"] > 0
    assert b["unattributed"]["seconds"] > 0
    spans = engine.eff.startup_report()["spans"]
    assert spans["weights_s"] > 0 and spans["cache_alloc_s"] > 0
    marks = engine.eff.startup_report()["marks"]
    assert marks["first_request"] > 0       # add_request stamped it
    assert marks["main"] is None            # no server here


def test_a_hundred_steps_from_a_warm_table_reach_no_listener(engine, warmed):
    """(C): the request below runs the executables ``warmed`` built, so
    neither ``totals.builds`` nor the count of calls JAX made into the
    listeners moves, and the accounting's build hooks are not entered."""
    calls, before = BUILD_EVENTS.calls, engine.eff.report()
    entered = []
    engine.eff.compile_started = lambda *a, **k: entered.append(a)
    try:
        _generate(engine, TOKENS)
        _generate(engine, TOKENS)
    finally:
        del engine.eff.compile_started      # the class's again
    after = engine.eff.report()
    assert after["step"]["steps"] - before["step"]["steps"] >= 100
    assert after["decode"]["windows"] > before["decode"]["windows"]
    assert entered == []
    assert after["builds"] == before["builds"]
    assert after["compiles"] == before["compiles"]
    assert BUILD_EVENTS.calls == calls


# ---------------------------------------------------------- server tier

# what GET /debug/perf answered before this PR, key by key
HAD = {
    "top": {"device", "totals", "rates", "windows", "steps", "loop",
            "compiles", "kv_pool"},
    "totals": {"decode", "prefill", "bytes_total", "bytes_effective",
               "compiles_total", "compile_s_total", "compile_in_flight",
               "compiles", "weight_bytes", "kv_position_bytes",
               "hbm_peak_bytes_per_s", "step", "loop"},
    "compiles_row": {"count", "seconds"},
    "compiles_entry": {"at", "at_unix", "duration_s", "kind", "window",
                       "kv_bucket", "batch"},
}
NEW_PARTS = {"trace_s", "lower_s", "backend_s", "cache_hit",
             "cache_load_s", "saved_s", "other_s"}


@pytest.fixture(scope="module")
def served(async_engine, warmed):
    """GET /debug/perf of a server that reached ``serving`` the way
    ``server.main`` marks it."""
    from production_stack_tpu.engine.server import build_app
    eff = async_engine.engine.eff
    eff.mark("main", eff.process_start_unix + 1.25)
    eff.mark("engine_built")
    eff.mark("serving")

    async def body():
        async with TestClient(TestServer(build_app(async_engine))) as client:
            r = await client.get("/debug/perf?limit=1000")
            assert r.status == 200
            return await r.json()
    return asyncio.run(body()), eff.startup_sentence()


def test_every_key_debug_perf_had_is_still_there(served):
    perf, _ = served
    assert HAD["top"] <= set(perf) and set(perf) - HAD["top"] == {"startup"}
    assert HAD["totals"] <= set(perf["totals"])
    assert set(perf["totals"]) - HAD["totals"] == {"builds"}
    for row in perf["totals"]["compiles"].values():
        assert set(row) == HAD["compiles_row"] | NEW_PARTS
    assert len(perf["compiles"]) == perf["totals"]["compiles_total"]
    for entry in perf["compiles"]:
        assert set(entry) == HAD["compiles_entry"] | NEW_PARTS


def test_the_startup_block_of_debug_perf(served):
    perf, sentence = served
    s = perf["startup"]
    assert set(s) == {"process_start_unix", "process_start_source",
                      "marks", "spans", "before_serving", "after_serving"}
    m = s["marks"]
    assert m["main"] == 1.25
    assert m["main"] < m["first_request"] < m["engine_built"] <= m["serving"]
    assert s["before_serving"] == perf["totals"]["builds"]
    assert s["after_serving"]["wall_s"] == 0
    assert s["after_serving"]["unattributed"]["seconds"] == 0
    # the log line says the same in a sentence
    b = s["before_serving"]
    for said in (f"serving {m['serving']} s", f"main at {m['main']} s",
                 f"weights {s['spans']['weights_s']} s",
                 f"{b['count']} builds in {b['wall_s']} s",
                 f"({b['hits']} loaded, {b['misses']} compiled)",
                 f"trace {b['trace_s']} s", f"lower {b['lower_s']} s",
                 f"outside any build {b['unattributed']['seconds']} s"):
        assert said in sentence, (said, sentence)
