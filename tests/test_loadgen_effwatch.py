"""Effwatch rig (loadgen effwatch): contract units, the fake engine's
synthetic perf block, router-side perf parsing, and the engine-free
smokes.

Tiers:
- units — effwatch_violations over synthetic records (each gate trips
  independently), CLI defaults;
- fake perf lever — POST /fault {"perf": {...}} drives the synthetic
  pad/dead fractions, compile counters, and the sum-skew knob; /load
  and /metrics tell the same story;
- router parsing — a real EngineStatsScraper scrape of a fake's /load
  lands the perf signals in router EngineStats;
- rig — fake-engine effwatch smoke (reconciliation holds), the
  anti-vacuity mis-sized window MUST fail reconciliation, and the
  sum-skew knob MUST fail the sum-to-1 gate. The real-engine audit
  stays behind ``slow`` (the committed EFF_r15.json is produced by
  benchmarks/run_effwatch.sh).
"""

import asyncio
import copy

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.loadgen.effwatch import (effwatch_violations,
                                                   run_effwatch)
from tests.fake_engine import FakeEngine


# ------------------------------------------------------------ units

def _clean_record():
    return {
        "value": 100.0,
        "detail": {
            "errors": 0,
            "error_samples": [],
            "deltas": {"real": 1000, "pad": 500, "dead": 100,
                       "token_steps_total": 1600, "windows": 10,
                       "compiles_total": 0},
            "accounted_decode_tokens": 1000,
            "client_decode_tokens": 1020,
        },
    }


def test_violations_clean_record_passes():
    assert effwatch_violations(_clean_record()) == []


def test_violations_catch_each_gate():
    # sum-to-1: kinds drift from the independent total
    rec = _clean_record()
    rec["detail"]["deltas"]["token_steps_total"] = 2000
    assert any("sum to the independent total" in v
               for v in effwatch_violations(rec))
    # reconciliation: accounted diverges from client-measured
    rec = _clean_record()
    rec["detail"]["accounted_decode_tokens"] = 1500
    assert any("diverge" in v for v in effwatch_violations(rec))
    # steady-window compile silence
    rec = _clean_record()
    rec["detail"]["deltas"]["compiles_total"] = 2
    assert any("compile events landed" in v
               for v in effwatch_violations(rec))
    # errors
    rec = _clean_record()
    rec["detail"]["errors"] = 3
    assert any("client-visible errors" in v
               for v in effwatch_violations(rec))
    # empty window
    rec = _clean_record()
    rec["detail"]["deltas"].update(real=0, pad=0, dead=0,
                                   token_steps_total=0)
    rec["detail"]["accounted_decode_tokens"] = 0
    assert any("no decode token-steps" in v
               for v in effwatch_violations(rec))
    # tolerance is honored
    rec = _clean_record()
    rec["detail"]["accounted_decode_tokens"] = 960   # 5.9% off
    assert effwatch_violations(rec, rate_tolerance=0.10) == []
    assert any("diverge" in v
               for v in effwatch_violations(rec, rate_tolerance=0.02))


def test_cli_defaults():
    from production_stack_tpu.loadgen.__main__ import build_parser
    args = build_parser().parse_args(["effwatch"])
    assert args.engine == "debug-tiny"
    assert args.duration == 20.0 and args.warmup == 8.0
    assert args.sum_tolerance == 0.02
    assert args.rate_tolerance == 0.10
    assert not args.anti_vacuity
    # r17 A/B knobs
    assert not args.ab and not args.no_window_adapt
    assert args.live_floor == 0.80
    assert args.improve_floor == 0.20
    assert args.stagger == 0.0 and args.mixed_tokens is None


def _ab_record(adapt_live=0.85, control_live=0.50, rate_a=130.0,
               rate_c=100.0):
    def side(live, rate):
        real = int(round(1000 * live))
        return {
            "errors": 0, "error_samples": [],
            "deltas": {"real": real, "pad": 1000 - real - 50,
                       "dead": 50, "token_steps_total": 1000,
                       "windows": 10, "compiles_total": 0},
            "accounted_decode_tokens": real,
            "client_decode_tokens": real,
            "accounted_decode_tokens_per_s": rate,
            "live_fraction_window": live,
        }
    return {"detail": {
        "adapt": side(adapt_live, rate_a),
        "control": side(control_live, rate_c),
        "accounted_decode_tokens_per_s_adapt": rate_a,
        "accounted_decode_tokens_per_s_control": rate_c,
    }}


def test_ab_rejects_contradictory_flags():
    """--anti-vacuity has no A/B semantics and --no-window-adapt IS
    the control side --ab already runs; silently dropping either
    would let a PASSED banner masquerade as something it is not."""
    from production_stack_tpu.loadgen.__main__ import (build_parser,
                                                       cmd_effwatch)
    for extra in ("--anti-vacuity", "--no-window-adapt"):
        args = build_parser().parse_args(["effwatch", "--ab", extra])
        assert cmd_effwatch(args) == 2, extra


def test_ab_violations_clean_and_each_gate():
    from production_stack_tpu.loadgen.effwatch import (
        effwatch_ab_violations)
    assert effwatch_ab_violations(_ab_record()) == []
    # adapt live fraction below the floor
    v = effwatch_ab_violations(_ab_record(adapt_live=0.7))
    assert any("below the 0.8 floor" in x for x in v), v
    # directionality: adapt must beat the control
    v = effwatch_ab_violations(_ab_record(adapt_live=0.85,
                                          control_live=0.86))
    assert any("does not beat the control" in x for x in v), v
    # throughput improvement floor
    v = effwatch_ab_violations(_ab_record(rate_a=110.0, rate_c=100.0))
    assert any("improved only" in x for x in v), v
    # a per-side gate trips with its side named
    rec = _ab_record()
    rec["detail"]["control"]["deltas"]["compiles_total"] = 3
    v = effwatch_ab_violations(rec)
    assert any(x.startswith("[control]") and "compile events" in x
               for x in v), v


# ----------------------------------------------- fake perf block tier

def test_fake_engine_perf_block_and_fault_lever():
    async def body():
        fake = FakeEngine(model="m", num_tokens=8)
        server = TestServer(fake.build_app())
        await server.start_server()
        try:
            async with TestClient(server) as client:
                # perf controls ride POST /fault without touching the
                # fault mode
                r = await client.post("/fault", json={
                    "perf": {"pad_fraction": 0.25,
                             "dead_fraction": 0.25,
                             "compiles_total": 3,
                             "compile_in_flight": 1}})
                assert (await r.json())["fault"] is None
                r = await client.post("/v1/chat/completions", json={
                    "model": "m", "max_tokens": 8,
                    "messages": [{"role": "user", "content": "hi"}]})
                assert r.status == 200
                r = await client.get("/load")
                perf = (await r.json())["perf"]
                steps = perf["token_steps"]
                # 8 served tokens -> 7 decode real (first = prefill)
                assert steps["real"] == 7
                assert steps["pad"] == 4 and steps["dead"] == 4
                assert steps["token_steps_total"] == 15
                assert perf["compiles_total"] == 3
                assert perf["compile_in_flight"] == 1
                assert perf["live_fraction"] == pytest.approx(7 / 15)
                # /metrics agrees with /load
                r = await client.get("/metrics")
                text = (await r.read()).decode()
                assert 'tpu:engine_token_steps_total{model_name="m",' \
                       'kind="real",phase="decode"} 7' in text
                assert "tpu:engine_mbu_perc" in text
                assert "tpu:engine_compiles_total" in text
                # the skew knob inflates the independent total
                await client.post("/fault", json={"perf": {"skew": 1.0}})
                r = await client.get("/load")
                steps = (await r.json())["perf"]["token_steps"]
                assert steps["token_steps_total"] == 30
        finally:
            await server.close()
    asyncio.run(body())


def test_router_scraper_parses_perf_block():
    """Router-side parsing satellite: one real EngineStatsScraper
    scrape of the fake's /load lands mbu/live-fraction/compile signals
    in EngineStats."""
    from production_stack_tpu.router.stats import EngineStatsScraper

    async def body():
        fake = FakeEngine(model="m", num_tokens=8)
        fake._apply_perf_overrides({"perf": {
            "pad_fraction": 0.5, "compiles_total": 7,
            "compile_in_flight": 2, "mbu_perc": 41.5,
            "effective_bytes_per_s": 3.4e11}})
        fake._note_served(9)           # 8 decode-real token-steps
        server = TestServer(fake.build_app())
        await server.start_server()
        try:
            url = f"http://127.0.0.1:{server.port}"

            class _Ep:
                pass
            ep = _Ep()
            ep.url = url
            scraper = EngineStatsScraper(lambda: [ep])
            import aiohttp
            async with aiohttp.ClientSession() as session:
                scraper.attach(session)
                await scraper.poll_now()
            stats = scraper.get()[url]
            assert stats.mbu_perc == pytest.approx(41.5)
            assert stats.live_fraction == pytest.approx(8 / 16)
            assert stats.compiles_total == 7
            assert stats.compile_in_flight == 2
            assert stats.decode_tokens_per_s > 0
        finally:
            await server.close()
    asyncio.run(body())


# -------------------------------------------------------------- rig

def test_effwatch_smoke_fake_engine(tmp_path):
    """Engine-free effwatch: synthetic pad/dead fractions, exact
    client reconciliation, zero compiles — all gates green."""
    record = asyncio.run(run_effwatch(
        engine="fake", users=3, duration_s=4.0, warmup_s=1.5,
        num_tokens=8, fake_pad_fraction=0.3, fake_dead_fraction=0.1,
        log_dir=str(tmp_path / "logs")))
    violations = effwatch_violations(record)
    assert not violations, violations
    d = record["detail"]
    assert d["requests"] > 0
    assert d["deltas"]["real"] == d["client_decode_tokens"]
    assert d["fraction_sum"] == pytest.approx(1.0, abs=0.02)
    assert d["live_fraction_steady"] == pytest.approx(0.6, abs=0.05)


def test_effwatch_anti_vacuity_fails_reconciliation(tmp_path):
    """The mis-sized accounting window (scrape taken before the warmup
    storm) must trip the reconciliation gate — the audit can fail."""
    record = asyncio.run(run_effwatch(
        engine="fake", users=3, duration_s=3.0, warmup_s=3.0,
        num_tokens=8, anti_vacuity=True,
        log_dir=str(tmp_path / "logs")))
    violations = effwatch_violations(record)
    assert any("diverge" in v for v in violations), violations


def test_effwatch_skew_fails_sum_gate(tmp_path):
    """A fake whose independent total is inflated must trip the
    sum-to-1 gate (and only that gate needs to trip)."""
    record = asyncio.run(run_effwatch(
        engine="fake", users=2, duration_s=3.0, warmup_s=1.0,
        num_tokens=8, fake_skew=0.25,
        log_dir=str(tmp_path / "logs")))
    violations = effwatch_violations(record)
    assert any("sum to the independent total" in v
               for v in violations), violations


def test_effwatch_ab_smoke_fake_engine(tmp_path):
    """Engine-free A/B plumbing smoke: the adapt side runs with
    better synthetic fractions and faster pacing than the control —
    both sides' gates, the live-fraction comparison, and the
    improvement arithmetic must come out green. (The real-engine A/B
    behind ``slow`` holds the actual perf claim.)"""
    from production_stack_tpu.loadgen.effwatch import (
        effwatch_ab_violations, run_effwatch_ab)
    record = asyncio.run(run_effwatch_ab(
        engine="fake", users=3, duration_s=4.0, warmup_s=1.5,
        num_tokens=8, fake_pad_fraction=0.08, fake_dead_fraction=0.05,
        # pacing of 10 ms against 20 ms a token: the improvement gate
        # compares two wall-clock rates, and a gap of 1.4 ms a token
        # (280 against 200 tokens/s) was less than what a loaded host
        # adds to every streamed token, on both sides alike
        fake_tokens_per_s=100.0,
        fake_control_pad_fraction=0.40,
        fake_control_dead_fraction=0.10,
        fake_control_tokens_per_s=50.0,
        log_dir=str(tmp_path / "logs")))
    violations = effwatch_ab_violations(record, live_floor=0.80,
                                        improve_floor=0.15)
    assert not violations, violations
    d = record["detail"]
    assert d["live_fraction_adapt"] > d["live_fraction_control"]
    assert d["improvement_perc"] > 15.0
    assert d["adapt"]["window_adapt"] and not \
        d["control"]["window_adapt"]


def test_compile_budget_zero_steady_compiles(tmp_path):
    """Tier-1 compile-budget regression (pins the bucket-set bound):
    a real debug-tiny engine warmed over the FULL (batch bucket x
    window bucket) grid must record ZERO compile events through a
    churny storm — staggered arrivals and mixed short/long budgets
    walk the adaptive dispatch across batch AND window buckets, and
    every executable it reaches must already be warm. A single cold
    combination here is a multi-second mid-serving stall in
    production."""
    from production_stack_tpu.loadgen.effwatch import (_scrape_perf,
                                                       _storm)
    from production_stack_tpu.loadgen.orchestrator import (
        _stop, free_port, launch_engine, wait_healthy)

    async def body():
        procs = []
        try:
            proc = launch_engine(
                "debug-tiny", free_port(),
                log_dir=str(tmp_path / "logs"), platform="cpu",
                extra_args=["--max-model-len", "256",
                            "--max-num-seqs", "2",
                            "--prefill-chunk", "32",
                            "--decode-window", "4",
                            "--kv-len-buckets", "256"])
            procs.append(proc)
            await wait_healthy(proc.url, 240.0)
            before = await _scrape_perf(proc.url)
            # warmup compiled the grid: greedy+plain over batch
            # buckets (1,2) x window buckets (1,2,4) and more (the
            # geometry is kept tiny on purpose — this runs in tier-1,
            # whose 870s budget is already tight)
            assert before["compiles_total"] >= 2 * 6
            c = await _storm(proc.url, "debug-tiny", users=3,
                             duration_s=5.0, num_tokens=8,
                             tag="churn", stagger_s=0.6,
                             mixed_tokens=[4, 12])
            after = await _scrape_perf(proc.url)
            assert c.errors == 0, c.samples
            assert c.requests > 0
            assert after["compiles_total"] == before["compiles_total"], \
                "steady-state serving compiled (bucket grid not " \
                "fully warmed)"
            # the storm actually walked the adaptive grid
            import aiohttp
            async with aiohttp.ClientSession() as session:
                async with session.get(
                        f"{proc.url}/debug/perf?limit=100") as r:
                    dp = await r.json()
            assert len({w["batch"] for w in dp["windows"]}) >= 2
            assert len({w["steps"] for w in dp["windows"]}) >= 2
        finally:
            _stop(procs)
    asyncio.run(body())


@pytest.mark.slow
def test_effwatch_real_engine(tmp_path):
    """The committed acceptance shape: a real debug-tiny process,
    10% reconciliation tolerance, zero steady compiles."""
    record = asyncio.run(run_effwatch(
        engine="debug-tiny", users=6, duration_s=20.0, warmup_s=8.0,
        num_tokens=32, log_dir=str(tmp_path / "logs")))
    violations = effwatch_violations(record)
    assert not violations, violations


@pytest.mark.slow
def test_effwatch_ab_real_engine(tmp_path):
    """The committed EFF_r17 acceptance shape: real debug-tiny
    same-storm A/B — adapt live fraction >= 0.80 and accounted decode
    tokens/s >= +20% over --no-window-adapt, every per-side gate
    green on both sides."""
    from production_stack_tpu.loadgen.effwatch import (
        effwatch_ab_violations, run_effwatch_ab)
    record = asyncio.run(run_effwatch_ab(
        engine="debug-tiny", users=32, duration_s=30.0, warmup_s=12.0,
        num_tokens=32, stagger_s=0.2, mixed_tokens=[10, 44], rounds=3,
        engine_args=["--max-num-seqs", "32", "--decode-batch-buckets",
                     "1,2,4,8,16,20,24,28,32"],
        log_dir=str(tmp_path / "logs")))
    violations = effwatch_ab_violations(record)
    assert not violations, violations
