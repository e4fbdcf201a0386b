"""loadgen unit tier: spec round-trip, seeded planning determinism,
Poisson arrival statistics, report schemata, invariant tracking, and a
fast closed/open-loop run against the in-process fake engine."""

import asyncio
import json
import random
import time

import pytest
from aiohttp.test_utils import TestServer

from production_stack_tpu.loadgen import arrival, report, workload
from production_stack_tpu.loadgen.client import RequestRecord
from production_stack_tpu.loadgen.runner import (InvariantTracker,
                                                 run_workload)
from production_stack_tpu.loadgen.spec import (ArrivalSpec, TrafficMix,
                                               WorkloadSpec, preset)
from tests.fake_engine import FakeEngine


# ------------------------------------------------------------------ spec

def test_spec_json_round_trip():
    spec = preset("mixed")
    again = WorkloadSpec.from_json(spec.to_json())
    assert again == spec


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="lora_model"):
        WorkloadSpec(mix=TrafficMix(lora=1.0)).validate()
    with pytest.raises(ValueError, match="mode"):
        WorkloadSpec(arrival=ArrivalSpec(mode="sideways")).validate()
    with pytest.raises(ValueError, match="positive weight"):
        WorkloadSpec(mix=TrafficMix(chat=0.0)).validate()


def _worst_case_model_tokens(s):
    """Worst-case final-round prompt under debug-tiny's character
    tokenizer (~8 model tokens per filler word, plus generated
    answers re-sent as history)."""
    worst_words = s.system_prompt_tokens + s.rounds_max * s.question_tokens_max
    return worst_words * 8 + (s.rounds_max - 1) * s.answer_tokens_max


def test_scaleout_preset_fits_orchestrator_engines():
    """The scaleout preset must fit the max-model-len 1024 engines the
    orchestrator launches — otherwise the curve measures the 400 path."""
    assert _worst_case_model_tokens(preset("scaleout").session) < 1024


def test_warmup_spec_fits_orchestrator_engines():
    """Warmup pokes must fit too: a 400'd warmup silently pushes the
    XLA compiles back into the measured window."""
    from production_stack_tpu.loadgen.runner import warmup_spec
    warm = warmup_spec(preset("scaleout"))
    assert _worst_case_model_tokens(warm.session) < 1024
    assert warm.model == preset("scaleout").model
    # the traffic mix carries over (each kind's executable compiles
    # during warmup, not inside the measured window)...
    assert warmup_spec(preset("mixed")).mix == preset("mixed").mix
    # ...and kind= pins it for per-kind round-robin warmup
    pinned = warmup_spec(preset("mixed"), kind="guided")
    assert pinned.mix.weights() == [("guided", 1.0)]


def test_ramp_stages_match_reference_shape():
    # the reference run.sh sweep: QPS 0.1 -> 4.1 in steps of 1.0
    stages = preset("ref-ramp").arrival.stages()
    assert [q for q, _ in stages] == [0.1, 1.1, 2.1, 3.1, 4.1]


def test_ramp_step_guard():
    # qps_step <= 0 must never loop the stage builder forever:
    # constant-rate (start == end) is the one sensible reading...
    flat = ArrivalSpec(mode="open", qps_start=2.0, qps_end=2.0,
                       qps_step=0.0, stage_duration_s=10.0)
    assert flat.stages() == [(2.0, 10.0)]
    # ...and an actual ramp with a non-advancing step is a spec error,
    # caught at validate() time (spec load), not mid-run
    with pytest.raises(ValueError, match="qps_step"):
        WorkloadSpec(arrival=ArrivalSpec(
            mode="open", qps_start=1.0, qps_end=4.0,
            qps_step=-1.0)).validate()


# ------------------------------------------------------- workload planning

def test_plan_sessions_deterministic_and_resumable():
    spec = preset("mixed")
    full = workload.plan_sessions(spec, 12)
    assert full == workload.plan_sessions(spec, 12)
    # planning [0,5) then [5,12) equals planning [0,12): a resumed run
    # faces the same traffic
    split = workload.plan_sessions(spec, 5) + \
        workload.plan_sessions(spec, 7, first_id=5)
    assert split == full
    # a different seed produces different plans
    other = WorkloadSpec.from_dict(
        {**json.loads(spec.to_json()), "seed": 1})
    assert workload.plan_sessions(other, 12) != full


def test_mix_produces_all_kinds_with_correct_payloads():
    spec = preset("mixed")
    plans = workload.plan_sessions(spec, 300)
    kinds = {p.kind for p in plans}
    assert kinds == {"chat", "guided", "shaped", "embeddings"}
    for plan in plans[:50]:
        state = workload.SessionState(plan, spec)
        req = state.next_request()
        if plan.kind == "embeddings":
            assert req.path == "/v1/embeddings"
            assert not req.stream
            assert len(plan.turns) == 1       # embeddings: single-shot
        else:
            assert req.path == "/v1/chat/completions"
            assert req.stream
            assert req.headers["x-user-id"] == plan.user_id
            if plan.kind == "guided":
                assert req.body["guided_choice"] == ["yes", "no", "maybe"]
            if plan.kind == "shaped":
                assert req.body["presence_penalty"] == 0.5


def test_session_history_accumulates():
    spec = preset("chat")
    plan = next(p for p in workload.plan_sessions(spec, 20)
                if len(p.turns) >= 3)
    state = workload.SessionState(plan, spec)
    state.next_request()
    state.record_answer("first answer")
    req2 = state.next_request()
    roles = [m["role"] for m in req2.body["messages"]]
    assert roles == ["system", "user", "assistant", "user"]
    assert req2.body["messages"][2]["content"] == "first answer"


# ------------------------------------------------------- arrival processes

def test_poisson_rate_and_exponential_gaps():
    rng = random.Random(42)
    qps, duration = 20.0, 200.0
    times = arrival.poisson_times(rng, qps, duration)
    # count within 10% of qps * duration (4000 samples, ~1.6% sigma)
    assert abs(len(times) - qps * duration) / (qps * duration) < 0.10
    gaps = [b - a for a, b in zip(times, times[1:])]
    mean = sum(gaps) / len(gaps)
    assert abs(mean - 1.0 / qps) / (1.0 / qps) < 0.10
    # exponential gaps: coefficient of variation ~= 1 (a uniform or
    # constant cadence would be far below)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    cv = var ** 0.5 / mean
    assert 0.85 < cv < 1.15
    assert all(0 <= t < duration for t in times)
    assert times == sorted(times)


def test_ramp_times_stage_rates():
    rng = random.Random(7)
    stages = [(2.0, 50.0), (20.0, 50.0)]
    out = arrival.ramp_times(rng, stages)
    first = [t for t, q in out if t < 50.0]
    second = [t for t, q in out if t >= 50.0]
    assert abs(len(first) - 100) < 35        # ~2 qps * 50 s
    assert abs(len(second) - 1000) < 150     # ~20 qps * 50 s
    assert all(q == 2.0 for t, q in out if t < 50.0)
    offsets = [t for t, _ in out]
    assert offsets == sorted(offsets)


# ---------------------------------------------------------------- reports

def _rec(i, *, kind="chat", out_tok=10, ttft=0.1, err=None, status=200,
         aborted=False, t0=1000.0):
    return RequestRecord(
        request_id=i, session_id=i, turn_index=0, kind=kind,
        launch_time=t0 + i * 0.1, finish_time=t0 + i * 0.1 + 1.0,
        ttft_s=ttft, e2e_s=1.0, prompt_tokens=20, output_tokens=out_tok,
        status=status, error=err, aborted=aborted)


def test_aggregate_and_bench_schema():
    records = [_rec(i) for i in range(10)]
    records.append(_rec(10, err="HTTP 500: boom", status=500))
    records.append(_rec(11, aborted=True))
    agg = report.aggregate(records)
    assert agg["launched"] == 12
    assert agg["finished"] == 10
    assert agg["errors"] == 1 and agg["http_5xx"] == 1
    # a failing run must carry its own diagnosis
    assert agg["error_samples"] == ["HTTP 500: boom"]
    assert agg["aborted_injected"] == 1
    assert agg["total_output_tokens"] == 100
    assert agg["ttft_s"]["p99"] == pytest.approx(0.1)
    # BENCH_*.json record shape: metric/value/unit/platform/detail
    b = report.bench_schema("loadgen test", agg, platform="cpu",
                            detail={"workload": "chat"})
    assert set(b) >= {"metric", "value", "unit", "platform", "detail"}
    assert b["value"] == agg["output_tokens_per_s"]
    assert b["unit"] == "out_tok/s"
    assert b["detail"]["workload"] == "chat"
    json.dumps(b)                            # serializable


def test_scaleout_record_efficiency():
    points = [
        {"replicas": 1, "output_tokens_per_s": 100.0},
        {"replicas": 2, "output_tokens_per_s": 180.0},
        {"replicas": 4, "output_tokens_per_s": 400.0},
    ]
    rec = report.scaleout_record(engine="debug-tiny", routing="session",
                                 workload="chat", points=points)
    eff = {p["replicas"]: p["scaling_efficiency"] for p in rec["points"]}
    assert eff[1] == 1.0
    assert eff[2] == pytest.approx(0.9)
    assert eff[4] == pytest.approx(1.0)
    assert rec["routing"] == "session"
    json.dumps(rec)


def test_percentile_edges():
    assert report.percentile([], 99) == 0.0
    assert report.percentile([5.0], 50) == 5.0
    assert report.percentile(list(range(100)), 0) == 0
    assert report.percentile(list(range(100)), 100) == 99


# ------------------------------------------------------------- invariants

def test_invariant_tracker_catches_violations():
    t = InvariantTracker(p99_ttft_bound_s=0.5)
    t.on_launch(0)
    t.on_launch(1)
    t.on_launch(1)                            # duplicate
    t.on_launch(0)                            # non-monotonic
    t.on_complete(_rec(0, err="HTTP 503: overload", status=503))
    t.on_complete(_rec(1, ttft=2.0))          # busts the p99 bound
    violations = t.finalize([_rec(1, ttft=2.0)])
    text = "\n".join(violations)
    assert "I3" in text and "I1" in text and "I4" in text


def test_invariant_tracker_clean_run_passes():
    t = InvariantTracker(p99_ttft_bound_s=10.0)
    recs = []
    for i in range(20):
        t.on_launch(i)
        r = _rec(i, aborted=(i == 3))         # injected abort is NOT an
        recs.append(r)                        # error, and later requests
        t.on_complete(r)                      # succeed (I5)
    assert t.finalize(recs) == []


def test_invariant_missing_terminal_record():
    t = InvariantTracker()
    t.on_launch(0)
    t.on_launch(1)
    t.on_complete(_rec(0))
    violations = t.finalize([_rec(0)])
    assert any("no terminal record" in v for v in violations)


# ------------------------------------------------------ end-to-end (fake)

def test_closed_loop_run_against_fake_engine():
    async def body():
        fake = FakeEngine(model="debug-tiny", num_tokens=6)
        server = TestServer(fake.build_app())
        await server.start_server()
        spec = preset("chat")
        spec.arrival.users = 3
        result = await run_workload(
            spec, f"http://127.0.0.1:{server.port}", max_sessions=5,
            checkpoint_interval_s=3600)
        await server.close()
        assert result.ok, result.violations
        assert result.summary["finished"] > 0
        assert result.summary["errors"] == 0
        assert result.summary["output_tokens_per_s"] > 0
        assert result.summary["ttft_s"]["p99"] > 0
        # x-user-id flowed through (session-affinity routing key)
        users = {u for _, u, _ in fake.requests_seen}
        assert all(u and u.startswith("lg-user-") for u in users)
    asyncio.run(body())


def test_open_loop_run_against_fake_engine():
    async def body():
        fake = FakeEngine(model="debug-tiny", num_tokens=4)
        server = TestServer(fake.build_app())
        await server.start_server()
        spec = preset("chat")
        spec.arrival = ArrivalSpec(mode="open", qps_start=8.0,
                                   qps_end=8.0, qps_step=1.0,
                                   stage_duration_s=2.0)
        result = await run_workload(
            spec, f"http://127.0.0.1:{server.port}", duration_s=3.0,
            checkpoint_interval_s=3600)
        await server.close()
        assert result.ok, result.violations
        assert result.summary["finished"] > 0
    asyncio.run(body())


def test_open_loop_drain_cancel_is_not_a_violation(monkeypatch):
    """Requests the harness itself cancels at drain (still in flight
    when the run ends — the normal state of an overloaded open-loop
    measurement) must get a terminal record, not surface as a false I3
    violation against the stack."""
    from production_stack_tpu.loadgen import runner as runner_mod
    monkeypatch.setattr(runner_mod, "DRAIN_GRACE_S", 0.2)

    async def body():
        # slow streams (~0.5 tok/s over 40 tokens) guarantee in-flight
        # requests at the 2 s deadline
        fake = FakeEngine(model="debug-tiny", num_tokens=40,
                          tokens_per_s=2.0)
        server = TestServer(fake.build_app())
        await server.start_server()
        spec = preset("chat")
        spec.arrival = ArrivalSpec(mode="open", qps_start=4.0,
                                   qps_end=4.0, qps_step=1.0,
                                   stage_duration_s=2.0)
        result = await run_workload(
            spec, f"http://127.0.0.1:{server.port}", duration_s=2.0,
            checkpoint_interval_s=3600)
        await server.close()
        assert result.ok, result.violations
        assert result.summary["cancelled_by_harness"] > 0
        assert result.summary["errors"] == 0
        # every launched id has a terminal record
        assert result.summary["launched"] == len(result.records)
    asyncio.run(body())


def test_soak_reports_server_errors_as_violations():
    async def body():
        from aiohttp import web

        async def boom(request):
            return web.json_response({"error": "kaput"}, status=500)

        app = web.Application()
        app.router.add_post("/v1/chat/completions", boom)
        server = TestServer(app)
        await server.start_server()
        spec = preset("chat")
        spec.arrival.users = 2
        result = await run_workload(
            spec, f"http://127.0.0.1:{server.port}", max_sessions=2,
            checkpoint_interval_s=3600)
        await server.close()
        assert not result.ok
        assert any(v.startswith("I1") for v in result.violations)
        assert result.summary["http_5xx"] > 0
    asyncio.run(body())


# ------------------------------------------------------------------- CLI

def test_cli_duration_parsing():
    from production_stack_tpu.loadgen.__main__ import parse_duration
    assert parse_duration("120") == 120.0
    assert parse_duration("120s") == 120.0
    assert parse_duration("5m") == 300.0
    assert parse_duration("4.4h") == pytest.approx(15840.0)
    with pytest.raises(Exception):
        parse_duration("soon")
