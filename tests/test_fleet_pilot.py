"""Fleet pilot (ISSUE 18 / r20): burn-rate + scheduled + phase policy
inputs, the /fleet collector with its degradation path, the bounded
remediator's guard chain and runbook, decision-log rotation, the
fake engine's wedge fault, and kvplane victim ordering.

Tiers:
- policy units — hand-built FleetSignals: a firing page IS the breach
  (reason ``burn_rate``, no tick accumulation, scale-down blocked);
  scheduled floors pre-provision on the injected wall clock; phase
  p95s breach like queue delay;
- collector — a canned in-process /fleet server + a real FakeEngine:
  fleet consumed while fresh, raw /load fallback when the obsplane is
  down OR serves only stale rows, recovery after a same-port restart
  (the satellite pin: fallback is a degradation, never a latch);
- remediator — every guard refusal is an explicit suppressed_*
  outcome, and the executed runbook lands drain -> wait -> restart ->
  undrain+breaker -> verify against in-process router/obsplane stubs;
- controller — remediation records count into
  ``tpu:autoscaler_remediations_total`` and the decision log rotates
  at its size cap;
- engine — wedge: health green, /load answering, inference parked
  forever; migrate_out retires the least recently active sequence.
"""

import asyncio
import json
import time

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.autoscaler.collector import FleetSignalCollector
from production_stack_tpu.autoscaler.controller import Autoscaler
from production_stack_tpu.autoscaler.policy import (DOWN, HOLD, UP,
                                                    AutoscalerPolicy,
                                                    FleetSignal,
                                                    PolicyConfig,
                                                    parse_phase_targets,
                                                    parse_schedule)
from production_stack_tpu.autoscaler.remediator import (RemediationPolicy,
                                                        Remediator)
from tests.fake_engine import FakeEngine


def _cfg(**kw):
    base = dict(min_replicas=1, max_replicas=4,
                target_queue_delay_ms=500.0, down_queue_delay_ms=100.0,
                target_utilization=0.9, down_utilization=0.5,
                up_cooldown_s=10.0, down_cooldown_s=30.0,
                up_breach_ticks=2, down_breach_ticks=2)
    base.update(kw)
    return PolicyConfig(**base).validate()


def _sig(replicas=1, ready=None, delay=0.0, **kw):
    return FleetSignal(replicas=replicas,
                       ready=replicas if ready is None else ready,
                       queue_delay_ms=delay, **kw)


_PAGE = ({"name": "chat_ttft_page", "slo": "chat_ttft",
          "severity": "page", "router": "http://r:1"},)
_TICKET = ({"name": "chat_ttft_ticket", "slo": "chat_ttft",
            "severity": "ticket", "router": "http://r:1"},)


# ------------------------------------------------------------ policy units

def test_burn_rate_page_scales_up_without_breach_ticks():
    pol = AutoscalerPolicy(_cfg(burn_rate_input=True, up_breach_ticks=3))
    d = pol.decide(_sig(source="fleet", alerts_firing=_PAGE), now=100.0)
    assert (d.direction, d.reason) == (UP, "burn_rate")
    assert d.target == 2
    assert d.signal["source"] == "fleet"
    assert d.signal["alerts_firing"] == ["chat_ttft_page"]


def test_burn_rate_input_off_ignores_the_page():
    pol = AutoscalerPolicy(_cfg(burn_rate_input=False))
    d = pol.decide(_sig(alerts_firing=_PAGE), now=100.0)
    assert d.direction == HOLD
    assert d.reason != "burn_rate"


def test_burn_rate_ticket_severity_is_not_a_page():
    pol = AutoscalerPolicy(_cfg(burn_rate_input=True))
    d = pol.decide(_sig(alerts_firing=_TICKET), now=100.0)
    assert d.direction == HOLD


def test_burn_rate_respects_max_settling_and_cooldown():
    pol = AutoscalerPolicy(_cfg(burn_rate_input=True, max_replicas=2))
    assert pol.decide(_sig(replicas=2, alerts_firing=_PAGE),
                      now=0.0).reason == "at_max"
    assert pol.decide(_sig(replicas=2, ready=1, alerts_firing=_PAGE),
                      now=0.0).reason in ("at_max",)
    pol2 = AutoscalerPolicy(_cfg(burn_rate_input=True))
    assert pol2.decide(_sig(ready=0, alerts_firing=_PAGE),
                       now=0.0).reason == "settling"
    pol2.note_scaled(UP, now=100.0)
    assert pol2.decide(_sig(alerts_firing=_PAGE),
                       now=101.0).reason == "cooldown_up"
    # cooldown expired -> the page scales again
    assert pol2.decide(_sig(alerts_firing=_PAGE),
                       now=200.0).direction == UP


def test_burning_fleet_never_scales_down():
    """An idle-looking signal + a firing page: the burn-rate branch
    runs first, so the down path is unreachable while pages fire."""
    pol = AutoscalerPolicy(_cfg(burn_rate_input=True, max_replicas=2,
                                down_breach_ticks=1, down_cooldown_s=0))
    for _ in range(5):
        d = pol.decide(_sig(replicas=2, delay=0.0, alerts_firing=_PAGE),
                       now=1000.0)
        assert d.direction != DOWN
        assert d.reason == "at_max"
    # same signal, page cleared -> idle scale-down resumes
    for _ in range(2):
        d = pol.decide(_sig(replicas=2, delay=0.0), now=1000.0)
    assert d.direction == DOWN and d.reason == "idle"


def _clock(minute_of_day):
    return lambda: time.struct_time(
        (2026, 8, 6, minute_of_day // 60, minute_of_day % 60,
         0, 3, 218, -1))


def test_scheduled_floor_preprovisions_inside_the_window():
    cfg = _cfg(scheduled_floors=parse_schedule("08:00-18:00=3"))
    pol = AutoscalerPolicy(cfg, wallclock_fn=_clock(9 * 60))
    d = pol.decide(_sig(replicas=1), now=0.0)
    assert (d.direction, d.reason, d.target) == (UP, "scheduled", 2)
    # outside the window the floor is gone
    pol = AutoscalerPolicy(cfg, wallclock_fn=_clock(19 * 60))
    assert pol.scheduled_floor() == 0
    assert pol.decide(_sig(replicas=1), now=0.0).direction == HOLD


def test_scheduled_floor_wraps_midnight_and_blocks_scale_down():
    cfg = _cfg(scheduled_floors=parse_schedule("22:00-02:00=2"),
               down_breach_ticks=1, down_cooldown_s=0)
    pol = AutoscalerPolicy(cfg, wallclock_fn=_clock(23 * 60))
    assert pol.scheduled_floor() == 2
    pol_next = AutoscalerPolicy(cfg, wallclock_fn=_clock(60))  # 01:00
    assert pol_next.scheduled_floor() == 2
    # at the floor, an idle fleet holds instead of dipping under it
    d = pol.decide(_sig(replicas=2, delay=0.0), now=100.0)
    assert d.direction == HOLD and d.reason == "at_min"


def test_phase_p95_breach_scales_up_with_reason():
    cfg = _cfg(phase_p95_targets=parse_phase_targets(
        "engine.prefill=250"))
    pol = AutoscalerPolicy(cfg)
    sig = _sig(source="fleet",
               phase_p95_ms={"engine.prefill": 400.0,
                             "engine.decode": 50.0})
    assert pol.decide(sig, now=0.0).reason == "breach_pending_up"
    d = pol.decide(sig, now=1.0)
    assert (d.direction, d.reason) == (UP, "phase_p95")
    assert d.signal["phase_p95_ms"] == {"engine.prefill": 400.0}
    # a breached phase also blocks the idle scale-down path
    pol2 = AutoscalerPolicy(_cfg(
        phase_p95_targets={"engine.prefill": 250.0},
        down_breach_ticks=1, down_cooldown_s=0))
    d = pol2.decide(_sig(replicas=2, delay=0.0,
                         phase_p95_ms={"engine.prefill": 400.0}),
                    now=0.0)
    assert d.direction != DOWN


def test_parse_helpers_and_config_validation():
    assert parse_phase_targets(" engine.prefill=250, a.b=10 ") == {
        "engine.prefill": 250.0, "a.b": 10.0}
    assert parse_phase_targets("") == {}
    with pytest.raises(ValueError):
        parse_phase_targets("engine.prefill")
    assert parse_schedule("08:00-18:00=3,22:30-01:00=2") == (
        (480, 1080, 3), (1350, 60, 2))
    assert parse_schedule("") == ()
    with pytest.raises(ValueError):
        parse_schedule("08:00-18:00")
    with pytest.raises(ValueError):
        parse_schedule("25:00-26:00=2")
    with pytest.raises(ValueError):
        _cfg(phase_p95_targets={"engine.prefill": -1.0})
    with pytest.raises(ValueError):
        _cfg(scheduled_floors=((0, 100, 99),))     # floor > max


# --------------------------------------------------- the /fleet collector

def _fleet_payload(url, *, age_s=0.1, state="live", in_flight=2.0,
                   capacity=8.0, qd=123.0, alerts=(), percentiles=None):
    return {
        "firing_alerts": list(alerts),
        "autoscaler_signal": {
            url: {"role": "engine", "state": state, "age_s": age_s,
                  "in_flight": in_flight, "capacity": capacity,
                  "est_queue_delay_ms": qd}},
        "fleet_percentiles": percentiles or {},
        "incidents": [],
    }


def _fleet_app(payload_fn):
    app = web.Application()

    async def fleet(request):
        return web.json_response(payload_fn())
    app.router.add_get("/fleet", fleet)
    return app


def test_fleet_collector_consumes_fleet_then_falls_back_on_restart():
    """The satellite pin: obsplane down -> the SAME collector degrades
    to the raw /load pass (source "load", failure counted), and a
    same-port obsplane restart brings the fleet path back — fallback
    is per-tick, never a latch."""
    async def body():
        fake = FakeEngine(model="m")
        eng_server = TestServer(fake.build_app())
        await eng_server.start_server()
        url = f"http://127.0.0.1:{eng_server.port}"
        fake.set_load_signals(capacity=5, queue_delay_ms=77)

        payload = lambda: _fleet_payload(url, alerts=[dict(_PAGE[0])],
                                         percentiles={
            "chat": {"engine.prefill": {"p95_ms": 321.0}},
            "rag": {"engine.prefill": {"p95_ms": 123.0}}})
        obs_server = TestServer(_fleet_app(payload))
        await obs_server.start_server()
        obs_port = obs_server.port
        obs_url = f"http://127.0.0.1:{obs_port}"

        collector = FleetSignalCollector(
            lambda: [url], obsplane_url=obs_url, freshness_s=5.0,
            fleet_timeout_s=1.0)
        await collector.start()
        try:
            sig = await collector.collect()
            assert sig.source == "fleet"
            assert sig.queue_delay_ms == 123.0
            assert sig.in_flight == 2.0 and sig.capacity == 8.0
            assert sig.ready == 1
            assert [a["name"] for a in sig.page_alerts()] == \
                ["chat_ttft_page"]
            # phase p95 is the max across classes
            assert sig.phase_p95_ms == {"engine.prefill": 321.0}
            # victim picking rides the fleet rows
            assert collector.per_engine()[url].in_flight == 2.0

            # obsplane dies -> raw /load pass, same tick cadence
            await obs_server.close()
            sig = await collector.collect()
            assert sig.source == "load"
            assert collector.last_source == "load"
            assert collector.fleet_failures == 1
            assert sig.queue_delay_ms == 77.0      # the engine's own
            assert sig.alerts_firing == ()
            assert collector.per_engine()[url].est_queue_delay_ms == 77

            # obsplane restarts on the SAME port -> fleet path resumes
            obs_server2 = TestServer(_fleet_app(payload), port=obs_port)
            await obs_server2.start_server()
            try:
                sig = await collector.collect()
                assert sig.source == "fleet"
                assert sig.queue_delay_ms == 123.0
                assert collector.fleet_failures == 1   # no new failure
            finally:
                await obs_server2.close()
        finally:
            await collector.close()
            await eng_server.close()
    asyncio.run(body())


def test_fleet_collector_stale_rows_fall_back():
    """An obsplane that answers HTTP but whose poll loop died serves
    stale ages — unusable, same as unreachable."""
    async def body():
        fake = FakeEngine(model="m")
        eng_server = TestServer(fake.build_app())
        await eng_server.start_server()
        url = f"http://127.0.0.1:{eng_server.port}"
        obs_server = TestServer(_fleet_app(
            lambda: _fleet_payload(url, age_s=60.0)))
        await obs_server.start_server()
        collector = FleetSignalCollector(
            lambda: [url],
            obsplane_url=f"http://127.0.0.1:{obs_server.port}",
            freshness_s=5.0)
        await collector.start()
        try:
            sig = await collector.collect()
            assert sig.source == "load"
            assert collector.fleet_failures == 1
        finally:
            await collector.close()
            await obs_server.close()
            await eng_server.close()
    asyncio.run(body())


# ------------------------------------------------------- remediator units

_INCIDENT = {
    "incident_id": "20260806T000000-0",
    "captured_at": 100.0,
    "alert": "chat_ttft_page",
    "attribution": {"process": "http://e:1", "role": "engine",
                    "phase": "engine.prefill", "confidence": "high",
                    "reason": "slow"},
}


def _remediator(**kw):
    policy_kw = dict(enabled=True, confidence_floor="high",
                     cooldown_s=0.0)
    policy_kw.update(kw.pop("policy_kw", {}))
    base = dict(obsplane_url="http://obs:1", router_urls=["http://r:1"],
                policy=RemediationPolicy(**policy_kw))
    base.update(kw)
    return Remediator(**base)


def _handle(rem, row, now=1000.0):
    return asyncio.run(rem._handle(dict(row,
                                        attribution=dict(
                                            row["attribution"])), now))


def test_remediator_guard_chain_each_refusal_is_an_outcome():
    # kill-switch (the default policy): suppressed, not silent
    rec = _handle(_remediator(policy_kw={"enabled": False}), _INCIDENT)
    assert rec["outcome"] == "suppressed_killswitch"
    assert rec["target"] == "http://e:1"

    # confidence floor
    weak = dict(_INCIDENT,
                attribution=dict(_INCIDENT["attribution"],
                                 confidence="medium"))
    rec = _handle(_remediator(), weak)
    assert rec["outcome"] == "suppressed_confidence"
    # ...and a lowered floor admits the same attribution past it
    # (guards after it then refuse: router role next door)
    rec = _handle(_remediator(
        policy_kw={"enabled": True, "confidence_floor": "medium"},
        engine_urls_fn=lambda: []), weak)
    assert rec["outcome"] == "suppressed_unmanaged"

    # role filter: a guilty router is somebody's pager
    routery = dict(_INCIDENT,
                   attribution=dict(_INCIDENT["attribution"],
                                    role="router"))
    rec = _handle(_remediator(), routery)
    assert rec["outcome"] == "suppressed_role"

    # unmanaged endpoint
    rec = _handle(_remediator(engine_urls_fn=lambda: ["http://other:2"]),
                  _INCIDENT)
    assert rec["outcome"] == "suppressed_unmanaged"

    # cooldown since the last executed remediation
    rem = _remediator(policy_kw={"enabled": True, "cooldown_s": 120.0},
                      engine_urls_fn=lambda: ["http://e:1"])
    rem._last_executed_at = 999.0
    rec = _handle(rem, _INCIDENT, now=1000.0)
    assert rec["outcome"] == "suppressed_cooldown"

    # per-window rate limit
    rem = _remediator(policy_kw={"enabled": True, "cooldown_s": 0.0,
                                 "max_per_window": 1,
                                 "window_s": 600.0},
                      engine_urls_fn=lambda: ["http://e:1"])
    rem._executed_at.append(900.0)
    rec = _handle(rem, _INCIDENT, now=1000.0)
    assert rec["outcome"] == "suppressed_rate_limit"
    # outside the window the budget refills (execution then fails on
    # the unreachable fake routers -> outcome failed/unresolved, but
    # NOT suppressed)
    rec = _handle(rem, _INCIDENT, now=2000.0)
    assert not rec["outcome"].startswith("suppressed")


def test_remediation_policy_validation():
    with pytest.raises(ValueError):
        RemediationPolicy(confidence_floor="certain").validate()
    with pytest.raises(ValueError):
        RemediationPolicy(max_per_window=0).validate()
    with pytest.raises(ValueError):
        RemediationPolicy(window_s=0).validate()


def test_remediator_executes_the_runbook_end_to_end():
    """drain at the router -> bounded in-flight wait -> restart hook ->
    undrain + breaker reset -> verify the alert left the firing set —
    exactly once per incident id."""
    async def body():
        admin_calls = []
        router_app = web.Application()

        async def admin_drain(request):
            admin_calls.append(("drain", await request.json()))
            return web.json_response({"ok": True})

        async def admin_breaker(request):
            admin_calls.append(("breaker", await request.json()))
            return web.json_response({"ok": True})
        router_app.router.add_post("/admin/drain", admin_drain)
        router_app.router.add_post("/admin/breaker", admin_breaker)
        router_server = TestServer(router_app)
        await router_server.start_server()
        router_url = f"http://127.0.0.1:{router_server.port}"

        fake = FakeEngine(model="m")        # idle: drains instantly
        eng_server = TestServer(fake.build_app())
        await eng_server.start_server()
        target = f"http://127.0.0.1:{eng_server.port}"

        firing = [{"name": "chat_ttft_page", "severity": "page"}]
        incident = dict(_INCIDENT,
                        attribution=dict(_INCIDENT["attribution"],
                                         process=target))
        obs_app = web.Application()

        async def fleet(request):
            return web.json_response({"firing_alerts": firing})

        async def incidents(request):
            assert request.query.get("role") == "engine,prefill"
            return web.json_response({"incidents": [incident]})
        obs_app.router.add_get("/fleet", fleet)
        obs_app.router.add_get("/fleet/incidents", incidents)
        obs_server = TestServer(obs_app)
        await obs_server.start_server()

        restarted = []

        async def restart_fn(url):
            restarted.append(url)
            firing.clear()          # the restart IS the fix
            return True

        rem = Remediator(
            obsplane_url=f"http://127.0.0.1:{obs_server.port}",
            router_urls=[router_url],
            policy=RemediationPolicy(
                enabled=True, confidence_floor="high",
                drain_timeout_s=3.0, drain_poll_s=0.05,
                verify_timeout_s=3.0, verify_poll_s=0.05),
            restart_fn=restart_fn,
            engine_urls_fn=lambda: [target])
        # make the canned incident (captured_at=100) actionable
        rem._since_captured_at = 0.0
        try:
            records = await rem.tick()
            assert len(records) == 1
            rec = records[0]
            assert rec["outcome"] == "resolved"
            assert rec["action"] == "drain_restart"
            assert rec["target"] == target
            assert "executed_at" in rec
            assert restarted == [target]
            assert rec["steps"][0] == f"drain@{router_url}:ok"
            assert "drained" in rec["steps"]
            assert "restart" in rec["steps"]
            assert "undrain+breaker_reset" in rec["steps"]
            # router saw drain up, drain down, breaker reset — in order
            assert [c[0] for c in admin_calls] == ["drain", "drain",
                                                   "breaker"]
            assert admin_calls[0][1] == {"url": target, "drain": True}
            assert admin_calls[1][1] == {"url": target, "drain": False}
            assert admin_calls[2][1] == {"url": target,
                                         "action": "reset"}
            # the same incident id is never acted on twice
            assert await rem.tick() == []
        finally:
            await rem.close()
            await obs_server.close()
            await eng_server.close()
            await router_server.close()
    asyncio.run(body())


def test_remediator_unresolved_and_failed_restart_are_outcomes():
    async def body():
        router_app = web.Application()

        async def admin_ok(request):
            return web.json_response({"ok": True})
        router_app.router.add_post("/admin/drain", admin_ok)
        router_app.router.add_post("/admin/breaker", admin_ok)
        router_server = TestServer(router_app)
        await router_server.start_server()
        router_url = f"http://127.0.0.1:{router_server.port}"

        fake = FakeEngine(model="m")
        eng_server = TestServer(fake.build_app())
        await eng_server.start_server()
        target = f"http://127.0.0.1:{eng_server.port}"

        def obs(incident_rows, firing):
            app = web.Application()

            async def fleet(request):
                return web.json_response({"firing_alerts": firing})

            async def incidents(request):
                return web.json_response({"incidents": incident_rows})
            app.router.add_get("/fleet", fleet)
            app.router.add_get("/fleet/incidents", incidents)
            return app

        incident = dict(_INCIDENT,
                        attribution=dict(_INCIDENT["attribution"],
                                         process=target))
        # alert never clears -> unresolved, never silent victory
        obs_server = TestServer(obs(
            [incident], [{"name": "chat_ttft_page",
                          "severity": "page"}]))
        await obs_server.start_server()
        rem = Remediator(
            obsplane_url=f"http://127.0.0.1:{obs_server.port}",
            router_urls=[router_url],
            policy=RemediationPolicy(
                enabled=True, drain_timeout_s=1.0, drain_poll_s=0.05,
                verify_timeout_s=0.3, verify_poll_s=0.05),
            restart_fn=lambda url: _true(),
            engine_urls_fn=lambda: [target])
        rem._since_captured_at = 0.0
        try:
            (rec,) = await rem.tick()
            assert rec["outcome"] == "unresolved"
        finally:
            await rem.close()
            await obs_server.close()

        # restart hook fails -> failed, and routing was still resumed
        incident2 = dict(incident, incident_id="20260806T000001-0")
        obs_server = TestServer(obs([incident2], []))
        await obs_server.start_server()
        rem = Remediator(
            obsplane_url=f"http://127.0.0.1:{obs_server.port}",
            router_urls=[router_url],
            policy=RemediationPolicy(
                enabled=True, drain_timeout_s=1.0, drain_poll_s=0.05,
                verify_timeout_s=0.3, verify_poll_s=0.05),
            restart_fn=lambda url: _false(),
            engine_urls_fn=lambda: [target])
        rem._since_captured_at = 0.0
        try:
            (rec,) = await rem.tick()
            assert rec["outcome"] == "failed"
            assert "restart_FAIL" in rec["steps"]
            # the finally-path still undrained + reset the breaker
            assert "undrain+breaker_reset" in rec["steps"]
        finally:
            await rem.close()
            await obs_server.close()
            await eng_server.close()
            await router_server.close()
    asyncio.run(body())


async def _true():
    return True


async def _false():
    return False


# ------------------------------------------ controller: metrics + rotation

class _StubCollector:
    async def collect(self, replicas=None):
        return _sig()

    def per_engine(self):
        return {}

    async def close(self):
        pass


class _StubActuator:
    replicas = 1

    def endpoint_urls(self):
        return []

    def draining_urls(self):
        return []

    async def apply(self, target, victims=None):
        pass


def test_remediation_records_count_into_metrics_and_log(tmp_path):
    log = tmp_path / "decisions.jsonl"
    scaler = Autoscaler(AutoscalerPolicy(_cfg()), _StubActuator(),
                        _StubCollector(), decision_log_path=str(log))
    scaler._log_remediation({"incident_id": "i-1",
                             "action": "drain_restart",
                             "outcome": "resolved"})
    scaler._log_remediation({"incident_id": "i-2",
                             "action": "drain_restart",
                             "outcome": "suppressed_killswitch"})
    assert len(scaler.remediation_events) == 2
    assert scaler.summary()["remediations"] == scaler.remediation_events
    text = scaler.metrics.render().decode()
    assert ('tpu:autoscaler_remediations_total{action="drain_restart",'
            'outcome="resolved"} 1.0') in text
    assert 'outcome="suppressed_killswitch"} 1.0' in text
    lines = [json.loads(l) for l in log.read_text().splitlines()]
    assert [l["kind"] for l in lines] == ["remediation", "remediation"]


def test_decision_log_rotates_at_the_size_cap(tmp_path):
    log = tmp_path / "decisions.jsonl"
    scaler = Autoscaler(AutoscalerPolicy(_cfg()), _StubActuator(),
                        _StubCollector(), decision_log_path=str(log),
                        decision_log_max_bytes=1)     # floored to 4096
    assert scaler.decision_log_max_bytes == 4096
    record = {"ts": 0.0, "direction": "hold", "reason": "in_band",
              "pad": "x" * 100}
    for _ in range(80):                    # ~9 KiB total -> 1+ rotation
        scaler._append_log_line(record)
    rotated = tmp_path / "decisions.jsonl.1"
    assert rotated.exists()
    assert log.stat().st_size < 4096
    assert rotated.stat().st_size >= 4096
    # both generations hold intact JSONL — rotation never splits a line
    for p in (log, rotated):
        for line in p.read_text().splitlines():
            json.loads(line)


def test_signal_source_gauge_follows_the_decision():
    from production_stack_tpu.autoscaler.controller import AutoscalerMetrics

    class _D:
        direction = "hold"
        reason = "in_band"
    m = AutoscalerMetrics()
    m.observe(_D(), ready=1, draining=0, replicas=1, source="fleet")
    text = m.render().decode()
    assert 'tpu:autoscaler_signal_source{source="fleet"} 1.0' in text
    assert 'tpu:autoscaler_signal_source{source="load"} 0.0' in text
    m.observe(_D(), ready=1, draining=0, replicas=1, source="load")
    text = m.render().decode()
    assert 'tpu:autoscaler_signal_source{source="load"} 1.0' in text


# --------------------------------------------------- wedge + victim order

def test_fake_engine_wedge_health_green_inference_parked():
    """The nastiest real-fleet failure: health 200, /load answering,
    inference stalled forever — invisible to liveness probes, visible
    only to the SLO plane (and thus only remediable via attribution)."""
    async def body():
        fake = FakeEngine(model="m", fault={"mode": "wedge"})
        async with TestClient(TestServer(fake.build_app())) as client:
            # probes stay green
            assert (await client.get("/v1/models")).status == 200
            req = asyncio.create_task(client.post(
                "/v1/completions",
                json={"model": "m", "prompt": "hi", "max_tokens": 2}))
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(asyncio.shield(req), 0.5)
            # the wedged request is visibly in flight on /load while
            # the endpoint keeps answering control-plane reads
            load = await (await client.get("/load")).json()
            assert load["running"] >= 1
            # persistent: a second request parks too (count is not
            # consumed) — fire-and-forget, both die with the server
            assert fake.fault["mode"] == "wedge"
            req.cancel()
            for t in (req,):
                try:
                    await t
                except (asyncio.CancelledError, Exception):
                    pass
    asyncio.run(body())


def test_migrate_out_retires_least_recently_active_first():
    """Satellite pin: victim selection is oldest-``last_active``-first
    (arrival as tie-break), NOT most-blocks-first."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions

    cfg = EngineConfig(
        model="debug-tiny", max_model_len=256, max_num_seqs=4,
        prefill_chunk=64,
        kv_transfer_config={"kv_role": "kv_both", "chunk_size": 32,
                            "local_cpu_gb": 0.05})
    eng = LLMEngine(cfg)
    prompts = {ch: [(ord(ch) * 131 + i * 37) % 500 for i in range(96)]
               for ch in "abc"}
    sids = {ch: eng.add_request(
        prompts[ch], SamplingOptions(temperature=0.0, max_tokens=64))
        for ch in "abc"}
    # run until every sequence holds blocks and is decoding (running
    # is keyed by decode slot, so compare by seq_id)
    want = set(sids.values())
    for _ in range(40):
        eng.step()
        decoding = {s.seq_id for s in eng.scheduler.running.values()}
        if want <= decoding and \
                all(any(eng.seqs[s].block_ids) for s in want):
            break
    else:
        pytest.fail("sequences never all reached decode")
    # stamp activity out of order vs both arrival and block count:
    # b is coldest, then a; c is hottest. Relative to the clock the
    # engine stamps with: a machine that started a minute ago reads
    # time.monotonic() below any fixed number of seconds
    now = time.monotonic()
    eng.seqs[sids["a"]].last_active = now - 200.0
    eng.seqs[sids["b"]].last_active = now - 300.0
    eng.seqs[sids["c"]].last_active = now - 100.0
    out = eng.migrate_out(max_seqs=2)
    assert out["migrated"] == [sids["b"], sids["a"]]
    assert out["freed_blocks"] > 0
    assert out["keys"]
    # a decode step stamps last_active forward on the survivor
    before = eng.seqs[sids["c"]].last_active
    eng.step()
    assert eng.seqs[sids["c"]].last_active > before
