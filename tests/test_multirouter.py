"""Multi-router control plane: shared-state gossip, QoS priority
tiers, the L4 splitter, and the multirouter rig's fake-engine smokes.

Unit tier drives HealthTracker peer merge / QosPolicy / AffinityTracker
with injected clocks; the e2e tier runs TWO real router apps
in-process gossiping over real sockets, plus the QoS admission and
preemption paths against fault-injecting FakeEngines. The full-size
multirouter run is behind the ``slow`` marker.
"""

import asyncio
import json

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.router.app import build_app, parse_args
from production_stack_tpu.router.qos import (DEFAULT_TIER_SPEC,
                                             QosPolicy,
                                             parse_tier_spec)
from production_stack_tpu.router.resilience import (CLOSED, OPEN,
                                                    HealthTracker)
from production_stack_tpu.router.routing import (AffinityTracker,
                                                 SessionRouter)
from production_stack_tpu.router.shared_state import (RouterPeers,
                                                      derive_router_id,
                                                      peers_payload)
from tests.fake_engine import FakeEngine

URL = "http://e0:8100"


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------- qos units

def test_tier_spec_parse_and_validation():
    tiers = parse_tier_spec(DEFAULT_TIER_SPEC)
    assert [t[0] for t in tiers] == ["tier0", "tier1", "tier2"]
    assert [t[1] for t in tiers] == [1.0, 0.85, 0.7]
    with pytest.raises(ValueError):
        parse_tier_spec("a=0.5,b=0.9")       # fractions must not rise
    with pytest.raises(ValueError):
        parse_tier_spec("a=1.5")             # outside (0, 1]
    with pytest.raises(ValueError):
        parse_tier_spec("a=1.0,a=0.5")       # duplicate name
    with pytest.raises(ValueError):
        parse_tier_spec("")                  # zero tiers
    with pytest.raises(ValueError):
        QosPolicy(DEFAULT_TIER_SPEC, tier_rates="nosuch=5")


def test_tier_resolution_header_name_index_and_default():
    q = QosPolicy(DEFAULT_TIER_SPEC)
    assert q.resolve({}).name == "tier0"                 # untagged
    assert q.resolve({"x-priority-class": "tier2"}).name == "tier2"
    assert q.resolve({"x-priority-class": "1"}).name == "tier1"
    assert q.resolve({"x-priority-class": "TIER2"}).name == "tier2"
    assert q.resolve({"x-priority-class": "zzz"}).name == "tier0"
    assert q.resolve({"x-priority-class": "99"}).name == "tier0"


def test_graduated_admission_sheds_low_tiers_first():
    q = QosPolicy(DEFAULT_TIER_SPEC)
    t0, t1, t2 = q.tiers
    # at 7/10 in flight: tier2 (0.7 bound) sheds, tier0/1 admit
    assert q.admit(t2, 7, 10)[0] == "shed"
    assert q.admit(t1, 7, 10)[0] == "admit"
    assert q.admit(t0, 7, 10)[0] == "admit"
    # at 9/10: tier1 (0.85) sheds too, tier0 still admits
    assert q.admit(t1, 9, 10)[0] == "shed"
    assert q.admit(t0, 9, 10)[0] == "admit"
    # no gate configured: pressure never sheds
    assert q.admit(t2, 1000, 0)[0] == "admit"
    assert q.shed_totals() == {"tier0": 0, "tier1": 1, "tier2": 1}
    assert q.sheds[("tier2", "pressure")] == 1


def test_token_bucket_rate_caps_a_tier():
    clock = Clock()
    q = QosPolicy(DEFAULT_TIER_SPEC, tier_rates="tier2=2",
                  now_fn=clock)
    t2 = q.tiers[2]
    # burst is max(1, rate) = 2 tokens up front
    assert q.admit(t2, 0, 0)[0] == "admit"
    assert q.admit(t2, 0, 0)[0] == "admit"
    assert q.admit(t2, 0, 0)[0] == "shed"
    assert q.sheds[("tier2", "bucket")] == 1
    clock.t += 0.5                             # refills 1 token
    assert q.admit(t2, 0, 0)[0] == "admit"
    # other tiers never touch tier2's bucket
    assert q.admit(q.tiers[0], 0, 0)[0] == "admit"


def test_preemption_picks_newest_lowest_tier_victim():
    q = QosPolicy(DEFAULT_TIER_SPEC, preempt_from=1)
    t0, t1, t2 = q.tiers
    e1, e2a, e2b = (asyncio.Event() for _ in range(3))
    s1 = q.register_preemptable(t1, e1)
    s2a = q.register_preemptable(t2, e2a)
    s2b = q.register_preemptable(t2, e2b)
    assert s1 is not None and s2a is not None
    # tier0 at the full gate preempts: newest tier2 victim first
    verdict, victim = q.admit(t0, 10, 10)
    assert verdict == "admit" and victim is s2b and e2b.is_set()
    verdict, victim = q.admit(t0, 10, 10)
    assert victim is s2a
    # then the tier1 slot
    verdict, victim = q.admit(t0, 10, 10)
    assert victim is s1 and e1.is_set()
    # nothing left to preempt: tier0 sheds like anyone
    assert q.admit(t0, 10, 10)[0] == "shed"
    # tier1 may not preempt its own tier
    q.register_preemptable(t1, asyncio.Event())
    assert q.admit(t1, 10, 10)[0] == "shed"
    assert q.preemptions == [0, 1, 2]
    # unregister is idempotent / tolerates popped slots
    q.unregister_preemptable(s2b)
    q.unregister_preemptable(None)


def test_tier0_never_registers_preemptable():
    q = QosPolicy(DEFAULT_TIER_SPEC)          # preempt_from = last tier
    assert q.register_preemptable(q.tiers[0], asyncio.Event()) is None
    assert q.register_preemptable(q.tiers[1], asyncio.Event()) is None
    assert q.register_preemptable(q.tiers[2],
                                  asyncio.Event()) is not None


def test_deadline_factor_tracks_admit_fraction():
    q = QosPolicy(DEFAULT_TIER_SPEC)
    assert q.deadline_factor(q.tiers[0]) == 1.0
    assert q.deadline_factor(q.tiers[2]) == 0.7


# ------------------------------------------------------ shared-state units

def test_peer_view_carries_transition_ages_and_drains():
    clock = Clock(100.0)
    t = HealthTracker(failure_threshold=2, cooldown_s=5.0,
                      now_fn=clock)
    assert t.peer_view() == {}            # nothing to converge on yet
    t.record_failure(URL, "connect")
    t.record_failure(URL, "connect")
    clock.t = 103.0
    view = t.peer_view()
    assert view[URL]["state"] == OPEN
    assert view[URL]["age_s"] == pytest.approx(3.0)
    assert view[URL]["cooldown_remaining_s"] == pytest.approx(2.0)
    t.start_drain("http://e1:8100")
    view = t.peer_view()
    assert view["http://e1:8100"]["draining"] is True
    assert json.dumps(view)               # JSON-clean (no inf)


def test_adopt_peer_open_and_close_by_age():
    clock = Clock(50.0)
    t = HealthTracker(failure_threshold=2, cooldown_s=5.0,
                      now_fn=clock)
    # peer saw the endpoint die 1s ago; we know nothing -> adopt OPEN
    t.adopt_peer_view({URL: {"state": "open", "age_s": 1.0,
                             "cooldown_remaining_s": 4.0}}, [URL])
    assert t.state_of(URL) == OPEN
    assert t.peer_adopted_opens == 1
    # the same stale echo again: our adopted transition is as new
    t.adopt_peer_view({URL: {"state": "open", "age_s": 1.0}}, [URL])
    assert t.peer_adopted_opens == 1
    # peer probed it back to life NOW (age 0 < our 1s) -> adopt CLOSE
    t.adopt_peer_view({URL: {"state": "closed", "age_s": 0.0}}, [URL])
    assert t.state_of(URL) == CLOSED
    assert t.peer_adopted_closes == 1
    # an OLD open from a third router must not reopen it
    t.adopt_peer_view({URL: {"state": "open", "age_s": 30.0}}, [URL])
    assert t.state_of(URL) == CLOSED


def test_adopt_respects_own_newer_observation_and_known_urls():
    clock = Clock(10.0)
    t = HealthTracker(failure_threshold=1, cooldown_s=5.0,
                      now_fn=clock)
    t.record_failure(URL, "connect")      # we JUST saw it die (age 0)
    t.adopt_peer_view({URL: {"state": "closed", "age_s": 8.0}}, [URL])
    assert t.state_of(URL) == OPEN        # our observation is newer
    # a peer with a stale config cannot plant state for unknown urls
    t.adopt_peer_view({"http://gone:1": {"state": "open",
                                         "age_s": 0.1}}, [URL])
    assert t.state_of("http://gone:1") == CLOSED
    assert "http://gone:1" not in t.snapshot()


def test_adopt_drain_last_writer_wins():
    clock = Clock(0.0)
    t = HealthTracker(now_fn=clock)
    t.adopt_peer_view({URL: {"state": "closed", "age_s": 1e9,
                             "draining": True, "drain_age_s": 2.0}},
                      [URL])
    assert URL in t.draining()
    # our own newer end_drain beats the peer's older drain flag
    clock.t = 5.0
    t.end_drain(URL)
    t.adopt_peer_view({URL: {"state": "closed", "age_s": 1e9,
                             "draining": True, "drain_age_s": 7.0}},
                      [URL])
    assert URL not in t.draining()
    # but a NEWER peer drain wins again
    clock.t = 8.0
    t.adopt_peer_view({URL: {"state": "closed", "age_s": 1e9,
                             "draining": True, "drain_age_s": 0.5}},
                      [URL])
    assert URL in t.draining()


def test_router_peers_liveness_and_cap_share():
    clock = Clock(0.0)
    t = HealthTracker(now_fn=clock)
    peers = RouterPeers("r0", ["http://ra:1", "http://rb:2"], t,
                        known_urls=lambda: [URL], interval_s=1.0,
                        now_fn=clock)
    assert peers.live_router_count() == 1          # nobody seen yet
    assert peers.cap_share() == 1.0
    pa = peers._peers["http://ra:1"]
    pa.last_seen = clock.t
    pa.ever_seen = True
    assert peers.live_router_count() == 2
    assert peers.cap_share() == 0.5
    assert peers.state_counts() == {"live": 1, "stale": 0,
                                    "unreachable": 1}
    clock.t = 10.0                                 # ra goes dark
    assert peers.state_counts()["stale"] == 1
    assert peers.live_router_count() == 1          # share flows back
    # signal records: seen peers report growing age; never-seen peers
    # contribute nothing (startup must not page)
    pa.last_attempt = clock.t
    recs = peers.signal_records()
    assert set(recs) == {"http://ra:1"}
    assert recs["http://ra:1"].peer_age_s == pytest.approx(10.0)


def test_derive_router_id_and_payload_shape():
    assert derive_router_id("10.0.0.5", 8000) == "10.0.0.5:8000"
    assert ":" in derive_router_id("0.0.0.0", 8000)
    t = HealthTracker()
    body = peers_payload("r7", t)
    assert body["router_id"] == "r7" and body["breakers"] == {}


# ------------------------------------------------------ affinity units

def test_affinity_tracker_reasons_and_bound():
    a = AffinityTracker(max_entries=2)
    a.note("s1", "e0", {"e0", "e1"})
    a.note("s1", "e0", {"e0", "e1"})
    assert a.moves == {"endpoint_lost": 0, "endpoint_recovered": 0,
                       "rebalance": 0}
    a.note("s1", "e1", {"e1"})            # home vanished
    assert a.moves["endpoint_lost"] == 1
    # the key returns to its pre-displacement home once it is back in
    # the candidate set: expected recovery churn, NOT the split-brain
    # rebalance signal
    a.note("s1", "e0", {"e0", "e1"})
    assert a.moves["endpoint_recovered"] == 1
    assert a.moves["rebalance"] == 0
    # a move to a THIRD engine while the home is available: rebalance
    a.note("s1", "e2", {"e0", "e1", "e2"})
    assert a.moves["rebalance"] == 1
    a.note("s2", "e0", {"e0"})
    a.note("s3", "e0", {"e0"})            # LRU evicts s1
    assert len(a._homes) == 2


def test_pressure_shed_does_not_drain_the_token_bucket():
    clock = Clock()
    q = QosPolicy(DEFAULT_TIER_SPEC, tier_rates="tier2=2",
                  now_fn=clock)
    t2 = q.tiers[2]
    # sustained pressure: sheds must not consume tokens
    for _ in range(5):
        assert q.admit(t2, 10, 10)[0] == "shed"
    assert q.sheds[("tier2", "pressure")] == 5
    # pressure clears: the full burst is still there
    assert q.admit(t2, 0, 10)[0] == "admit"
    assert q.admit(t2, 0, 10)[0] == "admit"
    assert q.admit(t2, 0, 10)[0] == "shed"     # now the bucket
    assert q.sheds[("tier2", "bucket")] == 1


def test_session_router_counts_moves_on_endpoint_loss():
    from production_stack_tpu.router.service_discovery import (
        EndpointInfo)
    eps = [EndpointInfo(url=f"http://e{i}:8100", model="m")
           for i in range(3)]
    r = SessionRouter()
    homes = {f"u{i}": r.route(eps, {}, {"x-user-id": f"u{i}"}, {})
             for i in range(16)}
    assert r.affinity_moves == {"endpoint_lost": 0,
                                "endpoint_recovered": 0,
                                "rebalance": 0}
    dead = homes["u0"]
    rest = [e for e in eps if e.url != dead]
    moved = [u for u, home in homes.items() if home == dead]
    for u in homes:
        r.route(rest, {}, {"x-user-id": u}, {})
    assert r.affinity_moves["endpoint_lost"] == len(moved)
    assert r.affinity_moves["rebalance"] == 0


# ---------------------------------------------------------- splitter

def test_l4_splitter_round_robin_and_connect_failover():
    from production_stack_tpu.loadgen.multirouter import L4Splitter

    async def body():
        async def serve(tag):
            async def handle(reader, writer):
                await reader.read(1)
                writer.write(tag)
                await writer.drain()
                writer.close()
            return await asyncio.start_server(handle, "127.0.0.1", 0)

        sa, sb = await serve(b"A"), await serve(b"B")
        pa = sa.sockets[0].getsockname()[1]
        pb = sb.sockets[0].getsockname()[1]
        sp = L4Splitter([("127.0.0.1", pa), ("127.0.0.1", pb)])
        await sp.start()

        async def once():
            r, w = await asyncio.open_connection("127.0.0.1", sp.port)
            w.write(b"x")
            await w.drain()
            tag = await r.read(1)
            w.close()
            return tag

        tags = [await once() for _ in range(4)]
        assert sorted(tags) == [b"A", b"A", b"B", b"B"]   # round robin
        # kill B: connections keep succeeding via connect failover
        sb.close()
        await sb.wait_closed()
        tags = [await once() for _ in range(4)]
        assert tags == [b"A"] * 4
        assert sp.connect_failovers >= 2
        await sp.close()
        sa.close()
        await sa.wait_closed()
    asyncio.run(body())


# ------------------------------------------------------------- e2e tier

def _router_args(backends, models, extra=None):
    argv = ["--service-discovery", "static",
            "--static-backends", ",".join(backends),
            "--static-models", ",".join(models),
            "--engine-stats-interval", "0.2",
            "--breaker-threshold", "2",
            "--breaker-cooldown", "0.3",
            "--breaker-probe-interval", "0.15"]
    return parse_args(argv + (extra or []))


async def _start_fakes(*fakes):
    servers = []
    for fake in fakes:
        server = TestServer(fake.build_app())
        await server.start_server()
        servers.append(server)
    return servers, [f"http://127.0.0.1:{s.port}" for s in servers]


def _chat(model="m"):
    return {"model": model,
            "messages": [{"role": "user", "content": "hi"}]}


def test_router_id_on_health_and_every_response():
    """--router-id lands on /health and as x-router-id on every
    response shape: proxied 200s, router sheds, error JSON."""
    async def body():
        fake = FakeEngine(model="m")
        servers, urls = await _start_fakes(fake)
        app = build_app(_router_args(urls, ["m"],
                                     extra=["--router-id", "replica-7",
                                            "--max-inflight", "1"]))
        async with TestClient(TestServer(app)) as client:
            h = await client.get("/health")
            assert (await h.json())["router_id"] == "replica-7"
            assert h.headers["x-router-id"] == "replica-7"
            r = await client.post("/v1/chat/completions", json=_chat())
            assert r.status == 200
            assert r.headers["x-router-id"] == "replica-7"
            assert r.headers["x-engine-id"].endswith(
                str(servers[0].port))
            # a 400 (missing model) is stamped too
            r = await client.post("/v1/chat/completions", json={})
            assert r.status == 400
            assert r.headers["x-router-id"] == "replica-7"
        for s in servers:
            await s.close()
    asyncio.run(body())


def test_gossip_converges_breaker_and_drain_between_real_apps():
    """Two real router apps over real sockets: an open observed by A
    reaches B within a gossip interval; a drain issued through A's
    /admin/drain reaches B; the probe-driven close propagates back."""
    async def body():
        fake = FakeEngine(model="m")
        servers, urls = await _start_fakes(fake)
        eurl = urls[0]

        def mk(rid, peer=None):
            extra = ["--router-id", rid,
                     "--peer-gossip-interval", "0.05",
                     "--breaker-probe-interval", "30"]
            if peer:
                extra += ["--peer-routers", peer]
            return build_app(_router_args(urls, ["m"], extra=extra))

        app_a = mk("rA")
        client_a = TestClient(TestServer(app_a))
        await client_a.start_server()
        url_a = f"http://127.0.0.1:{client_a.server.port}"
        app_b = mk("rB", peer=url_a)
        client_b = TestClient(TestServer(app_b))
        await client_b.start_server()

        async def wait_for(fn, timeout=3.0):
            deadline = asyncio.get_event_loop().time() + timeout
            while asyncio.get_event_loop().time() < deadline:
                if fn():
                    return True
                await asyncio.sleep(0.02)
            return fn()

        ha, hb = app_a["state"]["health"], app_b["state"]["health"]
        for _ in range(2):
            ha.record_failure(eurl, "connect")
        assert ha.state_of(eurl) == OPEN
        assert await wait_for(lambda: hb.state_of(eurl) == OPEN), \
            "B never adopted A's breaker open"
        assert hb.peer_adopted_opens == 1

        r = await client_a.post("/admin/drain",
                                json={"url": eurl, "drain": True})
        assert r.status == 200
        assert await wait_for(lambda: eurl in hb.draining()), \
            "B never adopted A's drain flag"

        ha.record_probe_result(eurl, True)
        assert await wait_for(lambda: hb.state_of(eurl) == CLOSED), \
            "B never adopted A's breaker close"

        await client_a.post("/admin/drain",
                            json={"url": eurl, "drain": False})
        assert await wait_for(lambda: eurl not in hb.draining())

        # liveness + metrics surface on B
        h = await (await client_b.get("/health")).json()
        assert h["peers"]["peers"][url_a]["state"] == "live"
        assert h["peers"]["live_routers"] == 2
        text = (await (await client_b.get("/metrics")).read()).decode()
        assert 'tpu:router_peers{state="live"} 1.0' in text
        await client_a.close()
        await client_b.close()
        for s in servers:
            await s.close()
    asyncio.run(body())


def test_qos_e2e_low_tier_sheds_and_tier_counters():
    """With --qos-tiers and a tiny --max-inflight over a slow engine,
    background traffic sheds 429 while untagged (tier0) requests keep
    landing; per-tier counters reach /health and /metrics."""
    async def body():
        fake = FakeEngine(model="m", ttft_s=0.3)
        servers, urls = await _start_fakes(fake)
        app = build_app(_router_args(
            urls, ["m"],
            extra=["--qos-tiers", "tier0=1.0,tier1=0.85,tier2=0.5",
                   "--max-inflight", "2"]))
        async with TestClient(TestServer(app)) as client:
            async def one(tier):
                headers = {"x-priority-class": tier} if tier else {}
                r = await client.post("/v1/chat/completions",
                                      json=_chat(), headers=headers)
                await r.read()
                return r
            # two slow untagged requests occupy the gate; a tier2
            # arrival is past its 0.5 * 2 = 1 bound -> 429 + Retry-After
            t1 = asyncio.ensure_future(one(None))
            t2 = asyncio.ensure_future(one(None))
            await asyncio.sleep(0.1)
            r = await one("tier2")
            assert r.status == 429
            assert "Retry-After" in r.headers
            assert (await t1).status == 200
            assert (await t2).status == 200
            h = await (await client.get("/health")).json()
            tiers = {t["tier"]: t for t in h["qos"]["tiers"]}
            assert tiers["tier2"]["sheds"]["pressure"] == 1
            assert tiers["tier0"]["admitted"] == 2
            text = (await (await client.get("/metrics")).read()).decode()
            assert 'tpu:router_qos_sheds_total{tier="tier2"} 1.0' in text
        for s in servers:
            await s.close()
    asyncio.run(body())


def test_qos_e2e_preemption_victim_gets_structured_503():
    """A tier0 arrival at the full gate preempts an in-dispatch tier2
    request: the victim answers 503 + Retry-After ("preempted"), the
    preemptor is served, and nothing feeds the breaker."""
    async def body():
        fake = FakeEngine(model="m", ttft_s=1.0)
        servers, urls = await _start_fakes(fake)
        app = build_app(_router_args(
            urls, ["m"],
            extra=["--qos-tiers", "tier0=1.0,tier1=0.85,tier2=0.7",
                   "--max-inflight", "1"]))
        async with TestClient(TestServer(app)) as client:
            victim = asyncio.ensure_future(client.post(
                "/v1/chat/completions", json=_chat(),
                headers={"x-priority-class": "tier2"}))
            await asyncio.sleep(0.15)         # victim is mid-dispatch
            r0 = await client.post("/v1/chat/completions", json=_chat())
            assert r0.status == 200, await r0.text()
            rv = await victim
            assert rv.status == 503
            body_v = await rv.json()
            assert "preempted" in body_v["error"]["message"]
            assert "Retry-After" in rv.headers
            # no health signal against the engine
            assert app["state"]["health"].state_of(urls[0]) == CLOSED
            h = await (await client.get("/health")).json()
            tiers = {t["tier"]: t for t in h["qos"]["tiers"]}
            assert tiers["tier2"]["preempted"] == 1
            assert tiers["tier2"]["sheds"]["preempted"] == 1
        for s in servers:
            await s.close()
    asyncio.run(body())


def test_qos_tier_feeds_slo_class_and_deadline_overlay():
    """Tiered requests reach the SLO engine under their tier class
    (tier0_shed_rate sees tier0 traffic) and background tiers get a
    scaled injected downstream deadline."""
    async def body():
        fake = FakeEngine(model="m")
        servers, urls = await _start_fakes(fake)
        app = build_app(_router_args(
            urls, ["m"],
            extra=["--qos-tiers", "tier0=1.0,tier1=0.85,tier2=0.7",
                   "--request-timeout", "100"]))
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/chat/completions", json=_chat())
            assert r.status == 200
            # untagged -> tier0 class -> tier0_shed_rate saw one good
            slo = app["state"]["slo"]
            good, bad = slo.window_counts("tier0_shed_rate", "5m")
            assert (good, bad) == (1, 0)
            assert fake.last_headers["x-request-deadline-ms"] == \
                "100000"
            r = await client.post(
                "/v1/chat/completions", json=_chat(),
                headers={"x-priority-class": "tier2"})
            assert r.status == 200
            assert fake.last_headers["x-request-deadline-ms"] == \
                str(int(100 * 1000 * 0.7))
            # an explicit client deadline always passes through
            r = await client.post(
                "/v1/chat/completions", json=_chat(),
                headers={"x-priority-class": "tier2",
                         "x-request-deadline-ms": "1234"})
            assert fake.last_headers["x-request-deadline-ms"] == "1234"
        for s in servers:
            await s.close()
    asyncio.run(body())


def test_apportioned_endpoint_cap_splits_across_live_routers():
    from production_stack_tpu.router.proxy import _endpoint_cap

    class _Peers:
        def __init__(self, share):
            self._share = share

        def cap_share(self):
            return self._share

    state = {"endpoint_cap": 10, "peers": _Peers(0.5)}
    assert _endpoint_cap(state, URL) == 5.0
    state["peers"] = _Peers(1.0 / 3.0)
    assert _endpoint_cap(state, URL) == pytest.approx(10 / 3)
    # floor at 1: a huge fleet never rounds an endpoint to zero slots
    state["endpoint_cap"] = 2
    state["peers"] = _Peers(0.1)
    assert _endpoint_cap(state, URL) == 1.0
    # no peers -> full cap (single-router behavior unchanged)
    assert _endpoint_cap({"endpoint_cap": 10}, URL) == 10.0


def test_slo_peer_signal_and_attribute_skip():
    """Peer freshness samples feed router_peer_lost; engine /load
    samples do NOT (attribute-gated), and vice versa."""
    from production_stack_tpu.signals import EngineLoad
    from production_stack_tpu.slo import SLOEngine, default_config
    from production_stack_tpu.router.shared_state import _PeerSignal

    eng = SLOEngine(default_config())
    eng.ingest_engine_loads({
        "http://peer:1": _PeerSignal(peer_age_s=2.0, scraped_at=1.0),
        "http://engine:1": EngineLoad(est_queue_delay_ms=100.0),
    }, now=1000.0)
    good, bad = eng.window_counts("router_peer_lost", "5m", now=1000.0)
    assert (good, bad) == (1, 0)
    good, bad = eng.window_counts("engine_queue_delay", "5m",
                                  now=1000.0)
    assert (good, bad) == (1, 0)          # only the engine record
    # a dark peer (age past the 10s bound) burns
    eng.ingest_engine_loads({
        "http://peer:1": _PeerSignal(peer_age_s=45.0, scraped_at=2.0),
    }, now=1001.0)
    good, bad = eng.window_counts("router_peer_lost", "5m", now=1001.0)
    assert (good, bad) == (1, 1)


def test_collector_accepts_multiple_router_urls():
    """The autoscaler's /health cross-check asks every router replica
    and takes the max — one replica mid-restart must not zero it."""
    from production_stack_tpu.autoscaler.collector import (
        SignalCollector)

    async def body():
        async def health_app(n):
            app = web.Application()

            async def h(request):
                return web.json_response({"healthy_endpoints": n})
            app.router.add_get("/health", h)
            server = TestServer(app)
            await server.start_server()
            return server

        s1, s2 = await health_app(3), await health_app(2)
        urls = [f"http://127.0.0.1:{s.port}" for s in (s1, s2)]
        dead = "http://127.0.0.1:1"
        col = SignalCollector(lambda: [],
                              router_url=",".join(urls + [dead]))
        assert col.router_urls == urls + [dead]
        await col.start()
        try:
            assert await col._router_healthy() == 3
            await s1.close()              # best replica goes dark
            assert await col._router_healthy() == 2
        finally:
            await col.close()
            await s2.close()
    asyncio.run(body())


# ------------------------------------------------------------ smokes

def test_multirouter_smoke_fake_engines(tmp_path):
    """Tier-1 multirouter smoke: 2 real peered routers behind the L4
    splitter — affinity matches the single-router control through a
    one-sided drain, the breaker converges on both replicas, a router
    SIGKILL costs only the counted blip, and the saturation sweep
    holds tier0 while tier2 sheds."""
    from production_stack_tpu.loadgen.multirouter import (
        multirouter_violations, run_multirouter)
    record = asyncio.run(run_multirouter(
        engines=3, routers=2, sessions=8, phase_duration_s=4.5,
        saturation_presat_s=2.5, settle_s=1.5, seed=1,
        convergence_storm_s=5.0,
        log_dir=str(tmp_path / "logs")))
    # smoke gates are loosened vs the committed full-size run (0.95
    # tier0 hold, 5% affinity, one probe interval): the short windows
    # carry connection-setup warmup and the suite runs it on a loaded
    # host — the smoke pins the MECHANICS, MULTIROUTER_r16.json pins
    # the numbers
    # tier0's hold is asserted below on what the routers admitted and
    # shed, not as the ratio of two goodputs (min_tier0_hold): that
    # ratio compares a 2.5 s window with a 4.5 s one, in the second of
    # which the tier2 clients share this host's cores with the routers,
    # and read 0.57-0.99 on one tree as the host's load varied
    violations = multirouter_violations(record, min_tier0_hold=0.0,
                                        affinity_tolerance=0.08,
                                        convergence_bound_s=1.5)
    assert not violations, violations
    d = record["detail"]
    pre0 = d["saturation"]["presat"]["tier0"]
    sat0 = d["saturation"]["saturated"]["tier0"]
    assert pre0["ok"] > 0 and pre0["shed"] == 0, pre0
    # saturated: tier2 sheds at least half (a violation above if not),
    # tier0 is admitted all but never (it sheds only where nothing is
    # left to preempt)
    assert sat0["ok"] > 0 and sat0["shed_fraction"] <= 0.1, sat0
    assert d["router_kill"]["kill_fired"]
    assert d["router_kill"]["post_restart_ok"] > 0


def test_multirouter_no_shared_state_fails_affinity(tmp_path):
    """Anti-vacuity: the identical rig with the gossip plane dark must
    FAIL the affinity gate — the one-sided drain splits the routers'
    endpoint views and sessions land on two engines at once."""
    from production_stack_tpu.loadgen.multirouter import (
        multirouter_violations, run_multirouter)
    record = asyncio.run(run_multirouter(
        engines=3, routers=2, sessions=8, phase_duration_s=5.0,
        settle_s=1.5, shared_state=False, seed=1,
        skip_kill=True, skip_saturation=True, skip_convergence=True,
        log_dir=str(tmp_path / "logs")))
    violations = multirouter_violations(record)
    assert any("affinity" in v for v in violations), (
        "the --no-shared-state run passed the affinity gate — the "
        "shared-state plane is not load-bearing", record["detail"])


@pytest.mark.slow
def test_chaos_router_kill_smoke(tmp_path):
    """Chaos with the --router-kill schedule: router replicas
    SIGKILLed behind the splitter, client errors confined to the blip
    windows. Slow tier: the multirouter smoke's kill phase already
    pins the same mechanics in tier-1."""
    from production_stack_tpu.loadgen.chaos import (chaos_violations,
                                                    run_chaos)
    record = asyncio.run(run_chaos(
        engines=3, users=4, duration_s=16.0, kill_interval_s=6.0,
        downtime_s=1.5, error_burst_interval_s=None,
        stream_fraction=0.2, num_tokens=4, seed=1,
        router_kill=True, router_kill_interval_s=5.0,
        router_downtime_s=1.5, log_dir=str(tmp_path / "logs")))
    violations = chaos_violations(record)
    assert not violations, violations
    assert record["detail"]["router_kills"] >= 1


@pytest.mark.slow
def test_multirouter_full_fake(tmp_path):
    """Full-size multirouter run (the committed-record shape) plus the
    shared-state overhead guard."""
    from production_stack_tpu.loadgen.multirouter import (
        multirouter_violations, run_multirouter)
    record = asyncio.run(run_multirouter(
        engines=3, routers=2, sessions=12, phase_duration_s=20.0,
        saturation_presat_s=8.0, seed=0, overhead_guard=True,
        log_dir=str(tmp_path / "logs")))
    violations = multirouter_violations(record,
                                        max_overhead_ratio=2.5)
    assert not violations, violations
