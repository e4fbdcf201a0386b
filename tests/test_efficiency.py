"""Engine efficiency telemetry (ISSUE 11): window accounting units
with an injected clock, BlockManager fragmentation accounting, the
scrape-time delta sync, and the real-engine perf surfaces
(/load perf block, /debug/perf, xla_compile trace events).

Tiers:
- unit — EngineEffAccounting with ``now_fn`` injection (reconciliation
  math, ring-derived rates, compile event overlap) and BlockManager
  fragmentation counters (alloc-failure classification, occupancy
  observer, state census) — no engine, no device;
- metrics — EngineMetrics.sync_eff/sync_kvpool delta semantics and
  exposition names;
- engine — a real debug-tiny AsyncLLMEngine behind the aiohttp server
  launched WITHOUT warmup, so the first request's XLA compiles happen
  mid-request and must surface as counters, /debug/perf events, AND
  xla_compile spans on that request's trace.
"""

import asyncio

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.block_manager import BlockManager
from production_stack_tpu.engine.efficiency import (DRAIN_REASONS,
                                                    EngineEffAccounting,
                                                    OCCUPANCY_BUCKETS)
from production_stack_tpu.engine.metrics import EngineMetrics
from production_stack_tpu.tracing import PhaseHistograms


# ------------------------------------------------------------ unit tier

class _Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.mark.parametrize("read,resident", [(96, 96), (60, 96), (0, 96)])
def test_window_bytes_leave_out_the_experts_not_read(read, resident):
    """A MoE window's modelled bytes are the steps' whole weight set
    less the experts off the steps' lists (ops/moe.py, the list path),
    and ``totals.moe`` sums what note_window was told; a dense engine
    (expert_bytes 0) reports no ``moe`` and subtracts nothing."""
    acct = EngineEffAccounting(weight_bytes=1000, kv_position_bytes=10,
                               expert_bytes=7)
    dense = EngineEffAccounting(weight_bytes=1000, kv_position_bytes=10)
    window = dict(steps=8, positions=1, batch=4, live_rows=4, kv_len=100,
                  real=32, pad=0, dead=0, window_s=0.5)
    for _ in range(2):
        acct.note_window(**window, experts_read=read,
                         experts_resident=resident)
        dense.note_window(**window)
    whole = 2 * 8 * (1000 + 4 * 10 * 100)
    r = acct.report()
    assert r["moe"] == {"experts_read": 2 * read,
                        "experts_resident": 2 * resident}
    assert r["bytes_total"] == whole - 2 * (resident - read) * 7
    assert r["bytes_effective"] == r["bytes_total"]
    assert "moe" not in dense.report()
    assert dense.report()["bytes_total"] == whole


def test_window_accounting_reconciles_with_injected_clock():
    """A steady synthetic stream of windows: kind totals must equal the
    independent token_steps_total, and the ring-derived rates must
    match hand-computed values at the injected timestamps."""
    clock = _Clock()
    acct = EngineEffAccounting(weight_bytes=1000, kv_position_bytes=10,
                               hbm_peak_bytes_per_s=1e6, now_fn=clock)
    # 10 windows, 1s apart: batch 4, 8 steps, 1 position; 2 live rows
    # emitting fully (16 real), 2 parked (16 pad), 0 dead
    for i in range(10):
        clock.t = float(i + 1)
        acct.note_window(steps=8, positions=1, batch=4, live_rows=2,
                         kv_len=100, real=16, pad=16, dead=0,
                         window_s=0.5)
    r = acct.report()
    dec = r["decode"]
    assert dec["real"] == 160 and dec["pad"] == 160
    assert dec["dead"] == 0
    assert dec["token_steps_total"] == 10 * 4 * 8
    assert dec["real"] + dec["pad"] + dec["dead"] == \
        dec["token_steps_total"]
    # per-window bytes: 8 * (1000 + 4*10*100) = 40000; half effective
    assert r["bytes_total"] == 10 * 8 * (1000 + 4000)
    assert r["bytes_effective"] == r["bytes_total"] // 2
    rates = acct.rates(horizon_s=10.0, now=10.0)
    # all 10 windows inside the horizon; 40000 bytes each, half live
    assert rates["total_bytes_per_s"] == pytest.approx(40000.0)
    assert rates["effective_bytes_per_s"] == pytest.approx(20000.0)
    assert rates["mbu_perc"] == pytest.approx(2.0)
    assert rates["live_fraction"] == pytest.approx(0.5)
    assert rates["decode_tokens_per_s"] == pytest.approx(16.0)
    # a narrower horizon sees only the last windows (cutoff is
    # inclusive: t in {5..10} = 6 windows over 5 seconds)
    rates5 = acct.rates(horizon_s=5.0, now=10.0)
    assert rates5["decode_tokens_per_s"] == pytest.approx(6 * 16 / 5.0)
    assert rates5["horizon_s"] == pytest.approx(5.0)


def test_window_accounting_speculative_positions_and_dead():
    """Speculative windows: positions = spec+1 per macro-step; rejected
    draft positions and finished tails land in dead, and the kinds
    still sum to the independent total."""
    acct = EngineEffAccounting(now_fn=_Clock(1.0))
    # batch 2, 4 macro-steps, 3 positions each; one live row emitted 7
    # tokens across its macro-steps, one row parked
    total = 2 * 4 * 3
    pad = 1 * 4 * 3
    real = 7
    dead = total - pad - real
    acct.note_window(steps=4, positions=3, batch=2, live_rows=1,
                     kv_len=64, real=real, pad=pad, dead=dead,
                     window_s=0.1)
    d = acct.report()["decode"]
    assert d["token_steps_total"] == total
    assert d["real"] + d["pad"] + d["dead"] == total
    assert d["dead"] == 5


def test_prefill_padding_accounting():
    acct = EngineEffAccounting(now_fn=_Clock(1.0))
    # bucket 64 over batch 8 = 512 positions; 100 real chunk tokens
    acct.note_prefill(bucket=64, batch=8, real_tokens=100)
    p = acct.report()["prefill"]
    assert p["real"] == 100 and p["pad"] == 412
    assert p["dispatches"] == 1 and p["by_rows"] == {"8": 1}
    # a one-row dispatch pads only to its chunk bucket
    acct.note_prefill(bucket=64, batch=1, real_tokens=40)
    p = acct.report()["prefill"]
    assert p["real"] == 140 and p["pad"] == 412 + 24
    assert p["by_rows"] == {"1": 1, "8": 1}
    assert p["chunks_by_path"] == {}


def test_prefill_chunks_are_counted_by_their_attention_path():
    """totals.prefill.chunks_by_path: the chunks of every dispatch
    under the attention path of the executable that ran it."""
    acct = EngineEffAccounting(now_fn=_Clock(1.0))
    acct.note_prefill(
        bucket=2048, batch=1, real_tokens=2048, chunks=1,
        attention_path="pallas_paged_latent_expanded_sparse")
    acct.note_prefill(
        bucket=2048, batch=1, real_tokens=2048, chunks=1,
        attention_path="pallas_paged_latent_expanded_sparse")
    acct.note_prefill(bucket=128, batch=4, real_tokens=300, chunks=3,
                      attention_path="pallas_paged_latent")
    p = acct.report()["prefill"]
    assert p["chunks_by_path"] == {
        "pallas_paged_latent": 3,
        "pallas_paged_latent_expanded_sparse": 2}
    assert p["dispatches"] == 3


@pytest.mark.parametrize("drained", [None, *DRAIN_REASONS])
def test_prefill_dispatches_are_counted_by_their_path(drained):
    """``totals.step``: a dispatch behind the windows in flight, or one
    that drained the queue, by reason; the step's ring entry names it;
    the two sum to ``totals.prefill.dispatches``."""
    acct = EngineEffAccounting(now_fn=_Clock(1.0))
    with acct.step():
        acct.note_prefill(bucket=64, batch=1, real_tokens=40,
                          drained=drained)
        acct.note_prefill(bucket=64, batch=1, real_tokens=40)
    with acct.step():
        pass
    r = acct.report()
    step = r["step"]
    assert set(step["prefill_drained"]) == set(DRAIN_REASONS)
    assert step["prefill_behind"] == (2 if drained is None else 1)
    assert step["prefill_drained"] == {
        k: int(k == drained) for k in DRAIN_REASONS}
    assert (step["prefill_behind"] + sum(step["prefill_drained"].values())
            == r["prefill"]["dispatches"] == 2)
    first, second = acct.recent_steps()
    key = "prefill_behind" if drained is None else "drained_" + drained
    assert first[key] >= 1 and first["prefill_behind"] >= 1
    assert not [k for k in second if "prefill" in k or "drained" in k]


def test_compile_tracking_and_event_overlap():
    clock = _Clock(0.0)
    hist = PhaseHistograms(("kind", "window", "kv_bucket"),
                           buckets=(1.0, 10.0))
    acct = EngineEffAccounting(now_fn=clock, compile_hist=hist)
    acct.compile_started("decode", 8, 512, 4)
    assert acct.report()["compile_in_flight"] == 1
    acct.compile_finished("decode", 8, 512, started_at=5.0, dur_s=2.5,
                          batch=4)
    acct.compile_started("prefill", 64, 256, 8)
    acct.compile_finished("prefill", 64, 256, started_at=20.0,
                          dur_s=0.5, batch=8)
    r = acct.report()
    assert r["compile_in_flight"] == 0
    assert r["compiles_total"] == 2
    assert r["compiles"]["decode|8|512|4"]["count"] == 1
    assert r["compiles"]["decode|8|512|4"]["seconds"] == \
        pytest.approx(2.5)
    # duration histogram got both observations under their labels
    # (snapshot values are (cumulative buckets, sum, count))
    snap = hist.snapshot()
    assert snap[("decode", "8", "512")][1] == pytest.approx(2.5)
    assert snap[("decode", "8", "512")][2] == 1
    # overlap filter: [6.0, 7.0] overlaps the decode compile (5.0-7.5)
    # but not the prefill one (20.0-20.5)
    events = acct.compile_events_between(6.0, 7.0)
    assert [e[2] for e in events] == ["decode"]
    # an interval strictly between the two catches neither
    assert acct.compile_events_between(10.0, 19.0) == []
    # recent_compiles renders both
    assert len(acct.recent_compiles()) == 2


def test_window_ring_is_bounded():
    acct = EngineEffAccounting(ring_entries=8, now_fn=_Clock(1.0))
    for _ in range(50):
        acct.note_window(steps=1, positions=1, batch=1, live_rows=1,
                         kv_len=1, real=1, pad=0, dead=0,
                         window_s=0.01)
    assert len(acct.recent_windows(100)) == 8
    assert acct.report()["decode"]["windows"] == 50   # totals keep all


def test_rates_clamp_to_ring_coverage():
    """Regression: a busy engine whose ring evicts entries faster than
    the horizon drains must divide by the span the ring actually
    witnessed, not the full horizon — otherwise every rate understates
    by the eviction ratio."""
    clock = _Clock(0.0)
    acct = EngineEffAccounting(weight_bytes=0, kv_position_bytes=1,
                               ring_entries=4, now_fn=clock)
    # 20 windows, 0.1s apart: ring keeps only the last 4 (t=1.7..2.0)
    for i in range(20):
        clock.t = 0.1 * (i + 1)
        acct.note_window(steps=1, positions=1, batch=1, live_rows=1,
                         kv_len=1, real=10, pad=0, dead=0,
                         window_s=0.05)
    rates = acct.rates(horizon_s=10.0, now=2.0)
    # oldest resident entry is at t=1.7 -> 0.3s coverage holding 3
    # entries within (1.7, 2.0]... the t=1.7 entry itself is included
    # (cutoff inclusive): 4 entries * 10 real / 0.3s
    assert rates["decode_tokens_per_s"] == pytest.approx(40 / 0.3,
                                                         rel=1e-3)
    # an un-evicted ring still divides by uptime
    acct2 = EngineEffAccounting(ring_entries=100, now_fn=_Clock(0.0))
    acct2._started_at = 0.0
    acct2.note_window(steps=1, positions=1, batch=1, live_rows=1,
                      kv_len=1, real=10, pad=0, dead=0, window_s=0.05)
    assert acct2.rates(horizon_s=10.0,
                       now=2.0)["decode_tokens_per_s"] == \
        pytest.approx(5.0)


def test_window_accounting_variable_geometry():
    """Continuous batching across windows: consecutive windows change
    batch bucket AND window length; the kind totals must still equal
    the independent total and the byte-model rates stay finite."""
    clock = _Clock()
    acct = EngineEffAccounting(weight_bytes=500, kv_position_bytes=4,
                               hbm_peak_bytes_per_s=1e6, now_fn=clock)
    # (batch_bucket, steps, live_rows, real): a churny sequence —
    # bucket 8 full, bucket 4 with a finished tail, bucket 2 draining,
    # bucket 8 again after admissions, a 1-step mid-window-admission
    # window
    shapes = [(8, 8, 8, 64), (4, 8, 3, 20), (2, 4, 2, 8),
              (8, 2, 7, 14), (1, 1, 1, 1)]
    expect_total = 0
    expect_real = 0
    for i, (b, w, live, real) in enumerate(shapes):
        clock.t = float(i + 1)
        total = b * w
        pad = (b - live) * w
        dead = total - pad - real
        assert dead >= 0
        acct.note_window(steps=w, positions=1, batch=b, live_rows=live,
                         kv_len=128, real=real, pad=pad, dead=dead,
                         window_s=0.01 * w)
        expect_total += total
        expect_real += real
    d = acct.report()["decode"]
    assert d["token_steps_total"] == expect_total
    assert d["real"] + d["pad"] + d["dead"] == expect_total
    assert d["real"] == expect_real
    rates = acct.rates(horizon_s=10.0, now=5.0)
    for key in ("effective_bytes_per_s", "total_bytes_per_s",
                "mbu_perc", "decode_tokens_per_s"):
        v = rates[key]
        assert v >= 0 and v == v and v != float("inf"), (key, v)
    assert 0.0 < rates["live_fraction"] < 1.0
    # the ring keeps per-window geometry for /debug/perf diagnosis
    ring = acct.recent_windows(10)
    assert [(w["batch"], w["steps"], w["live_rows"]) for w in ring] == \
        [(b, w, l) for (b, w, l, _) in shapes]


def test_config_bucket_derivation_and_lookup():
    from production_stack_tpu.engine.config import EngineConfig
    cfg = EngineConfig(max_num_seqs=8, decode_window=8)
    assert cfg.window_adapt
    assert cfg.decode_batch_buckets == (1, 2, 4, 8)
    assert cfg.decode_window_buckets == (1, 2, 4, 8)
    assert cfg.batch_bucket_for(3) == 4
    assert cfg.batch_bucket_for(8) == 8
    assert cfg.batch_bucket_for(99) == 8      # clamped to the cap
    # non-power-of-two caps are always covered
    cfg6 = EngineConfig(max_num_seqs=6, decode_window=6)
    assert cfg6.decode_batch_buckets == (1, 2, 4, 6)
    # custom sets: filtered to range, cap appended when missing
    cfgc = EngineConfig(decode_batch_buckets=(2, 3, 99),
                        decode_window_buckets=(4,))
    assert cfgc.decode_batch_buckets == (2, 3, 8)
    assert cfgc.decode_window_buckets == (4, 8)
    with pytest.raises(ValueError):
        EngineConfig(decode_batch_buckets=(0, -3))
    # speculation pins fixed geometry: the spec executable only warms
    # at the full shape, so adaptation would compile mid-serving
    assert not EngineConfig(speculative_ngram_tokens=3).window_adapt


def test_non_hot_variant_pins_fixed_geometry():
    """A window needing an executable variant outside the warmed
    (greedy/plain) grid — here full-sort sampling via top_p < 1 —
    must dispatch at the FULL fixed geometry: that variant warms at
    the full shape only, and adapting it would compile a cold
    executable per geometry reached, mid-serving."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions
    cfg = EngineConfig(model="debug-tiny", max_model_len=128,
                       max_num_seqs=4, prefill_chunk=32,
                       prefill_buckets=(16, 32))
    eng = LLMEngine(cfg)
    eng.add_request(
        eng.tokenizer.encode("full sort variant pins geometry"),
        SamplingOptions(temperature=1.0, top_p=0.5, max_tokens=6,
                        ignore_eos=True), seq_id="s")
    for _ in range(200):
        if any(o.finished for o in eng.step()):
            break
    ring = eng.eff.recent_windows(50)
    assert ring, "no decode windows recorded"
    assert all(w["batch"] == cfg.max_num_seqs
               and w["steps"] == cfg.decode_window for w in ring), \
        [(w["batch"], w["steps"]) for w in ring]


def test_kv_bucket_above_grid_pins_fixed_geometry():
    """The warmup grid exists at the smallest kv bucket only: a
    window whose attention length lands in a LARGER bucket must
    dispatch at the full fixed geometry (one lazy compile per
    variant, the pre-r17 cost) instead of walking the adaptive grid
    cold at that bucket."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions
    cfg = EngineConfig(model="debug-tiny", max_model_len=256,
                       max_num_seqs=4, prefill_chunk=32,
                       prefill_buckets=(32, 64),
                       kv_len_buckets=(64, 256))
    eng = LLMEngine(cfg)
    # ~90-token prompt: every decode window's attention length sits
    # in the 256 bucket, above the warmed 64 bucket
    eng.add_request(
        eng.tokenizer.encode("kv bucket pin " * 7),
        SamplingOptions(temperature=0.0, max_tokens=6,
                        ignore_eos=True), seq_id="s")
    for _ in range(200):
        if any(o.finished for o in eng.step()):
            break
    ring = eng.eff.recent_windows(50)
    assert ring, "no decode windows recorded"
    assert all(w["kv_len"] == 256 and w["batch"] == cfg.max_num_seqs
               and w["steps"] == cfg.decode_window for w in ring), \
        [(w["kv_len"], w["batch"], w["steps"]) for w in ring]


def test_admission_imminent_respects_kv_gate():
    """The mid-window-admission lever must not fire when the last
    scheduler pass deferred the head waiter on the KV admission gate:
    a waiter + free slot does not mean the next pass admits, and
    shortening windows / pausing the pipeline under pool pressure
    costs fusion for nothing."""
    from production_stack_tpu.engine.scheduler import (Scheduler,
                                                       SamplingOptions,
                                                       Sequence)
    sched = Scheduler(max_num_seqs=2, max_model_len=64,
                      prefill_chunk=16)
    sched.add(Sequence("w1", list(range(4)), SamplingOptions()))
    admit = {"ok": False}
    sched.can_admit = lambda seq: admit["ok"]
    sched.schedule()
    assert sched.waiting and sched.free_slots and sched.kv_deferred
    admit["ok"] = True
    sched.schedule()
    assert not sched.kv_deferred and not sched.waiting


def test_engine_variable_geometry_reconciles_with_compaction():
    """A real (CPU, debug-tiny) engine through a churny composition:
    three rows with different budgets admitted together, so windows
    shrink as rows finish, the batch bucket steps down 4 -> 2 -> 1,
    and the survivors are COMPACTED into the low slots mid-stream —
    through all of it real+pad+dead must equal the independent total
    and real must equal exactly the decode-emitted tokens."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.scheduler import SamplingOptions
    cfg = EngineConfig(model="debug-tiny", max_model_len=128,
                       max_num_seqs=4, prefill_chunk=32,
                       prefill_buckets=(16, 32))
    eng = LLMEngine(cfg)
    budgets = {"a": 3, "b": 9, "c": 21}
    for name, mt in budgets.items():
        eng.add_request(
            eng.tokenizer.encode("variable geometry " + name * 3),
            SamplingOptions(temperature=0.0, max_tokens=mt,
                            ignore_eos=True), seq_id=name)
    done = set()
    slots_seen = set()
    for _ in range(400):
        for out in eng.step():
            if out.finished:
                done.add(out.seq_id)
        if "b" in done and "c" not in done:
            # only c remains: compaction must have packed it low
            slots_seen.add(eng.seqs["c"].slot)
        if len(done) == 3:
            break
    assert done == set(budgets)
    # c started at slot 2 (admission order) and must have been
    # remapped to slot 0 once a and b finished
    assert 0 in slots_seen
    for name, mt in budgets.items():
        assert len(eng.seqs[name].output_tokens) == mt
    rep = eng.eff.report()
    d = rep["decode"]
    assert d["token_steps_total"] > 0
    assert d["real"] + d["pad"] + d["dead"] == d["token_steps_total"]
    # decode-real = every emitted token minus the prefill-sampled first
    assert d["real"] == sum(budgets.values()) - len(budgets)
    ring = eng.eff.recent_windows(100)
    assert len({w["batch"] for w in ring}) >= 2, \
        "batch bucket never adapted"
    assert len({w["steps"] for w in ring}) >= 2, \
        "window length never adapted"
    assert all(w["batch"] >= w["live_rows"] for w in ring)
    rates = eng.eff.rates()
    assert rates["decode_tokens_per_s"] >= 0


# --------------------------------------------------- block manager tier

def test_block_manager_alloc_failure_classification():
    bm = BlockManager(num_blocks=5, block_size=4)   # 4 allocatable
    got = bm.alloc(3)
    assert got is not None and len(got) == 3
    # 1 free remains: asking for 2 is the fragmentation regime
    assert bm.alloc(2) is None
    assert bm.alloc_failures_fragmented == 1
    assert bm.alloc_failures_exhausted == 0
    # drain the pool: now a failure is true exhaustion
    assert bm.alloc(1) is not None
    assert bm.alloc(1) is None
    assert bm.alloc_failures_exhausted == 1
    # zero-block requests (fully prefix-shared prompts) are not
    # allocation attempts
    allocs_before = bm.allocs
    assert bm.alloc(0) == []
    assert bm.allocs == allocs_before
    assert bm.alloc(-1) is None
    report = bm.frag_report()
    assert report["alloc_failures_fragmented"] == 1
    assert report["alloc_failures_exhausted"] == 1
    assert report["blocks_allocated"] == 4


def test_block_manager_state_census_and_evictions():
    bm = BlockManager(num_blocks=5, block_size=2,
                      enable_prefix_caching=True)
    blocks = bm.alloc(2)
    assert bm.frag_report()["active"] == 2
    assert bm.frag_report()["free"] == 2
    # register + free: the blocks become evictable cache, not free
    tokens = [1, 2, 3, 4]
    assert bm.register(tokens, blocks) == 2
    bm.free(blocks)
    rep = bm.frag_report()
    assert rep["active"] == 0 and rep["cached"] == 2 and rep["free"] == 2
    # allocating past the free list reclaims cached blocks (LRU) and
    # counts the evictions
    got = bm.alloc(4)
    assert got is not None and len(got) == 4
    assert bm.cache_evictions == 2
    assert bm.frag_report()["cached"] == 0


def test_block_manager_occupancy_observer():
    seen = []
    bm = BlockManager(num_blocks=5, block_size=4)
    bm.on_alloc_occupancy = seen.append
    bm.alloc(2)          # observed at usage 0.0
    bm.alloc(2)          # observed at usage 0.5
    bm.alloc(1)          # observed at usage 1.0 (fails, still observed)
    assert seen == [0.0, 0.5, 1.0]
    # the metrics layer's histogram shape accepts these observations
    hist = PhaseHistograms((), buckets=OCCUPANCY_BUCKETS)
    for v in seen:
        hist.observe(v)
    (cum, total, n), = hist.snapshot().values()
    assert n == 3 and total == pytest.approx(1.5)


# -------------------------------------------------------- metrics tier

def test_metrics_delta_sync_eff_and_kvpool():
    m = EngineMetrics(model="t")
    acct = EngineEffAccounting(hbm_peak_bytes_per_s=1e9,
                               weight_bytes=100,
                               kv_position_bytes=1,
                               now_fn=_Clock(1.0))
    acct.note_window(steps=4, positions=1, batch=2, live_rows=1,
                     kv_len=8, real=4, pad=4, dead=0, window_s=0.1)
    m.sync_eff(acct.report(), acct.rates(now=1.0))
    m.sync_eff(acct.report(), acct.rates(now=1.0))   # idempotent resync
    text = m.render().decode()
    assert 'tpu:engine_token_steps_total{kind="real",model_name="t",' \
           'phase="decode"} 4.0' in text
    assert 'tpu:engine_token_steps_total{kind="pad",model_name="t",' \
           'phase="decode"} 4.0' in text
    # a second window advances counters by the delta only
    acct.note_window(steps=4, positions=1, batch=2, live_rows=1,
                     kv_len=8, real=3, pad=4, dead=1, window_s=0.1)
    m.sync_eff(acct.report(), acct.rates(now=1.0))
    text = m.render().decode()
    assert 'kind="real",model_name="t",phase="decode"} 7.0' in text
    assert 'kind="dead",model_name="t",phase="decode"} 1.0' in text
    bm = BlockManager(num_blocks=5, block_size=4)
    bm.alloc(4)
    bm.alloc(1)
    m.sync_kvpool(bm.frag_report())
    m.sync_kvpool(bm.frag_report())
    text = m.render().decode()
    assert 'tpu:kvpool_blocks{model_name="t",state="active"} 4.0' in text
    assert 'tpu:kvpool_alloc_failures_total{model_name="t",' \
           'reason="exhausted"} 1.0' in text
    assert "tpu:engine_mbu_perc" in text
    assert "tpu:decode_window_live_fraction" in text
    assert "tpu:engine_compile_seconds" in text
    assert "tpu:kvpool_alloc_occupancy" in text


# --------------------------------------------------------- engine tier

@pytest.fixture(scope="module")
def cold_engine():
    """A real debug-tiny engine with NO warmup: the first request's
    XLA compiles happen mid-request, which is exactly what the compile
    observability must make visible."""
    from production_stack_tpu.engine.async_engine import AsyncLLMEngine
    from production_stack_tpu.engine.config import EngineConfig
    cfg = EngineConfig(model="debug-tiny", max_model_len=128,
                       max_num_seqs=2, prefill_chunk=32,
                       prefill_buckets=(16, 32))
    return AsyncLLMEngine(cfg)


def _with_client(engine, coro, **build_kw):
    from production_stack_tpu.engine.server import build_app

    async def runner():
        app = build_app(engine, **build_kw)
        async with TestClient(TestServer(app)) as client:
            return await coro(client)
    return asyncio.run(runner())


def test_engine_perf_surfaces_and_compile_trace(cold_engine):
    async def body(client):
        body = {"model": "debug-tiny",
                "messages": [{"role": "user", "content": "measure me"}],
                "max_tokens": 6, "temperature": 0.0,
                "ignore_eos": True}
        r = await client.post("/v1/chat/completions", json=body)
        assert r.status == 200
        trace_id = r.headers["x-trace-id"]
        # /load perf block: the request's decode steps are accounted
        r = await client.get("/load")
        perf = (await r.json())["perf"]
        steps = perf["token_steps"]
        assert steps["real"] == 5          # 6 tokens, first = prefill
        assert steps["token_steps_total"] == \
            steps["real"] + steps["pad"] + steps["dead"]
        assert perf["compiles_total"] >= 2   # cold start compiled
        assert perf["compile_in_flight"] == 0
        assert perf["weight_bytes"] > 0
        # /debug/perf: window ring + compile events + pool census
        r = await client.get("/debug/perf?limit=50")
        assert r.status == 200
        dp = await r.json()
        assert dp["windows"], "no window breakdowns recorded"
        w = dp["windows"][-1]
        # adaptive dispatch: one live row -> batch bucket 1 (not the
        # configured max_num_seqs=2); the 5-step decode budget walks a
        # 4-step window then a final 1-step one (the dead-budget cap
        # rejects the 8 bucket: a 3-step tail on one live row)
        assert w["batch"] == 1 and w["steps"] == 1
        assert [x["steps"] for x in dp["windows"][-2:]] == [4, 1]
        assert {"real", "pad", "dead", "kv_len", "live_rows",
                "window_s"} <= set(w)
        kinds = [e["kind"] for e in dp["compiles"]]
        assert "decode" in kinds and "prefill" in kinds
        # a carry brings its edit by slot with it (runner.edit_carry)
        assert "carry_edit" in kinds
        # compile events carry the dispatched batch bucket
        assert all("batch" in e for e in dp["compiles"])
        assert dp["kv_pool"]["active"] == 0   # request finished
        assert dp["totals"]["compiles_total"] == len(dp["compiles"])
        # the compile-stalled request's trace carries xla_compile
        # events (the compiles overlapped its life)
        r = await client.get(f"/debug/traces?trace_id={trace_id}")
        traces = (await r.json())["traces"]
        assert traces
        compile_spans = [s for s in traces[0]["spans"]
                         if s["name"] == "xla_compile"]
        assert compile_spans, "cold-start compiles missing from trace"
        assert compile_spans[0]["kind"] == "event"
        assert "kind" in compile_spans[0]["attrs"]
        # /metrics exposition carries the new families with live values
        r = await client.get("/metrics")
        text = (await r.read()).decode()
        assert 'tpu:engine_token_steps_total{kind="real"' in text
        assert "tpu:engine_compiles_total{" in text
        assert "tpu:engine_compile_seconds_bucket" in text
        assert "tpu:kvpool_blocks{" in text
    _with_client(cold_engine, body)


def test_debug_perf_behind_api_key(cold_engine):
    """/debug/perf follows /debug/traces' auth posture: enforced when
    an API key is configured (probe endpoints stay open)."""
    async def body(client):
        r = await client.get("/debug/perf")
        assert r.status == 401
        r = await client.get("/debug/perf",
                             headers={"Authorization": "Bearer sk"})
        assert r.status == 200
        r = await client.get("/load")   # probe surface stays open
        assert r.status == 200
        assert "perf" in await r.json()
    _with_client(cold_engine, body, api_key="sk")


def test_ring_entries_carry_wall_clock_stamps():
    """Window and compile ring entries are stamped with ``at_unix``
    (wall clock) alongside the monotonic ``at`` — the obsplane flight
    recorder aligns engine rings with trace spans across processes,
    which monotonic stamps (per-process epoch) cannot do."""
    wall = _Clock(1000.0)
    acct = EngineEffAccounting(now_fn=_Clock(5.0), wall_fn=wall)
    acct.note_window(steps=2, positions=1, batch=4, live_rows=3,
                     kv_len=256, real=6, pad=2, dead=0, window_s=0.1)
    entry = acct.recent_windows(1)[0]
    assert entry["at_unix"] == pytest.approx(1000.0)
    assert entry["at"] == pytest.approx(5.0)
    acct.compile_started("decode", 8, 512, 4)
    acct.compile_finished("decode", 8, 512, started_at=5.0, dur_s=2.0,
                          batch=4)
    row = acct.recent_compiles(1)[0]
    # wall stamp of the compile START: wall-at-finish minus duration
    assert row["at_unix"] == pytest.approx(998.0)
    assert row["duration_s"] == pytest.approx(2.0)
    # the trace-seal hook keeps its 6-tuple shape (server.py unpacks)
    events = acct.compile_events_between(5.5, 6.0)
    assert len(events) == 1 and len(events[0]) == 6
