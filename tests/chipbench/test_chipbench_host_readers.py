"""The per-layer metrics of ISSUE 38 (the device queue's depth at a
dispatch, the engine thread off the processor, the loop timeline, the
token's way to the socket) and the two counters PR 28 and PR 36 left
without a reader, on a fabricated run record: each reader finds its
number where the program writes it, and reads as nothing, without
raising, on the record of a program that does not write it (the parent
commit, which the driver measures with these same files).

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import, as
``test_chipbench_latent.py``'s does: every worker imports every test
file while it collects, so the completeness check there sees these
nine covered. Where the nine stand in the manifest, and that every
cell reports them, is ``test_chipbench_manifest.py``'s
(``manifest_history/pr38.json``).
"""

import copy
import json
import os

import pytest
import test_chipbench_readers as first

from chipbench import manifest as mf
from chipbench import run as runner

NEW = ("dispatch_dry_share", "step_host_offcpu_share", "loop_busy_share",
       "loop_stream_share", "loop_lag_p95_ms", "engine_preprocess_p50_ms",
       "engine_first_token_write_p50_ms", "prefill_behind_share",
       "prefill_real_share")
# what a program without ISSUE 38 has nothing to read for; the other
# three read a phase and two counters the parent writes already
NEW_IN_THE_PROGRAM = NEW[:5] + NEW[6:7]
with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
SPECS = {n: mf.load(os.path.join(mf.HERE, "metrics", n + ".json"))
         for n in NEW}
HOST_PHASES = ("expire", "schedule", "drain_process", "prefill_host",
               "prefill_dispatch", "prefill_process", "decode_host",
               "decode_dispatch", "decode_process", "housekeeping",
               "between_steps")
ALL_PHASES = HOST_PHASES + ("drain_sync", "prefill_sync", "decode_sync",
                            "no_work", "compile")
REASONS = ("guided", "shaped", "resume", "speculation", "reshape",
           "pressure")


def perf(at: float) -> dict:
    """``GET /debug/perf`` of a program with ISSUE 38 after ``at``
    windows of 10 s. Per 10 s: 400 dispatches, 28 of them into a dry
    queue; the engine thread 2 ms off the processor in each of its
    eleven host phases and 7 s in the decode sync; the loop 1.3 s on
    the processor, 0.9 s in the three pieces of a token's way out over
    9 000 payloads; 50 prefill dispatches, 45 behind the queue; 6 000
    prompt tokens in 8 000 computed positions."""
    off = dict.fromkeys(ALL_PHASES, 0.0)
    off.update(dict.fromkeys(HOST_PHASES, 0.002), decode_sync=7.0)
    return {"totals": {
        "step": {
            "wall_s": 10.0 * at,
            "offcpu_s": {k: v * at for k, v in off.items()},
            "dispatch_depth": {"0": 28 * at, "1": 300 * at, "2": 70 * at,
                               "3_or_more": 2 * at},
            "prefill_behind": 45 * at,
            "prefill_drained": {**dict.fromkeys(REASONS, 0),
                                "reshape": 4 * at, "pressure": 1 * at}},
        "loop": {"wall_s": 10.0 * at, "cpu_s": 1.3 * at,
                 "serialize_s": 0.5 * at, "write_s": 0.3 * at,
                 "dispatch_s": 0.1 * at, "payloads": 9000 * at},
        "prefill": {"real": 6000 * at, "pad": 2000 * at}}}


def trace(tid, started, preprocess, write=None, status="ok"):
    spans = [{"name": "preprocess", "kind": "phase",
              "duration_ms": preprocess},
             {"name": "decode", "kind": "phase", "duration_ms": 3000.0}]
    if write is not None:
        spans.append({"name": "first_token_write", "kind": "event",
                      "duration_ms": write})
    return {"trace_id": tid, "status": status, "started_at": started,
            "duration_ms": 4000.0, "attrs": {}, "spans": spans}


def fabricated() -> dict:
    # the loop ring: 21 samples in the window, lags 0.0, 0.1 .. 2.0 ms,
    # one before it and one after it that must not count
    ring = [{"at_unix": 990.0, "lag_s": 0.9, "cpu_s": 0.01}]
    ring += [{"at_unix": 1000.0 + 0.4 * i, "lag_s": 0.0001 * i,
              "cpu_s": 0.01} for i in range(21)]
    ring += [{"at_unix": 1010.0, "lag_s": 0.9, "cpu_s": 0.01}]
    return {
        "window": {"t0": 100.0, "t1": 110.0, "t0_unix": 1000.0,
                   "t1_unix": 1010.0},
        "perf_open": perf(3.0),
        "perf_close": {**perf(4.0), "loop": ring},
        "engine_traces": {"traces": [
            trace("a", 1001.0, 2.0, 0.10),
            trace("b", 1002.0, 4.0, 0.30),
            trace("c", 1003.0, 3.0, 0.20),
            # not ok, and started before the window: neither counts
            trace("d", 1004.0, 99.0, 99.0, status="http_503"),
            trace("z", 50.0, 99.0, 99.0)]},
        "router_traces": {"traces": []},
        "trace": None,
    }


def parent_shaped() -> dict:
    """The same run as the parent commit records it: no depth, no
    processor seconds, no loop account or ring, no write stamps."""
    run = copy.deepcopy(fabricated())
    del run["perf_close"]["loop"]
    for key in ("perf_open", "perf_close"):
        del run[key]["totals"]["loop"]
        del run[key]["totals"]["step"]["offcpu_s"]
        del run[key]["totals"]["step"]["dispatch_depth"]
    for t in run["engine_traces"]["traces"]:
        t["spans"] = [s for s in t["spans"] if s["kind"] == "phase"]
    return run


EXPECTED = {
    "dispatch_dry_share": 7.0,              # 28 of 400
    "step_host_offcpu_share": 0.22,         # 11 x 2 ms of 10 s
    "loop_busy_share": 13.0,
    "loop_stream_share": 9.0,
    "loop_lag_p95_ms": 1.9,                 # of 0.0, 0.1 .. 2.0
    "engine_preprocess_p50_ms": 3.0,
    "engine_first_token_write_p50_ms": 0.2,
    "prefill_behind_share": 90.0,           # 45 of 50
    "prefill_real_share": 75.0,
}
first.EXPECTED.update(EXPECTED)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_finds_its_number(name):
    value = runner.read_metric(SPECS[name], fabricated(), [])
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)


@pytest.mark.parametrize("name", NEW_IN_THE_PROGRAM)
def test_reads_as_nothing_on_a_program_without_it(name):
    assert runner.read_metric(SPECS[name], parent_shaped(), []) is None


@pytest.mark.parametrize("name", sorted(set(NEW) - set(NEW_IN_THE_PROGRAM)))
def test_reads_what_the_parent_writes_already(name):
    """``preprocess`` has been a phase of every engine trace, and the
    two counters were written since PR 28 and PR 36: the parent gives
    the same number, so the driver compares these from the first."""
    assert runner.read_metric(SPECS[name], parent_shaped(), []) == \
        pytest.approx(EXPECTED[name], rel=1e-6)


def test_perf_ring_reads_nothing_where_the_ring_is_absent():
    spec = SPECS["loop_lag_p95_ms"]
    run = fabricated()
    del run["perf_close"]["loop"]
    assert runner.read_metric(spec, run, []) is None
    run["perf_close"]["loop"] = []
    assert runner.read_metric(spec, run, []) is None
    # a ring whose entries lack the field, or lie outside the window
    run["perf_close"]["loop"] = [{"at_unix": 1001.0, "cpu_s": 0.01}]
    assert runner.read_metric(spec, run, []) is None
    run["perf_close"]["loop"] = [{"at_unix": 990.0, "lag_s": 0.5},
                                 {"at_unix": 1010.0, "lag_s": 0.5}]
    assert runner.read_metric(spec, run, []) is None


@pytest.mark.parametrize("ring, field, reduction, scale, want", [
    ("loop", "lag_s", "p50", 1000, 1.0),
    ("loop", "lag_s", "sum", 1, 0.021),
    ("loop", "cpu_s", "count", 1, 21.0),
    ("steps", "offcpu_s", "sum", 1000, 6.0)])
def test_perf_ring_reads_any_ring_of_debug_perf(ring, field, reduction,
                                                scale, want):
    run = fabricated()
    run["perf_close"]["steps"] = [
        {"at_unix": 1000.0 + i, "offcpu_s": 0.002} for i in range(3)]
    spec = {"reader": "perf_ring", "args": {
        "ring": ring, "field": field, "reduction": reduction,
        "scale": scale}}
    assert runner.read_metric(spec, run, []) == pytest.approx(want)


def test_a_window_without_dispatches_or_payloads_reads_nothing():
    run = fabricated()
    run["perf_close"] = {**copy.deepcopy(run["perf_open"]), "loop": []}
    for name in ("dispatch_dry_share", "loop_busy_share",
                 "prefill_behind_share", "prefill_real_share",
                 "loop_lag_p95_ms"):
        assert runner.read_metric(SPECS[name], run, []) is None


def test_offcpu_share_sums_the_phases_the_host_work_share_sums():
    host = mf.load(os.path.join(mf.HERE, "metrics",
                                "step_host_work_share.json"))
    assert SPECS["step_host_offcpu_share"]["args"]["paths"] == [
        p.replace(".phase_s.", ".offcpu_s.")
        for p in host["args"]["paths"]]
    assert SPECS["step_host_offcpu_share"]["args"]["over"] == \
        host["args"]["over"]
    from production_stack_tpu.engine.efficiency import HOST_WORK_PHASES
    assert sorted(HOST_WORK_PHASES) == sorted(
        p.rsplit(".", 1)[1] for p in host["args"]["paths"])


def test_the_counted_keys_are_the_programs():
    """The manifest's paths name every key of the two counters they sum
    over: a depth or a drain reason the program gains must be added."""
    from production_stack_tpu.engine.efficiency import (DEPTH_KEYS,
                                                        DRAIN_REASONS)
    assert SPECS["dispatch_dry_share"]["args"]["over"] == [
        "totals.step.dispatch_depth." + k for k in DEPTH_KEYS]
    assert SPECS["prefill_behind_share"]["args"]["over"] == [
        "totals.step.prefill_behind"] + [
        "totals.step.prefill_drained." + r for r in DRAIN_REASONS]


@pytest.mark.parametrize("name", NEW)
def test_manifest_entry_matches_the_metric_file(name):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == SPECS[name][key]
    assert "workloads" not in entry         # every cell reports it
    assert set(SPECS[name]) == {"name", "unit", "better", "source",
                                "layer", "moves", "reader", "args"}


def test_layers_and_sources_are_the_manifests_own():
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW}
    for name in NEW:
        assert SPECS[name]["layer"] in layers
        assert SPECS[name]["source"] in ("program_counter", "program_span")
