"""The per-layer metrics of the step timeline (PR 24) on a hand-made
run record: each reader finds its number where the program writes it,
and reads as nothing, without raising, on the record of a program that
does not write it yet (the parent commit, which the driver measures
with these same files).

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import, as
the later tables do: every worker imports every test file while it
collects, so the completeness check there sees these eight covered."""

import copy
import glob
import json
import os

import pytest
import test_chipbench_readers as first

from chipbench import manifest as mf
from chipbench import run as runner

SPECS = {}
for path in glob.glob(os.path.join(mf.HERE, "metrics", "*.json")):
    with open(path) as f:
        SPECS[os.path.basename(path)[:-5]] = json.load(f)

PHASES = ("expire", "schedule", "drain_sync", "drain_process",
          "prefill_host", "prefill_dispatch", "prefill_sync",
          "prefill_process", "decode_host", "decode_dispatch",
          "decode_sync", "decode_process", "housekeeping",
          "between_steps", "no_work", "compile")


def perf(scale: float, windows) -> dict:
    """``GET /debug/perf`` with the timeline's totals at ``scale``
    windows of 10 s: per 10 s, 7 s in decode_sync, 1.5 in prefill_sync,
    0.5 in drain_sync, 0.45 waiting for work, none in compile, and
    0.05 in each of the other eleven phases."""
    per = dict.fromkeys(PHASES, 0.05)
    per.update(decode_sync=7.0, prefill_sync=1.5, drain_sync=0.5,
               no_work=0.45, compile=0.0)
    return {"totals": {"step": {
                "steps": int(24 * scale),
                "wall_s": 10.0 * scale,
                "phase_s": {k: v * scale for k, v in per.items()},
                "starved_s": 0.12 * scale,
                "starved_by_phase": {"prefill_host": 0.12 * scale}}},
            "windows": windows}


def trace(tid, started, events, status="ok"):
    spans = [{"name": "queue_wait", "kind": "phase", "duration_ms": 900.0},
             {"name": "prefill", "kind": "phase", "duration_ms": 800.0}]
    spans += [{"name": n, "kind": "event", "duration_ms": d}
              for n, d in events]
    return {"trace_id": tid, "status": status, "started_at": started,
            "duration_ms": 5000.0, "attrs": {}, "spans": spans}


def hand_made() -> dict:
    windows = [
        {"at_unix": 1000.5, "steps": 8, "window_s": 0.42, "host_s": 0.012,
         "sync_s": 0.4},
        {"at_unix": 1001.0, "steps": 8, "window_s": 0.42, "host_s": 0.010,
         "sync_s": 0.4},
        {"at_unix": 1001.5, "steps": 4, "window_s": 0.21, "host_s": 0.008,
         "sync_s": 0.2},
        {"at_unix": 2000.0, "steps": 8, "window_s": 9.0, "host_s": 5.0,
         "sync_s": 4.0}]          # dispatched after the window closed
    return {
        "window": {"t0": 100.0, "t1": 110.0, "t0_unix": 1000.0,
                   "t1_unix": 1010.0},
        "perf_open": perf(3.0, []),
        "perf_close": perf(4.0, windows),
        "engine_traces": {"traces": [
            trace("a", 1001.0, [("lock_wait", 100.0), ("prefill_wait", 500.0),
                                ("first_token_emit", 410.0)]),
            trace("b", 1002.0, [("lock_wait", 300.0), ("prefill_wait", 600.0),
                                ("first_token_emit", 430.0)]),
            trace("c", 1003.0, [("lock_wait", 200.0), ("prefill_wait", 700.0),
                                ("first_token_emit", 420.0),
                                ("xla_compile", 9999.0)]),
            # not ok, and started before the window: neither counts
            trace("d", 1004.0, [("lock_wait", 9999.0)], status="http_503"),
            trace("z", 50.0, [("lock_wait", 9999.0)])]},
        "router_traces": {"traces": []},
        "trace": {"busy_s": 2.8, "window_s": 3.0, "device_planes": 1,
                  "modules": {
                      "jit_decode_window_1_": {"runs": 5, "total_s": 2.1},
                      "jit_prefill_chunk_7_": {"runs": 2, "total_s": 0.5},
                      "jit_prefill_chunk_8_": {"runs": 1, "total_s": 0.2},
                      "jit_convert_element_type_5_": {"runs": 30,
                                                      "total_s": 1e-5}}},
    }


def parent_shaped() -> dict:
    """The same run as a program without the timeline records it."""
    run = copy.deepcopy(hand_made())
    for key in ("perf_open", "perf_close"):
        del run[key]["totals"]["step"]
        for w in run[key]["windows"]:
            del w["host_s"], w["sync_s"]
    for t in run["engine_traces"]["traces"]:
        t["spans"] = [s for s in t["spans"] if s["kind"] == "phase"]
    run["trace"]["modules"] = {
        "jit__unknown_%d_" % i: m for i, m in
        enumerate(run["trace"]["modules"].values())}
    return run


EXPECTED = {
    # 11 host phases x 0.05 s of 10 s
    "step_host_work_share": 5.5,
    "device_starved_share": 1.2,
    # drain 0.5 + 0.05, prefill 0.05 + 0.05 + 1.5 + 0.05, of 10 s
    "prefill_loop_share": 22.0,
    "decode_host_ms_per_step": 1e3 * 0.030 / 20,
    "engine_lock_wait_p50_ms": 200.0,
    "engine_prefill_wait_p50_ms": 600.0,
    "engine_first_token_emit_p50_ms": 420.0,
    "prefill_device_share": 25.0,
}
first.EXPECTED.update(EXPECTED)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_its_number(name):
    value = runner.read_metric(SPECS[name], hand_made(), [])
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reads_as_nothing_on_a_program_without_the_timeline(name):
    assert runner.read_metric(SPECS[name], parent_shaped(), []) is None


def test_prefill_device_share_over_several_device_planes():
    run = hand_made()
    run["trace"]["device_planes"] = 4       # total_s sums the planes
    assert runner.read_metric(SPECS["prefill_device_share"], run, []) \
        == pytest.approx(25.0 / 4)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_manifest_entry_matches_the_metric_file(name):
    with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == SPECS[name][key]
    assert "workloads" not in entry

