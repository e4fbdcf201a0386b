"""The per-layer readers on a synthetic run record: each finds its
number where it is and returns nothing where there is nothing."""

import glob
import json
import os

import pytest

from chipbench import manifest as mf
from chipbench import run as runner

SPECS = {}
for path in glob.glob(os.path.join(mf.HERE, "metrics", "*.json")):
    with open(path) as f:
        SPECS[os.path.basename(path)[:-5]] = json.load(f)


def synthetic():
    def perf(real, total, fails, compiles):
        return {"totals": {"decode": {"real": real,
                                      "token_steps_total": total},
                           "compiles_total": compiles},
                "kv_pool": {"alloc_failures_exhausted": fails,
                            "alloc_failures_fragmented": 0},
                "device": {"engine_devices": [
                    {"peak_bytes_in_use": 8e9, "bytes_limit": 16e9}]},
                "windows": [
                    {"at_unix": 1000.5, "steps": 8, "live_rows": 16,
                     "window_s": 0.4},
                    {"at_unix": 1001.0, "steps": 8, "live_rows": 15,
                     "window_s": 0.44},
                    {"at_unix": 1001.5, "steps": 4, "live_rows": 16,
                     "window_s": 0.2},
                    {"at_unix": 2000.0, "steps": 8, "live_rows": 1,
                     "window_s": 9.0}],
                "compiles": [{"at_unix": 900.0}, {"at_unix": 1005.0}]}

    def trace(tid, started, total, phases):
        return {"trace_id": tid, "status": "ok", "started_at": started,
                "duration_ms": total, "attrs": {},
                "spans": [{"name": n, "kind": "phase", "duration_ms": d}
                          for n, d in phases.items()]}
    records = [
        {"due": 101.0, "sent": 101.002, "done": True, "max_tokens": 3,
         "prompt_tokens": 100, "token_times": [102.0, 102.0, 102.5]},
        {"due": 104.0, "sent": 104.010, "done": False, "max_tokens": 400,
         "prompt_tokens": 200, "token_times": [104.5, 105.0]}]
    return {
        "window": {"t0": 100.0, "t1": 110.0, "t0_unix": 1000.0,
                   "t1_unix": 1010.0},
        "records": records,
        "perf_open": perf(1000, 1200, 0, 19),
        "perf_close": perf(1900, 2200, 2, 20),
        "load_samples": [{"kv_pool": {"active": a, "num_blocks": 400}}
                         for a in (80, 100, 120)],
        "engine_traces": {"traces": [
            trace("a", 1001.0, 900.0, {"queue_wait": 5.0, "prefill": 600.0}),
            trace("b", 1002.0, 900.0, {"queue_wait": 15.0, "prefill": 700.0}),
            trace("c", 1003.0, 900.0, {"queue_wait": 25.0, "prefill": 800.0}),
            trace("z", 50.0, 900.0, {"queue_wait": 999.0})]},
        "router_traces": {"traces": [
            trace("a", 1001.0, 1000.0, {"backend_ttfb": 600.0,
                                        "relay": 399.5, "routing": 0.2})]},
        "trace": {"started_unix": 1000.4, "held_s": 1.0, "busy_s": 2.7,
                  "window_s": 3.0,
                  "modules": {
                      # 7 runs of 8 steps and one of 2: 58 steps of 32
                      # layers, 3.132 s
                      "jit__unknown_1_": {
                          "runs": 7, "total_s": 3.024, "median_s": 0.432,
                          "ops": {"paged_decode_attention": [7 * 8 * 32,
                                                             0.4]}},
                      "jit__unknown_2_": {
                          "runs": 1, "total_s": 0.108, "median_s": 0.108,
                          "ops": {"paged_decode_attention": [2 * 32, 0.01],
                                  "fusion": [9, 0.05]}},
                      "jit__unknown_9_": {
                          "runs": 2, "total_s": 1.3, "median_s": 0.65,
                          "ops": {"paged_attention": [64, 0.2]}},
                      "jit_convert_element_type_5_": {
                          "runs": 30, "total_s": 1e-5, "median_s": 5e-7,
                          "ops": {}}}},
        "device": {"kind": "TPU v5 lite"},
        "config_file": os.path.join(mf.HERE, "configs",
                                    "mistral-7b-int8.json"),
    }


EXPECTED = {
    "loadgen_lag_p95_ms": 10.0,
    "itl_p95_client_ms": 500.0,
    "itl_p99_ms": 500.0,
    "ttft_p50_client_ms": 750.0,
    "router_self_p50_ms": 0.5,
    "engine_queue_wait_p50_ms": 15.0,
    "engine_prefill_phase_p50_ms": 700.0,
    "kv_alloc_failures": 2.0,
    "kv_live_share": 25.0,
    "decode_live_share": 90.0,
    "decode_live_rows_p50": 16,
    "decode_step_host_ms": 1e3 * 1.04 / 20,
    "compiles_in_window": 1.0,
    "warmup_executables": 19.0,
    "decode_step_device_ms": 54.0,
    "prefill_dispatch_device_ms": 650.0,
    "device_idle_share": 10.0,
    "hbm_peak_share": 50.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_its_number(name):
    value = runner.read_metric(SPECS[name], synthetic(), [])
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)


def test_roofline_share_is_least_time_over_measured_time():
    run = synthetic()
    # at the middle of the traced interval (t = 100.9 on the clients'
    # clock) no request of this record was decoding yet
    assert runner.read_metric(SPECS["decode_step_roofline"], run,
                              []) is None
    run["trace"]["started_unix"] = 1004.2        # middle: t = 104.7
    value = runner.read_metric(SPECS["decode_step_roofline"], run, [])
    note = run["notes"]["decode_step_roofline"]
    assert (note["rows"], note["context_tokens"]) == (1, 201)
    assert note["bound"] == "bytes"
    assert value == pytest.approx(100 * note["seconds"] / 0.054, rel=1e-6)
    assert 0 < value < 100


@pytest.mark.parametrize("name", sorted(set(SPECS) - {
    "kv_alloc_failures", "decode_live_share", "compiles_in_window",
    "warmup_executables", "hbm_peak_share"}))
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    run = synthetic()
    run.update(records=[], load_samples=[], trace=None,
               engine_traces={"traces": []}, router_traces={"traces": []})
    run["perf_close"]["windows"] = []
    assert runner.read_metric(SPECS[name], run, []) is None


def test_every_metric_of_the_manifest_is_covered_here():
    assert set(EXPECTED) | {"decode_step_roofline"} == set(SPECS)
