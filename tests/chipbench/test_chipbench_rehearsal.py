"""End to end on the CPU at tiny sizes: one closed and one open cell,
through router and engine as on the chip, down to one well-formed last
line; and the two ways a run must refuse to give a result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BASE = os.path.join(ROOT, "tests", "chipbench", "rehearsal")


def bench(*args, env=None, cwd=ROOT):
    return subprocess.run(
        [sys.executable, "-m", "chipbench", *args], cwd=cwd,
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def rehearse(workload, trace):
    proc = bench("--manifest", os.path.join(BASE, "BENCHMARK.json"),
                 "--data", BASE, "--rehearse", "--workload", workload,
                 "--seed", str(2**31 + 77), "--seconds", "3",
                 "--trace", str(trace))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def well_formed(line, names):
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True, line["why"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    assert set(line["metrics"]) <= set(names)
    for m in line["metrics"].values():
        assert isinstance(m["value"], (int, float)) and m["unit"]
    assert line["probe"]["ok"] and len(line["probe"]["rows"]) == 3


def test_closed_cell_end_to_end():
    line = rehearse("tiny-dense-closed", 0)
    e2e = ["tpot_p50_ms", "out_tokens_per_s", "setup_s"]    # closed loop
    well_formed(line, e2e)
    assert set(line["metrics"]) == set(e2e)
    assert "breakdown" not in line
    # the window opened on a full, staggered batch and stayed full
    assert line["notes"]["in_flight_open"] == 4
    assert line["notes"]["in_flight_close"] == 4
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0


def test_open_cell_end_to_end_traced():
    with open(os.path.join(BASE, "BENCHMARK.json")) as f:
        per_layer = [m["name"] for m in json.load(f)["per_layer"]]
    line = rehearse("tiny-moe-open", 1)
    well_formed(line, per_layer)
    got = set(line["metrics"])
    # host-side per-layer metrics are read on the CPU too ...
    assert {"loadgen_lag_p95_ms", "itl_p95_client_ms", "ttft_p50_client_ms",
            "router_self_p50_ms",
            "engine_queue_wait_p50_ms", "kv_alloc_failures",
            "decode_live_share", "compiles_in_window",
            "warmup_executables"} <= got
    # ... and no device metric is ever printed from a CPU run
    assert not got & {"decode_step_device_ms", "decode_step_roofline",
                      "prefill_dispatch_device_ms", "device_idle_share",
                      "hbm_peak_share"}
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert set(line["end_to_end"]) == {"tpot_p50_ms", "out_tokens_per_s",
                                       "setup_s"}


def test_without_a_chip_there_is_no_result():
    proc = bench("--workload", "mistral7b-decode-closed", "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the paths."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = bench("--workload", "mistral7b-decode-closed", "--seed", "1",
                 "--seconds", "1", "--trace", "0", cwd=str(tmp_path),
                 env={"PYTHONPATH": ""})
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""


def test_an_unknown_workload_is_an_error():
    proc = bench("--workload", "nope", "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert proc.returncode == 2 and "no workload" in proc.stderr
