"""End to end on the CPU at tiny sizes: one closed and one open cell,
through router and engine as on the chip, down to one well-formed last
line; and the two ways a run must refuse to give a result. A run keeps
its children's logs in a directory of its own (``run.run_dir``), so
these tests may run side by side."""

import json
import os
import subprocess
import sys

from chipbench import run as runner

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
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    # each number compared beside its limit: the line's last key, and
    # the last lines of standard error
    assert list(line)[-1] == "compared"
    said = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert said == [f"chipbench: compared {k} = {v}, limit {limit}"
                    for k, (v, limit) in line["compared"].items()]
    # a run that was correct leaves nothing of its own behind
    assert not os.path.exists(runner.run_dir(workload, 2**31 + 77, trace))
    return line


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
    compared = line["compared"]
    assert compared["requests_failed"] == [0, 0]
    assert compared["counts_unreconciled"] == [0, 0]
    for i, row in enumerate(line["probe"]["rows"]):
        assert compared[f"probe{i}_logprob_gap"] == [
            row["max_abs_logprob_diff"], 0.3]
        assert compared[f"probe{i}_shared_top"] == [row["shared_top"], 10]
    # the CPU runs no kernel: a rehearsal is not held to one
    assert "executables_off_kernels" not in compared


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
    # the run at fault keeps ITS engine's log, where no other run writes
    where = runner.run_dir("mistral7b-decode-closed", 1, 0)
    assert where.startswith(os.path.join(ROOT, ".chipbench", "runs"))
    with open(os.path.join(where, "logs", "engine.log")) as f:
        assert "no accelerator" in f.read()


def test_runs_that_differ_share_no_directory():
    dirs = {runner.run_dir(w, s, t) for w in ("a-cell", "b-cell")
            for s in (1, 2**31 + 77) for t in (0, 1)}
    assert len(dirs) == 8
    assert all(os.path.dirname(d) == os.path.join(ROOT, ".chipbench", "runs")
               for d in dirs)


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


def test_probe_seeds_reads_program_and_control_under_the_files_limits(
        tmp_path):
    """The builder's tool for a probe's limits (chipbench/
    probe_seeds.py) at the tiny hybrid file, which here states limits
    of its own on both of the probe's numbers: the program reads under
    them, the float8 control in the program's place over them, the
    bfloat16 witness decides nothing, and the line keeps what each side
    said. About 40 s (an engine start and five reference passes): the
    tool is run on the chip once in many PRs, and only this keeps it
    working in between."""
    with open(os.path.join(BASE, "BENCHMARK.hybrid.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(BASE, "configs", "tiny-gdn.json")) as f:
        conf = json.load(f)
    conf["harness"] = {**conf.get("harness", {}),
                       "probe": {"logprob_gap_limit": 0.08,
                                 "mean_logprob_gap_limit": 0.03}}
    for c in manifest["configs"]:
        c["file"] = os.path.join(ROOT, c["file"])
        if c["name"] == "tiny-gdn":
            c["file"] = str(tmp_path / "tiny-gdn.json")
    (tmp_path / "tiny-gdn.json").write_text(json.dumps(conf))
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    seed = 2**31 + 81
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "probe_seeds.py"),
         "--manifest", str(tmp_path / "manifest.json"), "--data", BASE,
         "--rehearse", "--workload", "tiny-gdn-closed", "--seeds",
         str(seed), "--tag", "t", "--out-dir", str(tmp_path),
         "--control", 'fp8:round_to="float8_e4m3fn"',
         "--report", 'bf16:round_to="bfloat16"'],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["seeds_read"] == 1
    assert summary["limits_now"] == {"gap": 0.08, "mean": 0.03}
    lower, upper = (summary["lower_program_max"],
                    summary["upper_control_min"]["fp8"])
    assert lower["gap"] < 0.08 < 3 * 0.08 < upper["gap"]
    assert lower["mean"] < 0.03 < 3 * 0.03 < upper["mean"]
    assert summary["report_max"]["bf16"]["gap"] < 0.08
    (row,) = [json.loads(x) for x in
              (tmp_path / "t.jsonl").read_text().splitlines()]
    probe, detail = row["probe"], row["probe"]["detail"]
    assert probe["ok"] and len(detail["served"]) == 3
    assert not detail["controls"]["fp8"]["ok"]
    assert detail["controls"]["bf16"]["ok"]
    assert len(detail["controls"]["fp8"]["served"][0]["ids"]) == 20
    assert not os.path.exists(runner.run_dir("tiny-gdn-closed", seed,
                                             "probe"))
