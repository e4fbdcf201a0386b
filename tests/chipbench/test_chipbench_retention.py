"""What PR 47 added to the benchmark for ``brumby14b-decode-closed``:
the manifest's new entries as the manifest then is, the retention
yardstick (chipbench/roofline_retention.py) against hand counts, the
new readers on a hand-made record, the kernel clause of ``correct`` for
a model that has no attention path at all, the configuration's file
against the catalog's published keys, and the CPU rehearsal of the cell
at a tiny ``brumby`` file (``rehearsal/BENCHMARK.retention.json``,
``rehearsal/configs/tiny-brumby.json``). The plain reference
(chipbench/references/brumby) against the program is tests/
test_retention.py's.

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import, as
test_chipbench_hybrid's does. Where PR 47's entries stand in the
manifest is ``manifest_history/pr47.json``'s (test_chipbench_manifest).
"""

import json
import os
import subprocess
import sys

import pytest
import test_chipbench_readers as first

from chipbench import (engine_child, harness_key, roofline,
                       roofline_retention)
from chipbench import manifest as mf
from chipbench import run as runner

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "brumby14b-decode-closed"
CONFIG = os.path.join(mf.HERE, "configs", "brumby-14b-int8-l10.json")
NEW = ("retention_decode_step_roofline", "retention_decode_kernel_roofline",
       "retention_prefill_kernel_roofline",
       "retention_state_bytes_per_step")
with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(CONFIG) as f:
    BRUMBY = json.load(f)
SPECS = {n: mf.load(os.path.join(mf.HERE, "metrics", n + ".json"))
         for n in NEW}
# S [8256, 128] and z [8256] in float32, 8 key-value heads
LAYER_STATE = 8 * 8256 * 129 * 4
PAGE = 10 * LAYER_STATE


def record():
    """test_chipbench_readers' synthetic run as a run of the new cell:
    the configuration's file; 58 decode steps of 10 retention calls
    each in 1.74 s; three runs of a 256-token prefill executable (one
    of 16 rows, two of one); the counters ``totals.state`` and
    ``totals.prefill``; one request decoding while traced."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"]["started_unix"] = 1004.2          # middle: t = 104.7
    run["trace"]["modules"] = {
        "jit_decode_window_1_": {
            "runs": 7, "total_s": 1.68, "median_s": 0.24,
            "ops": {"retention_recurrent_step": [7 * 8 * 10, 1.12]}},
        "jit_decode_window_2_": {
            "runs": 1, "total_s": 0.06, "median_s": 0.06,
            "ops": {"retention_recurrent_step": [2 * 10, 0.04]}},
        "jit_prefill_chunk_9_": {
            "runs": 3, "total_s": 0.3, "median_s": 0.1,
            "ops": {"retention_chunk_scan": [30, 0.06]}}}
    for at, steps, disp, one, full in (("perf_open", 100, 10, 8, 2),
                                       ("perf_close", 158, 13, 10, 3)):
        totals = run[at]["totals"]
        totals["state"] = {"steps": steps, "step_rows": 15 * steps,
                           "step_bytes": steps * 2 * 16 * PAGE,
                           "scan_bytes": 0, "scan_tokens": 0,
                           "prefill_keys": 0, "pages_alloc": 0,
                           "pages_freed": 0, "alloc_failures": 0}
        # a dispatch: 256 positions a row, real and padded
        totals["prefill"] = {"real": 200 * (one + 16 * full),
                             "pad": 56 * (one + 16 * full),
                             "dispatches": disp,
                             "by_rows": {"1": one, "16": full}}
        run[at]["kv_pool"].update(bytes_per_token=0, layout="state",
                                  state_bytes_per_slot=PAGE)
    return run


def _least(needs):
    return max(needs["bytes"] / 819e9, needs["ops"] / 197e12)


# one live row; a prefill dispatch of (2 x 1 + 16) / 3 = 6 rows of 256
_STEP = roofline_retention.decode_step_needs(BRUMBY, 1)
_CALL1 = roofline_retention.retention_call_needs(BRUMBY, 1, 1)
_CHUNK = roofline_retention.retention_call_needs(BRUMBY, 6.0, 6 * 256.0)
EXPECTED = {
    "retention_decode_step_roofline": 100 * _least(_STEP) / (1.74 / 58),
    "retention_decode_kernel_roofline":
        100 * _least(_CALL1) / (1.16 / 580),
    "retention_prefill_kernel_roofline": 100 * _least(_CHUNK) / (0.06 / 30),
    "retention_state_bytes_per_step": 2.0 * 16 * PAGE,
}
first.EXPECTED.update(EXPECTED)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_finds_its_number(name):
    value = runner.read_metric(SPECS[name], record(), [])
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)
    if name.endswith("_roofline"):
        assert 0 < value <= 100


@pytest.mark.parametrize("name", NEW)
def test_new_reader_reads_nothing_from_a_program_without_it(name):
    """A record of a program without power retention (no such counters
    in ``totals.state``, no trace; and, for the trace's readers,
    another configuration's file with a trace that happens to hold the
    kernels' names): None, nothing raised: what the parent commit
    gives the driver's traced runs of the accepted cells."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"] = None
    assert runner.read_metric(SPECS[name], run, []) is None
    other = first.synthetic()           # Mistral's file: no such model
    other["trace"]["started_unix"] = 1004.2
    for op in ("retention_recurrent_step", "retention_chunk_scan"):
        other["trace"]["modules"]["jit__unknown_1_"]["ops"][op] = [1, 0.1]
    assert runner.read_metric(SPECS[name], other, []) is None
    hybrid = first.synthetic()          # state pages, counted as PR 42 did
    hybrid["config_file"] = CONFIG
    for at in ("perf_open", "perf_close"):
        hybrid[at]["totals"]["state"] = {"step_rows": 5, "scan_tokens": 9}
    assert runner.read_metric(
        SPECS["retention_state_bytes_per_step"], hybrid, []) is None


def test_the_step_note_names_the_retention_yardstick():
    run = record()
    runner.read_metric(SPECS["retention_decode_step_roofline"], run, [])
    note = run["notes"]["decode_step_roofline"]
    assert (note["rows"], note["bound"], note["yardstick"]) \
        == (1, "bytes", "roofline_retention")


def test_the_listless_step_metrics_read_this_cells_executables():
    """``decode_step_device_ms`` and ``prefill_dispatch_device_ms``
    find the cell's executables by the operations its file names (its
    ``harness`` key): 10 calls of the step kernel a step."""
    def spec(name):
        return mf.load(os.path.join(mf.HERE, "metrics", name + ".json"))
    run = record()
    assert runner.read_metric(spec("decode_step_device_ms"), run, []) \
        == pytest.approx(1e3 * 1.74 / 58)
    assert runner.read_metric(spec("prefill_dispatch_device_ms"), run, []) \
        == pytest.approx(100.0)


def test_the_yardstick_counts_the_issue_arithmetic():
    """ISSUE 47's cut, parameter by parameter; 545 MB of state a layer
    at 16 rows; a step's 9.53 GB least, the state's write not in it."""
    quantised, small = roofline_retention.layer_weights(BRUMBY)
    assert quantised == (2 * 5120 * 5120 + 2 * 5120 * 1024
                         + 3 * 5120 * 17408) == 330301440
    assert small == 5120 * 8 + 8 + 2 * 128 + 2 * 5120
    assert roofline_retention.sizes(BRUMBY) == (40, 8, 128, 8256)
    assert roofline_retention.state_bytes(BRUMBY) == LAYER_STATE \
        == 8 * 4260096 == 34080768
    assert PAGE == 340807680
    assert 16 * LAYER_STATE == 545292288            # "545 MB a layer"
    cfg = engine_child.model_config(BRUMBY, "b")
    assert cfg.state_bytes_per_seq == PAGE
    assert cfg.num_params == (10 * (quantised + small)
                              + 2 * 151936 * 5120 + 5120)
    assert abs(cfg.num_params / 4.86e9 - 1) < 0.002
    assert (cfg.attn_layers, cfg.gdn_layers, cfg.ret_layers,
            cfg.ret_features) == (0, 0, 10, 8256)
    step = roofline_retention.decode_step_needs(BRUMBY, 16)
    per_token = (40 + 16) * 128 * 2 + 8 * 4 + 40 * 128 * 2
    assert step["bytes"] == (
        10 * quantised + 151936 * 5120 + 2 * (10 * small + 5120)
        + 10 * (16 * LAYER_STATE + 16 * per_token))
    assert abs(step["bytes"] / 9.534e9 - 1) < 0.002
    least = roofline.least_seconds(step, "TPU v5 lite")
    assert least["bound"] == "bytes"
    assert 11.4e-3 < least["seconds"] < 11.9e-3
    # the state is 57 % of the least and, written as well as read, 73 %
    # of the 14.98 GB a step of the recurrent form moves
    moved = step["bytes"] + 10 * 16 * LAYER_STATE
    assert abs(moved / 14.98e9 - 1) < 0.003
    assert 0.72 < 2 * 10 * 16 * LAYER_STATE / moved < 0.74
    call = roofline_retention.retention_call_needs(BRUMBY, 16, 16)
    assert call["bytes"] == 16 * LAYER_STATE + 16 * per_token
    assert call["ops"] == 16 * 8 * (13 * 8256 * 128 + 24 * 8256)
    assert roofline.least_seconds(call, "TPU v5 lite")["bound"] == "bytes"
    # a chunk of one row: the state's read up to about 75 tokens, above
    # that the recurrence's operations (13 an element of S and token)
    short = roofline_retention.retention_call_needs(BRUMBY, 1, 64)
    assert roofline.least_seconds(short, "TPU v5 lite")["bound"] == "bytes"
    chunk = roofline_retention.retention_call_needs(BRUMBY, 1, 256)
    assert chunk["ops"] == 256 * 8 * (13 * 8256 * 128 + 24 * 8256)
    assert roofline.least_seconds(chunk, "TPU v5 lite")["bound"] \
        == "operations"


def test_kernels_off_for_a_model_without_an_attention_path():
    """``correct``'s kernel clause under this file's ``harness``:
    ``attention_paths`` is EMPTY and that is no fault; every executable
    must stand in ``mixer_paths`` by a kernel's name."""
    harness = harness_key.read(CONFIG)
    assert harness["kernel_tables"] == ["mixer_paths"]
    assert harness["decode_step"] == {"op": "retention_recurrent_step",
                                      "calls_per_step": 10}
    assert harness["prefill_dispatch"] == {"op": "retention_chunk_scan"}
    good = {"attention_paths": {}, "moe_paths": {}, "mixer_paths": {
        "decode|8|32768|16": "retention_recurrent",
        "prefill|256|32768|1": "retention_chunk"}}
    assert harness_key.kernels_off(good, harness) == {}
    off = {**good, "mixer_paths": {
        **good["mixer_paths"],
        "prefill|64|32768|16": "retention_chunk_jnp"}}
    assert harness_key.kernels_off(off, harness) == {
        "mixer_paths[prefill|64|32768|16]": "retention_chunk_jnp"}
    # an executable the program names in another table only is off too
    stray = {**good, "attention_paths": {"decode|1|512|1": "pallas_paged"}}
    assert harness_key.kernels_off(stray, harness) == {
        "mixer_paths[decode|1|512|1]": "not named"}
    assert "executables" in harness_key.kernels_off(
        {"attention_paths": {}, "mixer_paths": {}}, harness)
    # under the DEFAULT key the same device block fails: PR 46's reason
    assert harness_key.kernels_off(good, harness_key.of(
        {"num_hidden_layers": 10}))


def test_the_configuration_file_states_its_cut():
    """Every number of the catalog's ``config`` under the same key, the
    reduced key with its published value beside it, the deployment,
    every assumed size by name."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    assert BRUMBY["source"] == row["source_url"]
    assert BRUMBY["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert BRUMBY["published"][key] == value == 40
            assert BRUMBY[key] == 10
        else:
            assert BRUMBY[key] == value, key
    assert BRUMBY["deployment"] == {"pipeline_stages": 4, "stage_index": 0,
                                    "chips_per_layer": 1}
    for key in ("degree", "gate", "qk_norm_and_rope", "eps", "qk_scale",
                "state", "weights", "quantization", "tokenizer"):
        assert key in BRUMBY["assumed"]
    assert "switch-over" in BRUMBY["assumed"]["state"]
    assert BRUMBY["reference"] == "brumby"
    args = BRUMBY["engine_args"]
    assert args[args.index("--max-num-seqs") + 1] == "16"
    assert args[args.index("--max-model-len") + 1] == "32768"
    assert "--kv-pool-tokens" not in args and "--kv-block-size" not in args
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == BRUMBY["name"]]
    assert entry["file"] == "chipbench/configs/brumby-14b-int8-l10.json"
    assert entry["reduced"] == BRUMBY["reduced"]
    assert entry["source"] == BRUMBY["source"]


def test_the_traffic_and_the_cell_are_the_issues():
    cell = mf.Cell(MANIFEST, CELL, [])
    assert cell.chips == 1 and len(cell.why) <= 200
    assert cell.traffic_name == "decode-closed"
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 16
    assert cell.params["decode_batch_buckets"] == [16]
    from chipbench import traffic
    plan = traffic.make_plan(cell.traffic, 5, 50.0)
    assert min(plan.prompts) >= 64 and max(plan.prompts) <= 256
    assert set(plan.outputs) == {256}
    # what the traffic reaches, as the runner lays the engine out for a
    # model with state pages alone: ONE kv bucket, the batch-16 bucket
    from production_stack_tpu.engine.config import EngineConfig
    args = BRUMBY["engine_args"]
    ecfg = EngineConfig(
        model="debug-brumby", quantization="int8", max_num_seqs=16,
        max_model_len=32768,
        prefill_chunk=int(args[args.index("--prefill-chunk") + 1]))
    ecfg.kv_block_size = ecfg.max_model_len
    ecfg.kv_len_buckets = (ecfg.max_model_len,)
    assert ecfg.num_kv_blocks == 17 and ecfg.max_blocks_per_seq == 1
    shapes = engine_child.shapes_reached(ecfg, runner.reach_of(cell, plan))
    assert {kv for _, _, kv in shapes["decode"]} == {32768}
    assert {kv for _, kv in shapes["prefill"]} == {32768}
    assert all(b == 16 for b, _, _ in shapes["decode"])


@pytest.mark.parametrize("name", NEW)
def test_manifest_entry_matches_the_metric_file(name):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == SPECS[name][key]
    assert entry["workloads"] == [CELL]
    assert set(SPECS[name]) == {"name", "unit", "better", "source",
                                "layer", "moves", "reader", "args"}
    with open(os.path.join(mf.ROOT, "PERF.md")) as f:
        assert entry["layer"] in f.read()


def test_rehearsal_of_the_cell_at_a_tiny_file(tmp_path):
    """The benchmark's new cell in shape on the CPU, end to end through
    router and engine (rehearsal/BENCHMARK.retention.json): state pages
    alone behind the program's normal server entry point (admission by
    pages: 4 clients on 4 pages), the probe against
    chipbench/references/brumby.py, every listless counter metric and
    the new counter in a traced line (no device metric from a CPU
    run). From a tree of links, so that the run keeps its
    ``.chipbench/`` to itself. Some 50 s: an engine and a router
    start, 26 executables compile."""
    base = os.path.join(mf.ROOT, "tests", "chipbench", "rehearsal")
    for name in ("chipbench", "production_stack_tpu", "tests",
                 "BENCHMARK.json"):
        os.symlink(os.path.join(mf.ROOT, name), tmp_path / name)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--manifest",
         os.path.join(base, "BENCHMARK.retention.json"), "--data", base,
         "--rehearse", "--workload", "tiny-brumby-closed", "--seed",
         str(2**31 + 79), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["why"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["probe"]["ok"] and len(line["probe"]["rows"]) == 3
    got = line["metrics"]
    # 2 layers x 2 heads x (S [528, 32] + z [528]) float32 a sequence
    page = 2 * 2 * 528 * 33 * 4
    rows = got["retention_state_bytes_per_step"]["value"] / (2 * page)
    assert 1 <= rows <= 4
    assert got["compiles_in_window"]["value"] == 0
    assert got["kv_alloc_failures"]["value"] == 0
    assert 0 < got["kv_live_share"]["value"] <= 100
    with open(os.path.join(base, "BENCHMARK.retention.json")) as f:
        listless = {m["name"] for m in json.load(f)["per_layer"]
                    if "workloads" not in m}
    device = {"decode_step_device_ms", "prefill_dispatch_device_ms",
              "device_idle_share", "hbm_peak_share"}
    assert listless - device <= set(got)
    assert not set(got) & {"retention_decode_step_roofline",
                           "retention_decode_kernel_roofline",
                           "retention_prefill_kernel_roofline",
                           "device_idle_share"}
