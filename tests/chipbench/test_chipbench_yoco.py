"""What PR 50 added to the benchmark for ``phi4flash-longctx-closed``:
the manifest's new entries as the manifest then is, the yardstick of a
decoder-hybrid-decoder (chipbench/roofline_yoco.py) against hand counts
on made-up shapes and on the published ones, the new reader on a
hand-made record, the kernel clause of ``correct`` under the file's
``harness`` key, the configuration's file against the catalog's keys,
and the CPU rehearsal of the cell at a tiny ``phi4flash`` file
(``rehearsal/BENCHMARK.yoco.json``, ``rehearsal/configs/tiny-yoco.json``).
The plain reference (chipbench/references/phi4flash) against the
program is tests/test_yoco.py's.

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import, as
test_chipbench_retention's does. Where PR 50's entries stand in the
manifest is ``manifest_history/pr50.json``'s (test_chipbench_manifest).
"""

import json
import os
import subprocess
import sys

import pytest
import test_chipbench_readers as first

from chipbench import engine_child, harness_key, roofline, roofline_yoco
from chipbench import manifest as mf
from chipbench import run as runner

CELL = "phi4flash-longctx-closed"
CONFIG = os.path.join(mf.HERE, "configs", "phi4-mini-flash-int8.json")
NEW = ("yoco_decode_step_roofline", "yoco_prefill_chunk_roofline",
       "mamba_decode_kernel_roofline", "mamba_prefill_kernel_roofline",
       "shared_kv_step_share", "cross_prefill_share")
with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(CONFIG) as f:
    PHI = json.load(f)
SPECS = {n: mf.load(os.path.join(mf.HERE, "metrics", n + ".json"))
         for n in NEW}
KV_TOKEN = 20 * 64 * 2 * 2                # K and V of a token, a layer
PAGE = 16 * 5120 * 4 + 3 * 5120 * 2       # a sequence's state, a layer
KIND = "TPU v5 lite"


def record():
    """test_chipbench_readers' synthetic run as a run of the new cell:
    the configuration's file; 58 decode steps of 9 scan calls each in
    0.87 s; three runs of a 2048-token prefill executable; the counters
    ``totals.state``, ``totals.prefill`` and ``totals.shared_kv``; one
    request decoding at a context of 201 while traced."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"]["started_unix"] = 1004.2          # middle: t = 104.7
    run["trace"]["modules"] = {
        "jit_decode_window_1_": {
            "runs": 7, "total_s": 0.84, "median_s": 0.12,
            "ops": {"mamba_recurrent_step": [7 * 8 * 9, 0.0504],
                    "paged_decode_attention": [7 * 8 * 16, 0.4]}},
        "jit_decode_window_2_": {
            "runs": 1, "total_s": 0.03, "median_s": 0.03,
            "ops": {"mamba_recurrent_step": [2 * 9, 0.0018]}},
        "jit_prefill_chunk_9_": {
            "runs": 3, "total_s": 0.21, "median_s": 0.07,
            "ops": {"mamba_chunk_scan": [27, 0.0216],
                    "paged_decode_attention": [21, 0.001]}}}
    for at, steps, disp, ended in (("perf_open", 100, 10, 1),
                                   ("perf_close", 158, 13, 2)):
        totals = run[at]["totals"]
        totals["state"] = {"steps": steps, "step_rows": 8 * steps,
                           "scan_tokens": 2000 * disp,
                           "prefill_keys": 2000 * disp * 7000}
        totals["prefill"] = {"real": 2000 * disp, "pad": 48 * disp,
                             "dispatches": disp, "by_rows": {"1": disp},
                             "self_positions": 2048 * disp,
                             "cross_positions": ended}
        # 7 cross layers x 8 rows x 12 000 keys a step
        totals["shared_kv"] = {"reads": 7 * steps,
                               "keys_read": 7 * 8 * 12000 * steps}
    return run


def _least(needs):
    return roofline_yoco.least_seconds(needs, KIND)["seconds"]


_STEP = roofline_yoco.decode_step_needs(PHI, 1, 201.0, 201.0)
_CHUNK = roofline_yoco.prefill_chunk_needs(PHI, 2048, 7000.0, 1 / 3)
_CALL1 = roofline_yoco.mamba_call_needs(PHI, 1, 1)
_SCAN = roofline_yoco.mamba_call_needs(PHI, 1, 2048.0)
_AT12K = roofline_yoco.decode_step_needs(PHI, 8.0, 8 * 12000.0, 8 * 512.0)
EXPECTED = {
    "yoco_decode_step_roofline": 100 * _least(_STEP) / (0.87 / 58),
    "yoco_prefill_chunk_roofline": 100 * _least(_CHUNK) / 0.07,
    "mamba_decode_kernel_roofline": 100 * _least(_CALL1) / (0.0522 / 522),
    "mamba_prefill_kernel_roofline": 100 * _least(_SCAN) / (0.0216 / 27),
    "shared_kv_step_share": 100 * 7 * 8 * 12000 * KV_TOKEN
    / _AT12K["bytes"],
    "cross_prefill_share": 100 * 1 / (3 * 2048),
}
first.EXPECTED.update(EXPECTED)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_finds_its_number(name):
    value = runner.read_metric(SPECS[name], record(), [])
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)
    assert 0 < value <= 100


@pytest.mark.parametrize("name", NEW)
def test_new_reader_reads_nothing_from_a_program_without_it(name):
    """A record of a program that cannot run the model (no such
    counters, no trace; and, for the trace's readers, another
    configuration's file with a trace that happens to hold the kernels'
    names): None, nothing raised: what the parent commit gives the
    driver's traced runs of the accepted cells."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"] = None
    assert runner.read_metric(SPECS[name], run, []) is None
    other = first.synthetic()           # Mistral's file: no such model
    other["trace"]["started_unix"] = 1004.2
    for op in ("mamba_recurrent_step", "mamba_chunk_scan"):
        other["trace"]["modules"]["jit__unknown_1_"]["ops"][op] = [1, 0.1]
    for at in ("perf_open", "perf_close"):
        other[at]["totals"]["state"] = {"steps": 5, "step_rows": 9}
    assert runner.read_metric(SPECS[name], other, []) is None


def test_the_step_note_names_the_yardstick():
    run = record()
    runner.read_metric(SPECS["yoco_decode_step_roofline"], run, [])
    note = run["notes"]["decode_step_roofline"]
    assert (note["rows"], note["context_tokens"], note["bound"],
            note["yardstick"]) == (1, 201, "bytes", "roofline_yoco")


def test_the_listless_step_metrics_read_this_cells_executables():
    """``decode_step_device_ms`` and ``prefill_dispatch_device_ms``
    find the cell's executables by the operations its file names: NOT
    by ``paged_decode_attention``, which the prefill executable runs
    too, in its cross layers at one position a row."""
    def spec(name):
        return mf.load(os.path.join(mf.HERE, "metrics", name + ".json"))
    run = record()
    assert runner.read_metric(spec("decode_step_device_ms"), run, []) \
        == pytest.approx(1e3 * 0.87 / 58)
    assert runner.read_metric(spec("prefill_dispatch_device_ms"), run, []) \
        == pytest.approx(70.0)


def test_the_yardstick_on_made_up_shapes():
    """A model of 8 layers (2 + 1 + 1 periods), hidden 4, two heads of
    2, a state of 3 over 8 channels, by hand."""
    hf = dict(model_type="phi4flash", hidden_size=4, intermediate_size=6,
              num_hidden_layers=8, num_attention_heads=2,
              num_key_value_heads=2, vocab_size=10, sliding_window=5,
              assumed=dict(mamba_d_state=3, mamba_d_conv=4,
                           mamba_expand=2, mamba_dt_rank=1))
    s = roofline_yoco.sizes(hf)
    assert (s["mamba"], s["window"], s["full"], s["gmu"], s["cross"],
            s["di"], s["hd"]) == (3, 2, 1, 1, 1, 8, 2)
    w = roofline_yoco.layer_weights(hf)
    assert w["block"] == (3 * 4 * 6, 16)
    assert w["mamba"] == (4 * 16 + 8 * 4,
                          8 * 7 + 8 + 8 + 5 * 8 + 3 * 8 + 8)
    assert w["own"] == (4 * 12 + 4 * 4, 12 + 4 + 12)
    assert w["gmu"] == (64, 0) and w["cross"] == (32, 4 + 4 + 12)
    q_self = 3 * 96 + 3 * 64 + 6 * 72
    q_rest = 64 + 32 + 2 * 72 + 40
    read, passed = roofline_yoco.weights(hf, "self")
    assert read == q_self + 2 * (3 * 144 + 3 * 28 + 6 * 16)
    assert passed == q_self + 3 * 144 + 3 * 28 + 6 * 16
    assert roofline_yoco.weights(hf, "all")[1] \
        == passed + q_rest + 20 + 2 * 16 + 8
    assert roofline_yoco.kv_token_bytes(hf) == 2 * 2 * 2 * 2
    call = roofline_yoco.mamba_call_needs(hf, 2, 5)
    assert call == {"bytes": 2 * 2 * 3 * 8 * 4 + 5 * (24 + 6) * 4,
                    "ops": 0.0, "vector_ops": 7 * 5 * 24,
                    "exps": 5 * 24}
    # two rows at contexts 3 and 9: the window cuts the second to 5
    assert roofline_yoco.context_sums(hf, [3, 9]) == (2, 12.0, 8.0)
    step = roofline_yoco.decode_step_needs(hf, 2, 12.0, 8.0)
    keys = 2 * 12.0 + 2 * 8.0           # 2 readers, 2 window layers
    scan = roofline_yoco.mamba_call_needs(hf, 2, 2)
    assert step["bytes"] == (
        roofline_yoco.weights(hf, "all")[0] + keys * 16
        + 3 * (scan["bytes"] + 2 * 2 * 3 * 8 * 2))
    assert step["ops"] == (2 * 2 * roofline_yoco.weights(hf, "all")[1]
                           + keys * 2 * 2 * 6)
    assert step["shared_bytes"] == 12.0 * 16
    chunk = roofline_yoco.prefill_chunk_needs(hf, 4, 10.0, 0.5)
    tail_read, tail_passed = roofline_yoco.weights(hf, "cross")
    assert chunk["bytes"] == (
        read + 3 * roofline_yoco.mamba_call_needs(hf, 1, 4)["bytes"]
        + (12.0 + 2 * 9 + 0.5 * 12.0) * 16 + 0.5 * tail_read)
    assert chunk["ops"] == (
        4 * 2 * passed + 4 * (10.0 + 2 * 5) * 2 * 2 * 6
        + 0.5 * (2 * tail_passed + 12.0 * 2 * 2 * 6))
    with pytest.raises(KeyError, match="peaks"):
        roofline_yoco.least_seconds(call, "TPU v9")


def test_the_yardstick_counts_the_issue_arithmetic():
    """ISSUE 50's sums, layer by layer; a step at 8 rows of 12k is half
    weights, half the one shared layer."""
    w = roofline_yoco.layer_weights(PHI)
    block = w["block"][0]
    assert block == 78_643_200
    assert w["mamba"][0] == 26_214_400 + 13_107_200
    assert abs((w["mamba"][0] + w["mamba"][1] + block) / 119.9e6 - 1) < 2e-3
    assert w["own"][0] == 13_107_200 + 6_553_600
    assert abs((w["own"][0] + block) / 98.3e6 - 1) < 2e-3
    assert abs((w["gmu"][0] + block) / 104.9e6 - 1) < 2e-3
    assert abs((w["cross"][0] + block) / 91.8e6 - 1) < 2e-3
    cfg = engine_child.model_config(PHI, "p")
    assert roofline_yoco.weights(PHI, "all")[1] == cfg.num_params
    assert abs(cfg.num_params / 3.853e9 - 1) < 1e-3
    assert roofline_yoco.kv_token_bytes(PHI) == KV_TOKEN == 5120
    assert cfg.state_bytes_per_seq == 9 * PAGE == 3_225_600
    assert abs(_AT12K["bytes"] / 8.0e9 - 1) < 0.02
    assert 0.47 < 8 * 8 * 12000 * KV_TOKEN / _AT12K["bytes"] < 0.51
    least = roofline_yoco.least_seconds(_AT12K, KIND)
    assert least["bound"] == "bytes" and 9.5e-3 < least["seconds"] < 10e-3
    # a decode call of the scan is its pages' bytes; a chunk's its
    # vector operations
    assert roofline_yoco.least_seconds(
        roofline_yoco.mamba_call_needs(PHI, 8, 8), KIND)["bound"] == "bytes"
    assert roofline_yoco.least_seconds(_SCAN, KIND)["bound"] \
        == "vector operations"
    # a chunk runs 18 of 32 layers: 8.1e12 of the matrix unit's
    # operations at 2048 tokens
    chunk = roofline_yoco.prefill_chunk_needs(PHI, 2048, 7000.0)
    assert roofline_yoco.least_seconds(chunk, KIND)["bound"] == "operations"
    assert 8.0e12 < 2048 * 2 * roofline_yoco.weights(PHI, "self")[1] < 8.3e12
    assert roofline.PEAKS[KIND]["hbm_bytes_per_s"] == 819e9


def test_kernels_off_under_this_files_harness_key():
    harness = harness_key.read(CONFIG)
    assert harness["kernel_tables"] == ["attention_paths", "mixer_paths"]
    assert harness["decode_step"] == {"op": "mamba_recurrent_step",
                                      "calls_per_step": 9}
    assert harness["prefill_dispatch"] == {"op": "mamba_chunk_scan"}
    good = {"attention_paths": {"decode|8|16384|8": "pallas_paged_decode",
                                "prefill|2048|16384|1": "pallas_paged"},
            "moe_paths": {},
            "mixer_paths": {"decode|8|16384|8": "mamba_recurrent_step",
                            "prefill|2048|16384|1": "mamba_chunk_scan"}}
    assert harness_key.kernels_off(good, harness) == {}
    off = {**good, "mixer_paths": {
        **good["mixer_paths"], "decode|8|16384|8":
            "mamba_recurrent_step_jnp"}}
    assert harness_key.kernels_off(off, harness) == {
        "mixer_paths[decode|8|16384|8]": "mamba_recurrent_step_jnp"}


def test_the_configuration_file_is_the_catalogs_whole():
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert PHI["source"] == row["source_url"]
    assert PHI["reduced"] == []
    for key, value in row["config"].items():
        assert PHI[key] == value, key
    for key in ("mamba", "layers", "differential_attention", "biases",
                "weights", "quantization", "state_pages", "kv_cache",
                "tokenizer", "mamba_d_state", "mamba_d_conv",
                "mamba_expand", "mamba_dt_rank"):
        assert key in PHI["assumed"]
    assert PHI["reference"] == "phi4flash"
    assert PHI["quantization"] == "int8" and "stands_for" in PHI
    args = PHI["engine_args"]
    for flag, value in (("--max-num-seqs", "8"),
                        ("--max-model-len", "16384"),
                        ("--kv-pool-tokens", "131072"),
                        ("--prefill-chunk", "2048"),
                        ("--kv-block-size", "64")):
        assert args[args.index(flag) + 1] == value
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == PHI["name"]]
    assert entry["file"] == "chipbench/configs/phi4-mini-flash-int8.json"
    assert entry["reduced"] == [] and entry["source"] == PHI["source"]


def test_the_traffic_and_the_cell_are_the_issues():
    cell = mf.Cell(MANIFEST, CELL, [])
    assert cell.chips == 1 and len(cell.why) <= 200
    assert cell.traffic_name == "longctx-closed"
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 8
    assert cell.params["decode_batch_buckets"] == [8]
    from chipbench import traffic
    plan = traffic.make_plan(cell.traffic, 5, 50.0)
    assert min(plan.prompts) >= 8192 and max(plan.prompts) <= 15360
    assert set(plan.outputs) == {512}


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
    router and engine (rehearsal/BENCHMARK.yoco.json): a K/V pool of
    three layers for four readers and state pages behind the program's
    normal server entry point, the two-depth prefill, the probe against
    chipbench/references/phi4flash.py, every listless counter metric
    and the two new counters' in a traced line (no device metric from
    a CPU run). From a tree of links, so that the run keeps its
    ``.chipbench/`` to itself. Some 40 s: an engine and a router start,
    26 executables compile."""
    base = os.path.join(mf.ROOT, "tests", "chipbench", "rehearsal")
    for name in ("chipbench", "production_stack_tpu", "tests",
                 "BENCHMARK.json"):
        os.symlink(os.path.join(mf.ROOT, name), tmp_path / name)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--manifest",
         os.path.join(base, "BENCHMARK.yoco.json"), "--data", base,
         "--rehearse", "--workload", "tiny-yoco-closed", "--seed",
         str(2**31 + 79), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["why"]
    assert line["failed"] == 0 and line["attempted"] > 0
    metrics = line["metrics"]
    assert 0 < metrics["shared_kv_step_share"]["value"] < 100
    assert 0 < metrics["cross_prefill_share"]["value"] < 10
    assert "yoco_decode_step_roofline" not in metrics   # no device here
    listless = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m and m["source"] != "device_trace"
                } - {"hbm_peak_share"}      # no device memory on the CPU
    assert listless <= set(metrics)
