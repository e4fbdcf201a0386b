"""What PR 54 added to the benchmark for ``nemotron3nano-longctx-closed``:
the manifest's new entries as the manifest then is, the yardstick of a
model of one-sublayer blocks (chipbench/roofline_nemotronh.py) against
hand counts on made-up shapes and on the published ones (no share can
read over 100 % for want of bytes or operations counted), the new reader
on a hand-made record, the kernel clause of ``correct`` under the file's
``harness`` key, the configuration's file against the catalog's keys,
and the CPU rehearsal of the cell at a tiny ``nemotron_h`` file
(``rehearsal/BENCHMARK.nemotron.json``,
``rehearsal/configs/tiny-nemotron.json``). The plain reference
(chipbench/references/nemotron_h) against the program is
tests/test_nemotron.py's.

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import, as
test_chipbench_yoco's does. Where PR 54's entries stand in the manifest
is ``manifest_history/pr54.json``'s (test_chipbench_manifest).
"""

import json
import os
import subprocess
import sys

import pytest
import test_chipbench_readers as first

from chipbench import engine_child, harness_key, roofline
from chipbench import manifest as mf
from chipbench import roofline_nemotronh as rn
from chipbench import run as runner

CELL = "nemotron3nano-longctx-closed"
CONFIG = os.path.join(mf.HERE, "configs",
                      "nemotron-3-nano-30b-a3b-int8-e32.json")
NEW = ("nemotronh_decode_step_roofline", "nemotronh_prefill_chunk_roofline",
       "ssd_decode_kernel_roofline", "ssd_prefill_kernel_roofline",
       "ssd_state_bytes_per_slot", "nemotronh_expert_read_share")
with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(CONFIG) as f:
    NEMO = json.load(f)
SPECS = {n: mf.load(os.path.join(mf.HERE, "metrics", n + ".json"))
         for n in NEW}
KV_TOKEN = 2 * 2 * 128 * 2                  # K and V of a token, a block
STATE = 64 * 64 * 128 * 4                   # a head's states, a block
PAGE = STATE + 3 * 6144 * 2                 # and the convolution's inputs
KIND = "TPU v5 lite"


def record():
    """test_chipbench_readers' synthetic run as a run of the new cell:
    the configuration's file; 58 decode steps of 23 scan calls each in
    0.87 s; three runs of a 2048-token prefill executable; the counters
    ``totals.state``, ``totals.prefill``, ``totals.moe`` and
    ``kv_pool``; one request decoding at a context of 201 while
    traced."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"]["started_unix"] = 1004.2          # middle: t = 104.7
    run["trace"]["modules"] = {
        "jit_decode_window_1_": {
            "runs": 7, "total_s": 0.84, "median_s": 0.12,
            "ops": {"mamba2_recurrent_step": [7 * 8 * 23, 0.1288],
                    "paged_decode_attention": [7 * 8 * 6, 0.04]}},
        "jit_decode_window_2_": {
            "runs": 1, "total_s": 0.03, "median_s": 0.03,
            "ops": {"mamba2_recurrent_step": [2 * 23, 0.0046]}},
        "jit_prefill_chunk_9_": {
            "runs": 3, "total_s": 0.42, "median_s": 0.14,
            "ops": {"mamba2_chunk_scan": [69, 0.0276],
                    "paged_attention": [18, 0.03]}}}
    for at, steps, disp in (("perf_open", 100, 10), ("perf_close", 158, 13)):
        totals = run[at]["totals"]
        totals["state"] = {"steps": steps, "step_rows": 8 * steps,
                           "scan_tokens": 2000 * disp,
                           "prefill_keys": 2000 * disp * 7000}
        totals["prefill"] = {"real": 2000 * disp, "pad": 48 * disp,
                             "dispatches": disp, "by_rows": {"1": disp},
                             "held_rows": 23 * 3000 * disp}
        # 10 of the 32 held experts a block and step
        totals["moe"] = {"experts_read": 23 * 10 * steps,
                         "experts_resident": 23 * 32 * steps}
        run[at]["kv_pool"] = {**run[at].get("kv_pool", {}),
                              "state_bytes_per_slot": 23 * PAGE}
    return run


def _least(needs):
    return rn.least_seconds(needs, KIND)["seconds"]


_STEP = rn.decode_step_needs(NEMO, 1, 201.0, 10.0)
_CHUNK = rn.prefill_chunk_needs(NEMO, 2048, 7000.0, 3000.0)
_CALL1 = rn.ssd_call_needs(NEMO, 1, 1)
_SCAN = rn.ssd_call_needs(NEMO, 1, 2048.0)
EXPECTED = {
    "nemotronh_decode_step_roofline": 100 * _least(_STEP) / (0.87 / 58),
    "nemotronh_prefill_chunk_roofline": 100 * _least(_CHUNK) / 0.14,
    "ssd_decode_kernel_roofline": 100 * _least(_CALL1) / (0.1334 / 1334),
    "ssd_prefill_kernel_roofline": 100 * _least(_SCAN) / (0.0276 / 69),
    "ssd_state_bytes_per_slot": 23 * PAGE,
    "nemotronh_expert_read_share": 100 * 10 / 32,
}
first.EXPECTED.update(EXPECTED)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_finds_its_number(name):
    value = runner.read_metric(SPECS[name], record(), [])
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)
    assert 0 < value
    if SPECS[name]["unit"] == "%":
        assert value <= 100


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
    for at in ("perf_open", "perf_close"):
        run[at]["totals"].pop("moe", None)
        run[at].get("kv_pool", {}).pop("state_bytes_per_slot", None)
    assert runner.read_metric(SPECS[name], run, []) is None
    if SPECS[name]["source"] != "device_trace":
        return
    other = first.synthetic()           # Mistral's file: no such model
    other["trace"]["started_unix"] = 1004.2
    for op in ("mamba2_recurrent_step", "mamba2_chunk_scan"):
        other["trace"]["modules"]["jit__unknown_1_"]["ops"][op] = [1, 0.1]
    for at in ("perf_open", "perf_close"):
        other[at]["totals"]["state"] = {"steps": 5, "step_rows": 9}
    assert runner.read_metric(SPECS[name], other, []) is None


def test_the_step_note_names_the_yardstick():
    run = record()
    runner.read_metric(SPECS["nemotronh_decode_step_roofline"], run, [])
    note = run["notes"]["decode_step_roofline"]
    assert (note["rows"], note["context_tokens"], note["bound"],
            note["yardstick"]) == (1, 201, "bytes", "roofline_nemotronh")
    assert note["experts_read_a_block"] == pytest.approx(10.0)


def test_the_listless_step_metrics_read_this_cells_executables():
    """``decode_step_device_ms`` and ``prefill_dispatch_device_ms``
    find the cell's executables by the operations its file names, 23
    scan calls a step."""
    def spec(name):
        return mf.load(os.path.join(mf.HERE, "metrics", name + ".json"))
    run = record()
    assert runner.read_metric(spec("decode_step_device_ms"), run, []) \
        == pytest.approx(1e3 * 0.87 / 58)
    assert runner.read_metric(spec("prefill_dispatch_device_ms"), run, []) \
        == pytest.approx(140.0)


def test_the_yardstick_on_made_up_shapes():
    """A model of 5 blocks M E * M E, hidden 4, 2 / 1 attention heads
    of 2, Mamba-2 of 2 heads of 3 in 1 group with a state of 5, 2 held
    of a router's 4 experts top-2 of width 7, a shared one of 6, by
    hand."""
    hf = dict(model_type="nemotron_h", hybrid_override_pattern="ME*ME",
              hidden_size=4, num_attention_heads=2, num_key_value_heads=1,
              head_dim=2, mamba_num_heads=2, mamba_head_dim=3, n_groups=1,
              ssm_state_size=5, conv_kernel=4, n_routed_experts=2,
              num_experts_per_tok=2, moe_intermediate_size=7,
              moe_shared_expert_intermediate_size=6, n_shared_experts=1,
              vocab_size=10, deployment=dict(router_experts=4,
                                             chips_per_layer=2))
    s = rn.sizes(hf)
    assert (s["mamba"], s["moe"], s["attn"], s["di"], s["ch"], s["held"],
            s["router"]) == (2, 2, 1, 6, 16, 2, 4)
    w = rn.block_weights(hf)
    assert w["mamba"] == (4 * (6 + 16 + 2) + 6 * 4,
                          16 * 5 + 3 * 2 + 6 + 4)
    assert w["attn"] == (2 * 4 * 4 + 2 * 4 * 2, 4)
    assert w["moe"] == (2 * 4 * 6, 4 * 4 + 4 + 4)
    assert w["expert"] == (2 * 4 * 7, 0)
    q = 2 * 120 + 48 + 2 * 48 + 40
    sm = 2 * 96 + 4 + 2 * 24 + 4
    assert rn.weights_outside_experts(hf) == (q + 2 * sm, q + sm)
    assert rn.weights_outside_experts(hf, 2.0)[0] == 2 * q + 2 * sm
    assert rn.kv_token_bytes(hf) == 2 * 1 * 2 * 2
    assert rn.state_page_bytes(hf) == 5 * 6 * 4 + 3 * 16 * 2
    call = rn.ssd_call_needs(hf, 2, 5)
    assert call == {"bytes": 2 * 2 * 30 * 4 + 5 * (16 * 2 + 8 * 4),
                    "ops": 5 * 5 * 30}
    step = rn.decode_step_needs(hf, 2, 12.0, 1.5)
    scan = rn.ssd_call_needs(hf, 2, 2)
    assert step["bytes"] == (q + 2 * sm + 2 * 1.5 * 56 + 12.0 * 8
                             + 2 * (scan["bytes"] + 2 * 2 * 3 * 16 * 2))
    assert step["ops"] == (2 * 2 * (q + sm + 2 * 1.0 * 56)
                           + 12.0 * 2 * 4 * 2 + 2 * scan["ops"])
    chunk = rn.prefill_chunk_needs(hf, 4, 10.0, 3.0)
    one = rn.ssd_call_needs(hf, 1, 4)
    assert chunk["bytes"] == (q + 2 * sm + 2 * 2 * 56 + 12.0 * 8
                              + 2 * (one["bytes"] + 2 * 3 * 16 * 2))
    assert chunk["ops"] == (4 * 2 * (q + sm) + 2 * 3.0 * 2 * 56
                            + 4 * 10.0 * 2 * 4 * 2 + 2 * one["ops"])
    # no count handed in: the even share, 4 tokens x top-2 x 2 / 4
    assert rn.prefill_chunk_needs(hf, 4, 10.0)["ops"] \
        == chunk["ops"] + 2 * (4.0 - 3.0) * 2 * 56
    with pytest.raises(KeyError, match="peaks"):
        rn.least_seconds(call, "TPU v9")


def test_the_yardstick_counts_the_issue_arithmetic():
    """ISSUE 54's sums, block by block, at the PUBLISHED widths (an
    expert is 1856 wide however it is stored); a step at 8 rows of 12k
    is about 5.4 GB of which no part is most."""
    w = rn.block_weights(NEMO)
    assert w["mamba"][0] == 2688 * 10304 + 4096 * 2688
    assert abs(sum(w["mamba"]) / 38.74e6 - 1) < 1e-3
    assert abs(sum(w["attn"]) / 23.40e6 - 1) < 1e-3
    assert w["expert"][0] == 2 * 2688 * 1856 == 9_977_856
    assert abs(w["moe"][0] / 19.96e6 - 1) < 1e-3
    assert w["moe"][1] == 2688 * 128 + 128 + 2688
    cfg = engine_child.model_config(NEMO, "n")
    outside = rn.weights_outside_experts(NEMO)[1]
    # everything the chip holds but the embedding's rows (a step reads
    # 8 of them)
    assert outside + 23 * 32 * w["expert"][0] + 32768 * 2688 \
        == cfg.num_params
    assert abs(cfg.num_params / 9.018e9 - 1) < 1e-3
    uncut = engine_child.model_config(
        {**NEMO, **NEMO["published"], "deployment": None}, "whole")
    assert abs(uncut.num_params - 31.578e9) < 0.01e9
    assert rn.kv_token_bytes(NEMO) * 6 == 6144
    assert cfg.state_bytes_per_seq == 23 * PAGE == 49_082_368 \
        == 23 * rn.state_page_bytes(NEMO)
    at12k = rn.decode_step_needs(NEMO, 8.0, 8 * 12000.0, 10.5)
    assert abs(at12k["bytes"] / 5.39e9 - 1) < 0.01
    parts = {"experts": 23 * 10.5 * 9_977_856, "pages": 8 * 2 * 23 * PAGE,
             "kv": 8 * 12000 * 6144, "outside": rn.weights_outside_experts(
                 NEMO)[0],
             # x, B, C in, dt in and y out of the 23 scans, 8 positions
             "scan": 23 * 8 * (6144 * 2 + (4096 + 64) * 4)}
    assert abs(sum(parts.values()) / at12k["bytes"] - 1) < 1e-9
    assert max(parts.values()) < 0.5 * at12k["bytes"]
    assert 0.14 < parts["pages"] / at12k["bytes"] < 0.16
    least = rn.least_seconds(at12k, KIND)
    assert least["bound"] == "bytes" and 6.4e-3 < least["seconds"] < 6.8e-3
    # a decode call of the scan is its pages' bytes, rows x 2 x 2 MB;
    # a chunk's call its activations'; a whole chunk the matrix unit's
    call = rn.ssd_call_needs(NEMO, 8, 8)
    assert call["bytes"] >= 8 * 2 * 2_097_152
    assert rn.least_seconds(call, KIND)["bound"] == "bytes"
    assert rn.least_seconds(_SCAN, KIND)["bound"] == "bytes"
    assert _SCAN["ops"] == 5 * 2048 * 524_288
    chunk = rn.prefill_chunk_needs(NEMO, 2048, 10000.0)
    assert rn.least_seconds(chunk, KIND)["bound"] == "operations"
    assert 9.5e12 < chunk["ops"] < 10.5e12
    assert roofline.PEAKS[KIND]["hbm_bytes_per_s"] == 819e9


def test_kernels_off_under_this_files_harness_key():
    harness = harness_key.read(CONFIG)
    assert harness["kernel_tables"] == ["attention_paths", "mixer_paths"]
    assert harness["decode_step"] == {"op": "mamba2_recurrent_step",
                                      "calls_per_step": 23}
    assert harness["prefill_dispatch"] == {"op": "mamba2_chunk_scan"}
    probe = harness["probe"]
    assert probe["logprob_gap_limit"] or probe["mean_logprob_gap_limit"]
    good = {"attention_paths": {"decode|8|16384|8": "pallas_paged_decode",
                                "prefill|2048|16384|1": "pallas_paged"},
            "moe_paths": {"decode|8|16384|8": "list",
                          "prefill|2048|16384|1": "grouped"},
            "mixer_paths": {"decode|8|16384|8": "mamba2_recurrent_step",
                            "prefill|2048|16384|1": "mamba2_chunk_scan"}}
    assert harness_key.kernels_off(good, harness) == {}
    off = {**good, "mixer_paths": {
        **good["mixer_paths"], "prefill|2048|16384|1":
            "mamba2_chunk_scan_jnp"}}
    assert harness_key.kernels_off(off, harness) == {
        "mixer_paths[prefill|2048|16384|1]": "mamba2_chunk_scan_jnp"}


def test_the_configuration_file_is_the_catalogs_but_the_share():
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert NEMO["source"] == row["source_url"]
    assert NEMO["reduced"] == ["n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in NEMO["reduced"]:
            assert NEMO["published"][key] == value, key
        else:
            assert NEMO[key] == value, key
    assert (NEMO["n_routed_experts"], NEMO["vocab_size"]) == (32, 32768)
    assert NEMO["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert NEMO["deployment"] == {"chips_per_layer": 4, "chip_index": 0,
                                  "router_experts": 128,
                                  "pipeline_stages": 1}
    for key in ("rotary", "weights", "quantization", "expert_layout",
                "in_proj_columns", "state_pages", "kv_cache", "chunk_size",
                "tokenizer", "positions"):
        assert key in NEMO["assumed"]
    # the stored width is prose here: the program derives it, and no key
    # of the file can move what is read away from what is counted
    assert "expert_stored_width" not in NEMO["assumed"]
    stored = engine_child.model_config(NEMO, "n").moe_stored_size
    assert stored == 1920
    assert f"STORED {stored} wide" in NEMO["assumed"]["expert_layout"]
    for key in ("reduced_why", "expert_load_share", "stands_for",
                "harness_why", "engine_args_why"):
        assert len(NEMO[key]) > 40, key
    assert NEMO["reference"] == "nemotron_h"
    assert NEMO["quantization"] == "int8"
    args = NEMO["engine_args"]
    for flag, value in (("--max-num-seqs", "8"),
                        ("--max-model-len", "16384"),
                        ("--kv-pool-tokens", "131072"),
                        ("--prefill-chunk", "2048"),
                        ("--kv-block-size", "64")):
        assert args[args.index(flag) + 1] == value
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == NEMO["name"]]
    assert entry["file"] \
        == "chipbench/configs/nemotron-3-nano-30b-a3b-int8-e32.json"
    assert entry["reduced"] == NEMO["reduced"]
    assert entry["source"] == NEMO["source"]


def test_the_traffic_and_the_cell_are_the_issues():
    cell = mf.Cell(MANIFEST, CELL, [])
    assert cell.chips == 1 and len(cell.why) <= 200
    assert "four times their share" in cell.why
    assert cell.traffic_name == "longctx-closed"
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 8
    assert cell.params["decode_batch_buckets"] == [8]
    from chipbench import traffic
    plan = traffic.make_plan(cell.traffic, 5, 50.0)
    assert min(plan.prompts) >= 8192 and max(plan.prompts) <= 15360
    assert set(plan.outputs) == {512}
    assert not [w for w in MANIFEST["workloads"] if w["chips"] != 1]


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
    router and engine (rehearsal/BENCHMARK.nemotron.json): one of two
    chips' share of 8 ungated experts stored wider than published, a
    K/V pool of two layers for twelve blocks and state pages behind the
    program's normal server entry point, the probe against
    chipbench/references/nemotron_h.py handed the same share, every
    listless counter metric and the two new counters' in a traced line
    (no device metric from a CPU run). From a tree of links, so that
    the run keeps its ``.chipbench/`` to itself. Some 60 s: an engine
    and a router start, 26 executables compile."""
    base = os.path.join(mf.ROOT, "tests", "chipbench", "rehearsal")
    for name in ("chipbench", "production_stack_tpu", "tests",
                 "BENCHMARK.json"):
        os.symlink(os.path.join(mf.ROOT, name), tmp_path / name)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--manifest",
         os.path.join(base, "BENCHMARK.nemotron.json"), "--data", base,
         "--rehearse", "--workload", "tiny-nemotron-closed", "--seed",
         str(2**31 + 79), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["why"]
    assert line["failed"] == 0 and line["attempted"] > 0
    metrics = line["metrics"]
    assert metrics["ssd_state_bytes_per_slot"]["value"] \
        == 5 * (16 * 256 * 4 + 3 * 320 * 2)
    # the exact path reads every held expert (the kernels are off here)
    assert metrics["nemotronh_expert_read_share"]["value"] == 100.0
    assert "nemotronh_decode_step_roofline" not in metrics  # no device
    listless = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m and m["source"] != "device_trace"
                } - {"hbm_peak_share"}      # no device memory on the CPU
    assert listless <= set(metrics)


# ---------------------------------------------------------------------
# the reference against the program as the probe compares them
# (test_chipbench_reference.py's manner: bfloat16 against float32 on
# the same int8 weights, the served top-20)
# ---------------------------------------------------------------------

# through twelve tiny blocks the program's bfloat16 measures 0.004-0.015
# from the float32 reference on the CPU; 0.04 is over twice that and
# under what a routing scale of 1 (0.062, the least of the breakages
# below), a missing gate or a missing skip term (0.9-1.3) measure
TINY_TOLERANCE = 0.04


def _tiny():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import reference
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    base = os.path.join(mf.ROOT, "tests", "chipbench", "rehearsal")
    with open(os.path.join(base, "configs", "tiny-nemotron.json")) as f:
        conf = json.load(f)
    # experts of 48 at sd 0.02 add too little to tell a routing scale
    # by: their output projection at 0.06 (the key a file may state)
    conf["assumed"] = {"routed_down_init_std": 0.06}
    cfg = engine_child.model_config(conf, "tiny-nemotron")
    params = llama.init_params(cfg, jax.random.PRNGKey(5),
                               quantization="int8")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (9, 40, 77)]
    served = []
    for p in prompts:
        cache = kv_pool.cache_for(cfg, 9, 16, cfg.dtype, state_pages=2)
        tables = jnp.asarray([list(range(1, 9)) + [1]], jnp.int32)
        logits, _, _ = llama.forward(
            params, cfg, jnp.asarray([p]), jnp.arange(len(p))[None, :],
            cache, block_tables=tables, kv_len=128)
        lps = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        top_lp, top_id = jax.lax.top_k(lps, reference.TOP)
        served.append({"prompt_tokens": len(p),
                       "ids": [int(i) for i in top_id],
                       "logprobs": [float(v) for v in top_lp]})
    return conf, params, prompts, served


@pytest.fixture(scope="module")
def tiny():
    return _tiny()


def test_reference_agrees_with_the_program(tiny):
    from chipbench import reference
    from chipbench.references import nemotron_h
    conf, params, prompts, served = tiny
    assert conf["deployment"]["chip_index"] == 1    # experts 4-7 of 8
    rows = nemotron_h.next_token_logprobs(
        params, conf, prompts, [s["ids"] for s in served])
    out = reference.compare(served, rows, tolerance=TINY_TOLERANCE)
    assert out["ok"], out
    assert all(r["shared_top"] >= 18 for r in out["rows"])


@pytest.mark.parametrize("breakage", [
    {"gate_control": "off"}, {"skip_control": "off"},
    {"routed_scaling_factor": 1.0}, {"num_experts_per_tok": 1},
    {"deployment": None}, {"round_to": "float8_e4m3fn"}],
    ids=["no-gate", "no-skip", "scale-1", "top-1", "offset-0", "float8"])
def test_the_tolerance_sees_a_wrong_block(tiny, breakage):
    """A reference that departs from the served mathematics in one
    place (the ``lean`` control's switches, an expert a token, the
    other chip's experts, the float8 control) falls outside the
    tolerance: the comparison can tell."""
    from chipbench import reference
    from chipbench.references import nemotron_h
    conf, params, prompts, served = tiny
    rows = nemotron_h.next_token_logprobs(
        params, {**conf, **breakage}, prompts,
        [s["ids"] for s in served])
    assert not reference.compare(served, rows,
                                 tolerance=TINY_TOLERANCE)["ok"]
