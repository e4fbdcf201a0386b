"""What PR 56 added to the benchmark for ``ouro26b-decode-closed``: the
manifest's new entries as the manifest then is, the yardstick of a
looped decoder (chipbench/roofline_looped.py) against hand counts on
made-up shapes and at ISSUE 56's arithmetic (no share can read over
100 % for want of bytes or operations counted), the new reader on a
hand-made record, the kernel clause of ``correct`` under the file's
``harness`` key, the configuration's file against the catalog's keys,
the reference against the program in bfloat16 with the lean controls,
and the CPU rehearsal of the cell at a tiny ``ouro`` file
(``rehearsal/BENCHMARK.looped.json``, ``rehearsal/configs/tiny-ouro.json``).
The plain reference (chipbench/references/ouro) against the program in
float32 is tests/test_ouro.py's.

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import, as
test_chipbench_nemotronh's does. Where PR 56's entries stand in the
manifest is ``manifest_history/pr56.json``'s (test_chipbench_manifest).
"""

import json
import os
import subprocess
import sys

import pytest
import test_chipbench_readers as first

from chipbench import engine_child, harness_key, roofline
from chipbench import manifest as mf
from chipbench import roofline_looped as rl
from chipbench import run as runner
from production_stack_tpu.models import llama

CELL = "ouro26b-decode-closed"
CONFIG = os.path.join(mf.HERE, "configs", "ouro-2.6b-int8.json")
NEW = ("looped_decode_step_roofline", "looped_prefill_chunk_roofline",
       "looped_attention_kernel_roofline", "loop_kv_step_share",
       "loop_kv_bytes_per_token", "loop_passes_per_row_step")
with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(CONFIG) as f:
    OURO = json.load(f)
SPECS = {n: mf.load(os.path.join(mf.HERE, "metrics", n + ".json"))
         for n in NEW}
KV_TOKEN = 192 * 2 * 16 * 128 * 2           # K and V of a token, the pool
PASS = 48 * (4 * 2048 * 2048 + 3 * 2048 * 5632)     # a pass's int8 weights
KIND = "TPU v5 lite"


def record():
    """test_chipbench_readers' synthetic run as a run of the new cell:
    the configuration's file; 58 decode steps of 192 attention calls
    each in 1.74 s; three runs of a 256-token prefill executable of one
    row; the counters ``device.loop``, ``totals.step_bytes``,
    ``totals.prefill`` and ``kv_pool``; one request decoding at a
    context of 201 while traced, and of 202 at the window's middle."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["records"][0]["prompt_tokens"] = 190     # lands in the 256 bucket
    run["trace"]["started_unix"] = 1004.2          # middle: t = 104.7
    run["trace"]["modules"] = {
        "jit_decode_window_1_": {
            "runs": 7, "total_s": 1.68, "median_s": 0.24,
            "ops": {"paged_decode_attention": [7 * 8 * 192, 0.5376]}},
        "jit_decode_window_2_": {
            "runs": 1, "total_s": 0.06, "median_s": 0.06,
            "ops": {"paged_decode_attention": [2 * 192, 0.0192]}},
        "jit_prefill_chunk_9_": {
            "runs": 3, "total_s": 0.42, "median_s": 0.14,
            "ops": {"paged_attention": [3 * 192, 0.03]}}}
    for at, steps, disp in (("perf_open", 100, 10), ("perf_close", 158, 13)):
        run[at]["totals"]["prefill"] = {
            "real": 190 * disp, "pad": 66 * disp, "dispatches": disp,
            "by_rows": {"1": disp}}
        run[at]["totals"]["step_bytes"] = {
            "weights": 4 * PASS, "head": 2048 * 49152,
            "kv_per_position": KV_TOKEN, "passes": 4}
        run[at]["device"] = {**run[at].get("device", {}), "loop": {
            "passes": 4, "weight_layers": 48, "pool_layers": 192,
            "passes_run": 4 * 16 * steps, "row_steps": 16 * steps,
            "exit_mass": [0.25] * 4}}
        run[at]["kv_pool"] = {**run[at].get("kv_pool", {}),
                              "bytes_per_token": KV_TOKEN}
    return run


def _least(needs):
    return rl.least_seconds(needs, KIND)["seconds"]


_STEP = rl.decode_step_needs(OURO, 1, 201.0)
_CHUNK = rl.prefill_chunk_needs(OURO, 1.0, 195.0)    # prompts 190, 200
_CALL = rl.attention_call_needs(OURO, 1, 201.0)
_KV = (202 + 1) * KV_TOKEN
EXPECTED = {
    "looped_decode_step_roofline": 100 * _least(_STEP) / (1.74 / 58),
    "looped_prefill_chunk_roofline": 100 * _least(_CHUNK) / 0.14,
    "looped_attention_kernel_roofline":
        100 * _least(_CALL) / (0.5568 / (58 * 192)),
    "loop_kv_step_share": 100 * _KV / (_KV + 4 * PASS + 2048 * 49152),
    "loop_kv_bytes_per_token": KV_TOKEN,
    "loop_passes_per_row_step": 4.0,
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
    configuration's file with a trace that holds the same kernels'
    names, as every accepted cell's does): None, nothing raised: what
    the parent commit gives the driver's traced runs of the accepted
    cells."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"] = None
    run["records"] = []
    for at in ("perf_open", "perf_close"):
        run[at].get("kv_pool", {}).pop("bytes_per_token", None)
    assert runner.read_metric(SPECS[name], run, []) is None
    if name == "loop_kv_bytes_per_token":
        return      # a counter every program has: the cell's list keeps it
    other = first.synthetic()           # Mistral's file: no such model
    other["trace"]["started_unix"] = 1004.2
    assert runner.read_metric(SPECS[name], other, []) is None


def test_the_step_note_names_the_yardstick_and_its_parts():
    run = record()
    runner.read_metric(SPECS["looped_decode_step_roofline"], run, [])
    note = run["notes"]["decode_step_roofline"]
    assert (note["rows"], note["context_tokens"], note["bound"],
            note["yardstick"]) == (1, 201, "bytes", "roofline_looped")
    parts = note["bytes_by_part"]
    assert sum(parts.values()) == pytest.approx(_STEP["bytes"])
    assert parts["kv"] == 202 * KV_TOKEN


def test_the_listless_step_metrics_read_this_cells_executables():
    """``decode_step_device_ms`` and ``prefill_dispatch_device_ms`` find
    the cell's executables by the operations its file names, 192
    attention calls a step."""
    def spec(name):
        return mf.load(os.path.join(mf.HERE, "metrics", name + ".json"))
    run = record()
    assert runner.read_metric(spec("decode_step_device_ms"), run, []) \
        == pytest.approx(1e3 * 1.74 / 58)
    assert runner.read_metric(spec("prefill_dispatch_device_ms"), run, []) \
        == pytest.approx(140.0)


def test_the_yardstick_on_made_up_shapes():
    """A model of 2 layers run 3 times, hidden 4, 2 / 1 heads of 2, an
    MLP of 5, a vocabulary of 10, by hand."""
    hf = dict(model_type="ouro", num_hidden_layers=2, total_ut_steps=3,
              hidden_size=4, num_attention_heads=2, num_key_value_heads=1,
              head_dim=2, intermediate_size=5, vocab_size=10)
    assert rl.is_looped(hf) and not rl.is_looped({"model_type": "mistral"})
    q = 2 * 4 * 2 * 2 + 2 * 4 * 1 * 2 + 3 * 4 * 5       # 108
    assert rl.layer_weights(hf) == (q, 16)
    assert rl.kv_token_bytes(hf) == 3 * 2 * 2 * 1 * 2 * 2      # 48
    one_pass = 2 * (q + 2 * 16) + 2 * 4 + 4 * 5
    assert rl.pass_bytes(hf) == one_pass
    assert rl.pass_bytes(hf, 2.0) == one_pass + 2 * q
    assert rl.head_bytes(hf) == 40
    parts = rl.decode_step_parts(hf, 2, 12.0)
    assert parts == {"weights": 3 * one_pass, "head": 40,
                     "kv": (12 + 2) * 48}
    step = rl.decode_step_needs(hf, 2, 12.0)
    assert step["bytes"] == sum(parts.values())
    assert step["ops"] == 2 * 2 * (3 * 2 * q + 40) + 6 * 12 * 2 * 4 * 2
    assert rl.attention_call_needs(hf, 2, 12.0) == {
        "bytes": 12 * 2 * 1 * 2 * 2 + 2 * 2 * 2 * 2 * 2,
        "ops": 12 * 2 * 4 * 2}
    chunk = rl.prefill_chunk_needs(hf, 2, 4, before=3.0)
    assert chunk["bytes"] == 3 * one_pass + 40 + 2 * (3 + 8) * 48
    assert chunk["ops"] == (2 * 2 * 4 * 3 * 2 * q + 2 * 2 * 40
                            + 6 * (2 * 4 * (3 + 2.5)) * 2 * 4 * 2)
    with pytest.raises(KeyError, match="peaks"):
        rl.least_seconds(step, "TPU v9")


def test_the_yardstick_counts_the_issue_arithmetic():
    """ISSUE 56's sums at the PUBLISHED widths: a pass reads 2.466 GB
    of int8 weights, a token takes 1 572 864 B in 192 pool layers; at
    16 rows of about 315 tokens a step's least bytes are about 17.9 GB,
    55 % weights read four times and 44 % K/V, 21.8 ms at 819 GB/s."""
    assert rl.layer_weights(OURO)[0] * 48 == PASS == 2_466_250_752
    assert rl.kv_token_bytes(OURO) == KV_TOKEN == 1_572_864
    cfg = engine_child.model_config(OURO, "o")
    # everything the chip holds: the passes' weights once, the head,
    # the embedding's rows (a step reads 16 of them)
    small = 48 * 4 * 2048 + 2048 + 2049
    assert PASS + small + 2 * 2048 * 49152 == cfg.num_params \
        == 2_667_974_657
    assert abs(rl.pass_bytes(OURO) - (PASS + 2 * 48 * 4 * 2048)) < 20_000
    parts = rl.decode_step_parts(OURO, 16.0, 16 * 314.0)
    total = sum(parts.values())
    assert abs(parts["weights"] / 9.87e9 - 1) < 2e-3
    assert abs(parts["kv"] / 7.9e9 - 1) < 0.01
    assert abs(total / 17.9e9 - 1) < 0.01
    assert 0.54 < parts["weights"] / total < 0.57
    assert 0.43 < parts["kv"] / total < 0.45
    step = rl.decode_step_needs(OURO, 16.0, 16 * 314.0)
    least = rl.least_seconds(step, KIND)
    assert least["bound"] == "bytes" and 21.5e-3 < least["seconds"] < 22.2e-3
    # one attention call of 192 reads a 192nd of the K/V, by its bytes
    call = rl.attention_call_needs(OURO, 16.0, 16 * 314.0)
    assert abs(192 * call["bytes"] / parts["kv"] - 1) < 0.02
    assert rl.least_seconds(call, KIND)["bound"] == "bytes"
    # a prefill dispatch of one 64-token row is bound by its bytes (the
    # weights four times, 12 ms), one of 192 tokens by the matrix unit
    # (four passes of 2.47 G weights: 3.8 T operations, 19 ms)
    assert rl.least_seconds(rl.prefill_chunk_needs(OURO, 1.0, 64.0),
                            KIND)["bound"] == "bytes"
    one = rl.least_seconds(rl.prefill_chunk_needs(OURO, 1.0, 192.0), KIND)
    assert one["bound"] == "operations" and 19e-3 < one["seconds"] < 20e-3
    # M under the same mix: twelve times fewer bytes a token
    with open(os.path.join(mf.HERE, "configs", "mistral-7b-int8.json")) as f:
        m = json.load(f)
    assert 32 * 2 * 8 * 128 * 2 * 12 == KV_TOKEN
    assert m["num_hidden_layers"] == 32
    assert roofline.PEAKS[KIND]["hbm_bytes_per_s"] == 819e9


def test_kernels_off_under_this_files_harness_key():
    harness = harness_key.read(CONFIG)
    assert harness["kernel_tables"] == ["attention_paths"]
    assert harness["decode_step"] == {"op": "paged_decode_attention",
                                      "calls_per_step": 192}
    assert harness["prefill_dispatch"] == {"op": "paged_attention"}
    probe = harness["probe"]
    assert probe["logprob_gap_limit"] or probe["mean_logprob_gap_limit"]
    good = {"attention_paths": {"decode|8|512|16": "pallas_paged_decode",
                                "prefill|256|512|1": "pallas_paged"},
            "moe_paths": {}, "mixer_paths": {}}
    assert harness_key.kernels_off(good, harness) == {}
    off = {**good, "attention_paths": {
        **good["attention_paths"], "prefill|256|512|1": "jnp_gather"}}
    assert harness_key.kernels_off(off, harness) == {
        "attention_paths[prefill|256|512|1]": "jnp_gather"}


def test_the_configuration_file_is_the_catalogs_whole():
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ouro-2.6B")
    assert OURO["source"] == row["source_url"]
    assert OURO["reduced"] == []
    for key, value in row["config"].items():
        assert OURO[key] == value, key
    assert OURO["architectures"] == ["OuroForCausalLM"]
    for key in ("between_passes", "bias", "norms", "rotary", "exit",
                "kv_per_pass", "layer_types", "weights", "quantization",
                "kv_cache", "tokenizer", "positions"):
        assert key in OURO["assumed"]
    for key in ("stands_for", "harness_why", "engine_args_why"):
        assert len(OURO[key]) > 40, key
    # ``assumed`` is prose, as in every other file: no entry of it is
    # read by the program, and what the random sandwich norms start at
    # is the program's own (llama.LOOPED_SANDWICH_NORM_INIT)
    assert all(isinstance(v, str) for v in OURO["assumed"].values())
    assert f"at {llama.LOOPED_SANDWICH_NORM_INIT}," \
        in OURO["assumed"]["weights"]
    assert len(OURO["assumed"]["weights_why"]) > 40
    assert engine_child.model_config(OURO, "o") \
        == engine_child.model_config(
            {k: v for k, v in OURO.items() if k != "assumed"}, "o")
    assert OURO["reference"] == "ouro" and OURO["quantization"] == "int8"
    args = OURO["engine_args"]
    for flag, value in (("--max-num-seqs", "16"),
                        ("--max-model-len", "2048"),
                        ("--prefill-chunk", "256"),
                        ("--kv-block-size", "64")):
        assert args[args.index(flag) + 1] == value
    # the pool in whole blocks, within the issue's range
    pool = int(args[args.index("--kv-pool-tokens") + 1])
    assert pool % 64 == 0 and 5696 <= pool <= 7232
    cfg = engine_child.model_config(OURO, "o")
    assert (cfg.num_layers, cfg.loop_steps, cfg.pool_layers) == (48, 4, 192)
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == OURO["name"]]
    assert entry["file"] == "chipbench/configs/ouro-2.6b-int8.json"
    assert entry["reduced"] == [] and entry["source"] == OURO["source"]


def test_the_traffic_and_the_cell_are_the_issues():
    cell = mf.Cell(MANIFEST, CELL, [])
    assert cell.chips == 1 and len(cell.why) <= 200
    assert cell.traffic_name == "decode-closed"     # as it is: M's mix
    assert cell.traffic["loop"] == "closed" \
        and cell.traffic["clients"] == 16
    assert cell.params["decode_batch_buckets"] == [16]
    from chipbench import traffic
    plan = traffic.make_plan(cell.traffic, 5, 50.0)
    assert min(plan.prompts) >= 64 and max(plan.prompts) <= 256
    assert set(plan.outputs) == {256}
    assert not [w for w in MANIFEST["workloads"] if w["chips"] != 1]
    # the cell reports every metric that lists no cells
    listless = [m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m]
    assert set(listless) <= {s["name"] for s in cell.per_layer}


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


def test_the_reader_imports_no_other_models_reader():
    with open(os.path.join(mf.HERE, "readers", "roofline_looped.py")) as f:
        text = f.read()
    imported = [line.split()[1] for line in text.splitlines()
                if line.startswith("from ")]
    assert set(imported) == {"_common", "trace_module", "chipbench"}


def test_rehearsal_of_the_cell_at_a_tiny_file(tmp_path):
    """The benchmark's new cell in shape on the CPU, end to end through
    router and engine (rehearsal/BENCHMARK.looped.json): three layers
    run four times over twelve pool layers behind the program's normal
    server entry point, the probe against chipbench/references/ouro.py,
    every listless counter metric and the three new counters' in a
    traced line (no device metric from a CPU run). From a tree of
    links, so that the run keeps its ``.chipbench/`` to itself. Some
    40 s: an engine and a router start, 26 executables compile."""
    base = os.path.join(mf.ROOT, "tests", "chipbench", "rehearsal")
    for name in ("chipbench", "production_stack_tpu", "tests",
                 "BENCHMARK.json"):
        os.symlink(os.path.join(mf.ROOT, name), tmp_path / name)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--manifest",
         os.path.join(base, "BENCHMARK.looped.json"), "--data", base,
         "--rehearse", "--workload", "tiny-ouro-closed", "--seed",
         str(2**31 + 79), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["why"]
    assert line["failed"] == 0 and line["attempted"] > 0
    metrics = line["metrics"]
    assert metrics["loop_kv_bytes_per_token"]["value"] \
        == 12 * 2 * 4 * 32 * 2
    assert metrics["loop_passes_per_row_step"]["value"] == 4.0
    assert 0 < metrics["loop_kv_step_share"]["value"] < 100
    assert metrics["kv_alloc_failures"]["value"] == 0
    assert "looped_decode_step_roofline" not in metrics     # no device
    listless = {m["name"] for m in MANIFEST["per_layer"]
                if "workloads" not in m and m["source"] != "device_trace"
                } - {"hbm_peak_share"}      # no device memory on the CPU
    assert listless <= set(metrics)


# ---------------------------------------------------------------------
# the reference against the program as the probe compares them
# (test_chipbench_reference.py's manner: bfloat16 against float32 on
# the same int8 weights, the served top-20)
# ---------------------------------------------------------------------

# through twelve tiny layer-passes the program's bfloat16 measures
# 0.01-0.03 from the float32 reference on the CPU; 0.08 is over twice
# that and under what the least of the breakages below measures (a pass
# left out, 0.25 or more)
TINY_TOLERANCE = 0.08


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import reference
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    base = os.path.join(mf.ROOT, "tests", "chipbench", "rehearsal")
    with open(os.path.join(base, "configs", "tiny-ouro.json")) as f:
        conf = json.load(f)
    cfg = engine_child.model_config(conf, "tiny-ouro")
    params = llama.init_params(cfg, jax.random.PRNGKey(5),
                               quantization="int8")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (9, 40, 77)]
    served = []
    for p in prompts:
        cache = kv_pool.cache_for(cfg, 9, 16, cfg.dtype)
        tables = jnp.asarray([list(range(1, 9))], jnp.int32)
        logits, _, _ = llama.forward(
            params, cfg, jnp.asarray([p]), jnp.arange(len(p))[None, :],
            cache, block_tables=tables, kv_len=128)
        lps = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        top_lp, top_id = jax.lax.top_k(lps, reference.TOP)
        served.append({"prompt_tokens": len(p),
                       "ids": [int(i) for i in top_id],
                       "logprobs": [float(v) for v in top_lp]})
    return conf, params, prompts, served


def test_reference_agrees_with_the_program(tiny):
    from chipbench import reference
    from chipbench.references import ouro
    conf, params, prompts, served = tiny
    rows = ouro.next_token_logprobs(
        params, conf, prompts, [s["ids"] for s in served])
    out = reference.compare(served, rows, tolerance=TINY_TOLERANCE)
    assert out["ok"], out
    assert all(r["shared_top"] >= 18 for r in out["rows"])


@pytest.mark.parametrize("breakage", [
    {"total_ut_steps": 3}, {"kv_control": "last_pass"},
    {"kv_control": "first_pass"}, {"norm_control": "off"},
    {"sandwich_control": "off"}, {"round_to": "float8_e4m3fn"}],
    ids=["three-passes", "last-pass-kv", "first-pass-kv", "no-norm-between",
         "no-sandwich", "float8"])
def test_the_tolerance_sees_a_wrong_loop(tiny, breakage):
    """A reference that departs from the served mathematics in one
    place (the two lean controls that are this model's own, the other
    switches, the float8 control) falls outside the tolerance: the
    comparison can tell."""
    from chipbench import reference
    from chipbench.references import ouro
    conf, params, prompts, served = tiny
    rows = ouro.next_token_logprobs(
        params, {**conf, **breakage}, prompts,
        [s["ids"] for s in served])
    assert not reference.compare(served, rows,
                                 tolerance=TINY_TOLERANCE)["ok"]
