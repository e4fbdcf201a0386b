"""What PR 42 added to the benchmark for ``qwen3next-longctx-closed``:
the manifest's new entries as the manifest then is, the hybrid yardstick
(chipbench/roofline_hybrid.py) against hand counts, the new readers on
a hand-made record, the configuration's file against the catalog's
published keys, the plain reference (chipbench/references/qwen3_next)
against the program at a tiny size. The CPU rehearsal of the cell at a
tiny ``qwen3_next`` file (``rehearsal/BENCHMARK.hybrid.json``,
``rehearsal/configs/tiny-gdn.json``) is run by tests/test_gdn.py, away
from the rehearsals of this directory (it was put there while they
shared ``.chipbench/``'s one engine log; since PR 46 a run has a
directory of its own).

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import, as
test_chipbench_sparse's does. Where PR 42's entries stand in the
manifest is ``manifest_history/pr42.json``'s (test_chipbench_manifest).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_chipbench_readers as first

from chipbench import engine_child, reference, roofline, roofline_hybrid
from chipbench import manifest as mf
from chipbench import run as runner
from chipbench.references import qwen3_next

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "qwen3next-longctx-closed"
CONFIG = os.path.join(mf.HERE, "configs",
                      "qwen3-next-80b-a3b-int8-l24-e64.json")
NEW = ("hybrid_decode_step_device_ms", "hybrid_decode_step_roofline",
       "hybrid_prefill_chunk_roofline", "gdn_decode_kernel_roofline",
       "gdn_prefill_kernel_roofline", "state_bytes_per_slot")
# bfloat16 at a tiny size: the 0.02 of tests/chipbench's two- and three-
# layer toys, twice, for eight layers (the served path reads 0.005-0.022
# here; the least of the breakages below over 0.08)
TINY_TOLERANCE = 0.04
with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(CONFIG) as f:
    QWEN = json.load(f)
SPECS = {n: mf.load(os.path.join(mf.HERE, "metrics", n + ".json"))
         for n in NEW}
STATE = 18 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)


def record():
    """test_chipbench_readers' synthetic run as a run of the new cell:
    the configuration's file; 58 decode steps of 6 attention and 18
    Gated DeltaNet calls each in 0.58 s; two runs of the 2048-token
    prefill executable; the counters ``totals.moe``, ``totals.state``
    and ``totals.prefill`` and the state's bytes a slot; one request
    (200 prompt tokens, one token received) decoding while traced."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"]["started_unix"] = 1004.2          # middle: t = 104.7
    run["trace"]["modules"] = {
        "jit_decode_window_1_": {
            "runs": 7, "total_s": 0.56, "median_s": 0.08,
            "ops": {"paged_decode_attention": [7 * 8 * 6, 0.004],
                    "gdn_recurrent_step": [7 * 8 * 18, 0.028]}},
        "jit_decode_window_2_": {
            "runs": 1, "total_s": 0.02, "median_s": 0.02,
            "ops": {"paged_decode_attention": [2 * 6, 0.0002],
                    "gdn_recurrent_step": [2 * 18, 0.001]}},
        "jit_prefill_chunk_9_": {
            "runs": 2, "total_s": 0.2, "median_s": 0.1,
            "ops": {"paged_attention": [12, 0.03],
                    "gdn_chunk_scan": [36, 0.036]}}}
    for at, read, resident, q, disp in (
            ("perf_open", 1000, 2000, 1000, 10),
            ("perf_close", 1140, 3000, 3048, 12)):
        totals = run[at]["totals"]
        totals["moe"] = {"experts_read": read,
                         "experts_resident": resident}
        # a query: 6000 keys at or before it
        totals["state"] = {"scan_tokens": q, "prefill_keys": 6000 * q,
                           "step_rows": 0, "pages_alloc": 0,
                           "pages_freed": 0, "alloc_failures": 0}
        # a dispatch: 1024 positions, real and padded
        totals["prefill"] = {"real": 900 * disp, "pad": 124 * disp,
                             "dispatches": disp}
        run[at]["kv_pool"].update(bytes_per_token=12288, layout="kv+state",
                                  state_bytes_per_slot=STATE)
    return run


# one live row of 201 context tokens, 14 % of the 64 held experts read
# a layer and step
_STEP = roofline_hybrid.decode_step_needs(QWEN, [201], 0.14 * 64)
_CHUNK = roofline_hybrid.prefill_chunk_needs(QWEN, 2048, 6000.0)
_RULE1 = roofline_hybrid.gdn_call_needs(QWEN, 1, 1)
_RULE1024 = roofline_hybrid.gdn_call_needs(QWEN, 1, 1024)


def _least(needs):
    return max(needs["bytes"] / 819e9, needs["ops"] / 197e12)


EXPECTED = {
    "hybrid_decode_step_device_ms": 1e3 * 0.58 / 58,
    "hybrid_decode_step_roofline": 100 * _least(_STEP) / (0.58 / 58),
    "hybrid_prefill_chunk_roofline": 100 * _least(_CHUNK) / 0.1,
    "gdn_decode_kernel_roofline":
        100 * _least(_RULE1) / (0.029 / (58 * 18)),
    "gdn_prefill_kernel_roofline": 100 * _least(_RULE1024) / (0.036 / 36),
    "state_bytes_per_slot": float(STATE),
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
    """A record of a program without state pages (no ``totals.state``,
    no such bytes, no trace; and, for the trace's readers, another
    configuration's file with a trace that happens to hold the
    kernels' names): None, nothing raised: what the parent commit
    gives the driver's traced runs of the accepted cells."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"] = None
    assert runner.read_metric(SPECS[name], run, []) is None
    other = first.synthetic()           # Mistral's file: no hybrid
    for op in ("gdn_recurrent_step", "gdn_chunk_scan"):
        other["trace"]["modules"]["jit__unknown_1_"]["ops"][op] = [1, 0.1]
    if name != "state_bytes_per_slot":
        assert runner.read_metric(SPECS[name], other, []) is None


def test_the_step_note_names_the_hybrid_yardstick():
    run = record()
    runner.read_metric(SPECS["hybrid_decode_step_roofline"], run, [])
    note = run["notes"]["decode_step_roofline"]
    assert (note["rows"], note["context_tokens"], note["bound"],
            note["yardstick"]) == (1, 201, "bytes", "roofline_hybrid")
    assert note["experts_touched"] == pytest.approx(0.14 * 64)


def test_the_listless_step_metric_reads_one_step_here():
    """``decode_step_device_ms`` (trace_module, ``kind: decode_step``,
    ``per: step``) divides the attention kernel's calls by what this
    configuration's file says a step makes, 6 (``harness.decode_step.
    calls_per_step``), and reads what ``hybrid_decode_step_device_ms``
    reads; until PR 46 it divided by ``num_hidden_layers`` (24) and
    read four steps."""
    spec = mf.load(os.path.join(mf.HERE, "metrics",
                                "decode_step_device_ms.json"))
    assert runner.read_metric(spec, record(), []) == pytest.approx(
        EXPECTED["hybrid_decode_step_device_ms"], rel=1e-12)


def test_the_yardstick_counts_the_issue_arithmetic():
    """ISSUE 42's cut, parameter by parameter, and what a decode step
    and a chunk need."""
    (gdn_q, gdn_s), (attn_q, attn_s) = roofline_hybrid.mixer_weights(QWEN)
    assert gdn_q + gdn_s == (2048 * 12288 + 2048 * 64 + 8192 * 4
                             + 4096 * 2048 + 192) == 33718464
    assert attn_q + attn_s == (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
                               + 512) == 27263488
    assert roofline_hybrid.layer_counts(QWEN) == (18, 6)
    assert roofline_hybrid.state_bytes(QWEN) == 2146304
    assert 18 * roofline_hybrid.state_bytes(QWEN) == STATE == 38633472
    expert = 3 * 2048 * 512
    read, passed = roofline_hybrid.weights(QWEN, 64, 10 / 8)
    # every parameter the program holds (5.78 B), less the embedding
    # (rows are gathered, not read whole); the small leaves at 2 bytes
    small = (18 * gdn_s + 6 * attn_s + 24 * (2048 * 512 + 3 * 2048) + 2048)
    quantised = (18 * gdn_q + 6 * attn_q + 24 * expert + 2048 * 18992)
    assert read == quantised + 24 * 64 * expert + 2 * small
    assert quantised + small + 24 * 64 * expert + 2048 * 18992 \
        == engine_child.model_config(QWEN, "q").num_params
    assert passed == quantised + small + 24 * 1.25 * expert
    # a decode step of 8 rows at 12k: the issue's 3.3 GB, 4 ms
    step = roofline_hybrid.decode_step_needs(QWEN, [12000] * 8, 9.0)
    assert step["bytes"] == (
        quantised + 24 * 9 * expert + 2 * small
        + 6 * 8 * 12000 * 2 * 2 * 256 * 2
        + 18 * (8 * 2 * 2146304
                + 8 * ((2 * 2048 + 4096) * 2 + 2 * 32 * 4 + 4096 * 2)))
    least = roofline.least_seconds(step, "TPU v5 lite")
    assert least["bound"] == "bytes"
    assert 3.0e9 < step["bytes"] < 3.6e9 and 3.6e-3 < least["seconds"] < 4.4e-3
    # a token of a 2048-token chunk at 6k of context: the issue's
    # "about 2.7 GFLOP", the recurrence 6 x 128 x 128 a head
    chunk = roofline_hybrid.prefill_chunk_needs(QWEN, 2048, 6000.0)
    assert roofline.least_seconds(chunk, "TPU v5 lite")["bound"] \
        == "operations"
    assert 2.0e9 < chunk["ops"] / 2048 < 3.4e9
    rule = roofline_hybrid.gdn_call_needs(QWEN, 1, 2048)
    assert rule["ops"] == 2048 * 32 * 6 * 128 * 128
    assert rule["bytes"] == (
        2048 * (2 * 2048 * 2 + 4096 * 2 + 2 * 32 * 4 + 4096 * 2)
        + 2 * 32 * 128 * 128 * 4)
    one = roofline_hybrid.gdn_call_needs(QWEN, 8, 8)
    assert one["bytes"] > 8 * 2 * 2097152 and one["ops"] == 8 * 32 * 98304


def test_the_configuration_file_states_its_cut():
    """Every number of the catalog's ``config`` under the same key,
    the reduced keys with their published values beside them, the
    deployment, the assumed sizes."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert QWEN["source"] == row["source_url"]
    reduced = set(QWEN["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in reduced:
            assert QWEN["published"][key] == value and QWEN[key] != value
        else:
            assert QWEN[key] == value, key
    assert QWEN["deployment"] == {"chips_per_layer": 8, "chip_index": 0,
                                  "router_experts": 512,
                                  "pipeline_stages": 2}
    assert (QWEN["num_hidden_layers"], QWEN["num_experts"],
            QWEN["vocab_size"] * 8) == (24, 64, 151936)
    assert "8 chips" in QWEN["stands_for"]
    assert "eighth" in QWEN["expert_load_share"]
    for key in ("routed_down_init_std", "state_pages", "kv_cache",
                "in_proj_qkvz_columns", "rotary", "quantization",
                "multi_token_prediction"):
        assert key in QWEN["assumed"]
    args = QWEN["engine_args"]
    assert args[args.index("--kv-pool-tokens") + 1] == "131072"
    assert args[args.index("--prefill-chunk") + 1] == "2048"
    cfg = engine_child.model_config(QWEN, "q")
    assert abs(cfg.num_params / 5.78e9 - 1) < 0.01
    assert cfg.state_bytes_per_seq == STATE
    assert (cfg.router_experts, cfg.expert_offset, cfg.rotary_dim,
            cfg.gdn_layers, cfg.attn_layers) == (512, 0, 64, 18, 6)


def test_the_traffic_and_the_cell_are_the_issues():
    cell = mf.Cell(MANIFEST, CELL, [])
    assert cell.chips == 1 and len(cell.why) <= 200
    assert cell.traffic_name == "longctx-closed"
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 8
    assert cell.params["decode_batch_buckets"] == [8]
    from chipbench import traffic
    plan = traffic.make_plan(cell.traffic, 5, 50.0)
    assert sorted(plan.prompts) == [8640 + 896 * i for i in range(8)]
    assert set(plan.outputs) == {512}
    assert max(plan.prompt_ids(next(plan.stream()))) < QWEN["vocab_size"]
    from production_stack_tpu.engine.config import EngineConfig
    shapes = engine_child.shapes_reached(
        EngineConfig(model="debug-gdn", quantization="int8",
                     max_num_seqs=8, max_model_len=16384,
                     kv_pool_tokens=131072, prefill_chunk=2048),
        runner.reach_of(cell, plan))
    assert [8, 8, 16384] in shapes["decode"]
    assert [2048, 16384] in shapes["prefill"]
    assert all(b == 8 for b, _, _ in shapes["decode"])


# ---------------------------------------------------------------------
# the manifest's entries for this cell (where they stand in it, and what
# each cell reports: test_chipbench_manifest.py, manifest_history/)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("name", NEW)
def test_manifest_entry_matches_the_metric_file(name):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == SPECS[name][key]
    assert entry["workloads"] == [CELL]
    assert set(SPECS[name]) == {"name", "unit", "better", "source",
                                "layer", "moves", "reader", "args"}


def _strings(obj):
    if isinstance(obj, str):
        yield obj
    elif isinstance(obj, dict):
        for v in obj.values():
            yield from _strings(v)
    elif isinstance(obj, list):
        for v in obj:
            yield from _strings(v)


def test_every_manifest_string_is_at_most_200_characters():
    assert max(map(len, _strings(MANIFEST))) <= 200


def test_layers_are_the_manifests_own_or_named_in_perf_md():
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW}
    new_layers = {SPECS[n]["layer"] for n in NEW} - layers
    assert new_layers == {
        "kernels (ops/gdn.py delta rule)",
        "kernels (ops/gdn.py, ops/pallas_paged.py, ops/moe.py) as one step"}
    with open(os.path.join(mf.ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in new_layers)


# ---------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------

def _tiny():
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "tiny-gdn.json")) as f:
        conf = json.load(f)
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    cfg = engine_child.model_config(conf, "tiny-gdn")
    params = llama.init_params(cfg, jax.random.PRNGKey(5),
                               quantization="int8")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (9, 40, 100)]
    served = []
    for p in prompts:       # through both caches; 100 tokens: two chunks
        cache = kv_pool.cache_for(cfg, 9, 16, state_pages=2)
        logits, _, _ = llama.forward(
            params, cfg, jnp.asarray([p]), jnp.arange(len(p))[None], cache)
        lps = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        top_lp, top_id = jax.lax.top_k(lps, reference.TOP)
        served.append({"prompt_tokens": len(p),
                       "ids": [int(i) for i in top_id],
                       "logprobs": [float(v) for v in top_lp]})
    return conf, params, prompts, served


def test_reference_agrees_with_the_program_at_a_tiny_size():
    conf, params, prompts, served = _tiny()
    rows = qwen3_next.next_token_logprobs(
        params, conf, prompts, [s["ids"] for s in served])
    out = reference.compare(served, rows, tolerance=TINY_TOLERANCE)
    assert out["ok"], out
    assert all(r["shared_top"] >= 18 for r in out["rows"])


@pytest.mark.parametrize("breakage", [
    {"gdn_control": "no_decay"}, {"gdn_control": "beta_one"},
    {"gdn_control": "no_conv_carry", "conv_chunk": 2},
    {"attn_control": "no_gate"}, {"attn_control": "rotary_all"},
    {"num_experts_per_tok": 1},
    {"deployment": {"chips_per_layer": 2, "chip_index": 0,
                    "router_experts": 8}}],
    ids=lambda b: str(next(iter(b.values())))[:24])
def test_the_probe_tolerance_sees_a_wrong_block(breakage):
    """A reference that departs from the served mathematics in one
    place (no decay, beta one, a convolution that forgets, no output
    gate, rotary on every column, fewer experts a token, the other
    chip's experts) falls outside the tolerance at this size."""
    conf, params, prompts, served = _tiny()
    rows = qwen3_next.next_token_logprobs(
        params, {**conf, **breakage}, prompts, [s["ids"] for s in served])
    assert not reference.compare(served, rows,
                                 tolerance=TINY_TOLERANCE)["ok"]


# the mean gap over the sixty served log-probabilities at this size: the
# served path and the bfloat16 reference read under 0.005, float8 over
# 0.1
TINY_MEAN_LIMIT = 0.02


@pytest.mark.parametrize("limits", [(TINY_TOLERANCE, None),
                                    (None, TINY_MEAN_LIMIT)],
                         ids=["widest_gap", "mean_gap"])
@pytest.mark.parametrize("dtype, fails", [("float8_e4m3fn", True),
                                          ("bfloat16", False)])
def test_the_lower_precision_control_fails_and_the_served_one_passes(
        dtype, fails, limits):
    """The control a probe's limits are set under (chipbench/
    probe_seeds.py reads it on the chip at the cell's own size): the
    reference with its residual stream rounded to float8, the precision
    below the served bfloat16, PUT IN THE PROGRAM'S PLACE (its own
    top-20 held against the plain reference's) comes out not correct,
    by one prompt's widest gap and by the run's mean gap alike; rounded
    to the served precision it passes both, as the program does."""
    conf, params, prompts, served = _tiny()
    own = qwen3_next.next_token_logprobs(
        params, {**conf, "round_to": dtype}, prompts,
        [[0]] * len(prompts))
    stand_in = [{"prompt_tokens": r["prompt_tokens"], "ids": r["top_ids"],
                 "logprobs": r["top_logprobs"]} for r in own]
    plain = qwen3_next.next_token_logprobs(
        params, conf, prompts, [r["top_ids"] for r in own])
    out = reference.compare(stand_in, plain, *limits)
    assert out["ok"] is not fails, out
    rows = qwen3_next.next_token_logprobs(
        params, conf, prompts, [s["ids"] for s in served])
    assert reference.compare(served, rows, *limits)["ok"]


def test_the_cells_file_holds_the_probe_to_its_mean_gap():
    """N's readings on the chip (PERF.md section 2) separate on the
    mean gap and not on one prompt's widest, so its file states the one
    and not the other; the file's words give the readings."""
    from chipbench import harness_key
    assert harness_key.of(QWEN)["probe"] == {
        "logprob_gap_limit": None, "mean_logprob_gap_limit": 0.3}
    for reading in ("0.110", "0.617", "0.5435", "1.258"):
        assert reading in QWEN["harness_why"]
