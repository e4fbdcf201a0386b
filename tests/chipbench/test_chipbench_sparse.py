"""What PR 40 added to the benchmark for ``glm5-longctx-closed``: the
manifest's new entries as the manifest then is, the sparse yardstick
(chipbench/roofline_sparse.py) against hand counts, the new readers on
a hand-made record, the configuration's file against the catalog's
published keys, the plain reference (chipbench/references/glm_moe_dsa)
against the program at a tiny size. The CPU rehearsal of the cell at
a tiny ``glm_moe_dsa`` file (``rehearsal/BENCHMARK.sparse.json``,
``rehearsal/configs/tiny-dsa.json``) is run by tests/test_dsa.py, away
from the rehearsals of this directory (it was put there while they
shared ``.chipbench/``'s one engine log, whose tail
``test_without_a_chip_there_is_no_result`` reads; since PR 46 a run
has a directory of its own).

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import, as
test_chipbench_latent's and test_chipbench_host_readers' do: the
completeness check there sees these six metrics covered. Where PR 40's
entries stand in the manifest is ``manifest_history/pr40.json``'s
(test_chipbench_manifest).
"""

import json
import os
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_chipbench_readers as first

from chipbench import engine_child, reference, roofline, roofline_sparse
from chipbench import manifest as mf
from chipbench import run as runner
from chipbench.references import glm_moe_dsa

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "glm5-longctx-closed"
CONFIG = os.path.join(mf.HERE, "configs", "glm-5-int8-l7-e16.json")
NEW = ("sparse_decode_step_roofline", "sparse_prefill_chunk_roofline",
       "indexer_kernel_roofline", "sparse_attention_kernel_roofline",
       "sparse_attended_share", "index_bytes_per_token")
TINY_TOLERANCE = 0.02       # tests/chipbench: bfloat16 at a tiny size
with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(CONFIG) as f:
    GLM5 = json.load(f)
SPECS = {n: mf.load(os.path.join(mf.HERE, "metrics", n + ".json"))
         for n in NEW}


def record():
    """test_chipbench_readers' synthetic run as a run of the new cell:
    the configuration's file, 7 calls of each kernel a decode step, the
    counters ``totals.moe`` and ``totals.sparse`` and the index pool's
    bytes a token; one request (200 prompt tokens, one token received)
    decoding while traced, and two runs of the 2048-token prefill
    executable."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"]["started_unix"] = 1004.2          # middle: t = 104.7
    # 7 runs of 8 steps and one of 2: 58 steps of 7 layers, 0.58 s
    run["trace"]["modules"] = {
        "jit_decode_window_1_": {
            "runs": 7, "total_s": 0.56, "median_s": 0.08,
            "ops": {"paged_decode_attention": [7 * 8 * 7, 0.004],
                    "dsa_index_scores": [7 * 8 * 7, 0.008]}},
        "jit_decode_window_2_": {
            "runs": 1, "total_s": 0.02, "median_s": 0.02,
            "ops": {"paged_decode_attention": [2 * 7, 0.0002],
                    "dsa_index_scores": [2 * 7, 0.0004]}},
        "jit_prefill_chunk_9_": {
            "runs": 2, "total_s": 0.6, "median_s": 0.3,
            "ops": {"paged_attention": [14, 0.2],
                    "dsa_index_scores": [14, 0.02]}}}
    sparse = {"queries": 0, "keys_in_context": 0, "keys_scored": 0,
              "keys_attended": 0}
    for at, read, resident, q in (("perf_open", 1000, 2000, 1000),
                                  ("perf_close", 1140, 3000, 3048)):
        run[at]["totals"]["moe"] = {"experts_read": read,
                                    "experts_resident": resident}
        # a query: 8000 keys in context, all scored, 2048 attended
        prefill = {"queries": q, "keys_in_context": 8000 * q,
                   "keys_scored": 8000 * q, "keys_attended": 2048 * q}
        decode = {"queries": q, "keys_in_context": 10000 * q,
                  "keys_scored": 10000 * q, "keys_attended": 2048 * q}
        run[at]["totals"]["sparse"] = {
            **{k: prefill[k] + decode[k] for k in sparse if k != "queries"},
            "prefill": prefill, "decode": decode}
        run[at]["kv_pool"].update(bytes_per_token=10752,
                                  index_bytes_per_token=1792,
                                  layout="latent+index")
    return run


# one live row of 201 context tokens (under index_topk: all attended),
# 14 % of the 16 held experts read a layer and step
_STEP = roofline_sparse.decode_step_needs(GLM5, [201], 0.14 * 16)
_CHUNK = roofline_sparse.prefill_chunk_needs(GLM5, 2048, 8000.0, 8000.0,
                                             2048.0)
EXPECTED = {
    "sparse_decode_step_roofline":
        100 * _STEP["bytes"] / 819e9 / (0.58 / 58),
    "sparse_prefill_chunk_roofline":
        100 * max(_CHUNK["bytes"] / 819e9, _CHUNK["ops"] / 197e12) / 0.3,
    "indexer_kernel_roofline":
        100 * (201 * 128 * 2 / 819e9) / (0.0084 / (58 * 7)),
    "sparse_attention_kernel_roofline":
        100 * (201 * 640 * 2 / 819e9) / (0.0042 / (58 * 7)),
    "sparse_attended_share": 100 * 2 * 2048 / 18000,
    "index_bytes_per_token": 1792.0,
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
    """A record without ``totals.sparse``, the index pool's bytes or a
    trace (and, for the rooflines, another configuration's file with a
    trace): None, nothing raised."""
    run = first.synthetic()
    run["config_file"] = CONFIG
    run["trace"] = None
    assert runner.read_metric(SPECS[name], run, []) is None
    other = first.synthetic()           # Mistral's file: no indexer
    other["trace"]["modules"]["jit__unknown_1_"]["ops"][
        "dsa_index_scores"] = [1, 0.1]
    if name.endswith("_roofline"):
        assert runner.read_metric(SPECS[name], other, []) is None


def test_the_step_note_names_the_sparse_yardstick():
    run = record()
    runner.read_metric(SPECS["sparse_decode_step_roofline"], run, [])
    note = run["notes"]["decode_step_roofline"]
    assert (note["rows"], note["context_tokens"], note["bound"],
            note["yardstick"]) == (1, 201, "bytes", "roofline_sparse")
    assert note["experts_touched"] == pytest.approx(0.14 * 16)


def test_the_yardstick_counts_the_issue_arithmetic():
    """ISSUE 40's cut, parameter by parameter, and what a decode step
    and a row's attention read at 16k of context."""
    attn = roofline_sparse.attention_weights(GLM5)
    assert attn == (6144 * 2048 + 2048 * 16384 + 6144 * 576 + 512 * 28672
                    + 16384 * 6144) + (2048 * 4096 + 6144 * 128 + 6144 * 32)
    assert round(attn / 1e6, 1) == 174.4
    read, passed = roofline_sparse._layer_weights(GLM5, 16, 8 / 16)
    expert = 3 * 6144 * 2048
    assert read == (7 * attn + 3 * 6144 * 12288 + 6 * (
        6144 * 256 + expert + 16 * expert) + 6144 * 19360)
    assert passed == read - 6 * 15.5 * expert
    # a row at 16384 of context: every index key, 2048 latents
    index = roofline_sparse.index_call_needs(GLM5, [16384])
    attend = roofline_sparse.attention_call_needs(GLM5, [16384, 100])
    assert index["bytes"] == 16384 * 256
    assert attend["bytes"] == (2048 + 100) * 1280
    assert (index["bytes"] + 2048 * 1280) / 1e6 == pytest.approx(6.8, abs=0.02)
    assert attend["ops"] == 2.0 * 2148 * 64 * (576 + 512)
    step = roofline_sparse.decode_step_needs(GLM5, [12000] * 8, 4.0)
    weights = (7 * attn + 3 * 6144 * 12288 + 6 * (
        6144 * 256 + expert + 4 * expert) + 6144 * 19360)
    assert step["bytes"] == weights + 7 * 8 * (12000 * 256 + 2048 * 1280)
    assert roofline.least_seconds(step, "TPU v5 lite")["bound"] == "bytes"
    chunk = roofline_sparse.prefill_chunk_needs(GLM5, 2048, 9000.0, 9000.0,
                                                2048.0)
    assert roofline.least_seconds(chunk, "TPU v5 lite")["bound"] == "operations"
    per_token = chunk["ops"] / 2048
    assert 4.5e9 < per_token < 7.5e9        # ISSUE 40: about 6 GFLOP


def test_the_configuration_file_states_its_cut():
    """Every number of the catalog's ``config`` under the same key,
    the reduced keys with their published values beside them, the
    deployment, the assumed sizes."""
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    assert GLM5["source"] == row["source_url"]
    reduced = set(GLM5["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size"}
    for key, value in row["config"].items():
        if key in reduced:
            assert GLM5["published"][key] == value and GLM5[key] != value
        else:
            assert GLM5[key] == value, key
    assert GLM5["deployment"] == {"chips_per_layer": 16, "chip_index": 0,
                                  "router_experts": 256}
    assert (GLM5["num_hidden_layers"], GLM5["n_routed_experts"],
            GLM5["vocab_size"] * 8) == (7, 16, 154880)
    assert "16 chips" in GLM5["stands_for"]
    assert "sixteenth" in GLM5["expert_load_share"]
    for key in ("routed_down_init_std", "indexer_key_norm",
                "indexer_rotary", "indexer_departures", "kv_cache",
                "multi_token_prediction"):
        assert key in GLM5["assumed"]
    args = GLM5["engine_args"]
    assert args[args.index("--kv-pool-tokens") + 1] == "131072"
    assert args[args.index("--prefill-chunk") + 1] == "2048"


def test_the_traffic_and_the_cell_are_the_issues():
    cell = mf.Cell(MANIFEST, CELL, [])
    assert cell.chips == 1 and len(cell.why) <= 200
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 8
    assert cell.params["decode_batch_buckets"] == [8]
    from chipbench import traffic
    plan = traffic.make_plan(cell.traffic, 5, 50.0)
    # ISSUE 40's grid: eight lengths over 8192-15360, five to eight
    # chunks of 2048 each
    assert cell.traffic["prompt_tokens"]["knots"] == [[0, 8192], [1, 15360]]
    assert sorted(plan.prompts) == [8640 + 896 * i for i in range(8)]
    assert sorted(-(-n // 2048) for n in plan.prompts) == [
        5, 5, 6, 6, 6, 7, 7, 8]
    assert set(plan.outputs) == {512}
    assert max(plan.prompt_ids(next(plan.stream()))) < GLM5["vocab_size"]
    shapes = engine_child.shapes_reached(
        _engine_config(), runner.reach_of(cell, plan))
    assert [8, 8, 16384] in shapes["decode"]
    assert [2048, 16384] in shapes["prefill"]
    assert all(b == 8 for b, _, _ in shapes["decode"])


def _engine_config():
    from production_stack_tpu.engine.config import EngineConfig
    return EngineConfig(model="debug-dsa", quantization="int8",
                        max_num_seqs=8, max_model_len=16384,
                        kv_pool_tokens=131072, prefill_chunk=2048)


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


def test_layers_are_the_manifests_own_or_named_in_perf_md():
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW}
    new_layers = {SPECS[n]["layer"] for n in NEW} - layers
    assert new_layers == {"kernels (ops/dsa.py indexer and selection)"}
    with open(os.path.join(mf.ROOT, "PERF.md")) as f:
        assert "kernels (ops/dsa.py indexer and selection)" in f.read()


# ---------------------------------------------------------------------
# the reference against the program, and the cell on the CPU
# ---------------------------------------------------------------------

def _tiny():
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "tiny-dsa.json")) as f:
        conf = json.load(f)
    from production_stack_tpu.models import kv as kv_pool
    from production_stack_tpu.models import llama
    cfg = engine_child.model_config(conf, "tiny-dsa")
    params = llama.init_params(cfg, jax.random.PRNGKey(5),
                               quantization="int8")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (9, 40, 60)]
    served = []
    for p in prompts:       # through both pools: 60 tokens select 16
        cache = kv_pool.cache_for(cfg, 5, 16)
        logits, _, _ = llama.forward(
            params, cfg, jnp.asarray([p]), jnp.arange(len(p))[None], cache,
            block_tables=kv_pool.linear_tables(1, 64, 16))
        lps = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        top_lp, top_id = jax.lax.top_k(lps, reference.TOP)
        served.append({"prompt_tokens": len(p),
                       "ids": [int(i) for i in top_id],
                       "logprobs": [float(v) for v in top_lp]})
    return conf, params, prompts, served


def test_reference_agrees_with_the_program_at_a_tiny_size():
    conf, params, prompts, served = _tiny()
    rows = glm_moe_dsa.next_token_logprobs(
        params, conf, prompts, [s["ids"] for s in served])
    out = reference.compare(served, rows, tolerance=TINY_TOLERANCE)
    assert out["ok"], out
    assert all(r["shared_top"] >= 18 for r in out["rows"])


@pytest.mark.parametrize("breakage", [
    {"select_control": "first"}, {"index_topk": 8},
    {"num_experts_per_tok": 1}, {"routed_scaling_factor": 1.0},
    {"deployment": {"chips_per_layer": 2, "chip_index": 0,
                    "router_experts": 8}}],
    ids=lambda b: next(iter(b)))
def test_the_probe_tolerance_sees_a_wrong_block(breakage):
    """A reference that departs from the served mathematics in one
    place (another selection, fewer positions kept, fewer experts a
    token, another routing scale, the other chip's experts) falls
    outside the tolerance at this size, where every leaf is drawn at
    0.02."""
    conf, params, prompts, served = _tiny()
    rows = glm_moe_dsa.next_token_logprobs(
        params, {**conf, **breakage}, prompts, [s["ids"] for s in served])
    assert not reference.compare(served, rows,
                                 tolerance=TINY_TOLERANCE)["ok"]
