"""What PR 35 added to the benchmark for ``glm47flash-decode-closed``:
the manifest's new entries, the latent roofline's count against a hand
count, the new readers on a hand-made record and on the recorded small
trace, the plain reference against the program at a tiny size, and a
CPU rehearsal of the cell at a tiny ``glm4_moe_lite`` file.

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import:
every worker imports every test file while it collects, so the
completeness check there sees these four metrics covered.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_chipbench_readers as first

from chipbench import engine_child, reference, roofline, xplane
from chipbench import manifest as mf
from chipbench import roofline_latent
from chipbench import run as runner
from chipbench.references import glm4_moe_lite

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "glm47flash-decode-closed"
NEW = ("latent_decode_step_roofline", "latent_attention_kernel_roofline",
       "moe_read_share", "kv_bytes_per_token")
with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(os.path.join(mf.HERE, "configs",
                       "glm-4.7-flash-int8-l13.json")) as f:
    GLM = json.load(f)
SPECS = {n: mf.load(os.path.join(mf.HERE, "metrics", n + ".json"))
         for n in NEW}


def record():
    """test_chipbench_readers' synthetic run as a run of the new cell:
    the configuration's file, 13 calls of the kernel a step, the list
    path's counters and the pool's bytes a token; one request (200
    prompt tokens, one token received) decoding while traced."""
    run = first.synthetic()
    run["config_file"] = os.path.join(mf.HERE, "configs",
                                      "glm-4.7-flash-int8-l13.json")
    run["trace"]["started_unix"] = 1004.2          # middle: t = 104.7
    # 7 runs of 8 steps and one of 2: 58 steps of 13 layers, 0.58 s
    run["trace"]["modules"] = {
        "jit__unknown_1_": {"runs": 7, "total_s": 0.56, "median_s": 0.08,
                            "ops": {"paged_decode_attention":
                                    [7 * 8 * 13, 0.02]}},
        "jit__unknown_2_": {"runs": 1, "total_s": 0.02, "median_s": 0.02,
                            "ops": {"paged_decode_attention":
                                    [2 * 13, 0.0008]}},
        "jit__unknown_9_": {"runs": 2, "total_s": 1.3, "median_s": 0.65,
                            "ops": {"paged_attention": [26, 0.2]}}}
    for at, read, resident in (("perf_open", 1000, 2000),
                               ("perf_close", 1610, 3000)):
        run[at]["totals"]["moe"] = {"experts_read": read,
                                    "experts_resident": resident}
        run[at]["kv_pool"].update(bytes_per_token=16640, layout="latent")
    return run


# one live row, 201 context tokens, and record()'s counters (610 of
# 1000 experts read: 39.04 of 64 a layer): a step's least time by bytes
_LEAST_STEP = roofline_latent.decode_step_needs(
    GLM, 1, 201, 0.61 * 64)["bytes"] / 819e9
_LEAST_CALL = 201 * 640 * 2 / 819e9

EXPECTED = {
    "latent_decode_step_roofline": 100 * _LEAST_STEP / (0.58 / 58),
    "latent_attention_kernel_roofline":
        100 * _LEAST_CALL / (0.0208 / (58 * 13)),
    "moe_read_share": 61.0,
    "kv_bytes_per_token": 16640.0,
}
first.EXPECTED.update(EXPECTED)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_finds_its_number(name):
    value = runner.read_metric(SPECS[name], record(), [])
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_reads_nothing_from_a_program_without_it(name):
    """The parent commit's record (no ``totals.moe`` path of this cell,
    no ``kv_pool.bytes_per_token``, no trace): None, nothing raised."""
    run = first.synthetic()
    run["config_file"] = record()["config_file"]
    run["trace"] = None
    assert runner.read_metric(SPECS[name], run, []) is None


def test_the_step_note_names_the_latent_yardstick():
    run = record()
    runner.read_metric(SPECS["latent_decode_step_roofline"], run, [])
    note = run["notes"]["decode_step_roofline"]
    assert (note["rows"], note["context_tokens"], note["bound"],
            note["yardstick"]) == (1, 201, "bytes", "roofline_latent")


@pytest.mark.parametrize("read, touched", [
    (610, 39.04), (1000, 64.0), (31.25, 2.0)])
def test_the_step_yardstick_counts_the_experts_the_rows_chose(read,
                                                              touched):
    """ONE source: the program's counters (``totals.moe``, the share
    ``moe_read_share`` reports) say how many routed experts a layer of
    a step read, whether that is fewer than even routing's expectation
    (a selection bias makes routing uneven) or every expert (a program
    that reads them all is held to all their bytes). No counter, no
    number."""
    run = record()
    run["perf_close"]["totals"]["moe"]["experts_read"] = 1000 + read
    value = runner.read_metric(SPECS["latent_decode_step_roofline"],
                               run, [])
    assert run["notes"]["decode_step_roofline"][
        "experts_touched"] == pytest.approx(touched)
    assert value == pytest.approx(
        100 * roofline_latent.decode_step_needs(
            GLM, 1, 201, touched)["bytes"] / 819e9 / (0.58 / 58))
    for at in ("perf_open", "perf_close"):
        del run[at]["totals"]["moe"]
    assert runner.read_metric(SPECS["latent_decode_step_roofline"],
                              run, []) is None


def test_new_readers_on_the_recorded_small_trace():
    """chipbench/testdata/trace_small.json reduced as a run's trace:
    two calls of the decode kernel in 0.5 s over three runs of 1.2 s."""
    with open(os.path.join(mf.HERE, "testdata", "trace_small.json")) as f:
        reduced = xplane.reduce(json.load(f))
    run = record()
    run["trace"] = {**reduced, "started_unix": 1004.2, "held_s": 1.0}
    step = runner.read_metric(SPECS["latent_decode_step_roofline"],
                              run, [])
    call = runner.read_metric(SPECS["latent_attention_kernel_roofline"],
                              run, [])
    # 2 calls / 13 layers of a step took 1.2 s; a call 0.25 s
    assert step == pytest.approx(100 * _LEAST_STEP / (1.2 * 13 / 2))
    assert call == pytest.approx(100 * _LEAST_CALL / 0.25)


def test_manifest_resolves_with_the_new_cell():
    assert mf.problems(MANIFEST, []) == []
    cell = mf.Cell(MANIFEST, CELL, [])
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW) <= set(names)
    # its yardstick cannot read this family's file (ISSUE 35)
    assert "decode_step_roofline" not in names
    assert cell.config["reference"] == "glm4_moe_lite"
    assert (cell.traffic_name, cell.chips) == ("decode-closed", 1)
    assert cell.params["decode_batch_buckets"] == [16]
    for m in MANIFEST["per_layer"]:
        if m["name"] == "decode_step_roofline":
            # the two cells its yardstick can read, as PR 35 listed them
            assert m["workloads"] == ["mistral7b-decode-closed",
                                      "qwen15moe-decode-closed"]
        elif m["name"] in NEW:
            assert m["workloads"] == [CELL]


def test_configuration_holds_the_catalog_numbers():
    """Every published number under its published key; only the depth
    is reduced, and no width is."""
    published = {
        "hidden_size": 2048, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "num_attention_heads": 20,
        "num_key_value_heads": 20, "n_routed_experts": 64,
        "n_shared_experts": 1, "num_experts_per_tok": 4,
        "routed_scaling_factor": 1.8, "first_k_dense_replace": 1,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
        "max_position_embeddings": 202752, "rope_theta": 1000000,
        "num_nextn_predict_layers": 1, "n_group": 1, "topk_group": 1}
    assert {k: GLM[k] for k in published} == published
    assert GLM["reduced"] == ["num_hidden_layers"]
    assert GLM["num_hidden_layers"] == 13
    assert GLM["assumed"]["latent_pool_width"] == 640


def test_roofline_of_a_latent_decode_step_against_the_hand_count():
    """ISSUE 35's count at 15 rows and 5 250 context tokens: twelve
    expert layers of 400.5 MB (attention 21.76, router 0.13, shared
    expert 9.44, 39.1 of 64 experts of 9.44), the dense layer's 84.7,
    the head's 317.2, and the latents (at the padded 640 values:
    5 250 x 13 x 1 280 B = 87.4 MB): 5.30 GB, 6.5 ms at 819 GB/s."""
    touched = 64 * (1 - (63 / 64) ** 60)                   # even routing
    needs = roofline_latent.decode_step_needs(GLM, 15, 5250, touched)
    assert touched == pytest.approx(39.1, abs=0.05)
    attn = 1.573 + 3.932 + 1.180 + 4.588 + 10.486          # M
    assert roofline_latent.attention_weights(GLM) / 1e6 == \
        pytest.approx(attn, abs=0.002)
    by_hand = (12 * (attn + 0.131 + 9.437 + touched * 9.437)
               + attn + 62.915 + 317.194 + 5250 * 13 * 1280 / 1e6)
    assert needs["bytes"] / 1e6 == pytest.approx(by_hand, rel=1e-4)
    assert needs["bytes"] / 1e9 == pytest.approx(5.295, abs=0.005)
    # operations: two a weight a row (4 experts a token) + attention's
    passed = 13 * attn + 62.915 + 317.194 + 12 * (0.131 + 5 * 9.437)
    assert needs["ops"] == pytest.approx(
        2 * 15 * passed * 1e6
        + 13 * 2 * 5250 * 20 * (576 + 512), rel=1e-4)
    least = roofline.least_seconds(needs, "TPU v5 lite")
    assert least["bound"] == "bytes"
    assert least["seconds"] * 1e3 == pytest.approx(6.47, abs=0.01)
    # without the stated padding the cache is read at 576 values
    bare = {**GLM, "assumed": {}}
    assert roofline_latent.latent_width(bare) == 576
    assert (needs["bytes"] - roofline_latent.decode_step_needs(
        bare, 15, 5250, touched)["bytes"]) == 5250 * 13 * 64 * 2


def test_reference_imports_nothing_of_the_program():
    import ast
    with open(glm4_moe_lite.__file__) as f:
        source = f.read()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"functools", "typing", "jax", "jax.numpy"}
    assert 'default_matmul_precision("highest")' in source


# as tests/chipbench/test_chipbench_reference.py: bfloat16 program
# against the float32 reference on the same int8 weights, three tiny
# layers
TINY_TOLERANCE = 0.02


def _tiny():
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "tiny-mla.json")) as f:
        conf = json.load(f)
    from production_stack_tpu.models import llama
    cfg = engine_child.model_config(conf, "tiny-mla")
    params = llama.init_params(cfg, jax.random.PRNGKey(5),
                               quantization="int8")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (9, 40, 60)]
    served = []
    for p in prompts:      # at most 64 tokens: the exact expert path
        logits = llama.forward_train(params, cfg, jnp.asarray([p]))[0, -1]
        lps = jax.nn.log_softmax(logits.astype(jnp.float32))
        top_lp, top_id = jax.lax.top_k(lps, reference.TOP)
        served.append({"prompt_tokens": len(p),
                       "ids": [int(i) for i in top_id],
                       "logprobs": [float(v) for v in top_lp]})
    return conf, params, prompts, served


def test_reference_agrees_with_the_program_at_a_tiny_size():
    conf, params, prompts, served = _tiny()
    rows = glm4_moe_lite.next_token_logprobs(
        params, conf, prompts, [s["ids"] for s in served])
    out = reference.compare(served, rows, tolerance=TINY_TOLERANCE)
    assert out["ok"], out
    assert all(r["shared_top"] >= 18 for r in out["rows"])


@pytest.mark.parametrize("breakage", [
    {"no_selection_bias": True},
    {"num_experts_per_tok": 1}, {"first_k_dense_replace": 0},
    {"rms_norm_eps": 0.01}, {"routed_scaling_factor": 1.0}],
    ids=lambda b: next(iter(b)))
def test_the_probe_tolerance_sees_a_wrong_block(breakage):
    """A reference that departs from the served mathematics in one
    place falls outside the tolerance. (A routing scale of 1.0 for 1.8
    is among them here, where every leaf is drawn at 0.02; the chip's
    cell draws the routed experts' output projection at its file's
    ``assumed.routed_down_init_std`` and its probe does not see that
    one, PERF.md section 6; tests/test_mla.py holds the routed path to
    1e-4 in float32.)"""
    conf, params, prompts, served = _tiny()
    if "no_selection_bias" in breakage:
        layers = dict(params["layers"])
        layers["router_bias"] = jnp.zeros_like(layers["router_bias"])
        params = {**params, "layers": layers}
    if "first_k_dense_replace" in breakage:
        # the dense layer read as one more expert layer: give it the
        # first expert layer's router and experts
        take = {k: v for k, v in params["layers"].items()
                if k not in params["dense_layers"] or k in
                ("gate", "up", "down")}
        first_layer = jax.tree.map(lambda a: a[:1], take)
        layers = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b]),
            {**jax.tree.map(lambda a: a, params["dense_layers"]),
             **first_layer}, params["layers"])
        params = {**params, "layers": layers}
    rows = glm4_moe_lite.next_token_logprobs(
        params, {**conf, **breakage}, prompts,
        [s["ids"] for s in served])
    assert not reference.compare(served, rows,
                                 tolerance=TINY_TOLERANCE)["ok"]


def test_the_references_controls_change_only_what_they_name():
    """tools/mla_chip_check.py's two controls: given ITS OWN top-k
    choices the reference is itself, given others it is not; with its
    activations rounded to a lower precision it moves, by more the
    lower."""
    conf, params, prompts, _ = _tiny()
    tokens = prompts[2]
    T, k = len(tokens), conf["num_experts_per_tok"]
    layers = conf["num_hidden_layers"] - conf["first_k_dense_replace"]
    plain = np.asarray(glm4_moe_lite.logprobs(params, conf, tokens))
    # its own choices, recomputed from its own hidden states
    with jax.default_matmul_precision("highest"):
        own = []
        x = params["embed"]
        x = (x["w8"][jnp.asarray(tokens)].astype(jnp.float32)
             * x["scale"][jnp.asarray(tokens)].astype(jnp.float32)[:, None])
        for i in range(conf["num_hidden_layers"]):
            dense = i < conf["first_k_dense_replace"]
            group = params["dense_layers" if dense else "layers"]
            j = i if dense else i - conf["first_k_dense_replace"]
            if not dense:
                lp = jax.tree.map(lambda a: a[j], group)
                h = x + glm4_moe_lite._attention(
                    conf, lp, glm4_moe_lite._rms(x, lp["attn_norm"],
                                                 conf["rms_norm_eps"]))
                h = glm4_moe_lite._rms(h, lp["mlp_norm"],
                                       conf["rms_norm_eps"])
                sc = jax.nn.sigmoid(h @ lp["router"].astype(jnp.float32))
                own.append(jax.lax.top_k(
                    sc + lp["router_bias"].astype(jnp.float32), k)[1])
            x = glm4_moe_lite._layer(conf, group, j, x, dense)
    own = np.stack([np.asarray(o) for o in own])
    assert own.shape == (layers, T, k)
    same = np.asarray(glm4_moe_lite.logprobs(params, conf, tokens,
                                             chosen=own))
    np.testing.assert_allclose(same, plain, atol=1e-5)
    other = (own + 1) % conf["n_routed_experts"]
    moved = np.asarray(glm4_moe_lite.logprobs(params, conf, tokens,
                                              chosen=other))
    assert np.abs(moved - plain).max() > 10 * TINY_TOLERANCE

    def off(dtype):
        low = np.asarray(glm4_moe_lite.logprobs(
            params, {**conf, "round_to": dtype}, tokens))
        return np.abs(low - plain)[-1].max()
    assert 0 < off("bfloat16") < off("float8_e4m3fn")


def test_rehearsal_of_the_cell_at_a_tiny_file():
    """The new cell's shape on the CPU, end to end through router and
    engine: the latent pool behind the program's normal server entry
    point, the probe against glm4_moe_lite, and the counter metrics in
    a traced line (no device metric from a CPU run)."""
    base = os.path.join(HERE, "rehearsal")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--manifest",
         os.path.join(base, "BENCHMARK.latent.json"), "--data", base,
         "--rehearse", "--workload", "tiny-mla-closed", "--seed",
         str(2**31 + 78), "--seconds", "3", "--trace", "1"],
        cwd=mf.ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["why"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["probe"]["ok"] and len(line["probe"]["rows"]) == 3
    got = line["metrics"]
    # 3 layers x 256 values (144 padded to whole lanes) x 2 bytes
    assert got["kv_bytes_per_token"]["value"] == 3 * 256 * 2
    # off the chip every expert is read (ops/moe.py: the exact path)
    assert 0 < got["moe_read_share"]["value"] <= 100
    assert got["compiles_in_window"]["value"] == 0
    assert not set(got) & {"latent_decode_step_roofline",
                           "latent_attention_kernel_roofline",
                           "decode_step_roofline", "device_idle_share"}
    assert set(line["end_to_end"]) == {"tpot_p50_ms", "out_tokens_per_s",
                                       "setup_s"}


def test_the_rehearsal_manifest_of_the_cell_is_sound():
    base = os.path.join(HERE, "rehearsal")
    with open(os.path.join(base, "BENCHMARK.latent.json")) as f:
        assert mf.problems(json.load(f), [base]) == []
