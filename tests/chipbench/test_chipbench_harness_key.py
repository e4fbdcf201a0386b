"""What the harness is told of a model's executables (the optional
``harness`` key of a configuration's file, chipbench/harness_key.py),
and the two places that ask it: ``correct``'s kernel clause
(``kernels_off``, ``run.verdict``) and the two step metrics
(``readers/trace_module.py``). All on hand-written records: no engine,
no trace, no chip."""

import copy
import json
import os

import pytest

from chipbench import harness_key
from chipbench import manifest as mf
from chipbench import run as runner

CONFIGS = os.path.join(mf.HERE, "configs")
DEFAULT_OPS = ("paged_decode_attention", "paged_attention")


def spec(name):
    return mf.load(os.path.join(mf.HERE, "metrics", name + ".json"))


# ---------------------------------------------------------------------
# the key
# ---------------------------------------------------------------------

@pytest.mark.parametrize("file, tables, calls", [
    ("mistral-7b-int8", ["attention_paths"], 32),
    ("qwen15-moe-a2.7b-int8-l12", ["attention_paths"], 12),
    ("glm-4.7-flash-int8-l13", ["attention_paths"], 13),
    ("glm-5-int8-l7-e16", ["attention_paths"], 7),
    ("qwen3-next-80b-a3b-int8-l24-e64",
     ["attention_paths", "mixer_paths"], 6)])
def test_the_five_files_read_through_the_one_function(file, tables, calls):
    """M, Q, G and L state no key and get the defaults (the attention
    kernel once a layer); N's file says 6 of its 24 layers call it and
    that its mixers must be kernels too."""
    path = os.path.join(CONFIGS, file + ".json")
    key = harness_key.read(path)
    assert key["kernel_tables"] == tables
    assert key["decode_step"] == {"op": DEFAULT_OPS[0],
                                  "calls_per_step": calls}
    assert key["prefill_dispatch"] == {"op": DEFAULT_OPS[1]}
    assert ("harness" in mf.load(path)) == (calls == 6)
    # the probe's limits: the default's widest gap a prompt, but N's
    # mean gap a run (its readings: PERF.md section 2)
    assert key["probe"] == (
        {"logprob_gap_limit": None, "mean_logprob_gap_limit": 0.3}
        if calls == 6 else
        {"logprob_gap_limit": 0.3, "mean_logprob_gap_limit": None})


def test_a_part_that_is_absent_takes_its_default():
    hf = {"name": "x", "num_hidden_layers": 40,
          "harness": {"kernel_tables": ["mixer_paths"],
                      "decode_step": {"op": "retention_step"}}}
    assert harness_key.of(hf) == {
        "kernel_tables": ["mixer_paths"],
        "decode_step": {"op": "retention_step", "calls_per_step": 40},
        "prefill_dispatch": {"op": DEFAULT_OPS[1]},
        "probe": {"logprob_gap_limit": 0.3,
                  "mean_logprob_gap_limit": None}}
    assert hf["harness"]["decode_step"] == {"op": "retention_step"}


@pytest.mark.parametrize("key, word", [
    ({"kernel_table": ["attention_paths"]}, "kernel_table"),
    ({"decode_step": {"kernel": "x"}}, "decode_step.kernel"),
    ({"kernel_tables": []}, "names no table"),
    ({"kernel_tables": ["attention"]}, "names no table"),
    ({"probe": {"tolerance": 0.5}}, "probe.tolerance"),
    ({"probe": {"logprob_gap_limit": 0}}, "is no limit"),
    ({"probe": {"logprob_gap_limit": "0.5"}}, "is no limit"),
    ({"probe": {"mean_logprob_gap_limit": True}}, "is no limit"),
    ({"probe": {"logprob_gap_limit": None}}, "is no limit")])
def test_a_key_it_does_not_know_is_an_error_not_a_default(key, word):
    with pytest.raises(ValueError, match=word):
        harness_key.of({"name": "x", "num_hidden_layers": 2,
                        "harness": key})


def test_the_manifest_check_names_a_file_with_a_bad_key(tmp_path):
    """``manifest.problems`` (what a builder runs before the chip)
    reads every configuration's key."""
    with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    bad = mf.load(os.path.join(CONFIGS, "mistral-7b-int8.json"))
    bad["harness"] = {"decode_step": {"calls": 3}}
    (tmp_path / "chipbench" / "configs").mkdir(parents=True)
    for c in manifest["configs"]:
        with open(tmp_path / c["file"], "w") as f:
            json.dump(bad if c["name"] == "mistral-7b-int8"
                      else mf.load(os.path.join(mf.ROOT, c["file"])), f)
    (problem,) = mf.problems(manifest, [], root=str(tmp_path))
    assert "decode_step.calls" in problem and "mistral-7b-int8" in problem


# ---------------------------------------------------------------------
# correct's kernel clause
# ---------------------------------------------------------------------

DECODE, PREFILL = "decode|8|512|16", "prefill|1|512|1"
# the ``device`` block of GET /debug/perf as each cell's program fills
# it (the path names of my chip runs' engine logs; M has no other table)
SHAPES = {
    "M": {"attention_paths": {DECODE: "pallas_paged_decode",
                              PREFILL: "pallas_paged"},
          "moe_paths": {}, "mixer_paths": {}},
    "Q": {"attention_paths": {DECODE: "pallas_paged_decode",
                              PREFILL: "pallas_paged"},
          "moe_paths": {DECODE: "list", PREFILL: "grouped"},
          "mixer_paths": {}},
    "G": {"attention_paths": {DECODE: "pallas_paged_decode_latent",
                              PREFILL: "pallas_paged_latent"},
          "moe_paths": {DECODE: "list", PREFILL: "grouped"},
          "mixer_paths": {}},
    "L": {"attention_paths": {
              DECODE: "pallas_paged_decode_latent_sparse",
              PREFILL: "pallas_paged_latent_expanded_sparse"},
          "moe_paths": {DECODE: "list_tiled2", PREFILL: "grouped_tiled2"},
          "mixer_paths": {}},
    "N": {"attention_paths": {DECODE: "pallas_paged_decode",
                              PREFILL: "pallas_paged"},
          "moe_paths": {DECODE: "list", PREFILL: "grouped"},
          "mixer_paths": {DECODE: "gdn_recurrent", PREFILL: "gdn_chunk"}},
}
DEFAULT = harness_key.of({"num_hidden_layers": 32})
BOTH = harness_key.of({"num_hidden_layers": 24, "harness": {
    "kernel_tables": ["attention_paths", "mixer_paths"]}})
MIXERS = harness_key.of({"num_hidden_layers": 40, "harness": {
    "kernel_tables": ["mixer_paths"]}})
# a model whose every mixer keeps a state and none a K/V pool
STATE_ONLY = {"attention_paths": {}, "moe_paths": {},
              "mixer_paths": {DECODE: "retention_recurrent",
                              PREFILL: "retention_chunk"}}


def device(shape, **changes):
    out = copy.deepcopy(shape)
    out.update(platform="tpu", device_kind="TPU v5 lite", count=1,
               pallas_attention="auto", engine_devices=[])
    for table, rows in changes.items():
        out[table] = {**out.get(table, {}), **rows}
    return out


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_todays_five_shapes_are_on_their_kernels(cell):
    harness = BOTH if cell == "N" else DEFAULT
    assert harness_key.kernels_off(device(SHAPES[cell]), harness) == {}


@pytest.mark.parametrize("block, harness, off", [
    # no executable at all: a failure, as before
    (device({"attention_paths": {}}), DEFAULT, {"executables"}),
    (device({}), DEFAULT, {"executables"}),
    # the fallback of ops/pallas_paged.attention_path
    (device(SHAPES["M"], attention_paths={PREFILL: "jnp_gather"}),
     DEFAULT, {f"attention_paths[{PREFILL}]"}),
    # an attention entry that is no jnp_* but no paged kernel either
    (device(SHAPES["M"], attention_paths={DECODE: "flash_dense"}),
     DEFAULT, {f"attention_paths[{DECODE}]"}),
    # N: a mixer on the jax.numpy form of the rule
    (device(SHAPES["N"], mixer_paths={PREFILL: "gdn_chunk_jnp"}), BOTH,
     {f"mixer_paths[{PREFILL}]"}),
    # N: an executable the mixer table does not name
    (device({**SHAPES["N"], "mixer_paths": {DECODE: "gdn_recurrent"}}),
     BOTH, {f"mixer_paths[{PREFILL}]"}),
    # an executable only the experts' table knows is still an
    # executable: the attention table must name it
    (device(SHAPES["Q"], moe_paths={"spec|4|512|16": "list"}), DEFAULT,
     {"attention_paths[spec|4|512|16]"}),
    # no attention layer, under a file WITHOUT the key: the default
    # holds it to a table that names nothing
    (device(STATE_ONLY), DEFAULT,
     {f"attention_paths[{DECODE}]", f"attention_paths[{PREFILL}]"}),
    # ... and under its own key, with a fallback in it
    (device(STATE_ONLY, mixer_paths={DECODE: "retention_recurrent_jnp"}),
     MIXERS, {f"mixer_paths[{DECODE}]"}),
])
def test_what_is_off_its_kernel_is_named(block, harness, off):
    found = harness_key.kernels_off(block, harness)
    assert set(found) == off
    for key, path in found.items():
        if key != "executables":
            table, name = key[:-1].split("[")
            assert path == block.get(table, {}).get(name, "not named")


def test_a_model_with_no_attention_layer_passes_under_its_own_key():
    assert harness_key.kernels_off(device(STATE_ONLY), MIXERS) == {}
    # N's mixers held alone: nothing asks for its attention then
    assert harness_key.kernels_off(
        device(SHAPES["N"], attention_paths={DECODE: "jnp_gather"}),
        MIXERS) == {}


def hand_run(config, block, rehearsal=False):
    """The least of a run's record that ``verdict`` reads: one request
    that completed, a probe that passed, counts that agree."""
    rec = {"index": 0, "sent": 1.0, "ended": True, "done": True,
           "cut": False, "status": 200, "error": None, "trace_id": "t",
           "token_times": [1.1, 1.2], "max_tokens": 2, "prompt_tokens": 5}
    trace = {"trace_id": "t", "status": "ok",
             "attrs": {"output_tokens": 2, "prompt_tokens": 5}}
    return {"records": [rec], "rehearsal": rehearsal,
            "config_file": os.path.join(CONFIGS, config + ".json"),
            "probe": {"ok": True, "tolerance": 0.3, "rows": [
                {"prompt_tokens": 57, "max_abs_logprob_diff": 0.12,
                 "shared_top": 17, "ok": True}]},
            "perf_close": {"device": block},
            "engine_traces": {"traces": [trace], "ring_entries": 9},
            "router_traces": {"traces": [trace], "ring_entries": 9},
            "moved": {"generation_tokens": 2, "router_requests": 1}}


@pytest.mark.parametrize("config, block, correct", [
    ("mistral-7b-int8", device(SHAPES["M"]), True),
    ("qwen3-next-80b-a3b-int8-l24-e64", device(SHAPES["N"]), True),
    # N is now also held to its mixers running as kernels
    ("qwen3-next-80b-a3b-int8-l24-e64",
     device(SHAPES["N"], mixer_paths={DECODE: "gdn_recurrent_jnp"}),
     False),
    ("mistral-7b-int8",
     device(SHAPES["M"], attention_paths={DECODE: "jnp_gather"}), False),
    ("mistral-7b-int8", device({"attention_paths": {}}), False)])
def test_verdict_holds_a_run_to_its_files_tables(config, block, correct):
    out = runner.verdict(hand_run(config, block))
    assert out["correct"] is correct
    assert (out["compared"]["executables_off_kernels"][0] == 0) is correct
    assert out["compared"]["executables_off_kernels"][1] == 0
    if not correct:
        (why,) = out["why"]
        assert "table[executable]" in why
        assert "gdn_recurrent_jnp" in why or "jnp_gather" in why \
            or "names none" in why
    # a rehearsal on the CPU runs no kernel and is not held to one
    cpu = runner.verdict(hand_run(config, block, rehearsal=True))
    assert cpu["correct"] and "executables_off_kernels" not in \
        cpu["compared"]


def test_every_number_compared_stands_beside_its_limit():
    out = runner.verdict(hand_run("mistral-7b-int8", device(SHAPES["M"])))
    assert out["compared"] == {
        "requests_failed": [0, 0], "probe0_logprob_gap": [0.12, 0.3],
        "probe0_shared_top": [17, 10], "executables_off_kernels": [0, 0],
        "counts_unreconciled": [0, 0]}
    run = hand_run("mistral-7b-int8", device(SHAPES["M"]))
    run["moved"]["generation_tokens"] = 1       # the engine counted less
    run["records"][0].update(done=False, error="stream ended")
    out = runner.verdict(run)
    assert not out["correct"] and out["failed"] == 1
    assert out["compared"]["requests_failed"] == [1, 0]
    assert out["compared"]["counts_unreconciled"] == [1, 0]


def test_of_the_probes_gaps_those_with_a_limit_are_compared():
    """A configuration held to the run's mean gap (N's file) shows that
    number beside its limit and not the widest gap a prompt, which has
    none; over the limit the run is not correct and the why says by how
    much."""
    run = hand_run("qwen3-next-80b-a3b-int8-l24-e64", device(SHAPES["N"]))
    run["probe"].update(tolerance=None, mean_limit=0.3,
                        mean_abs_logprob_diff=0.11)
    out = runner.verdict(run)
    assert out["correct"], out["why"]
    assert out["compared"]["probe_mean_logprob_gap"] == [0.11, 0.3]
    assert "probe0_logprob_gap" not in out["compared"]
    assert out["compared"]["probe0_shared_top"] == [17, 10]
    run["probe"].update(ok=False, mean_abs_logprob_diff=0.62)
    out = runner.verdict(run)
    assert not out["correct"] and "mean gap 0.62 of 0.3" in out["why"][0]


# ---------------------------------------------------------------------
# the two step metrics
# ---------------------------------------------------------------------

def traced(config_file, decode_op, prefill_op, calls):
    """A reduced trace of 10 decode steps in two executables (4 + 6
    steps, ``calls`` calls of ``decode_op`` a step, 0.05 s a step) and
    two prefill executables (3 runs with a median of 0.2 s, 1 run of
    0.9 s) that run ``prefill_op``; an executable that runs neither."""
    return {"config_file": str(config_file), "trace": {"modules": {
        "jit_a": {"runs": 2, "total_s": 0.2, "median_s": 0.1,
                  "ops": {decode_op: [4 * calls, 0.01], "fusion": [9, 0.1]}},
        "jit_b": {"runs": 3, "total_s": 0.3, "median_s": 0.1,
                  "ops": {decode_op: [6 * calls, 0.02]}},
        "jit_c": {"runs": 3, "total_s": 0.7, "median_s": 0.2,
                  "ops": {prefill_op: [3 * calls, 0.3]}},
        "jit_d": {"runs": 1, "total_s": 0.9, "median_s": 0.9,
                  "ops": {prefill_op: [calls, 0.4]}},
        "jit_e": {"runs": 50, "total_s": 0.1, "median_s": 0.002,
                  "ops": {"copy": [50, 0.1]}}}}}


def test_a_model_without_attention_names_its_own_two_operations(tmp_path):
    """The operation is not attention and the calls a step come from
    the file: 40 layers, 40 calls of the recurrent step a decode
    step."""
    path = tmp_path / "state-only.json"
    path.write_text(json.dumps({
        "name": "state-only", "num_hidden_layers": 40, "harness": {
            "kernel_tables": ["mixer_paths"],
            "decode_step": {"op": "retention_step"},
            "prefill_dispatch": {"op": "retention_chunk_scan"}}}))
    run = traced(path, "retention_step", "retention_chunk_scan", 40)
    assert runner.read_metric(spec("decode_step_device_ms"), run, []) \
        == pytest.approx(1e3 * 0.5 / 10)
    assert runner.read_metric(spec("prefill_dispatch_device_ms"), run,
                              []) == pytest.approx(200.0)
    # the same trace under a file without the key: the defaults find
    # neither operation, and a reader with nothing to read says so
    other = traced(os.path.join(CONFIGS, "mistral-7b-int8.json"),
                   "retention_step", "retention_chunk_scan", 40)
    assert runner.read_metric(spec("decode_step_device_ms"), other,
                              []) is None
    assert runner.read_metric(spec("prefill_dispatch_device_ms"), other,
                              []) is None


@pytest.mark.parametrize("file, calls", [
    ("mistral-7b-int8", 32), ("qwen3-next-80b-a3b-int8-l24-e64", 6)])
def test_a_step_is_the_files_calls_of_the_operation(file, calls):
    """M: once a layer. N: 6 calls a step; divided by its 24 layers the
    same trace read four steps as one (0.2 s) until PR 46."""
    run = traced(os.path.join(CONFIGS, file + ".json"), *DEFAULT_OPS, calls)
    assert runner.read_metric(spec("decode_step_device_ms"), run, []) \
        == pytest.approx(50.0)
    assert runner.read_metric(spec("prefill_dispatch_device_ms"), run,
                              []) == pytest.approx(200.0)


def test_the_metric_files_name_a_kind_and_no_operation():
    assert spec("decode_step_device_ms")["args"] == {
        "kind": "decode_step", "per": "step"}
    assert spec("prefill_dispatch_device_ms")["args"] == {
        "kind": "prefill_dispatch", "per": "dispatch"}
    # the defaults of chipbench/harness_key.py are the one place where
    # run.py and readers/trace_module.py name an operation or a table
    for path in (os.path.join(mf.HERE, "run.py"),
                 os.path.join(mf.HERE, "readers", "trace_module.py")):
        with open(path) as f:
            code = f.read()
        for word in (*DEFAULT_OPS, "attention_paths", "mixer_paths",
                     "pallas_paged", "_jnp"):
            assert word not in code, (path, word)


def test_the_other_readers_still_name_an_operation():
    """``modules_with`` and ``module_ms`` take an operation's name, as
    the rooflines' readers give it from their metric files; a step is
    then the configuration's calls unless the caller says."""
    import sys
    sys.path.insert(0, os.path.join(mf.HERE, "readers"))
    try:
        import trace_module
    finally:
        sys.path.pop(0)
    run = traced(os.path.join(CONFIGS, "qwen3-next-80b-a3b-int8-l24-e64"
                              ".json"), *DEFAULT_OPS, 6)
    assert len(trace_module.modules_with(run, DEFAULT_OPS[0])) == 2
    assert trace_module.modules_with(run, "no_such_op") == []
    assert trace_module.module_ms(run, DEFAULT_OPS[0], per="step") \
        == pytest.approx(50.0)
    assert trace_module.module_ms(run, DEFAULT_OPS[0], per="step",
                                  calls_per_step=24) \
        == pytest.approx(200.0)
    assert trace_module.module_ms(run, "no_such_op") is None
    assert trace_module.read({"config_file": run["config_file"],
                              "trace": None}, "decode_step", "step") is None
