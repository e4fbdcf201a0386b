"""BENCHMARK.json: it loads, every name in it finds its file, and names
and units hold only what the contract allows."""

import json
import os
import re

import pytest

from chipbench import manifest as mf

ROOT = mf.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_manifest_has_no_problem():
    assert mf.problems(MANIFEST, []) == []


def test_command_and_paths():
    assert MANIFEST["command"] == ["python3", "-m", "chipbench"]
    assert "chipbench" in MANIFEST["paths"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_name_of_a_cell_resolves_to_a_file(cell):
    c = mf.Cell(MANIFEST, cell, [])
    assert c.chips == 1, "no cell takes four chips yet (PERF.md s7)"
    assert os.path.exists(c.config_file)
    assert c.config["name"] == c.config_entry["name"]
    assert c.config["source"] == c.config_entry["source"]
    assert sorted(c.config["reduced"]) == sorted(c.config_entry["reduced"])
    names = {m["name"] for m in c.end_to_end}
    assert names >= {"setup_s", "tpot_p50_ms", "out_tokens_per_s"}

    assert c.per_layer, "every cell reports a per-layer metric"
    if c.traffic["loop"] == "open":
        assert c.params["rate_rps"] > 0
    assert len(c.why) <= 200


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_names_units_and_keys(metric):
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}",
                        metric["name"])
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", metric["unit"])
    assert metric["better"] in ("lower", "higher")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in MANIFEST["end_to_end"]:
        assert set(metric) <= allowed | {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        assert metric["moves"] in {m["name"] for m in
                                   MANIFEST["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"


def test_width_keys_are_never_reduced():
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                       r"_dim$|_rank$|head_dim|num_experts_per_tok")
    for c in MANIFEST["configs"]:
        assert not [k for k in c["reduced"] if width.search(k)]


def test_an_unknown_name_fails_loudly():
    with pytest.raises(mf.ManifestError, match="no workload"):
        mf.Cell(MANIFEST, "no-such-cell", [])
    broken = json.loads(json.dumps(MANIFEST))
    broken["workloads"][0]["traffic"] = "no-such-traffic"
    with pytest.raises(mf.ManifestError, match="traffic/no-such-traffic"):
        mf.Cell(broken, broken["workloads"][0]["name"], [])
    broken = json.loads(json.dumps(MANIFEST))
    broken["end_to_end"][0]["workloads"] = [broken["workloads"][0]["name"]]
    assert any("which it moves, is not" in p
               for p in mf.problems(broken, []))
    broken = json.loads(json.dumps(MANIFEST))
    broken["per_layer"].append({**broken["per_layer"][0],
                                "name": "no_such_metric"})
    assert any("no_such_metric" in p for p in mf.problems(broken, []))


def test_the_rehearsal_manifest_is_sound_too():
    base = os.path.join(ROOT, "tests", "chipbench", "rehearsal")
    with open(os.path.join(base, "BENCHMARK.json")) as f:
        assert mf.problems(json.load(f), [base]) == []
