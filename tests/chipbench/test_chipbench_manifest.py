"""BENCHMARK.json: it loads, every name in it finds its file, names and
units hold only what the contract allows, and it has grown only at its
end: ``manifest_history/pr<n>.json`` holds the names of its four lists
as PR n left them, and each record is a prefix of today's. A PR that
gains a metric, a configuration or a cell ADDS its record there (a new
file; no file that is there is edited) and pins no length anywhere."""

import glob
import json
import os
import re

import pytest

from chipbench import manifest as mf

ROOT = mf.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]
LISTS = ("per_layer", "configs", "workloads", "end_to_end")
RECORDS = []        # oldest first
for _path in glob.glob(os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "manifest_history", "pr*.json")):
    with open(_path) as f:
        RECORDS.append(json.load(f))
    assert os.path.basename(_path) == f"pr{RECORDS[-1]['pr']}.json"
RECORDS.sort(key=lambda r: r["pr"])


def test_manifest_has_no_problem():
    assert mf.problems(MANIFEST, []) == []


def test_command_and_paths():
    assert MANIFEST["command"] == ["python3", "-m", "chipbench"]
    assert "chipbench" in MANIFEST["paths"]
    assert MANIFEST["run_seconds"] == 50    # no later PR may change it
    assert len(json.dumps(MANIFEST)) < 64 * 1024


@pytest.mark.parametrize("record", RECORDS, ids=lambda r: f"pr{r['pr']}")
def test_manifest_grew_only_at_its_end(record):
    """Nothing an accepted PR left was moved, renamed or taken away:
    its record is how today's lists begin. (No count is pinned: the
    next PR that gains only appends, here and in the manifest.)"""
    for key in LISTS:
        today = [m["name"] for m in MANIFEST[key]]
        assert today[:len(record[key])] == record[key], key


def test_the_history_is_a_chain_and_reaches_today():
    """Each record starts with the one before it, and the newest is
    the whole of today's manifest: a PR that gained without adding its
    record fails here."""
    for before, after in zip(RECORDS, RECORDS[1:]):
        for key in LISTS:
            assert after[key][:len(before[key])] == before[key]
    for key in LISTS:
        assert RECORDS[-1][key] == [m["name"] for m in MANIFEST[key]], key


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_reports_every_listless_metric_and_those_that_list_it(cell):
    """What the tests of PR 35, 38, 40 and 42 each asserted of the
    cells they knew, without their counts: a cell's traced line holds
    every metric that lists no workloads and exactly those others that
    list it (so G, L and N have no ``decode_step_roofline``, whose
    yardstick cannot read their families' files), and what the PR that
    brought the cell added with it."""
    names = [m["name"] for m in mf.Cell(MANIFEST, cell, []).per_layer]
    assert len(names) == len(set(names))
    for m in MANIFEST["per_layer"]:
        assert (m["name"] in names) == (cell in m.get("workloads", [cell]))
    for before, after in zip(RECORDS, RECORDS[1:]):
        if cell in after["workloads"] and cell not in before["workloads"]:
            own = set(after["per_layer"]) - set(before["per_layer"])
            assert own <= set(names)


@pytest.mark.parametrize("layer", sorted({m["layer"]
                                          for m in MANIFEST["per_layer"]}))
def test_every_layer_is_named_in_perf_md(layer):
    """``layer`` is the name PERF.md's list of layers has, letter for
    letter (the contract); a metric of a new layer brings its row."""
    with open(os.path.join(ROOT, "PERF.md")) as f:
        assert layer in f.read()


@pytest.mark.parametrize("cell", CELLS)
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
