"""What PR 52 added to the benchmark: ten per-layer metrics that read a
start and its builds from ``GET /debug/perf`` at the window's open
(``startup`` and ``totals.builds``; docs/observability.md "Start-up and
builds"), every one on the reader ``perf_value`` and moving ``setup_s``.
The manifest's entries against the metric files, the reader on a
hand-made record and on a record of a program without the block (the
parent commit), and the CPU rehearsal of a cell whose line holds all
ten (``rehearsal/BENCHMARK.startup.json``).

Its EXPECTED joins ``test_chipbench_readers.EXPECTED`` at import, as
test_chipbench_yoco's does. Where PR 52's entries stand in the manifest is
``manifest_history/pr52.json``'s (test_chipbench_manifest). They list
the seven cells the manifest then had: test_chipbench_yoco.py holds
its rehearsal, under a manifest of its own that no PR may edit, to
every listless counter of BENCHMARK.json.
"""

import json
import os
import subprocess
import sys

import pytest
import test_chipbench_readers as first

from chipbench import manifest as mf
from chipbench import run as runner

HISTORY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "manifest_history")
with open(os.path.join(mf.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(os.path.join(HISTORY, "pr50.json")) as f:
    BEFORE = json.load(f)
with open(os.path.join(HISTORY, "pr52.json")) as f:
    RECORD = json.load(f)
NEW = RECORD["per_layer"][len(BEFORE["per_layer"]):]
SPECS = {n: mf.load(os.path.join(mf.HERE, "metrics", n + ".json"))
         for n in NEW}
# metric -> (path into GET /debug/perf, layer, source)
ISSUE = {
    "startup_main_s": ("startup.marks.main",
                       "server + async engine, scheduler", "program_span"),
    "startup_serving_s": ("startup.marks.serving",
                          "server + async engine, scheduler",
                          "program_span"),
    "startup_weights_s": ("startup.spans.weights_s", "runner",
                          "program_span"),
    "build_trace_s": ("totals.builds.trace_s", "runner", "program_counter"),
    "build_lower_s": ("totals.builds.lower_s", "runner", "program_counter"),
    "build_compile_s": ("totals.builds.backend_miss_s", "runner",
                        "program_counter"),
    "build_cache_load_s": ("totals.builds.backend_hit_s", "runner",
                           "program_counter"),
    "build_cache_hits": ("totals.builds.hits", "runner", "program_counter"),
    "build_after_serving_s": ("startup.after_serving.wall_s", "runner",
                              "program_counter"),
    "build_unattributed_s": ("totals.builds.unattributed.seconds", "runner",
                             "program_counter"),
}


def test_the_record_gained_the_issues_ten_and_nothing_else():
    assert NEW == list(ISSUE)
    for key in ("configs", "workloads", "end_to_end"):
        assert RECORD[key] == BEFORE[key]


@pytest.mark.parametrize("name", ISSUE)
def test_manifest_entry_matches_the_metric_file(name):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    spec = SPECS[name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key]
    path, layer, source = ISSUE[name]
    assert (entry["layer"], entry["source"], entry["moves"]) == (
        layer, source, "setup_s")
    assert entry["unit"] == ("count" if name == "build_cache_hits" else "s")
    assert entry["better"] == ("higher" if name == "build_cache_hits"
                               else "lower")
    # every cell the manifest had reports it
    assert entry["workloads"] == RECORD["workloads"]
    assert set(spec) == {"name", "unit", "better", "source", "layer",
                         "moves", "reader", "args"}
    assert (spec["reader"], spec["args"]) == (
        "perf_value", {"path": path, "at": "open"})
    with open(os.path.join(mf.ROOT, "PERF.md")) as f:
        perf = f.read()
    assert entry["layer"] in perf and f"`{name}`" in perf
    with open(os.path.join(mf.ROOT, "docs", "observability.md")) as f:
        assert f"`{name}`" in f.read()


def record():
    """test_chipbench_readers' synthetic run with the block and the
    totals of a warm start at the window's open, and more built by its
    close (which no metric here reads)."""
    run = first.synthetic()
    builds = {"count": 21, "hits": 20, "misses": 1, "wall_s": 30.0,
              "trace_s": 11.0, "lower_s": 6.0, "backend_miss_s": 2.5,
              "backend_hit_s": 9.0, "cache_load_s": 8.0, "saved_s": 400.0,
              "other_s": 1.5,
              "unattributed": {"events": 90, "seconds": 7.25,
                               "trace_s": 1.0, "lower_s": 2.0,
                               "backend_s": 4.25, "hits": 30, "misses": 2}}
    run["perf_open"]["totals"]["builds"] = builds
    run["perf_open"]["startup"] = {
        "process_start_unix": 940.0, "process_start_source": "proc_stat",
        "marks": {"main": 3.5, "engine_built": 41.0, "serving": 41.25,
                  "first_request": 43.0},
        "spans": {"weights_s": 4.75, "cache_alloc_s": 0.5},
        "before_serving": {**builds, "count": 19, "wall_s": 26.0},
        "after_serving": {"count": 2, "wall_s": 4.0}}
    run["perf_close"]["totals"]["builds"] = {
        **builds, "count": 23, "wall_s": 99.0, "trace_s": 99.0,
        "hits": 99}
    return run


EXPECTED = {"startup_main_s": 3.5, "startup_serving_s": 41.25,
            "startup_weights_s": 4.75, "build_trace_s": 11.0,
            "build_lower_s": 6.0, "build_compile_s": 2.5,
            "build_cache_load_s": 9.0, "build_cache_hits": 20.0,
            "build_after_serving_s": 4.0, "build_unattributed_s": 7.25}
first.EXPECTED.update(EXPECTED)


@pytest.mark.parametrize("name", ISSUE)
def test_reader_finds_its_number_at_the_windows_open(name):
    assert runner.read_metric(SPECS[name], record(), []) == EXPECTED[name]


@pytest.mark.parametrize("name", ISSUE)
def test_reader_reads_nothing_from_a_program_without_the_block(name):
    """The parent commit's ``GET /debug/perf`` has no ``startup`` and
    no ``totals.builds``: None, nothing raised, and the line leaves the
    metric out. A mark not reached yet (null) reads the same."""
    assert runner.read_metric(SPECS[name], first.synthetic(), []) is None
    run = record()
    run["perf_open"]["startup"]["marks"] = dict.fromkeys(
        run["perf_open"]["startup"]["marks"])
    run["perf_open"]["startup"]["after_serving"] = None
    expect = None if name in ("startup_main_s", "startup_serving_s",
                              "build_after_serving_s") else EXPECTED[name]
    assert runner.read_metric(SPECS[name], run, []) == expect


def test_rehearsal_line_holds_all_ten(tmp_path):
    """The dense cell on the CPU, end to end through router and engine
    behind ``server.main``, on an EMPTY compile cache of its own: the
    traced line holds every one of the ten, none null; no build was
    loaded, the seconds of the builds are the back end's compiles, and
    the marks lie in the order a start reaches them, inside
    ``engine_ready_s``. From a tree of links, so that the run keeps its
    ``.chipbench/`` to itself. Some 40 s: an engine and a router
    start, a dozen executables compile."""
    base = os.path.join(mf.ROOT, "tests", "chipbench", "rehearsal")
    for name in ("chipbench", "production_stack_tpu", "tests",
                 "BENCHMARK.json"):
        os.symlink(os.path.join(mf.ROOT, name), tmp_path / name)
    cache = tmp_path / "empty-cache"
    cache.mkdir()
    dump = tmp_path / "run.json"
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--manifest",
         os.path.join(base, "BENCHMARK.startup.json"), "--data", base,
         "--rehearse", "--workload", "tiny-dense-closed", "--seed",
         str(2**31 + 52), "--seconds", "3", "--trace", "1",
         "--dump", str(dump)],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["why"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(ISSUE) <= set(got)
    assert all(isinstance(got[n], float) for n in ISSUE)
    assert got["build_cache_hits"] == 0
    assert got["build_cache_load_s"] == 0
    assert got["build_compile_s"] > 0 and got["build_trace_s"] > 0
    assert got["build_lower_s"] > 0 and got["startup_weights_s"] > 0
    assert got["build_unattributed_s"] > 0      # the weights' init jits
    assert 0 < got["startup_main_s"] < got["startup_serving_s"]
    ready = line["notes"]["engine_ready_s"]
    assert ready - 15 < got["startup_serving_s"] <= ready
    assert got["startup_serving_s"] < line["end_to_end"]["setup_s"]["value"]
    with open(dump) as f:
        perf = json.load(f)["perf_open"]
    builds, start = perf["totals"]["builds"], perf["startup"]
    assert got["warmup_executables"] == builds["count"]
    assert builds["hits"] == 0 and builds["misses"] == builds["count"]
    assert got["build_after_serving_s"] <= builds["wall_s"]
    assert start["process_start_source"] == "proc_stat"
    assert start["before_serving"]["count"] \
        + start["after_serving"]["count"] == builds["count"]
    assert builds["wall_s"] == pytest.approx(
        builds["trace_s"] + builds["lower_s"] + builds["backend_miss_s"]
        + builds["backend_hit_s"] + builds["other_s"], abs=1e-5)
    for row in perf["totals"]["compiles"].values():
        assert row["seconds"] == pytest.approx(
            row["trace_s"] + row["lower_s"] + row["backend_s"]
            + row["other_s"], rel=0.01, abs=2e-4)
