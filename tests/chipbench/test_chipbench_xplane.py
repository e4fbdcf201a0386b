"""The trace reduction on known intervals (chipbench/testdata)."""

import json
import os

import pytest

from chipbench import xplane

with open(os.path.join(os.path.dirname(xplane.__file__), "testdata",
                       "trace_small.json")) as f:
    SMALL = json.load(f)


def test_union_merges_overlapping_and_touching_intervals():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]


def test_busy_is_the_union_of_operations_not_their_sum():
    out = xplane.reduce(SMALL)
    # ops: [1.0,1.4] [1.2,1.6] (overlap) [2.0,2.5] [2.5,2.6] [2.9,2.95]
    assert out["busy_s"] == pytest.approx(0.6 + 0.6 + 0.05)
    # first device operation to last: what the profiler costs at the
    # trace's edges (host events from 0.0 to 3.01) is left out
    assert out["window_s"] == pytest.approx(1.95)
    assert out["device_planes"] == 1


def test_time_per_executable_and_top_ops():
    out = xplane.reduce(SMALL)
    dec = out["modules"]["jit__unknown_123_"]
    assert dec["runs"] == 3
    assert dec["total_s"] == pytest.approx(0.6 + 0.5 + 0.1)
    assert dec["median_s"] == pytest.approx(0.5)
    assert out["modules"]["jit__unknown_7_"]["runs"] == 1
    assert [n for n, _ in out["top_ops"]] == [
        "fusion.1_bf16_16_4096_",
        "paged_decode_attention.12_bf16_16_8_4_128_",
        "paged_attention.3_bf16_16_256_8_4_128_"]
    assert sum(s for _, s in out["top_ops"]) == pytest.approx(1.25)


def test_executables_are_told_apart_by_the_kernel_they_run():
    mods = xplane.reduce(SMALL)["modules"]
    assert mods["jit__unknown_123_"]["ops"] == {
        "fusion": [2, pytest.approx(0.9)],
        "paged_decode_attention": [2, pytest.approx(0.5)]}
    assert mods["jit__unknown_7_"]["ops"] == {
        "paged_attention": [1, pytest.approx(0.05)]}
    assert xplane.op_base("%copy.68.remat = bf16[4]{0} copy(x)") == \
        "copy.68.remat"
    assert xplane.op_base("%fusion.180 = bf16[16,1]{1,0} fusion(x)") == \
        "fusion"


def test_nested_operations_are_counted_once_under_the_innermost():
    events = [["%while.4 = (s32[], bf16[2,3]{1,0}) while(...)", 0.0, 10.0],
              ["%fusion.1 = bf16[16,4096]{1,0:T(8,128)} fusion(...)", 1.0, 3.0],
              ["%copy.2 = bf16[8]{0} copy(...)", 4.0, 2.0],
              ["%fusion.1 = bf16[16,4096]{1,0:T(8,128)} fusion(...)", 7.0, 1.0],
              ["after", 12.0, 1.0]]
    assert xplane.self_seconds(events) == {
        "while.4_s32_": pytest.approx(4.0),
        "fusion.1_bf16_16_4096_": pytest.approx(4.0),
        "copy.2_bf16_8_": pytest.approx(2.0), "after": pytest.approx(1.0)}


def test_names_keep_only_what_a_ledger_line_keeps():
    assert xplane.clean(
        "%copy.109 = bf16[32,385,8,64,128]{4,2,3,1,0:T(8,128)(2,1)} "
        "copy(bf16[32,385,8,64,128]{4,3,2,1,0} %x)") == \
        "copy.109_bf16_32_385_8_64_128_"
    assert xplane.clean("PjitFunction(_decode_impl)") == \
        "PjitFunction__decode_impl_"
    assert xplane.clean("$queues.py:175 get_nowait") == \
        "_queues.py_175_get_nowait"


def test_gaps_go_to_the_innermost_host_event_that_covers_them():
    gaps = dict(xplane.reduce(SMALL)["idle_gaps"])
    # idle between device operations: [1.6,2.0] under step() and the
    # shorter PjitFunction(_decode_impl); [2.6,2.9] under nothing
    assert gaps == {"PjitFunction__decode_impl_": pytest.approx(0.4),
                    "unattributed": pytest.approx(0.3)}


def test_small_gaps_are_one_row():
    events = {"devices": {"/device:TPU:0": {
        "ops": [["a", 0.0, 1.0], ["b", 1.00001, 1.0]], "modules": []}},
        "host": []}
    gaps = dict(xplane.reduce(events)["idle_gaps"])
    assert gaps == {"gaps_under_50us": pytest.approx(1e-5)}


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(ValueError, match="no operation ran"):
        xplane.reduce({"devices": {}, "host": [["x", 0.0, 1.0]]})
    with pytest.raises(ValueError, match="no operation ran"):
        xplane.reduce({"devices": {"/device:TPU:0": {
            "ops": [], "modules": []}}, "host": []})


def test_busy_is_averaged_over_the_chips():
    two = json.loads(json.dumps(SMALL))
    two["devices"]["/device:TPU:1"] = {"ops": [["x", 1.0, 0.2]],
                                       "modules": []}
    out = xplane.reduce(two)
    assert out["device_planes"] == 2
    assert out["busy_s"] == pytest.approx((1.25 + 0.2) / 2)


def test_a_recorded_profile_loads(tmp_path):
    """The file reader on a profile recorded here (the CPU has no device
    plane: its events are the host's)."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    events = xplane.load_events(xplane.find_xplane(str(tmp_path)))
    assert events["devices"] == {} and events["host"]
    name, start, dur = events["host"][0]
    assert isinstance(name, str) and dur >= 0 and start >= 0
