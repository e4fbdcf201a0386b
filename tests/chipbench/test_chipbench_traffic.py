"""The traffic generator: every seed offers the same multiset of work."""

import glob
import json
import os
from collections import Counter

import pytest

from chipbench import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OPEN = os.path.join(ROOT, "tests", "chipbench", "rehearsal", "traffic",
                    "open-tiny.json")
MIXES = sorted(glob.glob(os.path.join(ROOT, "chipbench", "traffic",
                                      "*.json"))) + [OPEN]
SEEDS = [0, 1, 7, 2**31 - 2, 2147483700 % 0x7FFFFFFF]


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
@pytest.mark.parametrize("seed", SEEDS[1:])
def test_every_seed_offers_the_same_multiset(path, seed):
    mix = load(path)
    a = traffic.make_plan(mix, SEEDS[0], 50, rate_rps=0.7)
    b = traffic.make_plan(mix, seed, 50, rate_rps=0.7)
    assert Counter(a.prompts) == Counter(b.prompts)
    assert Counter(a.outputs) == Counter(b.outputs)
    assert Counter(round(g, 9) for g in a.gaps) == Counter(
        round(g, 9) for g in b.gaps)
    assert (a.prompts, a.outputs) != (b.prompts, b.outputs), \
        "the seed must change the order"
    assert [r.output_tokens for r in a.lead_in] == [
        r.output_tokens for r in b.lead_in]


def test_quantile_grid_of_a_uniform_distribution():
    grid = traffic.quantile_grid({"knots": [[0, 100], [1, 200]]}, 4)
    assert grid == [112, 138, 162, 188]     # quantiles 1/8, 3/8, 5/8, 7/8


def test_quantile_grid_log_interpolation_hits_the_knots_shape():
    spec = {"knots": [[0, 64], [0.5, 256], [0.9, 1024], [1, 1536]],
            "interp": "log"}
    grid = traffic.quantile_grid(spec, 200)
    assert grid == sorted(grid) and 64 <= grid[0] and grid[-1] <= 1536
    assert abs(grid[100] - 256) <= 4                    # the median
    assert 0.08 <= sum(1 for v in grid if v > 1024) / 200 <= 0.12


@pytest.mark.parametrize("bad", [[[0, 5], [0.5, 3], [1, 9]],
                                 [[0.1, 1], [1, 2]], [[0, 1], [0.9, 2]]])
def test_quantile_grid_refuses_knots_that_do_not_ascend(bad):
    with pytest.raises(ValueError):
        traffic.quantile_grid({"knots": bad}, 4)


def test_open_loop_gaps_are_exponential_and_fill_the_window_exactly():
    gaps = traffic.exponential_gaps(40, 50.0)
    assert abs(sum(gaps) - 50.0) < 1e-9 and len(gaps) == 40
    mean = 50.0 / 40
    # an exponential's median is ln 2 of its mean
    assert abs(sorted(gaps)[20] / mean - 0.693) < 0.05


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("phase", [0.01, 3.3, 17.9, 49.0])
def test_any_window_of_one_cycle_holds_every_request_once(seed, phase):
    mix = load(OPEN)
    seconds, rate = 50.0, 0.8
    plan = traffic.make_plan(mix, seed, seconds, rate_rps=rate)
    n = len(plan.prompts)
    assert n == round(rate * seconds)
    inside = []
    for req in plan.stream():
        if req.due_s >= phase + seconds:
            break
        if req.due_s >= phase:
            inside.append(req)
    assert len(inside) == n
    assert Counter(r.prompt_tokens for r in inside) == Counter(plan.prompts)
    assert Counter(r.output_tokens for r in inside) == Counter(plan.outputs)


def test_open_loop_needs_a_rate():
    mix = load(OPEN)
    with pytest.raises(ValueError, match="rate_rps"):
        traffic.make_plan(mix, 1, 50)


def test_lead_in_staggers_first_completions_evenly():
    """Client i's lead-in runs (i+1)/N of the mean output: at a fixed
    time per token the first completions, and with them the first
    prefills, are evenly spaced over one cycle."""
    mix = load(os.path.join(ROOT, "chipbench", "traffic",
                            "decode-closed.json"))
    plan = traffic.make_plan(mix, 3, 50)
    outs = [r.output_tokens for r in plan.lead_in]
    assert len(outs) == plan.clients == 16
    mean = sum(plan.outputs) / len(plan.outputs)
    assert mean == 256
    assert outs == [16 * (i + 1) for i in range(16)]    # 2 windows apart
    assert all(r.lead_in for r in plan.lead_in)


def test_prompt_ids_are_bytes_drawn_from_seed_and_place():
    mix = load(os.path.join(ROOT, "chipbench", "traffic",
                            "decode-closed.json"))
    plan = traffic.make_plan(mix, 11, 50)
    stream = plan.stream()
    first, second = next(stream), next(stream)
    ids = plan.prompt_ids(first)
    assert len(ids) == first.prompt_tokens
    assert all(0 <= t < 256 for t in ids)
    assert ids == traffic.make_plan(mix, 11, 50).prompt_ids(first)
    assert ids[:8] != plan.prompt_ids(second)[:8]
    assert ids[:8] != traffic.make_plan(mix, 12, 50).prompt_ids(first)[:8]
    assert plan.prompt_ids(plan.lead_in[0])[:8] != ids[:8]
