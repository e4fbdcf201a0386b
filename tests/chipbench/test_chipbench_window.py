"""Window accounting on synthetic chunk times."""

import pytest

from chipbench import window
from chipbench.stats import median, percentile, spread


def rec(due, sent, times, done=True, **kw):
    return {"due": due, "sent": sent, "token_times": times, "done": done,
            "max_tokens": len(times), "prompt_tokens": 8, **kw}


def test_tokens_are_counted_by_arrival_whichever_request():
    spanning = rec(0.0, 0.0, [9.0, 9.5, 10.0, 10.5, 19.9, 20.0, 21.0])
    assert window.out_tokens([spanning], 10.0, 20.0) == 3   # 10, 10.5, 19.9
    inside = rec(11.0, 11.0, [12.0, 13.0])
    assert window.out_tokens([spanning, inside], 10.0, 20.0) == 5


def test_a_token_on_the_edge_belongs_to_the_window_it_opens():
    r = rec(0.0, 0.0, [10.0, 20.0])
    assert window.out_tokens([r], 10.0, 20.0) == 1      # [t0, t1)
    assert window.out_tokens([r], 20.0, 30.0) == 1


def test_ttft_is_from_the_due_time_not_the_send_time():
    late = rec(due=10.0, sent=10.4, times=[11.0, 11.1])
    assert window.ttft_ms([late], 10.0, 20.0) == [pytest.approx(1000.0)]
    assert window.lag_ms([late], 10.0, 20.0) == [pytest.approx(400.0)]


def test_ttft_belongs_to_the_window_by_its_first_token():
    before = rec(due=1.0, sent=1.0, times=[9.9, 12.0])
    inside = rec(due=8.0, sent=8.0, times=[10.5, 12.0])
    assert window.ttft_ms([before, inside], 10.0, 20.0) == [
        pytest.approx(2500.0)]


def test_tpot_of_requests_that_finished_in_the_window():
    done_in = rec(0.0, 0.0, [8.0, 9.0, 10.0, 11.0, 12.0])      # 4 gaps, 4 s
    done_after = rec(0.0, 0.0, [15.0, 25.0])
    cut = rec(0.0, 0.0, [11.0, 12.0, 13.0], done=False)
    assert window.tpot_ms([done_in, done_after, cut], 10.0, 20.0) == [
        pytest.approx(1000.0)]


def test_itl_gives_a_burst_one_gap_and_the_rest_zero():
    burst = rec(0.0, 0.0, [10.0, 10.0, 10.0, 10.4, 10.4, 10.4])
    assert window.itl_ms([burst], 10.0, 20.0) == pytest.approx(
        [0.0, 0.0, 400.0, 0.0, 0.0])
    # the first token of a request has no gap; a gap belongs to the
    # window its later token arrived in
    crossing = rec(0.0, 0.0, [9.0, 10.5])
    assert window.itl_ms([crossing], 10.0, 20.0) == [pytest.approx(1500.0)]
    assert window.itl_ms([crossing], 0.0, 10.0) == []


def test_end_to_end_leaves_out_what_has_no_sample():
    r = rec(0.0, 0.0, [30.0, 31.0])
    out = window.end_to_end([r], 10.0, 20.0)
    assert out["ttft_p50_ms"] is None and out["tpot_p50_ms"] is None
    assert out["out_tokens_per_s"] == 0.0


def test_rate_is_over_the_whole_window():
    r = rec(0.0, 0.0, [10.0 + 0.1 * k for k in range(50)])
    assert window.end_to_end([r], 10.0, 20.0)["out_tokens_per_s"] == 5.0


def test_statistics():
    assert percentile([], 95) is None and median([]) is None
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(list(range(101)), 95) == 95
    assert spread([10, 10, 10, 10]) == 0
    # statistics.quantiles(n=4) on 1..7: q1 = 2, q3 = 6, median 4
    assert spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)
