"""A percentile of what the clients recorded in the window:
``series`` is lag_ms | itl_ms | ttft_ms | tpot_ms."""

from _common import reduce_values

from chipbench import window


def read(run, series: str, reduction: str):
    w = run["window"]
    values = getattr(window, series)(run["records"], w["t0"], w["t1"])
    return reduce_values(values, reduction)
