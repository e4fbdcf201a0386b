"""Helpers the readers share. A reader is ``read(run, **args)``: it takes
its metric from the run's record (client records, ``/debug/perf``
snapshots, ``/debug/traces`` rows, ``/load`` samples, the reduced device
trace) and returns a number, or None when there is nothing to read (the
harness then leaves the metric out of the line)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.stats import median, percentile  # noqa: E402


def dig(obj, path: str):
    """``a.b.c`` into nested dicts; None where a key is missing."""
    for key in path.split("."):
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    return obj


def reduce_values(values, reduction: str):
    if not values:
        return None
    if reduction == "sum":
        return float(sum(values))
    if reduction == "count":
        return float(len(values))
    if reduction == "p50":      # the median the end-to-end metrics use
        return median(values)
    if reduction.startswith("p"):
        return percentile(values, float(reduction[1:]))
    raise ValueError(f"unknown reduction {reduction!r}")


def in_window(run, unix: float) -> bool:
    return run["window"]["t0_unix"] <= unix < run["window"]["t1_unix"]
