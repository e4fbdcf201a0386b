"""Counters of ``GET /debug/perf``: what the sum of ``paths`` moved by
between the window's open and close OVER what the sum of ``per`` moved
by (a quantity a unit of the other: bytes a step); None where either
did not move or is not there."""

from perf_delta import _moved


def read(run, paths, per):
    num, den = _moved(run, paths), _moved(run, per)
    return num / den if num is not None and den else None
