"""Compilations that started inside the measured window (``GET
/debug/perf`` ``compiles``, by wall-clock time): there should be none."""

from _common import in_window


def read(run):
    return float(sum(1 for c in run["perf_close"]["compiles"]
                     if in_window(run, c["at_unix"])))
