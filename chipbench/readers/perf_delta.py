"""Counters of ``GET /debug/perf``: what the sum of ``paths`` moved by
between the window's open and close; with ``over``, 100 x that over
what the sum of ``over`` moved by."""

from _common import dig


def _moved(run, paths):
    vals = [(dig(run["perf_open"], p), dig(run["perf_close"], p))
            for p in paths]
    if any(a is None or b is None for a, b in vals):
        return None
    return float(sum(b - a for a, b in vals))


def read(run, paths, over=None):
    num = _moved(run, paths)
    if over is None or num is None:
        return num
    den = _moved(run, over)
    return 100.0 * num / den if den else None
