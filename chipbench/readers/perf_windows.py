"""The decode windows of ``GET /debug/perf`` dispatched in the measured
window: a reduction of one ``field``, or (``ratio``) 1000 x the sum of
one field over the sum of another (window seconds over steps: host
milliseconds per decode step)."""

from _common import in_window, reduce_values


def read(run, field: str = None, reduction: str = "p50", ratio=None):
    rows = [w for w in run["perf_close"]["windows"]
            if in_window(run, w["at_unix"])]
    if not rows:
        return None
    if ratio:
        den = sum(w[ratio[1]] for w in rows)
        return 1e3 * sum(w[ratio[0]] for w in rows) / den if den else None
    return reduce_values([w[field] for w in rows], reduction)
