"""The decode windows of ``GET /debug/perf`` dispatched in the measured
window: 1000 x the sum of field ``num`` over the sum of field ``den``
(seconds over steps: milliseconds per decode step). ``perf_windows``
with ``ratio`` reads the same of fields every program writes; this one
reads as nothing where a window lacks either field, as the windows of
a program older than the field do."""

from _common import in_window


def read(run, num: str, den: str):
    rows = [w for w in run["perf_close"]["windows"]
            if in_window(run, w["at_unix"])]
    if not rows or any(num not in w or den not in w for w in rows):
        return None
    total = sum(w[den] for w in rows)
    return 1e3 * sum(w[num] for w in rows) / total if total else None
