"""The ``GET /load`` samples taken once a second inside the window: a
percentile of 100 x ``num`` / ``den`` (dotted paths)."""

from _common import dig, reduce_values


def read(run, num: str, den: str, reduction: str = "p50"):
    values = []
    for s in run.get("load_samples") or []:
        n, d = dig(s, num), dig(s, den)
        if n is not None and d:
            values.append(100.0 * n / d)
    return reduce_values(values, reduction)
