"""Order statistics, one definition for every metric and every test."""

import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile (the rule of loadgen/report.percentile,
    copied so that the yardstick does not move with the program); None
    where there is nothing to rank: a metric that has no sample is left
    out of the line, never printed as 0."""
    if not values:
        return None
    s = sorted(values)
    idx = min(len(s) - 1, max(0, int(round(p / 100.0 * (len(s) - 1)))))
    return s[idx]


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def spread(values: Sequence[float]) -> Optional[float]:
    """The spread the builder's contract defines: the distance between
    the first and third quartile (statistics.quantiles, n=4) as a share
    of the median."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / abs(med) if med else None
