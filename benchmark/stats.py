"""The arithmetic of the end-to-end metrics, and of a bound's spread."""

from __future__ import annotations

import math
import statistics


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p`` per
    cent of the sample at or below it.  ``values`` need not be sorted."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of an empty sample")
    return s[max(0, min(len(s) - 1, math.ceil(p / 100.0 * len(s)) - 1))]


def rate(count: int, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than nought")
    return count / seconds


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` as the contract has it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def stat(values, name: str) -> float:
    if name == "mean":
        return sum(values) / len(values)
    if name == "median":
        return statistics.median(values)
    if name.startswith("p"):
        return percentile(values, float(name[1:]))
    raise ValueError(f"unknown statistic {name!r}")
