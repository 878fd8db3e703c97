"""Percentile and spread arithmetic (one definition for every metric)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default, written out so that the yardstick
    does not change with a library).  Empty input returns None."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * (float(q) / 100.0)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50.0)


def iqr_share(values):
    """Distance between the first and third quartile as a share of the
    median, with the quartiles as ``statistics.quantiles(n=4)`` gives
    them (the rule the bounds are set by)."""
    xs = [float(v) for v in values]
    if len(xs) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(xs, n=4)
    med = statistics.median(xs)
    return (q3 - q1) / abs(med) if med else float("inf")
