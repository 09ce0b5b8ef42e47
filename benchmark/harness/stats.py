"""Order statistics with their sample counts."""

import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) of ``values`` by linear
    interpolation between closest ranks (numpy's default), or None for an
    empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values):
    return percentile(values, 50)


def summary(values, qs=(50, 90, 95, 99)):
    """{"n", "p<q>"..., "max"} of one sample; the count says how many
    values lie beyond each tail that is reported."""
    xs = list(values)
    if not xs:
        return {"n": 0}
    return {"n": len(xs), **{f"p{q}": percentile(xs, q) for q in qs},
            "max": max(xs)}
