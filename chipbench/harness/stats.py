"""Percentiles over samples that may hold misses (+inf)."""
import math


def percentile(values, q: float) -> float:
    """Linear-interpolated ``q``-th percentile (numpy's default method);
    +inf where an order statistic it needs is a miss."""
    v = sorted(values)
    if not v:
        return float("nan")
    pos = q / 100.0 * (len(v) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(v[hi]):
        return float("inf")
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
