"""Percentiles as the benchmark reports them (nearest rank, no
interpolation: the value is one that a request really had)."""

import math


def percentile(values, q):
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]
